"""kernels: the ``grouped_expert_matmul`` kernel's share of its roofline in
the traced slice of the window / full cell, where one kernel runs in two
regimes: a tick's call streams the experts that were hit for 4 rows each
(memory), an admission's multiplies ~256 rows an expert (as many operations
as bytes). The least time of the slice's calls is the larger of the bytes of
the held experts that RECEIVED a token (``experts_hit`` x one expert's three
matrices) over the HBM peak and the operations of the picks that landed on a
held expert (``picks_held`` x one token through one expert) over the bf16
peak — each summed over the slice, which is no more than the calls' own
larger-ofs added up. Over the kernel's device time (both calls a layer)."""

from chipbench import flops_hybrid_moe as flops
from chipbench.layer_metrics.grouped_expert_matmul_roofline import \
    kernel_seconds

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    chip, counts = record.get("chip"), record.get("slice_counters")
    secs = kernel_seconds(record, "grouped_expert_matmul")
    if record.get("kind") != "serve_hybrid_moe" or not chip or not counts \
            or not secs:
        return None
    c = record["config"]
    least = max(
        counts.get("experts_hit", 0) * flops.expert_bytes(c)
        / chip["hbm_bytes_s"],
        counts.get("picks_held", 0) * flops.expert_ops_per_pick(c)
        / chip["bf16_flops_s"])
    return least / secs * 100.0
