"""kernels: the ``paged_attention_full`` kernel's share of its roofline
in the traced slice (``ragged_paged_attention`` as the ticks of the
full layers: the slot's row pages): the program's ``rows_full`` over the slice x 4,096 B over the HBM
peak (``flops_hybrid_moe.tick_attention_floor_s``: memory) / the kernel's
device time."""

from chipbench import flops_hybrid_moe as flops
from chipbench.layer_metrics.grouped_expert_matmul_roofline import \
    kernel_seconds

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    chip, counts = record.get("chip"), record.get("slice_counters")
    secs = kernel_seconds(record, "paged_attention_full")
    if record.get("kind") != "serve_hybrid_moe" or not chip or not counts \
            or not secs or "rows_full" not in counts:
        return None
    return flops.tick_attention_floor_s(
        record["config"], chip, counts["rows_full"]) / secs * 100.0
