"""kernels: the ``prefill_attention_window`` kernel's share of its roofline
in the traced slice (``windowed_prefill_attention`` as the admissions of the
sliding_attention layers): ``flops_hybrid_moe.admit_attention_floor_s`` of the slice's
admissions at their true prompt rows (``admit_rows_used``) / the kernel's
device time. The kernel also computes the bucket's padding rows: not needed,
not counted."""

from chipbench import flops_hybrid_moe as flops
from chipbench.layer_metrics.grouped_expert_matmul_roofline import \
    kernel_seconds

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    sl, chip = record.get("slice"), record.get("chip")
    counts = record.get("slice_counters")
    secs = kernel_seconds(record, "prefill_attention_window")
    if record.get("kind") != "serve_hybrid_moe" or not sl or not chip \
            or not counts or not secs or not sl.get("admits") \
            or not counts.get("admit_rows_used"):
        return None
    return flops.admit_attention_floor_s(
        record["config"], chip, sl["admits"], counts["admit_rows_used"],
        "sliding_attention") / secs * 100.0
