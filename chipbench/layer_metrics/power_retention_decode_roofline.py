"""kernels: the ``power_retention_decode`` kernel's share of its roofline in
the traced slice, all layers: the state pages it updated (the program's
``state_pages``: live slots, summed over the slice's ticks) x the layers x
one layer's page (8 kv heads x 8,256 x 129 float32 = 34.08 MB, the minimal
expansion) read once and written once, over the HBM peak / the kernel's
device time. Bound: memory."""

from chipbench import flops_power_retention as flops
from chipbench.layer_metrics.grouped_expert_matmul_roofline import \
    kernel_seconds

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
KERNEL = "power_retention_decode"


def compute(record):
    chip, counts = record.get("chip"), record.get("slice_counters")
    secs = kernel_seconds(record, KERNEL)
    if record.get("kind") != "serve_retention" or not chip or not counts \
            or not secs or not counts.get("state_pages"):
        return None
    need = flops.tick_state_bytes(record["config"], counts["state_pages"])
    return need / chip["hbm_bytes_s"] / secs * 100.0
