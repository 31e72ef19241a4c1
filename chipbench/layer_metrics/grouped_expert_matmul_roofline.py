"""kernels: the ``grouped_expert_matmul`` kernel's share of its roofline in
the traced slice: the bytes of the held experts that RECEIVED a token (the
program's ``experts_hit`` over the slice's steps and layers x one expert's
three matrices) over the HBM peak / the kernel's device time (both calls a
layer: gate-up and down). The rows it multiplies are nothing beside the
weights and are not counted. Bound: memory."""

from chipbench import flops_latent_moe as flops

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
KERNEL = "grouped_expert_matmul"


def kernel_seconds(record, kernel):
    """Device seconds of the ops named after ``kernel``, per plane."""
    trace = record.get("trace")
    if not trace:
        return None
    found = [row["seconds"] for name, row in trace["ops"].items()
             if name.split(".")[0] == kernel]
    return sum(found) / trace["planes"] if found else None


def compute(record):
    chip, counts = record.get("chip"), record.get("slice_counters")
    secs = kernel_seconds(record, KERNEL)
    if record.get("kind") != "serve_latent_moe" or not chip or not counts \
            or not secs:
        return None
    need = counts.get("experts_hit", 0) * flops.expert_bytes(record["config"])
    return need / chip["hbm_bytes_s"] / secs * 100.0
