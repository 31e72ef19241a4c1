"""kernels, whole program (latent-attention sparse-expert serve): the least
time the chip could take for the traced slice's steps / the device time of
its segment programs.

Every step (a decode tick over all slots or one admission) must stream the
weights outside the routed experts once, and of the routed experts those
that received a token (the program's ``experts_hit``, summed over the
slice's steps and layers: NOT all that are held); a decode tick also reads
the cached rows its live slots attend to (the run's mean per decode step,
1,152 bytes a row a layer). All of it over the HBM peak. An admission's
operations (its true prompt length, not the admit width) take less than
its bytes at these sizes, so they are not counted. Bound: memory. This is
the share of the whole step that every later claim in the cell is bounded
by.
"""

from chipbench import flops_latent_moe as flops, trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    sl, chip = record.get("slice"), record.get("chip")
    counts = record.get("slice_counters")
    if record.get("kind") != "serve_latent_moe" or not sl or not chip \
            or not counts:
        return None
    secs = trace_reduce.module_seconds(
        record.get("trace"), record["config"]["serve"]["segment_modules"])
    if not secs:
        return None
    decode = sl["steps"] - sl["admits"]
    need = flops.step_bytes(
        record["config"], sl["steps"], counts.get("experts_hit", 0),
        decode * record["kv_rows_per_decode_step"])
    return need / chip["hbm_bytes_s"] / secs * 100.0
