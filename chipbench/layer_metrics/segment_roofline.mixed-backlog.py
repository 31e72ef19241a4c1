"""kernels, whole program (window / full attention sparse-expert serve): the
least time the chip could take for the traced slice's steps / the device
time of its segment programs.

A decode tick must stream the weights outside the routed experts once, of
the routed experts those that received a token (the program's
``experts_hit``: NOT all that are held) and the cached rows its live slots
attend — ``rows_full`` in the full layers, ``rows_window`` (at most the
window a slot a layer, whatever the position) in the window layers, 4,096
bytes a row — all over the HBM peak. An admission takes the larger of its
operations (its true prompt rows, the program's ``admit_rows_used``; window
layers counted at their window) over the bf16 peak and its weight stream.
Bound: memory for the ticks, compute for the admissions. This is the share
of the whole step that every later claim in the cell is bounded by.
"""

from chipbench import flops_hybrid_moe as flops, trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    sl, chip = record.get("slice"), record.get("chip")
    counts = record.get("slice_counters")
    if record.get("kind") != "serve_hybrid_moe" or not sl or not chip \
            or not counts or "rows_full" not in counts:
        return None
    secs = trace_reduce.module_seconds(
        record.get("trace"), record["config"]["serve"]["segment_modules"])
    if not secs:
        return None
    least = flops.slice_floor_s(
        record["config"], chip, sl["steps"], sl["admits"],
        counts.get("experts_hit", 0),
        counts["rows_full"] + counts.get("rows_window", 0),
        counts.get("admit_rows_used", 0))
    return least / secs * 100.0
