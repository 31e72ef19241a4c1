"""kernels, whole program (power-retention serve): the least time the chip
could take for the traced slice's steps / the device time of its segment
programs.

A decode tick must stream the weights once and read and write the state
pages of its live slots (the program's ``state_pages``, summed over the
slice's ticks; a page counted at the minimal expansion's width whatever the
program pads to); an admission takes the larger of its operations (its true
prompt rows, the program's ``admit_rows_used``) over the bf16 peak and the
weight stream. Bound: memory for the ticks, compute for the admissions.
This is the share of the whole step that every later claim in the cell is
bounded by.
"""

from chipbench import flops_power_retention as flops, trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    sl, chip = record.get("slice"), record.get("chip")
    counts = record.get("slice_counters")
    if record.get("kind") != "serve_retention" or not sl or not chip \
            or not counts or "state_pages" not in counts:
        return None
    secs = trace_reduce.module_seconds(
        record.get("trace"), record["config"]["serve"]["segment_modules"])
    if not secs:
        return None
    least = flops.slice_floor_s(
        record["config"], chip, sl["steps"], sl["admits"],
        counts["state_pages"], counts.get("admit_rows_used", 0))
    return least / secs * 100.0
