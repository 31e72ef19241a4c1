"""model step (serve): device time of the segment programs in the traced
slice / the steps of the segment loop that ran in it. A step is a decode
tick over all slots or one admission's prefill (at the engine's pinned admit
width): both are in the program and both are in this number."""

from chipbench import trace_reduce

META = {"layer": "model step", "unit": "ms", "moves": "tpot_mean_ms",
        "source": "device_trace"}


def compute(record):
    sl = record.get("slice")
    if record.get("kind") != "serve" or not sl or not sl.get("steps"):
        return None
    secs = trace_reduce.module_seconds(
        record.get("trace"), record["config"]["serve"]["segment_modules"])
    if secs is None:
        return None
    return secs / sl["steps"] * 1e3
