"""model step (train): device time of the train-step program in the traced
chain / the steps traced."""

from chipbench import trace_reduce

META = {"layer": "model step", "unit": "ms", "moves": "train_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    sl = record.get("slice")
    if record.get("kind") != "train" or not sl or not sl.get("steps"):
        return None
    secs = trace_reduce.module_seconds(
        record.get("trace"), record["workload"]["step_modules"])
    if secs is None:
        return None
    return secs / sl["steps"] * 1e3
