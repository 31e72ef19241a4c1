"""kernels, whole program (train): operations the step requires (6 per
matmul parameter, head included, plus causal attention; no recompute)
x tokens / device time of the traced steps / the chip's bf16 peak."""

from chipbench import flops, trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "train_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    sl, chip = record.get("slice"), record.get("chip")
    if record.get("kind") != "train" or not sl or not sl.get("steps") \
            or not chip:
        return None
    secs = trace_reduce.module_seconds(
        record.get("trace"), record["workload"]["step_modules"])
    if not secs:
        return None
    t = record["train"]
    need = flops.train_flops_per_token(record["config"]["model"], t["seq"]) \
        * t["tokens"] * sl["steps"]
    return need / secs / chip["bf16_flops_s"] * 100.0
