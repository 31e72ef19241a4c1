"""kernels, whole program (serve): the least time the chip could take for
the traced slice's work / the device time of its segment programs.

The least time of a decode step is its bytes over the HBM peak: the weights
once (every layer, the final norm and the head, in bf16) plus the cached
rows the live slots attend to (the run's mean per decode step). The least
time of an admission is the larger of its prefill's operations over the
bf16 peak (at the prompt lengths served, their mean square from the
requests: padding to the admit width is the program's choice and is not
counted) and the weights' bytes over the HBM peak. Bound: memory (decode).
"""

from chipbench import flops, trace_reduce

META = {"layer": "kernels", "unit": "%", "moves": "tpot_mean_ms",
        "source": "device_trace"}


def compute(record):
    sl, chip = record.get("slice"), record.get("chip")
    if record.get("kind") != "serve" or not sl or not chip:
        return None
    secs = trace_reduce.module_seconds(
        record.get("trace"), record["config"]["serve"]["segment_modules"])
    if not secs:
        return None
    model = record["config"]["model"]
    per = record["report"]["per_request"]
    decode = sl["steps"] - sl["admits"]
    tick_s = flops.decode_tick_bytes(
        model, record["kv_rows_per_decode_step"]) / chip["hbm_bytes_s"]
    weights_s = flops.weight_stream_bytes(model) / chip["hbm_bytes_s"]
    prefill = sum(flops.prefill_flops(model, r["prompt_len"])
                  for r in per) / len(per)
    admit_s = max(prefill / chip["bf16_flops_s"], weights_s)
    return (decode * tick_s + sl["admits"] * admit_s) / secs * 100.0
