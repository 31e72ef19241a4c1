"""engine: held experts that received at least one token, summed over the
expert layers, per step of the segment loop, over the span
``serve_tokens_per_s`` is taken over (``serving.moe.experts_hit``, counted
in the program and fetched with the tokens). Of layers x held experts (64 =
4 x 16 in ``openpangu-ultra-moe-l5-ep16``): what a step streams of the
routed experts' weights."""

META = {"layer": "engine", "unit": "experts", "moves": "serve_tokens_per_s",
        "source": "program_counter"}


def compute(record):
    counts = record.get("saturated_counters")
    if not counts or not counts.get("steps") or "experts_hit" not in counts:
        return None
    return counts["experts_hit"] / counts["steps"]
