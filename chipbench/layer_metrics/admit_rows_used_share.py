"""engine: prompt rows / bucket rows of the admissions, over the span
``serve_tokens_per_s`` is taken over (``serving.retention.admit_rows_used``
/ ``.admit_rows``, counted in the program and fetched with the tokens):
what of an admission's positions (the largest bucket's, 1,024) is the
prompt and not padding."""

META = {"layer": "engine", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "program_counter"}


def compute(record):
    counts = record.get("saturated_counters")
    if not counts or not counts.get("admit_rows"):
        return None
    return counts.get("admit_rows_used", 0) / counts["admit_rows"] * 100.0
