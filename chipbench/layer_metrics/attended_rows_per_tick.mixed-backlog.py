"""engine: key rows a decode tick's attention has to read, summed over the
live slots and the layers (``serving.window.rows_full`` +
``.rows_window``, counted in the program and fetched with the tokens), per
decode step of the span ``serve_tokens_per_s`` is taken over: what the two
kinds of cache decide. A full layer reads a row a position, a window layer
min(position + 1, 128) whatever the position; an all-full cache of the same
traffic would read 5 x ``rows_full``."""

META = {"layer": "engine", "unit": "rows", "moves": "serve_tokens_per_s",
        "source": "program_counter"}


def compute(record):
    counts = record.get("saturated_counters")
    if not counts or "rows_full" not in counts or not counts.get("steps"):
        return None
    width = record["config"]["serve"]["engine"]["prompt_buckets"][-1]
    ticks = counts["steps"] - counts.get("admit_rows", 0) // width
    if ticks <= 0:
        return None
    return (counts["rows_full"] + counts.get("rows_window", 0)) / ticks
