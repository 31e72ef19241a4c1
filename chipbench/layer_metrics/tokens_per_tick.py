"""engine: generated tokens per step of the segment loop, over the span
``serve_tokens_per_s`` is taken over (the run's ``saturated`` record: the
segments between the slots' filling and the window's end). A step is one
decode tick over all slots or one request's admission, which yields one
token."""

META = {"layer": "engine", "unit": "tokens", "moves": "serve_tokens_per_s",
        "source": "program_counter"}


def compute(record):
    sat = record.get("saturated")
    if not sat or not sat["steps"]:
        return None
    return sat["tokens"] / sat["steps"]
