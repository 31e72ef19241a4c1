"""model step: device time under the ``attention_window`` and
``attention_full`` scopes (``jax.named_scope`` in ``models/hybrid_moe.py``:
the paged kernel over the fixed parts and over the row pages in a tick, the
windowed prefill kernel in an admission, and what XLA puts around them) in
the traced slice / the steps of the segment loop that ran in it."""

META = {"layer": "model step", "unit": "ms", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
SCOPES = ("attention_window", "attention_full")


def compute(record):
    sl, scopes = record.get("slice"), record.get("scopes")
    if not sl or not sl.get("steps") or not scopes:
        return None
    found = [s for path, s in scopes.items()
             if any(part in SCOPES for part in path.split("/"))]
    if not found:
        return None
    return sum(found) / sl["steps"] * 1e3
