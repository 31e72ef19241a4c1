"""model step: device time under the ``retention`` scope
(``jax.named_scope`` in ``models/power_retention.py``: a tick's decode
kernel with its ``z`` update, an admission's chunked scan) in the traced
slice / the steps of the segment loop that ran in it."""

META = {"layer": "model step", "unit": "ms", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
SCOPE = "retention"


def compute(record):
    sl, scopes = record.get("slice"), record.get("scopes")
    if not sl or not sl.get("steps") or not scopes:
        return None
    found = [s for path, s in scopes.items() if SCOPE in path.split("/")]
    if not found:
        return None
    return sum(found) / sl["steps"] * 1e3
