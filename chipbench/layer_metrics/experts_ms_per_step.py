"""model step: device time under the ``experts`` and ``router`` scopes
(``jax.named_scope`` in ``models/latent_moe.py``: the routing, the sort by
held expert, both grouped matmuls and the combine) in the traced slice /
the steps of the segment loop that ran in it."""

META = {"layer": "model step", "unit": "ms", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
SCOPES = ("experts", "router")


def compute(record):
    sl, scopes = record.get("slice"), record.get("scopes")
    if not sl or not sl.get("steps") or not scopes:
        return None
    found = [s for path, s in scopes.items()
             if any(part in SCOPES for part in path.split("/"))]
    if not found:
        return None
    return sum(found) / sl["steps"] * 1e3
