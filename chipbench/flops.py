"""Operations and bytes the algorithm needs, from the model's sizes alone.

``model`` is the ``model`` object of a configuration file (the public
config.json's keys). Everything here is what the work REQUIRES: recomputed
operations, padding and copies the program happens to make are not counted,
so a share of a peak built on these numbers cannot pass 100 %.
"""


def sizes(model: dict) -> dict:
    h = model["hidden_size"]
    heads = model["num_attention_heads"]
    return {
        "H": h, "F": model["intermediate_size"], "V": model["vocab_size"],
        "L": model["num_hidden_layers"], "heads": heads,
        "kv_heads": model["num_key_value_heads"], "D": h // heads,
    }


def param_counts(model: dict) -> dict:
    s = sizes(model)
    kv = s["kv_heads"] * s["D"]
    layer = 2 * s["H"] * s["H"] + 2 * s["H"] * kv + 3 * s["H"] * s["F"]
    return {
        "layer_matmul": layer,
        "matmul": s["L"] * layer + s["H"] * s["V"],  # head included
        "embed": s["V"] * s["H"],
        "norms": (2 * s["L"] + 1) * s["H"],
        "total": s["L"] * layer + 2 * s["H"] * s["V"]
        + (2 * s["L"] + 1) * s["H"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward of next-token training at sequence length
    ``seq``: 6 per matmul parameter (head included, embedding lookup not),
    plus causal attention: QK^T and PV are 2 * 2 * D * heads operations per
    (query, key) pair, half the pairs are masked, backward costs twice the
    forward -> 3 * 2 * D * heads * seq per token per layer. No recompute."""
    s = sizes(model)
    attn = 6.0 * s["D"] * s["heads"] * seq * s["L"]
    return 6.0 * param_counts(model)["matmul"] + attn


def prefill_flops(model: dict, tokens: int) -> float:
    """Forward of ``tokens`` prompt positions (causal), head on the last
    position only."""
    s = sizes(model)
    p = param_counts(model)
    body = 2.0 * s["L"] * p["layer_matmul"] * tokens
    attn = 2.0 * s["D"] * s["heads"] * tokens * tokens * s["L"]
    return body + attn + 2.0 * s["H"] * s["V"]


def weight_stream_bytes(model: dict, itemsize: int = 2) -> float:
    """Bytes of weights one forward pass must read once: every layer, the
    final norm and the head; of the embedding only the rows looked up,
    which is nothing beside the rest."""
    p = param_counts(model)
    return float(p["matmul"] + p["norms"]) * itemsize


def kv_bytes_per_row(model: dict, itemsize: int = 2) -> float:
    s = sizes(model)
    return 2.0 * s["L"] * s["kv_heads"] * s["D"] * itemsize


def decode_tick_bytes(model: dict, kv_rows: float, itemsize: int = 2) -> float:
    """Bytes one decode tick must stream: the weights once, plus the
    ``kv_rows`` cached rows (summed over the live slots) it attends to."""
    return weight_stream_bytes(model, itemsize) \
        + kv_rows * kv_bytes_per_row(model, itemsize)
