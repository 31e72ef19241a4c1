"""Operations and bytes the power-retention decoder NEEDS, from the
configuration's sizes alone (its file's top-level keys are the public
config.json's). As ``flops.py``: recomputed operations, padding and copies
the program happens to make are not counted, so a share of a peak built on
these numbers cannot pass 100 %.

The unit of the retention sublayer is ONE STATE PAGE of one layer: per kv
head a state ``S`` of ``D x d`` and a sum of keys ``z`` of ``D`` float32,
``D = d (d + 1) / 2`` the symmetric square of a ``d``-wide key (8,256 at
128: the MINIMAL exact expansion, whatever width the program pads to). A
decode tick reads a live slot's page once and writes it once.
"""

STATE_ITEMSIZE = 4      # the configuration states float32 state


def param_counts(c: dict) -> dict:
    """Parameters by part: one layer's projections, gate, FFN, norms; the
    embedding and the head."""
    h, d = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    projections = h * heads * d * 2 + h * kv * d * 2     # q, o; k, v
    gate = h * kv + kv
    ffn = 3 * h * c["intermediate_size"]
    norms = 2 * h + 2 * d
    layer = projections + gate + ffn + norms
    layers = c["num_hidden_layers"]
    embed = h * c["vocab_size"]
    return {"projections": projections, "gate": gate, "ffn": ffn,
            "layer": layer, "embed": embed, "head": embed,
            "total": layers * layer + 2 * embed + h}


def weight_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes a forward pass streams whatever it holds in its slots: every
    layer, the head and the final norm (the embedding's rows looked up are
    nothing beside it); the gate is float32."""
    p = param_counts(c)
    layers = c["num_hidden_layers"]
    return float(layers * p["layer"] + p["head"] + c["hidden_size"]) \
        * itemsize + layers * p["gate"] * (4 - itemsize)


def state_width(c: dict) -> int:
    """Entries of the minimal exact expansion of a squared dot product."""
    d = c["head_dim"]
    return d * (d + 1) // 2


def state_page_bytes(c: dict) -> float:
    """Bytes of ONE layer's state of one sequence: S and z of every kv
    head."""
    return float(c["num_key_value_heads"] * state_width(c)
                 * (c["head_dim"] + 1) * STATE_ITEMSIZE)


def tick_state_bytes(c: dict, pages: float) -> float:
    """Bytes the ticks' state updates move: ``pages`` (live slots, summed
    over the ticks) pages, every layer's, read once and written once."""
    return pages * c["num_hidden_layers"] * 2.0 * state_page_bytes(c)


def admission_ops(c: dict, rows: float, rows_squared: float) -> float:
    """Operations of admissions that hold ``rows`` prompt rows in all
    (``rows_squared``: the sum of their squares): every matmul parameter
    twice a row (the head once an admission is nothing), and the retention
    in its cheapest exact form, the attention form: per causal (query, key)
    pair of a query head ``2 d`` for the weight and ``2 d`` for the sum."""
    p = param_counts(c)
    per_row = 2.0 * (p["projections"] + p["gate"] + p["ffn"])
    pairs = rows_squared / 2.0
    retention = 4.0 * c["head_dim"] * c["num_attention_heads"] * pairs
    return c["num_hidden_layers"] * (per_row * rows + retention)


def slice_floor_s(c: dict, chip: dict, steps: int, admits: int,
                  pages: float, admit_rows: float) -> float:
    """Least time of a slice of ``steps`` loop steps: each decode tick the
    weight stream and its live slots' state pages (``pages``, summed) over
    the HBM peak; each of the ``admits`` admissions the larger of its
    operations over the bf16 peak and the weight stream (``admit_rows``:
    their prompt rows in all; the squares are taken at the mean, which is
    the least a sum of squares can be)."""
    ticks = steps - admits
    w = weight_bytes(c)
    floor = (ticks * w + tick_state_bytes(c, pages)) / chip["hbm_bytes_s"]
    if admits:
        mean = admit_rows / admits
        ops = admission_ops(c, mean, mean * mean)
        floor += admits * max(ops / chip["bf16_flops_s"],
                              w / chip["hbm_bytes_s"])
    return floor
