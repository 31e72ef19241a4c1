"""Operations and bytes the window / full attention sparse-expert decoder
NEEDS, from the configuration's sizes alone (its file's top-level keys are
the public config.json's, ``share`` says what of the router and of the
vocabulary is held). As ``flops.py``: recomputed operations, padding and
copies the program happens to make are not counted, so a share of a peak
built on these numbers cannot pass 100 %.

The unit of the expert layer is ONE held expert that received a token
(``flops_latent_moe``'s rule): a step streams the weights outside the routed
experts once and of the routed experts only those its tokens picked. The
unit of the cache is ONE ROW of one layer, 2 x kv heads x head_dim values: a
tick's full layers read a row a position of a live slot, its window layers
at most ``sliding_window`` rows a live slot whatever the position.
"""

WINDOW = "sliding_attention"


def kinds(c: dict) -> list:
    """The served layers' kinds: the first ``num_hidden_layers`` of the
    published list."""
    return list(c["layer_types"][:c["num_hidden_layers"]])


def param_counts(c: dict) -> dict:
    """Parameters by part: one layer's attention, shared expert, router,
    one routed expert; a dense layer; the held embedding and head."""
    h, d = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    attention = 2 * h * heads * d + 2 * h * kv * d        # q, o; k, v
    norms = 2 * h + 2 * d
    expert = 3 * h * c["moe_intermediate_size"]
    shared = c["num_shared_experts"] * expert
    router = h * c["share"]["router_width"]
    dense_ffn = 3 * h * c["intermediate_size"]
    n_dense = c["first_k_dense_replace"]
    n_sparse = c["num_hidden_layers"] - n_dense
    embed = h * c["vocab_size"]
    outside = (n_dense * (attention + norms + dense_ffn)
               + n_sparse * (attention + norms + shared + router)
               + embed + h)                     # head and final norm
    return {
        "attention": attention, "expert": expert, "shared": shared,
        "router": router, "dense_layer": attention + norms + dense_ffn,
        "sparse_layer_outside": attention + norms + shared + router,
        "embed": embed,
        # what every step reads whatever it routes (the embedding's rows
        # looked up are nothing beside it)
        "outside_experts": outside,
        "held_experts": n_sparse * c["num_experts"] * expert,
        "total": outside + embed + n_sparse * c["num_experts"] * expert,
    }


def weight_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes of the share as it is held; the routers are float32."""
    p = param_counts(c)
    n_sparse = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return float(p["total"]) * itemsize + n_sparse * p["router"] \
        * (4 - itemsize)


def expert_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes of ONE routed expert's three matrices."""
    return float(param_counts(c)["expert"]) * itemsize


def outside_expert_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes a forward pass reads whatever it routes."""
    p = param_counts(c)
    n_sparse = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return float(p["outside_experts"]) * itemsize \
        + n_sparse * p["router"] * (4 - itemsize)


def cache_row_bytes(c: dict, itemsize: int = 2) -> float:
    """One cached row of ONE layer: K and V of every kv head."""
    return 2.0 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def page_bytes(c: dict, page_size: int, itemsize: int = 2) -> float:
    """A row page across the full layers."""
    return kinds(c).count("full_attention") * page_size \
        * cache_row_bytes(c, itemsize)


def fixed_part_bytes(c: dict, itemsize: int = 2) -> float:
    """A sequence's fixed part across the window layers."""
    return kinds(c).count(WINDOW) * c["sliding_window"] \
        * cache_row_bytes(c, itemsize)


def attention_ops_per_pair(c: dict) -> float:
    """Operations one (query token, key row) pair costs in one layer:
    every query head's score and its value sum."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


def expert_ops_per_pick(c: dict) -> float:
    """Operations of one token through one routed expert."""
    return 2.0 * param_counts(c)["expert"]


def admit_pairs(c: dict, rows: float, kind: str) -> float:
    """(query, key) pairs an admission of ``rows`` rows attends in one
    layer of ``kind``: the lower triangle, cut at the window."""
    w = c["sliding_window"]
    if kind == WINDOW and rows > w:
        return w * (w + 1) / 2.0 + (rows - w) * w
    return rows * (rows + 1) / 2.0


def tick_attention_floor_s(c: dict, chip: dict, rows: float) -> float:
    """Least time of ticks' attention over ``rows`` cached rows (summed
    over slots, steps and layers): a query reads each row once, 32,768
    operations on 4,096 bytes, 8 to the byte against the chip's 240:
    memory."""
    return rows * cache_row_bytes(c) / chip["hbm_bytes_s"]


def admit_attention_floor_s(c: dict, chip: dict, admits: float, rows: float,
                            kind: str) -> float:
    """Least time of the ``kind`` layers' attention in ``admits``
    admissions of ``rows`` prompt rows in all (taken at their mean: the
    least a sum of squares can be): an admission's rows are read once for
    all its queries, so the larger of its in-mask pairs' operations over
    the bf16 peak and its rows' bytes over the HBM peak, every layer of
    the kind."""
    mean = rows / admits
    return admits * kinds(c).count(kind) * max(
        admit_pairs(c, mean, kind) * attention_ops_per_pair(c)
        / chip["bf16_flops_s"],
        mean * cache_row_bytes(c) / chip["hbm_bytes_s"])


def admission_ops(c: dict, rows: float) -> float:
    """Operations of one admission of ``rows`` prompt rows: every matmul
    parameter outside the routed experts twice a row (the head once an
    admission is nothing), ``num_experts_per_tok`` x the share held of the
    router's experts routed experts a row a sparse layer, and attention
    over the in-mask pairs of each layer's kind."""
    p = param_counts(c)
    n_dense = c["first_k_dense_replace"]
    n_sparse = c["num_hidden_layers"] - n_dense
    per_row = 2.0 * (n_dense * p["dense_layer"]
                     + n_sparse * p["sparse_layer_outside"])
    held = c["num_experts"] / c["share"]["router_width"]
    per_row += n_sparse * c["num_experts_per_tok"] * held \
        * expert_ops_per_pick(c)
    attention = sum(admit_pairs(c, rows, k) for k in kinds(c)) \
        * attention_ops_per_pair(c)
    return per_row * rows + attention


def slice_floor_s(c: dict, chip: dict, steps: int, admits: int,
                  experts_hit: float, tick_rows: float,
                  admit_rows: float) -> float:
    """Least time of a slice of ``steps`` loop steps. A decode tick streams
    the weights outside the routed experts, the experts HIT and the rows
    its attention reads (``tick_rows``: ``rows_full + rows_window`` over
    the slice) over the HBM peak. Each of the ``admits`` admissions takes
    the larger of its operations (``admit_rows`` prompt rows in all, taken
    at their mean: the least a sum of squares can be; window layers at
    their window) over the bf16 peak and its weight stream (every held
    expert is hit by thousands of picks). ``experts_hit`` is the slice's
    sum, the admissions' among them."""
    ticks = steps - admits
    n_sparse = c["num_hidden_layers"] - c["first_k_dense_replace"]
    hit_admit = min(experts_hit, admits * n_sparse * c["num_experts"])
    floor = (ticks * outside_expert_bytes(c)
             + (experts_hit - hit_admit) * expert_bytes(c)
             + tick_rows * cache_row_bytes(c)) / chip["hbm_bytes_s"]
    if admits:
        stream = outside_expert_bytes(c) \
            + hit_admit / admits * expert_bytes(c)
        floor += admits * max(
            admission_ops(c, admit_rows / admits) / chip["bf16_flops_s"],
            stream / chip["hbm_bytes_s"])
    return floor
