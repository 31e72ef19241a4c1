"""Operations and bytes the latent-attention sparse-expert decoder NEEDS,
from the configuration's sizes alone (its file's top-level keys are the
public config.json's). As ``flops.py``: recomputed operations, padding and
copies the program happens to make are not counted, so a share of a peak
built on these numbers cannot pass 100 %.

The unit of the expert layer is ONE held expert that received a token: a
step streams the weights outside the routed experts once, and of the routed
experts only those the step's tokens picked (the program counts them:
``experts_hit``), never all that are held.
"""


def param_counts(c: dict) -> dict:
    """Parameters by part: one layer's attention, shared expert, router,
    one routed expert; a dense layer; the held embedding and head."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    attention = (h * rq + rq * heads * (dn + dr) + h * (rkv + dr)
                 + rkv * heads * (dn + dv) + heads * dv * h)
    norms = 4 * h + rq + rkv
    expert = 3 * h * c["moe_intermediate_size"]
    shared = c["n_shared_experts"] * expert
    router = h * c["share"]["router_width"]
    dense_ffn = 3 * h * c["intermediate_size"]
    n_dense = c["first_k_dense_replace"]
    n_expert = c["num_hidden_layers"] - n_dense
    outside = (n_dense * (attention + norms + dense_ffn)
               + n_expert * (attention + norms + shared + router)
               + h * c["vocab_size"] + h)       # head and final norm
    return {
        "attention": attention, "expert": expert, "shared": shared,
        "router": router, "dense_layer": attention + norms + dense_ffn,
        "expert_layer_outside": attention + norms + shared + router,
        "embed": h * c["vocab_size"],
        # what every step reads whatever it routes (the embedding's rows
        # looked up are nothing beside it)
        "outside_experts": outside,
        "held_experts": n_expert * c["n_routed_experts"] * expert,
        "total": outside + h * c["vocab_size"]
        + n_expert * c["n_routed_experts"] * expert,
    }


def expert_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes of ONE routed expert's three matrices."""
    return float(param_counts(c)["expert"]) * itemsize


def outside_expert_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes a forward pass reads whatever it routes; the router is
    float32."""
    p = param_counts(c)
    n_expert = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return float(p["outside_experts"]) * itemsize \
        + n_expert * p["router"] * (4 - itemsize)


def cache_row_bytes(c: dict, itemsize: int = 2) -> float:
    """One cached row of ONE layer: the latent and the one rotary key."""
    return float(c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize


def attention_ops_per_pair(c: dict) -> float:
    """Operations one (query token, cached row) pair costs in one layer,
    absorbed form: every head's score over [latent | rope] and its value
    sum over the latent."""
    r, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2.0 * c["num_attention_heads"] * ((r + dr) + r)


def attention_floor_s(c: dict, chip: dict, decode_rows: float,
                      admit_pairs: float, admit_rows: float) -> float:
    """Least time of the latent attention of ALL layers for ``decode_rows``
    cached rows read by single queries (each row once a query: ops and
    bytes side by side, the larger decides), and admissions of
    ``admit_pairs`` causal (query, row) pairs over ``admit_rows`` rows."""
    layers = c["num_hidden_layers"]
    ops, row = attention_ops_per_pair(c), cache_row_bytes(c)
    decode = decode_rows * max(ops / chip["bf16_flops_s"],
                               row / chip["hbm_bytes_s"])
    admit = max(admit_pairs * ops / chip["bf16_flops_s"],
                admit_rows * row / chip["hbm_bytes_s"])
    return layers * (decode + admit)


def step_bytes(c: dict, steps: float, experts_hit: float,
               decode_rows: float) -> float:
    """Bytes ``steps`` steps must stream: the weights outside the routed
    experts once a step, the routed experts that received a token
    (``experts_hit``, summed over steps and layers), and the cached rows
    the decode ticks attend to (``decode_rows``, summed over slots and
    steps; every layer has a row)."""
    return steps * outside_expert_bytes(c) \
        + experts_hit * expert_bytes(c) \
        + decode_rows * c["num_hidden_layers"] * cache_row_bytes(c)
