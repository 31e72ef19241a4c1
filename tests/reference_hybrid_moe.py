"""The plain reference of the window / full attention sparse-expert decoder
(``paddle_tpu/models/hybrid_moe.py``; K-EXAONE-236B-A23B's layer): the
equations in straightforward ``jax.numpy``, float32 at ``highest`` matmul
precision — no cache, no kernel, no batching, no sorting (a loop over the
held experts under a dense mask), and no code of ``paddle_tpu.models``. It
reads the program's parameter tree by its names, which is the one thing the
two share, and takes the same share (``held_experts``, the vocabulary slice
the tree holds). ``chipbench/reference_hybrid_moe.py`` is the benchmark's
copy (blocked and cast a layer at a time, for the published widths).

``m``: the model's sizes under the public config.json's keys
(``layer_types`` one kind a layer, ``sliding_window``).

    h = N1(x);  x = x + Attn_l(h);      h = N2(x);  x = x + FFN_l(h)
    Attn_l(h): q = Nq(h W_q) -> heads x d; k = Nk(h W_k), v = h W_v -> kv x d
               "sliding_attention": q, k = RoPE(q, k); key s is seen by
                   query t iff 0 <= t - s < sliding_window
               "full_attention": no rotary; key s is seen iff s <= t
               o = softmax(q k^T / sqrt(d)) v; concat(o) W_o
    FFN, a layer with ``w_gate``: SwiGLU(intermediate_size)
    FFN, a layer with ``router``: s = sigmoid(h_f32 W_g_f32); S = top-k(s)
               w_e = scale * s_e / (sum_{j in S} s_j + 1e-20)
               SwiGLU_shared(h) + sum_{e in S, e held} w_e SwiGLU_e(h)

DEPARTURES from the published description, each an item of the
configuration file's ``assumed`` (the config.json has no key for them):
pre-norm placement of N1 / N2; the per-head q / k norm; rotary on the
window layers ONLY; the router without its selection bias (a trained
buffer: zero here) and without group-limited selection (``n_group`` =
``topk_group`` = 1 make it a no-op); rotary pairs in the rotate-half
convention. What the experts absent from a share would add is left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x [S, heads, D] at positions 0..S-1; rotate-half
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def seen(s: int, kind: str, window: int):
    """[query, key] bool: which keys a query of a ``kind`` layer sees."""
    dist = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    if kind == "sliding_attention":
        return (dist >= 0) & (dist < window)
    return dist >= 0


def attention(h, w, m, kind):
    s = h.shape[0]
    heads, kv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                    m["head_dim"])
    eps = m["rms_norm_eps"]
    q = _rms((h @ w["wq"]).reshape(s, heads, d), w["nq"], eps)
    k = _rms((h @ w["wk"]).reshape(s, kv, d), w["nk"], eps)
    v = (h @ w["wv"]).reshape(s, kv, d)
    if kind == "sliding_attention":
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    # query head i reads kv head i // (heads / kv)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    sc = jnp.where(seen(s, kind, m["sliding_window"]), sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    return o.reshape(s, -1) @ w["wo"]


def route(h, router_w, m):
    """(picks [S, k], weights [S, k], scores [S, E])."""
    scores = jax.nn.sigmoid(h @ router_w)
    top, picks = jax.lax.top_k(scores, m["num_experts_per_tok"])
    w = m["routed_scaling_factor"] * top / (top.sum(-1, keepdims=True)
                                            + 1e-20)
    return picks, w, scores


def routed_experts(h, w, picks, weights, held):
    first, count = held
    out = jnp.zeros_like(h)
    for e in range(count):
        mask = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        out = out + mask[:, None] * _swiglu(h, w["we_gate"][e],
                                            w["we_up"][e], w["we_down"][e])
    return out


def ffn(h, w, m, held):
    """The FFN of normed rows: dense, or shared + held routed experts."""
    if "router" not in w:
        return _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    picks, weights, _ = route(h, w["router"], m)
    return _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"]) \
        + routed_experts(h, w, picks, weights, held)


def layer(x, w, m, held, kind):
    eps = m["rms_norm_eps"]
    x = x + attention(_rms(x, w["n1"], eps), w, m, kind)
    return x + ffn(_rms(x, w["n2"], eps), w, m, held)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def logits(params, tokens, m, held):
    """Logits [S, V] of one sequence ``tokens`` [S] at every position."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        x = params["embed"][tokens]
        for w, kind in zip(params["layers"], m["layer_types"]):
            x = layer(x, w, m, held, kind)
        return _rms(x, params["ln_f"], m["rms_norm_eps"]) @ params["lm_head"]
