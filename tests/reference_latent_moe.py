"""The plain reference of the latent-attention sparse-expert decoder
(``paddle_tpu/models/latent_moe.py``; openPangu-Ultra-MoE-718B's layer):
the published equations in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision — no cache, no kernel, no absorption, no
sorting (a loop over the held experts under a dense mask), and no code of
``paddle_tpu.models``. It reads the program's parameter tree by its names,
which is the one thing the two share, and takes the same share
(``held_experts``, the vocabulary slice the tree holds).
``chipbench/reference_latent_moe.py`` is the benchmark's copy (blocked and
cast a layer at a time, for the published widths).

``m``: a dict of the model's sizes under the public config.json's keys.

    a = Attn(N1(x));  x = x + N2(a);   m = FFN(N3(x));  x = x + N4(m)
    Attn(h): cq = Nq(h W_dq); q = cq W_uq -> heads x (nope | rope)
             [ckv | kr] = h W_dkv; ckv = Nkv(ckv); kr = RoPE(kr), ONE a token
             k_i = [ckv W_uk_i^T | kr]; v_i = ckv W_uv_i; q_i = [nope | RoPE(rope)]
             o_i = softmax(q_i k_i^T / sqrt(nope + rope), causal) v_i; concat_i(o_i) W_o
    FFN, dense layers: SwiGLU(intermediate_size)
    FFN, expert layers: s = sigmoid(h_f32 W_g_f32); S = top-k(s)
             w_e = scale * s_e / (sum_{j in S} s_j + 1e-20)
             SwiGLU_shared(h) + sum_{e in S, e held} w_e SwiGLU_e(h)
    MTP: h' = [Nh(x_L[t]) ; Ne(Emb(tok[t+1]))] W_p; one expert layer;
         Head(Nm(.)), the head shared
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


def attention(h, w, m):
    s = h.shape[0]
    heads = m["num_attention_heads"]
    dn, dr, rank = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                    m["kv_lora_rank"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    cq = _rms(h @ w["w_dq"], w["nq"], eps)
    q = (cq @ w["w_uq"]).reshape(s, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    ckr = h @ w["w_dkv"]
    ckv = _rms(ckr[:, :rank], w["nkv"], eps)
    kr = _rope(ckr[:, None, rank:], theta)
    k = jnp.concatenate([jnp.einsum("sr,hdr->shd", ckv, w["w_uk"]),
                         jnp.broadcast_to(kr, (s, heads, dr))], -1)
    v = jnp.einsum("sr,hrd->shd", ckv, w["w_uv"])
    sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(dn + dr)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    return o.reshape(s, -1) @ w["w_o"]


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


def layer(x, w, m, held):
    eps = m["rms_norm_eps"]
    x = x + _rms(attention(_rms(x, w["n1"], eps), w, m), w["n2"], eps)
    return x + _rms(ffn(_rms(x, w["n3"], eps), w, m, held), w["n4"], eps)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def residual(params, tokens, m, held):
    """The last layer's residual stream [S, H] (before the final norm)."""
    params = _f32(params)
    x = params["embed"][tokens]
    for group in ("dense", "moe"):
        for i in range(params[group]["n1"].shape[0]):
            x = layer(x, {k: v[i] for k, v in params[group].items()}, m,
                      held)
    return x


def logits(params, tokens, m, held):
    """Logits [S, V] of one sequence ``tokens`` [S] at every position."""
    with jax.default_matmul_precision("highest"):
        x = residual(params, tokens, m, held)
        return _rms(x, jnp.asarray(params["ln_f"], F32),
                    m["rms_norm_eps"]) @ jnp.asarray(params["lm_head"], F32)


def mtp_logits(params, mtp, hidden, next_tokens, m, held):
    """Logits [S, V] for the token at t+2 from ``hidden`` [S, H] (the main
    model's residual stream at t) and ``next_tokens`` [S] (the token at
    t+1)."""
    eps = m["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        params, mtp = _f32(params), _f32(mtp)
        x = jnp.concatenate([
            _rms(jnp.asarray(hidden, F32), mtp["nh"], eps),
            _rms(params["embed"][next_tokens], mtp["ne"], eps)], -1) \
            @ mtp["w_p"]
        x = layer(x, mtp["layer"], m, held)
        return _rms(x, mtp["nm"], eps) @ params["lm_head"]
