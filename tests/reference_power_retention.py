"""The plain reference of the power-retention decoder
(``paddle_tpu/models/power_retention.py``; Brumby-14B-Base's layer): the
equations in the ATTENTION form — the weights ``a_ts`` as a ``[T, T]``
matrix — in straightforward ``jax.numpy``, float32 at ``highest`` matmul
precision. No ``phi``, no state, no chunks, no cache, no kernel, and no code
of ``paddle_tpu``. It reads the program's parameter tree by its names, which
is the one thing the two share. ``chipbench/reference_power_retention.py``
is the benchmark's copy (blocked over rows, for the published widths).

``m``: a dict of the model's sizes under the public config.json's keys.
Written from the published description (Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239, and the model card's
modeling file) from memory; the config.json has the Qwen3 keys only, so each
item marked (†) is an ASSUMPTION the configuration file lists too.

    h = N1(x)
    q = Nq(h W_q);  k = Nk(h W_k);  v = h W_v        # Nq, Nk over the head (†)
    q, k = RoPE(q, k)                                 # rotate-half
    g_t = logsigmoid(h_t W_g + b_g), one a kv head    # the gate (†)
    a_ts = exp(g_{s+1} + .. + g_t) (q_t . k_s / sqrt(d))^2,  s <= t   # p = 2 (†)
    y_t = sum_s a_ts v_s / (sum_s a_ts + eps)         # normaliser, eps 1e-6 (†)
    x = x + concat_heads(y) W_o;   x = x + SwiGLU(N2(x))

``faults``: what a test plants to see that the comparison would show it
("no_decay", "no_normaliser", "degree_1").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EPS = 1e-6          # (†) the normaliser's epsilon


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


def retention(h, w, m, faults=()):
    """The sublayer on ``h`` [S, H] (already normed): [S, heads * d]."""
    s = h.shape[0]
    heads, kv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                    m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    # (†) RMSNorm over the head's lanes on q and k, as the Qwen3 block has
    q = _rope(_rms((h @ w["wq"]).reshape(s, heads, d), w["nq"], eps), theta)
    k = _rope(_rms((h @ w["wk"]).reshape(s, kv, d), w["nk"], eps), theta)
    v = (h @ w["wv"]).reshape(s, kv, d)
    # (†) the gate: one log decay a token a kv head
    g = jax.nn.log_sigmoid(h @ w["wg"] + w["bg"])             # [S, kv]
    if "no_decay" in faults:
        g = jnp.zeros_like(g)
    cum = jnp.cumsum(g, axis=0)
    # query head i reads kv head i // group
    group = heads // kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    cum = jnp.repeat(cum, group, axis=1)                      # [S, heads]
    dots = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(d)
    # (†) degree 2
    power = dots if "degree_1" in faults else dots * dots
    causal = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(causal, cum.T[:, :, None] - cum.T[:, None, :],
                              -jnp.inf))                      # [heads, t, s]
    a = power * decay
    num = jnp.einsum("hts,shd->thd", a, v)
    # (†) normalised by the sum of the weights
    den = 1.0 if "no_normaliser" in faults else \
        a.sum(-1).T[:, :, None] + EPS
    return (num / den).reshape(s, heads * d)


def hidden(params, tokens, m, faults=()):
    """Residual stream after the last layer (before the final norm)."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), params)
    eps = m["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = p["embed"][jnp.asarray(tokens)]
        for i in range(m["num_hidden_layers"]):
            w = {k: a[i] for k, a in p["layers"].items()}
            x = x + retention(_rms(x, w["n1"], eps), w, m, faults) @ w["wo"]
            h = _rms(x, w["n2"], eps)
            x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) \
                @ w["w_down"]
        return x


def logits(params, tokens, m, faults=()):
    """tokens [S] -> logits [S, V], every position, float32."""
    x = hidden(params, tokens, m, faults)
    with jax.default_matmul_precision("highest"):
        x = _rms(x, jnp.asarray(params["ln_f"], F32), m["rms_norm_eps"])
        return x @ jnp.asarray(params["lm_head"], F32)
