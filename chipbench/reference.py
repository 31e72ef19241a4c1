"""The plain reference of the dense decoder family both configurations are
(InternLM2, Mistral: pre-norm RMSNorm, rotary positions in the rotate-half
convention, grouped-query attention, SwiGLU, no biases, untied head), and
the rule that decides ``correct``.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, no batching tricks, and no code of the program under
test. It reads the program's parameter tree by its names (``embed``,
``wq`` ... stacked over layers on axis 0), which is the one thing the two
share. Attention is computed in blocks of queries only so that a 4096-long
sequence's scores fit beside a training state; the mathematics is the
unblocked one.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

# A greedy token may differ from the reference's choice only at a bf16 tie.
# A bf16 run of the model carries an error sigma on every logit (measured
# at the position in question: the rms distance, over the vocabulary, of
# the reference computed in bf16 from the reference in float32). Two tokens
# can be swapped only when their float32 logits lie within the error of a
# logit PAIR, sqrt(2) sigma, times this many. chip_smoke.TIE_SIGMAS is 4;
# on the chip the worst of 41,000 teacher-forced tokens lay 3.45 away (PR
# 24), which leaves a check of 40 runs about one chance in seven of a false
# alarm at 4 and none to speak of at 5.
TIE_SIGMAS = 5.0
Q_BLOCK = 1024


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, theta):
    # x [S, heads, D]; rotate-half: the head splits into two halves
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    # q [S, heads, D], k/v [S, kv_heads, D] -> [S, heads * D], causal
    s, heads, d = q.shape
    rep = heads // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    cols = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k).astype(jnp.float32)
        sc = sc / np.sqrt(d)
        rows = i * blk + jnp.arange(blk)
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(s // blk))
    return out.reshape(s, heads * d)


def hidden(params, tokens, model: dict, dtype):
    """Final hidden states [S, H] of one sequence ``tokens`` [S]."""
    heads = model["num_attention_heads"]
    kvh = model["num_key_value_heads"]
    d = model["hidden_size"] // heads
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    x = params["embed"][tokens].astype(dtype)
    s = x.shape[0]

    def layer(x, lp):
        w = {k: a.astype(dtype) for k, a in lp.items()}
        h = _rms(x, w["ln_attn"], eps)
        q = _rope((h @ w["wq"]).reshape(s, heads, d), theta)
        k = _rope((h @ w["wk"]).reshape(s, kvh, d), theta)
        v = (h @ w["wv"]).reshape(s, kvh, d)
        x = x + _attention(q, k, v) @ w["wo"]
        h = _rms(x, w["ln_mlp"], eps)
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        return x, None

    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln_attn",
             "ln_mlp")
    x, _ = jax.lax.scan(layer, x, {k: params[k] for k in names})
    return _rms(x, params["ln_f"], eps)


def _model_key(model: dict):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float))))


@functools.lru_cache(maxsize=None)
def _logits_program(model_key, float32: bool):
    model = dict(model_key)
    dtype = jnp.float32 if float32 else jnp.bfloat16

    def one(params, tokens, rows):
        h = hidden(params, tokens, model, dtype)[rows]
        return (h @ params["lm_head"].astype(dtype)).astype(jnp.float32)

    def logits(params, tokens, rows):
        with jax.default_matmul_precision("highest" if float32
                                          else "default"):
            return one(params, tokens, rows)

    return jax.jit(logits)


def logits_at(params, tokens, rows, model: dict, float32: bool):
    """Logits [len(rows), V] of one padded sequence at positions ``rows``."""
    return _logits_program(_model_key(model), float32)(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32))


def check_generation(params, model: dict, prompt, generated, pad_to: int,
                     max_rows: int, what: str) -> dict:
    """Teacher-forced check of one served request: at every generated
    position (the first ``max_rows`` of them), the token the system chose
    must be the float32 reference's choice, or lie within the bf16 tie band
    of it. The sequence is padded to ``pad_to`` (causal: what follows a
    position changes nothing before it), so one program serves every
    request. Returns {"ok", "exact", "ties", "worst_sigmas"}."""
    prompt = np.asarray(prompt, np.int32)
    gen = np.asarray(generated, np.int32)
    n = min(len(gen), max_rows)
    seq = np.zeros((pad_to,), np.int32)
    seq[: len(prompt)] = prompt
    seq[len(prompt): len(prompt) + len(gen) - 1] = gen[:-1]
    rows = np.full((max_rows,), len(prompt) - 1, np.int32)
    rows[:n] = len(prompt) - 1 + np.arange(n)
    f32 = np.asarray(logits_at(params, seq, rows, model, True))[:n]
    b16 = np.asarray(logits_at(params, seq, rows, model, False))[:n]
    sigma = np.sqrt(np.mean((b16 - f32) ** 2, axis=-1))
    best = f32.max(axis=-1)
    chosen = f32[np.arange(n), gen[:n]]
    sigmas = (best - chosen) / (np.sqrt(2.0) * sigma)
    exact = int(np.sum(f32.argmax(axis=-1) == gen[:n]))
    worst = float(sigmas.max())
    ok = bool(worst <= TIE_SIGMAS)
    if not ok:
        p = int(sigmas.argmax())
        print(f"chipbench: {what}: generated position {p}: token "
              f"{int(gen[p])} is {worst:.2f} pair-sigmas below the float32 "
              f"reference's {int(f32[p].argmax())} (sigma "
              f"{float(sigma[p]):.5f}) - not a bf16 tie", file=sys.stderr)
    return {"ok": ok, "checked": n, "exact": exact, "ties": n - exact,
            "worst_sigmas": worst}


@functools.lru_cache(maxsize=None)
def _loss_program(model_key):
    model = dict(model_key)

    def row_nll(params, row):
        h = hidden(params, row, model, jnp.float32)[:-1]
        lg = h @ params["lm_head"].astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    def loss(params, tokens):
        with jax.default_matmul_precision("highest"):
            total = jax.lax.map(lambda r: row_nll(params, r), tokens)
        b, s = tokens.shape
        return jnp.sum(total) / (b * (s - 1))

    return jax.jit(loss)


def loss(params, tokens, model: dict) -> float:
    """Mean next-token cross entropy of ``tokens`` [B, S] in float32."""
    return float(_loss_program(_model_key(model))(params, tokens))
