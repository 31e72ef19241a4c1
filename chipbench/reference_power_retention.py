"""The benchmark's plain reference of the power-retention decoder
(Brumby-14B-Base's layer), and the rule that decides ``correct`` for kind
``serve_retention``.

The equations of ``tests/reference_power_retention.py`` — the ATTENTION
form: the weights ``a_ts = exp(g_{s+1} + .. + g_t) (q_t . k_s / sqrt(d))^2``
as a matrix, no ``phi``, no state, no chunks, no cache — in straightforward
``jax.numpy`` and no code of the program under test; it reads the program's
parameter tree by its names. Float32 at ``highest`` matmul precision, or
(for the measured bf16 error) bfloat16 at the default. For the published
widths it works a layer at a time (the weights are cast as they are used),
in blocks of query rows (a ``[heads, rows, T]`` block of weights beside the
weights), and the head in blocks of the vocabulary. Each (†) is an
assumption the configuration file lists.

``faults`` plants what a control run wants to see refused: "no_decay".

**The rule.** Routing-free, so no search: at every checked position
(a) the token the timed path chose is the float32 reference's choice or lies
within ``TIE_SIGMAS`` pair-sigmas of it (``reference.py``'s rule: sigma is
the rms distance, over the vocabulary, of the reference computed in bf16
from the reference in float32 AT that position), and (b) the program's own
logits of the served sequence — its admission, then its ticks through the
state pages — lie as far from the float32 reference as a bf16 computation
may: their rms distance in units of that sigma, the median over the
positions (``logit_error``) and the median over the LAST QUARTER of each
request's positions (``logit_error_late``: an error that grows with the
ticks, as a state kept in too few bits makes it, shows here first), and
(c) the same distance for logits read from the state pages the timed
segment program left (``probes``; the kind's ``probe_states``).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

# the dense reference's norm, rotary helper and token band are this one's
from chipbench.reference import TIE_SIGMAS, _model_key, _rms, _rope

ROW_BLOCK = 256             # query rows a block of weights
VOCAB_BLOCK = 16384         # columns of the head a block
EPS = 1e-6                  # (†) the normaliser's epsilon


def _retention(q, k, v, cum):
    """q [S, heads, d], k / v [S, heads, d] (already repeated over the
    group), cum [S, heads] float32 the running sums of the log decays ->
    [S, heads * d]; causal, a block of query rows at a time."""
    s, heads, d = q.shape
    blk = min(ROW_BLOCK, s)
    assert s % blk == 0, (s, blk)
    cols = jnp.arange(s)
    cum_t = cum.T                                          # [heads, S]

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        cb = jax.lax.dynamic_slice_in_dim(cum_t, i * blk, blk, 1)
        dots = jnp.einsum("qhd,khd->hqk", qb, k).astype(jnp.float32) \
            / np.sqrt(d)
        rows = i * blk + jnp.arange(blk)
        seen = cols[None, None, :] <= rows[None, :, None]
        decay = jnp.exp(jnp.where(seen, cb[:, :, None] - cum_t[:, None, :],
                                  -jnp.inf))
        a = dots * dots * decay                            # (†) degree 2
        num = jnp.einsum("hqk,khd->qhd", a.astype(q.dtype), v)
        den = a.sum(-1).T[:, :, None] + EPS                # (†) normalised
        return (num.astype(jnp.float32) / den).astype(q.dtype)

    out = jax.lax.map(block, jnp.arange(s // blk))
    return out.reshape(s, heads * d)


def _layer(x, lp, model: dict, dtype, faults):
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    s = x.shape[0]
    w = {k: a.astype(jnp.float32 if k in ("wg", "bg") else dtype)
         for k, a in lp.items()}
    h = _rms(x, w["n1"], eps)
    # (†) RMSNorm over the head on q and k
    q = _rope(_rms((h @ w["wq"]).reshape(s, heads, d), w["nq"], eps), theta)
    k = _rope(_rms((h @ w["wk"]).reshape(s, kv, d), w["nk"], eps), theta)
    v = (h @ w["wv"]).reshape(s, kv, d)
    # (†) the gate: one log decay a token a kv head, float32
    g = jax.nn.log_sigmoid(h.astype(jnp.float32) @ w["wg"] + w["bg"])
    if "no_decay" in faults:
        g = jnp.zeros_like(g)
    rep = heads // kv
    y = _retention(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                   jnp.repeat(jnp.cumsum(g, 0), rep, 1))
    x = x + y @ w["wo"]
    h = _rms(x, w["n2"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@functools.lru_cache(maxsize=None)
def _programs(model_key, float32: bool, faults: tuple):
    model = dict(model_key)
    dtype = jnp.float32 if float32 else jnp.bfloat16
    precision = "highest" if float32 else "default"

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(dtype)

    @jax.jit
    def layer(x, lp):
        with jax.default_matmul_precision(precision):
            return _layer(x, lp, model, dtype, faults)

    @jax.jit
    def head(x, ln_f, w):
        with jax.default_matmul_precision(precision):
            return (_rms(x, ln_f, model["rms_norm_eps"])
                    @ w.astype(dtype)).astype(jnp.float32)

    return embed, layer, head


def logits_at(params, tokens, rows, model: dict, float32: bool,
              faults=()):
    """Logits [len(rows), V] of one padded sequence at positions ``rows``:
    a layer at a time, the head a block of the vocabulary at a time."""
    embed, layer, head = _programs(_model_key(model), float32, tuple(faults))
    x = embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(model["num_hidden_layers"]):
        x = layer(x, {k: a[i] for k, a in params["layers"].items()})
    x = x[jnp.asarray(rows, jnp.int32)]
    w = params["lm_head"]
    return jnp.concatenate(
        [head(x, params["ln_f"], w[:, c:c + VOCAB_BLOCK])
         for c in range(0, w.shape[1], VOCAB_BLOCK)], axis=1)


def check_generation(params, model: dict, prompt, generated, pad_to: int,
                     max_rows: int, what: str, program_logits=None,
                     faults=(), probes=()) -> dict:
    """Teacher-forced check of one served request, its first ``max_rows``
    generated tokens (``what`` names it in a refusal's line; none: its
    tokens are not judged). ``program_logits`` [n, V]: the program's own logits
    at those positions (``None``: tokens only). ``probes``: (i, logits [V])
    — the program's logits, read from a state it left, of generated
    position ``i`` (after ``generated[:i]``). Returns {"checked", "exact",
    "ties", "worst_sigmas", "logit_errors", "probe_errors"}: per position
    (per probe) the rms distance of the program's logits from the float32
    reference's in units of the reference's measured bf16 error there."""
    prompt = np.asarray(prompt, np.int32)
    gen = np.asarray(generated, np.int32)
    n = min(len(gen), max_rows)
    seq = np.zeros((pad_to,), np.int32)
    seq[: len(prompt)] = prompt
    seq[len(prompt): len(prompt) + n - 1] = gen[:n - 1]
    rows = np.full((max_rows,), len(prompt) - 1, np.int32)
    rows[:n] = len(prompt) - 1 + np.arange(n)
    f32 = np.asarray(logits_at(params, seq, rows, model, True, faults))[:n]
    b16 = np.asarray(logits_at(params, seq, rows, model, False, faults))[:n]
    sigma = np.sqrt(np.mean((b16 - f32) ** 2, axis=-1))
    best = f32.max(axis=-1)
    chosen = f32[np.arange(n), gen[:n]]
    sigmas = (best - chosen) / (np.sqrt(2.0) * sigma)
    worst = float(sigmas.max())
    if worst > TIE_SIGMAS and what:
        p = int(sigmas.argmax())
        print(f"chipbench: {what}: generated position {p}: token "
              f"{int(gen[p])} is {worst:.2f} pair-sigmas below the float32 "
              f"reference's {int(f32[p].argmax())} (sigma "
              f"{float(sigma[p]):.5f}) - not a bf16 tie", file=sys.stderr)
    errors = []
    if program_logits is not None:
        got = np.asarray(program_logits, np.float32)[:n]
        errors = (np.sqrt(np.mean((got - f32) ** 2, axis=-1)) / sigma
                  ).tolist()
    probe_errors = [float(np.sqrt(np.mean((np.asarray(lg, np.float32)
                                           - f32[i]) ** 2)) / sigma[i])
                    for i, lg in probes]
    exact = int(np.sum(f32.argmax(axis=-1) == gen[:n]))
    return {"checked": n, "exact": exact, "ties": n - exact,
            "beyond": int(np.sum(sigmas > TIE_SIGMAS)),
            "worst_sigmas": worst, "logit_errors": errors,
            "probe_errors": probe_errors, "sigma_mean": float(sigma.mean())}
