"""The plain reference of the window / full attention sparse-expert decoder
family (K-EXAONE-236B-A23B: pre-norm RMSNorm, GQA with a per-head norm on q
and k, three ``sliding_attention`` layers of window 128 with rotary then one
``full_attention`` layer without, a leading dense layer, then sigmoid-routed
experts beside a shared one, untied head), for ONE CHIP'S SHARE of it, and
the rule that decides ``correct`` in its cells.

The benchmark's copy of ``tests/reference_hybrid_moe.py``: the same
equations in plain ``jax.numpy``, float32 at ``highest`` matmul precision,
no kernel, no cache, no sorting, no code of the program under test. It reads
the program's parameter tree by its names (``params["layers"]``: one dict a
layer), which is the one thing the two share. What differs from the test
copy is size: 3.7 B parameters in float32 do not fit beside themselves in
bf16, so EACH LAYER IS A PROGRAM OF ITS OWN that casts its weights where it
uses them (the experts a ``lax.map``), and attention is computed in blocks
of queries; the mathematics is the unblocked one.

Per layer ``l``, ``x`` the residual stream (``eps`` 1e-5)::

    h = N1(x);  x = x + Attn_l(h);      h = N2(x);  x = x + FFN_l(h)
    Attn_l(h): q = Nq(h W_q) -> 64 x 128; k = Nk(h W_k), v = h W_v -> 8 x 128
               layer_types[l] == "sliding_attention": q, k = RoPE(q, k); key
                   s is seen by query t iff 0 <= t - s < sliding_window
               layer_types[l] == "full_attention": no rotary; seen iff s <= t
               o = softmax(q k^T / sqrt(128)) v; concat(o) W_o
    FFN, a layer with ``w_gate``: SwiGLU(intermediate_size)
    FFN, a layer with ``router``: the expert layer of
               ``reference_latent_moe`` (its ``route`` and
               ``routed_experts``, imported): sigmoid scores in float32 over
               all 128, top-8, normalised, scaled by 2.5; the shared expert
               + the picks that are HELD

What the config.json has no key for (pre-norm placement, the q / k norm,
rotary on the window layers only, the router's missing bias) is listed in
the configuration file's ``assumed``.

**The rule** is ``reference_latent_moe.check_generation``'s, imported and
run over this family's forward pass: a generated token is held to the
float32 reference under the position's legitimate routings (trades within
the router's measured bf16 tie band), and the program's own logits, replayed
as the engine drives the program, to the reference's at the positions where
no trade is legitimate, in units of the reference's measured bf16 error.
This module's limits are for this family's numbers (PERF.md section 4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_latent_moe as latent
from .reference_latent_moe import (BEYOND_SHARE_MAX,  # noqa: F401
                                   TIE_SIGMAS, UNJUDGED_SHARE_MAX, _rms,
                                   _rope, _swiglu)

# The program's logits against the float32 reference's, teacher-forced on
# what it served, in units of the reference's own bf16 error, over a run's
# clean positions: the median and the 90th percentile. Each limit lies
# between two readings on the chip (PERF.md section 4; my chip runs, PR 36):
# the sound program's largest over 18 checks, 1.300 / 1.423, and the
# controls' smallest, 6.00 / 7.16 (the full layer rotated too; 3 mantissa
# bits in the attention projections read 8.70 / 14.29).
LOGIT_ERROR_MAX = 1.5
LOGIT_ERROR_P90_MAX = 1.7
Q_BLOCK = 256
ATTN_KEYS = ("wq", "wk", "wv", "wo", "nq", "nk", "n1", "n2")
WINDOW = "sliding_attention"


def attention(h, w, model: dict, kind: str):
    """The attention sublayer of normed rows ``h`` [S, H] -> [S, H], over
    the sequence's own rows (no cache), a block of queries at a time.
    ``model``: ``_sizes`` of the configuration."""
    s = h.shape[0]
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    q = _rms((h @ w["wq"]).reshape(s, heads, d), w["nq"], eps)
    k = _rms((h @ w["wk"]).reshape(s, kv, d), w["nk"], eps)
    v = (h @ w["wv"]).reshape(s, kv, d)
    if kind == WINDOW:
        q, k = _rope(q, theta), _rope(k, theta)
    qg = q.reshape(s, kv, heads // kv, d)     # head i reads kv head i // 8
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    cols = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk, 0)
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k).astype(jnp.float32)
        sc = sc / np.sqrt(d)
        dist = (i * blk + jnp.arange(blk))[:, None] - cols[None, :]
        seen = dist >= 0
        if kind == WINDOW:
            seen = seen & (dist < model["sliding_window"])
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p.astype(q.dtype), v)

    o = jax.lax.map(block, jnp.arange(s // blk))
    return o.reshape(s, -1) @ w["wo"]


def layer(x, lp, model: dict, kind: str, held, dtype, trade=None,
          forced=None):
    """One layer on ``x`` [S, H]; an expert layer where ``lp`` has a
    router. Returns (x, the layer's routing or None)."""
    eps = model["rms_norm_eps"]
    w = {k: lp[k].astype(dtype) for k in ATTN_KEYS}
    x = x + attention(_rms(x, w["n1"], eps), w, model, kind)
    h = _rms(x, w["n2"], eps)
    if "router" not in lp:
        return x + _swiglu(h, *(lp[k].astype(dtype) for k in
                                ("w_gate", "w_up", "w_down"))), None
    r = latent.route(h, lp["router"], model, trade, forced)
    m = _swiglu(h, *(lp[k].astype(dtype)
                     for k in ("ws_gate", "ws_up", "ws_down")))
    m = m + latent.routed_experts(h, lp, r["picks"], r["weights"], held,
                                  dtype)
    return x + m, r


def _sizes(model: dict) -> dict:
    """The numbers of the configuration the layers read."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "sliding_window", "num_experts_per_tok",
            "routed_scaling_factor")
    return dict({k: model[k] for k in keys},
                rope_theta=model["rope_parameters"]["rope_theta"])


@functools.lru_cache(maxsize=None)
def _layer_program(sizes, kind: str, held, float32: bool, forced: bool):
    """One layer as a program of its own: it casts the layer's weights
    where it uses them, and they go when it returns."""
    model = dict(sizes)
    dtype = jnp.float32 if float32 else jnp.bfloat16

    def run(x, lp, given):
        with jax.default_matmul_precision("highest" if float32
                                          else "default"):
            return layer(x, lp, model, kind, held, dtype,
                         trade=None if forced else given,
                         forced=given if forced else None)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_program(eps: float, float32: bool):
    dtype = jnp.float32 if float32 else jnp.bfloat16

    def run(x, ln_f, lm_head, rows):
        with jax.default_matmul_precision("highest" if float32
                                          else "default"):
            h = _rms(x, ln_f.astype(dtype), eps)
            return (h[rows] @ lm_head.astype(dtype)).astype(jnp.float32)

    return jax.jit(run)


def logits_at(params, tokens, rows, model: dict, share: dict, float32: bool,
              trade=None, forced=None):
    """(logits [len(rows), V] of one padded sequence at ``rows``, the
    expert layers' routing stacked on axis 0) —
    ``reference_latent_moe.logits_at``'s contract. ``trade`` [Le, S, 2]
    int32 (default: none); ``forced`` picks [Le, S, k] instead."""
    dtype = jnp.float32 if float32 else jnp.bfloat16
    held = tuple(share["held_experts"])
    sizes = tuple(sorted(_sizes(model).items()))
    tokens = jnp.asarray(tokens, jnp.int32)
    n_sparse = sum("router" in lp for lp in params["layers"])
    if forced is None and trade is None:    # one program with or without
        trade = np.full((n_sparse, tokens.shape[0], 2), -1, np.int32)
    given = forced if forced is not None else trade
    x = params["embed"][tokens].astype(dtype)
    routing, j = [], 0
    for lp, kind in zip(params["layers"], model["layer_types"]):
        sparse = "router" in lp
        run = _layer_program(sizes, kind, held, float32,
                             sparse and forced is not None)
        mine = jnp.asarray(given[j], jnp.int32) if sparse else None
        x, r = run(x, lp, mine)
        if sparse:
            routing.append(r)
            j += 1
    lg = _head_program(model["rms_norm_eps"], float32)(
        x, params["ln_f"], params["lm_head"], jnp.asarray(rows, jnp.int32))
    return lg, {k: np.stack([np.asarray(r[k]) for r in routing])
                for k in routing[0]}


def check_generation(params, model: dict, share: dict, prompt, generated,
                     pad_to: int, max_rows: int, what: str,
                     program_rows=None) -> dict:
    """``reference_latent_moe.check_generation`` over this family's
    forward pass (the rule is imported, not copied: the function looks
    ``logits_at`` up in its module when it runs). ``program_rows``
    [max_rows, V]: the program's own logits at the first ``max_rows``
    generated positions of this sequence."""
    program = None
    if program_rows is not None:
        n0 = len(prompt)

        def program(seq):
            out = np.zeros((pad_to, program_rows.shape[-1]), np.float32)
            out[n0 - 1:n0 - 1 + len(program_rows)] = program_rows
            return out

    theirs = latent.logits_at
    latent.logits_at = logits_at
    try:
        return latent.check_generation(params, model, share, prompt,
                                       generated, pad_to, max_rows, what,
                                       program)
    finally:
        latent.logits_at = theirs
