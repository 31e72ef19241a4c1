"""The plain reference of the latent-attention sparse-expert decoder family
(openPangu-Ultra-MoE-718B: sandwich RMSNorm, MLA over one latent row a
token, sigmoid-routed experts beside a shared one, untied head), for ONE
CHIP'S SHARE of it, and the rule that decides ``correct`` in its cells.

The benchmark's copy of ``tests/reference_latent_moe.py``: the same
equations in plain ``jax.numpy``, float32 at ``highest`` matmul precision,
no kernel, no cache, no sorting (a loop over the held experts with a dense
mask), no code of the program under test. It reads the program's parameter
tree by its names, which is the one thing the two share. What differs from
the test copy is size: 4.9 B parameters in float32 do not fit beside
themselves in bf16, so a layer's weights are cast where the layer uses
them (the layer loop is a scan, the experts a ``lax.map``), and attention
is computed in blocks of queries; the mathematics is the unblocked one.

Per layer, ``x`` the residual stream (``eps`` 1e-5, every norm an RMSNorm
with a learned scale)::

    a = Attn(N1(x));  x = x + N2(a);   m = FFN(N3(x));  x = x + N4(m)
    Attn(h): cq = Nq(h W_dq); q = cq W_uq -> heads x (nope | rope)
             [ckv | kr] = h W_dkv; ckv = Nkv(ckv); kr = RoPE(kr), ONE a token
             k_i = [ckv W_uk_i^T | kr]; v_i = ckv W_uv_i; q_i = [nope | RoPE(rope)]
             o_i = softmax(q_i k_i^T / sqrt(nope + rope), causal) v_i; concat_i(o_i) W_o
    FFN, dense layers: SwiGLU(intermediate_size)
    FFN, expert layers: s = sigmoid(h_f32 W_g_f32) over ALL routed experts
             S = top-k(s); w_e = scale * s_e / (sum_{j in S} s_j + 1e-20)
             SwiGLU_shared(h) + sum_{e in S, e held} w_e SwiGLU_e(h)

**The share.** ``share["held_experts"]`` = (first, count): the sum runs
over the picks that are held; ``S`` and ``w_e`` are taken over the whole
router. The vocabulary is the slice the tree holds.

**Routing is discontinuous, so the rule is in two parts.** A bf16 run
carries an error on every router score; where two of the reference's own
scores on either side of its top-k lie within that error of each other the
program may rightly pick the lower one, and its logits then differ from the
reference's by a whole expert, not by rounding. So a routing is LEGITIMATE
at a (token, layer) if it is the reference's own or differs from it by
trading a picked expert ``a`` for an unpicked ``b`` whose float32 scores lie
within the tie band, ``s_a - s_b <= TIE_SIGMAS * sqrt(2) * sigma_router``
(a trade between two absent experts changes nothing here and is not
counted). ``check_generation`` holds every generated token to the float32
reference under the reference's own routing first; for the positions that
fail it searches the position's legitimate routings, one trade and then two
trades in different layers (a pass of the reference tries one candidate of
EVERY such position at once: what the trades of other tokens change at a
position is far under the logits' own error, one row in hundreds that
attention averages). A token is **explained** when it is within the logits'
tie band of the best under one of them. A greedy token moves only where
the two best logits lie within the error, so tokens are the blunter
witness of a lower precision: the program's OWN logits of the served
sequence (``forward_with_pages`` over the pages, its kernels,
teacher-forced) are held to the reference's too, at the positions where no
trade is legitimate: ``logit_error``, the median over those positions of the rms
difference over the vocabulary in units of the reference's measured bf16
error there (``LOGIT_ERROR_MAX``). Four numbers are judged, each with its
limit: that one; ``worst_sigmas`` over the tokens judged under the
reference's own routing or an explaining one (the rule of ``reference.py``);
the share of positions **beyond the band** under every routing tried
(``BEYOND_SHARE_MAX``); the share left **unjudged** because three or more of
their layers have trades, eight or more routings (``UNJUDGED_SHARE_MAX``).
Both error scales are MEASURED: the reference in bf16 (routing forced to the
float32 one's, so that the difference is rounding and not a flip) against
the reference in float32, per position for the logits and per layer for the
router's scores, as ``reference.py`` does for its family.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

# as reference.py: a token (a router pick) may differ from the float32
# reference's only within this many times the error of a PAIR of logits
# (scores), sqrt(2) sigma, sigma measured at the position (in the layer).
TIE_SIGMAS = 5.0
# Routing trades looked at on each side of the top-k boundary.
EDGE = 4
# The share of checked positions whose token lies beyond the tie band under
# every legitimate routing tried. Readings (as below): the change 0.0 in 29
# runs (under the reference's OWN routing alone 2-10 of 1,024 tokens a run
# lie beyond it, and the search explains every one), the control 0.123. A
# bf16 program's own router error can exceed the reference's measured one,
# and a search of one and two trades does not reach every legitimate set:
# hence a share and not zero.
BEYOND_SHARE_MAX = 0.01
# The share left unjudged: three or more layers with a trade within the band.
UNJUDGED_SHARE_MAX = 0.05
PASSES_MAX = 24     # routings tried beyond the reference's own, a request
# The program's logits against the float32 reference's, teacher-forced on
# what it served, in units of the reference's own bf16 error (rms over the
# vocabulary, at positions where no trade is legitimate), the median over a
# run's clean positions: the sharper of the two comparisons (a token moves
# only where the two best logits lie within the error). Readings (PERF.md
# §6, PR 29; my chip runs): the change 0.99-1.03 over 8 runs (90th
# percentile of positions 1.03-1.09), the control with 3 mantissa bits in
# the attention projections 9.76.
LOGIT_ERROR_MAX = 1.6
Q_BLOCK = 256


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, theta):
    # x [S, heads, D] at positions 0..S-1; rotate-half
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def attention(h, w, model: dict):
    """The attention sublayer of normed rows ``h`` [S, H] -> [S, H]; keys
    and values are EXPANDED from the latents (no absorption, no cache)."""
    s = h.shape[0]
    heads = model["num_attention_heads"]
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, eps, theta = (model["kv_lora_rank"], model["rms_norm_eps"],
                        model["rope_theta"])
    cq = _rms(h @ w["w_dq"], w["nq"], eps)
    q = (cq @ w["w_uq"]).reshape(s, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    ckr = h @ w["w_dkv"]
    ckv = _rms(ckr[:, :rank], w["nkv"], eps)
    kr = _rope(ckr[:, None, rank:], theta)                    # [S, 1, dr]
    k_nope = jnp.einsum("sr,hdr->shd", ckv, w["w_uk"])
    v = jnp.einsum("sr,hrd->shd", ckv, w["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (s, heads, dr))], -1)
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    cols = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k).astype(jnp.float32)
        sc = sc / np.sqrt(dn + dr)
        rows = i * blk + jnp.arange(blk)
        sc = jnp.where(cols[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(block, jnp.arange(s // blk))
    return o.reshape(s, -1) @ w["w_o"]


def route(h, router_w, model: dict, trade=None, forced=None):
    """Scores [S, E] (float32, ``highest``), picks [S, k] and weights
    [S, k] of normed rows ``h``, and the ``top`` [S, k + EDGE] scores with
    their experts ``order``. ``trade`` [S, 2] int32 = (a, b): the pick of
    rank ``a`` gives way to the expert of rank ``b`` there ((-1, -1):
    none). ``forced`` [S, k]: these picks instead (the weights are of the
    picks taken, whichever)."""
    k = model["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(h.astype(jnp.float32)
                                @ router_w.astype(jnp.float32))
    top, order = jax.lax.top_k(scores, min(k + EDGE, scores.shape[1]))
    picks = order[:, :k]
    if trade is not None:
        taken = jnp.take_along_axis(order, jnp.maximum(trade[:, 1:], 0), 1)
        picks = jnp.where(jnp.arange(k)[None, :] == trade[:, :1], taken,
                          picks)
    if forced is not None:
        picks = forced
    s_picked = jnp.take_along_axis(scores, picks, axis=1)
    w = model["routed_scaling_factor"] * s_picked \
        / (s_picked.sum(-1, keepdims=True) + 1e-20)
    return {"scores": scores, "picks": picks, "weights": w, "top": top,
            "order": order}


def routed_experts(h, w, picks, weights, held, dtype):
    """sum over the picks of a HELD expert of w_e SwiGLU_e(h): a loop over
    the held experts, each on every row under a dense mask."""
    first, count = held

    def one(args):
        e, wg, wu, wd = args
        mask = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        y = _swiglu(h, wg.astype(dtype), wu.astype(dtype), wd.astype(dtype))
        return y.astype(jnp.float32) * mask[:, None]

    parts = jax.lax.map(one, (jnp.arange(count), w["we_gate"], w["we_up"],
                              w["we_down"]))
    return parts.sum(0).astype(dtype)


ATTN_KEYS = ("w_dq", "w_uq", "w_dkv", "w_uk", "w_uv", "w_o", "nq", "nkv",
             "n1", "n2", "n3", "n4")


def layer(x, lp, model: dict, held, dtype, trade=None, forced=None):
    """One layer on ``x`` [S, H]; an expert layer where ``lp`` has a
    router. Returns (x, the layer's routing or None)."""
    eps = model["rms_norm_eps"]
    w = {k: lp[k].astype(dtype) for k in ATTN_KEYS}
    x = x + _rms(attention(_rms(x, w["n1"], eps), w, model), w["n2"], eps)
    h = _rms(x, w["n3"], eps)
    if "router" not in lp:
        m = _swiglu(h, *(lp[k].astype(dtype)
                         for k in ("w_gate", "w_up", "w_down")))
        return x + _rms(m, w["n4"], eps), None
    r = route(h, lp["router"], model, trade, forced)
    m = _swiglu(h, *(lp[k].astype(dtype)
                     for k in ("ws_gate", "ws_up", "ws_down")))
    m = m + routed_experts(h, lp, r["picks"], r["weights"], held, dtype)
    return x + _rms(m, w["n4"], eps), r


def hidden(params, tokens, model: dict, share: dict, dtype, trade=None,
           forced=None):
    """Final normed hidden states [S, H] of one sequence and the expert
    layers' routing stacked on axis 0. ``trade`` [Le, S, 2] / ``forced``
    [Le, S, k]: see ``route``."""
    held = tuple(share["held_experts"])
    x = params["embed"][tokens].astype(dtype)
    n_dense = params["dense"]["n1"].shape[0]
    for i in range(n_dense):
        x, _ = layer(x, {k: v[i] for k, v in params["dense"].items()},
                     model, held, dtype)

    def body(x, xs):
        lp, tr, fp = xs
        return layer(x, lp, model, held, dtype, tr, fp)

    x, routing = jax.lax.scan(body, x, (params["moe"], trade, forced))
    return _rms(x, params["ln_f"], model["rms_norm_eps"]), routing


def _key(d: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()
                        if isinstance(v, (int, float, list))))


@functools.lru_cache(maxsize=None)
def _logits_program(model_key, share_key, float32: bool, forced: bool):
    model, share = dict(model_key), dict(share_key)
    dtype = jnp.float32 if float32 else jnp.bfloat16

    def logits(params, tokens, rows, given):
        # ``given``: the picks when ``forced``, else the trades
        with jax.default_matmul_precision("highest" if float32
                                          else "default"):
            h, routing = hidden(
                params, tokens, model, share, dtype,
                trade=None if forced else given,
                forced=given if forced else None)
            lg = (h[rows] @ params["lm_head"].astype(dtype))
        return lg.astype(jnp.float32), routing

    return jax.jit(logits)


def logits_at(params, tokens, rows, model: dict, share: dict, float32: bool,
              trade=None, forced=None):
    """(logits [len(rows), V] of one padded sequence at ``rows``, the
    expert layers' routing). ``trade`` [Le, S, 2] int32 (default: none);
    ``forced`` picks [Le, S, k] instead."""
    tokens = jnp.asarray(tokens, jnp.int32)
    n_exp = params["moe"]["n1"].shape[0]
    if forced is None and trade is None:
        trade = np.full((n_exp, tokens.shape[0], 2), -1, np.int32)
    return _logits_program(_key(model), _key(share), float32,
                           forced is not None)(
        params, tokens, jnp.asarray(rows, jnp.int32),
        jnp.asarray(forced if forced is not None else trade, jnp.int32))


def trades_allowed(routing_f32: dict, routing_b16: dict, k: int, held):
    """The legitimate trades of every (layer, token): a list of (a, b),
    closest scores first, where the pick of rank ``a`` < k and the expert of
    rank ``b`` >= k have float32 scores within TIE_SIGMAS pair errors of each
    other (the error: rms over the layer's scores of the bf16 reference
    against the float32 one) and one of the two is held. Returns
    ({(layer, token): [(a, b), ...]}, the per-layer score sigma)."""
    first, count = held
    s32 = np.asarray(routing_f32["scores"])
    sigma = np.sqrt(np.mean(
        (np.asarray(routing_b16["scores"]) - s32) ** 2, axis=(1, 2)))
    top = np.asarray(routing_f32["top"])                  # [Le, S, k + EDGE]
    order = np.asarray(routing_f32["order"])
    here = (order >= first) & (order < first + count)
    lo = max(0, k - EDGE)
    gap = top[:, :, lo:k, None] - top[:, :, None, k:]     # [Le, S, a, b]
    ok = (gap <= TIE_SIGMAS * np.sqrt(2.0) * sigma[:, None, None, None]) \
        & (here[:, :, lo:k, None] | here[:, :, None, k:])
    out = {}
    for l, t, a, b in sorted(zip(*np.nonzero(ok)),
                             key=lambda i: gap[i[0], i[1], i[2], i[3]]):
        out.setdefault((int(l), int(t)), []).append((lo + int(a),
                                                     k + int(b)))
    return out, sigma


def check_generation(params, model: dict, share: dict, prompt, generated,
                     pad_to: int, max_rows: int, what: str,
                     program=None) -> dict:
    """Teacher-forced check of one served request (see the module's text):
    each of the first ``max_rows`` generated tokens against the float32
    reference under the position's legitimate routings and, with
    ``program`` (tokens [pad_to] -> the program's own logits [pad_to, V]
    of that sequence), the program's logits against the reference's where
    no trade is legitimate. Returns the numbers compared: {"checked",
    "exact", "ties", "worst_sigmas" (over the tokens within the band or
    explained), "explained" (by a trade), "beyond", "unjudged",
    "router_sigma", "passes", "logit_errors" (one a clean position, in
    units of the reference's own bf16 error there)}; the caller sums them
    over a run's requests and holds the shares and the median error to
    their limits."""
    prompt = np.asarray(prompt, np.int32)
    gen = np.asarray(generated, np.int32)
    n = min(len(gen), max_rows)
    seq = np.zeros((pad_to,), np.int32)
    seq[: len(prompt)] = prompt
    seq[len(prompt): len(prompt) + n - 1] = gen[: n - 1]
    rows = np.full((max_rows,), len(prompt) - 1, np.int32)
    rows[:n] = len(prompt) - 1 + np.arange(n)
    k = model["num_experts_per_tok"]
    a32, r32 = logits_at(params, seq, rows, model, share, True)
    b16, r16 = logits_at(params, seq, rows, model, share, False,
                         forced=np.asarray(r32["picks"]))
    trades, router_sigma = trades_allowed(
        r32, r16, k, tuple(share["held_experts"]))
    a32, b16 = np.asarray(a32)[:n], np.asarray(b16)[:n]
    sigma = np.sqrt(np.mean((b16 - a32) ** 2, axis=-1))
    at = np.arange(n)

    def sigmas(f32):
        f32 = np.asarray(f32)[:n]
        return (f32.max(axis=-1) - f32[at, gen[:n]]) / (np.sqrt(2.0) * sigma)

    sig = sigmas(a32)
    own = sig.copy()
    n_exp = np.asarray(r32["top"]).shape[0]
    logit_errors = []
    if program is not None:
        # positions whose token has no legitimate trade in any layer: there
        # the program's logits are the reference's but for rounding
        clean = [p for p in range(n) if not any(
            (l, int(rows[p])) in trades for l in range(n_exp))]
        got = np.asarray(program(seq))[rows[clean]]
        logit_errors = (np.sqrt(np.mean((got - a32[clean]) ** 2, axis=-1))
                        / sigma[clean]).tolist()
    # a failing position's candidates: one trade, then two in different
    # layers; candidate i of EVERY failing position rides pass i
    todo = {}
    for p in np.flatnonzero(sig > TIE_SIGMAS):
        t = int(rows[p])
        ones = [[(l, ab)] for l in range(n_exp) for ab in trades.get((l, t), [])]
        twos = [x + y for i, x in enumerate(ones) for y in ones[i + 1:]
                if x[0][0] != y[0][0]]
        todo[int(p)] = ones + twos
    layers = {p: len({c[0][0] for c in cands if len(c) == 1})
              for p, cands in todo.items()}
    passes = 0
    while passes < PASSES_MAX and any(len(c) > passes for c in todo.values()):
        trade = np.full((n_exp, pad_to, 2), -1, np.int32)
        tried = [p for p, c in todo.items() if len(c) > passes]
        for p in tried:
            for l, ab in todo[p][passes]:
                trade[l, rows[p]] = ab
        new = sigmas(logits_at(params, seq, rows, model, share, True,
                               trade=trade)[0])
        for p in tried:
            sig[p] = min(sig[p], new[p])
        passes += 1
        todo = {p: c for p, c in todo.items() if sig[p] > TIE_SIGMAS}
    unjudged = [p for p in todo if layers[p] >= 3]
    beyond = [p for p in todo if layers[p] < 3]
    for p in beyond:
        print(f"chipbench: {what}: generated position {p}: token "
              f"{int(gen[p])} is {sig[p]:.2f} pair-sigmas below the best of "
              f"the float32 reference (its own routing: {own[p]:.2f}, "
              f"{int(a32[p].argmax())}) under every legitimate routing "
              f"tried ({layers[p]} layers with trades; sigma "
              f"{float(sigma[p]):.5f}) - not a bf16 tie", file=sys.stderr)
    inside = np.ones(n, bool)
    inside[list(todo)] = False
    exact = int(np.sum(a32.argmax(axis=-1) == gen[:n]))
    return {"checked": n, "exact": exact, "ties": n - exact,
            "worst_sigmas": float(sig[inside].max()) if inside.any() else 0.0,
            "explained": int(np.sum(inside & (own > TIE_SIGMAS))),
            "beyond": len(beyond), "unjudged": len(unjudged),
            "beyond_worst_sigmas": float(max((sig[p] for p in beyond),
                                             default=0.0)),
            "router_sigma": float(router_sigma.max()), "passes": passes,
            "logit_errors": logit_errors}
