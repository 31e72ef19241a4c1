"""Pallas kernel for the lm_head activation gradient (the CE-tail dx).

The softmax part of dx is ``dh = (softmax(logits) * gs) @ W^T``. XLA's
emitter runs this [M,V]x[V,H] contraction at ~60-77% of MXU peak (narrow
N = hidden), and the fast transpose orientation cannot be reached from
XLA: a transposed read of the fused softmax operand forces a 2.9 GB fp32
materialisation of convert(logits) (measured +8.5 ms/step — r5 ledger in
ARCHITECTURE.md). This kernel gets both properties at once, by
construction:

- logits tiles stream in their NATURAL [M, V] layout; the softmax
  (exp(l - m) * (gs / se)) is computed in-kernel in fp32 — "fusion" is
  guaranteed, nothing materialises;
- each tile-dot is [bm, bk] x [bk, H] against a PRE-TRANSPOSED W
  (``wt = W.T`` — one 49 MB transpose outside the kernel), K-innermost
  with an fp32 VMEM accumulator, so the MXU pipeline stays full
  regardless of XLA's narrow-N tiling heuristics.

The one-hot (gold-label) term of dx is a cheap gather of W columns and
stays OUTSIDE the kernel (see llama._head_ce_tail_bwd).

M need not divide bm: out-of-bounds stores are masked by pallas, and the
scale vector is zero-padded while the exponent is clamped at 0 (for real
rows l - m <= 0 anyway, m being the row max), so ragged-edge garbage
contributes exact zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dx_kernel(l_ref, m_ref, c_ref, wt_ref, o_ref, acc_ref):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lf = l_ref[...].astype(jnp.float32)
    # clamp at 0: exact for real rows (m is the row max), kills overflow
    # from ragged-edge garbage (scaled by c = 0 afterwards)
    p = jnp.exp(jnp.minimum(lf - m_ref[...], 0.0)) * c_ref[...]
    acc_ref[...] += jnp.dot(p.astype(l_ref.dtype), wt_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(v == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def head_dx_softmax(logits, m, scale, wt, bm: int = 1408, bk: int = 512):
    """``(exp(logits - m) * scale[:, None]) @ wt`` with wt = W^T [V, H].

    logits [M, V] bf16; m, scale [M] fp32 (scale = gs * weight / sumexp —
    per-row weights, incl. zeros, fold in for free). Returns [M, H] in
    logits.dtype. Prefer M a multiple of bm: pallas materialises a
    PADDED COPY of the logits otherwise (~6.7 ms at the bench shape).
    """
    M, V = logits.shape
    H = wt.shape[1]
    # pick the largest candidate bm that DIVIDES M: a ragged M makes
    # pallas materialise a padded copy of the whole logits tensor
    # (measured 6.7 ms at the bench shape), which costs more than any
    # block-size preference. Candidates stay within the VMEM budget
    # (acc bm x H fp32 + double-buffered tiles < 16 MB at H<=1024).
    bm = next((b for b in (bm, 1024, 512, 256, 128) if M % b == 0), bm)
    bk = min(bk, V)
    while bk > 8 and V % bk:
        bk //= 2
    if M % bm or V % bk or bm % 8 or bk % 128:
        # shapes the blocked kernel can't tile cleanly (tiny/ragged M or
        # V) take the XLA formulation — an empty grid dim (e.g. V < bk)
        # would silently never write out, and a ragged M would pad-copy
        p = jnp.exp(logits.astype(jnp.float32)
                    - m[:, None]) * scale[:, None]
        return (p.astype(logits.dtype) @ wt).astype(logits.dtype)
    grid_m = -(-M // bm)
    m_pad = jnp.zeros((grid_m * bm, 1), jnp.float32).at[:M, 0].set(m)
    c_pad = jnp.zeros((grid_m * bm, 1), jnp.float32).at[:M, 0].set(scale)
    out = pl.pallas_call(
        _dx_kernel,
        name="head_dx_softmax",
        grid=(grid_m, V // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, v: (i, v)),
            pl.BlockSpec((bm, 1), lambda i, v: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, v: (i, 0)),
            pl.BlockSpec((bk, H), lambda i, v: (v, 0)),
        ],
        out_specs=pl.BlockSpec((bm, H), lambda i, v: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, H), logits.dtype),
        scratch_shapes=[pltpu.VMEM((bm, H), jnp.float32)],
    )(logits, m_pad, c_pad, wt)
    return out
