"""Causal attention of an admission over ITS OWN ROWS, with an optional
sliding window — the prefill half of a decoder whose layers differ by kind
(``models/hybrid_moe.py``: window layers beside full ones).

An admission of this family starts at position 0 with nothing cached, so
its T query rows attend keys among the same T rows: key ``s`` is seen by
query ``t`` iff ``0 <= t - s`` and, with ``window`` W, ``t - s < W``. The
caller writes the rows to their cache afterwards (row pages for a full
layer, the last W rows to the sequence's fixed part for a window layer);
this kernel reads no cache.

- **Grouped queries, unrepeated keys.** K and V stay as the projections
  produce them and as the cache stores them, ``[B, T, Hkv*D]``; a grid step
  (b, kv head h, query block i) takes the head's lane block ``[T, D]``
  whole into VMEM (1 MB at T 4096, re-used by every query block of the
  head) and the ``rep`` query heads it serves as ONE row block of
  ``rep * block_q`` rows (row ``r * block_q + i``: head ``h * rep + r``,
  query ``i`` of the block), so each key block is multiplied once for the
  whole group.
- **Blocks outside the mask are skipped, not masked.** The loop over key
  blocks runs from the block that holds the first key of the block's first
  row's window to the diagonal: ``ceil(block_q / block_k) + 1`` blocks of a
  window layer whatever T is (its work is O(T x W)), the lower triangle of
  a full layer. No ``[T, T]`` array exists anywhere.
- fp32 online softmax in VMEM scratch. A row whose window starts past the
  first visited block has seen nothing there: its running maximum is still
  ``-inf`` and is read as 0, so that block adds 0 to it and not NaN.

- **The bucket's padding costs a store.** The prompt's length
  (``n_valid``) is a prefetched scalar: a query block that starts at or
  past it takes no trip through the keys and returns 0 (finite: the rows
  go on through the layer). The padding rows of the block the prompt ends
  in are ordinary causal rows; the caller drops their output.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["windowed_prefill_attention", "windowed_prefill_active",
           "xla_windowed_attention"]

# tests set this True to force the kernel (pallas interpret mode) on CPU
FORCE_INTERPRET = False

BLOCK = 128                     # query rows and key rows of a block
VMEM_LIMIT = 64 * 2**20


def xla_windowed_attention(q, k, v, window: Optional[int] = None):
    """The same attention as one masked softmax: q [B, T, nH, D], k / v
    [B, T, Hkv, D]. Materialises [B, nH, T, T]: the reference, and the
    path of shapes the kernel does not tile."""
    B, T, nH, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, nH // Hkv, D)
    s = jnp.einsum("bthrd,bshd->bhrts", qg, k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = dist >= 0
    if window is not None:
        seen = seen & (dist < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhrts,bshd->bthrd", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(B, T, nH, D)


def _make_kernel(bq: int, bk: int, rep: int, window: Optional[int]):
    R = rep * bq

    def kernel(n_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        i = pl.program_id(2)
        first = i * bq                        # the block's first query
        n = n_ref[pl.program_id(0)]           # the sequence's valid rows

        @pl.when(first >= n)
        def _padding():
            # a whole block of the bucket's padding: nobody reads it; a
            # finite value, because its rows go on through the layer
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(first < n)
        def _attend():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            q = q_ref[...]                    # [R, D], PRE-SCALED
            hi = lax.div(first + bq + (bk - 1), bk)     # past the diagonal
            lo = 0 if window is None else \
                lax.div(lax.max(first - (window - 1), 0), bk)
            # a row's query position; rows are (head of the group, query)
            qpos = first + lax.rem(
                lax.broadcasted_iota(jnp.int32, (R, bk), 0), bq)

            # EVERY block takes the mask's select: one loop body. Leaving
            # it off the blocks wholly inside the mask (a second loop and
            # the diagonal on its own) measured 8 % SLOWER on the v5e
            def block(j, carry):
                rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
                s = lax.dot_general(q, k_ref[rows, :],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                dist = qpos - (j * bk + lax.broadcasted_iota(
                    jnp.int32, (R, bk), 1))
                seen = dist >= 0
                if window is not None:
                    seen = seen & (dist < window)
                s = lax.select(seen, s, lax.full_like(s, -jnp.inf))
                m_prev = m_ref[:, :1]
                m_new = lax.max(m_prev, lax.expand_dims(
                    lax.reduce_max(s, (1,)), (1,)))
                # nothing seen yet: the maximum is read as 0 and the block
                # adds exp(-inf) = 0
                m_use = lax.select(m_new == -jnp.inf,
                                   lax.full_like(m_new, 0.0), m_new)
                p = lax.exp(s - m_use)
                alpha = lax.exp(m_prev - m_use)
                l_new = l_ref[:, :1] * alpha + lax.expand_dims(
                    lax.reduce_sum(p, (1,)), (1,))
                acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[rows, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[...] = lax.broadcast_in_dim(m_new, m_ref.shape, (0, 1))
                l_ref[...] = lax.broadcast_in_dim(l_new, l_ref.shape, (0, 1))
                return carry

            lax.fori_loop(lo, hi, block, 0)
            # every row sees itself, so l > 0
            o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)

    return kernel


def windowed_prefill_attention(q, k, v, window: Optional[int] = None,
                               n_valid=None, *,
                               name: str = "windowed_prefill_attention",
                               block: int = 0, interpret: bool = False):
    """q [B, T, nH, D]; k, v [B, T, Hkv*D] (a cache row's kv heads side by
    side, as ``init_paged_pool`` stores them). Row t is at position t and
    attends keys ``max(0, t - window + 1) .. t`` (all of ``0 .. t`` without
    ``window``). ``n_valid`` [B] int32: rows from ``n_valid[b]`` on are
    padding nobody reads; whole query blocks of them are left out and
    return 0 (None: every row is computed). ``name`` is the kernel's name
    in a device trace (a model with two kinds of layer gives each its
    own). Returns [B, T, nH, D]. Raises on shapes that do not tile: gate
    with ``windowed_prefill_active``."""
    B, T, nH, D = q.shape
    HD = k.shape[-1]
    bq = bk = min(block or BLOCK, T)
    if k.shape != (B, T, HD) or v.shape != k.shape or HD % D \
            or nH % (HD // D) or T % bq:
        raise ValueError(
            f"windowed prefill needs q [B, T, nH, D] and k, v [B, T, "
            f"Hkv*D] with T a multiple of {bq}, got q{tuple(q.shape)} "
            f"k{tuple(k.shape)} v{tuple(v.shape)}")
    Hkv = HD // D
    rep = nH // Hkv
    nq, R = T // bq, rep * bq
    _selected["count"] += 1
    if n_valid is None:
        n_valid = jnp.full((B,), T, jnp.int32)
    # rows of a block: (head of the group, query), the group's heads
    # against one kv head's keys
    qs = (q * (1.0 / np.sqrt(D))).astype(q.dtype)
    qs = qs.reshape(B, nq, bq, Hkv, rep, D).transpose(0, 3, 1, 4, 2, 5)
    qs = qs.reshape(B, Hkv, nq, R, D)
    head = pl.BlockSpec((None, T, D), lambda b, h, i, n: (b, 0, h))
    rows = pl.BlockSpec((None, None, None, R, D),
                        lambda b, h, i, n: (b, h, i, 0, 0))
    out = pl.pallas_call(
        _make_kernel(bq, bk, rep, window), name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, Hkv, nq),
            in_specs=[rows, head, head], out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((R, D), jnp.float32),      # accumulator
                pltpu.VMEM((R, 128), jnp.float32),    # running max
                pltpu.VMEM((R, 128), jnp.float32)]),  # running sum
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nq, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret or (FORCE_INTERPRET and not _on_tpu()),
    )(jnp.asarray(n_valid, jnp.int32).reshape(B), qs, k, v)
    out = out.reshape(B, Hkv, nq, rep, bq, D).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(B, T, nH, D)


_selected = {"count": 0}


def selection_count() -> int:
    return _selected["count"]


def _on_tpu() -> bool:
    from .flash_attention import _on_tpu as on_tpu

    return on_tpu()


def windowed_prefill_active(rows: int, head_dim: int,
                            block: int = 0) -> bool:
    """True when an admission of ``rows`` rows runs as the kernel: TPU (or
    the test force, which takes any block of whole sublanes), kernels
    enabled, one device, whole blocks of rows and a lane-aligned head —
    else the masked softmax."""
    from .flash_attention import _multi_device_mesh_active

    block = block or BLOCK
    if not flags.get_flags(["use_pallas_kernels"])["use_pallas_kernels"]:
        return False
    if rows % min(block, rows):
        return False
    if FORCE_INTERPRET and not _on_tpu():
        return block % 8 == 0
    if not _on_tpu() or _multi_device_mesh_active():
        return False
    return block % 128 == 0 and head_dim % 128 == 0
