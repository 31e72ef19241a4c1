"""Unified page-indirect ragged attention — one launch, mixed phases.

The paged extension of ``decode_attention.py`` (the Ragged Paged
Attention design, PAPERS.md #1): KV lives in a flat pool of fixed-size
pages and each slot's sequence is the concatenation of the pages its
int32 page table names. The pool is read WHERE IT LIES: at rest it is
``[L, num_pages, page_size, Hkv*D]`` (``llama.init_paged_pool`` — the
minor dimension the kernel's blocks have, so nothing re-tiles it) and
the model's layer loop hands the kernel the whole stacked pool plus the
layer's index; a single layer's ``[num_pages, page_size, Hkv*D]`` pool
is served by the same call without ``layer``. The kernel
serves **prefill chunks and decode ticks in the same launch**: slot
``b`` carries ``q_len[b]`` query rows (1 = a decode tick, >1 = a
prefill chunk) whose row ``t`` sits at absolute position
``ctx_len[b] + t`` and attends keys ``[0, ctx_len[b] + t]``.

Page indirection and raggedness are BOTH BlockSpec index-map facts:

- grid = (slot, page-slot) with the page tables, context lengths,
  chunk widths and the layer index SCALAR-PREFETCHED. The K/V index
  map clamps the page slot at the slot's last *needed* page and then
  routes it through the page table — so the pipeline fetches block
  ``(layer, table[b, min(j, last)])``: per-slot KV HBM reads scale with
  ``ctx+q_len`` (position), not the table width, and a page-table hop
  costs zero extra DMAs (the indirection happens in index arithmetic
  the Mosaic pipeline already does).
- grid steps past the clamp re-name the SAME physical page, so the
  HBM→VMEM copy is elided; compute is skipped with ``pl.when``. The
  grid itself stays static — nothing recompiles as sequences grow or
  page tables change.
- masking is in VIRTUAL coordinates: the key row ``r`` of page slot
  ``j`` is position ``j*page_size + r`` regardless of which physical
  page backs it.

Query layout: the wrapper permutes q to kv-head-major
``[B, Hkv*Tq*rep, D]`` rows (``row = h*Tq*rep + t*rep + r`` — for
Tq == 1 exactly the grouped-GQA row order of the decode kernel), so
each kv head's queries are one contiguous row block and the repeated
cache is never materialised. fp32 online-softmax state (running
max/sum + accumulator) lives in VMEM scratch across the page-slot grid
steps; the last step normalises and writes the slot's output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["ragged_paged_attention", "paged_attention_active",
           "pages_read"]

# tests set this True (via monkeypatch) to force the kernel — in pallas
# interpret mode — on the CPU backend, so parity runs where tier-1 runs
FORCE_INTERPRET = False


def pages_read(ctx_len, q_len, page_size: int):
    """Pages the kernel fetches for a slot whose chunk ends at position
    ``ctx_len + q_len - 1`` (keys [0, end] visible -> end//page + 1).
    The analytic half of the pages-per-tick evidence; the clamp in the
    BlockSpec index map below is what enforces it."""
    return (ctx_len + q_len - 1) // page_size + 1


def _make_kernel(nH: int, Hkv: int, D: int, Tq: int, psz: int,
                 n_blocks: int):
    rep = nH // Hkv
    TR = Tq * rep                     # query rows per kv head

    def kernel(pt_ref, ctx_ref, qlen_ref, lay_ref, q_ref, k_ref, v_ref,
               o_ref, acc_ref, m_ref, l_ref):
        b = pl.program_id(0)
        j = pl.program_id(1)
        ctx = ctx_ref[b]
        last = (ctx + qlen_ref[b] - 1) // psz   # last needed page slot

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        # page slots past the clamp: the index map already re-fetched
        # nothing (same physical page as the previous step); skip compute
        @pl.when(j <= last)
        def _():
            q = q_ref[0]              # [Hkv*TR, D], PRE-SCALED, h-major
            parts = []
            for h in range(Hkv):
                kh = k_ref[0, :, h * D:(h + 1) * D]       # [psz, D]
                qh = q[h * TR:(h + 1) * TR]               # [TR, D]
                parts.append(jax.lax.dot_general(
                    qh, kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32))
            s = jnp.concatenate(parts, axis=0)            # [Hkv*TR, psz]
            # virtual key position of this page slot's rows vs the
            # per-row query position ctx + t (t = (row % TR) // rep)
            kpos = j * psz + jax.lax.broadcasted_iota(
                jnp.int32, (Hkv * TR, psz), 1)
            t = (jax.lax.broadcasted_iota(
                jnp.int32, (Hkv * TR, psz), 0) % TR) // rep
            s = jnp.where(kpos <= ctx + t, s, -jnp.inf)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)  # page 0: exp(-inf - m) = 0
            l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1,
                                                   keepdims=True)
            pb = p.astype(v_ref.dtype)
            pv_parts = []
            for h in range(Hkv):
                vh = v_ref[0, :, h * D:(h + 1) * D]       # [psz, D]
                ph = pb[h * TR:(h + 1) * TR]              # [TR, psz]
                pv_parts.append(jax.lax.dot_general(
                    ph, vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(
                pv_parts, axis=0)                         # [Hkv*TR, D]
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(j == n_blocks - 1)
        def _():
            # every query row has key 0 visible (ctx + t >= 0), so
            # l >= exp(s_0 - m) > 0 — padding rows included
            o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)

    return kernel


def ragged_paged_attention(q, kp, vp, page_table, ctx_len, q_len=None,
                           scale=None, interpret: bool = False,
                           layer=None):
    """Attention over a paged KV pool, mixed prefill/decode in one call.

    q: [B, Tq, nH, D] query chunks (row t of slot b sits at absolute
    position ``ctx_len[b] + t``; rows past ``q_len[b]`` are padding and
    produce garbage outputs the caller discards). kp/vp: the page pool
    in its layout at rest, already holding the chunk's own K/V rows (the
    caller scatters before attending, the same contract as the
    contiguous cache) — with ``layer`` (an int32 scalar, traced or not)
    the whole stacked ``[L, P, page_size, Hkv*D]`` pool, of which the
    kernel reads layer ``layer``'s pages and nothing else; without it
    one layer's ``[P, page_size, Hkv*D]``. The pool is never reshaped
    here: any other rank raises. page_table: [B, max_pages] int32
    physical page ids per virtual page slot. ctx_len: [B] rows already
    in the cache before this chunk. q_len: [B] live rows per chunk
    (None = all Tq). Returns [B, Tq, nH, D] in q.dtype. Raises on
    untileable shapes — callers gate with ``paged_attention_active``.
    """
    B, Tq, nH, D = q.shape
    if kp.ndim != (3 if layer is None else 4) or vp.shape != kp.shape:
        raise ValueError(
            f"paged kernel reads the pool where it lies: [L, P, psz, "
            f"Hkv*D] with a layer, [P, psz, Hkv*D] without, got "
            f"k{tuple(kp.shape)} v{tuple(vp.shape)} layer={layer}")
    if layer is None:      # one layer is a stack of one (a leading unit
        kp, vp, layer = kp[None], vp[None], 0   # dim re-tiles nothing)
    psz, HD = kp.shape[-2:]
    max_pages = page_table.shape[1]
    _selected["count"] += 1  # trace-time: once per compiled program
    if psz % 8 or HD % 128 or HD % D or nH % (HD // D):
        raise ValueError(
            f"paged kernel needs page_size%8==0 and lane-aligned KV "
            f"minor dim, got psz={psz} Hkv*D={HD} — gate callers "
            f"with paged_attention_active")
    Hkv = HD // D
    rep = nH // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    if q_len is None:
        q_len = jnp.full((B,), Tq, jnp.int32)
    # h-major query rows: row = h*Tq*rep + t*rep + r (Tq==1 reduces to
    # the decode kernel's grouped-GQA order); scale folded in outside
    qs = (q * scale).astype(q.dtype)
    qh = qs.reshape(B, Tq, Hkv, rep, D).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(B, Hkv * Tq * rep, D)

    def kv_map(b, j, pt_ref, ctx_ref, qlen_ref, lay_ref):
        # clamp at the slot's last needed page slot, then route through
        # the page table: past the clamp the SAME physical page repeats
        # and Mosaic skips the HBM->VMEM copy — these two index hops are
        # the entire "paged + ragged" property
        last = (ctx_ref[b] + qlen_ref[b] - 1) // psz
        return (lay_ref[0], pt_ref[b, jnp.minimum(j, last)], 0, 0)

    # the kernel sees [1, psz, Hkv*D]: the BlockSpec squeezes the layer
    kv_block = (None, 1, psz, HD)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Hkv * Tq * rep, D),
                         lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec(kv_block, kv_map),
            pl.BlockSpec(kv_block, kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hkv * Tq * rep, D),
                               lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv * Tq * rep, D), jnp.float32),    # accumulator
            pltpu.VMEM((Hkv * Tq * rep, 128), jnp.float32),  # running max
            pltpu.VMEM((Hkv * Tq * rep, 128), jnp.float32),  # running sum
        ],
    )
    out = pl.pallas_call(
        _make_kernel(nH, Hkv, D, Tq, psz, max_pages),
        name="ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv * Tq * rep, D), q.dtype),
        interpret=interpret or (FORCE_INTERPRET and not _on_tpu()),
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(ctx_len, jnp.int32),
      jnp.asarray(q_len, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), qh, kp, vp)
    # back from h-major rows to [B, Tq, nH, D]
    return out.reshape(B, Hkv, Tq, rep, D).transpose(0, 2, 1, 3, 4) \
              .reshape(B, Tq, nH, D)


# trace-time selection counter: incremented when a paged forward
# actually routes attention to the kernel (each jit compile traces
# once), so tests and the serving lane can assert kernel selection for
# a program without a chip
_selected = {"count": 0}


def selection_count() -> int:
    return _selected["count"]


def reset_selection_count() -> None:
    _selected["count"] = 0


def _on_tpu() -> bool:
    from .flash_attention import _on_tpu as on_tpu

    return on_tpu()


def paged_attention_active(page_size: int, num_heads: int,
                           num_kv_heads: int, head_dim: int) -> bool:
    """True when the unified paged kernel serves this pool shape: TPU
    (or the test force), kernels enabled, single-device, lane-aligned
    flat KV minor dim, sublane-aligned page size — the same
    dispatch/fallback contract as ``decode_attention_active`` (CPU and
    unaligned shapes take the gather + dense path)."""
    from .flash_attention import _multi_device_mesh_active

    f = flags.get_flags(["use_pallas_kernels", "use_paged_attention"])
    if not (f["use_pallas_kernels"] and f["use_paged_attention"]):
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    if num_heads % num_kv_heads:
        return False
    if (num_kv_heads * head_dim) % 128 or head_dim % 8:
        return False
    return page_size % 8 == 0
