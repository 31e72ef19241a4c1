"""Unified page-indirect ragged attention — one launch, mixed phases.

The paged extension of ``decode_attention.py`` (the Ragged Paged
Attention design, PAPERS.md #1): KV lives in a flat pool of fixed-size
pages and each slot's sequence is the concatenation of the pages its
int32 page table names. The pool is read WHERE IT LIES: at rest it is
``[L, num_pages, page_size, Hkv*D]`` (``llama.init_paged_pool``) and
the model's layer loop hands the kernel the whole stacked pool plus the
layer's index; a single layer's ``[num_pages, page_size, Hkv*D]`` pool
is served by the same call without ``layer``. The kernel
serves **prefill chunks and decode ticks in the same launch**: slot
``b`` carries ``q_len[b]`` query rows (1 = a decode tick, >1 = a
prefill chunk, 0 = a free slot) whose row ``t`` sits at absolute
position ``ctx_len[b] + t`` and attends keys ``[0, ctx_len[b] + t]``.

The kernel fetches its pages BY HAND, and only the pages a slot holds:

- the grid is over slots, nothing in it scales with the table's width.
  Both pool planes stay in HBM (``memory_space=ANY``); the page table,
  context lengths, chunk widths and the layer index are
  SCALAR-PREFETCHED.
- inside a slot a ``fori_loop`` runs over blocks of N pages up to the
  slot's last NEEDED page (``pages_read``). A block's pages are brought
  into a double-buffered VMEM scratch by one ``make_async_copy`` a page
  and plane, ``pool[layer, table[b, j]]`` -> rows ``[p*page_size,
  (p+1)*page_size)`` of the buffer, and the next block's copies (after
  a slot's last block: the next slot's first) are in flight while this
  one is computed. A slot's HBM reads are its
  ``pages_read`` pages and nothing else; a free slot (``q_len`` 0)
  copies nothing and computes nothing.
- N follows the shapes the kernel is traced with: ``N * page_size``
  key rows fill the MXU's 128 lanes, twice that where the query block
  is small (``_block_pages``). The loops over
  blocks and over a block's pages are rolled (``fori_loop``), so what
  is traced, lowered and loaded does not grow with the table's width
  nor with N; only the per-kv-head matmuls are unrolled.
- masking is in VIRTUAL coordinates: key row ``r`` of page slot ``j``
  is position ``j*page_size + r`` whichever physical page backs it.
  Rows of a block's buffer that no copy of this slot wrote (the tail
  past its last page) are masked the same way; the V buffer starts as
  zeros so that what lies there is always finite.

Query layout: the wrapper permutes q to kv-head-major
``[B, Hkv*Tq*rep, D]`` rows (``row = h*Tq*rep + t*rep + r`` — for
Tq == 1 exactly the grouped-GQA row order of the decode kernel), so
each kv head's queries are one contiguous row block and the repeated
cache is never materialised. fp32 online-softmax state (running
max/sum + accumulator) lives in VMEM scratch across a slot's blocks;
after the last one the slot's output is normalised and written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
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
    ``ctx_len + q_len - 1`` (keys [0, end] visible -> end//page + 1), and
    none for a slot with no query row. The kernel's loop over a slot's
    pages runs to exactly this bound."""
    return ((ctx_len + q_len - 1) // page_size + 1) * (q_len > 0)


def _block_pages(page_size: int, max_pages: int, q_rows: int) -> int:
    """Pages a block of the kernel's loop holds: as many as give the
    score tile 128 key rows (the MXU's lanes) — 256 while the float32
    tile of ``q_rows`` x key rows stays within 1 MiB of VMEM, which
    halves a decode tick's trips round the loop — and never more than
    the table names."""
    key_rows = 256 if q_rows * 256 * 4 <= 2 ** 20 else 128
    return max(1, min(key_rows // page_size, max_pages))


def _make_kernel(Hkv: int, D: int, Tq: int, rep: int, psz: int, N: int,
                 max_pages: int):
    TR = Tq * rep                     # query rows per kv head
    R = Hkv * TR
    KB = N * psz                      # key rows a block

    def kernel(pt_ref, ctx_ref, qlen_ref, lay_ref, q_ref, k_hbm, v_hbm,
               o_ref, kbuf, vbuf, sem, first_ref, acc_ref, m_ref, l_ref):
        # scalar control in lax primitives on int32 that is never
        # negative: ``//`` and ``%`` on a tracer each trace a function
        # of a dozen equations, at every start of the program
        b, slots = pl.program_id(0), pl.num_programs(0)
        ctx, qlen, lay = ctx_ref[b], qlen_ref[b], lay_ref[0]

        def held(slot):
            """``pages_read`` of ``slot``, inside the table (a chunk's
            padding rows may reach past its end)."""
            n = lax.div(ctx_ref[slot] + qlen_ref[slot] + (psz - 1), psz)
            return lax.select(qlen_ref[slot] > 0, lax.min(n, max_pages), 0)

        # this slot's pages, and those of the slot whose first block is
        # copied while this one's last is computed (none past the end)
        mine = held(b)
        nb = lax.min(b + 1, slots - 1)
        theirs = lax.select(b + 1 < slots, held(nb), 0)
        n_blocks = lax.div(mine + (N - 1), N)

        def each_page(slot, n_held, i, buf, act):
            """``act`` on both planes' copies of the pages ``slot`` holds
            (``n_held``) of its block ``i``, into buffer ``buf``."""
            def page(p, carry):
                src = pt_ref[slot, i * N + p]
                rows = pl.ds(pl.multiple_of(p * psz, psz), psz)
                for hbm, vmem in ((k_hbm, kbuf), (v_hbm, vbuf)):
                    act(pltpu.make_async_copy(
                        hbm.at[lay, src], vmem.at[buf, rows], sem.at[buf]))
                return carry

            lax.fori_loop(0, lax.min(n_held - i * N, N), page, 0)

        @pl.when(b == 0)
        def _():
            # p = 0 times whatever lies past a slot's last page must be
            # 0: from here on the buffer holds zeros or pool rows
            vbuf[...] = jnp.zeros_like(vbuf)
            first_ref[0] = 0

        # a slot's first block is in flight when the slot begins, in
        # buffer ``first``: the slot before it started the copies. Slot
        # 0 starts its own, and a free slot, whose loop below never
        # runs, the next slot's.
        first = first_ref[0]
        own = n_blocks > 0

        @pl.when((b == 0) | ~own)
        def _():
            each_page(lax.select(own, b, nb), lax.select(own, mine, theirs),
                      0, first, lambda c: c.start())

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

        def block(i, carry):
            buf = lax.rem(first + i, 2)
            # what is computed next — this slot's next block or, after
            # its last, the next slot's first — is copied meanwhile
            last = i + 1 == n_blocks
            each_page(lax.select(last, nb, b),
                      lax.select(last, theirs, mine),
                      lax.select(last, 0, i + 1), 1 - buf,
                      lambda c: c.start())
            each_page(b, mine, i, buf, lambda c: c.wait())
            q = q_ref[0]              # [Hkv*TR, D], PRE-SCALED, h-major
            parts = []
            for h in range(Hkv):
                kh = kbuf[buf, :, h * D:(h + 1) * D]      # [KB, D]
                qh = q[h * TR:(h + 1) * TR]               # [TR, D]
                parts.append(lax.dot_general(
                    qh, kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32))
            s = lax.concatenate(parts, 0)                 # [Hkv*TR, KB]
            # virtual key position of this block's rows vs the per-row
            # query position ctx + t (t = (row % TR) // rep); a padding
            # row (t >= q_len) sees what the last live row sees, so no
            # row looks past the pages that were fetched
            kpos = i * KB + lax.broadcasted_iota(jnp.int32, (R, KB), 1)
            t = lax.div(lax.rem(
                lax.broadcasted_iota(jnp.int32, (R, KB), 0), TR), rep)
            s = lax.select(kpos <= ctx + lax.min(t, qlen - 1), s,
                           lax.full_like(s, -jnp.inf))
            m_prev = m_ref[:, :1]
            m_new = lax.max(m_prev, lax.expand_dims(
                lax.reduce_max(s, (1,)), (1,)))
            p = lax.exp(s - m_new)
            alpha = lax.exp(m_prev - m_new)  # block 0: exp(-inf - m) = 0
            l_new = l_ref[:, :1] * alpha + lax.expand_dims(
                lax.reduce_sum(p, (1,)), (1,))
            pb = p.astype(vbuf.dtype)
            pv_parts = []
            for h in range(Hkv):
                vh = vbuf[buf, :, h * D:(h + 1) * D]      # [KB, D]
                ph = pb[h * TR:(h + 1) * TR]              # [TR, KB]
                pv_parts.append(lax.dot_general(
                    ph, vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            acc_ref[...] = acc_ref[...] * alpha + lax.concatenate(
                pv_parts, 0)                              # [Hkv*TR, D]
            m_ref[...] = lax.broadcast_in_dim(m_new, m_ref.shape, (0, 1))
            l_ref[...] = lax.broadcast_in_dim(l_new, l_ref.shape, (0, 1))
            return carry

        lax.fori_loop(0, n_blocks, block, 0)
        first_ref[0] = lax.rem(first + n_blocks, 2)
        # every live slot's rows have key 0 visible, so l > 0 there; a
        # free slot (no block ran) writes zeros
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)

    return kernel


def ragged_paged_attention(q, kp, vp, page_table, ctx_len, q_len=None,
                           scale=None, interpret: bool = False,
                           layer=None, name: str = "ragged_paged_attention"):
    """Attention over a paged KV pool, mixed prefill/decode in one call.

    q: [B, Tq, nH, D] query chunks (row t of slot b sits at absolute
    position ``ctx_len[b] + t``; rows past ``q_len[b]`` are padding and
    produce outputs the caller discards: they attend what the slot's
    last live row attends, and a slot with ``q_len`` 0 returns zeros).
    kp/vp: the page pool in its layout at rest, already holding the
    chunk's own K/V rows (the caller scatters before attending, the same
    contract as the contiguous cache) — with ``layer`` (an int32 scalar,
    traced or not) the whole stacked ``[L, P, page_size, Hkv*D]`` pool,
    of which the kernel reads layer ``layer``'s pages and nothing else;
    without it one layer's ``[P, page_size, Hkv*D]``. The pool is never
    reshaped here: any other rank raises. page_table: [B, max_pages]
    int32 physical page ids per virtual page slot. ctx_len: [B] rows
    already in the cache before this chunk. q_len: [B] live rows per
    chunk (None = all Tq). Returns [B, Tq, nH, D] in q.dtype. Raises on
    untileable shapes — callers gate with ``paged_attention_active``.
    ``name``: the kernel's name in a device trace (a model that calls it
    over two kinds of cache gives each call its own).
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
    R = Hkv * Tq * rep
    N = _block_pages(psz, max_pages, R)
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    if q_len is None:
        q_len = jnp.full((B,), Tq, jnp.int32)
    # h-major query rows: row = h*Tq*rep + t*rep + r (Tq==1 reduces to
    # the decode kernel's grouped-GQA order); scale folded in outside
    qs = (q * scale).astype(q.dtype)
    qh = qs.reshape(B, Tq, Hkv, rep, D).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(B, R, D)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, R, D), lambda b, *_: (b, 0, 0)),
                  in_hbm, in_hbm],
        out_specs=pl.BlockSpec((1, R, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, N * psz, HD), kp.dtype),    # K blocks, 2 deep
            pltpu.VMEM((2, N * psz, HD), vp.dtype),    # V blocks
            pltpu.SemaphoreType.DMA((2,)),             # one a buffer
            pltpu.SMEM((1,), jnp.int32),       # the next first block's
            pltpu.VMEM((R, D), jnp.float32),           # accumulator
            pltpu.VMEM((R, 128), jnp.float32),         # running max
            pltpu.VMEM((R, 128), jnp.float32),         # running sum
        ],
    )
    out = pl.pallas_call(
        _make_kernel(Hkv, D, Tq, rep, psz, N, max_pages),
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, D), q.dtype),
        # slots in order on one core: the V buffer is zeroed at slot 0
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
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
