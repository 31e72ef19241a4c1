"""Latent (MLA) attention over a paged LATENT cache — one launch, mixed phases.

The cache of a latent-attention model holds ONE row a token a layer,
``[ckv | kr]`` (the normed compressed key/value and the one rotary key all
heads share), in ONE plane ``[L, num_pages, page_size, W]``
(``latent_moe.init_paged_pool``; ``W`` is the row lane-padded: 512 + 64 ->
640). Every query head attends that same row, in the ABSORBED form: the
caller folds the key up-projection into the query (``q_lat = q_nope W_uk``)
and the kernel computes

    s = [q_lat | q_rope | 0] . row            (one dot over W)
    o_lat = softmax(s, causal) . row[:rank]   (the value IS the latent)

so per (query token, cached row) it does ``heads * 2 * (W_true + rank)``
operations on one row's bytes, and the caller expands ``o_lat`` with
``W_uv``. ``ragged_paged_attention`` cannot compute this: it wants two
planes of ``Hkv x D`` rows.

The kernel fetches its pages BY HAND, and only the pages a query block
can see (``ragged_paged_attention``'s walk, PR 31, over one plane and a
second grid axis):

- **the grid is (slot, query block)**, nothing in it scales with the
  table's width. A decode tick is already ``heads`` rows a slot (128) and
  an admission of 512 positions is 65,536: a query block is ``q_tokens``
  whole tokens (t-major rows ``t * heads + h``) and the online-softmax
  state is per query block. The plane stays in HBM (``memory_space=ANY``)
  and is handed ONCE; the page table, context lengths, chunk widths and
  the layer index are scalar-prefetched.
- **inside a grid step** a rolled ``fori_loop`` runs over blocks of N
  pages up to the block's last NEEDED page (the page of the last LIVE
  query position of the block, inside the table; none for a block past
  the slot's ``q_len``, which does no work and writes zeros — never
  garbage: its rows go on through the layer and into the cache). A block's
  pages come into a double-buffered VMEM block by one ``make_async_copy``
  a page, ``pool[layer, table[b, j]]`` -> rows ``[p*page_size,
  (p+1)*page_size)``, and the next block's copies (after a step's last
  block: the next grid step's first) are in flight while this one is
  computed. The grid runs in order on one core (``"arbitrary"``).
- **N follows the shapes the kernel is traced with** (``_block_pages``:
  512 key rows under a decode tick's 128 query rows, 256 under an
  admission's 512; on the chip a block costs ~0.5 us whatever its width —
  the chain dot, softmax, dot does not overlap with the next block's —
  so smaller blocks lose more than the rows they skip win: PERF.md §6,
  PR 33). The loops over blocks and over a block's pages are rolled
  (the copies ``COPIES_UNROLLED`` a trip) and the scalar control is
  ``lax`` primitives on non-negative int32, so what is traced, lowered
  and loaded does not grow with the table's width nor with N.
- **masking is in VIRTUAL coordinates**: key row ``r`` of page slot ``j``
  is position ``j*page_size + r`` whichever physical page backs it. A
  padding row of a live block (``t >= q_len``) sees what the slot's last
  live row sees, so no row looks past the pages that were fetched. One
  plane serves keys and values, so the buffer is zeroed at the first grid
  step: what lies past a block's last page is zeros or an earlier block's
  rows, always finite, and ``p = 0`` times it is 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["mla_paged_attention", "mla_attention_active"]

# tests set this True (via monkeypatch) to force the kernel — in pallas
# interpret mode — on the CPU backend, so parity runs where tier-1 runs
FORCE_INTERPRET = False

QUERY_ROWS = 512       # query rows a block aims at (whole tokens)
COPIES_UNROLLED = 4    # page copies a trip of the copy loop


def _block_pages(page_size: int, max_pages: int, q_rows: int) -> int:
    """Pages a block of the kernel's loop holds: 512 key rows while the
    float32 score tile of ``q_rows`` x key rows stays within 256 KiB of
    VMEM (a decode tick's 128 rows a slot: half the trips round the loop),
    else 256, and never more than the table names."""
    key_rows = 512 if q_rows * 512 * 4 <= 2 ** 18 else 256
    return max(1, min(key_rows // page_size, max_pages))


def _make_kernel(nH: int, TB: int, psz: int, N: int, rank: int,
                 slots: int, n_qblocks: int, max_pages: int):
    RB = TB * nH
    KB = N * psz                      # key rows a block

    def kernel(pt_ref, ctx_ref, qlen_ref, lay_ref, q_ref, pool_hbm, o_ref,
               buf, sem, first_ref, acc_ref, m_ref, l_ref):
        # scalar control in lax primitives on int32 that is never
        # negative: ``//``, ``%`` and ``jnp.where`` on a tracer each trace
        # a function of a dozen equations, at every start of the program
        b, qi = pl.program_id(0), pl.program_id(1)
        ctx, qlen, lay = ctx_ref[b], qlen_ref[b], lay_ref[0]

        def needed(slot, blk):
            """Pages query block ``blk`` of ``slot`` can see: up to the
            page of its last live position, ``ctx + min((blk + 1) * TB,
            q_len) - 1``, inside the table; none past ``q_len``."""
            live = lax.min((blk + 1) * TB, qlen_ref[slot])
            n = lax.div(ctx_ref[slot] + live + (psz - 1), psz)
            return lax.select(blk * TB < qlen_ref[slot],
                              lax.min(n, max_pages), 0)

        # this step's pages, and those of the grid step whose first block
        # is copied while this one's last is computed (none past the end)
        mine = needed(b, qi)
        wrap = qi + 1 == n_qblocks
        nb = lax.select(wrap, lax.min(b + 1, slots - 1), b)
        nqi = lax.select(wrap, 0, qi + 1)
        theirs = lax.select(wrap & (b + 1 == slots), 0, needed(nb, nqi))
        n_blocks = lax.div(mine + (N - 1), N)

        def each_page(slot, n_held, i, half, act):
            """``act`` on the copies of the pages ``slot`` needs
            (``n_held``) of its block ``i``, into half ``half`` of the
            buffer: ``COPIES_UNROLLED`` a trip, then the rest one by one
            (a call's time is scalar work a copy as much as arithmetic)."""
            def one(p):
                rows = pl.ds(pl.multiple_of(p * psz, psz), psz)
                act(pltpu.make_async_copy(
                    pool_hbm.at[lay, pt_ref[slot, i * N + p]],
                    buf.at[half, rows], sem.at[half]))

            def several(g, carry):
                for u in range(COPIES_UNROLLED):
                    one(g * COPIES_UNROLLED + u)
                return carry

            def single(p, carry):
                one(p)
                return carry

            n = lax.min(n_held - i * N, N)
            whole = lax.div(n, COPIES_UNROLLED)
            lax.fori_loop(0, whole, several, 0)
            lax.fori_loop(whole * COPIES_UNROLLED, n, single, 0)

        first_step = (b == 0) & (qi == 0)

        @pl.when(first_step)
        def _():
            # p = 0 times whatever lies past a block's last page must be
            # 0: from here on the buffer holds zeros or pool rows
            buf[...] = jnp.zeros_like(buf)
            first_ref[0] = 0

        # a step's first block is in flight when the step begins, in half
        # ``first``: the step before it started the copies. The first
        # step starts its own, and a step that needs nothing, whose loop
        # below never runs, the next step's.
        first = first_ref[0]
        own = n_blocks > 0

        @pl.when(first_step | ~own)
        def _():
            each_page(lax.select(own, b, nb), lax.select(own, mine, theirs),
                      0, first, lambda c: c.start())

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

        def block(i, carry):
            half = lax.rem(first + i, 2)
            # what is computed next — this step's next block or, after
            # its last, the next step's first — is copied meanwhile
            last = i + 1 == n_blocks
            each_page(lax.select(last, nb, b),
                      lax.select(last, theirs, mine),
                      lax.select(last, 0, i + 1), 1 - half,
                      lambda c: c.start())
            each_page(b, mine, i, half, lambda c: c.wait())
            q = q_ref[0]                                   # [RB, W] scaled
            rows = buf[half]                               # [KB, W]
            s = lax.dot_general(                           # [RB, KB]
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # virtual key position of this block's rows against the
            # row's query position ctx + t (t = row // heads); a padding
            # row (t >= q_len) sees what the last live row sees
            t = qi * TB + lax.div(
                lax.broadcasted_iota(jnp.int32, (RB, 1), 0), nH)
            seen = lax.broadcast_in_dim(
                ctx + lax.min(t, qlen - 1) - i * KB, (RB, KB), (0, 1))
            s = lax.select(
                lax.broadcasted_iota(jnp.int32, (RB, KB), 1) <= seen, s,
                lax.full_like(s, -jnp.inf))
            m_prev = m_ref[:, :1]
            m_new = lax.max(m_prev, lax.expand_dims(
                lax.reduce_max(s, (1,)), (1,)))
            p = lax.exp(s - m_new)
            alpha = lax.exp(m_prev - m_new)  # block 0: exp(-inf - m) = 0
            l_new = l_ref[:, :1] * alpha + lax.expand_dims(
                lax.reduce_sum(p, (1,)), (1,))
            pv = lax.dot_general(
                p.astype(rows.dtype), rows[:, :rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [RB, rank]
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = lax.broadcast_in_dim(m_new, m_ref.shape, (0, 1))
            l_ref[...] = lax.broadcast_in_dim(l_new, l_ref.shape, (0, 1))
            return carry

        lax.fori_loop(0, n_blocks, block, 0)
        first_ref[0] = lax.rem(first + n_blocks, 2)
        # a live block's every row sees key 0, so l > 0 there; a block
        # wholly past q_len (no block ran) writes zeros
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / lax.select(
            l > 0, l, lax.full_like(l, 1.0))).astype(o_ref.dtype)

    return kernel


def mla_paged_attention(q, pool, page_table, ctx_len, q_len=None, *,
                        layer=0, rank: int, interpret: bool = False):
    """q: [B, Tq, nH, W] absorbed queries ``[q_lat | q_rope | 0]``, already
    scaled (row t of slot b sits at absolute position ``ctx_len[b] + t``).
    pool: the latent plane where it lies, ``[L, P, page_size, W]``, already
    holding the chunk's own rows; the kernel reads layer ``layer`` (int32
    scalar, traced or not) and of it only the pages a query block can see.
    page_table [B, max_pages]; ctx_len [B]; q_len [B] live rows a chunk
    (None: all Tq; the live rows lie inside the table). Returns ``o_lat``
    [B, Tq, nH, rank] in q.dtype; rows past ``q_len`` in a block that
    holds none before it are zeros, other padding rows attend what the
    slot's last live row attends."""
    B, Tq, nH, W = q.shape
    if pool.ndim != 4 or pool.shape[-1] != W:
        raise ValueError(
            f"latent pool [L, P, psz, {W}] expected, got {pool.shape}")
    psz = pool.shape[2]
    max_pages = page_table.shape[1]
    TB = max(1, min(Tq, QUERY_ROWS // nH))
    if Tq % TB or psz % 8 or W % 128 or rank % 128 or rank > W:
        raise ValueError(
            f"mla kernel needs whole query blocks (Tq {Tq} % {TB}), "
            f"page_size % 8 and lane-aligned widths, got psz={psz} W={W} "
            f"rank={rank} - gate callers with mla_attention_active")
    RB, n_qblocks = TB * nH, Tq // TB
    N = _block_pages(psz, max_pages, RB)
    if q_len is None:
        q_len = jnp.full((B,), Tq, jnp.int32)
    _selected["count"] += 1  # trace-time: once per compiled program

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_qblocks),
        in_specs=[pl.BlockSpec((1, RB, W), lambda b, qi, *_: (b, qi, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, RB, rank), lambda b, qi, *_: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, N * psz, W), pool.dtype),   # row blocks, 2 deep
            pltpu.SemaphoreType.DMA((2,)),             # one a half
            pltpu.SMEM((1,), jnp.int32),       # the next first block's
            pltpu.VMEM((RB, rank), jnp.float32),       # accumulator
            pltpu.VMEM((RB, 128), jnp.float32),        # running max
            pltpu.VMEM((RB, 128), jnp.float32),        # running sum
        ],
    )
    out = pl.pallas_call(
        _make_kernel(nH, TB, psz, N, rank, B, n_qblocks, max_pages),
        name="mla_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Tq * nH, rank), q.dtype),
        # steps in order on one core: the buffer is zeroed at the first
        # and each step's first block is started by the one before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret or (FORCE_INTERPRET and not _on_tpu()),
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(ctx_len, jnp.int32),
      jnp.asarray(q_len, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q.reshape(B, Tq * nH, W), pool)
    return out.reshape(B, Tq, nH, rank)


# trace-time selection counter (the paged kernel's contract): tests and the
# benchmark assert the kernel was routed to without a chip
_selected = {"count": 0}


def selection_count() -> int:
    return _selected["count"]


def reset_selection_count() -> None:
    _selected["count"] = 0


def _on_tpu() -> bool:
    from .flash_attention import _on_tpu as on_tpu

    return on_tpu()


def mla_attention_active(page_size: int, row_width: int, rank: int) -> bool:
    """True when the latent paged kernel serves this pool: TPU (or the test
    force), kernels enabled, one device, a sublane-aligned page and
    lane-aligned row and rank — else the gather + dense path."""
    from .flash_attention import _multi_device_mesh_active

    f = flags.get_flags(["use_pallas_kernels", "use_paged_attention"])
    if not (f["use_pallas_kernels"] and f["use_paged_attention"]):
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    return page_size % 8 == 0 and row_width % 128 == 0 and rank % 128 == 0
