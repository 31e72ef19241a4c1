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

Raggedness and page indirection are index-map facts, as there (page table,
context lengths, chunk widths and the layer scalar-prefetched), with two
differences that the shapes force:

- **a grid axis over query rows.** A decode tick is already ``heads`` rows
  a slot (128) and an admission of 512 positions is 65,536: the grid is
  (slot, query block, key block), a query block is ``q_tokens`` whole
  tokens (t-major rows ``t * heads + h``), and the online-softmax state is
  per query block. Blocks wholly past a slot's ``q_len`` do no work and
  write zeros (never garbage: their rows go on through the layer and into
  the cache).
- **several pages a grid step.** A page of 16 rows is 20 KB: one page a
  step leaves the step's fixed cost and the copy's latency in charge (at 8
  pages a step a full house's tick took 1.39 ms a layer, 5.5 % of its
  roofline: PERF.md §6, PR 29). The pool is handed to the call
  ``PAGES_PER_STEP`` times, each operand with its own index map (page
  slot ``j * G + g``), so one step brings ``G`` pages (512 keys) through
  the ordinary pipeline; page slots past the block's last needed one
  re-name that page (copied again only where the step before did not name
  it) and are masked by their VIRTUAL position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["mla_paged_attention", "mla_attention_active"]

# tests set this True (via monkeypatch) to force the kernel — in pallas
# interpret mode — on the CPU backend, so parity runs where tier-1 runs
FORCE_INTERPRET = False

QUERY_ROWS = 512       # query rows a block aims at (whole tokens)
PAGES_PER_STEP = 32    # pages one grid step reads


def _make_kernel(nH: int, TB: int, psz: int, G: int, rank: int,
                 n_kblocks: int, max_pages: int):
    RB = TB * nH

    def kernel(pt_ref, ctx_ref, qlen_ref, lay_ref, q_ref, *rest):
        k_refs, (o_ref, acc_ref, m_ref, l_ref) = rest[:G], rest[G:]
        b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        ctx = ctx_ref[b]
        active = qi * TB < qlen_ref[b]
        # last page slot this block's last query position can see
        last = jnp.minimum((ctx + (qi + 1) * TB - 1) // psz, max_pages - 1)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(active & (j * G <= last))
        def _():
            q = q_ref[0]                                   # [RB, W] scaled
            rows = jnp.concatenate([r[0] for r in k_refs], axis=0)
            s = jax.lax.dot_general(                       # [RB, G * psz]
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            kpos = j * (G * psz) + jax.lax.broadcasted_iota(
                jnp.int32, (RB, G * psz), 1)
            t = qi * TB + jax.lax.broadcasted_iota(
                jnp.int32, (RB, G * psz), 0) // nH
            s = jnp.where(kpos <= ctx + t, s, -jnp.inf)
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)   # first block: exp(-inf) = 0
            l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1,
                                                   keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [RB, rank]
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(j == n_kblocks - 1)
        def _():
            # an active block's every row sees key 0, so l > 0; a block
            # wholly past q_len is zeros
            out = acc_ref[...] / jnp.where(active, l_ref[:, :1], 1.0)
            o_ref[0] = out.astype(o_ref.dtype)

    return kernel


def mla_paged_attention(q, pool, page_table, ctx_len, q_len=None, *,
                        layer=0, rank: int, interpret: bool = False):
    """q: [B, Tq, nH, W] absorbed queries ``[q_lat | q_rope | 0]``, already
    scaled (row t of slot b sits at absolute position ``ctx_len[b] + t``).
    pool: the latent plane where it lies, ``[L, P, page_size, W]``, already
    holding the chunk's own rows; the kernel reads layer ``layer`` (int32
    scalar, traced or not). page_table [B, max_pages]; ctx_len [B];
    q_len [B] live rows a chunk (None: all Tq). Returns ``o_lat``
    [B, Tq, nH, rank] in q.dtype; rows past ``q_len`` in a block that
    holds none before it are zeros, other padding rows are finite."""
    B, Tq, nH, W = q.shape
    if pool.ndim != 4 or pool.shape[-1] != W:
        raise ValueError(
            f"latent pool [L, P, psz, {W}] expected, got {pool.shape}")
    psz = pool.shape[2]
    max_pages = page_table.shape[1]
    G = min(PAGES_PER_STEP, max_pages)
    TB = max(1, min(Tq, QUERY_ROWS // nH))
    if Tq % TB or psz % 8 or W % 128 or rank % 128 or rank > W:
        raise ValueError(
            f"mla kernel needs whole query blocks (Tq {Tq} % {TB}), "
            f"page_size % 8 and lane-aligned widths, got psz={psz} W={W} "
            f"rank={rank} - gate callers with mla_attention_active")
    RB = TB * nH
    n_kblocks = -(-max_pages // G)
    if q_len is None:
        q_len = jnp.full((B,), Tq, jnp.int32)
    _selected["count"] += 1  # trace-time: once per compiled program

    def kv_map(g):
        def index(b, qi, j, pt_ref, ctx_ref, qlen_ref, lay_ref):
            # a block past q_len needs nothing: park it on page slot 0
            last = jnp.where(
                qi * TB < qlen_ref[b],
                jnp.minimum((ctx_ref[b] + (qi + 1) * TB - 1) // psz,
                            max_pages - 1), 0)
            return (lay_ref[0], pt_ref[b, jnp.minimum(j * G + g, last)],
                    0, 0)
        return index

    q_spec = pl.BlockSpec((1, RB, W), lambda b, qi, j, *_: (b, qi, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Tq // TB, n_kblocks),
        in_specs=[q_spec] + [pl.BlockSpec((None, 1, psz, W), kv_map(g))
                             for g in range(G)],
        out_specs=pl.BlockSpec((1, RB, rank),
                               lambda b, qi, j, *_: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((RB, rank), jnp.float32),   # accumulator
            pltpu.VMEM((RB, 128), jnp.float32),    # running max
            pltpu.VMEM((RB, 128), jnp.float32),    # running sum
        ],
    )
    out = pl.pallas_call(
        _make_kernel(nH, TB, psz, G, rank, n_kblocks, max_pages),
        name="mla_paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Tq * nH, rank), q.dtype),
        interpret=interpret or (FORCE_INTERPRET and not _on_tpu()),
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(ctx_len, jnp.int32),
      jnp.asarray(q_len, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q.reshape(B, Tq * nH, W), *([pool] * G))
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
