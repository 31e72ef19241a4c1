"""Ring attention — context parallelism for long sequences.

Reference counterpart: PaddleNLP's ``RingFlashAttention`` (SURVEY.md §2.2
SEP/CP row, §5.7): the sequence is sharded over the context-parallel group;
each rank holds a K/V chunk and ring-passes it around the group, merging
partial attention results with online-softmax (max/sum) rescaling, so no
rank ever materialises the full sequence.

TPU-native design: the ring is ``jax.lax.ppermute`` over a mesh axis —
XLA overlaps the permute (ICI neighbour exchange) with the per-chunk
attention compute, which is precisely the overlap the reference hand-codes
with async P2P isend/irecv. The per-chunk compute reuses the flash-attention
formulation; the cross-chunk merge is the same online-softmax algebra the
kernel uses *within* chunks.

Layout convention matches ``flash_attention``: [batch, seq, heads, dim],
with seq already sharded over ``axis_name`` (use inside ``shard_map``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


__all__ = ["ring_attention", "RingFlashAttention",
           "context_parallel_attention", "ulysses_attention",
           "ulysses_parallel_attention", "sp_slab_ring_attention",
           "sp_slab_prefill_attention"]


def _chunk_attention(q, k, v, scale, q_offset, k_offset, is_causal):
    """Unnormalised attention of local q against one K/V chunk.

    Returns (acc, m, l): fp32 weighted values, running max, running sum —
    the online-softmax partial state. Offsets are *global* sequence
    positions of element 0 of q / k, used for causal masking across chunks.

    Matmuls keep the input dtype (bf16 on TPU) with fp32 ACCUMULATION via
    ``preferred_element_type`` — full MXU rate; casting inputs to fp32
    first would run them at 1/8 rate (same rule as the flash kernels).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if is_causal:
        sq, sk = q.shape[1], k.shape[1]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # [B, H, Sq]
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B, H, Sq]
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc.astype(jnp.float32), m, l


def _merge(acc, m, l, acc2, m2, l2):
    """Online-softmax merge of two partial attention states."""
    m_new = jnp.maximum(m, m2)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    a1 = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m_safe), 0.0)
    return (
        acc * a1[..., None] + acc2 * a2[..., None],
        m_new,
        l * a1 + l2 * a2,
    )


def ring_attention(q, k, v, axis_name: str = "sep", is_causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over the ``axis_name`` mesh axis (call inside
    shard_map with q/k/v seq-sharded). Exact — numerically equal to full
    attention over the gathered sequence."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    q_offset = idx * s_local

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        acc, m, l, k_cur, v_cur = carry
        # chunk i currently held came from rank (idx - i) mod n
        src = jax.lax.rem(idx - i + n, n)

        def do_chunk(_):
            return _chunk_attention(
                q, k_cur, v_cur, scale, q_offset, src * s_local, is_causal)

        if is_causal:
            # causal load shape: chunks strictly after this rank's rows are
            # FULLY masked — skip their matmuls (the reference's causal
            # ring skips them the same way); the -inf partial merges as a
            # no-op
            def skip(_):
                return (jnp.zeros((b, h, s_local, d), jnp.float32),
                        jnp.full((b, h, s_local), -jnp.inf, jnp.float32),
                        jnp.zeros((b, h, s_local), jnp.float32))

            acc2, m2, l2 = jax.lax.cond(src <= idx, do_chunk, skip, None)
        else:
            acc2, m2, l2 = do_chunk(None)
        acc, m, l = _merge(acc, m, l, acc2, m2, l2)
        # pass K/V along the ring (skippable on the last step, but keeping
        # it unconditional lets XLA pipeline the permute under the compute)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, m, l, k_nxt, v_nxt), None

    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    m0 = jnp.full((b, h, s_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    # scan (not fori_loop): reverse-mode differentiable, static trip count
    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]  # [B, H, S, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# PaddleNLP-compatible alias
RingFlashAttention = ring_attention


def _sp_gspmd_entry(local_fn, q, k, v, mesh, axis_name, is_causal,
                    batch_axes, head_axes, fallback,
                    needs_head_divisible=False):
    """Shared GSPMD prologue for the sequence-parallel attention entries:
    resolve the mesh, validate that EVERY operand's sharded dims divide
    their axes (else take the fallback), and run ``local_fn`` under
    shard_map with matching PartitionSpecs."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import get_mesh
    from .flash_attention import _xla_attention

    def fall_back():
        if fallback is not None:
            return fallback()
        return _xla_attention(q, k, v, is_causal=is_causal)

    mesh = mesh or get_mesh()
    if mesh is None or axis_name not in mesh.axis_names or \
            mesh.shape[axis_name] <= 1:
        return fall_back()

    def _present(axes):
        if axes is None:
            return None
        axes = tuple(a for a in (axes if isinstance(axes, (tuple, list))
                                 else (axes,)) if a in mesh.axis_names)
        return axes or None

    baxes, haxes = _present(batch_axes), _present(head_axes)
    b_size = int(np.prod([mesh.shape[a] for a in (baxes or ())]))
    h_size = int(np.prod([mesh.shape[a] for a in (haxes or ())]))
    n = mesh.shape[axis_name]
    for x in (q, k, v):
        if x.shape[1] % n or x.shape[0] % b_size or x.shape[2] % h_size:
            return fall_back()
        if needs_head_divisible and (x.shape[2] // max(h_size, 1)) % n:
            return fall_back()

    from ...parallel.mesh import shard_map_compat

    spec = P(baxes, axis_name, haxes, None)
    fn = shard_map_compat(
        functools.partial(local_fn, axis_name=axis_name,
                          is_causal=is_causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return fn(q, k, v)


def context_parallel_attention(q, k, v, mesh=None, axis_name: str = "sep",
                               is_causal: bool = False, batch_axes=None,
                               head_axes=None, fallback=None):
    """GSPMD-level entry: q/k/v are *global* arrays; shard the seq dim over
    ``axis_name`` and run ring attention under shard_map. Falls back
    (``fallback()`` if given, else the XLA formulation) when the axis has
    size 1 / no mesh, or when any sharded dim doesn't divide its axes.

    ``batch_axes``/``head_axes`` name the mesh axes the batch and head
    dims are already sharded over (e.g. ('dp', 'sharding') and 'mp' in the
    hybrid llama layout) so the shard_map specs match the surrounding
    GSPMD sharding — those axes stay pure data parallelism inside the
    ring."""
    return _sp_gspmd_entry(ring_attention, q, k, v, mesh, axis_name,
                           is_causal, batch_axes, head_axes, fallback)


def _slab_dense_attention(q, k, v, offsets, scale=None):
    """Dense reference for the sequence-parallel prefill slab (r23): each
    batch row holds one C-token chunk of the SAME prompt at global offset
    ``offsets[r]``; every row attends every row's chunk under an absolute-
    position causal mask. This is exactly what the serving path's paged
    gather computes, and what ``sp_slab_ring_attention`` must match."""
    b, c, h, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.reshape(1, b * c, h, d)
    kf = k.reshape(1, b * c, h, d)
    vf = v.reshape(1, b * c, h, d)
    pos = (offsets[:, None] + jnp.arange(c, dtype=offsets.dtype)).reshape(-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf,
                   preferred_element_type=jnp.float32) * scale
    mask = pos[:, None] >= pos[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    # every query position attends at least itself, so the softmax row max
    # is finite — no masked-row NaN hazard
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.sum(p, axis=-1,
                                                   keepdims=True),
                     vf.astype(jnp.float32))
    return out.reshape(b, c, h, d).astype(q.dtype)


def sp_slab_ring_attention(q, k, v, q_offset, axis_name: str = "sp",
                           scale: Optional[float] = None):
    """Ring attention for the sequence-parallel prefill SLAB (r23, ISSUE
    18): the serving engine reshapes a long-prompt chunk of ``sp * C``
    tokens into an [sp, C] slab whose row r sits at global offset
    ``base + r*C``. Call inside shard_map with the slab's ROW axis (the
    batch dim) sharded over ``axis_name`` — one row per rank, so each
    rank holds q/k/v of shape [1, C, H, D] plus its row's global offset
    ``q_offset`` (shape [1], int32).

    K/V chunks and their offsets ring-pass via ``ppermute`` exactly like
    ``ring_attention``; the only delta is that causal masking uses the
    carried ABSOLUTE offsets rather than ``rank * s_local``, because slab
    rows are chunks of one prompt, not contiguous shards of a padded
    sequence. Exact: matches ``_slab_dense_attention`` bit-for-bit in
    fp32 accumulation terms (same online-softmax algebra)."""
    n = jax.lax.axis_size(axis_name)
    b, c, h, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    my_off = q_offset[0]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        acc, m, l, k_cur, v_cur, off_cur = carry

        def do_chunk(_):
            return _chunk_attention(q, k_cur, v_cur, scale, my_off,
                                    off_cur[0], True)

        def skip(_):
            # chunk lies entirely in this row's causal future — fully
            # masked, skip the matmuls (merge of the -inf state is a no-op)
            return (jnp.zeros((b, h, c, d), jnp.float32),
                    jnp.full((b, h, c), -jnp.inf, jnp.float32),
                    jnp.zeros((b, h, c), jnp.float32))

        acc2, m2, l2 = jax.lax.cond(off_cur[0] <= my_off + (c - 1),
                                    do_chunk, skip, None)
        acc, m, l = _merge(acc, m, l, acc2, m2, l2)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        off_nxt = jax.lax.ppermute(off_cur, axis_name, perm)
        return (acc, m, l, k_nxt, v_nxt, off_nxt), None

    acc0 = jnp.zeros((b, h, c, d), jnp.float32)
    m0 = jnp.full((b, h, c), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, c), jnp.float32)
    (acc, m, l, _, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v, q_offset), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]  # [B, H, C, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def sp_slab_prefill_attention(q, k, v, offsets, mesh=None,
                              axis_name: str = "sp", fallback=None,
                              scale: Optional[float] = None):
    """GSPMD-level entry for slab ring attention: q/k/v are the GLOBAL
    [sp, C, H, D] slab tensors and ``offsets`` the [sp] global row
    offsets. Shards the row (batch) dim over ``axis_name`` and runs
    ``sp_slab_ring_attention`` under shard_map; falls back to the dense
    absolute-position formulation (``fallback()`` if given) when the mesh
    lacks a usable ``axis_name`` axis or the row count doesn't equal the
    axis size — which is exactly the CPU/test regime, where the serving
    engine's paged gather path is already the bit-exact reference."""
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import get_mesh, shard_map_compat

    def fall_back():
        if fallback is not None:
            return fallback()
        return _slab_dense_attention(q, k, v, offsets, scale=scale)

    mesh = mesh or get_mesh()
    if mesh is None or axis_name not in mesh.axis_names or \
            mesh.shape[axis_name] <= 1 or \
            q.shape[0] != int(mesh.shape[axis_name]):
        return fall_back()

    spec = P(axis_name, None, None, None)
    fn = shard_map_compat(
        functools.partial(sp_slab_ring_attention, axis_name=axis_name,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec, P(axis_name)),
        out_specs=spec,
    )
    return fn(q, k, v, offsets)


def ulysses_attention(q, k, v, axis_name: str = "sep",
                      is_causal: bool = False,
                      scale: Optional[float] = None):
    """Ulysses-style sequence parallelism (reference: PaddleNLP/DeepSpeed
    "Ulysses" SP; SURVEY §5.7 [LOW] row): instead of ring-passing K/V
    chunks, ALL-TO-ALL reshards seq-parallel activations into
    head-parallel ones — each rank then holds the FULL sequence for a
    1/n subset of heads, computes ordinary (exact) attention, and an
    inverse all-to-all restores the seq-parallel layout.

    Call inside shard_map with q/k/v [B, S/n, H, D] seq-sharded over
    ``axis_name``; H must divide by the axis size. vs ring attention:
    2 all-to-alls of the activations instead of (n-1) K/V permutes —
    cheaper when 2·|q| < (n-1)·|kv| (e.g. GQA with few KV heads favours
    the ring; MHA at moderate n favours Ulysses) — the same trade the
    reference documents between its two SP implementations.
    """
    from .flash_attention import _xla_attention

    n = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"ulysses_attention: head count {h} must be "
                         f"divisible by the '{axis_name}' axis size {n}")

    def seq_to_heads(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]: head-split piece r goes to
        # rank r; received seq chunks concatenate in source-rank order,
        # i.e. global sequence order (tiled all_to_all does both in one
        # collective, and is its own well-defined transpose for autodiff)
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def heads_to_seq(x):
        # inverse: [B, S, H/n, D] -> [B, S/n, H, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full sequence per rank: plain exact attention (global positions are
    # just 0..S-1, so causal masking needs no cross-rank offsets)
    out = _xla_attention(qh, kh, vh, is_causal=is_causal, scale=scale)
    return heads_to_seq(out)  # _xla_attention already emits q.dtype


def ulysses_parallel_attention(q, k, v, mesh=None, axis_name: str = "sep",
                               is_causal: bool = False, batch_axes=None,
                               head_axes=None, fallback=None):
    """GSPMD-level Ulysses entry, mirroring ``context_parallel_attention``:
    q/k/v are global arrays; seq shards over ``axis_name`` and the
    all-to-all resharding runs under shard_map. Falls back when the axis
    is absent/size-1 or shapes (incl. per-shard head count % axis) don't
    divide."""
    return _sp_gspmd_entry(ulysses_attention, q, k, v, mesh, axis_name,
                           is_causal, batch_axes, head_axes, fallback,
                           needs_head_divisible=True)
