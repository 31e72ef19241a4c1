"""Ragged decode attention — per-slot KV reads bounded by position.

Counterpart of the "Ragged Paged Attention" TPU serving kernels
(PAPERS.md): decode attention over a slot-contiguous KV cache where every
slot has its OWN length. The XLA formulation (``llama._cache_attention``)
einsums the query against the full static ``[B, max_len]`` cache window
and masks the tail — correct, but every tick streams ``max_len`` KV rows
per slot from HBM regardless of how short the slot's sequence actually
is. At serving shapes (max_len 512, typical positions 64–200) that is
2–8x the KV bytes the math needs, on a path that is HBM-bound by
construction (SCALING.md §3c).

This kernel reads only ``ceil((pos+1)/block_k)`` KV blocks per slot and
masks the tail block — the same "build the layout XLA can't reach"
playbook as ``head_dx.py``:

- grid = (slot, kv-block) with the per-slot positions SCALAR-PREFETCHED
  (``pltpu.PrefetchScalarGridSpec``): the K/V BlockSpec index maps clamp
  the block index at the slot's last needed block, so Mosaic's pipeline
  sees the SAME block coordinates for every grid step past the slot's
  length and elides the HBM→VMEM copy — per-slot KV bytes scale with
  ``pos``, not ``max_len``. Compute for those steps is skipped with
  ``pl.when`` (the grid itself stays static — nothing recompiles as
  positions move).
- K/V are viewed as ``[B, max_len, Hkv*D]`` so the minor dim is
  lane-aligned (the packed flash-kernel trick: per-head slices of the
  flat minor dim instead of a [.., Hkv, D] layout that pads D to 128
  lanes); per-kv-head tile-dots run with fp32 accumulation.
- online-softmax state (fp32 running max / sum / [nH, D] accumulator)
  lives in VMEM scratch across the kv-block grid steps; the last block
  normalises and writes the slot's output.

GQA contracts grouped: q rows ``h*rep:(h+1)*rep`` dot kv head ``h`` — the
repeated cache is never materialised (same contract as the dense path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["ragged_decode_attention", "decode_attention_active",
           "pick_kv_block", "kv_blocks_read"]

# tests set this True (via monkeypatch) to force the kernel — in pallas
# interpret mode — on the CPU backend, so parity runs where tier-1 runs
FORCE_INTERPRET = False


def pick_kv_block(max_len: int, prefer: int = 128) -> int:
    """Largest sublane-aligned kv block that tiles ``max_len`` (0 = none).

    128 preferred: smaller blocks track ``pos`` tighter (less tail waste)
    but add grid steps; 128 rows x (Hkv*D) lanes keeps the per-step DMA
    large enough to pipeline while bounding overshoot to <1 block.

    r23 long-context refinement (ISSUE 18): once the window reaches 8K+
    the grid-step count dominates the tail-waste argument — a decode tick
    over a 32K window at block 128 runs 256 grid steps of mostly-DMA
    latency, while 512-row blocks cut that 4x and the <1-block overshoot
    is still noise against the window. 512 leads the candidate list only
    in that regime, so every existing (short) shape keeps its block
    choice bit-for-bit."""
    longctx = (512,) if (max_len >= 8192 and max_len % 512 == 0) else ()
    for b in longctx + (prefer, 256, 128, 64):
        if b <= max_len and max_len % b == 0:
            return b
    return 0


def kv_blocks_read(pos, block_k: int):
    """Blocks the kernel fetches for a slot at ``pos`` (keys [0, pos]
    visible -> ceil((pos+1)/block_k) = pos // block_k + 1). The analytic
    half of the bytes-read evidence in ``benchmarks/decode_profile.py``;
    the clamp in the BlockSpec index maps below is what enforces it."""
    return pos // block_k + 1


def _make_kernel(nH: int, Hkv: int, D: int, block_k: int, n_blocks: int,
                 quant: bool = False):
    rep = nH // Hkv

    def kernel(pos_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            # per-row KV scales ride along as [1, block_k] lane vectors
            # under the SAME clamped index map as their K/V rows — the
            # HBM stream carried the narrow dtype; dequant happens here,
            # on VMEM-resident tiles (r21 quantized serving). A row's
            # scale is a COLUMN of the score / probability tiles, so it
            # multiplies those ([nH, block_k] * [1, block_k], a plain
            # sublane broadcast) instead of the [block_k, D] K/V tiles,
            # which would need the scales moved from lanes to sublanes
            sk_ref, sv_ref, o_ref, acc_ref, m_ref, l_ref = rest
        else:
            o_ref, acc_ref, m_ref, l_ref = rest
        b = pl.program_id(0)
        j = pl.program_id(1)
        pos = pos_ref[b]

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        # blocks past the slot's length: the index map already re-fetched
        # nothing (same block coords as the previous step); skip compute
        @pl.when(j <= pos // block_k)
        def _():
            q = q_ref[0]  # [nH, D] — q arrives PRE-SCALED (like flash)
            parts = []
            for h in range(Hkv):
                kh = k_ref[0, :, h * D:(h + 1) * D]       # [block_k, D]
                qh = q[h * rep:(h + 1) * rep]             # [rep, D]
                if quant:
                    kh = kh.astype(jnp.float32)
                    qh = qh.astype(jnp.float32)
                parts.append(jax.lax.dot_general(
                    qh, kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32))
            s = jnp.concatenate(parts, axis=0)            # [nH, block_k]
            if quant:
                s = s * sk_ref[0, 0]
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (nH, block_k), 1)
            s = jnp.where(kpos <= pos, s, -jnp.inf)       # tail-block mask
            m_prev = m_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)  # block 0: exp(-inf - m) = 0
            l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pb = p * sv_ref[0, 0] if quant else p.astype(v_ref.dtype)
            pv_parts = []
            for h in range(Hkv):
                vh = v_ref[0, :, h * D:(h + 1) * D]       # [block_k, D]
                if quant:
                    vh = vh.astype(jnp.float32)
                ph = pb[h * rep:(h + 1) * rep]            # [rep, block_k]
                pv_parts.append(jax.lax.dot_general(
                    ph, vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(
                pv_parts, axis=0)                         # [nH, D]
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(j == n_blocks - 1)
        def _():
            # every slot has key 0 visible (pos >= 0), so l >= exp(0) > 0
            o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)

    return kernel


def ragged_decode_attention(q, kc, vc, pos, scale=None, block_k: int = 0,
                            interpret: bool = False, k_scale=None,
                            v_scale=None):
    """Single-token decode attention with per-slot ragged KV reads.

    q: [B, nH, D]; kc/vc: [B, max_len, Hkv, D] (the slot-contiguous
    cache); pos: [B] int32 — keys [0, pos[b]] are visible to slot b (row
    ``pos`` holds the token being decoded, already scattered by the
    caller). Returns [B, nH, D] in q.dtype. Falls back to raising on
    untileable shapes — callers gate with ``decode_attention_active``.

    ``k_scale``/``v_scale`` ([B, max_len] fp32, optional): a QUANTIZED
    cache's per-row scales (r21). They are viewed as
    ``[B, n_blocks, 1, block_k]`` so that a block's last two dims equal
    the array's (Mosaic tiles nothing narrower than 8 sublanes), and
    ride the same clamped index maps as the K/V blocks: the per-slot
    bytes-read property holds for them too, and the kernel dequantizes
    in VMEM — HBM carried int8/fp8.
    """
    B, nH, D = q.shape
    Smax, Hkv = kc.shape[1], kc.shape[2]
    quant = k_scale is not None
    _selected["count"] += 1  # trace-time: once per compiled program
    block_k = block_k or pick_kv_block(Smax)
    if not block_k or Smax % block_k:
        raise ValueError(f"max_len {Smax} has no aligned kv block — gate "
                         f"callers with decode_attention_active")
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    n_blocks = Smax // block_k
    # scale folded into q outside the kernel (narrow [B, nH, D] pass),
    # matching the flash kernels' convention
    qs = (q * scale).astype(q.dtype)
    kf = kc.reshape(B, Smax, Hkv * D)  # lane-aligned flat minor dim
    vf = vc.reshape(B, Smax, Hkv * D)

    def kv_map(b, j, pos_ref):
        # clamp at the slot's last needed block: past it, the SAME block
        # coords repeat and Mosaic skips the HBM->VMEM copy — this line
        # is the entire "read only [0, pos)" property
        return (b, jnp.minimum(j, pos_ref[b] // block_k), 0)

    def sc_map(b, j, pos_ref):
        return (b, jnp.minimum(j, pos_ref[b] // block_k), 0, 0)

    in_specs = [
        pl.BlockSpec((1, nH, D), lambda b, j, pos_ref: (b, 0, 0)),
        pl.BlockSpec((1, block_k, Hkv * D), kv_map),
        pl.BlockSpec((1, block_k, Hkv * D), kv_map),
    ]
    operands = [qs, kf, vf]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, block_k), sc_map)] * 2
        operands += [
            jnp.asarray(sc, jnp.float32).reshape(B, n_blocks, 1, block_k)
            for sc in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nH, D), lambda b, j, pos_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nH, D), jnp.float32),    # fp32 accumulator
            pltpu.VMEM((nH, 128), jnp.float32),  # running max
            pltpu.VMEM((nH, 128), jnp.float32),  # running sum
        ],
    )
    return pl.pallas_call(
        _make_kernel(nH, Hkv, D, block_k, n_blocks, quant=quant),
        name="ragged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nH, D), q.dtype),
        interpret=interpret or (FORCE_INTERPRET and not _on_tpu()),
    )(jnp.asarray(pos, jnp.int32), *operands)


# trace-time selection counter: incremented when the dispatch actually
# routes a decode tick to the kernel. Each jit compile traces once, so
# tests / decode_profile --smoke can assert "the ragged path was selected
# for this program" without a chip (selection is a trace-time decision).
_selected = {"count": 0}


def selection_count() -> int:
    return _selected["count"]


def reset_selection_count() -> None:
    _selected["count"] = 0


def _on_tpu() -> bool:
    from .flash_attention import _on_tpu as on_tpu

    return on_tpu()


def decode_attention_active(max_len: int, num_heads: int, num_kv_heads: int,
                            head_dim: int) -> bool:
    """True when the ragged kernel serves this decode shape: TPU (or the
    test force), kernels enabled, single-device, lane-aligned flat KV
    minor dim, and an aligned kv block that tiles ``max_len`` — the same
    dispatch/fallback contract as ``ring_attention``/``flash_attention``
    (CPU and indivisible shapes take the dense path)."""
    from .flash_attention import _multi_device_mesh_active

    f = flags.get_flags(["use_pallas_kernels", "use_ragged_decode"])
    if not (f["use_pallas_kernels"] and f["use_ragged_decode"]):
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    if num_heads % num_kv_heads:
        return False
    if (num_kv_heads * head_dim) % 128 or head_dim % 8:
        return False
    return bool(pick_kv_block(max_len))
