"""Fused decode-tick epilogue kernels — collapse the per-tick small ops.

The decode tick at serving batch sizes is HBM-bound on the WEIGHT
streams; the matmuls are fine. What fragments the step is everything
between them: at batch 8 the profile shows ~60 small fused ops per tick
(SCALING.md §3c) — rmsnorm reduce+scale pairs, the rope cos/sin/slice/
concat chains, residual adds — each a separate launch over a [8, 768]
tensor whose fixed per-op cost dwarfs its arithmetic. XLA will not fuse
ACROSS these chains because the matmuls sit between them.

These kernels collapse each between-matmul chain into ONE Pallas call
(the tick's tensors are tiny — a single grid cell wholly in VMEM; the
rmsnorm and rope kernels also serve an admission's rows, and take a grid
over row blocks once the rows outgrow one block, ``_row_grid``):

- ``fused_rms_norm``      rmsnorm chain -> 1 op
- ``fused_add_rms_norm``  residual add + next rmsnorm -> 1 op, 2 outputs
                          (the new residual stream AND the normed value)
- ``fused_rope_qk``       rope on q AND k in one kernel: positions ->
                          cos/sin computed in-kernel, per-head
                          rotate-half on the FLAT [B, H] layout (the
                          packed flash-kernel trick) -> 1 op for the
                          whole ~15-op chain, shared across q and k

Dispatch mirrors ``flash_attention``: TPU + flag + single-device, with
the jnp formulation (bit-identical math to ``models/llama``'s inline
chains) as the CPU/fallback path, and ``FORCE_INTERPRET`` so tier-1 CPU
tests can run the real kernels through the pallas interpreter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ... import flags

__all__ = ["tick_fusion_active", "fused_rms_norm", "fused_add_rms_norm",
           "fused_rope_qk", "quant_matmul", "quant_matmul_active"]

# tests set this True to force the kernels (pallas interpret mode) on CPU
FORCE_INTERPRET = False


def _interp() -> bool:
    from .flash_attention import _on_tpu

    return FORCE_INTERPRET and not _on_tpu()


def tick_fusion_active(hidden_size: int) -> bool:
    """True when the decode tick should use the fused epilogue kernels:
    TPU (or test force), kernels + flag enabled, single device, and a
    lane-aligned hidden dim (tiny test configs fall back to the inline
    jnp chains — same math)."""
    from .flash_attention import _multi_device_mesh_active, _on_tpu

    f = flags.get_flags(["use_pallas_kernels", "use_tick_fusion"])
    if not (f["use_pallas_kernels"] and f["use_tick_fusion"]):
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    return hidden_size % 128 == 0


# ---------------------------------------------------------------------------
# rmsnorm (+ residual add) — one kernel per chain over [rows, H]
# ---------------------------------------------------------------------------

# what one grid step's input and output blocks may hold. A tick's rows and
# an admission of 256 rows at H 2048 (3 MB in fused_rope_qk) are one block;
# 1,024 rows at Mistral's 4,096 (20 MB) are eight. The kernel's fp32
# temporaries and the pipeline's second buffers come on top, under the
# chip's 16 MB of scoped VMEM.
_BLOCK_BYTES = 4 * 2**20


def _row_grid(rows: int, itemsize: int, ins, outs, whole=()):
    """``pallas_call``'s grid arguments for a kernel whose rows are
    independent. ``ins``: the widths of its ``[rows, w]`` operands,
    ``outs``: those of its results, shaped like ``out_shape`` (one width or
    a list), ``whole``: the widths of ``[1, w]`` operands that follow
    ``ins`` and every step reads. Nothing (one block, no grid: the tick's
    call) while the blocks fit ``_BLOCK_BYTES``, else a grid over
    power-of-two row blocks; a ragged last block reads padding and its
    writes are dropped."""
    row_bytes = (sum(ins) + sum(jax.tree.leaves(outs))) * itemsize
    fit = max(16, _BLOCK_BYTES // row_bytes)
    if rows <= fit:
        return {}
    block = 1 << (fit.bit_length() - 1)

    def rows_of(w):
        return pl.BlockSpec((block, w), lambda i: (i, 0))

    return dict(
        grid=(pl.cdiv(rows, block),),
        in_specs=[rows_of(w) for w in ins]
        + [pl.BlockSpec((1, w), lambda i: (0, 0)) for w in whole],
        out_specs=jax.tree.map(rows_of, outs))


def _rms_kernel(eps):
    def kernel(x_ref, w_ref, o_ref):
        xf = x_ref[...].astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        normed = (xf * jax.lax.rsqrt(var + eps)).astype(x_ref.dtype)
        o_ref[...] = normed * w_ref[...].astype(x_ref.dtype)

    return kernel


def fused_rms_norm(x, w, eps: float):
    """rmsnorm(x) * w as ONE op. x: [B, H] (any number of rows); w: [H].
    Math matches ``llama._rms_norm`` (fp32 mean-square, cast before the
    gain)."""
    B, H = x.shape
    return pl.pallas_call(
        _rms_kernel(float(eps)),
        name="fused_rms_norm",
        out_shape=jax.ShapeDtypeStruct((B, H), x.dtype),
        interpret=_interp(),
        **_row_grid(B, x.dtype.itemsize, (H,), H, whole=(H,)),
    )(x, jnp.broadcast_to(w, (1, H)))


def _add_rms_kernel(eps):
    def kernel(x_ref, y_ref, w_ref, s_ref, o_ref):
        s = x_ref[...] + y_ref[...]
        s_ref[...] = s
        sf = s.astype(jnp.float32)
        var = jnp.mean(sf * sf, axis=-1, keepdims=True)
        normed = (sf * jax.lax.rsqrt(var + eps)).astype(s.dtype)
        o_ref[...] = normed * w_ref[...].astype(s.dtype)

    return kernel


def fused_add_rms_norm(x, y, w, eps: float):
    """(x + y, rmsnorm(x + y) * w) as ONE op — the residual add feeding
    the next pre-norm never round-trips HBM between two launches."""
    B, H = x.shape
    return pl.pallas_call(
        _add_rms_kernel(float(eps)),
        name="fused_add_rms_norm",
        out_shape=[jax.ShapeDtypeStruct((B, H), x.dtype),
                   jax.ShapeDtypeStruct((B, H), x.dtype)],
        interpret=_interp(),
    )(x, y, jnp.broadcast_to(w, (1, H)))


# ---------------------------------------------------------------------------
# rope on q and k — one kernel, cos/sin shared, flat [B, H] head slices
# ---------------------------------------------------------------------------


def _rope_qk_kernel(D, nq, nk, theta):
    half = D // 2
    lax = jax.lax

    # lax primitives, not jnp operators, on the values: under a trace every
    # ``a * b`` / ``z[:, i:j]`` of jnp's is a jitted function traced anew,
    # 153 of them a call site in this per-head loop (0.6 s of every start
    # of the llama serve cells, PERF.md §6 PR 35); a primitive binds at once
    def rotate(z_ref, o_ref, nheads, cos, sin):
        z = z_ref[...]
        rows = z.shape[0]
        cos = lax.convert_element_type(cos, z.dtype)
        sin = lax.convert_element_type(sin, z.dtype)
        for h in range(nheads):
            lo, mid, hi = h * D, h * D + half, (h + 1) * D
            x1 = lax.slice(z, (0, lo), (rows, mid))
            x2 = lax.slice(z, (0, mid), (rows, hi))
            o_ref[:, lo:mid] = lax.sub(lax.mul(x1, cos), lax.mul(x2, sin))
            o_ref[:, mid:hi] = lax.add(lax.mul(x1, sin), lax.mul(x2, cos))

    def kernel(pos_ref, q_ref, k_ref, oq_ref, ok_ref):
        B = q_ref.shape[0]
        # angles in fp32 like llama._rope_at: pos * theta^(-2i/D). Mosaic
        # only makes integer iotas, so the index is cast after the fact
        i2 = jax.lax.broadcasted_iota(jnp.int32, (B, half), 1).astype(
            jnp.float32) * 2.0
        freqs = jnp.power(jnp.float32(theta), -i2 / D)
        ang = pos_ref[...].astype(jnp.float32) * freqs  # [B, half]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        rotate(q_ref, oq_ref, nq, cos, sin)
        rotate(k_ref, ok_ref, nk, cos, sin)

    return kernel


def fused_rope_qk(zq, zk, pos, head_dim: int, theta: float):
    """Rope both projections in ONE op. zq: [B, nH*D]; zk: [B, Hkv*D];
    pos: [B] int32 (each row at its own absolute position — a tick's
    slots, or the flattened rows of an admission; broadcast a scalar for
    the shared-position path). cos/sin are computed in-kernel from
    ``pos`` — the XLA chain's iota/power/cos/sin/broadcast ops never
    exist as separate launches."""
    B, Hq = zq.shape
    Hk = zk.shape[1]
    return pl.pallas_call(
        _rope_qk_kernel(head_dim, Hq // head_dim, Hk // head_dim,
                        float(theta)),
        name="fused_rope_qk",
        out_shape=[jax.ShapeDtypeStruct((B, Hq), zq.dtype),
                   jax.ShapeDtypeStruct((B, Hk), zk.dtype)],
        interpret=_interp(),
        **_row_grid(B, zq.dtype.itemsize, (1, Hq, Hk), [Hq, Hk]),
    )(jnp.asarray(pos, jnp.int32).reshape(B, 1), zq, zk)


# ---------------------------------------------------------------------------
# quantized weight matmul — the tick's weight stream carries int8/fp8;
# dequantization happens in VMEM (r21, SCALING §3p)
# ---------------------------------------------------------------------------


def pick_n_block(N: int, prefer: int = 512) -> int:
    """Largest lane-aligned output block that tiles ``N`` (0 = none).
    Bigger blocks amortise the per-step overhead; the VMEM bound is the
    [K, block_n] weight tile (int8: K*block_n bytes — 4 MB at
    K=8192/block=512, comfortably pipelined)."""
    for b in (prefer, 256, 128):
        if b <= N and N % b == 0:
            return b
    return 0


def _quant_matmul_kernel(x_ref, w_ref, s_ref, o_ref):
    # the weight tile arrived in VMEM in its NARROW dtype (that was the
    # whole HBM stream); dequantize here and accumulate in fp32
    wf = w_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    o_ref[...] = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), wf,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def quant_matmul(x, w, scale, block_n: int = 0, interpret: bool = False):
    """``x @ (w * scale)`` with the dequantize INSIDE the kernel.

    x: [B, K] fp activations; w: [K, N] int8 (or fp8/e4m3) weights;
    scale: [N] fp32 per-output-channel scales. HBM→VMEM traffic for the
    weight stream is the narrow dtype — the point of the whole exercise
    (SCALING §3c bills the decode tick at weight-bytes/tick over HBM
    bandwidth); the per-tile dequant multiply runs on VMEM-resident
    data and the dot accumulates fp32. Grid tiles the output dim; x and
    the [K, block] weight tiles are single-cell blocks. Returns [B, N]
    fp32 (callers cast to the compute dtype). Gate call sites with
    ``quant_matmul_active``."""
    B, K = x.shape
    N = w.shape[1]
    block_n = block_n or pick_n_block(N)
    if not block_n:
        raise ValueError(f"N {N} has no lane-aligned block — gate callers "
                         f"with quant_matmul_active")
    return pl.pallas_call(
        _quant_matmul_kernel,
        name="quant_matmul",
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((B, K), lambda j: (0, 0)),
            pl.BlockSpec((K, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((B, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=interpret or _interp(),
    )(x, w, jnp.asarray(scale, jnp.float32).reshape(1, N))


def quant_matmul_active(K: int, N: int) -> bool:
    """True when the quantized projection matmul should take the Pallas
    in-kernel-dequant path: TPU (or the test force), kernels + flag
    enabled, single device, sublane-aligned contraction dim and a
    lane-aligned output block (tiny test configs and mesh paths fall
    back to the dense XLA dequantize-then-dot — same math)."""
    from .flash_attention import _multi_device_mesh_active, _on_tpu

    f = flags.get_flags(["use_pallas_kernels", "use_quant_matmul"])
    if not (f["use_pallas_kernels"] and f["use_quant_matmul"]):
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    return K % 32 == 0 and bool(pick_n_block(N))
