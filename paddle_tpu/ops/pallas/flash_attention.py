"""Flash attention.

Counterpart of the reference's ``flash_attn`` fused kernel
(``paddle/phi/kernels/fusion`` wrapping the FlashAttention CUDA lib;
SURVEY.md §2.1). Two paths:

* ``_pallas_flash_attention`` — tiled online-softmax kernel in VMEM for TPU
  (MXU-sized q/k blocks, numerically stable running max/sum rescaling).
* ``_xla_attention`` — plain jnp formulation for CPU tests and as the
  reference implementation; XLA fuses it reasonably but materialises the
  [S, S] score matrix.

Layout convention (paddle flash_attn): [batch, seq, num_heads, head_dim].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ... import flags


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention_probs(q, k, mask=None, is_causal=False, scale=None):
    """Masked softmax attention probabilities [B, H, Sq, Sk] — the ONE
    implementation of the fp32-accumulated logits + causal/additive-mask +
    softmax block (shared by `_xla_attention`, the probs-level-dropout SDPA
    path, and `flash_attention(return_softmax=True)`). q/k: [B, S, H, D]."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(causal, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(logits.dtype)
    return jax.nn.softmax(logits, axis=-1)


def attention_apply(probs, v, dtype=None):
    """probs [B, H, Sq, Sk] @ v [B, Sk, H, D] -> [B, Sq, H, D], fp32
    accumulation. ``dtype`` is the compute/output dtype — pass q's dtype
    when it differs from v's (the probs round to it before the matmul, as
    the pre-refactor `_xla_attention` did)."""
    dtype = dtype or v.dtype
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(dtype)


def _xla_attention(q, k, v, mask=None, is_causal=False, scale=None):
    # q,k,v: [B, S, H, D] -> scores over S. Matmuls keep the input dtype
    # (bf16 on TPU) with fp32 ACCUMULATION via preferred_element_type — the
    # MXU's native mode; casting inputs to fp32 first would run the matmul
    # at 1/8 MXU rate (this path is also the flash-VJP's recompute, so it
    # sets the backward-pass speed).
    probs = attention_probs(q, k, mask=mask, is_causal=is_causal, scale=scale)
    return attention_apply(probs, v, dtype=q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel (forward). Grid: (batch*heads, q_blocks); the kv loop runs
# inside the kernel with a running (max, sum) online softmax.
# ---------------------------------------------------------------------------

def _make_pallas_fwd(block_q: int, block_k: int, is_causal: bool,
                     causal_offset: int = 0, with_lse: bool = False,
                     seq_k: int = 0):
    """``causal_offset`` aligns the causal diagonal when sq != sk (KV-cache
    decode): query row i sits at absolute position i + offset, matching the
    XLA fallback's ``tril(..., k=sk-sq)`` convention. ``with_lse`` adds a
    second output with each row's logsumexp (needed by the backward pass:
    ``exp(s - lse)`` reconstitutes the softmax probabilities).

    Per-tile math is kept lean: the softmax scale is FOLDED INTO Q by the
    caller, so the kernels never multiply the [block_q, block_k] score
    matrix by it. Causal masking stays on-the-fly (iota/compare per tile):
    a precomputed additive mask was measured perf-neutral while breaking
    the O(S)-memory contract (an [sq, sk] operand whose per-cell VMEM
    block grows with sk). At seq 512 / D=64 the kernels measure at the
    balanced DMA+MXU+VPU limit (~1.35 us per grid cell).

    Every row sees at least one unmasked key in k-block 0 (causal:
    q_pos >= 0 always; non-causal: trivially), so the running max is finite
    from the first visited block and IEEE semantics make the -inf paths
    self-correcting: ``exp(-inf - finite) = 0`` — no isfinite guards needed.
    ``seq_k == block_k`` (the whole K/V fits one block — the common
    seq<=512 training shape) drops the online-softmax loop entirely for a
    straight-line softmax in VMEM."""
    from jax.experimental import pallas as pl

    single_block = seq_k == block_k

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None):
        # q_ref: [1, block_q, d] (PRE-SCALED q); k_ref/v_ref: [1, S, d]
        # (this head's K/V). Matmuls keep the input dtype (bf16) with fp32
        # ACCUMULATION via preferred_element_type — full MXU rate.
        qb = q_ref[0]
        S = k_ref.shape[1]
        q_idx = pl.program_id(1)

        def block_scores(start, kb):
            """Masked scores of this q block vs k block (scale pre-folded)."""
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if is_causal:
                q_pos = causal_offset + q_idx * block_q + \
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                k_pos = start * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
            return s

        if single_block:
            vb = v_ref[0]
            s = block_scores(0, k_ref[0])
            m = jnp.max(s, axis=-1)
            p = jnp.exp(s - m[:, None])
            l = jnp.sum(p, axis=-1)
            acc = jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[0] = (m + jnp.log(l))[:, None]
            return

        def body(start, carry):
            acc, m_prev, l_prev = carry
            kb = k_ref[0, pl.ds(start * block_k, block_k), :]
            vb = v_ref[0, pl.ds(start * block_k, block_k), :]
            s = block_scores(start, kb)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)  # iter 0: exp(-inf - m) = 0
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        n_k = S // block_k
        if is_causal:
            # only blocks up to the diagonal contribute
            last = jax.lax.div(
                causal_offset + (q_idx + 1) * block_q + block_k - 1,
                jnp.int32(block_k),
            )
            n_iter = jnp.minimum(n_k, last)
        else:
            n_iter = n_k
        acc0 = jnp.zeros((block_q, q_ref.shape[2]), jnp.float32)
        m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, n_iter, body, (acc0, m0, l0))
        o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            # exp(s - lse) reconstitutes softmax probs in the bwd pass
            # (shape [block_q, 1]: TPU block tiling needs the trailing unit dim)
            lse_ref[0] = (m + jnp.log(l))[:, None]

    if not with_lse:
        return lambda q_ref, k_ref, v_ref, o_ref: kernel(q_ref, k_ref,
                                                         v_ref, o_ref)
    return kernel


def _pick_block(seq_len: int, prefer: int = 512) -> int:
    """Largest MXU-friendly block that tiles ``seq_len`` (512 measured
    fastest at seq 512; 256/128 keep seq lens like 768 on the pallas path
    instead of silently falling back to the O(S^2) XLA formulation).
    Returns 0 when no aligned block tiles ``seq_len`` — callers' modulo
    guard then routes to the XLA formulation (never hand Mosaic a block
    that isn't sublane-aligned)."""
    for b in (512, 256, 128):
        if b <= prefer and seq_len % b == 0:
            return b
    return 0


def _pallas_flash_attention(q, k, v, is_causal=False, scale=None,
                            block_q: int = 0, block_k: int = 0,
                            with_lse: bool = False):
    """Forward flash attention via Pallas, [B, S, H, D] layout.

    ``with_lse=False`` → out[B, S, H, D] (XLA fallback on untileable
    shapes). ``with_lse=True`` → (out, lse[B*H, S, 1]) for the backward
    pass (trailing unit dim is the TPU block-tiling requirement), or
    ``None`` on untileable shapes (caller falls back)."""
    from jax.experimental import pallas as pl

    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if (not with_lse and not block_q and not block_k
            and _packed_eligible(q, k)):
        # transpose-free packed layout (see the packed section below)
        return _pallas_flash_fwd_packed(q, k, v, is_causal, scale=scale)[0]
    block_q = min(block_q, sq) if block_q else _pick_block(sq)
    block_k = min(block_k, sk) if block_k else _pick_block(sk)
    # sq > sk under causal would put query rows before any visible key
    # (fully-masked rows -> 0/0 in the guard-free kernels); route to the
    # XLA formulation, whose -inf softmax defines that edge
    if (not block_q or not block_k or sq % block_q or sk % block_k
            or (is_causal and sq > sk)):
        if with_lse:
            return None
        return _xla_attention(q, k, v, is_causal=is_causal, scale=scale)

    # fold batch & heads into the grid's first axis: [B*H, S, D]; scale is
    # folded into q here (one cheap pass) so the kernels never touch the
    # [block_q, block_k] score matrix with a multiply
    qr = (q * scale).astype(q.dtype).transpose(0, 2, 1, 3).reshape(
        b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    kernel = _make_pallas_fwd(block_q, block_k, is_causal,
                              causal_offset=sk - sq, with_lse=with_lse,
                              seq_k=sk)
    out_spec = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    out_shape = jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)
    if with_lse:
        out_spec = [out_spec,
                    pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32)]
    result = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
    )(qr, kr, vr)
    unfold = lambda x: x.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    if with_lse:
        return unfold(result[0]), result[1]
    return unfold(result)


def _pallas_flash_fwd_lse(q, k, v, is_causal=False, scale=None,
                          block_q: int = 0, block_k: int = 0):
    """(out[B,S,H,D], lse[B*H,S,1]) or None when shapes don't tile."""
    return _pallas_flash_attention(q, k, v, is_causal=is_causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   with_lse=True)


# ---------------------------------------------------------------------------
# Pallas backward kernels (flash-attention backward): probs are
# reconstituted blockwise from the saved logsumexp, so the [S, S] score
# matrix is never materialised. dq and dk/dv are separate kernels so each
# parallelises over its own output's blocks with no cross-block races.
# ---------------------------------------------------------------------------

def _make_pallas_bwd_dq(block_q, block_k, is_causal, scale, causal_offset=0,
                        seq_k: int = 0):
    """q arrives PRE-SCALED (s = qs@k matches the forward's lse). The true
    dq (w.r.t. UNSCALED q) is (ds @ k)·scale, applied on the narrow
    [block_q, d] result instead of scaling the [block_q, block_k] ds."""
    from jax.experimental import pallas as pl

    single_block = seq_k == block_k

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref):
        # q/do: [1, block_q, d]; k/v: [1, S, d]; lse/delta: [1, block_q, 1]
        qb = q_ref[0]
        dob = do_ref[0]
        lse = lse_ref[0, :, 0]
        delta = delta_ref[0, :, 0]
        S = k_ref.shape[1]
        q_idx = pl.program_id(1)

        def block_dq(start, kb, vb):
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp(s - lse[:, None])
            if is_causal:
                q_pos = causal_offset + q_idx * block_q + \
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                k_pos = start * block_k + \
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                p = jnp.where(q_pos >= k_pos, p, 0.0)
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None])
            return jax.lax.dot_general(
                ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if single_block:
            dq = block_dq(0, k_ref[0], v_ref[0]) * scale
            dq_ref[0] = dq.astype(dq_ref.dtype)
            return

        def body(start, dq_acc):
            kb = k_ref[0, pl.ds(start * block_k, block_k), :]
            vb = v_ref[0, pl.ds(start * block_k, block_k), :]
            return dq_acc + block_dq(start, kb, vb)

        n_k = S // block_k
        if is_causal:
            last = jax.lax.div(
                causal_offset + (q_idx + 1) * block_q + block_k - 1,
                jnp.int32(block_k))
            n_iter = jnp.minimum(n_k, last)
        else:
            n_iter = n_k
        dq0 = jnp.zeros((block_q, q_ref.shape[2]), jnp.float32)
        dq = jax.lax.fori_loop(0, n_iter, body, dq0) * scale
        dq_ref[0] = dq.astype(dq_ref.dtype)

    return kernel


def _make_pallas_bwd_dkv(block_q, block_k, is_causal,
                         causal_offset=0, seq_q: int = 0):
    """q arrives PRE-SCALED, so dk = ds^T @ qs needs no scale factor
    (s = scale·(q@k) ⇒ ∂/∂k carries the scale through qs)."""
    from jax.experimental import pallas as pl

    single_block = seq_q == block_q

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dk_ref, dv_ref):
        # k/v: [1, block_k, d]; q/do: [1, S, d]; lse/delta: [1, S, 1]
        kb = k_ref[0]
        vb = v_ref[0]
        S = q_ref.shape[1]
        k_idx = pl.program_id(1)

        def block_dkv(start, qb, dob, lse, delta):
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp(s - lse[:, None])
            if is_causal:
                q_pos = causal_offset + start * block_q + \
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                k_pos = k_idx * block_k + \
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                p = jnp.where(q_pos >= k_pos, p, 0.0)
            dv_c = jax.lax.dot_general(
                p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, None])
            dk_c = jax.lax.dot_general(
                ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_c, dv_c

        if single_block:
            dk, dv = block_dkv(0, q_ref[0], do_ref[0], lse_ref[0, :, 0],
                               delta_ref[0, :, 0])
            dk_ref[0] = dk.astype(dk_ref.dtype)
            dv_ref[0] = dv.astype(dv_ref.dtype)
            return

        def body(start, carry):
            dk_acc, dv_acc = carry
            qb = q_ref[0, pl.ds(start * block_q, block_q), :]
            dob = do_ref[0, pl.ds(start * block_q, block_q), :]
            lse = lse_ref[0, pl.ds(start * block_q, block_q), 0]
            delta = delta_ref[0, pl.ds(start * block_q, block_q), 0]
            dk_c, dv_c = block_dkv(start, qb, dob, lse, delta)
            return dk_acc + dk_c, dv_acc + dv_c

        n_q = S // block_q
        if is_causal:
            # query blocks strictly before this kv block's diagonal see none
            # of it: query row q_pos attends kv col k_pos iff q_pos >= k_pos
            first = jax.lax.div(k_idx * block_k - causal_offset,
                                jnp.int32(block_q))
            start0 = jnp.clip(first, 0, n_q)
        else:
            start0 = 0
        zeros = jnp.zeros((block_k, q_ref.shape[2]), jnp.float32)
        dk, dv = jax.lax.fori_loop(start0, n_q, body, (zeros, zeros))
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    return kernel


def _pallas_flash_bwd(q, k, v, do, out, lse, is_causal, scale=None,
                      block_q: int = 0, block_k: int = 0):
    """Flash backward: (dq, dk, dv) in the [B, S, H, D] layout."""
    from jax.experimental import pallas as pl

    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    block_q = min(block_q, sq) if block_q else _pick_block(sq)
    block_k = min(block_k, sk) if block_k else _pick_block(sk)
    if not block_q or not block_k or sq % block_q or sk % block_k:
        raise ValueError(
            f"flash backward needs tiling blocks for sq={sq}, sk={sk} — "
            "the forward's tileability gate should have routed this shape "
            "to the XLA path")

    # scale folded into q, matching the forward (the saved lse is the
    # logsumexp of the SCALED scores)
    qr = (q * scale).astype(q.dtype).transpose(0, 2, 1, 3).reshape(
        b * h, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    dor = do.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    outr = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta_i = rowsum(do_i * o_i) — the softmax-jacobian correction term
    # ([BH, S, 1]: trailing unit dim for TPU block tiling, like lse)
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1, keepdims=True)

    off = sk - sq
    dq = pl.pallas_call(
        _make_pallas_bwd_dq(block_q, block_k, is_causal, scale, off,
                            seq_k=sk),
        name="flash_attention_bwd_dq",
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
    )(qr, kr, vr, dor, lse, delta)

    dk, dv = pl.pallas_call(
        _make_pallas_bwd_dkv(block_q, block_k, is_causal, off,
                             seq_q=sq),
        name="flash_attention_bwd_dkv",
        grid=(b * h, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
    )(qr, kr, vr, dor, lse, delta)

    unfold = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return unfold(dq, sq), unfold(dk, sk), unfold(dv, sk)


# ---------------------------------------------------------------------------
# Packed flat-layout kernels: [B, S, H*D] with 128//D heads per grid cell.
#
# Why: D=64 leaves single-head blocks at half the 128-lane width, and the
# [B,S,H,D] -> [B*H,S,D] fold costs SIX materialised transposes per layer
# (fwd q/k/v + refolds in the backward). Packing 2 heads per cell makes the
# minor block dim a full 128 lanes ON THE MODEL'S NATIVE [B,S,H*D] layout —
# zero transposes anywhere — and the single-block structure lets ONE
# backward kernel produce dq, dk AND dv from one shared probability
# recompute (the two-kernel path recomputes p twice). Single-block only
# (the [S,S] score block lives in VMEM): longer sequences keep the blocked
# [B*H,S,D] path above; ring attention owns the sharded-seq regime.
# ---------------------------------------------------------------------------


def _packed_group(h: int, d: int) -> int:
    """Heads per grid cell for the packed layout (0 = ineligible)."""
    if d > 128 or 128 % d or d % 8:
        return 0
    hp = 128 // d
    return hp if h % hp == 0 else 0


def _packed_eligible(q, k) -> int:
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    hp = _packed_group(h, d)
    # <=512 keeps the fused backward's [S,S] fp32 intermediates well inside
    # VMEM and leaves S>=1024 on the blocked multi-block kernels (whose
    # causal block-skip bounds need their own live coverage)
    if hp and hk == h and sq == sk and sq % 128 == 0 and sq <= 512:
        return hp
    return 0


_LOG2_E = float(np.log2(np.e))


def _make_packed_fwd(S, d, hp, is_causal, q_cst=1.0):
    """Packed forward in the BASE-2 domain: the caller folds
    ``scale * log2(e)`` into q, so the score matrix arrives pre-multiplied
    and the softmax runs on ``exp2`` directly — one fewer VPU multiply per
    [S, S] element than ``exp`` (which lowers to mul-by-log2e + pow2).
    Probabilities are identical: ``2^(c*s - c*m) == e^(s - m)``. The saved
    lse is ALSO base-2 (``m2 + log2(l)``); the packed backward consumes it
    in the same domain."""
    return _make_packed_fwd_general(S, S, 0, d, hp, is_causal, q_cst=q_cst)


def _make_packed_fwd_general(Sq, Sk, q_off, d, hp, is_causal, q_cst=1.0):
    """Packed forward over a [Sq, Sk] score tile: q rows sit at absolute
    positions ``q_off + i``, k columns at ``j`` (k is always a prefix of
    the sequence in the split-causal decomposition). ``q_cst`` is the
    scale*log2(e) fold applied IN-KERNEL on the narrow [Sq, d] q tile —
    an XLA-level prescale pass would touch the full [B, S, H*D] array."""
    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref):
        if is_causal:
            qp = q_off + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
            kp = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
            causal = qp >= kp  # hoisted: shared by all heads in the cell
        for i in range(hp):
            sl = slice(i * d, (i + 1) * d)
            q = q_ref[0, :, sl]  # [Sq, d]
            if q_cst != 1.0:
                q = (q * q_cst).astype(q_ref.dtype)
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if is_causal:
                s = jnp.where(causal, s, -jnp.inf)
            m = jnp.max(s, axis=1)
            p = jnp.exp2(s - m[:, None])
            l = jnp.sum(p, axis=1)
            o = jax.lax.dot_general(p.astype(v.dtype), v,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            o_ref[0, :, sl] = (o / l[:, None]).astype(o_ref.dtype)
            lse_ref[0, 0, i, :] = m + jnp.log2(l)
    return kernel


def _make_packed_bwd(S, d, hp, is_causal, scale, q_cst=1.0):
    """Fused dq/dk/dv: one probability recompute serves all three grads
    (the blocked path pays it twice across its dq and dkv kernels).

    Base-2 domain like the packed forward: q arrives pre-scaled by
    ``scale * log2(e)`` and lse is base-2, so the recompute is one
    ``exp2`` with no extra multiply. ``ds`` (natural-domain softmax vjp,
    p*(dp-delta)) is unaffected — p's VALUES are domain-independent. The
    chain rule per input: dq = (ds @ k) * scale (w.r.t. UNSCALED q),
    dk = ds^T @ q_scaled / log2(e) (the pre-fold over-scales q by log2(e),
    divided back out on the narrow [S, d] result)."""
    return _make_packed_bwd_general(S, S, 0, d, hp, is_causal, scale,
                                    q_cst=q_cst)


def _make_packed_bwd_general(Sq, Sk, q_off, d, hp, is_causal, scale,
                             q_cst=1.0):
    """Fused dq + dk/dv over a [Sq, Sk] score tile (q rows at absolute
    positions ``q_off + i``; k a sequence prefix). In the split-causal
    decomposition a call's dk/dv are PARTIAL (only its q rows' share);
    the wrapper sums overlapping k regions."""
    inv_log2e = 1.0 / _LOG2_E

    def kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
               dq_ref, dk_ref, dv_ref):
        if is_causal:
            qp = q_off + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
            kp = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
            causal = qp >= kp  # hoisted: shared by all heads in the cell
        for i in range(hp):
            sl = slice(i * d, (i + 1) * d)
            q = q_ref[0, :, sl]
            if q_cst != 1.0:
                # scale*log2(e) fold, in-kernel on the narrow [Sq, d] tile
                q = (q * q_cst).astype(q_ref.dtype)
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            do = do_ref[0, :, sl]
            o = o_ref[0, :, sl]
            lse = lse_ref[0, 0, i, :]
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=1)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            p = jnp.exp2(s - lse[:, None])
            if is_causal:
                p = jnp.where(causal, p, 0.0)
            pb = p.astype(do.dtype)
            dv = jax.lax.dot_general(pb, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(q.dtype)
            dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dq_ref[0, :, sl] = (dq * scale).astype(dq_ref.dtype)
            dk_ref[0, :, sl] = (dk * inv_log2e).astype(dk_ref.dtype)
            dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)
    return kernel


def _pallas_flash_fwd_packed(q, k, v, is_causal, scale=None):
    """(out[B,S,H,D], lse[B,G,hp,S]) via the packed flat layout."""
    from jax.experimental import pallas as pl

    b, S, h, d = q.shape
    hp = _packed_eligible(q, k)
    assert hp, "caller must gate on _packed_eligible"
    G = h // hp
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    hd = h * d
    # base-2 domain: scale*log2(e) folded into q INSIDE the kernel (an
    # XLA-level prescale would be a full [B, S, H*D] elementwise pass)
    qf = q.reshape(b, S, hd)
    kf = k.reshape(b, S, hd)
    vf = v.reshape(b, S, hd)
    blk = pl.BlockSpec((1, S, hp * d), lambda bb, g: (bb, 0, g))
    from jax.experimental.pallas import tpu as pltpu

    out, lse = pl.pallas_call(
        _make_packed_fwd(S, d, hp, is_causal, q_cst=scale * _LOG2_E),
        name="flash_attention_packed_fwd",
        grid=(b, G),
        in_specs=[blk, blk, blk],
        out_specs=[blk, pl.BlockSpec((1, 1, hp, S),
                                     lambda bb, g: (bb, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, G, hp, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(qf, kf, vf)
    return out.reshape(b, S, h, d), lse


def _pallas_flash_bwd_packed(q, k, v, do, out, lse, is_causal, scale=None):
    """(dq, dk, dv) in [B,S,H,D] via the fused packed backward."""
    from jax.experimental import pallas as pl

    b, S, h, d = q.shape
    hp = _packed_eligible(q, k)
    assert hp, "caller must gate on _packed_eligible"
    G = h // hp
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    hd = h * d
    # base-2 domain, matching the packed forward (lse is base-2); the
    # scale*log2(e) fold happens in-kernel like the forward
    qf = q.reshape(b, S, hd)
    kf = k.reshape(b, S, hd)
    vf = v.reshape(b, S, hd)
    dof = do.reshape(b, S, hd)
    of = out.reshape(b, S, hd)
    blk = pl.BlockSpec((1, S, hp * d), lambda bb, g: (bb, 0, g))
    lse_blk = pl.BlockSpec((1, 1, hp, S), lambda bb, g: (bb, g, 0, 0))
    from jax.experimental.pallas import tpu as pltpu

    dq, dk, dv = pl.pallas_call(
        _make_packed_bwd(S, d, hp, is_causal, scale,
                         q_cst=scale * _LOG2_E),
        name="flash_attention_packed_bwd",
        grid=(b, G),
        in_specs=[blk, blk, blk, blk, blk, lse_blk],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((b, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, S, hd), k.dtype),
                   jax.ShapeDtypeStruct((b, S, hd), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(qf, kf, vf, dof, of, lse)
    r4 = lambda x: x.reshape(b, S, h, d)
    return r4(dq), r4(dk), r4(dv)


def flash_path_active(mask=None) -> bool:
    """True when `dot_product_attention` would take the Pallas flash path
    (TPU, kernels enabled, no additive mask, single-device mesh). Models use
    this to pick a remat structure: on the flash path the custom-VJP's O(S)
    residuals (out + logsumexp) are worth SAVING across `jax.checkpoint`
    boundaries instead of re-running the forward kernel in the backward."""
    return (
        _on_tpu()
        and flags.get_flags("use_pallas_kernels")["use_pallas_kernels"]
        and mask is None
        and not _multi_device_mesh_active()
    )


def dot_product_attention(q, k, v, mask=None, is_causal=False):
    """Public entry: picks Pallas on TPU (when enabled, mask-free, and not
    under a multi-device mesh), XLA reference elsewhere. Differentiable:
    the pallas path uses the flash BACKWARD kernels (`_pallas_flash_bwd`,
    O(S) memory via saved logsumexp); XLA-recompute backward remains only
    as the untileable-shape fallback."""
    use_pallas = flash_path_active(mask)
    if use_pallas:
        return _flash_custom_vjp(q, k, v, is_causal)
    return _xla_attention(q, k, v, mask=mask, is_causal=is_causal)


def _multi_device_mesh_active() -> bool:
    """GSPMD cannot auto-partition a pallas custom call across a >1-device
    mesh — the XLA formulation (which it CAN shard) is the right lowering
    there; pallas serves the single-chip hot path."""
    try:
        from ...parallel.mesh import get_mesh

        mesh = get_mesh()
        return mesh is not None and mesh.size > 1
    except Exception:
        return False


# custom VJP: pallas forward AND pallas flash backward — the saved residuals
# are (q, k, v, o, lse): O(S) memory, never the [S, S] score matrix. Falls
# back to XLA-recompute backward when shapes don't tile.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_custom_vjp(q, k, v, is_causal):
    return _pallas_flash_attention(q, k, v, is_causal=is_causal)


def _flash_fwd(q, k, v, is_causal):
    if _packed_eligible(q, k):
        out, lse = _pallas_flash_fwd_packed(q, k, v, is_causal)
        return out, (q, k, v, out, lse)  # packed lse is 4-D (the marker)
    fwd = _pallas_flash_fwd_lse(q, k, v, is_causal=is_causal)
    if fwd is None:  # untileable shapes: XLA path, recompute backward
        return (_pallas_flash_attention(q, k, v, is_causal=is_causal),
                (q, k, v, None, None))
    out, lse = fwd
    return out, (q, k, v, out, lse)


def _flash_bwd(is_causal, res, g):
    q, k, v, out, lse = res
    if lse is not None and lse.ndim == 4:  # packed path residuals
        return _pallas_flash_bwd_packed(q, k, v, g, out, lse, is_causal)
    if lse is not None:
        return _pallas_flash_bwd(q, k, v, g, out, lse, is_causal)
    _, vjp = jax.vjp(lambda q_, k_, v_: _xla_attention(
        q_, k_, v_, is_causal=is_causal), q, k, v)
    return vjp(g)


_flash_custom_vjp.defvjp(_flash_fwd, _flash_bwd)
