"""Fused multi-tensor optimizer update — flat buffers, in-place aliasing.

The ResNet-50 ledger's dominant residual (SCALING.md §3b, ~7 ms of a
56.6 ms step) is the multi-tensor optimizer's stack/unstack relayouts:
XLA's only route to one-launch-per-group updates is materialising packed
temporaries (``jnp.stack``/``concatenate``) and slicing the results back,
and three grouping restructurings each measured WORSE — the relayout cost
is intrinsic to the XLA formulation, not to the grouping choice. This is
the same "build the layout the compiler can't reach" failure mode
``head_dx`` beat with a hand kernel.

This module is that hand kernel, as a family:

- Every eligible group (same dtype / state structure / static extras)
  gets ONE flat ``[rows, 128]`` layout (``FlatPlan``): each tensor starts
  on a fresh row, tail lanes zero-padded. The layout is built ONCE per
  compiled program at trace time from static shapes; offsets/segment ids
  are host numpy.
- The kernels consume the flat param/grad/moment buffers directly with a
  1-D grid over row tiles and write the new param/moments IN PLACE via
  ``input_output_aliases`` — no packed temporary exists, no unstack, and
  optimizer state never leaves the flat layout between steps (the group
  update returns per-tensor ROW SLICES of the flat state, so the next
  step's "pack" is a major-axis concat, a pure memcpy — only the grads
  (born shaped from autodiff) and the updated params (consumed shaped by
  the model) cross the shaped<->flat boundary, once each per step).
- Per-group scalars (lr, betas, eps, weight decay, bias-correction step,
  the AMP ``found_inf`` skip flag) ride in SMEM; groups are already split
  by static extras (AdamW decay-vs-no-decay), so no per-row coefficient
  tables are needed. Lamb's per-tensor trust ratios use the plan's
  segment ids: one kernel pass updates the moments and emits the raw
  update ``r``, then a flat segment-sum epilogue (no relayout — all
  operands stay ``[rows, 128]``) applies the trust-scaled step.

Kinds: ``sgd``, ``momentum`` (+Nesterov), ``adam`` (Adam/AdamW, with or
without fp32 master weights), ``lamb``. Dispatch mirrors the other Pallas
families: TPU + flags + single-device, with the existing stack/flat XLA
grouping as the CPU/mesh/fallback path and ``FORCE_INTERPRET`` so tier-1
CPU tests run the real kernels through the pallas interpreter.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["FlatPlan", "fused_update_active", "fused_update_signature",
           "apply_flat_update", "selection_count", "reset_selection_count"]

# tests set this True to force the kernels (pallas interpret mode) on CPU
FORCE_INTERPRET = False

_HYPER_LEN = 8  # SMEM scalar vector: [lr, 1-b1^step, skip, b1/mu, b2, eps,
#                 wd, 1-b2^step] (the bias corrections are taken outside
#                 the kernel: Mosaic lowers no scalar pow)

_KINDS = ("sgd", "momentum", "adam", "lamb")


def _on_tpu() -> bool:
    from .flash_attention import _on_tpu as on_tpu

    return on_tpu()


def _interp() -> bool:
    return FORCE_INTERPRET and not _on_tpu()


def fused_update_active(n_tensors: int, kind: Optional[str]) -> bool:
    """True when a parameter group should take the flat Pallas update:
    TPU (or the test force), kernels + flag enabled, single device, a
    supported optimizer kind, and enough tensors that grouping matters
    (singletons update solo — one fused XLA launch already amortizes)."""
    from .flash_attention import _multi_device_mesh_active

    if kind not in _KINDS:
        return False
    f = flags.get_flags(["use_pallas_kernels", "use_pallas_fused_update"])
    if not (f["use_pallas_kernels"] and f["use_pallas_fused_update"]):
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    return n_tensors >= 2


def fused_update_signature() -> Tuple:
    """Hashable dispatch state for jit-cache keys: a runtime flag flip or
    test FORCE_INTERPRET toggle must rebuild the compiled step (the flat
    layout choice is baked in at trace time)."""
    f = flags.get_flags(["use_pallas_kernels", "use_pallas_fused_update"])
    return (f["use_pallas_kernels"], f["use_pallas_fused_update"],
            FORCE_INTERPRET)


# trace-time selection counter (decode_attention convention): lets the
# resnet_profile smoke gate assert "the fused path was selected for this
# program" without a chip.
_selected = {"count": 0}


def selection_count() -> int:
    return _selected["count"]


def reset_selection_count() -> None:
    _selected["count"] = 0


# ---------------------------------------------------------------------------
# FlatPlan: the once-per-program layout
# ---------------------------------------------------------------------------


class FlatPlan:
    """Static flat layout of a tensor group: tensor i owns rows
    [row_offsets[i], row_offsets[i] + rows[i]) of a ``[total_rows, 128]``
    buffer (rows[i] = ceil(size_i / 128); tail lanes and tail rows are
    zero so padding contributes exact zeros to every update kind)."""

    LANES = 128

    def __init__(self, shapes: Sequence[Tuple[int, ...]]):
        self.shapes = [tuple(int(d) for d in s) for s in shapes]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.rows = [-(-n // self.LANES) for n in self.sizes]
        self.row_offsets = np.concatenate(
            [[0], np.cumsum(self.rows)]).astype(np.int32)
        used = int(self.row_offsets[-1])
        # row-tile alignment: bf16 buffers need (16, 128) tiles; pad the
        # TOTAL (not each tensor — the kernel treats the buffer uniformly)
        self.total_rows = -(-used // 16) * 16
        self.block_rows = next(b for b in (512, 256, 128, 64, 32, 16)
                               if self.total_rows % b == 0)
        self.grid = self.total_rows // self.block_rows
        # per-row tensor index (padding rows -> segment len(shapes), which
        # every consumer drops); only Lamb's trust reduction reads this
        seg = np.full((self.total_rows,), len(self.shapes), np.int32)
        for i in range(len(self.shapes)):
            seg[self.row_offsets[i]:self.row_offsets[i + 1]] = i
        self.seg_ids = seg

    def pack(self, vals: Sequence[jax.Array], dtype=None) -> jax.Array:
        """Shaped (or already-flat-segment) tensors -> one [R, 128]
        buffer. A value that already IS this tensor's flat segment (the
        persistent state case) rides through as a major-axis concat
        operand — no relayout."""
        segs: List[jax.Array] = []
        for v, rows, n in zip(vals, self.rows, self.sizes):
            if v.ndim == 2 and v.shape == (rows, self.LANES):
                segs.append(v if dtype is None else v.astype(dtype))
                continue
            flat = v.reshape(-1)
            if dtype is not None:
                flat = flat.astype(dtype)
            pad = rows * self.LANES - n
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)])
            segs.append(flat.reshape(rows, self.LANES))
        tail = self.total_rows - int(self.row_offsets[-1])
        if tail:
            segs.append(jnp.zeros((tail, self.LANES), segs[0].dtype))
        return jnp.concatenate(segs, axis=0)

    def segment(self, buf: jax.Array, i: int) -> jax.Array:
        """Tensor i's rows of ``buf`` — a major-dim slice (state stays in
        this form between steps; no reshape ever touches it)."""
        r0 = int(self.row_offsets[i])
        return jax.lax.slice_in_dim(buf, r0, r0 + self.rows[i], axis=0)

    def unpack(self, buf: jax.Array, i: int) -> jax.Array:
        """Tensor i back in its model shape (the one per-step
        flat->shaped crossing params need)."""
        seg = self.segment(buf, i)
        return seg.reshape(-1)[:self.sizes[i]].reshape(self.shapes[i])


# ---------------------------------------------------------------------------
# kernels — hyper scalars in SMEM, buffers blocked (block_rows, 128),
# params/moments aliased in place
# ---------------------------------------------------------------------------


def _gate(skip, old, new):
    # found_inf short-circuit INSIDE the kernel: skip > 0 keeps every
    # buffer bit-identical (GradScaler contract — a skipped step must not
    # touch moments either)
    return jnp.where(skip > 0, old, new)


def _sgd_kernel(h_ref, p_ref, g_ref, op_ref):
    lr = h_ref[0].astype(p_ref.dtype)
    skip = h_ref[2]
    p = p_ref[...]
    op_ref[...] = _gate(skip, p, p - lr * g_ref[...].astype(p.dtype))


def _momentum_kernel(nesterov: bool):
    def kernel(h_ref, p_ref, g_ref, v_ref, op_ref, ov_ref):
        p, v = p_ref[...], v_ref[...]
        g = g_ref[...].astype(v.dtype)
        mu = h_ref[3].astype(v.dtype)
        lr = h_ref[0].astype(p.dtype)
        skip = h_ref[2]
        v_new = mu * v + g
        upd = g + mu * v_new if nesterov else v_new
        op_ref[...] = _gate(skip, p, p - lr * upd.astype(p.dtype))
        ov_ref[...] = _gate(skip, v, v_new)

    return kernel


def _adam_kernel(has_master: bool, decoupled: bool):
    def kernel(h_ref, p_ref, g_ref, m_ref, v_ref, *refs):
        if has_master:
            (w_ref, op_ref, om_ref, ov_ref, ow_ref) = refs
        else:
            (op_ref, om_ref, ov_ref) = refs
        lr, bc1, skip = h_ref[0], h_ref[1], h_ref[2]
        b1, b2, eps, wd, bc2 = (h_ref[3], h_ref[4], h_ref[5], h_ref[6],
                                h_ref[7])
        p, m, v = p_ref[...], m_ref[...], v_ref[...]
        dt = m.dtype
        gf = g_ref[...].astype(dt)
        m_new = b1.astype(dt) * m + (1 - b1).astype(dt) * gf
        v_new = b2.astype(dt) * v + (1 - b2).astype(dt) * gf * gf
        # bias correction in fp32 (matches Adam._update_one: the division
        # by a strong-typed fp32 scalar promotes)
        mhat = m_new.astype(jnp.float32) / bc1
        vhat = v_new.astype(jnp.float32) / bc2
        upd = lr * mhat / (jnp.sqrt(vhat) + eps)
        if has_master:
            w = w_ref[...]
            w_new = w - upd
            if decoupled:
                w_new = w_new - lr * wd * w  # decay off the OLD master
            ow_ref[...] = _gate(skip, w, w_new)
            op_ref[...] = _gate(skip, p, w_new.astype(p.dtype))
        else:
            p_new = p - upd.astype(p.dtype)
            if decoupled:
                p_new = p_new - (lr * wd).astype(p.dtype) * p
            op_ref[...] = _gate(skip, p, p_new)
        om_ref[...] = _gate(skip, m, m_new)
        ov_ref[...] = _gate(skip, v, v_new)

    return kernel


def _lamb_kernel(has_master: bool):
    # pass A of the two-pass Lamb: moments in place + raw update r out;
    # the trust-ratio reduction and the parameter step run as a FLAT
    # segment-sum epilogue outside (no relayout — see apply_flat_update)
    def kernel(h_ref, p_ref, g_ref, m_ref, v_ref, *refs):
        if has_master:
            (w_ref, om_ref, ov_ref, or_ref) = refs
        else:
            (om_ref, ov_ref, or_ref) = refs
        bc1, skip = h_ref[1], h_ref[2]
        b1, b2, eps, wd, bc2 = (h_ref[3], h_ref[4], h_ref[5], h_ref[6],
                                h_ref[7])
        m, v = m_ref[...], v_ref[...]
        pf = (w_ref[...] if has_master
              else p_ref[...].astype(jnp.float32))
        gf = g_ref[...].astype(jnp.float32)
        m_new = b1 * m + (1 - b1) * gf
        v_new = b2 * v + (1 - b2) * gf * gf
        mhat = m_new / bc1
        vhat = v_new / bc2
        or_ref[...] = mhat / (jnp.sqrt(vhat) + eps) + wd * pf
        om_ref[...] = _gate(skip, m, m_new)
        ov_ref[...] = _gate(skip, v, v_new)

    return kernel


def _run(kind: str, kernel, plan: FlatPlan, bufs: Sequence[jax.Array],
         hyper: jax.Array, out_structs, aliases: Dict[int, int]):
    br = plan.block_rows
    block = lambda: pl.BlockSpec((br, FlatPlan.LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        name="apply_flat_update_" + kind,
        grid=(plan.grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [block() for _ in bufs],
        out_specs=[block() for _ in out_structs],
        out_shape=list(out_structs),
        input_output_aliases=aliases,
        interpret=_interp(),
    )(hyper, *bufs)


def _struct(like):
    return jax.ShapeDtypeStruct(like.shape, like.dtype)


# ---------------------------------------------------------------------------
# group driver
# ---------------------------------------------------------------------------


def apply_flat_update(kind: str, plan: FlatPlan,
                      pvals: Sequence[jax.Array],
                      gvals: Sequence[jax.Array],
                      svals: Sequence[Dict[str, jax.Array]],
                      hyper: Dict[str, Any], lr, step,
                      skip=None) -> Tuple[List[jax.Array],
                                          List[Dict[str, jax.Array]]]:
    """One fused update over a whole tensor group.

    ``svals[i][k]`` may arrive shaped (first step / restored checkpoint)
    or as this plan's flat row segment (every subsequent step — the form
    this function returns state in). ``hyper`` carries the group's static
    scalars; ``skip`` is the optional traced found_inf flag (non-None ->
    the kernels keep every buffer unchanged when it is > 0).
    Returns (new shaped params, new FLAT-SEGMENT states).
    """
    _selected["count"] += 1  # trace-time: once per compiled program
    state_keys = list(svals[0].keys()) if svals and svals[0] else []
    has_master = "master" in state_keys
    mdt = jnp.float32 if has_master else pvals[0].dtype

    skipf = (jnp.float32(0.0) if skip is None
             else jnp.asarray(skip, jnp.float32))
    hvec = jnp.zeros((_HYPER_LEN,), jnp.float32)
    hvec = hvec.at[0].set(jnp.asarray(lr, jnp.float32))
    hvec = hvec.at[2].set(skipf)

    def with_betas(hvec):
        stepf = jnp.asarray(step, jnp.float32)
        b1, b2 = np.float32(hyper["beta1"]), np.float32(hyper["beta2"])
        return (hvec.at[3].set(b1).at[4].set(b2)
                .at[1].set(1 - b1 ** stepf).at[7].set(1 - b2 ** stepf))

    pbuf = plan.pack(pvals)
    gbuf = plan.pack(gvals, dtype=pvals[0].dtype)
    sbufs = {k: plan.pack([s[k] for s in svals]) for k in state_keys}

    if kind == "sgd":
        out = _run(kind, _sgd_kernel, plan, [pbuf, gbuf], hvec,
                   [_struct(pbuf)], {1: 0})
        new_p_buf, new_sbufs = out[0], {}
    elif kind == "momentum":
        hvec = hvec.at[3].set(np.float32(hyper["momentum"]))
        out = _run(kind, _momentum_kernel(bool(hyper.get("nesterov"))),
                   plan, [pbuf, gbuf, sbufs["velocity"]], hvec,
                   [_struct(pbuf), _struct(sbufs["velocity"])],
                   {1: 0, 3: 1})
        new_p_buf, new_sbufs = out[0], {"velocity": out[1]}
    elif kind == "adam":
        hvec = with_betas(hvec)
        hvec = hvec.at[5].set(np.float32(hyper["epsilon"]))
        hvec = hvec.at[6].set(np.float32(hyper.get("decay", 0.0)))
        decoupled = bool(hyper.get("decoupled")) and \
            float(hyper.get("decay", 0.0)) != 0.0
        bufs = [pbuf, gbuf, sbufs["moment1"], sbufs["moment2"]]
        outs = [_struct(pbuf), _struct(sbufs["moment1"]),
                _struct(sbufs["moment2"])]
        aliases = {1: 0, 3: 1, 4: 2}
        if has_master:
            bufs.append(sbufs["master"])
            outs.append(_struct(sbufs["master"]))
            aliases[5] = 3
        out = _run(kind, _adam_kernel(has_master, decoupled), plan, bufs,
                   hvec, outs, aliases)
        new_p_buf = out[0]
        new_sbufs = {"moment1": out[1], "moment2": out[2]}
        if has_master:
            new_sbufs["master"] = out[3]
    elif kind == "lamb":
        hvec = with_betas(hvec)
        hvec = hvec.at[5].set(np.float32(hyper["epsilon"]))
        hvec = hvec.at[6].set(np.float32(hyper.get("decay", 0.0)))
        bufs = [pbuf, gbuf, sbufs["moment1"], sbufs["moment2"]]
        outs = [_struct(sbufs["moment1"]), _struct(sbufs["moment2"]),
                jax.ShapeDtypeStruct(pbuf.shape, jnp.float32)]
        aliases = {3: 0, 4: 1}
        if has_master:
            bufs.append(sbufs["master"])
        out = _run(kind, _lamb_kernel(has_master), plan, bufs, hvec, outs,
                   aliases)
        m_new, v_new, r = out
        # flat epilogue: per-tensor trust ratios via segment-sum — every
        # operand stays [R, 128], so XLA emits plain reductions, not the
        # stacked-shape relayouts this family exists to kill
        pf = sbufs["master"] if has_master else pbuf.astype(jnp.float32)
        seg = jnp.asarray(plan.seg_ids)
        nseg = len(plan.shapes) + 1  # +1 absorbs padding rows
        w2 = jax.ops.segment_sum(jnp.sum(pf * pf, axis=1), seg, nseg)
        r2 = jax.ops.segment_sum(jnp.sum(r * r, axis=1), seg, nseg)
        w_n, r_n = jnp.sqrt(w2), jnp.sqrt(r2)
        trust = jnp.where((w_n > 0) & (r_n > 0), w_n / r_n, 1.0)
        lrf = jnp.asarray(lr, jnp.float32)
        pf_new = pf - lrf * trust[seg][:, None] * r
        pf_new = jnp.where(skipf > 0, pf, pf_new)
        new_p_buf = pf_new.astype(pbuf.dtype)
        new_sbufs = {"moment1": m_new, "moment2": v_new}
        if has_master:
            new_sbufs["master"] = pf_new
    else:  # pragma: no cover — fused_update_active gates kinds
        raise ValueError(f"unknown fused update kind {kind!r}")

    new_p = [plan.unpack(new_p_buf, i) for i in range(len(pvals))]
    new_s = [{k: plan.segment(new_sbufs[k], i) for k in state_keys}
             for i in range(len(pvals))]
    return new_p, new_s
