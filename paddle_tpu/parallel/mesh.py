"""Hybrid device mesh — the TPU-native ``HybridCommunicateGroup`` substrate.

Reference counterpart: ``python/paddle/distributed/fleet/base/topology.py``
(``CommunicateTopology`` / ``HybridCommunicateGroup``; SURVEY.md §2.2) which
builds per-axis NCCL process groups over the N-D rank grid. On TPU the same
topology is ONE ``jax.sharding.Mesh`` whose named axes are the parallelism
axes; XLA lowers collectives onto ICI rings per axis, so there is nothing to
"create" per group — an axis name *is* a process group.

Axis order follows the reference's hybrid order [dp, pp, sharding, mp, sep]
so rank math matches ``paddle.distributed.fleet``'s coordinate layout.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# the reference's hybrid-parallel axis order (outermost → innermost):
# data, pipeline, zero-sharding, tensor(model), sequence(sep),
# expert(ep — r7: innermost so MoE's all-to-all dispatch rides the
# fastest ICI neighbours, the same argument that puts mp inside).
# r23 adds 'sp' — the SERVING sequence-parallel prefill axis (ISSUE
# 18): prefill slabs shard their batch/chunk rows over it while decode
# stays replicated. It sits between sep and ep (inner enough for fast
# ICI on the ring/all-to-all attention exchanges); degree 1 everywhere
# it is unused, so existing mesh shapes and rank math are unchanged.
HYBRID_AXES: Tuple[str, ...] = ("dp", "pp", "sharding", "mp", "sep",
                                "sp", "ep")

_GLOBAL_MESH: Optional[Mesh] = None


def create_hybrid_mesh(
    dp: int = 1,
    pp: int = 1,
    sharding: int = 1,
    mp: int = 1,
    sep: int = 1,
    ep: int = 1,
    sp: int = 1,
    devices: Optional[Sequence] = None,
    set_as_global: bool = True,
) -> Mesh:
    """Build the hybrid mesh over ``devices`` (default: all jax devices).

    Degrees must multiply to the device count. Axis placement matters on real
    hardware: the innermost axes (mp, sep) get the fastest ICI neighbours,
    matching the reference's convention of putting tensor-parallel on NVLink.
    """
    if devices is None:
        devices = jax.devices()
    degrees = {"dp": dp, "pp": pp, "sharding": sharding, "mp": mp,
               "sep": sep, "sp": sp, "ep": ep}
    total = int(np.prod(list(degrees.values())))
    if total != len(devices):
        raise ValueError(
            f"hybrid degrees {degrees} multiply to {total} but "
            f"{len(devices)} devices are available"
        )
    shape = tuple(degrees[a] for a in HYBRID_AXES)
    arr = np.asarray(devices).reshape(shape)
    mesh = Mesh(arr, HYBRID_AXES)
    if set_as_global:
        set_mesh(mesh)
    return mesh


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: every
    call site in the tree routes through here so they all run with the
    same setting."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _GLOBAL_MESH


def mesh_axis_size(axis: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or _GLOBAL_MESH
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def named_sharding(spec: PartitionSpec, mesh: Optional[Mesh] = None
                   ) -> Optional[NamedSharding]:
    """NamedSharding on the (given or global) mesh; None when no mesh."""
    mesh = mesh or _GLOBAL_MESH
    if mesh is None:
        return None
    return NamedSharding(mesh, spec)


def host_to_global(x, spec: PartitionSpec, mesh: Optional[Mesh] = None):
    """Turn a host value (identical on every process) into a global
    ``jax.Array`` sharded by ``spec`` over the mesh.

    Needed by the multi-controller runtime (``init_parallel_env`` with
    ``PADDLE_TRAINERS_NUM>1``): jit rejects host numpy inputs with
    process-spanning shardings, so sharded train steps convert their inputs
    through here — each process materialises only its addressable shards
    (``jax.make_array_from_callback``). Single-process: a plain device_put.
    """
    mesh = mesh or _GLOBAL_MESH
    if mesh is None:
        return jax.device_put(np.asarray(x))
    sh = NamedSharding(mesh, spec)
    x = np.asarray(x)
    if jax.process_count() == 1:
        return jax.device_put(x, sh)
    return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])


def with_sharding_constraint(x, spec: PartitionSpec, mesh: Optional[Mesh] = None):
    """Sharding hint for XLA GSPMD; no-op without a mesh (single chip/tests).

    This is the TPU-native analog of the reference's explicit collective ops
    inside parallel layers (``c_identity`` / ``mp_allreduce_sum``): instead of
    calling a collective, we constrain layouts and let GSPMD insert the
    collective where layouts change.
    """
    mesh = mesh or _GLOBAL_MESH
    if mesh is None:
        return x
    if mesh.devices.size == 1:
        # a 1-device mesh constrains nothing, and pinning it would break
        # callers whose ARGUMENTS ride a bigger mesh than the (stale)
        # global one — this jax rejects the device-set mismatch outright
        return x
    _guard_manual_program(spec, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _guard_manual_program(spec, mesh=None) -> None:
    """Raise (naming the offending pipeline layer) when a GSPMD constraint
    is staged inside a fully-manual shard_map trace — the compiled 1F1B
    program — where it would deadlock on a real mesh. The flag lives in
    fleet's mp_layers (set by the 1F1B engine around its trace).

    Only a constraint that NAMES a mesh axis of size > 1 is an error: a
    fully-replicated spec (or one over size-1 axes) stages no collective
    and cannot deadlock — TP-capable layers legitimately emit those on
    pp-only meshes where their GSPMD branch is a no-op."""
    mesh = mesh or _GLOBAL_MESH
    if mesh is None:
        return
    names = []
    for e in tuple(spec):
        if e is None:
            continue
        names.extend(e if isinstance(e, tuple) else (e,))
    if not any(n in mesh.axis_names and int(mesh.shape[n]) > 1
               for n in names):
        return
    try:
        from ..distributed.fleet.meta_parallel.parallel_layers import (
            mp_layers as _mpl,
        )
    except Exception:
        return
    if _mpl.in_manual_program():
        who = _mpl._CURRENT_PIPE_LAYER_VAR.get()
        raise ValueError(
            f"layer {who or '<unknown>'} stages a GSPMD sharding "
            f"constraint (spec {spec}) inside the compiled 1F1B pipeline "
            "program. GSPMD collectives cannot ride inside the lax.switch "
            "stage dispatch (only the selected stage's devices would "
            "execute them — deadlock on a real mesh). Make the layer "
            "mp-free inside pipeline chunks, or give it a manual-TP "
            "forward (mp_layers.manual_tp_fns) like "
            "Column/RowParallelLinear.")
