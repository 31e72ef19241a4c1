"""The Tensor.

TPU-native counterpart of ``phi::DenseTensor`` + Python ``paddle.Tensor``
(``paddle/phi/core/dense_tensor.h`` + pybind eager tensor; SURVEY.md §2.1).
A ``Tensor`` is a thin mutable wrapper over a ``jax.Array`` (or a jax tracer
while inside ``jit``): XLA/PJRT owns layout, memory and device placement
(replacing the reference's allocator stack), while this wrapper carries the
framework-level state the reference keeps in ``AutogradMeta`` — ``stop_gradient``,
``.grad``, hooks, name, persistable — and the dygraph in-place semantics
(methods like ``add_`` rebind the underlying immutable array).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..enforce import InvalidArgumentError
from . import autograd
from .dtype import convert_dtype, is_floating_dtype
from .place import CPUPlace, CUDAPlace, Place, TPUPlace, device_for_place, expected_place

__all__ = ["Tensor", "to_tensor"]

# Host-sync audit hook (analysis.syncs): while a SyncAudit is active it
# holds ONE context-factory `(kind, value) -> contextmanager`; every
# device→host coercion below enters it so the auditor can record the
# sync (and its call site) without the framework paying anything when no
# audit is running — the list is empty then and the check is one truth
# test. Reference hazard class: the r8 GradScaler per-param ``bool()``.
_SYNC_AUDIT_HOOK: list = []


def _sync_scope(kind, value):
    """Audit scope for one coercion; nullcontext-free fast path."""
    return _SYNC_AUDIT_HOOK[0](kind, value)


_tensor_counter = [0]


def _auto_name(prefix="tensor"):
    _tensor_counter[0] += 1
    return f"{prefix}_{_tensor_counter[0]}"


class Tensor:
    """Mutable framework tensor over an immutable jax value."""

    __slots__ = (
        "_value",
        "stop_gradient",
        "grad",
        "_grad_node",
        "_out_index",
        "_hooks",
        "name",
        "persistable",
        "trainable",
        # auto-parallel dist attrs (reference: DistTensor.dist_attr)
        "process_mesh",
        "placements",
        "__weakref__",
    )

    def __init__(
        self,
        value: Any,
        stop_gradient: bool = True,
        name: Optional[str] = None,
        persistable: bool = False,
    ):
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = None
        self._out_index = 0
        self._hooks = []
        self.name = name or _auto_name()
        self.persistable = persistable
        self.trainable = not stop_gradient
        # auto-parallel dist attrs: None on dense tensors (reference:
        # DistTensor.dist_attr defaults), set by shard_tensor/reshard
        self.process_mesh = None
        self.placements = None

    # -- raw value access ---------------------------------------------------
    @property
    def value(self):
        return self._value

    # -- metadata (TensorMeta analog) --------------------------------------
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self) -> int:
        return self._value.ndim

    @property
    def dtype(self):
        return jnp.dtype(self._value.dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self) -> Place:
        devs = getattr(self._value, "devices", None)
        if devs is None:
            return expected_place()
        dev = next(iter(self._value.devices()))
        kind = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace}.get(
            dev.platform, CPUPlace
        )
        return kind(dev.id)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    @property
    def strides(self):
        """Element strides of the (always densely-packed) row-major layout.

        Reference: ``Tensor.strides`` / ``DenseTensor::strides()``
        (SURVEY §2.1 other-tensor-kinds). XLA arrays carry no user-visible
        aliasing layout — every jax.Array is logically contiguous — so the
        strides are the canonical C-order ones; the strided-READ ops
        (``as_strided``, ``Tensor.unfold``) are gather-based shims over
        this contract, and strided aliasing MUTATION is out of scope by
        design (immutable arrays)."""
        shape = self._value.shape
        out = []
        acc = 1
        for s in reversed(shape):
            out.append(acc)
            acc *= int(s)
        return list(reversed(out))

    def get_strides(self):
        return self.strides

    def is_contiguous(self) -> bool:
        """Always True: XLA buffers have no non-contiguous aliasing views
        (reference Tensor.is_contiguous)."""
        return True

    def contiguous(self) -> "Tensor":
        """Identity — see ``is_contiguous`` (reference Tensor.contiguous)."""
        return self

    def numel(self) -> int:
        return self.size

    def dim(self) -> int:
        return self.ndim

    def is_floating_point(self) -> bool:
        return is_floating_dtype(self.dtype)

    # -- conversion ---------------------------------------------------------
    def numpy(self) -> np.ndarray:
        if _SYNC_AUDIT_HOOK:
            with _sync_scope("tensor.numpy", self._value):
                return self._numpy_impl()
        return self._numpy_impl()

    def _numpy_impl(self) -> np.ndarray:
        return np.asarray(self._value)

    def item(self):
        if _SYNC_AUDIT_HOOK:
            with _sync_scope("tensor.item", self._value):
                return self._item_impl()
        return self._item_impl()

    def _item_impl(self):
        return self._value.item() if hasattr(self._value, "item") else self._value

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        if _SYNC_AUDIT_HOOK:
            with _sync_scope("tensor.numpy", self._value):
                a = self._numpy_impl()
        else:
            a = self._numpy_impl()
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        if _SYNC_AUDIT_HOOK:
            with _sync_scope("tensor.float", self._value):
                return float(self._item_impl())
        return float(self._item_impl())

    def __int__(self):
        if _SYNC_AUDIT_HOOK:
            with _sync_scope("tensor.int", self._value):
                return int(self._item_impl())
        return int(self._item_impl())

    def __bool__(self):
        if _SYNC_AUDIT_HOOK:
            with _sync_scope("tensor.bool", self._value):
                return bool(self._item_impl())
        return bool(self._item_impl())

    def __len__(self):
        if not self._value.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __repr__(self):
        sg = self.stop_gradient
        try:
            data = np.array2string(self.numpy(), precision=6, separator=", ", threshold=64)
        except Exception:
            data = f"<{type(self._value).__name__}>"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
            f"place={self.place}, stop_gradient={sg},\n       {data})"
        )

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor: Optional["Tensor"] = None, retain_graph: bool = False):
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self):  # paddle spelling
        self.grad = None

    def register_hook(self, hook):
        """Hook runs on this tensor's gradient during backward. For
        intermediates it can rewrite the flowing gradient; for leaves it runs
        before accumulation into ``.grad``."""
        if self._grad_node is not None:
            self._grad_node.hooks.setdefault(self._out_index, []).append(hook)
        else:
            self._hooks.append(hook)
        return hook

    def detach(self) -> "Tensor":
        t = Tensor(self._value, stop_gradient=True, name=self.name + ".detach")
        return t

    def element_size(self) -> int:
        """Bytes per element (reference: ``Tensor.element_size``)."""
        return int(jnp.dtype(self._value.dtype).itemsize)

    def pin_memory(self) -> "Tensor":
        """API parity: XLA manages host staging buffers itself."""
        return self

    def contiguous(self) -> "Tensor":
        """API parity: jax.Arrays are always dense/contiguous."""
        return self

    def coalesce(self) -> "Tensor":
        """Reference ``Tensor.coalesce``: only meaningful for sparse COO
        tensors (``paddle.sparse.sparse_coo_tensor(...).coalesce()``,
        where SparseCooTensor implements it); a dense tensor raises like
        the reference does."""
        raise ValueError(
            "coalesce() expects a sparse COO tensor; this tensor is dense "
            "(create one with paddle.sparse.sparse_coo_tensor)")

    def is_contiguous(self) -> bool:
        return True

    def clone(self) -> "Tensor":
        from ..ops.dispatch import run_op

        return run_op("clone", lambda x: x + jnp.zeros((), self._value.dtype), self)

    # -- device / dtype movement -------------------------------------------
    def to(self, device=None, dtype=None, blocking=True) -> "Tensor":
        # dtype casts and device moves both go through run_op so autograd is
        # preserved (jax.device_put is differentiable).
        from ..ops.dispatch import run_op
        from .place import _parse_device

        target_dt = convert_dtype(dtype) if dtype is not None else None
        dev = device_for_place(_parse_device(device)) if device is not None else None

        def f(a):
            if target_dt is not None:
                a = a.astype(target_dt)
            if dev is not None and not isinstance(a, jax.core.Tracer):
                a = jax.device_put(a, dev)
            return a

        t = run_op("to", f, self)
        t.name = self.name
        if self.stop_gradient:
            t.stop_gradient = True
        return t

    def cpu(self) -> "Tensor":
        return self.to("cpu")

    def cuda(self, device_id: int = 0) -> "Tensor":
        return self.to(f"gpu:{device_id}")

    def tpu(self, device_id: int = 0) -> "Tensor":
        return self.to(f"tpu:{device_id}")

    def astype(self, dt) -> "Tensor":
        from ..ops.dispatch import run_op

        target = convert_dtype(dt)
        return run_op("cast", lambda x: x.astype(target), self)

    def cast(self, dt) -> "Tensor":
        return self.astype(dt)

    # -- in-place machinery (dygraph mutation over immutable arrays) -------
    def _inplace_set(self, new_value) -> "Tensor":
        """Rebind the underlying array (the dygraph ``x.add_(y)`` discipline).

        In-place ops on tensors that participate in an active autograd graph
        would corrupt saved VJP residuals, mirroring the reference's inplace
        version-counter check — so we forbid them on non-leaf tensors.

        Static-graph hook: assigning a *symbolic* value (a recorded op's
        output) onto an eager tensor — BN running-stat updates etc. — keeps
        the eager value and schedules a replay-time write-back instead.
        """
        from ..static.graph import _SymbolicValue, register_state_write

        if isinstance(new_value, _SymbolicValue):
            register_state_write(self, new_value)
            return self
        if self._grad_node is not None:
            raise InvalidArgumentError(
                f"In-place update on non-leaf tensor {self.name} would "
                "invalidate its autograd graph."
            )
        self._value = new_value
        return self

    def copy_(self, other: "Tensor") -> "Tensor":
        val = other._value if isinstance(other, Tensor) else jnp.asarray(other)
        return self._inplace_set(val.astype(self._value.dtype))

    def set_value(self, value) -> "Tensor":
        val = value._value if isinstance(value, Tensor) else jnp.asarray(value)
        return self._inplace_set(val.astype(self._value.dtype))

    def zero_(self) -> "Tensor":
        return self._inplace_set(jnp.zeros_like(self._value))

    def fill_(self, v) -> "Tensor":
        return self._inplace_set(jnp.full_like(self._value, v))

    def scale_(self, s) -> "Tensor":
        return self._inplace_set(self._value * s)

    def add_(self, other) -> "Tensor":
        o = other._value if isinstance(other, Tensor) else other
        return self._inplace_set(self._value + o)

    def subtract_(self, other) -> "Tensor":
        o = other._value if isinstance(other, Tensor) else other
        return self._inplace_set(self._value - o)

    def multiply_(self, other) -> "Tensor":
        o = other._value if isinstance(other, Tensor) else other
        return self._inplace_set(self._value * o)

    def clip_(self, min=None, max=None) -> "Tensor":
        return self._inplace_set(jnp.clip(self._value, min, max))

    def exp_(self) -> "Tensor":
        return self._inplace_set(jnp.exp(self._value))

    def sqrt_(self) -> "Tensor":
        return self._inplace_set(jnp.sqrt(self._value))

    def floor_(self) -> "Tensor":
        return self._inplace_set(jnp.floor(self._value))

    def ceil_(self) -> "Tensor":
        return self._inplace_set(jnp.ceil(self._value))

    def round_(self) -> "Tensor":
        return self._inplace_set(jnp.round(self._value))

    def reciprocal_(self) -> "Tensor":
        return self._inplace_set(1.0 / self._value)

    def tanh_(self) -> "Tensor":
        return self._inplace_set(jnp.tanh(self._value))

    def scatter_(self, index, updates, overwrite=True) -> "Tensor":
        iv = index._value if isinstance(index, Tensor) else jnp.asarray(index)
        iv = iv.reshape(-1)  # paddle accepts (N,) or (N,1) row indices
        uv = (updates._value if isinstance(updates, Tensor)
              else jnp.asarray(updates))
        if overwrite:
            return self._inplace_set(self._value.at[iv].set(uv))
        return self._inplace_set(self._value.at[iv].add(uv))

    def flatten_(self, start_axis=0, stop_axis=-1) -> "Tensor":
        from ..ops.manipulation import flatten as _flatten

        # reuse the ops kernel's axis normalization/validation (0-d, ranges)
        flat = _flatten(Tensor(self._value, stop_gradient=True),
                        start_axis, stop_axis)
        return self._inplace_set(flat._value)

    def squeeze_(self, axis=None) -> "Tensor":
        return self._inplace_set(jnp.squeeze(
            self._value, axis=tuple(axis) if isinstance(axis, (list, tuple))
            else axis))

    def unsqueeze_(self, axis) -> "Tensor":
        return self._inplace_set(jnp.expand_dims(self._value, axis))

    def reshape_(self, shape) -> "Tensor":
        return self._inplace_set(self._value.reshape(tuple(shape)))

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, idx):
        from ..ops.dispatch import run_op

        idx = _unwrap_index(idx)
        return run_op("slice", lambda x: x[idx], self)

    def __setitem__(self, idx, value):
        idx = _unwrap_index(idx)
        v = value._value if isinstance(value, Tensor) else value
        self._inplace_set(self._value.at[idx].set(v))

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # Arithmetic dunders are attached by paddle_tpu.ops._tensor_methods at
    # import time (single source: the op registry), keeping this class free of
    # per-op code — the ``_C_ops`` fast-path discipline.


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(i._value if isinstance(i, Tensor) else i for i in idx)
    return idx


def to_tensor(
    data: Any,
    dtype: Optional[Any] = None,
    place: Optional[Union[str, Place]] = None,
    stop_gradient: bool = True,
) -> Tensor:
    """``paddle.to_tensor`` analog."""
    from .place import _parse_device

    if isinstance(data, Tensor):
        val = data._value
    elif isinstance(data, (jax.Array,)):
        val = data
    else:
        val = np.asarray(data)
        # paddle defaults python floats to fp32, ints to int64; jax x64 is off
        # so int64 becomes int32 — acceptable TPU-native default.
        if val.dtype == np.float64 and dtype is None:
            val = val.astype(np.float32)
    dt = convert_dtype(dtype) if dtype is not None else None
    if place is None:
        dev = device_for_place(expected_place())
    else:
        dev = device_for_place(place if isinstance(place, Place) else _parse_device(place))
    if isinstance(val, jax.Array) and not isinstance(val, jax.core.Tracer):
        if place is None and getattr(val.sharding, "num_devices", 1) > 1:
            # a mesh-sharded array (GSPMD path: dist.shard_tensor /
            # sharded-input pipelines) keeps its NamedSharding — re-placing
            # it on the single default device would silently de-shard it;
            # an EXPLICIT place still wins
            arr = val.astype(dt) if dt is not None else val
        else:
            arr = jax.device_put(val.astype(dt) if dt is not None else val,
                                 dev)
    elif isinstance(val, jax.core.Tracer):
        arr = val.astype(dt) if dt is not None else val
    else:
        arr = jax.device_put(jnp.asarray(val, dtype=dt), dev)
    return Tensor(arr, stop_gradient=stop_gradient)
