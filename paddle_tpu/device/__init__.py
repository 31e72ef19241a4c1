"""``paddle.device`` surface: device management + memory stats.

Reference: ``python/paddle/device/`` (SURVEY.md §2.1 Place/DeviceContext and
§5.5 memory observability). Memory stats come from PJRT via
``jax.Device.memory_stats()`` instead of the reference's allocator counters.
"""

from __future__ import annotations

from typing import List, Optional, Union

import jax

from ..core.place import (
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    _devices_for_type,
    device_for_place,
    expected_place,
    get_device,
    set_device,
)

__all__ = [
    "set_device", "get_device", "get_all_devices", "device_count",
    "synchronize", "max_memory_allocated", "max_memory_reserved",
    "memory_allocated", "memory_reserved", "empty_cache", "tpu", "cuda",
    "Stream", "Event", "current_stream", "stream_guard",
]


def get_all_devices() -> List[str]:
    out = []
    for d in jax.devices():
        out.append(f"{d.platform}:{d.id}")
    return out


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        device_type = expected_place().device_type
    return len(_devices_for_type(device_type))


def synchronize(device: Union[str, Place, None] = None) -> None:
    """Block until all queued work on the device is done (stream sync analog).

    XLA/PJRT has no user-visible streams; syncing = blocking on a trivial
    transfer from the device."""
    import jax.numpy as jnp

    place = expected_place() if device is None else device
    if isinstance(place, str):
        from ..core.place import _parse_device

        place = _parse_device(place)
    jax.device_put(jnp.zeros(()), device_for_place(place)).block_until_ready()


def _mem_stats(place: Optional[Place] = None) -> dict:
    dev = device_for_place(place or expected_place())
    # the CPU backend keeps no allocator statistics and returns None
    return dev.memory_stats() or {}


def memory_allocated(device=None) -> int:
    return int(_mem_stats().get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    return int(_mem_stats().get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    s = _mem_stats()
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    return max_memory_allocated(device)


def empty_cache() -> None:
    """XLA owns the allocator; nothing to flush. Kept for API parity."""


class _DeviceNamespace:
    """``paddle.device.cuda`` / ``paddle.device.tpu`` sub-namespace."""

    def __init__(self, kind: str):
        self._kind = kind

    def device_count(self) -> int:
        return device_count(self._kind)

    def synchronize(self, device=None) -> None:
        synchronize(device)

    def max_memory_allocated(self, device=None) -> int:
        return max_memory_allocated(device)

    def max_memory_reserved(self, device=None) -> int:
        return max_memory_reserved(device)

    def memory_allocated(self, device=None) -> int:
        return memory_allocated(device)

    def memory_reserved(self, device=None) -> int:
        return memory_reserved(device)

    def empty_cache(self) -> None:
        empty_cache()


def _last_dispatched():
    """The weakref slot dispatch.py maintains (or None)."""
    from ..ops.dispatch import _LAST_DISPATCHED

    return _LAST_DISPATCHED[0]


def _array_ready(ref) -> bool:
    if ref is None:
        return True
    arr = ref() if callable(ref) else ref
    if arr is None:
        # buffer object was garbage-collected: completion is UNKNOWABLE
        # (the dispatched computation may still be running) — report done
        # because no handle remains to poll; holding a strong ref instead
        # would pin arbitrarily large buffers in device memory
        return True
    try:
        return bool(arr.is_ready())
    except Exception:  # deleted/donated buffers count as "done"
        return True


class Stream:
    """API-parity stream object (reference: ``paddle.device.Stream`` over
    CUDA streams). XLA/PJRT schedules asynchronously on internal streams
    the user cannot target, so ordering is already program order:
    ``wait_event``/``wait_stream`` are no-ops, ``synchronize`` drains the
    device, and ``query`` polls the readiness of the most recently
    dispatched value without ever draining (see ``query``)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def record_event(self, event=None):
        event = event or Event()
        event.record(self)
        return event

    def wait_event(self, event) -> None:
        pass

    def wait_stream(self, stream) -> None:
        pass

    def synchronize(self) -> None:
        synchronize(self.device)

    def query(self) -> bool:
        """Non-blocking completion poll (reference ``Stream.query``). XLA
        dispatch is in-order and this framework's streams are the no-op
        stream model; the honest non-blocking answer is whether the MOST
        RECENTLY dispatched eager op's output is ready (``.is_ready()`` on
        the tracked array) — in-order dispatch means everything before it
        is then done too. Never drains the device (a synchronizing query
        would turn reference-style polling loops into full barriers)."""
        return _array_ready(_last_dispatched())


class Event:
    """API-parity event (reference: ``paddle.device.Event``). Recording is
    an async no-op under XLA's in-order dispatch; ``synchronize`` drains."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device
        self._recorded = False

    def record(self, stream=None) -> None:
        self._recorded = True
        self._stream = stream
        # snapshot the last dispatch at record time: query() then answers
        # "has the work recorded by this event completed", matching
        # cudaEventRecord/cudaEventQuery semantics under in-order dispatch
        self._marker = _last_dispatched()

    def query(self) -> bool:
        # non-blocking, like Stream.query (see there); the reference's
        # cudaEventQuery never drains the device either
        if not self._recorded:
            return True
        return _array_ready(getattr(self, "_marker", None))

    def synchronize(self) -> None:
        if self._recorded:
            synchronize(self.device)


_current_stream = Stream()


def current_stream(device=None) -> Stream:
    return _current_stream


class stream_guard:
    """Context manager for API parity with ``paddle.device.stream_guard``;
    under XLA there is one implicit in-order stream."""

    def __init__(self, stream: Stream):
        self._stream = stream

    def __enter__(self):
        return self._stream

    def __exit__(self, *exc):
        return False


tpu = _DeviceNamespace("tpu")
cuda = _DeviceNamespace("gpu")


# ---------------------------------------------------------------------------
# Custom-device plugins. Reference counterpart: the C-ABI plugin layer
# (`paddle/phi/backends/custom/custom_device.cc`, `paddle/phi/capi/`;
# SURVEY.md §2.3 item 24) that lets out-of-tree backends register as
# CustomPlace('npu') etc. The TPU-native equivalent IS the PJRT plugin ABI:
# any backend exposing a PJRT C-API plugin registers with jax and shows up
# here — no framework-side C code is needed because PJRT already
# standardises device mgmt/stream/memcpy/compile.
# ---------------------------------------------------------------------------

_BUILTIN_PLATFORMS = ("cpu", "gpu", "cuda", "tpu")


def get_all_custom_device_type() -> List[str]:
    """Backend names served by out-of-tree PJRT plugins (reference
    ``paddle.device.get_all_custom_device_type``). Enumerates the registered
    backend FACTORIES (not ``jax.devices()``, which only lists the default
    backend, and a plug-in's devices may report a generic platform name)."""
    try:
        from jax._src.xla_bridge import _backend_factories

        return [n for n in _backend_factories if n not in _BUILTIN_PLATFORMS]
    except ImportError:
        return []


def is_compiled_with_custom_device(device_type: str) -> bool:
    return device_type in get_all_custom_device_type()


def register_pjrt_plugin(name: str, library_path: str, options=None) -> None:
    """Register a PJRT plugin .so as a new device backend (the analog of
    the reference's ``CustomDevice`` runtime registration)."""
    from jax._src.xla_bridge import register_plugin

    register_plugin(name, library_path=library_path, options=options or {})
