"""Device abstraction.

Counterpart of the reference's ``phi::Place`` family
(``paddle/phi/common/place.h``; SURVEY.md §2.1): a ``Place`` names the device a
tensor lives on. On the TPU-native stack the actual device runtime is
XLA/PJRT, so a Place maps to a ``jax.Device``; ``TPUPlace`` is first-class
(the BASELINE north star's ``paddle.set_device('tpu')``).
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax

from ..enforce import InvalidArgumentError

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "CustomPlace",
    "set_device",
    "get_device",
    "device_for_place",
    "is_compiled_with_tpu",
]


class Place:
    """Base device identity: ``(device_type, device_id)``."""

    device_type: str = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and other.device_type == self.device_type
            and other.device_id == self.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def get_device_id(self) -> int:
        return self.device_id


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self, device_id: int = 0):
        super().__init__(device_id)


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):
    """GPU place. Kept for API parity; resolves to a jax 'gpu' device if one
    exists (the reference's primary backend — here secondary to TPU)."""

    device_type = "gpu"


class CustomPlace(Place):
    """Out-of-tree backend place (reference ``phi/backends/custom``):
    resolves to any registered PJRT platform by name."""

    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


_PLATFORM_ALIASES = {
    "tpu": ("tpu",),
    "gpu": ("gpu", "cuda", "rocm"),
    "cpu": ("cpu",),
}


@functools.lru_cache(maxsize=None)
def _devices_for_type(device_type: str):
    platforms = _PLATFORM_ALIASES.get(device_type, (device_type,))
    for platform in platforms:
        try:
            # local_devices, not devices: under the multi-controller launch
            # runtime each trainer may only place data on its own process's
            # devices (the reference's trainer->CUDA_VISIBLE_DEVICES pinning)
            devs = jax.local_devices(backend=platform)
            if devs:
                return tuple(devs)
        except RuntimeError:
            continue
    return ()


def device_for_place(place: Place) -> jax.Device:
    """Resolve a Place to the backing ``jax.Device``."""
    devs = _devices_for_type(place.device_type)
    if not devs:
        raise InvalidArgumentError(
            f"No {place.device_type!r} devices available "
            f"(jax sees: {[d.platform for d in jax.devices()]})."
        )
    if place.device_id >= len(devs):
        raise InvalidArgumentError(
            f"Device id {place.device_id} out of range for "
            f"{place.device_type!r} ({len(devs)} devices)."
        )
    return devs[place.device_id]


def _default_place() -> Place:
    # Prefer the accelerator, like the reference prefers CUDAPlace(0).
    if _devices_for_type("tpu"):
        return TPUPlace(0)
    if _devices_for_type("gpu"):
        return CUDAPlace(0)
    return CPUPlace(0)


_current_place: Optional[Place] = None


def _parse_device(device: Union[str, Place]) -> Place:
    if isinstance(device, Place):
        return device
    if not isinstance(device, str):
        raise InvalidArgumentError(f"device must be a str or Place, got {type(device)}")
    dev = device.lower()
    if ":" in dev:
        kind, _, idx_s = dev.partition(":")
        idx = int(idx_s)
    else:
        kind, idx = dev, 0
    cls = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace, "cuda": CUDAPlace}.get(kind)
    if cls is None:
        return CustomPlace(kind, idx)
    return cls(idx)


def set_device(device: Union[str, Place]) -> Place:
    """``paddle.set_device('tpu')`` analog: set the default place for new tensors."""
    global _current_place
    place = _parse_device(device)
    device_for_place(place)  # validate eagerly
    _current_place = place
    return place


def get_device() -> str:
    return f"{expected_place().device_type}:{expected_place().device_id}"


def expected_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def is_compiled_with_tpu() -> bool:
    return bool(_devices_for_type("tpu"))
