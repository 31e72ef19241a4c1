"""``paddle.utils.dlpack`` — zero-copy tensor interop via the DLPack
protocol (reference: ``paddle.utils.dlpack.to_dlpack/from_dlpack`` over
DLManagedTensor capsules; SURVEY.md §2.1 tensor API row). ``jax.dlpack``
carries the actual exchange; this module adds the Tensor wrapping. The
exchange object is a ``__dlpack__`` producer, the protocol's current
form: bare capsules are no longer accepted by consumers."""

from __future__ import annotations

__all__ = ["to_dlpack", "from_dlpack"]


def to_dlpack(x):
    """Tensor/array -> an object that speaks ``__dlpack__`` /
    ``__dlpack_device__`` (the device array itself), which any DLPack
    consumer's ``from_dlpack`` takes without a copy."""
    import jax

    from ..core.tensor import Tensor

    return x.value if isinstance(x, Tensor) else jax.numpy.asarray(x)


def from_dlpack(ext):
    """Any object with ``__dlpack__`` (what ``to_dlpack`` returns, or a
    producer tensor of another framework) -> Tensor."""
    import jax

    from ..core.tensor import Tensor

    return Tensor(jax.dlpack.from_dlpack(ext))
