"""``paddle.fft`` — discrete Fourier transforms.

Reference counterpart: ``python/paddle/fft.py`` backed by the phi fft kernels
(``paddle/phi/kernels/cpu|gpu/fft_*``, cuFFT on GPU; SURVEY.md §2.1 PHI
kernel corpus). Here every transform lowers to ``jnp.fft`` — XLA dispatches
to its native FFT implementation on TPU — wrapped as registered,
differentiable ops on the eager tape.
"""

from __future__ import annotations

import jax.numpy as jnp

from .core.tensor import Tensor, to_tensor
from .ops.dispatch import run_op
from .ops.registry import register_op

__all__ = [
    "fft", "ifft", "fft2", "ifft2", "fftn", "ifftn",
    "rfft", "irfft", "rfft2", "irfft2", "rfftn", "irfftn",
    "hfft", "ihfft", "fftfreq", "rfftfreq", "fftshift", "ifftshift",
]


def _norm(norm):
    # paddle uses "backward" | "forward" | "ortho" like numpy
    return norm or "backward"


def _wrap1(op_name, jfn, uses_n=True):
    if uses_n:
        def op(x, n=None, axis=-1, norm="backward", name=None):
            return run_op(op_name, lambda a: jfn(a, n=n, axis=axis,
                                                 norm=_norm(norm)), x)
    else:
        def op(x, axes=None, name=None):
            return run_op(op_name, lambda a: jfn(a, axes=axes), x)
    op.__name__ = op_name
    return register_op(op_name)(op)


def _wrapn(op_name, jfn):
    def op(x, s=None, axes=None, norm="backward", name=None):
        return run_op(op_name, lambda a: jfn(a, s=s, axes=axes,
                                             norm=_norm(norm)), x)
    op.__name__ = op_name
    return register_op(op_name)(op)


fft = _wrap1("fft", jnp.fft.fft)
ifft = _wrap1("ifft", jnp.fft.ifft)
rfft = _wrap1("rfft", jnp.fft.rfft)
irfft = _wrap1("irfft", jnp.fft.irfft)
hfft = _wrap1("hfft", jnp.fft.hfft)
ihfft = _wrap1("ihfft", jnp.fft.ihfft)

fftn = _wrapn("fftn", jnp.fft.fftn)
ifftn = _wrapn("ifftn", jnp.fft.ifftn)
rfftn = _wrapn("rfftn", jnp.fft.rfftn)
irfftn = _wrapn("irfftn", jnp.fft.irfftn)


def _wrap2(op_name, jfn):
    def op(x, s=None, axes=(-2, -1), norm="backward", name=None):
        return run_op(op_name, lambda a: jfn(a, s=s, axes=axes,
                                             norm=_norm(norm)), x)
    op.__name__ = op_name
    return register_op(op_name)(op)


fft2 = _wrap2("fft2", jnp.fft.fft2)
ifft2 = _wrap2("ifft2", jnp.fft.ifft2)
rfft2 = _wrap2("rfft2", jnp.fft.rfft2)
irfft2 = _wrap2("irfft2", jnp.fft.irfft2)


@register_op("fftshift")
def fftshift(x, axes=None, name=None) -> Tensor:
    return run_op("fftshift", lambda a: jnp.fft.fftshift(a, axes=axes), x)


@register_op("ifftshift")
def ifftshift(x, axes=None, name=None) -> Tensor:
    return run_op("ifftshift", lambda a: jnp.fft.ifftshift(a, axes=axes), x)


@register_op("fftfreq", differentiable=False)
def fftfreq(n, d=1.0, dtype=None, name=None) -> Tensor:
    return to_tensor(jnp.fft.fftfreq(n, d=d).astype(dtype or jnp.float32))


@register_op("rfftfreq", differentiable=False)
def rfftfreq(n, d=1.0, dtype=None, name=None) -> Tensor:
    return to_tensor(jnp.fft.rfftfreq(n, d=d).astype(dtype or jnp.float32))
