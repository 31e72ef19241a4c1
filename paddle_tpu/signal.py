"""``paddle.signal`` — short-time Fourier transforms.

Reference counterpart: ``python/paddle/signal.py`` (stft/istft over the fft
kernels; SURVEY.md §2.1 PHI kernel corpus). Framing/overlap-add run as XLA
gather/scatter; the FFTs lower to ``jnp.fft`` like ``paddle_tpu.fft``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .core.tensor import Tensor, to_tensor
from .ops.dispatch import run_op

__all__ = ["stft", "istft"]


def _frame(x, frame_length, hop_length):
    # x: [..., T] -> [..., frame_length, n_frames]
    T = x.shape[-1]
    n = 1 + (T - frame_length) // hop_length
    starts = np.arange(n) * hop_length
    idx = starts[None, :] + np.arange(frame_length)[:, None]  # [L, n]
    return jnp.take(x, jnp.asarray(idx), axis=-1)


def stft(x, n_fft, hop_length=None, win_length=None, window=None,
         center=True, pad_mode="reflect", normalized=False, onesided=True,
         name=None):
    """[..., T] → complex [..., n_fft//2+1 | n_fft, n_frames] (paddle
    layout: freq before frames)."""
    hop_length = hop_length or n_fft // 4
    win_length = win_length or n_fft
    wv = (window._value if isinstance(window, Tensor)
          else (jnp.asarray(window) if window is not None
                else jnp.ones((win_length,), jnp.float32)))
    if win_length < n_fft:  # pad window symmetrically to n_fft
        lpad = (n_fft - win_length) // 2
        wv = jnp.pad(wv, (lpad, n_fft - win_length - lpad))

    def f(a):
        if center:
            pad = [(0, 0)] * (a.ndim - 1) + [(n_fft // 2, n_fft // 2)]
            a = jnp.pad(a, pad, mode=pad_mode)
        frames = _frame(a, n_fft, hop_length)           # [..., L, n]
        frames = frames * wv[:, None]
        spec = jnp.fft.rfft(frames, axis=-2) if onesided \
            else jnp.fft.fft(frames, axis=-2)
        if normalized:
            spec = spec / jnp.sqrt(jnp.asarray(n_fft, spec.real.dtype))
        return spec

    return run_op("stft", f, x)


def istft(x, n_fft, hop_length=None, win_length=None, window=None,
          center=True, normalized=False, onesided=True, length=None,
          return_complex=False, name=None):
    """Inverse STFT via overlap-add with window-envelope normalization."""
    hop_length = hop_length or n_fft // 4
    win_length = win_length or n_fft
    wv = (window._value if isinstance(window, Tensor)
          else (jnp.asarray(window) if window is not None
                else jnp.ones((win_length,), jnp.float32)))
    if win_length < n_fft:
        lpad = (n_fft - win_length) // 2
        wv = jnp.pad(wv, (lpad, n_fft - win_length - lpad))

    def f(spec):
        if normalized:
            spec = spec * jnp.sqrt(jnp.asarray(n_fft, spec.real.dtype))
        frames = (jnp.fft.irfft(spec, n=n_fft, axis=-2) if onesided
                  else jnp.fft.ifft(spec, axis=-2).real)   # [..., L, n]
        frames = frames * wv[:, None]
        n = frames.shape[-1]
        T = n_fft + (n - 1) * hop_length
        out = jnp.zeros(frames.shape[:-2] + (T,), frames.dtype)
        env = jnp.zeros((T,), frames.dtype)
        for i in range(n):  # static unroll: n is a trace-time constant
            sl = slice(i * hop_length, i * hop_length + n_fft)
            out = out.at[..., sl].add(frames[..., :, i])
            env = env.at[sl].add(wv * wv)
        out = out / jnp.maximum(env, 1e-10)
        if center:
            out = out[..., n_fft // 2: T - n_fft // 2]
        if length is not None:
            out = out[..., :length]
        return out

    return run_op("istft", f, x)
