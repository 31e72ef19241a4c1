"""``paddle.audio.functional`` — filterbank / window math (reference:
``python/paddle/audio/functional/`` in the upstream tree; SURVEY.md treats
audio as part of the L8 python surface).

Filterbank construction is static host math (numpy); anything touching
signals goes through ``paddle_tpu.signal`` / tensor ops.
"""

from __future__ import annotations

import math
from typing import Optional, Union

import numpy as np

from ..core.tensor import Tensor, to_tensor

__all__ = [
    "hz_to_mel", "mel_to_hz", "mel_frequencies", "fft_frequencies",
    "compute_fbank_matrix", "power_to_db", "create_dct", "get_window",
]


def hz_to_mel(freq, htk: bool = False):
    """Hertz → mel. Slaney formula by default (reference default), HTK
    (2595·log10(1+f/700)) when ``htk``."""
    scalar = np.isscalar(freq)
    f = np.asarray(freq, np.float64)
    if htk:
        m = 2595.0 * np.log10(1.0 + f / 700.0)
    else:
        f_min, f_sp = 0.0, 200.0 / 3
        m = (f - f_min) / f_sp
        min_log_hz = 1000.0
        min_log_mel = (min_log_hz - f_min) / f_sp
        logstep = math.log(6.4) / 27.0
        m = np.where(f >= min_log_hz,
                     min_log_mel + np.log(np.maximum(f, 1e-10) / min_log_hz)
                     / logstep, m)
    return float(m) if scalar else m


def mel_to_hz(mel, htk: bool = False):
    scalar = np.isscalar(mel)
    m = np.asarray(mel, np.float64)
    if htk:
        f = 700.0 * (10.0 ** (m / 2595.0) - 1.0)
    else:
        f_min, f_sp = 0.0, 200.0 / 3
        f = f_min + f_sp * m
        min_log_hz = 1000.0
        min_log_mel = (min_log_hz - f_min) / f_sp
        logstep = math.log(6.4) / 27.0
        f = np.where(m >= min_log_mel,
                     min_log_hz * np.exp(logstep * (m - min_log_mel)), f)
    return float(f) if scalar else f


def mel_frequencies(n_mels: int = 64, f_min: float = 0.0,
                    f_max: float = 11025.0, htk: bool = False):
    mels = np.linspace(hz_to_mel(f_min, htk), hz_to_mel(f_max, htk), n_mels)
    return mel_to_hz(mels, htk)


def fft_frequencies(sr: int, n_fft: int):
    return np.linspace(0, sr / 2, 1 + n_fft // 2)


def compute_fbank_matrix(sr: int, n_fft: int, n_mels: int = 64,
                         f_min: float = 0.0, f_max: Optional[float] = None,
                         htk: bool = False, norm: Union[str, float] = "slaney"):
    """[n_mels, 1 + n_fft//2] triangular mel filterbank (librosa/reference
    convention; 'slaney' area-normalises each filter)."""
    f_max = f_max or sr / 2.0
    fft_f = fft_frequencies(sr, n_fft)
    mel_f = mel_frequencies(n_mels + 2, f_min, f_max, htk)
    fdiff = np.diff(mel_f)
    ramps = mel_f[:, None] - fft_f[None, :]
    weights = np.zeros((n_mels, len(fft_f)))
    for i in range(n_mels):
        lower = -ramps[i] / fdiff[i]
        upper = ramps[i + 2] / fdiff[i + 1]
        weights[i] = np.maximum(0, np.minimum(lower, upper))
    if norm == "slaney":
        enorm = 2.0 / (mel_f[2: n_mels + 2] - mel_f[:n_mels])
        weights *= enorm[:, None]
    elif isinstance(norm, (int, float)):
        norms = np.linalg.norm(weights, ord=norm, axis=-1, keepdims=True)
        weights = weights / np.maximum(norms, 1e-10)
    return weights.astype(np.float32)


def power_to_db(spect, ref_value: float = 1.0, amin: float = 1e-10,
                top_db: Optional[float] = 80.0):
    """10·log10(spect/ref) with an optional dynamic-range floor. Runs as
    one op whose scalar constants live in the closure, so it follows the
    input's committed device."""
    import jax.numpy as jnp

    from ..ops.dispatch import run_op

    x = spect if isinstance(spect, Tensor) else to_tensor(np.asarray(spect))
    offset = 10.0 * math.log10(max(amin, ref_value))

    def f(a):
        log_spec = 10.0 * jnp.log10(jnp.maximum(a, amin)) - offset
        if top_db is not None:
            log_spec = jnp.maximum(log_spec, jnp.max(log_spec) - top_db)
        return log_spec

    return run_op("power_to_db", f, x)


def create_dct(n_mfcc: int, n_mels: int, norm: Optional[str] = "ortho"):
    """[n_mels, n_mfcc] DCT-II basis (reference layout: matmul from mel)."""
    n = np.arange(n_mels, dtype=np.float64)
    k = np.arange(n_mfcc, dtype=np.float64)[None, :]
    basis = np.cos(math.pi / n_mels * (n[:, None] + 0.5) * k)
    if norm == "ortho":
        basis[:, 0] *= 1.0 / math.sqrt(2)
        basis *= math.sqrt(2.0 / n_mels)
    else:
        basis *= 2.0
    return basis.astype(np.float32)


def get_window(window: str, win_length: int, fftbins: bool = True):
    """hann/hamming/blackman/bartlett/ones windows (periodic when fftbins)."""
    n = win_length + (0 if fftbins else -1)
    t = np.arange(win_length, dtype=np.float64)
    if window in ("hann", "hanning"):
        w = 0.5 - 0.5 * np.cos(2 * math.pi * t / max(n, 1))
    elif window == "hamming":
        w = 0.54 - 0.46 * np.cos(2 * math.pi * t / max(n, 1))
    elif window == "blackman":
        w = (0.42 - 0.5 * np.cos(2 * math.pi * t / max(n, 1))
             + 0.08 * np.cos(4 * math.pi * t / max(n, 1)))
    elif window == "bartlett":
        w = 1.0 - np.abs(2 * t / max(n, 1) - 1.0)
    elif window in ("ones", "rectangular", "boxcar"):
        w = np.ones(win_length)
    else:
        raise ValueError(f"unsupported window {window!r}")
    return w.astype(np.float32)
