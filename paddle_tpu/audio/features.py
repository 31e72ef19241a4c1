"""``paddle.audio.features`` — Spectrogram / MelSpectrogram /
LogMelSpectrogram / MFCC layers (reference:
``python/paddle/audio/features/layers.py``), built on
``paddle_tpu.signal.stft`` and the functional filterbanks.

Windows/filterbanks/DCT bases are STATIC HOST MATH and stay numpy: they
embed as constants in the ops' closures, which follow the input tensor's
committed device.
"""

from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp

from .. import signal
from ..nn.layer.layers import Layer
from ..ops.dispatch import run_op
from . import functional as F

__all__ = ["Spectrogram", "MelSpectrogram", "LogMelSpectrogram", "MFCC"]


class Spectrogram(Layer):
    """|STFT|^power of [..., T] signals → [..., freq, frames]."""

    def __init__(self, n_fft: int = 512, hop_length: Optional[int] = None,
                 win_length: Optional[int] = None, window: str = "hann",
                 power: float = 2.0, center: bool = True,
                 pad_mode: str = "reflect", dtype: str = "float32"):
        super().__init__()
        self.n_fft = n_fft
        self.hop_length = hop_length or n_fft // 4
        self.win_length = win_length or n_fft
        self.power = power
        self.center = center
        self.pad_mode = pad_mode
        self._dtype = dtype
        self._window = F.get_window(window, self.win_length)  # numpy

    def forward(self, x):
        spec = signal.stft(x, self.n_fft, self.hop_length, self.win_length,
                           window=self._window, center=self.center,
                           pad_mode=self.pad_mode)
        power, dtype = self.power, self._dtype

        def mag_f(s):
            m = jnp.abs(s)
            if power != 1.0:
                m = m ** power
            return m.astype(dtype)

        return run_op("spectrogram_mag", mag_f, spec)


class MelSpectrogram(Layer):
    def __init__(self, sr: int = 22050, n_fft: int = 512,
                 hop_length: Optional[int] = None,
                 win_length: Optional[int] = None, window: str = "hann",
                 power: float = 2.0, center: bool = True,
                 pad_mode: str = "reflect", n_mels: int = 64,
                 f_min: float = 50.0, f_max: Optional[float] = None,
                 htk: bool = False, norm: Union[str, float] = "slaney",
                 dtype: str = "float32"):
        super().__init__()
        self._spectrogram = Spectrogram(n_fft, hop_length, win_length,
                                        window, power, center, pad_mode,
                                        dtype=dtype)
        self._fbank = F.compute_fbank_matrix(
            sr, n_fft, n_mels, f_min, f_max, htk, norm).astype(dtype)

    def forward(self, x):
        spec = self._spectrogram(x)          # [..., freq, frames]
        fb = self._fbank
        return run_op("mel_fbank", lambda s: jnp.matmul(fb, s), spec)


class LogMelSpectrogram(Layer):
    def __init__(self, *args, ref_value: float = 1.0, amin: float = 1e-10,
                 top_db: Optional[float] = None, **kwargs):
        super().__init__()
        self._mel = MelSpectrogram(*args, **kwargs)
        self.ref_value, self.amin, self.top_db = ref_value, amin, top_db

    def forward(self, x):
        return F.power_to_db(self._mel(x), self.ref_value, self.amin,
                             self.top_db)


class MFCC(Layer):
    def __init__(self, sr: int = 22050, n_mfcc: int = 40, n_mels: int = 64,
                 **mel_kwargs):
        super().__init__()
        self._log_mel = LogMelSpectrogram(sr=sr, n_mels=n_mels, **mel_kwargs)
        self._dct_t = F.create_dct(n_mfcc, n_mels).T  # [n_mfcc, n_mels]

    def forward(self, x):
        log_mel = self._log_mel(x)           # [..., n_mels, frames]
        dct_t = self._dct_t
        return run_op("mfcc_dct", lambda m: jnp.matmul(dct_t, m), log_mel)
