"""What both kinds share: configuration -> the program's config object,
weights from the seed, percentiles, the compile counter, the profiler."""

from __future__ import annotations

import math
import os
import shutil
import threading
import time
from typing import Optional, Sequence

# public config.json key -> LlamaConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
}


def llama_config(config: dict, **over):
    """The program's config object for a configuration file: the model's
    own sizes, then the file's ``program`` options, then ``over``."""
    import jax.numpy as jnp

    from paddle_tpu.models import llama

    model = config["model"]
    fields = {ours: model[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields["dtype"] = jnp.dtype(model.get("torch_dtype", "bfloat16")).type
    fields.update(config.get("program", {}))
    fields.update(over)
    return llama.LlamaConfig(**fields)


def prng_key(seed: int):
    """A key for any whole-number seed, 32 bits or more."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest rank (the rule of observability.metrics.percentile)."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


class CompileCounter:
    """Counts programs built (compiled, or read back from the persistent
    cache) while ``armed``: inside a measured window it must stay 0."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0
        self.in_window = 0
        self.armed = False
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += 1
            self.in_window += self.armed

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class SliceTracer:
    """Profiles one slice of a run. ``maybe_start`` / ``maybe_stop`` are
    called by the kind's driver at boundaries where the device is idle;
    the slice opens at the first boundary after ``start_s`` and closes at
    the first boundary ``length_s`` later."""

    def __init__(self, trace_dir: str, t_open: float, start_s: float,
                 length_s: float):
        self.dir = trace_dir
        self.t_open, self.start_s, self.length_s = t_open, start_s, length_s
        self.t_on: Optional[float] = None
        self.t_off: Optional[float] = None
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)

    @property
    def on(self) -> bool:
        return self.t_on is not None and self.t_off is None

    def maybe_start(self) -> bool:
        import jax

        if self.t_on is None and \
                time.perf_counter() - self.t_open >= self.start_s:
            jax.profiler.start_trace(self.dir)
            self.t_on = time.perf_counter()
            return True
        return False

    def maybe_stop(self, force: bool = False) -> bool:
        import jax

        if self.on and (force or time.perf_counter() - self.t_on
                        >= self.length_s):
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
            return True
        return False

    @property
    def window_s(self) -> Optional[float]:
        if self.t_on is None or self.t_off is None:
            return None
        return self.t_off - self.t_on


class HostWatch:
    """Tells a slow host from a slow chip. A thread sleeps ``period`` over
    and over and notes how late each wake-up comes: the worst lateness is
    how long this process was kept from running (a busy core, a paused
    machine) while the window was open. A stretch that ran long while every
    wake-up was on time was spent on the device or in its runtime."""

    def __init__(self, period: float = 0.01):
        self.period = period
        self.worst_s, self.worst_at = 0.0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.period):
            now = time.perf_counter()
            late = now - last - self.period
            if late > self.worst_s:
                self.worst_s, self.worst_at = late, last
            last = now

    def start(self) -> float:
        self.t0 = time.perf_counter()
        self._thread.start()
        return self.t0

    def stop(self) -> dict:
        self._stop.set()
        self._thread.join()
        return {"host_stall_max_ms": self.worst_s * 1e3,
                "host_stall_at_s": self.worst_at - self.t0}


def memory_peak_bytes(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
