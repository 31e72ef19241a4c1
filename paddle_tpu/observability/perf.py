"""Explained performance — analytic roofline ledgers joined with
runtime counters, plus an EWMA tick-time regression sentinel (ISSUE 9
tentpole, part 2).

The analysis subsystem proves what a program SHOULD cost (budgets.py
pins relayout/pack/sync ledgers per canonical program; SCALING.md §3c
derives the HBM-bound decode ceiling from the live param tree) and the
telemetry registry records what serving DID (ticks, tokens, wall
time). Nothing joined them at runtime: an operator watching
``serving.throughput_tok_s`` had no way to know whether 800 tok/s was
the hardware's roofline or a 10x regression. This module closes that
gap with host arithmetic only:

* :func:`serving_ledger` rebuilds the §3c analytic ledger from the
  LIVE param tree (the same arithmetic ``benchmarks/llama_decode.py``
  publishes): per-tick weight-stream bytes (non-embedding params;
  the lm_head is fully read, the embedding row is a gather), per-tick
  KV bytes at the average position, the HBM tick floor, the tok/s
  ceiling, and matmul FLOPs/token — and attaches the program's pinned
  hazard budget from ``analysis.budgets`` so the static and dynamic
  ledgers travel together.
* :class:`PerfMonitor` accumulates the serving counters the schedulers
  already hold (steps, new tokens, segment wall time — all host
  mirrors of the one audited segment fetch) and, per interval, reports
  **live roofline fraction** (measured tok/s / analytic ceiling) and
  **MFU** (measured FLOP/s / peak) through the gauges
  ``perf.roofline_fraction[<program>]`` / ``perf.mfu[<program>]`` /
  ``perf.tok_s[<program>]``.
* The **regression sentinel** is the runtime sibling of the static
  gate: an EWMA of seconds-per-tick, pinned against a runtime budget
  (explicit ``tick_budget_s``, or self-pinned from the first
  ``pin_after`` segments), emits a ``perf_regression`` flight event +
  ``perf.regressions`` counter when the EWMA crosses
  ``tolerance x budget`` — the 2.5 s-mid-serve class and silent
  10%-slower classes both become operator-visible events instead of a
  vibe in a dashboard.

The analytic ledger models ONE chip, the v5e, whatever backend the
process runs on: it is arithmetic on shapes, and the gate pins it on
the CPU. A MEASURED utilization divides by the peak of the device that
ran the work, looked up by its ``device_kind`` in ``CHIP_PEAKS`` through
:func:`chip_peaks`, which refuses a device it has no entry for.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

from . import flight as _flight
from . import metrics as _metrics

__all__ = ["serving_ledger", "PerfMonitor", "CHIP_PEAKS", "chip_peaks",
           "V5E_HBM_BPS", "V5E_PEAK_FLOPS", "install", "uninstall"]

# Published peaks of one chip, keyed by the ``device_kind`` jax reports.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM). The one table every utilization in the tree divides by.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
}


def chip_peaks(device_kind: str) -> dict:
    """Peaks of the chip whose ``jax.Device.device_kind`` is given. A
    device that is not in the table is an error, never a default: a
    utilization against another chip's peak is a wrong number."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}); a utilization can only be "
            f"reported for a chip in CHIP_PEAKS") from None


# the chip the analytic ledgers model (SCALING.md §2)
V5E_HBM_BPS = CHIP_PEAKS["TPU v5 lite"]["hbm_bytes_s"]
V5E_PEAK_FLOPS = CHIP_PEAKS["TPU v5 lite"]["bf16_flops_s"]


def serving_ledger(cfg, params, batch: int, avg_pos: float,
                   program: str = "paged_serving_segment",
                   hbm_bytes_s: float = V5E_HBM_BPS,
                   peak_flops_s: float = V5E_PEAK_FLOPS) -> dict:
    """Analytic byte/op ledger for a decode-bound serving program,
    computed from the LIVE param tree (host shape metadata only — no
    device sync). ``batch`` is the concurrent slot count, ``avg_pos``
    the average KV position a tick attends over.

    The arithmetic is SCALING.md §3c / ``llama_decode.py``'s, verbatim:
    every decode tick streams the non-embedding weights once plus the
    KV rows written so far; the ceiling is ``batch / tick_floor``.
    FLOPs/token = 2 x non-embedding params (matmul MACs x 2) plus the
    attention score/value contractions at ``avg_pos``."""
    import jax

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    embed_rows = cfg.vocab_size * cfg.hidden_size
    itemsize = np.dtype(cfg.dtype).itemsize
    weight_bytes = (n_params - embed_rows) * itemsize
    kv_bytes = (cfg.num_layers * 2 * float(avg_pos) * cfg.num_kv_heads
                * cfg.head_dim * batch * itemsize)
    tick_floor_s = (weight_bytes + kv_bytes) / hbm_bytes_s
    ceiling_tok_s = batch / tick_floor_s
    flops_per_token = (2.0 * (n_params - embed_rows)
                       + 4.0 * float(avg_pos) * cfg.num_heads
                       * cfg.head_dim * cfg.num_layers)
    ledger = {
        "program": program,
        "batch": int(batch),
        "avg_pos": float(avg_pos),
        "n_params": n_params,
        "weight_bytes_per_tick": int(weight_bytes),
        "kv_bytes_per_tick": int(kv_bytes),
        "hbm_bytes_s": hbm_bytes_s,
        "peak_flops_s": peak_flops_s,
        "tick_floor_s": tick_floor_s,
        "ceiling_tok_s": ceiling_tok_s,
        "flops_per_token": flops_per_token,
    }
    # join the STATIC hazard ledger the gate enforces for this program,
    # so /perf serves the analytic bytes next to the pinned budgets
    from ..analysis import budgets as _budgets

    b = _budgets.budget_for(program)
    if b is not None:
        ledger["hazard_budget"] = {
            "relayout_bytes_max": b.relayout_bytes_max,
            "pack_bytes_max": b.pack_bytes_max,
            "warm_compiles": b.warm_compiles,
            "allowed_syncs_per_replay": dict(b.allowed_syncs_per_replay),
            "bytes_platform": b.bytes_platform,
        }
    return ledger


class PerfMonitor:
    """Join one serving program's analytic ledger with its runtime
    counters; report roofline fraction + MFU per interval and watch the
    per-tick EWMA for regressions.

    Feed it per-segment host numbers via :meth:`note_segment` (the
    schedulers pass exact steps/tokens/elapsed from the audited fetch's
    host mirrors) and call :meth:`end_interval` whenever a report
    should be cut (the benchmarks cut one per rated serve; the ops
    endpoint serves the running interval live).

    ``tick_budget_s``: pinned seconds/tick the sentinel guards. When
    ``None`` it self-pins to the EWMA after ``pin_after`` segments —
    the 'no regression vs my own warm baseline' mode. ``tolerance``:
    multiplier over budget that trips the sentinel.
    """

    def __init__(self, cfg, params, batch: int, avg_pos: float = 64.0,
                 program: str = "paged_serving_segment",
                 hbm_bytes_s: float = V5E_HBM_BPS,
                 peak_flops_s: float = V5E_PEAK_FLOPS,
                 tick_budget_s: Optional[float] = None,
                 pin_after: int = 4, tolerance: float = 1.5,
                 ewma_alpha: float = 0.5):
        self.program = program
        self.ledger = serving_ledger(cfg, params, batch, avg_pos,
                                     program=program,
                                     hbm_bytes_s=hbm_bytes_s,
                                     peak_flops_s=peak_flops_s)
        self.tick_budget_s = tick_budget_s
        self._explicit_budget = tick_budget_s is not None
        self.pin_after = int(pin_after)
        self.tolerance = float(tolerance)
        self.ewma_alpha = float(ewma_alpha)
        self.tick_ewma_s: Optional[float] = None
        self.regressions = 0
        self.segments = 0             # lifetime (the self-pin clock)
        # interval accumulators (host ints/floats only)
        self._iv_segments = 0
        self._iv_steps = 0
        self._iv_tokens = 0
        self._iv_busy_s = 0.0
        self._iv_t0: Optional[float] = None
        self.last_report: Optional[dict] = None

    # --- per-segment intake ----------------------------------------------
    def note_segment(self, steps: int, new_tokens: int,
                     elapsed_s: Optional[float] = None) -> None:
        """One segment's host mirrors: device ticks run, tokens
        surfaced, and (when the caller timed the dispatch→fetch span)
        its wall time. ``elapsed_s=None`` skips the sentinel (ambient
        attachments that cannot time the segment still feed the
        throughput interval)."""
        if self._iv_t0 is None:
            self._iv_t0 = time.perf_counter()
        self.segments += 1
        self._iv_segments += 1
        self._iv_steps += int(steps)
        self._iv_tokens += int(new_tokens)
        if elapsed_s is None or steps <= 0:
            return
        self._iv_busy_s += float(elapsed_s)
        per_tick = float(elapsed_s) / int(steps)
        self.tick_ewma_s = (per_tick if self.tick_ewma_s is None
                            else (1 - self.ewma_alpha) * self.tick_ewma_s
                            + self.ewma_alpha * per_tick)
        _metrics.gauge(
            f"perf.tick_time_ewma_s[{self.program}]").set(self.tick_ewma_s)
        if not self._explicit_budget:
            if self.segments == self.pin_after:
                # self-pin: the warm baseline becomes the budget
                self.tick_budget_s = self.tick_ewma_s
            elif self.segments < self.pin_after:
                return
        if (self.tick_budget_s is not None
                and self.tick_ewma_s > self.tolerance * self.tick_budget_s):
            self.regressions += 1
            _metrics.counter("perf.regressions").inc()
            _flight.record(
                "perf_regression", program=self.program,
                tick_ewma_s=round(self.tick_ewma_s, 6),
                budget_s=round(self.tick_budget_s, 6),
                tolerance=self.tolerance, segment=self.segments)

    # --- interval reporting ----------------------------------------------
    def interval_report(self, now: Optional[float] = None) -> dict:
        """The running interval's explained numbers (without closing
        it): measured tok/s, roofline fraction, MFU, busy fraction."""
        now = time.perf_counter() if now is None else now
        elapsed = (now - self._iv_t0) if self._iv_t0 is not None else 0.0
        tok_s = self._iv_tokens / elapsed if elapsed > 0 else 0.0
        led = self.ledger
        return {
            "program": self.program,
            "interval_s": round(elapsed, 4),
            "segments": self._iv_segments,
            "steps": self._iv_steps,
            "tokens": self._iv_tokens,
            "tok_s": round(tok_s, 2),
            "ceiling_tok_s": round(led["ceiling_tok_s"], 2),
            # NOT rounded: on an off-chip lane the fraction of the chip
            # ceiling is ~1e-6 and rounding would zero the signal
            "roofline_fraction": (tok_s / led["ceiling_tok_s"]
                                  if led["ceiling_tok_s"] else 0.0),
            "mfu": (tok_s * led["flops_per_token"] / led["peak_flops_s"]
                    if led["peak_flops_s"] else 0.0),
            "busy_fraction": (round(self._iv_busy_s / elapsed, 4)
                              if elapsed > 0 else 0.0),
            "tick_ewma_s": self.tick_ewma_s,
            "tick_budget_s": self.tick_budget_s,
            "regressions": self.regressions,
        }

    def end_interval(self) -> dict:
        """Close the interval: publish the gauges, reset accumulators,
        return (and retain) the report."""
        rep = self.interval_report()
        p = self.program
        _metrics.gauge(f"perf.tok_s[{p}]").set(rep["tok_s"])
        _metrics.gauge(f"perf.roofline_fraction[{p}]").set(
            rep["roofline_fraction"])
        _metrics.gauge(f"perf.mfu[{p}]").set(rep["mfu"])
        self._iv_segments = 0
        self._iv_steps = 0
        self._iv_tokens = 0
        self._iv_busy_s = 0.0
        self._iv_t0 = None
        self.last_report = rep
        return rep

    def report(self) -> dict:
        """The ``/perf`` endpoint payload: the analytic ledger plus the
        running interval and the last closed one."""
        return {"ledger": dict(self.ledger),
                "interval": self.interval_report(),
                "last_interval": self.last_report}


# ---------------------------------------------------------------------------
# Ambient attachment (the gate's --ops mode): every engine segment feeds
# the interval accumulators through serving.SEGMENT_HOOKS. No elapsed
# time is available at that hook (the engine doesn't time its own
# dispatch→fetch span), so the sentinel stays quiet — the attachment
# proves hazard-neutrality, the schedulers provide the timed feed.
# ---------------------------------------------------------------------------

_INSTALLED: list = []


def install(monitor: PerfMonitor) -> None:
    from ..inference import serving as _serving

    for m, _ in _INSTALLED:
        if m is monitor:
            return

    def hook(steps: int, new_tokens: int, finished: int) -> None:
        monitor.note_segment(steps, new_tokens, elapsed_s=None)

    _serving.SEGMENT_HOOKS.append(hook)
    _INSTALLED.append((monitor, hook))


def uninstall(monitor: Optional[PerfMonitor] = None) -> None:
    from ..inference import serving as _serving

    keep = []
    for m, hook in _INSTALLED:
        if monitor is None or m is monitor:
            if hook in _serving.SEGMENT_HOOKS:
                _serving.SEGMENT_HOOKS.remove(hook)
        else:
            keep.append((m, hook))
    _INSTALLED[:] = keep
