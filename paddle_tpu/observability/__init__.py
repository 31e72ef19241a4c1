"""``paddle_tpu.observability`` — runtime telemetry with zero EXTRA
device→host syncs (ISSUE 5 tentpole).

Three layers over signals the framework already holds on the host:

* :mod:`metrics` — process-wide registry of counters / gauges /
  fixed-bucket histograms, Prometheus-text + JSON snapshot export, and
  rank-tagged snapshot merge for multi-process runs (launcher log-dir
  aggregation; no collective required).
* :mod:`tracing` — per-request lifecycle spans (enqueue → admit →
  prefill → decode → finish), journeys and scaling timelines emitted
  through ``profiler._hooks`` so they land in the SAME chrome trace as
  op dispatch and the serve loop's segment spans.
* :mod:`flight` — a bounded ring of recent structured events
  (admissions, backpressure, EOS, recompiles, loss-scale skips,
  prefix-cache hits/evictions) dumpable on demand, on exception, or
  on orderly exit/SIGTERM.

Plus the r14 live ops surface (ISSUE 9) over those signals:

* :mod:`slo` — per-priority-class error-budget ledgers and
  multi-window burn-rate alerting (segment-counted windows, an
  ok→warning→page state machine, ``slo_alert`` flight events).
* :mod:`perf` — the analytic roofline ledger (SCALING §3c, from the
  live param tree) joined with runtime counters: live roofline
  fraction + MFU per program, and an EWMA tick-time regression
  sentinel (``perf_regression`` events).
* :mod:`exporter` — ``OpsServer``, an explicit-start stdlib HTTP
  scrape surface: ``/metrics`` ``/snapshot.json`` ``/healthz``
  ``/flight`` ``/slo`` ``/perf`` (r16: + ``/journal`` and
  ``/request/<rid>``; r17: + ``/quality``).
* :mod:`quality` — r17 (ISSUE 12) online quality observability:
  shadow-pair diffing (token-match-rate, exact first-divergence
  position, logit-error budgets over the r17 in-program digests),
  ok→warning→page alert rules, and the canary controller's
  per-class verdicts with auto-hold — the quality bar every engine
  variant (quantized weights, new kernels, spec ladders) ships
  behind.

And the r16 black box (ISSUE 11) over everything above:

* :mod:`journal` — the deterministic serving journal: append-only,
  schema-versioned JSONL of every serving decision + its inputs (a
  lossless superset of flight events), per-rank files with monotonic
  seqs, size rotation, cross-replica merge, request journeys, and the
  recorded decision clock (``journal.now()``) that makes replay exact.
* :mod:`replay` — bit-exact incident replay: rebuild the serve from
  the journal header, re-run it on the recorded clock, and diff the
  decision + token stream (identity certified, or the first divergence
  named as seq/kind/field).

The hard contract: instrumentation consumes device values ONLY at the
two sanctioned ``allowed_sync`` points (serving's per-segment event
fetch, AMP's fused finite check). ``metrics`` refuses device values
outright, and ``python -m paddle_tpu.analysis --gate`` runs with
telemetry enabled — per-program sync/compile/relayout budgets must be
bit-identical to the uninstrumented programs
(``tests/test_observability.py::TestTelemetryAudit``).

Quick use::

    from paddle_tpu import observability as obs

    obs.metrics.counter("my.requests").inc()
    obs.metrics.histogram("my.latency_s").observe(0.012)   # host float!
    print(obs.metrics.render_prometheus())
    snap = obs.metrics.snapshot()                # JSON-able dict
    obs.flight.dump("postmortem.json")           # recent events

``set_enabled(False)`` turns every record path into a single-branch
no-op (the ≤2 % serving overhead gate compares against exactly that).
"""

from __future__ import annotations

from . import (capacity, exporter, flight, journal, metrics, perf,
               quality, replay, slo, tracing)
from .capacity import (CapacityMonitor, PoolMonitor, aggregate_meters,
                       attribute_request, capacity_plan)
from .exporter import OpsServer
from .flight import FLIGHT, dump_on_exception
from .journal import Journal, read_journal, request_journey
from .quality import CanaryController, QualityMonitor, compare_pair
from .metrics import (counter, enabled, gauge, histogram, merge_log_dir,
                      merge_snapshots, percentile, registry,
                      render_prometheus, reset, set_enabled, snapshot,
                      write_snapshot)
from .perf import PerfMonitor, serving_ledger
from .replay import replay_serve
from .slo import Objective, SLOMonitor
from .tracing import emit_journey_trace, emit_request_trace, span

__all__ = [
    "metrics", "tracing", "flight", "slo", "perf", "exporter", "journal",
    "replay", "quality", "capacity", "QualityMonitor", "CanaryController",
    "CapacityMonitor", "PoolMonitor", "capacity_plan",
    "attribute_request", "aggregate_meters",
    "compare_pair", "counter",
    "gauge", "histogram", "percentile", "registry", "snapshot",
    "render_prometheus", "merge_snapshots", "merge_log_dir",
    "write_snapshot", "reset", "set_enabled", "enabled", "span",
    "emit_request_trace", "emit_journey_trace", "FLIGHT",
    "dump_on_exception",
    "install_compile_listener", "Objective", "SLOMonitor", "PerfMonitor",
    "serving_ledger", "OpsServer", "Journal", "read_journal",
    "request_journey", "replay_serve",
]


# ---------------------------------------------------------------------------
# Compile events: the PR 4 CompileWatch monitoring channel, made a
# standing telemetry source — every real XLA backend compile increments
# ``jit.backend_compiles`` and leaves a flight event (a mid-serve
# recompile is the 2.5 s latency-cliff class; the flight ring makes the
# postmortem trivial). The listener is one string compare per monitoring
# event, installed once at package import (idempotent; jax is already an
# unconditional framework dependency by the time anything imports this).
# ---------------------------------------------------------------------------

_COMPILE_LISTENER = [None]


def install_compile_listener() -> None:
    if _COMPILE_LISTENER[0] is not None:
        return
    import jax.monitoring as mon

    from ..analysis.recompile import CompileWatch

    compiles = metrics.counter(
        "jit.backend_compiles",
        "real XLA backend compilations (CompileWatch channel)")

    def listener(event: str, duration: float, **kw) -> None:
        if event == CompileWatch._EVENT:
            compiles.inc()
            flight.record("recompile", duration_s=round(duration, 4))

    mon.register_event_duration_secs_listener(listener)
    _COMPILE_LISTENER[0] = listener


install_compile_listener()
