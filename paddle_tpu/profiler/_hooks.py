"""The program's one span primitive: host spans for the profiler's own trace.

Reference counterpart: the C++ host tracer's RAII ``RecordEvent`` calls
sprinkled through the eager layer and executor (SURVEY.md §5.1). Two
channels leave this module, and ``span`` writes to both:

* **the jax profiler's trace.** ``span`` enters a
  ``jax.profiler.TraceAnnotation``: while a jax trace is live
  (``jax.profiler.start_trace`` — ``paddle.profiler.Profiler`` or a
  benchmark's traced slice) the span lands on the xplane's ``/host:CPU``
  plane, on the SAME clock as the ``/device:*`` planes, with its keyword
  ids (``seg=``, ``pc_ns=`` …) as event stats. With no trace live TraceMe
  is a no-op.
* **collectors on ``perf_counter``'s clock.** ``paddle.profiler.Profiler``
  registers itself in ``COLLECTORS`` while recording and receives every
  span and every ``emit`` (its host tables). Spans stamped after the fact
  (``emit`` with two ``perf_counter_ns`` stamps — request lifecycles)
  reach only this channel; a ``serving.segment`` span carries its own
  ``perf_counter_ns`` start as the stat ``pc_ns``, which is the measured
  offset between the two clocks.

``span(..., tally=d)`` also adds the span's duration to ``d[name]``
(``[ns, count]``): the always-on reduction ``OnlineReport.segment_phases``
is read from. A span keeps its two stamps (``t0``, ``t1``) after it
closes, so an interval between two spans is timed with no clock read of
its own and reported by ``record`` (the engine's ``serving.segment.gap``,
PR 38). Spans are per segment or per request, never per op:
``ops.dispatch`` (the hot path) imports nothing but this module, calls
``emit`` behind its single ``if COLLECTORS`` check, and gets no annotation.
"""

from __future__ import annotations

import time
from typing import List, Optional

from jax.profiler import TraceAnnotation

# active Profiler instances (a stack: nested profilers each get events)
COLLECTORS: List[object] = []


def active() -> bool:
    return bool(COLLECTORS)


def now_ns() -> int:
    return time.perf_counter_ns()


def tracing() -> bool:
    """Whether a jax trace is live (annotations are being recorded)."""
    return TraceAnnotation.is_enabled()


def emit(name: str, start_ns: int, end_ns: int, kind: str = "op") -> None:
    for c in COLLECTORS:
        c._host_event(name, start_ns, end_ns, kind)


def record(name: str, start_ns: int, end_ns: int, kind: str = "op",
           tally: Optional[dict] = None) -> None:
    """A finished interval: added to ``tally[name]`` (``[ns, count]``)
    and, while a profiler records, emitted to it. Back-dated, so it
    reaches no jax trace."""
    if tally is not None:
        acc = tally.setdefault(name, [0, 0])
        acc[0] += end_ns - start_ns
        acc[1] += 1
    if COLLECTORS:
        emit(name, start_ns, end_ns, kind)


class span:
    """RAII host span (the RecordEvent analog for non-op subsystems): the
    serving scheduler and engine wrap each phase of a segment in one, so a
    trace shows what the host was doing beside the device's programs.
    ``ids`` ride the trace event as stats; ``tally`` (a dict) accumulates
    ``[total ns, count]`` under the span's name; ``t0`` / ``t1`` are its
    ``perf_counter_ns`` stamps. With no trace live and no collector the
    cost is two clock reads and a no-op TraceMe."""

    __slots__ = ("name", "kind", "tally", "t0", "t1", "_ann")

    def __init__(self, name: str, kind: str = "op",
                 tally: Optional[dict] = None, **ids):
        self.name = name
        self.kind = kind
        self.tally = tally
        self._ann = TraceAnnotation(name, **ids)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = now_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = now_ns()
        self._ann.__exit__(*exc)
        record(self.name, self.t0, self.t1, self.kind, self.tally)
        return False
