"""Request, journey and scaling lifecycles as spans over ``profiler._hooks``.

``profiler._hooks.span`` is the program's one span primitive: it writes a
``jax.profiler.TraceAnnotation`` into a live jax trace (the xplane's host
plane, on the device planes' clock) and reports to every recording
``paddle.profiler.Profiler``. The serve loop's spans are there, where the
work happens: ``serving.sched.ingest``, ``serving.segment`` and its phases
``serving.segment.{pick,inputs,launch,fetch,replay,telemetry}`` (``put``,
the host -> device copies, inside ``inputs``; inference/scheduler.py,
inference/serving.py). ``serving.segment.gap`` (an engine's fetch return
-> its next launch return, PR 38) is stamped from two of those spans'
own stamps and, like the replays below, reaches collectors only.

This module adds what can only be stamped AFTER the fact, from
``perf_counter`` stamps the serve loop already took, with no clock source
or sync of its own. These reach collectors only (a TraceAnnotation cannot
be back-dated); ``Profiler.export_chrome_tracing`` places them on the
trace's clock by a measured offset:

* **Request traces** — the scheduler stamps each ``Request``'s lifecycle
  (arrival → admit → first-token → finish) at the per-segment
  ``allowed_sync`` fetch; ``emit_request_trace`` replays those stamps as
  spans (``request.queue_wait`` / ``request.prefill`` /
  ``request.decode`` / ``request.e2e``) so a p99 outlier decomposes in
  the same trace viewer that shows segments and op dispatch.
* **Journeys** and **scaling timelines** — the same, from journal records
  (``emit_journey_trace``, ``emit_scaling_trace``).

Everything is emit-only: when no ``Profiler`` is active the replays
return at their first line.
"""

from __future__ import annotations

from ..profiler import _hooks

__all__ = ["span", "emit_request_trace", "emit_journey_trace",
           "emit_scaling_trace", "active"]

span = _hooks.span          # re-export: the RAII host span
active = _hooks.active


def _ns(t_s: float) -> int:
    return int(t_s * 1e9)


def emit_request_trace(rid: int, arrival_s: float, admit_s: float,
                       first_token_s: float, finish_s: float,
                       prefix_hit_len: int = 0) -> None:
    """Emit one finished request's lifecycle as host spans.

    Stamps are ``time.perf_counter`` seconds taken at the syncs that
    actually surfaced each event (the r7 measured-latency contract);
    zero-duration phases (e.g. first token AT finish) are skipped. The
    rid and prefix reuse ride in the span name so the trace viewer can
    group and filter without a metadata channel."""
    if not _hooks.COLLECTORS:
        return
    tag = f"req{rid}" + (f"+prefix{prefix_hit_len}" if prefix_hit_len
                         else "")
    kind = "serving.request"
    if admit_s > arrival_s > 0:
        _hooks.emit(f"request.queue_wait[{tag}]", _ns(arrival_s),
                    _ns(admit_s), kind=kind)
    if first_token_s > admit_s > 0:
        _hooks.emit(f"request.prefill[{tag}]", _ns(admit_s),
                    _ns(first_token_s), kind=kind)
    if finish_s > first_token_s > 0:
        _hooks.emit(f"request.decode[{tag}]", _ns(first_token_s),
                    _ns(finish_s), kind=kind)
    if finish_s > arrival_s > 0:
        _hooks.emit(f"request.e2e[{tag}]", _ns(arrival_s), _ns(finish_s),
                    kind=kind)


def emit_journey_trace(journey: dict) -> None:
    """Emit one journal-reconstructed request journey (r16, ISSUE 11:
    ``journal.request_journey``) as chrome-trace spans: one span per
    causal hop (arrival→dispatch, dispatch→admit, admit→first_token,
    …→finish), named ``journey.<to_kind>[req<rid>@r<rank>]`` so a
    cross-replica failover shows up as the rank changing mid-lane in
    the same viewer that shows segments and op dispatch. Wall stamps
    come from the journal records' write times — the journey is a
    postmortem reconstruction, so journal-write wall time IS the
    decision time. Free when no profiler collects."""
    if not _hooks.COLLECTORS:
        return
    evs = journey.get("events") or []
    rid = journey.get("rid")
    for a, b in zip(evs, evs[1:]):
        if b["t"] <= a["t"]:
            continue
        _hooks.emit(f"journey.{b['kind']}[req{rid}@r{b['rank']}]",
                    _ns(a["t"]), _ns(b["t"]), kind="serving.journey")


def emit_scaling_trace(records: list) -> None:
    """Emit an elastic episode's scaling timeline (r25, ISSUE 20) as
    chrome-trace spans from its journaled ``scale_decision`` records
    (``journal.tail(kind="scale_decision")`` rows or the policy's
    ``decision_log``). Two span families:

    * ``scaling.drain[r<idx>]`` — each replica's scale_down →
      drain_complete window (the polite-drain cost, visible next to
      the segments that finished inside it);
    * ``scaling.<action>→<action>[...]`` — consecutive decisions as
      intervals, so the viewer shows how long each fleet size held.

    Stamps come from the records' ``t`` fields (journal write times —
    the decision times). Free when no profiler collects."""
    if not _hooks.COLLECTORS or not records:
        return
    recs = sorted(records, key=lambda r: r["t"])
    drain_open: dict = {}
    for r in recs:
        if r["action"] == "scale_down":
            drain_open[r["replica"]] = r["t"]
        elif r["action"] == "drain_complete":
            t0 = drain_open.pop(r["replica"], None)
            if t0 is not None and r["t"] > t0:
                _hooks.emit(f"scaling.drain[r{r['replica']}]",
                            _ns(t0), _ns(r["t"]),
                            kind="serving.scaling")
    for a, b in zip(recs, recs[1:]):
        if b["t"] <= a["t"]:
            continue
        tag = f"r{a['replica']}" if a.get("replica") is not None else ""
        _hooks.emit(
            f"scaling.{a['action']}→{b['action']}[{tag}]",
            _ns(a["t"]), _ns(b["t"]), kind="serving.scaling")
