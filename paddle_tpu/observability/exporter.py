"""Operator scrape endpoint — a stdlib ``http.server`` surface over the
observability package's host-side state (ISSUE 9 tentpole, part 3).

Everything the registry/monitors/recorder hold is host data, so serving
it over HTTP is pure plumbing — the handler never touches a device
value and a scrape can never trigger a sync (the same contract the rest
of the package enforces at the record path). Endpoints:

=================  =======================================================
``/metrics``       Prometheus text exposition of the process registry
                   (or an attached one) — the standard scrape target.
``/snapshot.json`` Rank-tagged JSON snapshot; with ``log_dir`` set and
                   ``?merged=1`` (or ``/snapshot.json?merged=1``), the
                   ``merge_log_dir`` reduction over every
                   ``telemetry_rank*.json`` — the fleet view.
``/healthz``       Liveness + the r13 replica health machine: attached
                   ``FleetRouter`` replicas (live view) or the
                   ``fleet.replica_health`` gauge by rank from a merged
                   log dir. 200 while any replica serves, 503 when none.
``/flight``        Flight-recorder tail (``?n=`` bounds it, default 64;
                   r16: ``?kind=`` / ``?rid=`` filter by event kind /
                   request id).
``/slo``           The SLO monitor's budget/burn/alert state.
``/quality``       The shadow-diff quality monitor's state (r17,
                   ISSUE 12): token-match-rate, first-divergence
                   positions, logit-error stats, alert level/timeline
                   — plus the canary controller's verdicts when one is
                   attached.
``/perf``          The explained-performance ledger + interval report.
``/capacity``      The r18 capacity plane (ISSUE 13): exhaustion-alert
                   state (time-to-exhaustion, ok→warning→page),
                   per-pool breakdown (free / live / cache-held with
                   the reclaimable subset, COW ratio, high-water,
                   occupancy timeline) and per-replica page capacity;
                   ``?audit=1`` additionally runs the leak audit
                   (``leak_report``) and reports ``audit_clean``.
``/autoscaler``    The r25 elastic control loop (ISSUE 20): per-policy
                   desired vs actual replicas, lifecycle per replica,
                   scale-up/down/refusal counters, total warmup paid,
                   the last ``scale_decision`` (with its full input
                   vector + reason) and live drain progress.
``/journal``       Deterministic-journal tail (r16, ISSUE 11): the
                   lossless decision stream's newest records, filtered
                   by ``?n=`` / ``?kind=`` / ``?rid=`` — reads the
                   attached journal (or the process-wide one).
``/request/<rid>`` One request's cross-replica journey: the causal
                   record timeline (arrival → dispatch → admit →
                   preempt/failover → finish) joined from the journal.
=================  =======================================================

The server is started and stopped EXPLICITLY (``start()`` binds and
returns the port — pass ``port=0`` for an ephemeral loopback port;
``stop()`` joins the thread), so tier-1 never binds a port by accident:
constructing an ``OpsServer`` costs nothing until ``start()``.
Context-manager use closes it deterministically in tests::

    with OpsServer(port=0, slo_monitor=mon) as srv:
        urllib.request.urlopen(f"{srv.url}/metrics")
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

from . import flight as _flight
from . import metrics as _metrics

__all__ = ["OpsServer"]

_HEALTH_NAMES = {0.0: "healthy", 1.0: "suspect", 2.0: "dead"}


class OpsServer:
    """Scrape surface over the process (or an attached) registry, the
    flight recorder, and the optional SLO/perf monitors and fleet.

    ``registry``: defaults to the process-wide one at request time (so
    ``scoped_registry`` fleets export what they recorded). ``fleet``: a
    ``FleetRouter`` for the live ``/healthz`` replica view. ``log_dir``:
    where rank snapshots live for the merged views. ``recorder``:
    defaults to the process flight ring."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 registry: Optional[_metrics.Registry] = None,
                 slo_monitor=None, perf_monitor=None, fleet=None,
                 log_dir: Optional[str] = None, recorder=None,
                 journal=None, quality_monitor=None, canary=None,
                 capacity_monitor=None, pool_monitor=None,
                 autoscaler=None):
        self.host = host
        self.port = int(port)
        self.registry = registry
        self.slo_monitor = slo_monitor
        self.perf_monitor = perf_monitor
        self.fleet = fleet
        self.log_dir = log_dir
        self.recorder = recorder
        self.journal = journal         # r16: explicit > process-attached
        # r17 (ISSUE 12): explicit quality monitor / canary controller;
        # with a fleet attached, its shadow's monitor and canary are
        # the fallbacks (the live wiring an operator actually has)
        self.quality_monitor = quality_monitor
        self.canary = canary
        # r18 (ISSUE 13): the capacity signal plane — exhaustion-alert
        # monitor + per-pool breakdown, served at /capacity (with
        # ?audit=1 wiring the leak audit into the scrape surface)
        self.capacity_monitor = capacity_monitor
        self.pool_monitor = pool_monitor
        # r25 (ISSUE 20): explicit autoscaler policy/policies; with a
        # fleet attached, its bound policies are the fallback (the live
        # wiring an operator actually has)
        self.autoscaler = autoscaler
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # --- lifecycle --------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._httpd is not None

    @property
    def url(self) -> str:
        if not self.running:
            raise RuntimeError("OpsServer not started")
        return f"http://{self.host}:{self.port}"

    def start(self) -> int:
        """Bind and serve on a daemon thread; returns the bound port
        (the real one when constructed with ``port=0``)."""
        if self._httpd is not None:
            return self.port
        handler = _make_handler(self)
        self._httpd = ThreadingHTTPServer((self.host, self.port), handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name=f"ops-server:{self.port}", daemon=True)
        self._thread.start()
        return self.port

    def stop(self) -> None:
        if self._httpd is None:
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)
        self._httpd = None
        self._thread = None

    def __enter__(self) -> "OpsServer":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # --- payload builders (host data only) --------------------------------
    def _registry(self) -> _metrics.Registry:
        return self.registry if self.registry is not None \
            else _metrics.registry()

    def _recorder(self):
        return self.recorder if self.recorder is not None \
            else _flight.FLIGHT

    def payload_metrics(self) -> str:
        return self._registry().render_prometheus()

    def payload_snapshot(self, merged: bool = False) -> dict:
        if merged:
            if not self.log_dir:
                raise FileNotFoundError(
                    "merged snapshot requested but no log_dir attached")
            return _metrics.merge_log_dir(self.log_dir)
        return self._registry().snapshot()

    def payload_healthz(self) -> tuple:
        """(status_code, body): per-replica health from the live router
        when attached, else from the merged log dir's
        ``fleet.replica_health`` gauge, else plain process liveness."""
        replicas = None
        if self.fleet is not None:
            replicas = {str(r.idx): r.health
                        for r in self.fleet._replicas}
        elif self.log_dir:
            try:
                merged = _metrics.merge_log_dir(self.log_dir)
                by_rank = merged["gauges"].get(
                    "fleet.replica_health", {}).get("by_rank", {})
                replicas = {rank: _HEALTH_NAMES.get(code, "unknown")
                            for rank, code in by_rank.items()} or None
            except FileNotFoundError:
                replicas = None
        body = {"status": "ok"}
        if replicas is not None:
            healthy = sum(1 for h in replicas.values() if h == "healthy")
            body = {"status": ("ok" if healthy == len(replicas)
                               else "degraded" if healthy else "dead"),
                    "replicas": replicas,
                    "healthy": healthy, "total": len(replicas)}
        if self.fleet is not None:
            # r18 (ISSUE 13 satellite): per-replica page capacity next
            # to health — the scrape-visible form of the pages-aware
            # candidate ranking (r12) and the item-4 autoscaler's
            # scale-up signal, read off the same host mirrors the
            # router ranks on
            pages = {}
            for r in self.fleet._replicas:
                pc = r.prefix_cache
                row = {
                    "pages_free": r.engine.pager.pages_free,
                    "reclaimable": (pc.reclaimable_pages()
                                    if pc is not None else 0),
                }
                tier = getattr(pc, "host_tier", None)
                if tier is not None:
                    # r19 (ISSUE 14): the tier dimension next to health
                    # — hbm/host page split + transfer counters, read
                    # off the same host mirrors the router ranks on
                    row["tiers"] = {
                        "host_pages": tier.pages_host,
                        "spills": tier.spills,
                        "restores": tier.restores,
                        "imports": tier.imports,
                        "bytes_staged": tier.bytes_to_host,
                        "bytes_restored": tier.bytes_to_hbm,
                    }
                if getattr(r, "pool", None) is not None:
                    # r22 (ISSUE 17): pool role next to health — which
                    # side of the disaggregated split this replica is
                    row["pool"] = r.pool
                pages[str(r.idx)] = row
            if pages:
                body["pages"] = pages
            pools = _pool_rollup(self.fleet)
            if pools:
                body["pools"] = pools
        scale = _scale_rollup(self._autoscalers())
        if scale is not None:
            # r25 (ISSUE 20 satellite): elastic state next to health —
            # desired vs actual, per-replica lifecycle, the last scale
            # decision + reason, and drain progress
            body["scale"] = scale
        if self.slo_monitor is not None:
            body["slo_level"] = self.slo_monitor.worst_level()
        if self.capacity_monitor is not None:
            body["capacity_level"] = self.capacity_monitor.level
        code = 503 if body["status"] == "dead" else 200
        return code, body

    def payload_flight(self, n: int = 64, kind: Optional[str] = None,
                       rid: Optional[int] = None) -> dict:
        rec = self._recorder()
        evs = rec.events(kind, rid=rid)
        return {"capacity": rec.capacity,
                "total_buffered": len(rec),
                "dropped_events": rec.dropped_events,
                "matched": len(evs),
                "events": evs[-max(1, int(n)):]}

    def _journal(self):
        from . import journal as _jrnl

        j = self.journal if self.journal is not None else _jrnl.active()
        if j is None:
            raise FileNotFoundError(
                "no journal attached (pass journal= or journal.install)")
        return j

    def payload_journal(self, n: int = 64, kind: Optional[str] = None,
                        rid: Optional[int] = None) -> dict:
        j = self._journal()
        evs = j.tail(n, kind=kind, rid=rid)
        return {"total_records": j.total_records, "serves": j.serves,
                "dir": j.dir, "matched": len(evs), "records": evs}

    def payload_request(self, rid: int) -> dict:
        """The cross-replica journey join — reads the journal's full
        record stream (files when file-backed), not just the tail."""
        return self._journal().request_journey(rid)

    def _quality_monitor(self):
        if self.quality_monitor is not None:
            return self.quality_monitor
        if self.fleet is not None and getattr(self.fleet, "shadow",
                                              None) is not None:
            return self.fleet.shadow.monitor
        return None

    def _canary(self):
        if self.canary is not None:
            return self.canary
        if self.fleet is not None:
            return getattr(self.fleet, "canary", None)
        return None

    def payload_quality(self) -> dict:
        mon = self._quality_monitor()
        can = self._canary()
        if mon is None and can is None:
            return {"enabled": False}
        out = {"enabled": True}
        if mon is not None:
            out.update(mon.report())
        if can is not None:
            out["canary"] = can.report()
        return out

    def payload_capacity(self, audit: bool = False) -> dict:
        """The r18 capacity view: monitor alert state + per-pool
        breakdown (attached ``PoolMonitor``, or the fleet's paged
        replicas), with ``audit=True`` additionally running the
        operational leak audit (``FleetRouter.leak_report`` /
        ``PagedKVCache.leak_report``) — the programmatic-only audit
        made scrape-visible (ISSUE 13 satellite). All host data."""
        mon = self.capacity_monitor
        pm = self.pool_monitor
        if mon is None and pm is None and self.fleet is None:
            return {"enabled": False}
        out = {"enabled": True}
        if mon is not None:
            out["monitor"] = mon.report()
        if pm is not None:
            out["pool"] = pm.snapshot()
        if self.fleet is not None:
            reps = {}
            for r in self.fleet._replicas:
                pc = r.prefix_cache
                row = {
                    "health": r.health,
                    **r.engine.pager.stats(),
                    "reclaimable": (pc.reclaimable_pages()
                                    if pc is not None else 0),
                }
                tier = getattr(pc, "host_tier", None)
                if tier is not None:
                    row["tiers"] = tier.stats()
                if getattr(r, "pool", None) is not None:
                    row["pool"] = r.pool      # r22: disagg pool role
                reps[str(r.idx)] = row
            if reps:
                out["replicas"] = reps
            pools = _pool_rollup(self.fleet)
            if pools:
                out["pools"] = pools
            if getattr(self.fleet, "directory", None) is not None:
                out["directory"] = self.fleet.directory.stats()
        scale = _scale_rollup(self._autoscalers())
        if scale is not None:
            out["scale"] = scale    # r25: capacity is elastic now
        if audit:
            if self.fleet is not None:
                out["audit"] = self.fleet.leak_report()
            elif pm is not None:
                pc = pm.prefix_cache
                held = 0
                if pc is not None:
                    held = pc.physical_pages_held()
                out["audit"] = pm.pager.leak_report(expected_held=held)
            else:
                out["audit"] = []
            out["audit_clean"] = not out["audit"]
        return out

    def _autoscalers(self) -> list:
        if self.autoscaler is not None:
            return (list(self.autoscaler)
                    if isinstance(self.autoscaler, (list, tuple))
                    else [self.autoscaler])
        if self.fleet is not None:
            return list(getattr(self.fleet, "autoscalers", []) or [])
        return []

    def payload_autoscaler(self) -> dict:
        """The r25 elastic control loop's live state: one section per
        policy (``Autoscaler.report()``) — desired vs actual, replica
        lifecycles, action counters, last journaled decision with its
        input vector + reason, and in-flight drain progress."""
        ascs = self._autoscalers()
        if not ascs:
            return {"enabled": False}
        return {"enabled": True,
                "policies": [a.report() for a in ascs]}

    def payload_slo(self) -> dict:
        if self.slo_monitor is None:
            return {"enabled": False}
        return {"enabled": True, **self.slo_monitor.report()}

    def payload_perf(self) -> dict:
        if self.perf_monitor is None:
            return {"enabled": False}
        return {"enabled": True, **self.perf_monitor.report()}


def _pool_rollup(fleet) -> dict:
    """Per-pool aggregates for a pool-aware fleet (r22 DisaggRouter):
    replica membership, healthy count, and the summed ``pages_free`` /
    ``reclaimable`` availability axes — the scrape-visible form the
    item-3 autoscaler sizes pools from. Empty dict for a homogeneous
    fleet (no replica carries a pool role). All host mirrors."""
    pools: dict = {}
    for r in fleet._replicas:
        pool = getattr(r, "pool", None)
        if pool is None:
            continue
        row = pools.setdefault(pool, {
            "replicas": [], "healthy": 0,
            "pages_free": 0, "reclaimable": 0})
        row["replicas"].append(r.idx)
        row["healthy"] += 1 if r.health == "healthy" else 0
        row["pages_free"] += r.engine.pager.pages_free
        pc = r.prefix_cache
        if pc is not None:
            row["reclaimable"] += pc.reclaimable_pages()
    return pools


def _scale_rollup(autoscalers) -> Optional[dict]:
    """Fleet-level elastic rollup for /healthz and /capacity (r25,
    ISSUE 20 satellite): desired vs actual across every attached
    policy, per-replica lifecycle, the last journaled scale decision
    (action + reason) and in-flight drain progress. ``None`` when no
    policy is attached — the pre-elastic payloads are unchanged. All
    host mirrors."""
    if not autoscalers:
        return None
    out = {"desired": sum(a.desired for a in autoscalers),
           "actual": sum(a.actual for a in autoscalers),
           "drain_inflight": sum(a.drain_inflight
                                 for a in autoscalers),
           "scale_ups": sum(a.scale_ups for a in autoscalers),
           "scale_downs": sum(a.scale_downs for a in autoscalers)}
    lifecycles: dict = {}
    drains: dict = {}
    last = None
    for a in autoscalers:
        rep = a.report()
        lifecycles.update(rep.get("lifecycles", {}))
        drains.update(rep.get("drains", {}))
        ld = rep.get("last_decision")
        if ld is not None and (last is None or ld["t"] >= last["t"]):
            last = ld
    if lifecycles:
        out["lifecycles"] = lifecycles
    if drains:
        out["drains"] = drains
    if last is not None:
        out["last_decision"] = {"t": last["t"],
                                "action": last["action"],
                                "pool": last["pool"],
                                "replica": last["replica"],
                                "reason": last["reason"]}
    return out


def _make_handler(srv: OpsServer):
    class Handler(BaseHTTPRequestHandler):
        # ops traffic must not spam the serving process's stderr
        def log_message(self, fmt, *args):
            pass

        def _send(self, code: int, body, content_type: str) -> None:
            data = (body if isinstance(body, bytes)
                    else body.encode("utf-8"))
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _send_json(self, code: int, obj) -> None:
            self._send(code, json.dumps(obj, indent=1, default=str),
                       "application/json")

        def do_GET(self):
            u = urlparse(self.path)
            q = parse_qs(u.query)
            try:
                if u.path == "/metrics":
                    self._send(200, srv.payload_metrics(),
                               "text/plain; version=0.0.4")
                elif u.path == "/snapshot.json":
                    merged = q.get("merged", ["0"])[0] in ("1", "true")
                    self._send_json(200, srv.payload_snapshot(merged))
                elif u.path == "/healthz":
                    code, body = srv.payload_healthz()
                    self._send_json(code, body)
                elif u.path == "/flight":
                    n = int(q.get("n", ["64"])[0])
                    kind = q.get("kind", [None])[0]
                    rid = q.get("rid", [None])[0]
                    self._send_json(200, srv.payload_flight(
                        n, kind=kind,
                        rid=int(rid) if rid is not None else None))
                elif u.path == "/slo":
                    self._send_json(200, srv.payload_slo())
                elif u.path == "/capacity":
                    audit = q.get("audit", ["0"])[0] in ("1", "true")
                    self._send_json(200, srv.payload_capacity(audit))
                elif u.path == "/quality":
                    self._send_json(200, srv.payload_quality())
                elif u.path == "/perf":
                    self._send_json(200, srv.payload_perf())
                elif u.path == "/autoscaler":
                    self._send_json(200, srv.payload_autoscaler())
                elif u.path == "/journal":
                    n = int(q.get("n", ["64"])[0])
                    kind = q.get("kind", [None])[0]
                    rid = q.get("rid", [None])[0]
                    self._send_json(200, srv.payload_journal(
                        n, kind=kind,
                        rid=int(rid) if rid is not None else None))
                elif u.path.startswith("/request/"):
                    rid = int(u.path[len("/request/"):])
                    self._send_json(200, srv.payload_request(rid))
                elif u.path == "/":
                    self._send_json(200, {
                        "endpoints": ["/metrics", "/snapshot.json",
                                      "/healthz", "/flight", "/slo",
                                      "/quality", "/perf", "/capacity",
                                      "/autoscaler", "/journal",
                                      "/request/<rid>"]})
                else:
                    self._send_json(404, {"error": f"no route {u.path}"})
            except FileNotFoundError as e:
                self._send_json(404, {"error": str(e)})
            except Exception as e:   # scrape must never kill the server
                self._send_json(500, {"error": f"{type(e).__name__}: {e}"})

    return Handler
