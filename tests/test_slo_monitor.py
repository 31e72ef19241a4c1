"""SLO monitor & live ops surface (r14 tentpole, ISSUE 9): burn-rate
alert rules on synthetic outcome streams, exporter endpoint round-trips
on a loopback ephemeral port, explained-perf parity vs the analytic
ledger, the regression sentinel, the cold-start metric, merge_log_dir
robustness, exit-dump hooks, and the zero-sync / bit-identity audit
with the monitors attached.

Everything serving-shaped runs on the session-scoped ``tiny_llama``
fixture + the process-wide shared program cache, and the one serve this
file pays is module-scoped — the suite-time delta stays small (tier-1
already exceeds the 870 s verify budget on this container).
"""

import json
import os
import signal
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import flight, metrics
from paddle_tpu.observability.exporter import OpsServer
from paddle_tpu.observability.perf import (PerfMonitor, V5E_HBM_BPS,
                                           V5E_PEAK_FLOPS, serving_ledger)
from paddle_tpu.observability.slo import Objective, SLOMonitor


def _feed(mon, priority, ttft, n=4, segments=1):
    """n TTFT outcomes per segment for ``segments`` segments."""
    for _ in range(segments):
        for _ in range(n):
            mon.note_ttft(priority, ttft)
        mon.end_segment()


# ---------------------------------------------------------------------------
# burn-rate rules on synthetic outcome streams (no engine, no device)
# ---------------------------------------------------------------------------


class TestObjective:
    def test_validation(self):
        with pytest.raises(ValueError):
            Objective(ttft_target_s=0.1, compliance=1.0)
        with pytest.raises(ValueError):
            Objective()          # no targets at all
        with pytest.raises(ValueError):
            SLOMonitor({})
        with pytest.raises(ValueError):
            SLOMonitor({0: Objective(ttft_target_s=1.0)},
                       fast_window=8, slow_window=4)

    def test_none_target_skips_dimension(self):
        mon = SLOMonitor({0: Objective(e2e_target_s=1.0)})
        mon.note_ttft(0, 99.0)       # no TTFT objective -> not an outcome
        mon.note_e2e(0, 0.5)
        mon.end_segment()
        st = mon.report()["classes"]["0"]
        assert st["outcomes"] == 1 and st["violations"] == 0


class TestBurnRateRules:
    def _monitor(self, **kw):
        kw.setdefault("fast_window", 2)
        kw.setdefault("slow_window", 6)
        kw.setdefault("warn_burn", 2.0)
        kw.setdefault("page_burn", 8.0)
        kw.setdefault("clear_after", 3)
        return SLOMonitor({0: Objective(ttft_target_s=0.1,
                                        compliance=0.9)}, **kw)

    def test_compliant_stream_never_alerts(self):
        mon = self._monitor()
        _feed(mon, 0, 0.05, segments=30)
        assert mon.state(0) == "ok"
        assert mon.alert_log == []
        assert mon.budget_remaining(0) == 1.0

    def test_injected_overload_pages(self):
        """All-violating traffic burns at 1/(1-0.9) = 10x >= the page
        threshold: once the slow window fills past it, the state
        escalates (through warning) to page, the alert log carries the
        timeline, and the flight ring holds slo_alert events."""
        flight.clear()
        mon = self._monitor()
        _feed(mon, 0, 0.05, segments=6)          # healthy baseline
        _feed(mon, 0, 5.0, segments=6)           # sustained overload
        assert mon.state(0) == "page"
        levels = [a["level"] for a in mon.alert_log]
        assert levels == ["warning", "page"]
        # escalation order is monotonic and carried by flight events
        evs = flight.events("slo_alert")
        assert [e["level"] for e in evs] == levels
        assert all(e["cls"] == 0 for e in evs)
        assert mon.budget_remaining(0) < 0       # budget overspent
        assert metrics.counter("slo.alerts[page]").value >= 1

    def test_budget_arithmetic(self):
        mon = SLOMonitor({0: Objective(ttft_target_s=0.1,
                                       compliance=0.9)})
        for _ in range(95):
            mon.note_ttft(0, 0.01)
        for _ in range(5):
            mon.note_ttft(0, 1.0)
        mon.end_segment()
        # 5 violations of the allowed 10 (10% of 100): half the budget
        assert mon.budget_remaining(0) == pytest.approx(0.5)

    def test_hysteresis_back_to_ok(self):
        """One calm segment must NOT clear an alert (flap suppression);
        clear_after consecutive calm segments must."""
        mon = self._monitor()
        _feed(mon, 0, 5.0, segments=6)
        assert mon.state(0) == "page"
        _feed(mon, 0, 0.01, segments=1)
        assert mon.state(0) == "page"            # still armed
        _feed(mon, 0, 5.0, segments=6)           # relapse resets streak
        _feed(mon, 0, 0.01, segments=2)
        assert mon.state(0) == "page"
        # clear_after=3: after slow-window turnover + 3 calm segments
        # in a row the level drops
        _feed(mon, 0, 0.01, segments=8)
        assert mon.state(0) == "ok"
        assert mon.alert_log[-1]["level"] == "ok"

    def test_single_segment_blip_is_suppressed(self):
        """The multi-window rule: one bad segment spikes the fast
        window but the slow window absorbs it — no page."""
        mon = self._monitor()
        _feed(mon, 0, 0.05, segments=6)
        _feed(mon, 0, 5.0, segments=1)           # one-segment blip
        _feed(mon, 0, 0.05, segments=6)
        assert all(a["level"] != "page" for a in mon.alert_log)

    def test_class_isolation_and_undeclared_ignored(self):
        mon = SLOMonitor({0: Objective(ttft_target_s=0.1, compliance=0.9),
                          1: Objective(ttft_target_s=10.0,
                                       compliance=0.9)})
        for _ in range(8):
            for _ in range(4):
                mon.note_ttft(0, 5.0)            # class 0 burns
                mon.note_ttft(1, 0.5)            # class 1 compliant
                mon.note_ttft(7, 99.0)           # undeclared: ignored
            mon.end_segment()
        assert mon.state(0) != "ok" and mon.state(1) == "ok"
        assert "7" not in mon.report()["classes"]
        assert mon.worst_level() == mon.state(0)

    def test_reset_clears_everything(self):
        mon = self._monitor()
        _feed(mon, 0, 5.0, segments=8)
        mon.reset()
        assert (mon.state(0), mon.alert_log, mon.segment_no) == \
            ("ok", [], 0)
        assert mon.budget_remaining(0) == 1.0


# ---------------------------------------------------------------------------
# explained perf: ledger parity + regression sentinel (host-only)
# ---------------------------------------------------------------------------


class TestExplainedPerf:
    def test_ledger_parity_with_analysis_arithmetic(self, tiny_llama):
        """The ledger must reproduce the SCALING §3c arithmetic from
        the LIVE param tree — recomputed here independently, the way
        benchmarks/llama_decode.py does — and carry the program's
        pinned hazard budget from analysis.budgets."""
        import jax

        from paddle_tpu.analysis import budgets

        cfg, params = tiny_llama
        batch, avg_pos = 4, 48.0
        led = serving_ledger(cfg, params, batch, avg_pos)

        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params))
        itemsize = np.dtype(cfg.dtype).itemsize
        wbytes = (n_params - cfg.vocab_size * cfg.hidden_size) * itemsize
        kv = (cfg.num_layers * 2 * avg_pos * cfg.num_kv_heads
              * cfg.head_dim * batch * itemsize)
        assert led["weight_bytes_per_tick"] == int(wbytes)
        assert led["kv_bytes_per_tick"] == int(kv)
        assert led["ceiling_tok_s"] == pytest.approx(
            batch / ((wbytes + kv) / V5E_HBM_BPS))
        b = budgets.budget_for("paged_serving_segment")
        assert led["hazard_budget"]["relayout_bytes_max"] == \
            b.relayout_bytes_max
        assert led["hazard_budget"]["allowed_syncs_per_replay"] == \
            {"serving.segment_event_fetch": 1}

    def test_interval_roofline_and_mfu(self, tiny_llama):
        """roofline_fraction == measured tok/s / analytic ceiling and
        MFU == tok/s x FLOPs/token / peak, over a deterministic
        interval (the clock is passed in)."""
        cfg, params = tiny_llama
        pm = PerfMonitor(cfg, params, batch=4, avg_pos=48.0)
        pm.note_segment(steps=10, new_tokens=40, elapsed_s=0.010)
        pm.note_segment(steps=10, new_tokens=40, elapsed_s=0.010)
        rep = pm.interval_report(now=pm._iv_t0 + 2.0)
        assert rep["tok_s"] == pytest.approx(40.0)    # 80 tokens / 2 s
        assert rep["roofline_fraction"] == pytest.approx(
            40.0 / pm.ledger["ceiling_tok_s"], rel=1e-4)
        assert rep["mfu"] == pytest.approx(
            40.0 * pm.ledger["flops_per_token"] / V5E_PEAK_FLOPS,
            rel=1e-4)
        closed = pm.end_interval()
        assert metrics.gauge(
            "perf.roofline_fraction[paged_serving_segment]").value == \
            closed["roofline_fraction"]
        # the interval reset: a fresh one starts empty
        assert pm.interval_report()["tokens"] == 0

    def test_regression_sentinel_trips_on_slow_tick(self, tiny_llama):
        cfg, params = tiny_llama
        flight.clear()
        pm = PerfMonitor(cfg, params, batch=4, tick_budget_s=0.001,
                         tolerance=1.5, ewma_alpha=1.0)
        pm.note_segment(steps=8, new_tokens=8, elapsed_s=0.008)  # 1 ms/t
        assert pm.regressions == 0
        pm.note_segment(steps=8, new_tokens=8, elapsed_s=0.080)  # 10x
        assert pm.regressions == 1
        evs = flight.events("perf_regression")
        assert evs and evs[-1]["budget_s"] == pytest.approx(0.001)
        assert evs[-1]["tick_ewma_s"] > 0.0015

    def test_self_pinned_budget(self, tiny_llama):
        """With no explicit budget the sentinel pins the warm EWMA at
        pin_after and judges later segments against it."""
        cfg, params = tiny_llama
        pm = PerfMonitor(cfg, params, batch=4, pin_after=2,
                         tolerance=2.0, ewma_alpha=1.0)
        pm.note_segment(steps=10, new_tokens=10, elapsed_s=0.010)
        pm.note_segment(steps=10, new_tokens=10, elapsed_s=0.010)
        assert pm.tick_budget_s == pytest.approx(0.001)
        pm.note_segment(steps=10, new_tokens=10, elapsed_s=0.015)
        assert pm.regressions == 0               # 1.5x < 2x tolerance
        pm.note_segment(steps=10, new_tokens=10, elapsed_s=0.050)
        assert pm.regressions == 1


# ---------------------------------------------------------------------------
# exporter round-trips (loopback, port 0 — never a fixed port)
# ---------------------------------------------------------------------------


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


class TestExporter:
    def test_endpoint_round_trips(self, tmp_path):
        reg = metrics.Registry()
        reg.counter("t.requests").inc(3)
        reg.gauge("t.depth").set(2.5)
        rec = flight.FlightRecorder(capacity=16)
        for i in range(20):
            rec.record("tick", i=i)
        mon = SLOMonitor({0: Objective(ttft_target_s=0.1)})
        _feed(mon, 0, 0.01, segments=2)
        with OpsServer(port=0, registry=reg, slo_monitor=mon,
                       recorder=rec) as srv:
            code, text = _get(srv.url + "/metrics")
            assert code == 200
            assert "t_requests_total 3" in text
            assert "t_depth 2.5" in text
            code, text = _get(srv.url + "/snapshot.json")
            snap = json.loads(text)
            assert snap["counters"]["t.requests"]["value"] == 3
            code, text = _get(srv.url + "/healthz")
            body = json.loads(text)
            assert code == 200 and body["status"] == "ok"
            assert body["slo_level"] == "ok"
            code, text = _get(srv.url + "/flight?n=5")
            fl = json.loads(text)
            assert len(fl["events"]) == 5
            assert fl["events"][-1]["i"] == 19   # newest kept, ring bound
            code, text = _get(srv.url + "/slo")
            slo = json.loads(text)
            assert slo["enabled"] and slo["classes"]["0"]["state"] == "ok"
            code, text = _get(srv.url + "/perf")
            assert json.loads(text) == {"enabled": False}
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + "/nope")
            assert ei.value.code == 404
        assert not srv.running

    def test_fleet_merged_views(self, tmp_path):
        """/snapshot.json?merged=1 and /healthz reduce the rank files
        with merge_log_dir — the fleet view without a live router."""
        for rank, health in enumerate((0.0, 2.0)):
            reg = metrics.Registry()
            reg.counter("serving.segments").inc(5 + rank)
            reg.gauge("fleet.replica_health").set(health)
            metrics.write_snapshot(str(tmp_path), rank=rank, registry=reg)
        with OpsServer(port=0, log_dir=str(tmp_path)) as srv:
            _, text = _get(srv.url + "/snapshot.json?merged=1")
            merged = json.loads(text)
            assert merged["ranks"] == [0, 1]
            assert merged["counters"]["serving.segments"]["value"] == 11
            code, text = _get(srv.url + "/healthz")
            body = json.loads(text)
            assert code == 200 and body["status"] == "degraded"
            assert body["replicas"] == {"0": "healthy", "1": "dead"}

    def test_explicit_lifecycle_no_accidental_bind(self):
        srv = OpsServer(port=0)
        assert not srv.running
        with pytest.raises(RuntimeError):
            srv.url                               # not started, no port
        port = srv.start()
        try:
            assert port > 0 and srv.running
            assert srv.start() == port            # idempotent
        finally:
            srv.stop()
        assert not srv.running


# ---------------------------------------------------------------------------
# merge_log_dir robustness (satellite): truncated rank file skip+flag
# ---------------------------------------------------------------------------


class TestMergeRobustness:
    def _write_ranks(self, d, n=2):
        for rank in range(n):
            reg = metrics.Registry()
            reg.counter("serving.segments").inc(10 * (rank + 1))
            metrics.write_snapshot(str(d), rank=rank, registry=reg)

    def test_truncated_rank_file_skipped_and_flagged(self, tmp_path):
        self._write_ranks(tmp_path)
        # replica 2 died mid-snapshot: a half-written JSON
        whole = json.dumps(metrics.Registry().snapshot(rank=2))
        (tmp_path / "telemetry_rank2.json").write_text(whole[:37])
        flight.clear()
        before = metrics.counter("telemetry.merge_skipped_files").value
        merged = metrics.merge_log_dir(str(tmp_path))
        assert merged["ranks"] == [0, 1]          # survivors merged
        assert merged["counters"]["serving.segments"]["value"] == 30
        assert merged["skipped_files"] == ["telemetry_rank2.json"]
        assert metrics.counter(
            "telemetry.merge_skipped_files").value == before + 1
        evs = flight.events("merge_skipped")
        assert evs and evs[-1]["file"] == "telemetry_rank2.json"

    def test_all_corrupt_still_raises(self, tmp_path):
        (tmp_path / "telemetry_rank0.json").write_text("{\"rank\"")
        with pytest.raises(FileNotFoundError):
            metrics.merge_log_dir(str(tmp_path))

    def test_clean_dir_has_no_skip_key(self, tmp_path):
        self._write_ranks(tmp_path)
        assert "skipped_files" not in metrics.merge_log_dir(str(tmp_path))


# ---------------------------------------------------------------------------
# exit-dump hooks (satellite): orderly kills leave a postmortem
# ---------------------------------------------------------------------------


class TestExitDumpHooks:
    def test_sigterm_dump_chains_previous_handler(self, tmp_path,
                                                  monkeypatch):
        calls = []
        prev = signal.signal(signal.SIGTERM, lambda s, f: calls.append(s))
        monkeypatch.setattr(flight, "_EXIT_HOOKS_INSTALLED", [False])
        monkeypatch.setattr(flight, "_EXIT_DUMPED", [False])
        registered = []
        monkeypatch.setattr(flight.atexit, "register",
                            lambda fn, *a: registered.append((fn, a)))
        path = str(tmp_path / "postmortem.json")
        try:
            flight.install_excepthook(path, exit_dump=True)
            flight.record("orderly_shutdown", who="test")
            signal.raise_signal(signal.SIGTERM)
            assert calls == [signal.SIGTERM]      # chained, not replaced
            assert os.path.exists(path)
            with open(path) as f:
                dump = json.load(f)
            assert dump["reason"] == "sigterm"
            kinds = [e["kind"] for e in dump["events"]]
            assert "process_exit" in kinds and "orderly_shutdown" in kinds
            # the atexit leg registered too, and the second exit path is
            # a no-op (exactly one postmortem per process)
            assert registered and registered[0][1][1] == "atexit"
            os.remove(path)
            registered[0][0](*registered[0][1])
            assert not os.path.exists(path)
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_atexit_dump_without_signal(self, tmp_path, monkeypatch):
        monkeypatch.setattr(flight, "_EXIT_DUMPED", [False])
        path = str(tmp_path / "exit.json")
        flight.record("last_words", x=1)
        flight._exit_dump(path, "atexit")
        with open(path) as f:
            dump = json.load(f)
        assert dump["reason"] == "atexit"
        assert any(e["kind"] == "last_words" for e in dump["events"])


# ---------------------------------------------------------------------------
# serving integration: one module-scoped monitored serve (the only
# engine work this file pays) + the audit contracts
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def monitored_serve(tiny_llama):
    """One SLOScheduler serve with monitors + exporter attached —
    shared by the assertions below (module scope: ~one segment-program
    compile against the shared cache)."""
    from paddle_tpu.inference.scheduler import (SLOScheduler,
                                                staggered_arrivals)
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    cfg, params = tiny_llama
    eng = ServingEngine(cfg, params, slots=2, max_len=96,
                        prompt_buckets=(8, 16, 32))
    mon = SLOMonitor({0: Objective(ttft_target_s=30.0, e2e_target_s=60.0,
                                   compliance=0.9)},
                     fast_window=2, slow_window=6)
    pm = PerfMonitor(cfg, params, batch=eng.slots, avg_pos=16.0)
    sch = SLOScheduler(eng, max_queue=8, seg_steps=8, slo_monitor=mon,
                       perf_monitor=pm)
    arr = staggered_arrivals(7, 5, 0.002, cfg.vocab_size,
                             prompt_lens=(6, 12), gen_lens=(4, 8))
    rep = sch.serve(arr)
    sch.results()
    return eng, mon, pm, sch, rep, arr


class TestServingIntegration:
    def test_cold_start_first_class_metric(self, monitored_serve):
        """ROADMAP item 5's first deliverable: build->first-token is a
        real gauge + report field, stamped once per engine lifetime."""
        eng, _, _, _, rep, _ = monitored_serve
        assert eng.cold_start_s is not None and eng.cold_start_s > 0
        assert rep.cold_start_s == pytest.approx(eng.cold_start_s,
                                                 abs=1e-3)
        # reset_slots is not a rebuild: the stamp survives warm resets
        first = eng.cold_start_s
        eng.reset_slots()
        assert eng.cold_start_s == first

    def test_report_carries_slo_and_perf(self, monitored_serve):
        _, mon, pm, _, rep, _ = monitored_serve
        assert rep.slo is not None
        assert rep.slo["worst_level"] == "ok"     # loose targets: quiet
        assert rep.slo["alerts"] == []
        cls = rep.slo["classes"]["0"]
        assert cls["outcomes"] == 10              # 5 TTFT + 5 e2e
        assert cls["violations"] == 0
        assert rep.slo["segments"] == rep.segments
        assert rep.perf is not None
        assert rep.perf["segments"] == rep.segments
        assert rep.perf["steps"] == rep.ticks
        assert rep.perf["tokens"] == rep.total_tokens
        # the explained join: the monitor's live roofline fraction and
        # the report's own throughput describe the same serve
        frac = rep.perf["tok_s"] / pm.ledger["ceiling_tok_s"]
        assert rep.perf["roofline_fraction"] == pytest.approx(frac,
                                                              rel=1e-3)

    def test_page_alert_fires_under_tight_objective(self, tiny_llama,
                                                    monitored_serve):
        """Re-serve the same trace against an impossible objective: the
        burn-rate machine must page DURING the serve (flight-evidenced),
        without touching the serve's results."""
        from paddle_tpu.inference.scheduler import SLOScheduler
        from paddle_tpu.inference.serving import ServingEngine

        eng, _, _, _, rep_ok, arr = monitored_serve
        cfg, params = tiny_llama
        flight.clear()
        eng2 = ServingEngine(cfg, params, slots=2, max_len=96,
                             prompt_buckets=(8, 16, 32))
        mon = SLOMonitor({0: Objective(ttft_target_s=1e-9,
                                       compliance=0.9)},
                         fast_window=1, slow_window=2, clear_after=99)
        sch = SLOScheduler(eng2, max_queue=8, seg_steps=8,
                           slo_monitor=mon)
        rep = sch.serve(arr)
        assert mon.state(0) == "page"
        # with a 1-segment fast window the first violating segment can
        # escalate straight to page — the log just has to END there
        assert mon.alert_log and mon.alert_log[-1]["level"] == "page"
        assert any(e["level"] == "page"
                   for e in flight.events("slo_alert"))
        assert rep.slo["classes"]["0"]["budget_remaining"] < 0
        # alerting is observation only: same tokens as the quiet serve
        assert rep.total_tokens == rep_ok.total_tokens

    def test_exporter_serves_live_monitors(self, monitored_serve):
        _, mon, pm, _, _, _ = monitored_serve
        with OpsServer(port=0, slo_monitor=mon, perf_monitor=pm) as srv:
            _, text = _get(srv.url + "/slo")
            slo = json.loads(text)
            assert slo["classes"]["0"]["outcomes"] == 10
            _, text = _get(srv.url + "/perf")
            perf = json.loads(text)
            assert perf["enabled"]
            assert perf["ledger"]["program"] == "paged_serving_segment"
            assert perf["last_interval"]["roofline_fraction"] > 0
            _, text = _get(srv.url + "/metrics")
            assert "slo_budget_remaining" in text
            assert "serving_cold_start_s" in text


class TestMonitorAudit:
    def test_monitored_serve_loop_syncs(self, tiny_llama):
        """THE zero-extra-sync gate for the whole ops surface: the SLO
        monitor, perf monitor AND a live exporter scraping mid-serve
        add no device contact — the monitored serve loop still costs
        exactly one allowed fetch per segment, zero flagged, and its
        sync metrics are bit-identical with the monitors on vs off."""
        from paddle_tpu.analysis import auditor
        from paddle_tpu.inference.scheduler import Arrival, SLOScheduler
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.parallel import set_mesh

        set_mesh(None)
        cfg, params = tiny_llama
        rng = np.random.RandomState(11)
        reqs = [(rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32), 4)
                for _ in range(3)]
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8, 16, 32))
        mon = SLOMonitor({0: Objective(ttft_target_s=30.0)})
        pm = PerfMonitor(cfg, params, batch=2)
        sch = SLOScheduler(eng, max_queue=8, seg_steps=8,
                           slo_monitor=mon, perf_monitor=pm)

        def replay():
            rep = sch.serve([Arrival(0.0, p, n) for p, n in reqs])
            eng.reset_slots()
            sch._reqs.clear()
            return rep

        def audit(enabled, scrape_url=None):
            mon.reset()
            prev = metrics.set_enabled(enabled)
            try:
                if scrape_url:
                    urllib.request.urlopen(scrape_url, timeout=5).read()
                return auditor.audit_replay("monitored_serve", replay,
                                            replays=2)
            finally:
                metrics.set_enabled(prev)

        with OpsServer(port=0, slo_monitor=mon, perf_monitor=pm) as srv:
            rep_on = audit(True, scrape_url=srv.url + "/slo")
        rep_off = audit(False)
        for key in ("host_syncs_flagged", "host_syncs_allowed",
                    "warm_compiles"):
            assert rep_on.metrics[key] == rep_off.metrics[key], (
                key, rep_on.metrics[key], rep_off.metrics[key])
        assert rep_on.metrics["host_syncs_flagged"] == 0
        assert set(rep_on.metrics["host_syncs_allowed"]) == {
            "serving.segment_event_fetch"}

    def test_gate_cli_ops_flag(self):
        """--ops on attaches monitors + exporter around the audit and
        the budget still gates green (spot-check on the cheapest
        canonical program; the full-7 run is the standing --gate test
        in test_analysis, which now defaults to --ops on)."""
        from paddle_tpu.analysis.__main__ import main
        from paddle_tpu.inference import serving

        hooks_before = len(serving.SEGMENT_HOOKS)
        assert main(["--program", "fused_optimizer_update", "--gate",
                     "--ops", "on"]) == 0
        assert main(["--program", "fused_optimizer_update", "--gate",
                     "--ops", "off"]) == 0
        assert len(serving.SEGMENT_HOOKS) == hooks_before  # detached


# ---------------------------------------------------------------------------
# fleet: cold start for N=2 + monitor wiring through the router
# ---------------------------------------------------------------------------


class TestFleetMonitoring:
    def test_fleet_cold_start_and_slo(self, tiny_llama):
        from paddle_tpu.inference.fleet import FleetRouter, build_fleet
        from paddle_tpu.inference.scheduler import Arrival
        from paddle_tpu.parallel import set_mesh

        set_mesh(None)
        cfg, params = tiny_llama
        rng = np.random.RandomState(23)
        arr = [Arrival(0.0, rng.randint(0, cfg.vocab_size, (8,))
                       .astype(np.int32), 4) for _ in range(4)]
        engines = build_fleet(cfg, params, 2, slots=2, max_len=96,
                              prompt_buckets=(8, 16, 32))
        mon = SLOMonitor({0: Objective(ttft_target_s=30.0,
                                       e2e_target_s=60.0)})
        pm = PerfMonitor(cfg, params, batch=2)
        router = FleetRouter(engines, max_queue=8, seg_steps=8,
                             slo_monitor=mon, perf_monitor=pm)
        rep = router.serve(arr)
        # cold start recorded for BOTH replicas; the fleet headline is
        # the worst one (the autoscaling-relevant bound)
        per_rep = [p["cold_start_s"] for p in rep.per_replica]
        assert all(c is not None and c > 0 for c in per_rep)
        assert rep.cold_start_s == pytest.approx(max(per_rep))
        assert rep.slo is not None and rep.slo["worst_level"] == "ok"
        assert rep.slo["classes"]["0"]["outcomes"] == 2 * len(arr)
        assert rep.slo["segments"] == rep.segments
        assert rep.perf is not None
        assert rep.perf["steps"] == rep.ticks
        assert rep.perf["tokens"] == rep.total_tokens
