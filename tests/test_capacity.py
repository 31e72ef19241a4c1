"""Capacity & memory observability (r18 tentpole, ISSUE 13): page-level
HBM metering through POOL_HOOKS, per-request resource attribution
(page-seconds / fair-share weight streams / ledger-joined bytes),
predictive exhaustion alerting that LEADS the pages-backpressure valve,
the §3f×§3g capacity planner (±10% vs a measured serve), the /capacity
operator endpoint with the ?audit=1 leak view, per-replica pages on
/healthz + dispatch journal records, the monitored-serve sync audit
(flagged==[], allowed == segment fetches exactly), and the --capacity
on|off gate bit-identity.

Everything rides the session ``tiny_llama`` fixture and module-scoped
recorded serves; engine geometries are shared across tests to maximise
``serving._SHARED_PROGS`` hits (suite-time contract).
"""

import math
import types

import numpy as np
import pytest

from paddle_tpu.inference.paged_kv import PageAllocator
from paddle_tpu.inference.prefix_cache import make_prefix_cache
from paddle_tpu.inference.scheduler import Arrival, OnlineScheduler
from paddle_tpu.inference.serving import Request, ServingEngine
from paddle_tpu.observability import (CapacityMonitor, PoolMonitor,
                                      aggregate_meters, attribute_request,
                                      capacity_plan, flight,
                                      serving_ledger)
from paddle_tpu.observability import capacity as capmod
from paddle_tpu.parallel import set_mesh


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    set_mesh(None)
    return tiny_llama


def _mk(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 16)
    return ServingEngine(cfg, params, **kw)


def _trace(cfg, n=6, seed=11, gen=6, plen=8):
    rng = np.random.RandomState(seed)
    return [Arrival(0.0, rng.randint(0, cfg.vocab_size, (plen,))
                    .astype(np.int32), gen) for _ in range(n)]


def _fake_pager(num_pages=11, page_size=4, slots=1):
    """The minimal pager surface PoolMonitor reads — a bare allocator
    plus host mirrors (no device pool: the monitor must never need
    one)."""
    return types.SimpleNamespace(
        allocator=PageAllocator(num_pages), page_size=page_size,
        num_pages=num_pages, slot_pages=[[] for _ in range(slots)])


# ---------------------------------------------------------------------------
# module-scoped recorded serves
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def monitored(tiny):
    """ONE monitored plain-paged serve (no prefix cache, no sharing):
    the meter-identity, aggregation, endpoint and planner tests all
    read it."""
    cfg, params = tiny
    eng = _mk(cfg, params)
    ledger = serving_ledger(cfg, params, batch=eng.slots, avg_pos=12.0,
                            program="paged_serving_segment")
    cap = CapacityMonitor(ledger=ledger)
    pool = PoolMonitor(eng.pager).attach()
    arr = _trace(cfg)
    sch = OnlineScheduler(eng, seg_steps=16, capacity_monitor=cap)
    report = sch.serve(arr)
    results = sch.results()
    pool.detach()
    return {"report": report, "pool": pool, "cap": cap, "eng": eng,
            "sch": sch, "ledger": ledger, "results": results,
            "reqs": list(sch._reqs.values())}


@pytest.fixture(scope="module")
def overloaded(tiny):
    """ONE overloaded serve on a TIGHT pool (the r13 overload shape at
    a deterministic clock): demand builds for a full segment before the
    pool exhausts, so the capacity page must fire BEFORE the first
    pages-backpressure deferral — the alert-leads-valve bar."""
    cfg, params = tiny
    # span = ceil((8 + 24 - 1)/8) = 4 pages/request; 4 slots x 4 = 16
    # pages live at full concurrency; 20 usable pages => segment 1
    # admits 4 requests clean (free 4), segment 2's second reservation
    # (4 > 4 - 4) defers
    eng = _mk(cfg, params, slots=4, page_size=8, num_pages=21)
    cap = CapacityMonitor()
    pool = PoolMonitor(eng.pager, high_water_frac=0.75).attach()
    flight.clear()
    arr = _trace(cfg, n=12, seed=7, gen=24)
    sch = OnlineScheduler(eng, max_queue=64, seg_steps=16,
                          capacity_monitor=cap)
    report = sch.serve(arr)
    sch.results()
    pool.detach()
    return {"report": report, "cap": cap, "pool": pool, "eng": eng,
            "events": flight.events()}


@pytest.fixture(scope="module")
def saturated(tiny):
    """ONE saturated serve (n == slots, all at t=0) — concurrency
    equals slots exactly, the deterministic geometry the planner's
    ±10% validation reads."""
    cfg, params = tiny
    eng = _mk(cfg, params, slots=4, page_size=8)
    pool = PoolMonitor(eng.pager).attach()
    arr = _trace(cfg, n=4, seed=3, gen=16)
    sch = OnlineScheduler(eng, seg_steps=16)
    report = sch.serve(arr)
    sch.results()
    pool.detach()
    return {"report": report, "pool": pool, "eng": eng}


# ---------------------------------------------------------------------------
# the meter: accounting identities
# ---------------------------------------------------------------------------


class TestMeter:
    def test_page_seconds_match_allocator_log(self, monitored):
        """With no prefix cache and no forks, every held page belongs
        to exactly one request — Σ request.page_seconds equals the
        PoolMonitor's ∫ pages_used dt integral (the two sides stamp at
        the same host moments, within the finish-call slack)."""
        reqs = monitored["reqs"]
        total = sum(r.page_seconds for r in reqs)
        integral = monitored["pool"].page_seconds_integral
        assert total > 0.0
        assert total == pytest.approx(integral, rel=0.05, abs=0.05)
        for r in reqs:
            assert r.pages_reserved == monitored["eng"].pager.pages_needed(
                len(r.prompt) + r.max_new_tokens - 1)
            assert r.page_seconds > 0.0

    def test_stream_shares_tile_the_segment_steps(self, monitored):
        """The fair-share identity: each segment step distributes
        exactly one weight stream across its live slots, so Σ streams
        over the serve == total ticks, and Σ ticks ≥ ticks (slots
        overlap)."""
        rep = monitored["report"]
        reqs = monitored["reqs"]
        assert sum(r.meter_streams for r in reqs) == pytest.approx(
            rep.ticks, abs=1e-6)
        assert sum(r.meter_ticks for r in reqs) >= rep.ticks
        # greedy non-spec: one token per live tick exactly
        for r in reqs:
            assert r.meter_ticks == len(r.tokens)

    def test_ledger_join_and_class_aggregation(self, monitored):
        """attribute_request's byte arithmetic is the ledger's, and the
        per-class aggregate sums to the per-request bills exactly."""
        led = monitored["ledger"]
        reqs = monitored["reqs"]
        kv_slot = led["kv_bytes_per_tick"] / led["batch"]
        for r in reqs:
            a = attribute_request(r, ledger=led, page_size=16)
            assert a["hbm_bytes"] == int(
                r.meter_streams * led["weight_bytes_per_tick"]
                + r.meter_ticks * kv_slot)
            assert a["prefill_flops"] == int(
                led["flops_per_token"] * len(r.prompt))
        agg = monitored["report"].meter
        assert agg["ledger_joined"]
        assert agg["total"]["n"] == len(reqs)
        assert agg["total"]["ticks"] == sum(r.meter_ticks for r in reqs)
        assert agg["total"]["hbm_bytes"] == sum(
            attribute_request(r, ledger=led)["hbm_bytes"] for r in reqs)
        assert set(agg["per_class"]) == {"0"}
        rows = monitored["report"].per_request
        assert all("page_seconds" in row and "streams" in row
                   for row in rows)

    def test_meter_survives_preempt_and_resume(self, tiny):
        """A preempted request closes its page-holding interval (the
        bill keeps accruing across resume cycles instead of leaking the
        first holding)."""
        cfg, params = tiny
        eng = _mk(cfg, params)
        rng = np.random.RandomState(5)
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (8,))
                            .astype(np.int32), 12)
        eng.run_segment(8)               # both admitted, neither done
        slot = next(s for s, r in enumerate(eng._active) if r is not None)
        victim = eng.preempt_slot(slot)
        ps0 = victim.page_seconds
        assert ps0 > 0.0 and victim._pages_live == 0
        eng._queue[:0] = [victim]
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(32)
        assert victim.done
        assert victim.page_seconds > ps0


# ---------------------------------------------------------------------------
# the pool monitor: breakdown, COW ratio, high-water, timeline
# ---------------------------------------------------------------------------


class TestPoolMonitor:
    def test_breakdown_tiles_the_pool(self, monitored):
        """free + live + reclaimable + reserved-unbound covers every
        usable page; after the serve everything is back on the free
        list."""
        snap = monitored["pool"].snapshot()
        assert snap["pages_used"] == 0
        assert snap["pages_free"] == snap["num_pages"]
        assert snap["high_water_pages"] > 0
        assert snap["events"] > 0
        assert snap["trash_pages"] == 1

    def test_cow_ratio_matches_prefix_dedup(self, tiny):
        """The COW ratio (Σ refcounts ÷ physical pages) equals the
        §3f prefix-dedup virtual/physical count recomputed
        independently from the slot tables + cache entries — and
        exceeds 1 exactly when a cache-held prefix page is shared with
        a live slot."""
        cfg, params = tiny
        eng = _mk(cfg, params)
        cache = make_prefix_cache(eng)
        pool = PoolMonitor(eng.pager, prefix_cache=cache).attach()
        rng = np.random.RandomState(9)
        prefix = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
        tail = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
        p1 = np.concatenate([prefix, tail])
        eng.add_request(p1, 4)
        while eng.free_slot_count() < eng.slots or eng._queue:
            eng.run_segment(32, prefix_cache=cache)   # populates cache
        p2 = np.concatenate([prefix,
                             rng.randint(0, cfg.vocab_size, (8,))
                             .astype(np.int32)])
        eng.add_request(p2, 12)
        eng.run_segment(6, prefix_cache=cache)        # admit, stay live
        snap = pool.snapshot()
        virtual = (sum(len(e.pages) for e in cache._entries.values())
                   + sum(len(p) for p in eng.pager.slot_pages))
        assert snap["cow_virtual_pages"] == virtual
        assert snap["cow_ratio"] == pytest.approx(
            virtual / eng.pager.allocator.pages_used, abs=1e-4)
        assert snap["cow_ratio"] > 1.0          # the shared prefix page
        assert snap["reclaimable_pages"] < snap["cache_held_pages"]
        # drain; with only the cache holding pages, all of it reclaims
        while eng.free_slot_count() < eng.slots or eng._queue:
            eng.run_segment(32, prefix_cache=cache)
        snap = pool.snapshot()
        assert snap["reclaimable_pages"] == snap["cache_held_pages"] > 0
        assert cache.reclaimable_pages() == snap["reclaimable_pages"]
        assert (snap["pages_free"] + snap["live_pages"]
                + snap["reclaimable_pages"]
                + snap["reserved_unbound_pages"]) == snap["num_pages"]
        pool.detach()

    def test_high_water_event_fires_once_and_rearms(self):
        pg = _fake_pager(num_pages=11, page_size=4)
        pool = PoolMonitor(pg, high_water_frac=0.5,
                           rearm_margin=0.1).attach()
        flight.clear()
        a = pg.allocator
        held = a.alloc(6)                       # 0.6 >= 0.5: fires
        a.alloc(2)                              # still over: no repeat
        assert len(flight.events("pool_high_water")) == 1
        assert pool.high_water_events == 1
        a.release(held)                         # 0.2 < 0.4: re-arms
        a.alloc(5)                              # crosses again
        assert len(flight.events("pool_high_water")) == 2
        assert pool.high_water_pages == 8
        pool.detach()

    def test_timeline_is_bounded_and_decimated(self):
        pg = _fake_pager(num_pages=101, page_size=4)
        pool = PoolMonitor(pg, timeline_cap=32).attach()
        a = pg.allocator
        for _ in range(300):
            a.release(a.alloc(3))
        assert len(pool.timeline) <= 32
        assert pool._stride > 1
        assert pool.timeline[-1][0] <= pool.events
        pool.detach()
        n = pool.events
        a.alloc(1)
        assert pool.events == n          # detached: no longer observing


# ---------------------------------------------------------------------------
# exhaustion alerting
# ---------------------------------------------------------------------------


class TestExhaustionAlert:
    def test_alert_state_machine(self):
        cap = CapacityMonitor(fast_window=2, slow_window=4,
                              warn_horizon=8.0, page_horizon=2.0,
                              clear_after=2)
        assert cap.begin_segment(100) == "ok"         # no demand history
        cap.note_segment(1, 10)                       # bucket [10]
        assert cap.begin_segment(100) == "ok"         # tte 10 > 8
        cap.note_segment(1, 10)                       # [10, 10]
        assert cap.begin_segment(40) == "warning"     # tte 4
        cap.note_segment(1, 10)
        assert cap.begin_segment(15) == "page"        # tte 1.5
        # hysteretic clear: demand dries up, avail recovers — the level
        # drops only after clear_after consecutive calm evaluations
        for _ in range(4):
            cap.close_segment()                       # zero-demand buckets
        assert cap.begin_segment(1000) == "page"      # streak 1
        assert cap.begin_segment(1000) == "ok"        # streak 2: clears
        levels = [a["level"] for a in cap.alert_log]
        assert levels == ["warning", "page", "ok"]
        rec = cap.report()
        assert rec["alerts"] and rec["horizons"]["unit"] == "segments"
        cap.reset()
        assert cap.level == "ok" and not cap.alert_log

    def test_monitor_validation(self):
        with pytest.raises(ValueError, match="fast_window"):
            CapacityMonitor(fast_window=0)
        with pytest.raises(ValueError, match="page_horizon"):
            CapacityMonitor(warn_horizon=2.0, page_horizon=4.0)

    def test_page_fires_before_first_pages_backpressure(self, overloaded):
        """THE acceptance bar (ISSUE 13): at overload on a tight pool
        the capacity page leads the first pages-backpressure deferral —
        flight seq of the page alert < flight seq of the first
        backpressure{reason=pages} event."""
        evs = overloaded["events"]
        pages = [e["seq"] for e in evs if e["kind"] == "capacity_alert"
                 and e["level"] == "page"]
        defers = [e["seq"] for e in evs if e["kind"] == "backpressure"
                  and e.get("reason") == "pages"]
        assert defers, "the tight pool never deferred — trace broken"
        assert pages, "no capacity page fired"
        assert pages[0] < defers[0], (pages[0], defers[0])
        assert overloaded["report"].backpressure_pages > 0
        assert overloaded["report"].capacity["alerts"]
        # the declared-fraction high-water event also fired on the way
        assert any(e["kind"] == "pool_high_water" for e in evs)

    def test_report_sections_ride_online_report(self, overloaded):
        rep = overloaded["report"]
        assert rep.capacity["level"] in ("ok", "warning", "page")
        assert rep.capacity["segments"] == rep.segments
        assert rep.meter["total"]["n"] == rep.n_requests
        assert rep.as_dict()["capacity"] is rep.capacity


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


class TestPlanner:
    def test_plan_within_10pct_of_measured(self, saturated):
        """§3f×§3g arithmetic vs the measured saturated serve: the
        predicted pool high-water and tok/s land within ±10% of what
        the serve measured (deterministic here by saturating all slots
        with identical requests)."""
        rep = saturated["report"]
        plan = capacity_plan(
            {"mean_prompt_tokens": 8, "mean_new_tokens": 16,
             "rate_req_s": None},
            page_size=8, slots=4,
            measured={"per_tick_s": rep.makespan_s / rep.ticks,
                      "slot_occupancy": rep.slot_occupancy})
        measured_hw = saturated["pool"].high_water_pages
        assert abs(plan["predicted_high_water_pages"] / measured_hw - 1.0) \
            <= 0.10, (plan, measured_hw)
        assert abs(plan["predicted_tok_s"] / rep.throughput_tok_s - 1.0) \
            <= 0.10, (plan, rep.throughput_tok_s)
        assert plan["pool_pages"] >= plan["predicted_high_water_pages"] + 1

    def test_replica_scaling_arithmetic(self):
        stats = {"mean_prompt_tokens": 64, "mean_new_tokens": 100,
                 "rate_req_s": 10.0, "mean_service_s": 0.2}
        meas = {"per_tick_s": 0.01, "slot_occupancy": 1.0}
        p1 = capacity_plan(stats, page_size=16, slots=4, measured=meas)
        assert p1["offered_tok_s"] == 1000.0
        assert p1["tok_s_replica"] == 400.0
        assert p1["replicas"] == 3               # ceil(1000/400)
        p2 = capacity_plan(dict(stats, rate_req_s=20.0), page_size=16,
                           slots=4, measured=meas)
        assert p2["replicas"] == 5
        p3 = capacity_plan(stats, page_size=16, slots=4, measured=meas,
                           headroom=0.2)
        assert p3["replicas"] == 4               # ceil(1000/320)
        assert p3["pool_pages"] > p1["pool_pages"] or \
            p3["predicted_high_water_pages"] == 0
        # span arithmetic is §3f's exact ceil
        assert p1["span_pages"] == math.ceil((64 + 100 - 1) / 16)
        # little's-law concurrency clamps at slots
        assert p1["concurrency"] == min(4.0, 10.0 * 0.2)


# ---------------------------------------------------------------------------
# the audited contract: syncs, gate bit-identity, operator surfaces
# ---------------------------------------------------------------------------


class TestAuditedContract:
    def test_monitored_serve_sync_audit(self, tiny):
        """Zero extra syncs with the whole capacity plane attached:
        flagged == [], allowed == the segment fetches exactly."""
        from paddle_tpu.analysis import SyncAudit

        cfg, params = tiny
        eng = _mk(cfg, params)
        arr = _trace(cfg, n=4, seed=21)
        sch = OnlineScheduler(eng, seg_steps=16,
                              capacity_monitor=CapacityMonitor())
        pool = PoolMonitor(eng.pager).attach()
        sch.serve(arr)                   # warm (compiles outside audit)
        sch.results()
        eng.reset_slots()
        sch._reqs.clear()
        sch.capacity_monitor.reset()
        with SyncAudit() as audit:
            audit.phase = "serve"
            report = sch.serve(arr)
        pool.detach()
        assert audit.flagged("serve") == [], audit.flagged("serve")
        assert audit.allowed("serve") == {
            "serving.segment_event_fetch": report.segments}

    def test_gate_bit_identity_capacity_on_off(self):
        """The 9 canonical programs budget bit-identically with the
        capacity plane ambient-attached (--capacity on|off contract) —
        pinned here on the paged program whose allocator traffic the
        hooks actually observe."""
        from paddle_tpu.analysis import auditor, budgets, programs

        handle = programs.build("paged_serving_segment")

        def audit(attach):
            mon = CapacityMonitor() if attach else None
            if mon is not None:
                capmod.install(mon)
            try:
                return auditor.audit_replay("paged_serving_segment",
                                            handle.replay, replays=2)
            finally:
                if mon is not None:
                    capmod.uninstall(mon)

        rep_on = audit(True)
        rep_off = audit(False)
        rep_on.merge(auditor.audit_static(
            "paged_serving_segment", handle.hlo(),
            donation_threshold=handle.donation_threshold,
            expected_undonated=handle.expected_undonated))
        assert budgets.check(rep_on) == [], rep_on.format()
        for key in ("host_syncs_flagged", "host_syncs_allowed",
                    "warm_compiles"):
            assert rep_on.metrics[key] == rep_off.metrics[key], (
                key, rep_on.metrics[key], rep_off.metrics[key])

    def test_capacity_endpoint_round_trip(self, monitored):
        import json as _json
        import urllib.request

        from paddle_tpu.observability import OpsServer

        with OpsServer(port=0, capacity_monitor=monitored["cap"],
                       pool_monitor=monitored["pool"]) as srv:
            with urllib.request.urlopen(srv.url + "/capacity",
                                        timeout=5) as r:
                body = _json.loads(r.read())
            with urllib.request.urlopen(srv.url + "/capacity?audit=1",
                                        timeout=5) as r:
                audited = _json.loads(r.read())
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=5) as r:
                health = _json.loads(r.read())
        assert body["enabled"] is True
        assert body["monitor"]["segments"] == monitored["report"].segments
        assert body["pool"]["num_pages"] > 0
        assert "audit" not in body
        # the engine is drained: the operational leak audit is clean
        assert audited["audit_clean"] is True and audited["audit"] == []
        assert health["capacity_level"] == monitored["cap"].level

    def test_healthz_pages_and_dispatch_journal(self, tiny,
                                                tmp_path_factory):
        """The fleet satellite: /healthz gains per-replica pages_free/
        reclaimable and every journaled dispatch decision's candidate
        ranking carries the same pair — the item-4 autoscaler's signal
        with no new plumbing."""
        import json as _json
        import urllib.request

        from paddle_tpu.inference.fleet import FleetRouter, build_fleet
        from paddle_tpu.observability import OpsServer, journal

        cfg, params = tiny
        engines = build_fleet(cfg, params, 2, slots=2, max_len=96,
                              prompt_buckets=(8, 16, 32), paged=True,
                              page_size=16)
        router = FleetRouter(engines, seg_steps=16,
                             prefix_caches="auto")
        jdir = str(tmp_path_factory.mktemp("journal_capacity"))
        j = journal.Journal(jdir)
        with journal.attach(j):
            router.serve(_trace(cfg, n=5, seed=17))
        j.close()
        recs = journal.read_journal(jdir)["records"]
        cands = [r["candidates"] for r in recs
                 if r["kind"] == "dispatch" and r.get("candidates")]
        assert cands
        for cand_list in cands:
            for c in cand_list:
                assert isinstance(c["pages_free"], int)
                assert isinstance(c["reclaimable"], int)
        with OpsServer(port=0, fleet=router) as srv:
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=5) as r:
                body = _json.loads(r.read())
        assert set(body["pages"]) == {"0", "1"}
        for rep in router._replicas:
            assert body["pages"][str(rep.idx)]["pages_free"] == \
                rep.engine.pager.pages_free
        # the r14 shape is untouched: replica health stays a string map
        assert body["replicas"] == {"0": "healthy", "1": "healthy"}


class TestInstall:
    def test_ambient_install_sees_segments_and_pool_events(self, tiny):
        cfg, params = tiny
        mon = CapacityMonitor()
        capmod.install(mon)
        capmod.install(mon)              # idempotent
        try:
            eng = _mk(cfg, params)
            eng.add_request(np.arange(8, dtype=np.int32) % cfg.vocab_size,
                            4)
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(16)
        finally:
            capmod.uninstall(mon)
        assert mon.segment_no >= 1
        assert mon.pool_events > 0
        assert mon.pages_admitted_total > 0
        from paddle_tpu.inference import paged_kv, serving
        assert not any(h for h in paged_kv.POOL_HOOKS)
        # other installed hooks (slo/perf from other tests) may remain;
        # ours must be gone
        assert mon.segment_no == mon.segment_no  # no further advances
