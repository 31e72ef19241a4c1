"""Deterministic serving journal + bit-exact replay (r16 tentpole,
ISSUE 11): JSONL round-trip/rotation/rank-merge (truncated rank files
skipped-and-flagged, the r14 ``merge_log_dir`` semantics), replay
identity on a seeded preempt+shed overload serve and on a 2-replica
fleet failover at overload, first-divergence reporting on mutated
journals (wrong token, wrong dispatch), cross-replica request-journey
causal ordering, the one-sync-per-segment audit over a journaled serve
loop, and the gate's ``--journal on|off`` budget bit-identity.

Everything rides the session ``tiny_llama`` fixture and the shared
program cache; the two recorded serves are MODULE-SCOPED fixtures so
identity, divergence, journey and endpoint tests all read one
recording instead of re-serving.
"""

import copy
import json
import os
import urllib.request

import numpy as np
import pytest

from paddle_tpu.inference.fleet import (FaultInjector, FleetRouter,
                                        build_fleet)
from paddle_tpu.inference.prefix_cache import PagedPrefixCache
from paddle_tpu.inference.scheduler import Arrival, SLOScheduler
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability import journal, metrics, replay
from paddle_tpu.parallel import set_mesh


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    set_mesh(None)
    return tiny_llama


def _mk_engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 16)
    kw.setdefault("chunked_prefill", True)
    kw.setdefault("prefill_chunks", (8,))
    return ServingEngine(cfg, params, **kw)


def _slo_arr(cfg, rng):
    """Burst trace that provokes one preemption AND one shed in the
    first segments (the r13 audit trace shape)."""
    return ([Arrival(0.0, rng.randint(0, cfg.vocab_size, (8,))
                     .astype(np.int32), 24, priority=1)
             for _ in range(3)]
            + [Arrival(0.001, rng.randint(0, cfg.vocab_size, (8,))
                       .astype(np.int32), 4, priority=0),
               Arrival(0.001, rng.randint(0, cfg.vocab_size, (8,))
                       .astype(np.int32), 4, priority=1,
                       deadline_s=-0.001)])


@pytest.fixture(scope="module")
def slo_recorded(tiny, tmp_path_factory):
    """ONE journaled SLO serve (preempt + shed on a seeded burst),
    recorded to disk after a warm pass — shared by the replay-identity,
    journey, endpoint and divergence tests."""
    cfg, params = tiny
    rng = np.random.RandomState(59)
    arr = _slo_arr(cfg, rng)
    eng = _mk_engine(cfg, params)
    pc = PagedPrefixCache(eng.pager, capacity_pages=32)
    sch = SLOScheduler(eng, max_queue=8, seg_steps=16, prefix_cache=pc)
    sch.serve(arr)                       # warm: compiles + EWMA priming
    eng.reset_slots()
    pc.clear()
    sch._reqs.clear()
    sch.preemptions = 0
    sch.shed_count = 0
    sch.shed_per_class = {}
    jdir = str(tmp_path_factory.mktemp("journal_slo"))
    j = journal.Journal(jdir)
    j.params_info = {"prng_seed": 0}
    with journal.attach(j):
        report = sch.serve(arr)
    j.close()
    assert report.preemptions >= 1 and report.shed >= 1
    return {"dir": jdir, "journal": j, "params": params,
            "report": report,
            "records": journal.read_journal(jdir)["records"]}


@pytest.fixture(scope="module")
def fleet_recorded(tiny, tmp_path_factory):
    """ONE journaled 2-replica fleet serve at overload — a burst trace
    (every arrival due at t=0: offered load >> capacity, the bounded
    queues backpressure) with replica 1 crashed mid-serve — the
    ISSUE 11 acceptance scenario, recorded once. Burst keeps the crash
    schedule robust to machine speed (the r12 determinism contract):
    replica 1 always reaches its scheduled segment."""
    cfg, params = tiny
    rng = np.random.RandomState(7)
    arr = [Arrival(0.0, rng.randint(0, cfg.vocab_size,
                                    (int(rng.choice((8, 16))),))
                   .astype(np.int32), int(rng.choice((4, 8))))
           for _ in range(12)]

    def mk_router(inj):
        engines = build_fleet(cfg, params, 2, slots=2, max_len=96,
                              prompt_buckets=(8, 16, 32), paged=True,
                              page_size=16)
        return FleetRouter(engines, max_queue=3, seg_steps=8,
                           probe_after_s=60.0, fault_injector=inj)

    router = mk_router(None)
    router.serve(arr)                    # warm, no faults
    router.reset()
    router.fault_injector = FaultInjector(crash={1: 1})
    jdir = str(tmp_path_factory.mktemp("journal_fleet"))
    j = journal.Journal(jdir)
    j.params_info = {"prng_seed": 0}
    with journal.attach(j):
        report = router.serve(arr)
    j.close()
    assert report.failovers == 1 and report.requeued >= 1
    assert report.n_requests == len(arr)
    return {"dir": jdir, "journal": j, "params": params,
            "report": report,
            "records": journal.read_journal(jdir)["records"]}


# ---------------------------------------------------------------------------
# core: round-trip, rotation, rank merge
# ---------------------------------------------------------------------------


class TestJournalCore:
    def test_round_trip_rotation_and_rank_merge(self, tmp_path):
        """Small max_bytes forces rotation; the reader reassembles every
        part per rank, seqs stay contiguous per rank, and the global
        gseq gives one total order across ranks."""
        j = journal.Journal(str(tmp_path), max_bytes=400)
        j.begin_serve({"driver": "online", "trace": []})
        for i in range(20):
            j.record("segment", steps=i)
            with j.rank_scope(1):
                j.record("segment", steps=i, replica=1)
        j.close()
        parts = [p for p in os.listdir(tmp_path) if ".jsonl." in p]
        assert parts, "rotation never fired at max_bytes=400"
        out = journal.read_journal(str(tmp_path))
        assert out["ranks"] == [0, 1]
        recs = out["records"]
        assert len(recs) == 41          # header + 2x20
        for rank in (0, 1):
            seqs = [r["seq"] for r in recs if r["rank"] == rank]
            assert seqs == sorted(seqs)
            assert seqs[0] == 1 and seqs[-1] == len(seqs)  # lossless
        gseqs = [r["gseq"] for r in recs]
        assert gseqs == list(range(1, 42))
        secs = journal.sections(recs)
        assert len(secs) == 1 and secs[0]["header"]["driver"] == "online"

    def test_truncated_rank_file_skipped_and_flagged(self, tmp_path):
        """r14 merge semantics: a rank file truncated mid-write (the
        replica was killed) is skipped AND flagged — counter + flight
        event + skipped_files — never silently misparsed; only when NO
        file is readable does the merge raise."""
        j = journal.Journal(str(tmp_path))
        j.record("segment", steps=1)
        with j.rank_scope(1):
            j.record("segment", steps=2)
        j.close()
        r1 = os.path.join(tmp_path, "journal_rank1.jsonl")
        with open(r1, "a") as f:
            f.write('{"v": 1, "gseq": 99, "rank": 1, "seq"')  # torn write
        before = metrics.counter("journal.merge_skipped_files").value
        out = journal.read_journal(str(tmp_path))
        assert out["skipped_files"] == ["journal_rank1.jsonl"]
        assert [r["rank"] for r in out["records"]] == [0]
        assert metrics.counter("journal.merge_skipped_files").value \
            == before + 1
        # every file corrupt -> loud failure, not an empty postmortem
        with open(os.path.join(tmp_path, "journal_rank0.jsonl"), "w") as f:
            f.write("not json\n")
        os.remove(r1)
        with pytest.raises(FileNotFoundError):
            journal.read_journal(str(tmp_path))

    def test_newer_schema_refused(self, tmp_path):
        p = os.path.join(tmp_path, "journal_rank0.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"v": journal.SCHEMA_VERSION + 1,
                                "gseq": 1, "rank": 0, "seq": 1,
                                "t": 0.0, "kind": "segment"}) + "\n")
        with pytest.raises(journal.JournalError):
            journal.read_journal(str(tmp_path))

    def test_refuses_device_values(self):
        import jax.numpy as jnp

        j = journal.Journal()          # in-memory
        with pytest.raises(TypeError):
            j.record("bad", x=jnp.zeros((2,)))


# ---------------------------------------------------------------------------
# replay identity + divergence (tentpole c)
# ---------------------------------------------------------------------------


class TestReplay:
    def test_slo_overload_replay_identical(self, slo_recorded):
        """The preempt+shed serve replays to an IDENTICAL decision +
        token stream — every shed's deadline arithmetic, every preempt's
        victim pick, every finish's full token list."""
        res = replay.replay_serve(slo_recorded["dir"],
                                  params=slo_recorded["params"])
        assert res.identical, (res.error, res.divergence)
        kinds = {r["kind"] for r in slo_recorded["records"]}
        assert {"shed_decision", "preempt_decision", "finish",
                "clock"} <= kinds
        # the replayed report's control-plane counts match the recording
        assert res.report.preemptions == slo_recorded["report"].preemptions
        assert res.report.shed == slo_recorded["report"].shed

    def test_fleet_failover_replay_identical(self, fleet_recorded):
        """The acceptance bar: an overload serve with a mid-serve
        replica crash, journaled across a 2-replica fleet, replays
        offline to an identical token and decision stream (divergence
        report empty) — including the injected fault, the failover
        requeue and the cross-replica re-admission."""
        res = replay.replay_serve(fleet_recorded["dir"],
                                  params=fleet_recorded["params"])
        assert res.identical, (res.error, res.divergence)
        assert res.n_decisions == res.n_replayed > 0
        kinds = [r["kind"] for r in fleet_recorded["records"]]
        assert "fault" in kinds and "failover_requeue" in kinds
        assert res.report.failovers == 1

    def test_replay_rebuilds_params_from_header_seed(self, fleet_recorded):
        """The CLI path: params omitted -> rebuilt from the header's
        prng_seed, still identical."""
        res = replay.replay_serve(fleet_recorded["dir"])
        assert res.identical, (res.error, res.divergence)

    def test_mutated_token_first_divergence(self, fleet_recorded):
        recs = copy.deepcopy(fleet_recorded["records"])
        fin = next(r for r in recs if r["kind"] == "finish")
        fin["tokens"][0] = (fin["tokens"][0] + 1) % 100
        res = replay.replay_serve({"records": recs},
                                  params=fleet_recorded["params"])
        assert not res.identical
        d = res.divergence
        assert d["kind"] == "finish" and d["field"] in ("tokens",)
        assert d["seq"] == fin["seq"] and d["rank"] == fin["rank"]
        assert d["recorded"] != d["replayed"]

    def test_mutated_dispatch_first_divergence(self, fleet_recorded):
        recs = copy.deepcopy(fleet_recorded["records"])
        dsp = next(r for r in recs
                   if r["kind"] == "dispatch" and r["rid"] is not None)
        dsp["replica"] = 1 - dsp["replica"]
        res = replay.replay_serve({"records": recs},
                                  params=fleet_recorded["params"])
        assert not res.identical
        assert res.divergence["kind"] == "dispatch"
        assert res.divergence["field"] == "replica"

    def test_contiguous_engine_header_refused_by_name(self, fleet_recorded):
        """A journal is still WRITTEN with ``"paged": true`` per engine;
        one that recorded the contiguous engine names what is gone
        instead of building some other engine for it."""
        recs = copy.deepcopy(fleet_recorded["records"])
        hdr = next(r for r in recs if r["kind"] == "header")["header"]
        assert all(e["paged"] is True and e["page_size"] == 16
                   and e["num_pages"] for e in hdr["engines"])
        hdr["engines"][0]["paged"] = False
        with pytest.raises(journal.JournalError, match='"paged": false'):
            replay.replay_serve({"records": recs},
                                params=fleet_recorded["params"])


# ---------------------------------------------------------------------------
# request journeys (tentpole b)
# ---------------------------------------------------------------------------


class TestJourney:
    def test_preempt_resume_causal_order(self, slo_recorded):
        """A preempted request's journey reads causally: arrival ->
        admit -> preempt -> re-admit (resumed, with its parked tokens)
        -> finish."""
        recs = slo_recorded["records"]
        rid = next(r["rid"] for r in recs
                   if r["kind"] == "preempt_decision")
        jny = journal.request_journey(recs, rid)
        k = jny["kinds"]
        assert k.index("arrival") < k.index("admit") \
            < k.index("preempt_decision") < len(k)
        admits = [e for e in jny["events"] if e["kind"] == "admit"]
        assert len(admits) == 2
        assert admits[0]["resumed"] is False
        assert admits[1]["resumed"] is True
        assert admits[1]["tokens_done"] > 0      # generated work survived
        assert jny["preemptions"] == 1 and jny["finished"]
        # causal order == journal order (single-threaded decision loop)
        gseqs = [e["gseq"] for e in jny["events"]]
        assert gseqs == sorted(gseqs)

    def test_shed_journey_ends_without_finish(self, slo_recorded):
        recs = slo_recorded["records"]
        rid = next(r["rid"] for r in recs if r["kind"] == "shed_decision")
        jny = journal.request_journey(recs, rid)
        assert jny["shed"] and not jny["finished"]
        shed = next(e for e in jny["events"]
                    if e["kind"] == "shed_decision")
        # the arithmetic inputs ride the record: late_by is re-derivable
        assert shed["late_by_s"] == pytest.approx(
            shed["now_abs"] + shed["min_service_s"] - shed["deadline_abs"])

    def test_failover_cross_replica_journey(self, fleet_recorded):
        """A failover-requeued request's journey joins records ACROSS
        replicas: dispatch to the doomed replica, failover_requeue to a
        survivor, re-admit THERE (the admit record's replica changes),
        finish — with the fleet rid as the join key throughout."""
        recs = fleet_recorded["records"]
        rq = next(r for r in recs if r["kind"] == "failover_requeue")
        jny = journal.request_journey(recs, rq["rid"])
        k = jny["kinds"]
        assert k.index("dispatch") < k.index("failover_requeue") < \
            k.index("finish")
        admits = [e for e in jny["events"] if e["kind"] == "admit"]
        assert admits[-1]["replica"] == rq["dst"] != rq["src"]
        assert jny["requeues"] == 1 and jny["finished"]

    def test_journey_chrome_trace_spans(self, slo_recorded):
        """emit_journey_trace turns a journey into host spans on the
        profiler channel (one per causal hop)."""
        from paddle_tpu.observability import tracing
        from paddle_tpu.profiler import _hooks

        recs = slo_recorded["records"]
        rid = next(r["rid"] for r in recs if r["kind"] == "finish")
        jny = journal.request_journey(recs, rid)

        class _Sink:
            def __init__(self):
                self.events = []

            def _host_event(self, name, t0, t1, kind):
                self.events.append((name, t0, t1, kind))

        sink = _Sink()
        _hooks.COLLECTORS.append(sink)
        try:
            tracing.emit_journey_trace(jny)
        finally:
            _hooks.COLLECTORS.remove(sink)
        assert sink.events, "journey emitted no spans"
        assert all(k == "serving.journey" for *_, k in sink.events)
        assert any(f"req{rid}" in n for n, *_ in sink.events)


# ---------------------------------------------------------------------------
# audit: journaling adds zero syncs; gate budgets identical on/off
# ---------------------------------------------------------------------------


class TestJournalAudit:
    def test_journaled_serve_loop_syncs(self, tiny, tmp_path):
        """SyncAudit over a JOURNALED SLO serve: flagged == [], allowed
        == the per-segment event fetch exactly — the journal consumes
        only host mirrors of the one audited fetch."""
        from paddle_tpu.analysis import syncs

        cfg, params = tiny
        rng = np.random.RandomState(59)
        arr = _slo_arr(cfg, rng)
        eng = _mk_engine(cfg, params)
        pc = PagedPrefixCache(eng.pager, capacity_pages=32)
        sch = SLOScheduler(eng, max_queue=8, seg_steps=16,
                           prefix_cache=pc)
        sch.serve(arr)                  # warm (shapes shared in-process)
        eng.reset_slots()
        pc.clear()
        sch._reqs.clear()
        sch.shed_count = 0
        sch.shed_per_class = {}
        j = journal.Journal(str(tmp_path))
        with journal.attach(j):
            with syncs.SyncAudit() as sa:
                sa.phase = "replay"
                report = sch.serve(arr)
        j.close()
        flagged = sa.flagged("replay")
        assert flagged == [], [f"{e.kind}@{e.site}" for e in flagged]
        allowed = sa.allowed("replay")
        assert set(allowed) == {"serving.segment_event_fetch"}
        assert allowed["serving.segment_event_fetch"] == report.segments
        assert j.total_records > 0
        pc.clear()
        assert eng.pager.leak_report() == []

    def test_gate_budgets_identical_journal_on_off(self):
        """TestTelemetryAudit-style: auditing the canonical serving
        program with the journal attached yields bit-identical
        sync/compile metrics to journal-off."""
        from paddle_tpu.analysis import auditor, programs

        handle = programs.build("paged_serving_segment")

        def audit(journaled):
            if not journaled:
                return auditor.audit_replay("paged_serving_segment",
                                            handle.replay, replays=2)
            j = journal.Journal()       # in-memory
            with journal.attach(j):
                return auditor.audit_replay("paged_serving_segment",
                                            handle.replay, replays=2)

        rep_on = audit(True)
        rep_off = audit(False)
        for key in ("host_syncs_flagged", "host_syncs_allowed",
                    "warm_compiles"):
            assert rep_on.metrics[key] == rep_off.metrics[key], (
                key, rep_on.metrics[key], rep_off.metrics[key])

    def test_gate_cli_journal_flag(self):
        from paddle_tpu.analysis.__main__ import main

        assert main(["--program", "fused_optimizer_update", "--gate",
                     "--journal", "off", "--ops", "off"]) == 0
        assert journal.active() is None   # flag detached its journal


# ---------------------------------------------------------------------------
# ops surface: /journal, /request/<rid>, /flight filters, dropped counter
# ---------------------------------------------------------------------------


class TestJournalOps:
    def test_journal_and_request_endpoints(self, slo_recorded):
        from paddle_tpu.observability import OpsServer

        j = slo_recorded["journal"]
        rid = next(r["rid"] for r in slo_recorded["records"]
                   if r["kind"] == "finish")
        with OpsServer(port=0, journal=j) as srv:
            with urllib.request.urlopen(
                    f"{srv.url}/journal?n=8&kind=clock") as r:
                body = json.loads(r.read())
            assert body["total_records"] == j.total_records
            assert body["records"]
            assert all(e["kind"] == "clock" for e in body["records"])
            with urllib.request.urlopen(
                    f"{srv.url}/request/{rid}") as r:
                jny = json.loads(r.read())
            assert jny["rid"] == rid and jny["finished"]
            assert jny["kinds"][0] == "arrival"

    def test_flight_filters_and_dropped_counter(self):
        from paddle_tpu.observability import OpsServer, flight

        rec = flight.FlightRecorder(capacity=4)
        before = metrics.counter("flight.dropped_events").value
        for i in range(6):
            rec.record("widget", rid=i % 2, n=i)
        assert rec.dropped_events == 2          # 6 events, ring of 4
        assert metrics.counter("flight.dropped_events").value \
            == before + 2
        assert [e["n"] for e in rec.events(rid=1)] == [3, 5]
        assert rec.events(kind="nope") == []
        with OpsServer(port=0, recorder=rec) as srv:
            with urllib.request.urlopen(
                    f"{srv.url}/flight?kind=widget&rid=0&n=8") as r:
                body = json.loads(r.read())
        assert body["dropped_events"] == 2
        assert [e["n"] for e in body["events"]] == [2, 4]
