"""SLO-aware serving under overload and failure (r13 tentpole, ISSUE 8):
chunked-prefill token parity, priority preemption without inversion,
preempt->resume token identity, deadline load-shedding accounting,
fleet kill/recover determinism, the retry_after backpressure hint, and
the one-sync-per-segment audit over the chunked + failover loops.

Everything runs on the session-scoped ``tiny_llama`` fixture and the
process-wide shared program cache, so the suite-time delta stays small.
"""

import numpy as np
import pytest

from paddle_tpu.inference.fleet import (FaultInjector, FleetRouter,
                                        build_fleet)
from paddle_tpu.inference.prefix_cache import PagedPrefixCache
from paddle_tpu.inference.scheduler import (Arrival, SLOScheduler,
                                            staggered_arrivals)
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import llama
from paddle_tpu.parallel import set_mesh


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    set_mesh(None)
    return tiny_llama


def _dense_reference(cfg, params, prompt, n):
    out = llama.generate(params, np.asarray(prompt, np.int32)[None], cfg,
                         max_new_tokens=n, max_len=96)
    return [int(t) for t in np.asarray(out)[0]]


def _mk_engine(cfg, params, chunked=True, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 16)
    if chunked:
        kw.setdefault("chunked_prefill", True)
        kw.setdefault("prefill_chunks", (8,))
    return ServingEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# chunked prefill (tentpole a)
# ---------------------------------------------------------------------------


class TestChunkedPrefill:
    def test_token_parity_vs_unchunked(self, tiny):
        """Acceptance: splitting prefill into interleaved chunks must
        not change a single token — chunked == unchunked paged ==
        dense generate, with pages drained and chunk steps counted."""
        from paddle_tpu.observability import metrics

        cfg, params = tiny
        rng = np.random.RandomState(17)
        reqs = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), g)
                for l, g in [(12, 5), (30, 4), (7, 6), (25, 3), (14, 4)]]

        def serve(chunked):
            eng = _mk_engine(cfg, params, chunked=chunked)
            rids = [eng.add_request(p, g) for p, g in reqs]
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(16)
            out = eng.collect_finished()
            assert eng.pager.leak_report() == []
            return [out[r] for r in rids]

        before = metrics.counter("serving.prefill_chunks").value
        out_u = serve(False)
        out_c = serve(True)
        assert out_c == out_u
        p0, g0 = reqs[0]
        assert out_c[0] == _dense_reference(cfg, params, p0, g0)
        # the 30- and 25-token prompts really did split (ceil(32/8) = 4
        # chunk steps each at the pinned 32-wide admit window)
        assert metrics.counter("serving.prefill_chunks").value > before

    def test_decode_interleaves_with_long_prefill(self, tiny):
        """The point of chunking: while a long prompt prefills, the
        already-running slot keeps emitting tokens — the admit event
        lands mid-stream of the resident request's decode, not after a
        monolithic prefill stall. Verified from the event log: chunk
        steps and the co-resident decode ticks alternate."""
        cfg, params = tiny
        rng = np.random.RandomState(19)
        short = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
        long_p = rng.randint(0, cfg.vocab_size, (30,)).astype(np.int32)
        eng = _mk_engine(cfg, params)
        eng.add_request(short, 12)
        eng.run_segment(8)            # the short request is now resident
        eng.add_request(long_p, 4)    # 30 tokens -> 4 chunks of 8
        h = eng.dispatch_segment(16)
        import jax

        toks, aq, aslot, steps, qadm = jax.device_get(h.dev)
        eng.finish_segment(h)
        marker = h.chunk_marker
        n_pad = marker - 1            # the decode marker (== n_pad)
        chunk_steps = [i for i in range(int(steps)) if aq[i] >= marker]
        decode_steps = [i for i in range(int(steps)) if aq[i] == n_pad]
        assert len(chunk_steps) >= 3          # non-final chunks logged
        # at least one decode tick ran BETWEEN chunk steps (interleave,
        # not a monolithic prefill): some decode step falls inside the
        # chunk-step span
        assert any(chunk_steps[0] < d < chunk_steps[-1]
                   for d in decode_steps)
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(16)
        eng.collect_finished()
        assert eng.pager.leak_report() == []

    def test_seg_steps_too_small_fails_loudly(self, tiny):
        cfg, params = tiny
        eng = _mk_engine(cfg, params)
        eng.add_request(np.arange(30, dtype=np.int32) % cfg.vocab_size, 4)
        with pytest.raises(ValueError, match="chunked"):
            eng.run_segment(4)        # 4 < 2 * (32/8) worst case


# ---------------------------------------------------------------------------
# priority classes + preemption (tentpole b)
# ---------------------------------------------------------------------------


class TestPriorityPreemption:
    def test_preempt_resume_token_identity(self, tiny):
        """A high-priority arrival preempts a saturated engine's lowest
        class; the victim resumes later and every request — including
        the preempted one — matches its dense reference stream."""
        cfg, params = tiny
        rng = np.random.RandomState(23)
        # lows: 8-token prompts, 24 generations — prompt + full stream
        # (32) always fits the 64 bucket, so the victim is preemptible
        # whenever the high arrival lands; the high arrives one ms in,
        # i.e. during the first (multi-ms) segment, while both slots
        # are pinned by class-1 work (suite-time: r16 cut 48 -> 32
        # gens; r17 cuts 4 lows -> 3 and 32 -> 24 gens — two lows
        # still pin both slots with one queued, the preempt still
        # lands mid-stream at seg_steps=16, and the dense-reference
        # bill drops by another ~40%)
        arr = ([Arrival(0.0, rng.randint(0, cfg.vocab_size, (8,))
                        .astype(np.int32), 24, priority=1)
                for _ in range(3)]
               + [Arrival(0.001, rng.randint(0, cfg.vocab_size, (8,))
                          .astype(np.int32), 4, priority=0)])
        eng = _mk_engine(cfg, params, prompt_buckets=(8, 16, 64))
        pc = PagedPrefixCache(eng.pager, capacity_pages=32)
        sch = SLOScheduler(eng, max_queue=8, seg_steps=16,
                           prefix_cache=pc)
        rep = sch.serve(arr)
        out = sch.results()
        assert rep.n_requests == 4
        assert rep.preemptions >= 1
        preempted = [r for r in sch._reqs.values() if r.preemptions]
        assert preempted and preempted[0].prefix_hit_len > 0, \
            "resume should ride parked pages (ref bump, not re-prefill)"
        for rid, r in sch._reqs.items():
            assert out[rid] == _dense_reference(cfg, params, r.prompt,
                                                r.max_new_tokens)
        pc.clear()
        assert eng.pager.leak_report() == []

    def test_no_priority_inversion_under_overload(self, tiny):
        """Under a saturating burst with both classes arriving together,
        class 0 must keep its TTFT p99 below class 1's — the class-
        ordered queue exists exactly so high-priority latency does not
        ride the batch tail. (A burst, not a clocked trace: admission
        order is then fully queue-driven and the assertion cannot race
        the wall clock.)"""
        cfg, params = tiny
        rng = np.random.RandomState(29)
        arr = []
        for i in range(12):
            arr.append(Arrival(
                0.0,
                rng.randint(0, cfg.vocab_size,
                            (int(rng.choice((8, 16))),)).astype(np.int32),
                int(rng.choice((6, 10))),
                priority=0 if i % 3 == 0 else 1))
        eng = _mk_engine(cfg, params)
        sch = SLOScheduler(eng, max_queue=16, seg_steps=16)
        rep = sch.serve(arr, warm=True)
        assert rep.per_class is not None and set(rep.per_class) == {0, 1}
        assert (rep.per_class[0]["ttft_p99_s"]
                < rep.per_class[1]["ttft_p99_s"]), rep.per_class
        assert eng.pager.leak_report() == []

    def test_never_preempts_same_or_higher_class(self, tiny):
        """FCFS fairness within a class: an engine saturated with class-0
        work never preempts for a later class-0 (or class-1) arrival."""
        cfg, params = tiny
        rng = np.random.RandomState(31)
        arr = ([Arrival(0.0, rng.randint(0, cfg.vocab_size, (16,))
                        .astype(np.int32), 12, priority=0)
                for _ in range(3)]
               + [Arrival(0.05, rng.randint(0, cfg.vocab_size, (8,))
                          .astype(np.int32), 4, priority=1)])
        eng = _mk_engine(cfg, params)
        sch = SLOScheduler(eng, max_queue=8, seg_steps=16)
        rep = sch.serve(arr)
        assert rep.preemptions == 0
        assert rep.n_requests == 4


# ---------------------------------------------------------------------------
# deadline load-shedding + retry_after (tentpole b / satellite 1)
# ---------------------------------------------------------------------------


class TestSheddingAndBackpressure:
    def test_shed_accounting_matches_report(self, tiny):
        """A request whose deadline is already unmeetable is shed, not
        served late: report counts == scheduler counters == telemetry,
        shed rids are absent from results, everyone else serves."""
        from paddle_tpu.observability import metrics

        cfg, params = tiny
        rng = np.random.RandomState(37)
        mk = lambda dls, prio: Arrival(
            0.0, rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32),
            6, priority=prio, deadline_s=dls)
        arr = [mk(None, 0), mk(30.0, 0), mk(-0.001, 1), mk(-0.001, 1)]
        eng = _mk_engine(cfg, params)
        sch = SLOScheduler(eng, seg_steps=16)
        before = metrics.counter("scheduler.shed").value
        rep = sch.serve(arr)
        out = sch.results()
        assert rep.shed == 2 == sch.shed_count
        assert rep.shed_per_class == {1: 2}
        assert metrics.counter("scheduler.shed").value == before + 2
        assert metrics.counter("scheduler.shed[class1]").value >= 2
        assert rep.n_requests == 2 and len(out) == 2
        assert eng.pager.leak_report() == []

    def test_retry_after_hint_on_backpressure(self, tiny):
        """Satellite 1: a refused arrival yields a machine-readable
        retry_after_s derived from the drain rate, surfaced in the
        report and the gauge."""
        from paddle_tpu.observability import metrics

        cfg, params = tiny
        arr = staggered_arrivals(41, 8, 0.0, cfg.vocab_size,
                                 prompt_lens=(8,), gen_lens=(8,))
        eng = _mk_engine(cfg, params)
        sch = SLOScheduler(eng, max_queue=2, seg_steps=16)
        rep = sch.serve(arr)
        assert rep.backpressure_events > 0
        assert rep.retry_after_s is not None and rep.retry_after_s > 0
        assert metrics.gauge("serving.retry_after_s").value > 0
        assert rep.n_requests == 8     # refused arrivals retried client-side


# ---------------------------------------------------------------------------
# fleet failover (tentpole c)
# ---------------------------------------------------------------------------


def _fleet_arr(cfg, rng, n=10):
    return [Arrival(0.0, rng.randint(0, cfg.vocab_size, (8 + i % 8,))
                    .astype(np.int32), 6 + i % 4) for i in range(n)]


def _fleet_serve(cfg, params, arr, injector, n=2, **kw):
    engines = build_fleet(cfg, params, n, slots=2, max_len=96,
                          prompt_buckets=(8, 16, 32), paged=True,
                          page_size=16)
    router = FleetRouter(engines, max_queue=16, seg_steps=8,
                         fault_injector=injector, **kw)
    rep = router.serve(arr)
    out = router.results()
    return router, rep, [out[r] for r in sorted(out)]


@pytest.fixture(scope="module")
def fleet_baseline(tiny):
    """One shared no-fault reference serve (the crash/hang/persistent
    tests all compare against the identical trace — serving it three
    times was pure suite time)."""
    cfg, params = tiny
    arr = _fleet_arr(cfg, np.random.RandomState(43))
    _, rep0, out0 = _fleet_serve(cfg, params, arr, None)
    return arr, rep0, out0


class TestFleetFailover:
    def _serve(self, cfg, params, arr, injector, n=2, **kw):
        return _fleet_serve(cfg, params, arr, injector, n=n, **kw)

    def test_crash_zero_loss_token_identity(self, tiny, fleet_baseline):
        """Acceptance: a seeded replica kill completes with ZERO lost
        requests, and per-request tokens are identical to the no-fault
        run — not only for requests never resident on the killed
        replica (the criterion) but, greedy decode being deterministic,
        for the migrated ones too."""
        cfg, params = tiny
        arr, rep0, out0 = fleet_baseline
        inj = FaultInjector(crash={1: 1})
        router, rep1, out1 = self._serve(cfg, params, arr, inj,
                                         probe_after_s=60.0)
        assert rep1.n_requests == len(arr) == rep0.n_requests
        assert out1 == out0
        assert rep1.failovers == 1 and rep1.requeued > 0
        assert rep1.replica_health[1] == "dead"
        assert router.leak_report() == []
        assert ("crash", 1, 1) in inj.events

    def test_transient_hang_retries_through(self, tiny, fleet_baseline):
        """Bounded-attempt retry: one injected hang within the retry
        budget recovers the segment (suspect -> healthy), no failover,
        tokens identical."""
        cfg, params = tiny
        arr, rep0, out0 = fleet_baseline
        inj = FaultInjector(hang={0: (1, 1)})
        _, rep1, out1 = self._serve(cfg, params, arr, inj,
                                    max_finish_retries=1)
        assert rep1.failovers == 0
        assert out1 == out0
        assert rep1.replica_health == {0: "healthy", 1: "healthy"}

    def test_persistent_hang_escalates_to_dead(self, tiny,
                                               fleet_baseline):
        """A hang outlasting the retry budget is a wedge: the replica
        dies, its requests fail over, nothing is lost."""
        cfg, params = tiny
        arr, rep0, out0 = fleet_baseline
        inj = FaultInjector(hang={1: (1, 5)})
        router, rep1, out1 = self._serve(cfg, params, arr, inj,
                                         max_finish_retries=1,
                                         probe_after_s=60.0)
        assert rep1.failovers == 1
        assert rep1.n_requests == len(arr)
        assert out1 == out0
        assert router.leak_report() == []

    def test_recovered_replica_rejoins_rotation(self, tiny):
        """Re-admission probing: after the probe interval a dead replica
        is probed back to healthy and serves later arrivals again."""
        cfg, params = tiny
        rng = np.random.RandomState(47)
        # early burst, then a late BURST arriving after the crash +
        # probe window (a burst so least-loaded fans it across BOTH
        # replicas — trickled arrivals could all drain through one)
        arr = (_fleet_arr(cfg, rng, n=6)
               + [Arrival(0.3, rng.randint(0, cfg.vocab_size, (8,))
                          .astype(np.int32), 6) for _ in range(6)])
        inj = FaultInjector(crash={1: 0}, recover_after=1)
        router, rep, _ = self._serve(cfg, params, arr, inj,
                                     probe_after_s=0.0)
        assert rep.failovers == 1
        assert rep.replica_health == {0: "healthy", 1: "healthy"}
        assert rep.n_requests == len(arr)
        probed = [e for e in inj.events if e[0] == "probe"]
        assert probed, "the dead replica was never probed"
        # the revived replica took traffic again after recovery
        assert any(p["replica"] == 1 and p["requests"] > 0
                   for p in rep.per_replica)
        assert router.leak_report() == []

    def test_determinism_across_runs(self, tiny):
        """The same seeded kill schedule on the same burst trace yields
        identical per-request tokens run to run (the event-log replay is
        the durable state; nothing depends on wall clock)."""
        cfg, params = tiny
        rng = np.random.RandomState(53)
        arr = _fleet_arr(cfg, rng)
        outs = []
        for _ in range(2):
            inj = FaultInjector(crash={0: 1})
            _, rep, out = self._serve(cfg, params, arr, inj,
                                      probe_after_s=60.0)
            assert rep.n_requests == len(arr)
            outs.append(out)
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# audit: one sync per segment survives chunking, preemption and failover
# ---------------------------------------------------------------------------


class TestSLOAudit:
    def test_chunked_slo_serve_loop_syncs(self, tiny):
        """The whole r13 control plane — chunked prefill, class-ordered
        queue, preemption (a device scatter, not a fetch), shedding —
        keeps the r7/r9 contract: exactly ONE allowed device->host sync
        per segment, zero flagged."""
        from paddle_tpu.analysis import syncs

        cfg, params = tiny
        rng = np.random.RandomState(59)
        # lows: prompt 8 + gen 24 <= the 32 bucket, so the preempt
        # victim's resume always fits; the class-0 arrival and the
        # already-expired-deadline arrival land during the first segment
        arr = ([Arrival(0.0, rng.randint(0, cfg.vocab_size, (8,))
                        .astype(np.int32), 24, priority=1)
                for _ in range(3)]
               + [Arrival(0.001, rng.randint(0, cfg.vocab_size, (8,))
                          .astype(np.int32), 4, priority=0),
                  Arrival(0.001, rng.randint(0, cfg.vocab_size, (8,))
                          .astype(np.int32), 4, priority=1,
                          deadline_s=-0.001)])
        eng = _mk_engine(cfg, params)
        pc = PagedPrefixCache(eng.pager, capacity_pages=32)
        sch = SLOScheduler(eng, max_queue=8, seg_steps=16,
                           prefix_cache=pc)
        sch.serve(arr)                 # warm: compiles + first fetches
        eng.reset_slots()
        pc.clear()
        sch._reqs.clear()
        sch.shed_count = 0
        sch.shed_per_class = {}
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            report = sch.serve(arr)
        flagged = sa.flagged("replay")
        assert flagged == [], [f"{e.kind}@{e.site}" for e in flagged]
        allowed = sa.allowed("replay")
        assert set(allowed) == {"serving.segment_event_fetch"}
        assert allowed["serving.segment_event_fetch"] == report.segments
        assert report.preemptions >= 1 and report.shed >= 1
        pc.clear()
        assert eng.pager.leak_report() == []

    def test_fleet_failover_loop_syncs(self, tiny):
        """The failover path (abort, requeue-to-survivors, probing) is
        pure host bookkeeping: the fleet loop with a mid-serve replica
        kill still costs exactly one allowed fetch per APPLIED segment
        and zero flagged syncs."""
        from paddle_tpu.analysis import syncs

        cfg, params = tiny
        rng = np.random.RandomState(61)
        arr = _fleet_arr(cfg, rng, n=8)
        engines = build_fleet(cfg, params, 2, slots=2, max_len=96,
                              prompt_buckets=(8, 16, 32), paged=True,
                              page_size=16)
        router = FleetRouter(engines, max_queue=16, seg_steps=8,
                             probe_after_s=60.0)
        router.serve(arr)              # warm pass, no faults
        router.reset()
        router.fault_injector = FaultInjector(crash={1: 1})
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            rep = router.serve(arr)
        assert rep.failovers == 1 and rep.n_requests == len(arr)
        flagged = sa.flagged("replay")
        assert flagged == [], [f"{e.kind}@{e.site}" for e in flagged]
        allowed = sa.allowed("replay")
        assert set(allowed) == {"serving.segment_event_fetch"}
        # every APPLIED segment fetched once; the killed segment's fetch
        # never ran (its results are lost by definition)
        assert allowed["serving.segment_event_fetch"] == rep.segments
        assert router.leak_report() == []

    def test_chunked_cache_keys_bucketed(self, tiny):
        """Chunk widths are declared: repeated chunked segments grow no
        unbucketed program keys (the ("cseg", ...) family is finite)."""
        from paddle_tpu.analysis import recompile

        cfg, params = tiny
        eng = _mk_engine(cfg, params, slots=4)
        for _ in range(2):
            eng.add_request(np.arange(12, dtype=np.int32)
                            % cfg.vocab_size, 3)
            eng.run_segment(16)
        lint = recompile.lint_cache_keys(**eng.cache_info())
        assert not lint.hazard
        assert eng.pager.leak_report() == []
