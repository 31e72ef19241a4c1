"""Shadow & canary serving — online quality observability (r17
tentpole, ISSUE 12): in-program logit digests riding the single audited
segment fetch, shadow-diff identity on a bf16-vs-bf16-style control,
seeded logit-perturbation detection with EXACT first-divergence
positions, canary verdicts + auto-hold, the quality_serving_segment
gate budget, the one-sync-per-segment audit over a SHADOWED fleet loop
(allowed == primary + shadow fetches exactly), journal replay identity
with a shadow attached, the accept-rate drift rule, and the ≤2%
shadow-attachment overhead gate.

Everything rides the session ``tiny_llama`` fixture, one shared engine
geometry (maximising ``serving._SHARED_PROGS`` hits), and TWO
module-scoped recorded serves (control + perturbed) that the identity /
detection / journey / replay tests all read.
"""

import numpy as np
import pytest

from paddle_tpu.inference.fleet import FleetRouter, Shadow, build_fleet
from paddle_tpu.inference.scheduler import Arrival
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import llama
from paddle_tpu.observability import journal, metrics, replay
from paddle_tpu.observability.quality import (CanaryController,
                                              QualityMonitor,
                                              compare_pair)
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
    kw.setdefault("quality_digest", True)
    return ServingEngine(cfg, params, **kw)


def _trace(cfg, n=6, seed=11, gen=6):
    rng = np.random.RandomState(seed)
    return [Arrival(0.0, rng.randint(0, cfg.vocab_size, (8,))
                    .astype(np.int32), gen) for _ in range(n)]


def _perturb(params, scale=0.05, seed=99):
    """Seeded logit perturbation: noise on the output head — the
    variant class quantization error belongs to (every logit moves a
    little; some argmaxes flip)."""
    import jax

    p = dict(params)
    noise = jax.random.normal(jax.random.PRNGKey(seed),
                              params["lm_head"].shape,
                              params["lm_head"].dtype)
    p["lm_head"] = params["lm_head"] + scale * noise
    return p


@pytest.fixture(scope="module")
def control_recorded(tiny, tmp_path_factory):
    """ONE journaled CONTROL shadow serve: primary and shadow run the
    SAME params/config (the bf16-vs-bf16 certification shape) at
    sample_p=1.0, digests on both sides."""
    cfg, params = tiny
    arr = _trace(cfg)
    router = FleetRouter([_mk(cfg, params)],
                         shadow=Shadow(_mk(cfg, params), sample_p=1.0),
                         seg_steps=16)
    router.serve(arr)                    # warm: compiles qseg shapes
    router.reset()
    jdir = str(tmp_path_factory.mktemp("journal_shadow"))
    j = journal.Journal(jdir)
    j.params_info = {"prng_seed": 0}
    with journal.attach(j):
        report = router.serve(arr)
    j.close()
    return {"dir": jdir, "report": report, "router": router,
            "params": params, "arr": arr,
            "records": journal.read_journal(jdir)["records"]}


@pytest.fixture(scope="module")
def perturb_served(tiny):
    """ONE perturbed shadow serve: the shadow runs seeded logit-noised
    params with logit-error budgets armed and a (loose) SLO monitor
    attached — the detection, page-ordering and first-divergence tests
    all read it."""
    from paddle_tpu.observability.slo import Objective, SLOMonitor

    cfg, params = tiny
    pert = _perturb(params)
    arr = _trace(cfg)
    mon = QualityMonitor(logit_abs_warn=0.05, logit_abs_page=5.0)
    slo = SLOMonitor({0: Objective(ttft_target_s=30.0, e2e_target_s=60.0,
                                   compliance=0.99)})
    router = FleetRouter([_mk(cfg, params)],
                         shadow=Shadow(_mk(cfg, pert), sample_p=1.0,
                                       monitor=mon),
                         seg_steps=16, slo_monitor=slo)
    report = router.serve(arr)
    return {"report": report, "router": router, "monitor": mon,
            "slo": slo, "pert": pert, "arr": arr, "cfg": cfg,
            "params": params}


# ---------------------------------------------------------------------------
# digests: in-program evidence, bit-identical token streams
# ---------------------------------------------------------------------------


class TestDigests:
    def test_digest_self_consistency_and_token_identity(self, tiny):
        """The digest flag changes WHAT the fetch carries, never what
        the engine emits: tokens bit-identical digest-on vs digest-off,
        and each digest is self-consistent (greedy ⇒ top-1 id IS the
        emitted token, top-1 value IS its logit)."""
        cfg, params = tiny
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
                   for _ in range(3)]
        on = _mk(cfg, params)
        off = _mk(cfg, params, quality_digest=False)
        for p in prompts:
            on.add_request(p, 6)
            off.add_request(p, 6)
        assert on.run() == off.run()
        for p in prompts:
            on.add_request(p, 6)
        on.run_segment(32)
        assert on._finished
        for r in on._finished:
            assert r.digests is not None
            assert len(r.digests) == len(r.tokens)
            for t, (el, ids, vals) in zip(r.tokens, r.digests):
                assert ids[0] == t
                assert vals[0] == pytest.approx(el, abs=1e-5)
                assert vals == sorted(vals, reverse=True)

    def test_digest_requires_plain_paged(self, tiny):
        cfg, params = tiny
        with pytest.raises(ValueError, match="token level"):
            _mk(cfg, params, speculative=2)

    def test_compare_pair_semantics(self):
        assert compare_pair([1, 2, 3], [1, 2, 3])["match"]
        r = compare_pair([1, 2, 3], [1, 9, 3])
        assert r["first_divergence"] == 1 and not r["match"]
        # strict-prefix length divergence IS a divergence, at the
        # shorter length
        assert compare_pair([1, 2, 3], [1, 2])["first_divergence"] == 2
        # logit stats only over the matched prefix
        dp = [(1.0, [1, 2], [1.0, 0.5]), (2.0, [3, 4], [2.0, 1.0])]
        ds = [(1.5, [1, 2], [1.5, 0.5]), (9.0, [9, 8], [9.0, 1.0])]
        r = compare_pair([1, 3], [1, 9], dp, ds)
        assert r["first_divergence"] == 1
        assert r["logit_positions"] == 1
        assert r["logit_max_abs_err"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# shadow diffing: control identity, perturbation detection
# ---------------------------------------------------------------------------


class TestShadowDiff:
    def test_control_certifies_identity(self, control_recorded):
        """Same params, same config ⇒ the shadow pair certifies 100%
        token match with ZERO logit error and no quality alert — the
        bf16-vs-bf16 control that gives the perturbation detection its
        meaning."""
        rep = control_recorded["report"]
        q = rep.quality
        assert rep.shadow["mirrored"] == rep.n_requests
        assert rep.shadow["compared"] == rep.n_requests
        assert q["token_match_rate"] == 1.0
        assert q["pairs_mismatched"] == 0
        # same compiled executable (shared program cache), same params:
        # the digests are bit-identical, not just close
        assert q["logit_max_abs_err"] <= 1e-6
        assert q["level"] == "ok" and q["alerts"] == []

    def test_perturbation_detected_with_exact_positions(self,
                                                        perturb_served):
        """The seeded logit-noise variant is caught, and every reported
        first-divergence position equals the reference diff (primary
        stream vs dense greedy generation under the perturbed params —
        an independent oracle)."""
        d = perturb_served
        q = d["report"].quality
        assert q["pairs_mismatched"] >= 1
        results = {rid: req.tokens
                   for rid, (_, req) in d["router"]._reqs.items()}
        arr = sorted(d["arr"], key=lambda a: a.t)
        checked = 0
        for pair in d["monitor"].pair_log:
            rid = pair["rid"]
            prompt = arr[rid].prompt
            ref = [int(t) for t in np.asarray(llama.generate(
                d["pert"], np.asarray(prompt, np.int32)[None], d["cfg"],
                max_new_tokens=arr[rid].max_new_tokens,
                max_len=96))[0]]
            primary = results[rid]
            expect = next((i for i, (a, b)
                           in enumerate(zip(primary, ref)) if a != b),
                          None)
            assert pair["first_divergence"] == expect
            checked += 1
        assert checked >= 1

    def test_quality_page_before_any_slo_violation(self, perturb_served):
        """The ISSUE 12 ordering bar: the quality page fires while the
        per-class SLO ledger has seen ZERO violations — quality
        observability leads the latency surface, it does not trail
        it."""
        d = perturb_served
        assert d["monitor"].worst_level() == "page"
        assert any(a["level"] == "page" for a in d["monitor"].alert_log)
        slo_rep = d["slo"].report()
        assert slo_rep["alerts"] == []
        assert all(c["violations"] == 0
                   for c in slo_rep["classes"].values())

    def test_divergence_metrics_recorded(self, perturb_served):
        q = perturb_served["report"].quality
        assert q["logit_max_abs_err"] > 0.0
        assert q["kl_sampled_max"] is not None
        assert len(q["first_divergence_positions"]) == \
            q["pairs_mismatched"]


# ---------------------------------------------------------------------------
# the audited contract: syncs, budgets, replay, journeys
# ---------------------------------------------------------------------------


class TestShadowAudit:
    def test_shadowed_fleet_loop_syncs(self, tiny):
        """One-fetch-per-segment over the SHADOWED loop: zero flagged
        syncs, and the allowed label counts primary + shadow segment
        fetches EXACTLY — the shadow pays its own sanctioned fetch and
        nothing else."""
        from paddle_tpu.analysis import SyncAudit

        cfg, params = tiny
        arr = _trace(cfg, n=4, seed=23)
        router = FleetRouter([_mk(cfg, params)],
                             shadow=Shadow(_mk(cfg, params),
                                           sample_p=1.0),
                             seg_steps=16)
        router.serve(arr)                 # warm (compiles outside audit)
        router.reset()
        with SyncAudit() as audit:
            audit.phase = "serve"
            report = router.serve(arr)
        assert audit.flagged("serve") == [], audit.flagged("serve")
        allowed = audit.allowed("serve")
        expect = report.segments + report.shadow["segments"]
        assert allowed == {"serving.segment_event_fetch": expect}, (
            allowed, expect)

    def test_quality_program_budget_and_gate_bit_identity(self):
        """The 9th canonical program stays within its pinned budget,
        and its sync/compile metrics are bit-identical with the quality
        monitor attached vs not (the --quality on|off contract)."""
        from paddle_tpu.analysis import auditor, budgets, programs
        from paddle_tpu.observability import quality as q

        handle = programs.build("quality_serving_segment")

        def audit(attach):
            mon = QualityMonitor() if attach else None
            if mon is not None:
                q.install(mon)
            try:
                return auditor.audit_replay("quality_serving_segment",
                                            handle.replay, replays=2)
            finally:
                if mon is not None:
                    q.uninstall(mon)

        rep_on = audit(True)
        rep_off = audit(False)
        rep_on.merge(auditor.audit_static(
            "quality_serving_segment", handle.hlo(),
            donation_threshold=handle.donation_threshold,
            expected_undonated=handle.expected_undonated))
        assert budgets.check(rep_on) == [], rep_on.format()
        for key in ("host_syncs_flagged", "host_syncs_allowed",
                    "warm_compiles"):
            assert rep_on.metrics[key] == rep_off.metrics[key], (
                key, rep_on.metrics[key], rep_off.metrics[key])

    def test_shadowed_serve_replays_identical(self, control_recorded):
        """The r16 replay contract survives a shadow attachment: the
        PRIMARY decision stream replays bit-exactly WITHOUT the replay
        rebuilding the shadow (shadow records — clock reads included —
        carry the shadow mark and sit off the diffed stream)."""
        res = replay.replay_serve(control_recorded["dir"],
                                  params=control_recorded["params"])
        assert res.identical, (res.divergence, res.error)
        assert res.n_decisions > 0
        # the recording DOES carry marked shadow records (losslessness)
        assert any(r.get("shadow") for r in control_recorded["records"])

    def test_quality_endpoint_round_trip(self):
        import json as _json
        import urllib.request

        from paddle_tpu.observability import OpsServer

        mon = QualityMonitor()
        mon.note_pair(0, [1, 2, 3], [1, 2, 3])
        can = CanaryController(replica=1, weight=0.25)
        with OpsServer(port=0, quality_monitor=mon, canary=can) as srv:
            with urllib.request.urlopen(srv.url + "/quality",
                                        timeout=5) as r:
                body = _json.loads(r.read())
        assert body["enabled"] is True
        assert body["pairs"] == 1 and body["token_match_rate"] == 1.0
        assert body["canary"]["replica"] == 1

    def test_journey_gains_the_shadow_pair(self, control_recorded):
        recs = control_recorded["records"]
        rid = next(r["rid"] for r in recs if r["kind"] == "shadow_mirror")
        j = journal.request_journey(recs, rid)
        assert j["shadow_pair"] is True
        assert j["shadow_match"] is True
        kinds = j["kinds"]
        assert "shadow_mirror" in kinds and "shadow_finish" in kinds
        assert kinds.index("shadow_mirror") < kinds.index("shadow_finish")


# ---------------------------------------------------------------------------
# canary: verdicts, auto-hold, routing isolation
# ---------------------------------------------------------------------------


class TestCanary:
    def test_verdict_auto_hold_on_latency(self):
        """A canary whose latencies blow the ratio budget is HELD: the
        verdict is journaled and the routing weight drops to 0."""
        can = CanaryController(replica=1, weight=0.5, seed=0,
                               latency_ratio_max=1.5, min_outcomes=3,
                               verdict_every=6)
        for _ in range(6):
            can.note_outcome("control", "e2e", 0, 0.1)
        for _ in range(5):
            can.note_outcome("canary", "e2e", 0, 1.0)
        assert not can.held
        can.note_outcome("canary", "e2e", 0, 1.0)   # 6th -> verdict
        assert can.held and can.weight == 0.0
        assert can.verdicts[-1]["verdict"] == "hold"
        assert can.hold_reason == "latency_ratio"
        assert not can.assign(123)                  # held: no traffic

    def test_verdict_pass_and_insufficient(self):
        can = CanaryController(replica=1, weight=0.5, min_outcomes=3,
                               verdict_every=100)
        assert can.evaluate()["verdict"] == "insufficient"
        for _ in range(4):
            can.note_outcome("control", "e2e", 0, 0.1)
            can.note_outcome("canary", "e2e", 0, 0.11)
        v = can.evaluate(final=True)
        assert v["verdict"] == "pass" and not can.held

    def test_router_canary_split_and_isolation(self, tiny):
        """Seeded weight routes SOME traffic to the canary replica and
        control traffic NEVER lands there — the comparison populations
        stay disjoint; a held canary gets zero new traffic."""
        cfg, params = tiny
        arr = _trace(cfg, n=10, seed=31)

        def mk_router(can):
            engines = build_fleet(cfg, params, 2, slots=2, max_len=96,
                                  prompt_buckets=(8, 16, 32), paged=True,
                                  page_size=16)
            return FleetRouter(engines, seg_steps=16, canary=can)

        can = CanaryController(replica=1, weight=0.5, seed=3,
                               min_outcomes=4, verdict_every=4)
        router = mk_router(can)
        rep = router.serve(arr)
        assert rep.dispatches_canary > 0
        crep = router._replicas[1]
        assert crep.dispatches["affinity"] == 0
        assert crep.dispatches["least_loaded"] == 0
        assert crep.dispatches["canary"] == rep.dispatches_canary
        assert rep.canary is not None and rep.canary["verdicts"]

        held = CanaryController(replica=1, weight=0.5, seed=3)
        held.hold("operator")
        rep2 = mk_router(held).serve(arr)
        assert rep2.dispatches_canary == 0
        assert router._replicas[1].rids is not None  # canary drained


# ---------------------------------------------------------------------------
# accept-rate drift (slo.py satellite) + overhead gate
# ---------------------------------------------------------------------------


class TestDriftAndOverhead:
    def test_accept_drift_warns_on_sustained_drop(self):
        from paddle_tpu.observability.slo import Objective, SLOMonitor

        mon = SLOMonitor({0: Objective(ttft_target_s=1.0)},
                         accept_drift={"min_segments": 4, "sustain": 3,
                                       "drop": 0.25})
        for _ in range(6):
            mon.note_accept_rate(0.7)
        assert mon.drift_level == "ok"
        for _ in range(3):
            mon.note_accept_rate(0.2)
        assert mon.drift_level == "warning"
        rep = mon.report()["accept_drift"]
        assert rep["level"] == "warning" and rep["alerts"]
        mon.reset()
        assert mon.drift_level == "ok"

    def test_accept_drift_blip_suppressed(self):
        from paddle_tpu.observability.slo import Objective, SLOMonitor

        mon = SLOMonitor({0: Objective(ttft_target_s=1.0)},
                         accept_drift={"min_segments": 4, "sustain": 3,
                                       "drop": 0.25})
        for _ in range(6):
            mon.note_accept_rate(0.7)
        mon.note_accept_rate(0.1)           # one-segment blip
        for _ in range(4):
            mon.note_accept_rate(0.7)
        assert mon.drift_level == "ok" and not mon.drift_log

    def test_shadow_attachment_overhead_within_2pct(self, tiny):
        """The always-on cost bar: a shadow ATTACHED but sampling
        nothing (sample_p=0 — the machinery without the mirrored
        compute) costs ≤2% primary wall-clock, min-of-4 interleaved.
        Mirrored traffic itself costs sample_p × the variant's compute
        by design — that arithmetic lives in SCALING §3l, not in an
        overhead gate."""
        import time

        cfg, params = tiny
        arr = _trace(cfg, n=8, seed=41)

        def serve_once(with_shadow):
            eng = _mk(cfg, params)
            sh = (Shadow(_mk(cfg, params), sample_p=0.0)
                  if with_shadow else None)
            router = FleetRouter([eng], seg_steps=16, shadow=sh)
            t0 = time.perf_counter()
            router.serve(arr)
            return time.perf_counter() - t0

        serve_once(True)                  # warm every shape
        times = {True: [], False: []}
        for _ in range(4):
            for mode in (False, True):    # interleave off/on
                times[mode].append(serve_once(mode))
        t_on, t_off = min(times[True]), min(times[False])
        # 2 ms absolute slack: below the host-clock jitter floor on a
        # sub-second CPU workload; the 2% bar is the real gate
        assert t_on <= t_off * 1.02 + 0.002, (
            f"shadow-attachment overhead {t_on / t_off - 1.0:+.2%} "
            f"(on {t_on * 1e3:.1f} ms vs off {t_off * 1e3:.1f} ms) "
            f"exceeds the 2% acceptance bar")
