"""Disaggregated prefill/decode serving (r22 tentpole, ISSUE 17).

The ``DisaggRouter`` splits a fleet into a prefill pool (runs prompts
to first token) and a decode pool (runs everything after), with the KV
page set crossing pools through an explicit, journaled, budget-audited
handoff on the r19 host-bytes seam. These tests pin the subsystem's
contracts on the session-scoped ``tiny_llama`` fixture:

* **token identity** — pool placement is an execution detail: the
  disaggregated serve must emit bit-identical tokens to the r13
  co-resident fleet on the same arrivals.
* **decode-pool purity (the TBT-flatness mechanism)** — decode-pool
  segments carry no full-prompt prefills, only block-aligned suffix
  re-prefills after a handoff; measured as §3n interference rows
  (other requests' prefill rows admitted into a decode window).
* **handoff budget** — every crossing moves at most the request's own
  reserved KV footprint (``analysis.tiers.disagg_serve_audit``).
* **sync audit** — the two-pool loop keeps the r7 contract: one event
  fetch per segment plus exactly one labelled tier flush per handoff
  batch, nothing else.
* **cross-pool replay** — the journal header carries the pool
  topology, ``handoff`` is a first-class decision kind, and a
  prefill@A -> handoff -> decode@B journey replays bit-exactly.
* **ops surface** — /healthz and /capacity report per-replica pool
  role and per-pool page aggregates.
"""

import json
import urllib.request

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.analysis import (SyncAudit, disagg_serve_audit,
                                 handoff_audit, recompile)
from paddle_tpu.analysis.tiers import HandoffAuditor
from paddle_tpu.inference.disagg import DisaggRouter
from paddle_tpu.inference.fleet import FleetRouter, build_fleet
from paddle_tpu.inference.scheduler import Arrival
from paddle_tpu.observability import journal as _journal
from paddle_tpu.observability.exporter import OpsServer
from paddle_tpu.observability.slo import Objective, SLOMonitor

PSZ = 16


def _engines(cfg, params, n=2, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32, 64))
    kw.setdefault("paged", True)
    kw.setdefault("page_size", PSZ)
    kw.setdefault("num_pages", 24)
    return build_fleet(cfg, params, n, **kw)


def _disagg(cfg, params, **kw):
    es = _engines(cfg, params, 2)
    kw.setdefault("prefill_seg_steps", 4)
    kw.setdefault("decode_seg_steps", 8)
    kw.setdefault("max_queue", 10 ** 6)
    return DisaggRouter(es[:1], es[1:], **kw)


def _reqs(cfg, seed=0, n=8, lens=(24, 40, 56, 12), gen=8):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size,
                         (lens[i % len(lens)],)).astype(np.int32), gen)
            for i in range(n)]


def _burst(reqs):
    return [Arrival(0.0, p, g) for p, g in reqs]


def _interference(router, decode_only=False):
    """§3n rows: prefill rows of OTHER requests admitted into a
    request's decode window on its own engine, per generated token —
    the deterministic form of the co-residency TBT tax (mirrors the
    serving-lane metric)."""
    by_eng = {}
    for idx, r in router._reqs.values():
        by_eng.setdefault(idx, []).append(r)
    vals = []
    for idx, group in by_eng.items():
        if decode_only and router._replicas[idx].pool != "decode":
            continue
        for r in group:
            if (not r.finish_time or not r.first_token_time
                    or len(r.tokens) < 2):
                continue
            rows = sum(max(0, len(q.prompt) - q.prefix_hit_len)
                       for q in group
                       if q is not r and q.first_token_time
                       and r.first_token_time < q.first_token_time
                       <= r.finish_time)
            vals.append(rows / (len(r.tokens) - 1))
    return float(np.mean(vals)) if vals else 0.0


class TestDisaggIdentity:
    def test_tokens_identical_to_co_resident(self, tiny_llama):
        """Pool placement must not change a single token: the same
        burst through the 2-replica co-resident fleet and the
        1-prefill + 1-decode disaggregated fleet (same total engines)
        produces identical per-request generations — and the
        disaggregated serve actually exercises the handoff path."""
        cfg, params = tiny_llama
        reqs = _reqs(cfg)
        co = FleetRouter(_engines(cfg, params), max_queue=10 ** 6,
                         seg_steps=8, prefix_caches="auto")
        co.serve(_burst(reqs))
        dis = _disagg(cfg, params)
        dis.serve(_burst(reqs))
        assert dis.handoffs > 0
        assert dis.results() == co.results()

    def test_decode_pool_carries_no_full_prompt_prefills(self,
                                                         tiny_llama):
        """The flatness mechanism, structurally: every request that
        finishes on a decode replica arrived there with its prompt
        already page-resident (the handoff import) — at most one
        page's worth of suffix rows re-prefill — so the decode pool's
        interference stays at zero while the co-resident fleet's is
        positive on the same oversubscribed burst. Page-aligned
        prompts make the bound exact: the block-aligned export covers
        the whole prompt, so zero prompt rows re-prefill."""
        cfg, params = tiny_llama
        reqs = _reqs(cfg, lens=(32, 48, 64, 16))
        dis = _disagg(cfg, params)
        dis.serve(_burst(reqs))
        decode_reqs = [q for idx, q in dis._reqs.values()
                       if dis._replicas[idx].pool == "decode"]
        assert decode_reqs, "no request ever crossed to the decode pool"
        for q in decode_reqs:
            assert q.prefix_hit_len >= len(q.prompt) - PSZ, \
                f"rid {q.rid}: full-prompt prefill ran on a decode " \
                f"replica (hit {q.prefix_hit_len} of {len(q.prompt)})"
        co = FleetRouter(_engines(cfg, params), max_queue=10 ** 6,
                         seg_steps=8, prefix_caches="auto")
        co.serve(_burst(reqs))
        assert _interference(co) > 0.0          # burst makes co pay
        assert _interference(dis, decode_only=True) == 0.0

    def test_handoff_budget_ledger_and_report(self, tiny_llama):
        """Every crossing within bytes <= the request's reserved KV
        footprint, per-handoff AND per-request, plus conservation on
        both pools' host tiers; the ledger and the counters agree."""
        cfg, params = tiny_llama
        dis = _disagg(cfg, params)
        dis.serve(_burst(_reqs(cfg)))
        assert dis.handoffs > 0
        assert disagg_serve_audit(dis) == []
        pb = dis._replicas[0].prefix_cache.host_tier.page_bytes()
        assert handoff_audit(dis.handoff_log, pb) == []
        rep = dis.handoff_report()
        assert rep["handoffs"] == dis.handoffs == len(dis.handoff_log)
        assert rep["pages"] == sum(h["pages"] for h in dis.handoff_log)
        assert rep["bytes"] == sum(h["bytes"] for h in dis.handoff_log)
        stats = dis.pool_stats()
        assert set(stats) == {"prefill", "decode"}
        assert stats["prefill"]["replicas"] == [0]
        assert stats["decode"]["replicas"] == [1]


class TestDisaggAudits:
    def test_one_sync_per_segment_both_pools(self, tiny_llama):
        """The r7 sync contract survives disaggregation: a warmed
        two-pool serve fetches exactly one event log per segment and
        performs exactly one labelled tier flush per handoff batch —
        zero flagged syncs, nothing unlabelled."""
        cfg, params = tiny_llama
        reqs = _reqs(cfg)
        dis = _disagg(cfg, params)
        dis.serve(_burst(reqs), warm=True)      # compiles + first fetch
        dis.reset()
        with SyncAudit() as audit:
            audit.phase = "serve"
            rep = dis.serve(_burst(reqs))
        assert audit.flagged("serve") == [], \
            [f"{e.kind}@{e.site}" for e in audit.flagged("serve")]
        assert audit.allowed("serve") == {
            "serving.segment_event_fetch": rep.segments,
            "serving.tier_transfer": dis.handoff_flushes}

    def test_zero_post_warmup_compiles_per_pool(self, tiny_llama):
        """Per-pool envelopes must cover each pool's whole program
        space: after ``aot_warmup`` a serve triggers zero compiles in
        either pool, and the prefill/decode bills are disjoint slices
        of the co-resident union ladder (each strictly smaller)."""
        cfg, params = tiny_llama
        dis = _disagg(cfg, params)
        warm = dis.aot_warmup()
        union = {k for rep in warm.values()
                 for fam in rep.values() for k in [fam["keys"]]}
        for idx, rep in warm.items():
            for fam in rep.values():
                assert fam["keys"] > 0
        with recompile.enforce_zero_compiles("disagg serve") as cw:
            dis.serve(_burst(_reqs(cfg)))
        assert cw.compiles == 0
        assert dis.handoffs > 0                 # the path actually ran

    def test_gate_auditor_observes_without_perturbing(self, tiny_llama):
        """The ``--gate --disagg on`` contract: the HandoffAuditor is
        pure observation on the flight stream — the handoff ledger is
        identical with it attached or not, it sees every crossing, and
        a within-budget serve yields zero violations."""
        cfg, params = tiny_llama
        reqs = _reqs(cfg)
        dis = _disagg(cfg, params)
        dis.serve(_burst(reqs))
        bare = [dict(h) for h in dis.handoff_log]
        dis.reset()
        auditor = HandoffAuditor(
            page_bytes=dis._replicas[0].prefix_cache.host_tier
            .page_bytes())
        auditor.install()
        try:
            dis.serve(_burst(reqs))
        finally:
            auditor.uninstall()
        assert [dict(h) for h in dis.handoff_log] == bare
        assert auditor.handoffs == dis.handoffs
        assert auditor.pages == dis.handoff_pages
        assert auditor.violations == []

    def test_per_pool_slo_objectives(self, tiny_llama):
        """TTFT belongs to the prefill pool, TBT to the decode pool:
        the router feeds both ledgers from the stamps it already
        takes, and the monitor reports them per pool."""
        cfg, params = tiny_llama
        mon = SLOMonitor({}, pool_objectives={
            "prefill": Objective(ttft_target_s=30.0),
            "decode": Objective(tbt_target_s=30.0)})
        dis = _disagg(cfg, params, slo_monitor=mon)
        dis.serve(_burst(_reqs(cfg)))
        assert dis.handoffs > 0
        rep = mon.report()["pools"]
        assert rep["prefill"]["outcomes"] > 0       # one per first token
        assert rep["decode"]["outcomes"] > 0        # one per finish
        assert rep["prefill"]["violations"] == 0    # generous targets
        assert rep["decode"]["violations"] == 0
        assert mon.pool_state("prefill") == "ok"
        assert mon.pool_state("decode") == "ok"


class TestDisaggReplay:
    def test_cross_pool_journey_replays_bit_exactly(self, tiny_llama):
        """A journaled disaggregated serve replays to the identical
        decision stream from the header alone: the header carries the
        pool topology (role per replica, per-pool envelopes), the
        stream carries first-class ``handoff`` decisions, and
        prefill@A -> handoff -> decode@B reconstructs bit-exactly."""
        cfg, params = tiny_llama
        reqs = _reqs(cfg)
        dis = _disagg(cfg, params)
        j = obs.Journal()
        with _journal.attach(j):
            dis.serve(_burst(reqs))
        assert dis.handoffs > 0
        header = j.records()[0]["header"]
        assert header["driver"] == "disagg"
        assert header["pools"] == ["prefill", "decode"]
        env = header["disagg"]["envelopes"]
        assert set(env) == {"prefill", "decode"}
        kinds = {r["kind"] for r in j.records()[1:]}
        assert "handoff" in kinds
        res = obs.replay_serve(j.records(), params=params)
        assert res.identical, res.first_divergence

    def test_constructor_validation(self, tiny_llama):
        """Both pools must be non-empty; canary serving is rejected (its
        replica index arithmetic has no pool)."""
        cfg, params = tiny_llama
        es = _engines(cfg, params)
        with pytest.raises(ValueError, match="pool"):
            DisaggRouter(es[:1], [])
        with pytest.raises(ValueError, match="canary"):
            DisaggRouter(es[:1], es[1:], canary=object())


class TestDisaggOpsSurface:
    def test_healthz_and_capacity_report_pools(self, tiny_llama):
        """/healthz and /capacity carry the pool topology: per-replica
        role plus per-pool aggregate pages_free / reclaimable — the
        autoscaler's per-pool signal."""
        cfg, params = tiny_llama
        dis = _disagg(cfg, params)
        dis.serve(_burst(_reqs(cfg)))
        with OpsServer(port=0, fleet=dis) as srv:
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=10) as r:
                body = json.loads(r.read().decode())
            roles = {idx: row["pool"]
                     for idx, row in body["pages"].items()}
            assert roles == {"0": "prefill", "1": "decode"}
            pools = body["pools"]
            assert pools["prefill"]["replicas"] == [0]
            assert pools["decode"]["replicas"] == [1]
            with urllib.request.urlopen(srv.url + "/capacity",
                                        timeout=10) as r:
                cap = json.loads(r.read().decode())
            assert {row["pool"] for row in cap["replicas"].values()} \
                == {"prefill", "decode"}
            for pool in ("prefill", "decode"):
                row = cap["pools"][pool]
                assert row["healthy"] == 1
                assert row["pages_free"] >= 0
                assert row["reclaimable"] >= 0

    def test_dispatch_candidates_carry_pool_tag(self, tiny_llama):
        """Journaled dispatch decisions record which pool each
        candidate belonged to — the replay-side debugging surface for
        cross-pool routing."""
        cfg, params = tiny_llama
        dis = _disagg(cfg, params)
        j = obs.Journal()
        with _journal.attach(j):
            dis.serve(_burst(_reqs(cfg, n=4)))
        dispatches = [r for r in j.records()[1:]
                      if r["kind"] == "dispatch"]
        assert dispatches
        for d in dispatches:
            # the snapshot shows the WHOLE fleet with pool tags (decode
            # replicas present-but-ineligible), but fresh prompts only
            # ever land on the prefill pool
            assert {c["pool"] for c in d["candidates"]} \
                == {"prefill", "decode"}
            assert dis._replicas[d["replica"]].pool == "prefill"
