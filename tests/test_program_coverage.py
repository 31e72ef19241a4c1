"""Program-space coverage auditor (r20, ISSUE 15).

The serving bucket ladder as a declared, statically enumerable object:
registry-only key construction (linted over the serving/scheduler/fleet
ASTs), exact enumeration of every reachable segment program from an
engine config + workload envelope (proven against a brute-force replay
of the admission arithmetic), AOT bucket-ladder warmup, and the hard
zero-post-warmup-backend-compiles budget over a mixed workload
(chunked prefill + prefix/tier cache + preempt + failover, and the
speculative family) — plus the r15 persistent-cache interplay (a warm
restart skips the XLA recompiles; the enumeration is unchanged).

Suite-time note: engine geometries here deliberately match the other
serving test modules (conftest's session ``tiny_llama`` + the shared
``serving._SHARED_PROGS`` cache), so the segment programs this module
compiles are the same executables later modules would have compiled
anyway.
"""

import numpy as np
import pytest

from paddle_tpu.analysis import coverage, recompile
from paddle_tpu.inference.program_space import (PROGRAM_SPACE,
                                                WorkloadEnvelope,
                                                chunk_for)
from paddle_tpu.inference.serving import ServingEngine


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    return tiny_llama


def _prompts(cfg, seed, lens, n):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        (int(rng.choice(lens)),)).astype(np.int32)
            for _ in range(n)]


class TestRegistry:
    def test_key_formats_identical_to_legacy(self):
        """The registry constructs byte-identical tuples to the
        hand-built r7–r17 keys — _SHARED_PROGS entries and every test
        that pins a key stay valid."""
        S = PROGRAM_SPACE
        assert S.key("pseg", n_pad=4, s_max=16, steps=12) == \
            ("pseg", 4, 16, 12)
        assert S.key("qseg", n_pad=4, s_max=16, steps=12) == \
            ("qseg", 4, 16, 12)
        assert S.key("cseg", n_pad=4, s_max=16, c=8, steps=16) == \
            ("cseg", 4, 16, 8, 16)
        assert S.key("sseg", n_pad=4, k=3, steps=16) == ("sseg", 4, 3, 16)

    def test_key_rejects_wrong_axes(self):
        with pytest.raises(TypeError):
            PROGRAM_SPACE.key("pseg", n_pad=4, s_max=16)      # missing
        with pytest.raises(TypeError):
            PROGRAM_SPACE.key("pseg", n_pad=4, s_max=16, steps=12,
                              pre_max=0)                      # extra
        with pytest.raises(KeyError):
            PROGRAM_SPACE.key("zseg", n_pad=4)                # unknown

    def test_family_of_classifies_keys(self):
        S = PROGRAM_SPACE
        assert S.family_of(("pseg", 4, 16, 12)) == "pseg"
        assert S.family_of(("sseg", 4, 3, 16)) == "sseg"
        assert S.family_of((16, 2)) is None           # every key is tagged
        assert S.family_of(("seg", 4, 16, 0, 12)) is None
        assert S.family_of(("zseg", 1, 2, 3)) is None
        assert S.family_of(("pseg", 4, 16)) is None   # wrong arity

    def test_registry_only_construction_in_tier1(self):
        """Satellite 1's assertion: no hand-built program-key tuple
        survives anywhere in serving/scheduler/fleet — every jit memo
        key routes through PROGRAM_SPACE.key."""
        assert coverage.lint_registry_only() == []

    def test_lint_flags_handbuilt_key_tuple(self):
        """Seeded known-bad fixture: an unregistered key constructor is
        caught by the AST lint."""
        bad = ("def rogue(n_pad, s_max, steps):\n"
               "    key = ('pseg', n_pad, s_max, steps)\n"
               "    return key\n")
        hits = coverage.lint_source(bad, "fixture_module")
        assert len(hits) == 1 and "fixture_module:2" in hits[0]
        assert "PROGRAM_SPACE.key" in hits[0]
        # prose/docstring mentions are NOT flagged
        assert coverage.lint_source('"a (\'pseg\', ...) key"', "d") == []

    def test_chunk_cap_arithmetic_shared(self, tiny):
        """Satellite 1: the engine's chunk-cap routing IS the registry's
        chunk_for — one copy, no drift between dispatch and coverage."""
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(16, 32, 64), paged=True,
                            page_size=16, chunked_prefill=True,
                            prefill_chunks=(8, 16, 32))
        for w in (8, 16, 24, 32, 48, 64):
            assert eng._prefill_chunk_for(w) == \
                chunk_for(eng.prefill_chunks, w)


class TestEveryFamilyIsReachable:
    """``PROGRAM_SPACE`` holds no family whose predicate is true for no
    engine: each one is engaged by a constructor option that exists, and
    then enumerates at least one key."""

    ENGAGES = {
        "pseg": dict(),
        "qseg": dict(quality_digest=True),
        "qpseg": dict(quant="int8"),
        "cseg": dict(chunked_prefill=True, prefill_chunks=(8, 16)),
        "sseg": dict(speculative=3),
        "spseg": dict(seq_parallel=2, long_buckets=(64, 96),
                      prefill_chunks=(8, 16)),
    }

    def test_table_names_every_family(self):
        assert sorted(self.ENGAGES) == PROGRAM_SPACE.families()

    @pytest.mark.parametrize("family", sorted(ENGAGES))
    def test_family_engages_and_enumerates(self, tiny, family):
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=4, max_len=96, chunk=8,
                            prompt_buckets=(16, 32), page_size=16,
                            **self.ENGAGES[family])
        assert PROGRAM_SPACE.family(family).applies(eng)
        keys = eng.program_space()[family]
        assert keys and all(k[0] == family for k in keys)


class TestEnumeration:
    """The reachability proof: closed-form enumeration == brute-force
    replay of the admission arithmetic, across configs and envelopes.
    Pure host arithmetic — nothing compiles here."""

    ENVS = [
        dict(max_prompt=30, max_new_tokens=8, seg_steps=(16, 32)),
        dict(max_prompt=30, max_new_tokens=8, seg_steps=(16,),
             prefix_block=16),
        dict(max_prompt=12, max_new_tokens=3, seg_steps=(16,),
             prefix_block=16, resume=False),
        dict(max_prompt=20, max_new_tokens=6, seg_steps=(32,),
             prefix_block=8),
    ]

    @pytest.mark.parametrize("ckw", [
        dict(paged=True, page_size=16, prompt_buckets=(16, 32)),
        dict(paged=True, page_size=16, prompt_buckets=(16, 32),
             chunked_prefill=True, prefill_chunks=(8, 16)),
        dict(paged=True, page_size=16, prompt_buckets=(32,),
             speculative=3),
        dict(paged=True, page_size=16, prompt_buckets=(16, 32),
             quality_digest=True),
        dict(page_size=16, prompt_buckets=(16, 32), quant="int8"),
        dict(page_size=16, prompt_buckets=(16, 32), seq_parallel=2,
             long_buckets=(64, 96), prefill_chunks=(8, 16)),
    ])
    def test_enumeration_matches_admission_replay(self, tiny, ckw):
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=4, max_len=96, chunk=8,
                            **ckw)
        for ekw in self.ENVS:
            env = WorkloadEnvelope(**ekw)
            assert coverage.check_envelope(eng, env) == [], (ckw, ekw)
            space = eng.program_space(env)
            assert space, "enumeration must be non-empty"
            # every enumerated key classifies into a registered family
            for fam, keys in space.items():
                for k in keys:
                    assert PROGRAM_SPACE.family_of(k) == fam

    def test_width_pinning_respected(self, tiny):
        """The spec family carries no width by design; plain paged
        engines without a prefix cache pin to the top bucket."""
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=4, max_len=96,
                            prompt_buckets=(16, 32, 64), paged=True,
                            page_size=16)
        env = WorkloadEnvelope(max_prompt=60, max_new_tokens=8,
                               seg_steps=(16,))
        (keys,) = eng.program_space(env).values()
        assert keys == frozenset({("pseg", 4, 64, 16)})
        # with a prefix cache every covering bucket is reachable
        env_pc = WorkloadEnvelope(max_prompt=60, max_new_tokens=8,
                                  seg_steps=(16,), prefix_block=16)
        (keys_pc,) = eng.program_space(env_pc).values()
        assert keys_pc == frozenset({("pseg", 4, 16, 16),
                                     ("pseg", 4, 32, 16),
                                     ("pseg", 4, 64, 16)})


class TestMixedWorkloadCoverage:
    """Randomized mixed serve: every observed compile key is in the
    enumerated set and ZERO backend compiles happen post-warmup —
    chunked prefill + prefix cache with a host tier (spill/restore) +
    preemption + failover abort/resume on one engine, the speculative
    family on a second."""

    @pytest.fixture(scope="class")
    def served(self, tiny):
        cfg, params = tiny
        from paddle_tpu.inference.prefix_cache import make_prefix_cache

        eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=8,
                            prompt_buckets=(16, 32), paged=True,
                            page_size=16, num_pages=13,
                            chunked_prefill=True, prefill_chunks=(8, 16))
        pc = make_prefix_cache(eng, host_tier_pages=16)
        env = WorkloadEnvelope(max_prompt=30, max_new_tokens=8,
                               seg_steps=(16,), prefix_block=16)
        aot = eng.aot_warmup(env, prefix_cache=pc)
        rng = np.random.RandomState(7)
        prompts = _prompts(cfg, 7, (12, 24, 28, 30), 6)
        with recompile.enforce_zero_compiles(
                "mixed serve (chunked+tiers+preempt+failover)") as cw:
            for p in prompts:
                eng.add_request(p, int(rng.randint(2, 9)))
            eng.run_segment(16, prefix_cache=pc)
            # preempt a live slot mid-serve and requeue it (resume
            # re-prefills prompt + generated tokens through the cache)
            for s in range(eng.slots):
                if eng._active[s] is not None and eng.can_preempt(s):
                    eng._queue.insert(0, eng.preempt_slot(s, pc))
                    break
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(16, prefix_cache=pc)
            # failover: kill the replica with work in flight, resume
            # the orphans on the recovered engine
            for p in prompts[:2]:
                eng.add_request(p, 4)
            eng.dispatch_segment(16, prefix_cache=pc)
            orphans = eng.abort()
            assert orphans
            eng._queue.extend(orphans)
            # repeats of the same prompts exercise the host tier's
            # spill/restore transfers inside the budget too
            for p in prompts:
                eng.add_request(p, 3)
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(16, prefix_cache=pc)
        return eng, env, aot, cw

    def test_zero_post_warmup_compiles(self, served):
        _, _, _, cw = served
        assert cw.compiles == 0

    def test_observed_keys_all_enumerated(self, served):
        eng, env, _, _ = served
        enumerated = frozenset().union(*eng.program_space(env).values())
        assert set(eng.prog_key_hits) <= enumerated
        assert set(eng._progs) <= enumerated
        rep = coverage.coverage_report(eng, env)
        assert rep.ok, rep.format()
        assert rep.unenumerated == []

    def test_requests_all_finished_tokens_nonempty(self, served):
        eng, _, _, _ = served
        done = eng.collect_finished()
        assert done and all(len(t) > 0 for t in done.values())

    def test_aot_report_attributes_per_family(self, served):
        eng, _, aot, _ = served
        assert set(aot) == {"cseg"}
        assert aot["cseg"]["keys"] == 2      # widths 16 and 32, C=8
        assert eng.aot_warmup_s is not None and eng.aot_warmup_s > 0
        assert all(s >= 0 for s in eng.aot_key_seconds.values())

    def test_aot_report_carries_the_programs_temporaries(self, served):
        """``temp_bytes`` is the family's largest
        ``memory_analysis().temp_size_in_bytes`` and ``pool_bytes`` the
        planes as they lie: what "holds the pool once" is read from."""
        eng, _, aot, _ = served
        assert set(eng.aot_key_temp_bytes) == set(eng.aot_key_seconds)
        assert aot["cseg"]["temp_bytes"] == max(
            eng.aot_key_temp_bytes.values()) > 0
        pool = eng.pager.pool
        assert eng.pool_bytes == {n: a.size * a.dtype.itemsize
                                  for n, a in pool.items()}
        assert set(eng.pool_bytes) == {"k", "v"}

    def test_cold_start_gauge_splits(self, served):
        """cold_start_s = aot_warmup_s + first_token_s once warmed —
        the autoscaler's scale-up latency is a measured pair, not an
        XLA lottery."""
        eng, _, _, _ = served
        assert eng.cold_start_s is not None
        assert eng.first_token_s == pytest.approx(
            eng.cold_start_s - eng.aot_warmup_s)
        from paddle_tpu import observability as obs

        snap = obs.metrics.registry().snapshot()
        gauges = snap["gauges"]
        assert "serving.aot_warmup_s" in gauges
        assert "serving.first_token_s" in gauges
        assert "serving.program_space_keys" in gauges

    def test_fleet_replicas_share_warmup_compiles(self, tiny):
        """The fleet amortisation claim (SCALING §3o): replica 0 pays
        the ladder's XLA compiles, an identical-geometry replica's
        warmup hits _SHARED_PROGS and compiles NOTHING."""
        cfg, params = tiny
        from paddle_tpu.inference.fleet import FleetRouter

        def mk():
            return ServingEngine(cfg, params, slots=2, max_len=96,
                                 chunk=8, prompt_buckets=(16, 32),
                                 paged=True, page_size=16, num_pages=13,
                                 chunked_prefill=True,
                                 prefill_chunks=(8, 16))

        router = FleetRouter([mk(), mk()], seg_steps=16)
        env = WorkloadEnvelope(max_prompt=30, max_new_tokens=8,
                               seg_steps=(16,), prefix_block=16)
        e0, e1 = (r.engine for r in router._replicas)
        e0.aot_warmup(env)
        with recompile.CompileWatch() as cw:
            e1.aot_warmup(env)
        assert cw.compiles == 0
        assert set(e0._progs) == set(e1._progs)
        rep = router.aot_warmup(env)    # the router-level sweep
        assert set(rep) == {0, 1}
        assert all(r.engine.aot_warmup_s is not None
                   for r in router._replicas)

    def test_spec_family_zero_post_warmup_compiles(self, tiny):
        cfg, params = tiny
        # geometry matches tests/test_spec_sampling.py's module engine,
        # so this compile is shared suite-wide via _SHARED_PROGS
        eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=4,
                            prompt_buckets=(16,), paged=True,
                            page_size=16, speculative=3)
        env = WorkloadEnvelope(max_prompt=12, max_new_tokens=8,
                               seg_steps=(16,))
        eng.aot_warmup(env)
        with recompile.enforce_zero_compiles("spec serve") as cw:
            for p in _prompts(cfg, 11, (12,), 4):
                eng.add_request(p, 8)
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(16)
        assert cw.compiles == 0
        assert set(eng.prog_key_hits) == {("sseg", 4, 3, 16)}
        rep = coverage.coverage_report(eng, env)
        assert rep.ok and rep.unreached == []


class TestEscapesFlagged:
    def test_envelope_escaping_width_is_unenumerated(self, tiny):
        """A seg_steps value outside the declared envelope produces a
        key the enumeration does not contain — the differential flags
        it as an unenumerated compile (gate FAIL), exactly the
        mid-serve-compile class."""
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=8,
                            prompt_buckets=(16, 32), paged=True,
                            page_size=16, num_pages=13,
                            chunked_prefill=True, prefill_chunks=(8, 16))
        declared = WorkloadEnvelope(max_prompt=30, max_new_tokens=8,
                                    seg_steps=(8,), prefix_block=16)
        eng.aot_warmup(declared)
        for p in _prompts(cfg, 3, (12,), 2):
            eng.add_request(p, 4)
        # the serve loop runs 16-step segments the envelope never
        # declared (the executable is already shared process-wide, but
        # the KEY escapes the enumeration — which is the point)
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(16)
        rep = coverage.coverage_report(eng, declared)
        assert not rep.ok
        assert ("cseg", 2, 32, 8, 16) in rep.unenumerated

    def test_unused_ladder_entry_is_dead_weight(self, tiny):
        """Over-declared envelopes get billed: an enumerated-but-unused
        key shows up as dead weight with its compile seconds."""
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=8,
                            prompt_buckets=(16, 32), paged=True,
                            page_size=16, num_pages=13,
                            chunked_prefill=True, prefill_chunks=(8, 16))
        env = WorkloadEnvelope(max_prompt=30, max_new_tokens=8,
                               seg_steps=(8, 16), prefix_block=16)
        eng.aot_warmup(env)
        for p in _prompts(cfg, 5, (12,), 2):
            eng.add_request(p, 4)
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(16)       # only the 16-step rung is used
        rep = coverage.coverage_report(eng, env)
        assert rep.ok                  # dead weight warns, never fails
        dead = {k for k, _ in rep.unreached}
        assert ("cseg", 2, 16, 8, 8) in dead


class TestPersistentCacheInterplay:
    def test_warm_restart_skips_recompiles_enumeration_unchanged(
            self, tiny, tmp_path, monkeypatch, compile_cache_restored):
        """r15 interplay: aot_warmup through a populated persistent
        cache deserialises instead of recompiling — a restarted replica
        pays a fraction of the cold warmup's backend compiles — and the
        enumeration is a pure function of config + envelope (identical
        across the restart)."""
        import paddle_tpu as paddle
        from paddle_tpu.inference import serving as S

        cfg, params = tiny
        saved = dict(S._SHARED_PROGS)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        try:
            paddle.jit.enable_persistent_cache()
            S._SHARED_PROGS.clear()

            def build():
                return ServingEngine(cfg, params, slots=2, max_len=32,
                                     chunk=4, prompt_buckets=(16,),
                                     paged=True, page_size=16)

            env = WorkloadEnvelope(max_prompt=12, max_new_tokens=4,
                                   seg_steps=(8,))
            e1 = build()
            space1 = e1.program_space(env)
            with recompile.CompileWatch() as cold:
                e1.aot_warmup(env)
            assert cold.compiles > 0      # real XLA work into the disk

            S._SHARED_PROGS.clear()       # simulated process restart
            e2 = build()
            assert e2.program_space(env) == space1
            import jax.monitoring as mon

            hits = [0]

            def _on_event(event, **kw):
                if event == "/jax/compilation_cache/cache_hits":
                    hits[0] += 1

            mon.register_event_listener(_on_event)
            try:
                with recompile.CompileWatch() as warm:
                    e2.aot_warmup(env)
            finally:
                mon.unregister_event_listener(_on_event)
            # the segment program (the 2.5 s class) comes off disk: the
            # warm restart hits the persistent cache instead of paying
            # XLA again (at most stray eager singletons still compile)
            assert hits[0] >= 1
            assert warm.compiles <= cold.compiles
        finally:
            S._SHARED_PROGS.clear()
            S._SHARED_PROGS.update(saved)
