"""Speculative + sampled decoding (r15, ISSUE 10).

Covers the four contracts the tentpole ships on:

* **Sampling filters** — top-k / top-p mass truncation of
  ``llama.sample_filter_logits`` against an independent numpy
  reference on synthetic logits (property tests, no model).
* **In-program sampling** — per-slot seed isolation (two slots, same
  prompt, different seeds diverge; same seeds replay identically) and
  greedy == temperature-0 parity, all through the serving engine's
  compiled segment programs.
* **Speculative decoding** — greedy token identity vs the
  non-speculative engine (plain + chunked + EOS), the per-request
  accepted-length ledger, and the SyncAudit over the speculative serve
  loop: flagged == [] and exactly ONE allowed event fetch per segment.
* **Acceptance-aware SLO estimates** — the scheduler's deadline /
  retry_after arithmetic divides by the engine's measured acceptance
  EWMA so speculative serves don't over-shed.

Suite-cost discipline (the tier-1 budget is already past the driver's
line): ONE engine geometry module-wide — every engine shares (slots=4,
max_len=64, page=16, bucket 16, chunk=4), so the process-wide program
cache compiles each segment shape once — and generations stay short.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    return tiny_llama


def _engine(cfg, params, **kw):
    from paddle_tpu.inference.serving import ServingEngine

    base = dict(slots=4, max_len=64, chunk=4, prompt_buckets=(16,),
                paged=True, page_size=16)
    base.update(kw)
    return ServingEngine(cfg, params, **base)


def _serve(cfg, params, prompts, gen=8, **kw):
    eng = _engine(cfg, params, **kw)
    for p in prompts:
        eng.add_request(p, gen)
    return eng, eng.run()


@pytest.fixture(scope="module")
def prompts(tiny):
    cfg, _ = tiny
    rng = np.random.RandomState(11)
    return [rng.randint(0, cfg.vocab_size, (12,)).astype(np.int32)
            for _ in range(4)]


# ---------------------------------------------------------------------------
# sampling filters vs numpy reference (no model)
# ---------------------------------------------------------------------------


class TestSamplingFilters:
    def _np_topk_support(self, row, k):
        order = np.argsort(-row, kind="stable")
        kth = row[order[k - 1]]
        return row >= kth          # ties at the k-th value all survive

    def _np_topp_support(self, row, temp, p):
        z = row / temp
        probs = np.exp(z - z.max())
        probs = probs / probs.sum()
        order = np.argsort(-z, kind="stable")
        cum = np.cumsum(probs[order])
        # keep the smallest prefix whose mass BEFORE the token is < p
        # (the top token always survives) — the jax rule, re-derived
        keep_sorted = np.concatenate([[True], cum[:-1] < p])
        cutoff = z[order[np.nonzero(keep_sorted)[0].max()]]
        return z >= cutoff

    def test_topk_truncates_exactly(self, _seeded):
        from paddle_tpu.models.llama import sample_filter_logits

        rng = np.random.RandomState(3)
        logits = rng.randn(5, 33).astype(np.float32)
        for k in (1, 4, 16):
            filt = np.asarray(sample_filter_logits(
                jnp.asarray(logits), 1.0, top_k=k))
            for b in range(5):
                ref = self._np_topk_support(logits[b], k)
                assert ((filt[b] > -np.inf) == ref).all()
                # survivors keep their temperature-scaled values
                assert np.allclose(filt[b][ref], logits[b][ref])

    def test_topp_mass_truncation(self, _seeded):
        from paddle_tpu.models.llama import sample_filter_logits

        rng = np.random.RandomState(4)
        logits = rng.randn(6, 47).astype(np.float32) * 2.0
        for temp, p in ((1.0, 0.5), (0.7, 0.9), (1.3, 0.2)):
            filt = np.asarray(sample_filter_logits(
                jnp.asarray(logits), temp, top_p=p))
            for b in range(6):
                sup = filt[b] > -np.inf
                ref = self._np_topp_support(logits[b], temp, p)
                assert (sup == ref).all()
                # kept mass reaches p; dropping the weakest survivor
                # would fall below it (minimality of the nucleus)
                z = logits[b] / temp
                probs = np.exp(z - z.max()); probs /= probs.sum()
                assert probs[sup].sum() >= min(p, 1.0) - 1e-6
                if sup.sum() > 1:
                    weakest = np.argmin(np.where(sup, z, np.inf))
                    assert probs[sup].sum() - probs[weakest] < p + 1e-6

    def test_temperature_scales_before_filter(self, _seeded):
        from paddle_tpu.models.llama import sample_filter_logits

        logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
        hot = np.asarray(sample_filter_logits(logits, 2.0))
        assert np.allclose(hot, np.asarray(logits) / 2.0)


# ---------------------------------------------------------------------------
# in-program sampling through the segment programs
# ---------------------------------------------------------------------------


class TestInProgramSampling:
    SAMP = {"temperature": 1.0, "top_k": 16}

    def test_seed_isolation_and_replay(self, tiny, prompts, _seeded):
        cfg, params = tiny
        same = [prompts[0], prompts[0]]
        # two slots, same prompt, different seeds -> streams diverge
        eng = _engine(cfg, params, sampling=self.SAMP)
        eng.add_request(same[0], 8, seed=1)
        eng.add_request(same[1], 8, seed=2)
        out = eng.run()
        assert out[0] != out[1], "different seeds must diverge"
        # same seed, fresh serve -> bit-identical replay
        eng2 = _engine(cfg, params, sampling=self.SAMP)
        eng2.add_request(same[0], 8, seed=1)
        eng2.add_request(same[1], 8, seed=2)
        assert eng2.run() == out
        # same seed on BOTH slots of one serve -> identical streams
        eng3 = _engine(cfg, params, sampling=self.SAMP)
        eng3.add_request(same[0], 8, seed=7)
        eng3.add_request(same[1], 8, seed=7)
        out3 = eng3.run()
        assert out3[0] == out3[1], "same seed + same prompt must replay"

    def test_greedy_equals_temperature_zero(self, tiny, prompts, _seeded):
        cfg, params = tiny
        _, greedy = _serve(cfg, params, prompts)
        _, t0 = _serve(cfg, params, prompts,
                       sampling={"temperature": 0.0, "top_k": 16})
        assert greedy == t0
        # and the temperature-0 engine compiled the argmax program
        # family, not a sampled one (the bit-identity is by construction)
        eng = _engine(cfg, params, sampling={"temperature": 0.0})
        assert eng.sampling is None


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------


class TestSpeculative:
    def test_greedy_token_identity(self, tiny, prompts, _seeded):
        cfg, params = tiny
        eng0, base = _serve(cfg, params, prompts)
        eng1, spec = _serve(cfg, params, prompts, speculative=3)
        assert spec == base, "speculative greedy must be token-identical"
        assert eng1.pager.leak_report() == []
        assert list(eng1._progs) == [("sseg", 4, 3, 16)]

    def test_chunked_compose_and_eos(self, tiny, prompts, _seeded):
        cfg, params = tiny
        _, base = _serve(cfg, params, prompts)
        _, spec = _serve(cfg, params, prompts, speculative=3,
                         chunked_prefill=True, prefill_chunks=(8,))
        assert spec == base
        # EOS freezing inside a multi-token verify tick: truncation
        # matches the non-speculative engine's
        eos = base[0][2]
        _, b_eos = _serve(cfg, params, prompts, eos_token_id=eos)
        _, s_eos = _serve(cfg, params, prompts, speculative=3,
                          eos_token_id=eos)
        assert s_eos == b_eos
        # truncation at the first EOS occurrence, derived from the
        # unconstrained stream
        want = base[0].index(eos) + 1 if eos in base[0] else len(base[0])
        assert len(b_eos[0]) == want

    def test_accepted_length_ledger(self, tiny, prompts, _seeded):
        cfg, params = tiny
        eng = _engine(cfg, params, speculative=3)
        for p in prompts:
            eng.add_request(p, 8)
        reqs = list(eng._queue)
        eng.run()
        for r in reqs:
            assert r.spec_proposed > 0
            assert 0 <= r.spec_accepted <= r.spec_proposed
        assert eng.spec_accept_ewma >= 1.0

    def test_spec_serve_loop_sync_audit(self, tiny, prompts, _seeded):
        """ISSUE 10 acceptance: SyncAudit over the speculative serve
        loop — zero flagged syncs, exactly one allowed event fetch per
        segment (the acceptance log rides that same fetch)."""
        from paddle_tpu.analysis import syncs
        from paddle_tpu.inference.scheduler import (OnlineScheduler,
                                                    staggered_arrivals)

        cfg, params = tiny
        eng = _engine(cfg, params, speculative=3)
        sched = OnlineScheduler(eng, seg_steps=16)
        arrivals = staggered_arrivals(5, 6, 0.01, cfg.vocab_size,
                                      prompt_lens=(8, 12),
                                      gen_lens=(4, 6))
        sched.serve(arrivals)          # warm: compiles + first fetches
        eng.reset_slots()
        sched._reqs.clear()
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            report = sched.serve(arrivals)
        assert report.n_requests == 6
        flagged = sa.flagged("replay")
        assert flagged == [], [f"{e.kind}@{e.site}" for e in flagged]
        allowed = sa.allowed("replay")
        assert set(allowed) == {"serving.segment_event_fetch"}
        assert allowed["serving.segment_event_fetch"] == report.segments


# ---------------------------------------------------------------------------
# acceptance-aware SLO estimates (the small-fix satellite)
# ---------------------------------------------------------------------------


class TestAcceptanceAwareSLO:
    def test_min_service_divides_by_acceptance(self, tiny, prompts):
        from paddle_tpu.inference.scheduler import SLOScheduler
        from paddle_tpu.inference.serving import Request

        cfg, params = tiny
        eng = _engine(cfg, params, speculative=3)
        sch = SLOScheduler(eng, seg_steps=16)
        sch._per_tick_s = 0.01
        r = Request(0, prompts[0], 40)
        eng.spec_accept_ewma = 1.0
        base = sch._min_service_s(r)
        eng.spec_accept_ewma = 2.5
        assert sch._min_service_s(r) == pytest.approx(base / 2.5)
        # non-speculative engines keep the per-token estimate untouched
        eng_p = _engine(cfg, params)
        sch_p = SLOScheduler(eng_p, seg_steps=16)
        sch_p._per_token_s = 0.01
        assert sch_p._min_service_s(r) == pytest.approx(40 * 0.01)

    def test_retry_after_fallback_scales(self, tiny):
        from paddle_tpu.inference.scheduler import OnlineScheduler

        cfg, params = tiny
        eng = _engine(cfg, params, speculative=3)
        sch = OnlineScheduler(eng, seg_steps=16)
        eng.spec_accept_ewma = 2.0
        assert sch.retry_after_hint(0.0) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# persistent compile cache knob (ROADMAP item 5 satellite)
# ---------------------------------------------------------------------------


class TestPersistentCompileCache:
    def test_knob_writes_cache_entries(self, tmp_path, monkeypatch, _seeded,
                                       compile_cache_restored):
        """The directory comes from JAX_COMPILATION_CACHE_DIR."""
        import paddle_tpu as paddle

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
        d = paddle.jit.enable_persistent_cache()
        assert d == str(tmp_path / "cc")
        assert paddle.jit.persistent_cache_dir() == d
        f = jax.jit(lambda x: x * 3 + 1)
        f(jnp.ones((37,)))        # odd shape: certainly uncached
        import os
        assert os.listdir(d), "no persistent cache entries written"

    def test_knob_without_env_uses_the_checkout_dir(
            self, monkeypatch, compile_cache_restored):
        """No directory named from outside: one fixed path inside the
        checkout, never a temporary or per-process name (the path is
        part of the cache's key)."""
        import os

        import paddle_tpu as paddle

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        d = paddle.jit.enable_persistent_cache()
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(paddle.__file__)))
        assert d == os.path.join(root, ".jax_cache")
        assert paddle.jit.enable_persistent_cache() == d  # stable
