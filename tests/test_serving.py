"""Continuous-batching serving engine (VERDICT r1 item 8): greedy engine
output must equal the dense generate() path request-by-request, across
mixed prompt/generation lengths and slot turnover."""

import numpy as np
import pytest

from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import llama
from paddle_tpu.parallel import set_mesh


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    # r12 suite-time satellite: the model build is hoisted to the
    # SESSION-scoped conftest fixture (shared with test_paged_kv /
    # test_fleet_serving); this module-level shim keeps the mesh clear
    # for every consumer here
    set_mesh(None)
    return tiny_llama


def _dense_reference(cfg, params, prompt, n):
    out = llama.generate(params, np.asarray(prompt, np.int32)[None], cfg,
                         max_new_tokens=n, max_len=96)
    return [int(t) for t in np.asarray(out)[0]]


class TestServingEngine:
    def test_matches_dense_generate_mixed_lengths(self, tiny):
        cfg, params = tiny
        rng = np.random.RandomState(0)
        reqs = [
            (rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
            for l, n in [(5, 7), (12, 3), (30, 9), (3, 12), (17, 5),
                         (8, 8), (25, 4)]
        ]
        eng = ServingEngine(cfg, params, slots=3, max_len=96, chunk=4,
                            prompt_buckets=(8, 16, 32))
        rids = [eng.add_request(p, n) for p, n in reqs]
        results = eng.run()
        assert sorted(results) == sorted(rids)
        for rid, (p, n) in zip(rids, reqs):
            ref = _dense_reference(cfg, params, p, n)
            assert results[rid] == ref, (rid, results[rid], ref)

    def test_more_requests_than_slots_all_served(self, tiny):
        cfg, params = tiny
        rng = np.random.RandomState(1)
        eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=8,
                            prompt_buckets=(16,))
        rids = [eng.add_request(
            rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32), 5)
            for _ in range(7)]
        results = eng.run()
        assert sorted(results) == sorted(rids)
        assert all(len(v) == 5 for v in results.values())

    def test_single_token_request(self, tiny):
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8,))
        rid = eng.add_request(np.arange(4, dtype=np.int32), 1)
        results = eng.run()
        ref = _dense_reference(cfg, params, np.arange(4, dtype=np.int32), 1)
        assert results[rid] == ref

    def test_oversized_request_rejected(self, tiny):
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(64,))
        with pytest.raises(ValueError, match="max_len"):
            eng.add_request(np.zeros((60,), np.int32), 64)  # 60+63 > 96


class TestServingEos:
    def test_eos_freezes_slot_early(self, tiny):
        """eos_token_id must stop a request the step EOS is emitted (slot
        frozen in-program) and the tokens must still match the dense path
        truncated at its first EOS."""
        cfg, params = tiny
        p = np.random.RandomState(5).randint(
            0, cfg.vocab_size, (10,)).astype(np.int32)
        # find the greedy continuation and pick its 3rd token as "EOS" so
        # the engine must stop at position 3 of a 10-token budget
        ref = _dense_reference(cfg, params, p, 10)
        eos = ref[2]
        eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=4,
                            prompt_buckets=(16,), eos_token_id=eos)
        rid = eng.add_request(p, 10)
        results = eng.run()
        want = ref[:ref.index(eos) + 1]
        assert results[rid] == want, (results[rid], want)

    def test_mixed_eos_and_full_requests_share_slots(self, tiny):
        """Requests that hit EOS early retire and hand their slot to queued
        requests while non-EOS requests keep decoding — the continuous
        part of continuous batching under early termination."""
        cfg, params = tiny
        rng = np.random.RandomState(9)
        prompts = [rng.randint(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
                   for i in range(5)]
        refs = [_dense_reference(cfg, params, p, 8) for p in prompts]
        # an EOS token that appears early for request 0 only
        eos = refs[0][1]
        eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=4,
                            prompt_buckets=(16,), eos_token_id=eos)
        rids = [eng.add_request(p, 8) for p in prompts]
        results = eng.run()
        assert sorted(results) == sorted(rids)
        for rid, ref in zip(rids, refs):
            if eos in ref:
                want = ref[:ref.index(eos) + 1]
            else:
                want = ref
            assert results[rid] == want, (rid, results[rid], want)


    def test_eos_at_prefill_and_mid_generation(self, tiny):
        """EOS emitted AT the prefill token freezes the slot in the admit
        branch (the host only learns at the segment's fetch), EOS
        mid-generation in a tick: both truncate at the first EOS exactly
        like the dense path, and the freed slots serve the queue."""
        cfg, params = tiny
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
                   for i in range(5)]
        refs = [_dense_reference(cfg, params, p, 8) for p in prompts]
        eos_mid = refs[0][2]      # EOS mid-generation for request 0
        eos_pre = refs[1][0]      # EOS at the PREFILL token of request 1
        for eos in (eos_mid, eos_pre):
            eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=4,
                                prompt_buckets=(16,), eos_token_id=eos)
            rids = [eng.add_request(p, 8) for p in prompts]
            results = eng.run()
            for rid, ref in zip(rids, refs):
                want = ref[:ref.index(eos) + 1] if eos in ref else ref
                assert results[rid] == want, (eos, rid, results[rid], want)
            assert eng.pager.leak_report() == []


class TestSegmentReentry:
    def test_segments_match_dense_with_midflight_arrivals(self, tiny):
        """The re-entrant fused segment (r7): requests added BETWEEN
        segments — i.e. while earlier requests still occupy slots — must
        come out token-identical to dense generate(). This is the
        continuous-batching contract the one-shot drain can't express."""
        cfg, params = tiny
        rng = np.random.RandomState(21)
        wave1 = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
                 for l, n in [(5, 9), (12, 6), (8, 12)]]
        wave2 = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
                 for l, n in [(20, 4), (3, 8), (15, 5), (7, 10)]]
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8, 16, 32))
        rids1 = [eng.add_request(p, n) for p, n in wave1]
        ev = eng.run_segment(5)           # partial: slots still live
        assert ev["steps"] == 5
        rids2 = [eng.add_request(p, n) for p, n in wave2]  # arrive mid-run
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(7)
        out = eng.collect_finished()
        for rid, (p, n) in zip(rids1 + rids2, wave1 + wave2):
            ref = _dense_reference(cfg, params, p, n)
            assert out[rid] == ref, (rid, out[rid], ref)

    def test_segment_eos_freeze_and_reuse(self, tiny):
        """EOS inside a segment frees the slot in-program; a queued
        request must take it over within the SAME segment run."""
        cfg, params = tiny
        rng = np.random.RandomState(23)
        prompts = [rng.randint(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
                   for i in range(4)]
        refs = [_dense_reference(cfg, params, p, 8) for p in prompts]
        eos = refs[0][1]                  # early EOS for request 0 only
        eng = ServingEngine(cfg, params, slots=1, max_len=96,
                            prompt_buckets=(16,), eos_token_id=eos)
        rids = [eng.add_request(p, 8) for p in prompts]
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(24)
        out = eng.collect_finished()
        for rid, ref in zip(rids, refs):
            want = ref[:ref.index(eos) + 1] if eos in ref else ref
            assert out[rid] == want, (rid, out[rid], want)


class TestOnlineScheduler:
    def test_serve_matches_dense_per_request(self, tiny):
        """Scheduler-served output parity under a seeded staggered trace
        (satellite test (ii)): every request == dense generate()."""
        from paddle_tpu.inference.scheduler import (
            OnlineScheduler, staggered_arrivals)

        cfg, params = tiny
        arr = staggered_arrivals(31, 9, 0.02, cfg.vocab_size,
                                 prompt_lens=(5, 11, 23),
                                 gen_lens=(3, 7, 11))
        eng = ServingEngine(cfg, params, slots=3, max_len=96,
                            prompt_buckets=(8, 16, 32))
        sch = OnlineScheduler(eng, seg_steps=6)
        rep = sch.serve(arr)
        out = sch.results()
        assert rep.n_requests == len(arr) == len(out)
        for a, rid in zip(sorted(arr, key=lambda x: x.t), sorted(out)):
            ref = _dense_reference(cfg, params, a.prompt, a.max_new_tokens)
            assert out[rid] == ref, (rid, out[rid], ref)
        # measured telemetry is present and ordered
        for r in rep.per_request:
            assert r["ttft_s"] >= 0 and r["e2e_s"] >= r["ttft_s"]
        assert rep.ticks > 0 and rep.segments > 0

    def test_backpressure_bounded_queue(self, tiny):
        """Admission control: a bounded intake queue defers arrivals
        client-side (counted), yet every request is eventually served."""
        from paddle_tpu.inference.scheduler import (
            OnlineScheduler, staggered_arrivals)

        cfg, params = tiny
        arr = staggered_arrivals(33, 10, 0.0, cfg.vocab_size,
                                 prompt_lens=(6,), gen_lens=(6,))
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8,))
        sch = OnlineScheduler(eng, max_queue=2, seg_steps=4)
        rep = sch.serve(arr)
        assert rep.backpressure_events > 0
        assert rep.n_requests == 10
        assert len(sch.results()) == 10

    def test_segments_emit_profiler_spans(self, tiny, tmp_path):
        """Scheduler telemetry rides the profiler's host-span channel
        (profiler/_hooks): an active Profiler sees one 'serving.segment'
        span per segment, kind='serving'."""
        import paddle_tpu.profiler as profiler
        from paddle_tpu.inference.scheduler import (
            OnlineScheduler, staggered_arrivals)

        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8,))
        sch = OnlineScheduler(eng, seg_steps=4)
        arr = staggered_arrivals(35, 4, 0.0, cfg.vocab_size,
                                 prompt_lens=(6,), gen_lens=(5,))
        p = profiler.Profiler(timer_only=True, log_dir=str(tmp_path))
        p.start()
        rep = sch.serve(arr)
        p.stop()
        spans = [s for s in p._host_spans if s[0] == "serving.segment"]
        assert len(spans) == rep.segments
        assert all(s[1] == "serving" and s[3] > 0 for s in spans)


class TestDecodeKernelLane:
    def test_decode_profile_smoke(self):
        """The serving-lane kernel-selection gate (r6): run
        ``benchmarks/decode_profile.py --smoke`` in-process — asserts the
        ragged decode kernel is selected for the serving decode shape,
        the fused tick epilogue reduces the traced per-tick op count,
        fused/dense numerics agree, and per-slot KV blocks fetched scale
        with pos. A dispatch regression fails HERE, not on the chip."""
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(__file__), "..",
                            "benchmarks", "decode_profile.py")
        spec = importlib.util.spec_from_file_location("_decode_profile",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        ev = mod.smoke()
        assert ev["ops_fused"] < ev["ops_dense"]
        assert ev["kv_rows_read"][0] == ev["block_k"]
        assert max(ev["kv_rows_read"].values()) <= ev["kv_rows_dense"]


class TestUnrolledCachePath:
    def test_unrolled_matches_scan_generate_and_ragged(self, tiny):
        """scan_layers=False routes forward_with_cache through the
        unrolled static-index row-DUS branch (the decode fast path every
        bert_base_equiv benchmark runs); it must match the layer-scan
        branch token-for-token on generate AND on the ragged per-slot
        decode the serving engine uses."""
        import dataclasses

        import jax
        import jax.numpy as jnp

        cfg_s, params = tiny
        cfg_u = dataclasses.replace(cfg_s, scan_layers=False)
        rng = np.random.RandomState(11)
        prompt = jnp.array(rng.randint(0, cfg_s.vocab_size, (2, 10)),
                           jnp.int32)
        o_s = np.asarray(llama.generate(params, prompt, cfg_s,
                                        max_new_tokens=8, max_len=32))
        o_u = np.asarray(llama.generate(params, prompt, cfg_u,
                                        max_new_tokens=8, max_len=32))
        np.testing.assert_array_equal(o_s, o_u)

        caches = [llama.init_kv_cache(c, 2, 32) for c in (cfg_s, cfg_u)]
        outs = []
        for cfg, cache in zip((cfg_s, cfg_u), caches):
            lg, cache = llama.forward_with_cache(params, prompt, cfg,
                                                 cache, jnp.int32(0))
            posv = jnp.array([10, 10], jnp.int32)
            l2, cache = llama.forward_with_cache(
                params, jnp.array([[3], [5]], jnp.int32), cfg, cache, posv)
            outs.append((np.asarray(lg), np.asarray(l2),
                         np.asarray(cache["k"])))
        for a, b in zip(*outs):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)
