"""Chip certification for the INFERENCE surface — REAL TPU ONLY
(VERDICT r5 item 6 / weak #6: training was chip-certified, but
``generate()``'s scan program, the engine's segments, and the unrolled-KV
path were only exercised on-chip via benchmarks, never as parity-asserted
tests). Runs in the TPU lane (``benchmarks/tpu_test_lane.py``); the CPU
suite skips it like the other ``*_tpu.py`` files.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="inference chip certification runs on TPU only")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(**kw):
    from paddle_tpu.models import llama

    return llama.LlamaConfig.tiny(max_seq_len=96, **kw)


def _dense(cfg, params, prompt, n):
    from paddle_tpu.models import llama

    out = llama.generate(params, np.asarray(prompt, np.int32)[None], cfg,
                         max_new_tokens=n, max_len=96)
    return [int(t) for t in np.asarray(out)[0]]


def test_generate_greedy_parity_chip_vs_cpu():
    """Greedy prefill + scan-decode on the chip must emit the same tokens
    as the CPU backend (fp32 tiny config: same argmax stream)."""
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    cfg = _tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 12)).astype(np.int32)
    chip = np.asarray(llama.generate(params, jnp.asarray(prompt), cfg,
                                     max_new_tokens=10, max_len=96))
    # CPU reference in a subprocess (the in-process backend is pinned to
    # the chip; re-exec with JAX_PLATFORMS=cpu mirrors conftest)
    code = (
        "import numpy as np, jax, sys;"
        "sys.path.insert(0, {root!r});"
        "from paddle_tpu.models import llama;"
        "from paddle_tpu.parallel import set_mesh;"
        "set_mesh(None);"
        "cfg = llama.LlamaConfig.tiny(max_seq_len=96);"
        "params = llama.init_params(cfg, jax.random.PRNGKey(0));"
        "prompt = np.random.RandomState(0).randint("
        "0, cfg.vocab_size, (2, 12)).astype(np.int32);"
        "out = llama.generate(params, prompt, cfg, max_new_tokens=10,"
        " max_len=96);"
        "print('TOKS', np.asarray(out).tolist())"
    ).format(root=ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("TOKS"))
    cpu = np.asarray(eval(line[5:]))
    np.testing.assert_array_equal(chip, cpu)


def test_run_mixed_lengths_eos_matches_dense():
    """``run()`` on the chip: mixed prompt/generation lengths + EOS
    freeze, token-identical to dense generate()."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    cfg = _tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.RandomState(1)
    reqs = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
            for l, n in [(5, 7), (12, 3), (30, 9), (3, 12), (17, 5)]]
    refs = [_dense(cfg, params, p, n) for p, n in reqs]
    eos = refs[0][1]  # freezes request 0 early
    eng = ServingEngine(cfg, params, slots=2, max_len=96, chunk=4,
                        prompt_buckets=(8, 16, 32), eos_token_id=eos)
    rids = [eng.add_request(p, n) for p, n in reqs]
    out = eng.run()
    for rid, ref in zip(rids, refs):
        want = ref[:ref.index(eos) + 1] if eos in ref else ref
        assert out[rid] == want, (rid, out[rid], want)


def test_online_segments_match_dense():
    """The r7 re-entrant segment path on the chip: requests arriving
    between segments (slots mid-flight) still match dense generate()."""
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    cfg = _tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.RandomState(2)
    wave1 = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
             for l, n in [(5, 9), (12, 6)]]
    wave2 = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
             for l, n in [(20, 4), (7, 10)]]
    eng = ServingEngine(cfg, params, slots=2, max_len=96,
                        prompt_buckets=(8, 16, 32))
    rids1 = [eng.add_request(p, n) for p, n in wave1]
    eng.run_segment(4)
    rids2 = [eng.add_request(p, n) for p, n in wave2]
    while eng._queue or eng.free_slot_count() < eng.slots:
        eng.run_segment(8)
    out = eng.collect_finished()
    for rid, (p, n) in zip(rids1 + rids2, wave1 + wave2):
        assert out[rid] == _dense(cfg, params, p, n)


def test_unrolled_kv_matches_scan_layers_on_chip():
    """scan_layers=False (static-index row-DUS cache writes, the decode
    fast path) vs the layer-scan branch: generate parity AND ragged
    per-slot decode parity, on the chip's numerics."""
    import dataclasses

    from paddle_tpu.models import llama
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    cfg_s = _tiny()
    cfg_u = dataclasses.replace(cfg_s, scan_layers=False)
    params = llama.init_params(cfg_s, jax.random.PRNGKey(3))
    rng = np.random.RandomState(3)
    prompt = jnp.array(rng.randint(0, cfg_s.vocab_size, (2, 10)), jnp.int32)
    o_s = np.asarray(llama.generate(params, prompt, cfg_s,
                                    max_new_tokens=8, max_len=32))
    o_u = np.asarray(llama.generate(params, prompt, cfg_u,
                                    max_new_tokens=8, max_len=32))
    np.testing.assert_array_equal(o_s, o_u)

    outs = []
    for cfg in (cfg_s, cfg_u):
        cache = llama.init_kv_cache(cfg, 2, 32)
        lg, cache = llama.forward_with_cache(params, prompt, cfg, cache,
                                             jnp.int32(0))
        posv = jnp.array([10, 10], jnp.int32)
        l2, cache = llama.forward_with_cache(
            params, jnp.array([[3], [5]], jnp.int32), cfg, cache, posv)
        outs.append((np.asarray(lg), np.asarray(l2),
                     np.asarray(cache["k"])))
    for a, b in zip(*outs):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4)


def test_prefix_cache_hit_matches_cold_on_chip():
    """Shared-prefix admission (suffix-only prefill from reused KV rows)
    must be token-identical to cold admission on the chip."""
    from paddle_tpu.inference.prefix_cache import PagedPrefixCache
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    cfg = _tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(4))
    rng = np.random.RandomState(4)
    prefix = rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32)
    prompts = [np.concatenate(
        [prefix, rng.randint(0, cfg.vocab_size, (6,))]).astype(np.int32)
        for _ in range(3)]

    def serve(cached):
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8, 16, 64))
        pc = (PagedPrefixCache(eng.pager, capacity_pages=128)
              if cached else None)
        rids = [eng.add_request(p, 6) for p in prompts]
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(16, prefix_cache=pc)
        done = eng.collect_finished()
        return [done[r] for r in rids], pc

    cold, _ = serve(False)
    hot, pc = serve(True)
    assert cold == hot
    assert pc.hits >= 2
