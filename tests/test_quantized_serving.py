"""Quantized serving (r21 tentpole, ISSUE 16): int8/fp8 weight
streaming + per-page KV quantization behind the shadow/canary quality
bar.

Pins the subsystem's contracts:

* numeric recipe — per-out-channel weight quantization round-trips
  within the absmax step bound; re-quantizing a quantized tree is a
  loud ValueError;
* in-kernel dequant parity (FORCE_INTERPRET on CPU) — the Pallas
  ``quant_matmul`` and the scale-fed ``ragged_decode_attention`` match
  the dense dequantize-then-compute formulation that stays the
  CPU/mesh fallback;
* the quantized paged engine — mode validation, token determinism
  within one dtype, matched-prefix token agreement vs bf16 above the
  floor (bit-identity across dtypes is NOT the bar — SCALING §3p);
* per-page scale planes ride the page machinery — COW/prefix-hit and
  host-tier spill→restore serve token-identically to an uncached
  quantized serve, and ``page_bytes`` bills the true narrow bytes;
* SyncAudit over the quantized loop — one event fetch per segment,
  zero flagged;
* program space — the ``qpseg`` dtype rung enumerates, AOT-warms, and
  serves with zero post-warmup compiles;
* a journaled quantized serve replays bit-exactly (the header carries
  ``quant``; replay re-quantizes the same fp tree).

Suite-time contract: rides the session ``tiny_llama`` fixture and the
test_kv_tiers engine geometries; serves are short (gen <= 12) and the
heavier spill serve is module-scoped.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops.pallas.decode_attention as da
import paddle_tpu.ops.pallas.tick_fusion as tf
from paddle_tpu.inference.kv_tiers import HostTier, page_bytes
from paddle_tpu.inference.prefix_cache import PagedPrefixCache
from paddle_tpu.inference.scheduler import Arrival, OnlineScheduler
from paddle_tpu.inference.serving import ServingEngine, WorkloadEnvelope
from paddle_tpu.parallel import set_mesh
from paddle_tpu.quantization.serving import (
    QUANT_CODES, dequantize_weight, quant_dtype, quantize_kv_rows,
    quantize_llama_params, quantize_weight, quantized_weight_keys)


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    set_mesh(None)
    return tiny_llama


def _mk(cfg, params, quant="int8", num_pages=24, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32, 64))
    return ServingEngine(cfg, params, paged=True, page_size=16,
                         num_pages=num_pages, quant=quant, **kw)


def _trace(cfg, seed=3, n=4, plen=16, gen=8):
    rng = np.random.RandomState(seed)
    return [Arrival(0.0, rng.randint(0, cfg.vocab_size, (plen,))
                    .astype(np.int32), gen) for _ in range(n)]


def _serve(eng, arr, seg_steps=8, pc=None):
    sch = OnlineScheduler(eng, seg_steps=seg_steps, prefix_cache=pc)
    rep = sch.serve(arr)
    out = sch.results()
    return rep, [out[k] for k in sorted(out)]


# ---------------------------------------------------------------------------
# numeric recipe
# ---------------------------------------------------------------------------


class TestRecipe:
    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_weight_roundtrip_error_bound(self, mode):
        """Dequantized weights sit within the per-channel step size of
        the fp32 original (int8: half a step after rounding; fp8 keeps
        a relative-error bound from e4m3's 3 mantissa bits)."""
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 48),
                              jnp.float32)
        q, s = quantize_weight(w, mode)
        assert q.dtype == quant_dtype(mode) and s.shape == (48,)
        err = np.abs(np.asarray(dequantize_weight(q, s)) - np.asarray(w))
        step = np.asarray(s)[None, :]
        bound = 0.51 * step if mode == "int8" else 32.0 * step
        assert (err <= bound).all(), float(err.max())

    def test_kv_rows_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 2, 8),
                              jnp.float32)
        q, s = quantize_kv_rows(x, jnp.int8)
        assert q.shape == x.shape and s.shape == (2, 5)
        back = np.asarray(q, np.float32) * np.asarray(s)[..., None, None]
        assert np.abs(back - np.asarray(x)).max() <= \
            0.51 * float(np.asarray(s).max())

    def test_double_quantize_refused(self, tiny):
        cfg, params = tiny
        qp = quantize_llama_params(params, cfg, "int8")
        for name in quantized_weight_keys(cfg):
            assert qp[name].dtype == jnp.int8
            assert name + "_scale" in qp
        with pytest.raises(ValueError, match="double-quantize"):
            quantize_llama_params(qp, cfg, "int8")


# ---------------------------------------------------------------------------
# in-kernel dequant parity (interpret mode = the exact kernel path)
# ---------------------------------------------------------------------------


class TestKernelParity:
    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_quant_matmul_matches_dense(self, mode):
        w = jax.random.normal(jax.random.PRNGKey(3), (64, 256),
                              jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 64),
                              jnp.float32)
        q, s = quantize_weight(w, mode)
        got = tf.quant_matmul(x, q, s, interpret=True)
        ref = x @ dequantize_weight(q, s)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_quant_matmul_active_gate(self, monkeypatch):
        set_mesh(None)
        assert not tf.quant_matmul_active(64, 256)   # CPU, no force
        monkeypatch.setattr(tf, "FORCE_INTERPRET", True)
        assert tf.quant_matmul_active(64, 256)
        assert not tf.quant_matmul_active(63, 256)   # contraction align
        assert not tf.quant_matmul_active(64, 100)   # no lane block

    def test_decode_attention_scales_match_predequantized(self):
        B, S, H, Hkv, D = 2, 128, 4, 2, 128
        kc = jax.random.normal(jax.random.PRNGKey(5), (B, S, Hkv, D),
                               jnp.float32)
        vc = jax.random.normal(jax.random.PRNGKey(6), (B, S, Hkv, D),
                               jnp.float32)
        q = jax.random.normal(jax.random.PRNGKey(7), (B, H, D),
                              jnp.float32)
        pos = jnp.array([5, 97], jnp.int32)
        kq, ks = quantize_kv_rows(kc, jnp.int8)
        vq, vs = quantize_kv_rows(vc, jnp.int8)
        got = da.ragged_decode_attention(q, kq, vq, pos, interpret=True,
                                         k_scale=ks, v_scale=vs)
        kd = kq.astype(jnp.float32) * ks[..., None, None]
        vd = vq.astype(jnp.float32) * vs[..., None, None]
        ref = da.ragged_decode_attention(q, kd, vd, pos, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the quantized paged engine
# ---------------------------------------------------------------------------


class TestQuantEngine:
    def test_mode_and_combo_validation(self, tiny):
        cfg, params = tiny
        with pytest.raises(ValueError, match="quant"):
            _mk(cfg, params, quant="int4")
        with pytest.raises(ValueError, match="quant"):
            _mk(cfg, params, speculative=2)

    def test_pool_planes_and_true_page_bytes(self, tiny):
        """The quantized pool carries int8 K/V planes plus fp32
        per-page-row scale planes, and page_bytes bills the TRUE
        narrow bytes (the tier budgets + §3n arithmetic read this)."""
        cfg, params = tiny
        eng_q = _mk(cfg, params)
        eng_b = _mk(cfg, params, quant=None)
        assert set(eng_q.pager.pool) == {"k", "v", "ks", "vs"}
        assert eng_q.pager.pool["k"].dtype == jnp.int8
        assert eng_q.pager.pool["ks"].dtype == jnp.float32
        bq, bb = page_bytes(eng_q.pager), page_bytes(eng_b.pager)
        L = cfg.num_layers
        elems = L * 16 * cfg.num_kv_heads * cfg.head_dim
        assert bq == 2 * (elems + L * 16 * 4)   # int8 k/v + fp32 ks/vs
        assert bq < bb

    def test_deterministic_and_matches_bf16_above_floor(self, tiny):
        """Same dtype -> bit-identical serves; across dtypes the
        matched-prefix rate clears the floor (the §3p bar — random-init
        weights are the pessimistic case, so the floor is loose)."""
        from paddle_tpu.observability.quality import compare_pair

        cfg, params = tiny
        arr = _trace(cfg)
        _, out1 = _serve(_mk(cfg, params), arr)
        _, out2 = _serve(_mk(cfg, params), arr)
        assert out1 == out2
        _, outb = _serve(_mk(cfg, params, quant=None), arr)
        matched = compared = 0
        for b, q in zip(outb, out1):
            r = compare_pair(b, q)
            matched += r["tokens_matched"]
            compared += r["compared"]
        assert compared > 0 and matched / compared >= 0.5, \
            (matched, compared)

    def test_fp8_serves_deterministically(self, tiny):
        cfg, params = tiny
        arr = _trace(cfg, n=2)
        _, out1 = _serve(_mk(cfg, params, quant="fp8"), arr)
        _, out2 = _serve(_mk(cfg, params, quant="fp8"), arr)
        assert out1 == out2
        assert all(len(t) for t in out1)


# ---------------------------------------------------------------------------
# scale planes ride the page machinery: COW / prefix hits / host spill
# ---------------------------------------------------------------------------


class TestQuantPages:
    def test_prefix_hit_and_cow_token_identity(self, tiny):
        """Shared-prefix quantized serve through the paged prefix cache
        (hits + COW on the shared pages) is token-identical to the
        uncached quantized serve."""
        cfg, params = tiny
        rng = np.random.RandomState(11)
        prefix = rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32)
        arr = [Arrival(0.0, np.concatenate(
            [prefix, rng.randint(0, cfg.vocab_size, (8,))
             .astype(np.int32)]), 8) for _ in range(4)]
        _, cold = _serve(_mk(cfg, params), arr)
        eng = _mk(cfg, params)
        pc = PagedPrefixCache(eng.pager, capacity_pages=8)
        _, hit = _serve(eng, arr, pc=pc)
        assert pc.stats()["hits"] > 0
        assert hit == cold

    def test_host_spill_restore_token_identity(self, tiny):
        """Spill-heavy quantized serve through the host tier: the scale
        planes spill/restore with the page bytes and tokens match the
        uncached quantized serve; spilled host bytes are the narrow
        page size."""
        cfg, params = tiny
        rng = np.random.RandomState(12)
        prefs = [rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32)
                 for _ in range(4)]
        arr = [Arrival(0.0, np.concatenate(
            [prefs[i % 4], rng.randint(0, cfg.vocab_size, (8,))
             .astype(np.int32)]), 8) for i in range(8)]
        _, ref = _serve(_mk(cfg, params, num_pages=40), arr)
        eng = _mk(cfg, params, num_pages=11)
        tier = HostTier(eng.pager, capacity_pages=64)
        pc = PagedPrefixCache(eng.pager, capacity_pages=8,
                              host_tier=tier)
        _, out = _serve(eng, arr, pc=pc)
        assert out == ref
        assert pc.spills > 0 and pc.restores > 0
        for ent in tier._host.values():
            assert set(ent) >= {"k", "v", "ks", "vs"}
            assert ent["k"].dtype == np.int8


# ---------------------------------------------------------------------------
# sync audit over the quantized loop
# ---------------------------------------------------------------------------


class TestQuantSyncAudit:
    def test_one_fetch_per_segment_zero_flagged(self, tiny):
        from paddle_tpu.analysis import SyncAudit

        cfg, params = tiny
        arr = _trace(cfg, n=4)
        eng = _mk(cfg, params)
        sch = OnlineScheduler(eng, seg_steps=8)
        sch.serve(arr)                  # warm (compiles outside audit)
        sch.results()
        eng.reset_slots()
        sch._reqs.clear()
        with SyncAudit() as audit:
            audit.phase = "serve"
            rep = sch.serve(arr)
        assert audit.flagged("serve") == [], audit.flagged("serve")
        assert audit.allowed("serve") == {
            "serving.segment_event_fetch": rep.segments}


# ---------------------------------------------------------------------------
# program space: the qpseg dtype rung
# ---------------------------------------------------------------------------


class TestQuantProgramSpace:
    def test_qpseg_enumerates_and_zero_compile_serve(self, tiny):
        """The quantized engine's reachable ladder is the qpseg family
        (dtype axis = the quant code); aot_warmup compiles it and the
        serve afterwards triggers ZERO backend compiles."""
        from paddle_tpu.analysis import coverage, recompile
        from paddle_tpu.inference import serving as _serving
        from paddle_tpu.inference.program_space import PROGRAM_SPACE

        cfg, params = tiny
        arr = _trace(cfg, n=3)
        env = WorkloadEnvelope(max_prompt=16, max_new_tokens=8,
                               seg_steps=(8,), prefix_block=16)
        saved = dict(_serving._SHARED_PROGS)
        try:
            _serving._SHARED_PROGS.clear()
            eng = _mk(cfg, params)
            keys = PROGRAM_SPACE.enumerate(eng, env)
            fams = {k[0] for k in keys}
            assert "qpseg" in fams and "pseg" not in fams
            assert all(k[-1] == QUANT_CODES["int8"] for k in keys
                       if k[0] == "qpseg")
            eng.aot_warmup(env)
            sch = OnlineScheduler(eng, seg_steps=8)
            with recompile.enforce_zero_compiles(
                    "warmed quant serve") as cw:
                sch.serve(arr)
            assert cw.compiles == 0
            assert coverage.coverage_report(eng, env).ok
        finally:
            _serving._SHARED_PROGS.clear()
            _serving._SHARED_PROGS.update(saved)

    def test_dtype_axis_separates_modes(self, tiny):
        """int8 and fp8 engines enumerate DIFFERENT qpseg keys — the
        dtype axis is real, so the AOT ladder can't serve one mode's
        programs to the other."""
        from paddle_tpu.inference.program_space import PROGRAM_SPACE

        cfg, params = tiny
        env = WorkloadEnvelope(max_prompt=16, max_new_tokens=8,
                               seg_steps=(8,), prefix_block=16)
        k8 = PROGRAM_SPACE.enumerate(_mk(cfg, params), env)
        kf = PROGRAM_SPACE.enumerate(_mk(cfg, params, quant="fp8"), env)
        assert k8 and kf and not (set(k8) & set(kf))


# ---------------------------------------------------------------------------
# journaled quantized serve replays bit-exactly
# ---------------------------------------------------------------------------


class TestQuantReplay:
    def test_journal_replay_identical(self, tiny, tmp_path):
        from paddle_tpu.observability import journal as jmod
        from paddle_tpu.observability import replay_serve

        cfg, params = tiny
        arr = _trace(cfg, n=3)
        eng = _mk(cfg, params)
        sch = OnlineScheduler(eng, seg_steps=8)
        jq = jmod.Journal(str(tmp_path))
        jq.params_info = {"prng_seed": 0}
        with jmod.attach(jq):
            sch.serve(arr)
        jq.close()
        res = replay_serve(str(tmp_path), params=params)
        assert res.identical, res.divergence
        assert res.n_decisions > 0
