"""Program auditor (ISSUE 4): seeded known-bad fixtures per pass — each
hazard class the analyzer exists to catch is reconstructed in miniature
and must be FLAGGED (zero false negatives on this corpus), with a clean
twin asserting no false positive — plus the tier-1 budget gate over the
four canonical programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import budgets, hlo, recompile, syncs


# ---------------------------------------------------------------------------
# pass 1: host-sync detector
# ---------------------------------------------------------------------------


class TestHostSyncDetector:
    def test_hidden_bool_sync_flagged(self):
        """The GradScaler bug class: a per-iteration ``bool()`` on a
        device value inside a host loop."""
        x = paddle.to_tensor(np.ones(8, np.float32))
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            for _ in range(3):
                if x.sum() > 0:        # hidden device->host sync
                    pass
        flagged = sa.flagged("replay")
        assert len(flagged) == 3
        assert flagged[0].kind == "tensor.bool"
        assert "test_analysis.py" in flagged[0].site

    def test_item_and_numpy_flagged(self):
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            _ = x.numpy()
            _ = float(x.sum())
        kinds = [e.kind for e in sa.flagged("replay")]
        assert "tensor.numpy" in kinds and "tensor.float" in kinds

    def test_raw_array_and_device_get_flagged(self):
        """Syncs that bypass the framework Tensor (serving's event fetch
        pattern) are still seen via the jax-level patches."""
        v = jnp.arange(8)
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            _ = int(v[0])
            _ = jax.device_get(v)
        kinds = {e.kind for e in sa.flagged("replay")}
        assert "device_get" in kinds
        assert any(k.startswith("array.") for k in kinds)

    def test_allowed_sync_not_flagged(self):
        x = paddle.to_tensor(np.ones(4, np.float32))
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            with syncs.allowed_sync("test.intended_fetch"):
                _ = float(x.sum())
        assert sa.flagged("replay") == []
        assert sa.allowed("replay") == {"test.intended_fetch": 1}

    def test_clean_device_loop_negative(self):
        """A pure device loop (no coercion) records nothing."""
        f = jax.jit(lambda a: a * 2 + 1)
        v = jnp.ones(16)
        f(v)  # warm outside the audit
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            for _ in range(4):
                v = f(v)
        assert sa.flagged("replay") == []

    def test_one_coercion_one_event(self):
        """bool() -> item() -> __array__ nests: exactly ONE event."""
        x = paddle.to_tensor(np.ones((), np.float32))
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            bool(x)
        assert len(sa.events) == 1

    def test_patches_removed_after_audit(self):
        import jax as j

        with syncs.SyncAudit():
            pass
        assert not syncs._ORIG  # originals restored
        assert j.device_get.__module__ != "paddle_tpu.analysis.syncs"

    def test_grad_scaler_single_allowed_sync(self):
        """The r8 fix, enforced: unscale_ makes exactly ONE allowed
        finite-check sync for the whole parameter list — not one bool()
        per parameter."""
        params = [paddle.nn.Parameter(jnp.ones((8, 8), jnp.float32))
                  for _ in range(12)]
        opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                        parameters=params)
        scaler = paddle.amp.GradScaler(init_loss_scaling=2.0)
        for p in params:
            p.grad = paddle.to_tensor(np.ones((8, 8), np.float32))
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            scaler.unscale_(opt)
        assert sa.flagged("replay") == []
        assert sa.allowed("replay") == {"amp.grad_scaler.finite_check": 1}


# ---------------------------------------------------------------------------
# pass 2: recompile-hazard lint
# ---------------------------------------------------------------------------


class TestRecompileLint:
    def test_unbucketed_shape_fn_flagged(self):
        """A jit fn replayed over free-floating widths compiles once per
        width — the 2.5 s mid-serve class."""

        @paddle.jit.to_static
        def f(x):
            return x * 2

        with recompile.CompileWatch() as cw:
            for w in (3, 5, 7, 9, 11, 13):   # unbucketed dynamic dim
                f(paddle.to_tensor(np.ones((w,), np.float32)))
        assert cw.compiles >= 6
        lint = recompile.lint_cache_keys(**{
            "name": "fixture", "keys": f.cache_info()["keys"]})
        assert lint.hazard
        assert lint.n_shape_variants == 6
        assert "unbucketed" in lint.detail

    def test_bucketed_fn_negative(self):
        """Bucketed replay (two widths, many calls) stays under the
        variant bound and a warm replay compiles nothing."""

        @paddle.jit.to_static
        def g(x):
            return x + 1

        for w in (8, 16, 8, 16, 8, 16):
            g(paddle.to_tensor(np.ones((w,), np.float32)))
        with recompile.CompileWatch() as cw:
            for w in (8, 16, 8, 16):
                g(paddle.to_tensor(np.ones((w,), np.float32)))
        assert cw.compiles == 0
        lint = recompile.lint_cache_keys("fixture",
                                         g.cache_info()["keys"])
        assert not lint.hazard

    def test_live_cache_registry_sees_programs(self):
        @paddle.jit.to_static
        def h(x):
            return x - 1

        h(paddle.to_tensor(np.ones((4,), np.float32)))
        names = [r.name for r in recompile.live_cache_report()]
        assert any(n.startswith("to_static:") for n in names)


# ---------------------------------------------------------------------------
# pass 3: relayout accounting
# ---------------------------------------------------------------------------


class TestRelayoutAccounting:
    def test_stack_unstack_relayout_flagged(self):
        """The r8 ledger fixture: transpose forced to materialise (a
        concatenate consumes both orientations)."""

        def f(a):
            return jnp.concatenate([a.T, a], 0)

        txt = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
        inv = hlo.relayout_inventory(txt)
        assert any(e.op == "transpose" for e in inv)
        # 64x64 f32 = 16 KiB transposed (+ the layout-restoring copy)
        assert hlo.relayout_bytes(txt) >= 16384

    def test_elementwise_program_negative(self):
        txt = jax.jit(lambda a: a * 2 + 1).lower(
            jnp.ones((128, 128))).compile().as_text()
        assert hlo.relayout_bytes(txt) == 0

    def test_pack_class_counted_outside_fusions(self):
        inv = hlo.relayout_inventory(
            "ENTRY %main (p0: f32[4,8]) -> f32[8,8] {\n"
            "  %p0 = f32[4,8]{1,0} parameter(0)\n"
            "  ROOT %concatenate.1 = f32[8,8]{1,0} concatenate("
            "f32[4,8]{1,0} %p0, f32[4,8]{1,0} %p0), dimensions={0}\n"
            "}\n")
        assert [e.klass for e in inv] == ["pack"]
        assert inv[0].bytes == 8 * 8 * 4


# ---------------------------------------------------------------------------
# pass 4: donation / aliasing audit
# ---------------------------------------------------------------------------


class TestDonationAudit:
    def test_undonated_buffer_flagged(self):
        """A large param updated without donation: HBM holds input and
        output copies."""
        f = jax.jit(lambda a: a + 1)          # no donate_argnums
        txt = f.lower(jnp.ones((512, 512))).compile().as_text()
        rep = hlo.donation_report(txt, threshold=1 << 18)
        assert len(rep.large_undonated) == 1
        assert rep.large_undonated[0].bytes == 512 * 512 * 4

    def test_donated_buffer_negative(self):
        f = jax.jit(lambda a: a + 1, donate_argnums=(0,))
        txt = f.lower(jnp.ones((512, 512))).compile().as_text()
        rep = hlo.donation_report(txt, threshold=1 << 18)
        assert rep.large_undonated == []
        assert rep.donated_bytes == 512 * 512 * 4

    def test_expected_undonated_excused(self):
        # the installed jax names an HLO parameter after the Python
        # argument it came from ("weights.1")
        f = jax.jit(lambda weights: weights + 1)
        txt = f.lower(jnp.ones((512, 512))).compile().as_text()
        rep = hlo.donation_report(txt, threshold=1 << 18,
                                  expected_undonated=("weights",))
        assert rep.large_undonated == []


# ---------------------------------------------------------------------------
# pass 5: collective / mesh audit
# ---------------------------------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-device virtual mesh")
class TestCollectiveAudit:
    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                    ("dp", "mp"))

    def test_matched_axis_collective_negative(self):
        from functools import partial

        from paddle_tpu.parallel.mesh import shard_map_compat as smap

        mesh = self._mesh()
        f = partial(jax.lax.psum, axis_name="mp")
        g = smap(f, mesh=mesh, in_specs=jax.sharding.PartitionSpec("mp"),
                 out_specs=jax.sharding.PartitionSpec())
        txt = jax.jit(g).lower(jnp.ones((8, 64))).compile().as_text()
        chk = hlo.collective_check(txt, mesh, allowed_axes=("mp",))
        assert chk.inventory, "psum must lower to a collective"
        assert chk.ok

    def test_mismatched_axis_collective_flagged(self):
        """The seeded bad fixture: the program declares its collectives
        ride 'mp' but the psum actually spans 'dp' — the audit must
        refuse the axis set."""
        from functools import partial

        from paddle_tpu.parallel.mesh import shard_map_compat as smap

        mesh = self._mesh()
        f = partial(jax.lax.psum, axis_name="dp")
        g = smap(f, mesh=mesh, in_specs=jax.sharding.PartitionSpec("dp"),
                 out_specs=jax.sharding.PartitionSpec())
        txt = jax.jit(g).lower(jnp.ones((8, 64))).compile().as_text()
        chk = hlo.collective_check(txt, mesh, allowed_axes=("mp",))
        assert chk.disallowed_axes, "dp traffic must violate an mp-only "\
            "declaration"
        assert not chk.ok


# ---------------------------------------------------------------------------
# the canonical programs + budget gate (tier-1 enforcement)
# ---------------------------------------------------------------------------


class TestBudgetGate:
    def test_gate_canonical_programs_within_budget(self):
        """THE tier-1 smoke gate: every canonical program (eight as of
        r15, incl. the mp-sharded tp_serving_segment, the chunked-
        prefill chunked_serving_segment and the speculative
        spec_serving_segment) audits clean against its pinned budget —
        a reintroduced host sync, stray shape compile, new relayout,
        dropped donation, or off-axis collective fails here."""
        from paddle_tpu.analysis.__main__ import main

        assert main(["--gate"]) == 0

    def test_budget_check_catches_regression(self):
        """A synthetic report over budget produces violations (the gate
        actually bites)."""
        rep = analysis.AuditReport(program="paged_serving_segment")
        rep.metrics.update(host_syncs_flagged=1, warm_compiles=2,
                           relayout_bytes=10 << 20, replays=2,
                           host_syncs_allowed={})
        v = budgets.check(rep)
        assert any("host_syncs_flagged" in s for s in v)
        assert any("warm_compiles" in s for s in v)
        assert any("relayout_bytes" in s for s in v)

    def test_unknown_allowed_label_is_violation(self):
        rep = analysis.AuditReport(program="paged_serving_segment")
        rep.metrics.update(host_syncs_flagged=0, warm_compiles=0,
                           replays=2,
                           host_syncs_allowed={"rogue.label": 4})
        v = budgets.check(rep)
        assert any("rogue.label" in s for s in v)


class TestSchedulerAudit:
    def test_online_serve_loop_syncs(self):
        """Satellite 1: the auditor over the ONLINE serve loop. Per
        segment the loop may sync exactly once (the event fetch); the
        host replay, telemetry stamping and queue management must not
        touch the device."""
        from paddle_tpu.inference.scheduler import (OnlineScheduler,
                                                    staggered_arrivals)
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        eng = ServingEngine(cfg, llama.init_params(cfg), slots=4,
                            max_len=64, chunk=8, prompt_buckets=(16,))
        sched = OnlineScheduler(eng, seg_steps=16)
        arrivals = staggered_arrivals(0, 6, 0.01, cfg.vocab_size,
                                      prompt_lens=(8, 12), gen_lens=(4, 6))
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

    def test_engine_cache_keys_bucketed(self):
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        eng = ServingEngine(cfg, llama.init_params(cfg), slots=4,
                            max_len=64, chunk=8, prompt_buckets=(16,))
        for _ in range(2):
            eng.add_request(np.arange(8, dtype=np.int32) % cfg.vocab_size,
                            3)
            eng.run_segment(8)
        lint = recompile.lint_cache_keys(**eng.cache_info())
        assert not lint.hazard
