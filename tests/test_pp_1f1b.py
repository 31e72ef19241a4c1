"""Compiled SPMD 1F1B pipeline schedule (meta_parallel/pp_1f1b.py).

Reference test pattern (SURVEY.md §4 hybrid-parallel correctness): the
pipeline schedule must match the non-pipelined execution numerically — 1F1B
reorders micro-batch work, it does not change the math. We assert loss AND
per-parameter gradient parity against the eager grad-accumulation path, and
pin the dispatch: the compiled program must move activations between stages
with collective-permute (the ICI analog of the reference's P2P send/recv).
"""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.fleet.meta_parallel import (
    LayerDesc,
    PipelineLayer,
    PipelineParallel,
    SharedLayerDesc,
)
from paddle_tpu.parallel import create_hybrid_mesh, set_mesh


def _mse(out, y):
    return paddle.mean((out - y) ** 2)


def _build_pp(num_stages, n_layers, virtual=1, width=8, seed=7):
    paddle.seed(seed)
    descs = []
    for _ in range(n_layers):
        descs.append(LayerDesc(paddle.nn.Linear, width, width))
        descs.append(paddle.nn.functional.tanh)
    pl = PipelineLayer(layers=descs, num_stages=num_stages, loss_fn=_mse,
                       num_virtual_pipeline_stages=virtual)
    strategy = DistributedStrategy()
    strategy.pipeline_configs = {"accumulate_steps": 4}
    return PipelineParallel(pl, None, strategy), pl


def _grads(pl):
    return [None if p.grad is None else np.asarray(p.grad.numpy()).copy()
            for p in pl.parameters() if not p.stop_gradient]


@pytest.fixture
def pp4_mesh():
    mesh = create_hybrid_mesh(dp=2, pp=4)
    yield mesh
    set_mesh(None)


@pytest.fixture
def pp2v2_mesh():
    mesh = create_hybrid_mesh(dp=2, pp=2, devices=jax.devices()[:4])
    yield mesh
    set_mesh(None)


class Test1F1BParity:
    def test_loss_and_grad_parity_vs_grad_accum(self, pp4_mesh):
        pp, pl = _build_pp(num_stages=4, n_layers=8)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
        y = paddle.to_tensor(rng.randn(8, 8).astype("float32"))

        loss_ref = pp.train_batch((x, y))
        g_ref = _grads(pl)
        for p in pl.parameters():
            p.clear_grad()

        loss_1f1b = pp.train_batch((x, y), schedule="1f1b")
        g_new = _grads(pl)

        np.testing.assert_allclose(loss_1f1b.numpy(), loss_ref.numpy(),
                                   rtol=2e-5, atol=1e-7)
        assert len(g_ref) == len(g_new) and len(g_ref) > 0
        for a, b in zip(g_ref, g_new):
            assert (a is None) == (b is None)
            if a is not None:
                np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-6)

    def test_interleaved_virtual_stages_parity(self, pp2v2_mesh):
        # virtual_pp_degree=2 on pp=2: 4 chunks ride 2 devices — the
        # reference's interleaved 1F1B (virtual_pp_degree) on a ring
        pp, pl = _build_pp(num_stages=2, n_layers=8, virtual=2)
        rng = np.random.RandomState(1)
        x = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
        y = paddle.to_tensor(rng.randn(8, 8).astype("float32"))

        loss_ref = pp.train_batch((x, y))
        g_ref = _grads(pl)
        for p in pl.parameters():
            p.clear_grad()

        loss_1f1b = pp.train_batch((x, y), schedule="1f1b")
        g_new = _grads(pl)

        np.testing.assert_allclose(loss_1f1b.numpy(), loss_ref.numpy(),
                                   rtol=2e-5, atol=1e-7)
        for a, b in zip(g_ref, g_new):
            if a is not None:
                np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-6)

    def test_optimizer_step_applies(self, pp4_mesh):
        pp, pl = _build_pp(num_stages=4, n_layers=8, seed=9)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=pl.parameters())
        rng = np.random.RandomState(2)
        x = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
        y = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
        w0 = pl.run_functions[0].weight.numpy().copy()
        loss = pp.train_batch((x, y), optimizer=opt, schedule="1f1b")
        assert np.isfinite(float(loss.numpy()))
        assert not np.allclose(pl.run_functions[0].weight.numpy(), w0)

    def test_hlo_pins_collective_permute(self, pp4_mesh):
        pp, pl = _build_pp(num_stages=4, n_layers=8, seed=5)
        rng = np.random.RandomState(3)
        x = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
        y = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
        pp.train_batch((x, y), schedule="1f1b")
        eng = pp._1f1b_engine
        (key, fn), = eng._cache.items()
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(eng._mesh, PartitionSpec())
        pvals = [p._value for p in eng._params]
        bvals = [b._value for b in eng._buffers]
        kd = jax.device_put(
            jax.random.key_data(jax.random.PRNGKey(0)), rep)
        hlo = fn.lower(pvals, bvals, jax.device_put(x._value, rep),
                       jax.device_put(y._value, rep), kd).compile().as_text()
        assert "collective-permute" in hlo, (
            "1F1B activation transfer must compile to collective-permute")

    def test_llama_pipe_parity_pp_mp_dp(self):
        """Flagship-shaped 1F1B (VERDICT r2 item 3): LLaMA as a
        PipelineLayer with tied embeddings, TP decoder blocks, and the
        causal-LM loss — pp=2 x mp=2 x dp=2 in ONE mesh. The compiled
        schedule runs manual Megatron TP (local-shard matmuls + f/g
        collectives) inside the pp ring; parity vs the eager
        grad-accumulation path covers loss AND every parameter gradient,
        including the shared embedding (grad contributions from both the
        embed and the LM-head use)."""
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.llama_pipe import build_llama_pipe

        mesh = create_hybrid_mesh(pp=2, mp=2, dp=2)
        try:
            paddle.seed(0)
            cfg = LlamaConfig.tiny(num_layers=4)
            pl = build_llama_pipe(cfg, num_stages=2)
            strategy = DistributedStrategy()
            strategy.pipeline_configs = {"accumulate_steps": 4}
            pp = PipelineParallel(pl, None, strategy)

            rng = np.random.RandomState(0)
            x = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (8, 16)).astype("int64"))
            y = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (8, 16)).astype("int64"))

            loss_ref = pp.train_batch((x, y))
            g_ref = _grads(pl)
            for p in pl.parameters():
                p.clear_grad()

            loss_1f1b = pp.train_batch((x, y), schedule="1f1b")
            g_new = _grads(pl)

            np.testing.assert_allclose(loss_1f1b.numpy(), loss_ref.numpy(),
                                       rtol=2e-5, atol=1e-6)
            assert len(g_ref) == len(g_new) and len(g_ref) > 10
            for a, b in zip(g_ref, g_new):
                assert (a is None) == (b is None)
                if a is not None:
                    np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-5)

            # the mp-sharded weights keep their TP layout on the grads
            from jax.sharding import NamedSharding

            qw = pl.run_functions[1].wq.weight
            assert isinstance(qw.grad._value.sharding, NamedSharding)
            assert "mp" in str(qw.grad._value.sharding.spec)
        finally:
            set_mesh(None)

    def test_llama_pipe_parity_pp_mp_sharding(self):
        """ZeRO composition (VERDICT r3 item 2): the flagship PipelineLayer
        on pp=2 x mp=2 x sharding=2 in ONE compiled 1F1B program — params
        cross the shard_map boundary ZeRO-sharded, are all-gathered at
        program entry, grads reduce-scatter back to the shard layout, and
        the sharding ranks carry their own batch rows. Parity vs the eager
        grad-accumulation path covers loss and every parameter gradient."""
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.llama_pipe import build_llama_pipe

        mesh = create_hybrid_mesh(pp=2, mp=2, sharding=2)
        try:
            paddle.seed(11)
            cfg = LlamaConfig.tiny(num_layers=4)
            pl = build_llama_pipe(cfg, num_stages=2)
            strategy = DistributedStrategy()
            strategy.pipeline_configs = {"accumulate_steps": 4}
            pp = PipelineParallel(pl, None, strategy)

            rng = np.random.RandomState(2)
            x = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (8, 16)).astype("int64"))
            y = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (8, 16)).astype("int64"))

            loss_ref = pp.train_batch((x, y))
            g_ref = _grads(pl)
            for p in pl.parameters():
                p.clear_grad()

            loss_1f1b = pp.train_batch((x, y), schedule="1f1b")
            g_new = _grads(pl)

            np.testing.assert_allclose(loss_1f1b.numpy(), loss_ref.numpy(),
                                       rtol=2e-5, atol=1e-6)
            assert len(g_ref) == len(g_new) and len(g_ref) > 10
            for a, b in zip(g_ref, g_new):
                assert (a is None) == (b is None)
                if a is not None:
                    np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-5)

            # the compiled program must carry the ZeRO pair: an entry
            # all-gather and an exit reduce-scatter over 'sharding', on
            # top of the pp collective-permute ring
            eng = pp._1f1b_engine
            fn = next(iter(eng._cache.values()))
            pvals = [p._value for p in eng._params]
            bvals = [b._value for b in eng._buffers]
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            rep = NamedSharding(mesh, P())
            kd = jax.device_put(
                jax.random.key_data(jax.random.PRNGKey(0)), rep)
            hlo = fn.lower(pvals, bvals,
                           jax.device_put(x._value, rep),
                           jax.device_put(y._value, rep),
                           kd).compile().as_text()
            assert "all-gather" in hlo
            assert "reduce-scatter" in hlo
            assert "collective-permute" in hlo

            # grads keep the ZeRO shard layout at rest
            qw = pl.run_functions[1].wq.weight
            assert "sharding" in str(qw.grad._value.sharding.spec)
        finally:
            set_mesh(None)

    def test_llama_pipe_parity_virtual_stages(self):
        """Interleaved virtual stages on the transformer: 4 chunks over
        pp=2 (virtual_pp_degree=2), tied embeddings crossing the ring
        wrap."""
        from paddle_tpu.models.llama import LlamaConfig
        from paddle_tpu.models.llama_pipe import build_llama_pipe

        mesh = create_hybrid_mesh(pp=2, mp=2, dp=2)
        try:
            paddle.seed(3)
            cfg = LlamaConfig.tiny(num_layers=4)
            pl = build_llama_pipe(cfg, num_stages=2,
                                  num_virtual_pipeline_stages=2)
            strategy = DistributedStrategy()
            strategy.pipeline_configs = {"accumulate_steps": 4}
            pp = PipelineParallel(pl, None, strategy)

            rng = np.random.RandomState(5)
            x = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (8, 16)).astype("int64"))
            y = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (8, 16)).astype("int64"))

            loss_ref = pp.train_batch((x, y))
            g_ref = _grads(pl)
            for p in pl.parameters():
                p.clear_grad()
            loss_1f1b = pp.train_batch((x, y), schedule="1f1b")
            g_new = _grads(pl)

            np.testing.assert_allclose(loss_1f1b.numpy(), loss_ref.numpy(),
                                       rtol=2e-5, atol=1e-6)
            for a, b in zip(g_ref, g_new):
                if a is not None:
                    np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-5)
        finally:
            set_mesh(None)

    def test_parity_pp_dp_sharding_combined(self):
        """dp AND sharding together (pp=2 x dp=2 x sharding=2): the batch
        splits over BOTH data axes, unshardable grads pmean over each,
        shardable grads reduce-scatter over 'sharding' then pmean over dp.
        Parity against the grad-accumulation path on the small pipeline."""
        mesh = create_hybrid_mesh(pp=2, dp=2, sharding=2)
        try:
            pp, pl = _build_pp(num_stages=2, n_layers=4, seed=21)
            rng = np.random.RandomState(4)
            x = paddle.to_tensor(rng.randn(16, 8).astype("float32"))
            y = paddle.to_tensor(rng.randn(16, 8).astype("float32"))

            loss_ref = pp.train_batch((x, y))
            g_ref = _grads(pl)
            for p in pl.parameters():
                p.clear_grad()
            loss_1f1b = pp.train_batch((x, y), schedule="1f1b")
            g_new = _grads(pl)

            np.testing.assert_allclose(loss_1f1b.numpy(), loss_ref.numpy(),
                                       rtol=2e-5, atol=1e-7)
            assert len(g_ref) == len(g_new) and len(g_ref) > 0
            for a, b in zip(g_ref, g_new):
                assert (a is None) == (b is None)
                if a is not None:
                    np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-6)
        finally:
            set_mesh(None)

    def test_llama_pipe_parity_4axis_16dev(self):
        """The FULL 4-axis hybrid (VERDICT r4 item 7): dp2 x pp2 x mp2 x
        sharding2 — compiled 1F1B with manual TP, in-program ZeRO (entry
        all-gather / exit reduce-scatter over 'sharding') AND dp
        grad-averaging, in ONE program on a 16-device mesh. The suite's
        conftest pins 8 virtual devices, so this runs in a subprocess
        with 16 (same recipe, SURVEY §7.3.5); parity covers loss and
        every parameter gradient, and the HLO must carry all three
        collective families (all-gather, reduce-scatter,
        collective-permute)."""
        import os
        import subprocess
        import sys
        import textwrap

        code = textwrap.dedent("""
            import numpy as np
            import jax
            import paddle_tpu as paddle
            from paddle_tpu.distributed.fleet import DistributedStrategy
            from paddle_tpu.distributed.fleet.meta_parallel import (
                PipelineParallel,
            )
            from paddle_tpu.models.llama import LlamaConfig
            from paddle_tpu.models.llama_pipe import build_llama_pipe
            from paddle_tpu.parallel import create_hybrid_mesh, set_mesh

            mesh = create_hybrid_mesh(dp=2, pp=2, mp=2, sharding=2)
            paddle.seed(0)
            cfg = LlamaConfig.tiny(num_layers=4)
            pl = build_llama_pipe(cfg, num_stages=2)
            strategy = DistributedStrategy()
            strategy.pipeline_configs = {"accumulate_steps": 4}
            pp = PipelineParallel(pl, None, strategy)
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (16, 16)).astype("int64"))
            y = paddle.to_tensor(
                rng.randint(0, cfg.vocab_size, (16, 16)).astype("int64"))

            loss_ref = pp.train_batch((x, y))
            g_ref = [None if p.grad is None
                     else np.asarray(p.grad.numpy()).copy()
                     for p in pl.parameters() if not p.stop_gradient]
            for p in pl.parameters():
                p.clear_grad()
            loss_1f1b = pp.train_batch((x, y), schedule="1f1b")
            g_new = [None if p.grad is None
                     else np.asarray(p.grad.numpy()).copy()
                     for p in pl.parameters() if not p.stop_gradient]

            np.testing.assert_allclose(loss_1f1b.numpy(), loss_ref.numpy(),
                                       rtol=2e-5, atol=1e-6)
            assert len(g_ref) == len(g_new) and len(g_ref) > 10
            for a, b in zip(g_ref, g_new):
                assert (a is None) == (b is None)
                if a is not None:
                    np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-5)

            # the one compiled program must carry the ZeRO pair AND the
            # pp ring on top of the dp/mp reductions
            eng = pp._1f1b_engine
            fn = next(iter(eng._cache.values()))
            pvals = [p._value for p in eng._params]
            bvals = [b._value for b in eng._buffers]
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            rep = NamedSharding(mesh, P())
            kd = jax.device_put(
                jax.random.key_data(jax.random.PRNGKey(0)), rep)
            hlo = fn.lower(pvals, bvals,
                           jax.device_put(x._value, rep),
                           jax.device_put(y._value, rep),
                           kd).compile().as_text()
            assert "all-gather" in hlo
            assert "reduce-scatter" in hlo
            assert "collective-permute" in hlo

            qw = pl.run_functions[1].wq.weight
            # strict: the grad must be at REST in the ZeRO shard layout
            # (the 'mp' placement alone comes from TP and would mask a
            # dropped reduce-scatter exit)
            assert "sharding" in str(qw.grad._value.sharding.spec)
            set_mesh(None)
            print("4AXIS-PARITY-OK", float(loss_1f1b.numpy()))
        """)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        proc = subprocess.run([sys.executable, "-c", code],
                              cwd="/root/repo", env=env, timeout=900,
                              capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "4AXIS-PARITY-OK" in proc.stdout

    def test_gspmd_layer_in_chunk_raises_at_trace(self):
        """The manual-TP footgun guard (VERDICT r3 item 3): a layer that
        stages a GSPMD sharding constraint inside a 1F1B stage chunk must
        fail AT TRACE TIME with the layer's name — not deadlock on a real
        mesh. Also pins that the guard is scoped: the same layer works on
        the eager grad-accumulation path."""
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers import (
            mp_layers as _mpl,
        )

        class GspmdOnlyLayer(paddle.nn.Layer):
            def __init__(self, width):
                super().__init__()
                self.lin = paddle.nn.Linear(width, width)

            def forward(self, x):
                return _mpl._constrain(self.lin(x), P(None, "mp"))

        mesh = create_hybrid_mesh(pp=2, mp=2, devices=jax.devices()[:4])
        try:
            paddle.seed(13)
            descs = [LayerDesc(paddle.nn.Linear, 8, 8),
                     LayerDesc(GspmdOnlyLayer, 8),
                     LayerDesc(paddle.nn.Linear, 8, 8),
                     LayerDesc(paddle.nn.Linear, 8, 8)]
            pl = PipelineLayer(layers=descs, num_stages=2, loss_fn=_mse)
            strategy = DistributedStrategy()
            strategy.pipeline_configs = {"accumulate_steps": 4}
            pp = PipelineParallel(pl, None, strategy)
            rng = np.random.RandomState(7)
            x = paddle.to_tensor(rng.randn(8, 8).astype("float32"))
            y = paddle.to_tensor(rng.randn(8, 8).astype("float32"))

            # eager grad-accumulation path: GSPMD constraints are fine
            loss_ref = pp.train_batch((x, y))
            assert np.isfinite(float(loss_ref.numpy()))

            with pytest.raises(ValueError, match="GspmdOnlyLayer"):
                pp.train_batch((x, y), schedule="1f1b")
        finally:
            set_mesh(None)

    def test_manual_mp_is_context_local(self):
        """contextvars semantics: nested scopes restore, and a fresh
        context (another task/thread) does not observe the engine's
        manual mode."""
        import contextvars

        from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers import (
            mp_layers as _mpl,
        )

        assert _mpl.manual_axis() is None
        with _mpl.manual_mp("mp", program=True):
            assert _mpl.manual_axis() == "mp"
            assert _mpl.in_manual_program()
            with _mpl.manual_mp(None):
                assert _mpl.manual_axis() is None
                assert _mpl.in_manual_program()  # program flag survives
            assert _mpl.manual_axis() == "mp"
            # a FRESH context (what another thread starts from) sees no
            # manual mode even while this one is inside it
            ctx = contextvars.Context()
            assert ctx.run(_mpl.manual_axis) is None
            assert ctx.run(_mpl.in_manual_program) is False
        assert _mpl.manual_axis() is None
        assert not _mpl.in_manual_program()

    def test_uneven_batch_rejected(self, pp4_mesh):
        pp, pl = _build_pp(num_stages=4, n_layers=8, seed=4)
        x = paddle.to_tensor(np.random.randn(6, 8).astype("float32"))
        y = paddle.to_tensor(np.random.randn(6, 8).astype("float32"))
        with pytest.raises(ValueError, match="divisible"):
            pp.train_batch((x, y), schedule="1f1b")
