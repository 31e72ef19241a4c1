"""Fleet hybrid-parallel tests (reference test strategy: SURVEY.md §4 —
TP/sharded layers must match their dense counterparts numerically; topology
rank math unit-tested standalone; all on the 8-device virtual CPU mesh)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import (
    CommunicateTopology,
    DistributedStrategy,
    HybridCommunicateGroup,
)
from paddle_tpu.distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    LayerDesc,
    ParallelCrossEntropy,
    PipelineLayer,
    RowParallelLinear,
    SharedLayerDesc,
    VocabParallelEmbedding,
    get_rng_state_tracker,
)
from paddle_tpu.parallel import create_hybrid_mesh, set_mesh


@pytest.fixture(autouse=True)
def _no_mesh_left_behind():
    """``fleet.init`` (through ``mp4_mesh`` or a test's own ``Fleet``) sets
    the global mesh and the hybrid communicate group: every test ends with
    neither, so the next file of this xdist worker traces under no mesh."""
    yield
    set_mesh(None)
    from paddle_tpu.distributed.fleet.base.topology import (
        set_hybrid_communicate_group,
    )

    set_hybrid_communicate_group(None)


@pytest.fixture
def mp4_mesh():
    mesh = create_hybrid_mesh(dp=2, mp=4)
    fleet.fleet._is_initialized = False
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    return mesh


class TestTopology:
    def test_coordinate_roundtrip(self):
        topo = CommunicateTopology(dims=(2, 2, 1, 2, 1))
        assert topo.world_size() == 8
        for r in range(8):
            coord = topo.get_coord(r)
            assert topo.get_rank(**dict(zip(topo.get_hybrid_group_names(), coord))) == r

    def test_comm_list(self):
        topo = CommunicateTopology(dims=(2, 1, 1, 4, 1))
        mp_groups = topo.get_comm_list("model")
        assert len(mp_groups) == 2
        assert mp_groups[0] == [0, 1, 2, 3]
        assert mp_groups[1] == [4, 5, 6, 7]
        dp_groups = topo.get_comm_list("data")
        assert sorted(map(tuple, dp_groups)) == [(0, 4), (1, 5), (2, 6), (3, 7)]

    def test_axis_list(self):
        topo = CommunicateTopology(dims=(2, 1, 1, 4, 1))
        assert topo.get_axis_list("model", 0) == [0, 4]


class TestFleetInit:
    def test_init_builds_mesh_and_groups(self, mp4_mesh):
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 4
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_group().axis_name == "mp"
        assert hcg.get_parallel_mode() == "model"

    def test_strategy_roundtrip(self):
        s = DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
        s.sharding = True
        s.sharding_configs = {"stage": 2}
        s2 = DistributedStrategy.from_json(s.to_json())
        assert s2.hybrid_configs.mp_degree == 4
        assert s2.sharding_configs.stage == 2


class TestMpLayers:
    """TP layer == dense layer numerics (the reference's hybrid_parallel_mp_layers
    parity tests, but exact by construction under GSPMD)."""

    def test_column_parallel_vs_dense(self, mp4_mesh):
        paddle.seed(7)
        layer = ColumnParallelLinear(16, 32, gather_output=True)
        x = paddle.to_tensor(np.random.randn(4, 16).astype("float32"))
        y = layer(x)
        ref = x.numpy() @ layer.weight.numpy() + layer.bias.numpy()
        np.testing.assert_allclose(y.numpy(), ref, rtol=2e-5, atol=2e-5)

    def test_column_row_pair(self, mp4_mesh):
        paddle.seed(8)
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 16, input_is_parallel=True)
        x = paddle.to_tensor(np.random.randn(4, 16).astype("float32"))
        y = row(col(x))
        ref = (x.numpy() @ col.weight.numpy() + col.bias.numpy()) \
            @ row.weight.numpy() + row.bias.numpy()
        np.testing.assert_allclose(y.numpy(), ref, rtol=2e-5, atol=2e-5)

    def test_backward_through_tp_pair(self, mp4_mesh):
        col = ColumnParallelLinear(8, 16, gather_output=False)
        row = RowParallelLinear(16, 8, input_is_parallel=True)
        x = paddle.to_tensor(np.random.randn(2, 8).astype("float32"))
        loss = paddle.mean(row(col(x)))
        loss.backward()
        assert col.weight.grad is not None
        assert row.weight.grad is not None
        assert col.weight.grad.shape == [8, 16]

    def test_vocab_parallel_embedding(self, mp4_mesh):
        emb = VocabParallelEmbedding(64, 8)
        ids = paddle.to_tensor(np.array([[1, 3], [62, 0]], dtype="int32"))
        out = emb(ids)
        np.testing.assert_allclose(
            out.numpy(), emb.weight.numpy()[ids.numpy()], rtol=1e-6)

    def test_parallel_cross_entropy(self, mp4_mesh):
        logits = paddle.to_tensor(np.random.randn(4, 64).astype("float32"))
        label = paddle.to_tensor(np.array([1, 5, 63, 0], dtype="int64"))
        loss = ParallelCrossEntropy()(logits, label)
        import paddle_tpu.nn.functional as F

        ref = F.cross_entropy(logits, label, reduction="none")
        np.testing.assert_allclose(loss.numpy().squeeze(-1), ref.numpy(),
                                   rtol=1e-5, atol=1e-5)

    def test_parallel_cross_entropy_grad(self, mp4_mesh):
        logits = paddle.to_tensor(np.random.randn(4, 64).astype("float32"),
                                  stop_gradient=False)
        label = paddle.to_tensor(np.array([1, 5, 63, 0], dtype="int64"))
        loss = paddle.mean(ParallelCrossEntropy()(logits, label))
        loss.backward()
        g = logits.grad.numpy()
        # grad of mean CE = (softmax - onehot)/N
        import scipy.special as sp

        sm = sp.softmax(logits.numpy(), axis=-1)
        oh = np.eye(64)[label.numpy()]
        np.testing.assert_allclose(g, (sm - oh) / 4, rtol=1e-4, atol=1e-5)


class TestRngTracker:
    def test_streams_differ(self):
        tracker = get_rng_state_tracker()
        tracker.reset()
        tracker.add("a", 100)
        tracker.add("b", 200)
        with tracker.rng_state("a"):
            r1 = paddle.rand([4]).numpy()
        with tracker.rng_state("b"):
            r2 = paddle.rand([4]).numpy()
        assert not np.allclose(r1, r2)

    def test_stream_advances(self):
        tracker = get_rng_state_tracker()
        tracker.reset()
        tracker.add("s", 300)
        with tracker.rng_state("s"):
            r1 = paddle.rand([4]).numpy()
        with tracker.rng_state("s"):
            r2 = paddle.rand([4]).numpy()
        assert not np.allclose(r1, r2)

    def test_global_stream_untouched(self):
        paddle.seed(123)
        expected = paddle.rand([4]).numpy()
        paddle.seed(123)
        tracker = get_rng_state_tracker()
        with tracker.rng_state():
            paddle.rand([4])
        got = paddle.rand([4]).numpy()
        np.testing.assert_allclose(got, expected)


class TestRecompute:
    def test_recompute_matches_plain(self):
        from paddle_tpu.distributed.fleet import recompute

        paddle.seed(5)
        lin1 = paddle.nn.Linear(8, 16)
        lin2 = paddle.nn.Linear(16, 8)

        def block(x):
            return lin2(paddle.nn.functional.relu(lin1(x)))

        xv = np.random.randn(4, 8).astype("float32")
        x1 = paddle.to_tensor(xv, stop_gradient=False)
        loss1 = paddle.mean(block(x1))
        loss1.backward()
        g_plain = (x1.grad.numpy().copy(), lin1.weight.grad.numpy().copy())

        lin1.clear_gradients(); lin2.clear_gradients()
        x2 = paddle.to_tensor(xv, stop_gradient=False)
        loss2 = paddle.mean(recompute(block, x2))
        loss2.backward()
        np.testing.assert_allclose(loss2.numpy(), loss1.numpy(), rtol=1e-6)
        np.testing.assert_allclose(x2.grad.numpy(), g_plain[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(lin1.weight.grad.numpy(), g_plain[1],
                                   rtol=1e-5, atol=1e-6)


class TestPipeline:
    def test_pipeline_layer_segmentation(self):
        descs = [LayerDesc(paddle.nn.Linear, 8, 8) for _ in range(8)]
        hcg = HybridCommunicateGroup(
            CommunicateTopology(dims=(1, 4, 1, 1, 1)))
        pl = PipelineLayer(layers=descs, num_stages=4, topology=hcg)
        assert pl.segment_parts == [0, 2, 4, 6, 8]
        assert len(pl.stage_layers(0)) == 2

    def test_pipeline_full_forward_matches_sequential(self):
        paddle.seed(11)
        descs = [LayerDesc(paddle.nn.Linear, 8, 8) for _ in range(4)]
        pl = PipelineLayer(layers=descs, num_stages=1)
        x = paddle.to_tensor(np.random.randn(2, 8).astype("float32"))
        y = pl(x)
        ref = x
        for fn in pl.run_functions:
            ref = fn(ref)
        np.testing.assert_allclose(y.numpy(), ref.numpy())

    def test_shared_layer_desc_ties_weights(self):
        class Emb(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.weight = self.create_parameter([16, 8])

            def forward(self, x):
                return paddle.matmul(x, self.weight)

        def head_fwd(layer, x):
            return paddle.matmul(x, paddle.transpose(layer.weight, [1, 0]))

        descs = [
            SharedLayerDesc("emb", Emb),
            LayerDesc(paddle.nn.Linear, 8, 8),
            SharedLayerDesc("emb", Emb, forward_func=head_fwd),
        ]
        pl = PipelineLayer(layers=descs, num_stages=1)
        params = pl.parameters()
        # tied: the Emb weight appears once in dedup'd param list
        ids = [id(p) for p in params]
        assert len(ids) == len(set(ids))
        x = paddle.to_tensor(np.random.randn(2, 16).astype("float32"))
        out = pl(x)
        assert list(out.shape) == [2, 16]

    def test_train_batch_grad_accumulation(self, mp4_mesh):
        from paddle_tpu.distributed.fleet.meta_parallel import PipelineParallel

        paddle.seed(3)
        descs = [LayerDesc(paddle.nn.Linear, 8, 8) for _ in range(2)]

        def loss_fn(out, y):
            return paddle.mean((out - y) ** 2)

        pl = PipelineLayer(layers=descs, num_stages=1, loss_fn=loss_fn)
        strategy = DistributedStrategy()
        strategy.pipeline_configs = {"accumulate_steps": 2}
        hcg = fleet.get_hybrid_communicate_group()
        pp = PipelineParallel(pl, hcg, strategy)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=pl.parameters())
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        y = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        w_before = pl.run_functions[0].weight.numpy().copy()
        loss = pp.train_batch((x, y), optimizer=opt)
        assert loss is not None
        assert not np.allclose(pl.run_functions[0].weight.numpy(), w_before)


class TestHybridOptimizer:
    def test_sharded_state_placement(self, mp4_mesh):
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            HybridParallelOptimizer,
        )

        lin = paddle.nn.Linear(16, 16)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=lin.parameters())
        hopt = HybridParallelOptimizer(opt, strategy=None)
        hopt._sharding_stage = 1  # force ZeRO placement on the dp axis
        x = paddle.to_tensor(np.random.randn(4, 16).astype("float32"))
        loss = paddle.mean(lin(x) ** 2)
        loss.backward()
        w_before = lin.weight.numpy().copy()
        hopt.step()
        assert not np.allclose(lin.weight.numpy(), w_before)
        # moment accumulators exist and step ran with sharded placement
        st = opt._accumulators[id(lin.weight)]
        assert "moment1" in st or len(st) > 0


class TestReviewRegressions:
    def test_recompute_input_unused(self):
        """Input not reached by the function's output → zero grad, no crash."""
        from paddle_tpu.distributed.fleet import recompute

        lin = paddle.nn.Linear(4, 4)
        const = paddle.to_tensor(np.ones((2, 4), dtype="float32"))

        def f(x):
            return lin(const)  # ignores x entirely

        x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"),
                             stop_gradient=False)
        loss = paddle.mean(recompute(f, x))
        loss.backward()
        np.testing.assert_allclose(x.grad.numpy(), np.zeros((2, 4)))
        assert lin.weight.grad is not None

    def test_uneven_micro_batch_loss_weighting(self, mp4_mesh):
        """4 rows with accumulate_steps=8: loss must equal the full-batch
        mean, not half of it (review finding: k/n scaling bug)."""
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc,
            PipelineLayer,
            PipelineParallel,
        )

        paddle.seed(9)
        descs = [LayerDesc(paddle.nn.Linear, 8, 8)]

        def loss_fn(out, y):
            return paddle.mean((out - y) ** 2)

        pl = PipelineLayer(layers=descs, num_stages=1, loss_fn=loss_fn)
        strategy = DistributedStrategy()
        strategy.pipeline_configs = {"accumulate_steps": 8}
        hcg = fleet.get_hybrid_communicate_group()
        pp = PipelineParallel(pl, hcg, strategy)
        x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        y = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
        loss = pp.train_batch((x, y))
        full = paddle.mean((pl.run_functions[0](x) - y) ** 2)
        np.testing.assert_allclose(float(loss.numpy()), float(full.numpy()),
                                   rtol=1e-5)


class TestRoleMakers:
    def test_cloud_role_maker_env(self, monkeypatch):
        import paddle_tpu.distributed.fleet as fleet

        monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        rm = fleet.PaddleCloudRoleMaker(is_collective=True)
        assert rm.worker_index() == 2
        assert rm.worker_num() == 4
        assert rm.is_worker() and not rm.is_first_worker()
        # collective: a stale PS TRAINING_ROLE must not demote workers
        monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
        assert fleet.PaddleCloudRoleMaker(is_collective=True).is_worker()
        assert fleet.PaddleCloudRoleMaker(is_collective=False).is_server()

    def test_user_defined_role_maker_wired_into_fleet(self):
        import paddle_tpu.distributed.fleet as fleet

        rm = fleet.UserDefinedRoleMaker(
            is_collective=True, current_id=3, worker_num=8,
            worker_endpoints=[f"127.0.0.1:{9000 + i}" for i in range(8)])
        f = fleet.Fleet().init(role_maker=rm)
        assert f.worker_index() == 3
        assert f.worker_num() == 8
        assert not f.is_first_worker()
        assert rm._get_trainer_endpoints()[3] == "127.0.0.1:9003"


def test_p2p_and_object_collectives_api():
    """P2POp/batch_isend_irecv, scatter_object_list, wait, get_backend,
    destroy_process_group, split, distributed.utils — reference API
    surface (world-of-one semantics here; SPMD paths covered by the
    hybrid-parallel tests)."""
    import numpy as np
    import paddle_tpu as paddle

    d = paddle.distributed
    t = paddle.to_tensor(np.ones(4, np.float32))
    g1 = d.new_group([0])  # world-of-one group: eager P2P is identity
    tasks = d.batch_isend_irecv([d.P2POp(d.isend, t, 0, group=g1),
                                 d.P2POp(d.irecv, t, 0, group=g1)])
    assert len(tasks) == 2
    d.wait(t)
    assert d.get_backend() == "XLA"

    out = []
    d.scatter_object_list(out, [{"a": 1}])
    assert out == [{"a": 1}]

    y1 = d.split(paddle.to_tensor(np.ones((2, 8), np.float32)), (8, 4),
                 operation="linear", axis=1, name="t_split")
    y2 = d.split(paddle.to_tensor(np.ones((2, 8), np.float32)), (8, 4),
                 operation="linear", axis=1, name="t_split")
    assert y1.shape == [2, 4]
    np.testing.assert_allclose(y1.numpy(), y2.numpy())  # cached weights

    # name=None derives a stable per-call-site key (reference's optional
    # name): the same line reuses its weight across steps, a different
    # call site never weight-ties
    def site_a():
        return d.split(paddle.to_tensor(np.ones((2, 8), np.float32)), (8, 4),
                       operation="linear", axis=1)

    def site_b():
        return d.split(paddle.to_tensor(np.ones((2, 8), np.float32)), (8, 4),
                       operation="linear", axis=1)

    a1, a2, b1 = site_a(), site_a(), site_b()
    np.testing.assert_allclose(a1.numpy(), a2.numpy())  # same site: cached
    assert not np.allclose(a1.numpy(), b1.numpy())  # distinct sites: new init

    # ADVICE r2: two INSTANCES whose forward shares one source line must
    # not weight-tie — the auto key includes a per-instance token taken
    # from the caller's `self`
    class _SplitNet:
        def forward(self):
            return d.split(paddle.to_tensor(np.ones((2, 8), np.float32)),
                           (8, 4), operation="linear", axis=1)

    m1, m2 = _SplitNet(), _SplitNet()
    o1a, o1b, o2 = m1.forward(), m1.forward(), m2.forward()
    np.testing.assert_allclose(o1a.numpy(), o1b.numpy())  # same instance
    assert not np.allclose(o1a.numpy(), o2.numpy())  # new instance: new init

    from paddle_tpu.distributed import utils as dutils
    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(6, 2))
    np.testing.assert_allclose(
        dutils.global_scatter(x, np.array([6]), np.array([6]),
                              group=g1).numpy(),
        x.numpy())
    try:
        import pytest
        with pytest.raises(ValueError):
            d.P2POp("bogus", t, 0)
    except ImportError:
        pass


def test_spmd_p2p_ring_shift():
    """send/recv inside shard_map compile to a full-ring collective-permute
    with uniform-shift semantics (the PP send-to-next/recv-from-prev
    pattern); the matched pair moves every stage's buffer one hop."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import collective as C

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("pp",))
    g = C.new_group([0, 1, 2, 3], axis_name="pp")
    xs = jnp.arange(4, dtype=jnp.float32).reshape(4, 1)

    def recv_prev(x):
        return C.recv(Tensor(x), src=3, group=g)._value  # shift 1

    from paddle_tpu.parallel import shard_map_compat

    out = shard_map_compat(recv_prev, mesh=mesh, in_specs=P("pp", None),
                           out_specs=P("pp", None))(xs)
    assert np.asarray(out).ravel().tolist() == [3.0, 0.0, 1.0, 2.0]

    def send_next(x):
        return C.send(Tensor(x), dst=1, group=g)._value

    out = shard_map_compat(send_next, mesh=mesh, in_specs=P("pp", None),
                           out_specs=P("pp", None))(xs)
    assert np.asarray(out).ravel().tolist() == [3.0, 0.0, 1.0, 2.0]
