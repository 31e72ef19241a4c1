"""On-chip END-TO-END train-step certification — REAL TPU ONLY.

VERDICT r3 weak #7: the TPU lane certified kernels, not the framework — an
on-chip-only numeric regression in nn-layer bf16 numerics or the fused
optimizer would only surface as an unexplained bench drop. These tests run
FULL train steps (fwd + bwd + global-norm clip + AdamW, bf16 compute, fp32
master weights — the bench's exact path at tiny scale) on the chip and
compare the loss trajectory against the SAME program executed on the
in-process XLA CPU backend. bf16 reduction orders differ between backends,
so parity is trajectory-level with bf16 tolerances, not bitwise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="on-chip certification runs on TPU only")


def _llama_losses(device, n_steps=4):
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import create_hybrid_mesh, set_mesh

    cfg = llama.LlamaConfig.tiny()
    mesh = create_hybrid_mesh(devices=[device])
    try:
        params = llama.init_params(cfg)
        opt_state = llama.init_opt_state(params)
        params, opt_state = llama.shard_state(cfg, mesh, params, opt_state)
        rng = np.random.RandomState(0)
        tokens = jax.device_put(
            rng.randint(0, cfg.vocab_size, (4, 64)).astype(np.int32),
            device)
        step = llama.make_sharded_train_step(cfg, mesh, lr=1e-2)
        losses = []
        for _ in range(n_steps):
            params, opt_state, loss = step(params, opt_state, tokens, tokens)
            losses.append(float(loss))
        return losses
    finally:
        set_mesh(None)


def test_llama_train_step_tpu_matches_cpu():
    """The flagship's full fused step (embedding, rms-norm, rope,
    attention, SwiGLU, CE loss, global-norm clip, AdamW with fp32 master
    weights) produces the same bf16 loss trajectory on the chip as on the
    XLA CPU backend, and it trains (loss strictly decreases)."""
    tpu_losses = _llama_losses(jax.devices()[0])
    cpu_losses = _llama_losses(jax.devices("cpu")[0])
    assert all(np.isfinite(v) for v in tpu_losses), tpu_losses
    # training happens: 4 steps at lr 1e-2 on a memorizable batch
    assert tpu_losses[-1] < tpu_losses[0], tpu_losses
    # cross-backend bf16 trajectory parity (reduction orders differ)
    np.testing.assert_allclose(tpu_losses, cpu_losses, rtol=2e-2,
                               atol=2e-2)


def _mlp_losses(place, n_steps=4):
    import paddle_tpu as paddle

    prev = paddle.get_device()
    paddle.set_device(place)
    try:
        paddle.seed(7)
        rng = np.random.RandomState(1)
        model = paddle.nn.Sequential(
            paddle.nn.Linear(16, 32), paddle.nn.GELU(),
            paddle.nn.LayerNorm(32), paddle.nn.Linear(32, 4))
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters(),
                                     grad_clip=paddle.nn.ClipGradByGlobalNorm(
                                         1.0))
        ce = paddle.nn.CrossEntropyLoss()
        x = paddle.to_tensor(rng.randn(32, 16).astype(np.float32))
        y = paddle.to_tensor(rng.randint(0, 4, (32,)).astype(np.int64))
        step = paddle.jit.fused_train_step(lambda a, b: ce(model(a), b), opt,
                                           model=model)
        return [float(step(x, y).numpy()) for _ in range(n_steps)]
    finally:
        paddle.set_device(prev)


def test_fused_train_step_product_surface_tpu_matches_cpu():
    """The paddle-level fused_train_step (ONE donated XLA program for
    fwd+bwd+clip+AdamW, built from nn.Layer/optimizer/ClipGradByGlobalNorm
    — the hapi/user path) certifies the product surface on the chip:
    same trajectory as the CPU backend, and it trains."""
    tpu_losses = _mlp_losses("tpu")
    cpu_losses = _mlp_losses("cpu")
    assert all(np.isfinite(v) for v in tpu_losses), tpu_losses
    assert tpu_losses[-1] < tpu_losses[0], tpu_losses
    np.testing.assert_allclose(tpu_losses, cpu_losses, rtol=2e-3,
                               atol=1e-3)


def test_head_dx_pallas_kernel_parity_tpu():
    """r5 CE-tail kernel (ops/pallas/head_dx.py) on the chip: in-kernel
    softmax + blocked dots against the fp32 XLA reference, including a
    ragged M (non-divisible by the block) and zero row-weights."""
    from paddle_tpu.ops.pallas.head_dx import head_dx_softmax

    rng = np.random.RandomState(0)
    for M, bm in ((1024, 512), (1000, 512)):  # divisible + ragged
        V, H = 2048, 256
        l = jnp.asarray(rng.randn(M, V), jnp.bfloat16)
        wt = jnp.asarray(rng.randn(V, H), jnp.bfloat16)
        m = jnp.max(l, axis=-1).astype(jnp.float32)
        se = jnp.sum(jnp.exp(l.astype(jnp.float32) - m[:, None]), axis=-1)
        scale = (np.r_[np.zeros(3), np.ones(M - 3)].astype(np.float32)
                 / np.asarray(se))
        got = np.asarray(head_dx_softmax(
            l, m, jnp.asarray(scale), wt, bm=bm, bk=512), np.float32)
        p = (np.exp(np.float32(l) - np.asarray(m)[:, None])
             * scale[:, None])
        ref = p @ np.float32(wt)
        denom = np.abs(ref).max() + 1e-9
        assert np.abs(got - ref).max() / denom < 2e-2
        assert np.abs(got[:3]).max() == 0.0  # zero-weight rows stay zero


def test_ce_tail_custom_train_step_tpu_matches_cpu():
    """The custom-VJP CE tail through a FULL train step on the chip (the
    bench's exact head path: pallas dx kernel + iota-mask dW) vs the same
    program with autodiff CE on the CPU backend."""
    import dataclasses

    from paddle_tpu.models import llama
    from paddle_tpu.parallel import create_hybrid_mesh, set_mesh

    def run(device, custom):
        cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                                  ce_tail_custom=custom)
        mesh = create_hybrid_mesh(devices=[device])
        try:
            params = llama.init_params(cfg)
            opt_state = llama.init_opt_state(params)
            params, opt_state = llama.shard_state(cfg, mesh, params,
                                                  opt_state)
            tokens = jax.device_put(
                np.random.RandomState(0).randint(
                    0, cfg.vocab_size, (4, 64)).astype(np.int32), device)
            step = llama.make_sharded_train_step(cfg, mesh, lr=1e-2)
            losses = []
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state,
                                               tokens, tokens)
                losses.append(float(loss))
            return losses
        finally:
            set_mesh(None)

    tpu_custom = run(jax.devices()[0], True)
    cpu_autodiff = run(jax.devices("cpu")[0], False)
    np.testing.assert_allclose(tpu_custom, cpu_autodiff, rtol=2e-3,
                               atol=1e-3)


def test_amp_o1_gradscaler_forced_overflow_tpu():
    """r4 item 8: AMP O1 + GradScaler dynamics ON THE CHIP with a FORCED
    overflow — the found_inf step must be SKIPPED (params unchanged, loss
    scale halved) and the following finite step must apply."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    lin = nn.Linear(8, 8)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=lin.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                   incr_every_n_steps=2,
                                   decr_every_n_nan_or_inf=1)
    x = paddle.to_tensor(np.ones((2, 8), np.float32))

    def step(blow_up):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            out = lin(x)
            loss = (out * (1e38 if blow_up else 1.0)).pow(2).sum()
        scaler.scale(loss).backward()
        scaler.step(opt)
        scaler.update()
        opt.clear_grad()

    w0 = lin.weight.numpy().copy()
    s0 = scaler._scale
    step(blow_up=True)            # inf grads -> found_inf path
    np.testing.assert_array_equal(lin.weight.numpy(), w0)  # skipped
    assert scaler._scale < s0      # dynamic scale backed off
    step(blow_up=False)            # finite step applies
    assert not np.allclose(lin.weight.numpy(), w0)


def test_resnet_block_train_step_momentum_tpu_matches_cpu():
    """r4 item 8: a conv-net full train step on the chip — one ResNet
    bottleneck block (conv+BN+relu+residual) + CrossEntropy + MOMENTUM
    (the non-AdamW optimizer lane) through fused_train_step, loss
    trajectory vs the same program on the in-process CPU backend."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models.resnet import BottleneckBlock

    def run(device):
        prev = paddle.get_device()
        paddle.set_device(device)
        try:
            paddle.seed(7)
            block = nn.Sequential(
                BottleneckBlock(16, 4, data_format="NHWC"),
                nn.AdaptiveAvgPool2D(1, data_format="NHWC"),
                nn.Flatten(),
                nn.Linear(16, 10),
            )
            block.train()
            opt = paddle.optimizer.Momentum(
                learning_rate=0.05, momentum=0.9,
                parameters=block.parameters(), weight_decay=1e-4)
            ce = nn.CrossEntropyLoss()

            def loss_fn(x, y):
                return ce(block(x), y)

            step_fn = paddle.jit.fused_train_step(loss_fn, opt,
                                                  model=block)
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.rand(4, 8, 8, 16).astype(np.float32))
            y = paddle.to_tensor(rng.randint(0, 10, (4,)))
            return [float(step_fn(x, y)) for _ in range(3)]
        finally:
            paddle.set_device(prev)

    tpu = run("tpu:0" if jax.default_backend() != "cpu" else "cpu")
    cpu = run("cpu")
    np.testing.assert_allclose(tpu, cpu, rtol=2e-3, atol=1e-3)


def test_lamb_optimizer_step_tpu_matches_cpu():
    """r4 item 8: Lamb (trust-ratio, non-elementwise) parity on-chip —
    three steps on a two-layer net, trajectory vs the CPU backend."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    def run(device):
        prev = paddle.get_device()
        paddle.set_device(device)
        try:
            paddle.seed(3)
            net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                                nn.Linear(16, 4))
            opt = paddle.optimizer.Lamb(learning_rate=0.01,
                                        lamb_weight_decay=0.01,
                                        parameters=net.parameters())
            rng = np.random.RandomState(1)
            x = paddle.to_tensor(rng.randn(8, 8).astype(np.float32))
            y = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
            losses = []
            for _ in range(3):
                loss = paddle.mean((net(x) - y) ** 2)
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(loss))
            return losses
        finally:
            paddle.set_device(prev)

    tpu = run("tpu:0" if jax.default_backend() != "cpu" else "cpu")
    cpu = run("cpu")
    # TPU f32 dots default to bf16-mantissa MXU passes: ~1e-3 relative
    # per matmul is expected cross-backend noise, not a Lamb bug
    np.testing.assert_allclose(tpu, cpu, rtol=1e-2, atol=1e-3)
    assert tpu[-1] < tpu[0]  # and it actually optimizes
