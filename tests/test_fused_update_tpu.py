"""On-chip certification of the Pallas fused multi-tensor optimizer
update — REAL TPU ONLY (ISSUE 3 satellite: the chip-lane entry asserting
fused-update vs reference trajectory parity on TPU).

The CPU suite (tests/test_multi_tensor_update.py) proves the kernels
through the pallas interpreter; these tests prove the REAL Mosaic
lowering — SMEM hyper scalars, input/output aliasing, the [rows, 128]
grid — agrees with the XLA reference trajectories on the chip, for the
two configurations the benchmarks run: Momentum+wd over bf16 params (the
ResNet-50 profile config) and AdamW with fp32 master weights (the bench
config).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="on-chip certification runs on TPU only")

SHAPES = [(3, 3, 16, 16)] * 3 + [(1, 1, 32, 16), (7, 7, 3, 16),
                                 (256, 10), (10,)] + [(16,)] * 5 + [(32,)]


def _run(opt_factory, dtype, use_kernel, steps=4):
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.set_flags({"use_pallas_fused_update": use_kernel})
    try:
        rng = np.random.RandomState(0)
        params = [nn.Parameter(
            jnp.asarray(rng.randn(*s) * 0.1).astype(dtype))
            for s in SHAPES]
        opt = opt_factory(params)
        for s in range(steps):
            g_rng = np.random.RandomState(100 + s)
            for p in params:
                p.grad = paddle.to_tensor(
                    jnp.asarray(g_rng.randn(*p.shape) * 0.01)
                    .astype(dtype))
            opt.step()
            opt.clear_grad()
        return [p.numpy().astype(np.float32) for p in params], opt
    finally:
        paddle.set_flags({"use_pallas_fused_update": True})


def test_momentum_bf16_kernel_matches_reference_on_chip():
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import multi_tensor_update as mtu

    mtu.reset_selection_count()
    fused, opt = _run(
        lambda ps: paddle.optimizer.Momentum(
            learning_rate=0.05, momentum=0.9, parameters=ps,
            weight_decay=1e-4),
        "bfloat16", use_kernel=True)
    assert mtu.selection_count() >= 1, \
        "fused update not selected on the chip"
    for st in opt._accumulators.values():
        for v in st.values():
            assert v.ndim == 2 and v.shape[1] == 128
    ref, _ = _run(
        lambda ps: paddle.optimizer.Momentum(
            learning_rate=0.05, momentum=0.9, parameters=ps,
            weight_decay=1e-4),
        "bfloat16", use_kernel=False)
    for a, b in zip(fused, ref):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)


def test_adamw_master_kernel_matches_reference_on_chip():
    import paddle_tpu as paddle

    fused, _ = _run(
        lambda ps: paddle.optimizer.AdamW(
            learning_rate=0.01, weight_decay=0.1, parameters=ps,
            multi_precision=True),
        "bfloat16", use_kernel=True)
    ref, _ = _run(
        lambda ps: paddle.optimizer.AdamW(
            learning_rate=0.01, weight_decay=0.1, parameters=ps,
            multi_precision=True),
        "bfloat16", use_kernel=False)
    for a, b in zip(fused, ref):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)
