"""TPU-only: BN+ReLU must fuse into the convolution epilogue (VERDICT r2
item 4 — "verify BN+ReLU fuse into the conv epilogue").

The CPU suite (conftest forces the virtual CPU platform) skips this; the
TPU test lane (benchmarks/tpu_test_lane.py) runs it on the real chip each
round. The check is structural, on the optimized TPU HLO of the compiled
NHWC train step: no `batch-norm-*` instruction survives (XLA decomposes
training BN into the surrounding fusions), ReLU never stands alone, and
the elementwise-op count collapses into ~one fusion per convolution.
"""

import re

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn

pytestmark = pytest.mark.skipif(
    jax.devices()[0].platform != "tpu",
    reason="TPU-only: inspects the TPU backend's optimized HLO")


def test_bn_relu_fuse_into_conv_epilogue():
    from paddle_tpu.vision.models import resnet18

    model = resnet18(num_classes=10, data_format="NHWC")
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                     level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()

    def loss_fn(x, y):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return ce(model(x), y)

    step = paddle.jit.fused_train_step(loss_fn, opt, model=model)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(8, 64, 64, 3).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (8,)))
    step.compile(x, y)
    hlo = next(iter(step._cache.values()))._compiled.as_text()

    # 1. training BN decomposed away — nothing batch-norm-shaped survives
    #    to run as its own kernel
    assert "batch-norm" not in hlo, "unfused batch-norm op in optimized HLO"

    # 2. every elementwise chain landed inside a fusion: at top level the
    #    program is convolutions + fusions + data movement, with no bare
    #    maximum/add/multiply instructions (ReLU = maximum(x, 0))
    top_level = [l for l in hlo.splitlines()
                 if re.match(r"\s+\S+ = ", l) and "fused_computation" not in l]
    bare = [l.strip() for l in top_level
            if re.search(r"= (maximum|add|multiply|subtract|divide)\(",
                         l.strip())
            # scalar bookkeeping (step counter etc.) is fine; tensor-shaped
            # elementwise ops are what must not run standalone
            and not re.search(r"= \w+\[\]", l.strip())]
    assert not bare, f"standalone elementwise ops escaped fusion: {bare[:5]}"

    # 3. the fusion count stays in the same regime as the conv count — the
    #    epilogues (BN scale/shift + ReLU) ride with their convolutions
    #    rather than multiplying into separate kernels
    n_conv = len(re.findall(r"= \S+ convolution\(", hlo))
    n_fusion = len(re.findall(r"= \S+ fusion\(", hlo))
    assert n_conv >= 20  # fwd+bwd convs of an 18-layer resnet
    assert n_fusion < 12 * n_conv, (n_conv, n_fusion)
