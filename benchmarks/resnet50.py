"""BASELINE config 1: ResNet-50 ImageNet-geometry training throughput,
single chip (reference: PaddleClas ResNet50 default config).

Whole train step through the compiled path: ``fused_train_step`` (forward +
loss + backward + momentum update as ONE donated XLA program). The
benchmarked layout is NHWC end-to-end — channels-last is the layout TPU
convolutions tile natively, so no transpose pass precedes the MXU convs.

``host_input=True`` feeds a FRESH host batch through ``jax.device_put``
issued one step ahead (double buffering): the async transfer overlaps the
previous step's device compute. The default measurement uses
device-resident batches; the overlap path has not been timed on today's
code and is exercised at reduced size by ``tests/test_scaling_evidence.py``'s
sibling (`test_io_hapi`).

Prints one JSON line: images/sec + MFU (3x-forward FLOP convention,
12.27 GFLOP/img at 224x224) against the bf16 peak of the chip it ran on
(``observability.perf.CHIP_PEAKS``); it fails on any other device.

The parent process never imports jax: each batch size runs in a child of
its own, and a chip belongs to one process at a time.
"""

import json
import os
import sys

# runnable standalone: the repo root (one level up) holds paddle_tpu
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np

TRAIN_GFLOP_PER_IMG = 12.27  # 3 x 4.09 GFLOP fwd (fvcore count, 224x224)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def run(batch=128, size=224, iters=40, host_input=False):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.observability.perf import chip_peaks
    from paddle_tpu.vision import models

    dev = jax.devices()[0]
    peak_flops_s = chip_peaks(dev.device_kind)["bf16_flops_s"]
    model = models.resnet50(num_classes=1000, data_format="NHWC")
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=1e-4)
    # AMP O2 (pure bf16 with fp32 master weights) — the reference baseline
    # trains ResNet-50 in mixed precision (fp16/bf16 on tensor cores)
    model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                     level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()

    def loss_fn(x, y):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return ce(model(x), y)

    # fwd+bwd+optimizer as ONE compiled program per step (one dispatch)
    step_fn = paddle.jit.fused_train_step(loss_fn, opt, model=model)

    rng = np.random.RandomState(0)
    # a small rotation of prepared host batches: each step feeds a DIFFERENT
    # buffer so the host->device DMA really happens every step (one fixed
    # device array would hide the input pipeline entirely)
    host_x = [np.ascontiguousarray(
        rng.rand(batch, size, size, 3).astype(np.float32)) for _ in range(3)]
    host_y = [rng.randint(0, 1000, (batch,)) for _ in range(3)]

    def put(i):
        return (paddle.to_tensor(jax.device_put(host_x[i % 3], dev)),
                paddle.to_tensor(jax.device_put(host_y[i % 3], dev)))

    x, y = put(0)
    loss = step_fn(x, y)
    log(f"warmup loss {float(loss):.3f}")
    loss = step_fn(x, y)
    loss.value.block_until_ready()

    best = None
    for _ in range(3):
        nxt = (x, y)
        t0 = time.perf_counter()
        for i in range(iters):
            cur = nxt
            if host_input:
                # issue next batch's transfer BEFORE dispatching this step:
                # device_put is async, so the DMA rides under the compute
                nxt = put(i + 1)
            loss = step_fn(*cur)
        loss.value.block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    ips = iters * batch / best
    mfu = ips * TRAIN_GFLOP_PER_IMG * 1e9 / peak_flops_s
    log(f"b{batch} NHWC host-input={host_input}: {ips:,.0f} img/s, "
        f"step {best/iters*1e3:.1f} ms, MFU~{mfu*100:.1f}% "
        f"({dev.device_kind})")
    return {"ips": ips, "mfu": mfu,
            "device": {"platform": dev.platform, "kind": dev.device_kind}}


def main():
    # one batch size per process: a failed (OOM) attempt leaves the chip's
    # allocator fragmented, poisoning smaller retries in the same process
    import subprocess

    if len(sys.argv) > 1:
        print(json.dumps(run(int(sys.argv[1]))))
        return

    rec = None
    for batch in (128, 64, 32):
        proc = subprocess.run([sys.executable, __file__, str(batch)],
                              capture_output=True, text=True)
        log(proc.stderr[-500:])
        if proc.returncode == 0:
            rec = json.loads(proc.stdout.splitlines()[-1])
            break
    if rec is None:
        sys.exit("resnet50: no batch size ran")
    print(json.dumps({
        "metric": "resnet50_train_throughput",
        "value": round(rec["ips"], 1),
        "unit": "images/sec", "mfu": round(rec["mfu"], 4),
        "vs_baseline": round(rec["ips"] / 2850.0, 4),  # A100 fp16 ballpark
        "device": rec["device"],
    }))


if __name__ == "__main__":
    main()
