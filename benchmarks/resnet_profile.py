"""Per-instruction xplane profile of the ResNet-50 fused train step —
where do the ms between the measured step and the re-pinned 44 ms floor
(SCALING.md §3b) go?

Usage:
  python benchmarks/resnet_profile.py [batch] [top_n] [repeats]
      on-chip xplane profile; >=3 repeats with min/median/max (the r5
      dot_micro methodology: an optimizer-slice claim compares MEDIANS —
      a single capture can land on allocator luck)
  python benchmarks/resnet_profile.py --smoke
      CPU-safe regression gate for the Pallas fused multi-tensor
      optimizer update (no model, no conv forward: the optimizer-shape
      population alone)
  python benchmarks/resnet_profile.py --dw [batch] [repeats]
      NHWC-vs-NCHW per-instruction-class diff isolating the ~2.5 ms bwd
      weight-layout copies named in §3b (chip mode)

On-chip, run twice with FLAGS_use_pallas_fused_update flipped to get the
before/after optimizer-slice table the r8 ledger cites.

``--smoke`` is the fused-update lane hook (tests/test_multi_tensor_update
.py): it forces the Pallas kernels through the interpreter on CPU and
asserts (1) the fused update is SELECTED for the ResNet-50-like optimizer
population (and does NOT claim the bare CPU backend), (2) the update
program contains the kernel launch while the reference contains none, and
the analytic LAYOUT-CHANGING bytes per step strictly drop (the stack/flat
packing round-trips params+grads+state through packed temporaries; the
kernel's only layout crossings are grad-in and param-out — state rides
flat), (3) fused and reference update trajectories agree numerically over
multiple steps, (4) optimizer state stays in the flat [rows, 128] layout
between steps — so a kernel-selection or dispatch regression fails loudly
off-chip.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np


def _count_prim(jaxpr, prim: str) -> int:
    """Occurrences of a primitive incl. nested jaxprs (pallas_call bodies
    excluded — a kernel is ONE launch; the decode_profile convention)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == prim:
            n += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for sub in vs:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    n += _count_prim(inner, prim)
                elif hasattr(sub, "eqns"):
                    n += _count_prim(sub, prim)
    return n


def relayout_bytes(sizes, p_bytes, s_bytes_per_key, n_state_keys):
    """Analytic LAYOUT-CHANGING bytes per step for one packed group.

    XLA stack/flat packing: params, grads and every state buffer are
    packed into a temporary whose layout differs from the source tiles
    (in), and params + state sliced back out (out) ->
        in: P + G + K*M ; out: P + K*M.
    Pallas flat path: grads pack in, params pack in + unpack out; state
    never changes layout (its per-step segment/concat round trip is a
    tile-preserving memcpy, reported separately, and its EMISSION is the
    kernel's, not XLA's relayout loops) ->
        in: P + G ; out: P.
    """
    n = sum(sizes)
    P = n * p_bytes
    G = n * p_bytes
    M = n * s_bytes_per_key * n_state_keys
    ref = (P + G + M) + (P + M)
    fused = (P + G) + P
    memcpy_fused = 2 * M  # flat-segment slice/concat round trip
    return ref, fused, memcpy_fused


def _resnetish_population(paddle, scale=4):
    """A miniature of the ResNet-50 optimizer population: repeated conv
    shapes (the stack groups), 1x1/7x7 convs, BN gamma/beta/bias 1-D
    rows (the flat groups), and an fc — mixed, >8 tensors, bf16 (the
    AMP-O2 profile config). ``scale`` divides channel counts so the
    smoke runs in seconds on CPU."""
    import jax.numpy as jnp

    c1, c2, c3 = 64 // scale, 128 // scale, 256 // scale
    shapes = ([(3, 3, c1, c1)] * 4 + [(3, 3, c2, c2)] * 3
              + [(1, 1, c2, c3), (7, 7, 3, c1), (c3, 10), (10,)]
              + [(c1,)] * 6 + [(c2,)] * 4 + [(c3,)] * 2)
    rng = np.random.RandomState(0)
    params = [paddle.nn.Parameter(
        jnp.asarray(rng.randn(*s) * 0.05, jnp.bfloat16)) for s in shapes]
    grads = [np.asarray(rng.randn(*s) * 0.01, np.float32) for s in shapes]
    return params, grads


def smoke() -> dict:
    """CPU-safe fused-update selection + op-count + parity gate; returns
    the evidence dict (also printed from the CLI)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.ops.pallas.multi_tensor_update as mtu
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)

    def build_opt():
        params, grads = _resnetish_population(paddle)
        opt = paddle.optimizer.Momentum(
            learning_rate=0.1, momentum=0.9, parameters=params,
            weight_decay=1e-4)
        return params, grads, opt

    def trajectory(n_steps=2):  # step 2 covers the flat-state steady
        # state; the >=3-step parity bar lives in the pytest suite
        params, grads, opt = build_opt()
        for _ in range(n_steps):
            for p, g in zip(params, grads):
                p.grad = paddle.to_tensor(
                    jnp.asarray(g, jnp.bfloat16))
            opt.step()
            opt.clear_grad()
        return ([p.numpy().astype(np.float32) for p in params], opt)

    def update_jaxpr(opt, params, grads):
        for p in params:
            opt._ensure_state(p)
        keys = opt._state_names()
        evals = [opt._per_param_extras(p) for p in params]
        pvals = [p._value for p in params]
        gvals = [jnp.asarray(g, jnp.bfloat16) for g in grads]
        svals = [{k: opt._accumulators[id(p)][k] for k in keys}
                 for p in params]

        def f(pvals, gvals, svals, lr, step):
            return opt.apply_updates(pvals, gvals, svals, evals, evals,
                                     lr, step)

        return jax.make_jaxpr(f)(pvals, gvals, svals, jnp.float32(0.1),
                                 jnp.int32(1)).jaxpr

    force_prev = mtu.FORCE_INTERPRET
    try:
        # reference: kernels off — and on the bare CPU backend the fused
        # path must NOT engage on its own (dispatch honesty)
        mtu.FORCE_INTERPRET = False
        params, grads, opt = build_opt()
        assert not mtu.fused_update_active(len(params), "momentum") or \
            jax.default_backend() == "tpu", \
            "fused update claims CPU without the test force"
        jx_ref = update_jaxpr(opt, params, grads)
        assert _count_prim(jx_ref, "pallas_call") == 0
        ref_traj, _ = trajectory()

        # fused path, kernels forced through the interpreter
        mtu.FORCE_INTERPRET = True
        params, grads, opt = build_opt()
        assert mtu.fused_update_active(len(params), "momentum"), \
            "fused update NOT selectable for the ResNet-like population"
        mtu.reset_selection_count()
        jx_fused = update_jaxpr(opt, params, grads)
        assert mtu.selection_count() >= 1, \
            "fused update was not selected for the update program"
        n_kernels = _count_prim(jx_fused, "pallas_call")
        assert n_kernels >= 1, "no pallas_call in the fused update program"
        fused_traj, opt_f = trajectory()
        for a, b in zip(fused_traj, ref_traj):
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
        # state stays flat between steps (no per-step state relayout)
        st = next(iter(opt_f._accumulators.values()))
        flat_state = all(v.ndim == 2 and v.shape[1] == 128
                         for v in st.values())
        assert flat_state, {k: v.shape for k, v in st.items()}
    finally:
        mtu.FORCE_INTERPRET = force_prev

    # analytic layout-crossing bytes (the decode --bytes analog): the
    # whole Momentum population is one bf16 group with one state key
    sizes = [int(np.prod(p.shape)) for p in params]
    rel_ref, rel_fused, memcpy = relayout_bytes(sizes, 2, 2, 1)
    assert rel_fused < rel_ref, (rel_fused, rel_ref)
    return {"n_tensors": len(params), "pallas_calls": n_kernels,
            "relayout_bytes_ref": rel_ref,
            "relayout_bytes_fused": rel_fused,
            "flat_memcpy_bytes": memcpy, "state_flat": flat_state}


def _build_step(batch, data_format="NHWC"):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision import models

    model = models.resnet50(num_classes=1000, data_format=data_format)
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=1e-4)
    model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                     level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()

    def loss_fn(x, y):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return ce(model(x), y)

    step_fn = paddle.jit.fused_train_step(loss_fn, opt, model=model)
    rng = np.random.RandomState(0)
    shape = ((batch, 224, 224, 3) if data_format == "NHWC"
             else (batch, 3, 224, 224))
    x = paddle.to_tensor(rng.rand(*shape).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)))
    return step_fn, x, y


def _capture(step_fn, x, y, n_steps=6):
    """One xplane capture; returns (tmpdir, device ms/step)."""
    from paddle_tpu.profiler import _xplane

    tmp = tempfile.mkdtemp(prefix="xplane_rn_")
    with jax.profiler.trace(tmp):
        for _ in range(n_steps):
            loss = step_fn(x, y)
        float(loss)
    _, total_ns = _xplane.instr_profile(tmp)
    return tmp, total_ns / 1e6 / n_steps


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    batch = int(args[0]) if len(args) > 0 else 128
    top_n = int(args[1]) if len(args) > 1 else 40
    repeats = max(3, int(args[2])) if len(args) > 2 else 3

    step_fn, x, y = _build_step(batch)
    float(step_fn(x, y))
    float(step_fn(x, y))

    # >=3 independent captures: min/median/max, and the COMPARISON RULE
    # (dot_micro r6): any before/after optimizer-slice claim compares the
    # MEDIAN device ms/step — min and max are measurement luck; a
    # change is real only when the medians differ by >5%.
    caps = [_capture(step_fn, x, y) for _ in range(repeats)]
    times = sorted(ms for _, ms in caps)
    med = times[len(times) // 2]
    print(f"batch {batch}: device ms/step over {repeats} captures: "
          f"min {times[0]:.1f} / median {med:.1f} / max {times[-1]:.1f} "
          f"(compare MEDIANS; >5% medians = real)")

    from paddle_tpu.profiler import _xplane
    med_dir = min(caps, key=lambda c: abs(c[1] - med))[0]
    _xplane.print_instr_profile(med_dir, 6, top_n,
                                header=f"batch {batch} (median capture): ")


def dw_experiment():
    """Isolate the §3b '~2.5 ms bwd weight-layout copies' (chip mode):
    profile the identical train step in NHWC and NCHW and diff the
    per-instruction-class totals. The copy/transpose class is the dW
    layout suspect — if NHWC's copy class ~= NCHW's, the copies are
    intrinsic to conv backward (not schedulable); if NHWC >> NCHW they
    are NHWC-layout-specific and a dW-orientation kernel could attack
    them. Decision + numbers land in the ARCHITECTURE.md ledger."""
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    batch = int(args[0]) if len(args) > 0 else 128
    repeats = max(3, int(args[1])) if len(args) > 1 else 3
    from paddle_tpu.profiler import _xplane

    classes = ("copy", "transpose", "bitcast", "convolution", "fusion")
    for fmt in ("NHWC", "NCHW"):
        step_fn, x, y = _build_step(batch, data_format=fmt)
        float(step_fn(x, y))
        float(step_fn(x, y))
        rows = []
        for _ in range(repeats):
            tmp, ms = _capture(step_fn, x, y)
            agg, total = _xplane.instr_profile(tmp)
            by_class = {c: 0.0 for c in classes}
            other = 0.0
            for name, (calls, ns) in agg.items():
                for c in classes:
                    if name.startswith(c):
                        by_class[c] += ns / 1e6 / 6
                        break
                else:
                    other += ns / 1e6 / 6
            rows.append((ms, by_class, other))
        rows.sort(key=lambda r: r[0])
        ms, by_class, other = rows[len(rows) // 2]  # median capture
        cls = " ".join(f"{c}={v:.2f}" for c, v in by_class.items())
        print(f"{fmt}: median {ms:.1f} ms/step | {cls} other={other:.2f}")


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        print(smoke())
        print("fused-update smoke OK")
    elif "--dw" in sys.argv:
        dw_experiment()
    else:
        main()
