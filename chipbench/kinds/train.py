"""kind ``train``: the sharded train step on a one-device mesh, steps
chained by the donated state, each chain ended by one block_until_ready.

Driven through ``llama.init_params`` / ``init_opt_state`` / ``shard_state``
/ ``make_sharded_train_step`` and ``create_hybrid_mesh``.
"""

from __future__ import annotations

import math
import time

from .. import common, reference, traffic


def run(ctx) -> dict:
    from paddle_tpu.parallel import create_hybrid_mesh, set_mesh

    mesh = create_hybrid_mesh(devices=ctx["devices"][:1])
    try:
        return _run(ctx, mesh)
    finally:
        set_mesh(None)


def _run(ctx, mesh) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.models import llama

    config, workload, args = ctx["config"], ctx["workload"], ctx["args"]
    model = config["model"]
    cfg = common.llama_config(config, max_seq_len=workload["seq"])
    chain, lr = int(workload["chain"]), float(workload["lr"])

    def init(key):
        params = llama.init_params(cfg, key)
        return params, llama.init_opt_state(params)

    params, opt = jax.jit(init)(common.prng_key(args.seed))
    params, opt = llama.shard_state(cfg, mesh, params, opt)
    data_sh = NamedSharding(mesh, P(("dp", "sharding"), None))
    host = traffic.train_batches(workload, model["vocab_size"], args.seed)
    batches = [jax.device_put(b, data_sh) for b in host]
    step = llama.make_sharded_train_step(cfg, mesh, lr=lr)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt, batches[0], batches[0]).compile()
    text = compiled.as_text()
    ctx["log"]("compile", seconds=time.perf_counter() - t0,
               custom_calls=text.count("tpu_custom_call"))
    if not ctx["rehearse"] and "tpu_custom_call" not in text:
        raise SystemExit("chipbench: the compiled train step holds no "
                         "Pallas kernel")
    del text

    # -- correct, before the window: the step's loss against the plain
    # float32 reference at the parameters it started from, three steps on
    # one batch (the loss has to fall from the first to the fourth), and
    # the reference again at the parameters the three updates left: the
    # next step's loss has to agree with it. The two limits are the
    # workload file's: what this configuration's bf16 step was measured to
    # differ by, times a few
    atol_first, atol_updated = workload["loss_atol"]
    b0 = batches[0]
    ref_first = reference.loss(params, b0, model)
    losses = []
    for _ in range(3):
        params, opt, loss = compiled(params, opt, b0, b0)
        losses.append(float(loss))
    ref_after = reference.loss(params, b0, model)
    params, opt, loss = compiled(params, opt, b0, b0)
    losses.append(float(loss))
    ok = (all(math.isfinite(v) for v in losses)
          and abs(losses[0] - ref_first) <= atol_first
          and abs(losses[3] - ref_after) <= atol_updated
          and losses[3] < losses[0])
    ctx["log"]("check", losses=losses, reference_first=ref_first,
               reference_after_3=ref_after,
               tolerances=[atol_first, atol_updated], ok=ok)

    def run_chain(params, opt, i):
        for j in range(chain):
            b = batches[(i + j) % len(batches)]
            params, opt, loss = compiled(params, opt, b, b)
        loss.block_until_ready()
        return params, opt, loss

    params, opt, loss = run_chain(params, opt, 0)  # warm
    tracer = None
    if args.trace:
        tr = workload.get("trace", {})
        tracer = common.SliceTracer(
            ctx["trace_dir"], time.perf_counter(),
            tr.get("start_share", 0.35) * args.seconds, 0.0)
    steps, bad, traced_steps, chain_ms = 0, 0, 0, []
    watch = common.HostWatch()
    ctx["open_window"]()
    t_open = watch.start()
    while True:
        tracing = tracer is not None and tracer.maybe_start()
        t_chain = time.perf_counter()
        params, opt, loss = run_chain(params, opt, steps)
        chain_ms.append(round((time.perf_counter() - t_chain) * 1e3, 2))
        if tracing:
            tracer.maybe_stop(force=True)
            traced_steps = chain
        steps += chain
        elapsed = time.perf_counter() - t_open
        bad += not math.isfinite(float(loss))
        if elapsed >= args.seconds:
            break
    ctx["close_window"]()
    host = watch.stop()
    tokens = int(workload["batch"]) * int(workload["seq"])
    rate = tokens * steps / elapsed
    ctx["log"]("train", steps=steps, window_s=elapsed,
               step_ms=elapsed / steps * 1e3, train_tokens_per_s=rate,
               last_loss=float(loss), chain_ms=chain_ms, **host)
    return {
        "kind": "train", "attempted": steps, "failed": bad * chain,
        "correct": bool(ok and bad == 0),
        "end_to_end": {"train_tokens_per_s": rate},
        "train": {"steps": steps, "window_s": elapsed, "tokens": tokens,
                  "seq": int(workload["seq"])},
        "slice": ({"steps": traced_steps, "window_s": tracer.window_s}
                  if tracer is not None else None),
    }
