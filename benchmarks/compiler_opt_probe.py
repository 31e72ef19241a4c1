"""Probe TPU compiler options on the headline step via compile-time
compiler_options (set per compile, so one process can compare several).
Usage: python compiler_opt_probe.py [key=value ...] — no args = baseline."""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    opts = {}
    for a in sys.argv[1:]:
        k, v = a.split("=", 1)
        opts[k] = v
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import create_hybrid_mesh, set_mesh

    batch, seq = 48, 512
    cfg = llama.LlamaConfig.bert_base_equiv(max_seq_len=seq)
    mesh = create_hybrid_mesh(devices=jax.devices()[:1])
    params = llama.init_params(cfg)
    opt_state = llama.init_opt_state(params)
    rng = np.random.RandomState(0)
    tokens = jnp.array(rng.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    step = llama.make_sharded_train_step(cfg, mesh, lr=1e-4)
    lowered = step.lower(params, opt_state, tokens, tokens)
    try:
        compiled = lowered.compile(compiler_options=opts or None)
    except Exception as e:
        print(f"[{opts}] compile REJECTED: {type(e).__name__}: {str(e)[:300]}",
              flush=True)
        set_mesh(None)
        return
    params, opt_state, loss = compiled(params, opt_state, tokens, tokens)
    float(loss)
    params, opt_state, loss = compiled(params, opt_state, tokens, tokens)
    float(loss)
    iters, best = 20, None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = compiled(params, opt_state, tokens,
                                               tokens)
        float(loss)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    tps = iters * batch * seq / best
    print(f"[{opts}] {tps:,.0f} tok/s, step {best/iters*1e3:.1f} ms",
          flush=True)
    set_mesh(None)


if __name__ == "__main__":
    main()
