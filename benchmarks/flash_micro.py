"""Micro-benchmark for the packed flash-attention kernels on the real chip.

Times the packed forward and the fused packed backward in isolation at the
headline bench shape (b44 s512 h12 d64, causal), so kernel experiments can
iterate without paying a full train-step compile. Methodology matches
bench.py: jit once, chain iterations, force completion with a scalar fetch.

Usage: python benchmarks/flash_micro.py [b S h d iters]
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, args, iters, tag):
    """On-device loop: chained kernel calls inside ONE jitted scan (a
    kernel of a few tens of microseconds is shorter than one host
    dispatch, so per-call host timing is latency-bound). The first arg is multiplied by a carry
    that DEPENDS on the previous output — without that data dependence XLA
    hoists the loop-invariant kernel out of the scan and the loop times
    nothing. Per-iteration cost = slope between two loop lengths, which
    cancels the fixed dispatch/transfer overhead."""
    def loop(c, a0, rest, n):
        def body(carry, _):
            # ADD the near-zero carry: a multiplicative scalar gets factored
            # out of pure matmuls by XLA's algebraic simplifier (making the
            # body loop-invariant again); addition does not
            out = fn(a0 + (carry - 1.0).astype(a0.dtype), *rest)
            s = jax.tree.leaves(out)[0].astype(jnp.float32).ravel()[0]
            return 1.0 + 1e-24 * s, None  # ~1.0, but loop-variant
        c, _ = jax.lax.scan(body, c, None, length=n)
        return c
    jloop = jax.jit(loop, static_argnums=(3,))
    c = jnp.float32(1.0)
    times = {}
    for n in (iters, 2 * iters):
        float(jloop(c, args[0], args[1:], n))  # compile + warm
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            float(jloop(c, args[0], args[1:], n))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        times[n] = best
    per = (times[2 * iters] - times[iters]) / iters
    print(f"{tag}: {per*1e3:.3f} ms", flush=True)
    return per


def main():
    b, S, h, d, iters = 44, 512, 12, 64, 30
    argv = [int(a) for a in sys.argv[1:]]
    if argv:
        b, S, h, d, iters = argv + [b, S, h, d, iters][len(argv):]
    from paddle_tpu.ops.pallas import flash_attention as F

    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(b, S, h, d), jnp.bfloat16)
    q, k, v, do = mk(), mk(), mk(), mk()
    print(f"devices: {jax.devices()}  shape b{b} S{S} h{h} d{d}", flush=True)

    fwd = jax.jit(lambda q, k, v: F._pallas_flash_fwd_packed(q, k, v, True))
    out, lse = fwd(q, k, v)
    t_f = timeit(fwd, (q, k, v), iters, "packed fwd (out+lse)")

    bwd = jax.jit(lambda q, k, v, do, out, lse:
                  F._pallas_flash_bwd_packed(q, k, v, do, out, lse, True))
    t_b = timeit(bwd, (q, k, v, do, out, lse), iters, "packed bwd (dq,dk,dv)")

    # an MXU yardstick: one bf16 matmul with the same FLOP count as fwd
    # attention (4*B*H*S*S*D fwd; bwd is 2.5x)
    flops_f = 4 * b * h * S * S * d
    M = 4096
    Kd = max(128, flops_f // (2 * M * M))
    a1 = jnp.asarray(rng.randn(M, Kd), jnp.bfloat16)
    a2 = jnp.asarray(rng.randn(Kd, M), jnp.bfloat16)
    mm = jax.jit(lambda x, y: x @ y)
    t_m = timeit(mm, (a1, a2), iters, f"matmul yardstick ({M}x{Kd}x{M})")
    print(f"fwd {t_f*1e3:.3f} ms vs matmul-equal-flops {t_m*1e3:.3f} ms "
          f"(x{t_f/t_m:.1f}); bwd {t_b*1e3:.3f} ms (~2.5x flops -> "
          f"x{t_b/(2.5*t_m):.1f})", flush=True)


if __name__ == "__main__":
    main()
