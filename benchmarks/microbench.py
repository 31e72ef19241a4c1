"""Shared slope-timing harness for on-chip microbenchmarks.

Methodology (see flash_micro.py for the original derivation): a kernel
of a few tens of microseconds is shorter than one host dispatch, so
per-call host timing is latency-bound. Instead, chain n kernel calls inside ONE
jitted ``lax.scan`` and take the slope between two loop lengths, which
cancels the fixed dispatch/transfer overhead.

Anti-elision measures (each was observed to be necessary):
- the first argument is perturbed by an ADDITIVE near-zero carry that
  depends on the previous output — a multiplicative scalar gets factored
  out of pure matmuls by XLA's algebraic simplifier, making the body
  loop-invariant and the loop time nothing;
- the output is consumed QUADRATICALLY (sum(o*o)): a single-element read
  lets XLA slice through a dot and DCE the rest of the matmul (observed
  "13,825 TF/s"), and a LINEAR sum gets rewritten
  reduce(dot) -> dot(reduce, reduce), skipping the matmul too (observed
  "260% of peak"). sum(o*o) distributes over neither; the reduce
  epilogue is ~0.01 ms of HBM traffic.

(flash_micro.py keeps its own single-element consumption: its pallas
custom calls are opaque to XLA, so slicing/reduction rewrites cannot
reach inside them.)
"""
import time

import jax
import jax.numpy as jnp


def slope_timeit(fn, args, iters, reps=5):
    """Per-iteration seconds of ``fn(*args)``, slope-timed on device."""
    def loop(c, a0, rest, n):
        def body(carry, _):
            out = fn(a0 + (carry - 1.0).astype(a0.dtype), *rest)
            o = jax.tree.leaves(out)[0].astype(jnp.float32)
            s = jnp.sum(o * o)
            return 1.0 + 1e-24 * s, None
        c, _ = jax.lax.scan(body, c, None, length=n)
        return c

    jloop = jax.jit(loop, static_argnums=(3,))
    c = jnp.float32(1.0)
    times = {}
    for n in (iters, 2 * iters):
        float(jloop(c, args[0], args[1:], n))  # compile + warm
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            float(jloop(c, args[0], args[1:], n))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        times[n] = best
    return (times[2 * iters] - times[iters]) / iters


def parse_overrides(argv):
    """key=value CLI args -> LlamaConfig override dict (ints and bools
    coerced) — shared by perf_lab/step_profile/hlo_map/compiler_opt_probe."""
    ov = {}
    for a in argv:
        k, v = a.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            v = {"True": True, "False": False}.get(v, v)
        ov[k] = v
    return ov
