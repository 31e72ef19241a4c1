"""Recompile-hazard lint — the mid-serve XLA compile class.

r7's worst latency bug was a single stray program shape: a floating
prompt width let one segment arrive 64-wide instead of bucket-wide and
XLA compiled for 2.5 s in the middle of an online serve (vs ~60 ms of
actual work). The fix was shape pinning; this pass makes the CLASS of
bug visible before it costs a latency cliff:

* ``CompileWatch`` counts real backend compilations (via
  ``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``
  event) over a region. Budgets pin warm-replay compiles to ZERO — a
  warmed workload that still compiles is re-specialising on something.
* ``lint_cache_keys`` inspects a program cache's keys (the
  introspection hooks ``jit.TracedProgram.cache_info`` /
  ``jit.FusedTrainStep.cache_info`` / ``ServingEngine.cache_info``
  expose them) and flags unbucketed dynamic dims: many distinct shape
  signatures for one structurally-identical program means some input
  dim floats free and every new value will pay a fresh XLA compile.
* ``live_cache_report`` sweeps every registered live program cache
  (``jit.live_program_caches``) in one call.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["CompileWatch", "lint_cache_keys", "live_cache_report",
           "CompileBudgetError", "enforce_zero_compiles"]


class CompileWatch:
    """Count backend compilations inside the context.

    Uses the jax monitoring bus, so it sees EVERY XLA compile in the
    process — jitted framework programs, eager-op singletons, pallas
    kernels — not just the callable under audit. Warm the workload
    first; then a nonzero count during replay IS the hazard (nothing in
    a warmed loop should be compiling)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self._baseline = 0

    def _listener(self, event: str, duration: float, **kw) -> None:
        if event == self._EVENT:
            self.compiles += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._listener)
        return False

    def mark(self) -> None:
        """Start a fresh count (end of warmup)."""
        self._baseline = self.compiles

    @property
    def since_mark(self) -> int:
        return self.compiles - self._baseline


class CompileBudgetError(AssertionError):
    """A backend compile happened inside a region pinned to zero."""


@contextlib.contextmanager
def enforce_zero_compiles(label: str = "post-warmup serve"):
    """The hard zero-post-warmup-backend-compiles budget (r20,
    ISSUE 15): after ``ServingEngine.aot_warmup`` has compiled the full
    enumerated program space, a serve that stays inside its declared
    :class:`~paddle_tpu.inference.program_space.WorkloadEnvelope` must
    perform ZERO backend compiles over the whole mixed workload —
    speculation, chunked prefill, preempt/resume, shedding, failover,
    tiers and shadow included. Any compile inside the region raises
    :class:`CompileBudgetError` (it IS the 2.5 s mid-serve latency
    cliff, caught at test time instead of at p99)::

        eng.aot_warmup(envelope)
        with analysis.recompile.enforce_zero_compiles("mixed serve"):
            scheduler.serve(trace)

    Yields the underlying :class:`CompileWatch` so callers can inspect
    the count mid-region."""
    with CompileWatch() as cw:
        yield cw
        if cw.compiles:
            raise CompileBudgetError(
                f"{cw.compiles} backend compile(s) during {label} — the "
                f"zero-post-AOT-warmup budget is 0 (a program shape "
                f"escaped the declared envelope, or warmup missed an "
                f"enumerated key)")


@dataclass
class CacheLint:
    name: str                      # program/cache identity
    n_entries: int
    n_shape_variants: int          # max distinct shape sigs per structure
    hazard: bool
    detail: str = ""
    variants: List[Any] = field(default_factory=list)


def _split_key(key: Any) -> Tuple[Any, Any]:
    """(structure, shape-signature) halves of a cache key.

    The jit caches key on ``(arg_tree, shapes, ..., training, ...)``
    with the shape signature as a tuple of ``((dims...), dtype)`` pairs;
    serving keys are ``(bucket, nb)`` / ``("seg", n_pad, s_max, pre_max,
    steps)`` — already fully bucketed, so each is its own structure."""
    if isinstance(key, tuple):
        shapes = [p for p in key
                  if isinstance(p, tuple) and p and all(
                      isinstance(e, tuple) and len(e) == 2
                      and isinstance(e[0], tuple)
                      and isinstance(e[1], str) for e in p)]
        if shapes:
            rest = tuple(p for p in key if not any(p is s for s in shapes))
            return rest, tuple(shapes)
    return key, None


def lint_cache_keys(name: str, keys: Sequence[Any],
                    max_shape_variants: int = 4) -> CacheLint:
    """Flag a program cache whose keys differ ONLY by input shapes more
    than ``max_shape_variants`` ways — the unbucketed-dynamic-dim
    signature. A cache with many structurally different entries (other
    static args, train/eval) is fine; one structure recompiled per
    arriving shape is the 2.5 s-mid-serve class."""
    by_structure: Dict[Any, set] = {}
    for k in keys:
        structure, shapes = _split_key(k)
        try:
            by_structure.setdefault(structure, set()).add(shapes)
        except TypeError:  # unhashable structure: count it solo
            by_structure.setdefault(repr(structure), set()).add(repr(shapes))
    worst = max((len(v) for v in by_structure.values()), default=0)
    hazard = worst > max_shape_variants
    detail = ""
    variants: List[Any] = []
    if hazard:
        structure = max(by_structure, key=lambda s: len(by_structure[s]))
        variants = sorted(map(repr, by_structure[structure]))
        detail = (f"{worst} shape variants compiled for one program "
                  f"structure (> {max_shape_variants}): likely an "
                  f"unbucketed dynamic dim. Shapes: "
                  + "; ".join(variants[:6])
                  + ("; ..." if len(variants) > 6 else ""))
    return CacheLint(name=name, n_entries=len(list(keys)),
                     n_shape_variants=worst, hazard=hazard, detail=detail,
                     variants=variants)


def live_cache_report(max_shape_variants: int = 4) -> List[CacheLint]:
    """Lint every live registered program cache in the process."""
    from .. import jit

    out = []
    for obj in jit.live_program_caches():
        info = obj.cache_info()
        out.append(lint_cache_keys(info["name"], info["keys"],
                                   max_shape_variants=max_shape_variants))
    return out
