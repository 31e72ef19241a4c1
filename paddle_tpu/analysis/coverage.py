"""Program-space coverage auditor (ISSUE 15 tentpole, part b).

Three passes that together prove a serving config can never pay the
2.5 s mid-serve XLA compile:

1. **Registry-only lint** (``lint_registry_only``) — grep-the-AST over
   the serving/scheduler/fleet sources for hand-built program-key
   tuples (an ``ast.Tuple`` whose first element is a registered family
   tag). Every jit memo key must be constructed through
   ``serving.PROGRAM_SPACE.key`` — a bypassing call site is exactly how
   a width floats past the declared ladder, and this lint fails tier-1
   before it can.
2. **Envelope reachability replay** (``reachable_keys_replay``) — the
   registry's closed-form enumerators are fast arithmetic; this pass
   re-derives the reachable key set by brute-force replay of the
   ACTUAL admission arithmetic (bucket mapping, prefix-hit suffix
   widths, chunk-cap ladder, preempt-resume/failover length rewind,
   spec width pinning) over the envelope's integer domain, per length
   and hit offset, through the engine's own helpers. ``check_envelope``
   asserts replay ⊆ enumeration — the proof that every
   runtime-reachable key is in the enumerated set.
3. **Enumerated-vs-used differential** (``coverage_report``) — after a
   serve, diff the enumeration against what the engine actually
   compiled/used: an UNENUMERATED key is a gate FAIL (something
   escaped the envelope — the mid-serve-compile class), an unreached
   ladder entry is a dead-weight warning with its AOT compile-seconds
   attributed (``engine.aot_key_seconds``) so over-declared envelopes
   have a visible bill.

``aot_audit`` is the gate's entry: lint + enumerate + ``aot_warmup`` +
reachability proof in one call, returning the per-family size/seconds
report ``python -m paddle_tpu.analysis --gate --aot on`` prints.
"""

from __future__ import annotations

import ast
import inspect
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

__all__ = ["lint_registry_only", "lint_source", "lint_budget_coverage",
           "reachable_keys_replay", "check_envelope", "coverage_report",
           "aot_audit", "CoverageReport"]


def _registry():
    from ..inference.program_space import PROGRAM_SPACE
    return PROGRAM_SPACE


# --- 1. registry-only construction lint ------------------------------------

def lint_source(source: str, name: str,
                tags: Optional[FrozenSet[str]] = None) -> List[str]:
    """AST-lint one module source for hand-built program-key tuples.
    Flags every tuple literal whose first element is a registered
    family tag string — those MUST come from ``PROGRAM_SPACE.key``.
    String/docstring mentions don't parse as tuples, so prose stays
    free to name the families."""
    if tags is None:
        tags = _registry().tags()
    out: List[str] = []
    tree = ast.parse(source)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Tuple) or not node.elts:
            continue
        head = node.elts[0]
        if isinstance(head, ast.Constant) and head.value in tags:
            out.append(
                f"{name}:{node.lineno}: hand-built ({head.value!r}, ...) "
                f"program-key tuple — construct it via "
                f"serving.PROGRAM_SPACE.key({head.value!r}, ...) so the "
                f"coverage enumeration sees it")
    return out


def lint_registry_only(modules: Sequence = ()) -> List[str]:
    """Lint the serving-stack modules (default: serving, scheduler,
    fleet — every module that dispatches segment programs) for key
    construction outside the registry. Empty list = clean."""
    if not modules:
        from ..inference import fleet, scheduler, serving
        modules = (serving, scheduler, fleet)
    out: List[str] = []
    for mod in modules:
        out.extend(lint_source(inspect.getsource(mod), mod.__name__))
    return out


# --- 2. envelope reachability replay ---------------------------------------

def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def reachable_keys_replay(engine, envelope) -> FrozenSet[tuple]:
    """Brute-force the reachable key set by replaying the admission
    arithmetic over the envelope's integer domain.

    For every admissible prefill length L (fresh prompt lengths up to
    ``max_prompt``; with ``resume``, preempt/failover re-admissions up
    to ``max_prompt + max_new_tokens - 1`` capped at the largest bucket
    — the ``can_preempt`` bound) and every block-aligned prefix-hit
    length h < L, compute the key the dispatch path would build for a
    group whose extremes are (L, h), THROUGH the engine's own width
    helpers (``_bucket_for``, ``_prefill_chunk_for``) so the replay
    tests the runtime arithmetic, not a re-implementation of it."""
    from ..inference.program_space import PROGRAM_SPACE

    space = PROGRAM_SPACE
    env = envelope
    keys: set = set()
    buckets = engine.buckets
    top = buckets[-1]
    lo, hi = env.admit_lengths(buckets)
    blk = env.prefix_block
    n_pads = env.n_pads or (_pow2(engine.slots),)
    spec = bool(engine.speculative or engine.sampling)

    # suffix widths a dispatch group can produce: the no-hit group pins
    # to the top bucket; a group with >= 1 hit buckets its longest
    # suffix — any (L, h) pair yields suffix L - h, and a hit-less row
    # in the same group can raise suf_max to any admissible length
    widths = {top}
    hits_possible = blk is not None and hi > blk
    if hits_possible and not spec:
        for L in range(lo, hi + 1):
            for h in range(blk, L, blk):
                widths.add(engine._bucket_for(L - h))
            # a mixed group: some OTHER row hit (so suffix bucketing
            # engages — possible whenever any admissible length can
            # carry a hit) while THIS row missed and contributes its
            # full length as the group's longest suffix
            widths.add(engine._bucket_for(L))
    # r23 sequence-parallel long-context (spseg): replay the long-rung
    # arithmetic by brute force — for every ENGAGING first-admission
    # suffix (past the largest regular bucket, up to the envelope /
    # long-ladder cap) walk the continuation chain down one slab
    # (sp * C rows) at a time, mapping each surviving suffix through
    # the engine's own rung helper. The closed-form enumerator derives
    # the same set via residues; check_envelope asserts they agree.
    sp = int(getattr(engine, "seq_parallel", 0) or 0)
    sp_widths: set = set()
    if sp:
        C = engine.prefill_chunks[-1]
        Cs = sp * C
        cap = min(env.max_prompt, engine.long_buckets[-1])
        for L in range(top + 1, cap + 1):
            s = L
            while s > 0:
                lb = engine._long_rung(s)
                sp_widths.add((-(-lb // Cs) * Cs, C))
                s -= Cs

    for n_pad in n_pads:
        for steps in env.seg_steps:
            for w, c in sp_widths:
                keys.add(space.key("spseg", n_pad=n_pad, s_max=w,
                                   c=c, sp=sp, steps=steps))
            if spec:
                if steps >= 2:
                    keys.add(space.key("sseg", n_pad=n_pad,
                                       k=engine.speculative,
                                       steps=steps))
            elif engine.chunked:
                for w in widths:
                    C = engine._prefill_chunk_for(w)
                    s_max_c = -(-w // C) * C
                    if steps >= 2 * (s_max_c // C):
                        keys.add(space.key("cseg", n_pad=n_pad,
                                           s_max=s_max_c, c=C,
                                           steps=steps))
            elif getattr(engine, "quant", None):
                from ..quantization.serving import QUANT_CODES

                code = QUANT_CODES[engine.quant]
                for w in widths:
                    keys.add(space.key("qpseg", n_pad=n_pad, s_max=w,
                                       steps=steps, dtype=code))
            else:
                fam = "qseg" if engine.quality_digest else "pseg"
                for w in widths:
                    keys.add(space.key(fam, n_pad=n_pad, s_max=w,
                                       steps=steps))
    return frozenset(keys)


def check_envelope(engine, envelope) -> List[str]:
    """The reachability proof: every key the admission-arithmetic
    replay derives must be in the closed-form enumeration (and vice
    versa — a closed form that over-enumerates is dead weight by
    construction and flagged too). Empty list = the enumeration is
    exactly the reachable set."""
    enumerated = frozenset().union(
        *engine.program_space(envelope).values())
    replayed = reachable_keys_replay(engine, envelope)
    out = [f"reachable key {k} escapes the enumeration (envelope "
           f"replay derived it; program_space did not)"
           for k in sorted(replayed - enumerated, key=repr)]
    out += [f"enumerated key {k} is unreachable (no admission "
            f"arithmetic replay produces it)"
            for k in sorted(enumerated - replayed, key=repr)]
    return out


# --- 3. enumerated-vs-used differential ------------------------------------

@dataclass
class CoverageReport:
    program_space_size: int
    families: Dict[str, int]
    lint: List[str]
    envelope_mismatches: List[str]
    unenumerated: List[tuple]          # compiled/used but NOT enumerated
    unreached: List[Tuple[tuple, float]]  # enumerated, never used (+ s)
    aot_warmup_s: Optional[float] = None

    @property
    def ok(self) -> bool:
        """Gate verdict: construction linted clean, the reachability
        proof holds, and nothing compiled outside the enumeration.
        Unreached entries are warnings (dead ladder weight), not
        failures."""
        return not (self.lint or self.envelope_mismatches
                    or self.unenumerated)

    def format(self) -> str:
        lines = [f"program space: {self.program_space_size} keys "
                 + "(" + ", ".join(f"{f}: {n}" for f, n in
                                   sorted(self.families.items())) + ")"]
        if self.aot_warmup_s is not None:
            lines.append(f"aot warmup: {self.aot_warmup_s:.3f}s")
        for v in self.lint:
            lines.append(f"LINT: {v}")
        for v in self.envelope_mismatches:
            lines.append(f"ENVELOPE: {v}")
        for k in self.unenumerated:
            lines.append(f"UNENUMERATED COMPILE: {k} — a program key "
                         f"escaped the declared envelope (gate FAIL)")
        for k, s in self.unreached:
            lines.append(f"dead ladder weight: {k} never used "
                         f"(aot compile cost {s:.3f}s)")
        return "\n".join(lines)


def coverage_report(engine, envelope=None,
                    lint: bool = True) -> CoverageReport:
    """Diff the enumeration against what the engine actually compiled
    and (post-``aot_warmup``) actually USED. Call after a serve."""
    env = envelope or engine.default_envelope()
    by_family = engine.program_space(env)
    enumerated = frozenset().union(*by_family.values()) \
        if by_family else frozenset()
    compiled = set(engine._progs)
    used = set(engine.prog_key_hits)
    seen = compiled | used
    if engine.aot_warmup_s is not None:
        # every enumerated key was compiled at warmup; the interesting
        # side is what the serve traffic actually TOUCHED since
        reached = used
    else:
        reached = compiled
    unreached = [(k, engine.aot_key_seconds.get(k, 0.0))
                 for k in sorted(enumerated - reached, key=repr)]
    return CoverageReport(
        program_space_size=len(enumerated),
        families={f: len(v) for f, v in by_family.items()},
        lint=lint_registry_only() if lint else [],
        envelope_mismatches=check_envelope(engine, env),
        unenumerated=sorted(seen - enumerated, key=repr),
        unreached=unreached,
        aot_warmup_s=engine.aot_warmup_s)


def aot_audit(engine, envelope=None) -> dict:
    """The gate's AOT entry (``--aot on``): lint construction, prove
    the enumeration against the envelope replay, compile the full
    ladder, and return the printable per-family report. Raises
    AssertionError on a lint/reachability failure — those are
    structural bugs, not budget regressions."""
    env = envelope or engine.default_envelope()
    lint = lint_registry_only()
    assert not lint, "program-key construction outside the registry:\n" \
        + "\n".join(lint)
    mismatches = check_envelope(engine, env)
    assert not mismatches, "enumeration/reachability divergence:\n" \
        + "\n".join(mismatches)
    fam_report = engine.aot_warmup(env)
    return {
        "program_space_keys": sum(r["keys"] for r in fam_report.values()),
        "aot_warmup_s": round(engine.aot_warmup_s, 4),
        "families": {f: {"keys": r["keys"],
                         "seconds": round(r["seconds"], 4)}
                     for f, r in fam_report.items()},
    }


# --- 4. budget-registry completeness lint (r24) -----------------------------

def lint_budget_coverage(program_names: Optional[Sequence[str]] = None,
                         families: Optional[Sequence[str]] = None
                         ) -> List[str]:
    """Budget completeness is machine-checked, not convention: every
    registered canonical program AND every ``PROGRAM_SPACE`` family's
    declared ``budget_program`` must carry a budget entry with the r24
    ``peak_bytes_max`` ceiling pinned. The gate runs this alongside the
    per-program audits and FAILS on any gap — a new program or family
    cannot land without a statically bounded HBM peak. Empty list =
    complete. ``program_names``/``families`` default to the live
    registries (overridable so tests can prove the lint fires on a
    deliberately unregistered program)."""
    from . import budgets, programs

    if program_names is None:
        program_names = programs.names()
    reg = _registry()
    if families is None:
        families = reg.families()
    out: List[str] = []
    for name in program_names:
        b = budgets.BUDGETS.get(name)
        if b is None:
            out.append(f"canonical program {name!r} has no budget entry "
                       f"in analysis/budgets.py")
        elif b.peak_bytes_max is None:
            out.append(f"canonical program {name!r} has no peak_bytes_max "
                       f"— pin the measured HBM liveness peak (+<=5%)")
    for fam_name in families:
        try:
            fam = reg.family(fam_name)
        except KeyError:
            out.append(f"program family {fam_name!r} is not registered "
                       f"in PROGRAM_SPACE")
            continue
        prog = fam.budget_program
        if prog is None:
            out.append(f"program family {fam_name!r} declares no "
                       f"budget_program — name the canonical gate "
                       f"program that stands in for it")
            continue
        if prog not in programs.names():
            out.append(f"program family {fam_name!r} maps to unknown "
                       f"canonical program {prog!r}")
            continue
        b = budgets.BUDGETS.get(prog)
        if b is None or b.peak_bytes_max is None:
            out.append(f"program family {fam_name!r} maps to {prog!r} "
                       f"which lacks a pinned peak_bytes_max")
    return out
