"""Program-space registry: the serving bucket ladder as a declared,
statically enumerable object (ISSUE 15 tentpole).

Every compiled serving program is memoised under a small tuple key —
``("pseg", n_pad, s_max, steps)`` and friends. Until r20 those tuples
were constructed by hand at each jit call site in ``serving.py``, which
made the program space *implicit*: the only way to know what a config
could compile was to read the dispatch arithmetic, and the only way to
catch a width that escaped the ladder (the 2.5 s mid-serve XLA compile
class) was after it had already compiled (``analysis/recompile.py``'s
after-the-fact lint). This module makes the space explicit:

* each segment family registers its **key schema** (tag + axis names)
  and an **enumerator** — the closed-form arithmetic mapping an engine
  config + a declared :class:`WorkloadEnvelope` to the EXACT finite set
  of keys that config can reach;
* ``PROGRAM_SPACE.key(family, **axes)`` is the ONLY sanctioned key
  constructor — ``analysis/coverage.py`` lints the serving/scheduler/
  fleet ASTs for hand-built tagged tuples, so a new call site that
  bypasses the registry fails tier-1 before it can float a width;
* ``ServingEngine.program_space(envelope)`` returns the enumeration and
  ``ServingEngine.aot_warmup(envelope)`` compiles all of it at build,
  which is what turns the autoscaler's scale-up latency into a measured
  ``aot_warmup_s + first_token_s`` pair instead of an XLA lottery.

Key tuple formats are IDENTICAL to the hand-built r7–r17 tuples (tests
pin exact keys; ``_SHARED_PROGS`` entries stay byte-compatible) — the
registry changes who constructs them, never what they are.

The chunk-cap arithmetic (``chunk_for``) lives here too: the runtime
(``ServingEngine._prefill_chunk_for``) and the enumerator must agree on
the ladder-to-chunk mapping or coverage would diverge from dispatch —
one copy, imported by both.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, FrozenSet, Iterable, List, Optional, \
    Sequence, Tuple

__all__ = ["WorkloadEnvelope", "ProgramFamily", "ProgramSpace",
           "PROGRAM_SPACE", "FAMILY_TAGS", "chunk_for"]


# how many chunk steps a full-width prefill may take (the admission-
# throughput cap documented at ServingEngine._prefill_chunk_for — the
# runtime delegates here so dispatch and enumeration share one copy)
MAX_PREFILL_CHUNKS = 4


def chunk_for(prefill_chunks: Sequence[int], s_max: int) -> int:
    """Chunk width for an ``s_max``-wide admit window: the smallest
    declared ladder entry that bounds a full-width prefill at
    ``MAX_PREFILL_CHUNKS`` chunk steps (see the serving docstring for
    why the cap exists). The single copy of the cap arithmetic — the
    engine's ``_prefill_chunk_for`` and the ``cseg`` enumerator both
    call this."""
    for c in prefill_chunks:
        if c * MAX_PREFILL_CHUNKS >= s_max:
            return int(c)
    return int(prefill_chunks[-1])


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclass(frozen=True)
class WorkloadEnvelope:
    """The declared workload a serving deployment admits — the finite
    input domain the program-space enumeration closes over.

    * ``max_prompt`` — longest prompt a client may submit (must fit the
      engine's largest bucket; ``add_request`` enforces the same bound
      at intake, so the envelope is a declaration, not a hope).
    * ``max_new_tokens`` — largest generation a client may request.
    * ``seg_steps`` — every ``max_steps`` value the serve loop passes to
      ``run_segment``/``dispatch_segment`` (the scheduler's control-
      latency knob; ``ServingEngine.run()``'s drain loop uses
      ``4 * chunk``).
    * ``n_pads`` — the dispatch ``n_pad`` values; empty means the
      engine default (``pow2(slots)``), which every shipped caller
      uses.
    * ``resume`` — whether preempt-resume / failover-requeue admissions
      occur (they re-prefill prompt + generated-so-far, widening the
      reachable admission-length range to ``max_prompt +
      max_new_tokens - 1``; ``can_preempt`` caps it at the largest
      bucket).
    * ``prefix_block`` — the prefix cache's block size when one is
      attached (hit lengths are block multiples; None = no cache, so
      no suffix-bucketed widths are reachable).
    """
    max_prompt: int
    max_new_tokens: int
    seg_steps: Tuple[int, ...]
    n_pads: Tuple[int, ...] = ()
    resume: bool = True
    prefix_block: Optional[int] = None

    def __post_init__(self):
        if self.max_prompt < 1:
            raise ValueError(f"max_prompt must be >= 1, got "
                             f"{self.max_prompt}")
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")
        if not self.seg_steps:
            raise ValueError("envelope needs at least one seg_steps value")
        object.__setattr__(self, "seg_steps",
                           tuple(sorted({int(s) for s in self.seg_steps})))
        object.__setattr__(self, "n_pads",
                           tuple(sorted({int(n) for n in self.n_pads})))

    def admit_lengths(self, buckets: Sequence[int]) -> Tuple[int, int]:
        """(min, max) tokens one admission can prefill. Fresh requests
        prefill up to ``max_prompt``; a resume re-prefills prompt +
        generated-so-far, capped at the largest bucket (``can_preempt``
        refuses to preempt what could not re-admit; a fleet failover of
        an un-preemptable request re-prefills through the same bucketed
        window and would fail intake the same way a fresh overlong
        prompt does)."""
        hi = self.max_prompt
        if self.resume:
            hi = self.max_prompt + self.max_new_tokens - 1
        return 1, min(hi, max(buckets))


@dataclass(frozen=True)
class ProgramFamily:
    """One segment program-key family: schema + enumerator.

    ``tag`` is the leading string of the key tuple. ``axes`` name the
    remaining positions. ``enumerate_fn(engine, envelope)`` yields every
    key the family can reach from that config under that envelope;
    ``applies(engine)`` gates which families an engine config routes
    dispatches to. ``budget_program`` names the
    canonical gate program (``analysis/programs.py``) that stands in
    for this family in the budget registry — ``analysis.coverage``'s
    budget-completeness lint (r24) fails the gate if that program lacks
    a pinned ``peak_bytes_max``, so every reachable family has a
    statically bounded HBM peak."""
    name: str
    tag: str
    axes: Tuple[str, ...]
    doc: str
    enumerate_fn: Callable
    applies: Callable
    budget_program: Optional[str] = None

    def key(self, **kw) -> tuple:
        missing = [a for a in self.axes if a not in kw]
        extra = [k for k in kw if k not in self.axes]
        if missing or extra:
            raise TypeError(
                f"program family {self.name!r} takes axes {self.axes}; "
                f"missing {missing}, unexpected {extra}")
        return (self.tag,) + tuple(int(kw[a]) for a in self.axes)


class ProgramSpace:
    """The registry: families by name, the sanctioned key constructor,
    and the whole-config enumeration."""

    def __init__(self):
        self._families: Dict[str, ProgramFamily] = {}

    def register(self, family: ProgramFamily) -> ProgramFamily:
        if family.name in self._families:
            raise ValueError(f"program family {family.name!r} already "
                             f"registered")
        self._families[family.name] = family
        return family

    def family(self, name: str) -> ProgramFamily:
        if name not in self._families:
            raise KeyError(f"unknown program family {name!r}; registered: "
                           f"{sorted(self._families)}")
        return self._families[name]

    def families(self) -> List[str]:
        return sorted(self._families)

    def tags(self) -> FrozenSet[str]:
        return frozenset(f.tag for f in self._families.values())

    def key(self, name: str, **axes) -> tuple:
        """THE key constructor — every jit memo key in serving.py
        routes through here (enforced by ``analysis.coverage``'s AST
        lint: a hand-built tagged tuple anywhere in serving/scheduler/
        fleet fails tier-1)."""
        return self.family(name).key(**axes)

    def family_of(self, key: tuple) -> Optional[str]:
        """Which registered family a key tuple belongs to (None when
        the tuple matches no schema — the coverage differential treats
        that as an unenumerated compile)."""
        if not isinstance(key, tuple) or not key:
            return None
        for f in self._families.values():
            if f.tag == key[0] and len(key) == 1 + len(f.axes):
                return f.name
        return None

    def enumerate(self, engine, envelope: WorkloadEnvelope
                  ) -> FrozenSet[tuple]:
        """The EXACT finite key set ``engine``'s config can compile
        under ``envelope`` — the union of every applicable family's
        closed-form enumeration."""
        keys: set = set()
        for f in self._families.values():
            if f.applies(engine):
                keys.update(f.enumerate_fn(engine, envelope))
        return frozenset(keys)

    def enumerate_by_family(self, engine, envelope: WorkloadEnvelope
                            ) -> Dict[str, FrozenSet[tuple]]:
        return {f.name: frozenset(f.enumerate_fn(engine, envelope))
                for f in self._families.values() if f.applies(engine)}


PROGRAM_SPACE = ProgramSpace()


# --- shared enumeration arithmetic -----------------------------------------
# These mirror the dispatch-time width arithmetic in serving.py EXACTLY;
# analysis/coverage.py re-derives the same sets by brute-force replay of
# the admission arithmetic over the envelope's integer domain and
# asserts the two agree (the closed forms below are the fast path, the
# replay is the proof).


def _n_pads(engine, env: WorkloadEnvelope) -> Tuple[int, ...]:
    return env.n_pads or (_pow2(engine.slots),)


def _reachable_widths(engine, env: WorkloadEnvelope,
                      spec_pinned: bool) -> FrozenSet[int]:
    """Admit-window widths (s_max) a dispatch can produce.

    Without a prefix cache (or for the width-pinned spec family) every
    dispatch pins to the largest bucket. With one, a group containing
    at least one hit buckets by its longest SUFFIX — suffix lengths
    range over [1, L_adm] (a hit can shave any block multiple off any
    admissible length, and hit-less rows in the same group contribute
    their full length), so the reachable set is every bucket that
    covers some length ≤ L_adm, plus the always-reachable top bucket."""
    buckets = engine.buckets
    top = buckets[-1]
    if spec_pinned or env.prefix_block is None:
        return frozenset((top,))
    lo, hi = env.admit_lengths(buckets)
    if hi <= env.prefix_block:
        # no admissible length can carry a block-aligned hit AND a
        # nonempty suffix — suffix bucketing never engages
        return frozenset((top,))
    widths = {top}
    for b in buckets:
        if b >= lo:                     # covers some suffix length <= hi
            widths.add(b)
        if b >= hi:
            break
    return frozenset(widths)


# --- family registrations ---------------------------------------------------


def _quant(engine) -> Optional[str]:
    # getattr: coverage's replay probes run against lightweight engine
    # stand-ins in some tests; absent attr means not quantized
    return getattr(engine, "quant", None)


def _is_paged_plain(engine) -> bool:
    return (not engine.chunked and not engine.speculative
            and not engine.sampling and not engine.quality_digest
            and not _quant(engine))


def _is_paged_quality(engine) -> bool:
    return engine.quality_digest and not _quant(engine)


def _is_paged_quant(engine) -> bool:
    # r21: quant subsumes the plain/quality split — a quantized engine's
    # every paged segment (digests included) lives on the qpseg dtype
    # axis, because the compiled programs differ (narrow pool dtype +
    # scale planes) even where the loop structure is identical
    return bool(_quant(engine))


def _is_paged_chunked(engine) -> bool:
    return engine.chunked and not (engine.speculative or engine.sampling)


def _is_paged_spec(engine) -> bool:
    return bool(engine.speculative or engine.sampling)


def _seq_parallel(engine) -> int:
    # getattr for the same lightweight stand-in reason as _quant
    return int(getattr(engine, "seq_parallel", 0) or 0)


def _is_paged_sp(engine) -> bool:
    # r23: the spseg family ADDS to an sp engine's space (regular
    # traffic still rides pseg/cseg — those predicates are untouched)
    return _seq_parallel(engine) > 0


def sp_rungs(engine, env: WorkloadEnvelope) -> Tuple[int, ...]:
    """The ``long_buckets`` rungs a sequence-parallel engine can reach
    under ``env`` (r23). Engagement needs a first-admission suffix past
    the largest REGULAR bucket; continuations then shrink the suffix by
    whole slabs (``sp * C`` rows per landed slab), so reachable
    suffixes are every value congruent mod the slab width to some
    engaging length. Closed form over residues — the coverage replay
    re-derives the same set by brute-force (first-length x slab-count)
    walk and asserts equality."""
    lbs = engine.long_buckets
    top_b = engine.buckets[-1]
    cap = min(env.max_prompt, lbs[-1])
    if cap <= top_b:
        return ()
    Cs = _seq_parallel(engine) * engine.prefill_chunks[-1]
    residues = {f % Cs for f in range(top_b + 1,
                                      min(cap, top_b + Cs) + 1)}
    rungs = set()
    for s in range(1, cap + 1):
        if s % Cs not in residues:
            continue
        for b in lbs:
            if s <= b:
                rungs.add(b)
                break
    return tuple(sorted(rungs))


def _enum_pseg(engine, env: WorkloadEnvelope) -> Iterable[tuple]:
    fam = PROGRAM_SPACE.family("pseg")
    for n_pad in _n_pads(engine, env):
        for steps in env.seg_steps:
            for w in _reachable_widths(engine, env, spec_pinned=False):
                yield fam.key(n_pad=n_pad, s_max=w, steps=steps)


def _enum_qseg(engine, env: WorkloadEnvelope) -> Iterable[tuple]:
    fam = PROGRAM_SPACE.family("qseg")
    for n_pad in _n_pads(engine, env):
        for steps in env.seg_steps:
            for w in _reachable_widths(engine, env, spec_pinned=False):
                yield fam.key(n_pad=n_pad, s_max=w, steps=steps)


def _enum_qpseg(engine, env: WorkloadEnvelope) -> Iterable[tuple]:
    from ..quantization.serving import QUANT_CODES

    fam = PROGRAM_SPACE.family("qpseg")
    code = QUANT_CODES[_quant(engine)]
    for n_pad in _n_pads(engine, env):
        for steps in env.seg_steps:
            for w in _reachable_widths(engine, env, spec_pinned=False):
                yield fam.key(n_pad=n_pad, s_max=w, steps=steps,
                              dtype=code)


def _enum_cseg(engine, env: WorkloadEnvelope) -> Iterable[tuple]:
    fam = PROGRAM_SPACE.family("cseg")
    for n_pad in _n_pads(engine, env):
        for steps in env.seg_steps:
            for w in _reachable_widths(engine, env, spec_pinned=False):
                C = chunk_for(engine.prefill_chunks, w)
                s_max_c = -(-w // C) * C
                if steps < 2 * (s_max_c // C):
                    continue    # dispatch raises before building this key
                yield fam.key(n_pad=n_pad, s_max=s_max_c, c=C, steps=steps)


def _enum_spseg(engine, env: WorkloadEnvelope) -> Iterable[tuple]:
    fam = PROGRAM_SPACE.family("spseg")
    sp = _seq_parallel(engine)
    C = engine.prefill_chunks[-1]
    Cs = sp * C
    for n_pad in _n_pads(engine, env):
        for steps in env.seg_steps:
            for lb in sp_rungs(engine, env):
                yield fam.key(n_pad=n_pad, s_max=-(-lb // Cs) * Cs,
                              c=C, sp=sp, steps=steps)


def _enum_sseg(engine, env: WorkloadEnvelope) -> Iterable[tuple]:
    fam = PROGRAM_SPACE.family("sseg")
    for n_pad in _n_pads(engine, env):
        for steps in env.seg_steps:
            if steps < 2:
                continue        # dispatch raises before building this key
            yield fam.key(n_pad=n_pad, k=engine.speculative, steps=steps)


PROGRAM_SPACE.register(ProgramFamily(
    name="pseg", tag="pseg", axes=("n_pad", "s_max", "steps"),
    doc="r11 paged segment: ('pseg', n_pad, s_max, steps)",
    enumerate_fn=_enum_pseg, applies=_is_paged_plain,
    budget_program="paged_serving_segment"))

PROGRAM_SPACE.register(ProgramFamily(
    name="qseg", tag="qseg", axes=("n_pad", "s_max", "steps"),
    doc="r17 quality-digest paged segment: ('qseg', n_pad, s_max, steps)",
    enumerate_fn=_enum_qseg, applies=_is_paged_quality,
    budget_program="quality_serving_segment"))

PROGRAM_SPACE.register(ProgramFamily(
    name="qpseg", tag="qpseg", axes=("n_pad", "s_max", "steps", "dtype"),
    doc="r21 quantized paged segment: ('qpseg', n_pad, s_max, steps, "
        "dtype) — dtype is the declared QUANT_CODES code (int8=1, "
        "fp8=2); quality digests compose without a new axis (coverage "
        "is per-engine, and an engine fixes its digest setting)",
    enumerate_fn=_enum_qpseg, applies=_is_paged_quant,
    budget_program="quant_serving_segment"))

PROGRAM_SPACE.register(ProgramFamily(
    name="cseg", tag="cseg", axes=("n_pad", "s_max", "c", "steps"),
    doc="r13 chunked-prefill paged segment: ('cseg', n_pad, s_max_c, C, "
        "steps)",
    enumerate_fn=_enum_cseg, applies=_is_paged_chunked,
    budget_program="chunked_serving_segment"))

PROGRAM_SPACE.register(ProgramFamily(
    name="sseg", tag="sseg", axes=("n_pad", "k", "steps"),
    doc="r15 speculative/sampled paged segment: ('sseg', n_pad, K, "
        "steps) — width pinned to the largest bucket by design",
    enumerate_fn=_enum_sseg, applies=_is_paged_spec,
    budget_program="spec_serving_segment"))

PROGRAM_SPACE.register(ProgramFamily(
    name="spseg", tag="spseg", axes=("n_pad", "s_max", "c", "sp", "steps"),
    doc="r23 sequence-parallel long-context segment: ('spseg', n_pad, "
        "s_max, C, sp, steps) — s_max is a slab-rounded long_buckets "
        "rung, C the largest declared prefill chunk, sp the shard "
        "count (the slab's batch rows; the 'sp' mesh axis when one is "
        "set). Adds to (never replaces) the engine's pseg/cseg space: "
        "only prompts past the largest regular bucket engage it",
    enumerate_fn=_enum_spseg, applies=_is_paged_sp,
    budget_program="longctx_serving_segment"))


FAMILY_TAGS: FrozenSet[str] = PROGRAM_SPACE.tags()
