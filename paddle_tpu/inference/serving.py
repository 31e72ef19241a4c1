"""Continuous-batching generation engine (serving-shaped decode).

Reference counterpart: Paddle Inference / PaddleNLP's serving stack
(SURVEY.md §2.1 inference row: dynamic batching over the KV cache). The
reference's GPU serving engines (and vLLM-style systems) keep a fixed pool
of decode slots and swap finished requests out for queued ones so the
batch stays full — that scheduling idea, TPU-native:

* **One KV layout: the paged pool** (``inference/paged_kv.py``). One flat
  ``[L, pages, page, Hkv*D]`` plane per K and V plus per-slot page tables;
  admission is gated on pages free, a shared prefix is shared pages
  (``PagedPrefixCache``), and the pool rides every program's loop carry
  so it is updated in place. The model's side of it —
  ``forward_with_pages``, ``init_paged_pool``, the attention kernel —
  comes from ``models.family_of(cfg)``.
* **A segment is ONE compiled program** (``_paged_segment_prog`` and its
  chunked / sequence-parallel / speculative siblings): slot state lives
  on device and a step-bounded ``while_loop`` alternates admit (prefill
  of the next queued request into a free slot) and decode ticks over
  all slots. Admission costs no host round trip, so refill is greedy;
  the host pays one dispatch + one fetch per segment and replays the
  event log (``_replay_segment``) to hand tokens to requests.
* **Fixed-shape compiled programs.** Decode is a ragged tick over all
  slots with per-slot positions and per-slot REMAINING counts: a slot
  freezes in-program the step its request completes. Shapes never
  depend on request sizes — nothing recompiles as requests come and go,
  and ``program_space.PROGRAM_SPACE`` enumerates every key a
  configuration can reach (``aot_warmup`` compiles them at build).
* ``run()`` drains the queue with segments back to back;
  ``OnlineScheduler`` / ``FleetRouter`` drive ``dispatch_segment`` /
  ``finish_segment`` under arriving traffic.

Greedy decoding (temperature 0) — matching ``llama.generate``'s default —
so engine output is bit-comparable to that reference request-by-request
(``llama.generate`` keeps the contiguous cache; the engine has none).
``eos_token_id`` freezes a slot in-program the step EOS is emitted.

r15 (ISSUE 10): **speculative + sampled decoding inside the segment
program**. ``ServingEngine(speculative=K)`` drafts K tokens per live
slot from the slot's page-resident token history (in-program n-gram
lookup) and verifies all K+1 positions in ONE tick through the paged
q_len>1 path — accepted-length > 1 tokens per weight stream, the lever
that beats the HBM decode roofline (SCALING §3j). ``sampling=
{"temperature", "top_k", "top_p"}`` samples in-program with per-slot
RNG keys carried in segment state, seeded per request (deterministic
replay); greedy stays the default and bit-identical. Both ride the
``("sseg", n_pad, K, steps)`` program family and keep the audited
one-dispatch/one-fetch contract — acceptance counts travel in the same
event fetch and the host replay recovers per-request accepted lengths.
"""

from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.syncs import allowed_sync
from ..models import family_of, llama, require
from ..observability import flight as _flight
from ..observability import journal as _journal
from ..observability import metrics as _metrics
from ..profiler import _hooks
from .program_space import PROGRAM_SPACE, WorkloadEnvelope, chunk_for

__all__ = ["Request", "ServingEngine", "SEGMENT_HOOKS", "PROGRAM_SPACE",
           "WorkloadEnvelope"]

# Process-wide segment observers (r14, ISSUE 9): ``fn(steps, new_tokens,
# n_finished)`` called from ``_segment_telemetry`` after every segment's
# host replay — host ints only, so a hook can never add a device sync.
# ``observability.slo.install`` / ``observability.perf.install`` use this
# to attach the SLO monitor and the explained-perf interval accumulator
# to ANY engine (the analysis gate's --ops mode rides it: the canonical
# serving programs replay through run_segment with no scheduler in the
# loop, and the monitors must still see every segment). Empty by
# default — the common case costs one truthiness check per segment.
SEGMENT_HOOKS: List = []


@contextlib.contextmanager
def _mesh_scope(mesh):
    """Make ``mesh`` the global mesh for the duration of a program
    build/call (r12 tensor-parallel serving): the model's sharding
    constraints (``with_sharding_constraint``) read the global mesh at
    TRACE time, so an mp-sharded engine must trace its segment programs
    under its own mesh without leaking it into unrelated callers (tests
    and sibling engines pin ``set_mesh(None)``)."""
    if mesh is None:
        yield
        return
    from ..parallel.mesh import get_mesh, set_mesh

    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield
    finally:
        set_mesh(prev)


# --- in-program sampling primitives (r15, ISSUE 10) -----------------------
# Per-slot RNG state rides the segment as RAW uint32 [slots, 2] key data
# (threefry): raw keys scatter/donate like any int array, so the while-
# body carries stay trivial. All of these run INSIDE compiled programs.

def _split_rows(raw):
    """Advance each row's key: (next_state [n,2], consume [n,2])."""
    nk = jax.vmap(jax.random.split)(raw)
    return nk[:, 0], nk[:, 1]


def _subkeys_rows(raw, n: int):
    """n consumable subkeys per row: [rows, n, 2]."""
    return jax.vmap(lambda k: jax.random.split(k, n))(raw)


def _one_of(pred, a, b, st):
    """``lax.cond(pred, a, b, st)`` for a loop state that holds the paged
    pool: two loops of zero or one trip, ``a`` when ``pred`` and ``b``
    when not. Same branches, same result — but a ``while`` carries its
    state in place, where a ``conditional`` gives each branch a copy of
    every buffer the branch updates: around ``forward_with_pages`` that
    is the whole K and V pool once a step and twice per LAYER inside the
    branch (PERF.md, PR 26). ``tests/test_chip_compile.py
    ::test_paged_segment_holds_pool_once`` keeps the compiled program
    free of them."""
    n = jnp.asarray(pred).astype(jnp.int32)
    st = jax.lax.fori_loop(0, n, lambda _, s: a(s), st)
    return jax.lax.fori_loop(0, 1 - n, lambda _, s: b(s), st)


@llama.scoped("sample")
def _greedy(logits):
    """Every program's greedy token pick, under its scope's name."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _categorical_rows(filt, keys):
    """One independent categorical draw per row: ``filt`` [..., V]
    filtered logits, ``keys`` [..., 2] raw key per row."""
    V = filt.shape[-1]
    toks = jax.vmap(jax.random.categorical)(
        keys.reshape(-1, 2), filt.reshape(-1, V))
    return toks.reshape(filt.shape[:-1]).astype(jnp.int32)


def _uniform_rows(keys):
    """One uniform [0,1) draw per row key: keys [..., 2] -> [...]."""
    u = jax.vmap(lambda k: jax.random.uniform(k))(keys.reshape(-1, 2))
    return u.reshape(keys.shape[:-1])


@dataclass
class _PendingSegment:
    """A dispatched-but-not-fetched segment (r12): the device futures of
    one fused segment plus the host bookkeeping its replay needs. The
    fleet router dispatches one of these per replica and only then
    fetches them in turn — replica i+1's device work overlaps replica
    i's fetch wait, with the per-segment sync contract intact (each
    finish is still exactly one ``allowed_sync`` event fetch)."""
    picked: List["Request"]
    n: int
    now: float
    prefix_cache: object
    dev: tuple                     # (out, aq, aslot, step, qidx) futures
    pre_lens: object               # [n] reused-prefix rows per request
    req_pages: Optional[List[List[int]]] = None   # page reservations
    # r13: admission-time context per request. full_prompts[j] is the
    # tokens the admit actually prefills — prompt + any tokens already
    # generated before a preemption/failover requeue (the RESUME view);
    # the prefix-cache population after the sync must harvest THIS
    # span, not the original prompt. chunk_marker is the aq value the
    # chunked program logs for a non-final prefill-chunk step (the host
    # replay skips those steps — no decode happened on them).
    full_prompts: Optional[List[np.ndarray]] = None
    chunk_marker: Optional[int] = None
    # r15: True when the segment ran the speculative/sampled program —
    # its event log carries [steps, slots, K+1] token matrices plus the
    # per-step accepted counts the host replay distributes
    spec: bool = False
    # r17: True when the segment ran the quality-digest program — its
    # event log additionally carries per-step per-slot logit digests
    # (emitted logit + top-k ids/values) in the same fetch
    digest: bool = False
    # r23: True when the segment ran the sequence-parallel long-context
    # program — its event log additionally carries the pf/pfq/pfo
    # prefill-progress state (a long prefill may span segments; the
    # host keeps its page reservation and resumes it next dispatch)
    sp: bool = False
    # PR 29: True when the model counts per step (``SEGMENT_COUNTERS``) —
    # its event log additionally carries a [steps, n] int32 column block
    counters: bool = False
    seg: int = 0                   # the engine's index of this segment


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int
    tokens: List[int] = field(default_factory=list)
    submit_time: float = 0.0      # perf_counter at add_request
    finish_time: float = 0.0      # perf_counter at retirement
    # online-serving measured lifecycle (perf_counter; 0.0 = not yet):
    # these are HOST-OBSERVED times — a token "exists" for a client only
    # once a device->host sync delivered it, so first_token/finish are
    # stamped at the segment sync that surfaced them (r7: the measured
    # replacement for r5's uniform-step latency model)
    arrival_time: float = 0.0     # entered the system (arrival process)
    admit_time: float = 0.0       # packed into a slot (prefill dispatched)
    first_token_time: float = 0.0  # first generated token host-visible
    # PR 25: what splits the first-token wait into its parts (telemetry,
    # never a decision input): the serve loop's top that ingested the
    # request, and (dispatch time, step, steps) of the segment whose
    # event log holds its first token — see scheduler._ttft_parts
    ingest_time: float = 0.0
    first_token_seg: Optional[tuple] = None
    prefix_hit_len: int = 0       # KV rows reused from the prefix cache
    # r13 SLO-aware serving: smaller priority = more important (class 0
    # outranks class 1); deadline is an ABSOLUTE perf_counter e2e
    # deadline (0.0 = none — the request is never shed). preemptions /
    # requeues count how often this request lost its slot (priority
    # preemption) or its replica (fleet failover); generated tokens
    # survive either — re-admission resumes from prompt + tokens.
    priority: int = 0
    deadline: float = 0.0
    preemptions: int = 0
    requeues: int = 0
    # r15 speculative + sampled decoding (ISSUE 10): per-request sampling
    # seed (only consumed when the engine has a sampling config — the
    # slot's in-program RNG stream is derived from it at every admission,
    # folded with len(tokens) so a resume continues deterministically),
    # and the speculative draft ledger the host replay recovers from the
    # event log: drafts proposed for / accepted by this request (the
    # per-request acceptance rate the benchmark histograms by prompt
    # class).
    seed: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0
    # r17 quality digests (ISSUE 12): one (emitted_logit, top-k ids,
    # top-k values) triple per emitted token, recovered by the host
    # replay from the same single audited event fetch — None unless the
    # engine runs with quality_digest=True. The shadow-diff monitor
    # compares these across a primary/shadow pair.
    digests: Optional[List[tuple]] = None
    # r18 capacity meter (ISSUE 13): host-stamped resource attribution,
    # always on (a perf_counter read + int arithmetic per event — the
    # stamps are telemetry, never decision inputs, so they stay off the
    # journal clock). pages_reserved = span of the latest reservation
    # (persists after release — the request's §3f page footprint);
    # pages_fresh = the non-shared subset (what admission drew from the
    # free list); page_seconds accumulates held-pages x wall at every
    # release point (retire / requeue / preempt / abort), across
    # resume cycles. meter_ticks counts the weight streams the request
    # was live for (admit prefill + decode/verify ticks);
    # meter_streams its FAIR share (1/live per tick) — summed over a
    # serve the shares tile the segment steps exactly, the identity
    # tests/test_capacity.py pins. capacity.attribute_request joins
    # these with the §3c ledger into bytes/FLOPs.
    pages_reserved: int = 0
    pages_fresh: int = 0
    page_seconds: float = 0.0
    meter_ticks: int = 0
    meter_streams: float = 0.0
    # r19 tiered KV (ISSUE 14): tier traffic billed to THIS request —
    # pages/bytes promoted from the host tier (restore-on-hit) or
    # imported cross-replica for its admission. analysis.tiers enforces
    # tier_bytes <= the request's own KV size (pages_reserved x page
    # bytes): a memory tier must never move more than it saves.
    tier_pages: int = 0
    tier_bytes: int = 0
    _pages_live: int = 0          # currently-held pages (meter internal)
    _pages_t0: float = 0.0        # holding-interval open stamp

    def _meter_reserve(self, pages: int, fresh: int) -> None:
        self.pages_reserved = pages
        self.pages_fresh = fresh
        self._pages_live = pages
        self._pages_t0 = time.perf_counter()

    def _meter_release(self) -> None:
        """Close the open page-holding interval (idempotent)."""
        if self._pages_live:
            self.page_seconds += self._pages_live * (
                time.perf_counter() - self._pages_t0)
            self._pages_live = 0

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens

    def resume_view(self):
        """(tokens to prefill, generations still owed) for admission.
        Fresh requests prefill their prompt; a preempted / failed-over
        request resumes from prompt + everything already generated —
        greedy decode makes the continuation token-identical to an
        uninterrupted run, and the concatenated view lets the prefix
        cache serve the request's own harvested pages back to it (a
        resume is then a page-ref bump + suffix prefill)."""
        if not self.tokens:
            return self.prompt, self.max_new_tokens
        full = np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])
        return full, self.max_new_tokens - len(self.tokens)


# Process-wide compiled-program cache (r12): every program an engine
# builds closes over
# NOTHING but config scalars (cfg, slots, max_len, eos, chunk, mesh) —
# params and caches are arguments — so engines with identical geometry
# can share one jitted callable. A fleet of N identical replicas then
# compiles each segment shape ONCE per process instead of N times
# (compile cost is per binary, not per replica — the ROADMAP item 5
# direction), and the test suite's many tiny engines stop re-compiling
# the same programs per test. Keys hold no arrays; the cache pins only
# XLA executables.
_SHARED_PROGS: Dict[tuple, object] = {}


class ServingEngine:
    def __init__(self, cfg: llama.LlamaConfig, params, slots: int = 8,
                 max_len: Optional[int] = None, chunk: int = 32,
                 prompt_buckets: Sequence[int] = (32, 64, 128, 256),
                 eos_token_id: Optional[int] = None,
                 paged: bool = True, page_size: int = 16,
                 num_pages: Optional[int] = None, mesh=None,
                 chunked_prefill: bool = False,
                 prefill_chunks: Sequence[int] = (8, 16, 32, 64),
                 speculative: int = 0,
                 sampling: Optional[dict] = None,
                 sample_seed: int = 0,
                 quality_digest: bool = False,
                 digest_top_k: int = 4,
                 quant: Optional[str] = None,
                 seq_parallel: int = 0,
                 long_buckets: Sequence[int] = ()):
        if not paged:
            # the keyword survives only because the benchmark's config
            # files still pass ``"paged": true``
            raise ValueError(
                "paged=False: the contiguous KV cache is gone — "
                "ServingEngine serves from the paged pool only "
                "(llama.generate is the contiguous-cache reference)")
        self.cfg = cfg
        # the model seam (PR 29): what the engine needs of a model — its
        # parameters, its paged pool, its forward over pages, whether its
        # kernel is routed to — it asks of the module of ``cfg``'s family
        # (``models.family_of``); a serving family that module does not
        # list is refused here, by name
        self.model = family_of(cfg)
        for engaged, family in (
                (mesh is not None, "mesh"),
                (chunked_prefill, "chunked prefill"),
                (speculative or sampling, "speculative"),
                (quality_digest, "quality digest"),
                (quant, "quantized pool"),
                (seq_parallel, "sequence-parallel prefill")):
            if engaged:
                require(cfg, family)
        self.params = params
        self.slots = int(slots)
        # r12 tensor-parallel serving: an 'mp' mesh shards the weights
        # (llama.param_specs — Megatron column/row-parallel) and the KV
        # store on the head dim; a model bigger than one chip's HBM then
        # serves through the SAME one-dispatch/one-fetch segment programs
        # (GSPMD inserts one all-reduce per layer after the row-parallel
        # projections). Slot bookkeeping stays host-side and
        # mesh-oblivious.
        self.mesh = mesh
        if mesh is not None:
            mp = int(mesh.shape.get("mp", 1))
            if mp > 1 and (cfg.num_kv_heads % mp or cfg.num_heads % mp):
                raise ValueError(
                    f"num_heads {cfg.num_heads} / num_kv_heads "
                    f"{cfg.num_kv_heads} must divide the mp degree {mp} "
                    f"(the KV cache shards on the head dim)")
            self.params = llama.shard_state(cfg, mesh, params)
        self.max_len = int(max_len or cfg.max_seq_len)
        self.chunk = int(chunk)
        self.buckets = tuple(sorted(int(b) for b in prompt_buckets
                                    if b <= self.max_len))
        if not self.buckets:
            raise ValueError("no prompt bucket fits max_len")
        self.eos = eos_token_id
        self._progs: Dict[tuple, object] = {}  # program key -> jitted fn
        self._queue: List[Request] = []
        self._active: List[Optional[Request]] = [None] * self.slots
        self._rem_host = [0] * self.slots  # host mirror of remaining counts
        self._finished: List[Request] = []
        self.last_run_chunks = 0  # segments fetched since the last reset
        self.last_run_ticks = 0   # loop steps those segments ran
        self.last_latencies = {}  # rid -> submit->finish seconds (last run)
        self._next_rid = 0
        self.page_backpressure_events = 0  # admissions deferred for pages
        # r13 chunked prefill (ISSUE 8): split each admitted prompt into
        # fixed-width chunks interleaved with decode ticks INSIDE the
        # paged segment program, bounding time-between-tokens for
        # co-resident decodes by one chunk's cost instead of a whole
        # prefill. Chunk widths come from the small DECLARED ladder so
        # program cache keys stay bucketed (a floating chunk width would
        # be the 2.5 s mid-serve XLA-compile class all over again).
        self.chunked = bool(chunked_prefill)
        self.prefill_chunks = tuple(sorted(int(c) for c in prefill_chunks))
        if self.chunked and not self.prefill_chunks:
            raise ValueError("chunked_prefill needs a non-empty "
                             "prefill_chunks ladder")
        # r15 speculative + sampled decoding (ISSUE 10; ROADMAP item 3).
        # ``speculative=K``: each decode step drafts K tokens per live
        # slot from the slot's own resident token history (an in-program
        # n-gram/prompt-suffix lookup — no draft model, no host contact)
        # and the target model VERIFIES all K+1 positions in one batched
        # tick through the paged q_len>1 path — accepted-length > 1 per
        # weight stream is the only lever that beats the decode HBM
        # roofline (SCALING §3c/§3j). ``sampling`` = {"temperature",
        # "top_k", "top_p"}: per-slot threaded RNG keys carried in
        # segment state, seeded per request so serves replay
        # deterministically. temperature 0 normalises to None so the
        # default greedy path compiles the EXACT argmax programs
        # (bit-identical, budget-identical).
        self.speculative = int(speculative)
        if self.speculative < 0:
            raise ValueError(f"speculative draft length must be >= 0, "
                             f"got {speculative}")
        samp = None
        if sampling:
            t = float(sampling.get("temperature", 1.0))
            if t < 0.0:
                raise ValueError(f"temperature must be >= 0, got {t}")
            if t > 0.0:
                samp = (t, int(sampling.get("top_k", 0)),
                        float(sampling.get("top_p", 1.0)))
        self.sampling = samp
        self.sample_seed = int(sample_seed)
        # r17 quality digests (ISSUE 12): per-emitted-token logit
        # evidence computed IN-PROGRAM and rolled into the segment event
        # log — the emitted token's logit plus the top-k (ids, values)
        # of the tick's distribution — riding the SAME single audited
        # per-segment fetch. This is the raw material shadow-diff
        # quality monitoring (observability/quality.py) compares across
        # a primary/shadow engine pair: token divergence localises to an
        # exact position, and logit-error budgets (max |Δ|, sampled KL)
        # quantify "how different" below the token-flip threshold.
        # Digests ride the plain paged segment family only — chunked /
        # speculative / sampled variants diff at TOKEN level (their
        # event logs already carry the emitted stream); a digest there
        # would multiply the log width for no extra diff power on the
        # greedy chains they emit.
        self.quality_digest = bool(quality_digest)
        self.digest_top_k = int(digest_top_k)
        if self.quality_digest:
            if self.chunked or self.speculative or self.sampling:
                raise ValueError(
                    "quality_digest composes with the plain paged "
                    "segment only — chunked/speculative/sampled "
                    "variants are shadow-diffed at token level")
            if self.digest_top_k < 1:
                raise ValueError(f"digest_top_k must be >= 1, got "
                                 f"{digest_top_k}")
        # r21 quantized serving (ISSUE 16): ``quant`` = "int8" | "fp8"
        # shrinks the decode tick's HBM stream — the LAST roofline lever
        # after r15 speculation multiplied tokens per stream. Weights
        # re-quantize at build (per-output-channel scales ride the param
        # tree as ``<name>_scale`` companions; dequant happens in-kernel
        # on the TPU path, adjacent-to-dot on the dense fallback) and
        # the KV pool carries the narrow dtype with per-page scale
        # planes ("ks"/"vs") keyed by physical page id — COW, refcounts
        # and the host tier treat pages dtype-obliviously, so prefix
        # sharing and r19 spill survive unchanged. The
        # quantized programs are a new DTYPE AXIS on the paged segment
        # family ("qpseg"), enumerated and AOT-warmed like every other
        # rung. Composes with quality_digest (the shadow-diff quality
        # bar that certifies the rollout); mesh / chunked / speculative
        # / sampled combos are rejected until they earn their own
        # certification.
        self.quant = str(quant) if quant else None
        if self.quant:
            from ..quantization.serving import (QUANT_MODES,
                                                quantize_llama_params)

            if self.quant not in QUANT_MODES:
                raise ValueError(f"quant must be one of {QUANT_MODES}, "
                                 f"got {quant!r}")
            if mesh is not None:
                raise ValueError(
                    "quant under a mesh is not supported — the scale "
                    "companions would need their own param_specs entry "
                    "before the sharded dequant is certified")
            if self.chunked or self.speculative or self.sampling:
                raise ValueError(
                    "quant composes with the plain paged segment (and "
                    "quality_digest) only — chunked/speculative/sampled "
                    "variants need their own shadow certification")
            self.params = quantize_llama_params(self.params, cfg,
                                                self.quant)
        # r23 long-context serving (ISSUE 18): ``seq_parallel=sp`` adds
        # the sequence-parallel prefill family ("spseg") — prompts past
        # the largest REGULAR bucket admit through sp-wide prefill
        # SLABS (sp chunks of C tokens, the batch axis carrying the
        # shard axis: under an 'sp' mesh each chunk runs on its own
        # devices; without one the slab is a plain batched call with
        # bit-identical math). Every slab row scatters its KV slice
        # straight into the SHARED paged pool through the request's own
        # page-table row, so decode proceeds on the ordinary
        # page-indirect path with zero relayout at the prefill->decode
        # boundary. ``long_buckets`` is the declared LONG prompt rung
        # ladder (all rungs >= the largest regular bucket): intake for
        # long prompts caps at its top, and the spseg key family
        # enumerates over its rungs so the AOT warmup covers every
        # reachable slab width. A long prefill may SPAN segments (the
        # in-program pf/pfq/pfo progress state rides the single event
        # fetch out and back); its page reservation is taken ONCE at
        # first admission and HELD across the spanned segments (the
        # SCALING §3f multi-segment reservation extension — the r19
        # host tier is the pressure valve when one prompt's KV rivals
        # the pool). sp=1 degenerates exactly: regular traffic never
        # engages the family, so program keys and journal streams match
        # the plain paged engine byte for byte.
        self.seq_parallel = int(seq_parallel or 0)
        self.long_buckets = tuple(sorted(int(b) for b in long_buckets))
        if self.seq_parallel < 0:
            raise ValueError(f"seq_parallel must be >= 0, got "
                             f"{seq_parallel}")
        if self.seq_parallel:
            if self.speculative or self.sampling or self.quality_digest \
                    or self.quant:
                raise ValueError(
                    "seq_parallel composes with the plain/chunked paged "
                    "segment only — speculative/sampled/digest/quant "
                    "variants need their own certification")
            if not self.long_buckets:
                raise ValueError("seq_parallel needs a non-empty "
                                 "long_buckets rung ladder")
            if self.long_buckets[0] < max(self.buckets):
                raise ValueError(
                    f"every long bucket must be >= the largest regular "
                    f"bucket {max(self.buckets)} (got "
                    f"{self.long_buckets[0]} — regular traffic rides "
                    f"the ordinary pseg/cseg families)")
            if self.long_buckets[-1] > self.max_len:
                raise ValueError(
                    f"long bucket {self.long_buckets[-1]} exceeds "
                    f"max_len {self.max_len}")
        # rid -> {"pages", "resident"} for long prefills spanning
        # segments: the reservation taken at first admission plus how
        # many KV rows (prefix hit + slabs landed so far) are already
        # resident in the pool — the next dispatch resumes the prefill
        # at that offset with the SAME pages
        self._sp_inflight: Dict[int, dict] = {}
        # acceptance EWMA (emitted tokens per verify tick, >= 1): the
        # SLO scheduler threads this through its deadline and
        # retry_after_s estimates so speculative serves don't over-shed
        # (each tick retires accept_ewma tokens, not one)
        self.spec_accept_ewma = 1.0
        # the KV store (r11, inference/paged_kv.py): ONE flat page pool +
        # per-slot page tables. max_len is the PER-SLOT virtual cap
        # (max_pages * page_size); num_pages sizes the PHYSICAL pool —
        # below slots * max_pages admission is gated on pages free.
        from .paged_kv import PagedKVCache

        self.page_size = int(page_size)
        if self.max_len % self.page_size:
            raise ValueError(f"max_len {self.max_len} is not a "
                             f"multiple of page_size {self.page_size}")
        max_pages = self.max_len // self.page_size
        self.pager = PagedKVCache(
            cfg, self.slots, self.page_size,
            num_pages=int(num_pages or self.slots * max_pages + 1),
            max_pages=max_pages, mesh=mesh, quant=self.quant)
        self._pos = self._slot_vec()
        self._nxt = self._slot_vec()
        self._rem = self._slot_vec()
        self._init_spec_state()
        self._pending_seg = None  # at most ONE in-flight dispatched segment
        # PR 25: segments dispatched so far (the ``seg`` id every
        # ``serving.segment.*`` span carries) and per-phase host time,
        # span name -> [ns, count] — the serve loop resets the dict and
        # reduces it into ``OnlineReport.segment_phases``
        self.seg_index = 0
        self.segment_phases: Dict[str, list] = {}
        # PR 38: the return of the last ``fetch`` (its span's end stamp),
        # where the next segment's gap opens; None where no gap is open
        # (a serve's first segment, a loop turn that waited for work),
        # and whether a jax trace was live then
        self.gap_from_ns: Optional[int] = None
        self._gap_traced = False
        # PR 29: sums of the model's per-step counters, by group
        # (``serving.<group>.*`` of its ``COUNTER_GROUPS``: ``moe``,
        # ``retention``, ``window``) over the segments since the serve
        # loop last reset the dict
        self.segment_counts: Dict[str, Dict[str, int]] = {}
        # r14 cold-start metric (ISSUE 9 satellite; ROADMAP item 5's
        # first deliverable): build→first-emitted-token wall time, the
        # number autoscaling/rollout decisions gate on. Stamped ONCE per
        # engine lifetime at the first host-visible token (the fetch
        # that surfaced it), deliberately spanning the first segment's
        # XLA compile — that compile IS the cold-start cost being
        # measured. reset_slots does not clear it (warm resets are not
        # rebuilds).
        self.built_at = time.perf_counter()
        self.cold_start_s: Optional[float] = None
        # r20 (ISSUE 15): AOT bucket-ladder warmup bookkeeping. When
        # ``aot_warmup`` ran, the cold-start gauge splits into the
        # warmup cost (``aot_warmup_s`` — every enumerated program
        # compiled at build) and ``first_token_s`` (cold_start minus
        # warmup: queue/admit/prefill only, no XLA), the pair the
        # autoscaler's scale-up model sums. ``aot_key_seconds`` holds
        # per-key build+compile seconds (the coverage pass attributes
        # dead ladder entries' cost from it); ``prog_key_hits`` counts
        # post-warmup program-cache accesses (the enumerated-vs-used
        # differential's usage side).
        self.aot_warmup_s: Optional[float] = None
        self.first_token_s: Optional[float] = None
        self.aot_key_seconds: Dict[tuple, float] = {}
        self.aot_key_temp_bytes: Dict[tuple, int] = {}
        self.prog_key_hits: Dict[tuple, int] = {}
        from ..jit import register_compiled_cache

        register_compiled_cache(self)  # analysis.recompile introspection

    @property
    def pool_bytes(self) -> Dict[str, int]:
        """Bytes of each plane of the paged pool as it lies on the device:
        the yardstick for ``aot_warmup``'s ``temp_bytes`` — a segment
        program that holds the pool once has temporaries under
        ``pool_bytes["k"]``."""
        return {n: int(a.nbytes) for n, a in self.pager.pool.items()}

    def _slot_vec(self):
        """A zeroed [slots] int32 slot-state vector, replicated over the
        engine's mesh when one is set (slot state is tiny and every
        device needs all of it)."""
        v = jnp.zeros((self.slots,), jnp.int32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            v = jax.device_put(v, NamedSharding(self.mesh, P()))
        return v

    def _slot_arr(self, shape, dtype):
        """Zeroed per-slot state array, replicated over the engine's mesh
        (same contract as ``_slot_vec`` for non-vector shapes: the
        speculative token-history mirror and the per-slot RNG keys)."""
        v = jnp.zeros(shape, dtype)
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            v = jax.device_put(v, NamedSharding(self.mesh, P()))
        return v

    def _init_spec_state(self) -> None:
        """(Re)build the speculative / sampling slot state (r15):

        * ``_hist`` [slots, max_len+1] int32 — the slot's TOKEN history
          mirror of its page-resident KV rows (prompt suffix + every
          verified token): the in-program n-gram draft table. One
          overflow column past max_len absorbs clamped writes so a
          near-capacity slot can never corrupt valid history.
        * ``_hstart`` [slots] — first valid history index (= the shared
          prefix length at admission: prefix TOKENS are not re-staged,
          so the draft scan starts where this slot's own tokens do).
        * ``_rng`` [slots, 2] uint32 — raw per-slot PRNG key state,
          re-seeded from the request's seed at every admission.
        """
        if self.speculative or self.sampling:
            self._hist = self._slot_arr((self.slots, self.max_len + 1),
                                        jnp.int32)
            self._hstart = self._slot_vec()
        else:
            self._hist = self._hstart = None
        if self.sampling:
            self._rng = self._slot_arr((self.slots, 2), jnp.uint32)
        else:
            self._rng = None

    def cache_info(self) -> dict:
        """Compiled-program cache keys (analysis.recompile lint): paged
        segments key on ("pseg", n_pad, s_max, steps), chunked paged
        segments on ("cseg", n_pad, s_max_c, C, steps) with
        C drawn from the declared prefill_chunks ladder, speculative/
        sampled segments on ("sseg", n_pad, K, steps) with the admit
        width PINNED to the largest bucket, quality-digest paged
        segments on ("qseg", n_pad, s_max, steps), quantized paged
        segments on ("qpseg", n_pad, s_max, steps, dtype) with dtype
        drawn from the declared QUANT_CODES, sequence-parallel
        long-context segments on ("spseg", n_pad, s_max, C, sp, steps)
        with s_max a slab-rounded long_buckets rung — all bucketed by
        construction, so key-count growth here means a shape leaked
        past the buckets (the 2.5 s mid-serve compile class this
        engine's width pinning fixed). No key carries a prefix width:
        shared-prefix geometry rides the page tables as DATA, so prefix
        reuse adds zero program shapes."""
        return {"name": f"serving_engine:slots{self.slots}",
                "keys": list(self._progs.keys())}

    def paged_kernel_active(self) -> bool:
        """True when this engine's paged segments route attention to the
        unified page-indirect Pallas kernel (a trace-time dispatch
        decision — ``chip_smoke.py`` and the serve cells assert it, so a
        selection regression fails loudly)."""
        # a quantized pool takes the dequantizing gather path instead of
        # the page-indirect kernel (its per-page scales need the
        # gather); the weight stream is where the quant bytes win
        return not self.quant \
            and self.model.paged_kernel_active(self.cfg, self.page_size)

    def quant_kernel_active(self) -> bool:
        """True when this engine's quantized projection matmuls route to
        the in-kernel-dequant Pallas path (trace-time dispatch, like
        ``paged_kernel_active``; CPU tier-1 exercises the same kernel
        through FORCE_INTERPRET)."""
        from ..ops.pallas.tick_fusion import quant_matmul_active

        H = self.cfg.hidden_size
        return bool(self.quant) and quant_matmul_active(H, H)

    # --- request intake ---------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int,
                    seed: Optional[int] = None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        intake_cap = (max(self.long_buckets) if self.seq_parallel
                      else max(self.buckets))
        if len(prompt) > intake_cap:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest "
                f"{'long ' if self.seq_parallel else ''}bucket "
                f"{intake_cap}")
        if len(prompt) + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds cache max_len {self.max_len}")
        need = self.pager.pages_needed(len(prompt) + max_new_tokens - 1)
        if need > self.pager.num_pages - 1:
            raise ValueError(
                f"request spans {need} pages but the pool holds only "
                f"{self.pager.num_pages - 1} — it could never admit")
        rid = self._next_rid
        self._next_rid += 1
        # per-request sampling seed: explicit, or derived from the
        # engine's base seed + rid — either way fixed at intake, so one
        # trace replays its sampled streams identically serve to serve
        self._queue.append(Request(rid, prompt, int(max_new_tokens),
                                   submit_time=time.perf_counter(),
                                   seed=(self.sample_seed + rid
                                         if seed is None else int(seed))))
        return rid

    def _retire(self, r: Request) -> None:
        r.finish_time = time.perf_counter()
        self._finished.append(r)

    # --- compiled programs ------------------------------------------------
    def _shared_key(self, key: tuple) -> tuple:
        """Process-wide program-cache key: the engine geometry every
        program closure reads, plus the per-shape key. Engines agreeing
        on all of it trace byte-identical programs."""
        return (self.cfg, self.slots, self.max_len, self.eos, self.chunk,
                self.pager.max_pages, self.mesh, self.speculative,
                self.sampling, self.chunked, self.prefill_chunks, self.buckets,
                self.digest_top_k if self.quality_digest else None,
                self.quant,
                ((self.seq_parallel, self.long_buckets)
                 if self.seq_parallel else None),
                key)

    def _memo_prog(self, key: tuple, build):
        """Two-level memo: per-engine ``_progs`` (the recompile lint's
        introspection surface — ``cache_info`` keys stay per engine) in
        front of the process-wide ``_SHARED_PROGS`` store. Every access
        counts into ``prog_key_hits`` (r20: ``aot_warmup`` zeroes the
        counts after compiling the ladder, so what remains is the
        post-warmup usage side of the enumerated-vs-used coverage
        differential)."""
        self.prog_key_hits[key] = self.prog_key_hits.get(key, 0) + 1
        cached = self._progs.get(key)
        if cached is not None:
            return cached
        gkey = self._shared_key(key)
        fn = _SHARED_PROGS.get(gkey)
        if fn is None:
            fn = build()
            _SHARED_PROGS[gkey] = fn
        self._progs[key] = fn
        return fn

    # --- scheduling -------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"no bucket for prompt length {n}")

    def _long_rung(self, n: int) -> int:
        """Smallest declared long bucket covering an ``n``-token
        suffix (r23): the spseg admit-window rung. A continuation's
        shrinking suffix walks DOWN the ladder — every rung at or below
        the first admission's is statically enumerated."""
        for b in self.long_buckets:
            if n <= b:
                return b
        raise ValueError(f"no long bucket for suffix length {n}")

    # --- program-space coverage + AOT warmup (r20: ISSUE 15) --------------
    def default_envelope(self, seg_steps: Sequence[int] = (),
                         prefix_block: Optional[int] = None,
                         resume: bool = True) -> WorkloadEnvelope:
        """The widest envelope this engine's INTAKE admits: prompts up
        to the largest bucket, generations filling the cache, segments
        at ``run()``'s drain budget unless the caller declares its
        scheduler's ``seg_steps``. Deployments should declare tighter
        envelopes (every reachable key gets compiled at warmup — a
        loose envelope is dead ladder weight the coverage pass will
        name, not an error)."""
        max_prompt = (self.long_buckets[-1] if self.seq_parallel
                      else self.buckets[-1])
        return WorkloadEnvelope(
            max_prompt=max_prompt,
            max_new_tokens=max(1, self.max_len + 1 - max_prompt),
            seg_steps=tuple(seg_steps) or (4 * self.chunk,),
            resume=resume, prefix_block=prefix_block)

    def program_space(self, envelope: Optional[WorkloadEnvelope] = None
                      ) -> Dict[str, frozenset]:
        """Statically enumerate the EXACT finite program-key set this
        config can reach under ``envelope`` (default: the widest intake
        envelope), grouped by registered family. Every jit memo key the
        dispatch paths can construct is in here by construction — the
        keys and the dispatch arithmetic both live in
        ``program_space.PROGRAM_SPACE`` (the coverage pass replays the
        admission arithmetic over the envelope and diffs against this
        set; ``analysis.coverage`` is the enforcement)."""
        env = envelope or self.default_envelope()
        return PROGRAM_SPACE.enumerate_by_family(self, env)

    def aot_warmup(self, envelope: Optional[WorkloadEnvelope] = None,
                   prefix_cache=None) -> Dict[str, dict]:
        """Compile the FULL enumerated program space at build (the
        remaining third of old ROADMAP item 5): every key the envelope
        can reach is built through ``_memo_prog`` (fleet replicas share
        the compile via ``_SHARED_PROGS``; restarts share it via the
        r15 persistent cache) and executed once on empty state —
        ``n_real = 0`` with no live slots makes every segment's
        while_loop exit before its first iteration, so the execution
        costs microseconds and the XLA compile is the whole bill. After
        this, a serve that stays inside the envelope performs ZERO
        backend compiles (``analysis.recompile.enforce_zero_compiles``
        is the budget; ``analysis.coverage`` diffs enumerated vs used).

        Returns {family: {"keys": n, "seconds": s, "temp_bytes": b}} and
        stamps ``aot_warmup_s`` (the cold-start split's first half).
        ``b`` is the largest ``memory_analysis().temp_size_in_bytes``
        among the family's compiled programs — what a program needs
        BESIDE its arguments; against ``pool_bytes`` it says whether a
        paged segment holds the pool once (temporaries under one pool
        plane). Requires an idle engine (no live slots, queue, or
        in-flight segment).

        Pass the serve loop's ``prefix_cache`` when one will be
        attached: a tiered cache's D2H-stage/H2D-restore transfer
        programs are shape-keyed on the transferred page count and get
        prewarmed for every count the envelope's prefix lengths can
        reach."""
        assert all(r is None for r in self._active) and not self._queue, \
            "aot_warmup on a non-idle engine"
        assert self._pending_seg is None, \
            "aot_warmup with a dispatched segment in flight"
        env = envelope or self.default_envelope()
        t0 = time.perf_counter()
        by_family = PROGRAM_SPACE.enumerate_by_family(self, env)
        report: Dict[str, dict] = {}
        for fam in sorted(by_family):
            tf = time.perf_counter()
            for key in sorted(by_family[fam]):
                tk = time.perf_counter()
                self._aot_run_key(fam, key)
                self.aot_key_seconds[key] = time.perf_counter() - tk
            report[fam] = {"keys": len(by_family[fam]),
                           "seconds": time.perf_counter() - tf,
                           "temp_bytes": max(
                               (self.aot_key_temp_bytes[k]
                                for k in by_family[fam]), default=0)}
        # prewarm the between-segment eager singletons so the first
        # preempt / slot reset after warmup compiles nothing: the
        # preempt freeze scatter (device-operand index — one program
        # for all slots) and the slot-vector fill reset_slots rebuilds
        self._rem = self._rem.at[jnp.asarray(0, jnp.int32)].set(0)
        tier = getattr(prefix_cache, "host_tier", None) \
            if prefix_cache is not None else None
        if tier is not None:
            _, hi = env.admit_lengths(self.buckets)
            if self.seq_parallel:
                # long-context harvests can park whole long prompts in
                # the cache, so spill/restore transfer shapes reach the
                # full long-prompt page span
                hi = max(hi, min(env.max_prompt + env.max_new_tokens - 1,
                                 self.max_len))
            tier.prewarm_transfers(hi // self.page_size)
        # the segments ran empty (n_real=0); the engine returns to idle
        # zeros — it was asserted idle at entry, so nothing is lost
        self._pos = self._slot_vec()
        self._nxt = self._slot_vec()
        self._rem = self._slot_vec()
        # post-warmup usage starts clean: what accumulates in
        # prog_key_hits from here on is the serve's ACTUAL key traffic
        # (the coverage differential's used-vs-enumerated side)
        self.prog_key_hits = {}
        self.aot_warmup_s = (self.aot_warmup_s or 0.0) + (
            time.perf_counter() - t0)
        n_keys = sum(r["keys"] for r in report.values())
        _metrics.gauge("serving.aot_warmup_s").set(self.aot_warmup_s)
        _metrics.gauge("serving.program_space_keys").set(n_keys)
        _flight.record("aot_warmup", seconds=round(self.aot_warmup_s, 4),
                       keys=n_keys, families=sorted(report),
                       temp_bytes={f: r["temp_bytes"]
                                   for f, r in report.items()})
        return report

    def _aot_run_key(self, family: str, key: tuple) -> None:
        """Build + compile + once-execute ONE enumerated program key on
        empty dummy state. The dummy calls mirror the dispatch paths'
        real argument shapes exactly (that is what makes the jit cache
        hit later); donated state arrays thread through so the engine
        stays consistent. ``aot_key_temp_bytes[key]`` keeps what the
        compiled program says it needs besides its arguments."""
        i32 = jnp.int32

        def run(prog, *args):
            # lowering the call's own arguments compiles the executable
            # the call below then finds: one compile, and its
            # memory_analysis() (a segment program that holds the paged
            # pool once has temporaries far under one pool plane)
            mem = prog.lower(*args).compile().memory_analysis()
            self.aot_key_temp_bytes[key] = int(
                getattr(mem, "temp_size_in_bytes", 0))
            return prog(*args)

        with _mesh_scope(self.mesh):
            if family in {"pseg", "qseg", "cseg", "qpseg"}:
                # qpseg keys carry a trailing dtype code; steps sits at
                # a fixed index there, key[-1] everywhere else
                n_pad, s_max = key[1], key[2]
                steps = key[3] if family == "qpseg" else key[-1]
                prog = (self._chunked_segment_prog(n_pad, s_max, key[3],
                                                   steps)
                        if family == "cseg"
                        else self._paged_segment_prog(n_pad, s_max, steps))
                pgr = self.pager
                out = run(
                    prog, self.params, pgr.pool, pgr.page_table, self._pos,
                    self._nxt, self._rem, jnp.zeros((n_pad, s_max), i32),
                    jnp.ones((n_pad,), i32), jnp.zeros((n_pad,), i32),
                    jnp.zeros((n_pad,), i32),
                    jnp.zeros((n_pad, pgr.max_pages), i32), i32(0))
                pgr.pool, pgr.page_table = out[0], out[1]
                (self._pos, self._nxt, self._rem) = out[2:5]
            elif family == "spseg":
                _, n_pad, s_max, C, _sp, steps = key
                pgr = self.pager
                out = run(
                    self._sp_segment_prog(n_pad, s_max, C, steps),
                    self.params, pgr.pool, pgr.page_table, self._pos,
                    self._nxt, self._rem, jnp.zeros((n_pad, s_max), i32),
                    jnp.ones((n_pad,), i32), jnp.zeros((n_pad,), i32),
                    jnp.zeros((n_pad,), i32),
                    jnp.zeros((n_pad, pgr.max_pages), i32), i32(0))
                pgr.pool, pgr.page_table = out[0], out[1]
                (self._pos, self._nxt, self._rem) = out[2:5]
            elif family == "sseg":
                _, n_pad, _k, steps = key
                pgr = self.pager
                rng = (self._rng if self._rng is not None
                       else jnp.zeros((self.slots, 2), jnp.uint32))
                s_max = self.buckets[-1]
                if self.chunked:
                    C = self._prefill_chunk_for(s_max)
                    s_max = -(-s_max // C) * C
                out = run(
                    self._spec_segment_prog(n_pad, steps),
                    self.params, pgr.pool, pgr.page_table, self._pos,
                    self._nxt, self._rem, self._hist, self._hstart, rng,
                    jnp.zeros((n_pad, s_max), i32),
                    jnp.ones((n_pad,), i32), jnp.zeros((n_pad,), i32),
                    jnp.zeros((n_pad,), i32),
                    jnp.zeros((n_pad, pgr.max_pages), i32),
                    jnp.zeros((n_pad,), i32), i32(0))
                pgr.pool, pgr.page_table = out[0], out[1]
                (self._pos, self._nxt, self._rem) = out[2:5]
                self._hist, self._hstart = out[5], out[6]
                if self._rng is not None:
                    self._rng = out[7]
            else:
                raise KeyError(f"unknown program family {family!r}")

    @staticmethod
    def _pow2(n: int, lo: int = 1) -> int:
        p = lo
        while p < n:
            p *= 2
        return p

    # --- segments: host side (r7: online continuous batching) -------------
    def _phase(self, phase: str, seg: Optional[int] = None):
        """One phase of a segment's host work as a span (PR 25):
        ``serving.segment.<phase>`` with the segment's index, timed into
        ``segment_phases``."""
        return _hooks.span("serving.segment." + phase, "serving",
                           tally=self.segment_phases,
                           seg=self.seg_index if seg is None else seg)

    def _open_gap(self, fetch_end_ns: int) -> None:
        """The next segment's gap opens where this ``fetch`` returned."""
        self.gap_from_ns = fetch_end_ns
        self._gap_traced = _hooks.tracing()

    def _close_gap(self, launch_end_ns: int) -> None:
        """``serving.segment.gap`` (PR 38): from the return of the last
        ``fetch`` to the return of this ``launch`` — the host time in
        which this engine has nothing in flight — into
        ``segment_phases`` and to collectors, from the two phase spans'
        own stamps. A gap in which a jax trace started or stopped is
        the profiler's (a slice's ``stop_trace`` writes the whole
        trace), and is dropped like one that waited for work."""
        t0, self.gap_from_ns = self.gap_from_ns, None
        if t0 is not None and self._gap_traced == _hooks.tracing():
            _hooks.record("serving.segment.gap", t0, launch_end_ns,
                          "serving", tally=self.segment_phases)

    def _replay_segment(self, picked, toks, aq, aslot, steps: int, n: int,
                        on_admit=None, on_retire=None,
                        chunk_marker: Optional[int] = None,
                        acc=None, spec_stats: Optional[dict] = None,
                        dig=None):
        """Host replay of a segment's event log: walk the log
        chronologically, tracking slot occupancy (admits rebind a slot;
        decode ticks append one token to every slot the HOST knows is
        live via its rem mirror, so frozen-slot repeats and pad rows
        are dropped). ``on_admit(q, slot)`` / ``on_retire(req, slot)``
        are the page-table bookkeeping hooks, called in event order so a
        slot freed and re-admitted mid-segment releases the old
        occupant's pages before the new page list installs.
        ``chunk_marker`` (chunked prefill): aq values >= it mark
        NON-FINAL prefill-chunk steps — no decode ran and no token
        surfaced there, so the replay skips the step.

        r15 speculative event logs: ``acc`` ([steps, slots]) makes
        ``toks`` a [steps, slots, K+1] token matrix — a decode step is
        a VERIFY tick that emitted ``acc[st, s]`` tokens for slot ``s``
        (admits carry their one token at column 0). The replay walks
        each slot's accepted prefix, recovers per-request accepted
        lengths into the Request ledger, and accumulates the segment's
        draft accounting into ``spec_stats`` — host arithmetic on the
        SAME single fetched log, zero extra device contact."""
        spec_k = self.speculative
        admitted, first_tokens, finished = [], [], []
        first_steps = []    # the step whose log row holds the first token
        new_tokens = eos_stops = 0
        for st in range(steps):
            q = int(aq[st])
            if chunk_marker is not None and q >= chunk_marker:
                continue                   # mid-prefill chunk: no tokens
            if q < n:                      # admit event
                r = picked[q]
                s = int(aslot[st])
                assert self._active[s] is None, "admit into a live slot"
                if on_admit is not None:
                    on_admit(q, s)
                t = int(toks[st, s, 0] if acc is not None
                        else toks[st, s])
                r.tokens.append(t)
                # r18 meter: the admit's prefill streamed the weight
                # set once, solo (the admit branch runs alone)
                r.meter_ticks += 1
                r.meter_streams += 1.0
                if dig is not None:
                    self._append_digest(r, dig, st, s)
                new_tokens += 1
                admitted.append(r.rid)
                if len(r.tokens) == 1:
                    # a RESUMED request (preempt/failover) already
                    # delivered its first token before losing its slot —
                    # only a fresh admit opens the TTFT clock
                    first_tokens.append(r.rid)
                    first_steps.append(st)
                hit_eos = self.eos is not None and t == self.eos
                eos_stops += hit_eos
                if r.done or hit_eos:
                    self._rem_host[s] = 0
                    self._retire(r)
                    finished.append(r.rid)
                    if on_retire is not None:
                        on_retire(r, s)
                else:
                    self._active[s] = r
                    # remaining = owed minus everything generated so far
                    # (fresh: max_new - 1; resumed: the true tail)
                    self._rem_host[s] = r.max_new_tokens - len(r.tokens)
            elif acc is None:              # decode tick
                live_now = [(s, r) for s, r in enumerate(self._active)
                            if r is not None and self._rem_host[s] > 0]
                share = 1.0 / len(live_now) if live_now else 0.0
                for s, r in live_now:
                    # r18 meter: every live slot consumed this tick's
                    # one weight stream; the share splits it fairly
                    r.meter_ticks += 1
                    r.meter_streams += share
                    t = int(toks[st, s])
                    r.tokens.append(t)
                    if dig is not None:
                        self._append_digest(r, dig, st, s)
                    new_tokens += 1
                    if len(r.tokens) == 1:
                        first_tokens.append(r.rid)
                        first_steps.append(st)
                    self._rem_host[s] -= 1
                    if self.eos is not None and t == self.eos:
                        self._rem_host[s] = 0
                        eos_stops += 1
                    if self._rem_host[s] == 0:
                        self._retire(r)
                        self._active[s] = None
                        finished.append(r.rid)
                        if on_retire is not None:
                            on_retire(r, s)
            else:                          # spec VERIFY tick
                live_now = [(s, r) for s, r in enumerate(self._active)
                            if r is not None and self._rem_host[s] > 0]
                share = 1.0 / len(live_now) if live_now else 0.0
                any_live = False
                for s, r in live_now:
                    any_live = True
                    # r18 meter: a verify tick is still ONE weight
                    # stream however many tokens it retires — the
                    # spec-adjusted effective-ticks denominator
                    r.meter_ticks += 1
                    r.meter_streams += share
                    k_emit = int(acc[st, s])
                    if spec_stats is not None:
                        spec_stats["slot_ticks"] += 1
                    if spec_k:
                        r.spec_proposed += spec_k
                        r.spec_accepted += max(k_emit - 1, 0)
                        if spec_stats is not None:
                            spec_stats["proposed"] += spec_k
                            spec_stats["accepted"] += max(k_emit - 1, 0)
                    for i in range(k_emit):
                        if self._rem_host[s] <= 0:
                            break
                        t = int(toks[st, s, i])
                        r.tokens.append(t)
                        new_tokens += 1
                        if spec_stats is not None:
                            spec_stats["emitted"] += 1
                        if len(r.tokens) == 1:
                            first_tokens.append(r.rid)
                            first_steps.append(st)
                        self._rem_host[s] -= 1
                        if self.eos is not None and t == self.eos:
                            self._rem_host[s] = 0
                            eos_stops += 1
                    if self._rem_host[s] == 0:
                        self._retire(r)
                        self._active[s] = None
                        finished.append(r.rid)
                        if on_retire is not None:
                            on_retire(r, s)
                if any_live and spec_stats is not None:
                    spec_stats["verify_steps"] += 1
        if new_tokens and self.cold_start_s is None:
            self._note_cold_start()
        return (admitted, first_tokens, first_steps, finished, new_tokens,
                eos_stops)

    @staticmethod
    def _append_digest(r: Request, dig, st: int, s: int) -> None:
        """Distribute one event-log digest row to its request (r17):
        host arithmetic on the already-fetched arrays — (emitted-token
        logit, top-k ids, top-k values), index-aligned with
        ``r.tokens``."""
        dlg, dti, dtv = dig
        if r.digests is None:
            r.digests = []
        r.digests.append((float(dlg[st, s]),
                          [int(i) for i in dti[st, s]],
                          [float(v) for v in dtv[st, s]]))

    def _note_cold_start(self) -> None:
        """First host-visible token since build: stamp the cold-start
        and publish it (SERVING metric + flight event). Runs at the
        fetch that surfaced the token, so the stamp includes program
        build + first compile + first prefill — the full client-facing
        cold-start window.

        r20 (ISSUE 15): with ``aot_warmup`` the gauge SPLITS —
        ``aot_warmup_s`` (the whole enumerated ladder compiled at
        build) + ``first_token_s`` (cold_start minus warmup: queue,
        admit, prefill — no XLA left to pay). The split is what makes
        the autoscaler's scale-up latency a measured, bounded number:
        warmup cost amortises across the persistent cache / fleet
        shared programs, first_token_s is the irreducible tail."""
        self.cold_start_s = time.perf_counter() - self.built_at
        self.first_token_s = self.cold_start_s - (self.aot_warmup_s or 0.0)
        _metrics.gauge("serving.cold_start_s").set(self.cold_start_s)
        _metrics.gauge("serving.first_token_s").set(self.first_token_s)
        _flight.record("cold_start",
                       seconds=round(self.cold_start_s, 4),
                       aot_warmup_s=(round(self.aot_warmup_s, 4)
                                     if self.aot_warmup_s is not None
                                     else None),
                       first_token_s=round(self.first_token_s, 4),
                       slots=self.slots)

    def _segment_telemetry(self, steps, admitted, finished, eos_stops,
                           new_tokens, requeued) -> None:
        """Post-sync counters/flight for one segment — host arithmetic
        on the already-fetched event log (ISSUE 5 contract: the
        segment's device contact stays the single audited allowed_sync
        in the caller)."""
        _metrics.counter("serving.segments").inc()
        _metrics.counter("serving.ticks").inc(steps)
        _metrics.counter("serving.admissions").inc(len(admitted))
        _metrics.counter("serving.tokens_generated").inc(new_tokens)
        if eos_stops:
            _metrics.counter("serving.eos_stops").inc(eos_stops)
        _metrics.gauge("serving.slots_live").set(
            self.slots - self.free_slot_count())
        _flight.record("segment", steps=steps, admitted=len(admitted),
                       finished=len(finished), eos=eos_stops,
                       tokens=new_tokens, requeued=requeued)
        if SEGMENT_HOOKS:
            # r14 ambient observers (SLO monitor / perf intervals):
            # host ints only, same zero-extra-sync contract
            for hook in SEGMENT_HOOKS:
                hook(steps, new_tokens, len(finished))

    def _count_telemetry(self, counts) -> Dict[str, int]:
        """One segment's ``SEGMENT_COUNTERS`` ([steps, n] int32, fetched
        with the tokens; the columns of the model's ``COUNTER_GROUPS`` in
        order) into the ``serving.<group>.*`` counters and
        ``segment_counts[group]``: sums over the steps, a ``max_*`` column
        its maximum. Returns the segment's own, by name."""
        seg = {}
        columns = ((group, name)
                   for group, names in self.model.COUNTER_GROUPS.items()
                   for name in names)
        for j, (group, name) in enumerate(columns):
            col = counts[:, j]
            total = self.segment_counts.setdefault(group, {})
            if name.startswith("max_"):
                seg[name] = v = int(col.max(initial=0))
                _metrics.gauge(f"serving.{group}.{name}").set(v)
                total[name] = max(total.get(name, 0), v)
            else:
                seg[name] = v = int(col.sum())
                _metrics.counter(f"serving.{group}.{name}").inc(v)
                total[name] = total.get(name, 0) + v
        return seg

    def _spec_telemetry(self, stats: dict) -> None:
        """Per-segment speculative accounting (r15 satellite): counters
        for drafts proposed/accepted/rejected, the live accept-rate and
        effective-tokens-per-tick gauges, a ``spec_accept`` flight
        event, and the acceptance EWMA the SLO scheduler threads into
        its deadline/retry estimates. Host arithmetic on the replayed
        event log — the zero-extra-sync telemetry contract holds."""
        prop, accepted = stats["proposed"], stats["accepted"]
        if prop:
            _metrics.counter("spec.proposed").inc(prop)
            _metrics.counter("spec.accepted").inc(accepted)
            _metrics.counter("spec.rejected").inc(prop - accepted)
            _metrics.gauge("spec.accept_rate").set(accepted / prop)
        if stats["slot_ticks"]:
            # PER-SLOT accepted length: tokens one slot retires per
            # verify tick (not batch tokens/tick — a full batch already
            # amortises the weight stream over slots; this gauge is the
            # roofline-beating factor on TOP of that, SCALING §3j)
            eff = stats["emitted"] / stats["slot_ticks"]
            _metrics.gauge("spec.effective_tok_per_tick").set(eff)
            # EWMA over segments: each slot retires ~eff tokens per
            # tick, the factor the SLO deadline/shed estimates divide by
            self.spec_accept_ewma = 0.5 * self.spec_accept_ewma + 0.5 * eff
            _flight.record("spec_accept", proposed=prop,
                           accepted=accepted,
                           rate=round(accepted / prop, 4) if prop else 0.0,
                           tok_per_tick=round(eff, 4))

    def free_slot_count(self) -> int:
        return sum(1 for r in self._active if r is None)

    def reset_slots(self) -> None:
        """Clear all slot state (cache rows stay allocated — pos masking
        makes stale rows invisible). Used between warmup and a timed run."""
        assert all(r is None for r in self._active), \
            "reset_slots with live requests"
        assert self._pending_seg is None, \
            "reset_slots with a dispatched segment in flight"
        self._pos = self._slot_vec()
        self._nxt = self._slot_vec()
        self._rem = self._slot_vec()
        self._init_spec_state()
        self.spec_accept_ewma = 1.0
        self._rem_host = [0] * self.slots
        for r in self._queue:
            info = self._sp_inflight.pop(r.rid, None)
            if info is not None:
                r._meter_release()
                self.pager.release_pages(info["pages"])
        self._sp_inflight = {}
        self._queue = []
        self._finished = []
        self.last_run_ticks = 0
        self.last_run_chunks = 0
        self.last_latencies = {}
        self.page_backpressure_events = 0
        self.pager.reset()

    # --- preemption / teardown (r13: the SLO control plane's hooks) -------
    def can_preempt(self, slot: int) -> bool:
        """Whether ``slot``'s occupant could be preempted AND later
        resumed by this engine: the resume view (prompt + generated
        tokens) must still fit the largest prompt bucket — a request
        whose generation outgrew the admit window cannot re-prefill and
        must be left to finish in place."""
        r = self._active[slot]
        return (r is not None
                and len(r.prompt) + len(r.tokens) <= max(self.buckets))

    def preempt_slot(self, slot: int, prefix_cache=None) -> Request:
        """Evict ``slot``'s request between segments and return it for
        requeueing — the priority-preemption primitive (ISSUE 8b). The
        device sees one tiny scatter (rem[slot] = 0: the slot freezes
        and its writes route to the trash page) and NO sync; everything
        else is host bookkeeping:

        * with a ``prefix_cache``: the slot's page-aligned prefix
          (prompt + tokens generated so far) is PARKED in the cache by
          reference before the slot's refs release — harvest-by-
          reference, zero KV row copies — so the resume admission is a
          page-ref bump plus a suffix-only prefill of the unaligned
          tail;
        * without one: the pages free outright and resume re-prefills
          (still token-identical — greedy).

        The caller decides where the request re-enters the queue (the
        SLO scheduler reinserts it at the head of its class)."""
        assert self._pending_seg is None, \
            "preempt with a dispatched segment in flight"
        r = self._active[slot]
        assert r is not None, f"preempt of empty slot {slot}"
        # freeze on device: a dispatch, not a sync (the audit contract
        # of the serve loop — one fetch per segment — is untouched).
        # The index rides as a DEVICE operand, not a baked constant, so
        # one compiled scatter covers every slot — aot_warmup prewarms
        # it and the zero-post-warmup-compile budget holds across
        # preemptions of any slot (r20)
        self._rem = self._rem.at[jnp.asarray(slot, jnp.int32)].set(0)
        self._rem_host[slot] = 0
        self._active[slot] = None
        r.preemptions += 1
        fp, _ = r.resume_view()
        r._meter_release()
        pgr = self.pager
        if prefix_cache is not None:
            plen_b = prefix_cache.round_down(len(fp))
            if plen_b:
                prefix_cache.insert(
                    fp[:plen_b],
                    pgr.slot_pages[slot][:plen_b // self.page_size])
        pgr.free_slot(slot)
        _metrics.counter("serving.preemptions").inc()
        _flight.record("preempt", rid=r.rid, slot=slot,
                       tokens_done=len(r.tokens),
                       remaining=r.max_new_tokens - len(r.tokens),
                       parked=prefix_cache is not None)
        return r

    def abort(self) -> List[Request]:
        """Tear the engine down after a replica failure (fleet failover,
        ISSUE 8c) and return every request it still owed: the queue, the
        live slots, and anything an in-flight (dispatched, never
        fetched) segment had picked — that segment's event log is LOST,
        but its requests' host state never advanced, so each resumes
        elsewhere from its last fetched token (greedy decode keeps the
        stream identical). Slot vectors and the page pool reset so a
        recovered replica re-enters service empty."""
        orphans: List[Request] = []
        p, self._pending_seg = self._pending_seg, None
        self.gap_from_ns = None
        released_rids = set()
        if p is not None:
            for pages in p.req_pages:
                self.pager.release_pages(pages)
            for r in p.picked:
                r.admit_time = 0.0
                r._meter_release()
                released_rids.add(r.rid)
            orphans += p.picked
        # r23: held multi-segment prefill reservations die with the
        # replica (their landed KV rows are lost) — the request resumes
        # elsewhere with a fresh full prefill
        for rid, info in self._sp_inflight.items():
            if rid not in released_rids:
                self.pager.release_pages(info["pages"])
        self._sp_inflight = {}
        for r in self._active:
            if r is not None:
                r._meter_release()
        for r in self._queue:
            r._meter_release()   # held sp reservations just released
        orphans += [r for r in self._active if r is not None]
        orphans += self._queue
        self._queue = []
        self._active = [None] * self.slots
        self._rem_host = [0] * self.slots
        self._pos = self._slot_vec()
        self._nxt = self._slot_vec()
        self._rem = self._slot_vec()
        self._init_spec_state()
        self.pager.reset()
        return orphans

    def run_segment(self, max_steps: int, prefix_cache=None,
                    n_pad: Optional[int] = None,
                    now: Optional[float] = None) -> dict:
        """One fused continuous-batching segment: admit FCFS from the
        queue into free slots (at most ``n_pad``), decode up to
        ``max_steps`` ticks, ONE dispatch + ONE fetch, then replay the
        event log host-side to distribute tokens and retire requests.

        Returns {"steps", "admitted", "first_tokens", "finished"} — rid
        lists the caller (the online scheduler) stamps with the sync
        wall-clock time; ``now`` defaults to time.perf_counter() and is
        recorded as each admitted request's admit_time.

        r12: dispatch and fetch are separable — ``dispatch_segment``
        launches the program and returns immediately (jax async
        dispatch), ``finish_segment`` blocks on the event fetch and runs
        the host replay. The fleet router uses the split to overlap N
        replicas' device work; this method is the two back to back."""
        return self.finish_segment(
            self.dispatch_segment(max_steps, prefix_cache, n_pad, now))

    def dispatch_segment(self, max_steps: int, prefix_cache=None,
                         n_pad: Optional[int] = None,
                         now: Optional[float] = None) -> _PendingSegment:
        """Launch one fused segment WITHOUT fetching its event log: picks
        requests, reserves their page lists, dispatches the program,
        and records the device futures in a ``_PendingSegment``.
        At most one segment may be in flight per engine — the slot-state
        arrays the next dispatch would consume are this segment's donated
        outputs, and the host queue/slot mirrors only advance at the
        fetch."""
        if self._pending_seg is not None:
            raise RuntimeError(
                "dispatch_segment with a segment already in flight — "
                "finish_segment must run first (one outstanding segment "
                "per engine)")
        if now is None:
            # the admit_time stamp feeds the SLO EWMAs (decision
            # inputs), so it reads the r16 DECISION clock — recorded
            # with a journal attached, fed back during replay
            now = _journal.now()
        n_pad = n_pad or self._pow2(self.slots)
        pending = self._dispatch_segment_paged(max_steps, prefix_cache,
                                               n_pad, now)
        pending.seg = self.seg_index
        self.seg_index += 1
        self._pending_seg = pending
        return pending

    def finish_segment(self, pending: Optional[_PendingSegment] = None
                       ) -> dict:
        """Block on a dispatched segment's event fetch (THE audited
        per-segment sync) and replay it host-side. Returns the
        ``run_segment`` result dict."""
        p = pending if pending is not None else self._pending_seg
        if p is None or p is not self._pending_seg:
            raise RuntimeError("finish_segment without a matching "
                               "dispatched segment")
        self._pending_seg = None
        return self._finish_segment_paged(p)

    # --- segment programs (r11: page-table KV, inference/paged_kv.py) -----
    def _paged_segment_prog(self, n_pad: int, s_max: int, max_steps: int):
        """The segment program: one compiled, RE-ENTRANT drain step. It
        starts from the engine's *current* slot state (pool, page table,
        pos/nxt/rem as inputs, not zeros), admits up to ``n_pad`` queued
        requests into slots as they free, decodes for at most
        ``max_steps`` loop iterations, and returns the slot state plus
        an event log the host replays:

        * slot state is an argument — a segment composes with previous
          segments, so newly arrived requests join slots freed by
          EOS/retirement mid-flight;
        * the loop is step-bounded — the host regains control every
          ``max_steps`` ticks to ingest arrivals and stamp real
          (measured) per-request times at the sync;
        * outputs are an event log indexed by (local step, slot)
          (``out``) plus per-step admit records (``aq``/``aslot``) —
          NOT per-request rows — so requests admitted in *earlier*
          segments keep streaming into the same log and the host replay
          attributes tokens by tracking slot occupancy;
        * pool and page table are donated and updated in place: the pool
          rides the while_loop's carry through ``_one_of`` (never a
          ``lax.cond``) into the model's ``forward_with_pages``, whose
          layer loop carries it too, so the compiled program holds ONE
          copy of each plane and moves no more of it than the rows a
          step writes and the pages its attention reads
          (``aot_warmup``'s ``temp_bytes`` against ``pool_bytes`` says
          so);
        * the admit branch INSTALLS the request's host-reserved page
          list into the slot's table row and prefills the suffix
          directly into those pages at context offset ``pre_len`` —
          shared-prefix rows are already resident in the shared pages,
          so a hit contributes ZERO KV row copies to the program;
        * the decode branch passes the live mask so retired slots'
          writes route to the trash page.

        The memo key carries NO prefix width: prefix geometry is page
        DATA (pre_lens + tables), not shape — a shared-prefix workload
        adds zero program shapes.

        r17 (ISSUE 12): with ``quality_digest`` the program family is
        ("qseg", n_pad, s_max, steps) — same loop, same single fetch,
        but the event log additionally carries per-step per-slot logit
        digests (the emitted token's logit + the tick's top-k ids and
        values, fp32) computed in-program from logits the tick already
        produced. Digest arrays are [steps, slots(, k)] — bytes per
        tick are (1 + 2k) * 4 * slots, invisible next to the weight
        stream (SCALING §3l) — and ride the SAME audited fetch, so the
        one-dispatch/one-fetch contract is untouched (the
        quality_serving_segment gate program pins it)."""
        if self.quant:
            # r21: the quantized engine's segments are a DTYPE AXIS on
            # the paged family — the program BODY is identical (the
            # narrow pool dtype + scale planes flow through
            # llama.forward_with_pages from the donated pool operand);
            # the axis exists so the coverage auditor enumerates and
            # warms the quantized rungs separately (their compiled
            # programs differ, so their keys must too). quality_digest
            # composes: the digest columns certify the rollout.
            from ..quantization.serving import QUANT_CODES

            key = PROGRAM_SPACE.key("qpseg", n_pad=n_pad, s_max=s_max,
                                    steps=max_steps,
                                    dtype=QUANT_CODES[self.quant])
            return self._memo_prog(
                key, lambda: self._build_paged_segment_prog(
                    n_pad, s_max, max_steps,
                    digest_k=(self.digest_top_k if self.quality_digest
                              else 0)))
        if self.quality_digest:
            key = PROGRAM_SPACE.key("qseg", n_pad=n_pad, s_max=s_max,
                                    steps=max_steps)
            return self._memo_prog(
                key, lambda: self._build_paged_segment_prog(
                    n_pad, s_max, max_steps,
                    digest_k=self.digest_top_k))
        key = PROGRAM_SPACE.key("pseg", n_pad=n_pad, s_max=s_max,
                                steps=max_steps)
        return self._memo_prog(key, lambda: self._build_paged_segment_prog(
            n_pad, s_max, max_steps))

    def _build_paged_segment_prog(self, n_pad: int, s_max: int,
                                  max_steps: int, digest_k: int = 0):
        cfg, slots, eos = self.cfg, self.slots, self.eos
        max_pages = self.pager.max_pages
        # where sequences keep a fixed part beside their pages, slot s's is
        # part s + 1 and the table's last column names it (``paged_kv``)
        fixed_parts = self.pager.fixed_parts
        model = family_of(cfg)
        forward = model.forward_with_pages
        # a model that counts per step (experts picked, held, hit) hands
        # its counters back with the logits; they ride the event log
        n_counters = len(getattr(model, "SEGMENT_COUNTERS", ()))

        def forward_counted(st, new, *args, **kw):
            if not n_counters:
                return forward(*args, **kw)
            logits, pool, c = forward(*args, with_counters=True, **kw)
            new["cnt"] = st["cnt"].at[st["step"]].set(c)
            return logits, pool

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def segment(params, pool, ptab, pos, nxt, rem, prompts, lens,
                    gens, pre_lens, req_tables, n_real):
            i32 = jnp.int32
            st = dict(
                pool=pool, pt=ptab, pos=pos, nxt=nxt, rem=rem,
                out=jnp.zeros((max_steps, slots), i32),
                aq=jnp.full((max_steps,), n_pad, i32),    # n_pad = decode
                aslot=jnp.zeros((max_steps,), i32),
                qidx=i32(0), step=i32(0),
            )
            if n_counters:
                st["cnt"] = jnp.zeros((max_steps, n_counters), i32)
            if digest_k:
                # r17 logit digests: emitted-token logit + top-k
                # (ids, values) per step/slot — fp32 event-log columns
                # the host replay distributes per request
                st.update(
                    dlg=jnp.zeros((max_steps, slots), jnp.float32),
                    dti=jnp.zeros((max_steps, slots, digest_k), i32),
                    dtv=jnp.zeros((max_steps, slots, digest_k),
                                  jnp.float32),
                )

            def cond(st):
                work = jnp.any(st["rem"] > 0) | (st["qidx"] < n_real)
                return work & (st["step"] < max_steps)

            @llama.scoped("segment.admit")
            def admit(st):
                s = jnp.argmin(st["rem"])          # a rem==0 slot
                q = st["qidx"]
                row = jax.lax.dynamic_slice(req_tables, (q, 0),
                                            (1, max_pages))
                if fixed_parts:
                    row = jnp.concatenate(
                        [row, jnp.reshape(s + 1, (1, 1)).astype(i32)], 1)
                prow = jax.lax.dynamic_slice(prompts, (q, 0), (1, s_max))
                ln = lens[q]
                pln = pre_lens[q]
                # suffix-only prefill AT context offset pln: queries sit
                # at positions pln..pln+s_max-1 and attend the shared
                # prefix pages in place — the prefix's quadratic
                # attention, its per-token matmuls AND its KV writes are
                # all skipped
                extra = {}
                logits, pool = forward_counted(
                    st, extra, params, prow, cfg, st["pool"], row,
                    jnp.reshape(pln, (1,)), logit_pos=ln - 1)
                t0 = _greedy(logits).reshape(())
                rem_new = gens[q] - 1
                if eos is not None:
                    rem_new = jnp.where(t0 == eos, 0, rem_new)
                new = dict(
                    pool=pool,
                    pt=st["pt"].at[s].set(row[0]),
                    pos=st["pos"].at[s].set(pln + ln),
                    nxt=st["nxt"].at[s].set(t0),
                    rem=st["rem"].at[s].set(rem_new),
                    out=st["out"].at[st["step"], s].set(t0),
                    aq=st["aq"].at[st["step"]].set(q),
                    aslot=st["aslot"].at[st["step"]].set(s),
                    qidx=q + 1, step=st["step"], **extra,
                )
                if digest_k:
                    lg = logits.astype(jnp.float32)       # [1, V]
                    tv, ti = jax.lax.top_k(lg, digest_k)
                    el = jnp.take_along_axis(
                        lg, t0.reshape(1, 1), axis=-1)[0, 0]
                    new["dlg"] = st["dlg"].at[st["step"], s].set(el)
                    new["dti"] = st["dti"].at[st["step"], s].set(ti[0])
                    new["dtv"] = st["dtv"].at[st["step"], s].set(tv[0])
                return new

            @llama.scoped("segment.decode")
            def decode(st):
                live = st["rem"] > 0
                extra = {}
                logits, pool = forward_counted(
                    st, extra, params, st["nxt"][:, None], cfg, st["pool"],
                    st["pt"], st["pos"], live=live)
                tok = _greedy(logits)
                tok = jnp.where(live, tok, st["nxt"])
                rem = st["rem"] - live.astype(jnp.int32)
                if eos is not None:
                    rem = jnp.where(live & (tok == eos), 0, rem)
                new = dict(
                    pool=pool, pt=st["pt"],
                    pos=st["pos"] + live.astype(jnp.int32),
                    nxt=tok, rem=rem,
                    out=st["out"].at[st["step"]].set(tok),
                    aq=st["aq"], aslot=st["aslot"],
                    qidx=st["qidx"], step=st["step"], **extra,
                )
                if digest_k:
                    lg = logits.astype(jnp.float32)       # [slots, V]
                    tv, ti = jax.lax.top_k(lg, digest_k)
                    el = jnp.take_along_axis(lg, tok[:, None],
                                             axis=-1)[:, 0]
                    new["dlg"] = st["dlg"].at[st["step"]].set(el)
                    new["dti"] = st["dti"].at[st["step"]].set(ti)
                    new["dtv"] = st["dtv"].at[st["step"]].set(tv)
                return new

            def body(st):
                can_admit = (st["qidx"] < n_real) & jnp.any(st["rem"] == 0)
                st = _one_of(can_admit, admit, decode, st)
                st["step"] = st["step"] + 1
                return st

            st = jax.lax.while_loop(cond, body, st)
            outs = (st["pool"], st["pt"], st["pos"], st["nxt"], st["rem"],
                    st["out"], st["aq"], st["aslot"])
            if digest_k:
                outs += (st["dlg"], st["dti"], st["dtv"])
            if n_counters:
                outs += (st["cnt"],)
            return outs + (st["step"], st["qidx"])

        return segment

    # --- chunked prefill (r13: bounded time-between-tokens) ----------------

    def _prefill_chunk_for(self, s_max: int) -> int:
        """Chunk width for a segment whose admit window is ``s_max``
        wide: the smallest ladder entry that bounds a full-width prefill
        at ``program_space.MAX_PREFILL_CHUNKS`` chunk steps — short
        windows get tight time-between-tokens, long ones a bounded step
        count, and every width is DECLARED (a finite ("cseg", ..)
        program-key family; a floating chunk width would re-open the
        mid-serve-compile hazard the bucket pinning closed). The cap
        matters for ADMISSION throughput too: a prefill may only start
        while 2 x chunks steps remain in the segment budget, so a finer
        ladder narrows the start window and long prompts begin to
        monopolize segment heads (measured on the overload lane —
        8-chunk prefills throttled admission to one start per segment).

        r20: the arithmetic lives in ``program_space.chunk_for`` — ONE
        copy shared by dispatch and the ``cseg`` family's static
        enumerator, so coverage can never drift from the runtime."""
        return chunk_for(self.prefill_chunks, s_max)

    def _chunked_segment_prog(self, n_pad: int, s_max_c: int, C: int,
                              max_steps: int):
        """``_paged_segment_prog`` with the admit branch split into
        ``C``-token prefill chunks INTERLEAVED with decode ticks: a
        long prompt no longer stalls every co-resident decode for its
        whole prefill — between consecutive chunks the running slots
        each emit a token, so time-between-tokens is bounded by ONE
        chunk's cost (the ISSUE 8 TTFT-p99-spike fix; ROADMAP item 4).
        Same pool/page-table state, same event log, same single fetch:

        * in-program prefill PROGRESS state (``pf``/``pfq``/``pfo``): at
          most one slot is mid-prefill; each chunk step prefills tokens
          [pfo, pfo+C) of its suffix at context offset pre_len+pfo —
          exactly the q_len>1 page-indirect path the unified kernel
          already serves (``llama.forward_with_pages``), so no new
          kernel work exists here, only scheduling;
        * the FINAL chunk samples the first token and emits the admit
          event; non-final chunk steps log ``aq = n_pad + 1`` (the
          chunk marker) and the host replay skips them — the replay
          contract is unchanged;
        * a prefill only STARTS if its 2*ceil(len/C) worst-case step
          cost fits the remaining budget, so a segment never ends with
          a half-prefilled slot (no cross-segment prefill state to
          carry; un-started requests requeue exactly as before);
        * ``phase`` alternates chunk/decode steps while anything is
          live, and chunks run back-to-back when nothing is decoding
          (nobody is waiting on a token, so interleaving would only
          add latency).

        ``s_max_c`` is the admit window rounded up to a chunk multiple
        (slices never clamp); memo key ("cseg", n_pad, s_max_c, C,
        max_steps) with C from the declared ladder."""
        if s_max_c % C:
            raise ValueError(f"admit window {s_max_c} is not a multiple "
                             f"of the prefill chunk {C}")
        key = PROGRAM_SPACE.key("cseg", n_pad=n_pad, s_max=s_max_c, c=C,
                                steps=max_steps)
        return self._memo_prog(key, lambda: self._build_chunked_segment_prog(
            n_pad, s_max_c, C, max_steps))

    def _build_chunked_segment_prog(self, n_pad: int, s_max_c: int, C: int,
                                    max_steps: int):
        cfg, slots, eos = self.cfg, self.slots, self.eos
        max_pages = self.pager.max_pages

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def segment(params, pool, ptab, pos, nxt, rem, prompts, lens,
                    gens, pre_lens, req_tables, n_real):
            i32 = jnp.int32
            st = dict(
                pool=pool, pt=ptab, pos=pos, nxt=nxt, rem=rem,
                out=jnp.zeros((max_steps, slots), i32),
                aq=jnp.full((max_steps,), n_pad, i32),    # n_pad = decode
                aslot=jnp.zeros((max_steps,), i32),
                pf=i32(-1),      # slot mid-prefill (-1 = none)
                pfq=i32(0),      # its queue row
                pfo=i32(0),      # suffix tokens already prefilled
                phase=i32(0),    # 1 = just chunked -> decode next
                qidx=i32(0), step=i32(0),
            )

            def _startable(st):
                # a new prefill may begin only if its worst-case step
                # cost (chunks + interleaved decodes) fits the budget
                ln = lens[jnp.minimum(st["qidx"], n_pad - 1)]
                chunks = (ln + C - 1) // C
                return ((st["qidx"] < n_real)
                        & (st["step"] + 2 * chunks <= max_steps))

            def cond(st):
                work = (jnp.any(st["rem"] > 0) | (st["pf"] >= 0)
                        | _startable(st))
                return work & (st["step"] < max_steps)

            @llama.scoped("segment.admit")
            def chunk(st):
                starting = st["pf"] < 0
                s = jnp.where(starting,
                              jnp.argmin(st["rem"]).astype(jnp.int32),
                              st["pf"])
                q = jnp.where(starting, st["qidx"], st["pfq"])
                off = jnp.where(starting, 0, st["pfo"])
                row = jax.lax.dynamic_slice(req_tables, (q, 0),
                                            (1, max_pages))
                # installing the table row is idempotent across chunks
                pt = st["pt"].at[s].set(row[0])
                ln = lens[q]
                pln = pre_lens[q]
                ctok = jax.lax.dynamic_slice(prompts, (q, off), (1, C))
                # one C-token prefill chunk at context offset pln+off —
                # queries attend the shared prefix AND earlier chunks in
                # place through the page table, so chunked == one-shot
                # prefill mathematically (token-parity-tested)
                logits, pool = llama.forward_with_pages(
                    params, ctok, cfg, st["pool"], row,
                    jnp.reshape(pln + off, (1,)),
                    logit_pos=jnp.minimum(ln - 1 - off, C - 1))
                done = off + C >= ln
                t0 = _greedy(logits).reshape(())
                rem_new = gens[q] - 1
                if eos is not None:
                    rem_new = jnp.where(t0 == eos, 0, rem_new)
                return dict(
                    pool=pool, pt=pt,
                    pos=jnp.where(done, st["pos"].at[s].set(pln + ln),
                                  st["pos"]),
                    nxt=jnp.where(done, st["nxt"].at[s].set(t0),
                                  st["nxt"]),
                    rem=jnp.where(done, st["rem"].at[s].set(rem_new),
                                  st["rem"]),
                    out=jnp.where(done,
                                  st["out"].at[st["step"], s].set(t0),
                                  st["out"]),
                    aq=st["aq"].at[st["step"]].set(
                        jnp.where(done, q, i32(n_pad + 1))),
                    aslot=st["aslot"].at[st["step"]].set(s),
                    pf=jnp.where(done, i32(-1), s),
                    pfq=q, pfo=off + C, phase=i32(1),
                    qidx=jnp.where(starting, st["qidx"] + 1, st["qidx"]),
                    step=st["step"],
                )

            @llama.scoped("segment.decode")
            def decode(st):
                live = st["rem"] > 0
                logits, pool = llama.forward_with_pages(
                    params, st["nxt"][:, None], cfg, st["pool"],
                    st["pt"], st["pos"], live=live)
                tok = _greedy(logits)
                tok = jnp.where(live, tok, st["nxt"])
                rem = st["rem"] - live.astype(jnp.int32)
                if eos is not None:
                    rem = jnp.where(live & (tok == eos), 0, rem)
                return dict(
                    pool=pool, pt=st["pt"],
                    pos=st["pos"] + live.astype(jnp.int32),
                    nxt=tok, rem=rem,
                    out=st["out"].at[st["step"]].set(tok),
                    aq=st["aq"], aslot=st["aslot"],
                    pf=st["pf"], pfq=st["pfq"], pfo=st["pfo"],
                    phase=i32(0),
                    qidx=st["qidx"], step=st["step"],
                )

            def body(st):
                live_any = jnp.any(st["rem"] > 0)
                pf_active = st["pf"] >= 0
                can_start = ((~pf_active) & jnp.any(st["rem"] == 0)
                             & _startable(st))
                do_chunk = ((pf_active | can_start)
                            & ((st["phase"] == 0) | ~live_any))
                st = _one_of(do_chunk, chunk, decode, st)
                st["step"] = st["step"] + 1
                return st

            st = jax.lax.while_loop(cond, body, st)
            return (st["pool"], st["pt"], st["pos"], st["nxt"], st["rem"],
                    st["out"], st["aq"], st["aslot"], st["step"],
                    st["qidx"])

        return segment

    # --- sequence-parallel long-context prefill (r23: ISSUE 18) -----------

    def _sp_segment_prog(self, n_pad: int, s_max_c: int, C: int,
                         max_steps: int):
        """``_chunked_segment_prog`` with the prefill chunk widened into
        an sp-row SLAB: each chunk step prefills ``sp`` consecutive
        C-token chunks as ``sp`` BATCH rows of one
        ``forward_with_pages`` call, every row writing its KV slice
        straight into the request's pages at its own absolute offset.
        The batch axis IS the sequence-parallel shard axis — under an
        'sp' mesh GSPMD runs each row on its own devices (ring/Ulysses
        attention across shards, ``ops/pallas/ring_attention.py``);
        without one it is a plain batched call. Either way the math is
        BIT-IDENTICAL to the unsharded chunked prefill: all slab rows
        scatter before any row attends (per layer), the paged gather
        window and its absolute-position masks are unchanged, so each
        query reduces over exactly the same values (the page-parity and
        token-parity tests pin this). Decode is untouched — the slab
        lands pool pages the ordinary page-indirect decode path reads,
        zero relayout at the prefill->decode boundary.

        Differences from the cseg program:

        * a prefill may SPAN segments: ``_startable`` drops the
          2*chunks budget gate (a 128k prefill never fits one segment
          by design) and the final ``pf``/``pfq``/``pfo`` progress
          state returns in the SAME single fetch — the host keeps the
          page reservation and re-dispatches the remainder as a
          continuation with ``pre_len`` advanced past the landed rows;
        * slab coverage rounds the suffix up to ``sp*C``; overrun rows
          land in reserved tail pages or the trash page and are never
          read (position-masked), and the emitted first token comes
          from the WINNER row — the one holding the suffix's true last
          token.

        Memo key ("spseg", n_pad, s_max, C, sp, steps): s_max is a
        slab-rounded ``long_buckets`` rung, C the largest declared
        prefill chunk (TBT for co-resident decodes is bounded by ONE
        slab's cost — sp*C tokens through the model, which the 'sp'
        mesh runs as C per shard)."""
        sp = self.seq_parallel
        if s_max_c % (sp * C):
            raise ValueError(f"admit window {s_max_c} is not a multiple "
                             f"of the sp slab {sp}*{C}")
        key = PROGRAM_SPACE.key("spseg", n_pad=n_pad, s_max=s_max_c, c=C,
                                sp=sp, steps=max_steps)
        return self._memo_prog(key, lambda: self._build_sp_segment_prog(
            n_pad, s_max_c, C, sp, max_steps))

    def _build_sp_segment_prog(self, n_pad: int, s_max_c: int, C: int,
                               sp: int, max_steps: int):
        cfg, slots, eos = self.cfg, self.slots, self.eos
        max_pages = self.pager.max_pages
        Cs = sp * C

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def segment(params, pool, ptab, pos, nxt, rem, prompts, lens,
                    gens, pre_lens, req_tables, n_real):
            i32 = jnp.int32
            st = dict(
                pool=pool, pt=ptab, pos=pos, nxt=nxt, rem=rem,
                out=jnp.zeros((max_steps, slots), i32),
                aq=jnp.full((max_steps,), n_pad, i32),    # n_pad = decode
                aslot=jnp.zeros((max_steps,), i32),
                pf=i32(-1),      # slot mid-prefill (-1 = none)
                pfq=i32(0),      # its queue row
                pfo=i32(0),      # suffix tokens already prefilled
                phase=i32(0),    # 1 = just chunked -> decode next
                qidx=i32(0), step=i32(0),
            )

            def _startable(st):
                # unlike cseg there is NO worst-case budget gate: a
                # long prefill is EXPECTED to span segments — progress
                # carries over through pf/pfq/pfo
                return st["qidx"] < n_real

            def cond(st):
                work = (jnp.any(st["rem"] > 0) | (st["pf"] >= 0)
                        | _startable(st))
                return work & (st["step"] < max_steps)

            @llama.scoped("segment.admit")
            def chunk(st):
                starting = st["pf"] < 0
                s = jnp.where(starting,
                              jnp.argmin(st["rem"]).astype(jnp.int32),
                              st["pf"])
                q = jnp.where(starting, st["qidx"], st["pfq"])
                off = jnp.where(starting, 0, st["pfo"])
                row = jax.lax.dynamic_slice(req_tables, (q, 0),
                                            (1, max_pages))
                # installing the table row is idempotent across chunks
                pt = st["pt"].at[s].set(row[0])
                ln = lens[q]
                pln = pre_lens[q]
                ar = jnp.arange(sp, dtype=i32)
                # one sp-row slab: row i prefills suffix tokens
                # [off+i*C, off+(i+1)*C) at absolute offset
                # pln+off+i*C through the SAME page-table row — every
                # row scatters before any row attends, so the slab is
                # bit-identical to sp sequential chunks
                slab = jax.lax.dynamic_slice(
                    prompts, (q, off), (1, Cs)).reshape(sp, C)
                logits, pool = llama.forward_with_pages(
                    params, slab, cfg, st["pool"],
                    jnp.broadcast_to(row, (sp, max_pages)),
                    pln + off + ar * C,
                    logit_pos=jnp.clip(ln - 1 - off - ar * C, 0, C - 1))
                done = off + Cs >= ln
                # the winner row holds the suffix's true last token;
                # rows past it see garbage their clamp masks out
                r_star = jnp.clip((ln - 1 - off) // C, 0, sp - 1)
                t0 = _greedy(logits)[r_star]
                rem_new = gens[q] - 1
                if eos is not None:
                    rem_new = jnp.where(t0 == eos, 0, rem_new)
                return dict(
                    pool=pool, pt=pt,
                    pos=jnp.where(done, st["pos"].at[s].set(pln + ln),
                                  st["pos"]),
                    nxt=jnp.where(done, st["nxt"].at[s].set(t0),
                                  st["nxt"]),
                    rem=jnp.where(done, st["rem"].at[s].set(rem_new),
                                  st["rem"]),
                    out=jnp.where(done,
                                  st["out"].at[st["step"], s].set(t0),
                                  st["out"]),
                    aq=st["aq"].at[st["step"]].set(
                        jnp.where(done, q, i32(n_pad + 1))),
                    aslot=st["aslot"].at[st["step"]].set(s),
                    pf=jnp.where(done, i32(-1), s),
                    pfq=q, pfo=off + Cs, phase=i32(1),
                    qidx=jnp.where(starting, st["qidx"] + 1, st["qidx"]),
                    step=st["step"],
                )

            @llama.scoped("segment.decode")
            def decode(st):
                live = st["rem"] > 0
                logits, pool = llama.forward_with_pages(
                    params, st["nxt"][:, None], cfg, st["pool"],
                    st["pt"], st["pos"], live=live)
                tok = _greedy(logits)
                tok = jnp.where(live, tok, st["nxt"])
                rem = st["rem"] - live.astype(jnp.int32)
                if eos is not None:
                    rem = jnp.where(live & (tok == eos), 0, rem)
                return dict(
                    pool=pool, pt=st["pt"],
                    pos=st["pos"] + live.astype(jnp.int32),
                    nxt=tok, rem=rem,
                    out=st["out"].at[st["step"]].set(tok),
                    aq=st["aq"], aslot=st["aslot"],
                    pf=st["pf"], pfq=st["pfq"], pfo=st["pfo"],
                    phase=i32(0),
                    qidx=st["qidx"], step=st["step"],
                )

            def body(st):
                live_any = jnp.any(st["rem"] > 0)
                pf_active = st["pf"] >= 0
                can_start = ((~pf_active) & jnp.any(st["rem"] == 0)
                             & _startable(st))
                do_chunk = ((pf_active | can_start)
                            & ((st["phase"] == 0) | ~live_any))
                st = _one_of(do_chunk, chunk, decode, st)
                st["step"] = st["step"] + 1
                return st

            st = jax.lax.while_loop(cond, body, st)
            return (st["pool"], st["pt"], st["pos"], st["nxt"], st["rem"],
                    st["out"], st["aq"], st["aslot"], st["pf"],
                    st["pfq"], st["pfo"], st["step"], st["qidx"])

        return segment

    # --- speculative + sampled segments (r15: ISSUE 10, ROADMAP item 3) ---
    def _spec_segment_prog(self, n_pad: int, max_steps: int):
        """The paged segment with MULTI-TOKEN VERIFIED TICKS: every
        decode step drafts ``K = self.speculative`` tokens per live slot
        from the slot's page-resident token history (an in-program
        n-gram/prompt-suffix lookup — the draft table is built from
        segment state, zero host contact) and scores all K+1 positions
        in ONE forward pass through the unified paged q_len>1 path
        (``llama.forward_with_pages`` at the slot's context offset —
        exactly the chunked-prefill machinery, so verification adds no
        new kernel). Decode is HBM-bound (SCALING §3c: each tick streams
        the full weight set), so emitting accepted-length > 1 tokens per
        weight stream is the one lever that BEATS the roofline instead
        of approaching it (SCALING §3j).

        Acceptance is computed in-program and rolled into the event log
        (``out`` [steps, slots, K+1] + ``acc`` [steps, slots]): the host
        replay recovers per-request accepted lengths from the SAME
        single fetch — the audited one-dispatch/one-fetch contract is
        untouched. Greedy verification emits the target's argmax chain,
        so the speculative greedy stream is token-identical to the
        non-speculative engine by construction (the draft only decides
        how MANY chain tokens emit per tick, never their values). With a
        sampling config, rejection sampling against the deterministic
        (delta) draft keeps the emitted stream distributed exactly as
        non-speculative sampling; per-slot RNG keys ride segment state,
        re-seeded from the request's seed at admission.

        Admission reuses the r13 chunk branch with the chunk width C
        pinned by config (the declared ladder when ``chunked_prefill``,
        else the full admit window = one-step prefill), and the admit
        window itself is PINNED to the largest bucket — the memo key
        ("sseg", n_pad, K, steps) carries no width, so prefix hits and
        arrival jitter add zero program shapes. K = 0 with a sampling
        config is the plain SAMPLED paged segment (a verify tick over
        one position is exactly a sampled decode tick), which keeps the
        canonical paged/chunked greedy programs byte-identical."""
        K = self.speculative
        key = PROGRAM_SPACE.key("sseg", n_pad=n_pad, k=K, steps=max_steps)
        return self._memo_prog(key, lambda: self._build_spec_segment_prog(
            n_pad, K, max_steps))

    def _build_spec_segment_prog(self, n_pad: int, K: int, max_steps: int):
        cfg, slots, eos = self.cfg, self.slots, self.eos
        max_pages = self.pager.max_pages
        max_len = self.max_len
        sampling = self.sampling
        s_max = self.buckets[-1]
        if self.chunked:
            C = self._prefill_chunk_for(s_max)
            s_max = -(-s_max // C) * C
        else:
            C = s_max

        @functools.partial(jax.jit, donate_argnums=(1, 2, 6))
        def segment(params, pool, ptab, pos, nxt, rem, hist, hstart, rng,
                    prompts, lens, gens, pre_lens, req_tables, seeds,
                    n_real):
            i32 = jnp.int32
            sl = jnp.arange(slots)
            st = dict(
                pool=pool, pt=ptab, pos=pos, nxt=nxt, rem=rem,
                hist=hist, hstart=hstart, rng=rng,
                out=jnp.zeros((max_steps, slots, K + 1), i32),
                acc=jnp.zeros((max_steps, slots), i32),
                aq=jnp.full((max_steps,), n_pad, i32),    # n_pad = verify
                aslot=jnp.zeros((max_steps,), i32),
                pf=i32(-1), pfq=i32(0), pfo=i32(0), phase=i32(0),
                qidx=i32(0), step=i32(0),
            )

            def _startable(st):
                ln = lens[jnp.minimum(st["qidx"], n_pad - 1)]
                chunks = (ln + C - 1) // C
                return ((st["qidx"] < n_real)
                        & (st["step"] + 2 * chunks <= max_steps))

            def cond(st):
                work = (jnp.any(st["rem"] > 0) | (st["pf"] >= 0)
                        | _startable(st))
                return work & (st["step"] < max_steps)

            @llama.scoped("segment.admit")
            def chunk(st):
                # the admit path — the r13 chunk branch plus the spec
                # state writes: the chunk's tokens land in the slot's
                # history mirror, hstart pins the draft-scan floor at
                # the shared-prefix boundary, and a sampling engine
                # re-seeds the slot's RNG from the request seed
                starting = st["pf"] < 0
                s = jnp.where(starting,
                              jnp.argmin(st["rem"]).astype(jnp.int32),
                              st["pf"])
                q = jnp.where(starting, st["qidx"], st["pfq"])
                off = jnp.where(starting, 0, st["pfo"])
                row = jax.lax.dynamic_slice(req_tables, (q, 0),
                                            (1, max_pages))
                pt = st["pt"].at[s].set(row[0])
                ln = lens[q]
                pln = pre_lens[q]
                ctok = jax.lax.dynamic_slice(prompts, (q, off), (1, C))
                logits, pool = llama.forward_with_pages(
                    params, ctok, cfg, st["pool"], row,
                    jnp.reshape(pln + off, (1,)),
                    logit_pos=jnp.minimum(ln - 1 - off, C - 1))
                done = off + C >= ln
                if sampling is None:
                    t0 = _greedy(logits).reshape(())
                    rng_new = st["rng"]
                else:
                    k0, kuse = jax.random.split(
                        jax.random.PRNGKey(seeds[q]))
                    filt = llama.sample_filter_logits(logits, *sampling)
                    t0 = jax.random.categorical(
                        kuse, filt, axis=-1).astype(i32).reshape(())
                    rng_new = st["rng"].at[s].set(k0)
                rem_new = gens[q] - 1
                if eos is not None:
                    rem_new = jnp.where(t0 == eos, 0, rem_new)
                # token history: the chunk's suffix tokens at absolute
                # positions pln+off.. (clamped into the overflow column
                # so a near-capacity admit cannot corrupt valid rows)
                hidx = jnp.minimum(pln + off + jnp.arange(C), max_len)
                hist_new = st["hist"].at[s, hidx].set(ctok[0])
                return dict(
                    pool=pool, pt=pt,
                    pos=jnp.where(done, st["pos"].at[s].set(pln + ln),
                                  st["pos"]),
                    nxt=jnp.where(done, st["nxt"].at[s].set(t0),
                                  st["nxt"]),
                    rem=jnp.where(done, st["rem"].at[s].set(rem_new),
                                  st["rem"]),
                    hist=hist_new,
                    hstart=st["hstart"].at[s].set(pln),
                    rng=jnp.where(done, rng_new, st["rng"]),
                    out=jnp.where(done,
                                  st["out"].at[st["step"], s, 0].set(t0),
                                  st["out"]),
                    acc=jnp.where(done,
                                  st["acc"].at[st["step"], s].set(1),
                                  st["acc"]),
                    aq=st["aq"].at[st["step"]].set(
                        jnp.where(done, q, i32(n_pad + 1))),
                    aslot=st["aslot"].at[st["step"]].set(s),
                    pf=jnp.where(done, i32(-1), s),
                    pfq=q, pfo=off + C, phase=i32(1),
                    qidx=jnp.where(starting, st["qidx"] + 1, st["qidx"]),
                    step=st["step"],
                )

            @llama.scoped("segment.decode")
            def verify(st):
                live = st["rem"] > 0
                pos, nxt = st["pos"], st["nxt"]
                hist, hstart = st["hist"], st["hstart"]
                if K:
                    # n-gram draft (host-free): match the running bigram
                    # (hist[pos-1], nxt) against the slot's own history;
                    # on a hit the K tokens after the LATEST match are
                    # this tick's draft, else repeat-last (acceptance 0
                    # costs nothing but the already-paid tick)
                    hcols = jnp.arange(max_len + 1)
                    prev = jnp.take_along_axis(
                        hist, jnp.maximum(pos - 1, 0)[:, None],
                        axis=1)[:, 0]
                    hprev = jnp.concatenate(
                        [jnp.zeros((slots, 1), i32), hist[:, :-1]],
                        axis=1)
                    match = ((hist == nxt[:, None])
                             & (hprev == prev[:, None])
                             & (hcols[None] >= hstart[:, None] + 1)
                             & (hcols[None] < pos[:, None]))
                    found = jnp.any(match, axis=1)
                    j = jnp.argmax(jnp.where(match, hcols[None], -1),
                                   axis=1)
                    didx = jnp.minimum(
                        j[:, None] + 1 + jnp.arange(K)[None],
                        jnp.maximum(pos - 1, 0)[:, None])
                    drafts = jnp.take_along_axis(hist, didx, axis=1)
                    drafts = jnp.where(found[:, None], drafts,
                                       nxt[:, None])
                else:
                    drafts = jnp.zeros((slots, 0), i32)
                # ONE verify tick over all K+1 positions per slot: the
                # paged q_len>1 path at each slot's context offset —
                # the same single weight stream a 1-token tick pays
                x = jnp.concatenate([nxt[:, None], drafts], axis=1)
                logits, pool = llama.forward_with_pages(
                    params, x, cfg, st["pool"], st["pt"], pos,
                    live=live, logits_all=True)       # [slots, K+1, V]
                if sampling is None:
                    # greedy: the target argmax chain IS the emitted
                    # stream; drafts only gate how much of it lands
                    e = _greedy(logits)
                    ok = drafts == e[:, :K]
                    rng_new = st["rng"]
                else:
                    # rejection sampling for a deterministic (delta)
                    # draft: accept d_i with prob p_i(d_i); at the
                    # first rejection resample from p_i with d_i
                    # removed; full acceptance earns the bonus token
                    # from the K+1-th distribution — emitted tokens
                    # are distributed exactly as one-at-a-time sampling
                    filt = llama.sample_filter_logits(logits, *sampling)
                    probs = jax.nn.softmax(filt, axis=-1)
                    rng_new, kuse = _split_rows(st["rng"])
                    sub = _subkeys_rows(kuse, 2 * K + 1)
                    pad_d = jnp.concatenate(
                        [drafts, nxt[:, None]], axis=1)   # ii<a never
                    if K:                                 # hits col K
                        u = _uniform_rows(sub[:, :K])
                        pd = jnp.take_along_axis(
                            probs[:, :K], drafts[..., None],
                            axis=-1)[..., 0]
                        ok = u < pd
                        onehot = jax.nn.one_hot(
                            drafts, filt.shape[-1], dtype=jnp.bool_)
                        res = _categorical_rows(
                            jnp.where(onehot, -jnp.inf, filt[:, :K]),
                            sub[:, K:2 * K])
                    else:
                        ok = jnp.zeros((slots, 0), jnp.bool_)
                        res = jnp.zeros((slots, 0), i32)
                    bonus = _categorical_rows(filt[:, K], sub[:, 2 * K])
                    a0 = jnp.cumprod(ok.astype(i32), axis=1).sum(axis=1)
                    res_all = jnp.concatenate([res, bonus[:, None]],
                                              axis=1)
                    ii = jnp.arange(K + 1)
                    e = jnp.where(ii[None] < a0[:, None], pad_d, res_all)
                a = jnp.cumprod(ok.astype(i32), axis=1).sum(axis=1)
                m = jnp.minimum(a + 1, st["rem"])  # never emit past owed
                m = jnp.where(live, m, 0)
                if eos is not None:
                    ii2 = jnp.arange(K + 1)
                    eosm = (e == eos) & (ii2[None] < m[:, None])
                    has_eos = jnp.any(eosm, axis=1)
                    m = jnp.where(
                        has_eos,
                        jnp.argmax(eosm, axis=1).astype(i32) + 1, m)
                mi = jnp.maximum(m - 1, 0)
                nxt_new = jnp.where(
                    m > 0,
                    jnp.take_along_axis(e, mi[:, None], axis=1)[:, 0],
                    nxt)
                rem_new = st["rem"] - m
                if eos is not None:
                    rem_new = jnp.where(live & has_eos, 0, rem_new)
                # history: the K+1 INPUT tokens now page-resident at
                # pos..pos+K; entries past pos+m are stale, invisible
                # to the draft scan (< pos) and overwritten before the
                # next tick's attention can see them
                hwidx = jnp.minimum(
                    pos[:, None] + jnp.arange(K + 1)[None], max_len)
                hist_new = hist.at[sl[:, None], hwidx].set(x)
                return dict(
                    pool=pool, pt=st["pt"],
                    pos=pos + m, nxt=nxt_new, rem=rem_new,
                    hist=hist_new, hstart=hstart, rng=rng_new,
                    out=st["out"].at[st["step"]].set(e),
                    acc=st["acc"].at[st["step"]].set(m),
                    aq=st["aq"], aslot=st["aslot"],
                    pf=st["pf"], pfq=st["pfq"], pfo=st["pfo"],
                    phase=i32(0),
                    qidx=st["qidx"], step=st["step"],
                )

            def body(st):
                live_any = jnp.any(st["rem"] > 0)
                pf_active = st["pf"] >= 0
                can_start = ((~pf_active) & jnp.any(st["rem"] == 0)
                             & _startable(st))
                do_chunk = ((pf_active | can_start)
                            & ((st["phase"] == 0) | ~live_any))
                st = _one_of(do_chunk, chunk, verify, st)
                st["step"] = st["step"] + 1
                return st

            st = jax.lax.while_loop(cond, body, st)
            return (st["pool"], st["pt"], st["pos"], st["nxt"], st["rem"],
                    st["hist"], st["hstart"], st["rng"],
                    st["out"], st["aq"], st["aslot"], st["acc"],
                    st["step"], st["qidx"])

        return segment

    def _dispatch_segment_paged(self, max_steps: int, prefix_cache,
                                n_pad: int, now: float) -> _PendingSegment:
        """``dispatch_segment``'s body: pick FCFS gated on PAGES FREE
        (admission control is memory admission — the request's page
        span is known exactly at admission since generation length is
        fixed), reserve page lists host-side, launch ONE fused paged
        segment, host-replay the shared event log with page-table
        bookkeeping hooks. Same single audited sync per segment."""
        pgr = self.pager
        psz = self.page_size
        with self._phase("pick"):
            picked: List[Request] = []
            fulls: List[np.ndarray] = []      # admission (resume) views
            req_pages: List[List[int]] = []
            pre_lens_l: List[int] = []
            tables: List[np.ndarray] = []
            deferred = 0
            while self._queue and len(picked) < n_pad:
                r = self._queue[0]
                sp_info = (self._sp_inflight.get(r.rid)
                           if self.seq_parallel else None)
                if sp_info is not None:
                    # r23 long-prefill continuation: the pages were
                    # reserved at first admission and the first
                    # ``resident`` rows already landed in the pool — reuse
                    # both (zero allocator / prefix-cache / meter traffic;
                    # the reservation is HELD across the spanned segments)
                    fp, _ = r.resume_view()
                    row = np.zeros((pgr.max_pages,), np.int32)
                    row[:len(sp_info["pages"])] = sp_info["pages"]
                    self._queue.pop(0)
                    if not r.admit_time:
                        r.admit_time = now
                    picked.append(r)
                    fulls.append(fp)
                    req_pages.append(sp_info["pages"])
                    pre_lens_l.append(sp_info["resident"])
                    tables.append(row)
                    continue
                fp, remaining = r.resume_view()
                rows = len(fp) + remaining - 1
                total = pgr.pages_needed(rows)
                hit_pages: List[int] = []
                hit_len = 0
                restored = 0
                if prefix_cache is not None:
                    m = prefix_cache.match(fp)
                    if m is not None and getattr(m, "tier", "hbm") != "host":
                        hit_pages, hit_len = list(m.pages), m.length
                    elif m is not None:
                        # r19 tiered KV (ISSUE 14): host-tier hit —
                        # restore-on-hit is reserve + async staged upload +
                        # the normal ref-bump share. Restoring consumes free
                        # pages itself, so the WHOLE request span must fit;
                        # the pressure valve may spill colder entries first.
                        # A failed restore degrades to a plain miss (full
                        # prefill) — never an error.
                        if total > pgr.pages_free:
                            prefix_cache.evict_until(total)
                        if total <= pgr.pages_free:
                            rp = prefix_cache.restore(m.key, m.length)
                            if rp:
                                hit_pages, hit_len = rp, len(rp) * psz
                                restored = len(rp)
                need_new = total - len(hit_pages)
                if need_new > pgr.pages_free:
                    if prefix_cache is not None:
                        # page-pressure valve: cached history yields LRU
                        # pages before live traffic defers; eviction may
                        # have freed the very pages the hit named, so trim
                        # the hit at the first no-longer-referenced page
                        prefix_cache.evict_until(need_new)
                        k = 0
                        while (k < len(hit_pages)
                               and pgr.allocator.ref(hit_pages[k]) > 0):
                            k += 1
                        hit_pages, hit_len = hit_pages[:k], k * psz
                        need_new = total - k
                    if need_new > pgr.pages_free:
                        # FCFS: the queue head blocks, everything waits —
                        # pages free as live requests retire
                        deferred = len(self._queue)
                        if (not picked
                                and all(not p for p in pgr.slot_pages)):
                            # nothing live to free pages and nothing being
                            # admitted: the pool is pinned by references
                            # outside this engine's control — fail loudly
                            # rather than spin the serve loop forever
                            raise RuntimeError(
                                f"page pool starved: request needs "
                                f"{need_new} pages, {pgr.pages_free} free, "
                                f"no live slots to retire (pages held by an "
                                f"external prefix cache or fork?)")
                        break
                pages, row = pgr.reserve(rows, hit_pages)
                self._queue.pop(0)
                r.prefix_hit_len = hit_len
                r.admit_time = now
                r._meter_reserve(len(pages), len(pages) - len(hit_pages))
                if restored:
                    # r19: bill the promotion to the request it admitted
                    r.tier_pages += restored
                    r.tier_bytes += (restored
                                     * prefix_cache.host_tier.page_bytes())
                picked.append(r)
                fulls.append(fp)
                req_pages.append(pages)
                pre_lens_l.append(hit_len)
                tables.append(row)
            if deferred:
                self.page_backpressure_events += 1
                _metrics.counter("serving.backpressure_pages").inc()
                _flight.record("backpressure", reason="pages",
                               deferred=deferred, pages_free=pgr.pages_free)
            n = len(picked)

            spec = bool(self.speculative or self.sampling)
            # suffix width: the largest bucket when nothing was reused,
            # the suffix bucket when prefix hits shorten the prefill.
            # SPEC segments always pin to
            # the largest bucket: the ("sseg", n_pad, K, steps) key family
            # deliberately carries no width, so prefix hits stay page DATA
            # and add zero program shapes.
            # r23: the segment runs the sequence-parallel slab family when
            # any picked request is a long prefill — a fresh suffix past
            # the largest regular bucket, or a continuation mid-flight.
            # Everything else (sp engines included) rides pseg/cseg
            # unchanged: sp=1 or short-only traffic degenerates exactly.
            sp_engaged = [j for j in range(n) if self.seq_parallel and (
                picked[j].rid in self._sp_inflight
                or len(fulls[j]) - pre_lens_l[j] > self.buckets[-1])]
            sp_mode = bool(sp_engaged)

            chunk_marker = None
            if sp_mode:
                # slab width: the largest declared prefill chunk per shard;
                # admit window: the largest engaged rung, slab-rounded.
                # Rungs shrink as continuations land rows, and every rung
                # at or below the first admission's is enumerated.
                C = self.prefill_chunks[-1]
                Cs = self.seq_parallel * C
                lb = max(self._long_rung(max(1, len(fulls[j]) - pre_lens_l[j]))
                         for j in sp_engaged)
                s_max = -(-lb // Cs) * Cs
                chunk_marker = n_pad + 1
            elif spec or prefix_cache is None or not any(pre_lens_l):
                s_max = self.buckets[-1]
            else:
                suf_max = max((len(fulls[j]) - pre_lens_l[j]
                               for j in range(n)), default=1)
                s_max = self._bucket_for(suf_max)

            if self.chunked and not sp_mode:
                C = self._prefill_chunk_for(s_max)
                s_max = -(-s_max // C) * C        # chunk-aligned admit window
                worst = 2 * (s_max // C)
                if max_steps < worst:
                    raise ValueError(
                        f"seg_steps {max_steps} cannot fit one chunked "
                        f"prefill ({s_max // C} chunks x {C} interleaved = "
                        f"{worst} steps) — raise seg_steps or shrink the "
                        f"prompt buckets / chunk ladder")
                chunk_marker = n_pad + 1
            if spec:
                # the spec program admits through the chunk branch (one
                # full-width chunk when unchunked), so non-final chunk
                # steps log the same marker and a start needs 2*chunks of
                # step budget
                chunk_marker = n_pad + 1
                if max_steps < 2:
                    raise ValueError("speculative segments need seg_steps "
                                     ">= 2 (a prefill start reserves one "
                                     "chunk + one verify step)")

        with self._phase("inputs"):
            prompts = np.zeros((n_pad, s_max), np.int32)
            lens = np.ones((n_pad,), np.int32)
            gens = np.zeros((n_pad,), np.int32)   # gen 0 -> never admitted
            pre_lens = np.zeros((n_pad,), np.int32)
            req_tables = np.zeros((n_pad, pgr.max_pages), np.int32)
            seeds = np.zeros((n_pad,), np.int32)
            for j, r in enumerate(picked):
                suf = fulls[j][pre_lens_l[j]:]
                prompts[j, :len(suf)] = suf
                lens[j] = len(suf)
                gens[j] = r.max_new_tokens - len(r.tokens)
                pre_lens[j] = pre_lens_l[j]
                req_tables[j] = tables[j]
                # the slot's RNG stream derives from (request seed, tokens
                # already delivered): a fresh serve replays identically, a
                # preempt/failover resume continues from a deterministic
                # fold instead of re-playing consumed draws
                seeds[j] = (r.seed + 0x9E3779B1 * len(r.tokens)) & 0x7FFFFFFF

            # the host -> device copies (one small program each: the
            # jit_convert_element_type programs a device trace shows
            # between two segments), a phase of their own inside inputs
            with self._phase("put"):
                dev_in = [jnp.asarray(a) for a in
                          (prompts, lens, gens, pre_lens, req_tables)]
                if spec:
                    dev_in.append(jnp.asarray(seeds))
                dev_in.append(jnp.int32(n))

        if spec:
            with self._phase("launch") as launch, _mesh_scope(self.mesh):
                rng = (self._rng if self._rng is not None
                       else jnp.zeros((self.slots, 2), jnp.uint32))
                out = self._spec_segment_prog(n_pad, max_steps)(
                    self.params, pgr.pool, pgr.page_table, self._pos,
                    self._nxt, self._rem, self._hist, self._hstart, rng,
                    *dev_in)
            self._close_gap(launch.t1)
            pgr.pool, pgr.page_table = out[0], out[1]
            self._pos, self._nxt, self._rem = out[2:5]
            self._hist, self._hstart = out[5], out[6]
            if self._rng is not None:
                self._rng = out[7]
            return _PendingSegment(picked=picked, n=n,
                                   now=now, prefix_cache=prefix_cache,
                                   dev=out[8:], pre_lens=pre_lens_l,
                                   req_pages=req_pages,
                                   full_prompts=fulls,
                                   chunk_marker=chunk_marker, spec=True)

        with self._phase("launch") as launch, _mesh_scope(self.mesh):
            prog = (self._sp_segment_prog(n_pad, s_max, C, max_steps)
                    if sp_mode
                    else self._chunked_segment_prog(n_pad, s_max, C,
                                                    max_steps)
                    if self.chunked
                    else self._paged_segment_prog(n_pad, s_max, max_steps))
            out = prog(
                self.params, pgr.pool, pgr.page_table, self._pos, self._nxt,
                self._rem, *dev_in)
        self._close_gap(launch.t1)
        pgr.pool, pgr.page_table = out[0], out[1]
        self._pos, self._nxt, self._rem = out[2:5]
        return _PendingSegment(picked=picked, n=n, now=now,
                               prefix_cache=prefix_cache, dev=out[5:],
                               pre_lens=pre_lens_l, req_pages=req_pages,
                               full_prompts=fulls,
                               chunk_marker=chunk_marker,
                               digest=self.quality_digest, sp=sp_mode,
                               counters=hasattr(self.model,
                                                "SEGMENT_COUNTERS"))

    def _finish_segment_paged(self, p: _PendingSegment) -> dict:
        picked, n, prefix_cache = p.picked, p.n, p.prefix_cache
        pre_lens_l, req_pages = p.pre_lens, p.req_pages
        pgr = self.pager
        psz = self.page_size
        # THE per-segment sync: the one place the serve loop is allowed
        # to block on the device (audited — see analysis.syncs; the
        # budget pins it to exactly one per segment — the spec program's
        # acceptance counts ride the same fetch).
        # r19 tiered KV (ISSUE 14): queued host-tier stage gathers fold
        # into the SAME single device_get — the D2H spill staging costs
        # zero additional sync events by construction.
        acc = spec_stats = dig = counts = None
        tier = getattr(prefix_cache, "host_tier", None) \
            if prefix_cache is not None else None
        staged = tier.take_pending() if tier is not None else []
        with self._phase("fetch", p.seg) as fetch, \
                allowed_sync("serving.segment_event_fetch"):
            payload = (p.dev if not staged
                       else (p.dev, [s[2:] for s in staged]))
            got = jax.device_get(payload)
            dev = got if not staged else got[0]
            if p.spec:
                toks, aq, aslot, acc, steps, qadm = dev
            elif p.digest:
                # r17: digest columns ride the SAME single fetch — the
                # per-segment sync count is unchanged (audited)
                toks, aq, aslot, dlg, dti, dtv, steps, qadm = dev
                dig = (dlg, dti, dtv)
            elif p.sp:
                # r23: the prefill-progress triple rides the SAME
                # single fetch — a long prefill the step budget cut
                # mid-flight resumes next dispatch at row pfo
                toks, aq, aslot, sp_pf, sp_pfq, sp_pfo, steps, qadm = dev
            elif p.counters:
                # PR 29: the model's per-step counters, same fetch
                toks, aq, aslot, counts, steps, qadm = dev
            else:
                toks, aq, aslot, steps, qadm = dev
        self._open_gap(fetch.t1)
        if staged:
            tier.complete(staged, got[1])
        steps, qadm = int(steps), int(qadm)
        self.last_run_ticks += steps
        self.last_run_chunks += 1
        if p.spec:
            spec_stats = {"proposed": 0, "accepted": 0, "emitted": 0,
                          "verify_steps": 0, "slot_ticks": 0}

        with self._phase("replay", p.seg):
            # page bookkeeping rides the SHARED replay via hooks; retired
            # slots' releases are DEFERRED past the prefix-cache inserts so
            # harvest-by-reference can still retain a finished request's
            # prompt pages
            pending_frees: List[List[int]] = []

            def on_admit(q, s):
                pgr.install(s, req_pages[q])

            def on_retire(r, s):
                r._meter_release()
                pending_frees.append(pgr.slot_pages[s])
                pgr.slot_pages[s] = []

            (admitted, first_tokens, first_steps, finished, new_tokens,
             eos_stops) = self._replay_segment(
                 picked, toks, aq, aslot, steps, n, on_admit, on_retire,
                 chunk_marker=p.chunk_marker, acc=acc,
                 spec_stats=spec_stats, dig=dig)
            if p.chunk_marker is not None:
                chunk_steps = int(np.sum(np.asarray(aq[:steps])
                                         >= p.chunk_marker))
                if chunk_steps:
                    _metrics.counter("serving.prefill_chunks").inc(chunk_steps)
            if p.sp:
                # completed admissions retire their carry-over entries; a
                # prefill the budget cut mid-flight re-registers below
                for r in picked:
                    self._sp_inflight.pop(r.rid, None)
            if p.sp and int(sp_pf) >= 0:
                # r23 multi-segment prefill: keep the mid-flight request's
                # reservation AND meter open (its pages hold landed KV
                # rows), record the resident row count, and requeue it at
                # the head so the next dispatch continues the slab stream;
                # everything behind it releases and requeues as usual
                j = int(sp_pfq)
                assert qadm == j + 1, (
                    f"sp prefill progress desynced: pf row {j}, qadm {qadm}")
                self._sp_inflight[picked[j].rid] = {
                    "pages": req_pages[j],
                    "resident": pre_lens_l[j] + int(sp_pfo)}
                for k in range(qadm, n):
                    picked[k].admit_time = 0.0
                    picked[k]._meter_release()
                    pgr.release_pages(req_pages[k])
                _flight.record("sp_carryover", rid=picked[j].rid,
                               resident=pre_lens_l[j] + int(sp_pfo),
                               total=len(p.full_prompts[j]))
                self._queue[:0] = picked[j:]
            elif qadm < n:
                # step budget ran out before every picked request found a
                # slot: release the reservations and requeue FCFS
                for j in range(qadm, n):
                    picked[j].admit_time = 0.0
                    picked[j]._meter_release()
                    pgr.release_pages(req_pages[j])
                self._queue[:0] = picked[qadm:]

            # prefix-cache population: harvest BY REFERENCE — retain the
            # admitted request's prompt-spanning pages (zero row copies; the
            # cache and the slot share physical pages from this moment)
            if prefix_cache is not None:
                last_admit = {}                # slot -> its latest admit event
                for st in range(steps):
                    q = int(aq[st])
                    if q < n:
                        last_admit[int(aslot[st])] = q
                for s, q in last_admit.items():
                    fp = p.full_prompts[q]     # the span actually prefilled
                    plen_b = prefix_cache.round_down(len(fp))
                    if plen_b > pre_lens_l[q]:
                        prefix_cache.insert(fp[:plen_b],
                                            req_pages[q][:plen_b // psz])
            for pages in pending_frees:
                pgr.release_pages(pages)
        with self._phase("telemetry", p.seg):
            pgr._gauges()

            if spec_stats is not None:
                self._spec_telemetry(spec_stats)
            self._segment_telemetry(steps, admitted, finished, eos_stops,
                                    new_tokens, max(0, n - qadm))
            if counts is not None:
                counts = self._count_telemetry(counts[:steps])
        out = {"steps": steps, "admitted": admitted,
               "first_tokens": first_tokens,
               "first_token_steps": first_steps, "finished": finished,
               "tokens": new_tokens}
        if spec_stats is not None:
            out["spec"] = spec_stats
        if counts is not None:
            out["counters"] = counts
        return out

    def collect_finished(self) -> Dict[int, List[int]]:
        """Drain the finished list (segment mode's result channel),
        truncating at max_new_tokens / first EOS like run()."""
        done = {}
        for r in self._finished:
            toks = r.tokens[:r.max_new_tokens]
            if self.eos is not None and self.eos in toks:
                toks = toks[:toks.index(self.eos) + 1]
            r.tokens = toks
            if r.digests is not None:
                r.digests = r.digests[:len(toks)]  # stay index-aligned
            done[r.rid] = toks
            self.last_latencies[r.rid] = r.finish_time - r.submit_time
        self._finished = []
        return done

    # --- the engine loop --------------------------------------------------
    def run(self) -> Dict[int, List[int]]:
        """Drain the queue: continuous batching until every request is
        served. Returns rid -> generated tokens (greedy, incl. the first
        token sampled at prefill). The online product's loop without a
        scheduler: segments of ``4 * chunk`` steps back to back, greedy
        in-program admission, one dispatch + one fetch per segment."""
        self.last_run_ticks = 0
        self.last_run_chunks = 0
        self.last_latencies = {}
        while self._queue or any(r is not None for r in self._active):
            self.run_segment(4 * self.chunk)
        return self.collect_finished()
