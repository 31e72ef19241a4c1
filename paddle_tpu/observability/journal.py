"""Deterministic serving journal — the black-box decision recorder
(ISSUE 11 tentpole, part a).

The flight recorder (r10) keeps the last 2048 events; an incident at
4x overload produces tens of thousands. This module is the LOSSLESS
tier: an append-only, schema-versioned JSONL stream of every serving
decision plus the inputs behind it, written per rank with monotonic
sequence numbers, size-rotated, and merged across replicas the way
``metrics.merge_log_dir`` merges snapshots. Three record classes:

* **header** (``kind="header"``) — one per recorded serve: schema
  version, driver topology (online / slo scheduler or fleet router,
  with every constructor knob), per-engine geometry + seeds, the FULL
  arrival trace, prefix-cache/fault-injector state, and the mutable
  scheduler state (service-rate EWMAs, next rids) a replay must seed.
  The header is sufficient to REBUILD the serve (see
  :mod:`~paddle_tpu.observability.replay`).
* **clock** (``kind="clock"``) — every decision-relevant host clock
  read (``journal.now()``). Serving decisions are functions of (seeded
  trace, engine state, clock reads); recording the reads and feeding
  them back during replay makes the whole decision stream bit-exact
  REGARDLESS of replay-machine timing — compiles, container load and
  scheduler jitter cannot perturb a replayed incident.
* **decision records** — the superset of flight events (every
  ``flight.record`` forwards here through ``flight.LISTENERS``) plus
  enriched records carrying the inputs behind each choice: fleet
  dispatch candidate rankings, shed deadline arithmetic, preempt
  victim selection, fault-injector draws, per-request admit /
  first-token / finish (with the full token list — the token-identity
  ground truth).

The zero-extra-sync contract holds by construction: every recorded
value is a host mirror the serve loop already computed from the one
audited per-segment event fetch — the journal never touches a device
value, and ``python -m paddle_tpu.analysis --gate --journal on`` must
budget bit-identically to ``--journal off``
(tests/test_journal.py pins it, TestTelemetryAudit-style).

Record shape (one JSON object per line)::

    {"v": 1, "gseq": 17, "rank": 0, "seq": 17, "t": 1699...,
     "kind": "dispatch", ...decision fields...}

``seq`` is monotonic PER RANK (a gap inside one rank file means loss —
there is none by construction; rotation keeps every part). ``gseq`` is
the process-global total order the in-process fleet join sorts by;
cross-process merges fall back to ``(t, rank, seq)``.

Schema versioning rule: adding a field or a kind is compatible (readers
ignore unknown keys); renaming/removing a field or changing a field's
meaning bumps ``SCHEMA_VERSION`` and the reader refuses newer-versioned
files with a clear error instead of misparsing them.
"""

from __future__ import annotations

import contextlib
import collections
import dataclasses
import glob
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["SCHEMA_VERSION", "DECISION_KINDS", "Journal", "JournalError",
           "install", "uninstall", "attach", "active", "record", "now",
           "sleep", "rank_scope", "shadow_scope", "feed_clock",
           "read_journal", "merge_journal_dir", "sections",
           "request_journey", "journey_summary", "describe_engine",
           "describe_config", "describe_arrivals",
           "describe_prefix_cache", "describe_envelope"]

SCHEMA_VERSION = 1

# The kinds a replay must reproduce verbatim — the diffable decision
# stream. Everything else in the journal (cold_start seconds, recompile
# events, merge_skipped, slo_alert from optionally-attached monitors,
# process_exit) is context that may legitimately differ between the
# recording machine and a replay, so it is journaled losslessly but not
# judged. ``clock`` IS included: the replay echoes every fed value, so a
# mutated or mis-aligned feed surfaces as the first divergence instead
# of corrupting everything after it silently.
DECISION_KINDS = frozenset({
    "clock", "arrival", "dispatch", "fleet_dispatch",
    "admit", "first_token", "finish",
    "segment", "backpressure", "displaced",
    "shed", "shed_decision", "preempt", "preempt_decision",
    "spec_accept", "fault", "probe",
    "replica_dead", "replica_suspect", "replica_recovered",
    "failover_requeue", "prefix_hit", "prefix_evict",
    # r19 tiered KV (ISSUE 14): tier movement is deterministic host
    # bookkeeping over the event stream (stage completion is pinned to
    # segment boundaries), so spill/restore/import decisions and the
    # fleet's migration choices replay bit-exactly and are DIFFED
    "tier_transfer", "tier_migrate",
    # r22 disaggregated serving (ISSUE 17): the prefill->decode page-set
    # handoff is a routing DECISION (which decode replica, how many
    # pages, how many bytes) made from journaled state only, so the
    # cross-pool journey replays bit-exactly and the handoff is DIFFED
    "handoff",
    # r25 elastic autoscaling (ISSUE 20): every scale decision carries
    # its full input vector (burn rates, capacity level, queue depths,
    # per-replica pages_free/health/lifecycle, chip-fit verdict) and is
    # derived from journaled state + the fed clock only, so the whole
    # 1x->4x->1x elastic episode replays bit-exactly and is DIFFED
    "scale_decision",
})


class JournalError(RuntimeError):
    """Journal misuse or a replay whose control flow left the recorded
    path (e.g. the clock feed exhausted — the replayed serve took a
    branch the recorded one did not)."""


def _jsonable(x):
    """Host-data sanitiser: numpy scalars/arrays become plain ints /
    lists so the JSONL stays dependency-free to read. Device arrays are
    REFUSED — a journal write must never be the thing that syncs."""
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, np.ndarray):
        return [_jsonable(v) for v in x.tolist()]
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if hasattr(x, "device_buffer") or type(x).__module__.startswith("jax"):
        raise TypeError(
            f"journal refuses device value {type(x).__name__} — record "
            f"host mirrors only (the zero-extra-sync contract)")
    return x


class Journal:
    """Append-only JSONL decision journal.

    ``log_dir=None`` keeps records in memory only (the replay's scratch
    journal); with a directory, rank ``i``'s records append to
    ``journal_rank<i>.jsonl`` and rotate to
    ``journal_rank<i>.jsonl.<part>`` once ``max_bytes`` is exceeded —
    append-only, nothing is ever overwritten or evicted. A bounded
    in-memory tail (``tail()``) feeds the live ``/journal`` ops
    endpoint without touching the files."""

    def __init__(self, log_dir: Optional[str] = None, rank: int = 0,
                 max_bytes: int = 8 * 1024 * 1024,
                 tail_events: int = 4096):
        self.dir = log_dir
        if log_dir is not None:
            os.makedirs(log_dir, exist_ok=True)
        self.max_bytes = int(max_bytes)
        self._rank_stack: List[int] = [int(rank)]
        # rank -> [file handle, bytes written, next part number]
        self._files: Dict[int, list] = {}
        self._seqs: Dict[int, int] = {}
        self._gseq = 0
        self._lock = threading.Lock()       # exporter thread reads tail
        self._tail = collections.deque(maxlen=int(tail_events))
        self._memory: Optional[List[dict]] = ([] if log_dir is None
                                              else None)
        self.total_records = 0
        self.serves = 0                     # header records written
        self.header: Optional[dict] = None  # FIRST serve header seen
        self.params_info: Optional[dict] = None

    # --- write path -------------------------------------------------------
    def _rank_path(self, rank: int) -> str:
        return os.path.join(self.dir, f"journal_rank{rank}.jsonl")

    def _file(self, rank: int) -> list:
        ent = self._files.get(rank)
        if ent is None:
            ent = [open(self._rank_path(rank), "a"), 0, 0]
            ent[1] = ent[0].tell()
            self._files[rank] = ent
        return ent

    def _rotate(self, rank: int, ent: list) -> None:
        """Size rotation: the active file closes and renames to its
        part number; the next record opens a fresh active file. Every
        part is kept — rotation bounds FILE size (tailing, shipping),
        never history."""
        ent[0].close()
        os.replace(self._rank_path(rank),
                   self._rank_path(rank) + f".{ent[2]:03d}")
        ent[2] += 1
        ent[0] = open(self._rank_path(rank), "a")
        ent[1] = 0

    def record(self, kind: str, rank: Optional[int] = None, **data) -> dict:
        with self._lock:
            r = self._rank_stack[-1] if rank is None else int(rank)
            self._gseq += 1
            seq = self._seqs.get(r, 0) + 1
            self._seqs[r] = seq
            rec = {"v": SCHEMA_VERSION, "gseq": self._gseq, "rank": r,
                   "seq": seq, "t": time.time(), "kind": kind,
                   **{k: _jsonable(v) for k, v in data.items()}}
            if _SHADOW[0]:
                # r17 (ISSUE 12): records written from the SHADOW path
                # (mirrored segments, quality compares, drain clock
                # reads) are journaled losslessly but marked — the
                # replay diff excludes them, because the primary
                # decision stream must certify identical whether or
                # not a shadow happened to be attached (the shadow is
                # an observer, never a decider)
                rec["shadow"] = True
            self.total_records += 1
            self._tail.append(rec)
            if self._memory is not None:
                self._memory.append(rec)
            else:
                ent = self._file(r)
                line = json.dumps(rec, separators=(",", ":")) + "\n"
                ent[0].write(line)
                ent[1] += len(line)
                if ent[1] >= self.max_bytes:
                    self._rotate(r, ent)
            return rec

    def begin_serve(self, header: dict) -> None:
        """Record one serve's header — the replay contract's root. A
        journal may hold several serves (a ``warm=True`` pass records
        its own section); the reader splits on headers and the replay
        defaults to the LAST section (the measured pass)."""
        header = dict(header)
        header.setdefault("schema", SCHEMA_VERSION)
        if self.params_info is not None:
            header.setdefault("params", self.params_info)
        self.serves += 1
        rec = self.record("header", header=header)
        if self.header is None:
            self.header = rec["header"]

    @contextlib.contextmanager
    def rank_scope(self, rank: int):
        """Route records inside the scope to ``rank``'s file — the
        fleet wraps each replica's dispatch/finish in this, mirroring
        ``metrics.scoped_registry``."""
        self._rank_stack.append(int(rank))
        try:
            yield self
        finally:
            self._rank_stack.pop()

    def flush(self) -> None:
        with self._lock:
            for ent in self._files.values():
                ent[0].flush()

    def close(self) -> None:
        with self._lock:
            for ent in self._files.values():
                ent[0].close()
            self._files.clear()

    # --- read path --------------------------------------------------------
    def tail(self, n: int = 64, kind: Optional[str] = None,
             rid: Optional[int] = None) -> List[dict]:
        """Newest-last view of the bounded in-memory tail, optionally
        filtered — the live ``/journal?n=&kind=&rid=`` payload."""
        with self._lock:
            evs = list(self._tail)
        if kind is not None:
            evs = [e for e in evs if e["kind"] == kind]
        if rid is not None:
            evs = [e for e in evs if e.get("rid") == rid]
        return evs[-max(1, int(n)):]

    def records(self) -> List[dict]:
        """The full record stream: memory journals return their list;
        file-backed journals flush and re-read their directory (the
        files are the source of truth — the tail is bounded)."""
        if self._memory is not None:
            return list(self._memory)
        self.flush()
        return read_journal(self.dir)["records"]

    def request_journey(self, rid: int) -> dict:
        return request_journey(self.records(), rid)


# --- process-wide attachment (mirrors flight.FLIGHT / SEGMENT_HOOKS) ------

_ACTIVE: List[Optional[Journal]] = [None]
_FEED: List[Optional["_ClockFeed"]] = [None]


def _flight_listener(kind: str, data: dict) -> None:
    j = _ACTIVE[0]
    if j is not None:
        j.record(kind, **data)


def install(journal: Journal) -> None:
    """Make ``journal`` the process-wide active journal: explicit
    ``journal.record`` calls land in it AND every flight event forwards
    into it (the lossless-superset contract)."""
    from . import flight as _flight

    if _ACTIVE[0] is not None:
        raise JournalError("a journal is already installed")
    _ACTIVE[0] = journal
    _flight.LISTENERS.append(_flight_listener)


def uninstall(journal: Journal) -> None:
    from . import flight as _flight

    if _ACTIVE[0] is not journal:
        raise JournalError("uninstall of a journal that is not installed")
    _ACTIVE[0] = None
    if _flight_listener in _flight.LISTENERS:
        _flight.LISTENERS.remove(_flight_listener)


@contextlib.contextmanager
def attach(journal: Journal):
    """Scoped install/uninstall — the benchmark/test idiom::

        with journal.attach(j):
            report = scheduler.serve(trace)
    """
    install(journal)
    try:
        yield journal
    finally:
        uninstall(journal)


def active() -> Optional[Journal]:
    return _ACTIVE[0]


def record(kind: str, **data) -> None:
    """Journal a decision record iff a journal is attached (one list
    read when off — the serve loop's common case)."""
    j = _ACTIVE[0]
    if j is not None:
        j.record(kind, **data)


@contextlib.contextmanager
def rank_scope(rank: int):
    """Route records inside the scope to ``rank``'s journal file when a
    journal is attached; a no-op otherwise (the fleet wraps replica
    work in this unconditionally, mirroring ``scoped_registry``)."""
    j = _ACTIVE[0]
    if j is None:
        yield None
        return
    with j.rank_scope(rank):
        yield j


# r17 (ISSUE 12): depth-counted shadow marker. The fleet router wraps
# ALL shadow-path work (mirror intake, shadow segment dispatch/finish,
# quality compares, the post-serve shadow drain) in this scope so every
# record it produces — including ``clock`` reads — carries
# ``shadow: true``. Replay then diffs the primary decision stream
# alone: a serve with a shadow attached certifies bit-identical to its
# own replay WITHOUT the replay having to rebuild and re-run the
# shadow (the shadow is off the decision path by contract).
_SHADOW = [0]


@contextlib.contextmanager
def shadow_scope():
    """Mark every journal record (and decision-clock read) inside the
    scope as shadow-path — excluded from the replay diff. Re-entrant."""
    _SHADOW[0] += 1
    try:
        yield
    finally:
        _SHADOW[0] -= 1


def in_shadow_scope() -> bool:
    return bool(_SHADOW[0])


# --- the decision clock ----------------------------------------------------

class _ClockFeed:
    """Replays a recorded serve's clock reads in order. Exhaustion
    means the replayed control flow consumed MORE reads than the
    recording — a divergence, reported as such rather than papered
    over with wall time."""

    def __init__(self, values: Sequence[float]):
        self._vals = list(values)
        self._i = 0

    def next(self) -> float:
        if self._i >= len(self._vals):
            raise JournalError(
                f"clock feed exhausted after {self._i} reads — the "
                f"replayed serve's control flow diverged from the "
                f"recorded one")
        v = self._vals[self._i]
        self._i += 1
        return v

    @property
    def remaining(self) -> int:
        return len(self._vals) - self._i


def now() -> float:
    """THE decision clock. Every wall-clock read that can influence a
    serving decision (arrival due-ness, deadline shedding, segment
    stamps, probe backoff) routes through here instead of
    ``time.perf_counter()``:

    * no journal, no feed (the default): a plain ``perf_counter`` —
      two list reads of overhead;
    * journal attached (recording): the read is journaled as a
      ``clock`` record, making the serve's entire time base part of
      the black box;
    * clock feed active (replaying): the RECORDED value is returned
      (and echoed into the replay journal so the streams stay
      index-aligned) — the replayed decisions see the incident's
      clock, not the replay machine's.
    """
    feed = _FEED[0]
    if feed is not None:
        v = feed.next()
    else:
        v = time.perf_counter()
    j = _ACTIVE[0]
    if j is not None:
        j.record("clock", c=v)
    return v


def sleep(seconds: float) -> None:
    """Idle-wait that a replay skips: recorded serves really sleep
    (pacing the arrival clock); a replay's time base is the feed, so
    sleeping would only slow the diff down."""
    if _FEED[0] is None:
        time.sleep(seconds)


@contextlib.contextmanager
def feed_clock(values: Sequence[float]):
    """Scope a recorded clock feed (replay mode) — see ``now()``."""
    if _FEED[0] is not None:
        raise JournalError("a clock feed is already active")
    feed = _ClockFeed(values)
    _FEED[0] = feed
    try:
        yield feed
    finally:
        _FEED[0] = None


# --- readers / mergers -----------------------------------------------------

def _read_file(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)      # raises -> caller skips the FILE
            v = rec.get("v", 0)
            if v > SCHEMA_VERSION:
                raise JournalError(
                    f"{os.path.basename(path)}:{ln} is schema v{v}; "
                    f"this reader understands <= v{SCHEMA_VERSION}")
            out.append(rec)
    return out


def read_journal(path: str) -> dict:
    """Merge a journal directory (or read one file) into a single
    ordered record stream — the cross-replica join.

    Matches the r14 ``merge_log_dir`` robustness semantics: a
    truncated/corrupt rank file (a replica killed mid-write) is
    SKIPPED AND FLAGGED — counted in ``journal.merge_skipped_files``,
    recorded as a ``journal_merge_skipped`` flight event, and listed
    under ``"skipped_files"`` — rather than aborting the postmortem;
    only when NO file is readable does the merge raise. Records are
    ordered by ``gseq`` (the in-process total order); files from
    distinct processes interleave by ``(t, rank, seq)``.
    """
    from . import flight as _flight
    from . import metrics as _metrics

    if os.path.isfile(path):
        paths = [path]
    else:
        paths = sorted(glob.glob(os.path.join(path, "journal_rank*.jsonl"))
                       + glob.glob(os.path.join(path,
                                                "journal_rank*.jsonl.*")))
        if not paths:
            raise FileNotFoundError(f"no journal_rank*.jsonl under {path}")
    records: List[dict] = []
    skipped: List[str] = []
    for p in paths:
        try:
            records.extend(_read_file(p))
        except (json.JSONDecodeError, UnicodeDecodeError, OSError) as e:
            skipped.append(os.path.basename(p))
            _metrics.counter("journal.merge_skipped_files",
                             "journal rank files skipped as truncated/"
                             "corrupt").inc()
            _flight.record("journal_merge_skipped",
                           file=os.path.basename(p),
                           error=f"{type(e).__name__}: {e}")
    if not records:
        raise FileNotFoundError(
            f"no readable journal file under {path} "
            f"({len(skipped)} skipped as corrupt)")
    same_proc = len({r.get("gseq") for r in records}) == len(records)
    records.sort(key=(lambda r: r["gseq"]) if same_proc
                 else (lambda r: (r["t"], r["rank"], r["seq"])))
    out = {"records": records,
           "ranks": sorted({r["rank"] for r in records})}
    if skipped:
        out["skipped_files"] = skipped
    return out


def merge_journal_dir(log_dir: str) -> dict:
    """Alias mirroring ``metrics.merge_log_dir`` naming."""
    return read_journal(log_dir)


def sections(records: Sequence[dict]) -> List[dict]:
    """Split a record stream into serve sections at each header:
    ``[{"header": ..., "records": [...]}, ...]``. Records before the
    first header (gate runs, bare run_segment loops) form a headerless
    leading section only if non-empty."""
    out: List[dict] = []
    cur: Optional[dict] = None
    pre: List[dict] = []
    for r in records:
        if r["kind"] == "header":
            cur = {"header": r["header"], "records": []}
            out.append(cur)
        elif cur is not None:
            cur["records"].append(r)
        else:
            pre.append(r)
    if pre and not out:
        out.append({"header": None, "records": pre})
    return out


# --- request journeys (ISSUE 11 tentpole, part b) --------------------------

def request_journey(records: Sequence[dict], rid: int) -> dict:
    """One request's causal timeline, joined ACROSS replicas: every
    journal record carrying this rid (arrival → dispatch{reason} →
    admit → preempt/shed_decision → failover_requeue → first_token →
    finish), in journal order — which is causal order, because every
    record was written by the single-threaded serve loop at the moment
    it made the decision. The fleet's cross-replica hop is visible as
    the rank changing mid-journey."""
    evs = [r for r in records if r.get("rid") == rid]
    return {"rid": rid, "events": evs, **journey_summary(evs)}


def journey_summary(evs: Sequence[dict]) -> dict:
    kinds = [e["kind"] for e in evs]
    replicas: List[int] = []
    for e in evs:
        tgt = e.get("replica", e.get("dst", e["rank"]))
        if not replicas or replicas[-1] != tgt:
            if e["kind"] in ("dispatch", "fleet_dispatch",
                             "failover_requeue", "admit", "handoff"):
                replicas.append(tgt)
    fin = next((e for e in evs if e["kind"] == "finish"), None)
    shadow = next((e for e in evs if e["kind"] == "shadow_finish"), None)
    return {
        "kinds": kinds,
        "replicas": replicas,
        # r17: the shadow pair — whether this request was mirrored to a
        # shadow engine and, if the pair completed, its diff outcome
        "shadow_pair": any(e["kind"] in ("shadow_mirror", "shadow_finish")
                           for e in evs),
        "shadow_match": (shadow or {}).get("match"),
        "dispatch_reason": next((e.get("reason") for e in evs
                                 if e["kind"] in ("dispatch",
                                                  "fleet_dispatch")), None),
        "admits": kinds.count("admit"),
        "preemptions": kinds.count("preempt"),
        "requeues": kinds.count("failover_requeue"),
        "shed": "shed" in kinds or "shed_decision" in kinds,
        "finished": fin is not None,
        "n_tokens": (fin or {}).get("n_tokens"),
    }


# --- header describe helpers (the replay contract's vocabulary) ------------

def describe_config(cfg) -> dict:
    """LlamaConfig -> JSON (dtype by name; replay maps it back)."""
    d = dataclasses.asdict(cfg)
    d["dtype"] = np.dtype(cfg.dtype).name if not hasattr(
        cfg.dtype, "__name__") else cfg.dtype.__name__
    return d


def describe_engine(engine) -> dict:
    """Everything ``ServingEngine.__init__`` needs to rebuild this
    engine, PLUS the mutable state a mid-session serve starts from
    (next_rid offsets feed sampling seeds and class ordering; the
    acceptance EWMA feeds shed estimates)."""
    samp = None
    if engine.sampling is not None:
        t, k, p = engine.sampling
        samp = {"temperature": t, "top_k": k, "top_p": p}
    mesh = None
    if engine.mesh is not None:
        mesh = {str(k): int(v) for k, v in engine.mesh.shape.items()}
    return {
        "slots": engine.slots, "max_len": engine.max_len,
        "chunk": engine.chunk, "prompt_buckets": list(engine.buckets),
        # "paged" stays in the stored format: replay refuses a journal
        # that recorded the contiguous engine
        "eos_token_id": engine.eos, "paged": True,
        "page_size": engine.page_size,
        "num_pages": engine.pager.num_pages,
        "chunked_prefill": engine.chunked,
        "prefill_chunks": list(engine.prefill_chunks),
        "speculative": engine.speculative, "sampling": samp,
        "sample_seed": engine.sample_seed, "mesh": mesh,
        "quality_digest": getattr(engine, "quality_digest", False),
        "digest_top_k": getattr(engine, "digest_top_k", 4),
        "quant": getattr(engine, "quant", None),
        "seq_parallel": getattr(engine, "seq_parallel", 0),
        "long_buckets": list(getattr(engine, "long_buckets", ())),
        "next_rid": engine._next_rid,
        "spec_accept_ewma": engine.spec_accept_ewma,
    }


def describe_prefix_cache(pc) -> Optional[dict]:
    if pc is None:
        return None
    d = {"kind": "paged", "block": pc.block,
         "capacity_pages": pc.capacity_pages}
    if pc.host_tier is not None:
        # r19: the host spill tier is a routing/admission DECIDER
        # (restore-on-hit, spill-instead-of-drop), so replay must
        # rebuild it at the recorded capacity
        d["host_tier_pages"] = pc.host_tier.capacity_pages
    return d


def describe_envelope(env) -> Optional[dict]:
    """WorkloadEnvelope -> JSON (r22, ISSUE 17): the per-pool envelope
    is a LADDER decider — it fixes which programs each pool AOT-compiles
    — so the disaggregated header records one per pool and replay
    rebuilds the exact same (smaller) per-pool ladders."""
    if env is None:
        return None
    return {"max_prompt": env.max_prompt,
            "max_new_tokens": env.max_new_tokens,
            "seg_steps": list(env.seg_steps),
            "n_pads": list(env.n_pads),
            "resume": env.resume,
            "prefix_block": env.prefix_block}


def describe_arrivals(arrivals) -> List[dict]:
    return [{"at": a.t, "prompt": np.asarray(a.prompt).tolist(),
             "gen": int(a.max_new_tokens),
             "priority": int(getattr(a, "priority", 0)),
             "deadline_s": getattr(a, "deadline_s", None)}
            for a in arrivals]
