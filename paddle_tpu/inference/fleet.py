"""Fleet router (r12 tentpole): N ``ServingEngine`` replicas behind one
``serve(trace)`` entry — the data-parallel axis of multi-chip serving.

One engine saturates one chip; the "millions of users" axis is engines ×
chips (ROADMAP item 2). This module owns the layer in front of a fleet
of replicas — each an independent ``ServingEngine`` (optionally itself
mp-sharded over a tensor-parallel mesh, optionally pinned to its own
device) with its OWN prefix cache and its OWN telemetry registry:

* **Prefix-affinity dispatch.** The router hashes each request's
  block-aligned prompt prefix (the same alignment rule the prefix
  caches match on) to a preferred replica, so requests sharing a prefix
  land on the replica whose ``PrefixCache``/``PagedPrefixCache``
  already holds it — a per-replica cache is only as good as the
  router's ability to route repeat prefixes back to it. Requests too
  short to carry a cacheable prefix skip affinity entirely.
* **Least-loaded fallback + pages-free-aware admission.** When the
  preferred replica's bounded queue is full (or there is no affinity
  key), the request goes to the least-loaded replica (queued + live
  requests, ties to the lowest index — deterministic); replicas
  whose pool can hold the request right now are preferred over ones
  that would defer it on page pressure.
* **Fleet-level backpressure accounting.** Each replica's intake queue
  is bounded; when NO replica can take a due arrival it stays
  client-side and the refusal is billed to the replica that would have
  received it — the fleet counter is definitionally the sum of the
  replica counters (``backpressure_events == sum(replica...)``,
  enforced in tests).
* **Overlapped segment execution.** Each serve-loop turn DISPATCHES one
  fused segment per busy replica (jax async dispatch — no host block),
  then FINISHES them in order: replica i+1's device work overlaps
  replica i's event-fetch wait. The audited sync contract is unchanged
  — every segment still costs exactly one ``allowed_sync`` event fetch
  (``ServingEngine.dispatch_segment``/``finish_segment``).
* **Shadow & canary serving (r17, ISSUE 12).** ``shadow=Shadow(...)``
  mirrors a seeded sampled fraction of admitted requests to a variant
  engine strictly off the primary path (own segments, own sanctioned
  fetch, own registry, journal-marked records) and diffs the pairs
  through a ``QualityMonitor`` (token divergence, logit-error
  budgets); ``canary=CanaryController(...)`` routes a seeded weight of
  traffic to a variant replica, compares per-class latency vs the
  control population, and auto-holds (weight → 0) on a failing
  journaled verdict.
* **Elastic autoscaling (r25, ISSUE 20).** ``autoscaler=Autoscaler(...)``
  attaches the §3t control loop: replicas carry a lifecycle
  (offline/warming/serving/draining) orthogonal to r13 health, standby
  replicas join the dispatch set only after a journaled
  ``scale_decision`` (chip-fit proof + AOT warmup first), and
  scale-downs drain politely — stop admitting, requeue the queue to
  survivors, migrate hot prefixes through the host-tier seam, finish
  live slots in place. See ``inference/autoscaler.py``.
* **Rank-tagged telemetry.** Replica i's segment work records into its
  own ``metrics.Registry`` (``scoped_registry``), exactly as if it were
  launcher rank i; ``merged_telemetry()`` writes one
  ``telemetry_rank<i>.json`` per replica and reduces them with the
  EXISTING ``merge_log_dir`` machinery — one fleet report, counters
  summed, gauges kept per-rank. Fleet-level routing metrics
  (``fleet.dispatches.{affinity,least_loaded}``,
  ``fleet.backpressure_events``, ``fleet.replica_queue_depth``) land in
  the process registry / the replica registries respectively, and every
  dispatch decision leaves a ``fleet_dispatch`` flight event.

Determinism: routing depends only on the affinity hash (crc32 — stable
across processes, unlike ``hash()``) and replica queue/live counts,
which evolve deterministically with the event stream. A burst trace
(every arrival due at t=0) therefore yields an identical per-replica
assignment and identical tokens run-to-run (tested); under real clocked
arrivals the assignment may shift with timing, but greedy decode makes
per-request TOKENS independent of placement either way.
"""

from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..observability import flight as _flight
from ..observability import journal as _journal
from ..observability import metrics as _metrics
from ..observability import quality as _quality
from ..observability.metrics import percentile as _pctl
from .prefix_cache import _common_prefix, make_prefix_cache
from .scheduler import Arrival
from .serving import Request, ServingEngine

__all__ = ["FleetRouter", "FleetReport", "Shadow", "CacheDirectory",
           "build_fleet", "FaultInjector", "ReplicaCrash", "ReplicaHang"]


# ---------------------------------------------------------------------------
# fleet-global prefix-cache directory (r19 tentpole, ISSUE 14 part b):
# crc32 affinity routed requests to a replica that MIGHT hold the prefix;
# the directory routes them to the replica that DOES
# ---------------------------------------------------------------------------


class CacheDirectory:
    """prefix -> {replica: tier, pages, last_touch}, maintained from the
    per-replica ``PagedPrefixCache`` listener hooks (insert / evict /
    spill / restore — the cache's own state transitions ARE the
    directory's write stream, so it can never drift from the caches).

    Lookup mirrors the caches' matching rule exactly (longest
    block-aligned STRICT common prefix), so a directory hit means the
    steered replica's own ``match()`` will hit too — directed cache-hit
    steering instead of a blind hash pin. All state is host bytes/ints;
    updates and lookups are deterministic functions of the event
    stream, so steering decisions replay bit-exactly (the journaled
    dispatch candidates carry each replica's hit length + tier)."""

    def __init__(self, block: int):
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        self.block = int(block)
        self._tokens: Dict[bytes, np.ndarray] = {}
        # key -> replica idx -> {"tier", "pages", "touch"}
        self._owners: Dict[bytes, Dict[int, dict]] = {}
        self._seq = 0
        self.lookups = 0
        self.hits = 0
        self.updates = 0

    def attach(self, idx: int, cache) -> None:
        """Subscribe to one replica's cache transitions."""
        if cache is None or not hasattr(cache, "listeners"):
            return

        def on_event(event, key, tokens, tier, pages, _idx=idx):
            self._note(_idx, event, key, tokens, tier, pages)

        cache.listeners.append(on_event)

    def _note(self, idx: int, event: str, key: bytes, tokens,
              tier: str, pages: int) -> None:
        self.updates += 1
        self._seq += 1
        if event == "evict":
            owners = self._owners.get(key)
            if owners is not None:
                owners.pop(idx, None)
                if not owners:
                    self._owners.pop(key, None)
                    self._tokens.pop(key, None)
            return
        self._tokens[key] = np.asarray(tokens, np.int32)
        self._owners.setdefault(key, {})[idx] = {
            "tier": tier, "pages": int(pages), "touch": self._seq}

    def lookup(self, prompt) -> Optional[dict]:
        """Longest block-aligned strict common prefix across the whole
        fleet's cached entries, or None. Returns ``{"key", "rows",
        "owners": {idx: {tier, pages, touch}}}``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        b = self.block
        cap = (len(prompt) // b) * b
        if cap == len(prompt):
            cap -= b
        self.lookups += 1
        if cap <= 0 or not self._owners:
            return None
        best_l, best_key = 0, None
        for key, toks in self._tokens.items():
            m = (min(_common_prefix(prompt, toks), cap) // b) * b
            if m > best_l:
                best_l, best_key = m, key
        if best_key is None:
            return None
        self.hits += 1
        return {"key": best_key, "rows": best_l,
                "owners": {i: dict(info)
                           for i, info in self._owners[best_key].items()}}

    def reset(self) -> None:
        self._tokens.clear()
        self._owners.clear()
        self._seq = 0
        self.lookups = self.hits = self.updates = 0

    def stats(self) -> dict:
        return {"entries": len(self._owners),
                "placements": sum(len(o) for o in self._owners.values()),
                "lookups": self.lookups, "hits": self.hits,
                "updates": self.updates}


# ---------------------------------------------------------------------------
# fault injection (r13, ISSUE 8c): deterministic replica crash/hang harness
# ---------------------------------------------------------------------------


class ReplicaCrash(Exception):
    """Injected process-death: the in-flight segment's results are lost
    and the replica is immediately DEAD (no retry can help a corpse)."""


class ReplicaHang(Exception):
    """Injected wedge: the segment fetch 'times out'. Retries may
    succeed (a transient stall) — repeated hangs escalate to dead."""


class FaultInjector:
    """Declarative, deterministic fault schedule for the failover tests
    and the ``--failover`` benchmark lane. Faults fire at a replica's
    k-th ``finish_segment`` (segments are counted per replica, and the
    fleet's dispatch order is deterministic on a burst trace — the r12
    determinism contract — so a schedule keyed on (replica, segment) is
    exactly reproducible). ``seed``/``crash_p`` adds a seeded random
    crash mode on top for soak-style schedules.

    * ``crash={idx: seg_no}``: that finish raises ``ReplicaCrash`` once.
    * ``hang={idx: (seg_no, n)}``: that finish raises ``ReplicaHang``
      ``n`` consecutive times (attempt-counted, so bounded retry can
      ride through a transient hang when n <= the retry budget).
    * ``recover_after``: a dead replica's k-th re-admission probe
      succeeds (models a restart/repair completing).
    """

    def __init__(self, crash: Optional[Dict[int, int]] = None,
                 hang: Optional[Dict[int, tuple]] = None,
                 recover_after: int = 1, seed: int = 0,
                 crash_p: float = 0.0):
        self.crash = dict(crash or {})
        self.hang = {k: [int(v[0]), int(v[1])]
                     for k, v in (hang or {}).items()}
        self.recover_after = int(recover_after)
        self.seed = int(seed)
        self.crash_p = float(crash_p)
        self._rng = np.random.RandomState(seed)
        self._draws = 0                    # seeded rand() calls consumed
        self.events: List[tuple] = []      # (kind, replica, detail) log

    def describe(self) -> dict:
        """Rebuildable snapshot for the journal header (r16): the
        CURRENT schedule (fired crashes already popped) plus how many
        seeded draws were consumed, so a replay's injector fires the
        exact same faults from the exact same stream position."""
        return {"crash": dict(self.crash),
                "hang": {k: list(v) for k, v in self.hang.items()},
                "recover_after": self.recover_after, "seed": self.seed,
                "crash_p": self.crash_p, "draws": self._draws}

    def on_finish(self, idx: int, seg_no: int) -> None:
        """Called right before replica ``idx`` fetches its ``seg_no``-th
        segment; raises to inject the fault."""
        fire = self.crash.get(idx) == seg_no
        if not fire and self.crash_p:
            self._draws += 1
            draw = float(self._rng.rand())
            fire = draw < self.crash_p
            _journal.record("fault", fault="draw", replica=idx,
                            segment=seg_no, draw=draw, fired=fire)
        if fire:
            self.crash.pop(idx, None)
            self.events.append(("crash", idx, seg_no))
            _journal.record("fault", fault="crash", replica=idx,
                            segment=seg_no)
            raise ReplicaCrash(f"replica {idx} crashed at its segment "
                               f"{seg_no}")
        h = self.hang.get(idx)
        if h is not None and h[0] == seg_no and h[1] > 0:
            h[1] -= 1
            self.events.append(("hang", idx, seg_no))
            _journal.record("fault", fault="hang", replica=idx,
                            segment=seg_no, remaining=h[1])
            raise ReplicaHang(f"replica {idx} hung at its segment "
                              f"{seg_no}")

    def on_probe(self, idx: int, probe_no: int) -> bool:
        """Re-admission probe of a dead replica: True = recovered."""
        self.events.append(("probe", idx, probe_no))
        return probe_no >= self.recover_after


# ---------------------------------------------------------------------------
# shadow serving (r17 tentpole, ISSUE 12): mirror a sampled fraction of
# live traffic to a variant engine, strictly off the primary path
# ---------------------------------------------------------------------------


class Shadow:
    """Shadow-serving attachment for :class:`FleetRouter`.

    ``engine`` runs the VARIANT config (different kernels, chunking,
    spec-K — later quantized weights) and receives a seeded, sampled
    fraction of live requests as mirrors. The contract:

    * **Off the critical path.** The shadow runs its OWN segments with
      its OWN sanctioned per-segment event fetch (the same audited
      ``allowed_sync`` label — the fleet-loop sync audit counts
      primary + shadow segment fetches exactly, zero flagged). The
      primary's one-fetch/zero-extra-sync contract is untouched: shadow
      work is stepped strictly after each loop turn's primary work, its
      telemetry lands in its own registry, and every journal record it
      produces (clock reads included) carries the shadow mark so the
      primary decision stream replays bit-identically with or without
      the shadow attached.
    * **Seeded sampling.** ``wants(rid)`` is a pure crc32 draw on
      (seed, fleet rid) — deterministic, replayable, and stable across
      fleet sizes.
    * **Quality diffing.** When both sides of a mirrored pair finish,
      the attached :class:`~paddle_tpu.observability.quality
      .QualityMonitor` diffs token streams (exact first-divergence
      position) and — when both engines carry ``quality_digest`` — the
      per-token logit digests (max |Δ|, sampled KL), feeding the
      ok→warning→page rules and the ``/quality`` endpoint.
    """

    def __init__(self, engine: ServingEngine, sample_p: float = 1.0,
                 seed: int = 0, monitor=None,
                 seg_steps: Optional[int] = None):
        if not 0.0 <= float(sample_p) <= 1.0:
            raise ValueError(f"sample_p must be in [0, 1], got {sample_p}")
        self.engine = engine
        self.sample_p = float(sample_p)
        self.seed = int(seed)
        self.monitor = (monitor if monitor is not None
                        else _quality.QualityMonitor())
        self.seg_steps = seg_steps
        self.registry = _metrics.Registry()
        self.mirrored = 0
        self.dropped = 0           # mirrors skipped (doesn't fit shadow)
        self.compared = 0
        self.segments = 0
        self._map: Dict[int, int] = {}       # shadow erid -> fleet rid
        self._awaiting: set = set()          # fleet rids mid-pair
        self._primary: Dict[int, tuple] = {}  # rid -> (toks, digs, cls)
        self._shadow: Dict[int, tuple] = {}   # rid -> (toks, digs)

    def wants(self, rid: int) -> bool:
        """Seeded mirror draw for fleet rid ``rid`` (pure function)."""
        if self.sample_p <= 0.0:
            return False
        if self.sample_p >= 1.0:
            return True
        h = zlib.crc32(f"{self.seed}:{rid}".encode()) % 1_000_000
        return h < int(self.sample_p * 1_000_000)

    @property
    def busy(self) -> bool:
        e = self.engine
        return (bool(e._queue) or e.free_slot_count() < e.slots
                or e._pending_seg is not None)

    def stats(self) -> dict:
        return {"mirrored": self.mirrored, "dropped": self.dropped,
                "compared": self.compared, "segments": self.segments,
                "sample_p": self.sample_p,
                "pending_pairs": len(self._awaiting),
                "ticks": self.engine.last_run_ticks}

    def reset(self) -> None:
        self.engine.reset_slots()
        self.monitor.reset()
        self.registry.reset()
        self.mirrored = self.dropped = self.compared = self.segments = 0
        self._map.clear()
        self._awaiting.clear()
        self._primary.clear()
        self._shadow.clear()


@dataclass
class FleetReport:
    """Measured outcome of one fleet serve() (all times in seconds)."""
    replicas: int
    n_requests: int
    total_tokens: int
    makespan_s: float
    throughput_tok_s: float
    ttft_p50_s: float
    ttft_p99_s: float
    e2e_p50_s: float
    e2e_p99_s: float
    queue_wait_p50_s: float
    segments: int
    ticks: int
    backpressure_events: int       # == sum of per-replica counters
    dispatches_affinity: int
    dispatches_least_loaded: int
    # r13 failover accounting: replicas declared dead this serve,
    # requests requeued to survivors, final health per replica, and the
    # fleet-path retry_after_s backpressure hint (None = never refused)
    failovers: int = 0
    requeued: int = 0
    replica_health: Optional[Dict[int, str]] = None
    retry_after_s: Optional[float] = None
    # r14 (ISSUE 9): worst replica cold-start→first-token this fleet
    # paid (per-replica values ride in per_replica), plus the attached
    # monitors' state — the fleet analogs of OnlineReport's fields
    cold_start_s: Optional[float] = None
    slo: Optional[dict] = None
    perf: Optional[dict] = None
    # r17 (ISSUE 12): online quality observability — the shadow pair's
    # QualityMonitor report, the shadow attachment's own accounting,
    # canary dispatch count and the canary controller's verdicts/hold
    dispatches_canary: int = 0
    quality: Optional[dict] = None
    shadow: Optional[dict] = None
    canary: Optional[dict] = None
    # r19 (ISSUE 14): directed steering + tier accounting — directory
    # dispatches, cross-replica host-tier imports, and the directory's
    # own hit/entry stats (None when no directory is attached)
    dispatches_directory: int = 0
    tier_migrations: int = 0
    directory: Optional[dict] = None
    # r25 (ISSUE 20): elastic autoscaling — scale actions this serve
    # plus the attached policies' report (None when no autoscaler)
    scale_ups: int = 0
    scale_downs: int = 0
    autoscaler: Optional[dict] = None
    per_replica: List[dict] = field(default_factory=list)
    telemetry: Optional[dict] = None   # merge_log_dir reduction

    def as_dict(self, with_replicas: bool = True) -> dict:
        d = {k: v for k, v in self.__dict__.items()
             if k not in ("per_replica", "telemetry")}
        if with_replicas:
            d["per_replica"] = self.per_replica
        return d


class _Replica:
    """One engine + its isolated prefix cache, registry and counters."""

    _HEALTH_CODE = {"healthy": 0.0, "suspect": 1.0, "dead": 2.0}

    def __init__(self, idx: int, engine: ServingEngine, prefix_cache):
        self.idx = idx
        self.engine = engine
        self.prefix_cache = prefix_cache
        # r22 (ISSUE 17): pool role — None in a homogeneous fleet;
        # "prefill"/"decode" when a DisaggRouter owns this replica
        self.pool: Optional[str] = None
        self.registry = _metrics.Registry()
        self.backpressure_events = 0
        self.dispatches = {"affinity": 0, "least_loaded": 0,
                           "canary": 0, "directory": 0}
        self.segments = 0
        self.rids: List[int] = []          # fleet rids, assignment order
        # r13 failover: health state machine (healthy -> suspect on a
        # segment timeout / transient hang -> dead on repetition or
        # crash -> healthy again via re-admission probe)
        self.health = "healthy"
        self.timeouts = 0                  # consecutive slow segments
        self.dead_since = 0.0
        self.probes = 0
        # r25 elastic lifecycle (ISSUE 20), orthogonal to health:
        # offline (warm standby, never dispatched) -> warming (chip-fit
        # proved, AOT warmup running) -> serving (in the dispatch set)
        # -> draining (stops admitting, live slots finish, queue
        # requeued, prefixes migrated) -> offline. Without an
        # autoscaler every replica stays "serving" and nothing changes.
        self.lifecycle = "serving"
        self.drain: Optional[dict] = None   # progress while draining
        self.last_drain: Optional[dict] = None
        self.warmed_s: Optional[float] = None

    def set_health(self, state: str) -> None:
        self.health = state
        with _metrics.scoped_registry(self.registry):
            _metrics.gauge("fleet.replica_health").set(
                self._HEALTH_CODE[state])

    @property
    def queue_depth(self) -> int:
        return len(self.engine._queue)

    @property
    def live(self) -> int:
        return self.engine.slots - self.engine.free_slot_count()

    @property
    def load(self) -> int:
        return self.queue_depth + self.live

    @property
    def busy(self) -> bool:
        return bool(self.engine._queue) or self.live > 0


def build_fleet(cfg, params, n: int, devices: Optional[Sequence] = None,
                **engine_kw) -> List[ServingEngine]:
    """N identical engine replicas. With an explicit ``devices`` list,
    replica i's weights are committed to device ``i % ndev`` —
    computation follows the committed params, so replicas execute on
    distinct chips and their segments overlap through async dispatch
    (the data-parallel placement; a replica that should itself span
    chips takes ``mesh=`` instead). Default (``devices=None``) keeps
    the weights UNCOMMITTED on the default device: on a single-device
    host per-replica commitment buys nothing and measurably costs —
    committed args push every segment call off jax's jit fast path
    (~2.4x slower dispatch on this container's CPU lowering) — so
    placement is strictly opt-in."""
    import jax

    engines = []
    for i in range(n):
        p = params
        if devices:
            p = jax.device_put(params, devices[i % len(devices)])
        engines.append(ServingEngine(cfg, p, **engine_kw))
    return engines


class FleetRouter:
    """Prefix-affinity + least-loaded router over N engine replicas.

    ``engines`` may be heterogeneous in placement (per-device replicas,
    mp-sharded replicas) but must share the serving contract (same
    model/config). ``prefix_caches``: None (no caching), "auto" (one
    independent cache per replica via ``make_prefix_cache`` — the fleet
    isolation contract: a cache is keyed to ITS engine, never shared),
    or an explicit list. ``max_queue`` bounds each replica's intake
    queue; ``seg_steps`` is per-segment tick budget (the same control-
    latency knob as ``OnlineScheduler``)."""

    def __init__(self, engines: Sequence[ServingEngine],
                 max_queue: int = 64, seg_steps: int = 32,
                 prefix_caches=None, affinity_block: Optional[int] = None,
                 segment_timeout_s: Optional[float] = None,
                 max_finish_retries: int = 1, max_requeues: int = 3,
                 fault_injector: Optional[FaultInjector] = None,
                 probe_after_s: float = 0.05,
                 slo_monitor=None, perf_monitor=None,
                 shadow: Optional[Shadow] = None, canary=None,
                 directory: bool = False, autoscaler=None,
                 capacity_monitor=None):
        if not engines:
            raise ValueError("a fleet needs at least one engine")
        if prefix_caches == "auto":
            prefix_caches = [make_prefix_cache(e) for e in engines]
        elif prefix_caches is None:
            prefix_caches = [None] * len(engines)
        if len(prefix_caches) != len(engines):
            raise ValueError(f"{len(prefix_caches)} prefix caches for "
                             f"{len(engines)} engines")
        for e, pc in zip(engines, prefix_caches):
            if pc is not None and pc.pager is not e.pager:
                raise ValueError(
                    "a replica's prefix cache must wrap ITS OWN "
                    "pager (fleet isolation: one cache per engine)")
        blocks = {pc.block for pc in prefix_caches if pc is not None}
        if len(blocks) > 1:
            raise ValueError(f"replica caches disagree on block size "
                             f"{sorted(blocks)} — affinity hashing needs "
                             f"one alignment rule")
        self._replicas = [_Replica(i, e, pc)
                          for i, (e, pc) in enumerate(zip(engines,
                                                          prefix_caches))]
        self.max_queue = int(max_queue)
        self.seg_steps = int(seg_steps)
        self.affinity_block = int(affinity_block
                                  or (next(iter(blocks)) if blocks else 32))
        # affinity exists to route repeat prefixes back to the replica
        # whose CACHE holds them; without caches a prompt-hash pin is
        # pure load imbalance, so the router degrades to least-loaded
        self._use_affinity = any(pc is not None for pc in prefix_caches)
        self.backpressure_events = 0
        self._reqs: Dict[int, tuple] = {}   # fleet rid -> (replica, Request)
        self._next_rid = 0
        # r13 failover knobs (ISSUE 8c). segment_timeout_s: a finish
        # slower than this marks the replica suspect (None = timeouts
        # off — the default: a loaded single-core CI box must not
        # false-positive its own replicas dead). max_finish_retries:
        # bounded re-attempts of a hung segment fetch before declaring
        # the replica dead. max_requeues: per-request failover budget —
        # a request bounced more than this fails loudly instead of
        # ping-ponging across a dying fleet forever.
        self.segment_timeout_s = segment_timeout_s
        self.max_finish_retries = int(max_finish_retries)
        self.max_requeues = int(max_requeues)
        self.fault_injector = fault_injector
        self.probe_after_s = float(probe_after_s)
        # r14 (ISSUE 9): fleet-level live-ops monitors — fed from the
        # same host stamps ``_stamp`` already takes at each segment's
        # audited fetch; their gauges land in the PROCESS registry (the
        # fleet view), not the replica-scoped ones, so the hooks run
        # outside the scoped_registry blocks
        self.slo_monitor = slo_monitor
        self.perf_monitor = perf_monitor
        # r17 (ISSUE 12): shadow + canary attachments. The shadow is an
        # OBSERVER (mirrored traffic, own engine, own fetch, own
        # registry, journal-marked records — never a routing input);
        # the canary is a DECIDER (a seeded weight of live traffic
        # routes to its replica, control traffic never does), so its
        # config rides the journal header and replay rebuilds it.
        self.shadow = shadow
        if shadow is not None:
            if any(r.engine is shadow.engine for r in self._replicas):
                raise ValueError(
                    "shadow engine must not be a fleet replica — it "
                    "runs the variant config off the primary path")
        self.canary = canary
        if canary is not None:
            if not 0 <= canary.replica < len(self._replicas):
                raise ValueError(
                    f"canary replica {canary.replica} out of range for "
                    f"a {len(self._replicas)}-replica fleet")
            if len(self._replicas) < 2:
                raise ValueError(
                    "a canary needs >= 2 replicas: the canary replica "
                    "is excluded from control traffic, so a 1-replica "
                    "fleet would have no control population")
        # r19 tiered KV (ISSUE 14): the fleet cache directory — directed
        # cache-hit steering over the per-replica caches' live state,
        # with migration-on-miss through the replica-portable host tier.
        # Opt-in: blind affinity stays the default routing contract.
        self.directory: Optional[CacheDirectory] = None
        if directory:
            pcs = [(i, pc) for i, pc in enumerate(prefix_caches)
                   if pc is not None]
            if not pcs:
                raise ValueError(
                    "directory steering needs prefix caches — it "
                    "routes on the caches' live entry state")
            self.directory = CacheDirectory(pcs[0][1].block)
            for i, pc in pcs:
                self.directory.attach(i, pc)
        self.tier_migrations = 0            # cross-replica imports
        self.failovers = 0                  # replicas declared dead
        self.requeued = 0                   # requests moved to survivors
        self.last_retry_after_s: Optional[float] = None
        self._finished_count = 0
        self._serve_t0 = 0.0
        # r25 elastic autoscaling (ISSUE 20): one policy for the whole
        # fleet, or a list (the DisaggRouter attaches one per pool).
        # The policy is a DECIDER — its config rides the journal header
        # and replay rebuilds it. ``capacity_monitor`` is its r18
        # capacity_alert input, fed fleet-wide at every segment finish
        # (deterministic host ints, so the alert levels replay too).
        self.capacity_monitor = capacity_monitor
        self.autoscalers: list = []
        self._attach_autoscalers(autoscaler)

    def _attach_autoscalers(self, autoscaler) -> None:
        """Normalize + bind scale policies. Split out of ``__init__``
        so a pool-aware subclass can defer binding until after its
        replicas carry pool tags (a pool-scoped policy's ``bind``
        filters on them)."""
        if autoscaler is None:
            return
        ascs = (list(autoscaler)
                if isinstance(autoscaler, (list, tuple))
                else [autoscaler])
        self.autoscalers.extend(ascs)
        for asc in ascs:
            asc.bind(self)

    # --- AOT warmup (r20: ISSUE 15) --------------------------------------
    def aot_warmup(self, envelope=None) -> Dict[int, dict]:
        """Compile every replica's enumerated program space at build.
        Identical-geometry replicas share one XLA compile per key
        through ``serving._SHARED_PROGS`` — replica 0 pays the ladder,
        the rest execute the already-compiled programs on empty state
        (microseconds per key) — so a fleet scale-out's warmup cost is
        per BINARY, not per replica (SCALING §3o). Each replica's
        warmup runs under its scoped registry/rank so the
        ``aot_warmup_s`` gauges land per rank like every other serving
        metric."""
        out: Dict[int, dict] = {}
        for r in self._replicas:
            with _metrics.scoped_registry(r.registry), \
                    _journal.rank_scope(r.idx):
                out[r.idx] = r.engine.aot_warmup(
                    envelope, prefix_cache=r.prefix_cache)
        return out

    # --- routing ---------------------------------------------------------
    def _affinity_key(self, prompt: np.ndarray) -> Optional[bytes]:
        """Block-aligned STRICT prefix bytes (the prefix caches' rule:
        at least one token must remain to prefill), or None when the
        prompt is too short to carry a cacheable prefix."""
        b = self.affinity_block
        cap = (len(prompt) // b) * b
        if cap == len(prompt):
            cap -= b
        if cap <= 0:
            return None
        return np.asarray(prompt[:cap], np.int32).tobytes()

    def _page_ready(self, r: _Replica, a: Arrival) -> bool:
        eng = r.engine
        need = eng.pager.pages_needed(len(a.prompt) + a.max_new_tokens - 1)
        return eng.pager.pages_free >= need

    def _dispatch_candidates(self) -> List[_Replica]:
        """The replicas fresh arrivals may route to. The homogeneous
        fleet offers everyone; a pool-aware subclass (r22 DisaggRouter)
        narrows this to its prefill pool so prompts always start on
        prefill replicas and decode replicas take work only through the
        journaled handoff path. r25: only ``serving``-lifecycle
        replicas take fresh traffic — warming replicas are not ready,
        draining replicas are being emptied on purpose, and offline
        standbys hold no live programs."""
        return [r for r in self._replicas if r.lifecycle == "serving"]

    def _route(self, a: Arrival, dirinfo: Optional[dict] = None):
        """(replica, reason) for a due arrival, or (bill_target, None)
        when every queue is full (fleet backpressure). r13: suspect and
        dead replicas are EXCLUDED from dispatch — an affinity pin to an
        unhealthy replica falls through to least-loaded over the healthy
        set (the prefix re-prefills on the survivor; correctness over
        cache warmth), and only if NO healthy replica exists do suspects
        take traffic as a last resort (dead never).

        r19 directed steering (ISSUE 14): ``dirinfo`` (a
        ``CacheDirectory.lookup`` hit) outranks the blind affinity
        hash — the request goes to a replica that FACTUALLY holds its
        prefix (resident tiers before host tier: a restore costs an
        upload), provided that replica can take it right now; an
        untakeable owner set falls through to affinity/least-loaded,
        and the miss becomes a migration opportunity (``_migrate``).

        r17 canary split (ISSUE 12): with a canary attached, a seeded
        pure draw on the rid this arrival WILL take routes ``weight`` of
        traffic to the canary replica (healthy + queue/page room
        required — a degraded canary falls back to control rather than
        adding backpressure), and control traffic NEVER lands on the
        canary replica: the comparison populations stay disjoint, and
        an auto-hold (weight → 0) takes the variant out of the path
        while it drains its backlog."""
        can = self.canary
        ctl = self._dispatch_candidates()
        if can is not None:
            crep = self._replicas[can.replica]
            if (can.assign(self._next_rid) and crep.health == "healthy"
                    and crep.lifecycle == "serving"
                    and crep.queue_depth < self.max_queue
                    and self._page_ready(crep, a)):
                return crep, "canary"
            ctl = [r for r in ctl if r.idx != can.replica]
        if dirinfo is not None:
            owners = dirinfo["owners"]
            dcands = [r for r in ctl
                      if r.idx in owners and r.health == "healthy"
                      and r.queue_depth < self.max_queue
                      and self._page_ready(r, a)]
            if dcands:
                best = min(dcands,
                           key=lambda r: (owners[r.idx]["tier"] == "host",
                                          r.load, r.idx))
                return best, "directory"
        key = (self._affinity_key(a.prompt)
               if self._use_affinity else None)
        pref = (ctl[zlib.crc32(key) % len(ctl)]
                if key is not None else None)
        if (pref is not None and pref.health == "healthy"
                and pref.queue_depth < self.max_queue):
            return pref, "affinity"
        cands = [r for r in ctl
                 if r.queue_depth < self.max_queue
                 and r.health == "healthy"]
        if not cands:
            cands = [r for r in ctl
                     if r.queue_depth < self.max_queue
                     and r.health == "suspect"]
        if not cands:
            # all takeable queues full: bill the replica the request
            # WOULD have gone to, so fleet backpressure == sum(replica
            # counters)
            bill = pref if pref is not None else \
                min(ctl, key=lambda r: (r.load, r.idx))
            return bill, None
        best = min(cands, key=lambda r: (not self._page_ready(r, a),
                                         r.load, r.idx))
        return best, "least_loaded"

    def _migrate(self, dirinfo: dict, dst: _Replica,
                 rid: int) -> Optional[tuple]:
        """Import ``dirinfo``'s prefix from an owning replica's HOST
        tier into ``dst``'s cache (r19, ISSUE 14): host-tier pages are
        replica-portable bytes, so a steering miss costs one host-to-
        host copy instead of a full prefill recompute. Freshest staged
        owner wins; an owner whose entry never finished staging cannot
        export (moving HBM pages would need a sync) and is skipped.
        Returns (pages, bytes) imported, or None."""
        pc = dst.prefix_cache
        if pc is None or getattr(pc, "host_tier", None) is None:
            return None
        owners = sorted(dirinfo["owners"].items(),
                        key=lambda kv: -kv[1]["touch"])
        for idx, _info in owners:
            src = self._replicas[idx].prefix_cache
            if src is None or not hasattr(src, "export_host"):
                continue
            exp = src.export_host(dirinfo["key"])
            if exp is None:
                continue
            planes = {p: exp[p] for p in exp
                      if p not in ("tokens", "pages")}
            if not pc.import_host(exp["tokens"], planes):
                continue
            n = int(exp["pages"])
            nbytes = n * pc.host_tier.page_bytes()
            self.tier_migrations += 1
            _metrics.counter("fleet.tier_migrations").inc()
            _flight.record("tier_migrate", rid=rid, src=idx,
                           dst=dst.idx, pages=n, bytes=nbytes,
                           rows=int(len(exp["tokens"])))
            return n, nbytes
        return None

    # --- intake ----------------------------------------------------------
    def _ingest(self, pending: List[Arrival], now: float, t0: float) -> int:
        refused = 0
        _j = _journal.active()
        while pending and pending[0].t <= now:
            a = pending[0]
            dirinfo = (self.directory.lookup(a.prompt)
                       if self.directory is not None else None)
            rep, reason = self._route(a, dirinfo)
            cands = None
            if _j is not None:
                # the dispatch decision WITH its candidate ranking: the
                # per-replica load/health/page state the router compared
                # — the "why replica 2" answer a postmortem needs
                # (snapshotted BEFORE intake mutates the queues)
                # r18 (ISSUE 13): the ranking gains the page-capacity
                # numbers it was implicitly comparing — pages_free /
                # reclaimable per candidate, so the item-4 autoscaler
                # reads its scale-up signal straight off the dispatch
                # record (and /healthz mirrors the same pair live)
                # r19 (ISSUE 14): the ranking gains per-replica
                # directory-hit info (matched rows + tier) so a
                # steering decision's "why replica 2" replays
                # bit-exactly off the journal record alone
                # r22 (ISSUE 17): the ranking carries the pool tag —
                # a disaggregated dispatch record shows decode replicas
                # present-but-ineligible for fresh prompts
                owners = dirinfo["owners"] if dirinfo is not None else {}
                # r25 (ISSUE 20): the ranking carries the lifecycle —
                # an elastic dispatch record shows warming/draining/
                # offline replicas present-but-ineligible
                cands = [{"idx": x.idx, "health": x.health,
                          "pool": x.pool, "lifecycle": x.lifecycle,
                          "queue": x.queue_depth, "live": x.live,
                          "page_ready": self._page_ready(x, a),
                          "pages_free": x.engine.pager.pages_free,
                          "reclaimable": (
                              x.prefix_cache.reclaimable_pages()
                              if x.prefix_cache is not None else 0),
                          "dir_hit": (dirinfo["rows"]
                                      if x.idx in owners else 0),
                          "dir_tier": (owners[x.idx]["tier"]
                                       if x.idx in owners else None)}
                         for x in self._replicas]
                if reason is None:          # refusal: no rid assigned
                    _j.record("dispatch", rid=None, replica=rep.idx,
                              reason="backpressure", candidates=cands)
            if reason is None:
                refused += 1
                rep.backpressure_events += 1
                self.backpressure_events += 1
                hint = self.retry_after_hint(now)
                self.last_retry_after_s = hint
                with _metrics.scoped_registry(rep.registry):
                    _metrics.counter("serving.backpressure_events").inc()
                _metrics.counter("fleet.backpressure_events").inc()
                _metrics.gauge("fleet.retry_after_s").set(hint)
                _flight.record("backpressure", replica=rep.idx,
                               queue=rep.queue_depth, fleet=True,
                               retry_after_s=round(hint, 4))
                break                       # arrival stays client-side
            pending.pop(0)
            rid = self._next_rid
            self._next_rid += 1
            # r19 migration-on-miss (ISSUE 14): the steered owner could
            # not take this arrival and the chosen replica does not hold
            # the prefix — import the owner's replica-portable HOST
            # bytes into the destination cache so admission restores
            # instead of recomputing the prefill
            imported = None
            if (dirinfo is not None and reason != "directory"
                    and rep.idx not in dirinfo["owners"]):
                imported = self._migrate(dirinfo, rep, rid)
            erid = rep.engine.add_request(a.prompt, a.max_new_tokens)
            req = rep.engine._queue[-1]
            assert req.rid == erid
            req.arrival_time = t0 + a.t
            if imported is not None:
                req.tier_pages += imported[0]
                req.tier_bytes += imported[1]
            self._reqs[rid] = (rep.idx, req)
            rep.rids.append(rid)
            _journal.record("arrival", rid=rid, at=a.t, replica=rep.idx,
                            erid=erid, prompt_len=len(req.prompt),
                            gen=req.max_new_tokens)
            if _j is not None:
                _j.record("dispatch", rid=rid, replica=rep.idx,
                          reason=reason, candidates=cands)
            rep.dispatches[reason] += 1
            _metrics.counter(f"fleet.dispatches.{reason}").inc()
            with _metrics.scoped_registry(rep.registry):
                _metrics.gauge("fleet.replica_queue_depth").set(
                    rep.queue_depth)
            _flight.record("fleet_dispatch", rid=rid, replica=rep.idx,
                           reason=reason, queue=rep.queue_depth)
            if self.shadow is not None and self.shadow.wants(rid):
                self._mirror_to_shadow(rid, req)
        return refused

    # --- shadow serving (r17 tentpole, ISSUE 12) --------------------------
    def _mirror_to_shadow(self, rid: int, req: Request) -> None:
        """Mirror one admitted request into the shadow engine's queue.
        Runs inside the shadow scope + the shadow's registry: the
        primary's metrics and journal decision stream are untouched."""
        sh = self.shadow
        eng = sh.engine
        if (len(req.prompt) > max(eng.buckets)
                or len(req.prompt) + req.max_new_tokens - 1 > eng.max_len):
            sh.dropped += 1     # variant geometry can't hold the mirror
            return
        with _journal.shadow_scope(), \
                _metrics.scoped_registry(sh.registry):
            serid = eng.add_request(np.asarray(req.prompt, np.int32),
                                    req.max_new_tokens)
            sh._map[serid] = rid
            sh._awaiting.add(rid)
            sh.mirrored += 1
            _journal.record("shadow_mirror", rid=rid, shadow_rid=serid)

    def _shadow_step(self, now_abs: float) -> None:
        """Advance the shadow by at most one finish + one dispatch,
        strictly AFTER this loop turn's primary work. The shadow's
        segment fetch is its own sanctioned ``allowed_sync`` (the
        fleet-loop audit counts primary + shadow fetches exactly);
        ``now_abs`` is the loop's already-read decision clock, so the
        shadow adds ZERO clock reads to the primary stream."""
        sh = self.shadow
        if sh is None:
            return
        eng = sh.engine
        with _journal.shadow_scope():
            finished = False
            with _metrics.scoped_registry(sh.registry):
                if eng._pending_seg is not None:
                    eng.finish_segment()
                    sh.segments += 1
                    finished = True
            if finished:
                # pair collection runs OUTSIDE the shadow's scoped
                # registry: the quality gauges/counters are the
                # process (fleet-view) surface an operator scrapes
                self._collect_shadow()
            with _metrics.scoped_registry(sh.registry):
                if ((eng._queue or eng.free_slot_count() < eng.slots)
                        and eng._pending_seg is None):
                    eng.dispatch_segment(
                        sh.seg_steps if sh.seg_steps else self.seg_steps,
                        now=now_abs)

    def _collect_shadow(self) -> None:
        """Harvest finished shadow requests (tokens + digests) and diff
        any completed pairs. Caller holds the shadow scope but NOT the
        shadow's scoped registry — quality metrics are the process
        view."""
        sh = self.shadow
        eng = sh.engine
        if not eng._finished:
            return
        digs = {r.rid: r.digests for r in eng._finished}
        done = eng.collect_finished()
        for serid, toks in done.items():
            rid = sh._map.pop(serid, None)
            if rid is None:
                continue
            d = digs.get(serid)
            sh._shadow[rid] = (toks, d[:len(toks)] if d else None)
            self._compare_pair(rid)

    def _collect_primary(self, rep: _Replica, ev: dict) -> None:
        """Primary side of the pair: at a mirrored request's finish,
        snapshot its final token stream (and digests) — host mirrors of
        the fetch that just completed. Runs OUTSIDE the replica's
        scoped registry so the quality metrics land in the process
        (fleet-view) registry."""
        sh = self.shadow
        by_erid = {self._reqs[rid][1].rid: rid for rid in rep.rids}
        for erid in ev["finished"]:
            frid = by_erid[erid]
            if frid not in sh._awaiting:
                continue
            req = self._reqs[frid][1]
            toks = _quality.final_tokens(req.tokens, req.max_new_tokens,
                                         rep.engine.eos)
            digs = (req.digests[:len(toks)] if req.digests else None)
            with _journal.shadow_scope():
                sh._primary[frid] = (toks, digs, req.priority)
                self._compare_pair(frid)

    def _compare_pair(self, rid: int) -> None:
        """Diff a mirrored pair once BOTH sides finished. Caller holds
        the shadow scope (the quality_alert / quality_divergence /
        shadow_finish records are journaled but marked off the primary
        decision stream)."""
        sh = self.shadow
        if rid not in sh._primary or rid not in sh._shadow:
            return
        p_toks, p_digs, prio = sh._primary.pop(rid)
        s_toks, s_digs = sh._shadow.pop(rid)
        sh._awaiting.discard(rid)
        res = sh.monitor.note_pair(rid, p_toks, s_toks, p_digs, s_digs,
                                   cls=prio)
        sh.compared += 1
        _journal.record("shadow_finish", rid=rid, match=res["match"],
                        first_divergence=res["first_divergence"],
                        compared=res["compared"])

    def _drain_shadow(self) -> None:
        """Finish the shadow's remaining mirrored work after the
        primary trace completed — off the critical path by construction
        (primary makespan is already stamped). Entirely inside the
        shadow scope: its clock reads never enter the primary decision
        stream."""
        sh = self.shadow
        if sh is None:
            return
        with _journal.shadow_scope():
            while sh.busy:
                self._shadow_step(_journal.now())

    # --- the serve loop --------------------------------------------------
    def serve(self, arrivals: Sequence[Arrival], warm: bool = False
              ) -> FleetReport:
        """Serve the trace to completion across the fleet and return the
        measured report. ``warm=True`` replays the identical trace once
        first (compiles every replica's segment shapes), then resets all
        fleet state so the measured pass times routing + scheduling."""
        if warm:
            self.serve(arrivals, warm=False)
            self.reset()

        # r16 (ISSUE 11): header + decision-clock recording — see
        # OnlineScheduler.serve; the fleet's header additionally carries
        # every replica's geometry, the per-replica prefix caches and
        # the fault injector's live schedule/draw position
        _j = _journal.active()
        if _j is not None:
            _j.begin_serve(self._journal_header(arrivals))
        pending = sorted(arrivals, key=lambda a: a.t)
        reps = self._replicas
        for r in reps:
            r.engine.last_run_ticks = 0
            r.engine.last_run_chunks = 0
        segments = 0
        # STAGGERED pipeline, not barrier turns: every busy replica
        # keeps one async segment in flight (jax dispatch never blocks
        # the host), and each loop iteration finishes exactly the
        # OLDEST one, re-ingests arrivals, and tops the fleet back up.
        # Arrivals therefore enter a queue and get dispatched at the
        # next ANY-replica finish (~1/N of a full fleet sweep) instead
        # of waiting out a whole synchronized turn — the TTFT lever when
        # replicas contend for one host/core; on real parallel devices
        # it additionally keeps every chip busy continuously.
        inflight: List[tuple] = []          # (replica, handle, t_disp) FIFO
        t0 = _journal.now()
        self._serve_t0 = t0
        self._finished_count = 0
        self.last_retry_after_s = None
        while (pending or inflight or any(r.busy for r in reps)
               or self._has_deferred_work()):
            now = _journal.now() - t0
            self._probe_dead()
            self._ingest(pending, now, t0)
            # r25 (ISSUE 20): the elastic control loop runs on the
            # turn's already-read clock — zero extra clock reads, so
            # attaching a policy perturbs the decision stream only
            # through the decisions it actually takes
            self._autoscale(now)
            # r13: dead replicas are out of rotation entirely (abort
            # emptied them); suspects still drain their own backlog —
            # exclusion applies to NEW traffic in _route
            busy_idle = [r for r in reps
                         if r.health != "dead" and r.busy
                         and r.engine._pending_seg is None]
            for r in busy_idle:
                # r23: deferred cross-pool work (the DisaggRouter's
                # coalesced handoff drain) materialises BEFORE any
                # dispatch, so a handed-off request is page-resident on
                # its target before the target's next segment can admit
                self._pre_dispatch(r)
                with _metrics.scoped_registry(r.registry), \
                        _journal.rank_scope(r.idx):
                    h = r.engine.dispatch_segment(
                        self._seg_steps_for(r),
                        prefix_cache=r.prefix_cache)
                inflight.append((r, h, _journal.now()))
            # r17: shadow work rides strictly AFTER the primary
            # dispatches of this turn, on the already-read clock
            self._shadow_step(now + t0)
            if not inflight:
                if self._has_deferred_work():
                    # r23: nothing in flight to coalesce behind — drain
                    # the deferred handoffs now (requeues make their
                    # targets busy, so the next turn dispatches them)
                    self._pre_dispatch(None)
                elif pending:
                    # waiting for work: no engine's gap spans it
                    for r in reps:
                        r.engine.gap_from_ns = None
                    gap = pending[0].t - (_journal.now() - t0)
                    if gap > 0:
                        _journal.sleep(min(gap, 0.05))
                elif any(r.health == "dead" for r in reps):
                    _journal.sleep(0.001)   # wait out the probe window
                continue
            # finish the oldest in-flight segment (its event fetch is
            # the one audited allowed_sync for that segment) under the
            # failure protocol: crash/hang/timeout drive the health
            # state machine and failover
            r, h, t_disp = inflight.pop(0)
            if self._finish_one(r, h, t_disp):
                segments += 1
        if self.autoscalers:
            # final policy step: finalize any drain whose replica just
            # emptied (one recorded clock read — only when policies are
            # attached, so autoscaler-free journals are byte-identical
            # to r24's)
            self._autoscale(_journal.now() - t0, final=True)
        makespan = _journal.now() - t0
        # r17: the shadow drains AFTER the primary makespan stamp (off
        # the critical path), and the canary issues its final verdict
        self._drain_shadow()
        if self.canary is not None:
            self.canary.evaluate(final=True)

        reqs = [req for _, req in self._reqs.values()]
        assert all(
            req.done or (reps[i].engine.eos is not None
                         and reps[i].engine.eos in req.tokens)
            for i, req in self._reqs.values()), \
            "fleet exited with unserved requests"
        total_tokens = sum(len(r.tokens) for r in reqs)
        ttfts = [r.first_token_time - r.arrival_time for r in reqs]
        e2es = [r.finish_time - r.arrival_time for r in reqs]
        qwaits = [r.admit_time - r.arrival_time for r in reqs]
        assert self.backpressure_events == sum(r.backpressure_events
                                               for r in reps)
        return FleetReport(
            replicas=len(reps),
            n_requests=len(reqs),
            total_tokens=total_tokens,
            makespan_s=makespan,
            throughput_tok_s=total_tokens / makespan if makespan else 0.0,
            ttft_p50_s=_pctl(ttfts, 0.50),
            ttft_p99_s=_pctl(ttfts, 0.99),
            e2e_p50_s=_pctl(e2es, 0.50),
            e2e_p99_s=_pctl(e2es, 0.99),
            queue_wait_p50_s=_pctl(qwaits, 0.50),
            segments=segments,
            ticks=sum(r.engine.last_run_ticks for r in reps),
            backpressure_events=self.backpressure_events,
            dispatches_affinity=sum(r.dispatches["affinity"]
                                    for r in reps),
            dispatches_least_loaded=sum(r.dispatches["least_loaded"]
                                        for r in reps),
            dispatches_canary=sum(r.dispatches.get("canary", 0)
                                  for r in reps),
            dispatches_directory=sum(r.dispatches.get("directory", 0)
                                     for r in reps),
            tier_migrations=self.tier_migrations,
            directory=(self.directory.stats()
                       if self.directory is not None else None),
            quality=(self.shadow.monitor.report()
                     if self.shadow is not None else None),
            shadow=(self.shadow.stats()
                    if self.shadow is not None else None),
            canary=(self.canary.report()
                    if self.canary is not None else None),
            failovers=self.failovers,
            requeued=self.requeued,
            replica_health={r.idx: r.health for r in reps},
            scale_ups=sum(a.scale_ups for a in self.autoscalers),
            scale_downs=sum(a.scale_downs for a in self.autoscalers),
            autoscaler=({"policies": [a.report()
                                      for a in self.autoscalers]}
                        if self.autoscalers else None),
            retry_after_s=self.last_retry_after_s,
            cold_start_s=max(
                (round(r.engine.cold_start_s, 4) for r in reps
                 if r.engine.cold_start_s is not None), default=None),
            slo=(self.slo_monitor.report()
                 if self.slo_monitor is not None else None),
            perf=(self.perf_monitor.end_interval()
                  if self.perf_monitor is not None else None),
            per_replica=[{
                "replica": r.idx,
                "requests": len(r.rids),
                "tokens": sum(len(self._reqs[rid][1].tokens)
                              for rid in r.rids),
                "segments": r.segments,
                "ticks": r.engine.last_run_ticks,
                "health": r.health,
                "lifecycle": r.lifecycle,
                "probes": r.probes,
                "cold_start_s": (round(r.engine.cold_start_s, 4)
                                 if r.engine.cold_start_s is not None
                                 else None),
                "backpressure_events": r.backpressure_events,
                "dispatches": dict(r.dispatches),
                "prefix": (r.prefix_cache.stats()
                           if r.prefix_cache is not None else None),
                "pages": r.engine.pager.stats(),
            } for r in reps],
        )

    # --- failure protocol (r13, ISSUE 8c) --------------------------------
    def retry_after_hint(self, now: float) -> float:
        """Fleet-level backoff hint for a refused client — same rule as
        ``OnlineScheduler.retry_after_hint`` (elapsed per finished
        request, clamped to [1 ms, 60 s]; 1 s before any finish), fed
        by the fleet-wide finish counter.

        r25 drain-aware (ISSUE 20 satellite): draining replicas still
        finish their backlog — inflating the fleet finish rate — but
        admit nothing, so a retrying client can only land on the
        ``serving`` subset. The hint scales by live/serving so it
        quotes the capacity the retry can actually reach, not the
        capacity that is being decommissioned under it."""
        if self._finished_count and now > 0:
            base = now / self._finished_count
            serving = [r for r in self._replicas
                       if r.lifecycle == "serving" and r.health != "dead"]
            live = [r for r in self._replicas
                    if r.lifecycle in ("serving", "draining")
                    and r.health != "dead"]
            if serving and len(live) > len(serving):
                base *= len(live) / len(serving)
            return min(max(base, 1e-3), 60.0)
        return 1.0

    def _finish_one(self, rep: _Replica, h, t_disp: float) -> bool:
        """Fetch one dispatched segment under the failure protocol.
        Returns True when the segment's results were applied; False when
        the replica died and the segment was discarded (its requests
        failed over inside ``_kill_replica``).

        * ``ReplicaCrash`` (injected process death): immediately dead —
          the event log in flight is lost, requests resume elsewhere
          from their last FETCHED token.
        * ``ReplicaHang``: suspect; the fetch is retried up to
          ``max_finish_retries`` times (bounded-attempt retry — a
          transient stall recovers, a wedge escalates to dead).
        * real fetch slower than ``segment_timeout_s``: suspect on the
          first, dead on the second consecutive timeout; a fast segment
          clears suspect back to healthy. The slow segment's results
          are still REAL (the fetch completed) and are applied either
          way."""
        attempts = 0
        while True:
            try:
                if self.fault_injector is not None:
                    self.fault_injector.on_finish(rep.idx, rep.segments)
                with _metrics.scoped_registry(rep.registry), \
                        _journal.rank_scope(rep.idx):
                    ev = rep.engine.finish_segment(h)
                    t_sync = _journal.now()
                    outcomes = self._stamp(rep, ev, t_sync)
                break
            except ReplicaCrash as e:
                self._kill_replica(rep, f"crash: {e}")
                return False
            except ReplicaHang as e:
                attempts += 1
                if rep.health == "healthy":
                    rep.set_health("suspect")
                    _flight.record("replica_suspect", replica=rep.idx,
                                   reason="hang")
                if attempts > self.max_finish_retries:
                    self._kill_replica(
                        rep, f"hang persisted through {attempts - 1} "
                             f"retries: {e}")
                    return False
                _metrics.counter("fleet.finish_retries").inc()
        rep.segments += 1
        self._finished_count += len(ev["finished"])
        # r17 (ISSUE 12): shadow pair collection + canary outcome feed
        # — host mirrors of the fetch above, outside the replica's
        # scoped registry (quality/canary metrics are the fleet view)
        if self.shadow is not None and ev["finished"]:
            self._collect_primary(rep, ev)
        if self.canary is not None and outcomes:
            grp = ("canary" if rep.idx == self.canary.replica
                   else "control")
            for kind, prio, lat in outcomes:
                self.canary.note_outcome(grp, kind, prio, lat)
        # r14 fleet monitor feed (outside the scoped registry: the SLO/
        # perf gauges are the FLEET view, not a replica's) — host
        # mirrors of the fetch above plus its dispatch→fetch span
        if self.slo_monitor is not None:
            for kind, prio, lat in outcomes:
                (self.slo_monitor.note_ttft if kind == "ttft"
                 else self.slo_monitor.note_e2e)(prio, lat)
            sp = ev.get("spec")
            if sp and sp.get("proposed"):
                # r17 accept-drift feed (ISSUE 12 satellite)
                self.slo_monitor.note_accept_rate(
                    sp["accepted"] / sp["proposed"])
            self.slo_monitor.end_segment()
        if self.perf_monitor is not None:
            self.perf_monitor.note_segment(ev["steps"],
                                           ev.get("tokens", 0),
                                           elapsed_s=t_sync - t_disp)
        # r25 (ISSUE 20): fleet-wide capacity feed — the autoscaler's
        # capacity_alert input. The pages the just-admitted requests
        # reserve are noted into the closing demand bucket, then a
        # fresh segment opens on the SERVING pool's free/reclaimable
        # sums (draining replicas are being emptied on purpose — their
        # pages are not capacity a scale decision should count on).
        # Every term is a host int evolving with the event stream, so
        # the alert levels replay bit-exactly.
        if self.capacity_monitor is not None:
            cm = self.capacity_monitor
            if ev["admitted"]:
                by_erid = {self._reqs[rid][1].rid: self._reqs[rid][1]
                           for rid in rep.rids}
                need = sum(
                    rep.engine.pager.pages_needed(
                        len(by_erid[erid].prompt)
                        + by_erid[erid].max_new_tokens - 1)
                    for erid in ev["admitted"])
                cm.note_admission(need, admitted=len(ev["admitted"]))
            cm.close_segment()
            free = sum(x.engine.pager.pages_free
                       for x in self._replicas
                       if x.lifecycle == "serving"
                       and x.health != "dead")
            reclaim = sum(
                x.prefix_cache.reclaimable_pages()
                for x in self._replicas
                if x.lifecycle == "serving"
                and x.health != "dead" and x.prefix_cache is not None)
            cm.begin_segment(free, reclaim)
        # r22 (ISSUE 17): post-segment hook — a no-op here; the
        # DisaggRouter's handoff sweep (prefill slots whose first token
        # just landed move to the decode pool) runs at exactly this
        # point, when the replica's engine is idle and the segment's
        # event log has been applied
        self._post_segment(rep, ev)
        if attempts and rep.health == "suspect":
            # a retried fetch came back: the hang was transient
            rep.set_health("healthy")
            _flight.record("replica_recovered", replica=rep.idx,
                           via="finish_retry")
        elapsed = t_sync - t_disp
        if (self.segment_timeout_s is not None
                and elapsed > self.segment_timeout_s):
            rep.timeouts += 1
            if rep.timeouts >= 2:
                self._kill_replica(
                    rep, f"two consecutive segment timeouts "
                         f"({elapsed:.3f}s > {self.segment_timeout_s}s)")
                return True                 # this segment's tokens are real
            rep.set_health("suspect")
            _flight.record("replica_suspect", replica=rep.idx,
                           reason="timeout", elapsed_s=round(elapsed, 4))
        elif self.segment_timeout_s is not None:
            rep.timeouts = 0
            if rep.health == "suspect":
                rep.set_health("healthy")
                _flight.record("replica_recovered", replica=rep.idx,
                               via="fast_segment")
        return True

    def _post_segment(self, rep: _Replica, ev: dict) -> None:
        """Hook invoked after a fetched segment's results are applied
        and the monitors are fed, while ``rep``'s engine is idle. The
        homogeneous fleet does nothing; the r22 ``DisaggRouter``
        overrides this with the prefill→decode handoff sweep."""

    def _pre_dispatch(self, rep: Optional[_Replica]) -> None:
        """Hook invoked immediately before each segment dispatch (and
        from the idle branch with ``rep=None``): the point where work
        deferred across loop turns must land on its target replicas.
        No-op here; the r23 ``DisaggRouter`` drains its coalesced
        handoff batch — one labelled tier sync covering every boundary
        crossed since the previous dispatch."""

    def _has_deferred_work(self) -> bool:
        """True while cross-replica work is parked awaiting the next
        ``_pre_dispatch`` (keeps the serve loop alive when every engine
        is momentarily idle but a deferred handoff still owes tokens).
        The homogeneous fleet defers nothing."""
        return False

    def _seg_steps_for(self, rep: _Replica) -> int:
        """Per-replica segment budget. Homogeneous fleets use one knob;
        the r22 DisaggRouter gives each pool its own (short prefill
        segments so first tokens hand off promptly, long decode
        segments so steady generation amortises the fetch) — which is
        also what keeps each pool's enumerated ladder to ITS OWN steps
        axis."""
        return self.seg_steps

    def _failover_target(self, survivors: List[_Replica],
                         req: Request) -> _Replica:
        """Which survivor a failed-over request requeues onto. The
        homogeneous fleet takes the least-loaded; the r22 DisaggRouter
        keeps pool discipline (token-bearing requests resume on the
        decode pool, untouched ones restart on prefill) so a failover
        never admits a program outside the target pool's envelope."""
        return min(survivors, key=lambda x: (x.load, x.idx))

    def _kill_replica(self, rep: _Replica, reason: str) -> None:
        """Declare ``rep`` dead and fail its whole in-flight world over
        to the survivors (the zero-loss contract): queued requests,
        live slots, and the picked set of a dispatched-but-lost segment
        all requeue onto the least-loaded healthy replica, each resuming
        from its last FETCHED token — the already-replayed event log is
        the request's durable state, and greedy decode regenerates the
        identical continuation, so untouched requests (and in practice
        migrated ones too) match the no-fault run token for token."""
        rep.set_health("dead")
        rep.timeouts = 0
        rep.probes = 0
        rep.dead_since = _journal.now()
        self.failovers += 1
        _metrics.counter("fleet.replica_deaths").inc()
        _flight.record("replica_dead", replica=rep.idx, reason=reason)
        orphans = rep.engine.abort()
        if rep.prefix_cache is not None:
            # cache page refs pin the dead pool; drop them so the reset
            # pool audits clean for re-admission
            rep.prefix_cache.reset()
        if not orphans:
            return
        survivors = [x for x in self._replicas
                     if x.health == "healthy"
                     and x.lifecycle == "serving"]
        if not survivors:
            raise RuntimeError(
                f"replica {rep.idx} died with {len(orphans)} in-flight "
                f"requests and no healthy survivor to requeue onto")
        orphan_ids = {id(q) for q in orphans}
        moved = sorted(((frid, req) for frid, (ridx, req)
                        in self._reqs.items()
                        if ridx == rep.idx and id(req) in orphan_ids),
                       key=lambda t: t[0])
        for frid, req in moved:
            req.requeues += 1
            if req.requeues > self.max_requeues:
                raise RuntimeError(
                    f"request {frid} exceeded {self.max_requeues} "
                    f"failover requeues — replicas are dying faster "
                    f"than the fleet can serve")
            tgt = self._failover_target(survivors, req)
            if len(req.prompt) + len(req.tokens) > max(tgt.engine.buckets):
                # the grown resume prompt no longer fits an admit
                # window: rewind and regenerate — greedy decode
                # reproduces the identical stream from scratch
                req.tokens = []
            req.rid = tgt.engine._next_rid   # fresh engine-local rid
            tgt.engine._next_rid += 1
            tgt.engine._queue.append(req)
            self._reqs[frid] = (tgt.idx, req)
            tgt.rids.append(frid)
            rep.rids.remove(frid)
            self.requeued += 1
            _metrics.counter("fleet.failover_requeued").inc()
            _flight.record("failover_requeue", rid=frid, src=rep.idx,
                           dst=tgt.idx, tokens_kept=len(req.tokens))

    def _probe_dead(self) -> None:
        """Re-admission probing: after ``probe_after_s`` a dead replica
        is probed (through the injector when one is installed — models
        asking the restarted process for a health check); success puts
        it back in the healthy rotation, failure re-arms the backoff."""
        for rep in self._replicas:
            if rep.health != "dead":
                continue
            if _journal.now() - rep.dead_since < self.probe_after_s:
                continue
            rep.probes += 1
            ok = (self.fault_injector.on_probe(rep.idx, rep.probes)
                  if self.fault_injector is not None else True)
            _metrics.counter("fleet.probes").inc()
            _journal.record("probe", replica=rep.idx,
                            probe_no=rep.probes, recovered=ok)
            if ok:
                rep.timeouts = 0
                rep.set_health("healthy")
                _flight.record("replica_recovered", replica=rep.idx,
                               via="probe", probes=rep.probes)
            else:
                rep.dead_since = _journal.now()

    # --- elastic lifecycle (r25 tentpole, ISSUE 20) -----------------------
    def _autoscale(self, now: float, final: bool = False) -> None:
        """One control-loop turn for every attached policy, on the
        loop's already-read clock (zero extra clock reads)."""
        for asc in self.autoscalers:
            asc.step(now, final=final)

    def _warmup_envelope_for(self, rep: _Replica):
        """The envelope a replica activated mid-serve compiles. None =
        the engine's default envelope; the r22 DisaggRouter returns the
        replica's POOL envelope so a warmed standby joins its pool's
        (smaller) r20 ladder."""
        return None

    def _activate_replica(self, rep: _Replica) -> dict:
        """Bring an offline standby into the serving rotation,
        PRE-PAYING its warmup: the full program ladder compiles (or —
        the §3o fleet contract — re-registers against
        ``serving._SHARED_PROGS``, microseconds per key) BEFORE the
        lifecycle flips to ``serving``, so a scale-up can never cause a
        mid-serve compile. The two ``journal.now()`` reads bracketing
        the warmup are recorded clock reads — replay feeds them back,
        so the measured cost rides the journal and the decision stream
        stays bit-exact."""
        assert rep.lifecycle == "offline", rep.lifecycle
        rep.lifecycle = "warming"
        env = self._warmup_envelope_for(rep)
        t0 = _journal.now()
        with _metrics.scoped_registry(rep.registry), \
                _journal.rank_scope(rep.idx):
            fams = rep.engine.aot_warmup(env,
                                         prefix_cache=rep.prefix_cache)
        warm_s = _journal.now() - t0
        rep.lifecycle = "serving"
        rep.warmed_s = warm_s
        _flight.record("replica_warmed", replica=rep.idx,
                       seconds=round(warm_s, 6),
                       keys=sum(d["keys"] for d in fams.values()))
        return {"seconds": warm_s, "families": fams}

    def _begin_drain(self, rep: _Replica, now: float) -> dict:
        """Start a polite scale-down of ``rep``: stop admitting (the
        lifecycle flip removes it from ``_dispatch_candidates``),
        migrate its hot prefixes to the survivors' host tiers
        (directory-aware order), and requeue its QUEUED requests — the
        r13 failover machinery run ON PURPOSE, not under a death. Live
        slots finish in place; ``_finalize_drain`` runs from the policy
        step once the replica empties."""
        assert rep.lifecycle == "serving", rep.lifecycle
        rep.lifecycle = "draining"
        rep.drain = {"since": now, "requeued": 0,
                     "prefixes_migrated": 0, "pages_migrated": 0}
        survivors = [x for x in self._replicas
                     if x is not rep and x.lifecycle == "serving"
                     and x.health == "healthy"]
        self._drain_prefixes(rep, survivors)
        self._drain_requeue(rep, survivors)
        return rep.drain

    def _drain_prefixes(self, rep: _Replica,
                        survivors: List[_Replica]) -> None:
        """Migrate the draining replica's cached prefixes to survivor
        host tiers through the r19 replica-portable seam
        (``export_host`` → ``import_host``) so repeat traffic keeps
        hitting after the replica goes away. With a directory attached
        the HOT prefixes move first (touch-recency order off the
        directory's placements for this replica); blind fleets move in
        cache insertion order. Each move is a journaled
        ``tier_migrate`` decision — the drain's data motion replays."""
        pc = rep.prefix_cache
        if (pc is None or not hasattr(pc, "export_host")
                or getattr(pc, "host_tier", None) is None):
            return
        targets = [x for x in survivors
                   if x.prefix_cache is not None
                   and getattr(x.prefix_cache, "host_tier", None)
                   is not None]
        if not targets:
            return
        if self.directory is not None:
            keys = sorted(
                (k for k, owners in self.directory._owners.items()
                 if rep.idx in owners),
                key=lambda k: -self.directory._owners[k][rep.idx]["touch"])
            seen = set(keys)
            keys += [k for k in pc._entries if k not in seen]
        else:
            keys = list(pc._entries)
        for key in keys:
            exp = pc.export_host(key)
            if exp is None:
                continue        # never finished staging: can't move
            dst = min(targets, key=lambda x: (x.load, x.idx))
            planes = {p: exp[p] for p in exp
                      if p not in ("tokens", "pages")}
            if not dst.prefix_cache.import_host(exp["tokens"], planes):
                continue        # survivor already holds it
            n = int(exp["pages"])
            nbytes = n * dst.prefix_cache.host_tier.page_bytes()
            rep.drain["prefixes_migrated"] += 1
            rep.drain["pages_migrated"] += n
            self.tier_migrations += 1
            _metrics.counter("fleet.tier_migrations").inc()
            _flight.record("tier_migrate", rid=None, src=rep.idx,
                           dst=dst.idx, pages=n, bytes=nbytes,
                           rows=int(len(exp["tokens"])))

    def _drain_requeue(self, rep: _Replica,
                       survivors: List[_Replica]) -> None:
        """Requeue the draining replica's QUEUED (never admitted)
        requests onto survivors — the ``_kill_replica`` requeue
        sequence (fresh engine-local rid, stable fleet rid). The
        zero-strand contract: nothing is dropped; admitted slots keep
        their pages and finish in place."""
        queued = list(rep.engine._queue)
        if not queued:
            return
        if not survivors:
            raise RuntimeError(
                f"draining replica {rep.idx} holds {len(queued)} queued "
                f"requests with no serving survivor to requeue onto")
        ids = {id(q) for q in queued}
        rep.engine._queue.clear()
        moved = sorted(((frid, req) for frid, (ridx, req)
                        in self._reqs.items()
                        if ridx == rep.idx and id(req) in ids),
                       key=lambda t: t[0])
        for frid, req in moved:
            req.requeues += 1
            if req.requeues > self.max_requeues:
                raise RuntimeError(
                    f"request {frid} exceeded {self.max_requeues} "
                    f"requeues during drain")
            tgt = self._failover_target(survivors, req)
            if (len(req.prompt) + len(req.tokens)
                    > max(tgt.engine.buckets)):
                req.tokens = []
            req.rid = tgt.engine._next_rid
            tgt.engine._next_rid += 1
            tgt.engine._queue.append(req)
            self._reqs[frid] = (tgt.idx, req)
            tgt.rids.append(frid)
            rep.rids.remove(frid)
            self.requeued += 1
            rep.drain["requeued"] += 1
            _metrics.counter("fleet.failover_requeued").inc()
            _flight.record("failover_requeue", rid=frid, src=rep.idx,
                           dst=tgt.idx, tokens_kept=len(req.tokens))

    def _finalize_drain(self, rep: _Replica) -> dict:
        """The drain's last act, once the replica is empty: release its
        cache pages (evict listeners clear any directory placements)
        and park it offline. Returns the drain ledger."""
        assert not rep.busy, f"finalizing a busy replica {rep.idx}"
        if rep.prefix_cache is not None:
            rep.prefix_cache.reset()
        rep.lifecycle = "offline"
        info = rep.drain or {}
        rep.last_drain = info
        rep.drain = None
        return info

    def _stamp(self, r: _Replica, ev: dict, t_sync: float) -> List[tuple]:
        """Per-request lifecycle stamping at the sync that surfaced each
        event — identical rules to ``OnlineScheduler.serve``, recorded
        into the REPLICA's registry (the scoped context is active).
        Returns the ``(kind, priority, latency_s)`` outcomes so the
        caller can feed the fleet-level SLO monitor OUTSIDE the scoped
        registry (its gauges belong to the process/fleet view)."""
        by_erid = {self._reqs[rid][1].rid: (rid, self._reqs[rid][1])
                   for rid in r.rids}
        m_ttft = _metrics.histogram("serving.ttft_s")
        m_e2e = _metrics.histogram("serving.e2e_s")
        m_qw = _metrics.histogram("serving.queue_wait_s")
        outcomes: List[tuple] = []
        for erid in ev["admitted"]:
            frid, req = by_erid[erid]
            _journal.record("admit", rid=frid, replica=r.idx, erid=erid,
                            prefix_hit_len=req.prefix_hit_len,
                            resumed=bool(req.preemptions or req.requeues),
                            tokens_done=len(req.tokens))
        for erid in ev["first_tokens"]:
            frid, req = by_erid[erid]
            if req.first_token_time:
                # a rewound failover request re-emits its first token;
                # the client saw the original — the TTFT clock stands
                continue
            req.first_token_time = t_sync
            m_ttft.observe(t_sync - req.arrival_time)
            m_qw.observe(req.admit_time - req.arrival_time)
            outcomes.append(("ttft", req.priority,
                             t_sync - req.arrival_time))
            _journal.record("first_token", rid=frid, replica=r.idx,
                            ttft_s=t_sync - req.arrival_time)
        for erid in ev["finished"]:
            frid, req = by_erid[erid]
            req.finish_time = t_sync
            m_e2e.observe(t_sync - req.arrival_time)
            outcomes.append(("e2e", req.priority,
                             t_sync - req.arrival_time))
            # full emitted token stream = the replay's identity oracle
            _journal.record("finish", rid=frid, replica=r.idx,
                            tokens=req.tokens, n_tokens=len(req.tokens),
                            e2e_s=t_sync - req.arrival_time,
                            requeues=req.requeues,
                            spec_proposed=req.spec_proposed,
                            spec_accepted=req.spec_accepted)
        _metrics.gauge("fleet.replica_queue_depth").set(r.queue_depth)
        return outcomes

    def _journal_header(self, arrivals) -> dict:
        """The fleet serve's replay contract (r16, ISSUE 11): router
        knobs, every replica's rebuildable geometry + rid offset,
        per-replica prefix-cache shapes, the fault injector's LIVE
        schedule (fired crashes popped, seeded draws positioned), and
        the full trace."""
        return {
            "driver": "fleet",
            "fleet": {"max_queue": self.max_queue,
                      "seg_steps": self.seg_steps,
                      "affinity_block": self.affinity_block,
                      "segment_timeout_s": self.segment_timeout_s,
                      "max_finish_retries": self.max_finish_retries,
                      "max_requeues": self.max_requeues,
                      "probe_after_s": self.probe_after_s,
                      "directory": self.directory is not None,
                      "next_rid": self._next_rid},
            "engines": [_journal.describe_engine(r.engine)
                        for r in self._replicas],
            "prefix_caches": [_journal.describe_prefix_cache(
                r.prefix_cache) for r in self._replicas],
            "fault": (self.fault_injector.describe()
                      if self.fault_injector is not None else None),
            # r17: the canary is a DECIDER (routing input) and rides the
            # header for replay rebuild; the shadow is an OBSERVER —
            # described for the record, never rebuilt by replay
            "canary": (self.canary.describe()
                       if self.canary is not None else None),
            "shadow": (None if self.shadow is None else {
                "sample_p": self.shadow.sample_p,
                "seed": self.shadow.seed,
                "engine": _journal.describe_engine(self.shadow.engine)}),
            "llama": _journal.describe_config(
                self._replicas[0].engine.cfg),
            "monitors": {"slo": self.slo_monitor is not None,
                         "perf": self.perf_monitor is not None},
            # r25 (ISSUE 20): the autoscaler is a DECIDER — its full
            # config AND its input monitors' configs ride the header so
            # replay rebuilds the identical control loop (absent when
            # no policy is attached: pre-r25 journals replay unchanged)
            "autoscaler": ({
                "policies": [a.describe() for a in self.autoscalers],
                "slo": (self.slo_monitor.describe()
                        if self.slo_monitor is not None else None),
                "capacity": (self.capacity_monitor.describe()
                             if self.capacity_monitor is not None
                             else None),
            } if self.autoscalers else None),
            "telemetry_enabled": _metrics.enabled(),
            "trace": _journal.describe_arrivals(arrivals),
        }

    # --- results / lifecycle ---------------------------------------------
    def results(self) -> Dict[int, List[int]]:
        """Fleet rid -> generated tokens (truncated at max_new_tokens /
        first EOS, like ``ServingEngine.run``)."""
        for r in self._replicas:
            r.engine.collect_finished()
        return {rid: req.tokens for rid, (_, req) in self._reqs.items()}

    def assignment(self) -> List[List[int]]:
        """Per-replica fleet rids in assignment order (the determinism
        contract's observable)."""
        return [list(r.rids) for r in self._replicas]

    def reset(self) -> None:
        """Warm-run isolation: reset every replica's slots, cache and
        registry, and zero fleet counters (the fleet analog of
        ``OnlineScheduler``'s warm handling)."""
        for r in self._replicas:
            r.engine.reset_slots()
            if r.prefix_cache is not None:
                r.prefix_cache.reset()
            r.registry.reset()
            r.backpressure_events = 0
            r.dispatches = {"affinity": 0, "least_loaded": 0,
                            "canary": 0, "directory": 0}
            r.segments = 0
            r.rids = []
            r.health = "healthy"
            r.timeouts = 0
            r.probes = 0
            r.dead_since = 0.0
            r.lifecycle = "serving"
            r.drain = None
            r.last_drain = None
            r.warmed_s = None
        self.backpressure_events = 0
        self.failovers = 0
        self.requeued = 0
        self.tier_migrations = 0
        if self.directory is not None:
            # the cache resets above already drained it through the
            # evict listeners; zero the counters too
            self.directory.reset()
        self.last_retry_after_s = None
        self._finished_count = 0
        self._reqs.clear()
        self._next_rid = 0
        if self.slo_monitor is not None:
            self.slo_monitor.reset()
        if self.perf_monitor is not None:
            # cut (and discard) the warm interval; the self-pinned tick
            # budget survives — the warm baseline is the reference
            self.perf_monitor.end_interval()
        if self.shadow is not None:
            self.shadow.reset()
        if self.canary is not None:
            self.canary.reset()
        if self.capacity_monitor is not None:
            self.capacity_monitor.reset()
        # AFTER the per-replica "serving" default above: each policy's
        # reset re-applies its initial lifecycles (standbys go back
        # offline) and zeroes its decision ledger
        for asc in self.autoscalers:
            asc.reset()

    def leak_report(self) -> List[str]:
        """Aggregated page-leak audit across replicas: with no live
        requests, every replica's pool must be fully returned
        modulo its OWN cache's held pages (the fleet-isolation audit —
        a cache can only pin pages of the pager it wraps)."""
        bad: List[str] = []
        for r in self._replicas:
            pc = r.prefix_cache
            # distinct pages, not ref counts: entries sharing a
            # prefix hold its pages once physically (r19 fix)
            held = pc.physical_pages_held() if pc is not None else 0
            for msg in r.engine.pager.leak_report(expected_held=held):
                bad.append(f"replica {r.idx}: {msg}")
        return bad

    def merged_telemetry(self, log_dir: str) -> dict:
        """Write one rank-tagged snapshot per replica into ``log_dir``
        and reduce them with the existing multi-process machinery
        (``metrics.merge_log_dir``) — the fleet report an operator
        scrapes: counters summed across replicas, gauges kept per-rank
        with min/max/sum."""
        for r in self._replicas:
            _metrics.write_snapshot(log_dir, rank=r.idx,
                                    registry=r.registry)
        return _metrics.merge_log_dir(log_dir)
