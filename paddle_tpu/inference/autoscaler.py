"""Elastic fleet autoscaling as an observable control loop (r25 tentpole,
ISSUE 20 — ROADMAP item 3, SCALING §3t).

r14–r24 built every input this loop needs; this module closes them into
decisions:

* **Scale-up signals.** Queue pressure (summed intake depth over the
  replicas currently taking traffic), the r14 error-budget burn-rate
  level (``SLOMonitor.worst_level()``), and the r18 ``capacity_alert``
  level (``CapacityMonitor.level``, fed fleet-wide by the router at
  every segment finish). Any firing signal asks for one more replica.
* **Chip-fit before warmup.** A candidate must PROVE it fits before it
  is warmed: ``analysis.memory.chip_fit`` prices the §3s static HBM
  envelope (weights + provisioned pool + peak transient) against the
  configured per-replica budget — a refusal is a first-class journaled
  decision with the verdict attached, and the unfit candidate is never
  retried.
* **Warmup before traffic.** The §3o measured scale-up cost: the new
  replica's FULL enumerated program space is AOT-compiled
  (``ServingEngine.aot_warmup``) before it enters the dispatch
  candidate set. Identical-geometry replicas share compiles through
  ``serving._SHARED_PROGS``, so a standby's warmup executes
  already-compiled programs — zero mid-serve backend compiles
  fleet-wide (``analysis.recompile.enforce_zero_compiles`` is the test
  budget).
* **Polite drain on scale-down.** The victim stops admitting (its
  lifecycle leaves the dispatch candidate set), its QUEUED requests
  requeue onto survivors (the r13 failover machinery run on purpose —
  same journaled ``failover_requeue`` records), its live slots finish
  in place (zero stranded requests), and — *directory-aware* — its hot
  prefixes migrate out through the r19 ``CacheDirectory``/host-tier
  seam (``export_host`` → survivor ``import_host``, hottest placement
  first) so survivors never cold-start the drained replica's working
  set.
* **Every decision is an observability object.** A ``scale_decision``
  journal record (joined to ``DECISION_KINDS``) carries the complete
  input vector — burn rates, capacity level, queue depths, per-replica
  ``pages_free``/health/lifecycle, the chip-fit verdict and the static
  warmup-cost estimate — plus the chosen action and a human-readable
  reason. All controller clock reads route through ``journal.now()``,
  so the entire elastic episode (1x→4x→1x) replays bit-exactly via
  ``observability.replay`` (the journal header carries this policy's
  config and the monitors' configs; replay rebuilds all three).

Determinism: every input is a host int/float evolving with the event
stream or a fed clock value; thresholds and hysteresis counters are
segment-counted. The same journal therefore drives the same decisions.

Lifecycle state machine (per replica, orthogonal to r13 health)::

    offline --scale_up(chip_fit ok)--> warming --aot_warmup--> serving
    serving --scale_down--> draining --(not busy: 0 live, 0 queued)-->
    offline

``install(asc)`` / ``uninstall()`` attach an UNBOUND policy ambiently on
``serving.SEGMENT_HOOKS`` (pure host counting — how ``python -m
paddle_tpu.analysis --gate --autoscale on`` proves the controller adds
zero hazards to the canonical programs).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from ..observability import journal as _journal
from ..observability import metrics as _metrics

__all__ = ["Autoscaler", "install", "uninstall"]

_LEVELS_FIRING = ("warning", "page")


class Autoscaler:
    """One scaling policy over a :class:`~paddle_tpu.inference.fleet
    .FleetRouter`'s replicas (``pool=None``) or over one pool of a
    ``DisaggRouter`` (``pool="prefill"``/``"decode"`` — attach one
    policy per pool; each sees only its pool's replicas and signals).

    ``initial_replicas`` of the managed set start ``serving``; the rest
    start ``offline`` as warm standbys (engines built, weights
    resident, programs shared — the §3o model where a scale-up pays
    warmup, not a rebuild). ``hbm_bytes`` enables the chip-fit proof;
    ``None`` skips it (CI fleets on a CPU host have no HBM budget to
    prove against).
    """

    def __init__(self, *, min_replicas: int = 1,
                 max_replicas: Optional[int] = None,
                 initial_replicas: Optional[int] = None,
                 pool: Optional[str] = None,
                 queue_high: int = 8, queue_low: int = 0,
                 scale_on_slo: bool = True,
                 scale_on_capacity: bool = True,
                 scale_down_after: int = 3, cooldown_s: float = 0.0,
                 hbm_bytes: Optional[int] = None,
                 weights_bytes: Optional[int] = None,
                 transient_bytes: Optional[int] = None):
        if min_replicas < 1:
            raise ValueError(f"min_replicas must be >= 1, got "
                             f"{min_replicas}")
        if max_replicas is not None and max_replicas < min_replicas:
            raise ValueError(f"max_replicas {max_replicas} < "
                             f"min_replicas {min_replicas}")
        if queue_low > queue_high:
            raise ValueError(f"queue_low {queue_low} > queue_high "
                             f"{queue_high}")
        self.min_replicas = int(min_replicas)
        self.max_replicas = (int(max_replicas)
                             if max_replicas is not None else None)
        self.initial_replicas = (int(initial_replicas)
                                 if initial_replicas is not None else None)
        self.pool = pool
        self.queue_high = int(queue_high)
        self.queue_low = int(queue_low)
        self.scale_on_slo = bool(scale_on_slo)
        self.scale_on_capacity = bool(scale_on_capacity)
        self.scale_down_after = int(scale_down_after)
        self.cooldown_s = float(cooldown_s)
        self.hbm_bytes = int(hbm_bytes) if hbm_bytes is not None else None
        self.weights_bytes = (int(weights_bytes)
                              if weights_bytes is not None else None)
        self.transient_bytes = (int(transient_bytes)
                                if transient_bytes is not None else None)
        self.fleet = None
        self.desired = 0
        self._zero_counters()

    def _zero_counters(self) -> None:
        self.scale_ups = 0
        self.scale_downs = 0
        self.refusals = 0
        self.drains_completed = 0
        self.warmup_s_total = 0.0
        self.segments_observed = 0          # ambient (unbound) mode
        self.last_decision: Optional[dict] = None
        self.decision_log: List[dict] = []
        self._unfit: set = set()
        self._calm_streak = 0
        self._last_action_t: Optional[float] = None

    # --- attachment -------------------------------------------------------
    def bind(self, fleet) -> None:
        """Attach to a router (called by ``FleetRouter.__init__``):
        validate the managed set and apply the initial lifecycles."""
        self.fleet = fleet
        reps = self._managed()
        if not reps:
            raise ValueError(
                f"autoscaler (pool={self.pool!r}) manages no replicas")
        if self.max_replicas is None:
            self.max_replicas = len(reps)
        if self.max_replicas > len(reps):
            raise ValueError(
                f"max_replicas {self.max_replicas} exceeds the "
                f"{len(reps)} built replicas (the elastic model is warm "
                f"standbys, not engine construction mid-serve)")
        if self.initial_replicas is None:
            self.initial_replicas = self.min_replicas
        if not (self.min_replicas <= self.initial_replicas
                <= self.max_replicas):
            raise ValueError(
                f"initial_replicas {self.initial_replicas} outside "
                f"[{self.min_replicas}, {self.max_replicas}]")
        self._apply_initial()

    def _apply_initial(self) -> None:
        self.desired = self.initial_replicas
        for i, r in enumerate(self._managed()):
            r.lifecycle = ("serving" if i < self.initial_replicas
                           else "offline")

    def reset(self) -> None:
        """Warm-run isolation (fleet ``reset()`` calls this): zero the
        counters and reapply the initial lifecycles."""
        self._zero_counters()
        if self.fleet is not None:
            self._apply_initial()

    def describe(self) -> dict:
        """Rebuildable config snapshot for the journal header (replay
        reconstructs the policy — and its initial lifecycles — from
        exactly this)."""
        return {"min_replicas": self.min_replicas,
                "max_replicas": self.max_replicas,
                "initial_replicas": self.initial_replicas,
                "pool": self.pool,
                "queue_high": self.queue_high,
                "queue_low": self.queue_low,
                "scale_on_slo": self.scale_on_slo,
                "scale_on_capacity": self.scale_on_capacity,
                "scale_down_after": self.scale_down_after,
                "cooldown_s": self.cooldown_s,
                "hbm_bytes": self.hbm_bytes,
                "weights_bytes": self.weights_bytes,
                "transient_bytes": self.transient_bytes}

    @classmethod
    def from_description(cls, d: dict) -> "Autoscaler":
        return cls(**d)

    # --- state views ------------------------------------------------------
    def _managed(self) -> list:
        reps = self.fleet._replicas
        if self.pool is not None:
            reps = [r for r in reps if r.pool == self.pool]
        return reps

    @property
    def actual(self) -> int:
        """Replicas currently taking traffic."""
        if self.fleet is None:
            return 0
        return sum(1 for r in self._managed() if r.lifecycle == "serving")

    @property
    def drain_inflight(self) -> int:
        if self.fleet is None:
            return 0
        return sum(1 for r in self._managed()
                   if r.lifecycle == "draining")

    def _signals(self) -> dict:
        """The cheap per-turn scalars the decision rules compare."""
        reps = self._managed()
        serving = [r for r in reps
                   if r.lifecycle == "serving" and r.health != "dead"]
        queue_sum = sum(r.queue_depth for r in serving)
        slo_level, burn = "ok", None
        mon = self.fleet.slo_monitor
        if mon is not None:
            slo_level = mon.worst_level()
            states = list(mon._classes.values()) + list(mon._pools.values())
            burn = max((max(cs.burn_fast, cs.burn_slow) for cs in states),
                       default=0.0)
        cmon = getattr(self.fleet, "capacity_monitor", None)
        cap_level = cmon.level if cmon is not None else "ok"
        return {"queue_sum": queue_sum, "n_serving": len(serving),
                "slo_level": slo_level,
                "burn": round(burn, 6) if burn is not None else None,
                "capacity_level": cap_level}

    def _snapshot(self, sig: dict) -> dict:
        """The full input vector a ``scale_decision`` record carries —
        built only when a decision actually fires."""
        reps = self._managed()
        return dict(sig,
                    queue_depths={str(r.idx): r.queue_depth for r in reps},
                    pages_free={str(r.idx): r.engine.pager.pages_free
                                for r in reps},
                    health={str(r.idx): r.health for r in reps},
                    lifecycle={str(r.idx): r.lifecycle for r in reps},
                    backpressure=self.fleet.backpressure_events)

    # --- the control step (one call per serve-loop turn) ------------------
    def step(self, now: float, final: bool = False) -> None:
        """Evaluate once on the loop's already-read decision clock.
        ``final=True`` (after the serve loop) only finalizes drains —
        the trace is over, no new capacity decisions make sense."""
        for r in self._managed():
            if r.lifecycle == "draining" and not r.busy:
                self._finish_drain(r, now)
        sig = self._signals()
        self._gauges(sig)
        if final:
            return
        up = []
        if sig["queue_sum"] >= self.queue_high:
            up.append(f"queue depth {sig['queue_sum']} >= "
                      f"{self.queue_high}")
        if self.scale_on_slo and sig["slo_level"] in _LEVELS_FIRING:
            up.append(f"slo burn {sig['slo_level']} "
                      f"(burn={sig['burn']})")
        if self.scale_on_capacity and sig["capacity_level"] in \
                _LEVELS_FIRING:
            up.append(f"capacity {sig['capacity_level']}")
        calm = (not up and sig["queue_sum"] <= self.queue_low
                and sig["slo_level"] == "ok"
                and sig["capacity_level"] == "ok")
        self._calm_streak = self._calm_streak + 1 if calm else 0
        if (self._last_action_t is not None
                and now - self._last_action_t < self.cooldown_s):
            return
        if up:
            self._scale_up(now, sig, "; ".join(up))
        elif (self._calm_streak >= self.scale_down_after
              and sig["n_serving"] > self.min_replicas):
            self._scale_down(now, sig)

    def _gauges(self, sig: dict) -> None:
        sfx = f".{self.pool}" if self.pool else ""
        _metrics.gauge(f"autoscaler.desired{sfx}").set(self.desired)
        _metrics.gauge(f"autoscaler.actual{sfx}").set(sig["n_serving"])
        _metrics.gauge(f"autoscaler.drain_inflight{sfx}").set(
            self.drain_inflight)

    # --- actions ----------------------------------------------------------
    def _scale_up(self, now: float, sig: dict, why: str) -> None:
        cands = [r for r in self._managed()
                 if r.lifecycle == "offline" and r.health != "dead"
                 and r.idx not in self._unfit]
        active = sum(1 for r in self._managed()
                     if r.lifecycle in ("serving", "warming"))
        if not cands or active >= self.max_replicas:
            return
        cand = min(cands, key=lambda r: r.idx)
        fit = self._chip_fit(cand)
        if fit is not None and not fit["fits"]:
            self._unfit.add(cand.idx)
            self.refusals += 1
            self._decide(
                now, "refuse", cand, sig,
                reason=f"chip_fit refused replica {cand.idx}: envelope "
                       f"{fit['envelope_bytes']} B > hbm "
                       f"{fit['hbm_bytes']} B ({why})",
                fit=fit)
            self._last_action_t = now
            return
        self.desired = min(self.desired + 1, self.max_replicas)
        self.scale_ups += 1
        sfx = f".{self.pool}" if self.pool else ""
        _metrics.counter(f"autoscaler.scale_ups{sfx}").inc()
        self._decide(now, "scale_up", cand, sig,
                     reason=f"add replica {cand.idx}: {why}",
                     fit=fit, warmup=self._warmup_estimate(cand))
        warm = self.fleet._activate_replica(cand)
        self.warmup_s_total += warm["seconds"]
        self._last_action_t = now
        self._calm_streak = 0

    def _scale_down(self, now: float, sig: dict) -> None:
        serving = [r for r in self._managed()
                   if r.lifecycle == "serving" and r.health == "healthy"]
        if len(serving) <= max(self.min_replicas, 1):
            return
        can = getattr(self.fleet, "canary", None)
        if can is not None:
            # the canary replica carries the comparison population —
            # never the drain victim
            serving = [r for r in serving if r.idx != can.replica]
            if len(serving) < 2:
                return
        # least-loaded victim (fewest requeues to pay), ties to the
        # HIGHEST index — scale-downs peel standbys off in reverse
        # scale-up order
        victim = min(serving, key=lambda r: (r.load, -r.idx))
        self.desired = max(self.desired - 1, self.min_replicas)
        self.scale_downs += 1
        sfx = f".{self.pool}" if self.pool else ""
        _metrics.counter(f"autoscaler.scale_downs{sfx}").inc()
        self._decide(
            now, "scale_down", victim, sig,
            reason=f"drain replica {victim.idx}: calm for "
                   f"{self._calm_streak} evaluations (queue "
                   f"{sig['queue_sum']} <= {self.queue_low}, slo ok, "
                   f"capacity ok), {len(serving)} serving > min "
                   f"{self.min_replicas}")
        self.fleet._begin_drain(victim, now)
        self._last_action_t = now
        self._calm_streak = 0

    def _finish_drain(self, rep, now: float) -> None:
        info = self.fleet._finalize_drain(rep)
        self.drains_completed += 1
        self._decide(
            now, "drain_complete", rep, self._signals(),
            reason=f"replica {rep.idx} drained: "
                   f"{info.get('requeued', 0)} requeued, "
                   f"{info.get('prefixes_migrated', 0)} prefixes "
                   f"migrated, 0 stranded")

    def _decide(self, now: float, action: str, rep, sig: dict, *,
                reason: str, fit: Optional[dict] = None,
                warmup: Optional[dict] = None) -> None:
        rec = {"action": action, "pool": self.pool,
               "replica": rep.idx if rep is not None else None,
               "reason": reason, "desired": self.desired,
               "actual": self.actual, "inputs": self._snapshot(sig),
               "fit": fit, "warmup": warmup}
        _journal.record("scale_decision", **rec)
        self.last_decision = dict(rec, t=now)
        self.decision_log.append(self.last_decision)

    # --- chip fit + warmup estimate ---------------------------------------
    def _chip_fit(self, rep) -> Optional[dict]:
        """§3s static proof the candidate fits its HBM budget. ``None``
        when no budget is configured (fit checking off)."""
        if self.hbm_bytes is None:
            return None
        from ..analysis import memory as _memory

        fit = _memory.chip_fit(
            rep.engine.cfg,
            params=(rep.engine.params
                    if self.weights_bytes is None else None),
            pool=rep.engine.pager, hbm_bytes=self.hbm_bytes,
            weights_bytes=self.weights_bytes,
            transient_bytes=self.transient_bytes)
        return {k: fit[k] for k in
                ("fits", "hbm_bytes", "weights_bytes", "pool_bytes",
                 "transient_bytes", "envelope_bytes", "headroom_bytes",
                 "headroom_pages", "utilization")}

    def _warmup_estimate(self, rep) -> dict:
        """The static §3o cost estimate a scale-up decision carries:
        how many enumerated program keys the warmup will touch
        (deterministic — a pure function of geometry + envelope; the
        measured seconds ride the non-decision ``replica_warmed``
        flight record because wall time may legitimately differ on a
        replaying machine)."""
        env = self.fleet._warmup_envelope_for(rep)
        space = rep.engine.program_space(env)
        return {"keys": sum(len(v) for v in space.values()),
                "families": sorted(space)}

    # --- ambient mode + ops surface ---------------------------------------
    def observe_segment(self) -> None:
        self.segments_observed += 1

    def report(self) -> dict:
        """The ``/autoscaler`` endpoint section for this policy."""
        out = {"pool": self.pool, "desired": self.desired,
               "actual": self.actual,
               "drain_inflight": self.drain_inflight,
               "min_replicas": self.min_replicas,
               "max_replicas": self.max_replicas,
               "scale_ups": self.scale_ups,
               "scale_downs": self.scale_downs,
               "refusals": self.refusals,
               "drains_completed": self.drains_completed,
               "warmup_s_total": round(self.warmup_s_total, 6),
               "segments_observed": self.segments_observed,
               "last_decision": self.last_decision,
               "decisions": len(self.decision_log)}
        if self.fleet is not None:
            out["lifecycles"] = {str(r.idx): r.lifecycle
                                 for r in self._managed()}
            out["drains"] = {
                str(r.idx): dict(r.drain,
                                 requests_remaining=r.load)
                for r in self._managed()
                if r.lifecycle == "draining" and r.drain is not None}
        return out


# ---------------------------------------------------------------------------
# Ambient attachment (gate bit-identity): an UNBOUND policy observing
# every engine segment through ``serving.SEGMENT_HOOKS`` — pure host
# counting, zero decisions, zero hazards. Mirrors slo/capacity.install.
# ---------------------------------------------------------------------------

_INSTALLED: List[tuple] = []


def install(asc: Autoscaler) -> None:
    """Attach ``asc`` process-wide as a segment observer. Idempotent
    per policy; pair with :func:`uninstall`."""
    from . import serving as _serving

    for a, _ in _INSTALLED:
        if a is asc:
            return

    def hook(steps: int, new_tokens: int, finished: int) -> None:
        asc.observe_segment()

    _serving.SEGMENT_HOOKS.append(hook)
    _INSTALLED.append((asc, hook))


def uninstall(asc: Optional[Autoscaler] = None) -> None:
    """Detach ``asc`` (or every installed policy when ``None``)."""
    from . import serving as _serving

    keep = []
    for a, hook in _INSTALLED:
        if asc is None or a is asc:
            if hook in _serving.SEGMENT_HOOKS:
                _serving.SEGMENT_HOOKS.remove(hook)
        else:
            keep.append((a, hook))
    _INSTALLED[:] = keep
