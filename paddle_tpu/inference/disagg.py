"""Disaggregated prefill/decode serving (r22 tentpole, ISSUE 17):
specialized engine pools with an audited KV page-set handoff.

Production fleets separate prefill (compute-bound, bursty) from decode
(HBM-bound, steady). Co-residency is exactly why r13 needed chunked
prefill: a long prompt's prefill stalls the decode batch sharing its
engine, and TBT (time between tokens) degrades with prompt-mix, not
load. ``DisaggRouter`` splits the fleet into a prefill pool and a
decode pool instead:

* **Fresh arrivals route only to the prefill pool** (the
  ``_dispatch_candidates`` hook narrows affinity / least-loaded /
  directory steering to prefill replicas). A prefill replica admits
  the prompt, prefills it, and emits the first token — TTFT is the
  prefill pool's owned SLO.
* **The handoff** (``_post_segment`` sweep): after a prefill replica's
  segment fetch lands, every live slot whose first token is out is
  preempted (``preempt_slot`` parks the page-aligned prefix in the
  replica's cache BY REFERENCE and queues the write-through host
  stage) and the crossing is PARKED; the drain at the next dispatch
  (``_pre_dispatch`` — r23) materialises every parked crossing's
  staged bytes with ONE labelled ``serving.tier_transfer`` sync, so
  several boundaries crossing in the same loop turn share a single
  sync, and each request's page set crosses pools via
  r19's replica-portable ``export_host`` → ``import_host`` bytes. The
  request requeues on the chosen decode replica (the ``_kill_replica``
  requeue pattern: fresh engine-local rid, stable fleet rid), whose
  admission prefix-hits the imported entry, restores the pages, and
  suffix-prefills only the unaligned tail. Greedy decode makes the
  disaggregated token stream IDENTICAL to the co-resident one.

  **The device seam:** on this container the transfer is host bytes
  (D2H stage → host dict → H2D restore). On chips the same seam is a
  device-to-device ``jax.device_put`` of the page planes between the
  source and destination replica's HBM — ``export_host``/
  ``import_host`` is deliberately the ONLY crossing point, so swapping
  the transport touches nothing else.
* **The handoff is journaled and budget-audited.** Every handoff
  writes a ``handoff`` decision record (rid, src, dst, pages, bytes,
  rows) — ``handoff`` is in ``DECISION_KINDS``, so a cross-pool
  journey (prefill@A → handoff → decode@B) replays bit-exactly — and
  appends to the router's ``handoff_log`` ledger, which
  ``analysis.tiers.handoff_audit`` holds to bytes-moved ≤ the
  request's reserved KV footprint PER CROSSING. The request itself is
  billed (``Request.tier_pages``/``tier_bytes``) exactly once, at
  decode admission when the imported pages restore to HBM — the
  handoff import and that restore are one physical crossing on chips
  (``device_put`` lands directly in the destination HBM), so billing
  both halves of this container's host-bytes detour would double-count
  the transfer the seam models.
* **Per-pool envelopes shrink each pool's AOT ladder** (r20). The
  prefill pool declares ``resume=False`` — it only ever admits fresh
  prompts, so none of the resume-widened admission widths (prompt +
  generated-so-far up to the top bucket) are reachable and their
  programs are never compiled. Each pool also declares only ITS OWN
  ``seg_steps`` (short prefill segments so first tokens hand off
  promptly; long decode segments so steady generation amortises the
  fetch), so neither pool compiles the other's step-axis rungs. The
  per-pool warmup bill (SCALING §3o / §3q) is measurably below the
  co-resident ladder on the prefill side and no worse on decode.
* **Per-pool SLOs** (``slo.py``): ``pool_objectives={"prefill":
  Objective(ttft_target_s=...), "decode": Objective(tbt_target_s=
  ...)}`` — the router feeds ``note_pool_ttft`` at the first-token
  stamp (first tokens can only land on prefill replicas) and
  ``note_pool_tbt`` at the finish stamp.

Fallbacks keep the topology graceful, never wrong: a slot that cannot
re-admit (``can_preempt`` False — generation outgrew the top bucket)
or finds no healthy decode replica simply finishes in place on the
prefill replica (counted in ``handoff_fallbacks``); a handoff whose
host entry was evicted before export moves zero pages and the decode
replica re-prefills (correct, just costs compute).

Failover keeps pool discipline: ``_failover_target`` sends
token-bearing requests of a dead replica to the decode pool and
untouched ones back to prefill, so a failover never admits a program
outside the target pool's envelope.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..observability import flight as _flight
from ..observability import journal as _journal
from ..models import require
from ..observability import metrics as _metrics
from .fleet import FleetRouter, _Replica
from .prefix_cache import make_prefix_cache
from .scheduler import Arrival
from .serving import Request, ServingEngine

__all__ = ["DisaggRouter"]


class DisaggRouter(FleetRouter):
    """A :class:`FleetRouter` over two specialized pools.

    ``prefill_engines`` / ``decode_engines``: the pool memberships —
    replicas are indexed prefill-first, then decode (the order the
    journal header's ``pools`` list records and replay rebuilds).
    ``prefill_caches`` / ``decode_caches``: per-engine
    ``PagedPrefixCache`` instances WITH host tiers (the handoff
    transport), or ``"auto"`` to build them (host tier sized to the
    whole pool so a handoff burst never drops staged bytes).
    ``prefill_seg_steps`` / ``decode_seg_steps``: each pool's segment
    budget (default: the shared ``seg_steps`` knob). Remaining kwargs
    are FleetRouter's; ``canary`` is unsupported (its replica index
    semantics do not survive the pool split).
    """

    def __init__(self, prefill_engines: Sequence[ServingEngine],
                 decode_engines: Sequence[ServingEngine],
                 prefill_caches="auto", decode_caches="auto",
                 host_tier_pages: Optional[int] = None,
                 prefill_seg_steps: Optional[int] = None,
                 decode_seg_steps: Optional[int] = None,
                 seg_steps: int = 8, **kw):
        prefill_engines = list(prefill_engines)
        decode_engines = list(decode_engines)
        if not prefill_engines or not decode_engines:
            raise ValueError("disaggregation needs at least one engine "
                             "in each pool")
        if kw.get("canary") is not None:
            raise ValueError("canary serving is not supported on a "
                             "disaggregated fleet — run the canary "
                             "inside one pool's homogeneous FleetRouter")
        engines = prefill_engines + decode_engines
        for e in engines:
            require(e.cfg, "disaggregated serving")

        def _auto(es):
            return [make_prefix_cache(
                e, host_tier_pages=(host_tier_pages
                                    or e.pager.num_pages))
                    for e in es]

        pcs = ((_auto(prefill_engines) if prefill_caches == "auto"
                else list(prefill_caches))
               + (_auto(decode_engines) if decode_caches == "auto"
                  else list(decode_caches)))
        for pc in pcs:
            if pc is None or getattr(pc, "host_tier", None) is None:
                raise ValueError(
                    "every disagg replica needs a PagedPrefixCache "
                    "with a host tier — export_host/import_host is the "
                    "handoff transport (the device_put seam)")
        # r25 (ISSUE 20): a pool-scoped autoscaler's bind filters on
        # pool tags, which only exist after construction — defer the
        # attach until the tags are applied
        ascs = kw.pop("autoscaler", None)
        super().__init__(engines, prefix_caches=pcs,
                         seg_steps=seg_steps, **kw)
        self.n_prefill = len(prefill_engines)
        for r in self._replicas:
            r.pool = "prefill" if r.idx < self.n_prefill else "decode"
        self._attach_autoscalers(ascs)
        self.prefill_seg_steps = int(prefill_seg_steps or seg_steps)
        self.decode_seg_steps = int(decode_seg_steps or seg_steps)
        # the handoff ledger: every crossing, in decision order — the
        # generalized tier audit (analysis.tiers.handoff_audit) checks
        # each entry's bytes against the request's reserved footprint
        self.handoffs = 0
        self.handoff_pages = 0
        self.handoff_bytes = 0
        self.handoff_fallbacks = 0          # finished in place instead
        self.handoff_flushes = 0            # labelled tier_transfer syncs
        self.handoff_log: List[dict] = []
        # r23 (ISSUE 18 satellite): boundary sweeps PLAN crossings and
        # park them here; the drain at the next dispatch (or idle turn)
        # materialises every parked crossing under ONE labelled tier
        # sync — several boundaries crossing in the same loop turn
        # share it. Entries: (src replica, request, fleet rid).
        self._pending_handoffs: List[tuple] = []

    # --- pools ------------------------------------------------------------
    def pool_replicas(self, pool: str) -> List[_Replica]:
        return [r for r in self._replicas if r.pool == pool]

    def pool_envelope(self, pool: str):
        """The pool's declared :class:`WorkloadEnvelope` — what its
        replicas AOT-compile. Prefill: fresh admissions only
        (``resume=False`` drops every resume-widened admission width)
        at the prefill segment budget. Decode: the full resume range
        (every admission is a resumed request re-entering through a
        prefix hit) at the decode segment budget. Each pool's ladder
        carries only its own steps axis."""
        rep = self.pool_replicas(pool)[0]
        blk = rep.prefix_cache.block
        if pool == "prefill":
            return rep.engine.default_envelope(
                seg_steps=(self.prefill_seg_steps,), resume=False,
                prefix_block=blk)
        return rep.engine.default_envelope(
            seg_steps=(self.decode_seg_steps,), prefix_block=blk)

    def aot_warmup(self, envelope=None) -> Dict[int, dict]:
        """Per-pool warmup: each replica compiles ITS pool's envelope
        (identical-geometry replicas within a pool still share compiles
        via ``serving._SHARED_PROGS``). An explicit ``envelope``
        overrides both pools (the homogeneous escape hatch)."""
        out: Dict[int, dict] = {}
        for r in self._replicas:
            env = envelope or self.pool_envelope(r.pool)
            with _metrics.scoped_registry(r.registry), \
                    _journal.rank_scope(r.idx):
                out[r.idx] = r.engine.aot_warmup(
                    env, prefix_cache=r.prefix_cache)
        return out

    # --- routing hooks (the fleet's pool-aware mode) ----------------------
    def _dispatch_candidates(self) -> List[_Replica]:
        # fresh prompts start on prefill; decode replicas take work
        # only through the journaled handoff (or pool-kept failover).
        # r25: composed with the elastic lifecycle filter — a warming/
        # draining/offline prefill replica admits nothing
        return [r for r in self.pool_replicas("prefill")
                if r.lifecycle == "serving"]

    def _warmup_envelope_for(self, rep: _Replica):
        # r25: a standby warmed mid-serve compiles ITS pool's (smaller)
        # r20 ladder, exactly what aot_warmup gave its pool-mates
        return self.pool_envelope(rep.pool)

    def _seg_steps_for(self, rep: _Replica) -> int:
        return (self.prefill_seg_steps if rep.pool == "prefill"
                else self.decode_seg_steps)

    def _failover_target(self, survivors: List[_Replica],
                         req: Request) -> _Replica:
        pool = "decode" if req.tokens else "prefill"
        pooled = [x for x in survivors if x.pool == pool]
        return min(pooled or survivors, key=lambda x: (x.load, x.idx))

    def _handoff_target(self, req: Request) -> Optional[_Replica]:
        """The decode replica this request hands off to: healthy,
        preferring page-room for the request's full resume span and an
        un-full queue, then least-loaded (ties to lowest index — the
        same determinism rule as ``_route``)."""
        cands = [r for r in self._replicas
                 if r.pool == "decode" and r.health == "healthy"
                 and r.lifecycle == "serving"]
        if not cands:
            return None
        span = len(req.prompt) + req.max_new_tokens - 1

        def rank(r):
            need = r.engine.pager.pages_needed(span)
            return (r.engine.pager.pages_free < need,
                    r.queue_depth >= self.max_queue, r.load, r.idx)

        return min(cands, key=rank)

    # --- the handoff (the tentpole's state machine) -----------------------
    def _post_segment(self, rep: _Replica, ev: dict) -> None:
        """The handoff sweep. Runs after ``rep``'s segment fetch was
        applied and stamped (`_finish_one`), with the engine idle — the
        only point a slot can be preempted. State machine per slot:

        live, first token out
          → ``can_preempt`` and a healthy decode replica exists?
            → preempt (park page-aligned prefix by reference, queue
              write-through stage) — else finish in place (fallback)
        sweep end
          → PARK the planned crossings on ``_pending_handoffs``; no
            sync happens here (r23). The fleet's ``_pre_dispatch``
            hook drains the parked batch right before the next
            dispatch (or from the idle branch), so several boundaries
            crossing in the same loop turn share ONE labelled
            ``serving.tier_transfer`` sync instead of one each — the
            per-crossing ledger (journal decisions, byte billing,
            counters) is untouched, only the sync count collapses."""
        if rep.pool != "prefill":
            return
        eng = rep.engine
        pc = rep.prefix_cache
        frid_of = {id(self._reqs[frid][1]): frid for frid in rep.rids}
        planned = []
        for slot in range(eng.slots):
            req = eng._active[slot]
            if req is None or not req.first_token_time or req.done:
                continue
            if not eng.can_preempt(slot):
                self.handoff_fallbacks += 1     # finishes in place
                continue
            if self._handoff_target(req) is None:
                self.handoff_fallbacks += 1
                continue
            planned.append((slot, req))
        if not planned:
            return
        with _metrics.scoped_registry(rep.registry), \
                _journal.rank_scope(rep.idx):
            for slot, req in planned:
                out = eng.preempt_slot(slot, pc)
                assert out is req
        # the target is re-resolved at drain time — loads (and health)
        # can shift while the crossing is parked
        self._pending_handoffs.extend(
            (rep, req, frid_of[id(req)]) for _slot, req in planned)

    # --- the coalesced drain (r23) ----------------------------------------
    def _has_deferred_work(self) -> bool:
        return bool(self._pending_handoffs)

    def _pre_dispatch(self, rep) -> None:
        self._drain_handoffs()

    def _drain_handoffs(self) -> None:
        """Materialise every parked crossing. ONE labelled
        ``serving.tier_transfer`` sync covers ALL source tiers that
        staged since the last drain (the coalescing point — this is
        the multi-tier twin of ``kv_tiers.flush_tiers``, inlined so
        each tier's ``complete`` lands under its own replica's metric
        registry and journal rank scope); then each crossing runs the
        unchanged r22 export → import → bill → journal → requeue
        sequence."""
        if not self._pending_handoffs:
            return
        entries, self._pending_handoffs = self._pending_handoffs, []
        srcs = list({id(e[0]): e[0] for e in entries}.values())
        work = []
        for src in srcs:
            staged = src.prefix_cache.host_tier.take_pending()
            if staged:
                work.append((src, staged))
        if work:
            import jax

            from ..analysis.syncs import allowed_sync

            with allowed_sync("serving.tier_transfer"):
                vals = jax.device_get([[s[2:] for s in staged]
                                       for _, staged in work])
            for (src, staged), v in zip(work, vals):
                with _metrics.scoped_registry(src.registry), \
                        _journal.rank_scope(src.idx):
                    src.prefix_cache.host_tier.complete(staged, v)
            self.handoff_flushes += 1
        for src, req, frid in entries:
            dst = self._handoff_target(req)
            if dst is None:
                # every decode replica died while the crossing was
                # parked: pool discipline yields to liveness — requeue
                # by the failover rule among whatever is healthy
                survivors = [x for x in self._replicas
                             if x.health == "healthy"]
                if not survivors:
                    raise RuntimeError(
                        f"request {frid} was preempted for handoff but "
                        "no healthy replica remains to receive it")
                dst = self._failover_target(survivors, req)
                self.handoff_fallbacks += 1
            self._do_handoff(src, dst, req, frid)

    def _kill_replica(self, rep: _Replica, reason: str) -> None:
        # parked crossings sourced at the dying replica cannot wait for
        # the next drain: their requests live NOWHERE the base failover
        # can see (preempt_slot already removed them from the engine).
        # Their staged-but-unflushed futures die with the tier, so they
        # requeue WITHOUT import (export misses → bytes=0 journaled) —
        # the decode replica re-prefills from the resume view: correct,
        # just costs compute.
        mine = [e for e in self._pending_handoffs if e[0] is rep]
        if mine:
            self._pending_handoffs = [e for e in self._pending_handoffs
                                      if e[0] is not rep]
            rep.prefix_cache.host_tier.take_pending()   # discard futures
            for src, req, frid in mine:
                dst = self._handoff_target(req)
                if dst is not None:
                    self._do_handoff(src, dst, req, frid)
                    continue
                survivors = [x for x in self._replicas
                             if x.health == "healthy" and x is not rep]
                if not survivors:
                    raise RuntimeError(
                        f"request {frid} was preempted for handoff and "
                        f"its source replica {rep.idx} died with no "
                        "healthy survivor to receive it")
                self._do_handoff(src, self._failover_target(survivors,
                                                            req),
                                 req, frid)
                self.handoff_fallbacks += 1
        super()._kill_replica(rep, reason)

    def _do_handoff(self, src: _Replica, dst: _Replica, req: Request,
                    frid: int) -> None:
        pc_src, pc_dst = src.prefix_cache, dst.prefix_cache
        fp, _ = req.resume_view()
        plen_b = pc_src.round_down(len(fp))
        pages = nbytes = rows = 0
        resident = False
        if plen_b:
            key = np.asarray(fp[:plen_b], np.int32).tobytes()
            exp = pc_src.export_host(key)
            if exp is not None:
                rows = int(len(exp["tokens"]))
                planes = {p: exp[p] for p in exp
                          if p not in ("tokens", "pages")}
                # the device seam: host bytes here, device_put on chips
                if pc_dst.import_host(exp["tokens"], planes):
                    pages = int(exp["pages"])
                    nbytes = pages * pc_dst.host_tier.page_bytes()
                else:
                    resident = True     # dst already holds the prefix
        self.handoffs += 1
        self.handoff_pages += pages
        self.handoff_bytes += nbytes
        entry = {"rid": frid, "src": src.idx, "dst": dst.idx,
                 "pages": pages, "bytes": nbytes, "rows": rows,
                 "pages_reserved": req.pages_reserved,
                 "tokens_done": len(req.tokens), "resident": resident}
        self.handoff_log.append(entry)
        _metrics.counter("fleet.handoffs").inc()
        _flight.record("handoff", **entry)
        # requeue across pools — the _kill_replica pattern: the decode
        # engine assigns its own rid, the fleet rid stays stable (the
        # client's TTFT/finish stamps survive the crossing)
        req.rid = dst.engine._next_rid
        dst.engine._next_rid += 1
        dst.engine._queue.append(req)
        self._reqs[frid] = (dst.idx, req)
        dst.rids.append(frid)
        src.rids.remove(frid)

    # --- per-pool SLO feed ------------------------------------------------
    def _stamp(self, r: _Replica, ev: dict, t_sync: float) -> List[tuple]:
        outcomes = super()._stamp(r, ev, t_sync)
        mon = self.slo_monitor
        if mon is not None and r.pool is not None:
            by_erid = {self._reqs[frid][1].rid: self._reqs[frid][1]
                       for frid in r.rids}
            for erid in ev["first_tokens"]:
                req = by_erid[erid]
                if req.first_token_time == t_sync:   # stamped just now
                    mon.note_pool_ttft(r.pool,
                                       t_sync - req.arrival_time)
            for erid in ev["finished"]:
                req = by_erid[erid]
                if len(req.tokens) > 1 and req.first_token_time:
                    mon.note_pool_tbt(
                        r.pool, (t_sync - req.first_token_time)
                        / (len(req.tokens) - 1))
        return outcomes

    # --- replay / lifecycle / reporting -----------------------------------
    def _journal_header(self, arrivals) -> dict:
        h = super()._journal_header(arrivals)
        h["driver"] = "disagg"
        # pool topology: role per replica (index order) + per-pool
        # envelopes and segment budgets — everything replay_serve needs
        # to rebuild the disaggregated fleet from the header alone
        h["pools"] = [r.pool for r in self._replicas]
        h["disagg"] = {
            "prefill_seg_steps": self.prefill_seg_steps,
            "decode_seg_steps": self.decode_seg_steps,
            "envelopes": {
                p: _journal.describe_envelope(self.pool_envelope(p))
                for p in ("prefill", "decode")},
        }
        return h

    def reset(self) -> None:
        super().reset()
        for r in self._replicas:
            r.pool = "prefill" if r.idx < self.n_prefill else "decode"
        self.handoffs = 0
        self.handoff_pages = 0
        self.handoff_bytes = 0
        self.handoff_fallbacks = 0
        self.handoff_flushes = 0
        self.handoff_log = []
        self._pending_handoffs = []

    def handoff_report(self) -> dict:
        return {"handoffs": self.handoffs,
                "pages": self.handoff_pages,
                "bytes": self.handoff_bytes,
                "fallbacks": self.handoff_fallbacks,
                "flushes": self.handoff_flushes,
                "log": list(self.handoff_log)}

    def pool_stats(self) -> Dict[str, dict]:
        """Per-pool aggregates for the ops surface (all host mirrors):
        replica membership, summed ``pages_free`` and reclaimable
        cache pages — the /healthz // /capacity pool view."""
        out: Dict[str, dict] = {}
        for pool in ("prefill", "decode"):
            reps = self.pool_replicas(pool)
            out[pool] = {
                "replicas": [r.idx for r in reps],
                "pages_free": sum(r.engine.pager.pages_free
                                  for r in reps),
                "reclaimable": sum(r.prefix_cache.reclaimable_pages()
                                   for r in reps),
            }
        return out
