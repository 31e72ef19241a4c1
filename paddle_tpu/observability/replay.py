"""Bit-exact incident replay (ISSUE 11 tentpole, part c).

``python -m paddle_tpu.observability.replay <journal>`` rebuilds the
recorded serve — engines, scheduler or fleet router, prefix caches,
fault injector, the full arrival trace — from the journal's header,
re-runs it with the RECORDED clock fed back through ``journal.now()``,
and diffs the replayed decision + token stream against the journal:
either certifying identity or reporting the first divergence as
``(seq, kind, field, recorded, replayed)``.

Why this is bit-exact rather than best-effort: every serving decision
is a pure function of (the seeded trace, engine/scheduler state, and
the decision-clock reads). The journal records all three — the trace
and state in the header, the clock reads as ``clock`` records — so the
replay is immune to replay-machine timing: XLA compiles, container
load and host jitter change nothing, because the replayed loop never
looks at the real clock. Divergence therefore means exactly one of

* the journal was tampered with / corrupted (the mutated-journal test),
* the code running the replay differs from the code that recorded
  (a real regression-localisation signal: the first diverging record
  names the first decision the new code makes differently), or
* non-recorded state leaked into a decision (a bug in the recorder —
  the replay-identity tests in tests/test_journal.py exist to keep the
  recorded-state set complete).

What replay does NOT need: the recording's wall-clock budget (a 60 s
incident replays in seconds — device work is the only real cost) or
its monitors (SLO/perf monitors are observers, not deciders; their
``slo_alert`` events are journaled but outside the diffed decision
set). What it DOES need: the same model params — pass them in-process
(``replay_serve(path, params=...)``), or record
``Journal.params_info = {"prng_seed": s}`` so the CLI can rebuild them.

Limits (documented, enforced with clear errors): mesh-sharded (mp)
engines need the recording topology's devices — the CLI refuses rather
than mis-replaying; a serve that started from pre-warmed caches or
live slots replays from the recorded header state only (the standard
lane/test flow — warm pass, reset, measured serve — is exactly that).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import journal as _journal
from . import metrics as _metrics
from .journal import (DECISION_KINDS, Journal, JournalError, read_journal,
                      sections)

__all__ = ["ReplayResult", "rebuild", "rebuild_params", "replay_serve",
           "diff_decisions", "main"]

# journal bookkeeping fields never compared: wall stamps and sequence
# counters differ by construction (the replay interleaves non-decision
# records — cold_start, recompiles — differently than the recording)
_IGNORED_FIELDS = frozenset({"t", "gseq", "seq", "v"})


@dataclasses.dataclass
class ReplayResult:
    identical: bool
    n_decisions: int               # recorded decision records diffed
    n_replayed: int
    divergence: Optional[dict]     # first (seq, kind, field) mismatch
    error: Optional[str] = None    # control-flow divergence (clock feed)
    driver: Optional[str] = None
    report: Optional[object] = None   # the replayed OnlineReport/FleetReport

    def as_dict(self) -> dict:
        return {"identical": self.identical,
                "n_decisions": self.n_decisions,
                "n_replayed": self.n_replayed,
                "divergence": self.divergence,
                "error": self.error,
                "driver": self.driver}


# --- header -> live objects ------------------------------------------------

def _cfg_from(d: dict):
    import jax.numpy as jnp

    from ..models import llama

    d = dict(d)
    d["dtype"] = getattr(jnp, d["dtype"])
    return llama.LlamaConfig(**d)


def rebuild_params(header: dict, cfg=None):
    """Model params from the header's ``params`` info (a PRNG seed) —
    the CLI path. In-process callers usually pass params directly."""
    info = header.get("params") or {}
    if "prng_seed" not in info:
        raise JournalError(
            "journal header carries no params provenance — set "
            "Journal.params_info = {'prng_seed': s} when recording, or "
            "replay in-process with replay_serve(..., params=params)")
    import jax

    from ..models import llama

    cfg = cfg if cfg is not None else _cfg_from(header["llama"])
    return llama.init_params(cfg, jax.random.PRNGKey(
        int(info["prng_seed"])))


def _engine_from(d: dict, cfg, params):
    from ..inference.serving import ServingEngine

    if d.get("mesh"):
        raise JournalError(
            f"recorded engine is mesh-sharded over {d['mesh']} — replay "
            f"needs the recording topology's devices; rebuild the mesh "
            f"and engines yourself, then drive rebuild() manually")
    if not d["paged"]:
        raise JournalError(
            'recorded engine has "paged": false — the contiguous-cache '
            "engine it describes no longer exists, so its decisions "
            "cannot be replayed")
    kw: Dict[str, Any] = dict(
        slots=d["slots"], max_len=d["max_len"], chunk=d["chunk"],
        prompt_buckets=tuple(d["prompt_buckets"]),
        eos_token_id=d["eos_token_id"],
        page_size=d["page_size"], num_pages=d["num_pages"],
        chunked_prefill=d["chunked_prefill"],
        prefill_chunks=tuple(d["prefill_chunks"]),
        speculative=d["speculative"], sampling=d["sampling"],
        sample_seed=d["sample_seed"],
        quality_digest=d.get("quality_digest", False),
        digest_top_k=d.get("digest_top_k", 4),
        # r21: the engine re-quantizes the fp params in __init__, so a
        # recorded quantized serve rebuilds from the SAME fp tree
        quant=d.get("quant"),
        # r23: long-context geometry (absent in pre-r23 journals)
        seq_parallel=d.get("seq_parallel", 0),
        long_buckets=tuple(d.get("long_buckets") or ()))
    eng = ServingEngine(cfg, params, **kw)
    # mutable state the serve started from: rid offsets feed sampling
    # seeds and class-order keys; the acceptance EWMA feeds shed math
    eng._next_rid = int(d["next_rid"])
    eng.spec_accept_ewma = float(d["spec_accept_ewma"])
    return eng


def _prefix_cache_from(d: Optional[dict], engine):
    if d is None:
        return None
    from ..inference.prefix_cache import PagedPrefixCache

    if d["kind"] != "paged":
        raise JournalError(
            f"recorded prefix cache is of kind {d['kind']!r} — only the "
            f"paged cache exists")
    host_tier = None
    if d.get("host_tier_pages"):
        # r19: the spill tier decides restores/spills — rebuild it
        # at the recorded capacity so tier_transfer records replay
        from ..inference.kv_tiers import HostTier

        host_tier = HostTier(engine.pager,
                             capacity_pages=d["host_tier_pages"])
    return PagedPrefixCache(engine.pager,
                            capacity_pages=d["capacity_pages"],
                            host_tier=host_tier)


def _injector_from(d: Optional[dict]):
    if d is None:
        return None
    from ..inference.fleet import FaultInjector

    inj = FaultInjector(
        crash={int(k): int(v) for k, v in (d.get("crash") or {}).items()},
        hang={int(k): tuple(v) for k, v in (d.get("hang") or {}).items()},
        recover_after=d.get("recover_after", 1),
        seed=d.get("seed", 0), crash_p=d.get("crash_p", 0.0))
    for _ in range(int(d.get("draws", 0))):
        inj._rng.rand()            # fast-forward the consumed draws
    return inj


def _trace_from(header: dict):
    from ..inference.scheduler import Arrival

    return [Arrival(a["at"], np.asarray(a["prompt"], np.int32),
                    a["gen"], priority=a.get("priority", 0),
                    deadline_s=a.get("deadline_s"))
            for a in header["trace"]]


def rebuild(header: dict, params):
    """(driver, trace): the serve topology the header describes, built
    fresh — an ``OnlineScheduler``/``SLOScheduler`` over one engine, or
    a ``FleetRouter`` over N replicas with per-replica caches and the
    fault injector's recorded schedule."""
    from ..inference.fleet import FleetRouter
    from ..inference.scheduler import OnlineScheduler, SLOScheduler

    cfg = _cfg_from(header["llama"])
    trace = _trace_from(header)
    driver = header["driver"]
    engines = [_engine_from(d, cfg, params) for d in header["engines"]]
    if driver in ("fleet", "disagg"):
        fk = header["fleet"]
        pcs = [_prefix_cache_from(d, e)
               for d, e in zip(header["prefix_caches"], engines)]
        # r17: a canary is a routing DECIDER — rebuild it from its
        # recorded config (assign() is a pure seeded draw and the
        # latency verdicts re-derive from the fed clock, so holds
        # replay bit-exactly). A quality-linked canary's holds depend
        # on shadow-diff state replay does not rebuild: refuse loudly.
        canary = None
        ck = header.get("canary")
        if ck is not None:
            if ck.get("quality_linked"):
                raise JournalError(
                    "recorded canary was linked to a live quality "
                    "monitor — its hold decisions depend on shadow-"
                    "diff state the replay does not rebuild; replay "
                    "latency-only canaries, or drive rebuild() "
                    "yourself with the shadow re-attached")
            from .quality import CanaryController

            canary = CanaryController(
                ck["replica"], weight=ck["weight"], seed=ck["seed"],
                latency_ratio_max=ck["latency_ratio_max"],
                min_outcomes=ck["min_outcomes"],
                verdict_every=ck["verdict_every"])
        kw = dict(
            max_queue=fk["max_queue"], seg_steps=fk["seg_steps"],
            affinity_block=fk["affinity_block"],
            segment_timeout_s=fk["segment_timeout_s"],
            max_finish_retries=fk["max_finish_retries"],
            max_requeues=fk["max_requeues"],
            fault_injector=_injector_from(header.get("fault")),
            probe_after_s=fk["probe_after_s"],
            directory=bool(fk.get("directory", False)))
        # r25 (ISSUE 20): the autoscaler is a DECIDER — rebuild the
        # policies AND their input monitors from the recorded configs
        # so the elastic control loop re-derives every scale decision
        # from the fed clock + event stream (absent section: pre-r25
        # journal, nothing to rebuild)
        ak = header.get("autoscaler")
        if ak is not None:
            from ..inference.autoscaler import Autoscaler

            kw["autoscaler"] = [Autoscaler.from_description(p)
                                for p in ak["policies"]]
            if ak.get("slo") is not None:
                from .slo import SLOMonitor

                kw["slo_monitor"] = SLOMonitor.from_description(
                    ak["slo"])
            if ak.get("capacity") is not None:
                from .capacity import CapacityMonitor

                kw["capacity_monitor"] = CapacityMonitor \
                    .from_description(ak["capacity"])
        if driver == "disagg":
            # r22: the disaggregated fleet rebuilds from the header
            # alone — pool role per replica (index order is
            # prefill-first, the DisaggRouter construction order) plus
            # each pool's segment budget; the per-pool envelopes
            # re-derive from the rebuilt engines' geometry
            from ..inference.disagg import DisaggRouter

            pools = header["pools"]
            dk = header["disagg"]
            pre = [i for i, p in enumerate(pools) if p == "prefill"]
            dec = [i for i, p in enumerate(pools) if p == "decode"]
            if pre + dec != list(range(len(pools))):
                raise JournalError(
                    f"disagg header pools not prefill-first: {pools}")
            router = DisaggRouter(
                [engines[i] for i in pre], [engines[i] for i in dec],
                prefill_caches=[pcs[i] for i in pre],
                decode_caches=[pcs[i] for i in dec],
                prefill_seg_steps=dk["prefill_seg_steps"],
                decode_seg_steps=dk["decode_seg_steps"], **kw)
        else:
            router = FleetRouter(
                engines,
                prefix_caches=(pcs if any(p is not None for p in pcs)
                               else None),
                canary=canary, **kw)
        router._next_rid = int(fk.get("next_rid", 0))
        return router, trace
    sk = header["scheduler"]
    cls = SLOScheduler if driver == "slo" else OnlineScheduler
    kw: Dict[str, Any] = dict(max_queue=sk["max_queue"],
                              seg_steps=sk["seg_steps"])
    if driver == "slo":
        kw["preempt"] = sk["preempt"]
        kw["shed_deadlines"] = sk["shed_deadlines"]
    sched = cls(engines[0],
                prefix_cache=_prefix_cache_from(
                    header.get("prefix_cache"), engines[0]), **kw)
    # measured-state carry-over: the service-rate EWMAs a warm pass (or
    # earlier traffic) left behind are shed-decision inputs
    sched._per_tick_s = float(sk.get("per_tick_s", 0.0))
    if driver == "slo":
        sched._per_token_s = float(sk.get("per_token_s", 0.0))
    return sched, trace


# --- the diff --------------------------------------------------------------

def _decision_stream(records: Sequence[dict]) -> List[dict]:
    # r17: shadow-marked records (mirrored segments, quality compares,
    # shadow drain clock reads) are journaled losslessly but sit OFF
    # the decision stream — the shadow is an observer, and a serve must
    # replay identically whether or not one was attached (the replay
    # does not rebuild the shadow; see fleet.Shadow)
    return [r for r in records
            if r["kind"] in DECISION_KINDS and not r.get("shadow")]


def diff_decisions(recorded: Sequence[dict],
                   replayed: Sequence[dict]) -> Optional[dict]:
    """First divergence between two decision streams, or None when they
    are identical. Compared field-by-field (everything but wall stamps
    and sequence counters), so the report names the exact decision and
    the exact field that first went a different way."""
    n = min(len(recorded), len(replayed))
    for i in range(n):
        a, b = recorded[i], replayed[i]
        fields = (["kind"] if a["kind"] != b["kind"]
                  else sorted((set(a) | set(b)) - _IGNORED_FIELDS))
        for k in fields:
            if a.get(k) != b.get(k):
                return {"index": i, "seq": a.get("seq"),
                        "rank": a.get("rank"), "kind": a["kind"],
                        "field": k, "recorded": a.get(k),
                        "replayed": b.get(k)}
    if len(recorded) != len(replayed):
        tail = recorded[n] if len(recorded) > n else replayed[n]
        return {"index": n, "seq": tail.get("seq"),
                "rank": tail.get("rank"), "kind": tail.get("kind"),
                "field": "stream_length", "recorded": len(recorded),
                "replayed": len(replayed)}
    return None


# --- the replay ------------------------------------------------------------

def replay_serve(source, params=None, section: int = -1) -> ReplayResult:
    """Replay one recorded serve and diff it against the journal.

    ``source``: a journal directory/file path, a ``read_journal``
    result, or a raw record list. ``section`` picks which serve when
    the journal holds several (a ``warm=True`` pass records its own);
    the default ``-1`` is the LAST — the measured pass. ``params``:
    the model weights (rebuilt from the header's ``prng_seed`` when
    omitted).

    The replay runs inside a scratch metrics registry (its counters
    must not pollute the live process) with an in-memory scratch
    journal attached and the recorded clock fed back; the returned
    ``ReplayResult`` certifies identity or carries the first
    divergence."""
    if isinstance(source, str):
        records = read_journal(source)["records"]
    elif isinstance(source, dict):
        records = source["records"]
    else:
        records = list(source)
    secs = [s for s in sections(records) if s["header"] is not None]
    if not secs:
        raise JournalError("journal holds no serve header — nothing to "
                           "replay")
    sec = secs[section]
    header, sec_records = sec["header"], sec["records"]
    if params is None:
        params = rebuild_params(header)
    driver, trace = rebuild(header, params)
    clock = [r["c"] for r in sec_records if r["kind"] == "clock"]
    scratch = Journal()                      # in-memory
    error = None
    report = None
    prev_enabled = _metrics.set_enabled(
        bool(header.get("telemetry_enabled", True)))
    try:
        with _metrics.scoped_registry(_metrics.Registry()):
            with _journal.attach(scratch):
                try:
                    with _journal.feed_clock(clock):
                        report = driver.serve(trace)
                except JournalError as e:
                    error = str(e)           # control flow diverged
                except AssertionError as e:
                    error = f"replay invariant failed: {e}"
    finally:
        _metrics.set_enabled(prev_enabled)
    rec_dec = _decision_stream(sec_records)
    rep_dec = _decision_stream(scratch.records())
    div = diff_decisions(rec_dec, rep_dec)
    return ReplayResult(identical=div is None and error is None,
                        n_decisions=len(rec_dec),
                        n_replayed=len(rep_dec), divergence=div,
                        error=error, driver=header["driver"],
                        report=report)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="paddle_tpu.observability.replay",
        description="Re-execute a recorded serve and certify the "
                    "decision + token stream bit-identical (or report "
                    "the first divergence).")
    ap.add_argument("journal", help="journal directory (or one rank file)")
    ap.add_argument("--section", type=int, default=-1,
                    help="which recorded serve (default: last)")
    ap.add_argument("--params-seed", type=int, default=None,
                    help="override the header's params PRNG seed")
    ap.add_argument("--json", default=None, help="write the result JSON")
    ap.add_argument("--journey", type=int, default=None, metavar="RID",
                    help="also print request RID's journey")
    args = ap.parse_args(argv)

    merged = read_journal(args.journal)
    if merged.get("skipped_files"):
        print(f"warning: skipped corrupt rank files: "
              f"{merged['skipped_files']}")
    params = None
    if args.params_seed is not None:
        secs = [s for s in sections(merged["records"])
                if s["header"] is not None]
        hdr = dict(secs[args.section]["header"])
        hdr["params"] = {"prng_seed": args.params_seed}
        params = rebuild_params(hdr)
    res = replay_serve(merged, params=params, section=args.section)
    if args.journey is not None:
        j = _journal.request_journey(merged["records"], args.journey)
        print(f"journey rid={args.journey}: kinds={j['kinds']} "
              f"replicas={j['replicas']} tokens={j['n_tokens']}")
    if res.identical:
        print(f"REPLAY IDENTICAL: {res.n_decisions} decision records "
              f"(driver={res.driver}) reproduced bit-exactly")
    else:
        print("REPLAY DIVERGED:")
        if res.error:
            print(f"  control flow: {res.error}")
        if res.divergence:
            d = res.divergence
            print(f"  first divergence at decision #{d['index']} "
                  f"(rank {d['rank']} seq {d['seq']}): kind={d['kind']} "
                  f"field={d['field']}\n"
                  f"    recorded: {d['recorded']}\n"
                  f"    replayed: {d['replayed']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res.as_dict(), f, indent=1, default=str)
    return 0 if res.identical else 1


if __name__ == "__main__":
    sys.exit(main())
