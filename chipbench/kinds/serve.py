"""kind ``serve``: an open loop of requests against one paged ServingEngine
under the OnlineScheduler, offered at the rate fixed in the workload file.

The program is driven through ``llama.init_params``, ``ServingEngine``
(+ ``aot_warmup``) and ``OnlineScheduler.serve``. The engine's
``run_segment`` is wrapped from here (two clock reads a segment) so that
each segment has a host span, and every end-to-end time is taken on THIS
clock: a request is due at the window's opening plus its arrival offset,
and its first and last tokens are seen when the segment that produced them
returns (the event lists name the requests). The ``OnlineReport`` gives the
counters and the scheduler's own spans (per-layer metrics), and its times
are printed beside ours as a cross-check. A run that reads far off names
its slow segments on the ``segments`` line of its output. In a traced run
the profiler opens and closes at those boundaries.

Besides the traffic's keys (``traffic.py``) a serving workload file may give
``saturated_from_s``: by when the slots have filled. ``serve_tokens_per_s``
is the tokens delivered from the first fetch after it to the last fetch
inside the window, over the time between the two.
"""

from __future__ import annotations

import gc
import time
from typing import Optional

import numpy as np

from .. import common, reference, traffic


def build_engine(config: dict, seed: int):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.program_space import WorkloadEnvelope
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    serve = config["serve"]
    cfg = common.llama_config(config, max_seq_len=serve["engine"]["max_len"])
    # the weights: on the device, in one program, in the type they are
    # served in
    params = jax.jit(lambda k: llama.init_params(
        cfg, k, dtype=jnp.dtype(serve["weights_dtype"])))(
            common.prng_key(seed))
    eng = ServingEngine(cfg, params, **serve["engine"])
    env = serve["envelope"]
    warm = eng.aot_warmup(WorkloadEnvelope(
        max_prompt=env["max_prompt"], max_new_tokens=env["max_new_tokens"],
        seg_steps=(serve["seg_steps"],), resume=False))
    return cfg, params, eng, warm


def scheduler(eng, config: dict):
    from paddle_tpu.inference.scheduler import OnlineScheduler

    s = config["serve"]
    return OnlineScheduler(eng, max_queue=s["max_queue"],
                           seg_steps=s["seg_steps"])


def arrivals(reqs):
    from paddle_tpu.inference.scheduler import Arrival

    return [Arrival(r.t, r.prompt, r.max_new_tokens) for r in reqs]


def warm_serve(eng, config, workload, vocab, seed) -> None:
    """A few short requests of another seed, all due at once, so that every
    eager helper on the dispatch path is built before the window (the one
    segment program serves every length, so short generations warm what
    long ones use); then back to empty slots."""
    warm = config["serve"]["warm_serve"]
    w = dict(workload, rate_rps=float(warm["requests"]),
             gen_lens=[warm["max_new_tokens"]], gen_weights=[1])
    reqs = traffic.serve_requests(w, vocab, seed + 1, 1.0)
    for r in reqs:
        r.t = 0.0
    scheduler(eng, config).serve(arrivals(reqs))
    eng.reset_slots()


class SegmentSpans:
    """Host spans around ``engine.run_segment``; with a ``tracer``, the
    traced slice opens and closes at segment boundaries."""

    def __init__(self, eng, tracer: Optional[common.SliceTracer] = None):
        self.rows = []      # (t0, t1, steps, admitted, in_slice, tokens)
        self.first_seen = {}    # rid -> when its first token was fetched
        self.finish_seen = {}   # rid -> when its last token was fetched
        self.tracer = tracer
        inner = eng.run_segment

        def run_segment(max_steps, **kw):
            in_slice = tracer is not None and (tracer.maybe_start()
                                               or tracer.on)
            t0 = time.perf_counter()
            ev = inner(max_steps, **kw)
            t1 = time.perf_counter()
            self.rows.append((t0, t1, int(ev["steps"]),
                              len(ev["admitted"]), in_slice,
                              int(ev["tokens"])))
            for rid in ev["first_tokens"]:
                self.first_seen.setdefault(rid, t1)
            for rid in ev["finished"]:
                self.finish_seen[rid] = t1
            if tracer is not None:
                tracer.maybe_stop()
            return ev

        eng.run_segment = run_segment

    def log(self, t_open: float) -> dict:
        """Every segment of the run: when it started, host milliseconds per
        step of its loop, its steps and admissions, and the host time
        between it and the next."""
        r = self.rows
        return {
            "start_s": [round(a[0] - t_open, 3) for a in r],
            "ms_per_step": [round((a[1] - a[0]) / max(1, a[2]) * 1e3, 2)
                            for a in r],
            "steps": [a[2] for a in r], "admits": [a[3] for a in r],
            "between_ms_max": max([(b[0] - a[1]) * 1e3
                                   for a, b in zip(r, r[1:])], default=0.0),
        }

    def saturated(self, t_open: float, from_s: float, to_s: float) -> dict:
        """Tokens delivered, loop steps run and time passed from the first
        fetch at or after ``from_s`` to the last fetch inside the window of
        ``to_s`` seconds: the segments whose work lies wholly in between.
        Before ``from_s`` the slots are still filling; after ``to_s``
        nothing new is due and they empty."""
        ends = [r[1] - t_open for r in self.rows]
        a = next((i for i, e in enumerate(ends) if e >= from_s), None)
        b = max((i for i, e in enumerate(ends) if e <= to_s), default=None)
        if a is None or b is None or b <= a:
            return {"tokens": 0, "steps": 0, "seconds": 0.0}
        rows = self.rows[a + 1: b + 1]
        return {"tokens": sum(r[5] for r in rows),
                "steps": sum(r[2] for r in rows),
                "seconds": ends[b] - ends[a]}

    def slice(self) -> Optional[dict]:
        if self.tracer is None:
            return None
        rows = [r for r in self.rows if r[4]]
        inside = sum(r[1] - r[0] for r in rows)
        between = sum(b[0] - a[1] for a, b in zip(rows, rows[1:]))
        return {"segments": len(rows), "steps": sum(r[2] for r in rows),
                "admits": sum(r[3] for r in rows),
                "host_in_segment_s": inside, "host_between_s": between,
                "window_s": self.tracer.window_s}


def latencies_ms(spans: "SegmentSpans", t_open: float, reqs, rid0: int,
                 results: dict):
    """Per finished request, on this file's clock: first-token time from
    the DUE time (the window's opening + the arrival's offset) to the
    return of the segment that produced the token, and time per output
    token after the first (a last token is seen like a first one: when its
    segment returns)."""
    ttft, tpot = [], []
    for rid, t_fin in spans.finish_seen.items():
        t_first = spans.first_seen[rid]
        ttft.append((t_first - t_open - reqs[rid - rid0].t) * 1e3)
        n = len(results[rid])
        if n > 1:
            tpot.append((t_fin - t_first) / (n - 1) * 1e3)
    return ttft, tpot


def report_latencies_ms(per_request):
    """The same two from the scheduler's own stamps (rounded to 0.1 ms in
    the report): printed as a cross-check, never judged."""
    ttft = [r["ttft_s"] * 1e3 for r in per_request]
    tpot = [(r["e2e_s"] - r["ttft_s"]) / (r["gen_len"] - 1) * 1e3
            for r in per_request if r["gen_len"] > 1]
    return ttft, tpot


def kv_rows_per_decode_step(per_request, steps: int, admits: int) -> float:
    """Cached rows a decode step attends to, summed over the live slots,
    averaged over the run's decode steps: request r attends prompt + i + 1
    rows at its i-th decode step."""
    rows = 0.0
    for r in per_request:
        n = r["gen_len"] - 1
        rows += n * (r["prompt_len"] + 1) + n * (n - 1) / 2.0
    return rows / max(1, steps - admits)


def run(ctx) -> dict:
    config, workload, args = ctx["config"], ctx["workload"], ctx["args"]
    vocab = config["model"]["vocab_size"]
    cfg, params, eng, warm = build_engine(config, args.seed)
    ctx["log"]("warmup", programs={f: r["keys"] for f, r in warm.items()},
               seconds={f: r["seconds"] for f, r in warm.items()},
               paged_kernel=bool(eng.paged_kernel_active()))
    if not ctx["rehearse"] and not eng.paged_kernel_active():
        raise SystemExit("chipbench: the engine would not route attention "
                         "to the paged kernel")
    warm_serve(eng, config, workload, vocab, args.seed)
    reqs = traffic.serve_requests(workload, vocab, args.seed, args.seconds)
    arr = arrivals(reqs)
    sched = scheduler(eng, config)
    tracer = None
    if args.trace:
        tr = workload.get("trace", {})
        tracer = common.SliceTracer(
            ctx["trace_dir"], time.perf_counter(),
            tr.get("start_share", 0.35) * args.seconds,
            tr.get("length_s", 3.0))
    spans = SegmentSpans(eng, tracer)
    watch = common.HostWatch()
    gc.collect()
    ctx["open_window"]()
    t_open = watch.start()
    report = sched.serve(arr)
    ctx["close_window"]()
    host = watch.stop()
    results = sched.results()
    if tracer is not None:
        tracer.maybe_stop(force=True)
    del eng.run_segment

    per = report.per_request
    rid0 = min(r["rid"] for r in per)  # rids follow the order of arrival
    ttft, tpot = latencies_ms(spans, t_open, reqs, rid0, results)
    r_ttft, r_tpot = report_latencies_ms(per)
    done = [r for r in per
            if r["gen_len"] == reqs[r["rid"] - rid0].max_new_tokens]
    sat = spans.saturated(t_open, float(workload.get("saturated_from_s", 0.0)),
                          args.seconds)
    e2e = {
        "ttft_p95_ms": common.percentile(ttft, 0.95),
        "tpot_mean_ms": sum(tpot) / len(tpot),
        # tokens per second completed while the system is above capacity
        "serve_tokens_per_s": sat["tokens"] / max(sat["seconds"], 1e-9),
    }
    ctx["log"]("serve", requests=len(reqs), finished=len(done),
               tokens=report.total_tokens, makespan_s=report.makespan_s,
               ttft_p50_ms=common.percentile(ttft, 0.5),
               ttft_p95_ms=e2e["ttft_p95_ms"],
               tpot_p50_ms=common.percentile(tpot, 0.5),
               tpot_p95_ms=common.percentile(tpot, 0.95),
               tpot_mean_ms=e2e["tpot_mean_ms"],
               report_ttft_p95_ms=common.percentile(r_ttft, 0.95),
               report_tpot_mean_ms=sum(r_tpot) / len(r_tpot),
               serve_tokens_per_s=e2e["serve_tokens_per_s"], saturated=sat,
               tokens_per_s_over_makespan=report.total_tokens
               / report.makespan_s,
               queue_wait_p50_ms=report.queue_wait_p50_s * 1e3,
               segments=report.segments, ticks=report.ticks,
               slot_occupancy=report.slot_occupancy,
               backpressure_events=report.backpressure_events,
               backpressure_pages=report.backpressure_pages,
               # a step is one request's admission (one token) or a decode
               # tick (one token for every live slot)
               admission_step_share=len(per) / report.ticks,
               live_slots_per_decode_step=(report.total_tokens - len(per))
               / max(1, report.ticks - len(per)))
    ctx["log"]("segments", **spans.log(t_open), **host)

    # -- correct: a seeded sample of the served requests, every generated
    # token teacher-forced through the plain float32 reference. The engine
    # and its pool go first.
    n_check = config["serve"]["check_requests"]
    pick = np.random.RandomState(args.seed % (2**32)).permutation(
        len(per))[:n_check]
    slice_info = spans.slice()
    del sched, eng, spans
    gc.collect()
    env = config["serve"]["envelope"]
    pad_to = env["max_prompt"] + env["max_new_tokens"]
    wrong, verdicts = 0, []
    for i in pick:
        rid = per[i]["rid"]
        v = reference.check_generation(
            params, config["model"], reqs[rid - rid0].prompt, results[rid],
            pad_to, env["max_new_tokens"], f"request {rid - rid0}")
        verdicts.append(v)
        wrong += not v["ok"]
    ctx["log"]("check", requests=len(verdicts),
               tokens=sum(v["checked"] for v in verdicts),
               exact=sum(v["exact"] for v in verdicts),
               ties=sum(v["ties"] for v in verdicts),
               worst_sigmas=max(v["worst_sigmas"] for v in verdicts),
               wrong=wrong)
    unfinished = len(reqs) - len(done)
    rep = report.as_dict(with_requests=True)
    return {
        "kind": "serve", "attempted": len(reqs),
        "failed": unfinished + wrong,
        "correct": wrong == 0 and unfinished == 0,
        "end_to_end": e2e, "report": rep, "slice": slice_info,
        "saturated": sat,
        "kv_rows_per_decode_step": kv_rows_per_decode_step(
            per, report.ticks, len(per)),
    }
