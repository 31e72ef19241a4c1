"""kind ``serve_latent_moe``: kind ``serve``'s open loop (its spans, its
clock, its latency arithmetic, its ``saturated`` span) over ONE CHIP'S SHARE
of a latent-attention sparse-expert decoder
(``paddle_tpu.models.latent_moe``), with three things of its own:

* **the engine build.** The configuration file's top-level keys are the
  public config.json's (cut as its ``reduced`` says) and ``share`` says
  what of the router's experts and of the vocabulary is held here; the
  weights are made on the device from the seed, in the type they are served
  in; the run exits unless attention is routed to ``mla_paged_attention``
  and the held experts to ``grouped_expert_matmul``.
* **an opening backlog.** ``backlog`` requests of the workload's own mix are
  due at 0 s, before the Poisson arrivals at ``rate_rps``: a chip that joins
  a decode pool under load is handed a full queue, and the slots fill in
  one admission a step instead of at the arrival rate.
* **the check**, ``reference_latent_moe.check_generation``: routing is
  discontinuous, so a generated token is held to the float32 reference
  under the position's legitimate routings (the module's text says how);
  the run is correct when the share of tokens beyond the tie band under
  all of them, the share left unjudged, and the error of the program's
  own logits of the served sequences (the median over the positions with
  one legitimate routing, in units of the reference's measured bf16 error)
  are within their limits; the numbers compared are printed on the ``check`` line beside their limits.

The segments' counters (``serving.moe.*``: picks, picks held here, held
experts hit, the largest load of one expert) are fetched with the tokens;
this kind keeps each segment's and sums them over the ``saturated`` span and
over the traced slice. In a traced run the program's scope table
(``profiler._xplane.parse``) is read before the harness removes the trace.

``python3 -m chipbench.kinds.serve_latent_moe --workload <cell> --rates ..
--seconds .. --out <file.md>`` is ``chipbench/sweep.py`` over this kind's
engine (the knee, found once).
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from .. import common, reference_latent_moe as reference, traffic
from . import serve

# public config.json key -> LatentMoEConfig field (the cut ones come from
# the file's ``share``)
MODEL_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_hidden_layers": "num_layers",
    "first_k_dense_replace": "first_k_dense",
    "num_attention_heads": "num_heads", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "routed_scaling_factor": "routed_scaling_factor",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
}


def model_config(config: dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import latent_moe

    share = config["share"]
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields.update(
        n_routed_experts=share["router_width"],
        held_experts=tuple(share["held_experts"]),
        vocab_size=config["vocab_size"],
        vocab_slice=(0, config["vocab_size"]),
        dtype=jnp.dtype(config["torch_dtype"]).type)
    fields.update(config.get("program", {}))
    fields.update(over)
    return latent_moe.LatentMoEConfig(**fields)


def init_weights(cfg, seed: int, dtype):
    """The share's weights: on the device, in one program, in the type
    they are served in."""
    import jax

    from paddle_tpu.models import latent_moe

    return jax.jit(lambda k: latent_moe.init_params(cfg, k, dtype=dtype))(
        common.prng_key(seed))


def build_engine(config: dict, seed: int):
    import jax.numpy as jnp

    from paddle_tpu.inference.program_space import WorkloadEnvelope
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    sv = config["serve"]
    cfg = model_config(config, max_seq_len=sv["engine"]["max_len"])
    params = init_weights(cfg, seed, jnp.dtype(sv["weights_dtype"]))
    eng = ServingEngine(cfg, params, **sv["engine"])
    env = sv["envelope"]
    warm = eng.aot_warmup(WorkloadEnvelope(
        max_prompt=env["max_prompt"], max_new_tokens=env["max_new_tokens"],
        seg_steps=(sv["seg_steps"],), resume=False))
    return cfg, params, eng, warm


def requests(workload: dict, vocab: int, seed: int, seconds: float):
    """The opening backlog (due at 0 s), then the arrivals: both of the
    workload's mix, each a fixed multiset in an order from the seed."""
    n = int(workload.get("backlog", 0))
    first = []
    if n:
        first = traffic.serve_requests(
            dict(workload, rate_rps=n / seconds), vocab, seed + 7919,
            seconds)
        for r in first:
            r.t = 0.0
    return first + traffic.serve_requests(workload, vocab, seed, seconds)


class CountedSpans(serve.SegmentSpans):
    """``SegmentSpans`` that also keeps each segment's counters."""

    def __init__(self, eng, tracer=None):
        super().__init__(eng, tracer)
        self.counters = []
        inner = eng.run_segment

        def run_segment(max_steps, **kw):
            ev = inner(max_steps, **kw)
            self.counters.append(ev.get("counters") or {})
            return ev

        eng.run_segment = run_segment

    def counted(self, keep) -> dict:
        """The counters summed over the segments ``keep(i, row)`` names
        (a ``max_*`` counter: its maximum), and their steps."""
        out = {"steps": 0}
        for i, (row, c) in enumerate(zip(self.rows, self.counters)):
            if not keep(i, row):
                continue
            out["steps"] += row[2]
            for k, v in c.items():
                out[k] = max(out.get(k, 0), v) if k.startswith("max_") \
                    else out.get(k, 0) + v
        return out

    def saturated_counters(self, t_open, from_s, to_s) -> dict:
        """Over the segments ``saturated`` counts."""
        ends = [r[1] - t_open for r in self.rows]
        a = next((i for i, e in enumerate(ends) if e >= from_s), None)
        b = max((i for i, e in enumerate(ends) if e <= to_s), default=None)
        if a is None or b is None or b <= a:
            return {"steps": 0}
        return self.counted(lambda i, row: a < i <= b)


def program_logits(cfg, params, pad_to: int, page_size: int):
    """tokens [pad_to] -> the program's own logits [pad_to, V] of that one
    sequence: ``forward_with_pages`` (its kernels, its latent cache) as one
    chunk at position 0 over a pool of its own."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import latent_moe

    pages = -(-pad_to // page_size)
    table = 1 + jnp.arange(pages, dtype=jnp.int32)[None]

    @jax.jit
    def logits(params, tokens):
        pool = latent_moe.init_paged_pool(cfg, pages + 1, page_size)
        out, _ = latent_moe.forward_with_pages(
            params, tokens[None], cfg, pool, table,
            jnp.zeros((1,), jnp.int32), logits_all=True)
        return out[0]

    return lambda tokens: logits(params, jnp.asarray(tokens, jnp.int32))


def scope_seconds(trace_dir: str):
    """Device seconds by the program's scope path, from the trace the
    harness is about to reduce and remove; None where there is none."""
    from paddle_tpu.profiler import _xplane

    tables, _ = _xplane.parse(trace_dir)
    if not tables or not tables["scopes"]:
        return None
    return {k: v[1] / 1e9 for k, v in tables["scopes"].items()}


def run(ctx) -> dict:
    config, workload, args = ctx["config"], ctx["workload"], ctx["args"]
    vocab = config["vocab_size"]
    cfg, params, eng, warm = build_engine(config, args.seed)
    from paddle_tpu.ops.pallas import grouped_matmul, mla_attention

    kernels = {"mla_paged_attention": mla_attention.selection_count(),
               "grouped_expert_matmul": grouped_matmul.selection_count()}
    ctx["log"]("warmup", programs={f: r["keys"] for f, r in warm.items()},
               seconds={f: r["seconds"] for f, r in warm.items()},
               temp_bytes={f: r["temp_bytes"] for f, r in warm.items()},
               pool_bytes=eng.pool_bytes, kernels_routed_to=kernels)
    if not ctx["rehearse"] and not (eng.paged_kernel_active()
                                    and all(kernels.values())):
        raise SystemExit(f"chipbench: the engine would not route to the "
                         f"latent paged kernel and the grouped expert "
                         f"kernel ({kernels})")
    serve.warm_serve(eng, config, workload, vocab, args.seed)
    reqs = requests(workload, vocab, args.seed, args.seconds)
    sched = serve.scheduler(eng, config)
    tracer = None
    if args.trace:
        tr = workload.get("trace", {})
        tracer = common.SliceTracer(
            ctx["trace_dir"], time.perf_counter(),
            tr.get("start_share", 0.35) * args.seconds,
            tr.get("length_s", 3.0))
    spans = CountedSpans(eng, tracer)
    watch = common.HostWatch()
    gc.collect()
    ctx["open_window"]()
    t_open = watch.start()
    report = sched.serve(serve.arrivals(reqs))
    ctx["close_window"]()
    host = watch.stop()
    results = sched.results()
    if tracer is not None:
        tracer.maybe_stop(force=True)
    del eng.run_segment

    per = report.per_request
    rid0 = min(r["rid"] for r in per)  # rids follow the order of arrival
    done = [r for r in per
            if r["gen_len"] == reqs[r["rid"] - rid0].max_new_tokens]
    from_s = float(workload.get("saturated_from_s", 0.0))
    sat = spans.saturated(t_open, from_s, args.seconds)
    sat_counts = spans.saturated_counters(t_open, from_s, args.seconds)
    e2e = {"serve_tokens_per_s": sat["tokens"] / max(sat["seconds"], 1e-9)}
    ttft, _ = serve.latencies_ms(spans, t_open, reqs, rid0, results)
    ctx["log"]("serve", requests=len(reqs), finished=len(done),
               tokens=report.total_tokens, makespan_s=report.makespan_s,
               serve_tokens_per_s=e2e["serve_tokens_per_s"], saturated=sat,
               saturated_counters=sat_counts, moe=report.moe,
               ttft_p50_ms=common.percentile(ttft, 0.5),
               ttft_p95_ms=common.percentile(ttft, 0.95),
               tokens_per_s_over_makespan=report.total_tokens
               / report.makespan_s,
               segments=report.segments, ticks=report.ticks,
               slot_occupancy=report.slot_occupancy,
               backpressure_events=report.backpressure_events,
               backpressure_pages=report.backpressure_pages,
               pages=report.pages,
               admission_step_share=len(per) / report.ticks,
               live_slots_per_decode_step=(report.total_tokens - len(per))
               / max(1, report.ticks - len(per)))
    ctx["log"]("segments", **spans.log(t_open), **host)
    slice_info = spans.slice()
    slice_counts = spans.counted(lambda i, row: row[4]) if tracer else None
    scopes = scope_seconds(ctx["trace_dir"]) if tracer else None

    # -- correct: a seeded sample of the served requests, every generated
    # token (the first ``check_rows`` of each) teacher-forced through the
    # plain float32 reference at the published widths. The engine and its
    # pool go first: the reference casts a layer at a time beside the
    # weights.
    sv = config["serve"]
    pick = np.random.RandomState(args.seed % (2**32)).permutation(
        len(per))[:sv["check_requests"]]
    served = eng.params     # what the engine served with
    del sched, eng, spans
    gc.collect()
    env = sv["envelope"]      # causal: rows past the checked ones are cut
    pad_to = env["max_prompt"] + sv["check_rows"]
    program = program_logits(cfg, served, pad_to, sv["engine"]["page_size"])
    verdicts = []
    for i in pick:
        rid = per[i]["rid"]
        verdicts.append(reference.check_generation(
            params, config, config["share"], reqs[rid - rid0].prompt,
            results[rid], pad_to, sv["check_rows"],
            f"request {rid - rid0}", program))
    checked = sum(v["checked"] for v in verdicts)
    errors = sorted(e for v in verdicts for e in v["logit_errors"])
    shares = {"beyond_share": sum(v["beyond"] for v in verdicts) / checked,
              "unjudged_share": sum(v["unjudged"] for v in verdicts)
              / checked,
              "logit_error": errors[len(errors) // 2]}
    limits = {"beyond_share": reference.BEYOND_SHARE_MAX,
              "unjudged_share": reference.UNJUDGED_SHARE_MAX,
              "logit_error": reference.LOGIT_ERROR_MAX}
    ok = all(shares[k] <= limits[k] for k in limits)
    ctx["log"]("check", requests=len(verdicts), tokens=checked,
               exact=sum(v["exact"] for v in verdicts),
               ties=sum(v["ties"] for v in verdicts),
               explained=sum(v["explained"] for v in verdicts),
               passes=sum(v["passes"] for v in verdicts),
               worst_sigmas=max(v["worst_sigmas"] for v in verdicts),
               tie_sigmas=reference.TIE_SIGMAS,
               beyond_worst_sigmas=max(v["beyond_worst_sigmas"]
                                       for v in verdicts),
               router_sigma=max(v["router_sigma"] for v in verdicts),
               clean_positions=len(errors),
               logit_error_p90=errors[int(len(errors) * 0.9)],
               **shares, **{k + "_limit": v for k, v in limits.items()},
               ok=ok)
    unfinished = len(reqs) - len(done)
    wrong = 0 if ok else sum(v["beyond"] + v["unjudged"] > 0
                             for v in verdicts)
    return {
        "kind": "serve_latent_moe", "attempted": len(reqs),
        "failed": unfinished + wrong,
        "correct": ok and unfinished == 0,
        "end_to_end": e2e, "report": report.as_dict(with_requests=True),
        "slice": slice_info, "slice_counters": slice_counts,
        "saturated": sat, "saturated_counters": sat_counts,
        "scopes": scopes,
        "kv_rows_per_decode_step": serve.kv_rows_per_decode_step(
            per, report.ticks, len(per)),
    }


if __name__ == "__main__":   # the knee: chipbench/sweep.py, this engine
    from chipbench import run as runner, sweep

    serve.build_engine = build_engine
    load_cell = runner.load_cell

    def load_cell_with_vocab(*a):
        cell, config, workload = load_cell(*a)
        return cell, dict(config, model={"vocab_size": config["vocab_size"]}), \
            workload

    runner.load_cell = load_cell_with_vocab
    sys.exit(sweep.main())
