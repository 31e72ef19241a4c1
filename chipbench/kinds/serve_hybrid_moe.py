"""kind ``serve_hybrid_moe``: kind ``serve``'s open loop (its spans, its
clock, its ``saturated`` span) and kind ``serve_latent_moe``'s opening
backlog and counted spans, over ONE CHIP'S SHARE of a window / full
attention sparse-expert decoder (``paddle_tpu.models.hybrid_moe``) whose
sequences each hold row pages for the full layers AND a fixed part for the
window layers. Its own:

* **the engine build.** The configuration file's top-level keys are the
  public config.json's (cut as its ``reduced`` says; the per-layer lists
  are kept whole and the first ``num_hidden_layers`` entries taken) and
  ``share`` says what of the router's experts and of the vocabulary is
  held here; the weights are made on the device from the seed, in the type
  they are served in; the run exits unless the ticks are routed to the
  paged kernel over BOTH caches, the admissions to the windowed prefill
  kernel and the held experts to ``grouped_expert_matmul``.
* **the check** (``reference_hybrid_moe``; the rule is cell 4's, imported):
  a seeded sample of the served requests, at least one with a prompt of
  3,072 or more and one of 512 or less, the prompt and the first
  ``check_rows`` answered tokens of each teacher-forced through the float32
  reference at the published widths. (a) every token the timed path chose
  is the reference's under the position's legitimate routings or within
  the bf16 tie band (``beyond_share``, ``unjudged_share``); (b) the
  program's own logits of those sequences — replayed through
  ``forward_with_pages`` as the engine drives it: one admission of the
  bucket's width a request, then the ticks of all of them together through
  the paged kernel over the row pages and the fixed parts — lie within
  their limits of the reference's, in units of the reference's measured
  bf16 error: the median and the 90th percentile over the positions with
  one legitimate routing. The numbers compared are printed on the ``check``
  line beside their limits.

The segments' counters (``serving.moe.*`` and ``serving.window.*``: key
rows the ticks' full and window layers attended, the admissions' bucket
rows and prompt rows) are fetched with the tokens; they are summed over the
``saturated`` span and over the traced slice.

``python3 -m chipbench.kinds.serve_hybrid_moe --workload <cell> --rates ..
--seconds .. --out <file.md>`` is ``chipbench/sweep.py`` over this kind's
engine behind the cell's backlog (the knee, found once). ``... --control
low_precision|window --workload <cell> --seed <n> --seconds <s>`` is a
builder's control run: the cell served with 3 mantissa bits in its attention
projections, or by a program whose full layer is rotated too; either must
print ``"correct": false``.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from .. import common, reference_hybrid_moe as reference
from . import serve
from .serve_latent_moe import CountedSpans, requests, scope_seconds

# public config.json key -> HybridMoEConfig field (the cut ones come from
# the file's ``share``)
MODEL_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_hidden_layers": "num_layers",
    "first_k_dense_replace": "first_k_dense",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "sliding_window": "sliding_window",
    "num_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "routed_scaling_factor": "routed_scaling_factor",
    "rms_norm_eps": "rms_eps",
}
ATTENTION_PROJECTIONS = ("wq", "wk", "wv", "wo")


def model_config(config: dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import hybrid_moe

    share = config["share"]
    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields.update(
        layer_types=tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        n_routed_experts=share["router_width"],
        held_experts=tuple(share["held_experts"]),
        vocab_size=config["vocab_size"],
        vocab_slice=(0, config["vocab_size"]),
        dtype=jnp.dtype(config["torch_dtype"]).type)
    fields.update(over)
    return hybrid_moe.HybridMoEConfig(**fields)


def init_weights(cfg, seed: int, dtype):
    """The share's weights: on the device, in one program, in the type
    they are served in."""
    import jax

    from paddle_tpu.models import hybrid_moe

    return jax.jit(lambda k: hybrid_moe.init_params(cfg, k, dtype=dtype))(
        common.prng_key(seed))


def build_engine(config: dict, seed: int, degrade=None):
    """``degrade``: what a control run does to the weights the engine
    serves with."""
    import jax.numpy as jnp

    from paddle_tpu.inference.program_space import WorkloadEnvelope
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    sv = config["serve"]
    cfg = model_config(config, max_seq_len=sv["engine"]["max_len"])
    params = init_weights(cfg, seed, jnp.dtype(sv["weights_dtype"]))
    eng = ServingEngine(cfg, degrade(params) if degrade else params,
                        **sv["engine"])
    env = sv["envelope"]
    warm = eng.aot_warmup(WorkloadEnvelope(
        max_prompt=env["max_prompt"], max_new_tokens=env["max_new_tokens"],
        seg_steps=(sv["seg_steps"],), resume=False))
    return cfg, params, eng, warm


def low_precision(params):
    """The tree with 3 mantissa bits left in the attention projections
    (``reduce_precision``: a cast to a narrow type and back is elided
    under jit). Only the projections go through the program: what a jit
    returns is a copy, and the tree does not fit beside itself."""
    import jax

    cut = jax.jit(lambda ws: [jax.lax.reduce_precision(w, 8, 3) for w in ws])
    return dict(params, layers=[
        dict(lp, **dict(zip(ATTENTION_PROJECTIONS, cut(
            [lp[k] for k in ATTENTION_PROJECTIONS]))))
        for lp in params["layers"]])


def pick_checked(per, n: int, seed: int, long_from: int = 3072,
                 short_to: int = 512):
    """``n`` of the finished requests ``per``, in an order from the seed:
    the order's first prompt of ``long_from`` rows or more, its first of
    ``short_to`` or less, then its first others."""
    order = [per[i] for i in np.random.RandomState(
        seed % (2**32)).permutation(len(per))]
    picked = [r for r in (
        next((r for r in order if r["prompt_len"] >= long_from), None),
        next((r for r in order if r["prompt_len"] <= short_to), None))
        if r is not None]
    picked += [r for r in order
               if not any(r is p for p in picked)][:n - len(picked)]
    return [r["rid"] for r in picked]


def replay_logits(cfg, params, sequences, width: int, rows: int,
                  page_size: int):
    """The program's own logits of ``sequences`` (each (prompt, generated))
    at their first ``rows`` generated positions, [n, rows, V]: one
    admission of ``width`` rows a sequence into row pages and a fixed part
    of its own, then ``rows - 1`` ticks of all of them together, fed the
    generated tokens."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import hybrid_moe as model

    n = len(sequences)
    pages = -(-(width + rows) // page_size)
    table = np.concatenate(
        [1 + np.arange(n * pages, dtype=np.int32).reshape(n, pages),
         1 + np.arange(n, dtype=np.int32)[:, None]], 1)
    table = jnp.asarray(table)
    zero = jnp.zeros((1,), jnp.int32)

    @jax.jit
    def admit(params, pool, tokens, row, last):
        return model.forward_with_pages(params, tokens, cfg, pool, row,
                                        zero, logit_pos=last)

    @jax.jit
    def tick(params, pool, tokens, pos):
        return model.forward_with_pages(params, tokens, cfg, pool, table,
                                        pos)

    pool = model.init_paged_pool(cfg, n * pages + 1, page_size,
                                 fixed_parts=n + 1)
    out = np.zeros((n, rows, cfg.vocab[1]), np.float32)
    for b, (prompt, _) in enumerate(sequences):
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(prompt)] = prompt
        logits, pool = admit(params, pool, jnp.asarray(padded),
                             table[b:b + 1], jnp.int32(len(prompt) - 1))
        out[b, 0] = np.asarray(logits[0])
    pos = np.array([len(p) for p, _ in sequences], np.int32)
    for i in range(rows - 1):
        fed = np.array([[g[i]] for _, g in sequences], np.int32)
        logits, pool = tick(params, pool, jnp.asarray(fed),
                            jnp.asarray(pos + i))
        out[:, i + 1] = np.asarray(logits)
    return out


def check(cfg, params, served, config, sequences, names) -> dict:
    """The rule of this module's text over ``sequences`` (what the timed
    path served): ``params`` the true weights (the reference's),
    ``served`` what the engine served with (the replay's). The ``check``
    line's fields, ``ok`` among them."""
    sv = config["serve"]
    rows, width = sv["check_rows"], sv["envelope"]["max_prompt"]
    t0 = time.perf_counter()
    program = replay_logits(cfg, served, sequences, width, rows,
                            sv["engine"]["page_size"])
    t1 = time.perf_counter()
    # causal: rows past the checked ones are cut; one length for every
    # request, so one set of the reference's programs
    pad_to = width + rows
    verdicts = [reference.check_generation(
        params, config, config["share"], prompt, gen, pad_to, rows, name,
        program[b])
        for b, ((prompt, gen), name) in enumerate(zip(sequences, names))]
    t2 = time.perf_counter()
    checked = sum(v["checked"] for v in verdicts)
    errors = sorted(e for v in verdicts for e in v["logit_errors"])
    got = {"beyond_share": sum(v["beyond"] for v in verdicts) / checked,
           "unjudged_share": sum(v["unjudged"] for v in verdicts) / checked,
           "logit_error": errors[len(errors) // 2],
           "logit_error_p90": errors[int(len(errors) * 0.9)]}
    limits = {"beyond_share": reference.BEYOND_SHARE_MAX,
              "unjudged_share": reference.UNJUDGED_SHARE_MAX,
              "logit_error": reference.LOGIT_ERROR_MAX,
              "logit_error_p90": reference.LOGIT_ERROR_P90_MAX}
    ok = all(got[k] <= limits[k] for k in limits)
    return dict(
        requests=len(verdicts), tokens=checked,
        prompt_lens=[len(p) for p, _ in sequences],
        exact=sum(v["exact"] for v in verdicts),
        ties=sum(v["ties"] for v in verdicts),
        explained=sum(v["explained"] for v in verdicts),
        passes=sum(v["passes"] for v in verdicts),
        worst_sigmas=max(v["worst_sigmas"] for v in verdicts),
        tie_sigmas=reference.TIE_SIGMAS,
        beyond_worst_sigmas=max(v["beyond_worst_sigmas"] for v in verdicts),
        router_sigma=max(v["router_sigma"] for v in verdicts),
        clean_positions=len(errors), logit_error_max=errors[-1],
        replay_s=t1 - t0, reference_s=t2 - t1,
        wrong=sum(v["beyond"] + v["unjudged"] > 0 for v in verdicts),
        **got, **{k + "_limit": v for k, v in limits.items()}, ok=ok)


def run(ctx, degrade=None) -> dict:
    """``degrade`` (of the served weights) is a control run's."""
    config, workload, args = ctx["config"], ctx["workload"], ctx["args"]
    vocab = config["vocab_size"]
    cfg, params, eng, warm = build_engine(config, args.seed, degrade)
    from paddle_tpu.ops.pallas import (grouped_matmul, paged_attention,
                                       window_attention)

    kernels = {"ragged_paged_attention": paged_attention.selection_count(),
               "windowed_prefill_attention":
               window_attention.selection_count(),
               "grouped_expert_matmul": grouped_matmul.selection_count()}
    ctx["log"]("warmup", programs={f: r["keys"] for f, r in warm.items()},
               seconds={f: r["seconds"] for f, r in warm.items()},
               temp_bytes={f: r["temp_bytes"] for f, r in warm.items()},
               pool_bytes=eng.pool_bytes, pages=eng.pager.stats(),
               kernels_routed_to=kernels)
    if not ctx["rehearse"] and not (eng.paged_kernel_active()
                                    and all(kernels.values())):
        raise SystemExit(f"chipbench: the engine would not route to the "
                         f"paged kernel over both caches, the windowed "
                         f"prefill kernel and the grouped expert kernel "
                         f"({kernels})")
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
               window=report.counters.get("window"),
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

    # -- correct: a seeded sample of the served requests. The engine and
    # its pool go first: the replay holds pages of its own and the
    # reference casts a layer at a time beside the weights.
    sv = config["serve"]
    rids = pick_checked(done, sv["check_requests"], args.seed)
    served = eng.params     # what the engine served with
    del sched, eng, spans
    gc.collect()
    verdict = check(
        cfg, params, served, config,
        [(reqs[rid - rid0].prompt, results[rid][:sv["check_rows"]])
         for rid in rids],
        [f"request {rid - rid0}" for rid in rids])
    ctx["log"]("check", **verdict)
    ok = verdict["ok"]
    unfinished = len(reqs) - len(done)
    return {
        "kind": "serve_hybrid_moe", "attempted": len(reqs),
        "failed": unfinished + (0 if ok else verdict["wrong"] or len(rids)),
        "correct": ok and unfinished == 0,
        "end_to_end": e2e, "report": report.as_dict(with_requests=True),
        "slice": slice_info, "slice_counters": slice_counts,
        "saturated": sat, "saturated_counters": sat_counts,
        "scopes": scopes,
    }


def control(argv) -> int:
    """A builder's control run of one cell (the module's text)."""
    import argparse
    import json
    import os

    from chipbench import run as runner

    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=("low_precision", "window"),
                    required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    args.trace = 0
    manifest = runner.load_json(os.path.join(runner.ROOT, "BENCHMARK.json"))
    _, config, workload = runner.load_cell(runner.ROOT, manifest,
                                           args.workload)
    import paddle_tpu as paddle

    paddle.jit.enable_persistent_cache()
    ctx = {"args": args, "config": config, "workload": workload,
           "rehearse": False, "trace_dir": None,
           "log": lambda phase, **f: print(json.dumps(
               {"phase": phase, **f}), flush=True),
           "open_window": lambda: None, "close_window": lambda: None}
    if args.control == "low_precision":
        record = run(ctx, low_precision)
    else:
        # the other reading of the one assumed item a run can tell apart,
        # planted from outside: no configuration has it
        from paddle_tpu.models import hybrid_moe

        hybrid_moe.ROTARY_KINDS = (hybrid_moe.WINDOW, hybrid_moe.FULL)
        record = run(ctx)
    print(json.dumps({"control": args.control,
                      "correct": bool(record["correct"]),
                      "failed": int(record["failed"]),
                      "serve_tokens_per_s":
                      record["end_to_end"]["serve_tokens_per_s"]}))
    return 0


if __name__ == "__main__":
    if "--control" in sys.argv:
        sys.exit(control(sys.argv[1:]))
    # the knee: ``serve_retention``'s sweep behind the backlog, this engine
    from . import serve_retention

    serve_retention.build_engine = build_engine
    sys.exit(serve_retention.sweep_with_backlog(sys.argv[1:]))
