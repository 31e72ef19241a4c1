"""kind ``serve_retention``: kind ``serve``'s open loop (its spans, its
clock, its ``saturated`` span) and kind ``serve_latent_moe``'s opening
backlog and counted spans, over a power-retention decoder
(``paddle_tpu.models.power_retention``) whose sequences each hold ONE state
page. Its own:

* **the engine build.** The configuration file's top-level keys are the
  public config.json's (cut as its ``reduced`` says); the engine is built
  with ``page_size = max_len`` (a page is a sequence's state); the weights
  are made on the device from the seed, in the type they are served in; the
  run exits unless the ticks are routed to ``power_retention_decode``.
* **the check** (``reference_power_retention``: no routing to excuse): a
  seeded sample of the served requests, the prompt and the first
  ``check_rows`` answered tokens of each teacher-forced through the float32
  reference at the published widths. (a) every token the timed path chose
  is the reference's choice or within the bf16 tie band of it; (b) the
  program's own logits of those sequences — replayed through
  ``forward_with_pages`` as the engine drives it: one admission of the
  bucket's width a request, then the ticks of all of them together through
  the decode kernel and the state pages — lie within their limits of the
  reference's, in units of the reference's measured bf16 error: the median,
  the median over each request's last quarter, and the 90th percentile (a
  fault in one request of four, or in a share of the positions, moves the
  tail and not the median); (c) **the states the timed programs leave**:
  after the window the SAME engine object — the compiled segment program,
  every slot live — serves the sample's prompts again, one a slot, for
  ``probe_segments`` segments; then the state page of every slot, as that
  program's admissions and ticks left it, gives the logits of the slot's
  next position, and the farthest of them from the reference's is limited
  (``state_logit_error``): the tokens say little about a state kept in too
  few bits (the bf16 control passes (a)), the pages say it. The numbers
  compared are printed on the ``check`` line beside their limits.

The segments' counters (``serving.retention.*``: state pages the ticks
updated, the admissions' bucket rows and prompt rows) are fetched with the
tokens; they are summed over the ``saturated`` span and over the traced
slice. In a traced run the program's scope table is read before the harness
removes the trace.

``python3 -m chipbench.kinds.serve_retention --workload <cell> --rates ..
--seconds .. --out <file.md>`` is ``chipbench/sweep.py`` over this kind's
engine (the knee, found once). ``... --control state_bf16|no_decay
--workload <cell> --seed <n> --seconds <s>`` is a builder's control run:
the cell with its state planes in bfloat16, or checked against a reference
that drops the decay; either must print ``"correct": false``.
"""

from __future__ import annotations

import functools
import gc
import sys
import time

import numpy as np

from .. import common, reference_power_retention as reference
from . import serve
from .serve_latent_moe import CountedSpans, requests, scope_seconds

# the limits of (b) and (c), each between two readings on the chip (PERF.md
# §4 and §6, PR 34): the largest the change read over its seeds, and the
# smallest the control with the state planes in bfloat16 read
LOGIT_ERROR_MAX = 1.2
LOGIT_ERROR_LATE_MAX = 1.2
LOGIT_ERROR_P90_MAX = 1.3
STATE_LOGIT_ERROR_MAX = 1.25

# public config.json key -> PowerRetentionConfig field
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
}


def model_config(config: dict, **over):
    import jax.numpy as jnp

    from paddle_tpu.models import power_retention

    fields = {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}
    fields["dtype"] = jnp.dtype(config["torch_dtype"]).type
    return power_retention.PowerRetentionConfig(**fields, **over)


def init_weights(cfg, seed: int, dtype):
    """The weights: on the device, in one program, in the type they are
    served in."""
    import jax

    from paddle_tpu.models import power_retention

    return jax.jit(lambda k: power_retention.init_params(
        cfg, k, dtype=dtype))(common.prng_key(seed))


def build_engine(config: dict, seed: int, **over):
    """``over``: fields of the model's config a control run changes."""
    import jax.numpy as jnp

    from paddle_tpu.inference.program_space import WorkloadEnvelope
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    sv = config["serve"]
    if sv["engine"]["page_size"] != sv["engine"]["max_len"]:
        raise SystemExit("chipbench: a state page is a sequence: the "
                         "engine's page_size must be its max_len")
    cfg = model_config(config, max_seq_len=sv["engine"]["max_len"], **over)
    params = init_weights(cfg, seed, jnp.dtype(sv["weights_dtype"]))
    eng = ServingEngine(cfg, params, **sv["engine"])
    env = sv["envelope"]
    warm = eng.aot_warmup(WorkloadEnvelope(
        max_prompt=env["max_prompt"], max_new_tokens=env["max_new_tokens"],
        seg_steps=(sv["seg_steps"],), resume=False))
    return cfg, params, eng, warm


def replay_logits(cfg, params, sequences, width: int, rows: int):
    """The program's own logits of ``sequences`` (each (prompt, generated))
    at their first ``rows`` generated positions, [n, rows, V]: one
    admission of ``width`` rows a sequence into a state page of its own,
    then ``rows - 1`` ticks of all of them together, fed the generated
    tokens."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import power_retention as model

    n = len(sequences)
    table = 1 + jnp.arange(n, dtype=jnp.int32)[:, None]
    zero = jnp.zeros((1,), jnp.int32)

    @jax.jit
    def admit(params, pool, tokens, page, last):
        return model.forward_with_pages(params, tokens, cfg, pool, page,
                                        zero, logit_pos=last)

    @jax.jit
    def tick(params, pool, tokens, pos):
        return model.forward_with_pages(params, tokens, cfg, pool, table,
                                        pos)

    pool = model.init_paged_pool(cfg, n + 1, cfg.max_seq_len)
    out = np.zeros((n, rows, cfg.vocab_size), np.float32)
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


def probe_states(eng, cfg, prompts, seg_steps: int, segments: int):
    """(c) of the module's text. The engine's own segment program serves
    ``prompts`` (one a slot, none ends before the last segment does) for
    ``segments`` segments; then ONE tick over the engine's pool, which the
    engine gives up to it, reads every slot's state page as that program
    left it. Returns a (prompt, tokens the program chose, float32 logits
    [V] of the position after them) a slot. The engine is torn down."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import power_retention as model

    eng.reset_slots()
    for prompt in prompts:
        eng.add_request(prompt, seg_steps * segments)
    for _ in range(segments):
        eng.run_segment(seg_steps)
    pages = [eng.pager.slot_pages[s][0] for s in range(eng.slots)]
    pool, eng.pager.pool = eng.pager.pool, None
    held = eng.abort()                  # the live requests, by slot
    assert len(held) == eng.slots, "a probed request ended early"

    @functools.partial(jax.jit, donate_argnums=(1,))
    def tick(params, pool, tokens, table, pos):
        logits, pool = model.forward_with_pages(params, tokens, cfg, pool,
                                                table, pos)
        return logits.astype(jnp.float32), pool

    logits, pool = tick(
        eng.params, pool, jnp.asarray([[r.tokens[-1]] for r in held]),
        jnp.asarray(pages, jnp.int32)[:, None],
        jnp.asarray([len(r.prompt) + len(r.tokens) - 1 for r in held],
                    jnp.int32))
    del pool
    logits = np.asarray(logits)
    return [(r.prompt, list(r.tokens), logits[s])
            for s, r in enumerate(held)]


def check(cfg, params, config, sequences, names, faults=(),
          probes=()) -> dict:
    """The rule of this module's text over ``sequences`` (what the timed
    path served) and ``probes`` (``probe_states``'); the ``check`` line's
    fields, ``ok`` among them."""
    sv = config["serve"]
    rows = sv["check_rows"]
    pad_to = sv["envelope"]["max_prompt"] + rows
    program = replay_logits(cfg, params, sequences,
                            sv["envelope"]["max_prompt"], rows)
    probes = list(probes)
    verdicts, state_errors = [], []
    for b, ((prompt, gen), name) in enumerate(zip(sequences, names)):
        # a probe of this prompt chose the served tokens again (one
        # program, one input): its reference row is among this request's
        gen = list(gen)
        mine = [q for q in probes if np.array_equal(q[0], prompt)
                and q[1] == gen[:len(q[1])] and len(q[1]) < len(gen)]
        probes = [q for q in probes if not any(q is m for m in mine)]
        verdicts.append(reference.check_generation(
            params, config, prompt, gen, pad_to, rows, name, program[b],
            faults, [(len(q[1]), q[2]) for q in mine]))
        state_errors += verdicts[-1]["probe_errors"]
    for prompt, toks, logits in probes:     # any other: a pass of its own
        state_errors += reference.check_generation(
            params, config, prompt, toks + [0], pad_to, rows, None, None,
            faults, [(len(toks), logits)])["probe_errors"]
    errors = sorted(e for v in verdicts for e in v["logit_errors"])
    late = sorted(e for v in verdicts
                  for e in v["logit_errors"][-(rows // 4):])
    got = {"worst_sigmas": max(v["worst_sigmas"] for v in verdicts),
           "logit_error": errors[len(errors) // 2],
           "logit_error_late": late[len(late) // 2],
           "logit_error_p90": errors[int(len(errors) * 0.9)],
           "state_logit_error": max(state_errors, default=0.0)}
    limits = {"worst_sigmas": reference.TIE_SIGMAS,
              "logit_error": LOGIT_ERROR_MAX,
              "logit_error_late": LOGIT_ERROR_LATE_MAX,
              "logit_error_p90": LOGIT_ERROR_P90_MAX,
              "state_logit_error": STATE_LOGIT_ERROR_MAX}
    ok = all(got[k] <= limits[k] for k in limits)
    return dict(
        requests=len(verdicts),
        tokens=sum(v["checked"] for v in verdicts),
        exact=sum(v["exact"] for v in verdicts),
        ties=sum(v["ties"] for v in verdicts),
        beyond=sum(v["beyond"] for v in verdicts),
        logit_error_max=errors[-1],
        state_logit_errors=[round(e, 4) for e in state_errors],
        probes_on_served_rows=len(state_errors) - len(probes),
        sigma_mean=sum(v["sigma_mean"] for v in verdicts) / len(verdicts),
        state_dtype=str(np.dtype(cfg.state_dtype)), faults=list(faults),
        **got, **{k + "_limit": v for k, v in limits.items()}, ok=ok)


def run(ctx, faults=(), **over) -> dict:
    """``faults`` (planted in the reference) and ``over`` (fields of the
    model's config) are a control run's."""
    config, workload, args = ctx["config"], ctx["workload"], ctx["args"]
    vocab = config["vocab_size"]
    cfg, params, eng, warm = build_engine(config, args.seed, **over)
    from paddle_tpu.ops.pallas import power_retention

    routed = power_retention.selection_count()
    ctx["log"]("warmup", programs={f: r["keys"] for f, r in warm.items()},
               seconds={f: r["seconds"] for f, r in warm.items()},
               temp_bytes={f: r["temp_bytes"] for f, r in warm.items()},
               pool_bytes=eng.pool_bytes,
               kernels_routed_to={"power_retention_decode": routed})
    if not ctx["rehearse"] and not (eng.paged_kernel_active() and routed):
        raise SystemExit("chipbench: the engine would not route its ticks "
                         "to the power_retention_decode kernel")
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
               saturated_counters=sat_counts, retention=report.retention,
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

    # -- correct: a seeded sample of the served requests. Their prompts go
    # through the engine once more, one a slot, for the states it leaves;
    # then the engine and its pool go: the replay holds state pages of its
    # own and the reference casts a layer at a time beside the weights.
    sv = config["serve"]
    pick = np.random.RandomState(args.seed % (2**32)).permutation(
        len(per))[:sv["check_requests"]]
    rids = [per[i]["rid"] for i in pick]
    served = eng.params     # what the engine served with
    probes = probe_states(
        eng, cfg, [reqs[rids[s % len(rids)] - rid0].prompt
                   for s in range(eng.slots)],
        sv["seg_steps"], sv["probe_segments"])
    del sched, eng, spans
    gc.collect()
    verdict = check(
        cfg, served, config,
        [(reqs[rid - rid0].prompt, results[rid][:sv["check_rows"]])
         for rid in rids],
        [f"request {rid - rid0}" for rid in rids], faults, probes)
    ctx["log"]("check", **verdict)
    ok = verdict["ok"]
    unfinished = len(reqs) - len(done)
    return {
        "kind": "serve_retention", "attempted": len(reqs),
        "failed": unfinished + (0 if ok else len(rids)),
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
    ap.add_argument("--control", choices=("state_bf16", "no_decay"),
                    required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    args.trace = 0
    manifest = runner.load_json(os.path.join(runner.ROOT, "BENCHMARK.json"))
    _, config, workload = runner.load_cell(runner.ROOT, manifest,
                                           args.workload)
    import jax.numpy as jnp

    import paddle_tpu as paddle

    paddle.jit.enable_persistent_cache()
    ctx = {"args": args, "config": config, "workload": workload,
           "rehearse": False, "trace_dir": None,
           "log": lambda phase, **f: print(json.dumps(
               {"phase": phase, **f}), flush=True),
           "open_window": lambda: None, "close_window": lambda: None}
    if args.control == "state_bf16":
        record = run(ctx, state_dtype=jnp.bfloat16)
    else:
        record = run(ctx, ("no_decay",))
    print(json.dumps({"control": args.control,
                      "correct": bool(record["correct"]),
                      "failed": int(record["failed"]),
                      "serve_tokens_per_s":
                      record["end_to_end"]["serve_tokens_per_s"]}))
    return 0


def sweep_with_backlog(argv) -> int:
    """``chipbench/sweep.py`` over this kind's engine, every rate opened by
    the cell's backlog (so the slots are full from the first segment on,
    as in the cell), and beside its rows what FULL slots complete at each
    rate — the saturated completion rate ISSUE 34 calls the knee: tokens
    from the first fetch after ``saturated_from_s`` to the last inside the
    window, over the time between and the mix's mean answer. Appended to
    ``--out``."""
    import json

    from chipbench import run as runner, sweep, traffic

    serve.build_engine = build_engine
    load_cell = runner.load_cell

    def load_cell_with_vocab(*a):
        cell, config, workload = load_cell(*a)
        return cell, dict(config, model={"vocab_size": config["vocab_size"]}), \
            workload

    runner.load_cell = load_cell_with_vocab
    plain, scheduler = traffic.serve_requests, serve.scheduler
    rates = [float(r) for r in argv[argv.index("--rates") + 1].split(",")]
    offered, spans = [], []

    def with_backlog(workload, vocab, seed, seconds):
        if workload["rate_rps"] not in rates:   # the warm-up's few
            return plain(workload, vocab, seed, seconds)
        offered.append((workload, seconds))
        traffic.serve_requests = plain      # ``requests`` calls it
        try:
            return requests(workload, vocab, seed, seconds)
        finally:
            traffic.serve_requests = with_backlog

    def spanned(eng, config):
        vars(eng).pop("run_segment", None)  # the last rate's wrapper
        spans.append(serve.SegmentSpans(eng))
        return scheduler(eng, config)

    traffic.serve_requests, serve.scheduler = with_backlog, spanned
    rc = sweep.main(argv)
    lines = []
    for (w, seconds), sp in zip(offered, spans[-len(offered):]):
        sat = sp.saturated(sp.rows[0][0], w["saturated_from_s"], seconds)
        answer = sum(g * k for g, k in zip(w["gen_lens"], w["gen_weights"])) \
            / sum(w["gen_weights"])
        rate = sat["tokens"] / max(sat["seconds"], 1e-9)
        lines.append({"rate_rps": w["rate_rps"], "backlog": w["backlog"],
                      "saturated": sat, "saturated_tokens_per_s": rate,
                      "completes_rps": rate / answer})
        print(json.dumps(lines[-1]), flush=True)
    with open(argv[argv.index("--out") + 1], "a") as f:
        f.write("\n| rate req/s | due at 0 s | full slots: tokens | steps | "
                "seconds | tokens/s | completes req/s |\n|---|---|---|---|---|"
                "---|---|\n")
        for r in lines:
            f.write(f"| {r['rate_rps']:g} | {r['backlog']} | "
                    f"{r['saturated']['tokens']} | {r['saturated']['steps']} "
                    f"| {r['saturated']['seconds']:.2f} | "
                    f"{r['saturated_tokens_per_s']:.1f} | "
                    f"{r['completes_rps']:.3f} |\n")
    return rc


if __name__ == "__main__":
    if "--control" in sys.argv:
        sys.exit(control(sys.argv[1:]))
    sys.exit(sweep_with_backlog(sys.argv[1:]))
