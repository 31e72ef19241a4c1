"""Find the knee of a serving cell ONCE, when the cell is defined: one
process, one engine, the cell's traffic at several rates.

    python3 chipbench/sweep.py --workload <cell> --rates 16,20,24 --seconds 15 --out <file.md>

For each rate: the share of requests finished by the window's end, the
TTFT and TPOT tails, and the backlog (requests due and still without a first
token) at half the window and at its end. The knee is the highest rate whose
backlog at the window's end is no larger than at half the window (+ 3
requests of noise): above it the queue grows for as long as the window
lasts. The cells then take fixed rates from it (4/5 and 3/2); no run of the
benchmark searches.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    import jax

    from chipbench import common, peaks, run as runner, traffic
    from chipbench.kinds import serve

    manifest = runner.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, workload = runner.load_cell(ROOT, manifest, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("chipbench.sweep: needs a TPU", file=sys.stderr)
        return 2
    peaks.peaks(devs[0].device_kind)
    import paddle_tpu as paddle

    paddle.jit.enable_persistent_cache()
    vocab = config["model"]["vocab_size"]
    _, _, eng, _ = serve.build_engine(config, args.seed)
    serve.warm_serve(eng, config, workload, vocab, args.seed)
    rows = []
    S = args.seconds
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        w = dict(workload, rate_rps=rate)
        reqs = traffic.serve_requests(w, vocab, args.seed + 100 + i, S)
        sched = serve.scheduler(eng, config)
        report = sched.serve(serve.arrivals(reqs))
        per = report.per_request
        rid0 = min(r["rid"] for r in per)
        due = {r["rid"]: reqs[r["rid"] - rid0].t for r in per}
        first = [due[r["rid"]] + r["ttft_s"] for r in per]
        done = [due[r["rid"]] + r["e2e_s"] for r in per]

        def backlog(t):
            return sum(1 for r, f in zip(per, first)
                       if due[r["rid"]] <= t < f)

        ttft, tpot = serve.report_latencies_ms(per)
        row = {
            "rate_rps": rate, "requests": len(per),
            "finished_in_window": sum(d <= S for d in done) / len(per),
            "ttft_p50_ms": common.percentile(ttft, 0.5),
            "ttft_p95_ms": common.percentile(ttft, 0.95),
            "tpot_p95_ms": common.percentile(tpot, 0.95),
            "backlog_half": backlog(S / 2), "backlog_end": backlog(S),
            "makespan_s": report.makespan_s,
            "tokens_per_s": report.total_tokens / report.makespan_s,
            "slot_occupancy": report.slot_occupancy,
            "queue_wait_p50_ms": report.queue_wait_p50_s * 1e3,
            "backpressure_events": report.backpressure_events,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
        eng.reset_slots()
    sustained = [r["rate_rps"] for r in rows
                 if r["backlog_end"] <= r["backlog_half"] + 3]
    knee = max(sustained) if sustained else None
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(f"| rate req/s | requests | finished in window | ttft p50 ms "
                f"| ttft p95 ms | tpot p95 ms | backlog at {S / 2:g} s | "
                f"backlog at {S:g} s | makespan s | tokens/s | occupancy |\n")
        f.write("|---|---|---|---|---|---|---|---|---|---|---|\n")
        for r in rows:
            f.write(f"| {r['rate_rps']:g} | {r['requests']} | "
                    f"{r['finished_in_window']:.3f} | {r['ttft_p50_ms']:.1f} "
                    f"| {r['ttft_p95_ms']:.1f} | {r['tpot_p95_ms']:.2f} | "
                    f"{r['backlog_half']} | {r['backlog_end']} | "
                    f"{r['makespan_s']:.2f} | {r['tokens_per_s']:.0f} | "
                    f"{r['slot_occupancy']:.3f} |\n")
        f.write(f"\nknee (highest rate whose backlog does not grow): "
                f"{knee}\n")
    print(json.dumps({"knee": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
