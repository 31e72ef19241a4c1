"""Measure a cell's spread as the contract says: sets of runs with the same
seeds in each set, every run a fresh process, all in one call.

    chiprun -- python3 chipbench/sets.py --workload <cell> [--sets 2] [--runs 6] [--trace-runs 1] --out chiprun_out/<dir>

This parent never touches jax (one process per chip). It prints, for each
end-to-end metric, each set's median and spread (the distance between the
first and third quartile of ``statistics.quantiles(values, n=4)`` as a share
of the median), the wider spread, and five times it; and writes every run's
last line to ``<out>/<cell>.jsonl``.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SEEDS = [2147483659, 3, 2147484001, 77, 2200000123, 1234567, 91, 2147483999]


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(cell: str, seed: int, seconds, trace: int, log) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
           "--seed", str(seed), "--trace", str(trace)]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.perf_counter()
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT)
    wall = time.perf_counter() - t0
    log.write(r.stdout)
    log.write(r.stderr[-4000:])
    log.flush()
    if r.returncode != 0:
        print(f"run failed rc={r.returncode}: {r.stderr[-1500:]}", flush=True)
        return {"rc": r.returncode, "wall_s": wall}
    out = json.loads(r.stdout.splitlines()[-1])
    out.update(rc=0, wall_s=wall, seed=seed, trace=trace)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--trace-runs", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    cell = args.workload
    rows = []
    with open(os.path.join(args.out, cell + ".log"), "w") as log, \
            open(os.path.join(args.out, cell + ".jsonl"), "w") as res:
        for s in range(args.sets):
            for i in range(args.runs):
                row = one_run(cell, SEEDS[i % len(SEEDS)], args.seconds, 0,
                              log)
                row["set"] = s
                rows.append(row)
                res.write(json.dumps(row) + "\n")
                res.flush()
                print(json.dumps({k: row.get(k) for k in
                                  ("set", "seed", "rc", "wall_s", "correct",
                                   "failed")}
                                 | {k: v["value"] for k, v in
                                    row.get("metrics", {}).items()}),
                      flush=True)
        for i in range(args.trace_runs):
            row = one_run(cell, SEEDS[i % len(SEEDS)], args.seconds, 1, log)
            row["set"] = "trace"
            res.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
    good = [r for r in rows if r["rc"] == 0]
    names = sorted({k for r in good for k in r["metrics"]})
    for name in names:
        per_set, line = [], {"metric": name}
        for s in range(args.sets):
            vals = [r["metrics"][name]["value"] for r in good
                    if r["set"] == s and name in r["metrics"]]
            if name == "setup_s":
                vals = vals[1:] if s == 0 else vals  # the run that compiles
            if len(vals) >= 2:
                per_set.append((statistics.median(vals), spread(vals)))
        if per_set:
            line["medians"] = [m for m, _ in per_set]
            line["spreads"] = [sp for _, sp in per_set]
            line["widest"] = max(sp for _, sp in per_set)
            line["bound_5x"] = 5 * line["widest"]
            line["second_vs_first"] = per_set[-1][0] / per_set[0][0] - 1
        print(json.dumps(line), flush=True)
    return 0 if len(good) == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())
