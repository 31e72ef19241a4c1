"""chipbench: one cell of BENCHMARK.json, one run, one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration is ``chipbench/configs/<config>.json``, its traffic
``chipbench/workloads/<cell>.json``, its driver ``chipbench/kinds/<kind>.py``
(the workload file's ``kind``), and each per-layer metric is read by
``chipbench/layer_metrics/<metric>.py``: a later PR adds a cell, a
configuration or a metric by adding files and manifest entries, and edits
none. Earlier lines of the output are JSON too, one per phase; the LAST line
is the result the driver reads. Without a TPU whose kind is in ``peaks.py``
nothing runs and the exit code is 2. ``--rehearse`` (used by
``chipbench/tests`` only) takes the manifest and the tiny sizes under
``chipbench/tests/rehearse`` and may run on the CPU: it reports the platform
it ran on and never a device metric.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSE_DIR = os.path.join(HERE, "tests", "rehearse")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(base: str, manifest: dict, name: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in the manifest "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(base, configs[cell["config"]]["file"]))
    workload = load_json(os.path.join(
        base, manifest["paths"][0], "workloads", name + ".json"))
    return cell, config, workload


def layer_metric(base: str, manifest: dict, name: str):
    path = os.path.join(base, manifest["paths"][0], "layer_metrics",
                        name + ".py")
    if not os.path.exists(path):  # a rehearsal reads the real readers too
        path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + "".join(
            c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def breakdown(trace: dict, sl: dict) -> dict:
    """The ops that took most device time, and the device's idle time: by
    the programs that flank each gap (device clock) and, where the kind
    recorded host spans around its calls into the engine, by what the host
    was doing (host clock)."""
    from chipbench import trace_reduce

    gaps = sorted(trace["gaps"].items(), key=lambda kv: -kv[1]["seconds"])
    idle = [[f"device idle between {k} (x{v['count']}, longest "
             f"{v['longest'] * 1e3:.2f} ms)", v["seconds"]]
            for k, v in gaps[:8]]
    if sl and sl.get("host_between_s") is not None:
        idle.append(["host: scheduler loop between run_segment calls "
                     "(ingest, stamps, telemetry)", sl["host_between_s"]])
        idle.append(["host: inside run_segment beyond the device's busy "
                     "time (admission prep, dispatch, fetch, replay)",
                     max(0.0, sl["host_in_segment_s"] - trace["busy_s"])])
    return {"device_ops": trace_reduce.top(trace["ops"], 10),
            "idle_gaps": idle[:10]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    base = REHEARSE_DIR if args.rehearse else ROOT
    manifest = load_json(os.path.join(base, "BENCHMARK.json"))
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    cell, config, workload = load_cell(base, manifest, args.workload)

    import jax

    from chipbench import common, peaks, trace_reduce

    devs = jax.devices()
    if not args.rehearse:
        if devs[0].platform != "tpu":
            print(f"chipbench: needs a TPU, but jax reports platform "
                  f"{devs[0].platform!r} ({len(devs)} device(s)); nothing "
                  f"was run", file=sys.stderr)
            return 2
        if len(devs) < cell["chips"]:
            print(f"chipbench: {args.workload} needs {cell['chips']} chips, "
                  f"jax reports {len(devs)}; nothing was run",
                  file=sys.stderr)
            return 2
        chip = peaks.peaks(devs[0].device_kind)
    else:
        chip = None
    devs = devs[: cell["chips"]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    def log(phase: str, **fields) -> None:
        print(json.dumps({"phase": phase, **fields}), flush=True)

    import paddle_tpu as paddle

    cache_dir = paddle.jit.enable_persistent_cache()
    compiles = common.CompileCounter()
    log("start", workload=args.workload, seed=args.seed,
        seconds=args.seconds, trace=args.trace, device=device,
        compile_cache=cache_dir, import_s=time.perf_counter() - T_START)

    window = {}

    def open_window() -> None:
        compiles.armed = True
        window["open"] = time.perf_counter()

    def close_window() -> None:
        window["close"] = time.perf_counter()
        compiles.armed = False

    trace_dir = os.path.join(base, ".chipbench_out", "trace", args.workload)
    ctx = {"args": args, "config": config, "workload": workload,
           "devices": devs, "rehearse": args.rehearse, "log": log,
           "trace_dir": trace_dir, "open_window": open_window,
           "close_window": close_window}
    kind = importlib.import_module("chipbench.kinds." + workload["kind"])
    record = kind.run(ctx)
    setup_s = window["open"] - T_START
    log("window", setup_s=setup_s,
        window_s=window["close"] - window["open"],
        programs_built_in_window=compiles.in_window,
        programs_built=compiles.total, cache_hits=compiles.cache_hits,
        cache_misses=compiles.cache_misses)
    if compiles.in_window:
        print(f"chipbench: {compiles.in_window} program(s) were built "
              f"inside the measured window", file=sys.stderr)

    record.update(config=config, workload=workload, cell=cell, chip=chip,
                  device=device, trace=None)
    metrics = {}
    if args.trace:
        record["trace"] = trace = trace_reduce.reduce(trace_dir)
        if trace is not None:
            log("trace", slice=record["slice"], busy_s=trace["busy_s"],
                span_s=trace["span_s"], modules=trace["modules"],
                top_ops=[[n, s, trace["ops"][n]["calls"]] for n, s in
                         trace_reduce.top(trace["ops"], 30)])
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in manifest["per_layer"]:
            if not applies(m, args.workload):
                continue
            value = layer_metric(base, manifest, m["name"]).compute(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace is not None and not args.rehearse:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = record["slice"]["window_s"]
    else:
        values = dict(record["end_to_end"], setup_s=setup_s)
        for m in manifest["end_to_end"]:
            if applies(m, args.workload):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = common.memory_peak_bytes(devs)
    result = {"correct": bool(record["correct"]),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": metrics,
              "device": device}
    if args.trace and record["trace"] is not None:
        result["breakdown"] = breakdown(record["trace"], record["slice"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
