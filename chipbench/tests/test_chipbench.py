"""CPU rehearsals of the harness (not collected by tier-1, which runs
``tests/``): python -m pytest chipbench/tests -q"""

import glob
import importlib.util
import json
import os
import re
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


MANIFESTS = [ROOT, os.path.join(HERE, "rehearse")]


def run_cli(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                           *argv], capture_output=True, text=True, env=env,
                          timeout=300)


def reader(path):
    spec = importlib.util.spec_from_file_location(
        "lm_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("base", MANIFESTS)
def test_manifest_names_units_and_files(base):
    m = load(os.path.join(base, "BENCHMARK.json"))
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cell_dir = os.path.join(base, m["paths"][0])
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for x in m["configs"] + m["workloads"] + m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]), x["name"]
        for key in ("why", "layer", "source"):
            if key in x:
                assert 1 <= len(x[key]) <= 200 and "\n" not in x[key]
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
        assert set(x.get("workloads", cells)) <= set(cells)
    for x in m["end_to_end"]:
        assert x["source"] in ("host_clock", "device_trace")
        assert 0 < x["bound"] <= 0.1
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = load(os.path.join(base, c["file"]))
        assert cfg["reduced"] == c["reduced"]
    seen = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        wl = load(os.path.join(cell_dir, "workloads", w["name"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "kinds",
                                           wl["kind"] + ".py"))
        mine = [x["name"] for x in m["end_to_end"]
                if w["name"] in x.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in x.get("workloads", cells)
                   for x in m["per_layer"])
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # the metric it moves is reported in every cell this one is in
        target = e2e[x["moves"]]
        assert set(x.get("workloads", cells)) <= \
            set(target.get("workloads", cells))
        path = os.path.join(cell_dir, "layer_metrics", x["name"] + ".py")
        if not os.path.exists(path):
            path = os.path.join(BENCH, "layer_metrics", x["name"] + ".py")
        meta = reader(path).META
        assert {k: x[k] for k in ("layer", "unit", "moves", "source")} == meta


def test_traffic_same_seed_same_inputs_other_seed_same_work():
    from chipbench import traffic

    w = load(os.path.join(BENCH, "workloads",
                          "internlm2-1.8b.chat-rate80.json"))
    size = lambda rs: [len(r.prompt) for r in rs]  # noqa: E731
    gens = lambda rs: [r.max_new_tokens for r in rs]  # noqa: E731
    gaps = lambda rs: [round(y.t - x.t, 9)  # noqa: E731
                       for x, y in zip(rs, rs[1:])]
    a = traffic.serve_requests(w, 92544, 2**31 + 5, 20.0)
    b = traffic.serve_requests(w, 92544, 2**31 + 5, 20.0)
    c = traffic.serve_requests(w, 92544, 6, 20.0)
    assert len(a) == len(b) == len(c) == round(w["rate_rps"] * 20.0)
    assert all(x.t == y.t and x.max_new_tokens == y.max_new_tokens
               and (x.prompt == y.prompt).all() for x, y in zip(a, b))
    # another seed: another order and other token ids, the same multiset
    # of work
    assert size(a) != size(c) and gens(a) != gens(c) and gaps(a) != gaps(c)
    assert sorted(size(a)) == sorted(size(c))
    assert sorted(gens(a)) == sorted(gens(c))
    # (the last gap of each order runs past the last request)
    assert len(set(gaps(a)) & set(gaps(c))) >= len(a) - 3
    assert a[0].t == 0.0 and a[-1].t < 20.0 and c[-1].t < 20.0
    mean = sum(size(a)) / len(a)
    assert abs(mean - 113) < 4 and abs(sum(gens(a)) / len(a) - 128) < 3
    t = traffic.train_batches({"n_batches": 2, "batch": 2, "seq": 8}, 100, 3)
    assert t.shape == (2, 2, 8) and (t == traffic.train_batches(
        {"n_batches": 2, "batch": 2, "seq": 8}, 100, 3)).all()


def test_flops_of_the_two_configurations():
    from chipbench import flops

    mistral = load(os.path.join(BENCH, "configs",
                                "mistral-7b-v0.3-l2.json"))["model"]
    intern = load(os.path.join(BENCH, "configs",
                               "internlm2-1.8b.json"))["model"]
    assert flops.param_counts(mistral)["total"] == 704_663_552
    assert abs(flops.train_flops_per_token(mistral, 4096) / 3.62e9 - 1) < .01
    assert flops.param_counts(intern)["total"] == 1_889_110_016
    assert flops.kv_bytes_per_row(intern) == 96 * 1024
    assert abs(flops.weight_stream_bytes(intern) / 3.40e9 - 1) < 0.01


def _plane(name, lines):
    ev = lambda n, s, d: types.SimpleNamespace(  # noqa: E731
        name=n, start_ns=s, duration_ns=d)
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
        for ln, evs in lines.items()])


RECORDED = load(os.path.join(HERE, "data", "trace_small.json"))


def test_trace_reduce_on_a_small_recorded_trace():
    from chipbench import trace_reduce

    planes = [_plane(p["name"], p["lines"]) for p in RECORDED["planes"]]
    t = trace_reduce.reduce_planes(planes)
    want = RECORDED["want"]
    assert t["planes"] == want["planes"]
    for name, (calls, secs) in want["modules"].items():
        assert t["modules"][name]["calls"] == calls
        assert t["modules"][name]["seconds"] == pytest.approx(secs)
    assert t["busy_s"] == pytest.approx(want["busy_s"])
    assert t["span_s"] == pytest.approx(want["span_s"])
    assert sum(g["seconds"] for g in t["gaps"].values()) == \
        pytest.approx(want["gap_s"])
    # the loop that holds the other ops is no "top op"
    assert trace_reduce.top(t["ops"], 1)[0][0] == want["top_op"]
    assert trace_reduce.reduce_planes(
        [_plane("/host:CPU", {"XLA Ops": [["a", 0, 5]]})]) is None
    assert trace_reduce.union_seconds([(0, 10), (5, 20), (30, 40)]) == \
        pytest.approx(30e-9)


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(BENCH, "layer_metrics", "[a-z]*.py"))))
def test_layer_metric_reads_a_recorded_run(path):
    name = os.path.basename(path)[:-3]
    records = load(os.path.join(HERE, "data", "run_records.json"))
    mod = reader(path)
    got = {kind: mod.compute(rec) for kind, rec in records["records"].items()}
    want = records["want"][name]
    for kind, value in want.items():
        if value is None:
            assert got[kind] is None
        else:
            assert got[kind] == pytest.approx(value, rel=1e-6)
    assert mod.compute({"kind": "serve"}) is None  # nothing to read


def test_check_generation_refuses_a_wrong_token():
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference

    model = load(os.path.join(HERE, "rehearse", "configs",
                              "tiny-gqa.json"))["model"]
    h, f, v = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    n, kv = model["num_hidden_layers"], h // 2
    rng = np.random.RandomState(0)
    w = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(0, 0.05, shape), jnp.bfloat16)
    params = {"embed": w(v, h), "lm_head": w(h, v),
              "ln_f": jnp.ones((h,), jnp.bfloat16),
              "ln_attn": jnp.ones((n, h), jnp.bfloat16),
              "ln_mlp": jnp.ones((n, h), jnp.bfloat16),
              "wq": w(n, h, h), "wk": w(n, h, kv), "wv": w(n, h, kv),
              "wo": w(n, h, h), "w_gate": w(n, h, f), "w_up": w(n, h, f),
              "w_down": w(n, f, h)}
    prompt, gen = rng.randint(0, v, (8,)), []
    for i in range(6):  # the reference's own greedy continuation
        seq = np.zeros((16,), np.int32)
        seq[: 8 + i] = list(prompt) + gen
        gen.append(int(np.asarray(reference.logits_at(
            params, seq, [7 + i], model, True))[0].argmax()))
    good = reference.check_generation(params, model, prompt, gen, 16, 6, "r")
    assert good["ok"] and good["exact"] == 6
    gen[3] = (gen[3] + 1) % v
    bad = reference.check_generation(params, model, prompt, gen, 16, 6, "r")
    assert not bad["ok"] and bad["worst_sigmas"] > reference.TIE_SIGMAS


def test_run_refuses_the_cpu():
    r = run_cli("--workload", "internlm2-1.8b.chat-rate80", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and "needs a TPU" in r.stderr
    assert not any(line.startswith('{"correct"')
                   for line in r.stdout.splitlines())


@pytest.mark.parametrize("cell,trace,want", [
    ("tiny-gqa.chat", 0, {"ttft_p95_ms", "tpot_mean_ms",
                          "serve_tokens_per_s", "setup_s"}),
    # `segments` exists only as a file + an entry of the rehearsal's
    # manifest: a metric, a cell and a configuration are added by files
    ("tiny-gqa.chat", 1, {"queue_wait_p50_ms", "tokens_per_tick",
                          "segments"}),
    ("tiny-gqa.pretrain", 0, {"train_tokens_per_s", "setup_s"}),
])
def test_rehearsal_end_to_end(cell, trace, want):
    r = run_cli("--rehearse", "--workload", cell, "--seed",
                str(2**31 + 17), "--seconds", "1.5", "--trace", str(trace))
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.splitlines()[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"  # and so no device metric
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    window = [json.loads(x) for x in r.stdout.splitlines()
              if x.startswith('{"phase": "window"')][0]
    assert window["programs_built_in_window"] == 0


KNEE_LINE = re.compile(r"^knee: ([0-9.]+) req/s$", re.M)
SWEEP_FILE = re.compile(r"chipbench/sweeps/[A-Za-z0-9_.\-]+\.md")


def serve_cells():
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    for w in m["workloads"]:
        wl = load(os.path.join(BENCH, "workloads", w["name"] + ".json"))
        if wl["kind"] == "serve":
            yield pytest.param(wl, m["run_seconds"], id=w["name"])


@pytest.mark.parametrize("wl,run_seconds", serve_cells())
def test_serve_rate_is_its_multiple_of_the_sweeps_knee(wl, run_seconds):
    """A re-pitch changes the sweep file and both cells together: each
    rate is the multiple its file states (``rate_over_knee``) of the ONE
    knee line of the sweep file its ``rate_from`` names; below the knee to
    one decimal, above it to the nearest whole number."""
    named = SWEEP_FILE.findall(wl["rate_from"])
    assert len(named) == 1, wl["rate_from"]
    with open(os.path.join(ROOT, named[0])) as f:
        knees = KNEE_LINE.findall(f.read())
    assert len(knees) == 1, f"{named[0]} has {len(knees)} 'knee:' lines"
    knee, over = float(knees[0]), wl["rate_over_knee"]
    assert over in (0.8, 1.75, 2.0)
    want = round(over * knee, 1) if over < 1 else float(round(over * knee))
    assert wl["rate_rps"] == pytest.approx(want, abs=1e-9)
    assert f"{knee:g} req/s" in wl["rate_from"]
    assert wl.get("saturated_from_s", 0.0) < run_seconds
    if over > 1:  # judged by tokens/s: the span it is taken over is stated
        assert wl["saturated_from_s"] >= 5.0 and wl["saturated_from_why"]
