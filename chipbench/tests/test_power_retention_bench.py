"""What PR 34 adds to the benchmark, on the CPU (not collected by tier-1):
python -m pytest chipbench/tests/test_power_retention_bench.py -q

The flops file against hand counts, the five new readers on a record made
by hand (and on a record without the spans and counters: nothing read,
nothing raised), the configuration file against the catalog row, the
workload file's rate against the sweep file's one ``knee:`` line, the
reference against the repository's test reference, the check's refusal of a
planted fault and of a state kept in fewer bits, and a rehearsal of kind
``serve_retention`` on a tiny configuration."""

import argparse
import importlib.util
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CONFIG = "brumby-14b-l8"
CELL = CONFIG + ".reason-backlog"
TINY = os.path.join(HERE, "rehearse_power_retention")
READERS = ("segment_roofline.reason-backlog",
           "power_retention_decode_roofline", "retention_ms_per_step",
           "tokens_per_tick.reason-backlog", "admit_rows_used_share")


def load(path):
    with open(path) as f:
        return json.load(f)


def compute(name, record):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "lm_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.compute({"kind": "serve"}) is None      # nothing to read
    return mod.compute(record)


@pytest.fixture(scope="module")
def config():
    return load(os.path.join(BENCH, "configs", CONFIG + ".json"))


# -- the hand counts (ISSUE 34's, from the catalog row) ---------------------
LAYER = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
         + 5120 * 8 + 8 + 2 * 5120 + 2 * 128)                # 330.35 M
HEAD = 5120 * 151936
GATE = 5120 * 8 + 8
WEIGHT_BYTES = 2 * (8 * LAYER + HEAD + 5120) + 8 * GATE * 2  # gate: float32
PAGE = 8 * 8256 * 129 * 4                                    # 34.08 MB


def test_flops_file_against_hand_counts(config):
    from chipbench import flops_power_retention as flops

    p = flops.param_counts(config)
    assert p["layer"] == LAYER and round(LAYER / 1e4) == 33035
    assert p["total"] == 8 * LAYER + 2 * HEAD + 5120
    assert round(p["total"] * 2 / 1e7) == 840                # 8.40 GB
    assert flops.weight_bytes(config) == WEIGHT_BYTES
    assert flops.state_width(config) == 8256
    assert flops.state_page_bytes(config) == PAGE
    assert round(PAGE / 1e4) == 3408
    # a full house's tick: 16 pages x 8 layers, read and written
    assert flops.tick_state_bytes(config, 16) == 16 * 8 * 2 * PAGE
    chip = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}
    tick = flops.slice_floor_s(config, chip, 1, 0, 16, 0)
    assert tick == pytest.approx(19.0e-3, rel=0.01)          # ISSUE 34's
    assert flops.tick_state_bytes(config, 16) / 819e9 / tick == \
        pytest.approx(0.56, abs=0.01)
    # an admission of 1,024 rows: ~5.4 TFLOP of matmul + the retention
    ops = flops.admission_ops(config, 1024, 1024 ** 2)
    matmul = 8 * 2 * (LAYER - 2 * 5120 - 256) * 1024
    assert ops == pytest.approx(matmul + 8 * 4 * 128 * 40 * 1024 ** 2 / 2)
    assert matmul == pytest.approx(5.41e12, rel=0.01)
    # compute-bound at 1,024 rows, the weight stream at 128
    w = WEIGHT_BYTES / 819e9
    assert flops.slice_floor_s(config, chip, 1, 1, 0, 1024) == \
        pytest.approx(ops / 197e12) and ops / 197e12 > w
    assert flops.slice_floor_s(config, chip, 1, 1, 0, 128) == \
        pytest.approx(w)


def record_by_hand(config):
    """A traced slice of 4 segments: 124 ticks of 16 live slots + 4
    admissions of 500 prompt rows each, 3.6 s of ``jit_segment``, 1.8 s of
    the kernel in 992 calls."""
    return {
        "kind": "serve_retention", "config": config,
        "chip": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
        "slice": {"segments": 4, "steps": 128, "admits": 4,
                  "window_s": 3.65},
        "slice_counters": {"steps": 128, "state_pages": 124 * 16,
                           "admit_rows": 4096, "admit_rows_used": 2000},
        "saturated": {"tokens": 28000, "steps": 1800, "seconds": 48.0},
        "saturated_counters": {"steps": 1800, "state_pages": 27000,
                               "admit_rows": 51200, "admit_rows_used": 24550},
        "scopes": {"segment.decode/retention": 1.9,
                   "segment.decode/retention/power_retention_decode": 0.05,
                   "segment.admit/retention": 0.10,
                   "segment.decode/retention_qkv": 0.2,
                   "segment.decode/ffn": 0.7},
        "trace": {"planes": 1,
                  "modules": {"jit_segment": {"calls": 4, "seconds": 3.6}},
                  "ops": {"power_retention_decode.7": {"calls": 992,
                                                       "seconds": 1.8},
                          "fusion.406": {"calls": 992, "seconds": 0.24}}},
    }


def test_the_five_readers_on_a_record_made_by_hand(config):
    rec = record_by_hand(config)
    bw, peak = 819e9, 197e12
    admission = max((8 * 2 * (LAYER - 2 * 5120 - 256) * 500
                     + 8 * 4 * 128 * 40 * 500 ** 2 / 2) / peak,
                    WEIGHT_BYTES / bw)
    least = (124 * WEIGHT_BYTES + 124 * 16 * 8 * 2 * PAGE) / bw \
        + 4 * admission
    assert compute(READERS[0], rec) == pytest.approx(least / 3.6 * 100)
    assert compute(READERS[1], rec) == pytest.approx(
        124 * 16 * 8 * 2 * PAGE / bw / 1.8 * 100)
    assert compute(READERS[2], rec) == pytest.approx(
        (1.9 + 0.05 + 0.10) / 128 * 1e3)
    assert compute(READERS[3], rec) == pytest.approx(28000 / 1800)
    assert compute(READERS[4], rec) == pytest.approx(24550 / 51200 * 100)
    for name in READERS[:2]:
        assert 0 < compute(name, rec) < 100
    # a program without the spans and counters (the parent), or an
    # untraced run: nothing read, nothing raised
    bare = {k: v for k, v in rec.items()
            if k not in ("scopes", "slice_counters", "saturated_counters")}
    bare["trace"] = dict(rec["trace"], ops={})
    for name in (READERS[0], READERS[1], READERS[2], READERS[4]):
        assert compute(name, bare) is None
    other = dict(rec, slice_counters={"steps": 128, "experts_hit": 9},
                 saturated_counters={"steps": 1800, "picks": 3},
                 scopes={"segment.decode/experts": 1.0})
    for name in (READERS[0], READERS[1], READERS[2], READERS[4]):
        assert compute(name, other) is None


def test_manifest_entries_name_the_files_that_are_there():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["moves"] == "serve_tokens_per_s"
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("x", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert {k: m[k] for k in mod.META} == mod.META
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    tokens = [m for m in manifest["end_to_end"]
              if m["name"] == "serve_tokens_per_s"][0]
    assert tokens["workloads"][-1] == CELL and tokens["bound"] == 0.025
    assert os.path.exists(os.path.join(BENCH, "kinds", "serve_retention.py"))


def test_config_file_is_the_catalog_row_but_for_its_cut(config):
    published = {"attention_bias": False, "head_dim": 128,
                 "hidden_act": "silu", "hidden_size": 5120,
                 "intermediate_size": 17408,
                 "max_position_embeddings": 32768, "max_window_layers": 40,
                 "model_type": "brumby", "num_attention_heads": 40,
                 "num_hidden_layers": 40, "num_key_value_heads": 8,
                 "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "use_sliding_window": False,
                 "vocab_size": 151936}
    assert all(k in config for k in published)
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == config["reduced"] == sorted(config["published"]) \
        == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert mine["reduced"] == config["reduced"]
    assert mine["source"] == config["source"]
    for word in ("power_degree", "gate", "normaliser", "eps", "q/k norm",
                 "state dtype", "phi width"):
        assert word in config["assumed"]
    assert "five-stage" in config["deployment"] and \
        "embedding" in config["deployment"]
    eng = config["serve"]["engine"]
    assert eng["page_size"] == eng["max_len"] == 2048 and eng["slots"] == 16
    assert config["serve"]["seg_steps"] == 32
    assert config["serve"]["max_queue"] == 64


def test_rate_is_its_multiple_of_the_sweeps_knee():
    wl = load(os.path.join(BENCH, "workloads", CELL + ".json"))
    named = re.findall(r"chipbench/sweeps/[\w.\-]+\.md", wl["rate_from"])
    assert named == ["chipbench/sweeps/" + CONFIG + ".reason.md"]
    with open(os.path.join(ROOT, named[0])) as f:
        knees = re.findall(r"^knee: ([\d.]+) req/s$", f.read(), re.M)
    assert len(knees) == 1, f"{named[0]} has {len(knees)} 'knee:' lines"
    knee = float(knees[0])
    assert wl["rate_over_knee"] == 1.15
    assert wl["rate_rps"] == pytest.approx(round(1.15 * knee, 2), abs=1e-9)
    assert f"{knee:g} req/s" in wl["rate_from"]
    assert wl["backlog"] in (32, 40, 48) and "backlog_why" in wl
    assert wl["prompt_lens"] == [128, 256, 384, 512, 768, 1024]
    assert wl["prompt_weights"] == [1, 2, 3, 3, 2, 1]
    assert (wl["gen_lens"], wl["gen_weights"]) == ([256, 512, 1024],
                                                   [1, 2, 1])
    assert wl["kind"] == "serve_retention"


def test_requests_open_with_the_backlog():
    from chipbench.kinds import serve_retention as kind

    wl = load(os.path.join(BENCH, "workloads", CELL + ".json"))
    n = wl["backlog"]
    a = kind.requests(wl, 151936, 2**31 + 11, 51.0)
    b = kind.requests(wl, 151936, 2**31 + 11, 51.0)
    c = kind.requests(wl, 151936, 5, 51.0)
    assert [r.t for r in a[:n]] == [0.0] * n and a[n + 1].t > 0
    assert len(a) == n + round(wl["rate_rps"] * 51.0)
    assert all(x.t == y.t and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)                  # one multiset
    assert max(len(r.prompt) for r in a) == 1024
    assert max(r.prompt.max() for r in a) < 151936


def _tiny():
    """The tiny configuration with gates that start at 2 (a decay of ~0.88
    a token, for the model's 0.993): a dropped decay shows within a dozen
    rows; chunks of 8 rows, so a bucket of 16 crosses one."""
    import jax.numpy as jnp

    from chipbench.kinds import serve_retention as kind
    from paddle_tpu.models import power_retention

    config = load(os.path.join(TINY, "tiny-retention.json"))
    cfg = kind.model_config(config, max_seq_len=48, prefill_chunk=8)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(power_retention, "GATE_BIAS", 2.0)
        return config, cfg, kind.init_weights(cfg, 7, jnp.bfloat16)


def test_reference_is_the_test_reference():
    """The benchmark's blocked copy == ``tests/reference_power_retention``
    (one [T, T] matrix) on the tiny configuration, in float32."""
    import numpy as np

    from chipbench import reference_power_retention as reference

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import reference_power_retention as plain

    config, _, params = _tiny()
    seq = np.random.RandomState(3).randint(0, 256, 48).astype(np.int32)
    rows = np.arange(48)
    got = reference.logits_at(params, seq, rows, config, True)
    want = plain.logits(params, seq, config)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    gone = reference.logits_at(params, seq, rows, config, True,
                               ("no_decay",))
    np.testing.assert_allclose(
        gone, plain.logits(params, seq, config, ("no_decay",)), rtol=1e-4,
        atol=1e-4)
    assert np.abs(np.asarray(gone) - np.asarray(got)).max() > 0.05


def test_check_refuses_a_planted_fault_and_fewer_state_bits():
    """The rule has teeth at the tiny size too: sequences the program
    decodes greedily pass; the same against a reference without the decay
    are refused; and a state kept in 8-bit floats (two precisions below
    the configuration's float32: at this size bfloat16 hides in the band)
    is refused by the logits' limits."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from chipbench.kinds import serve_retention as kind

    config, cfg, params = _tiny()
    config["serve"]["check_rows"] = 24
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, n).astype(np.int32) for n in (9, 16, 5)]

    def greedy(cfg):
        program = kind.replay_logits  # the program's own greedy tokens
        seqs = [(p, np.zeros(24, np.int32)) for p in prompts]
        for i in range(24):
            lg = program(cfg, params, seqs, 16, i + 1)[:, i]
            for (_, g), row in zip(seqs, lg):
                g[i] = int(row.argmax())
        return seqs

    seqs = greedy(cfg)
    names = ["a", "b", "c"]
    # (c): logits read from a state "left" after 16 tokens (over 256
    # columns a position's error scatters 0.6-1.3: one that reads under 1
    # in all three) — sound ones on the served rows and on tokens of their
    # own (a pass of its own), and one off by a tenth of a logit's spread
    n = 16
    row = kind.replay_logits(cfg, params, seqs, 16, n + 1)[:, n]
    probes = [(p, list(g[:n]), lg) for (p, g), lg in zip(seqs, row)]
    good = kind.check(cfg, params, config, seqs, names, (), probes)
    assert good["ok"] and good["beyond"] == 0
    assert 0 < good["logit_error"] < kind.LOGIT_ERROR_MAX
    assert len(good["state_logit_errors"]) == 3
    assert good["probes_on_served_rows"] == 3
    assert 0 < good["state_logit_error"] < kind.STATE_LOGIT_ERROR_MAX
    other = (prompts[0], [int(t) for t in rng.randint(0, 256, n)])
    other += (kind.replay_logits(cfg, params, [(other[0], np.array(
        other[1] + [0]))], 16, n + 1)[0, n],)
    own = kind.check(cfg, params, config, seqs, names, (), [other])
    assert own["probes_on_served_rows"] == 0
    assert 0.5 < own["state_logit_error"] < 1.5     # one position of 256
    off = [(p, t, lg + 0.1 * lg.std() * rng.standard_normal(lg.shape))
           for p, t, lg in probes[:1]]
    moved = kind.check(cfg, params, config, seqs, names, (), off)
    assert not moved["ok"] and moved["logit_error"] == good["logit_error"]
    assert moved["state_logit_error"] > kind.STATE_LOGIT_ERROR_MAX
    bad = kind.check(cfg, params, config, seqs, names, ("no_decay",), probes)
    assert not bad["ok"] and bad["logit_error"] > 5 * kind.LOGIT_ERROR_MAX
    assert bad["state_logit_error"] > 5 * kind.STATE_LOGIT_ERROR_MAX
    low = dataclasses.replace(cfg, state_dtype=jnp.float8_e4m3fn)
    worse = kind.check(low, params, config, seqs, names)
    assert worse["logit_error"] > good["logit_error"]
    assert not worse["ok"]


@pytest.fixture(scope="module")
def rehearsal():
    """One untraced run of the tiny configuration through the kind, as
    ``run.py`` would drive it."""
    from chipbench.kinds import serve_retention as kind

    lines = {}
    ctx = {"args": argparse.Namespace(seed=2147483711, seconds=2.0, trace=0),
           "config": load(os.path.join(TINY, "tiny-retention.json")),
           "workload": load(os.path.join(TINY,
                                         "tiny-retention.backlog.json")),
           "rehearse": True,
           "log": lambda phase, **fields: lines.update({phase: fields}),
           "trace_dir": None, "open_window": lambda: None,
           "close_window": lambda: None}
    return kind.run(ctx), lines


def test_rehearsal_of_the_kind_on_a_tiny_config(rehearsal):
    record, lines = rehearsal
    assert record["correct"] and record["failed"] == 0
    assert record["attempted"] == 6 + 8          # the backlog + 4 req/s x 2 s
    assert record["end_to_end"]["serve_tokens_per_s"] > 0
    counts = record["saturated_counters"]
    assert counts["steps"] == record["saturated"]["steps"] > 0
    assert 0 < counts["admit_rows_used"] < counts["admit_rows"]
    assert counts["admit_rows"] % 16 == 0
    # a step is a tick (a page a live slot) or an admission (one token)
    assert counts["state_pages"] + counts["admit_rows"] // 16 == \
        record["saturated"]["tokens"]
    assert compute(READERS[3], record) == pytest.approx(
        record["saturated"]["tokens"] / counts["steps"])
    assert compute(READERS[4], record) == pytest.approx(
        counts["admit_rows_used"] / counts["admit_rows"] * 100)
    # no trace: the device metrics read nothing and do not raise
    for name in READERS[:3]:
        assert compute(name, record) is None
    check = lines["check"]
    assert check["ok"] and check["state_dtype"] == "float32"
    assert check["worst_sigmas"] <= check["worst_sigmas_limit"] == 5.0
    assert 0 < check["logit_error"] <= check["logit_error_limit"] == 1.2
    assert 0 < check["logit_error_late"] <= check["logit_error_late_limit"]
    assert 0 < check["logit_error_p90"] <= check["logit_error_p90_limit"]
    # (c): every slot's state page, as the segment program left it
    assert len(check["state_logit_errors"]) == 4
    assert check["probes_on_served_rows"] == 4
    assert 0 < check["state_logit_error"] <= check["state_logit_error_limit"]
    assert lines["serve"]["retention"]["state_pages"] > 0
    assert list(lines["warmup"]["kernels_routed_to"]) == \
        ["power_retention_decode"]
