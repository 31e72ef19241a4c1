"""What PR 36 adds to the benchmark, on the CPU (not collected by tier-1):
python -m pytest chipbench/tests/test_hybrid_moe_bench.py -q

The flops file against hand counts and against the built tree (tiny and
published sizes, ``jax.eval_shape``: nothing allocated), the twelve new
readers on a record made by hand (and on a record without the spans and
counters: nothing read, nothing raised), the configuration file against the
catalog row, the workload file's rate against the sweep file's one ``knee:``
line, the reference against the repository's test reference, the check's
refusal of the two controls' faults, and a rehearsal of kind
``serve_hybrid_moe`` on a tiny configuration."""

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
CONFIG = "k-exaone-236b-l5-ep8"
CELL = CONFIG + ".mixed-backlog"
TINY = os.path.join(HERE, "rehearse_hybrid_moe")
READERS = tuple(n + ".mixed-backlog" for n in (
    "segment_roofline", "grouped_expert_matmul_roofline",
    "paged_attention_full_roofline", "paged_attention_window_roofline",
    "prefill_attention_full_roofline", "prefill_attention_window_roofline",
    "attention_ms_per_step", "experts_ms_per_step", "tokens_per_tick",
    "admit_rows_used_share", "experts_hit_per_step",
    "attended_rows_per_tick"))
CHIP = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}


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


# -- the hand counts (ISSUE 36's, from the catalog row) ---------------------
ATTN = 2 * 6144 * 8192 + 2 * 6144 * 1024                       # 113.2 M
NORMS = 2 * 6144 + 2 * 128
EXPERT = 3 * 6144 * 2048                                       # 37.7 M
ROUTER = 6144 * 128
DENSE = ATTN + NORMS + 3 * 6144 * 18432                        # 453.0 M
OUTSIDE = ATTN + NORMS + EXPERT + ROUTER                       # 151.8 M
EMBED = 6144 * 19200                                           # 118.0 M
TOTAL = DENSE + 4 * (OUTSIDE + 16 * EXPERT) + 2 * EMBED + 6144
ROW = 2 * 8 * 128 * 2                                          # 4,096 B


def test_flops_file_against_hand_counts(config):
    from chipbench import flops_hybrid_moe as flops

    p = flops.param_counts(config)
    assert p["attention"] == ATTN and round(ATTN / 1e5) == 1132
    assert p["dense_layer"] == DENSE and round(DENSE / 1e5) == 4530
    assert p["sparse_layer_outside"] == OUTSIDE and \
        round(OUTSIDE / 1e5) == 1518
    assert p["total"] == TOTAL == 3_712_027_904
    assert flops.weight_bytes(config) == 2 * TOTAL + 4 * ROUTER * 2 \
        == 7_430_347_264
    assert flops.expert_bytes(config) == 2 * EXPERT \
        and round(2 * EXPERT / 1e5) == 755                   # 75.5 MB
    assert flops.cache_row_bytes(config) == ROW == 4096
    assert flops.kinds(config) == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert flops.page_bytes(config, 16) == 65536
    assert flops.fixed_part_bytes(config) == 4 * 128 * ROW == 2_097_152
    # ISSUE 36's tick: 7.19 GB of weights (every expert hit) + the full
    # layer's ~2.7k rows a slot + 128 rows a slot a window layer
    tick = flops.slice_floor_s(config, CHIP, 1, 0, 64, 64 * 2700 + 64 * 512,
                               0)
    stream = flops.outside_expert_bytes(config) + 64 * 2 * EXPERT
    assert round(stream / 1e7) == 719
    assert tick == pytest.approx((stream + (64 * 2700 + 64 * 512) * ROW)
                                 / 819e9)
    assert 9.5e-3 < tick < 10.0e-3
    # an admission of 4,096 rows: 2.42 GFLOP a row of matmuls = 9.9 TFLOP
    # (ISSUE 36's 2.66 counts the head on every row; it runs on one),
    # 0.27 TFLOP of attention in the full layer, 0.017 in a window layer
    per_row = 2 * (DENSE + 4 * OUTSIDE) + 4 * 8 * (16 / 128) * 2 * EXPERT
    assert per_row == pytest.approx(2.42e9, rel=0.01)
    full = 4096 * 4097 / 2 * 32768
    window = (128 * 129 / 2 + (4096 - 128) * 128) * 32768
    assert full == pytest.approx(0.275e12, rel=0.01)
    assert window == pytest.approx(0.0169e12, rel=0.01)
    ops = flops.admission_ops(config, 4096)
    assert ops == pytest.approx(per_row * 4096 + full + 4 * window)
    # compute-bound at 4,096 rows, the weight stream at 256
    assert flops.slice_floor_s(config, CHIP, 1, 1, 64, 0, 4096) == \
        pytest.approx(ops / 197e12) and ops / 197e12 > stream / 819e9
    assert flops.slice_floor_s(config, CHIP, 1, 1, 64, 0, 256) == \
        pytest.approx(stream / 819e9)
    # a short admission's window layers attend the lower triangle only
    assert flops.admit_pairs(config, 100, "sliding_attention") == \
        flops.admit_pairs(config, 100, "full_attention") == 5050


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_flops_counts_equal_the_built_tree(size, config):
    """``param_counts`` / ``weight_bytes`` / the cache's bytes against the
    tree and the pool the program builds (shapes only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import flops_hybrid_moe as flops
    from chipbench.kinds import serve_hybrid_moe as kind
    from paddle_tpu.models import hybrid_moe

    c = config if size == "published" else \
        load(os.path.join(TINY, "tiny-hybrid.json"))
    cfg = kind.model_config(c)
    tree = jax.eval_shape(lambda: hybrid_moe.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(np.prod(a.shape)) for a in leaves) == \
        flops.param_counts(c)["total"]
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves) \
        == flops.weight_bytes(c)
    psz = c["serve"]["engine"]["page_size"]
    pool = jax.eval_shape(lambda: hybrid_moe.init_paged_pool(
        cfg, 11, psz, fixed_parts=5))
    assert sum(int(np.prod(a.shape)) * 2 for a in pool.values()) == \
        11 * flops.page_bytes(c, psz) + 5 * flops.fixed_part_bytes(c)
    assert flops.page_bytes(c, psz) == hybrid_moe.page_bytes(cfg, psz)
    assert flops.fixed_part_bytes(c) == hybrid_moe.fixed_part_bytes(cfg)


def record_by_hand(config):
    """A traced slice of 4 segments: 120 ticks of 64 live slots + 8
    admissions of 2,000 prompt rows each, 2.6 s of ``jit_segment``."""
    ticks = 120
    return {
        "kind": "serve_hybrid_moe", "config": config, "chip": CHIP,
        "slice": {"segments": 4, "steps": 128, "admits": 8,
                  "window_s": 2.65},
        "slice_counters": {
            "steps": 128, "picks": 8 * 4 * (ticks * 64 + 16000),
            "picks_held": 4 * (ticks * 64 + 16000), "max_load": 300,
            "experts_hit": ticks * 60 + 8 * 64,
            "rows_full": ticks * 64 * 2700, "rows_window": ticks * 64 * 512,
            "admit_rows": 8 * 4096, "admit_rows_used": 16000},
        "saturated": {"tokens": 100000, "steps": 1800, "seconds": 40.0},
        "saturated_counters": {
            "steps": 1800, "experts_hit": 110000,
            "rows_full": 1600 * 64 * 2700, "rows_window": 1600 * 64 * 512,
            "admit_rows": 200 * 4096, "admit_rows_used": 200 * 2176},
        "scopes": {"segment.decode/attention_full/paged_attention_full": 0.2,
                   "segment.decode/attention_window/"
                   "paged_attention_window": 0.1,
                   "segment.admit/attention_full": 0.05,
                   "segment.decode/experts/grouped_expert_matmul": 1.0,
                   "segment.admit/router": 0.02,
                   "segment.decode/qkv": 0.3},
        "trace": {"planes": 1,
                  "modules": {"jit_segment": {"calls": 4, "seconds": 2.6}},
                  "ops": {"grouped_expert_matmul.3": {"calls": 1024,
                                                      "seconds": 1.0},
                          "paged_attention_full": {"calls": 120,
                                                   "seconds": 0.2},
                          "paged_attention_window.2": {"calls": 480,
                                                       "seconds": 0.1},
                          "prefill_attention_full": {"calls": 8,
                                                     "seconds": 0.03},
                          "prefill_attention_window.1": {"calls": 32,
                                                         "seconds": 0.02},
                          "fusion.406": {"calls": 992, "seconds": 0.24}}},
    }


def test_the_twelve_readers_on_a_record_made_by_hand(config):
    from chipbench import flops_hybrid_moe as flops

    rec = record_by_hand(config)
    bw, peak = 819e9, 197e12
    sc = rec["slice_counters"]
    outside = flops.outside_expert_bytes(config)
    admission = max(flops.admission_ops(config, 2000) / peak,
                    (outside + 64 * 2 * EXPERT) / bw)
    least = (120 * outside + 120 * 60 * 2 * EXPERT
             + (sc["rows_full"] + sc["rows_window"]) * ROW) / bw \
        + 8 * admission
    got = {n: compute(n, rec) for n in READERS}
    assert got[READERS[0]] == pytest.approx(least / 2.6 * 100)
    assert got[READERS[1]] == pytest.approx(max(
        sc["experts_hit"] * 2 * EXPERT / bw,
        sc["picks_held"] * 2 * EXPERT / peak) / 1.0 * 100)
    assert got[READERS[2]] == pytest.approx(
        sc["rows_full"] * ROW / bw / 0.2 * 100)
    assert got[READERS[3]] == pytest.approx(
        sc["rows_window"] * ROW / bw / 0.1 * 100)
    assert got[READERS[4]] == pytest.approx(
        8 * 2000 * 2001 / 2 * 32768 / peak / 0.03 * 100)
    assert got[READERS[5]] == pytest.approx(
        8 * 4 * (128 * 129 / 2 + 1872 * 128) * 32768 / peak / 0.02 * 100)
    assert got[READERS[6]] == pytest.approx((0.2 + 0.1 + 0.05) / 128 * 1e3)
    assert got[READERS[7]] == pytest.approx((1.0 + 0.02) / 128 * 1e3)
    assert got[READERS[8]] == pytest.approx(100000 / 1800)
    assert got[READERS[9]] == pytest.approx(2176 / 4096 * 100)
    assert got[READERS[10]] == pytest.approx(110000 / 1800)
    assert got[READERS[11]] == pytest.approx(64 * (2700 + 512))
    for name in READERS[:6]:
        assert 0 < got[name] < 100, name
    # a program without the spans and counters (the parent), or an
    # untraced run: nothing read, nothing raised
    bare = {k: v for k, v in rec.items()
            if k not in ("scopes", "slice_counters", "saturated_counters")}
    bare["trace"] = dict(rec["trace"], ops={})
    for name in READERS[:8] + READERS[9:]:
        assert compute(name, bare) is None, name
    other = dict(rec, kind="serve_latent_moe")
    for name in READERS[:6]:
        assert compute(name, other) is None, name


def test_manifest_entries_name_the_files_that_are_there():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(READERS)
    assert manifest["per_layer"][-len(mine):] == mine     # appended
    for m in mine:
        assert m["moves"] == "serve_tokens_per_s"
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location("x", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert {k: m[k] for k in mod.META} == mod.META
    cell = manifest["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1 \
        and cell["config"] == CONFIG and cell["traffic"] == "mixed-backlog"
    assert len(cell["why"]) <= 200
    assert manifest["configs"][-1]["name"] == CONFIG
    tokens = [m for m in manifest["end_to_end"]
              if m["name"] == "serve_tokens_per_s"][0]
    assert tokens["workloads"][-1] == CELL and tokens["bound"] == 0.025
    wl = load(os.path.join(BENCH, "workloads", CELL + ".json"))
    assert f"{wl['rate_rps']:g} req/s" in cell["why"]
    assert os.path.exists(os.path.join(BENCH, "kinds",
                                       wl["kind"] + ".py"))


def test_config_file_is_the_catalog_row_but_for_its_cut(config):
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * 12
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "layer_types": kinds, "max_position_embeddings": 262144,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
        "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "sliding_windows": [128, 128, 128, 0] * 12,
        "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
    assert all(k in config for k in published)
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == \
        sorted(config["published"]) == [
            "num_experts", "num_hidden_layers", "num_nextn_predict_layers",
            "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == \
        (5, 16, 19200, 0)
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert mine["reduced"] == config["reduced"]
    assert mine["source"] == config["source"]
    for word in ("norm placement", "qk norm and rotary", "router", "rotary"):
        assert word in config["assumed"]
    assert "eight chips share each layer" in config["deployment"]
    assert "7.43 GB" in config["bytes"]
    assert config["share"] == {"held_experts": [0, 16], "router_width": 128,
                               "vocab_slice": [0, 19200]}
    eng = config["serve"]["engine"]
    assert (eng["slots"], eng["max_len"], eng["page_size"],
            eng["prompt_buckets"]) == (64, 5120, 16, [4096])
    assert config["serve"]["seg_steps"] == 32
    assert config["serve"]["max_queue"] == 128


def test_rate_is_its_multiple_of_the_sweeps_knee():
    wl = load(os.path.join(BENCH, "workloads", CELL + ".json"))
    named = re.findall(r"chipbench/sweeps/[\w.\-]+\.md", wl["rate_from"])
    assert named == ["chipbench/sweeps/" + CONFIG + ".mixed.md"]
    with open(os.path.join(ROOT, named[0])) as f:
        knees = re.findall(r"^knee: ([\d.]+) req/s$", f.read(), re.M)
    assert len(knees) == 1, f"{named[0]} has {len(knees)} 'knee:' lines"
    knee = float(knees[0])
    assert wl["rate_over_knee"] == 1.15
    assert wl["rate_rps"] == pytest.approx(round(1.15 * knee, 2), abs=1e-9)
    assert f"{knee:g} req/s" in wl["rate_from"]
    assert wl["backlog"] in (64, 80, 96) and "backlog_why" in wl
    assert wl["prompt_lens"] == [256, 512, 1024, 3072, 4096]
    assert wl["prompt_weights"] == [1, 2, 2, 2, 3]
    # ISSUE 36's answers, letter for letter (mean 576)
    assert wl["gen_lens"] == [256, 512, 1024] and \
        wl["gen_weights"] == [1, 2, 1]
    assert wl["kind"] == "serve_hybrid_moe"


def test_requests_open_with_the_backlog():
    import numpy as np

    from chipbench.kinds import serve_hybrid_moe as kind

    wl = load(os.path.join(BENCH, "workloads", CELL + ".json"))
    n = wl["backlog"]
    a = kind.requests(wl, 19200, 2**31 + 11, 51.0)
    b = kind.requests(wl, 19200, 2**31 + 11, 51.0)
    c = kind.requests(wl, 19200, 5, 51.0)
    assert [r.t for r in a[:n]] == [0.0] * n and a[n + 1].t > 0
    assert len(a) == n + round(wl["rate_rps"] * 51.0)
    assert all(x.t == y.t and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)                  # one multiset
    lens = np.array([len(r.prompt) for r in a])
    assert lens.max() == 4096 and lens.min() == 256
    assert 2100 < lens.mean() < 2250
    assert max(r.prompt.max() for r in a) < 19200
    # the check's sample holds a long prompt and a short one
    per = [{"rid": i, "prompt_len": int(k)} for i, k in enumerate(lens)]
    for seed in (3, 2**31 + 5):
        rids = kind.pick_checked(per, 4, seed)
        got = [int(lens[r]) for r in rids]
        assert len(set(rids)) == 4 and max(got) >= 3072 and min(got) <= 512


def _tiny(**over):
    import jax.numpy as jnp

    from chipbench.kinds import serve_hybrid_moe as kind

    config = load(os.path.join(TINY, "tiny-hybrid.json"))
    cfg = kind.model_config(config, max_seq_len=64, **over)
    return config, cfg, kind.init_weights(cfg, 7, jnp.bfloat16)


def test_reference_is_the_test_reference():
    """The benchmark's copy (a program a layer, blocked attention, the
    latent family's router and expert loop) == ``tests/
    reference_hybrid_moe`` (one [T, T] matrix a layer) on the tiny
    configuration, in float32."""
    import numpy as np

    from chipbench import reference_hybrid_moe as reference

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import reference_hybrid_moe as plain

    config, cfg, params = _tiny()
    seq = np.random.RandomState(3).randint(0, 256, 48).astype(np.int32)
    rows = np.arange(48)
    got, routing = reference.logits_at(params, seq, rows, config,
                                       config["share"], True)
    m = dict(config, layer_types=cfg.kinds,
             rope_theta=config["rope_parameters"]["rope_theta"])
    want = plain.logits(params, seq, m, tuple(config["share"]
                                              ["held_experts"]))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert routing["picks"].shape == (4, 48, 4)


def test_check_refuses_both_controls_faults():
    """The rule has teeth at the tiny size too: sequences the program
    decodes greedily pass; the same served by a program whose full layer
    is rotated, or with 3 mantissa bits in the attention projections, are
    refused by the logits' limits."""
    import numpy as np

    from chipbench.kinds import serve_hybrid_moe as kind

    config, cfg, params = _tiny()
    config["serve"]["check_rows"] = 24
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 256, n).astype(np.int32) for n in (9, 30, 5)]
    seqs = [(p, np.zeros(24, np.int32)) for p in prompts]
    for i in range(24):
        lg = kind.replay_logits(cfg, params, seqs, 32, i + 1, 8)[:, i]
        for (_, g), row in zip(seqs, lg):
            g[i] = int(row.argmax())
    names = ["a", "b", "c"]
    good = kind.check(cfg, params, params, config, seqs, names)
    # (over 256 columns a position's error scatters 0.5-2.5 x the
    # reference's: the median is held here, the tail at the real width)
    assert good["beyond_share"] == 0 and good["unjudged_share"] == 0
    assert 0 < good["logit_error"] < good["logit_error_limit"]
    from paddle_tpu.models import hybrid_moe

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(hybrid_moe, "ROTARY_KINDS",
                   (hybrid_moe.WINDOW, hybrid_moe.FULL))
        bad = kind.check(cfg, params, params, config, seqs, names)
    assert not bad["ok"] and bad["logit_error"] > 3 * bad["logit_error_limit"]
    low = kind.check(cfg, params, kind.low_precision(params), config, seqs,
                     names)
    assert not low["ok"] and low["logit_error"] > low["logit_error_limit"]


@pytest.fixture(scope="module")
def rehearsal():
    """One untraced run of the tiny configuration through the kind, as
    ``run.py`` would drive it."""
    from chipbench.kinds import serve_hybrid_moe as kind

    lines = {}
    ctx = {"args": argparse.Namespace(seed=2147483711, seconds=2.0, trace=0),
           "config": load(os.path.join(TINY, "tiny-hybrid.json")),
           "workload": load(os.path.join(TINY, "tiny-hybrid.backlog.json")),
           "rehearse": True,
           "log": lambda phase, **fields: lines.update({phase: fields}),
           "trace_dir": None, "open_window": lambda: None,
           "close_window": lambda: None}
    return kind.run(ctx), lines


def test_rehearsal_of_the_kind_on_a_tiny_config(rehearsal):
    record, lines = rehearsal
    # what ``run.py`` adds before the readers see the record
    record = dict(record, config=load(os.path.join(TINY,
                                                   "tiny-hybrid.json")))
    assert record["correct"] and record["failed"] == 0
    assert record["attempted"] == 6 + 8          # the backlog + 4 req/s x 2 s
    assert record["end_to_end"]["serve_tokens_per_s"] > 0
    counts = record["saturated_counters"]
    assert counts["steps"] == record["saturated"]["steps"] > 0
    assert 0 < counts["admit_rows_used"] < counts["admit_rows"]
    assert counts["admit_rows"] % 32 == 0
    # a tick's window layers read at most the window a live slot
    admits = counts["admit_rows"] // 32
    tokens = record["saturated"]["tokens"] - admits
    assert 0 < counts["rows_window"] <= 4 * 8 * tokens
    assert counts["rows_full"] >= tokens
    for i, want in ((8, record["saturated"]["tokens"] / counts["steps"]),
                    (9, counts["admit_rows_used"] / counts["admit_rows"]
                     * 100),
                    (10, counts["experts_hit"] / counts["steps"]),
                    (11, (counts["rows_full"] + counts["rows_window"])
                     / (counts["steps"] - admits))):
        assert compute(READERS[i], record) == pytest.approx(want)
    # no trace: the device metrics read nothing and do not raise
    for name in READERS[:8]:
        assert compute(name, record) is None
    check = lines["check"]
    assert check["ok"] and check["requests"] == 3
    assert 0 < check["logit_error"] <= check["logit_error_limit"]
    assert 0 < check["logit_error_p90"] <= check["logit_error_p90_limit"]
    assert check["beyond_share"] <= check["beyond_share_limit"] == 0.01
    assert lines["serve"]["window"]["rows_window"] > 0
    assert lines["serve"]["moe"]["picks_held"] > 0
    assert list(lines["warmup"]["kernels_routed_to"]) == [
        "ragged_paged_attention", "windowed_prefill_attention",
        "grouped_expert_matmul"]
    assert lines["warmup"]["pages"]["fixed_parts"] == 4
