"""What PR 29 adds to the benchmark, on the CPU (not collected by tier-1):
python -m pytest chipbench/tests/test_latent_moe_bench.py -q

The six new readers on a recorded run of the new kind
(``data/latent_moe_record.json``, expected values worked out by hand below),
the flops file against hand counts, the workload file's rate against the
sweep file's one ``knee:`` line, the check's refusal of a wrong token, and
a rehearsal of kind ``serve_latent_moe`` on a tiny configuration.
``test_chipbench.py::test_layer_metric_reads_a_recorded_run`` has no
``want`` rows for the six readers in ``data/run_records.json`` (the
accepted benchmark's file, which this PR may not edit): they are checked
here."""

import argparse
import importlib.util
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CONFIG = "openpangu-ultra-moe-l5-ep16"
CELL = CONFIG + ".decode-backlog"
TINY = os.path.join(HERE, "rehearse_latent_moe")


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


# -- the hand counts (ISSUE 29's, from the catalog row) ---------------------
ATTENTION = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
             + 512 * 128 * 256 + 128 * 128 * 7680)          # 196.6 M
EXPERT = 3 * 7680 * 2048                                    # 47.2 M
NORMS = 4 * 7680 + 1536 + 512
OUTSIDE = ((ATTENTION + NORMS + 3 * 7680 * 18432)           # the dense layer
           + 4 * (ATTENTION + NORMS + EXPERT + 7680 * 256)  # shared, router
           + 7680 * 19200 + 7680)                           # head, final norm
OUTSIDE_BYTES = 2 * OUTSIDE + 4 * 2 * 7680 * 256            # router: float32


def test_flops_file_against_hand_counts(config):
    from chipbench import flops_latent_moe as flops

    p = flops.param_counts(config)
    assert p["attention"] == ATTENTION and round(ATTENTION / 1e5) == 1966
    assert p["expert"] == EXPERT == p["shared"]
    assert round(p["expert_layer_outside"] / 1e5) == 2458      # 245.8 M
    assert round(p["dense_layer"] / 1e5) == 6213               # 621.3 M
    assert p["outside_experts"] == OUTSIDE
    assert round(p["total"] / 1e7) == 492                      # 4.92 B
    assert flops.expert_bytes(config) == 2 * EXPERT
    assert flops.outside_expert_bytes(config) == OUTSIDE_BYTES
    assert flops.cache_row_bytes(config) == 1152
    assert flops.attention_ops_per_pair(config) == 278528
    # 242 operations to the byte against the chip's 240.5
    assert 278528 / 1152 == pytest.approx(241.8, abs=0.1)
    # a full house's tick: 15.7 of 16 experts hit in 4 layers, 128 x 530 rows
    tick = flops.step_bytes(config, 1, 4 * 15.7, 128 * 530)
    assert tick == pytest.approx(9.84e9, rel=0.01)


def test_the_six_readers_on_a_recorded_run(config):
    rec = dict(load(os.path.join(HERE, "data", "latent_moe_record.json"))
               ["record"], config=config)
    bw, peak = 819e9, 197e12
    decode, admits = 64 - 12, 12
    need = 64 * OUTSIDE_BYTES + 4000 * 2 * EXPERT \
        + decode * 68000.0 * 5 * 1152
    assert compute("segment_roofline.decode-backlog", rec) == pytest.approx(
        need / bw / 1.2 * 100)
    assert compute("grouped_expert_matmul_roofline", rec) == pytest.approx(
        4000 * 2 * EXPERT / bw / (0.40 + 0.22) * 100)
    pairs = (128 * 129 + 512 * 513 + 256 * 257) / 2 / 3
    rows = (128 + 512 + 256) / 3
    least = 5 * (decode * 68000.0 * max(278528 / peak, 1152 / bw)
                 + max(admits * pairs * 278528 / peak,
                       admits * rows * 1152 / bw))
    assert compute("mla_paged_attention_roofline", rec) == pytest.approx(
        least / (0.05 + 0.03) * 100)
    assert compute("experts_ms_per_step", rec) == pytest.approx(
        (0.30 + 0.52 + 0.10 + 0.02) / 64 * 1e3)
    assert compute("tokens_per_tick.decode-backlog", rec) == pytest.approx(
        300000 / 2900)
    assert compute("experts_hit_per_step", rec) == pytest.approx(62.5)
    for name in ("segment_roofline.decode-backlog",
                 "grouped_expert_matmul_roofline",
                 "mla_paged_attention_roofline"):
        assert 0 < compute(name, rec) < 100
    # a program without the spans and counters (the parent): nothing read
    bare = {k: v for k, v in rec.items()
            if k not in ("scopes", "slice_counters", "saturated_counters")}
    bare["trace"] = dict(rec["trace"], ops={})
    for name in ("segment_roofline.decode-backlog", "experts_ms_per_step",
                 "grouped_expert_matmul_roofline", "experts_hit_per_step",
                 "mla_paged_attention_roofline"):
        assert compute(name, bare) is None


def test_rate_is_its_multiple_of_the_sweeps_knee():
    wl = load(os.path.join(BENCH, "workloads", CELL + ".json"))
    named = re.findall(r"chipbench/sweeps/[\w.\-]+\.md", wl["rate_from"])
    assert named == ["chipbench/sweeps/" + CONFIG + ".decode.md"]
    with open(os.path.join(ROOT, named[0])) as f:
        knees = re.findall(r"^knee: ([\d.]+) req/s$", f.read(), re.M)
    assert len(knees) == 1, f"{named[0]} has {len(knees)} 'knee:' lines"
    knee, over = float(knees[0]), wl["rate_over_knee"]
    assert 1.15 <= over <= 1.25
    assert wl["rate_rps"] == pytest.approx(round(over * knee, 1), abs=1e-9)
    assert f"{knee:g} req/s" in wl["rate_from"]
    assert wl["backlog"] == 160 and wl["saturated_from_s"] >= 6.0
    assert wl["prompt_lens"] == [64, 128, 192, 256, 384, 512]
    assert wl["prompt_weights"] == [1, 2, 3, 3, 2, 1]
    assert (wl["gen_lens"], wl["gen_weights"]) == ([256, 512, 1024],
                                                   [1, 2, 1])


def test_config_file_is_the_catalog_row_but_for_its_cuts(config):
    published = {"attention_bias": False, "first_k_dense_replace": 3,
                 "hidden_act": "silu", "hidden_size": 7680,
                 "intermediate_size": 18432, "kv_lora_rank": 512,
                 "max_position_embeddings": 131072,
                 "model_type": "pangu_ultra_moe",
                 "moe_intermediate_size": 2048, "n_routed_experts": 256,
                 "n_shared_experts": 1, "norm_topk_prob": True,
                 "num_attention_heads": 128, "num_experts_per_tok": 8,
                 "num_hidden_layers": 61, "num_key_value_heads": 128,
                 "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "rms_norm_eps": 1e-05, "rope_theta": 25600000,
                 "routed_scaling_factor": 2.5, "sandwich_norm": True,
                 "tie_word_embeddings": False, "v_head_dim": 128,
                 "vocab_size": 153600}
    differs = sorted(k for k, v in published.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == sorted(config["published"])
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert mine["reduced"] == config["reduced"]
    assert config["share"] == {"held_experts": [0, 16], "router_width": 256,
                               "vocab_slice": [0, 19200]}
    for word in ("router", "norms", "rotary", "head_dim"):
        assert word in config["assumed"]


def test_requests_open_with_the_backlog():
    from chipbench.kinds import serve_latent_moe as kind

    wl = load(os.path.join(BENCH, "workloads", CELL + ".json"))
    a = kind.requests(wl, 19200, 2**31 + 11, 51.0)
    b = kind.requests(wl, 19200, 2**31 + 11, 51.0)
    c = kind.requests(wl, 19200, 5, 51.0)
    assert [r.t for r in a[:160]] == [0.0] * 160 and a[161].t > 0
    assert len(a) == 160 + round(wl["rate_rps"] * 51.0)
    assert all(x.t == y.t and (x.prompt == y.prompt).all()
               for x, y in zip(a, b))
    size = lambda rs: sorted((len(r.prompt), r.max_new_tokens)  # noqa: E731
                             for r in rs)
    assert size(a) != [(len(r.prompt), r.max_new_tokens) for r in a]
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)                  # one multiset
    assert max(r.prompt.max() for r in a) < 19200


@pytest.fixture(scope="module")
def rehearsal():
    """One untraced run of the tiny configuration through the kind, as
    ``run.py`` would drive it."""
    from chipbench.kinds import serve_latent_moe as kind

    lines = {}
    ctx = {"args": argparse.Namespace(seed=2147483711, seconds=2.0, trace=0),
           "config": load(os.path.join(TINY, "tiny-latent-moe.json")),
           "workload": load(os.path.join(TINY,
                                         "tiny-latent-moe.backlog.json")),
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
    assert 0 < counts["picks_held"] < counts["picks"]
    assert counts["experts_hit"] <= counts["steps"] * 2 * 4
    assert compute("experts_hit_per_step", record) == pytest.approx(
        counts["experts_hit"] / counts["steps"])
    assert compute("tokens_per_tick.decode-backlog", record) > 0
    # no trace: the device metrics read nothing and do not raise
    for name in ("segment_roofline.decode-backlog", "experts_ms_per_step",
                 "grouped_expert_matmul_roofline",
                 "mla_paged_attention_roofline"):
        assert compute(name, record) is None
    check = lines["check"]
    assert check["ok"] and check["worst_sigmas"] <= check["tie_sigmas"] == 5.0
    assert check["beyond_share"] <= check["beyond_share_limit"] == 0.01
    assert check["unjudged_share"] <= check["unjudged_share_limit"] == 0.05
    assert 0 < check["logit_error"] <= check["logit_error_limit"] == 1.6
    assert check["clean_positions"] > 0
    assert "router_sigma" in check


def test_check_refuses_a_wrong_token_and_lower_precision(rehearsal):
    """The rule has teeth at the tiny size too: a token that is not the
    reference's choice is refused, and so is a run whose weights were
    rounded to 8 bits (the precision below the configuration's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference_latent_moe as reference
    from chipbench.kinds import serve_latent_moe as kind

    config = load(os.path.join(TINY, "tiny-latent-moe.json"))
    cfg = kind.model_config(config, max_seq_len=48)
    params = kind.init_weights(cfg, 7, jnp.bfloat16)
    prompt = np.arange(3, 12, dtype=np.int32)
    share = config["share"]

    def greedy(p, n=16):
        seq = np.zeros((48,), np.int32)
        seq[:len(prompt)] = prompt
        out = []
        for i in range(n):
            lg, _ = reference.logits_at(p, seq, [len(prompt) - 1 + i],
                                        config, share, False)
            out.append(int(np.asarray(lg)[0].argmax()))
            seq[len(prompt) + i] = out[-1]
        return out

    good = greedy(params)
    v = reference.check_generation(params, config, share, prompt, good, 48,
                                   16, "bf16 greedy")
    assert v["checked"] == 16 and v["beyond"] == v["unjudged"] == 0
    assert v["worst_sigmas"] <= 5.0
    bad = list(good)
    lg, _ = reference.logits_at(params, np.concatenate(
        [prompt, good, np.zeros(48 - 25, np.int32)]).astype(np.int32),
        [len(prompt) + 4], config, share, True)
    bad[5] = int(np.asarray(lg)[0].argmin())
    v = reference.check_generation(params, config, share, prompt, bad, 48,
                                   16, "a planted token")
    assert v["beyond"] >= 1 and v["beyond_worst_sigmas"] > 5.0

    def to_8_bits(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype) \
            if a.ndim >= 2 and a.dtype == jnp.bfloat16 else a

    low = greedy(jax.tree_util.tree_map(to_8_bits, params), n=16)
    v8 = reference.check_generation(params, config, share, prompt, low, 48,
                                    16, "8-bit weights")
    assert low != good
    assert v8["beyond"] / v8["checked"] > reference.BEYOND_SHARE_MAX


def test_a_trade_within_the_band_explains_a_flipped_pick():
    """The search finds the routing a program rightly took: tokens made
    greedily under ONE legitimate trade at one position are beyond the band
    under the reference's own routing there, and explained."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference_latent_moe as reference
    from chipbench.kinds import serve_latent_moe as kind

    config = load(os.path.join(TINY, "tiny-latent-moe.json"))
    share = config["share"]
    cfg = kind.model_config(config, max_seq_len=48)
    params = kind.init_weights(cfg, 11, jnp.bfloat16)
    seq = np.zeros((48,), np.int32)
    seq[:20] = np.random.RandomState(3).randint(0, 256, 20)
    rows = np.arange(8, 20)
    _, r32 = reference.logits_at(params, seq, rows, config, share, True)
    _, r16 = reference.logits_at(params, seq, rows, config, share, False,
                                 forced=np.asarray(r32["picks"]))
    trades, sigma = reference.trades_allowed(r32, r16, 4, (4, 4))
    assert trades and (sigma > 0).all()
    top, order = np.asarray(r32["top"]), np.asarray(r32["order"])
    for (layer, t), pairs in trades.items():
        for a, b in pairs:
            assert a < 4 <= b
            assert top[layer, t, a] - top[layer, t, b] <= \
                5 * np.sqrt(2) * sigma[layer]
            assert any(4 <= order[layer, t, r] < 8 for r in (a, b))
    # route one token by its first trade: the picks differ there alone
    (layer, t), pairs = next(iter(trades.items()))
    trade = np.full((2, 48, 2), -1, np.int32)
    trade[layer, t] = pairs[0]
    _, traded = reference.logits_at(params, seq, rows, config, share, True,
                                    trade=trade)
    differs = (np.asarray(traded["picks"]) != np.asarray(r32["picks"]))
    assert differs[layer, t].sum() == 1 and differs.sum() <= 1 + differs[
        :, t + 1:].sum() + differs[layer + 1:, t].sum()
