"""The five readers PR 25 adds, on the record of a CPU rehearsal of the
serve kind (the tiny configuration under ``rehearse/``, run in this
process): python -m pytest chipbench/tests/test_span_metrics.py -q

``test_chipbench.py::test_layer_metric_reads_a_recorded_run`` checks every
reader against ``data/run_records.json``; that file is the accepted
benchmark's and has no entry for these five, so they are checked here."""

import argparse
import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSE = os.path.join(HERE, "rehearse")
PARTS = ("ttft_ingest_wait_ms", "ttft_slot_wait_ms", "ttft_admit_wait_ms",
         "ttft_delivery_wait_ms")


def load(path):
    with open(path) as f:
        return json.load(f)


def compute(name, record):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute(record)


@pytest.fixture(scope="module")
def record():
    """One untraced rehearsal of ``tiny-gqa.chat`` through ``kinds/serve``,
    as ``run.py`` would drive it."""
    from chipbench.kinds import serve

    ctx = {"args": argparse.Namespace(seed=2147483711, seconds=2.0, trace=0),
           "config": load(os.path.join(REHEARSE, "configs", "tiny-gqa.json")),
           "workload": load(os.path.join(REHEARSE, "workloads",
                                         "tiny-gqa.chat.json")),
           "rehearse": True, "log": lambda phase, **fields: None,
           "trace_dir": None, "open_window": lambda: None,
           "close_window": lambda: None}
    return serve.run(ctx)


def test_the_four_parts_sum_to_the_mean_first_token_time(record):
    per = record["report"]["per_request"]
    assert record["correct"] and len(per) >= 8
    parts = [compute(name, record) for name in PARTS]
    assert all(p is not None and p >= 0 for p in parts)
    mean_ttft_ms = sum(r["ttft_s"] for r in per) / len(per) * 1e3
    assert sum(parts) == pytest.approx(mean_ttft_ms, abs=0.2)
    # a segment's span is split at a step of its loop: both sides are there
    assert compute("ttft_admit_wait_ms", record) > 0
    assert compute("ttft_delivery_wait_ms", record) > 0


def test_segment_host_ms_is_the_host_phases_over_the_segments(record):
    report = record["report"]
    phases = report["segment_phases"]
    assert set(phases) == {"ingest", "pick", "inputs", "launch", "fetch",
                           "replay", "telemetry"}
    assert phases["fetch"]["count"] == report["segments"]
    want = sum(v["seconds"] for k, v in phases.items()
               if k != "fetch") / report["segments"] * 1e3
    got = compute("segment_host_ms", record)
    assert got == pytest.approx(want) and 0 < got < 1e3


@pytest.mark.parametrize("name", PARTS + ("segment_host_ms",))
def test_a_program_without_the_spans_reports_nothing(name, record):
    # the parent's report: every field but the two PR 25 adds
    report = {k: v for k, v in record["report"].items()
              if k not in ("ttft_parts_mean_s", "segment_phases")}
    assert compute(name, dict(record, report=report)) is None
    assert compute(name, {"kind": "serve"}) is None
    assert compute(name, {"kind": "train", "report": None}) is None


def test_manifest_lists_the_five_beside_their_readers():
    m = load(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    mine = {x["name"]: x for x in m["per_layer"]
            if x["name"] in PARTS + ("segment_host_ms",)}
    assert len(mine) == 5
    assert all(x["workloads"] == ["internlm2-1.8b.chat-rate80"]
               for n, x in mine.items() if n in PARTS)
    assert mine["segment_host_ms"]["workloads"] == \
        ["internlm2-1.8b.chat-overload"]
