"""TPU test lane: run the TPU-only pallas-kernel tests on the real chip and
record the result as a per-round artifact (VERDICT r2 weak #5: the kernel
tests are invisible to the CPU-forced default suite, so a silent
flash-kernel regression would only surface as a bench drop).

Writes ``TPU_TESTS_r<N>.json`` at the repo root:
  {"passed": n, "failed": n, "skipped": n, "duration_s": s,
   "tests": [{"id": ..., "outcome": ..., "duration_s": ...}, ...]}
and keeps the children's own reports under ``chiprun_out/tpu_test_lane/``.

This parent never imports jax. A chip belongs to one process at a time:
a parent that had touched JAX would hold it and every child below would
fail or hang. The children run one after another, and the platform in the
result is the one a child's jax reported.

Usage: python benchmarks/tpu_test_lane.py [round_number]
(no args: one past the highest round any ``*_r<N>.json`` record holds).
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the children's report files: one fixed place, which the chip tool
# brings back from the machine
REPORT_DIR = os.path.join(ROOT, "chiprun_out", "tpu_test_lane")

TPU_TEST_FILES = [
    "tests/test_flash_attention_tpu.py",
    "tests/test_flash_packed_gating.py",
    "tests/test_resnet_fusion_tpu.py",
    # r4: on-chip END-TO-END certification — full bf16 train steps
    # (framework numerics + fused optimizer), not just kernels
    "tests/test_train_step_tpu.py",
    # r7 (VERDICT r5 item 6): the INFERENCE surface — generate() chip-vs-
    # CPU greedy parity, fused-drain mixed-lengths+EOS, re-entrant
    # segments, unrolled-KV vs scan-layers cache parity, prefix-cache hit
    # (tests/test_decode_attention.py stays OUT of this lane: its
    # cpu-defaults-stay-dense assertion is false on a chip by design)
    "tests/test_inference_tpu.py",
    # r8 (ISSUE 3): the Pallas fused multi-tensor optimizer update —
    # real-Mosaic (SMEM scalars, in-place aliasing) trajectory parity
    "tests/test_fused_update_tpu.py",
    # r9 (ISSUE 4): the program auditor — sync/recompile/relayout/
    # donation passes on the REAL backend (the 8-device collective
    # fixtures skip on a single chip; the budget gate below certifies
    # the canonical programs' budgets on hardware)
    "tests/test_analysis.py",
    # r11 (ISSUE 6): the paged KV subsystem — on chip the engine/kernel
    # parity tests route attention through the REAL unified
    # page-indirect Mosaic kernel (scalar-prefetched page tables), so a
    # paging regression the CPU gather fallback hides fails here
    "tests/test_paged_kv.py",
    # r12 (ISSUE 7): the fleet serving subsystem — router determinism /
    # affinity / backpressure smoke on the real backend, plus the mp=2
    # tensor-parallel segment parity tests (these skip on a single-chip
    # host and run when the lane sees a multi-device TPU)
    "tests/test_fleet_serving.py",
    # r13 (ISSUE 8): the SLO robustness subsystem — chunked-prefill
    # parity through the REAL unified kernel, priority preemption /
    # resume identity, deadline shedding, fleet kill/recover
    "tests/test_slo_serving.py",
    # r14 (ISSUE 9): the SLO monitor & live ops surface — burn-rate
    # alert rules, exporter round-trips on loopback, explained-perf
    # ledger parity, the regression sentinel, cold-start stamping, and
    # the monitored-serve sync audit, all against the real backend
    "tests/test_slo_monitor.py",
    # r15 (ISSUE 10): speculative + sampled decoding — the multi-token
    # verified tick's greedy token identity, in-program sampling seed
    # isolation/replay, the speculative serve-loop sync audit and the
    # acceptance-aware SLO estimates, all against the real backend
    # (the verify path reuses the unified paged kernel's q_len>1 rows)
    "tests/test_spec_sampling.py",
    # r16 (ISSUE 11): the deterministic serving journal — replay
    # identity of journaled overload + fleet-failover serves on the
    # real backend (the fed decision clock makes replay timing-immune,
    # so chip compiles must not perturb a single decision), journey
    # joins, and the journaled-serve sync audit
    "tests/test_journal.py",
    # r17 (ISSUE 12): shadow & canary quality observability — the
    # in-program logit-digest segment on the real backend (digests
    # ride the real kernel's logits through the single fetch),
    # shadow-diff control identity, perturbation detection with exact
    # first-divergence positions, canary verdicts + auto-hold, and the
    # shadowed-fleet-loop sync audit
    "tests/test_quality.py",
    # r18 (ISSUE 13): capacity & memory observability — the page-level
    # metering identities, exhaustion-alert-leads-backpressure ordering
    # on a tight pool, the §3f×§3g planner validation, the /capacity
    # (+audit) endpoint and the monitored-serve sync audit, all against
    # the real backend's paged allocator traffic
    "tests/test_capacity.py",
    # r19 (ISSUE 14): tiered KV memory — spill->restore token identity
    # (host staging riding the real backend's single segment fetch),
    # the one-fetch audit over the tiered loop, directory steering +
    # migration-on-miss, the tier-transfer budget pass, and journal
    # replay of a spill-heavy serve, all against real D2H/H2D copies
    "tests/test_kv_tiers.py",
    # r20 (ISSUE 15): program-space coverage — registry-only key
    # construction, the envelope reachability proof, AOT warmup with
    # the zero-post-warmup-compile budget over the mixed workload, and
    # the persistent-cache warm-restart interplay, against REAL XLA:TPU
    # compiles (the 2.5 s class this whole subsystem exists to bound)
    "tests/test_program_coverage.py",
    # r21 (ISSUE 16): quantized serving — on chip the engine's
    # projection matmuls route through the REAL in-kernel-dequant
    # Mosaic path (quant_matmul) and the scale-fed decode-attention
    # kernel, so HBM genuinely carries int8/fp8; the parity, page-
    # machinery, sync-audit, qpseg-coverage and replay tests all gain
    # their hardware half here
    "tests/test_quantized_serving.py",
    # r22 (ISSUE 17): disaggregated prefill/decode serving — on chip
    # the handoff's host-bytes seam becomes the device-to-device
    # device_put path, so token identity across the pool split, the
    # per-crossing budget audit, per-pool AOT coverage and the
    # cross-pool replay all gain their hardware half here
    "tests/test_disagg.py",
    # r23 (ISSUE 18): long-context serving — on chip the spseg slab's
    # batch axis rides a REAL 'sp' mesh (each chunk's rows on their own
    # devices, ring attention via ppermute), so the sp=1 pseg
    # degeneracy, sp=2 pool page parity, slab-vs-dense identity, the
    # spanning-reservation continuation and the spseg AOT/zero-compile
    # certificate all gain their hardware half here
    "tests/test_longctx_serving.py",
    # r25 (ISSUE 20): elastic autoscaling — on chip the §3o warmup of
    # every scaled-up replica compiles the REAL ladder, chip_fit proves
    # candidates against the real HBM envelope, and the zero-compile +
    # sync-audit bars over the full elastic loop (scale-ups, drains,
    # directory migrations) gain their hardware half here
    "tests/test_autoscaler.py",
]


def _run_budget_gate(env) -> dict:
    """r9: certify the canonical programs' hazard budgets on the
    real chip (``python -m paddle_tpu.analysis --gate``) and record the
    per-program metrics next to the test outcomes. On TPU the relayout
    ledger counts the REAL tiled-layout copies, so a chip-only
    regression (a new relayout XLA:TPU materialises that the CPU
    lowering fused) fails here even when tier-1 stayed green."""
    out_json = os.path.join(REPORT_DIR, "analysis_gate.json")
    if os.path.exists(out_json):
        os.remove(out_json)  # never read a previous run's gate
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--gate",
         "--json", out_json],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    gate = {"returncode": proc.returncode, "programs": []}
    if os.path.exists(out_json):
        with open(out_json) as f:
            gate["programs"] = json.load(f)
    # r24: the per-program liveness peak ON CHIP — the XLA:TPU schedule
    # fuses/tiles differently from the CPU lowering, so these are the
    # measurements a "tpu"-scoped peak_bytes_max budget gets pinned
    # from (the chip cells of the budget registry)
    gate["peak_hbm_bytes"] = {
        p["program"]: p["metrics"].get("peak_bytes")
        for p in gate["programs"]
        if isinstance(p, dict) and "metrics" in p
        and not p.get("program", "").startswith("_")}
    if proc.returncode != 0:
        gate["tail"] = proc.stdout[-1500:]
    return gate


def _round_number(argv) -> int:
    if len(argv) > 1:
        return int(argv[1])
    rounds = [int(m.group(1)) for f in glob.glob(os.path.join(ROOT, "*_r*.json"))
              if (m := re.search(r"_r(\d+)\.json$", f))]
    return (max(rounds) + 1) if rounds else 1


def main() -> int:
    rnd = _round_number(sys.argv)
    os.makedirs(REPORT_DIR, exist_ok=True)
    report = os.path.join(REPORT_DIR, "junit.xml")
    if os.path.exists(report):
        os.remove(report)  # never count a previous run's report
    env = dict(os.environ, PADDLE_TPU_TEST_LANE="1")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", *TPU_TEST_FILES, "-q",
         f"--junit-xml={report}"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1800)
    dur = time.time() - t0
    tests = []
    counts = {"passed": 0, "failed": 0, "skipped": 0}
    if os.path.exists(report):
        import xml.etree.ElementTree as ET

        for tc in ET.parse(report).getroot().iter("testcase"):
            if tc.find("failure") is not None or tc.find("error") is not None:
                outcome = "failed"
            elif tc.find("skipped") is not None:
                outcome = "skipped"
            else:
                outcome = "passed"
            counts[outcome] += 1
            tests.append({
                "id": f"{tc.get('classname', '')}::{tc.get('name', '')}",
                "outcome": outcome,
                "duration_s": round(float(tc.get("time", 0.0)), 3)})
    else:
        # junit report missing (collection error): parse the summary line
        m = re.search(r"(\d+) passed", proc.stdout)
        counts["passed"] = int(m.group(1)) if m else 0
        m = re.search(r"(\d+) failed", proc.stdout)
        counts["failed"] = int(m.group(1)) if m else 0
        m = re.search(r"(\d+) skipped", proc.stdout)
        counts["skipped"] = int(m.group(1)) if m else 0
    gate = _run_budget_gate(env)
    result = {
        "round": rnd,
        "passed": counts.get("passed", 0),
        "failed": counts.get("failed", 0),
        "skipped": counts.get("skipped", 0),
        "duration_s": round(dur, 1),
        "returncode": proc.returncode,
        "analysis_gate": gate,
        "tests": tests,
    }
    out_path = os.path.join(ROOT, f"TPU_TESTS_r{rnd:02d}.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: result[k] for k in
                      ("round", "passed", "failed", "skipped", "duration_s")}
                     | {"analysis_gate_rc": gate["returncode"]}))
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout[-2000:])
    return proc.returncode or gate["returncode"]


if __name__ == "__main__":
    sys.exit(main())
