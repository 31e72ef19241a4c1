"""Serving/decode lane: run the decode + serving benches on the real chip
and record the result as a per-round artifact (VERDICT r3 item 6: the
README's serving claims had no captured artifact, so a serving regression
was invisible to the round record).

Writes ``SERVING_r<N>.json`` at the repo root:
  {"round": N, "platform": ..., "decode": {...llama_decode json...},
   "serving": {...llama_serving json incl. packing + p50/p99...},
   "online": {...llama_serving --online json: Poisson arrivals at
              0.5/1/2x the measured service rate, MEASURED per-request
              TTFT + e2e p50/p99, vs fixed batching...},
   "prefix": {...llama_serving --prefix json: shared-prefix KV cache
              on/off tok/s...},  (r7: the online serving subsystem)
   "paged": {...llama_serving --paged json: paged-KV engine vs
              contiguous on the same trace (token-identical), TTFT
              p50/p99, pages-per-token, tight-pool max_len-wall run,
              shared-prefix dedup ratio vs the row-copy cache...},
              (r11: the paged KV subsystem)
   "fleet": {...llama_serving --fleet json: N=1/2/4 engine replicas
              behind the prefix-affinity router on ONE seeded Poisson
              trace at N x the base rate — tok/s + TTFT p99 scaling vs
              N, token identity across fleet sizes, affinity/dispatch
              accounting, rank-merged telemetry...},
              (r12: the fleet serving subsystem)
   "overload": {...llama_serving --overload json: the latency-vs-load
              curve at 1/2/4x the measured service rate through the SLO
              scheduler — per-class TTFT/e2e, preempt + shed counts,
              the high-class-p99-bounded bar...},
   "failover": {...llama_serving --failover json: seeded replica kill
              mid-serve — zero lost requests, token identity vs the
              no-fault run, re-admission probing...},
              (r13: SLO-aware serving under overload and failure)
   "slo": {...llama_serving --slo json: the live ops surface on the
              overload trace — error-budget burn-rate alerting (zero
              alerts at 1x, a page alert before the first shed at 4x),
              explained perf (live roofline_fraction within 10% of the
              SCALING §3c model), cold-start→first-token for N=1 and
              fleet N=2 plus the r15 persistent-compile-cache
              cold-vs-warm restart pair, one literal OpsServer
              scrape...},
              (r14: SLO monitor & operator scrape endpoint)
   "spec": {...llama_serving --spec json: speculative decoding —
              effective tok/s ratio vs the non-speculative engine at
              measured acceptance (greedy token-identical asserted),
              acceptance histogram by prompt class + OOD control,
              acceptance-vs-K curve, sampled-speculative replay
              determinism...},
              (r15: speculative + sampled decoding in-program)
   "quality": {...llama_serving --shadow json: shadow & canary quality
              observability — a same-weights control certifying 100%
              token match through the shadow pair, a seeded
              logit-perturbation variant caught with exact
              first-divergence positions and a quality page firing
              before any per-class SLO violation, bit-exact journal
              replay with the shadow attached, the <=2%
              shadow-attachment overhead gate, and a seeded canary
              split with a journaled verdict + auto-hold demo...},
              (r17: shadow & canary serving, ISSUE 12)
   "quant": {...llama_serving --quant json: quantized serving — the
              analytic bytes/tick ledger (int8 weights+KV+scales vs
              bf16, >= 1.7x), the int8 shadow pair certified against
              the QualityMonitor token-match/logit/KL bar, a 25% int8
              canary split, within-dtype determinism + bit-exact
              journal replay, the qpseg AOT ladder's zero-compile
              certificate, and the fp8 determinism check...},
              (r21: quantized serving, ISSUE 16)
   "disagg": {...llama_serving --disagg json: disaggregated
              prefill/decode pools — the long-prompt overload trace
              served co-resident vs split pools (token identity,
              decode-pool TBT p99 flatness ordering), every KV
              page-set handoff within the bytes <= KV-size budget,
              zero post-warmup compiles under per-pool envelopes with
              the warmup bill split vs the co-resident union ladder,
              and the bit-exact cross-pool journal replay...},
              (r22: disaggregated serving, ISSUE 17)
   "longctx": {...llama_serving --longctx json: long-context serving —
              one 256-token prompt sequence-parallel-prefilled at
              sp=1/2/4 (the slab-step ledger exactly 1/sp, wall TTFT
              evidence), tokens bit-identical across sp and vs the
              unsharded reference, co-resident short-request TBT p99
              per sp, the sp=1 multi-segment spanning reservation,
              the spseg AOT ladder's zero-compile certificate, the
              one-fetch sync audit, and the bit-exact sp=2 journal
              replay...},
              (r23: long-context serving, ISSUE 18)
   "elastic": {...llama_serving --elastic json: elastic autoscaling —
              the seeded 1x->4x->1x step-load episode as an observable
              control loop (scale-up journal-ordered before the first
              error-budget page, every added replica §3o-warmed before
              traffic, polite drains stranding zero requests with the
              repeat wave's prefix hit-rate held at 1.0 through the
              directory-aware migration, and the bit-exact elastic
              journal replay, scale_decisions included)...},
              (r25: elastic fleet autoscaling, ISSUE 20)
   "telemetry_headlines": {...r10 runtime-telemetry headlines per mode —
              queue depth / slot occupancy / prefix hit rate /
              backpressure counters from paddle_tpu.observability; the
              full rank-tagged snapshots ride inside each mode's
              "telemetry" section...},
   "journal_headline": {...r16 deterministic-journal bars — the 4x
              overload serve and the replica-kill fleet serve each
              journaled and replayed in-lane (replay_identical:
              tokens + decision stream bit-exact), journal write
              overhead vs the 2% contract, and the shed / cross-replica
              failover journeys a postmortem reads first...}}

Usage: python benchmarks/serving_lane.py [round_number]
(no args: one past the highest round any ``*_r<N>.json`` record holds,
matching benchmarks/tpu_test_lane.py). The children measure the chip:
``llama_serving.py``'s default model needs a TPU and fails without one.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# ONE round-derivation rule for every artifact lane (a copy here would
# silently drift from the TPU test lane's numbering)
from tpu_test_lane import _round_number  # noqa: E402


def _run_json(script: str, timeout: int = 900, args: tuple = ()):
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join("benchmarks", script), *args],
            cwd=ROOT, capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        # a hung bench must still leave an artifact (the whole point of
        # this lane is making serving regressions visible)
        return {"rc": -1, "error": f"timeout after {timeout}s",
                "stderr_tail": (e.stderr or b"")[-1500:].decode(
                    "utf-8", "replace") if isinstance(e.stderr, bytes)
                else str(e.stderr or "")[-1500:],
                "duration_s": round(time.time() - t0, 1)}
    out = {}
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            out = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    out["rc"] = proc.returncode
    out["duration_s"] = round(time.time() - t0, 1)
    if proc.returncode != 0:
        out["stderr_tail"] = proc.stderr[-1500:]
    return out


def main() -> int:
    rnd = _round_number(sys.argv)
    # platform comes from a CHILD's report, and this parent never imports
    # jax: a chip belongs to one process at a time, so a parent that had
    # touched JAX would hold it and every child below would fail or hang
    result = {
        "round": rnd,
        "decode": _run_json("llama_decode.py"),
        "serving": _run_json("llama_serving.py"),
        "online": _run_json("llama_serving.py", args=("--online",)),
        "prefix": _run_json("llama_serving.py", args=("--prefix",)),
        "paged": _run_json("llama_serving.py", args=("--paged",)),
        "fleet": _run_json("llama_serving.py", args=("--fleet",)),
        # r13 (ISSUE 8): the SLO robustness lanes — latency-vs-load with
        # priorities/preemption/shedding, and the replica-kill run
        "overload": _run_json("llama_serving.py", args=("--overload",)),
        "failover": _run_json("llama_serving.py", args=("--failover",)),
        # r14 (ISSUE 9): the live ops surface — burn-rate alerting,
        # explained perf, cold start, one operator scrape
        "slo": _run_json("llama_serving.py", args=("--slo",)),
        # r15 (ISSUE 10): speculative decoding — effective tok/s ratio
        # vs non-spec at measured acceptance (greedy token-identical),
        # acceptance histogram by prompt class, acceptance-vs-K curve,
        # sampled-speculative replay determinism
        "spec": _run_json("llama_serving.py", args=("--spec",)),
        # r17 (ISSUE 12): shadow & canary quality observability
        "quality": _run_json("llama_serving.py", args=("--shadow",)),
        # r18 (ISSUE 13): capacity & memory observability — pool
        # timeline + breakdown, the capacity page firing before the
        # first pages-backpressure deferral on the tight-pool 4x
        # overload, the §3f×§3g planner validated ±10% cross-serve,
        # and the /capacity (+audit) scrape
        "capacity": _run_json("llama_serving.py", args=("--capacity",)),
        # r19 (ISSUE 14): tiered KV memory — the many-tenant
        # working-set-3x-pool trace served HBM-only vs tiered
        # (hit-rate + TTFT p99 vs the §3n model, token identity),
        # tier-transfer budget audit, SyncAudit over the tiered loop,
        # bit-exact journal replay, and the 2-replica directory
        # steering + migration-on-miss sub-run
        "tiered": _run_json("llama_serving.py", args=("--tiered",)),
        # r20 (ISSUE 15): program-space coverage + AOT warmup — the
        # fresh-replica scale-up certificate: full enumerated ladder
        # compiled at build, zero backend compiles over the mixed
        # serve (chunked + prefix + preempt + failover), cold-start
        # split into aot_warmup_s + first_token_s, tokens identical
        # AOT on|off, enumerated-vs-used differential clean
        "aot": _run_json("llama_serving.py", args=("--aot",)),
        # r21 (ISSUE 16): quantized serving — the analytic bytes/tick
        # ledger (int8+scales vs bf16 >= 1.7x on the HBM-bound tick),
        # the int8 shadow pair certified by the QualityMonitor bar
        # (token-match floor + logit/KL budgets, never paging), a 25%
        # int8 canary split, within-dtype determinism + bit-exact
        # journal replay, and the qpseg AOT ladder serving with zero
        # post-warmup compiles
        "quant": _run_json("llama_serving.py", args=("--quant",)),
        # r22 (ISSUE 17): disaggregated prefill/decode serving — the
        # long-prompt overload trace served co-resident vs split pools
        # (token identity, decode-pool TBT p99 flatness ordering),
        # every KV page-set handoff within the bytes <= KV-size
        # budget, zero post-warmup compiles under per-pool envelopes
        # with the warmup bill split vs the co-resident union ladder,
        # the one-fetch + one-flush sync audit, and the bit-exact
        # cross-pool journal replay
        "disagg": _run_json("llama_serving.py", args=("--disagg",)),
        # r23 (ISSUE 18): long-context serving — the 256-token prompt
        # sequence-parallel-prefilled at sp=1/2/4 (slab-step ledger
        # exactly 1/sp, wall TTFT evidence alongside), tokens
        # bit-identical across sp AND vs the unsharded reference,
        # co-resident short-request TBT p99 per sp, the sp=1
        # multi-segment spanning reservation, the spseg AOT ladder's
        # zero-compile certificate, the one-fetch-per-segment sync
        # audit, and the bit-exact sp=2 journal replay
        "longctx": _run_json("llama_serving.py", args=("--longctx",)),
        # r25 (ISSUE 20): elastic autoscaling — the 1x->4x->1x
        # step-load episode as an observable control loop: scale-up
        # journal-ordered before the first error-budget page, §3o
        # warmup before traffic on every added replica, zero-strand
        # polite drains holding the repeat wave's prefix hit-rate at
        # 1.0 through the directory-aware migration, and the bit-exact
        # elastic journal replay (scale_decisions included)
        "elastic": _run_json("llama_serving.py", args=("--elastic",)),
    }
    result["platform"] = result["online"].get("platform", "unknown")
    # r10: lift each mode's runtime-telemetry headline (queue depth,
    # occupancy, hit rate, backpressure — the operator-scrape numbers) to
    # the top level; the full rank-tagged snapshots stay nested under
    # online/prefix "telemetry"
    result["telemetry_headlines"] = {
        k: (result[k].get("telemetry") or {}).get("headline")
        for k in ("online", "prefix", "paged", "fleet", "overload",
                  "failover", "slo", "spec", "quality", "capacity",
                  "tiered", "quant", "disagg", "longctx", "elastic")}
    # r15: lift the speculative headline — the roofline-beating ratio
    # an operator (or the next round's reviewer) checks first
    spec = result["spec"].get("headline") or {}
    result["spec_headline"] = {
        "effective_tok_s_ratio": spec.get("effective_tok_s_ratio"),
        "accept_rate": spec.get("accept_rate"),
        "tokens_identical": spec.get("tokens_identical"),
        "pass": spec.get("pass"),
    }
    # r14: lift the SLO headline — the alert/explained-perf/cold-start
    # bars an operator (or the next round's reviewer) checks first
    slo = result["slo"]
    result["slo_headline"] = {
        "zero_alerts_at_1x": (slo.get("compliant_1x") or {}).get(
            "zero_alerts"),
        "page_fired_at_4x": (slo.get("overload_4x") or {}).get(
            "page_fired"),
        "page_before_first_shed": (slo.get("overload_4x") or {}).get(
            "page_before_first_shed"),
        "roofline_fraction_within_10pct": (slo.get("explained_perf")
                                           or {}).get("within_10pct"),
        "cold_start_n1_s": (slo.get("cold_start") or {}).get("n1_s"),
        "cold_start_fleet_worst_s": (slo.get("cold_start") or {}).get(
            "fleet_worst_s"),
    }
    # r17 (ISSUE 12): lift the quality headline — the shadow/canary
    # bars (control identity, perturbation caught with position, page
    # leads the SLO surface, replay survives the shadow, overhead,
    # auto-hold) a reviewer checks first
    result["quality_headline"] = result["quality"].get("headline")
    # r16 (ISSUE 11): lift the deterministic-journal headline — the
    # black-box bars (bit-exact replay of the overload + replica-kill
    # serves, journal write overhead vs the 2% contract, and the two
    # journeys a postmortem reads first)
    jo = result["overload"].get("journal") or {}
    jf = result["failover"].get("journal") or {}
    result["journal_headline"] = {
        "overload_replay_identical": jo.get("replay_identical"),
        "failover_replay_identical": jf.get("replay_identical"),
        "overhead_pct_min_of_3": jo.get("overhead_pct_min_of_3"),
        "overhead_within_2pct": jo.get("overhead_within_2pct"),
        "shed_journey_kinds": (jo.get("shed_journey") or {}).get("kinds"),
        "failover_journey_replicas": (jf.get("failover_journey")
                                      or {}).get("replicas"),
    }
    # r18 (ISSUE 13): lift the capacity headline — the alert-leads-
    # valve ordering, the planner's ±10% cross-serve validation and
    # the meter identity a reviewer checks first
    capd = result["capacity"]
    result["capacity_headline"] = {
        "page_fired_at_4x": (capd.get("overload_4x") or {}).get(
            "page_fired"),
        "page_before_first_backpressure": (
            capd.get("overload_4x") or {}).get(
            "page_before_first_backpressure"),
        "planner_high_water_within_10pct": (
            capd.get("planner") or {}).get("high_water_within_10pct"),
        "planner_tok_s_within_10pct": (capd.get("planner") or {}).get(
            "tok_s_within_10pct"),
        "meter_streams_identity": (capd.get("probe") or {}).get(
            "meter_streams_identity"),
        "audit_clean": (capd.get("ops_scrape") or {}).get("audit_clean"),
    }
    # r24 (ISSUE 19): lift the memory headline — the §3s static HBM
    # envelope the capacity planner now carries (weights + pool + peak
    # transient vs chip HBM) and its ±10% KV-live cross-validation
    # against the r18 PoolMonitor high-water
    env = (capd.get("planner") or {}).get("static_envelope") or {}
    fit = env.get("chip_fit") or {}
    result["memory_headline"] = {
        "envelope_bytes": fit.get("envelope_bytes"),
        "weights_bytes": fit.get("weights_bytes"),
        "pool_bytes": fit.get("pool_bytes"),
        "transient_bytes": fit.get("transient_bytes"),
        "hbm_bytes": fit.get("hbm_bytes"),
        "fits": fit.get("fits"),
        "utilization": fit.get("utilization"),
        "kv_live_within_10pct": env.get("kv_live_within_10pct"),
        "kv_live_ratio": env.get("kv_live_ratio"),
    }
    # r19 (ISSUE 14): lift the tiered-KV headline — token identity,
    # hit-rate + TTFT vs the §3n model, the tier-transfer budget, the
    # one-fetch audit, replay identity and directory steering
    result["tiered_headline"] = result["tiered"].get("headline")
    # r20 (ISSUE 15): lift the AOT/coverage headline — the
    # zero-mid-serve-compile certificate + the measured scale-up split
    # (aot_warmup_s + first_token_s vs the no-AOT cold start) a
    # reviewer (and the item-4 autoscaler) checks first
    result["aot_headline"] = result["aot"].get("headline")
    # r21 (ISSUE 16): lift the quantized-serving headline — the
    # bytes/tick ratio, the shadow certification verdict, determinism/
    # replay identity and the quant path's zero-compile certificate
    result["quant_headline"] = result["quant"].get("headline")
    # r22 (ISSUE 17): lift the disaggregated-serving headline — token
    # identity vs co-resident, the TBT flatness ordering, the
    # per-crossing handoff budget, the per-pool zero-compile + warmup
    # bill split, and the cross-pool replay identity
    result["disagg_headline"] = result["disagg"].get("headline")
    # r23 (ISSUE 18): lift the long-context headline — the 1/sp
    # slab-step law, token identity across sp and vs the unsharded
    # reference, the spanning reservation, the spseg zero-compile
    # certificate and the sp=2 replay identity
    result["longctx_headline"] = result["longctx"].get("headline")
    # r25 (ISSUE 20): lift the elastic headline — the control-loop
    # ordering bars (scale-up before the first page, warmup before
    # traffic, zero-strand drain with repeat hit-rate 1.0) and the
    # bit-exact elastic replay a reviewer checks first
    result["elastic_headline"] = result["elastic"].get("headline")
    path = os.path.join(ROOT, f"SERVING_r{rnd:02d}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    ok = all(result[k].get("rc") == 0
             for k in ("decode", "serving", "online", "prefix", "paged",
                       "fleet", "overload", "failover", "slo", "spec",
                       "quality", "capacity", "tiered", "aot", "quant",
                       "disagg", "longctx", "elastic"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
