"""Per-program hazard budgets — the ledgers, made enforceable.

Every number here was once a hand-computed ledger entry guarding a perf
win (ARCHITECTURE.md r6/r7/r8 ledgers). The registry pins them per
canonical program; ``check`` turns an ``AuditReport`` into a list of
violations and ``python -m paddle_tpu.analysis --gate`` fails on any —
so a reintroduced host sync, a stray shape compile, a new relayout or a
dropped donation breaks the suite instead of waiting for the next
profiling round.

Adding a budget: measure the program's metrics once (``python -m
paddle_tpu.analysis --program <name>``), pin the measured value (NOT a
padded guess — the point is that growth fails), and cite why the number
is what it is. Byte ceilings get a small (≤5%) allowance only when a
metric is platform-sensitive; counts are exact.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

__all__ = ["Budget", "BUDGETS", "budget_for", "check"]


@dataclass
class Budget:
    # dynamic (per warm replay) — platform-INDEPENDENT contracts: a sync
    # is a sync and a warm compile is a hazard on every backend
    flagged_syncs: int = 0                 # non-allowed device→host syncs
    allowed_syncs_per_replay: Dict[str, int] = field(default_factory=dict)
    warm_compiles: int = 0                 # XLA compiles after warmup
    # static (per compiled program) — byte ledgers are PLATFORM-SCOPED:
    # the values below were pinned on the `bytes_platform` lowering and
    # only bind there (XLA:TPU materialises different copies than
    # XLA:CPU; the chip lane records its own measured ledger into
    # TPU_TESTS_r<N>.json, from which a "tpu" budget gets pinned)
    relayout_bytes_max: Optional[int] = None
    pack_bytes_max: Optional[int] = None
    undonated_bytes_max: Optional[int] = None
    # r24: ceiling on the liveness pass's peak live HBM (memory.peak_live
    # — the number that actually OOMs a chip). Platform-scoped like the
    # other byte ledgers: XLA:CPU and XLA:TPU schedule and fuse
    # differently, so the chip cell gets pinned from the lane's
    # TPU_TESTS peak_hbm_bytes artifact, not from this CPU value. The
    # CPU values follow the INSTALLED jaxlib's schedule too: PR 21
    # re-measured the serving programs' peaks on jaxlib 0.9.0, whose
    # XLA:CPU keeps 10-20% (quant: 59%) more live at the peak than the
    # one r24 pinned them on, with the programs unchanged.
    peak_bytes_max: Optional[int] = None
    bytes_platform: str = "cpu"
    require_collectives_clean: bool = True
    notes: str = ""


_MiB = 1 << 20


BUDGETS: Dict[str, Budget] = {
    # Fused AMP-O2 train step: ONE program per step, params + velocity
    # donated, loss fetch happens outside the replay closure (the loop
    # body never reads it) — so the hot loop holds ZERO syncs. The
    # relayout/pack bytes are the optimizer's flat-pack traffic for this
    # 20-tensor population plus conv layout copies (measured on the CPU
    # lowering, pinned at measurement).
    "amp_o2_train_step": Budget(
        flagged_syncs=0,
        warm_compiles=0,
        # measured 15,108,056 B on the CPU lowering (fp32 dW transposes
        # of the 4096x128 linear + conv backward layout copies) + ~5%
        relayout_bytes_max=15_900_000,
        pack_bytes_max=1 * _MiB,       # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (batch rides < thresh)
        # liveness peak measured 10,076,748 B on the 8-virtual-device
        # CPU lowering the gate runs under (bf16 master/model param
        # copies + the fused backward's conv activation window; the
        # single-device lowering schedules ~1 MiB tighter) + ~5%
        peak_bytes_max=10_580_000,
        notes="r8 class: GradScaler-free bf16 path; params+state alias"),
    # The segment over the paged pool: ONE dispatch + ONE event fetch
    # (the measured r7 contract). The fetch is the allowed per-segment
    # sync; anything else in the loop is the 2.5 s-mid-serve class. Page
    # tables are DATA (no prefix-width shape family — zero unbucketed-dim
    # hazards from paging) and pack bytes are ZERO (a prefix hit
    # contributes no row copies to the program).
    "paged_serving_segment": Budget(
        flagged_syncs=0,
        allowed_syncs_per_replay={"serving.segment_event_fetch": 1},
        warm_compiles=0,
        # measured 1,040,964 B (while-body pool carries + the admit
        # branch's page-scatter copies) + ~5%
        relayout_bytes_max=1_095_000,
        pack_bytes_max=_MiB // 2,      # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (pool+table donated)
        # liveness peak measured 1,893,693 B (weights + donated pool
        # counted once + segment while carry) + ~5%
        peak_bytes_max=1_988_000,
        notes="r11 contract: paged pool + page tables, one fetch/segment, "
              "prefix reuse is refcount data not program shape"),
    # The CHUNKED-PREFILL paged segment (r13, ISSUE 8a): the
    # paged_serving_segment contract with admits split into declared-
    # ladder chunks interleaved with decode ticks. Chunking must be
    # FREE at the hazard level: still exactly one event fetch per
    # segment, zero warm compiles (chunk widths are declared, so the
    # ("cseg", ...) key family is finite), zero pack bytes (chunks
    # write page-indirectly in place — no staging concats), and the
    # relayout ledger is the same while-body pool-carry class as the
    # unchunked paged segment (measured slightly BELOW it: the chunk
    # branch's [1, C] windows carry less than the [1, s_max] admit).
    "chunked_serving_segment": Budget(
        flagged_syncs=0,
        allowed_syncs_per_replay={"serving.segment_event_fetch": 1},
        warm_compiles=0,
        # measured 967,404 B (while-body pool carries + chunk-scatter
        # copies) + ~5%
        relayout_bytes_max=1_015_000,
        pack_bytes_max=_MiB // 2,      # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (pool+table donated)
        # liveness peak measured 1,893,997 B (pool counted once; chunk
        # windows carry less than the full admit) + ~5%
        peak_bytes_max=1_989_000,
        notes="r13 contract: chunked prefill interleaved with decode — "
              "bounded time-between-tokens at zero extra syncs/compiles"),
    # The SPECULATIVE paged segment (r15, ISSUE 10): multi-token
    # verified ticks must be FREE at the hazard level — drafting is
    # in-program (the n-gram table is segment state, zero host
    # contact), acceptance counts ride the one allowed event fetch, and
    # the ("sseg", n_pad, K, steps) key family pins the admit width so
    # speculation adds zero program shapes. The relayout ledger is the
    # paged while-body pool-carry class plus the verify tick's [K+1]-
    # wide scatter copies (measured slightly ABOVE the unchunked paged
    # segment: the q_len>1 write path carries K+1 rows per slot).
    "spec_serving_segment": Budget(
        flagged_syncs=0,
        allowed_syncs_per_replay={"serving.segment_event_fetch": 1},
        warm_compiles=0,
        # measured 1,185,644 B (while-body pool carries + verify-chunk
        # scatter copies) + ~5%
        relayout_bytes_max=1_245_000,
        pack_bytes_max=_MiB // 2,      # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (pool+table+hist
                                        # donated; rng rides tiny)
        # liveness peak measured 1,908,150 B (pool counted once + the
        # verify tick's [K+1]-wide windows) + ~5%
        peak_bytes_max=2_004_000,
        notes="r15 contract: K-token drafts verified in one paged tick "
              "— accepted-length>1 per weight stream at zero extra "
              "syncs/compiles/shapes"),
    # The QUALITY-DIGEST paged segment (r17, ISSUE 12): the
    # paged_serving_segment contract with per-emitted-token logit
    # digests (emitted logit + top-k ids/values) rolled into the event
    # log. Quality evidence must be FREE at the hazard level: still
    # exactly ONE event fetch per segment (digest columns ride the same
    # fetch — the shadow-diff comparison is host arithmetic on the
    # replayed log), zero warm compiles (the ("qseg", ...) family is
    # bucketed like the plain paged family), zero pack bytes, and the
    # relayout ledger is the paged while-body pool-carry class plus the
    # digest columns' tiny carries (measured ~0.3% above the unchunked
    # paged segment — the digest arrays are [steps, slots, k] fp32,
    # invisible next to the pool).
    "quality_serving_segment": Budget(
        flagged_syncs=0,
        allowed_syncs_per_replay={"serving.segment_event_fetch": 1},
        warm_compiles=0,
        # measured 1,044,420 B (while-body pool carries + admit page-
        # scatter copies + digest-column carries) + ~5%
        relayout_bytes_max=1_097_000,
        pack_bytes_max=_MiB // 2,      # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (pool+table donated)
        # liveness peak measured 1,898,878 B (pool counted once + the
        # [steps, slots, k] digest carries) + ~5%
        peak_bytes_max=1_994_000,
        notes="r17 contract: in-program logit digests ride the single "
              "event fetch — quality evidence at zero extra syncs/"
              "compiles/shapes"),
    # The QUANTIZED paged segment (r21, ISSUE 16): the
    # paged_serving_segment contract with int8 weight streaming
    # (per-output-channel scale companions in the param tree, dequant
    # in-kernel / adjacent-to-dot) and an int8 KV pool carrying
    # per-page scale planes. Quantization must be FREE at the hazard
    # level: still exactly ONE event fetch per segment, zero warm
    # compiles (the ("qpseg", ..., dtype) family is a declared dtype
    # axis on the bucketed paged ladder), zero pack bytes, and the
    # relayout ledger is BELOW the bf16 paged segment's — the
    # while-body pool carries are int8 quarter-width; what remains is
    # mostly the dequantized-weight transposes the CPU lowering
    # materialises next to the dots.
    "quant_serving_segment": Budget(
        flagged_syncs=0,
        allowed_syncs_per_replay={"serving.segment_event_fetch": 1},
        warm_compiles=0,
        # measured 631,908 B (int8 pool carries + dense-fallback dequant
        # transposes) + ~5%
        relayout_bytes_max=663_000,
        pack_bytes_max=_MiB // 2,      # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (pool+table donated)
        # liveness peak measured 800,573 B — int8 weights + quarter-
        # width pool put the whole envelope at 42% of bf16's (a third
        # on the older XLA:CPU, which held less dequantized) + ~5%
        peak_bytes_max=841_000,
        notes="r21 contract: narrow weight/KV streams at zero extra "
              "syncs/compiles/shapes — the quantized roofline win is "
              "pure bytes, not a hazard trade"),
    # The LONG-CONTEXT sequence-parallel segment (r23, ISSUE 18): the
    # paged_serving_segment contract for prompts PAST the regular
    # bucket ladder — prefill runs as [sp, C] slab steps whose rows
    # scatter page-indirectly into the shared pool, so decode picks up
    # on the ordinary page-indirect path with ZERO relayout at the
    # prefill→decode boundary. Long context must be FREE at the hazard
    # level: still exactly one event fetch per segment, zero warm
    # compiles (the ("spseg", n_pad, s_max, C, sp, steps) family closes
    # over the declared long-bucket ladder — sp_rungs is statically
    # enumerated and AOT-warmed), zero pack bytes, and the relayout
    # ledger is the while-body pool-carry class plus the slab steps'
    # [sp, C]-window scatter copies (measured between the chunked and
    # plain paged segments: slabs carry sp*C-token windows where cseg
    # carries C and pseg carries s_max).
    "longctx_serving_segment": Budget(
        flagged_syncs=0,
        allowed_syncs_per_replay={"serving.segment_event_fetch": 1},
        warm_compiles=0,
        # measured 1,106,668 B (while-body pool carries + slab-window
        # scatter copies) + ~5%
        relayout_bytes_max=1_162_000,
        pack_bytes_max=_MiB // 2,      # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (pool+table donated)
        # liveness peak measured 1,894,249 B (pool counted once + the
        # [sp, C] slab windows) + ~5%
        peak_bytes_max=1_989_000,
        notes="r23 contract: sp-slab prefill scattering into the paged "
              "pool — long context at zero extra syncs/compiles and "
              "zero boundary relayout"),
    # The TENSOR-PARALLEL segment (r12): the paged_serving_segment
    # contract, GSPMD-sharded — same one fetch per segment and zero warm
    # compiles, PLUS every collective must attribute to the 'mp' axis
    # (enforced via require_collectives_clean + the handle's
    # allowed_axes). Pinned at mp=2, which is what the gate's 8 virtual
    # devices and tier-1 build; on a single device the program is
    # paged_serving_segment's and that row's ceilings are the ones that
    # apply.
    "tp_serving_segment": Budget(
        flagged_syncs=0,
        allowed_syncs_per_replay={"serving.segment_event_fetch": 1},
        warm_compiles=0,
        # measured 246,988 B at mp=2 (per-shard while-body pool carries
        # + the gathered window's attention transposes) + ~5%
        relayout_bytes_max=260_000,
        pack_bytes_max=_MiB // 2,      # measured 0
        undonated_bytes_max=_MiB // 2,  # measured 0 (sharded pool donates)
        # liveness peak measured 637,754 B at mp=2 (the per-device text
        # halves the sharded weights, the pool and the carries) + ~5%
        peak_bytes_max=670_000,
        notes="r12 contract: mp-sharded segment — one fetch/segment, "
              "all collectives ride the declared 'mp' axis"),
    # The donated multi-tensor update: the r8 ledger program. The pack
    # bytes ARE the stack/flat packing traffic the Pallas kernel
    # eliminates on chip; the CPU lowering keeps the XLA packing, so
    # the ceiling pins THAT path's bytes for this population.
    "fused_optimizer_update": Budget(
        flagged_syncs=0,
        warm_compiles=0,
        # relayout measured 0 on this CPU lowering; headroom = one stray
        # copy
        relayout_bytes_max=256 * 1024,
        # measured 1,009,920 B on jaxlib 0.9.0 (PR 21): its XLA:CPU
        # materialises the three flat-pack concats (params, grads,
        # velocity: f32[84160] each) that the older one fused into kLoop
        # bodies as index math and r8 pinned at 0. This is the jnp
        # fallback's ledger; on a TPU the group takes the Pallas kernel
        # over the same flat buffers. + ~5%
        pack_bytes_max=1_060_000,
        # measured 262,144 B: exactly the two (128,256) f32 gradient
        # inputs — grads are inputs, never donated; params+velocity alias
        undonated_bytes_max=300_000,
        # liveness peak measured 2,019,844 B: params+velocity (donated,
        # once) + the two undonated gradient inputs + ~5%
        peak_bytes_max=2_120_000,
        notes="r8 ledger program: 255.5->153.3 MB/step class, miniature"),
}


def budget_for(program: str) -> Optional[Budget]:
    return BUDGETS.get(program)


def check(report, budget: Optional[Budget] = None) -> List[str]:
    """Violations of ``budget`` (default: the program's registry entry)
    in ``report``. Empty list = within budget."""
    if budget is None:
        budget = budget_for(report.program)
    if budget is None:
        return [f"no budget registered for program {report.program!r}"]
    v: List[str] = []
    m = report.metrics

    flagged = m.get("host_syncs_flagged")
    if flagged is not None and flagged > budget.flagged_syncs:
        v.append(f"host_syncs_flagged {flagged} > {budget.flagged_syncs}")
    allowed = m.get("host_syncs_allowed") or {}
    replays = max(1, m.get("replays", 1))
    for label, count in allowed.items():
        cap = budget.allowed_syncs_per_replay.get(label)
        if cap is None:
            v.append(f"allowed sync label {label!r} not in budget "
                     f"({count}x)")
        elif count > cap * replays:
            v.append(f"allowed sync {label!r}: {count} > "
                     f"{cap}/replay x {replays}")

    compiles = m.get("warm_compiles")
    if compiles is not None and compiles > budget.warm_compiles:
        v.append(f"warm_compiles {compiles} > {budget.warm_compiles}")

    import jax

    if jax.default_backend() == budget.bytes_platform:
        for key, cap in (("relayout_bytes", budget.relayout_bytes_max),
                         ("pack_bytes", budget.pack_bytes_max),
                         ("undonated_bytes", budget.undonated_bytes_max),
                         ("peak_bytes", budget.peak_bytes_max)):
            val = m.get(key)
            if cap is not None and val is not None and val > cap:
                v.append(f"{key} {val / _MiB:.2f} MiB > "
                         f"{cap / _MiB:.2f} MiB")

    if budget.require_collectives_clean:
        bad = [f for f in report.findings
               if f.pass_name == "collective" and f.severity == "hazard"]
        if bad:
            v.append(f"{len(bad)} collective hazards: {bad[0].message}")

    # r20 (ISSUE 15): an unenumerated compile is unconditionally a
    # violation — a program key outside the declared envelope IS the
    # 2.5 s mid-serve-compile class, whatever the other budgets say
    cov = [f for f in report.findings
           if f.pass_name == "coverage" and f.severity == "hazard"]
    if cov:
        v.append(f"{len(cov)} coverage hazards: {cov[0].message}")
    return v
