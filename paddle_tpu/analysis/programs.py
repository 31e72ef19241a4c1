"""The registered canonical programs the gate audits.

Each is a miniaturised-but-structurally-faithful instance of a hot path
whose hazard ledger earlier rounds paid for by hand:

* ``amp_o2_train_step``      — conv+BN+linear AMP-O2 ``fused_train_step``
  (the r8 GradScaler/donation territory: params+opt state must alias,
  zero host syncs per step).
* ``fused_optimizer_update`` — ``Optimizer.step``'s donated jit update
  over a mixed-shape population (the r8 relayout-ledger territory: the
  stack/concat pack bytes are THE metric).
* ``paged_serving_segment``  — the re-entrant continuous-batching
  segment over the paged pool + its host replay (exactly ONE allowed
  device_get per segment, no stray shape compiles, zero pack bytes:
  prefix reuse is refcount data, not row copies).
* ``tp_serving_segment``     — the r12 mp-sharded segment (collectives
  must attribute to the 'mp' axis; the one-fetch contract survives
  GSPMD).
* ``chunked_serving_segment`` — the r13 chunked-prefill paged segment
  (prefill split into ladder-width chunks interleaved with decode
  ticks; still exactly one event fetch, chunk widths declared so the
  program-key family stays finite).
* ``spec_serving_segment``   — the r15 speculative segment (in-program
  n-gram draft + K+1-position verified ticks through the paged
  q_len>1 path; acceptance rides the single event fetch).
* ``quality_serving_segment`` — the r17 quality-digest paged segment
  (per-emitted-token logit + top-k ids/values computed in-program and
  rolled into the event log; the shadow-diff evidence stream must ride
  the SAME single fetch at zero extra syncs/compiles).
* ``quant_serving_segment``  — the r21 int8-quantized paged segment
  (narrow weight/KV streams with in-kernel or adjacent-to-dot dequant,
  per-page KV scale planes riding the pool; same one-dispatch/one-fetch
  loop on the qpseg dtype axis — zero extra syncs/compiles is the
  contract that makes the quantized rollout a pure bytes win).
* ``longctx_serving_segment`` — the r23 sequence-parallel long-context
  segment (a past-the-buckets prompt prefills as [sp, C] slabs whose
  rows scatter straight into the paged pool; decode proceeds on the
  ordinary page-indirect path with zero relayout at the boundary;
  still exactly one event fetch, spseg keys statically enumerated).

Builders are deterministic (fixed seeds, fixed shapes) so the measured
metrics are stable run to run and ``budgets.py`` can pin them as exact
ceilings.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["ProgramHandle", "register", "build", "names", "CANONICAL"]


@dataclass
class ProgramHandle:
    name: str
    hlo: Callable[[], str]          # optimized HLO text (compiled, cached)
    replay: Callable[[], Any]       # ONE warm iteration of the hot loop
    mesh: Any = None
    donation_threshold: int = 1 << 20
    expected_undonated: Tuple[str, ...] = ()
    allowed_axes: Optional[Tuple[str, ...]] = None
    notes: str = ""
    keepalive: tuple = ()           # pins models/engines for the handle's life
    # r20 (ISSUE 15): the serving programs carry their engine + the
    # workload envelope their replay stays inside, so the gate's --aot
    # mode can lint/enumerate/warm the full program space before the
    # audit and diff enumerated-vs-used after it (budgets must come out
    # bit-identical --aot on|off — warmup only moves WHEN compiles
    # happen, never what the warm replay does)
    aot_engine: Any = None
    aot_envelope: Any = None


CANONICAL: Dict[str, Callable[[], ProgramHandle]] = {}


def register(name: str):
    def deco(fn):
        CANONICAL[name] = fn
        return fn
    return deco


def names() -> List[str]:
    return sorted(CANONICAL)


def build(name: str) -> ProgramHandle:
    if name not in CANONICAL:
        raise KeyError(f"unknown canonical program {name!r}; "
                       f"registered: {names()}")
    return CANONICAL[name]()


def _memo(fn):
    box: list = []

    def wrapped():
        if not box:
            box.append(fn())
        return box[0]
    return wrapped


def _gate_envelope(seg_steps, max_prompt: int = 12,
                   max_new_tokens: int = 4):
    """The workload envelope the gate's canonical serving replays stay
    inside (12-token prompts, short generations, one seg_steps value —
    exactly what each ``replay()`` enqueues). ``--aot on`` enumerates +
    compiles this space up front and diffs it against what the audit
    replays actually use."""
    from paddle_tpu.inference.program_space import WorkloadEnvelope

    return WorkloadEnvelope(max_prompt=max_prompt,
                            max_new_tokens=max_new_tokens,
                            seg_steps=tuple(seg_steps))


# ---------------------------------------------------------------------------
# 1. AMP-O2 train step
# ---------------------------------------------------------------------------


@register("amp_o2_train_step")
def _build_amp_o2_train_step() -> ProgramHandle:
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn

    model = nn.Sequential(
        nn.Conv2D(3, 16, 3, padding=1), nn.BatchNorm2D(16), nn.ReLU(),
        nn.MaxPool2D(2), nn.Flatten(),
        nn.Linear(16 * 16 * 16, 128), nn.ReLU(), nn.Linear(128, 10))
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                     level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()

    def loss_fn(x, y):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return ce(model(x), y)

    step = paddle.jit.fused_train_step(loss_fn, opt, model=model)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(8, 3, 32, 32).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (8,)))

    return ProgramHandle(
        name="amp_o2_train_step",
        hlo=_memo(lambda: step.compiled_text(x, y)),
        replay=lambda: step(x, y),
        # the batch, labels, RNG key, BN buffers and per-step scalars ride
        # undonated by design; params + velocity alias in place
        donation_threshold=1 << 18,
        expected_undonated=(),
        notes="conv+BN AMP-O2 fused train step, b8 32x32, Momentum",
        keepalive=(model, opt, step, x, y))


# ---------------------------------------------------------------------------
# 2. Serving programs
# ---------------------------------------------------------------------------


@register("paged_serving_segment")
def _build_paged_serving_segment() -> ProgramHandle:
    import numpy as np

    import jax.numpy as j

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=8,
                        prompt_buckets=(16,), page_size=16)
    rng = np.random.RandomState(0)

    def replay():
        # end-to-end PAGED segment: reserve pages host-side, one fused
        # dispatch, one allowed event fetch, page bookkeeping on host
        # mirrors — every request finishes inside the segment so pages
        # drain back to the free list each iteration
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (12,)), 4)
        return eng.run_segment(12)

    def hlo():
        n_pad = eng._pow2(eng.slots)
        s_max = eng.buckets[-1]
        seg = eng._paged_segment_prog(n_pad, s_max, 12)
        pgr = eng.pager
        return seg.lower(
            params, pgr.pool, pgr.page_table,
            j.zeros((eng.slots,), j.int32), j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots,), j.int32),
            j.zeros((n_pad, s_max), j.int32), j.ones((n_pad,), j.int32),
            j.zeros((n_pad,), j.int32), j.zeros((n_pad,), j.int32),
            j.zeros((n_pad, pgr.max_pages), j.int32),
            j.int32(2)).compile().as_text()

    return ProgramHandle(
        name="paged_serving_segment",
        hlo=_memo(hlo),
        replay=replay,
        donation_threshold=1 << 16,
        expected_undonated=(),
        notes="paged re-entrant segment (page-table pool, COW-ready) + "
              "host event replay with page bookkeeping, llama-tiny",
        aot_engine=eng,
        aot_envelope=_gate_envelope(seg_steps=(12,)),
        keepalive=(eng,))


@register("chunked_serving_segment")
def _build_chunked_serving_segment() -> ProgramHandle:
    """The r13 chunked-prefill segment (ISSUE 8a): the paged segment
    with admits split into declared-ladder chunks interleaved with
    decode ticks. The contract the budget pins: chunking must not cost
    a single extra host sync (still exactly ONE event fetch per
    segment), zero warm compiles (chunk widths come from the declared
    ladder, so the ("cseg", ...) key family is finite and the warm
    replay covers it), and no new relayout/pack traffic beyond the
    while-body carries the paged segment already pays."""
    import numpy as np

    import jax.numpy as j

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=8,
                        prompt_buckets=(16,), page_size=16,
                        chunked_prefill=True, prefill_chunks=(8,))
    rng = np.random.RandomState(0)

    def replay():
        # end-to-end CHUNKED segment: two 12-token prompts each prefill
        # as 2 interleaved 8-token chunks, decode to completion inside
        # the segment (slots + pages drain), one allowed event fetch
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (12,)), 4)
        return eng.run_segment(16)

    def hlo():
        n_pad = eng._pow2(eng.slots)
        C = eng._prefill_chunk_for(eng.buckets[-1])
        s_max_c = -(-eng.buckets[-1] // C) * C
        seg = eng._chunked_segment_prog(n_pad, s_max_c, C, 16)
        pgr = eng.pager
        return seg.lower(
            params, pgr.pool, pgr.page_table,
            j.zeros((eng.slots,), j.int32), j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots,), j.int32),
            j.zeros((n_pad, s_max_c), j.int32), j.ones((n_pad,), j.int32),
            j.zeros((n_pad,), j.int32), j.zeros((n_pad,), j.int32),
            j.zeros((n_pad, pgr.max_pages), j.int32),
            j.int32(2)).compile().as_text()

    return ProgramHandle(
        name="chunked_serving_segment",
        hlo=_memo(hlo),
        replay=replay,
        donation_threshold=1 << 16,
        expected_undonated=(),
        notes="chunked-prefill paged segment (8-token chunks interleaved "
              "with decode ticks) + host event replay, llama-tiny",
        aot_engine=eng,
        aot_envelope=_gate_envelope(seg_steps=(16,)),
        keepalive=(eng,))


@register("longctx_serving_segment")
def _build_longctx_serving_segment() -> ProgramHandle:
    """The r23 sequence-parallel long-context segment (ISSUE 18): a
    prompt PAST the regular bucket ladder prefills as sp-row slabs —
    each slab step covers ``sp * C`` prompt tokens reshaped to [sp, C]
    rows at absolute offsets ``base + r*C``, every row scattering its
    K/V straight into the shared paged pool — interleaved with ordinary
    decode ticks for co-resident slots. The contract the budget pins:
    long-context must be free at the hazard level — still exactly ONE
    event fetch per segment, zero warm compiles (the ("spseg", n_pad,
    s_max, C, sp, steps) family is closed over the declared long-bucket
    ladder, so sp_rungs is statically enumerable), no pack traffic, and
    the relayout ledger stays in the while-body pool-carry class: the
    prefill→decode boundary costs ZERO relayout because decode reads
    the very pages the slab rows scattered."""
    import numpy as np

    import jax.numpy as j

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=8,
                        prompt_buckets=(16,), page_size=16,
                        prefill_chunks=(8,), seq_parallel=2,
                        long_buckets=(32,))
    rng = np.random.RandomState(0)

    def replay():
        # end-to-end LONG-CONTEXT segment: one 24-token prompt (past
        # the 16 bucket — slab-prefills as 2 steps of [2, 8] rows) plus
        # one co-resident 12-token prompt, decode to completion inside
        # the segment (slots + pages drain), one allowed event fetch
        eng.add_request(rng.randint(0, cfg.vocab_size, (24,)), 4)
        eng.add_request(rng.randint(0, cfg.vocab_size, (12,)), 4)
        return eng.run_segment(16)

    def hlo():
        n_pad = eng._pow2(eng.slots)
        C = eng.prefill_chunks[-1]
        Cs = eng.seq_parallel * C
        s_max = -(-eng.long_buckets[-1] // Cs) * Cs
        seg = eng._sp_segment_prog(n_pad, s_max, C, 16)
        pgr = eng.pager
        return seg.lower(
            params, pgr.pool, pgr.page_table,
            j.zeros((eng.slots,), j.int32), j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots,), j.int32),
            j.zeros((n_pad, s_max), j.int32), j.ones((n_pad,), j.int32),
            j.zeros((n_pad,), j.int32), j.zeros((n_pad,), j.int32),
            j.zeros((n_pad, pgr.max_pages), j.int32),
            j.int32(2)).compile().as_text()

    return ProgramHandle(
        name="longctx_serving_segment",
        hlo=_memo(hlo),
        replay=replay,
        donation_threshold=1 << 16,
        expected_undonated=(),
        notes="sequence-parallel long-context segment (sp=2 slab prefill "
              "scattering into the paged pool, page-indirect decode) + "
              "host event replay, llama-tiny",
        aot_engine=eng,
        aot_envelope=_gate_envelope(seg_steps=(16,), max_prompt=24),
        keepalive=(eng,))


@register("spec_serving_segment")
def _build_spec_serving_segment() -> ProgramHandle:
    """The r15 speculative segment (ISSUE 10): the paged segment whose
    decode steps draft K tokens from the slot's in-program n-gram table
    and verify all K+1 positions in one batched tick through the paged
    q_len>1 path. The contract the budget pins: speculation must be
    free at the hazard level — still exactly ONE event fetch per
    segment (the acceptance counts ride the same fetch; per-request
    accepted lengths are host replay arithmetic), zero flagged syncs,
    zero warm compiles (the ("sseg", n_pad, K, steps) key family pins
    the admit width to the largest bucket, so prefix hits and arrival
    jitter add no shapes), and no pack traffic beyond the while-body
    pool carries the paged segment already pays."""
    import numpy as np

    import jax.numpy as j

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=8,
                        prompt_buckets=(16,), page_size=16,
                        speculative=3)
    rng = np.random.RandomState(0)

    def replay():
        # end-to-end SPECULATIVE segment: two requests, drafts verified
        # in multi-token ticks, ONE fused dispatch, the single allowed
        # event fetch, host replay recovers acceptance — every request
        # finishes inside the segment so slots + pages drain
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (12,)), 6)
        return eng.run_segment(16)

    def hlo():
        n_pad = eng._pow2(eng.slots)
        K = eng.speculative
        seg = eng._spec_segment_prog(n_pad, 16)
        pgr = eng.pager
        return seg.lower(
            params, pgr.pool, pgr.page_table,
            j.zeros((eng.slots,), j.int32), j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots, eng.max_len + 1), j.int32),
            j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots, 2), j.uint32),
            j.zeros((n_pad, eng.buckets[-1]), j.int32),
            j.ones((n_pad,), j.int32),
            j.zeros((n_pad,), j.int32), j.zeros((n_pad,), j.int32),
            j.zeros((n_pad, pgr.max_pages), j.int32),
            j.zeros((n_pad,), j.int32),
            j.int32(2)).compile().as_text()

    return ProgramHandle(
        name="spec_serving_segment",
        hlo=_memo(hlo),
        replay=replay,
        donation_threshold=1 << 16,
        expected_undonated=(),
        notes="speculative paged segment (K=3 n-gram draft, multi-token "
              "verified ticks) + host acceptance replay, llama-tiny",
        aot_engine=eng,
        aot_envelope=_gate_envelope(seg_steps=(16,), max_new_tokens=6),
        keepalive=(eng,))


@register("quality_serving_segment")
def _build_quality_serving_segment() -> ProgramHandle:
    """The r17 quality-digest segment (ISSUE 12): the paged segment
    whose event log additionally carries per-step per-slot logit
    digests — the emitted token's logit plus the tick's top-k ids and
    values, computed in-program from logits the tick already produced.
    The contract the budget pins: quality evidence must be FREE at the
    hazard level — still exactly ONE event fetch per segment (the
    digest columns ride the same fetch; the shadow-diff comparison is
    host arithmetic on the replayed log), zero flagged syncs, zero warm
    compiles (the ("qseg", n_pad, s_max, steps) family is bucketed
    exactly like the plain paged family), and the relayout ledger is
    the paged while-body pool-carry class plus the digest columns'
    tiny carries."""
    import numpy as np

    import jax.numpy as j

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=8,
                        prompt_buckets=(16,), page_size=16,
                        quality_digest=True, digest_top_k=4)
    rng = np.random.RandomState(0)

    def replay():
        # end-to-end DIGEST segment: two requests decode to completion
        # inside the segment, the single allowed event fetch returns
        # tokens AND digests, the host replay distributes both
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (12,)), 4)
        return eng.run_segment(12)

    def hlo():
        n_pad = eng._pow2(eng.slots)
        s_max = eng.buckets[-1]
        seg = eng._paged_segment_prog(n_pad, s_max, 12)
        pgr = eng.pager
        return seg.lower(
            params, pgr.pool, pgr.page_table,
            j.zeros((eng.slots,), j.int32), j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots,), j.int32),
            j.zeros((n_pad, s_max), j.int32), j.ones((n_pad,), j.int32),
            j.zeros((n_pad,), j.int32), j.zeros((n_pad,), j.int32),
            j.zeros((n_pad, pgr.max_pages), j.int32),
            j.int32(2)).compile().as_text()

    return ProgramHandle(
        name="quality_serving_segment",
        hlo=_memo(hlo),
        replay=replay,
        donation_threshold=1 << 16,
        expected_undonated=(),
        notes="quality-digest paged segment (k=4 top-k logit digests "
              "in the event log) + host digest replay, llama-tiny",
        aot_engine=eng,
        aot_envelope=_gate_envelope(seg_steps=(12,)),
        keepalive=(eng,))


@register("quant_serving_segment")
def _build_quant_serving_segment() -> ProgramHandle:
    """The r21 quantized paged segment (ISSUE 16): the paged segment
    with int8 weight streaming (per-output-channel scales, dequant
    in-kernel on TPU / adjacent-to-dot on the dense fallback) and an
    int8 KV pool carrying per-page scale planes. The contract the
    budget pins: quantization must be FREE at the hazard level — the
    ("qpseg", n_pad, s_max, steps, dtype) family is bucketed exactly
    like the plain paged family, still exactly ONE event fetch per
    segment, zero flagged syncs, zero warm compiles — so the narrow
    HBM stream is a pure bytes win the roofline model (SCALING §3p)
    can bank without hazard caveats."""
    import numpy as np

    import jax.numpy as j

    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=8,
                        prompt_buckets=(16,), page_size=16,
                        quant="int8")
    rng = np.random.RandomState(0)

    def replay():
        # end-to-end QUANTIZED segment: two requests decode to
        # completion inside the segment — narrow weight/KV streams,
        # ONE fused dispatch, the single allowed event fetch
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (12,)), 4)
        return eng.run_segment(12)

    def hlo():
        n_pad = eng._pow2(eng.slots)
        s_max = eng.buckets[-1]
        seg = eng._paged_segment_prog(n_pad, s_max, 12)
        pgr = eng.pager
        return seg.lower(
            eng.params, pgr.pool, pgr.page_table,
            j.zeros((eng.slots,), j.int32), j.zeros((eng.slots,), j.int32),
            j.zeros((eng.slots,), j.int32),
            j.zeros((n_pad, s_max), j.int32), j.ones((n_pad,), j.int32),
            j.zeros((n_pad,), j.int32), j.zeros((n_pad,), j.int32),
            j.zeros((n_pad, pgr.max_pages), j.int32),
            j.int32(2)).compile().as_text()

    return ProgramHandle(
        name="quant_serving_segment",
        hlo=_memo(hlo),
        replay=replay,
        donation_threshold=1 << 16,
        expected_undonated=(),
        notes="int8-quantized paged segment (narrow weight/KV streams, "
              "per-page KV scales, in-kernel dequant) — qpseg dtype "
              "axis, llama-tiny",
        aot_engine=eng,
        aot_envelope=_gate_envelope(seg_steps=(12,)),
        keepalive=(eng,))


@register("tp_serving_segment")
def _build_tp_serving_segment() -> ProgramHandle:
    """The r12 tensor-parallel serving segment: the paged segment with
    weights GSPMD-sharded Megatron-style and the KV pool sharded on the
    head dim over an 'mp' mesh (``llama.paged_pool_spec``). The contract
    the budget pins: the ONE-dispatch/one-fetch shape survives sharding
    (same single allowed event fetch, zero warm compiles) and every
    collective in the program attributes to the 'mp' axis — an
    unattributed or off-axis collective is a GSPMD repartition hazard,
    exactly the class ``collective_check`` was promoted to catch. Builds
    mp=2 when two devices exist (tier-1's virtual-CPU platform, the
    MULTICHIP dryrun pattern), mp=1 on a single chip — the sync/compile
    budgets bind either way, the collective attribution bites at mp=2."""
    import numpy as np

    import jax
    import jax.numpy as j

    from paddle_tpu.inference.serving import ServingEngine, _mesh_scope
    from paddle_tpu.models import llama
    from paddle_tpu.parallel.mesh import create_hybrid_mesh

    devs = jax.devices()
    mp = 2 if len(devs) >= 2 else 1
    mesh = create_hybrid_mesh(mp=mp, devices=devs[:mp],
                              set_as_global=False)
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    eng = ServingEngine(cfg, params, slots=4, max_len=64, chunk=8,
                        prompt_buckets=(16,), page_size=16, mesh=mesh)
    rng = np.random.RandomState(0)

    def replay():
        # end-to-end mp-sharded segment: two requests, ONE fused
        # dispatch over the mesh, the single allowed event fetch, host
        # replay — every request finishes inside the segment so pages
        # drain back to the free list (the engine scopes the mesh itself)
        for _ in range(2):
            eng.add_request(rng.randint(0, cfg.vocab_size, (12,)), 4)
        return eng.run_segment(12)

    def hlo():
        n_pad = eng._pow2(eng.slots)
        s_max = eng.buckets[-1]
        pgr = eng.pager
        # the model's sharding constraints read the global mesh at trace
        # time: lower under the engine's own
        with _mesh_scope(mesh):
            seg = eng._paged_segment_prog(n_pad, s_max, 12)
            return seg.lower(
                eng.params, pgr.pool, pgr.page_table, eng._pos, eng._nxt,
                eng._rem, j.zeros((n_pad, s_max), j.int32),
                j.ones((n_pad,), j.int32), j.zeros((n_pad,), j.int32),
                j.zeros((n_pad,), j.int32),
                j.zeros((n_pad, pgr.max_pages), j.int32),
                j.int32(2)).compile().as_text()

    return ProgramHandle(
        name="tp_serving_segment",
        hlo=_memo(hlo),
        replay=replay,
        mesh=mesh,
        donation_threshold=1 << 16,
        expected_undonated=(),
        allowed_axes=("mp",),
        notes=f"mp={mp} GSPMD-sharded paged segment (column/row-"
              f"parallel weights, head-sharded KV pool), llama-tiny",
        aot_engine=eng,
        aot_envelope=_gate_envelope(seg_steps=(12,)),
        keepalive=(eng,))


# ---------------------------------------------------------------------------
# 3. Fused optimizer update
# ---------------------------------------------------------------------------


@register("fused_optimizer_update")
def _build_fused_optimizer_update() -> ProgramHandle:
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn

    # the r8 ledger population in miniature: a few big tiled tensors +
    # a crowd of small 1-D rows (the launch-bound class the flat pack
    # exists for)
    shapes = ([(128, 256)] * 2 + [(256,)] * 8 + [(64, 64)] * 4
              + [(32,)] * 6)
    rng = np.random.RandomState(0)
    params = [nn.Parameter(jnp.asarray(rng.randn(*s), jnp.float32))
              for s in shapes]
    opt = paddle.optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                    parameters=params)

    def grads(seed):
        r = np.random.RandomState(seed)
        return [jnp.asarray(r.randn(*s).astype(np.float32)) for s in shapes]

    gsets = [grads(s) for s in range(3)]
    it = [0]

    def replay():
        gs = gsets[it[0] % len(gsets)]
        it[0] += 1
        for p, g in zip(params, gs):
            p.grad = paddle.Tensor(g, stop_gradient=True)
        opt.step()

    def hlo():
        replay()  # materialise _jit_update + warm state
        pvals = [p._value for p in params]
        svals = [{k: opt._accumulators[id(p)][k]
                  for k in opt._state_names()} for p in params]
        evals = [opt._per_param_extras(p) for p in params]
        return opt._jit_update.lower(
            pvals, gsets[0], svals, evals, jnp.float32(opt.get_lr()),
            jnp.int32(opt._step_count + 1)).compile().as_text()

    return ProgramHandle(
        name="fused_optimizer_update",
        hlo=_memo(hlo),
        replay=replay,
        donation_threshold=1 << 16,
        expected_undonated=(),
        notes="Momentum multi-tensor update, 20 mixed-shape tensors "
              "(pack/relayout ledger program)",
        keepalive=(params, opt))
