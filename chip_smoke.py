"""The quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of ``LlamaConfig.bert_base_equiv`` (H=768, 12
heads x 64, V=32000, 12 layers, bf16 compute) with weights from a fixed
seed, and checks what comes out against the plain XLA formulation of the
same model (``use_pallas_kernels=False``):

  device  jax reports a TPU, and the framework places tensors on it
  train   the ``bench.py`` path: ``make_sharded_train_step`` on a one-chip
          mesh, batch 44 x 512, a few steps on one fixed batch
  serve   ``generate()`` at batch 8, then a paged ``ServingEngine`` (8
          slots, default flags) under ``OnlineScheduler.serve`` answering
          seeded requests of mixed lengths

``--chips 4`` runs, and only runs, the sharded (ZeRO-3 x tensor-parallel)
train step over four chips and the one-chip step it is compared with.

Each phase prints one JSON line. Any failed check ends the run with a
non-zero code; the last line of a run that passed is
``{"ok": true, "device": {...}}`` with the device as jax reports it.
Without a TPU the run stops in the first phase, before a model is built.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time

# the repo's tolerance for two bf16 trajectories of one train step
# (tests/test_train_step_tpu.py): formulations differ in reduction order
LOSS_RTOL = LOSS_ATOL = 2e-2
# A greedy token may differ from the reference's only at a bf16 tie. Two
# bf16 runs of the model each carry an error sigma on every logit (measured
# where it matters: the rms distance, over the vocabulary, of the bf16
# reference's logits from a float32 computation of the same position). They
# can pick different tokens only when the float32 logits of the two lie
# within the error of a logit PAIR, sqrt(2) sigma, times this many
TIE_SIGMAS = 4.0


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, device, **fields) -> None:
    print(json.dumps({"phase": phase, "device": device, **fields}),
          flush=True)


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@contextlib.contextmanager
def pallas_off():
    """Trace what runs inside with the Pallas kernels off (the flag is
    read while a program is traced)."""
    import paddle_tpu as paddle

    paddle.set_flags({"use_pallas_kernels": False})
    try:
        yield
    finally:
        paddle.set_flags({"use_pallas_kernels": True})


def mem(dev, key: str) -> int:
    return dev.memory_stats()[key]


# programs read back from the persistent compile cache, and programs that
# had to be compiled and were written to it
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                "/jax/compilation_cache/cache_misses": 0}


def count_cache_event(event: str, **_) -> None:
    if event in CACHE_EVENTS:
        CACHE_EVENTS[event] += 1


def require_kernels(compiled_text: str, what: str) -> None:
    check("tpu_custom_call" in compiled_text,
          f"{what}: the compiled program holds no tpu_custom_call — the "
          f"Pallas kernels were not selected")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but jax reports platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s)); nothing "
                 f"was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: asked for {chips} chips, jax reports "
                 f"{len(devs)}")
    import numpy as np

    import paddle_tpu as paddle

    check(paddle.get_device() == "tpu:0",
          f"paddle_tpu.get_device() is {paddle.get_device()!r}, not tpu:0")
    t = paddle.to_tensor(np.arange(4, dtype=np.float32))
    check(isinstance(t.place, paddle.TPUPlace),
          f"to_tensor landed on {t.place!r}, not a TPUPlace")
    cache_dir = paddle.jit.enable_persistent_cache()
    jax.monitoring.register_event_listener(count_cache_event)
    emit("device", device_info(devs), get_device=paddle.get_device(),
         tensor_place=str(t.place), compile_cache=cache_dir)
    return devs


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def fixed_batch(cfg, batch: int, seq: int, seed: int):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                       jnp.int32)


@dataclasses.dataclass
class TrainRun:
    losses: list
    params: dict          # after the last step, placed as the step left them
    program_text: str     # the compiled step
    compile_s: float
    step_ms: list


def train_run(cfg, mesh, tokens, steps: int, lr: float, seed: int) -> TrainRun:
    """``steps`` steps of the sharded train step on one fixed batch, from
    the seed's parameters."""
    import jax

    from paddle_tpu.models import llama

    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = llama.init_opt_state(params)
    params, opt_state = llama.shard_state(cfg, mesh, params, opt_state)
    step = llama.make_sharded_train_step(cfg, mesh, lr=lr)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, tokens, tokens).compile()
    compile_s = time.perf_counter() - t0
    losses, step_ms = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tokens, tokens)
        loss.block_until_ready()
        step_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
    return TrainRun(losses, params, compiled.as_text(), compile_s, step_ms)


def check_losses(losses, ref_losses, what: str) -> None:
    import numpy as np

    check(all(np.isfinite(v) for v in losses), f"{what}: losses {losses}")
    check(losses[-1] < losses[0], f"{what}: loss does not fall: {losses}")
    check(abs(losses[0] - ref_losses[0]) <= LOSS_ATOL,
          f"{what}: first-step loss {losses[0]} vs reference "
          f"{ref_losses[0]}")
    check(np.allclose(losses, ref_losses, rtol=LOSS_RTOL, atol=LOSS_ATOL),
          f"{what}: losses {losses} leave the reference's {ref_losses}")


def phase_train(cfg, devs, batch: int, seq: int, steps: int = 4,
                lr: float = 1e-4, seed: int = 0):
    from paddle_tpu.parallel import create_hybrid_mesh, set_mesh

    tokens = fixed_batch(cfg, batch, seq, seed)
    mesh = create_hybrid_mesh(devices=devs[:1])
    try:
        # the reference is the plain XLA formulation of the same step:
        # kernels off, autodiff CE tail. It rematerialises, because
        # without flash attention the saved [S, S] scores of 12 layers
        # would not fit beside the step under test (14 GB at b44)
        ref_cfg = dataclasses.replace(cfg, remat=True, ce_tail_custom=False)
        with pallas_off():
            ref = train_run(ref_cfg, mesh, tokens, steps, lr, seed)
        ref.params = None  # frees the device for the step under test
        run = train_run(cfg, mesh, tokens, steps, lr, seed)
    finally:
        set_mesh(None)
    check("tpu_custom_call" not in ref.program_text,
          "train: the reference step holds a Pallas kernel")
    require_kernels(run.program_text, "train")
    check_losses(run.losses, ref.losses, "train")
    emit("train", device_info(devs[:1]), batch=batch, seq=seq,
         losses=run.losses, reference_losses=ref.losses,
         custom_calls=run.program_text.count("tpu_custom_call"),
         compile_s=round(run.compile_s, 2),
         reference_compile_s=round(ref.compile_s, 2),
         step_ms=[round(v, 2) for v in run.step_ms],
         peak_bytes_in_use=mem(devs[0], "peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def logits_program(cfg, float32: bool):
    """Jitted full-sequence logits [S, V] of ``cfg``'s model in the plain
    XLA formulation; ``float32`` computes everything, matmul passes
    included, in float32 (the weights are float32 masters already)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import llama

    if float32:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)

    def logits(params, tokens):
        with jax.default_matmul_precision("highest" if float32 else "default"):
            return llama.forward(params, tokens, cfg)[0].astype(jnp.float32)

    return jax.jit(logits)


def compare_tokens(ref, got, context, params, ref_cfg, what: str) -> str:
    """'exact' when the streams agree; 'tie' when they part at a position
    where float32 cannot tell the two tokens apart beyond the bf16
    reference's own error (what follows a tie is another sequence, and is
    not compared). Anything else fails the run."""
    import jax.numpy as jnp
    import numpy as np

    check(len(got) == len(ref), f"{what}: {len(got)} tokens, wanted "
                                f"{len(ref)}")
    diff = [i for i, (a, b) in enumerate(zip(ref, got)) if a != b]
    if not diff:
        return "exact"
    p = diff[0]
    seq = np.concatenate([np.asarray(context, np.int32),
                          np.asarray(ref[:p], np.int32)])
    padded = np.zeros((1, ref_cfg.max_seq_len), np.int32)
    padded[0, :len(seq)] = seq  # causal: what follows changes nothing
    padded = jnp.asarray(padded)
    with pallas_off():
        bf16 = np.asarray(logits_program(ref_cfg, False)(params, padded))
        f32 = np.asarray(logits_program(ref_cfg, True)(params, padded))
    bf16, f32 = bf16[len(seq) - 1], f32[len(seq) - 1]
    sigma = float(np.sqrt(np.mean((bf16 - f32) ** 2)))
    gap = float(f32[ref[p]] - f32[got[p]])
    top2 = np.sort(f32)[-2:]
    sigmas = abs(gap) / (np.sqrt(2.0) * sigma)
    print(f"chip_smoke: {what} parts from the reference at generated "
          f"position {p}: reference token {ref[p]}, got {got[p]}; in "
          f"float32 the reference's token leads by {gap:.5f} (top-2 gap "
          f"{float(top2[1] - top2[0]):.5f}); a bf16 logit is off by sigma "
          f"{sigma:.5f} here, so the two are {sigmas:.2f} pair-sigmas "
          f"apart", file=sys.stderr, flush=True)
    check(sigmas <= TIE_SIGMAS,
          f"{what}: token {got[p]} at position {p} is {sigmas:.2f} "
          f"pair-sigmas from the reference's {ref[p]} — not a bf16 tie")
    return "tie"


def phase_serve(cfg, devs, n_requests: int = 10, slots: int = 8,
                prompt_lens=(24, 60, 100, 200), gen_lens=(16, 24, 32),
                seed: int = 0):
    import jax
    import numpy as np

    from paddle_tpu.inference.scheduler import (OnlineScheduler,
                                                poisson_arrivals)
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import llama
    from paddle_tpu.ops.pallas import decode_attention, paged_attention
    from paddle_tpu.parallel import set_mesh

    set_mesh(None)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    # the reference model: same weights, no fused tick and (under
    # pallas_off) no kernel. A config of its own also keeps its compiled
    # programs apart from those of the model under test.
    ref_cfg = dataclasses.replace(cfg, fused_tick_epilogue=False)
    max_gen = max(gen_lens)
    max_len = cfg.max_seq_len

    def reference(prompts, n):
        with pallas_off():
            return np.asarray(llama.generate(
                params, np.asarray(prompts, np.int32), ref_cfg,
                max_new_tokens=n, max_len=max_len))

    # -- generate(): prefill + the one-program decode scan, default flags
    rng = np.random.RandomState(seed)
    batch = rng.randint(0, cfg.vocab_size, (slots, 64)).astype(np.int32)
    ref = reference(batch, max_gen)
    decode_attention.reset_selection_count()
    check(llama._tick_fused_active(cfg),
          "serve: the fused decode tick is not active for this config")
    t0 = time.perf_counter()
    got = np.asarray(llama.generate(params, batch, cfg,
                                    max_new_tokens=max_gen, max_len=max_len))
    generate_s = time.perf_counter() - t0
    check(decode_attention.selection_count() > 0,
          "serve: generate() did not select the ragged decode kernel")
    gen_verdicts = [compare_tokens(list(ref[i]), list(got[i]), batch[i],
                                   params, ref_cfg, f"generate row {i}")
                    for i in range(slots)]

    # -- the paged engine under the online scheduler
    arrivals = poisson_arrivals(seed + 1, n_requests, 50.0, cfg.vocab_size,
                                prompt_lens=prompt_lens, gen_lens=gen_lens)
    refs = [list(reference(a.prompt[None], max_gen)[0, :a.max_new_tokens])
            for a in arrivals]
    paged_attention.reset_selection_count()
    eng = ServingEngine(cfg, params, slots=slots, paged=True)
    check(eng.paged_kernel_active(),
          "serve: the engine would not route attention to the paged kernel")
    sched = OnlineScheduler(eng)
    t0 = time.perf_counter()
    report = sched.serve(arrivals)
    serve_s = time.perf_counter() - t0
    results = sched.results()
    check(report.n_requests == n_requests and len(results) == n_requests,
          f"serve: {len(results)} of {n_requests} requests finished")
    check(paged_attention.selection_count() > 0,
          "serve: no segment selected the paged attention kernel")
    buckets = {min(b for b in eng.buckets if b >= len(a.prompt))
               for a in arrivals}
    check(len(buckets) >= 2, f"serve: prompts fell into buckets {buckets}")
    # request ids follow arrival order (the scheduler ingests by time)
    verdicts = [compare_tokens(refs[i], [int(t) for t in results[i]],
                               a.prompt, params, ref_cfg, f"request {i}")
                for i, a in enumerate(arrivals)]
    emit("serve", device_info(devs[:1]),
         generate={"batch": slots, "tokens": max_gen,
                   "seconds_with_compile": round(generate_s, 2),
                   "exact": gen_verdicts.count("exact"),
                   "bf16_ties": gen_verdicts.count("tie"),
                   "ragged_decode_selections":
                       decode_attention.selection_count()},
         engine={"requests": n_requests, "tokens": report.total_tokens,
                 "segments": report.segments, "ticks": report.ticks,
                 "prompt_buckets": sorted(buckets),
                 "seconds_with_compile": round(serve_s, 2),
                 "cold_start_s": report.cold_start_s,
                 "exact": verdicts.count("exact"),
                 "bf16_ties": verdicts.count("tie"),
                 "paged_attention_selections":
                     paged_attention.selection_count()},
         peak_bytes_in_use=mem(devs[0], "peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# four chips: the Fleet ZeRO-3 x tensor-parallel step, and nothing else
# ---------------------------------------------------------------------------

def phase_four_chips(cfg, devs, batch: int, seq: int, steps: int = 3,
                     lr: float = 1e-4, seed: int = 0):
    import numpy as np

    from paddle_tpu.models import llama
    from paddle_tpu.parallel import create_hybrid_mesh, set_mesh

    cfg = dataclasses.replace(cfg, sharding_stage=3)
    tokens = fixed_batch(cfg, batch, seq, seed)
    try:
        one = create_hybrid_mesh(devices=devs[:1])
        ref = train_run(cfg, one, tokens, steps, lr, seed)
        ref.params = None  # frees chip 0 for its share of the sharded step
        mesh = create_hybrid_mesh(sharding=2, mp=2, devices=devs[:4])
        run = train_run(cfg, mesh, tokens, steps, lr, seed)
    finally:
        set_mesh(None)
    check_losses(run.losses, ref.losses, "four chips")
    specs = llama.param_specs(cfg)
    for name, p in run.params.items():
        held = {s.device for s in p.addressable_shards}
        check(len(held) == 4, f"four chips: {name} lives on {len(held)} "
                              f"device(s): {sorted(d.id for d in held)}")
        if any(ax is not None for ax in specs[name]):
            shard = p.addressable_shards[0].data.shape
            check(np.prod(shard) < np.prod(p.shape),
                  f"four chips: {name} {specs[name]} is not split: every "
                  f"shard is {shard}")
    in_use = {d.id: mem(d, "bytes_in_use") for d in devs[:4]}
    check(all(v > 0 for v in in_use.values()),
          f"four chips: bytes_in_use {in_use}")
    text = run.program_text
    emit("four_chips", device_info(devs[:4]), mesh={"sharding": 2, "mp": 2},
         sharding_stage=3, batch=batch, seq=seq, losses=run.losses,
         one_chip_losses=ref.losses, compile_s=round(run.compile_s, 2),
         one_chip_compile_s=round(ref.compile_s, 2),
         step_ms=[round(v, 2) for v in run.step_ms],
         collectives={op: text.count(f" {op}(") + text.count(f" {op}-start(")
                      for op in ("all-reduce", "all-gather",
                                 "reduce-scatter", "all-to-all")},
         bytes_in_use=in_use)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step over four chips "
                         "and the one-chip step it is compared with")
    args = ap.parse_args(argv)

    devs = phase_device(args.chips)
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig.bert_base_equiv(max_seq_len=512)
    try:
        if args.chips == 4:
            phase_four_chips(cfg, devs, batch=44, seq=512)
        else:
            phase_train(cfg, devs, batch=44, seq=512)
            phase_serve(cfg, devs)
    except SmokeFailure as e:
        sys.exit(f"chip_smoke FAILED: {e}")
    emit("compile_cache", device_info(devs),
         read_back=CACHE_EVENTS["/jax/compilation_cache/cache_hits"],
         compiled_and_written=CACHE_EVENTS[
             "/jax/compilation_cache/cache_misses"])
    print(json.dumps({"ok": True, "device": device_info(devs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
