"""The Pallas kernels of the train and serve paths, compiled for the chip.

Tier-1 runs the kernels in the Pallas interpreter, which accepts what
Mosaic refuses: a float iota, a block whose second-minor dim is 1, a scalar
``pow`` (the three refusals PR 21 found). The TPU's compiler is installed
here and compiles for a chip that is DESCRIBED, not attached, so each case
below lowers one kernel at ``LlamaConfig.bert_base_equiv`` widths (H=768,
12 heads x 64, V=32000) for one chip of a ``v5e:2x2`` and asserts a
``tpu_custom_call`` in the compiled text. Nothing runs: these say a kernel
compiles, never that it is right or fast.

Five cases compile a whole program: ``test_paged_segment_holds_pool_once``
lowers the paged segment loop (admit and decode steps around
``llama.forward_with_pages``) and reads the compiled text and
``memory_analysis()`` for copies of the KV pool, which tier-1 cannot see
otherwise: they cost two thirds of a serve step before PR 26 (PERF.md);
``test_llama_segment_reads_stacked_weights_where_they_lie`` reads the same
program at a serve cell's own size for copies of a stacked weight (13 % of
that step before PR 35);
``test_latent_segment_holds_pool_once`` does the same for the latent
family's plane at the benchmark's own size, and
``test_retention_segment_holds_pool_once`` for the power-retention family's
state pages, and ``test_hybrid_segment_holds_both_caches_once`` for the
window / full family's row pages and fixed parts.

The topology is described inside a fixture (loading the TPU's library at
import would break collection under several workers) and everything is
compiled in the test's own process.
"""

import re
import types

import jax
import jax.numpy as jnp
import pytest

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
H, NH, D, V = 768, 12, 64, 32000       # bert_base_equiv widths
SLOTS, MAX_LEN, PAGE = 8, 512, 16      # the serving engine's defaults


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # read when the TPU's library loads: keeps its logs out of /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shaped(topo):
    """``shaped(shape, dtype)``: an abstract array on one described chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without a chip: keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash(batch, seq):
    from paddle_tpu.ops.pallas import flash_attention as fa

    def build(S):
        def loss(q, k, v):
            return fa._flash_custom_vjp(q, k, v, True).astype(F32).sum()

        x = S((batch, seq, NH, D), BF16)
        return jax.grad(loss, argnums=(0, 1, 2)), (x, x, x)
    return build


def _head_dx(S):
    from paddle_tpu.ops.pallas.head_dx import head_dx_softmax

    M = 44 * 512
    return head_dx_softmax, (S((M, V), BF16), S((M,), F32), S((M,), F32),
                             S((V, H), BF16))


def _ragged_decode(kv_dtype):
    from paddle_tpu.ops.pallas.decode_attention import ragged_decode_attention

    def build(S):
        kv = S((SLOTS, MAX_LEN, NH, D), kv_dtype)
        args = [S((SLOTS, NH, D), BF16), kv, kv, S((SLOTS,), I32)]
        if kv_dtype == I8:
            scale = S((SLOTS, MAX_LEN), F32)
            return (lambda q, k, v, pos, ks, vs: ragged_decode_attention(
                q, k, v, pos, k_scale=ks, v_scale=vs)), args + [scale, scale]
        return ragged_decode_attention, args
    return build


def _paged(tq, layers=0, slots=SLOTS, heads=(NH, NH, D), pages=None,
           max_pages=MAX_LEN // PAGE):
    """One layer's [P, page, Hkv*D] pool, or with ``layers`` the stacked
    pool and the layer as a traced scalar: the call of the layer scan."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    def build(S):
        nh, hkv, d = heads
        plane = (pages or slots * max_pages + 1, PAGE, hkv * d)
        pool = S(((layers,) if layers else ()) + plane, BF16)
        args = (S((slots, tq, nh, d), BF16), pool, pool,
                S((slots, max_pages), I32), S((slots,), I32),
                S((slots,), I32))
        if not layers:
            return ragged_paged_attention, args
        return (lambda q, k, v, pt, ctx, ql, lay: ragged_paged_attention(
            q, k, v, pt, ctx, ql, layer=lay)), args + (S((), I32),)
    return build


def _paged_cell(slots, tq):
    """The kernel as ``internlm2-1.8b``'s serve cells call it (program
    ``('pseg', 32, 256, 32)``): 16 / 8 heads x 128, the 24-layer pool of
    2049 pages, a table 64 pages wide."""
    return _paged(tq, layers=24, slots=slots, heads=(16, 8, 128),
                  pages=2049, max_pages=64)


def _tick(which, rows=SLOTS, width=H, kv_width=H, head_dim=D):
    """A tick's rows by default; ``rows`` x ``width`` for the rows of an
    admission (``_rows_qkv``): 256 x 2048 is ``internlm2-1.8b``'s, one
    block; 1,024 x 4,096 is Mistral's largest bucket, a grid over row
    blocks."""
    from paddle_tpu.ops.pallas import tick_fusion as tf

    def build(S):
        x, w = S((rows, width), BF16), S((width,), F32)
        if which == "rms":
            return (lambda x, w: tf.fused_rms_norm(x, w, 1e-6)), (x, w)
        if which == "add_rms":
            return (lambda x, y, w: tf.fused_add_rms_norm(x, y, w, 1e-6)), \
                (x, x, w)
        return (lambda q, k, pos: tf.fused_rope_qk(q, k, pos, head_dim,
                                                   10000.0)), \
            (x, S((rows, kv_width), BF16), S((rows,), I32))
    return build


def _quant_matmul(S):
    from paddle_tpu.ops.pallas.tick_fusion import quant_matmul

    return quant_matmul, (S((SLOTS, H), BF16), S((H, V), I8), S((V,), F32))


def _multi_tensor(kind):
    from paddle_tpu.ops.pallas import multi_tensor_update as mtu

    shapes = [(H, H), (H,), (4 * H, H), (V, H), (7,)]   # a mixed group
    state = {"momentum": ("velocity",), "adam": ("moment1", "moment2")}[kind]
    hyper = {"momentum": {"momentum": 0.9},
             "adam": {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
                      "decay": 0.01, "decoupled": True}}[kind]

    def build(S):
        plan = mtu.FlatPlan(shapes)
        group = [S(s, F32) for s in shapes]

        def update(pvals, gvals, *svals):
            return mtu.apply_flat_update(
                kind, plan, pvals, gvals,
                [dict(zip(state, s)) for s in zip(*svals)], hyper, 1e-3, 1)

        return update, (group, group) + (group,) * len(state)
    return build


def _mla_paged(slots, tq):
    """``mla_paged_attention`` at openPangu-Ultra-MoE's widths: 128 heads
    against one latent row of 640 lanes, a 5-layer pool of 128 x 96 pages:
    a full house's decode tick (128 rows a slot) and an admission of 512
    positions (65,536 query rows: the grid axis over query rows)."""
    from paddle_tpu.ops.pallas.mla_attention import mla_paged_attention

    def build(S):
        pool = S((5, 128 * 96 + 1, PAGE, 640), BF16)
        return (lambda q, c, pt, ctx, ql, lay: mla_paged_attention(
            q, c, pt, ctx, ql, layer=lay, rank=512)), (
                S((slots, tq, 128, 640), BF16), pool, S((slots, 96), I32),
                S((slots,), I32), S((slots,), I32), S((), I32))
    return build


def _grouped_experts(tokens, swiglu):
    """``grouped_expert_matmul`` over 4 layers x 16 held experts of 7680 x
    2048, the stack indexed by a traced layer: the worst-case row buffer
    of ``tokens`` x 8 picks."""
    from paddle_tpu.ops.pallas.grouped_matmul import (buffer_rows,
                                                      grouped_expert_matmul)

    def build(S):
        rows = buffer_rows(tokens * 8, 16)
        k, n = (7680, 2048) if swiglu else (2048, 7680)
        w = S((4, 16, k, n), BF16)
        args = (S((rows, k), BF16),) + (w,) * (2 if swiglu else 1) + (
            S((rows // 32,), I32), S((1,), I32), S((), I32))
        if swiglu:
            return (lambda x, wg, wu, te, nt, lay: grouped_expert_matmul(
                x, (wg, wu), te, nt, swiglu=True, layer=lay)), args
        return (lambda x, w, te, nt, lay: grouped_expert_matmul(
            x, w, te, nt, layer=lay)), args
    return build


def _retention_decode(slots):
    """``power_retention_decode``'s kernel at Brumby-14B's widths: 8 kv
    heads x 5 query heads x 128, the float32 state plane of 8 layers x
    (slots + 1) pages of ``[8, 9216, 128]`` handed once and aliased to the
    output: the benchmark's full house, and a replay's four sequences."""
    from paddle_tpu.ops.pallas import power_retention as op

    def build(S):
        plane = S((8, slots + 1, 8, 9216, 128), F32)
        return (lambda q, k, v, dec, s, page, live, fresh, lay:
                op._decode_kernel(q, k, v, dec, s, page, live, fresh, lay,
                                  False)), (
                S((slots, 8, 5, 128), BF16), S((slots, 8, 128), BF16),
                S((slots, 8, 128), BF16), S((slots, 8), F32), plane,
                S((slots,), I32), S((slots,), jnp.bool_),
                S((slots,), jnp.bool_), S((), I32))
    return build


CASES = {
    "power_retention_decode_16slots": _retention_decode(16),
    "power_retention_decode_4slots": _retention_decode(4),
    "mla_paged_decode_128slots": _mla_paged(128, 1),
    "mla_paged_admit_tq512": _mla_paged(1, 512),
    "grouped_experts_gate_up_t128": _grouped_experts(128, True),
    "grouped_experts_down_t512": _grouped_experts(512, False),
    "flash_fwd_bwd_packed_b44_s512": _flash(44, 512),
    "flash_fwd_bwd_blocked_b4_s4096": _flash(4, 4096),
    "head_dx_softmax_m22528": _head_dx,
    "ragged_decode_bf16": _ragged_decode(BF16),
    "ragged_decode_int8_kv_scales": _ragged_decode(I8),
    "ragged_paged_tq1": _paged(1),
    "ragged_paged_tq16": _paged(16),
    "ragged_paged_tq64": _paged(64),
    "ragged_paged_layered_tq1": _paged(1, layers=12),
    "ragged_paged_layered_tq16": _paged(16, layers=12),
    "ragged_paged_cell_decode_b32": _paged_cell(32, 1),
    "ragged_paged_cell_admit_tq256": _paged_cell(1, 256),
    "ragged_paged_cell_tq16": _paged_cell(32, 16),
    "ragged_paged_cell_tq64": _paged_cell(8, 64),
    "fused_rms_norm": _tick("rms"),
    "fused_add_rms_norm": _tick("add_rms"),
    "fused_rope_qk": _tick("rope"),
    "fused_rms_norm_admit_256x2048": _tick("rms", 256, 2048),
    "fused_rope_qk_admit_256x2048": _tick("rope", 256, 2048, 1024, 128),
    "fused_rms_norm_gridded_1024x4096": _tick("rms", 1024, 4096),
    "fused_rope_qk_gridded_1024x4096": _tick("rope", 1024, 4096, 1024, 128),
    "quant_matmul_int8_768x32000": _quant_matmul,
    "multi_tensor_momentum": _multi_tensor("momentum"),
    "multi_tensor_adam": _multi_tensor("adam"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, shaped, no_persistent_cache):
    fn, args = CASES[name](shaped)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in the " \
                                      f"compiled program"


def _compiled_segment(shaped, model, cfg, slots, max_pages, pages, n_pad,
                      s_max, steps, fixed_parts=0):
    """``('pseg', n_pad, s_max, steps)`` of ``cfg`` (family ``model``) over
    a pool of ``pages`` pages (and, where sequences keep a fixed part,
    ``fixed_parts`` of them), lowered from shapes alone and compiled for
    the described chip; bf16 weights."""
    from paddle_tpu.inference.serving import ServingEngine

    def abstract(build):
        return jax.tree.map(lambda a: shaped(a.shape, a.dtype),
                            jax.eval_shape(build))

    params = abstract(lambda: model.init_params(cfg, jax.random.PRNGKey(0),
                                                dtype=BF16))
    kinds = {"fixed_parts": fixed_parts} if fixed_parts else {}
    pool = abstract(lambda: model.init_paged_pool(cfg, pages, PAGE, **kinds))
    engine = types.SimpleNamespace(        # all the builder reads of one
        cfg=cfg, slots=slots, eos=None,
        pager=types.SimpleNamespace(max_pages=max_pages,
                                    fixed_parts=fixed_parts))
    segment = ServingEngine._build_paged_segment_prog(engine, n_pad, s_max,
                                                      steps)
    vec = shaped((slots,), I32)
    req = shaped((n_pad,), I32)
    return segment.lower(
        params, pool, shaped((slots, max_pages + bool(fixed_parts)), I32),
        vec, vec, vec,
        shaped((n_pad, s_max), I32), req, req, req,
        shaped((n_pad, max_pages), I32), shaped((), I32)).compile()


def _kernel_call_sites(text, name):
    return re.findall(rf"%({name}[.\d]*) = [^\n]*"
                      r"custom_call_target=\"tpu_custom_call\"", text)


def _moved(text, layer_elems, of_pages=None,
           ops="copy|copy-start|reshape|dynamic-slice|dynamic-update-slice"):
    """bf16 ``ops`` (by default copies, reshapes and slices) in the compiled
    text as large as one layer of a pool plane (``of_pages``: only shapes
    with that many pages a dimension — a weight as large, re-tiled once a
    call, is not the pool's guard's)."""
    moved = []
    for dims, op in re.findall(rf"= bf16\[([\d,]+)\]\S* ({ops})\(", text):
        sizes = [int(d) for d in dims.split(",")]
        n = 1
        for d in sizes:
            n *= d
        if n >= layer_elems and (of_pages is None or of_pages in sizes):
            moved.append((op, dims))
    return moved


def test_paged_segment_holds_pool_once(shaped, no_persistent_cache,
                                       monkeypatch):
    """The paged segment program (``jit_segment``: a while_loop of admit
    (1 x 64 prefill) or decode (8 x 1) steps around
    ``forward_with_pages``, pool donated) compiled with the Mosaic kernel
    chosen: the pool is updated in place and read where it lies. No
    ``copy`` / ``reshape`` / ``dynamic-slice`` / ``dynamic-update-slice``
    as large as ONE LAYER of a pool plane is in the compiled text, and the
    program's temporaries are under one plane. Any of the three sites
    undone — the layer scan taking the pool as xs/ys, the kernel's wrapper
    reshaping it, a ``lax.cond`` around the branches — brings them back."""
    from paddle_tpu.models import llama
    from paddle_tpu.ops.pallas import flash_attention

    # dispatch asks jax.default_backend(), which says cpu here
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    layers, pages, n_pad, s_max, steps = 4, 2049, 8, 64, 8
    cfg = llama.LlamaConfig(
        vocab_size=V, hidden_size=H, intermediate_size=4 * H,
        num_layers=layers, num_heads=NH, num_kv_heads=NH,
        max_seq_len=MAX_LEN, dtype=BF16, remat=False, scan_layers=True)
    compiled = _compiled_segment(shaped, llama, cfg, SLOTS, MAX_LEN // PAGE,
                                 pages, n_pad, s_max, steps)
    text = compiled.as_text()
    # one kernel a call site: the admit branch's and the decode branch's
    # layer scan, whatever the table's width and the pages a block
    kernels = _kernel_call_sites(text, "ragged_paged_attention")
    assert len(kernels) == 2, f"paged kernel call sites: {kernels}"

    layer_elems = pages * PAGE * NH * D
    moved = _moved(text, layer_elems)
    assert not moved, f"the compiled segment moves the pool: {moved}"
    plane_bytes = layers * layer_elems * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < plane_bytes, \
        f"temporaries {temp} B hold a pool plane ({plane_bytes} B)"


def test_llama_segment_reads_stacked_weights_where_they_lie(
        shaped, no_persistent_cache, monkeypatch):
    """``internlm2-1.8b``'s serve cells' own program, ``('pseg', 32, 256,
    32)`` at the published widths (24 layers, 32 slots, a table of 64
    pages, 2049 pages): the admit arm (1 x 256) and the decode arm (32 x 1)
    compute q, k, v and their rope by one formulation (``_rows_qkv``), so
    XLA gives ``wq`` / ``wk`` one layout. Until PR 35 the admit arm roped
    in XLA, which fused the chain into the dots and wanted both stacks
    transposed: the program transposed them at its entry and copied them
    BACK once a step for the decode arm (``copy.52`` / ``copy.51``, 0.88 ms
    of a 6.57 ms step, and 605.7 MB of temporaries). No ``copy`` /
    ``transpose`` as large as the smallest stacked layer weight (``wk``) is
    in the compiled text, and the temporaries are a few MB."""
    import json
    import os

    from chipbench.common import llama_config
    from paddle_tpu.models import llama
    from paddle_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "chipbench",
                           "configs", "internlm2-1.8b.json")) as f:
        cfg = llama_config(json.load(f))
    slots, max_len, pages, n_pad, s_max, steps = 32, 1024, 2049, 32, 256, 32
    compiled = _compiled_segment(shaped, llama, cfg, slots, max_len // PAGE,
                                 pages, n_pad, s_max, steps)
    text = compiled.as_text()
    kernels = _kernel_call_sites(text, "ragged_paged_attention")
    assert len(kernels) == 2, f"paged kernel call sites: {kernels}"

    wk_elems = cfg.num_layers * cfg.hidden_size \
        * cfg.num_kv_heads * cfg.head_dim
    moved = _moved(text, wk_elems, ops="copy|copy-start|transpose")
    assert not moved, f"the compiled segment re-tiles a weight stack: {moved}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2**20, f"temporaries {temp} B"


def test_latent_segment_holds_pool_once(shaped, no_persistent_cache,
                                        monkeypatch):
    """``test_paged_segment_holds_pool_once``' latent twin, at cell 4's
    size: ``('pseg', 128, 512, 32)`` of openPangu-Ultra-MoE's share (1
    dense + 4 expert layers at the published widths, 16 held experts, 128
    slots x 96 page slots) around ``latent_moe.forward_with_pages``, both
    Mosaic kernels chosen. ``mla_paged_attention`` is handed the latent
    plane once, in HBM: no copy / reshape / slice as large as ONE LAYER of
    it in the compiled text, temporaries under the plane, and four call
    sites (the dense layer and the expert layers' scan, in the admit and
    the decode branch) whatever the table's width."""
    from paddle_tpu.models import latent_moe
    from paddle_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    slots, max_len, n_pad, s_max, steps = 128, 1536, 128, 512, 32
    max_pages = max_len // PAGE
    pages = slots * max_pages + 1
    cfg = latent_moe.LatentMoEConfig(
        num_layers=5, first_k_dense=1, held_experts=(0, 16),
        vocab_slice=(0, 19200), max_seq_len=max_len)
    compiled = _compiled_segment(shaped, latent_moe, cfg, slots, max_pages,
                                 pages, n_pad, s_max, steps)
    text = compiled.as_text()
    kernels = _kernel_call_sites(text, "mla_paged_attention")
    assert len(kernels) == 4, f"latent kernel call sites: {kernels}"

    layer_elems = pages * PAGE * cfg.cache_row
    moved = _moved(text, layer_elems, of_pages=pages)
    assert not moved, f"the compiled segment moves the latent plane: {moved}"
    plane_bytes = cfg.num_layers * layer_elems * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < plane_bytes, \
        f"temporaries {temp} B hold the latent plane ({plane_bytes} B)"


def test_retention_segment_holds_pool_once(shaped, no_persistent_cache,
                                           monkeypatch):
    """The same guard for state pages, at ``brumby-14b-l8``'s size:
    ``('pseg', 16, 1024, 32)`` of 8 power-retention layers at the published
    widths (16 slots, ``page_size = max_len``: one page a slot) around
    ``power_retention.forward_with_pages``. The ``s`` plane (4.87 GB with
    the trash page's 0.30) is handed to ``power_retention_decode`` once, in
    HBM, aliased to its output; the admission writes its page in place: no
    float32 copy / reshape / slice as large as ONE LAYER of the plane in
    the compiled text, temporaries under ONE plane, arguments + temporaries
    inside the chip's 16 GiB, one kernel call site (the layer scan's, in
    the decode branch)."""
    from paddle_tpu.models import power_retention
    from paddle_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    slots, max_len, n_pad, s_max, steps = 16, 2048, 16, 1024, 32
    cfg = power_retention.PowerRetentionConfig(num_layers=8,
                                               max_seq_len=max_len)
    pages = slots + 1
    compiled = _compiled_segment(shaped, power_retention, cfg, slots, 1,
                                 pages, n_pad, s_max, steps)
    text = compiled.as_text()
    kernels = _kernel_call_sites(text, "power_retention_decode")
    assert len(kernels) == 1, f"decode kernel call sites: {kernels}"

    layer_elems = pages * cfg.num_kv_heads * cfg.state_width * cfg.head_dim
    moved = []
    for dims, op in re.findall(
            r"= f32\[([\d,]+)\]\S* (copy|copy-start|reshape|dynamic-slice)"
            r"\(", text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        if n >= layer_elems:
            moved.append((op, dims))
    assert not moved, f"the compiled segment moves the state plane: {moved}"
    plane_bytes = cfg.num_layers * layer_elems * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < plane_bytes, \
        f"temporaries {mem.temp_size_in_bytes} B hold the state plane " \
        f"({plane_bytes} B)"
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
    assert mem.alias_size_in_bytes >= plane_bytes    # donated, in place


def test_hybrid_segment_holds_both_caches_once(shaped, no_persistent_cache,
                                               monkeypatch):
    """The same guard for two kinds of cache in one program, at
    ``k-exaone-236b-l5-ep8``'s size: ``('pseg', 64, 4096, 32)`` of 1 dense
    + 4 sparse layers at the published widths (window, window, window,
    full, window; 16 held experts; 64 slots x 320 page slots + a fixed part
    a slot) around ``hybrid_moe.forward_with_pages``. Each plane is handed
    to its kernels once, in HBM: no copy / reshape / slice as large as ONE
    LAYER of the row pool or of the fixed parts, no copy as large as one
    layer's held experts or as ``wq`` (a layer's weights are separate
    arrays: nothing is sliced out of a stack inside the step loop), every
    kernel at its call sites under its own name (a loop's body is ONE site:
    a ladder of traced admit widths would multiply them), the pool donated,
    and arguments + temporaries inside the chip's 16 GiB. The admit
    branch's row-wise work runs in row blocks under a trip count the
    program reads from the prompt's length: every ``while`` directly under
    ``segment.admit`` compares its counter with a carried value, not a
    constant, and nothing as wide as the bucket's dense intermediate
    ``[4096, 18432]`` or the worst-case expert buffer ``[33280, 6144]`` is
    left."""
    # (~20 s: the one whole program of this size in the file)
    from paddle_tpu.models import hybrid_moe
    from paddle_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    slots, max_len, n_pad, s_max, steps = 64, 5120, 64, 4096, 32
    max_pages = max_len // PAGE
    pages = slots * max_pages + 1
    cfg = hybrid_moe.HybridMoEConfig(
        num_layers=5, held_experts=(0, 16), vocab_slice=(0, 19200),
        max_seq_len=max_len)
    compiled = _compiled_segment(shaped, hybrid_moe, cfg, slots, max_pages,
                                 pages, n_pad, s_max, steps,
                                 fixed_parts=slots + 1)
    text = compiled.as_text()
    sites = {name: len(_kernel_call_sites(text, name))
             for name in hybrid_moe.KERNEL_NAMES.values()}
    assert sites == {"paged_attention_full": 1, "paged_attention_window": 4,
                     "prefill_attention_full": 1,
                     "prefill_attention_window": 4}
    assert len(_kernel_call_sites(text, "grouped_expert_matmul")) == 16
    trips = re.findall(r"compare\(([^)]*)\), direction=LT, metadata=\{"
                       r"op_name=\"[^\"]*segment\.admit/while/cond/lt\"", text)
    assert trips and not [t for t in trips if "constant" in t], trips
    assert not re.findall(r"\[(?:4096,18432|33280,6144)\]", text)

    row_layer = pages * PAGE * cfg.kv_width
    moved = _moved(text, row_layer, of_pages=pages)
    assert not moved, f"the compiled segment moves the row pool: {moved}"
    part_layer = (slots + 1) * cfg.sliding_window * cfg.kv_width
    moved = _moved(text, part_layer, of_pages=slots + 1,
                   ops="copy|copy-start|reshape|dynamic-slice")
    assert not moved, f"the compiled segment moves the fixed parts: {moved}"
    # the step loop's computations come before ENTRY in the text (XLA
    # re-tiles each wq ONCE A SEGMENT at the program's entry, 0.5 GB of
    # temporaries and 1.2 ms in ~500: not the loop's)
    loop = text[:text.index("\nENTRY ")]
    wq = cfg.hidden_size * cfg.num_heads * cfg.head_dim
    copied = [m for m in _moved(loop, wq, ops="copy|copy-start")
              if "4096" not in m[1].split(",")]     # not an activation
    assert not copied, f"the step loop copies a weight: {copied}"
    mem = compiled.memory_analysis()
    pool_bytes = 2 * row_layer * 2 + 2 * 4 * part_layer * 2
    assert mem.alias_size_in_bytes >= pool_bytes     # donated, in place
    # 1,084,419,072 B + 10 % (1,588,627,456 B with the whole bucket's rows
    # in every intermediate)
    assert mem.temp_size_in_bytes < 1_190_000_000, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


def test_canonical_paged_segment_is_the_parents_program():
    """PR 32 took the contiguous engine out from around the paged segment
    and claims the program itself did not move: the gate's
    ``paged_serving_segment`` (llama-tiny, 4 slots, this installation's CPU
    lowering) keeps the key it is memoised under and the instruction
    count the parent commit compiled it to. A change to
    ``_build_paged_segment_prog`` or ``forward_with_pages`` moves the
    count: re-pin it as ``analysis/budgets.py``'s bytes are re-pinned."""
    from paddle_tpu.analysis import programs

    handle = programs.build("paged_serving_segment")
    text = handle.hlo()
    assert text.lstrip().startswith("HloModule jit_segment")
    assert len(re.findall(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = ", text,
                          re.M)) == 1858
    eng = handle.aot_engine
    assert list(eng._progs) == [("pseg", 4, 16, 12)]
    assert eng.program_space(handle.aot_envelope) == \
        {"pseg": frozenset({("pseg", 4, 16, 12)})}
