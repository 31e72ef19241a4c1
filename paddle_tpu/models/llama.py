"""LLaMA-family decoder — the flagship pretraining workload, TPU-first.

Reference counterpart: PaddleNLP's LLaMA with Fleet hybrid parallel
(BASELINE config 4: "LLaMA-7B with Fleet sharding stage2/3 + tensor-parallel
(c_allgather/reduce_scatter)"), built on the reference's
``ColumnParallelLinear``/``RowParallelLinear``/``VocabParallelEmbedding``
(``python/paddle/distributed/fleet/meta_parallel/parallel_layers/mp_layers.py``,
SURVEY.md §2.2) and flash-attention fused kernels (§2.1).

TPU-native design decisions (NOT a port):

* **One pure function** for the whole train step, jitted over a hybrid
  ``Mesh`` — XLA GSPMD inserts the all-gathers/reduce-scatters the reference
  codes by hand as ``c_*`` ops.
* **Scan over layers**: per-layer weights are stacked on a leading ``L`` axis
  and the decoder is a ``jax.lax.scan`` — O(1) compile time in depth, and the
  leading axis doubles as the pipeline-stage axis for PP.
* **Sharding rules, not collectives**: Megatron TP is expressed as
  PartitionSpecs (column-parallel = shard output dim on ``mp``, row-parallel
  = shard input dim on ``mp``, vocab-parallel embedding = shard vocab) plus
  activation constraints; ZeRO (sharding stage 1/2/3) is PartitionSpecs on
  optimizer state / params over ``('dp','sharding')``.
* **bf16 compute, fp32 master weights** — AMP-O2 with master weights
  (reference: ``paddle.amp`` O2 + ``GradScaler``; bf16 needs no loss scale).
* **Remat** (``jax.checkpoint``) per layer = the reference's
  ``fleet.recompute`` activation checkpointing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.pallas.flash_attention import (
    dot_product_attention,
    flash_path_active as _flash_path_active,
)
from ..parallel.mesh import with_sharding_constraint as wsc


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # ZeRO level for optimizer/param sharding over the ('dp','sharding') axes:
    # 1 = shard opt states, 2 = (+grads, implicit in jit), 3 = shard params too
    sharding_stage: int = 1
    remat: bool = True
    # scan_layers=True: decoder as lax.scan over stacked weights — O(1)
    # compile depth, the right shape for deep models and the pp axis.
    # scan_layers=False: python-unrolled layers — XLA saves residuals as
    # plain buffers with NO scan dynamic-update-slice stacking machinery;
    # measured ~20% faster on the bert-base-budget single-chip workload
    # (usually paired with remat=False when activations fit HBM).
    scan_layers: bool = True
    # sequence parallel: shard activations' seq dim over 'sep' outside matmuls
    sequence_parallel: bool = False
    # which SP attention formulation carries the sep axis (r7, mirroring
    # the reference's two SP implementations): "ring" = K/V blocks
    # ppermute around the sep ring with online-softmax merging; "ulysses"
    # = two all-to-alls reshard seq-parallel activations head-parallel,
    # exact attention per rank (cheaper when 2*|q| < (n-1)*|kv| — MHA at
    # moderate sep; GQA favours the ring). Both fall back dense when the
    # axis is absent or shapes don't divide.
    sp_impl: str = "ring"
    # single-chip chunked cross-entropy: head+CE recomputed per batch-chunk
    # so [B,S,V] logits never materialise (0 = off; see loss_fn)
    ce_chunks: int = 0
    # perf experiment knob: comma-joined set of backward-cotangent barrier
    # sites ('mlp', 'qkv', 'logits') — forces the named cotangents to
    # MATERIALISE once instead of letting XLA re-fuse their elementwise
    # chains into both consumer dots (dW and dx). See _barrier_grad.
    bwd_barriers: str = ""
    # store wq/wk/wv as ONE stacked [H, H+2*Hkv] matrix and w_gate/w_up
    # as [H, 2F]: one projection dot with a wider N instead of three/two
    # (fewer MXU ramp-ups, one dW instead of three in the bwd). The split
    # into q/k/v (gate/up) is a free minor-dim slice of the dot output.
    # r3's measured LOSS on this idea concatenated the weights PER STEP;
    # storing them fused removes that cost from the step entirely.
    fused_weights: bool = False
    # AMP-O2 gradient dtype: differentiate w.r.t. the bf16 param VIEW so
    # grads stay bf16 end-to-end (half the HBM traffic in the dW writes,
    # global-norm pass, and AdamW reads); the fp32 master weights are only
    # touched by the optimizer. Matches the reference's O2 GradScaler
    # contract (fp16/bf16 grads + fp32 master params).
    bf16_grads: bool = False
    # decode-tick fusion: on the KV-cache single-token path, collapse the
    # between-matmul small-op chains (rms, rope, residual+norm) into one
    # Pallas op each and run attention as the RAGGED kernel that reads
    # only KV rows [0, pos] per slot instead of the full max_len window
    # (ops/pallas/decode_attention.py, tick_fusion.py). Dispatch falls
    # back to the inline jnp chains off-TPU / under a mesh / on
    # non-tileable shapes — identical math either way.
    fused_tick_epilogue: bool = True
    # custom-VJP head+CE tail (single-chip, non-chunked path only): the
    # backward picks each dot's MXU orientation independently — dx runs
    # as (W @ dlogits^T)^T, the wide-N transpose formulation a bare-dot
    # microbench clocks at ~96% of peak vs ~60% for autodiff's
    # dlogits @ W^T (benchmarks/dot_variants.py); the softmax recompute
    # stays fused inside both bwd dots (no [M,V] cotangent materialises),
    # and dx's one-hot term becomes a cheap GATHER of W columns at the
    # target ids instead of a mask pass.
    ce_tail_custom: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw):
        """Tiny config for tests / compile-checks."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
        d.update(kw)
        return cls(**d)

    @classmethod
    def bert_base_equiv(cls, **kw):
        """~110M decoder matching BERT/ERNIE-base budget (BASELINE config 2).

        Unrolled + no remat: at this depth/width the activations fit HBM
        alongside the optimizer, and skipping both the recompute FLOPs and
        the scan residual-stacking copies is worth ~25% step time."""
        d = dict(vocab_size=32000, hidden_size=768, intermediate_size=3072,
                 num_layers=12, num_heads=12, num_kv_heads=12, max_seq_len=512,
                 remat=False, scan_layers=False, ce_tail_custom=True)
        d.update(kw)
        return cls(**d)

    @classmethod
    def cpu_small(cls, **kw):
        """~3M-param decoder: the serving benchmarks' CPU-tractable shape
        (the chip lane runs bert_base_equiv; off-chip artifact runs record
        this model so scheduling behaviour — not matmul speed — is what
        the numbers exercise). Unrolled+fp32 like bert_base_equiv so the
        same decode code paths run."""
        d = dict(vocab_size=2048, hidden_size=128, intermediate_size=512,
                 num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=512,
                 dtype=jnp.float32, remat=False, scan_layers=False)
        d.update(kw)
        return cls(**d)

    @classmethod
    def llama7b(cls, **kw):
        return cls(**kw)  # defaults above are 7B


# ---------------------------------------------------------------------------
# Sharding rules (Megatron TP + ZeRO over the hybrid mesh axes)
# ---------------------------------------------------------------------------

def param_specs(cfg: LlamaConfig) -> Dict[str, P]:
    """PartitionSpec per parameter. Leading axis of ``layers/*`` is the
    stacked layer axis (scanned; sharded over 'pp' when pipelining).

    TP mapping (reference mp_layers.py → specs):
      VocabParallelEmbedding → embed sharded on vocab over mp
      ColumnParallelLinear (wq/wk/wv/w1/w3) → output-dim over mp
      RowParallelLinear   (wo/w2)           → input-dim over mp
    ZeRO stage 3 additionally shards the non-mp dim over ('dp','sharding').
    """
    zdim = ("dp", "sharding") if cfg.sharding_stage >= 3 else None
    specs = {
        "embed": P("mp", zdim),                    # [V, H]
        "wq": P(None, zdim, "mp"),                 # [L, H, H]
        "wk": P(None, zdim, "mp"),                 # [L, H, Hkv]
        "wv": P(None, zdim, "mp"),                 # [L, H, Hkv]
        "wo": P(None, "mp", zdim),                 # [L, H, H]
        "w_gate": P(None, zdim, "mp"),             # [L, H, F]
        "w_up": P(None, zdim, "mp"),               # [L, H, F]
        "w_down": P(None, "mp", zdim),             # [L, F, H]
        "ln_attn": P(None, None),                  # [L, H]
        "ln_mlp": P(None, None),                   # [L, H]
        "ln_f": P(None),                           # [H]
        "lm_head": P(zdim, "mp"),                  # [H, V]
    }
    return _fuse_keys(cfg, specs)


def _fuse_keys(cfg: "LlamaConfig", d: Dict[str, Any]) -> Dict[str, Any]:
    """Rewrite a per-key dict to the fused_weights param tree: wq/wk/wv →
    wqkv, w_gate/w_up → w_gate_up (the fused matrices share wq's spec —
    the stacked minor dim stays the 'column' TP dim)."""
    if not cfg.fused_weights:
        return d
    out = {k: v for k, v in d.items()
           if k not in ("wq", "wk", "wv", "w_gate", "w_up")}
    out["wqkv"] = d["wq"]
    out["w_gate_up"] = d["w_gate"]
    return out


def opt_state_specs(cfg: LlamaConfig) -> Dict[str, P]:
    """ZeRO stage>=1: Adam moments sharded over ('dp','sharding') on the
    first shardable dim (reference: DygraphShardingOptimizer /
    GroupShardedOptimizerStage2 shard optimizer states)."""
    if cfg.sharding_stage < 1:
        return param_specs(cfg)
    z = ("dp", "sharding")
    return _fuse_keys(cfg, {
        "embed": P("mp", z),
        "wq": P(None, z, "mp"),
        "wk": P(None, z, "mp"),
        "wv": P(None, z, "mp"),
        "wo": P(None, "mp", z),
        "w_gate": P(None, z, "mp"),
        "w_up": P(None, z, "mp"),
        "w_down": P(None, "mp", z),
        "ln_attn": P(None, z),
        "ln_mlp": P(None, z),
        "ln_f": P(z),
        "lm_head": P(z, "mp"),
    })


def init_params(cfg: LlamaConfig, key: Optional[jax.Array] = None,
                dtype: Any = None) -> Dict[str, jax.Array]:
    """Initialise the parameter pytree (fp32 master weights)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    dtype = dtype or jnp.float32
    H, F, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_layers)
    Hkv = cfg.num_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 12)
    s = lambda fan_in: 1.0 / np.sqrt(fan_in)
    n = jax.random.normal
    out = {
        "embed": (n(ks[0], (V, H)) * 0.02).astype(dtype),
        "wq": (n(ks[1], (L, H, H)) * s(H)).astype(dtype),
        "wk": (n(ks[2], (L, H, Hkv)) * s(H)).astype(dtype),
        "wv": (n(ks[3], (L, H, Hkv)) * s(H)).astype(dtype),
        "wo": (n(ks[4], (L, H, H)) * s(H)).astype(dtype),
        "w_gate": (n(ks[5], (L, H, F)) * s(H)).astype(dtype),
        "w_up": (n(ks[6], (L, H, F)) * s(H)).astype(dtype),
        "w_down": (n(ks[7], (L, F, H)) * s(F)).astype(dtype),
        "ln_attn": jnp.ones((L, H), dtype),
        "ln_mlp": jnp.ones((L, H), dtype),
        "ln_f": jnp.ones((H,), dtype),
        "lm_head": (n(ks[8], (H, V)) * s(H)).astype(dtype),
    }
    if cfg.fused_weights:
        out["wqkv"] = jnp.concatenate(
            [out.pop("wq"), out.pop("wk"), out.pop("wv")], axis=-1)
        out["w_gate_up"] = jnp.concatenate(
            [out.pop("w_gate"), out.pop("w_up")], axis=-1)
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def scoped(name: str):
    """Decorator: trace ``fn`` under ``jax.named_scope(name)`` — the name a
    device trace shows its ops under (``profiler._xplane.SCOPES`` lists
    them). A fresh scope per call: the context manager jax hands out keeps
    state, so one instance shared by every caller is not re-entrant."""
    def deco(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return traced
    return deco


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


@jax.custom_vjp
def _barrier_grad(x):
    """Identity whose COTANGENT is fenced with an optimization_barrier.

    XLA fuses an elementwise backward chain (silu', rope shuffles, softmax
    recompute) into EVERY consumer dot's operand window, re-running it per
    dot; fencing the cotangent forces one materialisation that both the dW
    and dx dots then read. Whether that trade wins is shape-dependent —
    gate it with LlamaConfig.bwd_barriers and measure (benchmarks/perf_lab)."""
    return x


_barrier_grad.defvjp(lambda x: (x, None),
                     lambda _, g: (jax.lax.optimization_barrier(g),))


def _rope_at(x, theta, positions):
    # x: [B, S, H, D] at absolute ``positions`` — [S] (shared across the
    # batch) or [B, S] (ragged decode: every slot at its own position).
    # LLaMA rotate-half convention: the head dim splits into two contiguous
    # halves (lane-aligned slices on TPU — the strided ::2 interleave costs
    # extra vector shuffles every layer and again in every remat replay)
    b, s, h, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, D/2]
    if ang.ndim == 2:  # shared positions -> add the batch dim
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rope(x, theta):
    return _rope_at(x, theta, jnp.arange(x.shape[1]))


def _act_spec(cfg: LlamaConfig) -> P:
    # activations: batch over (dp, sharding-as-extra-dp), seq over sep when SP
    seq = "sep" if cfg.sequence_parallel else None
    return P(("dp", "sharding"), seq, None)


def _w(p, name, dt):
    """Weight ``name`` from a param/layer dict at compute dtype ``dt``.

    Quantized serving trees (quantization/serving.py) store matmul
    weights narrow (int8/fp8) with a companion per-output-channel
    ``<name>_scale`` fp32 plane; the dense dequantize here sits
    adjacent to the consuming dot so XLA fuses convert+scale into the
    operand read — the CPU/mesh fallback of the in-kernel-dequant
    Pallas path (see ``_mm``). fp trees pass straight through."""
    sc = p.get(name + "_scale")
    if sc is None:
        return p[name].astype(dt)
    return (p[name].astype(jnp.float32)
            * sc.astype(jnp.float32)[..., None, :]).astype(dt)


def _mm(h, p, name, dt):
    """``h @ weight[name]`` — the one projection-matmul site shared by
    fp and quantized param trees. On the 2D decode tick with a narrow
    weight, dispatch to the Pallas quant matmul (HBM streams the
    narrow dtype; dequant and fp32 accumulation happen in VMEM —
    ops/pallas/tick_fusion.py); everywhere else the dense
    dequantize-then-dot is the same math."""
    sc = p.get(name + "_scale")
    if sc is None:
        return h @ p[name].astype(dt)
    w = p[name]
    if h.ndim == 2 and w.ndim == 2:
        from ..ops.pallas.tick_fusion import (quant_matmul,
                                              quant_matmul_active)

        if quant_matmul_active(w.shape[0], w.shape[1]):
            return quant_matmul(h, w, sc).astype(dt)
    return h @ _w(p, name, dt)


def layer_params(params, cfg: "LlamaConfig"):
    """Per-layer stacked weights for the forward paths: ``layer_keys``
    plus any companion quantization ``_scale`` planes (stacked on the
    same leading [L] axis, so they scan/slice identically)."""
    out = {}
    for kk in layer_keys(cfg):
        out[kk] = params[kk]
        if kk + "_scale" in params:
            out[kk + "_scale"] = params[kk + "_scale"]
    return out


def _qkv_dots(cfg: LlamaConfig, h, lp, dt):
    """The three projections of normed rows ``h`` [..., H], flat:
    [..., nH*D], [..., Hkv*D], [..., Hkv*D]."""
    if not cfg.fused_weights:
        return tuple(_mm(h, lp, n, dt) for n in ("wq", "wk", "wv"))
    Hq = cfg.num_heads * cfg.head_dim
    Hkv = cfg.num_kv_heads * cfg.head_dim
    z = _mm(h, lp, "wqkv", dt)
    return z[..., :Hq], z[..., Hq:Hq + Hkv], z[..., Hq + Hkv:]


@scoped("qkv")
def _qkv_proj(cfg: LlamaConfig, x, lp, positions=None):
    """rms → q/k/v projections → rope at ``positions`` (default 0..S-1).
    Returns q [B,S,nH,D] and UNREPEATED k/v [B,S,Hkv,D] — the single
    source of the attention input convention for both training and the
    KV-cache decode path."""
    B, S, H = x.shape
    dt = x.dtype
    if positions is None:
        positions = jnp.arange(S)
    zq, zk, zv = _qkv_dots(
        cfg, _rms_norm(x, lp["ln_attn"], cfg.rms_eps), lp, dt)
    if "qkv" in cfg.bwd_barriers:
        zq, zk, zv = map(_barrier_grad, (zq, zk, zv))
    q = zq.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = zk.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = zv.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = _rope_at(q, cfg.rope_theta, positions)
    k = _rope_at(k, cfg.rope_theta, positions)
    return q, k, v


def _layer_qkv(cfg: LlamaConfig, x, lp):
    """Pre-attention half of a block: rms → qkv projections → rope → GQA."""
    q, k, v = _qkv_proj(cfg, x, lp)
    with jax.named_scope("qkv"):
        if cfg.num_kv_heads != cfg.num_heads:  # GQA: repeat kv heads
            rep = cfg.num_heads // cfg.num_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # heads are mp-sharded (follows from wq's output sharding); under
        # SP the seq dim STAYS sep-sharded — pinning it replicated here
        # would all-gather the sequence right before the ring attention
        seq_ax = "sep" if cfg.sequence_parallel else None
        q = wsc(q, P(("dp", "sharding"), seq_ax, "mp", None))
    return q, k, v


@scoped("post")
def _layer_post(cfg: LlamaConfig, x, attn, lp):
    """Post-attention half: output projection, residual, mlp."""
    B, S, H = x.shape
    dt = x.dtype
    attn = attn.reshape(B, S, H)
    x = x + wsc(_mm(attn, lp, "wo", dt), _act_spec(cfg))
    h = _rms_norm(x, lp["ln_mlp"], cfg.rms_eps)
    if cfg.fused_weights:
        F_ = cfg.intermediate_size
        zz = _mm(h, lp, "w_gate_up", dt)
        zg, up = zz[..., :F_], zz[..., F_:]
    else:
        zg = _mm(h, lp, "w_gate", dt)
        up = _mm(h, lp, "w_up", dt)
    if "mlp" in cfg.bwd_barriers:
        zg = _barrier_grad(zg)
        up = _barrier_grad(up)
    gate = jax.nn.silu(zg)
    x = x + wsc(_mm(gate * up, lp, "w_down", dt), _act_spec(cfg))
    return x


@scoped("attention")
def _attention(cfg: LlamaConfig, q, k, v):
    """Training attention dispatch: under sequence parallelism with a >1
    'sep' axis the seq dim is SHARDED, so attention must be the RING
    (context-parallel) formulation — K/V blocks ppermute around the sep
    ring with online-softmax merging — instead of letting GSPMD all-gather
    the whole sequence onto every device. The axis/divisibility fallback
    lives in context_parallel_attention itself (one guard, not two)."""
    if cfg.sequence_parallel:
        from ..ops.pallas.ring_attention import (
            context_parallel_attention, ulysses_parallel_attention)

        sp_fn = {"ring": context_parallel_attention,
                 "ulysses": ulysses_parallel_attention}[cfg.sp_impl]
        return sp_fn(
            q, k, v, axis_name="sep", is_causal=True,
            batch_axes=("dp", "sharding"), head_axes="mp",
            fallback=lambda: dot_product_attention(q, k, v, is_causal=True))
    return dot_product_attention(q, k, v, is_causal=True)


def _decoder_layer(cfg: LlamaConfig, x, lp):
    """One transformer block. x: [B, S, H]; lp: this layer's weight slice."""
    q, k, v = _layer_qkv(cfg, x, lp)
    attn = _attention(cfg, q, k, v)
    return _layer_post(cfg, x, attn, lp)


def forward_hidden(params: Dict[str, jax.Array], tokens: jax.Array,
                   cfg: LlamaConfig) -> jax.Array:
    """Final hidden states (post ln_f). tokens: [B, S] int32 → [B, S, H]."""
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
        x = wsc(x, _act_spec(cfg))

    layer_weights = {k: params[k] for k in layer_keys(cfg)}

    if cfg.remat and _flash_path_active():
        # Flash-path remat structure: checkpoint the two matmul halves but
        # keep attention OUTSIDE the remat region, so the flash custom-VJP's
        # O(S) residuals (q/k/v/out/logsumexp) are saved rather than the
        # forward kernel re-running inside the backward scan. The halves
        # still fully remat: saving their matmul outputs measures neutral
        # (the save/reload HBM traffic ≈ the recompute cost at this scale)
        # while costing ~2.4 GB — recompute is the better trade.
        qkv_part = jax.checkpoint(functools.partial(_layer_qkv, cfg))
        post_part = jax.checkpoint(functools.partial(_layer_post, cfg))

        def body(x, lp):
            q, k, v = qkv_part(x, lp)
            attn = _attention(cfg, q, k, v)
            return post_part(x, attn, lp), None
    else:
        def body(x, lp):
            return _decoder_layer(cfg, x, lp), None

        if cfg.remat:
            body = jax.checkpoint(body)  # fleet.recompute analog

    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, layer_weights)
    else:
        # python-unrolled: static per-layer slices, no scan stacking copies
        for i in range(cfg.num_layers):
            x, _ = body(x, {k: w[i] for k, w in layer_weights.items()})

    with jax.named_scope("head"):     # the head's own norm
        return _rms_norm(x, params["ln_f"], cfg.rms_eps)


def forward(params: Dict[str, jax.Array], tokens: jax.Array,
            cfg: LlamaConfig) -> jax.Array:
    """Logits for next-token prediction. tokens: [B, S] int32 → [B, S, V]."""
    x = forward_hidden(params, tokens, cfg)
    with jax.named_scope("head"):
        logits = x @ params["lm_head"].astype(cfg.dtype)
        return wsc(logits, P(("dp", "sharding"), None, "mp"))


def _nll_sum(logits, targets, weights) -> jax.Array:
    """Weighted token-nll sum over one logits block.

    The reduction upcasts to fp32 INSIDE the fused pass over the bf16
    logits: casting the whole [.., V] tensor first would materialise fp32
    holding bf16-precision values — pure HBM traffic for zero accuracy
    (the matmul already rounded to bf16)."""
    # stop_gradient on the max: lse's gradient (softmax) is exact for any
    # constant shift, and differentiating through jnp.max would cost an
    # extra [.., V] equality-mask pass plus an add_any combine in the bwd
    # (measured ~4.5 ms/step at the bench shape). Hand-written VJPs LOSE
    # here: an iota-onehot custom backward is +1.6 ms (the mask pass
    # outweighs the saved cotangent combine), scatter-based backwards are
    # +21..+50 ms (TPU scatters serialize). Autodiff of this exact form is
    # the measured optimum.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1).astype(jnp.float32))
    sumexp = jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - m[..., None]), axis=-1)
    gold = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    return jnp.sum((m + jnp.log(sumexp) - gold) * weights)


@jax.custom_vjp
def _head_ce_tail(h2, W, targets, wgt):
    """lm_head matmul + weighted token-nll SUM with a hand-picked backward.

    Forward math is bit-identical to ``_nll_sum(h2 @ W, targets, wgt)``;
    ``wgt`` [T] row-weights let the caller score ALL S positions with a
    zero on the last (no next-token label) — keeping the token dim a
    multiple of the pallas block so the kernel sees no ragged edge (a
    non-divisible M makes pallas materialise a PADDED copy of the 1.4 GB
    logits, measured 6.7 ms/step). The backward differs from autodiff
    only in SCHEDULING (same algebra):

    - the dx softmax term is a hand-written pallas kernel
      (ops/pallas/head_dx.py): softmax computed in-kernel from natural-
      layout logits tiles, tile-dots against a pre-transposed W with an
      fp32 VMEM accumulator (in-step 6.0 ms vs autodiff's 7.3 ms at the
      bench shape). Its one-hot term is a GATHER of W columns at the
      target ids (34 MB) — scatter-free.
    - dW keeps autodiff's wide-N orientation; its one-hot term is an
      in-tile iota mask fused into the dot's operand read.
    - the softmax recompute never materialises an [M, V] cotangent
      (saving one would cost ~1.8 ms of HBM at the bench shape).
    """
    return _nll_sum(h2 @ W.astype(h2.dtype), targets, wgt[None, :])


def _head_ce_tail_fwd(h2, W, targets, wgt):
    logits = h2 @ W.astype(h2.dtype)
    m = jax.lax.stop_gradient(
        jnp.max(logits, axis=-1).astype(jnp.float32))
    se = jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - m[..., None]), axis=-1)
    gold = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)
    out = jnp.sum((m + jnp.log(se) - gold) * wgt[None, :])
    return out, (h2, W, logits, m, se, targets, wgt)


def _head_ce_tail_bwd(res, gs):
    h2, W, logits, m, se, targets, wgt = res
    B, T, H = h2.shape
    V = logits.shape[-1]
    dt = h2.dtype
    M = B * T
    lf = logits.reshape(M, V)
    mf, sef, tf = m.reshape(M), se.reshape(M), targets.reshape(M)
    gsf = jnp.asarray(gs, jnp.float32)
    # per-row cotangent scale: gs * row-weight / sumexp feeds the softmax
    # terms; gs * row-weight scales the one-hot terms
    wf = jnp.broadcast_to(wgt[None, :], (B, T)).reshape(M)
    gw = gsf * wf
    Wd = W.astype(dt)

    # dx softmax term. On TPU this is the hand-written pallas kernel
    # (ops/pallas/head_dx.py): softmax computed in-kernel from natural-
    # layout logits tiles, tile-dots against a pre-transposed W with an
    # fp32 VMEM accumulator. XLA-level alternatives all lose (r5 ledger):
    # autodiff's orientation runs the dot at ~60-77% of peak, and every
    # transpose-orientation rewrite forces a >=1.4 GB materialisation
    # (the algebraic simplifier folds dot^T back, and a transposing
    # consumer cannot fuse the convert chain) that outweighs the win.
    from .. import flags
    from ..ops.pallas.flash_attention import _on_tpu
    from ..ops.pallas.head_dx import head_dx_softmax

    use_kernel = (
        _on_tpu()
        and flags.get_flags("use_pallas_kernels")["use_pallas_kernels"])
    if use_kernel:
        dh_soft = head_dx_softmax(lf, mf, gw / sef, Wd.T)
    else:
        p = (jnp.exp(lf.astype(jnp.float32) - mf[:, None])
             * (gw / sef)[:, None]).astype(dt)
        dh_soft = p @ Wd.T
    gold_rows = (jnp.take(Wd, tf, axis=1).T.astype(jnp.float32)
                 * gw[:, None]).astype(dt)                # [M, H]
    dh = (dh_soft - gold_rows).reshape(B, T, H)

    # dW: autodiff's wide-N orientation; one-hot as an in-tile iota mask
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (M, V), 1)
              == tf[:, None])
    dlog = ((jnp.exp(lf.astype(jnp.float32) - mf[:, None]) / sef[:, None]
             - onehot.astype(jnp.float32)) * gw[:, None]).astype(dt)
    dW = jax.lax.dot_general(h2.reshape(M, H), dlog,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32
                             ).astype(W.dtype)            # [H, V]
    return dh, dW, None, None


_head_ce_tail.defvjp(_head_ce_tail_fwd, _head_ce_tail_bwd)


def loss_fn(params, tokens, labels, cfg: LlamaConfig) -> jax.Array:
    """Next-token cross entropy (the reference's ``ParallelCrossEntropy`` /
    ``c_softmax_with_cross_entropy`` — here the vocab-sharded logsumexp
    reduction is a GSPMD-inserted collective).

    Single-chip, the head+CE is chunked over the batch dim with the chunk
    body ``jax.checkpoint``-ed: the [B,S,V] logits tensor (1.5 GB at the
    bench shape) is never materialised and never saved for the backward —
    each chunk's logits are recomputed from the (small) hidden states in
    the bwd, trading ~1.2 TF of recompute for ~5 passes of HBM traffic
    (measured worth ~4 ms/step at bert-base batch 48). Multi-device meshes
    keep the unchunked form: GSPMD owns the vocab-parallel layout there.

    ``labels`` is the same [B, S] token stream; the shift happens HERE:
    position i's logits are scored against labels[i+1]."""
    return _head_ce(params, forward_hidden(params, tokens, cfg), labels, cfg)


@scoped("head_ce")
def _head_ce(params, h, labels, cfg: LlamaConfig) -> jax.Array:
    """``loss_fn``'s tail: lm_head matmul + mean next-token nll over the
    final hidden states ``h`` [B, S, H] (the three forms its docstring
    describes)."""
    dt = cfg.dtype
    B, S, _ = h.shape
    nc = cfg.ce_chunks
    from ..parallel.mesh import get_mesh

    mesh = get_mesh()
    multi = mesh is not None and mesh.size > 1
    if nc and not multi and B % nc == 0:
        W = params["lm_head"].astype(dt)
        # pad the shifted targets so every position has a label; the pad
        # column carries weight 0 (exactly the reference's shift+mean)
        targets = jnp.concatenate(
            [labels[:, 1:], jnp.zeros((B, 1), labels.dtype)], axis=1)
        wgt = jnp.concatenate(
            [jnp.ones((S - 1,), jnp.float32), jnp.zeros((1,), jnp.float32)])
        hc = h.reshape(nc, B // nc, S, h.shape[-1])
        tc = targets.reshape(nc, B // nc, S)
        logit_bar = ("logits" in cfg.bwd_barriers)
        body = jax.checkpoint(
            lambda hcb, tcb: _nll_sum(
                _barrier_grad(hcb @ W) if logit_bar else hcb @ W,
                tcb, wgt[None, :]))
        total = jnp.float32(0.0)
        for i in range(nc):
            total = total + body(hc[i], tc[i])
        return total / (B * (S - 1))
    if cfg.ce_tail_custom and not multi:
        # custom-VJP tail: same forward math, hand-scheduled backward
        # (see _head_ce_tail) — single-chip only (the mesh path needs
        # the wsc sharding constraint + GSPMD's vocab-sharded CE). ALL S
        # positions are scored with weight 0 on the last: B*S is a
        # multiple of the pallas dx block, so the kernel sees no ragged
        # edge (a padded-copy of the logits costs 6.7 ms — r5 ledger).
        targets = jnp.concatenate(
            [labels[:, 1:], jnp.zeros((B, 1), labels.dtype)], axis=1)
        wgt = jnp.ones((S,), jnp.float32).at[-1].set(0.0)
        total = _head_ce_tail(h, params["lm_head"], targets, wgt)
        return total / (B * (S - 1))
    # slice h BEFORE the head matmul: slicing the [B,S,V] product instead
    # would materialise a second ~1.5 GB logits copy (the last position
    # has no next-token label and needn't be scored at all)
    logits = wsc(h[:, :-1] @ params["lm_head"].astype(dt),
                 P(("dp", "sharding"), None, "mp"))
    if "logits" in cfg.bwd_barriers:
        logits = _barrier_grad(logits)
    targets = labels[:, 1:]
    return _nll_sum(logits, targets, jnp.float32(1.0)) / (B * (S - 1))


# ---------------------------------------------------------------------------
# Training step (AdamW, fp32 master weights, ZeRO via sharding specs)
# ---------------------------------------------------------------------------

def init_opt_state(params):
    zeros = lambda p: jnp.zeros_like(p)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
    }


NO_DECAY_KEYS = ("ln_attn", "ln_mlp", "ln_f", "embed")

# per-layer stacked weights (leading [L] axis) — the one list both the
# training forward and the KV-cache decode path slice from
_LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "ln_attn", "ln_mlp")
_LAYER_KEYS_FUSED = ("wqkv", "wo", "w_gate_up", "w_down",
                     "ln_attn", "ln_mlp")


def layer_keys(cfg: LlamaConfig):
    return _LAYER_KEYS_FUSED if cfg.fused_weights else _LAYER_KEYS


def adamw_update(params, grads, opt_state, lr=3e-4, beta1=0.9, beta2=0.95,
                 eps=1e-8, weight_decay=0.1, no_decay_keys=None):
    """Fused-AdamW analog: one jitted tree-wide update (the reference's
    multi-tensor fused_adamw kernel; XLA fuses the per-leaf lambdas).
    Norm gains and the embedding are excluded from decay (the reference's
    ``apply_decay_param_fun`` convention); callers with different naming
    (e.g. models/bert.py) pass their own ``no_decay_keys``."""
    step = opt_state["step"] + 1
    t = step.astype(jnp.float32)
    c1 = 1.0 - jnp.power(beta1, t)
    c2 = 1.0 - jnp.power(beta2, t)

    def upd(wd, p, g, m, v):
        g = g.astype(jnp.float32)
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * (g * g)
        update = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p
        return p - lr * update, m, v

    nd = NO_DECAY_KEYS if no_decay_keys is None else no_decay_keys
    wds = {k: 0.0 if k in nd else weight_decay for k in params}
    out = jax.tree.map(upd, wds, params, grads, opt_state["m"],
                       opt_state["v"])
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
    return new_p, {"step": step, "m": new_m, "v": new_v}


def train_step(params, opt_state, tokens, labels, cfg: LlamaConfig,
               lr=3e-4):
    """One full step: fwd, bwd, global-norm clip, AdamW. Pure → jit it."""
    with jax.named_scope("loss"):       # forward and backward
        diff = params
        if cfg.bf16_grads:
            # differentiate w.r.t. the bf16 view: the fwd is numerically
            # IDENTICAL (every use site casts to cfg.dtype anyway) but the
            # cotangents stay bf16 — no [params]-sized fp32 convert pass
            diff = jax.tree.map(lambda p: p.astype(cfg.dtype)
                                if p.dtype == jnp.float32 else p, params)
        loss, grads = jax.value_and_grad(loss_fn)(diff, tokens, labels, cfg)
    with jax.named_scope("grad_clip"):
        # HybridParallelClipGrad analog: global norm across ALL parallel
        # axes (GSPMD reduces over every mesh axis for free)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, 1.0 / (gnorm + 1e-6))
        # keep each leaf's dtype: a strong fp32 scalar would PROMOTE bf16
        # grads to fp32 (defeating bf16_grads' traffic contract)
        grads = jax.tree.map(lambda g: g * clip.astype(g.dtype), grads)
    with jax.named_scope("optimizer"):
        params, opt_state = adamw_update(params, grads, opt_state, lr=lr)
    return params, opt_state, loss


def shard_state(cfg: LlamaConfig, mesh, params, opt_state=None):
    """device_put params (and opt state) to their canonical hybrid shardings
    (the reference's `shard_tensor`/placement step). Needed whenever arrays
    are already committed to devices with a different layout."""
    from jax.sharding import NamedSharding

    ps = {k: NamedSharding(mesh, v) for k, v in param_specs(cfg).items()}
    params = jax.device_put(params, ps)
    if opt_state is None:
        return params
    os_ = {k: NamedSharding(mesh, v) for k, v in opt_state_specs(cfg).items()}
    opt_state = {
        "step": jax.device_put(opt_state["step"], NamedSharding(mesh, P())),
        "m": jax.device_put(opt_state["m"], os_),
        "v": jax.device_put(opt_state["v"], os_),
    }
    return params, opt_state


def make_sharded_train_step(cfg: LlamaConfig, mesh, lr=3e-4):
    """jit the train step over ``mesh`` with the full hybrid shardings and
    donated param/opt buffers (in-place update semantics, TPU-style)."""
    from jax.sharding import NamedSharding

    ps = {k: NamedSharding(mesh, v) for k, v in param_specs(cfg).items()}
    os_spec = {k: NamedSharding(mesh, v) for k, v in opt_state_specs(cfg).items()}
    opt_sh = {"step": NamedSharding(mesh, P()), "m": os_spec, "v": os_spec}
    data_sh = NamedSharding(mesh, P(("dp", "sharding"), None))

    step = functools.partial(train_step, cfg=cfg, lr=lr)
    # a partial has no name of its own; this one is the program's name in
    # a device trace (jit_train_step)
    step.__name__ = "train_step"
    return jax.jit(
        step,
        in_shardings=(ps, opt_sh, data_sh, data_sh),
        out_shardings=(ps, opt_sh, NamedSharding(mesh, P())),
        donate_argnums=(0, 1),
    )


# ---------------------------------------------------------------------------
# KV-cache autoregressive decoding (inference). Reference: PaddleNLP's
# generation loop over the fused decode-attention kernels (SURVEY.md §2.4);
# here prefill and per-token decode are each ONE jitted program with the
# cache donated between steps, and the decode attention masks the padded
# cache tail instead of re-running the whole prefix.
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jax.Array]:
    """Per-layer stacked K/V cache: [L, B, max_len, Hkv, D]."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_pool_spec() -> P:
    """PartitionSpec of the paged KV pool [L, pages, page, Hkv*D] under
    tensor-parallel serving (r12): pages replicate, heads shard (a
    head's D lanes are contiguous in the flat minor dim, so splitting it
    over 'mp' splits whole kv heads). The kv-head dim follows wk/wv's
    column-parallel output sharding, so a tick's new K/V rows scatter
    into LOCAL shards and attention contracts per-shard — GSPMD inserts
    exactly one all-reduce per layer (after the row-parallel wo), none
    for the pool itself — and the host-side page tables (pure int32
    indices) stay replicated: page bookkeeping is unchanged under
    'mp'."""
    return P(None, None, None, "mp")


@scoped("attention")
def _cache_attention(cfg: LlamaConfig, q, kc, vc, positions):
    """q [B,T,nH,D] against the UNREPEATED cache kc/vc [B,Smax,Hkv,D].
    GQA contracts via a grouped einsum (q reshaped [B,T,Hkv,rep,D]) —
    the repeated cache is never materialised. Keys j > token position are
    masked (covers both causality and the unwritten cache tail).
    ``positions``: [T] shared, or [B, T] ragged (per-slot decode).

    Single-token decode (T=1) dispatches to the RAGGED Pallas kernel when
    shapes tile: each slot reads only ceil((pos+1)/block) KV blocks from
    HBM instead of the full static max_len window — the dense einsum
    below streams max_len rows per slot regardless of position, which at
    serving shapes is most of the tick's non-weight HBM traffic."""
    B, T, nH, D = q.shape
    if T == 1:
        from ..ops.pallas.decode_attention import (
            decode_attention_active, ragged_decode_attention)

        if decode_attention_active(kc.shape[1], cfg.num_heads,
                                   cfg.num_kv_heads, cfg.head_dim):
            pos_b = jnp.broadcast_to(
                jnp.reshape(jnp.asarray(positions)[..., 0], (-1,)),
                (B,)).astype(jnp.int32)
            return ragged_decode_attention(q[:, 0], kc, vc, pos_b)[:, None]
    return _dense_cache_attention(cfg, q, kc, vc, positions)


def _dense_cache_attention(cfg: LlamaConfig, q, kc, vc, positions):
    """The dense XLA formulation of cache attention (the dispatch
    fallback, shared by the contiguous and paged-gather paths)."""
    B, T, nH, D = q.shape
    Smax = kc.shape[1]
    rep = cfg.num_heads // cfg.num_kv_heads
    dt = q.dtype
    scale = 1.0 / np.sqrt(cfg.head_dim)
    qg = q.reshape(B, T, cfg.num_kv_heads, rep, D)
    s = jnp.einsum("bthrd,bshd->bhrts", qg, kc,
                   preferred_element_type=jnp.float32) * scale
    visible = jnp.arange(Smax) <= positions[..., None]  # [(B,) T, Smax]
    if visible.ndim == 2:
        visible = visible[None]
    s = jnp.where(visible[:, None, None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    attn = jnp.einsum("bhrts,bshd->bthrd", probs.astype(dt), vc,
                      preferred_element_type=jnp.float32).astype(dt)
    return attn.reshape(B, T, nH, D)


def _tick_fused_active(cfg: LlamaConfig) -> bool:
    """Does this decode tick use the fused Pallas epilogue kernels?"""
    if not cfg.fused_tick_epilogue:
        return False
    from ..ops.pallas.tick_fusion import tick_fusion_active

    return (tick_fusion_active(cfg.hidden_size)
            and cfg.head_dim % 8 == 0 and cfg.head_dim % 2 == 0)


@scoped("qkv")
def _rows_qkv(cfg: LlamaConfig, x, lp, positions):
    """``_qkv_proj`` over flat rows, for the paths whose fused kernels are
    active (``_tick_fused_active``): x [B, T, H] is B*T rows, each at its
    own ``positions[b, t]`` — a tick's slots, an admission's or a chunk's
    positions alike. The rmsnorm chain is one Pallas op and the q/k rope
    chains (cos/sin/slice/concat per head, twice) collapse into one
    shared-cos/sin kernel. Same math — the projections themselves stay
    XLA dots (they carry the weight stream the tick is roofline-bound
    on), and with no rope chain for XLA to fuse into them the stacked
    ``wq`` / ``wk`` are read in the layout they lie in at every T: a
    segment program whose admit arm ropes in XLA and whose decode arm
    ropes here copies both stacks whole, once a step (PR 35)."""
    from ..ops.pallas.tick_fusion import fused_rms_norm, fused_rope_qk

    B, T, H = x.shape
    rows = B * T
    dt = x.dtype
    h = fused_rms_norm(x.reshape(rows, H), lp["ln_attn"], cfg.rms_eps)
    if T > 1:
        # 3-D for ``_mm``: of narrow weights only a tick's few rows go to
        # quant_matmul (one block holds them all), an admission's take
        # the dense dequantize, as they did through ``_qkv_proj``
        h = h.reshape(B, T, H)
    zq, zk, zv = _qkv_dots(cfg, h, lp, dt)
    zq, zk = fused_rope_qk(zq.reshape(rows, -1), zk.reshape(rows, -1),
                           positions.reshape(rows), cfg.head_dim,
                           cfg.rope_theta)
    q = zq.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = zk.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = zv.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


@scoped("post")
def _decode_post(cfg: LlamaConfig, x, attn, lp):
    """T=1 fused-tick variant of ``_layer_post``: the attention-residual
    add and the mlp pre-norm are ONE kernel emitting both the new
    residual stream and the normed value (single-device path — no wsc)."""
    from ..ops.pallas.tick_fusion import fused_add_rms_norm

    B, _, H = x.shape
    dt = x.dtype
    o = _mm(attn.reshape(B, H), lp, "wo", dt)
    x2, h = fused_add_rms_norm(x[:, 0], o, lp["ln_mlp"], cfg.rms_eps)
    if cfg.fused_weights:
        F_ = cfg.intermediate_size
        zz = _mm(h, lp, "w_gate_up", dt)
        zg, up = zz[..., :F_], zz[..., F_:]
    else:
        zg = _mm(h, lp, "w_gate", dt)
        up = _mm(h, lp, "w_up", dt)
    x3 = x2 + _mm(jax.nn.silu(zg) * up, lp, "w_down", dt)
    return x3[:, None]


def forward_with_cache(params, tokens, cfg: LlamaConfig, cache, pos,
                       logit_pos=None):
    """Run ``tokens`` [B, T] at absolute positions pos..pos+T-1 against the
    cache. Returns (logits [B, V], updated cache). T is the prompt length
    for prefill and 1 for decode; ``pos`` may be a traced scalar, or a
    traced [B] vector (ragged decode, T==1: every slot writes and attends
    at its OWN position — the continuous-batching engine's path). Logits
    come from the last position, or from ``logit_pos`` (traced scalar —
    bucket-padded prompts read the true last token). Layers run under
    lax.scan over the stacked [L, ...] weights and cache — O(1) compile
    depth, matching the training path's scan_layers design."""
    dt = cfg.dtype
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    ragged = getattr(pos, "ndim", 0) == 1
    if ragged and T != 1:
        raise ValueError("per-slot pos requires single-token decode (T=1)")
    positions = pos[:, None] if ragged else pos + jnp.arange(T)
    layer_weights = layer_params(params, cfg)

    # fused tick epilogue: single-token decode collapses each
    # between-matmul small-op chain into one Pallas op (dispatch-gated;
    # prefill T>1 and CPU keep the inline jnp chains — same math)
    fused_tick = T == 1 and _tick_fused_active(cfg)
    if fused_tick:
        pos_b = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(positions)[..., 0], (-1,)),
            (B,)).astype(jnp.int32)

    def _qkv(x, lp):
        return (_rows_qkv(cfg, x, lp, pos_b) if fused_tick
                else _qkv_proj(cfg, x, lp, positions))

    def _post(x, attn, lp):
        return (_decode_post(cfg, x, attn, lp) if fused_tick
                else _layer_post(cfg, x, attn, lp))

    def body(x, per_layer):
        lp, kc, vc = per_layer
        q, k_new, v_new = _qkv(x, lp)
        with jax.named_scope("kv_write"):
            if ragged:
                # scatter each slot's new row at its own position
                rows = jnp.arange(B)
                kc = kc.at[rows, pos].set(k_new[:, 0].astype(kc.dtype))
                vc = vc.at[rows, pos].set(v_new[:, 0].astype(vc.dtype))
            else:
                kc = jax.lax.dynamic_update_slice(
                    kc, k_new.astype(kc.dtype), (0, pos, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    vc, v_new.astype(vc.dtype), (0, pos, 0, 0))
        attn = _cache_attention(cfg, q, kc, vc, positions)
        return _post(x, attn, lp), (kc, vc)

    if cfg.scan_layers:
        x, (kcs, vcs) = jax.lax.scan(body, x,
                                     (layer_weights, cache["k"], cache["v"]))
    else:
        # Unrolled layers (scan_layers=False): write the new K/V rows at a
        # STATIC layer index directly into the stacked cache buffers. The
        # layer-scan path must slice layer l's [B,S,Hkv,D] cache out of
        # the stacked xs and re-stack the updated copy into ys EVERY
        # layer — on the decode tick that is 4 full cache copies per
        # layer (~360 us/tick at the serving bench shape, measured in
        # benchmarks/decode_profile.py, vs ~0 for the in-place row DUS
        # here). Prefill/decode programs donate the cache, so these
        # updates happen in place.
        kcs, vcs = cache["k"], cache["v"]
        for i in range(cfg.num_layers):
            lp = {kk: layer_weights[kk][i] for kk in layer_weights}
            q, k_new, v_new = _qkv(x, lp)
            with jax.named_scope("kv_write"):
                if ragged:
                    rows = jnp.arange(B)
                    kcs = kcs.at[i, rows, pos].set(
                        k_new[:, 0].astype(kcs.dtype))
                    vcs = vcs.at[i, rows, pos].set(
                        v_new[:, 0].astype(vcs.dtype))
                else:
                    kcs = jax.lax.dynamic_update_slice(
                        kcs, k_new[None].astype(kcs.dtype),
                        (i, 0, pos, 0, 0))
                    vcs = jax.lax.dynamic_update_slice(
                        vcs, v_new[None].astype(vcs.dtype),
                        (i, 0, pos, 0, 0))
            attn = _cache_attention(cfg, q, kcs[i], vcs[i], positions)
            x = _post(x, attn, lp)
    logits = _head_logits(cfg, params, x, fused_tick, logit_pos)
    return logits, {"k": kcs, "v": vcs}


@scoped("head")
def _head_logits(cfg: LlamaConfig, params, x, fused_tick: bool,
                 logit_pos=None, logits_all: bool = False):
    """The decode paths' head: final norm, then the lm_head matmul on the
    last position's row — or the row at ``logit_pos`` (traced scalar, or
    [B] per row), or EVERY position with ``logits_all``. fp32 logits."""
    dt = cfg.dtype
    B = x.shape[0]
    if fused_tick:
        from ..ops.pallas.tick_fusion import fused_rms_norm

        x = fused_rms_norm(x[:, 0], params["ln_f"], cfg.rms_eps)[:, None]
    else:
        x = _rms_norm(x, params["ln_f"], cfg.rms_eps)
    if logits_all:
        return _mm(x, params, "lm_head", dt).astype(jnp.float32)  # [B,T,V]
    if logit_pos is None:
        last = x[:, -1]
    elif getattr(logit_pos, "ndim", 0) == 1:
        last = x[jnp.arange(B), logit_pos]  # per-row (batched prefill)
    else:
        last = jax.lax.dynamic_index_in_dim(x, logit_pos, axis=1,
                                            keepdims=False)
    return _mm(last, params, "lm_head", dt).astype(jnp.float32)  # [B, V]


@scoped("attention")
def _paged_attention(cfg: LlamaConfig, q, planes, layer, page_table,
                     positions, q_len=None):
    """Attention over layer ``layer`` of a paged KV pool. q [B,T,nH,D];
    ``planes``: the WHOLE pool as it lies ({"k","v"} [L, P, page_size,
    Hkv*D], plus a quantized pool's fp32 scale planes "ks"/"vs" [L, P,
    page_size]); ``layer``: int32 scalar, static or traced;
    page_table [B, max_pages]; ``positions`` [B, T] absolute query
    positions (row t of slot b at ``positions[b, t]``, keys [0,
    positions[b, t]] visible); ``q_len`` ([B] int32, optional): rows of
    each slot whose output the caller keeps, 0 for a slot that is not
    live. Dispatches to the unified page-indirect Pallas kernel when the
    shape tiles: the kernel indexes the pool by (layer, page) and copies
    only the pages a slot holds (none where ``q_len`` is 0), so per-slot
    KV reads scale with position and no layer is ever sliced out of the
    pool. The fallback (CPU/tier-1, the
    quantized pool, meshes) gathers ``pool[layer, page_table]`` — the
    slot's pages — and reshapes the GATHERED window for the dense
    formulation, identical math; a quantized pool's scale rows are
    fetched with their pages and the [B, W] window dequantized before
    the contraction (so HBM→gather traffic carried the narrow dtype)."""
    from ..ops.pallas.paged_attention import (paged_attention_active,
                                              ragged_paged_attention)

    B, T = q.shape[:2]
    kp, vp = planes["k"], planes["v"]
    psz = kp.shape[2]
    if "ks" not in planes and paged_attention_active(
            psz, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim):
        return ragged_paged_attention(q, kp, vp, page_table,
                                      positions[:, 0], q_len, layer=layer)
    dt = q.dtype
    gk = kp[layer, page_table]               # [B, max_pages, psz, Hkv*D]
    gv = vp[layer, page_table]
    if "ks" in planes:
        gk = gk.astype(dt) * planes["ks"][layer, page_table][
            ..., None].astype(dt)
        gv = gv.astype(dt) * planes["vs"][layer, page_table][
            ..., None].astype(dt)
    window = (B, page_table.shape[1] * psz, cfg.num_kv_heads, cfg.head_dim)
    return _dense_cache_attention(cfg, q, gk.reshape(window),
                                  gv.reshape(window), positions)


def forward_with_pages(params, tokens, cfg: LlamaConfig, pool, page_table,
                       pos, live=None, logit_pos=None, logits_all=False):
    """``forward_with_cache`` over a PAGED KV pool (inference/paged_kv).

    tokens [B, T] run at absolute positions ``pos[b] .. pos[b]+T-1``
    per row (``pos``: [B] int32 — every slot at its OWN base position:
    T == 1 is a ragged decode tick, T > 1 a prefill chunk at context
    offset ``pos[b]``). ``pool``: {"k","v"} [L, num_pages, page_size,
    Hkv*D] flat page pools (``init_paged_pool``); ``page_table``:
    [B, max_pages] int32 — virtual page slot j of row b is physical
    page ``page_table[b, j]``.

    The pool is ONE buffer per plane all the way through: the layer
    loop (a rolled ``lax.scan`` over the stacked weights and the layer
    index, or the unrolled loop of ``scan_layers=False``) CARRIES the
    planes, each layer scatters its new K/V rows in place at
    ``[layer, phys, prow]`` and attention reads the pool where it lies
    (``_paged_attention``). No layer's pool is sliced out, stacked back
    or reshaped, so a program that donates the pool holds it once.
    ``live`` ([B] bool, optional) routes retired slots' writes to the
    reserved trash page 0 instead (a frozen slot must never write a
    page the allocator may have handed to someone else), as do
    positions past the table; the paged kernel fetches no page for such
    a slot, and its logits mean nothing. Returns (logits [B, V], updated
    pool) — or, with ``logits_all=True``, logits at EVERY query position
    ([B, T, V]): the speculative verify tick scores all K+1 drafted
    positions from the same single weight stream (SCALING §3j), so the
    lm_head matmul runs over the whole chunk instead of one gathered
    row."""
    dt = cfg.dtype
    B, T = tokens.shape
    psz = pool["k"].shape[2]
    max_pages = page_table.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    # r23 (ISSUE 18): sequence-parallel prefill slabs arrive with the
    # slab's ROW axis as the batch axis ([sp, C] — one C-token chunk of
    # the same prompt per row). When the live mesh carries an 'sp' axis
    # that divides B, hint GSPMD to shard the batch dim over it so the
    # per-layer QKV/MLP matmuls of an sp-slab run 1/sp-sized per device;
    # the paged gather in _paged_attention then reads cross-shard rows
    # through the (replicated) pool, which GSPMD serves with the same
    # neighbour exchanges the ring formulation hand-codes (see
    # ops/pallas/ring_attention.sp_slab_ring_attention for the manual
    # twin). On CPU/no-mesh (every test) this is a literal no-op, keeping
    # the bit-exact gather path.
    from ..parallel.mesh import get_mesh, with_sharding_constraint
    from jax.sharding import PartitionSpec as _P

    _mesh = get_mesh()
    if (_mesh is not None and "sp" in _mesh.axis_names
            and int(_mesh.shape["sp"]) > 1
            and B % int(_mesh.shape["sp"]) == 0):
        x = with_sharding_constraint(x, _P("sp", None, None), _mesh)
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    positions = pos[:, None] + jnp.arange(T)            # [B, T]
    # destination coordinates for the chunk's K/V rows — shared by all
    # layers (virtual page -> physical page via the table; dead slots
    # and rows past the table land in trash page 0)
    vpage = positions // psz
    prow = positions % psz
    phys = jnp.take_along_axis(page_table,
                               jnp.minimum(vpage, max_pages - 1), axis=1)
    writable = vpage < max_pages
    if live is not None:
        writable = writable & live[:, None]
    phys = jnp.where(writable, phys, 0)
    layer_weights = layer_params(params, cfg)

    # quantized pool: K/V pages carry a narrow dtype plus per-page fp32
    # scale planes (one scale per cache row — see init_paged_pool); new
    # rows quantize at write time and their scales land at the SAME
    # [layer, phys, prow] coordinates, so trash-page routing, COW and
    # spill stay dtype-oblivious
    quant = "ks" in pool
    if quant:
        from ..quantization.serving import quantize_kv_rows

    # ONE qkv formulation for a tick and an admission alike, so that the
    # segment program's two arms read wq / wk in one layout
    fused = _tick_fused_active(cfg)
    qkv = _rows_qkv if fused else _qkv_proj
    fused_tick = fused and T == 1
    # a retired slot's output is dropped by every caller: the paged
    # kernel fetches none of its pages
    q_len = None if live is None else jnp.where(live, T, 0)

    def layer(x, planes, lp, i):
        q, k_new, v_new = qkv(cfg, x, lp, positions)
        with jax.named_scope("kv_write"):
            rows = {"k": k_new, "v": v_new}
            if quant:
                for n in ("k", "v"):
                    rows[n], rows[n + "s"] = quantize_kv_rows(
                        rows[n], planes[n].dtype)
            # [B, T, Hkv, D] rows land as [B, T, Hkv*D], scales as [B, T]
            planes = {n: a.at[i, phys, prow].set(
                rows[n].reshape((B, T) + a.shape[3:]).astype(a.dtype))
                for n, a in planes.items()}
        attn = _paged_attention(cfg, q, planes, i, page_table, positions,
                                q_len)
        return (_decode_post(cfg, x, attn, lp) if fused_tick
                else _layer_post(cfg, x, attn, lp)), planes

    planes = dict(pool)
    if cfg.scan_layers:
        # the planes ride the CARRY (never xs/ys: that slices a layer's
        # pool out and stacks a copy back, per layer); xs are the
        # stacked weights and the layer's index
        (x, planes), _ = jax.lax.scan(
            lambda c, xs: (layer(*c, *xs), None), (x, planes),
            (layer_weights, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    else:
        for i in range(cfg.num_layers):
            lp = {kk: layer_weights[kk][i] for kk in layer_weights}
            x, planes = layer(x, planes, lp, i)
    return (_head_logits(cfg, params, x, fused_tick, logit_pos, logits_all),
            planes)


def init_paged_pool(cfg: LlamaConfig, num_pages: int, page_size: int,
                    dtype=None, quant=None) -> Dict[str, jax.Array]:
    """Flat paged K/V pool: [L, num_pages, page_size, Hkv*D] — a cache
    row's kv heads side by side in the minor dimension (head h at lanes
    [h*D, (h+1)*D)), which is the block the paged kernel reads, so the
    pool is never re-tiled between a write and a read. Page 0 is the
    allocator's reserved trash page (see inference/paged_kv.py).

    ``quant`` ('int8' | 'fp8'): K/V pages store the narrow dtype and the
    pool carries per-page fp32 scale planes ``ks``/``vs``
    [L, num_pages, page_size] — one scale per cache row, keyed by
    physical page id so every page-granular mechanism (COW copies,
    refcounts, host-tier spill, fleet migration) moves scales with
    their pages without knowing the dtype."""
    if quant is not None:
        from ..quantization.serving import quant_dtype

        dtype = quant_dtype(quant)
    else:
        dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, num_pages, page_size,
             cfg.num_kv_heads * cfg.head_dim)
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if quant is not None:
        sshape = (cfg.num_layers, num_pages, page_size)
        pool["ks"] = jnp.zeros(sshape, jnp.float32)
        pool["vs"] = jnp.zeros(sshape, jnp.float32)
    return pool


def page_bytes(cfg: LlamaConfig, page_size: int, quant=None) -> int:
    """Bytes one pool page occupies across all layers: the K and V planes
    [L, page_size, Hkv*D], plus the fp32 ``ks``/``vs`` scale rows of a
    quantized pool."""
    if quant is not None:
        from ..quantization.serving import quant_dtype

        itemsize = jnp.dtype(quant_dtype(quant)).itemsize
    else:
        itemsize = jnp.dtype(cfg.dtype).itemsize
    kv = 2 * cfg.num_layers * page_size * cfg.num_kv_heads * cfg.head_dim \
        * itemsize
    return kv + (2 * cfg.num_layers * page_size * 4 if quant else 0)


def paged_kernel_active(cfg: LlamaConfig, page_size: int) -> bool:
    """True when attention over this model's pool routes to the unified
    page-indirect Pallas kernel."""
    from ..ops.pallas.paged_attention import paged_attention_active

    return paged_attention_active(page_size, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.head_dim)


# every serving family the engine has serves this model (the model seam:
# ``models.require`` refuses a family a model's module does not list)
SERVING_FAMILIES = ("paged", "mesh", "chunked prefill",
                    "sequence-parallel prefill", "speculative",
                    "quality digest", "quantized pool", "prefix cache",
                    "host tier", "disaggregated serving")


def prompt_kv(params, prompt, cfg: LlamaConfig,
              max_len: Optional[int] = None):
    """KV rows for a prompt, standalone: the prefix-cache registration
    path (inference/prefix_cache.py) and its parity tests. Returns
    ({"k","v"} [L, B, S_pad, Hkv, D], logits [B, V]) where S_pad =
    ``max_len or S`` — rows past S are zeros. Rope is position-dependent,
    so these rows are reusable by ANY request whose prompt starts with
    ``prompt`` (the keys live at the same absolute positions)."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim == 1:
        prompt = prompt[None]
    B, S = prompt.shape
    cache = init_kv_cache(cfg, B, max_len or S)
    logits, cache = forward_with_cache(params, prompt, cfg, cache,
                                       jnp.int32(0))
    return cache, logits


def sample_filter_logits(logits, temperature, top_k=0, top_p=1.0):
    """Temperature/top-k/top-p filtered logits over the LAST dim (any
    leading dims): tokens outside the kept support are -inf, so the
    sampling distribution is exactly ``softmax(result)``. Shared by
    ``generate``'s per-step sampler, the serving engine's in-program
    samplers (including the speculative verify tick's [slots, K+1, V]
    batch), and the numpy-reference property tests. ``temperature`` must
    be > 0 — greedy (temperature 0) is the caller's static argmax
    branch."""
    logits = logits / temperature
    if top_k:
        k = min(int(top_k), logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., k - 1:k]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        # nucleus sampling: keep the smallest prefix of the sorted probs
        # whose mass reaches top_p (the first token always survives)
        sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p              # mass BEFORE this token
        keep = keep.at[..., 0].set(True)        # the top token always survives
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@scoped("sample")
def _sample(logits, temperature, top_k, key, top_p=1.0):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = sample_filter_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def generate(params, prompt, cfg: LlamaConfig, max_new_tokens: int = 32,
             max_len: Optional[int] = None, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 1.0, seed: int = 0) -> jax.Array:
    """Autoregressive generation: greedy at temperature 0, otherwise
    temperature sampling with optional top-k and/or nucleus (top-p)
    filtering. Returns [B, max_new_tokens] int32.

    Prefill is one jitted program; every decode token is one jitted step
    with the cache DONATED (in-place on device). Sampling and the position
    counter live INSIDE the step, so the host loop only threads device
    references — no per-token host->device transfers or syncs.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    B, S = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    # the last sampled token is returned but never written back to the
    # cache, so S + max_new_tokens - 1 slots suffice
    max_len = max_len or min(cfg.max_seq_len, S + max_new_tokens - 1)
    if S + max_new_tokens - 1 > max_len:
        raise ValueError(f"prompt ({S}) + max_new_tokens ({max_new_tokens}) "
                         f"needs {S + max_new_tokens - 1} cache slots but "
                         f"max_len is {max_len}")
    prefill = _prefill_program(cfg, max_len, float(temperature), int(top_k),
                               float(top_p))
    cache, nxt, pos, key = prefill(params, prompt, jax.random.PRNGKey(seed))
    if max_new_tokens == 1:
        return nxt[:, None]
    decode_all = _decode_program(cfg, max_new_tokens, float(temperature),
                                 int(top_k), float(top_p))
    toks, _ = decode_all(params, cache, nxt, pos, key)
    return jnp.concatenate([nxt[:, None], toks.T], axis=1)


# Compiled-program factories, cached SEPARATELY: varying prompt lengths
# re-specialise only prefill (through jit's own shape cache) while ONE
# decode program serves them all. NOTE: on the default path max_len is
# derived from S + max_new_tokens - 1, which couples BOTH programs to the
# request sizes — serving loops should pass a fixed max_len so the cache
# shape (and with it every compiled program) stays stable. The KV cache
# is allocated INSIDE prefill (on device from the start; decode then
# donates it cleanly).

@functools.lru_cache(maxsize=32)
def _prefill_program(cfg: LlamaConfig, max_len: int, temperature: float,
                     top_k: int, top_p: float = 1.0):
    @jax.jit
    def prefill(params, prompt, key):
        cache = init_kv_cache(cfg, prompt.shape[0], max_len)
        logits, cache = forward_with_cache(params, prompt, cfg, cache,
                                           jnp.int32(0))
        key, sub = jax.random.split(key)
        nxt = _sample(logits, temperature, top_k, sub, top_p)
        return cache, nxt, jnp.int32(prompt.shape[1]), key

    return prefill


@functools.lru_cache(maxsize=32)
def _decode_program(cfg: LlamaConfig, max_new_tokens: int,
                    temperature: float, top_k: int, top_p: float = 1.0):
    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_all(params, cache, nxt, pos, key):
        # the whole decode loop is ONE compiled program (lax.scan): zero
        # host round-trips per token — the TPU-native replacement for the
        # reference's per-token python generation loop
        def body(carry, _):
            cache, nxt, pos, key = carry
            logits, cache = forward_with_cache(params, nxt[:, None], cfg,
                                               cache, pos)
            key, sub = jax.random.split(key)
            nxt = _sample(logits, temperature, top_k, sub, top_p)
            return (cache, nxt, pos + 1, key), nxt

        (cache, *_), toks = jax.lax.scan(
            body, (cache, nxt, pos, key), None, length=max_new_tokens - 1)
        # returning the final cache gives the donated input an aliasing
        # target (in-place update, no copy, no donation warning); callers
        # discard it
        return toks, cache  # toks: [T-1, B]

    return decode_all


# ---------------------------------------------------------------------------
# Beam search (reference: PaddleNLP generate(decode_strategy="beam_search")).
# Same one-program design as greedy/sampling decode: the whole beam loop is
# a single lax.scan; beam reordering gathers the KV cache along the
# flattened [B*num_beams] batch axis on device.
# ---------------------------------------------------------------------------


def beam_search_generate(params, prompt, cfg: LlamaConfig,
                         max_new_tokens: int = 32, num_beams: int = 4,
                         max_len: Optional[int] = None,
                         eos_token_id: Optional[int] = None,
                         length_penalty: float = 1.0) -> jax.Array:
    """Fixed-length beam search over the KV cache; returns the best beam's
    tokens [B, max_new_tokens]. ``eos_token_id`` (optional) freezes
    finished beams (their only continuation is another EOS at logprob 0).
    ``length_penalty`` rescales final scores by len**penalty as in the
    reference's BeamSearchScorer."""
    prompt = jnp.asarray(prompt, jnp.int32)
    B, S = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    max_len = max_len or min(cfg.max_seq_len, S + max_new_tokens - 1)
    if S + max_new_tokens - 1 > max_len:
        raise ValueError(f"prompt ({S}) + max_new_tokens ({max_new_tokens}) "
                         f"needs {S + max_new_tokens - 1} cache slots but "
                         f"max_len is {max_len}")

    prefill = _prefill_program(cfg, max_len, 0.0, 0)
    cache, _, pos, _ = prefill(params, prompt, jax.random.PRNGKey(0))
    # re-derive first logits (prefill returns the sampled token, not logits)
    # cheaply: one decode-shaped forward would advance the cache, so instead
    # run the beam program from the prefilled cache + prompt's last token
    beam = _beam_program(cfg, max_new_tokens, num_beams, eos_token_id,
                         float(length_penalty))
    return beam(params, cache, prompt[:, -1], pos - 1)


@functools.lru_cache(maxsize=16)
def _beam_program(cfg: LlamaConfig, max_new_tokens: int, num_beams: int,
                  eos_token_id: Optional[int], length_penalty: float):
    nb = num_beams

    # no donation: the cache changes shape when tiled to [B*nb] beams, so
    # the input buffer can never alias an output
    @jax.jit
    def beam_all(params, cache, last_tok, last_pos):
        # Step 0: recompute the prompt-final logits from the cached state
        # (position last_pos is already in the cache; masking makes the
        # duplicate write idempotent), then branch into nb beams.
        logits, cache = forward_with_cache(params, last_tok[:, None], cfg,
                                           cache, last_pos)
        B = logits.shape[0]
        lp = jax.nn.log_softmax(logits, axis=-1)
        scores, tok0 = jax.lax.top_k(lp, nb)              # [B, nb]
        cache = jax.tree.map(lambda c: jnp.repeat(c, nb, axis=1), cache)
        nxt = tok0.reshape(B * nb).astype(jnp.int32)
        hist = jnp.zeros((B, nb, max_new_tokens), jnp.int32)
        hist = hist.at[:, :, 0].set(tok0)
        finished = (tok0 == eos_token_id) if eos_token_id is not None \
            else jnp.zeros((B, nb), bool)
        lengths = jnp.ones((B, nb), jnp.float32)  # per-beam generated length
        pos = last_pos + 1

        def body(carry, i):
            cache, nxt, pos, scores, finished, hist, lengths = carry
            logits, cache = forward_with_cache(params, nxt[:, None], cfg,
                                               cache, pos)
            lp = jax.nn.log_softmax(logits, axis=-1)      # [B*nb, V]
            V = lp.shape[-1]
            if eos_token_id is not None:
                # finished beams may only emit EOS again, at logprob 0
                eos_only = jnp.full((V,), -jnp.inf).at[eos_token_id].set(0.0)
                lp = jnp.where(finished.reshape(B * nb)[:, None],
                               eos_only[None], lp)
            total = scores[:, :, None] + lp.reshape(B, nb, V)
            new_scores, idx = jax.lax.top_k(total.reshape(B, nb * V), nb)
            beam_idx = idx // V                           # [B, nb]
            tok = (idx % V).astype(jnp.int32)
            src = (jnp.arange(B)[:, None] * nb + beam_idx).reshape(B * nb)
            cache = jax.tree.map(lambda c: jnp.take(c, src, axis=1), cache)
            hist = jnp.take_along_axis(hist, beam_idx[:, :, None], axis=1)
            hist = hist.at[:, :, i].set(tok)
            lengths = jnp.take_along_axis(lengths, beam_idx, axis=1)
            if eos_token_id is not None:
                prev_finished = jnp.take_along_axis(finished, beam_idx,
                                                    axis=1)
                lengths = jnp.where(prev_finished, lengths, lengths + 1)
                finished = prev_finished | (tok == eos_token_id)
            else:
                lengths = lengths + 1
            nxt = tok.reshape(B * nb)
            return (cache, nxt, pos + 1, new_scores, finished, hist,
                    lengths), None

        carry = (cache, nxt, pos, scores, finished, hist, lengths)
        if max_new_tokens > 1:
            carry, _ = jax.lax.scan(body, carry,
                                    jnp.arange(1, max_new_tokens))
        _, _, _, scores, _, hist, lengths = carry
        # reference BeamSearchScorer: score = sum_logprobs / len**penalty,
        # each hypothesis normalised by its OWN length (EOS position) — at
        # the default penalty of 1.0 this is plain per-length averaging;
        # penalty 0.0 disables normalisation
        scores = scores / (lengths ** length_penalty)
        best = jnp.argmax(scores, axis=-1)                # [B]
        return jnp.take_along_axis(
            hist, best[:, None, None], axis=1)[:, 0]      # [B, T]

    return beam_all
