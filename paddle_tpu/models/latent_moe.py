"""Latent-attention sparse-expert decoder (the openPangu-Ultra-MoE /
DeepSeek-V3 layer family), served through the paged engine as ONE CHIP'S
SHARE of an expert-parallel stage.

Per layer, ``x`` the residual stream, every norm an RMSNorm with a learned
scale, no biases, an untied head (the equations ``tests/
reference_latent_moe.py`` writes out in plain float32)::

    a = Attn(N1(x));  x = x + N2(a)        # sandwich norm: the sublayer's
    m = FFN(N3(x));   x = x + N4(m)        # output is normed, THEN added

    Attn(h): cq = Nq(h W_dq);  q = cq W_uq -> heads x (nope | rope)
             [ckv | kr] = h W_dkv;  ckv = Nkv(ckv);  kr = RoPE(kr)
             k_i = [ckv W_uk_i | kr],  v_i = ckv W_uv_i   (kr: ONE a token)
             o_i = softmax(q_i k_i / sqrt(nope + rope), causal) v_i
    FFN, leading dense layers: SwiGLU(intermediate_size)
    FFN, expert layers: s = sigmoid(h W_g) in float32 over ALL routed
             experts; S = top-k(s); w_e = scale * s_e / (sum_S s + 1e-20)
             SwiGLU_shared(h) + sum_{e in S, e held here} w_e SwiGLU_e(h)

**The share.** ``held_experts`` = (first, count): the router keeps its
published width and its experts per token, this chip computes its own
experts' part and what the absent ones would add is left out (their chips
add it; on one chip the layer runs without its exchange and nothing stands
in for it). ``vocab_slice`` = (first, rows): the embedding and the head
hold those rows, token ids and logits are over the slice.
``share_params`` cuts an uncut tree to a share.

**The cache** holds one row a token a layer, ``[ckv | kr | 0]``: the
normed latent (``kv_lora_rank``), the one rotary key, zero lanes up to a
multiple of 128 (512 + 64 -> 640: a TPU array's minor dimension is tiled
by 128 lanes, so the padding is in HBM either way and the kernel's one dot
wants it). ONE plane ``{"c": [L, num_pages, page_size, row]}``, written in
place at ``[layer, phys, prow]``; trash page 0 and ``live`` as
``llama.forward_with_pages``. Attention reads it in the absorbed form
(``ops/pallas/mla_attention.py``): ``W_uk`` is folded into the query,
``W_uv`` applied to the output, for admissions and decode ticks alike.

What the serving engine asks of a model module (``models.family_of``):
``init_params``, ``init_paged_pool``, ``page_bytes``,
``paged_kernel_active``, ``forward_with_pages``, ``SERVING_FAMILIES`` and,
optionally, ``COUNTER_GROUPS`` (with ``SEGMENT_COUNTERS``, its columns).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .llama import _head_logits, _rms_norm, _rope_at, scoped

__all__ = ["LatentMoEConfig", "init_params", "init_mtp_params",
           "share_params", "init_paged_pool", "page_bytes",
           "paged_kernel_active", "forward_with_pages", "route",
           "mtp_logits", "SERVING_FAMILIES", "SEGMENT_COUNTERS",
           "COUNTER_GROUPS"]

# the one serving family this model is served by (``models.require``)
SERVING_FAMILIES = ("paged",)
# what an expert layer counts a step, summed over layers (the last: max),
# by group: ``serving.moe.*``, ``OnlineReport.moe``; the event log's
# columns are the groups' counters in order
COUNTER_GROUPS = {"moe": ("picks", "picks_held", "experts_hit", "max_load")}
SEGMENT_COUNTERS = sum(COUNTER_GROUPS.values(), ())
EXPERT_KEYS = ("we_gate", "we_up", "we_down")   # [L, E, ...]: the experts held


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 153600           # the published rows
    hidden_size: int = 7680
    intermediate_size: int = 18432     # the leading dense layers' FFN
    moe_intermediate_size: int = 2048  # one expert's width
    num_layers: int = 61
    first_k_dense: int = 3             # leading dense layers
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256        # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    # this chip's share: (first, count); None = everything
    held_experts: Optional[Tuple[int, int]] = None
    vocab_slice: Optional[Tuple[int, int]] = None
    max_seq_len: int = 2048
    rope_theta: float = 25600000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def experts(self) -> Tuple[int, int]:
        return tuple(self.held_experts or (0, self.n_routed_experts))

    @property
    def vocab(self) -> Tuple[int, int]:
        return tuple(self.vocab_slice or (0, self.vocab_size))

    @property
    def num_expert_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def cache_row(self) -> int:
        """Lanes of a cache row: [ckv | kr] padded to a multiple of 128."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @classmethod
    def tiny(cls, **kw):
        """Tiny config for tests: 1 dense + 2 expert layers, 16 experts."""
        d = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                 moe_intermediate_size=128, num_layers=3, first_k_dense=1,
                 num_heads=8, q_lora_rank=48, kv_lora_rank=128,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 n_routed_experts=16, num_experts_per_tok=4,
                 max_seq_len=64, rope_theta=10000.0, dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _normal(key, shape, fan_in: int, dtype):
    """Seeded N(0, 1/fan_in) weights, made one [K, N] slice at a time so
    that what a 4.9 B-parameter share needs beside its weights is one
    slice in float32, not one stacked array."""
    n = int(np.prod(shape[:-2]))
    scale = 1.0 / np.sqrt(fan_in)
    out = jax.lax.map(
        lambda k: (jax.random.normal(k, shape[-2:]) * scale).astype(dtype),
        jax.random.split(key, n))
    return out.reshape(shape)


def _attention_params(cfg: LatentMoEConfig, key, L: int, dtype):
    H, nH = cfg.hidden_size, cfg.num_heads
    R, Rq = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    ones = lambda *s: jnp.ones((L,) + s, dtype)
    return {
        "w_dq": _normal(ks[0], (L, H, Rq), H, dtype),
        "w_uq": _normal(ks[1], (L, Rq, nH * (dn + dr)), Rq, dtype),
        "w_dkv": _normal(ks[2], (L, H, R + dr), H, dtype),    # [ckv | kr]
        "w_uk": _normal(ks[3], (L, nH, dn, R), R, dtype),     # k_i = ckv W^T
        "w_uv": _normal(ks[4], (L, nH, R, dv), R, dtype),
        "w_o": _normal(ks[5], (L, nH * dv, H), nH * dv, dtype),
        "nq": ones(Rq), "nkv": ones(R),
        "n1": ones(H), "n2": ones(H), "n3": ones(H), "n4": ones(H),
    }


def _expert_ffn_params(cfg: LatentMoEConfig, key, L: int, dtype):
    H, Fe, Fs = cfg.hidden_size, cfg.moe_intermediate_size, cfg.shared_width
    E = cfg.experts[1]
    ks = jax.random.split(key, 7)
    return {
        # the router is float32 and as wide as published, whatever is held
        "router": _normal(ks[0], (L, H, cfg.n_routed_experts), H,
                          jnp.float32),
        "ws_gate": _normal(ks[1], (L, H, Fs), H, dtype),
        "ws_up": _normal(ks[2], (L, H, Fs), H, dtype),
        "ws_down": _normal(ks[3], (L, Fs, H), Fs, dtype),
        "we_gate": _normal(ks[4], (L, E, H, Fe), H, dtype),
        "we_up": _normal(ks[5], (L, E, H, Fe), H, dtype),
        "we_down": _normal(ks[6], (L, E, Fe, H), Fe, dtype),
    }


def init_params(cfg: LatentMoEConfig, key: Optional[jax.Array] = None,
                dtype: Any = None) -> Dict[str, Any]:
    """The share's parameter tree: ``embed`` / ``lm_head`` over the
    vocabulary slice, ``dense`` (stacked over the leading dense layers)
    and ``moe`` (stacked over the expert layers, the experts held)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    dtype = dtype or jnp.float32
    H, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab[1]
    Ld, Le = cfg.first_k_dense, cfg.num_expert_layers
    ks = jax.random.split(key, 8)
    dense = _attention_params(cfg, ks[2], Ld, dtype)
    dense.update(
        w_gate=_normal(ks[3], (Ld, H, F), H, dtype),
        w_up=_normal(ks[4], (Ld, H, F), H, dtype),
        w_down=_normal(ks[5], (Ld, F, H), F, dtype))
    moe = _attention_params(cfg, ks[6], Le, dtype)
    moe.update(_expert_ffn_params(cfg, ks[7], Le, dtype))
    return {
        "embed": (_normal(ks[0], (V, H), 1, jnp.float32) * 0.02
                  ).astype(dtype),
        "lm_head": _normal(ks[1], (H, V), H, dtype),
        "ln_f": jnp.ones((H,), dtype),
        "dense": dense, "moe": moe,
    }


def init_mtp_params(cfg: LatentMoEConfig, key, dtype: Any = None):
    """The multi-token-prediction module: two input norms, the joining
    projection, ONE expert layer, an output norm (the head is shared)."""
    dtype = dtype or jnp.float32
    H = cfg.hidden_size
    ks = jax.random.split(key, 3)
    layer = _attention_params(cfg, ks[0], 1, dtype)
    layer.update(_expert_ffn_params(cfg, ks[1], 1, dtype))
    return {"nh": jnp.ones((H,), dtype), "ne": jnp.ones((H,), dtype),
            "w_p": _normal(ks[2], (2 * H, H), 2 * H, dtype),
            "layer": {k: v[0] for k, v in layer.items()},
            "nm": jnp.ones((H,), dtype)}


def share_params(params, cfg: LatentMoEConfig,
                 share: LatentMoEConfig):
    """``share``'s part of a tree built for ``cfg``: its experts of every
    expert layer, its rows of the embedding and the head; everything else
    (attention, the shared expert, the router, the norms) is on every
    chip alike."""
    e0, ne = share.experts
    v0, nv = share.vocab
    c0 = cfg.experts[0]
    out = dict(params, embed=params["embed"][v0:v0 + nv],
               lm_head=params["lm_head"][:, v0:v0 + nv])
    out["moe"] = dict(params["moe"])
    for k in EXPERT_KEYS:
        out["moe"][k] = params["moe"][k][:, e0 - c0:e0 - c0 + ne]
    return out


# ---------------------------------------------------------------------------
# The paged latent pool
# ---------------------------------------------------------------------------

def init_paged_pool(cfg: LatentMoEConfig, num_pages: int, page_size: int,
                    dtype=None, quant=None) -> Dict[str, jax.Array]:
    """One plane ``{"c": [L, num_pages, page_size, cache_row]}``; page 0
    is the allocator's trash page."""
    if quant is not None:
        raise ValueError("the latent pool has no quantized form")
    return {"c": jnp.zeros((cfg.num_layers, num_pages, page_size,
                            cfg.cache_row), dtype or cfg.dtype)}


def page_bytes(cfg: LatentMoEConfig, page_size: int, quant=None) -> int:
    """Bytes one pool page occupies across all layers."""
    return cfg.num_layers * page_size * cfg.cache_row \
        * jnp.dtype(cfg.dtype).itemsize


def paged_kernel_active(cfg: LatentMoEConfig, page_size: int) -> bool:
    from ..ops.pallas.mla_attention import mla_attention_active

    return mla_attention_active(page_size, cfg.cache_row, cfg.kv_lora_rank)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def _swiglu(h, w_gate, w_up, w_down):
    dt = h.dtype
    return (jax.nn.silu(h @ w_gate.astype(dt)) * (h @ w_up.astype(dt))) \
        @ w_down.astype(dt)


def _latent_q(cfg: LatentMoEConfig, h, lp, positions):
    """Queries [B, T, heads, nope | rope], the rope part rotated."""
    dt = h.dtype
    B, T = h.shape[:2]
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = _rms_norm(h @ lp["w_dq"].astype(dt), lp["nq"], cfg.rms_eps)
    q = (cq @ lp["w_uq"].astype(dt)).reshape(B, T, cfg.num_heads, dn + dr)
    return q[..., :dn], _rope_at(q[..., dn:], cfg.rope_theta, positions)


def _latent_kv(cfg: LatentMoEConfig, h, lp, positions):
    """The token's latent ``ckv`` [B, T, rank] (normed) and its one rotary
    key ``kr`` [B, T, rope]."""
    R = cfg.kv_lora_rank
    ckr = h @ lp["w_dkv"].astype(h.dtype)
    ckv = _rms_norm(ckr[..., :R], lp["nkv"], cfg.rms_eps)
    kr = _rope_at(ckr[..., None, R:], cfg.rope_theta, positions)[:, :, 0]
    return ckv, kr


@scoped("latent_qkv")
def _latent_qkv(cfg: LatentMoEConfig, x, lp, positions):
    """N1, the two down-projections, and the ABSORBED query: returns
    (q [B, T, heads, cache_row] scaled, the cache rows [B, T, cache_row])."""
    dt = x.dtype
    h = _rms_norm(x, lp["n1"], cfg.rms_eps)
    q_nope, q_rope = _latent_q(cfg, h, lp, positions)
    ckv, kr = _latent_kv(cfg, h, lp, positions)
    q_lat = jnp.einsum("bthd,hdr->bthr", q_nope, lp["w_uk"].astype(dt))
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    pad = cfg.cache_row - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    q = jnp.concatenate([q_lat, q_rope], -1) * jnp.asarray(scale, dt)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad),))
    rows = jnp.pad(jnp.concatenate([ckv, kr], -1), ((0, 0),) * 2
                   + ((0, pad),))
    return q, rows


@scoped("attention")
def _paged_latent_attention(cfg: LatentMoEConfig, q, plane, layer,
                            page_table, positions, q_len):
    """``o_lat`` [B, T, heads, rank] of absorbed queries over layer
    ``layer`` of the latent plane: the page-indirect kernel where the pool
    tiles (it fetches and computes for a slot's first ``q_len`` rows
    only), else the slot's pages gathered and the same mathematics dense."""
    from ..ops.pallas.mla_attention import mla_paged_attention

    R = cfg.kv_lora_rank
    if paged_kernel_active(cfg, plane.shape[2]):
        return mla_paged_attention(q, plane, page_table, positions[:, 0],
                                   q_len, layer=layer, rank=R)
    B = q.shape[0]
    rows = plane[layer, page_table].reshape(B, -1, plane.shape[-1])
    s = jnp.einsum("bthc,bwc->bthw", q, rows).astype(jnp.float32)
    seen = jnp.arange(rows.shape[1])[None, None, :] <= positions[:, :, None]
    s = jnp.where(seen[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bthw,bwr->bthr", p, rows[..., :R])


@scoped("post")
def _attn_post(cfg: LatentMoEConfig, x, o_lat, lp):
    """``W_uv`` on the latent output, the output projection, N2, add."""
    dt = x.dtype
    B, T = x.shape[:2]
    o = jnp.einsum("bthr,hrd->bthd", o_lat, lp["w_uv"].astype(dt))
    a = o.reshape(B, T, -1) @ lp["w_o"].astype(dt)
    return x + _rms_norm(a, lp["n2"], cfg.rms_eps)


@scoped("router")
def route(cfg: LatentMoEConfig, h, router_w):
    """Picks [N, k] (expert ids over the WHOLE router) and their weights
    [N, k] (float32) for rows ``h`` [N, H]: sigmoid scores in float32 at
    the highest matmul precision, top-k, normalised over the picks, then
    scaled."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), router_w,
                               precision=jax.lax.Precision.HIGHEST))
    top, picks = jax.lax.top_k(s, cfg.num_experts_per_tok)
    w = cfg.routed_scaling_factor * top / (top.sum(-1, keepdims=True)
                                           + 1e-20)
    return picks.astype(jnp.int32), w


@scoped("experts")
def _routed_experts(cfg: LatentMoEConfig, h, picks, w, valid, lp):
    """sum over the picks that landed on a HELD expert of w_e SwiGLU_e(h),
    for rows ``h`` [N, H]; ``valid`` [N]: the row is a live token. Returns
    (out [N, H], counters [4] int32)."""
    from ..ops.pallas.grouped_matmul import (buffer_rows,
                                             grouped_expert_matmul,
                                             grouped_matmul_active,
                                             sort_picks)

    N, H = h.shape
    k = picks.shape[1]
    e0, E = cfg.experts
    local = picks - e0
    held = (local >= 0) & (local < E) & valid[:, None]          # [N, k]
    hot = (local[..., None] == jnp.arange(E)) & held[..., None]  # [N, k, E]
    sizes = hot.sum((0, 1)).astype(jnp.int32)
    counters = jnp.stack([k * valid.sum(), held.sum(), (sizes > 0).sum(),
                          sizes.max()]).astype(jnp.int32)
    if grouped_matmul_active(H, cfg.moe_intermediate_size):
        row, _, tile_expert, n_tiles = sort_picks(
            local.reshape(-1), held.reshape(-1), E)
        # buffer row -> its token (rows no pick owns read the zero row N)
        token = jnp.full((buffer_rows(N * k, E),), N, jnp.int32).at[
            jnp.where(held.reshape(-1), row, buffer_rows(N * k, E))].set(
                jnp.repeat(jnp.arange(N, dtype=jnp.int32), k), mode="drop")
        xs = jnp.concatenate([h, jnp.zeros((1, H), h.dtype)])[token]
        # stacked over the layers when the layer loop hands them so: the
        # kernel then indexes the stack (``expert_layer``), no copy
        lay = lp.get("expert_layer")
        mid = grouped_expert_matmul(xs, (lp["we_gate"], lp["we_up"]),
                                    tile_expert, n_tiles, swiglu=True,
                                    layer=lay)
        ys = grouped_expert_matmul(mid, lp["we_down"], tile_expert, n_tiles,
                                   layer=lay)
        y = jnp.where(held[..., None], ys[row.reshape(N, k)], 0)  # [N,k,H]
        out = jnp.sum(y.astype(jnp.float32) * w[..., None], 1)
        return out.astype(h.dtype), counters
    # the same sum as a dense mask over the held experts
    if "expert_layer" in lp:
        lp = {k: v[lp["expert_layer"]] if k in EXPERT_KEYS else v
              for k, v in lp.items()}
    wt = jnp.sum(jnp.where(hot, w[..., None], 0.0), 1)          # [N, E]
    dt = h.dtype
    mid = jax.nn.silu(jnp.einsum("nh,ehf->enf", h, lp["we_gate"].astype(dt))) \
        * jnp.einsum("nh,ehf->enf", h, lp["we_up"].astype(dt))
    y = jnp.einsum("enf,efh->enh", mid, lp["we_down"].astype(dt))
    out = jnp.einsum("enh,ne->nh", y.astype(jnp.float32), wt)
    return out.astype(dt), counters


@scoped("shared_expert")
def _shared_expert(h, lp):
    return _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _expert_ffn(cfg: LatentMoEConfig, h, lp, valid):
    """The expert layer's FFN on [B, T, H]: the shared expert on every
    row, the held experts on the rows routed to them."""
    B, T, H = h.shape
    h2 = h.reshape(B * T, H)
    picks, w = route(cfg, h2, lp["router"])
    routed, counters = _routed_experts(cfg, h2, picks, w,
                                       valid.reshape(B * T), lp)
    return (_shared_expert(h2, lp) + routed).reshape(B, T, H), counters


def _ffn(cfg: LatentMoEConfig, x, lp, valid):
    """N3, the layer's FFN (dense or experts, by its parameters), N4,
    add. Returns (x, counters or None)."""
    h = _rms_norm(x, lp["n3"], cfg.rms_eps)
    if "router" in lp:
        m, counters = _expert_ffn(cfg, h, lp, valid)
    else:
        with jax.named_scope("dense_ffn"):
            m = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        counters = None
    return x + _rms_norm(m, lp["n4"], cfg.rms_eps), counters


def _merge(a, b):
    """Counters of two layers: sums, and the larger largest load."""
    return jnp.concatenate([a[:3] + b[:3], jnp.maximum(a[3:], b[3:])])


# ---------------------------------------------------------------------------
# Forward over pages
# ---------------------------------------------------------------------------

def forward_with_pages(params, tokens, cfg: LatentMoEConfig, pool,
                       page_table, pos, live=None, logit_pos=None,
                       logits_all=False, with_counters=False):
    """``llama.forward_with_pages``' contract over the latent pool:
    tokens [B, T] at positions ``pos[b] .. pos[b]+T-1``, ``pool`` the one
    plane, carried through the layer loop and written in place; dead
    slots (``live``) and positions past the table write the trash page.
    The loop is the leading dense layers unrolled, then ONE ``lax.scan``
    over the stacked expert layers (two parameter trees cannot share a
    scan), the plane in the carry of both. Returns (logits, pool), and
    with ``with_counters`` the step's ``SEGMENT_COUNTERS`` [4] int32 over
    the live rows (``live``; under ``logit_pos`` the rows up to it)."""
    dt = cfg.dtype
    B, T = tokens.shape
    plane = pool["c"]
    psz = plane.shape[2]
    max_pages = page_table.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    positions = pos[:, None] + jnp.arange(T)
    vpage = positions // psz
    prow = positions % psz
    phys = jnp.take_along_axis(page_table,
                               jnp.minimum(vpage, max_pages - 1), axis=1)
    writable = vpage < max_pages
    valid = jnp.ones((B, T), bool)
    if live is not None:
        writable = writable & live[:, None]
        valid = valid & live[:, None]
    if logit_pos is not None and not logits_all:
        valid = valid & (jnp.arange(T)[None, :]
                         <= jnp.reshape(logit_pos, (-1, 1)))
    phys = jnp.where(writable, phys, 0)
    # rows whose output anything reads: none of a dead slot's, an
    # admission's up to ``logit_pos`` (the rest is the bucket's padding)
    q_len = valid.sum(1, dtype=jnp.int32)

    def layer(x, plane, lp, i):
        q, rows = _latent_qkv(cfg, x, lp, positions)
        with jax.named_scope("kv_write"):
            plane = plane.at[i, phys, prow].set(rows.astype(plane.dtype))
        o_lat = _paged_latent_attention(cfg, q, plane, i, page_table,
                                        positions, q_len)
        x = _attn_post(cfg, x, o_lat, lp)
        x, counters = _ffn(cfg, x, lp, valid)
        return x, plane, counters

    Ld = cfg.first_k_dense
    for i in range(Ld):
        x, plane, _ = layer(x, plane, {k: v[i] for k, v in
                                       params["dense"].items()}, i)

    # the held experts' weights stay STACKED and out of the scan's xs (a
    # layer's experts sliced out for a kernel are a copy of them): the
    # grouped kernel indexes the stack by ``expert_layer``
    experts = {k: params["moe"][k] for k in EXPERT_KEYS}
    rest = {k: v for k, v in params["moe"].items() if k not in experts}

    def expert_layer(carry, xs):
        x, plane, cnt = carry
        lp, j = xs
        x, plane, c = layer(x, plane, dict(lp, expert_layer=j, **experts),
                            Ld + j)
        return (x, plane, _merge(cnt, c)), None

    (x, plane, counters), _ = jax.lax.scan(
        expert_layer, (x, plane, jnp.zeros((4,), jnp.int32)),
        (rest, jnp.arange(cfg.num_expert_layers, dtype=jnp.int32)))
    logits = _head_logits(cfg, params, x, False, logit_pos, logits_all)
    if with_counters:
        return logits, {"c": plane}, counters
    return logits, {"c": plane}


# ---------------------------------------------------------------------------
# Multi-token prediction (built and tested; no segment program calls it)
# ---------------------------------------------------------------------------

def _causal_latent_attention(cfg: LatentMoEConfig, x, lp, positions):
    """The attention sublayer with no cache: keys and values expanded from
    the sequence's own latents (``W_uk``, ``W_uv``), causal."""
    dt = x.dtype
    h = _rms_norm(x, lp["n1"], cfg.rms_eps)
    q_nope, q_rope = _latent_q(cfg, h, lp, positions)
    ckv, kr = _latent_kv(cfg, h, lp, positions)
    k_nope = jnp.einsum("bsr,hdr->bshd", ckv, lp["w_uk"].astype(dt))
    v = jnp.einsum("bsr,hrd->bshd", ckv, lp["w_uv"].astype(dt))
    s = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope)
         + jnp.einsum("bthd,bsd->bhts", q_rope, kr)).astype(jnp.float32)
    s = s / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    T = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1).astype(dt), v)
    a = o.reshape(x.shape[0], T, -1) @ lp["w_o"].astype(dt)
    return x + _rms_norm(a, lp["n2"], cfg.rms_eps)


def mtp_logits(params, mtp, hidden, next_tokens, cfg: LatentMoEConfig):
    """The multi-token-prediction module on a whole sequence: ``hidden``
    [B, T, H] the main model's last-layer residual stream (before its
    final norm) at positions 0..T-1, ``next_tokens`` [B, T] the token at
    t+1. Returns logits [B, T, V] for the token at t+2 through the SHARED
    head: ``Head(Nm(Layer([Nh(hidden) ; Ne(Emb(next))] W_p)))``."""
    dt = cfg.dtype
    B, T = next_tokens.shape
    emb = params["embed"].astype(dt)[next_tokens]
    x = jnp.concatenate([_rms_norm(hidden.astype(dt), mtp["nh"], cfg.rms_eps),
                         _rms_norm(emb, mtp["ne"], cfg.rms_eps)], -1) \
        @ mtp["w_p"].astype(dt)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _causal_latent_attention(cfg, x, mtp["layer"], positions)
    x, _ = _ffn(cfg, x, mtp["layer"], jnp.ones((B, T), bool))
    x = _rms_norm(x, mtp["nm"], cfg.rms_eps)
    return (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
