"""Window / full attention sparse-expert decoder (the K-EXAONE layer
family), served through the paged engine as ONE CHIP'S SHARE of an
expert-parallel stage, with TWO KINDS OF CACHE for one sequence.

Per layer ``l``, ``x`` the residual stream, every norm an RMSNorm with a
learned scale, no biases, an untied head (the equations
``tests/reference_hybrid_moe.py`` writes out in plain float32)::

    h = N1(x);  x = x + Attn_l(h);      h = N2(x);  x = x + FFN_l(h)

    Attn_l(h): q = Nq(h W_q) -> heads x d;  k = Nk(h W_k), v = h W_v -> kv x d
               layer_types[l] == "sliding_attention": q, k = RoPE(q, k);
                   key s is seen by query t iff 0 <= t - s < sliding_window
               layer_types[l] == "full_attention": NO rotary;
                   key s is seen by query t iff s <= t
               o = softmax(q k^T / sqrt(d)) v;  Attn = concat(o) W_o
    FFN_l, l < first_k_dense: SwiGLU(intermediate_size)
    FFN_l, the others: ``latent_moe``'s expert layer (its ``route``,
               ``_routed_experts``, ``_shared_expert``), called from here

Layers differ by kind, so the layer loop is unrolled and ``params["layers"]``
is a LIST of one dict a layer (no stack to slice a layer out of: a layer's
weights are handed to its matmuls and to the grouped expert kernel as they
lie).

**The share** is ``latent_moe``'s: ``held_experts`` = (first, count) of the
router's experts, ``vocab_slice`` = (first, rows); ``share_params`` cuts an
uncut tree to a share.

**The cache: two kinds, one page table** (``inference/paged_kv.py``).

* A full layer keeps a row a token: row pages ``{"k", "v"}: [L_full,
  num_pages, page_size, kv*d]``, named by the slot's page table, as
  llama's.
* A window layer needs its last ``sliding_window`` rows and nothing else,
  whatever the sequence's length: the sequence's FIXED PART, ``{"wk",
  "wv"}: [L_window, fixed_parts, sliding_window, kv*d]``, position ``p`` at
  row ``p mod sliding_window``. Its id is the LAST column of the page
  table (``page_table[b, -1]``; part 0 is the trash part), so the table is
  one column wider than the row pages' ``max_pages``. A reused part is
  never cleared: a query at position ``t`` sees rows ``0 .. min(t, W-1)``,
  every one of which its own sequence has written by then (rows are
  written in order of position), and nothing older.

A tick (``T == 1``) writes its row to both kinds and attends each through
``ragged_paged_attention`` — the full layers over the slot's pages, the
window layers over the slot's part as ONE page of ``sliding_window`` rows
with the context clipped to ``W - 1`` (softmax does not mind the ring's
order; rotary was applied before the write). An admission (``T > 1``)
STARTS AT POSITION 0 (the family is served without prefix reuse or chunked
prefill: ``SERVING_FAMILIES``): its rows attend each other through
``windowed_prefill_attention``, which skips the key blocks outside the
mask, and are then written: all of them to the row pages, the last
``sliding_window`` of the prompt to the fixed part.

What the serving engine asks of a model module (``models.family_of``):
``init_params``, ``init_paged_pool``, ``page_bytes``,
``paged_kernel_active``, ``forward_with_pages``, ``SERVING_FAMILIES`` and,
optionally, ``COUNTER_GROUPS`` and ``fixed_part_bytes``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import latent_moe
from .latent_moe import _merge, _normal, _swiglu
from .llama import (_dense_cache_attention, _head_logits, _rms_norm,
                    _rope_at, scoped)

__all__ = ["HybridMoEConfig", "init_params", "share_params",
           "init_paged_pool", "page_bytes", "fixed_part_bytes",
           "paged_kernel_active", "forward_with_pages", "SERVING_FAMILIES",
           "COUNTER_GROUPS", "SEGMENT_COUNTERS", "KERNEL_NAMES"]

# the one serving family this model is served by (``models.require``)
SERVING_FAMILIES = ("paged",)
# a step counts the expert layers' four (``latent_moe``) and, of its two
# caches: key rows the ticks' full layers attended, the same of the window
# layers (min(position + 1, window) a live slot a layer), the rows an
# admission computed (its row blocks up to the prompt's end) and those of
# them that are the prompt's
COUNTER_GROUPS = {
    "moe": latent_moe.COUNTER_GROUPS["moe"],
    "window": ("rows_full", "rows_window", "admit_rows", "admit_rows_used"),
}
SEGMENT_COUNTERS = sum(COUNTER_GROUPS.values(), ())
EXPERT_KEYS = latent_moe.EXPERT_KEYS
WINDOW, FULL = "sliding_attention", "full_attention"
# ASSUMED (the config.json has no key): rotary on the window layers only, a
# full layer carries no position encoding (the EXAONE 4.0 hybrid
# convention). Tests and the benchmark's control plant the other reading
# HERE, from outside: no served configuration has it.
ROTARY_KINDS = (WINDOW,)
# each call site's kernel under a name of its own in a device trace
KERNEL_NAMES = {
    (FULL, "tick"): "paged_attention_full",
    (WINDOW, "tick"): "paged_attention_window",
    (FULL, "admit"): "prefill_attention_full",
    (WINDOW, "admit"): "prefill_attention_window",
}
# rows of one trip of an admission's row-wise work (norms, projections,
# FFN, experts); the trips follow the prompt's length (``_admit``)
ADMIT_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class HybridMoEConfig:
    vocab_size: int = 153600           # the published rows
    hidden_size: int = 6144
    intermediate_size: int = 18432     # the leading dense layers' FFN
    moe_intermediate_size: int = 2048  # one expert's width
    num_layers: int = 48
    first_k_dense: int = 1             # leading dense layers
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    # a kind a layer; None: three window layers, then a full one, repeated
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 128
    n_routed_experts: int = 128        # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    # this chip's share: (first, count); None = everything
    held_experts: Optional[Tuple[int, int]] = None
    vocab_slice: Optional[Tuple[int, int]] = None
    max_seq_len: int = 2048
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("query heads must be whole groups of kv heads")
        if len(self.kinds) != self.num_layers or \
                set(self.kinds) - {WINDOW, FULL}:
            raise ValueError(f"layer_types must name {self.num_layers} "
                             f"layers {WINDOW!r} or {FULL!r}")

    @property
    def kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(FULL if i % 4 == 3 else WINDOW
                     for i in range(self.num_layers))

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    @property
    def plane_index(self) -> Tuple[int, ...]:
        """A layer's index among the layers of its kind: where its rows
        lie in its kind's cache planes."""
        return tuple(self.kinds[:i].count(k)
                     for i, k in enumerate(self.kinds))

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
    def kv_width(self) -> int:
        """Lanes of a cache row: the kv heads side by side."""
        return self.num_kv_heads * self.head_dim

    @classmethod
    def tiny(cls, **kw):
        """Tiny config for tests: 1 dense + 4 expert layers as the served
        cut has them (window, window, window, full, window), window 8."""
        d = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                 moe_intermediate_size=128, num_layers=5, first_k_dense=1,
                 num_heads=8, num_kv_heads=4, head_dim=32, sliding_window=8,
                 n_routed_experts=16, num_experts_per_tok=4,
                 max_seq_len=64, rope_theta=10000.0,
                 dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _layer_params(cfg: HybridMoEConfig, key, sparse: bool, dtype):
    H, D = cfg.hidden_size, cfg.head_dim
    nq, nkv = cfg.num_heads * D, cfg.kv_width
    Fe, Fs, E = cfg.moe_intermediate_size, cfg.shared_width, cfg.experts[1]
    ks = jax.random.split(key, 11)
    lp = {
        "wq": _normal(ks[0], (H, nq), H, dtype),
        "wk": _normal(ks[1], (H, nkv), H, dtype),
        "wv": _normal(ks[2], (H, nkv), H, dtype),
        "wo": _normal(ks[3], (nq, H), nq, dtype),
        "nq": jnp.ones((D,), dtype), "nk": jnp.ones((D,), dtype),
        "n1": jnp.ones((H,), dtype), "n2": jnp.ones((H,), dtype),
    }
    if not sparse:
        F = cfg.intermediate_size
        lp.update(w_gate=_normal(ks[4], (H, F), H, dtype),
                  w_up=_normal(ks[5], (H, F), H, dtype),
                  w_down=_normal(ks[6], (F, H), F, dtype))
        return lp
    lp.update(
        # the router is float32 and as wide as published, whatever is held
        router=_normal(ks[4], (H, cfg.n_routed_experts), H, jnp.float32),
        ws_gate=_normal(ks[5], (H, Fs), H, dtype),
        ws_up=_normal(ks[6], (H, Fs), H, dtype),
        ws_down=_normal(ks[7], (Fs, H), Fs, dtype),
        we_gate=_normal(ks[8], (E, H, Fe), H, dtype),
        we_up=_normal(ks[9], (E, H, Fe), H, dtype),
        we_down=_normal(ks[10], (E, Fe, H), Fe, dtype))
    return lp


def init_params(cfg: HybridMoEConfig, key: Optional[jax.Array] = None,
                dtype: Any = None) -> Dict[str, Any]:
    """The share's parameter tree: ``embed`` / ``lm_head`` over the
    vocabulary slice, ``ln_f``, and ``layers``, a list of one dict a layer
    (attention, then the dense FFN or the router, the shared expert and
    the experts held)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    dtype = dtype or jnp.float32
    H, V = cfg.hidden_size, cfg.vocab[1]
    ks = jax.random.split(key, 2 + cfg.num_layers)
    return {
        "embed": (_normal(ks[0], (V, H), 1, jnp.float32) * 0.02
                  ).astype(dtype),
        "lm_head": _normal(ks[1], (H, V), H, dtype),
        "ln_f": jnp.ones((H,), dtype),
        "layers": [_layer_params(cfg, ks[2 + i], i >= cfg.first_k_dense,
                                 dtype) for i in range(cfg.num_layers)],
    }


def share_params(params, cfg: HybridMoEConfig, share: HybridMoEConfig):
    """``share``'s part of a tree built for ``cfg``: its experts of every
    expert layer, its rows of the embedding and the head; everything else
    (attention, the shared expert, the router, the norms) is on every chip
    alike."""
    e0, ne = share.experts
    v0, nv = share.vocab
    c0 = cfg.experts[0]
    return dict(
        params, embed=params["embed"][v0:v0 + nv],
        lm_head=params["lm_head"][:, v0:v0 + nv],
        layers=[{k: v[e0 - c0:e0 - c0 + ne] if k in EXPERT_KEYS else v
                 for k, v in lp.items()} for lp in params["layers"]])


# ---------------------------------------------------------------------------
# The two caches
# ---------------------------------------------------------------------------

def init_paged_pool(cfg: HybridMoEConfig, num_pages: int, page_size: int,
                    dtype=None, quant=None,
                    fixed_parts: int = 2) -> Dict[str, jax.Array]:
    """Row pages of the full layers ``{"k", "v"}: [L_full, num_pages,
    page_size, kv*d]`` (page 0 the trash page) and the window layers'
    fixed parts ``{"wk", "wv"}: [L_window, fixed_parts, sliding_window,
    kv*d]`` (part 0 the trash part)."""
    if quant is not None:
        raise ValueError("the window / full pool has no quantized form")
    dtype = dtype or cfg.dtype
    rows = (len(cfg.layers_of(FULL)), num_pages, page_size, cfg.kv_width)
    ring = (len(cfg.layers_of(WINDOW)), fixed_parts, cfg.sliding_window,
            cfg.kv_width)
    return {"k": jnp.zeros(rows, dtype), "v": jnp.zeros(rows, dtype),
            "wk": jnp.zeros(ring, dtype), "wv": jnp.zeros(ring, dtype)}


def page_bytes(cfg: HybridMoEConfig, page_size: int, quant=None) -> int:
    """Bytes one ROW page occupies across the full layers."""
    return 2 * len(cfg.layers_of(FULL)) * page_size * cfg.kv_width \
        * jnp.dtype(cfg.dtype).itemsize


def fixed_part_bytes(cfg: HybridMoEConfig) -> int:
    """Bytes of one sequence's fixed part across the window layers:
    whatever its length."""
    return 2 * len(cfg.layers_of(WINDOW)) * cfg.sliding_window \
        * cfg.kv_width * jnp.dtype(cfg.dtype).itemsize


def paged_kernel_active(cfg: HybridMoEConfig, page_size: int) -> bool:
    """True when a tick's attention over BOTH caches routes to the paged
    kernel (a fixed part is a page of ``sliding_window`` rows)."""
    from ..ops.pallas.paged_attention import paged_attention_active

    return all(paged_attention_active(rows, cfg.num_heads, cfg.num_kv_heads,
                                      cfg.head_dim)
               for rows in (page_size, cfg.sliding_window))


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

@scoped("qkv")
def _qkv(cfg: HybridMoEConfig, x, lp, positions, rotary: bool):
    """N1, the three projections, the per-head norms, rotary where the
    layer's kind has it. Returns q [B, T, heads, d], k [B, T, kv, d],
    v [B, T, kv*d]."""
    dt = x.dtype
    B, T = x.shape[:2]
    d = cfg.head_dim
    h = _rms_norm(x, lp["n1"], cfg.rms_eps)
    q = (h @ lp["wq"].astype(dt)).reshape(B, T, cfg.num_heads, d)
    k = (h @ lp["wk"].astype(dt)).reshape(B, T, cfg.num_kv_heads, d)
    v = h @ lp["wv"].astype(dt)
    q = _rms_norm(q, lp["nq"], cfg.rms_eps)
    k = _rms_norm(k, lp["nk"], cfg.rms_eps)
    if rotary:
        q = _rope_at(q, cfg.rope_theta, positions)
        k = _rope_at(k, cfg.rope_theta, positions)
    return q, k, v


def _admit_attention(cfg: HybridMoEConfig, q, k, v, kind: str,
                     n_valid=None):
    """An admission's rows over each other (position 0, nothing cached).
    Rows from ``n_valid[b]`` on are the bucket's padding: the kernel
    leaves whole blocks of them out (their output is 0) and nobody reads
    the others."""
    from ..ops.pallas.window_attention import (windowed_prefill_active,
                                               windowed_prefill_attention,
                                               xla_windowed_attention)

    B, T = q.shape[:2]
    window = cfg.sliding_window if kind == WINDOW else None
    if windowed_prefill_active(T, cfg.head_dim):
        return windowed_prefill_attention(
            q, k.reshape(B, T, -1), v, window, n_valid,
            name=KERNEL_NAMES[kind, "admit"])
    heads = (B, T, cfg.num_kv_heads, cfg.head_dim)
    return xla_windowed_attention(q, k, v.reshape(heads), window)


def _tick_attention(cfg: HybridMoEConfig, q, kp, vp, layer: int, table, ctx,
                    q_len, kind: str):
    """One query a slot over layer ``layer`` of a cache's planes ``[L, P,
    rows, kv*d]``: keys ``0 .. ctx[b]`` of the pages ``table[b]`` names."""
    from ..ops.pallas.paged_attention import (paged_attention_active,
                                              ragged_paged_attention)

    rows = kp.shape[2]
    if paged_attention_active(rows, cfg.num_heads, cfg.num_kv_heads,
                              cfg.head_dim):
        return ragged_paged_attention(q, kp, vp, table, ctx, q_len,
                                      layer=layer,
                                      name=KERNEL_NAMES[kind, "tick"])
    held = (q.shape[0], table.shape[1] * rows, cfg.num_kv_heads,
            cfg.head_dim)
    return _dense_cache_attention(cfg, q, kp[layer, table].reshape(held),
                                  vp[layer, table].reshape(held),
                                  ctx[:, None])


@scoped("post")
def _post(x, o, lp):
    B, T = x.shape[:2]
    return x + o.reshape(B, T, -1) @ lp["wo"].astype(x.dtype)


def _ffn(cfg: HybridMoEConfig, x, lp, valid):
    """N2, the layer's FFN (dense or experts, by its parameters), add.
    Returns (x, the expert layer's counters over these rows or None, its
    picks [B*T, k] or None)."""
    B, T, H = x.shape
    h = _rms_norm(x, lp["n2"], cfg.rms_eps)
    if "router" not in lp:
        with jax.named_scope("dense_ffn"):
            return x + _swiglu(h, lp["w_gate"], lp["w_up"],
                               lp["w_down"]), None, None
    h2 = h.reshape(B * T, H)
    picks, w = latent_moe.route(cfg, h2, lp["router"])
    routed, counters = latent_moe._routed_experts(
        cfg, h2, picks, w, valid.reshape(B * T), lp)
    m = latent_moe._shared_expert(h2, lp) + routed
    return x + m.reshape(B, T, H), counters, picks


# ---------------------------------------------------------------------------
# Forward over both caches
# ---------------------------------------------------------------------------

PLANES = {FULL: ("k", "v"), WINDOW: ("wk", "wv")}


def _write(planes, kind: str, j: int, rows, where) -> None:
    """K rows and V rows into plane ``j`` of ``kind``'s cache, in place,
    at the index ``where`` after the layer's."""
    with jax.named_scope("kv_write"):
        for n, r in zip(PLANES[kind], rows):
            a = planes[n]
            planes[n] = a.at[(j,) + where].set(r.astype(a.dtype))


def _attention_scope(kind: str):
    return jax.named_scope("attention_full" if kind == FULL
                           else "attention_window")


def _loads(cfg: HybridMoEConfig, picks, valid):
    """The valid rows' picks each held expert received, [held] int32
    (``latent_moe._routed_experts``' ``sizes``, which it does not hand
    out: an admission sums them over its row blocks)."""
    e0, E = cfg.experts
    return jax.lax.reduce_sum(
        ((picks[..., None] == e0 + jnp.arange(E))
         & valid.reshape(-1, 1, 1)).astype(jnp.int32), (0, 1))


def _admit(params, tokens, cfg: HybridMoEConfig, n_valid, logit_pos,
           logits_all: bool):
    """An admission's rows through the stack, over each other (position
    0, nothing cached). Everything row-wise (norms, projections, rotary,
    the FFN, the experts) runs in blocks of ``C = ADMIT_BLOCK`` rows (the
    whole bucket where it is no multiple of that) under a trip count taken
    from the longest prompt, ``ceil(max(n_valid) / C)``: rows past the last
    block are never computed (their q / k / v are zeros, their ``x`` the
    embedding; nobody reads them): one loop a layer boundary, a layer's
    attention over the whole bucket between two of them. Returns (logits,
    what each layer keeps for its cache, the expert layers' counters, the
    rows computed a sequence): a full layer's K and V rows [B, T, kv*d], a
    window layer's last ``sliding_window`` of the prompt [B, W, kv*d] in
    ring order (row r: the prompt's last position that is r mod W; one
    before position 0 where the prompt is shorter: masked until a tick
    writes the row)."""
    B, T = tokens.shape
    W = cfg.sliding_window
    C = T if T % ADMIT_BLOCK else ADMIT_BLOCK
    n_blk = jnp.minimum(-(-jnp.max(n_valid) // C), T // C)
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    last = n_valid[:, None] - 1
    ring_src = jnp.clip(last - (last - jnp.arange(W)) % W, 0, T - 1)
    rows_in = n_valid.sum(dtype=jnp.int32)

    # (a block's start is never negative: no index to normalize, at every
    # start's trace)
    def block(a, start):
        return jax.lax.dynamic_slice_in_dim(a, start, C, 1,
                                            allow_negative_indices=False)

    def put(a, rows, start):
        return jax.lax.dynamic_update_slice_in_dim(
            a, rows, start, 1, allow_negative_indices=False)

    def qkv_rows(layer, xb, start, qkv):
        """q, k, v of layer ``layer`` for the block of rows ``xb`` that
        starts at ``start``, into the bucket-wide buffers ``qkv``."""
        at = jnp.broadcast_to(start + jnp.arange(C), (B, C))
        rows = _qkv(cfg, xb, params["layers"][layer], at,
                    cfg.kinds[layer] in ROTARY_KINDS)
        return tuple(put(a, r, start) for a, r in zip(qkv, rows))

    def no_qkv():
        return tuple(jnp.zeros((B, T) + shape, x.dtype) for shape in (
            (cfg.num_heads, cfg.head_dim), (cfg.num_kv_heads, cfg.head_dim),
            (cfg.kv_width,)))

    def enter(i, qkv):
        start = i * C
        return qkv_rows(0, block(x, start), start, qkv)

    # one loop a layer boundary: a block's rows leave layer l - 1 (the
    # output projection, the FFN) and enter layer l (q, k, v) in one trip
    qkv = jax.lax.fori_loop(0, n_blk, enter, no_qkv())
    keeps, moe = [], jnp.zeros((4,), jnp.int32)
    for layer, (lp, kind) in enumerate(zip(params["layers"], cfg.kinds)):
        q, k, v = qkv
        with _attention_scope(kind):
            o = _admit_attention(cfg, q, k, v, kind, n_valid)
        rows = (k.reshape(B, T, -1), v)
        if kind == WINDOW:
            rows = tuple(jnp.take_along_axis(r, ring_src[..., None], axis=1)
                         for r in rows)
        keeps.append(rows)

        def leave(i, carry):
            x, loads, qkv = carry
            start = i * C
            valid = start + jnp.arange(C) < n_valid[:, None]
            xb, _, picks = _ffn(
                cfg, _post(block(x, start), block(o, start), lp), lp, valid)
            if picks is not None:
                loads = loads + _loads(cfg, picks, valid)
            if qkv:
                qkv = qkv_rows(layer + 1, xb, start, qkv)
            return put(x, xb, start), loads, qkv

        x, loads, qkv = jax.lax.fori_loop(0, n_blk, leave, (
            x, jnp.zeros((cfg.experts[1],), jnp.int32),
            no_qkv() if layer + 1 < cfg.num_layers else ()))
        if "router" in lp:
            # the layer's counters A STEP: a held expert counts once
            # however many blocks hit it, the largest load is the
            # admission's
            moe = _merge(moe, jnp.stack([
                cfg.num_experts_per_tok * rows_in, loads.sum(),
                (loads > 0).sum(), loads.max()]).astype(jnp.int32))
    return _head_logits(cfg, params, x, False, logit_pos, logits_all), \
        keeps, moe, n_blk * C


def _tick(params, tokens, cfg: HybridMoEConfig, planes, table, part, where,
          pos, q_len, logits_all: bool):
    """One token a slot at position ``pos[b]`` through the stack: every
    layer writes its row to its kind's cache (``where``: the full layers'
    index; the window layers write row ``pos mod W`` of the part) and
    attends it there. ``planes`` is updated in place. Returns (logits, the
    expert layers' counters)."""
    B = tokens.shape[0]
    W = cfg.sliding_window
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    valid = (q_len > 0)[:, None]
    moe = jnp.zeros((4,), jnp.int32)
    for lp, kind, j in zip(params["layers"], cfg.kinds, cfg.plane_index):
        q, k, v = _qkv(cfg, x, lp, pos[:, None], kind in ROTARY_KINDS)
        if kind == FULL:
            _write(planes, kind, j, (k.reshape(B, 1, -1), v), where)
            pages, ctx = table, pos
        else:
            _write(planes, kind, j, (k.reshape(B, -1), v[:, 0]),
                   (part, pos % W))
            pages, ctx = part[:, None], jnp.minimum(pos, W - 1)
        with _attention_scope(kind):
            o = _tick_attention(cfg, q, *(planes[n] for n in PLANES[kind]),
                                j, pages, ctx, q_len, kind)
        x, c, _ = _ffn(cfg, _post(x, o, lp), lp, valid)
        if c is not None:
            moe = _merge(moe, c)
    return _head_logits(cfg, params, x, False, None, logits_all), moe


def forward_with_pages(params, tokens, cfg: HybridMoEConfig, pool,
                       page_table, pos, live=None, logit_pos=None,
                       logits_all=False, with_counters=False):
    """``llama.forward_with_pages``' contract over the two caches: tokens
    [B, T] at positions ``pos[b] .. pos[b]+T-1``; ``page_table`` [B,
    max_pages + 1]: the slot's row pages, then its fixed part's id. ``T ==
    1`` is a tick at any position; ``T > 1`` is an admission and ``pos``
    MUST BE 0 (its rows attend each other and nothing cached, and are
    written afterwards; only the row blocks up to the longest prompt's end
    are computed: ``_admit``); rows past ``logit_pos`` (the bucket's
    padding, computed or left at zero) are written to the row pages beyond
    the prompt, where the ticks overwrite them, and never to the fixed
    part. Dead slots (``live``) and positions past the table write the
    trash page and the trash part. Every plane is written in place.
    Returns (logits, pool), and with ``with_counters`` the step's
    ``SEGMENT_COUNTERS`` [8] int32 (``admit_rows``: the rows an admission
    computed, ``B`` x its blocks' rows)."""
    B, T = tokens.shape
    planes = dict(pool)
    psz = planes["k"].shape[2]
    table, part = page_table[:, :-1], page_table[:, -1]
    max_pages = table.shape[1]
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    positions = pos[:, None] + jnp.arange(T)
    vpage = positions // psz
    phys = jnp.take_along_axis(table, jnp.minimum(vpage, max_pages - 1),
                               axis=1)
    writable = vpage < max_pages
    valid = jnp.ones((B, T), bool)
    if live is not None:
        writable = writable & live[:, None]
        valid = valid & live[:, None]
        part = jnp.where(live, part, 0)
    if logit_pos is not None and not logits_all:
        valid = valid & (jnp.arange(T)[None, :]
                         <= jnp.reshape(logit_pos, (-1, 1)))
    where = (jnp.where(writable, phys, 0), positions % psz)
    n_valid = valid.sum(1, dtype=jnp.int32)
    zero = jnp.int32(0)
    if T > 1:
        logits, keeps, moe, computed = _admit(params, tokens, cfg, n_valid,
                                              logit_pos, logits_all)
        for kind, j, rows in zip(cfg.kinds, cfg.plane_index, keeps):
            _write(planes, kind, j, rows, where if kind == FULL else (part,))
        cache = [zero, zero, B * computed, n_valid.sum(dtype=jnp.int32)]
    else:
        logits, moe = _tick(params, tokens, cfg, planes, table, part, where,
                            pos, n_valid, logits_all)
        ctx = jnp.where(n_valid > 0, pos + 1, 0)
        cache = [len(cfg.layers_of(FULL)) * ctx.sum(dtype=jnp.int32),
                 len(cfg.layers_of(WINDOW)) * jnp.minimum(
                     ctx, cfg.sliding_window).sum(dtype=jnp.int32),
                 zero, zero]
    if not with_counters:
        return logits, planes
    return logits, planes, jnp.concatenate([moe, jnp.stack(cache)])
