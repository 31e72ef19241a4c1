"""Power-retention decoder (the Brumby layer family), served through the
paged engine with A SEQUENCE'S RECURRENT STATE AS ITS ONE PAGE.

The block is the Qwen3-shaped one — pre-norm, per-head RMSNorm on q and k,
RoPE, SwiGLU, no biases but the gate's, an untied head — whose attention
sublayer is a gated degree-2 power retention (the equations
``tests/reference_power_retention.py`` writes out in the attention form, in
plain float32). Per layer, ``x`` the residual stream, ``h = N1(x)``::

    q = Nq(h W_q) [T, heads, d];  k = Nk(h W_k) [T, kv, d];  v = h W_v
    q, k = RoPE(q, k)
    g_t = logsigmoid(h_t W_g + b_g)   float32, one a kv head: the log decay
    a_ts = exp(g_{s+1} + ... + g_t) (q_t . k_s / sqrt(d))^2,  s <= t
    y_t = sum_s a_ts v_s / (sum_s a_ts + eps)
    x = x + concat_heads(y) W_o;   x = x + SwiGLU(N2(x))

No softmax and no cache rows: ``ops/pallas/power_retention.py`` keeps, per
kv head, ``S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T`` and ``z_t`` likewise
(``phi(q) . phi(k) = (q . k)^2`` exactly, ``D = state_width(d)`` wide) and
reads ``y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)``; query head ``i``
reads the state of kv head ``i // group``.

**The pool** is ``{"s": [L, num_pages, kv, D, d], "z": [L, num_pages, kv,
D]}`` in ``state_dtype``: a page is one sequence's state in every layer,
whatever its length (37.9 MB a layer at d 128; a cache row of a GQA decoder
of these widths is 4 KB). The engine is built with ``page_size = max_len``,
so a slot's table is ONE page id, ``page_table[b, 0]``; page 0 is the trash
page. What a state page asks of ``forward_with_pages`` beyond llama's
contract:

- ``pos[b] == 0`` starts from a zero state whatever the page held (a freed
  page is reused without a host-side clear); ``pos[b] > 0`` continues;
- rows past ``logit_pos`` (a bucket's padding) add nothing to the state and
  do not decay it;
- a dead slot (``live`` false) never touches a page that is not the trash
  page (the decode kernel moves nothing for it at all).

A tick (``T == 1``) updates the live slots' pages in place through the
``power_retention_decode`` kernel; an admission (``T > 1``) runs the chunked
form and writes the page once.

What the serving engine asks of a model module (``models.family_of``):
``init_params``, ``init_paged_pool``, ``page_bytes``,
``paged_kernel_active``, ``forward_with_pages``, ``SERVING_FAMILIES`` and,
optionally, ``COUNTER_GROUPS`` (with ``SEGMENT_COUNTERS``, its columns).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .latent_moe import _normal
from .llama import _head_logits, _rms_norm, _rope_at, scoped

__all__ = ["PowerRetentionConfig", "init_params", "init_paged_pool",
           "page_bytes", "paged_kernel_active", "forward_with_pages",
           "SERVING_FAMILIES", "SEGMENT_COUNTERS", "COUNTER_GROUPS"]

# the one serving family this model is served by (``models.require``)
SERVING_FAMILIES = ("paged",)
# a step counts: state pages a tick updated (its live slots), and an
# admission's bucket rows and those of them that are the prompt's
COUNTER_GROUPS = {             # ``serving.retention.*``
    "retention": ("state_pages", "admit_rows", "admit_rows_used")}
SEGMENT_COUNTERS = sum(COUNTER_GROUPS.values(), ())
# the layer is the DEGREE-2 one (``phi`` expands the square of the dot
# product); neither constant below is a key of the public config.json
RETENTION_EPS = 1e-6            # the normaliser's
GATE_BIAS = 5.0                 # b_g at init: a decay of ~0.993 a token


@dataclasses.dataclass(frozen=True)
class PowerRetentionConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_layers: int = 40
    num_heads: int = 40
    num_kv_heads: int = 8
    head_dim: int = 128
    state_dtype: Any = jnp.float32
    # rows a chunk of the admission's scan (a field so that a bucket of 16
    # rows still crosses chunks at the tests' size)
    prefill_chunk: int = 128
    max_seq_len: int = 2048
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("query heads must be whole groups of kv heads")

    @property
    def group(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def state_width(self) -> int:
        from ..ops.pallas.power_retention import state_width

        return state_width(self.head_dim)

    @classmethod
    def tiny(cls, **kw):
        """Tiny config for tests: 2 layers, H 128, 4 / 2 heads x 32."""
        d = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                 num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
                 prefill_chunk=8, max_seq_len=64, rope_theta=10000.0,
                 dtype=jnp.float32)
        d.update(kw)
        return cls(**d)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(cfg: PowerRetentionConfig, key: Optional[jax.Array] = None,
                dtype: Any = None) -> Dict[str, Any]:
    """``embed``, ``lm_head``, ``ln_f`` and ``layers`` (stacked over the
    layers). The gate is float32 whatever ``dtype``."""
    if key is None:
        key = jax.random.PRNGKey(0)
    dtype = dtype or jnp.float32
    H, F, V, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_layers)
    nq, nkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 10)
    ones = lambda *s: jnp.ones((L,) + s, dtype)
    layers = {
        "wq": _normal(ks[2], (L, H, nq), H, dtype),
        "wk": _normal(ks[3], (L, H, nkv), H, dtype),
        "wv": _normal(ks[4], (L, H, nkv), H, dtype),
        "wo": _normal(ks[5], (L, nq, H), nq, dtype),
        "wg": _normal(ks[6], (L, H, cfg.num_kv_heads), H, jnp.float32),
        "bg": jnp.full((L, cfg.num_kv_heads), GATE_BIAS, jnp.float32),
        "w_gate": _normal(ks[7], (L, H, F), H, dtype),
        "w_up": _normal(ks[8], (L, H, F), H, dtype),
        "w_down": _normal(ks[9], (L, F, H), F, dtype),
        "nq": ones(cfg.head_dim), "nk": ones(cfg.head_dim),
        "n1": ones(H), "n2": ones(H),
    }
    return {
        "embed": (_normal(ks[0], (V, H), 1, jnp.float32) * 0.02
                  ).astype(dtype),
        "lm_head": _normal(ks[1], (H, V), H, dtype),
        "ln_f": jnp.ones((H,), dtype),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# The pool of state pages
# ---------------------------------------------------------------------------

def init_paged_pool(cfg: PowerRetentionConfig, num_pages: int,
                    page_size: int, dtype=None,
                    quant=None) -> Dict[str, jax.Array]:
    """``{"s": [L, num_pages, kv, D, d], "z": [L, num_pages, kv, D]}``; a
    page is a sequence's state, so ``page_size`` (the rows a page stands
    for) sizes nothing; page 0 is the allocator's trash page."""
    if quant is not None:
        raise ValueError("a state page has no quantized form")
    dtype = dtype or cfg.state_dtype
    lead = (cfg.num_layers, num_pages, cfg.num_kv_heads, cfg.state_width)
    return {"s": jnp.zeros(lead + (cfg.head_dim,), dtype),
            "z": jnp.zeros(lead, dtype)}


def page_bytes(cfg: PowerRetentionConfig, page_size: int, quant=None) -> int:
    """Bytes one state page occupies across all layers."""
    return cfg.num_layers * cfg.num_kv_heads * cfg.state_width \
        * (cfg.head_dim + 1) * jnp.dtype(cfg.state_dtype).itemsize


def paged_kernel_active(cfg: PowerRetentionConfig, page_size: int) -> bool:
    from ..ops.pallas.power_retention import power_retention_active

    return power_retention_active(cfg.head_dim, cfg.head_dim)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

@scoped("retention_qkv")
def _retention_qkv(cfg: PowerRetentionConfig, x, lp, positions):
    """N1, the three projections, the per-head norms, RoPE. Returns (h, q
    [B, T, kv, group, d] scaled by 1 / sqrt(d), k [B, T, kv, d], v)."""
    dt = x.dtype
    B, T = x.shape[:2]
    d = cfg.head_dim
    h = _rms_norm(x, lp["n1"], cfg.rms_eps)
    q = (h @ lp["wq"].astype(dt)).reshape(B, T, cfg.num_heads, d)
    k = (h @ lp["wk"].astype(dt)).reshape(B, T, cfg.num_kv_heads, d)
    v = (h @ lp["wv"].astype(dt)).reshape(B, T, cfg.num_kv_heads, d)
    q = _rope_at(_rms_norm(q, lp["nq"], cfg.rms_eps), cfg.rope_theta,
                 positions)
    k = _rope_at(_rms_norm(k, lp["nk"], cfg.rms_eps), cfg.rope_theta,
                 positions)
    q = q * jnp.asarray(1.0 / np.sqrt(d), dt)
    return h, q.reshape(B, T, cfg.num_kv_heads, cfg.group, d), k, v


@scoped("gate")
def _gate(h, lp, valid):
    """The log decays [B, T, kv] in float32; 0 (no decay) on rows that
    are not ``valid``."""
    g = jax.nn.log_sigmoid(
        jnp.dot(h.astype(jnp.float32), lp["wg"],
                precision=jax.lax.Precision.HIGHEST) + lp["bg"])
    return jnp.where(valid[..., None], g, 0.0)


@scoped("retention")
def _retention(cfg: PowerRetentionConfig, q, k, v, g, s, z, layer, page,
               fresh, valid):
    """The sublayer over layer ``layer`` of the state planes: a tick
    through the decode kernel, more rows through the chunked form."""
    from ..ops.pallas.power_retention import (power_retention_chunked,
                                              power_retention_decode)

    if q.shape[1] == 1:
        y, s, z = power_retention_decode(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], s, z, page, valid[:, 0],
            fresh, layer=layer, eps=RETENTION_EPS)
        return y[:, None], s, z
    k = jnp.where(valid[..., None, None], k, 0)
    keep = ~fresh
    s0 = jnp.where(keep[:, None, None, None], s[layer, page], 0)
    z0 = jnp.where(keep[:, None, None], z[layer, page], 0)
    y, s1, z1 = power_retention_chunked(
        q, k, v, g, s0, z0, chunk=cfg.prefill_chunk, eps=RETENTION_EPS)
    s = s.at[layer, page].set(s1.astype(s.dtype))
    z = z.at[layer, page].set(z1.astype(z.dtype))
    return y, s, z


@scoped("post")
def _post(x, y, lp):
    dt = x.dtype
    B, T = x.shape[:2]
    return x + y.reshape(B, T, -1) @ lp["wo"].astype(dt)


@scoped("ffn")
def _ffn(cfg: PowerRetentionConfig, x, lp):
    dt = x.dtype
    h = _rms_norm(x, lp["n2"], cfg.rms_eps)
    m = jax.nn.silu(h @ lp["w_gate"].astype(dt)) * (h @ lp["w_up"].astype(dt))
    return x + m @ lp["w_down"].astype(dt)


# ---------------------------------------------------------------------------
# Forward over pages
# ---------------------------------------------------------------------------

def forward_with_pages(params, tokens, cfg: PowerRetentionConfig, pool,
                       page_table, pos, live=None, logit_pos=None,
                       logits_all=False, with_counters=False):
    """``llama.forward_with_pages``' contract over state pages: tokens
    [B, T] at positions ``pos[b] .. pos[b]+T-1``; the state of row ``b``
    is page ``page_table[b, 0]``, started from zero where ``pos[b] == 0``;
    rows past ``logit_pos`` leave the state alone; dead slots (``live``)
    touch no page but the trash page. The planes ride the carry of ONE
    ``lax.scan`` over the stacked layers and are updated where they lie.
    Returns (logits, pool), and with ``with_counters`` the step's
    ``SEGMENT_COUNTERS`` [3] int32."""
    dt = cfg.dtype
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    positions = pos[:, None] + jnp.arange(T)
    valid = jnp.ones((B, T), bool)
    if live is not None:
        valid = valid & live[:, None]
    if logit_pos is not None and not logits_all:
        valid = valid & (jnp.arange(T)[None, :]
                         <= jnp.reshape(logit_pos, (-1, 1)))
    # a row with nothing to add (a dead slot) reads and writes the trash page
    page = jnp.where(valid.any(1), page_table[:, 0], 0)
    fresh = pos == 0

    def layer(carry, xs):
        x, s, z = carry
        lp, i = xs
        h, q, k, v = _retention_qkv(cfg, x, lp, positions)
        g = _gate(h, lp, valid)
        y, s, z = _retention(cfg, q, k, v, g, s, z, i, page, fresh, valid)
        x = _ffn(cfg, _post(x, y, lp), lp)
        return (x, s, z), None

    (x, s, z), _ = jax.lax.scan(
        layer, (x, pool["s"], pool["z"]),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    logits = _head_logits(cfg, params, x, False, logit_pos, logits_all)
    pool = {"s": s, "z": z}
    if with_counters:
        rows = valid.sum(dtype=jnp.int32)
        tick = T == 1
        counters = jnp.stack([
            rows if tick else jnp.int32(0),
            jnp.int32(0 if tick else B * T),
            jnp.int32(0) if tick else rows])
        return logits, pool, counters
    return logits, pool
