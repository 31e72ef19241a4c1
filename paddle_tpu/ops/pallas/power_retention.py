"""Degree-2 power retention: the recurrent STATE of a sequence as its page.

A power-retention layer keeps no cache rows. Per kv head it keeps a state
``S [D, Dv]`` and a sum of keys ``z [D]`` in float32, where ``D`` is the
width of an exact expansion ``phi`` of the squared dot product::

    phi(q) . phi(k) = (q . k)^2                      (EXACTLY)
    S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T;   z_t = e^{g_t} z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

with ``g_t <= 0`` the log of the token's decay (one a kv head) and query
head ``i`` reading the state of kv head ``i // group``. The caller scales
``q`` by ``1 / sqrt(head_dim)``, so the weights are ``(q . k / sqrt(d))^2``.

**phi** is the block-symmetric expansion: the key's ``d`` lanes are ``nb =
d / 16`` blocks of 16; for each of the ``nb (nb + 1) / 2`` block pairs ``a
<= b`` the 16 x 16 outer product ``x_a (x) x_b``, weighted ``sqrt 2`` off the
diagonal: ``sum_{a<=b} w^2 (q_a.k_a)(q_b.k_b) = (sum_a q_a.k_a)^2``. At
``d`` 128 that is 36 pairs x 256 = 9,216 = 72 x 128 lanes (the minimal
expansion has 8,256 entries and no such tiling; the full square has 16,384).
Row ``p * 256 + i * 16 + j`` of a state is pair ``p`` = (a, b), entry (i, j):
a state is 36 x 16 SLABS of ``[16, Dv]``, slab (p, i) the rows that share
``x_a[i]``.

**The planes** are ``s [L, P, Hkv, D, Dv]`` and ``z [L, P, Hkv, D]``: page
``p`` of layer ``l`` is ONE SEQUENCE'S state, whatever its length.

``power_retention_decode`` is a tick: one token a slot. The kernel walks
(slot, kv head, block of pairs) in order on one core; the ``s`` plane stays
in HBM (``memory_space=ANY``), is aliased to the output and is handed ONCE;
a live slot's block is copied in by hand (double-buffered: the next live
block is in flight while this one is computed; a FRESH slot's is then
zeroed: a reused page may hold anything, NaN too), decayed, given its rank-1
increment, read out for the group's query heads and copied back to where it
came from; a dead slot moves nothing. The arithmetic is the vector unit's,
in float32, a slab at a time: ``s' = dec * s + (w k_a[i]) * (k_b (x) v)``
and, per query head, ``acc += q_a[i] * s'``; after a pair's 16 slabs
``num += sum_j (w q_b[j]) acc[j]``. The scalars ``k_a[i]``, ``q_a[i]`` come
as rows of lane-splat tiles the caller builds (``x[m]`` on every lane of row
``m``): a row read with a sublane broadcast is the splat. ``z`` is 1/128 of
the state and is XLA's: gathered, updated, scattered in place.

``power_retention_chunked`` is the admission: ``lax.scan`` over chunks of
rows; inside a chunk the attention form ``tril(exp(G_t - G_s) (q.k)^2) v``,
across chunks the state (``exp(G_t) phi(q) S``), one state write at the end.
XLA compiles it. Rows that are not ``valid`` (a bucket's padding) add
nothing and decay nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["phi", "state_width", "power_retention_decode",
           "power_retention_chunked", "power_retention_active",
           "selection_count", "reset_selection_count"]

# tests set this True (via monkeypatch) to force the kernel — in pallas
# interpret mode — on the CPU backend, so parity runs where tier-1 runs
FORCE_INTERPRET = False

BLOCK = 16              # lanes of a key block
SLAB = BLOCK            # rows of a slab
PAIR_ROWS = BLOCK * BLOCK
PAIRS_A_COPY = 9        # block pairs a copy moves (2,304 rows, 1.18 MB)


def _pairs(head_dim: int):
    """(a, b, weight) of every block pair a <= b, in state order."""
    nb = head_dim // BLOCK
    a, b = np.triu_indices(nb)
    return a.astype(np.int32), b.astype(np.int32), \
        np.where(a == b, 1.0, np.sqrt(2.0)).astype(np.float32)


def state_width(head_dim: int) -> int:
    """``D``: entries of ``phi`` of a ``head_dim``-wide key."""
    if head_dim % BLOCK:
        raise ValueError(f"head_dim {head_dim} is not blocks of {BLOCK}")
    nb = head_dim // BLOCK
    return nb * (nb + 1) // 2 * PAIR_ROWS


def phi(x):
    """[..., d] -> [..., D] float32, ``phi(q) . phi(k) == (q . k)^2``."""
    d = x.shape[-1]
    a, b, w = _pairs(d)
    blocks = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // BLOCK, BLOCK))
    out = blocks[..., a, :, None] * blocks[..., b, None, :] \
        * w[:, None, None]
    return out.reshape(x.shape[:-1] + (state_width(d),))


# ---------------------------------------------------------------------------
# The admission: chunks of rows, XLA's
# ---------------------------------------------------------------------------

def power_retention_chunked(q, k, v, g, s0, z0, *, chunk: int = 128,
                            eps: float = 1e-6):
    """q [B, T, Hkv, G, d] (scaled), k [B, T, Hkv, d], v [B, T, Hkv, Dv],
    g [B, T, Hkv] float32 log decays (0 on rows that are not valid, whose
    ``k`` the caller has zeroed), ``s0`` [B, Hkv, D, Dv] / ``z0`` [B, Hkv,
    D] float32 the states the rows continue from. Returns (y [B, T, Hkv,
    G, Dv] in q.dtype, s [B, Hkv, D, Dv], z [B, Hkv, D])."""
    B, T, Hkv, G, d = q.shape
    Dv = v.shape[-1]
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"{T} rows are not whole chunks of {C}")
    n = T // C
    dt = q.dtype
    f32 = jnp.float32

    def chunks(x):
        return jnp.moveaxis(x.reshape((B, n, C) + x.shape[2:]), 1, 0)

    tril = jnp.tril(jnp.ones((C, C), bool))

    def one(carry, xs):
        s, z = carry
        qc, kc, vc, gc = xs
        G_t = jnp.cumsum(gc, axis=1)                       # [B, C, Hkv]
        # inside the chunk: the attention form
        sc = jnp.einsum("bthrd,bshd->bhrts", qc, kc,
                        preferred_element_type=f32)
        lg = jnp.moveaxis(G_t, 1, 2)                       # [B, Hkv, C]
        decay = jnp.exp(jnp.where(tril, lg[..., :, None] - lg[..., None, :],
                                  -jnp.inf))               # [B, Hkv, t, s]
        a = sc * sc * decay[:, :, None]
        num = jnp.einsum("bhrts,bshv->bthrv", a.astype(dt), vc,
                         preferred_element_type=f32)
        den = jnp.moveaxis(a.sum(-1), 3, 1)                # [B, t, Hkv, G]
        # across chunks: the state the chunk began with
        pq = phi(qc).astype(dt)                            # [B, C, Hkv, G, D]
        e_t = jnp.exp(G_t)[..., None]                      # [B, C, Hkv, 1]
        num = num + e_t[..., None] * jnp.einsum(
            "bthrd,bhdv->bthrv", pq, s.astype(dt),
            preferred_element_type=f32)
        den = den + e_t * jnp.einsum("bthrd,bhd->bthr", pq, z.astype(dt),
                                     preferred_element_type=f32)
        y = (num / (den[..., None] + eps)).astype(dt)
        # the state the next chunk begins with
        last = G_t[:, -1]                                  # [B, Hkv]
        pk = phi(kc) * jnp.exp(last[:, None] - G_t)[..., None]
        e_c = jnp.exp(last)
        s = e_c[..., None, None] * s + jnp.einsum(
            "bshd,bshv->bhdv", pk.astype(dt), vc, preferred_element_type=f32)
        z = e_c[..., None] * z + pk.sum(1)
        return (s, z), y

    (s, z), y = lax.scan(one, (s0.astype(f32), z0.astype(f32)),
                         (chunks(q), chunks(k), chunks(v),
                          chunks(g.astype(f32))))
    y = jnp.moveaxis(y, 0, 1).reshape(B, T, Hkv, G, Dv)
    return y, s, z


# ---------------------------------------------------------------------------
# The tick
# ---------------------------------------------------------------------------

def _splat(x, lanes: int):
    """[..., d] -> [..., d, lanes]: ``x[m]`` on every lane of row ``m``."""
    return jnp.broadcast_to(x[..., None].astype(jnp.float32),
                            x.shape + (lanes,))


def _make_kernel(B: int, Hkv: int, G: int, d: int, Dv: int, PB: int):
    n_pairs = d // BLOCK * (d // BLOCK + 1) // 2
    NB = n_pairs // PB                 # copies a (slot, head)
    RB = PB * PAIR_ROWS                # rows a copy

    def kernel(page_ref, live_ref, fresh_ref, nxt_ref, first_ref, lay_ref,
               pa_ref, pb_ref, dec_ref, v_ref, ks_ref, qs_ref, s_hbm, num_ref,
               s_out, inbuf, outbuf, insem, outsem, cnt_ref, u_ref, r_ref):
        b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        lay = lay_ref[0]
        live = live_ref[b] > 0

        def rows(blk):
            return pl.ds(pl.multiple_of(blk * RB, RB), RB)

        def copy_in(slot, head, blk, half):
            return pltpu.make_async_copy(
                s_hbm.at[lay, page_ref[slot], head, rows(blk)],
                inbuf.at[half], insem.at[half])

        def copy_out(slot, head, blk, half):
            return pltpu.make_async_copy(
                outbuf.at[half],
                s_out.at[lay, page_ref[slot], head, rows(blk)],
                outsem.at[half])

        @pl.when(~live)
        def _():
            num_ref[...] = jnp.zeros_like(num_ref)

        @pl.when(live)
        def _():
            first = (b == first_ref[0]) & (h == 0) & (i == 0)

            @pl.when(first)
            def _():
                cnt_ref[0] = 0
                copy_in(b, h, i, 0).start()

            cnt = cnt_ref[0]
            half = lax.rem(cnt, 2)
            # the live block after this one: this head's next, the next
            # head's first, the next live slot's first; none after the last
            more_blk = i + 1 < NB
            same_slot = more_blk | (h + 1 < Hkv)
            has_next = same_slot | (nxt_ref[b] < B)
            ni = lax.select(more_blk, i + 1, 0)
            nh = lax.select(more_blk, h, lax.select(h + 1 < Hkv, h + 1, 0))
            nb = lax.select(same_slot, b, lax.min(nxt_ref[b], B - 1))

            @pl.when(has_next)
            def _():
                copy_in(nb, nh, ni, 1 - half).start()

            copy_in(b, h, i, half).wait()

            # a fresh slot starts from zero: what its page held may be
            # anything, NaN too, and 0 x NaN is NaN
            @pl.when(fresh_ref[b] > 0)
            def _():
                inbuf[half] = jnp.zeros((RB, Dv), inbuf.dtype)

            # this half of the out buffer was sent two blocks ago
            @pl.when(cnt >= 2)
            def _():
                copy_out(b, h, i, half).wait()

            dec = dec_ref[0, pl.ds(h, 1), :]               # [1, Dv] splat
            v = v_ref[0, pl.ds(h, 1), :]                   # [1, Dv]

            @pl.when(i == 0)
            def _():
                # u[m, :] = k[m] * v: every pair's k_b (x) v is 16 rows of it
                u_ref[...] = ks_ref[0, 0] * v
                r_ref[...] = jnp.zeros_like(r_ref)

            def pair(pp, carry):
                p = i * PB + pp
                a, bb = pa_ref[p], pb_ref[p]
                w = lax.select(a == bb, jnp.float32(1.0),
                               jnp.float32(np.sqrt(2.0)))
                blk_b = pl.ds(pl.multiple_of(bb * BLOCK, BLOCK), BLOCK)
                uw = u_ref[blk_b, :] * w                   # [16, Dv]
                acc = [jnp.zeros((SLAB, Dv), jnp.float32) for _ in range(G)]
                for ii in range(BLOCK):
                    slab = pl.ds(pl.multiple_of(
                        pp * PAIR_ROWS + ii * SLAB, SLAB), SLAB)
                    row = pl.ds(a * BLOCK + ii, 1)
                    new = dec * inbuf[half, slab, :].astype(jnp.float32) \
                        + ks_ref[0, 0, row, :] * uw
                    outbuf[half, slab, :] = new.astype(outbuf.dtype)
                    for r in range(G):
                        acc[r] = acc[r] + qs_ref[0, 0, r, row, :] * new
                for r in range(G):
                    r_ref[r] = r_ref[r] + (qs_ref[0, 0, r, blk_b, :] * w) \
                        * acc[r]
                return carry

            lax.fori_loop(0, PB, pair, 0)
            copy_out(b, h, i, half).start()
            cnt_ref[0] = cnt + 1

            @pl.when(i == NB - 1)
            def _():
                num_ref[0, 0] = jnp.sum(r_ref[...], axis=1)

            # the last live block leaves nothing in flight
            @pl.when(~has_next)
            def _():
                copy_out(b, h, i, half).wait()

                @pl.when(cnt >= 1)
                def _():
                    copy_out(b, h, i, 1 - half).wait()

    return kernel, NB, RB


def _decode_kernel(q, k, v, dec, s, page, live, fresh, layer, interpret):
    """The ``s`` plane's half of a tick: returns (num [B, Hkv, G, Dv]
    float32, the plane). ``dec`` [B, Hkv] the factor the old state takes;
    a ``fresh`` slot's old state is taken as zero."""
    B, Hkv, G, d = q.shape
    Dv = v.shape[-1]
    a, bb, _ = _pairs(d)
    PB = PAIRS_A_COPY if len(a) % PAIRS_A_COPY == 0 else len(a)
    kernel, NB, RB = _make_kernel(B, Hkv, G, d, Dv, PB)
    i32 = jnp.int32
    live = jnp.asarray(live).astype(i32)
    # the next live slot after each, and the first (B where there is none)
    idx = jnp.where(live > 0, jnp.arange(B, dtype=i32), B)
    after = lax.cummin(jnp.concatenate([idx[1:], jnp.full((1,), B, i32)]),
                       reverse=True)
    _selected["count"] += 1  # trace-time: once per compiled program

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(B, Hkv, NB),
        in_specs=[
            pl.BlockSpec((1, Hkv, Dv), lambda b, h, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, Hkv, Dv), lambda b, h, i, *_: (b, 0, 0)),
            pl.BlockSpec((1, 1, d, Dv), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, d, Dv),
                         lambda b, h, i, *_: (b, h, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, Dv), lambda b, h, i, *_: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, RB, Dv), s.dtype),          # blocks coming in
            pltpu.VMEM((2, RB, Dv), s.dtype),          # blocks going out
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), i32),                     # live blocks so far
            pltpu.VMEM((d, Dv), jnp.float32),          # k (x) v
            pltpu.VMEM((G, SLAB, Dv), jnp.float32),    # the numerators
        ],
    )
    num, s = pl.pallas_call(
        kernel,
        name="power_retention_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(s.shape, s.dtype)],
        # operand 12 (after the 8 prefetched scalars, dec, v, ks, qs) is
        # the plane, updated where it lies
        input_output_aliases={12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret or (FORCE_INTERPRET and not _on_tpu()),
    )(jnp.asarray(page, i32), live, jnp.asarray(fresh).astype(i32), after,
      jnp.min(idx).reshape(1),
      jnp.reshape(jnp.asarray(layer, i32), (1,)), jnp.asarray(a),
      jnp.asarray(bb),
      jnp.broadcast_to(dec.astype(jnp.float32)[..., None], (B, Hkv, Dv)),
      v.astype(jnp.float32), _splat(k, Dv), _splat(q, Dv), s)
    return num, s


def power_retention_decode(q, k, v, g, s, z, page, live, fresh, *, layer=0,
                           eps: float = 1e-6, interpret: bool = False):
    """One token a slot. q [B, Hkv, G, d] (scaled), k [B, Hkv, d], v [B,
    Hkv, Dv], g [B, Hkv] float32 log decays; ``s`` / ``z`` the planes where
    they lie, of which layer ``layer`` (int32 scalar, traced or not), page
    ``page[b]`` is slot b's state; ``live`` [B]: the slot's state is
    updated (a dead slot's is not touched: the kernel moves nothing for it,
    the fallback writes the trash page 0); ``fresh`` [B]: the state starts
    from zero whatever the page holds. Returns (y [B, Hkv, G, Dv] in
    q.dtype, s, z)."""
    f32 = jnp.float32
    dec = jnp.where(fresh[:, None], 0.0, jnp.exp(g.astype(f32)))  # [B, Hkv]
    page = jnp.where(live, page, 0)
    pk, pq = phi(k), phi(q)
    if power_retention_active(q.shape[-1], v.shape[-1]) or interpret:
        num, s = _decode_kernel(q, k, v, dec, s, page, live, fresh, layer,
                                interpret)
    else:
        old = s[layer, page].astype(f32)                   # [B, Hkv, D, Dv]
        # ``where``, not 0 x old: a fresh slot's page may hold anything
        new = jnp.where(fresh[:, None, None, None], 0.0,
                        dec[..., None, None] * old) \
            + pk[..., None] * v.astype(f32)[:, :, None, :]
        num = jnp.einsum("bhrd,bhdv->bhrv", pq, new,
                         precision=lax.Precision.HIGHEST)
        s = s.at[layer, page].set(new.astype(s.dtype))
    zo = z[layer, page].astype(f32)                        # [B, Hkv, D]
    zn = jnp.where(fresh[:, None, None], 0.0, dec[..., None] * zo) + pk
    den = jnp.einsum("bhrd,bhd->bhr", pq, zn,
                     precision=lax.Precision.HIGHEST)
    z = z.at[layer, page].set(zn.astype(z.dtype))
    y = num / (den[..., None] + eps)
    y = jnp.where(live[:, None, None, None], y, 0.0)
    return y.astype(q.dtype), s, z


# trace-time selection counter (the paged kernels' contract): tests and the
# benchmark assert the kernel was routed to without a chip
_selected = {"count": 0}


def selection_count() -> int:
    return _selected["count"]


def reset_selection_count() -> None:
    _selected["count"] = 0


def _on_tpu() -> bool:
    from .flash_attention import _on_tpu as on_tpu

    return on_tpu()


def power_retention_active(head_dim: int, v_dim: int) -> bool:
    """True when the decode kernel serves this state: TPU (or the test
    force), kernels enabled, one device, whole key blocks and (on the
    chip) a lane-wide value — else gather, update, scatter in XLA."""
    from .flash_attention import _multi_device_mesh_active

    if not flags.get_flags(["use_pallas_kernels"])["use_pallas_kernels"]:
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    return head_dim % BLOCK == 0 and (FORCE_INTERPRET and not _on_tpu()
                                      or v_dim % 128 == 0)
