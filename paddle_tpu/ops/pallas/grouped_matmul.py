"""Grouped expert matmul — the routed experts a chip HOLDS, and only those
that received a token.

An expert-parallel share holds ``E`` of a layer's routed experts. A step's
tokens pick experts over the whole router width; the picks that land on a
held expert are laid out SORTED BY EXPERT in a row buffer whose groups
start at multiples of ``ROW_TILE`` (``sort_picks``), so every row tile
belongs to one expert and a scalar-prefetched ``tile_expert`` table routes
the weight block's index map: a tile's step streams ITS expert's weights,
consecutive tiles of one expert re-use the block already in VMEM, tiles
past the last used one re-name it and compute nothing. An expert nobody
picked has no tile and its weights are never read; nothing stands in for
the experts that live on other chips. No token is dropped: the buffer is
sized for the worst case (every pick held), and what a step costs follows
the picks it actually has.

Two calls a layer, one kernel (``name="grouped_expert_matmul"``):
``silu(x W_gate) * (x W_up)`` then ``h W_down``; the pick weights are
applied where the rows are combined back per token
(``latent_moe._routed_experts``). The grid is (output block, row tile)
with the tiles innermost, the contraction unblocked: the blocks are wide
(MB of weights a step) because the work is one stream of weights with a
few rows against it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import flags

__all__ = ["grouped_expert_matmul", "grouped_matmul_active", "sort_picks",
           "ROW_TILE"]

# tests set this True to force the kernel (pallas interpret mode) on CPU
FORCE_INTERPRET = False

ROW_TILE = 32                    # rows of one tile: one expert's
BLOCK_BYTES = 8 * 2**20          # a weight block's budget in VMEM
VMEM_LIMIT = 64 * 2**20          # two weights x two buffers of such blocks


def sort_picks(local, valid, n_experts: int):
    """Lay picks out by held expert. ``local`` [N] int32: a pick's expert,
    counted from the first held one; ``valid`` [N] bool: the pick is of a
    held expert and of a live token. Returns

      row [N] int32         the pick's row in the buffer (0 where not valid)
      sizes [E] int32       picks of each held expert
      tile_expert [tiles]   the expert of each row tile (tiles past the
                            last used one repeat its expert)
      n_tiles [1] int32     row tiles in use

    for a buffer of ``buffer_rows(N, n_experts)`` rows: group ``e`` starts
    at a multiple of ``ROW_TILE`` and its picks keep their order."""
    hot = (local[:, None] == jnp.arange(n_experts)[None, :]) & valid[:, None]
    hot = hot.astype(jnp.int32)                            # [N, E]
    sizes = hot.sum(0)
    rank = jnp.sum((jnp.cumsum(hot, 0) - 1) * hot, -1)     # order in group
    padded = -(-sizes // ROW_TILE) * ROW_TILE
    ends = jnp.cumsum(padded)
    starts = ends - padded
    row = jnp.where(valid, jnp.sum(hot * starts[None, :], -1) + rank, 0)
    n_tiles = ends[-1] // ROW_TILE
    tiles = buffer_rows(local.shape[0], n_experts) // ROW_TILE
    first_row = jnp.minimum(jnp.arange(tiles), jnp.maximum(n_tiles - 1, 0)) \
        * ROW_TILE
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, first_row, side="right"), n_experts - 1)
    return (row.astype(jnp.int32), sizes,
            tile_expert.astype(jnp.int32), n_tiles.reshape(1))


def buffer_rows(n_picks: int, n_experts: int) -> int:
    """Rows that hold ``n_picks`` picks however they fall: every group may
    end in a part-filled tile."""
    return -(-n_picks // ROW_TILE) * ROW_TILE + n_experts * ROW_TILE


def _block_cols(k: int, n: int, itemsize: int) -> int:
    """The widest multiple of 128 dividing ``n`` whose [k, cols] block
    fits BLOCK_BYTES."""
    best = 128
    for cols in range(128, n + 1, 128):
        if n % cols == 0 and k * cols * itemsize <= BLOCK_BYTES:
            best = cols
    return best


def grouped_expert_matmul(x, weights, tile_expert, n_tiles, *,
                          swiglu: bool = False, layer=None,
                          interpret: bool = False):
    """x [rows, K] laid out by ``sort_picks``; ``weights``: one [E, K, N]
    array, or (gate, up) with ``swiglu`` — or, with ``layer`` (an int32
    scalar, traced or not), the layers' STACKED weights [L, E, K, N], of
    which the kernel reads layer ``layer``'s blocks and nothing else (a
    layer sliced out of the stack outside the call is a copy of its
    experts, 1.5 GB at the published widths, every layer of every step).
    Row tile i is multiplied by the weights of expert ``tile_expert[i]``;
    tiles from ``n_tiles[0]`` on are left as they lie (never read back).
    Returns [rows, N] in x.dtype, fp32 accumulation."""
    weights = tuple(weights) if swiglu else (weights,)
    if layer is None:      # one layer is a stack of one
        weights, layer = tuple(w[None] for w in weights), 0
    rows, K = x.shape
    _, E, Kw, N = weights[0].shape
    if Kw != K or rows % ROW_TILE or K % 128 or N % 128:
        raise ValueError(
            f"grouped matmul needs whole row tiles and lane-aligned "
            f"widths, got x{x.shape} w{weights[0].shape} - gate callers "
            f"with grouped_matmul_active")
    TN = _block_cols(K, N, weights[0].dtype.itemsize)
    n_w = len(weights)
    _selected["count"] += 1

    def kernel(te_ref, nt_ref, lay_ref, x_ref, *rest):
        w_refs, o_ref = rest[:n_w], rest[n_w]

        @pl.when(pl.program_id(1) < nt_ref[0])
        def _():
            xt = x_ref[...]
            acc = [jax.lax.dot_general(
                xt, w[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) for w in w_refs]
            out = jax.nn.silu(acc[0]) * acc[1] if swiglu else acc[0]
            o_ref[...] = out.astype(o_ref.dtype)

    def tile(i, nt_ref):          # tiles past the last used one: stay put
        return jnp.minimum(i, jnp.maximum(nt_ref[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N // TN, rows // ROW_TILE),
        in_specs=[pl.BlockSpec((ROW_TILE, K),
                               lambda n, i, te, nt, lay: (tile(i, nt), 0))]
        + [pl.BlockSpec((None, None, K, TN),
                        lambda n, i, te, nt, lay: (lay[0], te[i], 0, n))]
        * n_w,
        out_specs=pl.BlockSpec((ROW_TILE, TN),
                               lambda n, i, te, nt, lay: (tile(i, nt), n)),
    )
    return pl.pallas_call(
        kernel, name="grouped_expert_matmul", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret or (FORCE_INTERPRET and not _on_tpu()),
    )(tile_expert, n_tiles,
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), x, *weights)


_selected = {"count": 0}


def selection_count() -> int:
    return _selected["count"]


def _on_tpu() -> bool:
    from .flash_attention import _on_tpu as on_tpu

    return on_tpu()


def grouped_matmul_active(hidden: int, width: int) -> bool:
    """True when the held experts' matmuls run as the grouped kernel: TPU
    (or the test force), kernels enabled, one device, lane-aligned
    widths - else the dense masked formulation over the held experts."""
    from .flash_attention import _multi_device_mesh_active

    if not flags.get_flags(["use_pallas_kernels"])["use_pallas_kernels"]:
        return False
    if not (_on_tpu() or FORCE_INTERPRET):
        return False
    if _multi_device_mesh_active():
        return False
    return hidden % 128 == 0 and width % 128 == 0
