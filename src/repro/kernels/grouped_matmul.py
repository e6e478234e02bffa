"""Grouped (per-expert) matmul of a MoE layer's routed rows, Pallas.

The rows arrive sorted by expert, each expert's group padded with zero rows
to whole tiles of ``TM`` rows, so every row tile belongs to one expert:
``tile_expert[t]`` names it, and only the first ``n_tiles[0]`` tiles are in
use. The grid walks the row tiles in order; a tile's weight block is its
expert's whole (K, N) matrix, and consecutive tiles of one expert keep the
same block index, which Pallas does not copy again. So each expert that
received a row streams its weights from HBM once, and no other expert's
are read: the layer costs the weights of the experts hit, not of all of
them. Tiles past the used ones compute nothing and keep the last used
tile's blocks (nothing is copied for them); their output rows are left
unwritten, and the caller never reads them.

``grouped_matmul`` dispatches to the kernel (TPU / interpret mode) or, on
the CPU, to ``jax.lax.ragged_dot`` over the same padded groups.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TM = 16  # rows per tile: one bf16 sublane tile
# scoped VMEM: two (2048, 1408) bf16 weight blocks (gate and up), double-buffered
VMEM_BYTES = 64 * 2**20


def tiles_for(rows: int, groups: int) -> int:
    """Row tiles that hold ``rows`` rows in ``groups`` groups, each group
    padded to whole tiles, whatever the split: sum ceil(s_e / TM) <=
    (rows + groups (TM - 1)) / TM."""
    return (rows + groups * (TM - 1)) // TM


def _kernel(tile_expert_ref, n_tiles_ref, layer_ref, x_ref, *refs, act):
    *w_refs, o_ref = refs

    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _():
        x = x_ref[...]
        ys = [jnp.dot(x, w[0], preferred_element_type=jnp.float32) for w in w_refs]
        y = ys[0] if len(ys) == 1 else act(ys[0]) * ys[1]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def moe_grouped_matmul(x, ws: Sequence[jax.Array], tile_expert, n_tiles, layer, *,
                       act=None, interpret: bool = False):
    """x: (T*TM, K) rows in tile-aligned groups; ws: one (L, E, K, N)
    weight stacked over layers, or two (gate, up) whose ``act(x Wg) * (x
    Wu)`` is returned, of which layer ``layer`` (1,) int32 is read (the
    kernel indexes the stack itself, so a layer scan need not copy its
    layer's experts out); tile_expert: (T,) int32 expert of each tile;
    n_tiles: (1,) int32 tiles in use. Returns (T*TM, N) in x's dtype (rows
    of unused tiles unwritten)."""
    rows, K = x.shape
    N = ws[0].shape[-1]
    T = rows // TM

    def tile(t, n_used):  # unused tiles keep the last used tile's blocks
        return jnp.minimum(t, jnp.maximum(n_used[0] - 1, 0))

    w_spec = pl.BlockSpec((None, 1, K, N),
                          lambda t, te, n, lay: (lay[0], te[tile(t, n)], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(T,),
        in_specs=[pl.BlockSpec((TM, K), lambda t, te, n, lay: (tile(t, n), 0))]
        + [w_spec] * len(ws),
        out_specs=pl.BlockSpec((TM, N), lambda t, te, n, lay: (t, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile_expert, n_tiles, layer, x, *ws)


def grouped_matmul(x, ws: Sequence[jax.Array], tile_expert, n_tiles, group_rows, *,
                   layer=None, act=None, use_kernel: bool = False,
                   interpret: bool = False):
    """The kernel on TPU (or in interpret mode); on the CPU
    ``jax.lax.ragged_dot`` over the same padded groups (``group_rows``:
    (E,) padded rows of each expert's group). ``ws`` are (E, K, N), or
    (L, E, K, N) stacked over layers with ``layer`` the traced index of the
    one to use."""
    if layer is None:
        ws, layer = [w[None] for w in ws], 0
    if use_kernel or interpret:
        return moe_grouped_matmul(x, tuple(ws), tile_expert, n_tiles,
                                  jnp.reshape(layer, (1,)).astype(jnp.int32),
                                  act=act, interpret=interpret)
    ys = [jax.lax.ragged_dot(x, w[layer], group_rows) for w in ws]
    return ys[0] if len(ys) == 1 else (act(ys[0]) * ys[1]).astype(x.dtype)


def tile_layout(expert, n_experts: int, tiles: int):
    """Where each routed pair goes in the tile-aligned layout. ``expert``:
    (P,) expert of each pair, ``n_experts`` for a pair that joins no group.
    Returns (dest (P,) row of each pair, ``tiles * TM`` for none; per expert
    the group's rows (E,) and padded rows (E,); tile_expert (tiles,);
    n_tiles (1,))."""
    sizes = jnp.zeros((n_experts,), jnp.int32).at[expert].add(1, mode="drop")
    padded = -(-sizes // TM) * TM
    first = jnp.cumsum(padded) - padded  # each group's first row
    start = jnp.cumsum(sizes) - sizes  # its first pair in expert order
    order = jnp.argsort(expert, stable=True)
    e_sorted = expert[order]
    e_c = jnp.minimum(e_sorted, n_experts - 1)
    rank = jnp.arange(expert.shape[0], dtype=jnp.int32) - start[e_c]
    dest_sorted = jnp.where(e_sorted < n_experts, first[e_c] + rank, tiles * TM)
    dest = jnp.zeros_like(dest_sorted).at[order].set(dest_sorted)
    ends = jnp.cumsum(padded) // TM  # tiles up to each group's end
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles, dtype=jnp.int32), side="right"),
        n_experts - 1).astype(jnp.int32)
    return dest, sizes, padded, tile_expert, (ends[-1:]).astype(jnp.int32)
