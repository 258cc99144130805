"""Tile-leapfrog sorted-set intersection — the LFTJ inner loop on TPU.

The scalar leapfrog gallops over two sorted lists, skipping runs that
cannot match.  A systolic/vector machine cannot pointer-chase, so the skip
is lifted to *tile granularity*: for each (A-tile, B-tile) pair the kernel
first compares the tiles' min/max bounds — disjoint ranges are skipped
wholesale (``pl.when`` on a scalar), the vector analogue of a Minesweeper
gap box — and only overlapping tiles pay the dense 8×128 VPU membership
compare.  Sortedness makes the expected number of surviving tile pairs
linear in the tile count (the classic merge-path argument), so the kernel
does ``O((LA+LB)/128)`` tile visits instead of ``O(LA·LB/128²)``.

Layout: per frontier row, two padded sorted int32 lists.  Grid is
(row blocks, A tiles); B tiles are an inner loop so the per-row running
count lives in a VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import pallas_call

DEF_ROWS = 8     # frontier rows per block (sublane dim)
DEF_TILE = 128   # values per tile (lane dim)


def _intersect_kernel(a_ref, alen_ref, b_ref, blen_ref, out_ref, *,
                      tile: int, n_b_tiles: int):
    at = pl.program_id(1)
    a = a_ref[...]                      # (R, TILE)
    alen = alen_ref[...]                # (R, 1)
    rows = a.shape[0]
    a_col = at * tile + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    a_valid = a_col < alen              # (R, TILE)
    # tile bounds for the leapfrog skip (invalid lanes excluded)
    big = jnp.iinfo(jnp.int32).max
    a_min = jnp.min(jnp.where(a_valid, a, big))
    a_max = jnp.max(jnp.where(a_valid, a, -1))

    @pl.when(at == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    blen = blen_ref[...]                # hoisted: constant across B tiles

    def b_tile_body(state):
        bt, count, _ = state
        b = b_ref[:, pl.dslice(bt * tile, tile)]          # (R, TILE)
        b_col = bt * tile + jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
        b_valid = b_col < blen
        b_min = jnp.min(jnp.where(b_valid, b, big))
        b_max = jnp.max(jnp.where(b_valid, b, -1))
        # gap-box skip: disjoint [a_min,a_max] x [b_min,b_max] tile pairs
        # branch around the dense compare entirely — a skipped pair pays
        # only the scalar bounds check, no (R, TILE, TILE) VPU work
        overlap = (a_min <= b_max) & (b_min <= a_max)

        def dense_compare(_):
            eq = (a[:, :, None] == b[:, None, :])
            eq &= a_valid[:, :, None] & b_valid[:, None, :]
            hit = eq.any(axis=2)                           # (R, TILE)
            return hit.sum(axis=1, dtype=jnp.int32)

        add = jax.lax.cond(overlap, dense_compare,
                           lambda _: jnp.zeros((rows,), jnp.int32), None)
        # sortedness: every later B tile has min >= b_min, so once
        # b_min > a_max no tile can overlap again (a fully-padded tile
        # reports b_min == INT_MAX and also terminates the scan)
        return bt + 1, count + add, b_min > a_max

    _, count, _ = jax.lax.while_loop(
        lambda s: (s[0] < n_b_tiles) & jnp.logical_not(s[2]),
        b_tile_body,
        (jnp.int32(0), jnp.zeros((rows,), jnp.int32), jnp.bool_(False)))
    out_ref[:, 0] += count


@functools.partial(jax.jit, static_argnames=("rows_per_blk", "tile",
                                             "interpret"))
def intersect_count_pallas(a: jax.Array, a_len: jax.Array, b: jax.Array,
                           b_len: jax.Array, rows_per_blk: int = DEF_ROWS,
                           tile: int = DEF_TILE,
                           interpret: bool | None = None) -> jax.Array:
    """Per-row |A ∩ B| of padded sorted int32 lists.

    a: (R, LA), b: (R, LB) sorted, unique within the valid prefix;
    a_len/b_len: (R,).  R % rows_per_blk == 0; LA, LB % tile == 0
    (pad with any value; masking is by length).
    """
    r, la = a.shape
    lb = b.shape[1]
    assert r % rows_per_blk == 0 and la % tile == 0 and lb % tile == 0
    n_a_tiles = la // tile
    n_b_tiles = lb // tile
    grid = (r // rows_per_blk, n_a_tiles)
    out = pallas_call(
        functools.partial(_intersect_kernel, tile=tile,
                          n_b_tiles=n_b_tiles),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_blk, tile), lambda i, j: (i, j)),
            pl.BlockSpec((rows_per_blk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rows_per_blk, lb), lambda i, j: (i, 0)),
            pl.BlockSpec((rows_per_blk, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_blk, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
    )(a.astype(jnp.int32), a_len.astype(jnp.int32)[:, None],
      b.astype(jnp.int32), b_len.astype(jnp.int32)[:, None])
    return out[:, 0]
