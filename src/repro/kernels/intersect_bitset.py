"""Bitset intersection kernels — the dense half of the hybrid layout.

Hub neighborhoods (see ``graphs/layout.py``) are stored as uint32
characteristic vectors over the word-aligned node domain.  Two kernels
cover the two dense intersection shapes, both with the same per-row
``(rows, counts)`` contract as ``kernels/intersect.py``:

* **bitset ∩ bitset** — AND + SWAR popcount, accumulated across word
  tiles.  Cost is ``O(n_words / lanes)`` VPU ops per row pair,
  independent of set cardinality — the hub∩hub crossover the sorted-array
  tile-leapfrog cannot reach (it pays ``O(deg/128)`` tile visits).
* **bitset ∩ array** — gather-test membership: for each (sorted, padded)
  array element, gather one word of the row's bitset and test one bit.
  The TPU gathers within one 128-lane row only, so a tile of elements
  takes one in-row gather per 128-word tile of the bitset row
  (``n_words / 128`` of them) instead of ``log2(deg)`` binary-search
  rounds.

Grid layout mirrors ``intersect.py``: (row blocks, word/value tiles) with
a VMEM accumulator; tile 0 initializes the output.  The pure-jnp oracles
live in ``kernels/ref.py`` (``bitset_intersect_count_ref`` /
``bitset_member_count_ref``); ``kernels/ops.py`` routes between them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import pallas_call
from .ref import popcount32

DEF_ROWS = 8     # rows per block (sublane dim)
DEF_TILE = 128   # uint32 words / array values per tile (lane dim)
LANE_BITS = 7
LANES = 1 << LANE_BITS   # one vreg row: the widest in-kernel gather


# ---------------------------------------------------------------------------
# bitset ∩ bitset: AND + popcount accumulate
# ---------------------------------------------------------------------------

def _bitset_and_kernel(a_ref, b_ref, out_ref):
    wt = pl.program_id(1)

    @pl.when(wt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = a_ref[...] & b_ref[...]                    # (R, TILE) uint32
    out_ref[:, 0] += popcount32(v).sum(axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("rows_per_blk", "tile",
                                             "interpret"))
def bitset_intersect_count_pallas(a_words: jax.Array, b_words: jax.Array,
                                  rows_per_blk: int = DEF_ROWS,
                                  tile: int = DEF_TILE,
                                  interpret: bool | None = None) -> jax.Array:
    """Per-row ``popcount(a & b)`` of (R, W) uint32 bitset rows.

    R % rows_per_blk == 0 and W % tile == 0 (pad with zero words —
    zero-padding is the identity for AND + popcount).
    """
    r, w = a_words.shape
    assert b_words.shape == (r, w)
    assert r % rows_per_blk == 0 and w % tile == 0
    grid = (r // rows_per_blk, w // tile)
    out = pallas_call(
        _bitset_and_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_blk, tile), lambda i, j: (i, j)),
            pl.BlockSpec((rows_per_blk, tile), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rows_per_blk, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
    )(a_words.astype(jnp.uint32), b_words.astype(jnp.uint32))
    return out[:, 0]


# ---------------------------------------------------------------------------
# bitset ∩ array: gather-test membership
# ---------------------------------------------------------------------------

def _bitset_member_kernel(words_ref, b_ref, blen_ref, out_ref, *, tile: int,
                          n_word_tiles: int):
    bt = pl.program_id(1)

    @pl.when(bt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b = b_ref[...]                                  # (R, TILE) int32
    blen = blen_ref[...]                            # (R, 1)
    col = bt * tile + jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    valid = col < blen
    q = jnp.where(valid, b, 0)                      # padded lanes -> bit 0
    word = q >> 5
    # Mosaic gathers only within one 128-lane vreg row, so the word
    # gather visits the bitset row one 128-word tile at a time and keeps
    # the lanes whose word falls in that tile
    lane = word & (LANES - 1)
    word_tile = word >> LANE_BITS

    def gather_tile(t, w):
        start = pl.multiple_of(t * LANES, LANES)
        g = jnp.take_along_axis(words_ref[:, pl.ds(start, LANES)], lane,
                                axis=1)
        return jnp.where(word_tile == t, g, w)

    w = jax.lax.fori_loop(0, n_word_tiles, gather_tile,
                          jnp.zeros(b.shape, jnp.uint32))
    hit = (((w >> (q & 31).astype(jnp.uint32)) & 1) != 0) & valid
    out_ref[:, 0] += hit.sum(axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("rows_per_blk", "tile",
                                             "interpret"))
def bitset_member_count_pallas(words: jax.Array, b: jax.Array,
                               b_len: jax.Array,
                               rows_per_blk: int = DEF_ROWS,
                               tile: int = DEF_TILE,
                               interpret: bool | None = None) -> jax.Array:
    """Per-row |bitset ∩ B| — membership of padded sorted int32 lists
    ``b`` (valid prefix ``b_len``) in per-row bitsets ``words`` (R, W).

    R % rows_per_blk == 0, LB % tile == 0.  Array values must lie within
    the bitsets' word-aligned domain ``[0, 32*W)``.
    """
    r = words.shape[0]
    lb = b.shape[1]
    assert b.shape[0] == r and r % rows_per_blk == 0 and lb % tile == 0
    # zero words past the domain are never addressed by a valid value
    words = jnp.pad(words, ((0, 0), (0, -words.shape[1] % LANES)))
    w = words.shape[1]
    grid = (r // rows_per_blk, lb // tile)
    out = pallas_call(
        functools.partial(_bitset_member_kernel, tile=tile,
                          n_word_tiles=w // LANES),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_blk, w), lambda i, j: (i, 0)),
            pl.BlockSpec((rows_per_blk, tile), lambda i, j: (i, j)),
            pl.BlockSpec((rows_per_blk, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_blk, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
    )(words.astype(jnp.uint32), b.astype(jnp.int32),
      b_len.astype(jnp.int32)[:, None])
    return out[:, 0]
