"""Fused segment-outer-product — MACE's A-basis without the (E, C, M)
materialization (EXPERIMENTS.md §Perf cell C's residual bottleneck).

    A[n, c, m] = Σ_{j : dst_j = n} msg[j, c] · basis[j, m]

Edges arrive sorted by destination.  Grid = (node blocks, edge tiles);
per tile the kernel computes the per-edge outer products **and** the
node-scatter in one MXU matmul:

    acc[BN, C·M] += onehot(dst − n0)ᵀ[BN, TE] @ (msg ⊗ basis)[TE, C·M]

so the (E, C, M) tensor only ever exists one (TE, C·M) tile at a time in
VMEM, and the scatter becomes a matmul (systolic-friendly — no
random-access writes).  Accumulation lives in a VMEM scratch across the
edge-tile grid dimension; edge tiles beyond a block's range are masked by
the dst-in-range predicate (the first/last tiles of a block may straddle
block boundaries, which the same predicate handles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import pallas_call

DEF_TE = 128   # edges per tile
DEF_BN = 8     # nodes per block


def _kernel(starts_ref, msg_ref, basis_ref, dst_ref, out_ref, acc_scr, *,
            bn: int, te: int, n_tiles: int, total_tiles: int):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tiles past the edge array clip to the last tile in the index_map;
    # gate them out so the last tile is never double-accumulated
    in_range = starts_ref[b] + t < total_tiles

    @pl.when(in_range)
    def _accumulate():
        msg = msg_ref[...]                       # (TE, C)
        basis = basis_ref[...]                   # (TE, M)
        dst = dst_ref[...]                       # (1, TE)
        n0 = b * bn
        rel = dst[0] - n0                        # (TE,)
        valid = (rel >= 0) & (rel < bn)
        # one-hot scatter matrix (TE, BN)
        oh = (rel[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (te, bn), 1))
        oh &= valid[:, None]
        # per-edge outer products, flattened (TE, C*M)
        prod = (msg[:, :, None] * basis[:, None, :]).reshape(te, -1)
        acc_scr[...] += jax.lax.dot_general(
            oh.astype(jnp.float32), prod.astype(jnp.float32),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (BN, C*M)

    @pl.when(t == n_tiles - 1)
    def _flush():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_tiles", "bn",
                                             "te", "interpret"))
def segment_outer_pallas(msg: jax.Array, basis: jax.Array,
                         dst: jax.Array, block_tile0: jax.Array,
                         n_nodes: int, n_tiles: int, bn: int = DEF_BN,
                         te: int = DEF_TE,
                         interpret: bool | None = None) -> jax.Array:
    """msg (E, C), basis (E, M), dst (E,) sorted ascending (pad with
    n_nodes), block_tile0 (n_blocks,) = first edge-tile index overlapping
    each node block, n_tiles = static max tiles per block — both from
    :func:`block_tile_starts`.  Returns (n_nodes, C, M) float32.
    """
    e, c = msg.shape
    m = basis.shape[1]
    assert e % te == 0, "pad edges to the tile size"
    assert n_nodes % bn == 0, "pad nodes to the block size"
    n_blocks = n_nodes // bn
    total_tiles = e // te

    grid = (n_blocks, n_tiles)

    def msg_index(b, t, starts):
        return (jnp.minimum(starts[b] + t, total_tiles - 1), 0)

    def dst_index(b, t, starts):
        return (0, jnp.minimum(starts[b] + t, total_tiles - 1))

    out = pallas_call(
        functools.partial(_kernel, bn=bn, te=te, n_tiles=n_tiles,
                          total_tiles=total_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((te, c), msg_index),
                pl.BlockSpec((te, m), msg_index),
                pl.BlockSpec((1, te), dst_index),
            ],
            out_specs=pl.BlockSpec((bn, c * m), lambda b, t, s: (b, 0)),
            scratch_shapes=[pltpu.VMEM((bn, c * m), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_nodes, c * m), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_tile0, jnp.int32), msg, basis,
      dst.astype(jnp.int32)[None, :])
    return out.reshape(n_nodes, c, m)


def block_tile_starts(dst_sorted: np.ndarray, n_nodes: int,
                      bn: int = DEF_BN, te: int = DEF_TE
                      ) -> tuple[np.ndarray, int]:
    """(first edge-tile per bn-node block, static max tiles per block)."""
    e = dst_sorted.shape[0]
    total_tiles = max(1, e // te)
    n_blocks = -(-n_nodes // bn)
    first_edge = np.searchsorted(dst_sorted, np.arange(n_blocks) * bn,
                                 side="left")
    last_edge = np.searchsorted(dst_sorted,
                                np.arange(1, n_blocks + 1) * bn - 1,
                                side="right")
    t0 = np.minimum(first_edge // te, total_tiles - 1).astype(np.int32)
    t1 = np.minimum(np.maximum(last_edge - 1, first_edge) // te,
                    total_tiles - 1)
    n_tiles = int(max(1, (t1 - t0).max() + 1))
    return t0, n_tiles


def segment_expand(prefix: np.ndarray, counts: np.ndarray,
                   values: np.ndarray) -> np.ndarray:
    """Host-side segmented expansion — the enumeration dual of the
    segment-outer scatter above.  Where the kernel folds per-edge products
    *into* nodes, this unfolds per-row extension segments *out of* rows:

        out = [prefix[i] ++ v  for i, seg in enumerate(segments)
                               for v in seg]

    ``prefix`` (C, k) rows are repeated by ``counts`` (C,) and the
    flattened segment ``values`` (counts.sum(),) become the new last
    column.  Rows stay in segment order, so a lex-sorted prefix with
    ascending per-row segments yields lex-sorted output — the invariant
    ``repro.results.ResultCursor`` streams pages under.  Returns int64.
    """
    prefix = np.asarray(prefix)
    counts = np.asarray(counts, dtype=np.int64)
    values = np.asarray(values)
    reps = np.repeat(np.arange(counts.shape[0]), counts)
    out = np.empty((values.shape[0], prefix.shape[1] + 1), dtype=np.int64)
    out[:, :-1] = prefix[reps]
    out[:, -1] = values
    return out


def segment_outer_ref(msg, basis, dst, n_nodes: int):
    """Oracle: segment-sum of explicit outer products."""
    prod = msg[:, :, None] * basis[:, None, :]
    safe = jnp.clip(dst, 0, n_nodes)  # pad rows (dst == n_nodes) dropped
    out = jax.ops.segment_sum(prod, safe, num_segments=n_nodes + 1)
    return out[:n_nodes].astype(jnp.float32)
