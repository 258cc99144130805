"""Batched segmented binary search — the vectorized ``seek_lub`` on TPU.

Every lane carries one (query, segment) pair; ``n_iter`` branchless rounds
of midpoint gathers converge all lanes simultaneously.  This is the
log-time probe LFTJ and Minesweeper both build on (§2.2/§4.5), with the
B-tree ``seek_lub``/``seek_glb`` replaced by binary search over the
sorted-array trie.

VMEM layout: the sorted ``values`` array is the kernel's resident block,
and the midpoint gather (``jnp.take``) reads anywhere in it.  The TPU
compiler lowers gathers only within one 128-lane vreg row, so this kernel
does not compile for the TPU: it runs in the interpreter on the CPU, and
``kernels/ops.py`` refuses it on a TPU (``ops.TPU_REFUSED``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import pallas_call

DEF_ROWS = 8
DEF_LANES = 128


def _searchsorted_kernel(values_ref, lo_ref, hi_ref, q_ref,
                         pos_ref, found_ref, *, n_iter: int):
    values = values_ref[...]            # (1, M)
    m = values.shape[1]
    q = q_ref[...]
    lo = lo_ref[...]
    hi0 = hi_ref[...]
    hi = hi0

    def body(_, state):
        lo, hi = state
        active = lo < hi
        mid = (lo + hi) >> 1
        midc = jnp.clip(mid, 0, m - 1)
        v = jnp.take(values[0], midc)
        go_right = active & (v < q)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_iter, body, (lo, hi))
    pos_ref[...] = lo
    vpos = jnp.take(values[0], jnp.clip(lo, 0, m - 1))
    found_ref[...] = ((lo < hi0) & (vpos == q)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_iter", "rows_per_blk",
                                             "interpret"))
def searchsorted_segments_pallas(values: jax.Array, lo: jax.Array,
                                 hi: jax.Array, queries: jax.Array,
                                 n_iter: int, rows_per_blk: int = DEF_ROWS,
                                 interpret: bool | None = None):
    """Pallas twin of :func:`repro.kernels.ref.searchsorted_segments_ref`.

    queries: (R, W); lo/hi broadcastable to (R, W); values: (M,).
    Returns (pos, found) with found as bool.
    """
    q = queries.astype(jnp.int32)
    r, w = q.shape
    lo = jnp.broadcast_to(lo, q.shape).astype(jnp.int32)
    hi = jnp.broadcast_to(hi, q.shape).astype(jnp.int32)
    assert r % rows_per_blk == 0 and w % DEF_LANES == 0, (r, w)
    m = values.shape[0]
    grid = (r // rows_per_blk,)
    pos, found = pallas_call(
        functools.partial(_searchsorted_kernel, n_iter=n_iter),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec((rows_per_blk, w), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_blk, w), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_blk, w), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows_per_blk, w), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_blk, w), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, w), jnp.int32),
            jax.ShapeDtypeStruct((r, w), jnp.int32),
        ],
        interpret=interpret,
    )(values.astype(jnp.int32)[None, :], lo, hi, q)
    return pos, found.astype(bool)
