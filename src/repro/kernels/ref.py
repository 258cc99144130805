"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantics the TPU kernels must reproduce; they are also the
default execution path (``kernels/ops.py``).  On the CPU backend the Pallas
kernels run in the interpreter (``kernels/backend.py``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Segmented batched binary search (the vectorized ``seek_lub``)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_iter", "unroll"))
def searchsorted_segments_ref(values: jax.Array, lo: jax.Array,
                              hi: jax.Array, queries: jax.Array,
                              n_iter: int, unroll: bool = False
                              ) -> tuple[jax.Array, jax.Array]:
    """Branchless lower-bound of ``queries`` within ``values[lo:hi)``.

    values:  (M,) sorted within each segment
    lo, hi:  broadcastable to queries' shape — segment bounds per query
    queries: any shape
    n_iter:  static iteration count >= ceil(log2(max segment length)) + 1

    Returns (pos, found): ``pos`` = first index in [lo, hi) with
    ``values[pos] >= q`` (== hi if none), ``found`` = q present.
    """
    m = values.shape[0]
    q = queries
    lo0 = jnp.broadcast_to(lo, q.shape)
    hi0 = jnp.broadcast_to(hi, q.shape)
    lo_c, hi_c = lo0, hi0

    def body(_, state):
        lo_c, hi_c = state
        active = lo_c < hi_c
        mid = (lo_c + hi_c) >> 1
        v = values[jnp.clip(mid, 0, m - 1)]
        go_right = active & (v < q)
        lo_c = jnp.where(go_right, mid + 1, lo_c)
        hi_c = jnp.where(active & ~go_right, mid, hi_c)
        return lo_c, hi_c

    if unroll:
        # straight-line HLO so cost_analysis sees every round (dry-run)
        state = (lo_c, hi_c)
        for i in range(n_iter):
            state = body(i, state)
        lo_c, hi_c = state
    else:
        lo_c, hi_c = jax.lax.fori_loop(0, n_iter, body, (lo_c, hi_c))
    pos = lo_c
    found = (pos < hi0) & (values[jnp.clip(pos, 0, m - 1)] == q)
    return pos, found


@partial(jax.jit, static_argnames=("stride", "n1", "n2", "unroll"))
def searchsorted_segments_2level_ref(values: jax.Array, summary: jax.Array,
                                     lo: jax.Array, hi: jax.Array,
                                     queries: jax.Array, stride: int,
                                     n1: int, n2: int,
                                     unroll: bool = False):
    """Two-level segmented lower bound.

    ``summary[k] = values[k*stride]`` — the first level binary-searches the
    (tiny, cache/VMEM-resident) summary over the segment's *full* blocks;
    the second level searches a <= 2*stride window of the big table.  Cuts
    big-table gather rounds from ~log2(max_deg) to ~log2(2*stride).
    """
    q = queries
    lo_b = jnp.broadcast_to(lo, q.shape)
    hi_b = jnp.broadcast_to(hi, q.shape)
    fb0 = (lo_b + stride - 1) // stride        # first full block
    fb1 = hi_b // stride                       # one-past-last full block
    has_blocks = fb1 > fb0
    pos1, _ = searchsorted_segments_ref(
        summary, fb0, jnp.maximum(fb0, fb1), q, n1, unroll=unroll)
    wlo = jnp.where(has_blocks & (pos1 > fb0), (pos1 - 1) * stride, lo_b)
    wlo = jnp.maximum(wlo, lo_b)
    whi = jnp.where(has_blocks & (pos1 < fb1), pos1 * stride + 1, hi_b)
    whi = jnp.minimum(whi, hi_b)
    return searchsorted_segments_ref(values, wlo, whi, q, n2,
                                     unroll=unroll)


# ---------------------------------------------------------------------------
# Tile-leapfrog sorted intersection (counts)
# ---------------------------------------------------------------------------

@jax.jit
def intersect_count_ref(a: jax.Array, a_len: jax.Array,
                        b: jax.Array, b_len: jax.Array) -> jax.Array:
    """Per-row |A ∩ B| of two padded sorted int arrays.

    a: (R, LA), b: (R, LB); a_len/b_len: (R,) valid lengths.
    Oracle is the O(LA·LB) dense membership matrix (the in-tile compare the
    TPU kernel performs after tile skipping).
    """
    la = jnp.arange(a.shape[1])[None, :]
    lb = jnp.arange(b.shape[1])[None, :]
    va = la < a_len[:, None]
    vb = lb < b_len[:, None]
    eq = (a[:, :, None] == b[:, None, :]) & va[:, :, None] & vb[:, None, :]
    return eq.any(axis=2).sum(axis=1)


# ---------------------------------------------------------------------------
# Bitset intersection / membership (the hybrid-layout kernels)
# ---------------------------------------------------------------------------

def popcount32(v: jax.Array) -> jax.Array:
    """Per-element popcount of a uint32 array (SWAR bit trick)."""
    v = v.astype(jnp.uint32)
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


@jax.jit
def bitset_intersect_count_ref(a_words: jax.Array,
                               b_words: jax.Array) -> jax.Array:
    """Per-row |A ∩ B| of two bitset rows: popcount(AND).

    a_words, b_words: (R, W) uint32 characteristic vectors over a common
    word-aligned domain.  Same ``(rows, counts)`` contract as
    :func:`intersect_count_ref` — the cost is O(W) words regardless of
    set cardinality, which is the dense-layout win for hub∩hub.
    """
    return popcount32(a_words & b_words).sum(axis=1)


@jax.jit
def bitset_member_ref(words: jax.Array, queries: jax.Array) -> jax.Array:
    """Gather-test membership: bit ``q & 31`` of ``words[r, q >> 5]``.

    words: (R, W) uint32 per-row bitsets; queries: (R, Q) int ids within
    the word-aligned domain.  Returns (R, Q) bool — the O(1)-per-query
    probe the hybrid engine uses in place of segmented binary search.
    """
    q = queries.astype(jnp.int32)
    w = jnp.take_along_axis(words, (q >> 5).astype(jnp.int32), axis=1)
    return ((w >> (q & 31).astype(jnp.uint32)) & 1) != 0


@jax.jit
def bitset_member_count_ref(words: jax.Array, b: jax.Array,
                            b_len: jax.Array) -> jax.Array:
    """Per-row |bitset ∩ B| for padded sorted arrays ``b`` with valid
    lengths ``b_len`` — the bitset∩array half of the hybrid layout,
    same ``(rows, counts)`` contract as :func:`intersect_count_ref`."""
    valid = jnp.arange(b.shape[1])[None, :] < b_len[:, None]
    hit = bitset_member_ref(words, jnp.where(valid, b, 0)) & valid
    return hit.sum(axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Flash attention (causal, GQA) — oracle
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: float | None = None) -> jax.Array:
    """Plain softmax attention oracle.

    q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D).  Hq % Hkv == 0 (GQA).
    """
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, group, tq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        tk = k.shape[2]
        # queries are the last tq positions of the tk-length stream
        qpos = jnp.arange(tq) + (tk - tq)
        mask = qpos[:, None] >= jnp.arange(tk)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, tq, d).astype(q.dtype)
