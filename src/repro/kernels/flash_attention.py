"""Blocked online-softmax (flash) attention for TPU, causal + GQA.

Not a paper contribution — the assigned LM architectures' prefill cells are
attention-dominated, so the perf-critical layer gets an explicit
VMEM-tiled kernel.  Classic scheme: grid (batch·heads, q blocks, k blocks)
with the k-block dimension innermost/sequential; running max / denominator
/ accumulator live in VMEM scratch across k steps; causal blocks above the
diagonal are skipped with ``pl.when`` (structural zero work, the same
tile-skip idea the intersect kernel uses).

Block sizes default to (128, 128) — MXU-aligned on the (q, k) dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import pallas_call

DEF_BQ = 128
DEF_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  n_kb: int, q_offset: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: the first query of this q block is at stream position
    # q_offset + qb*bq; skip k blocks strictly above the diagonal.
    q_start = q_offset + qb * bq
    k_start = kb * bk
    needed = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (BQ, D)
        k = k_ref[0].astype(jnp.float32)          # (BK, D)
        v = v_ref[0].astype(jnp.float32)          # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...][:, :1]                 # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)            # (BQ, 1)
        l_prev = l_scr[...][:, :1]
        l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)
        acc = acc_scr[...]
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, scale: float | None = None,
                           bq: int = DEF_BQ, bk: int = DEF_BK,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D); GQA via Hq % Hkv == 0.

    Queries are the last Tq positions of the Tk stream (prefill: Tq == Tk).
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    bq_ = min(bq, tq)
    bk_ = min(bk, tk)
    assert tq % bq_ == 0 and tk % bk_ == 0
    qr = q.reshape(b * hq, tq, d)
    kr = k.reshape(b * hkv, tk, d)
    vr = v.reshape(b * hkv, tk, d)
    n_kb = tk // bk_
    grid = (b * hq, tq // bq_, n_kb)

    def kv_index(h, i, j):
        return ((h // hq) * hkv + (h % hq) // group, j, 0)

    out = pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq_, bk=bk_, n_kb=n_kb, q_offset=tk - tq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk_, d), kv_index),
            pl.BlockSpec((1, bk_, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 128), jnp.float32),
            pltpu.VMEM((bq_, 128), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, tq, d)
