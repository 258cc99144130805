"""Mixture-of-Experts FFN with explicit shard_map parallelism.

Two sharding modes, chosen per config by expert-count divisibility:

  * ``ep``: experts sharded over the ``model`` axis (moonshot: 64 experts /
    16 shards = 4 per shard).  Routing/top-k is computed redundantly per
    model shard (cheap); each shard dispatches only its own experts'
    tokens into a capacity-bounded (E_loc, C, d) buffer via sort-based
    (MegaBlocks-style) dispatch; outputs are ``psum``-combined over the
    model axis — the same d-wide all-reduce a dense TP FFN pays.
  * ``tp``: experts replicated, expert FFN width sharded over ``model``
    (granite: 40 experts don't divide 16; d_ff=512 shards to 32).  The
    down-projection contracts the sharded width, so the same final psum
    applies.

The sort-based dispatch (argsort by expert, position-in-expert via
prefix offsets, capacity drop) is the token-permutation machinery the
vectorized join engine uses for frontier expansion — scatter/gather with
static shapes.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import act_fn, normal_init


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    shard_mode: str = "ep"          # "ep" | "tp"
    n_shared_experts: int = 0       # always-on shared experts (DeepSeek/Kimi)


def init_moe_params(key, d_model: int, cfg: MoEConfig, n_layers: int,
                    dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e, ff = cfg.n_experts, cfg.d_ff_expert
    p = {
        "router": normal_init(ks[0], (n_layers, d_model, e), dtype=jnp.float32),
        "w_gate": normal_init(ks[1], (n_layers, e, d_model, ff), dtype=dtype),
        "w_up": normal_init(ks[2], (n_layers, e, d_model, ff), dtype=dtype),
        "w_down": normal_init(ks[3], (n_layers, e, ff, d_model), dtype=dtype),
    }
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["sh_gate"] = normal_init(kk[0], (n_layers, d_model, sff), dtype=dtype)
        p["sh_up"] = normal_init(kk[1], (n_layers, d_model, sff), dtype=dtype)
        p["sh_down"] = normal_init(kk[2], (n_layers, sff, d_model), dtype=dtype)
    return p


def moe_param_specs(cfg: MoEConfig, fsdp: bool = False):
    """PartitionSpecs for the stacked (L, ...) MoE params."""
    dp = "data" if fsdp else None
    if cfg.shard_mode == "ep":
        w = P(None, "model", dp, None)
        wd = P(None, "model", None, dp)
    else:
        w = P(None, None, dp, "model")
        wd = P(None, None, "model", dp)
    specs = {"router": P(None, None, None), "w_gate": w, "w_up": w,
             "w_down": wd}
    if cfg.n_shared_experts:
        specs["sh_gate"] = P(None, dp, "model")
        specs["sh_up"] = P(None, dp, "model")
        specs["sh_down"] = P(None, "model", dp)
    return specs


def _dispatch_compute(x, router, w_gate, w_up, w_down, *, cfg: MoEConfig,
                      e_off, n_total_experts: int, act: str, capacity: int):
    """Token dispatch + expert FFN for the experts [e_off, e_off+E_loc).

    x: (T, d).  Returns (partial_out (T, d), aux_loss scalar).
    """
    t, d = x.shape
    e_loc = w_gate.shape[0]
    k = cfg.top_k
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate_vals, idx = jax.lax.top_k(logits, k)                 # (T, k)
    gates = jax.nn.softmax(gate_vals, axis=-1)
    # load-balance aux (computed on the full router; identical per shard)
    frac = jnp.zeros(n_total_experts, jnp.float32)
    onehot_top1 = jax.nn.one_hot(idx[:, 0], n_total_experts,
                                 dtype=jnp.float32)
    frac = onehot_top1.mean(axis=0)
    aux = n_total_experts * jnp.sum(frac * probs.mean(axis=0))

    eflat = idx.reshape(-1)                                   # (T*k,)
    tflat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    gflat = gates.reshape(-1)
    order = jnp.argsort(eflat, stable=True)
    se, st, sg = eflat[order], tflat[order], gflat[order]
    starts = jnp.searchsorted(se, jnp.arange(n_total_experts,
                                             dtype=se.dtype))
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    local = (se >= e_off) & (se < e_off + e_loc) & (pos < capacity)
    slot_e = jnp.where(local, se - e_off, 0).astype(jnp.int32)
    slot_c = jnp.where(local, pos, 0).astype(jnp.int32)
    xg = jnp.where(local[:, None], x[st], 0).astype(x.dtype)
    buf = jnp.zeros((e_loc, capacity, d), x.dtype)
    buf = buf.at[slot_e, slot_c].add(xg)
    h = jnp.einsum("ecd,edf->ecf", buf, w_gate,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up,
                   preferred_element_type=jnp.float32)
    h = (act_fn(act)(h) * u).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, w_down,
                   preferred_element_type=jnp.float32)        # (E_loc,C,d)
    contrib = y[slot_e, slot_c] * jnp.where(local, sg, 0.0)[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[st].add(contrib)
    return out, aux


def moe_ffn(x, params_layer, cfg: MoEConfig, mesh, *, act: str = "silu",
            dtype=jnp.bfloat16):
    """x: (B, S, d) batch-sharded over (pod, data).  Returns (y, aux)."""
    dataxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    b, s, d = x.shape
    t_local = (b * s) // _axes_size(mesh, dataxes)
    capacity = int(cfg.capacity_factor * t_local * cfg.top_k
                   / cfg.n_experts) + 1
    if cfg.shard_mode == "ep":
        wspec = P("model", None, None)
        wdspec = P("model", None, None)
    else:
        wspec = P(None, None, "model")
        wdspec = P(None, "model", None)

    def f(x_loc, router, wg, wu, wd):
        tl = x_loc.shape[0] * x_loc.shape[1]
        xf = x_loc.reshape(tl, d)
        if cfg.shard_mode == "ep":
            e_loc = wg.shape[0]
            e_off = jax.lax.axis_index("model") * e_loc
        else:
            e_off = 0
        out, aux = _dispatch_compute(
            xf, router, wg, wu, wd, cfg=cfg, e_off=e_off,
            n_total_experts=cfg.n_experts, act=act, capacity=capacity)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        for ax in dataxes:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(x_loc.shape).astype(dtype), aux

    y, aux = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(dataxes, None, None), P(), wspec, wspec, wdspec),
        out_specs=(P(dataxes, None, None), P()),
        check_vma=False,
    )(x, params_layer["router"], params_layer["w_gate"],
      params_layer["w_up"], params_layer["w_down"])

    if cfg.n_shared_experts:
        g = act_fn(act)(jnp.einsum(
            "bsd,df->bsf", x, params_layer["sh_gate"],
            preferred_element_type=jnp.float32))
        u = jnp.einsum("bsd,df->bsf", x, params_layer["sh_up"],
                       preferred_element_type=jnp.float32)
        sh = jnp.einsum("bsf,fd->bsd", (g * u).astype(x.dtype),
                        params_layer["sh_down"],
                        preferred_element_type=jnp.float32)
        y = y + sh.astype(y.dtype)
    return y, aux


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
