"""Distributed scaling benchmark: sharded join, skew, CSR sharding,
compression.

Four measurements, written to ``BENCH_dist.json`` by ``record_baseline``:

* ``join/<n>shard`` — one vectorized-LFTJ triangle expansion level over
  the full edge frontier via ``dist.spmd_join_step``, frontier
  row-sharded over 1 vs every forced host device (CI runs with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on real
  accelerators the same code path shards over the physical mesh).  The
  derived field carries rows/s and the verified triangle count.
* ``skew/{static,rebalanced}`` — the adaptive-execution headline: a
  3-path join over a Zipf graph run level-synchronously on 8 shards
  (``dist.rebalance.AdaptiveJoin``) with the static first-level deal
  frozen vs mid-join frontier re-deals.  The derived fields carry wall
  and cost-model makespans plus the rebalanced/static ratio — the
  acceptance bar is ratio <= 0.7.
* ``sharded_csr/<query>`` — ``dist.sharded_csr.sharded_count`` over a
  row-partitioned CSR (8 shards) on every tier-1 query shape, each
  verified equal to the replicated-CSR count (``match=1``), with the
  exchanged adjacency volume.
* ``train/{uncompressed,compressed}_step`` + ``loss_curves`` — the tiny
  transformer's *sharded* data-parallel train step with an f32-pmean
  wire (``make_dp_train_step``) vs the int8 error-feedback compressed
  wire (``make_compressed_train_step``) — same mesh and batch split, so
  the timing gap isolates compression; both loss trajectories are kept
  so compression quality regressions show up as curve divergence, not
  just speed.

Run standalone (``python -m benchmarks.bench_dist``) this module forces
8 host devices before jax initializes; under ``benchmarks.run`` it
measures whatever device count the process already has.  ``--skew``
runs only the skew section (fast inner loop for re-balancer work).
"""
import json
import os
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import GraphDB, VLFTJ, get_query
from repro.core import engine as engine_mod
from repro.core.plan import executor_geometry
from repro.dist.compressed_step import (init_compressed_state,
                                        make_compressed_train_step,
                                        make_dp_train_step)
from repro.dist.rebalance import AdaptiveJoin
from repro.dist.sharded_csr import ShardedGraphDB, sharded_count
from repro.dist.sharded_join import spmd_join_step
from repro.graphs import node_sample, powerlaw_cluster, zipf_graph
from repro.models.transformer import TransformerConfig, init_params, loss_fn
from repro.train.optimizer import OptimizerConfig, init_opt_state

from .common import BenchRecord, timed

Rec = partial(BenchRecord, bench="dist")


def _mesh(n_shards: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n_shards]), ("data",))


def _triangle_frontier(g, pad_to: int):
    ea = g.edge_array()
    fr = ea[ea[:, 0] < ea[:, 1]].astype(np.int32)
    pad = (-len(fr)) % pad_to
    fr = np.pad(fr, ((0, pad), (0, 0)))
    mult = np.ones(len(fr), np.int64)
    if pad:
        mult[len(fr) - pad:] = 0
    return fr, mult


def _join_rows(quick: bool) -> list[BenchRecord]:
    rows: list[BenchRecord] = []
    g = powerlaw_cluster(1200 if quick else 4000, 6, seed=0)
    gdb = GraphDB(g, {})
    n_dev = jax.device_count()
    fr, mult = _triangle_frontier(g, pad_to=n_dev)
    width, _ = executor_geometry(gdb.max_degree)
    kw = dict(probe_cols=(0, 1), n_unary=0, lower_cols=(1,), upper_cols=(),
              width=width, n_iter=gdb.bsearch_iters, needs_degree=False)
    ref = VLFTJ(get_query("3-clique"), gdb).count()
    args = (gdb.dev("indptr"), gdb.dev("indices"),
            jnp.asarray(fr), jnp.asarray(mult))
    for shards in sorted({1, n_dev}):
        step = spmd_join_step(_mesh(shards), kw)
        total = int(step(*args))                      # warm + verify
        assert total == ref, (total, ref)
        _, us = timed(lambda: int(step(*args)), repeats=5, timeout_s=120)
        rps = len(fr) / (us / 1e6)
        rows.append(Rec(f"join/{shards}shard", us,
                        f"rows={len(fr)};rows_per_s={rps:.0f};"
                        f"triangles={total}"))
    return rows


SKEW_SHARDS = 16
CSR_SHARDS = 8
SHARDED_CSR_QUERIES = ("3-clique", "4-clique", "4-cycle", "3-path",
                       "2-lollipop", "3-lollipop")


def _skew_rows(quick: bool) -> list[BenchRecord]:
    """Static vs mid-join-rebalanced makespan on a Zipf 3-path.

    The workload is the regime where mid-join skew is real: *selective*
    seeds (an RDBMS-style ``v1`` predicate leaves ~80 seeds, so the
    law-of-large-numbers self-balancing of big frontiers never kicks
    in) over an assortative Zipf graph (hubs neighbor hubs — a seed's
    subtree mass is badly predicted by its own degree, which is all the
    static first-level deal can see).  Makespans are min-of-3 per
    variant; the derived fields also carry the deterministic cost-model
    ratio the tests assert on.  ``quick`` deliberately does NOT scale
    this section down: below this graph size per-shard level work drops
    under the per-dispatch fixed cost and wall makespan stops tracking
    the skew at all (the whole section is ~1-2 min).
    """
    n, m = (8000, 200000)
    g = zipf_graph(n, m, alpha=1.4, seed=0)
    unary = {f"v{i}": node_sample(g.n_nodes, 150, seed=i)
             for i in range(1, 5)}
    gdb = GraphDB(g, unary)
    q = get_query("3-path")
    reps = 3
    runs = {}
    for label, rebalance in (("static", False), ("rebalanced", True)):
        aj = AdaptiveJoin(q, gdb, n_shards=SKEW_SHARDS, threshold=1.2,
                          rebalance=rebalance)
        aj.count()          # warm the level kernels
        best, count = None, None
        for _ in range(reps):
            aj2 = AdaptiveJoin(q, gdb, n_shards=SKEW_SHARDS,
                               threshold=1.2, rebalance=rebalance)
            count = aj2.count()
            if best is None or aj2.stats["makespan"] < best["makespan"]:
                best = aj2.stats
        runs[label] = (best, count)
    ratio = (runs["rebalanced"][0]["makespan"]
             / max(runs["static"][0]["makespan"], 1e-12))
    cost_ratio = (runs["rebalanced"][0]["cost_makespan"]
                  / max(runs["static"][0]["cost_makespan"], 1e-12))
    assert runs["static"][1] == runs["rebalanced"][1]
    rows = []
    for label in ("static", "rebalanced"):
        st, cnt = runs[label]
        rows.append(Rec(
            f"skew/{label}", st["makespan"] * 1e6,
            f"count={cnt};shards={SKEW_SHARDS};"
            f"cost_makespan={st['cost_makespan']:.0f};"
            f"rebalances={len(st.get('rebalances', []))};"
            + (f"makespan_ratio={ratio:.3f};"
               f"cost_ratio={cost_ratio:.3f}"
               if label == "rebalanced" else
               f"total_time_us={st['total_time'] * 1e6:.0f}")))
    return rows


def _sharded_csr_rows(quick: bool) -> list[BenchRecord]:
    """Row-partitioned-CSR count parity on every tier-1 query shape."""
    g = powerlaw_cluster(300 if quick else 1000, 4, seed=11)
    unary = {f"v{i}": node_sample(g.n_nodes, 6, seed=i)
             for i in range(1, 5)}
    gdb = GraphDB(g, unary)
    rows: list[BenchRecord] = []
    for qname in SHARDED_CSR_QUERIES:
        sg = ShardedGraphDB(g, CSR_SHARDS, unary)
        ref = engine_mod.count(get_query(qname), gdb, engine="vlftj")
        got, us = timed(lambda: sharded_count(get_query(qname), sg),
                        repeats=1, timeout_s=300)
        assert got == ref, (qname, got, ref)
        rows.append(Rec(
            f"sharded_csr/{qname}", us,
            f"count={got};match={int(got == ref)};"
            f"shards={CSR_SHARDS};"
            f"exchanged_values={sg.exchange['values']}"))
    return rows


def _train_rows(quick: bool) -> tuple[list[BenchRecord], dict]:
    rows: list[BenchRecord] = []
    cfg = TransformerConfig(name="bench", n_layers=2, d_model=64, n_heads=4,
                            n_kv_heads=2, d_ff=128, vocab_size=256,
                            dtype=jnp.float32, remat=False)
    n_dev = jax.device_count()
    mesh = _mesh(n_dev)
    oc = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=50)
    n_steps = 12 if quick else 30
    p0 = init_params(jax.random.PRNGKey(0), cfg)

    def lf(p, b):
        return loss_fn(p, b, cfg)

    def batch_at(s):
        rng = np.random.default_rng(s)
        toks = rng.integers(0, 64, (16, 32), dtype=np.int32)
        return {"tokens": toks, "labels": (toks * 3 + 7) % 256}

    curves: dict = {"n_devices": n_dev, "steps": n_steps}
    for compressed in (False, True):
        p = jax.tree.map(jnp.copy, p0)
        opt = init_opt_state(p)
        err = init_compressed_state(p, mesh)
        step_c = make_compressed_train_step(lf, oc, mesh)
        # fair baseline: the same sharded DP step over an f32 wire, so
        # the timing gap isolates compression, not data parallelism
        step_u = make_dp_train_step(lf, oc, mesh)
        losses, times = [], []
        for s in range(n_steps):
            batch = batch_at(s)
            t0 = time.time()
            if compressed:
                p, opt, err, m = step_c(p, opt, err, batch)
            else:
                p, opt, m = step_u(p, opt, batch)
            jax.block_until_ready(m["loss"])
            times.append(time.time() - t0)
            losses.append(round(float(m["loss"]), 5))
        name = "compressed" if compressed else "uncompressed"
        curves[name] = losses
        us = float(np.median(times[1:])) * 1e6       # skip the compile step
        rows.append(Rec(f"train/{name}_step", us,
                        f"loss0={losses[0]:.3f};lossN={losses[-1]:.3f}"))
    return rows, curves


def run(quick: bool = True, skew_only: bool = False) -> list[BenchRecord]:
    if skew_only:
        return _skew_rows(quick)
    rows = _join_rows(quick) + _skew_rows(quick) + _sharded_csr_rows(quick)
    train_rows, _ = _train_rows(quick)
    return rows + train_rows


def record_baseline(path: str | None = None, quick: bool = True) -> dict:
    """Write BENCH_dist.json: shard scaling, skew re-balancing,
    sharded-CSR parity, and compression loss curves."""
    rows = _join_rows(quick) + _skew_rows(quick) + _sharded_csr_rows(quick)
    train_rows, curves = _train_rows(quick)
    payload = {
        "bench": "dist",
        "quick": quick,
        "rows": [{"name": r.name, "us_per_call": round(r.us_per_call, 2),
                  "derived": r.derived} for r in rows + train_rows],
        "loss_curves": curves,
    }
    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_dist.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return payload


if __name__ == "__main__":
    import argparse

    # eight virtual CPU devices for the sharded rows, set before the first
    # device use; the flag touches only the CPU backend, so on a chip host
    # the mesh is the chips
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()

    ap = argparse.ArgumentParser(description="distributed join/compression "
                                             "scaling benchmark")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skew", action="store_true",
                    help="run only the static-vs-rebalanced skew section")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the BENCH json here instead of CSV rows")
    a = ap.parse_args()
    if a.out and a.skew:
        rows = _skew_rows(quick=a.quick)
        payload = {"bench": "dist-skew", "quick": a.quick,
                   "rows": [{"name": r.name,
                             "us_per_call": round(r.us_per_call, 2),
                             "derived": r.derived} for r in rows]}
        with open(a.out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {a.out} ({len(payload['rows'])} rows)")
    elif a.out:
        payload = record_baseline(path=a.out, quick=a.quick)
        print(f"wrote {a.out} ({len(payload['rows'])} rows)")
    else:
        for row in run(quick=a.quick, skew_only=a.skew):
            print(row.csv())
