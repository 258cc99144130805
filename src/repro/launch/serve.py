"""Serving launcher — the paper's workload as a long-running service.

    PYTHONPATH=src python -m repro.launch.serve --nodes 20000 --requests 50

Loads (or generates) a graph, starts the QueryServer, and drives a mixed
batch of pattern queries, printing per-engine latency percentiles — the
operational analogue of Tables 6/7.  ``--edgelist`` serves a real SNAP
file.
"""
from __future__ import annotations

import argparse

import numpy as np

import repro  # noqa: F401
from repro.graphs import load_edgelist, powerlaw_cluster
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import QueryRequest, QueryServer

MIX = ["3-clique", "4-cycle", "3-path", "4-path", "1-tree", "2-comb",
       "2-lollipop"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--edgelist", default=None)
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--m-per-node", type=int, default=6)
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--selectivity", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.edgelist:
        g = load_edgelist(args.edgelist)
    else:
        g = powerlaw_cluster(args.nodes, args.m_per_node, seed=args.seed)
    print(f"graph: {g.n_nodes:,} nodes / {g.n_edges // 2:,} edges")
    server = QueryServer(g, default_selectivity=args.selectivity)

    rng = np.random.default_rng(args.seed)
    reqs = [QueryRequest(str(rng.choice(MIX)),
                         selectivity=float(rng.choice([8, 80])),
                         seed=int(rng.integers(3)))
            for _ in range(args.requests)]
    results = server.execute_batch(reqs)

    by_engine: dict[str, list[float]] = {}
    for r in results:
        by_engine.setdefault(r.engine, []).append(r.latency_s)
    total = sum(sum(v) for v in by_engine.values())
    print(f"\n{len(results)} requests, {total:.2f}s engine time")
    for eng, lats in sorted(by_engine.items()):
        lats.sort()
        p50 = lats[len(lats) // 2] * 1e3
        p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3
        print(f"  {eng:12s} n={len(lats):3d} p50={p50:8.1f}ms "
              f"p99={p99:8.1f}ms")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
