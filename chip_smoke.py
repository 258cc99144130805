"""Smoke run of the served join path on a TPU.

    python chip_smoke.py            # one chip: soc-Slashdot0811 at full scale
    python chip_smoke.py --chips 4  # the sharded join steps on four chips

One process drives the chip through the entry points a user calls: the
graph is served by ``QueryServer`` and every request goes query ->
``plan_query`` -> plan verifier -> engine.  Every count is checked against
a reference that shares no code with the engines: plain numpy/scipy over
the same CSR at full scale, and the scalar oracle ``lftj_ref`` on
ca-GrQc.  A mismatch or an error in any phase exits non-zero.

The figures printed on the way (compile seconds, request latency, device
peak bytes) come from one cold run: they are smoke figures, not benchmark
figures.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script says what JAX found and exits 2.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import threading
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
from scipy import sparse  # noqa: E402

from repro.core import (GraphDB, GraphStats, HybridGraphDB,  # noqa: E402
                        VLFTJ, execute_stats, get_query, plan_query)
from repro.core import engine as engine_mod  # noqa: E402
from repro.core.plan import MIN_WIDTH, executor_geometry  # noqa: E402
from repro.dist import WorkerPool  # noqa: E402
from repro.graphs.generators import make_snap_like  # noqa: E402
from repro.serve import QueryRequest, QueryServer  # noqa: E402

#: the six tier-1 shapes: vlftj, yannakakis and hybrid plans between them
SHAPES = ("3-clique", "4-clique", "4-cycle", "3-path", "2-lollipop",
          "3-lollipop")
SERVED_GRAPH = "soc-Slashdot0811"
ORACLE_GRAPH = "ca-GrQc"
#: the oracle graph's unary samples keep one vertex in this many: the
#: scalar ``lftj_ref`` visits every binding one at a time, and at the
#: default 1 in 10 the 3-lollipop alone has 8.6M result rows on ca-GrQc
ORACLE_SELECTIVITY = 1000.0
PAGE_ROWS = 1024
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeError(RuntimeError):
    """A smoke phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


@contextlib.contextmanager
def compile_meter():
    """Count the executables JAX builds (or loads from the persistent
    cache) inside the block, and the seconds that took."""
    tally = {"compiles": 0, "seconds": 0.0}

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE:
            tally["compiles"] += 1
            tally["seconds"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield tally
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


# ---------------------------------------------------------------------------
# plain numpy/scipy references over the CSR
# ---------------------------------------------------------------------------

def _sorted_member(keys: np.ndarray, probe: np.ndarray) -> np.ndarray:
    pos = np.minimum(np.searchsorted(keys, probe), len(keys) - 1)
    return keys[pos] == probe


def _grow_cliques(up: sparse.csr_matrix, keys: np.ndarray,
                  cliques: np.ndarray, budget: int = 1 << 23) -> np.ndarray:
    """k-cliques (ascending columns) -> (k+1)-cliques: every higher
    neighbor of the last vertex that is adjacent to all the others."""
    n = up.shape[0]
    lens = np.diff(up.indptr)[cliques[:, -1]]
    ends = np.cumsum(lens)
    out = [np.zeros((0, cliques.shape[1] + 1), np.int64)]
    s = 0
    while s < len(cliques):
        base = ends[s - 1] if s else 0
        e = max(s + 1, int(np.searchsorted(ends, base + budget, "right")))
        cl, ln = cliques[s:e], lens[s:e]
        rep = np.repeat(np.arange(len(cl)), ln)
        off = np.arange(ln.sum()) - np.repeat(np.cumsum(ln) - ln, ln)
        d = up.indices[up.indptr[cl[rep, -1]] + off].astype(np.int64)
        ok = np.ones(len(d), bool)
        for col in range(cl.shape[1] - 1):
            ok &= _sorted_member(keys, cl[rep, col] * n + d)
        out.append(np.column_stack([cl[rep[ok]], d[ok]]))
        s = e
    return np.concatenate(out)


def reference_counts(csr, unary: dict) -> dict[str, int]:
    """Counts of :data:`SHAPES` by matrix algebra and explicit clique
    listing — no code shared with the engines."""
    n = csr.n_nodes
    adj = sparse.csr_matrix(
        (np.ones(len(csr.indices), np.int64), csr.indices, csr.indptr),
        shape=(n, n))
    up = sparse.triu(adj, k=1, format="csr")       # edges (i, j), i < j
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(up.indptr))
    keys = rows * n + up.indices                  # sorted: CSR order
    tri = _grow_cliques(up, keys, np.column_stack([rows, up.indices]))
    quad = _grow_cliques(up, keys, tri)
    tri_at = np.bincount(tri.ravel(), minlength=n)
    quad_at = np.bincount(quad.ravel(), minlength=n)
    v1 = np.zeros(n, np.int64)
    v1[unary["v1"]] = 1
    v2 = np.zeros(n, np.int64)
    v2[unary["v2"]] = 1
    walk1 = adj @ v1                              # walks of length 1 from V1
    walk2 = adj @ walk1
    walk3 = adj @ walk2
    # 4-cycle a<b<c<d: for each pair a<c, (middles b with a<b<c) times
    # (vertices d > c adjacent to both)
    cyc = (up @ up).multiply(up @ up.T)
    return {"3-clique": len(tri), "4-clique": len(quad),
            "4-cycle": int(cyc.sum()),
            "3-path": int(walk3 @ v2),
            "2-lollipop": int(walk2 @ tri_at),
            "3-lollipop": int(walk3 @ quad_at)}


def check_rows(query, row_vars, rows: np.ndarray, csr, unary: dict) -> None:
    """Every row satisfies every atom and filter of ``query``."""
    n = csr.n_nodes
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(csr.indptr))
    keys = src * n + csr.indices
    col = {v: rows[:, row_vars.index(v)] for v in query.variables}
    for atom in query.atoms:
        if atom.rel == "edge":
            x, y = (col[v] for v in atom.vars)
            ok = _sorted_member(keys, x * n + y)
        else:
            ok = np.isin(col[atom.vars[0]], unary[atom.rel])
        check(bool(ok.all()), f"{query.name}: a row violates {atom}")
    for f in query.filters:
        check(bool((col[f.left] < col[f.right]).all()),
              f"{query.name}: a row violates {f}")


def strictly_ascending(rows: np.ndarray) -> bool:
    order = np.lexsort(rows.T[::-1])
    distinct = (np.diff(rows, axis=0) != 0).any(axis=1).all()
    return bool((order == np.arange(len(rows))).all() and distinct)


# ---------------------------------------------------------------------------
# the smoke
# ---------------------------------------------------------------------------

def _request(server: QueryServer, req: QueryRequest, log):
    with compile_meter() as meter:
        r = server.execute(req)
    peak = r.profile.memory["device_peak_bytes"] if r.profile else None
    log(f"{req.query_name:<10} engine={r.engine:<10} count={r.count} "
        f"latency_s={r.latency_s:.6f} compiles={meter['compiles']} "
        f"compile_s={meter['seconds']:.3f} device_peak_bytes={peak}")
    check("+partitioned" not in r.engine,
          f"{req.query_name}: routed off the single-executor path")
    return r, meter


def serve_and_check(csr, log) -> dict:
    """Serve ``csr`` through ``QueryServer`` and check every answer
    against :func:`reference_counts`; returns the figures."""
    server = QueryServer(csr)
    gdb = server._gdb_for(server.default_selectivity, 0)
    t0 = time.perf_counter()
    want = reference_counts(csr, gdb.unary)
    log(f"numpy references in {time.perf_counter() - t0:.2f}s: {want}")
    figures: dict = {"requests": {}}

    engines = set()
    for name in SHAPES:
        r, meter = _request(server, QueryRequest(name, profile=True), log)
        check(r.count == want[name],
              f"{name}: served {r.count}, reference {want[name]}")
        engines.add(r.engine)
        figures["requests"][name] = {
            "engine": r.engine, "latency_s": r.latency_s,
            "compiles": meter["compiles"],
            "compile_s": meter["seconds"],
            "device_peak_bytes": r.profile.memory["device_peak_bytes"]}
    check({"vlftj", "yannakakis", "hybrid"} <= engines,
          f"the shapes ran on {sorted(engines)}, not on all three "
          "device engines")

    # one enumeration page and its continuation
    q = get_query("3-path")
    first, _ = _request(server, QueryRequest("3-path", limit=PAGE_ROWS), log)
    check(first.next_cursor is not None or want["3-path"] <= PAGE_ROWS,
          "3-path: first page has no continuation")
    pages = [first.rows]
    if first.next_cursor is not None:
        nxt, _ = _request(server, QueryRequest(
            "3-path", limit=PAGE_ROWS, cursor=first.next_cursor), log)
        check(nxt.row_vars == first.row_vars, "3-path: columns changed")
        pages.append(nxt.rows)
    rows = np.concatenate(pages)
    check(len(rows) == min(want["3-path"], len(pages) * PAGE_ROWS),
          f"3-path: {len(rows)} rows on {len(pages)} pages")
    check_rows(q, first.row_vars, rows, csr, gdb.unary)
    check(strictly_ascending(rows),
          "3-path: pages are not one ascending run of distinct rows")

    # two requests under the preemptive scheduler
    t = time.perf_counter()
    both = server.execute_concurrent([QueryRequest("3-clique"),
                                      QueryRequest("4-clique")])
    for r in both:
        name = r.request.query_name
        check(r.count == want[name],
              f"concurrent {name}: {r.count}, reference {want[name]}")
    log(f"execute_concurrent 3-clique+4-clique "
        f"wall_s={time.perf_counter() - t:.6f}")

    # a repeated request: plan-cache hit, nothing compiled
    again, meter = _request(server, QueryRequest("3-clique", profile=True),
                            log)
    check(again.count == want["3-clique"], "repeat 3-clique: wrong count")
    check(again.plan_cached, "repeat 3-clique: plan cache missed")
    check(again.profile.jit["compiles"] == 0 and meter["compiles"] == 0,
          f"repeat 3-clique compiled: profile "
          f"{again.profile.jit['compiles']}, backend {meter['compiles']}")

    # the hybrid layout: the bitset check mode of the level kernel
    hgdb = HybridGraphDB.build(csr, gdb.unary)
    plan = plan_query(get_query("3-clique"), GraphStats.of(hgdb),
                      engine="vlftj")
    with compile_meter() as meter:
        t = time.perf_counter()
        c, stats = execute_stats(plan, hgdb)
        dt = time.perf_counter() - t
    bitset_rows = stats["raw"]["bitset_rows"]
    log(f"hybrid 3-clique count={c} bitset_rows={bitset_rows} "
        f"wall_s={dt:.6f} compiles={meter['compiles']}")
    check(c == want["3-clique"], f"hybrid 3-clique: {c}")
    check(bitset_rows > 0, "hybrid 3-clique: no row took the bitset check")
    figures["counts"] = want
    return figures


def _lftj_ref_count(part) -> int:
    csr, unary, name = part
    return engine_mod.count(get_query(name), GraphDB(csr, unary),
                            engine="lftj_ref")


def start_oracle(csr, unary: dict):
    """Start ``lftj_ref`` on every shape of :data:`SHAPES`, one CPU-only
    worker process each (``WorkerPool``'s process backend, which pins its
    workers to the CPU), while this process drives the chip.  Returns a
    function that waits for ``{shape: (count, seconds)}``."""
    parts = [(csr, unary, name) for name in SHAPES]
    pool = WorkerPool({i: [i] for i in range(len(parts))}, backend="process")
    box: dict = {}

    def work():
        try:
            box["run"] = pool.run(_lftj_ref_count, parts)
        except BaseException as e:          # re-raised by wait()
            box["error"] = e

    thread = threading.Thread(target=work, name="lftj_ref-oracle")
    thread.start()

    def wait() -> dict:
        thread.join()
        if "error" in box:
            raise box["error"]
        counts, seconds, _, backend = box["run"]
        check(backend == "process", f"oracle ran on the {backend} backend")
        return {name: (counts[i], seconds[i])
                for i, name in enumerate(SHAPES)}

    return wait


def oracle_check(server: QueryServer, wait, log) -> dict:
    """Serve all six shapes at :data:`ORACLE_SELECTIVITY` and check each
    against the ``lftj_ref`` counts that ``wait`` returns."""
    served = {name: server.execute(QueryRequest(
        name, selectivity=ORACLE_SELECTIVITY)) for name in SHAPES}
    got = {}
    for name, (ref, seconds) in wait().items():
        r = served[name]
        log(f"oracle {name:<10} engine={r.engine:<10} count={r.count} "
            f"lftj_ref={ref} lftj_ref_s={seconds:.2f}")
        check(r.count == ref, f"oracle {name}: served {r.count}, "
              f"lftj_ref {ref}")
        got[name] = ref
    return got


def run_smoke(served, oracle, log=print) -> dict:
    """The one-chip smoke: :func:`serve_and_check` on ``served`` and
    :func:`oracle_check` on ``oracle``, whose ``lftj_ref`` counts run on
    the host meanwhile.  Raises :class:`SmokeError` on a wrong answer;
    returns the figures."""
    oracle_server = QueryServer(oracle)
    gdb = oracle_server._gdb_for(ORACLE_SELECTIVITY, 0)
    wait = start_oracle(oracle, gdb.unary)
    with compile_meter() as total:
        figures = serve_and_check(served, log)
        figures["oracle"] = oracle_check(oracle_server, wait, log)
    figures["compiles"] = total["compiles"]
    figures["compile_s"] = total["seconds"]
    log(f"total compiles={total['compiles']} "
        f"compile_s={total['seconds']:.3f}")
    return figures


def run_four_chip(csr, devices, log=print) -> dict:
    """One 3-clique level over a 4-device mesh, CSR replicated
    (``spmd_join_step``) and CSR sharded with a ``ppermute`` ring
    (``spmd_sharded_join_step``), each against the one-device count."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist import (ShardedGraphDB, spmd_join_step,
                            spmd_sharded_join_step)
    from repro.launch.mesh import make_mesh

    gdb = GraphDB(csr, {})
    t = time.perf_counter()
    one_device = VLFTJ(get_query("3-clique"), gdb)
    one = one_device.count()
    log(f"one-device 3-clique count={one} "
        f"wall_s={time.perf_counter() - t:.6f}")
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    kw = dict(probe_cols=(0, 1), n_unary=0, lower_cols=(1,),
              upper_cols=(), n_iter=gdb.bsearch_iters, needs_degree=False)
    src = np.repeat(np.arange(csr.n_nodes, dtype=np.int32),
                    np.diff(csr.indptr))
    keep = src < csr.indices
    frontier = np.column_stack([src[keep], csr.indices[keep]]).astype(
        np.int32)
    replicated = NamedSharding(mesh, P())
    indptr = jax.device_put(csr.indptr.astype(np.int32), replicated)
    indices = jax.device_put(csr.indices.astype(np.int32), replicated)
    sgdb = ShardedGraphDB(csr, len(devices))

    def blocks(widths, make_step):
        """Per width class, the class's rows in blocks of one executor
        chunk per device (the last block padded) with a step that wide."""
        out = []
        for w in map(int, np.unique(widths)):
            f = frontier[widths == w]
            _, chunk = executor_geometry(w, width=w)
            rows = chunk * len(devices)
            pad = -len(f) % rows
            f = np.pad(f, ((0, pad), (0, 0)))
            m = np.pad(np.ones(len(f) - pad, np.int64), (0, pad))
            step = make_step(dict(kw, width=w))
            out += [(step, f[s:s + rows], m[s:s + rows])
                    for s in range(0, len(f), rows)]
        return out

    # the replicated step searches the check segments in the whole CSR,
    # so a row's tile is as wide as its probe segment (the last 3-clique
    # level's width classes); the ring gathers every bound vertex's
    # segment into one tile, so its width covers the longest of them
    longest = csr.degrees[frontier].max(axis=1)
    ring = np.minimum(np.maximum(
        1 << np.ceil(np.log2(np.maximum(longest, 1))).astype(np.int64),
        MIN_WIDTH), one_device.width)
    runs = {"replicated": blocks(
                one_device.row_widths(frontier),
                lambda k: partial(spmd_join_step(mesh, k), indptr, indices)),
            "sharded_ring": blocks(
                ring, lambda k: spmd_sharded_join_step(mesh, k, sgdb))}
    out = {"one_device": one}
    for name, batches in runs.items():
        with compile_meter() as meter:
            t = time.perf_counter()
            total = sum(int(step(f, m)) for step, f, m in batches)
            dt = time.perf_counter() - t
        log(f"{name} {len(devices)}-device 3-clique level count={total} "
            f"wall_s={dt:.6f} compiles={meter['compiles']} "
            f"compile_s={meter['seconds']:.3f}")
        check(total == one, f"{name}: {total}, one device {one}")
        out[name] = total
    return out


def result_line(dev, count: int) -> str:
    """The last line of standard output: the device as JAX reports it."""
    return json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded join steps on a "
                         "four-chip mesh")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU. JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind}); "
              "nothing was run.", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}.", file=sys.stderr)
        return 2

    def log(msg: str) -> None:
        print(f"smoke: {msg}", flush=True)

    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    log("figures below are smoke figures from one cold run, "
        "not benchmark figures")
    t = time.perf_counter()
    served = make_snap_like(SERVED_GRAPH, seed=0, scale=1.0)
    log(f"{SERVED_GRAPH}: {served.n_nodes} nodes, {served.n_edges} directed "
        f"edges, max degree {served.max_degree}, generated in "
        f"{time.perf_counter() - t:.2f}s")
    if args.chips == 4:
        run_four_chip(served, devices[:4], log)
    else:
        oracle = make_snap_like(ORACLE_GRAPH, seed=0, scale=1.0)
        run_smoke(served, oracle, log)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"device_peak_bytes={peak}")
    print(result_line(dev, len(devices)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
