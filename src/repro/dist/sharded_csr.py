"""Row-partitioned CSR: joins over graphs too large to replicate.

``spmd_join_step`` replicates the whole CSR on every device — fine until
the graph outgrows a device's HBM.  :class:`ShardedGraphDB` splits the
node domain into ``n_shards`` contiguous, edge-balanced ranges; shard
``s`` stores only its own rows (a local ``indptr`` rebased to 0 plus the
matching ``indices`` slice) and an owner map (the range ``bounds``) says
which shard serves any vertex.

Two executions consume the layout:

* :func:`sharded_count` — the host-level reference driver.  A full
  vectorized-LFTJ level loop in which *every* adjacency access goes
  through :meth:`ShardedGraphDB.gather_segments` /
  :meth:`~ShardedGraphDB.degrees_of`, i.e. only per-shard arrays are
  ever touched and cross-shard traffic is metered in
  ``ShardedGraphDB.exchange`` — the oracle the parity tests compare
  against the replicated engines on every tier-1 query shape.
* :func:`spmd_sharded_join_step` — the device-level SPMD expansion.
  Each device holds one shard's block; per level the frontier's probe
  and check adjacencies are collected during an ``n_shards``-hop
  ``ppermute`` ring rotation of the CSR blocks (the same ring wiring as
  ``dist.overlap.ring_all_reduce`` — :func:`~repro.dist.overlap
  .ring_schedule`), membership checks run as dense tile compares against
  the gathered segments, and one ``psum`` folds the counts.  Peak memory
  per device is one CSR shard (plus the in-flight neighbor block), not
  the whole graph.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core.plan import GraphStats, JoinPlan, compile_levels
from ..core.query import Query
from ..graphs.csr import CSRGraph, degrees_from_indptr
from .overlap import ring_schedule


class ShardedGraphDB:
    """Row-partitioned CSR + replicated unary sets.

    Shard ``s`` owns the contiguous node range ``[bounds[s],
    bounds[s+1])``, chosen so shard *edge* counts balance (a degree-sorted
    split would balance better under extreme skew but break the
    contiguous owner map the device exchange needs).  Unary predicates
    stay replicated — they are node bitmaps, small next to the adjacency.

    ``exchange`` meters the traffic a real deployment would put on the
    interconnect: ``gathers`` counts vectorized gather rounds (each maps
    to one ring rotation on devices) and ``values`` the adjacency
    entries shipped.
    """

    def __init__(self, csr: CSRGraph, n_shards: int,
                 unary: dict[str, np.ndarray] | None = None):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n_shards = n_shards
        self.n_nodes = csr.n_nodes
        self.n_edges = csr.n_edges
        targets = np.linspace(0, csr.indices.shape[0], n_shards + 1)
        bounds = np.searchsorted(csr.indptr, targets[1:-1], side="left")
        self.bounds = np.concatenate(
            [[0], np.maximum.accumulate(bounds), [csr.n_nodes]]
        ).astype(np.int64)
        self.local_indptr: list[np.ndarray] = []
        self.local_indices: list[np.ndarray] = []
        for s in range(n_shards):
            lo, hi = self.bounds[s], self.bounds[s + 1]
            iptr = csr.indptr[lo:hi + 1] - csr.indptr[lo]
            self.local_indptr.append(iptr.astype(np.int64))
            self.local_indices.append(
                csr.indices[csr.indptr[lo]:csr.indptr[hi]].astype(np.int64))
        self.unary = {k: np.asarray(v) for k, v in (unary or {}).items()}
        self.exchange = {"gathers": 0, "values": 0}

    # -- owner map -----------------------------------------------------------
    def owner_of(self, values: np.ndarray) -> np.ndarray:
        """Shard id owning each vertex."""
        v = np.asarray(values, dtype=np.int64)
        return np.searchsorted(self.bounds, v, side="right") - 1

    @property
    def shard_sizes(self) -> list[tuple[int, int]]:
        """Per-shard (nodes, edges) — the replication this layout avoids."""
        return [(int(self.bounds[s + 1] - self.bounds[s]),
                 int(self.local_indices[s].shape[0]))
                for s in range(self.n_shards)]

    # -- sharded accessors (all adjacency IO goes through these) -------------
    def degrees_of(self, values: np.ndarray) -> np.ndarray:
        """Degree lookup via each vertex's owning shard."""
        v = np.asarray(values, dtype=np.int64).ravel()
        owner = self.owner_of(v)
        deg = np.zeros(v.shape[0], dtype=np.int64)
        for s in range(self.n_shards):
            m = owner == s
            if not m.any():
                continue
            li = v[m] - self.bounds[s]
            iptr = self.local_indptr[s]
            deg[m] = iptr[li + 1] - iptr[li]
        self.exchange["gathers"] += 1
        return deg.reshape(np.asarray(values).shape)

    def gather_segments(self, values: np.ndarray
                        ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Adjacency of each vertex, row-major flattened.

        Returns ``(deg (R,), flat (deg.sum(),), reps (deg.sum(),))``:
        segment ``i`` occupies ``flat[offs[i]:offs[i+1]]`` (sorted, since
        shard slices preserve CSR order) and ``reps`` maps flat entries
        back to rows.  Host stand-in for one ring rotation: each shard
        contributes exactly the rows it owns.
        """
        v = np.asarray(values, dtype=np.int64).ravel()
        owner = self.owner_of(v)
        deg = np.zeros(v.shape[0], dtype=np.int64)
        starts = np.zeros(v.shape[0], dtype=np.int64)
        for s in range(self.n_shards):
            m = owner == s
            if not m.any():
                continue
            li = v[m] - self.bounds[s]
            iptr = self.local_indptr[s]
            starts[m] = iptr[li]
            deg[m] = iptr[li + 1] - iptr[li]
        total = int(deg.sum())
        flat = np.empty(total, dtype=np.int64)
        offs = np.concatenate([[0], np.cumsum(deg)])
        reps = np.repeat(np.arange(v.shape[0]), deg)
        pos = np.arange(total) - np.repeat(offs[:-1], deg)
        src = starts[reps] + pos
        own = owner[reps]
        for s in range(self.n_shards):
            m = own == s
            if m.any():
                flat[m] = self.local_indices[s][src[m]]
        self.exchange["gathers"] += 1
        self.exchange["values"] += total
        return deg, flat, reps

    # -- planner / device bridges --------------------------------------------
    def graph_stats(self) -> GraphStats:
        """Planner stats from shard metadata alone (no reassembly)."""
        max_deg = max((int(degrees_from_indptr(iptr).max(initial=0))
                       for iptr in self.local_indptr), default=0)
        n = max(1, self.n_nodes)
        return GraphStats(
            n_nodes=self.n_nodes, n_edges=self.n_edges,
            max_degree=max_deg, avg_degree=self.n_edges / n,
            unary_sizes=tuple(sorted(
                (name, int(len(ids))) for name, ids in self.unary.items())))

    def replicated(self) -> CSRGraph:
        """Reassembled full CSR — for parity tests only."""
        indptr = [np.zeros(1, dtype=np.int64)]
        off = 0
        for s in range(self.n_shards):
            indptr.append(self.local_indptr[s][1:] + off)
            off += int(self.local_indices[s].shape[0])
        return CSRGraph(indptr=np.concatenate(indptr),
                        indices=np.concatenate(self.local_indices)
                        if self.local_indices else np.zeros(0, np.int64),
                        n_nodes=self.n_nodes)

    def device_blocks(self) -> dict:
        """Uniformly padded per-shard blocks for the SPMD ring step.

        ``indptr`` (S, Ln+1) is end-padded with its last value (padding
        nodes read as degree 0); ``indices`` (S, Le) is zero-padded.
        """
        ln = max(self.bounds[s + 1] - self.bounds[s]
                 for s in range(self.n_shards))
        le = max(1, max((idx.shape[0] for idx in self.local_indices),
                        default=1))
        indptr = np.zeros((self.n_shards, ln + 1), dtype=np.int32)
        indices = np.zeros((self.n_shards, le), dtype=np.int32)
        for s in range(self.n_shards):
            iptr = self.local_indptr[s]
            indptr[s, :iptr.shape[0]] = iptr
            indptr[s, iptr.shape[0]:] = iptr[-1]
            idx = self.local_indices[s]
            indices[s, :idx.shape[0]] = idx
        return {"indptr": indptr, "indices": indices,
                "bounds": self.bounds.astype(np.int32)}


def _segment_member(deg_s, flat_s, reps_s, cand, cand_rows,
                    n_nodes: int) -> np.ndarray:
    """Membership of ``cand`` (row ``cand_rows``) in per-row sorted
    segments, via one global searchsorted over row-disjoint keys."""
    keys_seg = reps_s * n_nodes + flat_s          # globally ascending
    keys_c = cand_rows * n_nodes + cand
    idx = np.searchsorted(keys_seg, keys_c)
    ok = idx < keys_seg.shape[0]
    found = np.zeros(cand.shape[0], dtype=bool)
    found[ok] = keys_seg[idx[ok]] == keys_c[ok]
    return found


def sharded_count(query: Query, sgdb: ShardedGraphDB,
                  plan: JoinPlan | None = None,
                  chunk_rows: int = 8192) -> int:
    """Full WCOJ count touching the CSR only through shard-local arrays.

    Mirrors the vectorized-LFTJ level semantics (min-degree probe,
    membership checks, unary bitmaps, ``<`` filters, degree pruning) with
    every adjacency read routed through the sharded accessors, so its
    result equals the replicated engines' exactly while
    ``sgdb.exchange`` records the cross-shard traffic.
    """
    if plan is None:
        from ..core.planner import plan_query
        plan = plan_query(query, sgdb.graph_stats(), engine="vlftj")
    levels = plan.levels or compile_levels(query, plan.gao)
    n = sgdb.n_nodes
    bitmap: dict[str, np.ndarray] = {}
    for name, ids in sgdb.unary.items():
        bm = np.zeros(n, dtype=bool)
        bm[ids[ids < n]] = True
        bitmap[name] = bm

    def domain(lp) -> np.ndarray:
        if lp.unary:
            base = min((sgdb.unary[u] for u in lp.unary), key=len)
            vals = np.unique(np.asarray(base, dtype=np.int64))
            vals = vals[vals < n]
        else:
            vals = np.arange(n, dtype=np.int64)
        for u in lp.unary:
            vals = vals[bitmap[u][vals]]
        if lp.needs_degree:
            vals = vals[sgdb.degrees_of(vals) > 0]
        return vals

    k = len(levels)
    # trace hook: per-level exchange deltas (gathers / adjacency values
    # shipped) become 'exchange' events on the active trace — pure host
    # counter reads, mirroring what a real interconnect would carry
    from ..obs import current_trace
    tr = current_trace()

    def note_level(level: int, rows: int, g0: int, v0: int) -> None:
        if tr is None:
            return
        dg = sgdb.exchange["gathers"] - g0
        dv = sgdb.exchange["values"] - v0
        tr.level(level, obs_rows=rows,
                 var=plan.gao[level] if level < len(plan.gao) else None,
                 est_rows=(plan.level_est_rows[level]
                           if level < len(plan.level_est_rows) else None))
        tr.event("exchange", level=level, gathers=dg, values=dv,
                 bytes=dv * 8)

    frontier = domain(levels[0])[:, None]
    note_level(0, int(frontier.shape[0]),
               sgdb.exchange["gathers"], sgdb.exchange["values"])
    if k == 1:
        return int(frontier.shape[0])
    total = 0
    for level in range(1, k):
        g0, v0 = sgdb.exchange["gathers"], sgdb.exchange["values"]
        lp = levels[level]
        last = level == k - 1
        if frontier.shape[0] == 0:
            return total if last else 0
        if not lp.edge_sources:
            vals = domain(lp)
            if last and not lp.lower and not lp.upper:
                add = int(frontier.shape[0]) * int(vals.shape[0])
                note_level(level, total + add, g0, v0)
                return total + add
            reps = np.repeat(np.arange(frontier.shape[0]), vals.shape[0])
            cand = np.tile(vals, frontier.shape[0])
            ok = np.ones(cand.shape[0], dtype=bool)
            for col in lp.lower:
                ok &= cand > frontier[reps, col]
            for col in lp.upper:
                ok &= cand < frontier[reps, col]
            if last:
                note_level(level, total + int(ok.sum()), g0, v0)
                return total + int(ok.sum())
            frontier = np.concatenate(
                [frontier[reps[ok]], cand[ok][:, None]], axis=1)
            note_level(level, int(frontier.shape[0]), g0, v0)
            continue
        srcs = list(lp.edge_sources)
        out_parts: list[np.ndarray] = []
        for s0 in range(0, frontier.shape[0], chunk_rows):
            chunk = frontier[s0:s0 + chunk_rows]
            xs = chunk[:, srcs]                              # (C, P)
            deg = sgdb.degrees_of(xs)
            p = np.argmin(deg, axis=1)
            probe = np.take_along_axis(xs, p[:, None], axis=1)[:, 0]
            dstar, cand, reps = sgdb.gather_segments(probe)
            keep = np.ones(cand.shape[0], dtype=bool)
            for ci in range(len(srcs)):
                # gather check segments only for rows whose probe is a
                # DIFFERENT column — the probe column's adjacency is the
                # candidate set itself, already shipped (and its rows'
                # membership is trivially true)
                need_rows = np.flatnonzero(p != ci)
                if need_rows.size == 0:
                    continue
                seg = sgdb.gather_segments(xs[need_rows, ci])
                mask_c = (p != ci)[reps]
                comp = np.searchsorted(need_rows, reps[mask_c])
                keep[mask_c] &= _segment_member(*seg, cand[mask_c],
                                                comp, n)
            for u in lp.unary:
                keep &= bitmap[u][cand]
            for col in lp.lower:
                keep &= cand > chunk[reps, col]
            for col in lp.upper:
                keep &= cand < chunk[reps, col]
            if lp.needs_degree:
                keep &= sgdb.degrees_of(cand) > 0
            if last:
                total += int(keep.sum())
            else:
                out_parts.append(np.concatenate(
                    [chunk[reps[keep]], cand[keep][:, None]], axis=1))
        if last:
            note_level(level, total, g0, v0)
            return total
        frontier = (np.concatenate(out_parts, axis=0) if out_parts
                    else np.zeros((0, frontier.shape[1] + 1), np.int64))
        note_level(level, int(frontier.shape[0]), g0, v0)
    return total


# ---------------------------------------------------------------------------
# device-level SPMD ring step
# ---------------------------------------------------------------------------

def spmd_sharded_join_step(mesh, level_kw: dict, sgdb: ShardedGraphDB,
                           axis_names=None):
    """Sharded-CSR counterpart of :func:`~repro.dist.sharded_join
    .spmd_join_step`: one expansion level over ``mesh`` with **no CSR
    replication**.

    Each device holds one shard's padded ``(indptr, indices)`` block
    (``ShardedGraphDB.device_blocks``).  The frontier is row-sharded as
    usual; probe/check adjacency that lives on other shards is collected
    while the CSR blocks rotate around a ``ppermute`` ring (the
    :func:`~repro.dist.overlap.ring_schedule` wiring — after hop ``s``
    device ``me`` holds shard ``(me - s) % S``'s block, so ``S`` hops see
    every row).  Membership checks binary-search the gathered, per-row
    sorted segment tiles.  The returned function maps ``(frontier,
    mult)`` to the global weighted count — frontiers of any length (the
    wrapper pads to the shard multiple and zeroes the padding's
    ``mult``).  ``sgdb.n_shards`` must equal the ring size, and unary
    bitmaps are not supported (pre-filter the frontier; the replicated
    step has the same contract).
    """
    axes = tuple(mesh.axis_names) if axis_names is None else tuple(axis_names)
    if len(axes) != 1:
        raise ValueError("the sharded-CSR ring rotates over exactly one "
                         "mesh axis; pass axis_names=('data',)")
    axis = axes[0]
    n_dev = int(mesh.shape[axis])
    if sgdb.n_shards != n_dev:
        raise ValueError(f"graph is sharded {sgdb.n_shards} ways but the "
                         f"mesh axis {axis!r} has {n_dev} devices")
    if level_kw.get("n_unary", 0):
        raise ValueError("unary bitmaps are replicated; pre-filter the "
                         "frontier instead")
    blocks = sgdb.device_blocks()
    bounds = jnp.asarray(blocks["bounds"])
    probe_cols = tuple(level_kw["probe_cols"])
    lower_cols = tuple(level_kw.get("lower_cols", ()))
    upper_cols = tuple(level_kw.get("upper_cols", ()))
    width = int(level_kw["width"])
    needs_degree = bool(level_kw.get("needs_degree", False))
    n_iter = int(math.ceil(math.log2(max(2, width)))) + 1
    sentinel = np.int32(sgdb.n_nodes)    # > any vertex id

    def ring_deg_tiles(xs, iptr, idx, me, perm, want_tiles: bool):
        """Rotate the CSR blocks; collect degree (and segment tiles) for
        every vertex in ``xs``, whichever shard owns it."""
        ln = iptr.shape[0] - 1
        le = idx.shape[0]
        j = jnp.arange(width, dtype=jnp.int32)
        degs = jnp.zeros(xs.shape, jnp.int32)
        tiles = (jnp.full(xs.shape + (width,), sentinel, jnp.int32)
                 if want_tiles else None)
        cur_iptr, cur_idx = iptr, idx
        for s in range(sgdb.n_shards):
            sid = (me - s) % sgdb.n_shards
            lo, hi = bounds[sid], bounds[sid + 1]
            mine = (xs >= lo) & (xs < hi)
            li = jnp.clip(xs - lo, 0, max(0, ln - 1))
            st = cur_iptr[li]
            dg = cur_iptr[li + 1] - st
            degs = jnp.where(mine, dg, degs)
            if want_tiles:
                tl = cur_idx[jnp.clip(st[..., None] + j, 0, le - 1)]
                valid = j < dg[..., None]
                tl = jnp.where(valid, tl, sentinel)
                tiles = jnp.where(mine[..., None], tl, tiles)
            if s < sgdb.n_shards - 1:
                cur_iptr = jax.lax.ppermute(cur_iptr, axis, perm)
                if want_tiles:
                    cur_idx = jax.lax.ppermute(cur_idx, axis, perm)
        return degs, tiles

    def local_step(indptr_blk, indices_blk, frontier, mult):
        iptr, idx = indptr_blk[0], indices_blk[0]
        me = jax.lax.axis_index(axis)
        _, perm = ring_schedule(axis)
        xs = frontier[:, list(probe_cols)]                       # (C, P)
        degs, tiles = ring_deg_tiles(xs, iptr, idx, me, perm, True)
        p = jnp.argmin(degs, axis=1)
        cand = jnp.take_along_axis(tiles, p[:, None, None], axis=1)[:, 0]
        dstar = jnp.take_along_axis(degs, p[:, None], axis=1)
        keep = jnp.arange(width, dtype=jnp.int32)[None, :] < dstar
        for ci in range(len(probe_cols)):
            # sentinel-padded rows stay sorted: binary-search each
            # candidate in the gathered check segment
            seg = tiles[:, ci]                                   # (C, W)
            lo = jnp.zeros(cand.shape, jnp.int32)
            hi = jnp.full(cand.shape, width, jnp.int32)
            for _ in range(n_iter):
                mid = (lo + hi) // 2
                mv = jnp.take_along_axis(
                    seg, jnp.clip(mid, 0, width - 1), axis=1)
                go = mv < cand
                lo = jnp.where(go, mid + 1, lo)
                hi = jnp.where(go, hi, mid)
            at = jnp.take_along_axis(seg, jnp.clip(lo, 0, width - 1),
                                     axis=1)
            found = at == cand
            keep &= jnp.where((p == ci)[:, None], True, found)
        for col in lower_cols:
            keep &= cand > frontier[:, col][:, None]
        for col in upper_cols:
            keep &= cand < frontier[:, col][:, None]
        if needs_degree:
            # second ring pass, starting again from the home blocks
            # (ring_deg_tiles never mutates its inputs)
            degc, _ = ring_deg_tiles(jnp.clip(cand, 0, sentinel - 1),
                                     iptr, idx, me, perm, False)
            keep &= (degc > 0) & (cand < sentinel)
        counts = keep.sum(axis=1).astype(jnp.int64) * mult
        return jax.lax.psum(counts.sum(), axis)

    spec = PartitionSpec(axis)
    jitted = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(spec, spec, spec, spec), out_specs=PartitionSpec(),
        check_vma=False))
    # each block and frontier slice is placed straight onto its own
    # device, not staged through the first one
    sharded = NamedSharding(mesh, spec)
    indptr_j = jax.device_put(blocks["indptr"], sharded)
    indices_j = jax.device_put(blocks["indices"], sharded)

    def step(frontier, mult):
        frontier = np.asarray(frontier, dtype=np.int32)
        mult = np.asarray(mult, dtype=np.int64)
        pad = (-frontier.shape[0]) % n_dev
        if pad:
            frontier = np.pad(frontier, ((0, pad), (0, 0)))
            mult = np.pad(mult, (0, pad))
        return int(jitted(indptr_j, indices_j,
                          jax.device_put(frontier, sharded),
                          jax.device_put(mult, sharded)))

    step.n_shards = n_dev
    return step
