"""Sharded worst-case-optimal join execution.

Two granularities of parallelism, matching the paper's evaluation setup:

* :func:`spmd_join_step` / :func:`spmd_spmv_step` — device-level SPMD.
  The frontier (or edge list) is row-sharded over a jax mesh; every
  device runs the *same* jitted expansion level (``vlftj._expand_level``,
  reused verbatim — the kernel never learns it is distributed) against a
  replicated CSR, and a single ``psum`` folds the per-shard counts.
  Binding-space sharding means no shuffle: a partial binding's whole
  subtree lives on the shard that owns the seed row.

* :class:`PartitionedJoin` — host-level static over-partitioning (the
  granularity factor).  The first GAO level's domain is dealt into
  ``n_workers x granularity`` cost-balanced parts
  (:func:`repro.core.plan.partition_first_level`); parts go to workers
  with the same deterministic deal as
  :func:`repro.train.stragglers.reassign_shards`, so a dead worker's
  parts can be re-dealt without recomputing anything.
"""
from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.device_graph import GraphDB
from ..core.plan import JoinPlan, partition_first_level
from ..core.query import Query
from ..core.vlftj import VLFTJ, _expand_level
from ..train.stragglers import reassign_shards
from .pool import WorkerPool


def spmd_join_step(mesh, level_kw: dict, axis_names=None,
                   plan: JoinPlan | None = None):
    """Build a sharded expansion-level counter over ``mesh``.

    ``level_kw`` holds the static kernel arguments of
    ``vlftj._expand_level`` (probe_cols, lower_cols, width, n_iter, ...).
    The returned function maps ``(indptr, indices, frontier, mult)`` to
    the global weighted count: CSR replicated, frontier/mult row-sharded
    over every mesh axis in ``axis_names`` (default: all axes — a join
    has no MXU work for a model axis, but its HBM bandwidth is real, see
    ``configs/wcoj.py``).

    Frontiers of any length are accepted: the wrapper pads rows to the
    shard-count multiple and zeroes the padding's ``mult`` itself (the
    kernel's ``counts * mult`` weighting nullifies padded rows) — callers
    used to pre-pad by hand, and a wrong hand-zeroed ``mult`` silently
    miscounted.  When ``plan`` carries a
    :attr:`~repro.core.plan.JoinPlan.level_callback`
    (``dist.rebalance.FrontierRebalancer``), the callback runs on the
    host frontier first, so a skew-triggered re-deal can reorder rows
    into cost-balanced device blocks before the sharded dispatch.
    """
    axes = tuple(mesh.axis_names) if axis_names is None else tuple(axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= int(mesh.shape[a])
    kw = dict(level_kw)
    kw.setdefault("count_only", True)

    def local_step(indptr, indices, frontier, mult):
        row_valid = jnp.ones((frontier.shape[0],), bool)
        counts = _expand_level(indptr, indices, (), frontier, mult,
                               row_valid, **kw)
        return jax.lax.psum(counts.sum(), axes)

    jitted = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P(axes), P(axes)),
        out_specs=P(), check_vma=False))

    rows_on = NamedSharding(mesh, P(axes))
    callback = getattr(plan, "level_callback", None)

    def step(indptr, indices, frontier, mult):
        if callback is not None:
            fr, ml = np.asarray(frontier), np.asarray(mult)
            # callback convention (VLFTJ._run): `level` is the level
            # just expanded, so its frontier has level+1 bound columns
            # and the callback prices levels[level+1] — the level this
            # step is about to dispatch
            upd = callback(fr.shape[1] - 1, fr, ml)
            if upd is not None:
                frontier, mult = upd
        rows = int(frontier.shape[0])
        pad = (-rows) % n_shards
        if pad:
            fr = np.zeros((rows + pad, frontier.shape[1]), dtype=np.int32)
            fr[:rows] = np.asarray(frontier)
            ml = np.zeros(rows + pad, dtype=np.int64)
            ml[:rows] = np.asarray(mult)
            frontier, mult = fr, ml
        # rows go straight to the device that owns them
        return jitted(indptr, indices, jax.device_put(frontier, rows_on),
                      jax.device_put(mult, rows_on))

    step.n_shards = n_shards
    return step


def spmd_spmv_step(mesh, n_nodes: int, axis_names=None):
    """Edge-sharded counting SpMV (the #Minesweeper message pass, Idea 8).

    The returned function maps ``(indices, src_ids, c)`` to
    ``y[v] = sum_{(v,u) in E} c[u]``: edges (``indices``/``src_ids``)
    row-sharded, the count vector ``c`` replicated, per-shard
    segment-sums psum-folded into the replicated output.  Edge rows must
    divide the shard count (trim or pad to the shard boundary).
    """
    axes = tuple(mesh.axis_names) if axis_names is None else tuple(axis_names)

    def local_step(indices, src_ids, c):
        part = jax.ops.segment_sum(c[indices], src_ids,
                                   num_segments=n_nodes)
        return jax.lax.psum(part, axes)

    return jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(axes), P(axes), P()),
        out_specs=P(), check_vma=False))


class PartitionedJoin:
    """Granularity-factor partitioned WCOJ (host-level work splitting).

    Splits the first GAO level's seed domain into
    ``n_workers * granularity`` cost-balanced parts and runs each part as
    a seeded count on the shared :class:`~repro.core.vlftj.VLFTJ`
    executor.  Parts are dealt to workers statically (part ``p`` to
    worker ``p % n_workers``; with ``dead`` workers, survivors pick up
    the orphaned parts via the same deterministic re-deal the training
    loop uses) and execute on a real concurrent pool
    (:class:`~repro.dist.pool.WorkerPool`) — one worker per alive
    schedule entry, each draining its owned parts in schedule order.
    ``backend='auto'`` selects process vs thread by payload picklability;
    the seeded-count task closes over the jitted executor, so it lands on
    threads, where the XLA compute releases the GIL and the jit cache is
    shared.  ``backend='sequential'`` restores the old single-thread walk
    (the equality baseline in the tests).

    ``stats`` after :meth:`count`:

    * ``parts`` — number of parts (``n_workers * granularity``);
    * ``part_sizes`` — seeds per part (balanced to within one);
    * ``part_time`` / ``part_counts`` — per-part seconds and counts;
    * ``worker_time`` — per-worker summed part time (len ``n_workers``;
      dead workers stay at 0.0);
    * ``makespan`` — max worker time, ``<= total_time`` always;
    * ``total_time`` — summed part time (single-worker equivalent);
    * ``backend`` / ``wall_time`` — what the pool actually ran on, and
      the concurrent wall-clock (incl. pool overhead; compare with
      ``makespan``, which aggregates pure part seconds).
    """

    def __init__(self, query: Query, gdb: GraphDB, n_workers: int = 4,
                 granularity: int = 2, plan: JoinPlan | None = None,
                 dead: frozenset[int] | set[int] = frozenset(),
                 backend: str = "auto", **vlftj_kw):
        if n_workers < 1 or granularity < 1:
            raise ValueError("n_workers and granularity must be >= 1")
        self.executor = VLFTJ(query, gdb, plan=plan, **vlftj_kw)
        self.query = query
        self.gdb = gdb
        self.n_workers = n_workers
        self.granularity = granularity
        self.n_parts = n_workers * granularity
        seeds = self.executor._domain_values(self.executor.plan[0])
        self.parts = partition_first_level(
            self.executor.join_plan, seeds, gdb.csr.degrees, self.n_parts)
        self.schedule = reassign_shards(n_workers, set(dead), granularity)
        self.backend = backend
        self.stats: dict = {
            "parts": self.n_parts,
            "part_sizes": [int(p.shape[0]) for p in self.parts],
        }

    def _count_part(self, seeds: np.ndarray) -> int:
        return self.executor.seeded_count(
            seeds.astype(np.int32), np.ones(seeds.shape[0], dtype=np.int64))

    def count(self) -> int:
        # warm the jitted level kernels once before fanning out: the
        # first part otherwise compiles while every other worker blocks
        # on the same compile lock, charging compilation to one part's
        # time and skewing the makespan accounting
        if self.parts and self.backend != "sequential":
            warm = max(self.parts, key=lambda p: p.shape[0])
            self._count_part(warm[:1])
        pool = WorkerPool(self.schedule, backend=self.backend)
        results, ptime, wall, backend = pool.run(self._count_part,
                                                 self.parts)
        part_time = np.zeros(self.n_parts)
        part_counts = np.zeros(self.n_parts, dtype=np.int64)
        for pid, c in results.items():
            part_counts[pid] = c
            part_time[pid] = ptime[pid]
        worker_time = [0.0] * self.n_workers
        for worker, owned in self.schedule.items():
            worker_time[worker] = float(part_time[owned].sum())
        self.stats.update({
            "part_time": part_time.tolist(),
            "part_counts": part_counts.tolist(),
            "worker_time": worker_time,
            "makespan": max(worker_time),
            "total_time": float(part_time.sum()),
            "backend": backend,
            "wall_time": wall,
        })
        return int(part_counts.sum())

    def pages(self, page_rows: int = 1024) -> Iterator[np.ndarray]:
        """Stream the join's output as fixed-size pages in global
        GAO-lexicographic order.

        Each part gets its own bounded-memory
        :class:`~repro.results.ResultCursor` (the shared executor seeded
        with the part's first-level values).  The parts partition the
        first GAO variable's *domain*, so streams interleave only at
        first-column granularity: the part holding the globally smallest
        head row owns every row up to the next part's head value, and
        whole runs splice over with one ``searchsorted`` — the merge a
        scatter-gather coordinator would run over real workers' page
        responses, with no per-row Python work."""
        from ..results.cursor import ResultCursor

        k = len(self.executor.gao)
        streams: list[list] = []      # [head buffer, cursor] per live part
        for p in self.parts:
            if p.shape[0] == 0:
                continue
            cur = ResultCursor(self.executor, page_rows=page_rows,
                               seeds=p.astype(np.int32))
            page = cur.next_page()
            if page is not None:
                streams.append([page, cur])
        out: list[np.ndarray] = []
        buffered = 0
        while streams:
            i = min(range(len(streams)),
                    key=lambda j: tuple(streams[j][0][0]))
            buf, cur = streams[i]
            others = [streams[j][0][0, 0]
                      for j in range(len(streams)) if j != i]
            if others:
                # first-column values are disjoint across parts, so the
                # run boundary is where the next part's head value starts
                cut = int(np.searchsorted(buf[:, 0], min(others),
                                          side="left"))
            else:
                cut = buf.shape[0]
            take, rest = buf[:cut], buf[cut:]
            if rest.shape[0]:
                streams[i][0] = rest
            else:
                nxt = cur.next_page()
                if nxt is None:
                    streams.pop(i)
                else:
                    streams[i][0] = nxt
            out.append(take)
            buffered += take.shape[0]
            while buffered >= page_rows:
                cat = np.concatenate(out) if len(out) > 1 else out[0]
                yield cat[:page_rows]
                cat = cat[page_rows:]
                out = [cat] if cat.shape[0] else []
                buffered = int(cat.shape[0])
        if buffered:
            yield (np.concatenate(out)
                   if len(out) > 1 else out[0]).reshape(-1, k)

    def enumerate(self, limit: int | None = None, page_rows: int = 1024):
        """All output tuples as a :class:`~repro.results.ResultSet` —
        columns in the plan's GAO order, rows lex-sorted (``limit``
        truncates after the ordering), produced by merging the
        per-part page streams of :meth:`pages`."""
        from ..results.result_set import ResultSet

        out: list[np.ndarray] = []
        taken = 0
        for page in self.pages(page_rows=page_rows):
            out.append(page)
            taken += page.shape[0]
            if limit is not None and taken >= limit:
                break
        rows = (np.concatenate(out, axis=0) if out
                else np.zeros((0, len(self.executor.gao)), dtype=np.int64))
        return ResultSet(self.executor.gao,
                         rows if limit is None else rows[:limit])
