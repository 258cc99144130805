"""Vectorized LeapFrog TrieJoin — the TPU-native worst-case-optimal join.

The scalar LFTJ binds one variable at a time with leapfrogging iterators.
Here a *frontier* of thousands of partial bindings advances one GAO level
per step:

  1. **probe**: per frontier row, pick the shortest adjacency segment among
     the row's bound edge-neighbors (the leapfrog "smallest iterator first"
     rule, chosen per row with vector ops);
  2. **candidates**: the probe segment's values, a (rows, W) padded tile;
  3. **checks**: every other edge constraint via segmented binary search
     (``seek_lub``), every unary predicate via bitmap gather, every ``<``
     filter via vector compare — all lanes parallel;
  4. **expand**: count → compact into the next frontier (host numpy between
     jitted steps; static shapes inside).

The final level never materializes: surviving candidates are counted and
dotted with row multiplicities (the #Minesweeper trick, Idea 8).

Worst-case optimality carries over: each level emits exactly the scalar
LFTJ's bindings, and per-level work is O(probe segment + emitted · log N)
≤ Õ(AGM(Q)) for the same GAO.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from .device_graph import GraphDB
from .plan import (MIN_WIDTH, GraphStats, JoinPlan, LevelPlan,
                   compile_levels, executor_geometry)
from .query import Query

#: backward-compatible alias — the per-level compiler now lives in
#: ``core.plan`` so the planner and the engine share one definition.
compile_plan = compile_levels


# ---------------------------------------------------------------------------
# jitted level kernels
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=(
    "probe_cols", "n_unary", "lower_cols", "upper_cols",
    "width", "n_iter", "count_only", "needs_degree", "unroll",
    "check_mode", "check_width", "rotate_checks", "summary_stride",
    "n_iter2"))
def _expand_level(indptr, indices, bitmaps, frontier, mult,
                  row_valid, *, probe_cols, n_unary,
                  lower_cols, upper_cols, width, n_iter, count_only,
                  needs_degree, unroll=False, check_mode="bsearch",
                  check_width=0, rotate_checks=False, summary=None,
                  summary_stride=128, n_iter2=9, rep_tag=None,
                  bitset_words=None):
    """One GAO level for a frontier chunk.

    frontier: (C, n_bound) int32; mult: (C,) int64; row_valid: (C,) bool
    Returns weighted counts (C,) if count_only else (cand, keep).

    ``check_mode='bitset'`` (hybrid layout): every bound edge source in
    the chunk is a hub — membership is one gather into its
    ``bitset_words`` row plus a bit test, instead of ``n_iter``
    binary-search gather rounds.  ``rep_tag`` maps vertex id -> bitset
    row (the caller's bucketing guarantees tags >= 0 here).
    """
    m = indices.shape[0]
    xs = frontier[:, list(probe_cols)]                        # (C, P)
    starts = indptr[xs]
    degs = indptr[xs + 1] - starts                            # (C, P)
    p = jnp.argmin(degs, axis=1)                              # (C,)

    def sel(a):
        return jnp.take_along_axis(a, p[:, None], axis=1)[:, 0]

    start_star = sel(starts)
    deg_star = sel(degs)

    j = jnp.arange(width, dtype=jnp.int32)
    cand_idx = start_star[:, None] + j[None, :]
    cand = indices[jnp.clip(cand_idx, 0, max(0, m - 1))]      # (C, W)
    keep = (j[None, :] < deg_star[:, None]) & row_valid[:, None]

    # membership checks against every other bound edge-neighbor's segment.
    # rotate_checks synthesizes exactly the P-1 non-probe sources per row
    # (rotating from the argmin) — no wasted self-check lanes.
    n_probe = len(probe_cols)
    if rotate_checks and n_probe > 1:
        check_sources = []
        for s in range(1, n_probe):
            rot = (p[:, None] + s) % n_probe
            check_sources.append(
                (jnp.take_along_axis(xs, rot, axis=1)[:, 0], None))
    else:
        check_sources = [(xs[:, ci], ci) for ci in range(n_probe)]
    for y, ci in check_sources:
        lo = indptr[y][:, None]
        hi = (indptr[y + 1])[:, None]
        if check_mode == "bitset":
            # hybrid-layout membership: gather the check vertex's bitset
            # word at cand>>5 and test bit cand&31 — O(1) per lane
            # (kernels/intersect_bitset.py is the standalone form)
            row = rep_tag[y]                               # (C,) >= 0
            wordv = bitset_words[row[:, None],
                                 (cand >> 5).astype(jnp.int32)]  # (C, W)
            found = ((wordv >> (cand & 31).astype(jnp.uint32)) & 1) != 0
        elif check_mode == "tile":
            # tile-leapfrog membership (the Pallas-kernel strategy in
            # HLO): gather the check segment ONCE and dense-compare —
            # one table gather instead of n_iter binary-search rounds.
            # Caller guarantees every check segment fits check_width
            # (the engine buckets rows by degree).
            j2 = jnp.arange(check_width, dtype=jnp.int32)
            seg_idx = lo + j2[None, :]
            seg = indices[jnp.clip(seg_idx, 0, max(0, m - 1))]   # (C, W2)
            seg_ok = seg_idx < hi
            eq = (cand[:, :, None] == seg[:, None, :])
            eq &= seg_ok[:, None, :]
            found = eq.any(axis=2)
        elif check_mode == "bsearch2":
            from ..kernels.ref import searchsorted_segments_2level_ref
            _, found = searchsorted_segments_2level_ref(
                indices, summary, lo, hi, cand, stride=summary_stride,
                n1=n_iter, n2=n_iter2, unroll=unroll)
        else:
            _, found = kops.searchsorted_segments(
                indices, lo, hi, cand, n_iter, unroll=unroll)
        if ci is None:
            keep &= found
        else:
            is_probe = p == ci  # the chosen probe needs no self-check
            keep &= jnp.where(is_probe[:, None], True, found)

    for b in range(n_unary):
        keep &= bitmaps[b][jnp.clip(cand, 0, bitmaps[b].shape[0] - 1)]
    for col in lower_cols:
        keep &= cand > frontier[:, col][:, None]
    for col in upper_cols:
        keep &= cand < frontier[:, col][:, None]
    if needs_degree:
        keep &= (indptr[cand + 1] - indptr[cand]) > 0

    if count_only:
        counts = keep.sum(axis=1).astype(jnp.int64)
        return counts * mult
    return cand, keep


@partial(jax.jit, static_argnames=("n_unary", "needs_degree"))
def _filter_values(indptr, bitmaps, values, *, n_unary, needs_degree):
    keep = jnp.ones_like(values, dtype=bool)
    for b in range(n_unary):
        keep &= bitmaps[b][values]
    if needs_degree:
        keep &= (indptr[values + 1] - indptr[values]) > 0
    return keep


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class VLFTJ:
    """Host-orchestrated, device-vectorized LFTJ over a :class:`GraphDB`."""

    def __init__(self, query: Query, gdb: GraphDB,
                 gao: tuple[str, ...] | None = None,
                 chunk_rows: int = 8192,
                 elem_budget: int = 1 << 22,
                 width: int | None = None,
                 check_mode: str = "bsearch",
                 tile_width: int = 512,
                 rotate_checks: bool = False,
                 summary_stride: int = 128,
                 plan: JoinPlan | None = None):
        if plan is None:
            # plan-free construction is a thin wrapper over the planner
            from .planner import plan_query
            plan = plan_query(query, GraphStats.of(gdb), engine="vlftj",
                              gao=gao)
        elif gao is not None and tuple(gao) != plan.gao:
            raise ValueError("both plan= and a conflicting gao= given")
        self.query = query
        self.gdb = gdb
        self.join_plan = plan
        self.gao = plan.gao
        self.plan = plan.levels or compile_levels(query, self.gao)
        self.n_iter = gdb.bsearch_iters
        self.width, self._chunk_cap = executor_geometry(
            gdb.max_degree, chunk_rows, elem_budget, width)
        # membership strategy: 'bsearch' (log-round binary search),
        # 'auto' (degree-bucketed: rows whose check segments fit
        # ``tile_width`` take the gather-once tile-compare path — the
        # Pallas kernel's schedule; the heavy tail keeps binary search)
        self.check_mode = check_mode
        self.tile_width = tile_width
        self.rotate_checks = rotate_checks
        self.summary_stride = summary_stride
        if check_mode == "bsearch2":
            import math as _math
            blocks = max(2, gdb.max_degree // summary_stride + 2)
            self.n_iter1 = int(_math.ceil(_math.log2(blocks))) + 1
            self.n_iter2 = int(_math.ceil(_math.log2(2 * summary_stride
                                                     + 2))) + 1
        # hybrid-layout routing: the planner's per-level representation
        # choice is honoured only when the GraphDB actually carries a
        # bitset layout (hubs occupy the renumbered id prefix)
        layout = getattr(gdb, "layout", None)
        self._n_hubs = int(layout.n_hubs) if layout is not None else 0
        lv = plan.level_layouts
        self.level_layouts = (lv if len(lv) == len(self.plan)
                              else ("array",) * len(self.plan))
        # keep chunk x width under the element budget; a narrower width
        # class takes more rows per chunk under the same two caps
        self.chunk_rows = self._chunk_cap
        self._geometry = (chunk_rows, elem_budget)
        # the unified stats namespace (docs/OBSERVABILITY.md): scalar
        # counters plus per-GAO-level observations — level_rows maps
        # level -> observed frontier cardinality after it binds (the
        # "obs" side of Q-error), level_wall_s the host wall time spent
        # in that level, level_paths the kernel path taken per row
        # (bitset/tile/bsearch).  All plain host dict writes: tracing
        # harvests these after the run, so hot loops gain no device work.
        self.stats = {"chunks": 0, "frontier_peak": 0, "candidates": 0,
                      "tile_rows": 0, "bsearch_rows": 0, "bitset_rows": 0,
                      "ll_compiles": 0, "ll_calls": 0, "rows_expanded": 0,
                      "level_rows": {}, "level_wall_s": {},
                      "level_paths": {}}
        # AOT-compiled final-level executables keyed on frontier geometry
        # (see last_level_extensions) — one compile per shape, then the
        # page loop skips the jitted dispatch path entirely
        self._ll_compiled: dict = {}

    # -- host helpers --------------------------------------------------------
    def _domain_values(self, lp: LevelPlan) -> np.ndarray:
        """Unary-filtered candidate domain for an edge-unconstrained var."""
        if lp.unary:
            base = min((self.gdb.unary[u] for u in lp.unary), key=len)
            values = np.asarray(base, dtype=np.int32)
        else:
            values = np.arange(self.gdb.n_nodes, dtype=np.int32)
        bitmaps = tuple(self.gdb.dev(f"bitmap:{u}") for u in lp.unary)
        keep = np.asarray(_filter_values(
            self.gdb.dev("indptr"), bitmaps, jnp.asarray(values),
            n_unary=len(bitmaps), needs_degree=lp.needs_degree))
        return values[keep]

    def _expand_dense(self, frontier, mult, lp, last_count):
        """A level with no bound edge neighbor: cross product with the
        (unary-filtered) domain.  Rare; GAO choice avoids it."""
        values = self._domain_values(lp)
        C = frontier.shape[0]
        if last_count and not lp.lower and not lp.upper:
            return None, None, int(mult.sum()) * values.shape[0]
        reps = np.repeat(np.arange(C), values.shape[0])
        vals = np.tile(values, C)
        ok = np.ones(vals.shape[0], dtype=bool)
        for col in lp.lower:
            ok &= vals > frontier[reps, col]
        for col in lp.upper:
            ok &= vals < frontier[reps, col]
        reps, vals = reps[ok], vals[ok]
        if last_count:
            return None, None, int(mult[reps].sum())
        nf = np.concatenate([frontier[reps], vals[:, None].astype(np.int32)],
                            axis=1)
        return nf, mult[reps], 0

    def _bucket(self, frontier, mult, lp, layout: str = "array"):
        """Bucket rows by membership strategy: representation tags first
        (hybrid layout), then degree (``check_mode='auto'``).

        When the plan marked this level ``'bitset'``/``'mixed'`` and the
        graph carries a layout, rows whose bound edge sources are *all*
        hubs take the bitset gather-test path; the remainder falls
        through to the configured array strategy.  Hubs are the
        renumbered id prefix, so the tag test is one compare.
        """
        out = []
        if (layout != "array" and self._n_hubs and lp.edge_sources
                and len(lp.edge_sources) >= 2 and frontier.shape[0]):
            elig = (frontier[:, list(lp.edge_sources)]
                    < self._n_hubs).all(axis=1)
            if elig.any():
                self.stats["bitset_rows"] += int(elig.sum())
                out.append((frontier[elig], mult[elig], "bitset"))
                rest = ~elig
                frontier, mult = frontier[rest], mult[rest]
            if frontier.shape[0] == 0:
                return out
        if self.check_mode != "auto" or not lp.edge_sources:
            mode = (self.check_mode if self.check_mode in
                    ("tile", "bsearch2") else "bsearch")
            return out + [(frontier, mult, mode)]
        deg = self.gdb.csr.degrees
        maxdeg = np.max(
            deg[frontier[:, list(lp.edge_sources)]], axis=1)
        tile = maxdeg <= self.tile_width
        self.stats["tile_rows"] += int(tile.sum())
        self.stats["bsearch_rows"] += int((~tile).sum())
        if tile.any():
            out.append((frontier[tile], mult[tile], "tile"))
        if (~tile).any():
            out.append((frontier[~tile], mult[~tile], "bsearch"))
        return out

    def row_widths(self, frontier: np.ndarray, level: int = -1) -> np.ndarray:
        """Per-row candidate-tile width at ``level``: the row's probe
        segment (its smallest bound adjacency) rounded up to a power of
        two, between :data:`MIN_WIDTH` and the graph's padded width."""
        lp = self.plan[level]
        deg = self.gdb.csr.degrees[
            frontier[:, list(lp.edge_sources)]].min(axis=1)
        w = 1 << np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64)
        return np.minimum(np.maximum(w, MIN_WIDTH), self.width)

    def _width_split(self, frontier, mult, level: int):
        """Split a bucket into ``(frontier, mult, width, chunk_rows)`` runs
        of one width class each; each class keeps ``chunk x width`` under
        the element budget."""
        if frontier.shape[0] == 0:
            return
        widths = self.row_widths(frontier, level)
        order = np.argsort(widths, kind="stable")
        widths = widths[order]
        cuts = np.flatnonzero(np.diff(widths)) + 1
        for s, e in zip(np.r_[0, cuts], np.r_[cuts, len(order)]):
            w = int(widths[s])
            _, chunk = executor_geometry(w, *self._geometry, width=w)
            idx = order[s:e]
            yield frontier[idx], mult[idx], w, chunk

    # -- main loop -----------------------------------------------------------
    def _run(self, count_only: bool = True, frontier: np.ndarray | None = None,
             mult: np.ndarray | None = None, max_levels: int | None = None,
             start_level: int | None = None):
        """Advance the frontier through GAO levels ``< max_levels``
        (default: all).  ``repro.results.ResultCursor`` passes
        ``max_levels=len(plan)-1`` to materialize only the penultimate
        frontier and re-enter the final level itself, page by page.

        ``start_level`` resumes mid-join from a frontier with that many
        columns already bound (default: inferred from the frontier width)
        — the level-synchronous distributed driver
        (``repro.dist.rebalance.AdaptiveJoin``) advances shards one level
        at a time this way.  When the plan carries a ``level_callback``
        it runs at every interior level boundary and may replace the
        ``(frontier, mult)`` pair (e.g. re-dealing rows across shards)
        or *raise* to suspend — the quantum scheduler's budget callback
        raises ``repro.serve.scheduler.Preempted`` carrying exactly this
        ``(frontier, mult, next level)`` state, which a later
        ``_run(frontier=..., mult=..., start_level=...)`` call resumes
        without losing or repeating any work (level boundaries are the
        engine's only host-visible synchronization points, so suspension
        there is lossless by construction).
        """
        gdb = self.gdb
        indptr, indices = gdb.dev("indptr"), gdb.dev("indices")
        # device profiling (repro.obs.profile): resolved once per run —
        # None (the default) keeps every hook below a dead branch, so a
        # disabled profile adds zero work beyond this contextvar read
        # (lazy import: repro.obs pulls in repro.core at package level)
        from ..obs.profile import current_profile, span
        prof = current_profile()
        n_levels = len(self.plan) if max_levels is None else max_levels
        lv_rows = self.stats["level_rows"]
        lv_wall = self.stats["level_wall_s"]
        lv_paths = self.stats["level_paths"]
        if frontier is None:
            t0 = time.perf_counter()
            with span("vlftj.level", level=0, rows=0):
                frontier = self._domain_values(self.plan[0])[:, None]
            lv_rows[0] = int(frontier.shape[0])
            lv_wall[0] = round(time.perf_counter() - t0, 6)
        frontier = np.asarray(frontier, dtype=np.int32)
        if mult is None:
            mult = np.ones(frontier.shape[0], dtype=np.int64)
        start = frontier.shape[1] if start_level is None else start_level
        cb = self.join_plan.level_callback

        def boundary(level, frontier, mult):
            if cb is None or level >= n_levels - 1:
                return frontier, mult
            upd = cb(level, frontier, mult)
            if upd is None:
                return frontier, mult
            return (np.asarray(upd[0], dtype=np.int32),
                    np.asarray(upd[1], dtype=np.int64))

        total = 0
        for level in range(start, n_levels):
            t_lv = time.perf_counter()
            lp = self.plan[level]
            bitmaps = tuple(gdb.dev(f"bitmap:{u}") for u in lp.unary)
            last = level == n_levels - 1
            last_count = last and count_only
            with span("vlftj.level", level=level,
                      rows=int(frontier.shape[0])):
                self.stats["rows_expanded"] += int(frontier.shape[0])
                if not lp.edge_sources:
                    frontier, mult, add = self._expand_dense(
                        frontier, mult, lp, last_count)
                    total += add
                    if last_count:
                        lv_rows[level] = int(total)
                        lv_wall[level] = (lv_wall.get(level, 0.0) + round(
                            time.perf_counter() - t_lv, 6))
                        return total
                    lv_rows[level] = int(frontier.shape[0])
                    lv_wall[level] = (lv_wall.get(level, 0.0)
                                      + round(time.perf_counter() - t_lv, 6))
                    if prof is not None:
                        prof.sample_memory()
                    frontier, mult = boundary(level, frontier, mult)
                    continue
                C = frontier.shape[0]
                if C == 0:
                    lv_rows[level] = 0
                    break
                with span("vlftj.split", level=level):
                    groups = self._bucket(frontier, mult, lp,
                                          layout=self.level_layouts[level])
                    runs = [(f, m, mode, width, chunk)
                            for gf, gm, mode in groups
                            for f, m, width, chunk in self._width_split(
                                gf, gm, level)]
                paths = lv_paths.setdefault(level, {})
                for gfrontier, _, mode in groups:
                    paths[mode] = paths.get(mode, 0) + int(gfrontier.shape[0])
                new_rows, new_vals, new_mult = [], [], []
                for gfrontier, gmult, mode, width, chunk_rows in runs:
                    for s in range(0, gfrontier.shape[0], chunk_rows):
                        e = min(gfrontier.shape[0], s + chunk_rows)
                        # one chunk: its arguments, the dispatch, and the
                        # host conversion that blocks on the result, so the
                        # device work it causes lies inside this span
                        with span("vlftj.chunk", profiler_only=True,
                                  level=level, width=width, rows=e - s,
                                  mode=mode):
                            out = self._chunk(
                                gfrontier[s:e], gmult[s:e], chunk_rows,
                                bitmaps, lp, mode, width, last_count,
                                indptr, indices)
                        if prof is not None:
                            prof.record_jit_call()
                        if last_count:
                            total += out
                            continue
                        fchunk, mchunk, cand, keep = out
                        with span("vlftj.compact", profiler_only=True,
                                  level=level):
                            rows, cols = np.nonzero(keep)
                            new_rows.append(fchunk[rows])
                            new_vals.append(cand[rows, cols])
                            new_mult.append(mchunk[rows])
                if last_count:
                    lv_rows[level] = int(total)
                    lv_wall[level] = (lv_wall.get(level, 0.0)
                                      + round(time.perf_counter() - t_lv, 6))
                    if prof is not None:
                        prof.sample_memory()
                    return total
                with span("vlftj.compact", profiler_only=True, level=level):
                    frontier = np.concatenate(
                        [np.concatenate(new_rows, 0) if new_rows else
                         np.zeros((0, frontier.shape[1]), np.int32),
                         (np.concatenate(new_vals)[:, None].astype(np.int32)
                          if new_vals else np.zeros((0, 1), np.int32))],
                        axis=1)
                    mult = (np.concatenate(new_mult) if new_mult
                            else np.zeros(0, np.int64))
                # record before the boundary callback: a budget callback
                # may raise (preemption) and the observation must survive
                lv_rows[level] = int(frontier.shape[0])
                lv_wall[level] = (lv_wall.get(level, 0.0)
                                  + round(time.perf_counter() - t_lv, 6))
                if prof is not None:
                    # memory watermark at the level boundary — the
                    # engine's host-visible synchronization point, where
                    # the next level's frontier is fully materialized
                    prof.sample_memory()
                frontier, mult = boundary(level, frontier, mult)
                self.stats["frontier_peak"] = max(
                    self.stats["frontier_peak"], frontier.shape[0])
        if count_only:
            return int(mult.sum())
        return frontier

    def _chunk(self, frontier, mult, chunk_rows, bitmaps, lp, mode, width,
               last_count, indptr, indices):
        """Dispatch one chunk of a level's run and wait for it: the
        weighted count of its survivors (``last_count``), else
        ``(padded frontier, padded mult, candidates, keep mask)`` on the
        host."""
        real = frontier.shape[0]
        # pad a partial chunk only to the next power of two: kernel cost
        # tracks live rows (a 100-row tail no longer dispatches a full
        # chunk_rows kernel) while the jit cache stays bounded at
        # log2(chunk_rows) shapes per static-arg combo and width class
        crows = min(chunk_rows, max(8, 1 << (real - 1).bit_length()))
        pad = crows - real
        fchunk = np.pad(frontier, ((0, pad), (0, 0)))
        mchunk = np.pad(mult, (0, pad))
        rv = np.zeros(crows, dtype=bool)
        rv[:real] = True
        args = (indptr, indices, bitmaps, jnp.asarray(fchunk),
                jnp.asarray(mchunk), jnp.asarray(rv))
        kw = dict(probe_cols=lp.edge_sources, n_unary=len(bitmaps),
                  lower_cols=lp.lower, upper_cols=lp.upper, width=width,
                  n_iter=self.n_iter, needs_degree=lp.needs_degree,
                  check_mode=mode,
                  check_width=self.tile_width if mode == "tile" else 0,
                  rotate_checks=self.rotate_checks)
        if mode == "bsearch2":
            kw.update(n_iter=self.n_iter1, n_iter2=self.n_iter2,
                      summary=self.gdb.dev(f"summary:{self.summary_stride}"),
                      summary_stride=self.summary_stride)
        elif mode == "bitset":
            kw.update(rep_tag=self.gdb.dev("rep_tag"),
                      bitset_words=self.gdb.dev("bitset_words"))
        self.stats["chunks"] += 1
        self.stats["candidates"] += crows * width
        if last_count:
            return int(np.asarray(_expand_level(
                *args, count_only=True, **kw)).sum())
        cand, keep = (np.asarray(x) for x in _expand_level(
            *args, count_only=False, **kw))
        return fchunk, mchunk, cand, keep

    # -- enumeration support -------------------------------------------------
    def last_level_counts(self, frontier: np.ndarray,
                          row_valid: np.ndarray | None = None) -> np.ndarray:
        """Surviving final-level extension *counts* per penultimate-
        frontier row (unit multiplicity) — the cheap pass the adaptive
        cursor uses to size expansion chunks by actual fanout instead of
        the worst-case tile width.  Same constraint semantics as
        :meth:`last_level_extensions`; shares its AOT-compile cache."""
        lp = self.plan[-1]
        frontier = np.asarray(frontier, dtype=np.int32)
        C = frontier.shape[0]
        if row_valid is None:
            row_valid = np.ones(C, dtype=bool)
        if C == 0:
            return np.zeros(0, dtype=np.int64)
        if not lp.edge_sources:
            counts, _ = self.last_level_extensions(frontier, row_valid)
            return counts
        out = self._final_level_call(frontier, row_valid, count_only=True)
        return np.asarray(out, dtype=np.int64)

    def last_level_extensions(self, frontier: np.ndarray,
                              row_valid: np.ndarray | None = None
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Surviving final-level extensions for one penultimate-frontier
        chunk: ``(counts (C,), values (counts.sum(),))`` with each row's
        values ascending (CSR adjacencies are sorted).  Membership checks
        use the binary-search path — the degree-bucketing of
        ``check_mode='auto'`` reorders rows, which would break the
        row-aligned counts the cursor pages by."""
        lp = self.plan[-1]
        frontier = np.asarray(frontier, dtype=np.int32)
        C = frontier.shape[0]
        if row_valid is None:
            row_valid = np.ones(C, dtype=bool)
        if C == 0:
            return (np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64))
        if not lp.edge_sources:
            # dense level: per-row cross product with the sorted domain
            values = np.sort(self._domain_values(lp))
            counts = np.zeros(C, dtype=np.int64)
            out: list[np.ndarray] = []
            for r in range(C):
                if not row_valid[r]:
                    continue
                vals = values
                for col in lp.lower:
                    vals = vals[vals > frontier[r, col]]
                for col in lp.upper:
                    vals = vals[vals < frontier[r, col]]
                counts[r] = vals.shape[0]
                out.append(vals)
            flat = (np.concatenate(out) if out
                    else np.zeros(0, dtype=np.int64))
            return counts, flat.astype(np.int64)
        cand, keep = self._final_level_call(frontier, row_valid,
                                            count_only=False)
        counts = keep.sum(axis=1).astype(np.int64)
        return counts, cand[keep].astype(np.int64)

    def _final_level_call(self, frontier: np.ndarray, row_valid: np.ndarray,
                          count_only: bool):
        """Dispatch the final-level kernel for one frontier chunk.

        ``repro.results.ResultCursor`` re-enters this level once per
        page with an identical geometry, so non-``bsearch2`` modes are
        AOT-compiled once per ``(shape, width, count_only)`` and the
        compiled executable is dispatched directly — no per-page jit cache
        probe (static-arg hashing + aval matching).  The candidate tile is
        as wide as the widest width class among the chunk's valid rows.
        """
        lp = self.plan[-1]
        bitmaps = tuple(self.gdb.dev(f"bitmap:{u}") for u in lp.unary)
        mode = self.check_mode if self.check_mode in ("tile", "bsearch2") \
            else "bsearch"
        width = int(self.row_widths(frontier[row_valid]).max(
            initial=min(MIN_WIDTH, self.width)))
        kw = dict(probe_cols=lp.edge_sources, n_unary=len(bitmaps),
                  lower_cols=lp.lower, upper_cols=lp.upper,
                  width=width, n_iter=self.n_iter,
                  needs_degree=lp.needs_degree, count_only=count_only,
                  check_mode=mode,
                  check_width=self.tile_width if mode == "tile" else 0,
                  rotate_checks=self.rotate_checks)
        if mode == "bsearch2":
            kw.update(n_iter=self.n_iter1, n_iter2=self.n_iter2,
                      summary=self.gdb.dev(f"summary:{self.summary_stride}"),
                      summary_stride=self.summary_stride)
        from ..obs.profile import current_profile, span
        with span("vlftj.final", width=width,
                  rows=int(np.count_nonzero(row_valid))):
            args = (self.gdb.dev("indptr"), self.gdb.dev("indices"),
                    bitmaps, jnp.asarray(frontier),
                    jnp.ones(frontier.shape[0], dtype=jnp.int64),
                    jnp.asarray(row_valid))
            self.stats["ll_calls"] += 1
            prof = current_profile()
            if prof is not None:
                prof.record_jit_call()
            if mode == "bsearch2":
                # summary is a traced kwarg, not a static — the AOT
                # signature below would drop it; this mode keeps the
                # jitted dispatch
                out = _expand_level(*args, **kw)
            else:
                key = (frontier.shape, width, count_only)
                fn = self._ll_compiled.get(key)
                if fn is None:
                    self.stats["ll_compiles"] += 1
                    t_c = time.perf_counter()
                    with span("vlftj.compile", width=width):
                        fn = _expand_level.lower(*args, **kw).compile()
                    if prof is not None:
                        prof.record_compile(
                            f"final_level{frontier.shape}/width={width}"
                            f"/count={count_only}",
                            time.perf_counter() - t_c)
                    self._ll_compiled[key] = fn
                out = fn(*args)
            if count_only:
                return np.asarray(out)
            return tuple(np.asarray(x) for x in out)

    # -- public API ----------------------------------------------------------
    def count(self) -> int:
        return int(self._run(count_only=True))

    def enumerate(self, limit: int | None = None,
                  seeds: np.ndarray | None = None) -> np.ndarray:
        """All output tuples: int64, columns in GAO order
        (``self.output_vars``), rows lexicographically sorted; ``limit``
        truncates *after* the ordering (the shared engine contract —
        ``repro.results``).  ``seeds`` pre-binds the first GAO variable
        (the enumeration analogue of :meth:`seeded_count`)."""
        frontier = None if seeds is None \
            else np.asarray(seeds, dtype=np.int32)[:, None]
        out = self._run(count_only=False, frontier=frontier)
        rows = np.asarray(out, dtype=np.int64)
        k = len(self.plan)
        if rows.shape[0] == 0:
            return np.zeros((0, k), dtype=np.int64)
        from ..obs.profile import span
        with span("vlftj.sort", rows=int(rows.shape[0])):
            rows = rows[np.lexsort(rows.T[::-1])]
        return rows if limit is None else rows[:limit]

    @property
    def output_vars(self) -> tuple[str, ...]:
        """Column order of :meth:`enumerate` (the plan's GAO)."""
        return self.gao

    # -- suspend / resume ----------------------------------------------------
    def advance(self, frontier: np.ndarray | None = None,
                mult: np.ndarray | None = None,
                start_level: int | None = None,
                max_levels: int | None = None) -> np.ndarray:
        """Advance a partial-binding frontier through GAO levels — the
        public suspend/resume hook.

        Args:
            frontier: ``(rows, w)`` int32 partial bindings with ``w``
                GAO columns already bound (``None``: start fresh from
                the level-0 domain).
            mult: ``(rows,)`` int64 multiplicities (``None``: ones).
            start_level: resume level (``None``: inferred as ``w``).
            max_levels: stop after building the frontier of this many
                bound columns (``None``: all levels).

        Returns:
            The ``(rows', max_levels)`` frontier of surviving bindings.

        Raises:
            Whatever the plan's ``level_callback`` raises — the serving
            scheduler's budget callback raises
            :class:`repro.serve.scheduler.Preempted` carrying a
            :class:`repro.serve.scheduler.PlanSnapshot`; feeding that
            snapshot's ``(frontier, mult, start_level)`` back into this
            method continues the join exactly where it stopped.

        Example::

            ex = VLFTJ(query, gdb, plan=plan)
            penult = ex.advance(max_levels=len(ex.plan) - 1)
            counts = ex.last_level_counts(penult.astype(np.int32))
        """
        out = self._run(count_only=False, frontier=frontier, mult=mult,
                        start_level=start_level, max_levels=max_levels)
        return np.asarray(out, dtype=np.int64)

    def resume_count(self, frontier: np.ndarray, mult: np.ndarray,
                     start_level: int | None = None) -> int:
        """Finish a suspended *count* from a snapshot's ``(frontier,
        mult)`` state: the weighted count of all completions of the
        partial bindings.  ``resume_count(snap.frontier, snap.mult)``
        after an uninterrupted prefix equals the uninterrupted
        :meth:`count` — asserted in ``tests/test_scheduler.py``."""
        return int(self._run(
            count_only=True,
            frontier=np.asarray(frontier, dtype=np.int32),
            mult=np.asarray(mult, dtype=np.int64),
            start_level=start_level))

    def seeded_count(self, seed_values: np.ndarray,
                     seed_mult: np.ndarray) -> int:
        """Count with the first GAO variable pre-bound and weighted (the
        hybrid engine seeds the clique part with path-part counts)."""
        return int(self._run(
            count_only=True,
            frontier=np.asarray(seed_values, dtype=np.int32)[:, None],
            mult=np.asarray(seed_mult, dtype=np.int64)))


def vlftj_count(query: Query, gdb: GraphDB,
                gao: tuple[str, ...] | None = None, **kw) -> int:
    return VLFTJ(query, gdb, gao, **kw).count()
