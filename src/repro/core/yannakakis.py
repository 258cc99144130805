"""Vectorized counting Yannakakis — the TPU-native Minesweeper analogue.

The paper (§4.11): "#Minesweeper is to message passing what Minesweeper was
to Yannakakis".  For β-acyclic graph-pattern queries the work Minesweeper's
CDS caches away is exactly the work semijoin reduction + count message
passing never performs: every sub-pattern count is computed once per node,
not once per occurrence.  That is why Minesweeper dominates the acyclic,
low-selectivity benchmarks (Table 7, Figures 3-5) — and this engine
reproduces that behaviour with two fully-vectorized passes:

  1. bottom-up over the query's variable tree: per node-id count vectors
     ``c_leaf = [x ∈ v_i]``; ``c_parent = unary ⊙ ∏_children (A @ c_child)``
     where ``A @ c`` is a CSR gather, a prefix sum and its differences at
     ``indptr`` (one SpMV per query edge — O(#edges) total work, the
     instance-optimal flavour);
  2. the root vector's sum is the count (#Minesweeper's Idea-8 tallies).

For enumeration, the same messages act as semijoin filters: a node value
stays active iff every child message is nonzero, and the reduced frontier
is handed to the vectorized LFTJ for top-down materialization — classic
Yannakakis, zero dangling intermediates.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .device_graph import GraphDB
from .hypergraph import Hypergraph, is_beta_acyclic
from .plan import JoinPlan
from .query import Query


class NotTreeShaped(ValueError):
    pass


def variable_tree(query: Query) -> dict[str, list[str]]:
    """Adjacency of the query's variable graph; raises if not a forest."""
    adj: dict[str, list[str]] = {v: [] for v in query.variables}
    seen_edges = set()
    n_edges = 0
    for a in query.atoms:
        if a.arity == 1:
            continue
        if a.arity != 2:
            raise NotTreeShaped("binary atoms only")
        u, v = a.vars
        if u == v:
            raise NotTreeShaped("self loop")
        key = frozenset((u, v))
        if key in seen_edges:
            continue  # parallel atoms collapse (same constraint)
        seen_edges.add(key)
        adj[u].append(v)
        adj[v].append(u)
        n_edges += 1
    # forest check: every connected component must satisfy |E| = |V| - 1
    if query.filters:
        raise NotTreeShaped("filters break tree message passing")
    visited: set[str] = set()
    for root in adj:
        if root in visited:
            continue
        stack, comp_v = [root], 0
        comp_nodes = set()
        while stack:
            x = stack.pop()
            if x in comp_nodes:
                continue
            comp_nodes.add(x)
            stack.extend(adj[x])
        comp_e = sum(len(adj[x]) for x in comp_nodes) // 2
        if comp_e != len(comp_nodes) - 1:
            raise NotTreeShaped("variable graph is cyclic")
        visited |= comp_nodes
    return adj


#: block length of :func:`_prefix_sum`.  On a TPU v5e, XLA compiles an
#: int64 ``cumsum`` over 1.78M elements in about 2 s in such blocks and in
#: about 35 s as one window, and both run in the same time.
_SCAN_BLOCK = 1024


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a 1-D array, scanned within blocks of
    ``_SCAN_BLOCK`` and then across the blocks' totals (recursively)."""
    m = x.shape[0]
    if m <= _SCAN_BLOCK:
        return jnp.cumsum(x)
    rows = -(-m // _SCAN_BLOCK)
    blocks = jnp.pad(x, (0, rows * _SCAN_BLOCK - m)).reshape(rows,
                                                              _SCAN_BLOCK)
    within = jnp.cumsum(blocks, axis=1)
    before = _prefix_sum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(-1)[:m]


def _take64(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for a 64-bit ``table``, gathered as rows of two 32-bit
    words.  A TPU emulates 64-bit integers and lowers plain indexing to two
    gathers of 32-bit words; on a v5e the one row gather takes a sixth of
    their time."""
    words = jax.lax.bitcast_convert_type(table, jnp.uint32)
    return jax.lax.bitcast_convert_type(words[idx], table.dtype)


@partial(jax.jit, static_argnames=("num_segments",))
def _spmv(indptr, indices, src_ids, c, *, num_segments):
    """y[x] = Σ_{(x,z) ∈ E} c[z] over a CSR sorted by source, for
    ``num_segments`` = ``len(indptr) - 1`` rows.

    Each row's edges are the run ``indptr[x]:indptr[x+1]``, so a row sum is
    the difference of the messages' exclusive prefix sum at its two ends:
    a gather, a prefix sum and a gather, with no scatter (on a TPU v5e,
    XLA's scatter-add spends about 90 ns on each edge).  int64 arithmetic
    wraps, so every difference is exact wherever the row sum itself fits
    in int64; an empty row gives 0.  ``src_ids`` is unused: the row runs
    carry it."""
    msg = _take64(c, indices)
    cs = _prefix_sum(jnp.pad(msg, (1, 0)))   # cs[k] = Σ msg[:k]
    at = _take64(cs, indptr)
    return at[1:] - at[:-1]


class CountingYannakakis:
    """Count β-acyclic graph patterns in O(#query-edges) SpMV passes."""

    def __init__(self, query: Query, gdb: GraphDB,
                 root: str | None = None,
                 plan: JoinPlan | None = None):
        hg = Hypergraph.of(query)
        if not is_beta_acyclic(hg):
            raise NotTreeShaped("query is β-cyclic; use vlftj or hybrid")
        self.query = query
        self.gdb = gdb
        self.join_plan = plan
        self.adj = variable_tree(query)
        self.unary_of: dict[str, list[str]] = {v: [] for v in query.variables}
        for a in query.atoms:
            if a.arity == 1:
                self.unary_of[a.vars[0]].append(a.rel)
        if root is None and plan is not None and plan.root is not None:
            root = plan.root
        self.root = root or query.variables[0]
        # enumeration column order: the plan's GAO covers every variable
        # (yannakakis plans carry choose_gao(query)); plan-free
        # construction derives the same order directly
        if plan is not None and set(plan.gao) == set(query.variables):
            self.gao = plan.gao
        else:
            from .gao import choose_gao
            self.gao = choose_gao(query)
        # spmvs is the native counter; rows_expanded / level_rows source
        # the unified engine schema (obs/schema.ENGINE_STATS_SOURCE_KEYS):
        # every SpMV propagates one message over the n_nodes id domain,
        # and the root tally vector is the engine's one "frontier"
        self.stats = {"spmvs": 0, "rows_expanded": 0, "level_rows": {}}

    def _unary_mask(self, var: str) -> jnp.ndarray:
        n = self.gdb.n_nodes
        vec = jnp.ones(n, dtype=jnp.int64)
        for u in self.unary_of[var]:
            vec = vec * self.gdb.dev(f"bitmap:{u}").astype(jnp.int64)
        return vec

    def message_to_root(self, root: str | None = None) -> jnp.ndarray:
        """Per-node-id count vector at the root variable (Idea 8 tallies)."""
        root = root or self.root
        indptr = self.gdb.dev("indptr")
        indices = self.gdb.dev("indices")
        src_ids = self.gdb.dev("src_ids")
        n = self.gdb.n_nodes

        def up(var: str, parent: str | None) -> jnp.ndarray:
            c = self._unary_mask(var)
            for ch in self.adj[var]:
                if ch == parent:
                    continue
                c_ch = up(ch, var)
                self.stats["spmvs"] += 1
                self.stats["rows_expanded"] += n
                c = c * _spmv(indptr, indices, src_ids, c_ch,
                              num_segments=n)
            return c

        # product over the root's own component; other components multiply
        # as scalar factors (cross products)
        comp_roots = self._component_roots(root)
        self.stats["level_rows"][0] = n
        c_root = up(root, None)
        self._cross_factor = 1
        for r in comp_roots:
            if r != root:
                self._cross_factor *= int(up(r, None).sum())
        return c_root

    def _component_roots(self, root: str) -> list[str]:
        roots, visited = [], set()
        order = [root] + [v for v in self.query.variables if v != root]
        for v in order:
            if v in visited:
                continue
            roots.append(v)
            stack = [v]
            while stack:
                x = stack.pop()
                if x in visited:
                    continue
                visited.add(x)
                stack.extend(self.adj[x])
        return roots

    def count(self) -> int:
        # lazy: repro.obs pulls in repro.core at package level
        from ..obs.profile import span
        # int() waits for the root's sum, so every SpMV this count
        # dispatched runs inside the span
        with span("yannakakis.count", query=self.query.name):
            c_root = self.message_to_root()
            return int(c_root.sum()) * self._cross_factor

    def semijoin_reduce(self) -> dict[str, np.ndarray]:
        """Active-value masks per variable after full semijoin reduction
        (upward + downward passes) — the enumeration prefilter."""
        from ..obs.profile import span
        with span("yannakakis.semijoin", query=self.query.name):
            indptr = self.gdb.dev("indptr")
            indices = self.gdb.dev("indices")
            src_ids = self.gdb.dev("src_ids")
            n = self.gdb.n_nodes
            up_msg: dict[tuple[str, str], jnp.ndarray] = {}

            def up(var: str, parent: str | None) -> jnp.ndarray:
                c = self._unary_mask(var) > 0
                for ch in self.adj[var]:
                    if ch == parent:
                        continue
                    m = up(ch, var)
                    self.stats["spmvs"] += 1
                    self.stats["rows_expanded"] += n
                    c = c & (_spmv(indptr, indices, src_ids,
                                   m.astype(jnp.int64), num_segments=n) > 0)
                if parent is not None:
                    up_msg[(var, parent)] = c
                return c

            active: dict[str, jnp.ndarray] = {}

            def down(var: str, parent: str | None, mask_from_parent):
                c = self._unary_mask(var) > 0
                if mask_from_parent is not None:
                    c = c & mask_from_parent
                for ch in self.adj[var]:
                    if ch == parent:
                        continue
                    c = c & (_spmv(indptr, indices, src_ids,
                                   up_msg[(ch, var)].astype(jnp.int64),
                                   num_segments=n) > 0)
                active[var] = c
                for ch in self.adj[var]:
                    if ch == parent:
                        continue
                    m = _spmv(indptr, indices, src_ids, c.astype(jnp.int64),
                              num_segments=n) > 0
                    down(ch, var, m)

            for r in self._component_roots(self.root):
                up(r, None)
                down(r, None, None)
            return {v: np.asarray(m) for v, m in active.items()}

    def enumerate(self, limit: int | None = None) -> np.ndarray:
        """Backward-expansion enumeration: int64 tuples, columns in GAO
        order (``self.output_vars``), rows lex-sorted; ``limit``
        truncates after the ordering.  See
        ``repro.results.backward.yannakakis_rows``."""
        from ..results.backward import yannakakis_rows
        rows, _ = yannakakis_rows(self)
        return rows if limit is None else rows[:limit]

    @property
    def output_vars(self) -> tuple[str, ...]:
        """Column order of :meth:`enumerate`."""
        return self.gao


def yannakakis_count(query: Query, gdb: GraphDB) -> int:
    return CountingYannakakis(query, gdb).count()
