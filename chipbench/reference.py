"""The plain reference: pattern counts and rows by numpy/scipy over the CSR.

It shares no code with the program under test.  Each pattern is written
out below as its atoms, so that the reference states for itself what a
request means; counts come from sparse matrix algebra and explicit
clique listing, rows from a join that binds one variable at a time.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
from scipy import sparse


@dataclass(frozen=True)
class Pattern:
    """A graph pattern: ``edges`` between variables, a unary sample per
    variable in ``unary`` (``{var: "v1"}``), and ``less`` filters
    ``(x, y)`` meaning ``x < y``."""

    variables: tuple[str, ...]
    edges: tuple[tuple[str, str], ...]
    unary: tuple[tuple[str, str], ...] = ()
    less: tuple[tuple[str, str], ...] = ()


def _chain(names: str) -> tuple[tuple[str, str], ...]:
    return tuple(zip(names, names[1:]))


#: the paper's §5.1 patterns, as the program names them
PATTERNS: dict[str, Pattern] = {
    "3-clique": Pattern(tuple("abc"), (("a", "b"), ("a", "c"), ("b", "c")),
                        less=_chain("abc")),
    "4-clique": Pattern(tuple("abcd"),
                        (("a", "b"), ("a", "c"), ("a", "d"), ("b", "c"),
                         ("b", "d"), ("c", "d")),
                        less=_chain("abcd")),
    "4-cycle": Pattern(tuple("abcd"),
                       (("a", "b"), ("b", "c"), ("c", "d"), ("d", "a")),
                       less=_chain("abcd")),
    "3-path": Pattern(tuple("abcd"), _chain("abcd"),
                      unary=(("a", "v1"), ("d", "v2"))),
    "4-path": Pattern(tuple("abcde"), _chain("abcde"),
                      unary=(("a", "v1"), ("e", "v2"))),
    "1-tree": Pattern(tuple("abc"), (("a", "b"), ("a", "c")),
                      unary=(("b", "v1"), ("c", "v2"))),
    "2-comb": Pattern(tuple("abcd"), (("a", "b"), ("a", "c"), ("b", "d")),
                      unary=(("c", "v1"), ("d", "v2"))),
    "2-lollipop": Pattern(tuple("abcde"),
                          (("a", "b"), ("b", "c"), ("c", "d"), ("c", "e"),
                           ("d", "e")),
                          unary=(("a", "v1"),), less=(("d", "e"),)),
    "3-lollipop": Pattern(tuple("abcdefg"),
                          (("a", "b"), ("b", "c"), ("c", "d"), ("d", "e"),
                           ("d", "f"), ("d", "g"), ("e", "f"), ("e", "g"),
                           ("f", "g")),
                          unary=(("a", "v1"),), less=_chain("efg")),
}


def _sorted_member(keys: np.ndarray, probe: np.ndarray) -> np.ndarray:
    if keys.shape[0] == 0:
        return np.zeros(probe.shape, dtype=bool)
    pos = np.minimum(np.searchsorted(keys, probe), len(keys) - 1)
    return keys[pos] == probe


class Reference:
    """Counts and rows of :data:`PATTERNS` over one graph."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.n = n = int(indptr.shape[0] - 1)
        self.indptr = np.asarray(indptr, dtype=np.int64)
        self.indices = np.asarray(indices, dtype=np.int64)
        self.adj = sparse.csr_matrix(
            (np.ones(len(indices), np.int64), self.indices, self.indptr),
            shape=(n, n))
        src = np.repeat(np.arange(n, dtype=np.int64), np.diff(self.indptr))
        #: every directed edge as ``src * n + dst``, ascending
        self.keys = src * n + self.indices
        self._cliques: dict[int, np.ndarray] = {}

    # -- cliques ---------------------------------------------------------
    def cliques(self, k: int, budget: int = 1 << 23) -> np.ndarray:
        """All k-cliques as ascending vertex tuples (k >= 2)."""
        if k in self._cliques:
            return self._cliques[k]
        up = sparse.triu(self.adj, k=1, format="csr")
        if k == 2:
            rows = np.repeat(np.arange(self.n, dtype=np.int64),
                             np.diff(up.indptr))
            out = np.column_stack([rows, up.indices.astype(np.int64)])
        else:
            smaller = self.cliques(k - 1, budget)
            lens = np.diff(up.indptr)[smaller[:, -1]]
            ends = np.cumsum(lens)
            parts = [np.zeros((0, k), np.int64)]
            s = 0
            while s < len(smaller):
                base = ends[s - 1] if s else 0
                e = max(s + 1,
                        int(np.searchsorted(ends, base + budget, "right")))
                cl, ln = smaller[s:e], lens[s:e]
                rep = np.repeat(np.arange(len(cl)), ln)
                off = np.arange(ln.sum()) - np.repeat(np.cumsum(ln) - ln, ln)
                d = up.indices[up.indptr[cl[rep, -1]] + off].astype(np.int64)
                ok = np.ones(len(d), bool)
                for col in range(k - 2):
                    ok &= _sorted_member(self.keys, cl[rep, col] * self.n + d)
                parts.append(np.column_stack([cl[rep[ok]], d[ok]]))
                s = e
            out = np.concatenate(parts)
        self._cliques[k] = out
        return out

    def _at(self, k: int) -> np.ndarray:
        """Per vertex, the k-cliques that contain it."""
        return np.bincount(self.cliques(k).ravel(), minlength=self.n)

    # -- counts ----------------------------------------------------------
    def _indicator(self, ids: np.ndarray) -> np.ndarray:
        v = np.zeros(self.n, np.int64)
        v[ids] = 1
        return v

    def count(self, shape: str, unary: dict[str, np.ndarray]) -> int:
        """The count of ``shape`` with the samples ``unary`` (v1, v2, ...),
        by matrix algebra."""
        A = self.adj
        if shape in ("3-clique", "4-clique"):
            return int(len(self.cliques(int(shape[0]))))
        if shape == "4-cycle":
            # a<b<c<d: for each pair a<c, (middles b with a<b<c) times
            # (vertices d > c adjacent to both)
            up = sparse.triu(A, k=1, format="csr")
            return int((up @ up).multiply(up @ up.T).sum())
        v1 = self._indicator(unary["v1"])
        walk = [v1]
        for _ in range(4):
            walk.append(A @ walk[-1])
        if shape in ("3-path", "4-path"):
            k = int(shape[0])
            return int(walk[k] @ self._indicator(unary["v2"]))
        if shape == "1-tree":
            return int(walk[1] @ (A @ self._indicator(unary["v2"])))
        if shape == "2-comb":
            return int(walk[1] @ (A @ (A @ self._indicator(unary["v2"]))))
        if shape == "2-lollipop":
            return int(walk[2] @ self._at(3))
        if shape == "3-lollipop":
            return int(walk[3] @ self._at(4))
        raise KeyError(shape)

    # -- rows ------------------------------------------------------------
    def rows(self, shape: str, unary: dict[str, np.ndarray],
             columns: tuple[str, ...]) -> np.ndarray:
        """Every result row of ``shape``, columns in ``columns`` order,
        ascending and distinct: a join that binds one variable at a time,
        each next variable a neighbour of a bound one."""
        p = PATTERNS[shape]
        if sorted(columns) != sorted(p.variables):
            raise ValueError(f"{columns} are not the variables of {shape}")
        pred = dict(p.unary)
        adjacent = {v: set() for v in p.variables}
        for x, y in p.edges:
            adjacent[x].add(y)
            adjacent[y].add(x)
        order = [p.variables[0]]
        while len(order) < len(p.variables):
            order.append(next(v for v in p.variables if v not in order
                              and adjacent[v] & set(order)))
        first = order[0]
        start = (np.asarray(unary[pred[first]], np.int64) if first in pred
                 else np.arange(self.n, dtype=np.int64))
        bound = {first: start}
        deg = np.diff(self.indptr)
        for v in order[1:]:
            via = next(u for u in order if u in bound and u in adjacent[v])
            src = bound[via]
            ln = deg[src]
            rep = np.repeat(np.arange(src.shape[0]), ln)
            off = np.arange(ln.sum()) - np.repeat(np.cumsum(ln) - ln, ln)
            val = self.indices[self.indptr[src][rep] + off]
            bound = {u: col[rep] for u, col in bound.items()}
            ok = np.ones(val.shape[0], bool)
            for u in adjacent[v] & set(bound):
                if u != via:
                    ok &= _sorted_member(self.keys, bound[u] * self.n + val)
            if v in pred:
                ok &= np.isin(val, unary[pred[v]])
            for x, y in p.less:
                if x == v and y in bound:
                    ok &= val < bound[y]
                elif y == v and x in bound:
                    ok &= bound[x] < val
            bound = {u: col[ok] for u, col in bound.items()}
            bound[v] = val[ok]
        out = np.column_stack([bound[v] for v in columns])
        return out[np.lexsort(out.T[::-1])]
