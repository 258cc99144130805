"""The benchmark's data: SNAP-like graphs and the unary samples a request
names.

These are the benchmark's own copies of the program's generators
(``erdos_renyi``, ``barabasi_albert``, ``powerlaw_cluster``) and of its
sampling rule, so that no change to the program can change the data or
what a request means.  They return plain numpy CSR arrays.

A configuration fixes the generator, its parameters and its seed, as a
published data set fixes its edges.  ``--seed`` then relabels the vertex
ids by a random permutation: every seed serves an isomorphic graph with
the same degrees and the same work, stored in another order.
"""
from __future__ import annotations

import numpy as np


def csr_from_edges(src, dst, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric, loop-free, deduplicated CSR ``(indptr, indices)`` with
    each row's neighbours ascending (int64)."""
    src = np.asarray(src, dtype=np.int64).ravel()
    dst = np.asarray(dst, dtype=np.int64).ravel()
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    if src.size:
        keep = np.empty(src.shape[0], dtype=bool)
        keep[0] = True
        keep[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
        src, dst = src[keep], dst[keep]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, dst


def erdos_renyi(n: int, m: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """G(n, m): m undirected edges drawn uniformly, then deduplicated."""
    rng = np.random.default_rng(seed)
    k = int(m * 1.3) + 16
    src = rng.integers(0, n, size=k)
    dst = rng.integers(0, n, size=k)
    keep = src != dst
    return csr_from_edges(src[keep][:m], dst[keep][:m], n)


def _barabasi_albert_edges(n: int, m: int, rng) -> tuple[list, list]:
    targets = list(range(m))
    repeated: list[int] = []
    src_l: list[int] = []
    dst_l: list[int] = []
    for v in range(m, n):
        src_l.extend([v] * m)
        dst_l.extend(targets)
        repeated.extend(targets)
        repeated.extend([v] * m)
        idx = rng.integers(0, len(repeated), size=3 * m)
        uniq = list(dict.fromkeys(int(repeated[i]) for i in idx))[:m]
        while len(uniq) < m:
            c = int(rng.integers(0, v + 1))
            if c not in uniq:
                uniq.append(c)
        targets = uniq
    return src_l, dst_l


def powerlaw_cluster(n: int, m_per_node: int, tri_p: float,
                     seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Holme-Kim: preferential attachment, then ``tri_p * n`` random
    wedges closed into triangles."""
    indptr, indices = csr_from_edges(
        *_barabasi_albert_edges(n, m_per_node, np.random.default_rng(seed)),
        n)
    rng = np.random.default_rng(seed)
    deg = np.diff(indptr)
    cand = np.flatnonzero(deg >= 2)
    extra_src, extra_dst = [], []
    if cand.size:
        for u in rng.choice(cand, size=min(int(tri_p * n), cand.size),
                            replace=False):
            nb = indices[indptr[u]:indptr[u + 1]]
            i, j = rng.choice(nb.shape[0], size=2, replace=False)
            extra_src.append(int(nb[i]))
            extra_dst.append(int(nb[j]))
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    return csr_from_edges(np.concatenate([src, extra_src]),
                          np.concatenate([indices, extra_dst]), n)


GENERATORS = {"erdos_renyi": erdos_renyi,
              "powerlaw_cluster": powerlaw_cluster}


def build_graph(generator: dict, seed: int
                ) -> tuple[np.ndarray, np.ndarray]:
    """The configuration's graph with its vertex ids permuted by ``seed``.

    ``generator`` is the configuration's ``generator`` entry: ``kind``
    names one of :data:`GENERATORS`, the other keys are its arguments
    (``seed`` among them: the data set's own, fixed seed)."""
    spec = dict(generator)
    indptr, indices = GENERATORS[spec.pop("kind")](**spec)
    n = indptr.shape[0] - 1
    new_id = np.random.default_rng(seed).permutation(n)
    src = np.repeat(new_id, np.diff(indptr))
    return csr_from_edges(src, new_id[indices], n)


def node_sample(n_nodes: int, selectivity: float, seed: int) -> np.ndarray:
    """Sorted vertex ids, each kept with probability ``1/selectivity``;
    one id drawn at random where none is kept."""
    rng = np.random.default_rng(seed)
    ids = np.flatnonzero(rng.random(n_nodes) < 1.0 / selectivity)
    if ids.size == 0:
        ids = rng.integers(0, n_nodes, size=1)
    return ids.astype(np.int64)


def request_unary(n_nodes: int, selectivity: float, seed: int
                  ) -> dict[str, np.ndarray]:
    """The samples ``v1..v4`` of a request with this selectivity and
    predicate seed: ``v_i`` is :func:`node_sample` at seed ``7*seed + i``."""
    return {f"v{i}": node_sample(n_nodes, selectivity, seed * 7 + i)
            for i in range(1, 5)}
