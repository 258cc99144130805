"""The control: the reference in the program's place, with one guarantee
broken, judged by the benchmark's own comparison.  Not part of a run.

    python3 chipbench/control.py --workload <cell> --seed <n> --seconds <s>

The configurations promise exact counts.  The control answers every
count as an estimate would (edge sampling, as in DOULION: keep each edge
with probability ``P``, count on what is kept, scale by ``P`` to the
minus the pattern's edge count), which is the step that would tempt a
later change to whole-graph counting.  Pages it answers with the exact
reference rows.  The answers cover the requests of the cell's own traffic
at the cell's own size; ``checks.compare`` must find them not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import checks  # noqa: E402
import graphs  # noqa: E402
import traffic  # noqa: E402
from reference import PATTERNS, Reference  # noqa: E402

P = 0.9


def sparsified(indptr: np.ndarray, indices: np.ndarray, p: float,
               seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The graph with each undirected edge kept with probability ``p``."""
    n = indptr.shape[0] - 1
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    up = src < indices
    keep = np.random.default_rng([seed, 3]).random(int(up.sum())) < p
    return graphs.csr_from_edges(src[up][keep], indices[up][keep], n)


class Estimate:
    """Counts estimated on an edge sample; rows exact."""

    def __init__(self, indptr, indices, seed: int, p: float = P):
        self.exact = Reference(indptr, indices)
        self.sample = Reference(*sparsified(indptr, indices, p, seed))
        self.p = p

    def count(self, shape: str, unary: dict) -> int:
        k = len(PATTERNS[shape].edges)
        return int(round(self.sample.count(shape, unary) / self.p ** k))


def answers(cell: dict, seed: int, seconds: float, est: Estimate
            ) -> list[dict]:
    """The control's responses to the cell's traffic: for a closed loop,
    its rotation twice (a run repeats the same requests); for an open
    loop, every request of ``seconds`` of its schedule, continuations
    included."""
    mix = cell["mix"]
    if mix["loop"] == "closed":
        reqs = traffic.closed_rotation(mix, seed) * 2
    else:
        reqs = traffic.open_schedule(mix, seed, seconds)
    n = est.exact.n
    out, cursors, rows = [], {}, {}
    for req in reqs:
        r = dict(req, status="ok")
        cur = cursors.get(r["tenant"])
        if r["kind"] == "continuation" and cur is not None:
            r.update(cur, kind="continuation")
        elif r["kind"] != "count":
            r.update(kind="page", page=0)
        unary = graphs.request_unary(n, r["selectivity"], r["pred_seed"])
        if r["kind"] == "count":
            r["count"] = est.count(r["shape"], unary)
            out.append(r)
            continue
        cols = PATTERNS[r["shape"]].variables
        key = (r["shape"], r["selectivity"], r["pred_seed"])
        if key not in rows:
            rows[key] = est.exact.rows(r["shape"], unary, cols)
        lo = r["page"] * r["limit"]
        more = lo + r["limit"] < rows[key].shape[0]
        r.update(rows=rows[key][lo:lo + r["limit"]], row_vars=cols,
                 next_cursor="more" if more else None)
        if more:
            cursors[r["tenant"]] = {k: r[k] for k in
                                    ("shape", "selectivity", "pred_seed",
                                     "limit")} | {"page": r["page"] + 1}
        else:
            cursors.pop(r["tenant"], None)
        out.append(r)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run
    cell = run.load_cell(args.workload)
    indptr, indices = graphs.build_graph(cell["config_spec"]["generator"],
                                         args.seed)
    est = Estimate(indptr, indices, args.seed)
    numbers = checks.compare(answers(cell, args.seed, args.seconds, est),
                             est.exact)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": checks.correct(numbers), **numbers}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
