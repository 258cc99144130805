"""The comparison that decides ``correct``.

Every answer the window produced is compared with the plain reference
(``reference``), exactly:

``wrong_counts``
    count answers that differ from the reference's count;
``wrong_pages``
    pages that are not the reference's rows ``[k*limit, (k+1)*limit)`` of
    the pattern in the page's own column order (rows ascending and
    distinct), or that end a cursor before its rows are all delivered (a
    continuation past the last row, which returns an empty page, is
    allowed);
``lost``
    requests that raised, or never answered within the drain.

Each limit is 0.  A refusal by the server's stated limits (a 429, an
evicted cursor) is not an answer and is not compared; an analyst's
count that the close cut off was not due.
"""
from __future__ import annotations

import numpy as np

import graphs
from reference import PATTERNS, Reference

LIMITS = {"wrong_counts": 0, "wrong_pages": 0, "lost": 0}


def compare(responses: list[dict], ref: Reference) -> dict:
    """The compared numbers over ``responses`` (``loadgen``)."""
    unary: dict = {}
    counts: dict = {}
    rows: dict = {}

    def samples(r):
        key = (r["selectivity"], r["pred_seed"])
        if key not in unary:
            unary[key] = graphs.request_unary(ref.n, *key)
        return unary[key]

    out = dict.fromkeys(LIMITS, 0)
    out["checked"] = 0
    for r in responses:
        if r["status"] in ("error", "unfinished"):
            out["lost"] += 1
            continue
        if r["status"] != "ok":
            continue
        out["checked"] += 1
        key = (r["shape"], r["selectivity"], r["pred_seed"])
        if r["kind"] == "count":
            if key not in counts:
                counts[key] = ref.count(r["shape"], samples(r))
            out["wrong_counts"] += int(r["count"] != counts[key])
            continue
        cols = r["row_vars"]
        if sorted(cols) != sorted(PATTERNS[r["shape"]].variables):
            out["wrong_pages"] += 1
            continue
        if key + (cols,) not in rows:
            rows[key + (cols,)] = ref.rows(r["shape"], samples(r), cols)
        want = rows[key + (cols,)]
        lo = r["page"] * r["limit"]
        page = want[lo:lo + r["limit"]]
        got = np.asarray(r["rows"]).reshape(-1, len(cols))
        more = lo + r["limit"] < want.shape[0]
        ok = (got.shape == page.shape and np.array_equal(got, page)
              and (r["next_cursor"] is not None or not more))
        out["wrong_pages"] += int(not ok)
    return out


def correct(numbers: dict) -> bool:
    """Every compared number within its limit, and something compared."""
    return numbers["checked"] > 0 and all(
        numbers[k] <= lim for k, lim in LIMITS.items())
