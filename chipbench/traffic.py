"""Requests from a traffic mix and a seed.

A mix is a JSON file under ``traffic/``; this one generator reads every
mix.  A request is a plain dict:

``kind``
    ``count`` (a pattern count), ``page`` (a first page of rows) or
    ``continuation`` (the next page of the tenant's open cursor).
``class``
    ``count`` for whole-graph analytics, ``interactive`` for tenants'
    requests: the end-to-end metrics read them apart.
``shape``, ``selectivity``, ``pred_seed``
    the pattern and its unary samples (``graphs.request_unary``).
``tenant``, ``limit``, ``t_due``
    the quota bucket, the page size, and (open loop) when it is due, in
    seconds from the start of the window.

Every seed sends the same requests: a closed loop the same shapes in
another order, an open loop the same kinds at the same times.  The seed
changes which vertices a request touches (its samples, the tenant that
sends it), not how much work a run holds or when it arrives.
"""
from __future__ import annotations

import itertools

import numpy as np

SEED_SPACE = 1 << 31


def closed_rotation(mix: dict, seed: int) -> list[dict]:
    """A closed loop's rotation: every shape of the mix at each of
    ``predicate_seeds`` predicate seeds drawn from ``seed``, in an order
    drawn from ``seed``.  The client sends it round and round."""
    rng = np.random.default_rng(seed)
    pred_seeds = [int(s) for s in
                  rng.integers(0, SEED_SPACE, mix["predicate_seeds"])]
    reqs = [{"kind": "count", "class": mix["class"], "shape": shape,
             "selectivity": mix["selectivity"], "pred_seed": ps,
             "tenant": mix.get("tenant", "default")}
            for shape in mix["shapes"] for ps in pred_seeds]
    return [reqs[i] for i in rng.permutation(len(reqs))]


def cycle(rotation: list[dict]):
    """The closed loop's endless request stream."""
    return (dict(r) for r in itertools.cycle(rotation))


def _exact_shares(rng, n: int, shares: list[float]) -> np.ndarray:
    """``n`` labels with ``round(n * share)`` of each (the largest
    remainders fill up), shuffled."""
    raw = np.asarray(shares, float) * n / sum(shares)
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return rng.permutation(np.repeat(np.arange(len(shares)), counts))


def tenants(mix: dict, seed: int) -> list[dict]:
    """The mix's tenants: name, selectivity, predicate seed, and the
    Zipf popularity weight of its rank."""
    t = mix["tenants"]
    rng = np.random.default_rng([seed, 1])
    sels = _exact_shares(rng, t["count"], [1.0] * len(t["selectivities"]))
    weights = np.arange(1, t["count"] + 1, dtype=float) ** -t["zipf"]
    pred = rng.integers(0, SEED_SPACE, t["count"])
    return [{"tenant": f"t{i:02d}",
             "selectivity": t["selectivities"][sels[i]],
             "pred_seed": int(pred[i]), "weight": weights[i]}
            for i in range(t["count"])]


def open_schedule(mix: dict, seed: int, seconds: float) -> list[dict]:
    """An open loop's arrivals over ``[0, seconds)``: ``rate_per_s *
    seconds`` requests at uniformly drawn times (a Poisson process given
    its count), kinds in the mix's exact shares, and ``fresh_share`` of
    the requests with a predicate of their own in place of their
    tenant's (continuations keep the tenant's cursor).

    The times, the kinds and which requests are fresh come from the mix's
    own ``arrival_seed``: every run replays the same arrivals, so the
    queue a request meets is the same from run to run.  ``seed`` draws
    the tenants' samples, which tenant (by Zipf popularity) sends each
    request, and the fresh predicates."""
    fixed = np.random.default_rng([mix["arrival_seed"], 2])
    n = int(round(mix["rate_per_s"] * seconds))
    due = np.sort(fixed.uniform(0.0, seconds, n))
    kinds = mix["requests"]
    kind_of = _exact_shares(fixed, n, [k["share"] for k in kinds])
    fresh = np.zeros(n, bool)
    own = np.flatnonzero([kinds[k]["kind"] != "continuation"
                          for k in kind_of])
    fresh[own] = _exact_shares(fixed, own.size, [1 - mix["fresh_share"],
                                                 mix["fresh_share"]])
    rng = np.random.default_rng([seed, 2])
    ten = tenants(mix, seed)
    out = []
    for i in range(n):
        spec = kinds[kind_of[i]]
        pool = [t for t in ten if "selectivity" not in spec
                or t["selectivity"] == spec["selectivity"]]
        w = np.array([t["weight"] for t in pool])
        t = pool[rng.choice(len(pool), p=w / w.sum())]
        req = {"kind": spec["kind"], "class": "interactive",
               "shape": spec["shape"], "selectivity": t["selectivity"],
               "pred_seed": t["pred_seed"], "tenant": t["tenant"],
               "t_due": float(due[i])}
        if "limit" in spec:
            req["limit"] = spec["limit"]
        if fresh[i]:
            req["pred_seed"] = int(rng.integers(0, SEED_SPACE))
        out.append(req)
    return out
