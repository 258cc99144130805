"""Unified join-engine API: ``plan → execute``.

``count(query, gdb, engine=...)`` routes every request through the
cost-based planner (``core/planner.py``): the query + graph stats are
planned into a frozen :class:`~repro.core.plan.JoinPlan`, and
:func:`execute` dispatches the plan to its physical operator:

  * ``lftj_ref``        — faithful scalar LeapFrog TrieJoin (oracle)
  * ``minesweeper_ref`` — faithful Minesweeper w/ CDS (oracle)
  * ``binary``          — Selinger-style pairwise baseline
  * ``vlftj``           — vectorized worst-case-optimal join (TPU-native)
  * ``yannakakis``      — vectorized #MS / Yannakakis counting (β-acyclic)
  * ``hybrid``          — tree message passing + seeded core LFTJ
  * ``auto``            — cheapest estimated plan among the candidates
                          (subsumes the paper's Table 6/7 summary
                          heuristic: Minesweeper-analogue for acyclic,
                          hybrid for lollipop-shaped, LFTJ for cyclic).

Pass ``plan=`` to skip planning (e.g. a :class:`planner.PlanCache` hit),
or ``cache=`` to memoize plans across calls.

Beyond counting, :func:`enumerate` materializes the output tuples (flat
:class:`~repro.results.ResultSet` or trie-compressed
:class:`~repro.results.FactorizedResult`) and :func:`stream` returns a
bounded-memory page cursor — both resolve their plan through the same
planner path (``output='rows'``), so cached enumeration plans carry a
costed ``output_mode``.
"""
from __future__ import annotations

import numpy as np

from .binary_join import BinaryJoin
from .device_graph import GraphDB
from .hybrid import HybridJoin
from .hypergraph import Hypergraph, is_beta_acyclic
from .lftj_ref import LFTJ
from .minesweeper_ref import Minesweeper
from .plan import GraphStats, JoinPlan
from .planner import PlanCache, decompose_hybrid, plan_query
from .query import Query
from .vlftj import VLFTJ
from .yannakakis import CountingYannakakis

ENGINES = ("lftj_ref", "minesweeper_ref", "binary", "vlftj", "yannakakis",
           "hybrid", "auto")


def pick_engine(query: Query, stats: GraphStats | None = None) -> str:
    """Engine routing.  With ``stats`` the choice is cost-based (cheapest
    candidate plan); without, the paper's structural summary heuristic."""
    if stats is not None:
        return plan_query(query, stats, engine="auto").engine
    if is_beta_acyclic(Hypergraph.of(query)) and not query.filters:
        return "yannakakis"
    if decompose_hybrid(query) is not None:
        return "hybrid"
    return "vlftj"


def make_engine(plan: JoinPlan, gdb: GraphDB, **kw):
    """Construct a plan's physical operator instance (the single
    dispatch point shared by ``execute``/``execute_stats``/
    ``_engine_rows``).  Every instance carries a ``stats`` dict —
    harvest it through :func:`repro.obs.normalize_engine_stats`."""
    from ..obs.profile import span
    with span("engine.build", engine=plan.engine):
        engine = plan.engine
        query = plan.query
        if engine == "vlftj":
            return VLFTJ(query, gdb, plan=plan, **kw)
        if engine == "yannakakis":
            return CountingYannakakis(query, gdb, plan=plan)
        if engine == "hybrid":
            return HybridJoin(query, gdb, plan=plan, **kw)
        if engine == "lftj_ref":
            return LFTJ(query, gdb.to_database(), plan=plan)
        if engine == "minesweeper_ref":
            return Minesweeper(query, gdb.to_database(), plan=plan, **kw)
        if engine == "binary":
            return BinaryJoin(query, gdb.to_database(), plan=plan, **kw)
        raise ValueError(f"unknown engine {engine!r}; options: {ENGINES}")


def execute(plan: JoinPlan, gdb: GraphDB, **kw) -> int:
    """Run a compiled plan against a graph and return the count."""
    return make_engine(plan, gdb, **kw).count()


def execute_stats(plan: JoinPlan, gdb: GraphDB, **kw) -> tuple[int, dict]:
    """Run a plan and return ``(count, engine_stats)`` with the stats
    normalized onto the unified schema (``repro.obs.schema``).  When a
    :class:`repro.obs.QueryTrace` is active in the context, the per-level
    observations are harvested into it against the plan's
    ``level_est_rows`` annotation — all host-side dict reads, no new
    device work."""
    from ..obs import current_trace, normalize_engine_stats
    eng = make_engine(plan, gdb, **kw)
    out = eng.count()
    stats = normalize_engine_stats(plan.engine, getattr(eng, "stats", None))
    tr = current_trace()
    if tr is not None:
        tr.set_meta(query=plan.query.name, gao=list(plan.gao),
                    engine=plan.engine)
        tr.record_engine(stats["raw"], gao=plan.gao,
                         est_rows=plan.level_est_rows)
        tr.finish(count=out,
                  rows_expanded=stats["rows_expanded"],
                  kernel_dispatches=stats["kernel_dispatches"])
    return out, stats


def _resolve_plan(query: Query, gdb: GraphDB, engine: str,
                  plan: JoinPlan | None, cache: PlanCache | None,
                  gao: tuple[str, ...] | None,
                  output: str = "count", verify: bool = True) -> JoinPlan:
    """Shared plan resolution for ``count``/``enumerate``/``stream``.

    With ``verify`` (the default) the resolved plan — planner-produced
    or caller-supplied — passes static verification
    (:func:`repro.analysis.verify_for_execution`) before any device
    dispatch; error-severity findings raise
    :class:`repro.analysis.PlanVerificationError`.  Verification is
    memoized on ``(plan, stats fingerprint)``, so the steady-state cost
    on the serving path is a dict lookup.
    """
    if plan is None:
        if engine not in ENGINES:
            raise ValueError(
                f"unknown engine {engine!r}; options: {ENGINES}")
        stats = GraphStats.of(gdb)
        if gao is not None:
            # a pinned GAO bypasses the cache (keys don't carry the GAO)
            plan = plan_query(query, stats, engine=engine, gao=gao,
                              output=output)
        elif cache is not None:
            plan = cache.get_or_plan(query, stats, engine, output=output)
        else:
            plan = plan_query(query, stats, engine=engine, output=output)
    else:
        if (plan.query.atoms, plan.query.filters) != (query.atoms,
                                                      query.filters):
            raise ValueError(
                f"plan was built for {plan.query.name!r}, "
                f"not {query.name!r}")
        if engine != "auto" and plan.engine != engine:
            raise ValueError(f"plan uses engine {plan.engine!r} but "
                             f"engine={engine!r} was requested")
        if gao is not None and tuple(gao) != plan.gao:
            raise ValueError("both plan= and a conflicting gao= given")
    if verify:
        from ..analysis import verify_for_execution
        verify_for_execution(plan, gdb)
    return plan


def count(query: Query, gdb: GraphDB, engine: str = "auto",
          plan: JoinPlan | None = None, cache: PlanCache | None = None,
          gao: tuple[str, ...] | None = None, verify: bool = True,
          **kw) -> int:
    plan = _resolve_plan(query, gdb, engine, plan, cache, gao,
                         verify=verify)
    return execute(plan, gdb, **kw)


def _engine_rows(plan: JoinPlan, gdb: GraphDB, limit: int | None = None,
                 **kw) -> tuple[np.ndarray, tuple[str, ...]]:
    """Run a plan's engine enumeration: ``(rows, columns)``.

    Every engine's ``enumerate(limit=)`` follows one contract (int64,
    columns = its ``output_vars``, lex row order, limit truncates after
    ordering), so the limit pushes down uniformly."""
    eng = make_engine(plan, gdb, **kw)
    return eng.enumerate(limit=limit), eng.output_vars


def enumerate(query: Query, gdb: GraphDB, engine: str = "auto",
              limit: int | None = None,
              order: tuple[str, ...] | None = None,
              plan: JoinPlan | None = None, cache: PlanCache | None = None,
              gao: tuple[str, ...] | None = None,
              mode: str | None = None, verify: bool = True, **kw):
    """Enumerate output tuples through the same planner path as ``count``.

    Returns a :class:`repro.results.ResultSet` (flat, the default) or a
    :class:`repro.results.FactorizedResult` (``mode='factorized'``, or
    when the resolved plan's costed ``output_mode`` says so).  Columns
    follow ``order`` (default: ``query.variables`` — engine-independent,
    so any two engines agree row-for-row); rows are int64 and
    lexicographically sorted; ``limit`` truncates after the ordering.
    """
    from ..results import FactorizedResult, ResultSet
    plan = _resolve_plan(query, gdb, engine, plan, cache, gao,
                         output="rows", verify=verify)
    target = tuple(order) if order is not None else query.variables
    if set(target) != set(query.variables):
        raise ValueError(f"order {target} does not cover the query "
                         f"variables {query.variables}")
    mode = mode or (plan.output_mode if plan.output_mode != "count"
                    else "flat")
    if mode not in ("flat", "factorized"):
        raise ValueError(f"unknown mode {mode!r}; "
                         "options: ('flat', 'factorized')")
    if (mode == "factorized" and plan.engine == "vlftj"
            and target == plan.gao and limit is None):
        # native path: trie-compress the penultimate frontier and keep
        # the final level's extensions as leaf segments — the full flat
        # cross-product is never materialized
        from ..results.factorize import factorize_vlftj
        return factorize_vlftj(VLFTJ(query, gdb, plan=plan, **kw))
    push = limit if target == plan.gao else None
    rows, cols = _engine_rows(plan, gdb, limit=push, **kw)
    if cols != target:
        rows = rows[:, [cols.index(v) for v in target]]
        if rows.shape[0] > 1:
            rows = rows[np.lexsort(rows.T[::-1])]
    if limit is not None:
        rows = rows[:limit]
    if mode == "factorized":
        return FactorizedResult.from_rows(target, rows, sort=False)
    return ResultSet(target, rows)


def stream(query: Query, gdb: GraphDB, engine: str = "auto",
           page_rows: int = 1024, plan: JoinPlan | None = None,
           cache: PlanCache | None = None, verify: bool = True, **kw):
    """A :class:`repro.results.ResultCursor` over the query's output.

    Vectorized-LFTJ plans stream with bounded memory (the final level is
    re-entered per frontier chunk); other engines materialize once and
    page the rows.  Columns are the cursor's ``vars`` (the executing
    engine's output order)."""
    from ..results import ResultCursor
    plan = _resolve_plan(query, gdb, engine, plan, cache, None,
                         output="rows", verify=verify)
    if plan.engine == "vlftj":
        return ResultCursor(VLFTJ(query, gdb, plan=plan, **kw),
                            page_rows=page_rows)
    rows, cols = _engine_rows(plan, gdb, **kw)
    return ResultCursor.from_rows(cols, rows, page_rows=page_rows)
