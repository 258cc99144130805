"""The load generators: a closed loop and an open loop over the server.

Both turn traffic requests (``traffic``) into the program's
``QueryRequest`` and record one response dict per request: the request's
own keys, ``t_start`` and ``t_done`` (host seconds from the window's
start, the result on the host), ``status`` and what the server answered
(``count``; ``rows``, ``row_vars``, ``next_cursor`` and ``page`` for
pages).  ``status`` is ``ok``, ``rejected`` (a 429, or a cursor the
server's registry evicted), ``error`` (the call raised) or ``unfinished``
(no answer by the end of the drain) or ``cut`` (an analyst's count
still running at the close, which was not due).
"""
from __future__ import annotations

import time
import traceback


def query_request(serve, req: dict, cursor: str | None = None):
    """The program's request for a traffic request."""
    limit = req.get("limit")
    return serve.QueryRequest(req["shape"], selectivity=req["selectivity"],
                              seed=req["pred_seed"], tenant=req["tenant"],
                              limit=limit, cursor=cursor)


def answer(resp: dict, result) -> None:
    """Copy a ``QueryResult`` into a response dict."""
    status = result.stats.get("status") if result.stats else None
    if result.engine == "rejected" or status == 429:
        resp["status"] = "rejected"
        return
    resp.update(status="ok", count=int(result.count))
    if resp["kind"] != "count":
        resp.update(rows=result.rows, row_vars=tuple(result.row_vars or ()),
                    next_cursor=result.next_cursor)


def _raised(resp: dict, err: Exception) -> None:
    resp.update(status="error",
                error="".join(traceback.format_exception_only(err)).strip())


def closed_loop(server, serve, stream, seconds: float, spans) -> list[dict]:
    """One client: the next request of ``stream`` goes out when the last
    one has returned, until ``seconds`` have passed.  The request in
    flight at the close is waited for and recorded after it."""
    out = []
    while spans.now() < seconds:
        resp = dict(next(stream), t_start=spans.now())
        with spans.span("execute"):
            try:
                answer(resp, server.execute(query_request(serve, resp)))
            except Exception as err:  # recorded; judged by the checks
                _raised(resp, err)
        resp["t_done"] = spans.now()
        out.append(resp)
    return out


def open_loop(server, serve, sched, schedule: list[dict], seconds: float,
              spans, drain_s: float, analyst: dict | None = None
              ) -> list[dict]:
    """Requests arrive at their ``t_due`` whether or not earlier ones have
    returned.  Counts and first pages are submitted to the scheduler
    ``sched``, which runs one quantum per ``step``; continuations of a
    tenant's open cursor go to ``server.execute`` between steps (with no
    cursor open, a continuation asks for a first page).  ``analyst``, a
    count request, is resubmitted each time the last one completes, until
    the close.  After the close the loop steps on until every admitted
    request has an answer or ``drain_s`` more seconds have passed."""
    pending = list(reversed(schedule))
    inflight: dict[str, tuple[dict, object]] = {}
    cursors: dict[str, dict] = {}
    out: list[dict] = []

    def submit(resp: dict) -> bool:
        """True when the scheduler admitted the request."""
        with spans.span("submit" if resp["class"] == "interactive"
                        else "submit.count"):
            try:
                token = sched.submit(query_request(serve, resp))
            except serve.AdmissionError:
                resp["status"] = "rejected"
            except Exception as err:
                _raised(resp, err)
        if "status" in resp:
            resp["t_done"] = spans.now()
            out.append(resp)
            return False
        # the scheduler keeps its jobs in submission order and offers no
        # other way to read one job's result between steps
        inflight[token] = (resp, sched._jobs[-1])
        return True

    def continuation(resp: dict, cur: dict) -> None:
        resp.update(shape=cur["shape"], selectivity=cur["selectivity"],
                    pred_seed=cur["pred_seed"], limit=cur["limit"],
                    page=cur["page"])
        with spans.span("continuation"):
            try:
                answer(resp, server.execute(
                    query_request(serve, resp, cursor=cur["token"])))
            except ValueError as err:          # evicted or unknown cursor
                resp.update(status="rejected", error=str(err))
            except Exception as err:
                _raised(resp, err)
        resp["t_done"] = spans.now()
        done(resp)

    def done(resp: dict) -> None:
        out.append(resp)
        if resp["kind"] == "count" or resp["status"] != "ok":
            if resp["kind"] != "count":
                cursors.pop(resp["tenant"], None)
            return
        if resp["next_cursor"] is None:
            cursors.pop(resp["tenant"], None)
        else:
            cursors[resp["tenant"]] = {
                "token": resp["next_cursor"], "page": resp["page"] + 1,
                **{k: resp[k] for k in ("shape", "selectivity",
                                        "pred_seed", "limit")}}

    analyst_busy = False
    while True:
        now = spans.now()
        while pending and pending[-1]["t_due"] <= now:
            resp = dict(pending.pop(), t_start=spans.now())
            cur = cursors.get(resp["tenant"])
            if resp["kind"] == "continuation" and cur is not None:
                continuation(resp, cur)
                continue
            if resp["kind"] != "count":
                resp.update(kind="page", page=0)
            submit(resp)
        if analyst is not None and not analyst_busy and now < seconds:
            analyst_busy = submit(dict(analyst, t_start=now, t_due=now))
        if inflight:
            with spans.span("step"):
                sched.step()
            for token, (resp, job) in list(inflight.items()):
                if job.result is not None:
                    del inflight[token]
                    resp["t_done"] = spans.now()
                    answer(resp, job.result)
                    if resp["class"] == "count":
                        analyst_busy = False
                    done(resp)
        elif pending:
            with spans.span("wait"):
                time.sleep(max(0.0, min(pending[-1]["t_due"] - now, 0.05)))
        if not pending and (analyst is None or now >= seconds):
            waiting = [r for r, _ in inflight.values()
                       if r["class"] == "interactive"]
            if not waiting or now >= seconds + drain_s:
                break
    for resp, _job in inflight.values():
        # an analyst's count cut by the close is not an answer that was due
        resp.update(status="unfinished" if resp["class"] == "interactive"
                    else "cut", t_done=spans.now())
        out.append(resp)
    return out
