"""The load generators on tiny graphs: what they send, when, and what
they record."""
import pytest

import loadgen
import traffic
from conftest import tiny_cell, with_pages
from spans import Spans


@pytest.fixture(scope="module")
def server():
    import graphs
    from repro.graphs.csr import CSRGraph
    from repro.serve import QueryServer
    indptr, indices = graphs.build_graph(
        tiny_cell("slashdot.tenants")["config_spec"]["generator"], seed=4)
    return QueryServer(CSRGraph(indptr=indptr, indices=indices,
                                n_nodes=indptr.shape[0] - 1), page_rows=64)


def test_closed_loop_sends_one_after_another(server):
    from repro import serve
    mix = tiny_cell("gnutella.counts")["mix"]
    rotation = traffic.closed_rotation(mix, 9)
    assert len(rotation) == len(mix["shapes"]) * mix["predicate_seeds"]
    for req in rotation:
        server.execute(loadgen.query_request(serve, req))
    spans = Spans()
    out = loadgen.closed_loop(server, serve, traffic.cycle(rotation), 0.5,
                              spans)
    assert [r["shape"] for r in out] == [
        rotation[i % len(rotation)]["shape"] for i in range(len(out))]
    assert all(r["status"] == "ok" for r in out)
    for a, b in zip(out, out[1:]):
        assert a["t_start"] <= a["t_done"] <= b["t_start"]
    # the request in flight at the close is waited for and recorded
    assert out[-2]["t_start"] < 0.5 <= out[-1]["t_done"]
    assert [s[0] for s in spans.spans] == ["execute"] * len(out)


def test_open_loop_answers_every_request_from_its_due_time(server):
    from repro import serve
    mix = dict(with_pages(tiny_cell("slashdot.tenants"))["mix"],
               rate_per_s=40.0)
    schedule = traffic.open_schedule(mix, 21, 3.0)
    sched = serve.QuantumScheduler(server, quantum_rows=mix["quantum_rows"])
    spans = Spans()
    out = loadgen.open_loop(server, serve, sched, schedule, 3.0, spans, 30)
    assert len(out) == len(schedule) == 120
    # a cold server on the CPU falls behind: some tenants hit their quota
    assert {r["status"] for r in out} <= {"ok", "rejected"}
    assert sum(r["status"] == "ok" for r in out) >= 60
    assert all(r["t_start"] >= r["t_due"] and r["t_done"] >= r["t_start"]
               for r in out)
    pages = [r for r in out if r["kind"] != "count" and r["status"] == "ok"]
    assert {r["kind"] for r in pages} == {"page", "continuation"}
    # a continuation follows a page of the same tenant's cursor
    for r in pages:
        if r["kind"] == "continuation":
            assert r["page"] >= 1
            first = [p for p in pages if p["tenant"] == r["tenant"]
                     and p["kind"] == "page" and p["t_done"] <= r["t_start"]]
            assert first and first[-1]["pred_seed"] == r["pred_seed"]
    names = {s[0] for s in spans.spans}
    assert {"submit", "step"} <= names


def test_open_loop_drives_an_analyst_beside_the_tenants(server):
    from repro import serve
    mix = dict(tiny_cell("slashdot.tenants")["mix"], rate_per_s=20.0)
    analyst = {"kind": "count", "class": "count", "shape": "3-clique",
               "selectivity": 10, "pred_seed": 0, "tenant": "analyst"}
    sched = serve.QuantumScheduler(server, quantum_rows=64)
    out = loadgen.open_loop(server, serve, sched,
                            traffic.open_schedule(mix, 2, 1.0), 1.0,
                            Spans(), 30, analyst=analyst)
    counts = [r for r in out if r["class"] == "count"]
    assert len(counts) >= 2
    assert {r["status"] for r in counts} <= {"ok", "cut"}
    assert {r["status"] for r in out if r["class"] != "count"} <= {
        "ok", "rejected"}
