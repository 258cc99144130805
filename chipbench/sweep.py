"""Find an open-loop cell's knee: the highest offered rate whose answers
keep pace with the arrivals.  Run once, on the chip, when a cell's rate
is set; the benchmark's own runs never run it.

    python3 chipbench/sweep.py --workload <cell> --seeds 5,6 --seconds <s> --rates 1.2,1.4

Each pair of rate and seed gets a process of its own, built as a run is
(graph, server, warm-up), so that no rate finds the caches that another
rate warmed.  It drives the mix at that rate for ``--seconds`` and prints
one line: the requests due, those answered inside the window, those
refused or failed, the answers' p50 and p95 from due, how late the load
generator ran, how long the queue took to drain after the close, and
``keeps_pace``: no request refused or failed, and none ``behind``: due
more than :data:`GRACE_S` before the close and still unanswered there.
The knee is the highest rate that keeps pace on every seed.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import graphs
import run
from loadgen import open_loop
from readings import latency_ms
from spans import Spans
from traffic import open_schedule

#: a request due this long before the close has had time for its answer:
#: over twice the longest count of the tenants' mix on the chip (0.75 s)
GRACE_S = 2.0


def sweep_one(workload: str, seed: int, seconds: float, rate: float
              ) -> dict:
    """One rate on one seed, in this process."""
    cell = run.load_cell(workload)
    run.devices(cell["chips"])
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro import serve
    from repro.graphs.csr import CSRGraph
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    indptr, indices = graphs.build_graph(cell["config_spec"]["generator"],
                                         seed)
    server = serve.QueryServer(CSRGraph(indptr=indptr, indices=indices,
                                        n_nodes=indptr.shape[0] - 1))
    sched = run.warm_up(server, serve, cell, seed)["sched"]
    mix = dict(cell["mix"], rate_per_s=rate)
    schedule = open_schedule(mix, seed, seconds)
    spans = Spans()
    t = time.perf_counter()
    out = open_loop(server, serve, sched, schedule, seconds, spans,
                    mix["drain_s"])
    record = {"responses": out, "seconds": seconds, "end_s": spans.now()}
    late = sorted(r["t_start"] - r["t_due"] for r in out)
    answered = sum(r["status"] == "ok" and r["t_done"] <= seconds
                   for r in out)
    behind = sum(r["t_due"] < seconds - GRACE_S and
                 (r["status"] != "ok" or r["t_done"] > seconds) for r in out)
    not_ok = sum(r["status"] != "ok" for r in out)
    return {"rate_per_s": rate, "seed": seed, "due": len(schedule),
            "answered_in_window": answered, "behind": behind,
            "not_ok": not_ok, "keeps_pace": behind == 0 and not_ok == 0,
            "p50_ms": latency_ms(record, 50), "p95_ms": latency_ms(record, 95),
            "late_max_s": late[-1] if late else None,
            "drain_s": time.perf_counter() - t - seconds}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")]
    if len(seeds) * len(rates) == 1:
        print(json.dumps(sweep_one(args.workload, seeds[0], args.seconds,
                                   rates[0])), flush=True)
        return 0
    rc = 0
    for rate in rates:
        for seed in seeds:
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--workload", args.workload, "--seeds", str(seed),
                 "--seconds", str(args.seconds), "--rates", str(rate)])
            rc = rc or done.returncode
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
