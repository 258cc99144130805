"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
(``configs/<config>.json``), traffic mix (``traffic/<traffic>.json``) and
metric readers (``metrics/<metric>.py``) are found by name.  A run:

1. turns on JAX's persistent compilation cache (``repro.launch.compile_cache``);
2. builds the configuration's graph from ``--seed`` (``graphs``);
3. starts ``QueryServer`` on it;
4. warms up the cell's own request shapes (set-up ends here);
5. drives the traffic for ``--seconds`` (``loadgen``), with the profiler
   on when ``--trace 1``;
6. compares every answer with the plain reference (``checks``);
7. prints the result as the last line of standard output, the compared
   numbers with their limits as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints why
on standard error and exits 2 with no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import checks  # noqa: E402
import loadgen  # noqa: E402
import graphs  # noqa: E402
import traffic  # noqa: E402
from reference import Reference  # noqa: E402
from spans import Spans  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and the names of the metrics it reports."""
    bench = benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    e2e = [m["name"] for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per = [m["name"] for m in bench["per_layer"]
           if name in m.get("workloads",
                            [name] if m["moves"] in e2e else [])]
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return {**cell, "config_spec": config, "mix": mix,
            "end_to_end": e2e, "per_layer": per}


def reader(metric: str):
    """``read`` of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int) -> list:
    """The chips the cell runs on; :class:`NoChip` if JAX finds fewer."""
    import jax
    found = jax.devices()
    if found[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(found)} {found[0].platform} "
                     f"device(s) ({found[0].device_kind})")
    if len(found) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(found)}")
    return found


@contextlib.contextmanager
def compile_meter():
    """Count the executables XLA compiled, or loaded from the persistent
    compilation cache (``loaded``), inside the block."""
    import jax
    tally = {"compiles": 0, "seconds": 0.0, "loaded": 0}

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE:
            tally["compiles"] += 1
            tally["seconds"] += duration

    def on_event(event, **_):
        if event == CACHE_HIT:
            tally["loaded"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield tally
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


@contextlib.contextmanager
def gc_meter():
    """Count Python's full (generation 2) garbage collections inside the
    block and time them: each one stops the process."""
    tally = {"collections": 0, "seconds": 0.0, "longest": 0.0}
    began: list[float] = []

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            began.append(time.perf_counter())
        elif began:
            took = time.perf_counter() - began.pop()
            tally["collections"] += 1
            tally["seconds"] += took
            tally["longest"] = max(tally["longest"], took)

    gc.callbacks.append(on_gc)
    try:
        yield tally
    finally:
        gc.callbacks.remove(on_gc)


@contextlib.contextmanager
def profiled(on: bool, into: dict):
    """Trace the block with ``jax.profiler`` when ``on``; ``into["raw"]``
    then holds ``tracereduce.read_planes`` of the trace."""
    if not on:
        yield
        return
    import glob

    import jax
    import tracereduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        into["raw"] = tracereduce.read_planes(path)
        into["bytes"] = os.path.getsize(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def warm_up(server, serve, cell: dict, seed: int):
    """Run each of the cell's request shapes once; returns what the window
    needs: the closed loop's request stream, or the open loop's schedule
    and scheduler."""
    mix = cell["mix"]
    if mix["loop"] == "closed":
        rotation = traffic.closed_rotation(mix, seed)
        for req in rotation:
            server.execute(loadgen.query_request(serve, req))
        return {"stream": traffic.cycle(rotation)}
    sched = serve.QuantumScheduler(server, quantum_rows=mix["quantum_rows"])
    by_sel: dict = {}
    for t in traffic.tenants(mix, seed):
        by_sel.setdefault(t["selectivity"], t)
    for spec in mix["requests"]:
        for t in by_sel.values():
            if spec.get("selectivity", t["selectivity"]) != t["selectivity"]:
                continue
            req = {**spec, **t, "class": "interactive"}
            if spec["kind"] == "continuation":
                continue
            sched.submit(loadgen.query_request(serve, req))
    for r in sched.run():
        if r.next_cursor is not None:
            server.execute(serve.QueryRequest(
                r.request.query_name, limit=r.request.limit,
                cursor=r.next_cursor))
    if "analyst" in mix:
        sched.submit(loadgen.query_request(serve, mix["analyst"]))
        sched.run()
    return {"sched": sched}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             log=print) -> dict:
    """One run of ``cell`` (``load_cell``); returns the result line's
    object.  Raises :class:`NoChip` before any work without the chips."""
    chips = devices(cell["chips"])[: cell["chips"]]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro import serve
    from repro.graphs.csr import CSRGraph
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # small programs too: every run after a checkout's first loads them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = chips[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache}")

    indptr, indices = graphs.build_graph(cell["config_spec"]["generator"],
                                         seed)
    n = indptr.shape[0] - 1
    server = serve.QueryServer(CSRGraph(indptr=indptr, indices=indices,
                                        n_nodes=n))
    mix = cell["mix"]
    with compile_meter() as warm:
        loop = warm_up(server, serve, cell, seed)
    setup_s = time.perf_counter() - T_PROCESS
    log(f"set-up {setup_s:.3f} s: graph {n} nodes {indices.shape[0]} "
        f"directed edges; warm-up compiles {warm['compiles']} "
        f"({warm['loaded']} of them loaded from the compile cache)")

    spans = Spans(annotate=trace)
    raw: dict = {}
    plan_before = server.plan_cache_info()
    with compile_meter() as meter, gc_meter() as collected, \
            profiled(trace, raw), spans.span("window"):
        if mix["loop"] == "closed":
            responses = loadgen.closed_loop(server, serve, loop["stream"],
                                            seconds, spans)
        else:
            schedule = traffic.open_schedule(mix, seed, seconds)
            responses = loadgen.open_loop(
                server, serve, loop["sched"], schedule, seconds, spans,
                mix["drain_s"], analyst=mix.get("analyst"))
        end_s = spans.now()
    plan_after = server.plan_cache_info()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in chips)
    log(f"window {seconds} s, load generator ran until {end_s:.3f} s; "
        f"compiles in the window {meter['compiles']} "
        f"({meter['loaded']} loaded from the compile cache, "
        f"{meter['seconds']:.3f} s)")
    log(f"full garbage collections in the window {collected['collections']}"
        f", longest {collected['longest']:.3f} s, "
        f"{collected['seconds']:.3f} s in all")
    log(f"peak_bytes_in_use {peak}")
    log(lateness(responses))
    del server, loop
    gc.collect()

    numbers = checks.compare(responses, Reference(indptr, indices))
    reduced = None
    if trace:
        import tracereduce
        reduced = tracereduce.reduce(raw["raw"])
        log(f"trace {raw['bytes']} bytes")
    record = {"setup_s": setup_s, "seconds": seconds, "end_s": end_s,
              "responses": responses, "spans": spans.spans,
              "plan_cache": (plan_before, plan_after), "trace": reduced}
    metrics = {}
    bench = benchmark()
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in cell["per_layer" if trace else "end_to_end"]:
        value = reader(name)(record)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": checks.correct(numbers),
           "attempted": sum(r["status"] != "cut" for r in responses),
           "failed": sum(r["status"] in ("rejected", "error", "unfinished")
                         for r in responses),
           "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {k: {"value": numbers[k], "limit": lim}
                     for k, lim in checks.LIMITS.items()}
    out["checks"]["checked"] = {"value": numbers["checked"], "limit": 1}
    return out


def lateness(responses: list[dict]) -> str:
    """How late the load generator ran: the open loop's delay from due to
    sent, the closed loop's longest gap between an answer and the next
    request."""
    late = sorted(r["t_start"] - r["t_due"] for r in responses
                  if "t_due" in r and r["status"] != "cut")
    if late:
        return (f"load generator late by p50 {late[len(late) // 2]:.6f} s, "
                f"max {late[-1]:.6f} s over {len(late)} requests")
    gaps = [b["t_start"] - a["t_done"]
            for a, b in zip(responses, responses[1:])]
    return (f"closed loop: {len(responses)} requests, longest gap between "
            f"an answer and the next request {max(gaps, default=0.0):.6f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       log=lambda msg: print(msg, flush=True))
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run.", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit "
              f"{'>=' if name == 'checked' else '<='} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
