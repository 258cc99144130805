"""A run off the chip: it refuses without a TPU, fails where
only the benchmark's files are, and otherwise prints a result line with
every key the result line must hold."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT, tiny_cell


def _run(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    out = _run(["--workload", "gnutella.counts", "--seed", "1",
                "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["--workload", "gnutella.counts", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_result_line_has_every_key(no_chip_check):
    run = no_chip_check
    out = run.run_cell(tiny_cell("gnutella.counts"), 2**31 + 7, 0.5, False,
                       log=lambda _: None)
    json.dumps(out)
    assert list(out)[-1] == "checks"
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "count_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    assert out["attempted"] > 0 and out["failed"] == 0


def test_each_cell_reports_what_the_benchmark_lists():
    import run
    bench = run.benchmark()
    for cell in bench["workloads"]:
        c = run.load_cell(cell["name"])
        assert "setup_s" in c["end_to_end"] and len(c["end_to_end"]) >= 2
        assert c["per_layer"]
        for name in c["end_to_end"] + c["per_layer"]:
            assert callable(run.reader(name))
