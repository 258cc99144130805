"""``chip_smoke.py`` off the chip: its logic on tiny graphs, its refusal
to run without a TPU, and the two backend rules it relies on (Pallas
interpret mode and the compile-cache directory)."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from conftest import REPO_ROOT, make_gdb
from repro.core import engine as engine_mod
from repro.core import get_query
from repro.graphs.generators import make_snap_like
from repro.kernels import backend
from repro.launch import compile_cache

sys.path.insert(0, REPO_ROOT)
import chip_smoke as cs  # noqa: E402


@pytest.mark.parametrize("n,m,seed", [(40, 3, 1), (80, 3, 3)])
def test_reference_counts_match_lftj_ref(n, m, seed):
    """The numpy/scipy references that judge the full-scale run agree with
    the scalar oracle on the same data."""
    gdb = make_gdb(n, m, seed=seed)
    want = cs.reference_counts(gdb.csr, gdb.unary)
    got = {name: engine_mod.count(get_query(name), gdb, engine="lftj_ref")
           for name in cs.SHAPES}
    assert want == got
    assert min(got.values()) > 0


def test_smoke_logic_on_tiny_graphs():
    lines = []
    served = make_snap_like(cs.SERVED_GRAPH, seed=0, scale=0.005)
    oracle = make_snap_like(cs.ORACLE_GRAPH, seed=0, scale=0.05)
    figures = cs.run_smoke(served, oracle, log=lines.append)
    reqs = figures["requests"]
    assert set(reqs) == set(cs.SHAPES)
    assert {r["engine"] for r in reqs.values()} == {"vlftj", "yannakakis",
                                                    "hybrid"}
    assert set(figures["oracle"]) == set(cs.SHAPES)
    assert figures["compiles"] > 0
    assert any(line.startswith("hybrid 3-clique") for line in lines)


def test_smoke_rejects_a_wrong_count(monkeypatch):
    served = make_snap_like(cs.SERVED_GRAPH, seed=0, scale=0.002)
    real = cs.reference_counts

    def off_by_one(csr, unary):
        want = real(csr, unary)
        want["4-cycle"] += 1
        return want

    monkeypatch.setattr(cs, "reference_counts", off_by_one)
    with pytest.raises(cs.SmokeError, match="4-cycle"):
        cs.serve_and_check(served, log=lambda _: None)


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_script_alone_fails(tmp_path):
    """Copied away from the repository, the script cannot import the
    package and prints no result."""
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False),
                                                ("gpu", False)])
def test_interpret_follows_the_backend(monkeypatch, platform, interpret):
    monkeypatch.setattr(backend.jax, "default_backend", lambda: platform)
    assert backend.interpret_mode() is interpret
    assert backend.interpret_mode(False) is False
    if platform == "cpu":
        assert backend.interpret_mode(True) is True
    else:
        with pytest.raises(ValueError, match="interpret"):
            backend.interpret_mode(True)


def test_compile_cache_dir_from_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV)
    assert compile_cache.compile_cache_dir() == os.path.join(REPO_ROOT,
                                                             ".jax_cache")
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("env_set", [True, False])
def test_enable_compile_cache_sets_one_directory(monkeypatch, tmp_path,
                                                 env_set):
    if env_set:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    path = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == (str(tmp_path) if env_set
                    else os.path.join(REPO_ROOT, ".jax_cache"))


def test_smoke_result_line_shape():
    dev = jax.devices()[0]
    line = cs.result_line(dev, 1)
    assert json.loads(line) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": 1}}
