"""Tiny cells for running the harness on the CPU: the chip check is
replaced by the test, everything else is the benchmark's own code."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the repository's configurations, shrunk to a size a CPU test holds
TINY_GENERATORS = {
    "soc-Slashdot0811-like": {"kind": "powerlaw_cluster", "n": 400,
                         "m_per_node": 4, "tri_p": 0.5, "seed": 0},
    "p2p-Gnutella31-like": {"kind": "erdos_renyi", "n": 300, "m": 900,
                       "seed": 0},
}


#: first pages and continuations, as the harness serves them: no cell's mix
#: has them yet (PERF.md, Open questions), the tests keep them working
PAGES = [
    {"kind": "page", "shape": "3-path", "limit": 16, "selectivity": 3,
     "share": 0.15},
    {"kind": "continuation", "shape": "3-path", "limit": 16,
     "selectivity": 3, "share": 0.10},
]


def with_pages(cell: dict) -> dict:
    """``cell`` with dense tenants and :data:`PAGES` in its mix, so that
    the tiny graph's cursors have pages to continue."""
    mix = cell["mix"]
    mix["tenants"] = dict(mix["tenants"], selectivities=[2, 3])
    mix["requests"] = mix["requests"] + PAGES
    return cell


def tiny_cell(name: str, **mix_changes) -> dict:
    """``run.load_cell(name)`` with the graph shrunk and the mix changed
    as given (a smaller rate, for example)."""
    import run
    cell = run.load_cell(name)
    cell["config_spec"] = dict(
        cell["config_spec"], generator=TINY_GENERATORS[cell["config"]])
    cell["mix"] = dict(cell["mix"], **mix_changes)
    return cell


@pytest.fixture
def no_chip_check(monkeypatch):
    """Let ``run.run_cell`` run on the CPU devices."""
    import jax
    import run
    from repro.launch import compile_cache
    monkeypatch.setattr(run, "devices", lambda chips: jax.devices())
    # keep the tests' CPU programs out of the checkout's compile cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    return run
