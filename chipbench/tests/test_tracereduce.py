"""The reduction from a profiler trace to busy time, executables and idle
gaps: on hand-made planes, and on a trace recorded on a TPU v5e."""
import glob
import os

import pytest

import tracereduce
from conftest import HERE


def test_union_merges_overlaps():
    assert tracereduce.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        [0, 2.5], [3, 4]]


def test_module_names_drop_the_run_id():
    assert tracereduce.module_name("jit__expand_level(42)") == \
        "jit__expand_level"
    assert tracereduce.module_name("jit__spmv") == "jit__spmv"


def test_reduce_on_hand_made_planes():
    raw = {"spans": [("window", 10.0, 20.0), ("execute", 10.0, 15.0),
                     ("execute", 15.0, 21.0), ("wait", 18.0, 19.0)],
           "devices": {"/device:TPU:0": {
               "ops": [("a", 9.0, 11.0), ("b", 12.0, 14.0),
                       ("c", 13.0, 15.0), ("d", 19.5, 25.0)],
               "modules": [("jit_f(1)", 9.0, 11.0), ("jit_g(2)", 12.0, 15.0),
                           ("jit_f(3)", 19.5, 25.0)]}}}
    got = tracereduce.reduce(raw)
    assert got["window_s"] == 10.0
    assert got["busy_s"] == pytest.approx(1.0 + 3.0 + 0.5)
    assert got["device_ops"] == [["jit_g", 3.0], ["jit_f", 1.5]]
    # gaps [11, 12] and [15, 19.5]: at their midpoints only an execute
    # span is open (the wait span covers 18-19, not 17.25)
    assert got["idle_gaps"] == [["execute", 4.5], ["execute", 1.0]]
    assert ["execute", 0.0, 5.0] in [list(s) for s in got["spans"]]


def test_reduce_without_a_window_or_a_device_reads_nothing():
    assert tracereduce.reduce({"spans": [], "devices": {}}) is None
    assert tracereduce.reduce({"spans": [("window", 0, 1)],
                               "devices": {}}) is None


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    got = tracereduce.reduce(tracereduce.read_planes(path))
    assert 0 < got["busy_s"] < got["window_s"]
    names = {n for n, _ in got["device_ops"]}
    assert names & {"jit__expand_level", "jit__spmv"}
    assert {n for n, _ in got["idle_gaps"]} <= {
        "execute", "submit", "step", "continuation", "wait", "none"}
