"""A run whose timed path is broken underneath comes out not correct:
one test per fault the cells can have (there is no exchange between
chips: every cell runs on one)."""
import numpy as np
import pytest

from conftest import tiny_cell, with_pages

SEED = 2**31 + 123


def dense_tenants():
    return with_pages(tiny_cell("slashdot.tenants", rate_per_s=40.0))


def _run(run, cell):
    return run.run_cell(cell, SEED, 1.0, False, log=lambda _: None)


@pytest.mark.parametrize("cell", ["slashdot.clique3", "gnutella.counts",
                                  "dense_tenants"])
def test_sound_runs_are_correct(no_chip_check, cell):
    cell = dense_tenants() if cell == "dense_tenants" else tiny_cell(cell)
    out = _run(no_chip_check, cell)
    assert out["correct"] is True
    assert out["checks"]["checked"]["value"] > 0


@pytest.mark.parametrize("name", ["slashdot.clique3", "gnutella.counts",
                                  "slashdot.tenants"])
def test_answer_altered_where_it_is_produced(no_chip_check, monkeypatch,
                                             name):
    from repro.serve import QueryServer
    real = QueryServer._execute_plan

    def plus_one(self, plan, gdb, req):
        c, label, stats = real(self, plan, gdb, req)
        return c + 1, label, stats

    monkeypatch.setattr(QueryServer, "_execute_plan", plus_one)
    out = _run(no_chip_check, tiny_cell(name, rate_per_s=10.0))
    assert out["correct"] is False
    assert out["checks"]["wrong_counts"]["value"] > 0


def _half_rows(real):
    """A level program that sees only the first half of its rows and
    doubles what it counts: the mean taken over the rest."""
    def half(indptr, indices, bitmaps, frontier, mult, row_valid, **kw):
        keep = np.arange(row_valid.shape[0]) < (row_valid.shape[0] + 1) // 2
        out = real(indptr, indices, bitmaps, frontier, mult,
                   row_valid & keep, **kw)
        return out * 2 if kw.get("count_only") else out
    return half


@pytest.mark.parametrize("name", ["slashdot.clique3", "gnutella.counts"])
def test_half_of_the_batch_left_out(no_chip_check, monkeypatch, name):
    from repro.core import vlftj
    monkeypatch.setattr(vlftj, "_expand_level",
                        _half_rows(vlftj._expand_level))
    out = _run(no_chip_check, tiny_cell(name))
    assert out["correct"] is False


def test_half_of_the_batch_left_out_of_a_message(no_chip_check,
                                                 monkeypatch):
    """Tenants' counts run on SpMV messages: half the edges dropped, the
    rest doubled."""
    import jax.numpy as jnp
    from repro.core import yannakakis
    real = yannakakis._spmv

    def half(indptr, indices, src_ids, c, *, num_segments):
        keep = (jnp.arange(indices.shape[0]) % 2) == 0
        full = real(indptr, indices, src_ids, c * 0 + c,
                    num_segments=num_segments)
        kept = yannakakis.jax.ops.segment_sum(
            jnp.where(keep, c[indices], 0), src_ids,
            num_segments=num_segments)
        return 2 * kept + 0 * full

    monkeypatch.setattr(yannakakis, "_spmv", half)
    out = _run(no_chip_check, tiny_cell("slashdot.tenants",
                                        rate_per_s=10.0))
    assert out["correct"] is False


@pytest.mark.parametrize("name", ["slashdot.clique3", "gnutella.counts"])
def test_step_returns_its_state_unchanged(no_chip_check, monkeypatch, name):
    """An interior level that hands its frontier on as it came."""
    from repro.core import vlftj
    real = vlftj._expand_level

    def unchanged(indptr, indices, bitmaps, frontier, mult, row_valid,
                  **kw):
        out = real(indptr, indices, bitmaps, frontier, mult, row_valid,
                   **kw)
        if kw.get("count_only"):
            return out
        cand, keep = out
        lane = np.arange(cand.shape[1]) == 0
        return (np.broadcast_to(np.asarray(frontier)[:, -1:], cand.shape),
                np.asarray(row_valid)[:, None] & lane[None, :])

    monkeypatch.setattr(vlftj, "_expand_level", unchanged)
    out = _run(no_chip_check, tiny_cell(name))
    assert out["correct"] is False


def test_scheduler_step_returns_its_state_unchanged(no_chip_check,
                                                    monkeypatch):
    """A scheduler step that puts its job back as it found it, once the
    set-up is done: the job never answers."""
    from repro.serve import QuantumScheduler
    run = no_chip_check
    real = run.warm_up

    def warm_then_break(*args, **kw):
        out = real(*args, **kw)
        monkeypatch.setattr(QuantumScheduler, "_advance",
                            lambda self, job: False)
        return out

    monkeypatch.setattr(run, "warm_up", warm_then_break)
    out = _run(run, tiny_cell("slashdot.tenants", rate_per_s=10.0,
                              drain_s=1.0))
    assert out["correct"] is False
    assert out["checks"]["lost"]["value"] > 0


def test_cursor_step_returns_its_state_unchanged(no_chip_check,
                                                 monkeypatch):
    """A cursor that hands out its first page again on a continuation."""
    from repro.results import ResultCursor
    real = ResultCursor.take

    def again(self, n=None):
        if getattr(self, "_first", None) is None:
            self._first = real(self, n)
            return self._first
        return self._first

    monkeypatch.setattr(ResultCursor, "take", again)
    out = _run(no_chip_check, dense_tenants())
    assert out["correct"] is False
    assert out["checks"]["wrong_pages"]["value"] > 0
