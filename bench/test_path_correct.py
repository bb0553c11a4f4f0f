"""The path cell on the CPU at a small size: `correct` comes out false
when a sweep is wrong, the held-out split keeps every entry once, and
the per-layer readers read what a sweep records.

Each fault breaks the path driver underneath the harness, the way a
faulty change to the program would:

- unsolved: one point runs no iteration and claims its stop;
- weight: one point's pulled weights have one coordinate changed;
- accuracy: one point's held-out accuracy is off by one row;
- stale: the margin refresh between points hands back zeros;
- pick: every sweep names the point after its best as the best, which
  only `wrong_pick` sees.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench import data, reference, run
from bench import path as path_mix

SECONDS = 0.5


@pytest.fixture
def tiny_path(tiny_cell):
    cell = tiny_cell("path.real-sim")
    cell["config"]["data"]["train_rows"] = 2400
    cell["traffic"].update(points=8, solver_args=[
        "--P", "32", "--shrink", "--max-outer", "200"])
    return cell


def _run(cell, devices, control=False):
    return run.run_cell(cell, 2**31 + 77, SECONDS, False, devices,
                        t_setup0=0.0, control=control)


def test_sound_sweeps_are_correct(tiny_path, cpu_devices):
    out, notes = _run(tiny_path, cpu_devices)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    sweep = notes["first_sweep"]
    assert all(sweep["stopped"]) and len(sweep["n_outer"]) == 8
    assert sweep["nnz"][0] == 0 and sweep["nnz"][-1] > 0
    assert notes["stops"][0] == notes["stops"][1] < notes["stops"][2]


def test_control_is_not_correct(tiny_path, cpu_devices):
    out, _ = _run(tiny_path, cpu_devices, control=True)
    assert not out["correct"], out["checks"]


def _plant(monkeypatch, fault):
    from repro.engine.local import LocalBackend
    from repro.path import driver
    calls = {"n": 0}

    def nth():     # the set-up sweep makes two calls: point 4 of the first
        calls["n"] += 1
        return calls["n"] == 7

    if fault == "unsolved":
        real = driver.engine_loop.run_outer_loop

        def loop(outer, state, c, **kw):
            if nth():
                state, res = real(outer, state, c, **dict(kw, max_outer=0))
                return state, res._replace(converged=True)
            return real(outer, state, c, **kw)
        monkeypatch.setattr(driver.engine_loop, "run_outer_loop", loop)
    elif fault == "weight":
        real_pull = LocalBackend.host_weights

        def pull(self, w):
            w = real_pull(self, w)
            return w + (np.arange(w.shape[0]) == 0) if nth() else w
        monkeypatch.setattr(LocalBackend, "host_weights", pull)
    elif fault == "accuracy":
        real_acc = driver.validation_accuracy

        def acc(design, y, w):
            return real_acc(design, y, w) + (1.0 / len(y) if nth() else 0)
        monkeypatch.setattr(driver, "validation_accuracy", acc)
    elif fault == "pick":
        real_pick = driver.pick_best
        monkeypatch.setattr(driver, "pick_best", lambda points: (
            real_pick(points) + 1) % len(points))
    else:
        monkeypatch.setattr(LocalBackend, "margins",
                            lambda self, w: self.problem.margins(w) * 0)


@pytest.mark.parametrize("fault", ["unsolved", "weight", "accuracy",
                                   "stale", "pick"])
def test_path_fault_is_not_correct(fault, tiny_path, cpu_devices,
                                   monkeypatch):
    _plant(monkeypatch, fault)
    out, _ = _run(tiny_path, cpu_devices)
    assert not out["correct"], out["checks"]
    if fault == "pick":
        assert [k for k, c in out["checks"].items()
                if c["value"] > c["limit"]] == ["wrong_pick"]


def test_split_rows_keeps_every_entry_once():
    X, _ = data.sparse_classification(500, 60, 25, seed=3)
    head, tail = path_mix.split_rows(X, 380)
    assert head.shape == (380, 60) and tail.shape == (120, 60)
    assert head.nnz + tail.nnz == X.nnz
    for part, lo in ((head, 0), (tail, 380)):
        s = part.shape[0]
        real = part.col_rows < s
        # padding sits after the entries, and the layout is no wider than
        # its widest column
        assert np.all(np.diff(real.astype(int), axis=1) <= 0)
        assert real.sum(axis=1).max() == part.col_rows.shape[1]
    # every (row, column, value) entry lands in exactly one part, in order
    for j in range(60):
        keep = X.col_rows[j] < 500
        want = list(zip(X.col_rows[j][keep], X.col_vals[j][keep]))
        got = [(r + lo, v) for part, lo in ((head, 0), (tail, 380))
               for r, v in zip(part.col_rows[j], part.col_vals[j])
               if r < part.shape[0]]
        assert sorted(got) == sorted(want)
        assert [e for e in want if e[0] < 380] == [e for e in got
                                                   if e[0] < 380]
    w = np.random.default_rng(0).standard_normal(60)
    z = reference.matvec(X, w)
    np.testing.assert_allclose(reference.matvec(head, w), z[:380])
    np.testing.assert_allclose(reference.matvec(tail, w), z[380:])


def test_readers_read_a_sweep_record():
    sweeps = [{"n_outer": [1, 3, 4], "n_active": [[10], [10, 6, 4],
                                                  [8, 4, 4, 4]],
               "between_s": [0.002, 0.004, 0.006]},
              {"n_outer": [1, 2, 3], "n_active": [[10], [6, 6], [4, 4, 4]],
               "between_s": [0.001, 0.001, None]}]
    ctx = {"sweeps": sweeps, "n_features": 10}
    assert run._reader("outer_iters.path")(ctx) == pytest.approx(7.0)
    counts = [10, 10, 6, 4, 8, 4, 4, 4, 10, 6, 6, 4, 4, 4]
    assert run._reader("active_share.path")(ctx) == pytest.approx(
        100.0 * sum(counts) / (10 * len(counts)))
    assert run._reader("between_ms.path")(ctx) == pytest.approx(2.8)
    for name in ("outer_iters.path", "active_share.path", "between_ms.path",
                 "outer_ms.path"):
        assert run._reader(name)({"trace": {}}) is None
