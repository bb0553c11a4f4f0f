"""`correct` has to come out false when the timed path is wrong.

Each cell of BENCHMARK.json runs here on the CPU at a small size, with
the check for a chip skipped and everything else as in a run. The
control runs the program's own lower-precision path (bf16 design or
bank storage), which the limits in the configuration files must fail.
Each fault breaks the timed path underneath the harness, the way a
faulty change to the program would:

- unchanged: the step hands back the state it was given;
- half: half of the rows are left out;
- altered: one answer is changed where it is produced.

(The cells run on one chip, so none has an exchange between chips to
leave out.)
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run

SOLVE_CELLS = ("solve.real-sim", "solve.gisette")
SERVE_CELLS = ("serve.real-sim",)
SECONDS = 0.5


def _run(cell, devices, control=False):
    return run.run_cell(cell, 2**31 + 99, SECONDS, False, devices,
                        t_setup0=0.0, control=control)[0]


@pytest.mark.parametrize("name", SOLVE_CELLS + SERVE_CELLS)
def test_sound_run_is_correct(name, tiny_cell, cpu_devices):
    out = _run(tiny_cell(name), cpu_devices)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("name", SOLVE_CELLS + SERVE_CELLS)
def test_control_is_not_correct(name, tiny_cell, cpu_devices):
    out = _run(tiny_cell(name), cpu_devices, control=True)
    assert not out["correct"], out["checks"]


# -- faults in the solver's outer step ------------------------------------------

def _break_outer(monkeypatch, fault):
    from repro.core import pcdn
    real = pcdn.make_path_outer

    def broken(problem, cfg):
        outer = real(problem, cfg)

        def step(w, z, key, active, recheck, c):
            out = outer(w, z, key, active, recheck, c)
            if fault == "unchanged":
                return (w, z, key) + tuple(out[3:])
            w_new = out[0].at[0].add(1.0)
            return (w_new,) + tuple(out[1:])
        return step

    monkeypatch.setattr(pcdn, "make_path_outer", broken)


def _drop_half_rows(monkeypatch):
    """The problem is built from the first half of the rows only."""
    from repro.data.libsvm import PaddedCSC
    from repro.launch import common
    real = common.make_problem

    def half(X, y, **kw):
        s = X.shape[0]
        h = s // 2
        if hasattr(X, "col_rows"):
            keep = X.col_rows < h
            X = PaddedCSC(col_rows=np.where(keep, X.col_rows, h),
                          col_vals=np.where(keep, X.col_vals, 0),
                          shape=(h, X.shape[1]))
        else:
            X = X[:h]
        return real(X, y[:h], **kw)

    monkeypatch.setattr(common, "make_problem", half)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("name", SOLVE_CELLS)
def test_solve_fault_is_not_correct(name, fault, tiny_cell, cpu_devices,
                                    monkeypatch):
    cell = tiny_cell(name)
    cell["traffic"]["max_outer"] = 30
    if fault == "half":
        _drop_half_rows(monkeypatch)
    else:
        _break_outer(monkeypatch, fault)
    out = _run(cell, cpu_devices)
    assert not out["correct"], out["checks"]


# -- faults in the scorer ---------------------------------------------------------

def _break_scorer(monkeypatch, fault):
    from repro.serve import loop
    real = loop.margins_dense

    def broken(bank, X, **kw):
        z = real(bank, X, **kw)
        if fault == "unchanged":        # the weights never applied
            return jnp.zeros_like(z) + bank.bias[None, :]
        if fault == "half":
            h = (z.shape[0] + 1) // 2
            return z.at[h:].set(0.0)
        return z.at[0, 0].add(1.0)

    monkeypatch.setattr(loop, "margins_dense", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("name", SERVE_CELLS)
def test_serve_fault_is_not_correct(name, fault, tiny_cell, cpu_devices,
                                    monkeypatch):
    _break_scorer(monkeypatch, fault)
    out = _run(tiny_cell(name), cpu_devices)
    assert not out["correct"], out["checks"]
