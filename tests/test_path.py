"""Regularization-path engine: grid/c_max analytics, warm-path-vs-cold
equivalence, active-set shrinking, and the vmapped batch solver
(DESIGN.md section 8)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import PCDNConfig, make_problem, solve
from repro.core import bundles as B
from repro.core import pcdn
from repro.data import make_classification
from repro.path import PathConfig, c_grid, run_path, solve_batch

S, N = 300, 192


@pytest.fixture(scope="module")
def data():
    return make_classification(S, N, sparsity=0.9, corr=0.3, seed=0)


@pytest.fixture(scope="module")
def problem(data):
    X, y, _ = data
    return make_problem(X, y, c=1.0)


# -- c_max / grid -------------------------------------------------------------

def test_c_max_threshold(problem, data):
    """w = 0 is the solution at c <= c_max and is not above it."""
    X, y, _ = data
    cmax = problem.c_max()
    below = solve(make_problem(X, y, c=0.95 * cmax),
                  PCDNConfig(P=64, max_outer=30, tol_kkt=1e-5))
    assert int(jnp.sum(below.w != 0)) == 0 and below.converged
    above = solve(make_problem(X, y, c=1.5 * cmax),
                  PCDNConfig(P=64, max_outer=60, tol_kkt=1e-5))
    assert int(jnp.sum(above.w != 0)) > 0


def test_c_grid_geometry():
    cs = c_grid(0.5, n_points=5, span=16.0)
    assert cs.shape == (5,) and cs[0] == pytest.approx(0.5)
    assert cs[-1] == pytest.approx(8.0)
    ratios = cs[1:] / cs[:-1]
    np.testing.assert_allclose(ratios, ratios[0], rtol=1e-12)
    with pytest.raises(ValueError):
        c_grid(0.5, c_final=0.4)
    with pytest.raises(ValueError):
        c_grid(-1.0)


# -- warm path == cold solves -------------------------------------------------

def _kkt64(X, y, w, c):
    """Float64 KKT violation of the logistic problem at w (dense X)."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)
    u = c * y * (1.0 / (1.0 + np.exp(-y * (X @ w))) - 1.0)
    g = X.T @ u
    v = np.where(w > 0, g + 1.0, np.where(w < 0, g - 1.0,
                                          np.maximum(np.abs(g) - 1.0, 0.0)))
    return float(np.max(np.abs(v)))


@pytest.mark.parametrize("stop, shrink, tol", [
    ("absolute", False, 1e-5),
    # relative stops: 1e-6 * KKT_c(0) puts every point at 1.1e-6 .. 1.9e-5
    ("relative", True, 1e-6)])
def test_warm_path_matches_cold_solves(problem, data, stop, shrink, tol):
    X, y, _ = data
    cfg = PathConfig(solver=PCDNConfig(P=64, max_outer=150, tol_kkt=tol,
                                       shrink=shrink),
                     n_points=5, span=20.0, stop=stop)
    res = run_path(problem, cfg)
    assert all(p.converged for p in res.points)
    for i, c in enumerate(res.cs):
        cold = solve(make_problem(X, y, c=float(c)),
                     PCDNConfig(P=64, max_outer=300, tol_kkt=1e-5))
        assert cold.converged
        np.testing.assert_allclose(res.weights[i], np.asarray(cold.w),
                                   atol=2e-3)
        assert res.points[i].objective == pytest.approx(
            cold.objective, rel=1e-5)


@pytest.mark.parametrize("stop, shrink", [("absolute", False),
                                          ("relative", True)])
def test_path_records_and_best_pick(problem, data, stop, shrink):
    X, y, _ = data
    Xv, yv, _ = make_classification(120, N, sparsity=0.9, corr=0.3, seed=5)
    cfg = PathConfig(solver=PCDNConfig(P=64, max_outer=80, shrink=shrink,
                                       tol_kkt=1e-3 if stop == "absolute"
                                       else 1e-2),
                     n_points=4, span=10.0, stop=stop)
    res = run_path(problem, cfg, val_design=Xv, val_y=yv)
    assert len(res.points) == 4 and res.weights.shape == (4, N)
    assert res.points[0].nnz == 0            # the c_max anchor is all-zero
    accs = [p.val_accuracy for p in res.points]
    assert all(a is not None for a in accs)
    assert res.best_index is not None
    assert res.best.val_accuracy == max(accs)
    # the pick is the argmax of float64 held-out accuracies, ties to the
    # sparser model (then the smaller c)
    acc64 = [float(np.mean(np.where(np.asarray(Xv, np.float64) @ w < 0,
                                    -1.0, 1.0) == yv))
             for w in np.asarray(res.weights, np.float64)]
    np.testing.assert_allclose(accs, acc64, rtol=0, atol=1e-12)
    nnz = [p.nnz for p in res.points]
    assert res.best_index == min(range(4), key=lambda i: (-acc64[i], nnz[i],
                                                          i))
    for p in res.points:
        assert 0.0 <= p.between_s <= p.seconds
        assert len(p.n_active) == p.n_outer
        assert all(0 < a <= N for a in p.n_active)


def test_relative_stop_meets_each_point_stop_in_float64(problem, data):
    """A warm, shrinking sweep under the relative stop: every point,
    the c_max anchor among them, ends at a float64 KKT at or under its
    own stop eps * KKT_{c_i}(0), and the anchor takes the next stop."""
    X, y, _ = data
    eps = 1e-2
    cfg = PathConfig(solver=PCDNConfig(P=64, max_outer=200, tol_kkt=eps,
                                       shrink=True),
                     n_points=6, span=50.0, stop="relative")
    c_max = problem.c_max()
    tols = cfg.stops(c_max)
    kkt0 = [max(c / c_max - 1.0, 0.0) for c in cfg.grid(c_max)]
    np.testing.assert_allclose(tols[1:], eps * np.asarray(kkt0[1:]),
                               rtol=1e-12)
    assert kkt0[0] == 0.0 and tols[0] == tols[1] == tols.min()
    res = run_path(problem, cfg)
    assert all(p.converged for p in res.points)
    assert res.points[0].nnz == 0 and res.points[0].n_outer >= 1
    for i, p in enumerate(res.points):
        assert p.kkt <= tols[i]
        assert _kkt64(X, y, res.weights[i], p.c) <= tols[i]
    assert min(min(p.n_active) for p in res.points) < N


def test_shrink_consistency_holds_against_smallest_stop(problem):
    """A shrinking backend must compile its un-shrink threshold at or
    under the sweep's smallest stop: at it the sweep runs, above it the
    sweep is refused."""
    from repro.engine import LocalBackend
    cfg = PathConfig(solver=PCDNConfig(P=64, max_outer=40, tol_kkt=1e-2,
                                       shrink=True),
                     n_points=6, span=10.0, stop="relative")
    c_max = problem.c_max()
    unshrink = cfg.unshrink_tol(c_max)
    assert unshrink == cfg.stops(c_max).min() < cfg.solver.tol_kkt
    at = LocalBackend(problem, dataclasses.replace(cfg.solver,
                                                   tol_kkt=unshrink))
    assert len(run_path(None, cfg, backend=at).points) == 6
    above = LocalBackend(problem, cfg.solver)      # threshold = eps
    with pytest.raises(ValueError, match="un-shrink"):
        run_path(None, cfg, backend=above)
    # without a backend the sweep builds its own at the smallest stop
    assert len(run_path(problem, cfg).points) == 6


def test_held_out_design_is_uploaded_once_per_sweep(problem, monkeypatch):
    """run_path turns the held-out split into a device design once and
    scores every point against it."""
    from repro.core import design_matrix, problem as problem_mod
    from repro.path import driver
    Xv, yv, _ = make_classification(80, N, sparsity=0.9, corr=0.3, seed=6)
    built = []
    real = design_matrix.as_design

    def counting(X, *a, **kw):
        if not isinstance(X, design_matrix.DesignMatrix):
            built.append(X)
        return real(X, *a, **kw)
    monkeypatch.setattr(driver, "as_design", counting)
    monkeypatch.setattr(problem_mod, "as_design", counting)
    cfg = PathConfig(solver=PCDNConfig(P=64, max_outer=40), n_points=5,
                     span=10.0)
    res = run_path(problem, cfg, val_design=Xv, val_y=yv)
    assert all(p.val_accuracy is not None for p in res.points)
    assert len(built) == 1 and built[0] is Xv


# -- shrinking ----------------------------------------------------------------

def test_partition_active_covers_exactly_the_active_set():
    key = jax.random.PRNGKey(3)
    active = jnp.asarray(np.random.default_rng(0).random(50) < 0.3)
    idxs, b_active = B.partition_active(key, active, P=8)
    n_act = int(active.sum())
    assert int(b_active) == -(-n_act // 8)
    flat = np.asarray(idxs).ravel()
    real = flat[flat < 50]
    assert sorted(real) == sorted(np.flatnonzero(np.asarray(active)))
    # every real index lives in the leading b_active bundles
    lead = np.asarray(idxs)[:int(b_active)].ravel()
    assert sorted(lead[lead < 50]) == sorted(real)


def test_shrink_matches_noshrink_full_kkt(data):
    X, y, _ = data
    tol = 1e-4
    base = dict(P=64, max_outer=300, tol_kkt=tol)
    r_ns = solve(make_problem(X, y, c=2.0), PCDNConfig(**base))
    r_sh = solve(make_problem(X, y, c=2.0), PCDNConfig(shrink=True, **base))
    assert r_ns.converged and r_sh.converged
    # same full-set KKT stop, same objective at f32 noise
    assert float(r_sh.history.kkt[-1]) <= tol
    assert r_sh.objective == pytest.approx(r_ns.objective, rel=1e-5)
    # shrinking actually shrank something along the way
    assert int(r_sh.history.n_active.min()) < N
    # history exposes the active-set trajectory; non-shrink stays full
    assert int(r_ns.history.n_active.min()) == N


def test_shrink_recheck_unshrinks_violators(data):
    """recheck_every > 1 must still end at the full-set KKT tolerance."""
    X, y, _ = data
    r = solve(make_problem(X, y, c=3.0),
              PCDNConfig(P=64, max_outer=300, tol_kkt=1e-4, shrink=True,
                         recheck_every=5, shrink_tol=0.05))
    assert r.converged
    assert float(r.history.kkt[-1]) <= 1e-4


# -- vmapped batch solving ----------------------------------------------------

def test_batch_matches_looped_solves(problem, data):
    X, y, _ = data
    cs = [0.7, 1.3, 2.6]
    cfg = PCDNConfig(P=64, max_outer=200, tol_kkt=1e-4)
    bres = solve_batch(problem, cfg, cs)
    assert bool(np.all(np.asarray(bres.converged)))
    for i, c in enumerate(cs):
        r = solve(make_problem(X, y, c=c), cfg)
        assert float(bres.objective[i]) == pytest.approx(r.objective,
                                                         rel=1e-4)
        assert float(bres.kkt[i]) <= 1e-4
        assert int(bres.nnz[i]) == int(jnp.sum(r.w != 0))


def _batch_labels_match_solves(X, y, layout):
    problem = make_problem(X, y, c=1.0, layout=layout)
    rng = np.random.default_rng(7)
    flip = rng.random((2, S)) < 0.2
    ys = np.stack([np.where(flip[i], -y, y) for i in range(2)])
    cfg = PCDNConfig(P=64, max_outer=200, tol_kkt=1e-4)
    bres = solve_batch(problem, cfg, [1.0, 1.0], ys=ys, seeds=[11, 12])
    assert bool(np.all(np.asarray(bres.converged)))
    for i in range(2):
        r = solve(make_problem(X, ys[i], c=1.0, layout=layout),
                  PCDNConfig(P=64, max_outer=200, tol_kkt=1e-4,
                             seed=11 + i))
        assert float(bres.objective[i]) == pytest.approx(r.objective,
                                                         rel=1e-4)


def test_batch_per_problem_labels_and_seeds(data):
    X, y, _ = data
    _batch_labels_match_solves(X, y, "dense")


def test_batch_per_problem_labels_padded_csc(data):
    """Per-problem labels drop the padded-CSC problem's column labels
    (`with_labels`) and the batch gathers its labels at each slab entry;
    the single solves keep theirs and gather only the margins."""
    X, y, _ = data
    _batch_labels_match_solves(X, y, "padded_csc")


def test_batch_warm_start_freeze_semantics(problem):
    """A problem that starts at its optimum freezes immediately."""
    cfg = PCDNConfig(P=64, max_outer=50, tol_kkt=1e-4)
    r = solve_batch(problem, cfg, [0.8, 1.6])
    again = solve_batch(problem, cfg, [0.8, 1.6],
                        w0=np.asarray(r.w))
    assert bool(np.all(np.asarray(again.converged)))
    assert int(np.max(np.asarray(again.n_outer))) <= 2
    np.testing.assert_allclose(np.asarray(again.objective),
                               np.asarray(r.objective), rtol=1e-6)


# -- CLI drivers --------------------------------------------------------------

def test_path_cli_smoke(tmp_path):
    from repro.launch import path as launch_path
    out = tmp_path / "path.json"
    payload = launch_path.main([
        "--dataset", "a9a", "--scale", "0.02", "--points", "4",
        "--span", "10", "--P", "16", "--max-outer", "60",
        "--tol", "1e-3", "--out", str(out), "--save-weights"])
    assert out.exists() and (tmp_path / "path.json.weights.npy").exists()
    assert len(payload["points"]) == 4
    assert payload["best_c"] is not None


def test_path_cli_splits_csr_file_and_stops_relative(tmp_path, monkeypatch):
    """A sparse file dataset loads as CSR and its trailing --val-frac rows
    are held out; --stop relative --shrink builds one training problem,
    its backend once at the smallest stop, and sweeps to each point's
    stop."""
    from repro.data import save_libsvm
    from repro.data.libsvm import CSRMatrix
    from repro.launch import common
    from repro.launch import path as launch_path
    built = []
    real = common.make_problem

    def counting(X, *a, **kw):
        built.append(X)
        return real(X, *a, **kw)
    monkeypatch.setattr(common, "make_problem", counting)
    X, y, _ = make_classification(200, 96, sparsity=0.9, corr=0.3, seed=8)
    svm = tmp_path / "d.svm"
    save_libsvm(str(svm), CSRMatrix.from_dense(X), y)
    payload = launch_path.main([
        "--dataset", str(svm), "--layout", "padded_csc", "--val-frac",
        "0.25", "--points", "4", "--span", "10", "--P", "16",
        "--max-outer", "100", "--tol", "1e-2", "--stop", "relative",
        "--shrink"])
    points = payload["points"]
    assert len(built) == 1 and isinstance(built[0], CSRMatrix)
    assert built[0].shape == (150, 96)
    assert payload["best_c"] is not None
    assert all(p["converged"] for p in points)
    # scored on the 50 held-out rows: counts of right answers
    for p in points:
        assert p["val_accuracy"] * 50 == pytest.approx(
            round(p["val_accuracy"] * 50), abs=1e-9)
    kkt0 = np.asarray([p["c"] for p in points]) / payload["c_max"] - 1.0
    tols = 1e-2 * np.where(kkt0 > 0, kkt0, kkt0[1])
    assert all(p["kkt"] <= t for p, t in zip(points, tols))


def test_solve_cli_warm_start_roundtrip(tmp_path):
    from repro.launch import solve as launch_solve
    out = tmp_path / "solve.json"
    launch_solve.main(["--dataset", "a9a", "--solver", "pcdn", "--P", "16",
                       "--max-outer", "40", "--out", str(out)])
    # the report's "w" feeds --warm-start; warm resume converges fast
    f2 = launch_solve.main(["--dataset", "a9a", "--solver", "pcdn",
                            "--P", "16", "--max-outer", "40",
                            "--warm-start", str(out)])
    import json
    f1 = json.load(open(out))["objective"]
    assert f2 == pytest.approx(f1, rel=1e-4)
