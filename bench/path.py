"""Traffic kind "path": warm-started regularization paths, back to back.

Set-up builds the configuration's data set from its own fixed data seed,
trains on its first `train_rows` rows and holds out the rest, each part
re-padded to its own widest column (`split_rows`). The backend is the
one `repro.launch.path --stop relative --shrink` builds
(`repro.launch.common.make_path_backend` from the CLI's own defaults and
the mix's `solver_args`): its un-shrink threshold sits at the sweep's
smallest stop (`PathConfig.unshrink_tol`). A short sweep
warms its compiled outer step, the margin refresh and the held-out
scoring.

The window then runs whole sweeps through `repro.path.driver.run_path`:
`points` geometric points from the analytic c_max to `span` x c_max,
warm starts, each point stopped at eps x KKT_{c_i}(w = 0), held-out
accuracy at every point and the best c picked from it. Each sweep draws
its partition key from the mix's fixed set of `partitions` keys, in
cycles ordered by the seed, until `seconds` have passed and two sweeps
have run; the sweep running at that moment finishes and counts. `solve_s`
is seconds per whole sweep. With `trace_s` the trace ends at the first sweep boundary
past it, and never before two whole sweeps.

After the window every point is checked against the float64 reference,
which recomputes F(w), KKT(w), the margins X w and the held-out scores
from the data and w alone:

- `kkt_gap`: over every point of every sweep, the gap between the KKT
  value the engine stopped on and KKT_{c_i}(w_i), over the point's stop;
- `z_err`: the margins the program maintains, after each sweep's last
  point, against X w, over the margins' scale;
- `unstopped`: points that ended other than at their stop;
- `val_err`: over every point, the gap between the held-out accuracy the
  program reported and the float64 accuracy of the same w: the share of
  held-out rows by which the two counts of right answers differ;
- `wrong_pick`: sweeps whose best index is not the float64 pick, the
  highest float64 held-out accuracy with ties to the sparser model.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import time

import numpy as np

from bench import data, reference, solve

OUTER_PROGRAM = "jit_outer"     # the jitted `make_path_outer` step


def split_rows(X: data.Padded, cut: int) -> tuple:
    """-> (rows [0, cut), rows [cut, s)) of a padded layout, each renumbered
    from 0 and re-padded to its own widest column, with its own sentinel.
    A column keeps its entries in their order."""
    s, n = X.shape
    parts = []
    for lo, hi in ((0, cut), (cut, s)):
        keep = (X.col_rows >= lo) & (X.col_rows < hi)
        k = max(1, int(keep.sum(axis=1).max()))
        slot = np.cumsum(keep, axis=1) - 1          # place within the part
        col, j = np.nonzero(keep)
        rows = np.full((n, k), hi - lo, np.int32)
        vals = np.zeros((n, k), np.float32)
        rows[col, slot[col, j]] = X.col_rows[col, j] - lo
        vals[col, slot[col, j]] = X.col_vals[col, j]
        parts.append(data.Padded(col_rows=rows, col_vals=vals,
                                 shape=(hi - lo, n)))
    return tuple(parts)


def accuracy64(X, y, w) -> float:
    """Held-out accuracy of sign(X w) in float64, a zero margin read +1."""
    z = reference.matvec(X, np.asarray(w, np.float64))
    return float(np.mean(np.where(z < 0, -1.0, 1.0) == y))


def check(Xtr, ytr, Xval, yval, sweeps) -> tuple[dict, list]:
    """The compared numbers over every point of the window's sweeps, and
    a record per sweep. `sweeps` holds (PathResult, stops). Points whose
    c and w repeat a point already checked reuse its float64 values."""
    worst = {"z_err": 0.0, "kkt_gap": 0.0, "unstopped": 0, "val_err": 0.0,
             "wrong_pick": 0, "f_rel": 0.0}
    X64, V64 = reference.as_float64(Xtr), reference.as_float64(Xval)
    yval = np.asarray(yval, np.float64)
    seen = {}
    per_sweep = []
    for res, stops in sweeps:
        rec = {"n_outer": [], "n_active": [], "between_s": [],
               "stopped": [], "nnz": [], "val_accuracy": [],
               "best_index": res.best_index}
        pick64 = []
        for i, p in enumerate(res.points):
            w = np.asarray(res.weights[i], np.float64)
            key = (p.c, hashlib.sha1(w.tobytes()).hexdigest())
            if key not in seen:
                f64, kkt64, z64 = reference.objective_kkt(X64, ytr, w, p.c)
                seen[key] = (f64, kkt64, z64, accuracy64(V64, yval, w))
            f64, kkt64, z64, acc64 = seen[key]
            stopped = bool(p.converged and p.kkt <= stops[i])
            worst["kkt_gap"] = max(worst["kkt_gap"],
                                   abs(p.kkt - kkt64) / stops[i])
            worst["f_rel"] = max(worst["f_rel"],
                                 abs(p.objective - f64) / abs(f64))
            worst["val_err"] = max(
                worst["val_err"], math.inf if p.val_accuracy is None
                else abs(p.val_accuracy - acc64))
            worst["unstopped"] += int(not stopped)
            pick64.append((-acc64, p.nnz, i))
            for k, v in (("n_outer", p.n_outer), ("stopped", stopped),
                         ("n_active", list(p.n_active)),
                         ("between_s", p.between_s), ("nnz", p.nnz),
                         ("val_accuracy", p.val_accuracy)):
                rec[k].append(v)
        worst["wrong_pick"] += int(res.best_index != min(pick64)[2])
        z = np.asarray(res.last_state.z, np.float64)
        worst["z_err"] = max(
            worst["z_err"], float(np.max(np.abs(z - z64)))
            / max(1.0, float(np.max(np.abs(z64))))
            if z.shape == z64.shape else math.inf)
        per_sweep.append(rec)
    for k in ("z_err", "kkt_gap", "val_err", "f_rel"):
        if not math.isfinite(worst[k]):
            worst[k] = math.inf
    return worst, per_sweep


def run(config: dict, traffic: dict, seed: int, seconds: float, tracer,
        t_setup0: float, control: bool = False) -> dict:
    from repro.engine import LocalBackend
    from repro.launch import common
    from repro.path import PathConfig, run_path

    ns = solve.cli_args(traffic["solver_args"], solve.solve_seed(seed, 0),
                        control)
    path_cfg = PathConfig(
        solver=common.build_pcdn_config(ns, tol_kkt=float(traffic["eps"])),
        n_points=traffic["points"], span=float(traffic["span"]),
        stop="relative")
    with tracer.span("bench.data"):
        X, y = data.build(config["data"], config["data"]["seed"])
        cut = config["data"]["train_rows"]
        Xtr, Xval = split_rows(X, cut)
        ytr, yval = y[:cut], y[cut:]
        del X
    with tracer.span("bench.backend"):
        backend, prob = common.make_path_backend(ns, Xtr, ytr,
                                                 config["loss"], path_cfg)
        c_max = backend.c_max()
        warm = dataclasses.replace(path_cfg, n_points=2, solver=dataclasses
                                   .replace(path_cfg.solver,
                                            max_outer=traffic["warm_outer"]))
        run_path(None, warm, backend=backend, val_design=Xval, val_y=yval)
    outer = backend.outer
    stops = path_cfg.stops(c_max)

    setup_s = time.perf_counter() - t_setup0
    sweeps, ends, used = [], [], []
    keys = solve.partition_keys(traffic, seed)
    with tracer.window():
        t0 = time.perf_counter()
        while True:
            used.append(next(keys))
            b = LocalBackend(prob, dataclasses.replace(backend.cfg,
                                                       seed=used[-1]),
                             outer=outer)
            with tracer.span("bench.sweep"):
                res = run_path(None, path_cfg, backend=b, val_design=Xval,
                               val_y=yval)
            sweeps.append((res, stops))
            t_end = time.perf_counter()
            ends.append(t_end - t0)
            if len(sweeps) >= 2:
                tracer.lap()
            if t_end - t0 >= seconds and len(sweeps) >= 2:
                break
    memory = tracer.memory_peak()

    checks, per_sweep = check(Xtr, ytr, Xval, yval, sweeps)
    failed = sum(not all(r["stopped"]) for r in per_sweep)
    return {
        "setup_s": setup_s,
        "e2e": {"solve_s": (t_end - t0) / len(sweeps)},
        "attempted": len(sweeps),
        "failed": failed,
        "checks": checks,
        "memory_peak_bytes": memory,
        "notes": {"eps": traffic["eps"], "c_max": c_max,
                  "stops": stops.tolist(), "ends_s": ends,
                  "partition_keys": used,
                  "n_outer": [sum(r["n_outer"]) for r in per_sweep],
                  "first_sweep": per_sweep[0]},
        "layer": {"sweeps": per_sweep, "n_features": Xtr.shape[1],
                  "outer_program": OUTER_PROGRAM},
    }
