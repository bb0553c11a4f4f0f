"""Traffic kind "solve": cold solves to a relative KKT stop, back to back.

Set-up builds the configuration's data set (from its own fixed data
seed: one deployment solves one data set), the backend as the solve CLI
builds it (`repro.launch.common.make_backend` from the CLI's own
defaults and the mix's `solver_args`), and warms its compiled outer
step with a short solve. The window then runs cold solves through the
engine's loop (`repro.engine.loop.run_outer_loop`, which
`engine.loop.solve` wraps), each from w = 0 with a partition key from
`partition_keys`, until `seconds` have passed; the solve running at
that moment finishes and counts. Every seed runs the mix's fixed set of
keys, in another order. Every solve stops at the engine's
full-set KKT test with tol = eps * KKT(w = 0), KKT(w = 0) computed in
float64 on the host.

Each solve's answer is checked after the window against the float64
reference, which recomputes the margins X w, F(w) and KKT(w) from the
data and w alone:

- `z_err`: the widest gap between the margins the program maintains
  and X w, over the margins' scale;
- `kkt_gap`: the gap between the KKT value the engine stopped on and
  KKT(w), over the stop tolerance;
- `unstopped`: solves that ended other than at the stop (the cap, a
  divergence, a non-finite value);
- `f_rel`, the gap between the reported objective and F(w) over F(w),
  is printed as a reading but not compared: the program's own bf16
  storage reads no higher there than sound runs do.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import time

import numpy as np

from bench import data, reference, work

OUTER_PROGRAM = "jit_outer"     # the jitted `make_path_outer` step


def solve_seed(seed: int, index: int) -> int:
    """31-bit partition seed of solve `index` of a run."""
    return int(np.random.SeedSequence([seed, index]).generate_state(1)[0]
               & 0x7FFFFFFF)


def partition_keys(traffic: dict, seed: int):
    """The partition key of each solve of a run, in order: the mix's fixed
    set of `partitions` keys (drawn from `partition_seed`), the same for
    every seed, run in cycles, each cycle in an order drawn from the
    seed. Every full cycle is the same work."""
    k = traffic["partitions"]
    keys = [solve_seed(traffic["partition_seed"], j) for j in range(k)]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    while True:
        for j in rng.permutation(k):
            yield keys[j]


def cli_args(solver_args, seed: int, control: bool) -> argparse.Namespace:
    """The solve CLI's flags at their defaults, with the mix's overrides.
    The control runs the program's own bf16 design storage."""
    from repro.launch import common
    ap = argparse.ArgumentParser()
    common.add_solver_args(ap)
    common.add_backend_args(ap)
    ns = ap.parse_args(list(solver_args) + ["--seed", str(seed)])
    if control:
        ns.dtype = "bf16"
    return ns


def check(X, y, c: float, tol: float, solves) -> tuple[dict, list]:
    """The compared numbers over every solve of the window, and a
    record per solve. `solves` holds (margins z, SolveResult)."""
    worst = {"z_err": 0.0, "kkt_gap": 0.0, "f_rel": 0.0, "unstopped": 0}
    per_solve = []
    X64 = reference.as_float64(X)
    for z, res in solves:
        w = np.asarray(res.w, np.float64)
        z = np.asarray(z, np.float64)
        f64, kkt64, z64 = reference.objective_kkt(X64, y, w, c)
        kkt = float(res.history.kkt[-1])
        z_err = (float(np.max(np.abs(z - z64)))
                 / max(1.0, float(np.max(np.abs(z64))))
                 if z.shape == z64.shape else math.inf)
        stopped = bool(res.converged and not res.diverged)
        worst["z_err"] = max(worst["z_err"], z_err)
        worst["kkt_gap"] = max(worst["kkt_gap"], abs(kkt - kkt64) / tol)
        worst["f_rel"] = max(worst["f_rel"],
                             abs(res.objective - f64) / abs(f64))
        worst["unstopped"] += int(not stopped)
        per_solve.append({"n_outer": int(res.n_outer), "stopped": stopped,
                          "objective": float(res.objective), "f64": f64,
                          "kkt": kkt, "kkt64": kkt64})
    for k in ("z_err", "kkt_gap", "f_rel"):
        if not math.isfinite(worst[k]):
            worst[k] = math.inf
    return worst, per_solve


def run(config: dict, traffic: dict, seed: int, seconds: float, tracer,
        t_setup0: float, control: bool = False) -> dict:
    from repro.engine import LocalBackend
    from repro.engine import loop as engine_loop
    from repro.launch import common

    c = float(config["c"])
    with tracer.span("bench.data"):
        X, y = data.build(config["data"], config["data"]["seed"])
        kkt0 = reference.kkt_at_zero(X, y, c)
    tol = float(traffic["eps"]) * kkt0
    ns = cli_args(traffic["solver_args"], solve_seed(seed, 0), control)
    with tracer.span("bench.backend"):
        backend, prob = common.make_backend(ns, X, y, c, config["loss"])
        engine_loop.solve(backend, c, max_outer=traffic["warm_outer"],
                          tol_kkt=tol)
    outer = backend.outer

    setup_s = time.perf_counter() - t_setup0
    solves, ends, used = [], [], []
    keys = partition_keys(traffic, seed)
    with tracer.window():
        t0 = time.perf_counter()
        while True:
            used.append(next(keys))
            cfg = dataclasses.replace(backend.cfg, seed=used[-1])
            b = LocalBackend(prob, cfg, outer=outer)
            with tracer.span("bench.solve"):
                state, res = engine_loop.run_outer_loop(
                    outer, b.init_state(), c,
                    max_outer=traffic["max_outer"], tol_kkt=tol)
            solves.append((state.z, res))
            t_end = time.perf_counter()
            ends.append(t_end - t0)
            tracer.lap()
            if t_end - t0 >= seconds:
                break
    memory = tracer.memory_peak()

    checks, per_solve = check(X, y, c, tol, solves)
    s, n = X.shape
    return {
        "setup_s": setup_s,
        "e2e": {"solve_s": (t_end - t0) / len(solves)},
        "attempted": len(solves),
        "failed": checks["unstopped"],
        "checks": checks,
        "memory_peak_bytes": memory,
        "notes": {"eps": traffic["eps"], "kkt0": kkt0, "tol_kkt": tol,
                  "ends_s": ends, "partition_keys": used,
                  "n_outer": [p["n_outer"] for p in per_solve]},
        "layer": {"solves": per_solve,
                  "outer_program": OUTER_PROGRAM,
                  "work": work.outer_iteration_work(
                      s=s, n=n, P=backend.cfg.P, nnz=data.nnz(X),
                      dense=not isinstance(X, data.Padded))},
    }
