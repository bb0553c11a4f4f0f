"""Regularization-path engine benchmark -> BENCH_path.json.

    PYTHONPATH=src python benchmarks/bench_path.py [--smoke]

Two sections on one 99.9%-sparse synthetic problem (DESIGN.md §8):

  * warm_vs_cold + shrink_vs_noshrink — the SAME 20-point c-grid
    traversed four ways, every traversal stopping at the same per-point
    full-set KKT tolerance:

      cold_solves   20 independent cold `pcdn.solve` calls, one per grid
                    point, each paying its own XLA compile — the seed
                    deployment baseline (`repro.launch.solve` today: one
                    cold solve per process);
      cold_shared   state reset per point but one compiled dynamic-c
                    program — isolates warm-start value from compile
                    amortization;
      warm          the warm-started sweep (state chained);
      warm_shrink   the path engine's flagship config: warm starts +
                    PCDNConfig(shrink=True).

    The headline `speedup_engine_vs_cold_solves` compares warm_shrink
    against cold_solves; shrink_vs_noshrink (warm vs warm_shrink) shows
    shrinking cutting path wall-time further, with the max relative
    final-objective deviation pinned at f32 noise.

  * batch_vs_looped — the serving workload: B bootstrap label-resamples
    of the same design at one production c (CV-fold shape: similar
    per-problem difficulty, so the lockstep batch wastes almost nothing)
    solved simultaneously by the vmapped multi-problem solver, vs a
    Python loop of cold `pcdn.solve` (fresh compile each, the
    per-process baseline) and vs a loop of B=1 calls through one shared
    compiled batch program. Dense layout: vmapping turns every bundle
    reduction into a batched GEMM, which is where the throughput comes
    from even on CPU. Reports problems/second. (A wide c-grid on the
    sparse layout is the batch engine's WORST case — dissimilar
    iteration counts + gather-bound math; the sweep sections cover that
    regime with warm starts instead.)

Writes BENCH_path.json at the repo root and a copy under
benchmarks/results/.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.utils.compile_cache import enable_compile_cache
from repro.core import PCDNConfig, make_problem, pcdn, solve
from repro.data import make_classification
from repro.path import PathConfig, c_grid, run_path, solve_batch
from repro.path.batch import make_batch_outer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


def _warmed_outer(prob, solver):
    """Compile a path outer before timing; returns (outer, compile_s)."""
    outer = pcdn.make_path_outer(prob, solver)
    n, s = prob.n_features, prob.n_samples
    t0 = time.perf_counter()
    out = outer(jnp.zeros((n,), prob.dtype), jnp.zeros((s,), prob.dtype),
                jax.random.PRNGKey(0), jnp.ones((n,), bool),
                jnp.asarray(True), jnp.asarray(1.0, prob.dtype))
    jax.block_until_ready(out)
    return outer, time.perf_counter() - t0


def bench_sweeps(X, y, solver, n_points, span):
    """Traverse one c-grid four ways -> (warm_vs_cold, shrink_vs_noshrink)."""
    prob = make_problem(X, y, c=1.0, layout="padded_csc")
    cs = c_grid(prob.c_max(), n_points=n_points, span=span)
    cfg = PathConfig(solver=solver, n_points=n_points, span=span)

    # warm path: one compile + the chained sweep
    outer, compile_s = _warmed_outer(prob, solver)
    t0 = time.perf_counter()
    warm = run_path(prob, cfg, outer=outer)
    warm_s = time.perf_counter() - t0

    # the engine's flagship config: warm starts + active-set shrinking
    shrink_solver = dataclasses.replace(solver, shrink=True)
    shrink_outer, shrink_compile_s = _warmed_outer(prob, shrink_solver)
    t0 = time.perf_counter()
    warm_shrink = run_path(prob, dataclasses.replace(cfg,
                                                     solver=shrink_solver),
                           outer=shrink_outer)
    warm_shrink_s = time.perf_counter() - t0

    # ablation: same single program, state reset at every point
    t0 = time.perf_counter()
    cold_shared = run_path(prob, dataclasses.replace(cfg, warm_start=False),
                           outer=outer)
    cold_shared_s = time.perf_counter() - t0

    # baseline: 20 independent cold solves (fresh jit each — the one-
    # solve-per-process deployment this subsystem replaces)
    t0 = time.perf_counter()
    cold_iters, cold_conv, cold_objs = 0, True, []
    for c in cs:
        res = solve(make_problem(X, y, c=float(c), layout="padded_csc"),
                    solver)
        cold_iters += res.n_outer
        cold_conv &= res.converged
        cold_objs.append(res.objective)
    cold_solves_s = time.perf_counter() - t0

    cold_objs = np.array(cold_objs)
    warm_objs = np.array([p.objective for p in warm.points])
    shrink_objs = np.array([p.objective for p in warm_shrink.points])
    engine_s = warm_shrink_s + shrink_compile_s
    warm_vs_cold = {
        "n_points": n_points, "span": span, "c_max": prob.c_max(),
        "tol_kkt": solver.tol_kkt,
        "warm_shrink_seconds_incl_compile": engine_s,
        "warm_seconds_incl_compile": warm_s + compile_s,
        "compile_seconds": compile_s,
        "warm_iters": int(sum(p.n_outer for p in warm.points)),
        "warm_all_converged": all(p.converged for p in warm.points),
        "cold_solves_seconds": cold_solves_s,
        "cold_solves_iters": int(cold_iters),
        "cold_solves_all_converged": bool(cold_conv),
        "cold_shared_program_seconds": cold_shared_s,
        "cold_shared_iters": int(sum(p.n_outer for p in cold_shared.points)),
        "speedup_engine_vs_cold_solves": cold_solves_s / engine_s,
        "speedup_warm_only_vs_cold_solves":
            cold_solves_s / (warm_s + compile_s),
        "speedup_warm_only_vs_cold_shared": cold_shared_s / warm_s,
        "objective_max_rel_diff_vs_cold": float(np.max(
            np.abs(warm_objs - cold_objs) / np.abs(cold_objs))),
    }
    shrink_vs_noshrink = {
        "noshrink_seconds": warm_s,
        "shrink_seconds": warm_shrink_s,
        "speedup": warm_s / warm_shrink_s,
        "shrink_all_converged": all(p.converged
                                    for p in warm_shrink.points),
        "final_full_set_kkt": float(warm_shrink.points[-1].kkt),
        "objective_max_rel_diff": float(np.max(
            np.abs(shrink_objs - warm_objs) / np.abs(warm_objs))),
    }
    print(f"path engine (warm+shrink) {engine_s:.1f}s vs "
          f"{n_points} cold solves {cold_solves_s:.1f}s -> "
          f"{warm_vs_cold['speedup_engine_vs_cold_solves']:.1f}x "
          f"(warm only {warm_s + compile_s:.1f}s, "
          f"shared-program cold {cold_shared_s:.1f}s)", flush=True)
    print(f"shrink {warm_shrink_s:.1f}s vs noshrink {warm_s:.1f}s -> "
          f"{shrink_vs_noshrink['speedup']:.2f}x, obj rel diff "
          f"{shrink_vs_noshrink['objective_max_rel_diff']:.1e}", flush=True)
    return warm_vs_cold, shrink_vs_noshrink


def bench_batch(X, y, solver, batch, flip_frac=0.1, seed=3):
    prob = make_problem(X, y, c=1.0, layout="dense")
    c = 3.5 * prob.c_max()          # a mid-path production operating point
    cs = [float(c)] * batch
    rng = np.random.default_rng(seed)
    flip = rng.random((batch, prob.n_samples)) < flip_frac
    ys = np.stack([np.where(flip[i], -y, y)
                   for i in range(batch)]).astype(np.float32)

    outer = make_batch_outer(prob, solver, batched_labels=True)
    _ = solve_batch(prob, dataclasses.replace(solver, max_outer=1), cs,
                    ys=ys, outer=outer)                # compile
    t0 = time.perf_counter()
    bres = solve_batch(prob, solver, cs, ys=ys, outer=outer)
    batched_s = time.perf_counter() - t0

    # baseline 1: one cold pcdn.solve per fold, fresh compile each
    t0 = time.perf_counter()
    loop_objs = []
    for i in range(batch):
        res = solve(make_problem(X, ys[i], c=float(c)), solver)
        loop_objs.append(res.objective)
    looped_s = time.perf_counter() - t0

    # baseline 2: sequential folds through ONE compiled program (B=1
    # calls into a shared batch outer — compile paid once, no vmap win)
    outer1 = make_batch_outer(prob, solver, batched_labels=True)
    _ = solve_batch(prob, dataclasses.replace(solver, max_outer=1),
                    cs[:1], ys=ys[:1], outer=outer1)   # compile
    t0 = time.perf_counter()
    for i in range(batch):
        solve_batch(prob, solver, cs[:1], ys=ys[i:i + 1], outer=outer1)
    looped_shared_s = time.perf_counter() - t0

    obj_rel = float(np.max(np.abs(np.asarray(bres.objective) -
                                  np.array(loop_objs)) /
                           np.abs(loop_objs)))
    row = {
        "batch": batch, "c": float(c), "flip_frac": flip_frac,
        "layout": "dense",
        "batched_seconds": batched_s,
        "looped_cold_solves_seconds": looped_s,
        "looped_shared_program_seconds": looped_shared_s,
        "batched_problems_per_second": batch / batched_s,
        "looped_problems_per_second": batch / looped_s,
        "throughput_gain_vs_cold_solves": looped_s / batched_s,
        "throughput_gain_vs_shared_loop": looped_shared_s / batched_s,
        "all_converged": bool(np.all(np.asarray(bres.converged))),
        "objective_max_rel_diff": obj_rel,
    }
    print(f"batched {batch} folds {batched_s:.1f}s vs looped cold "
          f"{looped_s:.1f}s (shared-program loop {looped_shared_s:.1f}s) "
          f"-> {row['throughput_gain_vs_cold_solves']:.1f}x / "
          f"{row['throughput_gain_vs_shared_loop']:.1f}x, obj rel diff "
          f"{obj_rel:.1e}", flush=True)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + short grid (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        s, n, P, n_points, span, batch = 600, 2048, 128, 6, 30.0, 4
        max_outer = 400
    else:
        s, n, P, n_points, span, batch = 3000, 8192, 256, 20, 300.0, 16
        max_outer = 1000

    X, y, _ = make_classification(s, n, sparsity=0.999, corr=0.2, seed=1)
    solver = PCDNConfig(P=P, max_outer=max_outer, tol_kkt=1e-3)

    warm_vs_cold, shrink_vs_noshrink = bench_sweeps(X, y, solver,
                                                    n_points, span)
    payload = {
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "problem": {"s": s, "n": n, "sparsity": 0.999, "P": P},
        "warm_vs_cold": warm_vs_cold,
        "shrink_vs_noshrink": shrink_vs_noshrink,
        "batch_vs_looped": bench_batch(X, y, solver, batch),
    }

    os.makedirs(RESULTS_DIR, exist_ok=True)
    for path in (os.path.join(REPO_ROOT, "BENCH_path.json"),
                 os.path.join(RESULTS_DIR, "BENCH_path.json")):
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
    print("wrote BENCH_path.json")
    return payload


if __name__ == "__main__":
    main()
