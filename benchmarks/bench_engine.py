"""Unified-engine benchmark -> BENCH_engine.json.

    PYTHONPATH=src python benchmarks/bench_engine.py [--smoke]

The headline capability the engine refactor unlocks (DESIGN.md section
9): a WARM-STARTED regularization-path sweep running on the SHARDED
backend — one mesh placement, one compiled dynamic-c shard_map program,
(w, z, active) chained across the c-grid — versus the pre-engine
deployment of one cold sharded solve per grid point (fresh placement +
compile + zero-start every time, which is what `solve_sharded` alone
could do). Three traversals of the SAME grid, every point stopping at
the same full-set KKT tolerance:

    cold_solves   one `solve_sharded` per point (per-point placement +
                  compile; the seed deployment baseline)
    cold_shared   state reset per point, but ONE placed backend and ONE
                  compiled program — isolates warm-start value from
                  compile/placement amortization
    warm_shrink   the engine sweep: warm starts + active-set shrinking
                  on the mesh (the flagship config)

Runs on 8 forced host devices (mesh (2, 4) data x model) so it exercises
the real collective schedule; set XLA_FLAGS yourself to override. It
refuses to run on an accelerator backend (run it with JAX_PLATFORMS=cpu).

Writes BENCH_engine.json at the repo root and a copy under
benchmarks/results/.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import time         # noqa: E402

import numpy as np  # noqa: E402
import jax          # noqa: E402

from repro.utils.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import PCDNConfig                       # noqa: E402
from repro.core.sharded import solve_sharded            # noqa: E402
from repro.data import make_classification              # noqa: E402
from repro.engine import (ShardedBackend,               # noqa: E402
                          ShardedPCDNConfig)
from repro.path import PathConfig, run_path             # noqa: E402
from repro.launch.mesh import make_mesh                  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + short grid (CI)")
    args = ap.parse_args(argv)
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"bench_engine measures a (2, 4) mesh of forced host devices; "
            f"this process found {jax.default_backend()} devices, on "
            f"which that mesh is not what it would measure. Run it with "
            f"JAX_PLATFORMS=cpu.")
    enable_compile_cache()

    if args.smoke:
        s, n, P_local, n_points, span, max_outer = 600, 1024, 16, 5, 30.0, 300
    else:
        s, n, P_local, n_points, span, max_outer = 2000, 4096, 32, 12, 100.0, 600
    tol = 1e-3
    mesh = make_mesh((2, 4), ("data", "model"))
    X, y, _ = make_classification(s, n, sparsity=0.99, corr=0.2, seed=1)

    scfg = ShardedPCDNConfig(P_local=P_local, c=1.0, tol_kkt=tol,
                             shrink=True)
    # stop parameters for the sweep (P is informational here — execution
    # comes from scfg; see PathConfig docstring)
    solver = PCDNConfig(P=P_local * mesh.shape["model"],
                        max_outer=max_outer, tol_kkt=tol)
    pcfg = PathConfig(solver=solver, n_points=n_points, span=span)

    # --- engine: place + compile once, then the warm shrinking sweep ----
    t0 = time.perf_counter()
    backend = ShardedBackend(X, y, mesh, scfg)
    st = backend.init_state()   # trigger placement
    _ = jax.block_until_ready(backend.outer(
        *st, np.asarray(True), np.asarray(1.0, np.float32)))  # compile
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    warm = run_path(None, pcfg, backend=backend)
    warm_s = time.perf_counter() - t0
    cs = warm.cs

    # --- ablation: same placed backend + program, state reset per point
    t0 = time.perf_counter()
    cold_shared = run_path(None, dataclasses.replace(pcfg,
                                                     warm_start=False),
                           backend=backend)
    cold_shared_s = time.perf_counter() - t0

    # --- baseline: one cold solve_sharded per point (fresh placement +
    # compile each — the pre-engine per-process deployment)
    t0 = time.perf_counter()
    cold_iters, cold_conv, cold_objs, cold_kkts = 0, True, [], []
    for c in cs:
        w, f, conv, k, hist = solve_sharded(
            X, y, mesh, dataclasses.replace(scfg, c=float(c), shrink=False),
            max_outer=max_outer, tol_kkt=tol)
        cold_iters += k
        cold_conv &= conv
        cold_objs.append(f)
        cold_kkts.append(hist["kkt"][-1])
    cold_solves_s = time.perf_counter() - t0

    warm_objs = np.array([p.objective for p in warm.points])
    cold_objs = np.array(cold_objs)
    engine_s = warm_s + setup_s
    payload = {
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "mesh": {"data": 2, "model": 4},
        "problem": {"s": s, "n": n, "sparsity": 0.99,
                    "P_local": P_local},
        "grid": {"n_points": n_points, "span": span,
                 "c_max": float(warm.c_max), "tol_kkt": tol},
        "warm_shrink_seconds_incl_setup": engine_s,
        "warm_shrink_sweep_seconds": warm_s,
        "setup_seconds": setup_s,
        "warm_iters": int(sum(p.n_outer for p in warm.points)),
        "warm_all_converged": all(p.converged for p in warm.points),
        "warm_max_point_kkt": float(max(p.kkt for p in warm.points)),
        "cold_shared_program_seconds": cold_shared_s,
        "cold_shared_iters": int(sum(p.n_outer
                                     for p in cold_shared.points)),
        "cold_solves_seconds": cold_solves_s,
        "cold_solves_iters": int(cold_iters),
        "cold_solves_all_converged": bool(cold_conv),
        "cold_solves_max_point_kkt": float(np.max(cold_kkts)),
        "speedup_engine_vs_cold_solves": cold_solves_s / engine_s,
        "speedup_warm_vs_cold_shared": cold_shared_s / warm_s,
        "objective_max_rel_diff_vs_cold": float(np.max(
            np.abs(warm_objs - cold_objs) / np.abs(cold_objs))),
    }
    print(f"sharded warm+shrink sweep {engine_s:.1f}s (setup {setup_s:.1f}s)"
          f" vs {n_points} cold sharded solves {cold_solves_s:.1f}s -> "
          f"{payload['speedup_engine_vs_cold_solves']:.1f}x "
          f"(shared-program cold {cold_shared_s:.1f}s; warm iters "
          f"{payload['warm_iters']} vs cold {payload['cold_solves_iters']})",
          flush=True)
    print(f"objective max rel diff vs cold "
          f"{payload['objective_max_rel_diff_vs_cold']:.1e}", flush=True)

    os.makedirs(RESULTS_DIR, exist_ok=True)
    for path in (os.path.join(REPO_ROOT, "BENCH_engine.json"),
                 os.path.join(RESULTS_DIR, "BENCH_engine.json")):
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
    print("wrote BENCH_engine.json")
    return payload


if __name__ == "__main__":
    main()
