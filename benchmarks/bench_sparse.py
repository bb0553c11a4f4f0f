"""Dense vs padded-CSC bundle-step benchmark -> BENCH_sparse.json.

    PYTHONPATH=src python benchmarks/bench_sparse.py [--quick] [--no-big]

Per sparsity level (0.9 / 0.99 / 0.999) on the same synthetic problem:

  * bundle-step wall time for both backends (one jitted outer iteration
    = b bundle steps, timed after warm-up, divided by b)
  * memory: design-matrix resident bytes + per-bundle transient slab
    bytes (the two quantities the backend choice actually changes)
  * objective-trajectory max relative deviation dense vs sparse over a
    short PCDN run (equivalence evidence at bench scale)

Plus the "big" certificate: a 99.9%-sparse 20k x 50k problem (nnz/col
<= 64) generated directly in padded-CSC — the dense (s, n) form would be
~4 GB and is never materialized — solved for a few outer iterations via
`pcdn.solve`. Writes BENCH_sparse.json at the repo root and a copy under
benchmarks/results/.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax

from repro.utils.compile_cache import enable_compile_cache
from repro.core import PCDNConfig, make_problem, solve
from repro.core.pcdn import make_outer_iteration
from repro.data import make_classification, make_sparse_classification

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


def _time_outer(problem, cfg, n_timed=5):
    """Median seconds per *bundle step* of the jitted outer iteration."""
    import jax.numpy as jnp
    n = problem.n_features
    b = -(-n // cfg.P)
    w = jnp.zeros((n,), problem.dtype)
    z = problem.margins(w)
    key = jax.random.PRNGKey(0)
    outer = make_outer_iteration(problem, cfg)
    out = outer(w, z, key)                      # compile + warm-up
    jax.block_until_ready(out)
    times = []
    for _ in range(n_timed):
        t0 = time.perf_counter()
        out = outer(w, z, key)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) / b


def _design_bytes(problem):
    d = problem.design
    if hasattr(d, "col_rows"):
        return int(d.col_rows.nbytes + d.col_vals.nbytes)
    return int(d.X.nbytes)


def _slab_bytes(problem, P):
    d = problem.design
    if hasattr(d, "col_rows"):
        return int(P * d.k_max * (4 + d.col_vals.dtype.itemsize))
    return int(problem.n_samples * P * d.X.dtype.itemsize)


def bench_level(s, n, sparsity, P, n_outer_traj=6, seed=0):
    X, y, _ = make_classification(s, n, sparsity=sparsity, corr=0.2,
                                  seed=seed)
    dense = make_problem(X, y, c=1.0)
    sparse = make_problem(X, y, c=1.0, layout="padded_csc")
    cfg = PCDNConfig(P=P, max_outer=n_outer_traj, seed=1)

    t_dense = _time_outer(dense, cfg)
    t_sparse = _time_outer(sparse, cfg)

    rd = solve(dense, cfg)
    rs = solve(sparse, cfg)
    traj_rel = float(np.max(
        np.abs(rd.history.objective - rs.history.objective) /
        np.abs(rd.history.objective)))

    row = {
        "s": s, "n": n, "P": P, "sparsity": sparsity,
        "k_max": int(sparse.design.k_max),
        "bundle_step_seconds": {"dense": t_dense, "padded_csc": t_sparse},
        "speedup": t_dense / t_sparse,
        "design_bytes": {"dense": _design_bytes(dense),
                         "padded_csc": _design_bytes(sparse)},
        "slab_bytes_per_bundle": {"dense": _slab_bytes(dense, P),
                                  "padded_csc": _slab_bytes(sparse, P)},
        "objective_traj_max_rel_diff": traj_rel,
    }
    print(f"sparsity={sparsity}: dense {t_dense*1e3:.2f} ms/bundle, "
          f"padded_csc {t_sparse*1e3:.2f} ms/bundle "
          f"({row['speedup']:.1f}x), k_max={row['k_max']}, "
          f"traj_rel={traj_rel:.2e}", flush=True)
    return row


def bench_big(s=20_000, n=50_000, nnz_per_col=64, P=512, max_outer=3):
    """Sparse-only certificate: dense form (~s*n*4 B) never materialized."""
    pcsc, y, _ = make_sparse_classification(s, n, nnz_per_col=nnz_per_col,
                                            seed=7)
    prob = make_problem(pcsc, y, c=1.0)
    cfg = PCDNConfig(P=P, max_outer=max_outer, seed=0)
    t0 = time.perf_counter()
    res = solve(prob, cfg)
    wall = time.perf_counter() - t0
    row = {
        "s": s, "n": n, "nnz_per_col_max": nnz_per_col, "P": P,
        "k_max": int(prob.design.k_max),
        "design_bytes_padded_csc": _design_bytes(prob),
        "design_bytes_dense_equivalent": int(s) * int(n) * 4,
        "n_outer": int(res.n_outer),
        "objective_start": float(res.history.objective[0]),
        "objective_end": float(res.objective),
        "monotone_decrease": bool(np.all(np.diff(res.history.objective)
                                         <= 1e-6)),
        "wall_seconds": wall,
    }
    print(f"big sparse {s}x{n}: {row['design_bytes_padded_csc']/2**20:.0f} "
          f"MiB sparse vs {row['design_bytes_dense_equivalent']/2**30:.1f} "
          f"GiB dense-equivalent, F {row['objective_start']:.1f} -> "
          f"{row['objective_end']:.1f} in {wall:.1f}s", flush=True)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller shapes (CI smoke)")
    ap.add_argument("--no-big", action="store_true",
                    help="skip the 20k x 50k sparse-only run")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.quick:
        s, n, P = 1024, 2048, 128
    else:
        s, n, P = 4096, 8192, 256

    payload = {
        "backend": jax.default_backend(),
        "shapes": {"s": s, "n": n, "P": P},
        "levels": [bench_level(s, n, sp, P) for sp in (0.9, 0.99, 0.999)],
    }
    if not args.no_big:
        payload["big_sparse_only"] = bench_big(
            **({"s": 4000, "n": 10_000, "P": 256} if args.quick else {}))

    os.makedirs(RESULTS_DIR, exist_ok=True)
    for path in (os.path.join(REPO_ROOT, "BENCH_sparse.json"),
                 os.path.join(RESULTS_DIR, "BENCH_sparse.json")):
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
    print("wrote BENCH_sparse.json")
    return payload


if __name__ == "__main__":
    main()
