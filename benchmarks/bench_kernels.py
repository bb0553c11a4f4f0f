"""Kernel autotuning benchmark: measured roofline placement + the bf16
equivalence study (DESIGN.md section 12).

For every hot kernel x shape cell this driver

  1. times the DEFAULT launch config (the historical hard-coded launch),
  2. runs `kernels.autotune.tune` over the declared search space
     (block sizes along each tileable axis plus the impl axis:
     Pallas kernel vs the jitted jnp oracle) and persists the winner
     into the autotune cache so later solves/serves pick it up,
  3. places the cell on a MEASURED roofline: peak FLOP/s calibrated
     with a large f32 matmul, peak bytes/s with a streaming triad,
     analytic per-kernel flop/byte counts -> compute/memory terms,
     bound classification and attained fraction of the roofline bound,

then runs the bf16-vs-fp32 trajectory study — same problem, same
config, tol_kkt=0 and a fixed outer budget so iteration counts match by
construction — and reports the max objective rel-diff, the number the
CLI's `--dtype bf16` envelope gate (launch/common.py) is calibrated
against.

Output: BENCH_kernels.json at the repo root (the committed headline
artifact tests/test_autotune.py guards) + benchmarks/results/. `--smoke`
runs one tiny cell per kernel with few repeats and writes ONLY
benchmarks/results/BENCH_kernels_smoke.json, so CI smoke never clobbers
the committed headline numbers.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_kernels [--smoke]
        [--strategy exhaustive|hillclimb] [--repeats N] [--no-study]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.common import RESULTS_DIR, emit
from repro.utils.compile_cache import enable_compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADLINE = os.path.join(REPO_ROOT, "BENCH_kernels.json")


# ---------------------------------------------------------------------------
# peak calibration (roofline.calibrate_peaks wraps these for reuse)


def calibrate_peak_flops(n: int = 1024, repeats: int = 5) -> float:
    """Measured f32 matmul peak, FLOP/s. The (n, n) x (n, n) product is
    2n^3 flops and the best-case compute ceiling XLA reaches here."""
    import jax
    import jax.numpy as jnp
    a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.float32)
    f = jax.jit(lambda x: x @ x)
    jax.block_until_ready(f(a))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(f(a))
        best = min(best, time.perf_counter() - t0)
    return 2.0 * n ** 3 / best


def calibrate_peak_bandwidth(mb: int = 64, repeats: int = 5) -> float:
    """Measured streaming bandwidth, bytes/s (read + write of an f32
    buffer: y = x * 2 + 1 moves 8 bytes per element)."""
    import jax
    import jax.numpy as jnp
    n = mb * (1 << 20) // 4
    x = jnp.zeros((n,), jnp.float32)
    f = jax.jit(lambda v: v * 2.0 + 1.0)
    jax.block_until_ready(f(x))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        best = min(best, time.perf_counter() - t0)
    return 8.0 * n / best


# ---------------------------------------------------------------------------
# kernel cells: operand builders + analytic flop/byte counts
#
# Flop counts are the useful math of the kernel's contract (what the XLA
# oracle also has to do), byte counts the once-through traffic of its
# operands/outputs at their STORAGE dtype — the terms a perfectly fused
# implementation cannot avoid, i.e. the roofline bound for the cell.


def _rng(seed=0):
    return np.random.default_rng(seed)


def _cell_bundle(p, k, r, q, dtype):
    import jax.numpy as jnp
    from repro.kernels import ops
    g = _rng(1)
    isz = jnp.dtype(dtype).itemsize
    vals = jnp.asarray(g.standard_normal((p, k)), dtype)
    pos = jnp.asarray(g.integers(0, r, (p, k)), jnp.int32)
    z = jnp.asarray(g.standard_normal((r,)), jnp.float32)
    y = jnp.asarray(g.choice([-1.0, 1.0], (r,)), jnp.float32)
    w = jnp.asarray(0.1 * g.standard_normal((p,)), jnp.float32)
    alphas = jnp.asarray(0.5 ** np.arange(q), jnp.float32)

    def runner(cfg):
        return lambda: ops.pcdn_bundle(vals, pos, z, y, w, alphas, 1.0,
                                       impl=cfg["impl"],
                                       block_q=cfg["block_q"])

    flops = 5 * p * k + 8 * q * r + 3 * q * p   # direction + Armijo grid
    bytes_ = p * k * (isz + 4) + q * (r + p) * 4 + 2 * r * 4
    return runner, flops, bytes_


def _cell_direction(s, p, dtype):
    import jax.numpy as jnp
    from repro.kernels import ops
    g = _rng(2)
    isz = jnp.dtype(dtype).itemsize
    XB = jnp.asarray(g.standard_normal((s, p)), dtype)
    u = jnp.asarray(g.standard_normal((s,)), jnp.float32)
    v = jnp.asarray(np.abs(g.standard_normal((s,))), jnp.float32)
    w = jnp.asarray(0.1 * g.standard_normal((p,)), jnp.float32)

    def runner(cfg):
        return lambda: ops.pcdn_direction(XB, u, v, w, impl=cfg["impl"],
                                          block_s=cfg["block_s"],
                                          block_p=cfg["block_p"])

    return runner, 5 * s * p, s * p * isz + 2 * s * 4 + 4 * p * 4


def _cell_sparse_direction(p, k, s, dtype):
    import jax.numpy as jnp
    from repro.kernels import ops
    g = _rng(3)
    isz = jnp.dtype(dtype).itemsize
    rows = jnp.asarray(g.integers(0, s, (p, k)), jnp.int32)
    vals = jnp.asarray(g.standard_normal((p, k)), dtype)
    u = jnp.asarray(g.standard_normal((s,)), jnp.float32)
    v = jnp.asarray(np.abs(g.standard_normal((s,))), jnp.float32)
    w = jnp.asarray(0.1 * g.standard_normal((p,)), jnp.float32)

    def runner(cfg):
        return lambda: ops.pcdn_sparse_direction(
            rows, vals, u, v, w, impl=cfg["impl"],
            block_p=cfg["block_p"], block_k=cfg["block_k"])

    return runner, 5 * p * k, p * k * (isz + 4) + 2 * s * 4 + 4 * p * 4


def _cell_linesearch(s, q, dtype):
    import jax.numpy as jnp
    from repro.kernels import ops
    g = _rng(4)
    z = jnp.asarray(g.standard_normal((s,)), jnp.float32)
    d = jnp.asarray(0.1 * g.standard_normal((s,)), jnp.float32)
    y = jnp.asarray(g.choice([-1.0, 1.0], (s,)), jnp.float32)
    alphas = jnp.asarray(0.5 ** np.arange(q), jnp.float32)

    def runner(cfg):
        return lambda: ops.pcdn_linesearch(z, d, y, alphas,
                                           impl=cfg["impl"],
                                           block_s=cfg["block_s"])

    return runner, 8 * q * s, 3 * s * 4 + 2 * q * 4


def _cell_margins_dense(b, n, k, a, dtype):
    import jax.numpy as jnp
    from repro.kernels import ops
    g = _rng(5)
    isz = jnp.dtype(dtype).itemsize
    X = jnp.asarray(g.standard_normal((b, n)), dtype)
    idx = jnp.asarray(np.sort(g.permutation(n)[:a])[None, :].repeat(k, 0),
                      jnp.int32)
    val = jnp.asarray(g.standard_normal((k, a)), dtype)

    def runner(cfg):
        return lambda: ops.serve_margins_dense(
            X, idx, val, impl=cfg["impl"],
            block_b=cfg["block_b"], block_a=cfg["block_a"])

    # the gather touches (b, a) of X per model; idx/val stream once
    return (runner, 2 * b * k * a,
            b * a * isz * k + k * a * (4 + isz) + b * k * 4)


def _cell_margins_csc(n, kmax, k, a, b, dtype):
    import jax.numpy as jnp
    from repro.kernels import ops
    g = _rng(6)
    isz = jnp.dtype(dtype).itemsize
    col_rows = jnp.asarray(g.integers(0, b, (n, kmax)), jnp.int32)
    col_vals = jnp.asarray(g.standard_normal((n, kmax)), dtype)
    idx = jnp.asarray(np.sort(g.permutation(n)[:a])[None, :].repeat(k, 0),
                      jnp.int32)
    val = jnp.asarray(g.standard_normal((k, a)), dtype)

    def runner(cfg):
        return lambda: ops.serve_margins_csc(col_rows, col_vals, idx, val,
                                             n_requests=b,
                                             impl=cfg["impl"])

    return (runner, 2 * k * a * kmax,
            a * kmax * (4 + isz) * k + k * a * (4 + isz) + b * k * 4)


# (kernel, shape dict, builder) — full mode runs every row, --smoke the
# first row per kernel with tiny shapes.
CELLS = [
    ("pcdn_bundle", dict(p=128, k=32, r=1024, q=20),
     lambda d: _cell_bundle(128, 32, 1024, 20, d)),
    ("pcdn_bundle", dict(p=256, k=64, r=4096, q=20),
     lambda d: _cell_bundle(256, 64, 4096, 20, d)),
    ("pcdn_direction", dict(s=2048, p=128),
     lambda d: _cell_direction(2048, 128, d)),
    ("pcdn_direction", dict(s=8192, p=256),
     lambda d: _cell_direction(8192, 256, d)),
    ("pcdn_sparse_direction", dict(p=128, k=64, s=4096),
     lambda d: _cell_sparse_direction(128, 64, 4096, d)),
    ("pcdn_linesearch", dict(s=8192, q=20),
     lambda d: _cell_linesearch(8192, 20, d)),
    ("serve_margins_dense", dict(b=128, n=2048, k=8, a=256),
     lambda d: _cell_margins_dense(128, 2048, 8, 256, d)),
    ("serve_margins_csc", dict(n=2048, kmax=16, k=8, a=256, b=128),
     lambda d: _cell_margins_csc(2048, 16, 8, 256, 128, d)),
]

SMOKE_CELLS = [
    ("pcdn_bundle", dict(p=32, k=8, r=128, q=8),
     lambda d: _cell_bundle(32, 8, 128, 8, d)),
    ("pcdn_direction", dict(s=256, p=32),
     lambda d: _cell_direction(256, 32, d)),
    ("pcdn_sparse_direction", dict(p=32, k=8, s=256),
     lambda d: _cell_sparse_direction(32, 8, 256, d)),
    ("pcdn_linesearch", dict(s=512, q=8),
     lambda d: _cell_linesearch(512, 8, d)),
    ("serve_margins_dense", dict(b=16, n=128, k=4, a=32),
     lambda d: _cell_margins_dense(16, 128, 4, 32, d)),
    ("serve_margins_csc", dict(n=128, kmax=8, k=4, a=32, b=16),
     lambda d: _cell_margins_csc(128, 8, 4, 32, 16, d)),
]


def roofline_terms(flops, bytes_, us, peaks):
    """Place one measured cell against the calibrated peaks."""
    t_compute_us = flops / peaks["flops_per_s"] * 1e6
    t_memory_us = bytes_ / peaks["bytes_per_s"] * 1e6
    bound_us = max(t_compute_us, t_memory_us)
    return {
        "flops": int(flops), "bytes": int(bytes_),
        "intensity_flops_per_byte": flops / max(bytes_, 1),
        "t_compute_us": t_compute_us, "t_memory_us": t_memory_us,
        "bound": "compute" if t_compute_us >= t_memory_us else "memory",
        "roofline_us": bound_us,
        # fraction of the roofline bound the measured kernel attains
        # (1.0 == at the roof; small == far below it)
        "attained_frac": bound_us / max(us, 1e-9),
    }


def run_cells(cells, dtype_name, peaks, strategy, repeats, persist):
    from repro.kernels import autotune
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype_name)
    out = []
    for kernel, shape, build in cells:
        runner, flops, bytes_ = build(dtype)
        bucket = autotune.shape_bucket(**shape)
        res = autotune.tune(kernel, runner, bucket, dtype,
                            strategy=strategy, repeats=repeats,
                            persist=persist)
        cell = {
            "kernel": kernel, "shape": shape, "dtype": dtype_name,
            "default": {"config": autotune.DEFAULTS[kernel],
                        "us": res.default_us},
            "tuned": {"config": res.config, "us": res.us},
            "speedup": res.speedup,
            "n_candidates": len(res.table),
            "roofline": roofline_terms(flops, bytes_, res.us, peaks),
        }
        out.append(cell)
        emit(f"kernels/{kernel}", res.us,
             f"default={res.default_us:.0f}us tuned={res.us:.0f}us "
             f"x{res.speedup:.2f} cfg={res.config} "
             f"bound={cell['roofline']['bound']}")
    return out


# ---------------------------------------------------------------------------
# bf16-vs-fp32 equivalence study


def bf16_study(max_outer: int, losses=("logistic", "squared_hinge"),
               scale=None):
    """Matched-iteration trajectory comparison: same data, same config,
    tol_kkt=0 and a fixed outer budget, so iteration k of the bf16 run
    lines up with iteration k of the fp32 run. Reports the max relative
    objective difference across the trajectory — the calibration number
    behind launch/common.py's BF16_MIN_TOL gate."""
    import jax.numpy as jnp
    from repro.core import PCDNConfig, make_problem, solve
    from repro.data import paper_like
    study = {"dataset": "a9a", "max_outer": max_outer, "losses": {},
             "max_objective_rel_diff": 0.0}
    X, y, _ = paper_like("a9a", seed=0, scale=scale)
    for loss in losses:
        cfg = PCDNConfig(P=128, max_outer=max_outer, tol_kkt=0.0, seed=0)
        runs = {}
        for name, dt in (("fp32", jnp.float32), ("bf16", jnp.bfloat16)):
            prob = make_problem(X, y, c=1.0, loss=loss, dtype=dt)
            res = solve(prob, cfg)
            runs[name] = np.asarray(res.history.objective, np.float64)
        n = min(len(runs["fp32"]), len(runs["bf16"]))
        rel = np.abs(runs["bf16"][:n] - runs["fp32"][:n]) / \
            np.maximum(np.abs(runs["fp32"][:n]), 1e-12)
        study["losses"][loss] = {
            "n_iters": int(n),
            "final_fp32": float(runs["fp32"][n - 1]),
            "final_bf16": float(runs["bf16"][n - 1]),
            "max_rel_diff": float(rel.max()),
        }
        study["max_objective_rel_diff"] = max(
            study["max_objective_rel_diff"], float(rel.max()))
        emit(f"kernels/bf16_study_{loss}", 0.0,
             f"iters={n} max_rel_diff={rel.max():.2e}")
    study["envelope_rel_diff"] = 1e-3
    study["pass"] = study["max_objective_rel_diff"] <= 1e-3
    return study


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, few repeats, results-dir output "
                         "only (CI tier-1)")
    ap.add_argument("--strategy", default="exhaustive",
                    choices=["exhaustive", "hillclimb"])
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--dtypes", default="float32,bfloat16",
                    help="comma list of storage dtypes to sweep")
    ap.add_argument("--no-study", action="store_true",
                    help="skip the bf16 trajectory study")
    ap.add_argument("--no-persist", action="store_true",
                    help="do not write winners into the autotune cache")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.kernels import autotune
    repeats = args.repeats or (2 if args.smoke else 5)
    cells = SMOKE_CELLS if args.smoke else CELLS

    emit("kernels/calibrate", 0.0, "measuring peaks...")
    peaks = {"flops_per_s": calibrate_peak_flops(
                 256 if args.smoke else 1024),
             "bytes_per_s": calibrate_peak_bandwidth(
                 8 if args.smoke else 64)}
    emit("kernels/peaks", 0.0,
         f"{peaks['flops_per_s'] / 1e9:.1f} GFLOP/s "
         f"{peaks['bytes_per_s'] / 1e9:.1f} GB/s")

    all_cells = []
    for dtype_name in [d for d in args.dtypes.split(",") if d]:
        all_cells += run_cells(cells, dtype_name, peaks, args.strategy,
                               repeats, persist=not args.no_persist)

    payload = {
        "meta": {"backend": autotune.backend_tag(),
                 "strategy": args.strategy, "repeats": repeats,
                 "smoke": bool(args.smoke),
                 "when": time.strftime("%Y-%m-%dT%H:%M:%S")},
        "peaks": {"flops_gflops": peaks["flops_per_s"] / 1e9,
                  "bandwidth_gbps": peaks["bytes_per_s"] / 1e9},
        "cells": all_cells,
    }
    if not args.no_study:
        payload["bf16_study"] = bf16_study(
            max_outer=5 if args.smoke else 30,
            scale=0.25 if args.smoke else None)

    best = max(c["speedup"] for c in all_cells)
    payload["headline"] = {
        "best_speedup": best,
        "all_tuned_at_least_default": all(
            c["tuned"]["us"] <= c["default"]["us"] for c in all_cells),
    }
    emit("kernels/headline", 0.0, f"best tuned-over-default x{best:.2f}")

    os.makedirs(RESULTS_DIR, exist_ok=True)
    if args.smoke:
        out = os.path.join(RESULTS_DIR, "BENCH_kernels_smoke.json")
        paths = [out]
    else:
        paths = [HEADLINE, os.path.join(RESULTS_DIR, "BENCH_kernels.json")]
    for p in paths:
        with open(p, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
        print(f"[bench_kernels] wrote {p}")
    return payload


if __name__ == "__main__":
    main()
