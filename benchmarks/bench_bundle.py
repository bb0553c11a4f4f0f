"""Support-restricted bundle step benchmark -> BENCH_bundle.json.

    PYTHONPATH=src python benchmarks/bench_bundle.py [--smoke]

What PR 1's sparse backend did for the DIRECTION (O(P * k_max) instead
of O(s * P)), the support restriction (DESIGN.md section 11) does for
the remaining O(s) passes of a bundle step: the u/v gradient factors,
the Q-candidate Armijo grid, and the z += alpha * X_B d_B margin
maintenance. This bench measures each component separately and the
end-to-end step, over:

  * a sparsity x samples grid (sparsity in {0.9, 0.99, 0.999},
    s in {4k, 32k, 128k}), nnz_per_col = (1 - sparsity) * s — support
    scope is only timed where it is eligible (P * k_max < s; the grid
    records eligibility, which is the DESIGN.md section 11.3 contract);
  * an s-scaling arm at FIXED nnz_per_col: the s-independence
    certificate — the support-scoped line search must stay near-flat
    from s = 4k to 128k while the full-scope one grows linearly;
  * a short full-vs-support solve (objective trajectory max rel diff —
    the <= 1e-6 equivalence evidence at bench scale).

Full-scope baselines: "full_batched" is the PRE-support behavior (all
Q = 40 candidates in one (Q, s) pass — ls_chunk=40 reproduces it
exactly) and "full_chunked" the new chunked early-exit default.
Headline keys (guarded by tests/test_bundle_support.py):

    linesearch_speedup_at_0999   support vs full_batched at the largest
                                 benched s (the O(s*Q) gap grows with
                                 s; small-s cells are dispatch-bound)
    bundle_step_speedup_at_0999  whole step at s = 4096
    linesearch_support_s_growth  t(128k) / t(4k) at fixed nnz_per_col
                                 (1.0 = perfectly s-independent; the
                                 full-scope ratio is ~s ratio = 32)
    objective_traj_max_rel_diff

Writes BENCH_bundle.json at the repo root and benchmarks/results/.
Timings are of the jnp (XLA) paths — interpret-mode Pallas timings on
CPU would measure the interpreter (see benchmarks/bench_sparse.py).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.utils.compile_cache import enable_compile_cache
from repro.core import PCDNConfig, make_problem, solve
from repro.core import bundles as B
from repro.core.direction import delta_decrement, newton_direction
from repro.core.linesearch import (ArmijoParams, armijo_batched,
                                   armijo_support)
from repro.core.pcdn import make_outer_iteration, resolve_ls_scope
from repro.data import make_sparse_classification

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")

P_BUNDLE = 64
ARMIJO = ArmijoParams()


def _timed(fn, *args, n_timed=10):
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(n_timed):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _sparse_problem(s, n, nnz_per_col, seed=0):
    pcsc, y, _ = make_sparse_classification(s, n, nnz_per_col=nnz_per_col,
                                            seed=seed)
    return make_problem(pcsc, y, c=1.0)


def bench_components(prob, P=P_BUNDLE, seed=0):
    """Per-component jitted timings of ONE bundle step, both scopes."""
    design = prob.design
    n, s = prob.n_features, prob.n_samples
    rng = np.random.default_rng(seed)
    w = jnp.asarray((rng.standard_normal(n) *
                     (rng.random(n) < 0.1)).astype(np.float32))
    z = prob.margins(w)
    idx = jnp.asarray(rng.permutation(n)[:P], jnp.int32)
    loss = prob.loss

    @jax.jit
    def dir_full(w, z, idx):
        slab = design.gather_slab(idx)
        w_B, _ = B.gather_vec(w, idx)
        g, h = prob.bundle_grad_hess(z, slab, w_B)   # u/v over all s
        return newton_direction(g, h, w_B)

    @jax.jit
    def sup_build(idx):
        return design.slab_row_support(design.gather_slab(idx))

    @jax.jit
    def dir_support(w, z, idx):
        slab = design.gather_slab(idx)
        w_B, _ = B.gather_vec(w, idx)
        sup = design.slab_row_support(slab)
        z_R = jnp.take(z, sup.support, mode="fill", fill_value=0)
        y_R = jnp.take(prob.y, sup.support, mode="fill", fill_value=1)
        g, h = prob.bundle_grad_hess_support(slab, sup.pos, z_R, y_R, w_B)
        return newton_direction(g, h, w_B)

    # shared line-search inputs (one real direction)
    slab = design.gather_slab(idx)
    w_B, _ = B.gather_vec(w, idx)
    g, h = prob.bundle_grad_hess(z, slab, w_B)
    d = newton_direction(g, h, w_B)
    Delta = delta_decrement(g, h, w_B, d, ARMIJO.gamma)
    delta_z = design.slab_matvec(slab, d)
    sup = design.slab_row_support(slab)
    z_R = jnp.take(z, sup.support, mode="fill", fill_value=0)
    y_R = jnp.take(prob.y, sup.support, mode="fill", fill_value=1)
    delta_R = design.slab_matvec_support(slab, sup.pos, d)

    @jax.jit
    def ls_full_batched(z, delta_z, w_B, d, Delta):
        return armijo_batched(loss, prob.c, z, delta_z, prob.y, w_B, d,
                              Delta, ARMIJO).alpha

    @jax.jit
    def ls_support(z_R, delta_R, y_R, w_B, d, Delta):
        return armijo_support(loss, prob.c, z_R, delta_R, y_R, w_B, d,
                              Delta, ARMIJO).alpha

    @jax.jit
    def zup_full(z, idx, d, alpha):
        slab = design.gather_slab(idx)
        return z + alpha * design.slab_matvec(slab, d)

    @jax.jit
    def zup_support(z, idx, d, alpha):
        slab = design.gather_slab(idx)
        sup = design.slab_row_support(slab)
        delta_R = design.slab_matvec_support(slab, sup.pos, d)
        return design.scatter_support(z, sup.support, alpha * delta_R)

    alpha = jnp.float32(0.5)
    t_build = _timed(sup_build, idx)
    comp = {
        "direction": {"full": _timed(dir_full, w, z, idx),
                      "support": _timed(dir_support, w, z, idx)},
        "linesearch": {"full_batched": _timed(ls_full_batched, z, delta_z,
                                              w_B, d, Delta),
                       # support cost INCLUDES the support build so the
                       # speedup never hides shared work
                       "support": _timed(ls_support, z_R, delta_R, y_R,
                                         w_B, d, Delta) + t_build},
        "z_update": {"full": _timed(zup_full, z, idx, d, alpha),
                     "support": _timed(zup_support, z, idx, d, alpha)},
        "support_build": t_build,
    }
    comp["linesearch"]["speedup"] = (comp["linesearch"]["full_batched"] /
                                     comp["linesearch"]["support"])
    return comp


def bench_step(prob, P=P_BUNDLE, **cfg_kw):
    """Median seconds per bundle step of one jitted outer iteration."""
    cfg = PCDNConfig(P=P, max_outer=1, seed=1, **cfg_kw)
    n = prob.n_features
    b = -(-n // P)
    w = jnp.zeros((n,), prob.dtype)
    z = prob.margins(w)
    key = jax.random.PRNGKey(0)
    outer = make_outer_iteration(prob, cfg)
    return _timed(outer, w, z, key, n_timed=5) / b


def bench_cell(s, n, sparsity, P=P_BUNDLE, seed=0):
    nnz_per_col = max(1, int(round((1.0 - sparsity) * s)))
    prob = _sparse_problem(s, n, nnz_per_col, seed=seed)
    # time support wherever it is FEASIBLE (r_max < s) — including cells
    # where it loses, so the table shows the real crossover; the auto
    # rule's pick (margin * r_max <= s, DESIGN.md section 11.3) is
    # recorded separately.
    eligible = P * prob.design.k_max < s
    row = {
        "s": s, "n": n, "P": P, "sparsity": sparsity,
        "k_max": int(prob.design.k_max),
        "r_max": int(P * prob.design.k_max),
        "support_feasible": eligible,
        "auto_picks_support":
            resolve_ls_scope(PCDNConfig(P=P), prob) == "support",
        "bundle_step_seconds": {
            # ls_chunk=40 == the pre-support all-Q batched pass
            "full_batched": bench_step(prob, P, ls_scope="full",
                                       ls_chunk=40),
            "full_chunked": bench_step(prob, P, ls_scope="full"),
        },
    }
    if eligible:
        row["bundle_step_seconds"]["support"] = bench_step(
            prob, P, ls_scope="support")
        row["bundle_step_speedup"] = (
            row["bundle_step_seconds"]["full_batched"] /
            row["bundle_step_seconds"]["support"])
        row["components"] = bench_components(prob, P, seed=seed)
    bs = row["bundle_step_seconds"]
    sup = bs.get("support")
    sup_txt = ("%.2f ms (%.1fx)" % (sup * 1e3, row["bundle_step_speedup"])
               if sup else "ineligible (P*k_max >= s)")
    print(f"s={s} sparsity={sparsity}: full_batched "
          f"{bs['full_batched']*1e3:.2f} ms, full_chunked "
          f"{bs['full_chunked']*1e3:.2f} ms, support {sup_txt}", flush=True)
    return row


def bench_s_scaling(s_list, n, nnz_per_col, P=P_BUNDLE):
    """Fixed column degree, growing s: the s-independence certificate."""
    rows = []
    for s in s_list:
        prob = _sparse_problem(s, n, nnz_per_col, seed=3)
        comp = bench_components(prob, P, seed=3)
        rows.append({
            "s": s, "nnz_per_col": nnz_per_col,
            "linesearch_full_batched": comp["linesearch"]["full_batched"],
            "linesearch_support": comp["linesearch"]["support"],
            "bundle_step_full_batched": bench_step(prob, P,
                                                   ls_scope="full",
                                                   ls_chunk=40),
            "bundle_step_support": bench_step(prob, P, ls_scope="support"),
        })
        r = rows[-1]
        print(f"s-scaling s={s}: "
              f"ls full {r['linesearch_full_batched']*1e3:.2f} ms vs "
              f"support {r['linesearch_support']*1e3:.2f} ms; "
              f"step full {r['bundle_step_full_batched']*1e3:.2f} ms vs "
              f"support {r['bundle_step_support']*1e3:.2f} ms", flush=True)
    return rows


def bench_trajectory(s, n, sparsity, P=P_BUNDLE, max_outer=8):
    nnz_per_col = max(1, int(round((1.0 - sparsity) * s)))
    prob = _sparse_problem(s, n, nnz_per_col, seed=5)
    rf = solve(prob, PCDNConfig(P=P, max_outer=max_outer, seed=2,
                                ls_scope="full"))
    rs = solve(prob, PCDNConfig(P=P, max_outer=max_outer, seed=2,
                                ls_scope="support"))
    k = min(len(rf.history.objective), len(rs.history.objective))
    rel = float(np.max(
        np.abs(rf.history.objective[:k] - rs.history.objective[:k]) /
        np.abs(rf.history.objective[:k])))
    print(f"trajectory full vs support max rel diff: {rel:.2e}", flush=True)
    return rel


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (CI); headline keys still written")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        n, s_grid, s_scale, nnz_fix = 512, [1024, 4096], [1024, 4096], 8
        headline_s = 4096
    else:
        n, s_scale, nnz_fix = 4096, [4096, 32768, 131072], 32
        s_grid = [4096, 32768, 131072]
        headline_s = 4096

    grid = [bench_cell(s, n, sp)
            for sp in (0.9, 0.99, 0.999) for s in s_grid]
    scaling = bench_s_scaling(s_scale, n, nnz_fix)
    traj_rel = bench_trajectory(headline_s, n, 0.999)

    head = next(r for r in grid
                if r["sparsity"] == 0.999 and r["s"] == headline_s)
    # the line-search headline is the LARGEST benched s at 0.999: the
    # O(P*k_max*Q) vs O(s*Q) gap grows with s by construction, and the
    # sub-ms small-s cells are dispatch-noise-bound (their per-cell
    # figures stay in the grid)
    big = max((r for r in grid if r["sparsity"] == 0.999
               and "components" in r), key=lambda r: r["s"])
    sc0, sc1 = scaling[0], scaling[-1]
    payload = {
        "smoke": bool(args.smoke),
        "backend": jax.default_backend(),
        "P": P_BUNDLE,
        "grid": grid,
        "s_scaling_fixed_nnz": scaling,
        "linesearch_speedup_at_0999":
            big["components"]["linesearch"]["speedup"],
        "linesearch_speedup_s": big["s"],
        "bundle_step_speedup_at_0999": head["bundle_step_speedup"],
        "linesearch_support_s_growth":
            sc1["linesearch_support"] / sc0["linesearch_support"],
        "linesearch_full_s_growth":
            sc1["linesearch_full_batched"] / sc0["linesearch_full_batched"],
        "s_growth_factor": sc1["s"] / sc0["s"],
        "objective_traj_max_rel_diff": traj_rel,
    }
    ls_x = payload["linesearch_speedup_at_0999"]
    step_x = payload["bundle_step_speedup_at_0999"]
    print(f"headline: ls speedup {ls_x:.1f}x (s={big['s']}), step speedup "
          f"{step_x:.1f}x at sparsity 0.999 s={headline_s}; support ls grows "
          f"{payload['linesearch_support_s_growth']:.2f}x over a "
          f"{payload['s_growth_factor']:.0f}x s range (full: "
          f"{payload['linesearch_full_s_growth']:.1f}x)", flush=True)

    os.makedirs(RESULTS_DIR, exist_ok=True)
    for path in (os.path.join(REPO_ROOT, "BENCH_bundle.json"),
                 os.path.join(RESULTS_DIR, "BENCH_bundle.json")):
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
    print("wrote BENCH_bundle.json")
    return payload


if __name__ == "__main__":
    main()
