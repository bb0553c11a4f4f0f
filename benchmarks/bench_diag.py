"""Diagnostics overhead + certified-P agreement -> BENCH_diag.json
(DESIGN.md section 15).

    PYTHONPATH=src python benchmarks/bench_diag.py [--smoke]

Three arms:

  * attribution — a fixed-iteration PCDN solve (tol_kkt=0 pins both
    arms to identical solver work) timed with diagnostics fully off vs
    the full `--diag-out` harvest (record_kkt_vec + record_aux), with
    INTERLEAVED repeats (A B A B ...) so machine-load drift hits both
    arms. Headline `attribution.overhead_pct` is the acceptance
    number: the per-feature harvest must cost <= 5% of solve wall time.

  * safep — the power-iteration spectral-radius estimate of the
    normalized Gram vs `numpy.linalg.eigvalsh` of the densified matrix,
    on dense AND padded-CSC designs. Headline `safep.agreement` is the
    acceptance bool (every rel-err <= 1e-4); the ESO ω bound is
    cross-checked against a direct per-row count.

  * report — wall time to build the health-report payload and render
    the markdown from the enabled arm's real SolveHistory (no gate,
    recorded so regressions are visible in the trajectory).

Smoke mode writes only to benchmarks/results/ (CI); the full run also
writes the repo-root BENCH_diag.json that the acceptance criterion and
`benchmarks/sentinel.py` read.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax

from repro.utils.compile_cache import enable_compile_cache
from repro.core import PCDNConfig, make_problem, solve
from repro.data.synthetic import make_classification
from repro.diag import report as diag_report
from repro.diag import safep

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


def _time_pair(fn_a, fn_b, repeats: int = 5):
    """Best-of-N for two arms with INTERLEAVED repeats (A B A B ...), so
    slow machine-load drift hits both arms equally. Both arms are warmed
    before any timing (compile excluded)."""
    fn_a()
    fn_b()
    best_a = best_b = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn_a()
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def bench_attribution(s, n, P, iters, repeats, seed=0):
    """Off-vs-on wall time for the full --diag-out harvest on identical
    solver work."""
    X, y, _ = make_classification(s, n, sparsity=0.5, seed=seed)
    prob = make_problem(X, y, c=2.0)
    cfg_off = PCDNConfig(P=P, max_outer=iters, tol_kkt=0.0, seed=seed)
    cfg_on = dataclasses.replace(cfg_off, record_kkt_vec=True,
                                 record_aux=True)

    t_off, t_on = _time_pair(lambda: solve(prob, cfg_off),
                             lambda: solve(prob, cfg_on), repeats)
    res_off = solve(prob, cfg_off)
    res_on = solve(prob, cfg_on)

    assert res_on.history.kkt_vec is not None, \
        "enabled arm must thread the per-feature violation series"
    assert res_off.history.kkt_vec is None, \
        "disabled arm must not carry the attribution series"
    # byte-identical solver work: the extra outputs ride along, they do
    # not perturb the iterates
    drift = float(np.max(np.abs(
        np.asarray(res_on.w, np.float64) - np.asarray(res_off.w,
                                                      np.float64))))
    # attribution correctness on the benchmark shape: final recorded
    # vector == direct dense recomputation at the final iterate
    import jax.numpy as jnp
    w = jnp.asarray(res_on.w)
    g = prob.full_grad(prob.design.matvec(w), w)
    direct = np.asarray(prob.kkt_violation_from_grad(w, g), np.float64)
    attr_err = float(np.max(np.abs(
        res_on.history.kkt_vec[-1].astype(np.float64) - direct)))

    overhead = (t_on - t_off) / t_off * 100.0
    row = {
        "s": s, "n": n, "P": P, "iters": iters,
        "disabled_s": t_off, "enabled_s": t_on,
        "overhead_pct": overhead,
        "w_max_abs_drift": drift,
        "kkt_vec_shape": list(res_on.history.kkt_vec.shape),
        "attr_max_abs_err": attr_err,
    }
    print(f"[attribution] {iters} iters (s={s}, n={n}, P={P}): off "
          f"{t_off * 1e3:.1f}ms, on {t_on * 1e3:.1f}ms -> "
          f"{overhead:+.2f}% overhead, drift {drift:.1e}, "
          f"attr err {attr_err:.1e}", flush=True)
    return row, res_on


def _direct_rho(Xd: np.ndarray) -> float:
    norms = np.linalg.norm(Xd, axis=0)
    norms[norms == 0] = 1.0
    Xn = Xd / norms
    return float(np.linalg.eigvalsh(Xn.T @ Xn).max())


def bench_safep(shapes, seed=0):
    """Power iteration vs eigvalsh on dense + padded-CSC designs."""
    from repro.core import PaddedCSCDesign

    rows = []
    for i, (s, n, sparsity) in enumerate(shapes):
        X, y, _ = make_classification(s, n, sparsity=sparsity,
                                      seed=seed + i)
        for layout in ("dense", "padded_csc"):
            prob = make_problem(X, y, c=1.0, layout=layout)
            t0 = time.perf_counter()
            # high-sparsity Grams have a tight eigengap; give the power
            # method room to actually converge before judging agreement
            cert = safep.certify(prob.design, seed=seed, n_iter=3000)
            dt = time.perf_counter() - t0
            Xd = np.asarray(X, np.float64) if layout == "dense" else \
                np.asarray(prob.design.to_dense(), np.float64)
            rho_direct = _direct_rho(Xd)
            rel = abs(cert["rho_normalized"] - rho_direct) \
                / max(rho_direct, 1e-12)
            omega_direct = int(np.max(np.sum(Xd != 0, axis=1))) \
                if Xd.size else 0
            rows.append({
                "s": s, "n": n, "sparsity": sparsity, "layout": layout,
                "rho_power": cert["rho_normalized"],
                "rho_direct": rho_direct, "rel_err": rel,
                "power_iters": cert["power_iters"],
                "power_converged": cert["power_converged"],
                "omega": cert["omega"], "omega_direct": omega_direct,
                "omega_match": cert["omega"] == omega_direct,
                "P_spectral": cert["P_spectral"],
                "P_eso": cert["P_eso"], "P_cert": cert["P_cert"],
                "seconds": dt,
            })
            print(f"[safep] s={s} n={n} sp={sparsity} {layout}: rho "
                  f"{cert['rho_normalized']:.6f} vs {rho_direct:.6f} "
                  f"(rel {rel:.2e}), omega {cert['omega']} "
                  f"(direct {omega_direct}), P_cert {cert['P_cert']} "
                  f"in {dt * 1e3:.0f}ms", flush=True)
    max_rel = max(r["rel_err"] for r in rows)
    agreement = max_rel <= 1e-4 and all(r["omega_match"] for r in rows)
    return {"problems": rows, "max_rel_err": max_rel,
            "agreement": agreement}


def bench_report(res, prob_meta, repeats=5):
    """Payload build + markdown render time from a real SolveHistory."""
    hist = {k: np.asarray(v).tolist()
            for k, v in res.history._asdict().items() if v is not None}
    report = {"provenance": prob_meta, "loss": "logistic",
              "n_features": prob_meta["n"],
              "objective": float(res.objective),
              "converged": bool(res.converged),
              "nnz": int(np.sum(np.asarray(res.w) != 0)),
              "seconds": 0.0, "history": hist}

    def render():
        payload = diag_report.build_payload(report=report, tol_kkt=1e-3)
        return diag_report.render_markdown(payload)

    md = render()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        render()
        best = min(best, time.perf_counter() - t0)
    print(f"[report] {len(md)} chars rendered in {best * 1e3:.1f}ms",
          flush=True)
    return {"render_s": best, "markdown_chars": len(md)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        s, n, P, iters, repeats = 400, 300, 64, 10, 3
        shapes = [(120, 80, 0.0), (150, 100, 0.9)]
    else:
        s, n, P, iters, repeats = 2000, 2000, 256, 40, 5
        shapes = [(300, 200, 0.0), (400, 300, 0.9), (500, 400, 0.99)]

    attr_row, res_on = bench_attribution(s, n, P, iters, repeats)
    safep_block = bench_safep(shapes)
    report_row = bench_report(res_on, {"solver": "pcdn", "P": P, "s": s,
                                       "n": n, "tol_kkt": 0.0})

    payload = {
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "attribution": attr_row,
        "safep": safep_block,
        "report": report_row,
    }
    print(f"[diag] HEADLINE attribution overhead: "
          f"{attr_row['overhead_pct']:+.2f}% (acceptance: <= 5%); "
          f"safep agreement: {safep_block['agreement']} "
          f"(max rel err {safep_block['max_rel_err']:.2e})", flush=True)

    os.makedirs(RESULTS_DIR, exist_ok=True)
    targets = [os.path.join(RESULTS_DIR, "BENCH_diag.json")]
    if not args.smoke:
        targets.append(os.path.join(REPO_ROOT, "BENCH_diag.json"))
    for path in targets:
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
    print("wrote BENCH_diag.json")
    return payload


if __name__ == "__main__":
    main()
