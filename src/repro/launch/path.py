"""Regularization-path driver (DESIGN.md sections 8 / 9):

    python -m repro.launch.path --dataset real-sim --points 20 --shrink
    python -m repro.launch.path --backend sharded --data-parallel 2 \
        --model-parallel 4          # warm-started sweep on a device mesh

Builds the geometric c-grid from the analytic c_max, runs the
warm-started sweep on the selected execution backend (or, with --mode
batch, solves every grid point simultaneously in one vmapped program),
reports per-point objective / nnz / KKT / validation accuracy, and picks
the best c by held-out accuracy. Writes a JSON report with --out (+ a
.npy weight matrix next to it with --save-weights).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.utils.compile_cache import enable_compile_cache
from repro.core import as_design
from repro.core.problem import validation_accuracy
from repro.data.libsvm import CSRMatrix
from repro.launch import common
from repro.serve import artifact as art
from repro.path import PathConfig, PathPoint, PathResult, path_summary, \
    pick_best, problem_grid, run_path, solve_batch


def _rows(X, start: int, stop: int):
    """Rows [start, stop) of a dense array or a CSRMatrix."""
    if isinstance(X, CSRMatrix):
        return X.row_slice(start, stop)
    return X[start:stop]


def _load(args):
    """-> (X, y, val_design, val_y) honoring --val-frac: the trailing
    rows are held out, of a profile or a file dataset (a sparse file
    loads as CSR and splits by row)."""
    X, y, _Xte, _yte, _spec = common.load_dataset(args)
    if args.val_frac <= 0:
        return X, y, None, None
    s = X.shape[0]
    cut = max(1, int(round((1.0 - args.val_frac) * s)))
    if cut >= s:
        raise SystemExit(f"--val-frac {args.val_frac} leaves no "
                         f"validation rows (s={s})")
    return _rows(X, 0, cut), y[:cut], _rows(X, cut, s), y[cut:]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="real-sim",
                    help="paper dataset profile name or a .libsvm path")
    ap.add_argument("--loss", default="logistic",
                    choices=["logistic", "squared_hinge"])
    ap.add_argument("--points", type=int, default=20)
    ap.add_argument("--span", type=float, default=100.0,
                    help="c_final = span * c_max (ignored with --c-final)")
    ap.add_argument("--c-final", type=float, default=None)
    ap.add_argument("--cold", action="store_true",
                    help="disable warm starting (ablation)")
    ap.add_argument("--mode", default="sweep", choices=["sweep", "batch"],
                    help="sweep: sequential warm-started path; batch: "
                         "solve all grid points at once via vmap")
    ap.add_argument("--val-frac", type=float, default=0.2,
                    help="held-out row fraction (the trailing rows) for "
                         "the best-c pick; 0 disables")
    ap.add_argument("--stop", default="absolute",
                    choices=["absolute", "relative"],
                    help="per-point KKT stop (--mode sweep): absolute "
                         "stops every point at --tol; relative stops "
                         "point i at --tol * KKT_{c_i}(0) = --tol * "
                         "(c_i / c_max - 1), CDN's relative stop")
    common.add_solver_args(ap)
    common.add_backend_args(ap)
    ap.add_argument("--out", default=None, help="write path JSON here")
    ap.add_argument("--save-weights", action="store_true",
                    help="also write <out>.weights.npy")
    ap.add_argument("--save-model", default=None, metavar="PATH",
                    help="write the whole sweep as ONE kind='path' serve "
                         "artifact family — every grid point becomes a "
                         "servable model (DESIGN.md section 10.1)")
    common.add_obs_args(ap)
    common.add_diag_args(ap)
    common.add_fault_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.mode == "batch" and (args.ckpt_dir or args.resume):
        ap.error("--ckpt-dir/--resume require --mode sweep (the lockstep "
                 "batch engine solves all points at once — there is no "
                 "point cursor to checkpoint)")
    if args.mode == "batch" and args.shrink:
        ap.error("--shrink requires --mode sweep (the vmapped batch "
                 "engine has no active-set masking)")
    if args.mode == "batch" and args.backend == "sharded":
        ap.error("--mode batch is local-only (the vmapped batch solver "
                 "has no sharded execution backend yet)")
    if args.mode == "batch" and args.stop != "absolute":
        ap.error("--stop relative requires --mode sweep (the lockstep "
                 "batch engine stops every problem at one tolerance)")
    if args.mode == "batch" and args.diag_out:
        ap.error("--diag-out requires --mode sweep (the lockstep batch "
                 "engine keeps no per-iteration history)")
    common.check_dtype_envelope(args, ap, loss=args.loss)

    X, y, Xval, yval = _load(args)
    common.setup_obs(args)
    solver = common.build_pcdn_config(args)
    cfg = PathConfig(solver=solver, n_points=args.points, span=args.span,
                     c_final=args.c_final, warm_start=not args.cold,
                     stop=args.stop)
    backend, prob = common.make_path_backend(args, X, y, args.loss, cfg)
    print(f"[path] dataset={args.dataset} s={X.shape[0]} "
          f"n={backend.n_features} c_max={backend.c_max():.5g} "
          f"points={args.points} mode={args.mode} shrink={args.shrink} "
          f"stop={args.stop} "
          f"warm={not args.cold} backend={args.backend}")

    if args.mode == "batch":
        cs = problem_grid(prob, c_final=args.c_final,
                          n_points=args.points, span=args.span)
        t0 = time.perf_counter()
        bres = solve_batch(prob, solver, cs)
        total_s = time.perf_counter() - t0
        val = None if Xval is None else as_design(Xval)
        points = []
        for i, c in enumerate(cs):
            acc = (validation_accuracy(val, yval, np.asarray(bres.w[i]))
                   if val is not None else None)
            p = PathPoint(c=float(c), objective=float(bres.objective[i]),
                          nnz=int(bres.nnz[i]), kkt=float(bres.kkt[i]),
                          n_outer=int(bres.n_outer[i]),
                          seconds=None,   # lockstep: no per-point timing
                          converged=bool(bres.converged[i]),
                          val_accuracy=acc)
            points.append(p)
            print(f"[path] c={p.c:.5g} F={p.objective:.5f} nnz={p.nnz} "
                  f"kkt={p.kkt:.2e} iters={p.n_outer}"
                  + (f" val_acc={acc:.4f}" if acc is not None else ""))
        weights = np.asarray(bres.w)
        # synthesize a PathResult so the report schema (and the best-c
        # tie-break inside it) is shared with sweep mode
        res = PathResult(c_max=float(cs[0]), cs=cs, points=points,
                         weights=weights, best_index=pick_best(points),
                         total_seconds=total_s)
        payload = {"mode": "batch", **path_summary(res)}
    else:
        from repro import fault
        res = run_path(prob, cfg, val_design=Xval, val_y=yval,
                       verbose=True, backend=backend,
                       callback=common.make_progress_callback(args),
                       ckpt=common.make_checkpointer(args, ap),
                       resume=args.resume,
                       fault_plan=fault.plan_from_env())
        common.finish_progress(args)
        payload = {"mode": "sweep", "backend": args.backend,
                   **path_summary(res)}
        weights = res.weights
        if res.best is not None:
            print(f"[path] best c={res.best.c:.5g} "
                  f"val_acc={res.best.val_accuracy:.4f} nnz={res.best.nnz}")

    if args.out:
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
        if args.save_weights:
            np.save(args.out + ".weights.npy", weights)
        print(f"[path] wrote {args.out}")
    if args.save_model:
        metas = [{"objective": p.objective, "kkt": p.kkt, "nnz": p.nnz,
                  "n_outer": p.n_outer, "converged": p.converged,
                  "val_accuracy": p.val_accuracy} for p in res.points]
        family = art.path_family(
            weights, res.cs, args.loss, metas=metas,
            provenance=art.solver_provenance(
                solver="pcdn", dataset=args.dataset, backend=args.backend,
                mode=args.mode, P=args.P, tol_kkt=args.tol, stop=args.stop,
                seed=args.seed,
                shrink=bool(args.shrink), loss=args.loss,
                dtype=args.dtype, best_index=res.best_index))
        art.save_model(args.save_model, family)
        print(f"[path] wrote model family ({len(family)} points) to "
              f"{args.save_model}")
    if args.diag_out:
        best = res.best
        diag_report = {
            "provenance": art.solver_provenance(
                solver="pcdn", dataset=args.dataset, backend=args.backend,
                mode=args.mode, P=args.P, tol_kkt=args.tol, stop=args.stop,
                seed=args.seed,
                shrink=bool(args.shrink), loss=args.loss, dtype=args.dtype),
            "loss": args.loss, "n_features": int(backend.n_features),
            "objective": res.points[-1].objective if res.points else None,
            "converged": res.points[-1].converged if res.points else None,
            "nnz": res.points[-1].nnz if res.points else None,
            "seconds": res.total_seconds,
            "history": (common.history_dict(res.last_history)
                        if res.last_history is not None else None),
            "postmortem": res.last_postmortem}
        if best is not None:
            diag_report["best_c"] = best.c
        common.write_diag(args, diag_report, design=as_design(X),
                          tol_kkt=args.tol)
    common.finish_obs(args, meta={
        "cli": "path", "dataset": args.dataset, "mode": args.mode,
        "backend": args.backend, "points": len(res.points),
        "total_seconds": res.total_seconds})
    return payload


if __name__ == "__main__":
    main()
