"""PCDN solver driver (the paper's end-to-end path):
``python -m repro.launch.solve --dataset real-sim --loss logistic --P 512``

Loads/generates an l1 classification problem, runs the selected solver
(pcdn / cdn / scdn / tron) on the selected execution backend
(``--backend local|sharded`` — DESIGN.md section 9), reports the
Fig. 4-style trace, and writes a chaining-ready report with ``--out``.
``--warm-start`` and ``--shrink`` work on BOTH backends.

``--out`` reports are simultaneously (a) a servable model artifact
(``repro.serve`` schema — DESIGN.md section 10.1), (b) a ``--warm-start``
input (top-level sparse weight record), and (c) a history log.
``--save-model`` writes just the artifact.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.utils.compile_cache import enable_compile_cache
from repro.core import cdn_config, make_problem, scdn, tron, with_bundle_size
from repro.core.scdn import SCDNConfig
from repro.data.synthetic import train_accuracy
from repro.engine import LocalBackend, ShardedBackend
from repro.launch import common
from repro.serve import artifact as art


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="real-sim",
                    help="paper dataset profile name or a .libsvm path")
    ap.add_argument("--solver", default="pcdn",
                    choices=["pcdn", "cdn", "scdn", "tron"])
    ap.add_argument("--loss", default="logistic",
                    choices=["logistic", "squared_hinge"])
    ap.add_argument("--c", type=float, default=None,
                    help="regularization (default: paper's c* per dataset)")
    common.add_solver_args(ap)
    common.add_backend_args(ap)
    ap.add_argument("--sharded", action="store_true",
                    help="deprecated alias for --backend sharded")
    ap.add_argument("--out", default=None,
                    help="write the combined report (model artifact + "
                         "warm-start record + history) here")
    ap.add_argument("--save-model", default=None, metavar="PATH",
                    help="write just the serve artifact (no history)")
    common.add_obs_args(ap)
    common.add_diag_args(ap)
    common.add_fault_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.sharded:
        args.backend = "sharded"
    if ((args.ckpt_dir or args.resume)
            and args.solver not in ("pcdn", "cdn")):
        ap.error("--ckpt-dir/--resume require --solver pcdn or cdn (the "
                 "checkpoint image is the bundle solver's EngineState)")
    if args.diag_out and args.solver not in ("pcdn", "cdn"):
        ap.error("--diag-out requires --solver pcdn or cdn (the KKT "
                 "attribution harvest is a bundle-solver output)")
    if args.warm_start and args.solver not in ("pcdn", "cdn"):
        ap.error("--warm-start requires --solver pcdn or cdn")
    if args.shrink and args.solver not in ("pcdn", "cdn"):
        ap.error("--shrink requires --solver pcdn or cdn")
    if args.backend == "sharded" and args.solver != "pcdn":
        ap.error("--backend sharded supports --solver pcdn only")
    if args.dtype == "bf16" and args.solver not in ("pcdn", "cdn"):
        ap.error("--dtype bf16 is studied for --solver pcdn/cdn only")
    common.check_dtype_envelope(args, ap, loss=args.loss)

    X, y, Xte, yte, spec = common.load_dataset(args, with_test=True)
    if spec is not None:
        c = args.c or (spec.c_logistic if args.loss == "logistic"
                       else spec.c_svm)
    else:
        c = args.c or 1.0
    print(f"[solve] dataset={args.dataset} s={X.shape[0]} n={X.shape[1]} "
          f"c={c} loss={args.loss} solver={args.solver} P={args.P} "
          f"backend={args.backend}")
    common.setup_obs(args)
    progress = common.make_progress_callback(args)
    ckpt = common.make_checkpointer(args, ap)
    from repro import fault
    plan = fault.plan_from_env()

    t0 = time.time()
    if args.backend == "sharded":
        # pcdn on a mesh: resilient_solve owns the backend (its factory
        # rebuilds at a damped P_local after a rollback, on the SAME mesh)
        backend0, _ = common.make_backend(args, X, y, c, args.loss)

        def factory(P):
            if int(P) == int(args.P):
                return backend0
            import dataclasses as _dc
            cfg = _dc.replace(
                common.build_sharded_config(args, c, args.loss),
                P_local=max(int(P) // max(args.model_parallel, 1), 1))
            return ShardedBackend(X, y, backend0.mesh, cfg,
                                  layout=args.layout)

        w0 = (common.load_warm_start(args.warm_start, backend0.n_features,
                                     backend0.dtype)
              if args.warm_start else None)
        res = fault.resilient_solve(
            factory, c, P=args.P, w0=w0, max_outer=args.max_outer,
            tol_kkt=args.tol, callback=progress, checkpointer=ckpt,
            resume=args.resume, max_retries=args.retries, plan=plan)
        w = res.w                      # resilient_solve returns host w
        f, conv = res.objective, res.converged
        history = common.history_dict(res.history)
    else:
        prob = make_problem(X, y, c=c, loss=args.loss,
                            layout=args.layout,
                            dtype=common.DTYPES[args.dtype])
        w0 = (common.load_warm_start(args.warm_start, prob.n_features,
                                     prob.dtype)
              if args.warm_start else None)
        if args.solver in ("pcdn", "cdn"):
            base_cfg = (common.build_pcdn_config(args)
                        if args.solver == "pcdn" else
                        cdn_config(max_outer=args.max_outer,
                                   tol_kkt=args.tol, seed=args.seed,
                                   shrink=args.shrink,
                                   use_kernels=args.use_kernels,
                                   record_aux=common._record_aux(args),
                                   record_kkt_vec=
                                   common._record_kkt_vec(args)))

            def factory(P):
                return LocalBackend(prob, with_bundle_size(base_cfg, P))

            res = fault.resilient_solve(
                factory, c, P=base_cfg.P, w0=w0,
                max_outer=base_cfg.max_outer, tol_kkt=base_cfg.tol_kkt,
                recheck_every=base_cfg.recheck_every,
                tol_rel_obj=base_cfg.tol_rel_obj, callback=progress,
                checkpointer=ckpt, resume=args.resume,
                max_retries=args.retries, design=prob.design, plan=plan)
        elif args.solver == "scdn":
            res = scdn.solve(prob, SCDNConfig(max_rounds=args.max_outer,
                                              tol_kkt=args.tol,
                                              seed=args.seed))
        else:
            res = tron.solve(prob, tron.TRONConfig(max_outer=args.max_outer,
                                                   tol_kkt=args.tol))
        w, f, conv = res.w, res.objective, res.converged
        history = common.history_dict(getattr(res, "history")) \
            if hasattr(getattr(res, "history"), "_asdict") else \
            {k_: np.asarray(v).tolist()
             for k_, v in res.history.items()}
    nnz = int(np.sum(np.asarray(w) != 0))
    dt = time.time() - t0
    common.finish_progress(args)

    faults = getattr(res, "faults", None)
    if faults:
        print(f"[fault] rollbacks={faults['rollbacks']} "
              f"p_schedule={faults['p_schedule']} "
              f"p_cert={faults['p_cert']} "
              f"resumed_from={faults['resumed_from']}")
    print(f"[solve] F={f:.6f} converged={conv} nnz={nnz} time={dt:.1f}s")
    if Xte is not None:
        acc = train_accuracy(Xte, yte, np.asarray(w))
        print(f"[solve] test accuracy: {acc:.4f}")
    if args.out or args.save_model:
        meta = {"objective": float(f), "converged": bool(conv), "nnz": nnz}
        if isinstance(history, dict) and history.get("kkt"):
            meta["kkt"] = float(history["kkt"][-1])
            meta["n_outer"] = len(history["kkt"])
        family = art.ModelFamily(
            kind="binary",
            models=(art.artifact_from_solution(w, args.loss, c, meta=meta),),
            provenance=art.solver_provenance(
                solver=args.solver, dataset=args.dataset, backend=args.backend,
                P=args.P, tol_kkt=args.tol, seed=args.seed,
                shrink=bool(args.shrink), loss=args.loss,
                dtype=args.dtype))
        if args.save_model:
            art.save_model(args.save_model, family)
        if args.out:
            # the top-level sparse weight record keeps the report a valid
            # --warm-start input (launch.common.load_warm_start) exactly
            # as before the artifact schema existed; n_features comes
            # from the artifact block itself
            record = common.sparse_weight_record(w)
            record.pop("n_features")
            extra = {
                "objective": float(f), "converged": bool(conv),
                "nnz": nnz, "seconds": dt, **record,
                "history": history if isinstance(history, dict) else None}
            pm = getattr(res, "postmortem", None)
            if pm:
                extra["postmortem"] = pm
            art.save_model(args.out, family, extra=extra)
    if args.diag_out:
        from repro.core import as_design
        prov = art.solver_provenance(
            solver=args.solver, dataset=args.dataset, backend=args.backend,
            P=args.P, tol_kkt=args.tol, seed=args.seed,
            shrink=bool(args.shrink), loss=args.loss, dtype=args.dtype)
        diag_report = {
            "provenance": prov, "loss": args.loss,
            "n_features": int(np.asarray(w).shape[0]),
            "objective": float(f), "converged": bool(conv), "nnz": nnz,
            "seconds": dt,
            "history": history if isinstance(history, dict) else None,
            "postmortem": getattr(res, "postmortem", None)}
        common.write_diag(args, diag_report, design=as_design(X),
                          tol_kkt=args.tol)
    common.finish_obs(args, meta={
        "cli": "solve", "dataset": args.dataset, "solver": args.solver,
        "backend": args.backend, "objective": float(f),
        "converged": bool(conv), "nnz": nnz, "seconds": dt})
    return f


if __name__ == "__main__":
    main()
