"""Bring-up smoke test: solve -> path -> serve on one TPU chip.

    python chip_smoke.py [--seed 0] [--out-dir DIR]
    python chip_smoke.py --four-chips

Drives the system once through the entry points a user calls
(`repro.launch.solve`, `repro.launch.path`, `repro.launch.predict`), all
in this one process, at the published shapes of two of the paper's
datasets (Table 2), generated from `--seed`:

  1. device    JAX must find a TPU and the Pallas kernels must compile
               (not interpret); otherwise exit non-zero.
  2. dense     gisette, 6,000 x 5,000 dense (corr 0.8), solved on the
               default XLA route and with --use-kernels.
  3. sparse    real-sim, 57,848 x 20,958 with ~2.9M nonzeros, written
               as LIBSVM text and solved with --layout padded_csc on
               both routes (P = 48 picks the support-scoped step, so
               the kernel route runs the fused bundle kernel); then a
               short warm path sweep (P = 256, full scope, the sparse
               direction kernel) saved as a servable model family.
  4. serve     `predict --serve` under Poisson traffic on that family,
               on both routes.

Every solve's F(w) and KKT violation are recomputed in float64 on the
host from X, y and the returned w; F must agree with the reported
objective to 1e-5 relative, and the two routes with each other to 1e-4.
Serving must answer every request, compile nothing after warmup, and
return the margins that float64 host scoring gives.

`--four-chips` runs only the sharded data x model backend on a 2 x 2
mesh of four chips against the same padded-CSC problem solved on one
chip; their objectives must agree to 1e-4 relative.

The last line of standard output is one JSON object
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}},
printed only when every phase passed. Generated files go to --out-dir.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

TOL = 1e-3            # KKT stop of every solve (the CLIs' default)
# outer-iteration caps that keep the whole run inside ~10 minutes on one
# v5e: real-sim's support-scoped step (P = 48, 437 bundles) takes ~0.9 s
# per outer iteration there. The float64 recheck and the route agreement
# hold for any iterate, converged or not.
GISETTE_MAX_OUTER = 100
REALSIM_MAX_OUTER = 20
PATH_MAX_OUTER = 20
F_RECHECK_RTOL = 1e-5
ROUTE_RTOL = 1e-4
MARGIN_RTOL = 1e-5
REALSIM_NNZ_PER_COL = 276     # uniform 1..276 per column: ~2.9M nonzeros
P_SUPPORT = 48                # 4 * P * k_max <= s: support scope on real-sim
SERVE_ROWS = 256
SERVE_REQUESTS = 400


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


# -- float64 host recomputation ------------------------------------------------

def _csr_matvec(csr, w):
    rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))
    return np.bincount(rows, weights=csr.data * w[csr.indices],
                       minlength=csr.shape[0])


def _csr_rmatvec(csr, u):
    rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))
    return np.bincount(csr.indices, weights=csr.data * u[rows],
                       minlength=csr.shape[1])


def host_objective_kkt(X, y, w, c):
    """Logistic F(w) = c * sum log(1 + exp(-y_i z_i)) + |w|_1 and the
    inf-norm of its minimum-norm subgradient, in float64."""
    y = np.asarray(y, np.float64)
    if hasattr(X, "indptr"):
        X64 = type(X)(X.data.astype(np.float64), X.indices, X.indptr,
                      X.shape)
        z = _csr_matvec(X64, w)
    else:
        X64 = np.asarray(X, np.float64)
        z = X64 @ w
    f = c * np.sum(np.logaddexp(0.0, -y * z)) + np.sum(np.abs(w))
    u = c * y * (1.0 / (1.0 + np.exp(-y * z)) - 1.0)
    g = _csr_rmatvec(X64, u) if hasattr(X, "indptr") else X64.T @ u
    v = np.where(w > 0, g + 1.0,
                 np.where(w < 0, g - 1.0, np.maximum(np.abs(g) - 1.0, 0.0)))
    return float(f), float(np.max(np.abs(v)))


def report_weights(path):
    """-> (report, w) from a `launch.solve --out` report."""
    with open(path) as fh:
        rep = json.load(fh)
    w = np.zeros((int(rep["n_features"]),), np.float64)
    w[np.asarray(rep["w_indices"], np.int64)] = rep["w_values"]
    return rep, w


# -- phases ----------------------------------------------------------------------

def solve_routes(name, argv, X, y, c, out_dir, lower_outer):
    """Run launch.solve twice per route (cold, then warm), recheck each
    route's F and KKT in float64, and return {route: F}."""
    from repro.kernels import ops
    from repro.launch import solve
    objectives = {}
    for route, extra in (("xla", []), ("kernels", ["--use-kernels"])):
        walls = []
        report = os.path.join(out_dir, f"{name}-{route}.json")
        for _ in range(2):
            t0 = time.perf_counter()
            solve.main(argv + extra + ["--out", report])
            walls.append(time.perf_counter() - t0)
        rep, w = report_weights(report)
        f64, kkt64 = host_objective_kkt(X, y, w, c)
        custom = "tpu_custom_call" in lower_outer(bool(extra))
        print(f"[smoke] {name} route={route} F={rep['objective']:.9g} "
              f"F64={f64:.9g} rel={rel(rep['objective'], f64):.2e} "
              f"kkt={rep.get('history', {}).get('kkt', [None])[-1]} "
              f"kkt64={kkt64:.3e} converged={rep['converged']} "
              f"nnz={rep['nnz']} n_outer={len(rep['history']['kkt'])} "
              f"solve_s={rep['seconds']:.3f} first_call_s={walls[0]:.2f} "
              f"second_call_s={walls[1]:.2f} tpu_custom_call={custom}",
              flush=True)
        check(rel(rep["objective"], f64) <= F_RECHECK_RTOL,
              f"{name}/{route}: reported F {rep['objective']!r} vs float64 "
              f"recomputation {f64!r}")
        check(custom == (bool(extra) and not ops.interpret_mode()),
              f"{name}/{route}: tpu_custom_call in the solver step is "
              f"{custom}")
        objectives[route] = rep["objective"]
    r = rel(objectives["kernels"], objectives["xla"])
    print(f"[smoke] {name} routes agree: rel={r:.2e}", flush=True)
    check(r <= ROUTE_RTOL, f"{name}: routes disagree, rel {r:.2e}")
    return objectives


def _outer_lowering(X, y, c, P, layout):
    """Lowered text of the local solver's outer step on this device, per
    route: shows whether the kernel route really issues Mosaic calls."""
    import jax
    import jax.numpy as jnp
    from repro.core import PCDNConfig, make_problem
    from repro.engine import LocalBackend
    prob = make_problem(X, y, c=c, layout=layout)

    def lower(use_kernels):
        b = LocalBackend(prob, PCDNConfig(P=P, use_kernels=use_kernels))
        st = b.init_state()
        return jax.jit(b.outer).lower(st.w, st.z, st.key, st.active,
                                      jnp.bool_(True),
                                      jnp.float32(c)).as_text()
    return lower


def dense_phase(out_dir, seed, scale=1.0):
    from repro.data import paper_like
    Xtr, ytr, _, _, spec = paper_like("gisette", scale=scale, seed=seed,
                                      with_test=True)
    c = spec.c_logistic
    print(f"[smoke] dense gisette: X {Xtr.shape} (train split of "
          f"{spec.s}x{spec.n} at scale {scale}) c={c}", flush=True)
    argv = ["--dataset", "gisette", "--scale", str(scale), "--seed",
            str(seed), "--P", "256", "--tol", str(TOL), "--max-outer",
            str(GISETTE_MAX_OUTER)]
    solve_routes("gisette", argv, Xtr, ytr, c, out_dir,
                 _outer_lowering(Xtr, ytr, c, 256, "dense"))


def write_realsim(out_dir, seed, s, n, nnz_per_col):
    from repro.data.libsvm import padded_csc_to_csr, save_libsvm
    from repro.data.synthetic import make_sparse_classification
    t0 = time.perf_counter()
    Xp, y, _ = make_sparse_classification(s, n, nnz_per_col=nnz_per_col,
                                          seed=seed)
    csr = padded_csc_to_csr(Xp)
    path = os.path.join(out_dir, "real-sim.svm")
    save_libsvm(path, csr, y)
    print(f"[smoke] real-sim: {s}x{n}, nnz={csr.nnz} (padded k_max="
          f"{Xp.k_max}), written to {path} in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    return path


def sparse_phase(out_dir, seed, s=57_848, n=20_958,
                 nnz_per_col=REALSIM_NNZ_PER_COL, p_support=P_SUPPORT,
                 points=4):
    from repro.data import load_libsvm
    from repro.data.synthetic import PAPER_DATASETS
    from repro.launch import path as launch_path
    svm = write_realsim(out_dir, seed, s, n, nnz_per_col)
    X, y = load_libsvm(svm, layout="csr")
    c = PAPER_DATASETS["real-sim"].c_logistic
    argv = ["--dataset", svm, "--layout", "padded_csc", "--c", str(c),
            "--seed", str(seed), "--P", str(p_support), "--tol", str(TOL),
            "--max-outer", str(REALSIM_MAX_OUTER)]
    solve_routes("real-sim", argv, X, y, c, out_dir,
                 _outer_lowering(X, y, c, p_support, "padded_csc"))

    family = os.path.join(out_dir, "real-sim-family.json")
    t0 = time.perf_counter()
    payload = launch_path.main([
        "--dataset", svm, "--layout", "padded_csc", "--points",
        str(points), "--span", "20", "--P", "256", "--tol", str(TOL),
        "--max-outer", str(PATH_MAX_OUTER), "--seed", str(seed),
        "--use-kernels",
        "--save-model", family])
    wall = time.perf_counter() - t0
    print(f"[smoke] path sweep: {len(payload['points'])} points in "
          f"{wall:.2f}s (warm, --use-kernels), family at {family}",
          flush=True)
    for p in payload["points"]:
        print(f"[smoke]   c={p['c']:.6g} F={p['objective']:.9g} "
              f"nnz={p['nnz']} kkt={p['kkt']:.3e} n_outer={p['n_outer']} "
              f"converged={p['converged']}", flush=True)
    return svm, X, y, family


def serve_phase(out_dir, X, y, family, rows=SERVE_ROWS,
                n_requests=SERVE_REQUESTS):
    from repro.data.libsvm import CSRMatrix, save_libsvm
    from repro.launch import predict
    from repro.serve.artifact import load_model
    from repro.serve.predict import ModelBank
    hi = X.indptr[rows]
    sub = CSRMatrix(X.data[:hi], X.indices[:hi], X.indptr[:rows + 1],
                    (rows, X.shape[1]))
    reqs = os.path.join(out_dir, "requests.svm")
    save_libsvm(reqs, sub, y[:rows])
    bank = ModelBank.from_family(load_model(family))
    W = np.asarray(bank.dense_matrix(), np.float64)       # (K, n)
    bias = np.asarray(bank.bias, np.float64)
    Xd = sub.to_dense().astype(np.float64)
    for route, extra in (("xla", []), ("kernels", ["--use-kernels"])):
        t0 = time.perf_counter()
        out = predict.main(["--model", family, "--dataset", reqs,
                            "--serve", "--serve-requests", str(n_requests),
                            "--rate", "400", "--slo-ms", "200",
                            "--max-batch", "32"] + extra)
        wall = time.perf_counter() - t0
        z = np.asarray(out["margins"], np.float64)
        sel = np.arange(z.shape[0]) % rows
        z64 = Xd[sel] @ W.T + bias[None, :]
        err = float(np.max(np.abs(z - z64)))
        scale = max(1.0, float(np.max(np.abs(z64))))
        print(f"[smoke] serve route={route}: responses={out['responses']} "
              f"failed={out['failed']} rejects={out['rejects']} "
              f"recompiles={out['recompiles']} K={bank.n_models} "
              f"p50_ms={1e3 * (out['p50_s'] or 0):.3f} "
              f"p99_ms={1e3 * (out['p99_s'] or 0):.3f} "
              f"margin_max_abs_err={err:.3e} (scale {scale:.3g}) "
              f"wall_s={wall:.2f}", flush=True)
        check(out["failed"] == 0 and out["rejects"] == 0
              and out["responses"] == n_requests,
              f"serve/{route}: {out['responses']} of {n_requests} answered")
        check(out["recompiles"] == 0,
              f"serve/{route}: {out['recompiles']} recompiles under traffic")
        check(err <= MARGIN_RTOL * scale,
              f"serve/{route}: margins off host scoring by {err:.3e}")


def four_chip_phase(out_dir, seed, s=57_848, n=20_958,
                    nnz_per_col=REALSIM_NNZ_PER_COL):
    import jax
    from repro.data import load_libsvm
    from repro.data.synthetic import PAPER_DATASETS
    from repro.launch import solve
    from repro.launch.mesh import make_host_mesh
    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    mesh = make_host_mesh(2, 2)
    ids = sorted({d.id for d in mesh.devices.flat})
    print(f"[smoke] mesh {dict(mesh.shape)} over devices {ids}", flush=True)
    check(len(ids) == 4, f"mesh spans {len(ids)} distinct devices")
    svm = write_realsim(out_dir, seed, s, n, nnz_per_col)
    X, y = load_libsvm(svm, layout="csr")
    c = PAPER_DATASETS["real-sim"].c_logistic
    argv = ["--dataset", svm, "--layout", "padded_csc", "--c", str(c),
            "--seed", str(seed), "--P", "256", "--tol", str(TOL),
            "--max-outer", str(REALSIM_MAX_OUTER)]
    objectives = {}
    for name, extra in (("sharded-2x2", ["--backend", "sharded",
                                         "--data-parallel", "2",
                                         "--model-parallel", "2"]),
                        ("local-1chip", ["--backend", "local"])):
        report = os.path.join(out_dir, f"real-sim-{name}.json")
        t0 = time.perf_counter()
        solve.main(argv + extra + ["--out", report])
        wall = time.perf_counter() - t0
        rep, w = report_weights(report)
        f64, kkt64 = host_objective_kkt(X, y, w, c)
        print(f"[smoke] {name}: F={rep['objective']:.9g} F64={f64:.9g} "
              f"kkt64={kkt64:.3e} converged={rep['converged']} "
              f"n_outer={len(rep['history']['kkt'])} "
              f"solve_s={rep['seconds']:.3f} wall_s={wall:.2f}", flush=True)
        check(rel(rep["objective"], f64) <= F_RECHECK_RTOL,
              f"{name}: reported F {rep['objective']!r} vs float64 "
              f"{f64!r}")
        objectives[name] = rep["objective"]
    r = rel(objectives["sharded-2x2"], objectives["local-1chip"])
    print(f"[smoke] sharded vs one chip: rel={r:.2e}", flush=True)
    check(r <= ROUTE_RTOL, f"sharded vs one-chip objectives rel {r:.2e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir",
                    default=os.path.join(HERE, ".smoke_out"))
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded 2x2 solve vs the one-chip solve")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{d0.platform!r} ({d0.device_kind}). Nothing was run.",
              file=sys.stderr)
        return 2
    from repro.kernels import ops
    print(f"[smoke] device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} interpret_mode={ops.interpret_mode()} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)
    if ops.interpret_mode():
        print("chip_smoke: Pallas kernels would run interpreted",
              file=sys.stderr)
        return 2
    os.makedirs(args.out_dir, exist_ok=True)

    if args.four_chips:
        phases = [("four-chips", lambda: four_chip_phase(args.out_dir,
                                                         args.seed))]
    else:
        state = {}

        def sparse():
            state["svm"], state["X"], state["y"], state["family"] = \
                sparse_phase(args.out_dir, args.seed)

        phases = [("dense", lambda: dense_phase(args.out_dir, args.seed)),
                  ("sparse", sparse),
                  ("serve", lambda: serve_phase(args.out_dir, state["X"],
                                                state["y"],
                                                state["family"]))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:      # report every phase, then fail
            failed.append(name)
            traceback.print_exc()
            print(f"[smoke] phase {name} FAILED after "
                  f"{time.perf_counter() - t0:.1f}s: {e}", flush=True)
            if name == "sparse":
                break               # serve needs the sparse phase's family
            continue
        print(f"[smoke] phase {name} passed in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
