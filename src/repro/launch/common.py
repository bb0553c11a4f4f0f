"""Shared CLI plumbing for `repro.launch.solve` and `repro.launch.path`.

One place defines the flags both drivers share — `--backend / --layout /
--shrink / --warm-start / --use-kernels` plus the solver stop knobs and
the mesh shape — and one place builds the solver configs and execution
backends from them, so the flags behave identically in both CLIs
(DESIGN.md section 9.4).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import PCDNConfig, make_problem
from repro.data import load_libsvm, paper_like
from repro.engine import LocalBackend, ShardedBackend, ShardedPCDNConfig
from repro.engine.sharded import host_c_max
from repro.launch.mesh import make_host_mesh
from repro.path.driver import sweep_backend

# --dtype values -> storage dtype of the design values / serve bank
# (solver state stays f32 either way — DESIGN.md section 12)
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
DTYPE_NAMES = {"fp32": "float32", "bf16": "bfloat16"}

# the studied bf16 equivalence envelope (the BENCH_kernels.json
# trajectory study, DESIGN.md section 12): losses it covers and the
# tightest stopping tolerance the measured objective rel-diff supports
BF16_LOSSES = ("logistic", "squared_hinge")
BF16_MIN_TOL = 1e-3


def check_dtype_envelope(args, ap: argparse.ArgumentParser,
                         loss: str | None = None):
    """Refuse bf16 outside the studied equivalence envelope.

    The bf16-vs-fp32 trajectory study (BENCH_kernels.json, DESIGN.md
    section 12) covers the LOCAL backend with the logistic and
    squared-hinge losses down to a max objective rel-diff of ~1e-3 at
    matched iteration counts; anything beyond that is unvalidated, so
    the CLI rejects it instead of silently returning drifted solutions.
    """
    if getattr(args, "dtype", "fp32") != "bf16":
        return
    if getattr(args, "backend", "local") == "sharded":
        ap.error("--dtype bf16 is unstudied on --backend sharded "
                 "(the equivalence study covers the local backend only); "
                 "use --dtype fp32 or --backend local")
    if loss is not None and loss not in BF16_LOSSES:
        ap.error(f"--dtype bf16 is unstudied for loss {loss!r} "
                 f"(studied envelope: {', '.join(BF16_LOSSES)})")
    tol = getattr(args, "tol", None)
    if tol is not None and tol < BF16_MIN_TOL:
        ap.error(f"--tol {tol:g} is tighter than the bf16 equivalence "
                 f"envelope (max objective rel-diff ~{BF16_MIN_TOL:g}); "
                 f"use --tol >= {BF16_MIN_TOL:g} or --dtype fp32")


def add_backend_args(ap: argparse.ArgumentParser):
    """Execution-backend selection, identical in both CLIs."""
    ap.add_argument("--backend", default="local",
                    choices=["local", "sharded"],
                    help="execution backend (DESIGN.md section 9): local "
                         "single-program XLA, or the shard_map mesh "
                         "implementation")
    ap.add_argument("--layout", default="auto",
                    choices=["auto", "dense", "padded_csc"],
                    help="design-matrix backend; padded_csc never "
                         "densifies a .libsvm input (DESIGN.md section 7)")
    ap.add_argument("--data-parallel", type=int, default=1,
                    help="mesh data-axis size (--backend sharded)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="mesh model-axis size (--backend sharded)")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route bundle math through the fused Pallas "
                         "direction kernels (both backends)")
    ap.add_argument("--dtype", default="fp32", choices=["fp32", "bf16"],
                    help="storage dtype of the design values (DESIGN.md "
                         "section 12): bf16 halves design memory/HBM "
                         "traffic with f32 accumulation everywhere; "
                         "gated to the studied equivalence envelope "
                         "(local backend, logistic/squared_hinge, "
                         "--tol >= 1e-3)")


def add_solver_args(ap: argparse.ArgumentParser):
    """PCDN knobs shared by the single-solve and the path drivers."""
    ap.add_argument("--P", type=int, default=256, help="bundle size")
    ap.add_argument("--tol", type=float, default=1e-3)
    ap.add_argument("--max-outer", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=None,
                    help="size of a profile --dataset relative to its "
                         "published shape (1.0 = published; default: the "
                         "CPU-budget shape)")
    ap.add_argument("--shrink", action="store_true",
                    help="active-set shrinking (DESIGN.md section 8.2; "
                         "both backends)")
    ap.add_argument("--ls-scope", default="auto",
                    choices=["auto", "support", "full"],
                    help="line-search / margin-maintenance scope "
                         "(DESIGN.md section 11): 'support' restricts "
                         "every per-sample pass of a bundle step to the "
                         "bundle's row support (padded_csc layout; "
                         "O(P*k_max*Q) instead of O(s*Q)); 'auto' picks "
                         "it whenever it wins; both backends")
    ap.add_argument("--warm-start", default=None, metavar="CKPT",
                    help="w0 from a .npy vector or a JSON file (a dense "
                         "list or the sparse weight record a previous "
                         "--out report carries); both backends")


def add_obs_args(ap: argparse.ArgumentParser):
    """Telemetry flags, identical in the solve / path / predict CLIs
    (README "Observability"; DESIGN.md section 13)."""
    ap.add_argument("--metrics-out", default=None, metavar="JSONL",
                    help="enable the metrics registry and append one "
                         "JSONL run record (counters, gauges, p50/p99 "
                         "histograms) to this file on exit; "
                         "REPRO_METRICS=off force-disables")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="record a jax.profiler trace of the run under "
                         "DIR: the program's spans and the device "
                         "operations on one clock (the .xplane.pb, and "
                         "a perfetto_trace.json.gz that ui.perfetto.dev "
                         "loads)")


def add_diag_args(ap: argparse.ArgumentParser):
    """Diagnostics flags, identical in the solve / path CLIs
    (README "Diagnostics"; DESIGN.md section 15)."""
    ap.add_argument("--diag-out", default=None, metavar="MD",
                    help="write a markdown solver-health report here "
                         "(top-k KKT offenders, backtrack forensics, "
                         "certified-P table); turns on the per-feature "
                         "KKT attribution harvest (record_kkt_vec) and "
                         "the per-bundle aux for this run")
    ap.add_argument("--progress", action="store_true",
                    help="live one-line solve status on stderr (iter, "
                         "objective, KKT, mean_q); off by default so CI "
                         "logs stay clean")


def add_fault_args(ap: argparse.ArgumentParser):
    """Fault-tolerance flags, identical in the solve / path CLIs
    (README "Robustness"; DESIGN.md section 16)."""
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="crash-safe checkpoint directory (atomic "
                         "write-then-rename with a COMMITTED marker); "
                         "solve runs snapshot every --ckpt-every "
                         "iterations, path sweeps after every grid "
                         "point; checkpoints are mesh-agnostic host "
                         "arrays, so a run can resume on a different "
                         "device count")
    ap.add_argument("--ckpt-every", type=int, default=10, metavar="N",
                    help="solve-checkpoint cadence in outer iterations "
                         "(default 10; path sweeps always checkpoint "
                         "per point)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest committed checkpoint "
                         "in --ckpt-dir (incomplete or corrupted steps "
                         "are skipped); the resumed run reproduces the "
                         "uninterrupted one bit-for-bit")
    ap.add_argument("--retries", type=int, default=2, metavar="K",
                    help="max non-finite rollbacks before the solve "
                         "surfaces the post-mortem (each retry halves "
                         "the bundle size toward the certified safe P; "
                         "DESIGN.md section 16.3)")


def make_checkpointer(args, ap: argparse.ArgumentParser):
    """The `fault.SolveCheckpointer` behind --ckpt-dir, or None."""
    if getattr(args, "resume", False) and not getattr(args, "ckpt_dir", None):
        ap.error("--resume needs --ckpt-dir")
    if not getattr(args, "ckpt_dir", None):
        return None
    from repro.fault import SolveCheckpointer
    if args.ckpt_every < 1:
        ap.error(f"--ckpt-every must be >= 1, got {args.ckpt_every}")
    return SolveCheckpointer(args.ckpt_dir, every=args.ckpt_every)


def make_progress_callback(args):
    """The engine callback behind `--progress`: one stderr status line,
    rewritten in place (carriage return, no scroll). Returns None when
    the flag is off so the engine loop skips the call entirely."""
    if not getattr(args, "progress", False):
        return None
    import sys

    def cb(k, w, f, kkt, mean_q):
        print(f"\r[progress] iter {k:4d}  F={f:.6f}  kkt={kkt:.3e}  "
              f"mean_q={mean_q:5.2f}", end="", file=sys.stderr, flush=True)
    return cb


def finish_progress(args) -> None:
    """Terminate the in-place `--progress` line before normal output."""
    if getattr(args, "progress", False):
        import sys
        print(file=sys.stderr, flush=True)


def write_diag(args, report: dict, design=None, tol_kkt=None) -> None:
    """Render the `--diag-out` health report (DESIGN.md section 15.4).

    `report` is the same payload `--out` writes (history + provenance +
    optional postmortem); when `design` is given the certified-P table
    is computed here — the CLI already holds the design matrix, so the
    report never reloads the dataset.
    """
    if not getattr(args, "diag_out", None):
        return
    from repro import diag
    safep_record = None
    if design is not None:
        safep_record = diag.safep.certify(
            design, seed=getattr(args, "seed", 0),
            observed_p=getattr(args, "P", None))
        report.setdefault("diag", {})["safep"] = safep_record
    payload = diag.build_payload(report=report,
                                 safep_record=safep_record,
                                 tol_kkt=tol_kkt)
    with open(args.diag_out, "w") as fh:
        fh.write(diag.render_markdown(payload))
    print(f"[diag] health report written to {args.diag_out}")


# The persistent compile cache keys a program without its op metadata, so
# a traced run could load an executable compiled without the `pcdn.*`
# scopes (or under older names) and its device operations would name no
# phase. A traced run keys the cache by the metadata too.
_METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"
_metadata_key_before = False


def setup_obs(args) -> None:
    """Switch the telemetry planes on per the CLI flags (before any
    instrumented work runs)."""
    if getattr(args, "metrics_out", None):
        obs.registry.enable()
        obs.registry.reset()
    if getattr(args, "trace_out", None):
        global _metadata_key_before
        _metadata_key_before = getattr(jax.config, _METADATA_KEY)
        jax.config.update(_METADATA_KEY, True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # spans and device ops, not each call
        jax.profiler.start_trace(args.trace_out, create_perfetto_trace=True,
                                 profiler_options=opts)


def finish_obs(args, meta: dict | None = None) -> None:
    """Flush the telemetry outputs the CLI flags requested."""
    if getattr(args, "metrics_out", None):
        obs.write_metrics(args.metrics_out, meta)
        print(f"[obs] metrics appended to {args.metrics_out}")
        obs.registry.disable()
    if getattr(args, "trace_out", None):
        jax.profiler.stop_trace()
        jax.config.update(_METADATA_KEY, _metadata_key_before)
        print(f"[obs] profiler trace written under {args.trace_out}")


def load_dataset(args, with_test: bool = False):
    """-> (X, y, Xte, yte, spec). File datasets have no test split and a
    None spec; profile names go through `paper_like`. A padded_csc file
    load stays CSR: the local problem packs it (`make_problem`), the
    sharded placer re-packs it per shard, and a caller may first split
    its rows (`launch.path --val-frac`).
    """
    scale = args.scale
    if os.path.exists(args.dataset):
        if args.layout == "padded_csc":
            file_layout = "csr"
        else:
            file_layout = "dense"
        X, y = load_libsvm(args.dataset, layout=file_layout)
        return X, y, None, None, None
    if with_test:
        Xtr, ytr, Xte, yte, spec = paper_like(args.dataset, with_test=True,
                                              seed=args.seed, scale=scale)
        return Xtr, ytr, Xte, yte, spec
    X, y, spec = paper_like(args.dataset, seed=args.seed, scale=scale)
    return X, y, None, None, spec


def build_pcdn_config(args, **overrides) -> PCDNConfig:
    """The local-backend solver config (also the stop parameters every
    backend uses — max_outer / tol_kkt come from here)."""
    kw = dict(P=args.P, max_outer=args.max_outer, tol_kkt=args.tol,
              seed=args.seed, shrink=args.shrink,
              use_kernels=args.use_kernels,
              ls_scope=getattr(args, "ls_scope", "auto"),
              dtype=DTYPE_NAMES[getattr(args, "dtype", "fp32")],
              record_aux=_record_aux(args),
              record_kkt_vec=_record_kkt_vec(args))
    kw.update(overrides)
    return PCDNConfig(**kw)


def _record_aux(args) -> bool:
    """Per-bundle (q, alpha) aux outputs ride along exactly when the CLI
    asked for metrics OR diagnostics (the histograms and the health
    report's backtrack forensics consume them) — without the flags the
    compiled iteration stays byte-identical to the uninstrumented solver
    (DESIGN.md 13.2). A profiler trace (`--trace-out`) records the
    program users run."""
    return bool(getattr(args, "metrics_out", None)
                or getattr(args, "diag_out", None))


def _record_kkt_vec(args) -> bool:
    """Per-feature KKT attribution rides along exactly when `--diag-out`
    asked for a health report (DESIGN.md section 15.1)."""
    return bool(getattr(args, "diag_out", None))


def build_sharded_config(args, c: float, loss: str) -> ShardedPCDNConfig:
    """Mirror the CLI flags onto the sharded backend's config so
    --shrink / --use-kernels / --tol mean the same thing on a mesh."""
    return ShardedPCDNConfig(
        P_local=max(args.P // max(args.model_parallel, 1), 1), c=c,
        loss_name=loss, seed=args.seed, shrink=args.shrink,
        use_kernels=args.use_kernels, tol_kkt=args.tol,
        ls_scope=getattr(args, "ls_scope", "auto"),
        record_aux=_record_aux(args),
        record_kkt_vec=_record_kkt_vec(args))


def make_backend(args, X, y, c: float, loss: str, outer=None):
    """Build the execution backend the flags describe.

    local: an `L1Problem` + `LocalBackend`; sharded: a host mesh of
    --data-parallel x --model-parallel devices + `ShardedBackend`.
    Returns (backend, problem_or_None).
    """
    if args.backend == "sharded":
        mesh = make_host_mesh(args.data_parallel, args.model_parallel)
        cfg = build_sharded_config(args, c, loss)
        return ShardedBackend(X, y, mesh, cfg, layout=args.layout), None
    prob = make_problem(X, y, c=c, loss=loss, layout=args.layout,
                        dtype=DTYPES[getattr(args, "dtype", "fp32")])
    return LocalBackend(prob, build_pcdn_config(args), outer=outer), prob


def make_path_backend(args, X, y, loss: str, cfg):
    """The flags' backend for the path sweep `cfg` (a `PathConfig`), built
    once with its compiled un-shrink threshold at the sweep's smallest
    stop (`cfg.unshrink_tol`). local: the backend `run_path` builds
    itself (`path.driver.sweep_backend`); sharded: c_max from the host
    data sets the threshold before the mesh is placed. Returns (backend,
    problem_or_None)."""
    if args.backend == "sharded":
        scfg = build_sharded_config(args, 1.0, loss)
        scfg = dataclasses.replace(scfg, tol_kkt=cfg.unshrink_tol(
            host_c_max(X, y, loss)))
        mesh = make_host_mesh(args.data_parallel, args.model_parallel)
        return ShardedBackend(X, y, mesh, scfg, layout=args.layout), None
    prob = make_problem(X, y, c=1.0, loss=loss, layout=args.layout,
                        dtype=DTYPES[getattr(args, "dtype", "fp32")])
    return sweep_backend(prob, cfg), prob


def load_warm_start(path: str, n: int, dtype) -> jnp.ndarray:
    """Load a w0 vector from .npy, or from JSON: a dense list, or the
    sparse {n_features, w_indices, w_values} record `--out` writes — so
    solve runs chain."""
    if path.endswith(".npy"):
        w = np.asarray(np.load(path), np.float64).reshape(-1)
    else:
        with open(path) as fh:
            obj = json.load(fh)
        if isinstance(obj, dict):
            if "w_indices" not in obj:
                raise ValueError(
                    f"warm start {path!r} has no weight record "
                    f"(w_indices/w_values) — reports written by older "
                    f"--out versions lack it; re-run the source solve "
                    f"or pass a .npy")
            w = np.zeros((int(obj["n_features"]),), np.float64)
            w[np.asarray(obj["w_indices"], np.int64)] = obj["w_values"]
        else:
            w = np.asarray(obj, np.float64).reshape(-1)
    if w.shape[0] != n:
        raise ValueError(
            f"warm start {path!r} has {w.shape[0]} features, problem "
            f"has {n}")
    return jnp.asarray(w, dtype)


def history_dict(history) -> dict:
    """JSON-ready SolveHistory: absent optional series (bundle_q /
    bundle_alpha are None unless the backend ran with record_aux) are
    dropped, not serialized as null."""
    return {k: np.asarray(v).tolist() for k, v in history._asdict().items()
            if v is not None}


def sparse_weight_record(w) -> dict:
    """JSON-compact (indices, values) form of an l1 solution — nnz-sized,
    so a news20-scale report stays small where a dense float list would
    be tens of MB of decimal text."""
    w = np.asarray(w, np.float64)
    idx = np.flatnonzero(w)
    return {"n_features": int(w.shape[0]),
            "w_indices": idx.tolist(),
            "w_values": w[idx].tolist()}
