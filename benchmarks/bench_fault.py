"""Fault-tolerance cost benchmark -> BENCH_fault.json (DESIGN.md §16).

    PYTHONPATH=src python benchmarks/bench_fault.py [--smoke]

Two questions, two arms:

  * checkpoint — what does crash-safety COST when nothing crashes? A
    fixed-iteration PCDN solve (tol_kkt=0 so both arms do identical
    solver work) timed bare vs with a `SolveCheckpointer` snapshotting
    every 10th iteration (the `--ckpt-every` default). The headline
    `checkpoint.overhead_pct` is the acceptance number: crash-safety
    must cost <= 5% of solve wall time at the default cadence.

  * recovery — does recovery actually RECOVER? The real `launch.path`
    CLI is SIGKILL'd mid-sweep via the `REPRO_FAULT_PLAN` env channel
    (no test-only flags), resumed with `--resume`, and the resumed
    report is compared point-by-point against an uninterrupted run.
    `recovery.objective_rel_diff` is the acceptance number (<= 1e-6:
    the sweep checkpoints full solver state at point granularity, so
    resume is exact, not approximate), `recovery.resume_seconds` the
    headline cost of picking the sweep back up.

Smoke mode writes only to benchmarks/results/ (CI); the full run also
writes the repo-root BENCH_fault.json that `sentinel.py` gates.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax

from repro.utils.compile_cache import enable_compile_cache
from repro.core import PCDNConfig, make_problem
from repro.data.synthetic import make_classification
from repro.engine import LocalBackend
from repro.engine import loop as engine_loop
from repro.fault import SolveCheckpointer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


def _time_pair(fn_a, fn_b, repeats: int = 5):
    """Best-of-N for two arms with INTERLEAVED repeats (A B A B ...), so
    machine-load drift hits both arms equally. Warmed before timing."""
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


def bench_checkpoint(s, n, P, iters, every, repeats, seed=0):
    """Bare vs checkpointing wall time on identical solver work."""
    X, y, _ = make_classification(s, n, sparsity=0.5, seed=seed)
    prob = make_problem(X, y, c=2.0)
    backend = LocalBackend(prob, PCDNConfig(P=P, max_outer=iters,
                                            tol_kkt=0.0, seed=seed))
    ckdir = tempfile.mkdtemp(prefix="bench_fault_ck_")

    def run(state_cb):
        _, res = engine_loop.run_outer_loop(
            backend.outer, backend.init_state(), prob.c,
            max_outer=iters, tol_kkt=0.0, state_callback=state_cb)
        return res

    def run_bare():
        return run(None)

    def run_ckpt():
        ck = SolveCheckpointer(ckdir, every=every)
        return run(ck.solve_callback(backend))

    try:
        t_bare, t_ckpt = _time_pair(run_bare, run_ckpt, repeats)
        res_bare = run_bare()
        res_ckpt = run_ckpt()
        n_steps = len(SolveCheckpointer(ckdir, every=every).manager.steps())
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    # identical solver work: snapshots observe the carry, never touch it
    drift = abs(res_ckpt.objective - res_bare.objective) \
        / max(1.0, abs(res_bare.objective))
    overhead = (t_ckpt - t_bare) / t_bare * 100.0
    row = {
        "s": s, "n": n, "P": P, "iters": iters, "every": every,
        "bare_s": t_bare, "ckpt_s": t_ckpt,
        "overhead_pct": overhead,
        "objective_rel_drift": drift,
        "committed_steps": n_steps,
    }
    print(f"[checkpoint] {iters} iters (s={s}, n={n}, P={P}, "
          f"every={every}): bare {t_bare * 1e3:.1f}ms, ckpt "
          f"{t_ckpt * 1e3:.1f}ms -> {overhead:+.2f}% overhead, "
          f"{n_steps} committed steps, drift {drift:.1e}", flush=True)
    return row


def _run_cli(args, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    env["REPRO_AUTOTUNE"] = "off"
    env.pop("REPRO_FAULT_PLAN", None)
    if extra_env:
        env.update(extra_env)
    return subprocess.run([sys.executable, "-m"] + args,
                          capture_output=True, text=True, env=env,
                          timeout=900)


def bench_recovery(dataset, points, P, max_outer, tol):
    """SIGKILL the path CLI mid-sweep, resume, compare to uninterrupted."""
    work = tempfile.mkdtemp(prefix="bench_fault_rec_")
    base = ["repro.launch.path", "--dataset", dataset,
            "--points", str(points), "--P", str(P),
            "--max-outer", str(max_outer), "--tol", str(tol)]
    try:
        ref_path = os.path.join(work, "ref.json")
        out = _run_cli(base + ["--out", ref_path])
        if out.returncode != 0:
            raise RuntimeError(f"reference sweep failed:\n{out.stderr}")
        ckdir = os.path.join(work, "ck")
        kill_at = points // 2
        killed = _run_cli(
            base + ["--ckpt-dir", ckdir],
            extra_env={"REPRO_FAULT_PLAN": json.dumps(
                {"crash_at_point": kill_at, "crash_kind": "sigkill"})})
        if killed.returncode != -9:
            raise RuntimeError(f"expected SIGKILL exit, got "
                               f"{killed.returncode}:\n{killed.stderr}")
        res_path = os.path.join(work, "res.json")
        t0 = time.perf_counter()
        resumed = _run_cli(base + ["--ckpt-dir", ckdir, "--resume",
                                   "--out", res_path])
        resume_s = time.perf_counter() - t0
        if resumed.returncode != 0:
            raise RuntimeError(f"resume failed:\n{resumed.stderr}")
        with open(ref_path) as fh:
            ref = json.load(fh)
        with open(res_path) as fh:
            res = json.load(fh)
        rel = max(
            abs(a["objective"] - b["objective"]) / abs(a["objective"])
            for a, b in zip(ref["points"], res["points"]))
        row = {
            "dataset": dataset, "points": points, "P": P,
            "max_outer": max_outer, "tol": tol,
            "killed_at_point": kill_at,
            "sigkill_exit": killed.returncode,
            "resume_seconds": resume_s,
            "best_index_matches": ref["best_index"] == res["best_index"],
            "objective_rel_diff": rel,
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(f"[recovery] {dataset} {points}-point sweep SIGKILL'd at point "
          f"{kill_at}: resumed in {resume_s:.2f}s, max objective rel "
          f"diff {rel:.2e}, best_index match="
          f"{row['best_index_matches']}", flush=True)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        s, n, P, iters, repeats = 400, 300, 64, 20, 3
        points, sweep_P, sweep_outer = 3, 64, 10
    else:
        s, n, P, iters, repeats = 2000, 2000, 256, 40, 5
        points, sweep_P, sweep_outer = 6, 64, 25

    # the recovery arm's CLI children need the device, and a process
    # that has run JAX work holds it: run them before this process
    # computes anything
    recovery_row = bench_recovery("a9a", points, sweep_P, sweep_outer,
                                  tol=1e-3)
    ckpt_row = bench_checkpoint(s, n, P, iters, every=10, repeats=repeats)

    payload = {
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "checkpoint": ckpt_row,
        "recovery": recovery_row,
    }
    print(f"[fault] HEADLINE checkpoint overhead at every=10: "
          f"{ckpt_row['overhead_pct']:+.2f}% (acceptance: <= 5%); "
          f"resumed-sweep objective rel diff "
          f"{recovery_row['objective_rel_diff']:.2e} "
          f"(acceptance: <= 1e-6)", flush=True)

    os.makedirs(RESULTS_DIR, exist_ok=True)
    targets = [os.path.join(RESULTS_DIR, "BENCH_fault.json")]
    if not args.smoke:
        targets.append(os.path.join(REPO_ROOT, "BENCH_fault.json"))
    for path in targets:
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
    print("wrote BENCH_fault.json")
    return payload


if __name__ == "__main__":
    main()
