"""Telemetry overhead benchmark -> BENCH_obs.json (DESIGN.md section 13).

    PYTHONPATH=src python benchmarks/bench_obs.py [--smoke]

Measures what the observability subsystem costs when it is ON and proves
it costs nothing when it is OFF:

  * solve — a fixed-iteration PCDN solve (tol_kkt=0 so both arms do
    identical solver work) timed with telemetry disabled (record_aux off,
    registry off, tracer off) vs fully enabled (per-bundle (q, alpha)
    aux outputs + registry counters/histograms + trace spans). The
    headline `solve.overhead_pct` is the acceptance number: the enabled
    plane must cost <= 5% of solve wall time.

  * batcher — the serving front-end under a steady padded-bucket stream,
    same disabled-vs-enabled comparison (per-chunk latency histograms,
    counters and trace events are the instrumented path).

  * sharded — a 1x1-mesh ShardedBackend arm asserting the aux series
    (bundle_q / bundle_alpha) actually reach SolveHistory through
    shard_map, i.e. the telemetry plane exists on the mesh backend too.

The enabled arm records a real trace, which the benchmark validates with
`repro.obs.trace.validate_trace` before reporting — the emitted file
format is checked, not assumed. Smoke mode writes only to
benchmarks/results/ (CI); the full run also writes the repo-root
BENCH_obs.json that the acceptance criterion reads.
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
from repro import obs
from repro.core import PCDNConfig, make_problem, solve
from repro.data.synthetic import make_classification
from repro.engine import ShardedBackend, ShardedPCDNConfig
from repro.engine import loop as engine_loop
from repro.launch.mesh import make_host_mesh
from repro.serve.batcher import MicroBatcher
from repro.serve.predict import ModelBank

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")


def _time(fn, repeats: int = 5) -> float:
    """Best-of-N seconds per call, post-warmup (compile excluded)."""
    fn()                                   # warmup / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _time_pair(fn_a, fn_b, repeats: int = 5):
    """Best-of-N for two arms with INTERLEAVED repeats (A B A B ...), so
    slow machine-load drift hits both arms equally — back-to-back arm
    timing is exactly how a 2.4s solve reads as 13% slower than itself
    on a noisy box. Both arms are warmed before any timing."""
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


def _problem(s: int, n: int, c: float = 2.0, seed: int = 0):
    X, y, _ = make_classification(s, n, sparsity=0.5, seed=seed)
    return make_problem(X, y, c=c)


def bench_solve(s, n, P, iters, repeats, seed=0):
    """Disabled-vs-enabled wall time on identical solver work: tol_kkt=0
    pins both arms to exactly `iters` outer iterations."""
    prob = _problem(s, n, seed=seed)
    cfg_off = PCDNConfig(P=P, max_outer=iters, tol_kkt=0.0, seed=seed)
    cfg_on = dataclasses.replace(cfg_off, record_aux=True)

    def run_off():
        obs.disable()
        return solve(prob, cfg_off)

    def run_on():
        # re-enabling resets the tracer, so timed repeats do not grow an
        # unbounded in-memory event list
        obs.enable(metrics=True, trace_=True, process_name="bench_obs")
        return solve(prob, cfg_on)

    t_off, t_on = _time_pair(run_off, run_on, repeats)
    res_off = run_off()
    res_on = run_on()
    snap = obs.registry.get_registry().snapshot()
    trace_obj = obs.trace.get_tracer().to_dict()
    n_events = obs.trace.validate_trace(trace_obj)
    obs.disable()

    assert res_on.history.bundle_q is not None, \
        "enabled arm must thread per-bundle q into SolveHistory"
    assert res_off.history.bundle_q is None, \
        "disabled arm must not carry aux series"
    # identical solver work: the aux outputs ride along, they do not
    # perturb the iterates
    drift = abs(res_on.objective - res_off.objective) \
        / max(1.0, abs(res_off.objective))
    overhead = (t_on - t_off) / t_off * 100.0
    row = {
        "s": s, "n": n, "P": P, "iters": iters,
        "disabled_s": t_off, "enabled_s": t_on,
        "overhead_pct": overhead,
        "objective_rel_drift": drift,
        "bundle_q_shape": list(res_on.history.bundle_q.shape),
        "registry_counters": {k: v for k, v in snap["counters"].items()},
        "trace_events": n_events,
    }
    print(f"[solve] {iters} iters (s={s}, n={n}, P={P}): disabled "
          f"{t_off * 1e3:.1f}ms, enabled {t_on * 1e3:.1f}ms -> "
          f"{overhead:+.2f}% overhead, {n_events} trace events, "
          f"drift {drift:.1e}", flush=True)
    return row, trace_obj


def bench_batcher(K, n, n_requests, buckets, repeats, seed=0):
    """Steady-state batcher stream, disabled vs enabled registry+trace.
    Buckets are warmed first so neither arm pays compiles."""
    rng = np.random.default_rng(seed + 3)
    nnz = max(1, n // 100)
    W = np.zeros((K, n), np.float32)
    for k in range(K):
        sup = rng.choice(n, size=nnz, replace=False)
        W[k, sup] = rng.standard_normal(nnz).astype(np.float32)
    bank = ModelBank.from_dense(W, kind="path")
    X = rng.standard_normal((n_requests, n)).astype(np.float32)
    sizes = rng.integers(1, buckets[-1] + 1, size=32)

    def stream(batcher):
        start = 0
        for r in sizes:
            stop = min(start + int(r), n_requests)
            if stop <= start:
                start, stop = 0, int(r)
            batcher.predict(X[start:stop])
            start = stop

    def warmed():
        b = MicroBatcher(bank, buckets=buckets, layout="dense")
        for bk in buckets:
            b.predict(X[:bk])
        return b

    obs.disable()
    b_off = warmed()
    obs.enable(metrics=True, trace_=True, process_name="bench_obs")
    b_on = warmed()

    def run_off():
        obs.disable()
        stream(b_off)

    def run_on():
        obs.enable(metrics=True, trace_=True, process_name="bench_obs")
        stream(b_on)

    t_off, t_on = _time_pair(run_off, run_on, repeats)
    stats_on = b_on.stats()
    obs.disable()

    overhead = (t_on - t_off) / t_off * 100.0
    row = {
        "K": K, "n": n, "stream_batches": len(sizes),
        "disabled_s": t_off, "enabled_s": t_on,
        "overhead_pct": overhead,
        "latency_p50_s": stats_on.get("latency_p50_s"),
        "latency_p99_s": stats_on.get("latency_p99_s"),
    }
    print(f"[batcher] {len(sizes)}-batch stream: disabled "
          f"{t_off * 1e3:.1f}ms, enabled {t_on * 1e3:.1f}ms -> "
          f"{overhead:+.2f}% overhead", flush=True)
    return row


def bench_sharded(s, n, P, iters, seed=0):
    """1x1-mesh aux presence: the per-bundle (q, alpha) series must come
    out of the shard_map program and land in SolveHistory."""
    X, y, _ = make_classification(s, n, sparsity=0.5, seed=seed)
    mesh = make_host_mesh(1, 1)
    cfg = ShardedPCDNConfig(P_local=P, c=2.0, seed=seed, record_aux=True)
    backend = ShardedBackend(X, y, mesh, cfg)
    res = engine_loop.solve(backend, 2.0, max_outer=iters, tol_kkt=0.0)
    assert res.history.bundle_q is not None \
        and res.history.bundle_alpha is not None, \
        "sharded backend must thread aux through shard_map"
    row = {"mesh": [1, 1], "iters": res.n_outer,
           "aux_present": True,
           "bundle_q_shape": list(res.history.bundle_q.shape),
           "mean_q": float(np.mean(
               res.history.bundle_q[res.history.bundle_q >= 0]))}
    print(f"[sharded] 1x1 mesh: bundle_q {row['bundle_q_shape']} "
          f"mean_q={row['mean_q']:.2f}", flush=True)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        s, n, P, iters, repeats = 400, 300, 64, 10, 3
        K, bank_n, n_requests, buckets = 8, 4096, 512, (16, 64)
        sh_s, sh_n, sh_P, sh_iters = 200, 150, 32, 5
    else:
        s, n, P, iters, repeats = 2000, 2000, 256, 40, 5
        K, bank_n, n_requests, buckets = 16, 16384, 2048, (16, 64, 256)
        sh_s, sh_n, sh_P, sh_iters = 600, 500, 64, 10

    solve_row, trace_obj = bench_solve(s, n, P, iters, repeats)
    batcher_row = bench_batcher(K, bank_n, n_requests, buckets, repeats)
    sharded_row = bench_sharded(sh_s, sh_n, sh_P, sh_iters)

    payload = {
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "solve": solve_row,
        "batcher": batcher_row,
        "sharded": sharded_row,
        "trace_valid": True,
        "trace_events": solve_row["trace_events"],
    }
    print(f"[obs] HEADLINE solve overhead (enabled vs disabled): "
          f"{solve_row['overhead_pct']:+.2f}% "
          f"(acceptance: <= 5%)", flush=True)

    os.makedirs(RESULTS_DIR, exist_ok=True)
    targets = [os.path.join(RESULTS_DIR, "BENCH_obs.json")]
    if not args.smoke:
        targets.append(os.path.join(REPO_ROOT, "BENCH_obs.json"))
    for path in targets:
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
    # the trace the enabled arm recorded, for schema validation in CI
    trace_path = os.path.join(RESULTS_DIR, "bench_obs_trace.json")
    with open(trace_path, "w") as fh:
        json.dump(trace_obj, fh)
    print(f"wrote BENCH_obs.json + {os.path.basename(trace_path)}")
    return payload


if __name__ == "__main__":
    main()
