"""Traffic kind "serve": open-loop Poisson requests into `ServeLoop`.

Set-up makes the configuration's data from the seed, keeps a pool of its
rows as dense request rows (the loop admits dense rows only), makes the
served model family from the seed, and starts `repro.serve.loop.ServeLoop`
with the mix's settings, which compiles every bucket's scorer before it
returns. The window then offers `rate_rps` for `seconds` through
`bench.load`, each request timed from its due time.

Every answer due in the window is checked after it against float64
scoring of the same row by the same weights (`margin_err`, the widest
gap over the margins' scale), and every request has to be answered
(`unanswered`).
"""
from __future__ import annotations

import time

import numpy as np

from bench import data, load, reference

SCORER_PROGRAM = "jit__dense_xla"   # serve.predict's union-gather scorer


def make_family(spec: dict, n: int, seed: int):
    """The served path family: `models` weight vectors with nested
    supports growing geometrically from `support_min` to `support_max`
    features, as along a warm-started path; each model's weights are the
    previous model's plus N(0, 1) weights on the features it adds, and
    every weight is perturbed by `drift` of N(0, 1).
    -> (ModelFamily, (K, n) float64 weights)."""
    from repro.serve.artifact import ModelArtifact, ModelFamily
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    k = int(spec["models"])
    sizes = np.unique(np.geomspace(spec["support_min"], spec["support_max"],
                                   k).round().astype(np.int64))
    if sizes.shape[0] != k:
        raise ValueError(f"support sizes {sizes} are not {k} distinct sizes")
    order = rng.permutation(n)
    W = np.zeros((k, n), np.float64)
    w = np.zeros((n,), np.float64)
    models = []
    for i, size in enumerate(sizes):
        sup = order[:size]
        fresh = w[sup] == 0
        w[sup[fresh]] = rng.standard_normal(int(fresh.sum()))
        w[sup] += spec["drift"] * rng.standard_normal(size)
        w = w.astype(np.float32).astype(np.float64)   # as served
        idx = np.sort(sup)
        W[i] = w
        models.append(ModelArtifact(n_features=n, w_indices=idx,
                                    w_values=w[idx], loss_name="logistic",
                                    c=float(i + 1)))
    return ModelFamily(kind="path", models=tuple(models)), W


def run(config: dict, traffic: dict, seed: int, seconds: float, tracer,
        t_setup0: float, control: bool = False) -> dict:
    from repro.launch.common import DTYPES
    from repro.serve.loop import ServeLoop

    with tracer.span("bench.data"):
        X, _ = data.build(config["data"], seed)
        n = X.shape[1]
        rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        pool_ids = np.sort(rng.choice(X.shape[0], size=config["pool_rows"],
                                      replace=False))
        pool = data.dense_rows(X, pool_ids)
        family, W = make_family(config["family"], n, seed)
    slo_s = traffic["slo_ms"] / 1e3
    with tracer.span("bench.serve_loop"):
        server = ServeLoop(family, max_batch=traffic["max_batch"],
                           default_budget_s=traffic["budget_share"] * slo_s,
                           route=traffic["route"], use_kernels=False,
                           dtype=DTYPES["bf16" if control else "fp32"])
    due, row_ids = load.schedule(traffic["rate_rps"], seconds,
                                 pool.shape[0], seed)
    rows = list(pool)

    setup_s = time.perf_counter() - t_setup0
    try:
        stats0 = server.stats()["models"]["default"]
        with tracer.window():
            sent = load.drive(server.submit, rows, due, row_ids)
            lat, results = load.collect(sent["futures"], sent["t0"], due,
                                        timeout_s=traffic["answer_wait_s"])
        stats1 = server.stats()["models"]["default"]
    finally:
        server.stop()
    memory = tracer.memory_peak()

    answered = np.asarray([r is not None for r in results])
    z64 = reference.margins(pool, W, np.zeros((W.shape[0],)))
    err = np.zeros((len(results),))
    for i, r in enumerate(results):
        if r is not None:
            err[i] = np.max(np.abs(np.asarray(r.margins, np.float64)
                                   - z64[row_ids[i]]))
    scale = max(1.0, float(np.max(np.abs(z64[row_ids]))))
    margin_err = float(np.max(err)) / scale
    ok = answered & (err / scale <= config["limits"]["margin_err"])
    in_slo = ok & (lat <= slo_s)
    flushes = sum(stats1["flushes"].values()) - sum(stats0["flushes"].values())
    # where the slow requests were due: the seconds of the window that
    # hold most requests over the SLO (a host stall shows as one or two)
    over = np.bincount(due[~in_slo].astype(np.int64),
                       minlength=int(np.ceil(seconds)))
    worst = np.argsort(over)[::-1][:5]
    ms = {q: 1e3 * load.percentile(lat, q) for q in (50, 95, 99, 99.9)}
    return {
        "setup_s": setup_s,
        "e2e": {"serve_p95_ms": ms[95],
                "serve_rows_per_s": float(in_slo.sum()) / seconds},
        "attempted": int(len(due)),
        "failed": int((~answered).sum()),
        "checks": {"margin_err": margin_err,
                   "unanswered": int((~answered).sum())},
        "memory_peak_bytes": memory,
        "notes": {"requests": int(len(due)), "rate_rps": traffic["rate_rps"],
                  "refused": sent["refused"],
                  "generator_lag_max_s": sent["lag_max_s"],
                  "generator_lag_mean_s": sent["lag_mean_s"],
                  "p50_ms": ms[50], "p95_ms": ms[95], "p99_ms": ms[99],
                  "p999_ms": ms[99.9],
                  "late_10ms": int((sent["lag_s"] > 0.01).sum()),
                  "over_slo_by_second": [[int(s), int(over[s])]
                                         for s in worst if over[s]],
                  "slo_attainment": float(in_slo.mean()),
                  "flushes": {k: stats1["flushes"][k] - stats0["flushes"][k]
                              for k in stats1["flushes"]},
                  "margin_scale": scale},
        "layer": {"rows": stats1["rows"] - stats0["rows"],
                  "p99_ms": ms[99],
                  "flushes": flushes,
                  "scorer_program": SCORER_PROGRAM},
    }
