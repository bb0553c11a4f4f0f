"""Open-loop load, timed from when each request was due.

The serving program's own `serve.loop.drive_poisson` sends a fixed
count and times each request from its submission, so a generator that
falls behind hides the wait it imposed. Here the schedule is fixed
before the window opens, the window decides how many requests are due,
each latency runs from the request's due time to its answer, a refused
or failed request counts as never answered, and the generator reports
how late it ran.
"""
from __future__ import annotations

import math
import time

import numpy as np


def schedule(rate_rps: float, seconds: float, pool: int, seed: int):
    """-> (due offsets (N,), row ids (N,)) for a window of `seconds`.

    N = round(rate * seconds) requests. Their gaps are the N evenly
    spaced quantiles of the exponential distribution of mean 1/rate, in
    an order drawn from the seed and scaled so the last is due at the
    window's end: every seed offers the same gaps, so a seed changes the
    order of the work and not its amount. Rows are drawn from the seed.
    """
    n = max(1, int(round(rate_rps * seconds)))
    rng = np.random.default_rng(seed)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_rps
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps)
    due *= seconds / due[-1]
    return due, rng.integers(0, pool, size=n)


def drive(submit, rows, due, row_ids, *, clock=time.perf_counter,
          sleep=time.sleep) -> dict:
    """Submit rows[row_ids[i]] at t0 + due[i] through `submit(x)`, which
    returns a future or raises to refuse. Never waits for an answer.

    -> {"t0", "futures" (None where refused), "refused", "lag_s" (N,),
    "lag_max_s", "lag_mean_s"}: lag is how long after its due time a
    request was handed to `submit`.
    """
    futures = []
    refused = 0
    lags = np.zeros((len(due),))
    t0 = clock()
    for i, (d, r) in enumerate(zip(due, row_ids)):
        target = t0 + d
        now = clock()
        if now < target:
            sleep(target - now)
            now = clock()
        lags[i] = now - target
        try:
            futures.append(submit(rows[r]))
        except Exception:           # refused at admission: never answered
            futures.append(None)
            refused += 1
    return {"t0": t0, "futures": futures, "refused": refused, "lag_s": lags,
            "lag_max_s": float(lags.max(initial=0.0)),
            "lag_mean_s": float(lags.mean()) if len(due) else 0.0}


def collect(futures, t0: float, due, *, timeout_s: float = 60.0):
    """Wait for each answer (up to `timeout_s` past the call in all) and
    time it from its due time.

    -> (latency_s (N,), results): latency is inf and the result None for
    a request that was refused, failed, or never answered.
    """
    deadline = time.perf_counter() + timeout_s
    lat = np.full((len(futures),), math.inf)
    results = [None] * len(futures)
    for i, fut in enumerate(futures):
        if fut is None:
            continue
        try:
            res = fut.result(timeout=max(deadline - time.perf_counter(), 0))
        except Exception:           # failed or never came: no answer
            continue
        results[i] = res
        lat[i] = res.t_done - (t0 + due[i])
    return lat, results


def percentile(lat: np.ndarray, q: float) -> float:
    """The q-th percentile by nearest rank from above (an inf, that is a
    missing answer, counts as the slowest)."""
    return float(np.quantile(lat, q / 100.0, method="higher"))
