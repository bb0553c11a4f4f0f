"""Microbatching front-end: bucket-padded request batches (DESIGN.md 10.4).

XLA compiles one program per input SHAPE, so serving raw variable-sized
request batches would recompile constantly. The batcher quantizes every
batch to a small fixed set of bucket sizes: a pending chunk of r requests
is padded with empty rows up to the smallest bucket >= r, so after one
warmup call per bucket, steady-state traffic NEVER recompiles — the
recompile policy of DESIGN.md section 10.4. The bucket geometry and
chunk packing live in `serve.policy.BucketPolicy`, shared with the
continuous-batching `serve.loop.ServeLoop` (DESIGN.md section 14) so
both fronts pad identically; this class remains the synchronous
one-batch-at-a-time front-end (and the per-request baseline arm of
benchmarks/bench_serve2.py).

`route` picks the dense-layout scorer ("sparse" union-gather, "dense"
densified matmul, or "auto" from the measured crossover table of
BENCH_serve.json — see serve.predict.pick_route).

Two request layouts:

  * "dense":      requests are (B, n) float rows; padding appends zero
                  rows (their margins are computed and discarded).
  * "padded_csc": requests arrive as a CSRMatrix (row-major sparse); each
                  bucket chunk is packed into the feature-major padded-CSC
                  layout with a FIXED column width `k_max` — shape
                  stability demands a fixed width, so `k_max` is a
                  construction-time cap. A chunk whose column nnz
                  overflows it raises loudly (truncation would silently
                  change margins); derive the cap from the full request
                  set (`CSRMatrix.max_col_nnz`) when you have it.

Per bucket the batcher accounts calls, rows, padding overhead, warmup
(first-call, compile-inclusive) latency and steady-state latency, so
`stats()` exposes exactly the throughput/recompile story
benchmarks/bench_serve.py reports.

Observability (DESIGN.md section 13): each bucket additionally keeps a
fixed-bucket latency histogram of its steady-state calls, so `stats()`
reports p50/p99 per bucket — always on, since a histogram observe is
one bisect. When the global metrics registry is enabled the same
events are mirrored there (serve.rows / serve.pad_rows /
serve.compiles counters, serve.latency_s histograms) and every engine
invocation emits a span on the "serve" trace track.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import numpy as np

from repro import obs
from repro.serve.policy import BucketPolicy, default_buckets  # noqa: F401
from repro.serve.predict import (ModelBank, margins_dense,
                                 margins_padded_csc)


@dataclasses.dataclass
class BucketStats:
    bucket: int
    calls: int = 0                 # total engine invocations at this shape
    rows: int = 0                  # real (unpadded) requests served
    pad_rows: int = 0              # padding rows computed and discarded
    warmup_rows: int = 0           # real rows of the first (compile) call
    warmup_seconds: float = 0.0    # first call (includes XLA compile)
    busy_seconds: float = 0.0      # steady-state time after warmup
    # steady-state per-call latency distribution (warmup excluded — the
    # compile call would dominate every quantile)
    latency: obs.Histogram = dataclasses.field(
        default_factory=lambda: obs.Histogram(obs.LATENCY_BOUNDS_S))

    @property
    def warm_calls(self) -> int:
        return max(self.calls - 1, 0)

    @property
    def rows_per_s(self) -> Optional[float]:
        """Steady-state REQUEST throughput: real rows only — padding is
        engine work, not served traffic (pad_rows reports it separately).
        None until a bucket has warm calls."""
        if self.warm_calls == 0 or self.busy_seconds <= 0:
            return None
        return (self.rows - self.warmup_rows) / self.busy_seconds

    def as_dict(self) -> dict:
        return {"bucket": self.bucket, "calls": self.calls,
                "rows": self.rows, "pad_rows": self.pad_rows,
                "warmup_rows": self.warmup_rows,
                "warmup_seconds": self.warmup_seconds,
                "busy_seconds": self.busy_seconds,
                "rows_per_s": self.rows_per_s,
                "latency_p50_s": self.latency.quantile(0.5),
                "latency_p99_s": self.latency.quantile(0.99)}


class MicroBatcher:
    """Pads request batches to bucket shapes and scores them on a bank."""

    def __init__(self, bank: ModelBank, buckets: Sequence[int] = None,
                 layout: str = "dense", use_kernels: bool = False,
                 k_max: Optional[int] = None, max_batch: int = 64,
                 route: str = "sparse"):
        self.policy = BucketPolicy(
            buckets=tuple(buckets or default_buckets(max_batch)),
            layout=layout, k_max=k_max)
        self.bank = bank
        self.use_kernels = use_kernels
        self.route = route
        self._stats = {b: BucketStats(bucket=b) for b in self.buckets}

    # -- bucket geometry (delegated to the shared BucketPolicy) --------------
    @property
    def layout(self) -> str:
        return self.policy.layout

    @property
    def k_max(self) -> Optional[int]:
        return self.policy.k_max

    @property
    def buckets(self) -> tuple:
        return self.policy.buckets

    @property
    def max_bucket(self) -> int:
        return self.policy.max_bucket

    def bucket_for(self, r: int) -> int:
        """Smallest bucket >= r (r must not exceed the largest bucket)."""
        return self.policy.bucket_for(r)

    # -- request plumbing ----------------------------------------------------
    def predict(self, requests) -> np.ndarray:
        """Score any number of requests -> (B, K) margins.

        dense layout: (B, n) array rows. padded_csc layout: a CSRMatrix
        (row-major sparse requests). Oversized inputs are split into
        max-bucket chunks; the ragged tail is padded up to its bucket.
        """
        n_req = (requests.shape[0] if hasattr(requests, "shape")
                 else len(requests))
        out = []
        start = 0
        while start < n_req:
            stop = min(start + self.max_bucket, n_req)
            out.append(self._run_chunk(requests, start, stop))
            start = stop
        return np.concatenate(out, axis=0) if out else \
            np.zeros((0, self.bank.n_models), np.float32)

    def _run_chunk(self, requests, start: int, stop: int) -> np.ndarray:
        r = stop - start
        bucket = self.bucket_for(r)
        if self.layout == "dense":
            X = np.asarray(requests[start:stop], np.float32)
            if X.shape[1] != self.bank.n_features:
                raise ValueError(f"requests have {X.shape[1]} features, "
                                 f"bank has {self.bank.n_features}")
            X = self.policy.pad_dense(X, bucket)
            run = lambda: margins_dense(self.bank, X,
                                        use_kernels=self.use_kernels,
                                        route=self.route)
        else:
            packed = self.policy.pack_csc(requests, start, stop, bucket,
                                          self.bank.n_features)
            run = lambda: margins_padded_csc(self.bank, packed,
                                             use_kernels=self.use_kernels)
        st = self._stats[bucket]
        t0 = time.perf_counter()
        with obs.span("serve.chunk"):
            z = np.asarray(run())      # blocks until the device is done
        dt = time.perf_counter() - t0
        warm = st.calls > 0
        if warm:
            st.busy_seconds += dt
            st.latency.observe(dt)
        else:
            st.warmup_seconds += dt
            st.warmup_rows = r
        st.calls += 1
        st.rows += r
        st.pad_rows += bucket - r
        if obs.metrics_enabled():
            obs.inc("serve.calls")
            obs.inc("serve.rows", r)
            obs.inc("serve.pad_rows", bucket - r)
            if warm:
                obs.observe(f"serve.latency_s.bucket_{bucket}", dt)
                obs.observe("serve.latency_s", dt)
            else:
                obs.inc("serve.compiles")
                obs.observe("serve.warmup_s", dt)
        return z[:r]

    # -- accounting ----------------------------------------------------------
    def stats(self) -> dict:
        per_bucket = [self._stats[b].as_dict() for b in self.buckets
                      if self._stats[b].calls]
        rows = sum(s["rows"] for s in per_bucket)
        busy = sum(s["busy_seconds"] for s in per_bucket)
        # real served requests only — padding is engine overhead, not
        # traffic (each bucket's pad_rows reports it)
        warm_rows = sum(s["rows"] - s["warmup_rows"] for s in per_bucket)
        # batcher-wide steady-state latency: merge the per-bucket
        # histograms (same fixed bounds, so counts add exactly)
        agg = obs.Histogram(obs.LATENCY_BOUNDS_S)
        for b in self.buckets:
            h = self._stats[b].latency
            if h.count:
                agg.counts = [a + c for a, c in zip(agg.counts, h.counts)]
                agg.count += h.count
                agg.total += h.total
                agg.vmin = min(agg.vmin, h.vmin)
                agg.vmax = max(agg.vmax, h.vmax)
        return {
            "layout": self.layout,
            "use_kernels": self.use_kernels,
            "route": self.route,
            "buckets": per_bucket,
            "total_rows": rows,
            "compiles": len(per_bucket),   # one warmup per bucket shape
            "steady_rows_per_s": (warm_rows / busy) if busy > 0 else None,
            "latency_p50_s": agg.quantile(0.5),
            "latency_p99_s": agg.quantile(0.99),
        }
