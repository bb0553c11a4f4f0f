"""Block-size / layout / implementation autotuner for the hot kernels
(DESIGN.md section 12).

All committed BENCH numbers used to run the Pallas kernels with
hard-coded grids, block shapes and fp32 everywhere — "fast as the
hardware allows" was a hope, not a measurement. Richtárik–Takáč (arXiv
1212.0873) and Scherrer et al. (arXiv 1206.6409) both argue the win of
parallel CD is data/shape-dependent (per-row sparsity omega, memory-
system behavior), so kernel parameters must adapt to the problem
instance rather than being fixed at authorship time. This module makes
them adapt, once per problem shape:

  * every tunable kernel declares a DEFAULT config (exactly the
    pre-autotuner hard-coded behavior) and a SEARCH SPACE of candidate
    configs — block sizes along each tileable axis plus an ``impl``
    axis ("pallas": the Pallas kernel; "xla": the jnp oracle in
    `kernels/ref.py`, which is also the fastest route on backends
    where Pallas runs in interpreter mode);
  * `tune(kernel, runner, ...)` measures the candidates (exhaustive
    for small spaces, greedy coordinate hillclimb for larger ones —
    `benchmarks/hillclimb.py` drives and logs the climb) and persists
    the winner in an on-disk JSON cache keyed by
    ``(kernel, shape-bucket, dtype, backend)``;
  * `resolve(kernel, ...)` — called by every `kernels/ops.py` wrapper
    at trace time — merges defaults, the cached winner and explicit
    per-call overrides, so `make_bundle_step`, the sharded backend's
    kernel routing and the serving `ModelBank` all pick tuned configs
    transparently. Tuning itself NEVER happens implicitly: a cache
    miss costs a dict lookup and returns the defaults.

Shapes are bucketed to the next power of two per axis, so one tuning
run covers a neighborhood of problem shapes and a warm cache is hit by
every later solve/serve call at that scale.

Robustness contract (pinned by tests/test_autotune.py): a corrupt cache
file, a stale entry (unknown kernel, config keys outside the search
space, wrong value types) or an unwritable cache directory NEVER crash
a solve — every failure path falls back to the defaults silently.

Env knobs (README "Autotuner" section):

  REPRO_AUTOTUNE        "auto"/"on" (default) read the cache; "off"
                        ignore it entirely (defaults everywhere).
  REPRO_AUTOTUNE_CACHE  cache file path (default
                        ~/.cache/repro/autotune.json).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

from repro import obs

CACHE_VERSION = 1

# ---------------------------------------------------------------------------
# per-kernel defaults and search spaces
#
# The DEFAULTS are bit-for-bit the pre-autotuner hard-coded launches; a
# cold cache (or REPRO_AUTOTUNE=off) reproduces the old behavior exactly.
# `None` for a block size means "do not tile this axis" (the full extent
# in one program), matching the original single-slab kernels.

DEFAULTS: Dict[str, Dict[str, object]] = {
    "pcdn_bundle": {"impl": "pallas", "block_q": None},
    "pcdn_direction": {"impl": "pallas", "block_s": 512, "block_p": 128},
    "pcdn_sparse_direction": {"impl": "pallas", "block_p": 128,
                              "block_k": None},
    "pcdn_linesearch": {"impl": "pallas", "block_s": 1024},
    "serve_margins_dense": {"impl": "pallas", "block_b": 128,
                            "block_a": None},
    "serve_margins_csc": {"impl": "pallas"},
}

SEARCH_SPACES: Dict[str, Dict[str, Tuple[object, ...]]] = {
    "pcdn_bundle": {
        "impl": ("pallas", "xla"),
        "block_q": (None, 8, 16),
    },
    "pcdn_direction": {
        "impl": ("pallas", "xla"),
        "block_s": (128, 256, 512, 1024),
        "block_p": (32, 64, 128, 256),
    },
    "pcdn_sparse_direction": {
        "impl": ("pallas", "xla"),
        "block_p": (128, 256),      # lane axis of the outputs: 128-multiples
        "block_k": (None, 128, 256),
    },
    "pcdn_linesearch": {
        "impl": ("pallas", "xla"),
        "block_s": (256, 512, 1024, 2048),
    },
    "serve_margins_dense": {
        "impl": ("pallas", "xla"),
        "block_b": (128, 256),      # lane axis of the output rows
        "block_a": (None, 128, 512),
    },
    "serve_margins_csc": {
        "impl": ("pallas", "xla"),
    },
}


# ---------------------------------------------------------------------------
# shape bucketing and cache keys


def next_pow2(x: int) -> int:
    x = max(1, int(x))
    return 1 << (x - 1).bit_length()


def shape_bucket(**dims) -> Tuple[Tuple[str, int], ...]:
    """Deterministic (name, pow2-rounded-size) tuple — the shape part of
    a cache key. One tuning run covers every shape in the bucket."""
    return tuple(sorted((k, next_pow2(v)) for k, v in dims.items()))


def backend_tag() -> str:
    """'cpu-interp' / 'tpu' / ... — winners differ by backend AND by
    whether Pallas runs compiled or interpreted, so both are in the key.
    Resolved lazily (first kernel dispatch initializes jax anyway)."""
    import jax

    from repro.kernels import ops
    tag = jax.default_backend()
    if ops.interpret_mode():
        tag += "-interp"
    return tag


def cache_key(kernel: str, bucket, dtype, backend: Optional[str] = None
              ) -> str:
    backend = backend or backend_tag()
    shp = ",".join(f"{k}{v}" for k, v in bucket)
    return f"{kernel}|{shp}|{_dtype_name(dtype)}|{backend}"


def _dtype_name(dtype) -> str:
    try:
        import jax.numpy as jnp  # noqa: F401
        import numpy as np
        return np.dtype(dtype).name
    except Exception:
        return str(dtype)


# ---------------------------------------------------------------------------
# persistent cache


def enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "auto").strip().lower() not in (
        "0", "off", "false", "no")


def cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "autotune.json"))


# module-level cache state: (path, mtime_ns, entries). Reloaded when the
# path changes or the file is rewritten — cheap enough for trace time.
_cache_state: Optional[Tuple[str, int, dict]] = None


def _load_cache() -> dict:
    global _cache_state
    path = cache_path()
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        _cache_state = (path, -1, {})
        return {}
    if _cache_state is not None and _cache_state[0] == path \
            and _cache_state[1] == mtime:
        return _cache_state[2]
    try:
        with open(path) as fh:
            obj = json.load(fh)
        if not isinstance(obj, dict) or obj.get("version") != CACHE_VERSION:
            raise ValueError("version mismatch")
        entries = obj.get("entries", {})
        if not isinstance(entries, dict):
            raise ValueError("entries not a dict")
    except Exception:
        # corrupt / unreadable / wrong version: behave as empty, never raise
        entries = {}
    _cache_state = (path, mtime, entries)
    return entries


def invalidate_cache() -> None:
    """Drop the in-memory cache view (tests; after env changes)."""
    global _cache_state
    _cache_state = None


def _validate(kernel: str, config: dict) -> Optional[dict]:
    """A cached config is usable iff every key belongs to the kernel's
    search space and every value is one of the declared candidates (the
    'stale entry' contract: a config written by an older search space
    that no longer exists falls back to defaults, it does not crash)."""
    space = SEARCH_SPACES.get(kernel)
    if space is None or not isinstance(config, dict):
        return None
    out = {}
    for k, v in config.items():
        if k not in space:
            return None
        if v not in space[k]:
            return None
        out[k] = v
    return out


def lookup(kernel: str, bucket, dtype, backend: Optional[str] = None
           ) -> Optional[dict]:
    """Validated cached winner for this cell, or None.

    Metrics (registry enabled): autotune.lookup_hits counts lookups
    that return a usable cached winner; autotune.lookup_misses counts
    everything else (disabled tuner, empty cache, absent or stale
    entry) — the miss path is exactly "defaults were used".
    """
    cfg = _lookup(kernel, bucket, dtype, backend)
    obs.inc("autotune.lookup_hits" if cfg is not None
            else "autotune.lookup_misses")
    return cfg


def _lookup(kernel: str, bucket, dtype, backend: Optional[str] = None
            ) -> Optional[dict]:
    if not enabled():
        return None
    entries = _load_cache()
    if not entries:
        return None
    rec = entries.get(cache_key(kernel, bucket, dtype, backend))
    if not isinstance(rec, dict):
        return None
    return _validate(kernel, rec.get("config"))


def record(kernel: str, bucket, dtype, config: dict,
           us: Optional[float] = None, default_us: Optional[float] = None,
           backend: Optional[str] = None) -> bool:
    """Persist a tuned winner. Returns False (without raising) when the
    cache file cannot be written."""
    key = cache_key(kernel, bucket, dtype, backend)
    path = cache_path()
    try:
        entries = dict(_load_cache())
        entries[key] = {"config": dict(config), "us": us,
                        "default_us": default_us,
                        "when": time.strftime("%Y-%m-%dT%H:%M:%S")}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump({"version": CACHE_VERSION, "entries": entries}, fh,
                      indent=1)
        os.replace(tmp, path)
    except Exception:
        return False
    invalidate_cache()
    return True


def resolve(kernel: str, bucket, dtype,
            overrides: Optional[dict] = None) -> dict:
    """The trace-time dispatch decision of every ops.py wrapper.

    defaults <- cached winner <- explicit per-call overrides (a non-None
    kwarg always wins — callers who pass block sizes keep exact control).
    """
    cfg = dict(DEFAULTS[kernel])
    cached = lookup(kernel, bucket, dtype)
    if cached:
        cfg.update(cached)
    if overrides:
        for k, v in overrides.items():
            if v is not None:
                cfg[k] = v
    return cfg


# ---------------------------------------------------------------------------
# tuning


@dataclasses.dataclass(frozen=True)
class TuneResult:
    kernel: str
    config: dict                 # the winner
    us: float                    # winner's measured microseconds/call
    default_us: float            # the DEFAULT config's microseconds/call
    table: Tuple[dict, ...]      # every measured candidate {config, us}
    trajectory: Tuple[dict, ...]  # hillclimb steps {config, us} (exhaustive:
    #                               the winner only)

    @property
    def speedup(self) -> float:
        return self.default_us / max(self.us, 1e-9)


def time_call(fn: Callable[[], object], repeats: int = 5,
              warmup: int = 1) -> float:
    """Median microseconds per call; blocks on jax arrays."""
    import jax

    def run():
        out = fn()
        jax.block_until_ready(out)

    for _ in range(warmup):
        run()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def candidate_configs(kernel: str) -> List[dict]:
    """The full cartesian search space (DEFAULT config always included)."""
    space = SEARCH_SPACES[kernel]
    keys = sorted(space)
    configs = [dict(zip(keys, vals))
               for vals in itertools.product(*(space[k] for k in keys))]
    default = DEFAULTS[kernel]
    if default not in configs:
        configs.insert(0, dict(default))
    return configs


def _measure(runner: Callable[[dict], Callable], config: dict,
             repeats: int) -> Optional[float]:
    """Build + time one candidate; an infeasible candidate (runner or the
    launch raises) is skipped, not fatal."""
    try:
        fn = runner(config)
        return time_call(fn, repeats=repeats)
    except Exception:
        return None


def tune(kernel: str, runner: Callable[[dict], Callable], bucket, dtype,
         strategy: str = "exhaustive", repeats: int = 5,
         persist: bool = True, backend: Optional[str] = None) -> TuneResult:
    """Measure candidates and persist the winner for this cache cell.

    runner(config) -> zero-arg callable executing one kernel call with
    that config (the benchmark builds it around fixed random operands).
    strategy: "exhaustive" times the whole cartesian space; "hillclimb"
    starts from the defaults and greedily improves one axis at a time
    (the classic autotuner climb — `benchmarks/hillclimb.py` logs the
    trajectory). The DEFAULT config is always measured, so the recorded
    winner is never slower than the default by construction.
    """
    t_tune = time.perf_counter()
    with obs.span("autotune.tune") as tune_span:
        default = dict(DEFAULTS[kernel])
        table: List[dict] = []
        measured: Dict[str, float] = {}

        def key_of(cfg: dict) -> str:
            return json.dumps(cfg, sort_keys=True)

        def measure(cfg: dict) -> Optional[float]:
            k = key_of(cfg)
            if k in measured:
                return measured[k]
            us = _measure(runner, cfg, repeats)
            if us is not None:
                measured[k] = us
                table.append({"config": dict(cfg), "us": us})
            return us

        default_us = measure(default)
        if default_us is None:
            raise RuntimeError(
                f"autotune[{kernel}]: the default config {default} failed to "
                f"run — nothing to tune against")

        trajectory = [{"config": dict(default), "us": default_us}]
        if strategy == "exhaustive":
            for cfg in candidate_configs(kernel):
                measure(cfg)
            best = min(table, key=lambda r: r["us"])
            trajectory.append({"config": dict(best["config"]),
                               "us": best["us"]})
        elif strategy == "hillclimb":
            space = SEARCH_SPACES[kernel]
            current, current_us = dict(default), default_us
            improved = True
            while improved:
                improved = False
                for axis in sorted(space):
                    for v in space[axis]:
                        if current.get(axis) == v:
                            continue
                        cand = dict(current)
                        cand[axis] = v
                        us = measure(cand)
                        if us is not None and us < current_us:
                            current, current_us = cand, us
                            trajectory.append({"config": dict(cand), "us": us})
                            improved = True
            best = {"config": current, "us": current_us}
        else:
            raise ValueError(f"unknown strategy {strategy!r}")

        result = TuneResult(kernel=kernel, config=dict(best["config"]),
                            us=float(best["us"]), default_us=float(default_us),
                            table=tuple(table), trajectory=tuple(trajectory))
        if persist:
            record(kernel, bucket, dtype, result.config, us=result.us,
                   default_us=result.default_us, backend=backend)
        if tune_span is not None:
            tune_span.set_metadata(kernel=kernel, strategy=strategy,
                                   candidates=len(table),
                                   speedup=result.speedup)
    obs.inc("autotune.tunes")
    obs.observe("autotune.tune_seconds", time.perf_counter() - t_tune)
    return result
