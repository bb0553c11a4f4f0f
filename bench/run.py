"""Benchmark harness: one run of one cell, on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: `BENCHMARK.json` at the
root names the cell's configuration (a file under `bench/configs/`), its
traffic mix (`bench/traffic/<traffic>.json`, whose `kind` picks the
general traffic module `bench/<kind>.py`), and the per-layer metrics, each read
by `bench/metrics/<metric>.py`. A run builds its inputs from `--seed`,
warms every program it will use (counted in `setup_s`), measures for
`--seconds`, checks every answer of the window against the float64
reference in `bench/reference.py`, and prints one JSON object as the
last line of standard output: the end-to-end metrics with `--trace 0`,
the per-layer metrics read from a profiler trace of the window with
`--trace 1`. The numbers compared, each beside its limit, come last
there and are repeated as the last lines of standard error.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, where Pallas would run interpreted, and
where the program (`src/repro`) is not beside it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
for _p in (SRC, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class NoChip(RuntimeError):
    """No TPU, too few chips, or kernels that would run interpreted."""


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _load_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as fh:
        return json.load(fh)


def resolve(spec: dict, workload: str) -> dict:
    """-> the cell: its workload entry, configuration and traffic files,
    and the names of the metrics it reports."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return {
        "workload": cell,
        "config": _load_json(configs[cell["config"]]["file"]),
        "traffic": _load_json(os.path.join(
            "bench", "traffic", cell["traffic"] + ".json")),
        "end_to_end": [m for m in spec["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in spec["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the directory is part of every entry's key), or where
    JAX_COMPILATION_CACHE_DIR says. Every program is cached, so only a
    cell's first run in a checkout compiles."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def check_chip(chips: int) -> list:
    """The devices of this run; raises NoChip where they are not TPUs,
    too few, or where the Pallas kernels would run interpreted."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r} "
                     f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    from repro.kernels import ops
    if ops.interpret_mode():
        raise NoChip("Pallas kernels would run interpreted")
    return devices[:chips]


class Tracer:
    """The benchmark's own spans (`jax.profiler.TraceAnnotation`) around
    its calls into each layer, and with `trace` a profiler trace of the
    window, reduced by `bench.devtrace` once the trace ends. Where the
    mix sets `trace_s`, the trace ends at the first `lap()` after it has
    covered that many seconds of the window, and the window runs on
    untraced: a trace grows with the device operations it holds, and
    reading one of a whole window of short steps takes minutes."""

    def __init__(self, trace: bool, devices, trace_s: float | None = None):
        self.trace = trace
        self.devices = devices
        self.trace_s = trace_s
        self.reduced = None
        self.cost = {}
        self._span = self._tmp = None
        self._t0 = 0.0

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured window. Python's cyclic garbage collector is
        paused in it (after a collection and a freeze of everything set-up
        made): the load generator's own bookkeeping, tens of thousands of
        futures, would otherwise make its full collections stall the
        server's threads for 0.1 to 1.6 s at random."""
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            with self._traced():
                yield
        finally:
            gc.enable()
            gc.unfreeze()

    @contextlib.contextmanager
    def _traced(self):
        import jax
        from bench import devtrace
        if not self.trace:
            yield
            return
        self._tmp = tempfile.mkdtemp(prefix="bench-trace-")
        # device operations and the benchmark's own spans; no Python calls
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self._tmp, profiler_options=opts)
        self._span = self.span(devtrace.WINDOW_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        try:
            yield
        finally:
            self._stop()

    def lap(self) -> None:
        """Called by a workload between units of work: ends the trace once
        it has covered `trace_s` seconds of the window."""
        if (self._span is not None and self.trace_s is not None
                and time.perf_counter() - self._t0 >= self.trace_s):
            self._stop()

    def _stop(self) -> None:
        import jax
        from bench import devtrace
        if self._span is None:
            return
        span, self._span = self._span, None
        try:
            span.__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            t1 = time.perf_counter()
            events = devtrace.events_from_dir(self._tmp)
            t2 = time.perf_counter()
            self.reduced = devtrace.reduce_events(events)
            self.cost = {"stop_s": t1 - t0, "read_s": t2 - t1,
                         "reduce_s": time.perf_counter() - t2}
        finally:
            shutil.rmtree(self._tmp, ignore_errors=True)

    def memory_peak(self) -> int | None:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None


def _traffic_module(kind: str):
    import importlib
    return importlib.import_module(f"bench.{kind}")


def _reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             *, t_setup0: float, control: bool = False) -> tuple:
    """One run of one cell -> (the result object the harness prints last,
    the notes it prints on an earlier line)."""
    import jax
    tracer = Tracer(trace, devices, cell["traffic"].get("trace_s"))
    init_s = time.perf_counter() - t_setup0    # imports and the chip's start
    out = _traffic_module(cell["traffic"]["kind"]).run(
        cell["config"], cell["traffic"], seed, seconds, tracer, t_setup0,
        control=control)
    limits = cell["config"]["limits"]
    checks = {k: {"value": out["checks"][k], "limit": lim}
              for k, lim in limits.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]
             + cell["per_layer"]}
    if trace:
        red = tracer.reduced
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = dict(out["layer"], trace=red, device_kind=d0.device_kind)
        for m in cell["per_layer"]:
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        values = dict(out["e2e"], setup_s=out["setup_s"])
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": tracer.reduced["device_ops"],
                               "idle_gaps": tracer.reduced["idle_gaps"]}
    result["checks"] = checks
    notes = dict(out["notes"], setup_s=out["setup_s"], setup_init_s=init_s,
                 trace_cost=tracer.cost,
                 jax=jax.__version__,
                 readings={k: v for k, v in out["checks"].items()
                           if k not in limits})
    return result, notes


def _finite(obj):
    """The result with each non-finite number (a missing answer's
    latency) written as the largest float, so the line stays JSON."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return sys.float_info.max
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: the program is not at {SRC}; nothing was run",
              file=sys.stderr)
        return 2
    cell = resolve(load_spec(), args.workload)

    cache = enable_compile_cache()
    try:
        devices = check_chip(cell["workload"]["chips"])
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    print(f"[bench] {args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} device={devices[0].device_kind} "
          f"x{len(devices)} cache={cache}", file=sys.stderr, flush=True)
    result, notes = run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), devices, t_setup0=T_PROCESS)
    result = _finite(result)
    print(json.dumps(_finite(notes)), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
