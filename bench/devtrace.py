"""From a profiler trace to device busy time, program time and idle gaps.

`events_from_profile` flattens a JAX profiler trace (`.xplane.pb`) into
plain records: each device operation (`XLA Ops` line of a `/device:`
plane), each compiled program run (`XLA Modules` line) and each host
span the benchmark opened with `jax.profiler.TraceAnnotation` (names
starting with `bench.`). `reduce_events` turns those records, inside the
benchmark's window span, into the numbers the per-layer metrics read.
Everything is in nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import collections
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def events_from_profile(pd) -> dict:
    """-> {"ops": {device: [(name, start, dur)]}, "modules": {device:
    [(name, start, dur)]}, "spans": [(name, start, dur)]} from a
    `jax.profiler.ProfileData`."""
    ops: dict = collections.defaultdict(list)
    modules: dict = collections.defaultdict(list)
    spans = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                into = (ops if line.name == OPS_LINE else modules)[plane.name]
                into.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
            elif not device:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"ops": dict(ops), "modules": dict(modules), "spans": spans}


def events_from_dir(trace_dir: str) -> dict:
    """Events of the one `.xplane.pb` that `jax.profiler.trace` wrote
    under `trace_dir`."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    return events_from_profile(ProfileData.from_file(files[0]))


def _clip(events, lo: int, hi: int):
    """(name, start, end) of each event, clipped to [lo, hi]."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def merge(intervals) -> list:
    """Union of (start, end) intervals as sorted, disjoint intervals."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def _span_at(spans, a: int, b: int) -> str:
    """Name of the innermost benchmark span that covers most of [a, b]."""
    best, best_len = "host", -1
    for name, start, dur in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(start + dur, b) - max(start, a)
        if cover > 0.5 * (b - a) and (best_len < 0 or dur < best_len):
            best, best_len = name, dur
    return best


def reduce_events(ev: dict) -> dict:
    """Busy and idle time per device inside the window span, device time
    per program, the heaviest device operations and the longest idle gaps.

    busy_s is the union of the operation intervals, averaged over the
    devices that ran any; window_s is the window span's length.
    """
    windows = [s for s in ev["spans"] if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    _, lo, dur = windows[0]
    hi = lo + dur
    busy_ns, gaps = [], []
    op_time: dict = collections.Counter()
    for device, events in sorted(ev["ops"].items()):
        clipped = _clip(events, lo, hi)
        if not clipped:
            continue
        for name, a, b in clipped:
            op_time[name] += b - a
        busy = merge((a, b) for _, a, b in clipped)
        busy_ns.append(sum(b - a for a, b in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    programs: dict = {}
    for device, events in sorted(ev["modules"].items()):
        for name, a, b in _clip(events, lo, hi):
            base = name.split("(")[0]
            tot, cnt = programs.get(base, (0, 0))
            programs[base] = (tot + (b - a), cnt + 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [(_span_at(ev["spans"], a, b), (b - a) / 1e9)
                for a, b in gaps[:TOP]]
    return {
        "window_s": dur / 1e9,
        "busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
        "devices": len(busy_ns),
        "programs": {k: {"seconds": t / 1e9, "runs": c}
                     for k, (t, c) in programs.items()},
        "device_ops": [[k, v / 1e9] for k, v in op_time.most_common(TOP)],
        "idle_gaps": [[k, v] for k, v in top_gaps],
    }


def program_seconds(red: dict, prefix: str) -> tuple[float, int]:
    """(device seconds, runs) of the programs whose name starts with
    `prefix` (a jitted function `f` runs as the program `jit_f`)."""
    sec, runs = 0.0, 0
    for name, p in red["programs"].items():
        if name.startswith(prefix):
            sec += p["seconds"]
            runs += p["runs"]
    return sec, runs
