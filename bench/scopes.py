"""Device time per named scope, and the program's spans, from a profiler trace.

    python3 bench/scopes.py TRACE

TRACE is a `.xplane.pb` (or `.xplane.pb.gz`), or a directory holding one,
as `jax.profiler` writes it (`--trace-out DIR` of the CLIs, or a traced
benchmark window). The file is read straight from the protobuf wire
format (XSpace > XPlane > XLine > XEvent, and each plane's event and stat
metadata: the field numbers of tsl's `xplane.proto`), so neither
tensorflow nor xprof is needed.

Each device operation carries its JAX op path in the `tf_op` stat of its
event metadata, for example
`jit(outer)/while/body/pcdn.direction/jit(_take)/gather`: the
`jax.named_scope`s of the program are components of that path. Each
operation is charged its *exclusive* time: its interval less those of the
operations it contains on its line. A `while` around its body keeps only
its own loop control, under its own path, so nothing is counted twice and
the op paths together add up to the line's busy time. Host events whose names
start with `engine.`, `serve.`, `path.` or `bench.` are the program's and
the benchmark's spans. Times are integer picoseconds on the profiler's
one clock until they are reported in seconds.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import glob
import gzip
import json
import math
import os
import struct
import sys

OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("engine.", "serve.", "path.", "bench.")
WINDOW_SPAN = "bench.window"
SCOPE_PREFIX = "pcdn."
UNSCOPED = ""

# xplane.proto field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_MD_ID, _MD_NAME, _EVENT_MD_STATS = 1, 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_VALUE = 2


def _fields(buf):
    """(field number, value) of each field of one message: an int for
    varint and fixed-width fields, a memoryview for length-delimited."""
    i, end = 0, len(buf)
    while i < end:
        key, shift = 0, 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        kind = key & 7
        if kind == 0:
            v, shift = 0, 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, v
        elif kind == 2:
            n, shift = 0, 0
            while True:
                b = buf[i]
                i += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, buf[i:i + n]
            i += n
        elif kind == 1:
            yield key >> 3, struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif kind == 5:
            yield key >> 3, struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _metadata(entry, with_stats: bool):
    """A map entry of event or stat metadata -> (id, name, stats)."""
    md_id, name, stats = 0, "", []
    for f, v in _fields(entry):
        if f == _MAP_VALUE:
            for g, w in _fields(v):
                if g == _MD_ID:
                    md_id = w
                elif g == _MD_NAME:
                    name = _text(w)
                elif g == _EVENT_MD_STATS and with_stats:
                    stats.append(w)
    return md_id, name, stats


def _line(buf) -> tuple:
    """(name, timestamp ns, [(metadata id, offset ps, duration ps)]) of
    one encoded XLine. The hot loop of a read: each event's three varints
    are decoded in place and its stats skipped without being sliced."""
    name, ts_ns, events = "", 0, []
    i, end = 0, len(buf)
    while i < end:
        key = buf[i]                  # every XLine field key is one byte
        i += 1
        if key & 7 == 1:
            i += 8
            continue
        if key & 7 == 5:
            i += 4
            continue
        n = shift = 0
        while True:
            b = buf[i]
            i += 1
            n |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        if key == _LINE_EVENTS << 3 | 2:
            stop = i + n
            md = off = dur = 0
            while i < stop:
                ekey = buf[i]
                i += 1
                v = shift = 0
                while True:
                    b = buf[i]
                    i += 1
                    v |= (b & 0x7F) << shift
                    if b < 0x80:
                        break
                    shift += 7
                if ekey & 7 == 2:            # a stat: skip its bytes
                    i += v
                elif ekey == _EVENT_MD_ID << 3:
                    md = v
                elif ekey == _EVENT_OFFSET_PS << 3:
                    off = v
                elif ekey == _EVENT_DURATION_PS << 3:
                    dur = v
            events.append((md, off, dur))
        elif key & 7 == 2:
            if key == _LINE_NAME << 3 | 2:
                name = _text(buf[i:i + n])
            i += n
        elif key == _LINE_TIMESTAMP_NS << 3:
            ts_ns = n
    return name, ts_ns, events


def _tf_op(stats, stat_names: dict) -> str:
    """The op path of a `tf_op` stat ("path:type"), without its type."""
    for st in stats:
        md = val = None
        for f, v in _fields(st):
            if f == _STAT_MD_ID:
                md = v
            elif f == _STAT_STR:
                val = _text(v)
            elif f == _STAT_REF:
                val = stat_names.get(v, "")
        if md is not None and stat_names.get(md) == "tf_op" and val:
            return val.rsplit(":", 1)[0] if ":" in val else val
    return ""


def read_xspace(buf) -> dict:
    """-> {"ops": {device: [(tf_op, start_ps, dur_ps)]}, "spans":
    [(name, start_ps, dur_ps)]} from a serialized XSpace: the operations
    of each device's `XLA Ops` line, and the host spans."""
    buf = memoryview(buf)
    ops: dict = {}
    spans = []
    for f, plane in _fields(buf):
        if f != _SPACE_PLANES:
            continue
        name, lines, event_md, stat_md = "", [], [], []
        for g, v in _fields(plane):
            if g == _PLANE_NAME:
                name = _text(v)
            elif g == _PLANE_LINES:
                lines.append(v)
            elif g == _PLANE_EVENT_MD:
                event_md.append(v)
            elif g == _PLANE_STAT_MD:
                stat_md.append(v)
        device = name.startswith("/device:")
        if not (device or name.startswith("/host:")):
            continue
        stat_names = {i: n for i, n, _ in
                      (_metadata(e, False) for e in stat_md)}
        md_names, md_stats = {}, {}
        for e in event_md:
            i, n, st = _metadata(e, device)
            md_names[i], md_stats[i] = n, st
        for line in lines:
            lname, ts_ns, events = _line(line)
            base = ts_ns * 1000
            if device and lname == OPS_LINE:
                paths: dict = {}
                out = ops.setdefault(name, [])
                for md, off, dur in events:
                    if md not in paths:
                        paths[md] = _tf_op(md_stats.get(md, ()), stat_names)
                    out.append((paths[md], base + off, dur))
            elif not device:
                spans.extend((md_names[md], base + off, dur)
                             for md, off, dur in events
                             if md_names.get(md, "").startswith(
                                 SPAN_PREFIXES))
    return {"ops": ops, "spans": spans}


def load(path: str) -> dict:
    """`read_xspace` of a `.xplane.pb[.gz]` file, or of the one such file
    under a directory."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {path}, "
                               f"found {len(found)}")
        path = found[0]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        return read_xspace(fh.read())


def exclusive(events, lo: int, hi: int) -> dict:
    """{op path: ps} inside [lo, hi) of one line's operations, each
    charged the time in which it is the innermost operation running."""
    out: dict = collections.Counter()
    stack = []                                  # (path, end), innermost last
    t = None

    def charge(path, a, b):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out[path] += b - a

    last = (None, math.inf, 0)                  # closes every open op
    for path, start, dur in sorted(events, key=lambda e: (e[1], -e[2])) + [
            last]:
        while stack and stack[-1][1] <= start:
            p, end = stack.pop()
            if end > t:
                charge(p, t, end)
                t = end
        if stack and start > t:
            charge(stack[-1][0], t, start)
        t = start if t is None else max(t, start)
        stack.append((path, start + dur))
    return out


def window(ev: dict) -> tuple:
    """[lo, hi) in ps: the benchmark's window span where the trace has
    one, else from the first operation or span to the last one's end."""
    wins = [(s, s + d) for n, s, d in ev["spans"] if n == WINDOW_SPAN]
    if len(wins) == 1:
        return wins[0]
    if wins:
        raise ValueError(f"{len(wins)} {WINDOW_SPAN!r} spans in the trace")
    marks = [(s, s + d) for evs in ev["ops"].values() for _, s, d in evs]
    marks += [(s, s + d) for _, s, d in ev["spans"]]
    if not marks:
        return (0, 0)
    return (min(a for a, _ in marks), max(b for _, b in marks))


def op_seconds(ev: dict, lo: int, hi: int) -> dict:
    """Exclusive device seconds per op path inside [lo, hi), summed over
    devices."""
    out: dict = collections.Counter()
    for events in ev["ops"].values():
        out.update(exclusive(events, lo, hi))
    return {k: v / 1e12 for k, v in out.items()}


def scope_of(path: str) -> str:
    """The innermost component of an op path that names a scope of the
    program, or UNSCOPED."""
    for part in reversed(path.split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def scope_seconds(ops: dict) -> dict:
    """`op_seconds` folded by `scope_of`."""
    out: dict = collections.Counter()
    for path, sec in ops.items():
        out[scope_of(path)] += sec
    return dict(out)


class _Busy:
    """The union of one device's operation intervals, with the busy time
    inside any [a, b) found by bisection."""

    def __init__(self, events):
        starts, ends = [], []
        for a, b in sorted((s, s + d) for _, s, d in events):
            if ends and a <= ends[-1]:
                ends[-1] = max(ends[-1], b)
            else:
                starts.append(a)
                ends.append(b)
        self.starts, self.ends = starts, ends
        self.cum = [0]
        for a, b in zip(starts, ends):
            self.cum.append(self.cum[-1] + b - a)

    def within(self, a: int, b: int) -> int:
        i = bisect.bisect_right(self.ends, a)      # first ending after a
        j = bisect.bisect_left(self.starts, b)     # first starting at b+
        if j <= i:
            return 0
        busy = self.cum[j] - self.cum[i]
        busy -= max(0, a - self.starts[i])
        busy -= max(0, self.ends[j - 1] - b)
        return busy


def span_idle_seconds(ev: dict, name: str, lo: int, hi: int) -> list:
    """For each span `name` inside [lo, hi): seconds of it in which no
    operation ran on the device (the mean over devices that ran any)."""
    busy = [_Busy(events) for events in ev["ops"].values() if events]
    out = []
    for n, start, dur in ev["spans"]:
        if n != name or start < lo or start + dur > hi:
            continue
        idle = [dur - d.within(start, start + dur) for d in busy]
        out.append((sum(idle) / len(idle) if idle else dur) / 1e12)
    return out


def span_summary(ev: dict, lo: int, hi: int) -> dict:
    """{name: {"count", "total_s", "mean_s"}} of the spans in [lo, hi)."""
    acc: dict = {}
    for n, start, dur in ev["spans"]:
        if n == WINDOW_SPAN or start < lo or start + dur > hi:
            continue
        cnt, tot = acc.get(n, (0, 0))
        acc[n] = (cnt + 1, tot + dur)
    return {n: {"count": c, "total_s": t / 1e12, "mean_s": t / c / 1e12}
            for n, (c, t) in sorted(acc.items())}


def reduce(ev: dict) -> dict:
    """The numbers a reader of this module takes from one trace."""
    lo, hi = window(ev)
    ops = op_seconds(ev, lo, hi)
    idle = span_idle_seconds(ev, "engine.outer", lo, hi)
    return {"window_s": (hi - lo) / 1e12,
            "scopes": scope_seconds(ops),
            "spans": span_summary(ev, lo, hi),
            "engine_outer_idle_s": sum(idle) / len(idle) if idle else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help=".xplane.pb[.gz] or a directory")
    args = ap.parse_args(argv)
    print(json.dumps(reduce(load(args.trace)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
