"""Tests of `bench.scopes` (device time per named scope, program spans)
and pins of `bench.devtrace` on the two recorded traces, on the CPU."""
from __future__ import annotations

import gzip
import os
import struct

import pytest

from bench import devtrace, scopes
from bench.test_units import TESTDATA, _profile

GISETTE = os.path.join(TESTDATA, "gisette.xplane.pb.gz")


# -- a minimal protobuf writer for hand-made traces ---------------------------

def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _msg(field: int, body: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _str(field: int, s: str) -> bytes:
    return _msg(field, s.encode())


def _plane(name: str, lines, names: dict, tf_ops: dict = None) -> bytes:
    """A plane with `lines` [(line name, timestamp ns, [(md id, offset
    ps, duration ps)])], event metadata `names` {id: name} and `tf_ops`
    {id: op path} (a `tf_op` stat, stat metadata id 7)."""
    body = _str(2, name)
    for lname, ts, events in lines:
        evs = b"".join(_msg(4, _int(1, md) + _int(2, off) + _int(3, dur))
                       for md, off, dur in events)
        body += _msg(3, _str(2, lname) + _int(3, ts) + evs)
    for md, n in names.items():
        value = _int(1, md) + _str(2, n)
        if tf_ops and md in tf_ops:
            value += _msg(5, _int(1, 7) + _str(5, tf_ops[md] + ":"))
        body += _msg(4, _int(1, md) + _msg(2, value))
    body += _msg(5, _int(1, 7) + _msg(2, _int(1, 7) + _str(2, "tf_op")))
    # a fixed64 stat value, to be skipped
    body += _msg(6, _int(1, 7) + _varint(2 << 3 | 1) + struct.pack("<d", 1.5))
    return _msg(1, body)


def _hand_trace() -> bytes:
    """Device: a `while` [0, 10) us holding a direction op [1, 4) and an
    update op [5, 9), then an unscoped op [12, 13). Host: the window
    [0, 20) us, one `engine.outer` [0, 14) holding `engine.wait` [0, 13)."""
    us = 10**6
    device = _plane("/device:TPU:0", [
        ("XLA Ops", 0, [(1, 0, 10 * us), (2, 1 * us, 3 * us),
                        (3, 5 * us, 4 * us), (4, 12 * us, 1 * us)]),
        ("XLA Modules", 0, [(5, 0, 13 * us)])],
        {1: "while.1", 2: "fusion.2", 3: "fusion.3", 4: "copy.4",
         5: "jit_outer(1)"},
        {1: "jit(outer)/while",
         2: "jit(outer)/while/body/pcdn.direction/jit(_take)/gather",
         3: "jit(outer)/while/body/pcdn.update/scatter-add",
         4: "jit(outer)/copy"})
    host = _plane("/host:CPU", [
        ("python3", 0, [(1, 0, 20 * us), (2, 0, 14 * us), (3, 0, 13 * us),
                        (4, 0, 20 * us)])],
        {1: "bench.window", 2: "engine.outer", 3: "engine.wait",
         4: "other.span"})
    return device + host


# -- the scope reader -----------------------------------------------------------

def test_recorded_trace_op_paths():
    """On the trace recorded on a TPU v5e: the leaf device time of two op
    paths, as the protobuf library itself reads them from the file."""
    ev = scopes.load(GISETTE)
    ops = scopes.op_seconds(ev, *scopes.window(ev))
    assert ops["jit(outer)/while/body/closed_call/jit(_take)/gather"] == \
        pytest.approx(842.021e-6, abs=1e-9)
    assert ops["jit(outer)/dot_general"] == pytest.approx(390.524e-6,
                                                          abs=1e-9)


def test_recorded_trace_events_match_the_profiler():
    """Every device operation of the recorded trace, read from the wire
    format, at the start and duration `jax.profiler.ProfileData` gives."""
    ev = scopes.load(GISETTE)
    ref = devtrace.events_from_profile(_profile("gisette.xplane.pb.gz"))
    (device, ops), = ref["ops"].items()
    mine = ev["ops"][device]
    assert len(mine) == len(ops) == 1885
    assert [(s // 1000, d // 1000) for _, s, d in mine] == \
        [(int(s), int(d)) for _, s, d in ops]
    assert sorted(n for n, _, _ in ev["spans"]) == ["bench.solve",
                                                    devtrace.WINDOW_SPAN]


def test_parent_op_not_counted_twice():
    """The `while` that holds two ops keeps only its own 3 us; scopes
    fold op paths; the window clips; device idle inside engine.outer."""
    ev = scopes.read_xspace(_hand_trace())
    lo, hi = scopes.window(ev)
    assert (lo, hi) == (0, 20 * 10**6)
    ops = scopes.op_seconds(ev, lo, hi)
    assert ops["jit(outer)/while"] == pytest.approx(3e-6)
    assert sum(ops.values()) == pytest.approx(11e-6)    # the busy time
    assert scopes.scope_seconds(ops) == {
        "pcdn.direction": pytest.approx(3e-6),
        "pcdn.update": pytest.approx(4e-6), scopes.UNSCOPED:
        pytest.approx(4e-6)}
    # the window cuts the update op at 7 us
    assert scopes.op_seconds(ev, 0, 7 * 10**6)[
        "jit(outer)/while/body/pcdn.update/scatter-add"] == \
        pytest.approx(2e-6)
    # engine.outer [0, 14): the device ran [0, 10) and [12, 13)
    assert scopes.span_idle_seconds(ev, "engine.outer", lo, hi) == \
        [pytest.approx(3e-6)]
    red = scopes.reduce(ev)
    assert red["engine_outer_idle_s"] == pytest.approx(3e-6)
    assert red["spans"] == {
        "engine.outer": {"count": 1, "total_s": pytest.approx(14e-6),
                         "mean_s": pytest.approx(14e-6)},
        "engine.wait": {"count": 1, "total_s": pytest.approx(13e-6),
                        "mean_s": pytest.approx(13e-6)}}


@pytest.mark.parametrize("events, own", [
    ([("a", 0, 10), ("b", 0, 10)], {"b": 10}),        # same interval: one
    ([("a", 0, 10), ("b", 10, 5)], {"a": 10, "b": 5}),  # touching
    ([("a", 0, 10), ("b", 2, 3), ("c", 6, 2)], {"a": 5, "b": 3, "c": 2}),
    ([("a", 0, 10), ("b", 2, 8), ("c", 3, 1)], {"a": 2, "b": 7, "c": 1}),
    ([("a", 0, 10), ("b", 5, 10)], {"a": 5, "b": 10}),  # overlapping
])
def test_exclusive(events, own):
    """Each op keeps the time in which it is the innermost one running;
    together they cover the union of the intervals once."""
    assert dict(scopes.exclusive(events, 0, 100)) == own


def test_exclusive_clips_to_the_window():
    events = [("a", 0, 10), ("b", 2, 3), ("c", 6, 2)]
    assert dict(scopes.exclusive(events, 4, 8)) == {"a": 1, "b": 1, "c": 2}


def test_recorded_trace_exclusive_sums_to_busy_time():
    """On the trace recorded on a TPU v5e the op paths' exclusive device
    time adds up to the window's busy time, to the picosecond, and to
    `devtrace`'s reading of it; the `while` loops' own time, which has no
    op path, is part of it."""
    ev = scopes.load(GISETTE)
    lo, hi = scopes.window(ev)
    ops = scopes.op_seconds(ev, lo, hi)
    (events,) = ev["ops"].values()
    busy = scopes._Busy(events).within(lo, hi)
    assert sum(scopes.exclusive(events, lo, hi).values()) == busy
    assert sum(ops.values()) == pytest.approx(busy / 1e12, rel=1e-12)
    red = devtrace.reduce_events(devtrace.events_from_profile(
        _profile("gisette.xplane.pb.gz")))
    assert sum(ops.values()) == pytest.approx(red["busy_s"], rel=1e-4)
    assert ops[scopes.UNSCOPED] == pytest.approx(264.416e-6, abs=1e-9)


def test_scope_of_takes_the_innermost():
    assert scopes.scope_of("jit(f)/pcdn.kkt/jit(g)/pcdn.x/add") == "pcdn.x"
    assert scopes.scope_of("jit(f)/while/body/add") == scopes.UNSCOPED


def test_cli_prints_the_reduction(tmp_path, capsys):
    path = tmp_path / "t.xplane.pb.gz"
    with gzip.open(path, "wb") as fh:
        fh.write(_hand_trace())
    assert scopes.main([str(path)]) == 0
    assert '"pcdn.direction": 3e-06' in capsys.readouterr().out


# -- devtrace reads the recorded traces as it did -------------------------------

def test_hand_trace_reduction_pinned():
    red = devtrace.reduce_events(devtrace.events_from_profile(
        _profile("hand.xspace.txt")))
    assert (red["busy_s"], red["window_s"], red["devices"]) == \
        (pytest.approx(2.5e-6), pytest.approx(1e-5), 2)
    assert red["programs"] == {"jit_outer": {"seconds": pytest.approx(8e-6),
                                             "runs": 2}}
    assert red["device_ops"] == [["fusion.1", pytest.approx(3.5e-6)],
                                 ["dot.2", pytest.approx(1e-6)],
                                 ["copy.3", pytest.approx(1e-6)]]


def test_recorded_trace_reduction_pinned():
    red = devtrace.reduce_events(devtrace.events_from_profile(
        _profile("gisette.xplane.pb.gz")))
    assert red["busy_s"] == pytest.approx(0.002183271, abs=1e-12)
    assert red["window_s"] == pytest.approx(0.01999763, abs=1e-12)
    assert {k: (pytest.approx(v["seconds"], abs=1e-12), v["runs"])
            for k, v in red["programs"].items()} == {
        "jit_convert_element_type": (4.763e-06, 8),
        "jit_broadcast_in_dim": (1.818e-06, 3),
        "jit__threefry_seed": (6.72e-07, 1),
        "jit_outer": (0.002202098, 3)}
    assert [(n.split(" = ")[0], s) for n, s in red["device_ops"][:5]] == [
        ("%while.27", pytest.approx(0.001705769, abs=1e-12)),
        ("%fusion.71", pytest.approx(0.000742792, abs=1e-12)),
        ("%multiply_reduce_fusion.5", pytest.approx(0.000390523, abs=1e-12)),
        ("%while.28", pytest.approx(0.000355953, abs=1e-12)),
        ("%multiply_reduce_fusion.8", pytest.approx(0.000140155, abs=1e-12))]


def test_busy_within_matches_brute_force():
    """Busy time inside a span, by bisection, against marking each ps."""
    events = [("", 3, 4), ("", 5, 6), ("", 20, 2), ("", 30, 10), ("", 31, 2)]
    busy = scopes._Busy(events)
    mark = [False] * 50
    for _, s, d in events:
        for t in range(s, s + d):
            mark[t] = True
    for a in range(0, 45, 3):
        for b in range(a, 50, 4):
            assert busy.within(a, b) == sum(mark[a:b]), (a, b)
