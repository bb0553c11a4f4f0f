"""Unit tests of the benchmark's yardstick, on the CPU: trace reduction,
work counts, peaks, the load generator, the relative stop and the
window arithmetic, and the command's refusals."""
from __future__ import annotations

import gzip
import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import data, devtrace, load, reference, work

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TESTDATA = os.path.join(BENCH, "testdata")


# -- trace reduction ----------------------------------------------------------

def _profile(name):
    from jax.profiler import ProfileData
    path = os.path.join(TESTDATA, name)
    if name.endswith(".pb.gz"):
        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    with open(path) as fh:
        return ProfileData.from_text_proto(fh.read())


def _union_ns(intervals):
    """Brute-force union length, by marking every nanosecond."""
    lo = min(a for a, _ in intervals)
    hi = max(b for _, b in intervals)
    mark = np.zeros(hi - lo, bool)
    for a, b in intervals:
        mark[a - lo:b - lo] = True
    return int(mark.sum())


def test_hand_trace_reduction():
    """Overlapping ops, an op that crosses the window's edge, a second
    device plane and host spans: every number by hand."""
    ev = devtrace.events_from_profile(_profile("hand.xspace.txt"))
    red = devtrace.reduce_events(ev)
    # window [1000, 11000) ns; device 0 busy [1000,3000)+[2500,4000) and
    # [6000,7000) -> 4000 ns; device 1 busy [10000, 11000) after the
    # clip of [10000, 12000) -> 1000 ns; mean over the two devices
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx(2500e-9)
    assert red["devices"] == 2
    assert red["programs"]["jit_outer"] == {"seconds": pytest.approx(8e-6),
                                           "runs": 2}
    sec, runs = devtrace.program_seconds(red, "jit_outer")
    assert (sec, runs) == (pytest.approx(8e-6), 2)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(3.5e-6)]
    # the longest gap of device 1 is [1000, 10000), under bench.solve
    assert red["idle_gaps"][0] == ["bench.solve", pytest.approx(9e-6)]


def test_recorded_trace_reduction():
    """A trace recorded on a TPU v5e (a few outer iterations of
    solve.gisette, trimmed): busy time against a brute-force union, and
    the outer step's runs against the module line."""
    ev = devtrace.events_from_profile(_profile("gisette.xplane.pb.gz"))
    red = devtrace.reduce_events(ev)
    (_, lo, dur), = [s for s in ev["spans"] if s[0] == devtrace.WINDOW_SPAN]
    (device, ops), = ev["ops"].items()
    clipped = [(max(a, lo), min(a + d, lo + dur)) for _, a, d in ops
               if a + d > lo and a < lo + dur]
    assert red["busy_s"] == pytest.approx(_union_ns(clipped) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    sec, runs = devtrace.program_seconds(red, "jit_outer")
    assert runs == sum(1 for n, a, d in ev["modules"][device]
                       if n.startswith("jit_outer") and a >= lo
                       and a + d <= lo + dur)
    assert runs > 0 and sec > 0


def test_reduction_needs_one_window():
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.reduce_events({"ops": {}, "modules": {}, "spans": []})


@pytest.mark.parametrize("trace_s, traced_solves", [(None, 3), (0.0, 1)])
def test_traced_window_reads_its_spans(monkeypatch, trace_s, traced_solves):
    """A traced window records its own span and the benchmark's spans
    inside it, and reduces them; with `trace_s` the trace ends at the
    first lap past it and the window runs on untraced."""
    import jax
    import jax.numpy as jnp
    from bench import run
    seen = {}
    reduce = devtrace.reduce_events

    def keep(ev):
        seen.update(ev)
        return reduce(ev)
    monkeypatch.setattr(devtrace, "reduce_events", keep)
    tracer = run.Tracer(True, jax.devices(), trace_s)
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    with tracer.window():
        for _ in range(3):
            with tracer.span("bench.solve"):
                f(jnp.ones(8)).block_until_ready()
            tracer.lap()
    names = sorted(name for name, _, _ in seen["spans"])
    assert names == ["bench.solve"] * traced_solves + [devtrace.WINDOW_SPAN]
    assert tracer.reduced["window_s"] > 0
    assert set(tracer.cost) == {"stop_s", "read_s", "reduce_s"}


def test_merge():
    assert devtrace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4),
                                                                (5, 8)]


# -- work counts and peaks -----------------------------------------------------

def test_dense_outer_work():
    w = work.outer_iteration_work(s=4800, n=5000, P=256, nnz=0, dense=True)
    assert w["bundles"] == 20
    assert w["bytes"] == 4 * 4800 * 5000 + 20 * 12 * 4800
    assert w["flops"] == 7 * 4800 * 5000


def test_sparse_outer_work():
    s, n, P, nnz = 57848, 20958, 256, 2_900_000
    w = work.outer_iteration_work(s=s, n=n, P=P, nnz=nnz, dense=False)
    rows = s * (1 - math.exp(-P * (nnz / n) / s))
    assert w["bundles"] == 82
    assert w["rows_per_bundle"] == pytest.approx(rows)
    assert w["bytes"] == pytest.approx(8 * nnz + 82 * 12 * rows)
    assert w["flops"] == 7 * nnz
    # the distinct rows of a bundle never exceed s nor P * k entries
    assert rows < min(s, P * nnz / n)


def test_least_seconds_takes_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["flops_per_s"] == 197e12
    assert work.least_seconds({"bytes": 819e9, "flops": 1.0}, peak) == 1.0
    assert work.least_seconds({"bytes": 1.0, "flops": 394e12}, peak) == 2.0


def test_missing_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


# -- references ----------------------------------------------------------------

def test_reference_padded_matches_dense():
    """The padded-layout references equal the dense ones on the same
    matrix, duplicates of a (row, column) slot adding up."""
    Xp, y = data.sparse_classification(300, 40, nnz_per_col=12, seed=3)
    Xd = np.zeros((300, 40))
    for j in range(40):
        for r, v in zip(Xp.col_rows[j], Xp.col_vals[j]):
            if r < 300:
                Xd[r, j] += v
    w = np.random.default_rng(0).standard_normal(40) * (np.arange(40) % 3)
    assert reference.objective_kkt(Xp, y, w, 2.0)[:2] == pytest.approx(
        reference.objective_kkt(Xd, y, w, 2.0)[:2], rel=1e-12)
    assert reference.objective_kkt(reference.as_float64(Xp), y, w, 2.0)[:2] \
        == pytest.approx(reference.objective_kkt(Xd, y, w, 2.0)[:2],
                         rel=1e-12)
    np.testing.assert_allclose(reference.matvec(Xp, w), Xd @ w, rtol=1e-12,
                               atol=1e-12)
    rows = np.array([5, 17, 299])
    np.testing.assert_allclose(data.dense_rows(Xp, rows), Xd[rows],
                               rtol=1e-6)


def test_generators_match_the_programs():
    """The benchmark's copies of the generators draw the same data as
    the program's own at the same seed (today; the copy is what counts
    once the program's changes)."""
    from repro.data import synthetic
    Xp, y = data.sparse_classification(500, 60, nnz_per_col=9, seed=2**33)
    Xq, yq, _ = synthetic.make_sparse_classification(500, 60, nnz_per_col=9,
                                                     seed=2**33)
    np.testing.assert_array_equal(Xp.col_rows, Xq.col_rows)
    np.testing.assert_array_equal(Xp.col_vals, Xq.col_vals)
    np.testing.assert_array_equal(y, yq)
    spec = {"generator": "dense", "s_generated": 100, "s": 80, "n": 50,
            "sparsity": 0.009, "corr": 0.8, "scale_pm1": True}
    Xd, yd = data.build(spec, 5)
    Xt, yt, _, _, _ = synthetic.paper_like("gisette", scale=100 / 6000,
                                           seed=5, with_test=True)
    assert Xt.shape == (80, 83)     # paper_like rounds n differently
    X2, y2 = data.dense_classification(100, 83, 0.009, 0.8, True, seed=5)
    np.testing.assert_array_equal(X2[:80], Xt)
    np.testing.assert_array_equal(y2[:80], yt)
    assert Xd.shape == (80, 50) and yd.shape == (80,)


def test_kkt_at_zero_is_the_gradient_box_excess():
    X = np.array([[1.0, 0.0], [0.0, 2.0], [1.0, 1.0]])
    y = np.array([1.0, -1.0, 1.0])
    # g = X^T (c * y * (sigmoid(0) - 1)) = -c/2 * X^T y = -(c/2) [2, -1]
    assert reference.kkt_at_zero(X, y, 4.0) == pytest.approx(3.0)


# -- load generator -------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class _Done:
    def __init__(self, t_done):
        self.t_done = t_done

    def result(self, timeout=None):
        return self


class _Failed:
    def result(self, timeout=None):
        raise RuntimeError("batch failed")


def test_schedule_same_gaps_for_every_seed():
    d1, r1 = load.schedule(500.0, 4.0, 64, seed=1)
    d2, r2 = load.schedule(500.0, 4.0, 64, seed=2**40 + 3)
    assert d1.shape == d2.shape == (2000,)
    assert d1[-1] == pytest.approx(4.0) and d2[-1] == pytest.approx(4.0)
    np.testing.assert_allclose(np.sort(np.diff(d1, prepend=0)),
                               np.sort(np.diff(d2, prepend=0)))
    assert not np.array_equal(d1, d2)
    assert r1.max() < 64 and r2.min() >= 0


def test_partition_keys_same_set_for_every_seed():
    """A fixed set of k keys runs in cycles: every full cycle of every seed
    holds the same keys, in an order the seed draws."""
    import itertools
    from bench import solve
    mix = {"partitions": 8, "partition_seed": 7}
    a = list(itertools.islice(solve.partition_keys(mix, 1), 24))
    b = list(itertools.islice(solve.partition_keys(mix, 2**40 + 5), 24))
    assert a != b
    for run_keys in (a, b):
        cycles = [sorted(run_keys[i:i + 8]) for i in range(0, 24, 8)]
        assert cycles == [sorted(a[:8])] * 3 and len(set(a[:8])) == 8


def test_drive_times_from_due_and_counts_refusals():
    """A generator that stalls makes later requests late: their latency
    runs from when they were due, and a refused or failed request never
    counts as answered."""
    clock = _Clock()
    due = np.array([0.0, 0.010, 0.020, 0.030])
    calls = []

    def submit(x):
        calls.append((x, clock.t))
        if x == "r1":
            clock.t += 0.050            # the generator stalls in a submit
            return _Done(clock.t)
        if x == "r2":
            raise RuntimeError("queue full")
        if x == "r3":
            return _Failed()
        return _Done(clock.t + 0.005)

    sent = load.drive(submit, ["r0", "r1", "r2", "r3"], due,
                      np.arange(4), clock=clock, sleep=clock.sleep)
    assert sent["refused"] == 1
    assert [round(t - 100.0, 6) for _, t in calls] == [0.0, 0.01, 0.06,
                                                       0.06]
    assert sent["lag_max_s"] == pytest.approx(0.040)
    lat, results = load.collect(sent["futures"], sent["t0"], due)
    assert lat[0] == pytest.approx(0.005)
    assert lat[1] == pytest.approx(0.050)
    assert math.isinf(lat[2]) and math.isinf(lat[3])
    assert results[2] is None and results[3] is None
    assert load.percentile(lat, 99) == math.inf
    assert load.percentile(np.array([1.0, 2.0, 3.0, 4.0]), 50) == 3.0


# -- the relative stop and the window ------------------------------------------

def test_solve_stop_and_window_arithmetic(tiny_cell, cpu_devices):
    from bench import run
    cell = tiny_cell("solve.gisette")
    out, notes = run.run_cell(cell, 2**33 + 7, 0.5, False, cpu_devices,
                              t_setup0=0.0)
    X, y = data.build(cell["config"]["data"], cell["config"]["data"]["seed"])
    kkt0 = reference.kkt_at_zero(X, y, cell["config"]["c"])
    assert notes["kkt0"] == pytest.approx(kkt0)
    assert notes["tol_kkt"] == pytest.approx(cell["traffic"]["eps"] * kkt0)
    n = out["attempted"]
    assert n == len(notes["n_outer"]) >= 1 and out["failed"] == 0
    solve_s = out["metrics"]["solve_s"]["value"]
    # the window runs to the end of the solve that was running when
    # `seconds` elapsed, and solve_s is that span over every solve in it
    ends = notes["ends_s"]
    assert len(ends) == n >= 2
    assert ends[-1] >= 0.5 > ends[-2]
    assert solve_s == pytest.approx(ends[-1] / n)
    # every solve stopped on the engine's KKT, which the float64
    # reference confirms to within the KKT gap's limit
    assert out["checks"]["unstopped"]["value"] == 0
    assert out["correct"]


# -- the command ----------------------------------------------------------------

def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "solve.gisette",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            pass
    return False


class _FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("devices, interpret, match", [
    ([_FakeTPU()], True, "interpreted"),
    ([_FakeTPU()], False, None),
    ([], False, "needs a TPU"),
])
def test_check_chip_refuses_interpreted_kernels(monkeypatch, devices,
                                               interpret, match):
    """A TPU whose Pallas kernels would run in interpret mode is refused
    like no TPU at all; four chips asked of one is refused too."""
    import jax
    from bench import run
    from repro.kernels import ops
    monkeypatch.setattr(jax, "devices", lambda: devices or jax.local_devices(
        backend="cpu"))
    monkeypatch.setattr(ops, "interpret_mode", lambda: interpret)
    if match is None:
        assert run.check_chip(1) == devices
        with pytest.raises(run.NoChip, match="needs 4 chips"):
            run.check_chip(4)
    else:
        with pytest.raises(run.NoChip, match=match):
            run.check_chip(1)


def test_command_refuses_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not _has_result(proc.stdout)


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(str(tmp_path))
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)


# -- the spec and the files it names ---------------------------------------------

def test_every_cell_resolves_to_its_files():
    """Each cell's configuration, traffic mix and traffic module, and each
    metric's reader, are files found by name; every limit is set."""
    from bench import run
    spec = run.load_spec()
    for path in spec["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    for w in spec["workloads"]:
        cell = run.resolve(spec, w["name"])
        assert os.path.isfile(os.path.join(
            BENCH, cell["traffic"]["kind"] + ".py"))
        assert all(lim is not None
                   for lim in cell["config"]["limits"].values())
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(run._reader(m["name"]))
            assert m["moves"] in names
