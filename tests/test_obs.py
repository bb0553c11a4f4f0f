"""Telemetry subsystem (DESIGN.md section 13): registry / trace units,
the record_aux engine contract on both backends, the zero-cost-when-
disabled guarantees, wall-clock bookkeeping, and the SolveHistory edge
paths (divergence guard, lockstep freeze, shrink recheck)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import PCDNConfig, make_problem, scdn, solve
from repro.core.scdn import SCDNConfig
from repro.data import make_classification
from repro.engine import (LocalBackend, ShardedBackend, ShardedPCDNConfig,
                          loop as engine_loop)
from repro.launch.mesh import make_host_mesh


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with both telemetry planes off — the
    module-level gates are process state and must not leak across tests
    (or into the rest of the suite)."""
    obs.disable()
    obs.registry.reset()
    yield
    obs.disable()
    obs.registry.reset()


@pytest.fixture(scope="module")
def data():
    return make_classification(300, 128, sparsity=0.8, corr=0.3, seed=2)


@pytest.fixture(scope="module")
def problem(data):
    X, y, _ = data
    return make_problem(X, y, c=1.0)


# ---------------------------------------------------------------------------
# registry units


def test_registry_disabled_records_nothing():
    obs.inc("x")
    obs.set_gauge("g", 1.0)
    obs.observe("h", 0.5)
    obs.observe_many("h", [1.0, 2.0])
    assert obs.registry.get_registry().empty


def test_registry_counters_gauges_histograms():
    obs.registry.enable()
    obs.inc("c")
    obs.inc("c", 2.0)
    obs.set_gauge("g", 7.0)
    obs.observe_many("q", [1, 1, 1, 2, 3], bounds=obs.Q_BOUNDS)
    snap = obs.registry.get_registry().snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    h = snap["histograms"]["q"]
    assert h["count"] == 5 and h["min"] == 1 and h["max"] == 3


def test_histogram_quantiles_interpolate():
    h = obs.Histogram(bounds=(1.0, 2.0, 4.0, 8.0))
    h.observe_many([0.5] * 50 + [3.0] * 50)
    # half the mass below 1.0, half in (2, 4]: p50 sits at the boundary,
    # p99 inside the (2, 4] bucket
    assert h.quantile(0.5) <= 2.0
    assert 2.0 < h.quantile(0.99) <= 4.0


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv("REPRO_METRICS", "off")
    assert obs.registry.enable() is False
    obs.inc("x")
    assert obs.registry.get_registry().empty


def test_write_metrics_jsonl(tmp_path):
    obs.registry.enable()
    obs.inc("runs")
    path = tmp_path / "m.jsonl"
    obs.write_metrics(str(path), meta={"cli": "test"})
    obs.write_metrics(str(path), meta={"cli": "test"})
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 2
    rec = json.loads(lines[0])
    assert rec["cli"] == "test"
    assert rec["metrics"]["counters"]["runs"] == 1.0


# ---------------------------------------------------------------------------
# trace plane: program spans as profiler annotations


class _Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: records the name of
    every annotation built, and the metadata attached to it."""

    def __init__(self):
        self.names, self.metadata = [], {}

    def __call__(self, name, **args):
        self.names.append(name)
        rec = self

        class _Span:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **kw):
                rec.metadata.setdefault(name, []).append(kw)
        return _Span()


def _record_spans(monkeypatch, tracing: bool) -> _Recorder:
    rec = _Recorder()
    monkeypatch.setattr(obs.trace, "TraceAnnotation", rec)
    monkeypatch.setattr(obs.trace, "_tracing", lambda: tracing)
    return rec


def _host_spans(trace_dir) -> list:
    """(name, start ns, end ns, line) of every host event of the one
    .xplane.pb under trace_dir whose name is a program span."""
    import glob
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        line.name) for e in line.events
                       if e.name.startswith(("engine.", "serve.", "path.")))
    return out


def _profile(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(trace_dir), profiler_options=opts)


def test_trace_disabled_span_is_null():
    assert not obs.trace_enabled()
    span = obs.span("x")
    assert span is obs.trace._NULL_SPAN
    with span as entered:
        assert entered is None


def test_outer_loop_builds_no_annotation_when_off(monkeypatch, problem):
    """With no profiler session the engine loop builds no annotation and
    attaches no span arguments: one check per span site."""
    rec = _record_spans(monkeypatch, tracing=False)
    res = solve(problem, PCDNConfig(P=32, max_outer=5, tol_kkt=1e-8,
                                    seed=0))
    assert res.n_outer == 5
    assert rec.names == [] and rec.metadata == {}


def test_outer_loop_spans_per_iteration(monkeypatch, problem):
    rec = _record_spans(monkeypatch, tracing=True)
    res = solve(problem, PCDNConfig(P=32, max_outer=4, tol_kkt=1e-8,
                                    seed=0))
    phases = ["engine.outer", "engine.dispatch", "engine.wait",
              "engine.pull", "engine.record"]
    assert rec.names == phases * res.n_outer
    args = rec.metadata["engine.outer"]
    assert [a["k"] for a in args] == list(range(res.n_outer))
    assert args[-1]["objective"] == res.objective


def test_profiler_trace_nests_engine_spans(tmp_path, problem):
    """In a CPU profiler trace each iteration's four phases sit inside
    its engine.outer span, on the same thread, in order."""
    cfg = PCDNConfig(P=32, max_outer=3, tol_kkt=1e-8, seed=0)
    solve(problem, cfg)                        # compile outside the trace
    with _profile(tmp_path):
        res = solve(problem, cfg)
    spans = _host_spans(tmp_path)
    outers = sorted(s for s in spans if s[0] == "engine.outer")
    assert len(outers) == res.n_outer
    for _, a, b, line in outers:
        inner = sorted((s for s in spans if s[0] != "engine.outer"
                        and a <= s[1] and s[2] <= b), key=lambda s: s[1])
        assert [s[0] for s in inner] == ["engine.dispatch", "engine.wait",
                                         "engine.pull", "engine.record"]
        assert {s[3] for s in inner} == {line}


def test_profiler_trace_holds_serve_spans(tmp_path):
    """The serving loop's spans reach the profiler from the scheduler
    thread: warm-up, idle stretches, and each flush with its phases."""
    from repro.serve.loop import ServeLoop
    from repro.serve.predict import ModelBank
    rng = np.random.default_rng(0)
    W = np.zeros((3, 64), np.float32)
    W[:, :8] = rng.standard_normal((3, 8))
    X = rng.standard_normal((20, 64)).astype(np.float32)
    with _profile(tmp_path):
        with ServeLoop(ModelBank.from_dense(W, kind="path"),
                       buckets=(4, 8), default_budget_s=0.02) as loop:
            futs = loop.submit_many(X)
            for f in futs:
                f.result(timeout=30)
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    assert {"serve.warmup", "serve.wait", "serve.flush", "serve.stack",
            "serve.score", "serve.respond"} <= names
    flushes = [s for s in spans if s[0] == "serve.flush"]
    assert 3 <= len(flushes) <= 20
    for _, a, b, line in flushes:
        inner = [s[0] for s in spans if s[0] in ("serve.stack",
                                                 "serve.score",
                                                 "serve.respond")
                 and a <= s[1] and s[2] <= b and s[3] == line]
        assert inner == ["serve.stack", "serve.score", "serve.respond"]


def _sweep(problem, **kw):
    from repro.path import PathConfig, run_path
    cfg = PathConfig(solver=PCDNConfig(P=32, max_outer=30, tol_kkt=1e-2,
                                       shrink=True),
                     n_points=4, span=10.0, stop="relative")
    return run_path(problem, cfg, **kw)


def test_profiler_trace_nests_path_spans(tmp_path, problem, data):
    """Each point's margin refresh, weight pull and held-out scoring sit
    inside its path.point span, in order, around its engine iterations;
    the point's host seconds outside the engine loop are recorded under
    the profiler as without it."""
    X, y, _ = data
    _sweep(problem, val_design=X[:60], val_y=y[:60])   # compile first
    with _profile(tmp_path):
        res = _sweep(problem, val_design=X[:60], val_y=y[:60])
    spans = _host_spans(tmp_path)
    points = sorted(s for s in spans if s[0] == "path.point")
    assert len(points) == len(res.points) == 4
    for (_, a, b, line), p in zip(points, res.points):
        inner = sorted((s for s in spans if s[0].startswith("path.")
                        and s[0] != "path.point" and a <= s[1]
                        and s[2] <= b), key=lambda s: s[1])
        assert [s[0] for s in inner] == ["path.refresh", "path.pull",
                                         "path.validate"]
        assert {s[3] for s in inner} == {line}
        outers = [s for s in spans if s[0] == "engine.outer"
                  and a <= s[1] and s[2] <= b]
        assert len(outers) == p.n_outer
        assert inner[0][2] <= min(s[1] for s in outers)
        assert inner[1][1] >= max(s[2] for s in outers)
        assert 0.0 < p.between_s < p.seconds <= (b - a) * 1e-9 + 1e-3


def test_path_host_seconds_recorded_without_a_profiler(monkeypatch,
                                                       problem):
    rec = _record_spans(monkeypatch, tracing=False)
    res = _sweep(problem)
    assert rec.names == []
    for p in res.points:
        assert 0.0 < p.between_s < p.seconds
        assert len(p.n_active) == p.n_outer


def test_checkpoint_without_new_point_fields_resumes(tmp_path, problem):
    """A path checkpoint written before PathPoint had `between_s` and
    `n_active` still resumes: its points read None there."""
    import glob
    from repro import fault
    ref = _sweep(problem)
    ck_dir = str(tmp_path / "p")
    with pytest.raises(fault.InjectedCrash):
        _sweep(problem, ckpt=fault.SolveCheckpointer(ck_dir, every=10),
               fault_plan=fault.FaultPlan(crash_at_point=1,
                                          crash_kind="exception"))
    manifests = glob.glob(os.path.join(ck_dir, "step_*", "manifest.json"))
    assert manifests
    for path in manifests:
        with open(path) as fh:
            manifest = json.load(fh)
        for p in manifest["extra"]["points"]:
            del p["between_s"], p["n_active"]
        with open(path, "w") as fh:
            json.dump(manifest, fh)
    res = _sweep(problem, ckpt=fault.SolveCheckpointer(ck_dir, every=10),
                 resume=True)
    assert [p.between_s for p in res.points[:2]] == [None, None]
    assert [p.n_active for p in res.points[:2]] == [None, None]
    assert all(p.between_s is not None for p in res.points[2:])
    np.testing.assert_array_equal(res.weights, ref.weights)


def test_queue_wait_adds_up():
    """queue_wait_s is the sum over rows of (flush start - submit): with
    one row per flush, every row's latency less its flush's compute."""
    from repro.serve.loop import ServeLoop
    from repro.serve.predict import ModelBank
    W = np.zeros((2, 32), np.float32)
    W[:, 0] = 1.0
    X = np.ones((12, 32), np.float32)
    with ServeLoop(ModelBank.from_dense(W, kind="path"), buckets=(1,),
                   default_budget_s=0.01) as loop:
        stats0 = loop.stats()["models"]["default"]
        results = [f.result(timeout=30) for f in loop.submit_many(X)]
    stats = loop.stats()["models"]["default"]
    assert stats0["queue_wait_s"] == 0.0
    compute = sum(h.total for h in loop._slots["default"].compute.values())
    latency = sum(r.latency_s for r in results)
    assert stats["rows"] == len(results) == 12
    assert stats["queue_wait_s"] > 0
    assert stats["queue_wait_s"] == pytest.approx(latency - compute,
                                                  rel=1e-9, abs=1e-12)


# ---------------------------------------------------------------------------
# record_aux: the 10th-output engine contract (DESIGN.md section 13.2)


def test_local_outer_arity_disabled_vs_enabled(problem):
    """Without record_aux the outer returns EXACTLY the 9-tuple contract
    — no extra device outputs ride along for a disabled plane."""
    cfg = PCDNConfig(P=32, max_outer=5, seed=0)
    b_off = LocalBackend(problem, cfg)
    st = b_off.init_state()
    out = b_off.outer(st.w, st.z, st.key, st.active, jnp.asarray(True),
                      jnp.asarray(1.0, st.w.dtype))
    assert len(out) == 9

    import dataclasses
    b_on = LocalBackend(problem,
                        dataclasses.replace(cfg, record_aux=True))
    out = b_on.outer(st.w, st.z, st.key, st.active, jnp.asarray(True),
                     jnp.asarray(1.0, st.w.dtype))
    assert len(out) == 10
    q, alpha = out[9]
    b = problem.n_features // 32 + (problem.n_features % 32 > 0)
    assert q.shape == (b,) and alpha.shape == (b,)


def test_local_aux_lands_in_history_and_matches_ls_steps(problem):
    cfg = PCDNConfig(P=32, max_outer=10, tol_kkt=1e-8, seed=0,
                     record_aux=True)
    res = solve(problem, cfg)
    h = res.history
    assert h.bundle_q is not None and h.bundle_alpha is not None
    K = res.n_outer
    assert h.bundle_q.shape[0] == K == h.bundle_alpha.shape[0]
    # no shrinking: every bundle runs every iteration, no sentinels
    assert np.all(h.bundle_q >= 0)
    assert np.all(np.isfinite(h.bundle_alpha))
    # ls_steps was always the mean over bundles; the aux series must
    # reproduce it exactly
    np.testing.assert_allclose(h.bundle_q.mean(axis=1), h.ls_steps,
                               rtol=1e-6)
    # accepted alphas are Armijo-valid: beta^q in [0, 1] (0 when a
    # bundle exhausts its backtracks near convergence)
    assert np.all(h.bundle_alpha >= 0) and np.all(h.bundle_alpha <= 1.0)


def test_record_aux_does_not_perturb_solution(problem):
    cfg = PCDNConfig(P=32, max_outer=15, tol_kkt=1e-8, seed=0)
    import dataclasses
    r0 = solve(problem, cfg)
    r1 = solve(problem, dataclasses.replace(cfg, record_aux=True))
    assert r1.n_outer == r0.n_outer
    np.testing.assert_array_equal(np.asarray(r0.w), np.asarray(r1.w))
    assert r0.history.bundle_q is None
    assert r1.history.bundle_q is not None


def test_shrink_aux_uses_sentinels(data):
    """Shrinking runs a data-dependent number of bundles per iteration;
    slots past the live count must carry q == -1 / alpha == nan, and the
    two sentinel masks must agree."""
    X, y, _ = data
    prob = make_problem(X, y, c=1.0)      # shrinks to ~16 of 128 active
    cfg = PCDNConfig(P=32, max_outer=40, tol_kkt=1e-6, seed=0,
                     shrink=True, record_aux=True)
    res = solve(prob, cfg)
    h = res.history
    assert h.bundle_q is not None
    ran = h.bundle_q >= 0
    np.testing.assert_array_equal(ran, np.isfinite(h.bundle_alpha))
    assert ran.any(), "some bundles must have run"
    assert (~ran).any(), "shrinking must have idled some bundle slots"
    # rows stay consistent with the rest of the history
    assert h.bundle_q.shape[0] == len(h.n_active) == res.n_outer


def test_fused_kernel_path_reports_aux(problem):
    cfg = PCDNConfig(P=32, max_outer=5, tol_kkt=1e-8, seed=0,
                     use_kernels=True, record_aux=True)
    res = solve(problem, cfg)
    assert res.history.bundle_q is not None
    assert np.all(res.history.bundle_q >= 0)


def test_sharded_1x1_aux(data):
    X, y, _ = data
    mesh = make_host_mesh(1, 1)
    cfg = ShardedPCDNConfig(P_local=32, c=1.0, seed=0, record_aux=True)
    backend = ShardedBackend(X, y, mesh, cfg)
    res = engine_loop.solve(backend, 1.0, max_outer=6, tol_kkt=1e-8)
    h = res.history
    assert h.bundle_q is not None and h.bundle_alpha is not None
    assert h.bundle_q.shape[0] == res.n_outer
    assert np.all(h.bundle_q >= 0)
    np.testing.assert_allclose(h.bundle_q.mean(axis=1), h.ls_steps,
                               rtol=1e-5)


def test_sharded_disabled_arity(data):
    X, y, _ = data
    mesh = make_host_mesh(1, 1)
    cfg = ShardedPCDNConfig(P_local=32, c=1.0, seed=0)
    backend = ShardedBackend(X, y, mesh, cfg)
    st = backend.init_state()
    out = backend.outer(st.w, st.z, st.key, st.active, jnp.asarray(True),
                        jnp.asarray(1.0, backend.dtype))
    assert len(out) == 9
    res = engine_loop.solve(backend, 1.0, max_outer=3, tol_kkt=1e-8)
    assert res.history.bundle_q is None


def test_solver_loop_zero_registry_activity_when_disabled(problem):
    """The acceptance guarantee: an uninstrumented run leaves the
    registry COMPLETELY untouched — no counter, gauge or histogram may
    appear as a side effect of solving."""
    solve(problem, PCDNConfig(P=32, max_outer=5, seed=0))
    assert obs.registry.get_registry().empty
    assert not obs.trace_enabled()


def test_solver_loop_populates_registry_when_enabled(problem):
    obs.enable()
    cfg = PCDNConfig(P=32, max_outer=8, tol_kkt=1e-8, seed=0,
                     record_aux=True)
    res = solve(problem, cfg)
    snap = obs.registry.get_registry().snapshot()
    assert snap["counters"]["solver.outer_iters"] == res.n_outer
    assert snap["histograms"]["solver.iter_seconds"]["count"] == res.n_outer
    hq = snap["histograms"]["solver.bundle_q"]
    assert hq["count"] == int(np.sum(res.history.bundle_q >= 0))
    assert snap["gauges"]["solver.n_active"] == res.history.n_active[-1]


# ---------------------------------------------------------------------------
# wall-clock bookkeeping (the block_until_ready-before-timestamp fix)


def test_wall_clock_monotone_and_sums_to_total(problem):
    import time
    cfg = PCDNConfig(P=32, max_outer=12, tol_kkt=1e-8, seed=0)
    t0 = time.perf_counter()
    res = solve(problem, cfg)
    total = time.perf_counter() - t0
    wt = res.history.wall_time
    assert wt.shape == (res.n_outer,)
    # cumulative seconds: strictly nondecreasing, and the final entry
    # accounts for (almost) the whole solve — device work synced before
    # each timestamp, so no iteration's time leaks past the last row
    assert np.all(np.diff(wt) >= 0)
    assert 0 < wt[-1] <= total
    assert wt[-1] >= 0.5 * total, \
        "per-iteration times must account for the bulk of the solve"


def test_iter_seconds_histogram_consistent_with_wall_time(problem):
    obs.enable()
    cfg = PCDNConfig(P=32, max_outer=10, tol_kkt=1e-8, seed=0)
    res = solve(problem, cfg)
    h = obs.registry.get_registry().snapshot()[
        "histograms"]["solver.iter_seconds"]
    # summed per-iteration device+host time cannot exceed the loop's own
    # cumulative clock (it excludes history bookkeeping between syncs)
    assert h["count"] == res.n_outer
    assert h["sum"] <= res.history.wall_time[-1] * 1.5


# ---------------------------------------------------------------------------
# SolveHistory edge paths


def test_divergence_guard_history_consistent():
    X, y, _ = make_classification(300, 200, sparsity=0.0, corr=0.95,
                                  seed=2, row_normalize=False)
    prob = make_problem(X, y, c=1.0)
    obs.enable()
    res = scdn.solve(prob, SCDNConfig(P_bar=64, max_rounds=30))
    assert res.diverged and not res.converged
    # the aborted loop still records one consistent row per round run
    k = res.n_rounds
    assert len(res.history["objective"]) == k
    assert len(res.history["wall_time"]) == k
    assert obs.registry.get_registry().snapshot()[
        "counters"]["solver.divergence_trips"] == 1.0


def test_divergence_guard_emits_trace_instant(monkeypatch):
    X, y, _ = make_classification(300, 200, sparsity=0.0, corr=0.95,
                                  seed=2, row_normalize=False)
    prob = make_problem(X, y, c=1.0)
    rec = _record_spans(monkeypatch, tracing=True)
    scdn.solve(prob, SCDNConfig(P_bar=64, max_rounds=30))
    assert rec.names.count("engine.divergence_guard") == 1
    assert rec.names.count("engine.divergence_postmortem") == 1


def test_lockstep_freeze_bitwise():
    """A problem frozen at iteration k must keep its carry bit-identical
    to its value AT k while stragglers keep iterating."""
    rates = jnp.asarray([0.1, 0.5, 0.9], jnp.float32)

    def outer(x, r):
        x = x * r
        kkt = jnp.abs(x)
        return x, x, kkt, jnp.ones_like(x, jnp.int32)

    x0 = jnp.ones((3,), jnp.float32)
    (x,), f, kkt, nnz, n_outer, done = engine_loop.run_lockstep_loop(
        outer, (x0,), (rates,), max_outer=100, tol_kkt=1e-3,
        dtype=jnp.float32)
    assert bool(jnp.all(done))
    x = np.asarray(x)
    n_outer = np.asarray(n_outer)

    def ref(rate, k):
        """Iterative f32 product — the exact arithmetic the loop does."""
        v = np.float32(1.0)
        for _ in range(int(k)):
            v = np.float32(v * np.float32(rate))
        return v

    for i, rate in enumerate((0.1, 0.5, 0.9)):
        # froze exactly at the first k where |x| crosses tol ...
        assert abs(ref(rate, n_outer[i])) <= 1e-3
        assert abs(ref(rate, n_outer[i] - 1)) > 1e-3
        # ... and the frozen value is bit-identical to the value AT k
        assert x[i] == ref(rate, n_outer[i])
    # slower decay -> strictly more iterations (stragglers kept running
    # after the fast problem froze)
    assert n_outer[0] < n_outer[1] < n_outer[2]


def test_shrink_recheck_history_consistent(data):
    """recheck_every > 1: iterations between rechecks still record full
    history rows; n_active may only grow ON a recheck iteration."""
    X, y, _ = data
    prob = make_problem(X, y, c=1.0)
    cfg = PCDNConfig(P=32, max_outer=40, tol_kkt=1e-6, seed=0,
                     shrink=True, recheck_every=5, record_aux=True)
    res = solve(prob, cfg)
    h = res.history
    k = res.n_outer
    for field in ("objective", "kkt", "nnz", "ls_steps", "wall_time",
                  "n_active"):
        assert len(getattr(h, field)) == k, field
    assert h.bundle_q.shape[0] == k
    grow = np.flatnonzero(np.diff(h.n_active) > 0) + 1
    assert all(g % 5 == 0 for g in grow), \
        "un-shrink may only happen on recheck iterations"


# ---------------------------------------------------------------------------
# serving + kernels instrumentation


def test_batcher_latency_quantiles_and_counters():
    from repro.serve.batcher import MicroBatcher
    from repro.serve.predict import ModelBank
    rng = np.random.default_rng(0)
    W = np.zeros((4, 256), np.float32)
    W[:, :8] = rng.standard_normal((4, 8))
    bank = ModelBank.from_dense(W, kind="path")
    obs.enable()
    b = MicroBatcher(bank, buckets=(8, 32), layout="dense")
    X = rng.standard_normal((64, 256)).astype(np.float32)
    for lo, hi in ((0, 5), (5, 37), (37, 64), (0, 30)):
        b.predict(X[lo:hi])
    stats = b.stats()
    assert stats["latency_p50_s"] is not None
    assert stats["latency_p99_s"] >= stats["latency_p50_s"]
    for bucket in stats["buckets"]:
        assert "latency_p50_s" in bucket
    snap = obs.registry.get_registry().snapshot()
    assert snap["counters"]["serve.rows"] == 64 + 30
    assert snap["counters"]["serve.compiles"] == 2  # one per bucket


def test_batcher_disabled_zero_registry_activity():
    from repro.serve.batcher import MicroBatcher
    from repro.serve.predict import ModelBank
    W = np.zeros((2, 64), np.float32)
    W[:, 0] = 1.0
    b = MicroBatcher(ModelBank.from_dense(W, kind="path"),
                     buckets=(8,), layout="dense")
    b.predict(np.ones((5, 64), np.float32))
    assert obs.registry.get_registry().empty


def test_autotune_lookup_counters(monkeypatch, tmp_path):
    from repro.kernels import autotune
    obs.enable()
    # disabled tuner -> every lookup is a miss ("defaults were used")
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert autotune.lookup("pcdn_direction", (64, 64), "float32") is None
    snap = obs.registry.get_registry().snapshot()
    assert snap["counters"]["autotune.lookup_misses"] == 1.0
    assert "autotune.lookup_hits" not in snap["counters"]


def test_kernel_launch_counter_eager_only():
    """Eager ops.* dispatch increments kernels.<name>.launches; the same
    op traced under jit must not touch the registry from inside tracing
    (that would be a host callback in the compiled path)."""
    from repro.kernels import ops
    obs.enable()
    XB = jnp.ones((8, 4), jnp.float32)
    u = jnp.full((8,), 0.25, jnp.float32)
    v = jnp.ones((8,), jnp.float32)
    w_B = jnp.zeros((4,), jnp.float32)
    ops.pcdn_direction(XB, u, v, w_B)
    counters = obs.registry.get_registry().counters
    assert counters.get("kernels.pcdn_direction.launches") == 1.0

    @jax.jit
    def traced(XB, u, v, w_B):
        return ops.pcdn_direction(XB, u, v, w_B)[0]
    traced(XB, u, v, w_B)
    counters = obs.registry.get_registry().counters
    assert counters.get("kernels.pcdn_direction.launches") == 1.0, \
        "traced dispatch must not count launches"


# ---------------------------------------------------------------------------
# CLI integration (in-process)


def test_solve_cli_metrics_and_trace(tmp_path):
    from repro.launch import solve as solve_cli
    from repro.data.libsvm import save_libsvm
    X, y, _ = make_classification(120, 60, sparsity=0.5, seed=0)
    ds = tmp_path / "d.svm"
    save_libsvm(str(ds), X, y)
    mpath, tpath, rpath = (str(tmp_path / n) for n in
                           ("m.jsonl", "trace", "r.json"))
    solve_cli.main(["--dataset", str(ds), "--P", "16", "--max-outer", "10",
                    "--tol", "1e-6", "--c", "5.0",
                    "--metrics-out", mpath, "--trace-out", tpath,
                    "--out", rpath])
    # a profiler trace: the .xplane.pb holds the engine's spans
    names = [s[0] for s in _host_spans(tpath)]
    assert names.count("engine.outer") == len(
        json.load(open(rpath))["history"]["objective"])
    rec = json.loads(open(mpath).read().strip().splitlines()[-1])
    assert rec["cli"] == "solve"
    assert "solver.bundle_q" in rec["metrics"]["histograms"]
    report = json.load(open(rpath))
    assert "bundle_q" in report["history"]
    assert "bundle_alpha" in report["history"]
    # CLI run disables the planes on exit
    assert not obs.metrics_enabled() and not obs.trace_enabled()


def test_solve_cli_without_flags_records_nothing(tmp_path):
    from repro.launch import solve as solve_cli
    from repro.data.libsvm import save_libsvm
    X, y, _ = make_classification(120, 60, sparsity=0.5, seed=0)
    ds = tmp_path / "d.svm"
    save_libsvm(str(ds), X, y)
    rpath = str(tmp_path / "r.json")
    solve_cli.main(["--dataset", str(ds), "--P", "16", "--max-outer", "5",
                    "--c", "5.0", "--out", rpath])
    assert obs.registry.get_registry().empty
    report = json.load(open(rpath))
    assert "bundle_q" not in report["history"]


def test_trace_out_runs_the_program_users_run(tmp_path):
    """--trace-out alone compiles no aux outputs (the traced run is the
    untraced program), and the report CLI reads its Perfetto file."""
    from repro.diag import report as diag_report
    from repro.launch import solve as solve_cli
    from repro.data.libsvm import save_libsvm
    X, y, _ = make_classification(120, 60, sparsity=0.5, seed=0)
    ds = tmp_path / "d.svm"
    save_libsvm(str(ds), X, y)
    tdir, rpath = str(tmp_path / "trace"), str(tmp_path / "r.json")
    solve_cli.main(["--dataset", str(ds), "--P", "16", "--max-outer", "5",
                    "--c", "5.0", "--trace-out", tdir, "--out", rpath])
    assert "bundle_q" not in json.load(open(rpath))["history"]
    assert not obs.trace_enabled()
    md = tmp_path / "health.md"
    assert diag_report.main(["--trace", tdir, "-o", str(md)]) == 0
    assert "engine.outer" in md.read_text()


_COMPILE_OUTER = r"""
import argparse, contextlib, sys
import jax, jax.numpy as jnp
cache, mode, trace_dir = sys.argv[1:4]
jax.config.update("jax_compilation_cache_dir", cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if mode == "unscoped":
    jax.named_scope = lambda name: contextlib.nullcontext()
from repro.core import PCDNConfig
from repro.core.design_matrix import DenseDesign
from repro.core.problem import L1Problem
from repro.engine.local import LocalBackend
from repro.launch import common

def outer(X, y, w, z, key, active):
    prob = L1Problem(design=DenseDesign(X=X), y=y, c=1.0)
    return LocalBackend(prob, PCDNConfig(P=8)).outer(
        w, z, key, active, jnp.bool_(True), jnp.float32(1.0))

args = argparse.Namespace(metrics_out=None,
                          trace_out=trace_dir if mode == "traced" else None)
common.setup_obs(args)
f32 = jnp.float32
text = jax.jit(outer).lower(
    jax.ShapeDtypeStruct((40, 24), f32), jax.ShapeDtypeStruct((40,), f32),
    jax.ShapeDtypeStruct((24,), f32), jax.ShapeDtypeStruct((40,), f32),
    jax.ShapeDtypeStruct((2,), jnp.uint32),
    jax.ShapeDtypeStruct((24,), jnp.bool_)).compile().as_text()
common.finish_obs(args)
print("SCOPED" if "/pcdn.direction/" in text else "UNSCOPED")
"""


def test_trace_out_does_not_load_an_unscoped_executable(tmp_path):
    """The persistent compile cache keys a program without its op
    metadata. After a cache was filled by a build without the phase
    scopes, an untraced run loads that executable (its metadata names no
    phase), but a --trace-out run compiles one whose does."""
    repo = os.path.dirname(os.path.dirname(__file__))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu")
    cache = str(tmp_path / "cache")

    def run(mode):
        r = subprocess.run([sys.executable, "-c", _COMPILE_OUTER, cache,
                            mode, str(tmp_path / "trace")],
                           capture_output=True, text=True, env=env,
                           cwd=repo, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()[-1]

    assert run("unscoped") == "UNSCOPED"
    assert run("plain") == "UNSCOPED"       # loaded from the cache
    assert run("traced") == "SCOPED"


def test_obs_validate_cli(tmp_path):
    obs.registry.enable()
    obs.inc("runs")
    good = tmp_path / "good.jsonl"
    obs.write_metrics(str(good))
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"ts": "t"}) + "\n")
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-m", "repro.obs.validate",
                        str(good)], capture_output=True, text=True,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run([sys.executable, "-m", "repro.obs.validate",
                        str(good), str(bad)], capture_output=True,
                       text=True,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       env=env)
    assert r.returncode != 0
