"""Continuous-batching serving loop (DESIGN.md section 14): deadline vs
full flush policy, best-c selection over path families, measured-crossover
scorer routing, capacity-padded banks, zero-downtime hot-swap (zero
recompiles, torn-read-free responses), admission control, the Poisson
driver, and the committed BENCH_serve2.json acceptance guard."""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.serve import artifact as art
from repro.serve.loop import (ServeLoop, ServeOverload, SwapCapacityError,
                              _bank_capacity, drive_poisson)
from repro.serve.predict import (ModelBank, margins_dense, pick_route,
                                 scorer_cache_sizes, set_route_crossover)

RNG = np.random.default_rng(13)


def _binary_family(n, nnz, seed=0, scale=1.0, meta=None):
    rng = np.random.default_rng(seed)
    w = np.zeros(n, np.float64)
    w[rng.choice(n, nnz, replace=False)] = scale * rng.standard_normal(nnz)
    m = art.artifact_from_solution(w, "logistic", c=1.0,
                                   bias=float(rng.standard_normal()),
                                   meta=meta or {})
    return art.ModelFamily(kind="binary", models=(m,))


def _path_family(n, metas, seed=0):
    """kind="path" family with one member per meta dict; member i has
    i+1 nonzeros (strictly growing support, like a real c-sweep)."""
    rng = np.random.default_rng(seed)
    sup = rng.choice(n, len(metas), replace=False)
    models = []
    for i, meta in enumerate(metas):
        w = np.zeros(n, np.float64)
        w[sup[:i + 1]] = rng.standard_normal(i + 1)
        models.append(art.artifact_from_solution(
            w, "logistic", c=float(2.0 ** i), meta=dict(meta, nnz=i + 1)))
    return art.ModelFamily(kind="path", models=tuple(models))


# -- pick_best_c --------------------------------------------------------------

def test_pick_best_c_metric_ties_and_errors():
    fam = _path_family(64, [{"val_accuracy": 0.70},
                            {"val_accuracy": 0.90},
                            {"val_accuracy": 0.90},
                            {"val_accuracy": 0.85}])
    # max metric, tie (members 1 and 2 at 0.90) -> fewer nonzeros wins
    i, best = art.pick_best_c(fam, metric="val_accuracy")
    assert i == 1 and best.nnz == 2
    # metric="nnz" -> sparsest member
    i, best = art.pick_best_c(fam, metric="nnz")
    assert i == 0 and best.nnz == 1
    # a family whose members never recorded the metric has nothing to
    # select on — the error points at --val-frac
    bare = _path_family(64, [{}, {}])
    with pytest.raises(ValueError, match="val-frac"):
        art.pick_best_c(bare)
    with pytest.raises(ValueError, match="path"):
        art.pick_best_c(_binary_family(64, 3))


def test_pick_best_c_equal_nnz_tie_prefers_earlier_grid_point():
    rng = np.random.default_rng(4)
    sup = rng.choice(32, 2, replace=False)
    models = []
    for i in range(2):                       # same metric, same nnz
        w = np.zeros(32, np.float64)
        w[sup] = rng.standard_normal(2)
        models.append(art.artifact_from_solution(
            w, "logistic", c=float(i + 1), meta={"val_accuracy": 0.8}))
    fam = art.ModelFamily(kind="path", models=tuple(models))
    i, _ = art.pick_best_c(fam)
    assert i == 0                            # smaller c, stronger l1


# -- capacity-padded banks ----------------------------------------------------

def test_capacity_bank_pads_and_scores_identically():
    rng = np.random.default_rng(2)
    W = (rng.standard_normal((3, 48)) * (rng.random((3, 48)) < 0.2)) \
        .astype(np.float32)
    tight = ModelBank.from_dense(W, kind="path")
    wide = ModelBank.from_dense(W, kind="path", a_cap=2 * tight.a_max,
                                u_cap=2 * int(tight.union_idx.shape[0]))
    assert wide.a_max == 2 * tight.a_max
    assert int(wide.union_idx.shape[0]) == 2 * int(tight.union_idx.shape[0])
    X = rng.standard_normal((9, 48)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(margins_dense(wide, X)),
                               np.asarray(margins_dense(tight, X)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(margins_dense(wide, X, route="dense")),
        np.asarray(margins_dense(tight, X)), rtol=1e-4, atol=1e-4)


def test_capacity_overflow_raises():
    rng = np.random.default_rng(3)
    W = rng.standard_normal((2, 32)).astype(np.float32)   # fully dense rows
    with pytest.raises(ValueError, match="capacity"):
        ModelBank.from_dense(W, a_cap=4)
    with pytest.raises(ValueError, match="capacity"):
        ModelBank.from_dense(W, u_cap=8)


def test_bank_capacity_headroom():
    fam = _path_family(64, [{"val_accuracy": 0.7}, {"val_accuracy": 0.8},
                            {"val_accuracy": 0.9}])
    a_cap, u_cap = _bank_capacity(fam, 2.0)
    assert a_cap == 6 and u_cap == 6         # max nnz 3, union 3, x2


# -- measured-crossover routing -----------------------------------------------

def test_pick_route_uses_crossover_table():
    try:
        set_route_crossover([
            {"sparsity": 0.9, "min_batch_sparse": None},
            {"sparsity": 0.99, "min_batch_sparse": 256},
            {"sparsity": 0.999, "min_batch_sparse": 64}])
        assert pick_route(0.95, 10_000) == "dense"   # None: dense always
        assert pick_route(0.995, 255) == "dense"
        assert pick_route(0.995, 256) == "sparse"
        assert pick_route(0.9995, 64) == "sparse"
        assert pick_route(0.9995, 63) == "dense"
        assert pick_route(0.5, 4096) == "dense"      # below the table
    finally:
        set_route_crossover(None)                    # restore measured file


def test_margins_route_equivalence_and_validation():
    rng = np.random.default_rng(5)
    W = (rng.standard_normal((4, 40)) * (rng.random((4, 40)) < 0.15)) \
        .astype(np.float32)
    bank = ModelBank.from_dense(W, kind="path")
    X = rng.standard_normal((13, 40)).astype(np.float32)
    want = np.asarray(margins_dense(bank, X))        # sparse route
    np.testing.assert_allclose(
        np.asarray(margins_dense(bank, X, route="dense")), want,
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(margins_dense(bank, X, route="auto")), want,
        rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="route"):
        margins_dense(bank, X, route="csc")


# -- the loop: flush policy ---------------------------------------------------

def test_loop_full_and_deadline_and_drain_flushes():
    fam = _binary_family(32, 5, seed=7)
    with ServeLoop(fam, buckets=(4,), default_budget_s=10.0) as loop:
        X = RNG.standard_normal((4, 32)).astype(np.float32)
        # a full bucket flushes immediately regardless of the far deadline
        futs = loop.submit_many(X)
        res = [f.result(timeout=30) for f in futs]
        assert all(r.flush_reason == "full" and r.bucket == 4 for r in res)
        want = np.asarray(margins_dense(loop.bank(), X))
        np.testing.assert_allclose(
            np.stack([r.margins for r in res]), want, rtol=1e-5, atol=1e-5)
        # a lone request cannot fill the bucket: its own deadline flushes it
        r1 = loop.submit(X[0], budget_s=0.05).result(timeout=30)
        assert r1.flush_reason == "deadline"
        assert r1.latency_s <= 5.0           # bounded, not stranded
        # requests pending at stop() flush as "drain"
        f_last = loop.submit(X[1], budget_s=10.0)
    r_last = f_last.result(timeout=30)
    assert r_last.flush_reason == "drain"
    st = loop.stats()["models"]["default"]
    assert st["flushes"]["full"] >= 1
    assert st["flushes"]["deadline"] >= 1
    assert st["flushes"]["drain"] >= 1
    assert loop.stats()["responses"] == 6


def test_loop_multi_model_routing_and_validation():
    fams = {"a": _binary_family(24, 4, seed=1),
            "b": _binary_family(40, 6, seed=2)}    # heterogeneous widths
    with ServeLoop(fams, buckets=(1, 2), default_budget_s=0.05) as loop:
        assert loop.models() == ("a", "b")
        xa = RNG.standard_normal(24).astype(np.float32)
        xb = RNG.standard_normal(40).astype(np.float32)
        ra = loop.submit(xa, model="a").result(timeout=30)
        rb = loop.submit(xb, model="b").result(timeout=30)
        assert ra.model == "a" and rb.model == "b"
        np.testing.assert_allclose(
            ra.margins, np.asarray(margins_dense(loop.bank("a"),
                                                 xa[None, :]))[0],
            rtol=1e-5, atol=1e-5)
        with pytest.raises(ValueError, match="pick one"):
            loop.submit(xa)                  # ambiguous without model=
        with pytest.raises(KeyError, match="unknown model"):
            loop.submit(xa, model="zzz")
        with pytest.raises(ValueError, match="features"):
            loop.submit(xa, model="b")       # 24 features into a 40-wide slot


def test_loop_overload_admission_control():
    fam = _binary_family(16, 3, seed=9)
    with ServeLoop(fam, buckets=(8,), default_budget_s=30.0,
                   max_queue=4) as loop:
        X = RNG.standard_normal((8, 16)).astype(np.float32)
        futs = [loop.submit(x) for x in X[:4]]   # fills the queue; the far
        with pytest.raises(ServeOverload):       # deadline parks the flush
            loop.submit(X[4])
        assert loop.stats()["rejects"] == 1
    assert all(f.result(timeout=30).flush_reason == "drain" for f in futs)


# -- warm start + hot swap ----------------------------------------------------

def test_loop_steady_traffic_and_swap_never_recompile():
    """The warm-start regression: every (slot, bucket) program is compiled
    at construction, so steady traffic — including ACROSS a hot-swap —
    leaves every jit cache exactly where warmup put it."""
    fam = _binary_family(48, 6, seed=11)
    with ServeLoop(fam, buckets=(1, 2, 4), default_budget_s=0.05) as loop:
        assert loop.stats()["compiles"] >= 1     # warmup did compile
        sizes0 = scorer_cache_sizes()
        X = RNG.standard_normal((16, 48)).astype(np.float32)
        for f in loop.submit_many(X[:5]):
            f.result(timeout=30)
        assert scorer_cache_sizes() == sizes0    # traffic: no compiles
        ticket = loop.swap(model=_binary_family(48, 9, seed=12))
        assert ticket.installed.wait(timeout=30)
        assert ticket.version == 2
        for f in loop.submit_many(X[5:]):
            f.result(timeout=30)
        assert scorer_cache_sizes() == sizes0    # swap + traffic: still none
        st = loop.stats()["models"]["default"]
        assert st["version"] == 2 and st["installs"] == 1


def test_hot_swap_responses_match_version_at_flush_time():
    """Torn-read correctness: under concurrent submit/swap traffic, every
    response's margins equal a from-scratch score with the bank version
    that was installed at its batch's flush time."""
    n = 40
    fams = [_binary_family(n, 5, seed=21 + v, scale=1.0 + v)
            for v in range(3)]
    caps = _bank_capacity(fams[0], 2.0)
    ref_banks = {v + 1: ModelBank.from_family(f, a_cap=caps[0],
                                              u_cap=caps[1])
                 for v, f in enumerate(fams)}
    X = RNG.standard_normal((60, n)).astype(np.float32)
    results, errors = [], []

    with ServeLoop(fams[0], buckets=(1, 2, 4),
                   default_budget_s=0.01) as loop:
        def swapper():
            for f in fams[1:]:
                time.sleep(0.02)
                loop.swap(model=f).installed.wait(timeout=30)
        th = threading.Thread(target=swapper)
        th.start()
        for x in X:
            try:
                results.append(loop.submit(x).result(timeout=30))
            except Exception as e:            # pragma: no cover
                errors.append(e)
        th.join()

    assert not errors
    assert len(results) == len(X)
    seen = sorted({r.version for r in results})
    assert seen[0] == 1 and seen[-1] == 3     # traffic spanned all installs
    for i, r in enumerate(results):
        want = np.asarray(margins_dense(ref_banks[r.version],
                                        X[i][None, :]))[0]
        np.testing.assert_allclose(r.margins, want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"request {i} version {r.version}")


def test_swap_from_path_family_picks_best_c():
    fam0 = _binary_family(64, 4, seed=31)
    path = _path_family(64, [{"val_accuracy": 0.6}, {"val_accuracy": 0.95},
                             {"val_accuracy": 0.8}], seed=32)
    _, best = art.pick_best_c(path)
    with ServeLoop(fam0, buckets=(1,), default_budget_s=0.02) as loop:
        loop.swap(model=path).installed.wait(timeout=30)
        x = RNG.standard_normal(64).astype(np.float32)
        r = loop.submit(x).result(timeout=30)
        want = float(x @ best.dense_weights(np.float64) + best.bias)
        assert r.version == 2
        np.testing.assert_allclose(r.margins, [want], rtol=1e-4, atol=1e-4)


def test_swap_capacity_error():
    fam0 = _binary_family(64, 4, seed=41)
    too_big = _binary_family(64, 30, seed=42)   # > 2x headroom of nnz=4
    with ServeLoop(fam0, buckets=(1,), capacity_factor=2.0) as loop:
        with pytest.raises(SwapCapacityError):
            loop.swap(model=too_big)
        with pytest.raises(SwapCapacityError, match="do not match"):
            loop.swap(model=ModelBank.from_dense(
                np.ones((2, 64), np.float32)))  # K=2 into a K=1 slot
        assert loop.version() == 1              # slot untouched


# -- poisson driver -----------------------------------------------------------

def test_drive_poisson_accounts_offered_load():
    fam = _binary_family(32, 4, seed=51)
    X = RNG.standard_normal((16, 32)).astype(np.float32)
    with ServeLoop(fam, buckets=(1, 2, 4, 8), default_budget_s=0.25,
                   max_queue=64) as loop:
        out = drive_poisson(loop, X, rate_rps=300.0, n_requests=60,
                            seed=3, timeout_s=60.0)
    assert out["responses"] + out["rejects"] == out["n_requests"] == 60
    assert out["offered_rps"] > 0
    assert len(out["results"]) == out["responses"]
    if out["responses"]:
        assert out["p99_s"] >= out["p50_s"] > 0
    with pytest.raises(ValueError, match="rate_rps"):
        drive_poisson(None, X, rate_rps=0.0, n_requests=1)


# -- committed benchmark guards -----------------------------------------------

def _bench(name):
    path = os.path.join(os.path.dirname(__file__), os.pardir, name)
    if not os.path.exists(path):
        pytest.skip(f"no {name} checked out")
    payload = json.load(open(path))
    if payload.get("smoke"):
        pytest.skip("local --smoke run overwrote the committed full-run "
                    "figures; the acceptance number is pinned on full runs")
    return payload


def test_bench_serve2_headline_loop_vs_sync():
    """The committed BENCH_serve2.json must report the acceptance number:
    the continuous-batching loop sustains >= 2x the synchronous
    per-request baseline's rows/s at the same p99 SLO."""
    payload = _bench("BENCH_serve2.json")
    assert payload["headline_speedup"] >= 2.0
    assert payload["loop"]["max_sustained_rps"] is not None
    assert payload["loop"]["max_sustained_rps"] >= \
        2.0 * payload["sync"]["max_sustained_rps"]


def test_bench_serve2_hot_swap_is_invisible():
    """Hot-swap under load: zero recompiles and zero SLO violations
    attributable to the swap windows."""
    hs = _bench("BENCH_serve2.json")["hot_swap"]
    assert hs["n_swaps"] >= 1
    assert hs["recompiles"] == 0
    assert hs["swap_window_violations"] == 0
    assert hs["rejects"] == 0
    # every install landed and traffic saw each version
    assert len(set(hs["response_versions"])) == hs["n_swaps"] + 1


def test_bench_serve_commits_route_crossover_table():
    """BENCH_serve.json carries the measured dense-vs-sparse crossover
    that pick_route / --route auto consult."""
    table = _bench("BENCH_serve.json")["route_crossover"]
    assert [r["sparsity"] for r in table] == sorted(
        r["sparsity"] for r in table)
    for row in table:
        assert row["min_batch_sparse"] is None or row["min_batch_sparse"] >= 1
    # at extreme sparsity the union-gather route must win somewhere
    assert any(r["sparsity"] >= 0.999 and r["min_batch_sparse"] is not None
               for r in table)


# -- batch-failure resilience (DESIGN.md section 16.6) -------------------------

def test_batch_retry_recovers_transient_failure(monkeypatch):
    """One transient scorer failure is retried in place: the caller's
    future resolves normally and only the retry counter moves."""
    import repro.serve.loop as loop_mod
    fam = _binary_family(32, 5, seed=3)
    real = margins_dense
    boom = {"left": 1}

    def flaky(bank, X, **kw):
        if boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("transient device loss")
        return real(bank, X, **kw)

    with ServeLoop(fam, buckets=(1,), default_budget_s=0.05,
                   batch_retries=2) as loop:
        x = RNG.standard_normal(32).astype(np.float32)
        monkeypatch.setattr(loop_mod, "margins_dense", flaky)
        r = loop.submit(x).result(timeout=30)
        np.testing.assert_allclose(
            r.margins, np.asarray(real(loop.bank(), x[None, :]))[0],
            rtol=1e-5, atol=1e-5)
        st = loop.stats()["models"]["default"]
        assert st["retries"] == 1
        assert st["failed_batches"] == 0
        assert st["consecutive_failures"] == 0
        assert not st["quarantined"]
        assert loop.stats()["errors"] == 0


def test_quarantine_after_consecutive_failures_and_swap_clears(monkeypatch):
    """Retries exhausted N batches in a row -> the slot quarantines
    (clear error on submit, the loop itself keeps serving) and a
    hot-swap install clears it."""
    import repro.serve.loop as loop_mod
    fam = _binary_family(32, 5, seed=4)
    real = margins_dense

    def broken(bank, X, **kw):
        raise RuntimeError("wedged scorer")

    with ServeLoop(fam, buckets=(1,), default_budget_s=0.05,
                   batch_retries=0, quarantine_after=2) as loop:
        x = RNG.standard_normal(32).astype(np.float32)
        monkeypatch.setattr(loop_mod, "margins_dense", broken)
        for _ in range(2):                      # two one-request batches
            with pytest.raises(RuntimeError, match="wedged"):
                loop.submit(x).result(timeout=30)
        st = loop.stats()["models"]["default"]
        assert st["failed_batches"] == 2
        assert st["consecutive_failures"] == 2
        assert st["quarantined"]
        from repro.serve.loop import SlotQuarantined
        with pytest.raises(SlotQuarantined, match="quarantined after 2"):
            loop.submit(x)
        # the model is sick, not the loop: install a replacement...
        monkeypatch.setattr(loop_mod, "margins_dense", real)
        ticket = loop.swap(model=_binary_family(32, 7, seed=5))
        assert ticket.installed.wait(timeout=30)
        # ...and the slot serves again
        r = loop.submit(x).result(timeout=30)
        st = loop.stats()["models"]["default"]
        assert not st["quarantined"]
        assert st["consecutive_failures"] == 0
        assert r.version == 2


def test_failure_streak_resets_on_success(monkeypatch):
    import repro.serve.loop as loop_mod
    fam = _binary_family(24, 4, seed=6)
    real = margins_dense
    fail_next = {"on": True}

    def sometimes(bank, X, **kw):
        if fail_next["on"]:
            raise RuntimeError("blip")
        return real(bank, X, **kw)

    with ServeLoop(fam, buckets=(1,), default_budget_s=0.05,
                   batch_retries=0, quarantine_after=2) as loop:
        x = RNG.standard_normal(24).astype(np.float32)
        monkeypatch.setattr(loop_mod, "margins_dense", sometimes)
        with pytest.raises(RuntimeError):
            loop.submit(x).result(timeout=30)
        fail_next["on"] = False
        loop.submit(x).result(timeout=30)       # success resets the streak
        fail_next["on"] = True
        with pytest.raises(RuntimeError):
            loop.submit(x).result(timeout=30)
        st = loop.stats()["models"]["default"]
        assert st["failed_batches"] == 2        # total failures kept
        assert st["consecutive_failures"] == 1  # but the STREAK reset
        assert not st["quarantined"]


def test_quarantine_disabled_and_param_validation(monkeypatch):
    import repro.serve.loop as loop_mod
    fam = _binary_family(16, 3, seed=8)

    def broken(bank, X, **kw):
        raise RuntimeError("always down")

    with ServeLoop(fam, buckets=(1,), default_budget_s=0.05,
                   batch_retries=0, quarantine_after=None) as loop:
        x = RNG.standard_normal(16).astype(np.float32)
        monkeypatch.setattr(loop_mod, "margins_dense", broken)
        for _ in range(4):                      # never quarantines
            with pytest.raises(RuntimeError):
                loop.submit(x).result(timeout=30)
        assert not loop.stats()["models"]["default"]["quarantined"]
    with pytest.raises(ValueError, match="batch_retries"):
        ServeLoop(fam, buckets=(1,), batch_retries=-1)
    with pytest.raises(ValueError, match="quarantine_after"):
        ServeLoop(fam, buckets=(1,), quarantine_after=0)


def test_predict_serve_exits_nonzero_when_a_batch_fails(monkeypatch,
                                                        tmp_path):
    """`launch.predict --serve` counts failed responses and exits non-zero
    when there are any, instead of reporting a run that served nothing."""
    import repro.serve.loop as loop_mod
    from repro.data.libsvm import save_libsvm
    from repro.launch import predict as launch_predict
    n = 32
    model = tmp_path / "m.json"
    art.save_model(str(model), _binary_family(n, 5, seed=8))
    X = RNG.standard_normal((16, n)).astype(np.float32)
    reqs = tmp_path / "r.svm"
    save_libsvm(str(reqs), X, np.where(X[:, 0] > 0, 1.0, -1.0))

    real = margins_dense

    def broken(bank, X, **kw):
        # warmup (main thread) compiles; traffic (scheduler thread) fails
        if threading.current_thread() is threading.main_thread():
            return real(bank, X, **kw)
        raise RuntimeError("scorer lost its device")

    monkeypatch.setattr(loop_mod, "margins_dense", broken)
    with pytest.raises(SystemExit) as exc:
        launch_predict.main(["--model", str(model), "--dataset", str(reqs),
                             "--serve", "--serve-requests", "12",
                             "--rate", "400", "--max-batch", "4"])
    assert "12 of 12 responses failed" in str(exc.value)
    assert "scorer lost its device" in str(exc.value)
