"""Fault-tolerance subsystem (DESIGN.md section 16): atomic writes,
crash-safe checkpoint/resume for solves and path sweeps (bit-exact, and
across device counts — the checkpoints are mesh-agnostic host arrays),
the engine's non-finite detector + rollback, automatic P-backoff toward
the certified safe bundle size, the deterministic fault-injection
harness, and the CLI kill-resume path (a SIGKILL'd sweep resumed with
--resume produces the same artifact as the uninterrupted run)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import PCDNConfig, make_problem, with_bundle_size
from repro.data import make_classification
from repro.engine import (LocalBackend, ShardedBackend, ShardedPCDNConfig,
                          loop as engine_loop)
from repro import fault
from repro.fault import atomic
from repro.launch.mesh import make_mesh
from repro.path.driver import PathConfig, run_path

# tol reachable at EVERY bundle size the backoff schedule can visit:
# a backed-off retry (P=16 on this problem) plateaus above 1e-4 in f32,
# so rollback tests must not demand the high-P tolerance.
TOL = 1e-3


@pytest.fixture(scope="module")
def data():
    return make_classification(300, 128, sparsity=0.8, corr=0.3, seed=2)


@pytest.fixture(scope="module")
def prob(data):
    X, y, _ = data
    return make_problem(X, y, c=1.0)


def _factory(prob, **kw):
    cfg = PCDNConfig(P=32, max_outer=80, tol_kkt=TOL, **kw)

    def factory(P):
        return LocalBackend(prob, with_bundle_size(cfg, P))
    return factory


# -- atomic writes ------------------------------------------------------------

def test_atomic_write_roundtrip(tmp_path):
    p = str(tmp_path / "a.json")
    atomic.atomic_write_json(p, {"x": 1})
    assert json.load(open(p)) == {"x": 1}
    atomic.atomic_write_text(str(tmp_path / "t.txt"), "hi")
    assert open(tmp_path / "t.txt").read() == "hi"


def test_atomic_write_never_tears(tmp_path):
    """A failed write leaves the previous contents AND no tmp debris —
    the torn-file regression for the serve artifact hot-swap watcher."""
    p = str(tmp_path / "model.json")
    atomic.atomic_write_json(p, {"good": True})
    with pytest.raises(TypeError):
        atomic.atomic_write_json(p, {"bad": object()})   # unserializable
    assert json.load(open(p)) == {"good": True}          # intact
    assert [f for f in os.listdir(tmp_path)
            if f.startswith(".tmp-")] == []              # no debris


def test_save_model_is_atomic(tmp_path):
    """serve.artifact.save_model goes through the atomic writer: a
    reserved-key clash raises BEFORE the old artifact is disturbed."""
    from repro.serve import artifact as art
    rng = np.random.default_rng(0)
    w = np.zeros(32)
    w[rng.choice(32, 4, replace=False)] = 1.0
    fam = art.ModelFamily(kind="binary", models=(
        art.artifact_from_solution(w, "logistic", c=1.0),))
    p = str(tmp_path / "m.json")
    art.save_model(p, fam)
    good = open(p).read()
    with pytest.raises(ValueError, match="collide"):
        art.save_model(p, fam, extra={"models": []})
    assert open(p).read() == good
    assert art.load_model(p).n_features == 32


# -- fault plan / injection harness -------------------------------------------

def test_fault_plan_validation():
    with pytest.raises(ValueError, match="crash_kind"):
        fault.FaultPlan(crash_kind="nope")
    with pytest.raises(ValueError, match="nan_target"):
        fault.FaultPlan(nan_target="gradient")


def test_plan_from_env(monkeypatch):
    monkeypatch.delenv(fault.ENV_VAR, raising=False)
    assert fault.plan_from_env() is None
    monkeypatch.setenv(fault.ENV_VAR,
                       '{"crash_at_point": 2, "crash_kind": "sigkill"}')
    plan = fault.plan_from_env()
    assert plan.crash_at_point == 2 and plan.crash_kind == "sigkill"
    monkeypatch.setenv(fault.ENV_VAR, '{"typo_at_iter": 1}')
    with pytest.raises(ValueError, match="unknown keys"):
        fault.plan_from_env()
    monkeypatch.setenv(fault.ENV_VAR, '[1, 2]')
    with pytest.raises(ValueError, match="JSON object"):
        fault.plan_from_env()


def test_injection_fires_once():
    plan = fault.FaultPlan(crash_at_iter=1)
    calls = {"n": 0}

    def outer(w, z, key, active, recheck, c):
        calls["n"] += 1
        return ("w", "z", "key", 0.0, 0.0, 0, 0.0, "active", 0)

    wrapped = fault.wrap_outer(outer, plan)
    args = (None, None, None, None, True, 1.0)
    wrapped(*args)                       # k=0: clean
    with pytest.raises(fault.InjectedCrash):
        wrapped(*args)                   # k=1: crash
    # re-wrap from the redo point, same plan: the hook already fired
    rewrapped = fault.wrap_outer(outer, plan, start_iter=1)
    rewrapped(*args)                     # k=1 again: clean now
    assert calls["n"] == 2


def test_next_bundle_size_schedule():
    assert fault.next_bundle_size(32) == 16
    assert fault.next_bundle_size(1) == 1
    assert fault.next_bundle_size(256, p_cert=48) == 128   # plain halving
    assert fault.next_bundle_size(64, p_cert=48) == 48     # certified floor
    assert fault.next_bundle_size(32, p_cert=48) == 16     # already below
    assert fault.next_bundle_size(2, p_cert=0) == 1        # degenerate cert


# -- engine non-finite detector -----------------------------------------------

def test_nan_guard_local(prob):
    """NaN injected into margins mid-solve: the engine STOPS at that
    iteration (today's divergence_guard(f) with f=NaN compares False and
    would loop to max_outer) and hands back the LAST GOOD iterate."""
    backend = LocalBackend(prob, PCDNConfig(P=32, max_outer=80,
                                            tol_kkt=TOL))
    plan = fault.FaultPlan(nan_at_iter=3, nan_target="margins")
    outer = fault.wrap_outer(backend.outer, plan)
    state, res = engine_loop.run_outer_loop(
        outer, backend.init_state(), 1.0, max_outer=80, tol_kkt=TOL)
    assert res.nonfinite and res.diverged and not res.converged
    assert int(res.history.outer_iter[-1]) == 3       # stopped right there
    assert np.isfinite(res.objective)                 # last GOOD objective
    assert np.all(np.isfinite(np.asarray(state.w)))   # rolled-back carry
    assert np.all(np.isfinite(np.asarray(state.z)))
    assert res.postmortem is not None                 # PR 9 forensics rode


def test_nan_guard_sharded_1x1(data):
    X, y, _ = data
    mesh = make_mesh((1, 1), ("data", "model"))
    backend = ShardedBackend(X, y, mesh,
                             ShardedPCDNConfig(P_local=32, c=1.0,
                                               tol_kkt=TOL))
    plan = fault.FaultPlan(nan_at_iter=2, nan_target="margins")
    outer = fault.wrap_outer(backend.outer, plan)
    state, res = engine_loop.run_outer_loop(
        outer, backend.init_state(), 1.0, max_outer=60, tol_kkt=TOL)
    assert res.nonfinite and res.diverged
    assert np.all(np.isfinite(backend.host_weights(state.w)))


def test_nan_guard_kkt_only(prob):
    """A NaN that reaches only the KKT scalar still trips the detector."""
    backend = LocalBackend(prob, PCDNConfig(P=32, max_outer=40,
                                            tol_kkt=TOL))
    plan = fault.FaultPlan(nan_at_iter=1, nan_target="kkt")
    state, res = engine_loop.run_outer_loop(
        fault.wrap_outer(backend.outer, plan), backend.init_state(), 1.0,
        max_outer=40, tol_kkt=TOL)
    assert res.nonfinite
    assert int(res.history.outer_iter[-1]) == 1


# -- rollback + P-backoff -----------------------------------------------------

def test_resilient_clean_solve_matches_plain(prob):
    factory = _factory(prob)
    plain = engine_loop.solve(factory(32), 1.0, max_outer=80, tol_kkt=TOL)
    res = fault.resilient_solve(factory, 1.0, P=32, max_outer=80,
                                tol_kkt=TOL)
    assert res.converged and res.faults is None
    np.testing.assert_array_equal(np.asarray(plain.w), res.w)


def test_rollback_backoff_converges(prob):
    """The acceptance scenario: NaN into margins mid-solve -> rollback,
    P halves toward the certified bound, and the retried solve still
    converges to the same KKT tolerance."""
    factory = _factory(prob)
    plan = fault.FaultPlan(nan_at_iter=3, nan_target="margins")
    res = fault.resilient_solve(factory, 1.0, P=32, max_outer=80,
                                tol_kkt=TOL, plan=plan, design=prob.design)
    assert res.converged
    assert res.faults["rollbacks"] == 1
    assert res.faults["p_schedule"] == [32, 16]
    assert res.faults["p_cert"] is not None
    assert float(res.history.kkt[-1]) <= TOL
    # the merged history is one contiguous global-iteration record
    assert (np.diff(np.asarray(res.history.outer_iter)) == 1).all()


def test_rollback_respects_certified_floor(prob):
    assert fault.next_bundle_size(32, p_cert=20) == 20
    factory = _factory(prob)
    plan = fault.FaultPlan(nan_at_iter=2, nan_target="weights")
    res = fault.resilient_solve(factory, 1.0, P=32, max_outer=80,
                                tol_kkt=TOL, plan=plan, p_cert=20)
    assert res.converged
    assert res.faults["p_schedule"] == [32, 20]


def test_rollback_retries_exhausted_surfaces_postmortem(prob):
    factory = _factory(prob)
    plan = fault.FaultPlan(nan_at_iter=3, nan_target="margins")
    res = fault.resilient_solve(factory, 1.0, P=32, max_outer=80,
                                tol_kkt=TOL, plan=plan, max_retries=0)
    assert res.nonfinite and not res.converged
    assert res.faults["rollbacks"] == 1
    assert np.isfinite(res.objective)        # still the last good iterate
    assert np.all(np.isfinite(res.w))


# -- solve checkpoint / resume ------------------------------------------------

def test_solve_checkpoint_resume_bit_exact(prob, tmp_path):
    factory = _factory(prob)
    ref = fault.resilient_solve(factory, 1.0, P=32, max_outer=80,
                                tol_kkt=TOL,
                                checkpointer=fault.SolveCheckpointer(
                                    str(tmp_path / "ref"), every=2))
    plan = fault.FaultPlan(crash_at_iter=3, crash_kind="exception")
    ck = fault.SolveCheckpointer(str(tmp_path / "x"), every=2)
    with pytest.raises(fault.InjectedCrash):
        fault.resilient_solve(factory, 1.0, P=32, max_outer=80,
                              tol_kkt=TOL, checkpointer=ck, plan=plan)
    res = fault.resilient_solve(
        factory, 1.0, P=32, max_outer=80, tol_kkt=TOL,
        checkpointer=fault.SolveCheckpointer(str(tmp_path / "x"), every=2),
        resume=True)
    assert res.converged
    assert res.faults["resumed_from"] is not None
    np.testing.assert_array_equal(ref.w, res.w)


def test_corrupted_checkpoints_skipped(prob, tmp_path):
    """Both damage modes are survived: a step missing COMMITTED (crash
    between write and commit) is invisible; a committed step whose
    arrays were later corrupted falls back to the previous one."""
    factory = _factory(prob)
    d = str(tmp_path / "ck")
    ref = fault.resilient_solve(factory, 1.0, P=32, max_outer=80,
                                tol_kkt=TOL,
                                checkpointer=fault.SolveCheckpointer(
                                    d, every=1, keep=10))
    mgr = fault.CheckpointManager(d)
    steps = mgr.steps()
    assert len(steps) >= 3
    fault.corrupt_checkpoint(d, step=steps[-1], mode="truncate")
    fault.corrupt_checkpoint(d, step=steps[-2], mode="uncommit")
    assert mgr.steps() == [s for s in steps if s != steps[-2]]
    got = mgr.restore_latest_valid_raw()
    assert got is not None
    step, _leaves, meta = got
    assert step == steps[-3]                 # skipped both damaged ones
    res = fault.resilient_solve(
        factory, 1.0, P=32, max_outer=80, tol_kkt=TOL,
        checkpointer=fault.SolveCheckpointer(d, every=1, keep=10),
        resume=True)
    assert res.converged
    np.testing.assert_array_equal(ref.w, res.w)


def test_solve_and_path_checkpoints_do_not_mix(prob, tmp_path):
    d = str(tmp_path / "ck")
    factory = _factory(prob)
    fault.resilient_solve(factory, 1.0, P=32, max_outer=80, tol_kkt=TOL,
                          checkpointer=fault.SolveCheckpointer(d, every=2))
    ck = fault.SolveCheckpointer(d, every=2)
    with pytest.raises(ValueError, match="separate --ckpt-dir"):
        ck.restore_path(factory(32), cs=np.asarray([1.0]), c_max=1.0)


def test_checkpointer_rejects_bad_cadence(tmp_path):
    with pytest.raises(ValueError, match=">= 1"):
        fault.SolveCheckpointer(str(tmp_path), every=0)


# -- path sweep checkpoint / resume -------------------------------------------

def _path_cfg():
    return PathConfig(solver=PCDNConfig(P=32, max_outer=60, tol_kkt=TOL),
                      n_points=5, span=30.0)


def test_path_crash_resume_bit_exact(prob, data, tmp_path):
    X, y, _ = data
    ref = run_path(prob, _path_cfg(), val_design=X, val_y=y)
    ck = fault.SolveCheckpointer(str(tmp_path / "p"), every=10)
    plan = fault.FaultPlan(crash_at_point=2, crash_kind="exception")
    with pytest.raises(fault.InjectedCrash):
        run_path(prob, _path_cfg(), val_design=X, val_y=y, ckpt=ck,
                 fault_plan=plan)
    res = run_path(prob, _path_cfg(), val_design=X, val_y=y,
                   ckpt=fault.SolveCheckpointer(str(tmp_path / "p"),
                                                every=10),
                   resume=True)
    np.testing.assert_array_equal(ref.weights, res.weights)
    assert res.best_index == ref.best_index
    assert [p.objective for p in res.points] == \
        [p.objective for p in ref.points]


def test_path_resume_rejects_different_grid(prob, data, tmp_path):
    X, y, _ = data
    ck = fault.SolveCheckpointer(str(tmp_path / "p"), every=10)
    run_path(prob, _path_cfg(), ckpt=ck)
    other = PathConfig(solver=PCDNConfig(P=32, max_outer=60, tol_kkt=TOL),
                       n_points=7, span=30.0)
    with pytest.raises(ValueError, match="different c-grid"):
        run_path(prob, other,
                 ckpt=fault.SolveCheckpointer(str(tmp_path / "p"),
                                              every=10),
                 resume=True)


# -- cross-device-count restore -----------------------------------------------

RESHARD_SCRIPT = r"""
import numpy as np, jax
from repro.data import make_classification
from repro.engine import (LocalBackend, ShardedBackend, ShardedPCDNConfig,
                          loop as engine_loop)
from repro.core import PCDNConfig, make_problem
from repro.fault import SolveCheckpointer, host_state
from repro.launch.mesh import make_mesh

X, y, _ = make_classification(256, 64, sparsity=0.8, corr=0.3, seed=5)
assert len(jax.devices()) == 8

# writer: a 2x1 mesh runs 6 iterations and checkpoints every 3rd
cfg = ShardedPCDNConfig(P_local=16, c=1.0, tol_kkt=1e-3)
wb = ShardedBackend(X, y, make_mesh((2, 1), ("data", "model")), cfg)
ck = SolveCheckpointer("CKDIR", every=3)
st, res = engine_loop.run_outer_loop(
    wb.outer, wb.init_state(), 1.0, max_outer=6, tol_kkt=0.0,
    state_callback=ck.solve_callback(wb))
snap5 = ck.manager.load_raw(5)     # the host image of iteration 5

# reader 1: a DIFFERENT device count (4x2 mesh) restores the snapshot
rb = ShardedBackend(X, y, make_mesh((4, 2), ("data", "model")), cfg)
st4, meta = SolveCheckpointer("CKDIR", every=3).restore_solve(rb)
assert meta["outer_iter"] == 5
got = host_state(rb, st4)
for k in ("w", "z", "active", "key"):
    np.testing.assert_array_equal(snap5[k], got[k]), k
# ...and actually keeps solving from there (finite, global indices)
st4, r4 = engine_loop.run_outer_loop(
    rb.outer, st4, 1.0, max_outer=9, tol_kkt=0.0, start_iter=6)
assert np.isfinite(r4.objective) and r4.n_outer == 9
assert list(r4.history.outer_iter) == [6, 7, 8]

# reader 2: the LOCAL backend restores the same mesh-agnostic snapshot
prob = make_problem(X, y, c=1.0)
lb = LocalBackend(prob, PCDNConfig(P=16, max_outer=12, tol_kkt=1e-3))
stl, meta = SolveCheckpointer("CKDIR", every=3).restore_solve(lb)
assert meta["outer_iter"] == 5
np.testing.assert_array_equal(snap5["w"], np.asarray(stl.w))
print("ENGINE_OK")
"""


def test_resume_across_device_counts(tmp_path):
    """A checkpoint written on a 2-device mesh restores bit-exactly onto
    an 8-device (4x2) mesh AND onto the local backend, then keeps
    solving: the snapshot is unpadded host arrays, so the device count
    is not part of the format."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["REPRO_AUTOTUNE"] = "off"
    script = RESHARD_SCRIPT.replace("CKDIR", str(tmp_path / "ck"))
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ENGINE_OK" in out.stdout


# -- CLI kill-resume ----------------------------------------------------------

def _cli(args, env=None, **kw):
    e = dict(os.environ)
    e["REPRO_AUTOTUNE"] = "off"
    if env:
        e.update(env)
    return subprocess.run([sys.executable, "-m"] + args,
                          capture_output=True, text=True, env=e,
                          timeout=600, **kw)


def test_cli_sigkill_path_sweep_resumes_to_same_artifact(tmp_path):
    """THE acceptance scenario end-to-end through the real CLI: a path
    sweep SIGKILL'd mid-run (REPRO_FAULT_PLAN, no test-only flags)
    resumed with --resume produces the identical report — same best-c
    pick, objectives matching the uninterrupted run exactly."""
    base = ["repro.launch.path", "--dataset", "a9a", "--points", "3",
            "--P", "64", "--max-outer", "15", "--tol", "1e-3"]
    ref = _cli(base + ["--out", str(tmp_path / "ref.json")])
    assert ref.returncode == 0, ref.stderr[-4000:]
    killed = _cli(base + ["--ckpt-dir", str(tmp_path / "ck")],
                  env={"REPRO_FAULT_PLAN":
                       '{"crash_at_point": 1, "crash_kind": "sigkill"}'})
    assert killed.returncode == -9          # SIGKILL, not a clean exit
    assert (tmp_path / "ck").is_dir()
    resumed = _cli(base + ["--ckpt-dir", str(tmp_path / "ck"), "--resume",
                           "--out", str(tmp_path / "res.json")])
    assert resumed.returncode == 0, resumed.stderr[-4000:]
    assert "resuming path sweep at point 2/3" in resumed.stdout
    a = json.load(open(tmp_path / "ref.json"))
    b = json.load(open(tmp_path / "res.json"))
    assert a["best_index"] == b["best_index"]
    for pa, pb in zip(a["points"], b["points"]):
        rel = abs(pa["objective"] - pb["objective"]) / abs(pa["objective"])
        assert rel <= 1e-6
        assert pa["nnz"] == pb["nnz"]


def test_cli_solve_resume_continues(tmp_path):
    out1 = _cli(["repro.launch.solve", "--dataset", "a9a", "--P", "64",
                 "--max-outer", "8", "--tol", "1e-6",
                 "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "3"])
    assert out1.returncode == 0, out1.stderr[-4000:]
    out2 = _cli(["repro.launch.solve", "--dataset", "a9a", "--P", "64",
                 "--max-outer", "16", "--tol", "1e-6",
                 "--ckpt-dir", str(tmp_path / "ck"), "--resume"])
    assert out2.returncode == 0, out2.stderr[-4000:]
    assert "resuming solve at outer iteration 6" in out2.stdout
    assert "resumed_from=5" in out2.stdout


def test_cli_flag_validation(tmp_path):
    bad = _cli(["repro.launch.solve", "--dataset", "a9a",
                "--solver", "scdn", "--ckpt-dir", str(tmp_path / "x")])
    assert bad.returncode != 0
    assert "--solver pcdn or cdn" in bad.stderr
    bad2 = _cli(["repro.launch.path", "--dataset", "a9a",
                 "--mode", "batch", "--ckpt-dir", str(tmp_path / "y")])
    assert bad2.returncode != 0
    assert "--mode sweep" in bad2.stderr
    bad3 = _cli(["repro.launch.solve", "--dataset", "a9a", "--resume"])
    assert bad3.returncode != 0
    assert "--ckpt-dir" in bad3.stderr
