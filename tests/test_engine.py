"""Unified engine (DESIGN.md section 9): backend contract + local-vs-
sharded equivalence.

Multi-device coverage runs in a subprocess with 8 forced host devices
(same isolation rule as test_sharded_pcdn.py); the single-process tests
exercise the engine through a 1x1-mesh ShardedBackend, which needs no
device-count flag.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import PCDNConfig, make_problem, solve
from repro.data import make_classification
from repro.engine import (LocalBackend, ShardedBackend, ShardedPCDNConfig,
                          loop as engine_loop)
from repro.launch.mesh import make_host_mesh, make_mesh


@pytest.fixture(scope="module")
def data():
    return make_classification(300, 128, sparsity=0.8, corr=0.3, seed=2)


def test_engine_solve_matches_pcdn_solve(data):
    """pcdn.solve is a thin engine caller — same result through either."""
    X, y, _ = data
    prob = make_problem(X, y, c=1.0)
    # tol 1e-3, as the other tests of this fixture use: in f32 this
    # problem's KKT plateaus near 1.2e-4, so a 1e-4 stop is never reached
    # and neither route would report convergence
    cfg = PCDNConfig(P=32, max_outer=80, tol_kkt=1e-3)
    direct = solve(prob, cfg)
    via_engine = engine_loop.solve(
        LocalBackend(prob, cfg), prob.c, max_outer=cfg.max_outer,
        tol_kkt=cfg.tol_kkt)
    assert direct.converged and via_engine.converged
    assert via_engine.objective == pytest.approx(direct.objective)
    np.testing.assert_array_equal(np.asarray(direct.w),
                                  np.asarray(via_engine.w))


def test_make_host_mesh_raises_on_a_mesh_it_cannot_build():
    """A mesh larger than the visible devices is an error, never a
    quietly smaller mesh; the mesh it builds has Auto axes."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"needs {n + 1} devices"):
        make_host_mesh(n + 1, 1)
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(1, 0)
    mesh = make_host_mesh(1, 1)
    assert mesh.shape == {"data": 1, "model": 1}
    assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}


def test_local_backend_contract(data):
    X, y, _ = data
    prob = make_problem(X, y, c=1.0)
    b = LocalBackend(prob, PCDNConfig(P=32))
    assert b.n_features == 128 and b.n_samples == 300
    st = b.init_state()
    assert st.w.shape == (128,) and st.z.shape == (300,)
    assert bool(st.active.all())
    w0 = np.zeros(128, np.float32)
    w0[3] = 1.5
    st2 = b.init_state(w0)
    np.testing.assert_allclose(np.asarray(st2.z),
                               np.asarray(prob.margins(st2.w)), rtol=1e-6)
    assert b.c_max() == pytest.approx(prob.c_max())


def test_sharded_backend_1x1_mesh_matches_local(data):
    """The backend contract holds on a trivial mesh without any forced
    device count — same engine loop, same answer as the local backend."""
    X, y, _ = data
    mesh = make_mesh((1, 1), ("data", "model"))
    # tol 1e-3, like test_sharded_pcdn: at 1e-4 the sharded line search's
    # different f32 reduction order gives a long pre-existing KKT plateau
    cfg = ShardedPCDNConfig(P_local=32, c=1.0, tol_kkt=1e-3)
    backend = ShardedBackend(X, y, mesh, cfg)
    res = engine_loop.solve(backend, 1.0, max_outer=120, tol_kkt=1e-3)
    ref = solve(make_problem(X, y, c=1.0),
                PCDNConfig(P=32, max_outer=120, tol_kkt=1e-3))
    assert res.converged and ref.converged
    assert res.objective == pytest.approx(ref.objective, rel=1e-4)
    assert backend.c_max() == pytest.approx(
        make_problem(X, y, c=1.0).c_max(), rel=1e-5)
    assert backend.host_weights(res.w).shape == (128,)


def test_shrink_stop_consistency_guard(data):
    """A stop tolerance tighter than the backend's compiled un-shrink
    threshold would stall silently; the engine refuses it loudly."""
    X, y, _ = data
    prob = make_problem(X, y, c=1.0)
    backend = LocalBackend(prob, PCDNConfig(P=32, shrink=True,
                                            tol_kkt=1e-3))
    with pytest.raises(ValueError, match="un-shrink"):
        engine_loop.solve(backend, 1.0, max_outer=10, tol_kkt=1e-4)
    # equal or looser stop tolerances are fine
    engine_loop.check_shrink_stop_consistency(backend, 1e-3)
    engine_loop.check_shrink_stop_consistency(backend, 1e-2)


def test_lockstep_loop_freezes_on_convergence():
    """run_lockstep_loop freezes a converged problem's carry exactly."""
    import jax.numpy as jnp

    w = jnp.asarray([[1.0, 1.0], [8.0, 8.0]])
    (w_out,), f, kkt, nnz, n_outer, done = engine_loop.run_lockstep_loop(
        lambda w: (w * 0.5, jnp.abs(w[:, 0] * 0.5),
                   jnp.abs(w[:, 0] * 0.5), jnp.sum(w != 0, axis=1)),
        (w,), (), max_outer=10, tol_kkt=1.0, dtype=jnp.float32)
    # problem 0 converges after 1 iteration (0.5 <= 1), problem 1 needs 3
    assert int(n_outer[0]) == 1 and int(n_outer[1]) == 3
    assert bool(done.all())
    # problem 0's carry frozen at its first post-convergence value
    np.testing.assert_allclose(np.asarray(w_out[0]), [0.5, 0.5])
    np.testing.assert_allclose(np.asarray(w_out[1]), [1.0, 1.0])


SCRIPT = r"""
import dataclasses
import numpy as np
import jax
from repro.core import PCDNConfig, make_problem, solve
from repro.data import make_classification
from repro.engine import (LocalBackend, ShardedBackend, ShardedPCDNConfig,
                          loop as engine_loop)
from repro.path import PathConfig, run_path
from repro.launch.mesh import make_mesh

X, y, _ = make_classification(512, 256, sparsity=0.7, corr=0.4, seed=3)
mesh = make_mesh((2, 4), ("data", "model"))
tol = 1e-4

# 1) full solve trajectory WITH SHRINKING: local vs sharded to fp32
local = solve(make_problem(X, y, c=1.0),
              PCDNConfig(P=64, max_outer=150, tol_kkt=tol, shrink=True))
scfg = ShardedPCDNConfig(P_local=16, c=1.0, shrink=True, tol_kkt=tol)
sh = engine_loop.solve(ShardedBackend(X, y, mesh, scfg), 1.0,
                       max_outer=150, tol_kkt=tol)
assert local.converged and sh.converged
rel = abs(sh.objective - local.objective) / abs(local.objective)
assert rel < 1e-4, (sh.objective, local.objective)
assert float(sh.history.kkt[-1]) <= tol      # full-set stop on the mesh
assert int(sh.history.n_active.min()) < 256  # shrinking engaged

# ... and on the padded-CSC sharded layout
shs = engine_loop.solve(
    ShardedBackend(X, y, mesh, scfg, layout="padded_csc"), 1.0,
    max_outer=150, tol_kkt=tol)
assert shs.converged
assert abs(shs.objective - local.objective) / abs(local.objective) < 1e-4

# 2) warm-started 2-point path sweep: per-point agreement to fp32
pcfg = PathConfig(solver=PCDNConfig(P=64, max_outer=200, tol_kkt=tol,
                                    shrink=True), n_points=2, span=8.0)
r_local = run_path(make_problem(X, y, c=1.0), pcfg)
r_shard = run_path(None, pcfg,
                   backend=ShardedBackend(X, y, mesh, scfg))
assert all(p.converged for p in r_local.points)
assert all(p.converged for p in r_shard.points)
for pl, ps in zip(r_local.points, r_shard.points):
    assert abs(ps.c - pl.c) / pl.c < 1e-4            # same analytic grid
    assert abs(ps.objective - pl.objective) / abs(pl.objective) < 1e-4, \
        (ps.objective, pl.objective)
    assert ps.kkt <= tol

# 3) Pallas-kernel routing through the sharded bundle step: same answer
kcfg = dataclasses.replace(scfg, shrink=False, use_kernels=True)
ncfg = dataclasses.replace(scfg, shrink=False, use_kernels=False)
rk = engine_loop.solve(ShardedBackend(X, y, mesh, kcfg), 1.0,
                       max_outer=60, tol_kkt=1e-3)
rn = engine_loop.solve(ShardedBackend(X, y, mesh, ncfg), 1.0,
                       max_outer=60, tol_kkt=1e-3)
assert rk.converged and rn.converged
assert abs(rk.objective - rn.objective) / abs(rn.objective) < 1e-5

# 4) the path CLI's sharded mode end-to-end (acceptance criterion)
from repro.launch import path as launch_path
payload = launch_path.main([
    "--backend", "sharded", "--data-parallel", "2", "--model-parallel",
    "4", "--dataset", "a9a", "--scale", "0.05", "--points", "3",
    "--span", "10", "--P", "16", "--max-outer", "60", "--shrink"])
assert payload["backend"] == "sharded" and len(payload["points"]) == 3
print("ENGINE_OK")
"""


@pytest.mark.slow
def test_engine_local_vs_sharded_multi_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "ENGINE_OK" in out.stdout, out.stdout + out.stderr
