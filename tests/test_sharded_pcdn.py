"""Distributed PCDN == single-device PCDN (multi-device via subprocess).

These tests need >1 XLA device; jax fixes the device count at first init,
so they spawn a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 (keeping every other test on 1 device, as required by the
assignment's dry-run isolation rule).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import jax, numpy as np
from repro.core.sharded import ShardedPCDNConfig, solve_sharded
from repro.core import make_problem, PCDNConfig, solve
from repro.data import make_classification
from repro.launch.mesh import make_mesh

X, y, _ = make_classification(512, 256, sparsity=0.7, corr=0.4, seed=3)

mesh = make_mesh((2, 4), ("data", "model"))
cfg = ShardedPCDNConfig(P_local=16, c=1.0, data_axes=("data",))
w, f, conv, k, hist = solve_sharded(X, y, mesh, cfg, max_outer=40)
assert conv, "sharded PCDN must converge"
assert all(b <= a + 1e-4 for a, b in zip(hist["objective"],
                                         hist["objective"][1:])), "monotone"

prob = make_problem(X, y, c=1.0)
res = solve(prob, PCDNConfig(P=64, max_outer=40))
rel = abs(f - res.objective) / abs(res.objective)
assert rel < 1e-4, (f, res.objective)

# padded-CSC sparse layout: identical collective schedule, same answer
ws, fs, convs, ks, _ = solve_sharded(X, y, mesh, cfg, max_outer=40,
                                     layout="padded_csc")
assert convs, "sparse sharded PCDN must converge"
assert abs(fs - res.objective) / abs(res.objective) < 1e-4, (fs,
                                                            res.objective)

# multi-pod (3-axis) mesh
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg3 = ShardedPCDNConfig(P_local=32, c=1.0, data_axes=("pod", "data"))
w3, f3, conv3, k3, _ = solve_sharded(X, y, mesh3, cfg3, max_outer=40)
assert conv3
assert abs(f3 - res.objective) / abs(res.objective) < 1e-4

# multi-pod sparse
w4, f4, conv4, k4, _ = solve_sharded(X, y, mesh3, cfg3, max_outer=40,
                                     layout="padded_csc")
assert conv4
assert abs(f4 - res.objective) / abs(res.objective) < 1e-4
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_pcdn_matches_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "SHARDED_OK" in out.stdout, out.stdout + out.stderr


MOE_SCRIPT = r"""
import jax
from repro.configs import get_config
from repro.models.transformer import Model
from repro.launch.specs import train_batch_specs
from repro.launch.mesh import make_mesh

mesh1 = make_mesh((1, 1), ("data", "model"))
for arch, shape in [("deepseek-moe-16b", (2, 4)), ("grok-1-314b", (1, 8)),
                    ("grok-1-314b", (2, 2))]:
    cfg = get_config(arch, reduced=True)
    m1 = Model(cfg, mesh1)
    params = m1.init_params(jax.random.PRNGKey(0))
    batch = train_batch_specs(cfg, batch=4, seq=16, concrete=True, seed=2)
    ref = float(m1.loss_fn(params, batch))
    meshN = make_mesh(shape, ("data", "model"))
    mN = Model(cfg, meshN)
    pN = mN.shard_params(params)
    lossN = float(jax.jit(mN.loss_fn)(pN, batch))
    assert abs(ref - lossN) < 1e-4, (arch, shape, ref, lossN)
print("MOE_OK")
"""


@pytest.mark.slow
def test_sharded_moe_matches_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    out = subprocess.run([sys.executable, "-c", MOE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "MOE_OK" in out.stdout, out.stdout + out.stderr


DENSE_SCRIPT = r"""
import jax
from repro.configs import get_config
from repro.models.transformer import Model
from repro.launch.specs import train_batch_specs
from repro.launch.mesh import make_mesh

mesh1 = make_mesh((1, 1), ("data", "model"))
for arch in ["yi-6b", "recurrentgemma-2b", "falcon-mamba-7b",
             "whisper-small"]:
    cfg = get_config(arch, reduced=True)
    m1 = Model(cfg, mesh1)
    params = m1.init_params(jax.random.PRNGKey(0))
    batch = train_batch_specs(cfg, batch=4, seq=16, concrete=True, seed=2)
    ref = float(m1.loss_fn(params, batch))
    meshN = make_mesh((2, 4), ("data", "model"))
    mN = Model(cfg, meshN)
    pN = mN.shard_params(params)
    lossN = float(jax.jit(mN.loss_fn)(pN, batch))
    assert abs(ref - lossN) < 1e-4, (arch, ref, lossN)
print("DENSE_OK")
"""


@pytest.mark.slow
def test_sharded_dense_families_match_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    out = subprocess.run([sys.executable, "-c", DENSE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "DENSE_OK" in out.stdout, out.stdout + out.stderr
