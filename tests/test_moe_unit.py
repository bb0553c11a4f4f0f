"""MoE layer unit tests: routing, capacity, dispatch correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe as moe_mod
from repro.models import sharding as sh
from repro.models.transformer import Model
from repro.launch.mesh import make_mesh


def setup(arch="deepseek-moe-16b"):
    cfg = get_config(arch, reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg, mesh)
    key = jax.random.PRNGKey(0)
    params = sh.init_params(key, moe_mod.moe_decls(cfg))
    return cfg, mesh, model, params


def manual_moe(cfg, params, x):
    """Dense reference: run every expert on every token, weight by gates."""
    m = cfg.moe
    T = x.shape[0] * x.shape[1]
    xt = x.reshape(T, -1)
    logits = xt.astype(jnp.float32) @ params["router"]
    gates_all = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(gates_all, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    out = jnp.zeros_like(xt, dtype=jnp.float32)
    for e in range(m.n_experts):
        h = jax.nn.silu(xt @ params["w_gate"][e]) * (xt @ params["w_up"][e])
        oe = (h @ params["w_down"][e]).astype(jnp.float32)
        wsel = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)
        out = out + oe * wsel[:, None]
    if m.n_shared:
        sp = params["shared"]
        h = jax.nn.silu(xt @ sp["w_gate"]) * (xt @ sp["w_up"])
        out = out + (h @ sp["w_down"]).astype(jnp.float32)
    return out.reshape(x.shape).astype(x.dtype)


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "grok-1-314b"])
def test_moe_matches_dense_reference(arch):
    """With ample capacity the sort-based dispatch == dense compute."""
    cfg, mesh, model, params = setup(arch)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    got = moe_mod.apply_moe(cfg, params, x, mesh, model.rules)
    want = manual_moe(cfg, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_tokens():
    """With capacity_factor << 1 outputs shrink toward zero (dropped)."""
    cfg, mesh, model, params = setup()
    import dataclasses
    tight = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=0.05))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                          jnp.float32)
    full = moe_mod.apply_moe(cfg, params, x, mesh, model.rules)
    dropped = moe_mod.apply_moe(tight, params, x, mesh, model.rules)
    # shared experts still contribute; routed part must differ
    assert float(jnp.mean(jnp.abs(full - dropped))) > 1e-5


def test_moe_deterministic():
    cfg, mesh, model, params = setup()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model),
                          jnp.float32)
    a = moe_mod.apply_moe(cfg, params, x, mesh, model.rules)
    b = moe_mod.apply_moe(cfg, params, x, mesh, model.rules)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_grad_flows_to_router():
    cfg, mesh, model, params = setup()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.d_model),
                          jnp.float32)

    def loss(p):
        return jnp.sum(moe_mod.apply_moe(cfg, p, x, mesh, model.rules) ** 2)

    g = jax.grad(loss)(params)
    assert float(jnp.max(jnp.abs(g["router"]))) > 0
    assert float(jnp.max(jnp.abs(g["w_gate"]))) > 0
