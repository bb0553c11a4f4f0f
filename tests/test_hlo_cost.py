"""Calibration tests for the trip-count-aware HLO cost model — guards the
empirical fact that XLA cost_analysis counts while bodies once."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import hlo_cost
from repro.utils.hlo import Roofline


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_scan_trip_count_multiplied():
    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return h

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    compiled = _compile(f, x, w)
    # XLA's own analysis counts the loop body once (the bug we fix)
    assert compiled.cost_analysis()["flops"] < 2 * 2 * 128 * 256 * 256
    mc = hlo_cost.analyze(compiled.as_text())
    assert abs(mc.flops - 8 * 2 * 128 * 256 * 256) / mc.flops < 1e-6
    assert 8 in mc.trip_counts.values()


def test_nested_scan():
    def g(x, w):
        def outer(h, _):
            def inner(h2, _):
                return jnp.tanh(h2 @ w), None
            h2, _ = jax.lax.scan(inner, h, None, length=3)
            return h2, None
        h, _ = jax.lax.scan(outer, x, None, length=4)
        return h

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    mc = hlo_cost.analyze(_compile(g, x, w).as_text())
    assert abs(mc.flops - 12 * 2 * 64 * 128 * 128) / mc.flops < 1e-6


def test_grad_flops_counted():
    def h(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out.sum()

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    mc = hlo_cost.analyze(_compile(jax.grad(h, argnums=1), x, w).as_text())
    # fwd 5 matmuls + bwd 2 matmuls per step
    expected = (5 + 10) * 2 * 128 * 256 * 256
    assert abs(mc.flops - expected) / expected < 1e-6


def test_unrolled_matches_scan():
    def f_scan(x, w):
        def body(h, _):
            return h @ w, None
        h, _ = jax.lax.scan(body, x, None, length=6)
        return h

    def f_unroll(x, w):
        for _ in range(6):
            x = x @ w
        return x

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    m1 = hlo_cost.analyze(_compile(f_scan, x, w).as_text())
    m2 = hlo_cost.analyze(_compile(f_unroll, x, w).as_text())
    assert abs(m1.flops - m2.flops) / m2.flops < 1e-6


def test_roofline_terms():
    r = Roofline(flops=197e12 * 256, hbm_bytes=819e9 * 256,
                 coll_bytes=50e9 * 256 * 2, n_chips=256,
                 model_flops=197e12 * 128)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert abs(r.t_collective - 2.0) < 1e-9
    assert r.bottleneck == "collective"
    assert abs(r.useful_flops_ratio - 0.5) < 1e-9


def test_dot_attribution_sums_to_total():
    def f(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=4)
        return h

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    mc = hlo_cost.analyze(_compile(f, x, w).as_text())
    assert abs(sum(mc.dot_sources.values()) - mc.flops) / mc.flops < 1e-6
