"""Property tests for core/losses.py: the hand-written margin derivative
factors `dz` / `d2z` must match jax autodiff of `value`, and the
HESSIAN_FLOOR edge must keep Newton denominators positive where the true
curvature vanishes (paper footnote 1 / Lemma 1(b)).

Autodiff targets the PLAIN textbook forms (paper Eq. 2/3), not the
log1p/maximum-stabilized implementations: grad-of-stable-form has
spurious subgradient artifacts exactly at margin 0 (jnp.maximum /
jnp.abs tie-breaking) where the true losses are perfectly smooth.
Runs under `jax.enable_x64` (scoped, not global): in f32
the two only agree to ~eps at saturated margins, forcing vacuous
tolerances.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.losses import HESSIAN_FLOOR, get_loss
from repro.core.problem import make_problem

# margins away from exp overflow; labels are the +-1 contract
_Z = st.floats(-30.0, 30.0)
_Y = st.sampled_from([-1.0, 1.0])


def _plain_value(name):
    """The un-stabilized per-sample losses (paper Eq. 2/3 + Lasso)."""
    return {
        "logistic": lambda z, y: jnp.log1p(jnp.exp(-y * z)),
        "squared_hinge": lambda z, y: jnp.maximum(0.0, 1.0 - y * z) ** 2,
        "squared": lambda z, y: 0.5 * (z - y) ** 2,
    }[name]


def _check_scalar(name, z, y, rel=1e-5, abs_=1e-12):
    """dz/d2z at a scalar margin vs jax.grad of the plain form, in f64."""
    with jax.enable_x64(True):
        loss = get_loss(name)
        plain = _plain_value(name)
        f = lambda zz: plain(zz, jnp.float64(y))
        g = float(jax.grad(f)(jnp.float64(z)))
        h = float(jax.grad(jax.grad(f))(jnp.float64(z)))
        # the stable implementation must also VALUE-match the plain form
        assert float(loss.value(jnp.float64(z), jnp.float64(y))) == \
            pytest.approx(float(f(jnp.float64(z))), rel=rel, abs=abs_)
        assert float(loss.dz(jnp.float64(z), jnp.float64(y))) == \
            pytest.approx(g, rel=rel, abs=abs_)
        assert float(loss.d2z(jnp.float64(z), jnp.float64(y))) == \
            pytest.approx(h, rel=rel, abs=abs_)


@settings(max_examples=60, deadline=None)
@given(_Z, _Y)
def test_logistic_dz_d2z_match_autodiff(z, y):
    _check_scalar("logistic", z, y)


@settings(max_examples=60, deadline=None)
@given(_Z, _Y)
def test_squared_hinge_dz_d2z_match_autodiff(z, y):
    """d2z is the GENERALIZED second derivative: it equals the autodiff
    Hessian everywhere except exactly at the kink y*z == 1, where the
    classical one does not exist — nudge off it (measure-zero set)."""
    if abs(1.0 - y * z) < 1e-6:
        z += 1e-3
    _check_scalar("squared_hinge", z, y)


@settings(max_examples=40, deadline=None)
@given(st.floats(-5.0, 5.0), st.floats(-5.0, 5.0))
def test_squared_loss_matches_autodiff(z, y):
    _check_scalar("squared", z, y)


@settings(max_examples=40, deadline=None)
@given(st.lists(_Z, min_size=2, max_size=16),
       st.lists(_Y, min_size=2, max_size=16),
       st.sampled_from(["logistic", "squared_hinge"]))
def test_vector_forms_match_hessian_diagonal(zs, ys, name):
    """The (s,)-vector dz/d2z are grad and the DIAGONAL of jax.hessian of
    the summed loss — the exact contract problem.grad/hess_factor uses;
    the off-diagonal curvature is zero by per-sample separability."""
    k = min(len(zs), len(ys))
    with jax.enable_x64(True):
        z = jnp.asarray(zs[:k], jnp.float64)
        y = jnp.asarray(ys[:k], jnp.float64)
        if name == "squared_hinge":
            z = jnp.where(jnp.abs(1.0 - y * z) < 1e-6, z + 1e-3, z)
        loss = get_loss(name)
        plain = _plain_value(name)
        total = lambda zz: jnp.sum(plain(zz, y))
        g = np.asarray(jax.grad(total)(z))
        H = np.asarray(jax.hessian(total)(z))
        np.testing.assert_allclose(np.asarray(loss.dz(z, y)), g,
                                   rtol=1e-5, atol=1e-12)
        np.testing.assert_allclose(H,
                                   np.diag(np.asarray(loss.d2z(z, y))),
                                   rtol=1e-5, atol=1e-12)


def test_hessian_floor_edge():
    """Where the true curvature is exactly zero (L2-SVM with every margin
    satisfied), bundle_grad_hess must return h == HESSIAN_FLOOR > 0 so
    the Eq. 5 Newton step stays finite."""
    X = np.eye(4, dtype=np.float32)
    y = np.ones((4,), np.float32)
    prob = make_problem(X, y, c=1.0, loss="squared_hinge")
    w = jnp.full((4,), 5.0)            # margins z = 5 > 1: flat region
    z = prob.margins(w)
    assert float(jnp.max(prob.hess_factor(z))) == 0.0   # raw curvature 0
    slab = prob.design.gather_slab(jnp.arange(4, dtype=jnp.int32))
    g, h = prob.bundle_grad_hess(z, slab, w)
    np.testing.assert_allclose(np.asarray(h), HESSIAN_FLOOR, rtol=1e-6)
    assert bool(jnp.all(jnp.isfinite(-g / h)))


def test_hessian_floor_applies_under_x64_sweep():
    """Deterministic sweep version of the @given checks so the floor and
    derivative contracts stay covered even without hypothesis installed
    (the conftest stub skips @given tests in that case)."""
    for name in ("logistic", "squared_hinge", "squared"):
        for z in (-30.0, -2.0, -1e-3, 0.0, 0.5, 1.0 + 1e-3, 7.0, 30.0):
            for y in (-1.0, 1.0):
                if name == "squared_hinge" and abs(1.0 - y * z) < 1e-6:
                    continue
                _check_scalar(name, z, y)
