"""Labels in the padded-CSC column layout (DESIGN.md section 7.1).

`make_problem` gives a padded-CSC problem its labels at each column's
rows (`col_y`), so a bundle's direction gathers the margins once per
slab entry and forms u and v there. A problem without `col_y` gathers
its labels at each slab entry as well. Both compute the same numbers,
and the compiled step with `col_y` holds one element gather fewer.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PCDNConfig, make_problem
from repro.core.design_matrix import DenseDesign
from repro.core.pcdn import make_bundle_step, make_path_outer
from repro.core.problem import L1Problem
from repro.data import make_classification

S, N, P = 120, 70, 16


def _data(loss="logistic"):
    X, y, _ = make_classification(S, N, sparsity=0.93, corr=0.3, seed=3)
    X[:, 5] = 0.0                       # an empty column: all padding
    if loss == "squared":
        y = 0.7 * y + 0.1               # real-valued targets
    return X, y


def _pair(loss="logistic", dtype=jnp.float32):
    X, y = _data(loss)
    with_col_y = make_problem(X, y, c=1.0, loss=loss, layout="padded_csc",
                              dtype=dtype)
    return with_col_y, dataclasses.replace(with_col_y, col_y=None)


def _bits(a):
    return np.asarray(a).view(np.uint32)


def test_make_problem_builds_col_y_for_padded_csc_only():
    X, y = _data()
    ps = make_problem(X, y, c=1.0, layout="padded_csc")
    rows = np.asarray(ps.design.col_rows)
    want = np.where(rows < S, np.asarray(ps.y)[np.minimum(rows, S - 1)],
                    1.0)
    assert ps.col_y.shape == (N, ps.design.k_max)
    assert ps.col_y.dtype == ps.y.dtype
    np.testing.assert_array_equal(np.asarray(ps.col_y), want)
    assert make_problem(X, y, c=1.0).col_y is None
    direct = L1Problem(design=DenseDesign(X=jnp.asarray(X)),
                       y=jnp.asarray(y), c=1.0)
    assert direct.col_y is None


def test_with_c_keeps_col_y_and_with_labels_drops_it():
    ps, _ = _pair()
    assert ps.with_c(2.0).col_y is ps.col_y
    assert ps.with_labels(-ps.y).col_y is None
    leaves, tree = jax.tree_util.tree_flatten(ps)
    again = jax.tree_util.tree_unflatten(tree, leaves)
    assert again.col_y is ps.col_y


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("loss", ["logistic", "squared_hinge", "squared"])
def test_one_gather_form_matches_two_gather_form(loss, dtype):
    """Bitwise op by op: g and h of a ragged bundle (3 sentinel slots,
    columns padded to k_max, one empty column), and w and z after one
    full-scope outer iteration whose last bundle is ragged (32 !| 70).
    Compiled, each fusion sums in an order of XLA's choosing, so the
    compiled forms agree to float32 rounding."""
    new, old = _pair(loss, dtype)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(N).astype(np.float32))
    w = w * (rng.random(N) < 0.5)
    z = new.margins(w)
    idx = jnp.concatenate([
        jnp.asarray(rng.permutation(N)[:P - 3], jnp.int32),
        jnp.full((3,), N, jnp.int32)])
    w_B = jnp.where(idx < N, w[jnp.minimum(idx, N - 1)], 0.0)
    cfg = PCDNConfig(P=32, ls_scope="full")
    args = (w, z, jax.random.PRNGKey(1), jnp.ones((N,), bool),
            jnp.bool_(True), jnp.float32(1.0))

    def run(prob):
        g, h = prob.bundle_grad_hess(z, prob.gather_slab(idx), w_B)
        w1, z1 = make_path_outer(prob, cfg)(*args)[:2]
        return g, h, w1, z1

    with jax.disable_jit():
        eager_new, eager_old = run(new), run(old)
    for a, b in zip(eager_new, eager_old):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    assert float(jnp.max(jnp.abs(eager_new[2] - w))) > 0   # a real step

    for a, b in zip(run(new), run(old)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


_DEF = re.compile(r"%?([\w.-]+) = (\w+\[[\d,]*\])")
_GATHER = re.compile(r"%?([\w.-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"gather\((?:\w+\[[\d,]*\]\S* )?%?([\w.-]+)")


def _element_gathers(text: str, s: int, entries: int) -> int:
    """Gathers in compiled HLO text from an f32[s] operand into
    `entries` f32 elements: a per-entry gather of a per-sample vector."""
    shapes = dict(_DEF.findall(text))
    n = 0
    for _, dtype, dims, operand in _GATHER.findall(text):
        size = int(np.prod([int(d) for d in dims.split(",") if d]))
        n += (dtype == "f32" and size == entries
              and shapes.get(operand) == f"f32[{s}]")
    return n


@pytest.mark.parametrize("layout,col_y,want", [
    ("padded_csc", True, 1), ("padded_csc", False, 2), ("dense", False, 0)],
    ids=["padded_csc-col_y", "padded_csc-no_col_y", "dense"])
def test_full_scope_step_gathers_margins_once(layout, col_y, want):
    X, y = _data()
    prob = make_problem(X, y, c=1.0, layout=layout)
    assert (prob.col_y is not None) == (layout == "padded_csc")
    if not col_y:
        prob = dataclasses.replace(prob, col_y=None)
    cfg = PCDNConfig(P=P, ls_scope="full")
    step = jax.jit(lambda prob, w, z, idx:
                   make_bundle_step(prob, cfg)((w, z), idx))
    text = step.lower(prob, jnp.zeros((N,)), jnp.zeros((S,)),
                      jnp.arange(P, dtype=jnp.int32)).compile().as_text()
    k_max = make_problem(X, y, c=1.0, layout="padded_csc").design.k_max
    assert _element_gathers(text, S, P * k_max) == want


@pytest.mark.parametrize("loss,dtype,want", [
    ("logistic", jnp.float32, jnp.float32),
    ("logistic", jnp.bfloat16, jnp.bfloat16),
    ("squared", jnp.bfloat16, jnp.float32)],
    ids=["f32", "bf16-pm1", "bf16-real"])
def test_col_y_is_stored_like_col_vals_where_exact(loss, dtype, want):
    """+-1 labels are exact in bf16, so under bf16 storage `col_y` takes
    no more memory than `col_vals`; real-valued targets that bf16 would
    round stay in the solver's f32, and the direction reads them as f32."""
    X, y = _data(loss)
    ps = make_problem(X, y, c=1.0, loss=loss, layout="padded_csc",
                      dtype=dtype)
    assert ps.col_y.dtype == want
    assert ps.y.dtype == jnp.float32
    rows = np.asarray(ps.design.col_rows)
    yf = np.asarray(ps.y)
    np.testing.assert_array_equal(
        np.asarray(ps.col_y.astype(jnp.float32)),
        np.where(rows < S, yf[np.minimum(rows, S - 1)], 1.0))
    idx = jnp.asarray([0, 5, N - 1, N], jnp.int32)   # empty col, sentinel
    labels = ps.gather_slab(idx).labels
    np.testing.assert_array_equal(np.asarray(labels[3]), 1.0)
