"""Dense vs padded-CSC DesignMatrix backend equivalence (DESIGN.md §7).

Property-style over a grid of shapes/losses/sparsities: every problem
oracle (margins, bundle_grad_hess, full_grad, kkt_violation,
column_norms_sq) and full solver trajectories must agree between the two
backends to fp32 tolerance, including the ragged last bundle, empty
columns, and the Pallas kernel path. Plus the libsvm layout round-trips.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (DenseDesign, PCDNConfig, PaddedCSCDesign,
                        cdn_config, make_problem, scdn, solve, tron)
from repro.core.design_matrix import SparseSlab, as_design, padded_csc_arrays
from repro.data import make_classification
from repro.data.libsvm import (CSRMatrix, csr_to_padded_csc, load_libsvm,
                               save_libsvm)


def _sparse_X(s, n, sparsity=0.95, seed=0, empty_cols=()):
    X, y, _ = make_classification(s, n, sparsity=sparsity, corr=0.3,
                                  seed=seed)
    for j in empty_cols:
        X[:, j] = 0.0
    return X, y


def _pair(X, y, c=1.0, loss="logistic", l2=0.0):
    pd = make_problem(X, y, c=c, loss=loss, elastic_net_l2=l2)
    ps = make_problem(X, y, c=c, loss=loss, elastic_net_l2=l2,
                      layout="padded_csc")
    return pd, ps


CASES = [
    # (s, n, sparsity, loss, l2, empty_cols)
    (64, 40, 0.9, "logistic", 0.0, ()),
    (128, 96, 0.99, "logistic", 0.0, (0, 17, 95)),
    (96, 50, 0.95, "squared_hinge", 0.0, ()),
    (80, 33, 0.9, "logistic", 0.3, (32,)),   # l2 + last column empty
]


@pytest.mark.parametrize("s,n,sparsity,loss,l2,empty", CASES)
def test_oracles_agree(s, n, sparsity, loss, l2, empty):
    X, y = _sparse_X(s, n, sparsity, seed=s + n, empty_cols=empty)
    pd, ps = _pair(X, y, loss=loss, l2=l2)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    w = w * (rng.random(n) < 0.5)  # mixed signs + exact zeros for KKT

    zd, zs = pd.margins(w), ps.margins(w)
    np.testing.assert_allclose(zd, zs, atol=1e-5)
    np.testing.assert_allclose(pd.full_grad(zd, w), ps.full_grad(zs, w),
                               atol=1e-4)
    np.testing.assert_allclose(pd.kkt_violation(w), ps.kkt_violation(w),
                               atol=1e-4)
    np.testing.assert_allclose(pd.column_norms_sq(), ps.column_norms_sq(),
                               atol=1e-4)


@pytest.mark.parametrize("s,n,sparsity,loss,l2,empty", CASES)
def test_bundle_grad_hess_agree(s, n, sparsity, loss, l2, empty):
    """Includes the ragged bundle: P does not divide n, sentinel idx == n."""
    X, y = _sparse_X(s, n, sparsity, seed=7, empty_cols=empty)
    pd, ps = _pair(X, y, loss=loss, l2=l2)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    z = pd.margins(w)
    P = 16
    idx = jnp.concatenate([
        jnp.asarray(rng.permutation(n)[:P - 3], jnp.int32),
        jnp.full((3,), n, jnp.int32)])          # ragged: 3 sentinel slots
    w_B = jnp.where(idx < n, w[jnp.minimum(idx, n - 1)], 0.0)
    gd, hd = pd.bundle_grad_hess(z, pd.design.gather_slab(idx), w_B)
    gs, hs = ps.bundle_grad_hess(z, ps.design.gather_slab(idx), w_B)
    np.testing.assert_allclose(gd, gs, atol=1e-4)
    np.testing.assert_allclose(hd, hs, atol=1e-4)
    # sentinel slots contribute nothing on either backend
    np.testing.assert_allclose(gd[-3:], l2 * w_B[-3:], atol=1e-6)


def test_pcdn_trajectories_identical():
    """Same seed => same iterate trajectory to fp tolerance, ragged P."""
    X, y = _sparse_X(96, 70, 0.95, seed=3, empty_cols=(5,))
    pd, ps = _pair(X, y)
    for ls in ("batched", "backtracking"):
        cfg = PCDNConfig(P=32, max_outer=15, seed=4, ls_kind=ls)  # 32 !| 70
        rd, rs = solve(pd, cfg), solve(ps, cfg)
        np.testing.assert_allclose(rd.history.objective,
                                   rs.history.objective, rtol=1e-4)
        np.testing.assert_allclose(rd.w, rs.w, atol=1e-4)


def test_pcdn_kernel_path_matches_jnp_path_sparse():
    X, y = _sparse_X(128, 64, 0.95, seed=5)
    _, ps = _pair(X, y)
    r_jnp = solve(ps, PCDNConfig(P=32, max_outer=8, seed=0,
                                 use_kernels=False))
    r_ker = solve(ps, PCDNConfig(P=32, max_outer=8, seed=0,
                                 use_kernels=True))
    np.testing.assert_allclose(r_jnp.history.objective,
                               r_ker.history.objective, rtol=1e-4)


def test_cdn_scdn_tron_run_on_sparse_backend():
    X, y = _sparse_X(80, 40, 0.9, seed=6)
    pd, ps = _pair(X, y)
    rd = solve(pd, cdn_config(max_outer=5, seed=1))
    rs = solve(ps, cdn_config(max_outer=5, seed=1))
    np.testing.assert_allclose(rd.history.objective, rs.history.objective,
                               rtol=1e-4)
    sd = scdn.solve(pd, scdn.SCDNConfig(P_bar=4, max_rounds=5, seed=1))
    ss = scdn.solve(ps, scdn.SCDNConfig(P_bar=4, max_rounds=5, seed=1))
    np.testing.assert_allclose(sd.history["objective"],
                               ss.history["objective"], rtol=1e-4)
    td = tron.solve(pd, tron.TRONConfig(max_outer=10))
    t_s = tron.solve(ps, tron.TRONConfig(max_outer=10))
    np.testing.assert_allclose(td.objective, t_s.objective, rtol=1e-4)


def test_sparse_backend_never_exposes_dense_X():
    X, y = _sparse_X(32, 16, 0.9, seed=8)
    _, ps = _pair(X, y)
    assert isinstance(ps.design, PaddedCSCDesign)
    with pytest.raises(TypeError):
        _ = ps.X


def test_empty_column_and_all_zero_row():
    X, y = _sparse_X(40, 20, 0.9, seed=9, empty_cols=(0, 19))
    X[7, :] = 0.0
    pd, ps = _pair(X, y)
    res_d = solve(pd, PCDNConfig(P=8, max_outer=10, seed=0))
    res_s = solve(ps, PCDNConfig(P=8, max_outer=10, seed=0))
    np.testing.assert_allclose(res_d.history.objective,
                               res_s.history.objective, rtol=1e-4)
    # empty columns must stay at exactly 0 (they cannot reduce the loss)
    assert float(jnp.abs(res_s.w[0])) == 0.0
    assert float(jnp.abs(res_s.w[19])) == 0.0


# -- converters / data layer --------------------------------------------------

def _ragged_csr(seed=0):
    """Rows with wildly different nnz (incl. an empty row/column)."""
    rng = np.random.default_rng(seed)
    s, n = 23, 17
    X = np.zeros((s, n), np.float32)
    for i in range(s):
        k = rng.integers(0, n)          # 0..n-1 nnz in this row
        cols = rng.choice(n, size=k, replace=False)
        X[i, cols] = rng.standard_normal(k).astype(np.float32)
    X[:, 3] = 0.0
    X[11, :] = 0.0
    rows, cols = np.nonzero(X)
    vals = X[rows, cols]
    indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(rows, minlength=s))]).astype(np.int64)
    return CSRMatrix(vals, cols.astype(np.int32), indptr, (s, n)), X


def test_csr_to_dense_vectorized_round_trip():
    csr, X = _ragged_csr()
    np.testing.assert_array_equal(csr.to_dense(), X)


def test_csr_padded_csc_round_trip_ragged():
    csr, X = _ragged_csr(seed=3)
    pcsc = csr_to_padded_csc(csr)
    assert pcsc.k_max == csr.max_col_nnz()
    design = as_design(pcsc)
    np.testing.assert_allclose(np.asarray(design.to_dense()), X, atol=0)
    # direct from_csr agrees with the two-step conversion
    d2 = PaddedCSCDesign.from_csr(csr.data, csr.indices, csr.indptr,
                                  csr.shape)
    np.testing.assert_array_equal(np.asarray(d2.col_rows),
                                  np.asarray(design.col_rows))


def test_k_max_overflow_raises():
    csr, _ = _ragged_csr(seed=4)
    with pytest.raises(ValueError):
        padded_csc_arrays(csr.data, csr.indices, csr.indptr, csr.shape,
                          k_max=1)


def test_load_libsvm_padded_csc_layout(tmp_path):
    rng = np.random.default_rng(5)
    X = ((rng.random((30, 12)) < 0.3) *
         rng.standard_normal((30, 12))).astype(np.float32)
    y = np.where(rng.random(30) < 0.5, 1.0, -1.0).astype(np.float32)
    p = str(tmp_path / "t.svm")
    save_libsvm(p, X, y)
    pcsc, y2 = load_libsvm(p, n_features=12, layout="padded_csc")
    prob = make_problem(pcsc, y2, c=1.0)
    dense_prob = make_problem(*load_libsvm(p, n_features=12), c=1.0)
    np.testing.assert_allclose(prob.objective(jnp.ones(12)),
                               dense_prob.objective(jnp.ones(12)),
                               rtol=1e-5)


def test_dense_design_is_default_and_back_compat():
    X, y = _sparse_X(16, 8, 0.5, seed=10)
    prob = make_problem(X, y, c=1.0)
    assert isinstance(prob.design, DenseDesign)
    assert prob.X.shape == (16, 8)       # legacy dense accessor still works
    # legacy raw-slab call signature still accepted
    z = prob.margins(jnp.zeros(8))
    g, h = prob.bundle_grad_hess(z, prob.X, jnp.zeros(8))
    assert g.shape == (8,) and h.shape == (8,)


def test_gather_slab_types():
    X, y = _sparse_X(16, 8, 0.5, seed=11)
    _, ps = _pair(X, y)
    slab = ps.design.gather_slab(jnp.arange(4, dtype=jnp.int32))
    assert isinstance(slab, SparseSlab)
    assert slab.rows.shape == (4, ps.design.k_max)


def test_padded_csc_to_csr_sums_shared_slots_and_roundtrips_libsvm(tmp_path):
    """The generator's padded-CSC becomes CSR without densifying: slots
    that share a (row, column) pair add up (what X @ w gives them), and
    the LIBSVM text written from the CSR loads back as the same matrix."""
    from repro.data.libsvm import (load_libsvm, padded_csc_to_csr,
                                   save_libsvm)
    from repro.data.synthetic import make_sparse_classification
    Xp, y, _ = make_sparse_classification(40, 30, nnz_per_col=12, seed=4)
    s, n = Xp.shape
    mask = Xp.col_rows < s
    dense = np.zeros((s, n))
    np.add.at(dense, (Xp.col_rows[mask],
                      np.nonzero(mask)[0]), Xp.col_vals[mask])
    csr = padded_csc_to_csr(Xp)
    assert csr.nnz == np.count_nonzero(dense)
    np.testing.assert_allclose(csr.to_dense(), dense, rtol=1e-6, atol=1e-7)
    path = str(tmp_path / "x.svm")
    save_libsvm(path, csr, y)
    X2, y2 = load_libsvm(path, n_features=n, layout="csr")
    np.testing.assert_allclose(X2.to_dense(), dense, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(y2, y)
