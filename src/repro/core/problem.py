"""l1-regularized ERM problem container (paper Eq. 1).

    min_w  F_c(w) = c * sum_i phi(w . x_i, y_i) + ||w||_1

Holds the design matrix behind the `DesignMatrix` backend interface
(DESIGN.md section 7) — dense (s, n) array or padded-CSC sparse — plus
labels y (s,), regularization c and the loss. All solver math is phrased
through the per-sample margin z = X @ w, the intermediate quantity of
paper section 3.1, and through the backend's slab protocol for bundle
restrictions, so every solver runs unchanged on either layout.

`elastic_net_l2` adds an optional (lambda2/2)||w||^2 smooth term (paper
section 6 extension); it folds into the gradient/Hessian diagonals.

`col_y` is the labels in the padded-CSC column layout, y[col_rows]
(DESIGN.md section 7.1): `make_problem` builds it once for a sparse
design, so a bundle's direction reads its labels with a P-row gather and
gathers only the margins per slab entry; without it, the labels are
gathered per slab entry like the margins.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.design_matrix import (DenseDesign, DenseSlab, DesignMatrix,
                                      PaddedCSCDesign, Slab, SparseSlab,
                                      as_design)
from repro.core.losses import HESSIAN_FLOOR, Loss, get_loss

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class L1Problem:
    """l1-regularized problem over a DesignMatrix backend. y: (s,) +-1.

    col_y: optional (n, k_max) labels at a padded-CSC design's col_rows,
    1 at padding, in the design's dtype where that holds y exactly (see
    `make_problem`); None for a dense design, a problem built directly,
    and after `with_labels`.
    """

    design: DesignMatrix
    y: Array
    c: float
    loss_name: str = "logistic"
    elastic_net_l2: float = 0.0
    col_y: Optional[Array] = None

    # -- pytree plumbing (design, y, col_y are leaves; scalars static aux) ---
    def tree_flatten(self):
        return (self.design, self.y, self.col_y), (self.c, self.loss_name,
                                                   self.elastic_net_l2)

    @classmethod
    def tree_unflatten(cls, aux, children):
        design, y, col_y = children
        c, loss_name, l2 = aux
        return cls(design=design, y=y, c=c, loss_name=loss_name,
                   elastic_net_l2=l2, col_y=col_y)

    # -- trace-time substitution ---------------------------------------------
    def with_c(self, c) -> "L1Problem":
        """Replace the regularization weight; `c` may be a traced scalar.

        `c` lives in the pytree's static aux data, so a problem carrying a
        tracer must NOT cross a jit boundary — but substitution *inside* a
        traced function is exactly how the path engine (DESIGN.md section
        8) reuses one compiled outer iteration across every grid point.
        """
        return dataclasses.replace(self, c=c)

    def with_labels(self, y: Array) -> "L1Problem":
        """Replace labels (same design); used by the vmapped batch solver.

        Drops `col_y`: the batch solver swaps labels inside its traced
        iteration, where rebuilding the (n, k_max) label slab would cost
        a full gather every iteration; the direction then gathers the
        labels at each slab entry, as for a problem without one."""
        return dataclasses.replace(self, y=y, col_y=None)

    # -- basic accessors -----------------------------------------------------
    @property
    def X(self) -> Array:
        """Back-compat dense view. Only the dense backend has one — the
        sparse backend refuses rather than materialize (s, n)."""
        if isinstance(self.design, DenseDesign):
            return self.design.X
        raise TypeError(
            f"L1Problem.X is dense-only; this problem uses the "
            f"{self.design.layout!r} backend. Go through problem.design.")

    @property
    def loss(self) -> Loss:
        return get_loss(self.loss_name)

    @property
    def n_samples(self) -> int:
        return self.design.n_samples

    @property
    def n_features(self) -> int:
        return self.design.n_features

    @property
    def dtype(self):
        return self.design.dtype

    @property
    def solve_dtype(self):
        """Dtype of the solver STATE (w, z, labels): f32 when the design
        stores bf16 values (mixed-precision mode — fp32 accumulation for
        the margin state, DESIGN.md section 12), identity otherwise."""
        return jnp.promote_types(self.design.dtype, jnp.float32)

    # -- objective -----------------------------------------------------------
    def margins(self, w: Array) -> Array:
        return self.design.matvec(w)

    def objective_from_margins(self, z: Array, w: Array) -> Array:
        f = self.loss.margin_objective(z, self.y, self.c) + jnp.sum(jnp.abs(w))
        if self.elastic_net_l2:
            f = f + 0.5 * self.elastic_net_l2 * jnp.sum(jnp.square(w))
        return f

    def objective(self, w: Array) -> Array:
        return self.objective_from_margins(self.margins(w), w)

    # -- per-sample factors used by every solver ------------------------------
    def grad_factor(self, z: Array) -> Array:
        """u_i = c * dphi/dz_i ; grad_j L = sum_i u_i x_ij = X[:,j] . u."""
        return self.c * self.loss.dz(z, self.y)

    def hess_factor(self, z: Array) -> Array:
        """v_i = c * d2phi/dz2_i ; hess_jj L = sum_i v_i x_ij^2."""
        return self.c * self.loss.d2z(z, self.y)

    # -- support-gathered factors (DESIGN.md section 11) ---------------------
    def grad_factor_at(self, z_R: Array, y_R: Array) -> Array:
        """`grad_factor` over explicitly gathered (z_R, y_R) — evaluated
        at a bundle's <= P * k_max support rows instead of all s samples.
        Bitwise equal to grad_factor(z)[support] (elementwise map)."""
        return self.c * self.loss.dz(z_R, y_R)

    def hess_factor_at(self, z_R: Array, y_R: Array) -> Array:
        """`hess_factor` over explicitly gathered (z_R, y_R)."""
        return self.c * self.loss.d2z(z_R, y_R)

    def bundle_grad_hess_support(self, slab: SparseSlab, pos: Array,
                                 z_R: Array, y_R: Array, w_B: Array):
        """`bundle_grad_hess` computed entirely on a bundle's row support.

        z_R/y_R: (r_max,) margins and labels gathered at the slab's
        `slab_row_support`; pos maps slab entries into them. Same l2 fold
        and Hessian floor as the full-scope path, with u/v evaluated at
        <= P * k_max rows instead of s.
        """
        u_R = self.grad_factor_at(z_R, y_R)
        v_R = self.hess_factor_at(z_R, y_R)
        g, h = self.design.slab_grad_hess_support(slab, pos, u_R, v_R)
        if self.elastic_net_l2:
            g = g + self.elastic_net_l2 * w_B
            h = h + self.elastic_net_l2
        return g, jnp.maximum(h, HESSIAN_FLOOR)

    def gather_slab(self, idx: Array) -> Slab:
        """The design's slab for a (P,) bundle (sentinel == n); with
        `col_y`, the padded-CSC slab also carries its labels, a P-row
        gather like the one that fetches its values (a sentinel slot
        reads label 1, as padding does)."""
        slab = self.design.gather_slab(idx)
        if self.col_y is None:
            return slab
        return slab._replace(labels=jnp.take(self.col_y, idx, axis=0,
                                             mode="fill", fill_value=1))

    def bundle_grad_hess(self, z: Array, slab: Union[Slab, Array],
                         w_B: Array):
        """Gradient and Hessian diagonal restricted to a bundle slab.

        slab: a DenseSlab/SparseSlab from `gather_slab`, or (legacy) a
        raw dense (s, P) column block. Returns (g_B, h_B), each (P,).
        The reductions here are the compute hot-spot that the Pallas
        kernels fuse on TPU (DESIGN.md sections 3.1 / 7.3).

        A padded-CSC slab gathers the margins at its entries and forms u
        and v there, with the labels it carries (`gather_slab` with
        `col_y`) or else gathered from y at its rows: one element gather
        from an (s,) vector per slab entry, or two.
        """
        if isinstance(slab, SparseSlab):
            z_g = jnp.take(z, slab.rows, mode="fill", fill_value=0)
            if slab.labels is None:
                y_g = jnp.take(self.y, slab.rows, mode="fill", fill_value=1)
            else:
                y_g = slab.labels.astype(self.y.dtype)
            g, h = self.design.slab_grad_hess_gathered(
                slab, self.grad_factor_at(z_g, y_g),
                self.hess_factor_at(z_g, y_g))
        elif isinstance(slab, DenseSlab):
            g, h = self.design.slab_grad_hess(slab, self.grad_factor(z),
                                              self.hess_factor(z))
        else:  # raw dense (s, P) array — legacy call sites and tests
            u = self.grad_factor(z)
            v = self.hess_factor(z)
            g = slab.T @ u
            h = jnp.square(slab).T @ v
        if self.elastic_net_l2:
            g = g + self.elastic_net_l2 * w_B
            h = h + self.elastic_net_l2
        return g, jnp.maximum(h, HESSIAN_FLOOR)

    def full_grad(self, z: Array, w: Array) -> Array:
        """grad L(w) (n,) — used by TRON and the KKT stopping criterion."""
        g = self.design.rmatvec(self.grad_factor(z))
        if self.elastic_net_l2:
            g = g + self.elastic_net_l2 * w
        return g

    # -- KKT optimality measure ----------------------------------------------
    def kkt_violation_from_grad(self, w: Array, g: Array) -> Array:
        """Per-feature |minimum-norm subgradient| of F_c at w, given the
        smooth gradient g = grad L(w). (n,) nonnegative; all-zero iff w is
        optimal. The shrinking solver and the path engine consume the
        vector; `kkt_violation` reduces it to the scalar stop."""
        pos = g + 1.0
        neg = g - 1.0
        zero = jnp.maximum(jnp.abs(g) - 1.0, 0.0)
        v = jnp.where(w > 0, pos, jnp.where(w < 0, neg, zero))
        return jnp.abs(v)

    def kkt_violation(self, w: Array, z: Optional[Array] = None) -> Array:
        """inf-norm of the minimum-norm subgradient of F_c at w.

        v_j = g_j + 1        if w_j > 0
            = g_j - 1        if w_j < 0
            = max(|g_j|-1,0) if w_j = 0
        Zero iff w is optimal. Used as the LIBLINEAR-style outer stop.
        """
        if z is None:
            z = self.margins(w)
        g = self.full_grad(z, w)
        return jnp.max(self.kkt_violation_from_grad(w, g))

    # -- regularization path quantities ---------------------------------------
    def c_max(self) -> float:
        """Largest c for which w = 0 is optimal (DESIGN.md section 8.1).

        At the origin every margin is zero, so the loss gradient is
        c * X^T phi'(0, y); w = 0 satisfies the KKT conditions iff that
        vector stays inside the l1 subdifferential box [-1, 1]^n:

            c <= c_max = 1 / || X^T phi'(0, y) ||_inf

        (the elastic-net quadratic vanishes at 0 and does not move this).
        This is the analytic start of the regularization path: the paper's
        F_c = c * L + ||w||_1 parameterization puts lambda ~ 1/c, so the
        classical lambda_max is 1 / c_max and the path sweeps c UP from
        c_max (all-zero model) toward weaker regularization.
        """
        z0 = jnp.zeros((self.n_samples,), self.solve_dtype)
        u0 = self.loss.dz(z0, self.y)
        g0 = self.design.rmatvec(u0)
        denom = float(jnp.max(jnp.abs(g0)))
        if denom <= 0.0:
            raise ValueError("degenerate problem: X^T phi'(0, y) == 0 "
                             "(no feature correlates with the labels)")
        return 1.0 / denom

    # -- Lemma 1 quantities ----------------------------------------------------
    def column_norms_sq(self) -> Array:
        """(X^T X)_jj for j in N — the lambda_j of Lemma 1 / Theorem 2."""
        return self.design.column_norms_sq()


def make_problem(
    X,
    y,
    c: float,
    loss: str = "logistic",
    elastic_net_l2: float = 0.0,
    dtype=jnp.float32,
    layout: str = "auto",
    k_max: Optional[int] = None,
) -> L1Problem:
    """Build an L1Problem from a dense array, CSRMatrix, or DesignMatrix.

    layout="auto" keeps dense input dense and CSR input padded-CSC (no
    densification); "padded_csc" forces the sparse backend (converting a
    dense array if needed — handy for equivalence tests).
    """
    design = as_design(X, dtype=dtype, layout=layout, k_max=k_max)
    # labels live with the solver state: f32 even under bf16 storage
    y = jnp.asarray(np.asarray(y),
                    dtype=jnp.promote_types(dtype, jnp.float32))
    col_y = None
    if isinstance(design, PaddedCSCDesign):
        # one (n, k_max) gather at set-up; padding reads label 1 (finite
        # factors at z = 0, and its value 0 adds nothing). Stored like
        # col_vals where that holds every label exactly (+-1 in bf16), so
        # it never takes more memory than the design's values.
        y_col = y.astype(design.dtype)
        if not bool(jnp.all(y_col.astype(y.dtype) == y)):
            y_col = y
        col_y = jnp.take(y_col, design.col_rows, mode="fill", fill_value=1)
    return L1Problem(design=design, y=y, c=float(c), loss_name=loss,
                     elastic_net_l2=float(elastic_net_l2), col_y=col_y)


def validation_accuracy(design, y, w) -> float:
    """Classification accuracy of sign(X_val @ w) against +-1 labels.

    `design` may be anything `as_design` accepts (dense array, CSR,
    padded layout, DesignMatrix), so held-out metrics never densify a
    sparse split. A host array or layout is uploaded on every call; a
    DesignMatrix is used as it is, which is how a path sweep scores all
    its points against one device design (`path.driver.run_path`
    converts the split once). Zero margins count as +1, matching
    data.synthetic.train_accuracy.
    """
    d = as_design(design)
    z = np.asarray(d.matvec(jnp.asarray(np.asarray(w), d.dtype)))
    pred = np.sign(z)
    pred[pred == 0] = 1.0
    return float(np.mean(pred == np.asarray(y)))


def expected_max_column_norm(problem: L1Problem, P: int) -> float:
    """E_B[ lambda_bar(B) ] for uniform random size-P bundles (Lemma 1a).

    f(P) = (1/C(n,P)) * sum_k lambda_(k) * C(k-1, P-1)
    computed stably in log space with numpy (analysis-time only).
    """
    lam = np.sort(np.asarray(problem.column_norms_sq(), dtype=np.float64))
    return float(expected_max_of_sample(lam, P))


def expected_max_of_sample(lam_sorted: np.ndarray, P: int) -> float:
    """E[max of a uniform size-P subset] given sorted values (Lemma 1a Eq. 22).

    Weight of the k-th smallest value (1-indexed) is C(k-1,P-1)/C(n,P);
    computed in log space via cumulative log-factorials (no scipy needed).
    """
    lam_sorted = np.asarray(lam_sorted, dtype=np.float64)
    n = lam_sorted.shape[0]
    P = int(P)
    if not 1 <= P <= n:
        raise ValueError(f"P={P} out of [1, {n}]")
    if P == 1:
        return float(lam_sorted.mean())
    # log k! for k = 0..n
    logfact = np.concatenate([[0.0], np.cumsum(np.log(np.arange(1, n + 1)))])

    def logC(a: np.ndarray, b: int) -> np.ndarray:  # log C(a, b), a >= b
        return logfact[a] - logfact[b] - logfact[a - b]

    k = np.arange(P, n + 1)  # only k >= P contribute
    logw = logC(k - 1, P - 1) - logC(np.array([n]), P)
    w = np.exp(logw)
    return float(np.sum(w * lam_sorted[P - 1:]))
