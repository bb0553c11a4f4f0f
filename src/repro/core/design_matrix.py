"""Design-matrix backends: dense array vs. padded feature-major sparse.

The paper's six benchmark datasets (Table 2) are >99.9% sparse; a dense
(s, n) array caps the reproduction at toy scale and makes every bundle
gather O(s * P) regardless of nnz. This module gives every solver layer a
single `DesignMatrix` interface with two interchangeable backends
(DESIGN.md section 7):

  * `DenseDesign`     — the original (s, n) jnp array. Default; every
    existing caller and benchmark keeps its exact semantics.
  * `PaddedCSCDesign` — feature-major ELL/CSC hybrid: for each column j,
    the row ids and values of its nonzeros, padded to a static width
    k_max so all shapes are jit/scan-stable:

        col_rows : (n, k_max) int32, row id or sentinel `s` for padding
        col_vals : (n, k_max) float, 0 at padding slots

    Gather of a size-P bundle is O(P * k_max) instead of O(s * P);
    gradient/Hessian reductions become masked segment sums and the
    margin update z += alpha * X_B d_B a scatter-add at `col_rows`.

Both backends are registered pytrees, so an `L1Problem` carrying either
flows through `jax.jit` / `lax.scan` unchanged.

Mixed precision (DESIGN.md section 12): values may be STORED in bf16
(`dtype=jnp.bfloat16` at construction) while every reduction below
ACCUMULATES in f32 — products/sums upcast via
`jnp.promote_types(storage, float32)`, which is bitwise a no-op for f32
storage. Solver state (w, z, u, v) stays f32 regardless; only the
design values and their HBM traffic shrink. Bundle slabs are small
NamedTuples (`DenseSlab` / `SparseSlab`) produced by `gather_slab` and
consumed by `slab_grad_hess` (dense) or `slab_grad_hess_gathered`
(padded CSC) and `slab_matvec` — the only methods the inner solver loops
touch.

The k_max trade-off (DESIGN.md section 7.2): memory and gather work scale
with n * k_max = n * max_j nnz(col j), so a single heavy column inflates
every column's padding. `from_csr` accepts an explicit `k_max` to cap it
(raising if a real column overflows); hot/cold column splitting is the
documented follow-up for power-law datasets.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class DenseSlab(NamedTuple):
    """Dense (s, P) column slab for one bundle; padded columns zeroed."""
    XB: Array          # (s, P)
    valid: Array       # (P,) bool


class SparseSlab(NamedTuple):
    """Padded-CSC slab: per bundle feature, its nonzero rows/values.

    labels: the labels at `rows`, taken from the problem's per-column
    label slab (`L1Problem.col_y`) by `L1Problem.gather_slab`; None for
    a slab the design gathered alone, whose labels
    `L1Problem.bundle_grad_hess` gathers from y at `rows`.
    """
    rows: Array        # (P, k_max) int32; sentinel == n_samples at padding
    vals: Array        # (P, k_max) float; 0 at padding
    valid: Array       # (P,) bool
    labels: Optional[Array] = None   # (P, k_max), or None


class SlabSupport(NamedTuple):
    """The row support of one bundle slab (DESIGN.md section 11).

    support: (r_max,) int32 — sorted UNIQUE row ids touched by the
    bundle, sentinel-padded (== n_samples) exactly like k_max padding;
    r_max = P * k_max is the static worst case. pos: (P, k_max) int32 —
    for every slab entry, its index into `support` (always in-bounds;
    padding entries point at a sentinel slot and carry value 0, so they
    contribute nothing to any support-scoped reduction).
    """
    support: Array     # (r_max,) int32, sorted, sentinel == n_samples
    pos: Array         # (P, k_max) int32, index into support


Slab = Union[DenseSlab, SparseSlab]


def padded_row_support(rows: Array, sentinel: int) -> SlabSupport:
    """Static-shape unique row set of a padded (P, k_max) row-id array.

    Sort the flattened ids, blank duplicates to the sentinel, re-sort so
    the unique ids stay sorted with all sentinels trailing, then recover
    every entry's slot with one searchsorted. O(P*k_max log(P*k_max)) —
    never touches the sample axis.
    """
    flat = rows.reshape(-1)
    srt = jnp.sort(flat)
    dup = jnp.concatenate([jnp.zeros((1,), bool), srt[1:] == srt[:-1]])
    support = jnp.sort(jnp.where(dup, sentinel, srt))
    pos = jnp.searchsorted(support, rows).astype(jnp.int32)
    return SlabSupport(support=support.astype(jnp.int32), pos=pos)


class DesignMatrix:
    """Interface both backends implement (duck-typed; no abc overhead).

    matvec(w)            -> (s,)  margins X @ w
    rmatvec(u)           -> (n,)  X^T @ u
    column_norms_sq()    -> (n,)  diag(X^T X)
    gather_slab(idx)     -> Slab  for a (P,) bundle with sentinel == n
    slab_grad_hess(...)  -> (g, h) raw bundle reductions (no l2 / floor);
                            padded CSC: `slab_grad_hess_gathered`, from
                            factors already laid out as the slab
    slab_matvec(...)     -> (s,)  X_B @ d_B (dense margins delta)
    """

    layout: str = "abstract"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DenseDesign(DesignMatrix):
    """The original dense backend: X is a plain (s, n) array."""

    X: Array
    layout = "dense"

    def tree_flatten(self):
        return (self.X,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(X=children[0])

    # -- shape/dtype ---------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return self.X.shape

    @property
    def n_samples(self) -> int:
        return self.X.shape[0]

    @property
    def n_features(self) -> int:
        return self.X.shape[1]

    @property
    def dtype(self):
        return self.X.dtype

    @property
    def acc_dtype(self):
        """Accumulation dtype: f32 for bf16 storage, identity for f32+."""
        return jnp.promote_types(self.X.dtype, jnp.float32)

    # -- whole-matrix products ----------------------------------------------
    def matvec(self, w: Array) -> Array:
        return self.X.astype(self.acc_dtype) @ w

    def rmatvec(self, u: Array) -> Array:
        return self.X.T.astype(self.acc_dtype) @ u

    def column_norms_sq(self) -> Array:
        return jnp.sum(jnp.square(self.X.astype(self.acc_dtype)), axis=0)

    # -- bundle slab protocol -------------------------------------------------
    def gather_slab(self, idx: Array) -> DenseSlab:
        """idx: (P,) int32 with sentinel n for the ragged last bundle."""
        n = self.X.shape[1]
        valid = idx < n
        safe = jnp.minimum(idx, n - 1)
        XB = jnp.take(self.X, safe, axis=1)
        XB = XB * valid[None, :].astype(self.X.dtype)
        return DenseSlab(XB=XB, valid=valid)

    def slab_grad_hess(self, slab: DenseSlab, u: Array, v: Array):
        """g_j = sum_i u_i X_ij ; h_j = sum_i v_i X_ij^2 (raw, no l2/floor).

        The two tall-skinny matvecs are the compute hot-spot that
        kernels/pcdn_direction fuses on TPU (DESIGN.md section 3.1).
        """
        XB = slab.XB.astype(self.acc_dtype)
        g = XB.T @ u
        h = jnp.square(XB).T @ v
        return g, h

    def slab_matvec(self, slab: DenseSlab, d: Array) -> Array:
        """delta_z = X_B @ d_B, the (s,) margin delta of a bundle step."""
        return slab.XB.astype(self.acc_dtype) @ d

    def slab_coordinate_deltas(self, slab: DenseSlab, d: Array) -> Array:
        """(P, s) per-coordinate margin deltas d_j * X[:, j] — the blind
        single-coordinate steps SCDN's racing line searches evaluate."""
        return (slab.XB * d[None, :]).T

    def to_dense(self) -> Array:
        return self.X


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PaddedCSCDesign(DesignMatrix):
    """Feature-major padded sparse backend (ELL over columns).

    col_rows[j, k] is the row id of the k-th nonzero of column j, or the
    sentinel `n_samples` at padding slots; col_vals holds the values with
    zeros at padding. Static (n, k_max) shapes keep every solver loop
    jit/scan-able; sentinel rows are dropped by `mode="drop"` scatters and
    zero-filled by `mode="fill"` gathers, so padding contributes nothing
    to any reduction (DESIGN.md section 7.1).
    """

    col_rows: Array    # (n, k_max) int32
    col_vals: Array    # (n, k_max) float
    _n_samples: int    # static: sentinel value and margins length
    layout = "padded_csc"

    def tree_flatten(self):
        return (self.col_rows, self.col_vals), (self._n_samples,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        rows, vals = children
        return cls(col_rows=rows, col_vals=vals, _n_samples=aux[0])

    # -- shape/dtype ---------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return (self._n_samples, self.col_rows.shape[0])

    @property
    def n_samples(self) -> int:
        return self._n_samples

    @property
    def n_features(self) -> int:
        return self.col_rows.shape[0]

    @property
    def k_max(self) -> int:
        return self.col_rows.shape[1]

    @property
    def dtype(self):
        return self.col_vals.dtype

    @property
    def acc_dtype(self):
        """Accumulation dtype: f32 for bf16 storage, identity for f32+."""
        return jnp.promote_types(self.col_vals.dtype, jnp.float32)

    # -- whole-matrix products ----------------------------------------------
    def matvec(self, w: Array) -> Array:
        """z = X @ w as one scatter-add of every weighted nonzero."""
        acc = self.acc_dtype
        z = jnp.zeros((self._n_samples,), acc)
        return z.at[self.col_rows].add(
            self.col_vals.astype(acc) * w[:, None], mode="drop")

    def rmatvec(self, u: Array) -> Array:
        """X^T u: gather u at each column's rows, masked segment sum."""
        ug = jnp.take(u, self.col_rows, mode="fill", fill_value=0)
        return jnp.sum(ug * self.col_vals.astype(self.acc_dtype), axis=1)

    def column_norms_sq(self) -> Array:
        return jnp.sum(jnp.square(self.col_vals.astype(self.acc_dtype)),
                       axis=1)

    # -- bundle slab protocol -------------------------------------------------
    def gather_slab(self, idx: Array) -> SparseSlab:
        """O(P * k_max) bundle gather — never touches the other columns."""
        n = self.col_rows.shape[0]
        s = self._n_samples
        valid = idx < n
        safe = jnp.minimum(idx, n - 1)
        rows = jnp.where(valid[:, None], jnp.take(self.col_rows, safe,
                                                  axis=0), s)
        vals = jnp.take(self.col_vals, safe, axis=0) * \
            valid[:, None].astype(self.col_vals.dtype)
        return SparseSlab(rows=rows, vals=vals, valid=valid)

    def slab_grad_hess_gathered(self, slab: SparseSlab, ug: Array,
                                vg: Array):
        """Masked segment reductions over the padded column layout, from
        u/v already laid out as the slab, (P, k_max): the k-order sums
        that the full and support scopes share, so they agree bitwise
        wherever their addends do (padding vals are 0, so whatever u/v
        hold there adds nothing)."""
        vals = slab.vals.astype(self.acc_dtype)
        g = jnp.sum(ug * vals, axis=1)
        h = jnp.sum(vg * jnp.square(vals), axis=1)
        return g, h

    def slab_matvec(self, slab: SparseSlab, d: Array) -> Array:
        """delta_z via scatter-add at col_rows (duplicate rows accumulate)."""
        acc = self.acc_dtype
        z = jnp.zeros((self._n_samples,), acc)
        return z.at[slab.rows].add(slab.vals.astype(acc) * d[:, None],
                                   mode="drop")

    # -- support-scoped slab protocol (DESIGN.md section 11) -----------------
    def slab_row_support(self, slab: SparseSlab) -> SlabSupport:
        """Static (r_max = P * k_max) unique row set of one bundle slab.

        Everything a bundle step does to the per-sample intermediates is
        zero outside these rows (delta_i = 0 there), so the line search
        and the z update can be restricted to them — O(P * k_max) work
        instead of O(s) per bundle.
        """
        return padded_row_support(slab.rows, self._n_samples)

    def slab_grad_hess_support(self, slab: SparseSlab, pos: Array,
                               u_R: Array, v_R: Array):
        """`slab_grad_hess_gathered` with u/v given only at the support
        rows.

        u_R/v_R: (r_max,) factors evaluated at support order; pos maps
        each slab entry into them (always in-bounds, padding vals are 0),
        so the gather never touches the (s,)-sized vectors. Bitwise equal
        to the full-scope reduction: same addends in the same k-order.
        """
        return self.slab_grad_hess_gathered(slab, jnp.take(u_R, pos),
                                            jnp.take(v_R, pos))

    def slab_matvec_support(self, slab: SparseSlab, pos: Array,
                            d: Array) -> Array:
        """Support-compressed margin delta: (r_max,) values delta_R with
        delta_R[r] = (X_B d_B)[support[r]]. Sentinel support slots stay
        exactly 0 (padding entries carry value 0)."""
        acc = self.acc_dtype
        r_max = pos.shape[0] * pos.shape[1]
        out = jnp.zeros((r_max,), acc)
        return out.at[pos].add(slab.vals.astype(acc) * d[:, None])

    def scatter_support(self, z: Array, support: Array, upd: Array) -> Array:
        """z[support] += upd with sentinel slots dropped (the support-
        scoped form of the z += alpha * X_B d_B margin maintenance)."""
        return z.at[support].add(upd, mode="drop")

    def slab_coordinate_deltas(self, slab: SparseSlab, d: Array) -> Array:
        """(P, s) per-coordinate margin deltas (vmapped single scatters)."""
        s = self._n_samples
        acc = self.acc_dtype

        def one(rows_j, vals_j, d_j):
            return jnp.zeros((s,), acc).at[rows_j].add(
                vals_j.astype(acc) * d_j, mode="drop")

        return jax.vmap(one)(slab.rows, slab.vals, d)

    def to_dense(self) -> Array:
        """Materialize (s, n) — test/debug only; O(s * n) memory."""
        s, n = self.shape
        out = jnp.zeros((s, n), self.col_vals.dtype)
        cols = jnp.broadcast_to(jnp.arange(n)[:, None], self.col_rows.shape)
        return out.at[self.col_rows, cols].add(self.col_vals, mode="drop")

    # -- constructors ---------------------------------------------------------
    @classmethod
    def from_csr(cls, data, indices, indptr, shape, k_max=None,
                 dtype=np.float32) -> "PaddedCSCDesign":
        """Build from CSR triple without ever densifying (numpy-side).

        k_max defaults to the max column nnz; passing a smaller value
        raises if any column overflows (truncation would silently change
        the objective).
        """
        rows_np, vals_np, s, n = padded_csc_arrays(
            data, indices, indptr, shape, k_max=k_max, dtype=dtype)
        return cls(col_rows=jnp.asarray(rows_np),
                   col_vals=jnp.asarray(vals_np), _n_samples=s)

    @classmethod
    def from_dense(cls, X, k_max=None, dtype=np.float32) -> "PaddedCSCDesign":
        """Convert a small dense matrix (tests / benchmarks)."""
        X = np.asarray(X, dtype=dtype)
        s, n = X.shape
        nz_rows, nz_cols = np.nonzero(X.T)  # feature-major order
        # X.T nonzero walks columns of X in order: nz_rows is the column id
        indptr = np.concatenate(
            [[0], np.cumsum(np.bincount(nz_rows, minlength=n))])
        counts = np.diff(indptr).astype(np.int64)
        k = int(max(1, counts.max() if counts.size else 1))
        if k_max is not None:
            if k > int(k_max):
                raise ValueError(
                    f"k_max={k_max} < max column nnz {k}")
            k = int(k_max)
        col_rows = np.full((n, k), s, np.int32)
        col_vals = np.zeros((n, k), dtype)
        pos = np.arange(nz_rows.shape[0]) - indptr[nz_rows]
        col_rows[nz_rows, pos] = nz_cols
        col_vals[nz_rows, pos] = X.T[nz_rows, nz_cols]
        return cls(col_rows=jnp.asarray(col_rows),
                   col_vals=jnp.asarray(col_vals), _n_samples=s)


def padded_csc_arrays(data, indices, indptr, shape, k_max=None,
                      dtype=np.float32):
    """CSR triple -> (col_rows, col_vals, s, n) numpy padded-CSC arrays.

    Fully vectorized: stable-sorts the nnz stream by column, computes each
    entry's rank within its column from the column-start offsets, and
    scatters into the padded layout. O(nnz log nnz), no (s, n) temporary.
    """
    s, n = shape
    data = np.asarray(data, dtype=dtype)
    indices = np.asarray(indices, dtype=np.int64)
    indptr = np.asarray(indptr, dtype=np.int64)
    nnz = data.shape[0]
    row_ids = np.repeat(np.arange(s, dtype=np.int64), np.diff(indptr))
    order = np.argsort(indices, kind="stable")
    cols = indices[order]
    rows = row_ids[order]
    vals = data[order]
    counts = np.bincount(cols, minlength=n).astype(np.int64)
    k = int(max(1, counts.max() if counts.size else 1))
    if k_max is not None:
        if k > int(k_max):
            raise ValueError(f"k_max={k_max} < max column nnz {k}")
        k = int(k_max)
    col_start = np.concatenate([[0], np.cumsum(counts)])
    pos = np.arange(nnz, dtype=np.int64) - col_start[cols]
    col_rows = np.full((n, k), s, np.int32)
    col_vals = np.zeros((n, k), dtype)
    col_rows[cols, pos] = rows
    col_vals[cols, pos] = vals
    return col_rows, col_vals, int(s), int(n)


def as_design(X, dtype=jnp.float32, layout: str = "auto",
              k_max=None) -> DesignMatrix:
    """Coerce whatever callers hand us into a DesignMatrix.

    Accepts an existing DesignMatrix (passed through), a dense numpy/jax
    array, or a CSR-like object with .data/.indices/.indptr/.shape (e.g.
    data.libsvm.CSRMatrix or a scipy csr_matrix) — the latter never
    densifies. layout: "auto" keeps arrays dense and CSR sparse; "dense"
    / "padded_csc" force a backend (forcing CSR dense is refused — it
    would silently materialize (s, n)).
    """
    if isinstance(X, DesignMatrix):
        return X
    if all(hasattr(X, a) for a in ("col_rows", "col_vals", "shape")):
        # data.libsvm.PaddedCSC (numpy-side padded layout)
        if layout == "dense":
            raise ValueError(
                "PaddedCSC input with layout='dense' would densify; pass "
                "layout='padded_csc'/'auto'.")
        if k_max is not None and int(k_max) != int(X.col_rows.shape[1]):
            raise ValueError(
                f"k_max={k_max} conflicts with the prebuilt PaddedCSC "
                f"width {X.col_rows.shape[1]}; re-pad at conversion time.")
        return PaddedCSCDesign(col_rows=jnp.asarray(X.col_rows),
                               col_vals=jnp.asarray(X.col_vals, dtype=dtype),
                               _n_samples=int(X.shape[0]))
    if all(hasattr(X, a) for a in ("data", "indices", "indptr", "shape")):
        if layout == "dense":
            raise ValueError(
                "CSR input with layout='dense' would densify; pass "
                "layout='padded_csc'/'auto' (or convert explicitly).")
        return PaddedCSCDesign.from_csr(X.data, X.indices, X.indptr,
                                        X.shape, k_max=k_max, dtype=dtype)
    if layout == "padded_csc":
        return PaddedCSCDesign.from_dense(np.asarray(X), k_max=k_max,
                                          dtype=dtype)
    return DenseDesign(X=jnp.asarray(np.asarray(X), dtype=dtype))
