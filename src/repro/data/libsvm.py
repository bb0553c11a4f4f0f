"""LIBSVM text format reader/writer (the paper's six datasets ship in it).

Format, one sample per line:   <label> <idx>:<val> <idx>:<val> ...
Indices are 1-based. Three output layouts (DESIGN.md sections 3.1 / 7):

    layout="dense"       (s, n) float32 array — the original TPU slab path
    layout="csr"         CSRMatrix triple, no densification
    layout="padded_csc"  (col_rows, col_vals, shape) feature-major padded
                         arrays for the sparse DesignMatrix backend —
                         zero densification end to end

Parsing is numpy-vectorized: the per-line Python work is only collecting
"idx:val" tokens; index/value conversion of the whole nnz stream happens
in two `np.array(...).astype(...)` calls, which is ~an order of magnitude
faster than float()-per-token for the paper's datasets.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np


@dataclasses.dataclass
class CSRMatrix:
    data: np.ndarray      # (nnz,) float32
    indices: np.ndarray   # (nnz,) int32 column ids
    indptr: np.ndarray    # (s+1,) int64
    shape: Tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    def to_dense(self) -> np.ndarray:
        """Vectorized scatter — one fancy-indexed assignment, no row loop."""
        s, n = self.shape
        out = np.zeros((s, n), dtype=np.float32)
        row_ids = np.repeat(np.arange(s), np.diff(self.indptr))
        out[row_ids, self.indices] = self.data
        return out

    def row_slice(self, start: int, stop: int) -> "CSRMatrix":
        """Rows [start, stop) as their own CSRMatrix (a held-out split)."""
        a, b = int(self.indptr[start]), int(self.indptr[stop])
        return CSRMatrix(data=self.data[a:b], indices=self.indices[a:b],
                         indptr=self.indptr[start:stop + 1] - a,
                         shape=(stop - start, self.shape[1]))

    def sparsity(self) -> float:
        s, n = self.shape
        return 1.0 - self.nnz / float(s * n)

    def max_col_nnz(self) -> int:
        """k_max of the padded-CSC layout this matrix would convert to."""
        if self.nnz == 0:
            return 1
        return int(np.bincount(self.indices,
                               minlength=self.shape[1]).max())

    @classmethod
    def from_dense(cls, X) -> "CSRMatrix":
        """Row-major sparse view of a dense array (serving/test helper)."""
        X = np.asarray(X, np.float32)
        rows, cols = np.nonzero(X)
        indptr = np.concatenate(
            [[0], np.cumsum(np.bincount(rows, minlength=X.shape[0]))]
        ).astype(np.int64)
        return cls(data=X[rows, cols], indices=cols.astype(np.int32),
                   indptr=indptr, shape=X.shape)


@dataclasses.dataclass
class PaddedCSC:
    """Numpy-side padded feature-major layout (see core.design_matrix)."""
    col_rows: np.ndarray  # (n, k_max) int32; sentinel == s at padding
    col_vals: np.ndarray  # (n, k_max) float32; 0 at padding
    shape: Tuple[int, int]

    @property
    def k_max(self) -> int:
        return int(self.col_rows.shape[1])

    @property
    def nnz(self) -> int:
        return int(np.count_nonzero(self.col_rows < self.shape[0]))


def csr_to_padded_csc(csr: CSRMatrix,
                      k_max: Optional[int] = None) -> PaddedCSC:
    """CSR -> padded-CSC without densifying. k_max defaults to the max
    column nnz; a smaller explicit k_max raises if any column overflows
    (truncation would silently change the objective — DESIGN.md 7.2)."""
    from repro.core.design_matrix import padded_csc_arrays
    col_rows, col_vals, s, n = padded_csc_arrays(
        csr.data, csr.indices, csr.indptr, csr.shape, k_max=k_max)
    return PaddedCSC(col_rows=col_rows, col_vals=col_vals, shape=(s, n))


def padded_csc_to_csr(X: PaddedCSC) -> CSRMatrix:
    """Padded-CSC -> CSR without densifying. Entries that share a (row,
    column) slot — `make_sparse_classification` draws rows with
    replacement — are summed into one, the value X @ w gives them."""
    s, n = X.shape
    mask = X.col_rows < s
    cols = np.broadcast_to(np.arange(n, dtype=np.int64)[:, None],
                           mask.shape)[mask]
    key = X.col_rows[mask].astype(np.int64) * n + cols
    uniq, inv = np.unique(key, return_inverse=True)
    data = np.bincount(inv, weights=X.col_vals[mask].astype(np.float64),
                       minlength=uniq.shape[0]).astype(np.float32)
    rows = uniq // n
    indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(rows, minlength=s))]).astype(np.int64)
    return CSRMatrix(data=data, indices=(uniq % n).astype(np.int32),
                     indptr=indptr, shape=(s, n))


def _parse_libsvm_text(path: str):
    # Two flat 1-D token lists (not an (nnz, 2) unicode matrix — numpy
    # fixed-width string arrays cost max-token-width * 4 B per cell,
    # which is GBs of transient memory at paper-dataset nnz counts);
    # numeric conversion of each list is one vectorized np.asarray.
    labels, idx_tok, val_tok, ptr = [], [], [], [0]
    with open(path, "r") as fh:
        for line in fh:
            parts = line.split()
            if not parts:
                continue
            labels.append(parts[0])
            for tok in parts[1:]:
                k, _, v = tok.partition(":")
                idx_tok.append(k)
                val_tok.append(v)
            ptr.append(len(idx_tok))
    y = np.asarray(labels, dtype=np.float32)
    idx = np.asarray(idx_tok, dtype=np.int64) - 1    # 1-based on disk
    vals = np.asarray(val_tok, dtype=np.float32)
    return y, idx, vals, np.asarray(ptr, dtype=np.int64)


def normalize_labels(y: np.ndarray):
    """Raw file labels -> (y_norm, classes).

    ANY <= 2-label set normalizes to the solvers' +-1 contract with
    classes == [-1, +1]: {0, 1} and {-1, +1} map as historically (sign),
    and other two-label vocabularies ({1, 2}-style files are common in
    the wild) map smaller -> -1, larger -> +1 — never as raw codes,
    which would silently zero out the y == 0 class inside a +-1 loss.
    Three or more labels are a multiclass vocabulary: classes is the
    sorted unique label values and y_norm the float32 integer codes into
    it (what `serve.ovr.fit_ovr` and `launch.predict` consume).
    """
    uniq = np.unique(y)
    if uniq.size <= 2:
        if set(uniq.tolist()) <= {0.0, 1.0} or \
                set(uniq.tolist()) <= {-1.0, 1.0}:
            y = np.where(y > 0, 1.0, -1.0)
        else:
            y = np.where(y == uniq.max(), 1.0, -1.0)
        return y.astype(np.float32), np.asarray([-1.0, 1.0], np.float32)
    codes = np.searchsorted(uniq, y)
    return codes.astype(np.float32), uniq.astype(np.float32)


def load_libsvm(path: str, n_features: Optional[int] = None,
                dense: bool = True, layout: Optional[str] = None,
                k_max: Optional[int] = None, return_classes: bool = False):
    """-> (X, y) where X's type follows `layout` (y (s,) float32 +-1),
    or (X, y, classes) with return_classes=True.

    layout: "dense" (default; (s, n) float32 array), "csr" (CSRMatrix),
    or "padded_csc" (PaddedCSC — never materializes the dense matrix).
    The legacy `dense=False` flag maps to layout="csr".

    Labels: binary files keep the historical contract (y in {-1, +1},
    with 0/1 files mapped onto it). Multiclass integer-labeled files are
    supported with return_classes=True: y becomes the class CODES
    (0..K-1, float32) and `classes` the sorted label vocabulary — the
    exact inputs `serve.ovr.fit_ovr` takes. Loading a multiclass file
    without return_classes raises rather than silently feeding class ids
    into a +-1 solver.
    """
    if layout is None:
        layout = "dense" if dense else "csr"
    if layout not in ("dense", "csr", "padded_csc"):
        raise ValueError(f"unknown layout {layout!r}")

    y, idx, vals, ptr = _parse_libsvm_text(path)
    n = n_features or (int(idx.max()) + 1 if idx.size else 0)
    y, classes = normalize_labels(y)
    if classes.shape[0] > 2 and not return_classes:
        raise ValueError(
            f"{path!r} has {classes.shape[0]} label values "
            f"{classes.tolist()[:8]}...; pass return_classes=True to get "
            f"(X, codes, classes) for one-vs-rest training")
    csr = CSRMatrix(vals, idx.astype(np.int32), ptr, (y.shape[0], n))
    if layout == "dense":
        X = csr.to_dense()
    elif layout == "padded_csc":
        X = csr_to_padded_csc(csr, k_max=k_max)
    else:
        X = csr
    if not return_classes:
        return X, y
    if classes.shape[0] == 2:
        # uniform contract: y is always CODES into classes here, so
        # classes[codes] reconstructs the +-1 labels for binary files too
        y = (y > 0).astype(np.float32)
    return X, y, classes


def save_libsvm(path: str, X, y: np.ndarray) -> None:
    """Write (X, y) as LIBSVM text. X: a dense (s, n) array or a
    CSRMatrix, which is written as it is, never densified."""
    csr = X if isinstance(X, CSRMatrix) else CSRMatrix.from_dense(X)
    ptr = csr.indptr
    with open(path, "w") as fh:
        for i, yi in enumerate(y):
            cols = csr.indices[ptr[i]:ptr[i + 1]]
            vals = csr.data[ptr[i]:ptr[i + 1]]
            feats = " ".join(f"{j + 1}:{v:.6g}" for j, v in zip(cols, vals))
            fh.write(f"{yi:g} {feats}\n")
