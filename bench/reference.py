"""Plain float64 references, on the host, that import nothing of the program.

The solve cells' answers are weight vectors: `objective_kkt` recomputes
F_c(w) = c * sum_i log(1 + exp(-y_i x_i.w)) + |w|_1 and the infinity norm
of its minimum-norm subgradient from the data and w alone. The serve
cell's answers are margins: `margins` scores request rows against the
served weights. Data is either a dense (s, n) array or a padded
feature-major layout (`col_rows` (n, k) with the sentinel s at padding,
`col_vals` (n, k)), read through its two arrays and its shape.
"""
from __future__ import annotations

import numpy as np


def _is_padded(X) -> bool:
    return hasattr(X, "col_rows")


def matvec(X, w: np.ndarray) -> np.ndarray:
    """z = X w in float64."""
    if _is_padded(X):
        s = X.shape[0]
        rows = X.col_rows.reshape(-1)
        keep = rows < s
        vals = np.asarray(X.col_vals, np.float64) * w[:, None]
        return np.bincount(rows[keep], weights=vals.reshape(-1)[keep],
                           minlength=s)
    return np.asarray(X, np.float64) @ w


def _rmatvec(X, u: np.ndarray) -> np.ndarray:
    """X^T u in float64."""
    if _is_padded(X):
        s = X.shape[0]
        ug = np.where(X.col_rows < s, u[np.minimum(X.col_rows, s - 1)], 0.0)
        return np.sum(ug * np.asarray(X.col_vals, np.float64), axis=1)
    return np.asarray(X, np.float64).T @ u


def _min_norm_subgradient(g: np.ndarray, w: np.ndarray) -> np.ndarray:
    return np.abs(np.where(w > 0, g + 1.0,
                           np.where(w < 0, g - 1.0,
                                    np.maximum(np.abs(g) - 1.0, 0.0))))


def as_float64(X):
    """The design with its values in float64, converted once."""
    if _is_padded(X):
        return type(X)(col_rows=X.col_rows,
                       col_vals=X.col_vals.astype(np.float64),
                       shape=X.shape)
    return np.asarray(X, np.float64)


def objective_kkt(X, y, w, c: float) -> tuple[float, float, np.ndarray]:
    """Logistic F_c(w), its KKT violation (inf-norm of the minimum-norm
    subgradient) and the margins X w, all in float64."""
    y = np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)
    z = matvec(X, w)
    f = c * np.sum(np.logaddexp(0.0, -y * z)) + np.sum(np.abs(w))
    u = c * y * (1.0 / (1.0 + np.exp(-y * z)) - 1.0)
    kkt = np.max(_min_norm_subgradient(_rmatvec(X, u), w))
    return float(f), float(kkt), z


def kkt_at_zero(X, y, c: float) -> float:
    """KKT violation of the logistic problem at w = 0: the scale that the
    solve cells' relative stop multiplies by epsilon."""
    n = X.shape[1]
    return objective_kkt(X, y, np.zeros((n,), np.float64), c)[1]


def margins(rows: np.ndarray, W: np.ndarray, bias: np.ndarray) -> np.ndarray:
    """(B, K) float64 margins of dense request rows against (K, n) weights."""
    return (np.asarray(rows, np.float64) @ np.asarray(W, np.float64).T
            + np.asarray(bias, np.float64)[None, :])
