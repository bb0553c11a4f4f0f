"""A configuration's data, made on the host from the seed.

The generators are the benchmark's own copies of the program's
`repro.data.synthetic` generators (same draws, so the same seed gives
the same data as the program's CLIs), kept here so that a change to the
program cannot change what the benchmark feeds it. `build` reads the
`data` group of a configuration file: "sparse" makes a padded
feature-major design without ever holding the dense (s, n) matrix;
"dense" makes a latent-factor mixture and keeps its leading training
rows. Nothing is written to disk.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Padded:
    """Padded feature-major layout: column j holds `col_vals[j]` at rows
    `col_rows[j]`, padded with the sentinel row s and value 0. Entries
    that share a (row, column) slot add up, as X @ w adds them."""
    col_rows: np.ndarray        # (n, k_max) int32
    col_vals: np.ndarray        # (n, k_max) float32
    shape: tuple

    @property
    def nnz(self) -> int:
        return int(np.count_nonzero(self.col_rows < self.shape[0]))


def sparse_classification(s: int, n: int, nnz_per_col: int, seed: int,
                          w_nnz_frac: float = 0.02, noise: float = 0.1):
    """Each column holds a uniform 1..nnz_per_col entries at rows drawn
    with replacement, values N(0, 1/count); labels from a planted sparse
    weight vector through a logistic link. -> (Padded, y (s,) +-1)."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, nnz_per_col + 1, size=n)
    col_rows = np.full((n, nnz_per_col), s, np.int32)
    col_vals = np.zeros((n, nnz_per_col), np.float32)
    mask = np.arange(nnz_per_col)[None, :] < counts[:, None]
    nnz = int(mask.sum())
    col_rows[mask] = rng.integers(0, s, size=nnz)
    scale = 1.0 / np.sqrt(counts.astype(np.float32))
    col_vals[mask] = rng.standard_normal(nnz).astype(np.float32) * \
        np.repeat(scale, counts)
    w_true = np.zeros((n,), np.float32)
    k_w = max(1, int(w_nnz_frac * n))
    sup = rng.choice(n, size=k_w, replace=False)
    w_true[sup] = rng.standard_normal(k_w).astype(np.float32) * 2.0
    z = np.zeros((s,), np.float32)
    np.add.at(z, col_rows[mask], col_vals[mask] * np.repeat(w_true, counts))
    z += noise * rng.standard_normal(s).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-z))
    y = np.where(rng.random(s) < p, 1.0, -1.0).astype(np.float32)
    return Padded(col_rows=col_rows, col_vals=col_vals, shape=(s, n)), y


def dense_classification(s: int, n: int, sparsity: float, corr: float,
                         scale_pm1: bool, seed: int,
                         w_nnz_frac: float = 0.1, noise: float = 0.1):
    """A latent-factor mixture of correlation `corr`, masked to
    `sparsity` zeros, each column scaled to [-1, 1] when `scale_pm1`;
    labels from a planted sparse weight vector through a logistic link.
    -> (X (s, n) float32, y (s,) +-1)."""
    rng = np.random.default_rng(seed)
    k = max(4, n // 64)
    F = rng.standard_normal((k, n)).astype(np.float32) / np.sqrt(k)
    S = rng.standard_normal((s, k)).astype(np.float32)
    X = corr * (S @ F) + (1.0 - corr) * rng.standard_normal(
        (s, n)).astype(np.float32)
    if sparsity > 0:
        X *= rng.random((s, n)) >= sparsity
    if scale_pm1:
        X = X / np.maximum(np.abs(X).max(axis=0, keepdims=True), 1e-12)
    w_true = np.zeros((n,), np.float32)
    nnz = max(1, int(w_nnz_frac * n))
    sup = rng.choice(n, size=nnz, replace=False)
    w_true[sup] = rng.standard_normal(nnz).astype(np.float32) * 2.0
    logits = X @ w_true + noise * rng.standard_normal(s).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-logits))
    y = np.where(rng.random(s) < p, 1.0, -1.0).astype(np.float32)
    return X.astype(np.float32), y


def build(spec: dict, seed: int):
    """-> (X, y): X is a `Padded` layout or a dense float32 array, y is
    (s,) +-1 float32."""
    kind = spec["generator"]
    if kind == "sparse":
        return sparse_classification(spec["s"], spec["n"],
                                     spec["nnz_per_col"], seed)
    if kind == "dense":
        X, y = dense_classification(
            spec["s_generated"], spec["n"], sparsity=spec["sparsity"],
            corr=spec["corr"], scale_pm1=spec["scale_pm1"], seed=seed)
        return X[:spec["s"]], y[:spec["s"]]
    raise ValueError(f"unknown data generator {kind!r}")


def nnz(X) -> int:
    """Stored nonzeros: real entries of a padded layout, every entry of
    a dense design."""
    if isinstance(X, Padded):
        return X.nnz
    return int(X.size)


def dense_rows(X, row_ids: np.ndarray) -> np.ndarray:
    """(len(row_ids), n) float32 copy of the given rows."""
    row_ids = np.asarray(row_ids)
    if not isinstance(X, Padded):
        return np.asarray(X[row_ids], np.float32)
    s, n = X.shape
    where = np.full((s + 1,), -1, np.int64)
    where[row_ids] = np.arange(row_ids.shape[0])
    slot = where[X.col_rows]                     # (n, k): pool row or -1
    cols = np.broadcast_to(np.arange(n)[:, None], slot.shape)
    keep = slot >= 0
    out = np.zeros((row_ids.shape[0], n), np.float32)
    np.add.at(out, (slot[keep], cols[keep]), X.col_vals[keep])
    return out
