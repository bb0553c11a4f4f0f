"""Batched-margin prediction engine over sparse models (DESIGN.md 10.3).

Serving state is a `ModelBank`: the K models of an artifact family (an
OVR head, a path family, or one binary model) stacked into TWO sparse
layouts built once at load time —

  per-model padded (the Pallas kernel layout):
    idx (K, A_max) int32   active feature ids, sentinel == n_features
    val (K, A_max) float32 matching weights, 0 at padding

  union-compressed (the XLA scorer layout):
    union_idx (U,)   int32 sorted union of every model's active ids
    union_val (K, U) f32   each model's weights restricted to the union

A_max = max_k nnz(w_k) and U = |union|, so bank memory is K * A_max +
K * U, not K * n. Scoring touches ONLY active coordinates of the request
batch, in either request layout:

  * dense  (B, n) slab        -> ONE shared gather X[:, union_idx]
    followed by a (B, U) x (U, K) matmul — the gather (the expensive op
    on every backend) is amortized across all K models instead of paid
    per model;
  * padded-CSC request matrix -> gather the union's request columns
    once, scatter-add per model over request rows (slab_matvec's
    serving twin).

Each scorer has an XLA implementation (jitted; also the fast path on
CPU) and a Pallas kernel route (`use_kernels=True`, the per-model
gather of kernels/pcdn_margin.py); tests pin all four to the dense
matmul ground truth. `decide` turns margins into predictions: argmax
over classes for an OVR bank, sign for binary/path banks.

Dense-layout ROUTING (DESIGN.md 14.6): the union-gather scorer loses to
a plain densified matmul at low weight sparsity / small batch (the CPU
gather cost exceeds the matmul — BENCH_serve.json's scorer table shows
the measured table honestly). `margins_dense(..., route=...)` therefore
offers both: "sparse" (union-gather), "dense" (densified (K, n) matmul,
built lazily and cached on the bank), and "auto", which reads the
measured crossover point (sparsity x batch) recorded by
benchmarks/bench_serve.py under the `route_crossover` key of the
committed BENCH_serve.json and picks the winner per call.

Capacity-padded banks (DESIGN.md 14.5): `a_cap`/`u_cap` pad both
layouts to fixed widths beyond the current models' needs — the serving
loop's hot-swap installs a new model into the SAME shapes, so every
scorer program keyed on bank shapes is reused and steady state never
recompiles. idx padding uses the sentinel `n_features` (the kernels'
existing contract); union padding uses index 0 with zero weight
(always a valid gather, contributes exactly 0).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.design_matrix import PaddedCSCDesign
from repro.kernels import ops
from repro.serve.artifact import ModelFamily

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ModelBank:
    """Stacked sparse layouts of K models sharing n_features.

    `dtype` at build time sets the STORAGE dtype of val/union_val
    (f32 default; bf16 halves bank memory and scorer HBM traffic —
    DESIGN.md section 12). Every scorer upcasts to f32 before its
    contraction, so margins are always f32.
    """

    idx: Array                     # (K, A_max) int32, sentinel == n_features
    val: Array                     # (K, A_max) float, 0 at padding
    union_idx: Array               # (U,) int32 union of active ids
    union_val: Array               # (K, U) float weights on the union
    bias: Array                    # (K,) float32
    n_features: int
    kind: str = "binary"
    loss_name: str = "logistic"
    classes: Optional[np.ndarray] = None   # (K,) vocab for kind="ovr"

    @property
    def n_models(self) -> int:
        return int(self.idx.shape[0])

    @property
    def a_max(self) -> int:
        return int(self.idx.shape[1])

    @property
    def nnz(self) -> np.ndarray:
        return np.asarray(jnp.sum(self.idx < self.n_features, axis=1))

    def sparsity(self) -> float:
        """Mean fraction of zero weights across the bank's models
        (computed once and cached — route="auto" reads it per call)."""
        cached = getattr(self, "_sparsity_cache", None)
        if cached is None:
            cached = 1.0 - float(self.nnz.mean()) / max(self.n_features, 1)
            object.__setattr__(self, "_sparsity_cache", cached)
        return cached

    def dense_matrix(self) -> Array:
        """Densified (K, n) f32 weight stack for the dense-matmul route,
        built lazily from the per-model layout and cached on the bank."""
        W = getattr(self, "_dense_w_cache", None)
        if W is None:
            idx = np.asarray(self.idx)
            val = np.asarray(self.val, np.float32)
            Wn = np.zeros((self.n_models, self.n_features), np.float32)
            live = idx < self.n_features
            rows = np.repeat(np.arange(self.n_models), live.sum(axis=1))
            Wn[rows, idx[live]] = val[live]
            W = jnp.asarray(Wn)
            object.__setattr__(self, "_dense_w_cache", W)
        return W

    @classmethod
    def _build(cls, sparse_rows, bias, n: int, kind: str, loss_name: str,
               classes, dtype=np.float32, a_cap: Optional[int] = None,
               u_cap: Optional[int] = None) -> "ModelBank":
        """sparse_rows: [(indices, values)] per model -> both layouts.

        a_cap / u_cap pad the per-model and union layouts to FIXED widths
        (>= what the models need) so a later model swap at the same caps
        reuses every compiled scorer — see the module docstring.
        """
        K = len(sparse_rows)
        a_max = max(1, max(ii.shape[0] for ii, _ in sparse_rows))
        if a_cap is not None:
            if a_max > int(a_cap):
                raise ValueError(
                    f"bank needs a_max={a_max} active weights per model "
                    f"but the capacity is a_cap={a_cap}")
            a_max = int(a_cap)
        idx = np.full((K, a_max), n, np.int32)
        val = np.zeros((K, a_max), np.float32)
        for k, (ii, vv) in enumerate(sparse_rows):
            idx[k, :ii.shape[0]] = ii
            val[k, :ii.shape[0]] = vv
        union = np.unique(np.concatenate(
            [ii for ii, _ in sparse_rows] or [np.zeros(0, np.int64)]))
        if union.size == 0:
            union = np.zeros((1,), np.int64)    # all-zero bank (c_max point)
        if u_cap is not None:
            if union.shape[0] > int(u_cap):
                raise ValueError(
                    f"bank union has {union.shape[0]} active features but "
                    f"the capacity is u_cap={u_cap}")
        uval = np.zeros((K, union.shape[0]), np.float32)
        for k, (ii, vv) in enumerate(sparse_rows):
            uval[k, np.searchsorted(union, ii)] = vv
        if u_cap is not None and union.shape[0] < int(u_cap):
            # pad with index 0 / weight 0: a valid gather contributing 0
            # (the out-of-range sentinel would gather NaN under jnp.take's
            # default fill mode)
            pad = int(u_cap) - union.shape[0]
            union = np.concatenate([union, np.zeros((pad,), np.int64)])
            uval = np.concatenate([uval, np.zeros((K, pad), np.float32)],
                                  axis=1)
        b = np.zeros((K,), np.float32) if bias is None \
            else np.asarray(bias, np.float32).reshape(K)
        dtype = jnp.dtype(dtype)
        return cls(idx=jnp.asarray(idx), val=jnp.asarray(val, dtype=dtype),
                   union_idx=jnp.asarray(union.astype(np.int32)),
                   union_val=jnp.asarray(uval, dtype=dtype),
                   bias=jnp.asarray(b),
                   n_features=n, kind=kind, loss_name=loss_name,
                   classes=classes)

    @classmethod
    def from_family(cls, family: ModelFamily, dtype=np.float32,
                    a_cap: Optional[int] = None,
                    u_cap: Optional[int] = None) -> "ModelBank":
        rows = [(m.w_indices, m.w_values.astype(np.float32))
                for m in family.models]
        bias = np.asarray([m.bias for m in family.models], np.float32)
        return cls._build(rows, bias, family.n_features, family.kind,
                          family.loss_name, family.classes, dtype=dtype,
                          a_cap=a_cap, u_cap=u_cap)

    @classmethod
    def from_dense(cls, W, bias=None, kind: str = "binary",
                   loss_name: str = "logistic",
                   classes: Optional[np.ndarray] = None,
                   dtype=np.float32, a_cap: Optional[int] = None,
                   u_cap: Optional[int] = None) -> "ModelBank":
        """Stack (K, n) dense solutions (e.g. OVRResult.weights)."""
        W = np.asarray(W, np.float32)
        if W.ndim == 1:
            W = W[None, :]
        rows = [(np.flatnonzero(W[k]), W[k, np.flatnonzero(W[k])])
                for k in range(W.shape[0])]
        return cls._build(rows, bias, W.shape[1], kind, loss_name, classes,
                          dtype=dtype, a_cap=a_cap, u_cap=u_cap)


@jax.jit
def _dense_xla(X, union_idx, union_val, bias):
    """One shared active-union gather, then a small (B, U) x (U, K)
    contraction — the gather cost is paid once for all K models."""
    Xu = jnp.take(X, union_idx, axis=1)
    # bf16 bank storage upcasts here: the contraction accumulates in f32,
    # at f32 accuracy (a TPU's default precision rounds the operands to
    # bf16, which put served margins ~3e-3 off float64 scoring)
    return jnp.dot(Xu, union_val.T.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) + bias[None, :]


@jax.jit
def _matmul_xla(X, W, bias):
    """The densified baseline scorer: z = X @ W.T. Beats the union
    gather at low weight sparsity / small batch (the measured crossover
    table of BENCH_serve.json; route='auto' picks per call)."""
    return jnp.dot(X, W.T, precision=jax.lax.Precision.HIGHEST) + \
        bias[None, :]


# -- dense-layout route selection (the measured crossover) -------------------

# Fallback when no committed BENCH_serve.json is readable: the measured
# full-run crossover of the committed artifact (CPU, K=16, n=32768) —
# union-gather wins from B>=256 at 0.99 sparsity and from B>=64 at
# 0.999; never below 0.99. min_batch_sparse=None means dense always.
DEFAULT_ROUTE_CROSSOVER = (
    {"sparsity": 0.9, "min_batch_sparse": None},
    {"sparsity": 0.99, "min_batch_sparse": 256},
    {"sparsity": 0.999, "min_batch_sparse": 64},
)

_route_lock = threading.Lock()
_route_crossover: Optional[tuple] = None


def _bench_serve_path() -> str:
    # src/repro/serve/predict.py -> repo root (guarded by os.path.exists)
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, os.pardir, os.pardir, os.pardir,
                        "BENCH_serve.json")


def route_crossover() -> tuple:
    """The dense-vs-union-gather crossover table, loaded once from the
    committed BENCH_serve.json (`route_crossover` key, full runs only)
    with DEFAULT_ROUTE_CROSSOVER as the fallback."""
    global _route_crossover
    with _route_lock:
        if _route_crossover is None:
            entries = None
            path = _bench_serve_path()
            try:
                if os.path.exists(path):
                    with open(path) as fh:
                        payload = json.load(fh)
                    if not payload.get("smoke"):
                        entries = payload.get("route_crossover")
            except (OSError, ValueError):
                entries = None          # unreadable artifact -> fallback
            if entries:
                _route_crossover = tuple(
                    {"sparsity": float(e["sparsity"]),
                     "min_batch_sparse": (None
                                          if e.get("min_batch_sparse") is None
                                          else int(e["min_batch_sparse"]))}
                    for e in entries)
            else:
                _route_crossover = DEFAULT_ROUTE_CROSSOVER
        return _route_crossover


def set_route_crossover(entries) -> None:
    """Override (or with None, reset to lazy-loaded) the crossover table
    — tests and benchmarks pin it to make routing deterministic."""
    global _route_crossover
    with _route_lock:
        _route_crossover = None if entries is None else tuple(entries)


def pick_route(sparsity: float, batch: int) -> str:
    """'sparse' (union-gather) or 'dense' (densified matmul) for a bank
    of the given weight sparsity scoring a batch of the given size, per
    the measured crossover table. Conservative outside the measured
    range: sparser-than-measured banks inherit the sparsest entry;
    batches below the measured crossover go dense."""
    best = None
    for e in sorted(route_crossover(), key=lambda e: e["sparsity"]):
        if sparsity >= e["sparsity"]:
            best = e
    if best is None or best["min_batch_sparse"] is None:
        return "dense"
    return "sparse" if batch >= best["min_batch_sparse"] else "dense"


def scorer_cache_sizes() -> dict:
    """Compiled-program counts of the jitted scorers (XLA and Pallas
    routes) + install program — the hot-swap regression tests pin these
    flat across traffic and swaps (a growing cache is a recompile)."""
    sizes = {"dense_xla": _dense_xla._cache_size(),
             "csc_xla": _csc_xla._cache_size(),
             "matmul_xla": _matmul_xla._cache_size(),
             "dense_kernel": ops._margins_dense_pallas._cache_size(),
             "csc_kernel": ops._margins_csc_pallas._cache_size()}
    from repro.serve import loop as _loop   # lazy: loop imports this module
    sizes["install"] = _loop._install._cache_size()
    return sizes


@functools.partial(jax.jit, static_argnames=("n_requests",))
def _csc_xla(col_rows, col_vals, union_idx, union_val, bias, n_requests):
    """Shared gather of the union's request-matrix columns; per-model
    scaled scatter-add over request rows (slab_matvec's serving twin)."""
    rows = jnp.take(col_rows, union_idx, axis=0)          # (U, k_max)
    vals = jnp.take(col_vals.astype(jnp.float32), union_idx, axis=0)

    def one(vk):                                          # (U,) weights
        z = jnp.zeros((n_requests,), jnp.float32)
        return z.at[rows].add(vals * vk[:, None].astype(jnp.float32),
                              mode="drop")

    return jax.vmap(one)(union_val).T + bias[None, :]


def margins_dense(bank: ModelBank, X, use_kernels: bool = False,
                  route: str = "sparse") -> Array:
    """(B, K) margins for a dense (B, n) request slab.

    `route` selects the XLA scorer: "sparse" (union-gather), "dense"
    (densified matmul), or "auto" (measured crossover — see pick_route).
    Ignored with use_kernels=True (the kernel path is per-model gather).
    """
    if not isinstance(X, jax.Array):
        X = jnp.asarray(np.asarray(X), jnp.float32)
    elif X.dtype != jnp.float32:
        X = X.astype(jnp.float32)
    if X.ndim != 2 or X.shape[1] != bank.n_features:
        raise ValueError(f"requests must be (B, {bank.n_features}), got "
                         f"{X.shape}")
    if use_kernels:
        return ops.serve_margins_dense(X, bank.idx, bank.val) + \
            bank.bias[None, :]
    if route == "auto":
        route = pick_route(bank.sparsity(), int(X.shape[0]))
    if route == "dense":
        return _matmul_xla(X, bank.dense_matrix(), bank.bias)
    if route != "sparse":
        raise ValueError(f"unknown dense-layout route {route!r} "
                         "(expected 'sparse', 'dense' or 'auto')")
    return _dense_xla(X, bank.union_idx, bank.union_val, bank.bias)


def margins_padded_csc(bank: ModelBank, requests,
                       use_kernels: bool = False) -> Array:
    """(B, K) margins for a padded-CSC request batch.

    `requests`: a PaddedCSCDesign or a numpy-side data.libsvm.PaddedCSC —
    the feature-major layout of the REQUEST matrix (B rows, n features).
    """
    if isinstance(requests, PaddedCSCDesign):
        rows, vals = requests.col_rows, requests.col_vals
        B, n = requests.shape
    elif all(hasattr(requests, a) for a in ("col_rows", "col_vals",
                                            "shape")):
        rows = jnp.asarray(requests.col_rows)
        vals = jnp.asarray(requests.col_vals, jnp.float32)
        B, n = requests.shape
    else:
        raise TypeError(f"not a padded-CSC request batch: "
                        f"{type(requests).__name__}")
    if n != bank.n_features:
        raise ValueError(f"requests have {n} features, bank has "
                         f"{bank.n_features}")
    if use_kernels:
        return ops.serve_margins_csc(rows, vals, bank.idx, bank.val,
                                     n_requests=int(B)) + bank.bias[None, :]
    return _csc_xla(rows, vals, bank.union_idx, bank.union_val, bank.bias,
                    n_requests=int(B))


def predict(bank: ModelBank, requests, use_kernels: bool = False) -> Array:
    """Margins for either request layout (dispatch on the request type)."""
    if hasattr(requests, "col_rows"):
        return margins_padded_csc(bank, requests, use_kernels=use_kernels)
    return margins_dense(bank, requests, use_kernels=use_kernels)


def decide(bank: ModelBank, margins) -> np.ndarray:
    """Margins -> predictions.

    ovr bank: (B,) class labels by argmax margin; binary bank: (B,) +-1
    by sign (0 counts +1, matching validation_accuracy); path bank:
    (B, K) +-1 per grid point.
    """
    m = np.asarray(margins)
    if bank.kind == "ovr":
        if bank.classes is None:
            raise ValueError("ovr bank without a class vocabulary")
        return np.asarray(bank.classes)[np.argmax(m, axis=1)]
    pred = np.sign(m)
    pred[pred == 0] = 1.0
    if bank.kind == "binary":
        return pred[:, 0]
    return pred
