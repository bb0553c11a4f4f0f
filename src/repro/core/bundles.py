"""Random Gauss-Seidel bundle partitioning (paper Eq. 8).

Each outer iteration draws a fresh random permutation of the feature set N
and slices it into b = ceil(n / P) disjoint bundles of size P. When P does
not divide n the final bundle is padded with sentinel indices (== n); all
bundle math masks them out, so semantics match the paper's ragged last
bundle exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def num_bundles(n: int, P: int) -> int:
    return -(-n // P)  # ceil


def partition(key: Array, n: int, P: int) -> Array:
    """-> (b, P) int32 bundle indices; entries == n are padding."""
    b = num_bundles(n, P)
    perm = jax.random.permutation(key, n)
    pad = jnp.full((b * P - n,), n, dtype=perm.dtype)
    return jnp.concatenate([perm, pad]).reshape(b, P).astype(jnp.int32)


def partition_active(key: Array, active: Array, P: int) -> tuple[Array, Array]:
    """Active-set partition for the shrinking solver (DESIGN.md section 8.2).

    active: (n,) bool mask of un-shrunk features. Returns (idxs, b_active):
    idxs is the same static (b, P) layout as `partition`, but a fresh
    random permutation is stably reordered so every ACTIVE feature lands
    in the leading ceil(n_active / P) bundles (random order within the
    active block); all inactive/pad slots hold the sentinel n and are
    masked out of bundle math exactly like ragged-tail padding. b_active
    is the dynamic number of leading bundles that contain any work — the
    solver's fori_loop trip count, so shrunk features cost zero compute
    while every shape stays static.
    """
    n = active.shape[0]
    b = num_bundles(n, P)
    perm = jax.random.permutation(key, n)
    order = jnp.argsort(~active[perm], stable=True)   # actives first
    perm = perm[order]
    flat = jnp.where(active[perm], perm, n)
    pad = jnp.full((b * P - n,), n, dtype=flat.dtype)
    idxs = jnp.concatenate([flat, pad]).reshape(b, P).astype(jnp.int32)
    n_active = jnp.sum(active.astype(jnp.int32))
    b_active = (n_active + P - 1) // P
    return idxs, b_active


def gather_slab(X: Array, idx: Array) -> tuple[Array, Array]:
    """Gather the dense (s, P) column slab for one bundle from a raw array.

    idx: (P,) with possible sentinel n. Returns (XB, valid_mask) where
    padded columns are zeroed so they contribute nothing to any reduction.
    Solvers holding an L1Problem go through `L1Problem.gather_slab`
    instead (backend-dispatched — DESIGN.md section 7); this raw-array
    version remains for the sharded dense path, which works on local
    blocks.
    """
    n = X.shape[1]
    valid = idx < n
    safe = jnp.minimum(idx, n - 1)
    XB = jnp.take(X, safe, axis=1)
    XB = XB * valid[None, :].astype(X.dtype)
    return XB, valid


def gather_vec(v: Array, idx: Array) -> tuple[Array, Array]:
    """Gather (P,) entries of a feature-indexed vector with pad masking."""
    n = v.shape[0]
    valid = idx < n
    safe = jnp.minimum(idx, n - 1)
    out = jnp.take(v, safe) * valid.astype(v.dtype)
    return out, valid


def scatter_add(w: Array, idx: Array, upd: Array) -> Array:
    """w[idx] += upd with sentinel-safe drop semantics."""
    return w.at[idx].add(upd, mode="drop")
