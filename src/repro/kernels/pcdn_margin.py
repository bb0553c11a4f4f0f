"""Pallas TPU kernels: batched serving margins over sparse models.

Scoring side of the repo (DESIGN.md section 10.3). A served l1 model is
its active set — indices ``idx`` and values ``val`` of the nonzero
weights, padded to a static width A with sentinel ``idx == n`` — and a
request batch arrives in one of two layouts. Both kernels touch ONLY the
active coordinates of each model, which is where the serving speedup
comes from: work is O(A) per request instead of O(n), and solutions on
the paper's datasets are >= 99% sparse.

The TPU compiler has no gather from, or scatter into, a VMEM array at
arbitrary indices, so each layout's data-dependent addressing runs as
an XLA gather in the wrapper (`kernels/ops.py`) and the kernels do the
contractions:

Dense request layout  — X (B, n) row-major request slab:

    z[b, k] = sum_a val[k, a] * X[b, idx[k, a]]

  The wrapper gathers xg = X[:, idx] as (K, B, A) (sentinel idx == n
  fills 0). Grid (K, B_tiles, A_tiles): each program contracts one
  model's (1, BA) values with its (BB, BA) gathered tile on the MXU,
  accumulating into a resident (1, BB) row of z (constant index map
  along the a axis, the fastest grid axis: zero-init at a == 0).
  block_a=None keeps the whole active width in one tile; tiling caps
  the window for wide models (DESIGN.md section 12). Work is O(B * A)
  per model instead of O(B * n).

Padded-CSC request layout — the repo's feature-major sparse layout
(col_rows/col_vals of the REQUEST matrix, sentinel row id == B):

    z[:, k] = sum_a val[k, a] * X_csc[:, idx[k, a]]     (scatter-add)

  The wrapper gathers each model's active request columns, rows and
  val-scaled values, as E = A * k_max entries per model (sentinel
  models fill row id B and value 0). Grid (K, E_tiles): the scatter-add
  into the (B,) margin row becomes a one-hot contraction on the MXU,
  (1, BE) values against the (B, BE) mask rows == b, accumulated across
  the E tiles — the serving-side mirror of the solver's `slab_matvec`
  bundle update. Work is O(B * A * k_max) per model, independent of n.

Model values and request slabs may arrive in bf16 storage
(mixed-precision serve banks): both kernels upcast INSIDE the kernel,
so every contraction accumulates in f32.

Sentinel handling: model padding slots (idx == n) gather out of bounds
and fill 0 (dense) or fill the dropped row id B (sparse), so padding
contributes exactly nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DEFAULT_BLOCK_B = 128
BLOCK_E = 2048          # request entries per padded-CSC program


def _contract_nt(a: Array, b: Array) -> Array:
    """(1, M) x (N, M)^T -> (1, N) at f32 accuracy on the MXU."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _dense_kernel(xg_ref, val_ref, z_ref):
    a = pl.program_id(2)
    part = _contract_nt(val_ref[...].astype(jnp.float32),
                        xg_ref[...].astype(jnp.float32))   # (1, BB)

    @pl.when(a == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(part)

    z_ref[...] += part


def serve_margins_dense_kernel(xg: Array, val: Array,
                               block_b: int = DEFAULT_BLOCK_B,
                               block_a: int | None = None,
                               interpret: bool = True) -> Array:
    """Raw launch over gathered request columns xg (K, B, A) and model
    values val (K, A), with B % block_b == 0 (block_b a multiple of 8,
    and of 128 unless it is the whole B) and A % block_a == 0 (a
    multiple of 128 unless it is the whole A; None = whole A). Returns
    margins (B, K) float32."""
    K, B, A = xg.shape
    assert B % block_b == 0, (B, block_b)
    ba = A if block_a is None else int(block_a)
    assert A % ba == 0, (A, ba)
    z = pl.pallas_call(
        _dense_kernel,
        grid=(K, B // block_b, A // ba),
        in_specs=[
            pl.BlockSpec((None, block_b, ba), lambda k, j, a: (k, j, a)),
            pl.BlockSpec((None, 1, ba), lambda k, j, a: (k, 0, a)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_b),
                               lambda k, j, a: (k, 0, j)),
        out_shape=jax.ShapeDtypeStruct((K, 1, B), jnp.float32),
        interpret=interpret,
    )(xg, val.reshape(K, 1, A))
    return z.reshape(K, B).T


def _csc_kernel(rows_ref, vals_ref, z_ref):
    e = pl.program_id(1)
    rows = rows_ref[...]                   # (1, BE) request ids
    hit = jax.lax.broadcasted_iota(jnp.int32, (z_ref.shape[-1],
                                               rows.shape[1]), 0) == rows
    part = _contract_nt(vals_ref[...].astype(jnp.float32),
                        hit.astype(jnp.float32))          # (1, B)

    @pl.when(e == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(part)

    z_ref[...] += part


def serve_margins_csc_kernel(rows: Array, vals: Array, n_requests: int,
                             interpret: bool = True) -> Array:
    """Raw launch over each model's gathered request entries: rows (K, E)
    request ids (== n_requests at padding, which matches no request) and
    vals (K, E) val-scaled request values, E % BLOCK_E == 0 or
    E <= BLOCK_E. Returns margins (n_requests, K) float32."""
    K, E = rows.shape
    be = E if E <= BLOCK_E else BLOCK_E
    assert E % be == 0, (E, be)
    Bp = -(-int(n_requests) // 8) * 8
    spec = pl.BlockSpec((None, 1, be), lambda k, e: (k, 0, e))
    z = pl.pallas_call(
        _csc_kernel,
        grid=(K, E // be),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((None, 1, Bp), lambda k, e: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, 1, Bp), jnp.float32),
        interpret=interpret,
    )(rows.reshape(K, 1, E), vals.reshape(K, 1, E))
    return z.reshape(K, Bp)[:, :n_requests].T
