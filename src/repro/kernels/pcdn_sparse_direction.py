"""Pallas TPU kernel: fused PCDN bundle direction, padded-CSC layout.

Sparse sibling of kernels/pcdn_direction (DESIGN.md section 7.3). For a
bundle's padded column slab — rows (P, k_max) int32 with sentinel == s at
padding slots, vals (P, k_max) float — and per-sample factors
u = c*dphi/dz, v = c*d2phi/dz2 this computes, in ONE pass over the slab:

    g_j = sum_k u[rows_jk] * vals_jk          (bundle gradient, Eq. 12)
    h_j = max(sum_k v[rows_jk] * vals_jk^2, nu)
    d_j = Eq. 5 soft-threshold Newton direction

The slab is read once; both reductions and the elementwise epilogue run
out of VMEM. Work is O(P * k_max) instead of the dense kernel's O(s * P)
— the entire point of the sparse backend.

Grid = (P_tiles, K_tiles): each program owns (BP, BK) tiles of the slab
and of the gathered factors ug = u[rows], vg = v[rows]. The TPU compiler
has no gather from a VMEM vector at arbitrary indices, so the gather
runs as an XLA gather in the wrapper (`kernels/ops.py`) and the kernel
receives the gathered (P, k_max) tiles; sentinel rows (== s, out of
bounds) fill 0 there, so they contribute exactly 0 to both reductions.
The k axis is tileable (`block_k`, DESIGN.md section 12; a multiple of
128 or the whole k_max): the g/h output blocks are resident across the
inner k loop (constant index map in k, the fastest grid axis),
zero-initialized at k == 0, accumulated per tile, and finalized (l2
fold, Hessian floor, Eq. 5 direction) at the last k tile. The per-row
sums run as (1, BK) x (BP, BK)^T contractions so that g and h land
lane-major in their (1, BP) output blocks. Slab values may arrive in
bf16 storage: upcast in-kernel, all accumulation in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_BLOCK_P = 128
HESSIAN_FLOOR = 1e-12


def _row_sums(x: Array) -> Array:
    """(BP, BK) -> (1, BP) row sums, lane-major, on the MXU."""
    ones = jnp.ones((1, x.shape[1]), jnp.float32)
    return jax.lax.dot_general(ones, x, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(ug_ref, vg_ref, vals_ref, w_ref, l2_ref,
            d_ref, g_ref, h_ref, *, n_k: int):
    j = pl.program_id(1)
    vals = vals_ref[...].astype(jnp.float32)   # (BP, BK)
    g_part = _row_sums(ug_ref[...] * vals)     # (1, BP)
    h_part = _row_sums(vg_ref[...] * vals * vals)

    @pl.when(j == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_part)
        h_ref[...] = jnp.zeros_like(h_part)

    g_ref[...] += g_part
    h_ref[...] += h_part

    @pl.when(j == n_k - 1)
    def _finalize():
        w = w_ref[...]                          # (1, BP)
        l2 = l2_ref[0, 0]
        g = g_ref[...] + l2 * w
        h = jnp.maximum(h_ref[...] + l2, HESSIAN_FLOOR)
        # Eq. 5 closed form
        d_neg = -(g + 1.0) / h
        d_pos = -(g - 1.0) / h
        d_ref[...] = jnp.where(g + 1.0 <= h * w, d_neg,
                               jnp.where(g - 1.0 >= h * w, d_pos, -w))
        g_ref[...] = g
        h_ref[...] = h


def pcdn_sparse_direction_kernel(
    ug: Array, vg: Array, vals: Array, w_B: Array,
    l2: float = 0.0,
    block_p: int = DEFAULT_BLOCK_P,
    block_k: int | None = None,
    interpret: bool = True,
):
    """Raw kernel launch over the gathered factors ug = u[rows],
    vg = v[rows] (P, K) float32 and the slab values (P, K), with
    P % block_p == 0 (block_p a multiple of 8, and of 128 unless it is
    the whole P). block_k=None reduces the whole k_max axis in one tile;
    block_k=b (a multiple of 128) tiles it, zero-padding K here, so the
    padding contributes 0. Returns (d, g, h), each (P,) float32.
    """
    P, K = vals.shape
    assert P % block_p == 0, (P, block_p)
    bk = K if block_k is None or block_k >= K else int(block_k)
    n_k = -(-K // bk)
    Kp = n_k * bk
    if Kp != K:
        pad = ((0, 0), (0, Kp - K))
        ug, vg, vals = (jnp.pad(a, pad) for a in (ug, vg, vals))
    n_p = P // block_p
    w2 = w_B.reshape(1, P).astype(jnp.float32)
    l2a = jnp.full((1, 1), l2, jnp.float32)

    tile = pl.BlockSpec((block_p, bk), lambda i, j: (i, j))
    row = pl.BlockSpec((1, block_p), lambda i, j: (0, i))
    out_shape = [jax.ShapeDtypeStruct((1, P), jnp.float32)] * 3
    d, g, h = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(n_p, n_k),
        in_specs=[tile, tile, tile, row,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],   # l2
        out_specs=[row, row, row],
        out_shape=out_shape,
        interpret=interpret,
    )(ug.astype(jnp.float32), vg.astype(jnp.float32), vals, w2, l2a)
    return d.reshape(P), g.reshape(P), h.reshape(P)
