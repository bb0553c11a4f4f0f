"""Pallas TPU kernel: the fused support-restricted PCDN bundle step.

One launch per bundle replaces the previous 3-kernel + 2 dense-vector
round-trip sequence (sparse direction kernel -> dense (s,) slab_matvec ->
line-search kernel -> dense (s,) z update). Working entirely on the
bundle's row support (DESIGN.md section 11), the kernel:

    1. forms u_R = c * dphi(z_R), v_R = c * d2phi(z_R) at the (r_max,)
       support rows (NOT the (s,) margin vector),
    2. reduces g_j = sum_k u_R[pos_jk] * vals_jk and the Hessian
       diagonal over the (P, k_max) slab,
    3. applies the Eq. 5 soft-threshold epilogue -> d and the Eq. 7
       Armijo decrement Delta,
    4. scatter-adds the support-compressed margin delta
       delta_R = (X_B d_B)[support],
    5. evaluates the Q Armijo candidates on the (Q, r_max) support grid
       (loss + l1 + optional elastic-net parts).

Every intermediate between the slab read and the update emission stays
in VMEM — no HBM round trip of a (P,)-direction or an (s,) margin delta
between launches, which is the section 3.1 "minimize data transfer and
synchronization" argument applied to the whole bundle step.

The TPU compiler has no gather from, or scatter into, a VMEM vector at
arbitrary indices. So the slab enters in its support-dense form: the
wrapper (`kernels/ops.py`) scatters vals at (j, pos_jk) into
xr (P, r_max) and vals^2 into x2r (P, r_max), one XLA scatter-add each
(duplicate entries of a column add, exactly as in the padded-CSC
reductions). Steps 2 and 4 then become MXU contractions:

    g = u_R . xr^T,   h = v_R . x2r^T,   delta_R = d . xr

Work is O(P^2 * k_max + Q * P * k_max) per bundle: independent of the
sample count s.

The candidate axis is TILEABLE (`block_q`, DESIGN.md section 12): with
grid=(Q_tiles,), each program recomputes the cheap deterministic steps
1-4 (bitwise identical across programs — the d / delta / Delta output
blocks have constant index maps and every program writes the same
values) and evaluates only its (block_q, r_max) slice of the candidate
grid, capping the largest VMEM intermediate at block_q * r_max instead
of Q * r_max. block_q is a multiple of 8 or the whole (8-padded) Q. The
first-satisfying-alpha selection runs as a tiny XLA epilogue over the
(Q,) f_deltas.

Slab values may arrive in bf16 storage (mixed-precision mode): the
support-dense operands are built in f32, so all reductions and the
candidate grid accumulate in f32.

The support gather itself (z_R = z[support], y_R = y[support]) runs as
an XLA gather feeding the launch: a VMEM-resident (s,) operand with a
constant index map would reintroduce the O(s) per-launch transfer this
kernel exists to eliminate.

Scalars: `c` is TRACED (SMEM input) so one compiled step serves a whole
regularization-path sweep; l2/sigma/gamma/loss kind are static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

HESSIAN_FLOOR = 1e-12


def _phi(kind: str, z, y):
    if kind == "logistic":
        m = -y * z
        return jnp.maximum(m, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(m)))
    if kind == "squared_hinge":
        return jnp.square(jnp.maximum(0.0, 1.0 - y * z))
    if kind == "squared":
        return 0.5 * jnp.square(z - y)
    raise ValueError(kind)


def _dphi(kind: str, z, y):
    if kind == "logistic":
        return (jax.nn.sigmoid(y * z) - 1.0) * y
    if kind == "squared_hinge":
        return -2.0 * y * jnp.maximum(0.0, 1.0 - y * z)
    if kind == "squared":
        return z - y
    raise ValueError(kind)


def _d2phi(kind: str, z, y):
    if kind == "logistic":
        t = jax.nn.sigmoid(y * z)
        return t * (1.0 - t)
    if kind == "squared_hinge":
        return 2.0 * (y * z < 1.0).astype(z.dtype)
    if kind == "squared":
        return jnp.ones_like(z)
    raise ValueError(kind)


def _contract(a: Array, b: Array, dims) -> Array:
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(xr_ref, x2r_ref, zR_ref, yR_ref, w_ref, alphas_ref, c_ref,
            d_ref, delta_ref, Delta_ref, fd_ref, *,
            kind: str, l2: float, gamma: float):
    z = zR_ref[...]                        # (1, R) support margins
    yv = yR_ref[...]                       # (1, R)
    c = c_ref[0, 0]
    # step 1: per-sample factors at the support rows only
    u = c * _dphi(kind, z, yv)
    v = c * _d2phi(kind, z, yv)
    # step 2: slab reductions over the support-dense slab (P, R)
    xr = xr_ref[...]
    w = w_ref[...]                         # (1, P)
    g = _contract(u, xr, ((1,), (1,))) + l2 * w
    h = jnp.maximum(_contract(v, x2r_ref[...], ((1,), (1,))) + l2,
                    HESSIAN_FLOOR)
    # step 3: Eq. 5 soft-threshold Newton direction + Eq. 7 decrement
    d = jnp.where(g + 1.0 <= h * w, -(g + 1.0) / h,
                  jnp.where(g - 1.0 >= h * w, -(g - 1.0) / h, -w))
    Delta = (jnp.sum(g * d, keepdims=True)
             + gamma * jnp.sum(h * d * d, keepdims=True)
             + jnp.sum(jnp.abs(w + d), keepdims=True)
             - jnp.sum(jnp.abs(w), keepdims=True))
    # step 4: support-compressed margin delta (1, P) . (P, R)
    delta = _contract(d, xr, ((1,), (0,)))
    # step 5: this program's tile of Armijo candidates on the
    # (block_q, R) support grid
    alphas = alphas_ref[...]               # (BQ, 1)
    zq = z + alphas * delta
    lo = c * jnp.sum(_phi(kind, zq, yv) - _phi(kind, z, yv), axis=1,
                     keepdims=True)        # (BQ, 1)
    wq = w + alphas * d                    # (BQ, P)
    f_deltas = (lo + jnp.sum(jnp.abs(wq), axis=1, keepdims=True)
                - jnp.sum(jnp.abs(w), keepdims=True))
    if l2:
        f_deltas = f_deltas + 0.5 * l2 * (
            jnp.sum(jnp.square(wq), axis=1, keepdims=True)
            - jnp.sum(jnp.square(w), keepdims=True))
    # deterministic recompute: every program writes the same d/delta/Delta
    # into the constant-index-map blocks; fd is the per-tile output
    d_ref[...] = d
    delta_ref[...] = delta
    Delta_ref[...] = Delta
    fd_ref[...] = f_deltas


def pcdn_bundle_kernel(
    xr: Array, x2r: Array, z_R: Array, y_R: Array, w_B: Array,
    alphas: Array, c: Array,
    kind: str = "logistic", l2: float = 0.0, sigma: float = 0.01,
    gamma: float = 0.0, block_q: int | None = None, interpret: bool = True,
):
    """Raw launch. xr/x2r (P, R) support-dense slab and its squares,
    P % 8 == 0 and R % 128 == 0; z_R/y_R (R,); w_B (P,); alphas (Q,);
    c a scalar (may be traced). block_q=None runs the whole candidate
    grid in one program; block_q=b (rounded up to a multiple of 8) tiles
    it into ceil(Q/b) programs. Returns (upd_w (P,), upd_z (R,), alpha
    scalar, n_steps int32 scalar) — upd_* already scaled by the accepted
    alpha."""
    P, R = xr.shape
    Q = alphas.shape[0]
    q8 = -(-Q // 8) * 8
    bq = q8 if block_q is None else min(-(-int(block_q) // 8) * 8, q8)
    n_q = -(-Q // bq)
    Qp = n_q * bq
    alphas_f = alphas.astype(jnp.float32)
    # alpha = 0 padding candidates give f_delta = 0; sliced away before
    # the selection epilogue, so they can never be picked
    alphas_p = jnp.pad(alphas_f, (0, Qp - Q))
    kernel = functools.partial(_kernel, kind=kind, l2=float(l2),
                               gamma=float(gamma))
    out_shape = [
        jax.ShapeDtypeStruct((1, P), jnp.float32),     # d
        jax.ShapeDtypeStruct((1, R), jnp.float32),     # delta
        jax.ShapeDtypeStruct((1, 1), jnp.float32),     # Delta
        jax.ShapeDtypeStruct((Qp, 1), jnp.float32),    # f_deltas
    ]
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))  # noqa
    d, delta, Delta, fd = pl.pallas_call(
        kernel,
        grid=(n_q,),
        in_specs=[
            whole((P, R)),                                 # xr
            whole((P, R)),                                 # x2r
            whole((1, R)),                                 # z_R
            whole((1, R)),                                 # y_R
            whole((1, P)),                                 # w_B
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),       # alpha tile
            pl.BlockSpec(memory_space=pltpu.SMEM),         # c (traced)
        ],
        out_specs=[
            whole((1, P)),
            whole((1, R)),
            whole((1, 1)),
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_bytes(P, R, bq)),
        interpret=interpret,
    )(xr.astype(jnp.float32), x2r.astype(jnp.float32),
      z_R.reshape(1, R).astype(jnp.float32),
      y_R.reshape(1, R).astype(jnp.float32),
      w_B.reshape(1, P).astype(jnp.float32),
      alphas_p.reshape(Qp, 1),
      jnp.asarray(c, jnp.float32).reshape(1, 1))
    # selection epilogue: first candidate with f_delta <= sigma*alpha*Delta
    d = d.reshape(P)
    delta = delta.reshape(R)
    Delta = Delta.reshape(())
    f_deltas = fd.reshape(Qp)[:Q]
    ok = f_deltas <= sigma * alphas_f * Delta
    first = jnp.argmax(ok)
    alpha = jnp.where(jnp.any(ok), alphas_f[first], 0.0)
    return (alpha * d, alpha * delta, alpha,
            jnp.asarray(first + 1, jnp.int32))


def _vmem_bytes(P: int, R: int, bq: int) -> int:
    """Scoped VMEM for one program: the double-buffered (P, R) operands,
    their in-kernel temporaries and ~8 live (block_q, R) candidate
    planes, with 2x headroom, at least the 16 MiB default and at most
    the 128 MiB a v5e core has."""
    need = 4 * (6 * P * R + 8 * (bq + 8) * R)
    return int(min(max(2 * need, 16 << 20), 128 << 20))
