"""Public wrappers around the Pallas kernels: autotune-aware dispatchers.

Each wrapper resolves its launch configuration at TRACE time through
`kernels/autotune.resolve` — defaults (the historical hard-coded
launches) <- the persisted autotune cache winner for this
(kernel, shape-bucket, dtype, backend) cell <- explicit per-call
overrides (a caller passing `block_*`/`impl` keeps exact control) — and
then routes to one of two jitted implementations:

  * impl="pallas": the Pallas kernel (padding to tile-aligned shapes
    handled here);
  * impl="xla":    the jnp oracle from `kernels/ref.py` under jit — the
    same contract bit-for-bit at f32, and the measured winner on
    backends where Pallas runs in interpreter mode.

Because dispatch happens where the wrapper is CALLED (eagerly or inside
an outer jit trace), `make_bundle_step`, the sharded backend's kernel
routing and the serving `ModelBank` all pick tuned configs with no code
changes. Set REPRO_AUTOTUNE=off to pin every wrapper to the defaults
(tests/conftest.py does, so kernel-vs-oracle tests always exercise the
Pallas route).

Interpreter mode is controlled by the ``REPRO_KERNELS_INTERPRET`` env
var: "auto" (default) runs compiled kernels on TPU and the interpreter
on the CPU (tests), and refuses any other backend; "1"/"true" forces the
interpreter, "0"/"false" forces compiled kernels. Resolution is lazy
(first kernel trace), so importing
this module never initializes a jax backend and no import-order-
sensitive monkeypatching is needed on real TPU. Assigning the legacy
``repro.kernels.ops.INTERPRET = False`` still works: a non-None value
short-circuits the env lookup.

Also here: the custom_vjp for attention (forward = Pallas, backward =
recompute with the jnp oracle — standard flash recomputation strategy).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import autotune, ref
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.pcdn_bundle import pcdn_bundle_kernel
from repro.kernels.pcdn_direction import pcdn_direction_kernel
from repro.kernels.pcdn_linesearch import pcdn_linesearch_kernel
from repro.kernels.pcdn_margin import (BLOCK_E, serve_margins_csc_kernel,
                                       serve_margins_dense_kernel)
from repro.kernels.pcdn_sparse_direction import pcdn_sparse_direction_kernel

Array = jax.Array

# tri-state: None = resolve from REPRO_KERNELS_INTERPRET / backend on
# first use; assigning True/False here (legacy API) overrides both.
INTERPRET = None


def interpret_mode() -> bool:
    """Resolve (and cache) whether kernels run in interpreter mode."""
    global INTERPRET
    if INTERPRET is None:
        env = os.environ.get("REPRO_KERNELS_INTERPRET", "auto")
        env = env.strip().lower()
        if env in ("auto", ""):
            backend = jax.default_backend()
            if backend not in ("tpu", "cpu"):
                raise RuntimeError(
                    f"the Pallas kernels compile for TPU and are "
                    f"interpreted on the CPU; backend {backend!r} has "
                    f"neither (set REPRO_KERNELS_INTERPRET=1 to interpret)")
            INTERPRET = backend == "cpu"
        else:
            INTERPRET = env not in ("0", "false", "no", "off")
    return INTERPRET


def _launch_span(kernel: str, impl: str, x: Array):
    """Per-launch trace span (DESIGN.md section 13.3) for EAGER kernel
    dispatches only. Inside an outer jit trace the arguments are tracers
    and host timing would measure tracing, not execution — the span is
    suppressed there (the enclosing engine/serve span already covers the
    compiled program). Eager spans measure dispatch; the serving and
    benchmark callers block right after, so nesting under their spans
    stays proper."""
    if (obs.metrics_enabled() or obs.trace_enabled()) \
            and not isinstance(x, jax.core.Tracer):
        obs.inc(f"kernels.{kernel}.launches")
        return obs.span(f"kernels.{kernel}.{impl}")
    return obs.trace._NULL_SPAN


def _lane_tile(block: int | None, size: int) -> int:
    """Tile along an axis that is the lane (last) axis of some block.

    The TPU compiler takes a block dim that is a multiple of 128 lanes or
    the whole padded extent: `block` covering the 8-padded `size` (or
    None) gives that whole extent, anything smaller is rounded up to the
    next multiple of 128."""
    full = -(-size // 8) * 8
    if block is None or block >= full:
        return full
    return min(-(-int(block) // 128) * 128, full)


def _pad_to(x: Array, axis: int, multiple: int, value=0.0) -> Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# pcdn_direction


@functools.partial(jax.jit, static_argnames=("l2", "block_s", "block_p"))
def _direction_pallas(XB: Array, u: Array, v: Array, w_B: Array,
                      l2: float, block_s: int, block_p: int):
    """Pads s and P to tile multiples; padded samples carry u = v = 0 (no
    contribution), padded features get w = 0 / g = 0 -> d = 0 and are
    sliced away."""
    s, P = XB.shape
    bs = min(block_s, max(8, s))
    XBp = _pad_to(_pad_to(XB, 0, bs), 1, block_p)
    up = _pad_to(u, 0, bs)
    vp = _pad_to(v, 0, bs)
    wp = _pad_to(w_B, 0, block_p)
    d, g, h = pcdn_direction_kernel(XBp, up, vp, wp, l2=l2, block_s=bs,
                                    block_p=block_p,
                                    interpret=interpret_mode())
    return d[:P], g[:P], h[:P]


_direction_xla = jax.jit(ref.pcdn_direction_ref, static_argnames=("l2",))


def pcdn_direction(XB: Array, u: Array, v: Array, w_B: Array,
                   l2: float = 0.0, block_s: int | None = None,
                   block_p: int | None = None, impl: str | None = None):
    """Fused bundle direction. XB (s, P) any float dtype -> (d, g, h) (P,)."""
    s, P = XB.shape
    cfg = autotune.resolve(
        "pcdn_direction", autotune.shape_bucket(s=s, p=P), XB.dtype,
        {"impl": impl, "block_s": block_s, "block_p": block_p})
    with _launch_span("pcdn_direction", cfg["impl"], XB):
        if cfg["impl"] == "xla":
            return _direction_xla(XB, u, v, w_B, l2=l2)
        return _direction_pallas(XB, u, v, w_B, l2=l2,
                                 block_s=cfg["block_s"],
                                 block_p=cfg["block_p"])


# ---------------------------------------------------------------------------
# pcdn_sparse_direction


@functools.partial(jax.jit,
                   static_argnames=("l2", "block_p", "block_k"))
def _sparse_direction_pallas(rows: Array, vals: Array, u: Array, v: Array,
                             w_B: Array, l2: float, block_p: int,
                             block_k: int | None):
    """Gathers ug = u[rows], vg = v[rows] in XLA (sentinel rows fill 0),
    then pads P to the tile: padded features carry zero factors and
    w = 0, so g = 0 -> d = 0, and are sliced away."""
    P, K = rows.shape
    bp = _lane_tile(block_p, P)
    ug = jnp.take(u.astype(jnp.float32), rows, mode="fill", fill_value=0)
    vg = jnp.take(v.astype(jnp.float32), rows, mode="fill", fill_value=0)
    ugp, vgp, valsp = (_pad_to(a, 0, bp) for a in (ug, vg, vals))
    wp = _pad_to(w_B, 0, bp)
    d, g, h = pcdn_sparse_direction_kernel(
        ugp, vgp, valsp, wp, l2=l2, block_p=bp,
        block_k=None if block_k is None else _lane_tile(block_k, K),
        interpret=interpret_mode())
    return d[:P], g[:P], h[:P]


_sparse_direction_xla = jax.jit(ref.pcdn_sparse_direction_ref,
                                static_argnames=("l2",))


def pcdn_sparse_direction(rows: Array, vals: Array, u: Array, v: Array,
                          w_B: Array, l2: float = 0.0,
                          block_p: int | None = None,
                          block_k: int | None = None,
                          impl: str | None = None):
    """Fused sparse bundle direction over the padded-CSC slab layout.

    rows/vals (P, k_max) from PaddedCSCDesign.gather_slab -> (d, g, h),
    each (P,) float32. vals may be bf16 storage (in-kernel f32 upcast).
    """
    P, K = rows.shape
    s = u.shape[0]
    cfg = autotune.resolve(
        "pcdn_sparse_direction", autotune.shape_bucket(p=P, k=K, s=s),
        vals.dtype,
        {"impl": impl, "block_p": block_p, "block_k": block_k})
    with _launch_span("pcdn_sparse_direction", cfg["impl"], vals):
        if cfg["impl"] == "xla":
            return _sparse_direction_xla(rows, vals, u, v, w_B, l2=l2)
        return _sparse_direction_pallas(rows, vals, u, v, w_B, l2=l2,
                                        block_p=cfg["block_p"],
                                        block_k=cfg["block_k"])


# ---------------------------------------------------------------------------
# pcdn_linesearch


@functools.partial(jax.jit, static_argnames=("kind", "block_s"))
def _linesearch_pallas(z: Array, delta: Array, y: Array, alphas: Array,
                       kind: str, block_s: int) -> Array:
    """Pads s; padding contributes 0 because z = delta = y = 0 rows give
    phi(z+a*d) - phi(z) = 0."""
    s = z.shape[0]
    bs = min(block_s, max(8, s))
    zp = _pad_to(z, 0, bs)
    dp = _pad_to(delta, 0, bs)
    yp = _pad_to(y, 0, bs)
    return pcdn_linesearch_kernel(zp, dp, yp, alphas, kind=kind,
                                  block_s=bs, interpret=interpret_mode())


_linesearch_xla = jax.jit(ref.pcdn_linesearch_ref,
                          static_argnames=("kind",))


def pcdn_linesearch(z: Array, delta: Array, y: Array, alphas: Array,
                    kind: str = "logistic", block_s: int | None = None,
                    impl: str | None = None) -> Array:
    """Batched candidate loss deltas (Q,)."""
    s = z.shape[0]
    cfg = autotune.resolve(
        "pcdn_linesearch", autotune.shape_bucket(s=s, q=alphas.shape[0]),
        z.dtype, {"impl": impl, "block_s": block_s})
    with _launch_span("pcdn_linesearch", cfg["impl"], z):
        if cfg["impl"] == "xla":
            return _linesearch_xla(z, delta, y, alphas, kind=kind)
        return _linesearch_pallas(z, delta, y, alphas, kind=kind,
                                  block_s=cfg["block_s"])


# ---------------------------------------------------------------------------
# pcdn_bundle


@functools.partial(
    jax.jit, static_argnames=("kind", "l2", "sigma", "gamma", "block_q"))
def _bundle_pallas(vals: Array, pos: Array, z_R: Array, y_R: Array,
                   w_B: Array, alphas: Array, c, kind: str, l2: float,
                   sigma: float, gamma: float, block_q: int | None):
    """Scatters the slab into its support-dense form xr[j, pos_jk] +=
    vals_jk (and x2r with vals^2) in XLA, padding P to 8 and r_max to
    128: padded features carry xr = 0 and w = 0 (d = 0, no l1/Delta
    contribution), padded support slots z = 0 / y = 1 / delta = 0 (loss
    delta exactly 0)."""
    P, _ = vals.shape
    R = z_R.shape[0]
    Pp, Rp = -(-P // 8) * 8, -(-R // 128) * 128
    v32 = vals.astype(jnp.float32)
    cols = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[:, None],
                            pos.shape)
    xr = jnp.zeros((Pp, Rp), jnp.float32).at[cols, pos].add(v32)
    x2r = jnp.zeros((Pp, Rp), jnp.float32).at[cols, pos].add(v32 * v32)
    upd_w, upd_z, alpha, q = pcdn_bundle_kernel(
        xr, x2r, _pad_to(z_R, 0, 128), _pad_to(y_R, 0, 128, value=1.0),
        _pad_to(w_B, 0, 8), alphas, c, kind=kind, l2=l2, sigma=sigma,
        gamma=gamma, block_q=block_q, interpret=interpret_mode())
    return upd_w[:P], upd_z[:R], alpha, q


_bundle_xla = jax.jit(ref.pcdn_bundle_ref,
                      static_argnames=("kind", "l2", "sigma", "gamma"))


def pcdn_bundle(vals: Array, pos: Array, z_R: Array, y_R: Array,
                w_B: Array, alphas: Array, c,
                kind: str = "logistic", l2: float = 0.0,
                sigma: float = 0.01, gamma: float = 0.0,
                block_q: int | None = None, impl: str | None = None):
    """Fused support-restricted bundle step (DESIGN.md section 11).

    vals/pos (P, k_max) from `PaddedCSCDesign.gather_slab` +
    `slab_row_support`; z_R/y_R (r_max,) margins and labels gathered at
    the support rows (sentinel slots: z = 0, y = 1); alphas (Q,); `c`
    may be a traced scalar (path sweeps). vals may be bf16 storage
    (in-kernel f32 upcast). Returns (upd_w (P,), upd_z (r_max,), alpha,
    n_steps) with upd_* pre-scaled by the accepted alpha — the caller
    only scatters them at the bundle indices / support rows.

    The launch holds the support-dense slab (P, r_max) in VMEM; the
    default single program also keeps the whole (Q, r_max) candidate
    grid there, and a tuned block_q tiles the candidate axis
    (kernels/pcdn_bundle).
    """
    P, K = vals.shape
    cfg = autotune.resolve(
        "pcdn_bundle",
        autotune.shape_bucket(p=P, k=K, r=z_R.shape[0], q=alphas.shape[0]),
        vals.dtype, {"impl": impl, "block_q": block_q})
    with _launch_span("pcdn_bundle", cfg["impl"], vals):
        if cfg["impl"] == "xla":
            return _bundle_xla(vals, pos, z_R, y_R, w_B, alphas, c,
                               kind=kind, l2=l2, sigma=sigma, gamma=gamma)
        return _bundle_pallas(vals, pos, z_R, y_R, w_B, alphas, c,
                              kind=kind, l2=l2, sigma=sigma, gamma=gamma,
                              block_q=cfg["block_q"])


# ---------------------------------------------------------------------------
# serving margins


@functools.partial(jax.jit, static_argnames=("block_b", "block_a"))
def _margins_dense_pallas(X: Array, idx: Array, val: Array, block_b: int,
                          block_a: int | None) -> Array:
    """Gathers xg = X[:, idx] in XLA (sentinel columns fill 0) as
    (K, B, A), padding B with zero rows (margins sliced away) and A with
    zero values."""
    B, _ = X.shape
    K, A = idx.shape
    bb = _lane_tile(block_b, B)
    ba = _lane_tile(block_a, A)
    xg = jnp.take(_pad_to(X, 0, bb), idx.reshape(-1), axis=1, mode="fill",
                  fill_value=0)
    xg = _pad_to(xg.reshape(-1, K, A).transpose(1, 0, 2), 2, ba)
    z = serve_margins_dense_kernel(xg, _pad_to(val, 1, ba), block_b=bb,
                                   block_a=ba, interpret=interpret_mode())
    return z[:B]


_margins_dense_xla = jax.jit(ref.serve_margins_dense_ref)


def serve_margins_dense(X: Array, idx: Array, val: Array,
                        block_b: int | None = None,
                        block_a: int | None = None,
                        impl: str | None = None) -> Array:
    """Serving margins over a dense request slab (DESIGN.md section 10.3).

    X (B, n), idx/val (K, A) stacked model active sets with sentinel
    idx == n -> (B, K) float32. X and val may be bf16 storage.
    """
    B, n = X.shape
    K, A = idx.shape
    cfg = autotune.resolve(
        "serve_margins_dense", autotune.shape_bucket(b=B, n=n, k=K, a=A),
        val.dtype, {"impl": impl, "block_b": block_b, "block_a": block_a})
    with _launch_span("serve_margins_dense", cfg["impl"], X):
        if cfg["impl"] == "xla":
            return _margins_dense_xla(X, idx, val)
        return _margins_dense_pallas(X, idx, val, block_b=cfg["block_b"],
                                     block_a=cfg["block_a"])


@functools.partial(jax.jit, static_argnames=("n_requests",))
def _margins_csc_pallas(col_rows: Array, col_vals: Array, idx: Array,
                        val: Array, n_requests: int) -> Array:
    """Gathers each model's active request columns in XLA: sentinel
    models fill row id n_requests (matches no request) and value 0."""
    K, A = idx.shape
    rows = jnp.take(col_rows, idx, axis=0, mode="fill",
                    fill_value=n_requests)                 # (K, A, k_max)
    vals = jnp.take(col_vals.astype(jnp.float32), idx, axis=0,
                    mode="fill", fill_value=0.0) * \
        val.astype(jnp.float32)[:, :, None]
    rows, vals = rows.reshape(K, -1), vals.reshape(K, -1)
    if rows.shape[1] > BLOCK_E:
        rows = _pad_to(rows, 1, BLOCK_E, value=n_requests)
        vals = _pad_to(vals, 1, BLOCK_E)
    return serve_margins_csc_kernel(rows, vals, n_requests=n_requests,
                                    interpret=interpret_mode())


_margins_csc_xla = jax.jit(ref.serve_margins_csc_ref,
                           static_argnames=("n_requests",))


def serve_margins_csc(col_rows: Array, col_vals: Array, idx: Array,
                      val: Array, n_requests: int,
                      impl: str | None = None) -> Array:
    """Serving margins over a padded-CSC request batch.

    col_rows/col_vals (n, k_max) feature-major request layout (sentinel
    row id == n_requests), idx/val (K, A) -> (n_requests, K) float32.
    col_vals/val may be bf16 storage.
    """
    n, k_max = col_rows.shape
    K, A = idx.shape
    cfg = autotune.resolve(
        "serve_margins_csc",
        autotune.shape_bucket(n=n, kmax=k_max, k=K, a=A, b=n_requests),
        val.dtype, {"impl": impl})
    with _launch_span("serve_margins_csc", cfg["impl"], col_vals):
        if cfg["impl"] == "xla":
            return _margins_csc_xla(col_rows, col_vals, idx, val,
                                    n_requests=n_requests)
        return _margins_csc_pallas(col_rows, col_vals, idx, val,
                                   n_requests=n_requests)


# ---------------------------------------------------------------------------
# attention with flash forward + recompute backward


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q: Array, k: Array, v: Array, causal: bool = True,
                    sm_scale: float | None = None) -> Array:
    """q: (BH, Sq, D), k/v: (BH, Skv, D) -> (BH, Sq, D)."""
    return _flash_fwd_impl(q, k, v, causal, sm_scale)


def _flash_fwd_impl(q, k, v, causal, sm_scale):
    BH, Sq, D = q.shape
    Skv = k.shape[1]
    bq = min(128, max(8, Sq))
    bk = min(128, max(8, Skv))
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    # padded kv columns must not attend: causal mask handles the q side;
    # for kv we rely on padded k rows producing score 0*scale at m==0 —
    # instead mask explicitly by pushing padded keys to -inf via a huge
    # negative first component trick is brittle, so pad k with zeros and
    # mask via length: simplest correct route is slicing when no padding
    # was needed, else fall back to masked reference.
    if qp.shape[1] != Sq or kp.shape[1] != Skv:
        return ref.attention_ref(q, k, v, causal=causal, sm_scale=sm_scale)
    out = flash_attention_kernel(qp, kp, vp, causal=causal,
                                 sm_scale=sm_scale, block_q=bq, block_k=bk,
                                 interpret=interpret_mode())
    return out[:, :Sq]


def _flash_fwd(q, k, v, causal, sm_scale):
    return _flash_fwd_impl(q, k, v, causal, sm_scale), (q, k, v)


def _flash_bwd(causal, sm_scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=causal,
                                             sm_scale=sm_scale), q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
