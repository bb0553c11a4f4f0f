"""PCDN — Parallel Coordinate Descent Newton (paper Algorithm 3).

Outer iteration k:
  1. randomly partition N into b = ceil(n/P) bundles          (Eq. 8)
  2. for each bundle B^t sequentially (Gauss-Seidel):
     a. P one-dimensional Newton directions in parallel       (Eq. 4/5/10)
     b. one P-dimensional Armijo line search along d^t        (Eq. 6/11)
     c. w += alpha d ;  z += alpha * X_B d_B                  (Alg. 4 step 5)

CDN (Yuan et al. 2010) is exactly this solver with P=1 (`cdn_config`).

The inner loop is a single `lax.scan` over bundles, so one outer iteration
is one XLA computation; per-sample intermediates z live in the carry, which
is the paper's "maintain e^{w.x_i}" technique (section 3.1) in z-space.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import bundles as B
from repro.core.design_matrix import PaddedCSCDesign, SparseSlab
from repro.core.direction import delta_decrement, newton_direction
from repro.core.linesearch import (ArmijoParams, armijo_backtracking,
                                   armijo_batched, armijo_chunked,
                                   armijo_support, candidate_alphas)
from repro.core.problem import L1Problem
# history/result containers + the host convergence loop live in the
# engine layer now (DESIGN.md section 9); re-exported here for compat.
from repro.engine.loop import SolveHistory, SolveResult  # noqa: F401

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PCDNConfig:
    P: int                       # bundle size == degree of parallelism
    armijo: ArmijoParams = ArmijoParams()
    max_outer: int = 200
    tol_kkt: float = 1e-3        # stop when KKT violation <= tol_kkt
    tol_rel_obj: float = 0.0     # optional: stop when F <= (1+tol)(F*) given f_star
    ls_kind: str = "batched"     # "batched" (TPU-native) | "backtracking" (faithful)
    # -- line-search / margin-maintenance scope (DESIGN.md section 11) -------
    # "support": restrict the candidate grid, the u/v factors and the z
    #   update to the bundle's row support — O(P * k_max * Q) per bundle
    #   instead of O(s * Q). padded_csc layout only.
    # "full": evaluate over all s samples (the pre-support behavior; the
    #   batched variant now runs chunked with early exit).
    # "auto": support when the layout is padded_csc AND the margin rule
    #   AUTO_SUPPORT_MARGIN * P * k_max <= s holds (resolve_ls_scope).
    ls_scope: str = "auto"
    ls_chunk: int = 8            # candidate chunk of the full-scope search
    seed: int = 0
    use_kernels: bool = False    # route bundle math through Pallas kernels
    # -- mixed precision (DESIGN.md section 12) ------------------------------
    # storage dtype of the DESIGN VALUES ("float32" | "bfloat16"); solver
    # state (w, z, y) stays f32 either way — every reduction accumulates
    # in f32. Recorded here so artifacts/benchmarks can report it; the
    # design matrix itself is built with this dtype by launch/common.
    dtype: str = "float32"
    # -- active-set shrinking (CDN heritage; DESIGN.md section 8.2) ----------
    shrink: bool = False         # mask near-optimal zero features out of bundles
    shrink_tol: float = 0.01     # shrink j when w_j == 0 and |g_j| < 1 - shrink_tol
    recheck_every: int = 1       # full-set KKT recheck period (un-shrinks violators)
    # -- observability (DESIGN.md section 13.2) ------------------------------
    # surface per-bundle line-search telemetry (backtrack depth q^t and
    # accepted alpha) as a 10th outer output: (q (b,) int32, alpha (b,)).
    # Off by default so the compiled iteration is byte-identical to the
    # uninstrumented solver; the engine host loop folds the arrays into
    # SolveHistory.bundle_q / bundle_alpha at its per-iteration sync.
    record_aux: bool = False
    # -- diagnostics (DESIGN.md section 15.1) --------------------------------
    # surface the per-feature KKT violation vector (n,) as an extra outer
    # output for attribution (top-k offenders, distribution, churn). The
    # vector is already computed for the stop criterion, so the marginal
    # device cost is one (n,) transfer per iteration. Same contract as
    # record_aux: off by default, compiled step byte-identical when off.
    # The engine host loop dispatches extra outputs structurally — a
    # 2-tuple is the (q, alpha) bundle aux, a bare array is this vector —
    # so the two flags compose in any combination.
    record_kkt_vec: bool = False


def cdn_config(**kw) -> PCDNConfig:
    """CDN = PCDN with bundle size 1 (paper section 2.1)."""
    kw.setdefault("ls_kind", "backtracking")
    return PCDNConfig(P=1, **kw)


def with_bundle_size(cfg: PCDNConfig, P: int) -> PCDNConfig:
    """`cfg` at a different bundle size, everything else identical — the
    backend-rebuild hook the fault layer's P-backoff uses (DESIGN.md
    section 16.3)."""
    return dataclasses.replace(cfg, P=int(P))


def _line_search_fn(cfg: PCDNConfig) -> Callable:
    if cfg.ls_kind == "batched":
        # full-scope batched search runs chunked with early exit so the
        # (Q, s) candidate grid is never materialized (DESIGN.md §3.2)
        return functools.partial(armijo_chunked, chunk=cfg.ls_chunk)
    if cfg.ls_kind == "backtracking":
        return armijo_backtracking
    raise ValueError(f"unknown ls_kind {cfg.ls_kind!r}")


AUTO_SUPPORT_MARGIN = 4  # auto picks support iff MARGIN * P * k_max <= s


def resolve_ls_scope(cfg: PCDNConfig, problem: L1Problem) -> str:
    """Static scope decision (DESIGN.md section 11.3).

    "support" needs the padded_csc layout (a dense slab has no
    compressed row support); "auto" additionally requires the static
    support bound to beat the sample count with margin —
    AUTO_SUPPORT_MARGIN * P * k_max <= s. The margin covers the
    support build (a sort over P * k_max ids) and the gathers: the
    BENCH_bundle.json grid measures the crossover near r_max ~ s/4
    (support wins 4.5x at r_max/s ~ 0.06, loses ~0.7x at ~0.6).
    Force `ls_scope="support"` to override near the boundary.
    """
    if cfg.ls_scope == "full":
        return "full"
    sparse = isinstance(problem.design, PaddedCSCDesign)
    if cfg.ls_scope == "support":
        if not sparse:
            raise ValueError(
                "ls_scope='support' requires the padded_csc design "
                "backend; the dense layout has no compressed row support "
                "(use layout='padded_csc' or ls_scope='full'/'auto').")
        return "support"
    if cfg.ls_scope != "auto":
        raise ValueError(f"unknown ls_scope {cfg.ls_scope!r}")
    if sparse and (AUTO_SUPPORT_MARGIN * cfg.P * problem.design.k_max
                   <= problem.n_samples):
        return "support"
    return "full"


def make_bundle_step(problem: L1Problem, cfg: PCDNConfig):
    """One inner iteration t (steps 6-11 of Algorithm 3) as a scan body.

    Two shapes of the same update (identical accepted alpha; pinned by
    tests/test_bundle_support.py):

    * full scope — direction over the slab, dense (s,) margin delta,
      line search over all samples, dense z update.
    * support scope (DESIGN.md section 11) — every per-sample pass
      (u/v factors, candidate grid, z update) restricted to the
      bundle's <= P * k_max row support, so one bundle step is
      O(P * k_max * Q) and solve time stops scaling with s. With
      use_kernels the whole support step is ONE fused Pallas launch
      (kernels/pcdn_bundle).

    Both shapes name their phases with `jax.named_scope`, which only
    changes op metadata (the device trace attributes each operation to
    its phase): `pcdn.direction` (slab and w_B gathers, row support, g,
    h, d, Delta), `pcdn.linesearch` (X_B d and the Armijo search) and
    `pcdn.update` (the w and z updates).
    """
    loss = problem.loss
    gamma = cfg.armijo.gamma
    scope = resolve_ls_scope(cfg, problem)

    if cfg.use_kernels:
        from repro.kernels import ops as kops

    if scope == "support":
        design = problem.design
        fuse = cfg.use_kernels and cfg.ls_kind == "batched"

        def step(carry, idx):
            w, z = carry
            with jax.named_scope("pcdn.direction"):
                slab = design.gather_slab(idx)
                w_B, _ = B.gather_vec(w, idx)
                support, pos = design.slab_row_support(slab)
                z_R = jnp.take(z, support, mode="fill", fill_value=0)
                y_R = jnp.take(problem.y, support, mode="fill",
                               fill_value=1)
            if fuse:
                # the fused kernel computes direction, line search and
                # the support-local updates in ONE launch: it is named
                # as the line search, the phase that dominates it
                with jax.named_scope("pcdn.linesearch"):
                    upd_w, upd_z, alpha, n_steps = kops.pcdn_bundle(
                        slab.vals, pos, z_R, y_R, w_B,
                        candidate_alphas(cfg.armijo, z.dtype), problem.c,
                        kind=problem.loss_name, l2=problem.elastic_net_l2,
                        sigma=cfg.armijo.sigma, gamma=gamma)
                with jax.named_scope("pcdn.update"):
                    w = B.scatter_add(w, idx, upd_w)
                    z = design.scatter_support(z, support, upd_z)
                return (w, z), (n_steps, alpha)
            with jax.named_scope("pcdn.direction"):
                if cfg.use_kernels:
                    # backtracking search: no fused step, but the
                    # direction still routes through the sparse kernel —
                    # pos is the support-local row array, u/v handed
                    # over in support order (same composition as the
                    # sharded backend)
                    u_R = problem.grad_factor_at(z_R, y_R)
                    v_R = problem.hess_factor_at(z_R, y_R)
                    d, g, h = kops.pcdn_sparse_direction(
                        pos, slab.vals, u_R, v_R, w_B,
                        l2=problem.elastic_net_l2)
                else:
                    g, h = problem.bundle_grad_hess_support(slab, pos, z_R,
                                                            y_R, w_B)
                    d = newton_direction(g, h, w_B)
                Delta = delta_decrement(g, h, w_B, d, gamma)
            with jax.named_scope("pcdn.linesearch"):
                delta_R = design.slab_matvec_support(slab, pos, d)
                ls_fn = (armijo_support if cfg.ls_kind == "batched"
                         else armijo_backtracking)
                res = ls_fn(loss, problem.c, z_R, delta_R, y_R, w_B, d,
                            Delta, cfg.armijo, l2=problem.elastic_net_l2)
            with jax.named_scope("pcdn.update"):
                w = B.scatter_add(w, idx, res.alpha * d)
                z = design.scatter_support(z, support, res.alpha * delta_R)
            return (w, z), (res.n_steps, res.alpha)

        return step

    ls = _line_search_fn(cfg)

    def step(carry, idx):
        w, z = carry
        with jax.named_scope("pcdn.direction"):
            slab = problem.gather_slab(idx)
            w_B, _ = B.gather_vec(w, idx)
            if cfg.use_kernels:
                u = problem.grad_factor(z)
                v = problem.hess_factor(z)
                if isinstance(slab, SparseSlab):
                    d, g, h = kops.pcdn_sparse_direction(
                        slab.rows, slab.vals, u, v, w_B,
                        l2=problem.elastic_net_l2)
                else:
                    d, g, h = kops.pcdn_direction(
                        slab.XB, u, v, w_B, l2=problem.elastic_net_l2)
            else:
                g, h = problem.bundle_grad_hess(z, slab, w_B)
                d = newton_direction(g, h, w_B)
            Delta = delta_decrement(g, h, w_B, d, gamma)
        with jax.named_scope("pcdn.linesearch"):
            delta_z = problem.design.slab_matvec(slab, d)
            res = ls(loss, problem.c, z, delta_z, problem.y, w_B, d, Delta,
                     cfg.armijo, l2=problem.elastic_net_l2)
        with jax.named_scope("pcdn.update"):
            w = B.scatter_add(w, idx, res.alpha * d)
            z = z + res.alpha * delta_z
        return (w, z), (res.n_steps, res.alpha)

    return step


def jit_over_problem(fn: Callable, problem: L1Problem) -> Callable:
    """`fn(problem, *args)` jitted with the problem as an ARGUMENT and
    bound with functools.partial -> a callable of `*args`.

    A jitted closure over the problem would embed its arrays (the whole
    design matrix) in the executable as constants: on a v5e that made
    the gisette outer step a 534 MB executable, too large for the
    persistent compilation cache, recompiled by every process."""
    return functools.partial(jax.jit(fn), problem)


def make_outer_iteration(problem: L1Problem, cfg: PCDNConfig):
    """Legacy static-c outer iteration (all b bundles) + diagnostics.

    Kept for microbenchmarks that time one bare iteration (e.g.
    benchmarks/bench_sparse.py). Solver entry points go through the
    engine layer instead: `repro.engine.local.LocalBackend` wraps
    `make_path_outer`, whose traced-c contract subsumes this one.
    """
    def outer(problem: L1Problem, w: Array, z: Array, key: Array):
        step = make_bundle_step(problem, cfg)
        key, sub = jax.random.split(key)
        idxs = B.partition(sub, problem.n_features, cfg.P)  # (b, P)
        (w, z), (steps, alphas) = jax.lax.scan(step, (w, z), idxs)
        f = problem.objective_from_margins(z, w)           # incl. l2 term
        kkt = problem.kkt_violation(w, z)
        nnz = jnp.sum(w != 0)
        return w, z, key, f, kkt, nnz, jnp.mean(steps.astype(jnp.float32))

    return jit_over_problem(outer, problem)


def make_path_outer(problem: L1Problem, cfg: PCDNConfig):
    """The local backend's engine iteration (DESIGN.md sections 8 / 9.2).

    Implements the engine's outer-iteration contract
    (`repro.engine.loop`): a single jitted function reused across every
    path point and shrink state — none of the quantities that vary along
    a λ-sweep is baked in:

        outer(w, z, key, active, recheck, c)
          -> (w, z, key, f, kkt, nnz, mean_q, active, n_active)

    * `c` is a traced scalar (problem.with_c substitution), so a 20-point
      c-grid compiles ONCE instead of 20 times.
    * `active` is the (n,) un-shrunk mask. Bundles are drawn from the
      active set only (bundles.partition_active) and the bundle loop is a
      fori_loop with the dynamic trip count ceil(n_active / P): shrunk
      features keep their slots (static shapes) but cost zero compute.
    * `kkt` is always the FULL-set violation — the full gradient is
      already needed for the stop criterion, so the shrink bookkeeping is
      free. Shrinking masks j when w_j == 0 and |g_j| < 1 - shrink_tol
      (strictly interior to the l1 subdifferential box, per CDN's
      shrinking heritage); when `recheck` is set, any feature whose
      violation exceeds tol_kkt is un-shrunk again, so a wrongly shrunk
      feature survives at most recheck_every outer iterations.

    With cfg.shrink=False the active mask passes through untouched and
    the bundle loop covers the full feature set — the scan-based
    make_outer_iteration and this function then compute the same update
    (modulo the independent random partition draw).

    With cfg.record_aux=True a 10th output `(q (b,), alpha (b,))` carries
    the per-bundle backtrack depth and accepted step of this iteration
    (DESIGN.md section 13.2). Under shrinking, slots past the dynamic
    bundle count b_active hold sentinels q == -1 / alpha == nan.

    With cfg.record_kkt_vec=True the per-feature violation vector (n,)
    is appended after the optional aux tuple (DESIGN.md section 15.1);
    the engine dispatches extras by structure (tuple vs bare array), so
    both flags compose.
    """
    def outer(problem: L1Problem, w: Array, z: Array, key: Array,
              active: Array, recheck: Array, c: Array):
        n = problem.n_features
        prob = problem.with_c(c)
        step = make_bundle_step(prob, cfg)
        key, sub = jax.random.split(key)
        if cfg.shrink:
            idxs, b_active = B.partition_active(sub, active, cfg.P)
            if cfg.record_aux:
                # preallocated sentinel slots: a bundle past the dynamic
                # trip count b_active never runs and keeps q=-1/alpha=nan
                b_max = idxs.shape[0]
                aux0 = (jnp.full((b_max,), -1, jnp.int32),
                        jnp.full((b_max,), jnp.nan, w.dtype))
            else:
                aux0 = ()

            def body(t, carry):
                (w, z), q_sum, aux = carry
                (w, z), (q, alpha) = step((w, z), idxs[t])
                if cfg.record_aux:
                    aux = (aux[0].at[t].set(q.astype(jnp.int32)),
                           aux[1].at[t].set(alpha.astype(w.dtype)))
                return (w, z), q_sum + q.astype(jnp.float32), aux

            (w, z), q_sum, aux = jax.lax.fori_loop(
                0, b_active, body, ((w, z), jnp.float32(0.0), aux0))
            if cfg.record_aux:
                qs, alphas = aux
            mean_q = q_sum / jnp.maximum(b_active, 1).astype(jnp.float32)
        else:
            idxs = B.partition(sub, n, cfg.P)
            (w, z), (steps, alphas) = jax.lax.scan(step, (w, z), idxs)
            mean_q = jnp.mean(steps.astype(jnp.float32))
            if cfg.record_aux:
                qs = steps.astype(jnp.int32)
                alphas = alphas.astype(w.dtype)

        # objective, full gradient, KKT violation and the shrink update
        with jax.named_scope("pcdn.kkt"):
            f = prob.objective_from_margins(z, w)
            g = prob.full_grad(z, w)
            viol = prob.kkt_violation_from_grad(w, g)
            kkt = jnp.max(viol)
            if cfg.shrink:
                interior = (w == 0) & (jnp.abs(g) < 1.0 - cfg.shrink_tol)
                active = active & ~interior
                active = active | (recheck & (viol > cfg.tol_kkt))
            nnz = jnp.sum(w != 0)
            n_active = jnp.sum(active.astype(jnp.int32))
        base = (w, z, key, f, kkt, nnz, mean_q, active, n_active)
        if cfg.record_aux:
            base = base + ((qs, alphas),)
        if cfg.record_kkt_vec:
            base = base + (viol,)
        return base

    return jit_over_problem(outer, problem)


def solve(problem: L1Problem, cfg: PCDNConfig,
          w0: Optional[Array] = None,
          f_star: Optional[float] = None,
          callback: Optional[Callable] = None) -> SolveResult:
    """Run PCDN until the KKT (or relative-objective) stop or max_outer.

    Thin caller of the unified engine (DESIGN.md section 9): builds a
    `LocalBackend` over this problem and hands the stop parameters to
    `engine.loop.solve` — the same loop that drives the sharded backend
    and the path sweeps.
    """
    from repro.engine import loop as engine_loop
    from repro.engine.local import LocalBackend

    backend = LocalBackend(problem, cfg)
    return engine_loop.solve(
        backend, problem.c, w0=w0,
        max_outer=cfg.max_outer, tol_kkt=cfg.tol_kkt,
        recheck_every=cfg.recheck_every, tol_rel_obj=cfg.tol_rel_obj,
        f_star=f_star, callback=callback)
