"""Vmapped multi-problem batch solving (DESIGN.md section 8.3).

Solves B l1 problems that share one DesignMatrix — different c values,
labels and/or partition seeds — in a SINGLE XLA program: the per-problem
outer iteration is `jax.vmap`-ed over the (w, z, key, c[, y]) carries
while the design arrays are closed over (broadcast, resident once). This
is the throughput-oriented serving mode: one dispatch advances every
request in the batch by one outer iteration.

Contract (the "vmap batching contract" of DESIGN.md section 8.3):
  * the design matrix is shared and read-only; per-problem state is
    exactly the vmapped carry, so peak memory is B * (n + s) + one design;
  * every problem runs the same bundle schedule SHAPE (same P, same b)
    but its own random partition (per-problem PRNG key chain, identical
    to what a solo `pcdn.solve` with that seed would draw);
  * convergence is per-problem: a problem whose full-set KKT drops below
    tol is frozen (its carry is re-selected, not updated), so its result
    is bit-identical to stopping — stragglers keep iterating in lockstep.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bundles as B
from repro.core.pcdn import PCDNConfig, jit_over_problem, make_bundle_step
from repro.core.problem import L1Problem
from repro.engine import loop as engine_loop

Array = jax.Array


class BatchSolveResult(NamedTuple):
    w: Array            # (B, n)
    objective: Array    # (B,)
    kkt: Array          # (B,)
    nnz: Array          # (B,)
    n_outer: Array      # (B,) outer iterations until each problem froze
    converged: Array    # (B,) bool
    z: Array            # (B, s) final margins X w — free from the carry;
                        # OVR training reads its train accuracy off these
                        # without another B-way matvec (serve/ovr.py)


def make_batch_outer(problem: L1Problem, cfg: PCDNConfig,
                     batched_labels: bool):
    """One jitted, vmapped outer iteration over B problem carries.

    Returns outer(w (B,n), z (B,s), key (B,2), c (B,)[, y (B,s)])
    -> (w, z, key, f, kkt, nnz), all B-leading.
    """
    def one(problem, w, z, key, c, y):
        n = problem.n_features
        prob = problem.with_c(c)
        if y is not None:
            prob = prob.with_labels(y)
        step = make_bundle_step(prob, cfg)
        key, sub = jax.random.split(key)
        idxs = B.partition(sub, n, cfg.P)
        (w, z), (steps, _alphas) = jax.lax.scan(step, (w, z), idxs)
        f = prob.objective_from_margins(z, w)
        kkt = prob.kkt_violation(w, z)
        nnz = jnp.sum(w != 0)
        return w, z, key, f, kkt, nnz

    if batched_labels:
        mapped = jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0))
    else:
        mapped = jax.vmap(
            lambda problem, w, z, key, c: one(problem, w, z, key, c, None),
            in_axes=(None, 0, 0, 0, 0))
    return jit_over_problem(mapped, problem)


def solve_batch(problem: L1Problem, cfg: PCDNConfig,
                cs: Sequence[float],
                ys: Optional[np.ndarray] = None,
                seeds: Optional[Sequence[int]] = None,
                w0: Optional[np.ndarray] = None,
                outer=None) -> BatchSolveResult:
    """Solve B problems sharing `problem.design` in one vmapped program.

    cs: (B,) per-problem regularization values. ys: optional (B, s)
    per-problem labels (default: share problem.y). seeds: optional (B,)
    partition seeds (default: cfg.seed for every problem — same schedule,
    different c). w0: optional (B, n) warm starts.

    Matches a Python loop of `pcdn.solve` per problem up to f32 reduction
    -order noise from batched matvecs (tests/test_path.py pins this).
    """
    if cfg.shrink:
        raise ValueError(
            "solve_batch does not implement active-set shrinking (every "
            "problem would need its own active mask + dynamic trip count, "
            "breaking the lockstep vmap); pass PCDNConfig(shrink=False) "
            "and use run_path for shrinking sweeps")
    cs = np.asarray(cs, np.float64)
    batch = cs.shape[0]
    n, s = problem.n_features, problem.n_samples
    dtype = problem.dtype
    if ys is not None:
        ys = jnp.asarray(np.asarray(ys), dtype)
        if ys.shape != (batch, s):
            raise ValueError(f"ys must be ({batch}, {s}), got {ys.shape}")
    if seeds is None:
        seeds = [cfg.seed] * batch
    if len(seeds) != batch:
        raise ValueError(f"need {batch} seeds, got {len(seeds)}")

    if w0 is None:
        w = jnp.zeros((batch, n), dtype)
        z = jnp.zeros((batch, s), dtype)
    else:
        w = jnp.asarray(np.asarray(w0), dtype)
        if w.shape != (batch, n):
            raise ValueError(f"w0 must be ({batch}, {n}), got {w.shape}")
        z = jax.vmap(problem.design.matvec)(w)
    keys = jnp.stack([jax.random.PRNGKey(int(sd)) for sd in seeds])
    c_arr = jnp.asarray(cs, dtype)

    if outer is None:
        outer = make_batch_outer(problem, cfg, batched_labels=ys is not None)
    args = (ys,) if ys is not None else ()

    # the freeze-on-convergence host loop is the engine's (DESIGN.md §9)
    (w, z, keys), f, kkt, nnz, n_outer, done = engine_loop.run_lockstep_loop(
        outer, (w, z, keys), (c_arr,) + args,
        max_outer=cfg.max_outer, tol_kkt=cfg.tol_kkt, dtype=dtype)

    return BatchSolveResult(w=w, objective=f, kkt=kkt, nnz=nnz,
                            n_outer=n_outer, converged=done, z=z)
