"""Shotgun CDN baseline (Bradley et al. 2011; paper Algorithm 2).

SCDN picks Pbar features uniformly at random and updates them *in parallel*,
each with its own 1-D Newton direction and 1-D line search, racing on shared
memory. TPU has no shared-memory atomics (DESIGN.md section 3.5a), so we
simulate the Hogwild semantics faithfully at iteration granularity: all Pbar
updates are computed from the *same* stale (w, z), then applied together

    w <- w + sum_j alpha_j d_j e_j ,   z <- z + sum_j alpha_j d_j x^j .

This preserves the property under study — the per-coordinate line searches
do not account for each other, so the combined step can increase F_c and
the method diverges when Pbar exceeds the spectral threshold n/rho + 1
(section 2.2) — which our benchmarks reproduce.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bundles as B
from repro.core.direction import delta_decrement, newton_direction
from repro.core.linesearch import ArmijoParams, armijo_batched
from repro.core.problem import L1Problem
from repro.engine.loop import EngineState, run_outer_loop

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SCDNConfig:
    P_bar: int = 8               # paper section 5.1 follows Bradley et al.
    armijo: ArmijoParams = ArmijoParams()
    max_rounds: int = 2000       # each round = ceil(n/P_bar) parallel updates
    tol_kkt: float = 1e-3
    seed: int = 0


class SCDNResult(NamedTuple):
    w: Array
    objective: float
    n_rounds: int
    converged: bool
    diverged: bool
    history: dict


def make_round(problem: L1Problem, cfg: SCDNConfig):
    """One epoch-equivalent: ceil(n/P_bar) batches of P_bar racing updates."""
    n = problem.n_features
    loss = problem.loss
    n_batches = -(-n // cfg.P_bar)

    def one_batch(carry, key):
        w, z = carry
        idx = jax.random.randint(key, (cfg.P_bar,), 0, n)  # with replacement
        slab = problem.gather_slab(idx)
        w_B, _ = B.gather_vec(w, idx)
        g, h = problem.bundle_grad_hess(z, slab, w_B)
        d = newton_direction(g, h, w_B)

        # per-coordinate 1-D line searches, each blind to the others
        deltas = problem.design.slab_coordinate_deltas(slab, d)  # (P, s)

        def ls_one(delta_j, wj, dj, gj, hj):
            Delta = delta_decrement(gj[None], hj[None], wj[None], dj[None],
                                    cfg.armijo.gamma)
            res = armijo_batched(loss, problem.c, z, delta_j, problem.y,
                                 wj[None], dj[None], Delta, cfg.armijo)
            return res.alpha

        alphas = jax.vmap(ls_one)(deltas, w_B, d, g, h)
        upd = alphas * d
        w = B.scatter_add(w, idx, upd)
        z = z + problem.design.slab_matvec(slab, upd)
        return (w, z), None

    def round_fn(w, z, key):
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, n_batches)
        (w, z), _ = jax.lax.scan(one_batch, (w, z), keys)
        f = problem.objective_from_margins(z, w)
        kkt = problem.kkt_violation(w, z)
        return w, z, key, f, kkt

    return jax.jit(round_fn)


def solve(problem: L1Problem, cfg: SCDNConfig,
          f_star: Optional[float] = None,
          divergence_factor: float = 1e3) -> SCDNResult:
    """Host-side round loop = the engine's shared stop/history/timing
    helpers (DESIGN.md section 9) + SCDN's divergence guard: the Hogwild
    semantics under study mean F_c can INCREASE, so a round whose
    objective blows past divergence_factor * F_c(0) (or goes non-finite)
    aborts the run and flags `diverged` instead of iterating to
    max_rounds."""
    n = problem.n_features
    round_fn = make_round(problem, cfg)

    def outer(w, z, key, active, recheck, c):
        """Adapt the SCDN round to the engine's outer contract; the
        racing updates have no shrinking or traced-c story, so `active`
        passes through and `c`/`recheck` are unused (the round closes
        over problem.c)."""
        w, z, key, f, kkt = round_fn(w, z, key)
        return (w, z, key, f, kkt, jnp.sum(w != 0), jnp.float32(0.0),
                active, jnp.int32(n))

    state = EngineState(
        w=jnp.zeros((n,), problem.dtype),
        z=jnp.zeros((problem.n_samples,), problem.dtype),
        key=jax.random.PRNGKey(cfg.seed),
        active=jnp.ones((n,), bool))
    f0 = float(problem.objective_from_margins(state.z, state.w))

    def guard(f: float) -> bool:
        return (not np.isfinite(f)) or f > divergence_factor * f0

    _, res = run_outer_loop(outer, state, problem.c,
                            max_outer=cfg.max_rounds, tol_kkt=cfg.tol_kkt,
                            divergence_guard=guard)
    h = res.history
    return SCDNResult(w=res.w, objective=res.objective, n_rounds=res.n_outer,
                      converged=res.converged, diverged=res.diverged,
                      history={"round": h.outer_iter,
                               "objective": h.objective, "kkt": h.kkt,
                               "wall_time": h.wall_time})
