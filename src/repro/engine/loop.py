"""Unified solver engine: the outer-iteration contract (DESIGN.md section 9).

Every PCDN-family solver in this repo is a host-side convergence loop
around one jitted "outer iteration". Before the engine existed that loop
— carry threading, full-gradient KKT stopping, history recording,
wall-clock bookkeeping — was re-implemented by pcdn.solve, the sharded
solver, SCDN and the path driver. It now exists ONCE, here, behind a
pluggable *execution backend* interface:

    outer(w, z, key, active, recheck, c)
      -> (w, z, key, f, kkt, nnz, mean_q, active, n_active)

* ``(w, z, key, active)`` is the solver carry (`EngineState`): weights,
  per-sample margins z = X w, the PRNG chain for bundle partitions, and
  the un-shrunk feature mask.
* ``recheck`` (traced bool) asks the iteration to un-shrink any feature
  whose full-set KKT violation exceeds tolerance.
* ``c`` is a TRACED regularization scalar, so one compiled program
  serves a whole warm-started c-sweep (the dynamic-c contract of
  DESIGN.md section 8).
* ``kkt`` must be the FULL-set violation — the stop criterion is
  backend-independent.

Backends (duck-typed; see `ExecutionBackend`):

* `repro.engine.local.LocalBackend` — single XLA program wrapping
  `pcdn.make_bundle_step` / `pcdn.make_path_outer` (dense or padded-CSC
  design, optional fused Pallas kernels).
* `repro.engine.sharded.ShardedBackend` — the 2-D (data x model)
  shard_map implementation, same contract, so path sweeps, shrinking
  and warm starts run unchanged on a multi-device mesh.

`pcdn.solve`, `core.sharded.solve_sharded`, `path.driver.run_path`,
`path.batch.solve_batch`, and `scdn.solve` are all thin callers of the
helpers in this module.
"""
from __future__ import annotations

import time
from typing import Callable, NamedTuple, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

Array = jax.Array


class EngineState(NamedTuple):
    """The backend-independent solver carry."""

    w: Array        # (n,) weights (backend-native placement)
    z: Array        # (s,) margins X w
    key: Array      # PRNG key chain for bundle partitions
    active: Array   # (n,) bool un-shrunk mask (all-True without shrinking)


class SolveHistory(NamedTuple):
    outer_iter: np.ndarray     # (K,)
    objective: np.ndarray      # (K,) F_c(w) after each outer iteration
    kkt: np.ndarray            # (K,)
    nnz: np.ndarray            # (K,) number of nonzeros in w
    ls_steps: np.ndarray       # (K,) mean line-search steps per bundle
    wall_time: np.ndarray      # (K,) cumulative seconds
    n_active: np.ndarray       # (K,) un-shrunk features (== n without shrink)
    # per-bundle series (DESIGN.md section 13.2): present only when the
    # backend was built with record_aux=True — the outer iteration then
    # returns a 10th output (q (b,), alpha (b,)) and these are (K, b)
    # with sentinel q == -1 / alpha == nan on bundles that did not run
    # (the shrinking solver's unused trailing slots).
    bundle_q: Optional[np.ndarray] = None       # (K, b) int32
    bundle_alpha: Optional[np.ndarray] = None   # (K, b)
    # per-feature KKT attribution series (DESIGN.md section 15.1):
    # present only with record_kkt_vec=True — the outer then also
    # returns the (n,) per-feature violation vector (the same
    # kkt_violation_from_grad the scalar stop reduces), harvested at
    # the per-iteration host sync into a (K, n) array.
    kkt_vec: Optional[np.ndarray] = None        # (K, n)


class SolveResult(NamedTuple):
    w: Array
    objective: float
    n_outer: int
    converged: bool
    history: SolveHistory
    diverged: bool = False     # divergence guard OR non-finite detector
    # divergence post-mortem (DESIGN.md section 15.2): attached when the
    # divergence guard trips — which iterations/bundles drove the deep
    # backtracks and how alpha collapsed, from whatever series the run
    # recorded (richer with record_aux). None on non-diverged solves.
    postmortem: Optional[dict] = None
    # non-finite trip (DESIGN.md section 16.3): the always-on detector
    # caught NaN/inf in (f, kkt) — the objective-growth guard alone
    # cannot (NaN fails every comparison). When set, `w`/`objective` and
    # the returned EngineState are the LAST GOOD iterate, so a caller
    # (fault.resilient_solve) can roll back and retry at damped P.
    nonfinite: bool = False
    # rollback/P-backoff record attached by fault.resilient_solve:
    # {"rollbacks", "p_schedule", "p_cert", "resumed_from"}. None on
    # fault-free solves.
    faults: Optional[dict] = None


class ExecutionBackend(Protocol):
    """What the engine needs from an execution substrate (duck-typed).

    `outer` is the jitted iteration described in the module docstring.
    The remaining methods let drivers stay placement-agnostic: a local
    backend hands out plain jnp arrays, the sharded backend hands out
    mesh-placed (and feature-padded) arrays — callers never see the
    difference.
    """

    outer: Callable  # (w, z, key, active, recheck, c) -> 9-tuple

    @property
    def n_features(self) -> int: ...          # REAL feature count (unpadded)

    @property
    def dtype(self): ...

    def init_state(self, w0=None) -> EngineState: ...

    def margins(self, w: Array) -> Array: ...  # recompute z = X w

    def c_max(self) -> float: ...              # analytic path start

    def host_weights(self, w: Array) -> np.ndarray: ...  # (n_features,) host


def _build_postmortem(hist: dict, aux_q: list, aux_alpha: list,
                      k: int) -> dict:
    """Divergence post-mortem (DESIGN.md section 15.2) from the rows
    recorded so far, richer when per-bundle aux rode along. Local
    import — diag consumes the engine, so a top-level import would close
    the layering cycle. Shared by the guard trip and the non-finite
    detector."""
    from repro.diag import forensics
    postmortem = forensics.divergence_postmortem(
        objective=np.asarray(hist["objective"]),
        kkt=np.asarray(hist["kkt"]),
        ls_steps=np.asarray(hist["ls_steps"]),
        bundle_q=np.asarray(aux_q) if aux_q else None,
        bundle_alpha=np.asarray(aux_alpha) if aux_alpha else None)
    obs.instant("engine.divergence_postmortem",
                {"k": k, "objective_growth": postmortem["objective_growth"],
                 "deepest_mean_q": postmortem["deepest_mean_q"]})
    return postmortem


def run_outer_loop(outer: Callable, state: EngineState, c: float, *,
                   max_outer: int, tol_kkt: float,
                   recheck_every: int = 1, tol_rel_obj: float = 0.0,
                   f_star: Optional[float] = None,
                   callback: Optional[Callable] = None,
                   divergence_guard: Optional[Callable[[float], bool]] = None,
                   start_iter: int = 0,
                   state_callback: Optional[Callable] = None,
                   check_finite_w: bool = False,
                   ) -> Tuple[EngineState, SolveResult]:
    """Host-side convergence loop around a backend outer iteration.

    The single implementation of the stop logic (full-set KKT, optional
    relative-objective, optional divergence guard) and of history /
    wall-clock recording. Returns (state, SolveResult).

    divergence_guard(f) -> True aborts the loop and flags the result as
    diverged (SCDN's Hogwild semantics); converged stays False. On a
    trip the result carries a `postmortem` dict (repro.diag.forensics)
    built from the recorded series — richer when the backend also
    recorded per-bundle aux.

    Non-finite detection is ALWAYS on (DESIGN.md section 16.3): a NaN/inf
    objective or KKT — which `divergence_guard(f)`'s growth comparison
    can never catch, NaN compares False — aborts the loop with
    `diverged=True, nonfinite=True`, a postmortem, and the LAST GOOD
    iterate as the returned state/weights (the poisoned carry is
    discarded — it is what the caller must NOT keep). The detector reads
    only the f/kkt host floats the loop already syncs, so the fault-free
    hot path gains zero device work; `check_finite_w=True` additionally
    scans the weight vector each iteration (one device all-reduce — the
    belt-and-braces mode `fault.resilient_solve` runs retries under).

    start_iter shifts the iteration counter: the loop runs iterations
    [start_iter, max_outer) with GLOBAL indices, so a resumed solve
    replays the exact recheck cadence (k % recheck_every) and history
    numbering of the uninterrupted run — max_outer stays the TOTAL
    budget, not a per-resume increment.

    state_callback(k, EngineState, f, kkt) fires after each FINITE
    iteration's host sync — the periodic-checkpoint hook
    (fault.SolveCheckpointer.solve_callback); it never sees a poisoned
    carry.

    Outputs past the 9-tuple are dispatched STRUCTURALLY, so the two
    opt-in device-aux planes compose in any combination:

      * a 2-tuple of arrays — per-bundle (q (b,), alpha (b,)), the
        `record_aux` contract of DESIGN.md section 13.2 — harvested
        into `SolveHistory.bundle_q/bundle_alpha` (and, when the
        metrics registry is enabled, into the solver.bundle_q /
        solver.bundle_alpha histograms) at the same host sync that
        reads f/kkt.
      * a single array — the (n,) per-feature KKT violation vector,
        the `record_kkt_vec` contract of DESIGN.md section 15.1 —
        harvested into `SolveHistory.kkt_vec`.

    A 9-tuple outer records exactly the history it always did.

    callback(k, w, f, kkt, mean_q) fires after every iteration's host
    sync (mean_q is the iteration's mean line-search depth — the
    `--progress` CLI consumes it).
    """
    w, z, key, active = state
    c_arr = jnp.asarray(c, w.dtype)
    base_fields = ("outer_iter", "objective", "kkt", "nnz", "ls_steps",
                   "wall_time", "n_active")
    hist = {k: [] for k in base_fields}
    aux_q: list = []
    aux_alpha: list = []
    kkt_rows: list = []
    t0 = time.perf_counter()
    t_last = 0.0
    converged = diverged = nonfinite = False
    postmortem = None
    f = f_good = float("nan")
    prev_active = None
    k = start_iter - 1
    for k in range(start_iter, max_outer):
        # the iteration's phases are profiler spans (one check each when
        # no profiler records): dispatch, the wait for the device, the
        # scalar pulls, and the host bookkeeping between iterations
        with obs.span("engine.outer") as it_span:
            with obs.span("engine.dispatch"):
                # iteration 0 always rechecks so a stale warm-started
                # active set (e.g. carried across path points) is
                # repaired immediately.
                recheck = jnp.asarray(k == 0 or recheck_every <= 1
                                      or k % recheck_every == 0)
                # the pre-iteration carry is the rollback target should
                # this iteration come back non-finite
                prev_state = (w, z, key, active)
                out = outer(w, z, key, active, recheck, c_arr)
            w, z, key, f_, kkt, nnz, mean_q, active, n_active = out[:9]
            aux = kkt_vec = None
            for extra in out[9:]:
                if isinstance(extra, tuple):
                    aux = extra
                else:
                    kkt_vec = extra
            # sync BEFORE timestamping: float(f_) below only blocks on f_,
            # and a backend dispatching asynchronously would otherwise get
            # this iteration's device time attributed to a later row
            # (tests/test_obs.py pins monotone per-iteration times that
            # sum to ~ the loop total).
            with obs.span("engine.wait"):
                jax.block_until_ready((w, z, active))
            t_now = time.perf_counter() - t0
            with obs.span("engine.pull"):
                f = float(f_)
                kkt_f = float(kkt)
                n_active_i = int(n_active)
                nnz_i = int(nnz)
                mean_q_f = float(mean_q)
                if aux is not None:
                    q_np = np.asarray(aux[0])
                    a_np = np.asarray(aux[1])
                if kkt_vec is not None:
                    kkt_rows.append(np.asarray(kkt_vec))
            if it_span is not None:
                it_span.set_metadata(k=k, objective=f, kkt=kkt_f,
                                     mean_q=mean_q_f, n_active=n_active_i)
            with obs.span("engine.record"):
                hist["outer_iter"].append(k)
                hist["objective"].append(f)
                hist["kkt"].append(kkt_f)
                hist["nnz"].append(nnz_i)
                hist["ls_steps"].append(mean_q_f)
                hist["wall_time"].append(t_now)
                hist["n_active"].append(n_active_i)
                if aux is not None:
                    aux_q.append(q_np)
                    aux_alpha.append(a_np)
                    if obs.metrics_enabled():
                        ran = q_np >= 0   # sentinel -1: bundle did not run
                        obs.observe_many("solver.bundle_q", q_np[ran],
                                         bounds=obs.Q_BOUNDS)
                        obs.observe_many("solver.bundle_alpha", a_np[ran],
                                         bounds=obs.ALPHA_BOUNDS)
                if obs.metrics_enabled():
                    obs.inc("solver.outer_iters")
                    obs.observe("solver.iter_seconds", t_now - t_last)
                    obs.observe("solver.mean_q", mean_q_f,
                                bounds=obs.Q_BOUNDS)
                    obs.set_gauge("solver.n_active", n_active_i)
                    obs.set_gauge("solver.kkt", kkt_f)
                    if prev_active is not None and n_active_i != prev_active:
                        if n_active_i < prev_active:
                            obs.inc("solver.shrink_events",
                                    prev_active - n_active_i)
                        else:
                            obs.inc("solver.unshrink_events",
                                    n_active_i - prev_active)
                prev_active = n_active_i
                t_last = t_now
                if callback is not None:
                    callback(k, w, f, kkt_f, mean_q_f)
                # non-finite detector (DESIGN.md section 16.3): always
                # on, free on the hot path (f/kkt are already host floats)
                finite = np.isfinite(f) and np.isfinite(kkt_f)
                if finite and check_finite_w:
                    finite = bool(jnp.all(jnp.isfinite(w)))
                if not finite:
                    diverged = nonfinite = True
                    obs.inc("solver.nonfinite_trips")
                    obs.instant("engine.nonfinite_guard",
                                {"k": k, "objective": f, "kkt": kkt_f})
                    postmortem = _build_postmortem(hist, aux_q, aux_alpha,
                                                   k)
                    # roll the carry back to the last good iterate: the
                    # poisoned state must never leak into warm starts,
                    # checkpoints or the returned weights
                    w, z, key, active = prev_state
                    f = f_good
                    break
                f_good = f
                if state_callback is not None:
                    state_callback(k, EngineState(w, z, key, active), f,
                                   kkt_f)
                if divergence_guard is not None and divergence_guard(f):
                    diverged = True
                    obs.inc("solver.divergence_trips")
                    obs.instant("engine.divergence_guard",
                                {"k": k, "objective": f})
                    postmortem = _build_postmortem(hist, aux_q, aux_alpha,
                                                   k)
                    break
                if kkt_f <= tol_kkt:
                    converged = True
                    break
                if f_star is not None and tol_rel_obj > 0:
                    if (f - f_star) <= tol_rel_obj * abs(f_star):
                        converged = True
                        break
    history = SolveHistory(
        **{k_: np.asarray(v) for k_, v in hist.items()},
        bundle_q=np.asarray(aux_q) if aux_q else None,
        bundle_alpha=np.asarray(aux_alpha) if aux_alpha else None,
        kkt_vec=np.asarray(kkt_rows) if kkt_rows else None)
    result = SolveResult(w=w, objective=f, n_outer=k + 1,
                         converged=converged, history=history,
                         diverged=diverged, postmortem=postmortem,
                         nonfinite=nonfinite)
    return EngineState(w, z, key, active), result


def check_shrink_stop_consistency(backend, tol_kkt: float):
    """A shrinking backend bakes its UN-shrink threshold (cfg.tol_kkt)
    into the compiled iteration; driving it with a TIGHTER stop tolerance
    would let a feature with violation in (tol_kkt, cfg.tol_kkt] stay
    shrunk forever while the loop never reaches its stop — a silent
    max_outer burn. Refuse loudly instead."""
    cfg = getattr(backend, "cfg", None)
    if cfg is None or not getattr(cfg, "shrink", False):
        return
    un_shrink = getattr(cfg, "tol_kkt", None)
    if un_shrink is not None and tol_kkt < un_shrink:
        raise ValueError(
            f"stop tol_kkt={tol_kkt} is tighter than the backend's "
            f"compiled un-shrink threshold cfg.tol_kkt={un_shrink}; a "
            f"shrunk feature between them would never be reactivated. "
            f"Rebuild the backend with cfg.tol_kkt <= the stop tolerance.")


def solve(backend, c: float, w0=None, *,
          max_outer: int, tol_kkt: float, recheck_every: int = 1,
          tol_rel_obj: float = 0.0, f_star: Optional[float] = None,
          callback: Optional[Callable] = None) -> SolveResult:
    """One full solve on a backend: init state, loop to the KKT stop."""
    check_shrink_stop_consistency(backend, tol_kkt)
    state = backend.init_state(w0)
    _, result = run_outer_loop(
        backend.outer, state, c, max_outer=max_outer, tol_kkt=tol_kkt,
        recheck_every=recheck_every, tol_rel_obj=tol_rel_obj,
        f_star=f_star, callback=callback)
    return result


def run_lockstep_loop(outer: Callable, carry: Sequence[Array],
                      extra: Sequence, *, max_outer: int, tol_kkt: float,
                      dtype):
    """Freeze-on-convergence lockstep loop over B problems (vmap batching
    contract, DESIGN.md section 8.3).

    outer(*carry, *extra) must return (*carry', f, kkt, nnz), every array
    B-leading. A problem whose KKT drops below tol is frozen: its carry
    is re-selected (not updated) on later iterations, so its result is
    bit-identical to stopping while stragglers keep iterating.

    Returns (carry, f, kkt, nnz, n_outer, done).
    """
    carry = tuple(carry)
    batch = carry[0].shape[0]
    done = jnp.zeros((batch,), bool)
    n_outer = jnp.zeros((batch,), jnp.int32)
    f = jnp.full((batch,), jnp.inf, dtype)
    kkt = jnp.full((batch,), jnp.inf, dtype)
    nnz = jnp.zeros((batch,), jnp.int32)
    for _ in range(max_outer):
        out = outer(*carry, *extra)
        new_carry, (f_n, kkt_n, nnz_n) = out[:-3], out[-3:]
        carry = tuple(
            jnp.where(done.reshape((batch,) + (1,) * (old.ndim - 1)),
                      old, new)
            for old, new in zip(carry, new_carry))
        f = jnp.where(done, f, f_n)
        kkt = jnp.where(done, kkt, kkt_n)
        nnz = jnp.where(done, nnz, nnz_n)
        n_outer = jnp.where(done, n_outer, n_outer + 1)
        done = done | (kkt <= tol_kkt)
        if bool(jnp.all(done)):
            break
    return carry, f, kkt, nnz, n_outer, done
