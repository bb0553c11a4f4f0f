"""Warm-started regularization-path driver (DESIGN.md sections 8 / 9).

Solves an l1 problem along a geometric c-grid built from the analytic
c_max, chaining the engine carry (w, z, active-set) from each point into
the next. The sweep runs on ANY execution backend (`repro.engine`):
locally one `pcdn.make_path_outer` program is compiled for the whole
sweep — c is a traced argument — so a 20-point path pays one XLA
compile, not twenty; on a `ShardedBackend` the same driver runs the
warm-started sweep (including active-set shrinking) across a
multi-device mesh with one compiled shard_map program.

Each point stops at the full-set KKT test. `PathConfig.stop` is the
sweep's rule: "absolute" stops every point at `solver.tol_kkt`;
"relative" stops point i at tol_i = solver.tol_kkt * KKT_{c_i}(w = 0),
CDN's relative stop (Yuan et al., JMLR 2010), with KKT_c(0) = c / c_max - 1
from the analytic c_max (the anchor at c_max, where it is 0, takes the next
point's stop). A shrinking backend compiles one un-shrink threshold, which
must not exceed the sweep's smallest stop (`PathConfig.unshrink_tol`).

Per point the driver records objective / nnz / full-set KKT / iteration
and wall-time cost, the host seconds it spent outside the engine loop,
the active-set size of each iteration, plus (optionally) held-out
validation accuracy against a held-out design converted to a device
design once per sweep, and picks the best c by validation accuracy when
a validation split is given.
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.pcdn import PCDNConfig
from repro.core.design_matrix import as_design
from repro.core.problem import L1Problem, validation_accuracy
from repro.engine import loop as engine_loop
from repro.engine.local import LocalBackend
from repro.path import grid as grid_mod


@dataclasses.dataclass(frozen=True)
class PathConfig:
    """A λ-sweep: grid geometry + the per-point PCDN solver settings.

    `solver` supplies the stop parameters (max_outer / tol_kkt /
    recheck_every / tol_rel_obj) for every backend; its execution fields
    (P, ls_kind, use_kernels, shrink) govern the default local backend —
    a `ShardedBackend` brings its own `ShardedPCDNConfig` for those.
    `stop` says how `solver.tol_kkt` stops each point (`stops`).
    """

    solver: PCDNConfig = PCDNConfig(P=256)
    n_points: int = 20
    span: float = 100.0                 # c_final = span * c_max when unset
    c_final: Optional[float] = None
    warm_start: bool = True             # chain (w, z, active) across points
    stop: str = "absolute"              # "absolute": every point stops at
                                        # solver.tol_kkt; "relative": point
                                        # i at solver.tol_kkt * KKT_{c_i}(0)

    def grid(self, c_max: float) -> np.ndarray:
        """The ascending c-grid anchored at `c_max`."""
        return grid_mod.c_grid(c_max, c_final=self.c_final,
                               n_points=self.n_points, span=self.span)

    def stops(self, c_max: float) -> np.ndarray:
        """The KKT stop of each grid point.

        "relative": tol_i = solver.tol_kkt * KKT_{c_i}(0), where
        KKT_c(0) = c * ||X^T phi'(0, y)||_inf - 1 = c / c_max - 1 exactly
        (the elastic-net term vanishes at 0). It is 0 at the c_max anchor,
        which therefore takes the next point's stop.
        """
        tol = self.solver.tol_kkt
        cs = self.grid(c_max)
        if self.stop == "absolute":
            return np.full(cs.shape, tol)
        if self.stop != "relative":
            raise ValueError(f"unknown stop rule {self.stop!r}")
        kkt0 = cs / c_max - 1.0
        kkt0 = np.where(kkt0 > 0, kkt0, kkt0[kkt0 > 0].min())
        return tol * kkt0

    def unshrink_tol(self, c_max: float) -> float:
        """The un-shrink threshold a shrinking backend compiles for this
        sweep: its smallest stop, the loosest that still reactivates every
        shrunk feature that violates some point's stop."""
        return float(self.stops(c_max).min())


class PathPoint(NamedTuple):
    c: float
    objective: float
    nnz: int
    kkt: float
    n_outer: int
    seconds: Optional[float]            # wall time on this point (None in
                                        # batch mode — lockstep solves
                                        # have no per-point timing)
    converged: bool
    val_accuracy: Optional[float]       # None without a validation split
    # host seconds of the point outside the engine loop (margin refresh,
    # weight pull, validation, bookkeeping); None in batch mode and in
    # checkpoints written before it was recorded
    between_s: Optional[float] = None
    n_active: Optional[tuple] = None    # un-shrunk features per iteration


class PathResult(NamedTuple):
    c_max: float
    cs: np.ndarray                      # (n_points,) ascending grid
    points: list                        # [PathPoint]
    weights: np.ndarray                 # (n_points, n) solutions per point
    best_index: Optional[int]           # argmax val accuracy (ties -> sparser)
    total_seconds: float
    # final grid point's full SolveHistory (the tightest c — where
    # parallelism stress peaks) for the `--diag-out` health report;
    # None in batch mode, which has no per-iteration history.
    last_history: Optional[object] = None
    last_postmortem: Optional[dict] = None
    # the engine carry (w, z, key, active) after the final point; None in
    # batch mode
    last_state: Optional[engine_loop.EngineState] = None

    @property
    def best(self) -> Optional[PathPoint]:
        return None if self.best_index is None else self.points[self.best_index]


def pick_best(points: Sequence[PathPoint]) -> Optional[int]:
    """Highest validation accuracy; ties go to the sparser (smaller-c)
    model, the usual one-standard-error-rule direction. Shared by the
    sweep driver and the batch-mode CLI so both modes pick identically."""
    scored = [(p.val_accuracy, -p.nnz, -i) for i, p in enumerate(points)
              if p.val_accuracy is not None]
    if not scored:
        return None
    return -max(scored)[2]


def sweep_backend(problem: L1Problem, cfg: PathConfig,
                  outer=None) -> LocalBackend:
    """The local backend of the sweep `cfg`: `cfg.solver` with its
    compiled un-shrink threshold at the sweep's smallest stop
    (`PathConfig.unshrink_tol`; cfg.solver itself under the absolute
    stop). `run_path` builds this one when given none."""
    solver = dataclasses.replace(
        cfg.solver, tol_kkt=cfg.unshrink_tol(problem.c_max()))
    return LocalBackend(problem, solver, outer=outer)


def run_path(problem: Optional[L1Problem], cfg: PathConfig,
             val_design=None, val_y=None,
             verbose: bool = False, outer=None,
             backend=None, callback=None,
             ckpt=None, resume: bool = False,
             fault_plan=None) -> PathResult:
    """Sweep the c-grid; `problem.c` is a template value and is ignored.

    backend: any engine execution backend; defaults to a `LocalBackend`
    over `problem` (which may then not be None). With a backend given,
    `problem` is unused — data, placement and the compiled iteration all
    live in the backend, which is how one sweep runs on a sharded mesh.
    val_design / val_y: optional held-out split (anything `as_design`
    accepts), converted to a device design once per sweep and scored
    after each point; enables the best-c pick.
    outer: optional prebuilt `pcdn.make_path_outer(problem, solver)`
    for the default local backend (`sweep_backend`'s `solver`: cfg.solver
    with tol_kkt at `cfg.unshrink_tol(c_max)`, which is cfg.solver under
    the absolute stop) — benchmarks pass an already-compiled
    one so warm-vs-cold timings compare solver work, not XLA compile
    time.
    callback: forwarded to every point's engine loop (the `--progress`
    live status — signature (k, w, f, kkt, mean_q)).
    ckpt: optional `fault.SolveCheckpointer` — the finished carry, the
    per-point records and the weight rows are checkpointed after EVERY
    grid point (the point boundary is the natural resume unit; see the
    checkpointer docstring). resume=True restarts from the newest
    committed point checkpoint — the restored carry is the same host
    image the uninterrupted run had, so the resumed sweep's artifacts
    match bit-for-bit. The stored c-grid is validated against the live
    one. fault_plan: optional `fault.FaultPlan`; its iteration hooks
    count cumulative outer iterations across the sweep and
    `crash_at_point` fires right AFTER a point's checkpoint commits.
    """
    if (val_design is None) != (val_y is None):
        raise ValueError("pass both val_design and val_y or neither")
    solver = cfg.solver
    if backend is None:
        if problem is None:
            raise ValueError("run_path needs a problem or a backend")
        backend = sweep_backend(problem, cfg, outer=outer)
    c_max = backend.c_max()
    cs = cfg.grid(c_max)
    tols = cfg.stops(c_max)
    engine_loop.check_shrink_stop_consistency(backend, float(tols.min()))
    # the held-out split becomes a device design once; every point is
    # scored against it
    val = None if val_design is None else as_design(val_design)

    n = backend.n_features
    state = backend.init_state()

    points: list[PathPoint] = []
    res = None
    weights = np.zeros((len(cs), n), np.dtype(backend.dtype))
    i_start = 0
    if resume and ckpt is not None:
        got = ckpt.restore_path(backend, cs=cs, c_max=c_max)
        if got is not None:
            state, meta, saved_w = got
            i_start = int(meta["point_index"]) + 1
            points = [PathPoint(**p) for p in meta["points"]]
            weights[:i_start] = saved_w[:i_start]
            if verbose:
                print(f"[fault] resuming path sweep at point "
                      f"{i_start}/{len(cs)}", flush=True)
    outer_fn = backend.outer
    if fault_plan is not None:
        from repro.fault import inject as fault_inject
        outer_fn = fault_inject.wrap_outer(backend.outer, fault_plan)
    t_total0 = time.perf_counter()
    for i in range(i_start, len(cs)):
        c = cs[i]
        t0 = time.perf_counter()
        with obs.span("path.point") as point_span:
            if not cfg.warm_start:
                state = backend.init_state()
            else:
                # refresh margins from w once per point: O(one matvec),
                # stops f32 z-drift from accumulating across the sweep
                with obs.span("path.refresh"):
                    state = state._replace(z=backend.margins(state.w))
            t_loop = time.perf_counter()
            state, res = engine_loop.run_outer_loop(
                outer_fn, state, float(c),
                max_outer=solver.max_outer, tol_kkt=float(tols[i]),
                recheck_every=solver.recheck_every,
                tol_rel_obj=solver.tol_rel_obj, callback=callback)
            loop_s = time.perf_counter() - t_loop
            with obs.span("path.pull"):
                weights[i] = backend.host_weights(state.w)
            val_acc = None
            if val is not None:
                with obs.span("path.validate"):
                    val_acc = validation_accuracy(val, val_y, weights[i])
            seconds = time.perf_counter() - t0
            points.append(PathPoint(
                c=float(c), objective=res.objective,
                nnz=int(np.count_nonzero(weights[i])),
                kkt=(float(res.history.kkt[-1]) if res.history.kkt.size
                     else 0.0),
                n_outer=res.n_outer, seconds=seconds,
                converged=res.converged, val_accuracy=val_acc,
                between_s=seconds - loop_s,
                n_active=tuple(int(a) for a in res.history.n_active)))
            if point_span is not None:
                point_span.set_metadata(i=i, c=float(c),
                                        n_outer=res.n_outer,
                                        converged=res.converged)
        obs.inc("path.points")
        if verbose:
            p = points[-1]
            extra = f" val_acc={p.val_accuracy:.4f}" if p.val_accuracy is not None else ""
            print(f"[path] c={p.c:.5g} F={p.objective:.5f} nnz={p.nnz} "
                  f"kkt={p.kkt:.2e} tol={tols[i]:.2e} iters={p.n_outer} "
                  f"t={p.seconds:.2f}s{extra}", flush=True)
        if ckpt is not None:
            ckpt.save_path(backend, state, point_index=i, cs=cs,
                           c_max=c_max, points=points, weights=weights)
        if fault_plan is not None:
            fault_plan.fire_point(i)

    return PathResult(c_max=c_max, cs=cs, points=points, weights=weights,
                      best_index=pick_best(points),
                      total_seconds=time.perf_counter() - t_total0,
                      last_history=res.history if res else None,
                      last_postmortem=res.postmortem if res else None,
                      last_state=state)


def path_summary(result: PathResult) -> dict:
    """JSON-ready summary (weights omitted — they go to .npy if wanted)."""
    return {
        "c_max": result.c_max,
        "total_seconds": result.total_seconds,
        "best_index": result.best_index,
        "best_c": None if result.best is None else result.best.c,
        "points": [p._asdict() for p in result.points],
    }
