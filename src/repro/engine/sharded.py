"""Sharded execution backend: 2-D (data x model) shard_map PCDN
(DESIGN.md sections 3.4 / 4 / 9.3).

Layout:

    X : (s, n)  sharded  P(("pod","data"), "model")   - samples x features
    y : (s,)    sharded  P(("pod","data"))
    z : (s,)    sharded  P(("pod","data"))            - margins, replicated
                                                        over "model"
    w : (n,)    sharded  P("model")                   - replicated over data
    active:(n,) sharded  P("model")                   - un-shrunk mask

Each bundle draws P_local = P / n_model features *per model shard*
(stratified random partition — still a disjoint cover of N per outer
iteration, i.e. a valid Gauss-Seidel rule; see DESIGN.md section 3.4).

Collective schedule per bundle iteration (3 phases, all fused to the
minimum payload):

    1. psum over data-like axes of [g_part ; h_part]   (2*P_local floats)
    2. psum over "model" of the partial margins X_B d_B (s_local floats)
    3. ONE psum over ALL axes of the (Q,) per-candidate Armijo vector
       (loss part pre-divided by n_model, l1 part by n_data, so a single
       all-axes psum yields loss-sum-over-samples + l1-sum-over-features)

Phase 2 is the paper's footnote-3 reduction-sum for d.x_i, mapped onto the
ICI; phases 1+3 carry O(P + Q) floats — the paper's low-communication
property preserved at pod scale.

Both design-matrix layouts ride the same schedule: layout="dense" shards
the raw (s, n) array as above, layout="padded_csc" shards the padded
feature-major sparse arrays from `shard_problem_sparse` — each shard holds
its own columns' nonzeros with row ids local to its sample range, so the
shard-local bundle math drops from O(s_l * P_local) to O(P_local * k_max)
while every collective payload stays identical (DESIGN.md section 7.4).

This module used to be a standalone solver (`core/sharded.py`) with its
own outer loop, stop criterion and history code. It is now an *execution
backend* implementing the engine contract of `repro.engine.loop`:

    outer(w, z, key, active, recheck, c)
      -> (w, z, key, f, kkt, nnz, mean_q, active, n_active)

with `c` TRACED (one compiled program serves a whole c-sweep), active-set
shrinking (per-shard `bundles.partition_active`; the fori_loop trip count
is the pmax over model shards of the local active bundle counts, so every
shard runs the same number of collectives while shrunk features cost zero
compute), and optional routing of the shard-local bundle reductions
through the fused Pallas direction kernels (`use_kernels` — the kernels
compute the g/h PARTIALS per shard; the Newton direction is formed after
the phase-1 psum, so the collective schedule is unchanged).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from repro.core import bundles as B
from repro.core.design_matrix import padded_row_support
from repro.core.direction import delta_decrement, newton_direction
from repro.core.linesearch import (ArmijoParams, candidate_alphas,
                                   select_first_satisfying)
from repro.core.losses import HESSIAN_FLOOR, get_loss
from repro.engine.loop import EngineState

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ShardedPCDNConfig:
    P_local: int                   # bundle features per model shard
    c: float
    loss_name: str = "logistic"
    armijo: ArmijoParams = ArmijoParams()
    elastic_net_l2: float = 0.0
    data_axes: Sequence[str] = ("data",)   # ("pod","data") multi-pod
    model_axis: str = "model"
    seed: int = 0
    # --- perf variants (EXPERIMENTS.md section Perf) ---
    # "batched": one fused psum carries all Q Armijo candidates (TPU-native)
    # "backtracking": paper-faithful sequential loop — one scalar psum per
    #                 backtracking step (the OpenMP structure, kept as the
    #                 reproduction baseline)
    ls_kind: str = "batched"
    # fuse [g;h] into one collective and [Xd;Delta] into another; the
    # unfused variant issues 4 separate psums per bundle (baseline)
    fuse_collectives: bool = True
    # route the shard-local bundle reductions through the fused Pallas
    # direction kernels (partials only; see module docstring)
    use_kernels: bool = False
    # -- line-search / margin scope (DESIGN.md section 11.4) -----------------
    # "support" restricts the phase-3 loss evaluation, the u/v factors
    # and the z_l update to the bundle's shard-local row support. Valid
    # when the model axis has size 1 (data-sharded meshes): the local
    # slab support then IS the true support of the post-psum margin
    # delta. With model parallelism the bundle's rows span shards whose
    # supports are unknown locally, so full scope is kept (the
    # allgather-merge of per-shard supports is the documented follow-up).
    # The (Q,) phase-3 psum payload is IDENTICAL in both scopes.
    ls_scope: str = "auto"
    # -- active-set shrinking (same semantics as PCDNConfig; DESIGN.md 8.2)
    shrink: bool = False
    shrink_tol: float = 0.01
    recheck_every: int = 1
    tol_kkt: float = 1e-3          # un-shrink threshold (keep == stop tol)
    # -- observability (DESIGN.md section 13.2; same contract as
    # PCDNConfig.record_aux): surface per-bundle (q, alpha) as a 10th
    # outer output. Both are derived from all-axes psums (the phase-3
    # Armijo vector), so they are replicated and leave the shard_map
    # with P() out_specs — no extra collectives.
    record_aux: bool = False
    # -- diagnostics (DESIGN.md section 15.1; same contract as
    # PCDNConfig.record_kkt_vec): surface the per-feature KKT violation
    # vector as an extra outer output for attribution. `viol` is (n_local,)
    # per model shard and already replicated over data axes (it derives
    # from the data-psummed gradient), so it exits the shard_map with a
    # P(model_axis) spec and concatenates to the global (n_pad,) vector —
    # padded columns carry exactly zero violation (w == 0, g == 0).
    record_kkt_vec: bool = False

    @property
    def all_axes(self):
        return tuple(self.data_axes) + (self.model_axis,)


def _axis_size(axis) -> Array:
    return jax.lax.psum(1, axis)


def _dspec(cfg: ShardedPCDNConfig):
    return (tuple(cfg.data_axes) if len(cfg.data_axes) > 1
            else cfg.data_axes[0])


def make_sharded_outer(cfg: ShardedPCDNConfig, mesh: Mesh,
                       n_local: int, layout: str = "dense"):
    """Build the jitted sharded engine iteration.

    layout="dense": fn(X, y, w, z, key, active, recheck, c);
    layout="padded_csc": fn(col_rows, col_vals, y, w, z, key, active,
    recheck, c) where col_rows/col_vals are the (n, D*k_max) packed
    per-(column, data-shard) local-row arrays from `shard_problem_sparse`
    (DESIGN.md section 7.4). Both return the engine 9-tuple
    (w, z, key, f, kkt, nnz, mean_q, active, n_active) with identical
    collective schedules — only the shard-local bundle math differs.
    n_local = features per model shard (static). `c` and `recheck` are
    traced scalars. With cfg.record_aux a 10th output (q (b,), alpha
    (b,)) carries the per-bundle line-search telemetry (DESIGN.md
    section 13.2); under shrinking, slots past the pmax trip count hold
    sentinels q == -1 / alpha == nan. With cfg.record_kkt_vec the
    per-feature violation vector (n_pad,) follows the aux tuple
    (DESIGN.md section 15.1); extras are dispatched by structure.
    """
    loss = get_loss(cfg.loss_name)
    gamma = cfg.armijo.gamma
    sigma = cfg.armijo.sigma
    P_local = cfg.P_local
    data_axes = tuple(cfg.data_axes)
    model_axis = cfg.model_axis
    if layout not in ("dense", "padded_csc"):
        raise ValueError(f"unknown layout {layout!r}")
    if cfg.use_kernels:
        from repro.kernels import ops as kops

    # static support-scope eligibility (DESIGN.md section 11.4)
    n_model_static = int(mesh.shape[model_axis])
    support_ok = (layout == "padded_csc" and cfg.ls_kind == "batched"
                  and n_model_static == 1)
    if cfg.ls_scope == "support" and not support_ok:
        raise ValueError(
            "ls_scope='support' on the sharded backend requires "
            "layout='padded_csc', ls_kind='batched' and a model axis of "
            f"size 1 (got layout={layout!r}, ls_kind={cfg.ls_kind!r}, "
            f"model={n_model_static}); with model parallelism a bundle's "
            "row support spans shards and is unknown locally — use "
            "ls_scope='auto' to fall back to full scope.")
    elif cfg.ls_scope not in ("support", "auto", "full"):
        raise ValueError(f"unknown ls_scope {cfg.ls_scope!r}")

    def outer_local(*args):
        """Runs inside shard_map: every array is this shard's block."""
        if layout == "dense":
            X_l, y_l, w_l, z_l, active_l, key, recheck, c = args
        else:
            rows_l, vals_l, y_l, w_l, z_l, active_l, key, recheck, c = args
        s_l = z_l.shape[0]
        n_model = _axis_size(model_axis)
        n_data = _axis_size(data_axes)
        m_idx = jax.lax.axis_index(model_axis)
        # identical permutation across data shards of one model column:
        key, sub = jax.random.split(key)
        sub = jax.random.fold_in(sub, m_idx)
        alphas = candidate_alphas(cfg.armijo, z_l.dtype)   # (Q,)

        def gather_local(idx):
            """-> layout-specific slab for this shard's rows of bundle B."""
            if layout == "dense":
                XB, _ = B.gather_slab(X_l, idx)            # (s_l, P_local)
                return XB
            valid = idx < n_local
            safe = jnp.minimum(idx, n_local - 1)
            rB = jnp.where(valid[:, None], jnp.take(rows_l, safe, axis=0),
                           s_l)                            # (P_local, k)
            vB = jnp.take(vals_l, safe, axis=0) * \
                valid[:, None].astype(vals_l.dtype)
            return rB, vB

        def grad_hess_parts(slab, u, v, w_B):
            """Shard-local partial [g ; h] of one bundle (pre-psum)."""
            if cfg.use_kernels:
                # fused Pallas reduction; l2=0 keeps the g partial raw
                # (the elastic-net diagonal is applied after the phase-1
                # psum). The kernel floors each h PARTIAL at its internal
                # 1e-12, so the psum carries up to n_data extra floors —
                # bounded by D*1e-12, below f32 resolution of any
                # meaningful h. The kernel's locally-formed d is
                # discarded — the direction needs the GLOBAL g, h.
                if layout == "dense":
                    _, g, h = kops.pcdn_direction(slab, u, v, w_B, l2=0.0)
                else:
                    rB, vB = slab
                    _, g, h = kops.pcdn_sparse_direction(rB, vB, u, v, w_B,
                                                         l2=0.0)
                return g, h
            if layout == "dense":
                return slab.T @ u, jnp.square(slab).T @ v
            rB, vB = slab
            ug = jnp.take(u, rB, mode="fill", fill_value=0)
            vg = jnp.take(v, rB, mode="fill", fill_value=0)
            return (jnp.sum(ug * vB, axis=1),
                    jnp.sum(vg * jnp.square(vB), axis=1))

        def margin_delta_part(slab, d):
            if layout == "dense":
                return slab @ d
            rB, vB = slab
            return jnp.zeros((s_l,), vB.dtype).at[rB].add(
                vB * d[:, None], mode="drop")

        def full_grad_part(u):
            if layout == "dense":
                return X_l.T @ u
            ug = jnp.take(u, rows_l, mode="fill", fill_value=0)
            return jnp.sum(ug * vals_l, axis=1)

        # static per-shard scope decision ("auto" needs the local slab
        # bound P_local * k_max to beat the local sample count with the
        # same margin as the local backend — pcdn.AUTO_SUPPORT_MARGIN)
        if layout == "padded_csc":
            from repro.core.pcdn import AUTO_SUPPORT_MARGIN
            use_support = support_ok and (
                cfg.ls_scope == "support" or
                (cfg.ls_scope == "auto" and
                 AUTO_SUPPORT_MARGIN * P_local * rows_l.shape[1] <= s_l))
        else:
            use_support = False

        def bundle_step_support(carry, idx):
            """Support-restricted bundle step (DESIGN.md section 11.4):
            same phase-1 [g;h] psum and phase-3 (Q,) psum as the full-
            scope step; the per-sample passes between them touch only
            the bundle's shard-local row support."""
            w_l, z_l = carry
            rB, vB = gather_local(idx)
            w_B, _ = B.gather_vec(w_l, idx)
            support, pos = padded_row_support(rB, s_l)
            z_R = jnp.take(z_l, support, mode="fill", fill_value=0)
            y_R = jnp.take(y_l, support, mode="fill", fill_value=1)
            u_R = c * loss.dz(z_R, y_R)
            v_R = c * loss.d2z(z_R, y_R)
            if cfg.use_kernels:
                # pos is the support-local row id array: same kernel,
                # u/v handed over in support order (all gathers in
                # bounds; padding entries carry value 0)
                _, g_part, h_part = kops.pcdn_sparse_direction(
                    pos, vB, u_R, v_R, w_B, l2=0.0)
            else:
                g_part = jnp.sum(jnp.take(u_R, pos) * vB, axis=1)
                h_part = jnp.sum(jnp.take(v_R, pos) * jnp.square(vB),
                                 axis=1)
            # -- phase 1: grad/hess psum over sample shards (unchanged)
            if cfg.fuse_collectives:
                gh = jax.lax.psum(jnp.concatenate([g_part, h_part]),
                                  data_axes)
                g, h = gh[:P_local], gh[P_local:]
            else:
                g = jax.lax.psum(g_part, data_axes)
                h = jax.lax.psum(h_part, data_axes)
            if cfg.elastic_net_l2:
                g = g + cfg.elastic_net_l2 * w_B
                h = h + cfg.elastic_net_l2
            h = jnp.maximum(h, HESSIAN_FLOOR)
            d = newton_direction(g, h, w_B)
            # -- phase 2: model axis has size 1, so the margin-delta
            # psum is the identity and only the scalar Delta crosses it;
            # the (s_l,) dense delta is never built.
            Delta = jax.lax.psum(delta_decrement(g, h, w_B, d, gamma),
                                 model_axis)
            delta_R = jnp.zeros_like(z_R).at[pos].add(vB * d[:, None])
            # -- phase 3: the SAME (Q,) all-axes psum, loss part now
            # reduced over the support rows only
            zq = z_R[None, :] + alphas[:, None] * delta_R[None, :]
            loss_part = c * jnp.sum(
                loss.value(zq, y_R[None, :]) -
                loss.value(z_R, y_R)[None, :], axis=-1)
            l1_part = (jnp.sum(
                jnp.abs(w_B[None, :] + alphas[:, None] * d[None, :]),
                axis=-1) - jnp.sum(jnp.abs(w_B)))
            fused = loss_part / jnp.asarray(n_model, z_l.dtype) + \
                l1_part / jnp.asarray(n_data, z_l.dtype)
            f_deltas = jax.lax.psum(fused, cfg.all_axes)
            res = select_first_satisfying(f_deltas, alphas, Delta, sigma)
            w_l = B.scatter_add(w_l, idx, res.alpha * d)
            z_l = z_l.at[support].add(res.alpha * delta_R, mode="drop")
            return (w_l, z_l), (res.n_steps, res.alpha)

        def bundle_step(carry, idx):
            w_l, z_l = carry
            slab = gather_local(idx)
            w_B, _ = B.gather_vec(w_l, idx)
            u = c * loss.dz(z_l, y_l)
            v = c * loss.d2z(z_l, y_l)
            g_part, h_part = grad_hess_parts(slab, u, v, w_B)
            # -- phase 1: grad/hess psum over sample shards
            if cfg.fuse_collectives:
                gh = jax.lax.psum(jnp.concatenate([g_part, h_part]),
                                  data_axes)
                g, h = gh[:P_local], gh[P_local:]
            else:  # baseline: two separate collectives
                g = jax.lax.psum(g_part, data_axes)
                h = jax.lax.psum(h_part, data_axes)
            if cfg.elastic_net_l2:
                g = g + cfg.elastic_net_l2 * w_B
                h = h + cfg.elastic_net_l2
            h = jnp.maximum(h, HESSIAN_FLOOR)
            d = newton_direction(g, h, w_B)
            # Delta (Eq. 7) sums over the *global* bundle -> psum over model
            Delta_part = delta_decrement(g, h, w_B, d, gamma)
            dz_part = margin_delta_part(slab, d)           # (s_l,)
            # -- phase 2: margins of the bundle step (+ Delta when fused)
            if cfg.fuse_collectives:
                packed = jax.lax.psum(
                    jnp.concatenate([dz_part, Delta_part[None]]), model_axis)
                delta_z, Delta = packed[:-1], packed[-1]
            else:
                delta_z = jax.lax.psum(dz_part, model_axis)
                Delta = jax.lax.psum(Delta_part, model_axis)

            if cfg.ls_kind == "batched":
                # -- phase 3: ONE all-axes psum of the Q-candidate vector
                zq = z_l[None, :] + alphas[:, None] * delta_z[None, :]
                loss_part = c * jnp.sum(
                    loss.value(zq, y_l[None, :]) -
                    loss.value(z_l, y_l)[None, :], axis=-1)
                l1_part = (jnp.sum(
                    jnp.abs(w_B[None, :] + alphas[:, None] * d[None, :]),
                    axis=-1) - jnp.sum(jnp.abs(w_B)))
                fused = loss_part / jnp.asarray(n_model, z_l.dtype) + \
                    l1_part / jnp.asarray(n_data, z_l.dtype)
                f_deltas = jax.lax.psum(fused, cfg.all_axes)
                res = select_first_satisfying(f_deltas, alphas, Delta, sigma)
                alpha, n_steps = res.alpha, res.n_steps
            else:
                # paper-faithful Algorithm 4: sequential backtracking, one
                # scalar psum PER candidate — the latency baseline.
                f_base = c * jnp.sum(loss.value(z_l, y_l))

                def cond(st):
                    q, alpha_, done = st
                    return jnp.logical_and(~done, q < cfg.armijo.max_steps)

                def body(st):
                    q, alpha_, _ = st
                    lo = c * jnp.sum(loss.value(z_l + alpha_ * delta_z,
                                                y_l)) - f_base
                    l1 = jnp.sum(jnp.abs(w_B + alpha_ * d)) - \
                        jnp.sum(jnp.abs(w_B))
                    fd = jax.lax.psum(
                        lo / jnp.asarray(n_model, z_l.dtype) +
                        l1 / jnp.asarray(n_data, z_l.dtype), cfg.all_axes)
                    ok = fd <= sigma * alpha_ * Delta
                    return (q + 1,
                            jnp.where(ok, alpha_, alpha_ * cfg.armijo.beta),
                            ok)

                q, alpha, ok = jax.lax.while_loop(
                    cond, body, (jnp.int32(0),
                                 jnp.asarray(1.0, z_l.dtype),
                                 jnp.asarray(False)))
                alpha = jnp.where(ok, alpha, 0.0)
                n_steps = q
            w_l = B.scatter_add(w_l, idx, alpha * d)
            z_l = z_l + alpha * delta_z
            return (w_l, z_l), (n_steps, alpha)

        step_fn = bundle_step_support if use_support else bundle_step

        if cfg.shrink:
            # Per-shard active partition; the trip count is the pmax over
            # model shards, so every shard executes the same collective
            # schedule — shards with fewer active bundles run sentinel-
            # only bundles (zero contribution, zero update).
            idxs, b_active = B.partition_active(sub, active_l, P_local)
            trip = jax.lax.pmax(b_active, model_axis)
            if cfg.record_aux:
                b_max = idxs.shape[0]
                aux0 = (jnp.full((b_max,), -1, jnp.int32),
                        jnp.full((b_max,), jnp.nan, z_l.dtype))
            else:
                aux0 = ()

            def body(t, carry):
                wz, q_sum, aux = carry
                wz, (n_steps, alpha) = step_fn(wz, idxs[t])
                if cfg.record_aux:
                    aux = (aux[0].at[t].set(n_steps.astype(jnp.int32)),
                           aux[1].at[t].set(alpha.astype(z_l.dtype)))
                return wz, q_sum + n_steps.astype(jnp.float32), aux

            (w_l, z_l), q_sum, aux = jax.lax.fori_loop(
                0, trip, body, ((w_l, z_l), jnp.float32(0.0), aux0))
            if cfg.record_aux:
                aux_q, aux_alpha = aux
            mean_q = q_sum / jnp.maximum(trip, 1).astype(jnp.float32)
        else:
            idxs = B.partition(sub, n_local, P_local)      # (b, P_local)
            (w_l, z_l), (steps, step_alphas) = jax.lax.scan(
                step_fn, (w_l, z_l), idxs)
            mean_q = jnp.mean(steps.astype(jnp.float32))
            if cfg.record_aux:
                aux_q = steps.astype(jnp.int32)
                aux_alpha = step_alphas.astype(z_l.dtype)

        # diagnostics: objective + FULL-set KKT violation (replicated)
        f_loss = jax.lax.psum(c * jnp.sum(loss.value(z_l, y_l)), data_axes)
        f_l1 = jax.lax.psum(jnp.sum(jnp.abs(w_l)), model_axis)
        f = f_loss + f_l1
        if cfg.elastic_net_l2:
            f = f + 0.5 * cfg.elastic_net_l2 * jax.lax.psum(
                jnp.sum(jnp.square(w_l)), model_axis)
        # full local gradient for KKT: (n_local,) psum over data
        u = c * loss.dz(z_l, y_l)
        g_full = jax.lax.psum(full_grad_part(u), data_axes)
        if cfg.elastic_net_l2:
            g_full = g_full + cfg.elastic_net_l2 * w_l
        viol = jnp.abs(jnp.where(
            w_l > 0, g_full + 1.0,
            jnp.where(w_l < 0, g_full - 1.0,
                      jnp.maximum(jnp.abs(g_full) - 1.0, 0.0))))
        kkt = jax.lax.pmax(jnp.max(viol), cfg.all_axes)
        if cfg.shrink:
            interior = (w_l == 0) & (jnp.abs(g_full) < 1.0 - cfg.shrink_tol)
            active_l = active_l & ~interior
            active_l = active_l | (recheck & (viol > cfg.tol_kkt))
        nnz = jax.lax.psum(jnp.sum((w_l != 0).astype(jnp.int32)),
                           model_axis)
        n_active = jax.lax.psum(jnp.sum(active_l.astype(jnp.int32)),
                                model_axis)
        base = (w_l, z_l, f, kkt, nnz, mean_q, active_l, n_active)
        if cfg.record_aux:
            # q/alpha come out of the all-axes phase-3 psum: replicated
            # on every shard, so they exit the shard_map with P() specs.
            base = base + ((aux_q, aux_alpha),)
        if cfg.record_kkt_vec:
            base = base + (viol,)
        return base

    dspec = _dspec(cfg)

    if layout == "dense":
        design_specs = (P(dspec, model_axis),)   # X
    else:
        design_specs = (P(model_axis, dspec),    # col_rows (n, D*k_max)
                        P(model_axis, dspec))    # col_vals
    in_specs = design_specs + (
        P(dspec),               # y
        P(model_axis),          # w
        P(dspec),               # z
        P(model_axis),          # active
        P(),                    # key (replicated)
        P(),                    # recheck
        P(),                    # c
    )

    out_specs = (P(model_axis), P(dspec), P(), P(), P(), P(),
                 P(model_axis), P())
    if cfg.record_aux:
        out_specs = out_specs + ((P(), P()),)
    if cfg.record_kkt_vec:
        # viol is (n_local,) per model shard, replicated over data axes
        out_specs = out_specs + (P(model_axis),)

    mapped = jax.shard_map(
        outer_local, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs, check_vma=False,
    )

    def outer(*args):
        *design_y, w, z, key, active, recheck, c = args
        key, sub = jax.random.split(key)
        out = mapped(*design_y, w, z, active, sub, recheck, c)
        w, z, f, kkt, nnz, mean_q, active, n_active = out[:8]
        base = (w, z, key, f, kkt, nnz, mean_q, active, n_active)
        # pass extras (aux tuple and/or kkt vector) through in protocol
        # order; the engine host loop dispatches them by structure.
        return base + tuple(out[8:])

    return jax.jit(outer)


def make_sharded_margins(cfg: ShardedPCDNConfig, mesh: Mesh, s_local: int,
                         layout: str = "dense"):
    """Jitted z = X w on the mesh (warm-start refresh between path points).

    dense: fn(X, w) -> z; padded_csc: fn(col_rows, col_vals, w) -> z.
    """
    model_axis = cfg.model_axis
    dspec = _dspec(cfg)

    def margins_local(*args):
        if layout == "dense":
            X_l, w_l = args
            z_part = X_l @ w_l
        else:
            rows_l, vals_l, w_l = args
            z_part = jnp.zeros((s_local,), vals_l.dtype).at[rows_l].add(
                vals_l * w_l[:, None], mode="drop")
        return jax.lax.psum(z_part, model_axis)

    if layout == "dense":
        in_specs = (P(dspec, model_axis), P(model_axis))
    else:
        in_specs = (P(model_axis, dspec), P(model_axis, dspec),
                    P(model_axis))
    mapped = jax.shard_map(margins_local, mesh=mesh, in_specs=in_specs,
                           out_specs=P(dspec), check_vma=False)
    return jax.jit(mapped)


def shard_problem(X: np.ndarray, y: np.ndarray, mesh: Mesh,
                  cfg: ShardedPCDNConfig):
    """Place (X, y) and fresh (w, z) onto the mesh with the PCDN layout.
    Pads s and n so shards are equal-sized. Returns device arrays."""
    dspec = _dspec(cfg)
    d_sz = int(np.prod([mesh.shape[a] for a in cfg.data_axes]))
    m_sz = mesh.shape[cfg.model_axis]
    s, n = X.shape
    s_pad = (-s) % d_sz
    n_pad = (-n) % m_sz
    if s_pad or n_pad:
        X = np.pad(X, ((0, s_pad), (0, n_pad)))
        y = np.pad(y, (0, s_pad), constant_values=1.0)  # zero rows: no grad
    Xs = jax.device_put(X, NamedSharding(mesh, P(dspec, cfg.model_axis)))
    ys = jax.device_put(y, NamedSharding(mesh, P(dspec)))
    w = jax.device_put(np.zeros(X.shape[1], X.dtype),
                       NamedSharding(mesh, P(cfg.model_axis)))
    z = jax.device_put(np.zeros(X.shape[0], X.dtype),
                       NamedSharding(mesh, P(dspec)))
    return Xs, ys, w, z


def _is_csr_like(X) -> bool:
    return all(hasattr(X, a) for a in ("data", "indices", "indptr", "shape"))


def _host_c_max(X, y, loss_name: str) -> float:
    """Analytic path start 1 / ||X^T phi'(0, y)||_inf from the host-side
    data (one rmatvec; matches L1Problem.c_max — DESIGN.md section 8.1)."""
    loss = get_loss(loss_name)
    s, n = int(X.shape[0]), int(X.shape[1])
    y32 = jnp.asarray(np.asarray(y), jnp.float32)
    u0 = np.asarray(loss.dz(jnp.zeros((s,), jnp.float32), y32), np.float32)
    if _is_csr_like(X):
        rows = np.repeat(np.arange(s, dtype=np.int64),
                         np.diff(np.asarray(X.indptr)))
        g0 = np.zeros((n,), np.float32)
        np.add.at(g0, np.asarray(X.indices),
                  np.asarray(X.data, np.float32) * u0[rows])
    else:
        g0 = np.asarray(X, np.float32).T @ u0
    denom = float(np.max(np.abs(g0)))
    if denom <= 0.0:
        raise ValueError("degenerate problem: X^T phi'(0, y) == 0 "
                         "(no feature correlates with the labels)")
    return 1.0 / denom


def shard_problem_sparse(X, y: np.ndarray, mesh: Mesh,
                         cfg: ShardedPCDNConfig, k_max: int = None):
    """Sparse placer: per-(model column, data shard) padded local rows.

    X: dense np array or CSR-like (.data/.indices/.indptr/.shape) — the
    latter never densifies. Builds

        col_rows : (n_pad, D * k_max) int32   local row id or sentinel s_l
        col_vals : (n_pad, D * k_max) float32

    packed so shard (di, mi) sees the (n_local, k_max) block of its own
    columns with row ids local to its sample range — axis 0 is sharded
    over "model", axis 1 over the data axes (DESIGN.md section 7.4).
    k_max = max nnz of any (column, data-shard) cell unless given.
    Returns (col_rows, col_vals, ys, w, z) device arrays.
    """
    dspec = _dspec(cfg)
    d_sz = int(np.prod([mesh.shape[a] for a in cfg.data_axes]))
    m_sz = mesh.shape[cfg.model_axis]

    if _is_csr_like(X):
        s, n = X.shape
        vals = np.asarray(X.data, dtype=np.float32)
        cols = np.asarray(X.indices, dtype=np.int64)
        rows = np.repeat(np.arange(s, dtype=np.int64),
                         np.diff(np.asarray(X.indptr)))
    else:
        X = np.asarray(X)
        s, n = X.shape
        rows, cols = np.nonzero(X)
        vals = X[rows, cols].astype(np.float32)

    s_pad = s + (-s) % d_sz
    n_pad = n + (-n) % m_sz
    s_l = s_pad // d_sz
    y_full = np.ones((s_pad,), np.float32)  # zero rows: no gradient
    y_full[:s] = y

    # group nnz by (column, data shard) and rank within each group
    di = rows // s_l
    local_r = (rows % s_l).astype(np.int32)
    group = cols * d_sz + di
    order = np.argsort(group, kind="stable")
    group, local_r, cols_s, vals_s = (group[order], local_r[order],
                                      cols[order], vals[order])
    counts = np.bincount(group, minlength=n_pad * d_sz).astype(np.int64)
    k = int(max(1, counts.max() if counts.size else 1))
    if k_max is not None:
        if k > int(k_max):
            raise ValueError(f"k_max={k_max} < max (column, shard) nnz {k}")
        k = int(k_max)
    start = np.concatenate([[0], np.cumsum(counts)])
    pos = np.arange(group.shape[0], dtype=np.int64) - start[group]
    col_rows = np.full((n_pad, d_sz * k), s_l, np.int32)
    col_vals = np.zeros((n_pad, d_sz * k), np.float32)
    slot = (group % d_sz) * k + pos
    col_rows[cols_s, slot] = local_r
    col_vals[cols_s, slot] = vals_s

    rows_d = jax.device_put(
        col_rows, NamedSharding(mesh, P(cfg.model_axis, dspec)))
    vals_d = jax.device_put(
        col_vals, NamedSharding(mesh, P(cfg.model_axis, dspec)))
    ys = jax.device_put(y_full, NamedSharding(mesh, P(dspec)))
    w = jax.device_put(np.zeros(n_pad, np.float32),
                       NamedSharding(mesh, P(cfg.model_axis)))
    z = jax.device_put(np.zeros(s_pad, np.float32),
                       NamedSharding(mesh, P(dspec)))
    return rows_d, vals_d, ys, w, z


class ShardedBackend:
    """Engine execution backend over a multi-device mesh.

    Places (X, y) once at construction (the expensive host->device step),
    compiles one dynamic-c outer iteration and one margins program, and
    then serves any number of solves / path points against them — the
    composition that makes warm-started c-sweeps with shrinking run on a
    mesh (DESIGN.md section 9.3).

    Note: feature-count padding (n -> n_pad, multiple of the model-axis
    size) is internal; `n_features`/`host_weights` speak the REAL n.
    """

    def __init__(self, X, y: np.ndarray, mesh: Mesh,
                 cfg: ShardedPCDNConfig, layout: str = "auto",
                 k_max: Optional[int] = None):
        is_csr = _is_csr_like(X)
        if layout == "auto":
            layout = "padded_csc" if is_csr else "dense"
        if layout == "dense" and is_csr:
            raise ValueError("CSR input with layout='dense' would densify")
        self.mesh = mesh
        self.cfg = cfg
        self.layout = layout
        self._n = int(X.shape[1])
        self._s = int(X.shape[0])
        # eager: one host rmatvec now, so no reference to the (possibly
        # multi-GiB) host arrays survives construction
        self._c_max = _host_c_max(X, y, cfg.loss_name)
        d_sz = int(np.prod([mesh.shape[a] for a in cfg.data_axes]))

        if layout == "dense":
            Xs, ys, w0, z0 = shard_problem(np.asarray(X), np.asarray(y),
                                           mesh, cfg)
            self._design = (Xs,)
            n_pad, s_pad = Xs.shape[1], Xs.shape[0]
        else:
            rows_d, vals_d, ys, w0, z0 = shard_problem_sparse(
                X, np.asarray(y), mesh, cfg, k_max=k_max)
            self._design = (rows_d, vals_d)
            n_pad, s_pad = rows_d.shape[0], z0.shape[0]
        self._y = ys
        self._w0, self._z0 = w0, z0
        self.n_pad, self.s_pad = n_pad, s_pad
        self.n_local = n_pad // mesh.shape[cfg.model_axis]
        self._active0 = jax.device_put(
            np.ones((n_pad,), bool),
            NamedSharding(mesh, P(cfg.model_axis)))

        outer_fn = make_sharded_outer(cfg, mesh, self.n_local, layout)
        design, ys_ = self._design, self._y

        def outer(w, z, key, active, recheck, c):
            return outer_fn(*design, ys_, w, z, key, active, recheck, c)

        self.outer = outer
        self._margins_fn = make_sharded_margins(cfg, mesh, s_pad // d_sz,
                                                layout)

    @property
    def n_features(self) -> int:
        return self._n

    @property
    def n_samples(self) -> int:
        return self._s

    @property
    def dtype(self):
        return jnp.float32

    def init_state(self, w0: Optional[np.ndarray] = None) -> EngineState:
        if w0 is None:
            w, z = self._w0, self._z0
        else:
            wf = np.zeros((self.n_pad,), np.float32)
            wf[:self._n] = np.asarray(w0, np.float32)
            w = jax.device_put(
                wf, NamedSharding(self.mesh, P(self.cfg.model_axis)))
            z = self.margins(w)
        return EngineState(w=w, z=z,
                           key=jax.random.PRNGKey(self.cfg.seed),
                           active=self._active0)

    def margins(self, w: Array) -> Array:
        return self._margins_fn(*self._design, w)

    def c_max(self) -> float:
        return self._c_max

    def host_weights(self, w: Array) -> np.ndarray:
        return np.asarray(w)[:self._n]

    def host_margins(self, z: Array) -> np.ndarray:
        """(n_samples,) host margins with the sample padding stripped —
        the mesh-agnostic checkpoint image of z."""
        return np.asarray(z)[:self._s]

    def restore_state(self, w, z=None, active=None, key=None) -> EngineState:
        """EngineState from UNPADDED host arrays (a `fault.checkpoint`
        snapshot — possibly written under a different device count or by
        the local backend). Re-pads to this mesh's n_pad/s_pad and
        device_puts with the PCDN layout; padded sample rows carry z = 0
        exactly as the margins program produces for zero-padded X rows,
        so a restored carry is bit-identical to a recomputed one."""
        n, s = self._n, self._s
        wf = np.zeros((self.n_pad,), np.float32)
        wf[:n] = np.asarray(w, np.float32).reshape(n)
        w_d = jax.device_put(
            wf, NamedSharding(self.mesh, P(self.cfg.model_axis)))
        if active is None:
            act_d = self._active0
        else:
            af = np.zeros((self.n_pad,), bool)
            af[:n] = np.asarray(active).reshape(n).astype(bool)
            act_d = jax.device_put(
                af, NamedSharding(self.mesh, P(self.cfg.model_axis)))
        if z is None:
            z_d = self.margins(w_d)
        else:
            zf = np.zeros((self.s_pad,), np.float32)
            zf[:s] = np.asarray(z, np.float32).reshape(s)
            z_d = jax.device_put(
                zf, NamedSharding(self.mesh, P(_dspec(self.cfg))))
        key_d = (jax.random.PRNGKey(self.cfg.seed) if key is None
                 else jnp.asarray(np.asarray(key), jnp.uint32))
        return EngineState(w=w_d, z=z_d, key=key_d, active=act_d)
