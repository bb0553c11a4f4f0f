import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the FULL-size ModelConfig and abstract params/opt-state/cache
     (ShapeDtypeStruct everywhere — nothing is allocated),
  2. jits train_step / serve_step with explicit in/out shardings on the
     production mesh ((16,16) 'data','model'; multi-pod (2,16,16) adds
     'pod'),
  3. ``.lower().compile()`` — failures here (sharding mismatch, bad
     collective) are bugs,
  4. records memory_analysis(), cost_analysis() and the collective-byte
     parse of the optimized HLO into benchmarks/results/dryrun/<cell>.json
     for the roofline analysis (EXPERIMENTS.md section Dry-run / Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --cell train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod --skip-done
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import cell_input_specs
from repro.models import decode as dec
from repro.models.config import SHAPE_CELLS, cell_applicable, get_shape_cell
from repro.models.transformer import Model
from repro.optim.adamw import AdamWConfig, adamw_abstract_state
from repro.train.steps import (_batch_spec, cache_specs, make_serve_step,
                               make_train_step, opt_state_specs)
from repro.utils import hlo as hlo_util
from repro.utils import hlo_cost

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _named(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def model_flops_estimate(cfg, cell) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE) for train;
    2*N(_active)*D for inference cells."""
    from repro.utils.params import active_param_count
    n_active = active_param_count(cfg)
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * cell.global_batch


def lower_cell(arch: str, cell_name: str, multi_pod: bool,
               opt_overrides: dict | None = None):
    """-> result dict (raises on lowering/compile failure)."""
    cfg = get_config(arch)
    cell = get_shape_cell(cell_name)
    ok, reason = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": cell_name,
                "multi_pod": multi_pod, "status": "SKIP", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg, mesh)
    t0 = time.perf_counter()

    if cell.kind in ("train",):
        opt_cfg = AdamWConfig(**(opt_overrides or {}))
        step_fn, p_specs, o_specs = make_train_step(model, opt_cfg)
        params = model.abstract_params()
        opt = adamw_abstract_state(params, opt_cfg)
        batch = cell_input_specs(cfg, cell)
        b_specs = _batch_spec(mesh, batch, model.rules)
        jitted = jax.jit(
            step_fn,
            in_shardings=(_named(mesh, p_specs), _named(mesh, o_specs),
                          _named(mesh, b_specs)),
            out_shardings=(_named(mesh, p_specs), _named(mesh, o_specs),
                           None),
        )
        lowered = jitted.lower(params, opt, batch)
    elif cell.kind == "prefill":
        from repro.train.steps import make_prefill_step
        step_fn = make_prefill_step(model)
        params = model.abstract_params()
        p_specs = model.param_specs()
        batch = cell_input_specs(cfg, cell)
        b_specs = _batch_spec(mesh, batch, model.rules)
        out_spec = NamedSharding(mesh, P(
            tuple(a for a in ("pod", "data") if a in mesh.shape), None,
            "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None))
        jitted = jax.jit(step_fn,
                         in_shardings=(_named(mesh, p_specs),
                                       _named(mesh, b_specs)),
                         out_shardings=out_spec)
        lowered = jitted.lower(params, batch)
    else:  # decode
        step_fn = make_serve_step(model)
        params = model.abstract_params()
        p_specs = model.param_specs()
        cache = dec.init_cache(model, cell.global_batch, cell.seq_len,
                               concrete=False)
        c_specs = cache_specs(model, cache)
        batch = cell_input_specs(cfg, cell)
        b_specs = _batch_spec(mesh, batch, model.rules)
        jitted = jax.jit(
            step_fn,
            in_shardings=(_named(mesh, p_specs), _named(mesh, c_specs),
                          _named(mesh, b_specs)["tokens"]),
            out_shardings=(None, _named(mesh, c_specs)),
        )
        lowered = jitted.lower(params, cache, batch["tokens"])

    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    raw_cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    # trip-count-aware accounting (XLA's cost_analysis counts while bodies
    # once — see utils/hlo_cost.py). All numbers below are PER DEVICE: the
    # compiled module is the per-partition SPMD program.
    mc = hlo_cost.analyze(hlo_text)
    n_chips = 1
    for s in mesh.shape.values():
        n_chips *= s

    mf = model_flops_estimate(cfg, cell)
    result = {
        "arch": arch, "cell": cell_name, "multi_pod": multi_pod,
        "status": "OK",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        # totals across chips = per-device * n_chips (SPMD symmetric)
        "flops": mc.flops * n_chips,
        "hbm_bytes": mc.bytes * n_chips,
        "collective_bytes": mc.total_coll_bytes * n_chips,
        "collectives": {k: [mc.coll_bytes[k] * n_chips,
                            mc.coll_count.get(k, 0)]
                        for k in mc.coll_bytes},
        "trip_counts": mc.trip_counts,
        "raw_cost_analysis": {k: float(v) for k, v in raw_cost.items()
                              if isinstance(v, (int, float))
                              and "{" not in k},
        "model_flops": mf,
        "memory": {
            "bytes_per_device": getattr(
                mem, "temp_size_in_bytes", 0) + getattr(
                mem, "argument_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
    }
    roof = hlo_util.Roofline(
        flops=result["flops"], hbm_bytes=result["hbm_bytes"],
        coll_bytes=result["collective_bytes"], n_chips=n_chips,
        model_flops=mf, coll_count=sum(mc.coll_count.values()))
    result["roofline"] = roof.as_dict()
    return result


def lower_solver_cell(loss_name: str = "logistic", multi_pod: bool = False,
                      ls_kind: str = "batched", fuse: bool = True,
                      s: int = 2 ** 19, n: int = 2 ** 20,
                      P_local: int = 64):
    """Dry-run the paper's own technique at production scale: one sharded
    PCDN outer iteration over a dense (s, n) problem (kdda-class scale in
    the dense adaptation; X f32 = s*n*4 bytes sharded (data x model))."""
    from repro.engine.sharded import ShardedPCDNConfig, make_sharded_outer
    mesh = make_production_mesh(multi_pod=multi_pod)
    daxes = ("pod", "data") if multi_pod else ("data",)
    cfg = ShardedPCDNConfig(P_local=P_local, c=1.0, loss_name=loss_name,
                            data_axes=daxes, ls_kind=ls_kind,
                            fuse_collectives=fuse)
    d_sz = 1
    for a in daxes:
        d_sz *= mesh.shape[a]
    m_sz = mesh.shape[cfg.model_axis]
    n_local = n // m_sz
    outer = make_sharded_outer(cfg, mesh, n_local)

    dspec = daxes if len(daxes) > 1 else daxes[0]
    Xs = jax.ShapeDtypeStruct((s, n), jnp.float32)
    ys = jax.ShapeDtypeStruct((s,), jnp.float32)
    ws = jax.ShapeDtypeStruct((n,), jnp.float32)
    zs = jax.ShapeDtypeStruct((s,), jnp.float32)
    ks = jax.ShapeDtypeStruct((2,), jnp.uint32)
    # engine-contract extras: active mask, recheck flag, traced c
    acts = jax.ShapeDtypeStruct((n,), jnp.bool_)
    rs = jax.ShapeDtypeStruct((), jnp.bool_)
    cs_ = jax.ShapeDtypeStruct((), jnp.float32)
    shardings = (NamedSharding(mesh, P(dspec, "model")),
                 NamedSharding(mesh, P(dspec)),
                 NamedSharding(mesh, P("model")),
                 NamedSharding(mesh, P(dspec)),
                 NamedSharding(mesh, P()),
                 NamedSharding(mesh, P("model")),
                 NamedSharding(mesh, P()),
                 NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    lowered = jax.jit(
        lambda X, y, w, z, k, a, r, c: outer(X, y, w, z, k, a, r, c),
        in_shardings=shardings).lower(Xs, ys, ws, zs, ks, acts, rs, cs_)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    mem = compiled.memory_analysis()
    mc = hlo_cost.analyze(compiled.as_text())
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    # useful flops per outer iteration: every feature's grad/hess + Xd =
    # 6 s n (dense adaptation; matches the paper's O(s n) per outer pass)
    mf = 6.0 * s * n
    result = {
        "arch": f"pcdn-{loss_name}", "cell": f"solve_{s}x{n}",
        "multi_pod": multi_pod, "status": "OK",
        "variant": {"ls_kind": ls_kind, "fuse_collectives": fuse},
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": mc.flops * n_chips,
        "hbm_bytes": mc.bytes * n_chips,
        "collective_bytes": mc.total_coll_bytes * n_chips,
        "collectives": {k: [mc.coll_bytes[k] * n_chips,
                            mc.coll_count.get(k, 0)]
                        for k in mc.coll_bytes},
        "trip_counts": mc.trip_counts,
        "model_flops": mf,
        "memory": {"bytes_per_device": getattr(
            mem, "temp_size_in_bytes", 0) + getattr(
            mem, "argument_size_in_bytes", 0)},
    }
    roof = hlo_util.Roofline(
        flops=result["flops"], hbm_bytes=result["hbm_bytes"],
        coll_bytes=result["collective_bytes"], n_chips=n_chips,
        model_flops=mf, coll_count=sum(mc.coll_count.values()))
    result["roofline"] = roof.as_dict()
    return result


def cell_path(arch, cell, multi_pod):
    tag = "mp" if multi_pod else "sp"
    return os.path.join(RESULTS_DIR, f"{arch}__{cell}__{tag}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--cell", default=None,
                    choices=[c.name for c in SHAPE_CELLS])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--solver", action="store_true",
                    help="dry-run the sharded PCDN solver cell instead")
    ap.add_argument("--ls-kind", default="batched",
                    choices=["batched", "backtracking"])
    ap.add_argument("--no-fuse", action="store_true")
    args = ap.parse_args()

    if args.solver:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
            res = lower_solver_cell(multi_pod=mp, ls_kind=args.ls_kind,
                                    fuse=not args.no_fuse)
            tag = "mp" if mp else "sp"
            variant = f"{args.ls_kind}{'_nofuse' if args.no_fuse else ''}"
            path = os.path.join(RESULTS_DIR,
                                f"pcdn-solver__{variant}__{tag}.json")
            with open(path, "w") as fh:
                json.dump(res, fh, indent=1)
            r = res["roofline"]
            print(f"[dryrun] pcdn-solver {variant} mp={mp}: "
                  f"comp={r['t_compute_s']:.3f}s mem={r['t_memory_s']:.3f}s "
                  f"coll={r['t_collective_s']:.3f}s "
                  f"bottleneck={r['bottleneck']} "
                  f"useful={r['useful_flops_ratio']:.2f}")
        return 0

    os.makedirs(RESULTS_DIR, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    cells = [args.cell] if args.cell else [c.name for c in SHAPE_CELLS]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for mp in meshes:
        for arch in archs:
            for cell in cells:
                path = cell_path(arch, cell, mp)
                if args.skip_done and os.path.exists(path):
                    print(f"[dryrun] cached {arch} {cell} mp={mp}")
                    continue
                tag = "multi-pod" if mp else "single-pod"
                print(f"[dryrun] {arch} x {cell} ({tag}) ...", flush=True)
                try:
                    res = lower_cell(arch, cell, mp)
                except Exception as e:
                    failures += 1
                    res = {"arch": arch, "cell": cell, "multi_pod": mp,
                           "status": "FAIL", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    print(f"  FAIL: {e}")
                with open(path, "w") as fh:
                    json.dump(res, fh, indent=1)
                if res["status"] == "OK":
                    r = res["roofline"]
                    print(f"  OK lower={res['lower_s']}s "
                          f"compile={res['compile_s']}s "
                          f"flops={res['flops']:.3e} "
                          f"coll={res['collective_bytes']/1e9:.2f}GB "
                          f"bottleneck={r['bottleneck']} "
                          f"mem/dev={res['memory']['bytes_per_device']/1e9:.1f}GB",
                          flush=True)
                elif res["status"] == "SKIP":
                    print(f"  SKIP: {res['reason']}")
    print(f"[dryrun] done, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
