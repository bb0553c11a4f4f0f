"""Perf hillclimb driver (EXPERIMENTS.md section Perf).

Two cells, both about the paper's own solver:

  kernel (default) — greedy coordinate hillclimb of the fused bundle
     kernel's launch config (kernels/autotune.tune strategy="hillclimb"):
     start from the hard-coded default launch, improve one axis at a
     time (block_q tiling of the Armijo candidate grid, the impl axis),
     log every accepted step. The climb trajectory IS the deliverable:
     it shows which axis bought what on this backend, and the winner is
     persisted into the autotune cache so every later solve picks it up.
  ladder — the collective-schedule ladder of the sharded solver:
     faithful sequential Armijo + unfused psums -> fused psums ->
     batched candidates (single psum), with kernel-fusion memory
     accounting. (The historical cells A/B — transformer dry-run
     experiments from the seed scaffold, unrelated to this paper's
     solver — were retired; their archived results remain under
     results/hillclimb/.)

Usage: PYTHONPATH=src python -m benchmarks.hillclimb [--cell kernel|ladder|all]
Writes benchmarks/results/hillclimb/<name>.json.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse
import json

from repro.utils.compile_cache import enable_compile_cache

RESULTS = os.path.join(os.path.dirname(__file__), "results", "hillclimb")


def save(name, payload):
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{name}.json"), "w") as fh:
        json.dump(payload, fh, indent=1, default=float)
    r = payload.get("roofline", {})
    if r:
        print(f"  {name}: comp={r['t_compute_s']:.3f} mem={r['t_memory_s']:.3f} "
              f"coll={r['t_collective_s']:.3f} useful={r['useful_flops_ratio']:.3f}",
              flush=True)


def cell_kernel(smoke: bool = False):
    """Autotune hillclimb on the fused bundle kernel (+ its sparse
    direction sibling): the measured counterpart of bench_kernels'
    exhaustive sweep, logging the greedy trajectory step by step."""
    import numpy as np
    from benchmarks import bench_kernels as bk
    from repro.kernels import autotune

    cells = [c for c in (bk.SMOKE_CELLS if smoke else bk.CELLS)
             if c[0] in ("pcdn_bundle", "pcdn_sparse_direction")]
    import jax.numpy as jnp
    for kernel, shape, build in cells:
        print(f"[kernel] climbing {kernel} {shape}...", flush=True)
        runner, _, _ = build(jnp.float32)
        res = autotune.tune(kernel, runner, autotune.shape_bucket(**shape),
                            jnp.float32, strategy="hillclimb",
                            repeats=2 if smoke else 5, persist=not smoke)
        for i, step in enumerate(res.trajectory):
            print(f"  step {i}: {step['config']} -> {step['us']:.0f}us",
                  flush=True)
        shape_tag = "_".join(f"{k}{v}" for k, v in sorted(shape.items()))
        save(f"kernel_{kernel}_{shape_tag}", {
            "kernel": kernel, "shape": shape,
            "default_us": res.default_us, "tuned_us": res.us,
            "speedup": res.speedup,
            "trajectory": list(res.trajectory),
            "n_measured": len(res.table),
        })
        print(f"  {kernel}: default={res.default_us:.0f}us "
              f"tuned={res.us:.0f}us x{res.speedup:.2f}", flush=True)


def cell_ladder():
    """pcdn collective-schedule ladder (sharded solver)."""
    from repro.launch.dryrun import lower_solver_cell
    ladder = [
        ("baseline_faithful", dict(ls_kind="backtracking", fuse=False)),
        ("fused_psums", dict(ls_kind="backtracking", fuse=True)),
        ("batched_linesearch", dict(ls_kind="batched", fuse=True)),
    ]
    for name, kw in ladder:
        print(f"[ladder] pcdn {name}...", flush=True)
        res = lower_solver_cell(**kw)
        save(f"C_pcdn_{name}", res)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="kernel",
                    choices=["kernel", "ladder", "all"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no cache writes")
    args = ap.parse_args()
    enable_compile_cache()
    if args.cell in ("kernel", "all"):
        cell_kernel(smoke=args.smoke)
    if args.cell in ("ladder", "all"):
        cell_ladder()


if __name__ == "__main__":
    main()
