"""One-off sweeps that fix a cell's frozen settings, run on the chip.

    python3 bench/sweep.py eps  --workload solve.real-sim --seeds 11 12 \
        --ladder 1e-1 3e-2 1e-2 3e-3 1e-3 --max-outer 400
    python3 bench/sweep.py knee --workload serve.real-sim --seed 11 \
        --rates 500 1000 2000 4000 --seconds 10

`eps`: per partition seed, one cold solve of the cell's data set to the
smallest rung, and
the iterations and host seconds at which the engine's KKT first fell
under each rung times KKT(w = 0). The cell's eps is the smallest rung at
which a solve leaves room for three in the window.

`knee`: one serving run of the cell per rate, each with its own set-up;
the knee is the highest rate at which p99 stays within the SLO and the
generator keeps up. The cell's rate is about 4/5 of it.

Each prints one JSON line per seed or rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), p) for p in ("src", "")]

from bench import data, reference, run, solve  # noqa: E402


def eps_ladder(cell, seeds, ladder, max_outer: int) -> None:
    from repro.engine import loop as engine_loop
    from repro.launch import common
    config, traffic = cell["config"], cell["traffic"]
    c = float(config["c"])
    for seed in seeds:
        t0 = time.perf_counter()
        X, y = data.build(config["data"], config["data"]["seed"])
        kkt0 = reference.kkt_at_zero(X, y, c)
        ns = solve.cli_args(traffic["solver_args"], seed, False)
        backend, _ = common.make_backend(ns, X, y, c, config["loss"])
        engine_loop.solve(backend, c, max_outer=2, tol_kkt=0.0)
        t_setup = time.perf_counter() - t0
        res = engine_loop.solve(backend, c, max_outer=max_outer,
                                tol_kkt=min(ladder) * kkt0)
        h = res.history
        rungs = {}
        for eps in ladder:
            hit = [i for i, k in enumerate(h.kkt) if k <= eps * kkt0]
            rungs[str(eps)] = (None if not hit else
                               {"iters": hit[0] + 1,
                                "seconds": float(h.wall_time[hit[0]])})
        print(json.dumps({"workload": cell["workload"]["name"], "seed": seed,
                          "kkt0": kkt0, "setup_s": t_setup,
                          "n_outer": int(res.n_outer),
                          "final_kkt_rel": float(h.kkt[-1]) / kkt0,
                          "seconds": float(h.wall_time[-1]),
                          "rungs": rungs}), flush=True)


def knee(cell, seed: int, rates, seconds: float, devices) -> None:
    for rate in rates:
        cell["traffic"] = dict(cell["traffic"], rate_rps=rate)
        out, notes = run.run_cell(cell, seed, seconds, False, devices,
                                  t_setup0=time.perf_counter())
        m = out["metrics"]
        print(json.dumps(run._finite({
            "rate_rps": rate, "p99_ms": m["serve_p99_ms"]["value"],
            "rows_per_s": m["serve_rows_per_s"]["value"],
            "correct": out["correct"], "notes": notes}), default=float),
            flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("eps", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[11])
    ap.add_argument("--ladder", type=float, nargs="+",
                    default=[1e-1, 3e-2, 1e-2, 3e-3, 1e-3])
    ap.add_argument("--max-outer", type=int, default=400)
    ap.add_argument("--rates", type=float, nargs="+", default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_spec(), args.workload)
    run.enable_compile_cache()
    devices = run.check_chip(cell["workload"]["chips"])
    if args.what == "eps":
        eps_ladder(cell, args.seeds, args.ladder, args.max_outer)
    else:
        knee(cell, args.seeds[0], args.rates, args.seconds, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
