"""Readings that a cell's limits are set from, run on the chip.

    python3 bench/calibrate.py --workload solve.real-sim --seconds 10 \
        --seeds 101 102 ... [--control]

Runs the cell once per seed in this one process, exactly as `run.py`
does but with the window given here, and prints each run's compared
numbers as one JSON line. With `--control` the program runs its own
lower-precision path (bf16 design or bank storage), which the limits
must fail. `PERF.md` records both readings and the limit set between
them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), p) for p in ("src", "")]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = run.resolve(run.load_spec(), args.workload)
    run.enable_compile_cache()
    devices = run.check_chip(cell["workload"]["chips"])
    for seed in args.seeds:
        out, notes = run.run_cell(cell, seed, args.seconds, False, devices,
                                  t_setup0=time.perf_counter(),
                                  control=args.control)
        print(json.dumps(run._finite({
            "workload": args.workload, "seed": seed,
            "control": args.control, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "checks": {k: v["value"] for k, v in out["checks"].items()},
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "notes": notes}), default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
