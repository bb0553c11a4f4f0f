"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full]``

Prints ``name,us_per_call,derived`` CSV lines (per the repo convention)
and writes JSON artifacts under benchmarks/results/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger eps grids / more datasets")
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run")
    args = ap.parse_args(argv)
    enable_compile_cache()
    quick = not args.full

    from benchmarks import (fig1_iterations_vs_P, fig2_time_vs_P,
                            fig3_svm_runtime, fig4_logistic_traces,
                            fig5_datasize_scaling, fig6_core_scaling,
                            roofline, table3_optimal_P)
    modules = [
        ("fig1", fig1_iterations_vs_P),
        ("fig2", fig2_time_vs_P),
        ("fig3", fig3_svm_runtime),
        ("fig4", fig4_logistic_traces),
        ("fig5", fig5_datasize_scaling),
        ("fig6", fig6_core_scaling),
        ("table3", table3_optimal_P),
        ("roofline", roofline),
    ]
    if args.only:
        keep = set(args.only.split(","))
        modules = [m for m in modules if m[0] in keep]

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            mod.run(quick=quick)
            print(f"# {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:
            failures += 1
            print(f"{name},0,ERROR")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
