"""Serving driver: load a model artifact, score traffic, report latency.

    python -m repro.launch.solve --dataset a9a --save-model m.json
    python -m repro.launch.predict --model m.json --dataset a9a

Loads a `repro.serve` artifact (binary model, OVR head, or path family),
stacks it into a `ModelBank`, and streams the dataset's rows through the
microbatched prediction engine (DESIGN.md section 10.4): requests are
padded to bucket shapes so only the first call per bucket compiles, and
per-bucket latency / throughput are reported. `--layout padded_csc`
serves the feature-major sparse request path; `--use-kernels` routes
margins through the Pallas kernels (kernels/pcdn_margin.py), whose
outputs are checked against the XLA reference scorer on the first batch.

`--route` picks the dense-layout scorer: "sparse" (union-gather),
"dense" (densified matmul), or "auto", which reads the measured
crossover table committed in BENCH_serve.json (DESIGN.md 14.6).
`--best-c` reduces a kind="path" artifact to its best grid point
(serve.artifact.pick_best_c) before serving.

`--serve` switches to the continuous-batching loop (DESIGN.md 14):
open-loop Poisson traffic at `--rate` rps with per-request budget
`--slo-ms`, reporting admission-to-response p50/p99, padding
efficiency and SLO violations. `--swap-model` hot-swaps a second
artifact in mid-stream (best-c selected live for path artifacts) at
`--swap-at` of the run, demonstrating the zero-recompile swap.
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time

import numpy as np

from repro.utils.compile_cache import enable_compile_cache
from repro.data import load_libsvm, paper_like
from repro.data.libsvm import CSRMatrix
from repro.serve.artifact import ModelFamily, load_model, pick_best_c
from repro.serve.batcher import MicroBatcher, default_buckets
from repro.serve.loop import ServeLoop, drive_poisson
from repro.serve.predict import (ModelBank, decide, predict,
                                 scorer_cache_sizes)


def _load_requests(args, n_features: int):
    """-> (requests, y_raw, codes) — y_raw in the loader's normalized
    vocabulary (+-1 for <= 2 labels), codes the sorted-vocabulary class
    codes, both None when unlabeled. File datasets honor --layout;
    profile names score the held-out test split of the generator."""
    if os.path.exists(args.dataset):
        csr, codes, classes = load_libsvm(args.dataset,
                                          n_features=n_features,
                                          layout="csr",
                                          return_classes=True)
        codes = np.asarray(codes, np.int64)
        y_raw = np.asarray(classes)[codes]
        if args.layout == "padded_csc":
            return csr, y_raw, codes
        return csr.to_dense(), y_raw, codes
    _, _, Xte, yte, _ = paper_like(args.dataset, with_test=True,
                                   seed=args.seed)
    codes = (np.asarray(yte) > 0).astype(np.int64)
    if args.layout == "padded_csc":
        return CSRMatrix.from_dense(Xte), yte, codes
    return Xte, yte, codes


def _accuracy(bank: ModelBank, preds: np.ndarray, y_raw, codes) -> dict:
    """Per-kind accuracy: one scalar for binary/ovr, per-point for path.

    OVR banks compare on class CODES: both the loader's vocabulary and
    `bank.classes` are sorted ascending, so codes align even when the
    bank was trained on raw labels a binary file normalizes to +-1
    (the {3, 7}-labeled two-class case).
    """
    if bank.kind == "ovr":
        pred_codes = np.searchsorted(np.asarray(bank.classes), preds)
        return {"accuracy": float(np.mean(pred_codes == codes))}
    if bank.kind == "path":
        accs = [float(np.mean(preds[:, k] == y_raw))
                for k in range(bank.n_models)]
        best = int(np.argmax(accs))
        return {"per_point": accs, "best_index": best,
                "best_accuracy": accs[best]}
    return {"accuracy": float(np.mean(preds == y_raw))}


def _run_serve(args, family) -> dict:
    """--serve: the continuous-batching loop under open-loop Poisson
    load (DESIGN.md section 14), with an optional mid-stream hot-swap."""
    from repro.launch.common import DTYPES, finish_obs
    if args.layout != "dense":
        raise SystemExit("--serve admits dense request rows only "
                         "(--layout dense)")
    # the per-request budget (the internal flush deadline) gets headroom
    # under the SLO so deadline-flush jitter still lands responses under
    # it — the SLO is what we report p99 against, the budget is the knob
    budget_s = 0.8 * args.slo_ms / 1e3
    loop = ServeLoop(family, max_batch=args.max_batch,
                     buckets=([int(b) for b in args.buckets.split(",")]
                              if args.buckets else None),
                     default_budget_s=budget_s,
                     max_queue=args.max_queue, route=args.route,
                     use_kernels=args.use_kernels,
                     dtype=DTYPES[args.dtype])
    bank = loop.bank()
    print(f"[serve] model={args.model} kind={bank.kind} K={bank.n_models} "
          f"n={bank.n_features} sparsity={bank.sparsity():.4f} "
          f"routes={loop.stats()['models']['default']['routes']} "
          f"warm compiles={loop.stats()['compiles']}")

    requests, y_raw, codes = _load_requests(args, bank.n_features)
    X = np.asarray(requests, np.float32)     # loop serves dense rows
    n_req = min(args.serve_requests,
                X.shape[0] if args.limit is None else args.limit)

    caches0 = scorer_cache_sizes()
    swap_state = {}
    swapper = None
    if args.swap_model:
        swap_family = load_model(args.swap_model)
        delay = args.swap_at * args.serve_requests / args.rate

        def _fire():
            time.sleep(delay)
            swap_state["ticket"] = loop.swap(model=swap_family)

        swapper = threading.Thread(target=_fire, daemon=True)
        swapper.start()

    drive = drive_poisson(loop, X[:n_req], rate_rps=args.rate,
                          n_requests=args.serve_requests,
                          budget_s=budget_s)
    if swapper is not None:
        swapper.join()
        swap_state["ticket"].installed.wait(10.0)
    loop.stop()
    caches1 = scorer_cache_sizes()
    recompiles = sum(caches1.values()) - sum(caches0.values())

    results = drive.pop("results")
    stats = loop.stats()
    slot = stats["models"]["default"]
    pad_total = slot["rows"] + slot["pad_rows"]
    slo_violations = sum(r.latency_s > args.slo_ms / 1e3 for r in results)
    payload = {"model": args.model, "kind": bank.kind, "mode": "serve",
               "rate_rps": args.rate, "slo_ms": args.slo_ms,
               "route": args.route, **drive,
               "padding_efficiency": (slot["rows"] / pad_total
                                      if pad_total else None),
               "slo_violations": slo_violations,
               "recompiles": recompiles, "stats": stats}
    if args.swap_model:
        versions = sorted({r.version for r in results})
        payload["swap"] = {"model": args.swap_model,
                           "installed_version": swap_state["ticket"].version,
                           "response_versions": versions}
        print(f"[serve] hot-swap -> version "
              f"{swap_state['ticket'].version}, response versions "
              f"{versions}, recompiles={recompiles}")
    if results:
        # served margins in submit order (arrivals cycle the first n_req
        # rows), for checking against from-scratch scoring
        payload["margins"] = np.stack([r.margins for r in results]).tolist()
    if y_raw is not None and drive["rejects"] == 0 and results \
            and not drive["failed"] and bank.kind == "binary" \
            and not args.swap_model:
        preds = decide(bank, np.stack([r.margins for r in results]))
        # arrivals cycle the first n_req rows in submit order
        sel = np.arange(len(results)) % n_req
        payload["accuracy"] = float(np.mean(preds == y_raw[sel]))
        print(f"[serve] accuracy={payload['accuracy']:.4f}")
    print(f"[serve] {drive['responses']} responses at "
          f"{drive['offered_rps']:.0f} rps offered: "
          f"p50={1e3 * (drive['p50_s'] or 0):.2f}ms "
          f"p99={1e3 * (drive['p99_s'] or 0):.2f}ms "
          f"rejects={drive['rejects']} failed={drive['failed']} "
          f"slo_violations={slo_violations} "
          f"padding_eff={payload['padding_efficiency'] or 0:.3f} "
          f"flushes={slot['flushes']}")
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
        print(f"[serve] wrote {args.out}")
    finish_obs(args, meta={
        "cli": "predict--serve", "model": args.model,
        "dataset": args.dataset, "rate_rps": args.rate,
        "p99_s": drive["p99_s"], "rejects": drive["rejects"],
        "failed": drive["failed"], "recompiles": recompiles})
    if drive["failed"]:
        raise SystemExit(f"[serve] {drive['failed']} of "
                         f"{args.serve_requests} responses failed; first "
                         f"error: {drive['error']}")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True,
                    help="artifact JSON from --save-model (solve or path)")
    ap.add_argument("--dataset", required=True,
                    help="paper dataset profile name or a .libsvm path")
    ap.add_argument("--layout", default="dense",
                    choices=["dense", "padded_csc"],
                    help="request layout served to the margin engine")
    ap.add_argument("--use-kernels", action="store_true",
                    help="route margins through the Pallas kernels")
    ap.add_argument("--dtype", default="fp32", choices=["fp32", "bf16"],
                    help="bank storage dtype: bf16 halves bank memory "
                         "and scorer HBM traffic; margins still "
                         "accumulate in f32 (DESIGN.md section 12)")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated bucket sizes (default: powers "
                         "of two up to --max-batch)")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--limit", type=int, default=None,
                    help="serve only the first N requests")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write predictions + bucket stats JSON here")
    ap.add_argument("--route", default="sparse",
                    choices=["sparse", "dense", "auto"],
                    help="dense-layout scorer: union-gather, densified "
                         "matmul, or the measured BENCH_serve.json "
                         "crossover (DESIGN.md 14.6)")
    ap.add_argument("--best-c", nargs="?", const="val_accuracy",
                    default=None, metavar="METRIC",
                    help="serve only the best grid point of a path "
                         "artifact, selected by METRIC "
                         "(default val_accuracy; 'nnz' = sparsest)")
    ap.add_argument("--serve", action="store_true",
                    help="continuous-batching loop under Poisson load "
                         "instead of the synchronous batcher")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="[--serve] offered load, requests/s")
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="[--serve] per-request latency budget")
    ap.add_argument("--serve-requests", type=int, default=512,
                    help="[--serve] total Poisson arrivals to drive")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="[--serve] admission-control queue bound "
                         "(default: unbounded)")
    ap.add_argument("--swap-model", default=None,
                    help="[--serve] artifact to hot-swap in mid-stream "
                         "(path artifacts: best-c selected live)")
    ap.add_argument("--swap-at", type=float, default=0.5,
                    help="[--serve] fire the swap at this fraction of "
                         "the run")
    from repro.launch.common import add_obs_args, finish_obs, setup_obs
    add_obs_args(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    setup_obs(args)

    from repro.launch.common import DTYPES
    family = load_model(args.model)
    if args.best_c is not None:
        i, best = pick_best_c(family, metric=args.best_c)
        print(f"[predict] --best-c {args.best_c}: grid point {i} "
              f"(c={best.c:.4g}, nnz={best.nnz}, "
              f"meta={best.meta.get(args.best_c)})")
        family = ModelFamily(kind="binary", models=(best,),
                             provenance=family.provenance)
    if args.serve:
        return _run_serve(args, family)
    bank = ModelBank.from_family(family, dtype=DTYPES[args.dtype])
    print(f"[predict] model={args.model} kind={bank.kind} "
          f"K={bank.n_models} n={bank.n_features} a_max={bank.a_max} "
          f"sparsity={bank.sparsity():.4f} dtype={args.dtype}")

    requests, y_raw, codes = _load_requests(args, bank.n_features)
    n_req = requests.shape[0]
    if args.limit is not None and args.limit < n_req:
        if isinstance(requests, CSRMatrix):
            hi = requests.indptr[args.limit]
            requests = CSRMatrix(requests.data[:hi], requests.indices[:hi],
                                 requests.indptr[:args.limit + 1],
                                 (args.limit, requests.shape[1]))
        else:
            requests = requests[:args.limit]
        y_raw = None if y_raw is None else y_raw[:args.limit]
        codes = None if codes is None else codes[:args.limit]
        n_req = args.limit

    buckets = ([int(b) for b in args.buckets.split(",")] if args.buckets
               else default_buckets(args.max_batch))
    k_max = (requests.max_col_nnz()
             if isinstance(requests, CSRMatrix) else None)
    batcher = MicroBatcher(bank, buckets=buckets, layout=args.layout,
                           use_kernels=args.use_kernels, k_max=k_max,
                           route=args.route)

    # kernel-vs-reference guard on the first bucket's worth of traffic
    if args.use_kernels:
        head = min(n_req, buckets[0])
        if args.layout == "dense":
            probe = np.asarray(requests[:head], np.float32)
        else:
            probe = CSRMatrix(
                requests.data[:requests.indptr[head]],
                requests.indices[:requests.indptr[head]],
                requests.indptr[:head + 1], (head, requests.shape[1]))
            from repro.data.libsvm import csr_to_padded_csc
            probe = csr_to_padded_csc(probe, k_max=k_max)
        zk = np.asarray(predict(bank, probe, use_kernels=True))
        zr = np.asarray(predict(bank, probe, use_kernels=False))
        err = float(np.abs(zk - zr).max()) if zk.size else 0.0
        print(f"[predict] kernel-vs-reference max |err| = {err:.2e}")
        # bf16 banks: both scorers read identically-rounded bf16 weights
        # but reduce in different orders, so allow a looser (still f32-
        # accumulation-sized) band than the fp32 path
        rtol = 1e-4 if args.dtype == "fp32" else 1e-3
        if err > rtol * max(1.0, float(np.abs(zr).max())):
            raise SystemExit("Pallas margin kernel disagrees with the "
                             "reference scorer")

    margins = batcher.predict(requests)
    stats = batcher.stats()
    preds = decide(bank, margins)
    payload = {"model": args.model, "kind": bank.kind,
               "n_requests": int(n_req), "layout": args.layout,
               "use_kernels": args.use_kernels, "stats": stats}
    if y_raw is not None:
        payload.update(_accuracy(bank, preds, y_raw, codes))
        acc = payload.get("accuracy", payload.get("best_accuracy"))
        print(f"[predict] accuracy={acc:.4f} over {n_req} requests")
    for b in stats["buckets"]:
        rps = b["rows_per_s"]
        print(f"[predict] bucket={b['bucket']:>5} calls={b['calls']} "
              f"rows={b['rows']} pad={b['pad_rows']} "
              f"warmup={b['warmup_seconds'] * 1e3:.1f}ms "
              + (f"steady={rps:.0f} rows/s" if rps else "steady=n/a"))
    print(f"[predict] compiles={stats['compiles']} "
          f"(one warmup per bucket shape)")

    if args.out:
        payload["predictions"] = np.asarray(preds).tolist()
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=1, default=float)
        print(f"[predict] wrote {args.out}")
    finish_obs(args, meta={
        "cli": "predict", "model": args.model, "dataset": args.dataset,
        "layout": args.layout, "n_requests": int(n_req),
        "steady_rows_per_s": stats.get("steady_rows_per_s")})
    return payload


if __name__ == "__main__":
    main()
