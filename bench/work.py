"""The least work of one PCDN outer iteration, and the chip's peaks.

`outer_iteration_work` counts what any implementation of one outer
iteration has to move and compute, whatever the route (XLA or Pallas),
the line-search scope or the padding:

- bytes: the design's stored entries read once (a padded column layout
  at its real nonzeros, 4 B of value and 4 B of row id each; a dense
  design at 4 B per entry), plus, for each of the ceil(n / P) bundles,
  z read and written and y read (4 B each) over the bundle's row
  support: all s rows for a dense design, and for a sparse one the
  expected number of distinct rows that P columns of mean degree m
  touch, s * (1 - exp(-P * m / s));
- flops: 7 per stored entry: the bundle gradient (2) and Hessian
  diagonal (3), and the margin update (2).

The bound is the larger of bytes over the HBM peak and flops over the
compute peak, so a share of it cannot pass 100% for any correct program.
"""
from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
FLOPS_PER_ENTRY = 7
VALUE_BYTES = 4
ROW_ID_BYTES = 4
STATE_BYTES_PER_ROW = 12        # z read, z written, y read


def outer_iteration_work(*, s: int, n: int, P: int, nnz: int,
                         dense: bool) -> dict:
    """-> {"bytes", "flops", "bundles", "rows_per_bundle"} of one outer
    iteration over an s x n design with `nnz` stored nonzeros."""
    bundles = math.ceil(n / P)
    if dense:
        entries = s * n
        design_bytes = VALUE_BYTES * entries
        rows = float(s)
    else:
        entries = nnz
        design_bytes = (VALUE_BYTES + ROW_ID_BYTES) * nnz
        rows = s * (1.0 - math.exp(-P * (nnz / n) / s))
    return {"bytes": design_bytes + bundles * STATE_BYTES_PER_ROW * rows,
            "flops": FLOPS_PER_ENTRY * entries,
            "bundles": bundles, "rows_per_bundle": rows}


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of one chip of this kind. A kind missing from the table
    is an error: a guessed peak would make every roofline share wrong."""
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(work: dict, peak: dict) -> float:
    """Least time for `work` at the chip's peaks."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["flops"] / peak["flops_per_s"])
