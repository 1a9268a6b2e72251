"""The work a search needs, and its share of the chip's roofline.

Counted per dispatch from the cell's shapes and the leaves its query rows
route to (by the reference's descent), whatever implements the scan:

- bytes: every distinct candidate row of the probed leaves read once, at
  one byte per dimension (the rows are byte values; the program stores f32,
  so it moves four times this); plus the query rows (one byte per
  dimension) and the answers (``k`` ids and distances of 4 bytes each);
- operations: ``2 * dim`` for each (query row, candidate) pair.

The least time is the larger of bytes over HBM bandwidth and operations over
the bf16 peak (the distance contractions run at ``Precision.HIGHEST``, so
the program issues several bf16 passes per f32 product; the peak is that of
one pass).
"""

from __future__ import annotations

import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def dispatch_work(leaf_sizes: np.ndarray, q_leaf: np.ndarray, *, dim: int,
                  k: int) -> tuple[float, float]:
    """``(operations, bytes)`` one dispatch needs for its query rows'
    leaves ``q_leaf``."""
    sizes = leaf_sizes[q_leaf].astype(np.float64)
    pairs = float(sizes.sum())
    cand = float(leaf_sizes[np.unique(q_leaf)].astype(np.float64).sum())
    n = len(q_leaf)
    return 2.0 * dim * pairs, cand * dim + n * dim + n * k * 8.0


def roofline(ops: float, nbytes: float, device_s: float, peaks: dict):
    """``(share %, bound)``: the least time over ``device_s``, and which
    peak sets the least time. ``None`` share when nothing ran."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    if device_s <= 0 or (t_ops <= 0 and t_mem <= 0):
        return None, None
    bound = "hbm" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_ops, t_mem) / device_s, bound


def scan_roofline(run):
    """The scan's roofline share over the traced window's device-busy
    time, or ``None`` without a trace or dispatches."""
    if run.trace is None or not run.dispatch_queries:
        return None
    ops = nbytes = 0.0
    for q in run.dispatch_queries:
        o, b = dispatch_work(run.reference.leaf_sizes,
                             run.reference.leaves(q), dim=q.shape[1],
                             k=run.config["search"]["k"])
        ops += o
        nbytes += b
    share, bound = roofline(ops, nbytes, run.trace.busy_s, run.peaks)
    if share is not None:
        run.notes[f"roofline bound ({run.workload})"] = bound
    return share
