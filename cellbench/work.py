"""Work and bytes an algorithm needs, from shapes alone, and the table of peaks.

These do not depend on which kernel ran. A share of a roofline is the least
time the chip could take (the larger of operations over peak FLOP/s and bytes
over peak bytes/s) over the device time measured in the trace.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")) -> Dict[str, float]:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def lloyd_work(rows: int, cols: int, k: int, n_iter: int) -> Dict[str, float]:
    """Lloyd's iterations over an (rows, cols) float32 table: the distance
    cross term `2*rows*k*cols` operations an iteration, and ONE read of the
    table an iteration (a fused assign-and-update kernel reads X once; the
    (rows, k) intermediates and the centres are not counted)."""
    return {
        "flops": float(n_iter) * 2.0 * rows * k * cols,
        "bytes": float(n_iter) * rows * cols * 4.0,
    }


def gram_work(rows: int, cols: int) -> Dict[str, float]:
    """The Gram matrix of an (rows, cols) float32 table: `2*rows*cols**2`
    operations and one read of the table."""
    return {"flops": 2.0 * rows * cols * cols, "bytes": rows * cols * 4.0}


def floor_seconds(work: Dict[str, float], peaks: Dict[str, Any], chips: int = 1) -> Dict[str, Any]:
    """The least seconds `chips` chips could take, and which peak bounds it."""
    t_flops = work["flops"] / (peaks["flops_per_s"] * chips)
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
