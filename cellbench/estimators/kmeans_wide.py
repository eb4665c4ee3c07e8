"""KMeans where a centre holds few rows: `estimators/kmeans.py`'s comparison,
with the rows that float32 cannot place taken either way.

The comparison is the same: the harness fits again with `maxIter - 1`, the
reference takes those centres ONE exact Lloyd step, and the timed fit's centres
must land there. What differs is what one row does. At k=20 a centre is the
mean of 400,000 rows and a row assigned otherwise moves it by nothing that can
be seen. At k=1000 on 357,376 rows a centre holds one to 2,000 rows, and one
row moves its worst coordinate by 5e-3 to 4e-2 of the RMS centre coordinate:
as far as a bfloat16 assignment moves it. And one row in three fits IS assigned
otherwise: the program ranks float32 squared distances near 3,000 to 6,000,
whose last bit is 2.4e-4 to 4.9e-4, so a row whose two nearest centres lie
closer together than that is nearest to either as far as float32 can say, while
the reference decides it in float64 (3 of 12 seeds on the chip, PR 28, each by
exactly one row).

So `center_step_err` here is read against the exact step as float32 may have
taken it: where a few centres miss the exact step, the rows of those centres
whose margin between two of them is under `TIE` (in float64, from the
differences) may each go to the other centre, if that brings both centres to
where the program put them. A row that is no tie is never moved, and more than
`MAX_OFF` centres off is no tie's doing: a bfloat16 assignment misplaces
hundreds of rows at margins up to a hundred times `TIE`, and stays as far off
as before. Inertia and cluster sizes are compared as `estimators/kmeans.py`
compares them.
"""

from __future__ import annotations

import sys
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .. import refs
from . import kmeans as base

ESTIMATOR = base.ESTIMATOR
build = base.build
fit_outputs = base.fit_outputs
did_all_work = base.did_all_work
fit_work = base.fit_work
kernel_work = base.kernel_work
check_transform = base.check_transform

# A centre further than this from the exact step (worst coordinate over the RMS
# centre coordinate) has lost or gained a row: float32 rounding of the update
# reads 1.3e-6 to 2.2e-6, one row of the largest cluster 5e-3 (chip, PR 28).
ROUNDING = 1e-4
# Squared-distance margin under which float32 cannot rank two centres: the
# program's `x2 - 2 x.c + c2` rounds twice at magnitudes of 3,000 to 6,000
# (last bit 2.4e-4 to 4.9e-4), on each of the two distances compared. The rows
# it placed otherwise on the chip tied by 8.1e-5, 1.1e-4 and 2.1e-4 (PR 28).
TIE = 2e-3
MAX_OFF = 16


def means(sums: np.ndarray, counts: np.ndarray, before: np.ndarray) -> np.ndarray:
    """The centres of `refs.lloyd_step` from its sums and counts."""
    new = sums / np.maximum(counts, 1)[:, None]
    return np.where(counts[:, None] > 0, new, np.asarray(before, np.float64))


def step_as_float32_may_take_it(X: np.ndarray, before: np.ndarray, labels: np.ndarray,
                                sums: np.ndarray, counts: np.ndarray,
                                got: np.ndarray) -> Tuple[np.ndarray, float, List[float]]:
    """(step, scale, margins): the exact step from `before`, with each row that
    ties between two of the centres `got` misses moved to the other one where
    that brings both to `got`; the RMS coordinate of the exact step, which
    errors are read against; and the margins of the rows moved."""
    before64 = np.asarray(before, np.float64)
    step = means(sums, counts, before64)
    scale = float(np.sqrt((step * step).mean()))

    def miss(j: int, total: np.ndarray, count: int) -> float:
        centre = total / count if count > 0 else before64[j]
        return float(np.abs(got[j] - centre).max() / scale)

    off = np.nonzero(np.abs(got - step).max(axis=1) / scale > ROUNDING)[0]
    if off.size == 0 or off.size > MAX_OFF:
        return step, scale, []
    rows = np.nonzero(np.isin(labels, off))[0]
    x = X[rows].astype(np.float64)
    d2 = np.stack([((x - before64[j]) ** 2).sum(axis=1) for j in off], axis=1)
    own = np.searchsorted(off, labels[rows])
    mine = d2[np.arange(len(rows)), own]
    d2[np.arange(len(rows)), own] = np.inf
    other = d2.argmin(axis=1)
    margin = d2.min(axis=1) - mine
    sums, counts = sums.copy(), counts.copy()
    moved = []
    for i in np.argsort(margin):
        if margin[i] >= TIE:
            break
        a, b = int(off[own[i]]), int(off[other[i]])
        now = max(miss(a, sums[a], counts[a]), miss(b, sums[b], counts[b]))
        then = max(miss(a, sums[a] - x[i], counts[a] - 1), miss(b, sums[b] + x[i], counts[b] + 1))
        if then < now:
            sums[a] -= x[i]
            sums[b] += x[i]
            counts[a] -= 1
            counts[b] += 1
            moved.append(float(margin[i]))
    return means(sums, counts, before64), scale, moved


def check_fit(X: np.ndarray, answers: List[Dict[str, Any]],
              refit: Callable[[Dict[str, Any]], Any],
              params: Dict[str, Any], control: bool = False) -> List[Dict[str, float]]:
    """Every fit of the window that was kept, against one exact Lloyd step
    from the `maxIter - 1` fit's centres, ties taken either way."""
    before = np.asarray(refit({"maxIter": int(params["maxIter"]) - 1}).cluster_centers_)
    k = len(before)
    labels, _, sums, counts = refs.assign(X, before, sums_for=k)
    if control:  # the reference in the program's place, one bf16 pass
        centers = refs.lloyd_step(X, before, low_precision=True).astype(np.float32)
        lab, inertia, _, _ = refs.assign(X, centers, low_precision=True)
        answers = [{"centers": centers, "inertia": inertia,
                    "sizes": np.bincount(lab, minlength=k)}]
    readings = []
    for outputs in base.distinct(answers, "centers")[:base.MAX_DISTINCT]:
        centers = np.asarray(outputs["centers"], np.float64)
        step, scale, moved = step_as_float32_may_take_it(X, before, labels, sums, counts,
                                                         centers)
        if moved:
            print(f"kmeans_wide: {len(moved)} tied row(s) taken the program's way, "
                  f"margins {moved}", file=sys.stderr)
        labels_ref, inertia_ref, _, _ = refs.assign(X, outputs["centers"])
        sizes_ref = np.bincount(labels_ref, minlength=k)
        same = [a for a in answers if np.array_equal(a["centers"], outputs["centers"])]
        readings.append({
            "center_step_err": float(np.abs(centers - step).max() / scale),
            "inertia_rel_err": max(abs(a["inertia"] - inertia_ref) for a in same) / inertia_ref,
            "sizes_mismatch_share": max(float(np.abs(a["sizes"] - sizes_ref).sum())
                                        for a in same) / (2.0 * len(X)),
        })
    return readings
