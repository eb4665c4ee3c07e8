"""A plain reference for the regression forest: numpy, float64 sums, no
kernels, and nothing of `spark_rapids_ml_tpu` imported. Like `forest_ref.py`,
whose binning, weights and routing it leaves where they are, it grows no tree:
it holds a FITTED tree to what can be recomputed exactly from the rows.

A row's statistics are [w, w y, w y^2] (Spark's variance impurity); a node's
are their sums over its rows, and

* `impurity_w`: w * variance = sum(w y^2) - sum(w y)^2 / sum(w);
* `variance_gain`: Spark's weighted information gain of a split, from the
  parent's and the left child's sums: (impurity_w(parent) - impurity_w(left)
  - impurity_w(right)) / parent's weight, a variance;
* `node_sums`: every heap slot's sums from the slots the rows reach, level by
  level (`forest_ref.route` gives them), and which slots lie on the tree;
* `split_gains` / `best_split`: the gain of every (feature, bin) candidate of
  a node from its rows' bin ids, and the exact best.

`tests/test_forest_reference.py` loads this file by path (tier-1 imports no
`cellbench`), so there is one copy.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def row_stats(y: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(n, 3) float64 [w, w y, w y^2] of each row."""
    y = np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)
    return np.stack([w, w * y, w * y * y], axis=1)


def impurity_w(sums: np.ndarray) -> np.ndarray:
    """w * variance of sums [w, w y, w y^2] along the last axis."""
    sums = np.asarray(sums, np.float64)
    return sums[..., 2] - sums[..., 1] ** 2 / np.maximum(sums[..., 0], 1e-300)


def variance_gain(parent: np.ndarray, left: np.ndarray) -> np.ndarray:
    parent = np.asarray(parent, np.float64)
    left = np.asarray(left, np.float64)
    return (impurity_w(parent) - impurity_w(left) - impurity_w(parent - left)) \
        / np.maximum(parent[..., 0], 1e-300)


def node_sums(positions: List[np.ndarray], stats: np.ndarray, tree: Dict[str, np.ndarray]
              ) -> Tuple[np.ndarray, np.ndarray]:
    """(sums, reached): the (slots, 3) sums of every heap slot and which slots
    lie on the fitted tree (the root, and the children of its inner nodes).
    `positions[l]` is each row's slot after l levels (`forest_ref.route`); a
    row is counted at a level where it moved there (a row at a leaf stays)."""
    feature = np.asarray(tree["feature"])
    is_leaf = np.asarray(tree["is_leaf"])
    slots = feature.shape[0]
    sums = np.zeros((slots, stats.shape[1]), np.float64)
    for level, pos in enumerate(positions):
        moved = np.ones(pos.shape, bool) if level == 0 else pos != positions[level - 1]
        for k in range(stats.shape[1]):
            sums[:, k] += np.bincount(pos[moved], weights=stats[moved, k], minlength=slots)
    reached = np.zeros(slots, bool)
    reached[1] = True
    for p in np.flatnonzero(~is_leaf[:slots // 2] & (feature[:slots // 2] >= 0)):
        if reached[p]:  # ascending: parents before children
            reached[2 * p] = reached[2 * p + 1] = True
    return sums, reached


def split_gains(bins: np.ndarray, stats: np.ndarray, n_bins: int,
                min_instances: float = 1.0) -> np.ndarray:
    """(features, n_bins - 1) float64 variance gains of splitting these rows at
    "bin <= b" for every column of `bins` (rows, features) and b; a split that
    leaves a side under `min_instances` of weight reads -inf."""
    d = bins.shape[1]
    columns = np.ascontiguousarray(bins.T)  # a column of a row-major table is a strided read
    hist = np.empty((d, n_bins, stats.shape[1]), np.float64)
    for j in range(d):
        ids = columns[j].astype(np.intp)
        for k in range(stats.shape[1]):
            hist[j, :, k] = np.bincount(ids, weights=stats[:, k], minlength=n_bins)
    parent = hist[0].sum(axis=0)
    left = np.cumsum(hist, axis=1)[:, :-1]
    gains = variance_gain(parent[None, None, :], left)
    wl = left[..., 0]
    ok = (wl >= min_instances) & (parent[0] - wl >= min_instances)
    return np.where(ok, gains, -np.inf)


def best_split(bins: np.ndarray, stats: np.ndarray, n_bins: int
               ) -> Tuple[float, int, int, np.ndarray]:
    """(gain, feature, bin, all gains) of the exact best split of these rows
    over every column, the first in (feature, bin) order among equals."""
    gains = split_gains(bins, stats, n_bins)
    j, b = np.unravel_index(int(np.argmax(gains)), gains.shape)
    return float(gains[j, b]), int(j), int(b), gains
