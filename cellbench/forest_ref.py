"""A plain reference for the forest builder: numpy, float64 sums, no kernels,
and nothing of `spark_rapids_ml_tpu` imported. It does not grow trees (a
level-wise builder's ties and feature draws cannot be reproduced from outside);
it holds a FITTED tree to what can be recomputed exactly:

* `bin_table`: a table's bin ids from given edges, `searchsorted(side="left")`
  a column: bin = #edges < x, so a value equal to an edge stays in its bin;
* `tree_weights`: each tree's row weights, drawn again by the stated rule
  (docs/api.md): ONE generator a fit, `np.random.default_rng(seed &
  0x7FFFFFFF)`, tree i takes its i-th draw of n values, `poisson(
  subsamplingRate, n)` under bootstrap;
* `node_counts`: every row routed down a fitted tree on its RAW values against
  the tree's thresholds (right iff x > threshold, in float32 as the values and
  thresholds are), and each node's weighted class counts;
* `gini_gain`: Spark's weighted information gain of a split from the parent's
  and the left child's class counts;
* `split_gains` / `best_split`: the gain of every (feature, bin) candidate of a
  node from its rows' bin ids, and the exact best.

`tests/test_forest_reference.py` loads this file by path (tier-1 imports no
`cellbench`), so there is one copy.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, Tuple

import numpy as np


def bin_table(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """(n, d) bin ids of `X` against (d, nbins - 1) ascending edges: uint8 up
    to 256 bins, else int32."""
    n, d = X.shape
    out = np.empty((n, d), np.uint8 if edges.shape[1] < 256 else np.int32)

    def block(s):  # a column of a row-major table is a strided read: transpose a block
        cols = np.ascontiguousarray(X[s:s + 16384].T)
        ids = np.empty(cols.shape, out.dtype)
        for j in range(d):
            ids[j] = np.searchsorted(edges[j], cols[j], side="left")
        out[s:s + 16384] = ids.T

    # searchsorted releases the interpreter's lock: a block a thread
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(0, n, 16384)))
    return out


def tree_weights(seed: int, n: int, n_trees: int, subsampling_rate: float = 1.0,
                 bootstrap: bool = True) -> Iterator[np.ndarray]:
    """Each tree's (n,) float64 row weights, in order."""
    rng = np.random.default_rng(int(seed) & 0x7FFFFFFF)
    for _ in range(n_trees):
        if bootstrap:
            yield rng.poisson(subsampling_rate, size=n).astype(np.float64)
        elif subsampling_rate < 1.0:
            yield (rng.random(n) < subsampling_rate).astype(np.float64)
        else:
            yield np.ones(n, np.float64)


def gini_gain(parent: np.ndarray, left: np.ndarray) -> np.ndarray:
    """impurity(parent) - wL/w * impurity(left) - wR/w * impurity(right) with
    gini impurity 1 - sum p^2, from class counts along the last axis."""
    parent = np.asarray(parent, np.float64)
    left = np.asarray(left, np.float64)
    right = parent - left

    def w_times_impurity(c):
        w = c.sum(axis=-1)
        return w - (c * c).sum(axis=-1) / np.maximum(w, 1e-300)

    return (w_times_impurity(parent) - w_times_impurity(left) - w_times_impurity(right)) \
        / np.maximum(parent.sum(axis=-1), 1e-300)


def _descend(X: np.ndarray, tree: Dict[str, np.ndarray], pos: np.ndarray):
    """One level down: (which rows moved, their new heap slots). A row at a
    leaf stays; a row at an inner node goes right iff x > threshold."""
    f = np.asarray(tree["feature"])[pos]
    moved = ~np.asarray(tree["is_leaf"])[pos] & (f >= 0)
    right = X[np.arange(X.shape[0]), np.maximum(f, 0)] \
        > np.asarray(tree["threshold"], np.float32)[pos]
    return moved, np.where(moved, 2 * pos + right, pos)


def route(X: np.ndarray, tree: Dict[str, np.ndarray], depth: int) -> np.ndarray:
    """The heap slot (root 1, children 2p and 2p+1) each row is at after
    `depth` levels of `tree` (`feature`, `threshold`, `is_leaf`)."""
    pos = np.ones(X.shape[0], np.int64)
    for _ in range(depth):
        _, pos = _descend(X, tree, pos)
    return pos


def node_counts(X: np.ndarray, y: np.ndarray, w: np.ndarray, tree: Dict[str, np.ndarray],
                n_classes: int) -> Tuple[np.ndarray, np.ndarray]:
    """(counts, reached): the weighted class counts (slots, classes) of every
    heap slot rows reach, level by level, and which slots lie on the fitted
    tree (the root, and the children of its inner nodes)."""
    feature = np.asarray(tree["feature"])
    is_leaf = np.asarray(tree["is_leaf"])
    slots = feature.shape[0]
    depth = int(np.log2(slots)) - 1
    counts = np.zeros((slots, n_classes), np.float64)
    yi = np.asarray(y).astype(np.int64)
    pos = np.ones(X.shape[0], np.int64)
    moved = np.ones(X.shape[0], bool)
    for level in range(depth + 1):
        counts += np.bincount(pos[moved] * n_classes + yi[moved], weights=w[moved],
                              minlength=slots * n_classes).reshape(slots, n_classes)
        if level < depth:
            moved, pos = _descend(X, tree, pos)
    reached = np.zeros(slots, bool)
    reached[1] = True
    for p in np.flatnonzero(~is_leaf[:slots // 2] & (feature[:slots // 2] >= 0)):
        if reached[p]:  # ascending: parents before children
            reached[2 * p] = reached[2 * p + 1] = True
    return counts, reached


def split_gains(bins: np.ndarray, y: np.ndarray, w: np.ndarray, n_bins: int,
                n_classes: int, min_instances: float = 1.0) -> np.ndarray:
    """(features, n_bins - 1) float64 gini gains of splitting these rows at
    "bin <= b" for every column of `bins` (rows, features) and b; a split that
    leaves a side under `min_instances` of weight reads -inf."""
    yi = np.asarray(y).astype(np.intp)
    d = bins.shape[1]
    columns = np.ascontiguousarray(bins.T)  # a column of a row-major table is a strided read
    hist = np.empty((d, n_bins, n_classes), np.float64)
    for j in range(d):
        hist[j] = np.bincount(columns[j].astype(np.intp) * n_classes + yi, weights=w,
                              minlength=n_bins * n_classes).reshape(n_bins, n_classes)
    parent = hist[0].sum(axis=0)
    left = np.cumsum(hist, axis=1)[:, :-1]
    gains = gini_gain(parent[None, None, :], left)
    wl = left.sum(axis=-1)
    ok = (wl >= min_instances) & (parent.sum() - wl >= min_instances)
    return np.where(ok, gains, -np.inf)


def best_split(bins: np.ndarray, y: np.ndarray, w: np.ndarray, n_bins: int,
               n_classes: int, features=None) -> Tuple[float, int, int, np.ndarray]:
    """(gain, feature, bin, all gains) of the exact best split of these rows
    over `features` (default: every column), the first in (feature, bin) order
    among equals."""
    cols = np.arange(bins.shape[1]) if features is None else np.asarray(features)
    gains = split_gains(bins[:, cols], y, w, n_bins, n_classes)
    j, b = np.unravel_index(int(np.argmax(gains)), gains.shape)
    return float(gains[j, b]), int(cols[j]), int(b), gains
