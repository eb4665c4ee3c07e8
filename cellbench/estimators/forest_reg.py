"""RandomForestRegressor: how a cell builds it, hands it a label, and what
its fit is held to.

**The label.** `estimators/linreg.py`'s, as upstream's regression forest reads
the parquet its linear regression reads: `build` returns an adapter whose
`fit(X)` hands the program a `pyarrow.Table` (a zero-copy
`FixedSizeList<float32>[d]` view of the host table and the float64 `label`
column of `linreg.make_labels`: y = X beta* + b* + noise, R^2 = 0.9), made
once a table. A row's statistics are [w, w y, w y^2] in float32, real-valued:
the first cell whose histogram sums them.

**The comparison** (`forest_reg_ref.py`: numpy, float64, no kernels; the row
weights, the routing and the binning are `forest_ref.py`'s). As the
classifier's (`estimators/forest.py`):

* of the TIMED fits' own trees (every tree of the last fit,
  `forest.SAMPLED_TREES` of each other fit kept, drawn from the seed): the
  reference draws the tree's row weights again, routes every row down the
  tree on its raw values against the tree's thresholds, and sums each node's
  [w, w y, w y^2] in float64:
  `node_mismatch_share`: the share of nodes on the tree whose `node_weight`
  differs by 0.5 or more (weights are whole numbers);
  `node_gain_err`: of every split, the gain the timed fit reports against the
  float64 gain from the recounted sums of the parent and its left child, over
  the root's variance (its w * variance over its w): the largest;
  `leaf_value_err`: of every leaf, the mean the fit reports against the
  recounted sum(w y) / sum(w), over the label's standard deviation: the
  largest;
* one exact step by a SECOND executable (`forest.EXACT_STEP`: one tree, no
  bootstrap, every feature, depth 2): each of its three splits against the reference's
  best of every feature x (maxBins - 1) candidates on the reference's own
  bins of the node's rows (`split_gain_shortfall`, as the classifier's);
* `did_all_work`: `numTrees` trees, each with a node that split at depth
  `maxDepth - 1`.

The control (`control.fit.reference = "bf16"`): the reference from each row's
statistics rounded to bfloat16 before the sums, in the program's place: for
the reported gains, the leaf means, and the exact step's choice.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Callable, Dict, List

import numpy as np

from .. import forest_ref
from .. import forest_reg_ref as ref
from ..refs import round_bf16
from . import forest, linreg

ESTIMATOR = "RandomForestRegressor"
STATS = 3  # [w, w y, w y^2] a row

fit_outputs = forest.fit_outputs
did_all_work = forest.did_all_work


def build(params: Dict[str, Any], num_workers: int):
    from spark_rapids_ml_tpu.regression import RandomForestRegressor

    return linreg._FitsArrow(RandomForestRegressor(num_workers=num_workers, **params), params)


def fit_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    """`forest.forest_work` with the three statistics in the classes' place:
    a tree-level reads the bin matrix once and the rows' node ids and three
    statistics, whatever implements it; a node draws a third of the columns."""
    p = cfg["params"]
    return forest.forest_work(cfg["rows"], cfg["cols"], STATS, int(p["maxBins"]),
                              int(p["numTrees"]), int(p["maxDepth"]),
                              max(1, cfg["cols"] // 3))


kernel_work = fit_work


def _low(stats: np.ndarray) -> np.ndarray:
    """Each row's statistics rounded to bfloat16: the control's."""
    return round_bf16(stats.astype(np.float32)).astype(np.float64)


def _positions(X: np.ndarray, tree: Dict[str, np.ndarray]) -> List[np.ndarray]:
    depth = int(np.log2(np.asarray(tree["feature"]).shape[0])) - 1
    return [forest_ref.route(X, tree, level) for level in range(depth + 1)]


def compare_tree(X, y, w, tree: Dict[str, np.ndarray], scale: Dict[str, float],
                 control: bool = False) -> Dict[str, float]:
    """One fitted tree against the reference's routing of the weighted rows."""
    positions = _positions(X, tree)
    sums, reached = ref.node_sums(positions, ref.row_stats(y, w), tree)
    nodes = np.flatnonzero(reached)
    wrong = np.abs(np.asarray(tree["node_weight"], np.float64)[nodes] - sums[nodes, 0]) >= 0.5
    feature, is_leaf = np.asarray(tree["feature"]), np.asarray(tree["is_leaf"])
    inner = nodes[(feature[nodes] >= 0) & ~is_leaf[nodes]]
    leaves = nodes[(feature[nodes] < 0) | is_leaf[nodes]]
    root_var = ref.impurity_w(sums[1]) / sums[1, 0]
    exact_gain = ref.variance_gain(sums[inner], sums[2 * inner])
    exact_mean = sums[leaves, 1] / np.maximum(sums[leaves, 0], 1e-300)
    if control:  # the sums of bfloat16-rounded statistics in the program's place
        low, _ = ref.node_sums(positions, _low(ref.row_stats(y, w)), tree)
        gains = ref.variance_gain(low[inner], low[2 * inner])
        means = low[leaves, 1] / np.maximum(low[leaves, 0], 1e-300)
    elif tree.get("gain") is None:
        return {"node_mismatch_share": float(wrong.mean()), "node_gain_err": 1.0,
                "leaf_value_err": 1.0}
    else:
        gains = np.asarray(tree["gain"], np.float64)[inner]
        means = np.asarray(tree["value"], np.float64)[leaves, 0]
    return {
        "node_mismatch_share": float(wrong.mean()),
        "node_gain_err": float(np.abs(gains - exact_gain).max() / root_var) if inner.size else 0.0,
        "leaf_value_err": float(np.abs(means - exact_mean).max() / scale["y_std"]),
    }


def exact_step(X, y, tree: Dict[str, np.ndarray], edges: np.ndarray,
               control: bool = False) -> float:
    """`split_gain_shortfall` of a depth-2 tree grown on every row at weight 1
    over every feature: the worst of its (up to three) splits."""
    bins = forest_ref.bin_table(X, edges)
    n_bins = edges.shape[1] + 1
    stats = ref.row_stats(y, np.ones(X.shape[0]))
    low = _low(stats) if control else None
    pos = {0: np.ones(X.shape[0], np.int64), 1: forest_ref.route(X, tree, 1)}
    feature, thr = np.asarray(tree["feature"]), np.asarray(tree["threshold"], np.float32)
    worst = 0.0
    for p in (1, 2, 3):
        rows = np.flatnonzero(pos[0 if p == 1 else 1] == p)
        best, _, _, gains = ref.best_split(bins[rows], stats[rows], n_bins)
        if not np.isfinite(best) or best <= 0.0:
            continue  # nothing to split: the program must have made a leaf
        if control:  # the reference's own choice, from bfloat16-rounded statistics
            _, j, b, _ = ref.best_split(bins[rows], low[rows], n_bins)
        elif feature[p] < 0:
            return 1.0  # a leaf where a split of positive gain exists
        else:
            j = int(feature[p])
            b = int(np.searchsorted(edges[j], thr[p], side="left"))
        worst = max(worst, (best - gains[j, b]) / best)
    return float(worst)


def check_fit(X: np.ndarray, answers: List[Dict[str, Any]],
              refit: Callable[[Dict[str, Any]], Any],
              params: Dict[str, Any], control: bool = False) -> List[Dict[str, float]]:
    """Every kept fit of the window (every tree of the last, a seeded sample of
    the others') against the reference's routing, and one exact step through
    the public path."""
    y = linreg.labelled(X, params)[1]
    n_trees, seed = int(params["numTrees"]), int(params.get("seed", 0))
    weights = list(forest_ref.tree_weights(seed, X.shape[0], n_trees))
    one = fit_outputs(refit(forest.EXACT_STEP))
    step = exact_step(X, y, {k: one[k][0] for k in ("feature", "threshold", "is_leaf")},
                      one["bin_edges"], control)
    scale = {"y_std": float(np.std(y))}
    rng = np.random.default_rng([seed, 0x5A3])
    depth = int(params["maxDepth"])
    grown = answers[-1]["feature"][:, 2 ** (depth - 1):2 ** depth] >= 0
    print("forest_notes " + json.dumps({  # what the configuration's `assumed` quotes
        "label_mean": float(np.mean(y)), "label_std": scale["y_std"],
        "deepest_level_nodes_share": float(grown.mean()),  # of 2^maxDepth slots, two a split
        "exact_step_features": [int(f) for f in one["feature"][0][1:4]],
    }), file=sys.stderr)
    readings = []
    for i, out in enumerate(answers):
        last = i == len(answers) - 1
        trees = range(n_trees) if last else rng.choice(n_trees, forest.SAMPLED_TREES,
                                                        replace=False)
        per_tree = [
            compare_tree(X, y, weights[t],
                         {k: (None if out[k] is None else out[k][t])
                          for k in ("feature", "threshold", "is_leaf", "value",
                                    "node_weight", "gain")},
                         scale, control)
            for t in trees
        ]
        readings.append({
            **{k: max(r[k] for r in per_tree)
               for k in ("node_mismatch_share", "node_gain_err", "leaf_value_err")},
            "split_gain_shortfall": step,
        })
    return readings
