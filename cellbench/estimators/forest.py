"""RandomForestClassifier: how a cell builds it, hands it a label, and what
its fit is held to.

**The label.** As `estimators/logreg.py`: `build` returns an adapter whose
`fit(X)` hands the program a `pyarrow.Table` (a zero-copy
`FixedSizeList<float32>[d]` view of the host table and a float32 `label`
column). The labels come from the table's own rows through a rule that is not
linear in the columns and not separable (`make_labels`): a third of the
columns are informative, in two halves with gaussian loadings; with z1, z2 the
halves' standardised projections the logit is `LOGIT_SPREAD * (z1 * z2 +
0.7 * (|z1| - E|z|)) `, and the label a Bernoulli draw of its sigmoid, about
half ones. The generator is seeded by the table's first rows, so `--seed`
fixes them. This file asks the program for `ops/pallas_histogram.py::hist_gate`
as it is imported: a program without the level step whose cost and memory do
not grow with the level's width cannot hold this configuration's depth (a whole
level histogram is 12.6 GB at level 12), and fails here, at once, before the
table is made.

**The comparison** (`forest_ref.py`: numpy, float64, no kernels). A level-wise
builder's feature draws and ties cannot be reproduced from outside, so:

* of the TIMED fits' own trees (every tree of the last fit, `SAMPLED_TREES` of
  each other fit kept, drawn from the seed): the reference draws the tree's row
  weights again by the stated rule, routes every row down the tree on its raw
  values against the tree's thresholds, and compares every node on the tree:
  its `node_weight` and class counts (`value` x `node_weight`, whole numbers:
  `node_mismatch_share`, the share of nodes where any differs by 0.5 or more)
  and, where it split, its `gain` against float64 from the parent's and the
  left child's counts (`node_gain_err`, absolute: a gain is at most 0.5). A
  program that reports no gain reads 1;
* one exact step, the KMeans cells' device, by a SECOND executable: a fit
  through the public path with `numTrees=1`, `bootstrap=False`,
  `featureSubsetStrategy="all"`, `maxDepth=2`; each of its three splits against
  the reference's exact best over all d x (maxBins - 1) candidates on the
  node's rows (`split_gain_shortfall`: the float64 gain of the best candidate
  less that of the chosen one, over the best; a tie in float32 reads about
  1e-7 either way). It holds the histogram, the split search and the binning
  (the reference bins the host table itself from the model's edges); it does
  not see the window's program, which the first comparison does;
* `did_all_work`: `numTrees` trees, each with a node that split at depth
  `maxDepth - 1`, so that every level was grown.

The control (`control.fit.reference = "bf16"`): the reference with
bfloat16-rounded gains in the program's place, for the reported gains and for
the exact step's choice.

Cost on the chip's host, after the window has closed: routing about 0.25 s a
tree (thirteen gathers of 357,376 values from the host table), one binning
pass of the host table about 40 s, three nodes' exact searches about 10 s.
"""

from __future__ import annotations

import json
import sys
import zlib
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from spark_rapids_ml_tpu.ops.pallas_histogram import hist_gate  # noqa: F401  (the docstring)

from .. import forest_ref as ref
from ..refs import CHUNK, round_bf16

ESTIMATOR = "RandomForestClassifier"
SAMPLED_TREES = 2    # of each kept fit but the last, whose every tree is compared
LOGIT_SPREAD = 2.0   # not separable: the likelier class of a row has 0.75 on average
INFORMATIVE = 3      # one column in three carries the label, as upstream's generator is told
EXACT_STEP = {"numTrees": 1, "bootstrap": False, "featureSubsetStrategy": "all", "maxDepth": 2}

_TABLES: Dict[Tuple[int, Tuple[int, ...]], Tuple[Any, np.ndarray]] = {}


def make_labels(X: np.ndarray) -> np.ndarray:
    """float32 0/1 labels of `X`'s rows by the rule in the module's docstring."""
    rng = np.random.default_rng([zlib.crc32(np.ascontiguousarray(X[:8]).tobytes()), 0xF0E5])
    d = X.shape[1]
    informative = rng.permutation(d)[: max(2, d // INFORMATIVE)]
    halves = np.zeros((d, 2), np.float32)
    half = len(informative) // 2
    halves[informative[:half], 0] = rng.standard_normal(half)
    halves[informative[half:], 1] = rng.standard_normal(len(informative) - half)
    z = np.concatenate([(X[s:s + CHUNK] @ halves).astype(np.float64)
                        for s in range(0, X.shape[0], CHUNK)])
    z = (z - z.mean(axis=0)) / z.std(axis=0)
    logit = LOGIT_SPREAD * (z[:, 0] * z[:, 1] + 0.7 * (np.abs(z[:, 0]) - np.sqrt(2 / np.pi)))
    return (rng.random(X.shape[0]) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)


def labelled(X: np.ndarray, params: Dict[str, Any]):
    """(Arrow table, labels) of this host table, made once a table."""
    import pyarrow as pa

    key = (X.__array_interface__["data"][0], X.shape)
    if key not in _TABLES:
        _TABLES.clear()  # one table a process: nothing holds the last one's labels
        y = make_labels(X)
        features = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), X.shape[1])
        _TABLES[key] = (pa.table({params["featuresCol"]: features,
                                  params["labelCol"]: pa.array(y)}), y)
    return _TABLES[key]


class _FitsArrow:
    """The program's estimator behind the harness's `fit(X)`."""

    def __init__(self, estimator, params: Dict[str, Any]):
        self.estimator, self.params = estimator, params

    def fit(self, X: np.ndarray):
        return self.estimator.fit(labelled(X, self.params)[0])


def build(params: Dict[str, Any], num_workers: int):
    from spark_rapids_ml_tpu.classification import RandomForestClassifier

    return _FitsArrow(RandomForestClassifier(num_workers=num_workers, **params), params)


def fit_outputs(model) -> Dict[str, Any]:
    a = model.get_model_attributes()
    out = {k: np.asarray(a[k]) for k in ("feature", "threshold", "is_leaf", "value",
                                         "node_weight", "bin_edges")}
    out["gain"] = None if a.get("gain") is None else np.asarray(a["gain"])
    out["num_classes"] = int(a["num_classes"])
    return out


def did_all_work(outputs: Dict[str, Any], params: Dict[str, Any]) -> bool:
    """Fixed work per fit: `numTrees` trees, each grown through every level (a
    node at depth maxDepth - 1 split, so nodes at depth maxDepth exist)."""
    feature = outputs["feature"]
    depth = int(params["maxDepth"])
    if feature.shape != (int(params["numTrees"]), 2 ** (depth + 1)):
        return False
    return bool(np.all((feature[:, 2 ** (depth - 1):2 ** depth] >= 0).any(axis=1)))


def forest_work(rows: int, cols: int, classes: int, bins: int, trees: int, depth: int,
                features_a_node: int) -> Dict[str, float]:
    """A forest's tree-levels over an (rows, cols) table binned to one byte an
    id, whatever implements them: a tree-level reads the bin matrix once
    (rows * cols B) and the rows' node ids and statistics (rows * (4 + 4 *
    classes) B); its operations are one accumulate a row, feature and class,
    and the split search's about ten a candidate (a node's drawn features x
    bins - 1 thresholds: running sums, two impurities, a compare)."""
    levels = float(trees) * depth
    candidates = trees * float(2 ** depth - 1) * features_a_node * (bins - 1)
    return {"flops": levels * rows * cols * classes + 10.0 * candidates * classes,
            "bytes": levels * (rows * cols + rows * (4.0 + 4.0 * classes))}


def fit_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    p = cfg["params"]
    return forest_work(cfg["rows"], cfg["cols"], int(cfg["published"]["classes"]),
                       int(p["maxBins"]), int(p["numTrees"]), int(p["maxDepth"]),
                       max(1, int(np.sqrt(cfg["cols"]))))


kernel_work = fit_work


def _bf16(x: np.ndarray) -> np.ndarray:
    """float64 -> nearest bfloat16 -> float64 (-inf stays)."""
    return round_bf16(np.asarray(x, np.float32)).astype(np.float64)


def compare_tree(X, y, w, tree: Dict[str, np.ndarray], n_classes: int,
                 control: bool = False) -> Dict[str, float]:
    """One fitted tree against the reference's routing of the weighted rows."""
    counts, reached = ref.node_counts(X, y, w, tree, n_classes)
    nodes = np.flatnonzero(reached)
    weight = np.asarray(tree["node_weight"], np.float64)[nodes]
    reported = np.asarray(tree["value"], np.float64)[nodes] * weight[:, None]
    wrong = (np.abs(weight - counts[nodes].sum(axis=1)) >= 0.5) \
        | (np.abs(reported - counts[nodes]) >= 0.5).any(axis=1)
    feature = np.asarray(tree["feature"])
    inner = nodes[(feature[nodes] >= 0) & ~np.asarray(tree["is_leaf"])[nodes]]
    exact = ref.gini_gain(counts[inner], counts[2 * inner])
    if control:
        gains = _bf16(exact)
    elif tree.get("gain") is None:
        return {"node_mismatch_share": float(wrong.mean()), "node_gain_err": 1.0}
    else:
        gains = np.asarray(tree["gain"], np.float64)[inner]
    return {"node_mismatch_share": float(wrong.mean()),
            "node_gain_err": float(np.abs(gains - exact).max()) if inner.size else 0.0}


def exact_step(X, y, tree: Dict[str, np.ndarray], edges: np.ndarray, n_classes: int,
               control: bool = False) -> float:
    """`split_gain_shortfall` of a depth-2 tree grown on every row at weight 1
    over every feature: the worst of its (up to three) splits."""
    bins = ref.bin_table(X, edges)
    n_bins = edges.shape[1] + 1
    pos = {0: np.ones(X.shape[0], np.int64), 1: ref.route(X, tree, 1)}
    feature, thr = np.asarray(tree["feature"]), np.asarray(tree["threshold"], np.float32)
    worst = 0.0
    for p in (1, 2, 3):
        rows = np.flatnonzero(pos[0 if p == 1 else 1] == p)
        best, _, _, gains = ref.best_split(bins[rows], y[rows], np.ones(rows.size), n_bins,
                                           n_classes)
        if not np.isfinite(best) or best <= 0.0:
            continue  # nothing to split: the program must have made a leaf
        if control:  # the reference's own choice, from bfloat16-rounded gains
            j, b = np.unravel_index(int(np.argmax(_bf16(gains))), gains.shape)
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
    y = labelled(X, params)[1]
    n_trees, seed = int(params["numTrees"]), int(params.get("seed", 0))
    weights = list(ref.tree_weights(seed, X.shape[0], n_trees))
    one = fit_outputs(refit(EXACT_STEP))
    step = exact_step(X, y, {k: one[k][0] for k in ("feature", "threshold", "is_leaf")},
                      one["bin_edges"], one["num_classes"], control)
    rng = np.random.default_rng([seed, 0x5A3])
    depth = int(params["maxDepth"])
    grown = answers[-1]["feature"][:, 2 ** (depth - 1):2 ** depth] >= 0
    print("forest_notes " + json.dumps({  # what the configuration's `assumed.labels` quotes
        "ones_share": float(y.mean()),
        "deepest_level_nodes_share": float(grown.mean()),  # of 2^maxDepth slots, two a split
        "exact_step_features": [int(f) for f in one["feature"][0][1:4]],
    }), file=sys.stderr)
    readings = []
    for i, out in enumerate(answers):
        last = i == len(answers) - 1
        trees = range(n_trees) if last else rng.choice(n_trees, SAMPLED_TREES, replace=False)
        per_tree = [
            compare_tree(X, y, weights[t],
                         {k: (None if out[k] is None else out[k][t])
                          for k in ("feature", "threshold", "is_leaf", "value",
                                    "node_weight", "gain")},
                         out["num_classes"], control)
            for t in trees
        ]
        readings.append({
            "node_mismatch_share": max(r["node_mismatch_share"] for r in per_tree),
            "node_gain_err": max(r["node_gain_err"] for r in per_tree),
            "split_gain_shortfall": step,
        })
    return readings
