#
# RandomForestClassifier / RandomForestRegressor (L6 API) — pyspark.ml-compatible
# surface over the TPU histogram forest builder (ops/trees.py).
#
# Structural equivalent of reference python/src/spark_rapids_ml/tree.py +
# classification.py:285-676 + regression.py:865-1147:
#   * the reference splits numTrees across workers, each training locally on its
#     shard, then treelite-concatenates (tree.py:330-341,424-457 — P2 embarrassing
#     parallelism). The TPU builder instead grows every tree on ALL the (sharded)
#     data with per-level histogram psums — same API, better statistical efficiency
#     (no per-worker data fragmentation), and the "merge" is an ICI reduction.
#   * missing-label check (reference tree.py:415-421)
#   * probability/rawPrediction columns for the classifier
#     (reference classification.py:502-515)
#   * JSON forest dump for interop (reference tree.py:534-559 treelite JSON)
#

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..core.dataset import densify
from ..core.backend_params import HasFeaturesCols, _TpuClass
from ..core.estimator import (
    FitInputs,
    _TpuEstimatorSupervised,
    _TpuModelWithPredictionCol,
)
from ..core.params import (
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasSeed,
    HasWeightCol,
    Param,
    TypeConverters,
)
from ..ops.trees import (
    forest_fit,
    forest_to_json,
    predict_forest,
    resolve_feature_subset,
)


class _RandomForestClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        # reference tree.py:103-156
        return {
            "numTrees": "n_estimators",
            "maxDepth": "max_depth",
            "maxBins": "n_bins",
            "minInstancesPerNode": "min_samples_leaf",
            "minInfoGain": "min_impurity_decrease",
            "featureSubsetStrategy": "max_features",
            "subsamplingRate": "max_samples",
            "bootstrap": "bootstrap",
            "impurity": "split_criterion",
            "seed": "random_state",
            "minWeightFractionPerNode": None,
            "maxMemoryInMB": "",
            "cacheNodeIds": "",
            "checkpointInterval": "",
            "leafCol": None,
            "featuresCol": "",
            "labelCol": "",
            "predictionCol": "",
            "probabilityCol": "",
            "rawPredictionCol": "",
            "weightCol": "",
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_estimators": 20,
            "max_depth": 5,
            "n_bins": 32,
            "min_samples_leaf": 1,
            "min_impurity_decrease": 0.0,
            "max_features": "auto",
            "max_samples": 1.0,
            "bootstrap": True,
            "split_criterion": "gini",
            "random_state": 0,
        }


class _RandomForestParams(
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasSeed,
    HasWeightCol,
):
    numTrees: Param[int] = Param(
        "undefined", "numTrees", "Number of trees to train (>= 1).", TypeConverters.toInt
    )
    maxDepth: Param[int] = Param(
        "undefined", "maxDepth", "Maximum depth of the tree (>= 0).", TypeConverters.toInt
    )
    maxBins: Param[int] = Param(
        "undefined",
        "maxBins",
        "Max number of bins for discretizing continuous features.",
        TypeConverters.toInt,
    )
    minInstancesPerNode: Param[int] = Param(
        "undefined",
        "minInstancesPerNode",
        "Minimum number of instances each child must have after split.",
        TypeConverters.toInt,
    )
    minInfoGain: Param[float] = Param(
        "undefined",
        "minInfoGain",
        "Minimum information gain for a split to be considered at a tree node.",
        TypeConverters.toFloat,
    )
    featureSubsetStrategy: Param[str] = Param(
        "undefined",
        "featureSubsetStrategy",
        "The number of features to consider for splits at each tree node: "
        "auto|all|onethird|sqrt|log2|(0.0-1.0]|[1-n].",
        TypeConverters.toString,
    )
    subsamplingRate: Param[float] = Param(
        "undefined",
        "subsamplingRate",
        "Fraction of the training data used for learning each decision tree.",
        TypeConverters.toFloat,
    )
    bootstrap: Param[bool] = Param(
        "undefined", "bootstrap", "Whether bootstrap samples are used.", TypeConverters.toBoolean
    )
    impurity: Param[str] = Param(
        "undefined", "impurity", "Criterion used for information gain calculation.",
        TypeConverters.toString,
    )
    minWeightFractionPerNode: Param[float] = Param(
        "undefined",
        "minWeightFractionPerNode",
        "Minimum fraction of the weighted sample count each child must have.",
        TypeConverters.toFloat,
    )
    # Spark executor-memory/caching knobs with no TPU meaning; accepted and ignored
    # for drop-in compatibility (reference tree.py:103-156 maps them to "")
    maxMemoryInMB: Param[int] = Param(
        "undefined", "maxMemoryInMB",
        "Maximum memory in MB allocated to histogram aggregation (ignored).",
        TypeConverters.toInt,
    )
    cacheNodeIds: Param[bool] = Param(
        "undefined", "cacheNodeIds",
        "Whether to cache node IDs for each instance (ignored).",
        TypeConverters.toBoolean,
    )
    checkpointInterval: Param[int] = Param(
        "undefined", "checkpointInterval",
        "Checkpoint interval for the node-id cache (ignored).",
        TypeConverters.toInt,
    )
    leafCol: Param[str] = Param(
        "undefined", "leafCol",
        "Leaf-index output column (unsupported -> CPU fallback when set).",
        TypeConverters.toString,
    )

    def setFeaturesCol(self, value: str):
        return self._set(featuresCol=value)

    def setLabelCol(self, value: str):
        return self._set(labelCol=value)

    def getNumTrees(self) -> int:
        return self.getOrDefault("numTrees")

    def getMaxDepth(self) -> int:
        return self.getOrDefault("maxDepth")


class _RandomForestEstimator(_RandomForestClass, _TpuEstimatorSupervised, _RandomForestParams):
    _is_classification = False
    # Spark caps tree depth at 30; the heap-layout forest (2^(depth+1) slots) makes
    # an early clear error strictly better than a depth-exponential OOM
    _PARAM_BOUNDS_EXTRA = {"maxDepth": (0, 30)}
    # sklearn forests produce no leaf-index column; a fallback would silently
    # return a model missing the output the user asked for
    _FALLBACK_CANNOT_HONOR = frozenset({"leafCol"})

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            numTrees=20,
            maxDepth=5,
            maxBins=32,
            minInstancesPerNode=1,
            minInfoGain=0.0,
            featureSubsetStrategy="auto",
            subsamplingRate=1.0,
            bootstrap=True,
            seed=0,
            minWeightFractionPerNode=0.0,
            maxMemoryInMB=256,
            cacheNodeIds=False,
            checkpointInterval=10,
            leafCol="",
        )
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def _out_schema(self) -> List[str]:
        return ["feature", "threshold", "is_leaf", "value", "gain", "node_weight",
                "bin_edges", "num_classes"]

    def _row_stats(self, inputs: FitInputs) -> np.ndarray:
        raise NotImplementedError

    def _impurity_name(self) -> str:
        raise NotImplementedError

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # host rows + per-tree stats are staged once; each param map re-bins only if
        # its n_bins differs (P6 pattern, reference tree.py:475-507)
        return True

    def _streaming_fit(self, fd) -> Dict[str, Any]:
        """Out-of-core fit: X streams through host binning in row blocks and only
        the binned uint8 matrix (4x smaller than f32) + per-row stats reside on
        device (ops/trees.streaming_forest_fit) — the RandomForest analog of the
        reference's UVM/SAM path (reference utils.py:184-241). BASELINE config 4
        (50M x 64, ~12.8 GiB f32) bins to ~3.1 GiB on a 16 GiB chip. Selected by
        core/estimator.py when the design matrix exceeds stream_threshold_bytes;
        maxBins must fit uint8 (<= 256) — wider binning routes in-core."""
        from types import SimpleNamespace

        from .. import config as _config
        from ..core.dataset import densify as _densify
        from ..ops.trees import streaming_forest_fit
        from ..parallel.partition import pad_rows
        from ..parallel.partitioner import active_partitioner

        p = self._tpu_params
        if int(p["n_bins"]) > 256:
            self.logger.warning(
                "streamed RandomForest bins to uint8 (maxBins <= 256); fitting "
                "in-core despite stream_threshold_bytes."
            )
            inputs = self._build_fit_inputs(fd)
            return self._get_tpu_fit_func(None)(inputs)
        X = _densify(fd.features, self._float32_inputs)
        stats, n_classes = self._row_stats(
            SimpleNamespace(host_label=fd.label, host_row_weight=fd.weight)
        )
        part = active_partitioner(self.num_workers)
        mesh = part.mesh
        n_dev = part.num_workers

        def shard_fn(arr: np.ndarray):
            padded, _, _ = pad_rows(arr, n_dev)
            return part.shard(padded)

        attrs = streaming_forest_fit(
            np.asarray(X),
            stats,
            n_trees=int(p["n_estimators"]),
            max_depth=int(p["max_depth"]),
            max_bins=int(p["n_bins"]),
            impurity=self._impurity_name(),
            feature_subset=resolve_feature_subset(
                str(p["max_features"]), X.shape[1], self._is_classification
            ),
            min_instances=int(p["min_samples_leaf"]),
            min_info_gain=float(p["min_impurity_decrease"]),
            subsampling_rate=float(p["max_samples"]),
            bootstrap=bool(p["bootstrap"]),
            seed=int(p["random_state"]) if p["random_state"] is not None else 0,
            batch_rows=int(_config.get("stream_batch_rows")),
            shard_fn=shard_fn,
            mesh=mesh,
        )
        attrs["num_classes"] = n_classes
        return attrs

    def _get_tpu_fit_func(self, extra_params: Optional[List[Dict[str, Any]]] = None):
        base = dict(self._tpu_params)
        is_cls = self._is_classification

        def _fit(inputs: FitInputs):
            from ..observability import span
            from ..parallel.partition import pad_rows
            from ..parallel.partitioner import partitioner_for

            X = inputs.host_features
            with span("forest.labels"):
                stats, n_classes = self._row_stats(inputs)
            d = X.shape[1]

            mesh = inputs.mesh
            part = partitioner_for(mesh)
            n_dev = part.num_workers

            def shard_fn(arr: np.ndarray):
                padded, _, _ = pad_rows(arr, n_dev)
                return part.shard(padded, site="fit")

            param_sets = extra_params if extra_params is not None else [base]
            results = []
            for ep in param_sets:
                p = {**base, **ep}
                attrs = forest_fit(
                    X,
                    stats,
                    n_trees=int(p["n_estimators"]),
                    max_depth=int(p["max_depth"]),
                    max_bins=int(p["n_bins"]),
                    impurity=self._impurity_name(),
                    feature_subset=resolve_feature_subset(
                        str(p["max_features"]), d, is_cls
                    ),
                    min_instances=int(p["min_samples_leaf"]),
                    min_info_gain=float(p["min_impurity_decrease"]),
                    subsampling_rate=float(p["max_samples"]),
                    bootstrap=bool(p["bootstrap"]),
                    seed=int(p["random_state"]) if p["random_state"] is not None else 0,
                    shard_fn=shard_fn,
                    mesh=mesh,
                    # the table the normal upload already placed: binned there
                    X_dev=inputs.features,
                    # a classifier's statistics without a weightCol are 0 or 1
                    unit_stats=is_cls and inputs.host_row_weight is None,
                )
                attrs["num_classes"] = n_classes
                results.append(attrs)
            return results if extra_params is not None else results[0]

        return _fit


def _sk_forest_to_heap(sk_model, is_classification: bool, n_features: int) -> Dict[str, Any]:
    """Translate a fitted sklearn forest into this framework's heap-layout arrays
    (the CPU-fallback model translation; the reference's equivalent converts between
    cuML and Spark tree formats, utils.py:694-809)."""

    estimators = sk_model.estimators_
    depth = max(e.tree_.max_depth for e in estimators)
    n_slots = 2 ** (depth + 1)
    v_dim = sk_model.n_classes_ if is_classification else 1

    n_trees = len(estimators)
    feature = np.full((n_trees, n_slots), -1, np.int32)
    threshold = np.zeros((n_trees, n_slots), np.float32)
    is_leaf = np.zeros((n_trees, n_slots), bool)
    value = np.zeros((n_trees, n_slots, v_dim), np.float32)
    gain = np.zeros((n_trees, n_slots), np.float32)
    node_weight = np.zeros((n_trees, n_slots), np.float32)

    for ti, est in enumerate(estimators):
        t = est.tree_
        stack = [(0, 1)]  # (sklearn node id, heap pos)
        while stack:
            nid, pos = stack.pop()
            val = t.value[nid].reshape(-1)
            if is_classification:
                s = val.sum()
                value[ti, pos] = val / s if s > 0 else val
            else:
                value[ti, pos] = val[:1]
            w = float(t.weighted_n_node_samples[nid])
            node_weight[ti, pos] = w
            if t.children_left[nid] == -1:
                is_leaf[ti, pos] = True
            else:
                feature[ti, pos] = t.feature[nid]
                threshold[ti, pos] = t.threshold[nid]
                # per-unit-weight impurity decrease (same scale the TPU builder
                # records) so featureImportances works identically on fallback fits
                left, right = t.children_left[nid], t.children_right[nid]
                wl = float(t.weighted_n_node_samples[left])
                wr = float(t.weighted_n_node_samples[right])
                gain[ti, pos] = max(
                    float(t.impurity[nid])
                    - (wl / w) * float(t.impurity[left])
                    - (wr / w) * float(t.impurity[right]),
                    0.0,
                )
                stack.append((left, 2 * pos))
                stack.append((right, 2 * pos + 1))

    return {
        "feature": feature,
        "threshold": threshold,
        "is_leaf": is_leaf,
        "value": value,
        "gain": gain,
        "node_weight": node_weight,
        "bin_edges": np.zeros((n_features, 1), np.float32),
        "num_classes": sk_model.n_classes_ if is_classification else 0,
    }


class RandomForestRegressor(_RandomForestEstimator):
    """Random forest regression on the TPU mesh (reference regression.py:865-1147)."""

    _is_classification = False

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(impurity="variance")
        self._set_params(**kwargs)

    @classmethod
    def _param_value_mapping(cls):
        return {"split_criterion": lambda x: x if x == "variance" else None}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        base = dict(_RandomForestClass._get_tpu_params_default())
        base["split_criterion"] = "variance"
        return base

    @classmethod
    def _fallback_class(cls):
        from sklearn.ensemble import RandomForestRegressor as SkRFR

        return SkRFR

    def _impurity_name(self) -> str:
        return "variance"

    def _fit_fallback_model(self, twin: type, fd) -> Dict[str, Any]:
        X = densify(fd.features, float32=self._float32_inputs)
        sk = twin(
            n_estimators=self.getOrDefault("numTrees"),
            max_depth=max(self.getOrDefault("maxDepth"), 1),
            min_samples_leaf=self.getOrDefault("minInstancesPerNode"),
            bootstrap=self.getOrDefault("bootstrap"),
            random_state=self.getOrDefault("seed") & 0x7FFFFFFF,
        ).fit(X, fd.label, sample_weight=fd.weight)
        return _sk_forest_to_heap(sk, False, X.shape[1])

    def _row_stats(self, inputs: FitInputs):
        y = inputs.host_label.astype(np.float64)
        w = np.ones_like(y) if inputs.host_row_weight is None else inputs.host_row_weight
        stats = np.stack([w, w * y, w * y * y], axis=1).astype(np.float32)
        return stats, 0

    def _create_pyspark_model(self, attrs) -> "RandomForestRegressionModel":
        return RandomForestRegressionModel(**attrs)


class RandomForestClassifier(
    _RandomForestEstimator, HasProbabilityCol, HasRawPredictionCol
):
    """Random forest classification on the TPU mesh
    (reference classification.py:285-676)."""

    _is_classification = True

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            impurity="gini", probabilityCol="probability", rawPredictionCol="rawPrediction"
        )
        self._set_params(**kwargs)

    @classmethod
    def _param_value_mapping(cls):
        return {"split_criterion": lambda x: x if x in ("gini", "entropy") else None}

    @classmethod
    def _fallback_class(cls):
        from sklearn.ensemble import RandomForestClassifier as SkRFC

        return SkRFC

    def _impurity_name(self) -> str:
        return self._tpu_params.get("split_criterion", "gini")

    def _fit_fallback_model(self, twin: type, fd) -> Dict[str, Any]:
        X = densify(fd.features, float32=self._float32_inputs)
        sk = twin(
            n_estimators=self.getOrDefault("numTrees"),
            max_depth=max(self.getOrDefault("maxDepth"), 1),
            min_samples_leaf=self.getOrDefault("minInstancesPerNode"),
            bootstrap=self.getOrDefault("bootstrap"),
            random_state=self.getOrDefault("seed") & 0x7FFFFFFF,
        ).fit(X, fd.label, sample_weight=fd.weight)
        return _sk_forest_to_heap(sk, True, X.shape[1])

    def _row_stats(self, inputs: FitInputs):
        y = inputs.host_label
        classes = np.unique(y)
        n_classes = int(classes.max()) + 1 if len(classes) else 0
        if not np.array_equal(classes, classes.astype(np.int64)) or (
            len(classes) and classes.min() < 0
        ):
            raise ValueError("Labels must be non-negative integers 0..k-1.")
        if len(classes) != n_classes:
            # reference raises with workaround text (tree.py:415-421)
            raise RuntimeError(
                f"Labels {sorted(set(range(n_classes)) - set(classes.astype(int)))} "
                "are missing from the dataset: every class in 0..k-1 must appear."
            )
        w = (
            np.ones(len(y), np.float64)
            if inputs.host_row_weight is None
            else inputs.host_row_weight.astype(np.float64)
        )
        stats = np.zeros((len(y), n_classes), np.float32)
        stats[np.arange(len(y)), y.astype(int)] = w
        return stats, n_classes

    def _create_pyspark_model(self, attrs) -> "RandomForestClassificationModel":
        return RandomForestClassificationModel(**attrs)


class _RandomForestModel(_RandomForestClass, _TpuModelWithPredictionCol, _RandomForestParams):
    _is_classification = False

    def __init__(
        self,
        feature: np.ndarray,
        threshold: np.ndarray,
        is_leaf: np.ndarray,
        value: np.ndarray,
        bin_edges: np.ndarray,
        num_classes: int,
        gain: "np.ndarray | None" = None,
        node_weight: "np.ndarray | None" = None,
    ) -> None:
        feature = np.asarray(feature)
        # gain/node_weight absent on JSON-imported forests (the dump carries
        # structure, not training statistics) -> importances are all-zero there
        super().__init__(
            feature=feature,
            threshold=np.asarray(threshold),
            is_leaf=np.asarray(is_leaf),
            value=np.asarray(value),
            bin_edges=np.asarray(bin_edges),
            num_classes=int(num_classes),
            gain=(
                np.zeros(feature.shape, np.float32)
                if gain is None
                else np.asarray(gain)
            ),
            node_weight=(
                np.zeros(feature.shape, np.float32)
                if node_weight is None
                else np.asarray(node_weight)
            ),
        )
        self._setDefault(
            featuresCol="features", labelCol="label", predictionCol="prediction",
            numTrees=20, maxDepth=5,
        )

    @property
    def numFeatures(self) -> int:
        return int(self._model_attributes["bin_edges"].shape[0])

    def getNumTrees(self) -> int:
        return int(self._model_attributes["feature"].shape[0])

    @property
    def treeWeights(self) -> List[float]:
        return [1.0] * self.getNumTrees()

    @property
    def max_depth_(self) -> int:
        import math

        return int(math.log2(self._model_attributes["feature"].shape[1])) - 1

    def _reachable_slots(self, tree_idx: int) -> List[int]:
        """Heap slots actually present in tree `tree_idx` (walk from root slot 1;
        children of leaves are padding)."""
        a = self._model_attributes
        feat = a["feature"][tree_idx]
        leaf = a["is_leaf"][tree_idx]
        n_slots = feat.shape[0]
        out: List[int] = []
        stack = [1]
        while stack:
            p = stack.pop()
            if p >= n_slots:
                continue
            out.append(p)
            if not leaf[p] and feat[p] >= 0:
                stack.extend((2 * p, 2 * p + 1))
        return out

    @property
    def totalNumNodes(self) -> int:
        """Total number of nodes, summed over all trees (Spark
        TreeEnsembleModel.totalNumNodes)."""
        return sum(len(self._reachable_slots(i)) for i in range(self.getNumTrees()))

    @property
    def featureImportances(self) -> np.ndarray:
        """Impurity-based feature importances (Spark TreeEnsembleModel semantics:
        per tree, each internal node contributes gain x node weight to its split
        feature; trees are normalized to sum 1, averaged, and renormalized). The
        reference cannot compute this without a Spark conversion and raises
        (reference tree.py:567-572); here the builder records per-node gain and
        weight, so importances come straight from the heap arrays."""
        a = self._model_attributes
        d = self.numFeatures
        total = np.zeros(d, np.float64)
        for i in range(self.getNumTrees()):
            imp = np.zeros(d, np.float64)
            feat = a["feature"][i]
            contrib = a["gain"][i] * a["node_weight"][i]
            for p in self._reachable_slots(i):
                if feat[p] >= 0 and not a["is_leaf"][i][p]:
                    imp[feat[p]] += contrib[p]
            s = imp.sum()
            if s > 0:
                total += imp / s
        s = total.sum()
        return (total / s if s > 0 else total).astype(np.float64)

    def _tree_debug_string(self, tree_idx: int) -> str:
        a = self._model_attributes
        feat = a["feature"][tree_idx]
        thr = a["threshold"][tree_idx]
        leaf = a["is_leaf"][tree_idx]
        value = a["value"][tree_idx]
        lines: List[str] = []

        def walk(p: int, depth: int) -> None:
            pad = "  " * depth
            if leaf[p] or feat[p] < 0:
                v = value[p]
                pred = float(np.argmax(v)) if self._is_classification else float(v[0])
                lines.append(f"{pad}Predict: {pred}")
                return
            lines.append(f"{pad}If (feature {int(feat[p])} <= {float(thr[p])})")
            walk(2 * p, depth + 1)
            lines.append(f"{pad}Else (feature {int(feat[p])} > {float(thr[p])})")
            walk(2 * p + 1, depth + 1)

        walk(1, 1)
        return "\n".join(lines)

    @property
    def toDebugString(self) -> str:
        """Full text description of the forest (Spark toDebugString shape)."""
        n = self.getNumTrees()
        head = (
            f"{self.__class__.__name__} with {n} trees, "
            f"{self.totalNumNodes} total nodes\n"
        )
        parts = []
        for i in range(n):
            n_nodes = len(self._reachable_slots(i))
            parts.append(f"  Tree {i} ({n_nodes} nodes):\n{self._tree_debug_string(i)}")
        return head + "\n".join(parts)

    @property
    def trees(self) -> List["_DecisionTreeView"]:
        """Per-tree views (Spark returns DecisionTreeModels; without a JVM these are
        lightweight standalone equivalents with numNodes/depth/toDebugString/
        predict)."""
        return [_DecisionTreeView(self, i) for i in range(self.getNumTrees())]

    def _serving_device_attrs(self):
        # the forest predict kernel's device operands include the int/bool
        # structure arrays, not just float weights (the estimator default)
        return ("feature", "threshold", "is_leaf", "value")

    def _forest_outputs(self, X: np.ndarray) -> np.ndarray:
        from ..observability.inference import predict_dispatch

        a = self._model_attributes
        return np.asarray(
            predict_dispatch(
                self,
                predict_forest,
                X.astype(np.float32),
                a["feature"],
                a["threshold"],
                a["is_leaf"],
                a["value"].astype(np.float32),
                self.max_depth_,
            )
        )

    def toJSON(self) -> List[Dict]:
        """Forest dump (the reference's treelite-JSON role, tree.py:534-559)."""
        return forest_to_json(self._model_attributes, self._is_classification)

    @classmethod
    def fromJSON(
        cls, trees_json: List[Dict], n_features: int, num_classes: int = 0
    ) -> "_RandomForestModel":
        """Rebuild a model from a forest JSON dump (the import half of the
        reference's treelite interop, tree.py:439-449): a roundtrip through
        toJSON()/fromJSON() predicts identically, and externally-produced dumps in
        the same shape import the same way."""
        from ..ops.trees import forest_from_json

        attrs = forest_from_json(trees_json, n_features, cls._is_classification)
        attrs["num_classes"] = int(num_classes)
        return cls(**attrs)

    @classmethod
    def fromTreeliteJSON(
        cls,
        model_json: Any,
        n_features: int | None = None,
        num_classes: int = 0,
    ) -> "_RandomForestModel":
        """Import a treelite JSON dump — the format cuML forests serialize to and
        the reference's models carry (reference tree.py:534-559 `dump_as_json`,
        utils.py:700-809 node schema). Accepts the full model dict (with `trees` +
        `num_feature`) or a bare list of tree dicts plus n_features. Classification
        leaves may be `leaf_vector` class probabilities or scalar votes."""
        from ..ops.trees import forest_from_treelite_json

        attrs = forest_from_treelite_json(
            model_json, cls._is_classification, n_features
        )
        attrs["num_classes"] = int(num_classes)
        return cls(**attrs)


class _DecisionTreeView:
    """One tree of a fitted forest: the standalone stand-in for Spark's
    DecisionTree{Classification,Regression}Model returned by `model.trees`."""

    def __init__(self, forest: "_RandomForestModel", tree_idx: int) -> None:
        self._forest = forest
        self._idx = int(tree_idx)

    @property
    def numNodes(self) -> int:
        return len(self._forest._reachable_slots(self._idx))

    @property
    def depth(self) -> int:
        # floor(log2(slot)) is the node's level (root slot 1 -> level 0)
        slots = self._forest._reachable_slots(self._idx)
        return max(int(np.floor(np.log2(p))) for p in slots) if slots else 0

    @property
    def toDebugString(self) -> str:
        return (
            f"DecisionTreeModel ({self.numNodes} nodes)\n"
            + self._forest._tree_debug_string(self._idx)
        )

    def predict(self, value: np.ndarray) -> float:
        """Route one sample through this single tree."""
        a = self._forest._model_attributes
        x = np.asarray(value, np.float32).ravel()
        feat = a["feature"][self._idx]
        thr = a["threshold"][self._idx]
        leaf = a["is_leaf"][self._idx]
        val = a["value"][self._idx]
        p = 1
        while not leaf[p] and feat[p] >= 0:
            p = 2 * p + int(x[feat[p]] > thr[p])
        v = val[p]
        return (
            float(np.argmax(v)) if self._forest._is_classification else float(v[0])
        )


class RandomForestRegressionModel(_RandomForestModel):
    def predict(self, value: np.ndarray) -> float:
        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return float(self._forest_outputs(X)[0, 0])

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        return {self.getOrDefault("predictionCol"): self._forest_outputs(X)[:, 0]}

    def evaluate(self, dataset: Any):
        """Regression summary on a labeled dataset (Spark model surface; computed
        natively — the reference exposes no evaluate for forests)."""
        from ..core.estimator import extract_eval_columns
        from .regression import LinearRegressionSummary

        out, label, pred, weight = extract_eval_columns(self, dataset)
        return LinearRegressionSummary(
            out, label, pred, weight, num_features=self.numFeatures,
            fit_intercept=False,
        )


class RandomForestClassificationModel(
    _RandomForestModel, HasProbabilityCol, HasRawPredictionCol
):
    _is_classification = True

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        self._setDefault(probabilityCol="probability", rawPredictionCol="rawPrediction")

    @property
    def numClasses(self) -> int:
        return self._model_attributes["num_classes"]

    def predict(self, value: np.ndarray) -> float:
        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return float(np.argmax(self._forest_outputs(X)[0]))

    def predictProbability(self, value: np.ndarray) -> np.ndarray:
        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return self._forest_outputs(X)[0]

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        prob = self._forest_outputs(X)
        # normalize away any averaging drift
        prob = prob / np.maximum(prob.sum(axis=1, keepdims=True), 1e-12)
        return {
            self.getOrDefault("predictionCol"): prob.argmax(axis=1).astype(np.float64),
            self.getOrDefault("probabilityCol"): prob,
            self.getOrDefault("rawPredictionCol"): prob * self.getNumTrees(),
        }

    def evaluate(self, dataset: Any):
        """Classification summary on a labeled dataset (Spark 3.1+
        RandomForestClassificationSummary surface; binary models additionally get
        the ROC/PR sweep). Computed natively — the reference exposes no evaluate
        for forests."""
        from ..core.estimator import extract_eval_columns
        from .classification import (
            BinaryLogisticRegressionSummary,
            LogisticRegressionSummary,
        )

        out, label, pred, weight = extract_eval_columns(self, dataset)
        if self.numClasses == 2:
            prob = np.stack(out[self.getOrDefault("probabilityCol")].to_numpy())
            return BinaryLogisticRegressionSummary(out, label, pred, prob[:, 1], weight)
        return LogisticRegressionSummary(out, label, pred, weight)
