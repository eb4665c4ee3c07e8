#
# KMeans estimator/model (L6 API) — pyspark.ml.clustering.KMeans-compatible surface,
# fit as one SPMD Lloyd program over the TPU mesh.
#
# Structural equivalent of reference python/src/spark_rapids_ml/clustering.py:84-604:
#   * param mapping incl. tol=0 -> tiny epsilon (reference clustering.py:84-141)
#   * n_init forced to 1 for Spark parity (reference clustering.py:317-319)
#   * fit returns cluster centers + inertia + n_iter attributes
#     (reference clustering.py:376-456)
# (DBSCAN, the other member of the reference module, lives in models/dbscan.py.)
#

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..core.dataset import densify
from ..core.backend_params import HasFeaturesCols, _TpuClass
from ..core.estimator import FitInputs, _TpuEstimator, _TpuModelWithPredictionCol
from ..core.params import (
    HasFeaturesCol,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
    HasTol,
    HasWeightCol,
    Param,
    TypeConverters,
)
from ..observability import counter_inc, span
from ..ops.kmeans import assign_counts, kmeans_fit, kmeans_predict


class _KMeansClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        # reference clustering.py:84-141
        return {
            "k": "n_clusters",
            "maxIter": "max_iter",
            "tol": "tol",
            "initMode": "init",
            "initSteps": "init_steps",
            "seed": "random_state",
            "distanceMeasure": "metric",  # euclidean + cosine (spherical kmeans)
            "featuresCol": "",
            "predictionCol": "",
            "weightCol": "",
            "solver": None,
            "maxBlockSizeInMB": None,
        }

    @classmethod
    def _param_value_mapping(cls):
        # tol=0 would spin max_iter rounds; remap to a tiny epsilon like the reference
        return {
            "tol": lambda x: 1.0e-16 if x == 0 else float(x),
            "init": lambda x: (
                x if x in ("k-means||", "scalable-k-means++", "random") else None
            ),
            "metric": lambda x: x if x in ("euclidean", "cosine") else None,
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_clusters": 8,
            "max_iter": 300,
            "tol": 1e-4,
            "init": "k-means||",
            "init_steps": 2,
            "random_state": 1,
            "metric": "euclidean",
            "n_init": 1,  # Spark parity (reference clustering.py:317-319)
        }

    @classmethod
    def _fallback_class(cls):
        from sklearn.cluster import KMeans as SkKMeans

        return SkKMeans


class _KMeansParams(
    HasFeaturesCol, HasFeaturesCols, HasPredictionCol, HasMaxIter, HasTol, HasSeed, HasWeightCol
):
    k: Param[int] = Param(
        "undefined", "k", "The number of clusters to create. Must be > 1.", TypeConverters.toInt
    )
    initMode: Param[str] = Param(
        "undefined",
        "initMode",
        "The initialization algorithm. Supported options: 'k-means||' and 'random'.",
        TypeConverters.toString,
    )
    initSteps: Param[int] = Param(
        "undefined",
        "initSteps",
        "The number of steps for k-means|| initialization mode. Must be > 0.",
        TypeConverters.toInt,
    )
    distanceMeasure: Param[str] = Param(
        "undefined",
        "distanceMeasure",
        "the distance measure. Supported options: 'euclidean' and 'cosine'.",
        TypeConverters.toString,
    )
    solver: Param[str] = Param(
        "undefined",
        "solver",
        "The solver algorithm for optimization. Supported options: 'auto', 'row', 'block'.",
        TypeConverters.toString,
    )
    maxBlockSizeInMB: Param[float] = Param(
        "undefined",
        "maxBlockSizeInMB",
        "Maximum memory in MB for stacking input data into blocks.",
        TypeConverters.toFloat,
    )

    def getK(self) -> int:
        return self.getOrDefault("k")

    def setFeaturesCol(self, value: str):
        return self._set(featuresCol=value)

    def setPredictionCol(self, value: str):
        return self._set(predictionCol=value)


class KMeans(_KMeansClass, _TpuEstimator, _KMeansParams):
    # Spark's KMeans validator requires k > 1 (pyspark ParamValidators.gt(1))
    _PARAM_BOUNDS_EXTRA = {"k": (2, None)}
    """KMeans on the TPU mesh: one jitted Lloyd loop, centroid psum over ICI.

    Drop-in for pyspark.ml.clustering.KMeans / reference
    spark_rapids_ml.clustering.KMeans (reference clustering.py:226-456).

    Example
    -------
    >>> from spark_rapids_ml_tpu.clustering import KMeans
    >>> model = KMeans(k=4, featuresCol="features").fit(df)
    >>> model.transform(df)   # adds 'prediction' column
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            featuresCol="features",
            predictionCol="prediction",
            k=2,
            maxIter=20,
            tol=1e-4,
            initMode="k-means||",
            initSteps=2,
            seed=1,
            distanceMeasure="euclidean",
            solver="auto",
            maxBlockSizeInMB=0.0,
        )
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def setK(self, value: int) -> "KMeans":
        return self._set_params(k=value)  # type: ignore[return-value]

    def setMaxIter(self, value: int) -> "KMeans":
        return self._set_params(maxIter=value)  # type: ignore[return-value]

    def _out_schema(self) -> List[str]:
        # cluster_sizes feeds the training summary (absent on streamed/fallback
        # fits; the model tolerates it)
        return ["cluster_centers", "inertia", "n_iter", "cluster_sizes"]

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # the sharded design matrix is staged on the mesh ONCE and every param map's
        # Lloyd run reuses it (reference loops cuML fits over the concatenated data,
        # P6 pattern, SURVEY.md §2.7)
        return True

    def _get_tpu_fit_func(self, extra_params: Optional[List[Dict[str, Any]]] = None):
        base = dict(self._tpu_params)

        def _fit(inputs: FitInputs):
            param_sets = extra_params if extra_params is not None else [base]
            results = []
            for ep in param_sets:
                p = {**base, **ep}
                if int(p["n_clusters"]) > inputs.desc.m:
                    raise ValueError(
                        f"k={p['n_clusters']} exceeds the number of rows "
                        f"{inputs.desc.m}; initialization would select padding rows "
                        "as centers."
                    )
                res = kmeans_fit(
                    inputs.features,
                    inputs.row_weight,
                    k=int(p["n_clusters"]),
                    max_iter=int(p["max_iter"]),
                    tol=float(p["tol"]),
                    init=str(p["init"]),
                    init_steps=int(p["init_steps"]),
                    seed=int(p["random_state"]) if p["random_state"] is not None else 1,
                    metric=str(p.get("metric", "euclidean")),
                    unit_weight=inputs.unit_weight,
                )
                # one assignment pass for the training summary's clusterSizes
                # (Spark KMeansSummary; the reference produces no summary). Done
                # HERE — not inside kmeans_fit — so the IVF index builds that call
                # the op directly never pay it. Counts ALL real rows (padding is
                # positional: rows beyond desc.m), including user weight-0 rows,
                # matching Spark's groupBy(prediction).count().
                with span("kmeans.summary"):
                    # the fetched centres go up again for the summary's pass
                    counter_inc(
                        "h2d.bytes", int(res["cluster_centers"].nbytes),
                        site="fit.centers",
                    )
                    res["cluster_sizes"] = assign_counts(
                        inputs.features,
                        jnp.asarray(res["cluster_centers"]),
                        inputs.desc.m,
                        cosine=str(p.get("metric", "euclidean")) == "cosine",
                    )
                results.append(res)
            return results if extra_params is not None else results[0]

        return _fit

    def _create_pyspark_model(self, attrs: Dict[str, Any]) -> "KMeansModel":
        return KMeansModel(**attrs)

    def _streaming_fit(self, fd, chain_ops=None) -> Dict[str, Any]:
        """Out-of-core exact Lloyd (ops/streaming.py): full-pass center updates with
        one batch resident at a time — the KMeans analog of the reference's UVM/SAM
        large-dataset path (utils.py:184-241). Selected automatically when the design
        matrix exceeds stream_threshold_bytes (core/estimator.py). `chain_ops`
        carries upstream featurizer transforms when this fit is the terminal
        stage of a fused pipeline chain (pipeline.py): they apply in-program, so
        raw batches upload once and intermediates never touch the host."""
        from .. import config as _config
        from ..core.dataset import densify as _densify
        from ..ops.streaming import streaming_kmeans_fit
        from ..parallel.partitioner import active_partitioner

        p = self._tpu_params
        if int(p["n_clusters"]) > fd.n_rows:
            raise ValueError(
                f"k={p['n_clusters']} exceeds the number of rows {fd.n_rows}."
            )
        return streaming_kmeans_fit(
            _densify(fd.features, self._float32_inputs),
            fd.weight,
            k=int(p["n_clusters"]),
            max_iter=int(p["max_iter"]),
            tol=float(p["tol"]),
            seed=int(p["random_state"]) if p["random_state"] is not None else 1,
            batch_rows=int(_config.get("stream_batch_rows")),
            mesh=active_partitioner(self.num_workers).mesh,
            metric=str(p.get("metric", "euclidean")),
            float32=self._float32_inputs,
            chain_ops=chain_ops,
        )

    def _fit_fallback_model(self, twin: type, fd) -> Dict[str, Any]:
        if self.getOrDefault("distanceMeasure") != "euclidean":
            raise ValueError(
                "The sklearn CPU fallback cannot preserve distanceMeasure='cosine' "
                "(cosine IS supported on the TPU path; remove the other unsupported "
                f"params {getattr(self, '_fallback_requested_params', set())} to use it)."
            )
        X = densify(fd.features, float32=self._float32_inputs)
        init = self.getOrDefault("initMode")
        sk = twin(
            n_clusters=self.getOrDefault("k"),
            max_iter=self.getOrDefault("maxIter"),
            tol=self.getOrDefault("tol"),
            init="k-means++" if init != "random" else "random",
            n_init=1,
            random_state=self.getOrDefault("seed") & 0x7FFFFFFF,
        ).fit(X, sample_weight=fd.weight)
        return {
            "cluster_centers": sk.cluster_centers_.astype(np.float32),
            "inertia": float(sk.inertia_),
            "n_iter": int(sk.n_iter_),
        }


class KMeansSummary:
    """Training summary surface of pyspark.ml.clustering.KMeansSummary."""

    def __init__(
        self, k: int, cluster_sizes: np.ndarray, training_cost: float, num_iter: int
    ) -> None:
        self.k = int(k)
        self.clusterSizes = [int(s) for s in cluster_sizes]
        self.trainingCost = float(training_cost)
        self.numIter = int(num_iter)


class KMeansModel(_KMeansClass, _TpuModelWithPredictionCol, _KMeansParams):
    """Fitted KMeans model (reference clustering.py:459-604)."""

    def __init__(
        self,
        cluster_centers: np.ndarray,
        inertia: float,
        n_iter: int,
        cluster_sizes: "np.ndarray | None" = None,
    ) -> None:
        super().__init__(
            cluster_centers=np.asarray(cluster_centers),
            inertia=float(inertia),
            n_iter=int(n_iter),
            cluster_sizes=(
                np.asarray(cluster_sizes) if cluster_sizes is not None else None
            ),
        )
        self._setDefault(
            featuresCol="features",
            predictionCol="prediction",
            distanceMeasure="euclidean",
        )
        # Spark semantics: a summary exists on a freshly-fit model only; loaded
        # models have hasSummary=False. The estimator sets this flag after fit.
        self._has_training_summary = False

    def clusterCenters(self) -> List[np.ndarray]:
        """Spark MLlib KMeansModel surface."""
        return list(self._model_attributes["cluster_centers"])

    def partial_fit_updater(self, **kwargs):
        """Streamed continual-learning updater anchored on this model: mini-
        batch discounted center updates per arXiv 1505.06807 (continual/
        partial_fit.py, docs/design.md §7d)."""
        from ..continual.partial_fit import KMeansUpdater

        return KMeansUpdater(self, **kwargs)

    @property
    def hasSummary(self) -> bool:
        """True on a freshly-fit model (the reference always returns False,
        clustering.py:549-553 — the TPU fit records the sizes at no extra cost
        beyond one assignment pass)."""
        return (
            self._has_training_summary
            and self._model_attributes.get("cluster_sizes") is not None
        )

    @property
    def summary(self) -> KMeansSummary:
        """KMeansSummary (k, clusterSizes, trainingCost, numIter); raises after
        save/load like Spark."""
        if not self.hasSummary:
            raise RuntimeError(
                f"No training summary available for this {self.__class__.__name__}"
            )
        a = self._model_attributes
        return KMeansSummary(
            k=a["cluster_centers"].shape[0],
            cluster_sizes=a["cluster_sizes"],
            training_cost=a["inertia"],
            num_iter=a["n_iter"],
        )

    def cpu(self):
        """CPU twin of this model (the reference's model.cpu() builds the pyspark
        twin via py4j, clustering.py:524-544; pyspark is optional here so the twin
        is the sklearn estimator with the fitted state installed)."""
        from sklearn.cluster import KMeans as SkKMeans

        centers = np.asarray(self._model_attributes["cluster_centers"], np.float64)
        sk = SkKMeans(n_clusters=centers.shape[0], n_init=1)
        sk.cluster_centers_ = centers
        sk.inertia_ = float(self._model_attributes["inertia"])
        sk.n_iter_ = int(self._model_attributes["n_iter"])
        sk._n_threads = 1
        sk.n_features_in_ = centers.shape[1]
        sk.labels_ = None
        return sk

    @property
    def cluster_centers_(self) -> np.ndarray:
        return self._model_attributes["cluster_centers"]

    @property
    def inertia_(self) -> float:
        return self._model_attributes["inertia"]

    @property
    def _cosine(self) -> bool:
        return self.getOrDefault("distanceMeasure") == "cosine"

    def predict(self, value: np.ndarray) -> int:
        """Single-vector prediction (Spark API)."""
        from ..observability.inference import predict_dispatch

        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return int(
            np.asarray(
                predict_dispatch(
                    self, kmeans_predict, X, self.cluster_centers_, self._cosine
                )
            )[0]
        )

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        from ..observability.inference import predict_to_host

        if self._cosine and not np.all(np.linalg.norm(X, axis=1) > 0):
            raise ValueError(
                "Cosine distance is not defined for zero-length vectors; the input "
                "contains an all-zero feature row."
            )
        pred = predict_to_host(
            self, kmeans_predict, X, self.cluster_centers_, self._cosine
        )
        return {self.getOrDefault("predictionCol"): pred.astype(np.int32)}
