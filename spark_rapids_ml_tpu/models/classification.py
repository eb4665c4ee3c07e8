#
# LogisticRegression estimator/model (L6 API) — pyspark.ml.classification-compatible
# surface; distributed quasi-Newton fit on the TPU mesh (ops/logistic.py).
#
# Structural equivalent of reference python/src/spark_rapids_ml/classification.py:
#   * reg params -> (penalty, C, l1_ratio) mapping (reference classification.py:679-744)
#     — here mapped directly to (alpha, l1_ratio)
#   * L-BFGS with lbfgs_memory=10, linesearch_max_iter=20
#     (reference classification.py:1046-1052)
#   * missing-label validation (reference classification.py:1093-1102)
#   * single-label ±inf intercept handling (reference classification.py:1106-1121)
#   * multinomial intercept centering (reference classification.py:1135-1147)
#   * transform computes prediction/probability/rawPrediction from the decision
#     function (reference classification.py:1455-1553)
# (RandomForestClassifier, the other member of the reference module, lives in
# models/tree.py.)
#

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..core.dataset import densify
from ..core.backend_params import HasEnableSparseDataOptim, HasFeaturesCols, _TpuClass
from ..core.estimator import (
    FitInputs,
    _TpuEstimatorSupervised,
    _TpuModelWithPredictionCol,
)
from ..core.params import (
    HasAggregationDepth,
    HasElasticNetParam,
    HasFeaturesCol,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasRegParam,
    HasStandardization,
    HasThresholds,
    HasTol,
    HasWeightCol,
    Param,
    TypeConverters,
)
from ..observability import span
from ..ops.logistic import logreg_decision, logreg_fit


def _validate_labels(y_host) -> "tuple[np.ndarray, int]":
    """Shared label validation for the in-core and streamed LogisticRegression fit
    paths: labels must be non-negative integers with every class 0..k-1 present
    (reference raises with workaround text, classification.py:1093-1102).
    Returns (classes, n_classes)."""
    classes = np.unique(y_host)
    n_classes = int(classes.max()) + 1 if len(classes) > 0 else 0
    if not np.array_equal(classes, classes.astype(np.int64)) or (
        len(classes) > 0 and classes.min() < 0
    ):
        raise ValueError("Labels must be non-negative integers 0..k-1.")
    if len(classes) != n_classes and len(classes) > 1:
        raise RuntimeError(
            f"Labels {sorted(set(range(n_classes)) - set(classes.astype(int)))} "
            "are missing from the dataset: every class in 0..k-1 must appear. "
            "Re-index labels to be consecutive."
        )
    return classes, n_classes


class _LogisticRegressionClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        # reference classification.py:679-744 (there regParam/elasticNetParam are
        # refactored into cuML's (penalty, C, l1_ratio); our backend takes them direct)
        return {
            "regParam": "alpha",
            "elasticNetParam": "l1_ratio",
            "fitIntercept": "fit_intercept",
            "standardization": "standardization",
            "maxIter": "max_iter",
            "tol": "tol",
            "family": "family",
            "threshold": "",
            "thresholds": "",
            "featuresCol": "",
            "labelCol": "",
            "predictionCol": "",
            "probabilityCol": "",
            "rawPredictionCol": "",
            "weightCol": "",
            "aggregationDepth": "",
            "maxBlockSizeInMB": "",
            # sparse inputs are accepted and densified through the native kernel
            # (core/dataset.py densify); gather-based true-sparse device kernels are
            # a round-2 item (reference sparse path: classification.py:1002-1055)
            "enable_sparse_data_optim": "",
            # box constraints run NATIVELY via the projected fit
            # (ops/logistic._projected_fit) — the reference maps these to None and
            # falls back to Spark (classification.py:694-698); values stay on the
            # Spark side (matrices don't belong in the backend kernel dict)
            "lowerBoundsOnCoefficients": "",
            "upperBoundsOnCoefficients": "",
            "lowerBoundsOnIntercepts": "",
            "upperBoundsOnIntercepts": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        return {
            "family": lambda x: x if x in ("auto", "binomial", "multinomial") else None,
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "alpha": 0.0,
            "l1_ratio": 0.0,
            "fit_intercept": True,
            "standardization": True,
            "max_iter": 100,
            "tol": 1e-6,
            "family": "auto",
        }

    @classmethod
    def _fallback_class(cls):
        from sklearn.linear_model import LogisticRegression as SkLogReg

        return SkLogReg


class _LogisticRegressionParams(
    HasFeaturesCol,
    HasFeaturesCols,
    HasEnableSparseDataOptim,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasThresholds,
    HasWeightCol,
    HasAggregationDepth,
):
    family: Param[str] = Param(
        "undefined",
        "family",
        "The name of family which is a description of the label distribution to be "
        "used in the model. Supported options: auto, binomial, multinomial",
        TypeConverters.toString,
    )
    threshold: Param[float] = Param(
        "undefined",
        "threshold",
        "Threshold in binary classification prediction, in range [0, 1].",
        TypeConverters.toFloat,
    )
    # Spark LogisticRegression surface parity (reference classification.py:679-744):
    # aggregationDepth/maxBlockSizeInMB are Spark-executor tuning knobs with no TPU
    # meaning (accepted, ignored); the coefficient/intercept bounds run NATIVELY
    # via the projected fit (ops/logistic._projected_fit).
    maxBlockSizeInMB: Param[float] = Param(
        "undefined", "maxBlockSizeInMB",
        "Maximum stacked-block memory in MB (Spark tuning knob; ignored).",
        TypeConverters.toFloat,
    )
    lowerBoundsOnCoefficients: Param[Any] = Param(
        "undefined", "lowerBoundsOnCoefficients",
        "Lower-bound matrix ((numCoefficientSets, numFeatures)) for the "
        "box-constrained fit.",
        TypeConverters.toList,
    )
    upperBoundsOnCoefficients: Param[Any] = Param(
        "undefined", "upperBoundsOnCoefficients",
        "Upper-bound matrix ((numCoefficientSets, numFeatures)) for the "
        "box-constrained fit.",
        TypeConverters.toList,
    )
    lowerBoundsOnIntercepts: Param[Any] = Param(
        "undefined", "lowerBoundsOnIntercepts",
        "Lower-bound vector (numCoefficientSets) for the box-constrained fit.",
        TypeConverters.toList,
    )
    upperBoundsOnIntercepts: Param[Any] = Param(
        "undefined", "upperBoundsOnIntercepts",
        "Upper-bound vector (numCoefficientSets) for the box-constrained fit.",
        TypeConverters.toList,
    )

    def setFeaturesCol(self, value: str):
        return self._set(featuresCol=value)

    def setLabelCol(self, value: str):
        return self._set(labelCol=value)


class LogisticRegression(
    _LogisticRegressionClass, _TpuEstimatorSupervised, _LogisticRegressionParams
):
    """LogisticRegression on the TPU mesh: jitted L-BFGS (or FISTA for L1) with the
    gradient psum over ICI. Drop-in for pyspark.ml.classification.LogisticRegression /
    reference spark_rapids_ml.classification.LogisticRegression
    (reference classification.py:747-1204)."""

    def _validate_param_bounds(self) -> None:
        # bounds incompatibilities fail on the DRIVER before any dispatch, like the
        # numeric bounds (the worker-side checks remain as backstops)
        super()._validate_param_bounds()
        bound_names = (
            "lowerBoundsOnCoefficients", "upperBoundsOnCoefficients",
            "lowerBoundsOnIntercepts", "upperBoundsOnIntercepts",
        )
        any_bounds = any(self.isDefined(n) for n in bound_names)
        if not any_bounds:
            return
        if self.getOrDefault("elasticNetParam") != 0.0:
            raise ValueError(
                "Coefficient bounds support only L2 regularization "
                "(elasticNetParam must be 0.0), matching Spark."
            )
        icpt_bounded = self.isDefined("lowerBoundsOnIntercepts") or self.isDefined(
            "upperBoundsOnIntercepts"
        )
        if icpt_bounded and not self.getOrDefault("fitIntercept"):
            raise ValueError(
                "Intercept bounds require fitIntercept=True (an unbounded, "
                "unfitted intercept cannot honor them)."
            )
        if self.hasParam("enable_sparse_data_optim") and self.isDefined(
            "enable_sparse_data_optim"
        ) and self.getOrDefault("enable_sparse_data_optim"):
            raise ValueError(
                "Coefficient bounds require dense features "
                "(disable enable_sparse_data_optim)."
            )

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            probabilityCol="probability",
            rawPredictionCol="rawPrediction",
            regParam=0.0,
            elasticNetParam=0.0,
            fitIntercept=True,
            standardization=True,
            maxIter=100,
            tol=1e-6,
            family="auto",
            threshold=0.5,
            aggregationDepth=2,
            maxBlockSizeInMB=0.0,
        )
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def setRegParam(self, value: float) -> "LogisticRegression":
        return self._set_params(regParam=value)  # type: ignore[return-value]

    def setMaxIter(self, value: int) -> "LogisticRegression":
        return self._set_params(maxIter=value)  # type: ignore[return-value]

    def _out_schema(self) -> List[str]:
        return ["coefficients", "intercepts", "n_iter", "objective", "num_classes", "gradient"]

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # device-resident data is reused across param maps (the reference loops cuML
        # fits over the concatenated arrays, classification.py:1173-1190)
        return True

    def _supports_sparse_fit(self) -> bool:
        # matrix-free ELL kernels in ops/sparse.py (reference CSR training path,
        # classification.py:1002-1055)
        return True

    def _get_tpu_fit_func(self, extra_params: Optional[List[Dict[str, Any]]] = None):
        base = dict(self._tpu_params)
        bounds = None
        bound_vals = [
            self.getOrDefault(name) if self.isDefined(name) else None
            for name in (
                "lowerBoundsOnCoefficients", "upperBoundsOnCoefficients",
                "lowerBoundsOnIntercepts", "upperBoundsOnIntercepts",
            )
        ]
        if any(v is not None for v in bound_vals):
            bounds = tuple(bound_vals)

        def _fit(inputs: FitInputs):
            y_host = inputs.host_label
            if y_host is None and inputs.label is not None:
                # global-array path (spark/integration.py): no host copy travels;
                # recover the real labels from the device array, masking padding
                lab = np.asarray(inputs.label)
                w = np.asarray(inputs.row_weight)
                y_host = lab[w > 0]
            with span("logistic.labels"):
                classes, n_classes = _validate_labels(y_host)

            param_sets = extra_params if extra_params is not None else [base]
            results = []
            for p in param_sets:
                p = {**base, **p}
                family = p["family"]
                multinomial = family == "multinomial" or (
                    family == "auto" and n_classes > 2
                )
                if not multinomial and n_classes > 2:
                    raise ValueError(
                        f"Binomial family only supports 1 or 2 outcome classes but "
                        f"found {n_classes}."
                    )
                if len(classes) == 1:
                    # single-label degenerate fit: ±inf intercept, zero coefficients
                    # (reference classification.py:1106-1121)
                    d = inputs.desc.n
                    only = int(classes[0])
                    if multinomial:
                        coef = np.zeros((max(n_classes, 1), d), np.float32)
                        intercept = np.full((max(n_classes, 1),), -np.inf, np.float32)
                        intercept[only] = np.inf
                    else:
                        coef = np.zeros((1, d), np.float32)
                        intercept = np.array(
                            [np.inf if only == 1 else -np.inf], np.float32
                        )
                    if bounds is not None:
                        # the degenerate model must still live inside the user's box
                        lb_c, ub_c, lb_i, ub_i = bounds
                        if lb_c is not None or ub_c is not None:
                            lo = -np.inf if lb_c is None else np.asarray(lb_c, np.float32)
                            hi = np.inf if ub_c is None else np.asarray(ub_c, np.float32)
                            coef = np.clip(coef, lo, hi)
                        if lb_i is not None or ub_i is not None:
                            lo = -np.inf if lb_i is None else np.asarray(lb_i, np.float32)
                            hi = np.inf if ub_i is None else np.asarray(ub_i, np.float32)
                            intercept = np.clip(intercept, lo, hi)
                    results.append(
                        {
                            "coefficients": coef,
                            "intercepts": intercept,
                            "n_iter": 0,
                            "objective": 0.0,
                            "num_classes": n_classes,
                        }
                    )
                    continue
                common = dict(
                    n_classes=n_classes,
                    reg=float(p["alpha"]),
                    l1_ratio=float(p["l1_ratio"]),
                    fit_intercept=bool(p["fit_intercept"]),
                    standardize=bool(p["standardization"]),
                    max_iter=int(p["max_iter"]),
                    tol=float(p["tol"]),
                    multinomial=multinomial,
                )
                if inputs.sparse_values is not None:
                    from ..ops.sparse import sparse_logreg_fit

                    if bounds is not None:
                        raise ValueError(
                            "Coefficient bounds require dense features "
                            "(disable enable_sparse_data_optim)."
                        )
                    attrs = sparse_logreg_fit(
                        inputs.sparse_values,
                        inputs.sparse_indices,
                        inputs.desc.n,
                        inputs.label,
                        inputs.row_weight,
                        **common,
                    )
                else:
                    attrs = logreg_fit(
                        inputs.features, inputs.label, inputs.row_weight,
                        bounds=bounds, **common,
                    )
                attrs["num_classes"] = n_classes
                results.append(attrs)
            return results if extra_params is not None else results[0]

        return _fit

    def _create_pyspark_model(self, attrs: Dict[str, Any]) -> "LogisticRegressionModel":
        return LogisticRegressionModel(**attrs)

    def _streaming_fit(self, fd, chain_ops=None) -> Dict[str, Any]:
        """Out-of-core fit: X stays host-resident, every L-BFGS objective/gradient
        evaluation streams batches through the device (ops/streaming.py) — the
        LogisticRegression analog of the reference's UVM/SAM path (reference
        utils.py:184-241) that BASELINE config 3 (500M x 256) requires.
        L1/elastic-net runs the streamed FISTA; routes in-core (with a warning)
        only for coefficient bounds, sparse features, and single-class
        degenerate fits. `chain_ops` carries upstream featurizer transforms when
        this fit is the terminal stage of a fused pipeline chain (pipeline.py)."""
        from .. import config as _config
        from ..core.dataset import _is_sparse, densify as _densify
        from ..ops.streaming import streaming_logreg_fit
        from ..parallel.partitioner import active_partitioner

        p = self._tpu_params
        bounds_set = any(
            self.isDefined(name) and self.getOrDefault(name) is not None
            for name in (
                "lowerBoundsOnCoefficients", "upperBoundsOnCoefficients",
                "lowerBoundsOnIntercepts", "upperBoundsOnIntercepts",
            )
        )
        with span("logistic.labels"):
            classes, n_classes = _validate_labels(fd.label)
        if bounds_set or _is_sparse(fd.features) or len(classes) <= 1:
            if chain_ops:
                # the fuser gates on fuse-eligibility, so only a direct caller
                # can land here; in-core would silently drop the chain
                raise ValueError(
                    "This LogisticRegression configuration fits in-core and "
                    "cannot run a fused featurize->fit chain."
                )
            self.logger.warning(
                "streamed LogisticRegression covers dense multi-class fits "
                "only (no coefficient bounds); fitting in-core despite "
                "stream_threshold_bytes."
            )
            inputs = self._build_fit_inputs(fd)
            return self._get_tpu_fit_func(None)(inputs)
        family = p["family"]
        multinomial = family == "multinomial" or (family == "auto" and n_classes > 2)
        if not multinomial and n_classes > 2:
            raise ValueError(
                f"Binomial family only supports 1 or 2 outcome classes but "
                f"found {n_classes}."
            )
        attrs = streaming_logreg_fit(
            _densify(fd.features, self._float32_inputs),
            fd.label,
            fd.weight,
            n_classes=n_classes,
            reg=float(p["alpha"]),
            l1_ratio=float(p["l1_ratio"]),
            fit_intercept=bool(p["fit_intercept"]),
            standardize=bool(p["standardization"]),
            max_iter=int(p["max_iter"]),
            tol=float(p["tol"]),
            multinomial=multinomial,
            batch_rows=int(_config.get("stream_batch_rows")),
            mesh=active_partitioner(self.num_workers).mesh,
            float32=self._float32_inputs,
            chain_ops=chain_ops,
        )
        attrs["num_classes"] = n_classes
        return attrs

    def _fit_fallback_model(self, twin: type, fd) -> Dict[str, Any]:
        X = densify(fd.features, float32=self._float32_inputs)
        reg = self.getOrDefault("regParam")
        l1r = self.getOrDefault("elasticNetParam")
        kwargs: Dict[str, Any] = {
            "C": 1.0 / (reg * fd.n_rows) if reg > 0 else 1e12,
            "fit_intercept": self.getOrDefault("fitIntercept"),
            "max_iter": self.getOrDefault("maxIter"),
            "tol": self.getOrDefault("tol"),
        }
        if reg > 0 and l1r > 0:
            kwargs.update(l1_ratio=l1r, solver="saga")
        sk = twin(**kwargs).fit(
            np.asarray(X, dtype=np.float64), fd.label, sample_weight=fd.weight
        )
        coef = sk.coef_.astype(np.float32)
        return {
            "coefficients": coef,
            "intercepts": np.atleast_1d(sk.intercept_).astype(np.float32),
            "n_iter": int(np.max(sk.n_iter_)),
            "objective": 0.0,
            "num_classes": len(sk.classes_),
        }


class LogisticRegressionModel(
    _LogisticRegressionClass, _TpuModelWithPredictionCol, _LogisticRegressionParams
):
    """Fitted logistic regression model (reference classification.py:1206-1615)."""

    def __init__(
        self,
        coefficients: np.ndarray,
        intercepts: np.ndarray,
        n_iter: int,
        objective: float,
        num_classes: int,
        gradient: Optional[np.ndarray] = None,
    ) -> None:
        # `gradient`: the objective's gradient at (coefficients, intercepts) as
        # the dense quasi-Newton fit formed it, (rows, d+1); None from any
        # other path (ops/logistic.py::logreg_fit)
        super().__init__(
            coefficients=np.asarray(coefficients),
            intercepts=np.asarray(intercepts),
            n_iter=int(n_iter),
            objective=float(objective),
            num_classes=int(num_classes),
            gradient=None if gradient is None else np.asarray(gradient),
        )
        self._setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            probabilityCol="probability",
            rawPredictionCol="rawPrediction",
            threshold=0.5,
        )

    # --- Spark MLlib surface ---

    @property
    def numClasses(self) -> int:
        return self._model_attributes["num_classes"]

    def _serving_device_attrs(self):
        # what predict consumes; the fit's `gradient` is a record, not a weight
        return ("coefficients", "intercepts")

    def partial_fit_updater(self, **kwargs):
        """Streamed continual-learning updater anchored on this model:
        proximal-gradient steps warm-started from the served coefficients
        (continual/partial_fit.py, docs/design.md §7d)."""
        from ..continual.partial_fit import LogisticRegressionUpdater

        return LogisticRegressionUpdater(self, **kwargs)

    @property
    def numFeatures(self) -> int:
        return int(self._model_attributes["coefficients"].shape[1])

    @property
    def _is_multinomial_layout(self) -> bool:
        return self._model_attributes["coefficients"].shape[0] > 1

    @property
    def coefficients(self) -> np.ndarray:
        """Binary-only (d,) vector, Spark semantics."""
        if self._is_multinomial_layout:
            raise RuntimeError(
                "Multinomial models use coefficientMatrix instead of coefficients."
            )
        return self._model_attributes["coefficients"][0]

    @property
    def intercept(self) -> float:
        if self._is_multinomial_layout:
            raise RuntimeError(
                "Multinomial models use interceptVector instead of intercept."
            )
        return float(self._model_attributes["intercepts"][0])

    @property
    def coefficientMatrix(self) -> np.ndarray:
        return self._model_attributes["coefficients"]

    @property
    def interceptVector(self) -> np.ndarray:
        return self._model_attributes["intercepts"]

    @property
    def hasSummary(self) -> bool:
        """No training summary is produced (reference classification.py:1575-1581)."""
        return False

    @property
    def summary(self):
        """Spark raises when hasSummary is False; match it
        (reference classification.py:1583-1591)."""
        raise RuntimeError(
            f"No training summary available for this {self.__class__.__name__}"
        )

    def _margins(self, X: np.ndarray) -> np.ndarray:
        from ..observability.inference import predict_dispatch

        coef = self._model_attributes["coefficients"].astype(np.float32)
        icpt = self._model_attributes["intercepts"].astype(np.float32)
        # guard degenerate single-label ±inf intercepts on the host path
        if not np.all(np.isfinite(icpt)):
            if self._is_multinomial_layout:
                return np.broadcast_to(icpt, (X.shape[0], icpt.shape[0])).copy()
            return np.broadcast_to(icpt[0], (X.shape[0],)).copy()
        return np.asarray(
            predict_dispatch(
                self, logreg_decision, X, coef, icpt, self._is_multinomial_layout
            )
        )

    def _supports_sparse_transform(self) -> bool:
        return True

    def _transform_sparse(self, csr: Any) -> Dict[str, np.ndarray]:
        """Predict on CSR queries without densifying: margins via the ELL gather
        contraction (ops/sparse.py), then the shared output math."""
        import jax.numpy as jnp

        from ..ops.sparse import csr_to_ell, ell_matmat, ell_matvec

        coef = self._model_attributes["coefficients"].astype(np.float32)
        icpt = self._model_attributes["intercepts"].astype(np.float32)
        if not np.all(np.isfinite(icpt)):
            n = csr.shape[0]
            if self._is_multinomial_layout:
                z = np.broadcast_to(icpt, (n, icpt.shape[0])).copy()
            else:
                z = np.broadcast_to(icpt[0], (n,)).copy()
            return self._outputs_from_margins(z)
        from ..observability.inference import predict_dispatch

        values, indices = csr_to_ell(csr, float32=True)
        vj, ij = jnp.asarray(values), jnp.asarray(indices)
        if self._is_multinomial_layout:
            z = np.asarray(
                predict_dispatch(self, ell_matmat, vj, ij, jnp.asarray(coef.T))
            ) + icpt
        else:
            z = np.asarray(
                predict_dispatch(self, ell_matvec, vj, ij, jnp.asarray(coef[0]))
            ) + icpt[0]
        return self._outputs_from_margins(z)

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        return self._outputs_from_margins(self._margins(X))

    def _outputs_from_margins(self, z: np.ndarray) -> Dict[str, np.ndarray]:
        if z.ndim == 1:  # binomial
            raw = np.stack([-z, z], axis=1)
            with np.errstate(over="ignore"):
                p1 = 1.0 / (1.0 + np.exp(-z))
            prob = np.stack([1.0 - p1, p1], axis=1)
            thr = self.getOrDefault("threshold")
            pred = (p1 > thr).astype(np.float64)
        else:
            raw = z
            # clip ±inf margins (single-label degenerate models) to softmax-safe
            # finite values so probabilities come out one-hot rather than NaN
            zf = np.clip(z, -5e2, 5e2)
            zs = zf - zf.max(axis=1, keepdims=True)
            e = np.exp(zs)
            prob = e / e.sum(axis=1, keepdims=True)
            scaled = prob
            if self.isSet("thresholds"):
                t = np.asarray(self.getOrDefault("thresholds"), dtype=np.float64)
                scaled = prob / np.where(t == 0.0, 1e-12, t)
            pred = scaled.argmax(axis=1).astype(np.float64)
        return {
            self.getOrDefault("predictionCol"): pred,
            self.getOrDefault("probabilityCol"): prob,
            self.getOrDefault("rawPredictionCol"): raw,
        }

    def cpu(self):
        """sklearn LogisticRegression twin with the fitted state installed (the
        reference builds the pyspark twin via py4j; pyspark is optional here)."""
        from sklearn.linear_model import LogisticRegression as SkLR

        coef = np.asarray(self._model_attributes["coefficients"], np.float64)
        icpt = np.asarray(self._model_attributes["intercepts"], np.float64)
        k = int(self._model_attributes["num_classes"])
        sk = SkLR()
        sk.coef_ = coef
        sk.intercept_ = icpt
        sk.classes_ = np.arange(max(k, 2), dtype=np.float64)
        sk.n_features_in_ = coef.shape[1]
        sk.n_iter_ = np.array([int(self._model_attributes["n_iter"])])
        return sk

    def predict(self, value: np.ndarray) -> float:
        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return float(self._transform_arrays(X)[self.getOrDefault("predictionCol")][0])

    def predictProbability(self, value: np.ndarray) -> np.ndarray:
        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return self._transform_arrays(X)[self.getOrDefault("probabilityCol")][0]

    def predictRaw(self, value: np.ndarray) -> np.ndarray:
        """Raw margin vector for one feature vector (pyspark model surface)."""
        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return self._transform_arrays(X)[self.getOrDefault("rawPredictionCol")][0]

    def evaluate(self, dataset: Any) -> "LogisticRegressionSummary":
        """Evaluate on a labeled dataset, returning the Spark summary surface —
        computed natively (the reference converts to a pyspark model and
        delegates, classification.py:1597-1601)."""
        from ..core.estimator import extract_eval_columns

        out, label, pred, weight = extract_eval_columns(self, dataset)
        if self.numClasses == 2:
            prob = np.stack(out[self.getOrDefault("probabilityCol")].to_numpy())
            return BinaryLogisticRegressionSummary(
                out, label, pred, prob[:, 1], weight
            )
        return LogisticRegressionSummary(out, label, pred, weight)

    def _combine(
        self, models: List["LogisticRegressionModel"]
    ) -> "LogisticRegressionModel":
        """Keep sibling models for one-pass CV transform-evaluate
        (reference classification.py:1557-1572)."""
        first = models[0]
        first._combined_models = models
        return first


class LogisticRegressionSummary:
    """Evaluation summary over a predictions frame — the surface of
    pyspark.ml.classification.LogisticRegressionSummary, computed natively on the
    metrics/ reduction classes (the reference's model.evaluate() converts to a
    pyspark model and delegates, classification.py:1597-1601)."""

    def __init__(
        self,
        predictions,
        label: np.ndarray,
        pred: np.ndarray,
        weight: Optional[np.ndarray] = None,
    ) -> None:
        from ..metrics.MulticlassMetrics import MulticlassMetrics

        self.predictions = predictions
        self._m = MulticlassMetrics.from_predictions(label, pred, weight)
        self._labels = sorted(set(np.asarray(label, np.float64).tolist()))

    @property
    def labels(self) -> List[float]:
        return list(self._labels)

    @property
    def accuracy(self) -> float:
        return self._m.accuracy()

    @property
    def weightedPrecision(self) -> float:
        return self._m.weighted_precision()

    @property
    def weightedRecall(self) -> float:
        return self._m.weighted_recall()

    def weightedFMeasure(self, beta: float = 1.0) -> float:
        return self._m.weighted_f_measure(beta)

    @property
    def weightedTruePositiveRate(self) -> float:
        return self._m.weighted_recall()

    @property
    def weightedFalsePositiveRate(self) -> float:
        return self._m.weighted_false_positive_rate()

    @property
    def precisionByLabel(self) -> List[float]:
        return [self._m._precision(l) for l in self._labels]

    @property
    def recallByLabel(self) -> List[float]:
        return [self._m._recall(l) for l in self._labels]

    def fMeasureByLabel(self, beta: float = 1.0) -> List[float]:
        return [self._m._f_measure(l, beta) for l in self._labels]

    @property
    def truePositiveRateByLabel(self) -> List[float]:
        return self.recallByLabel

    @property
    def falsePositiveRateByLabel(self) -> List[float]:
        return [self._m._false_positive_rate(l) for l in self._labels]


class BinaryLogisticRegressionSummary(LogisticRegressionSummary):
    """Adds the threshold-sweep metrics (areaUnderROC, roc/pr curves) for binary
    models — pyspark.ml.classification.BinaryLogisticRegressionSummary surface."""

    def __init__(
        self,
        predictions,
        label: np.ndarray,
        pred: np.ndarray,
        score: np.ndarray,
        weight: Optional[np.ndarray] = None,
    ) -> None:
        from ..metrics.utils import binary_classification_sweep

        super().__init__(predictions, label, pred, weight)
        self._tps, self._fps = binary_classification_sweep(score, label, weight)
        self._P, self._N = self._tps[-1], self._fps[-1]

    @property
    def areaUnderROC(self) -> float:
        from ..metrics.utils import area_under_roc

        return area_under_roc(self._tps, self._fps)

    @property
    def roc(self):
        import pandas as pd

        return pd.DataFrame(
            {"FPR": self._fps / self._N, "TPR": self._tps / self._P}
        )

    @property
    def pr(self):
        import pandas as pd

        recall = self._tps / self._P
        precision = np.where(
            self._tps + self._fps > 0,
            self._tps / np.maximum(self._tps + self._fps, 1e-300),
            1.0,
        )
        return pd.DataFrame({"recall": recall, "precision": precision})
