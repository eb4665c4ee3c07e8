#
# LinearRegression estimator/model (L6 API) — pyspark.ml.regression.LinearRegression-
# compatible surface; OLS/Ridge/ElasticNet fit as one SPMD stats pass + replicated
# solver on the TPU mesh.
#
# Structural equivalent of reference python/src/spark_rapids_ml/regression.py:181-863:
#   * param mapping incl. regParam->alpha, standardization->normalize
#     (reference regression.py:183-215)
#   * solver dispatch by regularization (reference regression.py:548-606): here
#     closed-form L2 vs FISTA elastic net (ops/linear.py)
#   * single-pass fitMultiple reusing the data pass (reference regression.py:657-674)
#   * 1-feature inputs are supported (the reference guards/raises for dim==1 because
#     of a cuML limitation, regression.py:499-505 — no such limit here)
# (RandomForestRegressor, the other member of the reference module, lives in
# models/tree.py.)
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
    HasElasticNetParam,
    HasFeaturesCol,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasRegParam,
    HasSolver,
    HasStandardization,
    HasTol,
    HasWeightCol,
    Param,
    TypeConverters,
)
from ..ops.linear import linreg_fit, linreg_predict


class _LinearRegressionClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        # reference regression.py:183-215
        return {
            "regParam": "alpha",
            "elasticNetParam": "l1_ratio",
            "fitIntercept": "fit_intercept",
            "standardization": "normalize",
            "maxIter": "max_iter",
            "tol": "tol",
            "loss": "loss",
            "solver": "solver",
            # huber is NATIVE here (ops/linear.huber_fit) — the reference cannot
            # run it on device at all (cuML lacks huber; regression.py:183-215)
            "epsilon": "epsilon",
            "aggregationDepth": "",
            "maxBlockSizeInMB": "",
            "featuresCol": "",
            "labelCol": "",
            "predictionCol": "",
            "weightCol": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        return {
            "loss": lambda x: {
                "squaredError": "squared_loss",
                "squared_loss": "squared_loss",
                "huber": "huber",
            }.get(x),
            "solver": lambda x: {"auto": "eig", "normal": "eig", "eig": "eig", "l-bfgs": "eig"}.get(x),
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "alpha": 0.0,
            "l1_ratio": 0.0,
            "fit_intercept": True,
            "normalize": True,
            "max_iter": 100,
            "tol": 1e-6,
            "loss": "squared_loss",
            "solver": "eig",
            "epsilon": 1.35,
        }

    @classmethod
    def _fallback_class(cls):
        from sklearn.linear_model import LinearRegression as SkLR

        return SkLR


class _LinearRegressionParams(
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasSolver,
    HasWeightCol,
):
    loss: Param[str] = Param(
        "undefined",
        "loss",
        "The loss function to be optimized. Supported options: squaredError, huber.",
        TypeConverters.toString,
    )
    epsilon: Param[float] = Param(
        "undefined",
        "epsilon",
        "The shape parameter to control the amount of robustness (huber only).",
        TypeConverters.toFloat,
    )
    maxBlockSizeInMB: Param[float] = Param(
        "undefined",
        "maxBlockSizeInMB",
        "Maximum memory in MB for stacking input data into blocks.",
        TypeConverters.toFloat,
    )
    aggregationDepth: Param[int] = Param(
        "undefined",
        "aggregationDepth",
        "suggested depth for treeAggregate (>= 2).",
        TypeConverters.toInt,
    )

    def setFeaturesCol(self, value: str):
        return self._set(featuresCol=value)

    def setLabelCol(self, value: str):
        return self._set(labelCol=value)

    def setPredictionCol(self, value: str):
        return self._set(predictionCol=value)


class LinearRegression(_LinearRegressionClass, _TpuEstimatorSupervised, _LinearRegressionParams):
    """LinearRegression (OLS/Ridge/Lasso/ElasticNet/huber) on the TPU mesh.

    Squared loss: one sharded pass accumulates (XᵀWX, XᵀWy) with the psum over ICI;
    the d×d solve is replicated. Huber loss: native concomitant-scale L-BFGS
    (ops/linear.huber_fit — the reference has no device huber at all). Drop-in for
    pyspark.ml.regression.LinearRegression / reference
    spark_rapids_ml.regression.LinearRegression (reference regression.py:312-660).
    """

    # Spark ParamValidators.gt(1.0) for the huber shape parameter
    _PARAM_BOUNDS_EXTRA = {"epsilon": (1.0 + 1e-12, None)}

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            featuresCol="features",
            labelCol="label",
            predictionCol="prediction",
            regParam=0.0,
            elasticNetParam=0.0,
            fitIntercept=True,
            standardization=True,
            maxIter=100,
            tol=1e-6,
            loss="squaredError",
            epsilon=1.35,
            solver="auto",
            aggregationDepth=2,
            maxBlockSizeInMB=0.0,
        )
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def setRegParam(self, value: float) -> "LinearRegression":
        return self._set_params(regParam=value)  # type: ignore[return-value]

    def setElasticNetParam(self, value: float) -> "LinearRegression":
        return self._set_params(elasticNetParam=value)  # type: ignore[return-value]

    def _out_schema(self) -> List[str]:
        # scale present on huber (fallback) fits only; model defaults it to 1.0
        return ["coefficients", "intercept", "n_iter", "scale"]

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # the sufficient-statistics pass is shared across all param maps
        return True

    def _supports_sparse_fit(self) -> bool:
        # matrix-free ELL normal-equation solver in ops/sparse.py
        return True

    def _upload_fold(self, n_cols, unit_weight, dtype, row_weight, label):
        if self._tpu_params.get("loss", "squared_loss") == "huber":
            return None  # no sufficient statistics: the loss reads X every iteration
        from ..ops.linear import normal_equations_fold

        return normal_equations_fold(n_cols, unit_weight, dtype, row_weight, label)

    def _get_tpu_fit_func(self, extra_params: Optional[List[Dict[str, Any]]] = None):
        p = dict(self._tpu_params)

        def _fit(inputs: FitInputs):
            # dispatch PER PARAM SET on its own loss — a fitMultiple map may flip
            # between squared and huber (each extra set is a full backend dict)
            sets = extra_params if extra_params is not None else [p]
            results: List[Optional[Dict[str, Any]]] = [None] * len(sets)
            hb = [
                i for i, s in enumerate(sets)
                if s.get("loss", "squared_loss") == "huber"
            ]
            sq = [i for i in range(len(sets)) if i not in set(hb)]

            if hb:
                from ..ops.linear import huber_fit

                if inputs.sparse_values is not None:
                    raise ValueError(
                        "loss='huber' requires dense features "
                        "(disable enable_sparse_data_optim)."
                    )
                for i in hb:
                    if float(sets[i].get("l1_ratio", 0.0)) != 0.0:
                        # Spark: huber supports only L2 regularization
                        raise ValueError(
                            "loss='huber' supports only L2 regularization "
                            "(elasticNetParam must be 0.0)."
                        )
                hres = huber_fit(
                    inputs.features, inputs.label, inputs.row_weight,
                    epsilon=float(p.get("epsilon", 1.35)),
                    reg=float(p["alpha"]),
                    fit_intercept=bool(p["fit_intercept"]),
                    standardize=bool(p["normalize"]),
                    max_iter=int(p["max_iter"]),
                    tol=float(p["tol"]),
                    extra_param_sets=[sets[i] for i in hb],
                )
                for j, i in enumerate(hb):
                    results[i] = hres[j]

            if sq:
                common = dict(
                    reg=float(p["alpha"]),
                    l1_ratio=float(p["l1_ratio"]),
                    fit_intercept=bool(p["fit_intercept"]),
                    standardize=bool(p["normalize"]),
                    max_iter=int(p["max_iter"]),
                    tol=float(p["tol"]),
                    extra_param_sets=[sets[i] for i in sq],
                )
                if inputs.sparse_values is not None:
                    from ..ops.sparse import sparse_linreg_fit

                    sqres = sparse_linreg_fit(
                        inputs.sparse_values,
                        inputs.sparse_indices,
                        inputs.desc.n,
                        inputs.label,
                        inputs.row_weight,
                        **common,
                    )
                else:
                    sqres = linreg_fit(
                        inputs.features, inputs.label, inputs.row_weight,
                        mesh=inputs.mesh, unit_weight=inputs.unit_weight,
                        folded=inputs.folded, **common
                    )
                for j, i in enumerate(sq):
                    results[i] = sqres[j]
            return results if extra_params is not None else results[0]

        return _fit

    def _create_pyspark_model(self, attrs: Dict[str, Any]) -> "LinearRegressionModel":
        return LinearRegressionModel(**attrs)

    def _streaming_fit(self, fd, chain_ops=None) -> Dict[str, Any]:
        """Out-of-core fit: stream batches, accumulate (XᵀWX, XᵀWy) on device
        (ops/streaming.py), centred before the one solve stage the in-core
        pass feeds too.
        `chain_ops` carries upstream featurizer transforms when this fit is the
        terminal stage of a fused pipeline chain (pipeline.py)."""
        from .. import config as _config
        from ..core.dataset import densify as _densify
        from ..ops.linear import solve_from_stats
        from ..ops.streaming import streaming_linreg_stats
        from ..parallel.partitioner import active_partitioner

        p = self._tpu_params
        if p.get("loss", "squared_loss") == "huber":
            if chain_ops:
                # the fuser gates on fuse-eligibility, so only a direct caller
                # can land here; in-core would silently drop the chain
                raise ValueError(
                    "loss='huber' fits in-core and cannot run a fused "
                    "featurize->fit chain."
                )
            # huber has no sufficient-statistics form; fit in-core (the robust loss
            # needs the residuals every iteration)
            self.logger.warning(
                "loss='huber' has no streamed sufficient-statistics form; "
                "fitting in-core despite stream_threshold_bytes."
            )
            inputs = self._build_fit_inputs(fd)
            return self._get_tpu_fit_func(None)(inputs)
        mesh = active_partitioner(self.num_workers).mesh
        S, r, xbar, ybar, sw = streaming_linreg_stats(
            _densify(fd.features, self._float32_inputs),
            fd.label,
            fd.weight,
            batch_rows=int(_config.get("stream_batch_rows")),
            mesh=mesh,
            float32=self._float32_inputs,
            chain_ops=chain_ops,
        )
        attrs = solve_from_stats(
            S, r, xbar, ybar, sw,
            reg=float(p["alpha"]),
            l1_ratio=float(p["l1_ratio"]),
            fit_intercept=bool(p["fit_intercept"]),
            standardize=bool(p["normalize"]),
            max_iter=int(p["max_iter"]),
            tol=float(p["tol"]),
        )[0]
        # live telemetry (§6g): one convergence record for the streamed linreg —
        # the unpenalized normal-equation residual ‖(Sβ − r)/Σw + x̄(x̄·β + c − ȳ)‖
        # is the squared-loss gradient norm at the solution (≈0 for an exact l2
        # solve, the leftover prox residual for elastic net)
        from ..observability import convergence as obs_convergence

        coef = np.asarray(attrs["coefficients"], np.float64)
        xbar64 = np.asarray(xbar, np.float64)
        grad = (np.asarray(S, np.float64) @ coef - np.asarray(r, np.float64)) / float(sw) + (
            xbar64 * (xbar64 @ coef + float(attrs["intercept"]) - float(ybar)))
        obs_convergence(
            "linreg", attrs.get("n_iter", 1),
            grad_norm=float(np.linalg.norm(grad)),
        )
        return attrs

    def _fit_fallback_model(self, twin: type, fd) -> Dict[str, Any]:
        X = densify(fd.features, float32=self._float32_inputs)
        X64 = np.asarray(X, dtype=np.float64)
        fit_intercept = self.getOrDefault("fitIntercept")
        if self.getOrDefault("loss") == "huber":
            from sklearn.linear_model import HuberRegressor

            # sklearn's objective SUMS the data term; the native path (and Spark)
            # use the mean + lambda/2 penalty — rescale alpha for equivalence
            n_eff = float(np.sum(fd.weight)) if fd.weight is not None else float(
                fd.n_rows
            )
            sk = HuberRegressor(
                epsilon=max(self.getOrDefault("epsilon"), 1.0),
                alpha=0.5 * self.getOrDefault("regParam") * n_eff,
                fit_intercept=fit_intercept,
            ).fit(X64, fd.label, sample_weight=fd.weight)
            return {
                "coefficients": sk.coef_.astype(np.float32),
                "intercept": float(sk.intercept_),
                "n_iter": int(getattr(sk, "n_iter_", 1) or 1),
                # huber sigma — Spark's LinearRegressionModel.scale
                "scale": float(sk.scale_),
            }
        else:
            reg = self.getOrDefault("regParam")
            l1r = self.getOrDefault("elasticNetParam")
            n = fd.n_rows
            if reg == 0.0:
                sk = twin(fit_intercept=fit_intercept)
            elif l1r == 0.0:
                from sklearn.linear_model import Ridge

                sk = Ridge(alpha=reg * n, fit_intercept=fit_intercept)
            else:
                from sklearn.linear_model import ElasticNet

                sk = ElasticNet(
                    alpha=reg, l1_ratio=l1r, fit_intercept=fit_intercept,
                    max_iter=max(self.getOrDefault("maxIter"), 1000),
                )
            sk = sk.fit(X64, fd.label, sample_weight=fd.weight)
        return {
            "coefficients": sk.coef_.astype(np.float32),
            "intercept": float(sk.intercept_),
            "n_iter": int(getattr(sk, "n_iter_", 1) or 1),
        }


class LinearRegressionModel(
    _LinearRegressionClass, _TpuModelWithPredictionCol, _LinearRegressionParams
):
    """Fitted linear regression model (reference regression.py:700-863)."""

    def __init__(
        self,
        coefficients: np.ndarray,
        intercept: float,
        n_iter: int,
        scale: float = 1.0,
    ) -> None:
        super().__init__(
            coefficients=np.asarray(coefficients),
            intercept=float(intercept),
            n_iter=int(n_iter),
            scale=float(scale),
        )
        self._setDefault(featuresCol="features", labelCol="label", predictionCol="prediction")

    @property
    def coefficients(self) -> np.ndarray:
        return self._model_attributes["coefficients"]

    @property
    def intercept(self) -> float:
        return self._model_attributes["intercept"]

    @property
    def numFeatures(self) -> int:
        return int(self._model_attributes["coefficients"].shape[0])

    def partial_fit_updater(self, **kwargs):
        """Streamed continual-learning updater anchored on this model: exact
        re-solves from decayed normal-equation statistics (continual/
        partial_fit.py, docs/design.md §7d)."""
        from ..continual.partial_fit import LinearRegressionUpdater

        return LinearRegressionUpdater(self, **kwargs)

    @property
    def scale(self) -> float:
        """Huber scale sigma for huber fits; 1.0 for squared-error fits. (The
        reference hardcodes 1.0 because cuML has no huber, regression.py:760-763;
        here the huber path fits sklearn's HuberRegressor and its sigma is part of
        the model state.)"""
        return float(self._model_attributes.get("scale", 1.0))

    @property
    def hasSummary(self) -> bool:
        """No training summary is produced (reference regression.py:745-750)."""
        return False

    @property
    def summary(self):
        """Spark raises when hasSummary is False; match it."""
        raise RuntimeError(
            f"No training summary available for this {self.__class__.__name__}"
        )

    def evaluate(self, dataset: Any) -> "LinearRegressionSummary":
        """Evaluate on a labeled dataset, returning the Spark summary surface —
        computed natively (the reference exposes no evaluate/summary for
        regression at all)."""
        from ..core.estimator import extract_eval_columns

        out, label, pred, weight = extract_eval_columns(self, dataset)
        return LinearRegressionSummary(
            out, label, pred, weight,
            num_features=self.numFeatures,
            fit_intercept=bool(self.getOrDefault("fitIntercept")),
        )

    def cpu(self):
        """sklearn LinearRegression twin with the fitted state installed."""
        from sklearn.linear_model import LinearRegression as SkLinReg

        sk = SkLinReg()
        sk.coef_ = np.asarray(self._model_attributes["coefficients"], np.float64)
        sk.intercept_ = float(self._model_attributes["intercept"])
        sk.n_features_in_ = sk.coef_.shape[0]
        return sk

    def predict(self, value: np.ndarray) -> float:
        from ..observability.inference import predict_dispatch

        X = np.asarray(value, dtype=np.float32).reshape(1, -1)
        return float(
            np.asarray(
                predict_dispatch(
                    self, linreg_predict, X, self.coefficients, self.intercept
                )
            )[0]
        )

    def _combine(self, models: List["LinearRegressionModel"]) -> "LinearRegressionModel":
        """Stack models fitted by fitMultiple for CV transform-evaluate
        (reference regression.py:828-846)."""
        first = models[0]
        first._combined_models = models
        return first

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        from ..observability.inference import predict_dispatch

        pred = np.asarray(
            predict_dispatch(
                self, linreg_predict, X, self.coefficients,
                np.float32(self.intercept),
            )
        )
        return {self.getOrDefault("predictionCol"): pred}

    def _supports_sparse_transform(self) -> bool:
        return True

    def _transform_sparse(self, csr) -> Dict[str, np.ndarray]:
        """Predict on CSR queries without densifying (ELL gather matvec)."""
        import jax.numpy as jnp

        from ..observability.inference import predict_dispatch
        from ..ops.sparse import csr_to_ell, ell_matvec

        values, indices = csr_to_ell(csr, float32=True)
        pred = (
            np.asarray(
                predict_dispatch(
                    self,
                    ell_matvec,
                    jnp.asarray(values),
                    jnp.asarray(indices),
                    jnp.asarray(np.asarray(self.coefficients, np.float32)),
                )
            )
            + self.intercept
        )
        return {self.getOrDefault("predictionCol"): pred}


class LinearRegressionSummary:
    """Evaluation summary over a predictions frame — the surface of
    pyspark.ml.regression.LinearRegressionSummary, computed natively on the
    metrics/ reduction classes (the reference exposes no summary at all)."""

    def __init__(
        self,
        predictions,
        label: np.ndarray,
        pred: np.ndarray,
        weight: "np.ndarray | None" = None,
        num_features: int = 0,
        fit_intercept: bool = True,
    ) -> None:
        from ..metrics.RegressionMetrics import RegressionMetrics

        self.predictions = predictions
        self._m = RegressionMetrics.from_predictions(label, pred, weight)
        self._n = len(np.asarray(label))
        self._dof = max(self._n - num_features - (1 if fit_intercept else 0), 0)

    @property
    def rootMeanSquaredError(self) -> float:
        return self._m.root_mean_squared_error

    @property
    def meanSquaredError(self) -> float:
        return self._m.mean_squared_error

    @property
    def meanAbsoluteError(self) -> float:
        return self._m.mean_absolute_error

    @property
    def r2(self) -> float:
        return self._m.r2

    @property
    def explainedVariance(self) -> float:
        return self._m.explained_variance

    @property
    def numInstances(self) -> int:
        return self._n

    @property
    def degreesOfFreedom(self) -> int:
        return self._dof
