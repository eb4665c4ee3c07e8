#
# PCA estimator/model (L6 API) — pyspark.ml.feature.PCA-compatible surface with the
# fit/transform executing on the TPU mesh.
#
# Structural equivalent of reference python/src/spark_rapids_ml/feature.py:
#   * param mapping {k -> n_components} (reference feature.py:61-65)
#   * fit produces mean/components/explained_variance/singular_values attributes
#     (reference feature.py:260-285)
#   * transform projects raw rows for Spark parity (reference feature.py:438-451)
#

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..core.dataset import densify
from ..core.backend_params import _TpuClass
from ..core.estimator import FitInputs, _TpuEstimator, _TpuModelWithColumns
from ..core.params import (
    HasInputCol,
    HasInputCols,
    HasOutputCol,
    Param,
    TypeConverters,
)
from ..ops.pca import pca_transform


class _PCAClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        return {"k": "n_components", "inputCol": "", "inputCols": "", "outputCol": ""}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {"n_components": None, "whiten": False}

    @classmethod
    def _fallback_class(cls):
        from sklearn.decomposition import PCA as SkPCA

        return SkPCA


class _PCAParams(HasInputCol, HasInputCols, HasOutputCol):
    k: Param[int] = Param(
        "undefined",
        "k",
        "the number of principal components (> 0).",
        TypeConverters.toInt,
    )

    def getK(self) -> int:
        return self.getOrDefault("k")

    def setInputCol(self, value: str) -> "_PCAParams":
        return self._set(inputCol=value)  # type: ignore[return-value]

    def setInputCols(self, value: List[str]) -> "_PCAParams":
        return self._set(inputCols=value)  # type: ignore[return-value]

    def setOutputCol(self, value: str) -> "_PCAParams":
        return self._set(outputCol=value)  # type: ignore[return-value]


class PCA(_PCAClass, _TpuEstimator, _PCAParams):
    """PCA estimator running as one SPMD program over the TPU mesh.

    Drop-in for pyspark.ml.feature.PCA / reference spark_rapids_ml.feature.PCA
    (reference feature.py:117-253).

    Example
    -------
    >>> import pandas as pd, numpy as np
    >>> from spark_rapids_ml_tpu.feature import PCA
    >>> df = pd.DataFrame({"features": list(np.random.rand(100, 8).astype(np.float32))})
    >>> model = PCA(k=2, inputCol="features").fit(df)
    >>> out = model.transform(df)   # adds 'pca_features' column
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(outputCol="pca_features")
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def setK(self, value: int) -> "PCA":
        return self._set_params(k=value)  # type: ignore[return-value]

    def _out_schema(self) -> List[str]:
        return [
            "mean",
            "components",
            "explained_variance",
            "explained_variance_ratio",
            "singular_values",
        ]

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        # the sharded covariance pass is shared; each param map re-does only the
        # tiny replicated eigh (P6 pattern)
        return True

    def _upload_fold(self, n_cols, unit_weight, dtype, row_weight, label):
        from ..ops.pca import covariance_fold

        return covariance_fold(n_cols, unit_weight, dtype, row_weight)

    def _get_tpu_fit_func(self, extra_params: Optional[List[Dict[str, Any]]] = None):
        base_k = self.getOrDefault("k")

        def _fit(inputs: FitInputs):
            from ..ops.pca import covariance_for_fit, pca_attrs_from_cov

            ks = (
                [int(p.get("n_components", base_k)) for p in extra_params]
                if extra_params is not None
                else [base_k]
            )
            for k in ks:
                if k > inputs.desc.n:
                    raise ValueError(
                        f"k={k} exceeds the number of features {inputs.desc.n}"
                    )
            import jax

            from ..observability import span

            with span("pca.cov", {"waits": "device"}):
                # waited for here, so that the span reads the covariance pass
                # as the host sees it and `pca.eig` the eigensolve and fetch
                cov, mean, wsum = jax.block_until_ready(
                    covariance_for_fit(
                        inputs.features,
                        inputs.row_weight,
                        mesh=inputs.mesh,
                        unit_weight=inputs.unit_weight,
                        folded=inputs.folded,
                    )
                )
            with span("pca.eig"):
                results = [pca_attrs_from_cov(cov, mean, wsum, k) for k in ks]
            return results if extra_params is not None else results[0]

        return _fit

    def _create_pyspark_model(self, attrs: Dict[str, Any]) -> "PCAModel":
        return PCAModel(**attrs)

    def _streaming_fit(self, fd, chain_ops=None) -> Dict[str, Any]:
        """Out-of-core fit: stream batches, accumulate the covariance on device
        (ops/streaming.py; selected by core/estimator.py when the design matrix
        exceeds the stream threshold). `chain_ops` carries upstream featurizer
        transforms when this fit runs as a fused pipeline stage (pipeline.py):
        they apply in-program, so the raw batches upload once for the chain."""
        from .. import config as _config
        from ..ops.pca import pca_attrs_from_cov
        from ..ops.streaming import chain_out_dim, streaming_covariance
        from ..parallel.partitioner import active_partitioner

        k = self.getOrDefault("k")
        d_eff = chain_out_dim(fd.n_cols, chain_ops)
        if k > d_eff:
            raise ValueError(f"k={k} exceeds the number of features {d_eff}")
        mesh = active_partitioner(self.num_workers).mesh
        cov, mean, wsum = streaming_covariance(
            densify(fd.features, self._float32_inputs),
            fd.weight,
            batch_rows=int(_config.get("stream_batch_rows")),
            mesh=mesh,
            float32=self._float32_inputs,
            chain_ops=chain_ops,
        )
        return pca_attrs_from_cov(cov, mean, wsum, k)

    def _fit_fallback_model(self, twin: type, fd) -> Dict[str, Any]:
        X = densify(fd.features, float32=self._float32_inputs)
        sk = twin(n_components=self.getOrDefault("k")).fit(np.asarray(X, dtype=np.float64))
        return {
            "mean": sk.mean_.astype(np.float32),
            "components": sk.components_.astype(np.float32),
            "explained_variance": sk.explained_variance_,
            "explained_variance_ratio": sk.explained_variance_ratio_,
            "singular_values": sk.singular_values_,
        }


class PCAModel(_PCAClass, _TpuModelWithColumns, _PCAParams):
    """Fitted PCA model (reference feature.py:288-459)."""

    def __init__(
        self,
        mean: np.ndarray,
        components: np.ndarray,
        explained_variance: np.ndarray,
        explained_variance_ratio: np.ndarray,
        singular_values: np.ndarray,
    ) -> None:
        super().__init__(
            mean=np.asarray(mean),
            components=np.asarray(components),
            explained_variance=np.asarray(explained_variance),
            explained_variance_ratio=np.asarray(explained_variance_ratio),
            singular_values=np.asarray(singular_values),
        )
        self._setDefault(outputCol="pca_features")

    # --- Spark MLlib PCAModel surface ---

    @property
    def pc(self) -> np.ndarray:
        """Principal components as a (d, k) matrix, Spark's PCAModel.pc layout."""
        return self._model_attributes["components"].T

    def partial_fit_updater(self, **kwargs):
        """Streamed continual-learning updater anchored on this model:
        incremental PCA via the streamed covariance accumulators (continual/
        partial_fit.py, docs/design.md §7d)."""
        from ..continual.partial_fit import PCAUpdater

        return PCAUpdater(self, **kwargs)

    @property
    def explainedVariance(self) -> np.ndarray:
        """Proportion of variance explained per component (Spark semantics)."""
        return self._model_attributes["explained_variance_ratio"]

    # --- cuML-style surface (reference exposes these too) ---

    @property
    def mean(self) -> np.ndarray:
        return self._model_attributes["mean"]

    @property
    def components_(self) -> np.ndarray:
        return self._model_attributes["components"]

    @property
    def explained_variance_(self) -> np.ndarray:
        return self._model_attributes["explained_variance"]

    @property
    def singular_values_(self) -> np.ndarray:
        return self._model_attributes["singular_values"]

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        from ..observability.inference import predict_to_host

        out = predict_to_host(
            self, pca_transform, X, self._model_attributes["components"]
        )
        return {self.getOrDefault("outputCol"): out}

    def _chain_op(self):
        """This transform as a fused-pipeline chain op (pipeline.py): `project`
        applies exactly pca_transform's expression in-program
        (ops/streaming.py::_apply_chain), so a fused downstream fit sees
        bit-identical inputs to the staged transform path."""
        return ("project", self._model_attributes["components"])

    def cpu(self):
        """sklearn PCA twin with the fitted state installed (the reference builds
        the pyspark PCAModel via py4j, feature.py:375-389)."""
        from sklearn.decomposition import PCA as SkPCA

        comps = np.asarray(self._model_attributes["components"], np.float64)
        k, d = comps.shape
        sk = SkPCA(n_components=k)
        sk.components_ = comps
        sk.mean_ = np.asarray(self._model_attributes["mean"], np.float64)
        sk.explained_variance_ = np.asarray(
            self._model_attributes["explained_variance"], np.float64
        )
        sk.explained_variance_ratio_ = np.asarray(
            self._model_attributes["explained_variance_ratio"], np.float64
        )
        sk.singular_values_ = np.asarray(
            self._model_attributes["singular_values"], np.float64
        )
        sk.n_components_ = k
        sk.n_features_in_ = d
        sk.noise_variance_ = 0.0
        sk.whiten = False
        return sk


class _StandardScalerClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        return {
            "withMean": "with_mean",
            "withStd": "with_std",
            "inputCol": "",
            "inputCols": "",
            "outputCol": "",
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {"with_mean": False, "with_std": True}

    @classmethod
    def _fallback_class(cls):
        from sklearn.preprocessing import StandardScaler as SkStandardScaler

        return SkStandardScaler


class _StandardScalerParams(HasInputCol, HasInputCols, HasOutputCol):
    withMean: Param[bool] = Param(
        "undefined",
        "withMean",
        "center the data with the column means before scaling.",
        TypeConverters.toBoolean,
    )
    withStd: Param[bool] = Param(
        "undefined",
        "withStd",
        "scale the data to unit standard deviation.",
        TypeConverters.toBoolean,
    )

    def getWithMean(self) -> bool:
        return self.getOrDefault("withMean")

    def getWithStd(self) -> bool:
        return self.getOrDefault("withStd")

    def setInputCol(self, value: str) -> "_StandardScalerParams":
        return self._set(inputCol=value)  # type: ignore[return-value]

    def setInputCols(self, value: List[str]) -> "_StandardScalerParams":
        return self._set(inputCols=value)  # type: ignore[return-value]

    def setOutputCol(self, value: str) -> "_StandardScalerParams":
        return self._set(outputCol=value)  # type: ignore[return-value]


def _std_from_var(var: np.ndarray) -> np.ndarray:
    """Column std from the unbiased variance, zero-variance columns clamped to
    scale 1 (Spark's StandardScalerModel convention; also
    ops/linalg.py::standardize_columns). ONE host implementation shared by the
    in-core, streamed, and fallback fit arms so every arm lands the same bits."""
    std = np.sqrt(np.asarray(var))
    std[std <= 0.0] = 1.0
    return std


class StandardScaler(_StandardScalerClass, _TpuEstimator, _StandardScalerParams):
    """pyspark.ml.feature.StandardScaler surface with the column-moments fit
    running on the mesh (ops/linalg.py::weighted_moments in-core,
    ops/streaming.py::streaming_moments out-of-core).

    Spark defaults hold: withMean=False, withStd=True. In a Pipeline feeding a
    TPU estimator this stage is fuse-eligible (docs/design.md §6k): its
    transform becomes a "scale" chain op applied in-program by the downstream
    fit, bit-identical to the staged transform.

    Example
    -------
    >>> import pandas as pd, numpy as np
    >>> from spark_rapids_ml_tpu.feature import StandardScaler
    >>> df = pd.DataFrame({"features": list(np.random.rand(100, 8).astype(np.float32))})
    >>> model = StandardScaler(inputCol="features", withMean=True).fit(df)
    >>> out = model.transform(df)   # adds 'scaled_features' column
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(outputCol="scaled_features", withMean=False, withStd=True)
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def setWithMean(self, value: bool) -> "StandardScaler":
        return self._set_params(withMean=value)  # type: ignore[return-value]

    def setWithStd(self, value: bool) -> "StandardScaler":
        return self._set_params(withStd=value)  # type: ignore[return-value]

    def _out_schema(self) -> List[str]:
        return ["mean", "std"]

    def _get_tpu_fit_func(self, extra_params: Optional[List[Dict[str, Any]]] = None):
        def _fit(inputs: FitInputs):
            from ..ops.linalg import weighted_moments

            mean, var, _ = weighted_moments(inputs.features, inputs.row_weight)
            return {
                "mean": np.asarray(mean),
                "std": _std_from_var(var).astype(inputs.dtype),
            }

        return _fit

    def _create_pyspark_model(self, attrs: Dict[str, Any]) -> "StandardScalerModel":
        return StandardScalerModel(**attrs)

    def _streaming_fit(self, fd, chain_ops=None) -> Dict[str, Any]:
        """Out-of-core fit: one streamed moments pass (ops/streaming.py). The
        shared `streaming_moments` implementation is what the fused pipeline's
        in-chain scaler fit calls too, so both arms produce identical stats."""
        from .. import config as _config
        from ..ops.streaming import streaming_moments
        from ..parallel.partitioner import active_partitioner

        dt = np.float32 if self._float32_inputs else np.float64
        mean, var, _ = streaming_moments(
            densify(fd.features, self._float32_inputs),
            fd.weight,
            batch_rows=int(_config.get("stream_batch_rows")),
            mesh=active_partitioner(self.num_workers).mesh,
            float32=self._float32_inputs,
            chain_ops=chain_ops,
        )
        return {
            "mean": np.asarray(mean, dtype=dt),
            "std": _std_from_var(var).astype(dt),
        }

    def _fit_fallback_model(self, twin: type, fd) -> Dict[str, Any]:
        dt = np.float32 if self._float32_inputs else np.float64
        X = np.asarray(densify(fd.features, self._float32_inputs), np.float64)
        w = (
            np.asarray(fd.weight, np.float64)
            if fd.weight is not None
            else np.ones((X.shape[0],), np.float64)
        )
        wsum = w.sum()
        mean = (w[:, None] * X).sum(axis=0) / wsum
        var = np.maximum(
            ((w[:, None] * (X * X)).sum(axis=0) - wsum * mean * mean)
            / (wsum - 1.0),
            0.0,
        )
        return {"mean": mean.astype(dt), "std": _std_from_var(var).astype(dt)}


class StandardScalerModel(_StandardScalerClass, _TpuModelWithColumns, _StandardScalerParams):
    """Fitted StandardScaler (pyspark.ml.feature.StandardScalerModel surface:
    exposes both `mean` and `std` regardless of the withMean/withStd flags)."""

    def __init__(self, mean: np.ndarray, std: np.ndarray) -> None:
        super().__init__(mean=np.asarray(mean), std=np.asarray(std))
        self._setDefault(outputCol="scaled_features", withMean=False, withStd=True)

    @property
    def mean(self) -> np.ndarray:
        return self._model_attributes["mean"]

    @property
    def std(self) -> np.ndarray:
        return self._model_attributes["std"]

    def _shift_and_scale(self):
        """The (shift, scale) pair the transform ACTUALLY applies, honoring the
        withMean/withStd flags: `(x - shift) / scale`. The identity halves are
        literal zeros/ones so the flagged-off variants stay bit-identical to
        the raw input — and so `_chain_op` hands the fuser the exact arrays the
        staged transform uses."""
        mean = self._model_attributes["mean"]
        std = self._model_attributes["std"]
        shift = mean if self.getOrDefault("withMean") else np.zeros_like(mean)
        scale = std if self.getOrDefault("withStd") else np.ones_like(std)
        return shift, scale

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        from ..observability.inference import predict_dispatch
        from ..ops.linalg import scaler_transform

        shift, scale = self._shift_and_scale()
        out = np.asarray(predict_dispatch(self, scaler_transform, X, shift, scale))
        return {self.getOrDefault("outputCol"): out}

    def _chain_op(self):
        """This transform as a fused-pipeline chain op (pipeline.py): `scale`
        applies `(x - shift) / scale` in-program
        (ops/streaming.py::_apply_chain), bit-identical to scaler_transform."""
        shift, scale = self._shift_and_scale()
        return ("scale", shift, scale)

    def cpu(self):
        """sklearn StandardScaler twin with the fitted state installed."""
        from sklearn.preprocessing import StandardScaler as SkStandardScaler

        with_mean = bool(self.getOrDefault("withMean"))
        with_std = bool(self.getOrDefault("withStd"))
        sk = SkStandardScaler(with_mean=with_mean, with_std=with_std)
        mean = np.asarray(self._model_attributes["mean"], np.float64)
        std = np.asarray(self._model_attributes["std"], np.float64)
        sk.mean_ = mean if with_mean else None
        sk.scale_ = std if with_std else None
        sk.var_ = std * std if with_std else None
        sk.n_features_in_ = int(mean.shape[0])
        sk.n_samples_seen_ = 0
        return sk


class VectorAssembler(HasInputCols, HasOutputCol):
    """Combines scalar columns into one array-valued feature column —
    pyspark.ml.feature.VectorAssembler surface, provided so Pipelines written against
    pyspark port over. TPU pipelines usually skip it: Pipeline bypasses a
    VectorAssembler feeding a TPU estimator (reference pipeline.py:85-119)."""

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(outputCol="features")
        self._set(**kwargs)

    def setInputCols(self, value: List[str]) -> "VectorAssembler":
        return self._set(inputCols=value)  # type: ignore[return-value]

    def setOutputCol(self, value: str) -> "VectorAssembler":
        return self._set(outputCol=value)  # type: ignore[return-value]

    def transform(self, dataset: Any, params: Optional[dict] = None) -> Any:
        import pandas as pd

        if params:
            return self.copy(params).transform(dataset)
        if not isinstance(dataset, pd.DataFrame):
            raise TypeError("VectorAssembler requires a pandas DataFrame input")
        cols = self.getOrDefault("inputCols")
        out = dataset.copy()
        # pyspark assembles DoubleType vectors and flattens vector-valued inputs;
        # match both (estimators downcast per their float32_inputs setting)
        blocks = []
        for c in cols:
            col = dataset[c]
            if col.dtype == object:
                blocks.append(np.stack([np.asarray(v, dtype=np.float64) for v in col]))
            else:
                blocks.append(col.to_numpy(dtype=np.float64).reshape(-1, 1))
        stacked = np.hstack(blocks)
        out[self.getOrDefault("outputCol")] = list(stacked)
        return out
