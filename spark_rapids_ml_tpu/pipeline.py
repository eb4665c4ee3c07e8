#
# Pipeline — pyspark.ml.Pipeline-compatible surface with the reference's acceleration
# trick (reference python/src/spark_rapids_ml/pipeline.py:85-159): a
# VectorAssembler -> TPU-estimator pair is bypassed, feeding the scalar columns
# directly to the estimator via featuresCols and replacing the assembler with a
# NoOpTransformer — the vector column is never materialized.
#
# Whole-pipeline fusion (docs/design.md §6k): a featurize->fit suffix chain
# (StandardScaler / PCA feeding KMeans / LinearRegression / LogisticRegression /
# PCA) whose fits would stream out-of-core runs as ONE compiled program per
# batch — the featurizer transforms become in-program chain ops
# (ops/streaming.py::_apply_chain) applied by the downstream accumulator
# kernels, so intermediate feature matrices never round-trip to the host and
# raw input batches upload exactly once per pass (replayed from the HBM batch
# cache across passes AND across chain stages). Bit-parity with the staged
# transform->refit path is the contract, verified in
# tests/test_ingest_fusion.py.
#

from __future__ import annotations

import inspect
from typing import Any, Dict, List, Optional, Tuple

from .core.backend_params import _TpuParams
from .core.params import ParamMap, Params
from .utils import get_logger


class NoOpTransformer(Params):
    """Stage that passes data through unchanged (reference pipeline.py:37-49)."""

    def transform(self, dataset: Any, params: Optional[ParamMap] = None) -> Any:
        return dataset


class Transformer(Params):
    """Marker base for pure transformers (pyspark.ml.Transformer surface)."""

    def transform(self, dataset: Any, params: Optional[ParamMap] = None) -> Any:
        raise NotImplementedError


def _isTpuEstimator(stage: Any) -> bool:
    """reference pipeline.py:146-159 `_isGPUEstimator`."""
    return isinstance(stage, _TpuParams) and hasattr(stage, "_get_tpu_fit_func")


def _isVectorAssembler(stage: Any) -> bool:
    return type(stage).__name__ == "VectorAssembler" and stage.hasParam("inputCols")


def _resolve_fuse_min_rows() -> int:
    """`pipeline.fuse_min_rows` resolution: a non-zero config pin wins, then
    the defaults-module geometry (defaults.py::PIPELINE_FUSE_MIN_ROWS)."""
    from . import config as _config
    from .defaults import PIPELINE_FUSE_MIN_ROWS

    pinned = int(_config.get("pipeline.fuse_min_rows") or 0)
    if pinned > 0:
        return pinned
    return int(PIPELINE_FUSE_MIN_ROWS)


def _chain_streaming_capable(stage: Any) -> bool:
    """Whether the stage's streamed fit can apply an upstream chain in-program."""
    fit = getattr(stage, "_streaming_fit", None)
    if fit is None:
        return False
    try:
        return "chain_ops" in inspect.signature(fit).parameters
    except (TypeError, ValueError):
        return False


def _terminal_fuse_eligible(stage: Any) -> bool:
    """Static (param-level) fuse-eligibility of a chain's terminal estimator.
    These mirror the conditions under which the estimator's own streamed fit
    would route in-core or run a non-fusable variant — the fuser must know
    BEFORE fitting, so the staged path can carry those configurations
    (docs/design.md §6k eligibility table)."""
    if not (_isTpuEstimator(stage) and _chain_streaming_capable(stage)):
        return False
    if stage._use_cpu_fallback():
        return False
    # cosine KMeans normalizes rows host-side per batch — not expressible as a
    # post-chain in-program op today
    if (
        stage.hasParam("distanceMeasure")
        and stage.getOrDefault("distanceMeasure") != "euclidean"
    ):
        return False
    # huber has no sufficient-statistics form; its fit is in-core
    if stage.hasParam("loss") and stage.getOrDefault("loss") == "huber":
        return False
    # box-constrained logistic fits route in-core
    for name in (
        "lowerBoundsOnCoefficients", "upperBoundsOnCoefficients",
        "lowerBoundsOnIntercepts", "upperBoundsOnIntercepts",
    ):
        if (
            stage.hasParam(name)
            and stage.isDefined(name)
            and stage.getOrDefault(name) is not None
        ):
            return False
    return True


def _featurizer_fuse_eligible(stage: Any) -> bool:
    """Whether a stage can contribute a chain op: a TPU featurizer estimator
    whose fitted model exposes `_chain_op` (StandardScaler, PCA — marked via
    the `_chain_featurizer` class attribute is unnecessary; the model contract
    is checked after fit, the estimator contract here)."""
    return (
        _isTpuEstimator(stage)
        and _chain_streaming_capable(stage)
        and not stage._use_cpu_fallback()
        and stage.hasParam("outputCol")
    )


def _stage_input_cols(stage: Any) -> Tuple[Optional[str], Optional[List[str]]]:
    getter = getattr(stage, "_get_input_columns", None)
    if getter is None:
        return None, None
    return getter()


class Pipeline(Params):
    """Sequential stages; estimators are fit then their models transform
    (pyspark.ml.Pipeline semantics + the assembler bypass + §6k chain fusion)."""

    def __init__(self, stages: Optional[List[Any]] = None) -> None:
        super().__init__()
        self._stages = stages or []
        self.logger = get_logger(self.__class__)

    def getStages(self) -> List[Any]:
        return self._stages

    def setStages(self, value: List[Any]) -> "Pipeline":
        self._stages = value
        return self

    def copy(self, extra: Optional[ParamMap] = None) -> "Pipeline":
        """Copy with `extra` routed to the stages that own each param (by the
        param's parent uid when it names a stage, by param name otherwise —
        fitMultiple/CrossValidator grids address stage params, not pipeline
        params)."""
        that = super().copy(None)
        extra = extra or {}
        stage_uids = {getattr(s, "uid", None) for s in self._stages}

        def stage_extra(s: Any) -> ParamMap:
            out: ParamMap = {}
            for p, v in extra.items():
                parent = getattr(p, "parent", None)
                if parent in stage_uids:
                    if parent == getattr(s, "uid", None):
                        out[p] = v
                elif hasattr(s, "hasParam") and s.hasParam(p.name):
                    out[p] = v
            return out

        that._stages = [
            s.copy(stage_extra(s)) if hasattr(s, "copy") else s
            for s in self._stages
        ]
        return that  # type: ignore[return-value]

    def fit(self, dataset: Any, params: Optional[ParamMap] = None) -> "PipelineModel":
        if params:
            return self.copy(params)._fit(dataset)
        return self._fit(dataset)

    def fitMultiple(self, dataset: Any, paramMaps: List[ParamMap]):
        """Fit one PipelineModel per param map. All candidates share ONE
        feature-extraction memo and ONE HBM batch-cache scope: when the
        candidates fuse (§6k), every fit streams the SAME pinned host arrays,
        so pass 1 of candidate 1 uploads each raw batch once and every later
        pass — of every candidate — replays it from HBM
        (ops/device_cache.py)."""
        from .core.estimator import _FitMultipleIterator
        from .ops.device_cache import batch_cache

        memo: Dict[Any, Any] = {}
        with batch_cache():
            models = [
                self.copy(m)._fit(dataset, _extract_memo=memo) for m in paramMaps
            ]
        return _FitMultipleIterator(lambda i: models[i], len(paramMaps))

    def _fit(
        self, dataset: Any, _extract_memo: Optional[Dict[Any, Any]] = None
    ) -> "PipelineModel":
        stages = list(self._stages)

        # assembler bypass (reference pipeline.py:85-119): VectorAssembler feeding a
        # TPU estimator's featuresCol becomes featuresCols on the estimator directly
        for i in range(len(stages) - 1):
            a, b = stages[i], stages[i + 1]
            if (
                _isVectorAssembler(a)
                and _isTpuEstimator(b)
                and a.isDefined("outputCol")
                and b.hasParam("featuresCol")
                and b.getOrDefault("featuresCol") == a.getOrDefault("outputCol")
                and b.hasParam("featuresCols")
            ):
                self.logger.info(
                    "Bypassing VectorAssembler '%s' -> feeding %d scalar columns "
                    "directly to %s",
                    a.uid,
                    len(a.getOrDefault("inputCols")),
                    type(b).__name__,
                )
                # bypass on a COPY: mutating the user's estimator would corrupt its
                # reuse outside this pipeline (pyspark's Pipeline.fit also never
                # mutates the supplied stages)
                b = b.copy()
                b._set(featuresCols=a.getOrDefault("inputCols"))
                b._clear(b.getParam("featuresCol"))
                stages[i + 1] = b
                stages[i] = NoOpTransformer()

        chain_start = self._fuse_chain_start(stages, dataset)

        fitted: List[Any] = []
        for idx, stage in enumerate(stages):
            if chain_start is not None and idx == chain_start:
                chain_models = self._fused_chain_fit(
                    stages[idx:], dataset, _extract_memo
                )
                if chain_models is not None:
                    fitted.extend(chain_models)
                    break
                # data-level gates declined (sparse input, below threshold):
                # fall through to the staged loop for the remaining stages
                chain_start = None
            if hasattr(stage, "_get_tpu_fit_func") or (
                hasattr(stage, "fit") and not hasattr(stage, "transform")
            ):
                model = stage.fit(dataset)
                fitted.append(model)
                dataset = model.transform(dataset)
            elif hasattr(stage, "transform"):
                fitted.append(stage)
                dataset = stage.transform(dataset)
            else:
                raise TypeError(f"Pipeline stage {stage} is neither fit-able nor transform-able")
        return PipelineModel(fitted)

    # ---- §6k whole-pipeline fusion ----

    def _fuse_chain_start(self, stages: List[Any], dataset: Any) -> Optional[int]:
        """Index where a fusable featurize->fit SUFFIX chain begins, or None.
        Structural + cheap gates only (stage types, column linkage, config,
        row count); data-level gates (sparsity, stream threshold) run after
        extraction in _fused_chain_fit."""
        from . import config as _config

        if len(stages) < 2 or not bool(_config.get("pipeline.fuse")):
            return None
        from .core.dataset import _is_spark_df

        if _is_spark_df(dataset):
            return None  # the barrier/collect planes own Spark inputs
        term = stages[-1]
        if not _terminal_fuse_eligible(term):
            return None
        start = len(stages) - 1
        while start > 0 and _featurizer_fuse_eligible(stages[start - 1]):
            prev = stages[start - 1]
            cur_in, cur_in_cols = _stage_input_cols(stages[start])
            if cur_in_cols is not None or cur_in != prev.getOrDefault("outputCol"):
                break  # not column-linked: the chain cannot absorb this stage
            start -= 1
        if start == len(stages) - 1:
            return None  # no featurizer feeds the terminal — nothing to fuse
        # uniform compute dtype across the chain: one in-program cast discipline
        f32 = {bool(s._float32_inputs) for s in stages[start:]}
        if len(f32) != 1:
            return None
        try:
            n_rows = len(dataset)
        except TypeError:
            n_rows = int(getattr(dataset, "num_rows", 0))
        if n_rows < _resolve_fuse_min_rows():
            return None
        # degenerate single-class logistic fits route in-core; detect up front
        # so the staged path carries them instead of a mid-chain error
        if type(term).__name__ == "LogisticRegression":
            import numpy as np

            label_col = term.getOrDefault("labelCol")
            try:
                labels = np.asarray(dataset[label_col], dtype=np.float64)
            except Exception:
                return None
            if np.unique(labels[~np.isnan(labels)]).size <= 1:
                return None
        return start

    def _fused_chain_fit(
        self,
        chain: List[Any],
        dataset: Any,
        extract_memo: Optional[Dict[Any, Any]] = None,
    ) -> Optional[List[Any]]:
        """Fit a featurize->fit chain as one fused streamed program per batch.
        Returns the fitted models in stage order, or None when a data-level
        gate declines (caller falls back to the staged loop)."""
        from . import config as _config
        from .core.dataset import extract_feature_data
        from .observability import counter_inc as obs_counter_inc, fit_run
        from .ops.device_cache import batch_cache

        first, term = chain[0], chain[-1]
        for est in chain:
            est._validate_param_bounds()
        input_col, input_cols = _stage_input_cols(first)
        label_col = (
            term.getOrDefault("labelCol")
            if term._use_label() and term.hasParam("labelCol")
            else None
        )
        weight_col = (
            term.getOrDefault("weightCol") if term._use_sample_weight() else None
        )
        fd_key = (
            input_col,
            tuple(input_cols) if input_cols else None,
            label_col,
            weight_col,
            bool(first._float32_inputs),
        )
        fd = extract_memo.get(fd_key) if extract_memo is not None else None
        if fd is None:
            fd = extract_feature_data(
                dataset,
                input_col=input_col,
                input_cols=input_cols,
                label_col=label_col,
                weight_col=weight_col,
                float32=first._float32_inputs,
            )
            if extract_memo is not None:
                extract_memo[fd_key] = fd
        if fd.is_sparse:
            return None  # sparse chains stay staged (no dense chain ops)
        threshold = int(_config.get("stream_threshold_bytes") or 0)
        feature_bytes = fd.n_rows * fd.n_cols * (4 if first._float32_inputs else 8)
        if not threshold or feature_bytes <= threshold:
            return None  # in-core scale: the staged path is faster to compile
        chain_names = [type(est).__name__ for est in chain]
        self.logger.info(
            "fusing pipeline chain %s into one streamed program per batch "
            "(~%.0f MiB design matrix)",
            " -> ".join(chain_names),
            feature_bytes / 2**20,
        )
        # one parent run spans the chain so the §6f ingest section and the
        # fused-stage counter land in one exported report; each stage fit still
        # opens its own nested FitRun exactly like a staged fit would
        with fit_run(algo="Pipeline") as prun:
            fitted: List[Any] = []
            chain_ops: List[Tuple] = []
            kinds: List[str] = []
            # ONE batch-cache scope spans every stage: the chain's shared INPUT
            # batches upload once, later stages replay them from HBM
            with batch_cache():
                for est in chain:
                    model = _fused_stage_fit(est, fd, tuple(chain_ops))
                    fitted.append(model)
                    if est is not term:
                        op = model._chain_op()
                        chain_ops.append(op)
                        kinds.append(str(op[0]))
            label = ">".join(kinds + [type(term).__name__.lower()])
            obs_counter_inc("pipeline.fused_stages", len(chain), chain=label)
        report = prun.report() if prun is not None else None
        for model in fitted:
            model.pipeline_report_ = report
        return fitted


def _fused_stage_fit(est: Any, fd: Any, chain_ops: Tuple) -> Any:
    """One chain stage's fit, mirroring _TpuEstimator._fit/_fit_internal
    (core/estimator.py) with the streamed path forced and the upstream chain
    applied in-program."""
    from .observability import fit_run

    with fit_run(algo=type(est).__name__) as run:
        attrs = est._streaming_fit(fd, chain_ops=chain_ops or None)
        model = est._create_pyspark_model(attrs)
        model._num_workers = est._num_workers
        model._float32_inputs = est._float32_inputs
        model._has_training_summary = True
        est._copyValues(model)
    if run is not None:
        model.fit_report_ = run.report()
    return model


class PipelineModel(Params):
    def __init__(self, stages: List[Any]) -> None:
        super().__init__()
        self.stages = stages

    def transform(self, dataset: Any, params: Optional[ParamMap] = None) -> Any:
        for stage in self.stages:
            dataset = stage.transform(dataset)
        return dataset
