#
# Global config/flag system — the TPU analog of the reference's Spark-conf tier
# (SURVEY.md §5.6; reference reads spark.rapids.ml.{uvm.enabled, sam.enabled,
# cpu.fallback.enabled, verbose, float32_inputs, num_workers} at fit time,
# core.py:776-812 / params.py:275-286; documented in docs/site/configuration.md).
#
# Three tiers, mirroring the reference:
#   1. estimator Params / backend kwargs        (per-estimator, core/backend_params)
#   2. THIS module: process-wide defaults, settable programmatically or via
#      SRML_TPU_* environment variables         (the spark-conf analog)
#   3. hard defaults below
#
# Keys:
#   fallback.enabled   (bool, env SRML_TPU_FALLBACK_ENABLED)  — CPU fallback on
#                      unsupported params (reference spark.rapids.ml.cpu.fallback.enabled)
#   float32_inputs     (bool, env SRML_TPU_FLOAT32_INPUTS)
#   num_workers        (int,  env SRML_TPU_NUM_WORKERS)       — default mesh width
#   verbose            (bool, env SRML_TPU_VERBOSE)
#   trace_dir          (str,  env SRML_TPU_TRACE_DIR)         — xplane capture per fit
#

from __future__ import annotations

import os
from typing import Any, Dict

_DEFAULTS: Dict[str, Any] = {
    "fallback.enabled": True,
    "float32_inputs": True,
    "num_workers": None,
    "verbose": False,
    "trace_dir": None,
    # streamed out-of-core fit (ops/streaming.py): estimators with a streaming path
    # switch to it when the design matrix exceeds this many bytes (the TPU analog of
    # the reference's UVM/SAM managed memory, utils.py:184-241)
    "stream_threshold_bytes": 4 << 30,
    "stream_batch_rows": 1 << 20,
    # Spark-input fit data plane: "barrier" fans the fit out as barrier tasks over
    # TPU hosts (spark/integration.py), "collect" materializes on the driver (local
    # mode / tiny data), "auto" picks barrier when a usable pyspark is importable
    "spark_fit_mode": "auto",
    # fast_math=True lets ranking-only matmuls (KMeans assignment distances) run at
    # MXU bf16 single-pass precision; model attributes stay parity-precision
    "fast_math": False,
    # precision of PARITY matmuls (the ones feeding model attributes):
    #   highest = 6-pass bf16 (full f32, the default)
    #   high    = 3-pass bf16 (~2x faster on MXU, error ~2^-22 vs ~2^-24)
    # a TPU-measured accuracy/throughput tradeoff knob; tests pin highest
    "parity_precision": "highest",
    # fused one-X-read pallas Gram kernels: the PCA covariance AND the
    # normal-equation LinReg stats (ops/pallas_xtwx.py — the label rides as a
    # tile-aligned operand so XᵀX/Xᵀy/yᵀy come from one X read): "auto" = on for
    # TPU unit-weight f32 fits (measured 6x the XLA path at 12M x 128), "0" =
    # force XLA, "1" = skip the platform check (tests — runs the kernel's
    # interpreter off-TPU)
    "pallas_xtwx": "auto",
    # selection plane (ops/selection.py): THE top-k strategy for the whole
    # search family (exact kNN, IVF-Flat/PQ, CAGRA, streamed ANN, pairwise
    # sweeps). auto = approx on TPU (native approximate-selection unit +
    # parity re-rank keeps returned distances exact), exact_tiled elsewhere
    # (bit-for-bit equal to exact_full; two-stage vectorized select)
    "knn.selection": "auto",  # auto | exact_full | exact_tiled | approx
    # per-element expected recall of the approx strategy's winner pool
    # (jax.lax.approx_max_k recall_target); exact modes ignore it
    "knn.recall_target": 0.95,
    # exact_tiled tile width; 0 = platform auto (TPU: 2048 — small fixed tiles
    # vectorize on the VPU; CPU: max(8192, n/4) — the XLA CPU TopK custom call
    # is per-call-overhead-bound, so few large tiles win)
    "knn.select_tile": 0,
    # fused pallas distance+select scans (ops/pallas_select.py, design.md §5c):
    # the `pallas_fused` selection strategy fuses the (block, n_items) distance
    # tile with an in-register running top-k/argmin/count so the distance
    # matrix never materializes in HBM. `auto` engages it on TPU at FUSABLE
    # call sites (exact kNN scans, IVF coarse probes, DBSCAN neighborhood
    # counts, KMeans assignment) once the scanned item width reaches this
    # threshold; below it (or off-TPU) auto keeps the PR-5 strategies
    "knn.pallas_min_items": 1 << 16,
    # distance-ACCUMULATION precision of the fused scan: float32 is exact
    # (bit-identical to the XLA path); bfloat16/int8 compute an approximate
    # candidate pool on the fast MXU paths and the parity_rerank_sq invariant
    # restores exact-f32 returned distances (only the id set is approximate)
    "knn.pallas_precision": "float32",
    # HBM-resident batch cache (ops/device_cache.py): multi-pass streamed fits
    # retain pass-1 device batches and replay passes 2..N from HBM (the TPU
    # analog of the reference's cross-pass cuDF/UVM residency). The budget
    # bounds cache HBM; datasets above it cache a prefix and stream the tail
    "cache.enabled": True,
    "cache.hbm_budget_bytes": 2 << 30,
    # reliability subsystem (reliability/): retry/backoff policy, deterministic
    # fault injection, streamed-fit checkpoint-resume, and the
    # barrier->collect degradation ladder (docs/design.md "Reliability")
    "reliability.enabled": True,
    "reliability.max_attempts": 3,          # total attempts per retried unit
    "reliability.backoff_base_s": 0.05,     # exponential backoff base
    "reliability.backoff_max_s": 2.0,       # backoff cap
    "reliability.backoff_jitter": 0.1,      # +/- jitter/2, deterministic (hashed)
    "reliability.deadline_s": None,         # per-stage wall-clock deadline
    "reliability.checkpoint_batches": 16,   # streamed-fit snapshot cadence
    "reliability.fault_spec": "",           # fault grammar, reliability/faults.py
    "reliability.chaos_spec": "",           # replica chaos grammar, reliability/chaos.py
    "reliability.degrade_to_collect": True, # barrier fit failure -> collect mode
    # observability subsystem (observability/): typed metrics registry, per-fit
    # FitRun trace trees (model.fit_report_), driver-side aggregation of
    # barrier-worker metrics, JSONL + Prometheus exporters (docs/design.md §6d)
    "observability.enabled": True,          # FitRun scopes + trace collection
    "observability.metrics_dir": None,      # JSONL fit_reports.jsonl directory
    "observability.max_spans": 1024,        # trace-tree node cap per run
    # inference plane (observability/inference.py): TransformRun scopes, the
    # instrumented predict dispatch, and the recompile sentinel — warn (and
    # count transform.recompile_storm) once one model's predict has seen more
    # distinct (rows, cols, dtype) shape signatures than this; un-bucketed
    # pandas-UDF batch sizes silently force one XLA compile per batch
    "observability.recompile_warn_threshold": 8,
    # fraction of transform batches whose latency lands in the
    # transform.batch_s/predict_s histograms (counters always count); lower it
    # on hot serving paths where even histogram writes show up in profiles
    "observability.transform_sample_rate": 1.0,
    # JSONL report rotation (observability/export.py): rotate the live file at
    # max_report_bytes, keep max_report_files rotated generations
    "observability.max_report_bytes": 32 << 20,
    "observability.max_report_files": 4,
    # device-performance plane (observability/device.py, docs/design.md §6f):
    # compiled_kernel AOT cost/memory-analysis capture + compile accounting +
    # span cost attribution. Off = kernels run as plain jax.jit calls.
    "observability.device_enabled": True,
    # HBM telemetry: sample local_devices() memory_stats() at span boundaries
    # (gauges are simply absent on platforms without memory_stats — CPU)
    "observability.hbm_sampling": True,
    "observability.hbm_sample_interval_s": 0.05,  # span-boundary rate limit
    # communication plane (observability/comm.py, docs/design.md §6h):
    # per-rank skew above which a rank is flagged a straggler (its phase wall
    # time vs the rank median): fires a `straggler` event into the run's event
    # log + flight recorder and counts comm.stragglers{phase=}
    "observability.straggler_threshold": 1.5,
    # absolute per-phase wall-time floor for straggler flags: ratios over
    # millisecond-scale phases are scheduler jitter, not stragglers
    "observability.straggler_min_wall_s": 0.25,
    # live telemetry plane (observability/server.py, docs/design.md §6g):
    # opt-in driver-resident HTTP endpoint serving /metrics (Prometheus pull),
    # /healthz and /runs[/<run_id>] (live JSON view of open runs). None = no
    # server thread is ever started; 0 = bind an ephemeral port (exposed via
    # observability.server.server_address()); the server runs only while at
    # least one run scope is open (or start_metrics_server() pins it)
    "observability.http_port": None,
    # bind host for the telemetry endpoint. Default loopback: the endpoint is
    # unauthenticated, so exposing it beyond the driver host is an explicit
    # operator decision ("0.0.0.0" for cluster-visible scraping)
    "observability.http_host": "127.0.0.1",
    # failure flight recorder (observability/flight.py): bounded per-process
    # ring buffer of recent span opens/closes, events, HBM samples and
    # retry/fault/degrade transitions, dumped as postmortem_<run_id>.json on
    # unhandled fit/transform failure or degradation-ladder entry; <=0 disables
    "observability.flight_recorder_events": 256,
    # per-run cap on streamed-fit convergence records (kmeans inertia/shift,
    # logreg/linreg loss/grad-norm per iteration) kept in the run and exported
    # in the report's `convergence` section; overflow is counted, not kept
    "observability.max_convergence_records": 512,
    # online serving plane (serving/, docs/design.md §7): the driver-resident
    # inference server that turns per-request predict calls into fixed-shape
    # device batches. A batch closes when it reaches max_batch_rows OR the
    # OLDEST queued request has waited max_wait_ms — the classic latency/size
    # cutoff pair (Podracer decoupled feed threads, arXiv:2104.06272)
    "serving.max_batch_rows": 4096,
    "serving.max_wait_ms": 2.0,
    # smallest padding bucket: coalesced batches pad UP to the next power-of-
    # two row count >= this, so the set of predict shape signatures is fixed
    # and finite — bucketing IS the built-in fix for the recompile storms the
    # PR-4 sentinel detects (one XLA compile per ragged batch size)
    "serving.bucket_min_rows": 16,
    # AOT pre-warm on model registration: compile one executable per
    # (model, bucket) up front through the compiled_kernel cache so steady-
    # state serving never compiles
    "serving.prewarm": True,
    # HBM byte budget of the serving model registry (weights of hot models
    # stay device-resident; cold models evict LRU — pinned-while-serving —
    # and reload transparently, counted as serving.model_reloads)
    "serving.hbm_budget_bytes": 1 << 30,
    # backpressure: max requests queued per served model before submit/POST
    # rejects (HTTP 429); a bounded queue keeps tail latency bounded too
    "serving.queue_depth": 1024,
    # per-request wall-clock budget the HTTP handler waits on a future before
    # answering 504 (the request may still complete; its slot is not replayed)
    "serving.request_timeout_s": 30.0,
    # fault-tolerant serving fleet (serving/fleet.py + serving/router.py,
    # docs/design.md §7c). replicas: dispatcher replicas per served model
    # (0 = auto: tuning table, else 1 — the single-dispatcher plane);
    # heartbeat_timeout_s: how long a replica may go without a dispatcher
    # heartbeat before the health monitor marks it DEAD and replays its queue
    # onto survivors; hedge_after_p99_frac: issue a duplicate of a still-
    # queued request to a second replica once its queue wait exceeds this
    # fraction of the observed p99 latency (0 disables hedging)
    "serving.replicas": 0,
    "serving.hedge_after_p99_frac": 0.0,
    "serving.heartbeat_timeout_s": 2.0,
    # ANN index lifecycle (ops/ann_streaming.py + ops/ann_lifecycle.py,
    # docs/design.md §7b). build_batch_rows: row-batch geometry of the
    # pipelined out-of-core builds; 0 = auto (tuning table, else
    # stream_batch_rows). prefetch_depth: staged batches kept in flight so
    # host staging of batch i+1 overlaps device execution of batch i; 0 runs
    # the serial (pre-pipeline) loop — the bench baseline mode
    "ann.build_batch_rows": 0,
    "ann.prefetch_depth": 1,
    # incremental maintenance: IVF list capacity rounds UP to a power-of-two
    # bucket >= list_bucket_rows so in-slack adds never change the search
    # executable's shapes (0 = auto: tuning table, else the defaults-module
    # floor); compaction re-layouts the lists once tombstoned slots exceed
    # this percentage of occupied slots
    "ann.list_bucket_rows": 0,
    "ann.compact_tombstone_pct": 30,
    # lazy device residency of loaded/served indexes (ops/ann_lifecycle.py::
    # DeviceIndexCache): per-segment HBM budget; a segment uploads on FIRST
    # search, not at load — cold-start never stages the whole index
    "ann.index_cache_bytes": 1 << 30,
    # zero-copy ingest plane (ops/ingest.py, docs/design.md §6k): contiguous
    # right-dtype host blocks enter the device DMA path as views (no host
    # staging copy); exotic inputs fall back to a counted staging copy. Off =
    # every batch slice staged through np.ascontiguousarray, the pre-§6k path
    "ingest.zero_copy": True,
    # staging-buffer pool geometry (rows per pooled buffer) for the counted
    # copy fallback; 0 = auto (tuning table, else autotune/defaults.py).
    # Buffer REUSE engages only on backends whose device_put copies (TPU/GPU);
    # CPU jax aliases host memory, so reuse there would corrupt cached batches
    "ingest.staging_pool_rows": 0,
    # whole-pipeline fusion (pipeline.py, docs/design.md §6k): compile
    # featurize->fit chains (scale/PCA feeding KMeans/logreg/linreg) into one
    # streamed program per batch — intermediates never round-trip to host.
    # Bit-parity with the staged path is the contract; off = staged fits
    "pipeline.fuse": True,
    # rows below which fusion is skipped (staged fit overhead is negligible
    # and the staged trace is simpler to debug); 0 = auto (tuning table, else
    # autotune/defaults.py)
    "pipeline.fuse_min_rows": 0,
    # partitioner plane (parallel/partitioner.py, docs/design.md §10): the
    # single owner of mesh + shardings. feature_axis: width of the 2-D
    # SPMDPartitioner's feature axis (wide-k kNN / feature-sharded
    # covariance); 0 = auto (tuning table per (n, d) bucket, else 1 = pure
    # data-parallel). batch_rows_per_process: LOCAL rows each process stages
    # per streamed batch on multi-host runs; 0 = auto (tuning table, else
    # stream_batch_rows split evenly across the pod). Both resolve at host
    # resolution points only — never inside a trace
    "partition.feature_axis": 0,
    "partition.batch_rows_per_process": 0,
    # continuous-learning plane (spark_rapids_ml_tpu/continual/, docs/
    # design.md §7d): streamed partial_fit + drift detection + governed
    # promotion. decay: per-update discount on the persistent sufficient-
    # statistics carry (1.0 = infinite memory, the 1505.06807 a=1 default;
    # 0.0 = auto: tuning table, else autotune/defaults.py). update_batch_rows:
    # fixed block geometry of partial_fit ingest — every update batch is
    # re-blocked to this row count (zero-weight padding) so a steady update
    # stream re-enters ONE compiled executable per kernel (0 = auto).
    # drift_mads: MADs above the baseline median a per-row signal must land
    # to fire `continual.drift` (0.0 = auto). promote_every: attempt a
    # governed promotion after this many updates even without drift.
    # min_baseline: self-calibration floor — observations absorbed into the
    # noise baseline before the detector may fire (when no fit-time
    # convergence tail seeded it)
    "continual.decay": 0.0,
    "continual.update_batch_rows": 0,
    "continual.drift_mads": 0.0,
    "continual.promote_every": 4,
    "continual.min_baseline": 8,
    # trace plane (observability/tracing.py, docs/design.md §6l): per-request
    # causal traces with tail-based sampling. sample_rate: deterministic
    # hash-of-trace_id keep probability for unflagged, not-slow traces (the
    # flagged classes — error/hedged/failover/expired/shed — ALWAYS keep).
    # ring_traces: bounded per-process kept-trace ring served by /traces.
    # slow_frac: rolling slowest fraction that keeps regardless of sampling.
    "tracing.enabled": True,
    "tracing.sample_rate": 1.0,
    "tracing.ring_traces": 256,
    "tracing.slow_frac": 0.05,
    # closed-loop autotuner (spark_rapids_ml_tpu/autotune/, docs/design.md
    # §6i): telemetry-driven knob search persisted as per-platform tuning
    # tables. mode:
    #   off    never consult tables (every knob resolves to its built-in
    #          default unless config pins it)
    #   load   (default) consult the tuning table at the host-wrapper
    #          resolution points; misses fall through to defaults
    #   search on first sight of an uncovered (knob, shape-bucket) at a
    #          searchable knob, run the measurement loop, persist the winner,
    #          and use it — the opt-in online mode
    "autotune.mode": "load",
    # tuning-table directory (versioned tuning_<platform>_<device_kind>.json
    # files, atomic writes). None = in-memory tables only: lookups/searches
    # work for the life of the process but nothing persists
    "autotune.dir": None,
    # measurement-loop replication: timed reps per candidate (round-robin
    # across candidates so warming drift cannot favor late candidates), and
    # how many MADs of separation a challenger needs to displace the default
    # (judging two noise samples against each other is not a win)
    "autotune.replicates": 5,
    "autotune.noise_mads": 3.0,
}

_ENV_KEYS: Dict[str, str] = {
    "fallback.enabled": "SRML_TPU_FALLBACK_ENABLED",
    "float32_inputs": "SRML_TPU_FLOAT32_INPUTS",
    "num_workers": "SRML_TPU_NUM_WORKERS",
    "verbose": "SRML_TPU_VERBOSE",
    "trace_dir": "SRML_TPU_TRACE_DIR",
    "stream_threshold_bytes": "SRML_TPU_STREAM_THRESHOLD_BYTES",
    "stream_batch_rows": "SRML_TPU_STREAM_BATCH_ROWS",
    "spark_fit_mode": "SRML_TPU_SPARK_FIT_MODE",
    "fast_math": "SRML_TPU_FAST_MATH",
    "parity_precision": "SRML_TPU_PARITY_PRECISION",
    "pallas_xtwx": "SRML_TPU_PALLAS_XTWX",
    "knn.selection": "SRML_TPU_KNN_SELECTION",
    "knn.recall_target": "SRML_TPU_KNN_RECALL_TARGET",
    "knn.select_tile": "SRML_TPU_KNN_SELECT_TILE",
    "knn.pallas_min_items": "SRML_TPU_KNN_PALLAS_MIN_ITEMS",
    "knn.pallas_precision": "SRML_TPU_KNN_PALLAS_PRECISION",
    "cache.enabled": "SRML_TPU_CACHE_ENABLED",
    "cache.hbm_budget_bytes": "SRML_TPU_CACHE_BUDGET",
    "reliability.enabled": "SRML_TPU_RELIABILITY_ENABLED",
    "reliability.max_attempts": "SRML_TPU_MAX_ATTEMPTS",
    "reliability.backoff_base_s": "SRML_TPU_BACKOFF_BASE_S",
    "reliability.backoff_max_s": "SRML_TPU_BACKOFF_MAX_S",
    "reliability.backoff_jitter": "SRML_TPU_BACKOFF_JITTER",
    "reliability.deadline_s": "SRML_TPU_DEADLINE_S",
    "reliability.checkpoint_batches": "SRML_TPU_CHECKPOINT_BATCHES",
    "reliability.fault_spec": "SRML_TPU_FAULT_SPEC",
    "reliability.chaos_spec": "SRML_TPU_CHAOS_SPEC",
    "reliability.degrade_to_collect": "SRML_TPU_DEGRADE_TO_COLLECT",
    "observability.enabled": "SRML_TPU_OBSERVABILITY_ENABLED",
    "observability.metrics_dir": "SRML_TPU_METRICS_DIR",
    "observability.max_spans": "SRML_TPU_MAX_SPANS",
    "observability.recompile_warn_threshold": "SRML_TPU_RECOMPILE_WARN_THRESHOLD",
    "observability.transform_sample_rate": "SRML_TPU_TRANSFORM_SAMPLE_RATE",
    "observability.max_report_bytes": "SRML_TPU_MAX_REPORT_BYTES",
    "observability.max_report_files": "SRML_TPU_MAX_REPORT_FILES",
    "observability.device_enabled": "SRML_TPU_DEVICE_OBSERVABILITY",
    "observability.hbm_sampling": "SRML_TPU_HBM_SAMPLING",
    "observability.hbm_sample_interval_s": "SRML_TPU_HBM_SAMPLE_INTERVAL_S",
    "observability.straggler_threshold": "SRML_TPU_STRAGGLER_THRESHOLD",
    "observability.straggler_min_wall_s": "SRML_TPU_STRAGGLER_MIN_WALL_S",
    "observability.http_port": "SRML_TPU_METRICS_PORT",
    "observability.http_host": "SRML_TPU_METRICS_HOST",
    "observability.flight_recorder_events": "SRML_TPU_FLIGHT_RECORDER_EVENTS",
    "observability.max_convergence_records": "SRML_TPU_MAX_CONVERGENCE_RECORDS",
    "serving.max_batch_rows": "SRML_TPU_SERVING_MAX_BATCH_ROWS",
    "serving.max_wait_ms": "SRML_TPU_SERVING_MAX_WAIT_MS",
    "serving.bucket_min_rows": "SRML_TPU_SERVING_BUCKET_MIN_ROWS",
    "serving.prewarm": "SRML_TPU_SERVING_PREWARM",
    "serving.hbm_budget_bytes": "SRML_TPU_SERVING_HBM_BUDGET",
    "serving.queue_depth": "SRML_TPU_SERVING_QUEUE_DEPTH",
    "serving.request_timeout_s": "SRML_TPU_SERVING_REQUEST_TIMEOUT_S",
    "serving.replicas": "SRML_TPU_SERVING_REPLICAS",
    "serving.hedge_after_p99_frac": "SRML_TPU_SERVING_HEDGE_AFTER_P99_FRAC",
    "serving.heartbeat_timeout_s": "SRML_TPU_SERVING_HEARTBEAT_TIMEOUT_S",
    "ann.build_batch_rows": "SRML_TPU_ANN_BUILD_BATCH_ROWS",
    "ann.prefetch_depth": "SRML_TPU_ANN_PREFETCH_DEPTH",
    "ann.list_bucket_rows": "SRML_TPU_ANN_LIST_BUCKET_ROWS",
    "ann.compact_tombstone_pct": "SRML_TPU_ANN_COMPACT_TOMBSTONE_PCT",
    "ann.index_cache_bytes": "SRML_TPU_ANN_INDEX_CACHE_BYTES",
    "ingest.zero_copy": "SRML_TPU_INGEST_ZERO_COPY",
    "ingest.staging_pool_rows": "SRML_TPU_INGEST_STAGING_POOL_ROWS",
    "pipeline.fuse": "SRML_TPU_PIPELINE_FUSE",
    "pipeline.fuse_min_rows": "SRML_TPU_PIPELINE_FUSE_MIN_ROWS",
    "partition.feature_axis": "SRML_TPU_PARTITION_FEATURE_AXIS",
    "partition.batch_rows_per_process": "SRML_TPU_PARTITION_BATCH_ROWS_PER_PROCESS",
    "continual.decay": "SRML_TPU_CONTINUAL_DECAY",
    "continual.update_batch_rows": "SRML_TPU_CONTINUAL_UPDATE_BATCH_ROWS",
    "continual.drift_mads": "SRML_TPU_CONTINUAL_DRIFT_MADS",
    "continual.promote_every": "SRML_TPU_CONTINUAL_PROMOTE_EVERY",
    "continual.min_baseline": "SRML_TPU_CONTINUAL_MIN_BASELINE",
    "tracing.enabled": "SRML_TPU_TRACING_ENABLED",
    "tracing.sample_rate": "SRML_TPU_TRACING_SAMPLE_RATE",
    "tracing.ring_traces": "SRML_TPU_TRACING_RING_TRACES",
    "tracing.slow_frac": "SRML_TPU_TRACING_SLOW_FRAC",
    "autotune.mode": "SRML_TPU_AUTOTUNE_MODE",
    "autotune.dir": "SRML_TPU_TUNE_DIR",
    "autotune.replicates": "SRML_TPU_AUTOTUNE_REPLICATES",
    "autotune.noise_mads": "SRML_TPU_AUTOTUNE_NOISE_MADS",
}

_overrides: Dict[str, Any] = {}


def _coerce(key: str, raw: str) -> Any:
    default = _DEFAULTS[key]
    if isinstance(default, bool) or key in ("fallback.enabled", "float32_inputs", "verbose"):
        return raw.strip().lower() in ("1", "true", "yes", "on")
    if isinstance(default, int) or key in ("num_workers", "observability.http_port"):
        return int(raw)
    if isinstance(default, float) or key == "reliability.deadline_s":
        return float(raw)
    return raw


def get(key: str) -> Any:
    """Resolution order: programmatic set() > environment > default."""
    if key not in _DEFAULTS:
        raise KeyError(f"Unknown config key '{key}'; known: {sorted(_DEFAULTS)}")
    if key in _overrides:
        return _overrides[key]
    env = os.environ.get(_ENV_KEYS[key])
    if env is not None and env != "":
        return _coerce(key, env)
    return _DEFAULTS[key]


def source(key: str) -> str:
    """Where `get(key)` currently resolves from: 'set' (programmatic
    override), 'env', or 'default'. The autotuner's tuning tables slot in
    BETWEEN env and default (docs/design.md §6i): a knob's table entry is
    consulted only when this returns 'default' — set() and env always win."""
    if key not in _DEFAULTS:
        raise KeyError(f"Unknown config key '{key}'; known: {sorted(_DEFAULTS)}")
    if key in _overrides:
        return "set"
    env = os.environ.get(_ENV_KEYS[key])
    if env is not None and env != "":
        return "env"
    return "default"


_epoch = 0


def epoch() -> int:
    """Monotonic mutation counter, bumped by every set()/unset(). Hot paths
    (the trace plane's per-request config reads) cache derived values
    against it instead of re-resolving per call. Mutating os.environ
    directly without a set()/unset() in between does NOT bump it — export
    env before process start, or go through set()."""
    return _epoch


def set(key: str, value: Any) -> None:  # spark-conf style name (shadows the builtin deliberately)
    global _epoch
    if key not in _DEFAULTS:
        raise KeyError(f"Unknown config key '{key}'; known: {sorted(_DEFAULTS)}")
    _overrides[key] = value
    _epoch += 1


def unset(key: str) -> None:
    global _epoch
    _overrides.pop(key, None)
    _epoch += 1


def all() -> Dict[str, Any]:  # spark-conf style name (shadows the builtin deliberately)
    return {k: get(k) for k in _DEFAULTS}
