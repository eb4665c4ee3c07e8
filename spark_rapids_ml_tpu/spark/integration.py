#
# Spark barrier-task fan-out for TPU SPMD fits — the structural replacement for the
# reference's `dataset.mapInPandas(_train_udf).rdd.barrier()` execution pattern
# (reference core.py:845-1011) on a TPU-attached Spark cluster.
#
# Architecture (one barrier task per TPU HOST, not per chip — SURVEY.md §7 notes the
# worker=host topology change vs the reference's task↔GPU pinning):
#   1. each task concatenates its partition's Arrow batches to host arrays,
#   2. the barrier allGather carries (a) the jax.distributed coordinator address the
#      way the reference carries the NCCL uid (cuml_context.py:75-110), and (b) the
#      per-task PartitionInfo (row counts) the way the reference builds its
#      PartitionDescriptor (utils.py:325-355),
#   3. jax.distributed.initialize links the hosts; a global mesh spans the pod,
#   4. every task places its rows into the global array via
#      jax.make_array_from_process_local_data and runs the SAME jitted fit program —
#      collectives ride ICI/DCN; rank 0 yields the model-attribute row.
#
# pyspark is imported lazily: this module parses/serializes and orchestrates, and is
# testable without Spark down to the UDF boundary.
#

from __future__ import annotations

import json
import threading
from dataclasses import dataclass
from typing import Any, Callable, List

import numpy as np

from ..utils import get_logger


@dataclass
class PartitionInfo:
    """Per-barrier-task facts exchanged via allGather (the reference's
    PartitionDescriptor payload, utils.py:325-355). For sparse fits the ELL width
    travels too: every host must pad its ELL rows to the GLOBAL max nonzeros-per-row
    before the global array assembles (the sparse analog of the reference's nnz
    exchange, classification.py:1012-1016)."""

    rank: int
    n_rows: int
    coordinator: str = ""  # rank 0 advertises host:port for jax.distributed
    nnz: int = -1  # local nonzeros (sparse fits)
    ell_width: int = 0  # local max nonzeros/row (sparse fits)


def encode_partition_info(info: PartitionInfo) -> str:
    return json.dumps(
        {
            "rank": info.rank,
            "n_rows": info.n_rows,
            "coordinator": info.coordinator,
            "nnz": info.nnz,
            "ell_width": info.ell_width,
        }
    )


def decode_partition_info(payloads: List[str]) -> List[PartitionInfo]:
    infos = [PartitionInfo(**json.loads(p)) for p in payloads]
    return sorted(infos, key=lambda i: i.rank)


def _collect_partition(pdf_iter):
    """Concatenate a task's pandas batches into one DataFrame (the reference's
    executor-side HOT LOOP 1, core.py:906-941). A failure here (fault site
    `barrier_collect`) cannot be retried in-task — the Arrow iterator is
    consumed — so it aborts the stage and recovery happens one rung up:
    fit_on_spark re-runs the whole barrier stage under the RetryPolicy."""
    import pandas as pd

    from ..reliability import fault_point

    fault_point("barrier_collect")
    pdfs = [pdf for pdf in pdf_iter]
    if not pdfs:
        # an empty barrier partition would abort the whole stage with an opaque
        # error; match the reference's actionable empty-partition message
        # (core.py:959-962)
        raise RuntimeError(
            "A barrier task received an empty partition. Repartition the input so "
            "every task holds rows (fewer hosts than rows, avoid skewed keys)."
        )
    return pd.concat(pdfs, ignore_index=True) if len(pdfs) != 1 else pdfs[0]


# Serializes the jitted fit program when multiple barrier TASKS share one
# python process — which only happens in local-mode simulation (the test
# harness runs tasks as threads); production runs one task per TPU host
# process, so the lock is uncontended there. Concurrent XLA dispatch from
# many Python threads has been observed to hang some jaxlib builds; the
# control plane (collect, allGather, init retry) stays fully concurrent.
_DEVICE_PROGRAM_LOCK = threading.Lock()


# schema of the barrier fit stage's output rows: rank 0 carries the pickled
# model attributes; EVERY rank carries its serialized observability snapshot
# (counters/gauges/histograms/spans/events captured by the task's
# worker_scope), which the driver merges into the fit report —
# `counter_totals()` on the driver is otherwise silently process-local under a
# real multi-host fit (observability/runs.py)
BARRIER_FIT_SCHEMA = "model binary, metrics binary"


def _barrier_train_udf(estimator_payload: bytes, run_id: str = None,
                       traceparent: str = None) -> Callable:
    """Build the barrier mapInPandas UDF. Runs on executors; requires pyspark.
    `run_id` is the driver FitRun's trace context (docs/design.md §6g): it
    travels inside the closure, is stamped on every task's worker scope, and
    comes back on the metrics snapshot so the driver-side merge joins each row
    to exactly one run. `traceparent` is the same run's W3C trace context
    (§6l) riding alongside, so a worker snapshot is joinable to the driver's
    causal trace plane as well."""
    import pickle

    def train_udf(pdf_iter):
        import json as _json

        import pandas as pd
        from pyspark import BarrierTaskContext

        from ..observability import span as _obs_span, worker_scope
        from ..parallel.bootstrap import init_process_group
        from ..parallel.partitioner import active_partitioner

        est = pickle.loads(estimator_payload)
        ctx = BarrierTaskContext.get()
        rank = ctx.partitionId()
        n_tasks = ctx.getTaskInfos().__len__()

        with worker_scope(rank=rank, run_id=run_id,
                          traceparent=traceparent) as wscope:
            attrs = _barrier_task_body(
                est, ctx, rank, n_tasks, pdf_iter, init_process_group,
                active_partitioner, _obs_span,
            )
        # every rank yields exactly one row: rank 0 the model payload, everyone
        # their metrics snapshot. A None in the binary `model` column is a null
        # to Arrow — unlike the empty-DataFrame-against-a-schema case, which is
        # a type-inference crash (the pre-observability rank!=0 behavior was to
        # yield nothing at all for that reason).
        yield pd.DataFrame(
            {
                "model": [pickle.dumps(attrs) if rank == 0 else None],
                "metrics": [_json.dumps(wscope.snapshot()).encode()],
            }
        )

    return train_udf


def _features_nbytes(features: Any) -> Any:
    """Best-effort byte size of a task's ingested feature block (dense ndarray,
    scipy sparse, or pandas) for the per-rank skew record — None when nothing
    exposes a size."""
    nb = getattr(features, "nbytes", None)
    if nb is not None:
        return int(nb)
    data_nb = getattr(getattr(features, "data", None), "nbytes", None)
    if data_nb is not None:  # scipy sparse: data + indices
        idx_nb = getattr(getattr(features, "indices", None), "nbytes", 0)
        return int(data_nb) + int(idx_nb or 0)
    try:
        return int(features.memory_usage(index=False, deep=False).sum())
    except (AttributeError, TypeError, ValueError):
        return None


def _barrier_task_body(est, ctx, rank, n_tasks, pdf_iter, init_process_group,
                       active_partitioner, _obs_span):
    """One barrier task's work, returning the fit-attribute dict (meaningful on
    rank 0). Split from the generator so the task's worker_scope closes — with a
    complete metrics snapshot — before any output row is yielded."""
    import time as _time

    from ..observability import note_rank_phase

    # column resolution/casting goes through the SAME prep as the local path
    # (_use_label gate, float32 handling, idCol — core/estimator.py)
    t_collect = _time.perf_counter()
    with _obs_span("barrier.collect", {"rank": rank}):
        fd = est._pre_process_data(_collect_partition(pdf_iter))
    # per-rank skew material (§6h): this task's ingest wall/rows/bytes travel
    # on the worker-scope snapshot; the driver merge turns them into
    # comm.rank_skew{phase=} ratios, straggler events and the barrier timeline
    note_rank_phase(
        "collect", wall_s=_time.perf_counter() - t_collect,
        rows=fd.n_rows, nbytes=_features_nbytes(fd.features),
    )
    sparse_fit = est._sparse_fit_wanted(fd)
    ell_vals = ell_idx = None
    if sparse_fit:
        from ..ops.sparse import csr_to_ell

        ell_vals, ell_idx = csr_to_ell(fd.features, float32=est._float32_inputs)
    elif fd.is_sparse:
        # no sparse kernel for this estimator: densify locally as usual
        from ..core.dataset import densify

        fd.features = densify(fd.features, est._float32_inputs)

    # control plane: coordinator + partition sizes in one allGather round,
    # then a status round after init so every rank agrees on the outcome.
    # rank 0's reachable address comes from Spark's own task info (hostname
    # resolution can map to loopback). The ephemeral port is probed, closed,
    # and only later bound by init_process_group — a TOCTOU window a
    # concurrent job can race. Losing the race is no longer fatal: the loop
    # re-probes a FRESH port and re-gathers under the RetryPolicy, so a
    # stolen port costs one round instead of the whole barrier stage.
    from .. import profiling
    from ..parallel.bootstrap import reset_process_group
    from ..reliability import RetryPolicy, fault_point

    policy = RetryPolicy.from_config()
    failures = 0
    init_t0 = _time.monotonic()
    while True:
        coordinator = ""
        if rank == 0:
            import socket

            host = ctx.getTaskInfos()[0].address.split(":")[0]
            probe = socket.socket()
            probe.bind(("", 0))
            port = probe.getsockname()[1]
            probe.close()
            coordinator = f"{host}:{port}"
        fault_point("barrier_allgather", batch=failures)
        payloads = ctx.allGather(
            encode_partition_info(
                PartitionInfo(
                    rank,
                    fd.n_rows,
                    coordinator,
                    nnz=int(fd.features.nnz) if sparse_fit else -1,
                    ell_width=int(ell_vals.shape[1]) if sparse_fit else 0,
                )
            )
        )
        infos = decode_partition_info(payloads)
        err = ""
        try:
            fault_point("barrier_init", batch=failures)
            init_process_group(
                coordinator_address=next(
                    i.coordinator for i in infos if i.coordinator
                ),
                num_processes=n_tasks,
                process_id=rank,
            )
        except Exception as e:
            err = f"rank {rank}: {type(e).__name__}: {e}"
        # status round: the outcome list is identical on every rank, so all
        # ranks take the same retry-or-proceed branch (no split-brain). The
        # deadline check uses the MAX gathered elapsed for the same reason —
        # per-rank clocks differ (partition collect times vary) and a
        # rank-local decision could strand peers in the next allGather.
        statuses = [
            json.loads(s)
            for s in ctx.allGather(
                json.dumps(
                    {"err": err, "elapsed": _time.monotonic() - init_t0}
                )
            )
        ]
        errors = [s["err"] for s in statuses if s["err"]]
        if not errors:
            break
        failures += 1
        shared_elapsed = max(s["elapsed"] for s in statuses)
        if policy.give_up(failures, shared_elapsed, "barrier_init"):
            raise RuntimeError(
                "jax.distributed process-group init failed after "
                f"{failures} attempt(s): " + "; ".join(errors)
            )
        profiling.count("reliability.retry")
        profiling.count("reliability.retry.barrier_init")
        from ..observability import event as _obs_event

        _obs_event(
            "retry", site="barrier_init", attempt=failures,
            errors=len(errors),
        )
        reset_process_group()  # drop any partial link before re-probing
        policy.sleep(failures, "barrier_init")

    # global mesh over the pod, owned by the active Partitioner; every host
    # pads its rows to the common local size (XLA needs equal shards), real
    # rows marked by the weight vector. shard_inputs stages ONLY this
    # process's local rows (make_array_from_process_local_data) — no host
    # ever gathers a global array.
    part = active_partitioner()
    mesh = part.mesh

    max_rows = max(i.n_rows for i in infos)
    pad_to = part.local_pad_rows(max_rows)
    w_local = np.zeros((pad_to,), np.float32)
    w_local[: fd.n_rows] = 1.0 if fd.weight is None else fd.weight
    total_rows = sum(i.n_rows for i in infos)

    label_local = None
    if fd.label is not None:
        label_local = np.zeros((pad_to,), np.float32)
        label_local[: fd.n_rows] = fd.label

    if sparse_fit:
        # pad the local ELL width to the GLOBAL max so every host contributes
        # equally-shaped shards, then assemble the global sparse arrays
        r_global = max(i.ell_width for i in infos)
        v_local = np.zeros((pad_to, r_global), ell_vals.dtype)
        i_local = np.zeros((pad_to, r_global), ell_idx.dtype)
        v_local[: fd.n_rows, : ell_vals.shape[1]] = ell_vals
        i_local[: fd.n_rows, : ell_idx.shape[1]] = ell_idx
        w_global, label_global, values_global, indices_global = part.shard_inputs(
            w_local, label_local, v_local, i_local, site="fit"
        )
        fit_inputs = est._build_sparse_fit_inputs_from_global(
            values_global, indices_global, w_global, label_global, total_rows,
            fd.n_cols, mesh,
            rank_rows=[i.n_rows for i in infos],
            nnz=sum(i.nnz for i in infos if i.nnz > 0),
            unit_weight=fd.weight is None,
        )
    else:
        X_local = np.zeros((pad_to, fd.n_cols), np.float32)
        X_local[: fd.n_rows] = np.asarray(fd.features, dtype=np.float32)
        w_global, label_global, X_global = part.shard_inputs(
            w_local, label_local, X_local, site="fit"
        )
        fit_inputs = est._build_fit_inputs_from_global(
            X_global, w_global, label_global, total_rows, mesh,
            rank_rows=[i.n_rows for i in infos],
            unit_weight=fd.weight is None,
        )

    # run the estimator's fit program (same SPMD program on every host). The
    # phase timer starts AFTER the lock, like the span: the lock only exists
    # for the threaded local-mode harness, and queue-position wait there is
    # not rank work — timing it would flag the last-scheduled rank of a
    # healthy fit as a straggler. The straggler injection site fires INSIDE
    # the timed window (batch = RANK), so a spec like
    # `barrier_rank:batch=3:sleep=0.5` drags exactly one chosen rank and the
    # delay lands in that rank's fit_program wall alone (§6h)
    with _DEVICE_PROGRAM_LOCK:
        t_fit = _time.perf_counter()
        fault_point("barrier_rank", batch=rank)
        with _obs_span("barrier.fit_program", {"rank": rank}):
            attrs = est._get_tpu_fit_func(None)(fit_inputs)
        note_rank_phase(
            "fit_program", wall_s=_time.perf_counter() - t_fit, rows=fd.n_rows,
        )

    return attrs


def skip_stage_level_scheduling(spark_version: str, conf: Any) -> bool:
    """Decision matrix for the stage-level-scheduling analog (P7) — mirrors the
    reference's gating (reference core.py:637-696) with TPU resource names: the goal
    is that each TRAINING barrier task pins a whole TPU host while ETL stages share
    executors freely. Returns True when stage-level scheduling must be skipped.

    `conf` needs only a .get(key, default=None) -> Optional[str] surface."""
    logger = get_logger("spark.integration")

    def _get(key: str):
        try:
            return conf.get(key, None)
        except TypeError:
            return conf.get(key)

    if spark_version < "3.4.0":
        logger.info("stage-level scheduling requires spark 3.4.0+")
        return True
    master = _get("spark.master") or ""
    if "3.4.0" <= spark_version < "3.5.1" and not (
        master.startswith("spark://") or master.startswith("local-cluster")
    ):
        logger.info(
            "spark %s requires standalone/local-cluster mode for stage-level "
            "scheduling", spark_version,
        )
        return True
    executor_cores = _get("spark.executor.cores")
    executor_tpus = _get("spark.executor.resource.tpu.amount")
    if executor_cores is None or executor_tpus is None:
        logger.info(
            "stage-level scheduling requires spark.executor.cores and "
            "spark.executor.resource.tpu.amount to be set"
        )
        return True
    if int(executor_cores) == 1:
        logger.info("stage-level scheduling requires spark.executor.cores > 1")
        return True
    if float(executor_tpus) > 1:
        # hosts exposing >1 TPU resource slot: the operator owns the mapping
        logger.info(
            "stage-level scheduling skipped for spark.executor.resource.tpu.amount>1"
        )
        return True
    task_tpus = _get("spark.task.resource.tpu.amount")
    if task_tpus is not None and float(task_tpus) == float(executor_tpus):
        # every task would already serialize on the TPU slot
        return True
    return False


def apply_stage_level_scheduling(rdd: Any, session: Any) -> Any:
    """Attach a ResourceProfile that makes each training task claim >half the
    executor cores + the host's TPU resource, so barrier tasks land one-per-host
    (reference _try_stage_level_scheduling, core.py:697-740). No-op in local mode or
    when the decision matrix says skip."""
    logger = get_logger("spark.integration")
    sc = session.sparkContext
    master = sc.getConf().get("spark.master") or ""
    if master.startswith("local") and not master.startswith("local-cluster"):
        return rdd
    if skip_stage_level_scheduling(session.version, sc.getConf()):
        return rdd

    from pyspark.resource.profile import ResourceProfileBuilder
    from pyspark.resource.requests import TaskResourceRequests

    executor_cores = int(sc.getConf().get("spark.executor.cores"))
    # >half the executor cores forces one training task per executor (the TPU host);
    # the tpu resource request keeps ETL tasks off the chips during training
    task_cores = executor_cores // 2 + 1
    treqs = TaskResourceRequests().cpus(task_cores).resource("tpu", 1.0)
    rp = ResourceProfileBuilder().require(treqs).build
    logger.info(
        "training tasks pinned with ResourceProfile(cores=%d, tpu=1.0)", task_cores
    )
    return rdd.withResources(rp)


def _merge_worker_metrics(rows: Any) -> None:
    """Driver-side aggregation: fold each barrier worker's serialized metrics
    snapshot into the active FitRun (per-worker breakdown + merged totals) and
    into the process-global registry for FOREIGN-process snapshots — on a real
    multi-host fit the executors' counters never touched the driver, which is
    exactly why driver `counter_totals()` used to under-report. Same-process
    snapshots (the threaded local-mode harness) already flowed through the live
    fan-out and are recorded for the breakdown only (observability/runs.py)."""
    from ..observability import PROCESS_TOKEN, current_run, find_run, global_registry

    logger = get_logger("spark.integration")
    fallback_run = current_run()
    for r in rows:
        try:
            blob = r["metrics"]
        except (KeyError, IndexError, TypeError):
            continue  # a foreign/legacy row shape carries no snapshot
        if blob is None:
            continue
        try:
            snap = json.loads(bytes(blob).decode())
            # trace-context join (§6g): a stamped snapshot goes to ITS run;
            # legacy/unstamped snapshots keep the current-run fallback
            run = find_run(snap.get("run_id") or "") or fallback_run
            if run is not None:
                run.add_worker_snapshot(snap)
            elif snap.get("process") != PROCESS_TOKEN:
                global_registry().merge_snapshot(snap.get("metrics") or {})
        except Exception as e:
            # a mis-shaped/version-skewed snapshot (bad JSON, missing keys, a
            # kind conflict with the driver registry) must never fail a fit
            # whose expensive barrier stage already SUCCEEDED — log and move on
            logger.warning(
                "skipping unusable worker metrics snapshot (%s: %s)",
                type(e).__name__, e,
            )


def fit_on_spark(estimator: Any, spark_df: Any, num_hosts: int) -> Any:
    """Driver-side: run a TPU estimator's fit as barrier tasks on a Spark cluster.

    `num_hosts` is the number of TPU HOSTS (== barrier tasks == jax processes), NOT
    the chip count: each host process owns all its local chips and the global mesh
    spans num_hosts × local_device_count devices (SURVEY.md §7's worker=host
    topology). Requires pyspark."""
    import pickle

    from ..reliability import RetryPolicy, is_stage_retryable

    if num_hosts < 1:
        raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
    logger = get_logger("spark.integration")
    df = spark_df.repartition(num_hosts)
    # trace context: the open FitRun's id rides the UDF closure so every worker
    # snapshot comes back stamped with it (§6g)
    from ..observability import current_run

    run = current_run()
    udf = _barrier_train_udf(
        pickle.dumps(estimator),
        run_id=run.run_id if run is not None else None,
        traceparent=getattr(run, "traceparent", None),
    )
    rdd = df.mapInPandas(udf, schema=BARRIER_FIT_SCHEMA).rdd
    try:
        rdd = apply_stage_level_scheduling(rdd, spark_df.sparkSession)
    except Exception:  # pragma: no cover — never fail a fit over scheduling sugar
        logger.warning("stage-level scheduling unavailable; continuing without")
    barrier_rdd = rdd.barrier().mapPartitions(lambda it: it)
    # whole-stage retry: a dropped barrier task / preempted host fails the stage
    # as one unit (Spark's own barrier semantics), so recovery re-runs the stage
    # under the RetryPolicy; param/programming errors propagate immediately.
    # Exhaustion raises — the caller (core/estimator.py::_fit) owns the next
    # rung of the degradation ladder (collect mode).
    rows = RetryPolicy.from_config().run(
        barrier_rdd.collect, site="barrier_stage", retryable=is_stage_retryable
    )
    payload = next(r["model"] for r in rows if r["model"] is not None)
    attrs = pickle.loads(bytes(payload))
    _merge_worker_metrics(rows)
    model = estimator._create_pyspark_model(attrs)
    model._num_workers = estimator._num_workers
    model._float32_inputs = estimator._float32_inputs
    # freshly-fit marker (same semantics as _fit_internal): training summaries
    # exist on fit() results regardless of the data plane
    model._has_training_summary = True
    estimator._copyValues(model)
    logger.info("fit_on_spark complete: %s", type(model).__name__)
    return model
