#
# Inference-plane observability: TransformRun scopes, the instrumented predict
# dispatch every model family routes through, and shape-bucket telemetry with a
# recompile sentinel (docs/design.md §6e).
#
# PR 3 lit the fit plane; the serving path stayed dark. Three things live here:
#
#   * TransformRun — the transform-plane mirror of FitRun (observability/
#     runs.py): a scoped registry delta + trace tree + event log around one
#     user-level `.transform()` call, exported to `transform_reports.jsonl`.
#     The per-partition metrics of the distributed plane (spark/transform.py)
#     are delivered as worker snapshots and fold in through the same
#     process-aware merge the barrier fit plane uses.
#
#   * predict_dispatch — one choke point for every model family's jitted
#     predict kernel call, so KMeans/LogReg/PCA/forest/UMAP/kNN/DBSCAN all
#     report the SAME metric names: `transform.predict_calls{model=}`,
#     `transform.predict_rows{model=}`, a `transform.predict_s{model=}`
#     latency histogram, and the shape-bucket telemetry below. The analyzer
#     flags direct jax.jit use in models/*.py that bypasses this helper.
#
#   * Shape buckets + recompile sentinel — a per-model registry of distinct
#     (rows, cols, dtype) signatures seen by the predict kernels. Each NEW
#     signature is (to XLA) a new compile: `transform.compile{model=}` counts
#     them, and once distinct signatures exceed
#     `observability.recompile_warn_threshold` every further one increments
#     `transform.recompile_storm{model=}` and lands a `recompile_storm` event —
#     the silent failure mode of un-bucketed pandas-UDF batch sizes, where every
#     ragged partition tail forces a fresh XLA compile (DrJAX, arXiv:2403.07128:
#     MapReduce-over-JAX lives or dies on compiled-program reuse).
#

from __future__ import annotations

import contextlib
import itertools
import math
import threading
import time
from typing import Any, Dict, Iterator, Mapping, Optional, Tuple

from .. import config as _config
from ..utils import get_logger
from . import runs as _runs
from .export import TRANSFORM_REPORT_FILENAME
from .runs import FitRun, counter_inc, event, observe, span

_logger = get_logger("observability.inference")


class TransformRun(FitRun):
    """One transform call's observability scope — the inference-plane mirror of
    FitRun. `algo` is the model class name; the report exports to
    `transform_reports.jsonl` and attaches to the model as
    `model.transform_report_` (the latest transform wins)."""

    kind = "transform"
    _id_prefix = "transform"
    _root_suffix = "transform_run"
    _report_filename = TRANSFORM_REPORT_FILENAME


# ------------------------------------------------------------- run scope gates

_tls = threading.local()


def _suppress_depth() -> int:
    return getattr(_tls, "suppress_depth", 0)


@contextlib.contextmanager
def suppress_transform_runs() -> Iterator[None]:
    """Mark this thread as inside an inference-plane worker (a transform UDF
    batch, the one-row schema probe): nested `model.transform()` calls keep
    writing counters/spans through the fan-out but must NOT open their own
    TransformRun — one user call, one run."""
    _tls.suppress_depth = _suppress_depth() + 1
    try:
        yield
    finally:
        _tls.suppress_depth = _suppress_depth() - 1


def _bucketed_depth() -> int:
    return getattr(_tls, "bucketed_depth", 0)


@contextlib.contextmanager
def bucketed_signatures() -> Iterator[None]:
    """Mark this thread's predict shape signatures as BUCKETED BY DESIGN (the
    serving plane's finite power-of-two bucket table, serving/batcher.py):
    each new signature still counts `transform.compile{model=}` — it IS a
    compile — but is exempt from the recompile-storm sentinel. The sentinel
    exists to catch unbounded ragged-batch signature growth; a fixed bucket
    table is the fix it recommends, and warming that table must not trip it."""
    _tls.bucketed_depth = _bucketed_depth() + 1
    try:
        yield
    finally:
        _tls.bucketed_depth = _bucketed_depth() - 1


@contextlib.contextmanager
def transform_run(algo: str, site: str = "driver") -> Iterator[Optional[TransformRun]]:
    """TransformRun gated on `observability.enabled` AND on not already being
    inside a transform worker scope on this thread (see suppress_transform_runs)."""
    if not bool(_config.get("observability.enabled")) or _suppress_depth() > 0:
        yield None
        return
    with TransformRun(algo, site=site) as run:
        yield run


# ------------------------------------------------------- sampling (latency obs)

_sample_lock = threading.Lock()
_sample_counts: Dict[str, int] = {}


def _should_sample(key: str) -> bool:
    """Deterministic rate limiter for latency observations: with
    `observability.transform_sample_rate` = r, record observation n iff
    floor(n*r) advanced — every counter still counts, only histogram pressure
    drops. r>=1 short-circuits without touching the shared counter."""
    rate = float(_config.get("observability.transform_sample_rate"))
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    with _sample_lock:
        n = _sample_counts.get(key, 0) + 1
        _sample_counts[key] = n
    return math.floor(n * rate) > math.floor((n - 1) * rate)


# ------------------------------------------- shape buckets + recompile sentinel

_shape_lock = threading.Lock()
_shape_sigs: Dict[str, set] = {}
# signatures registered under bucketed_signatures() (the serving plane's
# finite bucket table): remembered for compile dedup, EXCLUDED from the storm
# count — a served model's 9-bucket table must not push an unrelated ragged
# transform over the threshold
_bucketed_sigs: Dict[str, set] = {}
_storm_warned: set = set()

# membership cap per model: a pathological fully-ragged serving stream (every
# batch a new row count) must not grow the registry forever. At the cap every
# unseen signature still counts as a compile (it IS one) — it just stops being
# remembered, which can only over-count, never hide, a storm.
_MAX_TRACKED_SIGS = 65536


def reset_shape_buckets() -> None:
    """Clear the per-model shape-signature registry (tests / long-lived workers
    that reload models)."""
    with _shape_lock:
        _shape_sigs.clear()
        _bucketed_sigs.clear()
        _storm_warned.clear()


def shape_signatures(model_name: str) -> Tuple[Tuple[Any, ...], ...]:
    with _shape_lock:
        return tuple(sorted(_shape_sigs.get(model_name, ()), key=repr))


def _shape_signature(x: Any) -> Tuple[int, int, str]:
    """(padded_rows, cols, dtype) of a predict operand — the triple XLA keys a
    compiled program on. Rows are whatever padding the caller applied (none, for
    raw pandas-UDF batches — which is exactly what the sentinel detects)."""
    shape = getattr(x, "shape", None)
    if not shape:
        try:
            return len(x), 1, "object"
        except TypeError:
            return 1, 1, "object"
    rows = int(shape[0])
    cols = 1
    for d in shape[1:]:
        cols *= int(d)
    return rows, cols, str(getattr(x, "dtype", "object"))


def record_shape_signature(model_name: str, sig: Tuple[int, int, str]) -> bool:
    """Register one predict-call shape signature. Returns True when the
    signature is NEW for this model (== one more XLA compile of its predict
    program) and fires the recompile sentinel once the distinct count exceeds
    `observability.recompile_warn_threshold`."""
    bucketed = _bucketed_depth() > 0
    with _shape_lock:
        sigs = _shape_sigs.setdefault(model_name, set())
        if sig in sigs:
            return False
        if len(sigs) < _MAX_TRACKED_SIGS:
            sigs.add(sig)
            if bucketed:
                _bucketed_sigs.setdefault(model_name, set()).add(sig)
        # the storm judges only UN-bucketed growth: a served model's finite
        # bucket table is the sentinel's recommended fix, not evidence
        n_distinct = len(sigs) - len(_bucketed_sigs.get(model_name, ()))
    counter_inc("transform.compile", 1, model=model_name)
    if bucketed:
        return True  # bucketed by design (serving plane): no storm accounting
    threshold = int(_config.get("observability.recompile_warn_threshold"))
    if threshold > 0 and n_distinct > threshold:
        counter_inc("transform.recompile_storm", 1, model=model_name)
        event(
            "recompile_storm",
            model=model_name,
            signatures=n_distinct,
            threshold=threshold,
            rows=sig[0],
            cols=sig[1],
            dtype=sig[2],
        )
        with _shape_lock:
            first = model_name not in _storm_warned
            _storm_warned.add(model_name)
        if first:
            _logger.warning(
                "recompile storm: %s predict has seen %d distinct "
                "(rows, cols, dtype) shape signatures (> threshold %d) — "
                "un-bucketed batch sizes force one XLA compile per batch; pad "
                "batches to a fixed set of sizes or raise "
                "observability.recompile_warn_threshold.",
                model_name, n_distinct, threshold,
            )
    return True


# ------------------------------------------------------------ predict dispatch


def predict_dispatch(model: Any, kernel: Any, *args: Any,
                     shape_of: Any = None, put_query: bool = False,
                     **kwargs: Any) -> Any:
    """Run one model family's predict kernel under the inference-plane
    instrumentation. `args`/`kwargs` pass through to `kernel` untouched (but see
    `put_query`); the shape signature is read from `shape_of` when the
    query block is not the first positional (kNN ring kernels lead with the
    mesh), else from the first array-like argument.

    Reported per call, uniformly across families:
      * `transform.predict_calls{model=}` / `transform.predict_rows{model=}`
      * span `transform.predict` (lands in any open Fit/Transform run's trace)
      * histogram `transform.predict_s{model=}` (sampled via
        `observability.transform_sample_rate`)
      * shape-bucket registration + recompile sentinel (see module header)

    `put_query=True` (the families whose kernel is one jitted program over the
    whole query block: KMeans, PCA) makes the upload a phase instead of a side
    effect of the call: a numpy query block (the operand the shape signature
    is read from) is placed on the device through the partitioner's choke
    point (span `h2d.put`, `h2d.bytes{site=transform}`) and the transfer is
    waited for under `h2d.wait` before the kernel is called. Kernels that keep
    the block on the host by design (streamed kNN/DBSCAN/UMAP, forests) leave
    it off.

    `transform.predict` covers the kernel call as issued from Python; jax
    dispatch is asynchronous, so it reads dispatch (and a first call's compile),
    not the kernel. With `put_query` the upload lies before it (`h2d.put`,
    `h2d.wait`) and the kernel's run and the outputs' way back after it
    (`transform.fetch`, see `fetch`); without, the operands' transfer is part
    of the call and the per-batch `transform.batch_s` histogram (the whole
    batch including the host materialization) bounds end-to-end time.
    """
    mname = type(model).__name__
    ref = shape_of
    if ref is None:
        for a in args:
            if hasattr(a, "shape") and getattr(a, "shape", None):
                ref = a
                break
    sig = _shape_signature(ref if ref is not None else args[0] if args else None)
    record_shape_signature(mname, sig)
    counter_inc("transform.predict_calls", 1, model=mname)
    counter_inc("transform.predict_rows", sig[0], model=mname)
    if put_query:
        args = _put_query(args, ref)
    t0 = time.perf_counter()
    with span("transform.predict", {"model": mname, "rows": sig[0]}):
        out = kernel(*args, **kwargs)
    if _should_sample("predict:" + mname):
        observe("transform.predict_s", time.perf_counter() - t0, model=mname)
    return out


def _put_query(args: Tuple[Any, ...], query: Any) -> Tuple[Any, ...]:
    """`args` with the host query block placed on the device (`h2d.put`) and
    resident (`h2d.wait`): on the default device, or beside the operands
    already committed to one (the serving plane's HBM-resident weights). The
    small weight operands stay arguments of the call."""
    import jax
    import numpy as np

    from ..parallel.partitioner import put_device_local

    if not isinstance(query, np.ndarray):
        return args
    device = None
    for a in args:
        if isinstance(a, jax.Array) and a.committed and len(a.devices()) == 1:
            (device,) = a.devices()
            break
    placed = put_device_local(query, site="transform", device=device)
    with span("h2d.wait", {"site": "transform", "waits": "upload"}):
        jax.block_until_ready(placed)
    return tuple(placed if a is query else a for a in args)


def fetch(out: Any) -> Any:
    """A kernel's result as a numpy array: span `transform.fetch` covers the
    wait for the kernel and the outputs' way back to the host, and
    `d2h.bytes{site=transform}` counts what came back."""
    import numpy as np

    with span("transform.fetch"):
        host = np.asarray(out)
    counter_inc("d2h.bytes", int(host.nbytes), site="transform")
    return host


def predict_to_host(model: Any, kernel: Any, *args: Any, **kwargs: Any) -> Any:
    """`predict_dispatch` with the upload, the kernel's dispatch and the way
    back as separate phases: the host table put and waited for, then the call,
    then `fetch`. For the families whose transform is one jitted program over
    a host table (KMeansModel, PCAModel)."""
    return fetch(predict_dispatch(model, kernel, *args, put_query=True, **kwargs))


@contextlib.contextmanager
def transform_batch(model: Any, n_rows: int,
                    nbytes: Optional[int] = None) -> Iterator[None]:
    """Instrument one transform batch (a whole local `.transform()` call, or
    one pandas-UDF batch of the distributed plane — the local call IS the
    per-batch unit there, so rows/batches/latency are counted in exactly one
    place and the partition totals can never double-count)."""
    mname = type(model).__name__
    counter_inc("transform.batches", 1, model=mname)
    counter_inc("transform.rows", int(n_rows), model=mname)
    if nbytes:
        counter_inc("transform.bytes", int(nbytes), model=mname)
    t0 = time.perf_counter()
    with span("transform.batch", {"model": mname, "rows": int(n_rows)}):
        yield
    if _should_sample("batch:" + mname):
        observe("transform.batch_s", time.perf_counter() - t0, model=mname)


# ------------------------------------------- partition sidecar (spark plane)

_rank_counter = itertools.count(0)


def partition_rank() -> int:
    """Partition ordinal for a transform UDF worker scope: the real Spark
    TaskContext partition id when one exists, else a process-local ordinal (the
    eager protocol-mock plane runs partitions sequentially in-process)."""
    try:
        from pyspark import TaskContext  # type: ignore

        tc = TaskContext.get()
        if tc is not None:
            return int(tc.partitionId())
    except Exception:  # noqa: fence/silent-except — pyspark absent or stubbed
        pass
    return next(_rank_counter)


def deliver_partition_snapshot(run_id: Optional[str], driver_token: str,
                               snapshot: Mapping[str, Any],
                               metrics_dir: Optional[str] = None) -> bool:
    """Hand one transform partition's worker-scope snapshot back to its run.

    * Driver-side run still open in THIS process (the eager local-mode plane):
      fold it in via the process-aware merge — same-process snapshots record
      the per-partition breakdown only (their writes already fanned out live),
      foreign ones merge into the run (spark/integration.py semantics).
    * Run not reachable (real lazy cluster: partitions execute after the
      driver's run closed, usually in another process): append the snapshot to
      `<metrics_dir>/transform_partials.jsonl` tagged with the run id — the
      durable half of the sidecar; `load_transform_partials` reads it back.
      The worker's writes already landed in its process-global registry, so
      nothing is merged twice here.
    Returns True when the snapshot reached a live run."""
    if run_id is None:
        return False
    run = _runs.find_run(run_id)
    if run is not None:
        run.add_worker_snapshot(snapshot)
        return True
    if metrics_dir:
        from .export import append_transform_partial

        try:
            append_transform_partial(
                dict(snapshot, run_id=run_id, driver=driver_token), metrics_dir
            )
        except OSError as e:
            _logger.warning("could not write transform partial: %s", e)
    return False
