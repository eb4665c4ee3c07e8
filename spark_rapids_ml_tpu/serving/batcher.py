#
# Async dynamic micro-batcher — the request-coalescing half of the serving
# plane (docs/design.md §7).
#
# The Podracer architectures (arXiv:2104.06272) decouple request feeding from
# accelerator stepping: feed threads enqueue, the accelerator executes
# fixed-shape batched steps. This module is that split for model inference:
#
#   * HTTP handler threads (or in-process callers) `submit()` variable-size
#     requests and block on a Future;
#   * ONE dispatcher thread per served model drains the queue, closing a batch
#     when it reaches `serving.max_batch_rows` OR the oldest queued request
#     has waited `serving.max_wait_ms` (the latency/size cutoff pair);
#   * the coalesced rows are written into a REUSED per-bucket staging buffer,
#     padded to the power-of-two row bucket (padding rows replicate the last
#     real row — always a valid input, so cosine/normalization paths never see
#     a synthetic zero vector), executed ONCE through the model's predict
#     kernels, and per-request slices scatter back to the waiting futures.
#
# Because every executed shape is a bucket, the set of predict shape
# signatures is finite and pre-warmable: steady-state serving never compiles
# and the PR-4 recompile sentinel (`transform.recompile_storm`) cannot fire.
#
# Deadlines ride WITH the request (docs/design.md §7c): `submit()` takes the
# caller's absolute deadline, an already-expired request fails fast at submit,
# and a request whose deadline passes while queued is expired at batch-CLOSE
# time — never padded, dispatched, and then discarded (counted
# `serving.expired{model=}`). Backpressure is bounded and advisory: a full
# queue sheds with a `Retry-After` hint derived from the EMA drain rate
# (counted `serving.shed_total{model=}`), not a bare 429.
#
# Telemetry (all label-aware; `{model=}`, plus `{replica=}` when the batcher
# runs as a fleet replica): per-request `serving.queue_s` / `serving.total_s`
# histograms, per-batch `serving.pad_s` / `serving.execute_s`
# / `serving.batch_occupancy` (real rows / bucket rows — proof the batcher is
# actually coalescing), counters `serving.requests` / `serving.rows` /
# `serving.batches` / `serving.padded_rows` / `serving.errors` /
# `serving.bucket_hit` / `serving.bucket_miss` (pre-warmed bucket or not).
#

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import config as _config
from ..observability import tracing as _tracing
from ..observability.device import compiles_total as _compiles_total
from ..observability.device import kernel_cost as _kernel_cost
from ..observability.runs import counter_inc, observe, span
from ..reliability.faults import fault_point
from ..utils import get_logger

_logger = get_logger("serving.batcher")


class ServingError(RuntimeError):
    """Base class for request-rejection errors of the serving plane."""


class QueueFull(ServingError):
    """Backpressure: the per-model queue reached `serving.queue_depth`.
    Carries `retry_after_s` — the drain-rate-derived backoff hint the HTTP
    surface returns as a `Retry-After` header instead of a bare 429."""

    def __init__(self, msg: str, retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class RequestTooLarge(ServingError):
    """A single request exceeded `serving.max_batch_rows`."""


class DeadlineExpired(ServingError):
    """The request's client deadline passed before it could be dispatched
    (at submit, or while queued, checked at batch-close time). Deliberately
    NOT retryable: the client has already given up on the answer."""


def bucket_rows(n: int, min_rows: Optional[int] = None,
                max_rows: Optional[int] = None) -> int:
    """The power-of-two row bucket `n` pads to: smallest 2^i >= max(n,
    serving.bucket_min_rows), clamped to the bucket ceiling (the power of two
    covering serving.max_batch_rows). The bucket floor resolves HERE, at
    registration/submit time, never inside a trace."""
    if min_rows is None:
        min_rows = int(_config.get("serving.bucket_min_rows"))
    if max_rows is None:
        max_rows = int(_config.get("serving.max_batch_rows"))
    n = max(int(n), max(int(min_rows), 1))
    bucket = 1 << (n - 1).bit_length()
    return min(bucket, 1 << (max(int(max_rows), 1) - 1).bit_length())


def bucket_table(min_rows: Optional[int] = None,
                 max_rows: Optional[int] = None) -> Tuple[int, ...]:
    """Every bucket the batcher can emit under the current config — the set
    registration pre-warms one executable for."""
    lo = bucket_rows(1, min_rows, max_rows)
    hi = bucket_rows(
        int(max_rows if max_rows is not None
            else _config.get("serving.max_batch_rows")),
        min_rows, max_rows,
    )
    out = []
    b = lo
    while b <= hi:
        out.append(b)
        b *= 2
    return tuple(out)


def pad_to_bucket(X: np.ndarray, bucket: int,
                  out: Optional[np.ndarray] = None) -> np.ndarray:
    """Pad a (n, d) float32 block to (bucket, d) by replicating the LAST real
    row (any real row is a valid model input; zeros would poison cosine /
    normalization paths). With `out` given, fills the reused staging buffer
    in place — steady-state serving allocates no per-batch host memory."""
    n = int(X.shape[0])
    if out is None:
        out = np.empty((bucket, X.shape[1]), np.float32)
    out[:n] = X
    if bucket > n:
        out[n:] = out[n - 1]
    return out


class _Request:
    __slots__ = ("X", "n_rows", "future", "enqueue_ts", "deadline_ts",
                 "trace")

    def __init__(self, X: np.ndarray, deadline_ts: Optional[float] = None,
                 trace: Optional["_tracing.RequestTrace"] = None):
        self.X = X
        self.n_rows = int(X.shape[0])
        self.future: "Future[Dict[str, np.ndarray]]" = Future()
        self.enqueue_ts = time.perf_counter()
        # absolute time.perf_counter() deadline, threaded from the client's
        # predict(..., timeout=) so queue time counts against the budget
        self.deadline_ts = deadline_ts
        # the request's causal trace (docs/design.md §6l), carried by
        # reference so queue/batch/execute/scatter spans land on it
        self.trace = trace


class MicroBatcher:
    """One served model's queue + dispatcher thread. `execute` is the bound
    predict closure the registry supplies (residency pin + padded predict);
    `warm_buckets` is the registry's set of pre-warmed bucket sizes (read-only
    here, used for the bucket_hit/bucket_miss counters). `labels` overrides
    the metric label set — the serving fleet runs one MicroBatcher per
    replica with `{"model": name, "replica": str(i)}` so every series splits
    per replica while still aggregating under the model label."""

    def __init__(self, name: str, n_cols: int,
                 execute: Callable[[np.ndarray, int], Dict[str, np.ndarray]],
                 warm_buckets: Optional[set] = None,
                 labels: Optional[Dict[str, str]] = None,
                 thread_suffix: str = ""):
        self.name = name
        self.n_cols = int(n_cols)
        self._execute = execute
        self.warm_buckets = warm_buckets if warm_buckets is not None else set()
        self.labels: Dict[str, str] = (
            dict(labels) if labels is not None else {"model": name}
        )
        self._queue: "deque[_Request]" = deque()
        self._cond = threading.Condition()
        self._stop = False
        self._staging: Dict[int, np.ndarray] = {}
        # dispatcher liveness: last_beat is stamped by the dispatcher loop on
        # every wakeup, so a thread hung inside execute (or dead) goes stale
        # and the fleet's health monitor can declare it within
        # serving.heartbeat_timeout_s. Drain-rate EMA feeds Retry-After.
        self.last_beat = time.perf_counter()
        self._drain_rate: Optional[float] = None  # requests/s, EMA
        self._last_drain_ts = time.perf_counter()
        self.batches_done = 0  # execute ordinal (the serving_execute site)
        self._thread = threading.Thread(
            target=self._loop,
            name=f"srml-serving-{name}{thread_suffix}", daemon=True,
        )
        self._thread.start()

    # ------------------------------------------------------------ client side

    def submit(self, X: np.ndarray,
               deadline_ts: Optional[float] = None,
               trace: Optional["_tracing.RequestTrace"] = None
               ) -> "Future[Dict[str, np.ndarray]]":
        """Enqueue one request; the returned Future resolves to this request's
        named output arrays (exactly `n_rows` leading rows each). A request
        whose `deadline_ts` has already passed fails fast HERE — it never
        occupies a queue slot it cannot use."""
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X[None, :]
        if X.ndim != 2 or X.shape[1] != self.n_cols:
            raise ServingError(
                f"model '{self.name}' expects (n, {self.n_cols}) features; "
                f"got shape {tuple(X.shape)}"
            )
        if X.shape[0] < 1:
            raise ServingError("empty request (0 rows)")
        if X.shape[0] > int(_config.get("serving.max_batch_rows")):
            raise RequestTooLarge(
                f"request of {X.shape[0]} rows exceeds serving.max_batch_rows="
                f"{_config.get('serving.max_batch_rows')}; split it client-side"
            )
        if deadline_ts is not None and time.perf_counter() >= deadline_ts:
            counter_inc("serving.expired", 1, **self.labels)
            if trace is not None:
                trace.add_event("deadline_expired", at="submit", **self.labels)
            raise DeadlineExpired(
                f"request deadline expired before enqueue on '{self.name}'"
            )
        req = _Request(X, deadline_ts=deadline_ts, trace=trace)
        with self._cond:
            if self._stop:
                raise ServingError(f"model '{self.name}' is shutting down")
            if len(self._queue) >= int(_config.get("serving.queue_depth")):
                counter_inc("serving.rejected", 1, **self.labels)
                counter_inc("serving.shed_total", 1, **self.labels)
                raise QueueFull(
                    f"model '{self.name}' queue is full "
                    f"(serving.queue_depth={_config.get('serving.queue_depth')})",
                    retry_after_s=self.retry_after_s(locked=True),
                )
            self._queue.append(req)
            self._cond.notify()
        return req.future

    def pending(self) -> int:
        with self._cond:
            return len(self._queue)

    def heartbeat_age_s(self) -> float:
        """Seconds since the dispatcher loop last proved it was making
        progress — the fleet health monitor's staleness signal."""
        return time.perf_counter() - self.last_beat

    def alive(self) -> bool:
        return self._thread.is_alive() and not self._stop

    def drain_rate(self) -> Optional[float]:
        """EMA requests/second the dispatcher is completing (None until the
        first batch lands)."""
        return self._drain_rate

    def retry_after_s(self, locked: bool = False) -> float:
        """How long a shed client should wait before retrying: current queue
        depth over the EMA drain rate, clamped to a sane [0.05s, 30s] band.
        With no drain history yet, one latency-cutoff interval is the best
        available guess."""
        if locked:
            depth = len(self._queue)
        else:
            with self._cond:
                depth = len(self._queue)
        rate = self._drain_rate
        if not rate or rate <= 0:
            return max(float(_config.get("serving.max_wait_ms")) / 1000.0, 0.05)
        return float(min(max(depth / rate, 0.05), 30.0))

    def steal_pending(self) -> List[_Request]:
        """Pop every still-queued request. The fleet's failover path calls
        this on a replica declared DEAD so the stranded requests can be
        replayed onto surviving replicas instead of rotting in a queue no
        dispatcher will ever drain."""
        with self._cond:
            out = list(self._queue)
            self._queue.clear()
        return out

    def stop(self, timeout: float = 10.0) -> None:
        """Stop accepting requests, drain what is queued, join the thread."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout)

    # -------------------------------------------------------- dispatcher side

    def _loop(self) -> None:
        while True:
            self.last_beat = time.perf_counter()
            with self._cond:
                while not self._queue and not self._stop:
                    self._cond.wait(0.05)
                    self.last_beat = time.perf_counter()
                if not self._queue and self._stop:
                    return
                first = self._queue.popleft()
            self._run_batch(self._coalesce(first))

    def _coalesce(self, first: _Request) -> List[_Request]:
        """Drain until size or latency cutoff: the batch closes at
        max_batch_rows, or when the FIRST (oldest) request has waited
        max_wait_ms — later arrivals never extend the oldest request's wait."""
        batch = [first]
        rows = first.n_rows
        max_rows = int(_config.get("serving.max_batch_rows"))
        deadline = first.enqueue_ts + (
            float(_config.get("serving.max_wait_ms")) / 1000.0
        )
        while rows < max_rows:
            with self._cond:
                if self._queue and rows + self._queue[0].n_rows <= max_rows:
                    nxt = self._queue.popleft()
                    batch.append(nxt)
                    rows += nxt.n_rows
                    continue
                if self._queue:
                    break  # next request would overflow: close this batch
                remaining = deadline - time.perf_counter()
                if remaining <= 0 or self._stop:
                    break
                self._cond.wait(min(remaining, 0.05))
        return batch

    def _note_drain(self, n: int) -> None:
        """Fold `n` completed requests into the drain-rate EMA (dispatcher
        thread only; readers tolerate a torn float)."""
        now = time.perf_counter()
        dt = now - self._last_drain_ts
        self._last_drain_ts = now
        if dt <= 0:
            return
        inst = n / dt
        self._drain_rate = (
            inst if self._drain_rate is None
            else 0.8 * self._drain_rate + 0.2 * inst
        )

    def _expire_overdue(self, batch: List[_Request]) -> List[_Request]:
        """Batch-close deadline check: fail every request whose client
        deadline has already passed (the answer would be discarded anyway)
        and return the still-live remainder — expired rows are never padded
        or dispatched."""
        now = time.perf_counter()
        live: List[_Request] = []
        for r in batch:
            if r.deadline_ts is not None and now >= r.deadline_ts:
                counter_inc("serving.expired", 1, **self.labels)
                if r.trace is not None:
                    r.trace.add_span("serving.queue", r.enqueue_ts, now,
                                 parent_id=r.trace.root_span_id,
                                 attrs=dict(self.labels), status="expired")
                    r.trace.add_event("deadline_expired", at="batch_close",
                                      **self.labels)
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(DeadlineExpired(
                        f"request deadline expired after "
                        f"{now - r.enqueue_ts:.3f}s in '{self.name}' queue"
                    ))
            else:
                live.append(r)
        return live

    def _trace_batch(self, traced: List[_Request], fan_in: List[Dict],
                     batch_sid: str, exec_sid: str, bnode: Any,
                     compiles0: int, anno: Dict[str, Any],
                     n: int, bucket: int,
                     t_start: float, t_padded: float, t_done: float) -> None:
        """Append the shared batch + execute spans to every member trace.
        The batch span is the fan-in point (links -> each member's root); the
        execute child joins the §6f kernel layer: executable signature,
        compile-vs-cached verdict, analyzed flops/bytes from the device plane
        attribution that landed on the `serving.batch` SpanNode."""
        batch_attrs: Dict[str, Any] = {
            "rows": n, "bucket": bucket,
            "occupancy": round(n / bucket, 6), **self.labels,
        }
        if anno:
            batch_attrs.update(anno)
        exec_attrs: Dict[str, Any] = {
            "compiled": _compiles_total() - compiles0,
        }
        dev = (bnode.attrs or {}).get("device") if bnode is not None else None
        if dev:
            for k in ("flops", "bytes", "comm_bytes", "calls"):
                if dev.get(k) is not None:
                    exec_attrs[k] = dev[k]
            kernels = dev.get("kernels") or {}
            if kernels:
                sigs = {}
                for kname in kernels:
                    rec = _kernel_cost(kname)
                    if rec is not None and rec.get("signature"):
                        sigs[kname] = rec["signature"]
                exec_attrs["kernels"] = dict(kernels)
                if sigs:
                    exec_attrs["signatures"] = sigs
        for r in traced:
            r.trace.add_span("serving.batch", t_start, t_done,
                         parent_id=r.trace.root_span_id,
                         attrs=batch_attrs, links=fan_in, span_id=batch_sid)
            r.trace.add_span("serving.execute", t_padded, t_done,
                         parent_id=batch_sid, attrs=exec_attrs,
                         span_id=exec_sid)
            if anno.get("generation") is not None:
                r.trace.add_event("model_generation",
                                  generation=anno["generation"],
                                  **self.labels)

    def _run_batch(self, batch: List[_Request]) -> None:
        n_closed = len(batch)
        batch = self._expire_overdue(batch)
        if not batch:
            self._note_drain(n_closed)
            return
        t_start = time.perf_counter()
        self.last_beat = t_start
        n = sum(r.n_rows for r in batch)
        for r in batch:
            observe("serving.queue_s", t_start - r.enqueue_ts, **self.labels)
        bucket = bucket_rows(n)
        # trace plumbing (§6l): members carrying a RequestTrace get a queue
        # span now; the micro-batch itself becomes ONE shared span (same
        # span_id across every member trace) with fan-in links to the N
        # request roots it coalesced — that link set is what attributes
        # padding/occupancy cost per request
        traced = [r for r in batch if r.trace is not None]
        batch_sid = _tracing.mint_span_id() if traced else None
        exec_sid = _tracing.mint_span_id() if traced else None
        fan_in = [
            {"trace_id": r.trace.trace_id, "span_id": r.trace.root_span_id}
            for r in traced
        ]
        for r in traced:
            # labels dict is frozen for the batcher's lifetime, so it is safe
            # to capture by reference (document() copies at export)
            r.trace.add_span("serving.queue", r.enqueue_ts, t_start,
                         parent_id=r.trace.root_span_id,
                         attrs=self.labels)
        compiles0 = _compiles_total() if traced else 0
        try:
            # the mid-batch failure site: an injected raise here fails exactly
            # this batch's futures (retryably, for OSError-class faults) and
            # the dispatcher loop carries on — the queue must never stall
            b_ord = self.batches_done
            self.batches_done = b_ord + 1
            fault_point("serving_execute", batch=b_ord)
            stage = self._staging.get(bucket)
            if stage is None:
                stage = self._staging[bucket] = np.empty(
                    (bucket, self.n_cols), np.float32
                )
            off = 0
            for r in batch:
                stage[off: off + r.n_rows] = r.X
                off += r.n_rows
            if bucket > n:
                stage[n:] = stage[n - 1]
            t_padded = time.perf_counter()
            observe("serving.pad_s", t_padded - t_start, **self.labels)
            counter_inc("serving.padded_rows", bucket - n, **self.labels)
            counter_inc(
                "serving.bucket_hit" if bucket in self.warm_buckets
                else "serving.bucket_miss", 1, **self.labels,
            )
            with span("serving.batch",
                      {"rows": n, "bucket": bucket, **self.labels}) as bnode:
                outputs = self._execute(stage, n)
            t_done = time.perf_counter()
            observe("serving.execute_s", t_done - t_padded, **self.labels)
            observe("serving.batch_occupancy", n / bucket, **self.labels)
        except Exception as e:
            counter_inc("serving.errors", 1, **self.labels)
            _logger.warning("serving batch failed for %s: %s", self.name, e)
            t_err = time.perf_counter()
            _tracing.take_batch_annotations()  # don't leak onto a later batch
            for r in traced:
                r.trace.add_event("error", kind_detail=type(e).__name__,
                                  **self.labels)
                r.trace.add_span("serving.batch", t_start, t_err,
                             parent_id=r.trace.root_span_id,
                             attrs={"rows": n, "bucket": bucket,
                                    **self.labels},
                             links=fan_in, status="error",
                             span_id=batch_sid)
            for r in batch:
                if not r.future.set_running_or_notify_cancel():
                    continue
                r.future.set_exception(e)
            self._note_drain(n_closed)
            return
        anno = _tracing.take_batch_annotations()  # drained every batch
        if traced:
            self._trace_batch(traced, fan_in, batch_sid, exec_sid, bnode,
                              compiles0, anno, n, bucket,
                              t_start, t_padded, t_done)
        # scatter per-request slices back to the waiting futures: exact row
        # counts, no cross-request bleed (sliced COPIES so one request's
        # result does not keep the whole bucket's outputs alive)
        off = 0
        now = time.perf_counter()
        for r in batch:
            out_r: Dict[str, Any] = {}
            for key, v in outputs.items():
                arr = np.asarray(v)
                if arr.ndim >= 1 and arr.shape[0] == bucket:
                    out_r[key] = arr[off: off + r.n_rows].copy()
                else:  # per-model scalars/metadata ride along unsliced
                    out_r[key] = arr
            off += r.n_rows
            if r.trace is not None:
                # srml-metric: serving.scatter — trace span family (§6l)
                r.trace.add_span("serving.scatter", t_done, now,
                             parent_id=r.trace.root_span_id,
                             attrs={"rows": r.n_rows, **self.labels})
            if r.future.set_running_or_notify_cancel():
                r.future.set_result(out_r)
            total_s = now - r.enqueue_ts
            # exemplar iff the pointed-at trace will survive tail sampling —
            # a /metrics exemplar must resolve at /traces/<id>
            ex = (
                r.trace.trace_id
                if r.trace is not None and _tracing.would_keep(r.trace,
                                                               total_s)
                else None
            )
            observe("serving.total_s", total_s, exemplar=ex, **self.labels)
        counter_inc("serving.batches", 1, **self.labels)
        counter_inc("serving.requests", len(batch), **self.labels)
        counter_inc("serving.rows", n, **self.labels)
        self._note_drain(n_closed)
