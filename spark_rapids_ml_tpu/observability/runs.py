#
# Per-fit run scopes, trace trees, and the fan-out write path — the collection
# half of the observability subsystem (docs/design.md §6d).
#
# Write path: every instrumentation call (`counter_inc`, `gauge_*`, `observe`,
# `add_span_total`, `span`, `event`) fans out to every active SINK:
#
#   * the process-global registry    — always; backs profiling.counter_totals()
#   * each open FitRun's registry    — process-global scope: barrier tasks run
#     as THREADS in the local-mode fit plane, and their metrics belong to the
#     driver thread's run
#   * this thread's worker_scope()   — thread-local: one barrier task's private
#     delta, serialized to the driver alongside the fit result
#
# A FitRun additionally collects a structured TRACE TREE (parent/child span
# nodes from the thread-local span stack) and an EVENT LOG (retries, fault
# firings, cache evictions, degradations) instead of the flat name-keyed sums
# profiling.py kept — arXiv:1612.01437's point that per-stage attribution, not
# end-to-end wall clock, is what localizes distributed-fit bottlenecks.
#
# Process identity: each snapshot carries (pid, boot token). The driver merges
# a worker snapshot into its own registries ONLY when the identity differs —
# in the threaded local-mode harness the worker already wrote through the
# fan-out path and a second merge would double-count; under a real multi-host
# fit the executor's counters never touched the driver process and the merge
# is exactly the fix for counter_totals() being silently process-local.
#

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, Iterator, List, Mapping, Optional, Sequence

try:
    import resource as _resource
except ImportError:  # pragma: no cover — a platform without it: no host usage
    _resource = None  # type: ignore[assignment]

from .. import config as _config
from ..utils import get_logger
from .registry import DEFAULT_TIME_BUCKETS, MetricsRegistry

_logger = get_logger("observability")

# identity of THIS process's metric stream (pid alone collides across hosts)
PROCESS_TOKEN = f"{os.getpid()}:{uuid.uuid4().hex[:12]}"

_GLOBAL = MetricsRegistry()

_span_ids = itertools.count(1)
_run_ids = itertools.count(1)

_state_lock = threading.RLock()
_active_runs: List["FitRun"] = []

_tls = threading.local()


def global_registry() -> MetricsRegistry:
    return _GLOBAL


_device_mod = None


def _device():
    """Lazy device-plane import (observability/device.py imports THIS module at
    its top; the reverse edge must resolve at call time)."""
    global _device_mod
    if _device_mod is None:
        from . import device as dev

        _device_mod = dev
    return _device_mod


_flight_mod = None


def _flight():
    """Lazy flight-recorder import (observability/flight.py imports this module
    inside dump_postmortem; same cycle-breaking as _device)."""
    global _flight_mod
    if _flight_mod is None:
        from . import flight as fl

        _flight_mod = fl
    return _flight_mod


_server_mod = None


def _server():
    """Lazy telemetry-server import (observability/server.py reads run state
    from this module at request time)."""
    global _server_mod
    if _server_mod is None:
        from . import server as srv

        _server_mod = srv
    return _server_mod


_comm_mod = None


def _comm():
    """Lazy communication-plane import (observability/comm.py, §6h: rank-skew
    gauges + straggler events on worker-snapshot merge; same cycle-breaking
    as _device)."""
    global _comm_mod
    if _comm_mod is None:
        from . import comm as cm

        _comm_mod = cm
    return _comm_mod


def _worker_scopes() -> List["WorkerScope"]:
    scopes = getattr(_tls, "worker_scopes", None)
    if scopes is None:
        scopes = _tls.worker_scopes = []
    return scopes


def _span_stack() -> List["SpanNode"]:
    stack = getattr(_tls, "span_stack", None)
    if stack is None:
        stack = _tls.span_stack = []
    return stack


def _sink_registries() -> List[MetricsRegistry]:
    regs = [_GLOBAL]
    with _state_lock:
        regs.extend(run.registry for run in _active_runs)
    regs.extend(scope.registry for scope in _worker_scopes())
    return regs


# --------------------------------------------------------------- write fan-out


def counter_inc(name: str, n: int = 1, **labels: Any) -> None:
    # fast path: outside any fit run / worker scope (the serving loop's
    # steady state) there is exactly one sink, so skip the fan-out list
    # build and its lock. The unlocked emptiness reads are GIL-atomic; a
    # racing run-open at worst misses one best-effort increment.
    if not _active_runs and not getattr(_tls, "worker_scopes", None):
        _GLOBAL.counter(name).inc(n, **labels)
        return
    for reg in _sink_registries():
        reg.counter(name).inc(n, **labels)


def legacy_count(name: str, n: int) -> None:
    """Signed fan-out for the legacy profiling.count() surface (see
    MetricsRegistry.legacy_count): kind is discovered from usage per sink."""
    for reg in _sink_registries():
        reg.legacy_count(name, n)


def gauge_set(name: str, value: Any, **labels: Any) -> None:
    for reg in _sink_registries():
        reg.gauge(name).set(value, **labels)


def gauge_inc(name: str, n: Any = 1, **labels: Any) -> None:
    for reg in _sink_registries():
        reg.gauge(name).inc(n, **labels)


def gauge_dec(name: str, n: Any = 1, **labels: Any) -> None:
    gauge_inc(name, -n, **labels)


def observe(name: str, value: float,
            buckets: Sequence[float] = DEFAULT_TIME_BUCKETS,
            exemplar: Any = None, **labels: Any) -> None:
    for reg in _sink_registries():
        reg.histogram(name, buckets=buckets).observe(
            value, exemplar=exemplar, **labels)


def add_span_total(name: str, seconds: float) -> None:
    """Flat accumulation of seconds under a span name PLUS a same-named
    exponential latency histogram: a distribution, not just a sum."""
    for reg in _sink_registries():
        reg.add_span_total(name, seconds)
        reg.histogram(name).observe(seconds)


def event(kind: str, **fields: Any) -> None:
    """Append a structured event (retry, fault, cache_evict, degrade, ...) to
    every open FitRun, this thread's worker scopes, AND the process flight
    recorder (observability/flight.py) — the ring buffer is exactly the place
    an event fired outside any run context still matters (postmortems)."""
    with _state_lock:
        targets: List[Any] = list(_active_runs)
    targets.extend(_worker_scopes())
    fl = _flight()
    if not targets and not fl.enabled():
        return  # no sink anywhere: skip building the entry entirely
    stack = _span_stack()
    entry = {
        "ts": round(time.time(), 6),
        "kind": kind,
        "span_id": stack[-1].span_id if stack else None,
        **fields,
    }
    for t in targets:
        t.add_event(entry)
    fl.note_event(entry)


# ------------------------------------------------------ progress & convergence


def progress(phase: str, done: Any, total: Any = None,
             unit: str = "units") -> None:
    """Publish live fit progress: gauges `fit.progress{phase=}` /
    `fit.progress_total{phase=}` / `fit.eta_s{phase=}` through the normal
    fan-out (global registry + open runs + worker scopes), plus a structured
    per-phase record on every open run (EMA-rate ETA) that /runs/<id> serves
    mid-fit (observability/server.py). Streamed-fit loops call this per pass
    and per batch (ops/streaming.py, ops/pairwise_streaming.py)."""
    done = int(done)
    gauge_set("fit.progress", done, phase=phase)
    if total is not None:
        gauge_set("fit.progress_total", int(total), phase=phase)
    with _state_lock:
        runs = list(_active_runs)
    eta = None
    for run in runs:
        e = run.note_progress(phase, done, total, unit)
        if e is not None:
            eta = e  # innermost (most recently opened) run's estimate wins
    if eta is not None:
        gauge_set("fit.eta_s", round(float(eta), 3), phase=phase)


# Process-wide monotonic sequence over ALL convergence records — fit-time
# iterations and later partial_fit updates land on ONE ordered axis, so drift
# trend windows can be compared across a fit run and the continual updates
# that follow it (iteration numbers restart per fit; `seq` never does).
_conv_seq = itertools.count()


def convergence(algo: str, iteration: Any, **fields: Any) -> None:
    """Append one per-iteration convergence record (KMeans inertia + center
    shift, logreg/linreg loss + grad norm, ...) to every open run — exported in
    the report's `convergence` section and visible mid-fit via /runs/<id>.
    Numeric fields coerce to plain floats so records stay JSON-clean."""
    rec: Dict[str, Any] = {
        "ts": round(time.time(), 6),
        "seq": next(_conv_seq),
        "algo": algo,
        "iteration": int(iteration),
    }
    for k, v in fields.items():
        try:
            rec[k] = float(v)
        except (TypeError, ValueError):
            rec[k] = v
    with _state_lock:
        runs = list(_active_runs)
    for run in runs:
        run.note_convergence(rec)
    _flight().note("convergence", **{k: v for k, v in rec.items() if k != "ts"})


# ------------------------------------------------------------------ host usage

# What the host did over an interval: of a span whose attrs carry `waits`
# ("upload", "device" or "none": what the host waits for inside it) and of
# every run scope (`span=run`, `waits=run`: a value of its own, so no label
# set a reader can give sums a span and the run that holds it). The
# differences of two samples, one `getrusage(RUSAGE_SELF)` and one
# `thread_time()` each: PROCESS totals over the interval (every thread, the
# runtime's transfer threads among them) beside the calling thread's own CPU
# seconds, so process minus caller is what the other threads did. Exact
# attribution with one client; under concurrent requests read them as rates
# (docs/design.md §6d).
# srml-metric: host.cpu_seconds{span,waits,mode}
# srml-metric: host.thread_cpu_seconds{span,waits}
# srml-metric: host.page_faults{span,waits,kind}
# srml-metric: host.ctx_switches{span,waits,kind}


def _host_sample() -> Any:
    """The process's rusage and the calling thread's CPU seconds, now; None
    where the platform has no `resource` (the `waits` flag then does nothing)."""
    if _resource is None:
        return None
    return _resource.getrusage(_resource.RUSAGE_SELF), time.thread_time()


def _host_usage_add(name: str, waits: Any, before: Any) -> None:
    """Add what the host used since `before` (a `_host_sample`) to the four
    `host.*` counters, under the span's name and its `waits`. A difference is
    never negative: the kernel's split of a tick between user and system time
    may step back by one, and a counter takes no negative increment."""
    after = _host_sample()
    if before is None or after is None:
        return
    (ru0, thread0), (ru1, thread1) = before, after
    counter_inc("host.cpu_seconds", max(ru1.ru_utime - ru0.ru_utime, 0.0),
                span=name, waits=waits, mode="user")
    counter_inc("host.cpu_seconds", max(ru1.ru_stime - ru0.ru_stime, 0.0),
                span=name, waits=waits, mode="sys")
    counter_inc("host.thread_cpu_seconds", max(thread1 - thread0, 0.0),
                span=name, waits=waits)
    counter_inc("host.page_faults", max(ru1.ru_minflt - ru0.ru_minflt, 0),
                span=name, waits=waits, kind="minor")
    counter_inc("host.page_faults", max(ru1.ru_majflt - ru0.ru_majflt, 0),
                span=name, waits=waits, kind="major")
    counter_inc("host.ctx_switches", max(ru1.ru_nvcsw - ru0.ru_nvcsw, 0),
                span=name, waits=waits, kind="voluntary")
    counter_inc("host.ctx_switches", max(ru1.ru_nivcsw - ru0.ru_nivcsw, 0),
                span=name, waits=waits, kind="involuntary")


# ----------------------------------------------------------------- trace spans

# jax.profiler, resolved lazily and once: False = not yet resolved, None =
# unavailable (never retried). Resolution waits until something else has
# imported jax, so this module keeps importing (and spans keep working)
# without it.
_jax_profiler: Any = False


def _trace_annotation(name: str) -> Any:
    """The `jax.profiler.TraceAnnotation` that puts a span on the profiler's
    clock — the ONE construction site in the package. Outside a profiler
    session the annotation is a no-op costing well under a microsecond."""
    global _jax_profiler
    jp = _jax_profiler
    if jp is False:
        if "jax" not in sys.modules:
            return contextlib.nullcontext()
        try:
            import jax.profiler as jp
        except Exception:  # pragma: no cover — jax is a hard dep everywhere else
            jp = None
        _jax_profiler = jp
    if jp is None:
        return contextlib.nullcontext()
    return jp.TraceAnnotation(name)


class SpanNode:
    """One node of a run's trace tree. Identity is process-unique so nodes from
    any thread link into the same tree; parentage comes from the thread-local
    span stack (a span opened inside another ON THE SAME THREAD is its child;
    a barrier-task thread's top-level spans become roots of that task's own
    subtree in the run)."""

    __slots__ = ("span_id", "parent_id", "name", "attrs", "t0", "start_ts",
                 "duration_s", "status", "thread")

    def __init__(self, name: str, attrs: Optional[Mapping[str, Any]], parent_id):
        self.span_id = next(_span_ids)
        self.parent_id = parent_id
        self.name = name
        self.attrs = dict(attrs) if attrs else {}
        self.start_ts = time.time()
        self.t0 = time.perf_counter()
        self.duration_s: Optional[float] = None
        self.status = "ok"
        self.thread = threading.current_thread().name

    def as_dict(self) -> Dict[str, Any]:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_ts": round(self.start_ts, 6),
            "duration_s": self.duration_s,
            "status": self.status,
            "thread": self.thread,
            **({"attrs": self.attrs} if self.attrs else {}),
        }


@contextlib.contextmanager
def span(name: str, attrs: Optional[Mapping[str, Any]] = None) -> Iterator[SpanNode]:
    """The span primitive: perf_counter + thread-local parent linkage for the
    run's trace tree, span totals and latency histogram, and a same-named
    `TraceAnnotation` around the body so every span also lands in any active
    profiler session, on the device trace's clock (no jax import of its own:
    see _trace_annotation). Failure-safe by construction (try/finally): a span
    whose body raises records its elapsed time with status='error' and counts
    toward `span.errors`. A span that is a wait says so in its attrs
    (`waits`: "upload", "device", or "none" for a host phase whose cost moves
    unexplained) and gets the host's usage over its interval in the `host.*`
    counters (see `_host_usage_add`); any other span samples nothing."""
    node = SpanNode(name, attrs, parent_id=(
        _span_stack()[-1].span_id if _span_stack() else None
    ))
    _span_stack().append(node)
    # open-span registration: every open run tracks the node so /runs/<id> can
    # serve the CURRENT span stack mid-fit, and the flight recorder keeps the
    # open in its ring (observability/server.py, observability/flight.py)
    with _state_lock:
        open_runs = list(_active_runs)
    for run in open_runs:
        run.note_span_open(node)
    _flight().note_span_open(node)
    waits = node.attrs.get("waits")
    # the open sample lies inside the span (taken while a transfer is in
    # flight it runs beside it, and the wait is no longer for it); the close
    # sample and its counter writes come after the span's seconds are fixed,
    # so no `span.seconds` holds them
    usage = _host_sample() if waits is not None else None
    try:
        with _trace_annotation(name):
            yield node
    except BaseException:
        node.status = "error"
        raise
    finally:
        node.duration_s = time.perf_counter() - node.t0
        if usage is not None:
            _host_usage_add(name, waits, usage)
        stack = _span_stack()
        if stack and stack[-1] is node:
            stack.pop()
        else:  # defensive: mis-nested exit must not corrupt the stack
            try:
                stack.remove(node)
            except ValueError:
                pass
        # inclusive device accounting: raw kernel cost rolls up into the
        # enclosing span on this thread, so a wrapper span opened ABOVE the
        # dispatch layer (serving.batch around transform.predict) still
        # carries the §6f cost of the kernels it caused.
        dev = node.attrs.get("device")
        if dev and stack:
            pdev = stack[-1].attrs.get("device")
            if pdev is None:
                pdev = stack[-1].attrs["device"] = {
                    "flops": 0.0, "bytes": 0.0, "transcendentals": 0.0,
                    "comm_bytes": 0.0, "calls": 0, "kernels": {},
                }
            for k in ("flops", "bytes", "transcendentals", "comm_bytes"):
                pdev[k] = pdev.get(k, 0.0) + float(dev.get(k, 0.0) or 0.0)
            pdev["calls"] = pdev.get("calls", 0) + int(dev.get("calls", 0) or 0)
            agg = pdev.setdefault("kernels", {})
            for kname, c in (dev.get("kernels") or {}).items():
                agg[kname] = agg.get(kname, 0) + c
        # device plane (observability/device.py): keep the HBM gauge fresh
        _device().on_span_close(node)
        _flight().note_span_close(node)
        for reg in _sink_registries():
            reg.add_span_total(name, node.duration_s)
            reg.histogram(name).observe(node.duration_s, status=node.status)
        if node.status == "error":
            counter_inc("span.errors", 1, span=name)
        with _state_lock:
            runs = list(_active_runs)
        for run in runs:
            run.add_span(node)
        for scope in _worker_scopes():
            scope.add_span(node)


def _tree(nodes: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Assemble flat span dicts into a nested tree (children sorted by start)."""
    by_id = {n["span_id"]: dict(n, children=[]) for n in nodes}
    roots: List[Dict[str, Any]] = []
    for n in by_id.values():
        parent = by_id.get(n["parent_id"])
        if parent is not None:
            parent["children"].append(n)
        else:
            roots.append(n)
    for n in by_id.values():
        n["children"].sort(key=lambda c: c["start_ts"])
    roots.sort(key=lambda c: c["start_ts"])
    return roots


# ------------------------------------------------------------------- run scope


class FitRun:
    """One fit's observability scope: a scoped MetricsRegistry delta, a trace
    tree, an event log, and the per-worker snapshots the driver folds in from
    the barrier plane. Opened by core/estimator.py::_fit around the whole
    degradation ladder; the finished report attaches to the trained model as
    `model.fit_report_` and (when `observability.metrics_dir` is set) appends
    to the JSONL run log (observability/export.py).

    The class attributes below are the subclass surface: TransformRun
    (observability/inference.py) reuses the whole scope/fan-out/aggregation
    machinery for the inference plane and only swaps identity + export file."""

    kind = "fit"
    _id_prefix = "fit"
    _root_suffix = "fit_run"
    # None -> the exporter's default (fit_reports.jsonl); subclasses override
    _report_filename: Optional[str] = None

    def __init__(self, algo: str, site: str = "driver",
                 max_spans: Optional[int] = None):
        self.algo = algo
        self.site = site
        self.run_id = f"{self._id_prefix}-{next(_run_ids)}-{uuid.uuid4().hex[:8]}"
        # every run is born with a trace context (docs/design.md §6l) so
        # barrier-fit / transform-partition worker snapshots can join the
        # driver's trace across process boundaries (the run_id discipline)
        try:
            from .tracing import format_traceparent, mint_span_id, mint_trace_id

            self.traceparent: Optional[str] = format_traceparent(
                mint_trace_id(), mint_span_id())
        except Exception:
            self.traceparent = None
        self.registry = MetricsRegistry()
        self.max_spans = (
            int(_config.get("observability.max_spans"))
            if max_spans is None
            else int(max_spans)
        )
        self._lock = threading.Lock()
        self._spans: List[Dict[str, Any]] = []
        self._dropped_spans = 0
        self._events: List[Dict[str, Any]] = []
        # events are bounded like spans: an eviction-heavy fit (dataset far
        # over the cache budget) fires a cache_evict per cross-stream eviction
        # per pass and must not grow run memory / snapshot size without limit
        self.max_events = max(self.max_spans, 1024)
        self._dropped_events = 0
        self._workers: List[Dict[str, Any]] = []
        # ranks already flagged as stragglers (§6h): one event per rank per run
        self._straggler_ranks: set = set()
        # live-telemetry state (docs/design.md §6g): the open-span stack the
        # /runs/<id> endpoint serves mid-run, per-phase progress with EMA ETA,
        # and the bounded per-iteration convergence record list
        self._open_spans: Dict[int, Dict[str, Any]] = {}
        self._progress: Dict[str, Dict[str, Any]] = {}
        self._convergence: List[Dict[str, Any]] = []
        self.max_convergence = max(
            0, int(_config.get("observability.max_convergence_records"))
        )
        self._dropped_convergence = 0
        self._orphan_snapshots = 0
        self.started_ts: Optional[float] = None
        self.duration_s: Optional[float] = None
        self.status = "ok"
        self._t0: Optional[float] = None
        self._root: Optional[Any] = None
        self._host_usage: Any = None

    # ---- sink surface (runs.py fan-out calls these) ----

    def note_span_open(self, node: SpanNode) -> None:
        with self._lock:
            if len(self._open_spans) < self.max_spans:
                self._open_spans[node.span_id] = {
                    "span_id": node.span_id,
                    "parent_id": node.parent_id,
                    "name": node.name,
                    "start_ts": round(node.start_ts, 6),
                    "thread": node.thread,
                }

    def add_span(self, node: SpanNode) -> None:
        with self._lock:
            self._open_spans.pop(node.span_id, None)
            if len(self._spans) >= self.max_spans:
                self._dropped_spans += 1
                return
            self._spans.append(node.as_dict())

    def add_event(self, entry: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped_events += 1
                return
            self._events.append(entry)

    # ---- live progress & convergence (runs.progress / runs.convergence) ----

    def note_progress(self, phase: str, done: int, total: Optional[int],
                      unit: str) -> Optional[float]:
        """Fold one progress observation into the per-phase record; returns the
        EMA-based ETA in seconds (None until a rate is established). The EMA
        smooths per-unit rate over updates (alpha 0.3) so the ETA tracks the
        steady-state pass rate instead of the compile-heavy first pass."""
        now = time.monotonic()
        with self._lock:
            st = self._progress.get(phase)
            if st is None:
                st = self._progress[phase] = {
                    "phase": phase, "done": 0, "total": None, "unit": unit,
                    "ema_rate": None, "eta_s": None, "updated_ts": None,
                    "_t": now,
                }
            delta = done - st["done"]
            dt = now - st["_t"]
            if delta > 0 and dt > 0:
                rate = delta / dt
                st["ema_rate"] = (
                    rate if st["ema_rate"] is None
                    else 0.3 * rate + 0.7 * st["ema_rate"]
                )
            st["done"] = done
            if total is not None:
                st["total"] = int(total)
            st["unit"] = unit
            st["_t"] = now
            st["updated_ts"] = round(time.time(), 6)
            if st["total"] and st["ema_rate"]:
                st["eta_s"] = round(
                    max(st["total"] - done, 0) / st["ema_rate"], 3
                )
            return st["eta_s"]

    def note_convergence(self, rec: Dict[str, Any]) -> None:
        # Copy before annotating: `rec` is shared across every open run, and
        # `rel_s` (run-relative timestamp) is per-run by definition.
        rec = dict(rec)
        if self.started_ts is not None and "ts" in rec:
            rec["rel_s"] = round(float(rec["ts"]) - self.started_ts, 6)
        with self._lock:
            if len(self._convergence) >= self.max_convergence:
                self._dropped_convergence += 1
                return
            self._convergence.append(rec)

    def progress_snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {
                phase: {k: v for k, v in st.items() if not k.startswith("_")}
                for phase, st in self._progress.items()
            }

    def live_view(self, summary: bool = False) -> Dict[str, Any]:
        """The /runs JSON surface: a mid-run view (observability/server.py).
        `summary` yields the /runs index row; the full view adds the open-span
        stack, convergence/event tails, and a full metrics snapshot."""
        base = {
            "run_id": self.run_id,
            "kind": self.kind,
            "algo": self.algo,
            "site": self.site,
            "status": self.status,
            "process": PROCESS_TOKEN,
            "started_ts": self.started_ts,
            "duration_s": (
                round(time.perf_counter() - self._t0, 6)
                if self._t0 is not None and self.duration_s is None
                else self.duration_s
            ),
            "progress": self.progress_snapshot(),
        }
        if summary:
            return base
        with self._lock:
            open_spans = sorted(
                self._open_spans.values(), key=lambda s: s["span_id"]
            )
            convergence = list(self._convergence[-64:])
            events_tail = list(self._events[-64:])
            n_workers = len(self._workers)
        base.update(
            open_spans=open_spans,
            convergence=convergence,
            events_tail=events_tail,
            workers=n_workers,
            metrics=self.registry.snapshot(),
        )
        return base

    # ---- worker aggregation (spark/integration.py) ----

    def add_worker_snapshot(self, worker: Mapping[str, Any]) -> None:
        """Fold one barrier worker's serialized scope into this run. Foreign-
        process snapshots merge into the run AND global registries (their
        counters never flowed through this process's fan-out); same-process
        snapshots (threaded local-mode harness) are recorded for the per-worker
        breakdown only — their writes already landed here live.

        Trace context (§6g): snapshots stamped with a `run_id` join on it — a
        snapshot carrying a DIFFERENT run's id is an ORPHAN (a stale sidecar
        replay, a crossed wire in a shared executor): it is recorded for
        forensics but its counters are NOT merged, and
        `observability.orphan_snapshots` counts it. Legacy snapshots without a
        run_id keep the old process-token-only semantics."""
        snap_run_id = worker.get("run_id")
        orphan = snap_run_id is not None and snap_run_id != self.run_id
        foreign = worker.get("process") != PROCESS_TOKEN
        with self._lock:
            self._workers.append(
                {
                    "rank": worker.get("rank"),
                    "process": worker.get("process"),
                    "run_id": snap_run_id,
                    "orphan": orphan,
                    "merged": foreign and not orphan,
                    # per-rank timing (§6h): the skew/straggler/timeline inputs
                    "started_ts": worker.get("started_ts"),
                    "wall_s": worker.get("wall_s"),
                    "phases": worker.get("phases") or {},
                    "metrics": worker.get("metrics") or {},
                    "events": worker.get("events") or [],
                    "spans": worker.get("spans") or [],
                }
            )
            if orphan:
                self._orphan_snapshots += 1
        if orphan:
            counter_inc("observability.orphan_snapshots", 1, run=self.run_id)
            return
        if foreign:
            snap = worker.get("metrics") or {}
            self.registry.merge_snapshot(snap)
            _GLOBAL.merge_snapshot(snap)
            for entry in worker.get("events") or []:
                self.add_event(dict(entry, worker_rank=worker.get("rank")))
        # communication plane (§6h): refresh rank-skew gauges and emit
        # straggler events for newly slow ranks; a telemetry failure must
        # never fail a merge whose barrier stage already succeeded
        try:
            _comm().note_worker_merge(self)
        except Exception as e:
            _logger.warning("rank-skew update failed: %s", e)

    def rank_view(self) -> Dict[str, Any]:
        """The per-rank barrier timeline of this run's merged worker
        snapshots (observability/comm.py::rank_timeline): served live by
        `/runs/<run_id>/ranks`, exported as the report's `ranks` section, and
        carried by postmortem bundles. Orphan snapshots are excluded — they
        belong to some OTHER run's timeline."""
        with self._lock:
            workers = [
                {
                    "rank": w.get("rank"),
                    "started_ts": w.get("started_ts"),
                    "wall_s": w.get("wall_s"),
                    "phases": w.get("phases") or {},
                }
                for w in self._workers
                if not w.get("orphan")
            ]
        return _comm().rank_timeline(workers)

    # ---- lifecycle ----

    def __enter__(self) -> "FitRun":
        self.started_ts = time.time()
        self._t0 = time.perf_counter()
        # the whole operation's host cost: `host.*{span=run,waits=run}`, the
        # denominator of the flagged spans' shares
        self._host_usage = _host_sample()
        # root trace node: named `.fit_run` (not `.fit`) so the legacy
        # span_totals entry for the estimator's own `{Algo}.fit` kernel span
        # is not double-counted by its enclosing run scope
        self._root = span(f"{self.algo}.{self._root_suffix}", {"site": self.site})
        with _state_lock:
            _active_runs.append(self)
        _device().note_run_start(self)
        try:
            # live telemetry endpoint (observability/server.py): held up by
            # refcount while any run is open; no-op when http_port is unset
            _server().on_run_start(self)
        except Exception as e:
            _logger.warning("telemetry endpoint start failed: %s", e)
        self._root.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # while the run is still a sink, and before any report is taken
        _host_usage_add("run", "run", self._host_usage)
        try:
            self._root.__exit__(exc_type, exc, tb)
        finally:
            with _state_lock:
                try:
                    _active_runs.remove(self)
                except ValueError:
                    pass
            _device().note_run_end(self)
            self.duration_s = time.perf_counter() - (self._t0 or time.perf_counter())
            if exc_type is not None:
                self.status = "error"
                # failure flight recorder (observability/flight.py): an
                # unhandled fit/transform failure dumps the postmortem bundle
                # next to the JSONL reports; never raises
                _flight().dump_postmortem(
                    self, reason=f"{self.kind}_error:{exc_type.__name__}"
                )
            try:
                metrics_dir = _config.get("observability.metrics_dir")
                if metrics_dir:
                    from .export import write_run_report

                    try:
                        write_run_report(
                            self.report(), metrics_dir,
                            filename=self._report_filename,
                        )
                    except OSError as e:
                        _logger.warning(
                            "could not write %s report: %s", self.kind, e
                        )
            finally:
                # endpoint release must never be skipped — a leaked refcount
                # would leave the server thread and socket alive after fit
                try:
                    _server().on_run_end(self)
                except Exception as e:
                    _logger.warning("telemetry endpoint release failed: %s", e)

    def report(self) -> Dict[str, Any]:
        """The structured fit report (finalized numbers after __exit__; callable
        mid-run for a live view)."""
        with self._lock:
            spans = list(self._spans)
            events = list(self._events)
            workers = [
                {k: v for k, v in w.items() if k != "spans"} for w in self._workers
            ]
            dropped = self._dropped_spans
            dropped_events = self._dropped_events
            convergence = list(self._convergence)
            dropped_convergence = self._dropped_convergence
            orphans = self._orphan_snapshots
            have_workers = bool(self._workers)
        device_section = _device().device_report_section(self.registry)
        # ingest section (docs/design.md §6k/§6f): this run's zero-copy vs
        # copied staging byte split and the before/after bytes-per-row cost
        # analysis. Best-effort: a section failure must never fail a report.
        ingest_section = None
        try:
            from ..ops import ingest as _ingest

            ingest_section = _ingest.report_section(self.registry)
        except Exception as e:
            _logger.warning("ingest report section failed: %s", e)
        ranks_section = None
        if have_workers:
            try:
                ranks_section = self.rank_view()
            except Exception as e:
                _logger.warning("rank timeline assembly failed: %s", e)
        # a run whose only snapshots were orphans has an EMPTY timeline —
        # exporting it would read as "this run had ranks, none reported"
        have_ranks = bool(ranks_section and ranks_section.get("ranks"))
        return {
            **({"device": device_section} if device_section else {}),
            **({"ingest": ingest_section} if ingest_section else {}),
            **({"ranks": ranks_section} if have_ranks else {}),
            "schema": 1,
            "kind": self.kind,
            "run_id": self.run_id,
            "traceparent": self.traceparent,
            "algo": self.algo,
            "site": self.site,
            "process": PROCESS_TOKEN,
            "started_ts": self.started_ts,
            "duration_s": (
                self.duration_s
                if self.duration_s is not None
                else (time.perf_counter() - self._t0 if self._t0 else None)
            ),
            "status": self.status,
            "trace": _tree(spans),
            "dropped_spans": dropped,
            "events": events,
            "dropped_events": dropped_events,
            "convergence": convergence,
            "dropped_convergence": dropped_convergence,
            "progress": self.progress_snapshot(),
            "orphan_snapshots": orphans,
            "metrics": self.registry.snapshot(),
            "workers": workers,
        }


def current_run() -> Optional[FitRun]:
    """The most recently opened still-active FitRun, if any."""
    with _state_lock:
        return _active_runs[-1] if _active_runs else None


def active_runs() -> List[FitRun]:
    """All currently-open run scopes, oldest first (the /runs index)."""
    with _state_lock:
        return list(_active_runs)


def find_run(run_id: str) -> Optional[FitRun]:
    """A still-active run by id — how a transform partition's metrics sidecar
    finds its driver-side run when both execute in one process (the eager
    local-mode plane; observability/inference.py)."""
    with _state_lock:
        for run in _active_runs:
            if run.run_id == run_id:
                return run
    return None


@contextlib.contextmanager
def fit_run(algo: str, site: str = "driver") -> Iterator[Optional[FitRun]]:
    """FitRun gated on `observability.enabled`: yields None (and collects
    nothing run-scoped) when the subsystem is off — the global registry keeps
    accumulating either way, so the legacy counter surface never degrades."""
    if not bool(_config.get("observability.enabled")):
        yield None
        return
    with FitRun(algo, site=site) as run:
        yield run


# ---------------------------------------------------------------- worker scope


class WorkerScope:
    """One barrier task's thread-local metric delta: everything this thread
    writes while the scope is open, snapshot-able to the payload shipped to the
    driver (spark/integration.py serializes it next to the fit result).

    `run_id` is the TRACE CONTEXT (§6g): the driver's run id, carried through
    the barrier/transform closure into the scope and stamped on every exported
    snapshot, so driver-side merge and offline `load_run_reports` join
    per-worker rows to exactly one run instead of guessing by process token."""

    def __init__(self, rank: Optional[int] = None, max_spans: int = 256,
                 max_events: int = 512, run_id: Optional[str] = None,
                 traceparent: Optional[str] = None):
        self.rank = rank
        self.run_id = run_id
        self.traceparent = traceparent
        self.registry = MetricsRegistry()
        self.max_spans = max_spans
        self.max_events = max_events
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._dropped_events = 0
        self._spans: List[Dict[str, Any]] = []
        self._dropped_spans = 0
        # per-rank timing for the communication plane (§6h): the scope's own
        # wall clock plus named phase records (collect, fit_program, transform
        # partition, ...) with rows/bytes — the raw material of the driver's
        # skew ratios, straggler events and barrier timeline
        self.started_ts = time.time()
        self._t0 = time.perf_counter()
        self._phases: Dict[str, Dict[str, Any]] = {}

    def note_phase(self, phase: str, wall_s: Optional[float] = None,
                   rows: Optional[int] = None, nbytes: Optional[int] = None,
                   start_ts: Optional[float] = None,
                   end_ts: Optional[float] = None) -> None:
        """Record (accumulating) one named phase's wall time / rows ingested /
        bytes for this rank. Callers pass measured wall_s; start/end default to
        a window ending NOW of that length, so merged timelines always carry
        usable start/end stamps."""
        now = time.time()
        if end_ts is None:
            end_ts = now
        if start_ts is None and wall_s is not None:
            start_ts = end_ts - float(wall_s)
        with self._lock:
            st = self._phases.setdefault(phase, {
                "wall_s": 0.0, "rows": 0, "bytes": 0,
                "start_ts": None, "end_ts": None,
            })
            if wall_s is not None:
                st["wall_s"] = round(st["wall_s"] + float(wall_s), 6)
            if rows:
                st["rows"] += int(rows)
            if nbytes:
                st["bytes"] += int(nbytes)
            if start_ts is not None:
                st["start_ts"] = (
                    round(start_ts, 6) if st["start_ts"] is None
                    else min(st["start_ts"], round(start_ts, 6))
                )
            st["end_ts"] = (
                round(end_ts, 6) if st["end_ts"] is None
                else max(st["end_ts"], round(end_ts, 6))
            )

    def add_event(self, entry: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped_events += 1
                return
            self._events.append(entry)

    def add_span(self, node: SpanNode) -> None:
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self._dropped_spans += 1
                return
            self._spans.append(node.as_dict())

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "schema": 1,
                "process": PROCESS_TOKEN,
                "rank": self.rank,
                "run_id": self.run_id,
                "traceparent": self.traceparent,
                "started_ts": round(self.started_ts, 6),
                "wall_s": round(time.perf_counter() - self._t0, 6),
                "phases": {k: dict(v) for k, v in self._phases.items()},
                "metrics": self.registry.snapshot(),
                "events": list(self._events),
                "dropped_events": self._dropped_events,
                "spans": list(self._spans),
                "dropped_spans": self._dropped_spans,
            }


def note_rank_phase(phase: str, wall_s: Optional[float] = None,
                    rows: Optional[int] = None, nbytes: Optional[int] = None,
                    start_ts: Optional[float] = None,
                    end_ts: Optional[float] = None) -> None:
    """Record one per-rank phase observation (wall time, rows ingested, bytes)
    on every worker scope open on THIS thread — the communication plane's
    (§6h) raw skew material. No-op outside a worker scope, so instrumented
    code paths (barrier task body, transform partitions) need no gating."""
    for scope in _worker_scopes():
        scope.note_phase(phase, wall_s=wall_s, rows=rows, nbytes=nbytes,
                         start_ts=start_ts, end_ts=end_ts)


@contextlib.contextmanager
def worker_scope(rank: Optional[int] = None,
                 run_id: Optional[str] = None,
                 traceparent: Optional[str] = None) -> Iterator[WorkerScope]:
    """Open a thread-local capture scope (stackable; inner scopes see the same
    writes). The barrier UDF wraps its whole body in one so each task's metric
    delta travels to the driver regardless of which process it ran in;
    `run_id` (and since §6l the W3C `traceparent`) stamps the driver's trace
    context on the exported snapshot."""
    scope = WorkerScope(rank=rank, run_id=run_id, traceparent=traceparent)
    _worker_scopes().append(scope)
    try:
        yield scope
    finally:
        scopes = _worker_scopes()
        try:
            scopes.remove(scope)
        except ValueError:
            pass
