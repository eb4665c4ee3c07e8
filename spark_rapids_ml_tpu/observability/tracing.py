#
# Trace plane: end-to-end causal request tracing (docs/design.md §6l).
#
# One `RequestTrace` per client request, minted at HTTP ingress (or accepted
# from a W3C `traceparent` header), carried by reference through router
# admission -> replica queue -> micro-batch close -> execute -> scatter-back.
# Spans are appended with raw perf_counter stamps (the clocks the serving
# plane already holds: enqueue_ts, batch open/close) and converted to wall
# time against the trace's birth instant, so parent/child timing is monotonic
# and non-overlapping by construction. A micro-batch span carries fan-in
# links to the N request root spans it served, which is what makes padding
# and occupancy cost attributable per request; fleet actions (hedge, replay,
# steal, shed, expiry) land as causal events that also force tail-keep.
#
# Storage is a bounded per-process ring with tail-based sampling: flagged
# traces (error/hedged/failover/expired/shed) always keep, the rolling
# slowest `tracing.slow_frac` keep as "slow", the rest keep at
# `tracing.sample_rate` by a deterministic hash of the trace id. Kept traces
# export to rotated `trace_reports.jsonl` (PR-4 writer) and serve live on
# `GET /traces` / `/traces/<id>`; exemplars attached to serving latency
# histograms point back into this ring.
#

from __future__ import annotations

import os
import re
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from .. import config as _config
from ..utils import get_logger

_logger = get_logger("observability.tracing")

# Event kinds that force tail-keep, and the flag each one raises.
_FLAG_EVENTS = {
    "hedge_issued": "hedged",
    "hedge_won": "hedged",
    "failover_replay": "failover",
    "queue_steal": "failover",
    "deadline_expired": "expired",
    "tenant_shed": "shed",
    "error": "error",
}

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)

_MAX_SPANS = 256     # per trace; beyond this, spans are counted, not stored
_MAX_EVENTS = 512
_SLOW_WINDOW = 512   # rolling durations window for the slow-keep threshold

_lock = threading.RLock()
# ring holds finished RequestTrace objects; their export documents build
# lazily on first read (/traces, JSONL, postmortem) — the request path pays
# for appends and the sampling decision, not for serialization
_ring: "OrderedDict[str, RequestTrace]" = OrderedDict()
_durations: deque = deque(maxlen=_SLOW_WINDOW)
# slow-threshold cache: sorting the 512-entry window on every finish is the
# kind of per-request cost the <2% overhead budget exists to catch, so the
# percentile recomputes at most every _SLOW_RECOMPUTE appends
_SLOW_RECOMPUTE = 16
_slow_cached: Optional[float] = None
_slow_dirty = 0
_slow_frac_at: Optional[float] = None
# per-request config reads cached against config.epoch(): re-resolved only
# after a set()/unset(), not once (or twice) per request
_rate_cached: Optional[float] = None
_rate_epoch = -1
_hot_cfg: Optional[tuple] = None  # (slow_frac, ring_cap, metrics_dir)
_hot_epoch = -1
_tls = threading.local()


# ------------------------------------------------------------------ ids


def mint_trace_id() -> str:
    return os.urandom(16).hex()


def mint_span_id() -> str:
    return os.urandom(8).hex()


class TraceContext:
    """Parsed/minted W3C trace context: 32-hex trace id, 16-hex parent
    span id, sampled flag."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)


def parse_traceparent(value) -> Optional[TraceContext]:
    """Parse a `traceparent` header. Returns None on anything malformed
    (wrong field widths, non-hex, all-zero ids, version ff) — callers count
    and replace, they never reject the request."""
    if not isinstance(value, str):
        return None
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    version, trace_id, span_id, flags = m.groups()
    if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return TraceContext(trace_id, span_id, sampled=bool(int(flags, 16) & 1))


def format_traceparent(trace_id: str, span_id: str,
                       sampled: bool = True) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def mint_context() -> TraceContext:
    return TraceContext(mint_trace_id(), mint_span_id())


# ------------------------------------------------------------------ config


def _enabled() -> bool:
    return bool(_config.get("tracing.enabled"))


def sample_rate() -> float:
    """`tracing.sample_rate` resolution: config pin (set()/env) wins, then
    the defaults-module constant. The resolved rate is cached against
    config.epoch(), since it is asked twice per request (would_keep +
    finish); a set()/unset() re-resolves immediately."""
    global _rate_cached, _rate_epoch
    ep = _config.epoch()
    if _rate_cached is not None and _rate_epoch == ep:
        return _rate_cached
    if _config.source("tracing.sample_rate") != "default":
        rate = float(_config.get("tracing.sample_rate"))
    else:
        from ..defaults import TRACING_SAMPLE_RATE

        rate = float(TRACING_SAMPLE_RATE)
    _rate_cached, _rate_epoch = rate, ep
    return rate


def _hash_sampled(trace_id: str, rate: float) -> bool:
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return int(trace_id[:8], 16) / float(0xFFFFFFFF) < rate


def _hot_config() -> tuple:
    """(slow_frac, ring_cap, metrics_dir) re-read only when config.epoch()
    moved — these three are consulted on every single finish."""
    global _hot_cfg, _hot_epoch
    ep = _config.epoch()
    if _hot_cfg is None or _hot_epoch != ep:
        _hot_cfg = (float(_config.get("tracing.slow_frac")),
                    max(1, int(_config.get("tracing.ring_traces"))),
                    _config.get("observability.metrics_dir"))
        _hot_epoch = ep
    return _hot_cfg


def _slow_threshold() -> Optional[float]:
    """Duration above which a trace counts as one of the rolling slowest
    `tracing.slow_frac`; None until the window has data. Cached between
    recomputes (every _SLOW_RECOMPUTE appends, or on a slow_frac change)."""
    global _slow_cached, _slow_dirty, _slow_frac_at
    frac = _hot_config()[0]
    if frac <= 0.0:
        return None
    with _lock:
        if len(_durations) < 8:  # too little history to call anything slow
            return None
        if (_slow_cached is not None and _slow_dirty < _SLOW_RECOMPUTE
                and _slow_frac_at == frac):
            return _slow_cached
        ordered = sorted(_durations)
        idx = max(0, min(len(ordered) - 1,
                         int((1.0 - frac) * (len(ordered) - 1))))
        _slow_cached = ordered[idx]
        _slow_dirty = 0
        _slow_frac_at = frac
        return _slow_cached


# ------------------------------------------------------------------ trace


class RequestTrace:
    """One request's causal record. Thread-safe append; `finish()` is
    idempotent (first caller wins — hedge losers land as dropped appends).

    Spans are held as raw tuples until `document()` materializes them —
    per-span dict building, wall-clock conversion and rounding happen once
    per EXPORT, not once per append on the request path. Span/event attrs
    are captured by reference: callers must not mutate an attrs dict after
    passing it (every call site builds a fresh dict or freezes it first)."""

    __slots__ = ("trace_id", "client_span_id", "root_span_id", "name",
                 "attrs", "_wall_t0", "_pc_t0", "_lock", "_spans", "_events",
                 "_span_ids", "_dropped_spans", "flags", "finished",
                 "status", "keep_reason", "_duration", "_doc")

    def __init__(self, name: str, ctx: Optional[TraceContext] = None,
                 **attrs):
        ctx = ctx or mint_context()
        self.trace_id = ctx.trace_id
        self.client_span_id = ctx.span_id
        self.root_span_id = mint_span_id()
        self.name = name
        self.attrs = dict(attrs)
        self._wall_t0 = time.time()
        self._pc_t0 = time.perf_counter()
        self._lock = threading.Lock()
        # raw span tuples: (sid, parent, name, t0_pc, t1_pc, status,
        #                   attrs, links)
        self._spans: List[tuple] = []
        self._events: List[Dict[str, Any]] = []
        self._span_ids = set()
        self._dropped_spans = 0
        self.flags: set = set()
        self.finished = False
        self.status = None
        self.keep_reason = None
        self._duration: Optional[float] = None
        self._doc: Optional[Dict[str, Any]] = None

    # -- clocks

    def now(self) -> float:
        return time.perf_counter()

    def _wall(self, pc_ts: float) -> float:
        return self._wall_t0 + (pc_ts - self._pc_t0)

    @property
    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.root_span_id)

    # -- appends

    def add_span(self, name: str, t0_pc: float, t1_pc: float,
             parent_id: Optional[str] = None, attrs: Optional[dict] = None,
             links: Optional[list] = None, status: str = "ok",
             span_id: Optional[str] = None) -> Optional[str]:
        """Append a completed span from raw perf_counter stamps. Returns the
        span id (None once the trace is finished or the span cap is hit)."""
        sid = span_id or mint_span_id()
        with self._lock:
            if self.finished or len(self._spans) >= _MAX_SPANS:
                if not self.finished:
                    self._dropped_spans += 1
                return None
            self._spans.append(
                (sid, parent_id, name, t0_pc, t1_pc, status, attrs, links)
            )
            self._span_ids.add(sid)
        return sid

    def add_event(self, kind: str, t_pc: Optional[float] = None, **fields):
        """Append a causal event; flagged kinds (hedge/replay/steal/shed/
        expiry/error) force tail-keep."""
        entry = {"kind": kind,
                 "ts": round(self._wall(t_pc if t_pc is not None
                                        else time.perf_counter()), 6)}
        entry.update(fields)
        flag = _FLAG_EVENTS.get(kind)
        with self._lock:
            if flag:
                self.flags.add(flag)
            if self.finished or len(self._events) >= _MAX_EVENTS:
                return
            self._events.append(entry)

    def flag(self, reason: str):
        with self._lock:
            self.flags.add(reason)

    # -- terminal

    def finish(self, status: str = "ok"):
        """Close the trace: tail-sampling decision, ring insert, JSONL
        export. Idempotent — the first finish wins. If no caller appended a
        span under `root_span_id` (the in-process predict path has no HTTP
        ingress span), the root is synthesized covering the whole trace."""
        t1 = time.perf_counter()
        with self._lock:
            if self.finished:
                return
            self.finished = True
            if status != "ok":
                self.flags.add("error")
            self.status = status
            if self.root_span_id not in self._span_ids:
                self._spans.insert(0, (self.root_span_id, None, self.name,
                                       self._pc_t0, t1, status,
                                       self.attrs or None, None))
                self._span_ids.add(self.root_span_id)
        _finish_collect(self, t1 - self._pc_t0)

    def document(self, duration: float) -> Dict[str, Any]:
        from .runs import PROCESS_TOKEN

        spans = []
        for sid, parent, name, t0_pc, t1_pc, status, attrs, links in \
                self._spans:
            entry = {
                "span_id": sid,
                "parent_span_id": parent,
                "name": name,
                "start_ts": round(self._wall(t0_pc), 6),
                "duration_s": round(max(0.0, t1_pc - t0_pc), 9),
                "status": status,
            }
            if attrs:
                entry["attrs"] = dict(attrs)
            if links:
                entry["links"] = list(links)
            spans.append(entry)
        doc: Dict[str, Any] = {
            "schema": 1,
            "kind": "trace",
            "trace_id": self.trace_id,
            "traceparent": self.traceparent,
            "name": self.name,
            "start_ts": round(self._wall_t0, 6),
            "duration_s": round(duration, 9),
            "status": self.status or "ok",
            "keep_reason": self.keep_reason,
            "flags": sorted(self.flags),
            "process": PROCESS_TOKEN,
            "spans": spans,
            "events": list(self._events),
        }
        if self.client_span_id:
            doc["client_span_id"] = self.client_span_id
        if self.attrs:
            doc["attrs"] = dict(self.attrs)
        if self._dropped_spans:
            doc["dropped_spans"] = self._dropped_spans
        return doc


# ------------------------------------------------------- collector / ring


def _doc_of(rt: RequestTrace) -> Dict[str, Any]:
    """The trace's export document, built once on first read — a finished
    trace is immutable, so concurrent builders produce identical content."""
    doc = rt._doc
    if doc is None:
        doc = rt._doc = rt.document(rt._duration or 0.0)
    return doc


def _finish_collect(rt: RequestTrace, duration: float):
    global _slow_dirty

    from . import runs as _runs

    reason = None
    if rt.flags:
        reason = sorted(rt.flags)[0]
    else:
        rate = sample_rate()
        if rate >= 1.0:  # keep-everything: the slow label adds nothing
            reason = "sampled"
        else:
            thresh = _slow_threshold()
            if thresh is not None and duration >= thresh:
                reason = "slow"
            elif _hash_sampled(rt.trace_id, rate):
                reason = "sampled"
    if reason is None:
        with _lock:
            _durations.append(duration)
            _slow_dirty += 1
        _runs.counter_inc("tracing.traces_dropped", 1)
        return
    rt.keep_reason = reason
    rt._duration = duration
    _, cap, metrics_dir = _hot_config()
    with _lock:
        _durations.append(duration)
        _slow_dirty += 1
        _ring[rt.trace_id] = rt
        _ring.move_to_end(rt.trace_id)
        while len(_ring) > cap:
            _ring.popitem(last=False)
    _runs.counter_inc("tracing.traces_kept", 1, reason=reason)
    if metrics_dir:
        try:
            from .export import TRACE_REPORT_FILENAME, write_run_report

            write_run_report(_doc_of(rt), metrics_dir,
                             filename=TRACE_REPORT_FILENAME)
        except Exception as e:  # export must never fail the request path
            _logger.warning("trace report write failed: %s: %s",
                            type(e).__name__, e)


def would_keep(rt: Optional[RequestTrace],
               duration: Optional[float] = None) -> bool:
    """Predict the tail-sampling decision for `rt` — used to decide whether
    a histogram exemplar pointing at this trace will resolve. Deterministic
    for the flag and hash arms; the slow arm consults the rolling window."""
    if rt is None:
        return False
    if rt.flags:
        return True
    if _hash_sampled(rt.trace_id, sample_rate()):
        return True
    if duration is not None:
        thresh = _slow_threshold()
        if thresh is not None and duration >= thresh:
            return True
    return False


def start_trace(name: str, ctx: Optional[TraceContext] = None,
                **attrs) -> Optional[RequestTrace]:
    """Mint a trace (or adopt a client context). Returns None when tracing
    is disabled — every call site treats None as 'no tracing'."""
    if not _enabled():
        return None
    return RequestTrace(name, ctx=ctx, **attrs)


def finish_future(rt: Optional[RequestTrace], fut) -> None:
    """Finish `rt` when `fut` resolves (status from the exception type)."""
    if rt is None:
        return

    def _done(f):
        try:
            exc = f.exception()
        except Exception as e:  # cancelled
            exc = e
        rt.finish(status="ok" if exc is None else type(exc).__name__)

    fut.add_done_callback(_done)


def get_trace(trace_id: str) -> Optional[Dict[str, Any]]:
    with _lock:
        rt = _ring.get(trace_id)
    return dict(_doc_of(rt)) if rt is not None else None


def trace_index() -> List[Dict[str, Any]]:
    """Newest-last summaries of the kept ring."""
    with _lock:
        kept = list(_ring.values())
    out = []
    for rt in kept:
        d = _doc_of(rt)
        out.append({
            "trace_id": d["trace_id"],
            "name": d["name"],
            "start_ts": d["start_ts"],
            "duration_s": d["duration_s"],
            "status": d["status"],
            "keep_reason": d["keep_reason"],
            "flags": d["flags"],
            "spans": len(d["spans"]),
            "events": len(d["events"]),
        })
    return out


def ring_snapshot() -> List[Dict[str, Any]]:
    """Full kept-trace docs, oldest-first (flight-recorder postmortems)."""
    with _lock:
        kept = list(_ring.values())
    return [dict(_doc_of(rt)) for rt in kept]


def reset_tracing() -> None:
    global _slow_cached, _slow_dirty, _slow_frac_at
    global _rate_cached, _rate_epoch, _hot_cfg, _hot_epoch
    with _lock:
        _ring.clear()
        _durations.clear()
        _slow_cached = None
        _slow_dirty = 0
        _slow_frac_at = None
        _rate_cached = None
        _rate_epoch = -1
        _hot_cfg = None
        _hot_epoch = -1


# -------------------------------------------- batch-thread annotations

# The execute path (`_predict_padded`) knows things the batcher does not —
# the serving model generation that answered. It runs on the dispatcher
# thread that called it, so a thread-local hand-off is race-free.


def annotate_batch(**attrs) -> None:
    cur = getattr(_tls, "batch_attrs", None)
    if cur is None:
        cur = {}
        _tls.batch_attrs = cur
    cur.update(attrs)


def take_batch_annotations() -> Dict[str, Any]:
    cur = getattr(_tls, "batch_attrs", None)
    _tls.batch_attrs = None
    return cur or {}


__all__ = [
    "TraceContext",
    "RequestTrace",
    "parse_traceparent",
    "format_traceparent",
    "mint_context",
    "mint_trace_id",
    "mint_span_id",
    "start_trace",
    "finish_future",
    "would_keep",
    "sample_rate",
    "get_trace",
    "trace_index",
    "ring_snapshot",
    "reset_tracing",
    "annotate_batch",
    "take_batch_annotations",
]
