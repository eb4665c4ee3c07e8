#
# Failure flight recorder — the forensics half of the live telemetry plane
# (docs/design.md §6g).
#
# Run reports (§6d/§6e) answer "what did this fit do" AFTER it finished; a fit
# that dies mid-stream, wedges, or enters the degradation ladder leaves only
# whatever was flushed. This module keeps a bounded per-process RING BUFFER of
# the most recent telemetry transitions — span opens/closes, structured events
# (retry/fault/degrade/cache_evict), HBM samples — cheap enough to be always on
# (`observability.flight_recorder_events`, default 256; <=0 disables).
#
# On an unhandled fit/transform failure (FitRun.__exit__ with an exception) or
# on ENTRY into the degradation ladder (core/estimator.py's degrade rungs), the
# ring dumps as an atomic postmortem bundle next to the JSONL reports:
#
#   <metrics_dir>/postmortem_<run_id>.json
#     { schema, ts, reason, run_id, kind, algo, process, ring: [...],
#       open_spans: [...], config: {...}, device: {...} }
#
# PR 1's deterministic fault sites make the dump path testable end to end: an
# injected DeviceError at `ingest` raises out of the streamed fit and the
# failure's bundle must contain the `fault` ring entry (ci/test.sh
# live-telemetry smoke). Writes are tmp-file + os.replace, so a concurrent
# reader only ever sees a whole bundle.
#

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from collections import deque
from typing import Any, Dict, List, Mapping, Optional

from .. import config as _config
from ..utils import get_logger

_logger = get_logger("observability.flight")

_lock = threading.Lock()
_ring: Optional[deque] = None
_ring_cap = -1  # cap the current ring was built with (rebuilt when config moves)
_dropped = 0  # entries evicted by the bound since the last reset (diagnostic)


def _capacity() -> int:
    try:
        return int(_config.get("observability.flight_recorder_events"))
    except (TypeError, ValueError):
        return 0


def _buffer() -> Optional[deque]:
    """The live ring, rebuilt if the configured capacity changed; None when the
    recorder is disabled (cap <= 0)."""
    global _ring, _ring_cap
    cap = _capacity()
    if cap <= 0:
        return None
    if _ring is None or _ring_cap != cap:
        old = list(_ring) if _ring is not None else []
        _ring = deque(old[-cap:], maxlen=cap)
        _ring_cap = cap
    return _ring


def enabled() -> bool:
    return _capacity() > 0


def _append(entry: Dict[str, Any]) -> None:
    """The one ring-append path (lock, disabled-check, bound accounting) —
    both the envelope-building note() and the pass-through note_event() go
    through here so the accounting can never diverge between them."""
    with _lock:
        ring = _buffer()
        if ring is None:
            return
        global _dropped
        if len(ring) == ring.maxlen:
            _dropped += 1
        ring.append(entry)


def note(kind: str, **fields: Any) -> None:
    """Append one transition to the ring. Must stay cheap (it sits on every
    span open/close) and must never raise."""
    _append({"ts": round(time.time(), 6), "kind": kind, **fields})


def note_span_open(node: Any) -> None:
    note("span_open", span_id=node.span_id, name=node.name,
         thread=node.thread)


def note_span_close(node: Any) -> None:
    note("span_close", span_id=node.span_id, name=node.name,
         duration_s=node.duration_s, status=node.status)


def note_event(entry: Mapping[str, Any]) -> None:
    """Mirror a structured run event into the ring. The entry keeps its own
    kind (`retry`/`fault`/`degrade`/`cache_evict`/...) — those ARE the
    transitions a postmortem reader greps for."""
    _append(dict(entry))


def note_hbm(total_bytes: int) -> None:
    note("hbm_sample", bytes_in_use=int(total_bytes))


def snapshot() -> List[Dict[str, Any]]:
    """Copy of the ring, oldest first."""
    with _lock:
        ring = _buffer()
        return [dict(e) for e in ring] if ring is not None else []


def reset_flight_recorder() -> None:
    """Drop all recorded transitions (tests / long-lived workers)."""
    global _ring, _ring_cap, _dropped
    with _lock:
        _ring = None
        _ring_cap = -1
        _dropped = 0


def _config_snapshot() -> Dict[str, Any]:
    """config.all(), coerced to JSON-safe values (every key is a primitive
    today; the str() fallback keeps a future exotic value from killing a dump
    that exists precisely to debug failures)."""
    out: Dict[str, Any] = {}
    for k, v in _config.all().items():
        out[k] = v if isinstance(v, (type(None), bool, int, float, str)) else str(v)
    return out


def dump_postmortem(run: Any = None, reason: str = "failure",
                    metrics_dir: Optional[str] = None) -> Optional[str]:
    """Write the postmortem bundle for `run` (an open or just-failed
    Fit/TransformRun; None dumps a process-scoped bundle). Returns the path, or
    None when no metrics dir is configured / the recorder is disabled. Never
    raises — this runs on failure paths that must keep propagating the ORIGINAL
    error."""
    try:
        if metrics_dir is None:
            metrics_dir = _config.get("observability.metrics_dir")
        if not metrics_dir or not enabled():
            return None
        from . import device as _device
        from . import runs as _runs
        from .export import _json_fallback

        open_spans = [n.as_dict() for n in _runs._span_stack()]
        run_id = getattr(run, "run_id", None) or "process"
        with _lock:
            dropped = _dropped
        bundle = {
            "schema": 1,
            "ts": round(time.time(), 6),
            "reason": reason,
            "run_id": run_id,
            "kind": getattr(run, "kind", None),
            "algo": getattr(run, "algo", None),
            "process": _runs.PROCESS_TOKEN,
            "ring": snapshot(),
            "ring_dropped": dropped,
            "open_spans": open_spans,
            "progress": (
                run.progress_snapshot() if hasattr(run, "progress_snapshot")
                else {}
            ),
            "config": _config_snapshot(),
        }
        device_section = _device.device_report_section(
            getattr(run, "registry", None)
        )
        if device_section:
            bundle["device"] = device_section
        # tail-sampled trace ring (§6l): the requests that died WITH the
        # process — error/hedged/failed-over/slowest traces — ride along so a
        # postmortem reader can walk causality without a live /traces endpoint
        from .tracing import ring_snapshot

        traces = ring_snapshot()
        if traces:
            bundle["traces"] = traces
        # per-rank barrier timeline (§6h): a degraded/failed barrier fit's
        # postmortem must show WHICH rank was slow, not just that one was
        if hasattr(run, "rank_view"):
            try:
                ranks = run.rank_view()
            except Exception as e:
                _logger.warning("postmortem rank timeline failed: %s", e)
                ranks = None
            if ranks and ranks.get("ranks"):
                bundle["ranks"] = ranks
        os.makedirs(metrics_dir, exist_ok=True)
        safe_id = "".join(c if c.isalnum() or c in "-_." else "_" for c in run_id)
        path = os.path.join(metrics_dir, f"postmortem_{safe_id}.json")
        fd, tmp = tempfile.mkstemp(dir=metrics_dir, prefix=".postmortem_")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(bundle, f, default=_json_fallback)
            os.replace(tmp, path)  # last dump wins: later rungs carry more ring
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        _runs.counter_inc("observability.postmortems", 1, reason=reason)
        _logger.warning("wrote postmortem bundle (%s) to %s", reason, path)
        return path
    except Exception as e:
        _logger.warning("postmortem dump failed: %s: %s", type(e).__name__, e)
        return None


def load_postmortem(path: str) -> Dict[str, Any]:
    """Round-trip helper for tests/CI: parse one postmortem bundle."""
    with open(path) as f:
        return json.load(f)
