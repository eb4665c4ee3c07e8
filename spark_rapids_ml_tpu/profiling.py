#
# Compat shims over the observability subsystem (observability/ — docs/design.md
# §6d). This module USED to own two flat process-global dicts (span seconds,
# event counts); it now forwards every call to the typed metrics registry and
# run-scope fan-out in `observability/`, keeping the historical surface —
# span / span_totals / reset_spans / count / counter_totals /
# reset_counters / trace — byte-compatible for every existing call site and
# test. New instrumentation should import `spark_rapids_ml_tpu.observability`
# directly (Counter/Gauge/Histogram with labels, structured spans, events).
#
# There is ONE span primitive, `observability.span`: trace-tree node, span
# totals, latency histogram, failure-safe timing (a span whose body raises
# still records, with status=error and a `span.errors` count) and the
# `jax.profiler.TraceAnnotation` that puts the span on the profiler's clock.
# `profiling.span` is that primitive plus an optional log line; nothing here
# times or annotates anything itself. `counter_totals()` also carries every
# span's `span.seconds{span=}` / `span.calls{span=}` (observability/registry.py).
#
# Enable xplane capture with SRML_TPU_TRACE_DIR=/path (see config.py): every
# fit is then traced automatically.
#

from __future__ import annotations

import contextlib
from typing import Dict, Iterator, Optional

from . import observability as _obs
from .utils import get_logger

_logger = get_logger("profiling")


@contextlib.contextmanager
def span(name: str, verbose: bool = False) -> Iterator[None]:
    """`observability.span(name)`, plus one log line with the span's seconds
    when `verbose`."""
    node = None
    try:
        with _obs.span(name) as node:
            yield
    finally:
        if verbose and node is not None:
            _logger.info("%s: %.3fs", name, node.duration_s)


def span_totals() -> Dict[str, float]:
    """Accumulated seconds per span name since process start (or last reset)."""
    return _obs.global_registry().span_totals()


def reset_spans() -> None:
    _obs.global_registry().reset_spans()


def count(name: str, n: int = 1) -> None:
    """Monotone event counter (legacy flat surface). The reliability subsystem
    reports retry/resume/degrade/fault totals here, the streamed-ingest tier
    reports `stream.upload_batches`/`stream.upload_bytes`, and the HBM batch
    cache reports `cache.hits`/`cache.misses`/`cache.evictions`
    (`cache.bytes_resident` is a real observability Gauge now — see
    ops/device_cache.py — surfaced through counter_totals() for compat).
    This surface never distinguished counters from gauges, so kind is
    discovered from usage: a name's first negative increment retypes it to a
    gauge carrying its accumulated value — any straggler gauge-as-counter
    call site keeps its arithmetic instead of crashing
    (MetricsRegistry.legacy_count)."""
    _obs.legacy_count(name, n)


def counter_totals() -> Dict[str, int]:
    """Accumulated event counts per name since process start (or last reset);
    includes gauges (by current value) — the historical surface reported
    gauges through this dict as signed increments."""
    return _obs.global_registry().counter_totals()


def reset_counters() -> None:
    _obs.global_registry().reset_counters()


@contextlib.contextmanager
def trace(trace_dir: Optional[str]) -> Iterator[None]:
    """Capture an xplane trace into trace_dir (no-op when trace_dir is falsy)."""
    if not trace_dir:
        yield
        return
    import jax.profiler

    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        _logger.info("wrote profiler trace to %s", trace_dir)
