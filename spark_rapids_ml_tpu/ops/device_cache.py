#
# HBM-resident batch cache for multi-pass streamed fits.
#
# The reference gets implicit cross-pass data reuse from cuDF/UVM residency on
# GPU (reference utils.py:184-241: once a managed-memory page is on device it
# stays there across Lloyd iterations and L-BFGS evaluations). The TPU rebuild
# has no UVM: every pass of a multi-pass streamed fit re-ran the full host
# slice -> pad -> shard_array ingest, so multi-pass fits were ingest-bound
# rather than compute-bound (arXiv:1612.01437 identifies exactly this
# host<->accelerator traffic as the dominant cost of Spark ML loops; DrJAX,
# arXiv:2403.07128, keeps sharded operands device-resident across MapReduce
# rounds the same way).
#
# This module makes the reuse explicit: on pass 1 of a multi-pass streamed fit
# the sharded device tuples yielded by ops/streaming._batch_stream (and the
# pairwise/item-tile generators) are RETAINED in HBM; passes 2..N replay them
# without touching the host. Contract:
#
#   * whole-batch granularity — a batch is cached as the exact tuple the
#     stream yielded, so replayed passes run the identical device ops on the
#     identical buffers and results are BIT-IDENTICAL to pure streaming
#     (tests/test_device_cache.py asserts this per estimator),
#   * keyed by (dataset identity, batch geometry, mesh shape) — dataset
#     identity pins the source host arrays for the cache lifetime so Python
#     id() reuse can never alias two datasets to one key,
#   * HBM byte budget (`cache.hbm_budget_bytes` / SRML_TPU_CACHE_BUDGET) with
#     LRU eviction ACROSS streams and prefix semantics WITHIN one: when a
#     dataset exceeds the budget the leading batches stay resident and the
#     tail streams every pass — that fraction of uploads is still saved, and
#     a stream never evicts its own batches (sequential replay would thrash),
#   * transparent to reliability: fault-injection sites fire before the cache
#     lookup (replayed batches are still fault-injectable) and checkpoint-
#     resume replays hits and misses through the same cursor arithmetic.
#
# Lifecycle: core/estimator.py opens a `batch_cache()` scope around each
# streamed fit and frees it at fit exit; ops-level multi-pass loops call
# `batch_cache()` themselves and transparently reuse the estimator's scope
# when one is active (direct ops calls get a fit-local cache instead).
#
# Observability (observability/ registry; legacy profiling.counter_totals()
# still surfaces everything): `cache.hits`, `cache.misses`, `cache.evictions`
# are monotone Counters; `cache.bytes_resident` is a REAL Gauge (inc on
# retain, dec on evict/close — it was negative counter increments before the
# typed registry existed, where a missed decrement was undetectable by type).
# Evictions also land as structured `cache_evict` events in the active FitRun.
# Host->device uploads are counted by the stream itself
# (`stream.upload_batches` / `stream.upload_bytes`) and each upload appears as
# a `stream.ingest` span in the fit trace tree, so "pass 2+ performs zero
# uploads" is directly assertable from a fit report.
#

from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Any, Dict, Iterator, Optional, Sequence, Tuple

from .. import config as _config
from .. import observability as _obs
from .. import profiling
from ..utils import get_logger

_logger = get_logger("ops.device_cache")

_tls = threading.local()

# (stream_key, batch_index) -> (batch_tuple, nbytes)
_EntryKey = Tuple[Any, int]


class DeviceBatchCache:
    """Single-owner (one fit, one thread) replay cache of streamed device
    batches. Use through `batch_cache()`; the raw class is exposed for the
    unit tests that pin down hit/miss/eviction accounting."""

    def __init__(self, budget_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self.bytes_resident = 0
        self._entries: "OrderedDict[_EntryKey, Tuple[tuple, int]]" = OrderedDict()
        # stream key -> source host arrays: pins the sources so id() reuse
        # cannot alias a freed dataset's key to a new array's key while this
        # cache lives
        self._key_pins: Dict[Any, Sequence[Any]] = {}
        # stream key -> pin count: a pinned stream's entries are NEVER evicted
        # (the serving plane pins a model's weights for the duration of each
        # in-flight batch; before this existed nothing stopped LRU pressure
        # from evicting a tuple a concurrent reader still referenced)
        self._pin_counts: Dict[Any, int] = {}

    def stream_key(self, arrays: Sequence[Any], batch_rows: int, mesh,
                   site: str = "ingest") -> Any:
        """Identity of one replayable stream: the source arrays (by pinned
        id), the batch geometry, and the mesh TOPOLOGY — axis shape and names,
        not just the device set: two meshes over the same devices shard
        differently, and a tuple sharded for one must never replay on the
        other."""
        mesh_id: Tuple[Any, ...]
        if mesh is None:
            mesh_id = ("nomesh",)
        else:
            mesh_id = (
                tuple(mesh.devices.shape),
                tuple(str(a) for a in mesh.axis_names),
                tuple(int(d.id) for d in mesh.devices.flat),
            )
        key = (site, tuple(id(a) for a in arrays), int(batch_rows), mesh_id)
        self._key_pins.setdefault(key, tuple(arrays))
        return key

    def contains(self, stream_key: Any, batch_index: int) -> bool:
        """Residency probe: no hit/miss counting, no LRU touch (stats views
        must not promote an entry they only looked at)."""
        return (stream_key, batch_index) in self._entries

    def get(self, stream_key: Any, batch_index: int) -> Optional[tuple]:
        """Resident batch tuple, or None (counted as hit/miss)."""
        entry = self._entries.get((stream_key, batch_index))
        if entry is None:
            profiling.count("cache.misses")
            return None
        self._entries.move_to_end((stream_key, batch_index))
        profiling.count("cache.hits")
        return entry[0]

    def pin(self, stream_key: Any) -> None:
        """Hold this stream's entries resident: eviction skips pinned streams
        (counted as `cache.evict_skipped_pinned`). Pins nest — a stream is
        evictable again only once every pin() has been matched by unpin()."""
        self._pin_counts[stream_key] = self._pin_counts.get(stream_key, 0) + 1

    def unpin(self, stream_key: Any) -> None:
        n = self._pin_counts.get(stream_key, 0) - 1
        if n <= 0:
            self._pin_counts.pop(stream_key, None)
        else:
            self._pin_counts[stream_key] = n

    def is_pinned(self, stream_key: Any) -> bool:
        return self._pin_counts.get(stream_key, 0) > 0

    def put(self, stream_key: Any, batch_index: int, batch: tuple) -> bool:
        """Retain a freshly-streamed batch. Evicts LRU entries of OTHER
        streams under budget pressure; never evicts the inserting stream's own
        batches (prefix semantics: cache the head, stream the tail) and never
        evicts a PINNED stream's batches (a reader is mid-flight on them —
        each skip counts `cache.evict_skipped_pinned`)."""
        if (stream_key, batch_index) in self._entries:
            return True  # a resumed pass replayed a batch already resident
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in batch)
        if nbytes > self.budget_bytes:
            return False
        # skipped pinned entries count ONCE per put() — the eviction loop
        # rescans from the head every pass, and re-counting the same pinned
        # entry each pass would overstate pin pressure E-fold
        skip_counted: set = set()
        while self.bytes_resident + nbytes > self.budget_bytes:
            victim = None
            for k in self._entries:
                if k[0] == stream_key:
                    continue
                if self.is_pinned(k[0]):
                    if k not in skip_counted:
                        skip_counted.add(k)
                        profiling.count("cache.evict_skipped_pinned")
                    continue
                victim = k
                break
            if victim is None:
                return False  # only own-prefix/pinned entries remain: stream
            self._evict(victim)
        self._entries[(stream_key, batch_index)] = (batch, nbytes)
        self.bytes_resident += nbytes
        _obs.gauge_inc("cache.bytes_resident", nbytes)
        return True

    def replace(self, stream_key: Any, batch_index: int, batch: tuple) -> bool:
        """Swap one entry's tuple in place, PRESERVING its pin counts — the
        serving plane's weight refresh (§7b) runs while other batches may
        hold pins on the same stream; a drop_stream + put would pop the pin
        bookkeeping and leave the fresh weights evictable mid-batch."""
        key = (stream_key, batch_index)
        old = self._entries.pop(key, None)
        if old is not None:
            _, old_bytes = old
            self.bytes_resident -= old_bytes
            _obs.gauge_dec("cache.bytes_resident", old_bytes)
        return self.put(stream_key, batch_index, batch)

    def _evict(self, entry_key: _EntryKey) -> None:
        _, nbytes = self._entries.pop(entry_key)
        self.bytes_resident -= nbytes
        profiling.count("cache.evictions")
        _obs.gauge_dec("cache.bytes_resident", nbytes)
        _obs.event("cache_evict", nbytes=nbytes, site=str(entry_key[0][0]))

    def resident_batches(self) -> int:
        return len(self._entries)

    def drop_stream(self, stream_key: Any) -> int:
        """Release every entry of one stream (lifecycle free — NOT counted as
        eviction pressure) and its source/pin bookkeeping. Returns the bytes
        released. The serving plane uses this when a model unregisters."""
        freed = 0
        for ek in [k for k in self._entries if k[0] == stream_key]:
            _, nbytes = self._entries.pop(ek)
            freed += nbytes
        if freed:
            self.bytes_resident -= freed
            _obs.gauge_dec("cache.bytes_resident", freed)
        self._key_pins.pop(stream_key, None)
        self._pin_counts.pop(stream_key, None)
        return freed

    def close(self) -> None:
        """Drop every device reference (the HBM frees once the accumulators
        release their last use) and unpin the sources. Not counted as
        evictions — lifecycle frees are not budget pressure."""
        if self.bytes_resident:
            _obs.gauge_dec("cache.bytes_resident", self.bytes_resident)
        self.bytes_resident = 0
        self._entries.clear()
        self._key_pins.clear()
        self._pin_counts.clear()


def cached_build(cache: Optional[DeviceBatchCache], cache_key: Any,
                 batch_index: int, site: str, build: Any) -> tuple:
    """THE cache-or-upload protocol, shared by every streamed batch/tile
    generator (ops/streaming.py::_batch_stream, the pairwise item-block
    generators): a resident batch replays as-is; otherwise `build()` runs the
    host slice/pad/upload, its cost lands in `stream.ingest_s.<site>`
    (span_totals) and the `stream.upload_batches`/`stream.upload_bytes`
    counters, and the fresh batch is retained budget-permitting. One
    implementation so the "zero pass-2 uploads" accounting CI asserts on can
    never drift between the tiers. The caller's fault point fires BEFORE this
    (replayed batches stay fault-injectable)."""
    if cache is not None:
        hit = cache.get(cache_key, batch_index)
        if hit is not None:
            return hit
    # each actual upload is a `stream.ingest` node in the fit trace tree (child
    # of the pass that triggered it); the per-site span inside it keeps the
    # per-site totals + per-batch latency histogram
    # srml-metric: stream.ingest_s — per-site span family (dynamic suffix)
    with _obs.span("stream.ingest", {"site": site, "batch": batch_index}), \
            _obs.span(f"stream.ingest_s.{site}"):
        batch = build()
    profiling.count("stream.upload_batches")
    profiling.count(
        "stream.upload_bytes",
        sum(int(a.nbytes) for a in batch if hasattr(a, "nbytes")),
    )
    if cache is not None:
        cache.put(cache_key, batch_index, batch)
    return batch


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def active_cache() -> Optional[DeviceBatchCache]:
    """The innermost open batch_cache() scope on this thread, if any."""
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def batch_cache() -> Iterator[Optional[DeviceBatchCache]]:
    """Per-fit cache scope. The OUTERMOST scope owns the cache (creates it
    from config, frees it on exit — core/estimator.py opens one around each
    streamed fit); nested scopes (the multi-pass loops in ops/) reuse the
    owner's cache so one fit's passes share residency. Yields None when
    `cache.enabled` is off or the budget is <= 0 — callers then stream every
    pass, the pre-cache behavior."""
    existing = active_cache()
    if existing is not None:
        yield existing
        return
    if not bool(_config.get("cache.enabled")):
        yield None
        return
    # the byte budget (the cache-head/stream-tail prefix split) is a tuning-
    # table knob (`cache.budget_bytes`, docs/design.md §6i); config set()/env
    # on cache.hbm_budget_bytes still win, per the resolution-order contract
    from .. import autotune as _autotune

    tuned = _autotune.lookup("cache.budget_bytes")
    budget = (
        int(tuned) if tuned is not None
        else int(_config.get("cache.hbm_budget_bytes") or 0)
    )
    if budget <= 0:
        yield None
        return
    cache = DeviceBatchCache(budget)
    _stack().append(cache)
    try:
        yield cache
    finally:
        _stack().remove(cache)
        cache.close()
