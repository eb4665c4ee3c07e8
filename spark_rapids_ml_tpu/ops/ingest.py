#
# Zero-copy ingest plane (docs/design.md §6k).
#
# Every streamed fit used to stage each batch through
# `np.ascontiguousarray(X[s:e], dtype=dt)` — a host copy (and often a host
# dtype conversion) per batch even when the slice was already contiguous with
# the right layout. This module is the single staging point that replaces
# those calls (a tools/analysis fence bans new ones elsewhere in ops/):
#
#   * `stage_block` hands a CONTIGUOUS, device-castable slice straight to the
#     device-put path as a VIEW — no host copy, no host conversion; the
#     consuming accumulator kernels cast to the compute dtype as their first
#     in-program op (ops/streaming.py::_apply_chain / .astype), so layout and
#     dtype conversion ride the device, not the host.
#   * Exotic inputs (non-contiguous strides, dtypes whose device cast is not
#     bit-equal to the host cast) fall back to a COUNTED copy through a
#     reusable staging-buffer pool.
#
# The returned view is never written by this library, but on backends whose
# `device_put` ALIASES host memory (CPU jax shares the numpy buffer with the
# device array) a staging buffer must not be reused either — a later batch
# would overwrite the HBM-cache-resident tensor of an earlier one. The pool
# therefore only reuses buffers where device_put copies (TPU/GPU); on CPU it
# allocates per block, which is exactly what the pre-§6k path did. Where it
# does copy, the copy is ASYNCHRONOUS: device_put returns before the runtime
# has read the host buffer, so a buffer may be refilled only after the
# transfer that reads it has finished. The upload site owns that fence
# (ops/streaming.py::_batch_stream blocks on each batch's transfer before the
# slicer runs again), which is why ONE buffer per key is enough.
#
# Telemetry (docs/metrics.md): `ingest.bytes_zero_copy` / `ingest.bytes_copied`
# / `ingest.copies_avoided` / `ingest.host_convert_s` / `ingest.rows_staged`,
# plus the run report's `ingest` section with the §6f before/after
# bytes-per-row cost analysis.
#

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .. import config as _config
from ..observability import counter_inc as obs_counter_inc

__all__ = [
    "StagingPool",
    "process_local_span",
    "report_section",
    "resolve_staging_pool_rows",
    "stage_block",
    "stage_local_block",
]

_device_put_copies_cache: Optional[bool] = None


def _device_put_copies() -> bool:
    """Whether this backend's device_put COPIES host memory (TPU/GPU) rather
    than aliasing it (CPU). Gates staging-buffer reuse — see module header."""
    global _device_put_copies_cache
    if _device_put_copies_cache is None:
        import jax

        _device_put_copies_cache = jax.default_backend() != "cpu"
    return _device_put_copies_cache


def resolve_staging_pool_rows(n: Optional[int] = None,
                              d: Optional[int] = None) -> int:
    """`ingest.staging_pool_rows` resolution (host-side only, so cached traces
    never bake a stale choice): a non-zero config pin wins, then the tuning
    table (per (n, d) shape bucket), then the defaults-module geometry."""
    from .. import autotune as _autotune
    from ..autotune.defaults import INGEST_STAGING_POOL_ROWS

    pinned = int(_config.get("ingest.staging_pool_rows") or 0)
    if pinned > 0:
        return pinned
    tuned = _autotune.lookup("ingest.staging_pool_rows", n=n, d=d)
    if tuned:
        return int(tuned)
    return int(INGEST_STAGING_POOL_ROWS)


class StagingPool:
    """Reusable host staging buffers for the counted copy fallback: per
    (slot, dtype, trailing-shape) key, ONE buffer sized
    `resolve_staging_pool_rows()` rows (growing to the largest block seen).
    Contract with the caller: a block handed out here is device_put and its
    transfer WAITED FOR before `buffer()` is asked for the same key again
    (`_batch_stream` does; device_put alone returns before the host buffer is
    read). Reuse is disabled entirely where device_put aliases host memory
    (CPU) — there every `buffer()` call allocates fresh, preserving the
    pre-pool semantics HBM batch caching depends on."""

    def __init__(self, pool_rows: Optional[int] = None) -> None:
        self._pool_rows = pool_rows
        self._bufs: Dict[Tuple, np.ndarray] = {}

    def buffer(self, shape: Tuple[int, ...], dtype: Any,
               slot: Any = None) -> np.ndarray:
        rows = int(shape[0])
        tail = tuple(int(x) for x in shape[1:])
        if not _device_put_copies():
            return np.empty((rows,) + tail, dtype)
        if self._pool_rows is None:
            self._pool_rows = resolve_staging_pool_rows()
        key = (slot, np.dtype(dtype), tail)
        buf = self._bufs.get(key)
        if buf is None or buf.shape[0] < rows:
            buf = np.empty((max(rows, self._pool_rows),) + tail, dtype)
            self._bufs[key] = buf
        return buf[:rows]


def _device_castable(src: np.dtype, dst: np.dtype) -> bool:
    """Dtypes the accumulator kernels may cast IN-PROGRAM with results
    bit-identical to the host `astype` they replace: the identity cast, exact
    widenings, and small ints (<= 32 bit — both numpy and XLA convert with
    IEEE round-to-nearest-even, and int64 would be silently narrowed by dtype
    canonicalization before the kernel ever saw it)."""
    src, dst = np.dtype(src), np.dtype(dst)
    if src == dst:
        return True
    if src == np.bool_:
        return True
    if src.kind in ("i", "u") and src.itemsize <= 4:
        return True
    if src.kind == "f" and dst.kind == "f" and src.itemsize < dst.itemsize:
        return True  # exact widening (f16->f32, f32->f64)
    return False


def stage_block(arr: np.ndarray, s: int, e: int, dtype: Any,
                pool: Optional[StagingPool] = None, *, slot: Any = None,
                force_copy: bool = False) -> np.ndarray:
    """Stage rows [s, e) of a host array for device upload.

    Fast path: the slice is contiguous and `_device_castable` to the compute
    dtype -> return it as a zero-copy VIEW (the consumer casts on device).
    Fallback (counted): copy/convert into a staging-pool buffer. Callers that
    must OWN the block (host-side mutation, e.g. cosine normalization) pass
    `force_copy=True`."""
    blk = np.asarray(arr[s:e])
    dt = np.dtype(dtype)
    if blk.ndim >= 2:
        obs_counter_inc("ingest.rows_staged", blk.shape[0])
    if (
        not force_copy
        and bool(_config.get("ingest.zero_copy"))
        and blk.flags.c_contiguous
        and _device_castable(blk.dtype, dt)
    ):
        obs_counter_inc("ingest.copies_avoided", 1)
        obs_counter_inc("ingest.bytes_zero_copy", blk.nbytes)
        return blk
    t0 = time.perf_counter()
    if pool is not None:
        out = pool.buffer(blk.shape, dt, slot)
        np.copyto(out, blk, casting="unsafe")
    else:
        out = np.ascontiguousarray(blk, dtype=dt)
        if out is blk:
            # ascontiguousarray no-ops on a conforming block, but this branch
            # promises caller-owned memory (force_copy mutators, kill switch)
            out = blk.copy()
    obs_counter_inc("ingest.bytes_copied", out.nbytes)
    obs_counter_inc("ingest.host_convert_s", time.perf_counter() - t0)
    return out


def process_local_span(s: int, e: int, partitioner: Any = None
                       ) -> Tuple[int, int]:
    """The sub-range of global rows [s, e) owned by THIS process under the
    active Partitioner's contiguous rank layout (docs/design.md §10): rank r
    of P stages rows [s + r*ceil(rows/P), ...) — so in a multi-host fit no
    host ever materializes a global batch; each process feeds only its slice
    to `stage_block` and `Partitioner.shard_inputs` assembles the global
    array from the per-process pieces. Single-process this is [s, e)."""
    from ..parallel.partitioner import active_partitioner

    part = partitioner if partitioner is not None else active_partitioner()
    rows = max(0, int(e) - int(s))
    p = max(1, int(part.process_count))
    r = int(part.process_index)
    per = -(-rows // p)
    ls = min(rows, r * per)
    le = min(rows, ls + per)
    return int(s) + ls, int(s) + le


def stage_local_block(arr: np.ndarray, s: int, e: int, dtype: Any,
                      pool: Optional[StagingPool] = None, *, slot: Any = None,
                      force_copy: bool = False,
                      partitioner: Any = None) -> np.ndarray:
    """`stage_block` restricted to this process's slice of global rows
    [s, e) — the per-process local-batch ingest step of the multi-host path
    (the zero-copy/counted-copy accounting applies unchanged to the slice)."""
    ls, le = process_local_span(s, e, partitioner)
    return stage_block(arr, ls, le, dtype, pool, slot=slot,
                       force_copy=force_copy)


def count_conversion(nbytes: int, seconds: float) -> None:
    """Count a host conversion copy made OUTSIDE stage_block (the Arrow/pandas
    extraction fallbacks in core/dataset.py) into the same ingest ledger."""
    obs_counter_inc("ingest.bytes_copied", int(nbytes))
    obs_counter_inc("ingest.host_convert_s", float(seconds))


def report_section(registry: Any) -> Optional[Dict[str, Any]]:
    """The run report's `ingest` section (observability/runs.py): this run's
    zero-copy vs copied byte split and the §6f cost analysis — bytes-per-row
    BEFORE is what the pre-§6k path would have staged through host copies
    (every byte), AFTER is what actually copied."""
    try:
        zc = float(registry.counter("ingest.bytes_zero_copy").value())
        cp = float(registry.counter("ingest.bytes_copied").value())
        avoided = int(registry.counter("ingest.copies_avoided").value())
        secs = float(registry.counter("ingest.host_convert_s").value())
        rows = int(registry.counter("ingest.rows_staged").value())
    except Exception:  # report assembly is best-effort
        return None
    if rows <= 0 and zc == 0.0 and cp == 0.0:
        return None
    total = zc + cp
    return {
        "bytes_zero_copy": zc,
        "bytes_copied": cp,
        "copies_avoided": avoided,
        "host_convert_s": secs,
        "rows_staged": rows,
        "bytes_per_row_before": (total / rows) if rows else 0.0,
        "bytes_per_row_after": (cp / rows) if rows else 0.0,
    }
