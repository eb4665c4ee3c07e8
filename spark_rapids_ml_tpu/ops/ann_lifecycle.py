#
# ANN index lifecycle: on-disk persistence, lazy device residency, and
# incremental add/delete with tombstone compaction (docs/design.md §7b).
#
# The selection/Pallas planes made ANN *search* fast; this module makes the
# index itself a managed artifact instead of a fit-once in-memory dict:
#
#   * ON-DISK FORMAT — a versioned directory of one `.npy` file per index
#     array plus a `MANIFEST.json` written LAST via tmp + os.replace (an
#     atomic write): the manifest IS the commit
#     point, so a reader never observes a torn index. Arrays load back as
#     copy-on-write memmaps (`np.load(mmap_mode="c")`): load() touches no
#     array bytes — pages fault in as searches (or mutations) reach them.
#   * LAZY DEVICE RESIDENCY — `DeviceIndexCache` uploads one named segment
#     (centers, cells, codes, ...) to HBM on FIRST use and replays it on
#     every later search (backed by ops/device_cache.py::DeviceBatchCache,
#     budget `ann.index_cache_bytes`). Cold-start after load() therefore
#     uploads only what the first search actually probes; mutation
#     invalidates exactly the segments it touched.
#   * INCREMENTAL MAINTENANCE — host-side appends into the dense IVF list
#     layout with BUCKETED capacity (max_cell rounds up to a power of two >=
#     `ann.list_bucket_rows`), so in-slack adds never change the search
#     executable's operand shapes: a live served model absorbs them with
#     zero new `device.compile{kernel=}` entries. Deletes tombstone a slot by
#     writing its `cell_ids` entry to -1 — the same sentinel every probe scan
#     already masks to INVALID_D2 — and compaction re-layouts the lists once
#     tombstones exceed `ann.compact_tombstone_pct` of occupied slots.
#
# Assignment/encoding of added rows runs in HOST numpy on purpose: routing a
# handful of new rows through the device kernels would mint one fresh
# (kernel, shape) AOT compile per add-batch size — exactly the storm the
# bucketed geometry exists to prevent. Add-path assignment quality matches
# the build's (same argmin over the same centers); it is not bit-coupled to
# the device matmul and does not need to be (cell membership is a recall
# knob, not a distance contract).
#

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..observability import span as obs_span
from ..observability.runs import (
    counter_inc as obs_counter_inc,
    gauge_set as obs_gauge_set,
)
from ..utils import get_logger

_logger = get_logger("ops.ann_lifecycle")

ANN_FORMAT_VERSION = 1
MANIFEST_NAME = "MANIFEST.json"


# --------------------------------------------------------------- on-disk store


def _atomic_write(path: str, data: bytes) -> None:
    """tmp + os.replace; a reader never sees a torn file."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _write_array(dirname: str, name: str, arr: np.ndarray) -> Dict[str, Any]:
    """One array -> one mmap-friendly `.npy` segment file, atomically."""
    arr = np.ascontiguousarray(arr)  # noqa: fence/host-staging-copy
    fname = f"{name}.npy"
    fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.save(f, arr)
        os.replace(tmp, os.path.join(dirname, fname))
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return {
        "file": fname,
        "shape": list(arr.shape),
        "dtype": str(arr.dtype),
        "nbytes": int(arr.nbytes),
    }


def save_index(path: str, arrays: Dict[str, np.ndarray], *, algo: str,
               meta: Optional[Dict[str, Any]] = None) -> str:
    """Persist one index as a directory of per-array segment files + a
    manifest. The manifest is written LAST (atomic tmp + os.replace): until it
    lands, a concurrent reader sees the PREVIOUS generation; array files are
    themselves replaced atomically, so re-saving over a live directory is a
    generation bump, not a torn state. Returns the manifest path."""
    with obs_span("ann.index_save", {"algo": algo, "arrays": len(arrays)}):
        os.makedirs(path, exist_ok=True)
        prev_gen = 0
        try:
            prev = read_manifest(path)
            prev_gen = int(prev.get("generation", 0))
        except (FileNotFoundError, ValueError):
            pass
        manifest: Dict[str, Any] = {
            "version": ANN_FORMAT_VERSION,
            "algo": str(algo),
            "generation": prev_gen + 1,
            "updated_ts": round(time.time(), 3),
            "arrays": {},
            "meta": dict(meta or {}),
        }
        for name, arr in arrays.items():
            if arr is None:
                continue
            manifest["arrays"][name] = _write_array(path, name, np.asarray(arr))
        mpath = os.path.join(path, MANIFEST_NAME)
        _atomic_write(
            mpath, json.dumps(manifest, indent=1, sort_keys=True).encode()
        )
    obs_counter_inc("ann.index_saves", 1, algo=str(algo))
    return mpath


def read_manifest(path: str) -> Dict[str, Any]:
    mpath = os.path.join(path, MANIFEST_NAME)
    with open(mpath) as f:
        try:
            doc = json.load(f)
        except json.JSONDecodeError as e:
            raise ValueError(f"corrupt ANN index manifest {mpath}: {e}") from e
    if not isinstance(doc, dict) or not isinstance(doc.get("arrays"), dict):
        raise ValueError(f"ANN index manifest {mpath} is not an index manifest")
    if doc.get("version") != ANN_FORMAT_VERSION:
        raise ValueError(
            f"ANN index at {path} has format version {doc.get('version')}; "
            f"this library reads version {ANN_FORMAT_VERSION}"
        )
    return doc


def load_index(path: str, *, mmap: bool = True
               ) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """Open a saved index: returns ({name: array}, manifest). With mmap=True
    (the default) arrays are copy-on-write memmaps — no array bytes are read
    here; pages fault in lazily as searches reach them, and in-memory
    mutation (incremental add/delete) never writes back to the files (a
    mutated index persists only through an explicit save)."""
    with obs_span("ann.index_load", {"path": os.path.basename(path)}):
        manifest = read_manifest(path)
        arrays: Dict[str, np.ndarray] = {}
        for name, spec in manifest["arrays"].items():
            fpath = os.path.join(path, spec["file"])
            arr = np.load(fpath, mmap_mode="c" if mmap else None)
            if list(arr.shape) != list(spec["shape"]) or str(arr.dtype) != spec["dtype"]:
                raise ValueError(
                    f"ANN index segment {fpath} does not match its manifest "
                    f"entry (shape {list(arr.shape)} vs {spec['shape']}, "
                    f"dtype {arr.dtype} vs {spec['dtype']})"
                )
            arrays[name] = arr
    obs_counter_inc("ann.index_loads", 1, algo=str(manifest.get("algo")))
    return arrays, manifest


# ------------------------------------------------------- lazy device residency


class DeviceIndexCache:
    """Per-index lazy HBM residency: each named segment (centers, cells,
    cell_ids, codes, ...) uploads on FIRST `get` and replays from the device
    cache on every later search — repeated kneighbors calls stop paying the
    host->device index transfer, and an index loaded from disk stages only
    the segments the first search actually touches. Single-owner like the
    underlying DeviceBatchCache (one model object, its search calls)."""

    def __init__(self, budget_bytes: Optional[int] = None):
        from .. import config as _config
        from .device_cache import DeviceBatchCache

        budget = int(
            budget_bytes if budget_bytes is not None
            else _config.get("ann.index_cache_bytes")
        )
        self._cache = DeviceBatchCache(max(budget, 0))

    def get(self, name: str, host_array: Any):
        """The device copy of one segment (uploading on first use)."""
        import jax.numpy as jnp

        key = ("ann_index", name)
        hit = self._cache.get(key, 0)
        if hit is not None:
            return hit[0]
        dev = jnp.asarray(host_array)
        obs_counter_inc("ann.device_loads", 1, attr=name)
        obs_counter_inc(
            "ann.device_load_bytes", int(getattr(host_array, "nbytes", 0)),
            attr=name,
        )
        self._cache.put(key, 0, (dev,))
        return dev

    def invalidate(self, *names: str) -> None:
        """Drop segments a mutation touched; the next search re-uploads."""
        for name in names:
            self._cache.drop_stream(("ann_index", name))

    def close(self) -> None:
        self._cache.close()


# -------------------------------------------------- bucketed list geometry


def resolve_list_bucket_rows() -> int:
    """`ann.list_bucket_rows` resolution: non-zero config pin >
    defaults-module floor."""
    from .. import config as _config
    from ..defaults import ANN_LIST_BUCKET_MIN_ROWS

    pinned = int(_config.get("ann.list_bucket_rows") or 0)
    if pinned > 0:
        return pinned
    return int(ANN_LIST_BUCKET_MIN_ROWS)


def resolve_compact_tombstone_pct() -> int:
    """`ann.compact_tombstone_pct` resolution (config pin > default)."""
    from .. import config as _config
    from ..defaults import ANN_COMPACT_TOMBSTONE_PCT

    if _config.source("ann.compact_tombstone_pct") != "default":
        return int(_config.get("ann.compact_tombstone_pct"))
    return int(ANN_COMPACT_TOMBSTONE_PCT)


def bucket_capacity(rows: int, min_rows: Optional[int] = None) -> int:
    """Power-of-two capacity >= rows, floored at the bucket knob: the bucketed
    geometry is what lets an in-slack add keep every compiled search
    executable's operand shapes — and therefore the AOT cache — unchanged."""
    floor = int(min_rows) if min_rows is not None else resolve_list_bucket_rows()
    rows = max(int(rows), 1)
    cap = 1 << (rows - 1).bit_length()
    return max(cap, floor)


# ------------------------------------------------------ incremental add/delete


class MutableIvfState:
    """Host bookkeeping of a mutable IVF layout: per-item cell assignment,
    per-cell fill pointers (slots [0, fill) are live-or-tombstoned; [fill,
    max_cell) are virgin slack) and the tombstone count the compaction
    trigger watches. Derived from a built layout on first mutation; persists
    through the index store as the `cell_fill` / `item_cells` arrays plus the
    manifest's `tombstones` meta."""

    def __init__(self, item_cells: np.ndarray, cell_fill: np.ndarray,
                 tombstones: int = 0):
        self.item_cells = np.asarray(item_cells, np.int32).copy()
        self.cell_fill = np.asarray(cell_fill, np.int32).copy()
        self.tombstones = int(tombstones)

    @classmethod
    def from_layout(cls, cell_ids: np.ndarray, n_items: int
                    ) -> "MutableIvfState":
        """Reconstruct bookkeeping from a dense layout: fill = highest live
        slot + 1 per cell (fresh builds are hole-free, so this equals the
        cell size), item->cell from one scan of cell_ids."""
        cell_ids = np.asarray(cell_ids)
        nlist, max_cell = cell_ids.shape
        live = cell_ids >= 0
        # fill pointer: one past the last live slot (0 for empty cells)
        rev = live[:, ::-1]
        has = rev.any(axis=1)
        fill = np.where(has, max_cell - rev.argmax(axis=1), 0)
        item_cells = np.full((int(n_items),), -1, np.int32)
        cells_of = np.repeat(np.arange(nlist), max_cell).reshape(nlist, max_cell)
        item_cells[cell_ids[live]] = cells_of[live].astype(np.int32)  # noqa: fence/host-staging-copy
        return cls(item_cells, fill.astype(np.int32), tombstones=0)

    def live_items(self) -> int:
        return int((self.item_cells >= 0).sum())


def ivf_assign_host(X_new: np.ndarray, centers: np.ndarray) -> np.ndarray:
    """Nearest-center assignment in host numpy — the add path's deliberate
    device-free twin of kmeans_predict (see the module header: a device call
    here would compile once per add-batch shape)."""
    X_new = np.asarray(X_new, np.float32)
    centers = np.asarray(centers, np.float32)
    x2 = np.sum(X_new * X_new, axis=1)[:, None]
    c2 = np.sum(centers * centers, axis=1)[None, :]
    d2 = x2 - 2.0 * (X_new @ centers.T) + c2
    return np.argmin(d2, axis=1).astype(np.int32)


def pq_encode_host(resid: np.ndarray, codebooks: np.ndarray) -> np.ndarray:
    """Host PQ encoding of residuals: per-subvector nearest codeword (the
    add-path twin of the streamed encoding passes)."""
    resid = np.asarray(resid, np.float32)
    m, n_codes, sub_d = codebooks.shape
    out = np.zeros((resid.shape[0], m), np.uint8)
    for m_i in range(m):
        sub = resid[:, m_i * sub_d : (m_i + 1) * sub_d]
        cb = codebooks[m_i]
        d2 = (
            np.sum(sub * sub, axis=1)[:, None]
            - 2.0 * (sub @ cb.T)
            + np.sum(cb * cb, axis=1)[None, :]
        )
        out[:, m_i] = np.argmin(d2, axis=1).astype(np.uint8)
    return out


def _grow_layout(attrs: Dict[str, Any], new_max_cell: int) -> None:
    """Re-allocate the dense list arrays at a larger bucketed capacity (one
    new search-executable shape — the amortized cost in-slack adds avoid)."""
    cells = np.asarray(attrs["cells"])
    cell_ids = np.asarray(attrs["cell_ids"])
    nlist, max_cell, d = cells.shape
    grown = np.zeros((nlist, new_max_cell, d), cells.dtype)
    grown[:, :max_cell] = cells
    grown_ids = np.full((nlist, new_max_cell), -1, cell_ids.dtype)
    grown_ids[:, :max_cell] = cell_ids
    attrs["cells"] = grown
    attrs["cell_ids"] = grown_ids
    if "codes" in attrs and attrs.get("codes") is not None:
        codes = np.asarray(attrs["codes"])
        grown_codes = np.zeros(
            (nlist, new_max_cell, codes.shape[2]), codes.dtype
        )
        grown_codes[:, :max_cell] = codes
        attrs["codes"] = grown_codes
    obs_counter_inc("ann.list_grows", 1)


def rebucket_layout(attrs: Dict[str, Any], *, slack_rows: int = 0) -> bool:
    """Round the list capacity up to its bucket (plus optional extra slack):
    called once when an index becomes mutable — paying the single shape
    change BEFORE a model is served is what makes later adds compile-free.
    Returns True when the layout grew."""
    cell_ids = np.asarray(attrs["cell_ids"])
    max_cell = cell_ids.shape[1]
    target = bucket_capacity(max_cell + int(slack_rows))
    if target <= max_cell:
        return False
    _grow_layout(attrs, target)
    return True


def ivf_add(attrs: Dict[str, Any], state: MutableIvfState,
            X_new: np.ndarray, positions: np.ndarray, *,
            cosine: bool = False) -> None:
    """Append rows into the IVF lists. Tombstoned slots are reused first
    (they sit below the fill pointer), then virgin slack; a cell out of both
    grows the whole layout to the next capacity bucket. Mutates `attrs`
    (cells / cell_ids / cell_sizes / codes) and `state` in place; `positions`
    are the new rows' item positions (the caller owns the position->user-id
    mapping)."""
    from .knn import normalize_rows_or_raise

    X_new = np.ascontiguousarray(np.asarray(X_new), np.float32)  # noqa: fence/host-staging-copy
    if cosine:
        X_new = normalize_rows_or_raise(X_new)
    positions = np.asarray(positions, np.int64)
    if X_new.shape[0] != positions.shape[0]:
        raise ValueError(
            f"{X_new.shape[0]} rows but {positions.shape[0]} positions"
        )
    centers = np.asarray(attrs["centers"])
    assign = ivf_assign_host(X_new, centers)

    # capacity: every target cell must fit its new rows in holes + slack
    cell_ids = np.asarray(attrs["cell_ids"])
    nlist, max_cell = cell_ids.shape
    add_counts = np.bincount(assign, minlength=nlist)
    holes = np.zeros((nlist,), np.int64)
    for c in np.unique(assign):
        holes[c] = int((cell_ids[c, : state.cell_fill[c]] < 0).sum())
    free = holes + (max_cell - state.cell_fill)
    if np.any(add_counts > free):
        needed = int((state.cell_fill + np.maximum(add_counts - holes, 0)).max())
        _grow_layout(attrs, bucket_capacity(needed))
        cell_ids = np.asarray(attrs["cell_ids"])
        max_cell = cell_ids.shape[1]

    cells = np.asarray(attrs["cells"])
    cell_sizes = np.asarray(attrs["cell_sizes"])
    codes = attrs.get("codes")
    codebooks = attrs.get("codebooks")
    new_codes = None
    if codes is not None and codebooks is not None:
        new_codes = pq_encode_host(
            X_new - centers[assign], np.asarray(codebooks)
        )
    for c in np.unique(assign):
        rows = np.nonzero(assign == c)[0]
        fill = int(state.cell_fill[c])
        hole_slots = np.nonzero(cell_ids[c, :fill] < 0)[0][: len(rows)]
        n_virgin = len(rows) - len(hole_slots)
        virgin_slots = np.arange(fill, fill + n_virgin)
        slots = np.concatenate([hole_slots, virgin_slots]).astype(np.int64)
        cells[c, slots] = X_new[rows]
        cell_ids[c, slots] = positions[rows]
        if new_codes is not None:
            np.asarray(attrs["codes"])[c, slots] = new_codes[rows]
        state.cell_fill[c] = fill + n_virgin
        state.tombstones -= len(hole_slots)
        cell_sizes[c] += len(rows)
    attrs["cells"] = cells
    attrs["cell_ids"] = cell_ids
    attrs["cell_sizes"] = cell_sizes

    grown_items = np.full(
        (max(int(positions.max()) + 1, len(state.item_cells)),), -1, np.int32
    )
    grown_items[: len(state.item_cells)] = state.item_cells
    grown_items[positions] = assign
    state.item_cells = grown_items
    obs_counter_inc("ann.items_added", int(len(positions)))
    obs_gauge_set("ann.tombstones", max(state.tombstones, 0))


def ivf_delete(attrs: Dict[str, Any], state: MutableIvfState,
               positions: np.ndarray) -> int:
    """Tombstone items by position: their `cell_ids` slots flip to -1 — the
    sentinel every probe scan already masks to INVALID_D2, so deleted items
    vanish from search results with no kernel or shape change. Returns how
    many positions were actually live."""
    positions = np.unique(np.asarray(positions, np.int64))
    cell_ids = np.asarray(attrs["cell_ids"])
    cell_sizes = np.asarray(attrs["cell_sizes"])
    deleted = 0
    for pos in positions:
        if pos < 0 or pos >= len(state.item_cells):
            continue
        c = int(state.item_cells[pos])
        if c < 0:
            continue
        slots = np.nonzero(cell_ids[c] == pos)[0]
        if len(slots) == 0:
            continue
        cell_ids[c, slots] = -1
        cell_sizes[c] -= len(slots)
        state.item_cells[pos] = -1
        state.tombstones += len(slots)
        deleted += 1
    attrs["cell_ids"] = cell_ids
    attrs["cell_sizes"] = cell_sizes
    if deleted:
        obs_counter_inc("ann.items_deleted", deleted)
        obs_gauge_set("ann.tombstones", max(state.tombstones, 0))
    return deleted


def needs_compaction(state: MutableIvfState) -> bool:
    """Compaction trigger: tombstoned slots exceed `ann.compact_tombstone_pct`
    of occupied (live + tombstoned) slots."""
    occupied = state.live_items() + max(state.tombstones, 0)
    if occupied <= 0 or state.tombstones <= 0:
        return False
    pct = resolve_compact_tombstone_pct()
    return 100 * state.tombstones > pct * occupied


def ivf_compact(attrs: Dict[str, Any], state: MutableIvfState) -> None:
    """Re-layout the lists without their tombstoned slots (centers untouched
    — compaction never refits the coarse quantizer). Capacity re-buckets to
    the live maximum, so a heavily-deleted index shrinks its scan width."""
    cells = np.asarray(attrs["cells"])
    cell_ids = np.asarray(attrs["cell_ids"])
    nlist, max_cell, d = cells.shape
    live_sizes = (cell_ids >= 0).sum(axis=1)
    new_max = bucket_capacity(int(live_sizes.max()) if nlist else 1)
    new_cells = np.zeros((nlist, new_max, d), cells.dtype)
    new_ids = np.full((nlist, new_max), -1, cell_ids.dtype)
    codes = attrs.get("codes")
    new_codes = (
        np.zeros((nlist, new_max, np.asarray(codes).shape[2]),
                 np.asarray(codes).dtype)
        if codes is not None else None
    )
    for c in range(nlist):
        slots = np.nonzero(cell_ids[c] >= 0)[0]
        m = len(slots)
        new_cells[c, :m] = cells[c, slots]
        new_ids[c, :m] = cell_ids[c, slots]
        if new_codes is not None:
            new_codes[c, :m] = np.asarray(codes)[c, slots]
    attrs["cells"] = new_cells
    attrs["cell_ids"] = new_ids
    attrs["cell_sizes"] = live_sizes.astype(np.int32)
    if new_codes is not None:
        attrs["codes"] = new_codes
    state.cell_fill = live_sizes.astype(np.int32)
    state.tombstones = 0
    obs_counter_inc("ann.compactions", 1)
    obs_gauge_set("ann.tombstones", 0)
