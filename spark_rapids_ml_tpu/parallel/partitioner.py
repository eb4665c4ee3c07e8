#
# The Partitioner — single owner of every sharding decision (L2; the
# multi-host completion of the mesh runtime, docs/design.md §10).
#
# Before this module, NamedSharding/device_put construction was scattered
# across ~10 files in ops/ and models/, every one assuming a single process
# owning the whole mesh. The Partitioner centralizes that: it owns the Mesh,
# the data/state PartitionSpecs, and the host->device placement entry points,
# so ops and models never build shardings themselves — they ask the active
# Partitioner (or pass its mesh through, which resolves back here via
# `shard_rows`/`replicate_rows`).
#
# The multi-host contract (DrJAX's MapReduce decomposition, arXiv:2403.07128;
# Podracer's per-process feed -> pod-wide SPMD step split, arXiv:2104.06272):
#   * each process stages ONLY its local rows — `shard_inputs` uses
#     jax.make_array_from_process_local_data, so no host ever gathers a
#     global array (that is the perf win at pod scale: ingest bandwidth
#     scales with the pod, collective bytes stay proportional to MODEL size);
#   * the fit program itself is unchanged: XLA inserts the cross-host
#     collectives when the jitted program runs over the pod-spanning mesh,
#     which is why the 2-process emulated fit is bit-identical to the
#     single-process fit (same global array, same mesh, same HLO).
#
# Precedence for "which partitioner is active":
#   1. an explicitly installed partitioner (`set_partitioner` /
#      `use_partitioner`) — the multi-host barrier task installs one built
#      from rendezvous rank info;
#   2. otherwise a cached default DataParallelPartitioner over `num_workers`
#      devices (all addressable devices when unspecified), which reuses
#      mesh.get_mesh's cached default mesh so single-process placement is
#      bit-identical to the pre-Partitioner path.
#

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, Sharding, SingleDeviceSharding

from .. import config as _config
from .. import observability as _obs
from ..observability.device import hbm_free_bytes as _free_bytes
from .mesh import DATA_AXIS, FEATURE_AXIS, get_mesh

ROW_MULTIPLE = 8  # float32 sublane tile; keeps per-device shards MXU-friendly


# ---------------------------------------------------------- chunked upload
#
# One `device_put` of a 4.29 GB table runs at 10 GB/s while the runtime's
# transfer threads contend inside the one transfer; the same bytes as row
# chunks, all in flight at once, run at 13 to 14 GB/s for the same CPU seconds
# (tools/upload_probe.py; PERF.md §6, PRs 36 and 37). So a sited put of a
# large host array onto ONE device goes up as contiguous row chunks,
# dispatched from the calling thread, each written as it lands into a
# preallocated array of the whole shape by a compiled `dynamic_update_slice`
# that the array is donated to (`jit_h2d_place`): the result is the array a
# single put would have made, in shape, dtype, sharding, committedness and
# layout, and HBM holds the table and the chunks in flight (1.5 tables at the
# peak, where one concatenate of the chunks holds 2.0 and ends 0.01 s later).
# Nothing is waited for here. A placement over several devices keeps the one
# sharded put (`reason=devices`): no path of this program that chunks a
# device's rows has run on a host of several chips. The probe's has
# (tools/upload_probe.py `mesh`; PERF.md §6 PR 37, ROADMAP S12(f)): there the
# sharded put is the slow one by far, so that gate is the next to lift.
# There is no setting: the path follows what the input shows.

CHUNK_BYTES = 32 << 20       # a row chunk, at most: 0.310-0.313 s for 4.29 GB at 128, 256 and 3000
                             # columns; 64 MiB 0.314-0.337, 16 MiB 0.306-0.309 with the caller's
                             # dispatch at the transfer's own pace, 8 MiB behind it (0.33-0.47)
CHUNK_MIN_BYTES = 256 << 20  # an array under this goes up in one put
CHUNK_ALIGN_ROWS = 1024      # chunks are whole tiles of either layout, but the last (rows so
                             # wide that 1,024 pass CHUNK_BYTES: a power of two of them)

_layout_refused: set = set()  # (shape, dtype, device) whose assembly missed the layout


def h2d_place(whole, chunk, start):
    """`chunk` written into `whole` from row `start` on; the next chunk's row."""
    placed = jax.lax.dynamic_update_slice_in_dim(whole, chunk, start, axis=0)
    return placed, start + chunk.shape[0]


_place = jax.jit(h2d_place, donate_argnums=(0, 2))
_identity = jax.jit(lambda a: a)


@functools.lru_cache(maxsize=None)
def _default_layout(shape: Tuple[int, ...], dtype: Any, device: Any) -> Any:
    """The layout the runtime gives an array of `shape` on `device`: what a
    whole `device_put` gets and every compiled program expects."""
    struct = jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(device))
    return _identity.lower(struct).compile().input_formats[0][0].layout


def _host_aliased(device: Any) -> bool:
    """The CPU backend's `device_put` aliases host memory: no transfer to
    keep in flight."""
    return device.platform == "cpu"


def _whole_put(x: Any, target: Any) -> jax.Array:
    """ONE `device_put` of `x`: as `target` says (a sharding or a device), or
    uncommitted on the default device where it is None."""
    if target is None:
        return jax.device_put(jax.numpy.asarray(x))
    return jax.device_put(x, target)


def _one_device(target: Any) -> Any:
    """The device a placement on ONE device puts on (the default device for
    None); None where `target` spreads the array over several."""
    if not isinstance(target, Sharding):
        return target if target is not None else jax.local_devices()[0]
    (device, *more) = target.device_set
    return None if more else device


def _chunk_rows(row_bytes: int, chunk_bytes: int) -> int:
    """Rows a chunk: `chunk_bytes` at most (but one row is the least), whole
    multiples of CHUNK_ALIGN_ROWS, or the power of two under it where the
    rows are so wide that fewer fit."""
    per = max(1, chunk_bytes // row_bytes)
    if per >= CHUNK_ALIGN_ROWS:
        return per - per % CHUNK_ALIGN_ROWS
    return 1 << (per.bit_length() - 1)


def _single_put_reason(x: Any, target: Any, min_bytes: int) -> Optional[str]:
    """Why `x` goes up in ONE put, in the order asked; None where it goes up
    in row chunks (`h2d.chunk_gate`, docs/metrics.md)."""
    if int(getattr(x, "nbytes", 0)) < min_bytes:
        return "bytes"
    if not isinstance(x, np.ndarray):
        return "source"  # already on a device, or no buffer to slice
    if jax.process_count() > 1:
        return "multiprocess"
    device = _one_device(target)
    if device is None:
        return "devices"
    if _host_aliased(device):
        return "platform"
    free = _free_bytes(device)
    if free is not None and free < 2 * x.nbytes:
        return "memory"  # the array and every chunk beside it: two tables at the worst
    if (x.shape, x.dtype, device) in _layout_refused:
        return "layout"
    return None


def _put_chunked(x: np.ndarray, target: Any, chunk_bytes: int) -> Tuple[Optional[jax.Array], int]:
    """`x` placed on the one device `target` names (`_one_device`), as
    contiguous row chunks of at most `chunk_bytes`, written there into one
    array of the whole shape. Returns the array and the chunks dispatched;
    (None, chunks) where it came out in another layout than a whole put's
    (remembered: the gate says `layout` from then on)."""
    dtype = jax.dtypes.canonicalize_dtype(x.dtype)  # as `device_put` would
    device = None if target is None else _one_device(target)  # None: uncommitted, as the put
    per = _chunk_rows(x.nbytes // len(x), chunk_bytes)
    whole = jax.numpy.empty(x.shape, dtype, device=device)
    start = jax.numpy.zeros((), np.int32, device=device)
    dispatched = 0
    for s in range(0, len(x), per):
        whole, start = _place(whole, jax.device_put(x[s:s + per], device), start)
        dispatched += 1
    (on,) = whole.devices()
    if whole.format.layout != _default_layout(whole.shape, whole.dtype, on):
        _layout_refused.add((x.shape, x.dtype, on))
        return None, dispatched
    if isinstance(target, Sharding):  # the same buffer, under the placement's own sharding
        whole = jax.make_array_from_single_device_arrays(x.shape, target, [whole])
    return whole, dispatched


def _put(site: Optional[str], x: Any, target: Any = None,
         place: Optional[Callable[[], jax.Array]] = None) -> jax.Array:
    """The one choke point of host->device placement: `x` put as `target`
    says (`_whole_put`), or by `place` where a caller has its own single put
    to the same `target` (`shard_inputs`). With a `site` ("fit", "transform")
    the DISPATCH of the transfer is the span `h2d.put` (attrs `bytes`, `site`)
    and `h2d.bytes{site=}` counts the `nbytes` of what is put; the transfer
    itself is asynchronous and is waited for under `h2d.wait` by whoever
    needs the array resident (core/estimator.py, observability/inference.py).
    A sited put of a large array goes up in row chunks (above):
    `h2d.chunk_gate{site=,chunked=,reason=}` says which way it went and why,
    `h2d.chunks{site=}` counts the chunks. Without a site the placement is
    neither timed nor counted nor chunked: the streamed tier accounts for its
    batches itself (`stream.ingest`, `stream.upload_bytes`), and nothing is
    counted twice."""
    place = place or functools.partial(_whole_put, x, target)
    if site is None:
        return place()
    nbytes = int(getattr(x, "nbytes", 0))
    with _obs.span("h2d.put", {"site": site, "bytes": nbytes}):
        reason = _single_put_reason(x, target, CHUNK_MIN_BYTES)
        out = None
        if reason is None:
            out, chunks = _put_chunked(x, target, CHUNK_BYTES)
            _obs.counter_inc("h2d.chunks", chunks, site=site)
            reason = "ok" if out is not None else "layout"
        chunked = out is not None
        if not chunked:
            out = place()
    _obs.counter_inc("h2d.bytes", nbytes, site=site)
    _obs.counter_inc("h2d.chunk_gate", 1, site=site,
                     chunked="true" if chunked else "false", reason=reason)
    return out


class Partitioner:
    """Owns the mesh and every sharding derived from it.

    Subclasses fix the mesh topology (1-D data-parallel, 2-D data x feature).
    All host->device placement in the fit/transform planes funnels through
    `shard` / `replicate` / `shard_inputs` so the multi-host staging rule
    (local rows only) holds everywhere at once.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    # ------------------------------------------------------------ topology

    @property
    def num_workers(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def process_index(self) -> int:
        return int(jax.process_index())

    @property
    def process_count(self) -> int:
        return int(jax.process_count())

    @property
    def is_multiprocess(self) -> bool:
        return self.process_count > 1

    @property
    def local_device_count(self) -> int:
        """Mesh devices addressable by THIS process (== mesh size when
        single-process; the per-host slice of the pod otherwise)."""
        pi = jax.process_index()
        n = sum(1 for d in self.mesh.devices.flat if d.process_index == pi)
        return n or 1

    # ------------------------------------------------------------ shardings

    @property
    def data_axis(self) -> str:
        """Name of the mesh axis rows shard over — the axis every in-program
        collective (psum/all_gather/ppermute) reduces across."""
        return DATA_AXIS

    def data_spec(self, ndim: int = 2) -> PartitionSpec:
        """Rows sharded across the data axis, everything else replicated."""
        return PartitionSpec(*([DATA_AXIS] + [None] * (ndim - 1)))

    def state_spec(self) -> PartitionSpec:
        """Model state (centroids, coefficients, covariance) is replicated —
        this is what makes the fits allreduce-shaped: collective bytes are
        proportional to the state, never to the data."""
        return PartitionSpec()

    def data_sharding(self, ndim: int = 2) -> NamedSharding:
        return NamedSharding(self.mesh, self.data_spec(ndim))

    def state_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.state_spec())

    # ------------------------------------------------------------ placement

    def shard(self, x: Any, site: Optional[str] = None) -> jax.Array:
        """Place a host array on the mesh with rows on the data axis
        (single-process; for multi-process staging use `shard_inputs`).
        `site`: see `_put`."""
        return _put(site, x, self.data_sharding(np.ndim(x)))

    def replicate(self, x: Any) -> jax.Array:
        return jax.device_put(x, self.state_sharding())

    def put_local(self, x: Any, site: Optional[str] = None,
                  device: Any = None) -> jax.Array:
        """Default-device placement for host-resident block scans that never
        enter the SPMD program (the pairwise streaming device blocks) and for
        a transform's host operands (observability/inference.py), which go to
        `device` where the weights they meet are committed to one."""
        return _put(site, x, device)

    def shard_inputs(self, *local_arrays: Optional[np.ndarray],
                     site: Optional[str] = None) -> List[Optional[jax.Array]]:
        """Assemble global row-sharded arrays from per-process LOCAL rows.

        Always via `jax.make_array_from_process_local_data`: each process
        stages only the rows it holds; no host gathers a global array. On a
        single process that is exactly a sharded device_put (bit-identical to
        the pre-Partitioner path). Every local array must already be padded
        to the common per-rank height (`local_pad_rows`); `None` entries pass
        through.
        """
        out: List[Optional[jax.Array]] = []
        for a in local_arrays:
            if a is None:
                out.append(None)
                continue
            sh = self.data_sharding(np.ndim(a))
            # called before the loop moves on, so the closure's late binding is safe
            out.append(_put(
                site, a, sh, lambda: jax.make_array_from_process_local_data(sh, a)))
        return out

    # ------------------------------------------------------------ staging

    def local_pad_rows(self, max_rank_rows: int) -> int:
        """Common per-rank padded height: every rank pads its local rows to
        this so XLA's equal-shard constraint holds pod-wide (ragged and even
        EMPTY local partitions become zero-weight rows)."""
        chunk = ROW_MULTIPLE * self.local_device_count
        return max(chunk, -(-int(max_rank_rows) // chunk) * chunk)

    def stage_inputs(
        self,
        max_rank_rows: int,
        X_local: np.ndarray,
        *extras_local: Optional[np.ndarray],
    ) -> Tuple[jax.Array, jax.Array, List[Optional[jax.Array]], int]:
        """The dense multi-host staging dance in one place: pad this
        process's local rows (and row-aligned extras) to the common per-rank
        height, mark real rows with a {0,1} weight, and assemble the global
        arrays. Returns (X_global, weight_global, extras_global, pad_to)."""
        pad_to = self.local_pad_rows(max_rank_rows)
        n_local = int(X_local.shape[0])
        w = np.zeros((pad_to,), np.float32)
        w[:n_local] = 1.0
        Xp = np.zeros((pad_to,) + tuple(X_local.shape[1:]), X_local.dtype)
        Xp[:n_local] = X_local
        padded_extras: List[Optional[np.ndarray]] = []
        for e in extras_local:
            if e is None:
                padded_extras.append(None)
                continue
            ep = np.zeros((pad_to,) + tuple(e.shape[1:]), e.dtype)
            ep[:n_local] = e
            padded_extras.append(ep)
        staged = self.shard_inputs(Xp, w, *padded_extras)
        return staged[0], staged[1], staged[2:], pad_to

    # ------------------------------------------------------------ serving

    def replica_device_groups(self, n_replicas: int) -> List[Tuple[Any, ...]]:
        """Disjoint local device groups for the serving fleet's replicas —
        drawn from the partitioner's mesh, not the raw local-device list, so
        a pod-sliced mesh hands each replica its slice of THIS host. With
        fewer local devices than replicas the groups degenerate to single
        devices shared round-robin (the CPU case)."""
        pi = jax.process_index()
        local = [d for d in self.mesh.devices.flat if d.process_index == pi]
        if not local:
            local = list(jax.local_devices())
        n = max(1, int(n_replicas))
        if n >= len(local):
            return [(local[i % len(local)],) for i in range(n)]
        per = len(local) // n
        return [tuple(local[i * per:(i + 1) * per]) for i in range(n)]


class DataParallelPartitioner(Partitioner):
    """1-D data-parallel partitioner: rows across every mesh device, state
    replicated. The default for every estimator."""

    def __init__(self, num_workers: Optional[int] = None, mesh: Optional[Mesh] = None):
        super().__init__(mesh if mesh is not None else get_mesh(num_workers))


class SPMDPartitioner(Partitioner):
    """2-D (data x feature) partitioner for wide-k kNN / feature-sharded
    covariance: rows across the data axis, features optionally across the
    feature axis. State stays replicated across data, sharded across feature
    when the caller opts a tensor in via `feature_spec`."""

    def __init__(self, num_workers: Optional[int] = None,
                 feature_axis: Optional[int] = None,
                 mesh: Optional[Mesh] = None):
        if mesh is None:
            fa = feature_axis if feature_axis is not None else resolve_feature_axis()
            mesh = get_mesh(num_workers, feature_axis=max(1, int(fa)))
        super().__init__(mesh)

    @property
    def feature_axis_size(self) -> int:
        return int(self.mesh.shape.get(FEATURE_AXIS, 1))

    def feature_spec(self, ndim: int = 2) -> PartitionSpec:
        """Rows on data, trailing (feature) dim on the feature axis."""
        if ndim < 2:
            return PartitionSpec(FEATURE_AXIS)
        return PartitionSpec(*([DATA_AXIS] + [None] * (ndim - 2) + [FEATURE_AXIS]))

    def feature_sharding(self, ndim: int = 2) -> NamedSharding:
        return NamedSharding(self.mesh, self.feature_spec(ndim))

    def shard_features(self, x: Any, site: Optional[str] = None) -> jax.Array:
        """Place with rows on data AND columns on feature — the wide-k kNN /
        feature-sharded covariance layout."""
        return _put(site, x, self.feature_sharding(np.ndim(x)))


# --------------------------------------------------------------- active mgmt

_lock = threading.Lock()
_active: Optional[Partitioner] = None
_default_cache: Dict[Tuple[int, int], Partitioner] = {}


def set_partitioner(p: Optional[Partitioner]) -> None:
    """Install the process-wide active partitioner (the barrier task does
    this right after the rendezvous). `None` uninstalls."""
    global _active
    with _lock:
        _active = p


def reset_partitioner() -> None:
    """Drop the active partitioner AND the default cache (tests; and the
    barrier retry path, whose re-rendezvous may change the pod shape)."""
    global _active
    with _lock:
        _active = None
        _default_cache.clear()


@contextlib.contextmanager
def use_partitioner(p: Partitioner):
    """Scoped install — the barrier fit body wraps the fit in this so a
    failed attempt never leaks a stale pod partitioner into retries."""
    global _active
    with _lock:
        prev, _active = _active, p
    try:
        yield p
    finally:
        with _lock:
            _active = prev


def active_partitioner(num_workers: Optional[int] = None) -> Partitioner:
    """The partitioner every sharding decision resolves against.

    An installed partitioner wins unless the caller demands an incompatible
    worker count (an estimator pinned to fewer workers than the pod mesh);
    then — and on plain single-process runs — a cached default
    DataParallelPartitioner over `num_workers` devices is returned."""
    with _lock:
        if _active is not None and (
            num_workers is None or _active.num_workers == num_workers
        ):
            return _active
    mesh = get_mesh(num_workers)  # reuses the cached default mesh
    key = (int(mesh.devices.size), 1)
    with _lock:
        p = _default_cache.get(key)
        if p is None or p.mesh is not mesh:
            p = DataParallelPartitioner(mesh=mesh)
            _default_cache[key] = p
        return p


def partitioner_for(mesh: Optional[Mesh]) -> Partitioner:
    """The partitioner that owns `mesh` — ops that take an explicit mesh
    parameter resolve their placements through this, so a mesh threaded
    through a call chain still lands on Partitioner-owned shardings."""
    if mesh is None:
        return active_partitioner()
    with _lock:
        if _active is not None and _active.mesh is mesh:
            return _active
        key = (int(mesh.devices.size), int(mesh.shape.get(FEATURE_AXIS, 1)))
        p = _default_cache.get(key)
        if p is not None and p.mesh is mesh:
            return p
        p = DataParallelPartitioner(mesh=mesh)
        _default_cache[key] = p
        return p


# --------------------------------------------------------------- helpers

def mesh_of(x: Any) -> Optional[Mesh]:
    """The mesh a placed array lives on, None for single-device arrays —
    replaces the scattered `isinstance(x.sharding, NamedSharding)` probes."""
    sh = getattr(x, "sharding", None)
    if isinstance(sh, NamedSharding):
        return sh.mesh
    return None


def shard_rows(x: Any, mesh: Optional[Mesh] = None) -> jax.Array:
    """Row-shard a host array via the partitioner owning `mesh` (active
    partitioner when None). The migration target for every former
    `shard_array(x, mesh)` call."""
    return partitioner_for(mesh).shard(x)


def replicate_rows(x: Any, mesh: Optional[Mesh] = None) -> jax.Array:
    return partitioner_for(mesh).replicate(x)


def put_device_local(x: Any, site: Optional[str] = None,
                     device: Any = None) -> jax.Array:
    """Default-device placement (host-resident pairwise block scans, a
    transform's host operands)."""
    return active_partitioner().put_local(x, site=site, device=device)


# --------------------------------------------------------------- knobs

def resolve_feature_axis(n: Optional[int] = None, d: Optional[int] = None) -> int:
    """Feature-axis width for SPMDPartitioner meshes. Host-resolution only
    (a partitioner is built per fit, never inside a trace): config pin >
    tuning table (knob `partition.feature_axis`, (n, d)-bucketed) > 1."""
    from .. import autotune as _autotune

    cfg = int(_config.get("partition.feature_axis") or 0)
    if cfg >= 1:
        return cfg
    tuned = _autotune.lookup("partition.feature_axis", n=n, d=d)
    if tuned is not None and int(tuned) >= 1:
        return int(tuned)
    return 1


def resolve_batch_rows_per_process(n: Optional[int] = None,
                                   d: Optional[int] = None) -> int:
    """Per-process row-batch geometry for multi-host streamed ingest: each
    process stages this many LOCAL rows per streamed batch. Config pin >
    tuning table > the single-process `stream_batch_rows` split across the
    pod. Host-resolution only — the value feeds padding geometry, so
    resolving it inside a trace would go stale."""
    from .. import autotune as _autotune

    cfg = int(_config.get("partition.batch_rows_per_process") or 0)
    if cfg >= 1:
        return cfg
    tuned = _autotune.lookup("partition.batch_rows_per_process", n=n, d=d)
    if tuned is not None and int(tuned) >= 1:
        return int(tuned)
    total = int(_config.get("stream_batch_rows"))
    return max(1, total // max(1, jax.process_count()))
