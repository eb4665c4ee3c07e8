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
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import config as _config
from .. import observability as _obs
from .mesh import DATA_AXIS, FEATURE_AXIS, get_mesh

ROW_MULTIPLE = 8  # float32 sublane tile; keeps per-device shards MXU-friendly


def _put(site: Optional[str], x: Any, place: Callable[[], jax.Array]) -> jax.Array:
    """The one choke point of host->device placement. With a `site` ("fit",
    "transform") the DISPATCH of the transfer is the span `h2d.put` (attrs
    `bytes`, `site`) and `h2d.bytes{site=}` counts the `nbytes` of what is put;
    the transfer itself is asynchronous and is waited for under `h2d.wait` by
    whoever needs the array resident (core/estimator.py, observability/
    inference.py). Without a site the placement is neither timed nor counted:
    the streamed tier accounts for its batches itself (`stream.ingest`,
    `stream.upload_bytes`), and nothing is counted twice."""
    if site is None:
        return place()
    nbytes = int(getattr(x, "nbytes", 0))
    with _obs.span("h2d.put", {"site": site, "bytes": nbytes}):
        out = place()
    _obs.counter_inc("h2d.bytes", nbytes, site=site)
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
        sharding = self.data_sharding(np.ndim(x))
        return _put(site, x, lambda: jax.device_put(x, sharding))

    def replicate(self, x: Any) -> jax.Array:
        return jax.device_put(x, self.state_sharding())

    def put_local(self, x: Any, site: Optional[str] = None,
                  device: Any = None) -> jax.Array:
        """Default-device placement for host-resident block scans that never
        enter the SPMD program (the pairwise streaming device blocks) and for
        a transform's host operands (observability/inference.py), which go to
        `device` where the weights they meet are committed to one."""
        import jax.numpy as jnp

        if device is not None:
            return _put(site, x, lambda: jax.device_put(x, device))
        return _put(site, x, lambda: jax.device_put(jnp.asarray(x)))

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
                site, a, lambda: jax.make_array_from_process_local_data(sh, a)))
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
        sharding = self.feature_sharding(np.ndim(x))
        return _put(site, x, lambda: jax.device_put(x, sharding))


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
