#
# Out-of-core blocked-pairwise tier: exact kNN and DBSCAN with the DATASET
# HOST-RESIDENT — the broadcast-replicate leg of the UVM/SAM replacement
# (reference utils.py:184-241 gives cuML managed memory so its brute-force
# paths can exceed device memory; DBSCAN broadcasts the entire dataset to every
# worker, reference clustering.py:1103-1163; exact NN-MG scans all items per
# query batch, reference knn.py:763-774).
#
# TPU formulation: the device only ever sees a (query_block, item_block)
# distance tile plus O(block) running state. Both operand sets stream from host
# through the double-buffered `_prefetch` pipeline (ops/streaming.py) so the
# host slice/device_put of tile i+1 overlaps the matmul of tile i:
#   * exact kNN: running top-k merge per query block (concat + top_k on device),
#   * DBSCAN: streamed eps-neighbor counting (core mask), then min-label
#     propagation rounds — device computes per-tile min CORE-neighbor labels,
#     the hook + pointer-jump contraction runs on host numpy between rounds
#     (O(n) host work vs the O(n*d*n/blk) device pass it steers).
#
# Cost model (why query blocks are large): one full sweep moves
# ceil(n_q / query_block) * n_items * d * 4 bytes host->device. DBSCAN pays one
# sweep for the core mask + one per propagation round (typically <= ~10 with
# pointer jumping) + one for borders. The in-core paths (ops/knn.py,
# ops/dbscan.py) stay the fast path below stream_threshold_bytes; the model
# layer routes (models/dbscan.py, models/knn.py).
#
# Distances use the same FAST-precision `_block_sq_dists` as the in-core scans,
# so streamed-vs-incore results agree rank-for-rank away from exact ties.
#

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map

from ..observability import (
    convergence as obs_convergence,
    counter_inc as obs_counter_inc,
    progress as obs_progress,
    span as obs_span,
)
from ..reliability import RetryPolicy, fault_point
from . import selection as _sel
from .knn import _block_sq_dists
from .selection import INVALID_D2, mask_invalid, merge_topk, select_topk
from .streaming import _prefetch
from ..observability.device import compiled_kernel

_I32MAX = np.iinfo(np.int32).max


@compiled_kernel("pairwise.tile_norms")
def _tile_norms(xb: jax.Array) -> jax.Array:
    """Σ x² of one item tile — computed ONCE at tile upload (and retained in
    the HBM batch cache alongside the tile), with the same reduce the distance
    kernels use, so cached replays are bitwise the in-kernel value. This is
    the streamed half of the norm hoist: no query-block sweep recomputes it
    (`knn.x2_tile_computes` counts actual computations; cached tiles add
    none)."""
    return jnp.sum(xb * xb, axis=1)


def _cached_tile(cache, cache_key, batch_index, build):
    """Item-block flavor of the shared cache-or-upload protocol
    (device_cache.cached_build): the fault point has already fired — replayed
    tiles stay fault-injectable."""
    from .device_cache import cached_build

    return cached_build(cache, cache_key, batch_index, "pairwise", build)


def _shard_blocks(X: np.ndarray, block: int, mesh, extras=None, cache=None,
                  cache_key=None):
    """Mesh variant of `_device_blocks`: each item block is SHARDED over the
    data axis (host->device traffic stays one copy of the data per sweep; the
    per-tile merge rides ICI collectives instead), row-aligned extras shard the
    same way. `block` must be a mesh-size multiple."""
    from ..parallel.partitioner import partitioner_for

    part = partitioner_for(mesh)
    n = X.shape[0]

    def gen():
        for s in range(0, n, block):
            e = min(s + block, n)
            fault_point("pairwise", batch=s // block)

            def build(s=s, e=e):
                xb = np.zeros((block,) + X.shape[1:], np.float32)
                xb[: e - s] = X[s:e]
                xd = part.shard(xb)
                obs_counter_inc("knn.x2_tile_computes")
                devs = [xd, _tile_norms(xd)]  # norm rides the cached tuple
                for a in extras or ():
                    ab = np.zeros((block,) + a.shape[1:], a.dtype)
                    ab[: e - s] = a[s:e]
                    devs.append(part.shard(ab))
                return (s, e - s, *devs)

            yield _cached_tile(cache, cache_key, s // block, build)

    return _prefetch(gen(), depth=1, site="pairwise")


@functools.lru_cache(maxsize=8)
def _mk_tile_topk_mesh(mesh, block: int, k: int, strategy: str, tile: int,
                       recall_target: float):
    """Sharded-items tile merge: local top-k per shard (configured selection
    strategy), all_gather the candidate pools over ICI, fold into the
    replicated running top-k (always exact — merge_topk) — the same
    local-then-merge shape as ops/knn.py::_knn_local_then_merge_fn."""
    from ..parallel.mesh import DATA_AXIS
    from ..parallel.partitioner import partitioner_for

    part = partitioner_for(mesh)
    n_dev = mesh.devices.size
    shard_rows = block // n_dev
    k_loc = min(k, shard_rows)

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            part.state_spec(), part.data_spec(2), part.data_spec(1),
            part.state_spec(), part.state_spec(), part.state_spec(),
            part.state_spec(),
        ),
        out_specs=(part.state_spec(), part.state_spec()),
        check_vma=False,
    )
    def f(qb, xb_local, x2_local, nv, base, best_d, best_i):
        rank = jax.lax.axis_index(DATA_AXIS)
        grow = rank * shard_rows + jnp.arange(shard_rows, dtype=jnp.int32)
        d2 = _block_sq_dists(qb, xb_local, x2_local)
        d2 = mask_invalid(d2, (grow < nv)[None, :])
        d2_sel, pos = select_topk(
            d2, k_loc, strategy=strategy, tile=tile, recall_target=recall_target
        )
        ids = base + grow[pos]
        d_all = jax.lax.all_gather(d2_sel, DATA_AXIS, axis=1)
        i_all = jax.lax.all_gather(ids, DATA_AXIS, axis=1)
        cat_d = jnp.concatenate([best_d, d_all.reshape(qb.shape[0], -1)], axis=1)
        cat_i = jnp.concatenate([best_i, i_all.reshape(qb.shape[0], -1)], axis=1)
        return merge_topk(cat_d, cat_i, k)

    return f


@functools.lru_cache(maxsize=8)
def _mk_tile_count_mesh(mesh, block: int):
    from ..parallel.mesh import DATA_AXIS
    from ..parallel.partitioner import partitioner_for

    part = partitioner_for(mesh)
    n_dev = mesh.devices.size
    shard_rows = block // n_dev

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            part.state_spec(), part.data_spec(2), part.data_spec(1),
            part.state_spec(), part.state_spec(),
        ),
        out_specs=part.state_spec(),
        check_vma=False,
    )
    def f(qb, xb_local, x2_local, nv, eps2):
        rank = jax.lax.axis_index(DATA_AXIS)
        grow = rank * shard_rows + jnp.arange(shard_rows, dtype=jnp.int32)
        d2 = _block_sq_dists(qb, xb_local, x2_local)
        cnt = jnp.sum((d2 <= eps2) & (grow < nv)[None, :], axis=1).astype(jnp.int32)
        return jax.lax.psum(cnt, DATA_AXIS)

    return f


@functools.lru_cache(maxsize=8)
def _mk_tile_minlabel_mesh(mesh, block: int):
    from ..parallel.mesh import DATA_AXIS
    from ..parallel.partitioner import partitioner_for

    part = partitioner_for(mesh)
    n_dev = mesh.devices.size
    shard_rows = block // n_dev

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            part.state_spec(), part.data_spec(2), part.data_spec(1),
            part.data_spec(1), part.data_spec(1),
            part.state_spec(), part.state_spec(),
        ),
        out_specs=part.state_spec(),
        check_vma=False,
    )
    def f(qb, xb_local, x2_local, labels_local, core_local, nv, eps2):
        rank = jax.lax.axis_index(DATA_AXIS)
        grow = rank * shard_rows + jnp.arange(shard_rows, dtype=jnp.int32)
        d2 = _block_sq_dists(qb, xb_local, x2_local)
        neigh = (d2 <= eps2) & core_local[None, :] & (grow < nv)[None, :]
        m = jnp.min(jnp.where(neigh, labels_local[None, :], _I32MAX), axis=1)
        return jax.lax.pmin(m, DATA_AXIS)

    return f


def _mesh_or_none(mesh):
    return mesh if (mesh is not None and mesh.devices.size > 1) else None


def _round_block(block: int, mesh) -> int:
    n_dev = mesh.devices.size
    return max(n_dev, ((block + n_dev - 1) // n_dev) * n_dev)


def _device_blocks(X: np.ndarray, block: int, extras=None, cache=None,
                   cache_key=None):
    """Yield (start, n_valid, device_block, *device_extras) with the ragged tail
    zero-padded to `block` (ONE compiled tile shape for the whole stream).
    `extras`: list of row-aligned host arrays uploaded alongside (labels, masks)."""
    from ..parallel.partitioner import put_device_local

    n = X.shape[0]

    def gen():
        for s in range(0, n, block):
            e = min(s + block, n)
            fault_point("pairwise", batch=s // block)

            def build(s=s, e=e):
                xb = np.zeros((block,) + X.shape[1:], np.float32)
                xb[: e - s] = X[s:e]
                xd = put_device_local(xb)
                obs_counter_inc("knn.x2_tile_computes")
                devs = [xd, _tile_norms(xd)]  # norm rides the cached tuple
                for a in extras or ():
                    ab = np.zeros((block,) + a.shape[1:], a.dtype)
                    ab[: e - s] = a[s:e]
                    devs.append(put_device_local(ab))
                return (s, e - s, *devs)

            yield _cached_tile(cache, cache_key, s // block, build)

    return _prefetch(gen(), depth=1, site="pairwise")


@compiled_kernel("pairwise.tile_topk_merge",
                 static_argnames=("k", "strategy", "tile", "recall_target"))
def _tile_topk_merge(qb, xb, x2b, nv_items, base_id, best_d, best_i, k: int,
                     strategy: str, tile: int, recall_target: float):
    """Merge one (qb, xb) tile into the per-query running top-k: configured
    selection over the tile's candidates (the wide axis — where the strategy
    wins), then an exact fold into the carried pool (an approximate fold
    would drop carried candidates, compounding per tile)."""
    d2 = _block_sq_dists(qb, xb, x2b)
    iv = jnp.arange(xb.shape[0]) < nv_items
    d2 = mask_invalid(d2, iv[None, :])
    cand_d, pos = select_topk(
        d2, min(k, xb.shape[0]), strategy=strategy, tile=tile,
        recall_target=recall_target,
    )
    cand_i = base_id + pos
    cat_d = jnp.concatenate([best_d, cand_d], axis=1)
    cat_i = jnp.concatenate([best_i, cand_i], axis=1)
    return merge_topk(cat_d, cat_i, k)


def streaming_exact_knn(
    Q: np.ndarray,
    X: np.ndarray,
    k: int,
    query_block: int = 4096,
    item_block: int = 131072,
    mesh=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact kNN with HOST-RESIDENT items: returns (euclidean distances, item
    row indices), matching ops/knn.py::exact_knn_single rank-for-rank (same
    FAST-precision distance form) at any dataset size. Device residency is one
    query block + one item block + the (query_block, k) running state. With a
    multi-device `mesh`, item blocks shard over the data axis (one host copy of
    the data per sweep; the per-tile candidate merge all_gathers over ICI).

    The item stream is swept once PER QUERY BLOCK — the HBM batch cache
    (ops/device_cache.py) retains the tiles the first sweep uploads, so the
    remaining ceil(nq/query_block)-1 sweeps replay from HBM (prefix-cached when
    the item set exceeds the budget)."""
    from .device_cache import batch_cache

    n, d = X.shape
    k_eff = min(k, n)
    nq = Q.shape[0]
    mesh = _mesh_or_none(mesh)
    strategy, sel_tile, rt = _sel.resolve(min(item_block, n), k_eff, None)
    _sel.record_selection(strategy, site="pairwise_knn")
    with batch_cache() as cache:
        if mesh is not None:
            item_block = _round_block(item_block, mesh)
            ckey = (
                cache.stream_key((X,), item_block, mesh, site="pairwise")
                if cache is not None
                else None
            )
            tile = _mk_tile_topk_mesh(
                mesh, item_block, k_eff, strategy, sel_tile, rt
            )

            def merge(qb, xb, x2b, nv, s, bd, bi):
                return tile(qb, xb, x2b, jnp.int32(nv), jnp.int32(s), bd, bi)

            def blocks():
                return _shard_blocks(
                    X, item_block, mesh, cache=cache, cache_key=ckey
                )
        else:
            ckey = (
                cache.stream_key((X,), item_block, None, site="pairwise")
                if cache is not None
                else None
            )

            def merge(qb, xb, x2b, nv, s, bd, bi):
                return _tile_topk_merge(
                    qb, xb, x2b, nv, s, bd, bi, k_eff, strategy, sel_tile, rt
                )

            def blocks():
                return _device_blocks(X, item_block, cache=cache, cache_key=ckey)

        out_d = np.empty((nq, k_eff), np.float32)
        out_i = np.empty((nq, k_eff), np.int64)
        policy = RetryPolicy.from_config()
        for qs in range(0, nq, query_block):
            qe = min(qs + query_block, nq)

            def _scan_query_block(qs=qs, qe=qe):
                # running state re-initializes per attempt, so a transient tile
                # failure replays this query block exactly (deterministic merge)
                qb = jnp.asarray(np.ascontiguousarray(Q[qs:qe], np.float32))  # noqa: fence/host-staging-copy
                best_d = jnp.full((qe - qs, k_eff), INVALID_D2, jnp.float32)
                best_i = jnp.full((qe - qs, k_eff), -1, jnp.int32)
                for s, nv, xb, x2b in blocks():
                    best_d, best_i = merge(qb, xb, x2b, nv, s, best_d, best_i)
                ids = np.asarray(best_i).astype(np.int64)
                if strategy == "approx":
                    # the re-rank invariant (design.md §5b) holds out-of-core
                    # too: the winner pool's FAST expansion distances are
                    # replaced by exact f32 distances recomputed against the
                    # HOST items (the pool is (block, k) — the gather is tiny
                    # next to the sweep), then re-sorted
                    with obs_span(
                        "knn.rerank", {"start": qs, "rows": qe - qs}
                    ):
                        qh = np.ascontiguousarray(Q[qs:qe], np.float32)  # noqa: fence/host-staging-copy
                        vecs = X[ids].astype(np.float32, copy=False)  # noqa: fence/host-staging-copy
                        d2 = ((qh[:, None, :] - vecs) ** 2).sum(-1)
                        order = np.argsort(d2, axis=1, kind="stable")
                        ids = np.take_along_axis(ids, order, axis=1)
                        out_d[qs:qe] = np.sqrt(
                            np.take_along_axis(d2, order, axis=1)
                        )
                else:
                    out_d[qs:qe] = np.sqrt(np.asarray(best_d))
                out_i[qs:qe] = ids

            # one trace span per query-block sweep over the item stream: the
            # per-fit report then attributes time to sweeps (with any item-tile
            # `stream.ingest` uploads as children) instead of one opaque scan
            with obs_span(
                "pairwise.query_block", {"start": qs, "rows": qe - qs}
            ):
                policy.run(_scan_query_block, site="pairwise")
            obs_progress(
                "pairwise.query_blocks", -(-qe // query_block),
                -(-nq // query_block), unit="blocks",
            )
    return out_d, out_i


@compiled_kernel("pairwise.tile_count")
def _tile_count(qb, xb, x2b, nv_items, eps2):
    d2 = _block_sq_dists(qb, xb, x2b)
    iv = jnp.arange(xb.shape[0]) < nv_items
    return jnp.sum((d2 <= eps2) & iv[None, :], axis=1).astype(jnp.int32)


@compiled_kernel("pairwise.tile_min_core_label")
def _tile_min_core_label(qb, xb, x2b, labels_b, core_b, nv_items, eps2):
    d2 = _block_sq_dists(qb, xb, x2b)
    iv = jnp.arange(xb.shape[0]) < nv_items
    neigh = (d2 <= eps2) & core_b[None, :] & iv[None, :]
    return jnp.min(jnp.where(neigh, labels_b[None, :], _I32MAX), axis=1)


def _streamed_min_core_labels(
    X: np.ndarray,
    labels: np.ndarray,
    core: np.ndarray,
    eps2: float,
    query_block: int,
    item_block: int,
    mesh=None,
    cache=None,
) -> np.ndarray:
    """One full streamed sweep: per row, min label among its CORE eps-neighbors
    (int32 max where none) — the out-of-core analog of
    ops/dbscan.py::_min_core_neighbor_labels. The tile key includes the labels/
    core arrays, so tiles replay across the query blocks of ONE round and the
    next round's fresh labels naturally LRU-evict them."""
    n = X.shape[0]
    ckey = (
        cache.stream_key((X, labels, core), item_block, mesh, site="pairwise")
        if cache is not None
        else None
    )
    if mesh is not None:
        tile_fn = _mk_tile_minlabel_mesh(mesh, item_block)

        def tile(qb, xb, x2b, lb, cb, nv):
            return tile_fn(qb, xb, x2b, lb, cb, jnp.int32(nv), jnp.float32(eps2))

        def blocks():
            return _shard_blocks(
                X, item_block, mesh, extras=[labels, core],
                cache=cache, cache_key=ckey,
            )
    else:
        def tile(qb, xb, x2b, lb, cb, nv):
            return _tile_min_core_label(qb, xb, x2b, lb, cb, nv, eps2)

        def blocks():
            return _device_blocks(
                X, item_block, extras=[labels, core],
                cache=cache, cache_key=ckey,
            )

    mins = np.full((n,), _I32MAX, np.int32)
    policy = RetryPolicy.from_config()
    for qs in range(0, n, query_block):
        qe = min(qs + query_block, n)

        def _minlabel_query_block(qs=qs, qe=qe):
            qb = jnp.asarray(np.ascontiguousarray(X[qs:qe], np.float32))  # noqa: fence/host-staging-copy
            acc = jnp.full((qe - qs,), _I32MAX, jnp.int32)
            for s, nv, xb, x2b, lb, cb in blocks():
                acc = jnp.minimum(acc, tile(qb, xb, x2b, lb, cb, nv))
            mins[qs:qe] = np.asarray(acc)

        policy.run(_minlabel_query_block, site="pairwise")
    return mins


def streaming_dbscan_fit_predict(
    X: np.ndarray,
    eps: float,
    min_samples: int,
    metric: str = "euclidean",
    max_rounds: int = 64,
    query_block: int = 8192,
    item_block: int = 131072,
    mesh=None,
) -> np.ndarray:
    """DBSCAN with the dataset host-resident; labels match
    ops/dbscan.py::dbscan_fit_predict (noise = -1, clusters compacted in
    first-appearance order). The propagation loop is host-driven: each round
    pays one streamed pairwise sweep, then the hook + two pointer-jumping
    contractions run in numpy (exactly ops/dbscan.py::_hook_and_jump's math).

    ONE batch cache spans the whole fit: the core-mask pass and every
    propagation round sweep the same item tiles per query block, so tiles
    upload once per (round, labels) key and replay from HBM across that
    round's query blocks, with LRU eviction as rounds retire their labels."""
    from .device_cache import batch_cache

    with batch_cache() as cache:
        return _streaming_dbscan_fit_predict(
            X, eps, min_samples, metric, max_rounds, query_block, item_block,
            mesh, cache,
        )


def _streaming_dbscan_fit_predict(
    X, eps, min_samples, metric, max_rounds, query_block, item_block, mesh, cache,
):
    from .dbscan import _compact_labels

    X = np.ascontiguousarray(np.asarray(X), dtype=np.float32)  # noqa: fence/host-staging-copy
    n = X.shape[0]
    if metric == "cosine":
        norms = np.linalg.norm(X, axis=1, keepdims=True)
        if float(norms.min()) <= 0.0:
            raise ValueError(
                "Cosine distance is not defined for zero-length vectors; the "
                "input contains an all-zero feature row."
            )
        # one host-side normalized copy; unavoidable without it: every tile
        # would renormalize the same rows ceil(n/query_block) times
        X = X / np.maximum(norms, 1e-30)
        eps2 = 2.0 * float(eps)
    else:
        eps2 = float(eps) * float(eps)

    mesh = _mesh_or_none(mesh)
    if mesh is not None:
        item_block = _round_block(item_block, mesh)
    count_key = (
        cache.stream_key((X,), item_block, mesh, site="pairwise")
        if cache is not None
        else None
    )
    if mesh is not None:
        count_fn = _mk_tile_count_mesh(mesh, item_block)

        def count_tile(qb, xb, x2b, nv):
            return count_fn(qb, xb, x2b, jnp.int32(nv), jnp.float32(eps2))

        def count_blocks():
            return _shard_blocks(
                X, item_block, mesh, cache=cache, cache_key=count_key
            )
    else:
        def count_tile(qb, xb, x2b, nv):
            return _tile_count(qb, xb, x2b, nv, eps2)

        def count_blocks():
            return _device_blocks(
                X, item_block, cache=cache, cache_key=count_key
            )

    # pass 1: streamed core mask
    core = np.empty((n,), bool)
    policy = RetryPolicy.from_config()
    for qs in range(0, n, query_block):
        qe = min(qs + query_block, n)

        def _core_query_block(qs=qs, qe=qe):
            qb = jnp.asarray(np.ascontiguousarray(X[qs:qe], np.float32))  # noqa: fence/host-staging-copy
            acc = jnp.zeros((qe - qs,), jnp.int32)
            for s, nv, xb, x2b in count_blocks():
                acc = acc + count_tile(qb, xb, x2b, nv)
            core[qs:qe] = np.asarray(acc) >= int(min_samples)

        policy.run(_core_query_block, site="pairwise")
        obs_progress(
            "dbscan.core_blocks", -(-qe // query_block),
            -(-n // query_block), unit="blocks",
        )

    # min-label propagation with host-side hook + pointer jumping
    labels = np.arange(n, dtype=np.int32)
    mins = None
    converged = False
    for round_no in range(max_rounds):
        mins = _streamed_min_core_labels(
            X, labels, core, eps2, query_block, item_block, mesh=mesh,
            cache=cache,
        )
        new = np.where(core, np.minimum(labels, mins), labels).astype(np.int32)
        new = new[new]
        new = new[new]
        # §6g: round-level progress (total = the max_rounds bound; the loop
        # usually converges much earlier) + a convergence record tracking how
        # many labels the round still moved
        obs_progress("dbscan.rounds", round_no + 1, max_rounds, unit="rounds")
        obs_convergence(
            "dbscan", round_no + 1,
            labels_changed=int(np.count_nonzero(new != labels)),
        )
        if np.array_equal(new, labels):
            converged = True
            break
        labels = new

    # border pass + compaction, shared with the in-core path. On the converged
    # exit the last round's `mins` was computed from exactly these labels, so
    # re-streaming the dataset (the dominant cost unit) would recompute it
    # verbatim; only the max_rounds-exhausted path needs a fresh sweep.
    if converged and mins is not None:
        border_min = mins
    else:
        border_min = _streamed_min_core_labels(
            X, labels, core, eps2, query_block, item_block, mesh=mesh,
            cache=cache,
        )
    out = np.full((n,), -1, dtype=np.int64)
    out[core] = labels[core]
    border = (~core) & (border_min < _I32MAX)
    out[border] = border_min[border]
    return _compact_labels(out)
