#
# k-nearest-neighbor kernels — the TPU-native replacement for
# cuml.neighbors.nearest_neighbors_mg.NearestNeighborsMG (reference knn.py:683-774:
# exact kNN with the query-block all-to-all over UCX endpoints and a distributed
# top-k merge inside cuML) and for the cuVS ANN indexes (reference knn.py:1510-1690).
#
# TPU formulation (P4 all-to-all, SURVEY.md §2.7):
#   * items live row-sharded across the mesh; each device scans ITS shard against the
#     (replicated or gathered) query block — an (nq, n_shard) distance matmul on the
#     MXU — and keeps a local top-k with GLOBAL item ids,
#   * one all_gather of the per-device top-k candidates over ICI (k·n_devices per
#     query — tiny next to the data) replaces cuML's UCX endpoint mesh,
#   * a final replicated top-k merge gives the global neighbors.
# Queries are processed in fixed-size blocks (lax.map) to bound the distance-matrix
# footprint in HBM.
#
# IVF-Flat: our own kmeans partitions the items into nlist cells, padded to a common
# cell size (static shapes); search probes the nprobe nearest cells with a masked
# distance scan — the cuVS ivf_flat equivalent re-expressed as dense gathers+matmuls.
#
# Selection plane: EVERY top-k below routes through ops/selection.py
# (exact_full | exact_tiled | approx behind `knn.selection`; merges stay
# exact). Invalid candidates mask to the large-finite INVALID_D2 sentinel, not
# inf (inf − inf in a downstream recomputation is a NaN factory); the -1-id /
# inf-distance OUTPUT contract of the search entry points is restored at the
# boundary from the id mask. Item norms (x2 = Σ X²) are hoisted out of the
# per-block scans: computed once per kernel invocation, or passed in
# precomputed (models cache them on the fitted model / built index).
#

from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax import shard_map

from ._precision import FAST
from ..parallel.mesh import DATA_AXIS
from . import selection as _sel
from .selection import INVALID_D2, mask_invalid, merge_topk, select_topk
from ..observability.device import compiled_kernel


def _block_sq_dists(
    Q: jax.Array, X: jax.Array, x2: Optional[jax.Array] = None
) -> jax.Array:
    """(nq, n) squared euclidean distances (FAST precision: ranking tolerates bf16
    passes; exact distances are recomputed at parity precision only for the winners).
    `x2` is the precomputed item-norm term Σ X² — pass it to keep the norm out
    of a per-block scan (fit/build time caches it; kernels compute it once)."""
    q2 = jnp.sum(Q * Q, axis=1, keepdims=True)
    if x2 is None:
        x2 = jnp.sum(X * X, axis=1)
    d2 = q2 - 2.0 * jnp.matmul(Q, X.T, precision=FAST) + x2
    return jnp.maximum(d2, 0.0)


def _span_or_null(name: str, attrs, tracing: bool):
    """Host-side selection/re-rank spans; no-op inside a trace (a trace-time
    span would record compile-time, not search time)."""
    if tracing:
        return contextlib.nullcontext()
    from .. import observability as _obs

    return _obs.span(name, attrs)


def _count_x2(x2, site: str, tracing: bool) -> None:
    """Norm-hoist telemetry: did this search recompute the item-norm term or
    ride a cached one? (tests assert refit invalidation + zero per-block
    recomputation from these counters)"""
    if tracing:
        return
    from .. import observability as _obs

    _obs.counter_inc(
        "knn.x2_cached" if x2 is not None else "knn.x2_recompute", 1, site=site
    )


@compiled_kernel(
    "knn.exact_scan",
    static_argnames=("k", "block", "strategy", "tile", "recall_target"),
)
def _exact_knn_scan(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    x2: Optional[jax.Array],
    k: int,
    block: int,
    strategy: str,
    tile: int,
    recall_target: float,
) -> Tuple[jax.Array, jax.Array]:
    """Blocked exact-kNN scan: FAST-precision distances + the configured
    selection per query block. x2 is hoisted out of the per-block scan —
    computed once here when the caller holds no cache."""
    nq = Q.shape[0]
    if x2 is None:
        x2 = jnp.sum(X * X, axis=1)
    pad = (-nq) % block
    Qp = jnp.pad(Q, ((0, pad), (0, 0)))

    def scan_block(qb):
        d2 = _block_sq_dists(qb, X, x2)
        d2 = mask_invalid(d2, valid[None, :])
        return select_topk(
            d2, k, strategy=strategy, tile=tile, recall_target=recall_target
        )

    d2b, idxb = jax.lax.map(scan_block, Qp.reshape(-1, block, Q.shape[1]))
    return d2b.reshape(-1, k)[:nq], idxb.reshape(-1, k)[:nq]


@compiled_kernel("knn.parity_rerank_sq", static_argnames=("k",))
def parity_rerank_sq(
    Q: jax.Array, X: jax.Array, valid: jax.Array, cand_idx: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Parity-precision re-rank of a winner pool: gather the candidate
    vectors, recompute SQUARED distances exactly (full-f32 difference form —
    no bf16 passes, no expansion cancellation), exact top-k. The approx
    selection strategy pairs with this so returned distances stay exact while
    only the id set is approximate (recall >= knn.recall_target)."""
    vecs = X[cand_idx]  # (nq, kc, d)
    d2 = jnp.sum((vecs - Q[:, None, :]) ** 2, axis=-1)
    d2 = mask_invalid(d2, valid[cand_idx])
    return merge_topk(d2, cand_idx, k)


def exact_knn_single(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    k: int,
    block: int = 1024,
    *,
    x2: Optional[jax.Array] = None,
    strategy: Optional[str] = None,
    model_name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Single-shard exact kNN: blocked scan, returns (distances², indices).

    Selection strategy comes from `knn.selection` (resolved HERE, outside the
    trace, so a config change can never be baked stale into a cached trace).
    Under `approx`, the scan selects a winner pool with approx_max_k and a
    parity-precision re-rank restores exact distances — the id set carries the
    recall target, the values don't. This is a FUSABLE site: `pallas_fused`
    (explicit, or `auto` on TPU past knn.pallas_min_items) runs the fused
    distance+select scan (ops/pallas_select.py) — bit-identical in f32 mode;
    under `knn.pallas_precision` bf16/int8 the fused pool re-ranks through
    the same parity_rerank_sq invariant as approx."""
    n = X.shape[0]
    k = min(int(k), n)
    strategy, tile, rt = _sel.resolve(n, k, strategy, fusable=True)
    tracing = _sel.is_tracing(Q, X, valid)
    if not tracing:
        _sel.record_selection(strategy, site="exact_knn", model=model_name)
    precision = q_block = item_tile = None
    if strategy == "pallas_fused":
        from . import pallas_select as _ps

        precision = _sel.resolve_fused_precision(None)
        kc = _ps.oversample_width(k, n, precision)
        q_block, item_tile = _ps.resolve_topk_geometry(
            int(Q.shape[0]), n, int(Q.shape[1]), kc
        )
    return _exact_knn_resolved(
        Q, X, valid, k, block, x2, strategy, tile, rt,
        precision=precision, q_block=q_block, item_tile=item_tile,
    )


def _exact_knn_resolved(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    k: int,
    block: int,
    x2: Optional[jax.Array],
    strategy: str,
    tile: int,
    rt: float,
    *,
    precision: Optional[str] = None,
    q_block: Optional[int] = None,
    item_tile: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """TRACE-PURE core of exact_knn_single: every knob — strategy, tile,
    recall target, fused precision, fused geometry — arrives concrete from a
    host-side resolution (exact_knn_single, or the shard_map factory
    `_knn_local_then_merge_fn`). No config read, no tuning-table read
    (tools/analysis purity/*): this is the form traced bodies may call."""
    n = X.shape[0]
    k = min(int(k), n)
    tracing = _sel.is_tracing(Q, X, valid)
    _count_x2(x2, "exact_knn", tracing)
    if strategy == "pallas_fused":
        from .pallas_select import fused_topk_pinned, oversample_width

        if precision == "float32":
            # exact mode: the fused scan IS the answer (bit-identical)
            with _span_or_null(
                "knn.select", {"strategy": strategy, "k": k}, tracing
            ):
                return fused_topk_pinned(
                    Q, X, valid, k, q_block=q_block, item_tile=item_tile,
                    x2=x2, precision=precision,
                )
        # approximate accumulation: oversampled pool + the §5b re-rank
        # invariant — returned distances stay exact-f32, ids carry the
        # approximation (the same contract as the approx strategy)
        kc = oversample_width(k, n, precision)
        with _span_or_null(
            "knn.select",
            {"strategy": strategy, "k": kc, "precision": precision},
            tracing,
        ):
            _, idx = fused_topk_pinned(
                Q, X, valid, kc, q_block=q_block, item_tile=item_tile,
                x2=x2, precision=precision,
            )
        with _span_or_null("knn.rerank", {"k": k}, tracing):
            if not tracing:
                from .. import observability as _obs

                _obs.counter_inc(
                    "knn.rerank_calls", 1, site="exact_knn",
                    precision=precision,
                )
            d2c, idc = parity_rerank_sq(Q, X, valid, idx, k)
            if kc == k:
                return d2c, idc
            # canonicalize through the k-shaped parity computation: the
            # oversampled-pool rerank runs at width kc, where XLA's reduce
            # vectorization can differ from the k-shaped program by 1 ulp.
            # Re-deriving the returned distances at width k makes the §5c
            # invariant exactly idempotent — returned (d2, ids) ARE
            # parity_rerank_sq(returned ids) bit-for-bit, the property the
            # tier-1 property tests assert
            return parity_rerank_sq(Q, X, valid, idc, k)
    if strategy == "approx":
        with _span_or_null("knn.select", {"strategy": strategy, "k": k}, tracing):
            _, idx = _exact_knn_scan(
                Q, X, valid, x2, k, block, strategy, tile, rt
            )
        with _span_or_null("knn.rerank", {"k": k}, tracing):
            if not tracing:
                from .. import observability as _obs

                _obs.counter_inc("knn.rerank_calls", 1, site="exact_knn")
            return parity_rerank_sq(Q, X, valid, idx, k)
    return _exact_knn_scan(Q, X, valid, x2, k, block, strategy, tile, rt)


def exact_knn_distributed(
    mesh: Mesh,
    Q: np.ndarray,
    X_sharded: jax.Array,
    valid_sharded: jax.Array,
    k: int,
    x2_sharded: Optional[jax.Array] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Distributed exact kNN over the mesh: local shard scans + all_gather top-k merge.

    Returns host (distances, global indices); distances are EUCLIDEAN (sqrt'd),
    matching the reference's returned distances (knn.py:783-802)."""
    n_total = X_sharded.shape[0]
    n_dev = mesh.devices.size
    shard_rows = n_total // n_dev
    k_eff = min(k, n_total)
    # a shard can hold fewer than k rows; the all-gathered candidate pool
    # (n_dev * k_local >= min(k_eff, n_total)) still covers the global top-k
    k_local = min(k_eff, shard_rows)
    # telemetry AND knob resolution fire HERE, on the host: the per-shard
    # scan runs inside the shard_map trace, where counters are suppressed and
    # config/tuning-table reads are banned (purity/* — a per-rank table read
    # could trace DIVERGENT programs across pod hosts). The factory receives
    # the fully resolved bundle. (fusable: the per-shard scan holds Q and its
    # X shard, so pallas_fused applies — one single-device pallas_call per
    # shard under shard_map)
    resolved = _sel.resolve(shard_rows, k_local, None, fusable=True)
    _sel.record_selection(resolved[0], site="exact_knn_distributed")
    _count_x2(x2_sharded, "exact_knn_distributed", False)

    merge = _knn_local_then_merge_fn(
        mesh, shard_rows, k_local, k_eff, with_x2=x2_sharded is not None,
        nq=int(np.asarray(Q).shape[0]), d=int(X_sharded.shape[1]),
        resolved=resolved,
    )
    if x2_sharded is not None:
        d2, gidx = merge(jnp.asarray(Q), X_sharded, valid_sharded, x2_sharded)
    else:
        d2, gidx = merge(jnp.asarray(Q), X_sharded, valid_sharded)
    return np.sqrt(np.asarray(d2)), np.asarray(gidx)


def _knn_local_then_merge_fn(
    mesh: Mesh, shard_rows: int, k_local: int, k_eff: int,
    with_x2: bool = False, *,
    nq: Optional[int] = None, d: Optional[int] = None,
    resolved: Optional[Tuple[str, int, float]] = None,
):
    """The shard-mapped local-topk + all_gather merge step, exposed so tests can
    lower it and assert the compiled collective structure (one gather batch, no
    quadratic exchange). The candidate MERGE stays exact (merge_topk). THIS
    factory is the host boundary for the shard body: strategy/tile/recall
    (`resolved`, else resolved here) and — for pallas_fused — precision and
    scan geometry all resolve BEFORE the trace, and the body calls the
    trace-pure _exact_knn_resolved (purity/*: a config or tuning-table read
    inside shard_map would bake per-host, tracing divergent programs across
    pod ranks)."""
    strategy, tile, rt = (
        resolved if resolved is not None
        else _sel.resolve(shard_rows, k_local, None, fusable=True)
    )
    precision = q_block = item_tile = None
    if strategy == "pallas_fused":
        from . import pallas_select as _ps

        precision = _sel.resolve_fused_precision(None)
        kc = _ps.oversample_width(k_local, shard_rows, precision)
        # nq/d default for legacy callers (tests lowering the factory with
        # exact strategies never reach here)
        q_block, item_tile = _ps.resolve_topk_geometry(
            nq if nq is not None else shard_rows,
            shard_rows, d if d is not None else 1, kc,
        )
    from ..parallel.partitioner import partitioner_for

    part = partitioner_for(mesh)
    in_specs = (part.state_spec(), part.data_spec(2), part.data_spec(1))
    if with_x2:
        in_specs = in_specs + (part.data_spec(1),)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=part.state_spec(),
        check_vma=False,  # post-all_gather results are replicated; size-1 aux axes
        # defeat the static replication checker
    )
    def _local_then_merge(q, x_local, valid_local, *maybe_x2):
        rank = jax.lax.axis_index(DATA_AXIS)
        x2_local = maybe_x2[0] if maybe_x2 else None
        d2, idx = _exact_knn_resolved(
            q, x_local, valid_local, k_local, 1024, x2_local,
            strategy, tile, rt,
            precision=precision, q_block=q_block, item_tile=item_tile,
        )
        gidx = idx + rank * shard_rows
        # all-to-all candidate exchange over ICI (the UCX replacement)
        d2_all = jax.lax.all_gather(d2, DATA_AXIS, axis=1)  # (nq, n_dev, k_local)
        gidx_all = jax.lax.all_gather(gidx, DATA_AXIS, axis=1)
        d2_all = d2_all.reshape(d2.shape[0], -1)
        gidx_all = gidx_all.reshape(d2.shape[0], -1)
        return merge_topk(d2_all, gidx_all, k_eff)

    return _local_then_merge


# ---------------------------------------------------------------------------
# IVF-Flat / IVF-PQ
# ---------------------------------------------------------------------------


def center_norms_sq(centers) -> np.ndarray:
    """Σ centers² computed ON DEVICE with the same reduce the probe kernels
    use, so a cached norm is bitwise the value the kernel would recompute.
    Cached on built IVF layouts (the norm-hoist satellite: built once per
    build, invalidated by construction on refit since every build emits a
    fresh dict)."""
    c = jnp.asarray(np.asarray(centers, dtype=np.float32))
    return np.asarray(jnp.sum(c * c, axis=1))


def ivfflat_build(
    X: jax.Array, w: jax.Array, nlist: int, max_iter: int, seed: int,
    return_assign: bool = False,
) -> Dict[str, np.ndarray]:
    """Partition items into nlist cells via our kmeans; lay cells out densely padded
    to the max cell size (static shapes for the probe scan)."""
    from .kmeans import kmeans_fit, kmeans_predict

    # ANN builds have no sample weights: w is purely the pad mask, so the
    # masked (weight-stream-free) Lloyd kernel is eligible under the mask opt-in
    fitted = kmeans_fit(
        X, w, k=nlist, max_iter=max_iter, tol=1e-4, init="k-means||",
        init_steps=2, seed=seed, unit_weight=True,
    )
    centers = fitted["cluster_centers"]
    assign = np.asarray(kmeans_predict(X, jnp.asarray(centers)))
    valid = np.asarray(w) > 0
    cells, cell_ids, cell_sizes = layout_cells(np.asarray(X), assign, nlist, valid)
    out = {
        "centers": centers,
        "center_norms": center_norms_sq(centers),
        "cells": cells,
        "cell_ids": cell_ids,
        "cell_sizes": cell_sizes,
    }
    if return_assign:
        out["assign"] = assign
    return out


def normalize_rows_or_raise(Xb: np.ndarray) -> np.ndarray:
    """Host-side row normalization for the cosine tier; zero-norm rows raise
    (Spark/cuML cosine semantics). THE single definition of the zero-row
    contract for host arrays — layout_cells and the streamed ANN builds
    (ops/ann_streaming.py) all route through it."""
    norms = np.linalg.norm(Xb, axis=1, keepdims=True)
    if len(norms) and float(norms.min()) <= 0.0:
        raise ValueError(
            "Cosine distance is not defined for zero-length vectors; the input "
            "contains an all-zero feature row."
        )
    return (Xb / np.maximum(norms, 1e-30)).astype(np.float32)


def layout_cells(
    Xh: np.ndarray,
    assign: np.ndarray,
    nlist: int,
    valid: "np.ndarray | None" = None,
    normalize: bool = False,
):
    """Dense (nlist, max_cell, d) cell layout with -1 id sentinels — shared by the
    in-core and streamed (ops/ann_streaming.py) IVF builds so the sentinel/offset
    conventions the probe scans depend on cannot diverge. Vectorized: stable-sort
    rows by cell, then each row's slot is its sorted position minus the cell's
    start offset (the former per-row Python loop was O(n) interpreted —
    disqualifying at 10M items). `normalize=True` writes unit rows (the cosine
    tier) into the gather temp that already exists — no extra dataset copy."""
    n, d = Xh.shape
    valid_idx = np.arange(n) if valid is None else np.nonzero(valid)[0]
    cell_sizes = np.bincount(assign[valid_idx], minlength=nlist)
    max_cell = max(int(cell_sizes.max()), 1)
    cells = np.zeros((nlist, max_cell, d), dtype=np.float32)
    cell_ids = np.full((nlist, max_cell), -1, dtype=np.int64)
    order = np.argsort(assign[valid_idx], kind="stable")
    sorted_rows = valid_idx[order]
    sorted_cells = assign[sorted_rows]
    within = np.arange(len(sorted_rows)) - np.repeat(
        np.concatenate([[0], np.cumsum(cell_sizes)[:-1]]), cell_sizes
    )
    gathered = Xh[sorted_rows]
    if normalize:
        gathered = normalize_rows_or_raise(gathered)
    elif gathered.dtype != np.float32:
        # cast inside the gather temp that already exists: callers hand Xh in
        # its source dtype (the streamed build no longer pre-converts the
        # whole dataset — that was a second full-dense host copy)
        gathered = gathered.astype(np.float32)
    cells[sorted_cells, within] = gathered
    cell_ids[sorted_cells, within] = sorted_rows
    return cells, cell_ids, cell_sizes.astype(np.int32)


def ivfpq_build(
    X: jax.Array,
    w: jax.Array,
    nlist: int,
    m_subvectors: int,
    n_bits: int,
    max_iter: int,
    seed: int,
) -> Dict[str, np.ndarray]:
    """IVF-PQ index: coarse kmeans cells + per-subspace product-quantization
    codebooks over the residuals (the cuVS ivf_pq equivalent, reference
    knn.py:1510-1524, re-expressed as dense kmeans + gathers).

    Returns centers (nlist,d), codebooks (m, 2^bits, d/m), codes (nlist, max_cell, m)
    uint8, cell_ids."""
    from .kmeans import kmeans_fit, kmeans_predict

    n, d = X.shape
    if d % m_subvectors != 0:
        raise ValueError(f"n features {d} not divisible by pq m={m_subvectors}")
    if not 1 <= n_bits <= 8:
        raise ValueError(f"n_bits must be in [1, 8] (uint8 codes), got {n_bits}")
    sub_d = d // m_subvectors
    n_codes = 2**n_bits
    flat = ivfflat_build(X, w, nlist, max_iter, seed, return_assign=True)
    coarse = flat["centers"]

    # residuals of real rows w.r.t. their coarse center (assignment reused from the
    # flat build — no second distance pass)
    assign = flat.pop("assign")
    Xh = np.asarray(X)
    valid = np.asarray(w) > 0
    resid = Xh - coarse[assign]

    codebooks = np.zeros((m_subvectors, n_codes, sub_d), np.float32)
    codes_flat = np.zeros((n, m_subvectors), np.uint8)
    rv = resid[valid]
    wv = jnp.ones((rv.shape[0],), jnp.float32)
    for m_i in range(m_subvectors):
        sub = rv[:, m_i * sub_d : (m_i + 1) * sub_d].astype(np.float32)  # noqa: fence/host-staging-copy
        k_eff = min(n_codes, sub.shape[0])
        fitted = kmeans_fit(
            jnp.asarray(sub), wv, k=k_eff, max_iter=max_iter, tol=1e-4,
            init="k-means||", init_steps=2, seed=seed + m_i, unit_weight=True,
        )
        cb = np.zeros((n_codes, sub_d), np.float32)
        cb[:k_eff] = fitted["cluster_centers"]
        if k_eff < n_codes:
            cb[k_eff:] = 1e18  # unused codes: unreachable
        codebooks[m_i] = cb
        all_sub = resid[:, m_i * sub_d : (m_i + 1) * sub_d].astype(np.float32)  # noqa: fence/host-staging-copy
        codes_flat[:, m_i] = np.asarray(
            kmeans_predict(jnp.asarray(all_sub), jnp.asarray(cb))
        ).astype(np.uint8)

    # lay codes out per cell, padded like the flat cells
    cell_ids = flat["cell_ids"]
    max_cell = cell_ids.shape[1]
    codes = np.zeros((nlist, max_cell, m_subvectors), np.uint8)
    pos = cell_ids >= 0
    codes[pos] = codes_flat[cell_ids[pos]]
    return {
        "centers": coarse,
        "center_norms": flat["center_norms"],
        "codebooks": codebooks,
        "codes": codes,
        "cell_ids": cell_ids,
        "cell_sizes": flat["cell_sizes"],
        "cells": flat["cells"],  # kept for optional exact refine
    }


@compiled_kernel(
    "knn.ivfpq_search",
    static_argnames=("k", "nprobe", "block", "strategy", "tile", "recall_target"),
)
def _ivfpq_search_impl(
    Q: jax.Array,
    centers: jax.Array,
    codebooks: jax.Array,
    codes: jax.Array,
    cell_ids: jax.Array,
    center_norms: Optional[jax.Array],
    k: int,
    nprobe: int,
    block: int,
    strategy: str,
    tile: int,
    recall_target: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    nlist, max_cell, m = codes.shape
    n_codes, sub_d = codebooks.shape[1], codebooks.shape[2]
    nq, d = Q.shape
    cb2 = jnp.sum(codebooks * codebooks, axis=-1)  # (m, n_codes)
    k_eff = min(k, nprobe * max_cell)

    def search_block(qb):
        bq = qb.shape[0]
        cd2 = _block_sq_dists(qb, centers, center_norms)  # (bq, nlist)
        _, probe = select_topk(cd2, nprobe, strategy="exact_full")  # (bq, nprobe)

        qres = qb[:, None, :] - centers[probe]  # (bq, nprobe, d)
        qsub = qres.reshape(bq, nprobe, m, sub_d)
        # LUT[bq, nprobe, m, n_codes] = ‖qsub‖² - 2·qsub·cb + ‖cb‖²
        cross = jnp.einsum("qpms,mcs->qpmc", qsub, codebooks, precision=FAST)
        q2 = jnp.sum(qsub * qsub, axis=-1)[..., None]
        lut = jnp.maximum(q2 - 2.0 * cross + cb2[None, None], 0.0)

        cell_codes = codes[probe].astype(jnp.int32)  # (bq, nprobe, max_cell, m)  # noqa: fence/host-staging-copy
        lut_t = jnp.swapaxes(lut, 2, 3)  # (bq, nprobe, n_codes, m)
        d2 = jnp.sum(
            jnp.take_along_axis(lut_t, cell_codes, axis=2), axis=-1
        )  # (bq, nprobe, max_cell)

        probed_ids = cell_ids[probe]
        flat_ids = probed_ids.reshape(bq, -1)
        flat_d2 = mask_invalid(d2.reshape(bq, -1), flat_ids >= 0)
        d2_sel, pos = select_topk(
            flat_d2, k_eff, strategy=strategy, tile=tile,
            recall_target=recall_target,
        )
        ids = jnp.take_along_axis(flat_ids, pos, axis=1)
        dists = jnp.sqrt(d2_sel)
        probe_of_pos = jnp.take_along_axis(probe, pos // max_cell, axis=1)
        flat_pos = probe_of_pos * max_cell + pos % max_cell
        return jnp.where(ids >= 0, dists, jnp.inf), ids, flat_pos

    pad = (-nq) % block
    Qp = jnp.pad(Q, ((0, pad), (0, 0)))
    db, ib, pb = jax.lax.map(search_block, Qp.reshape(-1, block, d))
    return (
        db.reshape(-1, k_eff)[:nq],
        ib.reshape(-1, k_eff)[:nq],
        pb.reshape(-1, k_eff)[:nq],
    )


def ivfpq_search(
    Q: jax.Array,
    centers: jax.Array,  # (nlist, d)
    codebooks: jax.Array,  # (m, n_codes, sub_d)
    codes: jax.Array,  # (nlist, max_cell, m) uint8
    cell_ids: jax.Array,  # (nlist, max_cell)
    k: int,
    nprobe: int,
    block: int = 256,
    *,
    center_norms: Optional[jax.Array] = None,
    strategy: Optional[str] = None,
    model_name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Asymmetric-distance (ADC) probe search: per query, build the (m, n_codes)
    lookup table of residual-subvector distances to each probed cell's center, then
    score codes by LUT gathers. The LUT uses the ‖a‖²-2ab+‖b‖² expansion (no
    (…, n_codes, sub_d) broadcast intermediate) and queries run in blocks to bound
    HBM. The candidate select (width nprobe·max_cell) takes the configured
    selection strategy; distances are ADC approximations either way, so the
    exact refine (pq_refine) remains the accuracy stage.
    Returns (approx euclidean distances, item ids, flat candidate positions)."""
    max_cell = codes.shape[1]
    k_eff = min(k, nprobe * max_cell)
    strategy, tile, rt = _sel.resolve(nprobe * max_cell, k_eff, strategy)
    if not _sel.is_tracing(Q, centers, codes):
        _sel.record_selection(strategy, site="ivfpq_search", model=model_name)
        _count_x2(center_norms, "ivfpq_search", False)
    return _ivfpq_search_impl(
        Q, centers, codebooks, codes, cell_ids, center_norms,
        k, nprobe, block, strategy, tile, rt,
    )


@compiled_kernel("knn.pq_refine", static_argnames=("k",))
def pq_refine(
    Q: jax.Array,
    cells: jax.Array,  # (nlist, max_cell, d) raw item vectors
    cand_ids_flat: jax.Array,  # (nq, kc) positions into the flattened cell layout
    cand_item_ids: jax.Array,  # (nq, kc) item ids (-1 invalid)
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Exact re-ranking of the ADC candidates (the reference's ivf_pq refine step,
    knn.py:1642-1666): gather the raw vectors of the top candidates, recompute true
    euclidean distances, take the final top-k (always exact — this IS the
    re-rank stage)."""
    nq, kc = cand_item_ids.shape
    flat_items = cells.reshape(-1, cells.shape[-1])
    vecs = flat_items[jnp.maximum(cand_ids_flat, 0)]  # (nq, kc, d)
    d2 = jnp.sum((vecs - Q[:, None, :]) ** 2, axis=-1)
    d2 = mask_invalid(d2, cand_item_ids >= 0)
    k_eff = min(k, kc)
    d2_sel, ids = merge_topk(d2, cand_item_ids, k_eff)
    dists = jnp.sqrt(d2_sel)
    return jnp.where(ids >= 0, dists, jnp.inf), ids


@compiled_kernel(
    "knn.ivfflat_search",
    static_argnames=("k", "nprobe", "block", "strategy", "tile", "recall_target"),
)
def _ivfflat_search_impl(
    Q: jax.Array,
    centers: jax.Array,
    cells: jax.Array,
    cell_ids: jax.Array,
    center_norms: Optional[jax.Array],
    k: int,
    nprobe: int,
    block: int,
    strategy: str,
    tile: int,
    recall_target: float,
) -> Tuple[jax.Array, jax.Array]:
    nlist, max_cell, d = cells.shape
    nq = Q.shape[0]
    k_eff = min(k, nprobe * max_cell)

    def search_block(qb):
        bq = qb.shape[0]
        cd2 = _block_sq_dists(qb, centers, center_norms)  # (bq, nlist)
        _, probe = select_topk(cd2, nprobe, strategy="exact_full")  # (bq, nprobe)
        probed_items = cells[probe]  # (bq, nprobe, max_cell, d)
        probed_ids = cell_ids[probe]
        flat_items = probed_items.reshape(bq, nprobe * max_cell, d)
        flat_ids = probed_ids.reshape(bq, nprobe * max_cell)
        d2 = jnp.sum((flat_items - qb[:, None, :]) ** 2, axis=-1)
        d2 = mask_invalid(d2, flat_ids >= 0)
        d2_sel, pos = select_topk(
            d2, k_eff, strategy=strategy, tile=tile, recall_target=recall_target
        )
        ids = jnp.take_along_axis(flat_ids, pos, axis=1)
        dists = jnp.sqrt(d2_sel)
        return jnp.where(ids >= 0, dists, jnp.inf), ids

    pad = (-nq) % block
    Qp = jnp.pad(Q, ((0, pad), (0, 0)))
    db, ib = jax.lax.map(search_block, Qp.reshape(-1, block, d))
    return db.reshape(-1, k_eff)[:nq], ib.reshape(-1, k_eff)[:nq]


def ivfflat_search(
    Q: jax.Array,
    centers: jax.Array,
    cells: jax.Array,
    cell_ids: jax.Array,
    k: int,
    nprobe: int,
    block: int = 64,
    *,
    center_norms: Optional[jax.Array] = None,
    strategy: Optional[str] = None,
    model_name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Probe the nprobe nearest cells per query; masked scan + configured
    selection over the nprobe·max_cell candidate width (the cell scan keeps
    the exact f32 difference-form distances, so approx here only approximates
    the id set, never the returned values). Queries run in fixed-size blocks
    (lax.map) so the probed-cell gather is (block, nprobe, max_cell, d).
    Returns (euclidean distances, item ids), id -1 where fewer than k found."""
    max_cell = cells.shape[1]
    k_eff = min(k, nprobe * max_cell)
    strategy, tile, rt = _sel.resolve(nprobe * max_cell, k_eff, strategy)
    if not _sel.is_tracing(Q, centers, cells):
        _sel.record_selection(strategy, site="ivfflat_search", model=model_name)
        _count_x2(center_norms, "ivfflat_search", False)
    return _ivfflat_search_impl(
        Q, centers, cells, cell_ids, center_norms,
        k, nprobe, block, strategy, tile, rt,
    )


# ---------------------------------------------------------------------------
# CAGRA-class graph index (the cuVS cagra equivalent, reference knn.py:1513-1524)
# ---------------------------------------------------------------------------
#
# Build: a fixed-degree kNN graph — exact for small item sets, IVF-Flat-assisted for
# large ones (cuVS builds its graph from an IVF-PQ/NN-descent pass the same way).
# Search: greedy beam traversal re-expressed with static shapes for XLA: a fixed-size
# candidate pool per query; each iteration expands the best unvisited node, gathers
# its fixed-degree adjacency row, scores the neighbors (gather + fused distance), and
# re-top-ks the pool. Duplicate ids are neutralized by a sort-adjacent-compare pass
# (they get distance=INVALID_D2 + visited=True so they neither rank nor re-expand).
# All iterations are a lax.fori_loop over purely dense ops — no dynamic frontier.


def cagra_build(
    X: jax.Array,
    w: jax.Array,
    graph_degree: int = 32,
    nlist: int = 0,
    seed: int = 42,
    exact_threshold: int = 32768,
) -> Dict[str, np.ndarray]:
    """Build the fixed-degree neighbor graph. Returns {"items", "graph",
    "item_norms_sq"} over the COMPACTED valid rows (padding rows are dropped so
    graph node ids align 1:1 with the caller's item row positions). The cached
    item norms feed cagra_search so queries never recompute Σ items²."""
    valid = np.asarray(w) > 0
    Xv = np.asarray(X)[valid].astype(np.float32)  # noqa: fence/host-staging-copy
    n_real = Xv.shape[0]
    deg = min(graph_degree, max(n_real - 1, 1))
    Xj = jnp.asarray(Xv)
    ones = jnp.ones((n_real,), jnp.float32)

    if n_real <= exact_threshold:
        _, idx = exact_knn_single(Xj, Xj, jnp.ones((n_real,), bool), deg + 1)
        idx = np.asarray(idx)
    else:
        if nlist <= 0:
            nlist = max(int(np.sqrt(n_real)), 8)
        index = ivfflat_build(Xj, ones, nlist=nlist, max_iter=10, seed=seed)
        _, idx = ivfflat_search(
            Xj,
            jnp.asarray(index["centers"]),
            jnp.asarray(index["cells"]),
            jnp.asarray(index["cell_ids"]),
            k=deg + 1,
            nprobe=max(2, nlist // 8),
            center_norms=jnp.asarray(index["center_norms"]),
        )
        idx = np.asarray(idx)

    # drop self-edges (usually slot 0); compact each row back to `deg` entries
    rows = np.arange(n_real)[:, None]
    not_self = idx != rows
    # stable partition: self (or any overflow) pushed to the end, then cut
    order = np.argsort(~not_self, axis=1, kind="stable")
    graph = np.take_along_axis(idx, order, axis=1)[:, :deg].astype(np.int32)  # noqa: fence/host-staging-copy
    graph = np.maximum(graph, 0)  # any -1 from an undersized IVF probe -> node 0
    graph = _optimize_graph_reverse_edges(Xv, graph, deg)
    return {"items": Xv, "graph": graph, "item_norms_sq": center_norms_sq(Xv)}


def _optimize_graph_reverse_edges(
    Xv: np.ndarray, graph: np.ndarray, deg: int
) -> np.ndarray:
    """Graph optimization (the role of cuVS cagra's optimize step): augment the
    forward kNN edges with REVERSE edges, then keep each node's `deg` closest
    distinct neighbors. Reverse edges give low-in-degree nodes entry points the
    greedy beam can actually reach — pure-forward kNN graphs strand hub-adjacent
    points. Fully vectorized: one lexsort over the doubled edge list."""
    n = Xv.shape[0]
    heads = np.repeat(np.arange(n, dtype=np.int64), graph.shape[1])
    tails = graph.reshape(-1).astype(np.int64)
    d = np.linalg.norm(Xv[heads] - Xv[tails], axis=1)
    all_h = np.concatenate([heads, tails])
    all_t = np.concatenate([tails, heads])
    all_d = np.concatenate([d, d])
    keep = all_h != all_t
    all_h, all_t, all_d = all_h[keep], all_t[keep], all_d[keep]

    # dedupe (h, t) pairs keeping the min distance, then rank per head by distance
    key = all_h * n + all_t
    o = np.lexsort((all_d, key))
    key_s = key[o]
    first = np.concatenate([[True], key_s[1:] != key_s[:-1]])
    h2, t2, d2 = all_h[o][first], all_t[o][first], all_d[o][first]
    o2 = np.lexsort((d2, h2))
    h3, t3 = h2[o2], t2[o2]
    counts = np.bincount(h3, minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    within = np.arange(len(h3)) - np.repeat(starts, counts)
    sel = within < deg
    out = graph.copy()  # nodes with < deg merged edges keep their forward fill
    out[h3[sel], within[sel]] = t3[sel].astype(np.int32)  # noqa: fence/host-staging-copy
    return out


@compiled_kernel(
    "knn.cagra_search",
    static_argnames=(
        "k", "itopk", "iterations", "search_width", "strategy", "tile",
        "recall_target",
    ),
)
def _cagra_search_impl(
    Q: jax.Array,
    items: jax.Array,
    graph: jax.Array,
    x2: Optional[jax.Array],
    k: int,
    itopk: int,
    iterations: int,
    search_width: int,
    strategy: str,
    tile: int,
    recall_target: float,
) -> Tuple[jax.Array, jax.Array]:
    n, d = items.shape
    deg = graph.shape[1]
    nq = Q.shape[0]
    itopk_eff = min(itopk, n)
    if x2 is None:
        x2 = jnp.sum(items * items, axis=1)

    def dists_to(ids):  # ids (nq, m) -> squared distances (nq, m)
        vecs = items[ids]  # gather
        cross = jnp.einsum("qmd,qd->qm", vecs, Q, precision=FAST)
        q2 = jnp.sum(Q * Q, axis=1, keepdims=True)
        return jnp.maximum(q2 - 2.0 * cross + x2[ids], 0.0)

    # entry points: an even stride over the items (randomization-free, shape-static)
    ids0 = jnp.linspace(0, n - 1, itopk_eff).astype(jnp.int32)
    ids0 = jnp.broadcast_to(ids0, (nq, itopk_eff))
    d20 = dists_to(ids0)
    visited0 = jnp.zeros((nq, itopk_eff), bool)

    width = max(1, min(search_width, itopk_eff))

    def body(_, state):
        ids, d2, visited = state
        # expand the `width` best unvisited pool entries (exact select: the
        # pool is the loop-carried state — an approximate pick here compounds
        # per iteration, which no recall target bounds)
        expand_key = mask_invalid(d2, ~visited)
        _, best = select_topk(expand_key, width, strategy="exact_full")
        visited = visited | (
            jnp.sum(jax.nn.one_hot(best, itopk_eff, dtype=jnp.int32), axis=1) > 0
        )
        best_ids = jnp.take_along_axis(ids, best, axis=1)  # (nq, width)
        nbrs = graph[best_ids].reshape(nq, width * deg)
        nd2 = dists_to(nbrs)

        all_ids = jnp.concatenate([ids, nbrs], axis=1)
        all_d2 = jnp.concatenate([d2, nd2], axis=1)
        all_vis = jnp.concatenate(
            [visited, jnp.zeros((nq, width * deg), bool)], axis=1
        )

        # duplicate suppression: sort by id; any entry equal to its left neighbor is
        # a duplicate -> INVALID_D2 (never ranks) + visited (never re-expands).
        # Stable sort keeps the pool's copy (with its visited flag) first.
        order = jnp.argsort(all_ids, axis=1, stable=True)
        sid = jnp.take_along_axis(all_ids, order, axis=1)
        sd2 = jnp.take_along_axis(all_d2, order, axis=1)
        svis = jnp.take_along_axis(all_vis, order, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((nq, 1), bool), sid[:, 1:] == sid[:, :-1]], axis=1
        )
        sd2 = jnp.where(dup, INVALID_D2, sd2)
        svis = svis | dup

        new_d2, pos = select_topk(sd2, itopk_eff, strategy="exact_full")
        new_ids = jnp.take_along_axis(sid, pos, axis=1)
        new_vis = jnp.take_along_axis(svis, pos, axis=1)
        return new_ids, new_d2, new_vis

    ids, d2, _ = jax.lax.fori_loop(0, iterations, body, (ids0, d20, visited0))
    k_eff = min(k, itopk_eff)
    d2_sel, pos = select_topk(
        d2, k_eff, strategy=strategy, tile=tile, recall_target=recall_target
    )
    out_ids = jnp.take_along_axis(ids, pos, axis=1)
    return jnp.sqrt(d2_sel), out_ids


def cagra_search(
    Q: jax.Array,
    items: jax.Array,  # (n, d)
    graph: jax.Array,  # (n, deg) int32
    k: int,
    itopk: int = 64,
    iterations: int = 32,
    search_width: int = 1,
    *,
    x2: Optional[jax.Array] = None,
    strategy: Optional[str] = None,
    model_name: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Greedy beam search over the neighbor graph. `search_width` (cuVS param of
    the same name) expands the W best unvisited pool entries per iteration — the
    gathers batch W*deg neighbors, so width converts iteration latency into MXU/
    gather throughput at equal total expansions. The in-loop pool maintenance
    selects exactly (loop-carried state); the configured strategy applies to
    the final k-of-itopk select. Cached `x2` (built index item norms) keeps
    Σ items² out of the per-search recompute.

    Returns (euclidean distances, item ids), shapes (nq, min(k, itopk))."""
    itopk_eff = min(itopk, items.shape[0])
    k_eff = min(k, itopk_eff)
    strategy, tile, rt = _sel.resolve(itopk_eff, k_eff, strategy)
    if not _sel.is_tracing(Q, items, graph):
        _sel.record_selection(strategy, site="cagra_search", model=model_name)
        _count_x2(x2, "cagra_search", False)
    return _cagra_search_impl(
        Q, items, graph, x2, k, itopk, iterations, search_width,
        strategy, tile, rt,
    )


def exact_knn_ring(
    mesh: Mesh,
    Q_sharded: jax.Array,  # (nq_padded, d) row-sharded queries
    X_sharded: jax.Array,  # (n_padded, d) row-sharded items
    valid_sharded: jax.Array,  # (n_padded,) bool
    k: int,
    x2_sharded: Optional[jax.Array] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Ring-allreduce exact kNN: BOTH queries and items stay sharded. Each device
    keeps its query block resident and the item shards rotate around the ring via
    ppermute; a running top-k merges after every hop. Peak per-device memory is
    one query block x one item shard — unlike the all_gather merge
    (exact_knn_distributed), nothing global ever materializes, so this is the path
    for query sets too large to replicate (the structural analog of cuML NN-MG's
    UCX block exchange, reference knn.py:763-774, laid onto the ICI ring).

    The item-norm term rotates WITH the shard (computed once pre-loop when no
    cache is passed), so no hop recomputes it; per-hop candidate selection
    takes the configured strategy (with a per-hop parity re-rank under approx
    — the shard is resident, so exactness costs one small gather), and the
    running merge stays exact.

    Returns host (distances, global item indices) for the real (unpadded) rows."""
    n_total = X_sharded.shape[0]
    n_dev = mesh.devices.size
    shard_rows = n_total // n_dev
    k_eff = min(k, n_total)
    # a shard may hold fewer than k rows; per-hop candidates are capped at the
    # shard size and the running pool still converges to the global top-k
    k_hop = min(k_eff, shard_rows)
    strategy, tile, rt = _sel.resolve(shard_rows, k_hop, None)
    _sel.record_selection(strategy, site="exact_knn_ring")
    _count_x2(x2_sharded, "exact_knn_ring", False)

    from ..parallel.partitioner import partitioner_for

    part = partitioner_for(mesh)
    in_specs = (part.data_spec(2), part.data_spec(2), part.data_spec(1))
    if x2_sharded is not None:
        in_specs = in_specs + (part.data_spec(1),)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(part.data_spec(2), part.data_spec(2)),
    )
    def _ring(q_local, x_local, valid_local, *maybe_x2):
        rank = jax.lax.axis_index(DATA_AXIS)
        nq_local = q_local.shape[0]
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        # the norm term is computed ONCE (or passed in cached) and rotates
        # with its shard — no hop recomputes Σ x²
        x2_local = (
            maybe_x2[0] if maybe_x2 else jnp.sum(x_local * x_local, axis=1)
        )

        def hop(h, state):
            x_cur, valid_cur, x2_cur, best_d2, best_idx = state
            # owner rank of the shard currently held: it started at `rank` and has
            # moved h hops along the ring
            owner = (rank - h) % n_dev
            d2 = _block_sq_dists(q_local, x_cur, x2_cur)
            d2 = mask_invalid(d2, valid_cur[None, :])
            hop_d2, idx = select_topk(
                d2, k_hop, strategy=strategy, tile=tile, recall_target=rt
            )
            if strategy == "approx":
                # the shard is resident: restore exact distances for the
                # approx winner pool before it enters the running merge
                hop_d2, idx = parity_rerank_sq(
                    q_local, x_cur, valid_cur, idx, k_hop
                )
            gidx = idx + owner * shard_rows
            # merge the hop's candidates into the running top-k (always exact)
            cat_d2 = jnp.concatenate([best_d2, hop_d2], axis=1)
            cat_idx = jnp.concatenate([best_idx, gidx], axis=1)
            best_d2, best_idx = merge_topk(cat_d2, cat_idx, k_eff)
            # rotate the item shard one hop along the ring
            x_next = jax.lax.ppermute(x_cur, DATA_AXIS, perm)
            valid_next = jax.lax.ppermute(valid_cur, DATA_AXIS, perm)
            x2_next = jax.lax.ppermute(x2_cur, DATA_AXIS, perm)
            return x_next, valid_next, x2_next, best_d2, best_idx

        # the running top-k derives from axis_index (varying over the mesh axis);
        # mark the literal init values varying too so the loop carry types agree
        init = (
            x_local,
            valid_local,
            x2_local,
            jax.lax.pcast(
                jnp.full((nq_local, k_eff), INVALID_D2, q_local.dtype),
                (DATA_AXIS,), to="varying",
            ),
            jax.lax.pcast(
                jnp.full((nq_local, k_eff), -1, jnp.int32),
                (DATA_AXIS,), to="varying",
            ),
        )
        _, _, _, best_d2, best_idx = jax.lax.fori_loop(0, n_dev, hop, init)
        return best_d2, best_idx

    if x2_sharded is not None:
        d2, gidx = _ring(Q_sharded, X_sharded, valid_sharded, x2_sharded)
    else:
        d2, gidx = _ring(Q_sharded, X_sharded, valid_sharded)
    return np.sqrt(np.maximum(np.asarray(d2), 0.0)), np.asarray(gidx)
