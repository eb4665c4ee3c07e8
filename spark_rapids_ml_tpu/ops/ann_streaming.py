#
# Out-of-core IVF-Flat: the ANN leg of the UVM/SAM replacement tier
# (reference utils.py:184-241 lets cuVS index datasets beyond device memory via
# managed memory; reference ANN role: knn.py:1538-1690).
#
# TPU formulation: the ITEM SET stays host-resident end to end.
#   * build: coarse centers fit in-core on a bounded row subsample, then the
#     full dataset streams through the device in batches only to be ASSIGNED to
#     cells (one (batch, nlist) distance matmul per batch); the dense
#     cell layout is materialized host-side.
#   * search: per query block, only the PROBED cells travel host->device —
#     device residency is (block, nprobe, max_cell, d) + centers, never the
#     dataset. This is the managed-memory access pattern made explicit: the
#     probe list is the page table, the gathered cells are the pages.
#
# In-core ivfflat (ops/knn.py) remains the fast path when cells fit HBM; the
# estimator (models/knn.py) picks this module above the stream threshold.
#

from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import span as obs_span
from ..parallel.partitioner import put_device_local
from ..observability.runs import (
    WorkerScope,
    counter_inc as obs_counter_inc,
    current_run,
    observe as obs_observe,
)
from ..reliability import RetryPolicy, fault_point
from . import selection as _sel
from .selection import mask_invalid, merge_topk, select_topk
from ..observability.device import compiled_kernel
from .streaming import _prefetch

# per-batch rank/phase timeline entries are recorded only for builds/searches
# up to this many batches: the timeline is a forensic surface (which batch
# dragged?), not an accounting one, and a million-batch build must not grow a
# million-row worker list on the run
_TIMELINE_BATCHES_CAP = 64


def _normalize_batch_or_raise(Xb: np.ndarray) -> np.ndarray:
    """Cosine-tier batch normalization — one shared zero-row contract
    (ops/knn.py::normalize_rows_or_raise)."""
    from .knn import normalize_rows_or_raise

    return normalize_rows_or_raise(Xb)


def _strided_sample_indices(n: int, sample_rows: int) -> np.ndarray:
    """Deterministic strided row subsample of EXACTLY min(n, sample_rows)
    rows, evenly spanning [0, n) — rows are not assumed shuffled, so the
    sample must cover the tail too. The old `step = max(1, n // min(n,
    sample_rows))` form kept every stride hit and returned up to ~2x
    sample_rows rows whenever n is just under a multiple of the step; a
    truncated arange would instead clamp the count but silently drop the tail
    distribution. `(i * n) // m` is strictly increasing for n >= m, so the
    indices are unique, exactly m, and reach within n/m rows of the end."""
    m = min(int(n), int(sample_rows))
    if m <= 0:
        return np.arange(0, 0)
    return (np.arange(m, dtype=np.int64) * int(n)) // m


def resolve_build_batch_rows() -> int:
    """`ann.build_batch_rows` resolution for the pipelined builds: a non-zero
    config pin wins, then an EXPLICITLY-configured `stream_batch_rows` (a
    deployment that sized batches for its streamed fits keeps that
    geometry), then the defaults-module build
    geometry (ANN_BUILD_BATCH_ROWS — two 64k-row staging buffers in flight,
    not the 1M-row streamed-fit default)."""
    from .. import config as _config
    from ..defaults import ANN_BUILD_BATCH_ROWS

    pinned = int(_config.get("ann.build_batch_rows") or 0)
    if pinned > 0:
        return pinned
    if _config.source("stream_batch_rows") != "default":
        return int(_config.get("stream_batch_rows"))
    return int(ANN_BUILD_BATCH_ROWS)


def _pipelined_run(
    total: int,
    batch_rows: int,
    site: str,
    dispatch: Callable[[int, int, int], object],
    finalize: Callable[[int, int, int, object], None],
    depth: Optional[int] = None,
) -> None:
    """THE pipelined out-of-core batch loop, shared by every streamed ANN
    build/search/refine stage. `dispatch(bi, s, e)` host-stages one batch
    (slice -> normalize -> device_put) and ASYNC-dispatches its device op(s),
    returning the in-flight device values; `finalize(bi, s, e, out)` performs
    the blocking host pull and the idempotent [s, e) host write. Routed
    through ops/streaming.py::_prefetch with `ann.prefetch_depth` extra
    batches in flight, host staging of batch i+1 overlaps device execution of
    batch i (jax dispatch is async; the DMA rides a separate engine on TPU).
    depth 0 degrades to the serial per-batch loop — the bench baseline.

    Retry contract (unchanged from the serial loops): `fault_point(site,
    batch=bi)` fires before each staging attempt, and BOTH halves run under
    the per-batch RetryPolicy. A drain-side failure re-runs `dispatch` for
    just that batch (the in-flight value died with the failed attempt) —
    writes target only [s, e), so a retried batch is bit-identical to a
    fault-free one.

    Telemetry: `ann.stage_s{site=}` / `ann.drain_s{site=}` histograms are the
    overlap evidence (pipelined wall << Σstage + Σdrain), and each batch of a
    small build lands as a rank row (rank = batch ordinal, phase = site) in
    the open run's §6h timeline, so a straggler batch is visible exactly like
    a straggler barrier rank."""
    from .. import config as _config

    if depth is None:
        depth = int(_config.get("ann.prefetch_depth"))
    policy = RetryPolicy.from_config()
    run = current_run()
    n_batches = -(-total // batch_rows) if total > 0 else 0
    timeline = run is not None and 1 < n_batches <= _TIMELINE_BATCHES_CAP
    t_loop0 = time.perf_counter()

    def gen():
        for bi, s in enumerate(range(0, total, batch_rows)):
            e = min(s + batch_rows, total)
            work = {"wall_s": 0.0}  # the batch's OWN stage+drain seconds

            def _stage(bi=bi, s=s, e=e, work=work):
                # timer opens BEFORE the fault point: an injected sleep= (a
                # deterministic straggler) is this batch's stall and must
                # land in ITS stage wall / timeline row
                t0 = time.perf_counter()
                fault_point(site, batch=bi)
                out = dispatch(bi, s, e)
                dt = time.perf_counter() - t0
                work["wall_s"] += dt
                obs_observe("ann.stage_s", dt, site=site)
                return out

            obs_counter_inc("ann.pipeline_batches", 1, site=site)
            yield bi, s, e, _stage, work, policy.run(_stage, site=site)

    stream = gen() if depth <= 0 else _prefetch(gen(), depth=depth)
    for bi, s, e, stage, work, out in stream:
        state = {"out": out, "fresh": True}

        def _drain(s=s, e=e, bi=bi, stage=stage, state=state, work=work):
            if not state["fresh"]:
                # the in-flight value died with the failed attempt: re-stage
                # and re-dispatch this batch (same idempotent write target)
                state["out"] = stage()
            state["fresh"] = False
            t0 = time.perf_counter()
            finalize(bi, s, e, state["out"])
            dt = time.perf_counter() - t0
            work["wall_s"] += dt
            obs_observe("ann.drain_s", dt, site=site)

        policy.run(_drain, site=site)
        if timeline:
            # batch-as-rank timeline row: same-process snapshots merge
            # breakdown-only (no metric double count), and the comm plane's
            # skew/straggler machinery applies to build batches for free.
            # wall_s is the batch's OWN stage+drain time — wall-clock from
            # staging would also count time parked in the prefetch buffer
            # behind a neighbor's drain and smear a straggler across two rows
            ws = WorkerScope(rank=bi, run_id=run.run_id)
            ws.note_phase(site, wall_s=work["wall_s"], rows=e - s)
            run.add_worker_snapshot(ws.snapshot())
    # whole-loop wall: the overlap denominator — Σstage + Σdrain exceeding
    # this is the proof that host staging hid behind device execution
    obs_observe("ann.pipeline_s", time.perf_counter() - t_loop0, site=site)


def streaming_ivfflat_build(
    X: np.ndarray,
    nlist: int,
    max_iter: int,
    seed: int,
    batch_rows: int,
    sample_rows: int = 1 << 18,
    return_assign: bool = False,
    cosine: bool = False,
) -> Dict[str, np.ndarray]:
    """Build the IVF layout with the dataset host-resident: centers from an
    in-core kmeans on a strided subsample (rows are not assumed shuffled), then
    streamed batch assignment. Returns the same dict shape as ops/knn.py::
    ivfflat_build but with `cells`/`cell_ids` as HOST arrays.

    `cosine=True` builds the index on the UNIT SPHERE without materializing a
    normalized copy of the dataset: the subsample, each assignment batch, and
    the cell layout's gather pass normalize on the fly (the in-core path
    instead normalizes the whole device array up front,
    models/knn.py::_normalize_or_raise). Queries must be normalized at search,
    which the model layer already does for cosine."""
    from .kmeans import kmeans_fit, kmeans_predict

    n, d = X.shape
    Xs = np.ascontiguousarray(X[_strided_sample_indices(n, sample_rows)],  # noqa: fence/host-staging-copy
                              dtype=np.float32)
    if cosine:
        Xs = _normalize_batch_or_raise(Xs)
    # the coarse kmeans trains on the SUBSAMPLE: k must fit it, not just n
    nlist = min(nlist, len(Xs))
    fitted = kmeans_fit(
        jnp.asarray(Xs), jnp.ones((len(Xs),), jnp.float32), k=nlist,
        max_iter=max_iter, tol=1e-4, init="k-means||", init_steps=2, seed=seed,
        unit_weight=True,
    )
    centers = fitted["cluster_centers"]
    centers_j = jnp.asarray(centers)

    # pipelined per-batch assignment: each batch writes only assign[s:e]
    # (idempotent), so a transient fault re-runs just that batch under the
    # retry policy — results are unchanged; host staging of batch i+1 overlaps
    # the device's assignment matmul of batch i (_pipelined_run)
    assign = np.empty((n,), np.int32)

    def _dispatch_assign(bi, s, e):
        Xb = np.ascontiguousarray(X[s:e], dtype=np.float32)  # noqa: fence/host-staging-copy
        if cosine:
            Xb = _normalize_batch_or_raise(Xb)
        return kmeans_predict(put_device_local(Xb), centers_j)

    def _finalize_assign(bi, s, e, out):
        assign[s:e] = np.asarray(out)

    _pipelined_run(n, batch_rows, "ann_assign", _dispatch_assign,
                   _finalize_assign)

    from .knn import layout_cells

    # X passes through UNconverted: layout_cells casts inside its row gather,
    # so the streamed path no longer materializes a second full-dense f32
    # copy of the dataset before laying out the cells
    cells, cell_ids, cell_sizes = layout_cells(
        np.asarray(X), assign, nlist,
        normalize=cosine,
    )
    from .knn import center_norms_sq

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


def streaming_ivfpq_build(
    X: np.ndarray,
    nlist: int,
    m_subvectors: int,
    n_bits: int,
    max_iter: int,
    seed: int,
    batch_rows: int,
    sample_rows: int = 1 << 18,
    cosine: bool = False,
) -> Dict[str, np.ndarray]:
    """Out-of-core IVF-PQ build (cuVS ivf_pq role, reference knn.py:1510-1524,
    under the managed-memory tier utils.py:184-241): coarse cells via the
    streamed IVF-Flat build, PQ codebooks trained in-core on a strided RESIDUAL
    subsample, then codes assigned in streamed encoding passes — the dataset
    itself never resides on device. Same index layout as ops/knn.py::ivfpq_build
    (codebooks (m, 2^bits, d/m), codes (nlist, max_cell, m) uint8)."""
    from .kmeans import kmeans_fit, kmeans_predict

    n, d = X.shape
    if d % m_subvectors != 0:
        raise ValueError(f"n features {d} not divisible by pq m={m_subvectors}")
    if not 1 <= n_bits <= 8:
        raise ValueError(f"n_bits must be in [1, 8] (uint8 codes), got {n_bits}")
    sub_d = d // m_subvectors
    n_codes = 2**n_bits
    flat = streaming_ivfflat_build(
        X, nlist, max_iter, seed, batch_rows, sample_rows, return_assign=True,
        cosine=cosine,
    )
    coarse = np.asarray(flat["centers"])
    assign = flat.pop("assign")

    # codebooks from a residual subsample (strided — rows are not assumed
    # shuffled); the in-core build trains on ALL residuals, so codebooks differ
    # in detail but the recall/quality contract is preserved (tested)
    sub_idx = _strided_sample_indices(n, sample_rows)
    X_sub = np.ascontiguousarray(X[sub_idx], np.float32)  # noqa: fence/host-staging-copy
    if cosine:
        X_sub = _normalize_batch_or_raise(X_sub)
    resid_s = X_sub - coarse[assign[sub_idx]]
    wv = jnp.ones((len(sub_idx),), jnp.float32)
    codebooks = np.zeros((m_subvectors, n_codes, sub_d), np.float32)
    for m_i in range(m_subvectors):
        sub = resid_s[:, m_i * sub_d : (m_i + 1) * sub_d]
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

    # pipelined streamed encoding: one batch upload covers all m
    # sub-encodings (dispatched async, pulled in the drain half); per-batch
    # retry as in the assignment loop (idempotent codes_flat[s:e] writes)
    cb_j = [jnp.asarray(codebooks[m_i]) for m_i in range(m_subvectors)]
    codes_flat = np.zeros((n, m_subvectors), np.uint8)

    def _dispatch_encode(bi, s, e):
        Xb_enc = np.ascontiguousarray(X[s:e], np.float32)  # noqa: fence/host-staging-copy
        if cosine:
            Xb_enc = _normalize_batch_or_raise(Xb_enc)
        resid_b = put_device_local(Xb_enc - coarse[assign[s:e]])
        return [
            kmeans_predict(
                resid_b[:, m_i * sub_d : (m_i + 1) * sub_d], cb_j[m_i]
            )
            for m_i in range(m_subvectors)
        ]

    def _finalize_encode(bi, s, e, outs):
        for m_i, out in enumerate(outs):
            codes_flat[s:e, m_i] = np.asarray(out).astype(np.uint8)

    _pipelined_run(n, batch_rows, "ann_encode", _dispatch_encode,
                   _finalize_encode)

    cell_ids = flat["cell_ids"]
    # size codes from the BUILT index, not the requested nlist: the IVF build
    # clamps nlist to the subsample size (streaming_ivfflat_build), so the
    # caller's nlist can exceed cell_ids.shape[0] — codes must match the
    # centers/cell layout actually built (ADVICE round-5 finding)
    nlist_eff, max_cell = cell_ids.shape
    codes = np.zeros((nlist_eff, max_cell, m_subvectors), np.uint8)
    pos = cell_ids >= 0
    codes[pos] = codes_flat[cell_ids[pos]]
    return {
        "centers": coarse,
        "center_norms": flat["center_norms"],
        "codebooks": codebooks,
        "codes": codes,
        "cell_ids": cell_ids,
        "cell_sizes": flat["cell_sizes"],
        "cells": flat["cells"],  # host-resident; kept for optional exact refine
    }


def streaming_cagra_build(
    X: np.ndarray,
    graph_degree: int = 32,
    nlist: int = 0,
    seed: int = 42,
    batch_rows: int = 1 << 16,
    sample_rows: int = 1 << 18,
    cosine: bool = False,
) -> Dict[str, np.ndarray]:
    """Out-of-core CAGRA-class graph build (cuVS cagra role, reference
    knn.py:1538-1690): the fixed-degree kNN graph comes from STREAMED IVF
    searches — items host-resident, each item batch queries the paged IVF index
    (streaming_ivfflat_search) for its deg+1 neighbors — then the same
    reverse-edge optimization as the in-core build runs on host. Search remains
    in-core (cagra_search walks the graph with random access; the returned
    {"items", "graph"} match ops/knn.py::cagra_build's contract)."""
    from .knn import _optimize_graph_reverse_edges

    X = np.ascontiguousarray(np.asarray(X), dtype=np.float32)  # noqa: fence/host-staging-copy
    if cosine:
        # the graph AND the returned items must live on the unit sphere (the
        # searcher walks euclidean distances over them) — one normalized copy,
        # exactly what the in-core estimator materializes before cagra_build
        X = _normalize_batch_or_raise(X)
    n = X.shape[0]
    deg = min(graph_degree, max(n - 1, 1))
    if nlist <= 0:
        nlist = max(int(np.sqrt(n)), 8)
    index = streaming_ivfflat_build(
        X, nlist=nlist, max_iter=10, seed=seed, batch_rows=batch_rows,
        sample_rows=sample_rows,
    )
    nprobe = min(nlist, max(2, nlist // 8))
    idx = np.full((n, deg + 1), -1, np.int64)
    for s in range(0, n, batch_rows):
        e = min(s + batch_rows, n)
        _, ib = streaming_ivfflat_search(X[s:e], index, k=deg + 1, nprobe=nprobe)
        # the paged search returns min(k, nprobe*max_cell) columns; leave any
        # shortfall as -1 (mapped to node 0 below, same as the in-core build)
        idx[s:e, : ib.shape[1]] = ib

    rows = np.arange(n)[:, None]
    not_self = idx != rows
    order = np.argsort(~not_self, axis=1, kind="stable")
    graph = np.take_along_axis(idx, order, axis=1)[:, :deg].astype(np.int32)  # noqa: fence/host-staging-copy
    graph = np.maximum(graph, 0)  # any -1 from an undersized probe -> node 0
    graph = _optimize_graph_reverse_edges(X, graph, deg)
    from .knn import center_norms_sq

    return {"items": X, "graph": graph, "item_norms_sq": center_norms_sq(X)}


@compiled_kernel("ann.probe_cells", static_argnames=("nprobe",))
def _probe_cells(
    Q: jax.Array, centers: jax.Array, nprobe: int, center_norms=None
):
    from .knn import _block_sq_dists

    cd2 = _block_sq_dists(Q, centers, center_norms)
    # coarse probe stays exact: nprobe already bounds recall; an approximate
    # probe would compound with the candidate-select approximation
    _, probe = select_topk(cd2, nprobe, strategy="exact_full")
    return probe


@compiled_kernel("ann.scan_probed",
                 static_argnames=("k", "strategy", "tile", "recall_target"))
def _scan_probed(qb, probed_items, probed_ids, k, strategy, tile, recall_target):
    """(bq, nprobe, max_cell, d) probed cells -> per-query top-k. EXACT f32
    difference-form distances, matching ops/knn.py::ivfflat_search's in-core
    cell scan rank-for-rank (the candidate set per query is small, so the exact
    form costs nothing; the expanded bf16 form was observed to reorder
    near-duplicate candidates vs the in-core path). The configured selection
    strategy applies to the candidate width; distances stay exact either way."""
    bq, nprobe, max_cell, d = probed_items.shape
    flat = probed_items.reshape(bq, nprobe * max_cell, d)
    flat_ids = probed_ids.reshape(bq, nprobe * max_cell)
    d2 = jnp.sum((flat - qb[:, None, :]) ** 2, axis=2)
    d2 = mask_invalid(d2, flat_ids >= 0)
    d2_sel, pos = select_topk(
        d2, k, strategy=strategy, tile=tile, recall_target=recall_target
    )
    ids = jnp.take_along_axis(flat_ids, pos, axis=1)
    dists = jnp.sqrt(d2_sel)
    return jnp.where(ids >= 0, dists, jnp.inf), ids


def streaming_ivfflat_search(
    Q: np.ndarray,
    index: Dict[str, np.ndarray],
    k: int,
    nprobe: int,
    block: int = 256,
) -> Tuple[np.ndarray, np.ndarray]:
    """Search with host-resident cells: per query block the probe list is
    computed on device, then ONLY the probed cells are gathered host-side and
    device_put — (block, nprobe, max_cell, d) device residency. Returns
    (euclidean distances, item ids) of width k_eff = min(k, nprobe*max_cell),
    id -1 where fewer than k found — the SAME width contract as the in-core
    ivfflat_search, so results are byte-identical across the threshold."""
    centers_j = jnp.asarray(index["centers"])
    center_norms = index.get("center_norms")
    cn_j = jnp.asarray(center_norms) if center_norms is not None else None
    cells = index["cells"]
    cell_ids = index["cell_ids"]
    nlist, max_cell, d = cells.shape
    nq = Q.shape[0]
    k_eff = min(k, nprobe * max_cell)
    strategy, tile, rt = _sel.resolve(nprobe * max_cell, k_eff, None)
    _sel.record_selection(strategy, site="ann_streaming_search")
    # the COARSE probe is a fusable scan (Q vs resident centers): route it
    # through the fused pallas distance+select kernel when `pallas_fused`
    # resolves for the nlist width (explicit, or auto on TPU past
    # knn.pallas_min_items). The probe stays exact-f32 either way — the probe
    # list bounds recall for the whole search, so knn.pallas_precision never
    # applies to it; ids are bit-identical to the exact_full probe.
    probe_fused = (
        _sel.resolve(nlist, min(nprobe, nlist), None, fusable=True)[0]
        == "pallas_fused"
    )
    if probe_fused:
        _sel.record_selection(
            "pallas_fused", site="ann_streaming_probe"
        )
    from .knn import _count_x2

    _count_x2(cn_j, "ann_streaming_search", False)

    out_d = np.full((nq, k_eff), np.inf, np.float32)
    out_i = np.full((nq, k_eff), -1, np.int64)

    def _dispatch_search(bi, s, e):
        qb = put_device_local(np.ascontiguousarray(Q[s:e], dtype=np.float32))  # noqa: fence/host-staging-copy
        if probe_fused:
            from .pallas_select import fused_probe

            probe = np.asarray(
                fused_probe(qb, centers_j, nprobe, center_norms=cn_j)
            )  # (bq, nprobe) — bit-identical to the exact probe
        else:
            probe = np.asarray(
                _probe_cells(qb, centers_j, nprobe, cn_j)
            )  # (bq, nprobe)
        # the host gather IS the out-of-core page-in; placement goes through
        # the active Partitioner's local-device path (process-local staging)
        probed_items = put_device_local(cells[probe])
        probed_ids = put_device_local(cell_ids[probe])
        # span covers the fused scan+select kernel dispatch — named for what
        # it times (the standalone `knn.select`/`knn.rerank` spans are
        # reserved for separately-dispatched selection/re-rank programs)
        with obs_span("ann.scan_select", {"start": s, "rows": e - s}):
            return _scan_probed(
                qb, probed_items, probed_ids, k_eff, strategy, tile, rt
            )

    def _finalize_search(bi, s, e, out):
        dists, ids = out
        out_d[s:e] = np.asarray(dists)
        out_i[s:e] = np.asarray(ids)

    # pipelined per-block retry: each block only writes out_d/out_i[s:e]
    # (idempotent); the host gather/page-in of block i+1 overlaps the device
    # scan of block i
    _pipelined_run(nq, block, "ann_search", _dispatch_search, _finalize_search)
    return out_d, out_i


@compiled_kernel("ann.refine_exact_tile", static_argnames=("k",))
def _refine_exact_tile(qb, vecs, item_ids, k: int):
    """Exact re-rank tile (always exact_full — this IS the re-rank stage)."""
    d2 = jnp.sum((vecs - qb[:, None, :]) ** 2, axis=-1)
    d2 = mask_invalid(d2, item_ids >= 0)
    d2_sel, ids = merge_topk(d2, item_ids, k)
    dists = jnp.sqrt(d2_sel)
    return jnp.where(ids >= 0, dists, jnp.inf), ids


def streaming_pq_refine(
    Q: np.ndarray,
    cells: np.ndarray,
    cand_ids_flat: np.ndarray,
    cand_item_ids: np.ndarray,
    k: int,
    block: int = 1024,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-paged exact re-rank of ADC candidates (ops/knn.py::pq_refine with the
    cell layout HOST-RESIDENT): the candidate gather is the page-in — only
    (block, kc, d) candidate vectors ever reach the device, never the full
    cell-padded dataset. Same result contract as pq_refine."""
    flat = cells.reshape(-1, cells.shape[-1])
    nq, kc = cand_item_ids.shape
    k_eff = min(k, kc)
    out_d = np.empty((nq, k_eff), np.float32)
    out_i = np.empty((nq, k_eff), np.int64)
    cand_pos = np.maximum(np.asarray(cand_ids_flat), 0)
    cand_ids = np.asarray(cand_item_ids)

    def _dispatch_refine(bi, s, e):
        vecs = put_device_local(flat[cand_pos[s:e]])  # the host page-in
        with obs_span("knn.rerank", {"start": s, "rows": e - s}):
            return _refine_exact_tile(
                put_device_local(np.ascontiguousarray(Q[s:e], np.float32)),  # noqa: fence/host-staging-copy
                vecs,
                put_device_local(cand_ids[s:e]),
                k_eff,
            )

    def _finalize_refine(bi, s, e, out):
        d_b, i_b = out
        out_d[s:e] = np.asarray(d_b)
        out_i[s:e] = np.asarray(i_b)

    # pipelined per-block retry (idempotent out_d/out_i[s:e] writes), same
    # site as the paged IVF search — both are search-phase page-ins
    _pipelined_run(nq, block, "ann_search", _dispatch_refine, _finalize_refine)
    return out_d, out_i
