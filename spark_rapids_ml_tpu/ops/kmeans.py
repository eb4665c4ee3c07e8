#
# KMeans fit/predict kernels — the TPU-native replacement for
# cuml.cluster.kmeans_mg.KMeansMG (reference clustering.py:376-456; the centroid
# allreduce happens inside cuML over NCCL).
#
# TPU formulation: Lloyd iterations as one jitted lax.while_loop over row-sharded data.
# Per iteration:
#   * assignment: pairwise squared distances via the ‖x‖² - 2x·c + ‖c‖² expansion —
#     an (n,k) matmul on the MXU,
#   * update: one-hot(assign)ᵀ @ X — another MXU matmul whose contraction over the
#     sharded row axis makes XLA emit the psum over ICI (exactly where cuML put its
#     NCCL allreduce).
# Empty clusters keep their previous center (cuML/Spark behavior for stability).
#
# Initialization: "random" picks k real rows; "k-means||" (Spark's default initMode)
# runs `initSteps` rounds of distance-weighted oversampling. The reference delegates to
# cuML's scalable-k-means++; the TPU version keeps shapes static by sampling a fixed
# 2k candidates per round via the Gumbel-top-k trick on log(d²) (sampling without
# replacement ∝ d², same distribution as k-means|| oversampling with l=2k), then runs
# weighted k-means++ on the small candidate set host-side — the same
# cluster-then-reduce structure as scalable k-means++.
#

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..autotune.defaults import (
    COUNT_BLOCK_SPLIT,
    COUNT_DEVICE_MAX_CENTERS,
    LLOYD_ASSIGN3_MIN_WORK,
    LLOYD_FUSED_MIN_K,
    LLOYD_RECHECK_SHARE,
)
from ..observability import counter_inc, span
from ..observability.device import compiled_kernel
from ._precision import FAST, parity_precision, pdot
from .selection import top_k_max


@functools.partial(jax.jit, static_argnames=("fast",))
def _sq_dists(X: jax.Array, centers: jax.Array, fast: bool = False,
              x2: Optional[jax.Array] = None) -> jax.Array:
    """(n, k) squared euclidean distances; the MXU hot loop. `fast=True` runs the
    cross-term matmul at MXU bf16 precision — valid for ASSIGNMENT (ranking) use;
    anything feeding model attributes stays at parity precision. `x2`: the
    squared row norms (n,), where the caller has them."""
    x2 = jnp.sum(X * X, axis=1, keepdims=True) if x2 is None else x2[:, None]
    c2 = jnp.sum(centers * centers, axis=1)
    cross = jnp.matmul(X, centers.T, precision=FAST) if fast else pdot(X, centers.T)
    d2 = x2 - 2.0 * cross + c2
    return jnp.maximum(d2, 0.0)


def _normalize_rows(X: jax.Array) -> jax.Array:
    norms = jnp.linalg.norm(X, axis=1, keepdims=True)
    return X / jnp.maximum(norms, 1e-12)


# Interval ranking (lloyd_fit's assignment where `recheck` > 0; docs/design.md
# §6d). float32 on the MXU is a sum of bf16 products: a = hi + mid + lo, three
# bf16 numbers of eight significand bits each. Six passes (HIGHEST) sum hi.hi,
# hi.mid, mid.hi, mid.mid, hi.lo, lo.hi; three (HIGH) the first three. The
# parts are widest where every cut truncates: |hi| <= |a|, |mid| < 2^-7 |a|,
# |lo| < 2^-15 |a|, and what three passes drop of one product x_l c_l is then
# under (2^-14 + 2 * 2^-15) |x_l c_l| = 2^-13 |x_l c_l|, all of one sign where
# the products are; sum_l |x_l c_l| <= |x| |c| (Cauchy-Schwarz): EPS3. A cut
# that rounds to nearest leaves less (3 * 2^-16 if all do), so the bound holds
# however the hardware cuts. The chip cuts hi by truncation (tools/
# lloyd_assign_bench.py `passes`: 5.8 * 2^-16 on rows where rounding would
# read 2^-30) and reads 3.9 * 2^-16 on rows built to reach 8 * 2^-16.
_EPS3 = 2.0**-13
_U32 = 2.0**-24  # float32's unit roundoff


def _cross3(X: jax.Array, Ct: jax.Array) -> jax.Array:
    """The ranking cross term X.C^T at three bf16 passes."""
    return jnp.matmul(X, Ct, precision=jax.lax.Precision.HIGH)


def _rank3(X: jax.Array, x2: jax.Array, centers: jax.Array, c2: jax.Array):
    """Nearest centre of every row from three-pass distances, and which rows
    those decide. Returns (labels (n,) int32, decided (n,) bool).

    d3 = x2 - 2.cross3 + c2 stands for an interval [d3 - e, d3 + e] with

        e_ij = 2 (EPS3 + d u) |x_i| |c_j|  +  8 u (x2_i + c2_j),   u = 2^-24.

    The first term bounds, in the cross term doubled, what the three dropped
    products sum to (EPS3, above) and the float32 accumulation of the three
    kept ones: d.u is the textbook bound of a length-d float32 dot product
    summed in sequence, which a blocked accumulation such as the MXU's
    (chunks of the contraction summed apart, then chunks and passes added)
    stays under. The second covers the two roundings of `x2 - 2.cross + c2`,
    every intermediate of which is under 2 (x2 + c2) in magnitude, once for
    d3 and once for the interval's ends. So the exact sum D6 of the very
    products six passes keep lies inside the interval, and where exactly one
    centre's interval reaches below the least upper end (second-least lower
    end > least upper end) that centre is the nearest by D6. The six-pass
    program rounds D6 in float32 by the same d.u and can differ from it only
    on rows its own rounding decides: float32 ties, which have no six-pass
    answer to agree with. Nothing here is fitted to a table.

    `max(d2, 0)` of the six-pass expression is monotone; it can merge two
    centres at 0 (the lower index then wins), so a row is decided only if the
    second-least lower end also exceeds 0. NaNs compare false: undecided."""
    d = X.shape[1]
    cross = _cross3(X, centers.T)
    d3 = x2[:, None] - 2.0 * cross + c2
    e = (2.0 * (_EPS3 + d * _U32)) * jnp.sqrt(x2)[:, None] * jnp.sqrt(c2) + (
        8.0 * _U32
    ) * (x2[:, None] + c2)
    lo, hi = d3 - e, d3 + e
    inf = jnp.array(jnp.inf, lo.dtype)
    ids = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)

    def least(a, b):
        # (least lower end, its centre, second-least lower end, least upper end)
        a_lo, a_id, a_lo2, a_hi = a
        b_lo, b_id, b_lo2, b_hi = b
        a_first = (a_lo < b_lo) | ((a_lo == b_lo) & (a_id < b_id))
        return (
            jnp.minimum(a_lo, b_lo),
            jnp.where(a_first, a_id, b_id),
            jnp.minimum(jnp.maximum(a_lo, b_lo), jnp.minimum(a_lo2, b_lo2)),
            jnp.minimum(a_hi, b_hi),
        )

    # one variadic reduction, fused into the matmul as the argmin was: no
    # (n, k) array reaches HBM
    lo1, labels, lo2, hi1 = jax.lax.reduce(
        (lo, ids, jnp.full_like(lo, inf), hi),
        (inf, jnp.array(np.iinfo(np.int32).max, jnp.int32), inf, inf),
        least, (1,),
    )
    # `lo1 <= hi1` holds of every row (each lower end is under its upper end);
    # it is asked so that the least lower end has a reader. Left unread, the
    # chip's compiler stores that output in bfloat16, and in some programs
    # keeps the reduction's running least there between tiles: the other
    # three outputs are then ranked against a bfloat16 number, and one row
    # in eleven came out decided for the wrong centre (PERF.md §6, PR 31)
    return labels, (lo2 > jnp.maximum(hi1, 0.0)) & (lo1 <= hi1)


def _assign3(X: jax.Array, x2: jax.Array, w: jax.Array, centers: jax.Array,
             six_only: jax.Array, recheck: int):
    """One row shard's assignment: ranked at three passes, and up to `recheck`
    undecided rows decided by the six-pass expression (gathered, ranked against
    all centres, scattered back). More undecided rows than that and the shard
    runs the six-pass assignment whole: `recheck` sets the speed, never the
    result. A shard that has stopped ranking (`six_only`, (1,) bool: see
    `lloyd_fit`) skips the three passes and holds every row undecided.
    Returns (labels (n,), [[rows given the second look, 1 if six passes ran
    whole]] int32 (1, 2))."""
    n = X.shape[0]

    def ranked(_):
        return _rank3(X, x2, centers, jnp.sum(centers * centers, axis=1))

    def unranked(_):
        return jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool)

    labels, decided = jax.lax.cond(six_only[0], unranked, ranked, None)
    undecided = ~decided & (w > 0)  # padding has no label to get right
    n_undecided = jnp.sum(undecided, dtype=jnp.int32)
    overflow = n_undecided > recheck

    def six_pass(X, x2):
        return jnp.argmin(_sq_dists(X, centers, x2=x2), axis=1).astype(jnp.int32)

    def whole(_):
        return six_pass(X, x2)

    def second_look(_):
        rows = jnp.arange(n, dtype=jnp.int32)
        # undecided rows first, in order; then decided ones fill the block
        # (their six-pass label is the one they have), so no index repeats.
        # The row norms ride through the sort: gathered one by one they cost
        # twice the sort
        picked, x2_picked = jax.lax.sort(
            (jnp.where(undecided, rows, rows + n), x2), num_keys=1, is_stable=False
        )
        picked, x2_picked = picked[:recheck], x2_picked[:recheck]
        picked = jnp.where(picked >= n, picked - n, picked)
        exact = six_pass(X[picked], x2_picked)
        return labels.at[picked].set(exact, unique_indices=True)

    # the barrier keeps the compiler from moving the update's one-hot into
    # both branches, where it becomes an (n, k) array in HBM
    labels = jax.lax.optimization_barrier(
        jax.lax.cond(overflow, whole, second_look, None)
    )
    looked = jnp.where(overflow, 0, n_undecided)
    return labels, jnp.stack([looked, overflow.astype(jnp.int32)])[None, :]


@compiled_kernel("kmeans.lloyd_fit",
                 static_argnames=("max_iter", "cosine", "fast_math", "unit_weight",
                                  "recheck", "mesh"))
def lloyd_fit(
    X: jax.Array,
    w: jax.Array,
    init_centers: jax.Array,
    tol: float,
    max_iter: int,
    cosine: bool = False,
    fast_math: bool = False,
    unit_weight: bool = False,
    recheck: int = 0,
    mesh=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Lloyd iterations until max center movement² <= tol² or max_iter.

    Returns (centers, inertia, n_iter, second_look). Convergence on per-center
    movement matches Spark's KMeans semantics (the reference remaps tol=0 to a
    tiny epsilon, clustering.py:84-141 — callers do that remap).

    cosine=True runs spherical kmeans (Spark's distanceMeasure='cosine'): callers
    pass row-normalized X; centers are re-normalized every update and the cost is
    Σ w·(1 - x̂·ĉ).

    fast_math=True runs the ASSIGNMENT distance matmul at MXU bf16 (single-pass)
    precision — the centroid-update contraction and the final reported inertia stay
    at parity precision, so model attributes remain fp32-exact while the hot loop's
    dominant matmul runs at full MXU throughput (config key `fast_math`).

    unit_weight=True says that `w` holds zeros and ones only (the pad prefix
    mask of a fit without a weightCol). The update contraction onehotᵀ·X then
    runs at the precision pair (DEFAULT, parity): `one_hot · w` is exact in
    bf16, so the bf16 passes that multiply its mid and low parts multiply
    zeros, and the passes kept are the very products the full matmul sums
    (three of six under `highest`). With arbitrary weights the product is no
    bf16 number and the contraction stays (parity, parity), as the counts and
    every inertia do in both cases.

    recheck > 0 (euclidean, six-pass parity, no fast_math: `_lloyd` decides)
    ranks the assignment at three passes and gives up to `recheck` rows a row
    shard the six-pass second look (`_rank3`, `_assign3`); the labels are the
    six-pass assignment's but for float32 ties. `mesh` names the mesh X's
    rows are sharded over, so that each shard selects, gathers and branches
    by itself and no row crosses a shard. Cosine keeps six passes: its
    interval is another one (1 - x̂·ĉ, both of unit norm) and no cell runs it.

    The interval's width grows with |x||c| and the centres' margins do not, so
    whether three passes decide most rows is a property of the TABLE, which
    no shape shows: on rows far from the origin against their spread (an
    uncentred table) every row is undecided, and an iteration that ranks and
    then runs six passes whole pays for nine. The first iteration may (a
    random start puts every centre on a row). A shard that holds more than
    `recheck` undecided rows in any later one stops ranking for the rest of
    the fit: the loss is bounded by two rankings a fit, whatever the table.
    `second_look`, int32 (row shards, 2): rows given the second look and
    iterations that ran six passes whole (by overflow, or after one), summed
    over the fit (zeros without `recheck`)."""
    k = init_centers.shape[0]
    if cosine:
        init_centers = _normalize_rows(init_centers)
    # the compiler cannot see through `* w` that the one-hot operand is exact
    # in bf16; a pure one-hot it runs at three passes by itself
    update_precision = (
        (FAST, parity_precision()) if unit_weight else parity_precision()
    )

    def _dists(centers, fast=False):
        if cosine:
            if fast:
                return 1.0 - jnp.matmul(X, centers.T, precision=FAST)
            return 1.0 - pdot(X, centers.T)
        return _sq_dists(X, centers, fast=fast)

    assign3, shards = None, 1
    if recheck:
        x2 = jnp.sum(X * X, axis=1)  # once a fit: the table does not change
        assign3 = functools.partial(_assign3, recheck=recheck)
        if mesh is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import DATA_AXIS

            shards = mesh.shape[DATA_AXIS]
            assign3 = shard_map(
                assign3, mesh=mesh,
                in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(),
                          P(DATA_AXIS)),
                out_specs=(P(DATA_AXIS), P(DATA_AXIS, None)), check_vma=False,
            )

    def cond(state):
        _, _, it, shift2, _, _ = state
        return jnp.logical_and(it < max_iter, shift2 > tol * tol)

    def body(state):
        centers, _, it, _, looks, six_only = state
        if assign3 is not None:
            assign, look = assign3(X, x2, w, centers, six_only)
            six_only = six_only | ((look[:, 1] > 0) & (it > 0))
            # saturating: a count that stops is better than one that wraps
            looks = looks + jnp.minimum(look, np.iinfo(np.int32).max - looks)
            # the loop's own inertia is overwritten below; its sum stays, and
            # with it the shape of the loop's all-reduce on a mesh
            min_d2 = jnp.zeros((), X.dtype)
        else:
            d2 = _dists(centers, fast=fast_math)
            assign = jnp.argmin(d2, axis=1)
            min_d2 = jnp.min(d2, axis=1)
        onehot = jax.nn.one_hot(assign, k, dtype=X.dtype) * w[:, None]
        counts = jnp.sum(onehot, axis=0)
        sums = jnp.matmul(onehot.T, X, precision=update_precision)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers
        )
        if cosine:
            new_centers = _normalize_rows(new_centers)
        inertia = jnp.sum(w * min_d2)
        shift2 = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
        return new_centers, inertia, it + 1, shift2, looks, six_only

    init_state = (
        init_centers, jnp.array(0.0, X.dtype), 0, jnp.array(jnp.inf, X.dtype),
        jnp.zeros((shards, 2), jnp.int32), jnp.zeros((shards,), bool),
    )
    centers, inertia, n_iter, _, looks, _ = jax.lax.while_loop(cond, body, init_state)
    # inertia reported against the final centers
    inertia = jnp.sum(w * jnp.min(_dists(centers), axis=1))
    return centers, inertia, n_iter, looks


@compiled_kernel("kmeans.predict", static_argnames=("cosine",))
def _kmeans_predict_xla(
    X: jax.Array, centers: jax.Array, cosine: bool = False
) -> jax.Array:
    if cosine:
        return jnp.argmax(pdot(_normalize_rows(X), _normalize_rows(centers).T), axis=1)
    return jnp.argmin(_sq_dists(X, centers), axis=1)


def kmeans_predict(
    X: jax.Array, centers: jax.Array, cosine: bool = False
) -> jax.Array:
    """Nearest-center assignment. Host wrapper (the PR-5 contract: strategy
    resolves OUTSIDE any trace): euclidean assignment routes to the fused
    pallas distance+argmin scan (ops/pallas_select.py — X streams through
    once, no (n, k) distance matrix in HBM, bit-identical argmin) when
    `knn.selection` is `pallas_fused`, or under `auto` on TPU at k >= 128
    (below that the lane-padded MXU tiles erase the fusion win — the
    documented ops/pallas_kmeans.py small-k region). Cosine keeps the XLA
    kernel: its ranking is a normalized argMAX, not this kernel's reduction.
    `kmeans.assign_path{path=}` proves which path ran."""
    from ..ops import pallas_select as _ps
    from . import selection as _sel

    tracing = _sel.is_tracing(X, centers)
    if (
        not cosine
        and not tracing
        and _ps.use_fused_assign(centers.shape[0], centers.shape[1])
    ):
        from .. import observability as _obs

        _obs.counter_inc("kmeans.assign_path", 1, path="pallas_fused")
        return _ps.fused_assign(X, centers)
    if not tracing:
        from .. import observability as _obs

        _obs.counter_inc("kmeans.assign_path", 1, path="xla")
    return _kmeans_predict_xla(X, centers, cosine)


# Per-centre counts of an assignment, reduced on the device so that only the
# (n_centers,) result crosses to the host: fetching the n labels (and the n
# weights) for a host np.bincount was a quarter of an in-core fit.
def _count_rows(
    labels: jax.Array, w: jax.Array, n_centers: int, blocks: int
) -> jax.Array:
    """Reduce (n,) labels to per-centre counts, traced inside a kernel.

    `blocks` 0: the rows with a non-zero weight are counted in int32,
    (n_centers,): exact at any row count, where a float32 sum stops at 2**24.
    `w` is the (n,) row weights, or a scalar row count m (rows at positions
    >= m are padding). Otherwise the float32 weights are summed within each of
    `blocks` row blocks, (blocks, n_centers), for the caller to add in float64."""
    ids = jnp.arange(n_centers, dtype=labels.dtype)
    if blocks:
        hit = labels.reshape(blocks, -1, 1) == ids
        return jnp.sum(jnp.where(hit, w.reshape(blocks, -1, 1), 0), axis=1)
    real = jnp.arange(labels.shape[0]) < w if w.ndim == 0 else w > 0
    hit = (labels[:, None] == ids) & real[:, None]
    return jnp.sum(hit, axis=0, dtype=jnp.int32)


@compiled_kernel("kmeans.assign_counts", static_argnames=("cosine", "blocks"))
def _assign_counts_xla(
    X: jax.Array, centers: jax.Array, w: jax.Array, cosine: bool = False,
    blocks: int = 0,
) -> jax.Array:
    """`_kmeans_predict_xla`'s labels (the same expression, so the same
    labels) reduced to per-centre counts in the program that computes them."""
    labels = _kmeans_predict_xla.__wrapped__(X, centers, cosine)
    return _count_rows(labels, w, centers.shape[0], blocks)


@compiled_kernel("kmeans.label_counts", static_argnames=("n_centers", "blocks"))
def _label_counts(
    labels: jax.Array, w: jax.Array, n_centers: int, blocks: int = 0
) -> jax.Array:
    """The reduction alone, for labels another kernel wrote (the fused pallas
    assignment, which kmeans_predict routes to at 128 centres and more on TPU)."""
    return _count_rows(labels, w, n_centers, blocks)


def assign_counts(
    X: jax.Array, centers: jax.Array, w: jax.Array, cosine: bool = False,
    exact: bool = False,
) -> np.ndarray:
    """Per-centre sum of `w` over the rows nearest each centre, on the host:
    what `np.bincount(kmeans_predict(X, centers), weights=w)` gives, without
    fetching anything of n rows. `w`: (n,) row weights, or a scalar row count
    m (rows at positions >= m are padding; implies `exact`). `exact` (0/1
    weights) returns int64 counts, bit-equal to the host's; otherwise float64
    sums within 1e-6 of the float64 bincount. The assignment takes
    kmeans_predict's route, so the labels are the ones it would return.
    `kmeans.count_path{path=device|host}` says where the rows were counted."""
    from ..parallel.partitioner import mesh_of, replicate_rows
    from . import pallas_select as _ps

    n, d = X.shape
    n_centers = int(centers.shape[0])
    w = jnp.asarray(w)
    exact = exact or w.ndim == 0
    if n_centers > COUNT_DEVICE_MAX_CENTERS:
        counter_inc("kmeans.count_path", 1, path="host")
        labels = np.asarray(kmeans_predict(X, centers, cosine))
        fetched = labels.nbytes
        if w.ndim == 0:
            out = np.bincount(labels[: int(w)], minlength=n_centers)
        else:
            wh = np.asarray(w)
            fetched += wh.nbytes
            out = np.bincount(labels, weights=wh, minlength=n_centers)
        counter_inc("d2h.bytes", int(fetched), site="fit")
        return out.astype(np.int64) if exact else out
    counter_inc("kmeans.count_path", 1, path="device")
    blocks = 0
    if not exact:
        blocks = n // X.sharding.shard_shape(X.shape)[0]  # row shards
        blocks *= math.gcd(n // blocks, COUNT_BLOCK_SPLIT)
    if not cosine and _ps.use_fused_assign(n_centers, d):
        out = _label_counts(kmeans_predict(X, centers), w, n_centers, blocks)
    else:
        counter_inc("kmeans.assign_path", 1, path="xla")
        out = _assign_counts_xla(X, centers, w, cosine, blocks)
    if blocks and mesh_of(X) is not None:
        # a count is reduced over the row shards already; the block sums are not
        out = replicate_rows(out, mesh_of(X))
    out = np.asarray(out)
    counter_inc("d2h.bytes", int(out.nbytes), site="fit")
    return out.astype(np.int64) if exact else out.sum(axis=0, dtype=np.float64)


@compiled_kernel("kmeans.inertia")
def kmeans_inertia(X: jax.Array, w: jax.Array, centers: jax.Array) -> jax.Array:
    return jnp.sum(w * jnp.min(_sq_dists(X, centers), axis=1))


def _random_real_rows(
    X: jax.Array, w: jax.Array, n_pick: int, key: jax.Array
) -> jax.Array:
    """Pick n_pick distinct real (w>0) rows via Gumbel-top-k on the mask."""
    g = jax.random.gumbel(key, (X.shape[0],), dtype=X.dtype)
    score = jnp.where(w > 0, g, -jnp.inf)
    _, idx = top_k_max(score, n_pick)  # exact: seeded init determinism
    return X[idx]


@functools.partial(jax.jit, static_argnames=("n_pick",))
def _sample_by_d2(
    X: jax.Array, w: jax.Array, centers: jax.Array, n_pick: int, key: jax.Array
) -> jax.Array:
    """Sample n_pick rows without replacement with probability ∝ d²(x, centers):
    Gumbel-top-k over log d² (k-means|| oversampling with static shapes)."""
    d2 = jnp.min(_sq_dists(X, centers), axis=1)
    logits = jnp.where(w > 0, jnp.log(d2 + 1e-30), -jnp.inf)
    g = jax.random.gumbel(key, logits.shape, dtype=X.dtype)
    _, idx = top_k_max(logits + g, n_pick)  # exact: seeded sampling
    return X[idx]


@functools.partial(jax.jit, static_argnames=("l", "steps"))
def _oversample_rounds(
    X: jax.Array, w: jax.Array, first: jax.Array, key: jax.Array, l: int, steps: int
) -> jax.Array:
    """All k-means|| oversampling rounds in ONE dispatch: the former host loop
    synced candidates to host every round (2 host round trips per step) and
    recomputed distances against the WHOLE candidate set each time; here the
    min-distance vector updates incrementally against only the new candidates
    (O(steps·l·n·d) instead of O(steps²·l·n·d)). Returns (1 + steps·l, d)
    candidates; already-chosen rows get d²=0 so they are ~never re-drawn, same
    as the host version's behavior."""
    n_c = 1 + steps * l
    buf = jnp.zeros((n_c, X.shape[1]), X.dtype).at[0].set(first)
    d2 = jnp.sum((X - first[None, :]) ** 2, axis=1)
    for r in range(steps):
        key, sub = jax.random.split(key)
        logits = jnp.where(w > 0, jnp.log(d2 + 1e-30), -jnp.inf)
        g = jax.random.gumbel(sub, logits.shape, dtype=X.dtype)
        _, idx = top_k_max(logits + g, l)  # exact: seeded sampling
        newc = X[idx]
        buf = jax.lax.dynamic_update_slice(buf, newc, (1 + r * l, 0))
        d2 = jnp.minimum(d2, jnp.min(_sq_dists(X, newc), axis=1))
    return buf


def _cand_sq_dists(candidates: np.ndarray, centers: np.ndarray) -> np.ndarray:
    """(n_cand, k) squared distances via the matmul expansion — never materializes
    the (n_cand, k, d) broadcast (IVF builds call this with k in the thousands)."""
    c2 = np.sum(centers * centers, axis=1)
    x2 = np.sum(candidates * candidates, axis=1)
    return np.maximum(
        x2[:, None] - 2.0 * (candidates @ centers.T) + c2[None, :], 0.0
    )


def _weighted_kmeans_pp_once(
    candidates: np.ndarray, weights: np.ndarray, k: int, rng: np.random.Generator
):
    n = candidates.shape[0]
    centers = np.empty((k, candidates.shape[1]), dtype=candidates.dtype)
    p = weights / weights.sum()
    centers[0] = candidates[rng.choice(n, p=p)]
    d2 = np.sum((candidates - centers[0]) ** 2, axis=1)
    # greedy k-means++ (sklearn-style): draw several d²-weighted trials per step
    # and keep the one that minimizes the resulting potential — a single
    # non-greedy draw can seed two centers in one heavy cluster and the local
    # refinement below cannot always escape that basin
    n_local_trials = 2 + int(np.log(k))
    for i in range(1, k):
        probs = weights * d2
        s = probs.sum()
        if s <= 0:
            centers[i] = candidates[rng.integers(n)]
            d2 = np.minimum(
                d2, np.sum((candidates - centers[i]) ** 2, axis=1)
            )
            continue
        trial_ids = rng.choice(n, size=n_local_trials, p=probs / s)
        trial_d2 = _cand_sq_dists(candidates, candidates[trial_ids])  # (n, t)
        new_d2 = np.minimum(d2[:, None], trial_d2)
        potentials = (weights[:, None] * new_d2).sum(axis=0)
        best_t = int(np.argmin(potentials))
        centers[i] = candidates[trial_ids[best_t]]
        d2 = new_d2[:, best_t]

    # local weighted Lloyd refinement over the (tiny) candidate set — Spark's
    # LocalKMeans runs the same after its ++ seeding; empty centers reseed at the
    # worst-covered candidate
    for _ in range(10):
        d2_all = _cand_sq_dists(candidates, centers)  # (n_cand, k)
        a = np.argmin(d2_all, axis=1)
        sums = np.zeros_like(centers)
        np.add.at(sums, a, candidates * weights[:, None])
        cnts = np.zeros(k, dtype=weights.dtype)
        np.add.at(cnts, a, weights)
        for j in np.nonzero(cnts <= 0)[0]:
            far = np.argmax(np.min(d2_all, axis=1))
            centers[j] = candidates[far]
            d2_all[far] = 0.0
        ok = cnts > 0
        centers[ok] = sums[ok] / cnts[ok, None]
    # score the FINAL centers (the in-loop d2_all predates the last update)
    cost = float(
        np.sum(weights * np.min(_cand_sq_dists(candidates, centers), axis=1))
    )
    return centers, cost


def _weighted_kmeans_pp(
    candidates: np.ndarray,
    weights: np.ndarray,
    k: int,
    rng: np.random.Generator,
    restarts: int = 8,
) -> np.ndarray:
    """Host-side weighted k-means++ over the small candidate set (the final reduce
    of scalable k-means++). Even the greedy ++ draw can land a poor basin the
    refinement cannot escape; restarts scored by weighted candidate inertia make
    that mode vanishingly unlikely at negligible cost (the candidate set is
    ~(1 + steps·2k) rows). Large k (IVF coarse quantizers call this with
    k=nlist in the thousands, candidates ~4k) caps restarts at 2: the greedy
    trials already remove most of the need for restarts, and the per-restart
    cost there is O(k²·t·d) host work."""
    if k > 64:
        restarts = min(restarts, 2)
    best = None
    best_cost = np.inf
    for _ in range(max(restarts, 1)):
        centers, cost = _weighted_kmeans_pp_once(candidates, weights, k, rng)
        # `best is None` guard: NaN costs (NaN features in the candidate set)
        # compare false against everything and must not leave best unset
        if best is None or cost < best_cost:
            best, best_cost = centers, cost
    return best


def kmeans_init(
    X: jax.Array,
    w: jax.Array,
    k: int,
    init: str,
    init_steps: int,
    seed: int,
    unit_weight: bool = False,
) -> np.ndarray:
    """Compute initial centers (host-side result). `unit_weight`: `w` holds
    only 0 and 1 (FitInputs.unit_weight), so the candidates' weights are counts.

    init == "random": k distinct real rows.
    init == "k-means||" (or "scalable-k-means++"): Gumbel-top-k oversampling rounds,
    then weighted k-means++ on the ~(1 + steps·2k) candidates.

    The caller holds the span `kmeans.init` (kmeans_fit, ops/streaming.py);
    its children here: `kmeans.init.random`, or the three phases of the
    k-means|| start, each ending in a read back to the host. The rows of
    centre shape that come back (the k picked rows, or the candidates) are
    counted as `d2h.bytes{site=fit.centers}`."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    if init == "random":
        with span("kmeans.init.random"):
            centers = np.asarray(_random_real_rows(X, w, k, key))
            counter_inc("d2h.bytes", int(centers.nbytes), site="fit.centers")
            return centers

    rng = np.random.default_rng(seed & 0x7FFFFFFF)
    with span("kmeans.init.oversample"):
        n_real = int(jnp.sum(w > 0))
        l = max(2, min(2 * k, n_real))  # never oversample past the real rows (padding)
        key, sub = jax.random.split(key)
        first = _random_real_rows(X, w, 1, sub)[0]
        key, sub = jax.random.split(key)
        candidates = np.asarray(
            _oversample_rounds(X, w, first, sub, l, max(init_steps, 1))
        )
        counter_inc("d2h.bytes", int(candidates.nbytes), site="fit.centers")
    with span("kmeans.init.weigh"):
        # weight candidates by how many points they attract (one cheap pass)
        counter_inc("h2d.bytes", int(candidates.nbytes), site="fit.centers")
        weights = assign_counts(
            X, jnp.asarray(candidates), w, exact=unit_weight
        ).astype(candidates.dtype)
        weights = np.maximum(weights, 1e-12)
    with span("kmeans.init.pp"):
        return _weighted_kmeans_pp(candidates, weights, k, rng)


def kmeans_fit(
    X: jax.Array,
    w: jax.Array,
    k: int,
    max_iter: int,
    tol: float,
    init: str,
    init_steps: int,
    seed: int,
    metric: str = "euclidean",
    unit_weight: bool = False,
) -> Dict[str, object]:
    cosine = metric == "cosine"
    if cosine:
        # Spark raises on zero-norm vectors with cosine distance; match it rather
        # than silently assigning an arbitrary direction
        min_norm = float(jnp.min(jnp.where(w > 0, jnp.linalg.norm(X, axis=1), jnp.inf)))
        if min_norm <= 0.0:
            raise ValueError(
                "Cosine distance is not defined for zero-length vectors; the input "
                "contains an all-zero feature row."
            )
        X = _normalize_rows(X)  # spherical kmeans operates on the unit sphere
    with span("kmeans.init"):
        init_centers = kmeans_init(X, w, k, init, init_steps, seed, unit_weight)
        counter_inc("h2d.bytes", int(init_centers.nbytes), site="fit.centers")
        init_centers = jnp.asarray(init_centers)
    with span("kmeans.lloyd", {"waits": "device"}):
        return _lloyd(X, w, init_centers, k, max_iter, tol, cosine, unit_weight)


def _second_look_rows(X: jax.Array, k: int, cosine: bool, fast_math: bool):
    """(`recheck`, `mesh`) for `lloyd_fit`: how many rows a row shard may take
    the six-pass second look, 0 where the assignment stays as it is: cosine
    (another interval), `fast_math` (one pass, by choice), a stated precision
    other than six passes, and the shapes at which three passes do not pay.
    Decided from what the fit is, as the update's precision pair is from
    `unit_weight`: the three passes saved are 2.k.d FLOP a row each, while the
    selection, the gather and the wider reduction cost what they cost at any
    k.d, and under 128 centres six passes hide behind the one read of X (the
    sweep of PERF.md §6 PR 31: slower under k.d of 384,000, faster from
    524,288). On a mesh the rows have to be split evenly over the data axis
    and nothing else, so that a shard holds whole rows."""
    from ..parallel.mesh import DATA_AXIS
    from ..parallel.partitioner import mesh_of

    if cosine or fast_math or parity_precision() != jax.lax.Precision.HIGHEST:
        return 0, None
    if k < LLOYD_FUSED_MIN_K or k * X.shape[1] < LLOYD_ASSIGN3_MIN_WORK:
        return 0, None
    mesh = mesh_of(X)
    shard = X.sharding.shard_shape(X.shape)
    if mesh is not None and mesh.devices.size > 1:
        if (shard[0] * mesh.shape.get(DATA_AXIS, 0), shard[1]) != X.shape:
            return 0, None
    else:
        mesh = None
    recheck = shard[0] // LLOYD_RECHECK_SHARE
    return recheck, mesh if recheck else None


def _lloyd(
    X: jax.Array,
    w: jax.Array,
    init_centers: jax.Array,
    k: int,
    max_iter: int,
    tol: float,
    cosine: bool,
    unit_weight: bool,
) -> Dict[str, object]:
    """Route to the fused pallas or the XLA Lloyd program, run it and fetch
    centres, inertia and `n_iter` (the span `kmeans.lloyd` of kmeans_fit)."""
    from .. import config as _config

    # Fused pallas Lloyd routing (SRML_TPU_PALLAS_KMEANS). Steady-state TPU
    # measurement at the bench shape (12M x 128, k=20, v5e) puts the XLA path
    # at 18.7 ms/iter (~92% of the two-X-reads HBM roofline) vs 26.3/37.5
    # ms/iter for the WEIGHTED fused kernel at 1-pass/6-pass precision — at
    # small k both fused matmuls pad k to the 128-lane MXU width and the
    # per-block argmin/one-hot VPU work dominates, so streaming X once does
    # not pay. Values:
    #   "auto" (default) self-resolves the documented small-k loss region:
    #          the fused kernel engages ONLY on TPU at k >= 128 (the lane
    #          padding vanishes and XLA's (n, k) intermediates approach the
    #          size of X); masked form when the weights are the unit
    #          prefix-mask, weighted otherwise. Off-TPU / small k: XLA.
    #   "1"    weighted kernel (any w), unconditional
    #   "mask" weight-stream-free kernel — requires unit_weight (the pad_rows
    #          prefix-mask contract); the (blk,1)-operand elimination measured
    #          3x on the Gram kernel (ops/pallas_xtwx.py); falls back to "1"
    #          when sample weights are present
    #   "0"/"" XLA always
    # `kmeans.lloyd_path{path=}` counts which path actually ran, and
    # `kmeans.lloyd_gate{fused=0|1,reason=}` which test decided it:
    # cosine | backend | small_k | vmem under "auto", else forced.
    from ..autotune.defaults import LLOYD_FUSED_MIN_K as _FUSED_MIN_K

    _pallas_env = __import__("os").environ.get("SRML_TPU_PALLAS_KMEANS", "auto")
    if _pallas_env == "auto":
        # upper bound on the auto gate: the kernel module's own VMEM
        # predicate (lloyd_fits_vmem — C+sums residents plus the (blk, k)
        # one-hot working set at the precision's split count) decides
        # placeability; an unplaceable (k, d) stays on XLA rather than
        # handing Mosaic a compile it cannot place. Forced "1"/"mask" stay
        # unconditional (explicit opt-in, as before).
        from .pallas_kmeans import _N_SPLIT, lloyd_fits_vmem

        _n_split = (
            1 if bool(_config.get("fast_math"))
            else _N_SPLIT[parity_precision()]
        )
        # the k-threshold of the auto gate is a tuning-table knob
        # (`lloyd.fused_min_k`, docs/design.md §6i): a platform where the
        # fused win boundary sits elsewhere ships a table entry instead of a
        # code change; the default stays the measured v5e boundary. Off-TPU
        # the gate is closed anyway, so the table is never consulted there.
        _min_k = _FUSED_MIN_K
        if jax.default_backend() == "tpu":
            from .. import autotune as _autotune

            _tuned_min_k = _autotune.lookup(
                "lloyd.fused_min_k", d=int(X.shape[1])
            )
            if _tuned_min_k is not None:
                _min_k = int(_tuned_min_k)
        # the tests in the order they are asked; the first that fails names
        # the reason, and a fused fit has passed the last (`reason=vmem`)
        if cosine:
            _reason = "cosine"
        elif jax.default_backend() != "tpu":
            _reason = "backend"
        elif k < _min_k:
            _reason = "small_k"
        else:
            _reason = "vmem"
        use_fused = _reason == "vmem" and lloyd_fits_vmem(
            k, int(X.shape[1]), _n_split
        )
        _pallas_env = "mask" if unit_weight else "1"
    else:
        _forced_on = _pallas_env in ("1", "mask")
        use_fused = _forced_on and not cosine
        _reason = "cosine" if _forced_on and cosine else "forced"
    from .. import observability as _obs

    # nothing else says that a wide fit left the fused kernel because its
    # centres do not fit VMEM
    counter_inc("kmeans.lloyd_gate", 1, fused=int(use_fused), reason=_reason)
    if use_fused:
        from ..parallel.partitioner import mesh_of
        from .pallas_kmeans import lloyd_fit_pallas

        mesh = mesh_of(X)
        prec = (
            jax.lax.Precision.DEFAULT
            if bool(_config.get("fast_math"))
            else parity_precision()
        )
        unit_mask = _pallas_env == "mask" and unit_weight
        _obs.counter_inc(
            "kmeans.lloyd_path", 1,
            path="pallas_masked" if unit_mask else "pallas_weighted",
        )
        centers, inertia, n_iter = lloyd_fit_pallas(
            X, w, init_centers, float(tol), int(max_iter), mesh=mesh,
            interpret=(jax.default_backend() != "tpu"),
            precision=prec,
            unit_mask=unit_mask,
        )
    else:
        _obs.counter_inc("kmeans.lloyd_path", 1, path="xla")
        fast_math = bool(_config.get("fast_math"))
        six_pass = parity_precision() == jax.lax.Precision.HIGHEST
        # bf16 passes of the update matmul at the stated float32 precision:
        # three where lloyd_fit may take the one-hot operand as exact
        exact_onehot = unit_weight and six_pass
        counter_inc("kmeans.lloyd_update", 1, passes=3 if exact_onehot else 6)
        # and of the assignment: three, with a six-pass second look, where
        # `_second_look_rows` says so
        recheck, mesh = _second_look_rows(X, k, cosine, fast_math)
        counter_inc(
            "kmeans.lloyd_assign", 1,
            passes=1 if fast_math else 3 if recheck or not six_pass else 6,
        )
        centers, inertia, n_iter, looks = lloyd_fit(
            X, w, init_centers, float(tol), int(max_iter), cosine=cosine,
            fast_math=fast_math, unit_weight=unit_weight, recheck=recheck,
            mesh=mesh,
        )
        if recheck:
            if not looks.is_fully_addressable:
                from ..parallel.partitioner import replicate_rows

                looks = replicate_rows(looks, mesh)
            rows, whole = np.asarray(looks).sum(axis=0)
            counter_inc("kmeans.lloyd_recheck_rows", int(rows))
            counter_inc("kmeans.lloyd_recheck_overflow", int(whole))
    centers = np.asarray(centers)
    counter_inc("d2h.bytes", int(centers.nbytes), site="fit.centers")
    return {
        "cluster_centers": centers,
        "inertia": float(inertia),
        "n_iter": int(n_iter),
    }
