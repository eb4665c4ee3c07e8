#
# Selection plane — THE top-k module for the whole search stack.
#
# Every top-k in the kNN/ANN family (exact_knn_*, ivfflat/ivfpq/cagra search,
# the streamed ANN probe scans, the pairwise item-tile merges, and the kmeans/
# tree score picks) routes through here; the analyzer (fence/topk-off-plane) bans direct
# jax.lax.top_k / jax.lax.approx_max_k anywhere else under ops/. Three
# strategies behind one API, picked by `knn.selection` (config.py):
#
#   exact_full   one full-width lax.top_k over the candidate axis (the
#                pre-selection-plane behavior, bit-for-bit).
#   exact_tiled  two-stage: reshape the candidate axis into tiles, a small
#                per-tile top-k, then a second top-k over the (tiles*k) pool.
#                EXACT — bit-for-bit equal to exact_full including tie order
#                (ties resolve lowest-index-first in both: within a tile the
#                per-tile top-k is index-stable, and pool positions are
#                tile-major so cross-tile ties also resolve by global index).
#                On TPU the small fixed-width per-tile selects vectorize on
#                the VPU where the full-width top_k lowers to sort passes; on
#                CPU the XLA TopK custom call is per-call-overhead-bound, so
#                the auto tile keeps the tile count small (see _auto_tile).
#   approx       jax.lax.approx_max_k (the TPU's native approximate-selection
#                unit, PartialReduce) at `knn.recall_target`. Callers that owe
#                the user exact distances (exact_knn_single and everything
#                stacked on it) follow with a parity-precision re-rank of the
#                winner pool (ops/knn.py::parity_rerank_sq) so returned
#                distances stay exact; recall of the id set is >= the target.
#   pallas_fused the fused Pallas distance+select scan (ops/pallas_select.py,
#                docs/design.md §5c): the (block, n_items) distance tile and
#                the running top-k/argmin/count live in VMEM registers, so the
#                distance matrix is NEVER materialized in HBM — X streams
#                through once per scan. Only FUSABLE call sites (the host
#                wrappers that hold Q and X, not a materialized d2) can run
#                it: `resolve(fusable=True)` marks them, and a d2-level
#                select asked for `pallas_fused` degrades to exact_full.
#                Exact-f32 mode is bit-identical to exact_full (tie order
#                included); `knn.pallas_precision` bf16/int8 modes select an
#                approximate candidate pool and the parity_rerank_sq
#                invariant restores exact returned distances.
#
# MERGES STAY EXACT: a running top-k merge (pairwise tile sweeps, the ring
# hop merge, the all-gather candidate merge) must never lose carried
# candidates, so merge pools always select with exact_full — the configured
# strategy applies to the per-tile/per-shard candidate selection feeding the
# pool, where the width (and the win) is.
#
# Invalid-entry convention: masked/padded candidates are set to INVALID_D2, a
# LARGE FINITE sentinel (f32max/2), never jnp.inf — inf entries surviving into
# a downstream recomputation (inf - inf) are NaN factories, and NaN never
# sorts. select_topk additionally clamps its input at INVALID_D2 so even a
# caller-provided inf (e.g. an overflowed distance) keeps exact_full and
# exact_tiled bit-identical. The -1-id / inf-distance OUTPUT contract of the
# search entry points is unchanged: they restore inf at the boundary from the
# id mask, not from the selection values.
#

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Large-finite invalid sentinel: big enough that no real squared distance on
# f32 inputs reaches it before the clamp, small enough that sums/differences
# of two sentinels stay finite (f32max/2 + f32max/2 == f32max, no overflow).
INVALID_D2 = np.float32(np.finfo(np.float32).max / 2)

STRATEGIES = ("auto", "exact_full", "exact_tiled", "approx", "pallas_fused")

# distance-accumulation modes of the fused pallas scan (knn.pallas_precision):
# float32 is bit-exact; bfloat16/int8 pair with the parity_rerank_sq re-rank
FUSED_PRECISIONS = ("float32", "bfloat16", "int8")


def mask_invalid(d2: jax.Array, valid: jax.Array) -> jax.Array:
    """Mask invalid candidate positions with the large-finite sentinel (NOT
    inf — see module header). `valid` broadcasts against d2."""
    return jnp.where(valid, d2, INVALID_D2)


def _backend() -> str:
    return jax.default_backend()


def _auto_tile(n: int, backend: str) -> int:
    """Platform tile DEFAULT when no tuning-table entry covers the bucket:
    on TPU small fixed tiles vectorize the per-tile select on the VPU; on CPU
    each TopK custom call pays per-call overhead, so keep the tile count
    small. The values live in autotune/defaults.py (the knob-registry
    defaults module); measured per-bucket choices live in the tuning table,
    whose entries carry their own `provenance` field (docs/design.md §6i)."""
    from ..autotune.defaults import default_select_tile

    return default_select_tile(n, backend)


def _fused_auto(n: int) -> bool:
    """Should `auto` hand a FUSABLE width-n scan to the fused pallas kernel?
    TPU only (off-TPU the kernel runs the Pallas interpreter — a correctness
    tool, not a fast path), and only once the scanned item width clears the
    `pallas.min_items` threshold (tuning table, else `knn.pallas_min_items`;
    small scans don't pay back the kernel's in-register selection work)."""
    if _backend() != "tpu":
        return False
    from .. import autotune as _autotune
    from .. import config as _config

    min_items = _autotune.lookup("pallas.min_items")
    if min_items is None:
        min_items = int(_config.get("knn.pallas_min_items"))
    return n >= int(min_items)


def resolve_fused_precision(precision: Optional[str] = None) -> str:
    """Resolve the fused scan's distance-accumulation mode
    (`knn.pallas_precision` unless the caller pinned one). Host-side — like
    `resolve`, so a config change can never be baked stale into a cached
    trace. Resolution order: caller-pinned > config set()/env > tuning table
    > default (the table may only steer this knob because every consuming
    site pairs non-f32 modes with the parity_rerank_sq exactness invariant —
    returned distances stay exact-f32 either way). Non-float32 modes REQUIRE
    the caller to follow with that re-rank."""
    from .. import autotune as _autotune
    from .. import config as _config

    if precision is None:
        precision = _autotune.lookup("pallas.precision")
    if precision is None:
        precision = str(_config.get("knn.pallas_precision"))
    if precision not in FUSED_PRECISIONS:
        raise ValueError(
            f"knn.pallas_precision must be one of {FUSED_PRECISIONS}, "
            f"got '{precision}'"
        )
    return precision


def resolve(
    n: int,
    k: int,
    strategy: Optional[str] = None,
    tile: Optional[int] = None,
    recall_target: Optional[float] = None,
    fusable: bool = False,
) -> Tuple[str, int, float]:
    """Resolve (strategy, tile, recall_target) for a width-n, top-k select.

    Reads config only for the pieces the caller left None, so jitted kernels
    that receive the resolved triple as static arguments never consult config
    at trace time (a stale traced strategy could otherwise outlive a config
    change). Degradations keep small selects on the fused exact path:
    tiled/approx fall back to exact_full when the width is a single tile or
    within 4x of k (the pool would be the whole input).

    `fusable=True` marks call sites that hold Q and X (not a materialized d2
    matrix) and can therefore run the fused pallas distance+select scan
    (ops/pallas_select.py): under `auto` on TPU such a site picks
    `pallas_fused` once n >= knn.pallas_min_items, for k <= FUSED_TOPK_MAX_K
    (the largest k Mosaic places — autotune/defaults.py). A NON-fusable site asked
    for `pallas_fused` (explicitly or via a threaded resolved value) degrades
    to exact_full — there is nothing left to fuse once d2 exists, and
    exact_full preserves the fused scan's bit-exact contract."""
    from .. import config as _config

    if strategy is None:
        strategy = str(_config.get("knn.selection"))
    if strategy not in STRATEGIES:
        raise ValueError(
            f"knn.selection must be one of {STRATEGIES}, got '{strategy}'"
        )
    if strategy == "auto":
        from ..autotune.defaults import FUSED_TOPK_MAX_K

        # the k bound comes BEFORE the width probe (a table consult): past it
        # Mosaic refuses the kernel's unrolled extraction (defaults module)
        if fusable and k <= FUSED_TOPK_MAX_K and _fused_auto(n):
            strategy = "pallas_fused"
        else:
            # tuning table first (docs/design.md §6i): a measured per-bucket
            # strategy beats the platform heuristic. A REAL set()/env pin on
            # knn.selection never reaches here (strategy wasn't "auto"), and
            # lookup() itself treats a pin to the literal sentinel "auto" as
            # "choose for me" — the table slots between env and the default
            from .. import autotune as _autotune

            tuned = _autotune.lookup("selection.strategy", n=n, k=k)
            if tuned is not None and (fusable or tuned != "pallas_fused"):
                strategy = tuned
            else:
                strategy = "approx" if _backend() == "tpu" else "exact_tiled"
    if strategy == "pallas_fused" and not fusable:
        strategy = "exact_full"
    # degradations: k-of-n selects with no real pool reduction run fused
    # exact. The tile term applies ONLY to exact_tiled — tying approx to the
    # tile width would silently disable the approx path (and its parity
    # re-rank) everywhere the platform auto-tile exceeds the data, leaving it
    # untested off-TPU and surprising users who asked for it explicitly.
    if k >= n or n <= 4 * k:
        strategy = "exact_full"
    if strategy == "exact_tiled":
        if tile is None:
            tile = int(_config.get("knn.select_tile") or 0)
        if tile <= 0:
            # tuning table between config and the platform heuristic: a
            # nonzero knn.select_tile (set()/env) took the branch above
            from .. import autotune as _autotune

            tuned = _autotune.lookup("selection.tile", n=n, k=k)
            tile = int(tuned) if tuned is not None else _auto_tile(n, _backend())
        if n <= tile:
            strategy = "exact_full"
    # knn.recall_target is read/validated ONLY when approx actually runs:
    # exact modes documentedly ignore it (a bad value must not crash exact
    # searches), and the forced-exact calls inside jitted kernels
    # (merge_topk, loop-carried selects) must not consult config at trace
    # time at all.
    if strategy == "approx":
        if recall_target is None:
            recall_target = float(_config.get("knn.recall_target"))
        if not 0.0 < recall_target <= 1.0:
            raise ValueError(
                f"knn.recall_target must be in (0, 1], got {recall_target}"
            )
    if tile is None:
        tile = 0  # unused by exact_full/approx; keep the static arg stable
    if recall_target is None:
        recall_target = 1.0  # unused outside approx
    return strategy, int(tile), float(recall_target)


def _tiled_topk_neg(neg: jax.Array, k: int, tile: int) -> Tuple[jax.Array, jax.Array]:
    """Two-stage largest-k of `neg` along the last axis (exact, tie order ==
    lax.top_k's lowest-index-first). Padding uses -INVALID_D2 and pads sit at
    the highest indices of the last tile, so they lose every tie."""
    *lead, n = neg.shape
    pad = (-n) % tile
    if pad:
        neg = jnp.pad(neg, [(0, 0)] * len(lead) + [(0, pad)],
                      constant_values=-INVALID_D2)
    nt = (n + pad) // tile
    kk = min(k, tile)
    negt = neg.reshape(*lead, nt, tile)
    v, i = jax.lax.top_k(negt, kk)  # selection-plane primitive home (fence-exempt file)
    base = (jnp.arange(nt, dtype=jnp.int32) * tile).reshape(
        (1,) * len(lead) + (nt, 1)
    )
    pool_v = v.reshape(*lead, nt * kk)
    pool_i = (i.astype(jnp.int32) + base).reshape(*lead, nt * kk)
    v2, p2 = jax.lax.top_k(pool_v, k)  # selection-plane primitive home (fence-exempt file)
    return v2, jnp.take_along_axis(pool_i, p2, axis=-1)


def select_topk(
    d2: jax.Array,
    k: int,
    *,
    strategy: str,
    tile: Optional[int] = None,
    recall_target: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Smallest-k along the last axis: returns (d2_topk, indices), distances
    ascending. TRACE-PURE by contract (tools/analysis purity/*): this
    function reads no config and consults no tuning table — `strategy` must
    arrive CONCRETE from a host-side `resolve()` call, so a cached trace can
    never bake a stale choice. Only the pure degradations live here: a
    k-of-n select with no real pool reduction (k >= n, n <= 4k, n within one
    tile) runs fused exact, and `pallas_fused` degrades to exact_full (a
    d2-level select can't fuse — the matrix already exists)."""
    n = d2.shape[-1]
    k = min(int(k), n)
    if strategy is None or strategy == "auto":
        raise ValueError(
            "select_topk requires a concrete strategy — call "
            "ops.selection.resolve() in the HOST wrapper and pass the "
            "resolved triple down (trace-purity contract, docs/design.md §6j)"
        )
    if strategy not in STRATEGIES:
        raise ValueError(
            f"knn.selection must be one of {STRATEGIES}, got '{strategy}'"
        )
    if strategy == "pallas_fused" or k >= n or n <= 4 * k:
        strategy = "exact_full"
    if strategy == "exact_tiled" and (not tile or n <= tile):
        strategy = "exact_full"
    if strategy == "approx":
        if recall_target is None:
            raise ValueError(
                "select_topk(strategy='approx') requires a concrete "
                "recall_target — resolve() in the host wrapper provides one"
            )
        if not 0.0 < recall_target <= 1.0:
            raise ValueError(
                f"knn.recall_target must be in (0, 1], got {recall_target}"
            )
    # clamp: inf (or beyond-sentinel) entries would rank after tiled padding
    # and break exact_full/exact_tiled bit-parity; after the clamp every
    # strategy sees identical values and ties resolve identically
    d2 = jnp.minimum(d2, INVALID_D2)
    if strategy == "exact_tiled":
        neg, idx = _tiled_topk_neg(-d2, k, tile)
    elif strategy == "approx":
        neg, idx = jax.lax.approx_max_k(  # selection-plane primitive home (fence-exempt file)
            -d2, k, recall_target=recall_target
        )
    else:
        neg, idx = jax.lax.top_k(-d2, k)  # selection-plane primitive home (fence-exempt file)
    return -neg, idx


def merge_topk(
    pool_d2: jax.Array, pool_ids: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k over an already-selected candidate pool (running-merge
    steps: ring hops, all-gather merges, pairwise tile folds). ALWAYS
    exact_full — an approximate merge can silently drop carried candidates,
    which no recall target bounds (the loss compounds per merge step)."""
    k = min(int(k), pool_d2.shape[-1])
    d2, pos = select_topk(pool_d2, k, strategy="exact_full")
    return d2, jnp.take_along_axis(pool_ids, pos, axis=-1)


def top_k_max(
    scores: jax.Array, k: int, *, strategy: str = "exact_full",
    tile: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Largest-k along the last axis: (values, indices), values descending.
    The non-distance score picks (kmeans|| candidate sampling, tree feature
    subsampling) route through here; they are deterministic-seeded, so the
    default stays exact."""
    d2, idx = select_topk(-scores, k, strategy=strategy, tile=tile)
    return -d2, idx


def record_selection(strategy: str, site: str, model: Optional[str] = None) -> None:
    """Host-side strategy telemetry: one `knn.select_strategy{...}` count per
    search-plane entry call. Callers skip this under tracing (a trace-time
    count would fire once per compile, not per search)."""
    from .. import observability as _obs

    labels = {"strategy": strategy, "site": site}
    if model:
        labels["model"] = model
    _obs.counter_inc("knn.select_strategy", 1, **labels)


def is_tracing(*arrays: Any) -> bool:
    """True when any argument is a tracer — host-side instrumentation
    (counters, spans) must not fire from inside a trace."""
    return any(isinstance(a, jax.core.Tracer) for a in arrays)
