#
# Fused Pallas distance+select kernel family (docs/design.md §5c) — the
# roofline-kernel half of the selection plane (ops/selection.py carries the
# strategy knob; this module carries the `pallas_fused` implementation).
#
# The XLA scans materialize the (block, n_items) squared-distance tile in HBM
# before selecting over it: `_exact_knn_scan` writes+reads (block, n) f32 per
# query block, `kmeans_predict` an (n, k) matrix, `_core_mask` a (block, n)
# tile per row block. At the sizes the search family exists for, that traffic
# IS the roofline (the device plane's `roofline_bound=memory` verdicts on the
# distance-scan family), and BENCH_TPU_SESSION_R4 measured a masked Pallas
# XᵀX kernel at ~2x XLA's own two-read HBM roofline on a real v5e. This
# kernel family fuses the distance tile with an in-register running
# top-k / argmin / count-below-eps so the matrix never leaves VMEM — X
# streams through HBM exactly once per scan:
#
#   for each (query block, item tile):   d2 = q2 - 2 Q Xtᵀ + x2     (MXU)
#     reduction=topk    merge the tile into a running (block, k) pool via
#                       k-step extraction (argmin + mask, unrolled — ties
#                       resolve lowest-global-index-first, matching lax.top_k
#                       bit-for-bit)                                 (VPU)
#     reduction=argmin  running argmin is just the k=1 pool — but the KMeans
#                       assignment form streams ROWS against resident
#                       centers, so the argmin closes per row block
#     reduction=count   counts += Σ (d2 <= eps²) & valid             (VPU)
#
# One kernel family serves four call sites: KMeans assignment
# (ops/kmeans.py::kmeans_predict — superseding the small-k loss region of the
# opt-in ops/pallas_kmeans.py Lloyd kernel, whose fused form pays lane
# padding below k~128), exact kNN (ops/knn.py::exact_knn_single and the
# per-shard scans under exact_knn_distributed), the IVF coarse probe
# (ops/ann_streaming.py::streaming_ivfflat_search), and DBSCAN neighborhood
# counting (ops/dbscan.py::_core_mask).
#
# Contracts (the §5b invariants, preserved bit-for-bit):
#   * exact-f32 mode is BIT-IDENTICAL to the select_topk(exact_full) path on
#     returned ids AND distances, tie order included: the kernel computes the
#     same max(q2 - 2·cross + x2, 0) expansion, masks invalid entries to the
#     same large-finite INVALID_D2 sentinel (never inf — kernel-internal inf
#     is confined to extracted-slot masking and pool init, where it only ever
#     feeds compares), clamps at the sentinel, and its k-step extraction
#     prefers the first (lowest-global-index) occurrence of every tie exactly
#     like lax.top_k. k > n_valid therefore returns the same
#     earliest-invalid-id tail as the XLA path.
#   * bf16/int8 distance accumulation (knn.pallas_precision) selects an
#     OVERSAMPLED candidate pool on the fast MXU paths; the caller re-ranks
#     it with ops/knn.py::parity_rerank_sq (exact f32 difference-form
#     distances, exact merge) so returned DISTANCES are bit-equal to
#     exact-f32 — only the id set is approximate. int8 quantizes per row
#     (dynamic symmetric max-abs scales), so it suits normalized/bounded
#     feature spaces; norms ride exact f32 either way.
#   * multi-device runs wrap the single-device pallas_call per-shard under
#     shard_map (the callers' existing merge contracts are untouched:
#     merge_topk stays exact, sentinel semantics preserved).
#
# Every host entry routes through `compiled_kernel`, so compile accounting,
# XLA cost/memory analysis (seeded with a pl.CostEstimate — a pallas custom
# call is otherwise invisible to the cost model) and MFU/roofline span
# attribution work exactly like every other kernel. Off-TPU the kernels run
# the Pallas interpreter, which is what makes the §5c parity property tests
# CPU-runnable in tier-1.
#

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observability.device import compiled_kernel
from .selection import INVALID_D2

# tile-geometry DEFAULTS live in the defaults module (defaults.py — the
# analyzer's fence/hardcoded-tunable rule bans new literals in ops/): the query
# block bounds the (block, tile) distance tile in VMEM (256*1024*4 = 1 MiB)
# next to one double-buffered X tile (1024*d*4). Tests pass explicit odd tiles
# to exercise ragged edges.
from ..defaults import (  # re-exported; kmeans/tests import here
    DEFAULT_ASSIGN_BLOCK,
    DEFAULT_ITEM_TILE,
    DEFAULT_QUERY_BLOCK,
    FUSED_ASSIGN_MIN_K,
    MIN_ASSIGN_BLOCK,
    MIN_ITEM_TILE,
    MIN_QUERY_BLOCK,
)

# VMEM ceiling the fused tiles must fit under (the scoped-VMEM budget is
# ~16 MiB; half is left for double buffering and compiler scratch — the
# ops/pallas_kmeans.py lesson that a 4096x512 block blows exactly that
# limit). A hardware property, NOT a tunable. Geometry resolution shrinks
# blocks toward the floors and REFUSES (-> XLA path) when nothing fits: a
# Mosaic compile failure at k in the thousands would crash a predict the
# XLA path handles fine.
_VMEM_BUDGET_BYTES = 8 << 20


def _interpret_default() -> bool:
    """Off-TPU the kernels run the Pallas interpreter: bit-exact, slow — the
    correctness tier that makes CPU tier-1 parity tests real. The platform is
    the ONLY thing that selects it: a failing backend probe raises."""
    return jax.default_backend() != "tpu"


def _cost_estimate(flops: float, bytes_accessed: float) -> pl.CostEstimate:
    """Seed XLA's cost model for the pallas custom call: without an estimate
    the device plane's cost_analysis sees ~zero flops and the bench's
    measured-MFU keys read hollow."""
    return pl.CostEstimate(
        flops=int(max(flops, 0)),
        bytes_accessed=int(max(bytes_accessed, 0)),
        transcendentals=0,
    )


def topk_fits_vmem(q_block: int, item_tile: int, d: int, k: int) -> bool:
    """Can the running-pool scan place (q_block, item_tile) at this (d, k)?
    The working-set formula `_topk_geometry`'s shrink loop asks."""
    work = (
        q_block * (k + item_tile) * 4 * 4  # concat d2+ids + masked copies
        + q_block * d * 4 + item_tile * d * 4  # Q block + X tile
        + q_block * k * 8  # running pool (d2 + ids)
    )
    return work <= _VMEM_BUDGET_BYTES


def assign_block_fits_vmem(blk: int, d: int, k: int, n_split: int) -> bool:
    """Can the fused assignment place a blk-row block at this (d, k,
    n_split)? Shared by `_assign_geometry` and the `use_fused_assign` gate,
    so the two can never drift and admit a block Mosaic cannot place."""
    copies = max(1, n_split)  # bf16 splitting materializes n_split copies
    centers_b = k * d * 4 * copies
    tile_b = blk * d * 4 * copies + blk * k * 4 * 2  # X block + d2/onehot
    return centers_b + tile_b <= _VMEM_BUDGET_BYTES


def _topk_geometry(
    nq: int, n: int, d: int, k: int,
    q_block: Optional[int], item_tile: Optional[int],
) -> Tuple[int, int]:
    """(q_block, item_tile) fitting the running-pool scan's VMEM residents:
    Q block + X tile + the (B, k+T) extraction working set (concat d2/ids
    copies). Caller-pinned values pass through untouched (tests exercise
    ragged geometries); unpinned axes halve toward their floors until the
    budget holds — a floor-sized scan always fits for any k the search
    family produces."""
    qb = q_block or min(DEFAULT_QUERY_BLOCK, max(nq, 1))
    t = item_tile or min(DEFAULT_ITEM_TILE, max(n, 1))

    if q_block is None:
        while not topk_fits_vmem(qb, t, d, k) and qb > MIN_QUERY_BLOCK:
            qb //= 2
    if item_tile is None:
        while not topk_fits_vmem(qb, t, d, k) and t > MIN_ITEM_TILE:
            t //= 2
    return max(qb, 1), max(t, 1)


def _assign_n_split() -> int:
    """Cross-term passes for the fused assignment. The XLA reference
    (`_sq_dists` with fast=False → pdot) runs at PARITY precision, so on TPU
    the kernel emulates it with the same bf16-split decomposition the fused
    Lloyd uses (`_dot_multipass` — Mosaic rejects the precision attribute
    itself, ops/pallas_kmeans.py header); off-TPU a single pass IS exact
    f32, bit-identical to pdot on the CPU interpreter."""
    if _interpret_default():
        return 1
    from ._precision import parity_precision
    from .pallas_kmeans import _N_SPLIT

    return _N_SPLIT[parity_precision()]


def _assign_geometry(d: int, k: int, n_split: int, n: int) -> Optional[int]:
    """Row-block for the fused assignment, or None when even the smallest
    block cannot fit resident centers + tiles under the VMEM budget — the
    caller must keep the XLA path (which handles any k) rather than hand
    Mosaic a compile it cannot place."""
    floor = min(MIN_ASSIGN_BLOCK, max(n, 1))
    blk = min(max(DEFAULT_ASSIGN_BLOCK, floor), max(n, 1))
    while True:
        if assign_block_fits_vmem(blk, d, k, n_split):
            return blk
        if blk <= floor:
            return None
        blk //= 2


def _cross_term(Q: jax.Array, Xt: jax.Array, precision: str) -> jax.Array:
    """(B, T) cross term Q·Xtᵀ at the configured accumulation mode.

    float32: a single dot_general with f32 accumulate — on TPU this is the
    MXU's DEFAULT tier (the FAST contract of `_block_sq_dists`: ranking-class
    matmuls may run single-pass), on the CPU interpreter it is exact f32 and
    therefore bit-identical to the XLA scan's matmul.
    bfloat16: operands rounded to bf16 before a single f32-accumulate pass.
    int8: per-row dynamic symmetric quantization (max-abs / 127) and an
    int8×int8→int32 MXU pass, rescaled into f32."""
    dims = (((1,), (1,)), ((), ()))
    if precision == "bfloat16":
        return jax.lax.dot_general(
            Q.astype(jnp.bfloat16), Xt.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32,
        )
    if precision == "int8":
        s_q = jnp.max(jnp.abs(Q), axis=1, keepdims=True) / 127.0  # (B, 1)
        s_x = jnp.max(jnp.abs(Xt), axis=1, keepdims=True) / 127.0  # (T, 1)
        Qq = jnp.round(Q / jnp.maximum(s_q, 1e-30)).astype(jnp.int8)
        Xq = jnp.round(Xt / jnp.maximum(s_x, 1e-30)).astype(jnp.int8)
        cross = jax.lax.dot_general(
            Qq, Xq, dims, preferred_element_type=jnp.int32
        ).astype(jnp.float32)
        return cross * s_q * s_x.reshape(1, -1)
    return jax.lax.dot_general(
        Q, Xt, dims, preferred_element_type=jnp.float32
    )


# --------------------------------------------------------------------- topk


def _topk_scan_kernel(
    n_items: int, k: int, precision: str,
    q_ref, x_ref, x2m_ref, pool_d2_ref, pool_id_ref,
):
    """One (query block, item tile) step: fused distances + running top-k.

    The pool refs are revisited across the minor (item-tile) grid dimension,
    so the running top-k lives in VMEM for a whole query block. Pool slots
    initialize to (+inf, -1): kernel-internal inf LOSES every tie against the
    INVALID_D2 sentinel real entries carry, which is exactly what makes the
    k > n_valid tail bit-match the XLA path (earliest invalid ids win); inf
    never feeds arithmetic, only compares, so the §5b NaN-factory rule holds.
    The k-step extraction takes the first occurrence of each minimum — pool
    entries (earlier tiles, lower global ids) sit before tile entries, and
    tile lanes are global-id-ordered, so every tie resolves
    lowest-global-index-first, byte-for-byte lax.top_k's order."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        pool_d2_ref[...] = jnp.full_like(pool_d2_ref, jnp.inf)
        pool_id_ref[...] = jnp.full_like(pool_id_ref, -1)

    Q = q_ref[...]  # (B, d)
    Xt = x_ref[...]  # (T, d)
    x2m = x2m_ref[...]  # (1, T): Σx² for valid items, -1 sentinel for masked
    T = Xt.shape[0]
    gids = t * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    # validity = caller mask (x2m >= 0; real norms are always >= 0) AND the
    # ragged-edge bound (the overhang of the last tile reads unspecified
    # memory, which interpret mode may fill with NaN — masked before ranking)
    valid = (x2m >= 0.0) & (gids < n_items)
    x2 = jnp.where(valid, x2m, 0.0)

    q2 = jnp.sum(Q * Q, axis=1, keepdims=True)  # (B, 1)
    cross = _cross_term(Q, Xt, precision)  # (B, T)
    # same op order as _block_sq_dists + mask_invalid + the select_topk clamp:
    # max(.,0), sentinel mask, clamp — bit-parity depends on this sequence
    d2 = jnp.maximum(q2 - 2.0 * cross + x2, 0.0)
    d2 = jnp.where(valid, d2, INVALID_D2)
    d2 = jnp.minimum(d2, INVALID_D2)

    cat_d2 = jnp.concatenate([pool_d2_ref[...], d2], axis=1)  # (B, k+T)
    cat_id = jnp.concatenate(
        [pool_id_ref[...], jnp.broadcast_to(gids, d2.shape)], axis=1
    )
    B, W = cat_d2.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (B, W), 1)
    new_d2, new_id = [], []
    for _ in range(k):  # k static: unrolled extraction
        m = jnp.min(cat_d2, axis=1, keepdims=True)
        am = jnp.argmin(cat_d2, axis=1)  # first occurrence: the tie contract
        sel = cols == am[:, None]
        new_d2.append(m)
        # exactly one lane is selected per row, so the masked sum IS the id
        new_id.append(jnp.sum(jnp.where(sel, cat_id, 0), axis=1, keepdims=True))
        cat_d2 = jnp.where(sel, jnp.inf, cat_d2)  # extracted: loses every tie
    pool_d2_ref[...] = jnp.concatenate(new_d2, axis=1)
    pool_id_ref[...] = jnp.concatenate(new_id, axis=1)


@compiled_kernel(
    "knn.pallas_fused_scan",
    static_argnames=("k", "q_block", "item_tile", "precision", "interpret"),
)
def _fused_topk_scan(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    x2: Optional[jax.Array],
    k: int,
    q_block: int,
    item_tile: int,
    precision: str,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    nq, d = Q.shape
    n = X.shape[0]
    if x2 is None:
        x2 = jnp.sum(X * X, axis=1)  # same reduce as the XLA scan's hoist
    x2m = jnp.where(valid, x2, -1.0)[None, :]  # mask folded into the norm row
    n_qb = -(-nq // q_block)
    n_t = -(-n // item_tile)
    pool_d2, pool_id = pl.pallas_call(
        functools.partial(_topk_scan_kernel, n, k, precision),
        name="select_topk_scan",
        grid=(n_qb, n_t),
        in_specs=[
            pl.BlockSpec((q_block, d), lambda i, t: (i, 0)),
            pl.BlockSpec((item_tile, d), lambda i, t: (t, 0)),
            pl.BlockSpec((1, item_tile), lambda i, t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((q_block, k), lambda i, t: (i, 0)),
            pl.BlockSpec((q_block, k), lambda i, t: (i, 0)),
        ],
        # padded to whole query blocks: the ragged tail block writes its
        # overhang into the pad rows, sliced off below — X is never padded
        # (a host-side pad would copy the dataset at exactly the HBM-filling
        # sizes this kernel exists for, the ops/pallas_kmeans.py lesson)
        out_shape=[
            jax.ShapeDtypeStruct((n_qb * q_block, k), jnp.float32),
            jax.ShapeDtypeStruct((n_qb * q_block, k), jnp.int32),
        ],
        interpret=interpret,
        cost_estimate=_cost_estimate(
            flops=2.0 * nq * n * d + 2.0 * nq * n * k,
            bytes_accessed=4.0 * (nq * d + n * d + n + 2 * nq * k),
        ),
    )(Q, X, x2m)
    return pool_d2[:nq], pool_id[:nq]


def resolve_topk_geometry(
    nq: int, n: int, d: int, k: int,
    q_block: Optional[int] = None, item_tile: Optional[int] = None,
) -> Tuple[int, int]:
    """HOST-side geometry resolution for the fused top-k scan: the defaults
    + the VMEM-budget shrink. Resolve in the host wrapper / shard_map factory
    and hand the pins to `fused_topk_pinned`."""
    return _topk_geometry(int(nq), int(n), int(d), int(k), q_block, item_tile)


def fused_topk_pinned(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    k: int,
    *,
    q_block: int,
    item_tile: int,
    x2: Optional[jax.Array] = None,
    precision: str = "float32",
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """TRACE-PURE core of the fused smallest-k scan: geometry arrives pinned
    (resolve_topk_geometry in a host wrapper), precision arrives resolved —
    no config read (tools/analysis purity/*). This is
    the form shard_map bodies call; same output contract as fused_topk."""
    n = X.shape[0]
    k = min(int(k), n)
    if interpret is None:
        interpret = _interpret_default()  # backend probe, not config
    return _fused_topk_scan(
        Q, X, valid, x2, k, int(q_block), int(item_tile), precision, interpret,
    )


def fused_topk(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    k: int,
    *,
    x2: Optional[jax.Array] = None,
    precision: str = "float32",
    q_block: Optional[int] = None,
    item_tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused smallest-k scan: (d2_topk ascending, global ids). Exact-f32 mode
    is bit-identical to the `select_topk(exact_full)` path (ids, distances,
    tie order, k > n_valid tails). bf16/int8 modes return the APPROXIMATE
    pool — callers owe the user a parity_rerank_sq pass (see fused_knn_select
    for the paired form). HOST wrapper: resolves geometry (defaults +
    VMEM shrink) and delegates to the trace-pure fused_topk_pinned."""
    n = X.shape[0]
    k = min(int(k), n)
    q_block, item_tile = resolve_topk_geometry(
        int(Q.shape[0]), int(n), int(Q.shape[1]), k, q_block, item_tile
    )
    return fused_topk_pinned(
        Q, X, valid, k, q_block=q_block, item_tile=item_tile, x2=x2,
        precision=precision, interpret=interpret,
    )


def oversample_width(k: int, n: int, precision: str) -> int:
    """Candidate-pool width for the approximate-compute modes: bf16/int8
    ranking error can push the true k-th winner just past the boundary, so
    the pool oversamples (k + max(8, k/4), clamped to n) before the exact
    re-rank cuts it back to k. float32 needs no slack — it IS exact."""
    if precision == "float32":
        return min(int(k), n)
    return min(n, int(k) + max(8, int(k) // 4))


# -------------------------------------------------------------------- probe


def fused_probe(
    Q: jax.Array,
    centers: jax.Array,
    nprobe: int,
    *,
    center_norms: Optional[jax.Array] = None,
) -> jax.Array:
    """IVF coarse probe: ids of the nprobe nearest cells per query. ALWAYS
    exact f32 (the probe list bounds recall for the whole search — the §5b
    rule that the coarse probe never goes approximate), bit-identical to the
    `select_topk(cd2, nprobe, exact_full)` probe."""
    nlist = centers.shape[0]
    ones = jnp.ones((nlist,), bool)
    _, probe = fused_topk(
        Q, centers, ones, min(int(nprobe), nlist),
        x2=center_norms, precision="float32",
    )
    return probe


# ------------------------------------------------------------------- argmin


def _assign_kernel(n_rows: int, n_split: int, x_ref, c_ref, c2_ref, out_ref):
    """KMeans assignment row block: fused distances + argmin over resident
    centers. The argmin closes within the block (centers all fit one tile),
    so the output streams out per block and no (n, k) tensor ever exists.
    Computes the FULL clamped d2 (including the x2 row term the argmin
    technically cancels): max(d2, 0) can clamp several centers of a
    duplicate-heavy row to exactly 0, and dropping x2 would re-order those
    ties against `kmeans_predict`'s argmin — full-form keeps bit-parity.
    The cross term runs at n_split bf16-split passes (_assign_n_split): the
    XLA reference ranks at PARITY precision, not FAST, and the fused path
    must not silently demote it."""
    from .pallas_kmeans import _dot_multipass

    Xb = x_ref[...]  # (B, d)
    C = c_ref[...]  # (k, d)
    c2 = c2_ref[...]  # (1, k)
    x2 = jnp.sum(Xb * Xb, axis=1, keepdims=True)
    cross = _dot_multipass(Xb, C, (((1,), (1,)), ((), ())), n_split)
    d2 = jnp.maximum(x2 - 2.0 * cross + c2, 0.0)
    # overhang rows of the last block read unspecified memory; their argmin
    # lands in the output pad rows, sliced off at the host — but NaN must not
    # reach argmin (NaN never sorts), so the edge rows are zeroed first
    b = pl.program_id(0)
    rows = b * Xb.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, (Xb.shape[0], 1), 0
    )
    d2 = jnp.where(rows < n_rows, d2, 0.0)
    out_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]


@compiled_kernel(
    "kmeans.pallas_assign",
    static_argnames=("block", "n_split", "interpret"),
)
def _fused_assign(
    X: jax.Array,
    centers: jax.Array,
    block: int,
    n_split: int,
    interpret: bool,
) -> jax.Array:
    n, d = X.shape
    k = centers.shape[0]
    c2 = jnp.sum(centers * centers, axis=1)[None, :]  # the XLA kernel's c2
    n_b = -(-n // block)
    out = pl.pallas_call(
        functools.partial(_assign_kernel, n, n_split),
        name="select_assign",
        grid=(n_b,),
        in_specs=[
            pl.BlockSpec((block, d), lambda b: (b, 0)),
            pl.BlockSpec((k, d), lambda b: (0, 0)),
            pl.BlockSpec((1, k), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((n_b * block, 1), jnp.int32),
        interpret=interpret,
        cost_estimate=_cost_estimate(
            flops=2.0 * n * k * d * (max(1, n_split) * (max(1, n_split) + 1) // 2),
            bytes_accessed=4.0 * (n * d + k * d + n),
        ),
    )(X, centers, c2)
    return out[:n, 0]


def fused_assign(
    X: jax.Array,
    centers: jax.Array,
    *,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused nearest-center assignment (argmin reduction): X streams through
    once, matching `argmin(_sq_dists(X, centers))` — bit-identical off-TPU
    (single-pass f32 == pdot on CPU), parity-class (bf16-split emulation of
    the pdot pass structure) on TPU. Raises when no row block fits VMEM —
    `use_fused_assign` gates that case to the XLA path before routing."""
    if interpret is None:
        interpret = _interpret_default()
    n, d = X.shape
    n_split = _assign_n_split()
    if block is None:
        block = _assign_geometry(d, int(centers.shape[0]), n_split, int(n))
        if block is None:
            raise ValueError(
                "fused assignment does not fit the VMEM budget at "
                f"k={int(centers.shape[0])}, d={d} — use the XLA path"
            )
    return _fused_assign(X, centers, block, n_split, interpret)


def use_fused_assign(
    k: int, d: Optional[int] = None, strategy: Optional[str] = None
) -> bool:
    """Should KMeans assignment run the fused kernel? `pallas_fused`
    explicitly → yes (any platform; interpret off-TPU). `auto` → TPU and
    k >= FUSED_ASSIGN_MIN_K, the measured win boundary where the MXU lane
    padding vanishes and XLA's (n, k) intermediates approach the size of X
    (the documented small-k loss region of ops/pallas_kmeans.py). Either
    way, a (k, d) whose resident centers + smallest row block cannot fit
    the VMEM budget stays on the XLA path (which handles any k) — even an
    explicit request must not hand Mosaic an unplaceable compile."""
    from . import selection as _sel
    from .. import config as _config

    s = strategy or str(_config.get("knn.selection"))
    if s not in ("pallas_fused", "auto"):
        return False
    if s == "auto":
        if _sel._backend() != "tpu":
            # auto off-TPU: XLA always
            return False
        if int(k) < FUSED_ASSIGN_MIN_K:
            return False
    if d is not None and not assign_block_fits_vmem(
        # placeability = the FLOOR block fits (what _assign_geometry's
        # shrink bottoms out at)
        MIN_ASSIGN_BLOCK, int(d), int(k), _assign_n_split()
    ):
        return False
    return True


# -------------------------------------------------------------------- count


def _count_kernel(n_items: int, precision: str,
                  q_ref, x_ref, x2m_ref, eps2_ref, out_ref):
    """DBSCAN neighborhood counting: counts += Σ (d2 <= eps²) & valid per
    item tile; the counts ref is revisited across the minor grid dimension."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    Q = q_ref[...]
    Xt = x_ref[...]
    x2m = x2m_ref[...]
    T = Xt.shape[0]
    gids = t * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    valid = (x2m >= 0.0) & (gids < n_items)
    x2 = jnp.where(valid, x2m, 0.0)
    q2 = jnp.sum(Q * Q, axis=1, keepdims=True)
    cross = _cross_term(Q, Xt, precision)
    d2 = jnp.maximum(q2 - 2.0 * cross + x2, 0.0)
    eps2 = eps2_ref[0, 0]
    hit = (d2 <= eps2) & valid  # invalid lanes (incl. NaN overhang) never count
    out_ref[...] += jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)


@compiled_kernel(
    "dbscan.pallas_count",
    static_argnames=("q_block", "item_tile", "precision", "interpret"),
)
def _fused_count(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    eps2: jax.Array,
    q_block: int,
    item_tile: int,
    precision: str,
    interpret: bool,
) -> jax.Array:
    nq, d = Q.shape
    n = X.shape[0]
    x2 = jnp.sum(X * X, axis=1)
    x2m = jnp.where(valid, x2, -1.0)[None, :]
    n_qb = -(-nq // q_block)
    n_t = -(-n // item_tile)
    counts = pl.pallas_call(
        functools.partial(_count_kernel, n, precision),
        name="select_count",
        grid=(n_qb, n_t),
        in_specs=[
            pl.BlockSpec((q_block, d), lambda i, t: (i, 0)),
            pl.BlockSpec((item_tile, d), lambda i, t: (t, 0)),
            pl.BlockSpec((1, item_tile), lambda i, t: (0, t)),
            pl.BlockSpec((1, 1), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((q_block, 1), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_qb * q_block, 1), jnp.int32),
        interpret=interpret,
        cost_estimate=_cost_estimate(
            flops=2.0 * nq * n * d,
            bytes_accessed=4.0 * (nq * d + n * d + n + nq),
        ),
    )(Q, X, x2m, jnp.asarray(eps2, jnp.float32).reshape(1, 1))
    return counts[:nq, 0]


def fused_count_below(
    Q: jax.Array,
    X: jax.Array,
    valid: jax.Array,
    eps2,
    *,
    precision: str = "float32",
    q_block: Optional[int] = None,
    item_tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Count-below-eps reduction: per query row, how many VALID items sit
    within eps² (self included when Q is X). eps2 rides as a runtime operand,
    so one compiled signature serves every eps. Bit-identical counts to the
    `_core_mask` XLA scan in f32 mode. Tile geometry resolves through the
    same VMEM-budget shrink as the topk scan (k=0 — no running pool), so a
    wide-d scan can never hand Mosaic an unplaceable compile."""
    if interpret is None:
        interpret = _interpret_default()
    q_block, item_tile = _topk_geometry(
        int(Q.shape[0]), int(X.shape[0]), int(Q.shape[1]), 0,
        q_block, item_tile,
    )
    return _fused_count(
        Q, X, valid, eps2, q_block, item_tile, precision, interpret,
    )


def use_fused_count(n_items: int, strategy: Optional[str] = None) -> bool:
    """Should a neighborhood-count scan run fused? Same gate shape as the
    kNN sites: explicit `pallas_fused` always, `auto` on TPU once the item
    width clears knn.pallas_min_items."""
    from . import selection as _sel
    from .. import config as _config

    s = strategy or str(_config.get("knn.selection"))
    if s == "pallas_fused":
        return True
    if s == "auto":
        return _sel._fused_auto(int(n_items))
    return False
