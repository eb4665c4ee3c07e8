#
# Pallas TPU kernel: fused Lloyd iteration (assignment + centroid accumulation).
#
# The XLA formulation of one Lloyd step reads X twice per iteration from HBM: once
# for the (n, k) distance matmul and once for the one-hotT @ X centroid update —
# plus it materializes the (n, k) distance/one-hot intermediates. This kernel fuses
# the whole step per row block in VMEM:
#     for each block of rows:  d2 = x2 - 2 Xb Ct + c2      (MXU)
#                              assign = argmin d2
#                              onehot = (iota == assign)    (VPU, never leaves VMEM)
#                              sums   += onehotT @ Xb       (MXU)
#                              counts += sum onehot
#                              inertia+= sum w * min d2
# so X streams through HBM exactly once per iteration and no (n, k) tensor exists.
#
# Single-device form (pallas_call has no GSPMD rule); the multi-device path wraps it
# per-shard under shard_map with a psum merge, exactly like the histogram kernel
# (ops/pallas_histogram.py).
#
# MEASURED (v5e, 12M x 128, k=20, steady-state marginal per-iteration): XLA
# lloyd_fit 18.7 ms/iter (~92% of its two-X-reads HBM roofline) vs this kernel at
# 26.3 (1-pass) / 37.5 (6-pass parity) ms/iter. At small k the two MXU matmuls pad
# k to the 128-lane width, so halving HBM traffic buys nothing — the kernel is
# VPU/MXU-bound, not DMA-bound. SRML_TPU_PALLAS_KMEANS therefore AUTO-resolves
# (the default since the §5c fused-selection PR): on TPU at k >= 128 — where
# lane padding vanishes and XLA's (n, k) distance/one-hot intermediates approach
# the size of X itself — the fused kernel engages (masked form under unit
# weights); below that, or off-TPU, the XLA path runs. "1"/"mask" force the
# kernel unconditionally, "0" forces XLA; `kmeans.lloyd_path{path=}` counts
# which path ran (ops/kmeans.py::kmeans_fit owns the routing). The ASSIGNMENT
# half of the win region is served by the lighter fused distance+argmin scan
# in ops/pallas_select.py (kmeans_predict routes there under the same gate).
#

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 0  # 0 = adaptive (see _block_rows); tests may pin a fixed size

# MXU passes emulating each f32 precision tier via bf16 splitting (_dot_multipass)
_N_SPLIT = {
    jax.lax.Precision.DEFAULT: 1,
    jax.lax.Precision.HIGH: 2,
    jax.lax.Precision.HIGHEST: 3,
}


def _block_rows(d: int, n_split: int = 1) -> int:
    """Row-block size targeting ~2 MiB of X per block: big enough to amortize DMA
    issue latency (TPU-measured: 1024-row blocks pay ~10% over 4096 at d=128),
    small enough that double-buffered blocks + the (B, 128-lane-padded) distance/
    one-hot intermediates stay inside the 16 MiB scoped-VMEM budget at any d
    (a lax.cond variant at 4096x512 was observed to blow exactly that limit).
    Multipass precision (n_split>1) materializes n_split bf16 copies of the X
    block and the one-hot, so the block shrinks with it (3-split at 4096x128
    was observed 2.56 MiB over the scoped-vmem limit)."""
    if BLOCK_ROWS:
        return BLOCK_ROWS
    target = 2 * 1024 * 1024 // (max(d, 1) * 4)
    blk = int(min(8192, max(512, 1 << (target.bit_length() - 1))))
    if n_split > 1:
        blk = max(512, blk // 2)
    return blk


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def lloyd_fits_vmem(k: int, d: int, n_split: int) -> bool:
    """Can the fused Lloyd place its VMEM residents at this (k, d, n_split)?
    The kernel keeps C and the sums accumulator (k, d) resident (bf16
    splitting materializes n_split operand copies of C and the one-hot) next
    to one _block_rows-sized X block and the (blk, k) distance/one-hot
    intermediates. The routing gate (ops/kmeans.py::kmeans_fit auto mode)
    asks THIS predicate instead of hand-rolling a formula, so the knowledge
    of the kernel's working set lives with the kernel — a (k, d) that fails
    here stays on the XLA path rather than handing Mosaic an unplaceable
    compile."""
    from .pallas_select import _VMEM_BUDGET_BYTES  # one budget, one source

    copies = max(1, int(n_split))
    blk = _block_rows(d, copies)
    # f32 operands carry 2-byte bf16 split copies when n_split > 1
    split_b = 2 * copies if copies > 1 else 0
    resident = k * d * (8 + split_b)  # C (+splits) and the f32 sums
    working = (
        blk * d * (4 + split_b)  # X block (+splits)
        + blk * k * (8 + split_b)  # distance tile + one-hot (+splits)
    )
    return resident + working <= _VMEM_BUDGET_BYTES


def _split_bf16(x, n_split: int):
    """Decompose f32 into n_split bf16 terms (x ≈ Σ parts): the classic
    hi/lo residual split behind XLA's HIGH/HIGHEST f32 matmul emulation."""
    parts = []
    r = x
    for _ in range(n_split):
        p = r.astype(jnp.bfloat16)
        parts.append(p)
        r = r - p.astype(jnp.float32)
    return parts


def _dot_multipass(a, b, dims, n_split: int):
    """dot_general with f32 operands emulated at higher precision via bf16
    splitting: n_split=1 → single-pass MXU (DEFAULT numerics), 2 → 3 passes
    (≙ Precision.HIGH), 3 → 6 passes (≙ Precision.HIGHEST ≈ full f32).
    Mosaic rejects precision=HIGH/HIGHEST on this toolchain (NotImplementedError /
    compile-helper crash, observed on v5e), so the decomposition is done by hand;
    each pass is a native bf16×bf16→f32 MXU matmul."""
    if n_split <= 1:
        return jax.lax.dot_general(
            a, b, dims, preferred_element_type=jnp.float32
        )
    pa = _split_bf16(a, n_split)
    pb = _split_bf16(b, n_split)
    acc = None
    # terms ordered smallest-magnitude first so the f32 accumulation loses the
    # least; skip terms whose combined order i+j >= n_split (below f32 ulp)
    for i in range(n_split - 1, -1, -1):
        for j in range(n_split - 1 - i, -1, -1):
            t = jax.lax.dot_general(
                pa[i], pb[j], dims, preferred_element_type=jnp.float32
            )
            acc = t if acc is None else acc + t
    return acc


def _lloyd_kernel(
    n_rows, n_split, x_ref, w_ref, c_ref, c2_ref, sums_ref, counts_ref, inertia_ref
):
    """One row block: fused distances + argmin + weighted accumulation.

    The grid covers ceil(n / BLOCK_ROWS) blocks with NO host-side padding of X —
    padding would copy the whole design matrix inside the jit, doubling HBM at
    exactly the HBM-filling sizes this kernel exists for (observed OOM at 12M x 128
    on a 16 GiB v5e). The ragged tail block is masked here instead: rows past
    n_rows load unspecified values from the edge block, so both X and w are zeroed
    before any arithmetic can propagate them (0 * garbage stays finite only when
    the garbage never reaches a matmul — hence masking X itself, not just w)."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        inertia_ref[...] = jnp.zeros_like(inertia_ref)

    Xb = x_ref[...]  # (B, d)
    w = w_ref[...]  # (B, 1)
    C = c_ref[...]  # (k, d)
    c2 = c2_ref[...]  # (1, k)

    row0 = b * Xb.shape[0]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (Xb.shape[0], 1), 0)
    valid = rows < n_rows  # (B, 1) bool
    # select, don't multiply: the edge block's unspecified region can be NaN
    # (interpret mode fills it so) and 0 * NaN is NaN
    Xb = jnp.where(valid, Xb, 0.0)
    w = jnp.where(valid, w, 0.0)

    cross = _dot_multipass(Xb, C, (((1,), (1,)), ((), ())), n_split)  # (B, k)
    # x2 cancels in the argmin; only the inertia needs it
    part = c2 - 2.0 * cross  # (B, k)
    assign = jnp.argmin(part, axis=1)  # (B,)
    k = C.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (Xb.shape[0], k), 1)
    onehot = (cols == assign[:, None]).astype(jnp.float32) * w  # (B, k) weighted

    sums_ref[...] += _dot_multipass(
        onehot, Xb, (((0,), (0,)), ((), ())), n_split
    )  # (k, d)
    counts_ref[...] += jnp.sum(onehot, axis=0)[None, :]  # (1, k)
    x2 = jnp.sum(Xb * Xb, axis=1, keepdims=True)  # (B, 1)
    min_part = jnp.min(part, axis=1, keepdims=True)  # (B, 1)
    d2min = jnp.maximum(x2 + min_part, 0.0)
    inertia_ref[...] += jnp.sum(w * d2min)[None, None]


def _lloyd_kernel_masked(
    n_split, nv_ref, x_ref, c_ref, c2_ref, sums_ref, counts_ref, inertia_ref
):
    """Unit-weight variant of _lloyd_kernel: NO weight vector operand. A (blk, 1)
    w block tile-pads to 128 lanes in VMEM and forces a layout-converting DMA —
    measured 3x slower on the sibling Gram kernel (ops/pallas_xtwx.py header).
    Row validity is the runtime scalar nv_ref (the pad_rows prefix-mask
    contract); sample-weighted fits keep the weighted kernel."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        inertia_ref[...] = jnp.zeros_like(inertia_ref)

    Xb = x_ref[...]  # (B, d)
    C = c_ref[...]  # (k, d)
    c2 = c2_ref[...]  # (1, k)

    row0 = b * Xb.shape[0]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (Xb.shape[0], 1), 0)
    valid = rows < nv_ref[0, 0]
    # select, don't multiply: unspecified edge-block values can be NaN
    Xb = jnp.where(valid, Xb, 0.0)

    cross = _dot_multipass(Xb, C, (((1,), (1,)), ((), ())), n_split)  # (B, k)
    part = c2 - 2.0 * cross
    assign = jnp.argmin(part, axis=1)
    k = C.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (Xb.shape[0], k), 1)
    onehot = jnp.where(
        valid, (cols == assign[:, None]).astype(jnp.float32), 0.0
    )  # (B, k)

    sums_ref[...] += _dot_multipass(onehot, Xb, (((0,), (0,)), ((), ())), n_split)
    counts_ref[...] += jnp.sum(onehot, axis=0)[None, :]
    x2 = jnp.sum(Xb * Xb, axis=1, keepdims=True)
    min_part = jnp.min(part, axis=1, keepdims=True)
    d2min = jnp.maximum(x2 + min_part, 0.0)
    inertia_ref[...] += jnp.sum(jnp.where(valid, d2min, 0.0))[None, None]


@functools.partial(jax.jit, static_argnames=("interpret", "blk", "n_split"))
def _lloyd_step_masked_jit(X, n_valid, centers, interpret: bool, blk: int, n_split: int):
    n, d = X.shape
    k = centers.shape[0]
    c2 = jnp.sum(centers * centers, axis=1)[None, :]

    sums, counts, inertia = pl.pallas_call(
        functools.partial(_lloyd_kernel_masked, n_split),
        name="lloyd_step_masked",
        grid=((n + blk - 1) // blk,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
            pl.BlockSpec((blk, d), lambda b: (b, 0)),
            pl.BlockSpec((k, d), lambda b: (0, 0)),
            pl.BlockSpec((1, k), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda b: (0, 0)),
            pl.BlockSpec((1, k), lambda b: (0, 0)),
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1, 1), X, centers, c2)
    return sums, counts[0], inertia[0, 0]


def lloyd_step_pallas_masked(
    X: jax.Array,
    n_valid,
    centers: jax.Array,
    interpret: bool = False,
    blk: int | None = None,
    precision: jax.lax.Precision = jax.lax.Precision.DEFAULT,
):
    """Unit-weight fused Lloyd pass over the first n_valid rows (runtime scalar);
    one X read, no weight stream. Returns (sums, counts, inertia)."""
    n_split = _N_SPLIT[precision]
    return _lloyd_step_masked_jit(
        X, n_valid, centers, interpret,
        blk if blk else _block_rows(X.shape[1], n_split), n_split,
    )


def lloyd_step_pallas(
    X: jax.Array,  # (n, d) f32
    w: jax.Array,  # (n,) f32 — 0 for padding rows
    centers: jax.Array,  # (k, d) f32
    interpret: bool = False,
    blk: int | None = None,
    precision: jax.lax.Precision = jax.lax.Precision.DEFAULT,
):
    """One fused Lloyd accumulation pass. Returns (sums (k,d), counts (k,),
    inertia scalar) — the caller forms new centers as sums/counts.

    blk resolves OUTSIDE the jitted inner so a test pinning the module-level
    BLOCK_ROWS actually takes effect — the jit cache is keyed on the static blk,
    never on the module global.

    precision sets both MXU matmuls (assignment cross-term and one-hot update):
    DEFAULT = single-pass bf16 class (fast_math numerics), HIGH = 3-pass,
    HIGHEST = 6-pass f32 parity (emulated in-kernel via bf16 splitting — Mosaic
    rejects the precision attribute itself on this toolchain). The kernel is
    HBM-streaming-bound at the shapes it exists for, so the extra parity passes
    ride mostly under the DMA floor."""
    n_split = _N_SPLIT[precision]
    return _lloyd_step_jit(
        X, w, centers, interpret,
        blk if blk else _block_rows(X.shape[1], n_split), n_split,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "blk", "n_split"))
def _lloyd_step_jit(
    X: jax.Array,
    w: jax.Array,
    centers: jax.Array,
    interpret: bool,
    blk: int,
    n_split: int,
):
    n, d = X.shape
    k = centers.shape[0]
    c2 = jnp.sum(centers * centers, axis=1)[None, :]  # (1, k)

    sums, counts, inertia = pl.pallas_call(
        functools.partial(_lloyd_kernel, n, n_split),
        name="lloyd_step",
        grid=((n + blk - 1) // blk,),
        in_specs=[
            pl.BlockSpec((blk, d), lambda b: (b, 0)),
            pl.BlockSpec((blk, 1), lambda b: (b, 0)),
            pl.BlockSpec((k, d), lambda b: (0, 0)),
            pl.BlockSpec((1, k), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda b: (0, 0)),
            pl.BlockSpec((1, k), lambda b: (0, 0)),
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(X, w[:, None], centers, c2)
    return sums, counts[0], inertia[0, 0]


@functools.lru_cache(maxsize=None)
def _fit_fn(
    mesh,
    interpret: bool,
    blk: int,
    precision=jax.lax.Precision.DEFAULT,
    unit_mask: bool = False,
):
    """Build (and cache) the jitted full-loop fit for a mesh/interpret/blk combo.

    The whole Lloyd loop runs ON DEVICE as a lax.while_loop around the fused step —
    a host-driven loop costs one host<->device round trip (dispatch + sync of the
    convergence scalar) per iteration. One dispatch for the whole fit, like
    ops/kmeans.lloyd_fit.

    The REPORTED inertia is recomputed against the final centers at parity
    precision (pdot) outside the kernel — the kernel's own inertia accumulator
    (default-precision matmul) only steers the convergence loop. This keeps the
    fast_math contract from ops/kmeans.lloyd_fit: ranking-class matmuls may run
    at bf16, anything reported as a model attribute stays parity-precision."""
    from ..parallel.mesh import DATA_AXIS
    from ..parallel.partitioner import partitioner_for
    from ._precision import pdot

    if mesh is not None and mesh.devices.size > 1:
        from jax import shard_map

        part = partitioner_for(mesh)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(part.data_spec(2), part.data_spec(1), part.state_spec()),
            out_specs=(part.state_spec(), part.state_spec(), part.state_spec()),
            check_vma=False,
        )
        def step(x_local, w_local, centers):
            if unit_mask:
                # per-shard valid-prefix count: one cheap read of w vs streaming
                # a (blk, 1) weight block through VMEM every grid step
                s, c, i = lloyd_step_pallas_masked(
                    x_local, jnp.sum(w_local.astype(jnp.int32)), centers,
                    interpret=interpret, blk=blk, precision=precision,
                )
            else:
                s, c, i = lloyd_step_pallas(
                    x_local, w_local, centers, interpret=interpret, blk=blk,
                    precision=precision,
                )
            return (
                jax.lax.psum(s, DATA_AXIS),
                jax.lax.psum(c, DATA_AXIS),
                jax.lax.psum(i, DATA_AXIS),
            )

    elif unit_mask:

        def step(X, w, centers):
            return lloyd_step_pallas_masked(
                X, jnp.sum(w.astype(jnp.int32)), centers,
                interpret=interpret, blk=blk, precision=precision,
            )

    else:
        step = functools.partial(
            lloyd_step_pallas, interpret=interpret, blk=blk, precision=precision
        )

    def fit(X, w, init_centers, tol, max_iter):
        def cond(state):
            _, _, it, shift2 = state
            return jnp.logical_and(it < max_iter, shift2 > tol * tol)

        def body(state):
            centers, _, it, _ = state
            sums, counts, inertia = step(X, w, centers)
            new_centers = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts, 1.0)[:, None],
                centers,
            )
            shift2 = jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1))
            return new_centers, inertia, it + 1, shift2

        state = (
            init_centers,
            jnp.array(0.0, X.dtype),
            jnp.array(0, jnp.int32),
            jnp.array(jnp.inf, X.dtype),
        )
        centers, _, n_iter, _ = jax.lax.while_loop(cond, body, state)
        # reported inertia: final centers, PARITY precision (see docstring)
        x2 = jnp.sum(X * X, axis=1)
        c2 = jnp.sum(centers * centers, axis=1)
        d2 = x2[:, None] - 2.0 * pdot(X, centers.T) + c2[None, :]
        inertia = jnp.sum(w * jnp.maximum(jnp.min(d2, axis=1), 0.0))
        return centers, inertia, n_iter

    return jax.jit(fit, static_argnames=("max_iter",))


def lloyd_fit_pallas(
    X: jax.Array,
    w: jax.Array,
    init_centers: jax.Array,
    tol: float,
    max_iter: int,
    mesh=None,
    interpret: bool = False,
    precision: jax.lax.Precision = jax.lax.Precision.DEFAULT,
    unit_mask: bool = False,
):
    """Full Lloyd loop over the fused kernel; identical convergence semantics to
    ops/kmeans.lloyd_fit (movement^2 <= tol^2). With a multi-device mesh the kernel
    runs per-shard under shard_map and the (sums, counts, inertia) partials psum.

    precision=HIGHEST makes the in-loop numerics match lloyd_fit's parity path
    (f32 assignment + f32 update accumulation); DEFAULT matches fast_math.

    unit_mask=True requires w to be the pad_rows {1…1,0…0} prefix mask per shard
    (FitInputs.unit_weight) and runs the weight-stream-free kernel — the same
    (blk, 1)-operand elimination that took the Gram kernel from 25.7 to
    8.2 ms/pass (ops/pallas_xtwx.py header)."""
    n_split = _N_SPLIT[precision]
    centers, inertia, n_iter = _fit_fn(
        mesh, interpret, _block_rows(X.shape[1], n_split), precision, unit_mask
    )(X, w, init_centers, jnp.asarray(tol, X.dtype), max_iter)
    return centers, float(inertia), int(n_iter)
