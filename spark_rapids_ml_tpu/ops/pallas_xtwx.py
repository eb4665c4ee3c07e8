#
# Pallas TPU kernel: fused Gram accumulation — S2 = XᵀX, s1 = colsum(X) over the
# valid-row prefix, in ONE streaming read of X.
#
# This is the hot op of the PCA covariance fit (the TPU replacement for PCAMG.fit's
# in-cuML covariance allreduce, reference python/src/spark_rapids_ml/feature.py:228-253)
# and — via `normal_eq_prefix_mask` — of the unit-weight normal-equation LinReg fit
# (the XᵀWy term rides along as a lane-dense (1, blk) label row, NOT the
# (blk, 1) layout documented below as poison, so one X read yields XᵀX, Xᵀy, and yᵀy
# together; reference regression.py:548-558). Two measured facts (2026-07-29, one
# v5e, 12M x 128 f32, steady-state marginal rate; earlier code and another JAX, not
# re-measured — docs/performance.md) shape the design:
#
#   * The XLA formulation (ops/linalg.py::weighted_covariance) runs at ~16 ms/pass:
#     the lhs (w-scaled X) and rhs (X) stream from HBM independently, so X crosses
#     HBM twice — XLA is AT its own two-read roofline (~740 GB/s), and no XLA
#     rewrite gets below it.
#   * A w vector operand is poison for the pallas kernel: a (blk, 1) f32 block pads
#     to 128 lanes in VMEM, so its tile footprint equals the X block itself and the
#     DMA does a layout-converting scatter — measured 25.7 ms/pass WITH the w operand
#     vs 8.2 ms/pass (93% of the single-read HBM roofline) without it.
#
# Hence: the kernel takes NO weight vector. Row validity is a runtime scalar
# `n_valid` (rows >= n_valid are masked in-kernel via iota compare) — exactly the
# shape of the repo's padding contract, where pad_rows (parallel/partition.py) places
# all padding at the end, so every shard's mask is a {1…1,0…0} prefix mask and
# n_valid = sum(w_local). True per-sample weights fall back to the XLA path.
#
# f32 parity precision is emulated in-kernel via bf16 splitting exactly as in
# ops/pallas_kmeans.py (Mosaic rejects the precision attribute on this toolchain):
# measured 1348 M rows/s at HIGH (3-pass), 722 M rows/s at HIGHEST (6-pass) vs the
# 119 M rows/s this path replaced.
#
# Single-device pallas_call; multi-device wraps per-shard under shard_map + psum
# (the same pattern as ops/pallas_histogram.py / ops/pallas_kmeans.py).
#

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..observability.device import compiled_kernel
from .pallas_kmeans import _N_SPLIT, _block_rows, _dot_multipass

# largest feature width the fused kernel accepts: S2 (d, d) plus a double-buffered
# (blk, d) block must fit the ~16 MiB scoped-VMEM budget with the multipass bf16
# copies (d=512: 1 MiB S2 + 2x1 MiB blocks + splits)
MAX_FUSED_COLS = 512


def _xtx_kernel(n_split, nv_ref, s_ref, x_ref, s2_ref, s1_ref):
    """One row block: S2 += Xbᵀ Xb, s1 += colsum(Xb) over valid rows.

    nv_ref holds the runtime valid-row count (rows past it are masked — the ragged
    tail block also loads unspecified values from past the array edge, which the
    same mask zeroes before any arithmetic). s_ref is a CSE guard: pallas_call is
    opaque to XLA, so chaining a varying scalar through it is the only way a
    benchmark loop of identical passes doesn't collapse to one (no caller sets
    it any more: ROADMAP N3; production passes 0)."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        s2_ref[...] = jnp.zeros_like(s2_ref) + s_ref[0, 0]
        s1_ref[...] = jnp.zeros_like(s1_ref)

    Xb = x_ref[...]  # (B, d)
    row0 = b * Xb.shape[0]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (Xb.shape[0], 1), 0)
    # select, don't multiply: the edge block's unspecified region can be NaN
    Xb = jnp.where(rows < nv_ref[0, 0], Xb, 0.0)

    s2_ref[...] += _dot_multipass(Xb, Xb, (((0,), (0,)), ((), ())), n_split)
    s1_ref[...] += jnp.sum(Xb, axis=0)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret", "blk", "n_split"))
def _xtx_jit(X, n_valid, cse_guard, interpret: bool, blk: int, n_split: int):
    n, d = X.shape
    s2, s1 = pl.pallas_call(
        functools.partial(_xtx_kernel, n_split),
        # the trace names the call by this; `gram_roofline` finds it by it
        # (cellbench/metrics/gram_roofline.json)
        name="_xtx_jit",
        grid=((n + blk - 1) // blk,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
            pl.BlockSpec((blk, d), lambda b: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, d), lambda b: (0, 0)),
            pl.BlockSpec((1, d), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(n_valid, jnp.int32).reshape(1, 1),
        jnp.asarray(cse_guard, jnp.float32).reshape(1, 1),
        X,
    )
    return s2, s1[0]


def xtx_pallas(
    X: jax.Array,
    n_valid,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
    interpret: bool = False,
    blk: int | None = None,
    cse_guard=0.0,
):
    """Single-device fused (XᵀX, colsum) over the first `n_valid` rows, one X read.
    Traceable (jit/shard_map-safe); n_valid may be a runtime scalar."""
    n_split = _N_SPLIT[precision]
    return _xtx_jit(
        X,
        n_valid,
        cse_guard,
        interpret,
        blk if blk else _block_rows(X.shape[1], n_split),
        n_split,
    )


def _xtxy_kernel(n_split, nv_ref, s_ref, x_ref, y_ref, s2_ref, s1_ref, xty_ref, ys_ref):
    """One row block of the fused NORMAL-EQUATION pass: S2 += XbᵀXb,
    s1 += colsum(Xb), xty += Xbᵀyb, ys += [Σy, Σy²] — all from one HBM read of X.

    The label enters as one LANE-DENSE (1, blk) row per grid step, NOT as the
    (blk, 1) column the module header documents as poison (3x measured slowdown)
    and NOT as a column appended to X ([X|y] would widen the X block to d+1,
    breaking 128-lane alignment and paying a second lane-tile of VMEM+DMA per
    row). XᵀY is one (1,blk)x(blk,d) MXU matmul at the same multipass-bf16
    precision as S2. Covers `gram_and_xty`'s role for unit-weight fits (the
    header's "unwirable" note predates this layout)."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        s2_ref[...] = jnp.zeros_like(s2_ref) + s_ref[0, 0]
        s1_ref[...] = jnp.zeros_like(s1_ref)
        xty_ref[...] = jnp.zeros_like(xty_ref)
        ys_ref[...] = jnp.zeros_like(ys_ref)

    Xb = x_ref[...]  # (B, d)
    B = Xb.shape[0]
    row0 = b * B
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    # select, don't multiply: the edge block's unspecified region can be NaN
    Xb = jnp.where(rows < nv_ref[0, 0], Xb, 0.0)

    yrow = y_ref[...]  # (1, B): this block's labels as one long row
    yrows = row0 + jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    yrow = jnp.where(yrows < nv_ref[0, 0], yrow, 0.0)

    s2_ref[...] += _dot_multipass(Xb, Xb, (((0,), (0,)), ((), ())), n_split)
    s1_ref[...] += jnp.sum(Xb, axis=0)[None, :]
    xty_ref[...] += _dot_multipass(yrow, Xb, (((1,), (0,)), ((), ())), n_split)
    ys_ref[...] += jnp.concatenate(
        [jnp.sum(yrow, keepdims=True), jnp.sum(yrow * yrow, keepdims=True)], axis=1
    )


@functools.partial(jax.jit, static_argnames=("interpret", "blk", "n_split"))
def _xtxy_jit(X, y, n_valid, cse_guard, interpret: bool, blk: int, n_split: int):
    n, d = X.shape
    # y rides as (n_blocks, 1, blk): one contiguous row per X row block (an
    # O(n) pad+copy of the 1-D label — ~1/d of the X read). The block's last
    # two dims EQUAL the array's, which is what Mosaic's (8, 128) block rule
    # asks of a one-row operand: the earlier (blk/128, 128) tile was refused
    # at blk=512 (d=512), where it is 4 sublanes tall.
    n_blocks = (n + blk - 1) // blk
    y3d = jnp.pad(y.astype(jnp.float32), (0, n_blocks * blk - n)).reshape(
        n_blocks, 1, blk
    )
    s2, s1, xty, ys = pl.pallas_call(
        functools.partial(_xtxy_kernel, n_split),
        name="gram_xtxy",
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
            pl.BlockSpec((blk, d), lambda b: (b, 0)),
            pl.BlockSpec((None, 1, blk), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, d), lambda b: (0, 0)),
            pl.BlockSpec((1, d), lambda b: (0, 0)),
            pl.BlockSpec((1, d), lambda b: (0, 0)),
            pl.BlockSpec((1, 2), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(n_valid, jnp.int32).reshape(1, 1),
        jnp.asarray(cse_guard, jnp.float32).reshape(1, 1),
        X,
        y3d,
    )
    return s2, s1[0], xty[0], ys[0, 0], ys[0, 1]


def xtxy_pallas(
    X: jax.Array,
    y: jax.Array,
    n_valid,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
    interpret: bool = False,
    blk: int | None = None,
    cse_guard=0.0,
):
    """Single-device fused (XᵀX, colsum(X), Xᵀy, Σy, Σy²) over the first
    `n_valid` rows in ONE X read. Traceable; n_valid may be a runtime scalar."""
    n_split = _N_SPLIT[precision]
    b = blk if blk else _block_rows(X.shape[1], n_split)
    b = max(128, (b // 128) * 128)  # the (1, blk) label row stays lane-aligned
    return _xtxy_jit(X, y, n_valid, cse_guard, interpret, b, n_split)


def normal_eq_prefix_mask(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    mesh=None,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
    interpret: bool = False,
    cse_guard=0.0,
):
    """Fused normal-equation sufficient statistics for UNIT-WEIGHT data under the
    repo's padding contract: returns (A=XᵀX, b=Xᵀy, x̄, ȳ, Σw, Σy²) — the tuple
    `ops/linear.py::linreg_sufficient_stats` produces, plus yᵀy (for R²/objective
    without another pass) — while reading X from HBM ONCE instead of the XLA
    path's twice (lhs and rhs stream independently; see module header).

    Same eligibility contract as `covariance_prefix_mask`: w must be a {0,1}
    prefix mask per shard (parallel/partition.py::pad_rows places padding at the
    global end). Per-sample weights use the XLA path; callers gate on
    `use_fused_gram` (ops/pca.py). Reference role: the cuML normal-equation
    Gram/XᵀY allreduce inside LinearRegressionMG.fit
    (reference python/src/spark_rapids_ml/regression.py:548-558).
    """
    if mesh is not None and mesh.devices.size > 1:
        from jax import shard_map

        from ..parallel.mesh import DATA_AXIS
        from ..parallel.partitioner import partitioner_for

        part = partitioner_for(mesh)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(part.data_spec(2), part.data_spec(1), part.data_spec(1)),
            out_specs=(
                part.state_spec(),
                part.state_spec(),
                part.state_spec(),
                part.state_spec(),
            ),
            check_vma=False,
        )
        def run(x_local, y_local, w_local):
            nv = jnp.sum(w_local.astype(jnp.int32))
            s2, s1, xty, ysum, yty = xtxy_pallas(
                x_local, y_local, nv, precision=precision, interpret=interpret,
                cse_guard=cse_guard,
            )
            return (
                jax.lax.psum(s2, DATA_AXIS),
                jax.lax.psum(s1, DATA_AXIS),
                jax.lax.psum(xty, DATA_AXIS),
                jax.lax.psum(
                    jnp.stack([ysum, yty, nv.astype(jnp.float32)]), DATA_AXIS
                ),
            )

        s2, s1, xty, packed = run(X, y, w)
        ysum, yty, wsum = packed[0], packed[1], packed[2]
    else:
        nv = jnp.sum(w.astype(jnp.int32))
        s2, s1, xty, ysum, yty = xtxy_pallas(
            X, y, nv, precision=precision, interpret=interpret, cse_guard=cse_guard
        )
        wsum = nv.astype(jnp.float32)

    xbar = s1 / wsum
    ybar = ysum / wsum
    return s2, xty, xbar, ybar, wsum, yty


def covariance_prefix_mask(
    X: jax.Array,
    w: jax.Array,
    mesh=None,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
    interpret: bool = False,
    cse_guard=0.0,
):
    """Fused covariance for UNIT-WEIGHT data under the repo's padding contract.

    Drop-in for ops/linalg.py::weighted_covariance — same (cov, mean, wsum) with the
    unbiased (Σw - 1) denominator — REQUIRING w to be a {0,1} mask whose zeros form a
    suffix of each shard (what parallel/partition.py::pad_rows produces: padding sits
    at the global end, so only the last shard has a zero suffix). Per-sample weights
    or non-suffix masks must use the XLA path; callers gate on that (models/feature.py).
    n_valid per shard is sum(w_local) — an O(n) read of w, ~1% of the X read.

    HOST wrapper: resolves the Partitioner-owned specs for a multi-device mesh
    and hands them, static, to the `pca.cov_pallas` compiled kernel — like the
    XLA pass it replaces, the per-shard kernel + psum + mean correction is ONE
    compiled program, so the fit report names it
    (`device.kernel_calls{kernel=pca.cov_pallas}`, with `interpret=` in the
    recorded signature) and the comm plane counts its all-reduce.
    """
    specs = None
    if mesh is not None and mesh.devices.size > 1:
        from ..parallel.partitioner import partitioner_for

        part = partitioner_for(mesh)
        specs = (part.data_spec(2), part.data_spec(1), part.state_spec())
    else:
        mesh = None
    return _covariance_prefix_mask(
        X, w, cse_guard, mesh, specs, precision, interpret
    )


@compiled_kernel("pca.cov_pallas",
                 static_argnames=("mesh", "specs", "precision", "interpret"))
def _covariance_prefix_mask(X, w, cse_guard, mesh, specs, precision, interpret):
    if mesh is not None:
        from jax import shard_map

        from ..parallel.mesh import DATA_AXIS

        x_spec, w_spec, state_spec = specs

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(x_spec, w_spec),
            out_specs=(state_spec, state_spec, state_spec),
            check_vma=False,
        )
        def run(x_local, w_local):
            nv = jnp.sum(w_local.astype(jnp.int32))
            s2, s1 = xtx_pallas(
                x_local, nv, precision=precision, interpret=interpret,
                cse_guard=cse_guard,
            )
            return (
                jax.lax.psum(s2, DATA_AXIS),
                jax.lax.psum(s1, DATA_AXIS),
                jax.lax.psum(nv.astype(jnp.float32), DATA_AXIS),
            )

        s2, s1, wsum = run(X, w)
    else:
        nv = jnp.sum(w.astype(jnp.int32))
        s2, s1 = xtx_pallas(
            X, nv, precision=precision, interpret=interpret, cse_guard=cse_guard
        )
        wsum = nv.astype(jnp.float32)

    mean = s1 / wsum
    cov = (s2 - wsum * jnp.outer(mean, mean)) / (wsum - 1.0)
    return cov, mean, wsum
