#
# Pallas TPU kernel: the binary logistic data term's value AND gradient in ONE
# streaming read of X.
#
# The quasi-Newton LogisticRegression fit (ops/logistic.py::_qn_fit, the TPU
# replacement for cuML's LogisticRegressionMG, reference classification.py:989-1052)
# evaluates  S(β, b) = Σᵢ wᵢ (softplus(zᵢ) − yᵢ zᵢ),  z = Xβ + b,  and its gradient
# about 207 times a fit. Through autodiff that is two XLA fusions an evaluation,
# the logits Xβ and the gradient Xᵀr, each a float32 multiply-and-reduce on the
# vector unit that reads the whole table at 91 % of the HBM peak (one v5e,
# 357,376 x 3000 f32, 5.73 ms a pass: PERF.md §5): neither can be made faster, but
# the table crosses the HBM bus twice where the algorithm needs it once.
#
# Here a block of X is read once. While it is resident in VMEM the kernel forms
# its logits, its share of S, the residual r = w (σ(z) − y), and accumulates
# g += Xᵀr and g_b += Σ r. `binomial_data_term` wraps that as a function with
# its own derivative rule (`jax.custom_vjp`): the forward pass is the sweep, the
# rule's residual is (g, g_b), the backward pass a scalar times a (d+1,) vector
# that reads nothing.
#
# Shape of the kernel (measured 2026-10-03, one v5e, 357,376 x 3000 f32: 5.69 to
# 5.77 ms an evaluation at 256 to 2048 samples a block, 744 to 753 GB/s, where
# the two XLA passes take 11.34 ms; PERF.md §6, PR 35):
#
#   * FEATURE-MAJOR blocks. The TPU runtime places a table whose width is no
#     multiple of 128 (upstream's benchmark table has 3000 columns) COLUMN-major,
#     tiled (8, 128) with no padding: 128 samples of 8 features a tile. The
#     kernel takes the transposed view Xᵀ (d, n), which is a bitcast of that
#     table and no copy, in blocks (d, blk): every feature, blk samples along
#     the lanes. A kernel over row blocks of X ran as fast (5.81 ms) but made
#     the compiler copy the table to row-major first: 4.39 GB more in HBM.
#     `eval_gate` therefore keeps a row-major table (a width that IS a multiple
#     of 128) on the two XLA passes: reason `layout`.
#   * Samples along lanes make every per-sample number a lane-dense row: y and
#     w arrive as (1, blk) rows (no (blk, 1) column operand, the layout
#     ops/pallas_xtwx.py documents as poison), z is a multiply by β (one value
#     a sublane, broadcast along lanes outside the kernel: 1.5 MB) and a sum
#     over sublanes, and Xᵀr a multiply by the r row and plain vreg adds into a
#     (d, 128) accumulator that lives in the output block across the grid (its
#     lanes are summed once, outside). No transpose, no cross-lane reduction
#     inside the sweep; both products stay on the vector unit in float32, as
#     the compiler's own passes do (the MXU at N=1 would take 51 M cycles a pass:
#     PERF.md §6, PR 34). The width need be no multiple of 8 or 128: a block's
#     first dimension is the array's own.
#   * `_eval_block_rows` sizes blk from d (512 samples, 6.1 MB of X a block, at
#     d=3000: 12.3 MB double-buffered, so the call raises `vmem_limit_bytes`
#     above the 16 MiB default scope; v5e has 128 MiB of VMEM). The grid is
#     sequential (v5e: one TensorCore).
#   * The grid covers the whole blocks only. Samples past the last whole block
#     (fewer than blk; none at 357,376 = 698 x 512) take the plain two-pass
#     expressions in `_sums_xla`, so the kernel masks nothing and reads no
#     out-of-range block.
#
# Single-device pallas_call; X row-sharded over a mesh runs the kernel per shard
# under shard_map and sums the packed (d+2,) partials in ONE psum (the pattern of
# ops/pallas_xtwx.py).
#

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..autotune.defaults import (
    LOGISTIC_EVAL_BLOCK_BYTES,
    LOGISTIC_EVAL_MAX_BLOCK_ROWS,
    LOGISTIC_EVAL_MIN_BLOCK_ROWS,
)
from .pallas_kmeans import _round_up

_LANES = 128  # samples a step inside a block: one lane tile


def _eval_block_rows(d: int, n: Optional[int] = None) -> int:
    """Samples of one block at width `d`: the largest power of two whose block
    stays inside `LOGISTIC_EVAL_BLOCK_BYTES` (512 at d=3000, 4096 up to 512
    columns) and, for a table of `n` samples, inside the table; 0 when not even
    `LOGISTIC_EVAL_MIN_BLOCK_ROWS` do (the gate's `cols`)."""
    rows = LOGISTIC_EVAL_BLOCK_BYTES // (_round_up(d, 8) * 4)
    if rows < LOGISTIC_EVAL_MIN_BLOCK_ROWS:
        return 0
    if n is not None:
        rows = min(rows, max(n, LOGISTIC_EVAL_MIN_BLOCK_ROWS))
    return min(LOGISTIC_EVAL_MAX_BLOCK_ROWS, 1 << (rows.bit_length() - 1))


def _vmem_limit_bytes(blk: int, d: int) -> int:
    """The call's scoped-VMEM limit: the two pipelined X blocks, β and the
    accumulator (double-buffered by the pipeline too) and a step's two
    (d, 128) products, and room for Mosaic's own scratch."""
    tile = _round_up(d, 8) * _LANES * 4
    return 2 * (blk // _LANES) * tile + 8 * tile + (8 << 20)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _features_major(X) -> bool:
    """Whether the placed table's transposed view is free: a tiled layout (the
    TPU's) whose minor dimension is the samples. An untiled one (the CPU's,
    where the kernel runs interpreted) takes any view."""
    layout = X.format.layout
    return not layout.tiling or tuple(layout.major_to_minor) == (1, 0)


def eval_gate(X, multinomial: bool) -> Tuple[bool, str]:
    """Whether the one-read kernel carries this fit's evaluations, and which
    test decided it, from what the input shows: `multinomial` (its (n, k) logits
    ride the MXU: another kernel), `dtype` (float32 only: a float64 fit keeps
    the passes it asked for), `cols` (no 256-sample block of this width fits
    the kernel's VMEM budget), `platform` (Mosaic lowers for a TPU only),
    `layout` (the runtime placed the table row-major, as it does a width that
    is a multiple of 128: the kernel's view of it would be a second table in
    HBM). The first that fails names the reason; a fused fit has passed
    `layout`."""
    if multinomial:
        return False, "multinomial"
    if jnp.dtype(X.dtype) != jnp.float32:
        return False, "dtype"
    if _eval_block_rows(X.shape[1]) == 0:
        return False, "cols"
    if not _on_tpu():
        return False, "platform"
    return _features_major(X), "layout"


def eval_plan(X) -> Tuple[Optional[object], Optional[tuple], bool]:
    """The static description `binomial_data_term` runs by, read off a placed
    table on the host: its mesh and the Partitioner-owned specs (rows, vectors,
    state) when its rows are sharded over more than one device, and the
    interpreter off a TPU (Mosaic lowers for no other backend: tests that force
    the gate reach the kernel this way)."""
    from ..parallel.partitioner import mesh_of, partitioner_for

    interpret = jax.devices()[0].platform != "tpu"
    mesh = mesh_of(X)
    if mesh is None or mesh.devices.size == 1:
        return None, None, interpret
    part = partitioner_for(mesh)
    return mesh, (part.data_spec(2), part.data_spec(1), part.state_spec()), interpret


def _softplus_sigmoid(z):
    """(softplus(z), σ(z)) from one exp: stable at both ends."""
    e = jnp.exp(-jnp.abs(z))
    return jnp.maximum(z, 0.0) + jnp.log1p(e), jnp.where(z >= 0.0, 1.0, e) / (1.0 + e)


def _eval_kernel(b_ref, beta_ref, x_ref, y_ref, w_ref, g_ref, s_ref):
    """One block of Xᵀ, (d, blk): g += x · r (lanes unsummed), s += the
    samples' [value terms; residuals] as lane vectors."""

    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    b = b_ref[0, 0]

    def step(t, carry):
        cols = pl.ds(pl.multiple_of(t * _LANES, _LANES), _LANES)
        x = x_ref[:, cols]  # (d, 128)
        z = jnp.sum(x * beta_ref[...], axis=0, keepdims=True) + b  # (1, 128)
        y = y_ref[:, cols]
        w = w_ref[:, cols]
        sp, sig = _softplus_sigmoid(z)
        r = w * (sig - y)
        s_ref[0:1, :] += w * (sp - y * z)
        s_ref[1:2, :] += r
        g_ref[...] += x * r
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // _LANES, step, 0)


def _sums_pallas(X, y, w, beta, b, blk: int, interpret: bool):
    """The kernel over the first `n // blk` whole blocks: (value, g, g_b) of
    those samples, unnormalized."""
    n, d = X.shape
    f32 = jnp.float32
    g, s = pl.pallas_call(
        _eval_kernel,
        name="logistic_eval",
        grid=(n // blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((d, _LANES), lambda i: (0, 0)),
            pl.BlockSpec((d, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((d, _LANES), lambda i: (0, 0)),
            pl.BlockSpec((2, _LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, _LANES), f32),
            jax.ShapeDtypeStruct((2, _LANES), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit_bytes(blk, d),
        ),
        interpret=interpret,
    )(
        jnp.asarray(b, f32).reshape(1, 1),
        jnp.broadcast_to(beta.astype(f32)[:, None], (d, _LANES)),
        X.T,
        y.astype(f32).reshape(1, n),
        w.astype(f32).reshape(1, n),
    )
    sums = jnp.sum(s, axis=1)
    return sums[0], jnp.sum(g, axis=1), sums[1]


def _sums_xla(X, y, w, beta, b):
    """The same three sums in plain jnp (two reads of these samples): those
    past the last whole block."""
    z = jnp.sum(X * beta, axis=1) + b
    sp, sig = _softplus_sigmoid(z)
    r = w * (sig - y)
    return jnp.sum(w * (sp - y * z)), jnp.sum(X * r[:, None], axis=0), jnp.sum(r)


def _local_sums(X, y, w, beta, b, interpret: bool):
    """One shard's sums packed as a (d+2,) vector [g, g_b, value]."""
    n = X.shape[0]
    blk = _eval_block_rows(X.shape[1], n)
    rows = (n // blk) * blk
    parts = []
    if rows:
        parts.append(_sums_pallas(X, y, w, beta, b, blk, interpret))
    if rows < n:
        parts.append(_sums_xla(X[rows:], y[rows:], w[rows:], beta, b))
    val, g, gb = (sum(p) for p in zip(*parts))
    return jnp.concatenate([g, jnp.stack([gb, val])])


def _eval_sums(plan, X, y, w, beta, b):
    mesh, specs, interpret = plan
    local = functools.partial(_local_sums, interpret=interpret)
    if mesh is None:
        packed = local(X, y, w, beta, b)
    else:
        from jax import shard_map

        from ..parallel.mesh import DATA_AXIS

        x_spec, vec_spec, state_spec = specs

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(x_spec, vec_spec, vec_spec, state_spec, state_spec),
            out_specs=state_spec,
            check_vma=False,
        )
        def run(x_local, y_local, w_local, beta, b):
            return jax.lax.psum(local(x_local, y_local, w_local, beta, b), DATA_AXIS)

        packed = run(X, y, w, beta, b)
    return packed[-1], packed[:-2], packed[-2]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def binomial_data_term(plan, X, y, w, beta, b):
    """Σᵢ wᵢ (softplus(zᵢ) − yᵢ zᵢ), z = Xβ + b, in one read of X; its
    derivative rule hands back the gradient the same sweep accumulated.
    Differentiable in `beta` (d,) and `b` (scalar) only. `plan` (static) is
    `eval_plan`'s. Traceable: it sits inside the compiled fit."""
    return _eval_sums(plan, X, y, w, beta, b)[0]


def _data_term_fwd(plan, X, y, w, beta, b):
    val, g, gb = _eval_sums(plan, X, y, w, beta, b)
    return val, (g, gb)


def _data_term_bwd(plan, res, ct):
    g, gb = res
    return None, None, None, ct * g, ct * gb


binomial_data_term.defvjp(_data_term_fwd, _data_term_bwd)
