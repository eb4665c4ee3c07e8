#
# Shared distributed linear-algebra kernels (L1).
#
# These replace the reference's cuML sufficient-statistics machinery: weighted moments
# and Gram/covariance accumulation with the allreduce that cuML MG runs over NCCL
# (e.g. PCAMG covariance, reference feature.py:228-253; distributed standardization via
# allGather-sum, reference utils.py:876-982). Here the inputs are row-sharded jax arrays
# and XLA inserts the psum over the mesh when the contraction crosses the sharded axis —
# the matmuls land on the MXU, the reduction rides ICI.
#
# All kernels are weight-aware: `w` is the {0,1} padding mask times any sample weight
# (parallel/partition.py), so padded rows contribute nothing.
#

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..autotune.defaults import GRAM_BLOCK_COLS, GRAM_TRIANGLE_MIN_COLS
from ..observability.device import compiled_kernel
from ._precision import pdot


@compiled_kernel("linalg.weighted_mean")
def weighted_mean(X: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Returns (mean, wsum). One pass; psum over the data axis is implicit."""
    wsum = jnp.sum(w)
    mean = pdot(w, X) / wsum
    return mean, wsum


@compiled_kernel("linalg.weighted_moments")
def weighted_moments(X: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (mean, var, wsum) with the unbiased (wsum-1) variance denominator,
    matching Spark's Summarizer semantics used by the reference's standardization
    (utils.py:876-982)."""
    wsum = jnp.sum(w)
    mean = pdot(w, X) / wsum
    sq = pdot(w, X * X)
    var = (sq - wsum * mean * mean) / (wsum - 1.0)
    return mean, jnp.maximum(var, 0.0), wsum


@compiled_kernel("scaler.transform")
def scaler_transform(X: jax.Array, shift: jax.Array, scale: jax.Array) -> jax.Array:
    """StandardScalerModel's column standardization. Bit-parity contract with the
    fused pipeline's "scale" chain op (ops/streaming.py::_apply_chain): identical
    expression, identical cast discipline — the staged transform->refit path and
    the fused featurize->fit chain must agree BITWISE (docs/design.md §6k)."""
    return (X.astype(shift.dtype) - shift) / scale


def kahan_add(acc, comp, term):
    """One compensated-summation step: returns (acc', comp') with the low-order
    bits the naive add would drop carried in `comp`. Accumulation error stays
    O(1) ulps over ANY number of terms instead of growing with their count (the
    streamed tier's float32 device accumulation then matches the float64 HOST
    accumulation it replaced: the terms were always float32, only their sum
    ever benefited from float64). XLA does not reassociate IEEE float ops, so
    the cancellation survives jit."""
    y = term - comp
    t = acc + y
    return t, (t - acc) - y


# Rows of one partial Gram matrix in `weighted_covariance`. On the chip a
# float32 matmul at HIGHEST is six bf16 passes summed into one float32
# accumulator along the contraction, and the small passes (hi·lo, mid·mid) stop
# registering once that accumulator is large: over 357,376 rows the diagonal
# read 2.3e-5 low, always low, which is further off than the three passes of
# HIGH (1.1e-5). Over 4,096 rows the loss is 1e-7 and over 16,384 already 1e-6
# (PERF.md §6, PR 32), so the partial sums stay this short and are added up
# outside the matmul.
GRAM_CHUNK_ROWS = 4096


def gram_column_blocks(d: int) -> Tuple[Tuple[int, int], ...]:
    """The column ranges `_centered_gram` cuts a Gram matrix of `d` columns
    into: one range, the whole matrix in a single matmul, under
    `GRAM_TRIANGLE_MIN_COLS`; from there on blocks of `GRAM_BLOCK_COLS`, the
    last one what is left, of which only the pairs (I, J) with I <= J are
    multiplied. A fit counts the form from this same answer
    (`ops/pca.py::covariance_for_fit`)."""
    if d < GRAM_TRIANGLE_MIN_COLS:
        return ((0, d),)
    return tuple((lo, min(lo + GRAM_BLOCK_COLS, d)) for lo in range(0, d, GRAM_BLOCK_COLS))


def _upper_gram_panels(X: jax.Array, w: jax.Array, mean: jax.Array, blocks):
    """Σ w_i (x_i-μ)(x_i-μ)ᵀ over the rows held here, as one panel a column
    block I: its rows of the matrix from the block's first column to the last
    column, which are the block pairs (I, J) with I <= J. One matmul per panel
    and `GRAM_CHUNK_ROWS` rows, the partial sums added with a compensated
    (Kahan) sum, so that neither the matmul's accumulator nor the sum of the
    parts loses what float32 holds. A table shorter than one chunk is one
    part; a single block's panel is the whole matrix."""
    n = X.shape[0]
    chunk = GRAM_CHUNK_ROWS

    def gram(xs, ws):
        xs = xs - mean[None, :]
        xw = xs * ws[:, None]
        return [pdot(xw[:, lo:hi].T, xs[:, lo:]) for lo, hi in blocks]

    if n <= chunk:
        return gram(X, w)

    def add(carry, terms):
        accs, comps = zip(*(kahan_add(a, c, t) for a, c, t in zip(*carry, terms)))
        return list(accs), list(comps)

    def body(i, carry):
        xs = jax.lax.dynamic_slice_in_dim(X, i * chunk, chunk, 0)
        ws = jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk, 0)
        return add(carry, gram(xs, ws))

    zeros = [jnp.zeros((hi - lo, X.shape[1] - lo), X.dtype) for lo, hi in blocks]
    full = n // chunk
    carry = jax.lax.fori_loop(0, full, body, (zeros, zeros))
    if n % chunk:
        carry = add(carry, gram(X[full * chunk:], w[full * chunk:]))
    return carry[0]


def _mirror_upper_panels(panels, blocks) -> jax.Array:
    """The whole symmetric matrix from its upper panels: every entry above
    the diagonal is copied below it, once. A single block's panel is the
    matrix as the one matmul gave it."""
    if len(blocks) == 1:
        return panels[0]
    # what is padded in lies below the diagonal and is never read
    G = jnp.concatenate(
        [jnp.pad(panel, ((0, 0), (lo, 0))) for panel, (lo, _) in zip(panels, blocks)], axis=0)
    return jnp.where(jnp.tri(G.shape[0], k=-1, dtype=bool), G.T, G)


def _centered_gram(X: jax.Array, w: jax.Array, mean: jax.Array, axis=None) -> jax.Array:
    """Σ w_i (x_i-μ)(x_i-μ)ᵀ, symmetric to the bit from `GRAM_TRIANGLE_MIN_COLS`
    columns on, where only its upper column blocks are computed: `w` is one
    scalar a row, so block (J, I) is the transpose of block (I, J) and a second
    matmul would only compute the same numbers again. With `axis` (inside a
    `shard_map`) the shards' upper panels are added by one psum before the
    mirror."""
    blocks = gram_column_blocks(X.shape[1])
    panels = _upper_gram_panels(X, w, mean, blocks)
    if axis is not None:
        panels = jax.lax.psum(panels, axis)
    return _mirror_upper_panels(panels, blocks)


@compiled_kernel("linalg.weighted_covariance", static_argnames=("mesh",))
def weighted_covariance(
    X: jax.Array, w: jax.Array, mesh=None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Centered covariance C = Σ w_i (x_i-μ)(x_i-μ)ᵀ / (Σw - 1): the weighted
    mean first, then the Gram matrix of the centred rows in short partial sums
    (`_centered_gram`). With a `mesh` of several devices each shard sums its own
    rows and one psum adds the shards; without one (a single device, a mesh
    that also splits the columns, or a caller that leaves the partitioning to
    XLA) the rows are taken as they come."""
    from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS

    wsum = jnp.sum(w)
    mean = pdot(w, X) / wsum
    if (mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1
            and mesh.shape.get(FEATURE_AXIS, 1) == 1):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        S2 = shard_map(
            lambda x, ws, m: _centered_gram(x, ws, m, axis=DATA_AXIS),
            mesh=mesh, in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P()),
            out_specs=P(), check_vma=False,
        )(X, w, mean)
    else:
        S2 = _centered_gram(X, w, mean)
    return S2 / (wsum - 1.0), mean, wsum


@compiled_kernel("linalg.gram_and_xty")
def gram_and_xty(
    X: jax.Array, y: jax.Array, w: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Normal-equation sufficient statistics: (XᵀWX, XᵀWy, Σw) in one sharded pass —
    the TPU form of the reference's LinearRegressionMG/RidgeMG allreduce."""
    Xw = X * w[:, None]
    return pdot(Xw.T, X), pdot(Xw.T, y), jnp.sum(w)


def power_iteration_lmax(G: jax.Array, n_steps: int = 16) -> jax.Array:
    """Largest eigenvalue of a symmetric PSD matrix via power iteration — used for
    FISTA Lipschitz constants in ops/linear.py and ops/logistic.py."""

    def body(i, v):
        v = pdot(G, v)
        return v / (jnp.linalg.norm(v) + 1e-30)

    d = G.shape[0]
    v = jax.lax.fori_loop(0, n_steps, body, jnp.ones((d,), G.dtype) / jnp.sqrt(d))
    return jnp.dot(v, pdot(G, v))


def standardize_columns(
    X: jax.Array, w: jax.Array, with_mean: bool = True
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Return (X_standardized, mean, scale): the reference's distributed
    standardization workaround (classification.py:1018-1028, utils.py:876-982) as a
    sharded kernel. Columns with zero variance get scale 1 to avoid division blowup.
    Padded rows are standardized too (they are masked at use sites via w)."""
    mean, var, _ = weighted_moments(X, w)
    scale = jnp.sqrt(var)
    scale = jnp.where(scale <= 0.0, 1.0, scale)
    if with_mean:
        Xs = (X - mean) / scale
    else:
        Xs = X / scale
    return Xs, mean, scale
