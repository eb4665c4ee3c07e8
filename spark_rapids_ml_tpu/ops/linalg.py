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

from ..defaults import GRAM_BLOCK_COLS, GRAM_TRIANGLE_MIN_COLS
from ..observability import counter_inc
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


def _gram_terms(xs, ws, mean, blocks, label=None, sums=False):
    """One row block's terms: Σ w_i (x_i-μ)(x_i-μ)ᵀ as one panel a column
    block I, its rows of the matrix from the block's first column to the last
    column, which are the block pairs (I, J) with I <= J (a single block's
    panel is the whole matrix). With `label` = (y, ȳ), y the block's labels,
    then Σ w_i (x_i-μ)(y_i-ȳ) (one matrix-vector product), Σ w_i (x_i-μ) and
    Σ w_i (y_i-ȳ); with `sums` and no label Σ w_i (x_i-μ) alone."""
    xs = xs - mean[None, :]
    xw = xs * ws[:, None]
    terms = [pdot(xw[:, lo:hi].T, xs[:, lo:]) for lo, hi in blocks]
    if label is not None:
        yc = label[0] - label[1]
        # xwᵀ(y - ȳ), not (y - ȳ)ᵀxw: on the CPU the matrix-vector form
        # loses seven times less over a part
        terms += [pdot(xw.T, yc), jnp.sum(xw, axis=0), jnp.sum(yc * ws)]
    elif sums:
        terms.append(jnp.sum(xw, axis=0))
    return terms


def _gram_zeros(d: int, blocks, dtype, label: bool = False, sums: bool = False):
    """The compensated carry (accumulators, compensations) of `gram_step`'s
    terms, all zero."""
    zeros = [jnp.zeros((hi - lo, d - lo), dtype) for lo, hi in blocks]
    if label:
        zeros += [jnp.zeros((d,), dtype)] * 2 + [jnp.zeros((), dtype)]
    elif sums:
        zeros.append(jnp.zeros((d,), dtype))
    return zeros, zeros


def gram_step(carry, xs, ws, mean, blocks, label=None, sums=False):
    """The step over one row block: its terms (`_gram_terms`) added into the
    compensated carry, each with `kahan_add`, so that neither the matmul's
    accumulator nor the sum of the blocks loses what float32 holds. Both
    loops take it: `accumulate_gram` over an array on the device, and
    `fold_part` over an upload's chunks as they land."""
    terms = _gram_terms(xs, ws, mean, blocks, label, sums)
    accs, comps = zip(*(kahan_add(a, c, t) for a, c, t in zip(*carry, terms)))
    return list(accs), list(comps)


def accumulate_gram(carry, X, w, mean, blocks, label=None, sums=False):
    """`carry` with the rows of `X` added, `GRAM_CHUNK_ROWS` rows a
    `gram_step` and then what is left. `label` = (y, ȳ) with y the labels of
    all of X's rows."""
    n, chunk = X.shape[0], GRAM_CHUNK_ROWS
    y = None if label is None else label[0]

    def rows(a, lo, size):
        return None if a is None else jax.lax.dynamic_slice_in_dim(a, lo, size, 0)

    def step(carry, xs, ws, ys):
        return gram_step(carry, xs, ws, mean, blocks,
                         None if ys is None else (ys, label[1]), sums)

    def body(i, carry):
        return step(carry, rows(X, i * chunk, chunk), rows(w, i * chunk, chunk),
                    rows(y, i * chunk, chunk))

    full = n // chunk
    if full:
        carry = jax.lax.fori_loop(0, full, body, carry)
    if n % chunk:
        rest = slice(full * chunk, None)
        carry = step(carry, X[rest], w[rest], None if y is None else y[rest])
    return carry


def _upper_gram_panels(X: jax.Array, w: jax.Array, mean: jax.Array, blocks, label=None):
    """Σ w_i (x_i-μ)(x_i-μ)ᵀ over the rows held here, as one panel a column
    block I (`_gram_terms`): one matmul per panel and `GRAM_CHUNK_ROWS` rows,
    the partial sums added with a compensated (Kahan) sum
    (`accumulate_gram`). A table shorter than one chunk is one part.

    With `label` = (y, ȳ) the same parts also form, under the same
    compensated sum and returned after the panels in this order,
    Σ w_i (x_i-μ)(y_i-ȳ), Σ w_i (x_i-μ) and Σ w_i (y_i-ȳ): the normal
    equations' right-hand side and the sums that put the centre where the
    rows' weighted mean is, whatever float32 lost of μ and ȳ, ride the loop
    that reads X for their Gram. Without it the program is the Gram's alone."""
    if X.shape[0] <= GRAM_CHUNK_ROWS:
        return _gram_terms(X, w, mean, blocks, label)
    carry = _gram_zeros(X.shape[1], blocks, X.dtype, label is not None)
    return accumulate_gram(carry, X, w, mean, blocks, label)[0]


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


def _centered_gram(X: jax.Array, w: jax.Array, mean: jax.Array, axis=None, label=None):
    """Σ w_i (x_i-μ)(x_i-μ)ᵀ, symmetric to the bit from `GRAM_TRIANGLE_MIN_COLS`
    columns on, where only its upper column blocks are computed: `w` is one
    scalar a row, so block (J, I) is the transpose of block (I, J) and a second
    matmul would only compute the same numbers again. With `axis` (inside a
    `shard_map`) the shards' upper panels are added by one psum before the
    mirror. With `label` = (y, ȳ) it returns that matrix followed by the three
    sums `_upper_gram_panels` forms beside it, added by the same psum."""
    blocks = gram_column_blocks(X.shape[1])
    panels = _upper_gram_panels(X, w, mean, blocks, label)
    if axis is not None:
        panels = jax.lax.psum(panels, axis)
    if label is None:
        return _mirror_upper_panels(panels, blocks)
    return (_mirror_upper_panels(panels[:len(blocks)], blocks), *panels[len(blocks):])


def centered_normal_equations(X: jax.Array, y: jax.Array, w: jax.Array, mean: jax.Array,
                              ybar: jax.Array, axis=None):
    """(Σ w(x-μ)(x-μ)ᵀ, Σ w(x-μ)(y-ȳ), Σ w(x-μ), Σ w(y-ȳ)) about a centre (μ, ȳ)
    near the weighted means: the normal equations' statistics, PCA's Gram with
    the rest riding its loop."""
    return _centered_gram(X, w, mean, axis, label=(y, ybar))


def rows_split(mesh) -> bool:
    """Whether a `mesh` splits the rows over several devices and the columns
    not at all: the one layout a pass sums shard by shard under `shard_map`.
    Otherwise (a single device, a mesh that also splits the columns, or a
    caller that leaves the partitioning to XLA) the rows are taken as they
    come."""
    from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS

    return (mesh is not None and mesh.shape.get(DATA_AXIS, 1) > 1
            and mesh.shape.get(FEATURE_AXIS, 1) == 1)


@compiled_kernel("linalg.weighted_covariance", static_argnames=("mesh",))
def weighted_covariance(
    X: jax.Array, w: jax.Array, mesh=None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Centered covariance C = Σ w_i (x_i-μ)(x_i-μ)ᵀ / (Σw - 1): the weighted
    mean first, then the Gram matrix of the centred rows in short partial sums
    (`_centered_gram`). With a `mesh` that splits the rows alone (`rows_split`)
    each shard sums its own rows and one psum adds the shards."""
    wsum = jnp.sum(w)
    mean = pdot(w, X) / wsum
    if rows_split(mesh):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS

        S2 = shard_map(
            lambda x, ws, m: _centered_gram(x, ws, m, axis=DATA_AXIS),
            mesh=mesh, in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P()),
            out_specs=P(), check_vma=False,
        )(X, w, mean)
    else:
        S2 = _centered_gram(X, w, mean)
    return S2 / (wsum - 1.0), mean, wsum


def recentre(S, sx, wsum, centre, r=None, sy=None, ycentre=None):
    """Moments summed about a `centre` moved to the rows' weighted mean: with
    δ = Σw(x-c)/Σw (`sx` over `wsum`) the mean is c + δ and the centred Gram
    S - Σw·δδᵀ. With the label's sums, r = Σw(x-c)(y-c_y) and `sy` =
    Σw(y-c_y), also r - Σw·δ·δ_y and ȳ = c_y + δ_y. Returns (S, x̄) or
    (S, r, x̄, ȳ). The one correction of every centred pass: the resident
    normal equations' (a centre float32 made of the whole table, δ what it
    lost) and the upload's fold (the first chunk's mean, `fold_finish`)."""
    dx = sx / wsum
    S = S - wsum * jnp.outer(dx, dx)
    if r is None:
        return S, centre + dx
    dy = sy / wsum
    return S, r - wsum * dx * dy, centre + dx, ycentre + dy


def fold_part(state, chunk: jax.Array, w: jax.Array, y=None):
    """One chunk of an upload added into the centred Gram: the step of
    `accumulate_gram` over the chunk's rows (cut into `GRAM_CHUNK_ROWS` parts
    inside the program where it is longer), its weights and labels the rows
    of `w` and `y` from the chunk's first row on. Every chunk is centred on
    the FIRST chunk's weighted mean (its plain mean where its weights sum to
    0) and the centred rows are summed beside the Gram, so `fold_finish` can
    move the centre to the whole table's mean by one correction (`recentre`).
    `state` is None for the first chunk, then (carry, next row, centre)."""
    rows, d = chunk.shape
    blocks = gram_column_blocks(d)
    if y is not None:
        y = y.astype(chunk.dtype)
    if state is None:
        ws = w[:rows]
        w0 = jnp.sum(ws)
        centre = (jnp.where(w0 > 0, pdot(ws, chunk) / w0, jnp.mean(chunk, axis=0)),)
        if y is not None:
            ys = y[:rows]
            centre += (jnp.where(w0 > 0, jnp.sum(ws * ys) / w0, jnp.mean(ys)),)
        state = (_gram_zeros(d, blocks, chunk.dtype, y is not None, True),
                 jnp.zeros((), jnp.int32), centre)
    carry, start, centre = state
    ws = jax.lax.dynamic_slice_in_dim(w, start, rows)
    label = None
    if y is not None:
        label = (jax.lax.dynamic_slice_in_dim(y, start, rows), centre[1])
    carry = accumulate_gram(carry, chunk, ws, centre[0], blocks, label, sums=True)
    return carry, start + rows, centre


def fold_finish(state, w: jax.Array):
    """The folded sums made the statistics: the upper panels mirrored and the
    centre moved to the weighted mean (`recentre`). Returns (S, x̄, Σw), or
    (S, r, x̄, ȳ, Σw) where the fold summed a label."""
    (sums, _), _, centre = state
    blocks = gram_column_blocks(centre[0].shape[0])
    S = _mirror_upper_panels(sums[:len(blocks)], blocks)
    wsum = jnp.sum(w)
    if len(centre) == 1:
        return (*recentre(S, sums[len(blocks)], wsum, centre[0]), wsum)
    r, sx, sy = sums[len(blocks):]
    return (*recentre(S, sx, wsum, centre[0], r, sy, centre[1]), wsum)


@compiled_kernel("linalg.weighted_covariance_part", donate_argnums=(0,))
def weighted_covariance_part(state, chunk: jax.Array, w: jax.Array):
    """PCA's fold step (`fold_part`) under the covariance's program name."""
    return fold_part(state, chunk, w)


@compiled_kernel("linalg.weighted_covariance_finish")
def weighted_covariance_finish(state, w: jax.Array):
    """(C, x̄, Σw) of the folded sums, as `weighted_covariance` returns them."""
    S, mean, wsum = fold_finish(state, w)
    return S / (wsum - 1.0), mean, wsum


class GramFold:
    """The consumer of an upload's row chunks (`parallel/partitioner.py::
    _put_chunked`) for a fit whose one pass over the table is the centred
    Gram: each placed chunk is dispatched to `part` (`fold_part` under the
    producer's program name) as it lands, so the device sums the Gram while
    later chunks are still in flight, and `close` dispatches `finish`
    (`fold_finish`). Nothing is waited for here."""

    def __init__(self, form: str, part, finish, w: jax.Array, y=None):
        self.form = form
        self._part, self._finish, self._w = part, finish, w
        self._operands = (w,) if y is None else (w, y)
        self._state = None
        self.parts = 0

    def __call__(self, chunk: jax.Array) -> None:
        self._state = self._part(self._state, chunk, *self._operands)
        self.parts += 1

    def close(self):
        """The statistics (`finish`'s, on the device) where the table went up
        in chunks, every one of them folded; None where it went up in one put,
        which the fit's own pass then reads. Counts `h2d.fold{engaged=,reason=}`
        once: `ok`, or `devices` (a placement over several devices is one
        sharded put) or `unchunked` (the chunk gate kept one put on one
        device), and `h2d.fold_parts`, the parts dispatched."""
        if self.parts:
            reason = "ok"
        elif len(self._w.sharding.device_set) > 1:
            reason = "devices"
        else:
            reason = "unchunked"
        _count_fold(self.form, reason)
        if reason != "ok":
            return None
        counter_inc("h2d.fold_parts", self.parts, site="fit")
        return self._finish(self._state, self._w)


def _count_fold(form: str, reason: str) -> None:
    counter_inc("h2d.fold", 1, site="fit", form=form,
                engaged="true" if reason == "ok" else "false", reason=reason)


def open_fold(form: str, fused: bool, part, finish, w: jax.Array, y=None):
    """A `GramFold` for a fit whose producer is the XLA centred pass; None
    where it is the fused Pallas Gram (`ops/pca.py::gram_gate`), which reads
    the resident table in one kernel (`h2d.fold{engaged=false,reason=fused}`)."""
    if fused:
        _count_fold(form, "fused")
        return None
    return GramFold(form, part, finish, w, y)


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
