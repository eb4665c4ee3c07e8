#
# Histogram-based decision-tree / random-forest builder — the TPU-native replacement
# for cuml.RandomForest{Classifier,Regressor} + treelite (reference tree.py:383-457:
# each Spark worker trains its share of trees with cuML's CUDA histogram builder, the
# serialized forests are allGathered and concatenated by treelite).
#
# TPU formulation (the reference's data-dependent CUDA tree kernels cannot be
# translated; this is the standard way to make trees XLA-friendly):
#   * features are quantile-binned once (LightGBM-style, max_bins buckets) — trees
#     then only ever touch uint8/int32 bin ids,
#   * trees grow LEVEL-WISE over a perfect binary heap layout (static shapes: level t
#     has 2^t node slots),
#   * per level, the (node, feature, bin, stat) histogram is built and searched a
#     FEATURE TILE at a time with a running best a node (nothing of a whole level's
#     size exists); with row-sharded inputs XLA reduces the per-shard partial
#     histograms across the mesh — the cross-device "histogram merge" is a psum,
#     not a treelite concat,
#   * split selection is a cumulative-sum + argmax over the tile (all dense math),
#   * child statistics are carried from the winning split, so each level costs exactly
#     one data pass.
# Prediction walks the heap with gathers, vmapped over trees.
#
# Impurities: gini / entropy (classification, stats = per-class weighted counts) and
# variance (regression, stats = [w, wy, wyy]), with Spark's weighted information-gain
# semantics (minInstancesPerNode, minInfoGain).
#

from __future__ import annotations

import functools
import math
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from ..defaults import FOREST_EDGE_BLOCK_COLS
from ..observability import counter_inc, span
from ..observability.device import compiled_kernel


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------


def _quantile_reads(m: int, qs: np.ndarray):
    """Where numpy's method "linear" reads the quantiles `qs` (a float64 array)
    of m values sorted ascending: the positions below and above each (-1 is
    the last) and the weight between them, in float64, as numpy computes them."""
    q = np.asanyarray(qs, dtype=np.float64)
    virtual = m * q + (1 + q * (1 - 1 - 1)) - 1  # _compute_virtual_index(m, q, 1, 1)
    prev = np.floor(virtual)
    nxt = prev + 1
    above, below = virtual >= m - 1, virtual < 0
    prev[above], nxt[above] = -1, -1
    prev[below], nxt[below] = 0, 0
    prev, nxt = prev.astype(np.intp), nxt.astype(np.intp)
    gamma = np.asanyarray(virtual - prev, dtype=virtual.dtype)
    return prev, nxt, gamma


def _lerp_quantiles(lo: np.ndarray, hi: np.ndarray, last: np.ndarray,
                    gamma: np.ndarray) -> np.ndarray:
    """numpy's two-sided lerp of the neighbours `lo`, `hi` (columns, quantiles)
    by `gamma`, the difference in the data's dtype; a column whose largest
    value `last` is NaN (NaN sorts last) is NaN throughout."""
    diff = hi - lo
    out = lo + diff * gamma
    np.subtract(hi, diff * (1 - gamma), out=out, where=np.broadcast_to(gamma >= 0.5, out.shape))
    out[np.isnan(last)] = np.nan
    return out


def _sorted_quantiles(S: np.ndarray, qs: np.ndarray) -> np.ndarray:
    """`np.quantile(S, qs, axis=1)` (method "linear", `qs` a float64 array),
    transposed to (columns, quantiles), of rows ALREADY sorted ascending:
    numpy's own arithmetic (the virtual index and the weight in float64, the
    neighbours' difference in the data's dtype, its two-sided lerp, a NaN
    anywhere makes the row NaN) without its partition, which is all but a few
    per cent of what the call costs. `tests/test_forest_reference.py` holds it
    to `np.quantile` to the bit."""
    prev, nxt, gamma = _quantile_reads(S.shape[1], qs)
    return _lerp_quantiles(S[:, prev], S[:, nxt], S[:, -1], gamma)


def _edge_rows(n: int, sample_limit: int, seed: int):
    """The rows the edges are read from: all n, or past `sample_limit` that many
    drawn without replacement from the seed, ascending (None: every row)."""
    if n <= sample_limit:
        return None
    return np.sort(np.random.default_rng(seed).choice(n, sample_limit, replace=False))


def quantile_bin_edges(
    X: np.ndarray, max_bins: int, sample_limit: int = 200_000, seed: int = 0
) -> np.ndarray:
    """Per-feature quantile thresholds, (d, max_bins-1). Bin b holds x <= edges[b]
    (last bin open). Computed host-side on a row sample, like every histogram GBM.

    `np.quantile(sample, qs, axis=0)` to the bit, by column blocks on the host's
    cores (at most 16 threads): a block of the sample's columns is made contiguous, sorted (numpy's
    sort releases the interpreter's lock and runs at a millisecond a column of
    200,000), and read at the quantiles' positions (`_sorted_quantiles`). The
    whole-array call partitions strided columns one after another: 39 s for
    200,000 x 3000 on the chip tool's host, the longest phase of a forest fit
    it was in. `device_quantile_bin_edges` gives the same edges from a table
    already on the chip."""
    n, d = X.shape
    idx = _edge_rows(n, sample_limit, seed)  # a set of rows: their order is nothing to a quantile
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    out = np.empty((d, max_bins - 1), np.float32)
    workers = min(16, os.cpu_count() or 1)
    cols = max(1, min((128 << 20) // (X.dtype.itemsize * min(n, sample_limit)),
                      max(16, -(-d // workers))))

    def block(lo):
        part = X[:, lo:lo + cols]
        if idx is not None:
            part = np.take(part, idx, axis=0)
        columns = part.T.copy()  # a column contiguous; the sample's own, so sorted in place
        columns.sort(axis=1)
        out[lo:lo + cols] = _sorted_quantiles(columns, qs)

    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(block, range(0, d, cols)))
    return out  # (d, max_bins-1)


def _sort_keys(x: jax.Array) -> jax.Array:
    """int32 keys in the order of float32 values, NaN (of either sign) past
    +inf as numpy sorts it: `_key_values` maps them back."""
    b = jax.lax.bitcast_convert_type(jnp.where(jnp.isnan(x), jnp.nan, x), jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _key_values(k: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(k ^ ((k >> 31) & 0x7FFFFFFF), jnp.float32)


@compiled_kernel("trees.forest_edges", static_argnames=("positions", "cols"))
def forest_edges(X: jax.Array, rows: jax.Array, positions: tuple, cols: int) -> jax.Array:
    """(d, len(positions)): of each column of the sample `X[rows]`, the values
    at `positions` of its ascending sort (NaN last, as numpy sorts). The
    sample's columns are gathered and sorted `cols` at a time, so no block's
    temporaries outgrow a block; the last block starts at d - cols and writes
    again what its overlap already holds. A float32 table is sorted as int32
    keys with no tie-break: 0.62 s for 200,000 x 3000 on a v5e, where the
    floats take 0.71 and either stable sort about 1.2 (PR 40)."""
    d = X.shape[1]
    cols = min(cols, d)
    pos = np.asarray(positions, np.int32)
    keyed = X.dtype == jnp.float32

    def block(b, out):
        lo = jnp.minimum(b * cols, d - cols)
        part = jnp.take(jax.lax.dynamic_slice_in_dim(X, lo, cols, axis=1), rows, axis=0).T
        if keyed:  # equal keys are equal values: no tie-break, no index carried
            ranked = _key_values(
                jax.lax.sort(_sort_keys(part), dimension=1, is_stable=False)[:, pos])
        else:
            ranked = jnp.sort(part, axis=1)[:, pos]
        return jax.lax.dynamic_update_slice_in_dim(out, ranked, lo, axis=0)

    out = jnp.zeros((d, len(positions)), X.dtype)
    return jax.lax.fori_loop(0, -(-d // cols), block, out)


def device_quantile_bin_edges(
    X_dev: jax.Array, n: int, max_bins: int, sample_limit: int = 200_000, seed: int = 0
) -> np.ndarray:
    """`quantile_bin_edges` of the host table whose first n rows `X_dev` holds,
    to the bit: the same rows drawn on the host, their columns sorted on the
    device (`forest_edges`), and only the order statistics the quantiles read
    fetched (at most 2 (max_bins - 1) + 1 a column: 3 MB at 3000 columns), to
    which the host applies numpy's arithmetic (`_lerp_quantiles`)."""
    idx = _edge_rows(n, sample_limit, seed)
    rows = np.arange(n) if idx is None else idx
    m = len(rows)
    prev, nxt, gamma = _quantile_reads(m, np.linspace(0, 1, max_bins + 1)[1:-1])
    positions = np.unique(np.concatenate([prev % m, nxt % m, [m - 1]]))
    ranked = np.asarray(forest_edges(X_dev, jnp.asarray(rows, jnp.int32),
                                     positions=tuple(positions.tolist()),
                                     cols=FOREST_EDGE_BLOCK_COLS))

    def at(p):
        return ranked[:, np.searchsorted(positions, p % m)]

    return _lerp_quantiles(at(prev), at(nxt), ranked[:, -1], gamma).astype(np.float32)


def _on_tpu() -> bool:
    """Where the device is a TPU (tests reach the device edges on the CPU by
    patching this)."""
    return jax.default_backend() == "tpu"


def edges_gate(X_dev, X_host: np.ndarray):
    """Whether a fit's quantile edges are made from the table on the device,
    and which test decided it, from what the fit was handed: `source` (no
    placed table: the streamed tier), `devices` (rows sharded over several:
    the sample would cross chips), `dtype` (the placed table is not the
    host's: a float64 table goes up as float32), `platform` (not a TPU: the
    host's threads are the device), else `ok`."""
    if not isinstance(X_dev, jax.Array):
        return False, "source"
    if len(X_dev.sharding.device_set) > 1:
        return False, "devices"
    if X_dev.dtype != X_host.dtype:
        return False, "dtype"
    if not _on_tpu():
        return False, "platform"
    return True, "ok"


def bin_features(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Digitize to int32 bins (n, d): bin = #edges < x, in [0, max_bins-1].
    Dispatches to the native OpenMP kernel when built (spark_rapids_ml_tpu/native.py),
    numpy searchsorted otherwise."""
    from ..native import bin_features as _native_bin

    return _native_bin(X, edges)


# ---------------------------------------------------------------------------
# Impurity algebra on stat vectors
# ---------------------------------------------------------------------------


def _parts(stats: jax.Array):
    """The statistics of an array whose LAST axis holds them, one array each."""
    return [stats[..., i] for i in range(stats.shape[-1])]


def _weight_of(parts, impurity: str) -> jax.Array:
    if impurity == "variance":
        return parts[0]
    return functools.reduce(jnp.add, parts)


def _impurity_w_of(parts, impurity: str) -> jax.Array:
    """w * impurity — the additive form used for gain computation — of
    statistics given one array each (a histogram tile keeps them apart: its
    last axis is the chip's lanes, and a trailing axis of s would be padded to
    a lane tile)."""
    w = _weight_of(parts, impurity)
    safe_w = jnp.maximum(w, 1e-12)
    if impurity == "variance":
        wy, wyy = parts[1], parts[2]
        return wyy - wy * wy / safe_w
    if impurity == "gini":
        return w - functools.reduce(jnp.add, [x * x for x in parts]) / safe_w
    # entropy
    shares = [x / safe_w for x in parts]
    ent = -functools.reduce(jnp.add, [
        jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)), 0.0) for p in shares
    ])
    return w * ent


def _stat_weight(stats: jax.Array, impurity: str) -> jax.Array:
    return _weight_of(_parts(stats), impurity)


def _impurity_times_w(stats: jax.Array, impurity: str) -> jax.Array:
    """`_impurity_w_of` of statistics along the last axis."""
    return _impurity_w_of(_parts(stats), impurity)


def _leaf_value(stats: jax.Array, impurity: str) -> jax.Array:
    """Leaf payload: class distribution (classification) or [mean] (regression)."""
    if impurity == "variance":
        w = jnp.maximum(stats[..., 0], 1e-12)
        return (stats[..., 1] / w)[..., None]
    w = jnp.maximum(jnp.sum(stats, axis=-1, keepdims=True), 1e-12)
    return stats / w


# ---------------------------------------------------------------------------
# Level-wise builder
# ---------------------------------------------------------------------------

# Which histogram a level takes (a static name a level; `level_forms` decides):
#   "xla"      jax.ops.segment_sum (off the TPU)
#   "direct"   the one-hot kernel over every node of the level
#   "grouped"  rows sorted by node, the node one-hot local to a row block


def feature_plan(d: int):
    """(d_pad, f_max): the width the bin matrix is padded to and the widest
    feature tile of a level step. Tiles are 4 * 2**k features (whole packed
    words, a power of two of them, at least `WORDS_PER_STEP`), so every level's
    tile divides `d_pad` whatever its width."""
    from .pallas_histogram import WORDS_PER_STEP, _round_up

    q = _round_up(-(-d // 4), WORDS_PER_STEP)
    q_max = WORDS_PER_STEP
    while q_max < min(q, 256):
        q_max *= 2
    return 4 * _round_up(q, q_max), 4 * q_max


def level_tile(width: int, d: int, nbins: int, s: int, n: int = 0) -> int:
    """Features a tile of a level of `width` nodes: as many as keep the tile's
    (width, F, nbins, s) float32 histogram, and the (n, F) int32 ids a form
    that is not grouped unpacks for it, within `FOREST_HIST_TILE_BYTES`."""
    from ..defaults import FOREST_HIST_TILE_BYTES
    from .pallas_histogram import WORDS_PER_STEP

    _, f_max = feature_plan(d)
    per_feature = 4 * max(width * nbins * s, n)
    f = 4 * WORDS_PER_STEP
    while 2 * f <= f_max and 2 * f * per_feature <= FOREST_HIST_TILE_BYTES:
        f *= 2
    return f


def level_forms(max_depth: int, n: int, d: int, nbins: int, s: int,
                use_pallas: bool, devices: int = 1):
    """The histogram form of each level, from shapes (`hist_gate`)."""
    from .pallas_histogram import hist_gate

    if not use_pallas:
        return ("xla",) * max_depth
    return tuple(
        "grouped" if hist_gate(2**t, d, nbins, s, n, devices)[0] else "direct"
        for t in range(max_depth)
    )


def _pack_words(Xb: jax.Array) -> jax.Array:
    """(n, d_pad) one-byte ids -> (n, Q) int32 with Q = d_pad // 4: byte k of
    word q is feature k * Q + q. Four contiguous column ranges, so the packing
    is elementwise (adjacent columns in a word would be a strided read, or a
    bitcast that XLA expands to an (n, d_pad) int32 array). A small caller's
    way in: the fit's own binning writes the words itself."""
    q = Xb.shape[1] // 4
    x = Xb.astype(jnp.int32)
    return (x[:, :q] | (x[:, q:2 * q] << 8) | (x[:, 2 * q:3 * q] << 16)
            | (x[:, 3 * q:] << 24))


def tile_features(packed: bool, j, f: int, d_pad: int) -> jax.Array:
    """The feature of each row of feature tile `j` (traced) of `f` features: a
    contiguous range of columns, or, of packed words, (byte, word of the tile)
    with byte k of word q feature k * (d_pad // 4) + q."""
    if not packed:
        return j * f + jnp.arange(f, dtype=jnp.int32)
    words = f // 4
    byte = jnp.arange(4, dtype=jnp.int32)[:, None]
    word = jnp.arange(words, dtype=jnp.int32)[None, :]
    return (byte * (d_pad // 4) + j * words + word).reshape(f)


def compact_gate(width: int, d: int, k_features: int):
    """Whether a level of `width` nodes histograms only the columns its nodes
    drew, and which test decided it, from shapes alone: `all_features` (every
    node may split on every column), `width` (the union of the level's draws,
    width * k_features columns at most, pads to as many columns as the whole
    matrix: from width * k_features >= d on, and at a few widths under it),
    else `ok`."""
    if k_features >= d:
        return False, "all_features"
    if feature_plan(width * k_features)[0] >= feature_plan(d)[0]:
        return False, "width"
    return True, "ok"


def _take_columns(words: jax.Array, cols) -> jax.Array:
    """The packed words of the features `cols` ((F,) int32, each below d):
    feature-major words in, (Q, n), and out, (F // 4, n), with feature i at
    byte i // (F // 4) of word i % (F // 4), as `_pack_words` lays them; F // 4
    is a multiple of `WORDS_PER_STEP` (`feature_plan`'s widths). A result word
    is four dynamic row slices, a byte each, of the words laid out as (Q, n /
    128, 128), where a row is whole tiles (a row of (Q, n) is one sublane of
    every tile it crosses); `WORDS_PER_STEP` result words at a time, so nothing
    but the result is of its size. No gather: the TPU's compiler lowers one
    through a chunked copy of the whole operand."""
    from .pallas_histogram import WORDS_PER_STEP

    q, n = words.shape
    qc = cols.shape[0] // 4
    word, shift = cols % q, 8 * (cols // q)
    rows = jnp.pad(words, ((0, 0), (0, -n % 128))).reshape(q, -1, 128)

    def block(b):  # the result's words [b * WORDS_PER_STEP, (b + 1) * WORDS_PER_STEP)
        out = []
        for i in range(WORDS_PER_STEP):
            w = 0
            for k in range(4):
                at = k * qc + b * WORDS_PER_STEP + i
                row = jax.lax.dynamic_slice_in_dim(rows, word[at], 1, axis=0)
                w = w | (((row >> shift[at]) & 0xFF) << (8 * k))
            out.append(w)
        return jnp.concatenate(out, axis=0)

    blocks = jax.lax.map(block, jnp.arange(qc // WORDS_PER_STEP))
    return blocks.reshape(qc, -1)[:, :n]


def _tile_ids(Xb, packed: bool, j, f: int) -> jax.Array:
    """(n, f) int32 bin ids of feature tile `j`, in `tile_features`' order."""
    if not packed:
        return jax.lax.dynamic_slice_in_dim(Xb, j * f, f, axis=1).astype(jnp.int32)
    w = jax.lax.dynamic_slice_in_dim(Xb, j * (f // 4), f // 4, axis=1)
    return jnp.concatenate([(w >> (8 * k)) & 0xFF for k in range(4)], axis=1)


def _row_bin(Xb, packed: bool, row_feat) -> jax.Array:
    """Each row's bin id at its own feature `row_feat`, (n,) int32. No per-row
    lane gather (the slowest op class on TPU) and no (n, d) float operand: ONE
    fused pass over the bin matrix, a select against the column's index and a
    row sum in integers, whatever the level's width."""
    cols = Xb.shape[1]
    col = row_feat % cols if packed else row_feat
    picked = jnp.sum(
        jnp.where(jnp.arange(cols, dtype=jnp.int32)[None, :] == col[:, None],
                  Xb.astype(jnp.int32), 0),
        axis=1,
    )
    return (picked >> (8 * (row_feat // cols))) & 0xFF if packed else picked


def _tile_histogram(form, Xb, packed, values, s, node_id, width, nbins, j, f, mesh,
                    grouped):
    """Feature tile `j` (traced) of `f` features of the level's histogram, one
    array for each of the `s` statistics: (f, width, nbins) each, or in the
    grouped form (f, A, nbins, C) with node a * C + c at [:, a, :, c] and the
    bins CUMULATIVE (the kernel's one-hot is "id <= bin": the split search wants
    the running sums and nothing else). Each producer's own order: nothing of a
    tile's size is transposed. `values` and the grouped form's rows are the
    kernel's lanes: where they hold `split_bf16x3` parts, the parts' sums are
    folded back into `s` statistics here."""
    from .pallas_histogram import (
        fold_parts, grouped_histogram_tile, node_bin_histogram, segment_histogram,
    )

    if form == "grouped":
        pt, grp, lanes, interpret = grouped
        return fold_parts(
            grouped_histogram_tile(pt, grp, j, f // 4, width, nbins, lanes, interpret), s)
    xt = _tile_ids(Xb, packed, j, f)
    if form == "direct":
        h = node_bin_histogram(xt, node_id, values, width, nbins, True, mesh=mesh,
                               feature_major=True)
    else:
        h = segment_histogram(node_id[:, None] * nbins + xt, values, width * nbins)
    return fold_parts(_parts(h.reshape(f, width, nbins, values.shape[1])), s)


def _tile_best(parts, cumulative, T, allowed, feat, nbins, impurity, min_instances):
    """The best split of every node over one feature tile: (gain, feature *
    (nbins - 1) + bin, left statistics), the lowest (feature, bin) among equal
    gains. parts: `_tile_histogram`'s tile (`cumulative`: its bins are running
    sums already); T: (nodes, s) node totals; allowed: (F, nodes) bool, the
    nodes' feature draws over this tile's features; feat: (F,) which features
    they are. Written once for both orders: the gains are (F, nodes..., bins at
    axis 2, ...), reduced over features and bins with the nodes' axes kept."""
    F = parts[0].shape[0]
    n_nodes, s = T.shape
    if parts[0].ndim == 4:  # grouped: (F, A, nbins, C)
        A, C = parts[0].shape[1], parts[0].shape[3]
        T_b = [T[:, i].reshape(1, A, 1, C) for i in range(s)]
        ok = allowed.reshape(F, A, 1, C)
    else:  # (F, nodes, nbins)
        T_b = [T[:, i].reshape(1, n_nodes, 1) for i in range(s)]
        ok = allowed[:, :, None]
    if not cumulative:
        parts = [jnp.cumsum(p, axis=2) for p in parts]
    L = [jax.lax.slice_in_dim(p, 0, nbins - 1, axis=2) for p in parts]  # split at bin 0..b-2
    R = [t - l for t, l in zip(T_b, L)]
    wL = _weight_of(L, impurity)
    wR = _weight_of(R, impurity)
    gain = (
        _impurity_w_of(T_b, impurity) - _impurity_w_of(L, impurity)
        - _impurity_w_of(R, impurity)
    ) / jnp.maximum(_weight_of(T_b, impurity), 1e-12)
    bins = jnp.arange(nbins - 1, dtype=jnp.int32).reshape(
        (1, 1, nbins - 1) + (1,) * (gain.ndim - 3))
    valid = (wL >= min_instances) & (wR >= min_instances) & ok
    gain = jnp.where(valid, gain, -jnp.inf)

    red = (0, 2)
    best_gain = jnp.max(gain, axis=red, keepdims=True)
    flat = feat.reshape((F,) + (1,) * (gain.ndim - 1)) * (nbins - 1) + bins
    best = jnp.min(  # the first of the maxima, as argmax over (feature, bin)
        jnp.where(gain == best_gain, flat, _NO_SPLIT), axis=red, keepdims=True
    )
    chosen = flat == best
    Lbest = jnp.stack(  # one term a node: exact
        [jnp.sum(jnp.where(chosen, l, 0.0), axis=red).reshape(n_nodes) for l in L], axis=1)
    return best_gain.reshape(n_nodes), best.reshape(n_nodes), Lbest


# what `_tile_best` reports where no gain equals the maximum (a NaN gain)
_NO_SPLIT = np.iinfo(np.int32).max


# Opt-in per-level wall-clock collection: a test/bench sets
# `ops.trees._LEVEL_TIMING = []` before fitting and reads (level, seconds)
# tuples back. While set, _grow_forest routes through _build_tree_impl, which
# runs each level as ONE AOT-compiled program (_level_step_jit.lower().compile()
# outside the timed window) with a sync after it — real device wall-clock,
# compile excluded, and no full-eager slowdown. The jitted build_tree entry
# point never times (hooks inside a jit body would record trace time).
_LEVEL_TIMING: "List | None" = None


def _level_step(
    state,
    Xb: jax.Array,
    values: jax.Array,
    edges: jax.Array,
    t: int,
    nbins: int,
    impurity: str,
    k_features: int,
    min_instances: int,
    min_info_gain: float,
    mesh,
    form: str = "xla",
    operand: str = "float32x3",
    packed: bool = False,
):
    """One tree level (width = 2**t): histogram and split search a feature tile
    at a time with a running best a node, heap writes, row routing, child-stat
    carry. Nothing of the whole level histogram's size (width, d, nbins, s)
    exists: a tile holds `level_tile` features. Pure state -> state so it can
    run either INLINED inside the jitted build_tree trace or as its own jitted
    program per level (timing mode: one compiled dispatch + sync per level
    measures real device wall-clock without making the whole tree eager).
    `Xb` is (n, d_pad) bin ids with d_pad from `feature_plan`, or under `packed`
    (n, d_pad // 4) words of four one-byte ids (`_pack_words`); `edges` carries d.

    Where `compact_gate` says so the histogram and the search run over the
    columns some node of the level drew and no other: the union of the draws,
    in ascending order, gathered into a narrow bin matrix of `feature_plan(width
    * k_features)` columns whose features keep their own ids, so every gain,
    tie rule and split is the whole-width scan's. A union wider than that
    matrix (float ties in the draw admit more than k_features to a node) runs
    the level whole-width and adds one to the state's overflow count."""
    from .pallas_histogram import _interpret, group_rows, lane_stats, node_tile, split_bf16x3

    (feat_arr, thr_arr, leaf_arr, val_arr, gain_arr, wgt_arr, node_id, T, key,
     overflows) = state
    n = Xb.shape[0]
    d = edges.shape[0]
    s = values.shape[1]
    width = 2**t

    # the feature draw: the same stream a level as ever (one split of the key,
    # one (width, d) uniform), masked inside each tile
    if k_features < d:
        key, sub = jax.random.split(key)
        scores = jax.random.uniform(sub, (width, d))
        from .selection import top_k_max

        kth = top_k_max(scores, k_features)[0][:, -1]
        allowed = scores >= kth[:, None]
    else:
        allowed = jnp.ones((width, d), bool)

    grp = None
    n_nodes = width
    words = Xb
    lanes = lane_stats(operand, s)  # statistics a kernel's lanes carry
    kernel_values = values  # the direct form's operand
    if form == "grouped":
        assert packed, "the grouped kernel reads four one-byte ids a word"
        c = node_tile(lanes)
        n_nodes = -(-width // c) * c
        grp = group_rows(node_id, values, width, operand)
        if grp["order"] is not None:  # rows sorted by node
            words = jnp.take(Xb, grp["order"], axis=0)
    elif form == "direct" and lanes > s:
        kernel_values = split_bf16x3(values)
    T_pad = jnp.pad(T, ((0, n_nodes - width), (0, 0)))

    def scan(X, cols):
        """The best split a node over the bin matrix `X` (`Xb`'s layout, rows
        in the level's order; the grouped form's feature-major words) whose
        feature i is feature cols[i] ((F,) int32, d for a padding slot), or,
        cols None, every feature of `Xb`."""
        cols_pad = X.shape[0 if form == "grouped" else 1] * (4 if packed else 1)
        f = level_tile(width, d if cols is None else cols.shape[0], nbins,
                       s if form == "xla" else lanes, 0 if form == "grouped" else n)
        ok = allowed if cols is None else (
            jnp.take(allowed, jnp.minimum(cols, d - 1), axis=1) & (cols < d))
        # feature-major: a tile's rows are a row gather; no padded feature, no padded node
        ok = jnp.pad(ok, ((0, n_nodes - width), (0, cols_pad - ok.shape[1]))).T
        grouped = None if grp is None else (X, grp, lanes, _interpret())

        def tile_best(j):
            h = _tile_histogram(form, X, packed, kernel_values, s, node_id, width, nbins, j,
                                f, mesh, grouped)
            at = tile_features(packed, j, f, cols_pad)
            return _tile_best(h, form == "grouped", T_pad, jnp.take(ok, at, axis=0),
                              at if cols is None else cols[at], nbins, impurity,
                              min_instances)

        best = tile_best(jnp.int32(0))
        if cols_pad > f:
            def merge(carry, j):
                new = tile_best(j)
                # an equal gain keeps the lower (feature, bin), whatever order the
                # tiles came in: the answer of one argmax over the whole level
                take = (new[0] > carry[0]) | ((new[0] == carry[0]) & (new[1] < carry[1]))
                return (jnp.where(take, new[0], carry[0]), jnp.where(take, new[1], carry[1]),
                        jnp.where(take[:, None], new[2], carry[2])), None

            best, _ = jax.lax.scan(merge, best, jnp.arange(1, cols_pad // f, dtype=jnp.int32))
        if cols is not None:
            # a node with no candidate names (feature 0, bin 0), as the
            # whole-width scan's first tile does: the leaf's threshold
            best = (best[0], jnp.where(best[0] == -jnp.inf, 0, best[1]), best[2])
        return best

    def whole():  # the bin matrix as the tiles read it
        return words.T if form == "grouped" else words

    def narrowed(cols):  # the same, of the features `cols` alone
        if not packed:
            return jnp.take(words, cols, axis=1)
        fm = _take_columns(words.T, cols)
        return fm if form == "grouped" else fm.T

    if compact_gate(width, d, k_features)[0]:
        narrow = feature_plan(width * k_features)[0]
        drawn = allowed.any(axis=0)
        fits = jnp.sum(drawn) <= narrow
        (cols,) = jnp.nonzero(drawn, size=narrow, fill_value=d)
        cols = cols.astype(jnp.int32)
        best = jax.lax.cond(fits, lambda: scan(narrowed(jnp.minimum(cols, d - 1)), cols),
                            lambda: scan(whole(), None))
        overflows = overflows + (~fits).astype(jnp.int32)
    else:
        best = scan(whole(), None)
    best_gain, best_flat, Lbest = (x[:width] for x in best)
    best_feat = jnp.minimum(best_flat // (nbins - 1), d - 1)  # _NO_SPLIT: a leaf anyway
    best_bin = best_flat % (nbins - 1)

    wT = _stat_weight(T, impurity)
    is_leaf_t = ~(best_gain > min_info_gain)  # also catches all -inf / NaN
    slots = width + jnp.arange(width)
    feat_arr = feat_arr.at[slots].set(jnp.where(is_leaf_t, -1, best_feat))
    thr_arr = thr_arr.at[slots].set(edges[best_feat, best_bin])
    leaf_arr = leaf_arr.at[slots].set(is_leaf_t)
    val_arr = val_arr.at[slots].set(_leaf_value(T, impurity))
    gain_arr = gain_arr.at[slots].set(
        jnp.where(is_leaf_t, 0.0, jnp.maximum(best_gain, 0.0))
    )
    wgt_arr = wgt_arr.at[slots].set(wT)

    # route rows; leaf rows stay in the left child slot (unreachable at predict)
    picked = _row_bin(Xb, packed, best_feat[node_id])
    go_right = (picked > best_bin[node_id]) & ~is_leaf_t[node_id]
    node_id = node_id * 2 + go_right.astype(jnp.int32)

    # children stats carried from the winning split
    T = jnp.stack([Lbest, T - Lbest], axis=1).reshape(2 * width, s)
    return (feat_arr, thr_arr, leaf_arr, val_arr, gain_arr, wgt_arr, node_id, T, key,
            overflows)


_level_step_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "t",
        "nbins",
        "impurity",
        "k_features",
        "min_instances",
        "min_info_gain",
        "mesh",
        "form",
        "operand",
        "packed",
    ),
)(_level_step)


def _build_tree_impl(
    Xb: jax.Array,  # (n, d) or (n, d_pad) bin ids (uint8 or int32), rows may be sharded
    values: jax.Array,  # (n, s) per-row stats already weighted (0 rows contribute 0)
    edges: jax.Array,  # (d, nbins-1) real thresholds
    key: jax.Array,  # per-tree PRNG key (feature subsets)
    max_depth: int,
    nbins: int,
    impurity: str,
    k_features: int,
    min_instances: int,
    min_info_gain: float,
    use_pallas: bool = False,
    mesh=None,
    level_timing=None,
    forms=None,
    operand: str = "float32x3",
    packed: bool = False,
) -> Dict[str, jax.Array]:
    """Grow one tree; returns heap arrays of size 2^(max_depth+1):
    feature (int32, -1 for leaf), threshold (f32), is_leaf (bool), value (slots, v),
    and `compact_overflows`, the levels `compact_gate` narrowed that ran
    whole-width because the union of the draws overflowed (int32 scalar).
    `forms` names each level's histogram (`level_forms`); without it every level
    takes the one-hot kernel (`use_pallas`) or the segment_sum. `packed`: `Xb`
    is the fit's (n, d_pad // 4) words of four one-byte ids, not (n, d) ids."""
    n = Xb.shape[0]
    d = edges.shape[0]
    s = values.shape[1]
    n_slots = 2 ** (max_depth + 1)
    v_dim = 1 if impurity == "variance" else s
    if forms is None:
        forms = ("direct" if use_pallas else "xla",) * max_depth
    d_pad, _ = feature_plan(d)
    if not packed:  # a bare caller's matrix (tests, the streamed tier's uint8)
        Xb = jnp.pad(Xb, ((0, 0), (0, d_pad - d)))
        if "grouped" in forms:
            Xb, packed = _pack_words(Xb), True

    state = (
        jnp.full((n_slots,), -1, jnp.int32),  # feature (-1 = leaf)
        jnp.zeros((n_slots,), jnp.float32),  # threshold
        jnp.zeros((n_slots,), bool),  # is_leaf
        jnp.zeros((n_slots, v_dim), jnp.float32),  # value
        # per-node split gain and weighted row count — the inputs to impurity-
        # based featureImportances (Spark TreeEnsembleModel semantics)
        jnp.zeros((n_slots,), jnp.float32),  # gain
        jnp.zeros((n_slots,), jnp.float32),  # node weight
        jnp.zeros((n,), jnp.int32),  # node_id
        jnp.sum(values, axis=0)[None, :],  # (1, s) root stats
        key,
        jnp.zeros((), jnp.int32),  # narrowed levels that ran whole-width
    )

    step_kw = dict(
        nbins=nbins, impurity=impurity, k_features=k_features,
        min_instances=min_instances, min_info_gain=min_info_gain,
        mesh=mesh, operand=operand, packed=packed,
    )
    for t in range(max_depth):
        if level_timing is not None:
            # AOT-compile OUTSIDE the timed window, then time the executable:
            # otherwise each level's first run per process times trace+compile
            # (seconds of XLA work) instead of device wall-clock
            exe = _level_step_jit.lower(
                state, Xb, values, edges, t=t, form=forms[t], **step_kw
            ).compile()
            t0 = time.perf_counter()  # noqa: purity/time-read (timing mode never runs under a jit)
            state = exe(state, Xb, values, edges)
            state[7].block_until_ready()  # T — the sync exists only in timing mode
            level_timing.append((t, time.perf_counter() - t0))  # noqa: purity/time-read
        else:
            state = _level_step(state, Xb, values, edges, t, form=forms[t], **step_kw)
    (feat_arr, thr_arr, leaf_arr, val_arr, gain_arr, wgt_arr, node_id, T, _,
     overflows) = state

    # deepest level: all leaves
    width = 2**max_depth
    slots = width + jnp.arange(width)
    leaf_arr = leaf_arr.at[slots].set(True)
    val_arr = val_arr.at[slots].set(_leaf_value(T, impurity))
    wgt_arr = wgt_arr.at[slots].set(_stat_weight(T, impurity))
    return {
        "feature": feat_arr,
        "threshold": thr_arr,
        "is_leaf": leaf_arr,
        "value": val_arr,
        "gain": gain_arr,
        "node_weight": wgt_arr,
        "compact_overflows": overflows,
    }


@compiled_kernel("trees.predict_forest", static_argnames=("max_depth",))
def predict_forest(
    X: jax.Array,  # (n, d) raw features
    feature: jax.Array,  # (n_trees, n_slots)
    threshold: jax.Array,
    is_leaf: jax.Array,
    value: jax.Array,  # (n_trees, n_slots, v)
    max_depth: int,
) -> jax.Array:
    """Average of per-tree leaf payloads, (n, v)."""

    d = X.shape[1]
    n_slots = feature.shape[1]
    # the mask-sum route builds a (n_slots, d) one-hot table per tree — fine for
    # trained forests (depth <= 12ish) but a vmapped OOM for deep imported
    # forests (depth-20 heap = 2M slots); those keep the lane gather
    use_mask_sum = n_slots * d <= (1 << 22)

    def one_tree(feat_t, thr_t, leaf_t, val_t):
        # feature one-hot table rows instead of a per-row lane gather on X
        # (same rewrite as build_tree routing: the lane gather is 2x slower
        # than the table-row + mask-sum form on TPU). SELECT, don't multiply:
        # 0 * NaN = NaN would let a NaN in any UNTESTED feature poison the
        # picked value; with where() only the tested feature's value flows
        # through, so NaN-in-tested-feature still compares False and routes
        # LEFT — the documented treelite default_left=True contract.
        if use_mask_sum:
            A = jax.nn.one_hot(jnp.maximum(feat_t, 0), d, dtype=X.dtype) > 0

        def walk(carry, _):
            p = carry
            stop = leaf_t[p]
            if use_mask_sum:
                picked = jnp.sum(jnp.where(A[p], X, 0.0), axis=1)
            else:
                f = jnp.maximum(feat_t[p], 0)
                picked = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
            go_right = picked > thr_t[p]
            p_next = p * 2 + go_right.astype(jnp.int32)
            return jnp.where(stop, p, p_next), None

        p0 = jnp.ones((X.shape[0],), jnp.int32)
        p, _ = jax.lax.scan(walk, p0, None, length=max_depth)
        return val_t[p]  # (n, v)

    vals = jax.vmap(one_tree)(feature, threshold, is_leaf, value)  # (trees, n, v)
    return jnp.mean(vals, axis=0)


# ---------------------------------------------------------------------------
# Forest driver
# ---------------------------------------------------------------------------


def resolve_feature_subset(strategy: str, d: int, is_classification: bool) -> int:
    """Spark featureSubsetStrategy resolution (auto/all/sqrt/log2/onethird/number)."""
    s = str(strategy)
    if s == "auto":
        return max(1, int(math.sqrt(d))) if is_classification else max(1, d // 3)
    if s == "all":
        return d
    if s == "sqrt":
        return max(1, int(math.sqrt(d)))
    if s == "log2":
        return max(1, int(math.log2(d)))
    if s == "onethird":
        return max(1, d // 3)
    try:
        val = float(s)
        if val.is_integer() and val >= 1:
            return min(d, int(val))
        if 0 < val <= 1:
            return max(1, int(val * d))
    except ValueError:
        pass
    raise ValueError(f"Unsupported featureSubsetStrategy: {strategy}")


@compiled_kernel(
    "trees.build_tree",
    static_argnames=(
        "max_depth",
        "nbins",
        "impurity",
        "k_features",
        "min_instances",
        "min_info_gain",
        "use_pallas",
        "mesh",  # jax.sharding.Mesh is hashable; static so shard_map can close over it
        "forms",
        "operand",
        "packed",
    ),
)
def build_tree(
    Xb: jax.Array,
    values: jax.Array,
    edges: jax.Array,
    key: jax.Array,
    max_depth: int,
    nbins: int,
    impurity: str,
    k_features: int,
    min_instances: int,
    min_info_gain: float,
    use_pallas: bool = False,
    mesh=None,
    forms=None,
    operand: str = "float32x3",
    packed: bool = False,
) -> Dict[str, jax.Array]:
    """Jitted tree growth (see _build_tree_impl). The jitted path NEVER times —
    the level-timing hooks would record trace time, not device time — so
    _grow_forest calls _build_tree_impl directly when _LEVEL_TIMING is set."""
    return _build_tree_impl(
        Xb, values, edges, key, max_depth, nbins, impurity, k_features,
        min_instances, min_info_gain, use_pallas, mesh, level_timing=None,
        forms=forms, operand=operand, packed=packed,
    )


@compiled_kernel("trees.bin_features", static_argnames=("d_pad",))
def bin_features_device(X: jax.Array, edges: jax.Array, d_pad: int) -> jax.Array:
    """`bin_features` on the device, from the table the fit already uploaded:
    (n, d) floats against (d, max_bins - 1) edges -> (n, d_pad // 4) int32, four
    one-byte ids a word as `_pack_words` lays them (byte k of word q is feature
    k * (d_pad // 4) + q; features past d read 0). bin = #edges < x,
    `np.searchsorted(side="left")` to the bit: a value equal to an edge stays
    in the edge's bin, +inf takes the last bin, and so does NaN (which numpy
    sorts past every edge). One fused pass: the table is read once and one byte
    an id is written; the comparisons against the (d,) edge rows are unrolled,
    max_bins - 1 of them (<= 255)."""
    d = X.shape[1]
    q = d_pad // 4
    words = None
    for k in range(4):
        lo, hi = k * q, min((k + 1) * q, d)
        if lo >= hi:
            break
        x = X[:, lo:hi]
        count = jnp.zeros(x.shape, jnp.int32)
        for b in range(edges.shape[1]):
            count = count + (x > edges[lo:hi, b][None, :]).astype(jnp.int32)
        ids = jnp.where(jnp.isnan(x), edges.shape[1], count)
        ids = jnp.pad(ids, ((0, 0), (0, q - (hi - lo)))) << (8 * k)
        words = ids if words is None else words | ids
    return words


def unpack_bins(words: np.ndarray, d: int) -> np.ndarray:
    """(n, d) uint8 bin ids of `bin_features_device`'s words, on the host."""
    w = np.asarray(words)
    return np.concatenate(
        [((w >> (8 * k)) & 0xFF).astype(np.uint8) for k in range(4)], axis=1)[:, :d]


def forest_fit(
    X_host: np.ndarray,
    raw_stats_host: np.ndarray,  # (n, s) unweighted per-row stats (already include sample weight)
    n_trees: int,
    max_depth: int,
    max_bins: int,
    impurity: str,
    feature_subset: int,
    min_instances: int,
    min_info_gain: float,
    subsampling_rate: float,
    bootstrap: bool,
    seed: int,
    shard_fn=None,
    mesh=None,
    X_dev=None,
    unit_stats: bool = False,
) -> Dict[str, np.ndarray]:
    """Bin once, then grow the forest tree-by-tree (one XLA compile; trees differ
    only in their bootstrap weights and PRNG key). `shard_fn` optionally places the
    host arrays on the mesh so histograms psum across devices. `X_dev` is the
    table as the fit's normal upload placed it (rows padded as `shard_fn` pads):
    with it, and max_bins <= 256, the bin ids are made ON the device, one byte
    each, and no second table goes up (and where `edges_gate` allows, the
    quantile edges are sorted there from it too); without it
    the host bins (`native.bin_features`) and the ids are uploaded. `unit_stats`:
    the caller vouches that every row statistic is 0 or 1 (a classifier without
    a weightCol), so weighted by a tree's whole-number row weights they are
    whole numbers, exact in the grouped kernel's narrow operands (`_operand`)."""
    if n_trees < 1:
        raise ValueError(f"numTrees must be >= 1, got {n_trees}")
    if max_depth < 0:
        raise ValueError(f"maxDepth must be >= 0, got {max_depth}")
    n, d = X_host.shape
    device, reason = edges_gate(X_dev, X_host)
    counter_inc("forest.edges_gate", 1, device=int(device), reason=reason)
    counter_inc("forest.edges_path", 1, path="device" if device else "host")
    with span("forest.edges"):
        if device:  # the table is on the chip already: sorted there
            edges = device_quantile_bin_edges(X_dev, n, max_bins, seed=seed)
        else:
            edges = quantile_bin_edges(X_host, max_bins, seed=seed)
    on_device = X_dev is not None and max_bins <= 256
    counter_inc("forest.bin_path", 1, path="device" if on_device else "host")
    with span("forest.bin"):
        if on_device:  # four one-byte ids a word
            Xb = bin_features_device(X_dev, jnp.asarray(edges), feature_plan(d)[0])
            Xb.block_until_ready()
        else:
            Xb_host = bin_features(X_host, edges)
            Xb = jnp.asarray(Xb_host) if shard_fn is None else shard_fn(Xb_host)
    raw_stats = (
        jnp.asarray(raw_stats_host) if shard_fn is None else shard_fn(raw_stats_host)
    )
    return _grow_forest(
        Xb, raw_stats, edges, n, n_trees, max_depth, max_bins, impurity,
        feature_subset, min_instances, min_info_gain, subsampling_rate,
        bootstrap, seed, shard_fn, mesh, unit_stats=unit_stats, packed=on_device,
    )


def tree_row_weights(seed: int, n: int, n_trees: int, subsampling_rate: float = 1.0,
                     bootstrap: bool = True):
    """The row weights of each tree, in order (docs/api.md states the rule and
    `cellbench/forest_ref.py` draws them again): ONE generator a fit,
    `np.random.default_rng(seed & 0x7FFFFFFF)`; tree i takes its i-th draw of
    n values: `poisson(subsamplingRate, n)` under bootstrap, else
    `random(n) < subsamplingRate` when that is below 1, else ones."""
    rng = np.random.default_rng(seed & 0x7FFFFFFF)
    for _ in range(n_trees):
        if bootstrap:
            yield rng.poisson(subsampling_rate, size=n).astype(np.float32)
        elif subsampling_rate < 1.0:
            yield (rng.random(n) < subsampling_rate).astype(np.float32)
        else:
            yield np.ones((n,), np.float32)


def _operand(unit_stats: bool, w_tree: np.ndarray) -> str:
    """The histogram kernels' operands for one tree: where every row statistic
    is 0 or 1 (`unit_stats`) the weighted statistics are the tree's whole-number
    row weights, exact in int8 up to 127 (the MXU's fastest operands on a v5e)
    and in bfloat16 up to 256; anything else (a regressor's real-valued
    statistics, a weightCol) rides as "float32x3", three exact bfloat16 parts a
    value (`ops/pallas_histogram.py::split_bf16x3`). Sums are float32 (int32
    under int8) either way, and every one is the sum of the statistics as given."""
    if not unit_stats:
        return "float32x3"
    top = float(w_tree.max(initial=0.0))
    return "int8" if top <= 127 else "bfloat16" if top <= 256 else "float32x3"


def _grow_forest(
    Xb: jax.Array,
    raw_stats: jax.Array,
    edges: np.ndarray,
    n: int,
    n_trees: int,
    max_depth: int,
    max_bins: int,
    impurity: str,
    feature_subset: int,
    min_instances: int,
    min_info_gain: float,
    subsampling_rate: float,
    bootstrap: bool,
    seed: int,
    shard_fn=None,
    mesh=None,
    unit_stats: bool = False,
    packed: bool = False,
) -> Dict[str, np.ndarray]:
    """The per-tree growth loop over ALREADY-BINNED device arrays — shared by the
    in-core forest_fit and the out-of-core streaming_forest_fit so a parity test
    between them exercises only the ingest path. `n` is the REAL row count (the
    binned arrays may carry padded rows whose stats are zero). Every tree is
    dispatched before any result is read: the trees come back in one fetch."""
    from .pallas_histogram import default_use_pallas, hist_gate

    use_pallas = default_use_pallas()
    multi = mesh is not None and mesh.devices.size > 1
    d, s = edges.shape[0], raw_stats.shape[1]
    devices = mesh.devices.size if multi else 1
    gate_bins = max_bins if packed else 257  # ids not packed a byte each: no grouped form
    forms = level_forms(max_depth, Xb.shape[0], d, gate_bins, s, use_pallas, devices)
    kernel = any(form != "xla" for form in forms)
    narrowed = 0  # levels a tree that histogram only the columns their nodes drew
    for t, form in enumerate(forms):
        grouped, reason = hist_gate(2**t, d, gate_bins, s, Xb.shape[0], devices)
        counter_inc("forest.hist_gate", n_trees, grouped=int(grouped), reason=reason)
        counter_inc("forest.hist_path", n_trees, path=form)
        engaged, reason = compact_gate(2**t, d, feature_subset)
        narrowed += engaged
        if not engaged:
            counter_inc("forest.hist_compact", n_trees, engaged="false", reason=reason)
    edges_j = jnp.asarray(edges)
    trees: List[Dict[str, jax.Array]] = []
    if _LEVEL_TIMING is not None:
        build_fn = functools.partial(_build_tree_impl, level_timing=_LEVEL_TIMING)
    else:
        build_fn = build_tree
    with span("forest.grow", {"waits": "device"}):
        for i, w_tree in enumerate(
            tree_row_weights(seed, n, n_trees, subsampling_rate, bootstrap)
        ):
            w_j = jnp.asarray(w_tree) if shard_fn is None else shard_fn(w_tree)
            operand = _operand(unit_stats, w_tree)
            # what the kernels multiply; with no kernel (segment_sum) the float32 as given
            counter_inc("forest.hist_operand", 1, operand=operand if kernel else "float32")
            trees.append(build_fn(
                Xb,
                raw_stats * w_j[:, None],
                edges_j,
                jax.random.PRNGKey((seed + 7919 * i) & 0x7FFFFFFF),
                max_depth=max_depth,
                nbins=max_bins,
                impurity=impurity,
                k_features=feature_subset,
                min_instances=min_instances,
                min_info_gain=min_info_gain,
                use_pallas=use_pallas,
                mesh=mesh if multi else None,
                forms=forms,
                operand=operand,
                packed=packed,
            ))
        jax.block_until_ready(trees[-1])
    counter_inc("forest.trees", n_trees)
    counter_inc("forest.levels", n_trees * max_depth)
    with span("forest.fetch"):
        fetched = jax.device_get(trees)
    out = {k: np.stack([t[k] for t in fetched]) for k in fetched[0]}
    overflows = int(out.pop("compact_overflows").sum())
    if narrowed:
        counter_inc("forest.hist_compact", n_trees * narrowed - overflows, engaged="true",
                    reason="ok")
    if overflows:
        counter_inc("forest.hist_compact", overflows, engaged="false", reason="overflow")
    out["bin_edges"] = edges
    return out


def streaming_forest_fit(
    X_host: np.ndarray,
    raw_stats_host: np.ndarray,
    n_trees: int,
    max_depth: int,
    max_bins: int,
    impurity: str,
    feature_subset: int,
    min_instances: int,
    min_info_gain: float,
    subsampling_rate: float,
    bootstrap: bool,
    seed: int,
    batch_rows: int,
    shard_fn=None,
    mesh=None,
) -> Dict[str, np.ndarray]:
    """Out-of-core forest fit: X streams through BINNING in host row blocks, and
    only the binned uint8 matrix (4x smaller than f32; max_bins <= 256) plus the
    per-row stats reside on device for the growth loop — the RandomForest analog
    of the reference's UVM/SAM larger-than-memory fitting (reference
    utils.py:184-241). BASELINE config 4 (50M x 64) is ~12.8 GiB as f32 but
    ~3.1 GiB binned, which fits a 16 GiB chip.

    Residency bound: n x d uint8 + n x s f32 stats + one (n,) f32 weight vector
    per tree placement. Quantile edges come from a strided row subsample (the
    same sample-bounded estimate quantile_bin_edges applies in-core)."""
    if n_trees < 1:
        raise ValueError(f"numTrees must be >= 1, got {n_trees}")
    if max_depth < 0:
        raise ValueError(f"maxDepth must be >= 0, got {max_depth}")
    if max_bins > 256:
        raise ValueError(
            f"streaming forest bins to uint8: maxBins must be <= 256, got {max_bins}"
        )
    n, d = X_host.shape
    # edges from a strided subsample: rows are not assumed shuffled
    step = max(1, n // 200_000)
    edges = quantile_bin_edges(
        np.ascontiguousarray(X_host[::step], dtype=np.float32), max_bins, seed=seed  # noqa: fence/host-staging-copy
    )

    Xb_host = np.empty((n, d), np.uint8)
    for s in range(0, n, batch_rows):
        e = min(s + batch_rows, n)
        Xb_host[s:e] = bin_features(
            np.ascontiguousarray(X_host[s:e], dtype=np.float32), edges  # noqa: fence/host-staging-copy
        ).astype(np.uint8)

    Xb = jnp.asarray(Xb_host) if shard_fn is None else shard_fn(Xb_host)
    raw_stats = (
        jnp.asarray(raw_stats_host.astype(np.float32))
        if shard_fn is None
        else shard_fn(raw_stats_host.astype(np.float32))
    )
    return _grow_forest(
        Xb, raw_stats, edges, n, n_trees, max_depth, max_bins, impurity,
        feature_subset, min_instances, min_info_gain, subsampling_rate,
        bootstrap, seed, shard_fn, mesh,
    )


def forest_to_json(model_attrs: Dict[str, np.ndarray], is_classification: bool) -> List[Dict]:
    """Portable nested-dict dump of the forest — the role of the reference's
    treelite JSON dump for Spark-tree interop (reference tree.py:534-559,
    utils.py:585-809)."""
    feature = model_attrs["feature"]
    threshold = model_attrs["threshold"]
    is_leaf = model_attrs["is_leaf"]
    value = model_attrs["value"]

    def node(tree_idx: int, p: int) -> Dict:
        if is_leaf[tree_idx, p] or feature[tree_idx, p] < 0 or 2 * p >= feature.shape[1]:
            payload = value[tree_idx, p].tolist()
            return (
                {"leaf_value": payload}
                if not is_classification
                else {"leaf_class_probs": payload}
            )
        return {
            "split_feature": int(feature[tree_idx, p]),
            "threshold": float(threshold[tree_idx, p]),
            "default_left": True,
            "left_child": node(tree_idx, 2 * p),
            "right_child": node(tree_idx, 2 * p + 1),
        }

    return [
        {"tree_id": i, "root": node(i, 1)} for i in range(feature.shape[0])
    ]


def forest_from_json(
    trees_json: List[Dict], n_features: int, is_classification: bool
) -> Dict[str, np.ndarray]:
    """Inverse of forest_to_json: rebuild the heap-layout forest arrays from the
    portable nested-dict dump, so forests exported by this framework (or translated
    from treelite/cuML dumps into the same shape) can be imported as models — the
    import half of the reference's treelite interop (reference tree.py:439-449)."""
    leaf_key = "leaf_class_probs" if is_classification else "leaf_value"

    def depth_of(node: Dict) -> int:
        if leaf_key in node or "left_child" not in node:
            return 0
        return 1 + max(depth_of(node["left_child"]), depth_of(node["right_child"]))

    if not trees_json:
        raise ValueError("empty forest JSON")
    roots = [t["root"] for t in trees_json]
    max_depth = max(depth_of(r) for r in roots)
    if max_depth > 20:
        # the heap layout allocates 2^(depth+1) slots per tree: one depth-25
        # branch in an imported (e.g. cuML-trained) forest would inflate every
        # array by 2^26 slots — fail with the number instead of a MemoryError
        raise ValueError(
            f"forest depth {max_depth} exceeds the dense-heap import limit (20); "
            f"re-train/dump with a bounded max_depth to import"
        )
    v_dims = set()

    def leaf_dim(node: Dict) -> None:
        if leaf_key in node:
            v_dims.add(len(node[leaf_key]))
        else:
            leaf_dim(node["left_child"])
            leaf_dim(node["right_child"])

    for r in roots:
        leaf_dim(r)
    if len(v_dims) != 1:
        raise ValueError(f"inconsistent leaf payload dims: {sorted(v_dims)}")
    v_dim = v_dims.pop()

    n_trees = len(roots)
    n_slots = 2 ** (max_depth + 1)
    feature = np.full((n_trees, n_slots), -1, np.int32)
    threshold = np.zeros((n_trees, n_slots), np.float32)
    is_leaf = np.zeros((n_trees, n_slots), bool)
    value = np.zeros((n_trees, n_slots, v_dim), np.float32)

    def fill(tree_idx: int, node: Dict, p: int) -> None:
        if leaf_key in node:
            is_leaf[tree_idx, p] = True
            value[tree_idx, p] = np.asarray(node[leaf_key], np.float32)
            return
        f = int(node["split_feature"])
        if not 0 <= f < n_features:
            raise ValueError(f"split_feature {f} out of range for d={n_features}")
        feature[tree_idx, p] = f
        threshold[tree_idx, p] = float(node["threshold"])
        fill(tree_idx, node["left_child"], 2 * p)
        fill(tree_idx, node["right_child"], 2 * p + 1)

    for i, r in enumerate(roots):
        fill(i, r, 1)
    return {
        "feature": feature,
        "threshold": threshold,
        "is_leaf": is_leaf,
        "value": value,
        "bin_edges": np.zeros((n_features, 1), np.float32),
    }


def _prev_f32_ftz(t: float) -> float:
    """Largest float32 strictly below t UNDER XLA's flush-to-zero semantics.

    nextafter(0.0, -inf) is a denormal, and XLA flushes denormals to +-0.0 — the
    nudge silently vanishes and equality routes the wrong way (caught by driving
    a '<' split at threshold 0.0). Denormal results are therefore snapped to the
    nearest FTZ-representable neighbor: -tiny below zero, 0.0 for positive
    denormals (consistent with denormal INPUTS also flushing to zero)."""
    p = np.nextafter(np.float32(t), np.float32(-np.inf))
    tiny = np.finfo(np.float32).tiny
    if p != 0.0 and abs(p) < tiny:
        p = np.float32(-tiny) if p < 0 else np.float32(0.0)
    return float(p)


def _treelite_tree_to_nested(tree: Dict, is_classification: bool) -> Dict:
    """One treelite-JSON tree (flat `nodes` list keyed by node_id — the schema the
    reference translates at utils.py:700-809) -> this module's nested dict.

    Routing semantics: this framework's predict goes LEFT iff x[f] <= threshold.
    Treelite records a comparison_op per split; for "<" the equality case must go
    right, so the threshold is nudged to the previous float32 (x <= prev(t) iff
    x < t for float32 inputs). "<=" imports unchanged.

    Missing values: predict routes NaN LEFT (NaN > t is false), which matches
    treelite's default_left=True. Nodes dumped with default_left=False would
    misroute NaN features — flagged with a warning on import since this engine
    has no per-node missing-direction bit.
    """
    nodes = {n["node_id"]: n for n in tree["nodes"]}
    leaf_key = "leaf_class_probs" if is_classification else "leaf_value"
    if any(
        n.get("default_left") is False
        for n in tree["nodes"]
        if "left_child" in n
    ):
        import warnings

        warnings.warn(
            "treelite dump contains default_left=False splits; this engine "
            "routes NaN/missing features LEFT, so predictions on rows with "
            "missing values may differ from the source model",
            stacklevel=3,
        )

    def conv(node_id: int) -> Dict:
        n = nodes[node_id]
        if "leaf_value" in n or "leaf_vector" in n:
            v = n.get("leaf_vector", n.get("leaf_value"))
            payload = list(v) if isinstance(v, (list, tuple)) else [float(v)]
            if is_classification and len(payload) < 2:
                raise ValueError(
                    "classification import needs per-class leaf_vector "
                    "probabilities (cuML RF dumps these); scalar leaves are "
                    "margin/regression outputs"
                )
            return {leaf_key: payload}
        op = n.get("comparison_op", "<=")
        thr = float(n["threshold"])
        if op == "<":
            thr = _prev_f32_ftz(thr)
        elif op != "<=":
            raise ValueError(f"unsupported treelite comparison_op {op!r}")
        return {
            "split_feature": int(n["split_feature_id"]),
            "threshold": thr,
            "default_left": bool(n.get("default_left", True)),
            "left_child": conv(n["left_child"]),
            "right_child": conv(n["right_child"]),
        }

    return {"root": conv(int(tree.get("root_id", 0)))}


def forest_from_treelite_json(
    model_json: Dict | List[Dict],
    is_classification: bool,
    n_features: int | None = None,
) -> Dict[str, np.ndarray]:
    """Import a treelite JSON dump (cuML `dump_as_json`, what the reference's
    models carry as `treelite_model` JSON, reference tree.py:534-559) into the
    heap-layout forest arrays. Accepts either the full model dict (with `trees`
    and `num_feature`) or a bare list of tree dicts (then n_features is required)."""
    if isinstance(model_json, dict):
        trees = model_json["trees"]
        if n_features is None:
            n_features = int(model_json.get("num_feature", 0)) or None
    else:
        trees = model_json
    if n_features is None:
        raise ValueError(
            "n_features is required when the dump carries no num_feature"
        )
    nested = [
        {"tree_id": i, **_treelite_tree_to_nested(t, is_classification)}
        for i, t in enumerate(trees)
    ]
    return forest_from_json(nested, int(n_features), is_classification)
