#
# Pallas TPU kernel: segment histogram via one-hot matmuls.
#
# The forest builder's hot op is the (node, feature, bin, stat) histogram
# (ops/trees.py _histogram). XLA lowers jax.ops.segment_sum to sort/scatter — the
# weakest op class on TPU (no hardware scatter). The TPU-native formulation is an
# MXU one-hot contraction: for each feature and each tile of segment ids,
#     hist_tile = onehot(seg_ids_block)ᵀ @ values_block
# with the one-hot built on the fly in VMEM (never materialized in HBM) and the
# output tile accumulated across row blocks by grid revisiting.
#
# Grid: (features, segment-tiles, row-blocks) — row-blocks innermost so each output
# tile is revisited consecutively and zeroed on the first visit. Block shapes follow
# Mosaic tiling rules: every minor dimension is either a multiple of the lane width
# or the full array dimension (seg ids travel transposed (d, n) with a full-d block;
# the kernel selects its feature row with program_id).
#
# The segment tile adapts to the level width (min(2048, n_segments rounded up to
# 128)) so shallow tree levels don't pay for a 2048-wide one-hot.
#
# Dispatch is an explicit `use_pallas` static argument threaded from forest_fit —
# NOT read from the environment inside traced code (jit caches would make a
# trace-time env read sticky). Multi-device note: pallas_call has no GSPMD
# partitioning rule, so on a mesh of several devices the two one-hot kernels
# above run per row shard under shard_map and the partial histograms are psummed
# (`_shard_psum`); the segment_sum path is what a CPU runs.
#
# Three forms of the forest's level histogram live here, and `hist_gate` says
# from shapes which one a level takes:
#   * `node_bin_histogram_pallas` ("direct"): the node one-hot spans EVERY node
#     of the level, so a level costs 2*n*d*width*nbins*s MXU operations: it
#     doubles with every level. Kept for rows sharded over several devices
#     (under shard_map with a psum) and for ids that are not packed four a word
#     (the streamed tier).
#   * `grouped_histogram_tile` ("grouped"): rows sorted by node (one argsort and
#     one row gather of the one-byte bin matrix a level), so a block of rows
#     meets few nodes and its node one-hot spans one tile of `node_tile(s)`
#     nodes only; a (row block, node tile) work list goes in by scalar prefetch
#     (the grouped-matmul pattern). A level costs n*d*nbins*128 multiply-adds
#     whatever its width, plus one work item a node tile.
#   * segment_sum ("xla"): off the TPU.
#

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tile defaults live in the knob-registry defaults module (docs/design.md
# §6i; the analyzer's fence/hardcoded-tunable rule bans new literals in ops/)
from ..autotune.defaults import (  # re-exported tile defaults
    PALLAS_HISTOGRAM_BLOCK_ROWS as BLOCK_ROWS,
    PALLAS_HISTOGRAM_GROUP_BLOCK_ROWS as GROUP_BLOCK_ROWS,
    PALLAS_HISTOGRAM_MAX_SEG_TILE as MAX_SEG_TILE,
    PALLAS_HISTOGRAM_WORDS_PER_STEP as WORDS_PER_STEP,
)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _hist_kernel(seg_ref, val_ref, out_ref, *, seg_tile: int):
    """seg_ref: (d, BLOCK_ROWS) int32 (all features for this row block);
    val_ref: (BLOCK_ROWS, s); out_ref: (1, seg_tile, s), revisited across row
    blocks."""
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    j = pl.program_id(0)
    c = pl.program_id(1)
    seg = seg_ref[j, :]  # (BLOCK_ROWS,)
    local = seg - c * seg_tile
    cols = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, seg_tile), 1)
    onehot = (cols == local[:, None]).astype(val_ref.dtype)  # (BLOCK_ROWS, seg_tile)
    partial = jax.lax.dot_general(
        onehot,
        val_ref[...],
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (seg_tile, s)
    out_ref[...] += partial[None, :, :]


@functools.partial(jax.jit, static_argnames=("n_segments", "interpret"))
def segment_histogram_pallas(
    seg_ids: jax.Array,  # (n, d) int32: per-feature segment id in [0, n_segments)
    values: jax.Array,  # (n, s) float32
    n_segments: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns (d, n_segments, s)."""
    n, d = seg_ids.shape
    s = values.shape[1]

    pad_rows = (-n) % BLOCK_ROWS
    if pad_rows:
        # padded rows carry zero values, so whatever segment they point at gains 0
        seg_ids = jnp.pad(seg_ids, ((0, pad_rows), (0, 0)), constant_values=0)
        values = jnp.pad(values, ((0, pad_rows), (0, 0)))
    n_padded = seg_ids.shape[0]
    seg_t = seg_ids.T  # (d, n): minor dim = rows, blocked at BLOCK_ROWS (128-aligned)

    seg_tile = min(MAX_SEG_TILE, _round_up(n_segments, 128))
    c_tiles = _round_up(n_segments, seg_tile) // seg_tile

    out = pl.pallas_call(
        functools.partial(_hist_kernel, seg_tile=seg_tile),
        name="hist_segments",
        grid=(d, c_tiles, n_padded // BLOCK_ROWS),
        in_specs=[
            pl.BlockSpec((d, BLOCK_ROWS), lambda j, c, b: (0, b)),
            pl.BlockSpec((BLOCK_ROWS, s), lambda j, c, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, seg_tile, s), lambda j, c, b: (j, c, 0)),
        out_shape=jax.ShapeDtypeStruct((d, c_tiles * seg_tile, s), jnp.float32),
        interpret=interpret,
    )(seg_t, values)
    return out[:, :n_segments, :]


def _rows_spec(ndim: int):
    """Rows over the data axis (`Partitioner.data_spec`, without asking for the
    active partitioner from inside a trace)."""
    from jax.sharding import PartitionSpec

    from ..parallel.mesh import DATA_AXIS

    return PartitionSpec(*([DATA_AXIS] + [None] * (ndim - 1)))


def _shard_psum(mesh, in_specs, local_fn):
    """shard_map wrapper shared by both histogram entry points: run local_fn on
    each device's row shard, psum the partial histograms over the mesh."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        check_vma=False,
    )
    def _wrapped(*args):
        return jax.lax.psum(local_fn(*args), DATA_AXIS)

    return _wrapped


def _nb_hist_kernel(
    n_rows,
    d_tile,
    w_tile,
    nbins,
    s,
    x_ref,  # (d_tile, B) int32 bin ids, this feature tile
    node_ref,  # (B, 1) int32 node ids
    val_ref,  # (B, s)
    out_ref,  # (d_tile, w_tile, nbins * s) accumulated across row blocks
):
    """Factored node x bin histogram block: one MXU contraction per feature.

    The v1 kernel one-hots the flattened (node*nbins+bin) segment id, whose cost
    scales with width*nbins per row — at depth 8 that is ~0.5e15 compares for a
    4M x 64 input (TPU-measured 6 s/tree). Here the one-hot factorizes:
        out[j, w, b*s+si] = sum_r [node==w] * [X[r,j]==b] * val[r,si]
    with the bin membership and the stat values fused into ONE (B, nbins*s)
    right-hand side (tile val nbins times along lanes, mask by bin equality), so
    each feature contributes a single (w_tile, B) @ (B, nbins*s) MXU dot."""
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = pl.program_id(1)
    B = val_ref.shape[0]

    rows = b * B + jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    valid = rows < n_rows  # ragged tail: no host-side pad copy (NaN-safe select)
    val = jnp.where(valid, val_ref[...], 0.0)  # (B, s)
    nodes = jnp.where(valid, node_ref[...], -1)  # (B, 1); -1 matches no node

    local = nodes - c * w_tile  # (B, 1)
    wcols = jax.lax.broadcasted_iota(jnp.int32, (B, w_tile), 1)
    onehot_n = (wcols == local).astype(val.dtype)  # (B, w_tile)

    cols = jax.lax.broadcasted_iota(jnp.int32, (B, nbins * s), 1)
    bin_of = cols // s  # static pattern: [0,0,0,1,1,1,...] for s=3
    val_tiled = jnp.tile(val, (1, nbins))  # (B, nbins*s), si = cols % s

    for j in range(d_tile):
        bins_j = x_ref[j, :][:, None]  # (B, 1)
        rhs = jnp.where(bin_of == bins_j, val_tiled, 0.0)  # (B, nbins*s)
        out_ref[j, ...] += jax.lax.dot_general(
            onehot_n,
            rhs,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (w_tile, nbins*s)


@functools.partial(
    jax.jit, static_argnames=("width", "nbins", "interpret", "blk", "feature_major")
)
def node_bin_histogram_pallas(
    Xb: jax.Array,  # (n, d) int32 bin ids in [0, nbins)
    node_id: jax.Array,  # (n,) int32 in [0, width)
    values: jax.Array,  # (n, s) f32, zero rows contribute nothing
    width: int,
    nbins: int,
    interpret: bool = False,
    blk: int = 512,
    feature_major: bool = False,
) -> jax.Array:
    """Returns (width, d, nbins, s) — the forest builder's level histogram, or
    (d, width, nbins, s), the kernel's own order, under `feature_major`. The
    node one-hot spans every node of the level: 2*n*d*width*nbins*s MXU
    operations, so the cost is independent of the flattened (node, bin) segment
    count but NOT of the level's width (it doubles a level); `hist_gate` keeps
    it to what the grouped form cannot take.

    blk=512 is the VMEM-safe default: Mosaic allocates the d_tile unrolled
    per-feature (blk, lane) rhs buffers WITHOUT reuse, so scoped-VMEM usage is
    ~d_tile*blk*512B — blk=2048 at d_tile=32 was observed to blow the 16 MiB
    limit (38 MiB stack)."""
    n, d = Xb.shape
    s = values.shape[1]

    # tiles: two VMEM constraints bound d_tile. (a) the output block
    # (d_tile, w_tile, lane) stays <=4 MiB; (b) Mosaic materializes the d_tile
    # unrolled per-feature (blk, lane) rhs buffers WITHOUT reuse, so their stack
    # must stay <=6 MiB — (a) alone explodes at shallow levels (w_tile=1 gives
    # budget 8192 -> d_tile=d -> 25 MiB of rhs at d=128, a hardware-only OOM
    # interpret-mode tests can never catch).
    w_tile = min(width, 256)
    c_tiles = _round_up(width, w_tile) // w_tile
    lane = nbins * s
    lane_pad = _round_up(lane, 128)
    if lane_pad > 128:
        # past one lane tile the (blk, lane) temporaries beside the rhs buffers
        # count too: 128 bins x 2 classes at blk=512 and 8 features a step asked
        # for 16.49 MiB of scoped VMEM (limit 16) when compiled for a v5e
        blk = min(blk, 256)
    out_budget = 4 * 1024 * 1024 // (w_tile * lane_pad * 4)
    rhs_budget = 6 * 1024 * 1024 // (blk * lane_pad * 4)
    d_tile = max(1, min(d, out_budget, rhs_budget))
    if d_tile < d:
        # a block's second-minor side is the whole array's or a multiple of 8
        # (128 bins x 2 classes gave 12: Mosaic refused the block at lowering)
        d_tile = max(8, d_tile // 8 * 8)
    d_tiles = _round_up(d, d_tile) // d_tile
    d_pad = d_tiles * d_tile - d
    Xt = Xb.T  # (d, n)
    if d_pad:
        # padded features histogram into real bins but are sliced off below
        Xt = jnp.pad(Xt, ((0, d_pad), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_nb_hist_kernel, n, d_tile, w_tile, nbins, s),
        name="hist_node_bins",
        grid=(d_tiles, c_tiles, (n + blk - 1) // blk),
        in_specs=[
            pl.BlockSpec((d_tile, blk), lambda j, c, b: (j, b)),
            pl.BlockSpec((blk, 1), lambda j, c, b: (b, 0)),
            pl.BlockSpec((blk, s), lambda j, c, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec(
            (d_tile, w_tile, nbins * s), lambda j, c, b: (j, c, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (d_tiles * d_tile, c_tiles * w_tile, nbins * s), jnp.float32
        ),
        interpret=interpret,
    )(Xt, node_id[:, None], values)
    out = out[:d, :width, :].reshape(d, width, nbins, s)
    return out if feature_major else out.transpose(1, 0, 2, 3)  # (width, d, nbins, s)


def node_bin_histogram(
    Xb: jax.Array,
    node_id: jax.Array,
    values: jax.Array,
    width: int,
    nbins: int,
    use_pallas: bool = False,
    mesh=None,
    feature_major: bool = False,
) -> jax.Array:
    """(width, d, nbins, s) level histogram ((d, width, nbins, s) under
    `feature_major`, every path's own order); pallas factored kernel on TPU, with
    the same shard_map+psum wrapping as segment_histogram for a multi-device mesh."""
    if use_pallas:
        interpret = _interpret()

        def _local_hist(x_local, node_local, val_local):
            return node_bin_histogram_pallas(
                x_local, node_local, val_local, width, nbins, interpret=interpret,
                feature_major=feature_major,
            )

        if mesh is not None and mesh.devices.size > 1:
            return _shard_psum(
                mesh, (_rows_spec(2), _rows_spec(1), _rows_spec(2)), _local_hist,
            )(Xb, node_id, values)
        return _local_hist(Xb, node_id, values)

    seg_ids = node_id[:, None] * nbins + Xb  # (n, d)
    hist = segment_histogram(seg_ids, values, width * nbins, use_pallas=False)
    d = Xb.shape[1]
    hist = hist.reshape(d, width, nbins, values.shape[1])
    return hist if feature_major else hist.transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# Grouped form: rows sorted by node, the node one-hot local to a row block
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    """Mosaic lowers for a TPU only (tests reach the kernels, interpreted, by
    patching this)."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Off a TPU the Pallas forms run interpreted, whatever the gate was told."""
    return jax.default_backend() != "tpu"


def node_tile(s: int) -> int:
    """Nodes a row block's one-hot spans in the grouped form: as many as fill
    the MXU's 128 output lanes at `s` statistics a node, and at least 8."""
    w = 8
    while 2 * w * s <= 128:
        w *= 2
    return w


def hist_gate(width: int, d: int, nbins: int, s: int, n: int,
              devices: int = 1) -> Tuple[bool, str]:
    """Whether a level of `width` nodes takes the grouped histogram, and which
    test decided it, from shapes alone: `platform` (Mosaic lowers for a TPU
    only; the XLA segment_sum runs), `devices` (rows sharded over several
    devices keep the one-hot kernel a shard and its psum), `bins` (the grouped
    kernel reads four one-byte ids a word; a caller whose ids are not packed,
    the streamed tier, asks with nbins as 257). `width`, `d`, `s` and `n`
    decide nothing: the grouped kernel's level costs the same at every width
    and less than the one-hot kernel's even at ONE node (357,376 x 3000, 128
    bins, two classes on a v5e: 0.224 s a level at 1 to 64 nodes against 1.56 s,
    `tools/forest_level_bench.py`, PERF.md section 6 PR 38), so wherever it can
    run it does."""
    del width, d, s, n
    if not _on_tpu():
        return False, "platform"
    if devices > 1:
        return False, "devices"
    if nbins > 256:
        return False, "bins"
    return True, "ok"


def group_rows(node_id: jax.Array, values: jax.Array, width: int,
               operand_dtype=jnp.float32) -> Dict[str, jax.Array]:
    """What the grouped kernel needs of a level's rows, computed by XLA inside
    the tree's program: `order` (the argsort of the node ids, None while one
    node tile holds the whole level and nothing moves), `lhs` (n, lanes): each
    row's statistics in the lanes of its node's place in its tile, `trow` (n, 1):
    the row's node tile, and the work list: (`blk`, `tile`) pairs in row order
    with `first` (the pair opens its tile: zero the accumulator) and `valid`
    (the list is padded to its static bound, row blocks + node tiles - 1),
    and `visited` (tiles with no row are never written)."""
    n, s = values.shape
    w = node_tile(s)
    n_tiles = -(-width // w)
    lanes = _round_up(w * s, 128)
    blk = GROUP_BLOCK_ROWS
    nb = -(-n // blk)
    if n_tiles == 1:
        order, ng, vg = None, node_id, values
    else:
        order = jnp.argsort(node_id)
        ng, vg = node_id[order], values[order]
    lhs = (  # statistic-major lanes: statistic i of tile node c at lane i * w + c
        vg.astype(operand_dtype)[:, :, None]
        * jax.nn.one_hot(ng % w, w, dtype=operand_dtype)[:, None, :]
    ).reshape(n, s * w)
    lhs = jnp.pad(lhs, ((0, 0), (0, lanes - s * w)))
    trow = (ng // w).astype(jnp.int32)

    starts = jnp.arange(nb, dtype=jnp.int32) * blk
    t_first = trow[starts]
    t_last = trow[jnp.minimum(starts + blk, n) - 1]
    count = t_last - t_first + 1
    incl = jnp.cumsum(count)
    n_items = nb + n_tiles - 1  # sorted rows: a new item is a new block or a new tile
    g = jnp.arange(n_items, dtype=jnp.int32)
    valid = g < incl[-1]
    b = jnp.minimum(jnp.searchsorted(incl, g, side="right"), nb - 1).astype(jnp.int32)
    tile = jnp.where(valid, t_first[b] + g - (incl[b] - count[b]), t_last[nb - 1])
    first = valid & jnp.concatenate([jnp.ones((1,), bool), tile[1:] != tile[:-1]])
    return {
        "order": order, "lhs": lhs, "trow": trow[:, None],
        "blk": jnp.where(valid, b, nb - 1), "tile": tile.astype(jnp.int32),
        "first": first.astype(jnp.int32), "valid": valid.astype(jnp.int32),
        "visited": jnp.zeros((n_tiles,), bool).at[tile].set(True),
    }


def _grouped_kernel(blk_ref, tile_ref, first_ref, valid_ref, qb0_ref,
                    p_ref, lhs_ref, trow_ref, out_ref, *, n_rows, nbins_pad):
    """One (row block, node tile) work item on one step's packed words.
    p_ref: (WORDS_PER_STEP, B) int32, four one-byte bin ids a word, rows along
    lanes (byte k of word i lands at out_ref[k, i]); lhs_ref: (B, lanes) the rows' statistics at their node's lanes;
    trow_ref: (B, 1) node tile of each row; out_ref: (4, WORDS_PER_STEP, 1,
    nbins_pad, lanes), revisited while the work list stays in one tile. A
    feature is one (nbins_pad, B) @ (B, lanes) contraction: the bin indicator
    is built with bins along sublanes, so nothing is transposed. The indicator
    is "id <= bin", so the sums are CUMULATIVE over bins: the split search
    wants the left child's statistics at every threshold and never a bin's
    own, and the running sum costs the MXU nothing more."""
    del qb0_ref  # the index maps read it
    g = pl.program_id(1)

    @pl.when(first_ref[g] == 1)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(valid_ref[g] == 1)
    def _():
        rows_here = lhs_ref.shape[0]
        rows = blk_ref[g] * rows_here + jax.lax.broadcasted_iota(
            jnp.int32, (rows_here, 1), 0)  # ragged last block: no padded copy
        keep = (rows < n_rows) & (trow_ref[...] == tile_ref[g])
        lhs = jnp.where(keep, lhs_ref[...], jnp.zeros_like(lhs_ref))
        acc_type = jnp.int32 if lhs.dtype == jnp.int8 else jnp.float32
        bins = jax.lax.broadcasted_iota(jnp.int32, (nbins_pad, rows_here), 0)
        for i in range(p_ref.shape[0]):
            word = p_ref[i:i + 1, :]  # (1, B)
            for k in range(4):
                ids = (word >> (8 * k)) & 0xFF
                onehot = (bins >= ids).astype(lhs.dtype)  # (nbins_pad, B): id <= bin
                out_ref[k, i, 0] += jnp.dot(
                    onehot, lhs, preferred_element_type=acc_type
                ).astype(jnp.float32)


def grouped_histogram_tile(pt: jax.Array, grp: Dict[str, jax.Array], tile_index,
                           words: int, width: int, nbins: int, s: int,
                           interpret: bool = False) -> jax.Array:
    """The level histogram of `4 * words` features, CUMULATIVE over bins (entry
    b sums the rows whose id is <= b), one array a statistic: s arrays of (4 *
    words, node tiles, nbins, node_tile(s)), node `a * node_tile(s) + c` at
    [:, a, :, c] (nodes along lanes, as the kernel leaves them: a trailing
    axis of s would be padded to a lane tile).
    `pt`: (Q, n) int32, the one-byte bin ids four a word, feature-major, rows
    in `grp`'s order; `tile_index` (traced) picks words [tile_index * words,
    (tile_index + 1) * words); `grp`: `group_rows` of the level. Which feature
    a byte is, is the caller's packing (`ops/trees.py::tile_features`): the
    feature axis is (byte, word of the tile). Values that
    are whole numbers up to 256 may ride as bfloat16 and up to 127 as int8
    (`group_rows`'s `operand_dtype`): a block's sums are float32 (int32 under
    int8), the accumulator float32."""
    n = pt.shape[1]
    w = node_tile(s)
    n_tiles = -(-width // w)
    lanes = grp["lhs"].shape[1]
    blk = GROUP_BLOCK_ROWS
    steps = words // WORDS_PER_STEP
    nbins_pad = _round_up(nbins, 16)
    qb0 = jnp.reshape(tile_index * steps, (1,)).astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_grouped_kernel, n_rows=n, nbins_pad=nbins_pad),
        name="hist_grouped",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps, grp["blk"].shape[0]),
            in_specs=[
                pl.BlockSpec((WORDS_PER_STEP, blk),
                             lambda j, g, b, t, f, v, q: (q[0] + j, b[g])),
                pl.BlockSpec((blk, lanes), lambda j, g, b, t, f, v, q: (b[g], 0)),
                pl.BlockSpec((blk, 1), lambda j, g, b, t, f, v, q: (b[g], 0)),
            ],
            out_specs=pl.BlockSpec((4, WORDS_PER_STEP, 1, nbins_pad, lanes),
                                   lambda j, g, b, t, f, v, q: (0, j, t[g], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (4, words, n_tiles, nbins_pad, lanes), jnp.float32),
        interpret=interpret,
    )(grp["blk"], grp["tile"], grp["first"], grp["valid"], qb0,
      pt, grp["lhs"], grp["trow"])
    out = jnp.where(grp["visited"][None, :, None, None],
                    out.reshape(4 * words, n_tiles, nbins_pad, lanes), 0.0)
    return [out[:, :, :nbins, i * w:(i + 1) * w] for i in range(s)]


def default_use_pallas() -> bool:
    """Pallas histogram is the TPU path for any device count: single-device it is a
    plain pallas_call; on a mesh it runs per-shard under shard_map with a psum merge
    (segment_histogram below). SRML_TPU_PALLAS_HISTOGRAM=1/0 forces it on/off."""
    import os

    forced = os.environ.get("SRML_TPU_PALLAS_HISTOGRAM", "")
    if forced == "1":
        return True
    if forced == "0":
        return False
    return _on_tpu()


def segment_histogram(
    seg_ids: jax.Array,
    values: jax.Array,
    n_segments: int,
    use_pallas: bool = False,
    mesh=None,
) -> jax.Array:
    """Returns (d, n_segments, s). `use_pallas` must be decided OUTSIDE traced code
    (see default_use_pallas). With a multi-device `mesh`, the pallas kernel runs on
    each device's row shard under shard_map and the partial histograms psum over the
    mesh — the same merge point where the segment_sum path's replicated output makes
    XLA psum (so multi-chip RF keeps the MXU kernel; VERDICT r1 weak #6)."""
    if use_pallas:
        interpret = _interpret()

        def _local_hist(seg_local, val_local):
            return segment_histogram_pallas(
                seg_local, val_local, n_segments, interpret=interpret
            )

        if mesh is not None and mesh.devices.size > 1:
            return _shard_psum(
                mesh, (_rows_spec(2), _rows_spec(2)), _local_hist
            )(seg_ids, values)
        return _local_hist(seg_ids, values)

    def all_features(s, v):
        return jax.vmap(
            lambda seg_j: jax.ops.segment_sum(v, seg_j, num_segments=n_segments),
            in_axes=1,
        )(s)

    # the vmapped scatter's update tensor holds n*d*s elements; past ~2^31 the
    # XLA CPU scatter thunk overflows its 32-bit element indexing and SEGFAULTS
    # (observed twice, deterministically, at 2e7 x 64 x 2). Chunk the rows so
    # each scatter stays far below that — zero-padded tail rows hit segment 0
    # with zero values, contributing nothing.
    n, d = seg_ids.shape
    s_dim = values.shape[1]
    chunk = max(1, (1 << 28) // max(d * s_dim, 1))
    if n > chunk:
        pad = (-n) % chunk
        seg_p = jnp.pad(seg_ids, ((0, pad), (0, 0)))
        val_p = jnp.pad(values, ((0, pad), (0, 0)))
        segs = seg_p.reshape(-1, chunk, d)
        vals = val_p.reshape(-1, chunk, s_dim)

        def chunk_step(carry, sv):
            sc, vc = sv
            return carry + all_features(sc, vc), None

        init = jnp.zeros((d, n_segments, s_dim), values.dtype)
        out, _ = jax.lax.scan(chunk_step, init, (segs, vals))
        return out
    return all_features(seg_ids, values)
