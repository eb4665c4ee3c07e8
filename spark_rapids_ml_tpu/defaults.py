#
# The one home of the numeric tile and threshold defaults, set from the
# `tools/` probes. Each constant carries the measurement that set it; to
# re-tune for a chip, run the probe under `tools/` and edit the value here.
# The analyzer's fence/hardcoded-tunable rule keeps new numeric literals out
# of ops/, so the split cannot silently regress.
#
# Nothing here reads config: a call site that has a config key reads it
# first and falls back to these values when the key is unset or holds its
# "choose for me" sentinel (auto / 0).
#

from __future__ import annotations

# --------------------------------------------------------- selection plane
# exact_tiled tile width (ops/selection.py::_auto_tile): on TPU small fixed
# tiles vectorize the per-tile select on the VPU; on CPU each XLA TopK custom
# call pays per-call overhead, so few large tiles win.
TPU_SELECT_TILE = 2048
CPU_SELECT_TILE_FLOOR = 8192
CPU_SELECT_TILE_DENOM = 4  # CPU tile = max(floor, ceil(n / denom))


def default_select_tile(n: int, backend: str) -> int:
    """The platform tile heuristic."""
    if backend == "tpu":
        return TPU_SELECT_TILE
    return max(CPU_SELECT_TILE_FLOOR, -(-int(n) // CPU_SELECT_TILE_DENOM))


# ---------------------------------------------- fused pallas scan geometry
# (ops/pallas_select.py) — the query block bounds the (block, tile) distance
# tile in VMEM (256*1024*4 = 1 MiB) next to one double-buffered X tile; the
# assignment form streams ROWS against resident centers. Floors are what the
# VMEM-budget shrink loops halve toward; a floor-sized scan always fits.
DEFAULT_QUERY_BLOCK = 256
DEFAULT_ITEM_TILE = 1024
DEFAULT_ASSIGN_BLOCK = 2048
MIN_ASSIGN_BLOCK = 256
MIN_QUERY_BLOCK = 8
MIN_ITEM_TILE = 128

# k >= this engages the fused assignment/Lloyd paths under `auto` on TPU:
# below it the (B, k) tiles pad k to the 128-lane MXU width and the XLA
# path's two-read formulation is already at its HBM roofline (the measured
# small-k loss region of ops/pallas_kmeans.py).
FUSED_ASSIGN_MIN_K = 128
LLOYD_FUSED_MIN_K = 128

# The XLA Lloyd program (ops/kmeans.py::lloyd_fit) ranks its assignment at
# three MXU passes, with a six-pass second look at the rows three passes
# cannot rank, from LLOYD_FUSED_MIN_K centres on where centres x columns
# reaches this. No config key reads it. What the mechanism saves grows
# with k.d (three passes of 2.k.d FLOP a row); what it adds does not (a sort
# of the rows, a gather, a wider reduction): on a v5e it is 2 to 3 times
# SLOWER at k.d of 2,560 to 32,768, 1.10 to 1.19 at 60,000 to 384,000
# (k=128 at 3000 columns: 1.095, six passes still half hidden behind the read
# of X), and 0.67 to 0.85 of the six-pass iteration at every one of the six
# shapes of 524,288 from 64 to 2048 columns, 0.74 at 3,000,000
# (tools/lloyd_assign_bench.py; PERF.md §6, PR 31). One row in
# LLOYD_RECHECK_SHARE of a row shard may take the second look in an iteration;
# more undecided rows send the iteration to six passes whole, so the share
# sets a speed and never a result (a thirty-second read the same fit time at
# the cell's shape, 2.433 against 2.442 s).
LLOYD_ASSIGN3_MIN_WORK = 1 << 19
LLOYD_RECHECK_SHARE = 16

# The XLA Gram program (ops/linalg.py::_centered_gram, PCA's covariance past
# the Pallas kernel's 512 columns, with a weightCol, in float64 or off a TPU)
# cuts the columns into blocks of GRAM_BLOCK_COLS from GRAM_TRIANGLE_MIN_COLS
# columns on, multiplies block I against the columns from I's first to the
# last only (the block pairs I <= J of the symmetric matrix), and mirrors the
# result once. No config key reads them. Seconds a 4,096-row part as a
# share of the single matmul's, one v5e, six passes, by columns
# (tools/gram_triangle_bench.py; PERF.md §6, PR 33):
#
#   columns          576    640    768   1024   1536   2048   3000   4096
#   256 a block    0.785  0.870  0.852  0.723  0.641  0.625  0.633  0.588
#   384            0.830  0.918  0.902  0.757  0.697  0.670  0.561  0.605
#   512            0.893  0.997  0.891  0.798  0.695  0.731  0.581  0.603
#   768                                 0.873  0.768  0.718  0.610  0.628
#
# No width loses at any column count measured, so the threshold is the
# sweep's lower edge and nothing under it was measured. 384: the least at
# upstream's 3000 columns (where 256 is the worst of the four), within 0.02
# of the least at 4096 and within 0.06 of 256 below; one matmul a block PAIR
# in place of one a block row read 0.596 at 3000 and 0.694 at 4096 columns in
# 512-column blocks, and 0.835 at 2048, where all its blocks are alike.
GRAM_TRIANGLE_MIN_COLS = 576
GRAM_BLOCK_COLS = 384

# Rows per centre (ops/kmeans.py::assign_counts). No config key reads
# them. Up to COUNT_DEVICE_MAX_CENTERS centres the device compares every row
# with every centre id and sums the hits in one fusion: work of n x centres,
# 1.5 ms at 81 centres and 75 ms at 8,193 for 8.4M rows on a v5e, where
# fetching the labels for np.bincount costs 0.09 s (0.19 s weighted) at any
# count; above it (an IVF build of nlist > 2,048) the host's way is the faster
# one and stays (tools/kmeans_count_bench.py; PERF.md §6, PR 27). Both sides
# grow with n alike, so the boundary is a centre count. COUNT_BLOCK_SPLIT:
# float32 weights are summed within this many row blocks a row shard and the
# blocks added in float64 on the host; sums of 0/1 weights stay exact while a
# block holds at most 2**24 rows.
COUNT_DEVICE_MAX_CENTERS = 8192
COUNT_BLOCK_SPLIT = 64

# The quasi-Newton LogisticRegression fit's one-read evaluation
# (ops/pallas_logistic.py): samples of one feature-major block (d, blk) of the
# table. No config key reads them. A block holds at most
# LOGISTIC_EVAL_BLOCK_BYTES of X (the pipeline holds two: the call raises its
# scoped-VMEM limit past the 16 MiB default, v5e has 128 MiB), a power of two
# of samples between LOGISTIC_EVAL_MIN_BLOCK_ROWS (a width whose 256-sample
# block does not fit, past 8192 columns, keeps the two XLA passes) and
# LOGISTIC_EVAL_MAX_BLOCK_ROWS (the largest the chip has run). One v5e, ms an
# evaluation by samples a block (tools/logistic_eval_bench.py; PERF.md §6,
# PR 35), where the two XLA passes take 11.34 and 6.47:
#
#   samples a block        256     512    1024    2048    4096
#   357,376 x 3000       5.695   5.763   5.750   5.733      -
#   2,000,000 x 300          -   3.808   3.245   3.219   3.222
#
# At 3000 columns any size streams at the HBM rate (744 to 753 GB/s); at 300 a
# 512-sample block is 0.6 MB and the grid's steps show (630 GB/s), from 1.2 MB
# on they do not (740 to 745).
LOGISTIC_EVAL_BLOCK_BYTES = 8 << 20
LOGISTIC_EVAL_MIN_BLOCK_ROWS = 256
LOGISTIC_EVAL_MAX_BLOCK_ROWS = 4096

# k <= this is where `auto` may hand a top-k scan to the fused running-pool
# kernel. NOT a tunable: it is the largest k Mosaic compiled on a v5e (PR 21,
# jax 0.9.0 / libtpu 0.0.34; k=10 and k=32 agree with XLA and numpy). The
# kernel unrolls its k-step extraction and every step leaves three lane-padded
# (q_block, 1) columns on the VMEM stack, which `topk_fits_vmem` does not
# model: at the default (256, 1024) geometry k=64 is refused with
# "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem ... Scoped
# allocation with size 24.84M and limit 16.00M exceeded scoped vmem limit by
# 8.84M" (k=128: 48.57M, k=256: 96.09M after a 335 s compile). Above the bound
# `auto` keeps the XLA strategies; an explicit `pallas_fused` still reaches
# the kernel and fails loudly. ROADMAP D9 owns the real fix.
FUSED_TOPK_MAX_K = 32

# ----------------------------------------------------- other pallas kernels
# segment-reduce histogram (ops/pallas_histogram.py)
PALLAS_HISTOGRAM_BLOCK_ROWS = 512
PALLAS_HISTOGRAM_MAX_SEG_TILE = 2048
# grouped form (rows sorted by node, ISSUE 38): rows a work item and packed
# words (four one-byte bin ids each) a grid step. Provenance: 512 rows x 32
# features is 0.27e9 multiply-adds a step, ten times a grid step's fixed cost,
# with a 2 MiB accumulator block (32 x 128 bins x 128 lanes, float32); a level
# of 357,376 x 3000 runs in 0.224 s, 0.18 s being the MXU's floor for the bin
# indicators' n*d*128*128 multiply-adds (my chip run, PR 38).
PALLAS_HISTOGRAM_GROUP_BLOCK_ROWS = 512
PALLAS_HISTOGRAM_WORDS_PER_STEP = 8
# bytes of one feature tile's (nodes, features, bins, statistics) histogram in
# the forest's level step (ops/trees.py): the split search holds about five
# arrays of this size at once, and nothing of a whole level's size
FOREST_HIST_TILE_BYTES = 256 * 1024 * 1024
# columns of a forest's 200,000-row edge sample gathered and sorted at once on
# the device (ops/trees.py::forest_edges); the block's temporaries are about
# 2.9 x its 200,000 x cols x 4 bytes. No config key reads it: the sort
# sets the pace at any width. Seconds for all 3000 columns of the sample of
# 357,376 rows on one v5e, dispatch to the order statistics on the host
# (tools/forest_edges_probe.py; PERF.md §6, PR 40), where the host's sorted
# column blocks take 3.59 s on 13 cores; a stable sort carries an index to
# break ties, which equal int32 keys need not:
#
#   columns a block                256     512    1024    1536
#   float32 values, stable       1.232   1.232   1.216   1.217
#   float32 values, unstable         -   0.711       -       -
#   int32 keys, stable               -   1.153       -   1.139
#   int32 keys, unstable (used)      -   0.619       -   0.647
#   temporaries, GB               0.73    1.46    2.93    4.39
FOREST_EDGE_BLOCK_COLS = 512

# ------------------------------------------------------------ ANN lifecycle
# (ops/ann_streaming.py + ops/ann_lifecycle.py, docs/design.md §7b)
#
# ANN_BUILD_BATCH_ROWS: the pipelined build's row-batch geometry when neither
# config (`ann.build_batch_rows`) decides. Provenance:
# 64k f32 rows at the BASELINE 256-col shape is a 64 MiB staging buffer — two
# in flight (prefetch depth 1) stay far under the 2 GiB default cache budget
# while each batch still amortizes dispatch overhead; the streamed-fit default
# (`stream_batch_rows`, 1M rows) remains the fallback when the caller already
# sized batches for a whole fit.
ANN_BUILD_BATCH_ROWS = 1 << 16
# --------------------------------------------------------- ingest / fusion
# (ops/ingest.py + pipeline.py, docs/design.md §6k)
#
# INGEST_STAGING_POOL_ROWS: rows per pooled staging buffer backing the counted
# copy fallback of the zero-copy ingest plane. Provenance: matches
# ANN_BUILD_BATCH_ROWS' rationale — 64k f32 rows at the BASELINE 256-col shape
# is a 64 MiB buffer; one per (dtype, width) key covers the double-buffered
# prefetch without the pool itself rivaling the HBM cache budget.
INGEST_STAGING_POOL_ROWS = 1 << 16
# PIPELINE_FUSE_MIN_ROWS: rows below which the pipeline fuser leaves a
# featurize->fit chain staged. Provenance: under ~4k rows a staged chain's
# extra host round-trip is < 1 ms on every measured platform — less than the
# fused chain's extra accumulator compile — and the staged trace is the one
# worth reading when debugging toy inputs.
PIPELINE_FUSE_MIN_ROWS = 4096

# ANN_LIST_BUCKET_MIN_ROWS: smallest bucketed IVF list capacity. Provenance:
# mirrors `serving.bucket_min_rows`'s floor rationale — below 8 slots the
# pow-2 ladder would re-layout on nearly every add; at 8 the padded-slot waste
# is bounded by one sub-KiB row block per list at d=16.
ANN_LIST_BUCKET_MIN_ROWS = 8
# ANN_COMPACT_TOMBSTONE_PCT: tombstoned slots as a percentage of occupied
# slots that triggers list compaction. Provenance: at 30% the probe scan's
# wasted candidate width stays under ~1.4x live width (the select is
# width-bound, not item-bound), while compaction — a full re-layout — stays
# rare under churny delete/add traffic.
ANN_COMPACT_TOMBSTONE_PCT = 30

# ------------------------------------------------- continuous-learning plane
# (spark_rapids_ml_tpu/continual/, docs/design.md §7d)
#
# CONTINUAL_DECAY: per-update discount applied to the persistent sufficient-
# statistics carry before each partial_fit fold. Provenance: 1.0 is the
# streaming-kmeans paper's a=1 "infinite memory" setting (arxiv 1505.06807)
# — forgetting is an opt-in policy decision, so the default never silently
# down-weights history; half-life h maps to decay = 0.5 ** (1 / h) updates.
CONTINUAL_DECAY = 1.0
# CONTINUAL_UPDATE_BATCH_ROWS: fixed block geometry partial_fit re-blocks
# every update batch to (zero-weight padding on the ragged tail). Provenance:
# 16k f32 rows at the BASELINE 256-col shape is a 16 MiB block — small enough
# that an update cycle stays sub-second (continual updates are latency-bound,
# unlike the 64k-row throughput-bound ANN builds), and a single power-of-two
# geometry keeps the whole update stream inside ONE compiled executable per
# accumulator kernel.
CONTINUAL_UPDATE_BATCH_ROWS = 1 << 14
# CONTINUAL_DRIFT_MADS: MADs of separation above the baseline median a fresh
# per-row signal needs to fire drift. Provenance: the usual 3-MAD separation
# before calling two samples different; drift is that judgment (is this
# batch's loss a new distribution or the old one's noise?).
CONTINUAL_DRIFT_MADS = 3.0

# ------------------------------------------------------------- trace plane
# (spark_rapids_ml_tpu/observability/tracing.py, docs/design.md §6l)
#
# TRACING_SAMPLE_RATE: fraction of UNFLAGGED request traces the tail sampler
# keeps (error/hedged/failed-over/expired/shed and the rolling-slowest
# tracing.slow_frac are always kept regardless). Provenance: 1.0 — the ring
# is already bounded (tracing.ring_traces docs) and a finished trace document
# costs ~1-2 KiB to assemble, so at bench-measured request rates keeping
# everything sits inside the <2% tracing_overhead budget the CI gate
# enforces; `tracing.sample_rate` dials retention down for high-QPS
# deployments where the document build on the scatter path matters.
TRACING_SAMPLE_RATE = 1.0
