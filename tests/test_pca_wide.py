"""PCA past `ops/pallas_xtwx.py::MAX_FUSED_COLS` columns: what the chip cell
`pca_k3_d3000.fit` holds the program to, at a size a CPU can carry, and the
counters and spans that cell reads (docs/design.md §6d). The reference is
numpy float64, written here: nothing of `cellbench/` is imported."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config, profiling
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.observability.export import iter_spans
from spark_rapids_ml_tpu.ops import linalg
from spark_rapids_ml_tpu.ops import pca as pca_ops
from spark_rapids_ml_tpu.ops.pallas_xtwx import MAX_FUSED_COLS

ROWS, WIDE = 2048, MAX_FUSED_COLS + 128
# one column under the width at which the XLA program computes only the upper
# column blocks, and a width of three blocks the last of which is narrow
FULL_COLS = linalg.GRAM_TRIANGLE_MIN_COLS - 1
TRIANGLE_COLS = 2 * linalg.GRAM_BLOCK_COLS + 40


def _table(rows, cols, seed):
    """The cells' PCA table: unit noise, four orthonormal factors of scale
    4, 3, 2, 1 and column means N(0, 1)."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((cols, 4)))
    loadings = q.T * np.array([4.0, 3.0, 2.0, 1.0])[:, None]
    X = (rng.standard_normal((rows, cols)) + rng.standard_normal(cols)[None, :]
         + rng.standard_normal((rows, 4)) @ loadings)
    return X.astype(np.float32)


def _round_bf16(a):
    """float32 -> nearest bfloat16 (ties to even) -> float32: what one MXU pass
    does to an operand."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    return ((u + ((u >> 16) & 1) + np.uint32(0x7FFF)) & np.uint32(0xFFFF0000)).view(np.float32)


def _errors(model, X, k):
    """The four numbers the cell compares, against the float64 covariance of
    the same float32 table and its leading eigenpairs."""
    X64 = X.astype(np.float64)
    mean = X64.mean(axis=0)
    Xc = X64 - mean
    cov = Xc.T @ Xc / (len(X) - 1.0)
    lam, vec = np.linalg.eigh(cov)
    lam, vec = lam[::-1][:k], vec[:, ::-1][:, :k].T
    total = float(np.trace(cov))
    a = {name: np.asarray(v, np.float64) for name, v in model.get_model_attributes().items()}
    sign = np.sign((a["components"] * vec).sum(axis=1))[:, None]
    implied = float(a["explained_variance"][0] / a["explained_variance_ratio"][0])
    return {
        "mean": float(np.abs(a["mean"] - mean).max() / np.sqrt(total / X.shape[1])),
        "components": float(np.abs(a["components"] - sign * vec).max()),
        "explained_variance": float(np.abs(a["explained_variance"] - lam).max() / lam[0]),
        "total_variance": abs(implied - total) / total,
    }


# Off the chip every matmul is true float32, so what separates the fit from
# the float64 reference is float32 accumulation over 2,048 rows and a float32
# eigensolve of 640 columns. One bf16 pass rounds each value to 8 bits of
# mantissa (relative error up to 2^-9): the column means move by that over
# sqrt(rows), the Gram matrix's entries by that over sqrt(rows) too (and its
# diagonal by 1.3e-6 of the value's square besides, always with one sign), and
# the leading directions turn by the rounding noise over the eigengap. Readings
# on seeds 0 to 5, the fit first and the fit of the bf16-rounded table second;
# each limit is seven times or more over the first and three times or more
# under the second.
LIMITS = {
    "mean": 5e-6,  # 4.7e-7 to 7.1e-7; 1.6e-4 to 2.8e-4
    "components": 5e-6,  # 1.6e-7 to 3.6e-7 (a float32 eigh's vectors); 1.2e-4 to 2.3e-4
    "explained_variance": 2e-6,  # 5.8e-8 to 2.3e-7; 6.8e-6 to 7.4e-5
    # 1.0e-8 to 7.2e-8; 4.8e-7 to 9.8e-6: at 2,048 rows the rounding's noise,
    # not its one-signed bias, is most of it, so the upper reading swings
    "total_variance": 3e-7,
}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_wide_fit_agrees_with_the_float64_eigenpairs(seed):
    X = _table(ROWS, WIDE, seed)
    model = PCA(k=3, inputCol="features").fit(X)
    counters = model.fit_report_["metrics"]["counters"]
    assert counters["pca.gram_path{path=xla}"] == 1
    errs = _errors(model, X, 3)
    assert all(errs[name] <= LIMITS[name] for name in LIMITS), errs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_one_bf16_pass_over_the_table_is_told_apart(seed):
    X = _table(ROWS, WIDE, seed)
    model = PCA(k=3, inputCol="features").fit(_round_bf16(X))
    errs = _errors(model, X, 3)
    over = {name for name in LIMITS if errs[name] > LIMITS[name]}
    assert over >= {"mean", "components", "explained_variance"}, errs


# ------------------------------------------------------------------ the gate


def _fit_counters(cols, setting=None):
    X = _table(256, cols, seed=cols)
    if setting is not None:
        config.set("pallas_xtwx", setting)
    try:
        model = PCA(k=3, inputCol="features").fit(X)
    finally:
        config.unset("pallas_xtwx")
    return {name: v for name, v in model.fit_report_["metrics"]["counters"].items()
            if name.startswith("pca.gram_")}


def _triangle(cols):
    return "pca.gram_form{blocks=%d,form=triangle}" % len(linalg.gram_column_blocks(cols))


@pytest.mark.parametrize("cols,setting,want", [
    # past the kernel's width the XLA program runs, whatever the setting
    (WIDE, None, {"pca.gram_gate{fused=0,reason=cols}": 1, "pca.gram_path{path=xla}": 1,
                  _triangle(WIDE): 1}),
    (WIDE, "1", {"pca.gram_gate{fused=0,reason=cols}": 1, "pca.gram_path{path=xla}": 1,
                 _triangle(WIDE): 1}),
    # at its last width the kernel (the interpreter, off the chip) when forced:
    # the XLA program's form is not counted where that program does not run
    (MAX_FUSED_COLS, "1",
     {"pca.gram_gate{fused=1,reason=setting}": 1, "pca.gram_path{path=pallas}": 1}),
    # `auto` wants a TPU, and the tests' CPU is none
    (MAX_FUSED_COLS, None,
     {"pca.gram_gate{fused=0,reason=platform}": 1, "pca.gram_path{path=xla}": 1,
      "pca.gram_form{form=full}": 1}),
    (MAX_FUSED_COLS, "0",
     {"pca.gram_gate{fused=0,reason=setting}": 1, "pca.gram_path{path=xla}": 1,
      "pca.gram_form{form=full}": 1}),
    # the XLA program's single matmul up to one column under the width from
    # which it computes the upper column blocks alone
    (FULL_COLS, None, {"pca.gram_gate{fused=0,reason=cols}": 1, "pca.gram_path{path=xla}": 1,
                       "pca.gram_form{form=full}": 1}),
    (TRIANGLE_COLS, None, {"pca.gram_gate{fused=0,reason=cols}": 1,
                           "pca.gram_path{path=xla}": 1, _triangle(TRIANGLE_COLS): 1}),
])
def test_a_fit_counts_which_gram_ran_and_why(cols, setting, want):
    assert _fit_counters(cols, setting) == want


@pytest.mark.parametrize("cols,unit_weight,dtype,backend,want", [
    (3000, True, np.float32, "tpu", (False, "cols")),  # upstream's benchmark table
    (256, True, np.float32, "tpu", (True, "platform")),
    (256, False, np.float32, "tpu", (False, "weights")),  # a weightCol
    (3000, False, np.float32, "tpu", (False, "weights")),  # asked before the width
    (256, True, np.float64, "tpu", (False, "dtype")),
    (256, True, np.float32, "cpu", (False, "platform")),
])
def test_the_gate_names_the_first_test_that_failed(monkeypatch, cols, unit_weight, dtype,
                                                   backend, want):
    class Device:
        platform = backend

    # what the gate sees of the platform, steered here and not by an option
    monkeypatch.setattr(pca_ops.jax, "devices", lambda: [Device()])
    assert pca_ops.gram_gate(cols, unit_weight, dtype) == want
    assert pca_ops.use_fused_gram(cols, unit_weight, dtype) is want[0]


# ----------------------------------------------------------------- the spans


def test_solve_and_fetch_are_the_children_of_eig_and_close_it():
    profiling.reset_counters()
    model = PCA(k=3, inputCol="features").fit(_table(512, WIDE, seed=9))
    spans = list(iter_spans(model.fit_report_))
    (eig,) = [s for s in spans if s["name"] == "pca.eig"]
    children = {s["name"]: s for s in spans if s["parent_id"] == eig["span_id"]}
    assert sorted(children) == ["pca.eig.fetch", "pca.eig.solve"]
    inside = sum(s["duration_s"] for s in children.values())
    # what lies between them is two span entries and exits, microseconds; the
    # slack is what a loaded test machine may take from this thread meanwhile
    assert 0.0 <= eig["duration_s"] - inside < 0.05
    counters = model.fit_report_["metrics"]["counters"]
    for name in ("pca.eig", "pca.eig.solve", "pca.eig.fetch", "pca.cov"):
        assert counters[f"span.calls{{span={name}}}"] == 1
        assert counters[f"span.seconds{{span={name}}}"] > 0


# ------------------------------------------- the Gram matrix in short partial sums


def _float64_cov(X, w):
    X64, w64 = X.astype(np.float64), w.astype(np.float64)
    mean = (w64[:, None] * X64).sum(axis=0) / w64.sum()
    Xc = X64 - mean
    return (Xc * w64[:, None]).T @ Xc / (w64.sum() - 1.0)


@pytest.mark.parametrize("num_workers", [1, 4])
@pytest.mark.parametrize("rows", [linalg.GRAM_CHUNK_ROWS - 5, 3 * linalg.GRAM_CHUNK_ROWS + 77])
def test_the_covariance_of_a_table_far_from_the_origin(rows, num_workers, n_devices):
    """One matmul under a chunk of rows, a loop of chunks and a remainder over
    it, alone or a shard each: the covariance of the centred rows, whatever the
    column means. Column means of 100 noise widths: the uncentred sufficient
    statistics (S2 - n m m') cancel seven digits of float32's seven there."""
    if num_workers > n_devices:
        pytest.skip(f"needs {num_workers} virtual devices")
    from spark_rapids_ml_tpu.parallel.partitioner import DataParallelPartitioner

    rng = np.random.default_rng(rows)
    X = (rng.standard_normal((rows, 24)) + 100.0).astype(np.float32)
    w = rng.integers(1, 4, rows).astype(np.float32)  # sample weights
    mesh = None
    if num_workers > 1:
        part = DataParallelPartitioner(num_workers)
        pad = -rows % num_workers
        Xd = part.shard(np.concatenate([X, np.zeros((pad, 24), np.float32)]))
        wd = part.shard(np.concatenate([w, np.zeros(pad, np.float32)]))
        mesh = part.mesh
    else:
        Xd, wd = jnp.asarray(X), jnp.asarray(w)
    cov, mean, wsum = linalg.weighted_covariance(Xd, wd, mesh=mesh)
    assert float(wsum) == float(w.sum())
    ref = _float64_cov(X, w)
    # float32's own rounding of a variance of 1 summed over some thousand rows
    # reads 1.2e-7 to 1.6e-7 here; the uncentred form read 3.6e-2 and 5.2e-2
    assert np.abs(np.asarray(cov, np.float64) - ref).max() < 2e-6
    assert np.abs(np.asarray(mean, np.float64) - 100.0).max() < 0.1


def test_no_matmul_of_the_gram_contracts_over_more_than_a_chunk_of_rows():
    """What the repair rests on, read from the lowered program: every matmul
    that yields a (d, d) matrix contracts over `GRAM_CHUNK_ROWS` rows or the
    remainder, never over the table (the column sums' matvec may)."""
    import re

    chunk, d = linalg.GRAM_CHUNK_ROWS, 8
    rows = 5 * chunk + 3
    text = linalg.weighted_covariance.lower(
        jax.ShapeDtypeStruct((rows, d), jnp.float32), jax.ShapeDtypeStruct((rows,), jnp.float32)
    ).as_text()
    dots = [line for line in text.splitlines()
            if "dot_general" in line and f"-> tensor<{d}x{d}xf32>" in line]
    contracted = sorted({int(n) for line in dots
                         for n in re.findall(rf"\(tensor<{d}x(\d+)xf32>, ", line)})
    assert contracted == [3, chunk], (contracted, dots)


def test_a_sharded_covariance_moves_the_state_once_and_no_rows(n_devices):
    from spark_rapids_ml_tpu.observability.comm import collectives_from_executable
    from spark_rapids_ml_tpu.parallel.partitioner import DataParallelPartitioner

    part = DataParallelPartitioner(min(4, n_devices))
    p, d = part.num_workers, 16
    rows = p * (2 * linalg.GRAM_CHUNK_ROWS + 8)
    Xd = part.shard(np.ones((rows, d), np.float32))
    wd = part.shard(np.ones((rows,), np.float32))
    exe = linalg.weighted_covariance.lower(Xd, wd, mesh=part.mesh).compile()
    summary = collectives_from_executable(exe) or {}
    if p == 1:
        assert summary == {}
        return
    # the d x d sum of the shards' parts, the column sums and the row count
    assert set(summary) == {"all_reduce"}, summary
    assert summary["all_reduce"]["bytes"] == (d * d + d + 1) * 4, summary


# ------------------------------------- only the upper column blocks are multiplied


def _gram(X, w):
    """`_centered_gram` about the float64 weighted mean, and that mean."""
    mean = (w.astype(np.float64) @ X.astype(np.float64) / w.sum()).astype(np.float32)
    return np.asarray(linalg._centered_gram(jnp.asarray(X), jnp.asarray(w), jnp.asarray(mean))), mean


@pytest.fixture(scope="module")
def wide_table():
    """Two parts and a remainder of rows, three column blocks the last of
    which is narrow; column means of three noise widths."""
    rows = 2 * linalg.GRAM_CHUNK_ROWS + 77
    rng = np.random.default_rng(33)
    X = (rng.standard_normal((rows, TRIANGLE_COLS)) + 3.0).astype(np.float32)
    return X, {"unit": np.ones(rows, np.float32),
               "weightCol": rng.integers(1, 4, rows).astype(np.float32)}


@pytest.mark.parametrize("weights", ["unit", "weightCol"])
def test_the_upper_blocks_give_the_float64_gram_and_an_exactly_symmetric_one(wide_table, weights):
    X, ws = wide_table
    w = ws[weights]
    assert len(linalg.gram_column_blocks(X.shape[1])) > 1
    G, mean = _gram(X, w)
    Xc = X.astype(np.float64) - mean.astype(np.float64)
    ref = (Xc * w.astype(np.float64)[:, None]).T @ Xc
    # float32 rounding of a sum of some 8,000 (24,000 weighted) products of
    # unit scale: the diagonal is 8,000 to 17,000, an ulp there 1e-3
    assert np.abs(G - ref).max() <= 4e-7 * np.abs(ref).max()
    assert (G == G.T).all()


@pytest.mark.parametrize("weights", ["unit", "weightCol"])
def test_the_upper_blocks_give_what_the_single_matmul_gives(wide_table, weights, monkeypatch):
    X, ws = wide_table
    w = ws[weights]
    G, _ = _gram(X, w)
    # the single matmul a part, whatever the shape
    monkeypatch.setattr(linalg, "GRAM_TRIANGLE_MIN_COLS", X.shape[1] + 1)
    F, _ = _gram(X, w)
    assert np.abs(G - F).max() <= 4e-7 * np.abs(F).max()
    # the single matmul computes both halves, and not to the same bits
    assert np.abs(F - F.T).max() <= 4e-7 * np.abs(F).max()


def _gram_dots_a_part(cols):
    """Matmuls that yield a block of the Gram matrix, counted in the
    optimised program of a table of two parts and a remainder: the loop's body
    holds a part's, the remainder a part's again."""
    import re

    rows = 2 * linalg.GRAM_CHUNK_ROWS + 8
    text = linalg.weighted_covariance.lower(
        jax.ShapeDtypeStruct((rows, cols), jnp.float32), jax.ShapeDtypeStruct((rows,), jnp.float32)
    ).compile().as_text()
    dots = re.findall(r"= f32\[(\d+),(\d+)\]\S* dot\(", text)
    assert len(dots) % 2 == 0, dots
    return len(dots) // 2, sorted({(int(a), int(b)) for a, b in dots})


def test_under_the_width_a_part_is_one_matmul_of_the_whole_matrix():
    count, shapes = _gram_dots_a_part(FULL_COLS)
    assert (count, shapes) == (1, [(FULL_COLS, FULL_COLS)])


def test_from_the_width_on_a_part_multiplies_the_upper_block_pairs_only():
    """One matmul a block ROW, block I against the columns from I's first to
    the last: B matmuls whose outputs are the B(B+1)/2 block pairs (I, J) with
    I <= J, and none of the other B(B-1)/2."""
    blocks = linalg.gram_column_blocks(TRIANGLE_COLS)
    B = len(blocks)
    count, shapes = _gram_dots_a_part(TRIANGLE_COLS)
    assert B >= 3 and count == B, (B, count)
    assert shapes == sorted((hi - lo, TRIANGLE_COLS - lo) for lo, hi in blocks), shapes
    pairs = sum((i1 - i0) * (j1 - j0) for a, (i0, i1) in enumerate(blocks) for j0, j1 in blocks[a:])
    assert sum(a * b for a, b in shapes) == pairs < TRIANGLE_COLS ** 2


@pytest.mark.parametrize("cols,blocks", [
    (1, 1), (MAX_FUSED_COLS + 1, 1), (FULL_COLS, 1),
    (linalg.GRAM_TRIANGLE_MIN_COLS, -(-linalg.GRAM_TRIANGLE_MIN_COLS // linalg.GRAM_BLOCK_COLS)),
    (3000, -(-3000 // linalg.GRAM_BLOCK_COLS)),
])
def test_the_column_blocks_cover_the_columns_once_and_in_order(cols, blocks):
    got = linalg.gram_column_blocks(cols)
    assert len(got) == blocks
    assert got[0][0] == 0 and got[-1][1] == cols
    assert all(a[1] == b[0] for a, b in zip(got, got[1:]))
    assert blocks == 1 or all(hi - lo <= linalg.GRAM_BLOCK_COLS for lo, hi in got)


@pytest.mark.parametrize("num_workers", [2, 4])
def test_the_shards_upper_blocks_add_up_to_the_single_device_covariance(wide_table, num_workers,
                                                                         n_devices):
    if num_workers > n_devices:
        pytest.skip(f"needs {num_workers} virtual devices")
    from spark_rapids_ml_tpu.observability.comm import collectives_from_executable
    from spark_rapids_ml_tpu.parallel.partitioner import DataParallelPartitioner

    X, ws = wide_table
    w = ws["weightCol"]
    one, _, _ = linalg.weighted_covariance(jnp.asarray(X), jnp.asarray(w))
    part = DataParallelPartitioner(num_workers)
    pad = -len(X) % num_workers
    Xd = part.shard(np.concatenate([X, np.zeros((pad, X.shape[1]), np.float32)]))
    wd = part.shard(np.concatenate([w, np.zeros(pad, np.float32)]))
    cov, _, _ = linalg.weighted_covariance(Xd, wd, mesh=part.mesh)
    one, cov = np.asarray(one), np.asarray(cov)
    assert np.abs(cov - one).max() <= 4e-7 * np.abs(one).max()
    assert (cov == cov.T).all()
    # one psum of the upper blocks, the column sums and the row count: the
    # lower blocks are never moved, and mirrored after the sum
    exe = linalg.weighted_covariance.lower(Xd, wd, mesh=part.mesh).compile()
    summary = collectives_from_executable(exe) or {}
    assert set(summary) == {"all_reduce"}, summary
    d = X.shape[1]
    upper = sum((hi - lo) * (d - lo) for lo, hi in linalg.gram_column_blocks(d))
    assert summary["all_reduce"]["bytes"] == (upper + d + 1) * 4 < (d * d + d + 1) * 4, summary
