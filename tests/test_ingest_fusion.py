"""Zero-copy ingest plane + whole-pipeline fusion (docs/design.md §6k).

Two contracts under test:

* ops/ingest.py stages contiguous, device-castable blocks as VIEWS (no host
  copy, no host conversion — the consuming kernels cast on device), with every
  fallback copy counted into the `ingest.*` ledger; the Arrow FixedSizeList
  fast path extracts the whole design matrix as a view of the Arrow buffer.
* Pipeline fuses a featurize->fit suffix chain (StandardScaler / PCA feeding
  KMeans / LinearRegression / LogisticRegression / PCA) into one streamed
  program per batch, BIT-IDENTICAL to the staged transform->refit path —
  equality is exact (assert_array_equal), not approximate, because both paths
  run the same device expressions on the same batches in the same order.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_ml_tpu import config, profiling
from spark_rapids_ml_tpu.ops import ingest
from spark_rapids_ml_tpu.reliability import reset_faults


@pytest.fixture(autouse=True)
def fusion_env():
    """Streamed-scale thresholds, fusion on at any size, fresh counters."""
    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    config.set("pipeline.fuse_min_rows", 1)
    profiling.reset_counters()
    reset_faults()
    yield
    for key in (
        "stream_threshold_bytes",
        "stream_batch_rows",
        "pipeline.fuse",
        "pipeline.fuse_min_rows",
        "ingest.zero_copy",
        "ingest.staging_pool_rows",
        "reliability.fault_spec",
        "reliability.checkpoint_batches",
        "reliability.backoff_base_s",
        "reliability.backoff_max_s",
    ):
        config.unset(key)
    reset_faults()


def _totals():
    return profiling.counter_totals()


def _fused_stages():
    """Sum of the labeled pipeline.fused_stages counter across chain shapes."""
    return sum(
        v for k, v in _totals().items() if k.startswith("pipeline.fused_stages")
    )


# ------------------------------------------------------------- stage_block


def test_stage_block_contiguous_is_zero_copy_view():
    X = np.arange(64, dtype=np.float32).reshape(8, 8)
    blk = ingest.stage_block(X, 2, 6, np.float32)
    assert np.shares_memory(blk, X)
    np.testing.assert_array_equal(blk, X[2:6])
    totals = _totals()
    assert totals["ingest.copies_avoided"] == 1
    assert totals["ingest.bytes_zero_copy"] == blk.nbytes
    assert totals.get("ingest.bytes_copied", 0) == 0
    assert totals["ingest.rows_staged"] == 4


def test_stage_block_device_castable_source_stays_in_source_dtype():
    """Small-int / exact-widening sources ride the device cast: the staged
    block keeps its SOURCE dtype (the kernel casts in-program)."""
    X = np.arange(40, dtype=np.int32).reshape(10, 4)
    blk = ingest.stage_block(X, 0, 10, np.float32)
    assert blk.dtype == np.int32
    assert np.shares_memory(blk, X)


def test_stage_block_noncontiguous_takes_counted_copy():
    X = np.asfortranarray(np.arange(64, dtype=np.float32).reshape(8, 8))
    blk = ingest.stage_block(X, 0, 8, np.float32)
    assert not np.shares_memory(blk, X)
    assert blk.flags.c_contiguous
    np.testing.assert_array_equal(blk, X)
    totals = _totals()
    assert totals["ingest.bytes_copied"] == blk.nbytes
    assert totals.get("ingest.copies_avoided", 0) == 0
    assert totals["ingest.host_convert_s"] >= 0.0


def test_stage_block_narrowing_dtype_takes_counted_copy():
    """float64 -> float32 is NOT device-castable (the device cast is not
    bit-equal to the host astype for all values): counted host conversion."""
    X = np.linspace(0, 1, 32, dtype=np.float64).reshape(8, 4)
    blk = ingest.stage_block(X, 0, 8, np.float32)
    assert blk.dtype == np.float32
    assert not np.shares_memory(blk, X)
    assert _totals()["ingest.bytes_copied"] == blk.nbytes


def test_stage_block_force_copy_owns_the_block():
    X = np.ones((6, 3), dtype=np.float32)
    blk = ingest.stage_block(X, 0, 6, np.float32, force_copy=True)
    assert not np.shares_memory(blk, X)
    blk[:] = 7.0  # caller-owned: mutation must not leak into the source
    assert X[0, 0] == 1.0


def test_stage_block_zero_copy_kill_switch():
    config.set("ingest.zero_copy", False)
    X = np.ones((6, 3), dtype=np.float32)
    blk = ingest.stage_block(X, 0, 6, np.float32)
    assert not np.shares_memory(blk, X)
    assert _totals()["ingest.bytes_copied"] == blk.nbytes


@pytest.mark.parametrize(
    "src,dst,ok",
    [
        (np.float32, np.float32, True),
        (np.float16, np.float32, True),  # exact widening
        (np.float32, np.float64, True),
        (np.float64, np.float32, False),  # narrowing
        (np.int32, np.float32, True),  # small int: IEEE RNE both sides
        (np.int64, np.float32, False),  # canonicalization would narrow it
        (np.bool_, np.float32, True),
    ],
)
def test_device_castable_matrix(src, dst, ok):
    assert ingest._device_castable(np.dtype(src), np.dtype(dst)) is ok


# ------------------------------------------------------------- StagingPool


def test_staging_pool_cpu_never_reuses_buffers(monkeypatch):
    """Where device_put ALIASES host memory (CPU), reuse would let a later
    block overwrite an earlier block's HBM-cache-resident tensor — the pool
    must allocate fresh per call."""
    monkeypatch.setattr(ingest, "_device_put_copies_cache", False)
    pool = ingest.StagingPool(pool_rows=16)
    a = pool.buffer((8, 4), np.float32)
    b = pool.buffer((8, 4), np.float32)
    assert not np.shares_memory(a, b)


def test_staging_pool_reuses_one_buffer_on_copying_backends(monkeypatch):
    """Where device_put copies (TPU/GPU) the pool hands the SAME buffer back
    for a key: the upload site fences each batch's transfer before the slicer
    runs again (test_batch_stream_fences_transfer_before_buffer_reuse), so one
    buffer per key is enough."""
    monkeypatch.setattr(ingest, "_device_put_copies_cache", True)
    pool = ingest.StagingPool(pool_rows=16)
    a = pool.buffer((8, 4), np.float32)
    b = pool.buffer((8, 4), np.float32)
    assert np.shares_memory(a, b)
    assert a.shape == (8, 4)
    # distinct slots and distinct (dtype, tail) keys get distinct buffers
    assert not np.shares_memory(a, pool.buffer((8, 4), np.float32, slot="w"))
    assert not np.shares_memory(a, pool.buffer((8, 4), np.float64))


def test_batch_stream_fences_transfer_before_buffer_reuse(monkeypatch):
    """device_put returns before the runtime has read the host buffer (seen on
    a v5e: a buffer rewritten right after device_put arrived corrupted), so
    `_batch_stream` must wait for each batch's transfer before the slicer may
    refill the reused staging buffer. Order of events, with reuse forced on:
    every batch is block_until_ready'd before the next slicer call."""
    import jax

    from spark_rapids_ml_tpu.ops import streaming

    monkeypatch.setattr(ingest, "_device_put_copies_cache", True)
    events = []
    real_block = jax.block_until_ready

    def spy_block(x):
        events.append("fence")
        return real_block(x)

    monkeypatch.setattr(streaming.jax, "block_until_ready", spy_block)
    X = np.asfortranarray(
        np.arange(64 * 4, dtype=np.float32).reshape(64, 4))  # copy path
    w = np.ones(64, np.float32)
    pool = ingest.StagingPool(pool_rows=16)

    def slicer(s, e):
        events.append("slice")
        return (ingest.stage_block(X, s, e, np.float32, pool, slot="X"),
                ingest.stage_block(w, s, e, np.float32, pool, slot="w"))

    n_batches = sum(1 for _ in streaming._prefetch(
        streaming._batch_stream(64, 16, None, slicer)))
    assert n_batches == 4
    assert events == ["slice", "fence"] * 4


def test_staging_pool_grows_past_pool_rows(monkeypatch):
    monkeypatch.setattr(ingest, "_device_put_copies_cache", True)
    pool = ingest.StagingPool(pool_rows=4)
    big = pool.buffer((32, 2), np.float32)
    assert big.shape == (32, 2)


def test_resolve_staging_pool_rows_config_pin_wins():
    config.set("ingest.staging_pool_rows", 123)
    assert ingest.resolve_staging_pool_rows() == 123
    config.unset("ingest.staging_pool_rows")
    from spark_rapids_ml_tpu.autotune.defaults import INGEST_STAGING_POOL_ROWS

    assert ingest.resolve_staging_pool_rows() == INGEST_STAGING_POOL_ROWS


# --------------------------------------------------------- Arrow fast path


def _arrow_table(X, **scalar_cols):
    n, d = X.shape
    fsl = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), d)
    cols = {"features": fsl}
    cols.update({k: pa.array(v) for k, v in scalar_cols.items()})
    return pa.table(cols)


def test_arrow_fixed_size_list_extracts_zero_copy():
    from spark_rapids_ml_tpu.core.dataset import extract_feature_data

    rng = np.random.default_rng(3)
    X = rng.normal(size=(200, 6)).astype(np.float32)
    fd = extract_feature_data(_arrow_table(X), input_col="features")
    np.testing.assert_array_equal(fd.features, X)
    totals = _totals()
    assert totals["ingest.bytes_zero_copy"] >= X.nbytes
    assert totals.get("ingest.bytes_copied", 0) == 0


def test_arrow_small_int_source_fits_bit_equal_to_host_cast():
    """int32 Arrow features ride the on-device cast; the fit is bit-identical
    to fitting the host-converted float32 matrix."""
    from spark_rapids_ml_tpu.clustering import KMeans

    rng = np.random.default_rng(5)
    X_int = rng.integers(-1000, 1000, size=(400, 5), dtype=np.int32)
    tbl = _arrow_table(X_int.astype(np.float32))
    # same table, int32 storage
    tbl_int = pa.table(
        {
            "features": pa.FixedSizeListArray.from_arrays(
                pa.array(X_int.reshape(-1)), 5
            )
        }
    )
    m_f32 = KMeans(k=3, seed=11, maxIter=8).fit(tbl)
    m_int = KMeans(k=3, seed=11, maxIter=8).fit(tbl_int)
    np.testing.assert_array_equal(
        np.asarray(m_f32.cluster_centers_), np.asarray(m_int.cluster_centers_)
    )


def test_arrow_fused_pipeline_copies_nothing():
    """The ISSUE acceptance path: Arrow in, fused featurize->fit chain, and
    pass-1 host conversion bytes stay at ZERO — every staged block is a view
    of the Arrow buffer."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import StandardScaler
    from spark_rapids_ml_tpu.pipeline import Pipeline

    rng = np.random.default_rng(7)
    X = rng.normal(size=(600, 8)).astype(np.float32)
    pipe = Pipeline(
        stages=[
            StandardScaler(inputCol="features", outputCol="scaled", withMean=True),
            KMeans(k=3, seed=2, maxIter=6, featuresCol="scaled"),
        ]
    )
    model = pipe.fit(_arrow_table(X))
    assert _fused_stages() == 2
    totals = _totals()
    assert totals.get("ingest.bytes_copied", 0) == 0
    assert totals["ingest.bytes_zero_copy"] >= X.nbytes
    report = model.stages[-1].pipeline_report_
    ing = report["ingest"]
    assert ing["bytes_per_row_after"] == 0.0
    assert ing["bytes_per_row_before"] > 0.0


# ------------------------------------- fused vs staged (bit-identical) chains


def _fit_pipe(make_stages, df, fuse):
    config.set("pipeline.fuse", fuse)
    try:
        from spark_rapids_ml_tpu.pipeline import Pipeline

        return Pipeline(stages=make_stages()).fit(df)
    finally:
        config.unset("pipeline.fuse")


def _cluster_df(n=500, d=8, seed=17):
    rng = np.random.default_rng(seed)
    X = np.concatenate(
        [
            rng.normal(-2, 1.0, (n // 2, d)),
            rng.normal(2, 1.0, (n - n // 2, d)),
        ]
    ).astype(np.float32)
    return pd.DataFrame({"features": list(X)})


def test_fused_scale_kmeans_bit_identical_to_staged():
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import StandardScaler

    df = _cluster_df()

    def stages():
        return [
            StandardScaler(
                inputCol="features", outputCol="scaled", withMean=True
            ),
            KMeans(k=2, seed=5, maxIter=10, featuresCol="scaled"),
        ]

    staged = _fit_pipe(stages, df, fuse=False)
    assert _fused_stages() == 0
    fused = _fit_pipe(stages, df, fuse=True)
    assert _fused_stages() == 2
    for attr in ("mean", "std"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fused.stages[0], attr)),
            np.asarray(getattr(staged.stages[0], attr)),
            err_msg=attr,
        )
    np.testing.assert_array_equal(
        np.asarray(fused.stages[1].cluster_centers_),
        np.asarray(staged.stages[1].cluster_centers_),
    )
    out_f = fused.transform(df)
    out_s = staged.transform(df)
    np.testing.assert_array_equal(
        np.asarray(out_f["prediction"]), np.asarray(out_s["prediction"])
    )


def test_fused_scale_pca_bit_identical_to_staged():
    from spark_rapids_ml_tpu.feature import PCA, StandardScaler

    rng = np.random.default_rng(19)
    X = (rng.normal(size=(500, 10)) * np.linspace(1, 3, 10)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})

    def stages():
        return [
            StandardScaler(
                inputCol="features", outputCol="scaled", withMean=True
            ),
            PCA(k=3, inputCol="scaled"),
        ]

    staged = _fit_pipe(stages, df, fuse=False)
    fused = _fit_pipe(stages, df, fuse=True)
    assert _fused_stages() == 2
    for key in ("components", "explained_variance", "mean"):
        np.testing.assert_array_equal(
            np.asarray(fused.stages[1].get_model_attributes()[key]),
            np.asarray(staged.stages[1].get_model_attributes()[key]),
            err_msg=key,
        )


def test_fused_pca_kmeans_bit_identical_to_staged():
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import PCA

    df = _cluster_df(seed=23, d=10)

    def stages():
        return [
            PCA(k=4, inputCol="features", outputCol="pca_features"),
            KMeans(k=2, seed=9, maxIter=10, featuresCol="pca_features"),
        ]

    staged = _fit_pipe(stages, df, fuse=False)
    fused = _fit_pipe(stages, df, fuse=True)
    assert _fused_stages() == 2
    np.testing.assert_array_equal(
        np.asarray(fused.stages[1].cluster_centers_),
        np.asarray(staged.stages[1].cluster_centers_),
    )


def test_fused_three_stage_chain_bit_identical_and_reported():
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import PCA, StandardScaler

    df = _cluster_df(seed=29, d=10)

    def stages():
        return [
            StandardScaler(
                inputCol="features", outputCol="scaled", withMean=True
            ),
            PCA(k=4, inputCol="scaled", outputCol="pca_features"),
            KMeans(k=2, seed=13, maxIter=10, featuresCol="pca_features"),
        ]

    staged = _fit_pipe(stages, df, fuse=False)
    fused = _fit_pipe(stages, df, fuse=True)
    assert (
        _totals().get("pipeline.fused_stages{chain=scale>project>kmeans}", 0)
        == 3
    )
    np.testing.assert_array_equal(
        np.asarray(fused.stages[2].cluster_centers_),
        np.asarray(staged.stages[2].cluster_centers_),
    )
    out_f = fused.transform(df)
    out_s = staged.transform(df)
    np.testing.assert_array_equal(
        np.asarray(out_f["prediction"]), np.asarray(out_s["prediction"])
    )
    # every chain model carries the parent report with the §6f ingest section
    for model in fused.stages:
        report = model.pipeline_report_
        assert report["algo"] == "Pipeline"
        assert report["ingest"]["rows_staged"] > 0
        assert (
            report["ingest"]["bytes_per_row_after"]
            <= report["ingest"]["bytes_per_row_before"]
        )


def test_fused_scale_linreg_bit_identical_to_staged():
    from spark_rapids_ml_tpu.feature import StandardScaler
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(31)
    X = rng.normal(size=(500, 6)).astype(np.float32)
    y = (X @ rng.normal(size=6)).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "label": y})

    def stages():
        return [
            StandardScaler(
                inputCol="features", outputCol="scaled", withMean=True
            ),
            LinearRegression(regParam=0.1, featuresCol="scaled"),
        ]

    staged = _fit_pipe(stages, df, fuse=False)
    fused = _fit_pipe(stages, df, fuse=True)
    assert _fused_stages() == 2
    np.testing.assert_array_equal(
        np.asarray(fused.stages[1].coefficients),
        np.asarray(staged.stages[1].coefficients),
    )
    np.testing.assert_array_equal(
        np.asarray(fused.stages[1].intercept),
        np.asarray(staged.stages[1].intercept),
    )


def test_cross_validator_inner_loop_fuses_bit_identical():
    """CrossValidator over a fusable Pipeline: every inner fit fuses (sharing
    one extraction memo + one batch-cache scope via fitMultiple) and the best
    model is bit-identical to the staged CV."""
    from spark_rapids_ml_tpu.classification import LogisticRegression
    from spark_rapids_ml_tpu.evaluation import MulticlassClassificationEvaluator
    from spark_rapids_ml_tpu.feature import StandardScaler
    from spark_rapids_ml_tpu.pipeline import Pipeline
    from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

    rng = np.random.default_rng(37)
    X = np.concatenate(
        [rng.normal(-2, 1, (120, 4)), rng.normal(2, 1, (120, 4))]
    ).astype(np.float32)
    y = np.repeat([0.0, 1.0], 120)
    df = pd.DataFrame({"features": list(X), "label": y})

    def run_cv():
        scaler = StandardScaler(
            inputCol="features", outputCol="scaled", withMean=True
        )
        lr = LogisticRegression(maxIter=20, featuresCol="scaled")
        grid = ParamGridBuilder().addGrid(lr.regParam, [0.0, 0.1]).build()
        cv = CrossValidator(
            estimator=Pipeline(stages=[scaler, lr]),
            estimatorParamMaps=grid,
            evaluator=MulticlassClassificationEvaluator(metricName="accuracy"),
            numFolds=2,
            seed=1,
        )
        return cv.fit(df)

    config.set("pipeline.fuse", False)
    staged_cv = run_cv()
    assert _fused_stages() == 0
    config.set("pipeline.fuse", True)
    fused_cv = run_cv()
    # 2 folds x 2 candidates x 2 stages + best-model refit's 2 stages
    assert _fused_stages() == 10
    np.testing.assert_array_equal(
        np.asarray(fused_cv.bestModel.stages[1].coefficients),
        np.asarray(staged_cv.bestModel.stages[1].coefficients),
    )
    np.testing.assert_array_equal(
        np.asarray(fused_cv.avgMetrics), np.asarray(staged_cv.avgMetrics)
    )


# -------------------------------------------- reliability inside the chain


def test_fused_chain_resumes_bit_identical_after_ingest_fault():
    """A transient ingest fault mid-chain resumes from the last checkpoint and
    the fused models are bit-identical to the fault-free fused run."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import StandardScaler

    config.set("reliability.checkpoint_batches", 2)
    config.set("reliability.backoff_base_s", 0.001)
    config.set("reliability.backoff_max_s", 0.002)
    df = _cluster_df(seed=41)

    def stages():
        return [
            StandardScaler(
                inputCol="features", outputCol="scaled", withMean=True
            ),
            KMeans(k=2, seed=7, maxIter=10, featuresCol="scaled"),
        ]

    clean = _fit_pipe(stages, df, fuse=True)
    config.set("reliability.fault_spec", "ingest:batch=3:raise=OSError")
    reset_faults()
    faulted = _fit_pipe(stages, df, fuse=True)
    totals = _totals()
    assert totals.get("reliability.fault.ingest", 0) == 1
    assert totals.get("reliability.resume.ingest", 0) >= 1
    assert _fused_stages() == 4  # both runs fused
    for attr in ("mean", "std"):
        np.testing.assert_array_equal(
            np.asarray(getattr(clean.stages[0], attr)),
            np.asarray(getattr(faulted.stages[0], attr)),
            err_msg=attr,
        )
    np.testing.assert_array_equal(
        np.asarray(clean.stages[1].cluster_centers_),
        np.asarray(faulted.stages[1].cluster_centers_),
    )


# ------------------------------------------------------------ fuse gating


def test_fuse_declines_below_min_rows():
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import StandardScaler

    config.set("pipeline.fuse_min_rows", 10**6)
    df = _cluster_df(seed=43)
    model = _fit_pipe(
        lambda: [
            StandardScaler(inputCol="features", outputCol="scaled"),
            KMeans(k=2, seed=3, maxIter=5, featuresCol="scaled"),
        ],
        df,
        fuse=True,
    )
    assert _fused_stages() == 0
    assert np.asarray(model.stages[1].cluster_centers_).shape == (2, 8)


def test_fuse_declines_in_core_scale_then_stages_fit_fine():
    """Below the stream threshold the data-level gate returns None mid-_fit
    and the staged loop carries the SAME stage list to completion."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import StandardScaler

    config.set("stream_threshold_bytes", 1 << 30)
    df = _cluster_df(seed=47)
    model = _fit_pipe(
        lambda: [
            StandardScaler(inputCol="features", outputCol="scaled"),
            KMeans(k=2, seed=3, maxIter=5, featuresCol="scaled"),
        ],
        df,
        fuse=True,
    )
    assert _fused_stages() == 0
    assert np.asarray(model.stages[1].cluster_centers_).shape == (2, 8)


def test_fuse_declines_cosine_kmeans():
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import StandardScaler

    df = _cluster_df(seed=53)
    model = _fit_pipe(
        lambda: [
            StandardScaler(inputCol="features", outputCol="scaled"),
            KMeans(
                k=2,
                seed=3,
                maxIter=5,
                featuresCol="scaled",
                distanceMeasure="cosine",
            ),
        ],
        df,
        fuse=True,
    )
    assert _fused_stages() == 0
    assert np.asarray(model.stages[1].cluster_centers_).shape == (2, 8)


def test_fuse_declines_huber_linreg():
    from spark_rapids_ml_tpu.feature import StandardScaler
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(59)
    X = rng.normal(size=(400, 5)).astype(np.float32)
    y = (X @ rng.normal(size=5)).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "label": y})
    model = _fit_pipe(
        lambda: [
            StandardScaler(inputCol="features", outputCol="scaled"),
            LinearRegression(loss="huber", featuresCol="scaled"),
        ],
        df,
        fuse=True,
    )
    assert _fused_stages() == 0
    assert np.asarray(model.stages[1].coefficients).shape == (5,)


def test_fuse_declines_unlinked_columns():
    """Terminal reading the RAW features column (not the scaler's output) must
    not fuse — the chain op would corrupt its input."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import StandardScaler

    df = _cluster_df(seed=61)
    model = _fit_pipe(
        lambda: [
            StandardScaler(inputCol="features", outputCol="scaled"),
            KMeans(k=2, seed=3, maxIter=5, featuresCol="features"),
        ],
        df,
        fuse=True,
    )
    assert _fused_stages() == 0
    assert np.asarray(model.stages[1].cluster_centers_).shape == (2, 8)
