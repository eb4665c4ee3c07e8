"""Inference-plane observability (observability/inference.py — docs/design.md
§6e): TransformRun scopes + transform_reports.jsonl, the instrumented predict
dispatch with shape-bucket telemetry and the recompile sentinel, per-partition
sidecar aggregation of the distributed transform plane, CV trial traces,
JSONL rotation and histogram quantiles."""

import json
import os

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config, observability as obs, profiling
from spark_rapids_ml_tpu.observability import inference as inf
from spark_rapids_ml_tpu.observability.export import (
    load_run_reports,
    load_transform_reports,
    write_run_report,
)
from spark_rapids_ml_tpu.observability.registry import interpolate_quantile


@pytest.fixture(autouse=True)
def _clean_metrics():
    profiling.reset_counters()
    profiling.reset_spans()
    inf.reset_shape_buckets()
    yield
    profiling.reset_counters()
    profiling.reset_spans()
    inf.reset_shape_buckets()
    for key in (
        "observability.metrics_dir",
        "observability.enabled",
        "observability.recompile_warn_threshold",
        "observability.transform_sample_rate",
        "observability.max_report_bytes",
        "observability.max_report_files",
        "stream_threshold_bytes",
        "stream_batch_rows",
    ):
        config.unset(key)


# ------------------------------------------------- protocol mock (spark plane)


class FakeBroadcast:
    def __init__(self, value):
        import uuid

        self.value = value
        self.id = ("fake", uuid.uuid4().hex)


class FakeSparkContext:
    def __init__(self):
        self.broadcasts = []

    def broadcast(self, value):
        b = FakeBroadcast(value)
        self.broadcasts.append(b)
        return b


class FakeSparkSession:
    def __init__(self):
        self.sparkContext = FakeSparkContext()


class FakeSparkDF:
    """The protocol surface of pyspark.sql.DataFrame the transform plane uses
    (mirrors tests/test_spark_transform.py). mapInPandas executes EAGERLY, which
    is exactly what makes the driver-side TransformRun receive the partition
    scopes while still open — the local-mode aggregation path under test."""

    def __init__(self, pdf, n_partitions=3, session=None):
        self._pdf = pdf.reset_index(drop=True)
        self._n_partitions = n_partitions
        self.sparkSession = session or FakeSparkSession()

    def limit(self, n):
        return FakeSparkDF(self._pdf.head(n), 1, self.sparkSession)

    def toPandas(self):
        return self._pdf

    def mapInPandas(self, udf, schema):
        chunks = np.array_split(np.arange(len(self._pdf)), self._n_partitions)
        outs = []
        for idx in chunks:
            part = self._pdf.iloc[idx].reset_index(drop=True)
            batches = iter(
                [part.iloc[: len(part) // 2], part.iloc[len(part) // 2 :]]
            )
            outs.extend(list(udf(batches)))
        out = pd.concat(outs, ignore_index=True) if outs else pd.DataFrame()
        return FakeSparkDF(out, self._n_partitions, self.sparkSession)


FakeSparkDF.__module__ = "pyspark.sql.mock"


def _blob_pdf(n=60, d=4, seed=0):
    rng = np.random.default_rng(seed)
    X = np.concatenate(
        [rng.normal(-3, 1, (n // 2, d)), rng.normal(3, 1, (n - n // 2, d))]
    ).astype(np.float32)
    return pd.DataFrame({"features": list(X), "tag": np.arange(n)})


def _sum_counters(report, prefix):
    return sum(
        v for k, v in report["metrics"]["counters"].items() if k.startswith(prefix)
    )


# --------------------------------------------------- TransformRun fundamentals


def test_transform_run_scope_and_export(tmp_path):
    config.set("observability.metrics_dir", str(tmp_path))
    with inf.transform_run("FakeModel") as run:
        obs.counter_inc("transform.rows", 7, model="FakeModel")
        with obs.span("transform.batch", {"model": "FakeModel"}):
            pass
    rep = run.report()
    assert rep["kind"] == "transform" and rep["algo"] == "FakeModel"
    assert rep["run_id"].startswith("transform-")
    (root,) = rep["trace"]
    assert root["name"] == "FakeModel.transform_run"
    back = load_transform_reports(str(tmp_path))
    assert back[-1]["run_id"] == rep["run_id"]
    # fit_reports.jsonl untouched by transform runs
    assert not os.path.exists(tmp_path / "fit_reports.jsonl")


def test_transform_run_suppressed_inside_worker():
    with inf.suppress_transform_runs():
        with inf.transform_run("FakeModel") as run:
            pass
    assert run is None
    config.set("observability.enabled", False)
    with inf.transform_run("FakeModel") as run:
        pass
    assert run is None


def test_local_transform_attaches_report(n_devices, tmp_path):
    from spark_rapids_ml_tpu.clustering import KMeans

    config.set("observability.metrics_dir", str(tmp_path))
    pdf = _blob_pdf()
    model = KMeans(k=2, maxIter=10, seed=1).fit(pdf)
    model.transform(pdf)
    rep = model.transform_report_
    assert rep["kind"] == "transform" and rep["status"] == "ok"
    assert _sum_counters(rep, "transform.rows") == len(pdf)
    assert _sum_counters(rep, "transform.batches") == 1
    hists = rep["metrics"]["histograms"]
    assert hists["transform.batch_s{model=KMeansModel}"]["count"] == 1
    assert hists["transform.predict_s{model=KMeansModel}"]["count"] == 1
    # exported next to (not into) the fit report
    assert load_transform_reports(str(tmp_path))[-1]["run_id"] == rep["run_id"]
    assert load_run_reports(str(tmp_path))[-1]["algo"] == "KMeans"


# ------------------------------------------- distributed plane aggregation


def test_spark_transform_partition_aggregation(n_devices, tmp_path):
    """THE acceptance criterion for the distributed plane: a STREAMED KMeans
    fit + a >=2-partition transform export BOTH fit_reports.jsonl and
    transform_reports.jsonl; the merged driver-side transform report's
    transform.rows equals the DataFrame count (the one-row schema probe stays
    out), per-partition snapshots are recorded breakdown-only (no double
    count), and the per-batch latency histogram is non-empty — all re-read
    from the exported JSONL, not in-process state."""
    from spark_rapids_ml_tpu.clustering import KMeans

    config.set("observability.metrics_dir", str(tmp_path))
    config.set("stream_threshold_bytes", 256)  # force the streamed fit path
    config.set("stream_batch_rows", 16)
    pdf = _blob_pdf(n=60)
    model = KMeans(k=2, maxIter=10, seed=1).fit(pdf)
    fit_reps = load_run_reports(str(tmp_path))
    assert fit_reps[-1]["algo"] == "KMeans" and fit_reps[-1]["kind"] == "fit"
    assert any(
        k.startswith("stream.upload_batches")
        for k in fit_reps[-1]["metrics"]["counters"]
    )
    sdf = FakeSparkDF(pdf, n_partitions=3)
    out = model.transform(sdf)
    assert len(out.toPandas()) == len(pdf)

    rep = load_transform_reports(str(tmp_path))[-1]
    assert rep["kind"] == "transform" and rep["site"] == "spark"
    assert rep["algo"] == "KMeansModel"
    # rows counted exactly once across 3 partitions x 2 batches each
    assert _sum_counters(rep, "transform.rows") == len(pdf)
    assert _sum_counters(rep, "transform.batches") == 6
    assert _sum_counters(rep, "transform.bytes") > 0
    hist = rep["metrics"]["histograms"]["transform.batch_s{model=KMeansModel}"]
    assert hist["count"] == 6
    # three same-process worker snapshots: breakdown only, never merged twice
    assert len(rep["workers"]) == 3
    assert all(w["merged"] is False for w in rep["workers"])
    # partition spans made it into the driver trace
    from spark_rapids_ml_tpu.observability.export import iter_spans

    parts = [s for s in iter_spans(rep) if s["name"] == "transform.partition"]
    assert len(parts) == 3


def test_foreign_partition_snapshot_merges():
    """A snapshot from another process (real multi-host serving) must MERGE
    into the run's registry — its writes never flowed through this process."""
    with inf.transform_run("M") as run:
        with obs.worker_scope(rank=0) as ws:
            obs.counter_inc("transform.rows", 10, model="M")
        snap = json.loads(json.dumps(ws.snapshot()))
        snap["process"] = "otherhost:cafecafe"
        snap["rank"] = 1
        inf.deliver_partition_snapshot(run.run_id, "driver-token", snap)
    rep = run.report()
    # 10 live (fan-out) + 10 merged foreign = 20
    assert _sum_counters(rep, "transform.rows") == 20
    assert [w["merged"] for w in rep["workers"]] == [True]


def test_late_partition_snapshot_goes_to_sidecar(tmp_path):
    """Run already closed (real lazy plane): the snapshot lands in the
    transform_partials.jsonl sidecar instead of vanishing."""
    with obs.worker_scope(rank=2) as ws:
        obs.counter_inc("transform.rows", 5, model="M")
    delivered = inf.deliver_partition_snapshot(
        "transform-999-dead", "driver-token", ws.snapshot(),
        metrics_dir=str(tmp_path),
    )
    assert delivered is False
    partials = obs.load_transform_partials(str(tmp_path))
    assert partials[0]["run_id"] == "transform-999-dead"
    assert partials[0]["rank"] == 2


def test_broadcast_payload_excludes_reports(n_devices):
    """A model's fit/transform reports are driver-side output and must not ride
    the executor broadcast (back-to-back transforms would otherwise ship the
    previous call's whole trace tree to every worker)."""
    import pickle

    from spark_rapids_ml_tpu.clustering import KMeans

    pdf = _blob_pdf(n=40)
    model = KMeans(k=2, maxIter=5, seed=1).fit(pdf)
    model.transform(pdf)  # attaches transform_report_
    assert hasattr(model, "fit_report_") and hasattr(model, "transform_report_")
    sdf = FakeSparkDF(pdf, n_partitions=2)
    model.transform(sdf)
    payload = b"".join(
        bytes(b.value) for b in sdf.sparkSession.sparkContext.broadcasts
    )
    shipped = pickle.loads(payload)
    assert not hasattr(shipped, "fit_report_")
    assert not hasattr(shipped, "transform_report_")
    # the driver model keeps (and refreshes) its reports
    assert model.transform_report_["site"] == "spark"
    assert model.fit_report_["algo"] == "KMeans"


# ------------------------------------------------------- recompile sentinel


def test_recompile_sentinel_threshold_semantics():
    """Fires strictly ABOVE the threshold, never at or below it."""
    config.set("observability.recompile_warn_threshold", 3)
    reg = obs.global_registry()
    for rows in (8, 16, 32):  # exactly threshold distinct signatures
        inf.record_shape_signature("SentinelModel", (rows, 4, "float32"))
    assert reg.counter("transform.compile").value(model="SentinelModel") == 3
    assert (
        reg.counter("transform.recompile_storm").value(model="SentinelModel") == 0
    )
    inf.record_shape_signature("SentinelModel", (64, 4, "float32"))  # 4th: storm
    inf.record_shape_signature("SentinelModel", (64, 4, "float32"))  # repeat: no-op
    inf.record_shape_signature("SentinelModel", (65, 4, "float32"))  # 5th: storm
    assert reg.counter("transform.compile").value(model="SentinelModel") == 5
    assert (
        reg.counter("transform.recompile_storm").value(model="SentinelModel") == 2
    )


def test_recompile_sentinel_event_in_run():
    config.set("observability.recompile_warn_threshold", 1)
    with inf.transform_run("M2") as run:
        inf.record_shape_signature("M2", (1, 2, "float32"))
        inf.record_shape_signature("M2", (2, 2, "float32"))
    rep = run.report()
    (ev,) = [e for e in rep["events"] if e["kind"] == "recompile_storm"]
    assert ev["model"] == "M2" and ev["signatures"] == 2 and ev["threshold"] == 1


def test_ragged_batches_fire_sentinel_bucketed_stay_silent(n_devices, tmp_path):
    from spark_rapids_ml_tpu.clustering import KMeans

    config.set("observability.metrics_dir", str(tmp_path))
    config.set("observability.recompile_warn_threshold", 3)
    pdf = _blob_pdf(n=64)
    model = KMeans(k=2, maxIter=5, seed=1).fit(pdf)

    inf.reset_shape_buckets()
    for i in range(0, 64, 16):  # bucketed: one signature
        model.transform(pdf.iloc[i : i + 16])
    reports = load_transform_reports(str(tmp_path))
    assert sum(_sum_counters(r, "transform.recompile_storm") for r in reports) == 0

    inf.reset_shape_buckets()
    n_before = len(reports)
    for n in (7, 11, 13, 17, 19):  # ragged: five signatures > 3
        model.transform(pdf.head(n))
    ragged = load_transform_reports(str(tmp_path))[n_before:]
    assert sum(_sum_counters(r, "transform.recompile_storm") for r in ragged) == 2


def test_transform_sample_rate_zero_keeps_counters():
    config.set("observability.transform_sample_rate", 0.0)
    with inf.transform_run("M3") as run:
        with inf.transform_batch(object(), 12):
            pass
    rep = run.report()
    assert _sum_counters(rep, "transform.rows") == 12
    assert "transform.batch_s{model=object}" not in rep["metrics"]["histograms"]


# ------------------------------------------------------------ CV trial traces


def test_cross_validator_cv_report(n_devices, tmp_path):
    from spark_rapids_ml_tpu.evaluation import RegressionEvaluator
    from spark_rapids_ml_tpu.regression import LinearRegression
    from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

    config.set("observability.metrics_dir", str(tmp_path))
    rng = np.random.default_rng(3)
    X = rng.normal(size=(120, 5)).astype(np.float32)
    y = (X @ np.arange(1, 6).astype(np.float32) + 0.01 * rng.normal(size=120))
    df = pd.DataFrame({"features": list(X), "label": y.astype(np.float32)})
    est = LinearRegression(standardization=False)
    grid = ParamGridBuilder().addGrid(est.regParam, [0.0, 10.0]).build()
    cv = CrossValidator(
        estimator=est,
        estimatorParamMaps=grid,
        evaluator=RegressionEvaluator(metricName="rmse"),
        numFolds=3,
        seed=5,
    )
    cv_model = cv.fit(df)
    rep = cv_model.cv_report_
    assert rep["kind"] == "cv" and rep["num_folds"] == 3
    assert rep["num_candidates"] == 2
    assert rep["best_index"] == int(np.argmin(rep["avg_metrics"]))
    assert len(rep["trials"]) == 3
    for t in rep["trials"]:
        assert t["fit_s"] > 0 and t["eval_s"] > 0 and len(t["scores"]) == 2
    assert rep["best_fit_report"] is not None
    # the parent run exported like any fit report, with per-fold spans
    from spark_rapids_ml_tpu.observability.export import iter_spans

    cv_runs = [
        r for r in load_run_reports(str(tmp_path)) if r["algo"] == "CrossValidator"
    ]
    assert cv_runs, "CV parent run not exported"
    names = {s["name"] for s in iter_spans(cv_runs[-1])}
    assert {"cv.fold", "cv.fit", "cv.refit"} <= names
    folds = [s for s in iter_spans(cv_runs[-1]) if s["name"] == "cv.fold"]
    assert sorted(s["attrs"]["fold"] for s in folds) == [0, 1, 2]


# --------------------------------------------------------------- JSONL rotation


def test_jsonl_rotation_preserves_round_trip(tmp_path):
    config.set("observability.max_report_bytes", 200)
    config.set("observability.max_report_files", 3)
    for i in range(10):
        write_run_report(
            {"schema": 1, "run_id": f"r-{i}", "pad": "x" * 150}, str(tmp_path)
        )
    live = tmp_path / "fit_reports.jsonl"
    assert live.exists() and (tmp_path / "fit_reports.jsonl.1").exists()
    rotated = sorted(p.name for p in tmp_path.glob("fit_reports.jsonl.*"))
    assert len(rotated) <= 3  # max_report_files generations retained
    back = load_run_reports(str(tmp_path))
    ids = [r["run_id"] for r in back]
    # chronological across rotated files; the newest reports always survive
    assert ids == sorted(ids, key=lambda s: int(s.split("-")[1]))
    assert ids[-1] == "r-9"
    assert all(r["pad"] == "x" * 150 for r in back)


def test_rotation_disabled_by_default(tmp_path):
    for i in range(5):
        write_run_report({"run_id": f"r-{i}"}, str(tmp_path))
    assert list(tmp_path.glob("fit_reports.jsonl.*")) == []
    assert len(load_run_reports(str(tmp_path))) == 5


# ---------------------------------------------------------- histogram quantile


def test_histogram_quantile_bucket_edges():
    reg = obs.MetricsRegistry()
    h = reg.histogram("q", buckets=[1.0, 2.0, 4.0, 8.0])
    for v in (1.5, 1.5, 3.0, 3.0):
        h.observe(v)
    # q*count on an exact cumulative boundary -> that bucket's UPPER bound
    assert h.quantile(0.5) == pytest.approx(2.0)
    # q=1.0 returns the TRUE observed maximum (not the bucket's upper bound —
    # 4.0 here would overshoot every sample) and q=0.0 the true minimum
    assert h.quantile(1.0) == pytest.approx(3.0)
    assert h.quantile(0.0) == pytest.approx(1.5)
    # geometric interpolation inside the (2, 4] and (1, 2] buckets
    assert h.quantile(0.75) == pytest.approx(2.0 * (4.0 / 2.0) ** 0.5)
    assert h.quantile(0.25) == pytest.approx(1.0 * (2.0 / 1.0) ** 0.5)
    # first bucket interpolates linearly from 0 (no finite lower edge)
    h0 = reg.histogram("q0", buckets=[1.0, 2.0])
    h0.observe(0.5)
    h0.observe(0.75)
    assert h0.quantile(0.5) == pytest.approx(0.5)  # frac 0.5 of (0, 1]
    # empty histogram: no quantiles exist — None, never an interpolated value
    assert reg.histogram("empty", buckets=[1.0]).quantile(0.5) is None
    assert reg.histogram("empty", buckets=[1.0]).quantile(0.0) is None


def test_histogram_quantile_inf_bucket_clamps():
    st = {"count": 4, "sum": 100.0, "buckets": [0, 0, 4]}
    assert interpolate_quantile(st, 0.99, [1.0, 2.0]) == pytest.approx(2.0)
