"""One span primitive on the profiler's clock, the phase spans of a fit and a
transform, the byte counters at the host<->device choke points, and the span
seconds in counter form (docs/design.md §6d)."""

import os
import re

import numpy as np
import pytest

from spark_rapids_ml_tpu import config, profiling
from spark_rapids_ml_tpu import observability as obs
from spark_rapids_ml_tpu.observability.export import iter_spans

PKG = os.path.dirname(os.path.abspath(obs.__file__ + "/.."))


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation: records construction, entry
    and exit."""

    log = []

    def __init__(self, name):
        self.name = name
        _Recorder.log.append(("new", name))

    def __enter__(self):
        _Recorder.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("exit", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    _Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    return _Recorder.log


@pytest.mark.parametrize("api", ["observability", "profiling"])
@pytest.mark.parametrize("raises", [False, True], ids=["ok", "raises"])
def test_a_span_opens_exactly_one_annotation_of_its_name(annotations, api, raises):
    open_span = obs.span if api == "observability" else profiling.span
    profiling.reset_spans()
    try:
        with open_span("unit.phase"):
            assert annotations == [("new", "unit.phase"), ("enter", "unit.phase")]
            if raises:
                raise KeyError("boom")
    except KeyError:
        pass
    assert annotations == [("new", "unit.phase"), ("enter", "unit.phase"),
                           ("exit", "unit.phase")]
    assert profiling.span_totals()["unit.phase"] >= 0.0


def test_nested_spans_annotate_in_order(annotations):
    with obs.span("outer"):
        with profiling.span("inner"):
            pass
    assert [e for e in annotations if e[0] != "new"] == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]


def test_no_annotation_is_constructed_outside_observability():
    sites = []
    for root, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                if re.search(r"TraceAnnotation\(", open(path).read()):
                    sites.append(os.path.relpath(path, PKG))
    assert sites == [os.path.join("observability", "runs.py")]
    src = open(os.path.join(PKG, "profiling.py")).read()
    assert "perf_counter" not in src and "import time" not in src


# ------------------------------------------------------------ fit phases


def _table(rows=3001, cols=16, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 4, (4, cols))
    return (centers[rng.integers(0, 4, rows)] + rng.normal(size=(rows, cols))).astype(np.float32)


def _estimator(family):
    if family == "kmeans":
        from spark_rapids_ml_tpu.clustering import KMeans

        return KMeans(k=4, maxIter=5, seed=3)
    from spark_rapids_ml_tpu.feature import PCA

    return PCA(k=3, inputCol="features")


FIT_TREE = {
    "kmeans": {
        "fit.ingest": [],
        "KMeans.prepare": ["fit.stage", "h2d.put", "h2d.put"],
        "KMeans.fit": ["h2d.wait", "kmeans.init", "kmeans.lloyd", "kmeans.summary"],
        "fit.finish": [],
    },
    "pca": {
        "fit.ingest": [],
        "PCA.prepare": ["fit.stage", "h2d.put", "h2d.put"],
        "PCA.fit": ["h2d.wait", "pca.cov", "pca.eig"],
        "fit.finish": [],
    },
}


@pytest.mark.parametrize("family", ["kmeans", "pca"])
def test_fit_report_carries_every_phase_nested_and_closed(n_devices, family):
    # large enough that the fixed few hundred microseconds between phases are
    # well under the 5 % asked of the closure, on a loaded test host too
    X = _table(rows=200001, cols=64)
    est = _estimator(family)
    est.fit(X)  # compiles
    roots = [est.fit(X).fit_report_["trace"] for _ in range(3)]
    for (root,) in roots:
        assert root["name"].endswith(".fit_run")
        tree = {c["name"]: [g["name"] for g in c["children"]] for c in root["children"]}
        assert tree == FIT_TREE[family]
        assert list(tree) == list(FIT_TREE[family])  # in the order they ran
        if family == "kmeans":
            (init,) = [c for c in root["children"][2]["children"] if c["name"] == "kmeans.init"]
            assert [c["name"] for c in init["children"]] == [
                "kmeans.init.oversample", "kmeans.init.weigh", "kmeans.init.pp"]
        covered = sum(c["duration_s"] for c in root["children"])
        assert covered <= root["duration_s"]
    # the phases account for the run: what no phase covers is under 5 % (the
    # best of three fits: a pause of the host between two phases is no phase)
    (root,) = min(roots, key=lambda r: 1 - sum(
        c["duration_s"] for c in r[0]["children"]) / r[0]["duration_s"])
    assert sum(c["duration_s"] for c in root["children"]) >= 0.95 * root["duration_s"]
    for parent in root["children"]:
        if parent["children"]:
            inner = sum(c["duration_s"] for c in parent["children"])
            assert inner <= parent["duration_s"] * 1.0001


@pytest.mark.parametrize("family", ["kmeans", "pca"])
def test_h2d_bytes_are_the_bytes_put(n_devices, family):
    from spark_rapids_ml_tpu.parallel.partition import pad_rows

    X = _table(rows=1003)
    model = _estimator(family).fit(X)
    Xp, weight, _ = pad_rows(X, n_devices)
    assert Xp.shape[0] > X.shape[0]  # the padding is put and counted too
    counters = model.fit_report_["metrics"]["counters"]
    assert counters["h2d.bytes{site=fit}"] == Xp.nbytes + weight.nbytes
    puts = [s for s in iter_spans(model.fit_report_) if s["name"] == "h2d.put"]
    assert sorted((s["attrs"] for s in puts), key=lambda a: -a["bytes"]) == [
        {"site": "fit", "bytes": Xp.nbytes}, {"site": "fit", "bytes": weight.nbytes}]
    assert "h2d.bytes{site=transform}" not in counters

    out = model.transform(X)
    assert len(out) == len(X)
    rep = model.transform_report_
    counters = rep["metrics"]["counters"]
    assert counters["h2d.bytes{site=transform}"] == X.nbytes
    col = out.columns[-1]
    width = 4 if family == "kmeans" else 4 * 3
    assert counters["d2h.bytes{site=transform}"] == len(X) * width, col
    (batch,) = rep["trace"][0]["children"]
    assert batch["name"] == "transform.batch"
    assert [c["name"] for c in batch["children"]] == [
        "transform.stage", "h2d.put", "h2d.wait", "transform.predict",
        "transform.fetch", "transform.output"]


def test_kmeans_fit_counts_what_it_reads_back(n_devices):
    X = _table(rows=1003)
    est = _estimator("kmeans")
    model = est.fit(X)
    k, steps = est.getOrDefault("k"), est.getOrDefault("initSteps")
    # int32 counts, reduced on the device: the weights of the start's
    # 1 + steps * 2k candidates, then the summary's k cluster sizes
    assert model.fit_report_["metrics"]["counters"]["d2h.bytes{site=fit}"] == (
        (1 + steps * 2 * k) * 4 + k * 4)
    assert sum(model.summary.clusterSizes) == len(X)


def test_streamed_fit_holds_one_init_span_with_the_phases_under_it(n_devices):
    from spark_rapids_ml_tpu.clustering import KMeans

    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    try:
        model = KMeans(k=2, maxIter=3, seed=5).fit(_table(rows=384, cols=8))
    finally:
        config.unset("stream_threshold_bytes")
        config.unset("stream_batch_rows")
    spans = list(iter_spans(model.fit_report_))
    (init,) = [s for s in spans if s["name"] == "kmeans.init"]
    assert [c["name"] for c in init["children"]] == [
        "kmeans.init.oversample", "kmeans.init.weigh", "kmeans.init.pp"]
    counters = model.fit_report_["metrics"]["counters"]
    # the streamed tier counts its own uploads; the choke point does not count them again
    assert counters["stream.upload_bytes"] > 0
    assert "h2d.bytes{site=fit}" not in counters
    assert not [s for s in spans if s["name"] == "h2d.put"]


def test_a_kernel_that_keeps_its_block_on_the_host_gets_it_untouched(n_devices):
    from spark_rapids_ml_tpu.observability.inference import predict_dispatch

    class Model:
        pass

    seen = []
    X = np.ones((8, 2), np.float32)
    with obs.worker_scope() as scope:
        predict_dispatch(Model(), lambda x: seen.append(x), X)
    assert seen[0] is X
    snap = scope.snapshot()
    assert [s["name"] for s in snap["spans"]] == ["transform.predict"]
    assert "h2d.bytes{site=transform}" not in snap["metrics"]["counters"]


def test_the_query_block_is_put_beside_committed_weights(n_devices):
    import jax

    from spark_rapids_ml_tpu.observability.inference import predict_to_host

    class Model:
        pass

    dev = jax.devices()[n_devices - 1]
    weights = jax.device_put(np.full((3, 2), 2.0, np.float32), dev)
    where = []

    def kernel(x, w):
        where.append((type(x), x.devices()))
        return x @ w.T

    X = np.ones((8, 2), np.float32)
    with obs.worker_scope() as scope:
        out = predict_to_host(Model(), kernel, X, weights)
    assert isinstance(out, np.ndarray) and np.all(out == 4.0)
    assert issubclass(where[0][0], jax.Array) and where[0][1] == {dev}
    counters = scope.snapshot()["metrics"]["counters"]
    assert counters["h2d.bytes{site=transform}"] == X.nbytes
    assert counters["d2h.bytes{site=transform}"] == out.nbytes


# ------------------------------------------------- span seconds as counters


def test_span_seconds_and_calls_ride_the_counters(n_devices):
    profiling.reset_spans()
    profiling.reset_counters()
    model = _estimator("pca").fit(_table(rows=512))
    totals, spans = profiling.counter_totals(), profiling.span_totals()
    assert spans["h2d.put"] > 0
    for name, seconds in spans.items():
        assert totals[f"span.seconds{{span={name}}}"] == seconds
    assert totals["span.calls{span=h2d.put}"] == 2
    assert totals["span.calls{span=PCA.fit}"] == 1
    rep = model.fit_report_["metrics"]
    for name, seconds in rep["spans"].items():
        assert rep["counters"][f"span.seconds{{span={name}}}"] == seconds
    assert rep["counters"]["span.calls{span=h2d.wait}"] == 1
    assert rep["counters"]["span.calls{span=pca.cov}"] == 1
    # a reset of either half empties the view (the legacy contract of reset_counters)
    profiling.reset_counters()
    assert profiling.counter_totals() == {}


def test_merging_a_worker_snapshot_does_not_double_the_span_counters():
    with obs.worker_scope(rank=0) as scope:
        for _ in range(3):
            with obs.span("worker.phase"):
                pass
        obs.counter_inc("worker.events", 2)
    snap = scope.snapshot()["metrics"]
    assert snap["counters"]["span.calls{span=worker.phase}"] == 3
    seconds = snap["spans"]["worker.phase"]
    driver = obs.MetricsRegistry()
    driver.merge_snapshot(snap)
    merged = driver.counter_totals()
    assert merged["span.calls{span=worker.phase}"] == 3
    assert merged["span.seconds{span=worker.phase}"] == seconds
    assert merged["worker.events"] == 2
    assert driver.span_totals() == {"worker.phase": seconds}
    driver.merge_snapshot(snap)  # a second worker adds, once
    assert driver.counter_totals()["span.calls{span=worker.phase}"] == 6
    assert driver.snapshot()["counters"]["span.seconds{span=worker.phase}"] == pytest.approx(
        2 * seconds)
