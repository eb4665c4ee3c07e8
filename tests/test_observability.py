"""Observability subsystem (observability/ — docs/design.md §6d): typed metrics
registry, per-fit FitRun trace trees, worker-snapshot aggregation, exporters,
and the profiling compat shims the rest of the tree rides on."""

import json
import os
import threading

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config, observability as obs, profiling


@pytest.fixture(autouse=True)
def _clean_metrics():
    profiling.reset_counters()
    profiling.reset_spans()
    yield
    profiling.reset_counters()
    profiling.reset_spans()
    for key in ("observability.metrics_dir", "stream_threshold_bytes",
                "stream_batch_rows", "observability.enabled"):
        config.unset(key)


# ------------------------------------------------------------------- registry


def test_counter_monotone_and_labeled():
    reg = obs.MetricsRegistry()
    c = reg.counter("x.events")
    c.inc()
    c.inc(2, site="a")
    c.inc(3, site="a")
    assert c.value() == 1
    assert c.value(site="a") == 5
    totals = reg.counter_totals()
    assert totals["x.events"] == 1
    assert totals["x.events{site=a}"] == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("x.events")  # one name, one kind


def test_gauge_set_inc_dec():
    reg = obs.MetricsRegistry()
    g = reg.gauge("x.level")
    g.set(10)
    g.inc(5)
    g.dec(15)
    assert g.value() == 0
    assert reg.counter_totals()["x.level"] == 0  # legacy surface includes gauges


def test_histogram_buckets_and_quantile():
    from spark_rapids_ml_tpu.observability.registry import quantile_from_state

    reg = obs.MetricsRegistry()
    h = reg.histogram("lat", buckets=[0.001, 0.01, 0.1])
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):
        h.observe(v)
    st = h.state()
    assert st["count"] == 5
    assert st["buckets"] == [1, 2, 1, 1]  # last slot is +inf
    assert abs(st["sum"] - 5.0605) < 1e-9
    assert quantile_from_state(st, 0.5, (0.001, 0.01, 0.1)) == 0.01


def test_snapshot_merge_adds_everything():
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    for reg, n in ((a, 1), (b, 2)):
        reg.counter("c").inc(n, site="s")
        reg.gauge("g").inc(10 * n)
        reg.histogram("h", buckets=[1.0]).observe(0.5)
        reg.add_span_total("sp", 0.25 * n)
    a.merge_snapshot(b.snapshot())
    assert a.counter("c").value(site="s") == 3
    assert a.gauge("g").value() == 30
    assert a.histogram("h", buckets=[1.0]).state()["count"] == 2
    assert a.span_totals()["sp"] == pytest.approx(0.75)


def test_label_key_round_trip():
    key = obs.label_key("m", {"b": 1, "a": "x"})
    assert key == "m{a=x,b=1}"
    name, labels = obs.split_label_key(key)
    assert name == "m" and labels == {"a": "x", "b": "1"}
    assert obs.split_label_key("bare") == ("bare", {})


# ----------------------------------------------------- profiling compat shims


def test_span_records_timing_when_body_raises():
    """The pre-observability span() updated its totals AFTER the annotation
    block, so a failed pass recorded nothing — the regression this pins."""
    with pytest.raises(OSError):
        with profiling.span("failing.pass"):
            raise OSError("mid-pass failure")
    assert "failing.pass" in profiling.span_totals()
    assert profiling.counter_totals()["span.errors{span=failing.pass}"] == 1


def test_add_span_total_feeds_histogram():
    obs.add_span_total("batch.s", 0.002)
    obs.add_span_total("batch.s", 0.004)
    assert profiling.span_totals()["batch.s"] == pytest.approx(0.006)
    st = obs.global_registry().histogram("batch.s").state()
    assert st["count"] == 2


def test_negative_count_still_works_as_gauge_delta():
    """Legacy gauge-as-counter call sites (signed increments through count())
    keep their arithmetic through the shim — including the historical
    positive-then-negative pattern, which retypes the metric to a gauge."""
    profiling.count("legacy.gauge", -3)
    profiling.count("legacy.gauge", -2)
    assert profiling.counter_totals()["legacy.gauge"] == -5
    profiling.count("legacy.mixed", 100)  # registers as a counter...
    profiling.count("legacy.mixed", -40)  # ...first negative retypes to gauge
    profiling.count("legacy.mixed", 10)
    assert profiling.counter_totals()["legacy.mixed"] == 70


def test_label_values_with_structural_chars_round_trip():
    """A ','/'=' in a label value (an exception message, say) must not re-key
    the metric when a worker snapshot merges on the driver."""
    reg = obs.MetricsRegistry()
    reg.counter("evt").inc(2, error="Foo,Bar=Baz")
    merged = obs.MetricsRegistry()
    merged.merge_snapshot(reg.snapshot())
    assert merged.counter_totals() == reg.counter_totals()
    (key,) = reg.counter_totals()
    name, labels = obs.split_label_key(key)
    assert name == "evt" and list(labels) == ["error"]


def test_event_log_is_bounded():
    with obs.FitRun("Eventy", max_spans=16) as run:
        for i in range(run.max_events + 50):
            obs.event("cache_evict", nbytes=i)
    rep = run.report()
    assert len(rep["events"]) == run.max_events
    assert rep["dropped_events"] == 50


# -------------------------------------------------- device-cache gauge (PR 3)


def test_cache_gauge_zero_after_eviction_and_close(n_devices):
    """Eviction + close must leave cache.bytes_resident at EXACTLY 0 — with the
    negative-increment counter hack a missed decrement was undetectable."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.device_cache import DeviceBatchCache

    batch = (jnp.ones((64, 8), jnp.float32),)
    nbytes = sum(int(a.nbytes) for a in batch)
    cache = DeviceBatchCache(budget_bytes=2 * nbytes + 1)
    k1 = cache.stream_key((np.ones(1),), 64, None, site="s1")
    k2 = cache.stream_key((np.ones(2),), 64, None, site="s2")
    assert cache.put(k1, 0, batch) and cache.put(k2, 0, batch)
    gauge = obs.global_registry().gauge("cache.bytes_resident")
    assert gauge.value() == 2 * nbytes
    cache.put(k2, 1, batch)  # over budget: evicts k1's entry (other stream)
    assert profiling.counter_totals()["cache.evictions"] == 1
    assert gauge.value() == 2 * nbytes
    cache.close()
    assert gauge.value() == 0
    assert profiling.counter_totals()["cache.bytes_resident"] == 0


# ------------------------------------------------------------ FitRun + scopes


def test_fit_run_concurrent_writes_exact_totals():
    """N barrier-task-style threads hammering counters/histograms under ONE
    FitRun: totals must be exact, and a reset_counters() mid-fit must not
    corrupt the scoped run (it clears the global registry only)."""
    n_threads, n_iter = 8, 200
    barrier = threading.Barrier(n_threads)

    with obs.fit_run("ConcurrentFit") as run:
        def hammer(rank):
            barrier.wait(timeout=30)
            for i in range(n_iter):
                profiling.count("hammer.events")
                profiling.count("hammer.by_rank", 1)
                obs.observe("hammer.lat", 0.001 * (i % 7))
                if rank == 0 and i == n_iter // 2:
                    profiling.reset_counters()  # mid-fit global reset

        threads = [
            threading.Thread(target=hammer, args=(r,)) for r in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    rep = run.report()
    assert rep["metrics"]["counters"]["hammer.events"] == n_threads * n_iter
    assert rep["metrics"]["counters"]["hammer.by_rank"] == n_threads * n_iter
    assert rep["metrics"]["histograms"]["hammer.lat"]["count"] == n_threads * n_iter
    # the global registry was reset mid-run and holds only the post-reset tail
    assert profiling.counter_totals()["hammer.events"] < n_threads * n_iter


def test_fit_run_trace_tree_nesting_and_events():
    with obs.fit_run("TraceFit") as run:
        with obs.span("outer", {"pass": 1}):
            with obs.span("inner"):
                obs.event("retry", site="t", attempt=1)
    rep = run.report()
    assert rep["status"] == "ok" and rep["duration_s"] > 0
    (root,) = rep["trace"]
    assert root["name"] == "TraceFit.fit_run"
    (outer,) = root["children"]
    assert outer["name"] == "outer" and outer["attrs"] == {"pass": 1}
    (inner,) = outer["children"]
    assert inner["name"] == "inner"
    (ev,) = rep["events"]
    assert ev["kind"] == "retry" and ev["span_id"] == inner["span_id"]


def test_fit_run_span_cap():
    with obs.FitRun("Capped", max_spans=3) as run:
        for _ in range(10):
            with obs.span("s"):
                pass
    rep = run.report()
    assert len(rep["trace"]) <= 3
    assert rep["dropped_spans"] >= 7  # root span competes for the cap too


def test_worker_snapshot_merge_is_process_aware():
    """Same-process snapshots (threaded local-mode harness) must not double
    count; foreign-process snapshots must merge into run AND global."""
    with obs.fit_run("Agg") as run:
        with obs.worker_scope(rank=0) as ws:
            profiling.count("agg.c", 5)
        snap = ws.snapshot()
        run.add_worker_snapshot(snap)  # same process: breakdown only
        run.add_worker_snapshot(
            json.loads(json.dumps(dict(snap, process="host2:deadbeef", rank=1)))
        )
    rep = run.report()
    assert rep["metrics"]["counters"]["agg.c"] == 10
    assert profiling.counter_totals()["agg.c"] == 10
    assert [w["merged"] for w in rep["workers"]] == [False, True]
    assert [w["rank"] for w in rep["workers"]] == [0, 1]


def test_observability_disabled_keeps_legacy_surface():
    config.set("observability.enabled", False)
    with obs.fit_run("Off") as run:
        profiling.count("off.c")
    assert run is None
    assert profiling.counter_totals()["off.c"] == 1


# ------------------------------------------------------------------ exporters


def test_run_report_jsonl_round_trip(tmp_path):
    config.set("observability.metrics_dir", str(tmp_path))
    with obs.fit_run("Exported") as run:
        profiling.count("exp.c", 2)
        with obs.span("phase"):
            pass
    reports = obs.load_run_reports(str(tmp_path))
    assert len(reports) == 1
    rep = reports[0]
    assert rep["run_id"] == run.report()["run_id"]
    assert rep["metrics"]["counters"]["exp.c"] == 2
    assert rep["trace"][0]["children"][0]["name"] == "phase"
    json.dumps(rep)  # fully JSON-serializable


def test_prometheus_rendering_and_textfile(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("up.loads").inc(3, site="ingest")
    reg.gauge("bytes.resident").set(42)
    reg.histogram("lat", buckets=[0.1, 1.0]).observe(0.5)
    text = obs.render_prometheus(reg.snapshot())
    assert '# TYPE srml_tpu_up_loads_total counter' in text
    assert 'srml_tpu_up_loads_total{site="ingest"} 3' in text
    assert "srml_tpu_bytes_resident 42" in text
    assert 'srml_tpu_lat_bucket{le="0.1"} 0' in text
    assert 'srml_tpu_lat_bucket{le="+Inf"} 1' in text
    assert "srml_tpu_lat_count 1" in text
    path = os.path.join(str(tmp_path), "metrics.prom")
    obs.write_prometheus_textfile(path, reg)
    assert open(path).read() == text


# --------------------------------------------- estimator fit report (e2e)


def test_streamed_fit_report_acceptance(n_devices, tmp_path):
    """THE acceptance criterion: a streamed multi-pass KMeans fit produces a
    model.fit_report_ whose trace tree holds ingest/step spans with per-batch
    histograms, whose counters include cache totals, and which round-trips
    through the JSONL exporter — with pass 2+ paying zero uploads, asserted
    from the REPORT, not process-global counters."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.observability.export import iter_spans

    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    config.set("observability.metrics_dir", str(tmp_path))
    rng = np.random.default_rng(0)
    X = np.concatenate(
        [rng.normal(-3, 1, (192, 8)), rng.normal(3, 1, (192, 8))]
    ).astype(np.float32)
    model = KMeans(k=2, maxIter=6, seed=5).fit(
        pd.DataFrame({"features": list(X)})
    )
    rep = model.fit_report_
    assert rep["status"] == "ok" and rep["algo"] == "KMeans"
    names = {s["name"] for s in iter_spans(rep)}
    assert {"KMeans.fit_run", "KMeans.fit_streaming", "kmeans.init",
            "kmeans.step", "stream.ingest"} <= names
    # ingest spans are CHILDREN of the pass-1 step span (compile rides pass 1)
    steps = [s for s in iter_spans(rep) if s["name"] == "kmeans.step"]
    assert len(steps) >= 2  # multi-pass
    pass1 = next(s for s in steps if s["attrs"]["pass"] == 1)
    assert pass1["attrs"]["compile"] is True
    assert any(c["name"] == "stream.ingest" for c in pass1["children"])
    # per-batch ingest histogram with one observation per upload
    c = rep["metrics"]["counters"]
    n_batches = -(-X.shape[0] // 64)
    assert c["stream.upload_batches"] == n_batches  # pass 2+ uploaded ZERO
    assert c["cache.hits"] == (len(steps) - 1) * n_batches
    hists = rep["metrics"]["histograms"]
    assert hists["stream.ingest_s.ingest{status=ok}"]["count"] == n_batches
    assert rep["metrics"]["gauges"]["cache.bytes_resident"] == 0
    # JSONL round-trip carries the same report
    back = obs.load_run_reports(str(tmp_path))
    assert back[-1]["run_id"] == rep["run_id"]
    assert back[-1]["metrics"]["counters"]["stream.upload_batches"] == n_batches


def test_fit_report_records_reliability_events(n_devices):
    """A streamed fit through an injected transient ingest fault lands the
    fault + resume as structured events in the fit report."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.reliability import reset_faults

    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    config.set("reliability.backoff_base_s", 0.001)
    config.set("reliability.backoff_max_s", 0.002)
    config.set("reliability.fault_spec", "ingest:batch=1:raise=OSError")
    reset_faults()
    try:
        rng = np.random.default_rng(1)
        X = rng.normal(size=(256, 6)).astype(np.float32)
        model = KMeans(k=2, maxIter=3, seed=2).fit(
            pd.DataFrame({"features": list(X)})
        )
    finally:
        for key in ("reliability.fault_spec", "reliability.backoff_base_s",
                    "reliability.backoff_max_s"):
            config.unset(key)
        reset_faults()
    kinds = [e["kind"] for e in model.fit_report_["events"]]
    assert "fault" in kinds and "resume" in kinds
    assert model.fit_report_["metrics"]["counters"]["reliability.fault.ingest"] == 1
