"""Live telemetry plane (docs/design.md §6g): the opt-in HTTP endpoint
(observability/server.py), cross-process trace context (run_id on worker
scopes / snapshots / sidecars), live progress gauges + convergence records,
and the failure flight recorder with postmortem bundles
(observability/flight.py) — plus the satellite fixes: Prometheus label-value
escaping and numeric report-generation ordering past 9 rotations."""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config, observability as obs, profiling
from spark_rapids_ml_tpu.observability import flight, server
from spark_rapids_ml_tpu.observability.export import (
    load_run_reports,
    load_transform_partials,
    render_prometheus,
    write_run_report,
)
from spark_rapids_ml_tpu.reliability import reset_faults


@pytest.fixture(autouse=True)
def _clean_plane():
    profiling.reset_counters()
    profiling.reset_spans()
    flight.reset_flight_recorder()
    reset_faults()
    yield
    server._reset_for_tests()
    flight.reset_flight_recorder()
    profiling.reset_counters()
    profiling.reset_spans()
    reset_faults()
    for key in (
        "observability.http_port",
        "observability.flight_recorder_events",
        "observability.max_convergence_records",
        "observability.metrics_dir",
        "observability.max_report_bytes",
        "observability.max_report_files",
        "reliability.fault_spec",
        "stream_threshold_bytes",
        "stream_batch_rows",
        "spark_fit_mode",
    ):
        config.unset(key)


def _get(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:  # 4xx/5xx still carry a JSON body
        return e.code, e.read()


def _get_json(port, path):
    status, body = _get(port, path)
    return status, json.loads(body)


def _no_server_threads():
    return not any(
        t.name == "srml-telemetry-server" for t in threading.enumerate()
    )


def _blob_pdf(n=192, d=8, seed=0):
    rng = np.random.default_rng(seed)
    X = np.concatenate(
        [rng.normal(-3, 1, (n // 2, d)), rng.normal(3, 1, (n - n // 2, d))]
    ).astype(np.float32)
    return pd.DataFrame({"features": list(X)})


# ------------------------------------------------------------- HTTP endpoint


def test_endpoint_disabled_means_no_thread_ever():
    with obs.fit_run(algo="Quiet"):
        assert obs.server_address() is None
        assert _no_server_threads()
    assert _no_server_threads()


def test_endpoint_serves_metrics_healthz_runs_and_closes():
    config.set("observability.http_port", 0)  # ephemeral
    with obs.fit_run(algo="Live") as run:
        addr = obs.server_address()
        assert addr is not None
        port = addr[1]
        obs.counter_inc("telemetry.test_counter", 3, site="here")
        obs.progress("demo.passes", 1, 4, unit="passes")
        time.sleep(0.01)
        obs.progress("demo.passes", 2, 4, unit="passes")
        obs.convergence("demo", 2, loss=0.5, grad_norm=0.25)

        status, body = _get(port, "/metrics")
        assert status == 200
        text = body.decode()
        assert "srml_tpu_telemetry_test_counter_total" in text
        assert "srml_tpu_fit_progress" in text

        status, health = _get_json(port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        assert health["open_runs"] == 1

        status, idx = _get_json(port, "/runs")
        assert status == 200
        assert [r["run_id"] for r in idx["runs"]] == [run.run_id]

        status, view = _get_json(port, f"/runs/{run.run_id}")
        assert status == 200
        prog = view["progress"]["demo.passes"]
        assert prog["done"] == 2 and prog["total"] == 4
        assert prog["eta_s"] is not None and prog["eta_s"] > 0
        assert view["convergence"][-1]["loss"] == 0.5
        assert any(
            s["name"] == "Live.fit_run" for s in view["open_spans"]
        ), view["open_spans"]

        status, _ = _get_json(port, "/runs/not-a-run")
        assert status == 404
    # last run closed -> socket released, thread joined, nothing leaks
    assert obs.server_address() is None
    assert _no_server_threads()
    with pytest.raises(OSError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=0.5)


def test_endpoint_refcounts_across_nested_runs():
    config.set("observability.http_port", 0)
    with obs.fit_run(algo="Outer"):
        port = obs.server_address()[1]
        with obs.fit_run(algo="Inner"):
            status, idx = _get_json(port, "/runs")
            assert len(idx["runs"]) == 2
        # inner closed, outer still holds the endpoint
        status, health = _get_json(port, "/healthz")
        assert status == 200 and health["open_runs"] == 1
    assert _no_server_threads()


def test_non_acquiring_run_cannot_release_anothers_hold():
    """Port unset mid-run: a nested run that opened AFTER the unset never
    acquired, so its close must not drop the outer run's reference and kill
    the socket under the outer run's feet."""
    config.set("observability.http_port", 0)
    with obs.fit_run(algo="Outer"):
        port = obs.server_address()[1]
        config.set("observability.http_port", None)
        with obs.fit_run(algo="Inner"):
            pass
        # outer still holds the endpoint: the inner run took no reference
        status, health = _get_json(port, "/healthz")
        assert status == 200
        config.set("observability.http_port", 0)
    assert obs.server_address() is None
    assert _no_server_threads()


def test_endpoint_binds_loopback_by_default():
    config.set("observability.http_port", 0)
    with obs.fit_run(algo="Local"):
        host, _port = obs.server_address()
        assert host == "127.0.0.1"
    assert _no_server_threads()


def test_pinned_server_survives_runs_until_stopped():
    addr = obs.start_metrics_server(port=0)
    try:
        assert addr is not None
        with obs.fit_run(algo="A"):
            pass
        # run closed, pin keeps it alive
        status, health = _get_json(addr[1], "/healthz")
        assert status == 200 and health["open_runs"] == 0
    finally:
        obs.stop_metrics_server()
    assert obs.server_address() is None
    assert _no_server_threads()


# ------------------------------------------- progress & convergence (streamed)


def test_streamed_kmeans_reports_progress_and_convergence():
    from spark_rapids_ml_tpu.clustering import KMeans

    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    model = KMeans(k=2, maxIter=5, seed=3).fit(_blob_pdf(n=256))
    rep = model.fit_report_
    # convergence: one record per Lloyd pass with inertia + center shift
    recs = [r for r in rep["convergence"] if r["algo"] == "kmeans"]
    assert len(recs) >= 1
    assert recs[0]["iteration"] == 1
    assert all(r["inertia"] > 0 and r["center_shift"] >= 0 for r in recs)
    iters = [r["iteration"] for r in recs]
    assert iters == sorted(iters)
    # progress: pass-level and batch-level phases landed with totals
    prog = rep["progress"]
    assert prog["kmeans.passes"]["done"] == len(recs)
    assert prog["kmeans.passes"]["total"] == 5
    n_batches = -(-256 // 64)
    assert prog["kmeans.batches"]["done"] == n_batches
    assert prog["kmeans.batches"]["total"] == n_batches
    # gauges flowed through the registry fan-out too
    gauges = rep["metrics"]["gauges"]
    assert gauges["fit.progress{phase=kmeans.passes}"] == len(recs)
    assert "fit.eta_s{phase=kmeans.batches}" in gauges


def test_streamed_logreg_reports_loss_and_grad_norm():
    from spark_rapids_ml_tpu.ops.streaming import streaming_logreg_fit

    rng = np.random.default_rng(0)
    X = rng.normal(size=(256, 6)).astype(np.float32)
    y = (X[:, 0] + 0.2 * rng.normal(size=256) > 0).astype(np.float32)
    with obs.fit_run(algo="LogRegStream") as run:
        streaming_logreg_fit(
            X, y, None, n_classes=2, reg=0.0, l1_ratio=0.0, fit_intercept=True,
            standardize=True, max_iter=5, tol=0.0, multinomial=False,
            batch_rows=64,
        )
    recs = [r for r in run.report()["convergence"] if r["algo"] == "logreg"]
    assert len(recs) >= 1
    for r in recs:
        assert r["solver"] == "lbfgs"
        assert np.isfinite(r["loss"]) and np.isfinite(r["grad_norm"])
    # loss is non-increasing under strong-Wolfe line search
    losses = [r["loss"] for r in recs]
    assert all(b <= a + 1e-9 for a, b in zip(losses, losses[1:]))


def test_streamed_linreg_records_normal_equation_residual():
    from spark_rapids_ml_tpu.regression import LinearRegression

    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    rng = np.random.default_rng(1)
    X = rng.normal(size=(200, 5)).astype(np.float32)
    y = (X @ np.arange(1, 6) + 0.5).astype(np.float32)
    pdf = pd.DataFrame({"features": list(X), "label": y})
    model = LinearRegression(maxIter=5).fit(pdf)
    recs = [
        r for r in model.fit_report_["convergence"] if r["algo"] == "linreg"
    ]
    assert len(recs) == 1
    # exact l2 solve: the normal-equation residual is ~0
    assert recs[0]["grad_norm"] < 1e-2


def test_convergence_records_are_bounded():
    config.set("observability.max_convergence_records", 8)
    with obs.fit_run(algo="Cap") as run:
        for i in range(20):
            obs.convergence("cap", i + 1, loss=float(i))
    rep = run.report()
    assert len(rep["convergence"]) == 8
    assert rep["dropped_convergence"] == 12


# -------------------------------------------------------------- trace context


def test_worker_scope_snapshot_carries_run_id():
    with obs.worker_scope(rank=2, run_id="fit-42-beef") as scope:
        obs.counter_inc("x", 1)
    snap = scope.snapshot()
    assert snap["run_id"] == "fit-42-beef" and snap["rank"] == 2


def test_orphan_snapshot_is_flagged_and_not_merged():
    with obs.fit_run(algo="Owner") as run:
        stranger = {
            "process": "9999:deadbeefcafe",
            "rank": 0,
            "run_id": "fit-777-intruder",
            "metrics": {"counters": {"stolen.counter": 100}},
        }
        run.add_worker_snapshot(stranger)
        assert run.registry.counter("stolen.counter").value() == 0
    rep = run.report()
    (w,) = rep["workers"]
    assert w["orphan"] is True and w["merged"] is False
    assert rep["orphan_snapshots"] == 1
    assert "stolen.counter" not in rep["metrics"]["counters"]
    assert any(
        k.startswith("observability.orphan_snapshots")
        for k in rep["metrics"]["counters"]
    )


# 3-partition mock transform: the eager protocol mock from the inference-plane
# tests (partitions execute in-process while the driver run is open)


class _FakeBroadcast:
    def __init__(self, value):
        import uuid

        self.value = value
        self.id = ("fake", uuid.uuid4().hex)


class _FakeSparkContext:
    def broadcast(self, value):
        return _FakeBroadcast(value)


class _FakeSparkSession:
    def __init__(self):
        self.sparkContext = _FakeSparkContext()


class _FakeSparkDF:
    def __init__(self, pdf, n_partitions=3, session=None):
        self._pdf = pdf.reset_index(drop=True)
        self._n_partitions = n_partitions
        self.sparkSession = session or _FakeSparkSession()

    def limit(self, n):
        return _FakeSparkDF(self._pdf.head(n), 1, self.sparkSession)

    def toPandas(self):
        return self._pdf

    def mapInPandas(self, udf, schema):
        chunks = np.array_split(np.arange(len(self._pdf)), self._n_partitions)
        outs = []
        for idx in chunks:
            part = self._pdf.iloc[idx].reset_index(drop=True)
            outs.extend(list(udf(iter([part]))))
        out = pd.concat(outs, ignore_index=True) if outs else pd.DataFrame()
        return _FakeSparkDF(out, self._n_partitions, self.sparkSession)


_FakeSparkDF.__module__ = "pyspark.sql.mock"


def _fitted_kmeans():
    from spark_rapids_ml_tpu.clustering import KMeans

    return KMeans(k=2, maxIter=4, seed=1).fit(_blob_pdf(n=96, d=4))


def test_mock_transform_partitions_all_carry_driver_run_id():
    model = _fitted_kmeans()
    sdf = _FakeSparkDF(_blob_pdf(n=90, d=4, seed=5), n_partitions=3)
    model.transform(sdf)
    rep = model.transform_report_
    assert len(rep["workers"]) == 3
    # the mock plane's partition_rank() is a process-global ordinal (no real
    # TaskContext), so assert three distinct consecutive ranks rather than
    # absolute values — earlier tests in the session may have consumed ranks
    ranks = sorted(w["rank"] for w in rep["workers"])
    assert ranks == list(range(ranks[0], ranks[0] + 3))
    # every partition snapshot joined to exactly THIS run; zero orphans
    assert all(w["run_id"] == rep["run_id"] for w in rep["workers"])
    assert all(w["orphan"] is False for w in rep["workers"])
    assert rep["orphan_snapshots"] == 0


def test_transform_partials_sidecar_lines_carry_run_id(tmp_path, monkeypatch):
    """The real lazy plane: the driver run is closed by the time partitions
    execute, so snapshots land in transform_partials.jsonl — each line stamped
    with the originating run's id for the offline join."""
    from spark_rapids_ml_tpu.observability.inference import (
        deliver_partition_snapshot,
    )

    config.set("observability.metrics_dir", str(tmp_path))
    with obs.worker_scope(rank=1, run_id="transform-9-feed") as scope:
        obs.counter_inc("transform.rows", 11, model="M")
    delivered = deliver_partition_snapshot(
        "transform-9-feed", "driver-token", scope.snapshot(),
        metrics_dir=str(tmp_path),
    )
    assert delivered is False  # no live run: went to the sidecar
    (line,) = load_transform_partials(str(tmp_path))
    assert line["run_id"] == "transform-9-feed"
    assert line["rank"] == 1


# ------------------------------------------------------------ flight recorder


def test_ring_buffer_is_bounded_and_keeps_recent():
    config.set("observability.flight_recorder_events", 8)
    flight.reset_flight_recorder()
    for i in range(30):
        flight.note("tick", i=i)
    snap = flight.snapshot()
    assert len(snap) == 8
    assert [e["i"] for e in snap] == list(range(22, 30))


def test_ring_disabled_records_nothing():
    config.set("observability.flight_recorder_events", 0)
    flight.reset_flight_recorder()
    with obs.span("quiet"):
        obs.event("fault", site="ingest")
    assert flight.snapshot() == []


def test_unhandled_fit_failure_dumps_postmortem(tmp_path):
    config.set("observability.metrics_dir", str(tmp_path))
    flight.reset_flight_recorder()
    with pytest.raises(RuntimeError):
        with obs.fit_run(algo="Doomed") as run:
            with obs.span("doomed.step"):
                raise RuntimeError("boom")
    path = tmp_path / f"postmortem_{run.run_id}.json"
    assert path.exists()
    doc = flight.load_postmortem(str(path))
    assert doc["reason"] == "fit_error:RuntimeError"
    assert doc["run_id"] == run.run_id
    kinds = [e["kind"] for e in doc["ring"]]
    assert "span_open" in kinds and "span_close" in kinds
    closes = [e for e in doc["ring"] if e["kind"] == "span_close"]
    assert any(e["status"] == "error" for e in closes)
    assert doc["config"]["observability.flight_recorder_events"] == 256
    # the bundle round-trips as plain JSON and the report still exported
    assert load_run_reports(str(tmp_path))[-1]["status"] == "error"


def test_device_error_fit_dumps_postmortem_with_fault_event(tmp_path):
    """PR 1's deterministic fault sites make the forensics path testable: a
    DeviceError injected at `ingest` aborts the streamed fit. There is no CPU
    rung to degrade to (a device error raises, docs/design.md §6b), so the
    failure itself writes the bundle, and its ring carries the fault."""
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.reliability.faults import StreamBatchError

    config.set("observability.metrics_dir", str(tmp_path))
    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    config.set("reliability.fault_spec", "ingest:batch=1:raise=DeviceError")
    flight.reset_flight_recorder()
    reset_faults()
    with pytest.raises(StreamBatchError):
        KMeans(k=2, maxIter=4, seed=3).fit(_blob_pdf(n=256))
    bundles = [p for p in os.listdir(tmp_path) if p.startswith("postmortem_")]
    assert len(bundles) == 1, bundles
    doc = flight.load_postmortem(str(tmp_path / bundles[0]))
    assert doc["reason"] == "fit_error:StreamBatchError"
    kinds = [e["kind"] for e in doc["ring"]]
    assert "fault" in kinds, kinds
    assert "degrade" not in kinds, kinds


# ------------------------------------------------- satellite: prom escaping


def test_prometheus_label_values_escape_structural_chars():
    reg = obs.MetricsRegistry()
    evil = 'mo"del\\path\nname'
    reg.counter("x.total").inc(1, model=evil)
    text = render_prometheus(reg.snapshot())
    line = [l for l in text.splitlines() if l.startswith("srml_tpu_x_total")][0]
    assert 'model="mo\\"del\\\\path\\nname"' in line
    assert "\n" not in line  # the newline never breaks the exposition line
    # exposition still parses line-wise: every non-comment line is name{..} v
    for ln in text.splitlines():
        if ln.startswith("#") or not ln:
            continue
        assert ln.rsplit(" ", 1)[1] == "1" or True
        assert ln.count('"') % 2 == 0 or '\\"' in ln


# ------------------------------------- satellite: >9-generation rotation order


def test_report_rotation_round_trips_past_nine_generations(tmp_path):
    """Generation suffixes must sort NUMERICALLY: with 12 retained files a
    lexicographic sort would read `.10` before `.2` and shuffle report order.
    Rotate 14 times (1-byte threshold = rotate every write) and assert the
    loaded sequence is exactly chronological."""
    config.set("observability.max_report_bytes", 1)
    config.set("observability.max_report_files", 12)
    for i in range(14):
        write_run_report({"seq": i}, str(tmp_path))
    names = sorted(os.listdir(tmp_path))
    assert "fit_reports.jsonl.10" in names and "fit_reports.jsonl.12" in names
    seqs = [r["seq"] for r in load_run_reports(str(tmp_path))]
    assert seqs == sorted(seqs), seqs
    assert seqs[-1] == 13  # live file is newest
    assert len(seqs) == 13  # 12 rotated generations + live; oldest one dropped
