"""Device-performance plane (observability/device.py — docs/design.md §6f):
compiled_kernel cost/memory-analysis capture + compile accounting, roofline
span attribution, HBM telemetry graceful degrade, histogram quantile edges,
corrupt-JSONL tolerance, scenario summaries, the profiler hook, and the
direction-aware *_mfu bench gate."""

import importlib.util
import json
import logging
import os
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config, profiling
from spark_rapids_ml_tpu import observability as obs
from spark_rapids_ml_tpu.observability import device as dev
from spark_rapids_ml_tpu.observability.export import (
    iter_spans,
    load_run_reports,
    write_run_report,
)


@pytest.fixture(autouse=True)
def _clean():
    profiling.reset_counters()
    profiling.reset_spans()
    dev.reset_device_plane()
    yield
    profiling.reset_counters()
    profiling.reset_spans()
    dev.reset_device_plane()
    for key in (
        "observability.device_enabled",
        "observability.hbm_sampling",
        "observability.peak_flops",
        "observability.peak_bw",
        "observability.profile_dir",
        "observability.profile_pass",
        "observability.metrics_dir",
        "stream_threshold_bytes",
        "stream_batch_rows",
    ):
        config.unset(key)


# ------------------------------------------------------------ compiled_kernel


def test_compiled_kernel_captures_cost_and_counts_signatures():
    @obs.compiled_kernel("t.mm", static_argnames=("scale",))
    def mm(a, b, scale=2.0):
        return (a @ b) * scale

    a, b = jnp.ones((32, 16)), jnp.ones((16, 8))
    out = mm(a, b)
    np.testing.assert_allclose(np.asarray(out), np.full((32, 8), 32.0))
    mm(a, b)  # same signature: cached executable, no second compile
    mm(jnp.ones((64, 16)), b)  # new shape: one more compile
    mm(a, b, scale=3.0)  # new STATIC value: one more compile
    # call-STYLE must not split the cache: explicitly passing the default
    # static, or passing it positionally, is the same signature
    mm(a, b, scale=2.0)
    mm(a, b, 2.0)
    mm(a, b=b)

    assert dev.compile_count("t.mm") == 3
    rec = dev.kernel_cost("t.mm")
    assert rec is not None and rec["flops"] > 0 and rec["bytes_accessed"] > 0
    totals = profiling.counter_totals()
    assert totals["device.compile{kernel=t.mm}"] == 3
    assert totals["device.kernel_calls{kernel=t.mm}"] == 7


def test_compiled_kernel_raises_on_aot_compile_failure(monkeypatch):
    """An AOT lower().compile() failure (on the chip: a Mosaic refusal, a VMEM
    or HBM overflow) RAISES to the caller. It used to be logged once and
    answered by plain jit, after which `device.compile{kernel=}` no longer
    described what ran."""
    @obs.compiled_kernel("t.refused")
    def k(a):
        return a * 2.0

    class Refusing:
        def lower(self, *a, **kw):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        def __call__(self, *a, **kw):
            pytest.fail("plain jit must not answer a failed AOT compile")

    monkeypatch.setattr(k, "_jit", Refusing())
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        k(jnp.ones((8,)))
    assert dev.compile_count("t.refused") == 0
    assert "device.kernel_calls{kernel=t.refused}" not in profiling.counter_totals()
    # nothing was cached for the signature: the next call compiles (and raises) again
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        k(jnp.ones((8,)))


def test_compiled_kernel_raises_on_aot_call_failure(monkeypatch):
    """A failing call of the cached AOT executable (wrong device, wrong
    sharding, OOM) RAISES; the executable is neither dropped nor replaced by
    plain jit for later calls."""
    @obs.compiled_kernel("t.callfail")
    def k(a):
        return a + 1.0

    x = jnp.ones((8,))
    np.testing.assert_allclose(np.asarray(k(x)), 2.0)  # compiled + cached
    (entry,) = k._cache.values()

    def broken_exe(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setitem(entry, "exe", broken_exe)
    monkeypatch.setattr(
        k, "_jit",
        lambda *a, **kw: pytest.fail("plain jit must not answer a failed call"))
    for _ in range(2):  # the second call hits the same executable, not jit
        with pytest.raises(RuntimeError, match="out of HBM"):
            k(x)
    assert profiling.counter_totals()["device.kernel_calls{kernel=t.callfail}"] == 1


def test_trace_epoch_rekeys_cache_on_parity_precision_change():
    """The sanction for the ONE trace-time config read (ops/_precision.py,
    docs/design.md §6j): parity_precision rides in every AOT signature, so
    changing it re-keys the cache and re-traces with the NEW value — the
    stale-bake hazard the purity pass bans is structurally impossible here."""
    from spark_rapids_ml_tpu.ops._precision import pdot

    @obs.compiled_kernel("t.epoch")
    def gram(x):
        return pdot(x.T, x)

    x = jnp.ones((16, 8))
    try:
        gram(x)
        gram(x)  # same epoch: cached, one compile
        assert dev.compile_count("t.epoch") == 1
        config.set("parity_precision", "high")
        gram(x)  # epoch changed: re-keyed, re-lowered with the new value
        assert dev.compile_count("t.epoch") == 2
        config.set("parity_precision", "highest")
        gram(x)  # back to the FIRST epoch's key: cache hit, no third compile
        assert dev.compile_count("t.epoch") == 2
    finally:
        config.unset("parity_precision")


def test_compiled_kernel_memory_analysis_breakdown():
    @obs.compiled_kernel("t.add")
    def add(a, b):
        return a + b

    add(jnp.ones((128,)), jnp.ones((128,)))
    rec = dev.kernel_cost("t.add")
    # two f32 (128,) args in, one out (CPU runtime reports exact sizes)
    assert rec["argument_bytes"] == 2 * 128 * 4
    assert rec["output_bytes"] == 128 * 4
    assert rec["peak_bytes"] >= rec["output_bytes"]


def test_compiled_kernel_inlines_under_trace():
    @obs.compiled_kernel("t.inner")
    def inner(x):
        return x * 2.0

    # grad/vmap trace through the wrapper: tracer leaves must fall back to the
    # plain jit path (the AOT executable cannot consume tracers)
    g = jax.grad(lambda x: inner(x).sum())(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(g), 2.0 * np.ones((4,)))
    v = jax.vmap(inner)(jnp.ones((3, 4)))
    assert v.shape == (3, 4)
    # the traced calls compiled no standalone executable for t.inner
    assert dev.compile_count("t.inner") == 0


def test_compiled_kernel_disabled_is_plain_jit():
    config.set("observability.device_enabled", False)

    @obs.compiled_kernel("t.off")
    def f(x):
        return x + 1.0

    np.testing.assert_allclose(np.asarray(f(jnp.zeros((4,)))), 1.0)
    assert dev.compile_count("t.off") == 0
    assert "device.compile{kernel=t.off}" not in profiling.counter_totals()


def test_compiled_kernel_donation_preserved():
    @obs.compiled_kernel("t.donate", donate_argnums=(0,))
    def bump(carry, x):
        return carry + x

    c = jnp.zeros((8,))
    c2 = bump(c, jnp.ones((8,)))
    np.testing.assert_allclose(np.asarray(c2), 1.0)
    assert c.is_deleted()  # the donated input really was consumed


def test_span_attribution_and_roofline_classification():
    config.set("observability.peak_flops", 1e12)
    config.set("observability.peak_bw", 1e9)  # ridge = 1000 flops/byte

    @obs.compiled_kernel("t.memk")
    def memk(a):
        return a + 1.0  # OI << 1000: memory-bound

    with obs.fit_run("DevTest") as run:
        with obs.span("devtest.step"):
            memk(jnp.ones((256, 64)))
    rep = run.report()
    step = next(s for s in iter_spans(rep) if s["name"] == "devtest.step")
    d = step["attrs"]["device"]
    assert d["flops"] > 0 and d["bytes"] > 0 and d["calls"] == 1
    assert d["roofline_bound"] == "memory"
    assert 0.0 <= d["mfu"] and d["roofline_frac"] >= 0.0
    assert d["kernels"] == {"t.memk": 1}
    # compute-bound classification with an inverted ridge
    config.set("observability.peak_flops", 1e12)
    config.set("observability.peak_bw", 1e15)  # ridge ~ 1e-3
    with obs.fit_run("DevTest2") as run2:
        with obs.span("devtest.step2"):
            memk(jnp.ones((256, 64)))
    rep2 = run2.report()
    step2 = next(s for s in iter_spans(rep2) if s["name"] == "devtest.step2")
    assert step2["attrs"]["device"]["roofline_bound"] == "compute"


def test_peak_overrides_and_platform_table():
    flops, bw, platform = dev.platform_peaks()
    assert flops > 0 and bw > 0
    config.set("observability.peak_flops", 123.0)
    config.set("observability.peak_bw", 456.0)
    assert dev.platform_peaks()[:2] == (123.0, 456.0)


def _FakeDevice(platform, device_kind):
    return types.SimpleNamespace(platform=platform, device_kind=device_kind)


@pytest.mark.parametrize("kind,flops,bw", [
    ("TPU v5 lite", 98e12, 819e9),
    ("TPU v5e", 98e12, 819e9),
    ("TPU v4", 137e12, 1228e9),
])
def test_known_tpu_device_kinds_resolve_to_their_row(monkeypatch, kind, flops, bw):
    monkeypatch.setattr(jax, "local_devices", lambda: [_FakeDevice("tpu", kind)])
    assert dev.platform_peaks() == (flops, bw, "tpu")


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v9 hyper"),  # a TPU no row names: there is no catch-all row
    ("tpu", ""),
    ("rocm", "MI300"),  # an unknown platform no longer gets the CPU row
])
def test_unknown_device_kind_raises_where_peaks_are_asked_for(
        monkeypatch, platform, kind):
    """A device that is not in the table is an error, not a default: a roofline
    share against another chip's peaks is a wrong number."""
    monkeypatch.setattr(
        jax, "local_devices", lambda: [_FakeDevice(platform, kind)])
    with pytest.raises(ValueError, match="no peak-table row"):
        dev.platform_peaks()
    with pytest.raises(ValueError, match="no peak-table row"):
        dev.platform_ici_bw()
    # an explicit override does not rescue it: the platform label itself
    # comes from the row
    config.set("observability.peak_flops", 1.0)
    with pytest.raises(ValueError, match="no peak-table row"):
        dev.platform_peaks()


# ----------------------------------------- streamed fit end-to-end (satellite)


def _streamed_kmeans_model():
    from spark_rapids_ml_tpu.clustering import KMeans

    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    rng = np.random.default_rng(0)
    X = np.concatenate(
        [rng.normal(-3, 1, (192, 8)), rng.normal(3, 1, (192, 8))]
    ).astype(np.float32)
    return KMeans(k=2, maxIter=6, seed=5).fit(
        pd.DataFrame({"features": list(X)})
    )


def test_streamed_kmeans_spans_carry_cost_and_roofline():
    model = _streamed_kmeans_model()
    rep = model.fit_report_
    steps = [s for s in iter_spans(rep) if s["name"] == "kmeans.step"]
    assert len(steps) >= 2
    for s in steps:
        d = s["attrs"]["device"]
        assert d["flops"] > 0 and d["bytes"] > 0
        assert d["roofline_bound"] in ("compute", "memory")
        assert "streaming.accum_kmeans" in d["kernels"]
    # compile counters match the distinct shape signatures the device plane
    # recorded per kernel — the accounting the recompile sentinel trusts
    counters = rep["metrics"]["counters"]
    for kernel in ("streaming.accum_kmeans",):
        key = f"device.compile{{kernel={kernel}}}"
        assert counters[key] == dev.compile_count(kernel), (key, counters)
    # the exported report carries the cost records themselves
    assert any(
        r["kernel"] == "streaming.accum_kmeans" and r["flops"] > 0
        for r in rep["device"]["kernels"]
    )


def test_scenario_summary_measures_mfu():
    model = _streamed_kmeans_model()
    summary = dev.scenario_summary(model.fit_report_, wall_s=1.0)
    assert summary["mfu"] > 0.0
    assert summary["roofline_bound"] in ("compute", "memory")
    assert summary["device_flops"] > 0 and summary["device_compiles"] >= 1


# ------------------------------------------------- HBM telemetry (satellite)


def test_memory_stats_graceful_degrade_on_cpu(caplog):
    """CPU runtimes return no memory_stats: gauges simply absent, nothing
    logged (no warning spam), and the probe short-circuits afterwards."""
    assert jax.local_devices()[0].platform == "cpu"
    with caplog.at_level(logging.WARNING):
        model = _streamed_kmeans_model()
        assert dev.sample_hbm(force=True) is None
    gauges = model.fit_report_["metrics"]["gauges"]
    assert not any("hbm" in k for k in gauges)
    totals = profiling.counter_totals()
    assert not any("hbm" in k for k in totals)
    assert not [r for r in caplog.records if "memory_stats" in r.message]
    # short-circuit: the unsupported verdict is cached
    assert dev._hbm_supported is False
    assert dev.sample_hbm(force=True) is None


def test_hbm_sampling_with_stubbed_stats(monkeypatch):
    """A runtime WITH memory_stats lands the in-use gauge and a per-run peak."""

    class _Dev:
        platform = "cpu"
        device_kind = "cpu"

        def memory_stats(self):  # stub standing in for a TPU runtime
            return {"bytes_in_use": 1 << 20}

    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev()])
    dev.reset_device_plane()
    with obs.fit_run("HbmTest") as run:
        assert dev.sample_hbm(force=True) == 1 << 20
    rep = run.report()
    assert rep["metrics"]["gauges"]["device.hbm_peak_bytes"] == 1 << 20
    assert (
        obs.global_registry().gauge("device.hbm_bytes_in_use").value()
        == 1 << 20
    )


# ------------------------------------------------ histogram quantile edges


def test_histogram_quantile_edges_and_minmax_merge():
    reg = obs.MetricsRegistry()
    h = reg.histogram("q", buckets=[1.0, 2.0, 4.0])
    assert h.quantile(0.5) is None  # empty: None, not an interpolation
    for v in (0.3, 1.7, 3.9):
        h.observe(v)
    assert h.quantile(0.0) == pytest.approx(0.3)  # true min
    assert h.quantile(1.0) == pytest.approx(3.9)  # true max
    assert h.quantile(-1.0) == pytest.approx(0.3)  # clamped
    assert h.quantile(2.0) == pytest.approx(3.9)
    # min/max survive snapshot merge (driver-side worker aggregation)
    other = obs.MetricsRegistry()
    oh = other.histogram("q", buckets=[1.0, 2.0, 4.0])
    oh.observe(0.1)
    oh.observe(9.0)
    reg.merge_snapshot(other.snapshot())
    assert reg.histogram("q").quantile(0.0) == pytest.approx(0.1)
    assert reg.histogram("q").quantile(1.0) == pytest.approx(9.0)
    # legacy states without min/max keep the interpolated clamp behavior
    from spark_rapids_ml_tpu.observability.registry import interpolate_quantile

    legacy = {"count": 4, "sum": 100.0, "buckets": [0, 0, 4]}
    assert interpolate_quantile(legacy, 1.0, [1.0, 2.0]) == pytest.approx(2.0)


# --------------------------------------------------- corrupt JSONL tolerance


def test_load_run_reports_skips_corrupt_lines(tmp_path):
    write_run_report({"run_id": "r-1"}, str(tmp_path))
    path = os.path.join(str(tmp_path), "fit_reports.jsonl")
    with open(path, "a") as f:
        f.write('{"run_id": "r-2", "truncated": tr\n')  # torn write
        f.write("not json at all\n")
        f.write('"a bare string is not a report"\n')
    write_run_report({"run_id": "r-3"}, str(tmp_path))
    reports = load_run_reports(str(tmp_path))
    assert [r["run_id"] for r in reports] == ["r-1", "r-3"]
    assert profiling.counter_totals()["observability.corrupt_lines"] == 3
    # a fully missing file still raises (pre-existing contract)
    with pytest.raises(OSError):
        load_run_reports(str(tmp_path / "nope.jsonl"))


# ----------------------------------------------------------- profiler hook


def test_profile_pass_gating(tmp_path):
    # no profile_dir: no-op, no trace artifacts
    with dev.profile_pass("site.a", 2):
        pass
    assert list(tmp_path.iterdir()) == []
    config.set("observability.profile_dir", str(tmp_path))
    config.set("observability.profile_pass", 2)
    with dev.profile_pass("site.a", 1):  # wrong pass: no capture
        pass
    assert list(tmp_path.iterdir()) == []
    with dev.profile_pass("site.a", 2):  # designated pass: captures
        jnp.ones((8,)).block_until_ready()
    out = tmp_path / "site_a"
    assert out.exists()
    assert profiling.counter_totals()["device.profile_captures{site=site.a}"] == 1
    with dev.profile_pass("site.a", 2):  # once per site per process
        pass
    assert profiling.counter_totals()["device.profile_captures{site=site.a}"] == 1


# ------------------------------------------------ bench gate: *_mfu direction


def _load_bench_check():
    path = Path(__file__).resolve().parent.parent / "ci" / "bench_check.py"
    spec = importlib.util.spec_from_file_location("bench_check_mfu", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_artifact(tmp_path, name, secondary):
    doc = {"parsed": {"secondary": dict(secondary, platform="cpu")}}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return p


def test_bench_check_mfu_is_higher_is_better(tmp_path):
    bc = _load_bench_check()
    _bench_artifact(tmp_path, "BENCH_r01.json",
                    {"pca_bench_secs": 10.0, "pca_mfu": 0.10})
    _bench_artifact(tmp_path, "BENCH_r02.json",
                    {"pca_bench_secs": 10.0, "pca_mfu": 0.04})
    # mfu DROPPED 60%: regression even though wall time is unchanged
    assert bc.check(str(tmp_path), threshold=0.25) == 1
    # mfu RISING is an improvement, never a failure
    _bench_artifact(tmp_path, "BENCH_r03.json",
                    {"pca_bench_secs": 10.0, "pca_mfu": 0.50})
    assert bc.check(str(tmp_path), threshold=0.25) == 0
    rows = bc.compare(
        bc.extract(str(tmp_path / "BENCH_r02.json")),
        bc.extract(str(tmp_path / "BENCH_r03.json")),
    )
    mfu_row = next(r for r in rows if r["scenario"] == "pca_mfu")
    assert mfu_row["verdict"] == "improved"
    secs_row = next(r for r in rows if r["scenario"] == "pca")
    assert secs_row["verdict"] == "ok"


def test_bench_check_extracts_mfu_from_escaped_tail(tmp_path):
    bc = _load_bench_check()
    # truncated wrapper whose bench line lives in an escaped `tail` string —
    # every quote appears as \" in the raw text and the regex sweep must hit
    raw = '{"tail": "{\\"pca_mfu\\": 0.031, \\"platform\\": \\"cpu\\"'
    (tmp_path / "BENCH_r01.json").write_text(raw)
    art = bc.extract(str(tmp_path / "BENCH_r01.json"))
    assert art["scenarios"].get("pca_mfu") == pytest.approx(0.031)
