"""Device-performance plane (observability/device.py — docs/design.md §6f):
compiled_kernel cost/memory-analysis capture + compile accounting, span cost
attribution, HBM telemetry graceful degrade, histogram quantile edges and
corrupt-JSONL tolerance."""

import logging
import os

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


@pytest.mark.parametrize("first,second", [("high", "highest"), ("highest", "high")])
def test_trace_epoch_change_retraces_a_kernel_already_traced_at_the_shape(first, second):
    """Re-keying the AOT cache is not enough: `jit.lower()` answers from jit's
    own trace cache, so a kernel that has traced a shape at one
    `parity_precision` used to compile the SAME program again under the new
    key. After the change of epoch the kernel must run what a kernel that has
    never traced compiles at the second precision. The CPU backend computes
    every f32 dot in full, so the bits cannot tell two precisions apart here;
    the compiled program's `operand_precision` can, and it is what decides the
    bits on the chip."""
    import re

    from spark_rapids_ml_tpu.ops._precision import pdot

    def make(name):
        @obs.compiled_kernel(name)
        def gram(x):
            return pdot(x.T, x)

        return gram

    def last_program(kernel):
        return list(kernel._cache.values())[-1]["exe"].as_text()

    def precisions(text):
        return re.findall(r"operand_precision=\{(\w+),(\w+)\}", text)

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(64, 8)).astype(np.float32))
    try:
        config.set("parity_precision", first)
        kernel = make("t.epoch_retrace")
        kernel(x)
        program_first = last_program(kernel)
        config.set("parity_precision", second)
        out_second = np.asarray(kernel(x))
        program_second = last_program(kernel)
        fresh = make("t.epoch_fresh")  # has traced nothing: a new process's kernel
        out_fresh = np.asarray(fresh(x))
        assert precisions(program_first) == [(first, first)]
        assert precisions(program_second) == [(second, second)]
        assert precisions(last_program(fresh)) == [(second, second)]
        np.testing.assert_array_equal(out_second, out_fresh)
        # an unchanged epoch re-traces and compiles nothing
        kernel(x)
        assert dev.compile_count("t.epoch_retrace") == 2
        assert "HloModule jit_gram" in program_second  # the trace readers' name
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


def test_streamed_kmeans_spans_carry_cost():
    model = _streamed_kmeans_model()
    rep = model.fit_report_
    steps = [s for s in iter_spans(rep) if s["name"] == "kmeans.step"]
    assert len(steps) >= 2
    for s in steps:
        d = s["attrs"]["device"]
        assert d["flops"] > 0 and d["bytes"] > 0
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
