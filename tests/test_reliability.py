"""Reliability subsystem (reliability/): deterministic fault injection at every
named site, checkpoint-resume for the streamed out-of-core fits, the
retry/backoff policy core, and the observability counters.

The load-bearing contract (ISSUE acceptance): with SRML_TPU_FAULT_SPEC injecting
a single transient fault at each named site, every streamed fit completes via
resume/retry with results IDENTICAL to the fault-free run — replay re-executes
the same device ops on the same batches in the same order, so equality is exact
(assert_array_equal), not approximate."""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config, profiling
from spark_rapids_ml_tpu.reliability import (
    DeviceError,
    RetryPolicy,
    StreamBatchError,
    fault_point,
    is_device_error,
    is_stage_retryable,
    is_transient,
    parse_fault_spec,
    reset_faults,
    resumable_accumulate,
)


@pytest.fixture(autouse=True)
def reliability_env():
    """Fast deterministic backoff, fresh counters/fault budgets, full cleanup."""
    config.set("reliability.backoff_base_s", 0.001)
    config.set("reliability.backoff_max_s", 0.002)
    profiling.reset_counters()
    reset_faults()
    yield
    for key in (
        "reliability.fault_spec",
        "reliability.backoff_base_s",
        "reliability.backoff_max_s",
        "reliability.max_attempts",
        "reliability.checkpoint_batches",
        "reliability.enabled",
        "stream_threshold_bytes",
        "stream_batch_rows",
        "fallback.enabled",
    ):
        config.unset(key)
    reset_faults()


def _inject(spec: str) -> None:
    config.set("reliability.fault_spec", spec)
    reset_faults()


# ------------------------------------------------------------- fault grammar


def test_fault_spec_grammar():
    specs = parse_fault_spec("ingest:batch=3:raise=OSError;barrier_init:times=2")
    assert len(specs) == 2
    assert specs[0].site == "ingest"
    assert specs[0].batch == 3
    assert specs[0].exc is OSError
    assert specs[0].times == 1  # transient by default
    assert specs[1].site == "barrier_init"
    assert specs[1].batch is None
    assert specs[1].times == 2


@pytest.mark.parametrize(
    "bad", ["ingest:batch", "ingest:frob=1", "ingest:raise=Nonsense", ":batch=1"]
)
def test_fault_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_fault_spec(bad)


def test_fault_point_fires_once_then_exhausts():
    _inject("mysite:raise=TimeoutError")
    with pytest.raises(TimeoutError):
        fault_point("mysite")
    fault_point("mysite")  # exhausted: no-op
    fault_point("othersite")  # unmatched site: no-op
    totals = profiling.counter_totals()
    assert totals["reliability.fault"] == 1
    assert totals["reliability.fault.mysite"] == 1


def test_fault_point_batch_targeting():
    _inject("s:batch=2:raise=OSError")
    fault_point("s", batch=0)
    fault_point("s", batch=1)
    with pytest.raises(OSError):
        fault_point("s", batch=2)


# -------------------------------------------------------- exception taxonomy


def test_exception_taxonomy():
    assert is_transient(OSError("preempted"))
    assert is_transient(MemoryError("one batch OOM"))
    assert is_transient(StreamBatchError("ingest", 3, OSError("x")))
    assert not is_transient(ValueError("bad param"))
    assert not is_transient(DeviceError("HBM fault"))
    assert is_device_error(DeviceError("x"))
    assert not is_device_error(OSError("x"))
    assert is_stage_retryable(RuntimeError("barrier wreckage"))
    assert is_stage_retryable(OSError("net"))
    assert not is_stage_retryable(ValueError("param"))
    assert not is_stage_retryable(DeviceError("x"))


# ------------------------------------------------------------- retry policy


def test_retry_policy_backoff_deterministic_and_bounded():
    p = RetryPolicy(max_attempts=5, backoff_base_s=0.1, backoff_max_s=0.5, jitter=0.2)
    delays = [p.delay_s(f, "site") for f in (1, 2, 3, 4)]
    assert delays == [p.delay_s(f, "site") for f in (1, 2, 3, 4)]  # replayable
    for f, d in enumerate(delays, start=1):
        base = min(0.1 * 2 ** (f - 1), 0.5)
        assert base * 0.9 <= d <= base * 1.1  # within +/- jitter/2
    assert p.delay_s(1, "a") != p.delay_s(1, "b")  # site-decorrelated


def test_retry_policy_run_retries_transient_only():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    p = RetryPolicy(max_attempts=3, backoff_base_s=0.001, backoff_max_s=0.001)
    assert p.run(flaky, site="t") == "ok"
    assert calls["n"] == 3
    assert profiling.counter_totals()["reliability.retry.t"] == 2

    def broken():
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        p.run(broken, site="t2")
    assert "reliability.retry.t2" not in profiling.counter_totals()


def test_retry_policy_exhaustion_raises_last_error():
    p = RetryPolicy(max_attempts=2, backoff_base_s=0.001, backoff_max_s=0.001)
    with pytest.raises(OSError, match="always"):
        p.run(lambda: (_ for _ in ()).throw(OSError("always")), site="x")
    assert profiling.counter_totals()["reliability.retry.x"] == 1


def test_retry_policy_from_config_honors_kill_switch():
    """reliability.enabled=False is the master switch: every policy-driven unit
    (ANN batches, pairwise blocks, barrier stage/init rounds) gets exactly one
    attempt, so failures surface immediately during debugging."""
    config.set("reliability.enabled", False)
    p = RetryPolicy.from_config()
    assert p.max_attempts == 1
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise OSError("transient")

    with pytest.raises(OSError):
        p.run(flaky, site="kill")
    assert calls["n"] == 1
    assert "reliability.retry.kill" not in profiling.counter_totals()


def test_retry_policy_deadline_gives_up_early():
    p = RetryPolicy(max_attempts=100, backoff_base_s=0.05, deadline_s=0.01)
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise OSError("x")

    with pytest.raises(OSError):
        p.run(always, site="d")
    assert calls["n"] == 1  # first backoff would already cross the deadline


# ------------------------------------------------- prefetch transparency


def test_prefetch_wraps_refill_errors_with_batch_context():
    from spark_rapids_ml_tpu.ops.streaming import _prefetch

    def gen():
        yield 0
        yield 1
        raise OSError("disk gone")

    got = []
    with pytest.raises(StreamBatchError) as ei:
        for x in _prefetch(gen(), depth=1, site="ingest"):
            got.append(x)
    assert got == [0, 1]  # both yielded batches were consumed before the break
    assert ei.value.site == "ingest"
    assert ei.value.batch_index == 2  # the refill of batch ordinal 2 broke
    assert isinstance(ei.value.__cause__, OSError)


def test_prefetch_passes_param_errors_through_unwrapped():
    """ValueError-class failures are API surface (bad cosine rows, bad params):
    they must keep their type even on a site-carrying stream."""
    from spark_rapids_ml_tpu.ops.streaming import _prefetch

    def gen():
        yield 0
        raise ValueError("zero-length vector")

    with pytest.raises(ValueError, match="zero-length"):
        list(_prefetch(gen(), depth=1, site="ingest"))


def test_prefetch_passes_errors_through_without_site():
    from spark_rapids_ml_tpu.ops.streaming import _prefetch

    def gen():
        yield 0
        raise RuntimeError("raw")

    with pytest.raises(RuntimeError, match="raw"):
        list(_prefetch(gen(), depth=1))


# ------------------------------------------------- checkpoint-resume core


def test_resumable_accumulate_resumes_from_snapshot_not_epoch_start():
    """n=10 unit batches, snapshot every 2: a transient failure fetching batch 7
    must replay from batch 6 (the last snapshot), not from batch 0."""
    config.set("reliability.checkpoint_batches", 2)
    fetched = []
    armed = {"fire": True}

    def factory(start_row):
        def gen():
            for i in range(start_row, 10):
                if i == 7 and armed["fire"]:
                    armed["fire"] = False
                    raise OSError("preempted")
                fetched.append(i)
                yield i
        return gen()

    out = resumable_accumulate(
        "unit", factory, lambda c, b: c + [b], [], batch_rows=1, n_rows=10
    )
    assert out == list(range(10))
    assert fetched == [0, 1, 2, 3, 4, 5, 6, 6, 7, 8, 9]
    assert profiling.counter_totals()["reliability.resume.unit"] == 1


def test_resumable_accumulate_budget_is_per_fault_not_per_stream():
    """Independent transient faults separated by forward progress must each get
    a fresh attempt budget: a long stream survives MORE total faults than
    max_attempts, as long as no single fault repeats past the budget."""
    config.set("reliability.checkpoint_batches", 1)
    config.set("reliability.max_attempts", 2)  # any single fault may retry once
    fire_at = {5, 12, 19}  # three independent faults, far apart
    armed = set(fire_at)

    def factory(start_row):
        def gen():
            for i in range(start_row, 25):
                if i in armed:
                    armed.discard(i)
                    raise OSError(f"preempted at {i}")
                yield i
        return gen()

    out = resumable_accumulate(
        "unit", factory, lambda c, b: c + [b], [], batch_rows=1, n_rows=25
    )
    assert out == list(range(25))
    assert profiling.counter_totals()["reliability.resume.unit"] == 3


def test_resumable_accumulate_repeating_fault_exhausts_budget():
    """The same fault firing on every attempt (no forward progress) must still
    exhaust max_attempts and raise — the budget reset needs real progress."""
    config.set("reliability.checkpoint_batches", 1)
    config.set("reliability.max_attempts", 3)
    attempts = {"n": 0}

    def factory(start_row):
        def gen():
            for i in range(start_row, 10):
                if i == 4:  # fires every attempt: batch 4 is poisoned
                    attempts["n"] += 1
                    raise OSError("hard preemption loop")
                yield i
        return gen()

    with pytest.raises(OSError):
        resumable_accumulate(
            "unit", factory, lambda c, b: c + [b], [], batch_rows=1, n_rows=10
        )
    assert attempts["n"] == 3  # initial + 2 retries, then give up


def test_resumable_accumulate_nontransient_propagates():
    def factory(start_row):
        def gen():
            yield 0
            raise ValueError("param bug")
        return gen()

    with pytest.raises(ValueError):
        resumable_accumulate(
            "unit", factory, lambda c, b: c + [b], [], batch_rows=1, n_rows=2
        )
    assert "reliability.resume.unit" not in profiling.counter_totals()


def test_resumable_accumulate_disabled_passthrough():
    config.set("reliability.enabled", False)

    def factory(start_row):
        def gen():
            yield 0
            raise OSError("no retries when disabled")
        return gen()

    with pytest.raises(OSError):
        resumable_accumulate(
            "unit", factory, lambda c, b: c + [b], [], batch_rows=1, n_rows=2
        )


# ---------------------------------------- streamed fit matrix (bit-identical)


@pytest.fixture
def tiny_stream(n_devices):
    config.set("stream_threshold_bytes", 1024)
    config.set("stream_batch_rows", 64)
    config.set("reliability.checkpoint_batches", 2)
    yield


def _linreg_case():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(500, 8)).astype(np.float32)
    y = (X @ rng.normal(size=8)).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "label": y})

    def fit():
        from spark_rapids_ml_tpu.regression import LinearRegression

        return LinearRegression(regParam=0.1).fit(df).get_model_attributes()

    return fit


def _pca_case():
    rng = np.random.default_rng(13)
    X = (rng.normal(size=(500, 10)) * np.linspace(1, 3, 10)).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})

    def fit():
        from spark_rapids_ml_tpu.feature import PCA

        return PCA(k=3, inputCol="features").fit(df).get_model_attributes()

    return fit


def _logreg_case():
    rng = np.random.default_rng(17)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    df = pd.DataFrame({"features": list(X), "label": y})

    def fit():
        from spark_rapids_ml_tpu.classification import LogisticRegression

        return (
            LogisticRegression(regParam=0.05, maxIter=25, tol=1e-7)
            .fit(df)
            .get_model_attributes()
        )

    return fit


def _kmeans_case():
    rng = np.random.default_rng(19)
    X = np.concatenate(
        [rng.normal(-3, 0.5, (200, 5)), rng.normal(3, 0.5, (200, 5))]
    ).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})

    def fit():
        from spark_rapids_ml_tpu.clustering import KMeans

        return KMeans(k=2, seed=3, maxIter=10).fit(df).get_model_attributes()

    return fit


def _assert_attrs_identical(clean, faulted):
    assert set(clean) == set(faulted)
    for key, value in clean.items():
        if value is None:
            assert faulted[key] is None
            continue
        np.testing.assert_array_equal(
            np.asarray(value), np.asarray(faulted[key]), err_msg=key
        )


@pytest.mark.parametrize(
    "case", [_linreg_case, _pca_case, _logreg_case, _kmeans_case],
    ids=["linreg", "pca", "logreg", "kmeans"],
)
def test_streamed_fit_resumes_bit_identical(tiny_stream, case):
    fit = case()
    clean = fit()
    _inject("ingest:batch=3:raise=OSError")
    faulted = fit()
    totals = profiling.counter_totals()
    assert totals.get("reliability.fault.ingest", 0) == 1
    assert totals.get("reliability.resume.ingest", 0) >= 1
    _assert_attrs_identical(clean, faulted)


def test_streamed_fit_nontransient_fault_propagates(tiny_stream):
    fit = _linreg_case()
    _inject("ingest:batch=1:raise=ValueError")
    with pytest.raises(ValueError, match="injected"):
        fit()


def test_streamed_ann_build_retries_bit_identical(tiny_stream):
    from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors

    rng = np.random.default_rng(23)
    X = rng.normal(size=(1200, 10)).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "id": np.arange(1200)})

    def fit():
        est = ApproximateNearestNeighbors(
            k=8, algorithm="ivfflat", algoParams={"nlist": 16, "nprobe": 8},
            inputCol="features", idCol="id",
        )
        return est.fit(df).get_model_attributes()

    clean = fit()
    _inject("ann_assign:batch=1:raise=OSError")
    faulted = fit()
    totals = profiling.counter_totals()
    assert totals.get("reliability.fault.ann_assign", 0) == 1
    assert totals.get("reliability.retry.ann_assign", 0) == 1
    for key in ("centers", "cells", "cell_ids", "cell_sizes"):
        np.testing.assert_array_equal(
            np.asarray(clean[key]), np.asarray(faulted[key]), err_msg=key
        )


def test_streamed_ann_search_retries_bit_identical():
    from spark_rapids_ml_tpu.ops.ann_streaming import (
        streaming_ivfflat_build,
        streaming_ivfflat_search,
    )

    rng = np.random.default_rng(29)
    X = rng.normal(size=(1500, 12)).astype(np.float32)
    index = streaming_ivfflat_build(X, nlist=16, max_iter=8, seed=3, batch_rows=400)
    d0, i0 = streaming_ivfflat_search(X[:96], index, k=8, nprobe=8, block=32)
    _inject("ann_search:batch=1:raise=OSError")
    d1, i1 = streaming_ivfflat_search(X[:96], index, k=8, nprobe=8, block=32)
    assert profiling.counter_totals().get("reliability.retry.ann_search", 0) == 1
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


def test_streamed_pq_encode_retries_bit_identical():
    from spark_rapids_ml_tpu.ops.ann_streaming import streaming_ivfpq_build

    rng = np.random.default_rng(31)
    X = rng.normal(size=(1000, 16)).astype(np.float32)
    kw = dict(nlist=8, m_subvectors=4, n_bits=5, max_iter=6, seed=5, batch_rows=300)
    clean = streaming_ivfpq_build(X, **kw)
    _inject("ann_encode:batch=2:raise=OSError")
    faulted = streaming_ivfpq_build(X, **kw)
    assert profiling.counter_totals().get("reliability.retry.ann_encode", 0) == 1
    np.testing.assert_array_equal(clean["codes"], faulted["codes"])
    np.testing.assert_array_equal(clean["codebooks"], faulted["codebooks"])


def test_streamed_pairwise_knn_retries_bit_identical(n_devices):
    from spark_rapids_ml_tpu.ops.pairwise_streaming import streaming_exact_knn

    rng = np.random.default_rng(37)
    X = rng.normal(size=(900, 8)).astype(np.float32)
    Q = X[:128]
    d0, i0 = streaming_exact_knn(Q, X, k=5, query_block=64, item_block=256)
    _inject("pairwise:batch=1:raise=OSError")
    d1, i1 = streaming_exact_knn(Q, X, k=5, query_block=64, item_block=256)
    assert profiling.counter_totals().get("reliability.retry.pairwise", 0) >= 1
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


def test_streamed_dbscan_retries_identical(n_devices):
    from spark_rapids_ml_tpu.ops.pairwise_streaming import (
        streaming_dbscan_fit_predict,
    )

    rng = np.random.default_rng(41)
    X = np.concatenate(
        [rng.normal(0, 0.2, (120, 4)), rng.normal(4, 0.2, (120, 4))]
    ).astype(np.float32)
    labels0 = streaming_dbscan_fit_predict(
        X, eps=0.8, min_samples=5, query_block=64, item_block=128
    )
    _inject("pairwise:batch=1:raise=OSError")
    labels1 = streaming_dbscan_fit_predict(
        X, eps=0.8, min_samples=5, query_block=64, item_block=128
    )
    assert profiling.counter_totals().get("reliability.retry.pairwise", 0) >= 1
    np.testing.assert_array_equal(labels0, labels1)


# ------------------------------------------------ device-error degradation


def test_device_error_raises_instead_of_a_host_fit(tiny_stream):
    """Unrecoverable device errors (DeviceError / XlaRuntimeError class) are
    never retried AND never answered by the sklearn twin: with fallback.enabled
    at its default the fit raises, so a model that exists ran on the device."""
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(43)
    X = rng.normal(size=(300, 6)).astype(np.float32)
    y = (X @ rng.normal(size=6)).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "label": y})

    assert config.get("fallback.enabled") is True
    _inject("ingest:batch=1:raise=DeviceError")
    with pytest.raises(StreamBatchError) as ei:
        LinearRegression(regParam=0.0).fit(df)
    assert isinstance(ei.value.__cause__, DeviceError)
    totals = profiling.counter_totals()
    assert not any(k.startswith("reliability.degrade") for k in totals), totals
    assert totals.get("reliability.resume.ingest", 0) == 0  # never retried


def test_xla_runtime_error_in_fit_raises_not_sklearn_model(monkeypatch):
    """The installed JAX's own device-failure class (jax.errors.JaxRuntimeError
    — what a Mosaic compile refusal, a VMEM overflow or an HBM OOM raises)
    thrown from inside the in-core fit kernel propagates out of
    `Estimator.fit`; the sklearn twin is never consulted."""
    from jax.errors import JaxRuntimeError

    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.models import clustering as clustering_mod

    assert is_device_error(JaxRuntimeError("RESOURCE_EXHAUSTED: HBM"))
    assert not is_transient(JaxRuntimeError("INTERNAL: Mosaic failed"))

    def refuse(*a, **k):
        raise JaxRuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(clustering_mod, "kmeans_fit", refuse)
    monkeypatch.setattr(
        KMeans, "_fallback_fit",
        lambda self, ds: pytest.fail("the sklearn twin must not run"))
    X = np.random.default_rng(5).normal(size=(64, 4)).astype(np.float32)
    with pytest.raises(JaxRuntimeError, match="Mosaic failed"):
        KMeans(k=2, maxIter=2, seed=1).fit(X)
    assert not any(
        k.startswith("reliability.degrade") for k in profiling.counter_totals())


def test_device_error_raises_when_reliability_disabled(tiny_stream):
    from spark_rapids_ml_tpu.regression import LinearRegression

    rng = np.random.default_rng(47)
    X = rng.normal(size=(300, 6)).astype(np.float32)
    y = (X @ rng.normal(size=6)).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "label": y})
    config.set("reliability.enabled", False)
    _inject("ingest:batch=1:raise=DeviceError")
    # the ingest pipeline still contextualizes the failure (StreamBatchError
    # wrapping the DeviceError), but nothing degrades or retries
    with pytest.raises(StreamBatchError) as ei:
        LinearRegression(regParam=0.0).fit(df)
    assert isinstance(ei.value.__cause__, DeviceError)


# ----------------------------------------------------------- observability


def test_counters_ride_profiling_totals():
    profiling.count("reliability.retry")
    profiling.count("reliability.retry", 2)
    totals = profiling.counter_totals()
    assert totals["reliability.retry"] == 3
    profiling.reset_counters()
    assert profiling.counter_totals() == {}
