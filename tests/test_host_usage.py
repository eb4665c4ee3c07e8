"""What the host does inside a wait: the `host.*` counters a span flagged with
`waits` and every run scope write (observability/runs.py, docs/design.md §6d).
Counts and CPU seconds of this process on the CPU backend: what is held here is
that the accounting is sound, never how fast anything is."""

import math
import os
import re
import threading
import time

import numpy as np
import pytest

from spark_rapids_ml_tpu import observability as obs
from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.observability import runs
from spark_rapids_ml_tpu.observability.inference import TransformRun

PKG = os.path.dirname(os.path.abspath(obs.__file__ + "/.."))
HOST = ("host.cpu_seconds", "host.thread_cpu_seconds", "host.page_faults",
        "host.ctx_switches")
# every label set a flagged span or a run writes, by counter
KINDS = {"host.cpu_seconds": [{"mode": "user"}, {"mode": "sys"}],
         "host.thread_cpu_seconds": [{}],
         "host.page_faults": [{"kind": "minor"}, {"kind": "major"}],
         "host.ctx_switches": [{"kind": "voluntary"}, {"kind": "involuntary"}]}


def _read(counters, name, **labels):
    """What `cellbench/readers/report_counter_per_op.py` would sum."""
    found = [float(v) for key, v in counters.items()
             if obs.split_label_key(key)[0] == name
             and all(obs.split_label_key(key)[1].get(k) == v for k, v in labels.items())]
    return sum(found) if found else None


def _host_keys(counters):
    return sorted(k for k in counters if k.startswith("host."))


def _burn(cpu_seconds):
    """Spin until THIS thread has used that much CPU."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_seconds:
        pass


def _in_scope(body, name="unit.wait", attrs=None):
    """The counters one span around `body` adds, seen by this thread alone."""
    with obs.worker_scope() as scope:
        with obs.span(name, attrs) as node:
            body()
    return scope.registry.snapshot()["counters"], node


# ------------------------------------------------------------ the primitive


def test_a_busy_loop_is_the_callers_cpu_and_the_processes():
    counters, node = _in_scope(lambda: _burn(0.05), attrs={"waits": "none"})
    caller = _read(counters, "host.thread_cpu_seconds", span="unit.wait")
    process = _read(counters, "host.cpu_seconds", span="unit.wait")
    assert caller >= 0.03 and process >= 0.03, (caller, process)
    # the close sample is taken a moment after the span's seconds are fixed
    assert caller <= node.duration_s * 1.01 + 1e-4, (caller, node.duration_s)


def test_the_close_sample_is_outside_the_spans_seconds(monkeypatch):
    """A flagged span's `span.seconds` is what an unflagged one's would be:
    the close sample and its counter writes come after `duration_s` is fixed
    (the open sample is inside: taken during a wait, the wait absorbs it)."""
    real = runs._host_usage_add

    def slow_add(name, waits, before):
        time.sleep(0.05)
        real(name, waits, before)

    monkeypatch.setattr(runs, "_host_usage_add", slow_add)
    counters, node = _in_scope(lambda: None, attrs={"waits": "device"})
    assert node.duration_s < 0.02
    assert _read(counters, "span.seconds", span="unit.wait") < 0.02
    assert _read(counters, "host.thread_cpu_seconds", span="unit.wait") is not None


def test_a_sleep_costs_the_caller_nothing():
    counters, node = _in_scope(lambda: time.sleep(0.05), attrs={"waits": "device"})
    assert node.duration_s >= 0.05
    assert _read(counters, "host.thread_cpu_seconds", waits="device") < 0.01


def test_another_threads_cpu_is_the_processes_and_not_the_callers():
    def body():
        worker = threading.Thread(target=_burn, args=(0.05,))
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()

    counters, _ = _in_scope(body, attrs={"waits": "upload"})
    assert _read(counters, "host.cpu_seconds", waits="upload") >= 0.03
    assert _read(counters, "host.thread_cpu_seconds", waits="upload") < 0.02


def test_fresh_pages_are_minor_faults():
    def body():
        fresh = np.empty(64 << 20, dtype=np.uint8)
        fresh[::4096] = 1  # one write a page
        assert fresh[0] == 1

    counters, _ = _in_scope(body, attrs={"waits": "none"})
    assert _read(counters, "host.page_faults", kind="minor", span="unit.wait") >= 1


@pytest.mark.parametrize("waits", ["upload", "device", "none"])
def test_a_flagged_span_writes_every_label_set_under_its_name_and_waits(waits):
    counters, _ = _in_scope(lambda: None, name="unit.flagged", attrs={"waits": waits, "site": "t"})
    want = sorted(obs.label_key(name, {"span": "unit.flagged", "waits": waits, **kind})
                  for name, kinds in KINDS.items() for kind in kinds)
    assert _host_keys(counters) == want
    assert all(math.isfinite(counters[k]) and counters[k] >= 0 for k in want)


@pytest.mark.parametrize("attrs", [None, {}, {"site": "fit", "bytes": 12}],
                         ids=["no_attrs", "empty", "other_attrs"])
def test_an_unflagged_span_samples_nothing(monkeypatch, attrs):
    def no_sample():
        raise AssertionError("an unflagged span sampled the host")

    monkeypatch.setattr(runs, "_host_sample", no_sample)
    counters, _ = _in_scope(lambda: _burn(0.001), attrs=attrs)
    assert _host_keys(counters) == []
    assert _read(counters, "span.seconds", span="unit.wait") > 0


def test_a_span_that_raises_still_records_its_usage():
    with obs.worker_scope() as scope:
        with pytest.raises(KeyError):
            with obs.span("unit.wait", {"waits": "device"}) as node:
                _burn(0.01)
                raise KeyError("boom")
    counters = scope.registry.snapshot()["counters"]
    assert node.status == "error"
    assert _read(counters, "host.thread_cpu_seconds", span="unit.wait") >= 0.005


def test_a_difference_is_never_negative(monkeypatch):
    """The kernel may move a tick from user to system time between two
    samples: the counter takes 0, not a negative increment."""
    real = runs._host_sample

    class Back:
        def __init__(self, ru):
            self.ru_utime, self.ru_stime = ru.ru_utime - 1.0, ru.ru_stime + 1.0
            self.ru_minflt, self.ru_majflt = ru.ru_minflt - 5, ru.ru_majflt
            self.ru_nvcsw, self.ru_nivcsw = ru.ru_nvcsw - 1, ru.ru_nivcsw - 1

    samples = []

    def stepping_back():
        ru, thread = real()
        samples.append(1)
        return (ru, thread) if len(samples) == 1 else (Back(ru), thread - 1.0)

    monkeypatch.setattr(runs, "_host_sample", stepping_back)
    counters, _ = _in_scope(lambda: None, attrs={"waits": "none"})
    assert len(samples) == 2
    assert _read(counters, "host.cpu_seconds", mode="user") == 0
    assert _read(counters, "host.cpu_seconds", mode="sys") >= 0.999
    assert _read(counters, "host.thread_cpu_seconds") == 0
    assert _read(counters, "host.page_faults", kind="minor") == 0
    assert all(v >= 0 for k, v in counters.items() if k.startswith("host."))


# ------------------------------------------------------------ the run scope


@pytest.mark.parametrize("scope_cls", [obs.FitRun, TransformRun], ids=["fit", "transform"])
def test_a_run_writes_its_whole_usage_under_waits_run(scope_cls):
    before = dict(profiling.counter_totals())
    with scope_cls("UnitAlgo") as run:
        _burn(0.03)
        with obs.span("unit.inner", {"waits": "device"}):
            _burn(0.01)
    counters = run.report()["metrics"]["counters"]
    want = sorted(obs.label_key(name, {"span": "run", "waits": "run", **kind})
                  for name, kinds in KINDS.items() for kind in kinds)
    assert [k for k in _host_keys(counters) if "span=run" in k] == want
    whole = _read(counters, "host.thread_cpu_seconds", waits="run")
    inner = _read(counters, "host.thread_cpu_seconds", span="unit.inner")
    assert whole >= 0.035 and 0.005 <= inner <= whole
    # the run is the denominator of the flagged spans' shares
    assert _read(counters, "host.cpu_seconds", waits="device") <= \
        _read(counters, "host.cpu_seconds", waits="run") + 1e-3
    # a value of `waits` of its own: no label set sums a span and its run
    assert _read(counters, "host.thread_cpu_seconds", waits="none") is None
    # and the process's totals moved by the same
    after = profiling.counter_totals()
    assert _read(after, "host.thread_cpu_seconds", waits="run") \
        - (_read(before, "host.thread_cpu_seconds", waits="run") or 0.0) >= whole - 1e-9


@pytest.mark.parametrize("what", ["span", "fit_run", "transform_run"])
def test_without_resource_spans_and_runs_still_close(monkeypatch, what):
    monkeypatch.setattr(runs, "_resource", None)
    assert runs._host_sample() is None
    with obs.worker_scope() as scope:
        if what == "span":
            with obs.span("unit.wait", {"waits": "upload"}) as closed:
                _burn(0.001)
            assert closed.duration_s > 0 and closed.status == "ok"
        else:
            with (obs.FitRun if what == "fit_run" else TransformRun)("UnitAlgo") as closed:
                with obs.span("unit.wait", {"waits": "device"}):
                    pass
            assert closed.status == "ok" and closed.duration_s >= 0
            assert _host_keys(closed.report()["metrics"]["counters"]) == []
    counters = scope.registry.snapshot()["counters"]
    assert _host_keys(counters) == []
    assert _read(counters, "span.seconds", span="unit.wait") is not None


# ------------------------------------------------------------ the flag's sites

# docs/metrics.md: a span that is a wait of a whole-table operation takes the
# flag, and nothing else does. `h2d.put` does not: a sample taken while the
# table is in flight delays the next put's dispatch; nor `transform.fetch`: no
# metric read it, and a small batch paid for it (PERF.md §6 PR 36)
FLAGGED = {("h2d.wait", "upload"), ("kmeans.lloyd", "device"), ("pca.cov", "device"),
           ("pca.eig.solve", "device"), ("logistic.solve", "device"), ("fit.stage", "none"),
           ("forest.grow", "device")}


def test_only_the_seven_spans_of_the_catalog_are_flagged():
    sites = []
    for root, _, files in os.walk(PKG):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(root, f)).read()
                sites += re.findall(r'span\(\s*"([a-z0-9_.]+)",\s*\{[^}]*"waits":\s*"(\w+)"', text)
    assert set(sites) == FLAGGED
    assert sorted(name for name, _ in sites).count("h2d.wait") == 2  # fit and transform
    assert len(sites) == len(FLAGGED) + 1


# ------------------------------------------------------------ the estimators


def _toy_fit(family):
    from spark_rapids_ml_tpu.classification import LogisticRegression, RandomForestClassifier
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import PCA

    rng = np.random.default_rng(36)
    X = rng.normal(size=(384, 12)).astype(np.float32)
    if family == "kmeans":
        return KMeans(k=3, maxIter=3, seed=1).fit(X), X
    if family == "pca":
        return PCA(k=2, inputCol="features").fit(X), X
    import pandas as pd

    y = (X[:, 0] + 0.3 * rng.normal(size=len(X)) > 0).astype(np.float32)
    frame = pd.DataFrame({"features": list(X), "label": y})
    if family == "forest":
        return RandomForestClassifier(numTrees=2, maxDepth=3, seed=1).fit(frame), X
    return LogisticRegression(maxIter=3, regParam=1e-3).fit(frame), X


@pytest.fixture(scope="module")
def fitted():
    cache = {}

    def get(family):
        if family not in cache:
            cache[family] = _toy_fit(family)
        return cache[family]

    return get


DEVICE_WAITS = {"kmeans": {"kmeans.lloyd"}, "pca": {"pca.cov", "pca.eig.solve"},
                "logreg": {"logistic.solve"}, "forest": {"forest.grow"}}


@pytest.mark.parametrize("labels", [{"waits": "upload"}, {"waits": "device"}, {"waits": "none"},
                                    {"waits": "run"}],
                         ids=["upload", "device", "none", "run"])
@pytest.mark.parametrize("family", ["kmeans", "pca", "logreg", "forest"])
def test_a_fit_leaves_its_waits_and_its_run_in_the_report(fitted, family, labels):
    model, _ = fitted(family)
    counters = model.fit_report_["metrics"]["counters"]
    for name in HOST:
        assert _read(counters, name, **labels) is not None, (name, labels, _host_keys(counters))
    spans = {obs.split_label_key(k)[1]["span"] for k in counters
             if k.startswith("host.cpu_seconds{")
             and all(obs.split_label_key(k)[1].get(a) == b for a, b in labels.items())}
    # a metric's labels take one kind of wait alone: no flagged span lies
    # inside another of the same `waits`
    want = {"upload": {"h2d.wait"}, "device": DEVICE_WAITS[family], "none": {"fit.stage"},
            "run": {"run"}}
    assert spans == want[labels["waits"]]


@pytest.mark.parametrize("family", ["kmeans", "pca", "logreg", "forest"])
def test_a_fits_shares_nest(fitted, family):
    """What the acceptance of a `--trace 1` line asks of every fit cell."""
    counters = fitted(family)[0].fit_report_["metrics"]["counters"]
    run_cpu = _read(counters, "host.cpu_seconds", waits="run")
    upload_cpu = _read(counters, "host.cpu_seconds", waits="upload")
    assert run_cpu > 0
    assert upload_cpu <= run_cpu + 1e-6
    assert _read(counters, "host.cpu_seconds", waits="upload", mode="sys") <= upload_cpu
    assert _read(counters, "host.thread_cpu_seconds", waits="upload") <= \
        1.01 * _read(counters, "span.seconds", span="h2d.wait") + 1e-4
    assert _read(counters, "host.page_faults", span="fit.stage", kind="minor") is not None
    # the puts carry no flag: their dispatch is timed as the parent timed it
    assert _read(counters, "host.cpu_seconds", span="h2d.put") is None
    assert _read(counters, "span.seconds", span="h2d.put") > 0


@pytest.mark.parametrize("labels", [{"waits": "upload"}, {"waits": "run"}], ids=["upload", "run"])
def test_a_transform_moves_the_processes_totals(fitted, labels):
    model, X = fitted("kmeans")
    model.transform(X)  # whatever compiles, compiles here
    before = dict(profiling.counter_totals())
    out = model.transform(X)
    after = dict(profiling.counter_totals())
    assert len(out) == len(X)
    calls = {"upload": "h2d.wait"}.get(labels["waits"])
    for name in HOST:
        assert _read(after, name, **labels) >= _read(before, name, **labels)
    assert _read(after, "host.thread_cpu_seconds", **labels) \
        > _read(before, "host.thread_cpu_seconds", **labels)
    if calls:
        assert _read(after, "span.calls", span=calls) == _read(before, "span.calls", span=calls) + 1
    report = model.transform_report_["metrics"]["counters"]
    assert _read(report, "host.cpu_seconds", **labels) is not None
    # the puts and the fetch carry no flag: a small batch pays two samples
    assert {obs.split_label_key(k)[1]["span"] for k in _host_keys(report)} == {"h2d.wait", "run"}
