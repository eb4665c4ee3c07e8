"""bench.py is ONE honest process: it refuses a CPU backend, does not catch a
failing unit, journals each finished unit to a progress JSONL and assembles
the one-line result from that journal.

These tests drive the helpers it keeps (`_read_progress`, `_assemble`), the
exit status (non-zero on a CPU backend and when a unit raises) and the device
identity on the line. Reference role: the bench runner protocol in the
reference harness (python/benchmark/benchmark/base.py:232-285) times every
family; the journal additionally keeps what a run measured before it was
killed at its time limit.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the parent commit's units, in order: this PR changes no unit, metric or shape
PARENT_UNITS = [
    "kmeans_headline", "pca", "logreg", "linreg", "rf", "umap", "dbscan",
    "fit_e2e", "cache", "ingest", "telemetry_overhead", "serving_qps",
    "serving_failover", "tracing_overhead", "continual", "large_k", "autotune",
    "knn", "ann", "ann_build", "wide256",
]

BOOT = {"unit": "boot", "status": "done", "platform": "tpu",
        "device_kind": "TPU v5 lite", "n_chips": 1,
        "result": {"n_rows": 100, "n_cols": 8}}


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write(path, entries):
    with open(path, "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")


def test_units_are_the_parent_commits(bench):
    assert bench.UNITS == PARENT_UNITS


def test_read_progress_last_entry_wins_and_skips_torn_lines(bench, tmp_path):
    p = tmp_path / "prog.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"unit": "pca", "status": "start"}) + "\n")
        f.write(json.dumps({"unit": "pca", "status": "done", "result": {"a": 1}}) + "\n")
        f.write('{"unit": "logreg", "status": "do')  # torn write from a kill
    state = bench._read_progress(str(p))
    assert state["pca"]["status"] == "done"
    assert "logreg" not in state


def test_read_progress_missing_file_is_empty(bench, tmp_path):
    assert bench._read_progress(str(tmp_path / "absent.jsonl")) == {}


def test_assemble_full_run_names_the_device(bench, tmp_path):
    p = tmp_path / "prog.jsonl"
    entries = [BOOT]
    for u in bench.UNITS:
        r = {"_value": 123.0} if u == "kmeans_headline" else {f"{u}_metric": 1.0}
        entries.append({"unit": u, "status": "done", "platform": "tpu", "result": r})
    _write(p, entries)
    line = bench._assemble(str(p), 240.0)
    assert line["metric"] == "kmeans_lloyd_rows_per_sec_per_chip"
    assert line["value"] == 123.0 and line["unit"] == "rows*iters/sec/chip"
    s = line["secondary"]
    assert (s["platform"], s["device_kind"], s["device_count"]) == (
        "tpu", "TPU v5 lite", 1)
    assert s["headline_n_rows"] == 100 and s["bench_budget_s"] == 240.0
    assert "skipped" not in s


def test_assemble_run_that_died_mid_unit_keeps_what_was_measured(bench, tmp_path):
    """A run killed at its time limit (or by a raising unit) leaves a `start`
    with no `done`: that unit and everything after it report as skipped, and
    the finished units' numbers stay on the line."""
    p = tmp_path / "prog.jsonl"
    _write(p, [
        BOOT,
        {"unit": "kmeans_headline", "status": "done", "platform": "tpu",
         "result": {"_value": 999.0, "kmeans_n_iter": 10}},
        {"unit": "pca", "status": "done", "platform": "tpu",
         "result": {"pca_cov_rows_per_sec_per_chip": 7.0}},
        {"unit": "logreg", "status": "start"},
    ])
    line = bench._assemble(str(p), 240.0)
    assert line["metric"] == "kmeans_lloyd_rows_per_sec_per_chip"
    assert line["value"] == 999.0
    s = line["secondary"]
    assert s["platform"] == "tpu"
    assert s["pca_cov_rows_per_sec_per_chip"] == 7.0
    assert s["skipped"][0] == "logreg"
    assert "rf" in s["skipped"] and "ann" in s["skipped"]


def test_assemble_headline_missing_promotes_family_metric(bench, tmp_path):
    p = tmp_path / "prog.jsonl"
    _write(p, [
        BOOT,
        {"unit": "kmeans_headline", "status": "start"},
        {"unit": "pca", "status": "done", "platform": "tpu",
         "result": {"pca_cov_rows_per_sec_per_chip": 55.5}},
    ])
    line = bench._assemble(str(p), 240.0)
    assert line["metric"] == "pca_cov_rows_per_sec_per_chip"
    assert line["value"] == 55.5 and line["unit"] == "rows/sec/chip"
    assert line["secondary"]["headline_fallback"] is True
    assert "kmeans_headline" in line["secondary"]["skipped"]


def test_assemble_deadline_skip_is_reported_skipped(bench, tmp_path):
    p = tmp_path / "prog.jsonl"
    bench._flush_progress(str(p), BOOT)
    bench._flush_progress(str(p), {"unit": "pca", "status": "deadline_skip"})
    state = bench._read_progress(str(p))
    assert state["pca"]["status"] == "deadline_skip" and "ts" in state["pca"]
    line = bench._assemble(str(p), 1.0)
    assert "pca" in line["secondary"]["skipped"]


def test_assemble_empty_progress_yields_labeled_zero_line(bench, tmp_path):
    p = tmp_path / "prog.jsonl"
    _write(p, [])
    line = bench._assemble(str(p), 240.0)
    assert line["value"] == 0.0
    assert line["secondary"]["platform"] == "none"
    assert line["secondary"]["device_kind"] is None
    assert set(line["secondary"]["skipped"]) == set(bench.UNITS)


def test_assemble_never_touches_the_baseline_without_a_dir(bench, tmp_path):
    p = tmp_path / "prog.jsonl"
    _write(p, [BOOT, {"unit": "kmeans_headline", "status": "done",
                      "platform": "tpu", "result": {"_value": 5.0}}])
    assert bench._assemble(str(p), 1.0)["vs_baseline"] == 1.0
    assert not os.path.exists(os.path.join(REPO, "BENCH_BASELINE.json"))
    # with a dir, a TPU headline seeds it and the next run compares against it
    first = bench._assemble(str(p), 1.0, baseline_dir=str(tmp_path))
    assert first["vs_baseline"] == 1.0
    _write(p, [BOOT, {"unit": "kmeans_headline", "status": "done",
                      "platform": "tpu", "result": {"_value": 10.0}}])
    assert bench._assemble(str(p), 1.0, baseline_dir=str(tmp_path))[
        "vs_baseline"] == 2.0


# ------------------------------------------------------------------ exit status


def test_main_refuses_a_cpu_backend(bench, capsys, monkeypatch, tmp_path):
    """No TPU -> exit 2, a reason on stderr, NO result line, nothing run."""
    monkeypatch.setattr(bench, "RESULTS_DIR", str(tmp_path / "results"))
    monkeypatch.setattr(
        bench, "run_units",
        lambda *a, **k: pytest.fail("units must not run without a TPU"))
    assert bench.main() == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "not 'tpu'" in out.err and "'cpu'" in out.err
    assert not os.path.exists(tmp_path / "results")


def _fake_tpu(bench, monkeypatch, tmp_path):
    import spark_rapids_ml_tpu.utils as srml_utils

    monkeypatch.setattr(bench, "_device", lambda: types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite"))
    monkeypatch.setattr(bench, "RESULTS_DIR", str(tmp_path / "results"))
    # the helper's contract has its own tests; do not re-point this test
    # process's compile cache at the checkout
    monkeypatch.setattr(srml_utils, "enable_compile_cache", lambda: "unused")


def test_main_does_not_catch_a_unit_that_raises(bench, capsys, monkeypatch, tmp_path):
    _fake_tpu(bench, monkeypatch, tmp_path)

    def boom(progress, deadline_ts, units=None):
        bench._flush_progress(progress, {"unit": "pca", "status": "start"})
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(bench, "run_units", boom)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        bench.main()  # propagates: the interpreter exits non-zero
    assert capsys.readouterr().out == ""  # and no result line was printed
    # what ran before the failure is on disk
    journal = tmp_path / "results" / "bench_progress_last.jsonl"
    assert bench._read_progress(str(journal))["pca"]["status"] == "start"


def test_main_prints_one_line_with_the_device(bench, capsys, monkeypatch, tmp_path):
    _fake_tpu(bench, monkeypatch, tmp_path)
    monkeypatch.setattr(bench, "REPO_ROOT", str(tmp_path))  # baseline IO lands here

    def units(progress, deadline_ts, units=None):
        bench._flush_progress(progress, BOOT)
        bench._flush_progress(progress, {
            "unit": "kmeans_headline", "status": "done", "platform": "tpu",
            "result": {"_value": 42.0}})

    monkeypatch.setattr(bench, "run_units", units)
    assert bench.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[0])
    assert line["value"] == 42.0
    s = line["secondary"]
    assert (s["platform"], s["device_kind"], s["device_count"]) == (
        "tpu", "TPU v5 lite", 1)
    saved = tmp_path / "results" / "chip_bench_tpu.json"
    assert json.loads(saved.read_text()) == line


def test_run_units_deadline_guard_and_unguarded_failure(bench, tmp_path):
    """`run_units` itself (callable on the CPU mesh for CI's counts-and-keys
    smoke): a unit past the deadline is journaled `deadline_skip` and not run;
    a unit that raises is not caught."""
    p = tmp_path / "prog.jsonl"
    bench.run_units(str(p), time.time(), units=["pca"])  # no time left
    state = bench._read_progress(str(p))
    assert state["boot"]["platform"] == "cpu" and state["boot"]["device_kind"]
    assert state["pca"]["status"] == "deadline_skip"
    with pytest.raises(KeyError):
        bench.run_units(str(p), time.time() + 600, units=["no_such_unit"])
    assert bench._read_progress(str(p))["no_such_unit"]["status"] == "start"


def test_bench_process_exits_nonzero_on_cpu():
    """The real process: `python bench.py` under JAX_PLATFORMS=cpu exits 2 and
    its stdout carries no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, timeout=300, capture_output=True, text=True,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "refusing to run" in proc.stderr
