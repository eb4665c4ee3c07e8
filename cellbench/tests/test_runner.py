"""The runner rehearsed end to end on the CPU: tiny configuration files that
live in this directory, four virtual devices for the sharded configuration.
Off the chip it must report no time, rate or device share."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cellbench import data, harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CELLS = ["kmeans_k20_d128.fit", "pca_k3_d256.fit", "kmeans_k20_d128.transform",
         "kmeans_k20_d128_4chip.fit"]
COUNTS = {"program_counter"}


def sources(bench_json=TINY):
    bench = json.load(open(bench_json))
    return {m["name"]: m["source"] for m in bench["per_layer"] + bench["end_to_end"]}


@pytest.mark.parametrize("workload", CELLS)
def test_untraced_rehearsal_is_correct_and_reports_no_speed(workload):
    res = harness.run_cell(workload, 2**31 + 12345, 0.2, False, bench_json=TINY, rehearsal=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    assert res["metrics"] == {}  # a rate or a set-up time from a CPU is not a device metric
    assert res["device"]["platform"] == "cpu" and "busy_s" not in res["device"]
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_rehearsal_reports_counts_only(workload):
    res = harness.run_cell(workload, 77, 0.2, True, bench_json=TINY, rehearsal=True)
    src = sources()
    assert res["correct"] is True
    assert res["metrics"], "a traced rehearsal still reads the program's counters"
    assert {src[name] for name in res["metrics"]} <= COUNTS
    assert "breakdown" not in res and "busy_s" not in res["device"]
    op = "transform" if workload.endswith("transform") else "fit"
    assert res["metrics"][f"compiles_in_window.{op}"]["value"] == 0
    if workload.startswith("kmeans") and op == "fit":
        assert res["metrics"]["fit_n_iter"]["value"] == 30


def test_sharded_configuration_all_reduces_on_four_devices():
    res = harness.run_cell("kmeans_k20_d128_4chip.fit", 5, 0.2, True, bench_json=TINY,
                           rehearsal=True)
    # Lloyd's combined all-reduce: (20x128 sums + 20 counts + inertia + shift) x 4 B
    assert res["metrics"]["allreduce_bytes_per_fit"]["value"] == 10324
    assert res["device"]["count"] == 4


def test_cli_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "cellbench", "run.py"), "--workload",
         "kmeans_k20_d128.fit", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_more_chips_asked_than_found_is_refused():
    with pytest.raises(harness.NoAccelerator):
        harness.start_jax(chips=64, rehearsal=True)


def test_same_seed_same_table_on_one_device_or_four():
    import jax

    table = {"components": 5, "center_scale": 0.3, "factor_scales": [2.0], "offset_scale": 1.0}
    big = 2**31 + 99
    a, pa = data.make_table(table, 3000, 16, big, jax.devices()[:1])
    b, _ = data.make_table(table, 3000, 16, big, jax.devices()[:4])
    c, _ = data.make_table(table, 3000, 16, big + 1, jax.devices()[:1])
    assert a.dtype == np.float32 and a.flags.c_contiguous and a.shape == (3000, 16)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert pa["centers"].shape == (5, 16) and pa["loadings"].shape == (1, 16)


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """One new configuration, traffic mix, per-layer metric (of an existing
    kind) and cell, as new files and new entries: nothing that is there is
    edited, and the runner finds each by its name."""
    extra = tmp_path / "cellbench_more"
    for d in ("configs", "traffic", "metrics"):
        (extra / d).mkdir(parents=True)
    cfg = json.load(open(os.path.join(HERE, "data", "configs", "kmeans_k20_d128.json")))
    cfg.update(rows=8192, cols=32, params={**cfg["params"], "k": 8, "maxIter": 5})
    (extra / "configs" / "kmeans_k8_d32.json").write_text(json.dumps(cfg))
    (extra / "traffic" / "fit_cold.json").write_text(json.dumps(
        {"operation": "fit", "rate_metric": "fit_rows_per_s_chip", "per_chip": True,
         "setup": [], "warmup_ops": 0}))
    (extra / "metrics" / "predict_calls_per_fit.json").write_text(json.dumps(
        {"kind": "report_counter_per_op", "counter": "device.kernel_calls",
         "labels": {"kernel": "kmeans.predict"}}))
    bench = json.load(open(TINY))
    bench["paths"].append("cellbench_more")
    bench["configs"].append({"name": "kmeans_k8_d32", "source": "test", "reduced": ["rows"],
                             "file": "cellbench_more/configs/kmeans_k8_d32.json", "why": "t"})
    bench["workloads"].append({"name": "kmeans_k8_d32.fit_cold", "config": "kmeans_k8_d32",
                               "traffic": "fit_cold", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "predict_calls_per_fit", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "kernels", "moves": "fit_rows_per_s_chip",
                               "workloads": ["kmeans_k8_d32.fit_cold"]})
    for m in bench["end_to_end"]:
        if m["name"] == "fit_rows_per_s_chip":
            m["workloads"].append("kmeans_k8_d32.fit_cold")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    res = harness.run_cell("kmeans_k8_d32.fit_cold", 3, 0.1, True, bench_json=str(path),
                           rehearsal=True)
    assert res["correct"] is True
    # k-means|| weighs its candidates with one predict pass, the summary takes another
    assert res["metrics"]["predict_calls_per_fit"]["value"] == 2
    assert "fit_n_iter" not in res["metrics"]  # it lists its cells, and this is not one
