"""`kmeans_k1000_d3000.fit` (PR 28): the cell as `BENCHMARK.json` declares it,
rehearsed on the CPU with a tiny copy of its configuration (counts only), and
the files behind its entries. The tiny benchmark file is not edited: the cell,
its configuration and its metrics are laid over a copy of it here."""

import json
import os

import pytest

from cellbench import harness
from cellbench.estimators import kmeans_wide as family

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CONFIG = "kmeans_k1000_d3000"
CELL = CONFIG + ".fit"
NEW = ["fit_lloyd_xla_per_op", "fit_centers_d2h_bytes_per_op", "fit_init_random_s"]
LISTED = ["fit_host_prepare_s", "fit_upload_floor_s", "ingest_bytes_copied_per_fit",
          "fit_device_busy_s", "fit_mfu", "lloyd_roofline", "fit_n_iter", "compiles_in_window.fit",
          "fit_upload_wait_s", "fit_h2d_bytes_per_op", "fit_ingest_s", "fit_finish_s", "fit_init_s",
          "fit_lloyd_s", "fit_summary_s", "fit_d2h_bytes_per_op"] + NEW


def real():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def tiny_with_cell(tmp_path_factory):
    """The tiny benchmark plus this cell: its configuration's entry pointed at
    the tiny copy, and every metric the real file lists for the cell."""
    bench, tiny = real(), json.load(open(TINY))
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    tiny["configs"].append({**entry, "file": f"cellbench/tests/data/configs/{CONFIG}.json"})
    tiny["workloads"].append(next(w for w in bench["workloads"] if w["name"] == CELL))
    have = {m["name"]: m for m in tiny["end_to_end"] + tiny["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL not in m.get("workloads", []):
            continue
        if m["name"] in have:
            have[m["name"]]["workloads"].append(CELL)
        else:
            tiny["per_layer"].append({**m, "workloads": [CELL]})
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def test_traced_rehearsal_reports_the_cells_counts(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 28, 0.2, True, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    cfg = harness.load_cell(CELL, tiny_with_cell)["cfg"]
    k, d, rows = cfg["params"]["k"], cfg["cols"], cfg["rows"]
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert got == {
        "ingest_bytes_copied_per_fit": 0.0,
        "fit_n_iter": float(cfg["params"]["maxIter"]),
        "compiles_in_window.fit": 0.0,
        # the table and its weights, padded to the four virtual devices
        "fit_h2d_bytes_per_op": float(rows * d * 4 + rows * 4),
        # the summary's k int32 sizes: a random start weighs no candidates
        "fit_d2h_bytes_per_op": 4.0 * k,
        "fit_lloyd_xla_per_op": 1.0,
        # the start's k rows and the result's k centres
        "fit_centers_d2h_bytes_per_op": 2.0 * k * d * 4,
    }
    assert set(res["checks"]) == {"center_step_err", "inertia_rel_err", "sizes_mismatch_share"}


def test_an_untraced_line_and_the_older_cells_leave_the_new_metrics_out(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 29, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["metrics"] == {}
    res = harness.run_cell("kmeans_k20_d128.fit", 2**31 + 30, 0.2, True,
                           bench_json=tiny_with_cell, rehearsal=True)
    assert not set(NEW) & set(res["metrics"])


def test_the_bf16_reference_in_the_programs_place_is_not_correct(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 31, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True, control=True)
    assert res["correct"] is False


def test_the_entries_are_appended_and_name_files_that_are_there():
    bench = real()
    assert bench["configs"][-1]["name"] == CONFIG and bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1] == {**bench["workloads"][-1], "config": CONFIG,
                                      "traffic": "fit", "chips": 1}
    assert [m["name"] for m in bench["per_layer"][-3:]] == NEW
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "fit_rows_per_s_chip"
        assert m["layer"] in {o["layer"] for o in bench["per_layer"][:-3]}
        spec = json.load(open(os.path.join(ROOT, "cellbench", "metrics", m["name"] + ".json")))
        assert spec["kind"] == "report_counter_per_op"
    listed = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert sorted(listed) == sorted(LISTED)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL  # appended, nothing moved
    spec = harness.load_cell(CELL, os.path.join(ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_rows_per_s_chip", "setup_s"]


def test_the_configuration_states_upstreams_widths_and_cuts_rows_only():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    (entry,) = [c for c in real()["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == cfg["reduced"] == ["rows"] and entry["source"] == cfg["source"]
    assert len(entry["source"]) <= 200 and "run_benchmark.sh" in entry["source"]
    assert cfg["params"] == {"k": 1000, "maxIter": 30, "tol": 1e-20, "initMode": "random"}
    assert (cfg["cols"], cfg["dtype"], cfg["estimator"]) == (3000, "float32", "kmeans_wide")
    assert cfg["published"]["rows"] == 1000000 and cfg["published"]["cols"] == cfg["cols"]
    assert cfg["published"]["k"] == cfg["params"]["k"]
    # the largest multiple of 1024 rows under the program's 4 GiB streaming threshold
    assert cfg["rows"] % 1024 == 0
    assert cfg["rows"] * 3000 * 4 < 4 << 30 <= (cfg["rows"] + 1024) * 3000 * 4
    assert cfg["program_settings"] == {}
    assert cfg["control"]["fit"] == {"program_settings": {"fast_math": True}}
    assert set(cfg["limits"]["fit"]) == {"center_step_err", "inertia_rel_err",
                                         "sizes_mismatch_share"}
    for key in ("source", "rows", "tol", "table"):
        assert cfg["assumed"][key]
    # the kernel's work from shapes: compute-bound at this shape
    work = family.kernel_work(cfg)
    assert work["flops"] == 30 * 2.0 * cfg["rows"] * 1000 * 3000
    assert work["flops"] / 197e12 > work["bytes"] / 819e9
