"""`pca_k3_d3000.fit` (PR 32): the cell as `BENCHMARK.json` declares it,
rehearsed on the CPU with a tiny copy of its configuration (counts only), and
the files behind its entries. The tiny benchmark file is not edited: the cell,
its configuration and its metrics are laid over a copy of it here."""

import json
import os

import pytest

from cellbench import harness
from cellbench.estimators import pca as family
from cellbench.readers import program_seconds_per_op

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CONFIG = "pca_k3_d3000"
CELL = CONFIG + ".fit"
NEW = ["cov_roofline", "fit_gram_xla_per_op", "fit_eig_s", "fit_eig_solve_s", "fit_eig_device_s"]
LISTED = ["fit_host_prepare_s", "fit_upload_floor_s", "ingest_bytes_copied_per_fit",
          "fit_device_busy_s", "fit_mfu", "compiles_in_window.fit", "fit_upload_wait_s",
          "fit_h2d_bytes_per_op", "fit_ingest_s", "fit_finish_s", "fit_cov_s"] + NEW


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
    res = harness.run_cell(CELL, 2**31 + 32, 0.2, True, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    cfg = harness.load_cell(CELL, tiny_with_cell)["cfg"]
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert got == {
        "ingest_bytes_copied_per_fit": 0.0,
        "compiles_in_window.fit": 0.0,
        # the table and its weights
        "fit_h2d_bytes_per_op": float(cfg["rows"] * cfg["cols"] * 4 + cfg["rows"] * 4),
        # past MAX_FUSED_COLS the XLA program forms the Gram matrix
        "fit_gram_xla_per_op": 1.0,
    }
    assert set(res["checks"]) == {"explained_variance_rel_err", "total_variance_rel_err",
                                  "components_err", "mean_err"}


def test_an_untraced_line_and_the_narrow_cell_leave_the_new_metrics_out(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 33, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["metrics"] == {}
    res = harness.run_cell("pca_k3_d256.fit", 2**31 + 34, 0.2, True,
                           bench_json=tiny_with_cell, rehearsal=True)
    assert not set(NEW) & set(res["metrics"])


def test_the_bf16_reference_in_the_programs_place_is_not_correct(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 35, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True, control=True)
    assert res["correct"] is False


def test_the_new_reader_reads_nothing_where_there_is_nothing_to_read():
    class Ctx:
        on_chip, ops, events, lo, hi = True, 3, [], 0.0, 1.0

    spec = {"program": "_pca_from_cov"}
    assert program_seconds_per_op.read(Ctx, spec) is None  # no such program in the trace
    Ctx.on_chip = False
    assert program_seconds_per_op.read(Ctx, spec) is None  # off the chip: no device seconds
    plane = "/device:TPU:0"
    Ctx.on_chip = True
    Ctx.events = [(plane, "XLA Modules", "jit__pca_from_cov", 0.0, 0.6e9, ""),
                  (plane, "XLA Modules", "jit_weighted_covariance", 0.6e9, 0.3e9, "")]
    Ctx.lo, Ctx.hi = 0.0, 1e9
    assert program_seconds_per_op.read(Ctx, spec) == pytest.approx(0.2)


def test_the_entries_are_appended_and_name_files_that_are_there():
    bench = real()
    assert bench["configs"][-1]["name"] == CONFIG and bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1] == {**bench["workloads"][-1], "config": CONFIG,
                                      "traffic": "fit", "chips": 1}
    assert [m["name"] for m in bench["per_layer"][-5:]] == NEW
    kinds = {}
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "fit_rows_per_s_chip"
        assert m["layer"] in {o["layer"] for o in bench["per_layer"][:-5]}
        spec = json.load(open(os.path.join(ROOT, "cellbench", "metrics", m["name"] + ".json")))
        kinds[m["name"]] = spec["kind"]
        assert os.path.exists(os.path.join(ROOT, "cellbench", "readers", spec["kind"] + ".py"))
    assert kinds == {"cov_roofline": "roofline", "fit_gram_xla_per_op": "report_counter_per_op",
                     "fit_eig_s": "report_counter_per_op",
                     "fit_eig_solve_s": "report_counter_per_op",
                     "fit_eig_device_s": "program_seconds_per_op"}
    listed = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert sorted(listed) == sorted(LISTED)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL  # appended, nothing moved
    spec = harness.load_cell(CELL, os.path.join(ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_rows_per_s_chip", "setup_s"]


def test_the_configuration_states_upstreams_widths_and_cuts_rows_only():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    narrow = json.load(open(os.path.join(ROOT, "cellbench", "configs", "pca_k3_d256.json")))
    (entry,) = [c for c in real()["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == cfg["reduced"] == ["rows"] and entry["source"] == cfg["source"]
    assert len(entry["source"]) <= 200 and "run_benchmark.sh" in entry["source"]
    assert cfg["architecture"] is None
    assert cfg["params"] == {"k": 3, "inputCol": "features"}
    assert (cfg["cols"], cfg["dtype"], cfg["estimator"]) == (3000, "float32", "pca")
    assert cfg["published"] == {"rows": 1000000, "cols": 3000, "k": 3, "dtype": "float32"}
    # the largest multiple of 1024 rows under the program's 4 GiB streaming threshold
    assert cfg["rows"] % 1024 == 0
    assert cfg["rows"] * 3000 * 4 < 4 << 30 <= (cfg["rows"] + 1024) * 3000 * 4
    assert cfg["program_settings"] == {}
    # the narrow PCA cell's table, control and guarantees
    assert cfg["table"] == narrow["table"] and cfg["control"] == narrow["control"]
    assert cfg["control"]["fit"] == {"program_settings": {"parity_precision": "high"}}
    assert {k: cfg["guarantees"][k] for k in narrow["guarantees"]} == narrow["guarantees"]
    assert set(cfg["limits"]["fit"]) == set(narrow["limits"]["fit"])
    for key in ("source", "rows", "table", "estimator", "limits"):
        assert cfg["assumed"][key]
    # the kernel's work from shapes: compute-bound at this width, where the
    # narrow cell's is memory-bound
    work = family.kernel_work(cfg)
    assert work == {"flops": 2.0 * cfg["rows"] * 3000 * 3000, "bytes": cfg["rows"] * 3000 * 4.0}
    assert work["flops"] / 197e12 > work["bytes"] / 819e9
    narrow_work = family.kernel_work(narrow)
    assert narrow_work["flops"] / 197e12 < narrow_work["bytes"] / 819e9
