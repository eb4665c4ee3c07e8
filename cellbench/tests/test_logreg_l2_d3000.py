"""`logreg_l2_d3000.fit` (PR 34): the cell as `BENCHMARK.json` declares it,
rehearsed on the CPU with a tiny copy of its configuration (counts only), and
the files behind its entries. The tiny benchmark file is not edited: the cell,
its configuration and its metrics are laid over a copy of it here."""

import json
import os

import numpy as np
import pytest

from cellbench import harness, work
from cellbench.estimators import logreg as family

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CONFIG = "logreg_l2_d3000"
CELL = CONFIG + ".fit"
NEW = {"logreg_roofline": "roofline", "fit_solve_s": "report_counter_per_op",
       "fit_solve_device_s": "program_seconds_per_op",
       "fit_loss_evals_per_op": "report_counter_per_op",
       "fit_linesearch_steps_per_op": "report_counter_per_op",
       "fit_labels_s": "report_counter_per_op", "fit_result_fetch_s": "report_counter_per_op",
       "fit_qn_path_per_op": "report_counter_per_op",
       "fit_ingest_zero_copy_bytes_per_op": "report_counter_per_op"}
LISTED = ["fit_host_prepare_s", "fit_upload_floor_s", "ingest_bytes_copied_per_fit",
          "fit_device_busy_s", "fit_mfu", "fit_n_iter", "compiles_in_window.fit",
          "fit_upload_wait_s", "fit_h2d_bytes_per_op", "fit_ingest_s", "fit_finish_s"]


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
    res = harness.run_cell(CELL, 2**31 + 34, 0.2, True, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    cfg = harness.load_cell(CELL, tiny_with_cell)["cfg"]
    table = cfg["rows"] * cfg["cols"] * 4
    got = {name: m["value"] for name, m in res["metrics"].items()}
    evals = got.pop("fit_loss_evals_per_op")
    steps = got.pop("fit_linesearch_steps_per_op")
    # one evaluation a line-search step, and the first iteration's own
    assert evals == steps + 1 and cfg["params"]["maxIter"] <= steps <= 20 * cfg["params"]["maxIter"]
    assert got == {
        # the Arrow column is a view of the host table: nothing is copied
        "ingest_bytes_copied_per_fit": 0.0,
        "fit_ingest_zero_copy_bytes_per_op": float(table),
        "compiles_in_window.fit": 0.0,
        # the table, its weights and its labels
        "fit_h2d_bytes_per_op": float(table + 2 * cfg["rows"] * 4),
        "fit_n_iter": float(cfg["params"]["maxIter"]),
        "fit_qn_path_per_op": 1.0,
    }
    assert set(res["checks"]) == {"objective_rel_err", "fit_gradient_err", "first_step_err",
                                  "grad_norm_rel"}


def test_the_bf16_reference_in_the_programs_place_is_not_correct(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 35, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True, control=True)
    assert res["correct"] is False and res["metrics"] == {}
    over = [name for name, c in res["checks"].items() if c["value"] > c["limit"]]
    # the step from zero, and the gradient where the timed fit stopped: there
    # the logits of a whole model are rounded, and the control reads 1e4 times
    # the program (7e-3 to 2e-2 against 2e-7 at this size)
    assert {"first_step_err", "fit_gradient_err"} <= set(over)
    assert res["checks"]["fit_gradient_err"]["value"] > 100 * res["checks"]["fit_gradient_err"]["limit"]


@pytest.mark.parametrize("fault", ["bf16_window", "no_gradient", "half_budget"])
def test_a_fault_in_the_window_alone_is_not_correct(fault):
    """The one-step fit after the window is an executable of its own (`maxIter`
    is static), so a fault of the timed program has to show in what the timed
    fits produced. A window that read the table in bfloat16, and a program
    that reports no gradient, fail `fit_gradient_err` while `first_step_err`
    passes. A loop that stopped at half its budget and said 200 is NOT caught
    by `grad_norm_rel` (PERF.md section 7): only the program's own `n_iter`
    and `logistic.loss_evals` speak for the iteration count."""
    from cellbench import logreg_ref as ref

    cfg = json.load(open(os.path.join(HERE, "data", "configs", CONFIG + ".json")))
    params, limits = cfg["params"], cfg["limits"]["fit"]
    rng = np.random.default_rng(34)
    X = (rng.normal(size=(8192, 48)) * np.linspace(1.0, 6.0, 48)).astype(np.float32)

    def refit(overrides):
        return family.build({**params, **overrides}, 1).fit(X)

    answer = family.fit_outputs(refit({"maxIter": 100} if fault == "half_budget" else {}))
    y = family.labelled(X, params)[1]
    if fault == "bf16_window":
        _, answer["gradient"] = ref.value_and_gradient(
            X, y, answer["coefficients"], answer["intercept"], params["regParam"],
            low_precision=True)
    elif fault == "no_gradient":
        answer["gradient"] = None
    (got,) = family.check_fit(X, [answer], refit, params)
    over = {name for name, limit in limits.items() if got[name] > limit}
    assert over == ({"fit_gradient_err"} if fault != "half_budget" else set()), got


def test_the_same_table_gives_the_same_labels_and_one_arrow_view():
    rng = np.random.default_rng(34)
    X = rng.normal(size=(4096, 24)).astype(np.float32)
    params = {"featuresCol": "features", "labelCol": "label"}
    table, y = family.labelled(X, params)
    assert family.labelled(X, params)[0] is table  # made once a table
    np.testing.assert_array_equal(y, family.make_labels(X.copy()))
    assert set(np.unique(y)) == {0.0, 1.0} and 0.3 < y.mean() < 0.7
    flat = table.column("features").chunk(0).flatten().to_numpy(zero_copy_only=True)
    assert np.shares_memory(flat, X)
    other = X + np.float32(1.0)
    assert not np.array_equal(family.labelled(other, params)[1], y)


def test_the_entries_are_appended_and_name_files_that_are_there():
    bench = real()
    names = [m["name"] for m in bench["per_layer"]]
    assert CONFIG in [c["name"] for c in bench["configs"]]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "fit", "chips": 1}
    for name, kind in NEW.items():
        m = bench["per_layer"][names.index(name)]
        assert m["workloads"] == [CELL] and m["moves"] == "fit_rows_per_s_chip"
        spec = json.load(open(os.path.join(ROOT, "cellbench", "metrics", name + ".json")))
        assert spec["kind"] == kind
        assert os.path.exists(os.path.join(ROOT, "cellbench", "readers", kind + ".py"))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert listed >= set(LISTED) | set(NEW) and "fit_d2h_bytes_per_op" not in listed
    spec = harness.load_cell(CELL, os.path.join(ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_rows_per_s_chip", "setup_s"]


def test_the_configuration_states_upstreams_settings_and_cuts_rows_only():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    wide = json.load(open(os.path.join(ROOT, "cellbench", "configs", "pca_k3_d3000.json")))
    (entry,) = [c for c in real()["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == cfg["reduced"] == ["rows"] and entry["source"] == cfg["source"]
    assert len(entry["source"]) <= 200 and "run_benchmark.sh" in entry["source"]
    assert cfg["architecture"] is None and cfg["estimator"] == "logreg"
    assert cfg["params"] == {"regParam": 1e-5, "elasticNetParam": 0.0, "standardization": False,
                             "maxIter": 200, "tol": 1e-30, "featuresCol": "features",
                             "labelCol": "label"}
    assert {k: cfg["published"][k] for k in ("rows", "cols", "dtype", "classes")} == {
        "rows": 1000000, "cols": 3000, "dtype": "float32", "classes": 2}
    # the other two d=3000 cells' table size
    assert (cfg["rows"], cfg["cols"], cfg["dtype"]) == (wide["rows"], 3000, "float32")
    assert cfg["program_settings"] == {} and cfg["seed_param"] is None
    # the program has no lower-precision path here: `parity_precision=high`
    # compiles to the same float32 multiply-and-reduce, so the control is the
    # bfloat16 reference in the program's place
    assert cfg["control"]["fit"] == {"reference": "bf16"}
    assert set(cfg["limits"]["fit"]) == {"objective_rel_err", "fit_gradient_err",
                                         "first_step_err", "grad_norm_rel"}
    for key in ("source", "rows", "table", "labels", "estimator", "limits"):
        assert cfg["assumed"][key]
    for key in ("objective", "precision", "solver", "labels"):
        assert cfg["guarantees"][key]


def test_the_work_function_is_one_read_of_the_table_an_iteration():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    got = family.kernel_work(cfg)
    assert got == family.fit_work(cfg) == {"flops": 200 * 4.0 * 357376 * 3000,
                                           "bytes": 200 * 357376 * 3000 * 4.0}
    floor = work.floor_seconds(got, work.load_peaks("TPU v5 lite"))
    assert floor["bound"] == "memory" and floor["seconds"] == pytest.approx(1.047, abs=5e-4)
