"""`rf_cls_depth13_d3000.fit` (PR 38): the cell as `BENCHMARK.json` declares it,
rehearsed on the CPU with a tiny copy of its configuration (counts and the
comparison only), and the files behind its entries. The tiny benchmark file is
not edited: the cell, its configuration and its metrics are laid over a copy of
it here."""

import json
import os

import numpy as np
import pytest

from cellbench import harness, work
from cellbench.estimators import forest as family

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CONFIG = "rf_cls_depth13_d3000"
CELL = CONFIG + ".fit"
NEW = {"fit_forest_bin_s": "report_counter_per_op", "fit_forest_grow_s": "report_counter_per_op",
       "fit_forest_fetch_s": "report_counter_per_op",
       "fit_forest_grow_device_s": "program_seconds_per_op",
       "fit_forest_labels_s": "report_counter_per_op",
       "fit_forest_levels_per_op": "report_counter_per_op",
       "fit_forest_hist_grouped_per_op": "report_counter_per_op",
       "fit_forest_bin_device_per_op": "report_counter_per_op",
       "forest_roofline": "roofline", "fit_forest_edges_s": "report_counter_per_op"}
LISTED = ["fit_host_prepare_s", "fit_upload_floor_s", "ingest_bytes_copied_per_fit",
          "fit_device_busy_s", "fit_mfu", "compiles_in_window.fit", "fit_upload_wait_s",
          "fit_h2d_bytes_per_op", "fit_ingest_s", "fit_finish_s", "fit_stage_s",
          "fit_ingest_zero_copy_bytes_per_op", "fit_device_wait_cpu_s", "fit_host_cpu_s"]


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
    res = harness.run_cell(CELL, 2**31 + 38, 0.2, True, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    cfg = harness.load_cell(CELL, tiny_with_cell)["cfg"]
    rows, trees = cfg["rows"], cfg["params"]["numTrees"]
    table = rows * cfg["cols"] * 4
    got = {name: m["value"] for name, m in res["metrics"].items()
           if not name.endswith("_s")}  # a CPU's seconds are nobody's
    assert got == {
        "ingest_bytes_copied_per_fit": 0.0,
        "fit_ingest_zero_copy_bytes_per_op": float(table),
        "compiles_in_window.fit": 0.0,
        # the table, its weights, its labels, the (rows, 2) statistics and a
        # tree's row weights a tree: no second table
        "fit_h2d_bytes_per_op": float(table + rows * 4 * (2 + 2 + trees)),
        "fit_upload_chunks_per_op": 0.0,
        "fit_forest_levels_per_op": float(trees * cfg["params"]["maxDepth"]),
        "fit_forest_hist_grouped_per_op": 0.0,  # a CPU: segment_sum
        "fit_forest_bin_device_per_op": 1.0,
    }
    assert set(res["checks"]) == {"node_mismatch_share", "node_gain_err",
                                  "split_gain_shortfall"}
    assert res["checks"]["node_mismatch_share"]["value"] == 0.0


def test_the_bf16_reference_in_the_programs_place_is_not_correct(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 39, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True, control=True)
    assert res["correct"] is False and res["metrics"] == {}
    gain = res["checks"]["node_gain_err"]
    assert gain["value"] > 10 * gain["limit"]


@pytest.mark.parametrize("fault", ["no_gain", "bf16_gains", "a_miscounted_node",
                                   "a_tree_short_of_its_depth"])
def test_a_fault_in_the_window_alone_is_not_correct(fault):
    """The exact step after the window is an executable of its own, so a fault
    of the timed program has to show in what the timed fits produced."""
    cfg = json.load(open(os.path.join(HERE, "data", "configs", CONFIG + ".json")))
    params = {**cfg["params"], "seed": 38}
    limits = cfg["limits"]["fit"]
    rng = np.random.default_rng(38)
    X = rng.normal(size=(4096, 24)).astype(np.float32)

    def refit(overrides):
        return family.build({**params, **overrides}, 1).fit(X)

    answer = family.fit_outputs(refit({}))
    assert family.did_all_work(answer, params)
    if fault == "no_gain":
        answer["gain"] = None
    elif fault == "bf16_gains":
        answer["gain"] = family._bf16(answer["gain"]).astype(np.float32)
    elif fault == "a_miscounted_node":
        answer["node_weight"] = answer["node_weight"].copy()
        answer["node_weight"][:, 3] += 1.0  # one row too many in one node of each tree
    else:
        answer["feature"] = answer["feature"].copy()
        depth = params["maxDepth"]
        answer["feature"][0, 2 ** (depth - 1):2 ** depth] = -1
        assert not family.did_all_work(answer, params)
        return
    (got,) = family.check_fit(X, [answer], refit, params)
    over = {name for name, limit in limits.items() if got[name] > limit}
    assert over == ({"node_mismatch_share"} if fault == "a_miscounted_node"
                    else {"node_gain_err"}), got


def test_the_same_table_gives_the_same_labels_and_one_arrow_view():
    rng = np.random.default_rng(38)
    X = rng.normal(size=(4096, 24)).astype(np.float32)
    params = {"featuresCol": "features", "labelCol": "label"}
    table, y = family.labelled(X, params)
    assert family.labelled(X, params)[0] is table  # made once a table
    np.testing.assert_array_equal(y, family.make_labels(X.copy()))
    assert set(np.unique(y)) == {0.0, 1.0} and 0.35 < y.mean() < 0.65
    flat = table.column("features").chunk(0).flatten().to_numpy(zero_copy_only=True)
    assert np.shares_memory(flat, X)
    assert not np.array_equal(family.labelled(X + np.float32(1.0), params)[1], y)


def test_the_entries_are_appended_and_name_files_that_are_there():
    bench = real()
    names = [m["name"] for m in bench["per_layer"]]
    # nothing here pins an entry's place in its list: later PRs append
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
    assert listed >= set(LISTED) | set(NEW) and "fit_labels_s" not in listed
    spec = harness.load_cell(CELL, os.path.join(ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_rows_per_s_chip", "setup_s"]


def test_the_configuration_states_upstreams_settings_and_cuts_scale_only():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    wide = json.load(open(os.path.join(ROOT, "cellbench", "configs", "pca_k3_d3000.json")))
    (entry,) = [c for c in real()["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "run_benchmark.sh" in entry["source"] and "--maxDepth 13" in entry["source"]
    assert entry["reduced"] == cfg["reduced"] and entry["reduced"][0] == "rows"
    assert all(key.split(":")[0] in ("rows", "numTrees") for key in entry["reduced"])
    assert cfg["architecture"] is None and cfg["estimator"] == "forest"
    published, params = cfg["published"], cfg["params"]
    assert {k: published[k] for k in ("rows", "cols", "dtype", "classes", "numTrees",
                                      "maxDepth", "maxBins")} == {
        "rows": 1000000, "cols": 3000, "dtype": "float32", "classes": 2, "numTrees": 50,
        "maxDepth": 13, "maxBins": 128}
    # no width is cut: depth, bins, columns and the feature draw are upstream's
    assert (params["maxDepth"], params["maxBins"]) == (13, 128)
    assert 4 <= params["numTrees"] <= 17 and "featureSubsetStrategy" not in params
    assert (cfg["rows"], cfg["cols"], cfg["dtype"]) == (wide["rows"], 3000, "float32")
    assert cfg["program_settings"] == {} and cfg["seed_param"] == "seed"
    assert cfg["control"]["fit"] == {"reference": "bf16"}
    assert set(cfg["limits"]["fit"]) == {"node_mismatch_share", "node_gain_err",
                                         "split_gain_shortfall"}
    for key in ("source", "rows", "numTrees", "table", "labels", "estimator", "limits"):
        assert cfg["assumed"][key]
    for key in ("counts", "levels", "feature_draw", "gains", "weights", "bins"):
        assert cfg["guarantees"][key]


def test_the_work_function_is_one_read_of_the_bin_matrix_a_tree_level():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    got = family.kernel_work(cfg)
    levels = cfg["params"]["numTrees"] * 13
    assert got == family.fit_work(cfg)
    assert got["bytes"] == levels * (357376 * 3000 + 357376 * 12.0)
    assert got["flops"] > levels * 357376 * 3000 * 2
    floor = work.floor_seconds(got, work.load_peaks("TPU v5 lite"))
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(levels * 1.0764e9 / 819e9, rel=1e-3)
