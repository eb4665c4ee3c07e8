"""`rf_reg_depth6_d3000.fit`: the cell as `BENCHMARK.json` declares it,
rehearsed on the CPU with a tiny copy of its configuration (counts and the
comparison only), and the files behind its entries. The tiny benchmark file is
not edited: the cell, its configuration and its metrics are laid over a copy of
it here."""

import json
import os

import numpy as np
import pytest

from cellbench import harness, work
from cellbench.estimators import forest_reg as family

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CONFIG = "rf_reg_depth6_d3000"
CELL = CONFIG + ".fit"
FOREST = ["fit_forest_bin_s", "fit_forest_grow_s", "fit_forest_fetch_s",
          "fit_forest_grow_device_s", "fit_forest_labels_s", "fit_forest_levels_per_op",
          "fit_forest_hist_grouped_per_op", "fit_forest_bin_device_per_op", "forest_roofline",
          "fit_forest_edges_s", "fit_forest_edges_device_per_op",
          "fit_forest_hist_compact_per_op"]
NEW = "fit_forest_hist_f32x3_per_op"


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
    res = harness.run_cell(CELL, 2**31 + 48, 0.2, True, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    cfg = harness.load_cell(CELL, tiny_with_cell)["cfg"]
    trees = cfg["params"]["numTrees"]
    got = {name: m["value"] for name, m in res["metrics"].items() if not name.endswith("_s")}
    assert got["fit_forest_levels_per_op"] == trees * cfg["params"]["maxDepth"]
    assert got["fit_forest_hist_grouped_per_op"] == 0.0  # a CPU: segment_sum
    assert got[NEW] == 0.0  # no kernel: the statistics summed as float32 by XLA
    assert got["fit_forest_bin_device_per_op"] == 1.0
    assert got["compiles_in_window.fit"] == 0.0
    assert set(res["checks"]) == {"node_mismatch_share", "node_gain_err", "leaf_value_err",
                                  "split_gain_shortfall"}
    assert res["checks"]["node_mismatch_share"]["value"] == 0.0
    assert res["checks"]["split_gain_shortfall"]["value"] == 0.0


def test_the_bf16_reference_in_the_programs_place_is_not_correct(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 49, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True, control=True)
    assert res["correct"] is False and res["metrics"] == {}
    for name in ("node_gain_err", "leaf_value_err"):
        assert res["checks"][name]["value"] > res["checks"][name]["limit"], res["checks"]


@pytest.mark.parametrize("fault", ["no_gain", "bf16_gains", "bf16_leaves",
                                   "a_miscounted_node", "a_tree_short_of_its_depth"])
def test_a_fault_in_the_window_alone_is_not_correct(fault):
    """The exact step after the window is an executable of its own, so a fault
    of the timed program has to show in what the timed fits produced."""
    cfg = json.load(open(os.path.join(HERE, "data", "configs", CONFIG + ".json")))
    params = {**cfg["params"], "seed": 48}
    limits = cfg["limits"]["fit"]
    rng = np.random.default_rng(48)
    X = rng.normal(size=(4096, 24)).astype(np.float32)

    def refit(overrides):
        return family.build({**params, **overrides}, 1).fit(X)

    answer = family.fit_outputs(refit({}))
    assert family.did_all_work(answer, params)
    want = {"node_gain_err"}
    if fault == "no_gain":
        answer["gain"] = None
        want = {"node_gain_err", "leaf_value_err"}
    elif fault == "bf16_gains":
        answer["gain"] = family.forest._bf16(answer["gain"]).astype(np.float32)
    elif fault == "bf16_leaves":
        answer["value"] = family.forest._bf16(answer["value"]).astype(np.float32)
        want = {"leaf_value_err"}
    elif fault == "a_miscounted_node":
        answer["node_weight"] = answer["node_weight"].copy()
        answer["node_weight"][:, 3] += 1.0  # one row too many in one node of each tree
        want = {"node_mismatch_share"}
    else:
        answer["feature"] = answer["feature"].copy()
        depth = params["maxDepth"]
        answer["feature"][0, 2 ** (depth - 1):2 ** depth] = -1
        assert not family.did_all_work(answer, params)
        return
    (got,) = family.check_fit(X, [answer], refit, params)
    assert {name for name, limit in limits.items() if got[name] > limit} == want, got


def test_the_entries_are_appended_and_name_files_that_are_there():
    bench = real()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-1] == NEW  # the one metric this configuration adds, at the end
    assert [c["name"] for c in bench["configs"]][-1] == CONFIG
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "fit", "chips": 1}
    assert len(cell["why"]) <= 200
    new = bench["per_layer"][-1]
    assert new["workloads"] == [CELL] and new["moves"] == "fit_rows_per_s_chip"
    spec = json.load(open(os.path.join(ROOT, "cellbench", "metrics", NEW + ".json")))
    assert spec == {"name": NEW, "kind": "report_counter_per_op", "counter": "forest.hist_operand",
                    "labels": {"operand": "float32x3"}, "total": True}
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    cls = {m["name"] for m in bench["per_layer"] if "rf_cls_depth13_d3000.fit"
           in m.get("workloads", [])}
    assert listed == cls | {NEW} and set(FOREST) <= listed
    spec = harness.load_cell(CELL, os.path.join(ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in spec["end_to_end"]] == ["fit_rows_per_s_chip", "setup_s"]


def test_the_configuration_states_upstreams_settings_and_cuts_scale_only():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    linreg = json.load(open(os.path.join(ROOT, "cellbench", "configs", "linreg_l2_d3000.json")))
    (entry,) = [c for c in real()["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "run_benchmark.sh" in entry["source"] and "--maxDepth 6" in entry["source"]
    assert entry["reduced"] == cfg["reduced"] == ["rows", "numTrees"]
    assert cfg["architecture"] is None and cfg["estimator"] == "forest_reg"
    published, params = cfg["published"], cfg["params"]
    assert {k: published[k] for k in ("rows", "cols", "dtype", "numTrees", "maxDepth",
                                      "maxBins", "impurity", "featureSubsetStrategy")} == {
        "rows": 1000000, "cols": 3000, "dtype": "float32", "numTrees": 30, "maxDepth": 6,
        "maxBins": 128, "impurity": "variance", "featureSubsetStrategy": "auto"}
    # no width is cut: depth, bins, columns and the feature draw are upstream's
    assert (params["maxDepth"], params["maxBins"]) == (6, 128)
    assert 4 <= params["numTrees"] <= 10 and "featureSubsetStrategy" not in params
    assert (cfg["rows"], cfg["cols"], cfg["dtype"]) == (linreg["rows"], 3000, "float32")
    assert cfg["table"] == linreg["table"]
    assert cfg["program_settings"] == {} and cfg["seed_param"] == "seed"
    assert cfg["control"]["fit"] == {"reference": "bf16"}
    assert set(cfg["limits"]["fit"]) == {"node_mismatch_share", "node_gain_err",
                                         "leaf_value_err", "split_gain_shortfall"}
    for key in ("source", "rows", "numTrees", "table", "labels", "estimator", "limits"):
        assert cfg["assumed"][key]
    for key in ("statistics", "trees", "levels", "feature_draw", "gains", "weights", "bins"):
        assert cfg["guarantees"][key]


def test_the_work_function_is_one_read_of_the_bin_matrix_ids_and_three_statistics():
    cfg = json.load(open(os.path.join(ROOT, "cellbench", "configs", CONFIG + ".json")))
    got = family.kernel_work(cfg)
    levels = cfg["params"]["numTrees"] * 6
    assert got == family.fit_work(cfg)
    assert got["bytes"] == levels * (357376 * 3000 + 357376 * 16.0)
    floor = work.floor_seconds(got, work.load_peaks("TPU v5 lite"))
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(levels * 1.07784e9 / 819e9, rel=1e-3)
