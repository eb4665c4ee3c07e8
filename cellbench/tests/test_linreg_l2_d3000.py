"""`linreg_l2_d3000.fit`: the cell as `BENCHMARK.json` declares it, rehearsed on
the CPU with a tiny copy of its configuration (counts only), and the files
behind its entries. The tiny benchmark file is not edited: the cell, its
configuration and its metrics are laid over a copy of it here."""

import json
import os

import numpy as np
import pytest

from cellbench import harness
from cellbench import linreg_ref as ref
from cellbench.estimators import linreg as family

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CONFIG = "linreg_l2_d3000"
CELL = CONFIG + ".fit"


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
    res = harness.run_cell(CELL, 2**31 + 42, 0.2, True, bench_json=tiny_with_cell,
                           rehearsal=True)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 1
    cfg = harness.load_cell(CELL, tiny_with_cell)["cfg"]
    table = cfg["rows"] * cfg["cols"] * 4
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert got["fit_linreg_stats_triangle_per_op"] == 1.0
    assert got["fit_n_iter"] == 1.0 and got["compiles_in_window.fit"] == 0.0
    # the Arrow column is a view of the host table; the float64 labels are
    # cast to the table's float32, one copy of them
    assert got["ingest_bytes_copied_per_fit"] == float(cfg["rows"] * 4)
    assert got["fit_ingest_zero_copy_bytes_per_op"] == float(table)
    # the table, its weights and its labels (float32 on the device)
    assert got["fit_h2d_bytes_per_op"] == float(table + 2 * cfg["rows"] * 4)
    assert set(res["checks"]) == {"fit_gradient_err", "coef_err", "objective_rel_err"}


def test_the_bf16_reference_in_the_programs_place_is_not_correct(tiny_with_cell):
    res = harness.run_cell(CELL, 2**31 + 43, 0.2, False, bench_json=tiny_with_cell,
                           rehearsal=True, control=True)
    assert res["correct"] is False and res["metrics"] == {}
    assert res["checks"]["fit_gradient_err"]["value"] > res["checks"]["fit_gradient_err"]["limit"]


def test_the_reference_minimiser_has_a_zero_gradient():
    rng = np.random.default_rng(42)
    X = (rng.normal(size=(5000, 40)) * np.linspace(1.0, 8.0, 40) + 3.0).astype(np.float32)
    y = family.make_labels(X)
    for standardization in (False, True):
        st = ref.stats(X, y)
        coef, intercept, value = ref.minimiser(st, 1e-3, standardization)
        got, grad = ref.value_and_gradient(X, y, coef, intercept, 1e-3,
                                           ref.penalty_scale(st, standardization))
        assert np.abs(grad).max() < 1e-9 * np.abs(ref.gradient_at_zero(st)).max()
        assert got == pytest.approx(value, rel=1e-10)


def test_the_same_table_gives_the_same_labels_and_one_arrow_view():
    rng = np.random.default_rng(42)
    X = rng.normal(size=(4096, 24)).astype(np.float32)
    params = {"featuresCol": "features", "labelCol": "label"}
    table, y = family.labelled(X, params)
    assert family.labelled(X, params)[0] is table  # made once a table
    np.testing.assert_array_equal(y, family.make_labels(X.copy()))
    assert y.dtype == np.float64 and table.column("label").type.bit_width == 64
    flat = table.column("features").chunk(0).flatten().to_numpy(zero_copy_only=True)
    assert np.shares_memory(flat, X)


def test_the_entries_are_appended_and_name_files_that_are_there():
    bench = real()
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
            assert os.path.isfile(os.path.join(ROOT, "cellbench", "metrics", m["name"] + ".json"))
