"""`fit_d2h_bytes_per_op` (PR 27): what a fit reads back from the device for
the k-means|| start's candidate weights and the summary's cluster sizes, as a
rehearsed cell reports it, and the files behind its entry of `BENCHMARK.json`."""

import json
import os

import pytest

from cellbench import harness
from cellbench.readers import report_counter_per_op

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
NAME = "fit_d2h_bytes_per_op"


def real_entry():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    return bench, entry


@pytest.fixture(scope="module")
def tiny_with_d2h(tmp_path_factory):
    bench = json.load(open(TINY))
    bench["per_layer"].append(real_entry()[1])
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def test_traced_rehearsal_reports_the_bytes_a_fit_reads_back(tiny_with_d2h):
    res = harness.run_cell("kmeans_k20_d128.fit", 2**31 + 27, 0.2, True,
                           bench_json=tiny_with_d2h, rehearsal=True)
    assert res["correct"] is True
    params = harness.load_cell("kmeans_k20_d128.fit", tiny_with_d2h)["cfg"]["params"]
    candidates = 1 + params["initSteps"] * 2 * params["k"]
    # int32 counts: one a candidate of the start, one a cluster of the summary
    assert res["metrics"][NAME]["value"] == 4 * candidates + 4 * params["k"]


def test_an_untraced_line_and_a_cell_off_the_list_leave_it_out(tiny_with_d2h):
    res = harness.run_cell("kmeans_k20_d128.fit", 2**31 + 28, 0.2, False,
                           bench_json=tiny_with_d2h, rehearsal=True)
    assert NAME not in res["metrics"]
    res = harness.run_cell("pca_k3_d256.fit", 2**31 + 29, 0.2, True,
                           bench_json=tiny_with_d2h, rehearsal=True)
    assert NAME not in res["metrics"]


def test_the_reader_sums_the_fit_site_only_and_reads_nothing_without_reports():
    spec = json.load(open(os.path.join(ROOT, "cellbench", "metrics", NAME + ".json")))
    reports = [{"d2h.bytes{site=fit}": 404.0, "d2h.bytes{site=transform}": 9e6},
               {"d2h.bytes{site=fit}": 396.0}]
    ctx = harness.Ctx(cfg={}, traffic={}, est=None, chips=1, on_chip=False, peaks=None,
                      ops=2, report_counters=reports)
    assert report_counter_per_op.read(ctx, spec) == 400.0
    none = harness.Ctx(cfg={}, traffic={}, est=None, chips=1, on_chip=False, peaks=None,
                       ops=0, report_counters=[])
    assert report_counter_per_op.read(none, spec) is None


def test_the_entry_has_its_file_and_an_accepted_layer():
    bench, entry = real_entry()
    spec = json.load(open(os.path.join(ROOT, "cellbench", "metrics", NAME + ".json")))
    assert spec == {"name": NAME, "kind": "report_counter_per_op", "counter": "d2h.bytes",
                    "labels": {"site": "fit"}}
    assert entry == {"name": NAME, "unit": "bytes", "better": "lower",
                     "source": "program_counter", "layer": "solver",
                     "moves": "fit_rows_per_s_chip", "workloads": ["kmeans_k20_d128.fit"]}
    assert bench["per_layer"][-1] is entry  # appended, nothing moved
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}
