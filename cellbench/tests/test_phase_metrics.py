"""The per-phase metrics of PR 26: the `counter_delta_per_op` reader, the
bytes each operation puts on the device as the rehearsed cells report them,
and the files behind every new entry of `BENCHMARK.json`."""

import json
import os

import pytest

from cellbench import harness
from cellbench.readers import counter_delta_per_op

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
NEW = ["fit_upload_wait_s", "fit_h2d_bytes_per_op", "fit_ingest_s", "fit_finish_s",
       "fit_init_s", "fit_lloyd_s", "fit_cov_s", "fit_summary_s",
       "transform_upload_wait_s", "transform_h2d_bytes_per_op", "transform_fetch_s",
       "transform_output_s"]


def ctx(before, after, ops):
    return harness.Ctx(cfg={}, traffic={}, est=None, chips=1, on_chip=False, peaks=None,
                       ops=ops, counters_before=before, counters_after=after)


SPEC = {"counter": "h2d.bytes", "labels": {"site": "transform"}}


def test_counter_delta_per_op_filters_by_labels_and_divides_by_operations():
    before = {"h2d.bytes{site=transform}": 100.0, "h2d.bytes{site=fit}": 7.0,
              "span.seconds{span=h2d.wait}": 1.0}
    after = {"h2d.bytes{site=transform}": 500.0, "h2d.bytes{site=fit}": 9000.0,
             "span.seconds{span=h2d.wait}": 3.0, "span.seconds{span=h2d.put}": 9.0}
    assert counter_delta_per_op.read(ctx(before, after, 4), SPEC) == 100.0
    assert counter_delta_per_op.read(ctx(before, after, 4), {"counter": "h2d.bytes"}) == 2348.25
    wait = {"counter": "span.seconds", "labels": {"span": "h2d.wait"}}
    assert counter_delta_per_op.read(ctx(before, after, 4), wait) == 0.5
    # a label set the window first touched counts from nothing
    put = {"counter": "span.seconds", "labels": {"span": "h2d.put"}}
    assert counter_delta_per_op.read(ctx(before, after, 3), put) == 3.0


@pytest.mark.parametrize("before,after,ops", [
    (None, {"h2d.bytes{site=transform}": 1.0}, 2),
    ({"h2d.bytes{site=transform}": 1.0}, None, 2),
    ({}, {"h2d.bytes{site=transform}": 1.0}, 0),
    ({"device.compile": 1.0}, {"device.compile": 1.0}, 2),  # a program without the counter
], ids=["no_before", "no_after", "no_operations", "counter_unknown"])
def test_counter_delta_per_op_reads_nothing_where_there_is_nothing(before, after, ops):
    assert counter_delta_per_op.read(ctx(before, after, ops), SPEC) is None


def test_a_known_counter_the_window_did_not_touch_reads_zero():
    same = {"h2d.bytes{site=fit}": 5.0}
    assert counter_delta_per_op.read(ctx(same, dict(same), 2), SPEC) == 0.0


@pytest.fixture(scope="module")
def tiny_with_phases(tmp_path_factory):
    """The tests' tiny benchmark with this PR's per-layer entries appended as
    the real `BENCHMARK.json` has them."""
    bench = json.load(open(TINY))
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    have = {m["name"] for m in bench["per_layer"]}
    added = [m for m in real["per_layer"] if m["name"] not in have]
    assert [m["name"] for m in added] == NEW
    bench["per_layer"] += added
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


@pytest.mark.parametrize("workload", ["kmeans_k20_d128.fit", "pca_k3_d256.fit",
                                      "kmeans_k20_d128.transform"])
def test_traced_rehearsal_reports_the_bytes_an_operation_puts(tiny_with_phases, workload):
    res = harness.run_cell(workload, 2**31 + 26, 0.2, True, bench_json=tiny_with_phases,
                           rehearsal=True)
    assert res["correct"] is True
    cfg = harness.load_cell(workload, tiny_with_phases)["cfg"]
    table = cfg["rows"] * cfg["cols"] * 4
    assert cfg["rows"] % 8 == 0  # no padding rows at one chip
    if workload.endswith(".fit"):
        # the table and the row weights put with it
        assert res["metrics"]["fit_h2d_bytes_per_op"]["value"] == table + cfg["rows"] * 4
        assert "transform_h2d_bytes_per_op" not in res["metrics"]
    else:
        assert res["metrics"]["transform_h2d_bytes_per_op"]["value"] == table
        assert "fit_h2d_bytes_per_op" not in res["metrics"]
    # seconds are not reported off the chip
    assert not [name for name in res["metrics"] if name.endswith("_s")]


def test_every_new_entry_has_its_metric_file_and_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        entry = entries[name]
        spec = json.load(open(os.path.join(ROOT, "cellbench", "metrics", name + ".json")))
        assert spec["name"] == name
        assert os.path.exists(os.path.join(ROOT, "cellbench", "readers", spec["kind"] + ".py"))
        fit = entry["moves"] == "fit_rows_per_s_chip"
        assert spec["kind"] == ("report_counter_per_op" if fit else "counter_delta_per_op")
        assert set(entry["workloads"]) <= cells
        assert all(w.endswith(".fit") == fit for w in entry["workloads"])
        assert entry["layer"] in layers | {"output frame"}
        seconds = spec["counter"] == "span.seconds"
        assert entry["source"] == ("program_span" if seconds else "program_counter")
        assert entry["unit"] == ("s" if seconds else "bytes")
    assert list(entries)[-len(NEW):] == NEW  # appended, nothing moved
