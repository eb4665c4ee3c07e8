"""The trace reducer: arithmetic on hand-made events, then the same functions
on a small trace recorded on the chip and kept in `data/` as plain tuples."""

import json
import os

import pytest

from cellbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6  # ns


def ev(plane, line, name, start_ms, dur_ms, module=""):
    return (plane, line, name, start_ms * MS, dur_ms * MS, module)


# two fits: each a host span with prepare+fit inside; on the device a `while`
# whose body operations overlap it, then a predict program after a gap
HAND = [
    ev(HOST, "main", "cellbench.fit", 0, 100),
    ev(HOST, "main", "KMeans.prepare", 1, 9),
    ev(HOST, "main", "KMeans.fit", 10, 89),
    ev(DEV, "XLA Ops", "while.3", 40, 30, "jit_lloyd_fit"),
    ev(DEV, "XLA Ops", "fusion.28", 41, 10, "jit_lloyd_fit"),
    ev(DEV, "XLA Ops", "fusion.7", 52, 17, "jit_lloyd_fit"),
    ev(DEV, "XLA Ops", "fusion.3", 80, 5, "jit__kmeans_predict_xla"),
    ev(DEV, "XLA Modules", "jit_lloyd_fit(123)", 40, 30),
    ev(HOST, "main", "cellbench.fit", 100, 100),
    ev(HOST, "main", "KMeans.prepare", 101, 9),
    ev(HOST, "main", "KMeans.fit", 110, 89),
    ev(DEV, "XLA Ops", "while.3", 140, 30, "jit_lloyd_fit"),
    ev(DEV, "XLA Ops", "fusion.3", 180, 5, "jit__kmeans_predict_xla"),
    ev(DEV, "XLA Steps", "ignored", 0, 200),
]


def test_union_counts_overlap_once():
    assert trace.union_intervals([(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)]) == [
        (0, 12), (20, 31), (40, 41)]


def test_window_busy_program_and_span_seconds():
    lo, hi = trace.window_of(HAND, "cellbench.fit")
    assert (lo, hi) == (0.0, 200 * MS)
    # while (30) + predict (5), twice; the body's operations add nothing
    assert trace.busy_seconds(HAND, lo, hi) == pytest.approx(0.070)
    assert trace.program_seconds(HAND, "lloyd_fit", lo, hi) == pytest.approx(0.060)
    assert trace.program_seconds(HAND, "kmeans_predict", lo, hi) == pytest.approx(0.010)
    assert trace.op_seconds(HAND, "fusion.28", lo, hi) == pytest.approx(0.010)
    assert trace.program_seconds(HAND, "no_such_program", lo, hi) == 0.0
    spans = trace.host_spans(HAND, "KMeans.prepare", lo, hi)
    assert len(spans) == 2 and sum(e - s for s, e in spans) == pytest.approx(18 * MS)
    # clipped to a narrower window, the first while counts only its part inside
    assert trace.busy_seconds(HAND, 50 * MS, 100 * MS) == pytest.approx(0.025)


def test_module_line_is_used_where_operations_carry_no_module():
    stripped = [e[:5] + ("",) for e in HAND]
    lo, hi = trace.window_of(stripped, "cellbench.fit")
    assert trace.program_seconds(stripped, "lloyd_fit", lo, hi) == pytest.approx(0.030)


def test_idle_gaps_are_charged_to_the_innermost_open_span():
    lo, hi = trace.window_of(HAND, "cellbench.fit")
    gaps = dict(trace.idle_gaps_by_span(HAND, lo, hi))
    # idle: 0-40, 70-80, 85-140, 170-180, 185-200 = 130 ms of 200
    assert sum(gaps.values()) == pytest.approx(0.130)
    # 0-40 has its middle in KMeans.fit (10-99); 85-140's middle (112.5) in the second fit
    assert gaps["KMeans.fit"] == pytest.approx(0.130)
    tops = trace.top_device_ops(HAND, lo, hi, n=2)
    assert tops[0][0] == "jit_lloyd_fit/while.3" and tops[0][1] == pytest.approx(0.060)


def test_no_device_plane_reads_nothing():
    host_only = [e for e in HAND if e[0] == HOST]
    assert trace.busy_seconds(host_only, 0, 200 * MS) == 0.0
    assert trace.idle_gaps_by_span(host_only, 0, 200 * MS) == []
    with pytest.raises(ValueError):
        trace.window_of(host_only, "cellbench.transform")


RECORDED = os.path.join(HERE, "data", "trace_kmeans_fit_v5e.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    rec = json.load(open(RECORDED))
    events = [tuple(e) for e in rec["events"]]
    lo, hi = trace.window_of(events, "cellbench.fit")
    want = rec["expected"]
    assert trace.device_planes(events) == ["/device:TPU:0"]
    assert len(trace.host_spans(events, "cellbench.fit", lo, hi)) == want["fits"]
    assert trace.busy_seconds(events, lo, hi) == pytest.approx(want["busy_s"], rel=1e-9)
    assert trace.program_seconds(events, "lloyd_fit", lo, hi) == pytest.approx(
        want["lloyd_s"], rel=1e-9)
    assert 0 < want["lloyd_s"] < want["busy_s"] < (hi - lo) / 1e9
    gaps = trace.idle_gaps_by_span(events, lo, hi)
    assert sum(v for _, v in gaps) == pytest.approx((hi - lo) / 1e9 - want["busy_s"], rel=1e-6)
    assert gaps[0][0] == want["largest_gap_span"]
