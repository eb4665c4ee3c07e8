"""The work-and-bytes formulas against values worked by hand, for both
configurations, and the table of peaks."""

import json
import os

import pytest

from cellbench import work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_lloyd_work_kmeans_k20_d128():
    cfg = json.load(open(os.path.join(CONFIGS, "kmeans_k20_d128.json")))
    assert (cfg["rows"], cfg["cols"]) == (8380416, 128)
    w = work.lloyd_work(cfg["rows"], cfg["cols"], 20, 30)
    # 30 iterations x 2 x 8,380,416 x 20 x 128; 30 reads of 4,290,772,992 B
    assert w["flops"] == 1_287_231_897_600
    assert w["bytes"] == 128_723_189_760
    floor = work.floor_seconds(w, V5E)
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(128_723_189_760 / 819e9)
    assert floor["seconds"] == pytest.approx(0.157171, rel=1e-5)
    # four chips hold four times the rows and have four times the peak
    w4 = work.lloyd_work(4 * cfg["rows"], cfg["cols"], 20, 30)
    assert work.floor_seconds(w4, V5E, chips=4)["seconds"] == pytest.approx(floor["seconds"])


def test_gram_work_pca_k3_d256():
    cfg = json.load(open(os.path.join(CONFIGS, "pca_k3_d256.json")))
    assert (cfg["rows"], cfg["cols"]) == (4190208, 256)
    w = work.gram_work(cfg["rows"], cfg["cols"])
    assert w["flops"] == 549_218_942_976  # 2 x 4,190,208 x 256 x 256
    assert w["bytes"] == 4_290_772_992
    floor = work.floor_seconds(w, V5E)
    assert floor["bound"] == "memory"  # 5.24 ms of reading against 2.79 ms of one-pass bf16
    assert floor["seconds"] == pytest.approx(5.2390e-3, rel=1e-4)


def test_compute_bound_is_named():
    assert work.floor_seconds({"flops": 197e12, "bytes": 1.0}, V5E) == {
        "seconds": 1.0, "bound": "compute"}


def test_peaks_known_kind_and_unknown_kind():
    assert work.load_peaks("TPU v5 lite") == V5E
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(KeyError):
            work.load_peaks(kind)
