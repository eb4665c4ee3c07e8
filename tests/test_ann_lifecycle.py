"""ANN index lifecycle (docs/design.md §7b): pipelined out-of-core builds
(bit-identical to the serial loop, retry x prefetch under injected faults),
the versioned on-disk index store with lazy mmap/device load, and incremental
add/delete with bucketed list geometry + tombstone compaction.

The load-bearing contracts (ISSUE 15 acceptance):
  * pipelined build == serial build, byte for byte, with and without faults;
  * save -> load -> search == fit -> search, byte for byte;
  * steady-state incremental adds on a served model compile NOTHING new.
"""

import json
import os

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config, profiling
from spark_rapids_ml_tpu.ops import ann_lifecycle as lc
from spark_rapids_ml_tpu.ops.ann_streaming import (
    _strided_sample_indices,
    resolve_build_batch_rows,
    streaming_ivfflat_build,
    streaming_ivfflat_search,
    streaming_ivfpq_build,
)
from spark_rapids_ml_tpu.reliability import reset_faults


@pytest.fixture(autouse=True)
def lifecycle_env():
    config.set("reliability.backoff_base_s", 0.001)
    config.set("reliability.backoff_max_s", 0.002)
    profiling.reset_counters()
    reset_faults()
    yield
    for key in (
        "reliability.fault_spec",
        "reliability.backoff_base_s",
        "reliability.backoff_max_s",
        "ann.prefetch_depth",
        "ann.build_batch_rows",
        "ann.list_bucket_rows",
        "ann.compact_tombstone_pct",
        "observability.straggler_min_wall_s",
        "serving.max_batch_rows",
        "serving.bucket_min_rows",
    ):
        config.unset(key)
    reset_faults()


def _inject(spec: str) -> None:
    config.set("reliability.fault_spec", spec)
    reset_faults()


def _data(n=1200, d=10, seed=7):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


# ------------------------------------------------------------ subsample clamp


def test_strided_sample_exactly_clamped():
    """Regression (ISSUE 15 satellite): `step = max(1, n // min(n, s))` kept
    every stride hit and returned up to ~2x sample_rows rows."""
    for n, s in ((10, 6), (1000, 300), (7, 7), (5, 10), (1 << 18, 1 << 16),
                 (1_000_000, 262_144)):
        m = min(n, s)
        idx = _strided_sample_indices(n, s)
        assert len(idx) == m, (n, s, len(idx))
        assert idx[0] == 0 and (np.diff(idx) > 0).all()
        assert idx[-1] < n
        # spans the dataset: the last sample sits within one stride of the
        # end (a truncated-prefix sample would drop the tail distribution)
        assert idx[-1] >= n - (n // m) - 1, (n, s, idx[-1])
    # the old form's worst case: n just under a multiple of the step
    old = np.arange(0, 10, max(1, 10 // min(10, 6)))
    assert len(old) > 6  # documents the bug the clamp fixes
    assert len(_strided_sample_indices(10, 6)) == 6


def test_build_batch_rows_resolution():
    from spark_rapids_ml_tpu.defaults import ANN_BUILD_BATCH_ROWS

    assert resolve_build_batch_rows() == ANN_BUILD_BATCH_ROWS
    # an EXPLICITLY-configured streamed-fit geometry wins over the build
    # default (a deployment that sized batches keeps them)...
    config.set("stream_batch_rows", 512)
    try:
        assert resolve_build_batch_rows() == 512
    finally:
        config.unset("stream_batch_rows")
    # ...and the dedicated knob's config pin beats everything
    config.set("ann.build_batch_rows", 123)
    assert resolve_build_batch_rows() == 123


# ------------------------------------------------- pipelined build parity


def test_pipelined_ivfflat_build_bit_identical_to_serial():
    X = _data()
    kw = dict(nlist=16, max_iter=6, seed=3, batch_rows=256)
    config.set("ann.prefetch_depth", 0)  # serial baseline
    serial = streaming_ivfflat_build(X, **kw)
    config.set("ann.prefetch_depth", 2)
    piped = streaming_ivfflat_build(X, **kw)
    for key in ("centers", "center_norms", "cells", "cell_ids", "cell_sizes"):
        np.testing.assert_array_equal(serial[key], piped[key], err_msg=key)


def test_pipelined_ivfpq_build_bit_identical_to_serial():
    X = _data(n=900, d=16, seed=11)
    kw = dict(nlist=8, m_subvectors=4, n_bits=5, max_iter=5, seed=5,
              batch_rows=200)
    config.set("ann.prefetch_depth", 0)
    serial = streaming_ivfpq_build(X, **kw)
    config.set("ann.prefetch_depth", 2)
    piped = streaming_ivfpq_build(X, **kw)
    for key in ("centers", "codebooks", "codes", "cell_ids", "cells"):
        np.testing.assert_array_equal(serial[key], piped[key], err_msg=key)


def test_pipelined_search_bit_identical_to_serial():
    X = _data(n=1500, d=12, seed=29)
    index = streaming_ivfflat_build(X, nlist=16, max_iter=8, seed=3,
                                    batch_rows=400)
    config.set("ann.prefetch_depth", 0)
    d0, i0 = streaming_ivfflat_search(X[:96], index, k=8, nprobe=8, block=32)
    config.set("ann.prefetch_depth", 2)
    d1, i1 = streaming_ivfflat_search(X[:96], index, k=8, nprobe=8, block=32)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


# ------------------------------------------------------- retry x prefetch


@pytest.mark.parametrize("spec,site", [
    ("ann_assign:batch=1:raise=OSError", "ann_assign"),
    ("ann_encode:batch=2:raise=OSError", "ann_encode"),
])
def test_retry_mid_pipeline_bit_identical(spec, site):
    """A transient raise= fault at a mid-pipeline batch retries just that
    batch; the built index is bit-identical to the fault-free build."""
    X = _data(n=1000, d=16, seed=31)
    kw = dict(nlist=8, m_subvectors=4, n_bits=5, max_iter=6, seed=5,
              batch_rows=200)
    config.set("ann.prefetch_depth", 2)
    clean = streaming_ivfpq_build(X, **kw)
    _inject(spec)
    faulted = streaming_ivfpq_build(X, **kw)
    totals = profiling.counter_totals()
    assert totals.get(f"reliability.retry.{site}", 0) == 1, totals
    for key in ("centers", "codebooks", "codes", "cell_ids", "cells"):
        np.testing.assert_array_equal(clean[key], faulted[key], err_msg=key)


def test_sleep_fault_straggler_batch_in_timeline():
    """A sleep= fault delaying one assignment batch mid-pipeline must surface
    that batch as a straggler rank (rank = batch ordinal, phase = site) in
    the run's §6h rank/phase timeline — and the build still completes with
    the batch's writes intact."""
    from spark_rapids_ml_tpu.observability import fit_run

    X = _data(n=1024, d=8, seed=17)
    config.set("observability.straggler_min_wall_s", 0.01)
    config.set("ann.prefetch_depth", 1)
    _inject("ann_assign:batch=2:sleep=0.4")
    with fit_run(algo="AnnBuild", site="test") as run:
        index = streaming_ivfflat_build(X, nlist=8, max_iter=4, seed=3,
                                        batch_rows=256)
        view = run.rank_view()
    assert index["cells"].shape[0] == 8
    assert 2 in view["stragglers"], view
    ranks = {r["rank"]: r for r in view["ranks"]}
    assert len(ranks) == 4  # 1024 rows / 256-row batches
    assert "ann_assign" in ranks[2]["phases"], ranks[2]
    slow = ranks[2]["phases"]["ann_assign"]["wall_s"]
    others = [ranks[r]["phases"]["ann_assign"]["wall_s"]
              for r in ranks if r != 2]
    assert slow > max(others), (slow, others)
    # overlap telemetry landed: per-batch stage/drain histograms + counters
    counters = run.report()["metrics"]["counters"]
    assert counters.get("ann.pipeline_batches{site=ann_assign}", 0) == 4


# ------------------------------------------------------------- on-disk store


def test_store_roundtrip_and_generations(tmp_path):
    path = str(tmp_path / "idx")
    arrays = {
        "centers": np.arange(12, dtype=np.float32).reshape(4, 3),
        "cell_ids": np.arange(8, dtype=np.int64).reshape(4, 2),
    }
    lc.save_index(path, arrays, algo="ivfflat", meta={"tombstones": 3})
    loaded, manifest = lc.load_index(path)
    assert manifest["version"] == lc.ANN_FORMAT_VERSION
    assert manifest["algo"] == "ivfflat"
    assert manifest["generation"] == 1
    assert manifest["meta"]["tombstones"] == 3
    for k, v in arrays.items():
        np.testing.assert_array_equal(loaded[k], v)
        assert isinstance(np.asarray(loaded[k]).base, np.memmap)  # lazy
    # COW mmap: in-memory mutation never writes back to the files
    np.asarray(loaded["cell_ids"])[0, 0] = -1
    again, _ = lc.load_index(path)
    assert np.asarray(again["cell_ids"])[0, 0] == 0
    # re-save over a live directory = generation bump
    lc.save_index(path, arrays, algo="ivfflat")
    assert lc.read_manifest(path)["generation"] == 2


def test_store_rejects_corrupt_and_stale(tmp_path):
    path = str(tmp_path / "idx")
    lc.save_index(path, {"a": np.zeros((2, 2), np.float32)}, algo="ivfflat")
    mpath = os.path.join(path, lc.MANIFEST_NAME)
    doc = json.load(open(mpath))
    doc["version"] = 999
    with open(mpath, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="format version"):
        lc.load_index(path)
    with open(mpath, "w") as f:
        f.write("{not json")
    with pytest.raises(ValueError, match="corrupt"):
        lc.load_index(path)


def test_bucket_capacity():
    config.set("ann.list_bucket_rows", 8)
    assert lc.bucket_capacity(1) == 8
    assert lc.bucket_capacity(8) == 8
    assert lc.bucket_capacity(9) == 16
    assert lc.bucket_capacity(100) == 128
    config.set("ann.list_bucket_rows", 32)
    assert lc.bucket_capacity(9) == 32


# ------------------------------------------------------ model save / load


def _fit_ann(X, algo="ivfflat", **params):
    from spark_rapids_ml_tpu.knn import ApproximateNearestNeighbors

    est = ApproximateNearestNeighbors(
        k=8, algorithm=algo, inputCol="features", idCol="id",
        algoParams=dict({"nlist": 16, "nprobe": 8}, **params),
    )
    df = pd.DataFrame({"features": list(X), "id": np.arange(len(X))})
    return est.fit(df)


@pytest.mark.parametrize("algo,params", [
    ("ivfflat", {}),
    ("ivfpq", {"M": 4, "n_bits": 5}),
])
def test_model_save_load_search_bit_identical(tmp_path, algo, params):
    from spark_rapids_ml_tpu.models.knn import ApproximateNearestNeighborsModel

    X = _data(n=600, d=12, seed=3)
    model = _fit_ann(X, algo=algo, **params)
    qdf = pd.DataFrame({"features": list(X[:24]), "id": np.arange(24)})
    _, _, ref = model.kneighbors(qdf)
    path = str(tmp_path / "model")
    model.write().save(path)
    loaded = ApproximateNearestNeighborsModel.load(path)
    _, _, got = loaded.kneighbors(qdf)
    np.testing.assert_array_equal(
        np.stack(ref["indices"]), np.stack(got["indices"])
    )
    np.testing.assert_array_equal(
        np.stack(ref["distances"]), np.stack(got["distances"])
    )
    # params round-tripped too (k, algorithm, algoParams drive the search)
    assert loaded.getK() == model.getK()
    assert loaded.getOrDefault("algorithm") == algo


def test_knn_model_save_load(tmp_path):
    from spark_rapids_ml_tpu.knn import NearestNeighbors
    from spark_rapids_ml_tpu.models.knn import NearestNeighborsModel

    X = _data(n=300, d=6, seed=9)
    model = NearestNeighbors(k=4, inputCol="features").fit(
        pd.DataFrame({"features": list(X)})
    )
    path = str(tmp_path / "nn")
    model.write().save(path)
    loaded = NearestNeighborsModel.load(path)
    ref = model._serving_predict(X[:8])
    got = loaded._serving_predict(X[:8])
    np.testing.assert_array_equal(ref["indices"], got["indices"])
    np.testing.assert_array_equal(ref["distances"], got["distances"])


def test_brute_force_model_not_persistable():
    X = _data(n=50, d=4)
    model = _fit_ann(X, algo="brute_force")
    with pytest.raises(NotImplementedError, match="brute_force"):
        model.write()


def test_lazy_device_load_counters(tmp_path):
    """A loaded index uploads segments on FIRST search only (ann.device_loads
    counts once per segment, later searches replay from the device cache)."""
    from spark_rapids_ml_tpu.models.knn import ApproximateNearestNeighborsModel

    X = _data(n=400, d=8, seed=21)
    model = _fit_ann(X)
    path = str(tmp_path / "m")
    model.write().save(path)
    loaded = ApproximateNearestNeighborsModel.load(path)
    profiling.reset_counters()
    qdf = pd.DataFrame({"features": list(X[:8]), "id": np.arange(8)})
    loaded.kneighbors(qdf)
    first = {
        k: v for k, v in profiling.counter_totals().items()
        if k.startswith("ann.device_loads")
    }
    assert any("attr=cells" in k for k in first), first
    loaded.kneighbors(qdf)
    again = {
        k: v for k, v in profiling.counter_totals().items()
        if k.startswith("ann.device_loads")
    }
    assert again == first  # second search uploaded nothing


# ------------------------------------------------- incremental add / delete


def test_incremental_add_delete_compact():
    X = _data(n=500, d=8, seed=5)
    model = _fit_ann(X)
    model.enable_incremental()
    cells_shape = np.asarray(model._model_attributes["cells"]).shape
    rng = np.random.default_rng(1)
    new = rng.normal(size=(6, 8)).astype(np.float32)
    ids = model.add_items(new)
    # in-slack adds keep the bucketed geometry (the zero-compile contract)
    assert np.asarray(model._model_attributes["cells"]).shape == cells_shape
    qdf = pd.DataFrame({"features": list(new), "id": np.arange(6)})
    _, _, got = model.kneighbors(qdf)
    np.testing.assert_array_equal(np.stack(got["indices"])[:, 0], ids)
    assert np.allclose(np.stack(got["distances"])[:, 0], 0.0)

    assert model.delete_items(ids) == 6
    _, _, after = model.kneighbors(qdf)
    assert not np.isin(np.stack(after["indices"]), ids).any()
    assert model.tombstone_fraction() > 0

    # compaction trigger: force the pct low, one more delete compacts
    config.set("ann.compact_tombstone_pct", 0)
    model.delete_items(model._item_row_ids[:1])
    assert model.tombstone_fraction() == 0.0
    totals = profiling.counter_totals()
    assert totals.get("ann.compactions", 0) >= 1, totals
    assert totals.get("ann.items_added", 0) == 6
    assert totals.get("ann.items_deleted", 0) == 7
    # deleted items stay gone after compaction; survivors still found
    _, _, post = model.kneighbors(qdf)
    assert not np.isin(np.stack(post["indices"]), ids).any()
    _, _, live = model.kneighbors(
        pd.DataFrame({"features": list(X[5:9]), "id": np.arange(4)})
    )
    np.testing.assert_array_equal(
        np.stack(live["indices"])[:, 0], np.arange(5, 9)
    )


def test_incremental_ivfpq_adds_encode():
    X = _data(n=400, d=16, seed=13)
    model = _fit_ann(X, algo="ivfpq", M=4, n_bits=5)
    model.enable_incremental()
    new = _data(n=3, d=16, seed=99) + 4.0
    ids = model.add_items(new)
    # ADC search (wide nprobe) must surface the added items at rank 1 —
    # their codes were host-encoded into the lists
    _, _, got = model.kneighbors(
        pd.DataFrame({"features": list(new), "id": np.arange(3)})
    )
    np.testing.assert_array_equal(np.stack(got["indices"])[:, 0], ids)


def test_incremental_list_growth_when_slack_exhausted():
    X = _data(n=200, d=6, seed=3)
    model = _fit_ann(X, nlist=4)
    model.enable_incremental()
    max_cell0 = np.asarray(model._model_attributes["cells"]).shape[1]
    # overflow one cell deliberately: many copies of one vector all assign
    # to the same list
    flood = np.tile(X[:1], (max_cell0 + 4, 1))
    model.add_items(flood)
    grown = np.asarray(model._model_attributes["cells"]).shape[1]
    assert grown > max_cell0
    assert grown == lc.bucket_capacity(grown)  # still bucketed
    assert profiling.counter_totals().get("ann.list_grows", 0) >= 1


def test_incremental_rejected_for_cagra():
    X = _data(n=300, d=8, seed=3)
    model = _fit_ann(X, algo="cagra")
    with pytest.raises(NotImplementedError, match="CAGRA"):
        model.add_items(X[:2])


def test_kneighbors_with_tombstones_is_read_only_across_tiers():
    """kneighbors on a tombstoned incremental model gathers live rows into
    locals — it must NOT mutate (compact) the model, and the gather must stay
    row-aligned when the live set falls back under the stream threshold
    (the in-core tier's x2/valid operands)."""
    from spark_rapids_ml_tpu.knn import NearestNeighbors

    X = _data(n=120, d=8, seed=51)
    model = NearestNeighbors(k=2, inputCol="features").fit(
        pd.DataFrame({"features": list(X)})
    )
    model.enable_incremental()  # bucketed capacity 128
    deleted = np.asarray(model._model_attributes["item_ids"])[:10].copy()
    model.delete_items(deleted)
    full_bytes = np.asarray(model._model_attributes["item_features"]).nbytes
    shape_before = np.asarray(model._model_attributes["item_features"]).shape
    qdf = pd.DataFrame({"features": list(X[:6])})
    for threshold in (64, full_bytes - 1):
        # 64: gathered live rows STAY over threshold -> blocked scan;
        # full_bytes-1: full array is over but the gathered live set falls
        # UNDER -> the in-core tier runs on the gathered locals (the
        # shape-mismatch regression)
        config.set("stream_threshold_bytes", threshold)
        try:
            _, _, kdf = model.kneighbors(qdf)
        finally:
            config.unset("stream_threshold_bytes")
        assert not np.isin(np.stack(kdf["indices"]), deleted).any()
    # read API: the model's arrays are untouched (a registered serving copy
    # would otherwise see its operand shapes change underneath it)
    assert np.asarray(model._model_attributes["item_features"]).shape \
        == shape_before
    assert model._tombstones == 10


# ------------------------------------------ served model: zero new compiles


def test_served_knn_absorbs_adds_with_zero_new_compiles():
    """THE acceptance contract: a live served kNN model absorbs adds/deletes
    with zero new device.compile{kernel=} entries — the bucketed geometry
    keeps every operand shape, so the AOT cache stays warm."""
    from spark_rapids_ml_tpu import serving
    from spark_rapids_ml_tpu.knn import NearestNeighbors

    config.set("serving.max_batch_rows", 32)
    config.set("serving.bucket_min_rows", 16)
    X = _data(n=100, d=8, seed=41)
    model = NearestNeighbors(k=3, inputCol="features").fit(
        pd.DataFrame({"features": list(X)})
    )
    model.enable_incremental(capacity_rows=256)
    reg = serving.ModelRegistry()
    try:
        reg.register("nn", model)
        ref = reg.predict("nn", X[:8])
        assert ref["indices"].shape == (8, 3)

        def compiles():
            return {
                k: v for k, v in profiling.counter_totals().items()
                if k.startswith("device.compile{")
            }

        c0 = compiles()
        new_vec = X[:2] + 50.0
        ids = model.add_items(new_vec)
        reg.refresh_weights("nn")
        out = reg.predict("nn", new_vec)
        np.testing.assert_array_equal(out["indices"][:, 0], ids)
        model.delete_items(ids[:1])
        reg.refresh_weights("nn")
        out2 = reg.predict("nn", new_vec[:1])
        assert out2["indices"][0, 0] != ids[0]
        delta = {k: v - c0.get(k, 0) for k, v in compiles().items()
                 if v != c0.get(k, 0)}
        assert not delta, f"incremental serving compiled: {delta}"
        totals = profiling.counter_totals()
        assert totals.get("serving.weight_refreshes{model=nn}", 0) == 2
    finally:
        reg.close()


def test_registry_mutate_serializes_with_inflight_batches():
    """registry.mutate(fn) runs the mutation under the entry's execution
    lock: concurrent predict traffic never observes a half-applied mutation
    (or raises on read-only installed device views), and every mutation
    refreshes the HBM weights."""
    import threading

    from spark_rapids_ml_tpu import serving
    from spark_rapids_ml_tpu.knn import NearestNeighbors

    config.set("serving.max_batch_rows", 32)
    config.set("serving.bucket_min_rows", 16)
    X = _data(n=80, d=6, seed=77)
    model = NearestNeighbors(k=2, inputCol="features").fit(
        pd.DataFrame({"features": list(X)})
    )
    model.enable_incremental(capacity_rows=256)
    reg = serving.ModelRegistry()
    errors: list = []
    try:
        reg.register("nn", model)
        stop = threading.Event()

        def client():
            while not stop.is_set():
                try:
                    out = reg.predict("nn", X[:4])
                    assert out["indices"].shape == (4, 2)
                except Exception as e:
                    errors.append(e)
                    return

        threads = [threading.Thread(target=client) for _ in range(3)]
        [t.start() for t in threads]
        added: list = []
        for i in range(8):
            vec = X[:1] + 10.0 * (i + 1)
            reg.mutate("nn", lambda m, v=vec: added.append(m.add_items(v)[0]))
        reg.mutate("nn", lambda m: m.delete_items(np.asarray(added[:4])))
        stop.set()
        [t.join(timeout=10) for t in threads]
        assert not errors, errors[:3]
        # every mutation refreshed the weights; the final state serves
        out = reg.predict("nn", (X[:1] + 80.0))
        assert out["indices"][0, 0] == added[7]
        totals = profiling.counter_totals()
        assert totals.get("serving.weight_refreshes{model=nn}", 0) == 9
    finally:
        reg.close()

