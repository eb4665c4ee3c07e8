"""KMeans parity tests vs sklearn (the reference compares GPU vs Spark ML CPU,
tests/test_kmeans.py)."""

import numpy as np
import pandas as pd
import pytest
from sklearn.cluster import KMeans as SkKMeans
from sklearn.datasets import make_blobs

from spark_rapids_ml_tpu.clustering import KMeans, KMeansModel


def _blobs(n=500, d=8, k=5, seed=0, std=0.5):
    X, y = make_blobs(
        n_samples=n, n_features=d, centers=k, cluster_std=std, random_state=seed
    )
    return X.astype(np.float32), y


def _match_centers(got: np.ndarray, expected: np.ndarray) -> float:
    """Max distance between matched center pairs (greedy match)."""
    from scipy.optimize import linear_sum_assignment
    from scipy.spatial.distance import cdist

    cost = cdist(got, expected)
    r, c = linear_sum_assignment(cost)
    return float(cost[r, c].max())


@pytest.mark.parametrize("init", ["k-means||", "random"])
def test_kmeans_recovers_blobs(init, n_devices):
    X, _ = _blobs()
    df = pd.DataFrame({"features": list(X)})
    est = KMeans(k=5, initMode=init, maxIter=50, seed=7, tol=1e-6)
    est.num_workers = n_devices
    model = est.fit(df)

    sk = SkKMeans(n_clusters=5, n_init=10, random_state=0).fit(X)
    # well-separated blobs: both should find essentially the true centers
    assert _match_centers(model.cluster_centers_, sk.cluster_centers_) < 0.15
    # inertia within 2% of sklearn's
    assert model.inertia_ <= sk.inertia_ * 1.02


def test_kmeans_transform_and_predict(n_devices):
    X, y = _blobs(n=300, d=4, k=3, seed=2)
    df = pd.DataFrame({"features": list(X)})
    model = KMeans(k=3, seed=5, maxIter=40).fit(df)
    out = model.transform(df)
    assert "prediction" in out.columns
    pred = out["prediction"].to_numpy()
    # cluster labels must be consistent: same-blob points share a label
    from sklearn.metrics import adjusted_rand_score

    assert adjusted_rand_score(y, pred) > 0.95
    # single-vector predict agrees with transform
    assert model.predict(X[0]) == pred[0]


def test_kmeans_weighted_fit(n_devices):
    """Sample weights shift centers (weightCol support). Spark requires k > 1, so
    the weighted-mean check uses a well-separated far cluster to isolate one
    center's weighted mean."""
    X = np.array([[0.0], [1.0], [1000.0]], dtype=np.float32)
    w = np.array([1.0, 100.0, 1.0], dtype=np.float32)
    df = pd.DataFrame({"features": list(X), "w": w})
    model = KMeans(k=2, weightCol="w", maxIter=20, initMode="random", seed=1).fit(df)
    centers = np.sort(np.asarray(model.cluster_centers_)[:, 0])
    # cluster 0 = weighted mean of the two near points; cluster 1 = the far point
    expected = (0.0 * 1 + 1.0 * 100) / 101
    assert abs(centers[0] - expected) < 1e-3
    assert abs(centers[1] - 1000.0) < 1e-2


def test_kmeans_tol_zero_remap():
    est = KMeans(k=2, tol=0.0)
    assert est.tpu_params["tol"] == 1.0e-16


def test_kmeans_persistence(tmp_path, n_devices):
    X, _ = _blobs(n=100, d=3, k=2, seed=4)
    df = pd.DataFrame({"features": list(X)})
    model = KMeans(k=2, seed=3).fit(df)
    path = str(tmp_path / "kmeans_model")
    model.save(path)
    loaded = KMeansModel.load(path)
    np.testing.assert_allclose(loaded.cluster_centers_, model.cluster_centers_)
    pred_a = model.transform(df)["prediction"].to_numpy()
    pred_b = loaded.transform(df)["prediction"].to_numpy()
    np.testing.assert_array_equal(pred_a, pred_b)


def test_kmeans_uneven_rows(n_devices):
    """Padding must not create phantom points at the origin."""
    X, _ = _blobs(n=97, d=5, k=3, seed=6)
    X += 100.0  # far from origin: a phantom zero-row would grab a center
    df = pd.DataFrame({"features": list(X)})
    model = KMeans(k=3, seed=0, maxIter=30).fit(df)
    # all centers near the data, none at the origin
    assert np.all(np.linalg.norm(model.cluster_centers_, axis=1) > 50)


def test_kmeans_cosine_clusters_by_direction(n_devices):
    """Spherical kmeans groups by direction, ignoring magnitude (Spark's
    distanceMeasure='cosine' semantics)."""
    rng = np.random.default_rng(0)
    dirs = np.array([[1.0, 0.0], [0.0, 1.0], [-1.0, 0.0]], dtype=np.float32)
    y = rng.integers(0, 3, size=240)
    scales = rng.uniform(0.1, 50.0, size=240)[:, None].astype(np.float32)  # magnitudes vary wildly
    X = (dirs[y] + rng.normal(scale=0.05, size=(240, 2)).astype(np.float32)) * scales
    df = pd.DataFrame({"features": list(X)})
    model = KMeans(k=3, distanceMeasure="cosine", seed=2, maxIter=30).fit(df)
    pred = model.transform(df)["prediction"].to_numpy()
    from sklearn.metrics import adjusted_rand_score

    assert adjusted_rand_score(y, pred) > 0.95
    # centers live on the unit sphere
    np.testing.assert_allclose(
        np.linalg.norm(model.cluster_centers_, axis=1), 1.0, atol=1e-4
    )
    assert model.predict(X[0]) == pred[0]


def test_kmeans_cosine_zero_vector_raises(n_devices):
    X = np.zeros((10, 3), dtype=np.float32)
    X[1:] = 1.0
    df = pd.DataFrame({"features": list(X)})
    with pytest.raises(ValueError, match="zero-length"):
        KMeans(k=2, distanceMeasure="cosine").fit(df)


def test_fast_math_config_matches_parity_clusters(n_devices):
    """fast_math runs assignment distances at MXU bf16: same clustering on
    separated data, model attributes still parity-precision floats."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.clustering import KMeans

    rng = np.random.default_rng(31)
    X = np.concatenate(
        [rng.normal(-5, 0.5, (60, 6)), rng.normal(5, 0.5, (60, 6))]
    ).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    parity = KMeans(k=2, seed=1, maxIter=25).fit(df)
    config.set("fast_math", True)
    try:
        fast = KMeans(k=2, seed=1, maxIter=25).fit(df)
    finally:
        config.unset("fast_math")

    def canon(c):
        c = np.asarray(c)
        return c[np.argsort(c[:, 0])]

    np.testing.assert_allclose(
        canon(parity.cluster_centers_), canon(fast.cluster_centers_), atol=1e-3
    )


def test_kmeans_training_summary(n_devices):
    """Freshly-fit models expose a KMeansSummary (clusterSizes/trainingCost/
    numIter); loaded models do not — Spark semantics. The reference produces no
    summary at all (clustering.py:549-553)."""
    import os
    import tempfile

    rng = np.random.default_rng(4)
    X = np.vstack(
        [rng.normal(-4, 0.5, (70, 3)), rng.normal(4, 0.5, (30, 3))]
    ).astype(np.float32)
    df = pd.DataFrame({"features": list(X)})
    m = KMeans(k=2, seed=1, maxIter=20).fit(df)
    assert m.hasSummary
    s = m.summary
    assert s.k == 2
    assert sorted(s.clusterSizes) == [30, 70]
    assert s.trainingCost == pytest.approx(
        m._model_attributes["inertia"]
    )
    assert s.numIter >= 1
    with tempfile.TemporaryDirectory() as td:
        m.save(os.path.join(td, "m"))
        m2 = KMeansModel.load(os.path.join(td, "m"))
        assert not m2.hasSummary
        with pytest.raises(RuntimeError):
            _ = m2.summary


# ------------------------------------------------ rows per centre, on the device


def _placed(n_shards, *arrays):
    """Row-shard host arrays over `n_shards` virtual devices (1: one device)."""
    from spark_rapids_ml_tpu.parallel.partitioner import active_partitioner

    import jax.numpy as jnp

    if n_shards == 1:
        return [jnp.asarray(a) for a in arrays]
    part = active_partitioner(n_shards)
    return [part.shard(a) for a in arrays]


_ABOVE_BOUNDARY = 8193  # over ops/kmeans.py's COUNT_DEVICE_MAX_CENTERS


@pytest.mark.parametrize("n_shards", [1, 8], ids=["one_device", "eight_devices"])
@pytest.mark.parametrize(
    "n_centers,cosine,selection",
    [(2, False, None), (20, False, None), (81, False, None),
     (_ABOVE_BOUNDARY, False, None), (20, True, None), (20, False, "pallas_fused")],
    ids=["k2", "k20", "k81", "above_boundary", "cosine", "pallas_labels"],
)
@pytest.mark.parametrize(
    "weights", ["unit", "zeros_in_the_middle", "padding_beyond_m", "random_float32"]
)
def test_assign_counts_equal_the_hosts_bincount(weights, n_centers, cosine, selection,
                                               n_shards, n_devices):
    """What `kmeans.init.weigh` and `kmeans.summary` read back: the per-centre
    count of kmeans_predict's own labels, bit for bit for 0/1 weights and to
    1e-6 of the float64 bincount for float32 weights."""
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.profiling import counter_totals
    from spark_rapids_ml_tpu.ops import kmeans as K

    assert K.COUNT_DEVICE_MAX_CENTERS < _ABOVE_BOUNDARY
    n_shards = min(n_shards, n_devices)
    rng = np.random.default_rng(n_centers)
    n = 2048
    X = (rng.normal(size=(n, 8)) + rng.integers(0, 4, (n, 1))).astype(np.float32)
    C = (rng.normal(size=(n_centers, 8)) + rng.integers(0, 4, (n_centers, 1))).astype(np.float32)
    m = n - 37
    w = {
        "unit": np.r_[np.ones(m), np.zeros(n - m)],
        "zeros_in_the_middle": (rng.random(n) > 0.3).astype(np.float64),
        "padding_beyond_m": np.r_[np.ones(m), np.zeros(n - m)],
        "random_float32": rng.uniform(0.1, 3.0, n),
    }[weights].astype(np.float32)
    Xj, wj = _placed(n_shards, X, w)
    Cj = _placed(1, C)[0]
    if selection:
        config.set("knn.selection", selection)
    try:
        labels = np.asarray(K.kmeans_predict(Xj, Cj, cosine))
        before = dict(counter_totals())
        if weights == "padding_beyond_m":
            got = K.assign_counts(Xj, Cj, m, cosine)
        else:
            got = K.assign_counts(Xj, Cj, wj, cosine, exact=weights == "unit")
        after = counter_totals()
    finally:
        if selection:
            config.unset("knn.selection")
    want = np.bincount(labels, weights=w.astype(np.float64), minlength=n_centers)
    on_host = n_centers > K.COUNT_DEVICE_MAX_CENTERS
    path = "kmeans.count_path{path=%s}" % ("host" if on_host else "device")
    assert after.get(path, 0) - before.get(path, 0) == 1
    if weights == "random_float32":
        np.testing.assert_allclose(got, want, rtol=1e-6)
    else:
        assert got.dtype == (np.float64 if weights == "zeros_in_the_middle" else np.int64)
        np.testing.assert_array_equal(got, want)
    fetched = after.get("d2h.bytes{site=fit}", 0) - before.get("d2h.bytes{site=fit}", 0)
    if on_host:  # the labels, and the weights where there are any
        assert fetched == (1 if weights == "padding_beyond_m" else 2) * n * 4
    else:
        assert fetched <= 64 * n_shards * n_centers * 4


@pytest.mark.parametrize("n_centers", [3, 9])
def test_label_counts_are_exact_beyond_float32s_integers(n_centers):
    """The reduction alone, labels in and counts out: a centre that holds more
    than 2**24 rows is counted to the row, which a float32 sum cannot do."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.kmeans import _label_counts

    n = (1 << 24) + 40_001
    labels = np.full((n,), 1, np.int32)
    labels[::1024] = n_centers - 1
    labels[5::4096] = 0
    m = n - 11
    want = np.bincount(labels[:m], minlength=n_centers)
    if want[1] % 2 == 0:  # an odd count over 2**24 is no float32
        m -= 1
        want[labels[m]] -= 1
    assert want[1] > 1 << 24 and int(np.float32(want[1])) != want[1]
    got = np.asarray(_label_counts(jnp.asarray(labels), jnp.asarray(m), n_centers))
    np.testing.assert_array_equal(got, want)


# (num_workers, distanceMeasure, weighted) -> first column of cluster_centers_,
# inertia, summary.clusterSizes of the parent tree (ecda1c3, CPU): the k-means||
# start draws from the candidates' weights, so counting them any other way than
# the host's bincount moves every line of this table
_PARENT_FIT = {
    (1, "euclidean", False): ([-3.31988, 9.29116, 8.28601, -8.46024, 0.14415],
                              89777.59375, [200, 183, 212, 202, 206]),
    (1, "cosine", False): ([-0.22988, 0.00588, 0.51235, -0.57987, 0.6991],
                           157.6925048828125, [202, 194, 229, 203, 175]),
    (1, "euclidean", True): ([0.27204, -8.45971, 8.41616, -3.2578, 9.5809],
                             137407.296875, [210, 202, 210, 201, 180]),
    (8, "euclidean", False): ([-3.31988, 9.29116, 8.28601, -8.46024, 0.14415],
                              89777.609375, [200, 183, 212, 202, 206]),
    (8, "cosine", False): ([-0.22988, 0.00588, 0.51235, -0.57987, 0.6991],
                           157.6925048828125, [202, 194, 229, 203, 175]),
    (8, "euclidean", True): ([0.27204, -8.45971, 8.41616, -3.25781, 9.5809],
                             137407.28125, [210, 202, 210, 201, 180]),
}


@pytest.mark.parametrize("num_workers,metric,weighted", list(_PARENT_FIT))
def test_seeded_fit_is_the_parents_fit(num_workers, metric, weighted, n_devices):
    if num_workers > n_devices:
        pytest.skip(f"needs {num_workers} virtual devices")
    X, _ = make_blobs(n_samples=1003, n_features=6, centers=5, cluster_std=4.0,
                      random_state=7)
    X = X.astype(np.float32)
    params = dict(k=5, seed=11, maxIter=4, tol=0.0, num_workers=num_workers,
                  distanceMeasure=metric)
    if weighted:
        w = np.random.default_rng(3).uniform(0.1, 3.0, size=len(X)).astype(np.float32)
        model = KMeans(weightCol="w", **params).fit(
            pd.DataFrame({"features": list(X), "w": w}))
    else:
        model = KMeans(**params).fit(X)
    first_column, inertia, sizes = _PARENT_FIT[(num_workers, metric, weighted)]
    np.testing.assert_allclose(model.cluster_centers_[:, 0], first_column, atol=2e-5)
    assert model._model_attributes["inertia"] == pytest.approx(inertia, rel=1e-6)
    assert list(model.summary.clusterSizes) == sizes
    counters = model.fit_report_["metrics"]["counters"]
    assert counters["kmeans.count_path{path=device}"] == 2
    assert "kmeans.count_path{path=host}" not in counters
