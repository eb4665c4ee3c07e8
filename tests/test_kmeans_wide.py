"""KMeans from a random start at wide and many-centred shapes: what the chip
cell `kmeans_k1000_d3000.fit` holds the program to, at sizes a CPU can carry,
and the counters that cell reads (docs/design.md §6d)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cellbench import refs
from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops
from spark_rapids_ml_tpu.ops.pallas_kmeans import lloyd_fits_vmem


def _mixture(rows, cols, components, seed, center_scale=0.3):
    """Overlapping gaussian components, so Lloyd is still moving at the
    iterations the tests look at."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((components, cols)) * center_scale
    X = centers[rng.integers(0, components, rows)] + rng.standard_normal((rows, cols))
    return X.astype(np.float32)


# Why 2e-5: off the chip every matmul is true float32, so what separates the
# program from the float64 step is the one-hot matmul's float32 accumulation
# over a cluster's rows and the final divide: a few 1e-7 of a coordinate against
# an RMS centre coordinate of 0.4 to 0.7, read as 3.6e-7 to 9.4e-7 on these six
# cases. One row assigned otherwise (the program ranks float32 expansions, the
# reference decides near-ties in float64) would move a centre of some tens of
# rows by 1e-2 and more: the limit sits a factor of twenty above the rounding
# and five hundred below one row.
STEP_LIMIT = 2e-5


@pytest.mark.parametrize("num_workers", [1, 4])
@pytest.mark.parametrize("k,d", [(20, 128), (250, 750), (1000, 72)])
def test_random_start_last_step_is_one_exact_lloyd_step(k, d, num_workers, n_devices):
    if num_workers > n_devices:
        pytest.skip(f"needs {num_workers} virtual devices")
    max_iter = 4
    # 30 rows a centre: at 6 a centre these shapes reach an exact fixed point in
    # three iterations (a centre of six rows in 128 dimensions keeps its rows)
    X = _mixture(rows=30 * k + 3, cols=d, components=k, seed=k + d)
    params = dict(k=k, tol=1e-20, initMode="random", seed=28, num_workers=num_workers)
    before = KMeans(maxIter=max_iter - 1, **params).fit(X)
    model = KMeans(maxIter=max_iter, **params).fit(X)
    # the same seed picks the same rows, so the shorter fit is the longer
    # fit's own trajectory and both ran to their ends
    assert before.summary.numIter == max_iter - 1
    assert model.summary.numIter == max_iter
    step = refs.lloyd_step(X, np.asarray(before.cluster_centers_))
    scale = float(np.sqrt((step * step).mean()))
    err = float(np.abs(np.asarray(model.cluster_centers_, np.float64) - step).max() / scale)
    assert err <= STEP_LIMIT, err
    labels, inertia, _, _ = refs.assign(X, np.asarray(model.cluster_centers_))
    assert list(model.summary.clusterSizes) == list(np.bincount(labels, minlength=k))
    assert model.inertia_ == pytest.approx(inertia, rel=1e-5)
    assert sum(model.summary.clusterSizes) == len(X)


@pytest.mark.parametrize("n_split", [1, 2, 3])
def test_upstream_widths_do_not_fit_the_fused_kernels_vmem(n_split):
    # residents alone: centres and sums, 1000 x 3000 x (8 + 2 a split copy) B,
    # are 24 to 42 MB against the 8 MiB budget
    assert not lloyd_fits_vmem(1000, 3000, n_split)
    assert lloyd_fits_vmem(128, 128, n_split)


def _lloyd_counters(monkeypatch, backend, k, d, cosine=False):
    rows = k + 24
    X = _mixture(rows, d, 8, seed=7)
    if backend is not None:
        # what the gate sees of the platform, steered here and not by an
        # option of the program; the XLA program below runs where it is
        monkeypatch.setattr(kmeans_ops.jax, "default_backend", lambda: backend)
    monkeypatch.delenv("SRML_TPU_PALLAS_KMEANS", raising=False)
    profiling.reset_counters()
    import jax.numpy as jnp

    out = kmeans_ops._lloyd(jnp.asarray(X), jnp.ones(rows, jnp.float32), jnp.asarray(X[:k]),
                            k, 1, 1e-20, cosine, True)
    assert out["n_iter"] == 1
    return {name: v for name, v in profiling.counter_totals().items()
            if name.startswith(("kmeans.lloyd_", "d2h.bytes"))}


def test_a_wide_fit_on_a_tpu_says_it_left_the_fused_kernel_for_vmem(monkeypatch):
    got = _lloyd_counters(monkeypatch, "tpu", k=1000, d=3000)
    # the rows given the second look are the table's; the rest is the shape's
    assert 0 <= got.pop("kmeans.lloyd_recheck_rows") <= 1024
    assert got == {"kmeans.lloyd_gate{fused=0,reason=vmem}": 1,
                   "kmeans.lloyd_path{path=xla}": 1,
                   "kmeans.lloyd_update{passes=3}": 1,
                   "kmeans.lloyd_assign{passes=3}": 1,
                   "kmeans.lloyd_recheck_overflow": 0,
                   "d2h.bytes{site=fit.centers}": 1000 * 3000 * 4}


@pytest.mark.parametrize("backend,k,cosine,reason", [
    (None, 1000, False, "backend"),  # the CPU of the tests
    ("tpu", 20, False, "small_k"),
    ("tpu", 1000, True, "cosine"),
])
def test_the_gate_names_the_first_test_that_failed(monkeypatch, backend, k, cosine, reason):
    got = _lloyd_counters(monkeypatch, backend, k=k, d=16, cosine=cosine)
    assert got[f"kmeans.lloyd_gate{{fused=0,reason={reason}}}"] == 1
    assert got["kmeans.lloyd_path{path=xla}"] == 1
    assert len([name for name in got if name.startswith("kmeans.lloyd_gate")]) == 1


def test_a_forced_path_is_counted_as_forced(monkeypatch):
    monkeypatch.setenv("SRML_TPU_PALLAS_KMEANS", "0")
    profiling.reset_counters()
    KMeans(k=3, maxIter=2, seed=1).fit(_mixture(64, 4, 3, seed=1))
    totals = profiling.counter_totals()
    assert totals["kmeans.lloyd_gate{fused=0,reason=forced}"] == 1
    assert totals["kmeans.lloyd_path{path=xla}"] == 1


@pytest.mark.parametrize("init", ["random", "k-means||"])
def test_centres_that_cross_are_counted_apart_from_the_counts(init, n_devices):
    k, d, steps = 12, 40, 2
    X = _mixture(1003, d, k, seed=3)
    model = KMeans(k=k, maxIter=3, seed=5, initMode=init, initSteps=steps).fit(X)
    counters = model.fit_report_["metrics"]["counters"]
    centres = k * d * 4
    candidates = (1 + steps * 2 * k) * d * 4
    if init == "random":
        # down: the start's rows and the result; up: the start, and the
        # result again for the summary's pass
        assert counters["d2h.bytes{site=fit.centers}"] == 2 * centres
        assert counters["h2d.bytes{site=fit.centers}"] == 2 * centres
        # what the parent counts: the summary's k int32 sizes, nothing else
        assert counters["d2h.bytes{site=fit}"] == 4 * k
        assert counters["span.calls{span=kmeans.init.random}"] == 1
    else:
        assert counters["d2h.bytes{site=fit.centers}"] == candidates + centres
        assert counters["h2d.bytes{site=fit.centers}"] == candidates + 2 * centres
        assert counters["d2h.bytes{site=fit}"] == 4 * (1 + steps * 2 * k) + 4 * k
        assert "span.calls{span=kmeans.init.random}" not in counters
    # the table's own upload is on its own label, as before
    from spark_rapids_ml_tpu.parallel.partition import pad_rows

    Xp, weight, _ = pad_rows(X, n_devices)
    assert counters["h2d.bytes{site=fit}"] == Xp.nbytes + weight.nbytes


def test_random_start_span_is_the_one_child_of_init():
    model = KMeans(k=4, maxIter=2, seed=2, initMode="random").fit(_mixture(200, 6, 4, seed=2))

    def walk(node):
        yield node
        for child in node["children"]:
            yield from walk(child)

    (init,) = [s for root in model.fit_report_["trace"] for s in walk(root)
               if s["name"] == "kmeans.init"]
    assert [c["name"] for c in init["children"]] == ["kmeans.init.random"]
