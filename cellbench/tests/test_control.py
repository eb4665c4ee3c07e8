"""`correct` has to be able to come out false.

* The control: the reference computed in bfloat16 and put in the program's
  place (on the chip KMeans's own `fast_math` path is the control; the CPU
  backend computes that path exactly, so the test-size copies name the
  bfloat16 reference). It must fail at least one number of every cell.
* Faults planted under the timed path, the rest of a run driven as it is:
  an answer altered where it is produced, part of the rows left out and the
  mean taken over the rest (half of the batch; one chip's shard, which is what
  a fit without its exchange between chips returns), and a solver that hands
  back its state unchanged.
"""

import os

import numpy as np
import pytest

from cellbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.tiny.json")
CELLS = ["kmeans_k20_d128.fit", "pca_k3_d256.fit", "kmeans_k20_d128.transform",
         "kmeans_k20_d128_4chip.fit"]


def run(workload, seed=31, **kw):
    return harness.run_cell(workload, seed, 0.05, False, bench_json=TINY, rehearsal=True, **kw)


def over(res):
    return sorted(k for k, c in res["checks"].items() if not c["value"] <= c["limit"])


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [41, 42, 43])
def test_the_control_is_not_correct(workload, seed):
    sound = run(workload, seed)
    assert sound["correct"] is True, sound["checks"]
    control = run(workload, seed, control=True)
    assert control["correct"] is False, control["checks"]
    assert control["attempted"] == sound["attempted"] or control["attempted"] >= 1


# ------------------------------------------------------------- planted faults


def _patch_kmeans_fit(monkeypatch, wrap):
    from spark_rapids_ml_tpu.models import clustering

    original = clustering.kmeans_fit
    monkeypatch.setattr(clustering, "kmeans_fit",
                        lambda X, w, **kw: wrap(original, X, w, kw))


def _keep_first_rows(share):
    def wrap(original, X, w, kw):
        import jax.numpy as jnp

        keep = jnp.arange(w.shape[0]) < int(w.shape[0] * share)
        return original(X, jnp.where(keep, w, 0.0), **{**kw, "unit_weight": False})
    return wrap


@pytest.mark.parametrize("workload", ["kmeans_k20_d128.fit", "kmeans_k20_d128_4chip.fit"])
def test_kmeans_fit_centre_altered_where_it_is_produced(monkeypatch, workload):
    def wrap(original, X, w, kw):
        res = original(X, w, **kw)
        centers = np.array(res["cluster_centers"])
        centers[3, 7] += 0.01
        return {**res, "cluster_centers": centers}
    _patch_kmeans_fit(monkeypatch, wrap)
    res = run(workload)
    assert res["correct"] is False and "center_step_err" in over(res)


def test_kmeans_fit_altered_in_the_middle_of_the_window_only(monkeypatch):
    """Every fit of the window is compared, not the last alone: the two
    warm-up fits and the window's first are sound, the second is altered, the
    rest are sound again."""
    calls = []

    def wrap(original, X, w, kw):
        res = original(X, w, **kw)
        calls.append(1)
        if len(calls) != 4:
            return res
        centers = np.array(res["cluster_centers"])
        centers[3, 7] += 0.01
        return {**res, "cluster_centers": centers}
    _patch_kmeans_fit(monkeypatch, wrap)
    res = harness.run_cell("kmeans_k20_d128.fit", 31, 1.0, False, bench_json=TINY,
                           rehearsal=True)
    assert res["attempted"] >= 3
    assert res["correct"] is False and "center_step_err" in over(res)


def test_kmeans_fit_half_of_the_rows_left_out(monkeypatch):
    _patch_kmeans_fit(monkeypatch, _keep_first_rows(0.5))
    res = run("kmeans_k20_d128.fit")
    assert res["correct"] is False and "center_step_err" in over(res)


def test_four_chip_fit_without_its_exchange(monkeypatch):
    """Without the all-reduce a chip's centres are the means of its own shard."""
    _patch_kmeans_fit(monkeypatch, _keep_first_rows(0.25))
    res = run("kmeans_k20_d128_4chip.fit")
    assert res["correct"] is False and "center_step_err" in over(res)


def test_kmeans_solver_returns_its_state_unchanged(monkeypatch):
    def wrap(original, X, w, kw):
        res = original(X, w, **{**kw, "max_iter": 0})  # the start, handed back
        return {**res, "n_iter": 30}
    _patch_kmeans_fit(monkeypatch, wrap)
    res = run("kmeans_k20_d128.fit")
    assert res["correct"] is False and "center_step_err" in over(res)


def test_kmeans_fit_that_stops_early_counts_as_failed(monkeypatch):
    def wrap(original, X, w, kw):
        return original(X, w, **{**kw, "max_iter": 7})
    _patch_kmeans_fit(monkeypatch, wrap)
    res = run("kmeans_k20_d128.fit")
    assert res["failed"] == res["attempted"] >= 1


def test_transform_labels_altered_where_they_are_produced(monkeypatch):
    from spark_rapids_ml_tpu.models import clustering

    original = clustering.kmeans_predict

    def altered(X, centers, cosine=False):
        labels = np.array(original(X, centers, cosine))
        labels[::997] = (labels[::997] + 1) % centers.shape[0]
        return labels
    monkeypatch.setattr(clustering, "kmeans_predict", altered)
    res = run("kmeans_k20_d128.transform")
    assert res["correct"] is False and over(res) == ["label_mismatch_share"]


def test_transform_frames_are_sampled_by_the_seed_and_the_last_is_kept(monkeypatch):
    """Of the window's frames every `compare_every`-th, offset by the seed, is
    compared, and the last always: a fault in a sampled frame alone is seen."""
    from spark_rapids_ml_tpu.models import clustering

    original = clustering.kmeans_predict
    calls = []

    def altered(X, centers, cosine=False):
        labels = np.array(original(X, centers, cosine))
        calls.append(1)
        if len(calls) % 8 == 3:  # with seed 31 and two warm-ups: the sampled ones
            labels[::997] = (labels[::997] + 1) % centers.shape[0]
        return labels
    monkeypatch.setattr(clustering, "kmeans_predict", altered)
    res = harness.run_cell("kmeans_k20_d128.transform", 31, 1.0, False, bench_json=TINY,
                           rehearsal=True)
    assert res["attempted"] >= 9, "the window is too short to hold a sampled frame"
    assert res["correct"] is False and over(res) == ["label_mismatch_share"]


def test_pca_explained_variance_altered_where_it_is_produced(monkeypatch):
    from spark_rapids_ml_tpu.ops import pca as ops_pca

    original = ops_pca.pca_attrs_from_cov

    def altered(cov, mean, wsum, k):
        res = original(cov, mean, wsum, k)
        return {**res, "explained_variance": res["explained_variance"] * (1.0 + 1e-4)}
    monkeypatch.setattr(ops_pca, "pca_attrs_from_cov", altered)
    res = run("pca_k3_d256.fit")
    assert res["correct"] is False and "explained_variance_rel_err" in over(res)


def test_pca_half_of_the_rows_left_out(monkeypatch):
    from spark_rapids_ml_tpu.ops import pca as ops_pca

    original = ops_pca.covariance_for_fit

    def half(X, w, mesh=None, unit_weight=False):
        import jax.numpy as jnp

        keep = jnp.arange(w.shape[0]) < w.shape[0] // 2
        return original(X, jnp.where(keep, w, 0.0), mesh=mesh, unit_weight=False)
    monkeypatch.setattr(ops_pca, "covariance_for_fit", half)
    res = run("pca_k3_d256.fit")
    assert res["correct"] is False and "mean_err" in over(res)
