"""The centred Gram summed under the upload (`ops/linalg.py::GramFold`): each
row chunk of the table is folded into PCA's covariance or the normal
equations as it lands (`parallel/partitioner.py::_put_chunked`'s consumer),
centred on the first chunk's weighted mean and corrected once at the end
(`linalg.recentre`), and the fit takes those statistics in place of its own
pass over the resident table.

Held here: the folded statistics against the resident pass and float64, the
fits on them against the float64 minimiser, when the fold engages and why it
does not, and the layout fallback. The chunked upload's gate is cut to toy
sizes and its platform test says what a chip's would, as in
tests/test_partitioner.py. Small, seeded, CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from cellbench import linreg_ref as ref
from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu import observability as obs
from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.feature import PCA
from spark_rapids_ml_tpu.ops import linalg, linear
from spark_rapids_ml_tpu.parallel import partitioner as _P
from spark_rapids_ml_tpu.regression import LinearRegression

# The folded covariance is held to 2e-6 of the largest diagonal entry, of the
# float64 covariance and of the resident pass alike. Both are float32 sums
# of compensated parts: on these tables they read 1.1e-7 to 2.5e-7 of it, and
# the first chunk's centre costs nothing that shows (its correction is about
# 1/128 of the diagonal here, formed to float32's 6e-8). A fold centred at
# the origin instead reads 1.4e-5 to 1.8e-5 at column means of 30: the
# cancellation of 900 against spreads of 1 to 16 that the centre is there to
# avoid (`test_a_centre_at_the_origin_fails_the_tolerance`).
COV_TOL = 2e-6

# the fold's parts, by the kernel names the device plane counts them under
FOLD_KERNELS = ("linalg.weighted_covariance_part", "linear.normal_equations_part")


def _table(rows, cols, mean, seed=0):
    """Columns of spread 1 to 4 about `mean`, or about N(0, 1) means for 0."""
    rng = np.random.default_rng(seed)
    centre = mean if mean else rng.normal(size=cols)
    return (rng.normal(size=(rows, cols)) * np.linspace(1.0, 4.0, cols) + centre).astype(np.float32)


@pytest.fixture
def chunked(monkeypatch):
    """The chunk gate's sizes cut so that a table of 64 KiB or more goes up in
    chunks of `rows` rows (1,024 rows a chunk by default, multiples of 128),
    and its platform test saying what a chip's would; the row weights and
    labels of these tables stay under the threshold and go up whole."""
    def cut(row_bytes, rows=1024):
        monkeypatch.setattr(_P, "CHUNK_BYTES", rows * row_bytes)
    monkeypatch.setattr(_P, "CHUNK_MIN_BYTES", 64 << 10)
    monkeypatch.setattr(_P, "CHUNK_ALIGN_ROWS", 128)
    monkeypatch.setattr(_P, "_host_aliased", lambda device: False)
    _P._layout_refused.clear()
    yield cut
    _P._layout_refused.clear()


def _counters(report_owner):
    return dict(report_owner.fit_report_["metrics"]["counters"])


def _fold_counters(counters):
    return {k: v for k, v in counters.items() if k.startswith("h2d.fold")}


def _kernels_called(counters):
    return {obs.split_label_key(k)[1]["kernel"] for k in counters
            if obs.split_label_key(k)[0] == "device.kernel_calls"}


def _fold_table(X, w, per, part, finish, y=None):
    """`X` put up in chunks of `per` rows with a `GramFold` of (part, finish)
    riding them; the fold's statistics and its parts."""
    device = jax.devices()[0]
    wd = jax.device_put(w, device)
    yd = None if y is None else jax.device_put(y, device)
    fold = linalg.GramFold("test", part, finish, wd, yd)
    placed, chunks = _P._put_chunked(X, device, per * X.shape[1] * X.itemsize, fold)
    np.testing.assert_array_equal(np.asarray(placed), X)
    assert fold.parts == chunks == -(-len(X) // per)
    return fold.close(), fold.parts


def _covariance64(X, w):
    X64, w64 = X.astype(np.float64), w.astype(np.float64)
    mean = w64 @ X64 / w64.sum()
    Xc = X64 - mean
    return (Xc * w64[:, None]).T @ Xc / (w64.sum() - 1.0), mean


COV_CASES = {
    # 640 columns cross GRAM_TRIANGLE_MIN_COLS; 1,000 rows are seven chunks
    # of 128 and a ragged last one of 104
    "ragged_640": dict(rows=1000, cols=640, per=128, mean=0.0, weighted=False),
    "means_30": dict(rows=1000, cols=640, per=128, mean=30.0, weighted=False),
    # chunks of 8,192 rows, longer than GRAM_CHUNK_ROWS: cut inside their program
    "chunks_past_a_part": dict(rows=20000, cols=64, per=8192, mean=30.0, weighted=False),
    # sample weights, zero on the whole first chunk: its plain mean is the centre
    "weighted_first_chunk_zero": dict(rows=1000, cols=640, per=128, mean=30.0, weighted=True),
}


@pytest.mark.parametrize("case", sorted(COV_CASES))
def test_the_folded_covariance_is_the_resident_one(case):
    c = COV_CASES[case]
    X = _table(c["rows"], c["cols"], c["mean"])
    w = np.ones(c["rows"], np.float32)
    if c["weighted"]:
        w = np.random.default_rng(1).uniform(0.0, 2.0, c["rows"]).astype(np.float32)
        w[:c["per"]] = 0.0
    (C, mean, wsum), _ = _fold_table(X, w, c["per"], linalg.weighted_covariance_part,
                                     linalg.weighted_covariance_finish)
    C_res, mean_res, wsum_res = linalg.weighted_covariance(jnp.asarray(X), jnp.asarray(w))
    C64, mean64 = _covariance64(X, w)
    scale = np.abs(np.diagonal(C64)).max()
    C = np.asarray(C, np.float64)
    assert np.abs(C - C64).max() < COV_TOL * scale
    assert np.abs(C - np.asarray(C_res, np.float64)).max() < COV_TOL * scale
    np.testing.assert_array_equal(C, C.T)  # mirrored, to the bit
    # the fold's mean is the first chunk's centre and a compensated sum:
    # no further off float64 than the resident pass's one long matvec
    err = np.abs(np.asarray(mean, np.float64) - mean64).max()
    assert err <= max(np.abs(np.asarray(mean_res, np.float64) - mean64).max(), 1e-6 * 30)
    assert float(wsum) == pytest.approx(float(wsum_res), rel=1e-6)


def test_a_centre_at_the_origin_fails_the_tolerance():
    """What COV_TOL is there to catch: the same fold, every part centred at
    the origin in place of the first chunk's mean."""
    X = _table(1000, 640, 30.0)
    w = jnp.ones((len(X),), jnp.float32)
    blocks = linalg.gram_column_blocks(X.shape[1])
    state = (linalg._gram_zeros(X.shape[1], blocks, jnp.float32, False, True),
             jnp.zeros((), jnp.int32), (jnp.zeros((X.shape[1],), jnp.float32),))
    step = jax.jit(linalg.fold_part)
    for s in range(0, len(X), 128):
        state = step(state, jnp.asarray(X[s:s + 128]), w)
    C = np.asarray(linalg.weighted_covariance_finish(state, w)[0], np.float64)
    C64, _ = _covariance64(X, np.ones(len(X), np.float32))
    assert np.abs(C - C64).max() > 5 * COV_TOL * np.abs(np.diagonal(C64)).max()


def test_pca_fits_on_the_fold():
    """The public path: the table's chunks carry the covariance's parts, the
    fit counts its gate, path and form as before and reads the fold; its
    model is the resident pass's (the same fit with the gate left to the CPU,
    which keeps the single put)."""
    X = _table(1000, 640, 30.0)
    resident = PCA(k=3, inputCol="features", num_workers=1).fit(X)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(_P, "CHUNK_MIN_BYTES", 64 << 10)
        mp.setattr(_P, "CHUNK_BYTES", 128 * 640 * 4)
        mp.setattr(_P, "CHUNK_ALIGN_ROWS", 128)
        mp.setattr(_P, "_host_aliased", lambda device: False)
        folded = PCA(k=3, inputCol="features", num_workers=1).fit(X)
    counters = _counters(folded)
    assert _fold_counters(counters) == {
        "h2d.fold{engaged=true,form=gram,reason=ok,site=fit}": 1,
        "h2d.fold_parts{site=fit}": 8}
    assert counters["h2d.chunks{site=fit}"] == 8
    assert counters["pca.gram_path{path=xla}"] == 1
    assert counters["pca.gram_form{blocks=2,form=triangle}"] == 1
    assert "linalg.weighted_covariance_part" in _kernels_called(counters)
    assert "linalg.weighted_covariance" not in _kernels_called(counters)
    assert _fold_counters(_counters(resident)) == {
        "h2d.fold{engaged=false,form=gram,reason=unchunked,site=fit}": 1}
    np.testing.assert_allclose(folded.mean, resident.mean, rtol=0, atol=1e-4)
    np.testing.assert_allclose(folded.explainedVariance, resident.explainedVariance, rtol=1e-5)
    np.testing.assert_allclose(np.abs(folded.components_ @ resident.components_.T),
                               np.eye(3), atol=1e-4)


def _labelled(rows, cols, mean, seed=2):
    X = _table(rows, cols, mean, seed)
    rng = np.random.default_rng(seed)
    beta = rng.normal(size=cols) * (rng.random(cols) < 0.5)
    y = X.astype(np.float64) @ beta + 3.0 + rng.normal(size=rows)
    return X, y


def test_the_folded_normal_equations_are_the_resident_ones():
    """(S, r, x̄, ȳ, Σw) from the fold against `normal_equations` on the
    resident table: S and r to 2e-6 of their largest entries, the means to
    float32's rounding of column means of 30."""
    X, y = _labelled(1000, 640, 30.0)
    w = np.ones(len(X), np.float32)
    w[-5:] = 0.0
    folded, parts = _fold_table(X, w, 128, linear.normal_equations_part,
                                linear.normal_equations_finish, y)
    resident = linear.normal_equations(jnp.asarray(X), jnp.asarray(y, jnp.float32), jnp.asarray(w))
    assert parts == 8
    for name, a, b in zip(("S", "r", "xbar", "ybar", "n"), folded, resident):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.abs(a - b).max() <= 2e-6 * max(np.abs(b).max(), 1.0), name
    assert float(folded[4]) == 995.0


def _arrow(X, y):
    features = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), X.shape[1])
    return pa.table({"features": features, "label": pa.array(y)})


def _through_origin(X, y, reg):
    """The float64 ridge minimiser with no intercept, (XᵀX/n + λI)β = Xᵀy/n,
    and the gradient of (1/2n)‖y - Xβ‖² + (λ/2)‖β‖² at a β."""
    X64 = X.astype(np.float64)
    n = len(X)
    best = np.linalg.solve(X64.T @ X64 / n + reg * np.eye(X.shape[1]), X64.T @ y / n)
    return best, lambda coef: X64.T @ (X64 @ coef - y) / n + reg * coef


@pytest.mark.parametrize("fit_intercept,standardization", [
    (True, False), (False, False), (True, True)])
def test_linear_regression_on_the_fold_is_the_float64_minimiser(chunked, fit_intercept,
                                                                standardization):
    """The public path from an Arrow table, as `linreg_l2_d3000.fit` hands it
    over: the fit reads the fold and lands on the float64 minimiser
    (`cellbench/linreg_ref.py`; through the origin by numpy where no
    intercept is fitted) within what `tests/test_linreg_reference.py` holds
    the resident pass to: a gradient under 1e-5 of the one at zero (measured
    3e-6 here, the resident pass alike) and coefficients within 1e-4 of their
    RMS. Through the origin the solve takes S + n·x̄x̄ᵀ, of condition 1.3e3
    on this table, which leaves 4.5e-4 of the RMS in the resident pass's
    coefficients too: 1e-3 there."""
    X, y = _labelled(2000, 640, 0.0)
    chunked(640 * 4, rows=256)
    reg = 1e-2
    model = LinearRegression(num_workers=1, regParam=reg, fitIntercept=fit_intercept,
                             standardization=standardization).fit(_arrow(X, y))
    counters = _counters(model)
    assert _fold_counters(counters) == {
        "h2d.fold{engaged=true,form=normal_equations,reason=ok,site=fit}": 1,
        "h2d.fold_parts{site=fit}": 8}
    assert counters["linear.stats_form{form=centred_triangle}"] == 1
    assert "linear.normal_equations" not in _kernels_called(counters)
    coef = np.asarray(model.coefficients, np.float64)
    if fit_intercept:
        st = ref.stats(X, y)
        best, best_intercept, _ = ref.minimiser(st, reg, standardization)
        _, grad = ref.value_and_gradient(X, y, coef, model.intercept, reg,
                                         ref.penalty_scale(st, standardization))
        grad0 = ref.gradient_at_zero(st)
        assert model.intercept == pytest.approx(best_intercept, abs=1e-3)
        coef_tol = 1e-4
    else:
        best, gradient = _through_origin(X, y, reg)
        grad, grad0 = gradient(coef), gradient(np.zeros_like(coef))
        assert model.intercept == 0.0
        coef_tol = 1e-3
    assert np.abs(grad).max() < 1e-5 * np.sqrt(np.mean(grad0 * grad0))
    assert np.abs(coef - best).max() < coef_tol * np.sqrt(np.mean(best * best))


def test_fit_multiple_folds_once_for_every_map(chunked):
    X, y = _labelled(2000, 640, 0.0)
    chunked(640 * 4, rows=256)
    est = LinearRegression(num_workers=1, standardization=False)
    maps = [{est.getParam("regParam"): 1e-2}, {est.getParam("regParam"): 1e-1}]
    with obs.worker_scope() as scope:
        models = [m for _, m in est.fitMultiple(_arrow(X, y), maps)]
    counters = scope.registry.snapshot()["counters"]
    assert _fold_counters(counters) == {
        "h2d.fold{engaged=true,form=normal_equations,reason=ok,site=fit}": 1,
        "h2d.fold_parts{site=fit}": 8}
    st = ref.stats(X, y)
    for model, reg in zip(models, (1e-2, 1e-1)):
        best, _, _ = ref.minimiser(st, reg, False)
        coef = np.asarray(model.coefficients, np.float64)
        assert np.abs(coef - best).max() < 1e-4 * np.sqrt(np.mean(best * best))


def _fused_pca(chunked):
    chunked(64 * 4)
    config.set("pallas_xtwx", "1")  # the fused Gram, interpreted here
    try:
        return PCA(k=2, inputCol="features", num_workers=1).fit(_table(4096, 64, 0.0))
    finally:
        config.unset("pallas_xtwx")


def _sharded_pca(chunked):
    chunked(640 * 4)
    return PCA(k=2, inputCol="features", num_workers=8).fit(_table(1024, 640, 0.0))


def _small_pca(chunked):
    chunked(640 * 4)
    return PCA(k=2, inputCol="features", num_workers=1).fit(_table(16, 640, 0.0))


def _fused_linreg(chunked):
    chunked(64 * 4)
    X, y = _labelled(4096, 64, 0.0)
    config.set("pallas_xtwx", "1")
    try:
        return LinearRegression(num_workers=1, regParam=1e-2).fit(_arrow(X, y))
    finally:
        config.unset("pallas_xtwx")


def _sharded_linreg(chunked):
    chunked(640 * 4)
    X, y = _labelled(1024, 640, 0.0)
    return LinearRegression(num_workers=8, regParam=1e-2).fit(_arrow(X, y))


@pytest.mark.parametrize("arrange,form,reason", [
    (_fused_pca, "gram", "fused"), (_sharded_pca, "gram", "devices"),
    (_small_pca, "gram", "unchunked"), (_fused_linreg, "normal_equations", "fused"),
    (_sharded_linreg, "normal_equations", "devices")],
    ids=["pca_fused", "pca_devices", "pca_unchunked", "linreg_fused", "linreg_devices"])
def test_the_fold_stands_aside_and_says_why(chunked, arrange, form, reason, n_devices):
    """A fit that could fold and did not counts `h2d.fold` once with the
    reason, folds no part, and runs its own pass over the resident table."""
    counters = _counters(arrange(chunked))
    assert _fold_counters(counters) == {
        f"h2d.fold{{engaged=false,form={form},reason={reason},site=fit}}": 1}
    assert not _kernels_called(counters) & set(FOLD_KERNELS)


def _kmeans(X):
    return KMeans(k=3, maxIter=2, seed=1, num_workers=1).fit(X)


def _logistic(X):
    y = (X[:, 0] > X[:, 0].mean()).astype(np.float64)
    return LogisticRegression(maxIter=3, num_workers=1).fit(_arrow(X, y))


@pytest.mark.parametrize("fit", [_kmeans, _logistic], ids=["kmeans", "logistic"])
def test_a_fit_with_no_pass_to_fold_uploads_as_before(chunked, fit):
    """KMeans and LogisticRegression read the table more than once: their
    chunks go up with nothing riding them, and nothing is counted."""
    X = _table(1024, 640, 0.0)
    chunked(640 * 4, rows=256)
    counters = _counters(fit(X))
    assert counters["h2d.chunks{site=fit}"] == 4
    assert _fold_counters(counters) == {}
    assert not _kernels_called(counters) & set(FOLD_KERNELS)


def test_the_layout_fallback_keeps_the_folded_statistics(chunked, monkeypatch):
    """Where the assembled array comes out in another layout than a whole
    put's, `_put` puts the table again whole; the fold already holds every
    row and the fit reads it, folding nothing twice. The shape is remembered:
    the next fit's gate keeps the single put, and that fit runs its own pass."""
    monkeypatch.setattr(_P, "_default_layout", lambda shape, dtype, device: "another layout")
    X = _table(1000, 640, 30.0)
    chunked(640 * 4, rows=128)
    first = PCA(k=3, inputCol="features", num_workers=1).fit(X)
    counters = _counters(first)
    assert counters["h2d.chunk_gate{chunked=false,reason=layout,site=fit}"] == 1
    assert _fold_counters(counters) == {
        "h2d.fold{engaged=true,form=gram,reason=ok,site=fit}": 1,
        "h2d.fold_parts{site=fit}": 8}
    assert counters["device.kernel_calls{kernel=linalg.weighted_covariance_part}"] == 8
    assert "linalg.weighted_covariance" not in _kernels_called(counters)
    second = PCA(k=3, inputCol="features", num_workers=1).fit(X)
    counters = _counters(second)
    assert _fold_counters(counters) == {
        "h2d.fold{engaged=false,form=gram,reason=unchunked,site=fit}": 1}
    assert "linalg.weighted_covariance" in _kernels_called(counters)
    np.testing.assert_allclose(first.explainedVariance, second.explainedVariance, rtol=1e-5)
    np.testing.assert_allclose(first.mean, second.mean, rtol=0, atol=1e-4)
