"""Ridge LinearRegression through the public path, from an Arrow table (the
path `linreg_l2_d3000.fit` takes), against the plain float64 reference
`cellbench/linreg_ref.py` (numpy only; nothing of `ops/linear.py`): the
minimiser with and without standardization; the centred pass against the raw
moments it replaced, on a table far from the origin; the fused and streamed
producers against the in-core pass; PCA's Gram left as it was; the spans and
counters the cell's metric files name; an elastic-net fit's optimality.

Small, seeded, CPU: a few thousand rows, 12 to 640 columns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from cellbench import linreg_ref as ref
from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.observability.export import iter_spans
from spark_rapids_ml_tpu.ops import linalg, linear
from spark_rapids_ml_tpu.regression import LinearRegression

H = jax.lax.Precision.HIGHEST


def _table(rows, cols, seed, mean_scale=1.0):
    """Columns of spread 1 to 4 about means N(0, mean_scale^2); a label with an
    intercept and noise, float64 as the cell writes it."""
    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(rows, cols)) * np.linspace(1.0, 4.0, cols)
         + mean_scale * rng.normal(size=cols)).astype(np.float32)
    beta = rng.normal(size=cols) * (rng.random(cols) < 0.5)
    y = X.astype(np.float64) @ beta + 3.0 + rng.normal(size=rows)
    return X, y


def _arrow(X, y):
    features = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), X.shape[1])
    return pa.table({"features": features, "label": pa.array(y)})


def _fit(X, y, **params):
    return LinearRegression(num_workers=1, **params).fit(_arrow(X, y))


def _gradient_err(X, y, coef, intercept, reg, standardization, st):
    """The cell's `fit_gradient_err`: the float64 gradient at the fit, largest
    coordinate over the RMS coordinate of the gradient at zero."""
    _, grad = ref.value_and_gradient(X, y, coef, intercept, reg,
                                     ref.penalty_scale(st, standardization))
    g0 = ref.gradient_at_zero(st)
    return float(np.abs(grad).max() / np.sqrt(np.mean(g0 * g0)))


VARIANTS = {"ols": dict(regParam=0.0, standardization=False),
            "ridge": dict(regParam=1e-2, standardization=False),
            "ridge_standardized": dict(regParam=1e-2, standardization=True)}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("shape", [(4096, 64, 1), (9000, 640, 2)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_the_fit_is_the_float64_minimiser(shape, variant):
    """9,000 x 640 crosses `GRAM_TRIANGLE_MIN_COLS` (upper column blocks) and
    takes two full `GRAM_CHUNK_ROWS` parts and a remainder. On these tables
    (centred Gram of condition about 16) float32 leaves a gradient of 1e-7 to
    1e-6 of the one at zero and coefficients within 1e-5 of their RMS: 1e-5
    and 1e-4 leave ten times of room, and a fit that penalised the intercept,
    skipped the centring or solved in bf16 reads 1e-3 or more."""
    params = VARIANTS[variant]
    X, y = _table(*shape)
    model = _fit(X, y, **params)
    st = ref.stats(X, y)
    best, best_intercept, _ = ref.minimiser(st, params["regParam"], params["standardization"])
    coef = np.asarray(model.coefficients, np.float64)
    assert _gradient_err(X, y, coef, model.intercept, params["regParam"],
                         params["standardization"], st) < 1e-5
    assert np.abs(coef - best).max() < 1e-4 * np.sqrt(np.mean(best * best))
    assert model.intercept == pytest.approx(best_intercept, abs=1e-3)


def _raw_moments(X, y):
    """XᵀX, Xᵀy, x̄, ȳ, n in float32 over the whole table: what the fit formed
    before its statistics were centred in the pass."""
    Xj, yj = jnp.asarray(X), jnp.asarray(y, jnp.float32)
    n = jnp.float32(X.shape[0])
    return (jnp.matmul(Xj.T, Xj, precision=H), jnp.matmul(Xj.T, yj, precision=H),
            jnp.sum(Xj, axis=0) / n, jnp.sum(yj) / n, n)


def test_the_centred_pass_holds_a_table_far_from_the_origin():
    """Column means of 30 against spreads of 1 to 4: the raw XᵀX is 60 to 900
    times the centred one, and subtracting n·x̄x̄ᵀ in float32 leaves its
    rounding (6e-8 of the raw entries) in the centred matrix: on the CPU too,
    where a matmul loses nothing else. Measured: the centred pass holds S to
    1.6e-7 and the fit's gradient to 6e-8 of the one at zero, the raw form
    9e-5 and 7.3e-7 (its intercept 0.012 off where the centred one is 2e-5)."""
    X, y = _table(8192, 48, 3, mean_scale=30.0)
    st = ref.stats(X, y)
    ones = jnp.ones((X.shape[0],), jnp.float32)
    centred = linear.normal_equations(jnp.asarray(X), jnp.asarray(y, jnp.float32), ones)
    raw = linear.center_moments(*_raw_moments(X, y))

    def s_err(stats):
        return float(np.abs(np.asarray(stats[0], np.float64) - st["S"]).max()
                     / np.abs(st["S"]).max())

    def fit_err(stats):
        (res,) = linear.solve_from_stats(*stats, reg=1e-5, l1_ratio=0.0, fit_intercept=True,
                                         standardize=False, max_iter=1, tol=0.0)
        return _gradient_err(X, y, res["coefficients"], res["intercept"], 1e-5, False, st)

    assert s_err(centred) < 1e-6 < 1e-5 < s_err(raw)
    assert fit_err(centred) < 2e-7 < 5e-7 < fit_err(raw)


@pytest.mark.parametrize("producer", ["fused", "streamed"])
def test_every_producer_feeds_the_one_solve_the_same_statistics(producer):
    """The fused Pallas pass (interpreted here) and the streamed tier
    accumulate raw moments and centre them before the solve: on a table with
    column means N(0, 1) and spreads 1 to 4 that costs them up to 1e-6 of S,
    so their coefficients agree with the in-core centred pass to 1e-4 of the
    coefficients' RMS (measured 1e-6 to 1e-5)."""
    X, y = _table(4096, 64, 4)
    params = dict(regParam=1e-2, standardization=False)
    incore = _fit(X, y, **params)
    settings = ({"pallas_xtwx": "1"} if producer == "fused"
                else {"stream_threshold_bytes": 1024, "stream_batch_rows": 1024})
    for key, value in settings.items():
        config.set(key, value)
    try:
        other = _fit(X, y, **params)
    finally:
        for key in settings:
            config.unset(key)
    counters = other.fit_report_["metrics"]["counters"]
    assert counters[f"linear.stats_form{{form={producer}}}"] == 1
    a, b = np.asarray(incore.coefficients), np.asarray(other.coefficients)
    assert np.abs(a - b).max() < 1e-4 * np.sqrt(np.mean(a * a))
    assert other.intercept == pytest.approx(incore.intercept, abs=1e-3)


def _parent_weighted_covariance(X, w):
    """`ops/linalg.py::weighted_covariance` as it was before the normal
    equations rode its loop, written out: the weighted mean, then the centred
    rows' upper column-block panels in `GRAM_CHUNK_ROWS`-row parts under a
    compensated sum, mirrored."""
    blocks = linalg.gram_column_blocks(X.shape[1])
    chunk = linalg.GRAM_CHUNK_ROWS

    def body_fn(X, w):
        wsum = jnp.sum(w)
        mean = jnp.matmul(w, X, precision=H) / wsum

        def gram(xs, ws):
            xs = xs - mean[None, :]
            xw = xs * ws[:, None]
            return [jnp.matmul(xw[:, lo:hi].T, xs[:, lo:], precision=H) for lo, hi in blocks]

        def add(carry, terms):
            accs, comps = zip(*(linalg.kahan_add(a, c, t) for a, c, t in zip(*carry, terms)))
            return list(accs), list(comps)

        def body(i, carry):
            xs = jax.lax.dynamic_slice_in_dim(X, i * chunk, chunk, 0)
            ws = jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk, 0)
            return add(carry, gram(xs, ws))

        zeros = [jnp.zeros((hi - lo, X.shape[1] - lo), X.dtype) for lo, hi in blocks]
        full = X.shape[0] // chunk
        carry = jax.lax.fori_loop(0, full, body, (zeros, zeros))
        carry = add(carry, gram(X[full * chunk:], w[full * chunk:]))
        G = jnp.concatenate([jnp.pad(p, ((0, 0), (lo, 0)))
                             for p, (lo, _) in zip(carry[0], blocks)], axis=0)
        G = jnp.where(jnp.tri(G.shape[0], k=-1, dtype=bool), G.T, G)
        return G / (wsum - 1.0), mean, wsum

    return jax.jit(body_fn)(X, w)


def test_pcas_gram_is_the_bits_it_was():
    """PCA's covariance runs the helpers the normal equations now share; with
    no label its program is the one it was, to the bit (the table crosses the
    triangle's width and takes two full parts and a remainder)."""
    X, _ = _table(9000, 640, 5)
    w = np.ones(len(X), np.float32)
    w[-7:] = 0.0
    got = linalg.weighted_covariance(jnp.asarray(X), jnp.asarray(w))
    want = _parent_weighted_covariance(jnp.asarray(X), jnp.asarray(w))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cols,form", [(64, "centred_full"), (640, "centred_triangle")])
def test_a_fit_counts_its_pass_and_its_solve_once(cols, form):
    X, y = _table(2048, cols, 6)
    counters_of = []
    for _ in range(2):  # the second fit is warm, as the cell's window is
        model = _fit(X, y, regParam=1e-5, standardization=False)
        counters_of.append(model.fit_report_["metrics"]["counters"])
    counters = counters_of[-1]
    assert counters[f"linear.stats_form{{form={form}}}"] == 1
    assert sum(v for k, v in counters.items() if k.startswith("linear.stats_form")) == 1
    assert counters["linear.solver{path=l2}"] == 1
    for name in ("linear.stats", "linear.solve", "linear.fetch"):
        assert counters[f"span.calls{{span={name}}}"] == 1, name
    spans = {s["name"] for s in iter_spans(model.fit_report_)}
    assert {"linear.stats", "linear.solve", "linear.fetch"} <= spans


def test_an_elastic_net_fit_meets_its_optimality_conditions():
    """FISTA on the centred Gram form: at its answer the smooth part's
    gradient g (float64) is -λα·sign(β_j) where β_j ≠ 0 and within λα of zero
    where β_j = 0, and the residuals sum to zero (the intercept). Float32
    iterates hold these to 2 % of λα."""
    X, y = _table(3000, 12, 7)
    lam, alpha = 0.5, 0.5
    model = _fit(X, y, regParam=lam, elasticNetParam=alpha, standardization=False,
                 maxIter=20000, tol=1e-12)
    counters = model.fit_report_["metrics"]["counters"]
    assert counters["linear.solver{path=elastic_net}"] == 1
    coef = np.asarray(model.coefficients, np.float64)
    _, grad = ref.value_and_gradient(X, y, coef, model.intercept, lam * (1.0 - alpha))
    g, l1 = grad[:-1], lam * alpha
    nz = coef != 0.0
    assert nz.any() and (~nz).any()
    assert np.abs(g[nz] + l1 * np.sign(coef[nz])).max() < 0.02 * l1
    assert np.abs(g[~nz]).max() <= 1.02 * l1
    assert abs(grad[-1]) < 0.02 * l1
