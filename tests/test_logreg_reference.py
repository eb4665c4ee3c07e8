"""Binary ridge LogisticRegression through the public path, from an Arrow table
(the path `logreg_l2_d3000.fit` takes), against the plain float64 reference
`cellbench/logreg_ref.py` (numpy only; nothing of `ops/logistic.py`): the
optimum, the first step, the reported objective and gradient; the spans and
counters the cell's metric files name; and that the loop's grown state (PR 34:
it counts its evaluations and line-search steps, and returns the gradient it
holds at its last iterate) changed no iterate.

Small, seeded, CPU: a few thousand rows, 24 to 64 columns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pyarrow as pa
import pytest

from cellbench import logreg_ref as ref
from spark_rapids_ml_tpu.classification import LogisticRegression
from spark_rapids_ml_tpu.observability.export import iter_spans
from spark_rapids_ml_tpu.ops import logistic

REG = 1e-5


def _table(rows, cols, seed):
    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(rows, cols)) + 0.3 * rng.normal(size=cols)).astype(np.float32)
    beta = rng.normal(size=cols) * (2.0 / np.sqrt(cols))
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-(X @ beta + 0.25)))).astype(np.float32)
    return X, y


def _arrow(X, y):
    features = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), X.shape[1])
    return pa.table({"features": features, "label": pa.array(y)})


def _fit(X, y, **params):
    params = {"regParam": REG, "standardization": False, "maxIter": 200, "tol": 1e-30, **params}
    return LogisticRegression(num_workers=1, **params).fit(_arrow(X, y))


SHAPES = [(4096, 24, 1), (3072, 64, 2), (6144, 40, 3)]


@pytest.fixture(scope="module", params=SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def fitted(request):
    X, y = _table(*request.param)
    return X, y, _fit(X, y)


def test_the_fit_reaches_the_float64_newton_optimum(fitted):
    """The float32 loop stops when its objective stops changing: within one
    float32 step of f (6e-8 of about 0.5), which on these well-conditioned
    tables (Hessian eigenvalues 0.05 to 0.3) is 1e-3 or less of a coefficient;
    measured 1e-4 to 6e-4 of the largest. 3e-3 leaves five times of room and
    is a tenth of what one skipped iteration, or a penalised intercept, shows."""
    X, y, model = fitted
    coef, intercept, f_opt = ref.newton_optimum(X, y, REG)
    scale = np.abs(coef).max()
    assert np.abs(model.coefficients - coef).max() / scale < 3e-3
    assert abs(model.intercept - intercept) / scale < 3e-3
    attrs = model.get_model_attributes()
    # and from above: no float32 iterate lies under the optimum by more than rounding
    assert -2e-7 < (attrs["objective"] - f_opt) / f_opt < 1e-5


def test_the_reported_objective_is_the_objective_at_the_models_coefficients(fitted):
    X, y, model = fitted
    value, grad = ref.value_and_gradient(X, y, model.coefficients, model.intercept, REG)
    assert abs(model.get_model_attributes()["objective"] - value) / value < 5e-7
    _, grad0 = ref.value_and_gradient(X, y, np.zeros(X.shape[1]), 0.0, REG)
    assert np.linalg.norm(grad) / np.linalg.norm(grad0) < 2e-3


def test_the_reported_gradient_is_the_gradient_at_the_models_coefficients(fitted):
    """What `logreg_l2_d3000.fit` holds its timed fits to (`fit_gradient_err`):
    the loop's own gradient at its last iterate, the one output of the compiled
    fit that float64 can check from outside. Float32 sums over a few thousand
    rows leave 2e-7 to 4e-7 of the RMS coordinate of the gradient at zero;
    bfloat16 operands leave 4e-3 or more."""
    X, y, model = fitted
    reported = model.get_model_attributes()["gradient"]
    assert reported.shape == (1, X.shape[1] + 1) and reported.dtype == np.float32
    _, grad = ref.value_and_gradient(X, y, model.coefficients, model.intercept, REG)
    _, grad0 = ref.value_and_gradient(X, y, np.zeros(X.shape[1]), 0.0, REG)
    rms0 = np.sqrt(np.mean(grad0 * grad0))
    assert np.abs(reported[0] - grad).max() / rms0 < 2e-6
    _, low = ref.value_and_gradient(X, y, model.coefficients, model.intercept, REG,
                                    low_precision=True)
    assert np.abs(low - grad).max() / rms0 > 1e-3


def test_the_gradient_is_a_record_not_a_served_weight(fitted, tmp_path):
    from spark_rapids_ml_tpu.classification import LogisticRegressionModel

    _, _, model = fitted
    assert model._serving_device_attrs() == ("coefficients", "intercepts")
    model.write().overwrite().save(str(tmp_path / "m"))
    back = LogisticRegressionModel.load(str(tmp_path / "m")).get_model_attributes()
    np.testing.assert_array_equal(back["gradient"], model.get_model_attributes()["gradient"])


def test_a_standardized_fit_reports_the_gradient_in_the_coefficients_own_space():
    """The loop optimises sigma-scaled coefficients; the model's `gradient` is
    with respect to the coefficients it returns (times sigma). With the penalty
    on the scaled coefficients the objective is
    `CE + (reg/2) |coef * sigma|^2`: the reference's cross-entropy gradient
    plus `reg * sigma^2 * coef`."""
    X, y = _table(4096, 24, 7)
    X = X * np.linspace(0.5, 3.0, 24).astype(np.float32)
    model = _fit(X, y, standardization=True, regParam=1e-3)
    reported = model.get_model_attributes()["gradient"][0]
    _, ce_grad = ref.value_and_gradient(X, y, model.coefficients, model.intercept, 0.0)
    want = ce_grad + np.append(1e-3 * X.astype(np.float64).var(axis=0, ddof=1)
                               * model.coefficients, 0.0)
    _, grad0 = ref.value_and_gradient(X, y, np.zeros(24), 0.0, 0.0)
    assert np.abs(reported - want).max() / np.sqrt(np.mean(grad0 * grad0)) < 5e-6


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_one_step_from_zero_is_along_the_negative_gradient(shape):
    """Whatever the line search makes of its length: float32 products summed
    over a few thousand rows leave 1e-6 of the RMS coordinate."""
    X, y = _table(*shape)
    model = _fit(X, y, maxIter=1)
    assert model.get_model_attributes()["n_iter"] == 1
    step = np.append(model.coefficients, model.intercept).astype(np.float64)
    _, grad0 = ref.value_and_gradient(X, y, np.zeros(X.shape[1]), 0.0, REG)
    assert step @ -grad0 > 0
    err = np.abs(step / np.linalg.norm(step) + grad0 / np.linalg.norm(grad0)).max()
    assert err * np.sqrt(grad0.size) < 2e-5


def test_the_references_chunked_sums_are_the_float64_formula():
    """Coefficients are taken as the float32 numbers a model holds."""
    X, y = _table(40000, 24, 4)  # more than two of the reference's chunks
    rng = np.random.default_rng(5)
    coef = (rng.normal(size=24) * 0.2).astype(np.float32).astype(np.float64)
    b = 0.125
    value, grad = ref.value_and_gradient(X, y, coef, b, REG)
    X64, y64 = X.astype(np.float64), y.astype(np.float64)
    z = X64 @ coef + b
    want = (np.logaddexp(0.0, z) - y64 * z).mean() + 0.5 * REG * coef @ coef
    r = 1.0 / (1.0 + np.exp(-z)) - y64
    want_grad = np.append(X64.T @ r / len(X) + REG * coef, r.mean())
    assert abs(value - want) / want < 1e-7
    assert np.abs(grad - want_grad).max() / np.abs(want_grad).max() < 1e-6
    low, low_grad = ref.value_and_gradient(X, y, coef, b, REG, low_precision=True)
    assert 1e-6 < abs(low - value) / value < 1e-2
    assert 1e-5 < np.abs(low_grad - grad).max() / np.abs(grad).max() < 1e-1


def test_the_fit_carries_the_spans_and_counters_the_cells_metrics_read(fitted):
    X, y, model = fitted
    report = model.fit_report_
    spans = {s["name"] for s in iter_spans(report)}
    assert {"logistic.labels", "logistic.solve", "logistic.fetch", "fit.ingest",
            "LogisticRegression.prepare", "h2d.wait", "fit.finish"} <= spans
    counters = report["metrics"]["counters"]
    n_iter = model.get_model_attributes()["n_iter"]
    assert counters["logistic.path{path=qn}"] == 1
    assert counters["logistic.loss_evals"] == counters["logistic.linesearch_steps"] + 1
    assert counters["logistic.loss_evals"] >= n_iter >= 1
    for name in ("logistic.labels", "logistic.solve", "logistic.fetch"):
        assert counters[f"span.calls{{span={name}}}"] == 1
        assert counters[f"span.seconds{{span={name}}}"] > 0
    # the Arrow column is a view of the host table, and X, weights, labels go up
    assert counters["ingest.bytes_zero_copy"] == X.nbytes
    assert "ingest.bytes_copied" not in counters
    assert counters["h2d.bytes{site=fit}"] == X.nbytes + 2 * 4 * len(X)


@pytest.mark.parametrize("params,path", [
    ({"standardization": True}, "qn"),
    ({"elasticNetParam": 0.5, "regParam": 1e-3}, "fista"),
    ({"upperBoundsOnCoefficients": [[0.5] * 24]}, "projected"),
], ids=["standardized", "elastic_net", "bounded"])
def test_the_other_paths_name_themselves(params, path):
    """The quasi-Newton paths count their evaluations and report their
    gradient; the prox paths, which hold none at their last iterate, report
    None."""
    X, y = _table(2048, 24, 6)
    model = _fit(X, y, maxIter=20, **params)
    spans = {s["name"] for s in iter_spans(model.fit_report_)}
    counters = model.fit_report_["metrics"]["counters"]
    assert {"logistic.labels", "logistic.solve", "logistic.fetch"} <= spans
    assert counters[f"logistic.path{{path={path}}}"] == 1
    assert ("logistic.loss_evals" in counters) == (path == "qn")
    assert (model.get_model_attributes()["gradient"] is not None) == (path == "qn")


def _lbfgs_as_it_was(loss, params0, max_iter, tol):
    """`ops/logistic.py::_run_lbfgs` before it counted (PR 33's tree)."""
    opt = optax.lbfgs(
        memory_size=logistic.LBFGS_MEMORY,
        linesearch=optax.scale_by_zoom_linesearch(
            max_linesearch_steps=logistic.LINESEARCH_MAX_STEPS),
    )
    value_and_grad = optax.value_and_grad_from_state(loss)

    def cond(state):
        _, _, it, delta, gnorm = state
        return jnp.logical_and(it < max_iter, jnp.logical_and(delta > tol, gnorm > tol))

    def body(state):
        params, opt_state, it, _, _ = state
        value, grad = value_and_grad(params, state=opt_state)
        updates, opt_state = opt.update(grad, opt_state, params, value=value, grad=grad,
                                        value_fn=loss)
        new_params = optax.apply_updates(params, updates)
        new_value = optax.tree_utils.tree_get(opt_state, "value")
        delta = jnp.abs(value - new_value) / jnp.maximum(jnp.abs(new_value), 1.0)
        return new_params, opt_state, it + 1, delta, optax.tree_utils.tree_norm(grad)

    inf = jnp.array(jnp.inf, params0.dtype)
    params, _, n_iter, _, _ = jax.lax.while_loop(
        cond, body, (params0, opt.init(params0), 0, inf, inf))
    return params, n_iter


@pytest.mark.parametrize("multinomial", [False, True], ids=["weighted_binomial", "multinomial"])
def test_counting_changed_no_iterate(multinomial):
    """A weighted and a multinomial fit give what the loop gave before its
    state grew two counters and it returned its gradient: the same iterates,
    the same `n_iter`."""
    rng = np.random.default_rng(8)
    X = jnp.asarray(rng.normal(size=(2048, 24)).astype(np.float32))
    w = jnp.asarray(rng.integers(1, 4, size=2048).astype(np.float32))
    scale = jnp.ones((24,), jnp.float32)
    if multinomial:
        labels = rng.integers(0, 3, size=2048)
        y = jnp.asarray(np.eye(3, dtype=np.float32)[labels])
        loss = logistic._multinomial_loss_fn(X, y, w, scale, 1e-3, True)
        params0 = jnp.zeros((3, 25), jnp.float32)
    else:
        y = jnp.asarray((rng.random(2048) < 0.4).astype(np.float32))
        loss = logistic._binomial_loss_fn(X, y, w, scale, 1e-3, True)
        params0 = jnp.zeros((25,), jnp.float32)
    want, want_iter = jax.jit(lambda: _lbfgs_as_it_was(loss, params0, 25, 1e-30))()
    got, got_iter, obj, grad, evals, steps = logistic._qn_fit(
        X, y, w, scale, 1e-3, True, 25, 1e-30, multinomial)
    assert int(got_iter) == int(want_iter) >= 3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-7)
    assert int(evals) == int(steps) + 1 > int(got_iter)
    assert float(obj) == pytest.approx(float(loss(want)), rel=1e-6)
    # the gradient the loop holds is the gradient at the point it returns
    np.testing.assert_allclose(np.asarray(grad), np.asarray(jax.grad(loss)(got)),
                               rtol=1e-4, atol=2e-7)
