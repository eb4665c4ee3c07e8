"""Lloyd's centre update `onehotᵀ·X` at the precision pair (DEFAULT, parity)
where the weights are the zero/one pad mask (`unit_weight`), and at
(parity, parity) where they are not (docs/design.md §6d). On the chip the pair
halves the update's bf16 passes; the CPU computes float32 exactly under
either, so what is checked here is which pair the lowered program asks for,
that both give the same fit, and what the counter says."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops
from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit

ROWS, COLS, K, REAL = 256, 12, 5, 231  # the last ROWS - REAL rows are padding

_DOT_PRECISION = re.compile(r"dot_general.*?precision = \[(\w+), (\w+)\]")


def _table(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((ROWS, COLS)).astype(np.float32)
    X[REAL:] = 0.0
    return X, rng


def _mask():
    return (np.arange(ROWS) < REAL).astype(np.float32)


def _dot_precisions(unit_weight, **statics):
    """The operand precisions of every `dot_general` of the lowered program."""
    X, _ = _table()
    text = lloyd_fit.lower(
        jnp.asarray(X), jnp.asarray(_mask()), jnp.asarray(X[:K]), 0.0, 3,
        unit_weight=unit_weight, **statics,
    ).as_text()
    return sorted(_DOT_PRECISION.findall(text))


@pytest.fixture
def parity(request):
    """The setting is read while tracing, and `lower()` answers from jit's
    trace cache where it can: a test that changes it drops that cache."""
    config.set("parity_precision", request.param)
    jax.clear_caches()
    try:
        yield request.param.upper()
    finally:
        config.unset("parity_precision")
        jax.clear_caches()


@pytest.mark.parametrize("parity", ["highest", "high"], indirect=True)
@pytest.mark.parametrize("statics", [{}, {"fast_math": True}, {"cosine": True}],
                         ids=["plain", "fast_math", "cosine"])
def test_the_unit_mask_puts_default_on_the_one_hot_of_one_dot(parity, statics):
    """`unit_weight` moves one operand of one contraction, under either parity
    setting; a weighted fit asks for DEFAULT nowhere that it did not before."""
    weighted = _dot_precisions(False, **statics)
    unit = _dot_precisions(True, **statics)
    assert unit.count(("DEFAULT", parity)) == 1
    assert ("DEFAULT", parity) not in weighted
    # the one dot that changed was at (parity, parity): the update, and no other
    changed = list(weighted)
    changed.remove((parity, parity))
    assert sorted(changed + [("DEFAULT", parity)]) == unit
    if not statics.get("fast_math"):
        assert all("DEFAULT" not in pair for pair in weighted)


@pytest.mark.parametrize("cosine", [False, True], ids=["euclidean", "cosine"])
@pytest.mark.parametrize("seed", [0, 1])
def test_a_zero_one_mask_fits_the_same_under_either_pair(seed, cosine):
    X, rng = _table(seed)
    if cosine:
        X[:REAL] /= np.linalg.norm(X[:REAL], axis=1, keepdims=True)
    init = X[rng.choice(REAL, K, replace=False)]
    args = (jnp.asarray(X), jnp.asarray(_mask()), jnp.asarray(init), 0.0, 8)
    c6, inertia6, n6, _ = lloyd_fit(*args, cosine=cosine)
    c3, inertia3, n3, _ = lloyd_fit(*args, cosine=cosine, unit_weight=True)
    np.testing.assert_array_equal(np.asarray(c3), np.asarray(c6))
    assert float(inertia3) == float(inertia6) and int(n3) == int(n6)
    # and padding is padding: the real rows alone give the same centres
    real = (jnp.asarray(X[:REAL]), jnp.ones(REAL, jnp.float32), jnp.asarray(init), 0.0, 8)
    np.testing.assert_allclose(
        np.asarray(lloyd_fit(*real, cosine=cosine, unit_weight=True)[0]),
        np.asarray(c3), rtol=1e-5, atol=1e-6,
    )


def _frame(seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((300, 8)).astype(np.float32)
    return pd.DataFrame({"features": list(X), "w": rng.uniform(0.5, 2.0, 300)})


def _update_counters(model):
    counters = model.fit_report_["metrics"]["counters"]
    return {k: v for k, v in counters.items() if k.startswith("kmeans.lloyd_update")}


@pytest.mark.parametrize("params,unit_weight", [
    ({}, True),
    ({"weightCol": "w"}, False),
    ({"distanceMeasure": "cosine"}, True),
], ids=["unweighted", "weightCol", "cosine"])
def test_a_fit_tells_the_program_of_its_weights_and_counts_the_passes(
        monkeypatch, params, unit_weight):
    """`KMeans.fit` hands `lloyd_fit` the `unit_weight` of its input (a fact of
    the fit: no weightCol), and counts the update's passes once."""
    seen = []

    def spy(*args, **kwargs):
        seen.append(kwargs["unit_weight"])
        return lloyd_fit(*args, **kwargs)

    monkeypatch.setattr(kmeans_ops, "lloyd_fit", spy)
    model = KMeans(k=4, maxIter=3, seed=1, **params).fit(_frame())
    assert seen == [unit_weight]
    passes = 3 if unit_weight else 6
    assert _update_counters(model) == {f"kmeans.lloyd_update{{passes={passes}}}": 1}


def test_parity_high_is_not_counted_as_the_three_pass_update():
    """Under `high` the pair is still taken, but what runs is not the three
    float32-exact passes that the metric `fit_lloyd_update3_per_op` reads."""
    config.set("parity_precision", "high")
    try:
        model = KMeans(k=4, maxIter=3, seed=1).fit(_frame())
    finally:
        config.unset("parity_precision")
    assert _update_counters(model) == {"kmeans.lloyd_update{passes=6}": 1}
