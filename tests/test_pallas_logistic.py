"""The one-read evaluation of the binary logistic data term
(`ops/pallas_logistic.py`): the kernel's three sums against float64 NumPy, the
derivative rule against autodiff of the two-pass form, the three solvers with
the rule engaged through the public `logreg_fit`, the gate's reasons, and the
row-sharded form on the virtual 8-device mesh.

CPU, interpreter (`interpret=True` off a TPU): counts and correctness, never a
speed. The gate's platform test is monkeypatched where a fit has to reach the
kernel; there is no config key for it.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu import profiling
from spark_rapids_ml_tpu.ops import logistic
from spark_rapids_ml_tpu.ops import pallas_logistic as pk

ONE_DEVICE = (None, None, True)  # eval_plan's: no mesh, no specs, interpreted


def _table(rows, cols, seed, weights="unit"):
    """A table with column offsets, labels of a logistic model over all its
    columns, and weights: all one, or 1 to 3 with zero-weight padding rows."""
    rng = np.random.default_rng(seed)
    X = (rng.normal(size=(rows, cols)) + 0.3 * rng.normal(size=cols)).astype(np.float32)
    beta = rng.normal(size=cols) * (2.0 / np.sqrt(cols))
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-(X @ beta + 0.25)))).astype(np.float32)
    w = np.ones(rows, np.float32)
    if weights == "padded":
        w = rng.integers(1, 4, size=rows).astype(np.float32)
        w[-rows // 10:] = 0.0
    return X, y, w


def _float64(X, y, w, beta, b):
    """Value, g, g_b of the normalized data term in float64."""
    X, y, w = (a.astype(np.float64) for a in (X, y, w))
    z = X @ beta.astype(np.float64) + float(b)
    r = w * (1.0 / (1.0 + np.exp(-z)) - y)
    return (w * (np.logaddexp(0.0, z) - y * z)).sum() / w.sum(), X.T @ r / w.sum(), r.sum() / w.sum()


# a ragged last block (1000 = 1 x 512 + 488 samples that take the plain
# expressions; 1024 x 128 and 2048 x 3000 are one and four whole blocks), a width that is no multiple of 8 or 128, the cell's own width
SHAPES = [(1024, 128), (1000, 300), (2048, 3000)]


@pytest.mark.parametrize("fit_intercept", [True, False], ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("weights", ["unit", "padded"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_the_sweep_gives_value_and_gradient_of_float64(shape, weights, fit_intercept):
    X, y, w = _table(*shape, seed=shape[1], weights=weights)
    rng = np.random.default_rng(1)
    params = np.append(rng.normal(size=shape[1]) / np.sqrt(shape[1]), 0.3).astype(np.float32)
    loss = logistic._binomial_loss_fn(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), jnp.ones(shape[1], jnp.float32),
        0.0, fit_intercept, fused=ONE_DEVICE)
    value, grad = jax.jit(jax.value_and_grad(loss))(jnp.asarray(params))
    want, want_g, want_gb = _float64(X, y, w, params[:-1], params[-1] if fit_intercept else 0.0)
    assert abs(float(value) - want) / want < 5e-7
    rms = np.sqrt(np.mean(want_g * want_g))
    assert np.abs(np.asarray(grad[:-1]) - want_g).max() / rms < 2e-6
    if fit_intercept:
        assert abs(float(grad[-1]) - want_gb) < 2e-6 * max(rms, abs(want_gb))
    else:
        assert float(grad[-1]) == 0.0


def test_the_block_follows_the_width_and_the_table():
    assert pk._eval_block_rows(3000) == 512  # 6.1 MB of the cell's table a block
    assert pk._eval_block_rows(128) == pk._eval_block_rows(512) == 4096
    assert pk._eval_block_rows(8192) == 256 and pk._eval_block_rows(8200) == 0
    assert pk._eval_block_rows(300, n=1000) == 512 and pk._eval_block_rows(300, n=100) == 256
    # two pipelined blocks of the cell's width pass the 16 MiB default scope
    assert 16 << 20 < pk._vmem_limit_bytes(512, 3000) < 64 << 20


@pytest.mark.parametrize("reg,scaled", [(0.0, False), (1e-3, True)], ids=["plain", "scaled_ridge"])
def test_the_rule_is_autodiff_of_the_two_pass_form(reg, scaled):
    """`jax.grad` and `jax.value_and_grad` through the rule, with
    standardization's `/scale` and the ridge term outside it. Both sides are
    float32 sums in their own order: a few 1e-7 of the largest entry apart."""
    X, y, w = _table(1500, 40, seed=3, weights="padded")
    scale = np.linspace(0.5, 3.0, 40).astype(np.float32) if scaled else np.ones(40, np.float32)
    args = tuple(jnp.asarray(a) for a in (X, y, w, scale))
    two_pass = logistic._binomial_loss_fn(*args, reg, True)
    fused = logistic._binomial_loss_fn(*args, reg, True, fused=ONE_DEVICE)
    params = jnp.asarray(np.random.default_rng(4).normal(size=41).astype(np.float32) * 0.2)
    want_value, want = jax.value_and_grad(two_pass)(params)
    value, grad = jax.jit(jax.value_and_grad(fused))(params)
    assert float(value) == pytest.approx(float(want_value), rel=1e-6)
    assert float(jax.jit(fused)(params)) == pytest.approx(float(want_value), rel=1e-6)
    scale_of = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want), rtol=0, atol=2e-6 * scale_of)
    np.testing.assert_allclose(np.asarray(jax.jit(jax.grad(fused))(params)), np.asarray(want),
                               rtol=0, atol=2e-6 * scale_of)


SOLVERS = {
    "qn": dict(reg=1e-3, l1_ratio=0.0),
    "fista": dict(reg=1e-3, l1_ratio=0.5),
    "projected": dict(reg=1e-3, l1_ratio=0.0,
                      bounds=(None, np.full((1, 24), 0.3, np.float32), None, None)),
}


@pytest.mark.parametrize("path", list(SOLVERS))
def test_each_solver_reaches_the_two_pass_coefficients(path, monkeypatch):
    """`_qn_fit`, `_fista_fit` and `_projected_fit` through `logreg_fit`, a
    fixed ten iterations: another summation order moves last bits, not the
    iterates' digits nor the budget."""
    X, y, w = _table(2048, 24, seed=5)
    args = tuple(jnp.asarray(a) for a in (X, y, w))
    common = dict(n_classes=2, fit_intercept=True, standardize=True, max_iter=10, tol=1e-30,
                  multinomial=False, **SOLVERS[path])
    before = dict(profiling.counter_totals())
    want = logistic.logreg_fit(*args, **common)
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    got = logistic.logreg_fit(*args, **common)
    after = dict(profiling.counter_totals())

    def added(key):
        return after.get(key, 0) - before.get(key, 0)

    assert added("logistic.eval{form=two_pass}") == added("logistic.eval{form=fused}") == 1
    assert added("logistic.eval_gate{fused=0,reason=platform}") == 1
    assert added("logistic.eval_gate{fused=1,reason=layout}") == 1
    assert got["n_iter"] == want["n_iter"] == 10
    scale = np.abs(want["coefficients"]).max()
    assert np.abs(got["coefficients"] - want["coefficients"]).max() / scale < 1e-5
    assert abs(got["intercepts"][0] - want["intercepts"][0]) / scale < 1e-5
    assert got["objective"] == pytest.approx(want["objective"], rel=1e-6)


def _placed(shape=(512, 300), dtype=jnp.float32, tiling=((8, 128),), major_to_minor=(1, 0)):
    """What the gate reads of a placed table, without a chip to place one."""
    layout = types.SimpleNamespace(tiling=tiling, major_to_minor=major_to_minor)
    return types.SimpleNamespace(shape=shape, dtype=dtype, format=types.SimpleNamespace(layout=layout))


@pytest.mark.parametrize("table,multinomial,on_tpu,verdict", [
    (_placed(), True, True, (False, "multinomial")),
    (_placed(dtype=jnp.float64), False, True, (False, "dtype")),
    (_placed(shape=(512, 8200)), False, True, (False, "cols")),
    (_placed(), False, False, (False, "platform")),
    # the TPU runtime's placement of a width that is a multiple of 128
    (_placed(shape=(512, 256), major_to_minor=(0, 1)), False, True, (False, "layout")),
    (_placed(), False, True, (True, "layout")),
    # the CPU's untiled layout, where the kernel runs interpreted
    (_placed(tiling=(), major_to_minor=(0, 1)), False, True, (True, "layout")),
], ids=["multinomial", "dtype", "cols", "platform", "layout", "fused", "untiled"])
def test_the_gate_names_its_reason(table, multinomial, on_tpu, verdict, monkeypatch):
    monkeypatch.setattr(pk, "_on_tpu", lambda: on_tpu)
    assert pk.eval_gate(table, multinomial) == verdict


def test_the_gate_reads_a_real_array_and_keeps_two_passes_on_the_cpu():
    X = jnp.zeros((64, 12), jnp.float32)
    assert pk.eval_gate(X, False) == (False, "platform")
    assert pk.eval_plan(X) == ONE_DEVICE


def test_the_sharded_sweep_is_the_single_device_one(n_devices):
    """Rows over the virtual 8-device mesh: per-shard kernels (320 samples a
    shard: one 256-sample block and 64 that take the plain expressions) and
    ONE psum of the packed partials."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    X, y, w = _table(2560, 48, seed=6, weights="padded")
    rows, vec = NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P("data"))
    Xs, ys, ws = jax.device_put(X, rows), jax.device_put(y, vec), jax.device_put(w, vec)
    plan = pk.eval_plan(Xs)
    assert plan[0] is mesh and plan[2] is True
    scale = jnp.ones(48, jnp.float32)
    params = jnp.asarray(np.random.default_rng(7).normal(size=49).astype(np.float32) * 0.2)
    sharded = logistic._binomial_loss_fn(Xs, ys, ws, scale, 1e-3, True, fused=plan)
    single = logistic._binomial_loss_fn(jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), scale,
                                        1e-3, True, fused=ONE_DEVICE)
    value, grad = jax.jit(jax.value_and_grad(sharded))(params)
    want_value, want = jax.jit(jax.value_and_grad(single))(params)
    assert float(value) == pytest.approx(float(want_value), rel=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want), rtol=0,
                               atol=1e-6 * float(jnp.max(jnp.abs(want))))
    want64, want_g, _ = _float64(X, y, w, np.asarray(params[:-1]), float(params[-1]))
    assert abs(float(value) - 0.5e-3 * float(params[:-1] @ params[:-1]) - want64) / want64 < 1e-6
