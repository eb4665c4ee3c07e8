"""Fused pallas Gram kernel (ops/pallas_xtwx.py): interpret-mode parity vs the XLA
weighted_covariance, single-device and per-shard under shard_map, plus the
estimator-facing dispatch gate (ops/pca.py::use_fused_gram)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import config as srml_config
from spark_rapids_ml_tpu.ops.linalg import weighted_covariance
from spark_rapids_ml_tpu.ops.pallas_xtwx import (
    covariance_prefix_mask,
    xtx_pallas,
)


def _data(n=1000, d=24, seed=0):
    # modest column offsets: the S2 - n*mean^2 correction cancels ~|mean|^2/var of
    # the f32 mantissa in BOTH paths, so huge offsets would only test rounding noise
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 2, (n, d)) + rng.normal(0, 0.5, (d,))).astype(np.float32)


def test_xtx_matches_numpy_with_prefix_mask():
    X = _data()
    n_valid = 937  # ragged: mask must zero rows 937..999 in-kernel
    s2, s1 = xtx_pallas(jnp.asarray(X), n_valid, interpret=True)
    Xv = X[:n_valid].astype(np.float64)
    np.testing.assert_allclose(np.asarray(s2), Xv.T @ Xv, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), Xv.sum(0), rtol=1e-4)


def test_xtx_ragged_tail_block_masked():
    # n not a multiple of the block: the edge block loads unspecified values that
    # the in-kernel mask must zero before arithmetic
    X = _data(n=777)
    s2, s1 = xtx_pallas(jnp.asarray(X), 777, interpret=True, blk=512)
    Xv = X.astype(np.float64)
    np.testing.assert_allclose(np.asarray(s2), Xv.T @ Xv, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), Xv.sum(0), rtol=1e-4)


@pytest.mark.parametrize("precision", ["DEFAULT", "HIGH", "HIGHEST"])
def test_covariance_matches_xla_path(precision):
    """Parity across precision tiers: on the CPU interpret backend every tier is a
    real f32 matmul, so all must agree with the XLA weighted_covariance."""
    X = _data(n=1203)
    w = np.ones((1203,), np.float32)
    w[1100:] = 0.0  # suffix pad mask, the pad_rows contract
    cov_ref, mean_ref, ws_ref = jax.jit(weighted_covariance)(
        jnp.asarray(X), jnp.asarray(w)
    )
    cov_p, mean_p, ws_p = covariance_prefix_mask(
        jnp.asarray(X),
        jnp.asarray(w),
        precision=getattr(jax.lax.Precision, precision),
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(cov_p), np.asarray(cov_ref), rtol=2e-3, atol=5e-4)
    np.testing.assert_allclose(np.asarray(mean_p), np.asarray(mean_ref), rtol=1e-5, atol=1e-6)
    assert float(ws_p) == pytest.approx(float(ws_ref))


def test_covariance_sharded_psum(n_devices):
    """8-device mesh: per-shard kernel + psum must equal the single-device result.
    Padding sits at the global end (pad_rows), so only the last shard masks rows."""
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_array
    from spark_rapids_ml_tpu.parallel.partition import pad_rows

    X = _data(n=1000, d=16)
    mesh = get_mesh(n_devices)
    Xp, w, _ = pad_rows(X, n_devices)
    Xd = shard_array(Xp, mesh)
    wd = shard_array(w, mesh)
    cov_p, mean_p, ws_p = covariance_prefix_mask(Xd, wd, mesh=mesh, interpret=True)
    cov_ref, mean_ref, ws_ref = jax.jit(weighted_covariance)(
        jnp.asarray(Xp), jnp.asarray(w)
    )
    np.testing.assert_allclose(np.asarray(cov_p), np.asarray(cov_ref), rtol=2e-3, atol=5e-4)
    np.testing.assert_allclose(np.asarray(mean_p), np.asarray(mean_ref), rtol=1e-5, atol=1e-6)
    assert float(ws_p) == pytest.approx(1000.0)


def test_cse_guard_does_not_change_result():
    X = _data(n=500)
    s2a, _ = xtx_pallas(jnp.asarray(X), 500, interpret=True, cse_guard=0.0)
    s2b, _ = xtx_pallas(jnp.asarray(X), 500, interpret=True, cse_guard=1e-37)
    np.testing.assert_allclose(np.asarray(s2a), np.asarray(s2b), rtol=1e-6)


def test_use_fused_gram_gate():
    from spark_rapids_ml_tpu.ops.pca import use_fused_gram

    on_tpu = jax.devices()[0].platform == "tpu"
    # auto: requires unit weights + narrow-enough features + f32 + TPU
    assert use_fused_gram(128, unit_weight=True) == on_tpu
    assert use_fused_gram(128, unit_weight=False) is False
    assert use_fused_gram(4096, unit_weight=True) is False
    assert use_fused_gram(128, unit_weight=True, dtype=np.float64) is False
    srml_config.set("pallas_xtwx", "0")
    try:
        assert use_fused_gram(128, unit_weight=True) is False
    finally:
        srml_config.unset("pallas_xtwx")
    srml_config.set("pallas_xtwx", "1")
    try:
        # force-on overrides only the platform check — never the SEMANTIC
        # requirements (sample weights would be silently dropped, wide features
        # would blow the kernel's VMEM budget, f64 would lose the user's precision)
        assert use_fused_gram(128, unit_weight=True) is True
        assert use_fused_gram(128, unit_weight=False) is False
        assert use_fused_gram(4096, unit_weight=True) is False
        assert use_fused_gram(128, unit_weight=True, dtype=np.float64) is False
    finally:
        srml_config.unset("pallas_xtwx")


def test_pca_estimator_fused_dispatch_runs_kernel(monkeypatch):
    """End-to-end PCA.fit through the FUSED branch: force the gate on and thread
    interpret=True into covariance_prefix_mask so the pallas kernel really executes
    on the CPU backend. Model attributes must match the XLA-path fit, and the
    kernel must actually have been invoked (not silently fall back)."""
    import pandas as pd

    from spark_rapids_ml_tpu.feature import PCA
    from spark_rapids_ml_tpu.ops import pallas_xtwx as px

    X = _data(n=400, d=12, seed=3)
    df = pd.DataFrame({"features": list(X)})
    m_ref = PCA(k=4, inputCol="features").fit(df)

    calls = []
    real = px.covariance_prefix_mask

    def spy(Xa, wa, mesh=None, **kw):
        calls.append(1)
        kw["interpret"] = True
        return real(Xa, wa, mesh=mesh, **kw)

    monkeypatch.setattr(px, "covariance_prefix_mask", spy)
    srml_config.set("pallas_xtwx", "1")
    try:
        m_fused = PCA(k=4, inputCol="features").fit(df)
    finally:
        srml_config.unset("pallas_xtwx")
    assert calls, "fused covariance kernel was not dispatched"
    np.testing.assert_allclose(
        np.asarray(m_ref.components_), np.asarray(m_fused.components_),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(m_ref.explained_variance_),
        np.asarray(m_fused.explained_variance_),
        rtol=1e-4,
    )


def test_xtxy_matches_numpy_with_prefix_mask():
    """Fused normal-equation stats: one pass must yield XᵀX, colsum, Xᵀy, Σy, Σy²
    over the valid prefix, with the ragged region masked in BOTH operands."""
    from spark_rapids_ml_tpu.ops.pallas_xtwx import xtxy_pallas

    X = _data(n=1000, d=24)
    rng = np.random.default_rng(5)
    y = rng.normal(0, 3, (1000,)).astype(np.float32)
    n_valid = 937
    s2, s1, xty, ysum, yty = xtxy_pallas(
        jnp.asarray(X), jnp.asarray(y), n_valid, interpret=True
    )
    Xv = X[:n_valid].astype(np.float64)
    yv = y[:n_valid].astype(np.float64)
    np.testing.assert_allclose(np.asarray(s2), Xv.T @ Xv, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), Xv.sum(0), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(xty), Xv.T @ yv, rtol=1e-4, atol=1e-3)
    assert float(ysum) == pytest.approx(yv.sum(), rel=1e-4)
    assert float(yty) == pytest.approx((yv * yv).sum(), rel=1e-4)


def test_xtxy_ragged_and_non_lane_multiple():
    """n neither a block nor a 128-lane multiple: the padded y tile and the
    ragged X edge block must both mask to zero."""
    from spark_rapids_ml_tpu.ops.pallas_xtwx import xtxy_pallas

    n = 777
    X = _data(n=n, d=16)
    y = np.random.default_rng(9).normal(size=(n,)).astype(np.float32)
    s2, s1, xty, ysum, yty = xtxy_pallas(
        jnp.asarray(X), jnp.asarray(y), n, interpret=True, blk=512
    )
    Xv, yv = X.astype(np.float64), y.astype(np.float64)
    np.testing.assert_allclose(np.asarray(xty), Xv.T @ yv, rtol=1e-4, atol=1e-3)
    assert float(ysum) == pytest.approx(yv.sum(), rel=1e-4)


def test_normal_eq_matches_xla_stats_sharded(n_devices):
    """normal_eq_prefix_mask under an 8-device mesh vs the in-core XLA pass:
    the fused one-read pass's raw moments, centred as the solve takes them
    (`center_moments`), must reproduce its (S, r, x̄, ȳ, Σw), and it adds Σy²."""
    from spark_rapids_ml_tpu.ops.linear import center_moments, normal_equations
    from spark_rapids_ml_tpu.ops.pallas_xtwx import normal_eq_prefix_mask
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_array
    from spark_rapids_ml_tpu.parallel.partition import pad_rows

    X = _data(n=1000, d=16)
    y = (X @ np.arange(16, dtype=np.float32) * 0.1).astype(np.float32)
    mesh = get_mesh(n_devices)
    Xp, w, _ = pad_rows(X, n_devices)
    yp = np.zeros((Xp.shape[0],), np.float32)
    yp[: len(y)] = y
    Xd, wd, yd = shard_array(Xp, mesh), shard_array(w, mesh), shard_array(yp, mesh)
    A_f, b_f, xbar_f, ybar_f, n_f, yty_f = normal_eq_prefix_mask(
        Xd, yd, wd, mesh=mesh, interpret=True
    )
    S_f, r_f, _, _, _ = center_moments(A_f, b_f, xbar_f, ybar_f, n_f)
    S_r, r_r, xbar_r, ybar_r, n_r = normal_equations(
        jnp.asarray(Xp), jnp.asarray(yp), jnp.asarray(w)
    )
    np.testing.assert_allclose(np.asarray(S_f), np.asarray(S_r), rtol=2e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(r_f), np.asarray(r_r), rtol=2e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(xbar_f), np.asarray(xbar_r), rtol=1e-5, atol=1e-6)
    assert float(ybar_f) == pytest.approx(float(ybar_r), rel=1e-5)
    assert float(n_f) == pytest.approx(1000.0)
    yv = y.astype(np.float64)
    assert float(yty_f) == pytest.approx(float((yv * yv).sum()), rel=1e-4)


def test_linreg_fit_fused_path_matches_xla(monkeypatch):
    """linreg_fit with the gate forced on must dispatch normal_eq_prefix_mask and
    produce the same coefficients/intercept as the XLA stats path."""
    from spark_rapids_ml_tpu.ops import linear as lin
    from spark_rapids_ml_tpu.ops import pallas_xtwx as px

    rng = np.random.default_rng(11)
    n, d = 900, 12
    X = _data(n=n, d=d, seed=11)
    coef_true = rng.normal(size=(d,)).astype(np.float32)
    y = (X @ coef_true + 0.5 + 0.01 * rng.normal(size=(n,))).astype(np.float32)
    w = np.ones((n,), np.float32)
    args = dict(reg=0.1, l1_ratio=0.0, fit_intercept=True, standardize=True,
                max_iter=10, tol=1e-9)
    ref = lin.linreg_fit(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), **args
    )[0]

    calls = []
    real = px.normal_eq_prefix_mask

    def spy(Xa, ya, wa, **kw):
        calls.append(1)
        kw["interpret"] = True
        return real(Xa, ya, wa, **kw)

    monkeypatch.setattr(px, "normal_eq_prefix_mask", spy)
    srml_config.set("pallas_xtwx", "1")
    try:
        fused = lin.linreg_fit(
            jnp.asarray(X), jnp.asarray(y), jnp.asarray(w),
            unit_weight=True, **args
        )[0]
    finally:
        srml_config.unset("pallas_xtwx")
    assert calls, "fused normal-equation kernel was not dispatched"
    np.testing.assert_allclose(
        fused["coefficients"], ref["coefficients"], rtol=5e-4, atol=5e-5
    )
    assert fused["intercept"] == pytest.approx(ref["intercept"], rel=5e-4, abs=5e-4)


@pytest.mark.parametrize("d", [129, 512])
def test_xtx_boundary_widths(d):
    """Lane-padding (d=129) and the MAX_FUSED_COLS VMEM boundary (d=512) —
    widths the dispatch gate admits but hardware time hasn't covered."""
    rng = np.random.default_rng(7)
    n = 700
    X = rng.normal(size=(n, d)).astype(np.float32)
    s2, s1 = xtx_pallas(jnp.asarray(X), n - 60, interpret=True, blk=256)
    Xv = X[: n - 60].astype(np.float64)
    np.testing.assert_allclose(np.asarray(s2), Xv.T @ Xv, rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), Xv.sum(0), rtol=1e-4, atol=1e-4)
