"""Fused pallas Lloyd kernel (ops/pallas_kmeans.py): interpret-mode parity vs the
XLA lloyd_fit, single-device and per-shard under shard_map."""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit
from spark_rapids_ml_tpu.ops.pallas_kmeans import lloyd_fit_pallas, lloyd_step_pallas


def _blobs(n=600, d=16, k=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 5, (k, d)).astype(np.float32)
    X = (centers[rng.integers(0, k, n)] + rng.normal(0, 0.5, (n, d))).astype(np.float32)
    init = centers + rng.normal(0, 0.3, centers.shape).astype(np.float32)
    return X, init


def test_fused_step_matches_xla_accumulation():
    X, init = _blobs()
    w = np.ones((len(X),), np.float32)
    w[-40:] = 0.0  # padding rows contribute nothing
    sums, counts, inertia = lloyd_step_pallas(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(init), interpret=True
    )
    # reference accumulation
    d2 = ((X[:, None, :] - init[None]) ** 2).sum(-1)
    assign = d2.argmin(1)
    onehot = np.eye(init.shape[0], dtype=np.float32)[assign] * w[:, None]
    np.testing.assert_allclose(np.asarray(sums), onehot.T @ X, rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.asarray(counts), onehot.sum(0), atol=1e-5)
    assert float(inertia) == pytest.approx(float((w * d2.min(1)).sum()), rel=1e-5)


@pytest.mark.parametrize("precision", ["DEFAULT", "HIGH", "HIGHEST"])
def test_fused_fit_matches_lloyd_fit(n_devices, precision):
    """Parity gate for the fused kernel at every precision tier: same centers,
    inertia AND effective iteration count as the XLA parity path. On the CPU
    interpret backend the DEFAULT tier is f32-exact too, so all three tiers must
    match exactly; on real TPU the HIGHEST (6-pass) tier is the parity claim
    (chip_smoke.py checks the kernel against XLA and numpy there)."""
    import jax

    X, init = _blobs(n=512)
    w = np.ones((512,), np.float32)
    c_ref, in_ref, it_ref, _ = lloyd_fit(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(init), 1e-6, 20
    )
    c_p, in_p, it_p = lloyd_fit_pallas(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(init), 1e-6, 20, interpret=True,
        precision=getattr(jax.lax.Precision, precision),
    )
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_ref), rtol=1e-4, atol=1e-3)
    assert in_p == pytest.approx(float(in_ref), rel=1e-4)
    assert it_p == int(it_ref)


def test_multipass_dot_tightens_precision():
    """The bf16-split emulation must actually add precision: 3-split (HIGHEST)
    reproduces the f64 reference where 1-split (single MXU pass numerics on TPU)
    would not. Interpret mode executes the same split arithmetic, so the
    decomposition identity is checkable on CPU."""
    from spark_rapids_ml_tpu.ops.pallas_kmeans import _dot_multipass

    rng = np.random.default_rng(0)
    a = (rng.normal(size=(64, 96)) * rng.uniform(0.1, 100, 96)).astype(np.float32)
    b = rng.normal(size=(96, 32)).astype(np.float32)
    ref = a.astype(np.float64) @ b.astype(np.float64)
    dims = (((1,), (0,)), ((), ()))
    # what a single bf16 MXU pass would produce (CPU dot is f32-exact, so the
    # bf16 input rounding is simulated explicitly)
    a16 = np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))
    b16 = np.asarray(jnp.asarray(b).astype(jnp.bfloat16).astype(jnp.float32))
    err_1pass = np.abs(a16 @ b16 - ref).max()
    err3 = np.abs(
        np.asarray(_dot_multipass(jnp.asarray(a), jnp.asarray(b), dims, 3)) - ref
    ).max()
    scale = np.abs(ref).max()
    assert err3 <= 1e-6 * scale
    assert err3 < err_1pass / 100  # decisively tighter than one bf16 pass


def test_fused_fit_sharded(n_devices):
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_array

    X, init = _blobs(n=1024, seed=3)
    w = np.ones((1024,), np.float32)
    mesh = get_mesh()
    c_ref, in_ref, _, _ = lloyd_fit(
        shard_array(X, mesh), shard_array(w, mesh), jnp.asarray(init), 1e-6, 15
    )
    c_p, in_p, _ = lloyd_fit_pallas(
        shard_array(X, mesh), shard_array(w, mesh), jnp.asarray(init), 1e-6, 15,
        mesh=mesh, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_ref), rtol=1e-4, atol=1e-3)
    assert in_p == pytest.approx(float(in_ref), rel=1e-4)


def test_estimator_env_gate(monkeypatch, n_devices):
    """SRML_TPU_PALLAS_KMEANS=1 routes KMeans.fit through the fused kernel with
    matching clusters."""
    import pandas as pd

    from spark_rapids_ml_tpu.clustering import KMeans

    X, _ = _blobs(n=240, d=6, k=2, seed=7)
    df = pd.DataFrame({"features": list(X)})
    base = KMeans(k=2, seed=1, maxIter=20).fit(df)
    monkeypatch.setenv("SRML_TPU_PALLAS_KMEANS", "1")
    fused = KMeans(k=2, seed=1, maxIter=20).fit(df)

    def canon(c):
        c = np.asarray(c)
        return c[np.argsort(c[:, 0])]

    np.testing.assert_allclose(
        canon(base.cluster_centers_), canon(fused.cluster_centers_), atol=1e-3
    )


def test_masked_step_matches_weighted_step():
    """Unit-weight masked kernel (no weight operand) must reproduce the weighted
    kernel's accumulators when w is a prefix mask."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.pallas_kmeans import lloyd_step_pallas_masked

    X, init = _blobs(n=600)
    n_valid = 530
    w = np.ones((600,), np.float32)
    w[n_valid:] = 0.0
    s_ref, c_ref, i_ref = lloyd_step_pallas(
        jnp.asarray(X), jnp.asarray(w), jnp.asarray(init), interpret=True
    )
    s_m, c_m, i_m = lloyd_step_pallas_masked(
        jnp.asarray(X), n_valid, jnp.asarray(init), interpret=True
    )
    np.testing.assert_allclose(np.asarray(s_m), np.asarray(s_ref), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(c_m), np.asarray(c_ref), atol=1e-5)
    assert float(i_m) == pytest.approx(float(i_ref), rel=1e-5)


@pytest.mark.parametrize("precision", ["DEFAULT", "HIGHEST"])
def test_masked_fit_matches_lloyd_fit(n_devices, precision):
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_array
    from spark_rapids_ml_tpu.parallel.partition import pad_rows

    X, init = _blobs(n=500)
    mesh = get_mesh(n_devices)
    Xp, w, _ = pad_rows(X, n_devices)
    Xd, wd = shard_array(Xp, mesh), shard_array(w, mesh)
    c_ref, in_ref, it_ref, _ = lloyd_fit(
        jnp.asarray(Xp), jnp.asarray(w), jnp.asarray(init), 1e-6, 20
    )
    c_m, in_m, it_m = lloyd_fit_pallas(
        Xd, wd, jnp.asarray(init), 1e-6, 20, mesh=mesh, interpret=True,
        precision=getattr(jax.lax.Precision, precision), unit_mask=True,
    )
    np.testing.assert_allclose(np.asarray(c_m), np.asarray(c_ref), rtol=1e-4, atol=1e-3)
    assert in_m == pytest.approx(float(in_ref), rel=1e-4)
    assert it_m == int(it_ref)


def test_estimator_mask_optin_routes_masked_kernel(monkeypatch):
    """SRML_TPU_PALLAS_KMEANS=mask + unit weights through the KMeans ESTIMATOR
    must run the masked kernel and still match the XLA fit."""
    import pandas as pd

    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.ops import pallas_kmeans as pk

    X, _ = _blobs(n=400, d=8)
    df = pd.DataFrame({"features": list(X)})
    ref = KMeans(k=4, maxIter=15, seed=2).fit(df)

    calls = []
    real = pk.lloyd_fit_pallas

    def spy(*a, **kw):
        calls.append(kw.get("unit_mask"))
        return real(*a, **kw)

    monkeypatch.setattr(pk, "lloyd_fit_pallas", spy)
    monkeypatch.setenv("SRML_TPU_PALLAS_KMEANS", "mask")
    masked = KMeans(k=4, maxIter=15, seed=2).fit(df)
    assert calls == [True]
    # same seed + same init path: cluster ordering is deterministic, compare direct
    np.testing.assert_allclose(
        np.asarray(masked.cluster_centers_),
        np.asarray(ref.cluster_centers_),
        rtol=1e-4, atol=1e-3,
    )
