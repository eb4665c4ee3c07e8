"""Communication-optimality checks on the compiled SPMD programs.

The reference's cuML kernels allreduce once per iteration over NCCL (SURVEY §2.7 P1);
here the same guarantee must come out of XLA's partitioner: the sharded-contraction
formulation has to compile to O(1) cross-device collectives per pass, INDEPENDENT of
mesh size and data shape. These tests pin that property by counting collective ops in
the optimized HLO — a regression here (e.g. an accidental resharding that inserts
all-to-alls or per-feature reduces) would silently destroy multi-chip scaling long
before any wall-clock test could notice on the 8-device CPU mesh.

Counting goes through the communication plane's extraction API
(observability/comm.py::collectives_of_computation, docs/design.md §6h) — the ONE
place that parses HLO text for collectives; ci/lint_python.py bans ad-hoc opcode
parsing everywhere else, so these assertions and the run reports' collective
accounting can never drift apart.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.observability import collectives_of_computation


def _count_collectives(fn, *args):
    """Per-kind op counts of the compiled program (0 for absent kinds)."""
    summary = collectives_of_computation(fn, *args)
    return {
        kind: summary.get(kind, {}).get("ops", 0)
        for kind in (
            "all_reduce", "all_gather", "all_to_all",
            "collective_permute", "reduce_scatter",
        )
    }


def _mesh(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def _sharded_blob(mesh: Mesh, n: int, d: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    X = jax.device_put(
        rng.normal(size=(n, d)).astype(np.float32), NamedSharding(mesh, P("data", None))
    )
    w = jax.device_put(
        np.ones((n,), np.float32), NamedSharding(mesh, P("data"))
    )
    return X, w


@pytest.mark.parametrize("n_dev", [2, 8])
def test_lloyd_step_allreduce_count_constant(n_dev, n_devices):
    """One Lloyd iteration must emit a constant number of all-reduces (the
    sums/counts/inertia reductions — XLA may fuse them into <=3 ops) regardless
    of mesh width, and zero all-to-alls/permutes."""
    from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit

    mesh = _mesh(n_dev)
    X, w = _sharded_blob(mesh, 64 * n_dev, 16)
    init = jnp.asarray(np.random.default_rng(1).normal(size=(4, 16)), jnp.float32)

    counts = _count_collectives(
        lambda X, w, c: lloyd_fit(X, w, c, 0.0, 3), X, w, init
    )
    # the while body reduces (sums, counts, inertia); the final reported inertia
    # adds one more reduce outside the loop. Anything above 6 means the
    # partitioner started resharding per iteration.
    assert 1 <= counts["all_reduce"] <= 6, counts
    assert counts["all_to_all"] == 0, counts
    assert counts["all_gather"] == 0, counts


def test_lloyd_allreduce_count_same_at_2_and_8_devices(n_devices):
    from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit

    found = {}
    for n_dev in (2, 8):
        mesh = _mesh(n_dev)
        X, w = _sharded_blob(mesh, 64 * n_dev, 16)
        init = jnp.asarray(
            np.random.default_rng(1).normal(size=(4, 16)), jnp.float32
        )
        counts = _count_collectives(
            lambda X, w, c: lloyd_fit(X, w, c, 0.0, 3), X, w, init
        )
        found[n_dev] = counts["all_reduce"]
    assert found[2] == found[8], found


def test_covariance_single_allreduce(n_devices):
    """The PCA covariance contraction (X^T diag(w) X) must compile to one
    all-reduce batch: d x d result, never per-row or per-column collectives."""
    from spark_rapids_ml_tpu.ops.linalg import weighted_covariance

    mesh = _mesh(8)
    X, w = _sharded_blob(mesh, 512, 32)
    counts = _count_collectives(weighted_covariance, X, w)
    assert 1 <= counts["all_reduce"] <= 3, counts
    assert counts["all_to_all"] == 0, counts


def test_covariance_allreduce_bytes_are_dxd_shaped(n_devices):
    """Payload accounting sanity (§6h): the covariance all-reduce moves O(d²)
    bytes — a per-row reduction would move O(n·d) and show up here as orders of
    magnitude more analyzed payload."""
    from spark_rapids_ml_tpu.ops.linalg import weighted_covariance

    mesh = _mesh(8)
    d = 32
    X, w = _sharded_blob(mesh, 512, d)
    summary = collectives_of_computation(weighted_covariance, X, w)
    total = sum(st["bytes"] for st in summary.values())
    assert total >= d * d * 4, summary  # at least the d x d f32 result
    assert total <= 16 * d * d * 4 + 4096, summary  # nowhere near O(n*d)


@pytest.mark.parametrize("form", ["two_pass", "fused"])
def test_logreg_grad_allreduce_constant_per_lbfgs_iter(form, n_devices):
    """The L-BFGS while body computes one value+grad over the sharded rows: the
    whole compiled fit must carry a small constant all-reduce count (loss+grad
    inside the loop body + standardization moments + final extras), not one that
    scales with features or linesearch steps. The one-read form
    (ops/pallas_logistic.py, per-shard kernels under shard_map) sums its packed
    partials in ONE psum an evaluation site: four sites and the weights' sum."""
    from spark_rapids_ml_tpu.ops.logistic import _qn_fit
    from spark_rapids_ml_tpu.ops.pallas_logistic import eval_plan

    mesh = _mesh(8)
    # 320 rows a shard: one 256-sample block for the kernel and 64 rows past it
    X, w = _sharded_blob(mesh, 2560, 32)
    y = jax.device_put(
        (np.random.default_rng(2).random(2560) < 0.5).astype(np.float32),
        NamedSharding(mesh, P("data")),
    )
    scale = jnp.ones((32,), jnp.float32)
    fused = eval_plan(X) if form == "fused" else None

    def fit(X, y, w, scale):
        return _qn_fit(
            X, y, w, scale, jnp.float32(0.1), fit_intercept=True, max_iter=5,
            tol=jnp.float32(1e-6), multinomial=False, fused=fused,
        )[0]

    counts = _count_collectives(fit, X, y, w, scale)
    assert 1 <= counts["all_reduce"] <= 8, counts
    assert counts["all_to_all"] == 0, counts


def test_exact_knn_uses_gather_not_quadratic_exchange(n_devices):
    """The distributed exact kNN merge is one all-gather of local top-k blocks
    (P4): the compiled program must not fall back to gathering the full item
    matrix (which would show as all-gathers proportional to feature width)."""
    from spark_rapids_ml_tpu.ops.knn import _knn_local_then_merge_fn

    mesh = _mesh(8)
    X, w = _sharded_blob(mesh, 512, 32)
    valid = jax.device_put(
        np.ones((512,), bool), NamedSharding(mesh, P("data"))
    )
    Q = jnp.asarray(
        np.random.default_rng(3).normal(size=(16, 32)).astype(np.float32)
    )

    merge = _knn_local_then_merge_fn(mesh, shard_rows=64, k_local=4, k_eff=4)
    counts = _count_collectives(merge, Q, X, valid)
    total_comm = (
        counts["all_gather"] + counts["all_reduce"] + counts["collective_permute"]
    )
    assert 1 <= total_comm <= 6, counts
