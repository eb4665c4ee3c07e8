#
# Every tunable resolves in one order: a config pin (set() over the
# environment), else the defaults.py constant — or, for the selection
# strategy and tile, the platform heuristic. A pin to a key's "choose for me"
# sentinel (auto / 0) resolves as if the key were unset.
#
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu import defaults as D
from spark_rapids_ml_tpu import pipeline
from spark_rapids_ml_tpu.continual import drift, partial_fit
from spark_rapids_ml_tpu.observability import tracing
from spark_rapids_ml_tpu.ops import ann_lifecycle, ann_streaming, device_cache, ingest
from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops
from spark_rapids_ml_tpu.ops import pallas_kmeans
from spark_rapids_ml_tpu.ops import pallas_select as ps
from spark_rapids_ml_tpu.ops import selection as sel
from spark_rapids_ml_tpu.parallel import partitioner
from spark_rapids_ml_tpu.serving import batcher, fleet

_MIN_ITEMS = config._DEFAULTS["knn.pallas_min_items"]


class Case(NamedTuple):
    resolve: Callable[[], Any]
    default: Any
    key: Optional[str] = None
    pin: Optional[Tuple[Any, Any]] = None  # (set() value, what it resolves to)
    env: Optional[Tuple[str, Any]] = None  # (environment value, what it resolves to)
    sentinel: Any = None  # the key's "choose for me" value, where it has one
    tpu: bool = False  # the gate is asked as on a TPU (it is closed elsewhere)


def _min_items_gate():
    return sel._fused_auto(_MIN_ITEMS - 1), sel._fused_auto(_MIN_ITEMS)


def _assign_gate():
    k = D.FUSED_ASSIGN_MIN_K
    return (ps.use_fused_assign(k - 1, 64, "auto"),
            ps.use_fused_assign(k, 64, "auto"))


def _lloyd_gate(monkeypatch):
    """The Lloyd gate's reason just under and at LLOYD_FUSED_MIN_K centres; at
    the threshold the VMEM test is answered no, so the XLA program runs."""
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu import profiling

    monkeypatch.setattr(kmeans_ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_kmeans, "lloyd_fits_vmem", lambda *a: False)
    monkeypatch.delenv("SRML_TPU_PALLAS_KMEANS", raising=False)
    reasons = []
    for k in (D.LLOYD_FUSED_MIN_K - 1, D.LLOYD_FUSED_MIN_K):
        X = jnp.asarray(np.random.default_rng(k).normal(size=(k + 8, 4)),
                        jnp.float32)
        profiling.reset_counters()
        kmeans_ops._lloyd(X, jnp.ones(k + 8, jnp.float32), X[:k], k, 1,
                          1e-20, False, True)
        (gate,) = [name for name in profiling.counter_totals()
                   if name.startswith("kmeans.lloyd_gate")]
        reasons.append(gate)
    return tuple(reasons)


def _cache_budget():
    with device_cache.batch_cache() as cache:
        return None if cache is None else cache.budget_bytes


def _sample_rate():
    tracing.reset_tracing()
    return tracing.sample_rate()


_TILE_DEFAULT = D.default_select_tile(100_000, "cpu")

CASES = {
    "selection.strategy": Case(
        lambda: sel.resolve(100_000, 10)[0], "exact_tiled", "knn.selection",
        pin=("exact_full", "exact_full"), env=("approx", "approx"),
        sentinel="auto"),
    "selection.tile": Case(
        lambda: sel.resolve(100_000, 10, "exact_tiled")[1], _TILE_DEFAULT,
        "knn.select_tile", pin=(4096, 4096), env=("8192", 8192), sentinel=0),
    "pallas.min_items": Case(
        _min_items_gate, (False, True), "knn.pallas_min_items",
        pin=(2 * _MIN_ITEMS, (False, False)),
        env=(str(_MIN_ITEMS // 2), (True, True)), tpu=True),
    "pallas.precision": Case(
        sel.resolve_fused_precision, "float32", "knn.pallas_precision",
        pin=("bfloat16", "bfloat16"), env=("int8", "int8")),
    "pallas.topk_geometry": Case(
        lambda: ps._topk_geometry(4096, 1 << 20, 128, 16, None, None),
        (D.DEFAULT_QUERY_BLOCK, D.DEFAULT_ITEM_TILE)),
    "pallas.assign_block": Case(
        lambda: ps._assign_geometry(64, 160, 1, 100_000), D.DEFAULT_ASSIGN_BLOCK),
    "assign.fused_min_k": Case(_assign_gate, (False, True), tpu=True),
    "lloyd.fused_min_k": Case(
        None, ("kmeans.lloyd_gate{fused=0,reason=small_k}",
               "kmeans.lloyd_gate{fused=0,reason=vmem}")),
    "serving.bucket_min_rows": Case(
        lambda: batcher.bucket_rows(1), config._DEFAULTS["serving.bucket_min_rows"],
        "serving.bucket_min_rows", pin=(32, 32), env=("64", 64)),
    "serving.replicas": Case(
        fleet.resolve_replicas, 1, "serving.replicas",
        pin=(3, 3), env=("2", 2), sentinel=0),
    "serving.hedge_after_p99_frac": Case(
        fleet._hedge_frac, 0.0, "serving.hedge_after_p99_frac",
        pin=(1.5, 1.5), env=("2.0", 2.0)),
    "cache.hbm_budget_bytes": Case(
        _cache_budget, config._DEFAULTS["cache.hbm_budget_bytes"],
        "cache.hbm_budget_bytes", pin=(1 << 20, 1 << 20),
        env=(str(1 << 21), 1 << 21)),
    "ann.build_batch_rows": Case(
        ann_streaming.resolve_build_batch_rows, D.ANN_BUILD_BATCH_ROWS,
        "ann.build_batch_rows", pin=(512, 512), env=("256", 256), sentinel=0),
    "ann.list_bucket_rows": Case(
        ann_lifecycle.resolve_list_bucket_rows, D.ANN_LIST_BUCKET_MIN_ROWS,
        "ann.list_bucket_rows", pin=(32, 32), env=("16", 16), sentinel=0),
    "ann.compact_tombstone_pct": Case(
        ann_lifecycle.resolve_compact_tombstone_pct, D.ANN_COMPACT_TOMBSTONE_PCT,
        "ann.compact_tombstone_pct", pin=(50, 50), env=("40", 40)),
    "ingest.staging_pool_rows": Case(
        ingest.resolve_staging_pool_rows, D.INGEST_STAGING_POOL_ROWS,
        "ingest.staging_pool_rows", pin=(123, 123), env=("456", 456), sentinel=0),
    "pipeline.fuse_min_rows": Case(
        pipeline._resolve_fuse_min_rows, D.PIPELINE_FUSE_MIN_ROWS,
        "pipeline.fuse_min_rows", pin=(100, 100), env=("200", 200), sentinel=0),
    "continual.decay": Case(
        partial_fit.resolve_decay, D.CONTINUAL_DECAY, "continual.decay",
        pin=(0.9, 0.9), env=("0.5", 0.5), sentinel=0.0),
    "continual.update_batch_rows": Case(
        partial_fit.resolve_update_batch_rows, D.CONTINUAL_UPDATE_BATCH_ROWS,
        "continual.update_batch_rows", pin=(512, 512), env=("256", 256),
        sentinel=0),
    "continual.drift_mads": Case(
        drift.resolve_drift_mads, D.CONTINUAL_DRIFT_MADS, "continual.drift_mads",
        pin=(4.0, 4.0), env=("5.0", 5.0), sentinel=0.0),
    "partition.feature_axis": Case(
        partitioner.resolve_feature_axis, 1, "partition.feature_axis",
        pin=(2, 2), env=("4", 4), sentinel=0),
    "partition.batch_rows_per_process": Case(
        partitioner.resolve_batch_rows_per_process,
        config._DEFAULTS["stream_batch_rows"] // jax.process_count(),
        "partition.batch_rows_per_process", pin=(4096, 4096),
        env=("2048", 2048), sentinel=0),
    "tracing.sample_rate": Case(
        _sample_rate, D.TRACING_SAMPLE_RATE, "tracing.sample_rate",
        pin=(0.25, 0.25), env=("0.5", 0.5)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_tunable_resolves_pin_then_default(name, monkeypatch):
    case = CASES[name]
    resolve = case.resolve or (lambda: _lloyd_gate(monkeypatch))
    if case.tpu:
        monkeypatch.setattr(sel, "_backend", lambda: "tpu")
    if case.key is not None:
        monkeypatch.delenv(config._ENV_KEYS[case.key], raising=False)
        config.unset(case.key)
    assert resolve() == case.default
    if case.key is None:
        return
    env_var = config._ENV_KEYS[case.key]
    try:
        monkeypatch.setenv(env_var, case.env[0])
        assert resolve() == case.env[1]
        config.set(case.key, case.pin[0])
        assert resolve() == case.pin[1]
        monkeypatch.delenv(env_var)
        if case.sentinel is not None:
            config.set(case.key, case.sentinel)
            assert resolve() == case.default
    finally:
        config.unset(case.key)
        tracing.reset_tracing()
