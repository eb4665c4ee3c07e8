"""Fused Pallas distance+select kernel family (ops/pallas_select.py, docs/
design.md §5c): interpret-mode parity property tests on CPU.

The §5c contracts under test:
  * exact-f32 fused scans are BIT-IDENTICAL to the select_topk(exact_full)
    path — ids, distances, tie order, masked/k>n_valid tails — including
    per-shard under shard_map through the production distributed path;
  * bf16/int8 distance accumulation returns distances bit-equal to the
    exact-f32 difference-form recompute (the parity_rerank_sq invariant:
    only the id set carries the approximation);
  * the `pallas_fused` strategy value resolves per the PR-5 host-wrapper
    contract (fusable-only, auto gating, degradations);
  * routing counters prove which path ran (kmeans.lloyd_path,
    kmeans.assign_path, knn.rerank, knn.select_strategy).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.ops import pallas_select as ps
from spark_rapids_ml_tpu.ops import selection as sel
from spark_rapids_ml_tpu.ops.knn import exact_knn_distributed, exact_knn_single
from spark_rapids_ml_tpu.profiling import counter_totals


@pytest.fixture(autouse=True)
def _clean_config():
    yield
    for key in (
        "knn.selection",
        "knn.pallas_precision",
        "knn.pallas_min_items",
        "knn.select_tile",
    ):
        config.unset(key)


def _data(n=997, d=13, nq=33, seed=0, mask_frac=0.2, ties=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    if ties:
        # duplicate rows: equal distances whose order only the lowest-index
        # tie rule resolves — the bit-parity stress case
        X[n // 2] = X[n // 10]
        X[n // 2 + 1] = X[n // 10]
    Q = X[:nq].copy()
    valid = rng.random(n) > mask_frac
    return jnp.asarray(Q), jnp.asarray(X), jnp.asarray(valid)


def _reference_topk(Q, X, valid, k, x2=None):
    """The XLA exact_full scan the fused kernel must match bit-for-bit."""
    return exact_knn_single(Q, X, valid, k, x2=x2, strategy="exact_full")


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------ fused topk


@pytest.mark.parametrize("q_block,item_tile", [(7, 100), (32, 256), (33, 997)])
def test_fused_topk_bitwise_parity(q_block, item_tile):
    """Random masks + ties + non-divisible tiles: ids AND distances bit-equal
    to the exact_full path at every tile geometry."""
    Q, X, valid = _data()
    rd, ri = _reference_topk(Q, X, valid, 10)
    fd, fi = ps.fused_topk(
        Q, X, valid, 10, q_block=q_block, item_tile=item_tile
    )
    _assert_bitwise(fi, ri)
    _assert_bitwise(fd, rd)


def test_fused_topk_k_exceeds_valid():
    """k > n_valid: the XLA path fills the tail with the EARLIEST invalid ids
    at exactly INVALID_D2; the fused pool must reproduce that tail bitwise."""
    Q, X, _ = _data(n=200, nq=9, ties=False)
    valid = np.zeros(200, bool)
    valid[[3, 77, 150]] = True
    rd, ri = _reference_topk(Q, X, jnp.asarray(valid), 10)
    fd, fi = ps.fused_topk(Q, X, jnp.asarray(valid), 10, item_tile=64)
    _assert_bitwise(fi, ri)
    _assert_bitwise(fd, rd)
    assert np.asarray(fd)[:, 3:].max() == np.asarray(fd)[:, 3:].min() == sel.INVALID_D2


def test_fused_topk_cached_x2_bitwise():
    """The PR-5 norm hoist: a cached x2 must flow through the fused scan and
    keep bit-parity (the cache is the same reduce the kernel would run)."""
    Q, X, valid = _data(seed=3)
    x2 = jnp.sum(X * X, axis=1)
    rd, ri = _reference_topk(Q, X, valid, 8, x2=x2)
    fd, fi = ps.fused_topk(Q, X, valid, 8, x2=x2)
    _assert_bitwise(fi, ri)
    _assert_bitwise(fd, rd)


def test_exact_knn_single_routes_pallas_fused():
    """The host wrapper routes `knn.selection=pallas_fused` through the fused
    scan with results bit-identical to exact_full, and records the strategy."""
    Q, X, valid = _data(seed=5)
    rd, ri = _reference_topk(Q, X, valid, 10)
    before = dict(counter_totals())
    config.set("knn.selection", "pallas_fused")
    fd, fi = exact_knn_single(Q, X, valid, 10)
    config.unset("knn.selection")
    _assert_bitwise(fi, ri)
    _assert_bitwise(fd, rd)
    key = "knn.select_strategy{site=exact_knn,strategy=pallas_fused}"
    assert counter_totals().get(key, 0) > before.get(key, 0)


def test_fused_distributed_matches_xla(n_devices):
    """Per-shard pallas_call under shard_map through the PRODUCTION
    exact_knn_distributed path: merge contracts untouched, results bitwise."""
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_array
    from spark_rapids_ml_tpu.parallel.partition import pad_rows

    rng = np.random.default_rng(11)
    X = rng.normal(size=(1000, 12)).astype(np.float32)
    X[500] = X[2]  # cross-shard tie
    mesh = get_mesh()
    Xp, w, _ = pad_rows(X, mesh.devices.size)
    Xd, vd = shard_array(Xp, mesh), shard_array(w > 0, mesh)
    Q = X[:40]
    d_ref, i_ref = exact_knn_distributed(mesh, Q, Xd, vd, 7)
    config.set("knn.selection", "pallas_fused")
    d_f, i_f = exact_knn_distributed(mesh, Q, Xd, vd, 7)
    config.unset("knn.selection")
    _assert_bitwise(i_f, i_ref)
    _assert_bitwise(d_f, d_ref)


# ------------------------------------------------------- mixed-precision rerank


@pytest.mark.parametrize("precision", ["bfloat16", "int8"])
@pytest.mark.parametrize("seed,k,mask_frac", [(0, 10, 0.2), (7, 3, 0.0), (13, 25, 0.5)])
def test_rerank_invariant_distances_exact(precision, seed, k, mask_frac):
    """The parity_rerank_sq invariant, §5c acceptance: under bf16/int8
    accumulation the RETURNED (distances, ids) are bit-equal to the exact-f32
    parity_rerank_sq computation of the returned ids (idempotency: the
    re-rank IS the definition of the returned values), across random masks,
    ties and k — only the id set carries the approximation. Invalid tail
    slots carry exactly INVALID_D2, and the exact values agree with a
    difference-form recompute to f32 reduce-order tolerance."""
    from spark_rapids_ml_tpu.ops.knn import parity_rerank_sq

    Q, X, valid = _data(seed=seed, mask_frac=mask_frac)
    config.set("knn.selection", "pallas_fused")
    config.set("knn.pallas_precision", precision)
    d2, ids = exact_knn_single(Q, X, valid, k)
    config.unset("knn.selection")
    config.unset("knn.pallas_precision")
    ids_h = np.asarray(ids)
    valid_h = np.asarray(valid)
    got = np.asarray(d2)
    # idempotency: re-running the exact-f32 parity re-rank on the returned
    # ids reproduces the returned distances AND ids bit-for-bit
    d2_2, ids_2 = parity_rerank_sq(Q, X, valid, jnp.asarray(ids_h), k)
    np.testing.assert_array_equal(np.asarray(d2_2), got)
    np.testing.assert_array_equal(np.asarray(ids_2), ids_h)
    # and the values are the true f32 squared distances (reduce-order ulp)
    d2_exact = np.asarray(
        jnp.sum((X[jnp.asarray(ids_h)] - Q[:, None, :]) ** 2, axis=-1)
    )
    slot_valid = valid_h[ids_h]
    np.testing.assert_allclose(
        got[slot_valid], d2_exact[slot_valid], rtol=1e-6, atol=0
    )
    assert (got[~slot_valid] == sel.INVALID_D2).all()
    # the id sets stay high-recall vs exact (loose: the pool oversamples)
    _, exact_ids = _reference_topk(Q, X, valid, k)
    exact_ids = np.asarray(exact_ids)
    recall = np.mean([
        len(set(ids_h[i]) & set(exact_ids[i])) / k for i in range(len(ids_h))
    ])
    assert recall >= 0.8, recall


def test_rerank_counter_fires():
    Q, X, valid = _data(seed=2)
    before = dict(counter_totals())
    config.set("knn.selection", "pallas_fused")
    config.set("knn.pallas_precision", "bfloat16")
    exact_knn_single(Q, X, valid, 5)
    config.unset("knn.selection")
    config.unset("knn.pallas_precision")
    after = counter_totals()
    fired = sum(
        v - before.get(key, 0)
        for key, v in after.items()
        if key.startswith("knn.rerank")
    )
    assert fired >= 1


def test_float32_mode_never_reranks():
    Q, X, valid = _data(seed=4)
    before = dict(counter_totals())
    config.set("knn.selection", "pallas_fused")
    exact_knn_single(Q, X, valid, 5)
    config.unset("knn.selection")
    after = counter_totals()
    fired = sum(
        v - before.get(key, 0)
        for key, v in after.items()
        if key.startswith("knn.rerank")
    )
    assert fired == 0


def test_oversample_width():
    assert ps.oversample_width(10, 1000, "float32") == 10
    assert ps.oversample_width(10, 1000, "bfloat16") == 18
    assert ps.oversample_width(100, 1000, "int8") == 125
    assert ps.oversample_width(100, 110, "int8") == 110  # clamped to n


def test_bad_precision_raises():
    with pytest.raises(ValueError, match="knn.pallas_precision"):
        sel.resolve_fused_precision("float16")
    config.set("knn.pallas_precision", "fp8")
    with pytest.raises(ValueError, match="knn.pallas_precision"):
        sel.resolve_fused_precision(None)


# ------------------------------------------------------------ kmeans assignment


def test_fused_assign_bitwise_with_ties():
    """Fused argmin assignment == kmeans_predict bitwise, including duplicate
    centers (equal distances) where only the tie rule decides."""
    from spark_rapids_ml_tpu.ops.kmeans import kmeans_predict

    rng = np.random.default_rng(0)
    X = rng.normal(size=(701, 9)).astype(np.float32)
    centers = X[:130].copy()
    centers[5] = centers[3]  # duplicate center: argmin tie
    Xj, Cj = jnp.asarray(X), jnp.asarray(centers)
    a_ref = np.asarray(kmeans_predict(Xj, Cj))
    config.set("knn.selection", "pallas_fused")
    a_f = np.asarray(kmeans_predict(Xj, Cj))
    config.unset("knn.selection")
    np.testing.assert_array_equal(a_f, a_ref)
    # direct kernel entry with an odd block: ragged row tail
    a_d = np.asarray(ps.fused_assign(Xj, Cj, block=100))
    np.testing.assert_array_equal(a_d, a_ref)


def test_use_fused_assign_gate():
    # explicit strategy wins on any platform (interpret mode off-TPU)
    assert ps.use_fused_assign(8, strategy="pallas_fused") is True
    # auto: CPU never fuses (the kernel would run interpreted)
    assert ps.use_fused_assign(1024, strategy="auto") is (
        jax.default_backend() == "tpu"
    )
    # small k never auto-fuses even on TPU (the measured loss region)
    assert ps.use_fused_assign(8, strategy="auto") is False
    # a pinned exact strategy forces the XLA kernel
    assert ps.use_fused_assign(1024, strategy="exact_full") is False


def test_vmem_geometry_bounds():
    """A (k, d) whose resident centers can't fit the VMEM budget must stay
    on the XLA path — EVEN under an explicit pallas_fused request (Mosaic
    must never see an unplaceable compile) — and the geometry resolvers
    shrink blocks rather than exceed the budget."""
    # k=8192 centers at d=512: 16 MiB resident > the 8 MiB budget
    assert ps._assign_geometry(512, 8192, 1, 100_000) is None
    assert ps.use_fused_assign(8192, 512, strategy="pallas_fused") is False
    assert ps.use_fused_assign(8192, 512, strategy="auto") is False
    # a fitting shape returns a block between the floor and the default
    blk = ps._assign_geometry(64, 160, 1, 100_000)
    assert blk is not None and ps.MIN_ASSIGN_BLOCK <= blk <= ps.DEFAULT_ASSIGN_BLOCK
    # fused_assign without a fitting block refuses loudly
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(64, 512)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(8192, 512)).astype(np.float32))
    with pytest.raises(ValueError, match="VMEM"):
        ps.fused_assign(X, C)
    # topk geometry: large k shrinks the query block, never the budget
    qb, t = ps._topk_geometry(4096, 1 << 20, 128, 2048, None, None)
    work = qb * (2048 + t) * 16 + qb * 128 * 4 + t * 128 * 4 + qb * 2048 * 8
    assert work <= ps._VMEM_BUDGET_BYTES
    # the count kernel resolves through the same shrink (k=0, wide d)
    qb2, t2 = ps._topk_geometry(1 << 16, 1 << 16, 2048, 0, None, None)
    assert (
        qb2 * t2 * 16 + (qb2 + t2) * 2048 * 4 <= ps._VMEM_BUDGET_BYTES
    )
    # kernels still run (and stay bit-exact) at a shrunken geometry
    Q, Xd, valid = _data(seed=9)
    rd, ri = _reference_topk(Q, Xd, valid, 10)
    fd, fi = ps.fused_topk(Q, Xd, valid, 10, q_block=ps.MIN_QUERY_BLOCK)
    _assert_bitwise(fi, ri)
    _assert_bitwise(fd, rd)


def test_lloyd_fits_vmem_predicate():
    """The fused-Lloyd auto gate asks the kernel module's own VMEM predicate:
    the measured win shape fits, center counts in the thousands don't."""
    from spark_rapids_ml_tpu.ops.pallas_kmeans import lloyd_fits_vmem

    assert lloyd_fits_vmem(128, 128, 3) is True  # the k>=128 win boundary
    assert lloyd_fits_vmem(20, 128, 3) is True   # small k always places
    assert lloyd_fits_vmem(4096, 128, 3) is False  # IVF-scale k: XLA path
    assert lloyd_fits_vmem(128, 8192, 3) is False  # huge d: block won't fit


def test_assign_n_split_matches_parity_contract(monkeypatch):
    """Off-TPU the assignment cross term is a single exact-f32 pass (bit-
    equal to pdot on CPU); on TPU it inherits the parity_precision pass
    structure (3-split for HIGHEST, 2 for HIGH) like the fused Lloyd."""
    assert ps._assign_n_split() == 1  # CPU interpreter: exact f32
    monkeypatch.setattr(ps, "_interpret_default", lambda: False)
    assert ps._assign_n_split() == 3  # parity_precision default: highest
    config.set("parity_precision", "high")
    try:
        assert ps._assign_n_split() == 2
    finally:
        config.unset("parity_precision")


def test_assign_path_counter():
    from spark_rapids_ml_tpu.ops.kmeans import kmeans_predict

    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    C = X[:6]
    before = dict(counter_totals())
    kmeans_predict(X, C)
    config.set("knn.selection", "pallas_fused")
    kmeans_predict(X, C)
    config.unset("knn.selection")
    after = counter_totals()
    xla_key = "kmeans.assign_path{path=xla}"
    fused_key = "kmeans.assign_path{path=pallas_fused}"
    assert after.get(xla_key, 0) - before.get(xla_key, 0) >= 1
    assert after.get(fused_key, 0) - before.get(fused_key, 0) >= 1


def test_lloyd_path_auto_and_forced(monkeypatch):
    """SRML_TPU_PALLAS_KMEANS=auto (the new default) keeps small-k CPU fits on
    the XLA Lloyd and counts the path; '1' still forces the fused kernel."""
    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(240, 6)).astype(np.float32))
    w = jnp.ones((240,), jnp.float32)
    monkeypatch.delenv("SRML_TPU_PALLAS_KMEANS", raising=False)
    before = dict(counter_totals())
    ref = kmeans_fit(X, w, k=3, max_iter=8, tol=1e-4, init="random", init_steps=2,
                     seed=0, unit_weight=True)
    after = counter_totals()
    xla_key = "kmeans.lloyd_path{path=xla}"
    assert after.get(xla_key, 0) - before.get(xla_key, 0) == 1
    monkeypatch.setenv("SRML_TPU_PALLAS_KMEANS", "1")
    before = dict(counter_totals())
    fused = kmeans_fit(X, w, k=3, max_iter=8, tol=1e-4, init="random",
                       init_steps=2, seed=0, unit_weight=True)
    after = counter_totals()
    w_key = "kmeans.lloyd_path{path=pallas_weighted}"
    assert after.get(w_key, 0) - before.get(w_key, 0) == 1
    np.testing.assert_allclose(
        fused["cluster_centers"], ref["cluster_centers"], rtol=1e-4, atol=1e-3
    )


# --------------------------------------------------------------- dbscan counts


def test_fused_count_matches_core_mask_bitwise():
    from spark_rapids_ml_tpu.ops.dbscan import _core_mask, _core_mask_xla

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(403, 7)).astype(np.float32))
    valid = jnp.asarray(rng.random(403) > 0.15)
    eps2 = 1.7
    ref = np.asarray(_core_mask_xla(X, valid, eps2, 4))
    config.set("knn.selection", "pallas_fused")
    fused = np.asarray(_core_mask(X, valid, eps2, 4))
    config.unset("knn.selection")
    np.testing.assert_array_equal(fused, ref)
    # raw counts too (the reduction itself, odd tile geometry)
    counts = np.asarray(
        ps.fused_count_below(X, X, valid, eps2, q_block=50, item_tile=111)
    )
    d2 = np.maximum(
        (np.asarray(X)[:, None, :] - np.asarray(X)[None, :, :]) ** 2, 0
    ).sum(-1)
    expect = ((d2 <= eps2) & np.asarray(valid)[None, :]).sum(1)
    np.testing.assert_array_equal(counts, expect)


def test_dbscan_labels_identical_under_fused():
    from spark_rapids_ml_tpu.ops.dbscan import dbscan_fit_predict

    rng = np.random.default_rng(3)
    X = np.concatenate([
        rng.normal(-4, 0.4, (80, 5)), rng.normal(4, 0.4, (80, 5)),
        rng.uniform(-10, 10, (12, 5)),
    ]).astype(np.float32)
    valid = np.ones(len(X), bool)
    ref = dbscan_fit_predict(jnp.asarray(X), jnp.asarray(valid), 1.2, 5)
    config.set("knn.selection", "pallas_fused")
    fused = dbscan_fit_predict(jnp.asarray(X), jnp.asarray(valid), 1.2, 5)
    config.unset("knn.selection")
    np.testing.assert_array_equal(fused, ref)


def test_use_fused_count_gate(monkeypatch):
    assert ps.use_fused_count(100, strategy="pallas_fused") is True
    assert ps.use_fused_count(1 << 20, strategy="exact_tiled") is False
    # auto follows the TPU + min-items gate
    monkeypatch.setattr(sel, "_backend", lambda: "tpu")
    config.set("knn.pallas_min_items", 1000)
    assert ps.use_fused_count(2000, strategy="auto") is True
    assert ps.use_fused_count(500, strategy="auto") is False
    monkeypatch.setattr(sel, "_backend", lambda: "cpu")
    assert ps.use_fused_count(2000, strategy="auto") is False


# ------------------------------------------------------------------- IVF probe


def test_fused_probe_bitwise():
    from spark_rapids_ml_tpu.ops.ann_streaming import _probe_cells

    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.normal(size=(257, 11)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(19, 11)).astype(np.float32))
    norms = jnp.sum(centers * centers, axis=1)
    ref = np.asarray(_probe_cells(Q, centers, 8, norms))
    fused = np.asarray(ps.fused_probe(Q, centers, 8, center_norms=norms))
    np.testing.assert_array_equal(fused, ref)


def test_streaming_search_identical_under_fused_probe():
    """End-to-end: the paged IVF search with the fused coarse probe returns
    byte-identical results (the probe is exact either way)."""
    from spark_rapids_ml_tpu.ops.ann_streaming import (
        streaming_ivfflat_build, streaming_ivfflat_search,
    )

    rng = np.random.default_rng(5)
    X = rng.normal(size=(3000, 8)).astype(np.float32)
    index = streaming_ivfflat_build(X, nlist=64, max_iter=4, seed=1,
                                    batch_rows=512)
    Q = X[:50]
    d_ref, i_ref = streaming_ivfflat_search(Q, index, k=5, nprobe=8)
    config.set("knn.selection", "pallas_fused")
    # pin the min-items gate low enough that the probe would fuse under auto
    # on TPU; here the EXPLICIT strategy drives it (CPU interpret mode)
    d_f, i_f = streaming_ivfflat_search(Q, index, k=5, nprobe=8)
    config.unset("knn.selection")
    np.testing.assert_array_equal(i_f, i_ref)
    np.testing.assert_array_equal(d_f, d_ref)


# ----------------------------------------------------------------- resolution


def test_resolve_pallas_fused_semantics(monkeypatch):
    # explicit + fusable: sticks (width clear of the small-select degrade)
    assert sel.resolve(4096, 10, "pallas_fused", fusable=True)[0] == "pallas_fused"
    # explicit + NON-fusable (a d2-level select): degrades to exact_full
    assert sel.resolve(4096, 10, "pallas_fused")[0] == "exact_full"
    # small widths degrade like every strategy
    assert sel.resolve(30, 10, "pallas_fused", fusable=True)[0] == "exact_full"
    # auto off-TPU never picks pallas even for fusable sites
    monkeypatch.setattr(sel, "_backend", lambda: "cpu")
    assert sel.resolve(1 << 20, 10, "auto", fusable=True)[0] == "exact_tiled"
    # auto on TPU: fusable sites fuse past the min-items threshold...
    monkeypatch.setattr(sel, "_backend", lambda: "tpu")
    assert sel.resolve(1 << 17, 10, "auto", fusable=True)[0] == "pallas_fused"
    # the auto gate is closed above the largest k Mosaic places on a v5e (the
    # unrolled extraction overflows scoped VMEM at k=64 — autotune/defaults.py);
    # an explicit request still reaches the kernel and fails loudly there
    assert sel.resolve(1 << 17, 32, "auto", fusable=True)[0] == "pallas_fused"
    assert sel.resolve(1 << 17, 33, "auto", fusable=True)[0] == "approx"
    assert sel.resolve(1 << 17, 64, "pallas_fused", fusable=True)[0] == "pallas_fused"
    # ...below it (or at a non-fusable site) auto keeps the PR-5 strategy
    assert sel.resolve(1 << 10, 10, "auto", fusable=True)[0] == "approx"
    assert sel.resolve(1 << 17, 10, "auto")[0] == "approx"
    # the threshold is config-tunable
    config.set("knn.pallas_min_items", 100)
    assert sel.resolve(1 << 10, 10, "auto", fusable=True)[0] == "pallas_fused"


def test_select_topk_accepts_pallas_fused_as_exact():
    """A materialized-d2 select asked for pallas_fused runs exact_full (the
    defensive degrade — bit-exact either way)."""
    rng = np.random.default_rng(0)
    d2 = jnp.asarray(rng.random((6, 500)).astype(np.float32))
    rd, ri = sel.select_topk(d2, 5, strategy="exact_full")
    fd, fi = sel.select_topk(d2, 5, strategy="pallas_fused")
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(rd))


def test_strategies_tuple_and_config_row():
    assert "pallas_fused" in sel.STRATEGIES
    assert config.get("knn.pallas_precision") == "float32"
    assert int(config.get("knn.pallas_min_items")) == 1 << 16
