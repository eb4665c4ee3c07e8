"""The forest builder against a plain reference (`cellbench/forest_ref.py`:
numpy, float64, nothing of the program imported; loaded by path, tier-1 imports
no `cellbench`): exact splits, every node's counts and gain with the row
weights drawn again, the grouped histogram against a numpy histogram, the
device binning against `searchsorted`, the level step's shapes at the
published widths, and the seeded trees of the parent commit."""

import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu.classification import RandomForestClassifier
from spark_rapids_ml_tpu.ops import pallas_histogram as ph
from spark_rapids_ml_tpu.ops import trees
from spark_rapids_ml_tpu.regression import RandomForestRegressor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_ref():
    spec = importlib.util.spec_from_file_location(
        "forest_ref", os.path.join(ROOT, "cellbench", "forest_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_ref()


def _table(n, d, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    z = X[:, 0] * X[:, 1] + 0.8 * np.abs(X[:, 2]) - 0.6 + 0.5 * X[:, 3 % d]
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-2.0 * z))).astype(np.float64)
    return X, y


def _fit(X, y, **params):
    est = RandomForestClassifier(**params)
    est.num_workers = 1
    return est.fit(pd.DataFrame({"features": list(X), "label": y}))


def _tree(attrs, i):
    return {k: np.asarray(attrs[k])[i] for k in
            ("feature", "threshold", "is_leaf", "value", "gain", "node_weight")}


# ------------------------------------------------------------ (a) one exact step


def test_every_split_of_an_exact_tree_is_the_references_best():
    """featureSubsetStrategy=all, no bootstrap, depth 3, at a width (37) that
    is no multiple of the feature tile: each of the seven splits is the float64
    best over every (feature, bin) of the node's rows on the same bins."""
    X, y = _table(6000, 37, seed=1)
    model = _fit(X, y, numTrees=1, maxDepth=3, maxBins=32, bootstrap=False,
                 featureSubsetStrategy="all", seed=4)
    a = model.get_model_attributes()
    tree, edges = _tree(a, 0), np.asarray(a["bin_edges"])
    bins = ref.bin_table(X, edges)
    splits = 0
    for level in range(3):
        pos = ref.route(X, tree, level)
        for p in range(2**level, 2 ** (level + 1)):
            rows = np.flatnonzero(pos == p)
            if tree["is_leaf"][p] or tree["feature"][p] < 0 or not rows.size:
                continue
            best, f, b, gains = ref.best_split(bins[rows], y[rows], np.ones(rows.size), 32, 2)
            chosen_f = int(tree["feature"][p])
            chosen_b = int(np.searchsorted(edges[chosen_f], tree["threshold"][p], side="left"))
            assert gains[chosen_f, chosen_b] >= best * (1 - 1e-6)  # a float32 tie either way
            assert tree["gain"][p] == pytest.approx(best, rel=1e-5)
            splits += (chosen_f, chosen_b) == (f, b)
    assert splits >= 6  # and the very candidate, but for a tie


# ------------------------------------------------- (b) every node of a forest


def test_every_node_of_a_bootstrap_forest_matches_the_reference_routing():
    """Depth 8, the feature draw (6 of 40 a node), bootstrap weights: with the
    weights drawn again by the stated rule the reference routes every row down
    each fitted tree; every node on the tree has the reference's weight and
    class counts exactly, and every split its float64 gain."""
    X, y = _table(20000, 40, seed=2)
    seed = 2**31 + 77  # past 31 bits: the rule masks it
    model = _fit(X, y, numTrees=3, maxDepth=8, maxBins=64, seed=seed)
    a = model.get_model_attributes()
    nodes = 0
    for i, w in enumerate(ref.tree_weights(seed, len(y), 3)):
        tree = _tree(a, i)
        counts, reached = ref.node_counts(X, y, w, tree, 2)
        on_tree = np.flatnonzero(reached)
        np.testing.assert_array_equal(tree["node_weight"][on_tree], counts[on_tree].sum(axis=1))
        np.testing.assert_allclose(
            tree["value"][on_tree] * tree["node_weight"][on_tree, None], counts[on_tree],
            atol=1e-3)
        inner = on_tree[(tree["feature"][on_tree] >= 0) & ~tree["is_leaf"][on_tree]]
        np.testing.assert_allclose(tree["gain"][inner],
                                   ref.gini_gain(counts[inner], counts[2 * inner]), atol=2e-7)
        assert (tree["feature"][2**7:2**8] >= 0).any()  # grown to the last level
        nodes += on_tree.size
    assert nodes > 600


# ------------------------------------------------------ (c) grouped histogram


@pytest.mark.parametrize("width,n,d,nbins,s,operand", [
    (1, 700, 40, 16, 2, jnp.float32),
    (16, 1301, 40, 128, 2, jnp.bfloat16),
    (512, 3001, 33, 32, 2, jnp.int8),
    (4096, 5003, 8, 16, 3, jnp.float32),
])
def test_grouped_histogram_equals_a_plain_histogram(width, n, d, nbins, s, operand):
    """Uneven and EMPTY nodes (two thirds hold no row), a ragged last row block
    (n is no multiple of 512), rows of weight 0; the kernel interpreted."""
    rng = np.random.default_rng(width)
    Xb = rng.integers(0, nbins, size=(n, d)).astype(np.uint8)
    live = rng.choice(width, size=max(1, width // 3), replace=False)
    node = rng.choice(live, size=n, p=rng.dirichlet(np.ones(live.size))).astype(np.int32)
    vals = (rng.integers(0, 5, size=(n, s)) * (rng.random((n, 1)) < 0.8)).astype(np.float32)
    d_pad, _ = trees.feature_plan(d)

    @jax.jit
    def run(Xb, node, vals):
        words = trees._pack_words(jnp.pad(Xb, ((0, 0), (0, d_pad - d))))
        grp = ph.group_rows(node, vals, width, operand)
        if grp["order"] is not None:
            words = jnp.take(words, grp["order"], axis=0)
        return ph.grouped_histogram_tile(words.T, grp, jnp.int32(0), d_pad // 4, width,
                                         nbins, s, interpret=True)

    h = np.stack([np.asarray(part) for part in run(Xb, node, vals)])  # (s, F, A, nbins, C)
    feat = np.asarray(trees.tile_features(True, 0, d_pad, d_pad))
    got = h.transpose(2, 4, 1, 3, 0).reshape(-1, d_pad, nbins, s)[:width]
    want = np.zeros((width, d, nbins, s))
    for j in range(d):
        np.add.at(want, (node, j, Xb[:, j]), vals)
    # the kernel's bins are running sums: entry b counts the ids <= b
    np.testing.assert_array_equal(got[:, np.argsort(feat)][:, :d], np.cumsum(want, axis=2))


def test_a_whole_tree_is_the_same_in_every_histogram_form():
    rng = np.random.default_rng(3)
    n, d, nbins = 5000, 37, 32
    Xb = rng.integers(0, nbins, size=(n, d)).astype(np.uint8)
    y = ((Xb[:, 0] > 10) ^ (Xb[:, 5] > 20) ^ (rng.random(n) < 0.2)).astype(int)
    vals = np.zeros((n, 2), np.float32)
    vals[np.arange(n), y] = rng.poisson(1.0, n)
    edges = jnp.asarray(np.sort(rng.normal(size=(d, nbins - 1)), axis=1).astype(np.float32))
    kw = dict(max_depth=8, nbins=nbins, impurity="gini", k_features=6, min_instances=1,
              min_info_gain=0.0)
    args = (jnp.asarray(Xb), jnp.asarray(vals), edges, jax.random.PRNGKey(3))
    want = trees.build_tree(*args, forms=("xla",) * 8, **kw)
    mixed = trees.build_tree(*args, forms=("direct",) * 3 + ("grouped",) * 5,
                             operand="bfloat16", **kw)
    for k in want:
        np.testing.assert_array_equal(np.asarray(want[k]), np.asarray(mixed[k]), err_msg=k)


# ----------------------------------------------------------- (d) device binning


def test_device_binning_is_searchsorted_left_to_the_bit():
    """Values on an edge, NaN and both infinities included; four one-byte ids
    a word, and nothing wider, is what the device keeps."""
    from spark_rapids_ml_tpu.native import bin_features

    rng = np.random.default_rng(5)
    n, d, nbins = 4000, 37, 128
    X = rng.normal(size=(n, d)).astype(np.float32)
    edges = trees.quantile_bin_edges(X, nbins)
    X[rng.integers(0, n, 600), rng.integers(0, d, 600)] = edges[
        rng.integers(0, d, 600), rng.integers(0, nbins - 1, 600)]  # some land on their column's
    for j in range(d):
        X[j, j] = edges[j, (3 * j) % (nbins - 1)]  # exactly on an edge of the same column
    X[100:110, 3] = np.nan
    X[200:205, 7] = np.inf
    X[300:305, 9] = -np.inf
    d_pad, _ = trees.feature_plan(d)
    words = trees.bin_features_device(jnp.asarray(X), jnp.asarray(edges), d_pad=d_pad)
    assert words.dtype == jnp.int32 and words.shape == (n, d_pad // 4)
    got = trees.unpack_bins(np.asarray(words), d)
    want = np.stack([np.searchsorted(edges[j], X[:, j], side="left") for j in range(d)], axis=1)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, bin_features(X, edges))
    assert got[100, 3] == nbins - 1 and got[200, 7] == nbins - 1 and got[300, 9] == 0
    assert got[5, 5] == (3 * 5) % (nbins - 1)  # equal to the edge: in the edge's bin


@pytest.mark.parametrize("n,d,bins,limit,dtype", [
    (3000, 12, 32, 200_000, np.float32), (5000, 300, 128, 1000, np.float32),
    (777, 5, 256, 200_000, np.float32), (4001, 7, 64, 200_000, np.float64),
    (2, 3, 4, 200_000, np.float32),
])
def test_bin_edges_are_numpys_quantiles_to_the_bit(n, d, bins, limit, dtype):
    """The sorted-column form of the host quantiles (thirty times faster at
    200,000 x 3000) against the call it replaces, a row sample, NaN, inf and a
    constant column included."""
    rng = np.random.default_rng(n)
    X = (rng.normal(size=(n, d)) * rng.choice([1e-3, 1.0, 1e4], size=d)).astype(dtype)
    if n == 777:
        X[5, 2], X[7, 4], X[:, 3] = np.nan, np.inf, 1.0
    sample = X[np.random.default_rng(9).choice(n, limit, replace=False)] if n > limit else X
    want = np.quantile(sample, np.linspace(0, 1, bins + 1)[1:-1], axis=0).T.astype(np.float32)
    got = trees.quantile_bin_edges(X, bins, sample_limit=limit, seed=9)
    np.testing.assert_array_equal(got, want)


# ------------------------------------- (e) the level step at the published widths


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_level_step_at_published_widths_holds_no_whole_level_histogram(monkeypatch):
    """Width 4096, 3000 columns, 128 bins, 2 classes, 357,376 rows, from shapes
    alone: no intermediate of the traced level step has width*d*nbins*s
    elements (the largest is a feature tile of it), and the gate names the
    grouped form there. ISSUE 38 expected the one-hot kernel to keep the levels
    of 16 nodes or fewer; on the chip it takes 1.56 s a level at ONE node where
    the grouped kernel takes 0.224 s (PERF.md section 6 PR 38), so the gate
    keeps it only for what the grouped form cannot take."""
    assert ph.hist_gate(4096, 3000, 128, 2, 357376) == (False, "platform")
    monkeypatch.setattr(ph, "_on_tpu", lambda: True)
    assert ph.hist_gate(4096, 3000, 128, 2, 357376) == (True, "ok")
    assert ph.hist_gate(32, 3000, 128, 2, 357376) == (True, "ok")
    assert ph.hist_gate(1, 3000, 128, 2, 357376) == (True, "ok")
    assert ph.hist_gate(4096, 3000, 128, 2, 357376, devices=4) == (False, "devices")
    assert ph.hist_gate(4096, 3000, 512, 2, 357376) == (False, "bins")
    forms = trees.level_forms(13, 357376, 3000, 128, 2, True)
    assert forms == ("grouped",) * 13
    assert trees.level_forms(3, 357376, 3000, 128, 2, True, devices=4) == ("direct",) * 3

    n, d, nbins, s, t = 357376, 3000, 128, 2, 12
    width, slots = 2**t, 2**14
    d_pad, _ = trees.feature_plan(d)
    S = jax.ShapeDtypeStruct
    state = (S((slots,), jnp.int32), S((slots,), jnp.float32), S((slots,), jnp.bool_),
             S((slots, s), jnp.float32), S((slots,), jnp.float32), S((slots,), jnp.float32),
             S((n,), jnp.int32), S((width, s), jnp.float32), S((2,), jnp.uint32))

    def step(state, words, values, edges):
        return trees._level_step(state, words, values, edges, t, nbins, "gini", 54, 1, 0.0,
                                 None, form="grouped", operand="bfloat16", packed=True)

    jaxpr = jax.make_jaxpr(step)(state, S((n, d_pad // 4), jnp.int32), S((n, s), jnp.float32),
                                 S((d, nbins - 1), jnp.float32))
    whole = width * d * nbins * s
    largest = max(int(np.prod(a.shape)) for a in _avals(jaxpr.jaxpr) if hasattr(a, "shape"))
    # the largest thing a level holds is the bin matrix's own words (moved when
    # the rows are grouped): an eleventh of a whole level histogram here
    assert largest == n * (d_pad // 4) and largest * 8 <= whole, (largest, whole)


# ------------------------------------------------ (f) the parent's seeded trees


def _digest(model):
    a = model.get_model_attributes()
    h = hashlib.sha256()
    for i in range(a["feature"].shape[0]):
        on_tree = sorted(model._reachable_slots(i))
        for k in ("feature", "threshold", "is_leaf", "value", "gain", "node_weight"):
            h.update(np.ascontiguousarray(np.asarray(a[k])[i][on_tree]).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("case,want", [
    ("classifier", "58cbf9e1a2364a3b"),
    ("classifier_all_features", "aacab197b3680254"),
    ("regressor", "a4a331367f0fd342"),
])
def test_seeded_trees_are_the_parents(case, want):
    """Every node on every tree (feature, threshold, value, gain, weight, to the
    bit) as commit 6a31db5 grew it for the same seed: the tiled split search,
    the device binning and the new routing change no tree."""
    X = np.random.default_rng(7).normal(size=(3000, 12)).astype(np.float32)
    frame = {"features": list(X)}
    if case == "regressor":
        frame["label"] = (X[:, 0] * 2 + np.sin(X[:, 1])).astype(np.float64)
        est = RandomForestRegressor(numTrees=2, maxDepth=4, maxBins=16, seed=3)
    else:
        frame["label"] = (X[:, 0] * X[:, 1] + X[:, 2] > 0.2).astype(np.float64)
        est = (RandomForestClassifier(numTrees=3, maxDepth=6, maxBins=32, seed=11)
               if case == "classifier" else
               RandomForestClassifier(numTrees=2, maxDepth=5, maxBins=16, seed=5,
                                      featureSubsetStrategy="all", bootstrap=False))
    est.num_workers = 1
    assert _digest(est.fit(pd.DataFrame(frame))) == want


# ------------------------------- the two Pallas forms compiled for a described v5e


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described, not attached (the TPU's compiler is
    installed where the tests run); skipped where it cannot be described."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("form,t", [("direct", 4), ("grouped", 12)])
def test_level_step_compiles_for_a_v5e_at_the_published_lanes(form, t, one_chip, monkeypatch):
    """128 bins x 2 classes, 3000 columns (rows cut: a block's shape does not
    depend on them): Mosaic takes both kernels' blocks and their scoped VMEM.
    The one-hot kernel as the parent had it did neither at these lanes (a
    12-feature block, then 16.49 MiB of scoped VMEM at 512 rows a block)."""
    monkeypatch.setattr(ph, "_interpret", lambda: False)
    n, d, nbins, s = 16384, 3000, 128, 2
    d_pad, _ = trees.feature_plan(d)
    slots = 2**14

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (S((slots,), jnp.int32), S((slots,), jnp.float32), S((slots,), jnp.bool_),
             S((slots, s), jnp.float32), S((slots,), jnp.float32), S((slots,), jnp.float32),
             S((n,), jnp.int32), S((2**t, s), jnp.float32), S((2,), jnp.uint32))
    compiled = trees._level_step_jit.lower(
        state, S((n, d_pad // 4), jnp.int32), S((n, s), jnp.float32),
        S((d, nbins - 1), jnp.float32), t=t, nbins=nbins, impurity="gini", k_features=54,
        min_instances=1, min_info_gain=0.0, mesh=None, form=form, operand="bfloat16",
        packed=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
