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


def _load_ref(name="forest_ref"):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "cellbench", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_ref()
reg_ref = _load_ref("forest_reg_ref")


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


@pytest.mark.parametrize("form", ["xla", "grouped"])
def test_every_node_of_a_regression_forest_matches_the_float64_variance_reference(
        form, monkeypatch):
    """A regressor at 2,048 x 24, three bootstrap trees of depth 4, a third of
    the features a node, a real-valued label of mean 400 and spread 1,700
    (`cellbench/forest_reg_ref.py`, float64): every node on every tree has the
    reference's weight exactly, every split the float64 variance gain of the
    recounted [w, w y, w y^2] to 1e-5 of the root's variance, and every node's
    value the recounted mean to 1e-5 of the label's spread: float32 sums.
    `grouped` runs the grouped kernel, interpreted, on "float32x3" lanes."""
    if form == "grouped":
        monkeypatch.setattr(ph, "_on_tpu", lambda: True)
    rng = np.random.default_rng(48)
    X = rng.normal(size=(2048, 24)).astype(np.float32)
    y = 400.0 + 1700.0 * (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] + 0.3 * rng.normal(size=2048))
    seed = 2**31 + 48
    est = RandomForestRegressor(numTrees=3, maxDepth=4, maxBins=32, seed=seed)
    est.num_workers = 1
    model = est.fit(pd.DataFrame({"features": list(X), "label": y}))
    counters = model.fit_report_["metrics"]["counters"]
    operand = "float32x3" if form == "grouped" else "float32"
    assert counters[f"forest.hist_operand{{operand={operand}}}"] == 3
    a = model.get_model_attributes()
    y32 = y.astype(np.float32).astype(np.float64)  # the label as the fit casts it
    for i, w in enumerate(ref.tree_weights(seed, len(y), 3)):
        tree = _tree(a, i)
        sums, reached = reg_ref.node_sums([ref.route(X, tree, t) for t in range(5)],
                                          reg_ref.row_stats(y32, w), tree)
        on_tree = np.flatnonzero(reached)
        np.testing.assert_array_equal(tree["node_weight"][on_tree], sums[on_tree, 0])
        np.testing.assert_allclose(tree["value"][on_tree, 0],
                                   sums[on_tree, 1] / sums[on_tree, 0], rtol=0, atol=1e-5 * 1700)
        inner = on_tree[(tree["feature"][on_tree] >= 0) & ~tree["is_leaf"][on_tree]]
        root_var = reg_ref.impurity_w(sums[1]) / sums[1, 0]
        np.testing.assert_allclose(tree["gain"][inner],
                                   reg_ref.variance_gain(sums[inner], sums[2 * inner]),
                                   rtol=0, atol=1e-5 * root_var)
        assert (tree["feature"][2**3:2**4] >= 0).any()  # grown to the last level


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


def test_the_classifiers_operands_are_the_parents_and_anything_else_is_exact():
    """`_operand`: a classifier's whole-number statistics keep the parent's
    int8 (weights up to 127) and bfloat16 (up to 256); real-valued ones, and
    weights past 256, ride as three bfloat16 parts where the parent had a
    float32 operand."""
    w = np.array([0.0, 3.0, 127.0], np.float32)
    assert trees._operand(True, w) == "int8"
    assert trees._operand(True, np.append(w, 128.0)) == "bfloat16"
    assert trees._operand(True, np.append(w, 256.0)) == "bfloat16"
    assert trees._operand(True, np.append(w, 257.0)) == "float32x3"
    assert trees._operand(True, np.zeros(0, np.float32)) == "int8"
    assert trees._operand(False, w) == "float32x3"


def _kernel_dots(operand, s):
    """The dot_generals of the grouped kernel's body."""
    n, width, nbins, d = 1024, 64, 32, 96
    d_pad, _ = trees.feature_plan(d)
    words = jnp.zeros((d_pad // 4, n), jnp.int32)
    vals = jnp.ones((n, s), jnp.float32)
    node = jnp.zeros((n,), jnp.int32)

    def run(words, node, vals):
        grp = ph.group_rows(node, vals, width, operand)
        return ph.grouped_histogram_tile(words, grp, jnp.int32(0), ph.WORDS_PER_STEP, width,
                                         nbins, ph.lane_stats(operand, s), interpret=True)

    (call,) = [e for e in jax.make_jaxpr(run)(words, node, vals).jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return [e for e in _eqns(call.params["jaxpr"]) if e.primitive.name == "dot_general"]


@pytest.mark.parametrize("operand,s,lhs", [
    ("int8", 2, jnp.int8), ("bfloat16", 2, jnp.bfloat16), ("float32x3", 3, jnp.bfloat16),
])
def test_the_grouped_kernel_takes_one_dot_a_feature(operand, s, lhs):
    """Four features a packed word, `WORDS_PER_STEP` words a step: one
    (bins, rows) x (rows, lanes) dot each, the classifier's int8 and bfloat16
    lanes as before and the regressor's three parts a statistic in the same
    one dot (they take lanes, not passes)."""
    dots = _kernel_dots(operand, s)
    assert len(dots) == 4 * ph.WORDS_PER_STEP
    assert {e.invars[1].aval.dtype for e in dots} == {jnp.dtype(lhs)}
    assert {e.params["preferred_element_type"] for e in dots} == {
        jnp.dtype(jnp.int32 if operand == "int8" else jnp.float32)}


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


EDGE_CASES = [
    (3000, 12, 32, 200_000, np.float32), (5000, 300, 128, 1000, np.float32),
    (777, 5, 256, 200_000, np.float32), (4001, 7, 64, 200_000, np.float64),
    (2, 3, 4, 200_000, np.float32),
    (1500, 13, 128, 1000, np.float32),  # every special value, sampled
]


def _edge_case(n, d, bins, limit, dtype):
    """A table with columns of three scales and, in two cases, NaN, both
    infinities, a constant column and -0.0 beside 0.0; and `np.quantile` of
    the seed's row sample, the call the edges replace."""
    rng = np.random.default_rng(n)
    X = (rng.normal(size=(n, d)) * rng.choice([1e-3, 1.0, 1e4], size=d)).astype(dtype)
    if n == 777:
        X[5, 2], X[7, 4], X[:, 3] = np.nan, np.inf, 1.0
    if n == 1500:
        X[rng.integers(0, n, 40), 0] = np.nan
        X[rng.integers(0, n, 5), 0] = -np.nan  # the sign bit set: still last
        X[rng.integers(0, n, 300), 1] = np.inf
        X[rng.integers(0, n, 300), 1] = -np.inf
        X[:, 2] = -3.5
        X[:, 4] = np.where(rng.random(n) < 0.5, -0.0, 0.0).astype(dtype)
        X[: n // 3, 5] = 0.0
        X[n // 3: n // 2, 5] = -0.0
    sample = X[np.random.default_rng(9).choice(n, limit, replace=False)] if n > limit else X
    want = np.quantile(sample, np.linspace(0, 1, bins + 1)[1:-1], axis=0).T.astype(np.float32)
    return X, want


@pytest.mark.parametrize("n,d,bins,limit,dtype", EDGE_CASES)
def test_bin_edges_are_numpys_quantiles_to_the_bit(n, d, bins, limit, dtype):
    """The sorted-column form of the host quantiles (thirty times faster at
    200,000 x 3000) against the call it replaces, a row sample, NaN, inf and a
    constant column included."""
    X, want = _edge_case(n, d, bins, limit, dtype)
    got = trees.quantile_bin_edges(X, bins, sample_limit=limit, seed=9)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,d,bins,limit,dtype", EDGE_CASES)
def test_device_edges_are_numpys_quantiles_to_the_bit(n, d, bins, limit, dtype, monkeypatch):
    """The sample's columns sorted on the device (the gate bypassed), the order
    statistics numpy reads fetched and lerped on the host: `np.quantile`
    again, from a placed table whose rows are padded as `shard_fn` pads them,
    in column blocks that 3, 5, 7, 12, 13 and 300 columns do not fill."""
    monkeypatch.setattr(trees, "FOREST_EDGE_BLOCK_COLS", 4)
    X, want = _edge_case(n, d, bins, limit, dtype)
    with jax.enable_x64(X.dtype == np.float64):  # a float64 table placed as float64
        X_dev = jnp.pad(jnp.asarray(X), ((0, 3), (0, 0)), constant_values=np.nan)
        assert X_dev.dtype == X.dtype
        got = trees.device_quantile_bin_edges(X_dev, n, bins, sample_limit=limit, seed=9)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, trees.quantile_bin_edges(X, bins, sample_limit=limit, seed=9))


def test_the_edges_gate_reads_what_the_fit_was_handed(monkeypatch):
    """Its five verdicts, in order: no placed table, rows over several devices,
    another dtype on the device than on the host, not a TPU, else the device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    X = np.zeros((16, 3), np.float32)
    placed = jnp.asarray(X)
    sharded = jax.device_put(X, NamedSharding(Mesh(np.array(jax.devices()[:2]), ("rows",)),
                                              PartitionSpec("rows")))
    monkeypatch.setattr(trees, "_on_tpu", lambda: True)
    assert trees.edges_gate(None, X) == (False, "source")
    assert trees.edges_gate(X, X) == (False, "source")  # a host array is no placed table
    assert trees.edges_gate(sharded, X) == (False, "devices")
    assert trees.edges_gate(placed, X.astype(np.float64)) == (False, "dtype")
    assert trees.edges_gate(placed, X) == (True, "ok")
    monkeypatch.setattr(trees, "_on_tpu", lambda: False)
    assert trees.edges_gate(placed, X) == (False, "platform")


def _edge_counters(model):
    counters = model.fit_report_["metrics"]["counters"]
    return {k: v for k, v in counters.items() if k.startswith("forest.edges_")}


def test_a_fit_with_device_edges_is_the_host_forms_to_the_byte(monkeypatch):
    """The public path with the platform test saying TPU: the edges come from
    the placed table, and the edges and every tree array are the host form's
    byte for byte."""
    X, y = _table(600, 7, 11)
    X[3, 2], X[4, 5] = np.nan, -np.inf
    params = dict(numTrees=3, maxDepth=4, maxBins=32, seed=13)
    host = _fit(X, y, **params)
    assert _edge_counters(host) == {"forest.edges_gate{device=0,reason=platform}": 1,
                                    "forest.edges_path{path=host}": 1}
    monkeypatch.setattr(trees, "_on_tpu", lambda: True)
    device = _fit(X, y, **params)
    assert _edge_counters(device) == {"forest.edges_gate{device=1,reason=ok}": 1,
                                      "forest.edges_path{path=device}": 1}
    a, b = host.get_model_attributes(), device.get_model_attributes()
    for k in ("bin_edges", "feature", "threshold", "is_leaf", "value", "gain", "node_weight"):
        want, got = np.asarray(a[k]), np.asarray(b[k])
        assert want.dtype == got.dtype and want.tobytes() == got.tobytes(), k


def test_each_fit_makes_its_own_tables_edges(monkeypatch):
    """Two tables of one shape fitted back to back in one process with the
    same seed: each model holds its own table's edges; nothing is kept from
    the fit before."""
    monkeypatch.setattr(trees, "_on_tpu", lambda: True)
    params = dict(numTrees=1, maxDepth=2, maxBins=16, seed=3)
    for table_seed in (21, 22):
        X, y = _table(500, 6, table_seed)
        model = _fit(X, y, **params)
        assert _edge_counters(model)["forest.edges_path{path=device}"] == 1
        np.testing.assert_array_equal(np.asarray(model.get_model_attributes()["bin_edges"]),
                                      trees.quantile_bin_edges(X, 16, seed=3))
    first, _ = _table(500, 6, 21)
    assert not np.array_equal(trees.quantile_bin_edges(first, 16, seed=3),
                              np.asarray(model.get_model_attributes()["bin_edges"]))


# ------------------------------------- (e) the level step at the published widths


def _avals(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


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
             S((n,), jnp.int32), S((width, s), jnp.float32), S((2,), jnp.uint32),
             S((), jnp.int32))

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
    assert "cond" not in {e.primitive.name for e in jaxpr.jaxpr.eqns}  # 4096 x 54 >= 3000

    # width 32: 32 x 54 = 1,728 columns at most, padded to 2,048: the level's
    # tiles read a narrow matrix of 2,048 columns (two tiles of 1,024), and only
    # an overflowing union reads all 3,072 (three)
    t, narrow = 5, trees.feature_plan(32 * 54)[0]
    assert trees.compact_gate(2**t, d, 54) == (True, "ok") and (narrow, d_pad) == (2048, 3072)
    state = state[:7] + (S((2**t, s), jnp.float32),) + state[8:]
    jaxpr = jax.make_jaxpr(step)(state, S((n, d_pad // 4), jnp.int32), S((n, s), jnp.float32),
                                 S((d, nbins - 1), jnp.float32))
    (cond,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond"]
    whole_branch, narrow_branch = cond.params["branches"]  # index 1: the union fits
    f = trees.level_tile(2**t, 32 * 54, nbins, s)
    for branch, cols in ((narrow_branch, narrow), (whole_branch, d_pad)):
        read = {a.shape for e in _eqns(branch.jaxpr) if e.primitive.name == "pallas_call"
                for a in (v.aval for v in e.invars) if a.shape[-1:] == (n,)}
        assert read == {(cols // 4, n)}, (cols, read)
        (loop,) = [e for e in _eqns(branch.jaxpr) if e.primitive.name == "scan" and any(
            x.primitive.name == "pallas_call" for x in _eqns(e.params["jaxpr"].jaxpr))]
        assert loop.params["length"] == cols // f - 1  # the tiles after the first


# ------------------------------------ (e2) a level narrowed to the drawn columns


@pytest.mark.parametrize("strategy,cls,engaged", [
    ("all", True, 0), ("onethird", True, 2), ("sqrt", True, 6), ("auto", True, 6),
    ("auto", False, 2),
])
def test_the_narrowing_gate_reads_shapes_alone(strategy, cls, engaged):
    """At 3000 columns and depth 13: every node may split on every column
    under `all` (`all_features`); else a level narrows while its width times
    the features a node draws stays under the columns, and the deeper levels
    can draw them all (`width`): 54 a node (sqrt, and auto for a classifier)
    at widths 1 to 32, 1000 (onethird, and auto for a regressor) at 1 and 2."""
    k = trees.resolve_feature_subset(strategy, 3000, cls)
    got = [trees.compact_gate(2**t, 3000, k) for t in range(13)]
    if strategy == "all":
        assert got == [(False, "all_features")] * 13
    else:
        assert got == [(True, "ok")] * engaged + [(False, "width")] * (13 - engaged)


def _level_inputs(form, n=3000, d=37, nbins=32, t=1, seed=23):
    rng = np.random.default_rng(seed)
    width, slots, s = 2**t, 2**(t + 2), 2
    d_pad, _ = trees.feature_plan(d)
    Xb = jnp.pad(jnp.asarray(rng.integers(0, nbins, size=(n, d)).astype(np.uint8)),
                 ((0, 0), (0, d_pad - d)))
    if form == "grouped":
        Xb = trees._pack_words(Xb)
    node = jnp.asarray(rng.integers(0, width, n).astype(np.int32))
    values = jnp.asarray(rng.poisson(1.0, (n, s)).astype(np.float32))
    T = jax.ops.segment_sum(values, node, num_segments=width)
    state = (jnp.full((slots,), -1, jnp.int32), jnp.zeros((slots,), jnp.float32),
             jnp.zeros((slots,), bool), jnp.zeros((slots, s), jnp.float32),
             jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.float32),
             node, T, jax.random.PRNGKey(seed), jnp.zeros((), jnp.int32))
    edges = jnp.asarray(np.sort(rng.normal(size=(d, nbins - 1)), axis=1).astype(np.float32))
    return state, Xb, values, edges


@pytest.mark.parametrize("form", ["xla", "grouped"])
@pytest.mark.parametrize("tied,empty,overflows", [(20, False, 0), (20, True, 0), (37, False, 1)])
def test_a_level_whose_draws_tie_splits_as_the_whole_width_step(form, tied, empty, overflows,
                                                                 monkeypatch):
    """Float ties at the k-th score admit more than k features to a node. Two
    nodes drawing 6 of 37 narrow to `feature_plan(12)` = 32 columns: with the
    last 20 features tied at the top every node allows all 20, a union past
    2 x 6 that the 32 columns still hold; with all 37 tied the union overflows
    them, the level runs whole-width and counts one overflow. Either way every
    output is the whole-width step's on the same draw (the gate turned off),
    an empty node's too: a leaf with no candidate, whose threshold is feature
    0's first edge though feature 0 was not drawn."""
    state, Xb, values, edges = _level_inputs(form)
    if empty:  # every row at node 0: node 1 holds none
        state = state[:6] + (jnp.zeros_like(state[6]), jnp.stack([values.sum(0), 0 * values[0]])) \
            + state[8:]
    top = jnp.arange(37) >= 37 - tied
    monkeypatch.setattr(jax.random, "uniform",
                        lambda key, shape: jnp.broadcast_to(jnp.where(top, 1.0, 0.5), shape))
    kw = dict(t=1, nbins=32, impurity="gini", k_features=6, min_instances=1,
              min_info_gain=0.0, mesh=None, form=form, operand="float32",
              packed=form == "grouped")
    got = trees._level_step(state, Xb, values, edges, **kw)
    assert int(got[9]) == overflows
    monkeypatch.setattr(trees, "compact_gate", lambda *a: (False, "width"))
    want = trees._level_step(state, Xb, values, edges, **kw)
    assert int(want[9]) == 0
    assert (np.asarray(got[0])[2:4] >= 0).tolist() == [True, not empty]
    if empty:
        assert np.asarray(got[1])[3] == np.asarray(edges)[0, 0]
    for i in range(9):
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want[i]), err_msg=str(i))


def test_a_narrowed_tree_on_eight_devices_is_the_one_devices():
    """Rows sharded over eight devices take the one-hot kernel a shard with a
    psum, on the fit's packed words; the levels of one to four nodes narrow
    (6 of 37 features a node) through the words' transpose: every heap array
    is the single device's segment_sum tree's."""
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_array

    rng = np.random.default_rng(29)
    n, d, nbins, depth = 4096, 37, 16, 5
    Xb = rng.integers(0, nbins, size=(n, d)).astype(np.uint8)
    y = ((Xb[:, 0] > 5) ^ (Xb[:, 9] > 10) ^ (rng.random(n) < 0.2)).astype(int)
    vals = np.zeros((n, 2), np.float32)
    vals[np.arange(n), y] = rng.poisson(1.0, n)
    edges = jnp.asarray(np.sort(rng.normal(size=(d, nbins - 1)), axis=1).astype(np.float32))
    kw = dict(max_depth=depth, nbins=nbins, impurity="gini", k_features=6, min_instances=1,
              min_info_gain=0.0)
    assert [trees.compact_gate(2**t, d, 6)[0] for t in range(depth)] == [True] * 3 + [False] * 2
    want = trees.build_tree(jnp.asarray(Xb), jnp.asarray(vals), edges, jax.random.PRNGKey(5),
                            forms=("xla",) * depth, **kw)
    mesh = get_mesh()
    d_pad, _ = trees.feature_plan(d)
    words = np.asarray(trees._pack_words(jnp.pad(jnp.asarray(Xb), ((0, 0), (0, d_pad - d)))))
    got = trees.build_tree(shard_array(words, mesh), shard_array(vals, mesh), edges,
                           jax.random.PRNGKey(5), use_pallas=True, mesh=mesh,
                           forms=("direct",) * depth, packed=True, **kw)
    assert mesh.devices.size == 8
    for k in want:
        np.testing.assert_array_equal(np.asarray(want[k]), np.asarray(got[k]), err_msg=k)


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
    ("classifier_grouped_d300", "69025c90664a43f9"),
])
def test_seeded_trees_are_the_parents(case, want, monkeypatch):
    """Every node on every tree (feature, threshold, value, gain, weight, to the
    bit) as commit 6a31db5 grew it for the same seed: the tiled split search,
    the device binning and the new routing change no tree. The 300-column case
    (recorded at commit d610c1f, before a level histogrammed only the columns
    its nodes drew) takes the grouped kernel, interpreted, at every level, and
    draws 17 of 300 features a node: four of its seven levels narrow."""
    if case == "classifier_grouped_d300":
        monkeypatch.setattr(ph, "_on_tpu", lambda: True)
        X = np.random.default_rng(7).normal(size=(3000, 300)).astype(np.float32)
        y = (X[:, 0] * X[:, 1] + X[:, 150] - 0.5 * np.abs(X[:, 299]) > 0.1)
        est = RandomForestClassifier(numTrees=2, maxDepth=7, maxBins=32, seed=19)
        est.num_workers = 1
        model = est.fit(pd.DataFrame({"features": list(X), "label": y.astype(np.float64)}))
        counters = model.fit_report_["metrics"]["counters"]
        assert counters["forest.hist_path{path=grouped}"] == 2 * 7
        assert counters["forest.hist_compact{engaged=true,reason=ok}"] == 2 * 4
        assert counters["forest.hist_compact{engaged=false,reason=width}"] == 2 * 3
        assert _digest(model) == want
        return
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
             S((n,), jnp.int32), S((2**t, s), jnp.float32), S((2,), jnp.uint32),
             S((), jnp.int32))
    compiled = trees._level_step_jit.lower(
        state, S((n, d_pad // 4), jnp.int32), S((n, s), jnp.float32),
        S((d, nbins - 1), jnp.float32), t=t, nbins=nbins, impurity="gini", k_features=54,
        min_instances=1, min_info_gain=0.0, mesh=None, form=form, operand="bfloat16",
        packed=True).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("form,t", [("grouped", 5), ("direct", 4), ("direct", 10)])
def test_a_regressors_level_compiles_for_a_v5e_on_three_part_lanes(form, t, one_chip,
                                                                    monkeypatch):
    """The regressor's [w, w y, w y^2] as "float32x3" (nine bfloat16 lanes a
    node), 128 bins, 3000 columns, 1000 drawn a node: Mosaic takes both
    kernels' blocks, the one-hot kernel's past 256 nodes too, where its node
    tile narrows so that nine lanes a bin fit its output block."""
    monkeypatch.setattr(ph, "_interpret", lambda: False)
    n, d, nbins, s = 16384, 3000, 128, 3
    d_pad, _ = trees.feature_plan(d)
    slots = 2**11

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (S((slots,), jnp.int32), S((slots,), jnp.float32), S((slots,), jnp.bool_),
             S((slots, 1), jnp.float32), S((slots,), jnp.float32), S((slots,), jnp.float32),
             S((n,), jnp.int32), S((2**t, s), jnp.float32), S((2,), jnp.uint32),
             S((), jnp.int32))
    compiled = trees._level_step_jit.lower(
        state, S((n, d_pad // 4), jnp.int32), S((n, s), jnp.float32),
        S((d, nbins - 1), jnp.float32), t=t, nbins=nbins, impurity="variance",
        k_features=1000, min_instances=1, min_info_gain=0.0, mesh=None, form=form,
        operand="float32x3", packed=True).compile()
    assert "tpu_custom_call" in compiled.as_text()
