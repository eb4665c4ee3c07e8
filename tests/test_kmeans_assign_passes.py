"""Lloyd's assignment ranked at three MXU passes with a six-pass second look
(`ops/kmeans.py::_rank3`, `_assign3`; docs/design.md §6d) where the fit is
euclidean, at six-pass parity, without `fast_math`, and has 128 centres or
more on centres x columns of 524,288 or more; six passes whole everywhere else. The CPU computes float32 exactly
under either precision, so what is checked here is which precision the
lowered program asks for on which dot, that the labels are the six-pass
labels with the ranking matmul EMULATED at three passes (float32 cut into
bf16 slices as the chip cuts it), that the bound holds for that emulation, what the overflow
branch does, and what the counters say. Nothing here is a speed."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import config
from spark_rapids_ml_tpu.clustering import KMeans
from spark_rapids_ml_tpu.observability import collective_summary
from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops
from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit
from tools.lloyd_assign_bench import cross3_split

_DOT_PRECISION = re.compile(r"dot_general.*?precision = \[(\w+), (\w+)\]")


@pytest.fixture
def settings(request):
    """Config keys for one test; both are read while tracing, and `lower()`
    answers from jit's trace cache where it can, so that cache is dropped."""
    for key, value in request.param.items():
        config.set(key, value)
    jax.clear_caches()
    try:
        yield request.param
    finally:
        for key in request.param:
            config.unset(key)
        jax.clear_caches()


@pytest.fixture
def three_passes(monkeypatch):
    """The ranking matmul as the chip runs it: three bf16 passes. Functions
    that call `_cross3` have to trace after this (a fresh `jax.jit`)."""
    monkeypatch.setattr(kmeans_ops, "_cross3", cross3_split)


def _routed(monkeypatch, rows, cols, k, cosine=False, seed=0):
    """What `_lloyd` hands `lloyd_fit` for a fit of this shape: (args, kwargs).
    The fit itself is not run: the lowered text is what is read."""
    seen = []

    def spy(X, w, init, *args, **kwargs):
        seen.append(((X, w, init, *args), kwargs))
        return init, jnp.zeros(()), jnp.zeros((), jnp.int32), jnp.zeros((1, 2), jnp.int32)

    monkeypatch.setattr(kmeans_ops, "lloyd_fit", spy)
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, cols)).astype(np.float32)
    if cosine:
        X /= np.linalg.norm(X, axis=1, keepdims=True)
    Xj = jnp.asarray(X)
    kmeans_ops._lloyd(Xj, jnp.ones(rows, jnp.float32), Xj[:k], k, 3, 0.0, cosine, True)
    (args, kwargs), = seen
    return args, kwargs


def _dot_precisions(args, kwargs):
    return sorted(_DOT_PRECISION.findall(lloyd_fit.lower(*args, **kwargs).as_text()))


# ------------------------------------- (a) which dot asks for which precision


WIDE = (256, 4096, 128)  # rows, columns, centres: 128 x 4096 = 524,288


def test_the_ranking_dot_asks_for_three_passes_and_the_second_look_for_six(monkeypatch):
    args, kwargs = _routed(monkeypatch, *WIDE)
    assert kwargs["recheck"] == 256 // kmeans_ops.LLOYD_RECHECK_SHARE
    # ranking; then second look, six passes whole, final inertia; the update
    assert _dot_precisions(args, kwargs) == [
        ("DEFAULT", "HIGHEST"), ("HIGH", "HIGH"),
        ("HIGHEST", "HIGHEST"), ("HIGHEST", "HIGHEST"), ("HIGHEST", "HIGHEST"),
    ]


@pytest.mark.parametrize("rows,cols,k,cosine,settings,passes", [
    (256, 128, 20, False, {}, [("DEFAULT", "HIGHEST")] + [("HIGHEST", "HIGHEST")] * 2),
    (256, 3000, 128, False, {}, [("DEFAULT", "HIGHEST")] + [("HIGHEST", "HIGHEST")] * 2),
    (1024, 128, 1000, False, {}, [("DEFAULT", "HIGHEST")] + [("HIGHEST", "HIGHEST")] * 2),
    (256, 8192, 64, False, {}, [("DEFAULT", "HIGHEST")] + [("HIGHEST", "HIGHEST")] * 2),
    (*WIDE, False, {"fast_math": True},
     [("DEFAULT", "DEFAULT"), ("DEFAULT", "HIGHEST"), ("HIGHEST", "HIGHEST")]),
    (*WIDE, False, {"parity_precision": "high"},
     [("DEFAULT", "HIGH")] + [("HIGH", "HIGH")] * 2),
    (*WIDE, True, {}, [("DEFAULT", "HIGHEST")] + [("HIGHEST", "HIGHEST")] * 2),
], ids=["k20_d128", "k128_d3000", "k1000_d128", "k64_d8192", "fast_math", "parity_high",
        "cosine"], indirect=["settings"])
def test_where_the_shape_test_does_not_engage_the_program_is_the_six_pass_one(
        monkeypatch, rows, cols, k, cosine, settings, passes):
    args, kwargs = _routed(monkeypatch, rows, cols, k, cosine=cosine)
    assert kwargs["recheck"] == 0 and kwargs["mesh"] is None
    assert _dot_precisions(args, kwargs) == passes
    # and it is the program a caller without the new arguments gets
    plain = {k_: v for k_, v in kwargs.items() if k_ not in ("recheck", "mesh")}
    assert lloyd_fit.lower(*args, **plain).as_text() == lloyd_fit.lower(*args, **kwargs).as_text()


# ------------------------- (b) planted near-ties, three passes really emulated

COLS, K = 48, 9


def _planted(seed):
    """Centres and rows with near-ties: rows within 1e-6 of the bisector of two
    centres, rows with three centres inside one interval, and one centre of
    ten times the others' norm (as the cell's singleton centres are)."""
    rng = np.random.default_rng(seed)
    C = rng.standard_normal((K, COLS)) * 0.4
    C[K - 1] *= 10.0
    # centres 2 and 3 a small step from centre 1, so that three lie together
    C[2] = C[1] + rng.standard_normal(COLS) * 2e-3
    C[3] = C[1] + rng.standard_normal(COLS) * 2e-3
    C = C.astype(np.float32).astype(np.float64)
    rows = [C[rng.integers(K, size=600)] + rng.standard_normal((600, COLS))]
    for a, b in ((0, 4), (5, 6), (7, K - 1), (1, 2)):
        mid, axis = (C[a] + C[b]) / 2, (C[b] - C[a]) / np.linalg.norm(C[b] - C[a])
        side = rng.standard_normal((40, COLS)) * 0.05
        side -= np.outer(side @ axis, axis)  # along the bisector, not across it
        step = rng.uniform(-1e-6, 1e-6, (40, 1))
        rows.append(mid + side + step * axis)
    rows.append(C[[1, 2, 3]].mean(axis=0) + rng.standard_normal((40, COLS)) * 1e-3)
    X = np.concatenate(rows).astype(np.float32)
    return X, C.astype(np.float32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_planted_near_ties_get_the_six_pass_label_row_for_row(three_passes, seed):
    X, C = _planted(seed)
    Xj, Cj = jnp.asarray(X), jnp.asarray(C)
    x2, w = jnp.sum(Xj * Xj, axis=1), jnp.ones(len(X), jnp.float32)
    six = np.asarray(jnp.argmin(kmeans_ops._sq_dists(Xj, Cj), axis=1))
    ranked, decided = jax.jit(kmeans_ops._rank3)(Xj, x2, Cj, jnp.sum(Cj * Cj, axis=1))
    decided = np.asarray(decided)
    # the bound is exercised: the planted rows are undecided, the bulk is not,
    # and three passes alone would have mislabelled some of them
    assert 100 <= (~decided).sum() <= 400
    np.testing.assert_array_equal(np.asarray(ranked)[decided], six[decided])
    assign3 = jax.jit(kmeans_ops._assign3, static_argnames="recheck")
    labels, looks = assign3(Xj, x2, w, Cj, jnp.zeros((1,), bool), recheck=len(X) // 2)
    np.testing.assert_array_equal(np.asarray(labels), six)
    assert np.asarray(looks).tolist() == [[int((~decided).sum()), 0]]
    # a shard that has stopped ranking holds every row undecided: six passes
    # whole, or, where that many fit the second look, the second look
    for recheck, seen in ((len(X) // 2, [[0, 1]]), (len(X), [[len(X), 0]])):
        labels, looks = assign3(Xj, x2, w, Cj, jnp.ones((1,), bool), recheck=recheck)
        np.testing.assert_array_equal(np.asarray(labels), six)
        assert np.asarray(looks).tolist() == seen


def test_three_passes_without_the_bound_would_mislabel_planted_rows(three_passes):
    """What the second look is for: the emulated three-pass distances alone
    rank some near-tied row otherwise than six passes do."""
    wrong = 0
    for seed in range(3):
        X, C = _planted(seed)
        cross = cross3_split(jnp.asarray(X), jnp.asarray(C).T)
        d3 = (X * X).sum(axis=1)[:, None] - 2.0 * np.asarray(cross) + (C * C).sum(axis=1)
        six = np.asarray(jnp.argmin(kmeans_ops._sq_dists(jnp.asarray(X), jnp.asarray(C)), axis=1))
        wrong += int((d3.argmin(axis=1) != six).sum())
    assert wrong > 0


def test_padding_rows_take_no_second_look(three_passes):
    X, C = _planted(0)
    w = np.ones(len(X), np.float32)
    w[600:] = 0.0  # every planted row is padding
    Xj, Cj = jnp.asarray(X), jnp.asarray(C)
    x2 = jnp.sum(Xj * Xj, axis=1)
    _, decided = jax.jit(kmeans_ops._rank3)(Xj, x2, Cj, jnp.sum(Cj * Cj, axis=1))
    _, looks = jax.jit(kmeans_ops._assign3, static_argnames="recheck")(
        Xj, x2, jnp.asarray(w), Cj, jnp.zeros((1,), bool), recheck=300)
    assert np.asarray(looks).tolist() == [[int((~np.asarray(decided)[:600]).sum()), 0]]
    assert (~np.asarray(decided)[600:]).sum() >= 100


def test_every_output_of_the_ranking_reduction_has_a_reader():
    """The reduction's four outputs are its running state. An output nobody
    reads is stored in bfloat16 by the chip's compiler, and where the running
    least lower end is kept there between tiles the other three are ranked
    against a bfloat16 number: 32,958 of 357,376 rows decided for the wrong
    centre (my chip runs, PR 31). So none may be dropped."""
    X, C = _planted(0)
    Xj, Cj = jnp.asarray(X), jnp.asarray(C)
    jaxpr = jax.make_jaxpr(kmeans_ops._rank3)(
        Xj, jnp.sum(Xj * Xj, axis=1), Cj, jnp.sum(Cj * Cj, axis=1)).jaxpr
    (reduce,) = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "reduce"]
    assert len(reduce.outvars) == 4
    assert not any(isinstance(v, jax.core.DropVar) for v in reduce.outvars)


# ------------------------------------------------------ (c) the overflow branch


@pytest.mark.parametrize("recheck,looked,whole", [(400, 211, 0), (3, 3, 1), (2, 0, 4)],
                         ids=["fits", "the_first_overflows", "a_later_one_ends_the_ranking"])
def test_with_recheck_under_the_undecided_rows_the_iteration_runs_six_passes_whole(
        three_passes, recheck, looked, whole):
    """208 rows are undecided in the first iteration (the planted ties), 3 in
    the second, none after. The first may overflow; one that overflows later
    sends the rest of the fit to six passes, though their rows would fit."""
    X, C = _planted(1)
    Xj, w = jnp.asarray(X), jnp.ones(len(X), jnp.float32)
    fit = jax.jit(lloyd_fit.__wrapped__,
                  static_argnames=("max_iter", "unit_weight", "recheck"))
    c6, inertia6, n6, none = fit(Xj, w, jnp.asarray(C), 0.0, max_iter=4, unit_weight=True)
    c3, inertia3, n3, looks = fit(Xj, w, jnp.asarray(C), 0.0, max_iter=4, unit_weight=True,
                                  recheck=recheck)
    np.testing.assert_array_equal(np.asarray(c3), np.asarray(c6))
    assert float(inertia3) == float(inertia6) and int(n3) == int(n6) == 4
    assert np.asarray(none).tolist() == [[0, 0]]
    assert np.asarray(looks).tolist() == [[looked, whole]]


# ------------------------------------------------------------- (d) the bound


def _adversarial(kind, rng, n, d):
    worst = np.float32(1 + 2.0**-7 - 2.0**-23)  # mid and lo both at their largest
    if kind == "normal":
        return rng.standard_normal((n, d))
    if kind == "one_sign":
        return rng.uniform(0.5, 2.0, (n, d))
    if kind == "worst_slices":
        return np.full((n, d), worst)
    if kind == "worst_slices_scaled":
        return worst * 2.0 ** rng.integers(-3, 4, (n, d))
    return rng.standard_normal((n, d)) * 10.0 ** rng.integers(-3, 4, (n, 1))  # large_norms


@pytest.mark.parametrize("d", [48, 3000])
@pytest.mark.parametrize("kind", ["normal", "one_sign", "worst_slices",
                                  "worst_slices_scaled", "large_norms"])
def test_three_passes_stay_inside_the_bound(kind, d):
    """|three-pass - six-pass| of the cross term never exceeds EPS3 |x| |c|,
    and on the rows built for it (equal signs, every slice at its largest)
    comes within a tenth of it."""
    rng = np.random.default_rng(d)
    X = _adversarial(kind, rng, 96, d).astype(np.float32)
    C = _adversarial(kind, rng, 24, d).astype(np.float32)
    three = np.asarray(cross3_split(jnp.asarray(X), jnp.asarray(C).T), np.float64)
    six = np.asarray(jnp.matmul(jnp.asarray(X), jnp.asarray(C).T,
                                precision=jax.lax.Precision.HIGHEST), np.float64)
    scale = np.linalg.norm(X.astype(np.float64), axis=1)[:, None] * np.linalg.norm(
        C.astype(np.float64), axis=1)
    worst = float(np.max(np.abs(three - six) / scale))
    assert worst <= kmeans_ops._EPS3
    if kind == "worst_slices":
        assert worst >= 0.9 * kmeans_ops._EPS3


def test_the_slices_are_a_truncation_and_add_up():
    """The emulation cuts float32 as the chip does: hi + mid + lo == a, each
    part a bf16 number, |mid| < 2^-7 |a|, |lo| < 2^-15 |a|."""
    from tools.lloyd_assign_bench import _slices

    rng = np.random.default_rng(4)
    a = (rng.standard_normal(4096) * 10.0 ** rng.integers(-6, 7, 4096)).astype(np.float32)
    hi, mid, lo = (np.asarray(p) for p in _slices(jnp.asarray(a)))
    np.testing.assert_array_equal(hi + mid + lo, a)
    for part in (hi, mid, lo):
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(part).astype(jnp.bfloat16).astype(jnp.float32)), part)
    assert np.all(np.abs(hi) <= np.abs(a)) and np.all(np.abs(mid) < 2.0**-7 * np.abs(a))
    assert np.all(np.abs(lo) < 2.0**-15 * np.abs(a))


# ------------------------------------------------- (e) the counters of a fit


def _assign_counters(model):
    counters = model.fit_report_["metrics"]["counters"]
    return {k: v for k, v in counters.items()
            if k.startswith(("kmeans.lloyd_assign", "kmeans.lloyd_recheck"))}


def _table(rows=512, cols=2048, seed=5):
    return np.random.default_rng(seed).standard_normal((rows, cols)).astype(np.float32)


K_WIDE = 256  # 256 centres x 2048 columns = 524,288: where three passes begin


def test_a_wide_fit_counts_three_passes_and_its_second_looks():
    model = KMeans(k=K_WIDE, maxIter=3, seed=1, initMode="random").fit(_table())
    counters = _assign_counters(model)
    assert counters.pop("kmeans.lloyd_assign{passes=3}") == 1
    assert set(counters) == {"kmeans.lloyd_recheck_rows", "kmeans.lloyd_recheck_overflow"}
    assert 0 <= counters["kmeans.lloyd_recheck_rows"] <= 3 * 512
    # and the fit is the six-pass fit
    np.testing.assert_array_equal(
        np.asarray(model.cluster_centers_),
        _six_pass_fit(KMeans(k=K_WIDE, maxIter=3, seed=1, initMode="random"), _table()))


def _six_pass_fit(estimator, X):
    """The same fit with the shape test closed."""
    import unittest.mock as mock

    with mock.patch.object(kmeans_ops, "_second_look_rows", lambda *fit: (0, None)):
        return np.asarray(estimator.fit(X).cluster_centers_)


@pytest.mark.parametrize("params,settings,counted", [
    ({"k": 20}, {}, "passes=6"),
    ({"k": K_WIDE - 1}, {}, "passes=6"),
    ({"k": K_WIDE}, {"fast_math": True}, "passes=1"),
    ({"k": K_WIDE}, {"parity_precision": "high"}, "passes=3"),  # `pdot` ranks at HIGH
    ({"k": K_WIDE, "distanceMeasure": "cosine"}, {}, "passes=6"),
], ids=["k20", "one_centre_short", "fast_math", "parity_high", "cosine"], indirect=["settings"])
def test_a_fit_outside_the_shape_test_counts_no_second_look(params, settings, counted):
    model = KMeans(maxIter=3, seed=1, initMode="random", **params).fit(_table())
    assert _assign_counters(model) == {f"kmeans.lloyd_assign{{{counted}}}": 1}


def test_the_overflow_counter_says_when_six_passes_ran_whole(monkeypatch):
    """One row a shard may take the second look, on a table of 64 distinct
    points (so that some of the 256 starting rows coincide, and the rows
    nearest a doubled centre tie): every iteration overflows on every shard, and the fit is still the six-pass fit."""
    rng = np.random.default_rng(9)
    X = rng.standard_normal((64, 2048)).astype(np.float32)[rng.integers(64, size=512)]
    monkeypatch.setattr(kmeans_ops, "LLOYD_RECHECK_SHARE", 512 // jax.device_count())
    model = KMeans(k=K_WIDE, maxIter=3, seed=1, initMode="random").fit(X)
    counters = _assign_counters(model)
    # (the centres are rows of 64 points and stop moving at once)
    n_iter = int(model.summary.numIter)
    assert counters["kmeans.lloyd_recheck_overflow"] == n_iter * jax.device_count()
    assert counters["kmeans.lloyd_recheck_rows"] == 0
    monkeypatch.undo()
    np.testing.assert_array_equal(
        np.asarray(model.cluster_centers_),
        _six_pass_fit(KMeans(k=K_WIDE, maxIter=3, seed=1, initMode="random"), X))


# ---------------------------------------------------------------- on a mesh


def test_on_a_mesh_no_row_crosses_a_shard(n_devices):
    """Rows placed as tests/test_kmeans.py places them: each shard selects,
    gathers and branches by itself, so the compiled program has the six-pass
    program's collectives to the byte and no gather over rows."""
    from spark_rapids_ml_tpu.parallel.partitioner import active_partitioner

    part = active_partitioner(n_devices)
    X = _table(rows=64 * n_devices, cols=4096)
    Xj, wj = part.shard(X), part.shard(np.ones(len(X), np.float32))
    init = jnp.asarray(X[:128])
    recheck, mesh = kmeans_ops._second_look_rows(Xj, 128, False, False)
    assert recheck == 64 // kmeans_ops.LLOYD_RECHECK_SHARE
    assert (mesh is not None) == (n_devices > 1)

    def collectives(**kwargs):
        exe = lloyd_fit.lower(Xj, wj, init, 0.0, 3, unit_weight=True, **kwargs).compile()
        return collective_summary(exe.as_text())

    three, six = collectives(recheck=recheck, mesh=mesh), collectives()
    assert set(three) <= {"all_reduce"}, three
    assert {k: (v["ops"], v["bytes"]) for k, v in three.items()} == {
        k: (v["ops"], v["bytes"]) for k, v in six.items()}
    c6, inertia6, n6, _ = lloyd_fit(Xj, wj, init, 0.0, 3, unit_weight=True)
    c3, inertia3, n3, looks = lloyd_fit(Xj, wj, init, 0.0, 3, unit_weight=True,
                                        recheck=recheck, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(c3), np.asarray(c6))
    assert float(inertia3) == float(inertia6) and int(n3) == int(n6)
    assert looks.shape == (max(n_devices, 1), 2)
