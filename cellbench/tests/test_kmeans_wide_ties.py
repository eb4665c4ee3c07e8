"""`estimators/kmeans_wide.py` (PR 28): the exact Lloyd step as float32 may
have taken it. A row that ties between two centres may lie with either; a row
that does not tie may not, and neither may many rows."""

import numpy as np
import pytest

from cellbench import refs
from cellbench.estimators import kmeans_wide as wide


def planted(seed=0, k=12, d=40, per=30):
    """Well-separated clusters, and two rows placed by hand between centres 0
    and 1: one a float32 tie (margin 1e-4), one nearer 0 by a margin of 0.5."""
    rng = np.random.default_rng(seed)
    centers = (rng.standard_normal((k, d)) * 6.0).astype(np.float32)
    X = (centers[np.repeat(np.arange(k), per)] + rng.standard_normal((k * per, d))).astype(np.float32)
    mid = (centers[0].astype(np.float64) + centers[1]) / 2
    along = centers[1].astype(np.float64) - centers[0]
    gap2 = float(along @ along)
    # a point at mid - t * along has margin d2(c1) - d2(c0) = 2 t |along|^2
    tie = (mid - (1e-4 / (2 * gap2)) * along).astype(np.float32)
    # off the line between the centres, so that it is no stand-in for the tie
    across = rng.standard_normal(d)
    across -= (across @ along) / gap2 * along
    clear = (mid - (0.5 / (2 * gap2)) * along + 3.0 * across / np.linalg.norm(across)).astype(np.float32)
    X = np.vstack([X, tie, clear])
    return X, centers


def exact(X, before):
    labels, _, sums, counts = refs.assign(X, before, sums_for=len(before))
    return labels, sums, counts, wide.means(sums, counts, before)


def moved_to(X, labels, sums, counts, before, row, dest):
    s, c = sums.copy(), counts.copy()
    s[labels[row]] -= X[row]
    c[labels[row]] -= 1
    s[dest] += X[row]
    c[dest] += 1
    return wide.means(s, c, before)


def worst(got, step):
    return float(np.abs(got - step).max() / np.sqrt((step * step).mean()))


def test_the_exact_step_is_left_alone():
    X, before = planted()
    labels, sums, counts, step = exact(X, before)
    got, _, moved = wide.step_as_float32_may_take_it(X, before, labels, sums, counts, step)
    assert moved == [] and np.array_equal(got, step)


def test_a_tied_row_may_lie_with_either_centre():
    X, before = planted()
    labels, sums, counts, step = exact(X, before)
    tie = len(X) - 2
    assert labels[tie] == 0
    program = moved_to(X, labels, sums, counts, before, tie, 1)
    assert worst(program, step) > 100 * wide.ROUNDING  # one row of thirty: far off
    got, _, moved = wide.step_as_float32_may_take_it(X, before, labels, sums, counts, program)
    assert len(moved) == 1 and 0 <= moved[0] < wide.TIE
    assert worst(program, got) < 1e-12


def test_a_row_that_does_not_tie_is_not_moved():
    X, before = planted()
    labels, sums, counts, step = exact(X, before)
    clear = len(X) - 1
    assert labels[clear] == 0
    program = moved_to(X, labels, sums, counts, before, clear, 1)
    got, _, moved = wide.step_as_float32_may_take_it(X, before, labels, sums, counts, program)
    # the tie beside it may be tried in its place (it lies nearer where the
    # lost row lay than the centre does), but it is another row: the centres
    # stay far from where the program put them, and that is the verdict
    assert worst(program, got) > 10 * wide.ROUNDING
    assert all(m < wide.TIE for m in moved)


def test_both_rows_astray_and_only_the_tie_is_excused():
    X, before = planted()
    labels, sums, counts, step = exact(X, before)
    tie, clear = len(X) - 2, len(X) - 1
    s, c = sums.copy(), counts.copy()
    for row in (tie, clear):
        s[0] -= X[row]
        s[1] += X[row]
        c[0] -= 1
        c[1] += 1
    program = wide.means(s, c, before)
    got, _, moved = wide.step_as_float32_may_take_it(X, before, labels, sums, counts, program)
    assert len(moved) == 1
    assert worst(program, got) > 10 * wide.ROUNDING


def test_many_centres_off_is_no_ties_doing():
    X, before = planted(k=2 * wide.MAX_OFF + 2)
    labels, sums, counts, step = exact(X, before)
    program = step + 1e-2  # every centre off
    got, _, moved = wide.step_as_float32_may_take_it(X, before, labels, sums, counts, program)
    assert moved == [] and np.array_equal(got, step)


@pytest.mark.parametrize("low_precision,correct", [(False, True), (True, False)])
def test_check_fit_holds_the_exact_step_and_refuses_a_bf16_one(low_precision, correct):
    rng = np.random.default_rng(5)
    k, d = 16, 64
    centers = rng.standard_normal((k, d)) * 0.4
    X = (centers[rng.integers(0, k, 4096)] + rng.standard_normal((4096, d))).astype(np.float32)
    before = X[:k].copy()
    after = refs.lloyd_step(X, before, low_precision=low_precision).astype(np.float32)
    labels, inertia, _, _ = refs.assign(X, after)

    class Model:
        cluster_centers_ = before

    answer = {"centers": after, "inertia": inertia, "sizes": np.bincount(labels, minlength=k)}
    (reading,) = wide.check_fit(X, [answer, dict(answer)], lambda overrides: Model(),
                                {"maxIter": 30})
    assert set(reading) == {"center_step_err", "inertia_rel_err", "sizes_mismatch_share"}
    assert (reading["center_step_err"] < 1e-5) is correct
    assert reading["inertia_rel_err"] < 1e-12 and reading["sizes_mismatch_share"] == 0.0
