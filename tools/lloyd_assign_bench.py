"""Microbenchmark, outside any cell of BENCHMARK.json: what ranking Lloyd's
assignment at three MXU passes with a six-pass second look
(`ops/kmeans.py::_rank3`, `_assign3`) costs and gives on the chip, against the
six-pass assignment. Where `_rank3`'s EPS3 and the shape test of
`_second_look_rows` come from. One JSON line a reading; refuses a CPU backend.

    chiprun -- python -m tools.lloyd_assign_bench [passes] [boundary] [cell] [offset]

`passes`: is `Precision.HIGH` the three bf16 passes `_rank3`'s bound is derived
for (against an explicit hi/mid split), and how far are three passes, six
passes and the exact float64 product apart, in units of |x||c|, on random and
on adversarial rows (one sign, every bf16 residual at its largest).
`boundary`: seconds an iteration of `lloyd_fit`, six passes against three, at
eight shapes about the shape test's boundary (tables of 2.1 GB, random-row
start).
`cell`: the `kmeans_k1000_d3000` shape whole: the share of rows undecided by
iteration, the rows whose label is not the six-pass program's, and a fit's
seconds at six passes and at three. `offset`: the same with every column
moved off the origin by 1 and by 10 noise widths, the tables on which three
passes decide little or nothing.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops import kmeans as K

HIGH, HIGHEST = jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST
TABLE_BYTES = 2_144_256_000  # half the cells' table
CELL = (357376, 3000, 1000)
PASSES = (2048, 3000, 256)
BOUNDARY = ((8192, 64, 0.3), (4096, 128, 0.2), (2048, 256, 0.15), (1024, 512, 0.12),
            (512, 1024, 0.1), (256, 2048, 0.09), (128, 8192, 0.06), (512, 3000, 0.08))


def _say(**line):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/lloyd_assign_bench.jsonl", "a") as out:
        out.write(json.dumps(line) + "\n")


def _slices(a):
    """float32's 24 significand bits cut into three bf16 numbers at fixed
    places, as the MXU takes them: hi the top eight bits, mid the next eight,
    lo the last. Each a truncation, never a rounding; hi + mid + lo == a."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)
    mid = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFFFF00), jnp.float32) - hi
    return hi, mid, a - hi - mid


def cross3_split(X, Ct):
    """The three passes written out: hi.hi + hi.mid + mid.hi, each product of
    two bf16 numbers exact in float32 (the dots below take bf16-valued
    operands, which `HIGHEST` multiplies exactly on the chip and the CPU)."""
    xh, xm, _ = _slices(X)
    ch, cm, _ = _slices(Ct)
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    return dot(xh, ch) + (dot(xh, cm) + dot(xm, ch))


def passes():
    n, d, k = PASSES
    rng = np.random.default_rng(31)
    # every slice at its largest: mid = 2^-7 - 2^-15, lo = 2^-15 - 2^-23
    worst = np.float32(1 + 2.0**-7 - 2.0**-23)
    tables = {
        "normal": (rng.standard_normal((n, d)), 0.1 * rng.standard_normal((k, d))),
        "one_sign": (rng.uniform(0.5, 2.0, (n, d)), rng.uniform(0.5, 2.0, (k, d))),
        "worst_slices": (np.full((n, d), worst), np.full((k, d), worst)),
        "worst_slices_scaled": (worst * 2.0 ** rng.integers(-3, 4, (n, d)),
                                worst * 2.0 ** rng.integers(-3, 4, (k, d))),
    }
    f = jax.jit(lambda X, C: (
        jnp.matmul(X, C.T, precision=HIGH), jnp.matmul(X, C.T, precision=HIGHEST),
        cross3_split(X, C.T)))
    for name, (X, C) in tables.items():
        X, C = X.astype(np.float32), C.astype(np.float32)
        high, highest, split = (np.asarray(a, np.float64) for a in f(X, C))
        exact = X.astype(np.float64) @ C.astype(np.float64).T
        scale = np.linalg.norm(X.astype(np.float64), axis=1)[:, None] * np.linalg.norm(
            C.astype(np.float64), axis=1)

        def rel(a, b):
            return float(np.max(np.abs(a - b) / scale))

        _say(probe="passes", table=name, d=d, eps3=K._EPS3, d_u=d * K._U32,
             high_vs_slices=rel(high, split),
             high_vs_highest=rel(high, highest), highest_vs_exact=rel(highest, exact),
             high_vs_exact=rel(high, exact), slices_vs_exact=rel(split, exact))


def _mixture(rows, cols, k, center_scale, seed, offset=0.0):
    """A table as cellbench/data.py draws them, made on the device; `offset`
    moves every column off the origin by so many noise widths."""
    key = jax.random.PRNGKey(seed)
    kc, kl, kn, ki = jax.random.split(key, 4)
    comps = jax.random.normal(kc, (max(k, 2), cols), jnp.float32) * center_scale
    block = 1 << 16

    @jax.jit
    def make(i):
        lab = jax.random.randint(jax.random.fold_in(kl, i), (block,), 0, comps.shape[0])
        noise = jax.random.normal(jax.random.fold_in(kn, i), (block, cols), jnp.float32)
        return comps[lab] + noise + offset

    X = jnp.concatenate([make(i) for i in range(-(-rows // block))])[:rows]
    init = X[jax.random.choice(ki, rows, (k,), replace=False)]
    return X, jnp.ones((rows,), jnp.float32), init


def _timed_fit(recheck):
    """`lloyd_fit`'s program with `max_iter` traced: one compile serves the
    two lengths whose difference is an iteration's time."""
    def fit(X, w, init, max_iter):
        return K.lloyd_fit.__wrapped__(
            X, w, init, 0.0, max_iter, unit_weight=True, recheck=recheck)
    return jax.jit(fit)


def _seconds(f, *args):
    jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best, out


def _sweep(shapes):
    for k, cols, center_scale in shapes:
        rows = TABLE_BYTES // (4 * cols) // 1024 * 1024
        X, w, init = _mixture(rows, cols, k, center_scale, seed=k + cols)
        line = {"probe": "sweep", "rows": rows, "cols": cols, "k": k}
        for name, recheck in (("six", 0), ("three", rows // K.LLOYD_RECHECK_SHARE)):
            f = _timed_fit(recheck)
            short, _ = _seconds(f, X, w, init, 2)
            long, out = _seconds(f, X, w, init, 12)
            line[f"{name}_iter_s"] = (long - short) / 10
            line[f"{name}_fixed_s"] = short - 2 * (long - short) / 10
            if recheck:
                line["looks_12_iters"] = np.asarray(out[3]).tolist()
        line["three_over_six"] = line["three_iter_s"] / line["six_iter_s"]
        _say(**line)
        del X, w, init


def boundary():
    """Shapes about the shape test's boundary, narrow to wide."""
    _sweep(BOUNDARY)


def cell(offset=0.0):
    rows, cols, k = CELL
    X, w, init = _mixture(rows, cols, k, 0.08, seed=31, offset=offset)
    x2 = jnp.sum(X * X, axis=1)
    recheck = rows // K.LLOYD_RECHECK_SHARE

    @jax.jit
    def step(X, x2, w, centers):
        """One Lloyd step by the six-pass labels; beside it what `_rank3`
        leaves undecided and where `_assign3` differs from six passes."""
        c2 = jnp.sum(centers * centers, axis=1)
        six = jnp.argmin(K._sq_dists(X, centers), axis=1).astype(jnp.int32)
        _, decided = K._rank3(X, x2, centers, c2)
        three, _ = K._assign3(X, x2, w, centers, jnp.zeros((1,), bool), recheck)
        onehot = jax.nn.one_hot(six, k, dtype=X.dtype)
        counts = jnp.sum(onehot, axis=0)
        sums = jnp.matmul(onehot.T, X, precision=(jax.lax.Precision.DEFAULT, HIGHEST))
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers)
        return new, jnp.sum(~decided), jnp.sum(three != six)

    centers, undecided, differ = init, [], []
    for _ in range(8):
        centers, n_und, n_diff = step(X, x2, w, centers)
        undecided.append(int(n_und) / rows)
        differ.append(int(n_diff))
    _say(probe="cell.undecided", offset=offset, share_by_iteration=undecided,
         labels_not_six_pass=differ)
    for name, limit in (("six", 0), ("three", recheck)):
        s, out = _seconds(_timed_fit(limit), X, w, init, 30)
        _say(probe="cell.fit", offset=offset, assignment=name, fit_s=s,
             looks=np.asarray(out[3]).tolist(), n_iter=int(out[2]), inertia=float(out[1]))


def offset():
    for by in (1.0, 10.0):
        cell(by)


def main(argv) -> int:
    if jax.default_backend() != "tpu":
        print("lloyd_assign_bench needs the chip", file=sys.stderr)
        return 2
    for name in argv or ("passes", "boundary", "cell"):
        {"passes": passes, "boundary": boundary, "cell": cell, "offset": offset}[name]()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
