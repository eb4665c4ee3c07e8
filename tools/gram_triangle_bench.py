"""Microbenchmark, outside any cell of BENCHMARK.json: what computing only the
upper column blocks of the XLA Gram (`ops/linalg.py::_centered_gram`) costs
and gives on the chip against the single matmul, by block width and column
count. Where `defaults.py::GRAM_BLOCK_COLS` and
`GRAM_TRIANGLE_MIN_COLS` come from. One JSON line a reading (seconds a
4,096-row part, the least of five calls of `weighted_covariance`; the worst
entry's distance from the single matmul's in units of the largest entry;
whether the result equals its transpose); refuses a CPU backend.

    chiprun -- python -m tools.gram_triangle_bench [cols ...]

Every width is run as one block (the single matmul) and in blocks of each of
`WIDTHS` that cut it in two or more; `pairs` is the same triangle with one
matmul a block PAIR (I, J) where the program has one a block ROW (block I
against the columns from I's first to the last). Tables of `PARTS` parts,
unit weights, the cells' PCA geometry without its factors (column means
N(0, 1), unit noise).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops import linalg

COLS = (576, 640, 768, 1024, 1536, 2048, 3000, 4096)
WIDTHS = (256, 384, 512, 768)
PAIR_WIDTHS = (512,)
PARTS = 24


def _say(**line):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gram_triangle_bench.jsonl", "a") as out:
        out.write(json.dumps(line) + "\n")


def _pair_blocks(X, w, mean, blocks):
    """`linalg._upper_gram_panels` cut further: one matmul a block pair."""
    chunk = linalg.GRAM_CHUNK_ROWS
    pairs = [(I, J) for a, I in enumerate(blocks) for J in blocks[a:]]

    def body(i, carry):
        xs = jax.lax.dynamic_slice_in_dim(X, i * chunk, chunk, 0) - mean[None, :]
        xw = xs * jax.lax.dynamic_slice_in_dim(w, i * chunk, chunk, 0)[:, None]
        terms = [linalg.pdot(xw[:, i0:i1].T, xs[:, j0:j1]) for (i0, i1), (j0, j1) in pairs]
        accs, comps = zip(*(linalg.kahan_add(a, c, t) for a, c, t in zip(*carry, terms)))
        return list(accs), list(comps)

    zeros = [jnp.zeros((i1 - i0, j1 - j0), X.dtype) for (i0, i1), (j0, j1) in pairs]
    upper = iter(jax.lax.fori_loop(0, X.shape[0] // chunk, body, (zeros, zeros))[0])
    return [jnp.concatenate([next(upper) for _ in blocks[a:]], axis=1)
            for a in range(len(blocks))]


def _program(width, pairs=False):
    """A fresh jit of `weighted_covariance` (same name, so the same program
    name) that traces with `width` columns a block; None is the single matmul."""
    fn = linalg.weighted_covariance._fn

    @functools.wraps(fn)
    def weighted_covariance(X, w):
        linalg.GRAM_TRIANGLE_MIN_COLS = 0 if width else X.shape[1] + 1
        linalg.GRAM_BLOCK_COLS = width or X.shape[1]
        if not pairs:
            return fn(X, w)
        wsum = jnp.sum(w)
        mean = linalg.pdot(w, X) / wsum
        blocks = linalg.gram_column_blocks(X.shape[1])
        G = linalg._mirror_upper_panels(_pair_blocks(X, w, mean, blocks), blocks)
        return G / (wsum - 1.0), mean, wsum

    return jax.jit(weighted_covariance)


def _seconds(program, X, w, calls=5):
    out = jax.block_until_ready(program(X, w))
    best = float("inf")
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(program(X, w))
        best = min(best, time.perf_counter() - t0)
    return best, out[0]


def main(argv) -> int:
    if jax.devices()[0].platform != "tpu":
        print("gram_triangle_bench measures a chip; this backend is "
              f"{jax.devices()[0].platform}", file=sys.stderr)
        return 3
    saved = linalg.GRAM_TRIANGLE_MIN_COLS, linalg.GRAM_BLOCK_COLS
    rows = PARTS * linalg.GRAM_CHUNK_ROWS
    for d in [int(a) for a in argv] or COLS:
        key = jax.random.PRNGKey(d)
        X = jax.random.normal(key, (rows, d), jnp.float32) + jax.random.normal(
            jax.random.fold_in(key, 1), (d,), jnp.float32)[None, :]
        w = jnp.ones((rows,), jnp.float32)
        full_s, full = _seconds(_program(None), X, w)
        scale = float(jnp.abs(full).max())
        _say(cols=d, rows=rows, form="full", blocks=1, part_s=full_s / PARTS, call_s=full_s)
        variants = [(bw, False) for bw in WIDTHS if bw < d]
        variants += [(bw, True) for bw in PAIR_WIDTHS if bw < d]
        for width, pairs in variants:
            s, cov = _seconds(_program(width, pairs), X, w)
            _say(cols=d, rows=rows, form="pairs" if pairs else "triangle", width=width,
                 blocks=-(-d // width), part_s=s / PARTS, call_s=s, of_full=s / full_s,
                 off_full=float(jnp.abs(cov - full).max()) / scale,
                 symmetric=bool((cov == cov.T).all()))
        del X, full
    linalg.GRAM_TRIANGLE_MIN_COLS, linalg.GRAM_BLOCK_COLS = saved
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
