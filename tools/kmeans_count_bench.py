"""Microbenchmark, outside any cell of BENCHMARK.json: what counting rows per
centre costs on the chip by each of ops/kmeans.py's ways, against the host's
(labels fetched, np.bincount), at candidate-set sizes from a k=20 fit's 81 to
an IVF build's 16,385. Decides `COUNT_DEVICE_MAX_CENTERS`. One JSON line a
centre count; refuses a CPU backend.

    chiprun -- python -m tools.kmeans_count_bench [rows]
"""

from __future__ import annotations

import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops import kmeans as K

CENTERS = (20, 81, 321, 1025, 4097, 8193, 16385)
COLS = 128
REPEATS = 5


def _median_s(fn):
    fn()  # compile, first touch
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times), out


def _device_count(labels, w, n_centers, blocks):
    f = jax.jit(functools.partial(K._count_rows, n_centers=n_centers, blocks=blocks))
    return lambda: np.asarray(f(labels, w))


def main(rows: int) -> int:
    if jax.default_backend() != "tpu":
        print("kmeans_count_bench needs the chip", file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(27)
    X = jax.random.normal(key, (rows, COLS), jnp.float32)
    w = jnp.ones((rows,), jnp.float32)
    wf = jax.random.uniform(key, (rows,), jnp.float32, 0.1, 3.0)
    for n_centers in CENTERS:
        centers = X[:: rows // n_centers][:n_centers]
        line = {"rows": rows, "centers": n_centers,
                "device": jax.devices()[0].device_kind}
        line["predict_s"], labels = _median_s(
            lambda: K.kmeans_predict(X, centers).block_until_ready())
        line["device_int_s"], a = _median_s(_device_count(labels, w, n_centers, 0))
        line["device_float_s"], c = _median_s(_device_count(labels, wf, n_centers, 64))

        def host(weights):
            lab = np.asarray(labels)
            return np.bincount(
                lab, weights=None if weights is None else np.asarray(weights),
                minlength=n_centers)

        line["host_int_s"], ref = _median_s(lambda: host(None))
        line["host_float_s"], ref_f = _median_s(lambda: host(wf))
        line["int_equal"] = bool((a == ref).all())
        line["float_rel_err"] = float(
            np.max(np.abs(c.sum(axis=0, dtype=np.float64) - ref_f) / ref_f.max()))
        if n_centers < 128:  # the XLA route: the reduction rides the predict's program
            line["fused_int_s"], d = _median_s(lambda: np.asarray(
                K._assign_counts_xla(X, centers, w, False, 0)))
            line["fused_float_s"], _ = _median_s(lambda: np.asarray(
                K._assign_counts_xla(X, centers, wf, False, 64)))
            line["int_equal"] = line["int_equal"] and bool((d == ref).all())
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 8_380_416))
