"""Microbenchmark, outside any cell of BENCHMARK.json: what one evaluation of
the binary logistic data term (value and gradient) costs on the chip as
autodiff's two XLA passes over X and as the one-read Pallas sweep
(`ops/pallas_logistic.py`), by samples a block. Where
`defaults.py::LOGISTIC_EVAL_*` come from. One JSON line a reading:
the table's placed layout; per form the milliseconds an evaluation (the
difference of a 40- and a 10-evaluation compiled loop, the least of three
calls each, every evaluation at the point the last one's gradient moved it to),
the table's bytes over that, the program's temporaries and any copy of the
table it makes, and the sums' distance from the two-pass program's (value,
gradient over its RMS coordinate, intercept entry); refuses a CPU backend.

    chiprun -- python -m tools.logistic_eval_bench [rows cols [block ...]]

Defaults: 357376 3000 (the `logreg_l2_d3000` cell's table) at 256, 512, 1024
and 2048 samples a block. The table is made on the device from a seed (unit
noise, labels of a logistic model over all columns, unit weights); its rows are
cut to a multiple of the largest block, so that every size sweeps every row
(the program itself hands the rows past the last whole block to plain jnp).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops import pallas_logistic
from spark_rapids_ml_tpu.ops._precision import pdot

LOOPS = (10, 40)


def _say(**line):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/logistic_eval_bench.jsonl", "a") as out:
        out.write(json.dumps(line) + "\n")


def two_pass(X, y, w, beta, b):
    def data(beta, b):
        z = pdot(X, beta) + b
        return jnp.sum(w * (jax.nn.softplus(z) - y * z))

    value, (g, gb) = jax.value_and_grad(data, argnums=(0, 1))(beta, b)
    return value, g, gb


def sweep(blk):
    return lambda X, y, w, beta, b: pallas_logistic._sums_pallas(X, y, w, beta, b, blk, False)


def _looped(form, evaluations):
    def run(X, y, w, beta, b):
        def body(_, carry):
            beta, b, total = carry
            value, g, gb = form(X, y, w, beta, b)
            return beta - 1e-9 * g, b - 1e-9 * gb, total + value

        return jax.lax.fori_loop(0, evaluations, body, (beta, b, jnp.float32(0)))

    return jax.jit(run)


def _seconds(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("logistic_eval_bench: refusing to run off a TPU", file=sys.stderr)
        return 2
    rows, cols = (int(argv[0]), int(argv[1])) if len(argv) >= 2 else (357376, 3000)
    blocks = [int(a) for a in argv[2:]] or [256, 512, 1024, 2048]
    rows -= rows % max(blocks)
    k_x, k_beta, k_y = jax.random.split(jax.random.key(35), 3)
    X = jax.jit(lambda k: jax.random.normal(k, (rows, cols), jnp.float32))(k_x)
    truth = jax.random.normal(k_beta, (cols,), jnp.float32) * (2.5 / cols ** 0.5)
    y = (jax.random.uniform(k_y, (rows,)) < jax.nn.sigmoid(X @ truth)).astype(jnp.float32)
    w = jnp.ones((rows,), jnp.float32)
    args = (X, y, w, truth * 0.5, jnp.float32(0.1))
    layout = X.format.layout
    _say(rows=rows, cols=cols, device=jax.devices()[0].device_kind,
         major_to_minor=list(layout.major_to_minor), tiling=[list(t) for t in layout.tiling],
         gate=list(pallas_logistic.eval_gate(X, False)))
    want = jax.jit(two_pass)(*args)
    rms = float(jnp.sqrt(jnp.mean(want[1] ** 2)))
    for name, form in [("two_pass", two_pass)] + [(f"sweep_{b}", sweep(b)) for b in blocks]:
        exe = jax.jit(form).lower(*args).compile()
        got = exe(*args)
        copies = [line.split("=")[1].split("copy(")[0].strip() for line in exe.as_text().splitlines()
                  if " copy(" in line and f"{rows},{cols}" in line]
        t = {n: _seconds(_looped(form, n), *args) for n in LOOPS}
        per = (t[LOOPS[1]] - t[LOOPS[0]]) / (LOOPS[1] - LOOPS[0])
        _say(form=name, ms_an_evaluation=per * 1e3, table_gb_per_s=rows * cols * 4 / per / 1e9,
             # a scratch compile of a loop nobody else runs: nothing for the device plane to attribute
             temp_bytes=int(exe.memory_analysis().temp_size_in_bytes),  # noqa: fence/device-analysis-off-plane
             table_copies=copies,
             value_off=abs(float(got[0]) - float(want[0])) / abs(float(want[0])),
             gradient_off=float(jnp.max(jnp.abs(got[1] - want[1]))) / rms,
             intercept_off=abs(float(got[2]) - float(want[2])) / (abs(float(want[2])) + rms))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
