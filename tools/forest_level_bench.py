"""Microbenchmark, outside any cell of BENCHMARK.json: what one level of the
forest builder costs on the chip by width and by histogram form
(`ops/trees.py::_level_step`: "direct", the one-hot kernel over every node of
the level, against "grouped", rows sorted by node, with float32, bfloat16 and
int8 operands), what the device binning costs, and what a whole tree costs and
holds. What `ops/pallas_histogram.py::hist_gate` and `ops/trees.py::_operand`
rest on. One JSON line a reading; refuses a CPU backend.

    chiprun -- python -m tools.forest_level_bench [rows cols [part ...]]

Defaults: 357376 3000 (the `rf_cls_depth13_d3000` cell's table), 128 bins, two
classes, 54 features a node; parts `bin levels tree`. The table is unit noise
made on the device from a seed, the label a noisy threshold on two columns.
A level is timed on a made-up state: every row at a node drawn uniformly, the
nodes' totals summed from the rows, so every node of the level holds rows;
each reading is the least of three calls of the level's compiled step, and the
two forms' best splits are compared where both ran.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops import trees

NBINS, CLASSES, DEPTH = 128, 2, 13
# (form, operand) -> the levels it is timed at
LEVELS = {("direct", "float32"): (0, 4), ("grouped", "float32"): (4,),
          ("grouped", "bfloat16"): (0, 4, 9, 12), ("grouped", "int8"): (0, 4, 6, 9, 11, 12)}


def _say(**line):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/forest_level_bench.jsonl", "a") as out:
        out.write(json.dumps(line) + "\n")


def _peak():
    stats = jax.devices()[0].memory_stats()  # noqa: fence/device-analysis-off-plane (a probe)
    return int((stats or {}).get("peak_bytes_in_use", 0))


def _least(fn, reps=3):
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best, out


def main(argv):
    if jax.default_backend() != "tpu":
        print("forest_level_bench: needs a TPU", file=sys.stderr)
        return 3
    rows = int(argv[0]) if argv else 357376
    cols = int(argv[1]) if len(argv) > 1 else 3000
    parts = argv[2:] or ["bin", "levels", "tree"]
    k_features = max(1, int(np.sqrt(cols)))
    key = jax.random.PRNGKey(38)
    X = jax.random.normal(key, (rows, cols), jnp.float32)
    sample = np.asarray(X[:: max(1, rows // 20000)])
    edges_h = trees.quantile_bin_edges(sample, NBINS)
    edges = jnp.asarray(edges_h)
    d_pad = trees.feature_plan(cols)[0]

    exe = trees.bin_features_device.lower(X, edges, d_pad=d_pad).compile()
    seconds, words = _least(lambda: exe(X, edges))
    head = trees.unpack_bins(np.asarray(words[:2048]), cols)
    want = trees.bin_features(np.asarray(X[:2048]), edges_h).astype(np.uint8)
    if "bin" in parts:
        _say(part="bin", rows=rows, cols=cols, seconds=seconds,
             equal_to_host=bool(np.array_equal(head, want)), peak_bytes=_peak())

    y = ((X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
          + jax.random.normal(jax.random.fold_in(key, 1), (rows,)) > 0.3)).astype(jnp.int32)
    w = jax.random.poisson(jax.random.fold_in(key, 2), 1.0, (rows,)).astype(jnp.float32)
    values = jax.nn.one_hot(y, CLASSES, dtype=jnp.float32) * w[:, None]
    del X
    kw = dict(nbins=NBINS, impurity="gini", k_features=k_features, min_instances=1,
              min_info_gain=0.0, mesh=None, packed=True)

    if "levels" in parts:
        slots = 2 ** (DEPTH + 1)
        for t in sorted({t for levels in LEVELS.values() for t in levels}):
            width = 2**t
            node = jax.random.randint(jax.random.fold_in(key, 100 + t), (rows,), 0, width)
            T = jax.ops.segment_sum(values, node, num_segments=width)
            state = (jnp.full((slots,), -1, jnp.int32), jnp.zeros((slots,), jnp.float32),
                     jnp.zeros((slots,), bool), jnp.zeros((slots, CLASSES), jnp.float32),
                     jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.float32),
                     node, T, jax.random.PRNGKey(t))
            found = {}
            for (form, operand), levels in LEVELS.items():
                if t not in levels:
                    continue
                t0 = time.perf_counter()
                step = trees._level_step_jit.lower(
                    state, words, values, edges, t=t, form=form, operand=operand,
                    **kw).compile()
                compile_s = time.perf_counter() - t0
                seconds, out = _least(lambda: step(state, words, values, edges))
                found[form, operand] = [np.asarray(out[i])[width:2 * width] for i in (0, 1, 4)]
                _say(part="level", t=t, width=width, form=form, operand=operand,
                     seconds=seconds,
                     compile_s=compile_s, peak_bytes=_peak(),
                     temp_bytes=int(
                         step.memory_analysis()  # noqa: fence/device-analysis-off-plane
                         .temp_size_in_bytes))
            if len(found) > 1:
                first = next(iter(found.values()))
                _say(part="level_forms_agree", t=t, forms=[list(k) for k in found],
                     agree=bool(all(np.array_equal(a, b) for other in found.values()
                                    for a, b in zip(first, other))))

    if "tree" in parts:
        forms = trees.level_forms(DEPTH, rows, cols, NBINS, CLASSES, True)
        args = (words, values, edges, jax.random.PRNGKey(7))
        tkw = dict(max_depth=DEPTH, nbins=NBINS, impurity="gini", k_features=k_features,
                   min_instances=1, min_info_gain=0.0, use_pallas=True, mesh=None,
                   forms=forms, operand="int8", packed=True)
        t0 = time.perf_counter()
        tree = trees.build_tree(*args, **tkw)
        jax.block_until_ready(tree)
        first = time.perf_counter() - t0
        seconds, tree = _least(lambda: trees.build_tree(*args, **tkw), reps=2)
        feat = np.asarray(tree["feature"])
        _say(part="tree", forms=forms, first_call_s=first, seconds=seconds,
             internal_nodes=int((feat >= 0).sum()),
             deepest_level_nodes=int((feat[2 ** (DEPTH - 1):2 ** DEPTH] >= 0).sum()),
             peak_bytes=_peak())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
