"""Probe, outside any cell of BENCHMARK.json: what a forest's quantile edges cost
on the chip when the sample's columns are sorted where the table already is
(`ops/trees.py::forest_edges`), by form and block width, against the host's
sorted column blocks (`quantile_bin_edges`), which are what the device form
has to equal to the bit. One JSON line a reading; refuses a CPU backend.

    chiprun -- python -m tools.forest_edges_probe [rows cols [part ...]]

Defaults: 357376 3000 (the `rf_cls_depth13_d3000` cell's table), 128 bins, the
200,000-row sample; parts `forms fit` (`forms:a,b` times forms a and b alone).

* `forms`: the table is unit noise made on the host and put on the chip whole
  (the runtime's default layout, which the fit's chunked upload keeps). The
  host edges are timed once. Each form is compiled, then called three times
  (dispatch to the order statistics on the host; the least is reported) and
  its edges compared with the host's: `gather` (the sample's rows gathered a
  column block at a time, transposed, sorted along the rows as floats),
  `gather_axis0` (the same sorted along axis 0, no transpose), `gather_int`
  (the program's: monotone int32 keys in place of the floats, sorted with no
  tie-break, which equal keys need not have), `gather_int_stable` (the same
  by `jnp.sort`, whose stable sort carries an index to break ties: the
  program's form in call 1), `gather_unstable` (floats, no tie-break), `keyed_int`
  (every row sorted, the unsampled ones keyed past NaN, so the sample comes
  first: no gather).
* `fit`: the cell's own table (`cellbench/data.py`, seed 4000000001) fitted
  through the public path with one tree of depth 2: the model's `bin_edges`
  against `quantile_bin_edges` of the host table, and the gate's counters.
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

NBINS, LIMIT, SEED = 128, 200_000, 38


def _say(**line):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/forest_edges_probe.jsonl", "a") as out:
        out.write(json.dumps(line) + "\n")


def _blocked(rank_block):
    """A (X, rows or mask, positions, cols) program that ranks column blocks
    as `trees.forest_edges` does, with `rank_block(part, sel, pos)` in place
    of its gather and sort."""

    def fn(X, sel, positions, cols):
        d = X.shape[1]
        cols = min(cols, d)
        pos = np.asarray(positions, np.int32)

        def block(b, out):
            lo = jnp.minimum(b * cols, d - cols)
            ranked = rank_block(jax.lax.dynamic_slice_in_dim(X, lo, cols, axis=1), sel, pos)
            return jax.lax.dynamic_update_slice_in_dim(out, ranked, lo, axis=0)

        return jax.lax.fori_loop(0, -(-d // cols), block,
                                 jnp.zeros((d, len(positions)), X.dtype))

    return jax.jit(fn, static_argnames=("positions", "cols"))


FORMS = {
    "gather": _blocked(
        lambda part, rows, pos: jnp.sort(jnp.take(part, rows, axis=0).T, axis=1)[:, pos]),
    "gather_axis0": _blocked(
        lambda part, rows, pos: jnp.sort(jnp.take(part, rows, axis=0), axis=0)[pos, :].T),
    "gather_int": None,  # the program's own since PR 40's call 1
    "gather_int_stable": _blocked(
        lambda part, rows, pos: trees._key_values(jnp.sort(
            trees._sort_keys(jnp.take(part, rows, axis=0).T), axis=1)[:, pos])),
    "gather_unstable": _blocked(
        lambda part, rows, pos: jax.lax.sort(jnp.take(part, rows, axis=0).T, dimension=1,
                                             is_stable=False)[:, pos]),
    "keyed_int": _blocked(
        lambda part, mask, pos: trees._key_values(jnp.sort(
            jnp.where(mask[None, :], trees._sort_keys(part.T), jnp.int32(0x7FFFFFFF)),
            axis=1)[:, pos])),
}
WIDTHS = {"gather": (256, 512, 1024, 1536), "gather_axis0": (512,), "gather_int": (512, 1536),
          "gather_int_stable": (512,), "gather_unstable": (512,), "keyed_int": (512,)}


def _edges_from(ranked, positions, m):
    prev, nxt, gamma = trees._quantile_reads(m, np.linspace(0, 1, NBINS + 1)[1:-1])

    def at(p):
        return ranked[:, np.searchsorted(positions, p % m)]

    return trees._lerp_quantiles(at(prev), at(nxt), ranked[:, -1], gamma).astype(np.float32)


def forms(rows, cols, names=None):
    t0 = time.perf_counter()
    X_host = np.random.default_rng(SEED).standard_normal((rows, cols), dtype=np.float32)
    made = time.perf_counter() - t0
    t0 = time.perf_counter()
    X = jax.device_put(X_host)
    X.block_until_ready()
    _say(part="forms", what="table", rows=rows, cols=cols, make_s=made,
         put_s=time.perf_counter() - t0)
    host_s = []
    for _ in range(2):
        t0 = time.perf_counter()
        want = trees.quantile_bin_edges(X_host, NBINS, sample_limit=LIMIT, seed=SEED)
        host_s.append(time.perf_counter() - t0)
    _say(part="forms", what="host", seconds=host_s, threads=min(16, os.cpu_count() or 1))

    idx = trees._edge_rows(rows, LIMIT, SEED)
    sample = np.arange(rows) if idx is None else idx
    m = len(sample)
    prev, nxt, _ = trees._quantile_reads(m, np.linspace(0, 1, NBINS + 1)[1:-1])
    positions = tuple(np.unique(np.concatenate([prev % m, nxt % m, [m - 1]])).tolist())
    rows_j = jnp.asarray(sample, jnp.int32)
    mask = np.zeros(X.shape[0], bool)
    mask[sample] = True
    mask_j = jnp.asarray(mask)
    for form, widths in WIDTHS.items():
        if names and form not in names:
            continue
        fn = trees.forest_edges.jitted if FORMS[form] is None else FORMS[form]
        sel = mask_j if form == "keyed_int" else rows_j
        for width in widths:
            line = {"part": "forms", "form": form, "cols": width, "positions": len(positions)}
            try:
                t0 = time.perf_counter()
                exe = fn.lower(X, sel, positions=positions, cols=width).compile()
                line["compile_s"] = time.perf_counter() - t0
                mem = exe.memory_analysis()  # noqa: fence/device-analysis-off-plane (a probe)
                line["temp_bytes"] = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
                seconds = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    ranked = np.asarray(exe(X, sel))
                    seconds.append(time.perf_counter() - t0)
                line["seconds"] = seconds
                line["best_s"] = min(seconds)
                got = _edges_from(ranked, np.asarray(positions), m)
                line["edges_equal"] = bool(np.array_equal(got, want))
                line["edges_bits_equal"] = bool(np.array_equal(got.view(np.int32),
                                                               want.view(np.int32)))
            except Exception as e:  # a form the chip refuses is a reading too
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            _say(**line)
    t0 = time.perf_counter()
    got = trees.device_quantile_bin_edges(X, rows, NBINS, sample_limit=LIMIT, seed=SEED)
    _say(part="forms", what="program", seconds=time.perf_counter() - t0,
         edges_equal=bool(np.array_equal(got, want)),
         edges_bits_equal=bool(np.array_equal(got.view(np.int32), want.view(np.int32))))


def fit(rows, cols):
    sys.path.insert(0, os.getcwd())
    from cellbench import data
    from cellbench.estimators import forest

    with open("cellbench/configs/rf_cls_depth13_d3000.json") as f:
        cfg = json.load(f)
    seed = 4000000001
    X, _ = data.make_table(cfg["table"], rows, cols, seed, jax.devices())
    params = {**cfg["params"], "numTrees": 1, "maxDepth": 2, "seed": seed}
    t0 = time.perf_counter()
    model = forest.build(params, 1).fit(X)
    fitted = time.perf_counter() - t0
    got = np.asarray(model.get_model_attributes()["bin_edges"])
    t0 = time.perf_counter()
    want = trees.quantile_bin_edges(X, cfg["params"]["maxBins"], seed=seed)
    host_s = time.perf_counter() - t0
    counters = model.fit_report_["metrics"]["counters"]
    _say(part="fit", rows=rows, cols=cols, fit_s=fitted, host_edges_s=host_s,
         places=int(got.size), equal=int(np.sum(got == want)),
         bits_equal=bool(np.array_equal(got.view(np.int32), want.view(np.int32))),
         counters={k: v for k, v in counters.items() if k.startswith("forest.edges")
                   or "span=forest.edges" in k})


def main(argv):
    if jax.default_backend() != "tpu":
        print("forest_edges_probe: needs a TPU", file=sys.stderr)
        return 3
    rows = int(argv[0]) if argv else 357376
    cols = int(argv[1]) if len(argv) > 1 else 3000
    for part in argv[2:] or ["forms", "fit"]:
        name, _, names = part.partition(":")  # forms:a,b runs forms a and b alone
        if name == "forms":
            forms(rows, cols, names.split(",") if names else None)
        else:
            fit(rows, cols)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
