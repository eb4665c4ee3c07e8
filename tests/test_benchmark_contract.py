"""The program's side of the benchmark's contract: every span, counter, label,
model attribute and program name that a per-layer metric of `BENCHMARK.json`
reads of the program is there, under that name, when the cell's estimator runs
through the public path.

`cellbench/` is the one yardstick and no tier-1 test imports it; this file only
READS `BENCHMARK.json`, `cellbench/metrics/*.json` and `cellbench/configs/*.json`.
One case per (per-layer metric, cell) pair whose metric file reads the program
(kinds `report_counter_per_op`, `counter_delta`, `counter_delta_per_op`,
`span_seconds_per_op`, `model_attribute`, `roofline`,
`program_seconds_per_op`); the other kinds
(`device_busy_per_op`, `mfu`, `upload_floor`) read the device trace and the
harness's own clock. A metric file of a kind this file does not know fails by
name: give the new kind its reader's check here.

Each cell runs once per module at toy size on the CPU (rows, columns, `k`,
`maxIter` cut to its family's toy size; every other parameter the
configuration's own; a supervised family gets toy labels from the toy table,
handed over as the Arrow table its cell hands over): a cold operation, then a warm one, which is the one
read, as the harness reads a window after its warm-up. Counts and names only: nothing here is a speed.
"""

import json
import math
import os
import re

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, COLS, MAX_K, MAX_ITER = 512, 16, 4, 3
# a family whose cell sits on the far side of one of the program's shape tests
# keeps that side at toy size: `kmeans_wide` ranks Lloyd's assignment at three
# passes, which `ops/kmeans.py::_second_look_rows` engages from 128 centres on
# 524,288 centre coordinates (256 x 4096 floats here: 4 MB); the configuration
# `pca_k3_d3000` leaves the Pallas Gram kernel for the XLA program past
# `ops/pallas_xtwx.py::MAX_FUSED_COLS` = 512 columns, and that program
# multiplies only the upper column blocks of the Gram matrix from
# `autotune/defaults.py::GRAM_TRIANGLE_MIN_COLS` = 576 columns on
TOY = {"kmeans_wide": {"rows": 256, "cols": 4096, "max_k": 128},
       "pca_k3_d3000": {"rows": 256, "cols": 640},
       "forest": {"num_trees": 2, "max_depth": 4}}

# (metric, cell) pairs whose counter reads 0 in that cell BY DESIGN: the
# program counts the same name under another label there
READS_ZERO = {
    ("fit_lloyd_assign3_per_op", "kmeans_k20_d128.fit"): {"passes": "6"},
}

# the chunked upload's sizes at toy size (parallel/partitioner.py: 256 MiB, 32
# MiB, 1,024 rows on the chip): every toy table goes up in two to four chunks
# and every toy weight, label and centre in one put
CHUNKING = {"CHUNK_MIN_BYTES": 16 << 10, "CHUNK_BYTES": 8 << 10, "CHUNK_ALIGN_ROWS": 128}

# kinds that read the harness's clock or the device trace, nothing of the program
HARNESS_KINDS = {"device_busy_per_op", "mfu", "upload_floor"}


def _load(*rel):
    with open(os.path.join(REPO, *rel)) as f:
        return json.load(f)


BENCH = _load("BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIG_FILES = {c["name"]: c["file"] for c in BENCH["configs"]}


def _pairs():
    out = []
    for entry in BENCH["per_layer"]:
        spec = _load("cellbench", "metrics", entry["name"] + ".json")
        if spec["kind"] in HARNESS_KINDS:
            continue
        for cell in entry["workloads"]:
            out.append(pytest.param(entry, spec, cell, id=f"{entry['name']}-{cell}"))
    return out


# ------------------------------------------------------- the file's own form
# what the driver refuses BENCHMARK.json for before any run (PR 38's first
# hand-in: a `why` of 210 characters): names, units, one-line texts of at most
# 200 characters, just the keys an entry may have
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
_UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
_KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
         "workloads": {"name", "config", "traffic", "chips", "why"},
         "end_to_end": {"name", "unit", "better", "bound", "source"},
         "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def _is_line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and text.isprintable() and "\t" not in text)


@pytest.mark.parametrize("section", sorted(_KEYS))
def test_every_entry_of_the_benchmark_file_has_the_form_the_driver_admits(section):
    entries = BENCH[section]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names), names
    for e in entries:
        assert set(e) - {"workloads"} == _KEYS[section], (e["name"], sorted(e))
        assert _NAME.match(e["name"]), e["name"]
        for key in ("why", "source", "layer"):
            if key in e and not (section in ("end_to_end", "per_layer") and key == "source"):
                assert _is_line(e[key]), (
                    f"{section} {e['name']}: `{key}` has {len(e[key])} characters, "
                    "the driver admits 1 to 200 printable ones on one line")
        if "unit" in e:
            assert _UNIT.match(e["unit"]) and e["better"] in ("lower", "higher"), e
            assert e["source"] in ("device_trace", "program_span", "program_counter",
                                   "host_clock"), e
        for cell in e.get("workloads", []):
            assert cell in CELLS, (e["name"], cell)
        for key in [e.get("config"), e.get("traffic"), *e.get("reduced", [])]:
            assert key is None or _NAME.match(key), (e["name"], key)
        assert len(e.get("reduced", [])) <= 16
    if section == "configs":
        files = [e["file"] for e in entries]
        assert len(set(files)) == len(files)
        for e in entries:
            assert e["file"].split("/")[0] in BENCH["paths"], e["file"]
            assert os.path.isfile(os.path.join(REPO, e["file"])), e["file"]
            assert any(w["config"] == e["name"] for w in BENCH["workloads"]), e["name"]
    if section == "workloads":
        assert {w["config"] for w in entries} <= set(CONFIG_FILES)
        pairs = [(w["config"], w["traffic"]) for w in entries]
        assert len(set(pairs)) == len(pairs) and all(w["chips"] in (1, 4) for w in entries)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


# ------------------------------------------------------------- the toy runs


def _build(cfg, chips):
    """The configuration's estimator with its own parameters, sizes cut."""
    from spark_rapids_ml_tpu.classification import LogisticRegression, RandomForestClassifier
    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import PCA

    families = {"kmeans": KMeans, "kmeans_wide": KMeans, "pca": PCA,
                "logreg": LogisticRegression, "forest": RandomForestClassifier}
    if cfg["estimator"] not in families:
        pytest.fail(f"configuration names estimator family {cfg['estimator']!r}: "
                    "tests/test_benchmark_contract.py does not know how to build it")
    params = dict(cfg["params"])
    if "k" in params:
        max_k = TOY.get(cfg["estimator"], {}).get("max_k", MAX_K)
        params["k"] = min(int(params["k"]), max_k)
    if "maxIter" in params:
        params["maxIter"] = min(int(params["maxIter"]), MAX_ITER)
    if "numTrees" in params:
        toy = TOY[cfg["estimator"]]
        params["numTrees"] = min(int(params["numTrees"]), toy["num_trees"])
        params["maxDepth"] = min(int(params["maxDepth"]), toy["max_depth"])
    if cfg.get("seed_param"):
        params[cfg["seed_param"]] = 7
    return families[cfg["estimator"]](num_workers=chips, **params)


def _dataset(cfg, X):
    """What the cell's `fit` is handed: the bare table, or for a family that
    takes a label an Arrow table of the table's rows (a zero-copy view, as
    `cellbench/estimators/logreg.py` makes it) and 0/1 labels drawn from them."""
    if "labelCol" not in cfg["params"]:
        return X
    import pyarrow as pa

    rng = np.random.default_rng(34)
    logits = X @ rng.normal(size=X.shape[1]) * (2.0 / np.sqrt(X.shape[1]))
    labels = (rng.random(len(X)) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    features = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), X.shape[1])
    return pa.table({cfg["params"]["featuresCol"]: features,
                     cfg["params"]["labelCol"]: pa.array(labels)})


def _programs_called(counters):
    """Optimized-HLO text of every executable of the kernels the run called."""
    from spark_rapids_ml_tpu.observability import device, split_label_key

    called = {split_label_key(key)[1].get("kernel") for key in counters
              if split_label_key(key)[0] == "device.kernel_calls"}
    return [entry["exe"].as_text() for kernel in list(device._kernels)
            if kernel.name in called for entry in list(kernel._cache.values())]


def _run_cell(cell_name):
    from spark_rapids_ml_tpu import config, profiling
    from spark_rapids_ml_tpu.observability import device
    from spark_rapids_ml_tpu.observability.export import iter_spans
    from spark_rapids_ml_tpu.observability import runs as obs_runs
    from spark_rapids_ml_tpu.ops import pallas_histogram, pallas_logistic
    from spark_rapids_ml_tpu.parallel import partitioner

    cell = CELLS[cell_name]
    cfg = _load(CONFIG_FILES[cell["config"]])
    if cell["traffic"] not in ("fit", "transform"):
        pytest.fail(f"cell {cell_name} has traffic {cell['traffic']!r}: "
                    "tests/test_benchmark_contract.py knows fit and transform")
    toy = TOY.get(cell["config"], TOY.get(cfg["estimator"], {}))
    X = np.random.default_rng(30).normal(
        size=(toy.get("rows", ROWS), toy.get("cols", COLS))).astype(np.float32)
    # on the chip `auto` takes the Pallas Gram kernel; off it only "1" does
    # (interpret mode), and the benchmark's `_xtx_jit` lives in that kernel
    settings = {**cfg.get("program_settings", {}), "pallas_xtwx": "1"}
    for key, value in settings.items():
        config.set(key, value)
    # likewise the quasi-Newton fit's one-read evaluation: its gate has no
    # setting, so its platform test says what the chip would (the kernel runs
    # interpreted here, inside the same `_qn_fit` program)
    on_tpu, pallas_logistic._on_tpu = pallas_logistic._on_tpu, lambda: True
    # and the forest's level histogram: `hist_gate` says what the chip would,
    # and both Pallas forms run interpreted inside the same `build_tree` program
    hist_on_tpu, pallas_histogram._on_tpu = pallas_histogram._on_tpu, lambda: True
    # and the upload's: its gate has no setting either, so its platform test
    # says what the chip would and its sizes are cut to the toy table's
    upload = {name: getattr(partitioner, name) for name in (*CHUNKING, "_host_aliased")}
    for name, value in {**CHUNKING, "_host_aliased": lambda device: False}.items():
        setattr(partitioner, name, value)
    # every wait of the operation for something on the device, with the spans
    # open around it on the waiting thread
    import jax

    waits = []
    block_until_ready = jax.block_until_ready

    def recording(x):
        waits.append(([(node.name, dict(node.attrs)) for node in obs_runs._span_stack()],
                      [tuple(a.shape) for a in jax.tree_util.tree_leaves(x)
                       if isinstance(a, jax.Array)]))
        return block_until_ready(x)

    jax.block_until_ready = recording
    device.reset_device_plane()  # the cold operation compiles, whatever ran before
    try:
        estimator = _build(cfg, int(cell["chips"]))
        dataset = _dataset(cfg, X)
        model = estimator.fit(dataset) if cell["traffic"] == "transform" else None

        def operate():
            return estimator.fit(dataset) if cell["traffic"] == "fit" else model.transform(X)

        cold_before = dict(profiling.counter_totals())
        operate()
        before = dict(profiling.counter_totals())
        del waits[:]
        result = operate()
        after = dict(profiling.counter_totals())
    finally:
        jax.block_until_ready = block_until_ready
        for name, value in upload.items():
            setattr(partitioner, name, value)
        pallas_logistic._on_tpu = on_tpu
        pallas_histogram._on_tpu = hist_on_tpu
        for key in settings:
            config.unset(key)
    fitted = result if cell["traffic"] == "fit" else model
    report = fitted.fit_report_ if cell["traffic"] == "fit" else fitted.transform_report_
    counters = dict(report["metrics"].get("counters") or {})
    return {
        "cell": cell_name,
        "estimator": type(estimator).__name__,
        "traffic": cell["traffic"],
        "model": fitted,
        "report_counters": counters,
        "spans": {s["name"] for s in iter_spans(report)},
        "cold_before": cold_before, "before": before, "after": after,
        "programs": _programs_called(counters),
        "table_shape": X.shape, "has_label": "labelCol" in cfg["params"], "waits": list(waits),
        # a forest's row statistics and each tree's row weights go up too
        "small_puts": 1 + estimator.getNumTrees() if cfg["estimator"] == "forest" else 0,
    }


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(cell_name):
        if cell_name not in cache:
            cache[cell_name] = _run_cell(cell_name)
        return cache[cell_name]

    return get


@pytest.fixture(scope="module")
def emitted():
    """The metric names the package's source emits, as the analyzer's
    metric-contract pass harvests them."""
    import sys

    sys.path.insert(0, REPO)
    from tools.analysis.core import ProjectIndex
    from tools.analysis.metrics import _harvest_emissions

    index = ProjectIndex(REPO, targets=("spark_rapids_ml_tpu",))
    return {e.name for mod in index.files for e in _harvest_emissions(mod)}


# ------------------------------------------- what each reader kind would find


def _total(counters, name, labels):
    from spark_rapids_ml_tpu.observability import split_label_key

    total = 0.0
    for key, value in counters.items():
        base, have = split_label_key(key)
        if base == name and all(have.get(k) == v for k, v in labels.items()):
            total += float(value)
    return total


def _check_host_usage(entry, spec, counters):
    """What a reader would sum of a `host.*` counter: the host's usage over a
    flagged span or the run scope (observability/runs.py). The label sets that
    include the metric's labels are there, each a finite difference that is
    never negative; 0 is a sound reading (a toy wait takes no fault, no switch
    and no system time), but a whole run that used no CPU is not."""
    from spark_rapids_ml_tpu.observability import split_label_key

    labels = spec.get("labels", {})
    found = {}  # key -> (value, the key's `waits`)
    for key, value in counters.items():
        name, have = split_label_key(key)
        if name == spec["counter"] and all(have.get(k) == v for k, v in labels.items()):
            found[key] = (float(value), have["waits"])
    assert found, (
        f"{entry['name']}: no `{spec['counter']}` with labels {labels}: "
        f"{sorted(k for k in counters if k.startswith(spec['counter']))}")
    assert all(math.isfinite(v) and v >= 0 for v, _ in found.values()), found
    assert len({w for _, w in found.values()}) == 1, (
        f"{entry['name']} sums spans of different waits: {sorted(found)}")
    read = sum(v for v, _ in found.values())
    if spec["counter"] == "host.cpu_seconds" and labels.get("waits") == "run":
        assert read > 0, found
    return read


def _check_upload_chunks(entry, spec, run, added):
    """What a reader would sum of `h2d.chunks{site=}`: what the gate beside it
    says was done (`h2d.chunk_gate{site=,chunked=,reason=}`, once a put). Here
    the table alone is over the toy threshold: ONE chunked put of as many
    chunks as the toy sizes make of it, and every other put of the operation
    whole because of its `bytes`; `h2d.bytes` still counts each array once."""
    site = spec["labels"]["site"]
    rows, cols = run["table_shape"]
    fit, tile = max(1, CHUNKING["CHUNK_BYTES"] // (4 * cols)), CHUNKING["CHUNK_ALIGN_ROWS"]
    per = fit - fit % tile if fit >= tile else 1 << (fit.bit_length() - 1)
    assert added("h2d.chunk_gate", {"site": site, "chunked": "true", "reason": "ok"}) == 1
    assert added("h2d.chunks", {"site": site}) == -(-rows // per) >= 2
    whole = added("h2d.chunk_gate", {"site": site, "chunked": "false"})
    assert whole == added("h2d.chunk_gate", {"site": site, "chunked": "false", "reason": "bytes"})
    assert whole == (0 if site == "transform" else  # weights, label, a family's own
                     (2 if run["has_label"] else 1) + run["small_puts"])
    assert added("h2d.bytes", {"site": site}) >= 4 * rows * cols
    assert added("span.calls", {"span": "h2d.put"}) == 1 + whole


def _check_report_counter(entry, spec, run, emitted):
    from spark_rapids_ml_tpu.observability import label_key

    assert run["traffic"] == "fit", "report_counter_per_op reads fit_report_"
    if spec["counter"].startswith("host."):
        _check_host_usage(entry, spec, run["report_counters"])
        return
    if spec["counter"] == "h2d.chunks":
        _check_upload_chunks(entry, spec, run,
                             lambda name, labels: _total(run["report_counters"], name, labels))
        return
    labels = spec.get("labels", {})
    key = label_key(spec["counter"], labels)
    counters = run["report_counters"]
    other = READS_ZERO.get((entry["name"], run["cell"]))
    if other is not None:
        assert key not in counters and counters[label_key(spec["counter"], other)] == 1, (
            f"{entry['name']} should read 0 in {run['cell']}: "
            f"{sorted(k for k in counters if k.startswith(spec['counter']))}")
        return
    if not labels and _total(counters, spec["counter"], {}) == 0:
        # a counter read whole, of something that did not happen in this fit
        # (the in-core path makes no host copy): the reader reads 0, as on the
        # chip. The name must still be one the package emits.
        assert spec["counter"] in emitted, (
            f"{entry['name']}: no library code emits `{spec['counter']}`")
        return
    # the reader sums the label sets that INCLUDE the metric's labels
    # (`pca.gram_form{blocks=,form=triangle}` under `{"form": "triangle"}`)
    read = _total(counters, spec["counter"], labels)
    assert read > 0, (
        f"{entry['name']}: no `{key}` in fit_report_: "
        f"{sorted(k for k in counters if k.startswith(spec['counter']))}")
    if entry["unit"] == "count" and not spec.get("total"):
        # an indicator of a path or a form: once a fit. A metric whose file
        # says `"total": true` reads a fit's total (the solver's evaluations)
        assert read == 1, f"{key} counts {read} in one fit"


def _check_counter_delta(entry, spec, run, emitted):
    name = spec["counter"]
    cold = _total(run["before"], name, {}) - _total(run["cold_before"], name, {})
    assert cold > 0, f"{entry['name']}: the cold operation added nothing to `{name}`"
    warm = _total(run["after"], name, {}) - _total(run["before"], name, {})
    assert warm == 0, f"{entry['name']}: the warm operation added {warm} to `{name}`"


def _check_counter_delta_per_op(entry, spec, run, emitted):
    from spark_rapids_ml_tpu.observability import label_key

    if spec["counter"].startswith("host."):
        # the process's totals move by what the warm operation used
        added = (_check_host_usage(entry, spec, run["after"])
                 - _check_host_usage(entry, spec, run["before"]))
        assert added >= 0, f"{entry['name']}: the warm operation added {added}"
        if spec.get("labels", {}).get("waits") == "run":
            assert added > 0, f"{entry['name']}: a whole transform used no CPU"
        return
    if spec["counter"] == "h2d.chunks":
        _check_upload_chunks(entry, spec, run, lambda name, labels: (
            _total(run["after"], name, labels) - _total(run["before"], name, labels)))
        return
    key = label_key(spec["counter"], spec.get("labels", {}))
    assert key in run["after"], f"{entry['name']}: no `{key}` among the process's counters"
    assert run["after"][key] - run["before"].get(key, 0) > 0, key


def _check_span(entry, spec, run, emitted):
    name = spec["span"].format(estimator=run["estimator"])
    assert name in run["spans"], (
        f"{entry['name']}: no span `{name}` in the run's trace tree: {sorted(run['spans'])}")


# what the estimator families under cellbench/estimators put under each name
MODEL_ATTRIBUTES = {"n_iter": lambda model: int(
    model.summary.numIter if model.hasSummary else model.get_model_attributes()["n_iter"])}


def _check_model_attribute(entry, spec, run, emitted):
    if spec["attribute"] not in MODEL_ATTRIBUTES:
        pytest.fail(f"{entry['name']} reads model attribute {spec['attribute']!r}: "
                    "tests/test_benchmark_contract.py does not know where the program keeps it")
    assert 1 <= MODEL_ATTRIBUTES[spec["attribute"]](run["model"]) <= MAX_ITER


def _check_roofline(entry, spec, run, emitted):
    # the trace names a compiled program `jit_<function>` and an operation
    # inside it by the scope `jit(<function>)` of the jit it was traced under
    if "program" in spec:
        want = f"HloModule jit_{spec['program']}"
    else:
        want = f"jit({spec['op']})"
    assert any(want in text for text in run["programs"]), (
        f"{entry['name']}: none of the {len(run['programs'])} programs the run "
        f"called carries `{want}`")


def _check_program_seconds(entry, spec, run, emitted):
    # the roofline reader's `program` without a floor: the same name, the same way
    assert "program" in spec and "op" not in spec, spec
    _check_roofline(entry, spec, run, emitted)


CHECKS = {
    "report_counter_per_op": _check_report_counter,
    "counter_delta": _check_counter_delta,
    "counter_delta_per_op": _check_counter_delta_per_op,
    "span_seconds_per_op": _check_span,
    "model_attribute": _check_model_attribute,
    "roofline": _check_roofline,
    "program_seconds_per_op": _check_program_seconds,
}


@pytest.mark.parametrize("entry,spec,cell", _pairs())
def test_the_program_gives_the_metric_what_it_reads(entry, spec, cell, runs, emitted):
    if spec["kind"] not in CHECKS:
        pytest.fail(f"cellbench/metrics/{entry['name']}.json is of kind {spec['kind']!r}: "
                    "tests/test_benchmark_contract.py has no check for that reader")
    CHECKS[spec["kind"]](entry, spec, runs(cell), emitted)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_upload_is_waited_for_under_h2d_wait_and_nowhere_before(cell, runs):
    """The chunks and their assembly are dispatched inside `h2d.put` and waited
    for by nobody there: the one wait for the table is the innermost span
    `h2d.wait` of the cell's site, flagged `waits=upload`, which is what
    `*_upload_wait_s` and the `waits=upload` usage counters read."""
    run = runs(cell)
    site = run["traffic"]
    for stack, _ in run["waits"]:
        assert "h2d.put" not in [name for name, _ in stack], stack
    for_the_table = [stack for stack, shapes in run["waits"] if run["table_shape"] in shapes]
    assert for_the_table, run["waits"]
    name, attrs = for_the_table[0][-1]  # the first wait that holds the table
    assert (name, attrs.get("site"), attrs.get("waits")) == ("h2d.wait", site, "upload")
