"""One cell's operations as the harness makes them, outside the benchmark (as
tools/upload_probe.py is): phases of N operations each, untraced or under the
profiler as the harness starts it, with every operation's own `host.*`
counters and flagged spans' seconds read from its `fit_report_` /
`transform_report_`. What PERF.md §6 PR 36 ranks the upload's wait against
(several processes of one cell, a fit at a time) and how it tells the
profiler's share of `fit_upload_cpu_s` (phases `u,t,u` in one process).

    chiprun -- python -m tools.host_usage_run <cell> <seed> <n_ops> <phase>[,<phase>...] [rows]

phase: u (untraced) or t (traced). `rows` makes it a rehearsal at that size on
whatever backend is there. One JSON line an operation and one a phase (the
per-layer metrics' means as their readers compute them), also appended to
chiprun_out/host_usage.jsonl.
"""

import gc
import glob
import json
import os
import shutil
import sys
import time

import numpy as np

from cellbench import data, harness
from cellbench.readers.report_counter_per_op import split_key, total

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NEW = [os.path.basename(p)[:-5] for p in sorted(glob.glob(os.path.join(ROOT, "cellbench/metrics/*.json")))]


def say(**line):
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "host_usage.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")


def per_span(counters):
    out = {}
    for key, v in counters.items():
        name, labels = split_key(key)
        if name.startswith("host."):
            short = name[5:] + ("." + labels.get("mode", labels.get("kind")) if ("mode" in labels or "kind" in labels) else "")
            out.setdefault(labels["span"], {})[short] = v
        elif name == "span.seconds" and labels.get("span") in (
                "h2d.wait", "h2d.put", "fit.stage", "kmeans.lloyd", "pca.cov", "pca.eig.solve",
                "logistic.solve"):
            out.setdefault(labels["span"], {})["seconds"] = v
    return out


def main(cell_name, seed, n_ops, phases, rows=None):
    bench_json = os.path.join(ROOT, "BENCHMARK.json")
    spec = harness.load_cell(cell_name, bench_json)
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    rehearsal = rows is not None
    jax, devices, _ = harness.start_jax(int(cell["chips"]), rehearsal)
    devices = devices[:int(cell["chips"])]
    est = harness._module("estimators", cfg["estimator"], spec["dirs"])
    from spark_rapids_ml_tpu import config as program_config

    for key, value in cfg.get("program_settings", {}).items():
        program_config.set(key, value)
    cols = cfg["cols"] if not rehearsal else min(cfg["cols"], 64)
    X, _ = data.make_table(cfg["table"], rows or cfg["rows"], cols, seed, devices)
    params = dict(cfg["params"])
    if rehearsal:
        if "k" in params:
            params["k"] = min(params["k"], 4)
        if "maxIter" in params:
            params["maxIter"] = min(params["maxIter"], 3)
    if cfg.get("seed_param"):
        params[cfg["seed_param"]] = int(seed) % 2147483647
    operation = traffic["operation"]
    estimator = est.build(params, len(devices))
    model = estimator.fit(X) if "fit" in traffic.get("setup", []) else None

    def operate():
        with jax.profiler.TraceAnnotation(f"cellbench.{operation}"):
            return estimator.fit(X) if operation == "fit" else model.transform(X)

    for _ in range(int(traffic.get("warmup_ops", 1))):
        operate()
    gc.collect()
    gc.freeze()
    mine = [json.load(open(os.path.join(ROOT, "cellbench/metrics", m + ".json"))) for m in NEW]
    mine = [m for m in mine if m.get("counter", "").startswith("host.")
            or m["name"] in ("fit_stage_s", "fit_upload_dispatch_s", "fit_upload_wait_s", "transform_upload_wait_s")]
    want = "report_counter_per_op" if operation == "fit" else "counter_delta_per_op"
    mine = [m for m in mine if m["kind"] == want]
    trace_dir = os.path.join(ROOT, ".cellbench_trace")
    for phase in phases:
        if phase == "t":
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        reports = []
        t_phase = time.perf_counter()
        try:
            for i in range(n_ops):
                t0 = time.perf_counter()
                result = operate()
                op_s = time.perf_counter() - t0
                report = result.fit_report_ if operation == "fit" else model.transform_report_
                counters = dict(report["metrics"].get("counters") or {})
                reports.append(counters)
                say(cell=cell_name, seed=seed, phase=phase, op=i, op_s=op_s, spans=per_span(counters))
        finally:
            if phase == "t":
                jax.profiler.stop_trace()
                shutil.rmtree(trace_dir, ignore_errors=True)
        means = {m["name"]: float(np.mean([total(c, m["counter"], m.get("labels", {})) for c in reports]))
                 for m in mine}
        say(cell=cell_name, seed=seed, phase=phase, summary=True, ops=n_ops,
            phase_s=time.perf_counter() - t_phase, platform=devices[0].platform, means=means)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4].split(","),
         int(sys.argv[5]) if len(sys.argv) > 5 else None)
