"""One cell, one run: set-up, a window of one kind of operation, the reading of
the trace, and the comparison that decides `correct`.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is found by name: `configs/<config>.json`, `traffic/<traffic>.json`,
`metrics/<metric>.json`, `readers/<kind>.py`, `estimators/<estimator>.py`. The
cells and which metrics each reports come from `BENCHMARK.json`.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Ctx:
    """What a per-layer reader may read."""
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    est: Any
    chips: int
    on_chip: bool
    peaks: Optional[Dict[str, float]]
    ops: int = 0
    window_s: float = 0.0
    events: List[Any] = dataclasses.field(default_factory=list)
    lo: float = 0.0
    hi: float = 0.0
    h2d_bytes_per_s: float = 0.0
    report_counters: List[Dict[str, float]] = dataclasses.field(default_factory=list)
    counters_before: Optional[Dict[str, float]] = None
    counters_after: Optional[Dict[str, float]] = None
    op_outputs: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


# ------------------------------------------------------------------ the files


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_json: str) -> Dict[str, Any]:
    """The cell's entry, its configuration and traffic files, and the metrics
    `BENCHMARK.json` makes it report. Files are looked for by name under every
    directory of `paths`, the newest first, then beside this file."""
    bench = _load(bench_json)
    base = os.path.dirname(os.path.abspath(bench_json))
    dirs = [os.path.join(base, p) for p in reversed(bench["paths"])] + [HERE]
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_json}")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cfg_path = os.path.join(base, files[cell["config"]])
    if not os.path.exists(cfg_path):
        cfg_path = os.path.join(ROOT, files[cell["config"]])
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)]
    return {
        "cell": cell,
        "cfg": _load(cfg_path),
        "traffic": _load(_find("traffic", cell["traffic"] + ".json", dirs)),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
        "dirs": dirs,
    }


def _find(kind: str, filename: str, dirs: List[str]) -> str:
    """The first `<dir>/<kind>/<filename>` that exists."""
    for d in dirs:
        path = os.path.join(d, kind, filename)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no {kind}/{filename} under any of {dirs}")


def _module(kind: str, name: str, dirs: List[str]):
    """The module `<kind>/<name>.py`, found like a data file."""
    path = _find(kind, name + ".py", dirs)
    if os.path.dirname(os.path.dirname(path)) != HERE:
        spec = importlib.util.spec_from_file_location(f"cellbench.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        return mod
    return importlib.import_module(f"cellbench.{kind}.{name}")


# ------------------------------------------------------------------ the device


def start_jax(chips: int, rehearsal: bool):
    """Import JAX, point its persistent cache at a fixed directory inside the
    checkout, and refuse anything but a TPU with enough chips."""
    import jax

    imported_at = time.perf_counter()
    if not rehearsal:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(str(e)) from e
    if not rehearsal and devices[0].platform != "tpu":
        raise NoAccelerator(f"platform is {devices[0].platform!r}, not 'tpu'")
    if len(devices) < chips:
        raise NoAccelerator(f"{len(devices)} device(s), the cell asks for {chips}")
    return jax, devices, imported_at


def time_upload(jax, X: np.ndarray, devices) -> List[float]:
    """Bytes per second of a `device_put` of the table, rows over the chips,
    twice: the first pays what the process pays once (staging buffers)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    sharding = NamedSharding(Mesh(np.array(devices), ("data",)), PartitionSpec("data", None))
    rates = []
    for _ in range(2):
        t0 = time.perf_counter()
        on_dev = jax.device_put(X, sharding)
        on_dev.block_until_ready()
        rates.append(X.nbytes / (time.perf_counter() - t0))
        on_dev.delete()
    return rates


def program_counters() -> Dict[str, float]:
    from spark_rapids_ml_tpu import profiling

    return dict(profiling.counter_totals())


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


# ------------------------------------------------------------------ one run


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             bench_json: str = os.path.join(ROOT, "BENCHMARK.json"),
             rehearsal: bool = False,
             control: Any = False, t0: Optional[float] = None,
             keep_trace: Optional[str] = None,
             settings: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell once; the dict is the result line. `rehearsal=True` skips
    the look for a chip (tests): no time, rate or device share is reported
    then. `control=True` runs the cell's lower-precision control as its
    configuration names it (`limits.py`, tests), which has to come out as not
    correct; `control="reference"` puts the bfloat16 reference in the program's
    place whatever the file names; `settings` are further program settings for
    such a reading."""
    t0 = time.perf_counter() if t0 is None else t0
    stamps = {"start": time.perf_counter() - t0}
    spec = load_cell(workload, bench_json)
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    chips = int(cell["chips"])
    jax, devices, imported_at = start_jax(chips, rehearsal)
    stamps["jax_imported"] = imported_at - t0
    stamps["jax_devices"] = time.perf_counter() - t0
    devices = devices[:chips]
    on_chip = devices[0].platform == "tpu"
    est = _module("estimators", cfg["estimator"], spec["dirs"])
    operation = traffic["operation"]
    ctrl = (cfg.get("control") or {}).get(operation, {}) if control else {}
    if control == "reference":
        ctrl = {"reference": "bf16"}

    from spark_rapids_ml_tpu import config as program_config

    settings = {**cfg.get("program_settings", {}), **ctrl.get("program_settings", {}),
                **(settings or {})}
    for key, value in settings.items():
        program_config.set(key, value)
    try:
        stamps["program_import"] = time.perf_counter() - t0
        return _run(jax, devices, spec, est, operation, seed, seconds, trace, on_chip,
                    rehearsal, bool(ctrl.get("reference")), t0, keep_trace, stamps)
    finally:
        for key in settings:
            program_config.unset(key)


def _run(jax, devices, spec, est, operation, seed, seconds, trace, on_chip,
         rehearsal, reference_control, t0, keep_trace, stamps) -> Dict[str, Any]:
    from . import data
    from . import trace as reducer
    from . import work

    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    chips = len(devices)
    peaks = work.load_peaks(devices[0].device_kind) if on_chip else None
    ctx = Ctx(cfg=cfg, traffic=traffic, est=est, chips=chips, on_chip=on_chip, peaks=peaks)

    # ---- set-up: table, upload rate, estimator, what the traffic needs, warm-up
    parts = ctx.notes["setup_parts_s"] = stamps
    X, _ = data.make_table(cfg["table"], cfg["rows"], cfg["cols"], seed, devices)
    parts["table"] = time.perf_counter() - t0
    if on_chip:
        ctx.notes["h2d_bytes_per_s_each"] = time_upload(jax, X, devices)
        ctx.h2d_bytes_per_s = max(ctx.notes["h2d_bytes_per_s_each"])
    parts["upload_timing"] = time.perf_counter() - t0
    params = dict(cfg["params"])
    if cfg.get("seed_param"):
        params[cfg["seed_param"]] = int(seed) % 2147483647

    def refit(overrides: Dict[str, Any]):
        return est.build({**params, **overrides}, chips).fit(X)

    estimator = est.build(params, chips)
    model = estimator.fit(X) if "fit" in traffic.get("setup", []) else None
    span = f"cellbench.{operation}"

    def operate():
        with jax.profiler.TraceAnnotation(span):
            return estimator.fit(X) if operation == "fit" else model.transform(X)

    parts["traffic_setup"] = time.perf_counter() - t0
    for _ in range(int(traffic.get("warmup_ops", 1))):
        operate()
    gc.collect()
    gc.freeze()
    ctx.counters_before = program_counters()
    setup_s = time.perf_counter() - t0

    # ---- the window: one kind of operation, back to back, one client
    trace_dir = os.path.join(ROOT, ".cellbench_trace") if trace else None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    attempted = failed = 0
    answers: List[Any] = []  # what the comparison is given once the window has closed
    every = max(1, int(traffic.get("compare_every", 1)))
    ends: List[float] = []
    start = time.perf_counter()
    try:
        while True:
            attempted += 1
            result = operate()
            now = time.perf_counter()
            if operation == "fit":
                outputs = est.fit_outputs(result)
                ok = est.did_all_work(outputs, params)
                ctx.report_counters.append(
                    dict(result.fit_report_["metrics"].get("counters") or {}))
            else:
                outputs = {}
                ok = len(result) == len(X)
            ctx.op_outputs.append(outputs)
            failed += 0 if ok else 1
            ends.append(now)
            closing = now - start >= seconds
            # a sample drawn from the seed, the last operation always in it
            if closing or (attempted + int(seed)) % every == 0:
                answers.append(outputs if operation == "fit" else result)
            if closing:
                break
    finally:
        if trace:
            jax.profiler.stop_trace()
    ctx.window_s = now - start
    ctx.ops = attempted
    op_s = np.diff([start] + ends)
    ctx.notes["op_s"] = [float(np.min(op_s)), float(np.median(op_s)), float(np.max(op_s))]
    ctx.counters_after = program_counters()
    peak_bytes = memory_peak(devices) if on_chip else 0

    # ---- the trace
    device: Dict[str, Any] = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": peak_bytes,
    }
    breakdown = None
    if trace:
        names = [f"{est.ESTIMATOR}.prepare", f"{est.ESTIMATOR}.fit", "transform.batch",
                 "transform.predict"]
        xplane = reducer.find_xplane(trace_dir)
        ctx.events = reducer.load_events(xplane, names)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, os.path.join(keep_trace, f"{cell['name']}.xplane.pb"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.lo, ctx.hi = reducer.window_of(ctx.events, span)
        if on_chip:
            device["busy_s"] = reducer.busy_seconds(ctx.events, ctx.lo, ctx.hi)
            device["window_s"] = (ctx.hi - ctx.lo) / 1e9
            breakdown = {
                "device_ops": reducer.top_device_ops(ctx.events, ctx.lo, ctx.hi),
                "idle_gaps": reducer.idle_gaps_by_span(ctx.events, ctx.lo, ctx.hi),
            }

    # ---- the comparison, once the window has closed and the peak is read
    limits = cfg["limits"][operation]
    if operation == "fit":
        readings = est.check_fit(X, answers, refit, params, control=reference_control)
    else:
        readings = est.check_transform(X, model, answers, params, control=reference_control)
    ctx.notes["answers_compared"] = len(answers)
    checks = {name: {"value": float(max(r[name] for r in readings)), "limit": float(limit)}
              for name, limit in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # ---- the metrics
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in spec["per_layer"]:
            if rehearsal and m["source"] != "program_counter":
                continue
            mspec = _load(_find("metrics", m["name"] + ".json", spec["dirs"]))
            value = _module("readers", mspec["kind"], spec["dirs"]).read(ctx, mspec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    elif not rehearsal:
        rate = cfg["rows"] * (attempted - failed) / ctx.window_s
        if traffic.get("per_chip"):
            rate /= chips
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        metrics[traffic["rate_metric"]] = {"value": rate, "unit": units[traffic["rate_metric"]]}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}

    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    notes = {**ctx.notes, "window_s": ctx.window_s, "setup_s": setup_s,
             "h2d_bytes_per_s": ctx.h2d_bytes_per_s, "seed": int(seed)}
    print("notes " + json.dumps(notes), file=sys.stderr)
    return result


def report(result: Dict[str, Any]) -> None:
    """Each number compared beside its limit as the last lines on standard
    error, then the result as the last line on standard output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} value {c['value']:.6g} limit {c['limit']:.6g} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
