#!/usr/bin/env python
"""Flagship benchmark: distributed KMeans fit throughput + per-family secondaries.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N},
whose `secondary` names the device it ran on (`platform`, `device_kind`,
`device_count`).

Protocol follows the reference harness (reference python/benchmark/benchmark/base.py:
232-285: timed fit with quality score). The headline metric is Lloyd-iteration row
throughput — rows * iterations / wall-clock — which the north-star target tracks
(BASELINE.json: rows/sec/chip); per-family numbers land in `secondary`.

ONE process: `main()` imports JAX once, refuses to run unless
`jax.devices()[0].platform == "tpu"` (exit 2, no line — a measurement path that
finds no chip fails, it never falls back to the CPU), runs the units here, and
prints the line. A unit that raises is not caught: the traceback is the report
and the exit code is non-zero. Each completed unit is appended to a progress
JSONL file the moment it finishes, so a run killed at its time limit still
leaves what it measured on disk; the line is assembled from that file.

The compile cache lives where `JAX_COMPILATION_CACHE_DIR` says, else at
`<checkout>/.jax_cache` (spark_rapids_ml_tpu.utils.enable_compile_cache).

`vs_baseline`: the reference publishes no machine-readable numbers (BASELINE.md), so
the ratio is computed against a locally-recorded baseline in BENCH_BASELINE.json when
present (first run writes it), else 1.0.
"""

import functools
import json
import os
import sys
import time

import numpy as np

# Benchmark units, in priority order: cheap/high-value families land before the
# O(n*nq) kNN/ANN scans so a run that meets its deadline keeps the most evidence.
# "kmeans_headline" carries the headline metric; the rest merge into `secondary`.
UNITS = [
    "kmeans_headline",
    "pca",
    "logreg",
    "linreg",
    "rf",
    "umap",
    "dbscan",
    "fit_e2e",
    "cache",
    "ingest",
    "telemetry_overhead",
    "serving_qps",
    "serving_failover",
    "tracing_overhead",
    "continual",
    "large_k",
    "autotune",
    "knn",
    "ann",
    "ann_build",
    "wide256",
]

UNIT_START_MARGIN_S = 30.0  # don't start a unit with less than this left

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
# progress journal + assembled line land here (git-ignored)
RESULTS_DIR = os.path.join(REPO_ROOT, "benchmark", "results")


# --------------------------------------------------------------------- progress IO


def _flush_progress(path: str, entry: dict) -> None:
    """Append one JSON line and fsync, so what was measured survives a process
    killed right after."""
    entry = dict(entry, ts=round(time.time(), 2))
    with open(path, "a") as f:
        f.write(json.dumps(entry) + "\n")
        f.flush()
        os.fsync(f.fileno())


def _read_progress(path: str) -> dict:
    """Latest entry per unit (later lines win)."""
    state: dict = {}
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    e = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn write from a killed run
                state[e.get("unit", "?")] = e
    except OSError:
        pass
    return state


# -------------------------------------------------------------------------- units


def run_units(progress: str, deadline_ts: float, units=None) -> None:
    """Build data, run each unit in THIS process, flush results incrementally.
    A unit that raises propagates (no guard): the caller exits non-zero."""
    units = list(UNITS if units is None else units)

    import jax
    import jax.numpy as jnp

    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit
    from spark_rapids_ml_tpu.parallel.mesh import get_mesh, shard_array

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    n_chips = jax.device_count()

    # size to platform: HBM-filling on TPU (~6 GiB f32 design matrix per chip on a
    # 16 GiB v5e, leaving headroom for the one-hot update and compiler scratch),
    # small on CPU
    if on_tpu:
        n_rows, n_cols, k, iters = 12_000_000, 128, 20, 10
    else:
        n_rows, n_cols, k, iters = 100_000, 64, 8, 10

    # synthesize blobs ON DEVICE: host→device transfer is the enemy (and the metric
    # tracks compute, not ingest — the reference times cuML fit after cudf ingest
    # too). The init is k REAL ROWS of X (what k-means|| reduces to), NOT the true
    # centers: a near-optimal init converges in ~2 Lloyd iterations and the
    # whole-fit metric then measures per-fit constants instead of iteration
    # throughput (this exact distortion made the round-2 headline read 101M when
    # the steady-state rate of the same code was ~640M rows*iters/s).
    mesh = get_mesh()
    rowsh = NamedSharding(mesh, P("data", None))

    # only units in this set read the shared headline design matrix; a run
    # whose units all build their own data (rf/umap/dbscan/fit_e2e/wide256)
    # skips the ~6 GiB generation entirely
    NEED_X = {"kmeans_headline", "pca", "logreg", "linreg", "large_k", "knn",
              "ann", "ann_build"}
    need_data = bool(NEED_X & set(units))

    @functools.partial(jax.jit, out_shardings=(rowsh, None))
    def make_data(key):
        k1, k2, k3 = jax.random.split(key, 3)
        centers_true = jax.random.normal(k1, (k, n_cols), jnp.float32) * 5.0
        assign = jax.random.randint(k2, (n_rows,), 0, k)
        X = centers_true[assign] + jax.random.normal(k3, (n_rows, n_cols), jnp.float32)
        init = X[:k] * 1.0
        return X, init

    if need_data:
        Xd, init = make_data(jax.random.PRNGKey(0))
        Xd.block_until_ready()
        w = shard_array(np.ones((n_rows,), dtype=np.float32), mesh)
    else:
        Xd = init = w = None

    _flush_progress(
        progress,
        {
            "unit": "boot",
            "status": "done",
            "platform": platform,
            "device_kind": jax.devices()[0].device_kind,
            "n_chips": n_chips,
            "result": {"n_rows": n_rows, "n_cols": n_cols},
        },
    )

    def _timed(fn, repeats=3):
        """Median wall-clock of fn() (synced); fn returns arrays to sync on."""
        ts = []
        out = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), out

    from spark_rapids_ml_tpu.observability.device import (
        kernel_cost, platform_peaks,
    )

    # per-chip peaks of THIS device_kind (an unknown kind raises, never defaults)
    peak_flops, peak_bw, _ = platform_peaks()

    def _kmeans_rates(X_, w_, init_, n_, d_):
        """THE Lloyd timing recipe (protocol 2): whole-fit throughput (reference
        protocol base.py:232-285 times the whole fit) plus the steady-state
        marginal rate (full fit minus a 1-iter fit cancels per-fit constants)
        and the two-X-read HBM roofline fraction — one helper so the headline
        and the 256-col tier can never drift apart. The Lloyd step reads X twice
        per iteration (distance matmul + one-hot update) plus the (n, k)
        intermediates once each; peak_bw is per-chip HBM."""
        # compile warmups, untimed
        jax.block_until_ready(lloyd_fit(X_, w_, init_, 0.0, 1))
        jax.block_until_ready(lloyd_fit(X_, w_, init_, 0.0, iters))
        t_full, (centers_, inertia_, it_) = _timed(
            lambda: lloyd_fit(X_, w_, init_, 0.0, iters)
        )
        t_one, _ = _timed(lambda: lloyd_fit(X_, w_, init_, 0.0, 1))
        it_ = int(it_)
        whole = n_ * it_ / t_full / n_chips
        if it_ > 1:
            marg_t = max(t_full - t_one, 1e-9) / (it_ - 1)
            marginal = n_ / marg_t / n_chips
        else:
            # t_full - t_one is pure timing noise at n_iter=1; no marginal rate
            print(
                "bench: fit converged in <=1 iteration; marginal rate undefined",
                file=sys.stderr,
            )
            marg_t, marginal = None, None
        bytes_per_iter = 2 * n_ * d_ * 4 + 2 * n_ * k * 4
        roof = (
            (bytes_per_iter / peak_bw) / marg_t / n_chips
            if on_tpu and marg_t is not None
            else None
        )
        iter_ceiling = peak_bw / (2 * d_ * 4 + 2 * k * 4)
        return {
            "t_full": t_full,
            "centers": centers_,
            "inertia": inertia_,
            "n_iter": it_,
            "whole": whole,
            "marginal": marginal,
            "roofline_frac": roof,
            "whole_frac": whole / iter_ceiling if on_tpu else None,
        }

    def unit_kmeans_headline():
        hr = _kmeans_rates(Xd, w, init, n_rows, n_cols)
        fit_time, inertia, n_iter = hr["t_full"], hr["inertia"], hr["n_iter"]
        value = hr["whole"]

        # MEASURED MFU: analyzed flops of the lloyd executable from the device
        # plane's XLA cost_analysis capture (observability/device.py) over the
        # timed whole-fit window — replaces the round-3 hand-rolled analytic
        # estimate. The analysis runs on the post-partitioning per-device
        # module, so flops are already per-chip (no n_chips division), and
        # XLA counts a dynamic-trip while_loop body once, so this is a stable
        # lower bound; the bench gate tracks its direction.
        lloyd_rec = kernel_cost("kmeans.lloyd_fit")
        mfu = (
            lloyd_rec["flops"] / fit_time / peak_flops
            if lloyd_rec and lloyd_rec.get("flops") and peak_flops > 0
            else None
        )

        # profiler trace AFTER the timed region (trace capture inflates the run)
        from spark_rapids_ml_tpu.profiling import trace as xplane_trace

        trace_dir = "/tmp/srml_bench_xplane" if on_tpu else None
        if trace_dir:
            with xplane_trace(trace_dir):
                jax.block_until_ready(lloyd_fit(Xd, w, init, 0.0, iters))

        # secondary metric: the fast-math variant (assignment distances at MXU
        # bf16, model attributes still parity precision — config key fast_math)
        fast_fit = functools.partial(lloyd_fit, fast_math=True)
        jax.block_until_ready(fast_fit(Xd, w, init, 0.0, iters))
        fast_time, (_, _, n_iter_f) = _timed(lambda: fast_fit(Xd, w, init, 0.0, iters))
        fast_rate = n_rows * int(n_iter_f) / fast_time / n_chips

        # TPU-only: the fused pallas Lloyd variants at 6-pass parity precision —
        # weighted (measured slower than XLA at this small-k shape, see
        # ops/pallas_kmeans.py header) and masked/no-weight-stream (the (blk,1)-
        # operand elimination that took the Gram kernel 3x; candidate to displace
        # the XLA headline path). Each carries a live parity check (same n_iter,
        # inertia within fp32 tolerance). Not guarded: a Mosaic refusal fails
        # the unit, which is what it should do.
        def _pallas_variant(label, **variant_kw):
            from spark_rapids_ml_tpu.ops.pallas_kmeans import lloyd_fit_pallas

            mesh_obj = getattr(getattr(Xd, "sharding", None), "mesh", None)
            fit = functools.partial(
                lloyd_fit_pallas, mesh=mesh_obj,
                precision=jax.lax.Precision.HIGHEST, **variant_kw,
            )
            fit(Xd, w, init, 0.0, iters)  # compile warmup (returns host values)
            t, (c_v, in_v, it_v) = _timed(lambda: fit(Xd, w, init, 0.0, iters))
            it_v = int(it_v)
            if it_v <= 1:
                print(
                    f"bench: {label} fit converged in <=1 iteration; "
                    "whole-fit rate reflects per-fit constants only",
                    file=sys.stderr,
                )
            rate = n_rows * it_v / t / n_chips
            parity = bool(
                it_v == n_iter
                and abs(float(in_v) - float(inertia))
                <= 1e-4 * abs(float(inertia))
            )
            return rate, parity

        fused_rate = fused_parity = masked_rate = masked_parity = None
        if on_tpu:
            fused_rate, fused_parity = _pallas_variant("fused")
            masked_rate, masked_parity = _pallas_variant("masked", unit_mask=True)

        return {
            "_value": round(value, 1),
            "kmeans_marginal_rows_per_sec_per_chip": (
                round(hr["marginal"], 1) if hr["marginal"] is not None else None
            ),
            "kmeans_n_iter": n_iter,
            "kmeans_frac_of_ceiling": (
                round(hr["whole_frac"], 3) if hr["whole_frac"] is not None else None
            ),
            "kmeans_fast_math_rows_per_sec_per_chip": round(fast_rate, 1),
            "kmeans_fused_pallas_rows_per_sec_per_chip": (
                round(fused_rate, 1) if fused_rate is not None else None
            ),
            "fused_parity_ok": fused_parity,
            "kmeans_masked_pallas_rows_per_sec_per_chip": (
                round(masked_rate, 1) if masked_rate is not None else None
            ),
            "masked_parity_ok": masked_parity,
            "mfu": round(mfu, 6) if mfu is not None else None,
            "roofline_frac": (
                round(hr["roofline_frac"], 3)
                if hr["roofline_frac"] is not None
                else None
            ),
            # the north-star anchor: measured per-chip rate vs the A100 cuML
            # roofline estimate (same operational-intensity model; >=0.667
            # clears BASELINE's "within 1.5x of A100" bar — benchmark/a100_model.py).
            # Numerator is the MARGINAL (steady-state) rate, like the x256 tier:
            # the A100 roofline excludes per-fit constants, so dividing the
            # whole-fit rate by it would deflate the ratio by compile/init time.
            **_a100.anchor_fields(
                "kmeans",
                hr["marginal"] if on_tpu else None,
                _a100.kmeans_rows_iters_per_sec(n_cols, k),
                bound="hbm",
            ),
            "xplane_trace": trace_dir,
            "kmeans_inertia": float(inertia),
        }

    sys.path.insert(0, REPO_ROOT)
    from benchmark import a100_model as _a100
    from benchmark.chip_bench import FAMILIES, make_ctx

    ctx = make_ctx(Xd, w, mesh, on_tpu, platform, repo_root=REPO_ROOT)
    family_fns = dict(FAMILIES)

    def unit_wide256():
        """256-col variants of the two north-star algorithms (BASELINE targets
        are x256): drop the 128-col matrix first — 6 GiB each, both won't fit."""
        nonlocal ctx, Xd, w
        out = {}
        # drop every live reference (ctx holds one) so HBM is actually freed
        ctx = dict(ctx, X=None, w=None)
        Xd = w = None
        n256, d256 = (6_000_000, 256) if on_tpu else (50_000, 64)
        rowsh256 = NamedSharding(mesh, P("data", None))

        @functools.partial(jax.jit, out_shardings=(rowsh256, None))
        def make_wide(key):
            k1, k2, k3 = jax.random.split(key, 3)
            c = jax.random.normal(k1, (k, d256), jnp.float32) * 5.0
            a = jax.random.randint(k2, (n256,), 0, k)
            Xw_ = c[a] + jax.random.normal(k3, (n256, d256), jnp.float32)
            return Xw_, Xw_[:k] * 1.0

        X256, init256 = make_wide(jax.random.PRNGKey(1))
        X256.block_until_ready()
        w256 = shard_array(np.ones((n256,), np.float32), mesh)
        wr = _kmeans_rates(X256, w256, init256, n256, d256)
        # key names carry the REAL width: the CPU-sized CI smoke runs 64 cols
        # and must not masquerade as the 256-col north-star shape
        tag = f"kmeans_{d256}col"
        if wr["marginal"] is not None:
            out[f"{tag}_marginal_rows_per_sec_per_chip"] = round(wr["marginal"], 1)
            out[f"{tag}_frac_of_ceiling"] = (
                round(wr["roofline_frac"], 3)
                if wr["roofline_frac"] is not None
                else None
            )
            if on_tpu:
                # the x256 shapes ARE the BASELINE north-star shapes: anchor
                # them too, not just the 128-col headline
                out.update(
                    _a100.anchor_fields(
                        tag, wr["marginal"],
                        _a100.kmeans_rows_iters_per_sec(d256, k), bound="hbm",
                    )
                )
        ctx256 = dict(ctx)
        ctx256.update(X=X256, w=w256)
        from benchmark.chip_bench import bench_pca

        p256 = bench_pca(ctx256)
        out[f"pca_{d256}col_rows_per_sec_per_chip"] = p256.get(
            "pca_cov_rows_per_sec_per_chip"
        )
        out[f"pca_{d256}col_roofline_frac"] = p256.get("pca_roofline_frac")
        for anchor_key in ("pca_vs_a100_est", "pca_vs_a100_est_v5p"):
            if p256.get(anchor_key) is not None:
                out[anchor_key.replace("pca_", f"pca_{d256}col_")] = p256[anchor_key]
        return out

    def run_unit(name):
        if name == "kmeans_headline":
            return unit_kmeans_headline()
        if name == "wide256":
            return unit_wide256()
        return family_fns[name](ctx)

    def _transform_latency(report):
        """p50/p95/p99 transform latency per histogram from a unit's run report
        (observability/inference.py populates transform.batch_s/predict_s;
        quantiles interpolate within the exponential buckets)."""
        from spark_rapids_ml_tpu.observability.registry import (
            interpolate_quantile, split_label_key,
        )

        out = {}
        for key, st in (report["metrics"].get("histograms") or {}).items():
            hname, labels = split_label_key(key)
            if hname not in ("transform.batch_s", "transform.predict_s"):
                continue
            bounds = st.get("bounds") or []
            tag = hname.split(".")[-1]
            if labels.get("model"):
                tag += f"_{labels['model']}"
            out[tag] = {
                "count": st["count"],
                "p50": round(interpolate_quantile(st, 0.50, bounds), 6),
                "p95": round(interpolate_quantile(st, 0.95, bounds), 6),
                "p99": round(interpolate_quantile(st, 0.99, bounds), 6),
            }
        return out

    for name in units:
        if time.time() > deadline_ts - UNIT_START_MARGIN_S:
            _flush_progress(progress, {"unit": name, "status": "deadline_skip"})
            continue
        _flush_progress(progress, {"unit": name, "status": "start"})
        t0 = time.time()
        # one observability run per scenario: the BENCH json gains
        # per-stage span attribution (`<unit>_stage_s`) and, with
        # SRML_TPU_METRICS_DIR set, each unit appends a full structured
        # run report to fit_reports.jsonl (observability/export.py)
        from spark_rapids_ml_tpu.observability import fit_run

        with fit_run(algo=name, site="bench") as obs_run:
            result = run_unit(name)
        if obs_run is not None:
            obs_report = obs_run.report()
            stage_s = sorted(
                obs_report["metrics"]["spans"].items(),
                key=lambda kv: -kv[1],
            )[:8]
            if stage_s:
                result[f"{name}_stage_s"] = {
                    k: round(v, 4) for k, v in stage_s
                }
            tlat = _transform_latency(obs_report)
            if tlat:
                result[f"{name}_transform_latency_s"] = tlat
            # device-performance plane: measured MFU + roofline
            # classification for EVERY scenario from the run's XLA
            # cost-analysis counters (observability/device.py;
            # ci/bench_check.py gates *_mfu direction-aware)
            from spark_rapids_ml_tpu.observability.device import (
                scenario_summary,
            )

            dev = scenario_summary(obs_report, wall_s=time.time() - t0)
            result[f"{name}_mfu"] = dev["mfu"]
            result[f"{name}_roofline_bound"] = dev["roofline_bound"]
            result[f"{name}_device_flops"] = dev["device_flops"]
            result[f"{name}_device_compiles"] = dev["device_compiles"]
            # communication plane (observability/comm.py, design §6h):
            # analyzed collective bytes over the scenario wall against
            # the ICI peak, plus the worst rank-skew gauge when the
            # scenario exercised the rank-snapshot plane — both gated
            # advisory by ci/bench_check.py (lower is better)
            from spark_rapids_ml_tpu.observability.comm import (
                scenario_comm_summary,
            )

            cs = scenario_comm_summary(
                obs_report, wall_s=time.time() - t0
            )
            if cs["comm_frac"] is not None:
                result[f"{name}_comm_frac"] = cs["comm_frac"]
                result[f"{name}_comm_bytes"] = cs["comm_bytes"]
            if cs["rank_skew"] is not None:
                result[f"{name}_rank_skew"] = cs["rank_skew"]
        result[f"{name}_bench_secs"] = round(time.time() - t0, 1)
        _flush_progress(
            progress,
            {
                "unit": name,
                "status": "done",
                "platform": platform,
                "result": result,
            },
        )


# ----------------------------------------------------------------------- assembly


def _assemble(progress_path: str, budget_s: float, baseline_dir: str = None) -> dict:
    """Build the one-line result from the progress file. Baseline read/seed IO
    only happens when `baseline_dir` is given (main passes the repo root; unit
    tests call with None so a synthetic progress file can never poison the
    repo's recorded baseline)."""
    state = _read_progress(progress_path)
    boot = state.pop("boot", {})
    secondary: dict = {}
    headline_value = None
    skipped = []
    for name in UNITS:
        e = state.get(name)
        if e is None or e.get("status") != "done":
            # never started, or met the deadline guard, or the run died in it
            skipped.append(name)
            continue
        result = dict(e.get("result", {}))
        if name == "kmeans_headline":
            headline_value = result.pop("_value", None)
        secondary.update(result)

    metric = "kmeans_lloyd_rows_per_sec_per_chip"
    unit_name = "rows*iters/sec/chip"
    if headline_value is None:
        # headline unit never completed: promote the first captured family
        # number so the line still carries a real measurement (clearly named)
        for key, unit_n in (
            ("pca_cov_rows_per_sec_per_chip", "rows/sec/chip"),
            ("logreg_rows_iters_per_sec_per_chip", "rows*iters/sec/chip"),
            ("linreg_rows_per_sec_per_chip", "rows/sec/chip"),
            ("rf_rows_trees_per_sec_per_chip", "rows*trees/sec/chip"),
        ):
            if secondary.get(key) is not None:
                metric, unit_name = key, unit_n
                headline_value = secondary[key]
                secondary["headline_fallback"] = True
                break
    platform = boot.get("platform") or "none"

    # vs_baseline (protocol 2 = whole-fit timing with a k-real-rows far init;
    # protocol-less baselines were recorded under the old near-optimal init whose
    # n_iter=2 made the same code read ~6x slower — comparing across protocols
    # would report a spurious "speedup", so a mismatched baseline is reseeded)
    vs_baseline = 1.0
    baseline_path = (
        os.path.join(baseline_dir, "BENCH_BASELINE.json") if baseline_dir else None
    )
    is_kmeans_headline = metric.startswith("kmeans_lloyd_rows_per_sec_per_chip")
    try:
        protocol = 2
        base = None
        if baseline_path is None:
            pass
        elif os.path.exists(baseline_path):
            with open(baseline_path) as f:
                base = json.load(f)
            if base.get("protocol") != protocol:
                print(
                    f"bench: baseline protocol {base.get('protocol')} != {protocol}; "
                    "reseeding baseline, vs_baseline reset to 1.0",
                    file=sys.stderr,
                )
                base = None
        if base is not None and is_kmeans_headline and headline_value:
            if base.get("platform") == platform and base.get("value", 0) > 0:
                vs_baseline = headline_value / base["value"]
        elif (
            baseline_path is not None
            and base is None
            and platform == "tpu"
            and is_kmeans_headline
            and headline_value
        ):
            # only a TPU run may seed the local baseline
            with open(baseline_path, "w") as f:
                json.dump(
                    {
                        "platform": platform,
                        "value": headline_value,
                        "unit": unit_name,
                        "protocol": protocol,
                    },
                    f,
                )
    except OSError:
        pass

    secondary["platform"] = platform
    secondary["device_kind"] = boot.get("device_kind")
    secondary["device_count"] = boot.get("n_chips")
    secondary["bench_budget_s"] = budget_s
    if boot.get("result"):
        secondary.update(
            {f"headline_{k}": v for k, v in boot["result"].items()}
        )
    if skipped:
        secondary["skipped"] = skipped
    return {
        "metric": metric,
        "value": headline_value if headline_value is not None else 0.0,
        "unit": unit_name,
        "vs_baseline": round(vs_baseline, 4),
        "secondary": secondary,
    }


def _device():
    """The first device as JAX reports it (a seam the tests replace)."""
    import jax

    return jax.devices()[0]


def main() -> int:
    # total wall budget, anchored at process start: a unit does not START with
    # less than UNIT_START_MARGIN_S left; unfinished ones land in `skipped`
    budget_s = float(os.environ.get("SRML_BENCH_BUDGET_S", "240"))
    deadline_ts = time.time() + budget_s

    dev = _device()
    if dev.platform != "tpu":
        print(
            f"bench: refusing to run: jax.devices()[0].platform is "
            f"{dev.platform!r}, not 'tpu'. A speed comes from the chip or not "
            "at all; there is no CPU fallback.",
            file=sys.stderr,
        )
        return 2

    sys.path.insert(0, REPO_ROOT)
    from spark_rapids_ml_tpu.utils import enable_compile_cache

    enable_compile_cache()

    # the progress journal doubles as the on-disk record: what was measured
    # survives a run killed at its time limit
    os.makedirs(RESULTS_DIR, exist_ok=True)
    progress_path = os.path.join(RESULTS_DIR, "bench_progress_last.jsonl")
    if os.path.exists(progress_path):
        os.remove(progress_path)  # a stale journal would pass for this run's

    run_units(progress_path, deadline_ts)  # a unit that raises exits non-zero

    line = _assemble(progress_path, budget_s, baseline_dir=REPO_ROOT)
    with open(os.path.join(RESULTS_DIR, "chip_bench_tpu.json"), "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
