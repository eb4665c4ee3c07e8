#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path a user would call — `Estimator.fit` →
`Model.transform` → `serving.register_model` / `POST /v1/models/<name>:predict`
— at the repo's flagship width (KMeans k=20 and PCA k=16 over 4,000,000 × 128
float32), then the streamed out-of-core tier, then each of the ten Pallas
kernels through its host wrapper, and checks every result against a plain
numpy reference. Weights and data are random, made from a seed.

Contract (builder's instructions, docs/design.md §8):
  * refuses to run unless `jax.devices()[0].platform == "tpu"` (exit 2, no
    result line) — a measurement path that finds no chip fails, it never
    falls back to the CPU;
  * there is no `except` between a leg and the exit status: the first failed
    check raises, the traceback is the report, the exit code is non-zero and
    no result line is printed;
  * the last line of stdout is one JSON object
    `{"ok": true, "device": {"platform", "kind", "count"}}`;
  * one process holds the chip; everything started here (HTTP server,
    dispatcher threads) is stopped here.

Every wall time printed is COLD SET-UP information (compile + first run),
labelled as such. It is not a speed; speeds are the benchmark's job.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

N_ROWS = 4_000_000  # x128 f32 = 2.0 GB: under the 4 GiB stream threshold
N_COLS = 128
KMEANS_K = 20
KMEANS_ITERS = 10
PCA_K = 16
SEED = 20260926
_CHUNK = 250_000  # host reference passes walk X in chunks of this many rows


def _check(ok: bool, what: str) -> None:
    """`assert` is stripped under -O; a failed check must always raise."""
    if not ok:
        raise AssertionError(what)


def _say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ data + refs


def make_blobs(n: int, d: int, k: int, seed: int):
    """Seeded gaussian blobs: k centers at ~5 sigma per coordinate, unit noise."""
    rng = np.random.default_rng(seed)
    centers = (rng.standard_normal((k, d)) * 5.0).astype(np.float32)
    labels = rng.integers(0, k, size=n)
    X = rng.standard_normal((n, d), dtype=np.float32)
    for s in range(0, n, _CHUNK):
        X[s:s + _CHUNK] += centers[labels[s:s + _CHUNK]]
    return X, centers


def np_assign(X: np.ndarray, C: np.ndarray) -> Tuple[np.ndarray, float]:
    """Plain numpy nearest-center labels + inertia (f32 sgemm cross term per
    chunk, f64 everywhere else)."""
    C64 = C.astype(np.float64)
    c2 = (C64 * C64).sum(axis=1)
    labels = np.empty(X.shape[0], np.int64)
    inertia = 0.0
    for s in range(0, X.shape[0], _CHUNK):
        x = X[s:s + _CHUNK]
        x2 = np.einsum("ij,ij->i", x, x, dtype=np.float64)
        d2 = x2[:, None] - 2.0 * (x @ C.T).astype(np.float64) + c2[None, :]
        labels[s:s + _CHUNK] = np.argmin(d2, axis=1)
        inertia += float(np.maximum(d2.min(axis=1), 0.0).sum())
    return labels, inertia


def np_cluster_means(X: np.ndarray, labels: np.ndarray, k: int):
    """Per-cluster row means: one-hot sgemm per chunk, f64 across chunks."""
    sums = np.zeros((k, X.shape[1]), np.float64)
    counts = np.zeros(k, np.int64)
    eye = np.eye(k, dtype=np.float32)
    for s in range(0, X.shape[0], _CHUNK):
        lab = labels[s:s + _CHUNK]
        sums += (eye[lab].T @ X[s:s + _CHUNK]).astype(np.float64)
        counts += np.bincount(lab, minlength=k)
    return sums / np.maximum(counts, 1)[:, None], counts


def np_covariance(X: np.ndarray):
    """Plain numpy (mean, unbiased covariance): f32 sgemm Gram per chunk, f64
    accumulation across chunks and for the mean correction."""
    n, d = X.shape
    S2 = np.zeros((d, d), np.float64)
    s1 = np.zeros(d, np.float64)
    for s in range(0, n, _CHUNK):
        x = X[s:s + _CHUNK]
        S2 += (x.T @ x).astype(np.float64)
        s1 += x.sum(axis=0, dtype=np.float64)
    mean = s1 / n
    return mean, (S2 - n * np.outer(mean, mean)) / (n - 1.0)


def match_rows(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """For each row of A the index of its nearest row of B; checked to be a
    permutation (cluster order is arbitrary between two fits)."""
    d2 = ((A[:, None, :].astype(np.float64) - B[None, :, :]) ** 2).sum(axis=2)
    perm = d2.argmin(axis=1)
    _check(len(set(perm.tolist())) == len(perm),
           "centers do not match one-to-one (a fit merged or split a blob)")
    return perm


def _counter(report: Dict[str, Any], name: str, **labels: str) -> float:
    """Sum of a run report's counters named `name` whose labels include
    `labels` (keys look like `name{a=x,b=y}`)."""
    from spark_rapids_ml_tpu.observability.registry import split_label_key

    total = 0.0
    for key, v in (report["metrics"].get("counters") or {}).items():
        base, have = split_label_key(key)
        if base == name and all(have.get(k) == val for k, val in labels.items()):
            total += float(v)
    return total


# ------------------------------------------------------------ device bookkeeping


def device_table() -> List[Dict[str, Any]]:
    """Per-device memory_stats snapshot (bytes in use, peak, allocation count):
    the runtime's own record of where work was placed."""
    import jax

    rows = []
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        rows.append({
            "id": d.id,
            "bytes_in_use": int(ms.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
            "num_allocs": int(ms.get("num_allocs", 0)),
        })
    return rows


def devices_touched(before, after) -> List[int]:
    """Ids of the devices whose allocator served at least one allocation
    between two `device_table()` snapshots."""
    return [a["id"] for b, a in zip(before, after)
            if a["num_allocs"] != b["num_allocs"]]


# ------------------------------------------------------------------------ legs


def leg_fit(st: Dict[str, Any]) -> None:
    import jax

    from spark_rapids_ml_tpu.clustering import KMeans
    from spark_rapids_ml_tpu.feature import PCA

    X = st["X"]
    n_dev = len(jax.devices())
    before = device_table()

    km = KMeans(k=KMEANS_K, maxIter=KMEANS_ITERS, seed=7).fit(X)
    rep = km.fit_report_
    _check(rep["status"] == "ok", "KMeans fit report status != ok")
    _check(_counter(rep, "kmeans.lloyd_path", path="xla") == 1,
           "KMeans k=20 did not take the XLA Lloyd path")
    _check(_counter(rep, "device.kernel_calls", kernel="kmeans.lloyd_fit") >= 1,
           "kmeans.lloyd_fit never ran on the device (a host-fitted model?)")
    C = np.asarray(km.cluster_centers_)
    _check(C.shape == (KMEANS_K, N_COLS) and C.dtype == np.float32
           and bool(np.isfinite(C).all()), "KMeans centers: wrong shape/dtype/NaN")
    # numpy reference over ALL seeded rows: a converged Lloyd fit is a fixed
    # point, so each center is the mean of the rows numpy assigns to it.
    # atol 2e-3 on coordinates of magnitude <= ~20: XLA's f32 accumulation of
    # ~200k rows per center at HIGHEST matmul precision lands at 3.2e-4 on one
    # v5e and 1.5e-4 on four (PR 21); a bf16-class (single-pass) update would
    # miss by ~5e-2.
    labels, inertia_ref = np_assign(X, C)
    means, counts = np_cluster_means(X, labels, KMEANS_K)
    _check(int(km._model_attributes["n_iter"]) < KMEANS_ITERS,
           "KMeans did not converge inside maxIter on separated blobs")
    err = float(np.abs(C - means).max())
    _check(err <= 2e-3, f"KMeans centers vs numpy cluster means: {err:.3e} > 2e-3")
    _check(list(km.summary.clusterSizes) == counts.tolist(),
           "KMeans summary.clusterSizes != numpy bincount of assignments")
    # inertia: sum of 4M f32 min-distances (~128 each); rtol 1e-4 is f32
    # reduction-order slack, two orders tighter than a bf16 distance pass
    rel = abs(km.inertia_ - inertia_ref) / inertia_ref
    _check(rel <= 1e-4, f"KMeans inertia vs numpy: rel {rel:.3e} > 1e-4")
    # and the fit recovered the generating blobs: a center is the mean of
    # `count` unit-variance rows, so 6 standard errors bounds the largest of
    # its 2,560 coordinates' deviations
    perm = match_rows(C, st["true_centers"])
    err_true = float(np.abs(C - st["true_centers"][perm]).max())
    tol_true = 6.0 / float(np.sqrt(counts.min()))
    _check(err_true <= tol_true,
           f"KMeans centers vs generating centers: {err_true:.3e} > {tol_true:.3e}")
    _say(f"  kmeans: n_iter={km._model_attributes['n_iter']} "
         f"max|center - numpy mean|={err:.2e} inertia rel err={rel:.1e}")

    pca = PCA(k=PCA_K, inputCol="features").fit(X)
    rep = pca.fit_report_
    _check(rep["status"] == "ok", "PCA fit report status != ok")
    _check(_counter(rep, "device.kernel_calls", kernel="pca.cov_pallas") == 1,
           "PCA did not take the Pallas Gram kernel (pca.cov_pallas)")
    _check(_counter(rep, "device.kernel_calls",
                    kernel="linalg.weighted_covariance") == 0,
           "PCA ran the XLA covariance pass")
    sigs = [r["signature"] for r in rep["device"]["kernels"]
            if r["kernel"] == "pca.cov_pallas"
            and f"({N_ROWS}, {N_COLS})" in r["signature"]]
    _check(len(sigs) == 1 and "interpret=False" in sigs[0],
           f"pca.cov_pallas was not compiled with interpret=False: {sigs}")
    a = pca._model_attributes
    comps = np.asarray(a["components"], np.float64)
    ev = np.asarray(a["explained_variance"], np.float64)
    _check(comps.shape == (PCA_K, N_COLS) and bool(np.isfinite(comps).all()),
           "PCA components: wrong shape or non-finite")
    mean_ref, cov_ref = np_covariance(X)
    lam_ref = np.linalg.eigvalsh(cov_ref)[::-1][:PCA_K]
    # eigenvalues of the blob covariance (~25..100): rtol 2e-4 separates the
    # kernel's 6-pass bf16 emulation of f32 (~1e-6) from a single bf16 pass
    # (~2e-3); the residual test is robust to near-degenerate eigenpairs
    rel_ev = float(np.abs(ev - lam_ref).max() / lam_ref.max())
    _check(rel_ev <= 2e-4, f"PCA explained variance vs numpy eigh: {rel_ev:.3e}")
    resid = float(np.abs(cov_ref @ comps.T - comps.T * ev[None, :]).max()
                  / lam_ref.max())
    _check(resid <= 1e-3, f"PCA eigen-residual |C v - lambda v|: {resid:.3e}")
    ortho = float(np.abs(comps @ comps.T - np.eye(PCA_K)).max())
    _check(ortho <= 1e-4, f"PCA components not orthonormal: {ortho:.3e}")
    err_mean = float(np.abs(np.asarray(a["mean"], np.float64) - mean_ref).max())
    _check(err_mean <= 1e-4, f"PCA mean vs numpy: {err_mean:.3e}")
    _say(f"  pca: explained-variance rel err={rel_ev:.1e} "
         f"eigen-residual={resid:.1e} mean err={err_mean:.1e}")

    after = device_table()
    st.update(km=km, pca=pca)
    if n_dev > 1:
        # the estimator's own staging step on a small block: where do the row
        # shards of a fit input land?
        probe = KMeans(k=KMEANS_K)
        staged = probe._build_fit_inputs(probe._pre_process_data(X[:n_dev * 4096]))
        shard_devs = sorted(s.device.id for s in staged.features.addressable_shards)
        _check(len(set(shard_devs)) == n_dev,
               f"fit-input shards sit on devices {shard_devs}, not {n_dev} distinct")
        _say(f"  fit-input row shards are addressable on devices {shard_devs}")
        _check(f"axis_sizes=({n_dev}, 1)" in sigs[0],
               f"pca.cov_pallas did not run over the {n_dev}-device mesh: {sigs}")
        _say(f"  the Pallas Gram kernel ran per shard under shard_map over "
             f"the ({n_dev}, 1) mesh")
        shard_bytes = N_ROWS * N_COLS * 4 // n_dev
        holders = [a_["id"] for a_ in after
                   if a_["peak_bytes_in_use"] >= shard_bytes]
        _check(len(holders) == n_dev,
               f"fit inputs were not resident on all {n_dev} devices: peaks "
               f"{[(a_['id'], a_['peak_bytes_in_use']) for a_ in after]}")
        for name, model, kernel in (("kmeans", km, "kmeans.lloyd_fit"),
                                    ("pca", pca, "pca.cov_pallas")):
            ar = _counter(model.fit_report_, "comm.collective_ops",
                          kind="all_reduce", kernel=kernel)
            ar_bytes = _counter(model.fit_report_, "comm.collective_bytes",
                                kind="all_reduce", kernel=kernel)
            _check(ar > 0, f"{kernel}: no all-reduce in the compiled program")
            _say(f"  {name}: {kernel} compiled with {ar:.0f} all-reduce op(s), "
                 f"{ar_bytes:.0f} bytes per call (a count from HLO)")
        _say(f"  fit inputs resident on devices {holders} "
             f"(peak >= one {shard_bytes >> 20} MiB shard each)")
    st["fit_touched"] = devices_touched(before, after)


def leg_transform(st: Dict[str, Any]) -> None:
    import pandas as pd

    X, km, pca = st["X"], st["km"], st["pca"]
    C = np.asarray(km.cluster_centers_)
    V = np.asarray(pca._model_attributes["components"], np.float64)
    before = device_table()
    blocks = {
        "pandas": (X[:5000], lambda b: pd.DataFrame({"features": list(b)})),
        "numpy": (X[N_ROWS // 2:N_ROWS // 2 + 7_777], lambda b: b),
    }
    for kind, (block, wrap) in blocks.items():
        pred = km.transform(wrap(block))["prediction"].to_numpy()
        ref, _ = np_assign(block, C)
        # separated blobs: no row sits near a boundary, so labels are exact
        _check(pred.shape == ref.shape and bool((pred == ref).all()),
               f"KMeans transform ({kind}) != numpy argmin")
        proj = np.stack(pca.transform(wrap(block))["pca_features"].to_numpy())
        ref_p = block.astype(np.float64) @ V.T
        # projections of magnitude <= ~60 at HIGHEST f32 matmul precision:
        # atol 2e-3 is ~30 ulp; a bf16 pass would miss by ~0.2
        err = float(np.abs(proj - ref_p).max())
        _check(proj.shape == ref_p.shape and err <= 2e-3,
               f"PCA transform ({kind}) vs numpy: {err:.3e}")
        _say(f"  {kind} block of {len(block)} rows: kmeans labels exact, "
             f"pca max err={err:.1e}")
    st["transform_touched"] = devices_touched(before, device_table())


def _post(url: str, rows: np.ndarray) -> Dict[str, Any]:
    body = json.dumps({"instances": rows.tolist()}).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        _check(resp.status == 200, f"HTTP {resp.status} from {url}")
        return json.loads(resp.read())


def leg_serve(st: Dict[str, Any]) -> None:
    from spark_rapids_ml_tpu import serving
    from spark_rapids_ml_tpu.observability.device import compiles_total

    X, km, pca = st["X"], st["km"], st["pca"]
    threads_before = set(threading.enumerate())
    before = device_table()
    host, port = serving.start_serving(port=0)
    for name, model in (("km", km), ("pca", pca)):
        stats = serving.register_model(name, model)  # AOT pre-warms each bucket
        _check(stats["warm_buckets"] == stats["buckets"],
               f"{name}: pre-warm did not cover every bucket: {stats}")
    compiles_warm = compiles_total()

    rng = np.random.default_rng(SEED + 1)
    sizes = [1, 2, 3, 16, 17, 255, 256] + rng.integers(1, 257, size=29).tolist()
    starts = rng.integers(0, N_ROWS - 256, size=len(sizes)).tolist()
    requests = [(("km", "pca")[i % 2], X[s:s + n])
                for i, (s, n) in enumerate(zip(starts, sizes))]
    answers: List[Any] = [None] * len(requests)

    def ask(i: int) -> None:
        name, rows = requests[i]
        answers[i] = _post(
            f"http://{host}:{port}/v1/models/{name}:predict", rows)

    # half sequential, half from 6 client threads so the batcher coalesces
    half = len(requests) // 2
    for i in range(half):
        ask(i)
    clients = [threading.Thread(target=lambda lo=lo: [
        ask(i) for i in range(half + lo, len(requests), 6)]) for lo in range(6)]
    for t in clients:
        t.start()
    for t in clients:
        t.join(timeout=120)
        _check(not t.is_alive(), "an HTTP client thread did not finish")
    compiles_after = compiles_total()

    # one transform call per model over all its requests' rows (one compile),
    # sliced back per request
    for name, model, col in (("km", km, "prediction"),
                             ("pca", pca, "pca_features")):
        mine = [(rows, ans) for (nm, rows), ans in zip(requests, answers)
                if nm == name]
        want_all = model.transform(
            np.concatenate([rows for rows, _ in mine]))[col].to_numpy()
        at = 0
        for rows, ans in mine:
            _check(ans is not None and ans["rows"] == len(rows),
                   f"{name}: no or short answer for a {len(rows)}-row request")
            want = want_all[at:at + len(rows)]
            at += len(rows)
            if name == "km":
                got = np.asarray(ans["outputs"][col])
                _check(bool((got == want).all()), "served KMeans != transform")
            else:
                got = np.asarray(ans["outputs"][col], np.float32)
                # same kernel at another padded batch height: f32 ulp-level
                # drift only (values <= ~60 -> atol 1e-4)
                err = float(np.abs(got - np.stack(want)).max())
                _check(got.shape == (len(rows), PCA_K) and err <= 1e-4,
                       f"served PCA vs transform: {err:.3e}")
    _check(compiles_after == compiles_warm,
           f"serving compiled after pre-warm: {compiles_warm} -> {compiles_after}")
    st["serve_touched"] = devices_touched(before, device_table())
    report = serving.stop_serving()
    _check(report is not None and report["status"] == "ok",
           "serving session report missing or not ok")
    deadline = time.time() + 10
    while time.time() < deadline:
        left = [t for t in threading.enumerate()
                if t not in threads_before and t.is_alive()]
        if not left:
            break
        time.sleep(0.1)
    _check(not left, f"threads left after stop_serving: {[t.name for t in left]}")
    _say(f"  {len(requests)} HTTP requests of 1..256 rows answered == transform; "
         f"device.compile total {compiles_warm} before and after; no thread left")


def _staging_integrity(X_f: np.ndarray, rows: int) -> int:
    """Drive the shared ingest generator over a column-major block (every batch
    takes the counted-copy path through the reusable staging buffer) and compare
    each uploaded device batch with the host rows BIT FOR BIT. A staging buffer
    refilled before its asynchronous host->device transfer finished would show
    here and nowhere else: blob statistics cannot see one batch overwritten by
    the next. Device batches are held and compared only after the stream ends,
    so no comparison serializes the uploads."""
    from spark_rapids_ml_tpu.ops.ingest import StagingPool, stage_block
    from spark_rapids_ml_tpu.ops.streaming import _batch_stream, _prefetch
    from spark_rapids_ml_tpu.parallel.partitioner import active_partitioner

    n = X_f.shape[0]
    w = np.ones(n, np.float32)
    pool = StagingPool()

    def slicer(s, e):
        return (stage_block(X_f, s, e, np.float32, pool, slot="X"),
                stage_block(w, s, e, np.float32, pool, slot="w"))

    held = list(_prefetch(
        _batch_stream(n, rows, active_partitioner().mesh, slicer)))
    for i, (xb, _) in enumerate(held):
        s, e = i * rows, min((i + 1) * rows, n)
        _check(bool(np.array_equal(np.asarray(xb)[:e - s], X_f[s:e])),
               f"staged batch {i} differs from its host rows after upload")
    return len(held)


def leg_stream(st: Dict[str, Any]) -> None:
    from spark_rapids_ml_tpu import config
    from spark_rapids_ml_tpu.clustering import KMeans

    X = st["X"]
    C_incore = np.asarray(st["km"].cluster_centers_)
    # column-major copy of the same rows: slices of it are not contiguous, so
    # every batch goes through the staging-buffer copy path instead of a view
    X_f = np.asfortranarray(X)
    config.set("stream_threshold_bytes", X.nbytes // 2)  # the input now streams
    for layout, data, copied in (("row-major", X, False),
                                 ("column-major", X_f, True)):
        m = KMeans(k=KMEANS_K, maxIter=KMEANS_ITERS, seed=7).fit(data)
        rep = m.fit_report_
        _check(rep["status"] == "ok", "streamed KMeans report status != ok")
        _check(_counter(rep, "stream.upload_batches") > 0,
               "streamed fit uploaded no batch (in-core path ran?)")
        _check(_counter(rep, "cache.hits") > 0,
               "HBM batch cache had no hit on passes 2..N")
        moved = _counter(rep, "ingest.bytes_copied")
        _check((moved > 0) == copied,
               f"{layout}: ingest.bytes_copied={moved:.0f}, expected "
               f"{'a counted copy' if copied else 'zero-copy views'}")
        C = np.asarray(m.cluster_centers_)
        # another init (strided sample) and another summation order than the
        # in-core fit, same fixed point: atol 2e-3 as for the in-core check
        err = float(np.abs(C - C_incore[match_rows(C, C_incore)]).max())
        _check(err <= 2e-3, f"streamed ({layout}) vs in-core centers: {err:.3e}")
        _say(f"  {layout}: upload_batches="
             f"{_counter(rep, 'stream.upload_batches'):.0f} cache.hits="
             f"{_counter(rep, 'cache.hits'):.0f} bytes_copied={moved:.0f} "
             f"max|center - in-core|={err:.1e}")
    config.unset("stream_threshold_bytes")
    n_staged = min(1 << 20, N_ROWS)
    n_batches = _staging_integrity(X_f[:n_staged], rows=n_staged // 16)
    _say(f"  staging-buffer integrity: {n_batches} pooled batches bit-equal "
         "to their host rows after upload")


# ------------------------------------------------------------------ kernel leg
#
# Each check runs one host wrapper at a shape its `auto` gate sends to the
# Pallas kernel on a v5e, and the XLA path on the same inputs. `gate=True`
# additionally asserts the gate itself is open (only true on a TPU; the tier-1
# test drives the same functions at tiny sizes in interpret mode).


def check_gram(d: int, n: int = 200_077, gate: bool = True) -> str:
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops._precision import parity_precision
    from spark_rapids_ml_tpu.ops.linalg import weighted_covariance
    from spark_rapids_ml_tpu.ops.pallas_select import _interpret_default
    from spark_rapids_ml_tpu.ops.pallas_xtwx import covariance_prefix_mask
    from spark_rapids_ml_tpu.ops.pca import use_fused_gram

    if gate:
        _check(use_fused_gram(d, True), f"use_fused_gram({d}) is closed")
    rng = np.random.default_rng(SEED + d)
    X = (rng.standard_normal((n, d)) * 2.0 + 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    w[-100:] = 0.0  # the pad_rows contract: zero-weight suffix
    cov_p, mean_p, _ = covariance_prefix_mask(
        jnp.asarray(X), jnp.asarray(w), mesh=None,
        precision=parity_precision(), interpret=_interpret_default())
    cov_x, mean_x, _ = weighted_covariance(jnp.asarray(X), jnp.asarray(w))
    _, cov_ref = np_covariance(X[:-100])
    scale = float(np.abs(cov_ref).max())
    # both paths run f32-parity matmuls: 1e-4 of the largest entry (~4)
    e_x = float(np.abs(np.asarray(cov_p) - np.asarray(cov_x)).max()) / scale
    e_r = float(np.abs(np.asarray(cov_p) - cov_ref).max()) / scale
    _check(e_x <= 1e-4 and e_r <= 1e-4,
           f"Gram d={d}: vs XLA {e_x:.2e}, vs numpy {e_r:.2e}")
    _check(float(np.abs(np.asarray(mean_p) - np.asarray(mean_x)).max()) <= 1e-5,
           f"Gram d={d}: mean differs from XLA")
    return f"vs XLA {e_x:.1e}, vs numpy {e_r:.1e}"


def check_normal_eq(d: int, n: int = 200_077, gate: bool = True) -> str:
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops._precision import parity_precision
    from spark_rapids_ml_tpu.ops.linear import center_moments, normal_equations
    from spark_rapids_ml_tpu.ops.pallas_select import _interpret_default
    from spark_rapids_ml_tpu.ops.pallas_xtwx import normal_eq_prefix_mask
    from spark_rapids_ml_tpu.ops.pca import use_fused_gram

    if gate:
        _check(use_fused_gram(d, True), f"use_fused_gram({d}) is closed")
    rng = np.random.default_rng(SEED + 7 * d)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) + rng.standard_normal(n)).astype(np.float32)
    w = np.ones(n, np.float32)
    w[-100:] = 0.0
    A_p, b_p, xbar_p, ybar_p, wsum_p, yty_p = normal_eq_prefix_mask(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w), mesh=None,
        precision=parity_precision(), interpret=_interpret_default())
    # the fused pass's raw moments, centred as the solve takes them, against
    # the in-core XLA pass's centred statistics
    S_p, r_p, _, _, _ = center_moments(A_p, b_p, xbar_p, ybar_p, wsum_p)
    S_x, r_x, _, ybar_x, wsum_x = normal_equations(
        jnp.asarray(X), jnp.asarray(y), jnp.asarray(w))
    Xv, yv = X[:-100].astype(np.float64), y[:-100].astype(np.float64)
    b_ref = Xv.T @ yv
    r_ref = (Xv - Xv.mean(axis=0)).T @ (yv - yv.mean())
    # XᵀX entries ~n, Xᵀy ~n*sqrt(d): 1e-4 of the largest entry, as for Gram
    e_A = float(np.abs(np.asarray(S_p) - np.asarray(S_x)).max()) / n
    e_b = float(np.abs(np.asarray(b_p) - b_ref).max() / np.abs(b_ref).max())
    e_yty = abs(float(yty_p) - float(yv @ yv)) / float(yv @ yv)
    _check(e_A <= 1e-4 and e_b <= 1e-4 and e_yty <= 1e-4,
           f"normal-eq d={d}: A {e_A:.2e} b {e_b:.2e} yty {e_yty:.2e}")
    _check(float(wsum_p) == float(wsum_x) == n - 100, "normal-eq: wrong Σw")
    _check(abs(float(ybar_p) - float(ybar_x)) <= 1e-4, "normal-eq: ȳ differs")
    _check(float(np.abs(np.asarray(r_p) - np.asarray(r_x)).max()
                 / np.abs(r_ref).max()) <= 1e-4, "normal-eq: Xᵀy differs from XLA")
    _check(float(np.abs(np.asarray(xbar_p) - Xv.mean(axis=0)).max()) <= 1e-4,
           "normal-eq: x̄ differs from numpy")
    return f"A vs XLA {e_A:.1e}, Xᵀy vs numpy {e_b:.1e}"


def check_lloyd(unit_mask: bool, n: int = 262_181, d: int = 128, k: int = 128,
                gate: bool = True) -> str:
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.defaults import LLOYD_FUSED_MIN_K
    from spark_rapids_ml_tpu.ops._precision import parity_precision
    from spark_rapids_ml_tpu.ops.kmeans import lloyd_fit
    from spark_rapids_ml_tpu.ops.pallas_kmeans import (
        _N_SPLIT, lloyd_fit_pallas, lloyd_fits_vmem,
    )
    from spark_rapids_ml_tpu.ops.pallas_select import _interpret_default

    prec = parity_precision()
    if gate:
        _check(k >= LLOYD_FUSED_MIN_K and lloyd_fits_vmem(k, d, _N_SPLIT[prec]),
               f"fused Lloyd gate is closed at k={k} d={d}")
    rng = np.random.default_rng(SEED + 11)
    X = rng.standard_normal((n, d)).astype(np.float32)
    w = np.ones(n, np.float32)
    w[-50:] = 0.0
    Xj, wj, init = jnp.asarray(X), jnp.asarray(w), jnp.asarray(X[:k] * 1.0)
    c_p, in_p, it_p = lloyd_fit_pallas(
        Xj, wj, init, 0.0, 3, mesh=None, interpret=_interpret_default(),
        precision=prec, unit_mask=unit_mask)
    c_x, in_x, it_x, _ = lloyd_fit(Xj, wj, init, 0.0, 3)
    # unstructured data: a handful of boundary rows may flip between two
    # f32-parity matmul emulations; one flipped row of ~2,000 moves a center
    # coordinate by ~5e-4, so atol 5e-3 allows a few and rejects bf16 (~0.1)
    err = float(np.abs(np.asarray(c_p) - np.asarray(c_x)).max())
    rel_in = abs(float(in_p) - float(in_x)) / float(in_x)
    _check(int(it_p) == int(it_x) == 3, "Lloyd: iteration counts differ")
    _check(err <= 5e-3 and rel_in <= 1e-4,
           f"Lloyd (unit_mask={unit_mask}): centers {err:.2e} inertia {rel_in:.2e}")
    return f"centers vs XLA {err:.1e}, inertia rel {rel_in:.1e}"


def check_assign3(rows_a_device: int = 32_768, d: int = 1024, k: int = 512) -> str:
    """The XLA Lloyd program's three-pass ranking with its six-pass second
    look (`ops/kmeans.py::_assign3`) over whatever mesh the process has: under
    `shard_map` on several chips, plain on one. Against the six-pass program
    on the same placed table."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.observability import collective_summary
    from spark_rapids_ml_tpu.ops.kmeans import _second_look_rows, lloyd_fit
    from spark_rapids_ml_tpu.parallel.partitioner import active_partitioner

    part = active_partitioner()
    n_dev = int(part.mesh.devices.size)
    n = rows_a_device * n_dev
    X, _ = make_blobs(n, d, k, SEED + 17)
    w = np.ones(n, np.float32)
    w[-50:] = 0.0
    Xj, wj = part.shard(X), part.shard(w)
    init = jnp.asarray(X[np.random.default_rng(SEED + 19).choice(n, k, replace=False)])
    recheck, mesh = _second_look_rows(Xj, k, False, False)
    _check(recheck == rows_a_device // 16 and (mesh is not None) == (n_dev > 1),
           f"assign3: the shape test gave recheck={recheck}, mesh={mesh} on {n_dev} devices")
    args = (Xj, wj, init, 0.0, 6)
    c_3, in_3, it_3, looks = lloyd_fit(*args, unit_weight=True, recheck=recheck, mesh=mesh)
    c_6, in_6, it_6, _ = lloyd_fit(*args, unit_weight=True)
    looks = np.asarray(jax.device_get(looks))
    err = float(np.abs(np.asarray(c_3) - np.asarray(c_6)).max())
    rel_in = abs(float(in_3) - float(in_6)) / float(in_6)
    _check(int(it_3) == int(it_6), "assign3: iteration counts differ")
    _check(looks.shape == (n_dev, 2) and (looks >= 0).all() and looks.sum() > 0,
           f"assign3: the second look's counts are {looks.tolist()}")
    # a row six passes cannot rank may lie with either centre: one such row of
    # ~256 moves a centre coordinate by ~1e-2; bit-equal on every run so far
    _check(err <= 1e-5 and rel_in <= 1e-6,
           f"assign3: centres {err:.2e} inertia {rel_in:.2e} off the six-pass program")
    exe = lloyd_fit.lower(*args, unit_weight=True, recheck=recheck, mesh=mesh).compile()
    kinds = collective_summary(exe.as_text())
    _check(set(kinds) <= {"all_reduce"}, f"assign3: a row crosses a shard: {kinds}")
    return (f"{n_dev} row shard(s) of {rows_a_device} x {d}, k={k}: centres vs six passes "
            f"{err:.1e}, [rows looked at again, iterations whole] a shard {looks.tolist()}, "
            f"collectives {({kind: v['ops'] for kind, v in kinds.items()})}")


def check_assign(n: int = 262_181, d: int = 128, k: int = 128,
                 gate: bool = True) -> str:
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.kmeans import _kmeans_predict_xla
    from spark_rapids_ml_tpu.ops.pallas_select import fused_assign, use_fused_assign

    if gate:
        _check(use_fused_assign(k, d), f"fused assign gate is closed at k={k}")
    rng = np.random.default_rng(SEED + 13)
    X = rng.standard_normal((n, d)).astype(np.float32)
    C = X[rng.choice(n, k, replace=False)] * 0.5
    a_p = np.asarray(fused_assign(jnp.asarray(X), jnp.asarray(C)))
    a_x = np.asarray(_kmeans_predict_xla(jnp.asarray(X), jnp.asarray(C)))
    diff = np.nonzero(a_p != a_x)[0]
    # two f32-parity emulations may break a near-tie differently: allow it
    # only where numpy f64 says the two centers are within 1e-4 relative
    if len(diff):
        x = X[diff].astype(np.float64)
        dp = ((x - C[a_p[diff]].astype(np.float64)) ** 2).sum(axis=1)
        dx = ((x - C[a_x[diff]].astype(np.float64)) ** 2).sum(axis=1)
        _check(bool((np.abs(dp - dx) <= 1e-4 * dx).all()),
               "fused assign picked a center that is not a near-tie")
    _check(len(diff) <= max(1, n // 10_000),
           f"fused assign: {len(diff)} of {n} rows differ from XLA")
    return f"{len(diff)} near-tie rows of {n} differ from XLA"


def _ladder_items(n: int, d: int, seed: int):
    """Items whose neighbour ranking survives bf16-class distance error: 64
    'near' items on a radial ladder r_j = 3 * 1.15^j (scattered over the index
    range so they land in many tiles), everything else beyond radius 1e5.
    Queries are unit-scale gaussians, so the cross term matters but cannot
    reorder rungs except at rare near-ties."""
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((n, d))
    U /= np.linalg.norm(U, axis=1, keepdims=True)
    radius = 1e5 * (1.0 + rng.random(n))
    near = rng.choice(n, 64, replace=False)
    radius[near] = 3.0 * 1.15 ** np.arange(64)
    X = (U * radius[:, None]).astype(np.float32)
    valid = rng.random(n) > 0.1
    return X, valid, near


def check_topk(k: int, n: int = 65_709, d: int = 64, nq: int = 1000,
               gate: bool = True) -> str:
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import selection as sel
    from spark_rapids_ml_tpu.ops.knn import exact_knn_single
    from spark_rapids_ml_tpu.ops.pallas_select import fused_topk

    if gate:
        _check(sel.resolve(n, k, None, fusable=True)[0] == "pallas_fused",
               f"fused top-k gate is closed at n={n} k={k}")
    X, valid, near = _ladder_items(n, d, SEED + 17)
    rng = np.random.default_rng(SEED + 19)
    Q = (rng.standard_normal((nq, d)) / np.sqrt(d)).astype(np.float32)
    d2_p, id_p = fused_topk(jnp.asarray(Q), jnp.asarray(X), jnp.asarray(valid), k)
    d2_x, id_x = exact_knn_single(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(valid), k,
        strategy="exact_full")
    id_p, id_x, d2_p = np.asarray(id_p), np.asarray(id_x), np.asarray(d2_p)
    # numpy f64 reference restricted to the valid near items (the only ones
    # that can rank): exact distances, exact order
    cand = near[valid[near]]
    ref_d2 = ((Q[:, None, :].astype(np.float64)
               - X[cand][None, :, :].astype(np.float64)) ** 2).sum(axis=2)
    order = np.argsort(ref_d2, axis=1)[:, :k]
    ref_id = cand[order]
    ref_top = np.take_along_axis(ref_d2, order, axis=1)
    # ranking-class matmuls run single-pass on the MXU: ids may differ from
    # the f64 order only at near-ties (<= 0.5% of slots); returned distances
    # carry bf16-class cross-term error (rtol 1e-2)
    miss_ref = float((id_p != ref_id).mean())
    miss_xla = float((id_p != id_x).mean())
    _check(miss_ref <= 5e-3 and miss_xla <= 5e-3,
           f"top-k k={k}: id mismatch vs numpy {miss_ref:.3%}, vs XLA {miss_xla:.3%}")
    rel = float(np.abs(np.sort(d2_p, axis=1) - ref_top).max() / ref_top.max())
    _check(bool((np.diff(d2_p, axis=1) >= 0).all()) and rel <= 1e-2,
           f"top-k k={k}: distances not ascending or off by {rel:.2e}")
    return f"id mismatch vs numpy {miss_ref:.2%}, vs XLA {miss_xla:.2%}"


def check_count(n: int = 65_709, d: int = 16, gate: bool = True) -> str:
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.dbscan import _core_mask_xla
    from spark_rapids_ml_tpu.ops.pallas_select import (
        fused_count_below, use_fused_count,
    )

    if gate:
        _check(use_fused_count(n), f"fused count gate is closed at n={n}")
    # 64 tight blobs near the origin (|x|^2 ~ 9, blob radius ~0.06, centers
    # >= 1 apart): with eps^2 = 0.5 a row's neighbours are exactly the valid
    # rows of its own blob. The scan's single-pass (bf16-class) cross term is
    # off by up to ~3e-3 * |q||x| ~ 0.04 in d^2 — far from both the intra-blob
    # (<= 0.02) and inter-blob (>= 1) side of the threshold. Data far from the
    # origin would NOT be safe: the |q|^2 - 2qx + |x|^2 expansion cancels
    # (ROADMAP D11).
    rng = np.random.default_rng(SEED + 23)
    blob = rng.integers(0, 64, size=n)
    centers = rng.standard_normal((64, d)) * 0.75
    X = (centers[blob] + 0.01 * rng.standard_normal((n, d))).astype(np.float32)
    sep = ((centers[:, None] - centers[None]) ** 2).sum(axis=2)
    _check(float(sep[~np.eye(64, dtype=bool)].min()) > 1.0,
           "count check: seeded blob centers landed too close")
    valid = rng.random(n) > 0.2
    want = np.bincount(blob[valid], minlength=64)[blob]
    got = np.asarray(fused_count_below(
        jnp.asarray(X), jnp.asarray(X), jnp.asarray(valid), 0.5))
    _check(bool((got == want).all()),
           f"fused count: {int((got != want).sum())} of {n} rows differ from numpy")
    min_samples = int(np.median(want))
    core_x = np.asarray(_core_mask_xla(
        jnp.asarray(X), jnp.asarray(valid), 0.5, min_samples))
    _check(bool((((got >= min_samples) & valid) == core_x).all()),
           "fused count core mask differs from the XLA scan")
    return f"{n} neighbourhood counts exact; core mask == XLA"


def check_logistic_eval(d: int, n: int = 100_013, gate: bool = True,
                        want: Tuple[bool, str] = (True, "layout")) -> str:
    """The quasi-Newton fit's one-read evaluation (`ops/pallas_logistic.py`)
    on a table placed as a fit places it: the gate's verdict from the placed
    table's own layout (`want`: the runtime keeps a width that is a multiple
    of 128 row-major, and such a table keeps the two XLA passes), then value
    and gradient of the binary loss through the rule against float64 numpy
    and against autodiff's two passes."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.logistic import _binomial_loss_fn
    from spark_rapids_ml_tpu.ops.pallas_logistic import eval_gate, eval_plan

    rng = np.random.default_rng(SEED + d)
    X = (rng.standard_normal((n, d)) + 0.3 * rng.standard_normal(d)).astype(np.float32)
    truth = rng.standard_normal(d) * (2.0 / np.sqrt(d))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-(X @ truth + 0.25)))).astype(np.float32)
    w = rng.integers(1, 4, size=n).astype(np.float32)
    w[-100:] = 0.0  # the pad_rows contract: zero-weight suffix
    params = np.append(truth * 0.5, 0.1).astype(np.float32)
    Xj = jnp.asarray(X)
    verdict = eval_gate(Xj, False)
    layout = Xj.format.layout
    placed = f"placed major_to_minor={tuple(layout.major_to_minor)}"
    if gate:
        _check(verdict == want, f"logistic eval d={d}: the gate says {verdict}, {placed}")
        if not want[0]:
            return f"{placed}: the gate keeps two passes, reason `{want[1]}`"
    args = (Xj, jnp.asarray(y), jnp.asarray(w), jnp.ones(d, jnp.float32), 1e-5, True)
    v_f, g_f = jax.jit(jax.value_and_grad(_binomial_loss_fn(*args, fused=eval_plan(Xj))))(params)
    v_x, g_x = jax.jit(jax.value_and_grad(_binomial_loss_fn(*args)))(params)
    X64, w64 = X.astype(np.float64), w.astype(np.float64)
    z = X64 @ params[:-1].astype(np.float64) + float(params[-1])
    r = w64 * (1.0 / (1.0 + np.exp(-z)) - y)
    coef = params[:-1].astype(np.float64)
    v_ref = (w64 * (np.logaddexp(0.0, z) - y * z)).sum() / w64.sum() + 0.5e-5 * coef @ coef
    g_ref = np.append(X64.T @ r / w64.sum() + 1e-5 * coef, r.sum() / w64.sum())
    rms = float(np.sqrt(np.mean(g_ref * g_ref)))
    e_r = float(np.abs(np.asarray(g_f) - g_ref).max()) / rms
    e_x = float(np.abs(np.asarray(g_f) - np.asarray(g_x)).max()) / rms
    e_v = abs(float(v_f) - v_ref) / v_ref
    # float32 sums over 1e5 rows on the vector unit: the sweep's per-lane sums
    # leave 5e-6 of the RMS coordinate, XLA's two passes 8e-5 (one v5e, PR 35);
    # bfloat16 operands would leave 3e-3 (PERF.md §2)
    _check(e_r <= 2e-5 and e_x <= 3e-4 and e_v <= 2e-6,
           f"logistic eval d={d}: gradient vs numpy {e_r:.2e}, vs two passes {e_x:.2e}, "
           f"value {e_v:.2e}")
    return f"{placed}: gradient vs numpy {e_r:.1e}, vs two passes {e_x:.1e}, value {e_v:.1e}"


def check_histograms(n: int = 100_013, d: int = 64, gate: bool = True) -> str:
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops.pallas_histogram import (
        default_use_pallas, node_bin_histogram, segment_histogram,
    )

    if gate:
        _check(default_use_pallas(), "pallas histogram gate is closed")
    width, nbins, s = 16, 32, 3
    rng = np.random.default_rng(SEED + 29)
    Xb = jnp.asarray(rng.integers(0, nbins, size=(n, d)).astype(np.int32))
    node = jnp.asarray(rng.integers(0, width, size=n).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((n, s)).astype(np.float32))
    h_p = np.asarray(node_bin_histogram(Xb, node, vals, width, nbins, True))
    h_x = np.asarray(node_bin_histogram(Xb, node, vals, width, nbins, False))
    seg = node[:, None] * nbins + Xb
    g_p = np.asarray(segment_histogram(seg, vals, width * nbins, True))
    g_x = np.asarray(segment_histogram(seg, vals, width * nbins, False))
    # each bin sums ~200 unit gaussians (sum|v| ~ 160). Both kernels contract
    # a one-hot with the stat values in ONE default-precision MXU pass, which
    # rounds the values to bf16 (rel 2^-9 each, random sign): expected error
    # ~2e-3 * sqrt(200) / 160 ~ 2e-4 of sum|v| (2.6e-4 measured on a v5e, PR
    # 21), against exact f32 from the XLA segment_sum path. 1e-3 admits that
    # and rejects anything worse than bf16 operands (ROADMAP S8 owns the fix:
    # Mosaic now accepts precision=HIGHEST on the kernel dot).
    scale = float(np.abs(np.asarray(vals)).sum() / (width * nbins))
    e_h = float(np.abs(h_p - h_x).max()) / scale
    e_g = float(np.abs(g_p - g_x).max()) / scale
    _check(h_p.shape == (width, d, nbins, s) and e_h <= 1e-3,
           f"node-bin histogram vs XLA: {e_h:.2e}")
    _check(g_p.shape == (d, width * nbins, s) and e_g <= 1e-3,
           f"segment histogram vs XLA: {e_g:.2e}")
    return f"node-bin vs XLA {e_h:.1e}, segment vs XLA {e_g:.1e}"


# the ten pl.pallas_call sites: xtwx (2), kmeans (2), select (3), histogram
# (2), logistic (1: 3000 and 300 columns are placed column-major and take the
# kernel, 256 row-major and keeps two passes; 3000 columns walk 512-sample
# blocks, 300 the largest, 4096, with 1,709 samples past the last whole block);
# and the XLA Lloyd program's three-pass assignment, which runs per row
# shard (`python chip_smoke.py assign3` on four chips runs that check alone)
KERNEL_CHECKS: List[Tuple[str, Callable[[], str]]] = [
    ("pallas_xtwx xtx (Gram) d=128", lambda: check_gram(128)),
    ("pallas_xtwx xtx (Gram) d=512", lambda: check_gram(512)),
    ("pallas_xtwx xtxy (normal-eq) d=128", lambda: check_normal_eq(128)),
    ("pallas_xtwx xtxy (normal-eq) d=512", lambda: check_normal_eq(512)),
    ("pallas_kmeans lloyd masked k=128", lambda: check_lloyd(True)),
    ("pallas_kmeans lloyd weighted k=128", lambda: check_lloyd(False)),
    ("pallas_select assign k=128", check_assign),
    ("pallas_select top-k scan k=10", lambda: check_topk(10)),
    ("pallas_select top-k scan k=32", lambda: check_topk(32)),
    ("pallas_select count (DBSCAN)", check_count),
    ("pallas_histogram node-bin + segment, 32 bins", check_histograms),
    ("pallas_logistic eval d=3000", lambda: check_logistic_eval(3000, n=40_013)),
    ("pallas_logistic eval d=300", lambda: check_logistic_eval(300)),
    ("pallas_logistic eval d=256 (row-major: two passes)",
     lambda: check_logistic_eval(256, want=(False, "layout"))),
    ("xla lloyd assign3 k=512 d=1024", check_assign3),
]


def leg_kernels(st: Dict[str, Any]) -> None:
    only = st.get("only") or [""]
    for name, fn in KERNEL_CHECKS:
        if not any(word in name for word in only):
            continue
        t0 = time.perf_counter()
        detail = fn()
        by = "XLA" if name.startswith("xla") else "Mosaic"
        _say(f"  {name}: compiled by {by}, {detail} "
             f"[{time.perf_counter() - t0:.1f}s cold set-up, not a speed]")


def leg_after(st: Dict[str, Any]) -> None:
    """Nothing on the path hid the device: no degradation rung fired, every
    compiled executable carries a cost analysis, nothing ran in interpret mode."""
    from spark_rapids_ml_tpu import native, profiling
    from spark_rapids_ml_tpu.observability.device import kernel_cost_records

    totals = profiling.counter_totals()
    bad = {k: v for k, v in totals.items()
           if k.startswith("reliability.degrade") and v}
    _check(not bad, f"a degradation rung fired: {bad}")
    # compiled_kernel has no fallback left to count (an AOT compile or call
    # failure raises); what remains countable is an executable XLA gave no
    # cost analysis for
    unanalyzed = {k: v for k, v in totals.items()
                  if k.startswith("device.analysis_unavailable") and v}
    _check(not unanalyzed, f"executables without cost analysis: {unanalyzed}")
    interp = [r["kernel"] for r in kernel_cost_records()
              if "interpret=True" in r["signature"]]
    _check(not interp, f"kernels compiled in interpret mode: {interp}")
    _say(f"  reliability.degrade.* = 0; no interpret-mode kernel; "
         f"native.available() = {native.available()}")


# ------------------------------------------------------------------------ main


def main() -> int:
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: refusing to run: jax.devices()[0].platform is "
              f"{dev.platform!r}, not 'tpu' (JAX found no accelerator). This "
              "script proves the system on the chip; it never falls back to "
              "the CPU.", file=sys.stderr)
        return 2

    import jaxlib

    from spark_rapids_ml_tpu.parallel.partitioner import active_partitioner
    from spark_rapids_ml_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_: Any) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            cache_events["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # metadata only; no leg depends on it
        libtpu = "not installed as a package"
    _say(f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind!r} "
         f"devices={len(devices)} jax={jax.__version__} "
         f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    _say(f"chip_smoke: compile cache directory = {cache_dir}")
    mesh = active_partitioner().mesh
    _check(mesh.devices.size == len(devices),
           f"mesh has {mesh.devices.size} devices, jax has {len(devices)}")
    _say(f"chip_smoke: mesh {dict(mesh.shape)} over devices "
         f"{[d.id for d in mesh.devices.flat]}")

    only = sys.argv[1:]  # words of kernel checks' names: those alone, no other leg
    legs = (("fit", leg_fit), ("transform", leg_transform),
            ("serve", leg_serve), ("stream", leg_stream),
            ("kernels", leg_kernels), ("after", leg_after))
    st: Dict[str, Any] = {"only": only}
    if only:
        legs = legs[4:]
    else:
        t0 = time.perf_counter()
        X, true_centers = make_blobs(N_ROWS, N_COLS, KMEANS_K, SEED)
        _say(f"[data] {N_ROWS} x {N_COLS} float32 seeded blobs on the host "
             f"({X.nbytes / 1e9:.2f} GB) in {time.perf_counter() - t0:.1f}s")
        st.update(X=X, true_centers=true_centers)
    for name, leg in legs:
        _say(f"[{name}]")
        t0 = time.perf_counter()
        leg(st)
        _say(f"[{name}] passed; {time.perf_counter() - t0:.1f}s wall incl. "
             "compile and host reference (cold set-up information, not a speed)")

    if not only:
        _say(f"chip_smoke: placement by device id (allocator activity per leg): "
             f"fit={st['fit_touched']} transform={st['transform_touched']} "
             f"serve={st['serve_touched']} of devices {[d.id for d in devices]}")
    _say(f"chip_smoke: persistent compile cache hits={cache_events['hits']} "
         f"misses={cache_events['misses']}; total set-up wall "
         f"{time.perf_counter() - t_start:.1f}s (cold when hits=0)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
