#
# Out-of-core fitting: streamed sufficient-statistics accumulation.
#
# The reference fits datasets larger than device memory through RMM UVM/SAM managed
# memory (reference utils.py:184-241, SURVEY.md §2.5 last row). TPUs have no UVM;
# the TPU-native answer (SURVEY.md §7 "hard parts") is to stream host batches through
# the device and ACCUMULATE the model-sufficient statistics on device:
#   * PCA / LinearRegression: (XᵀWX, XᵀWy, Σwx, Σwy, Σw) accumulate exactly —
#     the fit result is IDENTICAL to the in-core path, with device residency bounded
#     by two batches (double-buffered prefetch) + the d×d stats,
#   * KMeans: per-pass Lloyd over batches (minibatch-free exact variant: each
#     iteration streams all batches, accumulating one-hotᵀX sums and counts).
# Estimators switch to this path automatically when the padded design matrix would
# exceed `config` threshold SRML_TPU_STREAM_THRESHOLD_BYTES (see core/estimator.py).
#

from __future__ import annotations

from collections import deque
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import (
    convergence as obs_convergence,
    counter_inc as obs_counter_inc,
    progress as obs_progress,
    span as obs_span,
)
from ..observability.device import compiled_kernel
from ..reliability import (
    StreamBatchError,
    fault_point,
    is_device_error,
    is_transient,
    resumable_accumulate,
)
from ._precision import pdot
from .ingest import StagingPool, stage_block
from .linalg import kahan_add as _kahan_add


# ----------------------------------------------------------------- fused chains
#
# A "chain" is the featurize prefix of a fused featurize->fit pipeline
# (pipeline.py::_try_fused_fit, docs/design.md §6k): a tuple of host-side
# ("scale", mean, std) / ("project", components) ops applied ON DEVICE inside
# every accumulator kernel — after the in-program ingest cast, before any
# statistic — so the intermediate (scaled / projected X) exists only inside
# the compiled program: it never round-trips to host and never materializes a
# second HBM copy. The expressions are EXACTLY the staged transforms'
# (StandardScalerModel: (X - mean) / std; PCAModel: pdot(X, components.T));
# bit-parity with the staged path is the contract the fuser ships under.


def chain_out_dim(d: int, chain_ops) -> int:
    """Feature width after the chain (a projection rewrites it to its
    component count; scaling preserves it)."""
    for op in chain_ops or ():
        if op[0] == "project":
            d = int(np.asarray(op[1]).shape[0])
    return d


def _prep_chain(chain_ops, dt):
    """Split host chain ops into the (static kinds, device operand arrays)
    pair the accumulator kernels take. Operands are staged once per fit in
    compute dtype — the staged transforms' own operand dtype."""
    if not chain_ops:
        return (), ()
    kinds = []
    arrays = []
    for op in chain_ops:
        kinds.append(str(op[0]))
        arrays.extend(jnp.asarray(np.asarray(a, dtype=dt)) for a in op[1:])
    return tuple(kinds), tuple(arrays)


def _apply_chain(X, dt, chain, chain_arrays):
    """The FIRST fused step of every accumulator kernel: the in-program
    ingest cast (identity when the batch already arrived in compute dtype)
    followed by the featurize chain."""
    X = X.astype(dt)
    i = 0
    for kind in chain:
        if kind == "scale":
            mean, std = chain_arrays[i], chain_arrays[i + 1]
            i += 2
            X = (X - mean) / std
        elif kind == "project":
            comps = chain_arrays[i]
            i += 1
            X = pdot(X, comps.T)
        else:
            raise ValueError(f"unknown chain op '{kind}'")
    return X


def _prefetch(iterable, depth: int = 1, site: Optional[str] = None, start_batch: int = 0):
    """Double-buffered batch pipeline: keep `depth` extra batches in flight so the
    host slice/pad/device_put of batch i+1 overlaps the device accumulation of
    batch i (jax dispatch is async; the DMA rides a separate engine on TPU). This
    is the streamed-ingest overlap the reference gets implicitly from UVM
    prefetching. Peak device residency is depth+1 batches — depth=1 is true
    double buffering (the out-of-core batch-size guidance assumes 2 live
    batches; a larger depth trades HBM for pipeline slack).

    Exception transparency: with a `site`, a failure the reliability ladder
    handles (transient host/I-O errors, device errors) raised while REFILLING
    the buffer is wrapped in a StreamBatchError carrying the batch ordinal
    (offset by `start_batch` on resumed streams), so the checkpoint-resume layer
    (reliability/checkpoint.py) sees where the pipeline broke instead of a bare
    mid-pipeline exception. Param/programming errors (ValueError-class) keep
    their original type — they are API surface, not pipeline weather."""
    it = iter(iterable)
    buf: deque = deque()
    pulled = start_batch

    def _refill() -> bool:
        nonlocal pulled
        try:
            buf.append(next(it))
        except StopIteration:
            return False
        except StreamBatchError:
            raise  # already carries its site/batch context
        except Exception as e:
            if site is None or not (is_transient(e) or is_device_error(e)):
                raise
            raise StreamBatchError(site, pulled, e) from e
        pulled += 1
        return True

    for _ in range(depth):
        if not _refill():
            break
    while buf:
        yield buf.popleft()
        _refill()


def _batch_stream(n: int, batch_rows: int, mesh, slicer, start_row: int = 0,
                  site: str = "ingest", cache=None, cache_key=None):
    """THE out-of-core ingest loop, shared by every streamed fit: `slicer(s, e)`
    returns row-aligned HOST arrays — X first, the weight vector LAST — for rows
    [s, e); this pads to the mesh (zero-weighting pad rows), shards, and yields
    device tuples. The ragged tail keeps its natural size: it compiles one extra
    accumulator entry ONCE and reuses it every pass (padding it to batch_rows
    instead was measured to upload a nearly-all-zeros full batch per pass when
    n % batch_rows is small). `start_row` (a batch boundary) re-opens the stream
    mid-pass for checkpoint-resume; `site` names the fault-injection point
    (reliability/faults.py) planted before each batch is sliced.

    With a `cache` (ops/device_cache.py) + `cache_key`, batches already HBM-
    resident replay without touching the host; fresh batches are retained after
    upload, budget permitting. The fault point fires BEFORE the cache lookup so
    replayed batches stay fault-injectable, and every actual upload is counted
    (`stream.upload_batches`/`stream.upload_bytes`) and timed
    (`stream.ingest_s.<site>` in span_totals) — the evidence that passes 2..N
    of a cached fit stop paying host->device ingest."""
    from ..parallel.partition import pad_rows
    from ..parallel.partitioner import partitioner_for

    from .device_cache import cached_build

    part = partitioner_for(mesh) if mesh is not None else None
    for s in range(start_row, n, batch_rows):
        e = min(s + batch_rows, n)
        batch_index = s // batch_rows
        fault_point(site, batch=batch_index)

        def build(s=s, e=e):
            arrays = slicer(s, e)
            if part is not None:
                X_, *extras = arrays
                Xp, pad_w, extras_p = pad_rows(X_, part.num_workers, *extras)
                *mid, wv = extras_p
                out = [part.shard(Xp)]
                out += [part.shard(a) for a in mid]
                out.append(part.shard(pad_w * wv))
                out = tuple(out)
            else:
                out = tuple(jnp.asarray(a) for a in arrays)
            # transfer fence: device_put returns BEFORE the runtime has read the
            # host buffer (measured on a v5e: a 64 MiB buffer rewritten right
            # after device_put arrived fully corrupted), and the slicer's
            # staging buffers are reused by the next batch (ops/ingest.py).
            # Waiting here costs no overlap that matters: the accumulate that
            # consumes this batch needs the transfer anyway, and batch i's
            # compute (already dispatched) still runs under it.
            return jax.block_until_ready(out)

        yield cached_build(cache, cache_key, batch_index, site, build)


def _accumulate_stream(carry, accum, n, batch_rows, mesh, slicer, site: str = "ingest",
                       cache=None, cache_key=None,
                       progress_phase: Optional[str] = None):
    """Checkpoint-resumable streamed accumulation, shared by every streamed fit:
    fold `accum(carry, batch_tuple) -> carry` over the prefetched batch stream,
    snapshotting (carry, cursor) every reliability.checkpoint_batches batches so
    a transient batch failure resumes from the last snapshot instead of
    restarting the pass (reliability/checkpoint.py) — resumed results are
    bit-identical to the fault-free pass. `cache`/`cache_key` (multi-pass fits:
    one cache handle across all passes) replay HBM-resident batches instead of
    re-uploading; a resumed stream replays hits and re-uploads misses through
    the same cursor arithmetic.

    Every folded batch publishes the live batch-progress gauge
    `fit.progress{phase=<progress_phase>}` (done/total + EMA ETA — §6g), so a
    mid-pass fit is visible through /runs/<id>. The counter restarts each pass
    and clamps at the total on a checkpoint-resume replay (progress is
    advisory telemetry, never an accounting surface)."""
    total_batches = max(1, -(-n // batch_rows))
    phase = progress_phase or f"{site}.batches"
    state = {"done": 0}

    def accum_with_progress(c, batch):
        c = accum(c, batch)
        state["done"] = min(state["done"] + 1, total_batches)
        obs_progress(phase, state["done"], total_batches, unit="batches")
        return c

    def factory(start_row: int):
        state["done"] = min(start_row // batch_rows, total_batches)
        return _prefetch(
            _batch_stream(n, batch_rows, mesh, slicer, start_row=start_row, site=site,
                          cache=cache, cache_key=cache_key),
            site=site,
            start_batch=start_row // batch_rows,
        )

    return resumable_accumulate(
        site, factory, accum_with_progress, carry, batch_rows, n
    )


# Every streamed accumulator donates its carry (argnum 0): the per-batch carry
# update then reuses the old stats buffers instead of allocating a fresh set
# per batch. Batch operands are NEVER donated — cached batches (device_cache)
# must survive the call to replay on later passes. The checkpoint-resume layer
# snapshots carry COPIES for the same reason (reliability/checkpoint.py).
@compiled_kernel("streaming.accum_linreg", static_argnames=("chain",),
                 donate_argnums=(0,))
def _accum_linreg(carry, X, y, w, chain_arrays=(), chain=()):
    A, b, sx, sy, sw = carry
    dt = A.dtype
    X = _apply_chain(X, dt, chain, chain_arrays)
    y = y.astype(dt)
    w = w.astype(dt)
    Xw = X * w[:, None]
    return (
        A + pdot(Xw.T, X),
        b + pdot(Xw.T, y),
        sx + pdot(w, X),
        sy + jnp.sum(w * y),
        sw + jnp.sum(w),
    )


@compiled_kernel("streaming.accum_cov", static_argnames=("chain",),
                 donate_argnums=(0,))
def _accum_cov(carry, X, w, chain_arrays=(), chain=()):
    S2, sx, sw = carry
    dt = S2.dtype
    X = _apply_chain(X, dt, chain, chain_arrays)
    w = w.astype(dt)
    return (
        S2 + pdot((X * w[:, None]).T, X),
        sx + pdot(w, X),
        sw + jnp.sum(w),
    )


def streaming_linreg_stats(
    X: np.ndarray,
    y: np.ndarray,
    w: Optional[np.ndarray],
    batch_rows: int,
    mesh=None,
    float32: bool = True,
    chain_ops=None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Streamed normal-equation statistics with O(batch) device residency:
    each batch is device_put (sharded over the mesh when given) and its raw
    moments (XᵀWX, XᵀWy, Σwx, Σwy, Σw) accumulated; the sums are centred once
    at the end (`ops/linear.py::center_moments`), so the solve gets the
    centred (S, r, x̄, ȳ, Σw) the in-core `normal_equations` gives it. dtype
    follows float32 (float64 additionally needs jax x64 mode, matching the
    in-core path's device behavior). `chain_ops` fuses a featurize prefix into
    the per-batch program (docs/design.md §6k)."""
    from .device_cache import batch_cache
    from .linear import center_moments

    obs_counter_inc("linear.stats_form", 1, form="streamed")

    dt = np.float32 if float32 else np.float64
    n = X.shape[0]
    d = chain_out_dim(X.shape[1], chain_ops)
    kinds, chain_arrays = _prep_chain(chain_ops, dt)
    A = jnp.zeros((d, d), dt)
    b = jnp.zeros((d,), dt)
    sx = jnp.zeros((d,), dt)
    sy = jnp.zeros((), dt)
    sw = jnp.zeros((), dt)
    carry = (A, b, sx, sy, sw)

    pool = StagingPool()
    ones = np.ones((min(batch_rows, n),), dt) if w is None else None

    def slicer(s, e):
        return (
            stage_block(X, s, e, dt, pool, slot="X"),
            stage_block(y, s, e, dt, pool, slot="y"),
            ones[: e - s]
            if w is None
            else stage_block(w, s, e, dt, pool, slot="w"),
        )

    with batch_cache() as cache:
        ckey = (
            cache.stream_key(
                tuple(a for a in (X, y, w) if a is not None), batch_rows, mesh
            )
            if cache is not None
            else None
        )
        carry = _accumulate_stream(
            carry,
            lambda c, batch: _accum_linreg(c, *batch, chain_arrays, kinds),
            n, batch_rows, mesh, slicer, cache=cache, cache_key=ckey,
            progress_phase="linreg.batches",
        )
    A, b, sx, sy, sw = carry
    return center_moments(A, b, sx / sw, sy / sw, sw)


def streaming_covariance(
    X: np.ndarray,
    w: Optional[np.ndarray],
    batch_rows: int,
    mesh=None,
    float32: bool = True,
    chain_ops=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Streamed weighted covariance (cov, mean, Σw) for PCA — the same math as
    ops/linalg.weighted_covariance, dtype per `float32` (see
    streaming_linreg_stats). `chain_ops` fuses a featurize prefix into the
    per-batch program; the active HBM batch-cache scope is shared, so the
    other passes of a fused chain replay these batches."""
    from .device_cache import batch_cache

    dt = np.float32 if float32 else np.float64
    n = X.shape[0]
    d = chain_out_dim(X.shape[1], chain_ops)
    kinds, chain_arrays = _prep_chain(chain_ops, dt)
    carry = (
        jnp.zeros((d, d), dt),
        jnp.zeros((d,), dt),
        jnp.zeros((), dt),
    )

    pool = StagingPool()
    ones = np.ones((min(batch_rows, n),), dt) if w is None else None

    def slicer(s, e):
        return (
            stage_block(X, s, e, dt, pool, slot="X"),
            ones[: e - s]
            if w is None
            else stage_block(w, s, e, dt, pool, slot="w"),
        )

    with batch_cache() as cache:
        ckey = (
            cache.stream_key(
                tuple(a for a in (X, w) if a is not None), batch_rows, mesh
            )
            if cache is not None
            else None
        )
        carry = _accumulate_stream(
            carry,
            lambda c, batch: _accum_cov(c, *batch, chain_arrays, kinds),
            n, batch_rows, mesh, slicer, cache=cache, cache_key=ckey,
            progress_phase="pca.batches",
        )
    S2, sx, sw = carry
    mean = sx / sw
    cov = (S2 - sw * jnp.outer(mean, mean)) / (sw - 1.0)
    return cov, mean, sw


def streaming_moments(
    X: np.ndarray,
    w: Optional[np.ndarray],
    batch_rows: int,
    mesh=None,
    float32: bool = True,
    chain_ops=None,
) -> Tuple[np.ndarray, np.ndarray, float]:
    """Streamed weighted feature moments -> (mean, var, Σw), Spark Summarizer
    semantics (variance normalized by Σw-1, matching ops/linalg
    weighted_moments and the streamed-logreg standardization pass). This is
    the StandardScaler fit statistic; `chain_ops` lets a fused pipeline
    compute the moments of an already-chained (e.g. projected) feature space.
    Shares the active HBM batch-cache scope: in a fused chain the fit passes
    that follow replay the batches this pass uploaded."""
    from .device_cache import batch_cache

    dt = np.float32 if float32 else np.float64
    n = X.shape[0]
    d = chain_out_dim(X.shape[1], chain_ops)
    kinds, chain_arrays = _prep_chain(chain_ops, dt)
    carry = (jnp.zeros((d,), dt), jnp.zeros((d,), dt), jnp.zeros((), dt))

    pool = StagingPool()
    ones = np.ones((min(batch_rows, n),), dt) if w is None else None

    def slicer(s, e):
        return (
            stage_block(X, s, e, dt, pool, slot="X"),
            ones[: e - s]
            if w is None
            else stage_block(w, s, e, dt, pool, slot="w"),
        )

    with batch_cache() as cache:
        ckey = (
            cache.stream_key(
                tuple(a for a in (X, w) if a is not None), batch_rows, mesh
            )
            if cache is not None
            else None
        )
        carry = _accumulate_stream(
            carry,
            lambda c, batch: _accum_moments(c, *batch, chain_arrays, kinds),
            n, batch_rows, mesh, slicer, cache=cache, cache_key=ckey,
            progress_phase="scaler.batches",
        )
    sx, sxx, sw_j = carry
    wsum = float(sw_j)
    mean = np.asarray(sx) / wsum
    var = np.maximum((np.asarray(sxx) - wsum * mean * mean) / (wsum - 1.0), 0.0)
    return mean, var, wsum


@compiled_kernel(
    "streaming.logreg_value_grad",
    static_argnames=("fit_intercept", "multinomial", "chain"),
    donate_argnums=(0, 1, 2, 3),
)
def _logreg_accum_value_grad(
    acc_v, comp_v, acc_g, comp_g, params, X, y_enc, w, scale, chain_arrays,
    fit_intercept, multinomial, chain=(),
):
    """One batch of the UNNORMALIZED cross-entropy value+grad folded into the
    running device accumulators (no /Σw, no penalty — the caller normalizes and
    adds the L2 term once). The per-batch loss form mirrors
    ops/logistic._binomial_loss_fn / _multinomial_loss_fn so the streamed
    objective is the in-core objective. The whole carry (accumulators + Kahan
    compensations) is donated: each batch update reuses the buffers in place of
    a fresh allocation, and the running loss/grad never round-trips to host
    mid-pass."""
    dt = acc_g.dtype
    X = _apply_chain(X, dt, chain, chain_arrays)
    y_enc = y_enc.astype(dt)
    w = w.astype(dt)

    def f(p):
        if multinomial:
            coef_s, b = p[:, :-1], p[:, -1]
            z = pdot(X, (coef_s / scale).T) + jnp.where(fit_intercept, b, 0.0)
            return -jnp.sum(w * jnp.sum(y_enc * jax.nn.log_softmax(z, axis=1), axis=1))
        coef_s, b = p[:-1], p[-1]
        z = pdot(X, coef_s / scale) + jnp.where(fit_intercept, b, 0.0)
        return jnp.sum(w * (jax.nn.softplus(z) - y_enc * z))

    v, g = jax.value_and_grad(f)(params)
    acc_v, comp_v = _kahan_add(acc_v, comp_v, v)
    acc_g, comp_g = _kahan_add(acc_g, comp_g, g)
    return acc_v, comp_v, acc_g, comp_g


@compiled_kernel("streaming.accum_moments", static_argnames=("chain",),
                 donate_argnums=(0,))
def _accum_moments(carry, X, w, chain_arrays=(), chain=()):
    sx, sxx, sw = carry
    dt = sx.dtype
    X = _apply_chain(X, dt, chain, chain_arrays)
    w = w.astype(dt)
    return (sx + pdot(w, X), sxx + pdot(w, X * X), sw + jnp.sum(w))


def _strong_wolfe(f, x, fx, gx, p, max_steps: int, c1=1e-4, c2=0.9):
    """Strong-Wolfe line search (zoom), scipy-style: each trial costs one full
    streamed data pass. Returns (alpha, f_new, g_new, n_evals); when the budget
    runs out it falls back to the best SUFFICIENT-DECREASE (Armijo) point seen —
    never to an objective-increasing trial — and signals failure with alpha=0 if
    no trial achieved sufficient decrease at all (the caller stops rather than
    step uphill). The reference's QN solver caps linesearch at 20 the same way."""
    d0 = float(np.vdot(gx, p))
    if d0 >= 0:  # not a descent direction (numerical breakdown): bail
        return 0.0, fx, gx, 0

    def phi(alpha):
        fv, gv = f(x + alpha * p)
        return fv, gv, float(np.vdot(gv, p))

    def armijo(alpha, f_a):
        return f_a <= fx + c1 * alpha * d0

    if max_steps <= 0:
        return 0.0, fx, gx, 0
    best = None  # best Armijo-satisfying trial: (alpha, f, g)
    alpha_prev, f_prev = 0.0, fx
    alpha = 1.0
    n_evals = 0
    lo = hi = None
    f_lo = None
    for i in range(max_steps):
        f_a, g_a, d_a = phi(alpha)
        n_evals += 1
        if armijo(alpha, f_a) and (best is None or f_a < best[1]):
            best = (alpha, f_a, g_a)
        if not armijo(alpha, f_a) or (i > 0 and f_a >= f_prev):
            lo, hi, f_lo = alpha_prev, alpha, f_prev
            break
        if abs(d_a) <= -c2 * d0:
            return alpha, f_a, g_a, n_evals
        if d_a >= 0:
            lo, hi, f_lo = alpha, alpha_prev, f_a
            break
        alpha_prev, f_prev = alpha, f_a
        alpha *= 2.0
    else:
        # expansion budget exhausted with every trial Armijo-passing: return the
        # LAST EVALUATED point (alpha has already been doubled past it — returning
        # alpha would pair an unevaluated step with stale f/g and corrupt the
        # L-BFGS curvature history)
        return alpha_prev, f_a, g_a, n_evals

    # zoom phase
    while n_evals < max_steps:
        mid = 0.5 * (lo + hi)
        f_m, g_m, d_m = phi(mid)
        n_evals += 1
        if not armijo(mid, f_m) or f_m >= f_lo:
            hi = mid
        else:
            if best is None or f_m < best[1]:
                best = (mid, f_m, g_m)
            if abs(d_m) <= -c2 * d0:
                return mid, f_m, g_m, n_evals
            if d_m * (hi - lo) >= 0:
                hi = lo
            lo, f_lo = mid, f_m
    if best is None:
        return 0.0, fx, gx, n_evals  # no sufficient decrease anywhere: signal stop
    return best[0], best[1], best[2], n_evals


def streaming_logreg_fit(
    X: np.ndarray,
    y: np.ndarray,
    w: Optional[np.ndarray],
    n_classes: int,
    reg: float,
    l1_ratio: float,
    fit_intercept: bool,
    standardize: bool,
    max_iter: int,
    tol: float,
    multinomial: bool,
    batch_rows: int,
    mesh=None,
    float32: bool = True,
    chain_ops=None,
):
    """Out-of-core distributed L-BFGS logistic regression: X stays HOST-resident;
    each objective/gradient evaluation streams batches through the device and
    accumulates the unnormalized loss and gradient (sharded over the mesh when
    given — the per-batch contraction carries the gradient psum exactly where the
    in-core path does). The L-BFGS two-loop recursion and strong-Wolfe zoom line
    search run on host over the SMALL parameter vector (memory 10, linesearch
    <= 20 evals — the reference's QN settings, classification.py:1046-1052).

    This is the LogisticRegression analog of the reference's UVM/SAM
    larger-than-device-memory fitting (reference utils.py:184-241): BASELINE
    config 3 (500M x 256) cannot stage the design matrix in HBM.

    Solver dispatch mirrors the in-core logreg_fit: elasticNetParam > 0 runs a
    streamed FISTA (full-pass smooth gradient + host prox/Nesterov updates, the
    Lipschitz constant from a streamed Gram pass); otherwise distributed L-BFGS.

    Pass counts (docs/performance.md): L-BFGS costs 1 + ~2-4 streamed passes per
    iteration (one per line-search objective evaluation); FISTA costs exactly
    1 + n_iter passes plus one Gram pass (+1 moments pass when standardizing).
    ONE batch cache (ops/device_cache.py) spans every pass of the fit — the
    moments/Gram passes populate it and each value_and_grad evaluation replays
    from HBM, so only pass 1 (plus whatever exceeds the cache budget) pays
    host->device ingest; with the cache disabled every batch re-uploads per
    pass, the original out-of-core contract. The ragged tail batch compiles one
    extra accumulator entry once and reuses it every pass."""
    from .device_cache import batch_cache

    with batch_cache() as cache:
        return _streaming_logreg_fit(
            X, y, w, n_classes, reg, l1_ratio, fit_intercept, standardize,
            max_iter, tol, multinomial, batch_rows, mesh, float32, cache,
            chain_ops,
        )


def _streaming_logreg_fit(
    X, y, w, n_classes, reg, l1_ratio, fit_intercept, standardize, max_iter,
    tol, multinomial, batch_rows, mesh, float32, cache, chain_ops=None,
):
    dt = np.float32 if float32 else np.float64
    n = X.shape[0]
    d = chain_out_dim(X.shape[1], chain_ops)
    kinds, chain_arrays = _prep_chain(chain_ops, dt)
    reg_l1 = reg * l1_ratio
    reg_l2 = reg * (1.0 - l1_ratio)
    ckey = (
        cache.stream_key(
            tuple(a for a in (X, y, w) if a is not None), batch_rows, mesh
        )
        if cache is not None
        else None
    )

    pool = StagingPool()
    ones = np.ones((min(batch_rows, n),), dt) if w is None else None

    def _slicer(s, e):
        return (
            stage_block(X, s, e, dt, pool, slot="X"),
            stage_block(y, s, e, dt, pool, slot="y"),
            ones[: e - s]
            if w is None
            else stage_block(w, s, e, dt, pool, slot="w"),
        )

    # streamed standardization moments (Spark Summarizer wsum-1 variance,
    # matching ops/linalg.weighted_moments)
    if standardize:
        carry = (jnp.zeros((d,), dt), jnp.zeros((d,), dt), jnp.zeros((), dt))
        with obs_span("logreg.moments"):
            carry = _accumulate_stream(
                carry,
                lambda c, batch: _accum_moments(
                    c, batch[0], batch[2], chain_arrays, kinds
                ),
                n, batch_rows, mesh, _slicer, cache=cache, cache_key=ckey,
                progress_phase="logreg.moments",
            )
        sx, sxx, sw_j = carry
        wsum = float(sw_j)
        mean = np.asarray(sx) / wsum
        var = np.maximum(
            (np.asarray(sxx) - wsum * mean * mean) / (wsum - 1.0), 0.0
        )
        scale_h = np.sqrt(var)
        scale_h[scale_h <= 0.0] = 1.0
    else:
        scale_h = np.ones((d,), dt)
        wsum = float(np.sum(w)) if w is not None else float(n)
    scale = jnp.asarray(scale_h.astype(dt))

    if multinomial:
        shape = (n_classes, d + 1)
    else:
        shape = (d + 1,)

    _step_no = [0]

    def value_and_grad(params_flat: np.ndarray):
        # one objective/gradient evaluation == one full streamed pass: a
        # `logreg.step` span per pass in the fit trace, with its per-batch
        # `stream.ingest` uploads (if any) as children
        _step_no[0] += 1
        with obs_span("logreg.step", {"pass": _step_no[0]}):
            return _value_and_grad(params_flat)

    def _value_and_grad(params_flat: np.ndarray):
        params = jnp.asarray(params_flat.reshape(shape).astype(dt))

        def _accum_vg(carry, batch):
            Xb, yb, wb = batch
            y_enc = (
                jax.nn.one_hot(yb.astype(jnp.int32), n_classes, dtype=Xb.dtype)
                * (wb > 0)[:, None]
                if multinomial
                else yb
            )
            # Kahan-compensated device accumulation with the carry DONATED
            # (buffer reuse per batch); functional from the caller's view — the
            # resume layer's snapshots are copies (reliability/checkpoint.py),
            # never aliases of a buffer a later batch will donate
            return _logreg_accum_value_grad(
                *carry, params, Xb, y_enc, wb, scale, chain_arrays,
                bool(fit_intercept), bool(multinomial), kinds,
            )

        acc_v, _, acc_g, _ = _accumulate_stream(
            (
                jnp.zeros((), dt), jnp.zeros((), dt),
                jnp.zeros(shape, dt), jnp.zeros(shape, dt),
            ),
            _accum_vg,
            n, batch_rows, mesh, _slicer, cache=cache, cache_key=ckey,
            # phase is per-accumulation kind, not per-fit: blending the cheap
            # moments/gram passes into this EMA would corrupt the gradient
            # pass's ETA by the ratio of their per-batch costs
            progress_phase="logreg.grad",
        )
        coef_s = params_flat.reshape(shape)[..., :-1]
        value = float(acc_v) / wsum + 0.5 * reg_l2 * float(np.sum(coef_s * coef_s))
        grad = np.asarray(acc_g, np.float64) / wsum
        grad[..., :-1] += reg_l2 * coef_s
        return value, grad.reshape(-1)

    if reg_l1 > 0.0:
        # ---- streamed FISTA (elastic net): the in-core _fista_fit with the
        # smooth gradient evaluated by streamed passes; prox/Nesterov updates on
        # the small host parameter vector. Lipschitz from one streamed Gram pass
        # (the same (0.5|0.25)*lmax + reg_l2 bound as ops/logistic.py:311-312).
        from .linalg import power_iteration_lmax

        carry = (jnp.zeros((d, d), dt), jnp.zeros((d,), dt), jnp.zeros((), dt))
        # X/scale rides the fused program as one more ("scale", 0, scale)
        # chain link — (x - 0)/scale is bit-equal to x/scale, and the scaled
        # batch never materializes outside the accumulator
        gram_kinds = kinds + ("scale",)
        gram_arrays = chain_arrays + (jnp.zeros((d,), dt), scale)
        with obs_span("logreg.gram"):
            carry = _accumulate_stream(
                carry,
                lambda c, batch: _accum_cov(
                    c, batch[0], batch[2], gram_arrays, gram_kinds
                ),
                n, batch_rows, mesh, _slicer, cache=cache, cache_key=ckey,
                progress_phase="logreg.gram",
            )
        S2, _, sw_g = carry
        lmax = float(power_iteration_lmax(S2 / sw_g))
        lipschitz = (0.5 if multinomial else 0.25) * lmax + reg_l2 + 1e-12
        step = 1.0 / lipschitz
        coef_mask = np.ones(shape, np.float64)
        coef_mask[..., -1] = 0.0  # intercept entries are never penalized

        def prox(pv):
            soft = np.sign(pv) * np.maximum(np.abs(pv) - step * reg_l1, 0.0)
            return np.where(coef_mask > 0, soft, pv)

        pk = np.zeros(shape, np.float64)
        zk = pk.copy()
        tk = 1.0
        n_iter = 0
        for it in range(int(max_iter)):
            fv, g = value_and_grad(zk.reshape(-1))
            p_next = prox(zk - step * g.reshape(shape))
            t_next = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * tk * tk))
            zk = p_next + ((tk - 1.0) / t_next) * (p_next - pk)
            delta = float(
                np.max(np.abs(p_next - pk)) / (np.max(np.abs(p_next)) + 1e-12)
            )
            pk, tk = p_next, t_next
            n_iter = it + 1
            # §6g: loss here is the SMOOTH objective at the momentum point
            # (what the streamed pass evaluated); the L1 term is added once at
            # the end, so the record tracks descent direction, not the exact
            # composite objective
            obs_progress("logreg.iters", n_iter, int(max_iter), unit="iters")
            obs_convergence(
                "logreg", n_iter, loss=fv,
                grad_norm=float(np.linalg.norm(g)), delta=delta,
                solver="fista",
            )
            if delta <= tol:
                break
        x = pk.reshape(-1)
        fx, _ = value_and_grad(x)
        fx += reg_l1 * float(np.sum(np.abs(pk * coef_mask)))
        return _finish_logreg(
            x, shape, scale_h, fit_intercept, multinomial, n_iter, fx
        )

    # ---- host L-BFGS (two-loop recursion, memory 10) ----
    m = 10
    x = np.zeros(int(np.prod(shape)), np.float64)
    fx, gx = value_and_grad(x)
    s_hist: list = []
    y_hist: list = []
    n_iter = 0
    for it in range(int(max_iter)):
        gnorm = float(np.linalg.norm(gx))
        if gnorm <= tol:
            break
        # two-loop recursion
        q = gx.copy()
        alphas = []
        for s_i, y_i in zip(reversed(s_hist), reversed(y_hist)):
            rho_i = 1.0 / float(np.vdot(y_i, s_i))
            a_i = rho_i * float(np.vdot(s_i, q))
            q -= a_i * y_i
            alphas.append((a_i, rho_i))
        if s_hist:
            gamma = float(np.vdot(s_hist[-1], y_hist[-1])) / float(
                np.vdot(y_hist[-1], y_hist[-1])
            )
            q *= gamma
        for (a_i, rho_i), s_i, y_i in zip(reversed(alphas), s_hist, y_hist):
            b_i = rho_i * float(np.vdot(y_i, q))
            q += (a_i - b_i) * s_i
        p = -q
        alpha, f_new, g_new, _ = _strong_wolfe(
            value_and_grad, x, fx, gx, p, max_steps=20
        )
        if alpha == 0.0:
            break
        x_new = x + alpha * p
        s_i = x_new - x
        y_i = g_new - gx
        if float(np.vdot(s_i, y_i)) > 1e-10:
            s_hist.append(s_i)
            y_hist.append(y_i)
            if len(s_hist) > m:
                s_hist.pop(0)
                y_hist.pop(0)
        delta = abs(fx - f_new) / max(abs(f_new), 1.0)
        x, fx, gx = x_new, f_new, g_new
        n_iter = it + 1
        obs_progress("logreg.iters", n_iter, int(max_iter), unit="iters")
        obs_convergence(
            "logreg", n_iter, loss=fx,
            grad_norm=float(np.linalg.norm(gx)), delta=delta, solver="lbfgs",
        )
        if delta <= tol:
            break

    return _finish_logreg(x, shape, scale_h, fit_intercept, multinomial, n_iter, fx)


def _finish_logreg(x, shape, scale_h, fit_intercept, multinomial, n_iter, fx):
    """Un-standardize + Spark intercept centering, shared by both streamed solvers
    (same finishing as ops/logistic.logreg_fit)."""
    params = x.reshape(shape)
    if multinomial:
        coef = params[:, :-1] / scale_h
        intercept = params[:, -1]
        if fit_intercept:
            intercept = intercept - intercept.mean()
    else:
        coef = (params[:-1] / scale_h).reshape(1, -1)
        intercept = params[-1:]
    return {
        "coefficients": coef.astype(np.float32),
        "intercepts": intercept.astype(np.float32),
        "n_iter": int(n_iter),
        "objective": float(fx),
    }


@compiled_kernel("streaming.accum_kmeans", static_argnames=("cosine", "chain"),
                 donate_argnums=(0,))
def _accum_kmeans(carry, centers, X, w, chain_arrays=(), cosine: bool = False,
                  chain=()):
    """One batch of a streamed Lloyd iteration: accumulate per-cluster weighted sums,
    counts and inertia against FIXED centers."""
    sums, counts, inertia = carry
    dt = sums.dtype
    X = _apply_chain(X, dt, chain, chain_arrays)
    w = w.astype(dt)
    if cosine:
        d2 = 1.0 - pdot(X, centers.T)
    else:
        x2 = jnp.sum(X * X, axis=1, keepdims=True)
        c2 = jnp.sum(centers * centers, axis=1)
        d2 = jnp.maximum(x2 - 2.0 * pdot(X, centers.T) + c2, 0.0)
    assign = jnp.argmin(d2, axis=1)
    min_d2 = jnp.min(d2, axis=1)
    onehot = jax.nn.one_hot(assign, centers.shape[0], dtype=X.dtype) * w[:, None]
    return (
        sums + pdot(onehot.T, X),
        counts + jnp.sum(onehot, axis=0),
        inertia + jnp.sum(w * min_d2),
    )


def streaming_kmeans_fit(
    X: np.ndarray,
    w: Optional[np.ndarray],
    k: int,
    max_iter: int,
    tol: float,
    seed: int,
    batch_rows: int,
    mesh=None,
    metric: str = "euclidean",
    init_sample_rows: int = 1 << 18,
    float32: bool = True,
    chain_ops=None,
):
    """Out-of-core EXACT Lloyd: each iteration streams every batch through the device
    against fixed centers and accumulates (Σ one-hotᵀWX, counts, inertia); centers
    update once per full pass, so iterates match in-core Lloyd on the same init
    (not a minibatch approximation). Device residency is one batch + (k, d) stats
    plus whatever the HBM batch cache retains: ONE cache (ops/device_cache.py)
    spans every Lloyd iteration, so iteration 1 uploads and iterations 2..N
    replay from HBM (prefix-cached when the dataset exceeds the budget) — the
    KMeans analog of the reference's UVM/SAM large-dataset path
    (reference utils.py:184-241). Initialization runs in-core k-means|| on a row
    subsample bounded by `init_sample_rows`."""
    from .device_cache import batch_cache

    with batch_cache() as cache:
        return _streaming_kmeans_fit(
            X, w, k, max_iter, tol, seed, batch_rows, mesh, metric,
            init_sample_rows, float32, cache, chain_ops,
        )


def _streaming_kmeans_fit(
    X, w, k, max_iter, tol, seed, batch_rows, mesh, metric, init_sample_rows,
    float32, cache, chain_ops=None,
):
    from .kmeans import _normalize_rows, kmeans_init

    dt = np.float32 if float32 else np.float64
    n, d = X.shape
    cosine = metric == "cosine"
    if cosine and chain_ops:
        raise ValueError(
            "cosine KMeans is not fuse-eligible (host-side normalization); "
            "the pipeline fuser must leave it staged"
        )
    d = chain_out_dim(d, chain_ops)
    kinds, chain_arrays = _prep_chain(chain_ops, dt)
    # the cache key pins the RAW sources: a None weight materializes to the
    # same implicit all-ones below, so leaving it out of the key lets every
    # pass — and every candidate of a CV loop over the same X — replay the
    # same HBM-resident batches
    ckey = (
        cache.stream_key(
            tuple(a for a in (X, w) if a is not None), batch_rows, mesh
        )
        if cache is not None
        else None
    )
    if w is None:
        w = np.ones((n,), dt)

    # init on a subsample (rows are not assumed shuffled: use a strided sample)
    with obs_span("kmeans.init", {"sample_rows": min(n, init_sample_rows)}):
        step = max(1, n // min(n, init_sample_rows))
        # strided: never contiguous past step 1, and k-means|| owns the buffer
        Xs = np.ascontiguousarray(X[::step], dtype=dt)  # noqa: fence/host-staging-copy
        ws = np.ascontiguousarray(w[::step], dtype=dt)  # noqa: fence/host-staging-copy
        Xs_j = jnp.asarray(Xs if not cosine else np.asarray(
            Xs / np.maximum(np.linalg.norm(Xs, axis=1, keepdims=True), 1e-30)))
        if kinds:
            # same in-program expressions the per-batch accumulators run, so
            # the init sample sees bit-identical features to the staged path
            Xs_j = _apply_chain(Xs_j, dt, kinds, chain_arrays)
        centers = jnp.asarray(
            kmeans_init(Xs_j, jnp.asarray(ws), k, "k-means||", 2, seed)
        )
        if cosine:
            centers = _normalize_rows(centers)

    pool = StagingPool()

    def _slicer(s, e):
        if cosine:
            # normalization mutates: the block must own its buffer
            Xb = stage_block(X, s, e, dt, pool, slot="X", force_copy=True)
            norms = np.linalg.norm(Xb, axis=1, keepdims=True)
            if np.any(norms <= 0):
                raise ValueError(
                    "Cosine distance is not defined for zero-length vectors."
                )
            np.divide(Xb, norms, out=Xb)
            return Xb, stage_block(w, s, e, dt, pool, slot="w")
        return (
            stage_block(X, s, e, dt, pool, slot="X"),
            stage_block(w, s, e, dt, pool, slot="w"),
        )

    inertia = np.inf
    n_iter = 0
    for it in range(max_iter):
        carry = (
            jnp.zeros((k, d), dt),
            jnp.zeros((k,), dt),
            jnp.zeros((), dt),
        )
        # one Lloyd iteration == one full streamed pass: a `kmeans.step` span
        # per pass (pass 1 carries the jit compile of the batch accumulator),
        # with any `stream.ingest` uploads it triggered as child spans
        with obs_span("kmeans.step", {"pass": it + 1, "compile": it == 0}):
            carry = _accumulate_stream(
                carry,
                lambda c, batch, centers=centers: _accum_kmeans(
                    c, centers, batch[0], batch[1], chain_arrays, cosine, kinds
                ),
                n, batch_rows, mesh, _slicer, cache=cache, cache_key=ckey,
                progress_phase="kmeans.batches",
            )
        sums, counts, inertia_j = carry
        new_centers = jnp.where(
            counts[:, None] > 0,
            sums / jnp.maximum(counts, 1.0)[:, None],
            centers,
        )
        if cosine:
            new_centers = _normalize_rows(new_centers)
        shift2 = float(jnp.max(jnp.sum((new_centers - centers) ** 2, axis=1)))
        centers = new_centers
        inertia = float(inertia_j)
        n_iter = it + 1
        # live telemetry (§6g): pass-level progress gauge + per-iteration
        # convergence record, both visible mid-fit through /runs/<run_id>
        obs_progress("kmeans.passes", n_iter, max_iter, unit="passes")
        obs_convergence(
            "kmeans", n_iter, inertia=inertia,
            center_shift=float(np.sqrt(shift2)),
        )
        if shift2 <= tol * tol:
            break

    return {
        "cluster_centers": np.asarray(centers),
        "inertia": inertia,
        "n_iter": n_iter,
    }
