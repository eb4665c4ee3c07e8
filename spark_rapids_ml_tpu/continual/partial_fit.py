#
# Streamed `partial_fit`: persistent sufficient-statistics carries over the
# SAME accumulator kernels the out-of-core fits run (ops/streaming.py), so a
# model keeps learning from update batches after fit with no new math and —
# after warm-up — no new executables.
#
# The shape of every updater is the streaming-kmeans decomposition (arXiv
# 1505.06807): the model state is a small FUNCTIONAL carry of sufficient
# statistics; an update batch folds into it; a per-update `decay` in (0, 1]
# discounts history before each fold (decay = 0.5 ** (1 / half_life_updates);
# 1.0 = the paper's a=1 "infinite memory" setting). Because the carries are
# the checkpoint-resume carries, snapshot/restore reuses
# reliability/checkpoint.py::copy_carry verbatim and every update pass is
# fault-resumable (site "continual") with bit-identical results.
#
# Zero-compile contract (the §7b/§7d extension from index maintenance to
# learning): every update batch is re-blocked to ONE fixed geometry —
# `continual.update_batch_rows` rows, the ragged tail zero-weight padded to a
# full block — so a steady stream of arbitrarily-sized update batches re-enters
# one compiled executable per accumulator kernel. Zero-weight rows are exact
# no-ops in every accumulator (each statistic is a w-weighted sum), so the
# padding changes no bits. Warm-up (the first update + first candidate/score)
# compiles each kernel once; after that, `device.compile` stays flat.
#

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .. import config as _config
from ..observability import counter_inc, convergence as obs_convergence, span as obs_span
from ..observability.device import compiled_kernel
from ..ops._precision import pdot
from ..ops.ingest import StagingPool, stage_block
from ..ops.streaming import (
    _accum_cov,
    _accum_kmeans,
    _accum_linreg,
    _accumulate_stream,
    _finish_logreg,
    _logreg_accum_value_grad,
)
from ..reliability.checkpoint import copy_carry

# floor under a weight sum, so an empty cluster or batch divides by no zero
_EPS_COUNT = 1e-12


# ------------------------------------------------------------ knob resolution


def resolve_decay() -> float:
    """`continual.decay` resolution: a non-auto config pin wins, then the
    tuning table, then the defaults-module constant (1.0 — forgetting is
    opt-in)."""
    from .. import autotune as _autotune
    from ..autotune.defaults import CONTINUAL_DECAY

    pinned = float(_config.get("continual.decay") or 0.0)
    if pinned > 0.0:
        return pinned
    tuned = _autotune.lookup("continual.decay")
    if tuned:
        return float(tuned)
    return float(CONTINUAL_DECAY)


def resolve_update_batch_rows(n: int, d: int) -> int:
    """`continual.update_batch_rows` resolution: config pin, then tuning table
    per (n, d) bucket, then the defaults-module fixed block geometry."""
    from .. import autotune as _autotune
    from ..autotune.defaults import CONTINUAL_UPDATE_BATCH_ROWS

    pinned = int(_config.get("continual.update_batch_rows") or 0)
    if pinned > 0:
        return pinned
    tuned = _autotune.lookup("continual.update_batch_rows", n=n, d=d)
    if tuned:
        return int(tuned)
    return int(CONTINUAL_UPDATE_BATCH_ROWS)


# ------------------------------------------------------------ residual kernels
#
# Small drift/validation statistics the fit-time kernels don't already
# produce: weighted squared residuals against a FIXED model. Each compiles
# once at warm-up (fixed block geometry) and is shared by the per-update drift
# signal and the holdout validation score.


@compiled_kernel("continual.resid_linear", donate_argnums=(0,))
def _accum_resid_linear(carry, X, y, w, coef, intercept):
    ssr, sw = carry
    dt = ssr.dtype
    X = X.astype(dt)
    y = y.astype(dt)
    w = w.astype(dt)
    r = y - (pdot(X, coef) + intercept)
    return ssr + jnp.sum(w * r * r), sw + jnp.sum(w)


@compiled_kernel("continual.resid_pca", donate_argnums=(0,))
def _accum_resid_pca(carry, X, w, components, mean):
    ssr, sw = carry
    dt = ssr.dtype
    X = X.astype(dt)
    w = w.astype(dt)
    Xc = X - mean
    proj = pdot(Xc, components.T)
    r2 = jnp.sum(Xc * Xc, axis=1) - jnp.sum(proj * proj, axis=1)
    return ssr + jnp.sum(w * jnp.maximum(r2, 0.0)), sw + jnp.sum(w)


# ----------------------------------------------------- fixed-geometry ingest


def _fixed_block_slicer(X, y, w, block_rows: int, dt, pool: StagingPool):
    """Slicer over the PADDED row range [0, ceil(n/block)·block): full natural
    blocks take the zero-copy `stage_block` fast path; the (at most one) tail
    block is staged through a pooled buffer, zero-filled past the valid rows
    with weight 0 — an exact no-op in every w-weighted accumulator, so the
    fixed geometry costs no bits and buys one executable per kernel."""
    n, d = X.shape

    def slicer(s, e):
        valid = min(e, n) - s
        if valid == e - s:
            out = [stage_block(X, s, e, dt, pool, slot="X")]
            if y is not None:
                out.append(stage_block(y, s, e, dt, pool, slot="y"))
            if w is None:
                wb = pool.buffer((e - s,), dt, slot="w1")
                wb[:] = 1.0
            else:
                wb = stage_block(w, s, e, dt, pool, slot="w")
            out.append(wb)
            return tuple(out)
        Xb = pool.buffer((e - s, d), dt, slot="Xpad")
        Xb[valid:] = 0.0
        Xb[:valid] = X[s:s + valid]
        out = [Xb]
        if y is not None:
            yb = pool.buffer((e - s,), dt, slot="ypad")
            yb[valid:] = 0.0
            yb[:valid] = y[s:s + valid]
            out.append(yb)
        wb = pool.buffer((e - s,), dt, slot="wpad")
        wb[valid:] = 0.0
        wb[:valid] = 1.0 if w is None else w[s:s + valid]
        out.append(wb)
        return tuple(out)

    return slicer


def _wsum(X, w) -> float:
    return float(np.sum(w)) if w is not None else float(X.shape[0])


# ------------------------------------------------------------------- updaters


class PartialFitUpdater:
    """Base streamed partial_fit: a persistent carry + the carry lifecycle.

    State machine (docs/design.md §7d): ANCHORED -(update*)-> PENDING
    -(candidate+validate)-> either PROMOTED (rebase: the candidate attrs
    become the new anchor) or REJECTED (carry keeps accumulating toward the
    next attempt). `snapshot()`/`restore()` bound any excursion; both reuse
    the checkpoint layer's donation-safe carry copy."""

    algo = ""
    signal = ""

    def __init__(self, model, name=None, decay=None, update_batch_rows=None,
                 mesh=None):
        self._model = model
        self.name = name or type(model).__name__
        self.decay = resolve_decay() if decay is None else float(decay)
        if not (0.0 < self.decay <= 1.0):
            raise ValueError(
                f"continual.decay must be in (0, 1], got {self.decay}"
            )
        self._ubr = update_batch_rows
        self._mesh = mesh
        self._pool = StagingPool()
        self._dt = np.float32
        self.updates = 0
        self.rows = 0
        self._carry = None
        self._anchor_attrs = None
        self.rebase(dict(model._model_attributes))

    # -- subclass surface -------------------------------------------------
    def _rebase_carry(self, attrs):
        raise NotImplementedError

    def _accum(self, carry, batch):
        raise NotImplementedError

    def _signal_total(self):
        """Host float of the carry's cumulative signal statistic."""
        raise NotImplementedError

    def candidate(self):
        """Model-attrs dict the current carry implies (what a promotion would
        install)."""
        raise NotImplementedError

    def score(self, attrs, X, y=None, w=None):
        """Holdout validation score for an attrs dict — lower is better."""
        raise NotImplementedError

    # -- carry lifecycle --------------------------------------------------
    def rebase(self, attrs) -> None:
        """Re-anchor on an attrs dict (at construction, and after every
        promotion): drift/residual statistics are measured against the
        anchor, so the anchor is always the last weights serving traffic."""
        self._anchor_attrs = dict(attrs)
        self._rebase_carry(self._anchor_attrs)

    def anchor_attrs(self):
        return dict(self._anchor_attrs)

    def snapshot(self):
        return {
            "carry": copy_carry(self._carry),
            "anchor": dict(self._anchor_attrs),
            "updates": self.updates,
            "rows": self.rows,
        }

    def restore(self, snap) -> None:
        self._carry = copy_carry(snap["carry"])
        self._anchor_attrs = dict(snap["anchor"])
        self.updates = int(snap["updates"])
        self.rows = int(snap["rows"])

    # -- the update fold --------------------------------------------------
    def update_batch_rows(self, n: int, d: int) -> int:
        if self._ubr is None:
            self._ubr = resolve_update_batch_rows(n, d)
        return self._ubr

    def _fold(self, carry, accum, X, y, w, block_rows):
        n = X.shape[0]
        padded = -(-n // block_rows) * block_rows
        slicer = _fixed_block_slicer(X, y, w, block_rows, self._dt, self._pool)
        return _accumulate_stream(
            carry, accum, padded, block_rows, self._mesh, slicer,
            site="continual", progress_phase="continual.batches",
        )

    def update(self, X, y=None, w=None):
        """Fold one update batch into the carry: decay history, stream the
        batch through the fixed-geometry blocks, and return the per-row
        signal (the drift detector's observation)."""
        X = np.asarray(X)
        n = int(X.shape[0])
        block_rows = self.update_batch_rows(n, X.shape[1])
        with obs_span("continual.update",
                      {"model": self.name, "rows": n}):
            if self.decay != 1.0:
                self._carry = jax.tree_util.tree_map(
                    lambda a: a * self.decay, self._carry
                )
            before = self._signal_total()
            self._carry = self._fold(self._carry, self._accum, X, y, w,
                                     block_rows)
            bw = _wsum(X, w)
            value = (self._signal_total() - before) / max(bw, _EPS_COUNT)
        self.updates += 1
        self.rows += n
        counter_inc("continual.updates", 1, model=self.name)
        counter_inc("continual.update_rows", n, model=self.name)
        # same convergence axis as the fit (satellite: records carry a
        # process-monotonic `seq` + run-relative `rel_s`), marked as the
        # partial_fit phase so trend windows can split fit vs update
        obs_convergence(self.algo, self.updates,
                        **{self.signal: value},
                        update_rows=n, phase="partial_fit")
        return {"rows": n, "updates": self.updates,
                "signal": self.signal, "value": float(value)}

    def apply_to(self, model=None, attrs=None) -> dict:
        """Install candidate attrs on a model object (the offline, unserved
        path; served models promote through serving.mutate_model)."""
        attrs = attrs if attrs is not None else self.candidate()
        (model or self._model)._model_attributes.update(attrs)
        return attrs


class KMeansUpdater(PartialFitUpdater):
    """Mini-batch KMeans with discounted center updates (arXiv 1505.06807):
    the carry is (Σ w·x per cluster, Σ w per cluster, Σ w·min-d²) against the
    ANCHOR centers, seeded with the anchor's mass (cluster_sizes) so candidate
    centers are the paper's discounted blend of history and fresh data."""

    algo = "kmeans"
    signal = "inertia"

    def _rebase_carry(self, attrs):
        dt = self._dt
        centers = np.asarray(attrs["cluster_centers"], dt)
        k = centers.shape[0]
        sizes = attrs.get("cluster_sizes")
        counts = (np.asarray(sizes, dt) if sizes is not None
                  else np.zeros((k,), dt))
        self._centers = jnp.asarray(centers)
        self._carry = (
            jnp.asarray(centers * counts[:, None]),
            jnp.asarray(counts),
            jnp.zeros((), dt),
        )

    def _accum(self, carry, batch):
        Xb, wb = batch
        return _accum_kmeans(carry, self._centers, Xb, wb)

    def _signal_total(self):
        return float(self._carry[2])

    def candidate(self):
        sums, counts, inertia = self._carry
        sums_h = np.asarray(sums)
        counts_h = np.asarray(counts)
        anchor = np.asarray(self._anchor_attrs["cluster_centers"], self._dt)
        centers = np.where(
            counts_h[:, None] > 0,
            sums_h / np.maximum(counts_h, _EPS_COUNT)[:, None],
            anchor,
        ).astype(self._dt)
        return {
            "cluster_centers": centers,
            "inertia": float(inertia),
            "n_iter": int(self.updates),
            "cluster_sizes": counts_h,
        }

    def score(self, attrs, X, y=None, w=None):
        dt = self._dt
        centers = jnp.asarray(np.asarray(attrs["cluster_centers"], dt))
        k, d = centers.shape
        carry = (jnp.zeros((k, d), dt), jnp.zeros((k,), dt),
                 jnp.zeros((), dt))
        carry = self._fold(
            carry,
            lambda c, b: _accum_kmeans(c, centers, b[0], b[1]),
            np.asarray(X), None, w, self.update_batch_rows(X.shape[0], d),
        )
        return float(carry[2]) / max(_wsum(X, w), _EPS_COUNT)


class LinearRegressionUpdater(PartialFitUpdater):
    """Exact-stats linear regression: the carry is the streamed normal-
    equation statistics (XᵀWX, XᵀWy, Σwx, Σwy, Σw); a candidate is an EXACT
    re-solve (ops/linear.solve_from_stats) from the decayed statistics — no
    SGD approximation needed when the sufficient statistics are this small.
    The served coefficients anchor the drift residual."""

    algo = "linreg"
    signal = "residual"

    def __init__(self, model, reg=None, l1_ratio=None, fit_intercept=None,
                 standardize=None, max_iter=100, tol=1e-6, **kw):
        self._reg = _param(model, "regParam", 0.0) if reg is None else reg
        self._l1r = (_param(model, "elasticNetParam", 0.0)
                     if l1_ratio is None else l1_ratio)
        self._fi = (_param(model, "fitIntercept", True)
                    if fit_intercept is None else fit_intercept)
        self._std = (_param(model, "standardization", True)
                     if standardize is None else standardize)
        self._max_iter = int(max_iter)
        self._tol = float(tol)
        super().__init__(model, **kw)

    def _rebase_carry(self, attrs):
        dt = self._dt
        d = int(np.asarray(attrs["coefficients"]).shape[0])
        self._coef = jnp.asarray(np.asarray(attrs["coefficients"], dt))
        self._intercept = jnp.asarray(np.asarray(attrs["intercept"], dt))
        # stats carry starts empty at construction only: across promotions the
        # exact statistics persist (decay is the only forgetting mechanism)
        if self._carry is None:
            self._carry = (
                (jnp.zeros((d, d), dt), jnp.zeros((d,), dt),
                 jnp.zeros((d,), dt), jnp.zeros((), dt), jnp.zeros((), dt)),
                (jnp.zeros((), dt), jnp.zeros((), dt)),
            )
        else:
            stats, _ = self._carry
            self._carry = (stats, (jnp.zeros((), dt), jnp.zeros((), dt)))

    def _accum(self, carry, batch):
        Xb, yb, wb = batch
        return (
            _accum_linreg(carry[0], Xb, yb, wb),
            _accum_resid_linear(carry[1], Xb, yb, wb, self._coef,
                                self._intercept),
        )

    def _signal_total(self):
        return float(self._carry[1][0])

    def candidate(self):
        from ..ops.linear import solve_from_stats

        (A, b, sx, sy, sw), _ = self._carry
        swf = float(sw)
        if swf <= 0.0:
            raise RuntimeError("partial_fit carry is empty: no update rows")
        res = solve_from_stats(
            A, b, sx / sw, sy / sw, sw,
            reg=float(self._reg), l1_ratio=float(self._l1r),
            fit_intercept=bool(self._fi), standardize=bool(self._std),
            max_iter=self._max_iter, tol=self._tol,
        )[0]
        return {
            "coefficients": np.asarray(res["coefficients"]),
            "intercept": float(res["intercept"]),
            "n_iter": int(res["n_iter"]),
        }

    def score(self, attrs, X, y=None, w=None):
        dt = self._dt
        coef = jnp.asarray(np.asarray(attrs["coefficients"], dt))
        intercept = jnp.asarray(np.asarray(attrs["intercept"], dt))
        carry = (jnp.zeros((), dt), jnp.zeros((), dt))
        carry = self._fold(
            carry,
            lambda c, b: _accum_resid_linear(c, b[0], b[1], b[2], coef,
                                             intercept),
            np.asarray(X), np.asarray(y), w,
            self.update_batch_rows(X.shape[0], X.shape[1]),
        )
        return float(carry[0]) / max(_wsum(X, w), _EPS_COUNT)


class LogisticRegressionUpdater(PartialFitUpdater):
    """Streamed proximal-gradient (FISTA-style single step) logistic
    regression warm-started from the served coefficients: each update folds
    the Kahan-compensated value+grad AT THE ANCHOR plus a Gram pass (the
    Lipschitz source, parameter-independent so it survives promotions); a
    candidate takes one prox step of the accumulated discounted full gradient
    from the anchor — streamed SGD whose minibatch is the whole
    inter-promotion window. The value/grad carry resets on rebase (a gradient
    at the OLD anchor is stale once the anchor moves); the Gram carry and its
    discounted mass persist."""

    algo = "logreg"
    signal = "loss"

    def __init__(self, model, reg=None, l1_ratio=None, fit_intercept=None,
                 **kw):
        self._reg = _param(model, "regParam", 0.0) if reg is None else reg
        self._l1r = (_param(model, "elasticNetParam", 0.0)
                     if l1_ratio is None else l1_ratio)
        self._fi = (_param(model, "fitIntercept", True)
                    if fit_intercept is None else fit_intercept)
        attrs = model._model_attributes
        self._num_classes = int(attrs["num_classes"])
        self._multinomial = np.asarray(attrs["coefficients"]).shape[0] > 1
        super().__init__(model, **kw)

    def _params_from_attrs(self, attrs):
        dt = self._dt
        coef = np.asarray(attrs["coefficients"], np.float64)
        inter = np.asarray(attrs["intercepts"], np.float64)
        if self._multinomial:
            p = np.concatenate([coef, inter[:, None]], axis=1)
        else:
            p = np.concatenate([coef[0], inter])
        return p.astype(dt)

    def _rebase_carry(self, attrs):
        dt = self._dt
        params_h = self._params_from_attrs(attrs)
        d = params_h.shape[-1] - 1
        self._shape = params_h.shape
        self._params_h = params_h.astype(np.float64)
        self._params = jnp.asarray(params_h)
        self._scale = jnp.ones((d,), dt)
        vg = (jnp.zeros((), dt), jnp.zeros((), dt),
              jnp.zeros(self._shape, dt), jnp.zeros(self._shape, dt))
        if self._carry is None:
            gram = (jnp.zeros((d, d), dt), jnp.zeros((d,), dt),
                    jnp.zeros((), dt))
        else:
            _, gram = self._carry
        self._carry = (vg, gram)

    def _accum(self, carry, batch):
        Xb, yb, wb = batch
        if self._multinomial:
            y_enc = (
                jax.nn.one_hot(yb.astype(jnp.int32), self._num_classes,
                               dtype=Xb.dtype)
                * (wb > 0)[:, None]
            )
        else:
            y_enc = yb
        vg = _logreg_accum_value_grad(
            *carry[0], self._params, Xb, y_enc, wb, self._scale, (),
            bool(self._fi), bool(self._multinomial), (),
        )
        return (vg, _accum_cov(carry[1], Xb, wb))

    def _signal_total(self):
        return float(self._carry[0][0])

    def candidate(self):
        from ..ops.linalg import power_iteration_lmax

        (acc_v, _, acc_g, _), (S2, _, sw) = self._carry
        swf = float(sw)
        if swf <= 0.0:
            raise RuntimeError("partial_fit carry is empty: no update rows")
        reg_l1 = float(self._reg) * float(self._l1r)
        reg_l2 = float(self._reg) * (1.0 - float(self._l1r))
        g = np.asarray(acc_g, np.float64) / swf
        coef_s = self._params_h[..., :-1]
        g[..., :-1] += reg_l2 * coef_s
        lmax = float(power_iteration_lmax(S2 / sw))
        lipschitz = (0.5 if self._multinomial else 0.25) * lmax \
            + reg_l2 + 1e-12
        step = 1.0 / lipschitz
        p = self._params_h - step * g
        if reg_l1 > 0.0:
            coef = p[..., :-1]
            p[..., :-1] = np.sign(coef) * np.maximum(
                np.abs(coef) - step * reg_l1, 0.0
            )
        new_coef = p[..., :-1]
        fx = float(acc_v) / swf \
            + 0.5 * reg_l2 * float(np.sum(coef_s * coef_s)) \
            + reg_l1 * float(np.sum(np.abs(new_coef)))
        attrs = _finish_logreg(
            p.reshape(-1), self._shape,
            np.ones((self._shape[-1] - 1,), np.float64),
            bool(self._fi), bool(self._multinomial), self.updates, fx,
        )
        attrs["num_classes"] = self._num_classes
        return attrs

    def score(self, attrs, X, y=None, w=None):
        dt = self._dt
        params = jnp.asarray(self._params_from_attrs(attrs))
        carry = (jnp.zeros((), dt), jnp.zeros((), dt),
                 jnp.zeros(self._shape, dt), jnp.zeros(self._shape, dt))

        def accum(c, b):
            Xb, yb, wb = b
            if self._multinomial:
                y_enc = (
                    jax.nn.one_hot(yb.astype(jnp.int32), self._num_classes,
                                   dtype=Xb.dtype)
                    * (wb > 0)[:, None]
                )
            else:
                y_enc = yb
            return _logreg_accum_value_grad(
                *c, params, Xb, y_enc, wb, self._scale, (),
                bool(self._fi), bool(self._multinomial), (),
            )

        carry = self._fold(
            carry, accum, np.asarray(X), np.asarray(y), w,
            self.update_batch_rows(X.shape[0], X.shape[1]),
        )
        reg_l1 = float(self._reg) * float(self._l1r)
        reg_l2 = float(self._reg) * (1.0 - float(self._l1r))
        coef = np.asarray(attrs["coefficients"], np.float64)
        return float(carry[0]) / max(_wsum(X, w), _EPS_COUNT) \
            + 0.5 * reg_l2 * float(np.sum(coef * coef)) \
            + reg_l1 * float(np.sum(np.abs(coef)))


class PCAUpdater(PartialFitUpdater):
    """Incremental PCA via the streamed covariance accumulator: the carry is
    (Σ wxxᵀ, Σ wx, Σ w) over the update stream (a rank-k model cannot seed the
    full covariance, so the carry is exact statistics of the updates; decay is
    the forgetting mechanism). Drift is the off-subspace residual against the
    served components."""

    algo = "pca"
    signal = "residual"

    def _rebase_carry(self, attrs):
        dt = self._dt
        comps = np.asarray(attrs["components"], dt)
        self._k, d = comps.shape
        self._components = jnp.asarray(comps)
        self._mean = jnp.asarray(np.asarray(attrs["mean"], dt))
        if self._carry is None:
            cov = (jnp.zeros((d, d), dt), jnp.zeros((d,), dt),
                   jnp.zeros((), dt))
        else:
            cov, _ = self._carry
        self._carry = (cov, (jnp.zeros((), dt), jnp.zeros((), dt)))

    def _accum(self, carry, batch):
        Xb, wb = batch
        return (
            _accum_cov(carry[0], Xb, wb),
            _accum_resid_pca(carry[1], Xb, wb, self._components, self._mean),
        )

    def _signal_total(self):
        return float(self._carry[1][0])

    def candidate(self):
        from ..ops.pca import pca_attrs_from_cov

        (S2, sx, sw), _ = self._carry
        swf = float(sw)
        if swf <= 1.0:
            raise RuntimeError(
                "partial_fit carry needs weight > 1 for a covariance"
            )
        mean = sx / sw
        cov = (S2 - sw * jnp.outer(mean, mean)) / (sw - 1.0)
        return pca_attrs_from_cov(cov, mean, sw, self._k)

    def score(self, attrs, X, y=None, w=None):
        dt = self._dt
        comps = jnp.asarray(np.asarray(attrs["components"], dt))
        mean = jnp.asarray(np.asarray(attrs["mean"], dt))
        carry = (jnp.zeros((), dt), jnp.zeros((), dt))
        carry = self._fold(
            carry,
            lambda c, b: _accum_resid_pca(c, b[0], b[1], comps, mean),
            np.asarray(X), None, w,
            self.update_batch_rows(X.shape[0], X.shape[1]),
        )
        return float(carry[0]) / max(_wsum(X, w), _EPS_COUNT)


# ------------------------------------------------------------------- factory


def _param(model, name, default):
    try:
        return model.getOrDefault(name)
    except Exception:
        return default


def partial_fit_updater(model, **kwargs) -> PartialFitUpdater:
    """Dispatch a model object to its updater class by model attributes (the
    models' own `partial_fit_updater()` convenience methods land here)."""
    attrs = getattr(model, "_model_attributes", {})
    if "cluster_centers" in attrs:
        return KMeansUpdater(model, **kwargs)
    if "components" in attrs:
        return PCAUpdater(model, **kwargs)
    if "intercepts" in attrs:
        return LogisticRegressionUpdater(model, **kwargs)
    if "coefficients" in attrs:
        return LinearRegressionUpdater(model, **kwargs)
    raise TypeError(
        f"no partial_fit updater for {type(model).__name__}: expected a "
        "KMeans / PCA / LogisticRegression / LinearRegression model"
    )


__all__ = [
    "KMeansUpdater",
    "LinearRegressionUpdater",
    "LogisticRegressionUpdater",
    "PCAUpdater",
    "PartialFitUpdater",
    "partial_fit_updater",
    "resolve_decay",
    "resolve_update_batch_rows",
]
