#
# Linear regression fit kernels (OLS / Ridge / ElasticNet) — the TPU-native replacement
# for cuml.linear_model.{linear_regression_mg, ridge_mg} and cuml.solvers.cd_mg
# (reference regression.py:528-606 dispatches among the three by regularization; the
# gradient/Gram allreduce happens inside cuML over NCCL).
#
# TPU formulation: ONE sharded data pass builds the normal-equation sufficient
# statistics (XᵀWX, XᵀWy) — the contraction over the sharded row axis is where XLA
# inserts the psum (the cuML NCCL allreduce's place). Everything after is d×d and
# replicated:
#   * no L1  -> direct solve of (XᵀWX/n + λI) w = XᵀWy/n   (OLS: λ=0; Ridge)
#   * L1 > 0 -> FISTA proximal gradient on the Gram form — all matrix-vector work,
#     MXU/VPU-friendly with a statically-bounded lax.while_loop, where the reference
#     uses cuML's sequential coordinate descent (CD's per-coordinate data dependence is
#     hostile to wide-vector hardware; FISTA optimizes the same objective).
#
# Objective (Spark parity): 1/(2n)·Σ wᵢ(yᵢ - xᵢ·β - b)² + λ(α‖β‖₁ + (1-α)/2·‖β‖²),
# with `standardization=True` applying the penalty to σ-scaled coefficients
# (implemented by solving in X/σ space and unscaling, the reference's approach at
# regression.py:534-544,634-648).
#

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.device import compiled_kernel
from ._precision import pdot
from .linalg import power_iteration_lmax


@compiled_kernel("linear.sufficient_stats")
def linreg_sufficient_stats(
    X: jax.Array, y: jax.Array, w: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One sharded pass: (XᵀWX, XᵀWy, x̄, ȳ, Σw). The only distributed step."""
    wsum = jnp.sum(w)
    xbar = pdot(w, X) / wsum
    ybar = jnp.sum(w * y) / wsum
    Xw = X * w[:, None]
    A = pdot(Xw.T, X)
    b = pdot(Xw.T, y)
    return A, b, xbar, ybar, wsum


def _center_stats(A, b, xbar, ybar, n, fit_intercept):
    """Convert raw moments to centered (about the weighted mean) moments."""
    if fit_intercept:
        A = A - n * jnp.outer(xbar, xbar)
        b = b - n * xbar * ybar
    return A, b


@compiled_kernel("linear.solve_l2", static_argnames=("fit_intercept",))
def solve_l2(
    A: jax.Array,
    b: jax.Array,
    xbar: jax.Array,
    ybar: jax.Array,
    n: jax.Array,
    scale: jax.Array,
    reg: float,
    fit_intercept: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Closed-form OLS/Ridge in (optionally σ-scaled) space; returns (coef, intercept)
    in the ORIGINAL feature space."""
    Ac, bc = _center_stats(A, b, xbar, ybar, n, fit_intercept)
    # scale to standardized space: As = D⁻¹ Ac D⁻¹, bs = D⁻¹ bc, D = diag(scale)
    As = Ac / jnp.outer(scale, scale)
    bs = bc / scale
    d = As.shape[0]
    lhs = As / n + reg * jnp.eye(d, dtype=As.dtype)
    coef_s = jnp.linalg.solve(lhs, bs / n)
    coef = coef_s / scale
    intercept = jnp.where(fit_intercept, ybar - jnp.dot(xbar, coef), 0.0)
    return coef, intercept


@compiled_kernel("linear.solve_elastic_net",
                 static_argnames=("fit_intercept", "max_iter"))
def solve_elastic_net(
    A: jax.Array,
    b: jax.Array,
    xbar: jax.Array,
    ybar: jax.Array,
    n: jax.Array,
    scale: jax.Array,
    reg: float,
    l1_ratio: float,
    fit_intercept: bool,
    max_iter: int,
    tol: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """FISTA on  f(β) = 1/(2n)·βᵀAβ - bᵀβ/n (+ L2)  with prox for λ·α‖β‖₁.

    Returns (coef, intercept, n_iter) in the original feature space."""
    Ac, bc = _center_stats(A, b, xbar, ybar, n, fit_intercept)
    As = (Ac / jnp.outer(scale, scale)) / n
    bs = (bc / scale) / n
    l1 = reg * l1_ratio
    l2 = reg * (1.0 - l1_ratio)

    # Lipschitz constant of ∇f: λ_max(As) + l2, bounded via a few power iterations
    L = power_iteration_lmax(As) + l2 + 1e-12
    step = 1.0 / L

    def soft(x, t):
        return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)

    def cond(state):
        _, _, _, it, delta = state
        return jnp.logical_and(it < max_iter, delta > tol)

    def body(state):
        wk, zk, tk, it, _ = state
        grad = pdot(As, zk) - bs + l2 * zk
        w_next = soft(zk - step * grad, step * l1)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        z_next = w_next + ((tk - 1.0) / t_next) * (w_next - wk)
        delta = jnp.max(jnp.abs(w_next - wk)) / (jnp.max(jnp.abs(w_next)) + 1e-12)
        return w_next, z_next, t_next, it + 1, delta

    w0 = jnp.zeros((As.shape[0],), As.dtype)
    state = (w0, w0, jnp.array(1.0, As.dtype), 0, jnp.array(jnp.inf, As.dtype))
    coef_s, _, _, n_iter, _ = jax.lax.while_loop(cond, body, state)
    coef = coef_s / scale
    intercept = jnp.where(fit_intercept, ybar - jnp.dot(xbar, coef), 0.0)
    return coef, intercept, n_iter


def linreg_fit(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    reg: float,
    l1_ratio: float,
    fit_intercept: bool,
    standardize: bool,
    max_iter: int,
    tol: float,
    extra_param_sets: Optional[List[Dict[str, Any]]] = None,
    mesh=None,
    unit_weight: bool = False,
) -> List[Dict[str, Any]]:
    """Full fit: one distributed stats pass, then per-param-map host-replicated solves.

    `extra_param_sets` reuses the SAME sufficient statistics for every param map — the
    single-pass fitMultiple the reference implements by looping cuML fits over the
    concatenated data (regression.py:657-674); here the data pass itself is shared.
    Returns one attribute dict per model.

    Unit-weight fits on TPU take the fused one-X-read pallas stats pass
    (ops/pallas_xtwx.py::normal_eq_prefix_mask — halves the HBM traffic of the
    XLA two-read Gram); the same `use_fused_gram` gate as the PCA covariance."""
    from .pca import use_fused_gram

    if use_fused_gram(X.shape[1], unit_weight, dtype=X.dtype):
        from ._precision import parity_precision
        from .pallas_xtwx import normal_eq_prefix_mask

        interpret = jax.devices()[0].platform != "tpu"
        A, b, xbar, ybar, n, _yty = normal_eq_prefix_mask(
            X, y, w, mesh=mesh, precision=parity_precision(), interpret=interpret
        )
    else:
        A, b, xbar, ybar, n = linreg_sufficient_stats(X, y, w)
    return solve_from_stats(
        A, b, xbar, ybar, n,
        reg=reg, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, max_iter=max_iter, tol=tol,
        extra_param_sets=extra_param_sets,
    )


def solve_from_stats(
    A: jax.Array,
    b: jax.Array,
    xbar: jax.Array,
    ybar: jax.Array,
    n: jax.Array,
    reg: float,
    l1_ratio: float,
    fit_intercept: bool,
    standardize: bool,
    max_iter: int,
    tol: float,
    extra_param_sets: Optional[List[Dict[str, Any]]] = None,
) -> List[Dict[str, Any]]:
    """Solve per param map from sufficient statistics (shared by the in-core and
    streaming out-of-core paths; ops/streaming.py accumulates the same stats). The
    column std for standardization comes from diag(A): var = (ΣwX² - n·x̄²)/(n-1)."""
    if standardize:
        # unbiased column std, Spark's Summarizer convention (reference utils.py:876-982)
        var = (jnp.diagonal(A) - n * xbar * xbar) / jnp.maximum(n - 1.0, 1.0)
        scale = jnp.sqrt(jnp.maximum(var, 0.0))
        scale = jnp.where(scale <= 0.0, 1.0, scale)
    else:
        scale = jnp.ones_like(xbar)

    param_sets = extra_param_sets if extra_param_sets is not None else [
        {"alpha": reg, "l1_ratio": l1_ratio, "fit_intercept": fit_intercept,
         "max_iter": max_iter, "tol": tol}
    ]
    results = []
    for p in param_sets:
        p_reg = float(p.get("alpha", reg))
        p_l1r = float(p.get("l1_ratio", l1_ratio))
        p_fi = bool(p.get("fit_intercept", fit_intercept))
        p_mi = int(p.get("max_iter", max_iter))
        p_tol = float(p.get("tol", tol))
        if p_reg == 0.0 or p_l1r == 0.0:
            coef, intercept = solve_l2(A, b, xbar, ybar, n, scale, p_reg, p_fi)
            n_iter = 1
        else:
            coef, intercept, n_iter = solve_elastic_net(
                A, b, xbar, ybar, n, scale, p_reg, p_l1r, p_fi, p_mi, p_tol
            )
            n_iter = int(n_iter)
        results.append(
            {
                "coefficients": np.asarray(coef),
                "intercept": float(intercept),
                "n_iter": int(n_iter),
            }
        )
    return results


@compiled_kernel("linear.predict")
def linreg_predict(X: jax.Array, coef: jax.Array, intercept: jax.Array) -> jax.Array:
    return pdot(X, coef) + intercept


# ---------------------------------------------------------------------------
# Huber regression (robust loss) — NATIVE on the mesh.
#
# The reference has no device path at all for loss='huber' (cuML lacks it; the
# reference falls back to Spark, regression.py:183-215 maps loss to squared only).
# Here the jointly-convex concomitant-scale formulation (Huber 1981, the same
# objective sklearn's HuberRegressor and Spark's HuberAggregator optimize)
#     L(beta, b, sigma) = sum_i w_i [ sigma + H_eps((y_i - x_i.beta - b)/sigma) sigma ]
#                         + reg * ||beta_s||^2
# is minimized by the shared optax L-BFGS loop (ops/logistic._run_lbfgs): the
# residual matvec over the sharded row axis is where XLA inserts the psum.
# sigma is parameterized as exp(s) for positivity; `standardize` applies the
# penalty to sigma-scaled coefficients like the squared-loss path.
# ---------------------------------------------------------------------------


@compiled_kernel("linear.huber_qn",
                 static_argnames=("fit_intercept", "standardize", "max_iter"))
def _huber_qn(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    epsilon: jax.Array,
    reg: jax.Array,
    fit_intercept: bool,
    standardize: bool,
    max_iter: int,
    tol: jax.Array,
):
    from .linalg import weighted_moments
    from .logistic import _run_lbfgs

    d = X.shape[1]
    wsum = jnp.sum(w)
    if standardize:
        _, var, _ = weighted_moments(X, w)
        scale = jnp.sqrt(jnp.maximum(var, 0.0))
        # zero-variance columns pass through unscaled (solve_from_stats convention)
        scale = jnp.where(scale <= 0.0, 1.0, scale)
    else:
        scale = jnp.ones((d,), X.dtype)

    ybar = jnp.sum(w * y) / wsum
    b0 = jnp.where(fit_intercept, ybar, 0.0)
    resid0 = y - b0
    sigma0 = jnp.sqrt(jnp.sum(w * resid0 * resid0) / wsum) + 1e-6
    params0 = jnp.concatenate(
        [jnp.zeros((d,), X.dtype), jnp.array([b0, jnp.log(sigma0)], X.dtype)]
    )

    def loss(params):
        coef_s, b, s = params[:d], params[d], params[d + 1]
        sigma = jnp.exp(s)
        r = y - pdot(X, coef_s / scale) - jnp.where(fit_intercept, b, 0.0)
        z = r / sigma
        az = jnp.abs(z)
        Hz = jnp.where(az <= epsilon, z * z, 2.0 * epsilon * az - epsilon * epsilon)
        # Spark HuberCostFun convention: mean data term + (lambda/2)||beta_s||^2
        # (same regParam meaning as the squared-loss path's A/n + reg*I)
        return jnp.sum(w * (sigma + Hz * sigma)) / wsum + 0.5 * reg * jnp.sum(
            coef_s * coef_s
        )

    params, n_iter, _, _ = _run_lbfgs(loss, params0, max_iter, tol)
    coef = params[:d] / scale
    return coef, params[d], jnp.exp(params[d + 1]), n_iter


def huber_fit(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    epsilon: float,
    reg: float,
    fit_intercept: bool,
    standardize: bool,
    max_iter: int,
    tol: float,
    extra_param_sets: Optional[List[Dict[str, Any]]] = None,
) -> List[Dict[str, Any]]:
    """Native huber fits — one result per param set, the solve_from_stats
    convention (extra sets are full backend-param dicts; None => one base fit).
    No sufficient-statistics shortcut exists for the robust loss, but the jitted
    program is compiled once and reused across maps."""
    param_sets = (
        extra_param_sets if extra_param_sets is not None else [{}]
    )
    results = []
    for ps in param_sets:
        coef, b, sigma, n_iter = _huber_qn(
            X, y, w,
            jnp.asarray(float(ps.get("epsilon", epsilon)), X.dtype),
            jnp.asarray(float(ps.get("alpha", reg)), X.dtype),
            fit_intercept=bool(ps.get("fit_intercept", fit_intercept)),
            standardize=bool(ps.get("normalize", standardize)),
            max_iter=int(ps.get("max_iter", max_iter)),
            tol=jnp.asarray(float(ps.get("tol", tol)), X.dtype),
        )
        results.append(
            {
                "coefficients": np.asarray(coef, np.float32),
                "intercept": float(b),
                "n_iter": int(n_iter),
                "scale": float(sigma),
            }
        )
    return results
