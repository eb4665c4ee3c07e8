#
# Linear regression fit kernels (OLS / Ridge / ElasticNet) — the TPU-native replacement
# for cuml.linear_model.{linear_regression_mg, ridge_mg} and cuml.solvers.cd_mg
# (reference regression.py:528-606 dispatches among the three by regularization; the
# gradient/Gram allreduce happens inside cuML over NCCL).
#
# TPU formulation: ONE sharded data pass builds the normal-equation sufficient
# statistics, CENTRED about the weighted means: S = Σ w(x-x̄)(x-x̄)ᵀ and
# r = Σ w(x-x̄)(y-ȳ), beside x̄, ȳ and Σw — the contraction over the sharded row
# axis is where the psum goes (the cuML NCCL allreduce's place). That tuple is the
# one interface of the solve stage (`solve_from_stats`), whoever produced it: the
# in-core XLA pass (`normal_equations`), the fused Pallas pass at 512 columns or
# fewer and the streamed tier, whose raw moments are centred before they are
# handed over (`center_moments`). Everything after is d×d and replicated:
#   * no L1  -> direct solve of (S/n + λI) β = r/n   (OLS: λ=0; Ridge), b = ȳ - x̄·β
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

from ..observability import counter_inc, span
from ..observability.device import compiled_kernel
from ._precision import pdot
from .linalg import (
    centered_normal_equations,
    fold_finish,
    fold_part,
    gram_column_blocks,
    open_fold,
    power_iteration_lmax,
    recentre,
    rows_split,
)


@compiled_kernel("linear.normal_equations", static_argnames=("mesh",))
def normal_equations(
    X: jax.Array, y: jax.Array, w: jax.Array, mesh=None
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The centred statistics (S, r, x̄, ȳ, Σw) of the module header: the
    weighted means first, one matrix-vector pass, then S and r in one pass over
    the rows centred by them, in short parts under a compensated sum and, from
    `GRAM_TRIANGLE_MIN_COLS` columns on, over the upper column blocks of S
    alone (`linalg.centered_normal_equations`: PCA's Gram with r riding its
    loop). Centring first is what float32 needs: the raw XᵀWX of a table whose
    column means are not zero loses the centred matrix in its cancellation,
    and a whole-table contraction loses more in the matmul's accumulator
    (PERF.md §6). The same loop sums the centred rows, whose mean δ is
    what the first pass's float32 lost of x̄ (and of ȳ): the intercept
    ȳ - x̄·β is first order in it, the moments second order (S - Σw·δδᵀ). With
    a `mesh` that splits the rows alone each shard sums its own rows and one
    psum adds the shards."""
    wsum = jnp.sum(w)
    y = y.astype(X.dtype)
    xbar = pdot(w, X) / wsum
    ybar = jnp.sum(w * y) / wsum
    if rows_split(mesh):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS

        S, r, dx, dy = shard_map(
            lambda x, ys, ws, m, mb: centered_normal_equations(x, ys, ws, m, mb, axis=DATA_AXIS),
            mesh=mesh, in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
            out_specs=(P(), P(), P(), P()), check_vma=False,
        )(X, y, w, xbar, ybar)
    else:
        S, r, dx, dy = centered_normal_equations(X, y, w, xbar, ybar)
    return (*recentre(S, dx, wsum, xbar, r, dy, ybar), wsum)


@compiled_kernel("linear.normal_equations_part", donate_argnums=(0,))
def normal_equations_part(state, chunk: jax.Array, w: jax.Array, y: jax.Array):
    """The normal equations' fold step (`linalg.fold_part` with the label)
    under their program name: one chunk of the upload as it lands."""
    return fold_part(state, chunk, w, y)


@compiled_kernel("linear.normal_equations_finish")
def normal_equations_finish(state, w: jax.Array):
    """(S, r, x̄, ȳ, Σw) of the folded sums, as `normal_equations` returns them."""
    return fold_finish(state, w)


def normal_equations_fold(n_cols: int, unit_weight: bool, dtype, w: jax.Array, y: jax.Array):
    """The upload's consumer that sums the normal equations' statistics as
    the table's chunks land (`linalg.GramFold`), for a fit whose producer
    would be `normal_equations`; None where it is the fused Pallas pass."""
    from .pca import use_fused_gram

    return open_fold("normal_equations", use_fused_gram(n_cols, unit_weight, dtype=dtype),
                     normal_equations_part, normal_equations_finish, w, y)


@compiled_kernel("linear.center_moments")
def center_moments(A, b, xbar, ybar, n):
    """Raw moments (XᵀWX, XᵀWy, x̄, ȳ, Σw), as the fused Pallas pass and the
    streamed tier accumulate them, made the centred statistics the solve
    takes: S = XᵀWX - n·x̄x̄ᵀ, r = XᵀWy - n·x̄ȳ."""
    return A - n * jnp.outer(xbar, xbar), b - n * xbar * ybar, xbar, ybar, n


def _about_origin(S, r, xbar, ybar, n, fit_intercept):
    """The moments one parameter map solves with: the centred ones where it
    fits an intercept, the moments about the origin where it does not (a
    fitMultiple may ask for both from one pass)."""
    if fit_intercept:
        return S, r
    return S + n * jnp.outer(xbar, xbar), r + n * xbar * ybar


@compiled_kernel("linear.solve_l2", static_argnames=("fit_intercept",))
def solve_l2(
    S: jax.Array,
    r: jax.Array,
    xbar: jax.Array,
    ybar: jax.Array,
    n: jax.Array,
    scale: jax.Array,
    reg: float,
    fit_intercept: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Closed-form OLS/Ridge from the centred statistics in (optionally
    σ-scaled) space; returns (coef, intercept) in the ORIGINAL feature space.

    The left-hand side is symmetric positive (semi)definite, so it is solved
    by its Cholesky factors, whose blocked updates run at `highest`: on a v5e
    at 3000 columns 2.4 ms against LU's 7.2 ms, and a gradient 1.6e-6 of the
    one at zero off float64 against LU's 2.6e-6 (PERF.md §6)."""
    from jax.scipy.linalg import cho_factor, cho_solve

    A, b = _about_origin(S, r, xbar, ybar, n, fit_intercept)
    # scale to standardized space: As = D⁻¹ A D⁻¹, bs = D⁻¹ b, D = diag(scale)
    As = A / jnp.outer(scale, scale)
    bs = b / scale
    d = As.shape[0]
    lhs = As / n + reg * jnp.eye(d, dtype=As.dtype)
    coef_s = cho_solve(cho_factor(lhs), bs / n)
    coef = coef_s / scale
    intercept = jnp.where(fit_intercept, ybar - pdot(xbar, coef), 0.0)
    return coef, intercept


@compiled_kernel("linear.solve_elastic_net",
                 static_argnames=("fit_intercept", "max_iter"))
def solve_elastic_net(
    S: jax.Array,
    r: jax.Array,
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

    Takes the centred statistics; returns (coef, intercept, n_iter) in the
    original feature space."""
    A, b = _about_origin(S, r, xbar, ybar, n, fit_intercept)
    As = (A / jnp.outer(scale, scale)) / n
    bs = (b / scale) / n
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
    intercept = jnp.where(fit_intercept, ybar - pdot(xbar, coef), 0.0)
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
    folded=None,
) -> List[Dict[str, Any]]:
    """Full fit: one distributed stats pass, then per-param-map host-replicated solves.

    `extra_param_sets` reuses the SAME sufficient statistics for every param map — the
    single-pass fitMultiple the reference implements by looping cuML fits over the
    concatenated data (regression.py:657-674); here the data pass itself is shared.
    Returns one attribute dict per model.

    Unit-weight fits on TPU up to `MAX_FUSED_COLS` columns take the fused
    one-X-read pallas pass (ops/pallas_xtwx.py::normal_eq_prefix_mask), whose
    raw moments are centred before the solve; the same `use_fused_gram` gate as
    the PCA covariance. Every other fit takes `normal_equations`, unless the
    upload already summed its statistics chunk by chunk (`folded`:
    `normal_equations_fold`'s, the same step as the resident pass).
    `linear.stats_form{form=centred_triangle|centred_full|fused}` counts which
    producer ran, from the same shape tests, and the span `linear.stats` is
    the pass from its dispatch to its result on the device (for a folded fit
    what is left of it once the table has landed)."""
    from .pca import use_fused_gram

    fused = use_fused_gram(X.shape[1], unit_weight, dtype=X.dtype)
    if fused:
        form = "fused"
    elif len(gram_column_blocks(X.shape[1])) > 1:
        form = "centred_triangle"
    else:
        form = "centred_full"
    counter_inc("linear.stats_form", 1, form=form)
    with span("linear.stats", {"waits": "device"}):
        if fused:
            from ._precision import parity_precision
            from .pallas_xtwx import normal_eq_prefix_mask

            interpret = jax.devices()[0].platform != "tpu"
            raw = normal_eq_prefix_mask(
                X, y, w, mesh=mesh, precision=parity_precision(), interpret=interpret
            )
            stats = center_moments(*raw[:5])
        elif folded is not None:
            stats = folded
        else:
            stats = normal_equations(X, y, w, mesh=mesh)
        # waited for, so that the pass and the solve below are not one number
        stats = jax.block_until_ready(stats)
    return solve_from_stats(
        *stats,
        reg=reg, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, max_iter=max_iter, tol=tol,
        extra_param_sets=extra_param_sets,
    )


def solve_from_stats(
    S: jax.Array,
    r: jax.Array,
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
    """Solve per param map from the CENTRED statistics (S, r, x̄, ȳ, Σw) of the
    module header, whichever pass produced them. The column std for
    standardization comes from the centred diagonal: var = diag(S)/(n-1).
    Each map counts `linear.solver{path=l2|elastic_net}`; `linear.solve` is its
    compiled solve, waited for, and `linear.fetch` the result to the host."""
    if standardize:
        # unbiased column std, Spark's Summarizer convention (reference utils.py:876-982)
        var = jnp.diagonal(S) / jnp.maximum(n - 1.0, 1.0)
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
        l2 = p_reg == 0.0 or p_l1r == 0.0
        counter_inc("linear.solver", 1, path="l2" if l2 else "elastic_net")
        with span("linear.solve"):
            if l2:
                out = solve_l2(S, r, xbar, ybar, n, scale, p_reg, p_fi)
            else:
                out = solve_elastic_net(
                    S, r, xbar, ybar, n, scale, p_reg, p_l1r, p_fi, p_mi, p_tol
                )
            out = jax.block_until_ready(out)
        with span("linear.fetch"):
            results.append(
                {
                    "coefficients": np.asarray(out[0]),
                    "intercept": float(out[1]),
                    "n_iter": int(out[2]) if len(out) > 2 else 1,
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
