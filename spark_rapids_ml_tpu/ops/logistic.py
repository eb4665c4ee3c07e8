#
# Logistic regression fit kernels — the TPU-native replacement for
# cuml.linear_model.logistic_regression_mg.LogisticRegressionMG (reference
# classification.py:989-1052: a C++ quasi-Newton (L-BFGS/OWL-QN) solver with the
# gradient allreduce over NCCL, configured with linesearch_max_iter=20,
# lbfgs_memory=10, penalty_normalized=False).
#
# TPU formulation: the loss/gradient over row-sharded data is ONE jitted function —
# jax.value_and_grad of the weighted cross-entropy; the contraction over the sharded
# row axis makes XLA emit the psum (where cuML put its NCCL allreduce). The optimizer
# loop is a lax.while_loop around optax.lbfgs (memory 10, zoom linesearch ≤20 steps —
# the reference's cuML settings). Through autodiff an evaluation is two reads of X
# (logits, then gradient); where `pallas_logistic.eval_gate` says so the binary data
# term is instead ONE sweep with its own derivative rule (ops/pallas_logistic.py).
#
# L1/elastic-net uses FISTA proximal gradient instead of OWL-QN: same distributed
# gradient, soft-threshold prox on coefficients (not intercept), Lipschitz constant
# from a one-pass Gram + power iteration. OWL-QN's orthant projections are branchy;
# FISTA is pure matrix arithmetic — the TPU-friendly way to the same objective.
#
# Objective (Spark parity): (1/Σw)·Σᵢ wᵢ·CE(yᵢ, xᵢ) + λ(α‖β‖₁ + (1-α)/2·‖β‖²),
# penalty on σ-scaled coefficients when standardization=True (implemented by
# optimizing β_s with effective coefficients β_s/σ — no scaled data copy; XLA fuses
# the divide into the logits matmul).
#

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..observability import counter_inc, span
from ..observability.device import compiled_kernel
from ._precision import pdot
from .linalg import power_iteration_lmax, weighted_moments
from .pallas_logistic import binomial_data_term, eval_gate, eval_plan

LINESEARCH_MAX_STEPS = 20  # reference classification.py:1046-1052
LBFGS_MEMORY = 10


def _binomial_loss_fn(X, y, w, scale, reg_l2, fit_intercept, fused=None):
    """Returns f(params) for params = [coef_s (d,), intercept]. y in {0,1}.
    `fused` (static) is None for the two `pdot` passes an evaluation, or
    `pallas_logistic.eval_plan`'s description for the one-read sweep: the data
    term alone changes hands; the `/scale` of standardization and the ridge
    term stay here, in plain jnp, differentiated as ever."""
    wsum = jnp.sum(w)

    def loss(params):
        coef_s, b = params[:-1], params[-1]
        beta, b = coef_s / scale, jnp.where(fit_intercept, b, 0.0)
        if fused is None:
            z = pdot(X, beta) + b
            # stable log-loss: softplus(z) - y*z
            data = jnp.sum(w * (jax.nn.softplus(z) - y * z))
        else:
            data = binomial_data_term(fused, X, y, w, beta, b)
        return data / wsum + 0.5 * reg_l2 * jnp.sum(coef_s * coef_s)

    return loss


def _multinomial_loss_fn(X, y_onehot, w, scale, reg_l2, fit_intercept):
    """params = (k, d+1): rows [coef_s_k..., intercept_k]."""
    wsum = jnp.sum(w)

    def loss(params):
        coef_s, b = params[:, :-1], params[:, -1]
        z = pdot(X, (coef_s / scale).T) + jnp.where(fit_intercept, b, 0.0)
        logz = jax.nn.log_softmax(z, axis=1)
        ce = -jnp.sum(w * jnp.sum(y_onehot * logz, axis=1)) / wsum
        return ce + 0.5 * reg_l2 * jnp.sum(coef_s * coef_s)

    return loss


def _run_lbfgs(loss, params0, max_iter: int, tol: float):
    """jitted L-BFGS loop (optax) with objective-decrease + gradient stopping, the
    stopping style of the reference's QN solver.

    Returns `(params, n_iter, grad, (loss_evals, linesearch_steps))`. `grad`
    is the gradient at `params` as the loop itself formed it: the zoom state
    carries the accepted point's gradient into the next iteration, so this
    costs no pass, and it is the one output of the timed program whose
    arithmetic can be held to float64 from outside (zeros if no iteration
    ran). The counts are the loop's own, of its value-and-gradient evaluations
    (each is a forward and a backward pass over the data: one per zoom
    line-search step, and one for the first iteration, whose state holds no
    value yet) and of the line search's steps, a fit's totals. The caller's
    closing `loss(params)` is one forward pass more and is not counted."""
    opt = optax.lbfgs(
        memory_size=LBFGS_MEMORY,
        linesearch=optax.scale_by_zoom_linesearch(max_linesearch_steps=LINESEARCH_MAX_STEPS),
    )
    value_and_grad = optax.value_and_grad_from_state(loss)

    def cond(state):
        _, _, it, delta, gnorm, _, _ = state
        return jnp.logical_and(
            it < max_iter, jnp.logical_and(delta > tol, gnorm > tol)
        )

    def body(state):
        params, opt_state, it, _, _, evals, ls_steps = state
        # `value_and_grad_from_state` evaluates afresh only where the state
        # carries no finite value (the first iteration)
        fresh = ~jnp.isfinite(optax.tree_utils.tree_get(opt_state, "value"))
        value, grad = value_and_grad(params, state=opt_state)
        updates, opt_state = opt.update(
            grad, opt_state, params, value=value, grad=grad, value_fn=loss
        )
        new_params = optax.apply_updates(params, updates)
        new_value = optax.tree_utils.tree_get(opt_state, "value")
        delta = jnp.abs(value - new_value) / jnp.maximum(jnp.abs(new_value), 1.0)
        gnorm = optax.tree_utils.tree_norm(grad)
        steps = optax.tree_utils.tree_get(opt_state, "num_linesearch_steps").astype(jnp.int32)
        return (new_params, opt_state, it + 1, delta, gnorm,
                evals + steps + fresh.astype(jnp.int32), ls_steps + steps)

    state0 = (
        params0,
        opt.init(params0),
        0,
        jnp.array(jnp.inf, params0.dtype),
        jnp.array(jnp.inf, params0.dtype),
        jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32),
    )
    params, opt_state, n_iter, _, _, evals, ls_steps = jax.lax.while_loop(cond, body, state0)
    return params, n_iter, optax.tree_utils.tree_get(opt_state, "grad"), (evals, ls_steps)


def count_lbfgs(path: str, counts) -> None:
    """A quasi-Newton fit's totals as counters, once its result is on the host:
    `logistic.path{path=}` (which compiled fit ran), `logistic.loss_evals`,
    `logistic.linesearch_steps` (`_run_lbfgs`'s counts; the prox paths, which
    have no line search, pass none)."""
    counter_inc("logistic.path", 1, path=path)
    if counts:
        counter_inc("logistic.loss_evals", int(counts[0]))
        counter_inc("logistic.linesearch_steps", int(counts[1]))


@compiled_kernel("logistic.qn_fit",
                 static_argnames=("fit_intercept", "max_iter", "multinomial", "fused"))
def _qn_fit(
    X, y_enc, w, scale, reg_l2, fit_intercept: bool, max_iter: int, tol, multinomial: bool,
    fused=None,
):
    if multinomial:
        loss = _multinomial_loss_fn(X, y_enc, w, scale, reg_l2, fit_intercept)
        params0 = jnp.zeros((y_enc.shape[1], X.shape[1] + 1), X.dtype)
    else:
        loss = _binomial_loss_fn(X, y_enc, w, scale, reg_l2, fit_intercept, fused)
        params0 = jnp.zeros((X.shape[1] + 1,), X.dtype)
    params, n_iter, grad, counts = _run_lbfgs(loss, params0, max_iter, tol)
    return params, n_iter, loss(params), grad, *counts


def _accelerated_prox_loop(smooth, prox, params0, step, max_iter: int, tol):
    """The shared FISTA/projected-gradient machinery: Nesterov-accelerated
    proximal steps with relative-movement stopping. `prox` is the soft-threshold
    for elastic net and the box clip for bound constraints."""
    grad_fn = jax.grad(smooth)

    def cond(state):
        _, _, _, it, delta = state
        return jnp.logical_and(it < max_iter, delta > tol)

    def body(state):
        pk, zk, tk, it, _ = state
        p_next = prox(zk - step * grad_fn(zk))
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        z_next = p_next + ((tk - 1.0) / t_next) * (p_next - pk)
        delta = jnp.max(jnp.abs(p_next - pk)) / (jnp.max(jnp.abs(p_next)) + 1e-12)
        return p_next, z_next, t_next, it + 1, delta

    dtype = params0.dtype
    state0 = (params0, params0, jnp.array(1.0, dtype), 0, jnp.array(jnp.inf, dtype))
    params, _, _, n_iter, _ = jax.lax.while_loop(cond, body, state0)
    return params, n_iter


@compiled_kernel("logistic.fista_fit",
                 static_argnames=("fit_intercept", "max_iter", "multinomial", "fused"))
def _fista_fit(
    X, y_enc, w, scale, reg_l1, reg_l2, lipschitz, fit_intercept: bool, max_iter: int,
    tol, multinomial: bool, fused=None,
):
    """Proximal-gradient elastic-net fit; prox applies only to coefficient entries."""
    if multinomial:
        smooth = _multinomial_loss_fn(X, y_enc, w, scale, reg_l2, fit_intercept)
        params0 = jnp.zeros((y_enc.shape[1], X.shape[1] + 1), X.dtype)
        coef_mask = jnp.concatenate(
            [jnp.ones((y_enc.shape[1], X.shape[1])), jnp.zeros((y_enc.shape[1], 1))], axis=1
        ).astype(X.dtype)
    else:
        smooth = _binomial_loss_fn(X, y_enc, w, scale, reg_l2, fit_intercept, fused)
        params0 = jnp.zeros((X.shape[1] + 1,), X.dtype)
        coef_mask = jnp.concatenate(
            [jnp.ones((X.shape[1],)), jnp.zeros((1,))]
        ).astype(X.dtype)

    step = 1.0 / lipschitz

    def prox(p):
        soft = jnp.sign(p) * jnp.maximum(jnp.abs(p) - step * reg_l1, 0.0)
        return jnp.where(coef_mask > 0, soft, p)

    params, n_iter = _accelerated_prox_loop(smooth, prox, params0, step, max_iter, tol)
    return params, n_iter, smooth(params) + reg_l1 * jnp.sum(jnp.abs(params * coef_mask))


@compiled_kernel("logistic.projected_fit",
                 static_argnames=("fit_intercept", "max_iter", "multinomial", "fused"))
def _projected_fit(
    X, y_enc, w, scale, reg_l2, lipschitz, fit_intercept: bool, max_iter: int,
    tol, multinomial: bool, lb, ub, fused=None,
):
    """Box-constrained fit: accelerated projected gradient (the same loop as
    _fista_fit with the prox of the box indicator = clip). `lb`/`ub` are full
    params-shaped bounds in the STANDARDIZED space (coef entries pre-multiplied by
    sigma; intercept entries unscaled; +-inf where unbounded). Spark exposes this
    as lowerBounds/upperBoundsOnCoefficients/Intercepts and solves it with
    L-BFGS-B — projection onto the box is the TPU-friendly route to the same
    optimum."""
    if multinomial:
        smooth = _multinomial_loss_fn(X, y_enc, w, scale, reg_l2, fit_intercept)
        params0 = jnp.zeros((y_enc.shape[1], X.shape[1] + 1), X.dtype)
    else:
        smooth = _binomial_loss_fn(X, y_enc, w, scale, reg_l2, fit_intercept, fused)
        params0 = jnp.zeros((X.shape[1] + 1,), X.dtype)

    step = 1.0 / lipschitz

    def proj(p):
        return jnp.clip(p, lb, ub)

    params, n_iter = _accelerated_prox_loop(
        smooth, proj, proj(params0), step, max_iter, tol
    )
    return params, n_iter, smooth(params)


@compiled_kernel("logistic.gram_lmax")
def _gram_lmax(X, w, scale):
    """λ_max of (X/σ)ᵀW(X/σ)/Σw via one sharded Gram pass + power iteration."""
    wsum = jnp.sum(w)
    Xs = X / scale
    G = pdot((Xs * w[:, None]).T, Xs) / wsum
    return power_iteration_lmax(G)


def _lipschitz(X, w, scale, reg_l2, multinomial: bool):
    """The prox paths' step bound, from the Gram's largest eigenvalue."""
    return (0.5 if multinomial else 0.25) * _gram_lmax(X, w, scale) + reg_l2 + 1e-12


def logreg_fit(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    n_classes: int,
    reg: float,
    l1_ratio: float,
    fit_intercept: bool,
    standardize: bool,
    max_iter: int,
    tol: float,
    multinomial: bool,
    bounds: "tuple | None" = None,
) -> Dict[str, Any]:
    """Full fit; returns Spark-layout model attributes:
    coefficients (k_rows, d) and intercepts (k_rows,) with k_rows = 1 for binomial,
    and `gradient` (k_rows, d+1): the objective's gradient at them with respect
    to (coefficients, intercept), the intercept's entry last, as the
    quasi-Newton loop formed it at its last iterate (None on the prox paths).

    `bounds` = (lb_coef, ub_coef, lb_icpt, ub_icpt) in ORIGINAL coefficient space
    ((k_rows, d) matrices / (k_rows,) vectors, None where unbounded) switches on the
    box-constrained projected fit — the reference maps these Spark params to None
    (unsupported, classification.py:694-698); here they run natively.

    `logistic.eval{form=fused|two_pass}` counts how the binary data term is
    evaluated (one Pallas sweep over X, or autodiff's two passes) and
    `logistic.eval_gate{fused=0|1,reason=}` which test of
    `pallas_logistic.eval_gate` decided it, once a fit, whichever solver runs."""
    d = X.shape[1]
    fused, reason = eval_gate(X, multinomial)
    counter_inc("logistic.eval_gate", 1, fused=int(fused), reason=reason)
    counter_inc("logistic.eval", 1, form="fused" if fused else "two_pass")
    plan = eval_plan(X) if fused else None
    if standardize:
        _, var, _ = weighted_moments(X, w)
        scale = jnp.sqrt(var)
        scale = jnp.where(scale <= 0.0, 1.0, scale)
    else:
        scale = jnp.ones((d,), X.dtype)

    reg_l1 = reg * l1_ratio
    reg_l2 = reg * (1.0 - l1_ratio)

    if multinomial:
        y_enc = jax.nn.one_hot(y.astype(jnp.int32), n_classes, dtype=X.dtype) * (
            (w > 0)[:, None]
        )
    else:
        y_enc = y

    icpt_bounded = False
    if bounds is not None:
        if reg_l1 > 0.0:
            raise ValueError(
                "Coefficient bounds support only L2 regularization "
                "(elasticNetParam must be 0.0), matching Spark."
            )
        lb_c, ub_c, lb_i, ub_i = bounds
        k_rows = n_classes if multinomial else 1
        inf = jnp.inf

        def _mat(v, fill, name):
            if v is None:
                return jnp.full((k_rows, d), fill, X.dtype)
            arr = np.asarray(v, np.float32)
            if arr.ndim == 1 and k_rows == 1:
                arr = arr.reshape(1, -1)
            if arr.shape != (k_rows, d):
                raise ValueError(
                    f"{name} must have shape ({k_rows}, {d}) "
                    f"(numCoefficientSets x numFeatures), got {arr.shape}."
                )
            return jnp.asarray(arr)

        def _vec(v, fill, name):
            if v is None:
                return jnp.full((k_rows,), fill, X.dtype)
            arr = np.asarray(v, np.float32).reshape(-1)
            if arr.shape != (k_rows,):
                raise ValueError(
                    f"{name} must have length {k_rows} (numCoefficientSets), "
                    f"got {arr.shape[0]}."
                )
            return jnp.asarray(arr)

        lbm_raw = _mat(lb_c, -inf, "lowerBoundsOnCoefficients")
        ubm_raw = _mat(ub_c, inf, "upperBoundsOnCoefficients")
        lbi = _vec(lb_i, -inf, "lowerBoundsOnIntercepts")
        ubi = _vec(ub_i, inf, "upperBoundsOnIntercepts")
        if bool(jnp.any(lbm_raw > ubm_raw)) or bool(jnp.any(lbi > ubi)):
            raise ValueError(
                "Each lower bound must be <= the matching upper bound."
            )
        # constraint l <= coef <= u in original space <=> l*sigma <= coef_s <= u*sigma
        lbm = lbm_raw * scale[None, :]
        ubm = ubm_raw * scale[None, :]
        icpt_bounded = lb_i is not None or ub_i is not None
        if icpt_bounded and not fit_intercept:
            raise ValueError(
                "Intercept bounds require fitIntercept=True (an unbounded, "
                "unfitted intercept cannot honor them)."
            )
        lb_full = jnp.concatenate([lbm, lbi[:, None]], axis=1)
        ub_full = jnp.concatenate([ubm, ubi[:, None]], axis=1)
        if not multinomial:
            lb_full, ub_full = lb_full[0], ub_full[0]
        path = "projected"
        lipschitz = _lipschitz(X, w, scale, reg_l2, multinomial)
        solve = functools.partial(
            _projected_fit, X, y_enc, w, scale, reg_l2, lipschitz, bool(fit_intercept),
            int(max_iter), float(tol), bool(multinomial), lb_full, ub_full, fused=plan,
        )
    elif reg_l1 > 0.0:
        path = "fista"
        lipschitz = _lipschitz(X, w, scale, reg_l2, multinomial)
        solve = functools.partial(
            _fista_fit, X, y_enc, w, scale, reg_l1, reg_l2, lipschitz,
            bool(fit_intercept), int(max_iter), float(tol), bool(multinomial), fused=plan,
        )
    else:
        path = "qn"
        solve = functools.partial(
            _qn_fit, X, y_enc, w, scale, reg_l2, bool(fit_intercept), int(max_iter),
            float(tol), bool(multinomial), fused=plan,
        )

    with span("logistic.solve", {"waits": "device"}):
        # waited for, so that the device's solve and the host's fetch below
        # are not one number
        params, n_iter, obj, *qn = jax.block_until_ready(solve())
    with span("logistic.fetch"):
        count_lbfgs(path, qn[1:])
        params = np.asarray(params, dtype=np.float64)
        scale_h = np.asarray(scale, dtype=np.float64)
        n_iter, obj = int(n_iter), float(obj)
        gradient = None
        if qn:
            # d/d(coef) = d/d(coef_s) * sigma: the loop optimises coef_s = coef * sigma
            gradient = np.atleast_2d(np.array(qn[0], dtype=np.float64))  # a copy: scaled in place
            gradient[:, :-1] *= scale_h
            gradient = gradient.astype(np.float32)
    if multinomial:
        coef = params[:, :-1] / scale_h
        intercept = params[:, -1]
        # Spark centers multinomial intercepts (reference classification.py:1135-1147)
        # — but never when the user bounded them (centering would break the box)
        if fit_intercept and not icpt_bounded:
            intercept = intercept - intercept.mean()
    else:
        coef = (params[:-1] / scale_h).reshape(1, -1)
        intercept = params[-1:]
    return {
        "coefficients": coef.astype(np.float32),
        "intercepts": intercept.astype(np.float32),
        "n_iter": n_iter,
        "objective": obj,
        "gradient": gradient,
    }


@compiled_kernel("logistic.decision", static_argnames=("multinomial",))
def logreg_decision(X, coef, intercept, multinomial: bool):
    """Raw margins: (n,) for binomial single-vector, (n,k) for multinomial."""
    if multinomial:
        return pdot(X, coef.T) + intercept
    return pdot(X, coef[0]) + intercept[0]
