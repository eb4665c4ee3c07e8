#
# True sparse device kernels — the TPU-native replacement for the reference's CSR
# training path (reference classification.py:1002-1055 trains LogisticRegressionMG
# directly on CSR; CSR ingest core.py:220-265; int64 index escalation for >1e9 nnz
# classification.py:960-966).
#
# TPU has no native CSR. The TPU-first formulation is ELL (padded row-wise) storage:
#   values  (n, r)  float   — r = max nonzeros per row
#   indices (n, r)  int32/64 — column ids, padding entries point at column 0 with
#                              value 0 so they contribute nothing
# Every sparse contraction becomes a dense-shaped gather/scatter XLA shards cleanly
# over the row axis of the mesh:
#   X v    = sum_r values[:, r] * v[indices[:, r]]            (gather  + reduce)
#   Xᵀ r   = scatter-add of values * r into a (d,) vector     (the transpose pass;
#            under SPMD the replicated output is all-reduced — psum where the
#            reference's NCCL allreduce sat)
# Memory is O(n·r) = O(nnz) for bounded row skew — never O(n·d).
#
# Solvers are MATRIX-FREE: logistic regression reuses the L-BFGS/FISTA machinery with
# gather-based losses (autodiff turns the gather into the scatter-add transpose);
# linear regression solves the normal equations by conjugate gradients with a centered
# matvec closure — the d×d Gram matrix is never materialized, so d can be large too.
#

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from ..observability.device import compiled_kernel

# int32 column/row indices are escalated to int64 past this many nonzeros, mirroring
# the reference's nnz>INT32_MAX fallback (classification.py:960-966)
INT32_LIMIT = 2**31 - 1


def csr_to_ell(
    csr: Any, float32: bool = True
) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized scipy CSR -> ELL conversion (no Python per-row loop).

    Returns (values (n, r), indices (n, r)). Padding cells are (0.0, col 0)."""
    csr = csr.tocsr()
    n, _ = csr.shape
    dtype = np.float32 if float32 else np.float64
    counts = np.diff(csr.indptr)
    r = int(counts.max()) if n else 0
    r = max(r, 1)
    idx_dtype = np.int64 if (csr.nnz > INT32_LIMIT or n > INT32_LIMIT) else np.int32
    if idx_dtype == np.int32 and dtype == np.float32 and csr.nnz:
        from ..native import csr_to_ell as native_csr_to_ell

        native = native_csr_to_ell(csr.indptr, csr.indices, csr.data, n, r)
        if native is not None:  # OpenMP host kernel (native/src/srml_native.cpp)
            return native
    values = np.zeros((n, r), dtype=dtype)
    indices = np.zeros((n, r), dtype=idx_dtype)
    if csr.nnz:
        rows = np.repeat(np.arange(n), counts)
        offsets = np.arange(csr.nnz) - np.repeat(csr.indptr[:-1], counts)
        values[rows, offsets] = csr.data
        indices[rows, offsets] = csr.indices
    return values, indices


def pad_ell_rows(
    values: np.ndarray,
    indices: np.ndarray,
    num_workers: int,
    *extra_row_aligned: Optional[np.ndarray],
    row_multiple: int = 8,
):
    """Row-pad ELL arrays to an equal, tile-friendly shard per worker (the sparse twin
    of parallel/partition.py pad_rows). Returns (values, indices, weight, extras)."""
    n = values.shape[0]
    chunk = num_workers * row_multiple
    padded = ((n + chunk - 1) // chunk) * chunk
    pad = padded - n
    weight = np.ones((padded,), dtype=values.dtype)
    if pad:
        weight[n:] = 0.0
        values = np.concatenate(
            [values, np.zeros((pad, values.shape[1]), values.dtype)], axis=0
        )
        indices = np.concatenate(
            [indices, np.zeros((pad, indices.shape[1]), indices.dtype)], axis=0
        )
    extras = []
    for e in extra_row_aligned:
        if e is None:
            extras.append(None)
        elif pad:
            extras.append(np.concatenate([e, np.zeros((pad,) + e.shape[1:], e.dtype)]))
        else:
            extras.append(e)
    return values, indices, weight, extras


# ---- ELL primitive contractions (all jit-inlined into the solvers) ----


@compiled_kernel("sparse.ell_matvec")
def ell_matvec(values: jax.Array, indices: jax.Array, v: jax.Array) -> jax.Array:
    """X @ v -> (n,)."""
    return jnp.sum(values * v[indices], axis=1)


@compiled_kernel("sparse.ell_matmat")
def ell_matmat(values: jax.Array, indices: jax.Array, M: jax.Array) -> jax.Array:
    """X @ M -> (n, k) for M (d, k)."""
    return jnp.einsum("nr,nrk->nk", values, M[indices])


def ell_rmatvec(values: jax.Array, indices: jax.Array, r: jax.Array, d: int) -> jax.Array:
    """Xᵀ @ r -> (d,). Scatter-add; XLA all-reduces the replicated output shards."""
    contrib = (values * r[:, None]).reshape(-1)
    return jnp.zeros((d,), values.dtype).at[indices.reshape(-1)].add(contrib)


def ell_rmatmat(values: jax.Array, indices: jax.Array, R: jax.Array, d: int) -> jax.Array:
    """Xᵀ @ R -> (d, k) for R (n, k)."""
    k = R.shape[1]
    contrib = (values[:, :, None] * R[:, None, :]).reshape(-1, k)
    return jnp.zeros((d, k), values.dtype).at[indices.reshape(-1)].add(contrib)


@compiled_kernel("sparse.weighted_moments", static_argnames=("d",))
def sparse_weighted_moments(
    values: jax.Array, indices: jax.Array, w: jax.Array, d: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(mean, var, wsum) per column with the unbiased (wsum-1) denominator — the
    sparse twin of ops/linalg.weighted_moments. Implicit zeros count toward the
    moments exactly as the dense kernel counts them."""
    wsum = jnp.sum(w)
    s1 = ell_rmatvec(values, indices, w, d)
    s2 = ell_rmatvec(values * values, indices, w, d)
    mean = s1 / wsum
    var = (s2 - wsum * mean * mean) / jnp.maximum(wsum - 1.0, 1.0)
    return mean, jnp.maximum(var, 0.0), wsum


def _matvec_lmax(matvec, d: int, dtype, n_steps: int = 16) -> jax.Array:
    """Matrix-free power iteration for the largest eigenvalue (FISTA Lipschitz)."""

    def body(i, v):
        v = matvec(v)
        return v / (jnp.linalg.norm(v) + 1e-30)

    v = jax.lax.fori_loop(0, n_steps, body, jnp.ones((d,), dtype) / jnp.sqrt(d))
    return jnp.dot(v, matvec(v))


# ---- sparse logistic regression (matrix-free L-BFGS / FISTA) ----


def _sparse_binomial_loss(values, indices, y, w, scale, reg_l2, fit_intercept):
    wsum = jnp.sum(w)

    def loss(params):
        coef_s, b = params[:-1], params[-1]
        z = ell_matvec(values, indices, coef_s / scale) + jnp.where(
            fit_intercept, b, 0.0
        )
        ce = jnp.sum(w * (jax.nn.softplus(z) - y * z)) / wsum
        return ce + 0.5 * reg_l2 * jnp.sum(coef_s * coef_s)

    return loss


def _sparse_multinomial_loss(values, indices, y_onehot, w, scale, reg_l2, fit_intercept):
    wsum = jnp.sum(w)

    def loss(params):
        coef_s, b = params[:, :-1], params[:, -1]
        z = ell_matmat(values, indices, (coef_s / scale).T) + jnp.where(
            fit_intercept, b, 0.0
        )
        logz = jax.nn.log_softmax(z, axis=1)
        ce = -jnp.sum(w * jnp.sum(y_onehot * logz, axis=1)) / wsum
        return ce + 0.5 * reg_l2 * jnp.sum(coef_s * coef_s)

    return loss


@compiled_kernel("sparse.qn_fit",
                 static_argnames=("d", "fit_intercept", "max_iter", "multinomial"))
def _sparse_qn_fit(
    values, indices, y_enc, w, scale, reg_l2, d: int, fit_intercept: bool,
    max_iter: int, tol, multinomial: bool,
):
    from .logistic import _run_lbfgs

    if multinomial:
        loss = _sparse_multinomial_loss(
            values, indices, y_enc, w, scale, reg_l2, fit_intercept
        )
        params0 = jnp.zeros((y_enc.shape[1], d + 1), values.dtype)
    else:
        loss = _sparse_binomial_loss(
            values, indices, y_enc, w, scale, reg_l2, fit_intercept
        )
        params0 = jnp.zeros((d + 1,), values.dtype)
    params, n_iter, _, counts = _run_lbfgs(loss, params0, max_iter, tol)
    return params, n_iter, loss(params), *counts


@compiled_kernel("sparse.fista_fit",
                 static_argnames=("d", "fit_intercept", "max_iter", "multinomial"))
def _sparse_fista_fit(
    values, indices, y_enc, w, scale, reg_l1, reg_l2, lipschitz, d: int,
    fit_intercept: bool, max_iter: int, tol, multinomial: bool,
):
    if multinomial:
        smooth = _sparse_multinomial_loss(
            values, indices, y_enc, w, scale, reg_l2, fit_intercept
        )
        params0 = jnp.zeros((y_enc.shape[1], d + 1), values.dtype)
        coef_mask = jnp.concatenate(
            [jnp.ones((y_enc.shape[1], d)), jnp.zeros((y_enc.shape[1], 1))], axis=1
        ).astype(values.dtype)
    else:
        smooth = _sparse_binomial_loss(
            values, indices, y_enc, w, scale, reg_l2, fit_intercept
        )
        params0 = jnp.zeros((d + 1,), values.dtype)
        coef_mask = jnp.concatenate([jnp.ones((d,)), jnp.zeros((1,))]).astype(
            values.dtype
        )

    grad_fn = jax.grad(smooth)
    step = 1.0 / lipschitz

    def prox(p):
        soft = jnp.sign(p) * jnp.maximum(jnp.abs(p) - step * reg_l1, 0.0)
        return jnp.where(coef_mask > 0, soft, p)

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

    state0 = (params0, params0, jnp.array(1.0, values.dtype), 0,
              jnp.array(jnp.inf, values.dtype))
    params, _, _, n_iter, _ = jax.lax.while_loop(cond, body, state0)
    return params, n_iter, smooth(params) + reg_l1 * jnp.sum(jnp.abs(params * coef_mask))


def sparse_logreg_fit(
    values: jax.Array,
    indices: jax.Array,
    d: int,
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
) -> Dict[str, Any]:
    """Sparse twin of ops/logistic.logreg_fit — same objective, Spark-layout attrs.
    Standardization divides by the column std only (no centering — centering a sparse
    matrix would densify it; the reference's sparse path has the same convention,
    classification.py:1018-1028)."""
    from .logistic import count_lbfgs

    if standardize:
        _, var, _ = sparse_weighted_moments(values, indices, w, d)
        scale = jnp.sqrt(var)
        scale = jnp.where(scale <= 0.0, 1.0, scale)
    else:
        scale = jnp.ones((d,), values.dtype)

    reg_l1 = reg * l1_ratio
    reg_l2 = reg * (1.0 - l1_ratio)

    if multinomial:
        y_enc = jax.nn.one_hot(y.astype(jnp.int32), n_classes, dtype=values.dtype) * (
            (w > 0)[:, None]
        )
    else:
        y_enc = y

    if reg_l1 > 0.0:
        wsum = jnp.sum(w)

        def gram_mv(v):
            xv = ell_matvec(values, indices, v / scale)
            return ell_rmatvec(values, indices, w * xv, d) / scale / wsum

        lmax = _matvec_lmax(gram_mv, d, values.dtype)
        lipschitz = (0.5 if multinomial else 0.25) * lmax + reg_l2 + 1e-12
        path, counts = "fista", ()
        params, n_iter, obj = _sparse_fista_fit(
            values, indices, y_enc, w, scale, reg_l1, reg_l2, lipschitz, int(d),
            bool(fit_intercept), int(max_iter), float(tol), bool(multinomial),
        )
    else:
        path = "qn"
        params, n_iter, obj, *counts = _sparse_qn_fit(
            values, indices, y_enc, w, scale, reg_l2, int(d), bool(fit_intercept),
            int(max_iter), float(tol), bool(multinomial),
        )
    count_lbfgs(path, counts)
    params = np.asarray(params, dtype=np.float64)
    scale_h = np.asarray(scale, dtype=np.float64)
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
        "objective": float(obj),
    }


# ---- sparse linear regression (matrix-free CG / FISTA on normal equations) ----


@compiled_kernel("sparse.linreg_solve",
                 static_argnames=("d", "fit_intercept", "max_iter", "l1_zero"))
def _sparse_linreg_solve(
    values, indices, y, w, scale, d: int, reg, l1_ratio, fit_intercept: bool,
    max_iter: int, tol, l1_zero: bool,
):
    """Solve min 1/(2n)Σw(y - Xβ - b)² + λ(α‖β‖₁ + (1-α)/2‖β‖²) in σ-scaled space
    without materializing XᵀX. The centered+scaled Gram matvec is
      Aₛ v = D⁻¹ (Xᵀ W X - n x̄ x̄ᵀ) D⁻¹ v / n
    computed as two ELL passes plus rank-one mean corrections."""
    wsum = jnp.sum(w)
    xbar = ell_rmatvec(values, indices, w, d) / wsum
    ybar = jnp.sum(w * y) / wsum

    def gram_mv(v):
        u = v / scale
        xv = ell_matvec(values, indices, u)
        av = ell_rmatvec(values, indices, w * xv, d)
        if fit_intercept:
            av = av - wsum * xbar * jnp.dot(xbar, u)
        return (av / scale) / wsum

    by = ell_rmatvec(values, indices, w * y, d)
    if fit_intercept:
        by = by - wsum * xbar * ybar
    bs = (by / scale) / wsum

    l1 = reg * l1_ratio
    l2 = reg * (1.0 - l1_ratio)

    if l1_zero:
        # OLS/Ridge: CG on (Aₛ + λI) β = bₛ
        coef_s, _ = jax.scipy.sparse.linalg.cg(
            lambda v: gram_mv(v) + reg * v, bs, tol=1e-10, maxiter=200
        )
        n_iter = jnp.array(1, jnp.int32)
    else:
        L = _matvec_lmax(gram_mv, d, values.dtype) + l2 + 1e-12
        step = 1.0 / L

        def soft(x, t):
            return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)

        def cond(state):
            _, _, _, it, delta = state
            return jnp.logical_and(it < max_iter, delta > tol)

        def body(state):
            wk, zk, tk, it, _ = state
            grad = gram_mv(zk) - bs + l2 * zk
            w_next = soft(zk - step * grad, step * l1)
            t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
            z_next = w_next + ((tk - 1.0) / t_next) * (w_next - wk)
            delta = jnp.max(jnp.abs(w_next - wk)) / (jnp.max(jnp.abs(w_next)) + 1e-12)
            return w_next, z_next, t_next, it + 1, delta

        w0 = jnp.zeros((d,), values.dtype)
        state = (w0, w0, jnp.array(1.0, values.dtype), 0,
                 jnp.array(jnp.inf, values.dtype))
        coef_s, _, _, n_iter, _ = jax.lax.while_loop(cond, body, state)

    coef = coef_s / scale
    intercept = jnp.where(fit_intercept, ybar - jnp.dot(xbar, coef), 0.0)
    return coef, intercept, n_iter


def sparse_linreg_fit(
    values: jax.Array,
    indices: jax.Array,
    d: int,
    y: jax.Array,
    w: jax.Array,
    reg: float,
    l1_ratio: float,
    fit_intercept: bool,
    standardize: bool,
    max_iter: int,
    tol: float,
    extra_param_sets: Optional[List[Dict[str, Any]]] = None,
) -> List[Dict[str, Any]]:
    """Sparse twin of ops/linear.linreg_fit. The moments pass is shared across param
    maps (single-pass fitMultiple); each map re-solves matrix-free."""
    if standardize:
        _, var, _ = sparse_weighted_moments(values, indices, w, d)
        scale = jnp.sqrt(var)
        scale = jnp.where(scale <= 0.0, 1.0, scale)
    else:
        scale = jnp.ones((d,), values.dtype)

    param_sets = extra_param_sets if extra_param_sets is not None else [
        {"alpha": reg, "l1_ratio": l1_ratio, "fit_intercept": fit_intercept,
         "max_iter": max_iter, "tol": tol}
    ]
    results = []
    for p in param_sets:
        p_reg = float(p.get("alpha", reg))
        p_l1r = float(p.get("l1_ratio", l1_ratio))
        coef, intercept, n_iter = _sparse_linreg_solve(
            values, indices, y, w, scale, int(d),
            jnp.asarray(p_reg, values.dtype), jnp.asarray(p_l1r, values.dtype),
            bool(p.get("fit_intercept", fit_intercept)),
            int(p.get("max_iter", max_iter)),
            float(p.get("tol", tol)),
            l1_zero=(p_reg == 0.0 or p_l1r == 0.0),
        )
        results.append(
            {
                "coefficients": np.asarray(coef),
                "intercept": float(intercept),
                "n_iter": int(n_iter),
            }
        )
    return results
