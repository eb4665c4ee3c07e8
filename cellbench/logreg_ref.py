"""Plain numpy reference for binary ridge logistic regression. Nothing here
imports the program or JAX.

Spark's objective, the one `cellbench/configs/logreg_l2_d3000.json` states:

    f(beta, b) = (1/n) sum_i [softplus(z_i) - y_i z_i] + (lambda/2) |beta|^2,
    z_i = x_i . beta + b,   the intercept b unpenalised.

Its value and gradient at a point are two passes over the host table (logits
`X beta`, then `X' r` with r = sigmoid(z) - y), as `refs.py` forms its sums:
float32 BLAS inside a `CHUNK`-row chunk, float64 across chunks and for every
sum over rows. `low_precision=True` is the controls' arithmetic: both
products from bfloat16-rounded operands, as one MXU pass would form them.

`newton_optimum` is the damped Newton iteration on the same objective in
float64 throughout: the one stationary point a strongly convex objective has.
It forms the (d+1) x (d+1) Hessian, so it is for small tables (the tier-1
test); at 357,376 x 3000 the cell compares the value, the gradient and one
step, which cost two passes a point.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from .refs import CHUNK, round_bf16


def _softplus(z: np.ndarray) -> np.ndarray:
    return np.maximum(z, 0.0) + np.log1p(np.exp(-np.abs(z)))


def _sigmoid(z: np.ndarray) -> np.ndarray:
    e = np.exp(-np.abs(z))
    return np.where(z >= 0, 1.0 / (1.0 + e), e / (1.0 + e))


def logits(X: np.ndarray, coef: np.ndarray, intercept: float,
           low_precision: bool = False) -> np.ndarray:
    """`X coef + intercept` in float64 from float32 products a chunk."""
    c32 = np.ascontiguousarray(coef, np.float32)
    if low_precision:
        c32 = round_bf16(c32)
    z = np.empty(X.shape[0], np.float64)
    for s in range(0, X.shape[0], CHUNK):
        x = X[s:s + CHUNK]
        z[s:s + CHUNK] = (round_bf16(x) if low_precision else x) @ c32
    return z + float(intercept)


def value_and_gradient(X: np.ndarray, y: np.ndarray, coef: np.ndarray, intercept: float,
                       reg: float, low_precision: bool = False
                       ) -> Tuple[float, np.ndarray]:
    """(f, gradient) at (coef, intercept); the gradient is (d+1,), the
    intercept's entry last."""
    n = X.shape[0]
    coef = np.asarray(coef, np.float64)
    y = np.asarray(y, np.float64)
    z = logits(X, coef, intercept, low_precision)
    value = float((_softplus(z) - y * z).sum() / n + 0.5 * reg * (coef * coef).sum())
    r = _sigmoid(z) - y
    r32 = r.astype(np.float32)
    if low_precision:
        r32 = round_bf16(r32)
    g = np.zeros(X.shape[1], np.float64)
    for s in range(0, n, CHUNK):
        x = X[s:s + CHUNK]
        g += r32[s:s + CHUNK] @ (round_bf16(x) if low_precision else x)
    return value, np.append(g / n + reg * coef, r.sum() / n)


def newton_optimum(X: np.ndarray, y: np.ndarray, reg: float, tol: float = 1e-13,
                   max_steps: int = 100) -> Tuple[np.ndarray, float, float]:
    """(coef, intercept, f) at the optimum, by Newton steps halved until the
    objective falls, all in float64, until the gradient's norm is under `tol`."""
    X64 = np.hstack([np.asarray(X, np.float64), np.ones((X.shape[0], 1))])
    y = np.asarray(y, np.float64)
    n, d1 = X64.shape
    ridge = np.full(d1, float(reg))
    ridge[-1] = 0.0

    def value(p):
        z = X64 @ p
        return float((_softplus(z) - y * z).sum() / n + 0.5 * (ridge * p * p).sum())

    p = np.zeros(d1)
    f = value(p)
    for _ in range(max_steps):
        s = _sigmoid(X64 @ p)
        g = X64.T @ (s - y) / n + ridge * p
        if np.linalg.norm(g) < tol:
            break
        H = (X64 * (s * (1.0 - s))[:, None]).T @ X64 / n + np.diag(ridge)
        step = np.linalg.solve(H + 1e-14 * np.eye(d1), g)
        t = 1.0
        while value(p - t * step) > f and t > 1e-8:
            t *= 0.5
        p = p - t * step
        f = value(p)
    return p[:-1], float(p[-1]), f
