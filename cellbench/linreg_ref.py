"""Plain numpy reference for ridge linear regression. Nothing here imports the
program or JAX.

The objective `cellbench/configs/linreg_l2_d3000.json` states, the one
`spark_rapids_ml_tpu/ops/linear.py` minimises:

    f(beta, b) = (1/2n) sum_i (y_i - x_i . beta - b)^2 + (lambda/2) |D beta|^2,

the intercept b unpenalised, D the identity, or under `standardization` the
columns' unbiased standard deviations (the penalty on the scaled
coefficients).

Everything is float64: each `CHUNK`-row chunk of the host table is cast to
float64 before any product, and every sum across chunks is float64.

* `value_and_gradient`: f and its gradient at a point, in ONE pass (a chunk's
  residual `X beta + b - y`, then `X' r` from the same chunk);
* `minimiser`: the exact minimiser. The weighted means first, then the
  centred Gram `sum (x - xbar)(x - xbar)'` and `sum (x - xbar)(y - ybar)`, then
  a Cholesky solve of `(S/n + lambda D^2) beta = r/n`, `b = ybar - xbar . beta`.
  `low_precision=True` is the controls' arithmetic: the same from
  bfloat16-rounded operands, as one MXU pass would form the products.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from .refs import CHUNK, round_bf16


def _chunks(X: np.ndarray, y: np.ndarray, low_precision: bool = False):
    for s in range(0, X.shape[0], CHUNK):
        x, ys = X[s:s + CHUNK], np.asarray(y[s:s + CHUNK], np.float64)
        if low_precision:
            x, ys = round_bf16(x), round_bf16(ys.astype(np.float32)).astype(np.float64)
        yield x.astype(np.float64), ys


def stats(X: np.ndarray, y: np.ndarray, low_precision: bool = False) -> Dict[str, object]:
    """The centred statistics of the table: n, x̄, ȳ, S = Σ(x-x̄)(x-x̄)ᵀ,
    r = Σ(x-x̄)(y-ȳ), Syy = Σ(y-ȳ)², two passes over the host table."""
    n, d = X.shape
    sx = np.zeros(d)
    for s in range(0, n, CHUNK):
        x = round_bf16(X[s:s + CHUNK]) if low_precision else X[s:s + CHUNK]
        sx += x.sum(axis=0, dtype=np.float64)
    yv = np.asarray(y, np.float64)
    if low_precision:
        yv = round_bf16(yv.astype(np.float32)).astype(np.float64)
    xbar, ybar = sx / n, float(yv.sum()) / n
    S, r, syy = np.zeros((d, d)), np.zeros(d), 0.0
    for x, ys in _chunks(X, y, low_precision):
        x -= xbar
        ys = ys - ybar
        S += x.T @ x
        r += ys @ x
        syy += ys @ ys
    return {"n": n, "xbar": xbar, "ybar": ybar, "S": S, "r": r, "syy": syy}


def penalty_scale(st: Dict[str, object], standardization: bool) -> np.ndarray:
    """D of the module header: ones, or the unbiased column standard
    deviations (a constant column keeps 1)."""
    if not standardization:
        return np.ones(len(st["xbar"]))
    sd = np.sqrt(np.diagonal(st["S"]) / max(st["n"] - 1, 1))
    return np.where(sd > 0, sd, 1.0)


def minimiser(st: Dict[str, object], reg: float, standardization: bool = False
              ) -> Tuple[np.ndarray, float, float]:
    """(coef, intercept, f) at the exact minimiser of the statistics `st`."""
    from scipy.linalg import cho_factor, cho_solve

    n = st["n"]
    dd = penalty_scale(st, standardization)
    lhs = st["S"] / n + reg * np.diag(dd * dd)
    coef = cho_solve(cho_factor(lhs), st["r"] / n)
    intercept = float(st["ybar"] - st["xbar"] @ coef)
    # at the optimal intercept: f = (Syy - 2 beta'r + beta'S beta)/2n + penalty
    value = float((st["syy"] - 2.0 * coef @ st["r"] + coef @ st["S"] @ coef) / (2.0 * n)
                  + 0.5 * reg * np.sum((dd * coef) ** 2))
    return coef, intercept, value


def value_and_gradient(X: np.ndarray, y: np.ndarray, coef: np.ndarray, intercept: float,
                       reg: float, dd: np.ndarray = None) -> Tuple[float, np.ndarray]:
    """(f, gradient) at (coef, intercept); the gradient is (d+1,), the
    intercept's entry last. `dd` is D of the module header (ones if None)."""
    n = X.shape[0]
    coef = np.asarray(coef, np.float64)
    dd = np.ones_like(coef) if dd is None else dd
    g, sse, rsum = np.zeros_like(coef), 0.0, 0.0
    for x, ys in _chunks(X, y):
        res = x @ coef + float(intercept) - ys
        g += res @ x
        sse += res @ res
        rsum += res.sum()
    value = float(sse / (2.0 * n) + 0.5 * reg * np.sum((dd * coef) ** 2))
    return value, np.append(g / n + reg * dd * dd * coef, rsum / n)


def gradient_at_zero(st: Dict[str, object]) -> np.ndarray:
    """The gradient at (0, 0) from the statistics: -(Xᵀy/n, ȳ), with
    Xᵀy = r + n x̄ ȳ."""
    n = st["n"]
    return -np.append((st["r"] + n * st["xbar"] * st["ybar"]) / n, st["ybar"])
