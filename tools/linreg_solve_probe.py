"""Probe, outside any cell of BENCHMARK.json: what the normal equations of the
`linreg_l2_d3000` cell cost on the chip and how close each float32 solve of
them comes to float64. One JSON line a reading; refuses a CPU backend.

    python -m tools.linreg_solve_probe [rows cols]      # on a host with a TPU

Defaults: 357376 3000 (the cell's table, `cellbench/data.py`, seed 4200000001,
and its labels, `cellbench/estimators/linreg.py::make_labels`). The table is
put on the chip whole; the program's pass (`ops/linear.py::normal_equations`)
is timed (least of three, dispatch to ready) and its statistics compared with
the float64 ones of the host table (`cellbench/linreg_ref.py`). Then each
solve of those statistics at the cell's ridge 1e-5 is compiled and timed
(least of five) and its answer held to float64 by the largest coordinate of
the gradient at it over the RMS coordinate of the gradient at zero (the
cell's `fit_gradient_err`): `lu` (`jnp.linalg.solve`), `cholesky`
(`jax.scipy.linalg.cho_factor`/`cho_solve`, the program's `solve_l2`),
each also with one step of iterative refinement (`_refined`: the residual at
`highest`, solved again with the same factors), and `f64` (the float32
statistics solved in float64 on the host: the floor the statistics set).
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _emit(**row) -> None:
    print(json.dumps(row), flush=True)


def _least(fn, times: int) -> float:
    best = float("inf")
    for _ in range(times):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _solves(reg: float):
    from jax.scipy.linalg import cho_factor, cho_solve

    def lhs_rhs(S, r, n):
        return S / n + reg * jnp.eye(S.shape[0], dtype=S.dtype), r / n

    def refine(lhs, rhs, coef, solve):
        res = rhs - jnp.matmul(lhs, coef, precision=jax.lax.Precision.HIGHEST)
        return coef + solve(res)

    def lu(S, r, n):
        lhs, rhs = lhs_rhs(S, r, n)
        return jnp.linalg.solve(lhs, rhs)

    def lu_refined(S, r, n):
        lhs, rhs = lhs_rhs(S, r, n)
        lu_, piv, perm = jax.lax.linalg.lu(lhs)

        def solve(b):
            return jax.scipy.linalg.lu_solve((lu_, piv), b)

        return refine(lhs, rhs, solve(rhs), solve)

    def cholesky(S, r, n):
        lhs, rhs = lhs_rhs(S, r, n)
        return cho_solve(cho_factor(lhs), rhs)

    def cholesky_refined(S, r, n):
        lhs, rhs = lhs_rhs(S, r, n)
        factors = cho_factor(lhs)

        def solve(b):
            return cho_solve(factors, b)

        return refine(lhs, rhs, solve(rhs), solve)

    return {"lu": lu, "lu_refined": lu_refined, "cholesky": cholesky,
            "cholesky_refined": cholesky_refined}


def main(argv) -> int:
    rows, cols = (int(argv[0]), int(argv[1])) if len(argv) >= 2 else (357376, 3000)
    if jax.devices()[0].platform != "tpu" and "--cpu" not in argv:
        print("refusing: no TPU", file=sys.stderr)
        return 2
    from cellbench import data
    from cellbench import linreg_ref as ref
    from cellbench.estimators import linreg as family
    from spark_rapids_ml_tpu.ops._precision import pdot
    from spark_rapids_ml_tpu.ops.linear import normal_equations

    with open("cellbench/configs/linreg_l2_d3000.json") as f:
        cfg = json.load(f)
    reg = float(cfg["params"]["regParam"])
    t0 = time.perf_counter()
    X, _ = data.make_table(cfg["table"], rows, cols, 4200000001, jax.devices()[:1])
    y = family.make_labels(X)
    _emit(part="table", rows=rows, cols=cols, seconds=time.perf_counter() - t0)
    Xd, yd = jax.device_put(X), jax.device_put(y.astype(np.float32))
    wd = jnp.ones((rows,), jnp.float32)
    jax.block_until_ready((Xd, yd, wd))

    def run_pass():
        return normal_equations(Xd, yd, wd)

    t0 = time.perf_counter()
    S, r, xbar, ybar, n = jax.block_until_ready(run_pass())
    first = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}  # noqa: fence/device-analysis-off-plane (a probe)
    _emit(part="pass", first_s=first, least_s=_least(run_pass, 3),
          peak_bytes=int(stats.get("peak_bytes_in_use", 0)))

    t0 = time.perf_counter()
    st = ref.stats(X, y)
    host_s = time.perf_counter() - t0
    best, _, _ = ref.minimiser(st, reg)
    g0 = ref.gradient_at_zero(st)
    rms0 = float(np.sqrt(np.mean(g0 * g0)))
    S64, r64 = np.asarray(S, np.float64), np.asarray(r, np.float64)
    _emit(part="stats", host_float64_s=host_s,
          S_err=float(np.abs(S64 - st["S"]).max() / np.abs(st["S"]).max()),
          S_diag_err=float(np.abs(np.diagonal(S64) / np.diagonal(st["S"]) - 1.0).max()),
          r_err=float(np.abs(r64 - st["r"]).max() / np.abs(st["r"]).max()),
          xbar_err=float(np.abs(np.asarray(xbar, np.float64) - st["xbar"]).max()),
          ybar_err=abs(float(ybar) - st["ybar"]))

    def held(coef, intercept):
        _, grad = ref.value_and_gradient(X, y, coef, intercept, reg)
        return {"fit_gradient_err": float(np.abs(grad).max() / rms0),
                "coef_err": float(np.abs(coef - best).max() / np.sqrt(np.mean(best * best)))}

    nf = float(n)
    coef = np.linalg.solve(S64 / nf + reg * np.eye(cols), r64 / nf)
    _emit(part="solve", form="f64",
          **held(coef, float(ybar) - np.asarray(xbar, np.float64) @ coef))
    for name, solve in _solves(reg).items():
        compiled = jax.jit(lambda S_, r_, n_, xb, yb, solve=solve: (
            lambda c: (c, yb - pdot(xb, c)))(solve(S_, r_, n_)))
        t0 = time.perf_counter()
        coef, intercept = jax.block_until_ready(compiled(S, r, n, xbar, ybar))
        first = time.perf_counter() - t0
        least = _least(lambda: compiled(S, r, n, xbar, ybar), 5)
        _emit(part="solve", form=name, first_s=first, least_s=least,
              **held(np.asarray(coef, np.float64), float(intercept)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
