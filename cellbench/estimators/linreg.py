"""LinearRegression (ridge, closed form): how a cell builds it, hands it a
label, and what its fit is held to.

**The label.** The harness times `estimator.fit(X)` on a bare host table, so
`build` returns an adapter whose `fit(X)` hands the program a `pyarrow.Table`:
a `FixedSizeList<float32>[d]` column that is a zero-copy view of that same
table and a float64 `label` column, as upstream's generator writes it (the
program's cast to float32 is part of every timed fit). The labels are
`y = X beta* + b* + eps` with beta* nonzero on half the columns, drawn from
N(0, 10^2) as the port's generator draws it (`benchmark/gen_data.py`), the
intercept `INTERCEPT`, and gaussian noise whose scale makes R^2 = `R2`. The
generator is seeded by the table's own first rows, so `--seed` fixes them;
they and the Arrow table are made once, by the first fit of set-up, and kept
at module level so that `check_fit` shares them.

**The comparison.** The fit is a direct solve, so the timed fit's own answer
is held to float64 (`linreg_ref.py`, every product of the host table in
float64):

* `fit_gradient_err`: the largest coordinate of the float64 gradient of the
  configuration's objective at the timed fit's (coefficients, intercept), the
  intercept's coordinate included, over the RMS coordinate of the gradient at
  zero. At the minimiser it is zero; what it reads is what the program's
  float32 pass and solve left. This is the decisive number;
* `coef_err`: the largest coordinate difference from the float64 minimiser,
  over the minimiser's RMS coordinate (the gradient's error magnified by the
  condition of the centred Gram);
* `objective_rel_err`: the float64 objective at the fit's coefficients against
  the float64 objective at the minimiser, relative.
"""

from __future__ import annotations

import sys
import time
import zlib
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .. import linreg_ref as ref
from ..refs import CHUNK
from .kmeans import distinct

ESTIMATOR = "LinearRegression"
MAX_DISTINCT = 3
COEF_SCALE = 10.0   # beta*'s informative coordinates are N(0, COEF_SCALE^2)
INTERCEPT = 25.0    # b*
R2 = 0.9            # the noise's share of the label's variance is 1 - R2

_TABLES: Dict[Tuple[int, Tuple[int, ...]], Tuple[Any, np.ndarray]] = {}
_STATS: Dict[Tuple[Tuple[int, Tuple[int, ...]], bool], Dict[str, Any]] = {}


def make_labels(X: np.ndarray) -> np.ndarray:
    """float64 labels of `X`'s rows, as the module docstring says. The
    generator is seeded by the table's first rows: the same table gives the
    same labels."""
    rng = np.random.default_rng([zlib.crc32(np.ascontiguousarray(X[:8]).tobytes()), 0x11E])
    d = X.shape[1]
    beta = np.zeros(d)
    beta[:d // 2] = rng.normal(scale=COEF_SCALE, size=d // 2)
    rng.shuffle(beta)
    beta32 = beta.astype(np.float32)
    z = np.concatenate([(X[s:s + CHUNK] @ beta32).astype(np.float64)
                        for s in range(0, X.shape[0], CHUNK)])
    noise = z.std() * np.sqrt((1.0 - R2) / R2)
    return z + INTERCEPT + rng.normal(scale=noise, size=X.shape[0])


def _key(X: np.ndarray):
    return X.__array_interface__["data"][0], X.shape


def labelled(X: np.ndarray, params: Dict[str, Any]):
    """(Arrow table, labels) of this host table, made once a table."""
    import pyarrow as pa

    key = _key(X)
    if key not in _TABLES:
        _TABLES.clear()  # one table a process: nothing holds the last one's labels
        _STATS.clear()
        y = make_labels(X)
        features = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), X.shape[1])
        _TABLES[key] = (pa.table({params["featuresCol"]: features,
                                  params["labelCol"]: pa.array(y)}), y)
    return _TABLES[key]


def table_stats(X: np.ndarray, y: np.ndarray, low_precision: bool = False) -> Dict[str, Any]:
    """`linreg_ref.stats` of this table, formed once a process."""
    key = (_key(X), low_precision)
    if key not in _STATS:
        t0 = time.perf_counter()
        _STATS[key] = ref.stats(X, y, low_precision)
        print(f"linreg_ref.stats low_precision={low_precision} "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return _STATS[key]


class _FitsArrow:
    """The program's estimator behind the harness's `fit(X)`."""

    def __init__(self, estimator, params: Dict[str, Any]):
        self.estimator, self.params = estimator, params

    def fit(self, X: np.ndarray):
        return self.estimator.fit(labelled(X, self.params)[0])


def build(params: Dict[str, Any], num_workers: int):
    from spark_rapids_ml_tpu.regression import LinearRegression

    return _FitsArrow(LinearRegression(num_workers=num_workers, **params), params)


def fit_outputs(model) -> Dict[str, Any]:
    a = model.get_model_attributes()
    return {"coefficients": np.asarray(a["coefficients"], np.float64).reshape(-1),
            "intercept": float(a["intercept"]), "n_iter": int(a["n_iter"])}


def did_all_work(outputs: Dict[str, Any], params: Dict[str, Any]) -> bool:
    """The closed form ran (one solve, no iteration) and gave finite numbers."""
    return (outputs["n_iter"] == 1 and bool(np.isfinite(outputs["coefficients"]).all())
            and bool(np.isfinite(outputs["intercept"])))


def normal_eq_work(rows: int, cols: int) -> Dict[str, float]:
    """The normal equations' statistics of an (rows, cols) float32 table and
    its labels: the Gram's `2*rows*cols**2` operations and Xᵀy's `2*rows*cols`,
    and one read of the table and the labels (compute-bound at 3000 columns:
    `work.gram_work`'s floor)."""
    return {"flops": 2.0 * rows * cols * cols + 2.0 * rows * cols,
            "bytes": rows * cols * 4.0 + rows * 4.0}


def kernel_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    return normal_eq_work(cfg["rows"], cfg["cols"])


def fit_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    """The pass and the direct solve's `(2/3)*cols**3`."""
    work = normal_eq_work(cfg["rows"], cfg["cols"])
    return {**work, "flops": work["flops"] + 2.0 / 3.0 * cfg["cols"] ** 3}


def check_fit(X: np.ndarray, answers: List[Dict[str, Any]],
              refit: Callable[[Dict[str, Any]], Any],
              params: Dict[str, Any], control: bool = False) -> List[Dict[str, float]]:
    """Every fit of the window that was kept (at most `MAX_DISTINCT` answers
    that differ are each given the reference's pass) against float64. With
    `control` the reference's own minimiser from bfloat16-rounded operands
    takes the program's place."""
    y = labelled(X, params)[1]
    reg = float(params["regParam"])
    standardization = bool(params.get("standardization", True))
    st = table_stats(X, y)
    dd = ref.penalty_scale(st, standardization)
    best, _, best_value = ref.minimiser(st, reg, standardization)
    grad0 = ref.gradient_at_zero(st)
    rms0 = float(np.sqrt(np.mean(grad0 * grad0)))
    rms_best = float(np.sqrt(np.mean(best * best)))
    if control:
        low, low_intercept, _ = ref.minimiser(table_stats(X, y, low_precision=True), reg,
                                              standardization)
        answers = [{"coefficients": low, "intercept": low_intercept, "n_iter": 1}]
    readings = []
    for outputs in distinct(answers, "coefficients")[:MAX_DISTINCT]:
        value, grad = ref.value_and_gradient(X, y, outputs["coefficients"],
                                             outputs["intercept"], reg, dd)
        readings.append({
            "fit_gradient_err": float(np.abs(grad).max() / rms0),
            "coef_err": float(np.abs(outputs["coefficients"] - best).max() / rms_best),
            "objective_rel_err": abs(value - best_value) / best_value,
        })
    return readings
