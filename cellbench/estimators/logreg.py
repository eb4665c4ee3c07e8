"""LogisticRegression (binary, ridge): how a cell builds it, hands it a label,
and what its fit is held to.

**The label.** The harness times `estimator.fit(X)` on a bare host table, so
`build` returns an adapter whose `fit(X)` hands the program a `pyarrow.Table`:
a `FixedSizeList<float32>[d]` column that is a zero-copy view of that same
table and a float32 `label` column, the program's normal ingest path
(`core/dataset.py::_extract_arrow`, the path a Spark executor's Arrow batches
take). The labels are Bernoulli draws of a logistic model over ALL columns,
from a generator seeded by the table's own first rows, so `--seed` fixes them;
they and the Arrow table are made once, by the first fit of set-up, and kept
at module level so that `refit` and `check_fit` share them.

**The comparison.** An L-BFGS iterate after a fixed budget cannot be
reproduced from outside (every line search's branch follows the last bits),
and `tol=1e-30` asks for the budget, not for the optimum. What can be held to
float64 (`logreg_ref.py`, two passes over the host table a point):

* `objective_rel_err`: the objective the model reports against the
  reference's at the model's own coefficients;
* `fit_gradient_err`: the gradient the TIMED fit reports at its own last
  iterate (the model's `gradient`: what the loop's line search formed at the
  accepted point and would have used next, so it is a number of the window's
  own executable) against the reference's at the model's coefficients: largest
  coordinate difference over the RMS coordinate of the gradient at zero. Near
  the optimum the gradient is small but the sums it is made of are not, so the
  arithmetic's error is what it is at zero. This is the number that holds the
  timed loop's reads of the table to float32; a program that reports no
  gradient reads 1, as if it had reported none of it;
* `first_step_err`: one L-BFGS step from zero is a positive multiple of the
  negative gradient at zero whatever the implementation. The harness fits again
  through the public path with `maxIter=1`; the unit vector of that model's
  (coefficients, intercept) against the reference's: largest coordinate
  difference over the RMS coordinate. This holds the gradient's arithmetic,
  the label's path and the intercept to float32: the KMeans cells' "one exact
  step";
* `grad_norm_rel`: the gradient's norm at the timed fit's coefficients over
  its norm at zero, both in float64: how far the budget went toward the one
  stationary point a strongly convex objective has.

The optimum itself is compared where that is cheap: in tier-1, at small size
(`tests/test_logreg_reference.py`).
"""

from __future__ import annotations

import zlib
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from .. import logreg_ref as ref
from ..refs import CHUNK
from .kmeans import distinct

ESTIMATOR = "LogisticRegression"
MAX_DISTINCT = 3
LOGIT_SPREAD = 2.5   # standard deviation of the true model's logits: not separable
LOGIT_SHIFT = 0.25   # their mean: the classes are not balanced to the row

_TABLES: Dict[Tuple[int, Tuple[int, ...]], Tuple[Any, np.ndarray]] = {}


def make_labels(X: np.ndarray) -> np.ndarray:
    """float32 0/1 labels of `X`'s rows: Bernoulli of sigmoid(x . beta* + b*),
    beta* gaussian over every column and scaled so that the logits have
    standard deviation `LOGIT_SPREAD` and mean `LOGIT_SHIFT`. The generator is
    seeded by the table's first rows: the same table gives the same labels."""
    rng = np.random.default_rng([zlib.crc32(np.ascontiguousarray(X[:8]).tobytes()), 0x1AB])
    beta = rng.standard_normal(X.shape[1]).astype(np.float32)
    z = np.concatenate([(X[s:s + CHUNK] @ beta).astype(np.float64)
                        for s in range(0, X.shape[0], CHUNK)])
    z = (z - z.mean()) * (LOGIT_SPREAD / z.std()) + LOGIT_SHIFT
    return (rng.random(X.shape[0]) < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)


def labelled(X: np.ndarray, params: Dict[str, Any]):
    """(Arrow table, labels) of this host table, made once a table."""
    import pyarrow as pa

    key = (X.__array_interface__["data"][0], X.shape)
    if key not in _TABLES:
        _TABLES.clear()  # one table a process: nothing holds the last one's labels
        y = make_labels(X)
        features = pa.FixedSizeListArray.from_arrays(pa.array(X.reshape(-1)), X.shape[1])
        _TABLES[key] = (pa.table({params["featuresCol"]: features,
                                  params["labelCol"]: pa.array(y)}), y)
    return _TABLES[key]


class _FitsArrow:
    """The program's estimator behind the harness's `fit(X)`."""

    def __init__(self, estimator, params: Dict[str, Any]):
        self.estimator, self.params = estimator, params

    def fit(self, X: np.ndarray):
        return self.estimator.fit(labelled(X, self.params)[0])


def build(params: Dict[str, Any], num_workers: int):
    from spark_rapids_ml_tpu.classification import LogisticRegression

    return _FitsArrow(LogisticRegression(num_workers=num_workers, **params), params)


def fit_outputs(model) -> Dict[str, Any]:
    a = model.get_model_attributes()
    gradient = a.get("gradient")
    return {"coefficients": np.asarray(a["coefficients"], np.float64)[0],
            "intercept": float(a["intercepts"][0]),
            "objective": float(a["objective"]), "n_iter": int(a["n_iter"]),
            "gradient": None if gradient is None else np.asarray(gradient, np.float64)[0]}


def did_all_work(outputs: Dict[str, Any], params: Dict[str, Any]) -> bool:
    """Fixed work per fit: every one of `maxIter` iterations ran."""
    return outputs["n_iter"] == int(params["maxIter"])


def qn_work(rows: int, cols: int, n_iter: int) -> Dict[str, float]:
    """A quasi-Newton fit's iterations over an (rows, cols) float32 table: ONE
    read of the table an iteration (a fused evaluate-and-accumulate kernel
    forms the logits and the gradient of a row block while it is resident, and
    a line search that accepts its first step evaluates once an iteration),
    and the two matrix-vector products' `4*rows*cols` operations. The (rows,)
    vectors and the solver's history are not counted."""
    return {"flops": float(n_iter) * 4.0 * rows * cols,
            "bytes": float(n_iter) * rows * cols * 4.0}


def fit_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    return qn_work(cfg["rows"], cfg["cols"], int(cfg["params"]["maxIter"]))


kernel_work = fit_work


def check_fit(X: np.ndarray, answers: List[Dict[str, Any]],
              refit: Callable[[Dict[str, Any]], Any],
              params: Dict[str, Any], control: bool = False) -> List[Dict[str, float]]:
    """Every fit of the window that was kept (at most `MAX_DISTINCT` answers
    that differ are each given the reference's two passes), and one step from
    zero through the public path, against float64."""
    y = labelled(X, params)[1]
    reg = float(params["regParam"])
    zero = np.zeros(X.shape[1])
    _, grad0 = ref.value_and_gradient(X, y, zero, 0.0, reg)
    if control:  # the reference in the program's place, one bf16 pass: its own
        # step from zero, and its own objective and gradient where the timed
        # fit stopped
        _, low0 = ref.value_and_gradient(X, y, zero, 0.0, reg, low_precision=True)
        first = -low0
        last = answers[-1]
        low, low_grad = ref.value_and_gradient(X, y, last["coefficients"], last["intercept"],
                                               reg, low_precision=True)
        answers = [{**last, "objective": low, "gradient": low_grad}]
    else:
        one = fit_outputs(refit({"maxIter": 1}))
        first = np.append(one["coefficients"], one["intercept"])
    step_err = float(np.abs(first / np.linalg.norm(first) + grad0 / np.linalg.norm(grad0)).max()
                     * np.sqrt(grad0.size))
    rms0 = float(np.sqrt(np.mean(grad0 * grad0)))
    readings = []
    for outputs in distinct(answers, "coefficients")[:MAX_DISTINCT]:
        value, grad = ref.value_and_gradient(X, y, outputs["coefficients"],
                                             outputs["intercept"], reg)
        same = [a for a in answers
                if np.array_equal(a["coefficients"], outputs["coefficients"])]
        readings.append({
            "objective_rel_err": max(abs(a["objective"] - value) for a in same) / value,
            "fit_gradient_err": max(
                1.0 if a["gradient"] is None else float(np.abs(a["gradient"] - grad).max() / rms0)
                for a in same),
            "first_step_err": step_err,
            "grad_norm_rel": float(np.linalg.norm(grad) / np.linalg.norm(grad0)),
        })
    return readings
