"""PCA: how a cell builds it and what its fit is held to.

The reference forms the covariance of the same host table in float64 and takes
its leading eigenpairs; the fit's mean, components, explained variance and the
total variance it implies (explained_variance / explained_variance_ratio) must
agree. The total variance is the number that separates float32 from one bf16
pass: rounding a value to 8 bits of mantissa adds its own variance, about
1.3e-6 of the value's square, to every diagonal entry of the Gram matrix, and
always with the same sign.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from .. import refs, work

ESTIMATOR = "PCA"


def build(params: Dict[str, Any], num_workers: int):
    from spark_rapids_ml_tpu.feature import PCA

    return PCA(num_workers=num_workers, **params)


def fit_outputs(model) -> Dict[str, Any]:
    a = model.get_model_attributes()
    return {k: np.asarray(a[k], np.float64) for k in
            ("mean", "components", "explained_variance", "explained_variance_ratio")}


def did_all_work(outputs: Dict[str, Any], params: Dict[str, Any]) -> bool:
    return outputs["components"].shape[0] == int(params["k"])


def fit_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    return work.gram_work(cfg["rows"], cfg["cols"])


kernel_work = fit_work


def _from_cov(mean: np.ndarray, cov: np.ndarray, k: int) -> Dict[str, Any]:
    lam, vec = refs.top_eigen(cov, k)
    return {"mean": mean, "components": vec, "explained_variance": lam,
            "explained_variance_ratio": lam / np.trace(cov)}


def check_fit(X: np.ndarray, answers: List[Dict[str, Any]],
              refit: Callable[[Dict[str, Any]], Any],
              params: Dict[str, Any], control: bool = False) -> List[Dict[str, float]]:
    """Every fit of the window that was kept against the float64 covariance's
    leading eigenpairs."""
    k = int(params["k"])
    mean, cov = refs.covariance(X)
    ref = _from_cov(mean, cov, k)
    if control:  # the reference in the program's place, one bf16 pass
        answers = [_from_cov(*refs.covariance(X, low_precision=True), k)]
    total_ref = float(np.trace(cov))
    lam, comp_ref = ref["explained_variance"], ref["components"]
    readings = []
    for outputs in answers:
        total = float(outputs["explained_variance"][0] / outputs["explained_variance_ratio"][0])
        comp = outputs["components"]
        sign = np.sign((comp * comp_ref).sum(axis=1))[:, None]
        readings.append({
            "explained_variance_rel_err":
                float(np.abs(outputs["explained_variance"] - lam).max() / lam[0]),
            "total_variance_rel_err": abs(total - total_ref) / total_ref,
            "components_err": float(np.abs(comp - sign * comp_ref).max()),
            "mean_err": float(np.abs(outputs["mean"] - mean).max()
                              / np.sqrt(total_ref / cov.shape[0])),
        })
    return readings
