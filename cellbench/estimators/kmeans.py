"""KMeans: how a cell builds it, what it must have done, what it is held to.

The comparison (see PERF.md, "How correct is decided here"): Lloyd's result
after `maxIter` steps cannot be reproduced from outside, because the k-means||
start is random by design. What can be: ONE exact Lloyd step. The harness fits
again through the public path with `maxIter - 1` (same seed, same start, so the
same first `maxIter - 1` iterates), the reference takes that fit's centres one
exact step further, and the timed fit's centres must land there. Inertia and
cluster sizes are recomputed from the timed fit's own centres.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import numpy as np

from .. import refs, work

ESTIMATOR = "KMeans"
MAX_DISTINCT = 3


def build(params: Dict[str, Any], num_workers: int):
    from spark_rapids_ml_tpu.clustering import KMeans

    return KMeans(num_workers=num_workers, **params)


def fit_outputs(model) -> Dict[str, Any]:
    return {
        "centers": np.asarray(model.cluster_centers_),
        "inertia": float(model.inertia_),
        "sizes": np.asarray(model.summary.clusterSizes, np.int64),
        "n_iter": int(model.summary.numIter),
    }


def did_all_work(outputs: Dict[str, Any], params: Dict[str, Any]) -> bool:
    """Fixed work per fit: every one of `maxIter` iterations ran."""
    return outputs["n_iter"] == int(params["maxIter"])


def fit_work(cfg: Dict[str, Any]) -> Dict[str, float]:
    p = cfg["params"]
    return work.lloyd_work(cfg["rows"], cfg["cols"], int(p["k"]), int(p["maxIter"]))


kernel_work = fit_work


def distinct(answers: List[Dict[str, Any]], key: str) -> List[Dict[str, Any]]:
    """The answers that differ in `key`, the last first: the same table and
    seed give the same answer, so the reference is as a rule asked once."""
    seen, out = set(), []
    for a in reversed(answers):
        mark = np.asarray(a[key]).tobytes()
        if mark not in seen:
            seen.add(mark)
            out.append(a)
    return out


def check_fit(X: np.ndarray, answers: List[Dict[str, Any]],
              refit: Callable[[Dict[str, Any]], Any],
              params: Dict[str, Any], control: bool = False) -> List[Dict[str, float]]:
    """Every fit of the window that was kept, against one exact Lloyd step
    from the `maxIter - 1` fit's centres (at most `MAX_DISTINCT` answers that
    differ are each given the reference's assignment pass)."""
    before = np.asarray(refit({"maxIter": int(params["maxIter"]) - 1}).cluster_centers_)
    step = refs.lloyd_step(X, before)
    scale = float(np.sqrt((step * step).mean()))
    if control:  # the reference in the program's place, one bf16 pass
        centers = refs.lloyd_step(X, before, low_precision=True).astype(np.float32)
        lab, inertia, _, _ = refs.assign(X, centers, low_precision=True)
        answers = [{"centers": centers, "inertia": inertia,
                    "sizes": np.bincount(lab, minlength=len(centers))}]
    readings = []
    for outputs in distinct(answers, "centers")[:MAX_DISTINCT]:
        centers = np.asarray(outputs["centers"], np.float64)
        labels, inertia_ref, _, _ = refs.assign(X, outputs["centers"])
        sizes_ref = np.bincount(labels, minlength=len(centers))
        same = [a for a in answers if np.array_equal(a["centers"], outputs["centers"])]
        readings.append({
            "center_step_err": float(np.abs(centers - step).max() / scale),
            "inertia_rel_err": max(abs(a["inertia"] - inertia_ref) for a in same) / inertia_ref,
            "sizes_mismatch_share": max(float(np.abs(a["sizes"] - sizes_ref).sum())
                                        for a in same) / (2.0 * len(X)),
        })
    return readings


def check_transform(X: np.ndarray, model, frames: List[Any], params: Dict[str, Any],
                    control: bool = False) -> List[Dict[str, float]]:
    """The `prediction` column of every frame kept, against the exact nearest
    centre of every row."""
    centers = np.asarray(model.cluster_centers_)
    labels_ref, _, _, _ = refs.assign(X, centers)
    if control:
        columns = [refs.assign(X, centers, low_precision=True)[0]]
    else:
        columns = [f[model.getOrDefault("predictionCol")].to_numpy() for f in frames]
    return [{"label_mismatch_share": float((labels != labels_ref).mean())
             if labels.shape == labels_ref.shape else 1.0} for labels in columns]
