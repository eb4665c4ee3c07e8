#
# DBSCAN kernels — the TPU-native replacement for cuml.cluster.dbscan_mg.DBSCANMG
# (reference clustering.py:1018-1092: the whole dataset is broadcast to every worker
# (P3), cuML MG partitions the adjacency computation internally, rank 0 emits labels).
#
# TPU formulation:
#   * core-point detection: blocked pairwise-distance scan over row-sharded data
#     (an (block, n) matmul per block on the MXU), counting eps-neighbors,
#   * cluster formation = connected components of the core-core eps-graph, computed by
#     iterative min-label propagation with pointer jumping (O(log n) rounds, each one
#     blocked distance pass + a gather) — the XLA-friendly union-find,
#   * border points take the label of their minimum-label core neighbor; noise = -1,
#   * labels are finally compacted to 0..n_clusters-1 in first-appearance order
#     (cuML/sklearn convention).
#

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from .knn import _block_sq_dists
from ..observability.device import compiled_kernel


@compiled_kernel("dbscan.core_mask", static_argnames=("block",))
def _core_mask_xla(
    X: jax.Array, valid: jax.Array, eps2: float, min_samples: int, block: int = 512
) -> jax.Array:
    """Bool mask of core points (eps-neighbor count incl. self >= min_samples).
    The item-norm term is hoisted out of the per-block scan (computed once,
    not once per lax.map iteration — the selection-plane norm hoist)."""
    n = X.shape[0]
    pad = (-n) % block
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    x2 = jnp.sum(X * X, axis=1)

    def count_block(qb):
        d2 = _block_sq_dists(qb, X, x2)
        return jnp.sum((d2 <= eps2) & valid[None, :], axis=1)

    counts = jax.lax.map(count_block, Xp.reshape(-1, block, X.shape[1]))
    return (counts.reshape(-1)[:n] >= min_samples) & valid


def _core_mask(
    X: jax.Array, valid: jax.Array, eps2: float, min_samples: int, block: int = 512
) -> jax.Array:
    """Core-point detection, host wrapper (the PR-5 resolution contract):
    routes to the fused pallas distance+count scan (ops/pallas_select.py —
    the (block, n) distance tile never leaves VMEM, counts bit-identical)
    when `knn.selection` is `pallas_fused`, or under `auto` on TPU once n
    clears knn.pallas_min_items; XLA blocked scan otherwise."""
    from .pallas_select import fused_count_below, use_fused_count

    if use_fused_count(X.shape[0]):
        counts = fused_count_below(X, X, valid, eps2)
        return (counts >= min_samples) & valid
    return _core_mask_xla(X, valid, eps2, min_samples, block)


@compiled_kernel("dbscan.min_core_neighbor_labels",
                 static_argnames=("block",))
def _min_core_neighbor_labels(
    X: jax.Array, labels: jax.Array, core: jax.Array, eps2: float, block: int = 512
) -> jax.Array:
    """For every row: min label among its CORE eps-neighbors (int32 max if none)."""
    n = X.shape[0]
    pad = (-n) % block
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    big = jnp.iinfo(jnp.int32).max
    x2 = jnp.sum(X * X, axis=1)  # hoisted out of the per-block scan

    def min_label_block(qb):
        d2 = _block_sq_dists(qb, X, x2)
        neigh = (d2 <= eps2) & core[None, :]
        return jnp.min(jnp.where(neigh, labels[None, :], big), axis=1)

    mins = jax.lax.map(min_label_block, Xp.reshape(-1, block, X.shape[1]))
    return mins.reshape(-1)[:n]


@compiled_kernel("dbscan.hook_and_jump")
def _hook_and_jump(
    labels: jax.Array, mins: jax.Array, core: jax.Array
) -> jax.Array:
    """Hook: core points take the min neighbor label; then two pointer-jumping steps
    compress label chains (labels index rows)."""
    new_labels = jnp.where(core, jnp.minimum(labels, mins), labels)
    new_labels = new_labels[new_labels]
    new_labels = new_labels[new_labels]
    return new_labels


@compiled_kernel("dbscan.propagate_labels", static_argnames=("max_rounds",))
def _propagate_labels(
    X: jax.Array, core: jax.Array, eps2: float, max_rounds: int
) -> jax.Array:
    """Min-label propagation with pointer jumping as ONE on-device lax.while_loop.

    The previous host-driven loop dispatched each round separately and synced
    labels to host every 4 rounds for the convergence check — up to 64
    host<->device round trips per fit. On-device the convergence
    check (any label changed) runs every round for free and the whole
    propagation is a single dispatch."""
    n = X.shape[0]
    labels0 = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        _, r, changed = state
        return jnp.logical_and(r < max_rounds, changed)

    def body(state):
        labels, r, _ = state
        mins = _min_core_neighbor_labels(X, labels, core, eps2)
        new = _hook_and_jump(labels, mins, core)
        return new, r + 1, jnp.any(new != labels)

    labels, _, _ = jax.lax.while_loop(cond, body, (labels0, 0, jnp.bool_(True)))
    return labels


def dbscan_fit_predict(
    X: jax.Array,
    valid: jax.Array,
    eps: float,
    min_samples: int,
    max_rounds: int = 64,
    metric: str = "euclidean",
) -> np.ndarray:
    """Full DBSCAN labeling; returns int labels (noise = -1) for all rows
    (padding rows get -1).

    metric='cosine' reduces exactly to the euclidean scan on row-normalized data:
    for unit vectors ||a-b||^2 = 2(1 - cos(a,b)), so cosine distance <= eps is the
    squared-euclidean threshold 2*eps (the same reduction cuML's cosine DBSCAN
    applies; reference exposes it via the metric param, clustering.py)."""
    n = X.shape[0]
    if metric == "cosine":
        norms = jnp.linalg.norm(X, axis=1, keepdims=True)
        min_norm = float(jnp.min(jnp.where(valid[:, None], norms, jnp.inf)))
        if min_norm <= 0.0:
            raise ValueError(
                "Cosine distance is not defined for zero-length vectors; the input "
                "contains an all-zero feature row."
            )
        X = X / jnp.maximum(norms, 1e-30)
        eps2 = 2.0 * float(eps)
    else:
        eps2 = float(eps) * float(eps)
    core = _core_mask(X, valid, eps2, int(min_samples))
    labels = _propagate_labels(X, core, eps2, max_rounds)

    labels_h = np.asarray(labels)
    core_h = np.asarray(core)
    valid_h = np.asarray(valid)

    # border points: min-label core neighbor (one more pass)
    border_min = np.asarray(
        _min_core_neighbor_labels(X, jnp.asarray(labels_h), jnp.asarray(core_h), eps2)
    )
    out = np.full((n,), -1, dtype=np.int64)
    out[core_h] = labels_h[core_h]
    border = (~core_h) & valid_h & (border_min < np.iinfo(np.int32).max)
    out[border] = border_min[border]

    return _compact_labels(out)


def _compact_labels(out: np.ndarray) -> np.ndarray:
    """Compact labels to 0..k-1 in first-appearance order (sklearn/cuML
    convention), vectorized: order cluster representatives by their first row of
    appearance. Shared by the in-core and out-of-core (pairwise_streaming) paths."""
    n = out.shape[0]
    clustered = out >= 0
    if clustered.any():
        uniq, first_idx = np.unique(out[clustered], return_index=True)
        order = np.argsort(np.nonzero(clustered)[0][first_idx])
        rank = np.empty(len(uniq), dtype=np.int64)
        rank[order] = np.arange(len(uniq))
        final = np.full((n,), -1, dtype=np.int64)
        final[clustered] = rank[np.searchsorted(uniq, out[clustered])]
        return final
    return out
