#
# Exact and approximate k-NN estimators (L6 API) — the reference's
# spark_rapids_ml.knn surface (reference python/src/spark_rapids_ml/knn.py):
#   * NearestNeighbors: fit() just captures the item set (reference knn.py:347-367 —
#     no compute), kneighbors() runs the distributed all-to-all search (stack §3.4)
#   * exactNearestNeighborsJoin: flattened (query, item, distance) join
#     (reference knn.py:435-482)
#   * ApproximateNearestNeighbors: IVF-Flat per-device index + probe search
#     (reference knn.py:838-1723 wraps cuVS ivf_flat/ivf_pq/cagra)
#   * neither is persistable, matching the reference (knn.py:384-408)
#

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

from ..core.backend_params import HasIDCol, _TpuClass
from ..core.dataset import extract_feature_data
from ..core.estimator import FitInputs, _TpuEstimator, _TpuModel
from ..core.params import Param, TypeConverters
from ..core.backend_params import DictTypeConverters, HasFeaturesCols
from ..core.params import HasInputCol
from ..parallel.partitioner import active_partitioner
from ..parallel.partition import pad_rows
from ..ops.knn import (
    exact_knn_distributed,
    ivfflat_build,
    ivfflat_search,
    ivfpq_build,
    ivfpq_search,
)
from ..utils import get_logger

# query sets at/above this row count switch exact kNN from the all_gather merge to
# the ring-permute path (queries stay sharded; ops/knn.exact_knn_ring)
_RING_QUERY_THRESHOLD = 65536


def _ap(algo_params: Dict[str, Any], *names: str, default: Any) -> Any:
    """First present key among the accepted spellings — cuML and cuVS names are both
    honored, like the reference's translation table (knn.py:1324-1404)."""
    for n in names:
        if n in algo_params:
            return algo_params[n]
    return default


def _normalize_or_raise(X, w):
    """Row-normalize for cosine metrics; zero-norm REAL rows raise (Spark/cuML
    cosine semantics). Works on jax arrays; padding rows (w==0) are exempt."""
    import jax.numpy as jnp

    norms = jnp.linalg.norm(X, axis=1, keepdims=True)
    min_norm = float(jnp.min(jnp.where(jnp.asarray(w)[:, None] > 0, norms, jnp.inf)))
    if min_norm <= 0.0:
        raise ValueError(
            "Cosine distance is not defined for zero-length vectors; the input "
            "contains an all-zero feature row."
        )
    return X / jnp.maximum(norms, 1e-30)


class _NNParams(HasInputCol, HasFeaturesCols, HasIDCol):
    k: Param[int] = Param(
        "undefined", "k", "number of nearest neighbors to retrieve (> 0).",
        TypeConverters.toInt,
    )

    def getK(self) -> int:
        return self.getOrDefault("k")

    def setK(self, value: int):
        return self._set_params(k=value)

    def setInputCol(self, value: str):
        return self._set(inputCol=value)


class _NearestNeighborsClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        return {"k": "n_neighbors", "inputCol": "", "featuresCols": "", "idCol": ""}

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {"n_neighbors": 5}


class NearestNeighbors(_NearestNeighborsClass, _TpuEstimator, _NNParams):
    """Exact k-NN: fit stores the item set; kneighbors runs the sharded all-to-all
    search over the mesh (reference knn.py:76-835)."""

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(k=5)
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def _out_schema(self) -> List[str]:
        return []

    def _get_tpu_fit_func(self, extra_params=None):
        raise NotImplementedError("NearestNeighbors.fit stores data; no kernel runs.")

    def _create_pyspark_model(self, attrs: Dict[str, Any]) -> "NearestNeighborsModel":
        return NearestNeighborsModel(**attrs)

    def _fit(self, dataset: Any) -> "NearestNeighborsModel":
        # no heavy compute at fit time (reference knn.py:347-367) — but the
        # item-norm term Σ X² IS computed once here and cached on the model,
        # so no kneighbors query block ever recomputes it (selection-plane
        # norm hoist; a refit builds a fresh model, which IS the invalidation)
        from ..ops.knn import center_norms_sq

        dataset = self._ensureIdCol(dataset)
        fd = self._pre_process_data(dataset)
        items = np.asarray(fd.features)
        model = NearestNeighborsModel(
            item_features=items,
            item_ids=(
                fd.row_id
                if fd.row_id is not None
                else np.arange(fd.n_rows, dtype=np.int64)
            ),
            item_norms_sq=center_norms_sq(items),
            item_df=dataset,
        )
        model._num_workers = self._num_workers
        self._copyValues(model)
        return model

    def write(self):
        raise NotImplementedError(
            "NearestNeighbors is not persistable (reference knn.py:384-408)."
        )


class NearestNeighborsModel(_NearestNeighborsClass, _TpuModel, _NNParams):
    def __init__(
        self,
        item_features: np.ndarray,
        item_ids: np.ndarray,
        item_df: Any = None,
        item_norms_sq: "np.ndarray | None" = None,
        item_valid: "np.ndarray | None" = None,
    ) -> None:
        attrs = dict(item_features=item_features, item_ids=item_ids)
        if item_norms_sq is not None:
            # cached Σ X² — searched-for with .get() so directly-constructed
            # models (no fit) still work, just without the hoisted norm
            attrs["item_norms_sq"] = np.asarray(item_norms_sq)
        if item_valid is not None:
            # incremental tier (docs/design.md §7b): rows are laid out in a
            # BUCKETED capacity and this mask carries live/tombstoned/slack;
            # absent on fresh fits, so the non-incremental paths are unchanged
            attrs["item_valid"] = np.asarray(item_valid, bool)
        super().__init__(**attrs)
        self._item_df = item_df
        self._tombstones = 0
        self._item_fill = None  # high-water slot count (incremental tier)
        self._setDefault(k=5)

    # ---- persistence (ANN index store, docs/design.md §7b) ----

    def _ann_index_spec(self):
        """Arrays persisted through the versioned mmap-friendly index format
        (ops/ann_lifecycle.py) instead of arrays.npz."""
        arrays = {
            n: np.asarray(self._model_attributes[n])
            for n in ("item_features", "item_ids", "item_norms_sq",
                      "item_valid")
            if self._model_attributes.get(n) is not None
        }
        return arrays, "exact", {"tombstones": int(self._tombstones)}

    @classmethod
    def _from_row(cls, attrs: Dict[str, Any]) -> "NearestNeighborsModel":
        manifest = attrs.pop("__ann_manifest__", None)
        model = cls(**attrs)
        if manifest is not None:
            model._tombstones = int(
                (manifest.get("meta") or {}).get("tombstones", 0)
            )
        return model

    # ---- incremental add/delete (docs/design.md §7b) ----

    def _live_mask(self) -> np.ndarray:
        valid = self._model_attributes.get("item_valid")
        if valid is None:
            return np.ones(
                (len(self._model_attributes["item_features"]),), bool
            )
        return np.asarray(valid, bool)

    def enable_incremental(self, capacity_rows: int = 0) -> int:
        """Re-lay the item set into a BUCKETED row capacity (power of two >=
        the live count, optionally >= capacity_rows) with an explicit valid
        mask. Paying this single shape change BEFORE the model is served is
        what makes later add/delete calls compile-free: every search
        executable's operand shapes stay fixed while the slack absorbs adds.
        Returns the capacity."""
        from ..ops.ann_lifecycle import bucket_capacity

        a = self._model_attributes
        items = np.asarray(a["item_features"], np.float32)
        n = len(items)
        cap = bucket_capacity(max(n, int(capacity_rows)))
        if a.get("item_valid") is not None and cap <= len(items):
            return len(items)  # already bucketed at (or past) this capacity
        grown = np.zeros((cap, items.shape[1]), np.float32)
        grown[:n] = items
        ids = np.full((cap,), -1, np.int64)
        ids[:n] = np.asarray(a["item_ids"], np.int64)
        valid = np.zeros((cap,), bool)
        valid[:n] = self._live_mask()[:n]
        x2 = np.zeros((cap,), np.float32)
        x2n = a.get("item_norms_sq")
        from ..ops.knn import center_norms_sq

        x2[:n] = np.asarray(x2n) if x2n is not None else center_norms_sq(items)
        a.update(
            item_features=grown, item_ids=ids, item_valid=valid,
            item_norms_sq=x2,
        )
        self._item_fill = n
        return cap

    def add_items(self, X_new: np.ndarray, ids: "np.ndarray | None" = None
                  ) -> np.ndarray:
        """Append items (reusing tombstoned slots first, then slack; the
        capacity bucket grows only when both run out — the amortized shape
        change in-slack adds avoid). Returns the user ids assigned."""
        from ..observability.runs import counter_inc as _counter_inc
        from ..ops.ann_lifecycle import bucket_capacity
        from ..ops.knn import center_norms_sq

        a = self._model_attributes
        if a.get("item_valid") is None:
            self.enable_incremental()
        X_new = np.ascontiguousarray(np.asarray(X_new), np.float32)
        m = len(X_new)
        valid = np.asarray(a["item_valid"], bool)
        item_ids = np.asarray(a["item_ids"], np.int64)
        if ids is None:
            base = int(item_ids.max(initial=-1)) + 1
            ids = np.arange(base, base + m, dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64)
        if self._item_fill is None:
            # high-water reconstruction: one past the last live slot
            self._item_fill = (
                int(len(valid) - np.argmax(valid[::-1])) if valid.any() else 0
            )
        fill = int(self._item_fill)
        holes = np.nonzero(~valid[:fill])[0][:m]
        n_virgin = m - len(holes)
        if fill + n_virgin > len(valid):
            cap = bucket_capacity(fill + n_virgin)
            self.enable_incremental(capacity_rows=cap)
            valid = np.asarray(a["item_valid"], bool)
            item_ids = np.asarray(a["item_ids"], np.int64)
        slots = np.concatenate(
            [holes, np.arange(fill, fill + n_virgin)]
        ).astype(np.int64)
        items = np.asarray(a["item_features"])
        items[slots] = X_new
        item_ids[slots] = ids
        valid[slots] = True
        np.asarray(a["item_norms_sq"])[slots] = center_norms_sq(X_new)
        a.update(item_features=items, item_ids=item_ids, item_valid=valid)
        self._item_fill = fill + n_virgin
        self._tombstones = max(self._tombstones - len(holes), 0)
        _counter_inc("ann.items_added", m)
        return ids

    def delete_items(self, ids: np.ndarray) -> int:
        """Tombstone items by user id: their valid-mask entries flip False —
        the search kernels mask them to INVALID_D2, so no shape or kernel
        changes. Compaction (tombstones past `ann.compact_tombstone_pct` of
        occupied rows) repacks the live rows into a possibly smaller bucket."""
        from ..observability.runs import counter_inc as _counter_inc
        from ..ops.ann_lifecycle import resolve_compact_tombstone_pct

        a = self._model_attributes
        if a.get("item_valid") is None:
            self.enable_incremental()
        valid = np.asarray(a["item_valid"], bool)
        item_ids = np.asarray(a["item_ids"], np.int64)
        hit = np.isin(item_ids, np.asarray(ids, np.int64)) & valid
        n = int(hit.sum())
        if n == 0:
            return 0
        valid[hit] = False
        item_ids[hit] = -1
        a.update(item_ids=item_ids, item_valid=valid)
        self._tombstones += n
        _counter_inc("ann.items_deleted", n)
        occupied = int(valid.sum()) + self._tombstones
        if occupied and (
            100 * self._tombstones
            > resolve_compact_tombstone_pct() * occupied
        ):
            self.compact_items()
        return n

    def compact_items(self) -> None:
        """Repack live rows (dropping tombstoned slots) into a fresh bucketed
        capacity. Changes shapes — a served model must be refreshed after."""
        from ..observability.runs import counter_inc as _counter_inc
        from ..ops.ann_lifecycle import bucket_capacity

        a = self._model_attributes
        valid = self._live_mask()
        live = np.nonzero(valid)[0]
        cap = bucket_capacity(max(len(live), 1))
        items = np.zeros((cap, np.asarray(a["item_features"]).shape[1]),
                         np.float32)
        items[: len(live)] = np.asarray(a["item_features"])[live]
        ids = np.full((cap,), -1, np.int64)
        ids[: len(live)] = np.asarray(a["item_ids"])[live]
        x2 = np.zeros((cap,), np.float32)
        x2_src = a.get("item_norms_sq")
        if x2_src is not None:
            x2[: len(live)] = np.asarray(x2_src)[live]
        new_valid = np.zeros((cap,), bool)
        new_valid[: len(live)] = True
        a.update(
            item_features=items, item_ids=ids, item_norms_sq=x2,
            item_valid=new_valid,
        )
        self._item_fill = len(live)
        self._tombstones = 0
        _counter_inc("ann.compactions", 1)

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        raise NotImplementedError("Use kneighbors() / exactNearestNeighborsJoin().")

    def _serving_device_attrs(self) -> Tuple[str, ...]:
        # item_features (+ the fit-cached Σ X² and the incremental tier's
        # valid mask when present) are the device operands of the serving
        # scan; item_ids stay host-side (the gather back to user ids happens
        # on the host after the top-k returns)
        return tuple(
            n for n in ("item_features", "item_norms_sq", "item_valid")
            if isinstance(self._model_attributes.get(n), np.ndarray)
            or hasattr(self._model_attributes.get(n), "shape")
        )

    def _serving_predict(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        """Serving-batch kNN: the same single-shard exact scan the production
        search path uses (ops/knn.exact_knn_single — strategy knob, sentinel
        and selection telemetry all apply), per query row independent, routed
        through predict_dispatch like every other family. Returns the
        kneighbors() column surface: per-row neighbor `indices` (user item
        ids) and EUCLIDEAN `distances`."""
        import jax.numpy as jnp

        from ..observability.inference import predict_dispatch
        from ..ops.knn import exact_knn_single

        items = self._model_attributes["item_features"]
        item_ids = np.asarray(self._model_attributes["item_ids"])
        n_items = int(items.shape[0])
        k = min(self.getK(), n_items)
        x2 = self._model_attributes.get("item_norms_sq")
        # incremental tier: the valid mask carries live/tombstoned/slack rows
        # — deleted items mask to INVALID_D2 inside the scan, and because the
        # bucketed capacity (not the live count) is the operand shape, adds
        # and deletes never mint a new executable (§7b zero-compile contract)
        valid = self._model_attributes.get("item_valid")
        d2, idx = predict_dispatch(
            self,
            exact_knn_single,
            jnp.asarray(np.asarray(X, np.float32)),
            jnp.asarray(items),
            # plain jnp.asarray: when the registry installed the HBM-resident
            # mask, this is a no-op — an np round trip would pull it to host
            # and re-upload it every micro-batch
            jnp.asarray(valid)
            if valid is not None else jnp.ones((n_items,), bool),
            k,
            x2=jnp.asarray(x2) if x2 is not None else None,
            model_name=type(self).__name__,
            shape_of=X,
        )
        d2, idx = np.asarray(d2), np.asarray(idx)
        # all items are valid here, so idx is always in range; keep the -1/inf
        # API convention anyway for callers that serve a masked index
        ids = np.where(
            idx >= 0, item_ids[np.clip(idx, 0, n_items - 1)], -1
        )
        return {
            "indices": ids,
            "distances": np.sqrt(np.maximum(d2, 0.0)).astype(np.float32),
        }

    def kneighbors(self, query_df: Any) -> Tuple[Any, Any, pd.DataFrame]:
        """Returns (item_df, query_df, knn_df): knn_df has query_id + arrays of item
        indices (ids) and euclidean distances (reference knn.py:574-660)."""
        query_df = self._ensureIdCol(query_df)
        input_col, input_cols = self._get_input_columns()
        id_col = self.getIdCol()
        fd = extract_feature_data(
            query_df, input_col=input_col, input_cols=input_cols, id_col=id_col
        )
        Q = np.asarray(fd.features)
        query_ids = (
            fd.row_id if fd.row_id is not None else np.arange(len(Q), dtype=np.int64)
        )

        items = self._model_attributes["item_features"]
        item_ids = self._model_attributes["item_ids"]
        k = min(self.getK(), items.shape[0])
        from .. import config as _config

        threshold = int(_config.get("stream_threshold_bytes"))
        item_valid = self._model_attributes.get("item_valid")
        x2 = self._model_attributes.get("item_norms_sq")
        if items.nbytes > threshold and item_valid is not None:
            # the out-of-core blocked scan has no validity operand: gather the
            # LIVE rows into locals (tombstoned + bucketed-slack rows are zero
            # vectors and must not be candidates). Side-effect-free on
            # purpose — kneighbors is a read API, and compacting here would
            # change operand shapes underneath a concurrent serving
            # registration of this same model object.
            mask = np.asarray(item_valid, bool)
            items = np.ascontiguousarray(np.asarray(items)[mask])
            item_ids = np.asarray(item_ids)[mask]
            if x2 is not None:
                x2 = np.asarray(x2)[mask]
            item_valid = None  # locals are now fully live
            k = min(self.getK(), len(items))
        if items.nbytes > threshold:
            # out-of-core tier: items stay host-resident; the device scans
            # (query_block, item_block) tiles with a running top-k merge — the
            # reference's UVM-backed brute scan made explicit
            # (reference knn.py:763-774, utils.py:184-241)
            from ..ops.pairwise_streaming import streaming_exact_knn

            self.logger.warning(
                "item set ~%.0f MiB exceeds stream_threshold_bytes=%d; using the "
                "out-of-core blocked scan (host-resident items).",
                items.nbytes / 2**20,
                threshold,
            )
            from ..observability.inference import predict_dispatch

            dists, gidx = predict_dispatch(
                self, streaming_exact_knn,
                Q, np.asarray(items), k, mesh=active_partitioner(self.num_workers).mesh,
            )
            ids = np.where(gidx >= 0, item_ids[np.maximum(gidx, 0)], -1)
            knn_df = pd.DataFrame(
                {
                    f"query_{id_col}": query_ids,
                    "indices": list(ids),
                    "distances": list(dists.astype(np.float32)),
                }
            )
            return self._item_df, query_df, knn_df
        part = active_partitioner(self.num_workers)
        mesh = part.mesh
        Xp, valid, _ = pad_rows(items, part.num_workers)
        if item_valid is not None:
            # incremental tier: tombstoned/slack rows are invalid like padding
            valid = np.asarray(valid).copy()
            valid[: len(items)] *= np.asarray(item_valid, valid.dtype)
        Xd = part.shard(Xp)
        vd = part.shard(valid)
        # cached item norms (computed once at fit) shard alongside the items —
        # no query block recomputes Σ X² (padding rows are invalid-masked, so
        # their zero norm never participates); x2 is the LOCAL sliced above,
        # kept row-aligned with items through the live-row gather
        if x2 is not None:
            x2p = np.zeros((Xp.shape[0],), np.float32)
            x2p[: len(items)] = np.asarray(x2)
            x2d = part.shard(x2p)
        else:
            x2d = None
        if len(Q) >= _RING_QUERY_THRESHOLD and mesh.devices.size > 1:
            # large query sets shard over the mesh too and the item shards rotate
            # around the ring (ops/knn.exact_knn_ring) — nothing global materializes
            from ..ops.knn import exact_knn_ring

            from ..observability.inference import predict_dispatch

            Qp, qvalid, _ = pad_rows(Q, part.num_workers)
            Qd = part.shard(Qp)
            # the query block is not the leading arg here: shape_of pins the
            # recompile-sentinel signature to the PADDED query shard
            dists, gidx = predict_dispatch(
                self, exact_knn_ring, mesh, Qd, Xd, vd, k,
                x2_sharded=x2d, shape_of=Qd,
            )
            dists, gidx = dists[: len(Q)], gidx[: len(Q)]
        else:
            from ..observability.inference import predict_dispatch

            dists, gidx = predict_dispatch(
                self, exact_knn_distributed, mesh, Q, Xd, vd, k,
                x2_sharded=x2d, shape_of=Q,
            )
        # padded positions never win (inf distance); -1 ids appear only when
        # fewer than k LIVE items exist (the incremental tier's delete path)
        ids = np.where(gidx >= 0, np.asarray(item_ids)[np.maximum(gidx, 0)], -1)

        knn_df = pd.DataFrame(
            {
                f"query_{id_col}": query_ids,
                "indices": list(ids),
                "distances": list(dists.astype(np.float32)),
            }
        )
        return self._item_df, query_df, knn_df

    def exactNearestNeighborsJoin(
        self, query_df: Any, distCol: str = "distCol"
    ) -> pd.DataFrame:
        """Flattened (query_id, item_id, distance) join (reference knn.py:435-482).
        Short-tail slots (id -1 / inf distance — reachable once the incremental
        tier's deletes leave fewer than k live items) are filtered like
        approxSimilarityJoin's: a join row must name a real item."""
        _, query_df, knn_df = self.kneighbors(query_df)
        id_col = self.getIdCol()
        rows = []
        for _, r in knn_df.iterrows():
            for item_id, dist in zip(r["indices"], r["distances"]):
                if item_id >= 0 and np.isfinite(dist):
                    rows.append((r[f"query_{id_col}"], item_id, dist))
        return pd.DataFrame(rows, columns=[f"query_{id_col}", f"item_{id_col}", distCol])

    # NearestNeighborsModel persists through the ANN index store (§7b) — the
    # estimator stays non-persistable like the reference, but a fitted model
    # (its item set IS the index) saves/loads without refit via the inherited
    # write()/read() chain + the _ann_index_spec hook above.


class _ApproxNNClass(_TpuClass):
    @classmethod
    def _param_mapping(cls):
        return {
            "k": "n_neighbors",
            "algorithm": "algorithm",
            "algoParams": "algo_params",
            "metric": "metric",
            "inputCol": "",
            "featuresCols": "",
            "idCol": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        return {
            "algorithm": lambda x: x
            if x in ("ivfflat", "ivf_flat", "ivfpq", "ivf_pq", "cagra", "brute_force")
            else None,
            "metric": lambda x: x
            if x in ("euclidean", "sqeuclidean", "l2", "cosine")
            else None,
        }

    @classmethod
    def _get_tpu_params_default(cls) -> Dict[str, Any]:
        return {
            "n_neighbors": 5,
            "algorithm": "ivfflat",
            "algo_params": None,
            "metric": "euclidean",
        }


class ApproximateNearestNeighbors(_ApproxNNClass, _TpuEstimator, _NNParams):
    """ANN with an IVF-Flat index built by our distributed kmeans
    (reference knn.py:838-1723; algorithm/algoParams names follow the reference's
    cuVS translation table knn.py:1324-1404 — ivfflat params: nlist, nprobe)."""

    algorithm: Param[str] = Param(
        "undefined",
        "algorithm",
        "algorithm to use: 'ivfflat', 'ivfpq', 'cagra' or 'brute_force'.",
        TypeConverters.toString,
    )
    algoParams: Param[Dict[str, Any]] = Param(
        "undefined",
        "algoParams",
        "algorithm parameters, e.g. {'nlist': 64, 'nprobe': 8}.",
        DictTypeConverters._toDict,
    )
    metric: Param[str] = Param(
        "undefined", "metric", "distance metric.", TypeConverters.toString
    )

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(k=5, algorithm="ivfflat", metric="euclidean", algoParams=None)
        self.initialize_tpu_params()
        self._set_params(**kwargs)

    def _out_schema(self) -> List[str]:
        return ["centers", "center_norms", "cells", "cell_ids", "cell_sizes"]

    def _get_tpu_fit_func(self, extra_params: Optional[List[Dict[str, Any]]] = None):
        algo_params = self.getOrDefault("algoParams") or {}
        # both cuML and cuVS spellings are accepted, like the reference's
        # translation table (knn.py:1370-1380: nlist/n_lists, nprobe/n_probes)
        nlist = int(_ap(algo_params, "nlist", "n_lists", default=64))
        seed = int(algo_params.get("seed", 42))
        algo = self.getOrDefault("algorithm")

        cosine = self.getOrDefault("metric") == "cosine"

        def _fit(inputs: FitInputs) -> Dict[str, Any]:
            if cosine:
                # cosine reduces to euclidean on the unit sphere (cuVS handles
                # cosine the same way): normalize items at build; queries normalize
                # at search and distances convert to 1 - cos = d^2/2
                inputs.features = _normalize_or_raise(inputs.features, inputs.row_weight)
            if algo == "cagra":
                # cuVS cagra param names (reference knn.py:1324-1404,1513-1524)
                from ..ops.knn import cagra_build

                return cagra_build(
                    inputs.features,
                    inputs.row_weight,
                    graph_degree=int(
                        _ap(
                            algo_params, "graph_degree",
                            "intermediate_graph_degree", default=32,
                        )
                    ),
                    nlist=int(_ap(algo_params, "nlist", "n_lists", default=0)),
                    seed=seed,
                )
            if algo in ("ivfpq", "ivf_pq"):
                # cuVS ivf_pq param names (reference translation table knn.py:1324-1404)
                return ivfpq_build(
                    inputs.features,
                    inputs.row_weight,
                    nlist=min(nlist, inputs.desc.m),
                    m_subvectors=int(_ap(algo_params, "M", "pq_dim", default=4)),
                    n_bits=int(_ap(algo_params, "n_bits", "pq_bits", default=8)),
                    max_iter=20,
                    seed=seed,
                )
            return ivfflat_build(
                inputs.features, inputs.row_weight, nlist=min(nlist, inputs.desc.m),
                max_iter=20, seed=seed,
            )

        return _fit

    def _streaming_fit(self, fd) -> Dict[str, Any]:
        """Out-of-core ANN builds: items stay host-resident; the device sees
        only assignment/encoding/search batches (ops/ann_streaming.py) — the
        ANN leg of the reference's UVM/SAM tier (utils.py:184-241). IVF-Flat
        streams cell assignment; IVF-PQ adds subsample codebooks + streamed
        encoding passes; CAGRA derives its graph from streamed IVF searches.
        Search then pages in only the probed cells for the IVF indexes
        (ApproximateNearestNeighborsModel.kneighbors picks the streamed search
        when the cells exceed the stream threshold). Cosine streams too: the
        builds normalize per batch (no normalized dataset copy except CAGRA,
        whose graph search needs unit items resident anyway)."""
        from ..core.dataset import densify as _densify
        from ..ops.ann_streaming import (
            resolve_build_batch_rows,
            streaming_cagra_build,
            streaming_ivfflat_build,
            streaming_ivfpq_build,
        )

        algo = self.getOrDefault("algorithm")
        if algo not in ("ivfflat", "ivf_flat", "ivfpq", "ivf_pq", "cagra"):
            self.logger.warning(
                "streamed ANN covers ivfflat/ivfpq/cagra; fitting in-core "
                "despite stream_threshold_bytes."
            )
            inputs = self._build_fit_inputs(fd)
            return self._get_tpu_fit_func(None)(inputs)
        cosine = self.getOrDefault("metric") == "cosine"
        algo_params = self.getOrDefault("algoParams") or {}
        nlist = int(_ap(algo_params, "nlist", "n_lists", default=64))
        seed = int(algo_params.get("seed", 42))
        # batch geometry is a lifecycle knob (`ann.build_batch_rows`, §7b):
        # config pin > an explicit stream_batch_rows > defaults.py
        batch_rows = resolve_build_batch_rows()
        X = np.asarray(_densify(fd.features, self._float32_inputs))
        if algo == "cagra":
            # the BUILD streams, but cagra_search walks the graph with random
            # access and needs the item set device-resident — unlike the IVF
            # searches there is no paged variant, so query time will stage
            # items on device. Say so now rather than OOM-ing at kneighbors.
            self.logger.warning(
                "streamed CAGRA build keeps items host-resident, but CAGRA "
                "search requires the full item set (~%.0f MiB) on device; "
                "kneighbors() will stage it and may exhaust device memory — "
                "prefer algorithm='ivfflat'/'ivfpq' for datasets beyond HBM.",
                X.nbytes / 2**20,
            )
            return streaming_cagra_build(
                X,
                graph_degree=int(
                    _ap(
                        algo_params, "graph_degree",
                        "intermediate_graph_degree", default=32,
                    )
                ),
                nlist=int(_ap(algo_params, "nlist", "n_lists", default=0)),
                seed=seed,
                batch_rows=batch_rows,
                cosine=cosine,
            )
        if algo in ("ivfpq", "ivf_pq"):
            return streaming_ivfpq_build(
                X,
                nlist=min(nlist, fd.n_rows),
                m_subvectors=int(_ap(algo_params, "M", "pq_dim", default=4)),
                n_bits=int(_ap(algo_params, "n_bits", "pq_bits", default=8)),
                max_iter=20,
                seed=seed,
                batch_rows=batch_rows,
                cosine=cosine,
            )
        return streaming_ivfflat_build(
            X,
            nlist=min(nlist, fd.n_rows),
            max_iter=20,
            seed=seed,
            batch_rows=batch_rows,
            cosine=cosine,
        )

    def _create_pyspark_model(self, attrs) -> "ApproximateNearestNeighborsModel":
        return ApproximateNearestNeighborsModel(**attrs)

    def _fit(self, dataset: Any) -> "ApproximateNearestNeighborsModel":
        from ..observability import fit_run

        dataset = self._ensureIdCol(dataset)
        fd = self._pre_process_data(dataset)
        # one FitRun over the whole build (this override used to bypass the
        # §6d scope the generic _fit opens): the pipelined streamed builds'
        # batch counters/histograms and rank timeline land in one exported
        # report, like every other estimator's
        with fit_run(algo=type(self).__name__) as run:
            if self.getOrDefault("algorithm") == "brute_force":
                model = ApproximateNearestNeighborsModel(
                    centers=np.zeros((0, fd.n_cols), np.float32),
                    cells=np.zeros((0, 0, fd.n_cols), np.float32),
                    cell_ids=np.zeros((0, 0), np.int64),
                    cell_sizes=np.zeros((0,), np.int32),
                )
                items = np.asarray(fd.features)
                if self.getOrDefault("metric") == "cosine":
                    import jax.numpy as jnp

                    items = np.asarray(
                        _normalize_or_raise(
                            jnp.asarray(items), jnp.ones(len(items))
                        )
                    )
                model._brute_items = items
                from ..ops.knn import center_norms_sq

                model._brute_norms = center_norms_sq(items)
            else:
                model = self._fit_internal(dataset, None)[0]
        if run is not None:
            model.fit_report_ = run.report()
        model._item_row_ids = (
            fd.row_id if fd.row_id is not None else np.arange(fd.n_rows, dtype=np.int64)
        )
        model._item_df = dataset
        self._copyValues(model)
        return model

    def write(self):
        raise NotImplementedError("ApproximateNearestNeighbors is not persistable.")


class ApproximateNearestNeighborsModel(_ApproxNNClass, _TpuModel, _NNParams):
    algorithm = ApproximateNearestNeighbors.algorithm
    algoParams = ApproximateNearestNeighbors.algoParams
    metric = ApproximateNearestNeighbors.metric

    def __init__(
        self,
        centers: Optional[np.ndarray] = None,
        cells: Optional[np.ndarray] = None,
        cell_ids: Optional[np.ndarray] = None,
        cell_sizes: Optional[np.ndarray] = None,
        codebooks: Optional[np.ndarray] = None,
        codes: Optional[np.ndarray] = None,
        items: Optional[np.ndarray] = None,
        graph: Optional[np.ndarray] = None,
        center_norms: Optional[np.ndarray] = None,
        item_norms_sq: Optional[np.ndarray] = None,
    ) -> None:
        if graph is not None:
            # CAGRA-class graph index (ops/knn.py cagra_build)
            attrs = dict(items=np.asarray(items), graph=np.asarray(graph))
            if item_norms_sq is not None:
                attrs["item_norms_sq"] = np.asarray(item_norms_sq)
        else:
            attrs = dict(
                centers=np.asarray(centers),
                cells=np.asarray(cells),
                cell_ids=np.asarray(cell_ids),
                cell_sizes=np.asarray(cell_sizes),
            )
            if center_norms is not None:
                # cached Σ centers² from the build — probe scans never
                # recompute it (rebuilt on refit with the index itself)
                attrs["center_norms"] = np.asarray(center_norms)
        if codebooks is not None:
            attrs["codebooks"] = np.asarray(codebooks)
            attrs["codes"] = np.asarray(codes)
        super().__init__(**attrs)
        self._setDefault(k=5, algorithm="ivfflat", metric="euclidean", algoParams=None)
        self._brute_items: Optional[np.ndarray] = None
        self._brute_norms: Optional[np.ndarray] = None
        self._item_row_ids: Optional[np.ndarray] = None
        self._item_df: Any = None
        self._ivf_state: Any = None  # MutableIvfState once mutated (§7b)
        self._dev: Any = None  # lazy DeviceIndexCache (per-segment HBM)
        self.logger = get_logger(self.__class__)

    def __getstate__(self):
        # the device cache holds jax buffers — never pickle it; the receiver
        # re-uploads lazily on its first search
        state = dict(self.__dict__)
        state["_dev"] = None
        return state

    def _transform_arrays(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        raise NotImplementedError("Use kneighbors() / approxSimilarityJoin().")

    # ---- lazy device residency (ops/ann_lifecycle.py::DeviceIndexCache) ----

    def _dev_get(self, name: str, host_array: Any = None):
        """Device copy of one index segment: uploaded on FIRST search, then
        HBM-resident across searches — a loaded index stages only what the
        query path touches (cold-start never uploads the whole index)."""
        arr = (
            host_array if host_array is not None
            else self._model_attributes.get(name)
        )
        if arr is None:
            return None
        if self._dev is None:
            from ..ops.ann_lifecycle import DeviceIndexCache

            self._dev = DeviceIndexCache()
        return self._dev.get(name, arr)

    def _invalidate_device(self, *names: str) -> None:
        if self._dev is not None:
            self._dev.invalidate(*names)

    # ---- persistence (ANN index store, docs/design.md §7b) ----

    def _ann_index_spec(self):
        if self._brute_items is not None:
            raise NotImplementedError(
                "brute_force ANN models hold no index to persist; refit (or "
                "use NearestNeighborsModel, whose item set persists)."
            )
        arrays = {
            k: np.asarray(v)
            for k, v in self._model_attributes.items()
            if v is not None and hasattr(v, "shape")
        }
        if self._item_row_ids is not None:
            arrays["item_row_ids"] = np.asarray(self._item_row_ids)
        meta: Dict[str, Any] = {"tombstones": 0}
        if self._ivf_state is not None:
            arrays["item_cells"] = np.asarray(self._ivf_state.item_cells)
            arrays["cell_fill"] = np.asarray(self._ivf_state.cell_fill)
            meta["tombstones"] = int(self._ivf_state.tombstones)
        return arrays, str(self.getOrDefault("algorithm")), meta

    @classmethod
    def _from_row(cls, attrs: Dict[str, Any]
                  ) -> "ApproximateNearestNeighborsModel":
        manifest = attrs.pop("__ann_manifest__", None)
        item_row_ids = attrs.pop("item_row_ids", None)
        item_cells = attrs.pop("item_cells", None)
        cell_fill = attrs.pop("cell_fill", None)
        model = cls(**attrs)
        if item_row_ids is not None:
            model._item_row_ids = np.asarray(item_row_ids)
        if item_cells is not None and cell_fill is not None:
            from ..ops.ann_lifecycle import MutableIvfState

            model._ivf_state = MutableIvfState(
                item_cells, cell_fill,
                tombstones=int(
                    ((manifest or {}).get("meta") or {}).get("tombstones", 0)
                ),
            )
        return model

    # ---- incremental add/delete (docs/design.md §7b) ----

    def _ensure_ivf_state(self):
        if "graph" in self._model_attributes or self._brute_items is not None:
            raise NotImplementedError(
                "incremental add/delete covers the IVF indexes (ivfflat/"
                "ivfpq); CAGRA graphs and brute_force require a rebuild."
            )
        if self._item_row_ids is None:
            raise ValueError(
                "model has no item-id mapping; fit it (or load a saved "
                "index) before mutating"
            )
        if self._ivf_state is None:
            from ..ops.ann_lifecycle import MutableIvfState

            self._ivf_state = MutableIvfState.from_layout(
                np.asarray(self._model_attributes["cell_ids"]),
                len(self._item_row_ids),
            )
        return self._ivf_state

    def enable_incremental(self, slack_rows: int = 0) -> None:
        """Round the IVF list capacity up to its power-of-two bucket (plus
        optional slack): the one shape change, paid BEFORE serving, that
        makes later in-slack adds zero-compile (§7b)."""
        from ..ops.ann_lifecycle import rebucket_layout

        self._ensure_ivf_state()
        if rebucket_layout(self._model_attributes, slack_rows=slack_rows):
            self._invalidate_device("cells", "cell_ids", "codes")

    def add_items(self, X_new: np.ndarray, ids: "np.ndarray | None" = None
                  ) -> np.ndarray:
        """Append items into the IVF lists (host-side assign/encode, hole
        reuse, bucketed growth — ops/ann_lifecycle.py::ivf_add). Returns the
        user ids assigned to the new items."""
        from ..ops.ann_lifecycle import ivf_add

        state = self._ensure_ivf_state()
        X_new = np.ascontiguousarray(np.asarray(X_new), np.float32)
        m = len(X_new)
        row_ids = np.asarray(self._item_row_ids)
        if ids is None:
            base = int(row_ids.max(initial=-1)) + 1
            ids = np.arange(base, base + m, dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64)
        positions = np.arange(len(row_ids), len(row_ids) + m, dtype=np.int64)
        ivf_add(
            self._model_attributes, state, X_new, positions,
            cosine=self.getOrDefault("metric") == "cosine",
        )
        self._item_row_ids = np.concatenate([row_ids, ids])
        self._invalidate_device("cells", "cell_ids", "codes")
        self._maybe_compact()
        return ids

    def delete_items(self, ids: np.ndarray) -> int:
        """Tombstone items by user id: their list slots flip to the -1
        sentinel the probe scans already mask to INVALID_D2 — deleted items
        vanish from results with no kernel or shape change."""
        from ..ops.ann_lifecycle import ivf_delete

        state = self._ensure_ivf_state()
        positions = np.nonzero(
            np.isin(np.asarray(self._item_row_ids), np.asarray(ids, np.int64))
        )[0]
        n = ivf_delete(self._model_attributes, state, positions)
        if n:
            self._invalidate_device("cell_ids")
            self._maybe_compact()
        return n

    def _maybe_compact(self) -> None:
        from ..ops.ann_lifecycle import ivf_compact, needs_compaction

        if self._ivf_state is not None and needs_compaction(self._ivf_state):
            ivf_compact(self._model_attributes, self._ivf_state)
            self._invalidate_device("cells", "cell_ids", "codes")

    def tombstone_fraction(self) -> float:
        """Tombstoned slots / occupied slots — what the compaction trigger
        compares against `ann.compact_tombstone_pct`."""
        if self._ivf_state is None:
            return 0.0
        occupied = self._ivf_state.live_items() + max(
            self._ivf_state.tombstones, 0
        )
        return self._ivf_state.tombstones / occupied if occupied else 0.0

    def kneighbors(self, query_df: Any) -> Tuple[Any, Any, pd.DataFrame]:
        import jax.numpy as jnp

        query_df = self._ensureIdCol(query_df)
        input_col, input_cols = self._get_input_columns()
        id_col = self.getIdCol()
        fd = extract_feature_data(
            query_df, input_col=input_col, input_cols=input_cols, id_col=id_col
        )
        Q = np.asarray(fd.features)
        query_ids = (
            fd.row_id if fd.row_id is not None else np.arange(len(Q), dtype=np.int64)
        )
        k = self.getK()
        cosine = self.getOrDefault("metric") == "cosine"
        if cosine:
            # the index holds unit vectors; normalize queries the same way
            Q = np.asarray(
                _normalize_or_raise(jnp.asarray(Q), jnp.ones(len(Q)))
            )

        from ..observability.inference import predict_dispatch

        if self._brute_items is not None:
            from ..ops.knn import exact_knn_single

            items = self._brute_items
            x2b = self._brute_norms
            d2, idx = predict_dispatch(
                self, exact_knn_single,
                jnp.asarray(Q), self._dev_get("brute_items", items),
                jnp.ones((items.shape[0],), bool), min(k, items.shape[0]),
                x2=(
                    self._dev_get("brute_norms", x2b)
                    if x2b is not None else None
                ),
                model_name=type(self).__name__,
            )
            dists = np.sqrt(np.asarray(d2))
            pos = np.asarray(idx)
        elif "graph" in self._model_attributes:
            from ..ops.knn import cagra_search

            algo_params = self.getOrDefault("algoParams") or {}
            dists_j, ids_j = predict_dispatch(
                self, cagra_search,
                jnp.asarray(Q),
                # lazy per-segment device residency (§7b): items/graph upload
                # on the FIRST search and replay from HBM afterwards
                self._dev_get("items"),
                self._dev_get("graph"),
                k=k,
                itopk=int(algo_params.get("itopk_size", max(64, k))),
                iterations=int(algo_params.get("max_iterations", 32)),
                # width>1 batches the neighbor gathers: ~2.5x faster at equal
                # recall on this kernel (cuVS search_width)
                search_width=int(algo_params.get("search_width", 4)),
                x2=self._dev_get("item_norms_sq"),
                model_name=type(self).__name__,
            )
            dists = np.asarray(dists_j)
            pos = np.asarray(ids_j)
        else:
            algo_params = self.getOrDefault("algoParams") or {}
            nlist = self._model_attributes["centers"].shape[0]
            nprobe = int(
                _ap(algo_params, "nprobe", "n_probes", default=max(1, nlist // 8))
            )
            cn_j = self._dev_get("center_norms")
            if "codebooks" in self._model_attributes:
                from ..ops.knn import pq_refine

                refine_ratio = int(algo_params.get("refine_ratio", 2))
                dists_j, ids_j, flat_pos = predict_dispatch(
                    self, ivfpq_search,
                    jnp.asarray(Q),
                    self._dev_get("centers"),
                    self._dev_get("codebooks"),
                    self._dev_get("codes"),
                    self._dev_get("cell_ids"),
                    k=k * max(refine_ratio, 1),
                    nprobe=min(nprobe, nlist),
                    center_norms=cn_j,
                    model_name=type(self).__name__,
                )
                if refine_ratio > 1:
                    # exact re-rank of the ADC candidates (reference knn.py:1642-1666)
                    from .. import config as _config

                    cells_np = self._model_attributes["cells"]
                    threshold = _config.get("stream_threshold_bytes")
                    if threshold and getattr(cells_np, "nbytes", 0) > threshold:
                        # out-of-core: device_put of the full cell layout would
                        # OOM exactly when the build streamed — page in only
                        # the candidate vectors (ops/ann_streaming.py)
                        from ..ops.ann_streaming import streaming_pq_refine

                        self.logger.info(
                            "IVF-PQ cells ~%.0f MiB exceed stream_threshold_"
                            "bytes; refining with host-paged candidates",
                            cells_np.nbytes / 2**20,
                        )
                        dists_j, ids_j = streaming_pq_refine(
                            np.asarray(Q), np.asarray(cells_np),
                            np.asarray(flat_pos), np.asarray(ids_j), k=k,
                        )
                    else:
                        from ..observability import span as _obs_span

                        with _obs_span("knn.rerank", {"k": k}):
                            dists_j, ids_j = pq_refine(
                                jnp.asarray(Q),
                                self._dev_get("cells", cells_np),
                                flat_pos,
                                ids_j,
                                k=k,
                            )
            else:
                from .. import config as _config

                cells_np = self._model_attributes["cells"]
                threshold = _config.get("stream_threshold_bytes")
                if threshold and getattr(cells_np, "nbytes", 0) > threshold:
                    # out-of-core search: cells stay host-resident, only the
                    # probed cells page onto the device (ops/ann_streaming.py)
                    from ..ops.ann_streaming import streaming_ivfflat_search

                    self.logger.info(
                        "IVF cells ~%.0f MiB exceed stream_threshold_bytes; "
                        "searching with host-resident cells",
                        cells_np.nbytes / 2**20,
                    )
                    dists_j, ids_j = predict_dispatch(
                        self, streaming_ivfflat_search,
                        np.asarray(Q), self._model_attributes, k=k,
                        nprobe=min(nprobe, nlist),
                    )
                else:
                    dists_j, ids_j = predict_dispatch(
                        self, ivfflat_search,
                        jnp.asarray(Q),
                        self._dev_get("centers"),
                        self._dev_get("cells", cells_np),
                        self._dev_get("cell_ids"),
                        k=k,
                        nprobe=min(nprobe, nlist),
                        center_norms=cn_j,
                        model_name=type(self).__name__,
                    )
            dists = np.asarray(dists_j)
            pos = np.asarray(ids_j)

        ids = np.where(pos >= 0, self._item_row_ids[np.maximum(pos, 0)], -1)
        if cosine:
            # searches ran euclidean on unit vectors: cosine distance = d^2 / 2
            dists = np.where(np.isfinite(dists), (dists * dists) / 2.0, dists)
        knn_df = pd.DataFrame(
            {
                f"query_{id_col}": query_ids,
                "indices": list(ids),
                "distances": list(dists.astype(np.float32)),
            }
        )
        return self._item_df, query_df, knn_df

    def approxSimilarityJoin(
        self, query_df: Any, distCol: str = "distCol"
    ) -> pd.DataFrame:
        _, query_df, knn_df = self.kneighbors(query_df)
        id_col = self.getIdCol()
        rows = []
        for _, r in knn_df.iterrows():
            for item_id, dist in zip(r["indices"], r["distances"]):
                if item_id >= 0 and np.isfinite(dist):
                    rows.append((r[f"query_{id_col}"], item_id, dist))
        return pd.DataFrame(rows, columns=[f"query_{id_col}", f"item_{id_col}", distCol])

    def write(self):
        # brute_force holds no index (its item set lives outside the
        # attribute dict); the real indexes persist via the ANN store (§7b)
        if self._brute_items is not None:
            raise NotImplementedError(
                "brute_force ApproximateNearestNeighborsModel is not "
                "persistable; use an indexed algorithm (ivfflat/ivfpq/cagra)."
            )
        return super().write()
