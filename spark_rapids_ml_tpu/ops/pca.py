#
# PCA fit/transform kernels — the TPU-native replacement for cuml.decomposition.pca_mg
# (reference feature.py:228-269 calls PCAMG.fit with partition descriptors; the
# covariance allreduce happens inside cuML over NCCL).
#
# TPU formulation: one sharded pass builds the dxd covariance from sufficient
# statistics (ops/linalg.py, psum over ICI implicit in the sharded contraction), then a
# replicated symmetric eigendecomposition extracts the top-k components. What one
# v5e chip read of the two (PERF.md §5; device seconds a fit): at d = 256 on
# 4,190,208 rows the Gram kernel 0.0202 s and the eigh 0.0015 s; at d = 3000 on
# 357,376 rows the XLA Gram 0.1327 s (0.2428 s before it left out the lower
# column blocks of the symmetric matrix, PR 33) and the eigh 0.3389 s. The full
# eigh, taken for three components, is tiny at a few hundred columns and 72 %
# of the device's work at upstream's 3000, where its program also takes five
# minutes to compile cold (the TPU's eigh is a divide and conquer unrolled into
# 97,000 lines of HLO).
#
# Parity notes:
#   * component signs canonicalized so each component's max-|.| element is positive —
#     the reference's signFlip (deprecated/native/src/rapidsml_jni.cu:35) / sklearn
#     svd_flip convention.
#   * transform does NOT center: Spark's PCA projects raw rows, and the reference adds
#     the projected mean back onto cuML's centered output to match
#     (reference feature.py:438-451). We project raw rows directly.
#

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import counter_inc, span
from ..observability.device import compiled_kernel
from .linalg import gram_column_blocks, weighted_covariance


@compiled_kernel("pca.from_cov", static_argnames=("k",))
def _pca_from_cov(cov: jax.Array, k: int):
    eigvals, eigvecs = jnp.linalg.eigh(cov)  # ascending
    # top-k, descending
    vals = eigvals[::-1][:k]
    vecs = eigvecs[:, ::-1][:, :k].T  # (k, d)
    # sign canonicalization: max-|.| element of each component positive
    idx = jnp.argmax(jnp.abs(vecs), axis=1)
    signs = jnp.sign(vecs[jnp.arange(k), idx])
    signs = jnp.where(signs == 0, 1.0, signs)
    vecs = vecs * signs[:, None]
    total_var = jnp.trace(cov)
    return vals, vecs, total_var


def gram_gate(n_cols: int, unit_weight: bool, dtype=jnp.float32) -> Tuple[bool, str]:
    """Whether the fused one-X-read pallas Gram kernel (ops/pallas_xtwx.py) should
    carry this covariance/normal-equation fit, and which test decided it.

    The SEMANTIC requirements — prefix-mask unit weights, a feature width inside
    the kernel's VMEM budget, f32 data (the kernel accumulates via bf16 splits
    into f32; an f64 fit must keep the XLA path the user asked for) — are never
    overridable. The `pallas_xtwx` config only steers the remaining heuristics:
    "0" forces the XLA path, "1" skips the TPU-platform check (tests/interpret),
    "auto" requires a real TPU backend.

    The tests in the order they are asked: `setting` ("0"), `weights`, `cols`,
    `dtype`, `setting` ("1"), `platform`. The first that fails names the reason;
    a fused fit has passed the last one asked of it (`platform` under "auto",
    `setting` under "1")."""
    from .. import config as _config

    mode = str(_config.get("pallas_xtwx")).lower()
    if mode not in ("0", "false", "off", "1", "true", "on", "auto"):
        raise ValueError(
            f"pallas_xtwx must be '0', '1' or 'auto', got '{mode}'."
        )
    if mode in ("0", "false", "off"):
        return False, "setting"
    from .pallas_xtwx import MAX_FUSED_COLS

    if not unit_weight:
        return False, "weights"
    if n_cols > MAX_FUSED_COLS:
        return False, "cols"
    if jnp.dtype(dtype) != jnp.float32:
        return False, "dtype"
    if mode in ("1", "true", "on"):
        return True, "setting"
    return jax.devices()[0].platform == "tpu", "platform"


def use_fused_gram(n_cols: int, unit_weight: bool, dtype=jnp.float32) -> bool:
    """`gram_gate`'s verdict alone (LinearRegression's normal equations ask it
    at a call site of their own, ops/linear.py)."""
    return gram_gate(n_cols, unit_weight, dtype)[0]


def covariance_for_fit(
    X: jax.Array, w: jax.Array, mesh=None, unit_weight: bool = False
):
    """Covariance dispatch for estimator fits: the fused pallas kernel when the
    measured win applies (see gram_gate), else the XLA sufficient-statistics
    pass. Both return (cov, mean, wsum) with identical semantics.

    `pca.gram_path{path=xla|pallas}` counts which one ran and
    `pca.gram_gate{fused=0|1,reason=}` which test decided it: beyond
    `MAX_FUSED_COLS` columns (upstream's benchmark table has 3000) nothing
    else says that the fit left the one-read kernel for the XLA program.
    `pca.gram_form{form=triangle,blocks=B|form=full}` counts which form that
    program took, from the shape test it takes it by
    (`linalg.gram_column_blocks`); a fit on the kernel counts neither."""
    fused, reason = gram_gate(X.shape[1], unit_weight, dtype=X.dtype)
    counter_inc("pca.gram_gate", 1, fused=int(fused), reason=reason)
    counter_inc("pca.gram_path", 1, path="pallas" if fused else "xla")
    if fused:
        from ._precision import parity_precision
        from .pallas_xtwx import covariance_prefix_mask

        # force-on ("1") off-TPU is the tests' escape hatch: Mosaic can't lower
        # for CPU/GPU backends, so run the kernel's interpreter there
        interpret = jax.devices()[0].platform != "tpu"
        return covariance_prefix_mask(
            X, w, mesh=mesh, precision=parity_precision(), interpret=interpret
        )
    blocks = len(gram_column_blocks(X.shape[1]))
    if blocks > 1:
        counter_inc("pca.gram_form", 1, form="triangle", blocks=blocks)
    else:
        counter_inc("pca.gram_form", 1, form="full")
    return weighted_covariance(X, w, mesh=mesh)


def pca_fit(
    X: jax.Array, w: jax.Array, k: int, mesh=None, unit_weight: bool = False
) -> Dict[str, np.ndarray]:
    """Distributed PCA fit. X: (padded_m, d) rows sharded over the mesh; w: padding/
    sample weights. Returns host-side model attributes (the analog of the model row the
    reference collects, feature.py:260-285)."""
    cov, mean, wsum = covariance_for_fit(X, w, mesh=mesh, unit_weight=unit_weight)
    return pca_attrs_from_cov(cov, mean, wsum, k)


def pca_attrs_from_cov(
    cov: jax.Array, mean: jax.Array, wsum: jax.Array, k: int
) -> Dict[str, np.ndarray]:
    """Model attributes from a (possibly streamed, ops/streaming.py) covariance."""
    with span("pca.eig.solve", {"waits": "device"}):
        # waited for, so that the device's eigensolve and the host's
        # conversion below are not one number
        vals, vecs, total_var = jax.block_until_ready(_pca_from_cov(cov, k))
    with span("pca.eig.fetch"):
        n = float(wsum)
        vals_h = np.asarray(vals, dtype=np.float64)
        return {
            "mean": np.asarray(mean),
            "components": np.asarray(vecs),
            "explained_variance": vals_h,
            "explained_variance_ratio": vals_h / float(total_var),
            "singular_values": np.sqrt(np.maximum(vals_h, 0.0) * (n - 1.0)),
        }


@compiled_kernel("pca.transform")
def pca_transform(X: jax.Array, components: jax.Array) -> jax.Array:
    """Spark-parity projection of raw (uncentered) rows: X @ Vᵀ."""
    from ._precision import pdot

    return pdot(X, components.T)
