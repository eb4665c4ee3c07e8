"""Seeded tables, made on the device in row blocks and brought to the host.

One generator for every configuration: a row is a gaussian-mixture centre plus
low-rank factors plus unit noise plus a per-column offset, and each part is
switched by the configuration's `table` group. KMeans cells use overlapping
mixture components (Lloyd then never reaches an exact fixed point inside
`maxIter`, so every fit does the same work); PCA cells use a few strong factors
(distinct leading eigenvalues) and non-zero column means.

The same `seed` gives the same table on one chip or four: a block's key depends
on the block's index only. Blocks are small (<= `BLOCK_BYTES`), so making the
table never sets the process's device-memory peak.
"""

from __future__ import annotations

import collections
from typing import Any, Dict, Tuple

import numpy as np

BLOCK_BYTES = 128 << 20


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (the driver's seeds pass
    2**31, which `PRNGKey` alone refuses without x64)."""
    import jax

    seed = int(seed)
    if seed < 0 or seed >= 1 << 62:
        raise ValueError(f"seed {seed} outside [0, 2**62)")
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def table_params(table: Dict[str, Any], cols: int, seed: int) -> Dict[str, np.ndarray]:
    """The small host-made parts of a table: mixture centres, orthonormal
    factor loadings (scaled), column offsets."""
    rng = np.random.default_rng([int(seed), 0xCE11])
    k = int(table.get("components", 1))
    centers = (rng.standard_normal((k, cols)) * float(table.get("center_scale", 0.0)))
    scales = np.asarray(table.get("factor_scales", []), np.float64)
    if scales.size:
        q, _ = np.linalg.qr(rng.standard_normal((cols, scales.size)))
        loadings = q.T * scales[:, None]
    else:
        loadings = np.zeros((0, cols))
    offset = rng.standard_normal(cols) * float(table.get("offset_scale", 0.0))
    return {
        "centers": centers.astype(np.float32),
        "loadings": loadings.astype(np.float32),
        "offset": offset.astype(np.float32),
        "noise_scale": np.float32(table.get("noise_scale", 1.0)),
    }


def _block_fn(block_rows: int, cols: int):
    import jax
    import jax.numpy as jnp

    def block(key, centers, loadings, offset, noise_scale):
        k_lab, k_fac, k_noise = jax.random.split(key, 3)
        x = jax.random.normal(k_noise, (block_rows, cols), jnp.float32) * noise_scale
        x = x + offset[None, :]
        if centers.shape[0] > 1:
            lab = jax.random.randint(k_lab, (block_rows,), 0, centers.shape[0])
            x = x + centers[lab]
        else:
            x = x + centers[0][None, :]
        if loadings.shape[0]:
            f = jax.random.normal(k_fac, (block_rows, loadings.shape[0]), jnp.float32)
            x = x + jnp.matmul(f, loadings, precision=jax.lax.Precision.HIGHEST)
        return x

    return jax.jit(block)


def make_table(table: Dict[str, Any], rows: int, cols: int, seed: int,
               devices) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """(X, params): a C-ordered float32 (rows, cols) host array with every page
    written, and the small parts it was made from."""
    import jax

    params = table_params(table, cols, seed)
    block_rows = max(8, min(rows, BLOCK_BYTES // (4 * cols)))
    n_blocks = -(-rows // block_rows)
    fn = _block_fn(block_rows, cols)
    key = seed_key(seed)
    X = np.empty((rows, cols), np.float32)
    on_dev = [{n: jax.device_put(v, d) for n, v in params.items()} for d in devices]
    pending: collections.deque = collections.deque()

    def drain_one() -> None:
        b, arr = pending.popleft()
        s = b * block_rows
        e = min(rows, s + block_rows)
        X[s:e] = np.asarray(arr)[: e - s]

    for b in range(n_blocks):
        i = b % len(devices)
        p = on_dev[i]
        kb = jax.device_put(jax.random.fold_in(key, b), devices[i])
        block = fn(kb, p["centers"], p["loadings"], p["offset"], p["noise_scale"])
        block.copy_to_host_async()
        pending.append((b, block))
        if len(pending) > 4 * len(devices):
            drain_one()
    while pending:
        drain_one()
    return X, params
