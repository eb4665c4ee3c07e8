"""Why `jnp.linalg.eigh(cov, subset_by_index=(d-k, d))` is not PCA's eigensolve
(ROADMAP S21, PERF.md §7): in jax 0.9.0 the TPU lowering's divide and conquer
(`jax._src.tpu.linalg.eigh`), asked for the leading pairs only, can return a
number that is no eigenvalue. A pruned branch leaves its parent block's first
column in the eigenvalue slot, and the final `argsort` ranks that leftover
with the eigenvalues. Here: 96 x 96, eigenvalues 100, 80, 60 and 93 near 1,
`A[0, 0] = 70`. The full solve returns 60, 80, 100; the subset (93, 96)
returns 70, 80, 100, residual 10. The library's pure-JAX work loop on the
CPU (`termination_size=1`, so the recursion runs to single columns): a logic
check, no speed, nothing a cell runs.

    JAX_PLATFORMS=cpu python -m tools.eigh_subset_probe
"""

from __future__ import annotations

import json
import sys

import jax.numpy as jnp
import numpy as np
from jax._src.tpu.linalg.eigh import eigh


def main() -> int:
    n = 96
    noise = np.random.default_rng(0).standard_normal((n, n))
    A = np.eye(n) + 0.01 * (noise + noise.T) / 2
    A[0, 0] = A[1, 1] = 70.0
    A[0, 1] = A[1, 0] = 10.0  # the pair 60, 80
    A[5, 5] = 100.0
    A = jnp.asarray(A, jnp.float32)
    for subset in (None, (n - 3, n)):
        vals, vecs = eigh(A, termination_size=1, subset_by_index=subset)
        vals, vecs = np.asarray(vals)[-3:], np.asarray(vecs)[:, -3:]
        residual = np.abs(np.asarray(A) @ vecs - vecs * vals).max(axis=0)
        print(json.dumps({"subset_by_index": subset, "leading": vals.tolist(),
                          "residual": residual.tolist()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
