"""Plain numpy references. Nothing here imports the program or JAX.

Copied in spirit from `chip_smoke.py` (`np_assign`, `np_cluster_means`,
`np_covariance`) and sharpened so that the reference is closer to the exact
answer than float32 on the chip can be:

* nearest-centre labels: float32 sgemm screens every row, and each row whose
  two nearest centres lie within `AMBIGUOUS` of each other is decided again in
  float64 from the differences (no expansion, no cancellation);
* sums: float32 sgemm over short chunks (`CHUNK` rows keep the in-chunk
  rounding near 1e-8 of a chunk's sum), float64 across chunks;
* covariance: chunks are shifted by a rough mean before the Gram product, so
  the final `S2 - n m m'` cancels nothing that matters.

`round_bf16` is the controls' lower precision: what one MXU pass does to a
float32 operand (round to nearest even, 8 bits of mantissa).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

CHUNK = 16384
AMBIGUOUS = 1e-2  # squared-distance margin under which float64 decides


def round_bf16(a: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even) -> float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    bias = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + bias) & np.uint32(0xFFFF0000)).view(np.float32)


def assign(X: np.ndarray, C: np.ndarray, low_precision: bool = False,
           sums_for: Optional[int] = None):
    """Nearest-centre labels of every row, the summed squared distance, and
    (with `sums_for=k`) the per-label row sums and counts in float64.

    `low_precision=True` is the control: the cross term from bfloat16-rounded
    operands and no float64 second look."""
    n, d = X.shape
    k = C.shape[0]
    C32 = np.ascontiguousarray(C, np.float32)
    C64 = C32.astype(np.float64)
    c2 = (C64 * C64).sum(axis=1)
    Ct = (round_bf16(C32) if low_precision else C32).T.copy()
    labels = np.empty(n, np.int32)
    inertia = 0.0
    sums = np.zeros((k, d), np.float64) if sums_for else None
    counts = np.zeros(k, np.int64) if sums_for else None
    eye = np.eye(k, dtype=np.float32)
    for s in range(0, n, CHUNK):
        x = X[s:s + CHUNK]
        xq = round_bf16(x) if low_precision else x
        cross = (xq @ Ct).astype(np.float64)
        x2 = np.einsum("ij,ij->i", x, x).astype(np.float64)
        d2 = x2[:, None] - 2.0 * cross + c2[None, :]
        lab = d2.argmin(axis=1)
        best = d2[np.arange(len(lab)), lab]
        if not low_precision:
            d2[np.arange(len(lab)), lab] = np.inf
            close = np.nonzero(d2.min(axis=1) - best < AMBIGUOUS)[0]
            if close.size:
                diff = x[close].astype(np.float64)[:, None, :] - C64[None, :, :]
                exact = (diff * diff).sum(axis=2)
                lab[close] = exact.argmin(axis=1)
                best[close] = exact.min(axis=1)
        labels[s:s + CHUNK] = lab
        inertia += float(np.maximum(best, 0.0).sum())
        if sums_for:
            sums += (eye[lab].T @ x).astype(np.float64)
            counts += np.bincount(lab, minlength=k)
    return labels, inertia, sums, counts


def lloyd_step(X: np.ndarray, C: np.ndarray, low_precision: bool = False) -> np.ndarray:
    """One exact Lloyd update from centres C: the mean of the rows nearest each
    centre; an empty cluster keeps its centre."""
    _, _, sums, counts = assign(X, C, low_precision, sums_for=C.shape[0])
    new = sums / np.maximum(counts, 1)[:, None]
    return np.where(counts[:, None] > 0, new, np.asarray(C, np.float64))


def covariance(X: np.ndarray, low_precision: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """(mean, unbiased covariance) in float64. `low_precision=True` is the
    control: Gram and column sums of the bfloat16-rounded table, unshifted, as a
    single-pass kernel would form them."""
    n, d = X.shape
    shift = np.zeros(d, np.float32) if low_precision else X[:CHUNK].mean(axis=0)
    S2 = np.zeros((d, d), np.float64)
    s1 = np.zeros(d, np.float64)
    for s in range(0, n, CHUNK):
        x = X[s:s + CHUNK]
        y = round_bf16(x) if low_precision else x - shift[None, :]
        S2 += (y.T @ y).astype(np.float64)
        s1 += y.sum(axis=0, dtype=np.float64)
    m = s1 / n
    cov = (S2 - n * np.outer(m, m)) / (n - 1.0)
    return m + shift.astype(np.float64), cov


def top_eigen(cov: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Leading k eigenvalues (descending) and their eigenvectors as rows."""
    lam, vec = np.linalg.eigh(cov)
    return lam[::-1][:k], vec[:, ::-1][:, :k].T
