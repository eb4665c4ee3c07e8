#
# Driver/worker utilities (structural equivalent of reference
# python/src/spark_rapids_ml/utils.py).  GPU/RMM-specific pieces of the reference have no
# TPU analog and are replaced by mesh/partition helpers in spark_rapids_ml_tpu.parallel.
#

from __future__ import annotations

import logging
import os
import sys
from typing import Any, Iterator, List, Optional, Tuple, Union

import numpy as np

_LOG_FORMAT = "%(asctime)s %(levelname)s %(name)s: %(message)s"


def get_logger(cls: Any, level: Union[int, str] = logging.INFO) -> logging.Logger:
    """Per-class logger (reference utils.py:555-576)."""
    name = cls if isinstance(cls, str) else getattr(cls, "__name__", str(cls))
    logger = logging.getLogger(f"spark_rapids_ml_tpu.{name}")
    logger.setLevel(level)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(logging.Formatter(_LOG_FORMAT))
        logger.addHandler(handler)
        logger.propagate = False
    return logger


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a FIXED directory before the
    first compile, and return the directory in use. Entry points call this
    (chip_smoke.py); library code never does.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
    is set here. Otherwise the cache lives at `<checkout>/.jax_cache` (git-
    ignored): the directory is part of the cache key, so a path built from a
    temporary name, a pid or the time would never hit."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    cache_dir = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def _get_default_params_from_func(func: Any, unsupported_set: Optional[set] = None) -> dict:
    """Introspect a callable's keyword defaults (reference utils.py:87-105 uses this to
    pull cuML constructor defaults; here used for sklearn fallback twins)."""
    import inspect

    unsupported_set = unsupported_set or set()
    sig = inspect.signature(func)
    return {
        name: p.default
        for name, p in sig.parameters.items()
        if p.default is not inspect.Parameter.empty and name not in unsupported_set
    }


def dtype_to_float32(arr: np.ndarray) -> np.ndarray:
    if arr.dtype != np.float32:
        return arr.astype(np.float32)
    return arr


def concat_arrays(chunks: List[np.ndarray], order: str = "C") -> np.ndarray:
    """Memory-aware concat of per-batch arrays into one contiguous array
    (reference utils.py:358-400 `_concat_and_free`)."""
    if len(chunks) == 1:
        arr = chunks[0]
        return np.asarray(arr, order=order)  # type: ignore[arg-type]
    total_rows = sum(c.shape[0] for c in chunks)
    if chunks[0].ndim == 1:
        out = np.empty((total_rows,), dtype=chunks[0].dtype)
    else:
        out = np.empty((total_rows, chunks[0].shape[1]), dtype=chunks[0].dtype, order=order)  # type: ignore[call-overload]
    offset = 0
    while chunks:
        c = chunks.pop(0)
        out[offset : offset + c.shape[0]] = c
        offset += c.shape[0]
        del c
    return out


def chunk_rows(n_rows: int, max_bytes: int, row_bytes: int) -> List[Tuple[int, int]]:
    """Split n_rows into (start, end) chunks of at most max_bytes
    (reference clustering.py:437-454 chunking of model rows vs the 2GB limit)."""
    rows_per_chunk = max(1, max_bytes // max(1, row_bytes))
    return [(s, min(s + rows_per_chunk, n_rows)) for s in range(0, n_rows, rows_per_chunk)]


def pad_to_multiple(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple
