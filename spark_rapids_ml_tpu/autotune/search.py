#
# Measurement loop — the search half of the closed-loop autotuner
# (docs/design.md §6i).
#
# Candidates are timed through the EXISTING observability machinery, not a
# parallel harness: every trial kernel is a `compiled_kernel` (the §6f AOT
# cache), so the warmup pass compiles exactly once per candidate signature
# and the timed reps run cached executables; each timed rep runs inside an
# `autotune.trial` span, so the device plane attributes the analyzed
# flops/bytes of the candidate's kernels to it.
#
# Noise handling is median + MAD (`autotune.noise_mads`): reps are taken
# round-robin across candidates (a monotone warming trend cannot flatter
# late candidates), each candidate keeps its median + median-absolute-
# deviation, and a challenger only displaces the default when its median win
# clears `autotune.noise_mads` MADs of the noisier of the two — otherwise
# the DEFAULT is persisted (speedup 1.0), so `load` mode never re-searches
# a bucket the loop already judged inconclusive.
#

from __future__ import annotations

import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import defaults as _defaults
from . import knobs as _knobs
from . import table as _table

# trial operands are capped so an online search triggered by a huge live
# shape stays bounded (the entry still keys on the REAL bucket; the win on
# the capped width is the same per-tile story)
_MAX_TRIAL_N = 1 << 20
_MAX_TRIAL_D = 512
_MAX_TRIAL_K = 1024
_TRIAL_QUERIES = 64

# tile-first: the strategy search times exact_tiled at the freshly tuned
# tile, so a combined run must resolve the tile before judging the strategy
SEARCH_ORDER = (
    "selection.tile",
    "selection.strategy",
    "pallas.topk_geometry",
    "pallas.assign_block",
)


def _backend() -> str:
    from ..ops.selection import _backend as b

    return b()


def _sync(out: Any) -> None:
    """Wait for the device to finish `out` (dispatch is asynchronous; a timing
    without this measures the enqueue)."""
    import jax

    jax.block_until_ready(out)


def _seed_for(key: str) -> int:
    return zlib.crc32(key.encode("utf-8")) & 0x7FFFFFFF


# ------------------------------------------------------------ trial kernels


def _select_trial_kernel():
    """The d2-level selection trial, AOT-cached per (strategy, tile, k)
    signature like every library kernel (defined lazily so importing the
    autotune package never imports jax)."""
    global _SELECT_TRIAL
    if _SELECT_TRIAL is None:
        from ..observability.device import compiled_kernel

        @compiled_kernel(
            "autotune.select_trial", static_argnames=("k", "strategy", "tile")
        )
        def _run(d2, k: int, strategy: str, tile: int):
            from ..ops.selection import select_topk

            return select_topk(d2, k, strategy=strategy, tile=tile)

        _SELECT_TRIAL = _run
    return _SELECT_TRIAL


_SELECT_TRIAL = None


# -------------------------------------------------------------- measurement


def _measure_candidates(
    cands: Dict[str, Callable[[], Any]],
    replicates: int,
    knob: str,
) -> Dict[str, Dict[str, Any]]:
    """Round-robin timed reps per candidate; per-candidate median/MAD."""
    import numpy as np

    from ..observability import runs as _runs

    for fn in cands.values():  # warmup: AOT compile, untimed
        _sync(fn())
    times: Dict[str, List[float]] = {label: [] for label in cands}
    for rep in range(max(int(replicates), 1)):
        for label, fn in cands.items():
            with _runs.span(
                "autotune.trial",
                {"knob": knob, "candidate": label, "rep": rep},
            ):
                t0 = time.perf_counter()
                _sync(fn())
                times[label].append(time.perf_counter() - t0)
    stats: Dict[str, Dict[str, Any]] = {}
    for label, ts in times.items():
        arr = np.asarray(ts, dtype=np.float64)
        med = float(np.median(arr))
        stats[label] = {
            "median_s": med,
            "mad_s": float(np.median(np.abs(arr - med))),
            "trials": len(ts),
        }
    return stats


def _choose(stats: Dict[str, Dict[str, Any]], default_label: str,
            noise_mads: float) -> Tuple[str, float]:
    """(winner label, speedup vs default). A challenger needs its median win
    to clear `noise_mads` MADs of the noisier arm; otherwise the default
    stands and the persisted speedup is exactly 1.0."""
    best = min(stats, key=lambda lb: stats[lb]["median_s"])
    dflt = stats[default_label]
    if best != default_label:
        gap = dflt["median_s"] - stats[best]["median_s"]
        noise = noise_mads * max(stats[best]["mad_s"], dflt["mad_s"])
        if gap <= noise:
            best = default_label
    return best, dflt["median_s"] / max(stats[best]["median_s"], 1e-12)


def _entry(knob: str, bucket: str, dtype: str, value: Any, winner: str,
           speedup: float, stats: Dict[str, Dict[str, Any]],
           default_label: str, trial_shape: Dict[str, int]) -> Dict[str, Any]:
    platform, kind = _table.platform_key()
    st = stats[winner]
    return {
        "knob": knob,
        "bucket": bucket,
        "dtype": dtype,
        "value": value,
        "platform": platform,
        "device_kind": kind,
        "median_s": round(st["median_s"], 6),
        "mad_s": round(st["mad_s"], 6),
        "baseline_s": round(stats[default_label]["median_s"], 6),
        "baseline_mad_s": round(stats[default_label]["mad_s"], 6),
        "speedup": round(speedup, 4),
        "trials": st["trials"],
        "candidates": {
            lb: round(s["median_s"], 6) for lb, s in sorted(stats.items())
        },
        "trial_shape": trial_shape,
        "searched_ts": round(time.time(), 3),
        "provenance": (
            "spark_rapids_ml_tpu.autotune search "
            f"(table v{_table.TABLE_VERSION}); defaults in "
            "spark_rapids_ml_tpu/autotune/defaults.py; docs/design.md §6i"
        ),
    }


# ---------------------------------------------------------------- searchers


def _trial_dims(n: Optional[int], d: Optional[int], k: Optional[int]
                ) -> Tuple[int, int, int]:
    """Trial operand sizes: the REAL requested dims, capped. The entry still
    keys on the pow2 bucket, but candidates must be judged at the triggering
    workload's true width — a tile that wins at the padded bucket width can
    lose at the real one (ragged last-tile padding), and persisting that
    winner would slow the very workload that asked for the search."""
    n_t = min(int(n) if n else 1 << 16, _MAX_TRIAL_N)
    d_t = min(int(d) if d else 64, _MAX_TRIAL_D)
    k_t = min(int(k) if k else 16, _MAX_TRIAL_K)
    return max(n_t, 8), max(d_t, 2), max(k_t, 1)


def _search_selection_tile(n, d, k, dtype, replicates, noise_mads):
    import jax.numpy as jnp
    import numpy as np

    n_t, _, k_t = _trial_dims(n, d, k)
    if n_t <= 4 * k_t:
        return None  # resolve() degrades this bucket to exact_full anyway
    rng = np.random.default_rng(_seed_for(f"selection.tile|{n_t}|{k_t}"))
    d2 = jnp.asarray(
        (rng.normal(size=(_TRIAL_QUERIES, n_t)) ** 2).astype(np.float32)
    )
    backend = _backend()
    default_tile = _defaults.default_select_tile(n_t, backend)
    grid = set(_knobs.KNOBS["selection.tile"].grid)
    grid.update((n_t // 8, n_t // 4, n_t // 2, default_tile))
    # candidate bound mirrors resolve(): any tile < n is legal (resolve's
    # 4k degradation is on n, not the tile); sub-k tiles make degenerate
    # per-tile pools, so floor at k
    cands_vals = sorted(t for t in grid if k_t < t < n_t)[:10]
    if not cands_vals:
        return None
    run = _select_trial_kernel()
    cands: Dict[str, Callable[[], Any]] = {
        str(t): (lambda t=t: run(d2, k_t, "exact_tiled", t))
        for t in cands_vals
    }
    if default_tile in cands_vals:
        default_label = str(default_tile)
    else:
        # default_tile >= n_t: the platform default degrades to exact_full
        # at this bucket (resolve's n <= tile rule) — measure the full-width
        # arm AS the baseline so speedup compares against real default
        # behavior, and a "full" win persists the default tile (which keeps
        # degrading to exact_full: a true behavioral no-op entry)
        cands["full"] = lambda: run(d2, k_t, "exact_full", 0)
        default_label = "full"
    if len(cands) < 2:
        return None
    stats = _measure_candidates(cands, replicates, "selection.tile")
    winner, speedup = _choose(stats, default_label, noise_mads)
    # a "full" winner means no tile beats the default path: persist the
    # default tile (a behavioral no-op entry) so load mode never re-searches
    value = default_tile if winner == "full" else int(winner)
    bucket = _knobs.bucket_for(_knobs.KNOBS["selection.tile"], n, None, k)
    return _entry(
        "selection.tile", bucket, dtype, value, winner, speedup, stats,
        default_label, {"n": n_t, "k": k_t, "nq": _TRIAL_QUERIES},
    )


def _search_selection_strategy(n, d, k, dtype, replicates, noise_mads):
    import jax.numpy as jnp
    import numpy as np

    n_t, _, k_t = _trial_dims(n, d, k)
    if n_t <= 4 * k_t:
        return None
    rng = np.random.default_rng(_seed_for(f"selection.strategy|{n_t}|{k_t}"))
    d2 = jnp.asarray(
        (rng.normal(size=(_TRIAL_QUERIES, n_t)) ** 2).astype(np.float32)
    )
    backend = _backend()
    # tile for the exact_tiled arm: the freshly searched table entry when one
    # exists (SEARCH_ORDER runs the tile first), else the platform default
    tbl = _table.load_table()
    tile_entry = tbl.get(_table.entry_key(
        "selection.tile",
        _knobs.bucket_for(_knobs.KNOBS["selection.tile"], n, None, k), dtype,
    ))
    tile = None
    if tile_entry is not None:
        tile = _knobs._coerce_value(
            _knobs.KNOBS["selection.tile"], tile_entry.get("value")
        )
    if tile is None:
        tile = _defaults.default_select_tile(n_t, backend)
    tile = min(int(tile), max(n_t - 1, 1))
    # exactness="bit": the search may only choose among strategies whose
    # outputs are bit-identical to each other AND to the default path. Where
    # the platform default is `approx` (TPU auto), ANY exact winner would
    # return a different id set than a table-less run — faster and more
    # accurate, but not reproducible across table-present/absent
    # environments — so the knob is simply not searched there: the
    # approx-vs-exact tradeoff belongs to the user (knn.recall_target), not
    # to a wall-time search.
    default_strategy = "approx" if backend == "tpu" else "exact_tiled"
    if default_strategy not in ("exact_full", "exact_tiled"):
        return None
    cand_strategies = ["exact_full", "exact_tiled"]
    run = _select_trial_kernel()
    cands = {
        s: (lambda s=s: run(d2, k_t, s, tile if s == "exact_tiled" else 0))
        for s in cand_strategies
    }
    stats = _measure_candidates(cands, replicates, "selection.strategy")
    winner, speedup = _choose(stats, default_strategy, noise_mads)
    bucket = _knobs.bucket_for(_knobs.KNOBS["selection.strategy"], n, None, k)
    return _entry(
        "selection.strategy", bucket, dtype, winner, winner, speedup, stats,
        default_strategy, {"n": n_t, "k": k_t, "nq": _TRIAL_QUERIES, "tile": tile},
    )


def _search_topk_geometry(n, d, k, dtype, replicates, noise_mads):
    if _backend() != "tpu":
        return None  # off-TPU the fused scan runs the interpreter: no signal
    import jax.numpy as jnp
    import numpy as np

    from ..ops.pallas_select import _topk_geometry, fused_topk, topk_fits_vmem

    n_t, d_t, k_t = _trial_dims(n, d, k)
    rng = np.random.default_rng(_seed_for(f"pallas.topk_geometry|{n_t}|{d_t}"))
    X = jnp.asarray(rng.normal(size=(n_t, d_t)).astype(np.float32))
    Q = X[:_TRIAL_QUERIES]
    ones = jnp.ones((n_t,), bool)
    dq, dt = _topk_geometry(_TRIAL_QUERIES, n_t, d_t, k_t, None, None)
    geoms = {(dq, dt)}
    for qb in (dq // 2, dq, dq * 2):
        for t in (dt // 2, dt, dt * 2):
            # candidates run as PINNED values (pins bypass the shrink
            # loop), so each must pass the kernel's own fit predicate
            if (
                _defaults.MIN_QUERY_BLOCK <= qb
                and _defaults.MIN_ITEM_TILE <= t <= n_t
                and topk_fits_vmem(qb, t, d_t, k_t)
            ):
                geoms.add((qb, t))
    cands = {
        f"{qb}x{t}": (lambda qb=qb, t=t: fused_topk(
            Q, X, ones, k_t, q_block=qb, item_tile=t
        ))
        for qb, t in sorted(geoms)
    }
    default_label = f"{dq}x{dt}"
    stats = _measure_candidates(cands, replicates, "pallas.topk_geometry")
    winner, speedup = _choose(stats, default_label, noise_mads)
    wq, wt = (int(x) for x in winner.split("x"))
    bucket = _knobs.bucket_for(_knobs.KNOBS["pallas.topk_geometry"], n, d, k)
    return _entry(
        "pallas.topk_geometry", bucket, dtype, [wq, wt], winner, speedup,
        stats, default_label, {"n": n_t, "d": d_t, "k": k_t},
    )


def _search_assign_block(n, d, k, dtype, replicates, noise_mads):
    if _backend() != "tpu":
        return None
    import jax.numpy as jnp
    import numpy as np

    from ..ops.pallas_select import (
        _assign_geometry,
        _assign_n_split,
        assign_block_fits_vmem,
        fused_assign,
    )

    n_t, d_t, k_t = _trial_dims(n, d, k)
    n_split = _assign_n_split()
    rng = np.random.default_rng(_seed_for(f"pallas.assign_block|{d_t}|{k_t}"))
    X = jnp.asarray(rng.normal(size=(n_t, d_t)).astype(np.float32))
    centers = X[:k_t]
    default_blk = _assign_geometry(d_t, k_t, n_split, n_t)
    if default_blk is None:
        return None  # nothing placeable: the XLA path owns this bucket
    grid = {
        b for b in _knobs.KNOBS["pallas.assign_block"].grid
        if _defaults.MIN_ASSIGN_BLOCK <= b <= n_t
        # candidates run as PINNED blocks, so each must pass the kernel's
        # own fit predicate — including blocks ABOVE the default start,
        # which _assign_geometry itself would never propose
        and assign_block_fits_vmem(b, d_t, k_t, n_split)
    }
    grid.add(default_blk)
    if len(grid) < 2:
        return None
    cands = {
        str(b): (lambda b=b: fused_assign(X, centers, block=b))
        for b in sorted(grid)
    }
    stats = _measure_candidates(cands, replicates, "pallas.assign_block")
    winner, speedup = _choose(stats, str(default_blk), noise_mads)
    bucket = _knobs.bucket_for(_knobs.KNOBS["pallas.assign_block"], n, d, k)
    return _entry(
        "pallas.assign_block", bucket, dtype, int(winner), winner, speedup,
        stats, str(default_blk), {"n": n_t, "d": d_t, "k": k_t},
    )


_SEARCHERS: Dict[str, Callable] = {
    "selection.tile": _search_selection_tile,
    "selection.strategy": _search_selection_strategy,
    "pallas.topk_geometry": _search_topk_geometry,
    "pallas.assign_block": _search_assign_block,
}


# ------------------------------------------------------------ entry points


def search_knob(name: str, *, n: Optional[int] = None, d: Optional[int] = None,
                k: Optional[int] = None, dtype: str = "float32",
                replicates: Optional[int] = None) -> Optional[Dict[str, Any]]:
    """Search ONE knob for one shape bucket: run its measurement trials,
    persist the winning entry into the platform table (atomic write), and
    return the entry. None when the knob has no searcher, the platform gives
    no signal (e.g. pallas geometry off-TPU), or the bucket degenerates.

    Trials run under the `searching` thread-local, so any lookup() a trial's
    own host wrapper makes resolves to pure defaults — a search can never
    recurse into itself."""
    searcher = _SEARCHERS.get(name)
    if searcher is None:
        return None
    from .. import config as _config

    if replicates is None:
        replicates = int(_config.get("autotune.replicates"))
    noise_mads = float(_config.get("autotune.noise_mads"))
    _knobs._tl.searching = True
    try:
        entry = searcher(n, d, k, dtype, replicates, noise_mads)
    finally:
        _knobs._tl.searching = False
    if entry is None:
        return None
    tbl = _table.load_table()
    tbl.put(_table.entry_key(name, entry["bucket"], dtype), entry)
    tbl.save()
    return entry


def run_search(knob_names: Optional[List[str]] = None,
               shapes: Optional[List[Tuple[int, int, int]]] = None,
               dtype: str = "float32",
               replicates: Optional[int] = None) -> Dict[str, Any]:
    """The offline CLI's search sweep: every requested searchable knob over
    every (n, d, k) shape, tile before strategy (SEARCH_ORDER). Returns the
    summary the CLI prints; entries are persisted as each knob finishes, so
    an interrupted sweep keeps its completed work."""
    if knob_names is None:
        knob_names = [
            kn for kn in SEARCH_ORDER if _knobs.KNOBS[kn].searchable
        ]
    for kn in knob_names:
        if kn not in _knobs.KNOBS:
            raise KeyError(
                f"unknown knob '{kn}'; known: {sorted(_knobs.KNOBS)}"
            )
    ordered = sorted(
        knob_names,
        key=lambda kn: SEARCH_ORDER.index(kn) if kn in SEARCH_ORDER else 99,
    )
    if shapes is None:
        shapes = [(1 << 16, 64, 16)]
    t0 = time.perf_counter()
    results: List[Dict[str, Any]] = []
    skipped: List[Dict[str, str]] = []
    done: set = set()  # (knob, bucket, dtype) searched THIS sweep
    for n, d, k in shapes:
        for kn in ordered:
            knob = _knobs.KNOBS[kn]
            if not knob.searchable:
                skipped.append({"knob": kn, "reason": "not searchable"})
                continue
            # two requested shapes can land in one bucket (a knob may key on
            # a subset of the dims): re-searching it would just overwrite
            # the first result with duplicate work
            key = (kn, _knobs.bucket_for(knob, n, d, k), dtype)
            if key in done:
                skipped.append(
                    {"knob": kn, "reason": f"bucket {key[1]} already searched"}
                )
                continue
            entry = search_knob(
                kn, n=n, d=d, k=k, dtype=dtype, replicates=replicates
            )
            done.add(key)
            if entry is None:
                skipped.append(
                    {"knob": kn, "reason": "no signal on this platform/shape"}
                )
            else:
                results.append(entry)
    tbl = _table.load_table()
    return {
        "table_path": tbl.path,
        "table_entries": len(tbl),
        "platform": tbl.platform,
        "device_kind": tbl.device_kind,
        "results": results,
        "skipped": skipped,
        "search_s": round(time.perf_counter() - t0, 3),
    }
