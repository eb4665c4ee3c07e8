#
# Communication-plane observability: HLO collective accounting, per-rank skew
# and straggler detection, and the barrier timeline (docs/design.md §6h).
#
# §6d–§6g lit the single-process axis end to end; the DISTRIBUTED axis stayed
# dark: XLA inserts the collectives (the whole point of the one-SPMD-program
# architecture, design.md §1) and nothing measured them, and per-rank skew was
# invisible even though arXiv:1612.01437 identifies straggler/partition-skew
# handling as the dominant cost of distributed Spark ML. Two things live
# here:
#
#   * Collective accounting — the ONE place in the tree that parses optimized
#     HLO text for collective ops (fence/hlo-parse-off-plane bans the dash-spelled
#     opcode patterns everywhere else, exactly like the top-k and
#     cost_analysis bans). `extract_collectives` walks an executable's HLO
#     once per (kernel, signature) — observability/device.py calls it from
#     `_compile_and_capture` — and records op counts, payload bytes (result
#     shape × dtype width) and replica-group shape per kind. Kinds use
#     underscore spellings (`all_reduce`, `all_gather`, `reduce_scatter`,
#     `collective_permute`, `all_to_all`) so callers never need the HLO text
#     forms. Per call, analyzed bytes aggregate as
#     `comm.collective_ops{kind=,kernel=}` / `comm.collective_bytes{...}` and
#     attribute to the innermost open span like flops/bytes do.
#
#   * Rank skew & stragglers — worker-scope snapshots (barrier fit tasks,
#     transform partitions) carry per-rank wall time, rows and bytes per
#     phase (observability/runs.py::WorkerScope.note_phase). On every
#     driver-side snapshot merge the per-phase skew ratio (max/median) lands
#     in the run-scoped `comm.rank_skew{phase=}` gauge, and a rank whose wall
#     time exceeds `observability.straggler_threshold` × median emits ONE
#     `straggler` event into the run's event log, the flight-recorder ring
#     and `comm.stragglers{phase=}`. `rank_timeline` assembles the per-rank
#     barrier timeline (start/end per phase, skew, straggler flags) served
#     live by `/runs/<run_id>/ranks` (observability/server.py), exported in
#     the run report's `ranks` section, and carried by postmortem bundles so
#     a degraded barrier fit's dump shows WHICH rank was slow.
#

from __future__ import annotations

import re
import statistics
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence

from .. import config as _config
from ..utils import get_logger

_logger = get_logger("observability.comm")

# HLO opcode (dash spelling, only legal here) -> canonical kind (underscore
# spelling, what every metric label / record key / caller uses)
_HLO_KINDS: Dict[str, str] = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "collective-permute": "collective_permute",
    "all-to-all": "all_to_all",
}

COLLECTIVE_KINDS = tuple(_HLO_KINDS.values())

# HLO primitive type -> bytes per element (token/opaque types count as 0)
_DTYPE_BYTES: Dict[str, int] = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# one DEFINITION line: `%name = <shape> <opcode>(...` — an optional -start
# suffix is the async launch (counted); the paired -done op re-references the
# start's result and must NOT match (it would double-count the payload).
# Operand USES of a collective's result (`fusion(... %all-reduce.8 ...)`)
# never match: the opcode must sit between the result shape and its `(`.
# A tuple shape may nest parentheses — TPU tiled layouts spell them
# (`f32[20,128]{1,0:T(8,128)}`), and XLA's all-reduce combiner on TPU merges a
# fit's reductions into ONE tuple-shaped op — so the tuple alternative runs
# lazily to the `)` that is followed by the opcode, not to the first `)`.
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<shape>\(.*?\)|\S+)\s+"
    r"(?P<op>" + "|".join(re.escape(k) for k in _HLO_KINDS) + r")"
    r"(?P<start>-start)?\(",
    re.MULTILINE,
)

_ARRAY_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

# replica_groups={{0,1},{2,3}} (explicit lists) or the iota form
# replica_groups=[2,4]<=[8] (newer XLA)
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[0-9,{}\s]*\}\}|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)"
)


def _shape_bytes(shape: str) -> int:
    """Payload bytes of one HLO result shape (array or tuple): dtype width ×
    element count, summed over tuple elements. Layout suffixes (`{1,0}`) and
    dynamic-dimension markers are ignored by construction of the regex."""
    total = 0
    for dtype, dims in _ARRAY_RE.findall(shape):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        n = 1
        for d in dims.split(","):
            d = d.strip()
            if d:
                n *= int(d)
        total += width * n
    return total


def extract_collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """Every collective DEFINITION in an optimized-HLO text dump, in program
    order: `{"kind", "bytes", "shape", "replica_groups", "async"}` per op.
    `bytes` is the result-shape payload (the data the collective lands);
    `replica_groups` is the raw group attribute string (iota or explicit),
    empty when the op carries none."""
    out: List[Dict[str, Any]] = []
    for m in _OP_RE.finditer(hlo_text):
        line_end = hlo_text.find("\n", m.end())
        rest = hlo_text[m.end(): line_end if line_end >= 0 else len(hlo_text)]
        g = _GROUPS_RE.search(rest)
        out.append(
            {
                "kind": _HLO_KINDS[m.group("op")],
                "bytes": _shape_bytes(m.group("shape")),
                "shape": m.group("shape"),
                "replica_groups": g.group(1) if g else "",
                "async": bool(m.group("start")),
            }
        )
    return out


def collective_summary(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """Per-kind aggregation of `extract_collectives`:
    `{kind: {"ops": N, "bytes": total, "replica_groups": [distinct...]}}`.
    Kinds with zero ops are absent — an empty dict means a collective-free
    program (the single-device / fully-local case)."""
    summary: Dict[str, Dict[str, Any]] = {}
    for rec in extract_collectives(hlo_text):
        st = summary.setdefault(
            rec["kind"], {"ops": 0, "bytes": 0, "replica_groups": []}
        )
        st["ops"] += 1
        st["bytes"] += rec["bytes"]
        if rec["replica_groups"] and rec["replica_groups"] not in st["replica_groups"]:
            st["replica_groups"].append(rec["replica_groups"])
    return summary


def collectives_from_executable(exe: Any) -> Optional[Dict[str, Dict[str, Any]]]:
    """Collective summary of a compiled executable (its post-SPMD optimized
    module). Returns None when the runtime exposes no HLO text — callers
    (observability/device.py) treat that as "no collective accounting", never
    an error."""
    as_text = getattr(exe, "as_text", None)
    if not callable(as_text):
        return None
    try:
        text = as_text()
    except Exception as e:
        _logger.debug("executable as_text() failed: %s", e)
        return None
    if not text:
        return None
    return collective_summary(text)


def collectives_of_computation(fn: Any, *args: Any,
                               static_argnames: Sequence[str] = ()) -> Dict[str, Dict[str, Any]]:
    """jit → lower → compile `fn` on `args` and summarize its collectives —
    the one source of truth the communication-optimality tests
    (tests/test_collective_counts.py) assert through."""
    import jax

    jitted = jax.jit(fn, static_argnames=tuple(static_argnames))
    exe = jitted.lower(*args).compile()
    return collectives_from_executable(exe) or {}


# ------------------------------------------- per-rank skew / barrier timeline


def straggler_threshold() -> float:
    try:
        return float(_config.get("observability.straggler_threshold"))
    except (TypeError, ValueError):
        return 1.5


def straggler_min_wall_s() -> float:
    """Absolute wall-time floor under which a rank is never flagged: a ratio
    over millisecond-scale phases is GIL/scheduler jitter, not a straggler —
    without the floor an ordinary barrier fit's ~ms `collect` phase trips the
    1.5x threshold on noise alone."""
    try:
        return float(_config.get("observability.straggler_min_wall_s"))
    except (TypeError, ValueError):
        return 0.25


def _median(values: List[float]) -> float:
    return float(statistics.median(values))


def rank_timeline(workers: Sequence[Mapping[str, Any]],
                  threshold: Optional[float] = None) -> Dict[str, Any]:
    """Assemble merged worker snapshots into the barrier timeline: one entry
    per rank (wall time, start ts, per-phase start/end/rows/bytes, its worst
    skew ratio, straggler flag) plus per-phase max/median skew ratios and the
    straggler rank list. `task` is the implicit whole-scope phase every
    snapshot carries via its `wall_s`. Skew is only defined from 2 ranks up
    (a median of one is the rank itself), and a rank is only FLAGGED when its
    phase wall also clears `observability.straggler_min_wall_s` — a ratio
    over a millisecond-scale phase is scheduling noise, not a straggler."""
    thr = straggler_threshold() if threshold is None else float(threshold)
    min_wall = straggler_min_wall_s()
    per_rank: Dict[Any, Dict[str, Any]] = {}
    for w in workers:
        rank = w.get("rank")
        entry = per_rank.setdefault(rank, {
            "rank": rank,
            "wall_s": None,
            "started_ts": w.get("started_ts"),
            "rows": 0,
            "bytes": 0,
            "phases": {},
            "skew": None,
            "skew_phase": None,
            "straggler": False,
        })
        if w.get("wall_s") is not None:
            entry["wall_s"] = max(entry["wall_s"] or 0.0, float(w["wall_s"]))
        for phase, st in (w.get("phases") or {}).items():
            ph = entry["phases"].setdefault(phase, {
                "wall_s": 0.0, "rows": 0, "bytes": 0,
                "start_ts": None, "end_ts": None,
            })
            ph["wall_s"] += float(st.get("wall_s") or 0.0)
            ph["rows"] += int(st.get("rows") or 0)
            ph["bytes"] += int(st.get("bytes") or 0)
            for key, pick in (("start_ts", min), ("end_ts", max)):
                v = st.get(key)
                if v is not None:
                    ph[key] = v if ph[key] is None else pick(ph[key], v)
        # top-level rows/bytes are the rank's LARGEST phase, not a sum: the
        # same partition rides several phases (collect rows == fit rows), and
        # summing would double-count it in the timeline
        entry["rows"] = max(
            (int(ph["rows"]) for ph in entry["phases"].values()), default=0
        )
        entry["bytes"] = max(
            (int(ph["bytes"]) for ph in entry["phases"].values()), default=0
        )
    # per-phase walls across ranks; named phases FIRST so that on a tied skew
    # ratio the rank's `skew_phase` names the informative phase, not the
    # implicit whole-scope `task` catch-all
    phase_walls: Dict[str, List[Any]] = {}
    for entry in per_rank.values():
        for phase, ph in entry["phases"].items():
            phase_walls.setdefault(phase, []).append(
                (entry["rank"], float(ph["wall_s"]))
            )
    for entry in per_rank.values():
        if entry["wall_s"] is not None:
            phase_walls.setdefault("task", []).append(
                (entry["rank"], float(entry["wall_s"]))
            )
    skew: Dict[str, float] = {}
    stragglers: set = set()
    for phase, pairs in phase_walls.items():
        walls = [wll for _, wll in pairs]
        if len(walls) < 2:
            continue
        med = _median(walls)
        if med <= 0:
            continue
        skew[phase] = round(max(walls) / med, 4)
        for rank, wll in pairs:
            ratio = wll / med
            entry = per_rank[rank]
            if entry["skew"] is None or ratio > entry["skew"]:
                entry["skew"] = round(ratio, 4)
                entry["skew_phase"] = phase
            if ratio > thr and wll >= min_wall:
                entry["straggler"] = True
                stragglers.add(rank)
    ranks = sorted(
        per_rank.values(),
        key=lambda e: (e["rank"] is None, e["rank"]),
    )
    return {
        "ranks": ranks,
        "skew": skew,
        "stragglers": sorted(stragglers, key=lambda r: (r is None, r)),
        "threshold": thr,
    }


def note_worker_merge(run: Any) -> None:
    """FitRun.add_worker_snapshot hook: recompute the rank timeline over the
    run's merged snapshots, land the per-phase skew ratios in the RUN-scoped
    `comm.rank_skew{phase=}` gauges (plus the process-global registry — a
    dashboard scraping /metrics sees skew without joining runs), and emit ONE
    `straggler` event per newly-detected slow rank into the run's event log,
    the flight recorder and `comm.stragglers{phase=}`. Must never raise — it
    sits on the fit-result merge path of a barrier stage that already
    SUCCEEDED.

    Events are emitted from a STREAMING prefix (snapshots merge one at a
    time) and cannot be retracted, so they only fire once >= 3 ranks are
    visible — a max/median over two ranks flags whichever happens to be
    slower, and an early skewed prefix would stamp a permanent false alert
    on a normal rank. The timeline itself (`rank_view`, the report's `ranks`
    section, `/runs/<id>/ranks`) is always recomputed over the full merged
    set: treat events as alerts, the timeline as truth."""
    from . import flight as _flight
    from . import runs as _runs

    timeline = run.rank_view()
    if not timeline["ranks"]:
        return
    regs = [run.registry, _runs.global_registry()]
    for phase, ratio in timeline["skew"].items():
        for reg in regs:
            reg.gauge("comm.rank_skew").set(ratio, phase=phase)
    if len(timeline["ranks"]) < 3:
        return  # prefix too small for a defensible, unretractable alert
    seen = getattr(run, "_straggler_ranks", None)
    if seen is None:
        seen = run._straggler_ranks = set()
    thr = timeline["threshold"]
    for entry in timeline["ranks"]:
        if not entry["straggler"] or entry["rank"] in seen:
            continue
        seen.add(entry["rank"])
        worst_phase = entry.get("skew_phase") or "task"
        event = {
            "ts": round(time.time(), 6),
            "kind": "straggler",
            "rank": entry["rank"],
            "phase": worst_phase,
            "ratio": entry["skew"],
            "threshold": thr,
            "wall_s": entry["wall_s"],
        }
        run.add_event(event)
        _flight.note_event(event)
        for reg in regs:
            reg.counter("comm.stragglers").inc(1, phase=worst_phase)
        _logger.warning(
            "straggler: rank %s ran %.2fx the median in phase '%s' "
            "(threshold %.2fx)", entry["rank"], entry["skew"] or 0.0,
            worst_phase, thr,
        )
