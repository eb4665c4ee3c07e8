#!/usr/bin/env python
"""Bench regression gate: compare per-scenario wall times across the two newest
recorded benchmark rounds and fail on a >25% regression.

Inputs are the repo's recorded bench artifacts:

  * `BENCH_r*.json` — driver-captured rounds. Each holds the bench.py JSON line
    (sometimes only as a truncated stdout `tail`), whose `secondary` carries one
    `<scenario>_bench_secs` wall time per benchmark unit (bench.py flushes one
    per completed unit). Scenario times are extracted by regex over the raw
    file text, so a truncated tail still yields every scenario it mentions.
  * `BENCH_TPU_SESSION*.json` — real-TPU session captures, same extraction;
    included when present so a TPU-vs-TPU comparison uses real numbers.

Rules:
  * Only rounds measured on the SAME platform compare (a CPU round vs a TPU
    round says nothing about the code) — mismatches report and pass.
  * A scenario regresses when `new > old * (1 + threshold)`; default threshold
    0.25. Scenarios present in only one round are listed, never failed on.
  * Exit 1 on any regression — unless SRML_BENCH_CHECK_ADVISORY=1, which
    prints the same per-scenario table and always exits 0. ci/test.sh wires
    this gate in as an ADVISORY tier (the recorded rounds predate PR 1);
    export SRML_BENCH_CHECK_ADVISORY=0 to enforce it strictly.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

DEFAULT_THRESHOLD = 0.25

# optional backslashes before the quotes: inside an artifact whose wrapper JSON
# is truncated (unparseable), the bench line's quotes appear escaped (\") and
# the regex must still sweep the raw text
_SECS_RE = re.compile(r'\\?"(\w+)_bench_secs\\?"\s*:\s*([0-9]+(?:\.[0-9]+)?)')
# selection-plane stage times (bench_knn/bench_ann emit `<unit>_select_s`):
# gated like scenario wall times so a selection regression can't hide inside
# a unit whose total time moved for other reasons
_SELECT_RE = re.compile(r'\\?"(\w+)_select_s\\?"\s*:\s*([0-9]+(?:\.[0-9]+)?)')
# measured MFU per scenario (`<unit>_mfu`, observability/device.py): gated
# DIRECTION-AWARE — mfu is higher-is-better, unlike every wall-time key
_MFU_RE = re.compile(
    r'\\?"(\w+_mfu)\\?"\s*:\s*([0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)'
)
# communication plane (`<unit>_comm_frac` / `<unit>_rank_skew`,
# observability/comm.py §6h): both lower-is-better like wall times — a rising
# comm_frac means the scenario spends more of its window on the interconnect,
# a rising rank_skew means the barrier is waiting longer on its slowest rank
_COMM_RE = re.compile(
    r'\\?"(\w+_(?:comm_frac|rank_skew))\\?"\s*:\s*'
    r"([0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)"
)
# live-telemetry overhead (`telemetry_overhead_pct`, §6g): gated against an
# ABSOLUTE budget (default <2%), not a round-over-round ratio — the value sits
# near zero, where ratios of two small noisy numbers are meaningless
_OVERHEAD_RE = re.compile(
    r'\\?"(\w+_overhead_pct)\\?"\s*:\s*(-?[0-9]+(?:\.[0-9]+)?)'
)
# serving plane (`serving_p99_ms` / `serving_failover_p99_ms`, serving/
# design §7/§7c): tail latency of the closed-loop scenarios — lower-is-better
# like wall times, but behind an ABSOLUTE noise floor (see _NOISE_FLOORS:
# single-digit-ms CPU tails are scheduler jitter; ratio-judging two jitter
# samples is noise)
_SERVING_P99_RE = re.compile(
    r'\\?"(serving\w*_p99_ms)\\?"\s*:\s*'
    r"([0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)"
)
# failover-fleet CONTRACT keys (serving/fleet.py, §7c): judged against
# absolute invariants on the NEWEST artifact carrying them — a mid-run
# replica kill must lose zero requests, the restarted replica must rejoin
# with zero compiles, and fault-window throughput must hold >= the frac
# floor of the no-fault baseline. Never ratio-judged: the contract either
# holds or the fleet is broken.
_FAILOVER_RE = re.compile(
    r'\\?"(serving_failover_(?:failed_requests|rejoin_compiles|qps_frac))'
    r'\\?"\s*:\s*(-?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)'
)
DEFAULT_FAILOVER_QPS_FRAC_MIN = 0.8
# autotune plane (`autotune_speedup`, docs/design.md §6i): tuned-vs-default
# ratio of the better-tuned unit — HIGHER is better like mfu, behind an
# absolute noise floor (both rounds hovering at ~1.0 means the table holds
# no real win on this platform; ratio-judging two 1.0-ish samples is noise —
# the gate only engages once a round has shown a genuine tuned win)
_SPEEDUP_RE = re.compile(
    r'\\?"(\w+_speedup)\\?"\s*:\s*([0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)'
)
# ANN lifecycle plane (`ann_build_rows_per_s`, docs/design.md §7b): pipelined
# out-of-core build throughput — HIGHER is better like mfu (the ISSUE-15 gate:
# pipelined build must not fall back under the serial baseline's rate). The
# regex anchors on the exact `_rows_per_s` suffix, so the legacy
# `*_rows_per_sec_per_chip` keys never match
_ROWS_PER_S_RE = re.compile(
    r'\\?"(\w+_rows_per_s)\\?"\s*:\s*([0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)'
)
# zero-copy ingest plane (`ingest_gb_per_s_per_chip`, docs/design.md §6k):
# streamed host->device ingest bandwidth of the single-pass moments fit —
# HIGHER is better like mfu. The exact `_gb_per_s_per_chip` suffix anchors
# the match so no wall-time key can collide
_GBPS_RE = re.compile(
    r'\\?"(\w+_gb_per_s_per_chip)\\?"\s*:\s*'
    r"([0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)"
)
# measurement-noise companion (`*_overhead_noise_pct`, the MAD of the
# scenario's pair deltas): when the noise floor reaches the budget the point
# estimate carries no signal, so the check reports INCONCLUSIVE instead of
# flagging scheduler jitter as a regression
_OVERHEAD_NOISE_RE = re.compile(
    r'\\?"(\w+_overhead_noise_pct)\\?"\s*:\s*(-?[0-9]+(?:\.[0-9]+)?)'
)
DEFAULT_OVERHEAD_BUDGET_PCT = 2.0
_PLATFORM_RE = re.compile(r'\\?"platform\\?"\s*:\s*\\?"(\w+)\\?"')


def _higher_is_better(name: str) -> bool:
    return name.endswith(
        ("_mfu", "_speedup", "_rows_per_s", "_gb_per_s_per_chip")
    )


# absolute noise floors for the comm keys: near zero (CPU-mesh comm_frac sits
# at ~1e-6) a round-over-round ratio compares two noise samples — the same
# rationale as the telemetry-overhead absolute budget above. Values are only
# ratio-judged once EITHER round clears the floor.
_NOISE_FLOORS = (
    ("_comm_frac", 0.01),  # <1% of ICI peak: noise, not a communication story
    ("_rank_skew", 1.5),   # below the straggler threshold: balanced enough
    ("_p99_ms", 5.0),      # single-digit-ms serving tails: scheduler jitter
    ("_speedup", 1.1),     # tuned ~= default on both rounds: nothing to lose
)


def _below_noise_floor(name: str, old: float, new: float) -> bool:
    for suffix, floor in _NOISE_FLOORS:
        if name.endswith(suffix):
            return max(old, new) < floor
    return False


def _round_key(path: str) -> Tuple[int, str]:
    m = re.search(r"BENCH_r(\d+)\.json$", path)
    return (int(m.group(1)) if m else -1, path)


def discover(root: str) -> List[str]:
    """Newest-last list of comparable bench artifacts: all BENCH_r*.json by
    round number, then any BENCH_TPU_SESSION*.json (by name) as the most
    trusted real-hardware captures."""
    rounds = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")), key=_round_key)
    sessions = sorted(glob.glob(os.path.join(root, "BENCH_TPU_SESSION*.json")))
    return rounds + sessions


def extract(path: str) -> Dict[str, object]:
    """Scenario wall times + platform of one bench artifact. Prefers the
    structured `parsed.secondary` when the file carries one; falls back to a
    regex sweep of the raw text (the stdout tail can be truncated mid-line)."""
    with open(path) as f:
        raw = f.read()
    scenarios: Dict[str, float] = {}
    overheads: Dict[str, float] = {}
    overhead_noise: Dict[str, float] = {}
    failover: Dict[str, float] = {}
    platform: Optional[str] = None
    try:
        doc = json.loads(raw)
    except json.JSONDecodeError:
        doc = {}
    parsed = doc.get("parsed") if isinstance(doc, dict) else None
    secondary = (parsed or {}).get("secondary") or {}
    for k, v in secondary.items():
        if k.endswith("_bench_secs") and isinstance(v, (int, float)):
            scenarios[k[: -len("_bench_secs")]] = float(v)
        elif k.endswith("_select_s") and isinstance(v, (int, float)):
            scenarios[k[: -len("_s")]] = float(v)
        elif k.endswith("_mfu") and isinstance(v, (int, float)):
            scenarios[k] = float(v)  # keeps the _mfu suffix: direction marker
        elif k.endswith(("_comm_frac", "_rank_skew")) and isinstance(
            v, (int, float)
        ):
            scenarios[k] = float(v)  # comm plane: lower-is-better default
        elif k.startswith("serving") and k.endswith("_p99_ms") \
                and isinstance(v, (int, float)):
            scenarios[k] = float(v)  # serving tail: lower-is-better + floor
        elif k.startswith("serving_failover_") and k.split("_", 2)[-1] in (
            "failed_requests", "rejoin_compiles", "qps_frac"
        ) and isinstance(v, (int, float)):
            failover[k] = float(v)  # absolute contract keys, never ratios
        elif k.endswith("_speedup") and isinstance(v, (int, float)):
            scenarios[k] = float(v)  # autotune plane: higher-is-better + floor
        elif k.endswith("_rows_per_s") and isinstance(v, (int, float)):
            scenarios[k] = float(v)  # ann build throughput: higher-is-better
        elif k.endswith("_gb_per_s_per_chip") and isinstance(v, (int, float)):
            scenarios[k] = float(v)  # ingest bandwidth: higher-is-better
        elif k.endswith("_overhead_noise_pct") and isinstance(v, (int, float)):
            overhead_noise[k[: -len("_noise_pct")] + "_pct"] = float(v)
        elif k.endswith("_overhead_pct") and isinstance(v, (int, float)):
            overheads[k] = float(v)  # absolute-budget check, never a ratio
    if isinstance(secondary.get("platform"), str):
        platform = secondary["platform"]
    # fall back to regex over DECODED text: inside the artifact the bench line
    # usually lives in the `tail` string field, where every quote is escaped —
    # scanning the raw file would miss it
    texts = [raw]
    if isinstance(doc, dict) and isinstance(doc.get("tail"), str):
        texts.insert(0, doc["tail"])
    for text in texts:
        if scenarios:
            break
        for name, secs in _SECS_RE.findall(text):
            scenarios[name] = float(secs)
        for name, secs in _SELECT_RE.findall(text):
            scenarios[f"{name}_select"] = float(secs)
        for name, v in _MFU_RE.findall(text):
            scenarios[name] = float(v)
        for name, v in _COMM_RE.findall(text):
            scenarios[name] = float(v)
        for name, v in _SERVING_P99_RE.findall(text):
            scenarios[name] = float(v)
        for name, v in _FAILOVER_RE.findall(text):
            failover[name] = float(v)
        for name, v in _SPEEDUP_RE.findall(text):
            scenarios[name] = float(v)
        for name, v in _ROWS_PER_S_RE.findall(text):
            scenarios[name] = float(v)
        for name, v in _GBPS_RE.findall(text):
            scenarios[name] = float(v)
        for name, v in _OVERHEAD_NOISE_RE.findall(text):
            overhead_noise[name[: -len("_noise_pct")] + "_pct"] = float(v)
        for name, v in _OVERHEAD_RE.findall(text):
            overheads[name] = float(v)
    if platform is None:
        for text in texts:
            m = _PLATFORM_RE.findall(text)
            if m:
                platform = m[-1]
                break
    return {
        "path": path,
        "name": os.path.basename(path),
        "platform": platform,
        "scenarios": scenarios,
        "overheads": overheads,
        "overhead_noise": overhead_noise,
        "failover": failover,
    }


def compare(old: Dict[str, object], new: Dict[str, object],
            threshold: float = DEFAULT_THRESHOLD) -> List[Dict[str, object]]:
    """Per-scenario comparison rows, worst regression first."""
    rows: List[Dict[str, object]] = []
    old_s: Dict[str, float] = old["scenarios"]  # type: ignore[assignment]
    new_s: Dict[str, float] = new["scenarios"]  # type: ignore[assignment]
    for name in sorted(set(old_s) | set(new_s)):
        o, n = old_s.get(name), new_s.get(name)
        if o is None or n is None:
            rows.append({"scenario": name, "old_s": o, "new_s": n,
                         "ratio": None, "verdict": "only-one-round"})
            continue
        ratio = n / o if o > 0 else float("inf")
        if _below_noise_floor(name, o, n):
            rows.append({"scenario": name, "old_s": o, "new_s": n,
                         "ratio": ratio, "verdict": "ok (below noise floor)"})
            continue
        if _higher_is_better(name):
            # mfu: new/old BELOW 1-threshold is the regression; above is the win
            verdict = "REGRESSED" if ratio < 1.0 - threshold else (
                "improved" if ratio > 1.0 + threshold else "ok"
            )
        else:
            verdict = "REGRESSED" if ratio > 1.0 + threshold else (
                "improved" if ratio < 1.0 - threshold else "ok"
            )
        rows.append({"scenario": name, "old_s": o, "new_s": n,
                     "ratio": ratio, "verdict": verdict})
    rows.sort(key=lambda r: -(r["ratio"] or 0.0))
    return rows


def render_table(rows: List[Dict[str, object]]) -> str:
    lines = [f"{'scenario':<22} {'old_s':>9} {'new_s':>9} {'ratio':>7}  verdict"]
    for r in rows:
        o = f"{r['old_s']:.1f}" if r["old_s"] is not None else "-"
        n = f"{r['new_s']:.1f}" if r["new_s"] is not None else "-"
        ratio = f"{r['ratio']:.2f}" if r["ratio"] is not None else "-"
        lines.append(
            f"{r['scenario']:<22} {o:>9} {n:>9} {ratio:>7}  {r['verdict']}"
        )
    return "\n".join(lines)


def check_overheads(artifacts: List[Dict[str, object]],
                    advisory: bool = False) -> int:
    """Absolute-budget check for `*_overhead_pct` keys (live-telemetry plane,
    §6g): the NEWEST artifact carrying one is held to the budget (default
    <2%, env SRML_TELEMETRY_OVERHEAD_MAX). One artifact suffices — this is a
    contract check, not a round-over-round comparison."""
    budget = float(os.environ.get(
        "SRML_TELEMETRY_OVERHEAD_MAX", str(DEFAULT_OVERHEAD_BUDGET_PCT)
    ))
    with_overhead = [a for a in artifacts if a.get("overheads")]
    if not with_overhead:
        return 0
    newest = with_overhead[-1]
    noise_by_key = newest.get("overhead_noise") or {}
    n_over = 0
    for name, pct in sorted(newest["overheads"].items()):  # type: ignore[union-attr]
        noise = noise_by_key.get(name)  # type: ignore[union-attr]
        if noise is not None and noise >= budget:
            # the noise floor reached the budget: the point estimate is
            # scheduler jitter, not signal — report, don't judge
            print(
                f"bench_check: {name} = {pct:.2f}% "
                f"(budget {budget:.1f}%, noise ±{noise:.2f}%, {newest['name']})"
                "  INCONCLUSIVE (measurement noise >= budget)"
            )
            continue
        over = pct > budget
        n_over += int(over)
        print(
            f"bench_check: {name} = {pct:.2f}% "
            f"(budget {budget:.1f}%, {newest['name']})"
            + ("  OVER BUDGET" if over else "  ok")
        )
    if n_over and advisory:
        print(
            f"bench_check: ADVISORY — {n_over} overhead key(s) over budget; "
            "not failing (SRML_BENCH_CHECK_ADVISORY=1; set 0 to enforce)"
        )
        return 0
    return n_over


def check_failover(artifacts: List[Dict[str, object]],
                   advisory: bool = False) -> int:
    """Absolute contract check for the failover-fleet keys (serving/fleet.py,
    §7c) on the NEWEST artifact carrying them: a mid-run replica kill must
    lose ZERO requests, the restarted replica must rejoin with ZERO compiles,
    and fault-window qps must hold >= the frac floor (default 0.8, env
    SRML_FAILOVER_QPS_FRAC_MIN) of the no-fault baseline. One artifact
    suffices — the contract either holds or the fleet is broken."""
    frac_min = float(os.environ.get(
        "SRML_FAILOVER_QPS_FRAC_MIN", str(DEFAULT_FAILOVER_QPS_FRAC_MIN)
    ))
    with_failover = [a for a in artifacts if a.get("failover")]
    if not with_failover:
        return 0
    newest = with_failover[-1]
    fo: Dict[str, float] = newest["failover"]  # type: ignore[assignment]
    n_bad = 0
    checks = (
        ("serving_failover_failed_requests", lambda v: v == 0, "== 0"),
        ("serving_failover_rejoin_compiles", lambda v: v == 0, "== 0"),
        ("serving_failover_qps_frac", lambda v: v >= frac_min,
         f">= {frac_min:g}"),
    )
    for name, ok_fn, want in checks:
        v = fo.get(name)
        if v is None:
            continue  # a truncated tail may carry only some of the keys
        ok = ok_fn(v)
        n_bad += int(not ok)
        print(
            f"bench_check: {name} = {v:g} (want {want}, {newest['name']})"
            + ("  ok" if ok else "  CONTRACT VIOLATED")
        )
    if n_bad and advisory:
        print(
            f"bench_check: ADVISORY — {n_bad} failover contract key(s) "
            "violated; not failing (SRML_BENCH_CHECK_ADVISORY=1; set 0 to "
            "enforce)"
        )
        return 0
    return n_bad


def _verdict(overhead_failures: int, failover_failures: int = 0) -> int:
    """Final exit verdict for paths that skipped the wall-time comparison:
    the log's LAST line must agree with the exit code, so an overhead or
    failover failure reported pages earlier is restated here."""
    if overhead_failures or failover_failures:
        parts = []
        if overhead_failures:
            parts.append(
                f"{overhead_failures} telemetry-overhead key(s) over budget"
            )
        if failover_failures:
            parts.append(
                f"{failover_failures} failover contract key(s) violated"
            )
        print(f"bench_check: FAIL — {'; '.join(parts)} (see lines above)")
        return 1
    print("bench_check: OK")
    return 0


def check(root: str, threshold: float = DEFAULT_THRESHOLD,
          advisory: bool = False) -> int:
    artifacts = [extract(p) for p in discover(root)]
    overhead_failures = check_overheads(artifacts, advisory=advisory)
    failover_failures = check_failover(artifacts, advisory=advisory)
    artifacts = [a for a in artifacts if a["scenarios"]]
    if len(artifacts) < 2:
        print(
            "bench_check: fewer than two bench artifacts carry per-scenario "
            f"wall times ({len(artifacts)} found) — skipping wall-time "
            "comparison."
        )
        return _verdict(overhead_failures, failover_failures)
    old, new = artifacts[-2], artifacts[-1]
    print(
        f"bench_check: comparing {old['name']} (platform={old['platform']}) "
        f"-> {new['name']} (platform={new['platform']}), "
        f"threshold +{threshold:.0%}"
    )
    if old["platform"] != new["platform"]:
        print(
            "bench_check: platform mismatch — wall times are not comparable "
            "across backends; skipping wall-time "
            "comparison."
        )
        return _verdict(overhead_failures, failover_failures)
    rows = compare(old, new, threshold)
    print(render_table(rows))
    regressed = [r for r in rows if r["verdict"] == "REGRESSED"]
    if not regressed:
        print("bench_check: no scenario regressed beyond the threshold")
        return _verdict(overhead_failures, failover_failures)
    names = ", ".join(r["scenario"] for r in regressed)
    if advisory:
        print(
            f"bench_check: ADVISORY — {len(regressed)} scenario(s) regressed "
            f">{threshold:.0%} ({names}); not failing "
            "(SRML_BENCH_CHECK_ADVISORY=1; set 0 to enforce)"
        )
        return 0  # advisory covers overhead failures too (already reported)
    print(
        f"bench_check: FAIL — {len(regressed)} scenario(s) regressed "
        f">{threshold:.0%}: {names}"
    )
    return 1


def main(argv: List[str]) -> int:
    root = argv[1] if len(argv) > 1 else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir
    )
    threshold = float(os.environ.get("SRML_BENCH_CHECK_THRESHOLD",
                                     str(DEFAULT_THRESHOLD)))
    advisory = os.environ.get("SRML_BENCH_CHECK_ADVISORY", "") == "1"
    return check(os.path.abspath(root), threshold=threshold, advisory=advisory)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
