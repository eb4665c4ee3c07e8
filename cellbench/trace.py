"""From a profiler trace to numbers: the one reduction every PR shares.

`load_events` flattens an `.xplane.pb` (read with `jax.profiler.ProfileData`,
nothing else) into plain tuples; everything after that is arithmetic on those
tuples, so the tests run it on a small recorded trace kept as JSON.

An event is `(plane, line, name, start_ns, dur_ns, module)`:

* a device plane is named `/device:TPU:<i>`; its line `XLA Ops` holds one event
  per HLO operation that ran (a `while` and the operations of its body overlap,
  so busy time is the UNION of intervals, never their sum), and its line
  `XLA Modules` one event per execution of a compiled program;
* host spans are the `jax.profiler.TraceAnnotation`s of the program
  (`KMeans.prepare`, `KMeans.fit`, ...) and of the harness (`cellbench.<op>`),
  found by name on the host plane's thread lines.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, str, str, float, float, str]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_op(name: str) -> str:
    """`%fusion.28 = (...) fusion(...)` -> `fusion.28`."""
    return name.split(" = ", 1)[0].lstrip("%")


def short_module(name: str) -> str:
    """`jit_lloyd_fit(1234)` -> `jit_lloyd_fit`."""
    return name.split("(", 1)[0]


def load_events(xplane_path: str, span_names: Iterable[str] = ()) -> List[Event]:
    """Device events of every TPU plane, and the host events whose name is in
    `span_names` or starts with `cellbench.`. Operation names are cut to the
    HLO name, and each operation carries the compiled program it ran inside
    (by time, from the modules line: the chip's trace does not say)."""
    from jax.profiler import ProfileData

    wanted = set(span_names)
    out: List[Event] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device:
                    name = short_op(ev.name) if line.name == OPS_LINE else short_module(ev.name)
                    out.append((plane.name, line.name, name, float(ev.start_ns),
                                float(ev.duration_ns), ""))
                elif ev.name in wanted or ev.name.startswith("cellbench."):
                    out.append((plane.name, line.name, ev.name, float(ev.start_ns),
                                float(ev.duration_ns), ""))
    return attribute_modules(out)


def attribute_modules(events: Sequence[Event]) -> List[Event]:
    """Give each device operation the name of the program execution whose
    interval holds the operation's start."""
    out = list(events)
    for plane in device_planes(events):
        mods = sorted((e[3], e[3] + e[4], e[2]) for e in events
                      if e[0] == plane and e[1] == MODULES_LINE)
        starts = [m[0] for m in mods]
        for i, e in enumerate(out):
            if e[0] == plane and e[1] == OPS_LINE and not e[5] and mods:
                j = bisect.bisect_right(starts, e[3]) - 1
                if j >= 0 and e[3] <= mods[j][1]:
                    out[i] = e[:5] + (mods[j][2],)
    return out


def device_planes(events: Sequence[Event]) -> List[str]:
    return sorted({e[0] for e in events if e[0].startswith(DEVICE_PREFIX)})


def union_intervals(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(intervals, lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def busy_intervals(events: Sequence[Event], plane: str, lo: float, hi: float):
    """Union of the plane's operation intervals inside [lo, hi] (ns)."""
    return union_intervals(_clip(
        ((e[3], e[3] + e[4]) for e in events if e[0] == plane and e[1] == OPS_LINE),
        lo, hi))


def busy_seconds(events: Sequence[Event], lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = sum(e - s for p in planes for s, e in busy_intervals(events, p, lo, hi))
    return total / len(planes) / 1e9


def _seconds_of(events: Sequence[Event], lo: float, hi: float, picks) -> float:
    """Seconds of the union of the device events chosen by the first of
    `picks` that chooses any, averaged over the device planes."""
    planes = device_planes(events)
    total = 0.0
    for p in planes:
        for pick in picks:
            found = [(e[3], e[3] + e[4]) for e in events if e[0] == p and pick(e)]
            if found:
                total += sum(e - s for s, e in union_intervals(_clip(found, lo, hi)))
                break
    return total / max(len(planes), 1) / 1e9


def program_seconds(events: Sequence[Event], match: str, lo: float, hi: float) -> float:
    """Device seconds of the compiled programs whose name contains `match`:
    the operations attributed to such a module, or (where operations carry no
    module) its executions on the modules line."""
    return _seconds_of(events, lo, hi, (
        lambda e: e[1] == OPS_LINE and match in e[5],
        lambda e: e[1] == MODULES_LINE and match in e[2]))


def op_seconds(events: Sequence[Event], match: str, lo: float, hi: float) -> float:
    """Device seconds of the operations whose own name contains `match`."""
    return _seconds_of(events, lo, hi, (lambda e: e[1] == OPS_LINE and match in e[2],))


def host_spans(events: Sequence[Event], name: str, lo: float, hi: float):
    """(start, end) of the host spans called `name` that start inside [lo, hi]."""
    return sorted((e[3], e[3] + e[4]) for e in events
                  if not e[0].startswith(DEVICE_PREFIX) and e[2] == name
                  and lo <= e[3] <= hi)


def window_of(events: Sequence[Event], op_span: str) -> Tuple[float, float]:
    """The traced window: first start to last end of the harness's own
    per-operation spans."""
    spans = [(e[3], e[3] + e[4]) for e in events
             if not e[0].startswith(DEVICE_PREFIX) and e[2] == op_span]
    if not spans:
        raise ValueError(f"no host span {op_span!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def top_device_ops(events: Sequence[Event], lo: float, hi: float, n: int = 10):
    """[name, seconds] of the operations that took most device time (first
    device plane; sums per name, `module/op`)."""
    planes = device_planes(events)
    if not planes:
        return []
    acc: Dict[str, float] = {}
    for e in events:
        if e[0] == planes[0] and e[1] == OPS_LINE and lo <= e[3] <= hi:
            key = f"{e[5]}/{e[2]}" if e[5] else e[2]
            acc[key[:64]] = acc.get(key[:64], 0.0) + e[4] / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_span(events: Sequence[Event], lo: float, hi: float, n: int = 10):
    """[span, seconds]: the first device plane's idle time inside [lo, hi],
    each gap charged to the innermost host span open at the gap's middle
    (`outside_any_span` where none is)."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = busy_intervals(events, planes[0], lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [(e[3], e[3] + e[4], e[2]) for e in events
             if not e[0].startswith(DEVICE_PREFIX)]
    acc: Dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name = min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ else "outside_any_span"
        acc[name] = acc.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
