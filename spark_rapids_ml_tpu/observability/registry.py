#
# Typed metrics registry — the storage half of the observability subsystem
# (docs/design.md §6d). The pre-observability `profiling.py` kept two flat
# process-global dicts (name -> float seconds, name -> int count); everything
# that wanted richer semantics had to fake them — the HBM batch cache modeled
# its bytes-resident GAUGE as negative counter increments, and per-batch
# latencies collapsed into a single sum that could never answer "p99 ingest
# time". This module gives each semantic its own type, MLlib-style (fit
# summaries as first-class API, arXiv:1505.06807):
#
#   Counter   monotone event count        (retries, uploads, cache hits)
#   Gauge     set/inc/dec current value   (cache.bytes_resident)
#   Histogram exponential-bucket samples  (per-batch ingest/step seconds)
#   span totals  name -> accumulated seconds (the legacy span_totals surface)
#
# All metrics carry optional LABELS (site=, algo=, pass_=...) serialized into
# the key as `name{k=v,...}`; unlabeled metrics keep their bare name, which is
# what keeps every pre-existing `profiling.counter_totals()` assertion working
# unchanged through the compat shims.
#
# A MetricsRegistry is a plain value container: thread-safe, snapshot-able to
# a JSON-serializable dict, and MERGEABLE — `merge_snapshot` is how the driver
# folds per-barrier-worker snapshots into one fit report (spark/integration.py)
# and how a FitRun's scoped registry stays independent of `reset_counters()`
# on the global one (observability/runs.py).
#

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

# default exponential latency buckets: 100us * 2^i, i in [0, 20) — covers one
# fast device step through a ~52 s pathological batch; the +inf bucket is
# implicit (observations above the last bound land in it)
DEFAULT_TIME_BUCKETS: Tuple[float, ...] = tuple(1e-4 * 2.0 ** i for i in range(20))


# the counter-form view of span totals (MetricsRegistry._span_counters)
# srml-metric: span.seconds{span}
# srml-metric: span.calls{span}
SPAN_SECONDS = "span.seconds"
SPAN_CALLS = "span.calls"

# characters with structural meaning in a label key; sanitized out of label
# names/values so split_label_key is a TRUE inverse of label_key — an
# unescaped ','/'=' in a value (e.g. an exception message used as a label)
# would otherwise silently re-key the metric when a worker snapshot merges
_LABEL_STRUCTURAL = str.maketrans({"{": "_", "}": "_", ",": "_", "=": "_"})


def label_key(name: str, labels: Optional[Mapping[str, Any]] = None) -> str:
    """Canonical metric key: `name` or `name{k=v,...}` with sorted label names;
    structural characters in label names/values sanitize to '_'."""
    if not labels:
        return name
    inner = ",".join(
        f"{str(k).translate(_LABEL_STRUCTURAL)}"
        f"={str(labels[k]).translate(_LABEL_STRUCTURAL)}"
        for k in sorted(labels)
    )
    return f"{name}{{{inner}}}"


def split_label_key(key: str) -> Tuple[str, Dict[str, str]]:
    """Inverse of label_key (values come back as strings)."""
    if not key.endswith("}") or "{" not in key:
        return key, {}
    name, _, inner = key.partition("{")
    labels: Dict[str, str] = {}
    for part in inner[:-1].split(","):
        if not part:
            continue
        k, _, v = part.partition("=")
        labels[k] = v
    return name, labels


class _Metric:
    """Shared per-name metric state: a dict of label-key -> value, guarded by
    the owning registry's lock (metrics never outlive their registry)."""

    kind = "metric"

    def __init__(self, name: str, lock: threading.RLock):
        self.name = name
        self._lock = lock
        self._values: Dict[str, Any] = {}

    def _key(self, labels: Optional[Mapping[str, Any]]) -> str:
        return label_key(self.name, labels)


class Counter(_Metric):
    """Monotone event counter. Negative increments are a type error — that is
    exactly the gauge-as-counter hack this registry exists to retire."""

    kind = "counter"

    def inc(self, n: int = 1, **labels: Any) -> None:
        if n < 0:
            raise ValueError(
                f"Counter '{self.name}' increment must be >= 0 (got {n}); "
                "use a Gauge for values that go down."
            )
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0) + n

    def value(self, **labels: Any) -> int:
        with self._lock:
            return self._values.get(self._key(labels), 0)


class Gauge(_Metric):
    """Current-value metric: set to an absolute value or moved by deltas."""

    kind = "gauge"

    def set(self, value: Any, **labels: Any) -> None:
        with self._lock:
            self._values[self._key(labels)] = value

    def inc(self, n: Any = 1, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0) + n

    def dec(self, n: Any = 1, **labels: Any) -> None:
        self.inc(-n, **labels)

    def value(self, **labels: Any) -> Any:
        with self._lock:
            return self._values.get(self._key(labels), 0)


class Histogram(_Metric):
    """Exponential-bucket histogram. Per label-set state is
    {"count": n, "sum": s, "buckets": [per-bucket counts, len(bounds)+1]} —
    the last slot is the +inf bucket. Bounds are upper-inclusive (`v <= le`),
    Prometheus semantics."""

    kind = "histogram"

    def __init__(self, name: str, lock: threading.RLock,
                 buckets: Sequence[float] = DEFAULT_TIME_BUCKETS):
        super().__init__(name, lock)
        self.bounds: Tuple[float, ...] = tuple(sorted(float(b) for b in buckets))

    def observe(self, value: float, exemplar: Any = None,
                **labels: Any) -> None:
        v = float(value)
        # leftmost bound with v <= bound; +inf slot otherwise. Bisection is
        # overkill at 20 bounds; a linear scan stays cache-friendly and cheap.
        idx = len(self.bounds)
        for i, b in enumerate(self.bounds):
            if v <= b:
                idx = i
                break
        key = self._key(labels)
        with self._lock:
            state = self._values.get(key)
            if state is None:
                state = self._values[key] = {
                    "count": 0,
                    "sum": 0.0,
                    "buckets": [0] * (len(self.bounds) + 1),
                    "min": v,
                    "max": v,
                }
            state["count"] += 1
            state["sum"] += v
            state["buckets"][idx] += 1
            # true observed extremes: what q=0.0 / q=1.0 return EXACTLY instead
            # of a bucket-edge interpolation that can overshoot every sample
            if v < state["min"]:
                state["min"] = v
            if v > state["max"]:
                state["max"] = v
            # per-bucket exemplar slot (§6l): one trace_id per bucket,
            # last-write-wins — the freshest trace that landed in this latency
            # band, which is what a /metrics p99 spike resolves through
            if exemplar is not None:
                ex = state.get("exemplars")
                if ex is None:
                    ex = state["exemplars"] = (
                        [None] * (len(self.bounds) + 1))
                ex[idx] = {
                    "value": v,
                    "trace_id": str(exemplar),
                    "labels": dict(labels),
                    "ts": round(time.time(), 6),
                }

    def state(self, **labels: Any) -> Optional[Dict[str, Any]]:
        with self._lock:
            st = self._values.get(self._key(labels))
            if st is None:
                return None
            out = {
                "count": st["count"], "sum": st["sum"],
                "buckets": list(st["buckets"]),
                "min": st.get("min"), "max": st.get("max"),
            }
            ex = st.get("exemplars")
            if ex is not None:
                out["exemplars"] = [
                    dict(e) if e is not None else None for e in ex]
            return out

    def quantile(self, q: float, **labels: Any) -> Optional[float]:
        """Estimated q-quantile with exponential-bucket interpolation (see
        interpolate_quantile). Edge semantics: None when no observations exist
        (an empty histogram has no quantiles — interpolating would fabricate
        one); q<=0.0 returns the true observed minimum and q>=1.0 the true
        observed maximum."""
        st = self.state(**labels)
        if st is None or st["count"] <= 0:
            return None
        return interpolate_quantile(st, q, self.bounds)


class MetricsRegistry:
    """Thread-safe collection of typed metrics + legacy span totals.

    One registry instance backs the process-global metric surface
    (`observability.global_registry()`, which the `profiling` compat shims
    read); every FitRun and barrier-worker scope owns another, fed by the same
    fan-out write path (observability/runs.py), so `reset_counters()` on the
    global registry can never corrupt an in-flight scoped run."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._metrics: Dict[str, _Metric] = {}
        self._span_totals: Dict[str, float] = {}

    # ---- get-or-create (kind-checked: one name, one type) ----

    def _get(self, name: str, kind: type, **kw: Any) -> Any:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = kind(name, self._lock, **kw)
            elif not isinstance(m, kind):
                raise TypeError(
                    f"metric '{name}' already registered as {m.kind}, "
                    f"requested {kind.__name__.lower()}"
                )
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_TIME_BUCKETS) -> Histogram:
        return self._get(name, Histogram, buckets=buckets)

    def legacy_count(self, name: str, n: int) -> None:
        """Signed increment for the legacy `profiling.count()` surface, which
        never distinguished counters from gauges: positive increments create/
        use a Counter; the first NEGATIVE increment retypes the metric to a
        Gauge carrying its accumulated values — a name's kind is discovered
        from usage, so the historical gauge-as-counter pattern (positive then
        negative increments under one name) keeps its arithmetic."""
        with self._lock:
            m = self._metrics.get(name)
            if isinstance(m, Gauge) or (m is None and n < 0):
                self.gauge(name).inc(n)
            elif (m is None or isinstance(m, Counter)) and n >= 0:
                self.counter(name).inc(n)
            elif isinstance(m, Counter):  # first negative on a counter: retype
                g = Gauge(name, self._lock)
                g._values = dict(m._values)
                self._metrics[name] = g
                g.inc(n)
            else:  # name already a histogram etc.: surface the kind conflict
                self.counter(name).inc(n)

    # ---- legacy span totals (profiling.span_totals surface) ----

    def add_span_total(self, name: str, seconds: float) -> None:
        with self._lock:
            self._span_totals[name] = self._span_totals.get(name, 0.0) + seconds

    def span_totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._span_totals)

    def reset_spans(self) -> None:
        with self._lock:
            self._span_totals.clear()

    # ---- flat read surfaces ----

    def _flat(self, kind: str) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        with self._lock:
            for m in self._metrics.values():
                if m.kind != kind:
                    continue
                for key, v in m._values.items():
                    if kind != "histogram":
                        out[key] = v
                        continue
                    st = {"count": v["count"], "sum": v["sum"],
                          "buckets": list(v["buckets"]),
                          "min": v.get("min"), "max": v.get("max"),
                          "bounds": list(m.bounds)}  # type: ignore[attr-defined]
                    ex = v.get("exemplars")
                    if ex is not None:
                        st["exemplars"] = [
                            dict(e) if e is not None else None for e in ex]
                    out[key] = st
        return out

    def _span_counters(self) -> Dict[str, Any]:
        """Every span's seconds and calls in counter form, `span.seconds{span=}`
        and `span.calls{span=}`: a read-time view of what the write path already
        keeps (the span totals and the same-named latency histogram's counts),
        so a reader that sees only counters (a fit report's `counters`, a
        before/after pair of `counter_totals()`) can take a span's seconds per
        operation. A name shows once both halves hold it, so `reset_counters()`
        and `reset_spans()` each empty the view."""
        out: Dict[str, Any] = {}
        with self._lock:
            for name, seconds in self._span_totals.items():
                hist = self._metrics.get(name)
                if not isinstance(hist, Histogram):
                    continue
                calls = sum(st["count"] for st in hist._values.values())
                if calls:
                    out[label_key(SPAN_SECONDS, {"span": name})] = seconds
                    out[label_key(SPAN_CALLS, {"span": name})] = calls
        return out

    def _counters(self) -> Dict[str, Any]:
        out = self._flat("counter")
        out.update(self._span_counters())
        return out

    def counter_totals(self) -> Dict[str, Any]:
        """Counters AND gauges flattened to one name -> value dict — the exact
        legacy `profiling.counter_totals()` surface (pre-observability code
        reported gauges through it as signed counter increments, and its tests
        assert e.g. `totals['cache.bytes_resident'] == 0`) — plus the span
        view of `_span_counters`."""
        out = self._counters()
        out.update(self._flat("gauge"))
        return out

    def reset_counters(self) -> None:
        """Clear counter/gauge/histogram VALUES (metric objects and their
        types/buckets survive — a reset must not let a name change kind)."""
        with self._lock:
            for m in self._metrics.values():
                m._values.clear()

    # ---- snapshot / merge ----

    def snapshot(self) -> Dict[str, Any]:
        """JSON-serializable full state: the payload barrier workers ship to
        the driver and the `metrics` section of a fit report."""
        return {
            "counters": self._counters(),
            "gauges": self._flat("gauge"),
            "histograms": self._flat("histogram"),
            "spans": self.span_totals(),
        }

    def merge_snapshot(self, snap: Mapping[str, Any]) -> None:
        """Fold another registry's snapshot into this one: counters, gauges and
        span totals ADD (a merged gauge is a sum over workers — total bytes
        resident across the pod); histograms merge count/sum/bucket-wise. The
        snapshot's `span.seconds` / `span.calls` are a view of its spans and
        histograms, which merge below: stored as counters they would count
        every span twice."""
        for key, v in (snap.get("counters") or {}).items():
            name, labels = split_label_key(key)
            if name in (SPAN_SECONDS, SPAN_CALLS):
                continue
            self.counter(name).inc(v, **labels)
        for key, v in (snap.get("gauges") or {}).items():
            name, labels = split_label_key(key)
            self.gauge(name).inc(v, **labels)
        for name, secs in (snap.get("spans") or {}).items():
            self.add_span_total(name, secs)
        for key, st in (snap.get("histograms") or {}).items():
            name, labels = split_label_key(key)
            h = self.histogram(name, buckets=st.get("bounds") or DEFAULT_TIME_BUCKETS)
            lkey = label_key(name, labels)
            with self._lock:
                mine = h._values.get(lkey)
                if mine is None:
                    mine = h._values[lkey] = {
                        "count": 0, "sum": 0.0,
                        "buckets": [0] * (len(h.bounds) + 1),
                    }
                mine["count"] += st["count"]
                mine["sum"] += st["sum"]
                for fn, key_mm in ((min, "min"), (max, "max")):
                    other = st.get(key_mm)
                    if other is None:
                        continue
                    ours = mine.get(key_mm)
                    mine[key_mm] = other if ours is None else fn(ours, other)
                theirs: List[int] = list(st["buckets"])
                if len(theirs) == len(mine["buckets"]):
                    mine["buckets"] = [
                        a + b for a, b in zip(mine["buckets"], theirs)
                    ]
                else:  # mismatched bucket layouts: keep count/sum, drop shape
                    mine["buckets"][-1] += sum(theirs)
                # exemplar slots keep last-write-wins across the merge too:
                # per bucket, the later timestamp survives
                theirs_ex = st.get("exemplars")
                if theirs_ex and len(theirs_ex) == len(mine["buckets"]):
                    ex = mine.get("exemplars")
                    if ex is None:
                        ex = mine["exemplars"] = (
                            [None] * len(mine["buckets"]))
                    for i, other in enumerate(theirs_ex):
                        if other is None:
                            continue
                        ours = ex[i]
                        if ours is None or (other.get("ts") or 0) >= (
                                ours.get("ts") or 0):
                            ex[i] = dict(other)


def interpolate_quantile(state: Mapping[str, Any], q: float,
                         bounds: Sequence[float]) -> float:
    """Quantile estimate with WITHIN-bucket interpolation, matched to the
    exponential bucket layout: mass inside a bucket is assumed log-uniform, so
    the estimate is `lo * (hi/lo)**frac` (geometric interpolation — a straight
    linear blend would systematically overestimate low quantiles when bucket
    widths double). The first bucket interpolates linearly from 0; the +inf
    bucket clamps to the largest finite bound (nothing sane to extrapolate to).
    Exact edge semantics: when q*count lands exactly on a bucket's cumulative
    boundary the estimate is that bucket's upper bound — the same
    upper-inclusive convention the buckets themselves use (`v <= le`). States
    that track true observed extremes ("min"/"max" keys, Histogram.observe)
    return them EXACTLY at q<=0.0 / q>=1.0 instead of a bucket-edge estimate;
    legacy states without them keep the interpolated clamp."""
    total = state["count"]
    if total <= 0:
        return math.nan
    q = min(max(float(q), 0.0), 1.0)
    if q <= 0.0 and state.get("min") is not None:
        return float(state["min"])
    if q >= 1.0 and state.get("max") is not None:
        return float(state["max"])
    target = q * total
    bounds = [float(b) for b in bounds]
    seen = 0.0
    for i, c in enumerate(state["buckets"]):
        if c <= 0:
            continue
        if seen + c >= target - 1e-12:
            frac = 0.0 if c == 0 else min(max((target - seen) / c, 0.0), 1.0)
            if i >= len(bounds):  # +inf bucket
                return bounds[-1] if bounds else math.nan
            hi = bounds[i]
            lo = bounds[i - 1] if i > 0 else 0.0
            if lo <= 0.0:
                return hi * frac
            return lo * (hi / lo) ** frac
        seen += c
    return bounds[-1] if bounds else math.nan


def quantile_from_state(state: Mapping[str, Any], q: float,
                        bounds: Sequence[float]) -> float:
    """Approximate quantile from histogram state (upper bound of the bucket the
    q-th sample lands in) — good enough for report summaries; +inf bucket
    reports the largest finite bound."""
    total = state["count"]
    if total <= 0:
        return math.nan
    target = q * total
    seen = 0
    for i, c in enumerate(state["buckets"]):
        seen += c
        if seen >= target and c > 0:
            return float(bounds[i]) if i < len(bounds) else float(bounds[-1])
    return float(bounds[-1])
