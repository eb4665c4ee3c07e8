#
# Drift detection over the convergence plane: is a fresh update batch's
# per-row signal (inertia / loss / residual) the fit-time distribution's
# noise, or a new distribution?
#
# The judgment is the one `autotune.noise_mads` makes: a robust location (median) plus a MAD noise floor,
# and a challenger only counts as DIFFERENT beyond `continual.drift_mads`
# MADs of separation. The baseline seeds from the fit-time convergence tail
# when a fit report is available (`baseline_from_convergence`); otherwise the
# detector self-calibrates on the first `continual.min_baseline` observations
# before it may fire. In-distribution observations keep extending the rolling
# window (trends adapt); drifted observations are NOT absorbed, so a sustained
# shift keeps firing instead of normalizing itself away.
#
# A firing emits `continual.drift{model=,signal=}` (counter) and a
# `continual.drift` event — event() fans into every open run report AND the
# flight recorder, so a post-mortem ring dump carries the drift history.
#

from __future__ import annotations

from collections import deque
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

from .. import config as _config
from ..observability import counter_inc, event

# sigma = _MAD_TO_SIGMA * MAD under normality (1 / Phi^-1(3/4))
_MAD_TO_SIGMA = 1.4826
# relative noise floor: identical-to-the-ulp baselines (tiny synthetic
# streams) would otherwise make ANY deviation "drift"
_REL_FLOOR = 1e-3
_ABS_FLOOR = 1e-12


def resolve_drift_mads() -> float:
    """`continual.drift_mads` resolution: config pin, then tuning table, then
    the defaults-module constant (CONTINUAL_DRIFT_MADS, 3.0)."""
    from .. import autotune as _autotune
    from ..autotune.defaults import CONTINUAL_DRIFT_MADS

    pinned = float(_config.get("continual.drift_mads") or 0.0)
    if pinned > 0.0:
        return pinned
    tuned = _autotune.lookup("continual.drift_mads")
    if tuned:
        return float(tuned)
    return float(CONTINUAL_DRIFT_MADS)


def baseline_from_convergence(records: Iterable[Dict[str, Any]], algo: str,
                              field: str, n_rows: int = 1,
                              tail: int = 8) -> List[float]:
    """Per-row baseline from a fit report's convergence tail: the last `tail`
    records of `algo` carrying `field`, normalized by the fit's row count so
    they compare against partial_fit's per-row signals."""
    vals = [
        float(r[field]) for r in records
        if r.get("algo") == algo and field in r
        and r.get("phase") != "partial_fit"
    ]
    return [v / max(int(n_rows), 1) for v in vals[-int(tail):]]


class DriftDetector:
    """Median + MAD-floor threshold over per-update signals (lower = better
    signals only: inertia, loss, residual — all per-row)."""

    def __init__(self, model: str = "", signal: str = "",
                 baseline: Optional[Iterable[float]] = None,
                 mads: Optional[float] = None,
                 min_baseline: Optional[int] = None, window: int = 64):
        self.model = model
        self.signal = signal
        self.mads = resolve_drift_mads() if mads is None else float(mads)
        self.min_baseline = (
            int(_config.get("continual.min_baseline"))
            if min_baseline is None else int(min_baseline)
        )
        self._window: deque = deque(maxlen=int(window))
        for v in baseline or ():
            self._window.append(float(v))

    def __len__(self) -> int:
        return len(self._window)

    def threshold(self) -> Optional[float]:
        """Current firing threshold; None while the baseline is still
        calibrating."""
        if len(self._window) < max(self.min_baseline, 2):
            return None
        vals = np.asarray(self._window, np.float64)
        med = float(np.median(vals))
        mad = float(np.median(np.abs(vals - med)))
        noise = max(_MAD_TO_SIGMA * mad, _REL_FLOOR * abs(med), _ABS_FLOOR)
        return med + self.mads * noise

    def observe(self, value: float) -> Optional[Dict[str, float]]:
        """Feed one per-update signal. Returns the drift record when it
        fires, else None (and extends the rolling baseline)."""
        value = float(value)
        thr = self.threshold()
        if thr is not None and value > thr:
            counter_inc("continual.drift", 1, model=self.model,
                        signal=self.signal)
            event("continual.drift", model=self.model, signal=self.signal,
                  value=value, threshold=thr)
            return {"value": value, "threshold": thr}
        self._window.append(value)
        return None


__all__ = ["DriftDetector", "baseline_from_convergence", "resolve_drift_mads"]
