#
# Deterministic, config-driven fault injection — the testability half of the
# reliability subsystem. arXiv:1612.01437 identifies straggler/failure handling
# as the dominant availability cost of Spark ML at scale; before this module the
# failure paths of the streamed fits and the barrier fit plane were untestable
# (nothing in the tree could raise at a chosen ingest batch or barrier round).
#
# Grammar (SRML_TPU_FAULT_SPEC / config "reliability.fault_spec"):
#
#   spec      := clause (';' clause)*
#   clause    := site (':' field)*
#   field     := 'batch=' INT     -- fire only when the site sees this batch ordinal
#              | 'raise=' NAME    -- exception class to raise (default OSError)
#              | 'times=' INT     -- how many firings before the fault exhausts
#                                    (default 1: a TRANSIENT fault)
#              | 'sleep=' FLOAT   -- DELAY instead of raising: sleep this many
#                                    seconds and return (a deterministic
#                                    straggler — the comm plane's rank-skew/
#                                    straggler detection is tested with it,
#                                    docs/design.md §6h)
#
#   e.g.  SRML_TPU_FAULT_SPEC="ingest:batch=3:raise=OSError"
#         SRML_TPU_FAULT_SPEC="barrier_init:raise=TimeoutError;ann_assign:batch=1"
#         SRML_TPU_FAULT_SPEC="barrier_rank:batch=3:sleep=0.5"  # rank 3 drags
#
# Named sites planted in the tree (docs/design.md "Reliability"):
#   ingest            ops/streaming.py::_batch_stream    (every streamed fit)
#   ann_assign        ops/ann_streaming.py  IVF cell-assignment batches
#   ann_encode        ops/ann_streaming.py  PQ encoding batches
#   ann_search        ops/ann_streaming.py  paged IVF search blocks
#   pairwise          ops/pairwise_streaming.py  item-block generators
#   barrier_collect   spark/integration.py  per-partition Arrow collect
#   barrier_allgather spark/integration.py  control-plane allGather round
#   barrier_init      spark/integration.py  jax.distributed process-group init
#   barrier_rank      spark/integration.py  per-rank fit body (batch = RANK:
#                     with sleep=, delays one chosen rank — straggler injection)
#   serving_dispatch  serving/fleet.py + serving/registry.py  request routing
#                     (batch = request ordinal; pre-enqueue — a raise here
#                     rejects one request)
#   serving_execute   serving/batcher.py    dispatcher batch execution (batch =
#                     that batcher's batch ordinal; in fleet mode each replica's
#                     batcher counts its own)
#   serving_heartbeat serving/fleet.py      health-monitor heartbeat read
#                     (batch = replica index)
#
# The same three serving sites are also CHAOS sites (reliability/chaos.py):
# the chaos grammar adds fleet-level verbs — kill/hang/slow a whole replica —
# on top of this module's raise/sleep.
#
# Firing state lives process-wide and is keyed by the spec string, so a fault
# with times=1 fires exactly once per configured spec — the injected failure is
# transient and the retry/resume machinery it exercises must converge.
#

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Optional, Tuple

from .. import config as _config
from .. import profiling
from ..utils import get_logger

_logger = get_logger("reliability.faults")


class DeviceError(RuntimeError):
    """Unrecoverable accelerator-side failure — the stand-in the fault harness
    raises for the runtime's own error class (jax.errors.JaxRuntimeError, the
    XlaRuntimeError of earlier releases). `is_device_error` treats both
    identically: never retried, never degraded — a device error raises out of
    the fit."""


class StreamBatchError(RuntimeError):
    """A streamed-batch failure carrying its site and batch-ordinal context, so
    the checkpoint-resume layer can catch it and resume from the last snapshot
    instead of surfacing a bare mid-pipeline exception (ops/streaming.py)."""

    def __init__(self, site: str, batch_index: int, cause: Optional[BaseException] = None):
        super().__init__(
            f"streamed batch failure at site '{site}', batch {batch_index}"
            + (f": {type(cause).__name__}: {cause}" if cause is not None else "")
        )
        self.site = site
        self.batch_index = batch_index
        if cause is not None:
            # explicit chaining: is_transient/is_device_error classify by the
            # wrapped failure, which must survive a plain `raise` too
            self.__cause__ = cause


# exceptions a fault clause may raise — a registry, not eval()
_EXC_REGISTRY = {
    "OSError": OSError,
    "IOError": OSError,
    "TimeoutError": TimeoutError,
    "ConnectionError": ConnectionError,
    "MemoryError": MemoryError,
    "RuntimeError": RuntimeError,
    "ValueError": ValueError,
    "DeviceError": DeviceError,
}


@dataclass(frozen=True)
class FaultSpec:
    """One parsed clause of the fault grammar."""

    site: str
    batch: Optional[int] = None  # None: fire at any batch
    exc: type = OSError
    times: int = 1  # firings before the fault exhausts (1 == transient)
    sleep: float = 0.0  # >0: delay this many seconds instead of raising


def parse_fault_spec(raw: str) -> List[FaultSpec]:
    specs: List[FaultSpec] = []
    for clause in raw.split(";"):
        clause = clause.strip()
        if not clause:
            continue
        fields = clause.split(":")
        site, batch, exc, times = fields[0].strip(), None, OSError, 1
        sleep, exc_given = 0.0, False
        if not site:
            raise ValueError(f"fault clause with empty site: {clause!r}")
        for field in fields[1:]:
            key, sep, value = field.partition("=")
            key = key.strip()
            if not sep:
                raise ValueError(f"malformed fault field {field!r} in {clause!r}")
            if key == "batch":
                batch = int(value)
            elif key == "raise":
                if value not in _EXC_REGISTRY:
                    raise ValueError(
                        f"unknown exception {value!r} in fault clause {clause!r}; "
                        f"known: {sorted(_EXC_REGISTRY)}"
                    )
                exc = _EXC_REGISTRY[value]
                exc_given = True
            elif key == "times":
                times = int(value)
            elif key == "sleep":
                sleep = float(value)
                if sleep < 0:
                    raise ValueError(
                        f"negative sleep in fault clause {clause!r}"
                    )
            else:
                raise ValueError(f"unknown fault field {key!r} in {clause!r}")
        if sleep > 0 and exc_given:
            # contradictory clause: a sleep fault returns, so the raise= could
            # only be silently ignored — reject at parse time like every other
            # malformed field instead of handing back a delay-only fault
            raise ValueError(
                f"fault clause {clause!r} combines sleep= with raise=; "
                "a sleep fault delays instead of raising — use separate "
                "clauses for a delay and a failure"
            )
        specs.append(FaultSpec(site, batch, exc, times, sleep))
    return specs


# (spec string, parsed clauses, remaining firing counts) — re-parsed whenever the
# configured spec string changes, reset explicitly by tests via reset_faults().
# The lock keeps the firing budget exact when barrier tasks run as threads.
_armed: Optional[Tuple[str, List[FaultSpec], List[int]]] = None
_armed_lock = threading.Lock()


def _active() -> Optional[Tuple[str, List[FaultSpec], List[int]]]:
    global _armed
    raw = _config.get("reliability.fault_spec") or ""
    if not raw:
        _armed = None
        return None
    if _armed is None or _armed[0] != raw:
        specs = parse_fault_spec(raw)
        _armed = (raw, specs, [s.times for s in specs])
    return _armed


def reset_faults() -> None:
    """Re-arm the configured spec (firing counts restart from `times`)."""
    global _armed
    _armed = None


def fault_point(site: str, batch: Optional[int] = None) -> None:
    """A named injection site. No-op unless a configured fault clause matches,
    in which case the clause's exception raises and its firing budget decrements
    — deterministic: same spec + same call sequence = same failure."""
    fire: Optional[FaultSpec] = None
    left = 0
    with _armed_lock:  # budget decrements stay exact across barrier-task threads
        state = _active()
        if state is None:
            return
        _, specs, remaining = state
        for i, spec in enumerate(specs):
            if spec.site != site or remaining[i] <= 0:
                continue
            if spec.batch is not None and batch != spec.batch:
                continue
            remaining[i] -= 1
            fire, left = spec, remaining[i]
            break
    if fire is None:
        return
    profiling.count("reliability.fault")
    profiling.count(f"reliability.fault.{site}")
    from ..observability import event as _obs_event

    if fire.sleep > 0:
        # delay fault: a deterministic straggler, not a failure — the comm
        # plane's skew/straggler detection (docs/design.md §6h) is driven by it
        _obs_event("fault", site=site, batch=batch, sleep_s=fire.sleep)
        _logger.warning(
            "fault injection: sleeping %.3fs at site '%s'%s (%d firings left)",
            fire.sleep, site,
            f" batch {batch}" if batch is not None else "", left,
        )
        import time

        time.sleep(fire.sleep)
        return
    _obs_event("fault", site=site, batch=batch, exc=fire.exc.__name__)
    _logger.warning(
        "fault injection: raising %s at site '%s'%s (%d firings left)",
        fire.exc.__name__, site,
        f" batch {batch}" if batch is not None else "", left,
    )
    raise fire.exc(
        f"injected {fire.exc.__name__} at site '{site}'"
        + (f" batch {batch}" if batch is not None else "")
    )


def is_device_error(e: BaseException) -> bool:
    """Unrecoverable accelerator failure (compile refusal, VMEM/HBM overflow,
    a lost device): never retried and never answered by a host fit — it raises
    out of `Estimator.fit`, so a model that exists did run on the device. A
    StreamBatchError is classified by the failure it wraps."""
    if isinstance(e, StreamBatchError) and e.__cause__ is not None:
        return is_device_error(e.__cause__)
    if isinstance(e, DeviceError):
        return True
    import sys

    jax = sys.modules.get("jax")  # no jax imported -> no jax error to classify
    return jax is not None and isinstance(e, jax.errors.JaxRuntimeError)


def is_transient(e: BaseException) -> bool:
    """Whether a failure is worth a retry/resume: host-side I/O classes
    (preempted host, dropped connection, ingest OOM) are; device errors and
    everything that looks like a programming/param error are not."""
    if isinstance(e, StreamBatchError):
        cause = e.__cause__
        return cause is None or is_transient(cause)
    if is_device_error(e):
        return False
    return isinstance(e, (OSError, TimeoutError, ConnectionError, MemoryError))


def is_stage_retryable(e: BaseException) -> bool:
    """Whether a whole barrier STAGE failure is worth re-running: broader than
    is_transient (a dropped barrier surfaces as RuntimeError-class wreckage from
    deep in the stack), but param/programming errors and device errors still
    propagate — retrying those can only fail identically."""
    if is_device_error(e):
        return False
    if isinstance(
        e, (ValueError, TypeError, NotImplementedError, AssertionError, KeyError, AttributeError)
    ):
        return False
    return isinstance(e, Exception)
