#
# Reliability subsystem: retry/backoff policy, deterministic fault injection,
# and checkpoint-resume for the streamed out-of-core fits — plus the exception
# taxonomy (transient vs stage-retryable vs unrecoverable device error) that
# drives the barrier->collect degradation ladder in core/estimator.py and
# spark/integration.py (a device error is never degraded: it raises).
#
# Observability: every retry/resume/degrade/fault-firing increments a
# profiling counter (profiling.counter_totals()) so the behavior under faults
# is visible, not silent. See docs/design.md "Reliability".
#

from .chaos import (
    ChaosSpec,
    ReplicaKilled,
    chaos_enabled,
    chaos_point,
    parse_chaos_spec,
    reset_chaos,
)
from .checkpoint import copy_carry, resumable_accumulate
from .faults import (
    DeviceError,
    FaultSpec,
    StreamBatchError,
    fault_point,
    is_device_error,
    is_stage_retryable,
    is_transient,
    parse_fault_spec,
    reset_faults,
)
from .policy import RetryPolicy

__all__ = [
    "ChaosSpec",
    "DeviceError",
    "FaultSpec",
    "ReplicaKilled",
    "RetryPolicy",
    "StreamBatchError",
    "chaos_enabled",
    "chaos_point",
    "copy_carry",
    "fault_point",
    "is_device_error",
    "is_stage_retryable",
    "is_transient",
    "parse_chaos_spec",
    "parse_fault_spec",
    "reset_chaos",
    "reset_faults",
    "resumable_accumulate",
]
