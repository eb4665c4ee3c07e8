#
# Observability subsystem: typed metrics registry, per-fit trace trees, the
# inference-plane mirror (TransformRun + predict dispatch + recompile
# sentinel), driver-side aggregation across the barrier fit plane, and
# exporters (docs/design.md §6d/§6e). `profiling.py` is a thin compat shim over
# this package; new instrumentation should import from here directly.
#
#   registry.py   Counter / Gauge / Histogram (+ quantile) / MetricsRegistry
#   runs.py       write fan-out, structured spans, events, FitRun, worker_scope,
#                 live progress gauges + convergence records
#   inference.py  TransformRun, predict_dispatch, shape buckets + sentinel
#   export.py     JSONL run/transform reports (rotating) + Prometheus textfile
#   device.py     compiled_kernel cost/memory-analysis capture, HBM telemetry,
#                 span cost attribution, compile accounting
#   server.py     opt-in live HTTP endpoint: /metrics, /healthz, /runs[/<id>],
#                 /runs/<id>/ranks (barrier timeline)
#   flight.py     failure flight recorder: bounded ring buffer + postmortem
#                 bundles (postmortem_<run_id>.json)
#   tracing.py    causal request tracing (§6l): W3C traceparent ids, per-request
#                 span trees with fan-in links, tail-based sampling ring,
#                 trace_reports.jsonl export + /traces live endpoints
#   comm.py       communication plane: HLO collective accounting, per-rank
#                 skew + straggler detection, timeline
#

from .registry import (
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    interpolate_quantile,
    label_key,
    split_label_key,
)
from .runs import (
    PROCESS_TOKEN,
    FitRun,
    WorkerScope,
    active_runs,
    add_span_total,
    convergence,
    counter_inc,
    current_run,
    event,
    find_run,
    fit_run,
    gauge_dec,
    gauge_inc,
    gauge_set,
    global_registry,
    legacy_count,
    note_rank_phase,
    observe,
    progress,
    span,
    worker_scope,
)
from .comm import (
    COLLECTIVE_KINDS,
    collective_summary,
    collectives_from_executable,
    collectives_of_computation,
    extract_collectives,
    rank_timeline,
)
from .inference import (
    TransformRun,
    deliver_partition_snapshot,
    predict_dispatch,
    reset_shape_buckets,
    suppress_transform_runs,
    transform_batch,
    transform_run,
)
from .export import (
    TRACE_REPORT_FILENAME,
    load_run_reports,
    load_serving_reports,
    load_trace_reports,
    load_transform_partials,
    load_transform_reports,
    render_prometheus,
    write_prometheus_textfile,
    write_run_report,
)
from .device import (
    CompiledKernel,
    compiled_kernel,
    kernel_cost,
    kernel_cost_records,
    sample_hbm,
)
from .server import (
    server_address,
    start_metrics_server,
    stop_metrics_server,
)
from .flight import (
    dump_postmortem,
    load_postmortem,
    reset_flight_recorder,
)
from .tracing import (
    RequestTrace,
    TraceContext,
    format_traceparent,
    get_trace,
    parse_traceparent,
    reset_tracing,
    ring_snapshot,
    start_trace,
    trace_index,
    would_keep,
)

__all__ = [
    "DEFAULT_TIME_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "interpolate_quantile",
    "label_key",
    "split_label_key",
    "PROCESS_TOKEN",
    "FitRun",
    "WorkerScope",
    "active_runs",
    "add_span_total",
    "convergence",
    "counter_inc",
    "current_run",
    "event",
    "find_run",
    "fit_run",
    "gauge_dec",
    "gauge_inc",
    "gauge_set",
    "global_registry",
    "legacy_count",
    "note_rank_phase",
    "observe",
    "progress",
    "span",
    "worker_scope",
    "COLLECTIVE_KINDS",
    "collective_summary",
    "collectives_from_executable",
    "collectives_of_computation",
    "extract_collectives",
    "rank_timeline",
    "TransformRun",
    "deliver_partition_snapshot",
    "predict_dispatch",
    "reset_shape_buckets",
    "suppress_transform_runs",
    "transform_batch",
    "transform_run",
    "TRACE_REPORT_FILENAME",
    "load_run_reports",
    "load_serving_reports",
    "load_trace_reports",
    "load_transform_partials",
    "load_transform_reports",
    "render_prometheus",
    "write_prometheus_textfile",
    "write_run_report",
    "CompiledKernel",
    "compiled_kernel",
    "kernel_cost",
    "kernel_cost_records",
    "sample_hbm",
    "server_address",
    "start_metrics_server",
    "stop_metrics_server",
    "dump_postmortem",
    "load_postmortem",
    "reset_flight_recorder",
    "RequestTrace",
    "TraceContext",
    "format_traceparent",
    "get_trace",
    "parse_traceparent",
    "reset_tracing",
    "ring_snapshot",
    "start_trace",
    "trace_index",
    "would_keep",
]
