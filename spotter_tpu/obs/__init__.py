"""Observability tier (ISSUE 7): request-scoped tracing, flight recorder,
Prometheus metrics view, and trace-correlated JSON logs.

Import layering matters here: `obs.trace` and `obs.recorder` are
stdlib-only (the supervisor and the jax-free engine error paths ride
through them), while `obs.http` pulls in aiohttp and `obs.prom`/`obs.logs`
stay stdlib. This package root re-exports only the stdlib-safe surface;
HTTP glue is imported explicitly as `spotter_tpu.obs.http`.
"""

from spotter_tpu.obs.perf import (  # noqa: F401
    HBM_SAMPLE_ENV,
    PEAK_TFLOPS_ENV,
    PERF_LEDGER_ENV,
    SLO_TARGET_PCT_ENV,
    CompileLedger,
    HbmSampler,
    PerfLedger,
    SloBurn,
    peak_tflops_for,
    perf_enabled,
    sample_hbm_once,
)
from spotter_tpu.obs.recorder import (  # noqa: F401
    DUMP_EXIT_CODES,
    TRACE_DUMP_DIR_ENV,
    TRACE_RING_ENV,
    TRACE_SLOWEST_K_ENV,
    FlightRecorder,
    dump_for_exit,
    get_recorder,
    reset_recorder,
)
from spotter_tpu.obs.trace import (  # noqa: F401
    DECODE,
    DEVICE,
    ENGINE_STAGES,
    FETCH,
    H2D,
    NO_TRACE,
    NETWORK,
    OTHER,
    POSTPROCESS,
    QUEUE_WAIT,
    REQUEST_ID_HEADER,
    ROUTE,
    STAGES,
    TRACEPARENT_HEADER,
    Timeline,
    Trace,
    batch_trace_id,
    batch_traces,
    begin_trace,
    current_trace,
    disable_timeline,
    enable_timeline,
    new_request_id,
    host_spans_snapshot,
    parse_traceparent,
    record_span,
    set_annotator,
    set_batch_traces,
    set_current_trace,
    span,
    timeline_snapshot,
    trace_id_for_request,
    trace_stats,
    traceparent_value,
)
