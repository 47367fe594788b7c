"""Profiling/tracing — the subsystem the reference lacks (SURVEY.md §5.1).

Three mechanisms, all opt-in and zero-cost when off:

- `install_span_annotator()`: hands `jax.profiler.TraceAnnotation` to
  `obs.span`, so the program's host spans (engine stages, the decode pool's
  per-image work, the detector's decode and draw) appear in any capture
  that traces the host.
- `maybe_start_profiler_server()`: starts jax.profiler's gRPC server when
  `SPOTTER_TPU_PROFILER_PORT` is set, so TensorBoard / xprof can connect and
  capture live TPU traces from a serving pod, and the span timeline that
  `/metrics` then serves as `host_timeline`.
- `capture(log_dir, duration_s)`: timed start_trace/stop_trace pair used by
  the `/profile` endpoint — the device work of whatever traffic is in
  flight lands in the trace. (For ad-hoc scoped captures, use
  `jax.profiler.trace` directly — it is already a context manager.)

The per-stage latency breakdown (preprocess / device / postprocess) is in
`Metrics.record_batch(..., stages=...)` — always on, host-side only.
"""

import logging
import os
import threading
import time

import jax

logger = logging.getLogger(__name__)

PROFILER_PORT_ENV = "SPOTTER_TPU_PROFILER_PORT"

_server_lock = threading.Lock()
_server_started = False


def maybe_start_profiler_server() -> int | None:
    """Start jax.profiler.start_server once if the env asks for it, and with
    it the span timeline (`obs.enable_timeline`): a process that can be
    captured keeps the stamps a capture is joined to."""
    global _server_started
    port = os.environ.get(PROFILER_PORT_ENV, "")
    if not port:
        return None
    with _server_lock:
        if not _server_started:
            jax.profiler.start_server(int(port))
            _server_started = True
            from spotter_tpu import obs

            obs.enable_timeline()
            logger.info("jax profiler server listening on :%s; span timeline on", port)
    return int(port)


def install_span_annotator() -> None:
    """Make every annotated `obs.span` a `jax.profiler.TraceAnnotation`
    while it runs, so that a capture with the host tracer at level 1 or
    more holds the program's own spans on the device trace's clock. The
    serving process calls this at start-up; `obs/trace.py` itself stays
    free of jax."""
    from spotter_tpu import obs

    obs.set_annotator(jax.profiler.TraceAnnotation)


_capture_lock = threading.Lock()


def capture(log_dir: str, duration_s: float = 1.0) -> dict:
    """Timed capture: trace everything the device runs for duration_s.

    Serializes captures (jax.profiler supports one active trace); returns a
    small summary the /profile endpoint can serve.
    """
    duration_s = float(duration_s)
    if not (0.0 < duration_s <= 60.0):  # also rejects NaN
        raise ValueError(f"duration_s must be in (0, 60], got {duration_s}")
    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("a profiler capture is already running")
    try:
        t0 = time.monotonic()
        t0_wall = time.time()
        jax.profiler.start_trace(log_dir)
        try:
            time.sleep(duration_s)
        finally:
            # never leave the process-wide trace running: an orphaned trace
            # would make every later start_trace fail for the process life
            jax.profiler.stop_trace()
        # flight-recorder join (ISSUE 10 satellite): the trace ids of
        # requests whose window overlapped the capture, so an xprof trace
        # can be lined up against /debug/traces request-by-request
        try:
            from spotter_tpu.obs import get_recorder

            overlapping = get_recorder().trace_ids_between(
                t0_wall, time.time()
            )
        except Exception:
            overlapping = []
        return {
            "log_dir": log_dir,
            "duration_s": round(time.monotonic() - t0, 3),
            "overlapping_trace_ids": overlapping,
        }
    finally:
        _capture_lock.release()
