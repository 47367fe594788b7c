"""Request-scoped tracing: trace context, spans, and cross-hop propagation.

Zero-dependency (stdlib only — the supervisor and the jax-free error paths
import through here too). One `Trace` is born per request at the first hop
that sees it (edge router, fleet edge, or the standalone server when hit
directly), propagates via `contextvars` through the async handler tree and
into the batcher's engine worker threads (`asyncio.to_thread` copies the
context), and crosses process boundaries as a W3C-compatible `traceparent`
header plus an `X-Request-ID` the client can quote back.

Span capture is a monotonic-clock read and a list append under the GIL; when
no trace is active (flight recorder off, or a codepath outside a request)
no Span or Trace is allocated.

`span` is the one way to time host work, and it has four sinks: the request
`Trace` (as ever), a process-wide table `name -> count, wall_ms, cpu_ms`
that `/metrics` serves as `host_spans` (on in every run, traced or not),
for spans with no `await` inside, a profiler annotation that puts the span
on the device trace's own clock, and, in a process set up to be captured, the
`Timeline`: a bounded ring of every span's stamps on the monotonic clock,
which `/metrics` serves as `host_timeline` and which a reader joins to the
device trace afterwards. This module stays stdlib-only: the serving process
hands the annotation factory in (`set_annotator`), so the supervisor never
imports jax for it.

Stage-name vocabulary: `STAGES` is the ONE list of stage names shared by
trace spans and the Metrics stage histograms (ISSUE 7 satellite —
`/metrics` said `preprocess` where another report said `staging` and
neither matched the decode+h2d split from PR 3).
"""

import bisect
import collections
import contextvars
import gc
import hashlib
import os
import re
import threading
import time

from spotter_tpu.testing import faults

# ---- stage vocabulary (one list, used by spans and Metrics) ----

ROUTE = "route"          # edge hop: pool pick + router overhead
FETCH = "fetch"          # detector: URL fetch (single-flight wait included)
DECODE = "decode"        # host decode: PIL open/convert + cache lookup, and
                         # the engine's decode/resize staging half
QUEUE_WAIT = "queue_wait"  # batcher: submit -> batch dispatch
H2D = "h2d"              # engine: host->device transfer enqueue
DEVICE = "device"        # engine: dispatch -> data-on-host
POSTPROCESS = "postprocess"  # engine threshold/boxes + detector draw/encode

STAGES = (ROUTE, FETCH, DECODE, QUEUE_WAIT, H2D, DEVICE, POSTPROCESS)

# Not pipeline stages, but part of "where did the time go":
# - OTHER: the self-measured remainder (total - sum(stages)) a server
#   reports in Server-Timing so upstream traces tile — HTTP parse/
#   serialize and handler overhead;
# - NETWORK: the edge-measured transport slice of a downstream call
#   (await duration minus what the downstream hop accounted for) — the
#   classic client-minus-server attribution.
OTHER = "other"
NETWORK = "network"

# engine-side subset, in stage order (what Metrics.record_batch carries)
ENGINE_STAGES = (DECODE, H2D, DEVICE, POSTPROCESS)

TRACEPARENT_HEADER = "traceparent"
REQUEST_ID_HEADER = "X-Request-ID"

_TRACEPARENT_RE = re.compile(
    r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)

# Debug-only allocation counters: the recorder-off acceptance test asserts
# the no-trace path creates zero Span/Trace objects. Unlocked by design —
# a rare lost increment under thread races is acceptable for a debug stat,
# and "exactly zero" (the property under test) is race-free either way;
# a lock here would tax every span on the hot path instead.
_traces_created = 0
_spans_created = 0


def trace_stats() -> dict:
    return {
        "traces_created": _traces_created,
        "spans_created": _spans_created,
    }


class Span:
    """One timed stage inside a trace. Times are milliseconds relative to
    the trace start, so a serialized trace is self-contained. A `detail`
    span lies inside a stage span (`engine.stack_pad` inside `decode`): it
    is shown, and left out of the per-stage totals, which would otherwise
    count its time twice. `args` is what the span said of itself (the
    engine's batch sequence number, bucket and image count)."""

    __slots__ = ("name", "start_ms", "duration_ms", "detail", "args")

    def __init__(
        self,
        name: str,
        start_ms: float,
        duration_ms: float,
        detail: bool = False,
        args: dict | None = None,
    ) -> None:
        global _spans_created
        self.name = name
        self.start_ms = start_ms
        self.duration_ms = duration_ms
        self.detail = detail
        self.args = args
        _spans_created += 1

    def to_dict(self) -> dict:
        out = {
            "name": self.name,
            "start_ms": round(self.start_ms, 3),
            "duration_ms": round(self.duration_ms, 3),
        }
        if self.detail:
            out["detail"] = True
        if self.args:
            out.update(self.args)
        return out


class Trace:
    """One request's trace: identity + an append-only span list.

    Appends happen from the handler task, per-image subtasks, and the
    batcher's engine worker thread concurrently; `list.append` under the
    GIL plus the `_lock` on the mutators keeps the structure consistent
    without a lock on the read-mostly hot path.
    """

    def __init__(
        self,
        trace_id: str,
        request_id: str,
        parent_span_id: str | None = None,
    ) -> None:
        global _traces_created
        self.trace_id = trace_id
        self.request_id = request_id
        self.parent_span_id = parent_span_id
        # os.urandom beats uuid4 ~2x per id; trace creation sits on the
        # request hot path and the id only needs W3C's 8 random bytes
        self.span_id = os.urandom(8).hex()
        self.started_at = time.time()
        self._t0 = _now()
        self.spans: list[Span] = []
        self.status = "ok"
        self.error: str | None = None
        self.duration_ms: float | None = None
        self._lock = threading.Lock()
        _traces_created += 1

    # -- span capture --

    def add_span(
        self,
        name: str,
        t_start: float,
        t_end: float,
        detail: bool = False,
        args: dict | None = None,
    ) -> None:
        """Append a span from absolute monotonic timestamps."""
        self.spans.append(
            Span(
                name, (t_start - self._t0) * 1e3, (t_end - t_start) * 1e3,
                detail, args,
            )
        )

    def add_span_ms(self, name: str, start_ms: float, duration_ms: float) -> None:
        """Append a span from pre-computed relative milliseconds (merged
        downstream Server-Timing entries land here with start 0)."""
        self.spans.append(Span(name, start_ms, duration_ms))

    def set_error(self, status: str, error: str) -> None:
        with self._lock:
            self.status = status
            self.error = error[:2000]

    def finish(self) -> float:
        """Stamp the total duration (idempotent: the first call wins so a
        late finisher cannot shrink an already-recorded total)."""
        with self._lock:
            if self.duration_ms is None:
                self.duration_ms = (_now() - self._t0) * 1e3
            return self.duration_ms

    # -- serialization --

    def stage_totals(self) -> dict[str, float]:
        """Per-name summed durations (ms) — the Server-Timing payload.
        Detail spans lie inside stage spans and are left out."""
        totals: dict[str, float] = {}
        for s in list(self.spans):
            if not s.detail:
                totals[s.name] = totals.get(s.name, 0.0) + s.duration_ms
        return totals

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "request_id": self.request_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "started_at": self.started_at,
            "duration_ms": (
                round(self.duration_ms, 3) if self.duration_ms is not None else None
            ),
            "status": self.status,
            "error": self.error,
            "spans": [s.to_dict() for s in list(self.spans)],
        }


# ---- context propagation ----

_current: contextvars.ContextVar[Trace | None] = contextvars.ContextVar(
    "spotter_tpu_trace", default=None
)
# The batch the engine worker thread is currently serving: set by the
# batcher right before `asyncio.to_thread` (which copies the context), so
# engine-side stage spans fan out to every request trace in the batch.
_batch_traces: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "spotter_tpu_batch_traces", default=None
)


def current_trace() -> Trace | None:
    return _current.get()


def set_current_trace(trace: Trace | None) -> contextvars.Token:
    return _current.set(trace)


def new_request_id() -> str:
    return os.urandom(16).hex()


def trace_id_for_request(request_id: str) -> str:
    """Deterministic trace id from an X-Request-ID (ISSUE 7 satellite): a
    client that minted its own request id can locate the trace without ever
    having seen a traceparent."""
    return hashlib.sha256(request_id.encode()).hexdigest()[:32]


def parse_traceparent(value: str | None) -> tuple[str, str] | None:
    """(trace_id, parent_span_id) from a W3C traceparent, or None."""
    if not value:
        return None
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    trace_id, span_id = m.group(1), m.group(2)
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def traceparent_value(trace: Trace) -> str:
    """The header value for the OUTGOING hop: this trace's span is the
    downstream request's parent."""
    return f"00-{trace.trace_id}-{trace.span_id}-01"


def begin_trace(
    request_id: str | None = None,
    traceparent: str | None = None,
    enabled: bool = True,
) -> Trace | None:
    """Create (or decline to create) the request trace and install it in
    the current context. With the recorder off (`enabled=False`) this is
    the whole cost of tracing: one None check per helper downstream."""
    if not enabled:
        return None
    parent = parse_traceparent(traceparent)
    if request_id is None or not str(request_id).strip():
        request_id = new_request_id()
    request_id = str(request_id).strip()[:128]
    if parent is not None:
        trace = Trace(parent[0], request_id, parent_span_id=parent[1])
    else:
        trace = Trace(trace_id_for_request(request_id), request_id)
    set_current_trace(trace)
    return trace


# ---- the process-wide span table (the `host_spans` key of /metrics) ----

_now = time.monotonic  # a module global, so a test can put a fake clock here
_table_lock = threading.Lock()
_table: dict[str, list] = {}  # name -> [count, wall_ms, cpu_ms]
# `factory(name, **args)` -> a context manager that puts the span into a
# running profiler capture; None until the serving process installs jax's
_annotator = None


# Pass as a span's `trace` to keep it off every request trace (table and
# annotation only): the decode pool's per-image spans, 48 to a batch.
NO_TRACE: tuple = ()


def set_annotator(factory) -> None:
    """Install (or, with None, remove) the profiler annotation factory:
    `jax.profiler.TraceAnnotation` in the serving process
    (engine/profiler.py). Outside a capture an annotation is one flag check."""
    global _annotator
    _annotator = factory


def record_span(
    name: str, wall_s: float, cpu_s: float = 0.0, count: int = 1,
    start: float | None = None,
) -> None:
    """Add to the table without a `span` object: a wait that is measured
    from two stamps the caller already has (the batcher's queue wait).
    With `start` (the first stamp) the span goes on the timeline too."""
    with _table_lock:
        row = _table.get(name)
        if row is None:
            row = _table[name] = [0, 0.0, 0.0]
        row[0] += count
        row[1] += wall_s * 1e3
        row[2] += cpu_s * 1e3
    timeline = _timeline
    if timeline is not None and start is not None:
        timeline.add(name, start, start + wall_s)


def host_spans_snapshot() -> dict:
    with _table_lock:
        out = {
            name: {
                "count": count,
                "wall_ms": round(wall_ms, 3),
                "cpu_ms": round(cpu_ms, 3),
            }
            for name, (count, wall_ms, cpu_ms) in _table.items()
        }
    count, wall_s = _gc_pauses
    if count:
        out[GC_SPAN] = {"count": count, "wall_ms": round(wall_s * 1e3, 3), "cpu_ms": 0.0}
    return out


def reset_host_spans() -> None:
    global _gc_pauses
    with _table_lock:
        _table.clear()
    _gc_pauses = (0, 0.0)


# ---- the timeline (the `host_timeline` key of /metrics) ----

# Python's collector pauses, one span each while the timeline is on: a
# collection stops every thread, so whatever the chip waited on waited on it
GC_SPAN = "python.gc"
# A benchmark window is 51 s of sending and its replies' tail, read after
# its end: 90 s still reach back to a capture made 3 s into it.
TIMELINE_SECONDS = 90.0
# A served image makes eight spans and a batch eleven; the benchmark's
# fastest cell (YOLOS-base, 29 images a second in batches of about 16) makes
# some 280 a second, and the collector 15 more: 27,000 in 90 s, so 2**16
# stamps hold 90 s with room. An entry is a tuple of five references (name
# and batch are shared objects; two floats and the thread id are its own):
# 160 bytes, 10.5 MB with the ring full.
TIMELINE_ENTRIES = 1 << 16


class Timeline:
    """The last `seconds` of spans, at most `entries` of them, as
    `(name, thread id, t0, t1, batch)` stamps of `_now` (the monotonic
    clock, one for every process of the host). Appends come from every
    thread and from the collector's callback, so they take no lock: a
    bounded `deque`'s append is one step for the interpreter. A stamp is no
    stack frame, so a span with an `await` inside is on it like any other.
    `snapshot()` encodes it for `/metrics`: the names and threads as tables,
    each entry as integers, microseconds of the monotonic clock."""

    def __init__(self, seconds: float = TIMELINE_SECONDS, entries: int = TIMELINE_ENTRIES) -> None:
        self.seconds = float(seconds)
        self._ring: collections.deque = collections.deque(maxlen=int(entries))
        self._appended = 0
        self._since = _now()

    def add(self, name: str, t0: float, t1: float, batch=None) -> None:
        self._ring.append((name, threading.get_ident(), t0, t1, batch))
        self._appended += 1  # a lost increment only makes `complete_from` earlier

    def snapshot(self) -> dict:
        """`complete_from_us`: from then on every span that ended is here
        (the later of when the ring began, `seconds` ago, and the newest
        stamp the count bound pushed out)."""
        now = _now()
        rows = list(self._ring)
        complete_from = max(self._since, now - self.seconds)
        if self._appended > len(rows) and rows:
            complete_from = max(complete_from, rows[0][3])
        # appended at their ends, so (nearly) in the order of t1
        rows = rows[bisect.bisect_left(rows, complete_from, key=lambda r: r[3]):]
        names: dict = {}
        threads: dict = {}
        entries = [
            [names.setdefault(name, len(names)), threads.setdefault(ident, len(threads)),
             round(t0 * 1e6), round(t1 * 1e6), batch]
            for name, ident, t0, t1, batch in rows
        ]
        thread_names = {t.ident: t.name for t in threading.enumerate()}
        return {
            "now_us": round(now * 1e6),
            "complete_from_us": round(complete_from * 1e6),
            "names": list(names),
            "threads": [thread_names.get(ident, str(ident)) for ident in threads],
            "entries": entries,
        }


_timeline: Timeline | None = None
_gc_pauses = (0, 0.0)  # (collections, seconds) while the timeline is on
_gc_t0 = 0.0


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook. A collection can start between any two steps
    of any thread's Python code, that of a thread inside `_table_lock`
    too, so this takes no lock: collections never overlap, and the ring's
    append is one step."""
    global _gc_t0, _gc_pauses
    if phase == "start":
        _gc_t0 = _now()
        return
    t1 = _now()
    count, wall_s = _gc_pauses
    _gc_pauses = (count + 1, wall_s + (t1 - _gc_t0))
    timeline = _timeline
    if timeline is not None:
        timeline.add(GC_SPAN, _gc_t0, t1)


def enable_timeline() -> None:
    """Turn the timeline on (idempotent): in a process that serves a
    profiler (engine/profiler.py), so that a capture can be joined to it.
    Off, a span pays one `None` check for it and nothing is allocated."""
    global _timeline
    if _timeline is None:
        _timeline = Timeline()
        gc.callbacks.append(_on_gc)


def disable_timeline() -> None:
    global _timeline
    if _timeline is not None:
        gc.callbacks.remove(_on_gc)
        _timeline = None


def timeline_snapshot() -> dict | None:
    timeline = _timeline
    return None if timeline is None else timeline.snapshot()


class span:
    """`with span("detector.pil_decode", annotate=True):` — time one piece
    of host work. On exit it goes to the request trace (the ambient one, an
    explicit one, or each of a batch's: pass the list), to the span table
    and, where it is on, to the timeline, with its `batch` tag; with
    `annotate`, it is a profiler annotation while it runs.

    `stage`: the name from the stage vocabulary that the trace records
    the span under (`engine.decode` is the engine's half of `decode`); a
    span with none, whose own name is no stage either, is a detail inside
    one. `annotate` is for spans with no `await` inside: an annotation
    lives on its thread's stack, and an `await` would leave it open under
    whatever runs next. `cpu` adds the thread's CPU time (decode-pool
    work: wall over CPU says whether the pool ran in parallel). Further
    keywords (`batch=`, `bucket=`, `n=`) go on the annotation and the
    trace's span.

    No active trace ⇒ no Span allocated, but the fault harness's
    `slow_stage` injection still applies so SLO tests get deterministic
    latency whether or not tracing captured it.

    `start()` / `stop()` are the `with` block taken apart, for the one
    span that opens in one method and closes in another (the engine's
    `device` stage: dispatch to data-on-host)."""

    __slots__ = (
        "name", "trace", "stage", "args", "seconds",
        "_annotate", "_cpu", "_t0", "_c0", "_ann",
    )

    def __init__(
        self,
        name: str,
        trace=None,
        *,
        stage: str | None = None,
        annotate: bool = False,
        cpu: bool = False,
        **args,
    ) -> None:
        self.name = name
        self.trace = trace
        self.stage = stage
        self.args = args
        self.seconds = 0.0
        self._annotate = annotate
        self._cpu = cpu
        self._ann = None

    def start(self) -> "span":
        if self._annotate and _annotator is not None:
            self._ann = _annotator(self.name, **self.args)
            self._ann.__enter__()
        if self._cpu:
            self._c0 = time.thread_time()
        self._t0 = _now()
        return self

    def stop(self) -> None:
        t1 = _now()
        self.seconds = t1 - self._t0
        cpu_s = time.thread_time() - self._c0 if self._cpu else 0.0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        record_span(self.name, self.seconds, cpu_s)
        timeline = _timeline
        if timeline is not None:
            timeline.add(self.name, self._t0, t1, self.args.get("batch"))
        tr = self.trace if self.trace is not None else _current.get()
        if not tr:
            return
        name = self.stage or self.name
        detail = name not in STAGES
        args = self.args or None
        if isinstance(tr, Trace):
            tr.add_span(name, self._t0, t1, detail, args)
            return
        # a batch's traces, one entry per image: a stage span goes to each
        # (an image's stages tile its own wait), a detail once per request
        for one in dict.fromkeys(tr) if detail else tr:
            one.add_span(name, self._t0, t1, detail, args)

    def __enter__(self) -> "span":
        self.start()
        # inside the window, so an injected delay shows in the stage it names
        delay = faults.stage_delay_s(self.stage or self.name)
        if delay > 0.0:
            time.sleep(delay)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()


# ---- batch fan-out (engine worker thread -> per-request traces) ----


def set_batch_traces(traces: list) -> None:
    """Called by the batcher in the `_run_batch` task, before handing the
    batch to the worker thread; `asyncio.to_thread` copies the context so
    the engine sees the same list."""
    _batch_traces.set(traces or None)


def batch_traces() -> list:
    """What an engine-side `span` passes as its `trace`: the traces riding
    in the current batch, one per traced image. Empty outside a traced
    batch, and never the ambient trace: the worker thread's context is the
    batcher's pump task's, which was born inside whatever request came
    first."""
    return _batch_traces.get() or []


def batch_trace_id() -> str | None:
    """The exemplar trace id for this engine batch (first traced item)."""
    traces = _batch_traces.get()
    return traces[0].trace_id if traces else None
