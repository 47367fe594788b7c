"""Standalone aiohttp serving runtime (no Ray required).

Serves the same route the Ray Serve app exposes behind the manager proxy
(route_prefix /detect — rayservice-template.yaml:10; proxy target
handlers.go:298-304), plus /healthz and /metrics (SURVEY.md §5.5 requires
throughput/latency counters that the reference lacks).

Resilience surface (ISSUE 1): /detect answers 429 (queue full) or 503
(breaker open / draining) with a Retry-After hint when the request is shed;
/healthz is READINESS (503 while the breaker is open or a drain is in
progress) while /livez is LIVENESS (200 whenever the process serves HTTP) —
the split k8s needs to stop routing without restarting the pod; /drain is
the preStop hook: stop admitting, flush the queue, wait for in-flight
batches. SPOTTER_TPU_FAULTS arms the fault-injection harness
(spotter_tpu/testing/faults.py) for chaos staging — loud at startup.

Replica lifecycle (ISSUE 2): the HTTP surface binds BEFORE the model loads —
bring-up runs as a background task through the `loading -> warming -> ready`
state machine exposed at /startupz, so a k8s startupProbe can wait out a
long warmup without the pod being killed (readiness stays 503 throughout).
JAX's persistent compilation cache (`JAX_COMPILATION_CACHE_DIR`, else
`<checkout>/.jax_cache`) is armed before the engine compiles, making a
post-preemption restart warm;
`time_to_ready_s` and `restarts_total` (from `SPOTTER_TPU_RESTARTS`, set by
the supervisor) land in /metrics. A `PreemptionWatcher` (SIGTERM + the
`SPOTTER_TPU_PREEMPTION_FILE`/`_URL` maintenance source) drains and exits
with the distinct preemption code. When `SPOTTER_TPU_ADMIN_TOKEN` is set,
the state-changing admin endpoints (/drain, /profile) require it in the
`X-Admin-Token` header — without the guard any client could drain a replica
out of the fleet or trigger a trace capture.

Ragged scheduling (ISSUE 9): `--ragged` (or `SPOTTER_TPU_RAGGED=1`) swaps
the batcher's per-bucket FIFO for the unified scheduler — deadline-slack
admission ordering and mixed-resolution superbatch packing; /healthz then
reports `ragged: true` and /metrics grows `padding_waste_pct` +
`slack_at_dispatch_ms`. Unset keeps per-bucket semantics bit-identical.

Caching tier (ISSUE 5): `--cache-mb` (or `SPOTTER_TPU_CACHE_MAX_MB`) arms
the content-addressed result cache + single-flight coalescing tier in the
detector/batcher; /healthz then reports the cache's size state and /metrics
the hit/miss/coalesce/eviction counters. Unset/0 leaves serving
bit-identical to a cache-less build.
"""

import argparse
import asyncio
import json
import logging
import math
import os
import tempfile

import pydantic
from aiohttp import web

from spotter_tpu import obs
from spotter_tpu.engine.metrics import setup_phases_s
from spotter_tpu.obs import http as obs_http
from spotter_tpu.obs import logs as obs_logs
from spotter_tpu.ops import preprocess
from spotter_tpu.serving import integrity, lifecycle, tenancy, wire
from spotter_tpu.serving.detector import QueriesUnsupportedError
from spotter_tpu.serving.fleet import classify_request
from spotter_tpu.serving.resilience import AdmissionError
from spotter_tpu.serving.tenancy import TenantQuotaError
from spotter_tpu.testing import faults, stub_engine

logger = logging.getLogger(__name__)

# Back-compat aliases: the admin guard moved to obs/http.py (ISSUE 7) so
# /debug/traces on the router shares it; existing imports keep working.
ADMIN_TOKEN_ENV = obs_http.ADMIN_TOKEN_ENV
ADMIN_TOKEN_HEADER = obs_http.ADMIN_TOKEN_HEADER
_admin_rejection = obs_http.admin_rejection


def _rmdir_quiet(path: str) -> None:
    """Drop a just-created empty trace dir on failed /profile requests."""
    try:
        os.rmdir(path)
    except OSError:  # non-empty (trace partially written) or already gone
        pass


def _shed_response(exc: AdmissionError) -> web.Response:
    # Retry-After never renders 0 (REVIEW): sub-second hints (the tenant
    # rate-shed jitter floors at 0.05 s) ceil to 1 — a "0" header invites
    # the immediate retry the shed exists to push back. The precise float
    # rides in the body for clients that want fast pacing.
    return web.json_response(
        {
            "error": str(exc),
            "status": exc.status,
            "retry_after_s": round(max(exc.retry_after_s, 0.0), 3),
        },
        status=exc.status,
        headers={"Retry-After": f"{max(1, math.ceil(exc.retry_after_s))}"},
    )


def _not_ready_response(tracker: lifecycle.StartupTracker) -> web.Response:
    return web.json_response(
        {"error": f"replica starting up ({tracker.state})", "status": 503},
        status=503,
        headers={"Retry-After": "2"},
    )


def _build_detector_blocking(model_name: str | None):
    """The heavy half of bring-up, run in an executor: the model/engine
    build (`build_detector_app` arms the compile cache before its first
    jit; the stub engine compiles nothing)."""
    if stub_engine.stub_mode_enabled():
        logger.warning(
            "STUB ENGINE ACTIVE (%s) — canned detections, no device; "
            "never production", stub_engine.STUB_ENGINE_ENV,
        )
        return stub_engine.build_stub_detector()
    from spotter_tpu.serving.app import build_detector_app

    return build_detector_app(model_name, warmup=False)


def make_app(
    detector=None,
    model_name: str | None = None,
    warmup: bool = False,
    preemption: bool = False,
    bringup_exit_cb=os._exit,
    fatal_exit_cb=os._exit,
    integrity_exit_cb=os._exit,
) -> web.Application:
    """Build the serving app.

    With `detector` given (tests), the app is ready immediately. Otherwise
    bring-up runs as a background task after the HTTP surface binds: the
    startupProbe watches /startupz while the model loads and warms.
    `preemption=True` (the `main()` path) installs the PreemptionWatcher.

    A FAILED bring-up (bad MODEL_NAME, OOM, compile error) must not leave
    the process alive serving 503s forever — the supervisor/kubelet only
    react to process exit. It marks the terminal `failed` startup state and
    calls `bringup_exit_cb(BRINGUP_FAILED_EXIT_CODE)` (default `os._exit`,
    overridable in tests) so the crash-loop/backoff machinery takes over.

    Engine fault domain (ISSUE 4): the batcher is wired with the startup
    tracker (a degraded-dp rebuild re-enters `warming` on /startupz) and
    with `fatal_exit_cb` — on a fatal device error at dp=1 the process
    exits `FATAL_ENGINE_EXIT_CODE` (85) for an immediate supervisor warm
    restart instead of serving breaker-open 503s off a dead chip.

    Verified readiness (ISSUE 17): with the integrity plane enabled
    (`SPOTTER_TPU_INTEGRITY`, default on), bring-up passes through the
    `verifying` state — on-device weights attestation plus a golden probe
    through the real batcher must PASS before READY, on cold start and
    warm compile-cache restore alike, and again after every degraded-dp
    rebuild. A failure exits `INTEGRITY_EXIT_CODE` (86) via
    `integrity_exit_cb` so the supervisor cold-restarts with the suspect
    compile cache quarantined. The injected-detector path (tests) skips
    verification, exactly like it skips bring-up.
    """
    app = web.Application(client_max_size=64 * 1024 * 1024)
    tracker = lifecycle.StartupTracker()
    app["startup"] = tracker
    app["detector"] = detector
    # tenant isolation plane (ISSUE 19): None unless configured — every
    # tenant branch below is then absent and serving is bit-identical
    tenant_plane = tenancy.from_env()
    app["tenancy"] = tenant_plane
    if faults.maybe_activate_from_env() is not None:
        logger.warning(
            "FAULT INJECTION ACTIVE (%s) — this server is a chaos target, "
            "never production",
            faults.FAULTS_ENV,
        )

    def _stamp_identity(det) -> None:
        # fleet-mergeable snapshot identity (ISSUE 12): the model name
        # joins replica_id/pid/generation in every /metrics snapshot so
        # the aggregator's per-replica table and restart detection are
        # principled. Generation itself rides set_restarts (below).
        model = (
            model_name
            or os.environ.get("MODEL_NAME")
            or ("stub" if stub_engine.stub_mode_enabled() else None)
        )
        if model is not None:
            det.engine.metrics.set_identity(model=model)
        # weights digest (ISSUE 15): engines that can fingerprint their
        # loaded params expose weights_digest(); an operator-pinned
        # SPOTTER_TPU_WEIGHTS_DIGEST (already stamped at Metrics init)
        # outranks the computed one
        from spotter_tpu.engine.metrics import default_weights_digest

        digest_fn = getattr(det.engine, "weights_digest", None)
        if digest_fn is not None and default_weights_digest() is None:
            try:
                digest = digest_fn() if callable(digest_fn) else digest_fn
            except Exception:
                digest = None
            if digest:
                det.engine.metrics.set_identity(weights_digest=str(digest))

    def _wire_fault_domain(det) -> None:
        det.batcher.attach_lifecycle(tracker)
        if det.batcher.fatal_exit_cb is None:
            det.batcher.fatal_exit_cb = fatal_exit_cb
        # HBM telemetry (ISSUE 10): poll device.memory_stats() into the
        # perf ledger's gauges. Only engines with real devices get a
        # sampler (stub/fake engines have no `.devices`); the thread is a
        # daemon and is stopped on app cleanup. SPOTTER_TPU_HBM_SAMPLE_S=0
        # disables it.
        from spotter_tpu.obs import perf as obs_perf

        devices_fn = getattr(det.engine, "devices", None)
        if devices_fn is not None and app.get("hbm_sampler") is None:
            sampler = obs_perf.HbmSampler(
                devices_fn, det.engine.metrics.perf
            )
            if sampler.start():
                app["hbm_sampler"] = sampler

    if detector is not None:
        detector.engine.metrics.set_restarts(lifecycle.restarts_from_env())
        _stamp_identity(detector)
        _wire_fault_domain(detector)
        detector.attach_tenancy(tenant_plane)
        tracker.mark_ready(detector.engine.metrics)

    def _make_integrity_recheck(plane):
        def recheck(source: str) -> bool:
            if plane.verify_blocking(source):
                return True
            plane.integrity_exit(plane.last_error or source)
            return False

        return recheck

    async def _bring_up(app: web.Application) -> None:
        loop = asyncio.get_running_loop()
        # set-up phases, each a span under `setup.`: /metrics shows them as
        # one dict (`setup_phases_s`) and readiness logs them in one line.
        # The first is everything before bring-up began: interpreter start,
        # imports, the HTTP surface.
        obs.record_span("setup.imports", lifecycle.process_age_s())
        try:
            det = await loop.run_in_executor(
                None, _build_detector_blocking, model_name
            )
            tracker.mark(lifecycle.WARMING)
            if warmup:
                with obs.span("setup.warmup"):
                    await loop.run_in_executor(None, det.engine.warmup)
            app["detector"] = det
            det.engine.metrics.set_restarts(lifecycle.restarts_from_env())
            _stamp_identity(det)
            _wire_fault_domain(det)
            det.attach_tenancy(tenant_plane)
            # SDC injection seam (ISSUE 17, chaos only): corrupt the live
            # weights AFTER load, BEFORE verification — the flipped-bit-
            # after-restore shape the attestation gate must catch
            n_corrupt = faults.take_corrupt_weights()
            if n_corrupt and hasattr(det.engine, "corrupt_weights"):
                logger.warning(
                    "FAULT: corrupting %d weight leaves before "
                    "verification", n_corrupt,
                )
                det.engine.corrupt_weights(n_corrupt)
            plane = None
            if integrity.integrity_enabled():
                # verified readiness (ISSUE 17): attest + golden probe must
                # pass before READY — a warm compile-cache restore is just
                # as much an SDC ingress as a cold load, so both verify
                tracker.mark(lifecycle.VERIFYING)
                plane = integrity.IntegrityPlane(
                    det.engine, det.batcher, exit_cb=integrity_exit_cb
                )
                app["integrity"] = plane
                source = (
                    "warm-restore"
                    if lifecycle.restarts_from_env() > 0
                    else "cold-start"
                )
                if not await plane.verify(source):
                    tracker.mark_failed(plane.last_error or "integrity")
                    plane.integrity_exit(plane.last_error or source)
                    return
                det.batcher.integrity_recheck_cb = (
                    _make_integrity_recheck(plane)
                )
            ttr = tracker.mark_ready(det.engine.metrics)
            logger.info(
                "replica ready in %.1f s; set-up phases (s): %s", ttr,
                json.dumps(setup_phases_s()),
            )
            if plane is not None:
                await plane.start()
        except asyncio.CancelledError:  # server shutdown mid-bring-up
            raise
        except Exception as exc:
            logger.exception("replica bring-up failed; exiting %d",
                             lifecycle.BRINGUP_FAILED_EXIT_CODE)
            tracker.mark_failed(f"{type(exc).__name__}: {exc}")
            bringup_exit_cb(lifecycle.BRINGUP_FAILED_EXIT_CODE)

    async def on_startup(app: web.Application) -> None:
        # profiler server after the loop exists; tasks stored for cleanup
        from spotter_tpu.engine import profiler

        profiler.install_span_annotator()
        profiler.maybe_start_profiler_server()
        if app["detector"] is None:
            app["bringup_task"] = asyncio.create_task(_bring_up(app))
        if preemption:
            async def drain_on_preempt():
                det = app["detector"]
                if det is not None:
                    await det.drain()

            watcher = lifecycle.PreemptionWatcher(drain_on_preempt)
            app["preemption_watcher"] = watcher
            await watcher.start()

    async def detect(request: web.Request) -> web.Response:
        # the request's life in the handler, sheds included, as one span (a
        # wait: no annotation, and on no request trace)
        with obs.span("app.detect", obs.NO_TRACE):
            return await _detect(request)

    async def _detect(request: web.Request) -> web.Response:
        # Request-scoped trace (ISSUE 7): continue the edge's traceparent or
        # mint ids from/with X-Request-ID; EVERY branch below — sheds
        # included — echoes the request id, and completed traces land in
        # the flight recorder with per-stage Server-Timing on the response.
        trace, request_id = obs_http.begin_http_trace(request)
        tenant = None
        tadm = None

        def done(resp: web.Response) -> web.Response:
            # per-tenant occupancy + SLO accounting (ISSUE 19): every
            # outcome releases the inflight slot exactly once; sheds and
            # server errors burn the tenant's budget, everything else
            # credits it
            if tadm is not None:
                tadm.release(
                    good=resp.status not in (429, 503) and resp.status < 500
                )
            # replica identity header (ISSUE 14 satellite): every /detect
            # outcome — sheds and errors included — names the replica that
            # produced it, so a slow or corrupt response joins /debug/fleet
            # rows and stitched traces by replica id. The deploy version
            # rides along (ISSUE 15) so clients, edges and the rollout
            # controller can attribute every response to a build.
            if det is not None:
                resp.headers[wire.REPLICA_HEADER] = (
                    det.engine.metrics.replica_id
                )
                resp.headers[wire.VERSION_HEADER] = (
                    det.engine.metrics.version
                )
            return obs_http.finish_http_trace(
                trace, request_id, resp, server_timing=True
            )

        det = request.app["detector"]
        if det is None:  # still loading/warming: shed, probe /startupz
            return done(_not_ready_response(tracker))
        if faults.take_flaky(det.engine.metrics.replica_id):
            # injected intermittent failure (ISSUE 14 chaos matrix): the
            # gray-failure shape hard ejection can't see — a 500 rate below
            # the consecutive-failure threshold. 500 is a REPLAYABLE status
            # at the pool, so the edge masks each one
            return done(
                web.json_response(
                    {"error": "injected flaky failure", "status": 500},
                    status=500,
                )
            )
        if tenant_plane is not None:
            # edge quota (ISSUE 19): resolve the tenant and charge its
            # token bucket / inflight cap BEFORE any parse/fetch/decode
            # work — an over-quota tenant sheds 429 here, strictly before
            # any in-quota request could be shed below
            tenant = tenant_plane.resolve(request.headers)
            try:
                tadm = tenant_plane.try_admit(tenant)
            except TenantQuotaError as exc:
                det.engine.metrics.record_shed()
                det.engine.metrics.record_admit_shed(
                    classify_request(request.headers, None)[0]
                )
                return done(_shed_response(exc))
        try:
            shed = det.check_admission()
            if shed is not None:  # draining / breaker open: reject before parsing
                return done(_shed_response(shed))
            try:
                payload = await request.json()
            except json.JSONDecodeError:
                return done(web.Response(status=400, text="Invalid JSON body"))
            # request class (ISSUE 8): X-Request-Class header > request_class
            # payload key (stripped) > deadline tag > env default — the PR 6
            # fleet precedence, honored at the replica too so the brownout
            # ladder's bulk-only rung and the limiter's class-ordered shed work
            # with or without a fleet edge in front
            cls, payload = classify_request(request.headers, payload)
            shed = det.check_admission(cls, tenant)
            if shed is not None:  # brownout bulk shed: reject before fetching
                return done(_shed_response(shed))
            # data-plane observations (ISSUE 11): per-URL cache outcomes for
            # X-Cache and deterministic-failure verdicts for X-Spotter-Negative
            info: dict = {}
            try:
                response = await det.detect(
                    payload, cls=cls, info=info, tenant=tenant
                )
            except pydantic.ValidationError as exc:
                return done(web.Response(status=400, text=f"Invalid request: {exc}"))
            except QueriesUnsupportedError as exc:
                # open-vocab queries on a closed-set model (ISSUE 13): the
                # request can never succeed on this deployment — a client
                # error, not a server one
                return done(web.Response(status=400, text=str(exc)))
            except AdmissionError as exc:  # every image shed -> 429/503
                return done(_shed_response(exc))
            except Exception:
                logger.exception("detect failed")
                return done(web.Response(status=500, text="Internal server error"))
            # the reply built and dumped, inline on the event loop
            with obs.span("app.serialize", trace, annotate=True):
                body = response.model_dump(exclude_none=True)
                # binary wire format (ISSUE 11): `Accept: application/x-spotter-frame`
                # negotiates the length-prefixed frame (raw JPEG segments, deflated
                # header — no base64 tax). NOT negotiated -> the exact pre-existing
                # json_response call, byte-identical on the wire (exclude_none: the
                # `degraded` marker is absent unless a brownout concession shaped
                # this response — schemas.py contract).
                frame = wire.wants_frame(request.headers.get("Accept"))
                if frame:
                    # corrupt_frame injection (ISSUE 14): while armed, one byte of
                    # the encoded frame is flipped AFTER the checksums were
                    # computed — the deterministic way to prove the edge CRC
                    # validator catches, counts, and replays corruption
                    resp = web.Response(
                        body=faults.corrupt_frame_bytes(
                            wire.encode_frame(body), det.engine.metrics.replica_id
                        ),
                        content_type=wire.FRAME_CONTENT_TYPE,
                    )
                else:
                    resp = web.json_response(body)
            x_cache = wire.summarize_cache_outcomes(
                (info.get("cache") or {}).values()
            )
            if x_cache is not None:
                resp.headers[wire.X_CACHE_HEADER] = x_cache
            verdicts = wire.encode_negative_header(info.get("negative") or {})
            if verdicts is not None:
                resp.headers[wire.NEGATIVE_HEADER] = verdicts
            out_bytes = resp.body
            det.engine.metrics.record_wire(
                len(out_bytes) if isinstance(out_bytes, (bytes, bytearray)) else 0,
                frame,
            )
            return done(resp)
        finally:
            # leak guard (REVIEW): a client disconnect (CancelledError
            # in any await) or an uncaught error below must still free
            # the tenant's inflight slot, or the tenant is permanently
            # 429-locked at its inflight cap and its occupancy skews
            # the limiter/brownout forever. Idempotent: when done()
            # ran, it already released with the real outcome; this
            # no-outcome release never touches the SLO burn.
            if tadm is not None:
                tadm.release(good=None)

    async def startupz(request: web.Request) -> web.Response:
        """Startup probe: 200 only once the replica reached ready. A long
        warmup answers 503 with the state, which a startupProbe tolerates up
        to its failureThreshold — unlike a liveness probe, it won't kill."""
        snap = tracker.snapshot()
        return web.json_response(snap, status=200 if tracker.ready else 503)

    async def healthz(request: web.Request) -> web.Response:
        """Readiness: 503 drops this replica from the LB while starting up,
        while the breaker is open, or while a drain is in progress."""
        det = request.app["detector"]
        if det is None:
            return _not_ready_response(tracker)
        health = det.health()
        health["startup"] = tracker.state
        health["pool"] = lifecycle.pool_from_env()
        return web.json_response(health, status=200 if health["ready"] else 503)

    async def livez(request: web.Request) -> web.Response:
        """Liveness: the process is serving HTTP — restart only on hang."""
        return web.json_response({"status": "alive"})

    async def drain(request: web.Request) -> web.Response:
        """k8s preStop: stop admitting, flush the queue, wait for in-flight
        batches. Idempotent — a second call reports the drained state.
        Guarded by SPOTTER_TPU_ADMIN_TOKEN when set.

        Body (optional JSON, ISSUE 15): {"deadline_ms": N} caps the wait;
        the response reports `in_flight` (batches still running at the
        deadline) and `queued_failed`, so a rollout controller or preStop
        hook waits precisely instead of sleeping a fixed grace period."""
        rejected = _admin_rejection(request)
        if rejected is not None:
            return rejected
        det = request.app["detector"]
        if det is None:
            return _not_ready_response(tracker)
        try:
            body = await request.json()
        except json.JSONDecodeError:
            body = {}
        timeout_s = None
        if isinstance(body, dict) and "deadline_ms" in body:
            try:
                timeout_s = max(float(body["deadline_ms"]), 0.0) / 1000.0
            except (TypeError, ValueError):
                return web.Response(
                    status=400, text="deadline_ms must be a number"
                )
        summary = await det.drain(timeout_s)
        return web.json_response(summary)

    async def metrics(request: web.Request) -> web.Response:
        det = request.app["detector"]
        if det is None:
            return obs_http.metrics_response(
                request, {"startup": tracker.snapshot()}
            )
        # JSON view unchanged for existing consumers; ?format=prometheus or
        # Accept: text/plain selects the text exposition (ISSUE 7)
        snap = det.engine.metrics.snapshot()
        # the persistent compile cache's hits and misses (set-up's compiles)
        snap.update(lifecycle.compile_cache_totals())
        # output-integrity plane (ISSUE 17): verification + probe + attest
        # counters ride the replica snapshot additively
        plane = request.app.get("integrity")
        if plane is not None:
            snap["integrity"] = plane.snapshot()
        # per-tenant accounting (ISSUE 19): bounded top-K view — prom
        # renders it {tenant=..., stat=...}; absent when unconfigured
        if tenant_plane is not None:
            snap["tenants"] = tenant_plane.metrics_view()
        return obs_http.metrics_response(request, snap)

    async def debug_tenants(request: web.Request) -> web.Response:
        """Full per-tenant table (ISSUE 19) — admin-token-gated like
        /profile; the bounded top-K view lives in /metrics."""
        rejected = _admin_rejection(request)
        if rejected is not None:
            return rejected
        if tenant_plane is None:
            return web.json_response({"enabled": False})
        return web.json_response(tenant_plane.snapshot())

    async def profile(request: web.Request) -> web.Response:
        """Capture a jax.profiler trace of in-flight device work.

        Body (optional JSON): {"duration_s": 1.0}. The server picks the
        trace directory (under SPOTTER_TPU_PROFILE_DIR or the system temp
        dir — never a client-supplied path) and returns it; open it with
        TensorBoard/xprof. Guarded by SPOTTER_TPU_ADMIN_TOKEN when set.
        """
        rejected = _admin_rejection(request)
        if rejected is not None:
            return rejected
        from spotter_tpu.engine import profiler

        try:
            body = await request.json()
        except json.JSONDecodeError:
            body = {}
        if not isinstance(body, dict):
            return web.Response(status=400, text="body must be a JSON object")
        try:
            duration_s = min(float(body.get("duration_s", 1.0)), 30.0)
        except (TypeError, ValueError):
            return web.Response(status=400, text="duration_s must be a number")
        if not duration_s > 0.0:  # also rejects NaN before any dir is made
            return web.Response(status=400, text="duration_s must be > 0")
        base = os.environ.get("SPOTTER_TPU_PROFILE_DIR")
        log_dir = tempfile.mkdtemp(prefix="spotter-trace-", dir=base or None)
        try:
            summary = await asyncio.get_running_loop().run_in_executor(
                None, profiler.capture, log_dir, duration_s
            )
        except ValueError as exc:  # bad duration (e.g. <= 0, NaN)
            _rmdir_quiet(log_dir)
            return web.Response(status=400, text=str(exc))
        except RuntimeError as exc:  # capture already in progress
            _rmdir_quiet(log_dir)
            return web.Response(status=409, text=str(exc))
        return web.json_response(summary)

    async def on_cleanup(app: web.Application) -> None:
        sampler = app.get("hbm_sampler")
        if sampler is not None:
            sampler.stop()
        plane = app.get("integrity")
        if plane is not None:
            await plane.aclose()
        task = app.get("bringup_task")
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
        watcher = app.get("preemption_watcher")
        if watcher is not None:
            await watcher.stop()
        if app["detector"] is not None:
            await app["detector"].aclose()

    app.router.add_post("/detect", detect)
    app.router.add_get("/startupz", startupz)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/livez", livez)
    app.router.add_post("/drain", drain)
    app.router.add_get("/metrics", metrics)
    app.router.add_post("/profile", profile)
    # per-tenant isolation table (ISSUE 19): admin-token-gated like /profile
    app.router.add_get("/debug/tenants", debug_tenants)
    # flight-recorder view (ISSUE 7): admin-token-gated like /profile
    app.router.add_get("/debug/traces", obs_http.make_debug_traces_handler())
    # device-efficiency ledger view (ISSUE 10): top-K expensive dispatches
    # (trace ids join /debug/traces), compile-shape table, HBM, burn-rate —
    # admin-token-gated like /profile
    app.router.add_get(
        "/debug/perf",
        obs_http.make_debug_perf_handler(
            lambda: (
                app["detector"].engine.metrics
                if app["detector"] is not None
                else None
            )
        ),
    )
    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)
    return app


def main() -> None:
    parser = argparse.ArgumentParser(description="spotter-tpu standalone detection server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--model", default=None, help="overrides MODEL_NAME env")
    parser.add_argument("--no-warmup", action="store_true")
    parser.add_argument(
        "--serve-dp",
        default=None,
        help="data-parallel serving width: shard batches over this many "
        "local chips with aggregate bucket sizing (SPOTTER_TPU_SERVE_DP; "
        "'all' = every local chip)",
    )
    parser.add_argument(
        "--serve-tp",
        default=None,
        help="tensor-parallel width: split the model's attention/MLP "
        "weights over this many chips per dp group "
        "(SPOTTER_TPU_SERVE_TP; composes with --serve-dp into a dp×tp "
        "mesh — the bucket ladder scales by dp only). Use when one chip's "
        "HBM can't hold (or serve fast enough) the model, e.g. "
        "OWLv2/ViT-L at tp=2/4",
    )
    parser.add_argument(
        "--explain-sharding",
        action="store_true",
        help="print the per-param sharding report for the resolved mesh "
        "(param path -> PartitionSpec -> per-device bytes, dead TP rules "
        "flagged) and exit without serving",
    )
    parser.add_argument(
        "--device-preprocess",
        action="store_true",
        help="uint8 ingest + on-device rescale/normalize "
        "(SPOTTER_TPU_DEVICE_PREPROCESS=1): 4x less H2D traffic, decode-only "
        "host work",
    )
    parser.add_argument(
        "--decode-workers",
        type=int,
        default=None,
        help=f"host decode/resize pool size ({preprocess.DECODE_WORKERS_ENV})",
    )
    parser.add_argument(
        "--ragged",
        action="store_true",
        help="ragged mixed-resolution batching + deadline-slack scheduling "
        "(SPOTTER_TPU_RAGGED=1): mixed-size images pack into one padded "
        "superbatch chosen to minimize padded-pixel waste, slo traffic "
        "fills dispatches before bulk; unset keeps per-bucket FIFO "
        "semantics bit-identical",
    )
    parser.add_argument(
        "--cache-mb",
        type=float,
        default=None,
        help="content-addressed result cache + request coalescing budget in "
        "MB (SPOTTER_TPU_CACHE_MAX_MB; 0 disables the tier — the default)",
    )
    parser.add_argument(
        "--stub-engine",
        action="store_true",
        help=f"canned-detection stub engine ({stub_engine.STUB_ENGINE_ENV}=1); "
        "failover tests/bench only",
    )
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    # SPOTTER_TPU_LOG_JSON=1: structured logs carrying the trace/request id
    # of whatever request was active when the line was emitted (ISSUE 7)
    obs_logs.maybe_setup_json_logging()
    if args.stub_engine:
        os.environ[stub_engine.STUB_ENGINE_ENV] = "1"
    # ingest/topology flags land in the env: bring-up (and any supervisor
    # respawn of it) reads them there, so flag and env behave identically
    if args.serve_dp is not None:
        os.environ["SPOTTER_TPU_SERVE_DP"] = str(args.serve_dp)
    if args.serve_tp is not None:
        os.environ["SPOTTER_TPU_SERVE_TP"] = str(args.serve_tp)
    if args.explain_sharding:
        from spotter_tpu.serving.app import explain_sharding

        print(explain_sharding(args.model))
        return
    if args.device_preprocess:
        os.environ["SPOTTER_TPU_DEVICE_PREPROCESS"] = "1"
    if args.ragged:
        from spotter_tpu.engine.scheduler import RAGGED_ENV

        os.environ[RAGGED_ENV] = "1"
    if args.decode_workers is not None:
        os.environ[preprocess.DECODE_WORKERS_ENV] = str(args.decode_workers)
    if args.cache_mb is not None:
        from spotter_tpu.caching.result_cache import CACHE_MAX_MB_ENV

        os.environ[CACHE_MAX_MB_ENV] = str(args.cache_mb)
    web.run_app(
        make_app(
            model_name=args.model, warmup=not args.no_warmup, preemption=True
        ),
        host=args.host,
        port=args.port,
    )


if __name__ == "__main__":
    main()
