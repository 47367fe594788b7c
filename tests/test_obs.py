"""Observability tier (ISSUE 7): request-scoped tracing, flight recorder,
Prometheus exposition, and the fatal-exit trace dump — all on the CPU test
backend, model-free (stub engine).

The cross-process acceptance case reuses testing/cluster.py: a REAL
supervised stub replica serves /detect behind the in-process edge (router
and fleet apps), the trace propagates over HTTP via traceparent +
X-Request-ID, and the replica's Server-Timing merges into ONE edge trace
whose summed spans reconcile with the response latency.
"""

import asyncio
import json
import os
import re

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

os.environ["SPOTTER_TPU_TINY"] = "1"

import httpx

from spotter_tpu import obs
from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.errors import FATAL_ENGINE_EXIT_CODE, FatalEngineError
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.obs import http as obs_http
from spotter_tpu.obs import prom
from spotter_tpu.serving.detector import AmenitiesDetector
from spotter_tpu.serving.replica_pool import ReplicaPool
from spotter_tpu.serving.router import make_router_app
from spotter_tpu.serving.standalone import make_app
from spotter_tpu.testing import cluster, faults
from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient

# the injected device latency (ms) for attribution/reconciliation asserts:
# large enough that edge/HTTP overhead fits inside the 5% tolerance
DEVICE_MS = 150.0

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def fresh_recorder(monkeypatch):
    """Each test gets its own recorder built from a clean env."""
    monkeypatch.delenv(obs.TRACE_RING_ENV, raising=False)
    monkeypatch.delenv(obs.TRACE_SLOWEST_K_ENV, raising=False)
    monkeypatch.delenv(obs_http.ADMIN_TOKEN_ENV, raising=False)
    obs.reset_recorder()
    obs.set_current_trace(None)
    yield
    obs.reset_recorder()
    obs.set_current_trace(None)


def _stub_detector(**batcher_kwargs) -> AmenitiesDetector:
    engine = StubEngine()
    batcher = MicroBatcher(engine, max_delay_ms=2.0, **batcher_kwargs)
    return AmenitiesDetector(engine, batcher, StubHttpClient())


# ---------------------------------------------------------------------------
# unit: trace context + propagation primitives


def test_traceparent_roundtrip():
    tr = obs.begin_trace(request_id="req-42")
    value = obs.traceparent_value(tr)
    parsed = obs.parse_traceparent(value)
    assert parsed == (tr.trace_id, tr.span_id)
    # the trace id is a deterministic function of the request id, so a
    # client that only kept its X-Request-ID can still find the trace
    assert tr.trace_id == obs.trace_id_for_request("req-42")
    for bad in (None, "", "garbage", "00-zz-11-01", "00-" + "0" * 32 + "-" + "1" * 16 + "-01"):
        assert obs.parse_traceparent(bad) is None


def test_child_trace_continues_parent():
    parent = obs.begin_trace(request_id="edge-req")
    child = obs.begin_trace(
        request_id="edge-req", traceparent=obs.traceparent_value(parent)
    )
    assert child.trace_id == parent.trace_id
    assert child.parent_span_id == parent.span_id


def test_span_capture_and_server_timing_merge():
    tr = obs.begin_trace(request_id="r")
    with obs.span(obs.FETCH, tr):
        pass
    tr.add_span_ms(obs.DEVICE, 0.0, 12.5)
    merged = obs_http.merge_server_timing(tr, "decode;dur=3.25, h2d;dur=1.0")
    assert merged == pytest.approx(4.25)
    totals = tr.stage_totals()
    assert totals[obs.DEVICE] == pytest.approx(12.5)
    assert totals[obs.DECODE] == pytest.approx(3.25)
    assert set(totals) >= {obs.FETCH, obs.DEVICE, obs.DECODE, obs.H2D}


def test_slow_stage_fault_parsing_and_delay():
    assert faults._parse_slow_stage("device:100") == {"device": 0.1}
    assert faults._parse_slow_stage("device:100;fetch:50") == {
        "device": 0.1, "fetch": 0.05,
    }
    with pytest.raises(ValueError):
        faults._parse_slow_stage("device")
    with pytest.raises(ValueError):
        faults._parse_slow_stage("device:abc")
    assert faults.stage_delay_s(obs.DEVICE) == 0.0  # no plan active
    with faults.inject(slow_stage="device:40"):
        assert faults.stage_delay_s(obs.DEVICE) == pytest.approx(0.04)
        assert faults.stage_delay_s(obs.FETCH) == 0.0


def test_slow_stage_env_activation(monkeypatch):
    monkeypatch.setenv(faults.FAULTS_ENV, "slow_stage=queue_wait:5")
    plan = faults.maybe_activate_from_env()
    try:
        assert faults.stage_delay_s(obs.QUEUE_WAIT) == pytest.approx(0.005)
    finally:
        faults._active = None
    monkeypatch.setenv(faults.FAULTS_ENV, "slow_stage=broken")
    with pytest.raises(ValueError):
        faults.maybe_activate_from_env()
    faults._active = None


# ---------------------------------------------------------------------------
# in-process: standalone server contract


def test_detect_trace_has_full_span_set_and_reconciles():
    """One /detect through the real app + batcher + stub engine: the trace
    carries every non-edge stage and its summed spans reconcile with the
    measured response latency within the 5% acceptance tolerance."""

    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        gaps = []
        async with TestClient(TestServer(app)) as client:
            # warmup: first-use costs (pydantic validators, PIL JPEG
            # plugin, profiler import on startup) must not land inside the
            # measured trace's unattributed gap
            warm = await client.post(
                "/detect", json={"image_urls": ["http://example.com/w.jpg"]}
            )
            assert warm.status == 200
            with faults.inject(slow_stage=f"device:{DEVICE_MS:.0f}"):
                # best-of-3: the reconciliation property is about the
                # TRACE's structure; a 1-core CI box can drop a GC pause
                # into any single request's unattributed gap
                for attempt in range(3):
                    rid = f"req-reconcile-{attempt}"
                    resp = await client.post(
                        "/detect",
                        json={"image_urls": ["http://example.com/a.jpg"]},
                        headers={obs.REQUEST_ID_HEADER: rid},
                    )
                    assert resp.status == 200
                    assert resp.headers[obs.REQUEST_ID_HEADER] == rid
                    assert obs.TRACEPARENT_HEADER in resp.headers
                    timing = resp.headers[obs_http.SERVER_TIMING_HEADER]
                    assert "device;dur=" in timing
                    (t,) = obs.get_recorder().lookup(rid)
                    names = {s["name"] for s in t["spans"]}
                    assert names >= {
                        obs.FETCH, obs.DECODE, obs.QUEUE_WAIT, obs.H2D,
                        obs.DEVICE, obs.POSTPROCESS,
                    }
                    assert len(t["spans"]) >= 6
                    assert t["duration_ms"] >= DEVICE_MS
                    # the injected latency is attributed to the device span
                    device_ms = sum(
                        s["duration_ms"] for s in t["spans"]
                        if s["name"] == obs.DEVICE
                    )
                    assert device_ms >= DEVICE_MS
                    # the stage spans tile; a detail span lies inside one
                    span_sum = sum(
                        s["duration_ms"] for s in t["spans"]
                        if not s.get("detail")
                    )
                    gaps.append(
                        abs(span_sum - t["duration_ms"]) / t["duration_ms"]
                    )
                    if gaps[-1] < 0.05:
                        break
        # the spans tile the request: sum reconciles with the response
        # latency within the 5% acceptance tolerance
        assert min(gaps) < 0.05, f"no attempt reconciled: gaps={gaps}"

    asyncio.run(run())


def test_shed_responses_echo_request_id_and_pin_trace():
    async def run():
        detector = _stub_detector()
        detector.batcher._draining = True  # check_admission -> 503 shed
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers={obs.REQUEST_ID_HEADER: "req-shed"},
            )
            assert resp.status == 503
            assert resp.headers[obs.REQUEST_ID_HEADER] == "req-shed"
        snap = obs.get_recorder().snapshot()
        shed = [t for t in snap["errors"] if t["request_id"] == "req-shed"]
        assert shed and shed[0]["status"] == "shed"

    asyncio.run(run())


def test_errored_request_trace_lands_in_pinned_error_set():
    """An engine failure that kills a whole request pins its trace."""

    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            with faults.inject(engine_error=1):
                resp = await client.post(
                    "/detect",
                    json={"image_urls": ["http://example.com/bad.jpg"]},
                    headers={obs.REQUEST_ID_HEADER: "req-errored"},
                )
            assert resp.status == 200  # per-image containment, as ever
            body = await resp.json()
            assert "Processing Error" in body["images"][0]["error"]
        snap = obs.get_recorder().snapshot()
        pinned = [t for t in snap["errors"] if t["request_id"] == "req-errored"]
        assert len(pinned) == 1
        # single-item batch: the raw error surfaces (nothing was isolated)
        assert pinned[0]["status"] == "RuntimeError"
        assert "injected engine failure" in pinned[0]["error"]

    asyncio.run(run())


def test_poison_isolation_pins_only_the_poisoned_trace():
    """The bisect-isolation case: two co-batched requests, one poisoned —
    only the poisoned item's trace carries PoisonImageError and lands in
    the pinned error set; the innocent neighbor's trace stays ok."""

    async def run():
        engine = StubEngine()
        batcher = MicroBatcher(engine, max_delay_ms=20.0)
        good = Image.fromarray(np.zeros((8, 8, 3), np.uint8))
        bad = faults.poison_image(
            Image.fromarray(np.full((8, 8, 3), 255, np.uint8))
        )
        rec = obs.get_recorder()

        async def one(img, request_id):
            tr = obs.begin_trace(request_id=request_id)
            try:
                await batcher.submit(img)
            except Exception:
                pass
            rec.record(tr)

        with faults.inject(poison_item=1):
            await asyncio.gather(
                one(good, "req-innocent"), one(bad, "req-poisoned")
            )
        await batcher.stop()
        snap = rec.snapshot()
        pinned = {t["request_id"]: t for t in snap["errors"]}
        assert "req-poisoned" in pinned
        assert "req-innocent" not in pinned
        assert pinned["req-poisoned"]["status"] == "PoisonImageError"
        ok = rec.lookup("req-innocent")
        assert ok and ok[0]["status"] == "ok"
        assert engine.metrics.snapshot()["poison_isolated_total"] == 1

    asyncio.run(run())


def test_debug_traces_admin_gated_and_lookup(monkeypatch):
    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers={obs.REQUEST_ID_HEADER: "req-gated"},
            )
            assert resp.status == 200
            monkeypatch.setenv(obs_http.ADMIN_TOKEN_ENV, "sekrit")
            assert (await client.get("/debug/traces")).status == 401
            ok = await client.get(
                "/debug/traces", headers={obs_http.ADMIN_TOKEN_HEADER: "sekrit"}
            )
            assert ok.status == 200
            snap = await ok.json()
            assert snap["enabled"] and snap["recorded_total"] >= 1
            by_id = await client.get(
                "/debug/traces?request_id=req-gated",
                headers={obs_http.ADMIN_TOKEN_HEADER: "sekrit"},
            )
            assert by_id.status == 200
            assert (await by_id.json())["traces"][0]["request_id"] == "req-gated"
            miss = await client.get(
                "/debug/traces?request_id=nope",
                headers={obs_http.ADMIN_TOKEN_HEADER: "sekrit"},
            )
            assert miss.status == 404

    asyncio.run(run())


def test_recorder_off_path_allocates_no_spans(monkeypatch):
    monkeypatch.setenv(obs.TRACE_RING_ENV, "0")
    obs.reset_recorder()
    assert not obs.get_recorder().enabled

    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            before = obs.trace_stats()
            resp = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers={obs.REQUEST_ID_HEADER: "req-off"},
            )
            assert resp.status == 200
            # correlation id still echoed with the recorder off
            assert resp.headers[obs.REQUEST_ID_HEADER] == "req-off"
            assert obs_http.SERVER_TIMING_HEADER not in resp.headers
            after = obs.trace_stats()
        assert after["spans_created"] == before["spans_created"]
        assert after["traces_created"] == before["traces_created"]
        assert obs.get_recorder().recorded_total == 0

    asyncio.run(run())


# ---------------------------------------------------------------------------
# Prometheus exposition

_METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"            # metric name
    # label values are quoted strings where backslash escapes (\\, \", \n)
    # are legal per the text-format spec (ISSUE 10 satellite)
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""  # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"  # more labels
    r" (-?[0-9.e+-]+|\+Inf|-Inf|NaN)"        # value
    r"( # \{trace_id=\"[0-9a-f]+\"\} [0-9.e+-]+ [0-9.e+-]+)?$"  # exemplar
)
_TYPE_LINE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$"
)


def _assert_parses(text: str) -> list[str]:
    lines = [ln for ln in text.splitlines() if ln.strip()]
    for ln in lines:
        if ln.startswith("#"):
            assert _TYPE_LINE.match(ln), f"bad TYPE line: {ln!r}"
        else:
            assert _METRIC_LINE.match(ln), f"bad metric line: {ln!r}"
    return lines


def test_prometheus_exposition_parses_and_json_unchanged():
    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(
                "/detect", json={"image_urls": ["http://example.com/a.jpg"]}
            )
            assert resp.status == 200
            # default stays JSON with every pre-existing field
            js = await (await client.get("/metrics")).json()
            for key in ("images_total", "errors_total", "breaker_state",
                        "latency_ms_p50", "shed_total", "cache_hits_total"):
                assert key in js
            # ?format=prometheus and Accept: text/plain both select text
            for kwargs in (
                {"path": "/metrics?format=prometheus"},
                {"path": "/metrics", "headers": {"Accept": "text/plain"}},
            ):
                text_resp = await client.get(
                    kwargs["path"], headers=kwargs.get("headers", {})
                )
                assert text_resp.status == 200
                assert text_resp.content_type == "text/plain"
                text = await text_resp.text()
                lines = _assert_parses(text)
                assert any(
                    ln.startswith("spotter_tpu_images_total") for ln in lines
                )
                assert "# TYPE spotter_tpu_images_total counter" in lines
                assert any(
                    ln.startswith("spotter_tpu_latency_ms_bucket{le=")
                    for ln in lines
                )
                assert any(
                    ln.startswith("spotter_tpu_latency_ms_count") for ln in lines
                )
                assert 'spotter_tpu_breaker_state_info{value="closed"} 1' in lines

    asyncio.run(run())


def test_prometheus_histogram_exemplar_carries_trace_id():
    m = Metrics()
    m.record_batch(
        4, 0.012,
        stages={obs.DECODE: 0.001, obs.DEVICE: 0.008},
        trace_id="a" * 32,
    )
    text = prom.render(m.snapshot())
    _assert_parses(text)
    ex_lines = [ln for ln in text.splitlines() if "# {trace_id=" in ln]
    assert len(ex_lines) == 1
    assert f'trace_id="{"a" * 32}"' in ex_lines[0]
    assert ex_lines[0].startswith('spotter_tpu_latency_ms_bucket{le="25"}')


def test_prometheus_renders_pool_and_fleet_snapshots():
    pool = ReplicaPool(["http://127.0.0.1:1", "http://127.0.0.1:2"])
    text = prom.render(pool.snapshot())
    lines = _assert_parses(text)
    assert any(
        ln.startswith("spotter_tpu_replicas_requests{url=") for ln in lines
    )
    from spotter_tpu.serving.fleet import static_fleet

    async def run():
        controller = static_fleet(
            ["http://127.0.0.1:1"], ["http://127.0.0.1:2"]
        )
        text = prom.render(controller.snapshot())
        lines = _assert_parses(text)
        assert any(
            ln.startswith('spotter_tpu_pool_size{pool="spot",state="ready"}')
            for ln in lines
        )

    asyncio.run(run())


def test_prometheus_label_escaping_per_text_format_spec():
    """Exposition escaping (ISSUE 10 satellite): label values carrying
    quotes, backslashes, and newlines (model names, replica URLs) must
    render per the text-format spec — backslash as \\\\, double quote as
    \\", newline as \\n — and every emitted line must still parse."""
    snapshot = {
        # string leaf -> info-style gauge with a `value` label
        "breaker_state": 'open "half"\nprobing\\mode',
        # labeled two-level map (the pool_size shape)
        "pool_size": {'spot"pool\n\\a': {"ready": 2}},
        # per-replica list labeled by url
        "replicas": [
            {"url": 'http://h/"x"\\path\nend', "requests": 3, "ok": True}
        ],
        # burn-rate map: plain labels stay plain
        "slo_burn_rate": {"fast": 0.5, "slow": 0.25},
    }
    text = prom.render(snapshot)
    lines = _assert_parses(text)
    assert (
        'spotter_tpu_breaker_state_info'
        '{value="open \\"half\\"\\nprobing\\\\mode"} 1'
    ) in lines
    assert (
        'spotter_tpu_pool_size{pool="spot\\"pool\\n\\\\a",state="ready"} 2'
    ) in lines
    assert (
        'spotter_tpu_replicas_requests'
        '{url="http://h/\\"x\\"\\\\path\\nend"} 3'
    ) in lines
    assert 'spotter_tpu_slo_burn_rate{window="fast"} 0.5' in lines
    # no raw newline may survive inside any sample line (it would split
    # the exposition mid-sample)
    for ln in lines:
        assert "\n" not in ln


def test_prometheus_escaping_round_trips_through_a_parser():
    """The escaped label value must decode back to the original string
    under the spec's unescaping rules — proof the renderer escapes, not
    mangles."""
    ugly = 'a"b\\c\nd'
    text = prom.render({"model_name": ugly})
    (line,) = [
        ln for ln in text.splitlines()
        if ln.startswith("spotter_tpu_model_name_info")
    ]
    start = line.index('value="') + len('value="')
    end = line.rindex('"}')
    escaped = line[start:end]
    decoded = (
        escaped.replace("\\\\", "\x00")
        .replace('\\"', '"')
        .replace("\\n", "\n")
        .replace("\x00", "\\")
    )
    assert decoded == ugly


# ---------------------------------------------------------------------------
# fatal-exit flight-recorder dump (the exit-85 acceptance case)


class _FatalEngine:
    """Duck-typed engine whose every detect() is a device loss."""

    def __init__(self) -> None:
        self.metrics = Metrics()
        self.batch_buckets = (1, 2, 4, 8)

    def detect(self, images):
        raise FatalEngineError("DATA_LOSS: device 0 halted (test)")


def test_fatal_exit_dumps_offending_trace(tmp_path, monkeypatch):
    monkeypatch.setenv(obs.TRACE_DUMP_DIR_ENV, str(tmp_path))
    exits: list[int] = []

    async def run():
        batcher = MicroBatcher(
            _FatalEngine(), max_delay_ms=1.0, fatal_exit_cb=exits.append
        )
        img = Image.fromarray(np.zeros((8, 8, 3), np.uint8))
        obs.begin_trace(request_id="req-fatal")
        with pytest.raises(FatalEngineError):
            await batcher.submit(img)
        await batcher.stop()

    asyncio.run(run())
    assert exits == [FATAL_ENGINE_EXIT_CODE]
    dumps = list(tmp_path.glob("spotter-tpu-traces-*-exit85.json"))
    assert len(dumps) == 1
    payload = json.loads(dumps[0].read_text())
    pinned = [t for t in payload["errors"] if t["request_id"] == "req-fatal"]
    assert len(pinned) == 1
    assert pinned[0]["status"] == "fatal"
    assert "DATA_LOSS" in pinned[0]["error"]
    # the queue-wait span made it in before the device died
    assert any(s["name"] == obs.QUEUE_WAIT for s in pinned[0]["spans"])


def test_preemption_exit_dumps_ring(tmp_path, monkeypatch):
    from spotter_tpu.serving import lifecycle

    monkeypatch.setenv(obs.TRACE_DUMP_DIR_ENV, str(tmp_path))
    tr = obs.begin_trace(request_id="req-preempt")
    obs.get_recorder().record(tr)
    codes: list[int] = []

    async def run():
        watcher = lifecycle.PreemptionWatcher(
            on_preempt=_noop, exit_cb=codes.append, install_sigterm=False,
            poll_s=0.01, file_source=None, url_source=None,
        )
        await watcher.start()
        watcher.trigger("test preemption")
        for _ in range(100):
            if codes:
                break
            await asyncio.sleep(0.01)
        await watcher.stop()

    async def _noop():
        return None

    asyncio.run(run())
    assert codes == [lifecycle.PREEMPTED_EXIT_CODE]
    dumps = list(tmp_path.glob("spotter-tpu-traces-*-exit83.json"))
    assert len(dumps) == 1
    payload = json.loads(dumps[0].read_text())
    assert any(t["request_id"] == "req-preempt" for t in payload["ring"])


# ---------------------------------------------------------------------------
# cross-process: trace propagates router -> replica over real HTTP


@pytest.fixture(scope="module")
def slow_device_replica(tmp_path_factory):
    """One REAL supervised stub replica with 150 ms injected device latency
    (testing/cluster.py), shared by the edge-propagation tests."""
    workdir = str(tmp_path_factory.mktemp("obs-replica"))
    replicas = cluster.start_replicas(
        1, workdir,
        env={"SPOTTER_TPU_FAULTS": f"slow_stage=device:{DEVICE_MS:.0f}"},
    )
    try:
        yield replicas[0]
    finally:
        for r in replicas:
            r.shutdown()


def test_trace_propagates_router_to_replica_over_http(slow_device_replica):
    replica = slow_device_replica

    async def run():
        pool = ReplicaPool([replica.url])
        app = make_router_app(pool)
        async with TestClient(TestServer(app)) as client:
            # warmup: pays TCP connect + client-pool setup once, so the
            # measured request's unattributed network slice stays inside
            # the 5% reconciliation tolerance
            warm = await client.post(
                "/detect", json={"image_urls": ["http://img.example/0.jpg"]}
            )
            assert warm.status == 200
            gaps = []
            t = None
            for attempt in range(3):  # best-of-3, as in the in-process case
                rid = f"req-e2e-{attempt}"
                resp = await client.post(
                    "/detect",
                    json={"image_urls": ["http://img.example/1.jpg"]},
                    headers={obs.REQUEST_ID_HEADER: rid},
                )
                assert resp.status == 200
                assert resp.headers[obs.REQUEST_ID_HEADER] == rid
                # the EDGE recorder holds one trace: route spans + the
                # replica's Server-Timing merged in — every hop in one place
                (t,) = obs.get_recorder().lookup(rid)
                names = {s["name"] for s in t["spans"]}
                assert names >= {
                    obs.ROUTE, obs.FETCH, obs.DECODE, obs.QUEUE_WAIT,
                    obs.H2D, obs.DEVICE, obs.POSTPROCESS,
                }
                assert len(t["spans"]) >= 6
                device_ms = sum(
                    s["duration_ms"] for s in t["spans"]
                    if s["name"] == obs.DEVICE
                )
                assert device_ms >= DEVICE_MS
                span_sum = sum(s["duration_ms"] for s in t["spans"])
                gaps.append(
                    abs(span_sum - t["duration_ms"]) / t["duration_ms"]
                )
                if gaps[-1] < 0.05:
                    break
            assert min(gaps) < 0.05, f"no attempt reconciled: gaps={gaps}"

            # the REPLICA's own recorder has the same request, same trace
            # id, retrievable over HTTP by the client's request id
            reply = httpx.get(
                f"{replica.url}/debug/traces",
                params={"request_id": t["request_id"]},
                timeout=5.0,
            )
            assert reply.status_code == 200
            remote = reply.json()["traces"][0]
            assert remote["trace_id"] == t["trace_id"]
            assert remote["parent_span_id"] is not None

    asyncio.run(run())


def test_trace_through_fleet_edge_and_suspended_echo(slow_device_replica):
    from spotter_tpu.serving.fleet import make_fleet_app, static_fleet

    replica = slow_device_replica

    async def run():
        controller = static_fleet([replica.url], [])
        app = make_fleet_app(controller)
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(
                "/detect",
                json={"image_urls": ["http://img.example/2.jpg"]},
                headers={obs.REQUEST_ID_HEADER: "req-fleet"},
            )
            assert resp.status == 200
            assert resp.headers[obs.REQUEST_ID_HEADER] == "req-fleet"
        traces = obs.get_recorder().lookup("req-fleet")
        assert traces and {s["name"] for s in traces[0]["spans"]} >= {
            obs.ROUTE, obs.DEVICE,
        }

        # a suspended pool's fast 503 still echoes the correlation id
        # (ISSUE 7 satellite: sheds and fast-fails carry X-Request-ID)
        empty = ReplicaPool([], allow_empty=True)
        sapp = make_router_app(empty)
        async with TestClient(TestServer(sapp)) as client:
            resp = await client.post(
                "/detect",
                json={"image_urls": ["http://img.example/3.jpg"]},
                headers={obs.REQUEST_ID_HEADER: "req-suspended"},
            )
            assert resp.status == 503
            assert resp.headers[obs.REQUEST_ID_HEADER] == "req-suspended"
            assert "Retry-After" in resp.headers

    asyncio.run(run())
