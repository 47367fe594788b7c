"""Fleet observability plane (ISSUE 12): mergeable snapshots, cross-replica
aggregation, stitched fleet traces, and the fleet_top rendering.

Merge-math golden tests drive the PURE functions (merge_snapshots,
fleet_burn, fleet_mfu) with real Metrics-produced snapshots; the stateful
FleetAggregator is driven through observe()/mark_down() with no sockets;
the HTTP surfaces run real in-process topologies (stub replicas behind the
real router); and the cross-process stitching case reuses
testing/cluster.py so the replica's flight recorder is genuinely a
different process from the edge's.
"""

import asyncio
import json
import math
import os
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

os.environ["SPOTTER_TPU_TINY"] = "1"

from spotter_tpu import obs
from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.metrics import (
    REPLICA_ID_ENV,
    STAGE_BUCKETS_MS,
    Metrics,
)
from spotter_tpu.obs import http as obs_http
from spotter_tpu.obs import prom
from spotter_tpu.obs.aggregate import (
    FleetAggregator,
    fleet_burn,
    fleet_mfu,
    merge_snapshots,
    quantile_from_hist,
)
from spotter_tpu.serving.detector import AmenitiesDetector
from spotter_tpu.serving.replica_pool import ReplicaPool
from spotter_tpu.serving.router import make_router_app
from spotter_tpu.serving.standalone import make_app
from spotter_tpu.testing import cluster
from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

# the keys the fleet plane ADDED to Metrics.snapshot() — the prom
# byte-stability pin below strips exactly these
MERGE_SUBSTRATE_KEYS = (
    "replica", "stage_ms_histogram", "slo_burn_raw", "perf_raw",
)


@pytest.fixture(autouse=True)
def fresh_recorder(monkeypatch):
    monkeypatch.delenv(obs.TRACE_RING_ENV, raising=False)
    monkeypatch.delenv(obs_http.ADMIN_TOKEN_ENV, raising=False)
    obs.reset_recorder()
    obs.set_current_trace(None)
    yield
    obs.reset_recorder()
    obs.set_current_trace(None)


def assert_nan_free(obj, path="$"):
    """Every float anywhere in the structure is finite — and the whole
    thing survives strict JSON (allow_nan=False), the acceptance bar."""
    if isinstance(obj, float):
        assert math.isfinite(obj), f"non-finite value at {path}"
    elif isinstance(obj, dict):
        for k, v in obj.items():
            assert_nan_free(v, f"{path}.{k}")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            assert_nan_free(v, f"{path}[{i}]")


def _loaded_metrics(batches=3, latency_s=0.05, batch=4, sheds=0) -> Metrics:
    m = Metrics()
    for _ in range(batches):
        m.record_batch(
            batch, latency_s,
            stages={"device": latency_s * 0.8, "decode": latency_s * 0.1},
        )
    if sheds:
        m.record_shed(sheds)
    return m


# ---------------------------------------------------------------------------
# mergeable snapshots (satellites 1 + 2)


def test_snapshot_carries_identity_and_raw_stage_buckets():
    m = _loaded_metrics(batches=2)
    snap = m.snapshot()
    rep = snap["replica"]
    assert rep["pid"] == os.getpid()
    assert rep["replica_id"]
    assert rep["generation"] == 0
    assert rep["uptime_s"] >= 0.0
    assert rep["model"] is None  # stamped by the serving bootstrap
    # raw mergeable stage state alongside the point quantiles
    dev = snap["stage_ms_histogram"]["device"]
    assert dev["count"] == 2
    assert dev["sum"] == pytest.approx(2 * 0.05 * 0.8 * 1e3, rel=1e-6)
    assert len(dev["buckets"]) == len(STAGE_BUCKETS_MS)
    assert dev["buckets"][-1][0] is None  # +Inf bound serialized as null
    assert dev["buckets"][-1][1] == 2  # cumulative
    assert "stage_device_ms_p50" in snap  # point summary unchanged
    # SLO burn + MFU raw state ride the perf block
    assert "buckets" in snap["slo_burn_raw"]
    assert snap["perf_raw"]["window_span_s"] >= 0.0


def test_identity_env_override_and_generation_via_restarts(monkeypatch):
    monkeypatch.setenv(REPLICA_ID_ENV, "pod-7")
    m = Metrics()
    m.set_restarts(3)
    m.set_identity(model="rtdetr_v2_r101vd")
    rep = m.snapshot()["replica"]
    assert rep["replica_id"] == "pod-7"
    assert rep["generation"] == 3  # restart count IS the reset generation
    assert rep["model"] == "rtdetr_v2_r101vd"


def test_prom_exposition_byte_stable_despite_merge_substrate():
    """The raw merge state is JSON-only: the Prometheus rendering of a
    snapshot is byte-identical with and without it (satellite 1's
    'keep the prom summary rendering byte-stable' pin)."""
    m = _loaded_metrics(batches=4, sheds=2)
    snap = m.snapshot()
    for key in MERGE_SUBSTRATE_KEYS:
        assert key in snap, f"snapshot lost merge-substrate key {key}"
    stripped = {k: v for k, v in snap.items() if k not in MERGE_SUBSTRATE_KEYS}
    assert prom.render(snap) == prom.render(stripped)
    # the pre-existing stage summary gauges still render
    assert "spotter_tpu_stage_device_ms_p50" in prom.render(snap)


# ---------------------------------------------------------------------------
# merge math goldens (pure functions)


def test_merged_counters_equal_sum_of_members():
    ms = [
        _loaded_metrics(batches=2, batch=4, sheds=1),
        _loaded_metrics(batches=3, batch=2),
        _loaded_metrics(batches=1, batch=8, sheds=4),
    ]
    snaps = [m.snapshot() for m in ms]
    fleet = merge_snapshots(snaps)
    for key in ("images_total", "batches_total", "shed_total",
                "errors_total", "cache_hits_total"):
        assert fleet[key] == sum(s[key] for s in snaps), key
    hist = fleet["latency_ms_histogram"]
    assert hist["count"] == sum(
        s["latency_ms_histogram"]["count"] for s in snaps
    )
    assert hist["sum"] == pytest.approx(
        sum(s["latency_ms_histogram"]["sum"] for s in snaps)
    )
    # stage raw buckets add too
    assert fleet["stage_ms_histogram"]["device"]["count"] == sum(
        s["stage_ms_histogram"]["device"]["count"] for s in snaps
    )
    assert_nan_free(fleet)


def test_fleet_quantiles_recomputed_from_buckets_not_averaged():
    fast = _loaded_metrics(batches=10, latency_s=0.020)  # le=25 bucket
    slow = _loaded_metrics(batches=10, latency_s=0.200)  # le=250 bucket
    s_fast, s_slow = fast.snapshot(), slow.snapshot()
    fleet = merge_snapshots([s_fast, s_slow])
    # 20 samples, half at 20 ms, half at 200 ms: the merged-histogram p50
    # lands on the 25 ms bucket bound. An averaged-averages "p50" would be
    # (20 + 200) / 2 = 110 ms — pinned wrong here.
    assert fleet["latency_ms_p50"] == 25.0
    naive = (s_fast["latency_ms_p50"] + s_slow["latency_ms_p50"]) / 2
    assert abs(fleet["latency_ms_p50"] - naive) > 50.0
    assert fleet["latency_ms_p99"] == 250.0
    # quantile helper is NaN-free on empty
    assert quantile_from_hist({"buckets": [], "count": 0}, 0.5) == 0.0


def test_fleet_burn_recomputed_from_merged_buckets():
    loud = _loaded_metrics(batches=9, batch=10)  # 90 good
    loud.record_shed(10)  # 10 bad
    quiet = Metrics()  # zero traffic
    fleet = merge_snapshots([loud.snapshot(), quiet.snapshot()])
    # merged: 10 bad / 100 events = 0.1 ratio over a 1% budget -> burn 10.
    # An average of member burns would halve it (quiet member burns 0).
    assert fleet["slo_burn_rate"]["fast"] == pytest.approx(10.0, abs=0.01)
    rates, target = fleet_burn(
        [loud.snapshot()["slo_burn_raw"], quiet.snapshot()["slo_burn_raw"]]
    )
    assert rates["fast"] == pytest.approx(10.0, abs=0.01)
    assert target == 99.0


def test_fleet_mfu_weighted_by_span_times_peak_not_averaged():
    a = {"window_span_s": 60.0, "device_s": 30.0, "flops": 100e12,
         "useful_flops": 50e12, "peak_flops": 200e12}
    b = {"window_span_s": 30.0, "device_s": 15.0, "flops": 30e12,
         "useful_flops": 30e12, "peak_flops": 100e12}
    out = fleet_mfu([a, b])
    # sum(flops) / sum(span x peak) = 130e12 / 1.5e16 = 0.8667%
    assert out["mfu_pct"] == pytest.approx(0.867, abs=1e-3)
    mfu_a = 100 * 100e12 / (60 * 200e12)  # 0.833
    mfu_b = 100 * 30e12 / (30 * 100e12)  # 1.0
    assert abs(out["mfu_pct"] - (mfu_a + mfu_b) / 2) > 0.04
    # members with no peak (stub engines) contribute duty but never MFU
    out2 = fleet_mfu([{"window_span_s": 10.0, "device_s": 5.0,
                       "flops": 0.0, "useful_flops": 0.0,
                       "peak_flops": 0.0}])
    assert out2["mfu_pct"] == 0.0
    assert out2["device_duty_cycle_pct"] == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# aggregator state machine: resets, staleness, NaN-free at 0/1/N


def test_generation_bump_folds_counters_never_negative():
    agg = FleetAggregator(lambda: ["http://a"], interval_s=30.0)
    gen0 = _loaded_metrics(batches=5, batch=4)  # 20 images
    snap0 = gen0.snapshot()
    agg.observe("http://a", snap0)
    assert agg.fleet_snapshot()["images_total"] == 20
    # the replica restarts: generation bumps, counters restart near zero
    gen1 = _loaded_metrics(batches=1, batch=2)  # 2 images
    snap1 = gen1.snapshot()
    snap1["replica"] = dict(snap1["replica"], generation=1)
    agg.observe("http://a", snap1)
    fleet = agg.fleet_snapshot()
    assert fleet["images_total"] == 22  # 20 retained + 2 new, monotone
    assert fleet["replicas"]["generation_resets_total"] == 1
    # next scrape of the SAME generation does not double-fold
    gen1.record_batch(2, 0.01)
    snap1b = gen1.snapshot()
    snap1b["replica"] = dict(snap1b["replica"], generation=1)
    agg.observe("http://a", snap1b)
    assert agg.fleet_snapshot()["images_total"] == 24
    assert agg.fleet_snapshot()["replicas"]["generation_resets_total"] == 1


def test_counter_regression_without_generation_also_folds():
    """Defense in depth: a replica replaced behind the same URL without a
    generation source still must not drag fleet counters backwards."""
    agg = FleetAggregator(lambda: ["http://a"], interval_s=30.0)
    big = _loaded_metrics(batches=10, batch=4).snapshot()  # 40 images
    small = _loaded_metrics(batches=1, batch=1).snapshot()  # 1 image
    # strip the generation signal entirely
    big.pop("replica")
    small.pop("replica")
    agg.observe("http://a", big)
    agg.observe("http://a", small)
    assert agg.fleet_snapshot()["images_total"] == 41


def test_fleet_snapshot_nan_free_at_zero_one_n_members():
    # zero members ever seen
    empty = FleetAggregator(lambda: [], interval_s=30.0).fleet_snapshot()
    assert_nan_free(empty)
    json.dumps(empty, allow_nan=False)
    assert empty["images_per_sec"] == 0
    assert empty["slo_burn_rate"] == {"fast": 0.0, "slow": 0.0}
    assert empty["mfu_pct"] == 0.0
    # one member
    agg1 = FleetAggregator(lambda: ["http://a"], interval_s=30.0)
    agg1.observe("http://a", _loaded_metrics().snapshot())
    assert_nan_free(agg1.fleet_snapshot())
    # N members, one dying mid-scrape
    agg = FleetAggregator(lambda: ["http://a", "http://b", "http://c"],
                          interval_s=30.0)
    for u in ("http://a", "http://b", "http://c"):
        agg.observe(u, _loaded_metrics(batches=2).snapshot())
    before = agg.fleet_snapshot()
    agg.mark_down("http://b", "ConnectError('killed mid-scrape')")
    fleet = agg.fleet_snapshot()
    assert_nan_free(fleet)
    json.dumps(fleet, allow_nan=False)
    assert fleet["replicas"]["up"] == 2
    assert fleet["replicas"]["stale"] == 1
    # counters keep the dead member's history — cumulative facts
    assert fleet["images_total"] == before["images_total"]
    row = next(r for r in fleet["per_replica"] if r["url"] == "http://b")
    assert row["up"] is False and row["stale"] is True
    assert "killed mid-scrape" in row["last_error"]


def test_stale_member_drops_out_of_gauges_keeps_counters():
    agg = FleetAggregator(
        lambda: ["http://a", "http://b"], interval_s=30.0,
        stale_after_s=0.05,
    )
    busy = _loaded_metrics(batches=5, batch=4).snapshot()
    agg.observe("http://a", busy)
    agg.observe("http://b", busy)
    fresh = agg.fleet_snapshot()
    assert fresh["replicas"]["up"] == 2
    assert fresh["images_per_sec"] > 0
    time.sleep(0.08)  # both members go stale (no successful scrape since)
    stale = agg.fleet_snapshot()
    assert stale["replicas"]["up"] == 0
    assert stale["replicas"]["stale"] == 2
    # gauges emptied (a dead fleet is not still "serving" its last rate);
    # counters retained
    assert stale["images_per_sec"] == 0
    assert stale["images_total"] == fresh["images_total"]
    assert all(r["stale"] for r in stale["per_replica"])
    assert_nan_free(stale)


# ---------------------------------------------------------------------------
# HTTP surfaces: fleet /metrics, /debug/fleet, chaos mid-scrape


def _stub_detector(service_ms: float = 0.0) -> AmenitiesDetector:
    engine = StubEngine(service_ms=service_ms)
    return AmenitiesDetector(
        engine, MicroBatcher(engine, max_delay_ms=1.0), StubHttpClient()
    )


async def _stub_fleet(n: int):
    dets, servers, urls = [], [], []
    for _ in range(n):
        det = _stub_detector()
        server = TestServer(make_app(detector=det))
        await server.start_server()
        dets.append(det)
        servers.append(server)
        urls.append(f"http://{server.host}:{server.port}")
    return dets, servers, urls


def test_router_fleet_metrics_merge_and_prom_labels(monkeypatch):
    async def run():
        dets, servers, urls = await _stub_fleet(2)
        pool = ReplicaPool(urls, health_interval_s=0.25)
        # long interval: enabled (fleet block present) but the background
        # task won't race the manual scrape_once calls below
        agg = FleetAggregator(lambda: urls, interval_s=30.0)
        app = make_router_app(pool, aggregator=agg)
        async with TestClient(TestServer(app)) as client:
            for i in range(8):
                resp = await client.post(
                    "/detect",
                    json={"image_urls": [f"http://img/{i % 3}.jpg"]},
                )
                assert resp.status == 200
            await agg.scrape_once()
            snap = json.loads(await (await client.get("/metrics")).read())
            fleet = snap["fleet"]
            member_sum = sum(
                d.engine.metrics.snapshot()["images_total"] for d in dets
            )
            assert fleet["images_total"] == member_sum == 8
            assert fleet["replicas"]["up"] == 2
            assert fleet["brownout_rung"] == 0
            rows = {r["url"]: r for r in fleet["per_replica"]}
            assert set(rows) == set(urls)
            assert all(r["model"] == "stub" for r in rows.values())
            assert_nan_free(fleet)
            # prom exposition: fleet counters + per-replica {url} labels
            text = await (
                await client.get("/metrics?format=prometheus")
            ).text()
            assert "spotter_tpu_fleet_images_total 8" in text
            assert (
                f'spotter_tpu_fleet_per_replica_images_total{{url="{urls[0]}"}}'
                in text
            )
            assert "spotter_tpu_fleet_slo_burn_rate" in text

            # chaos: kill one replica mid-scrape — the fleet surface stays
            # NaN-free and the member is marked down/stale
            await servers[0].close()
            await agg.scrape_once()
            snap2 = json.loads(await (await client.get("/metrics")).read())
            fleet2 = snap2["fleet"]
            assert_nan_free(fleet2)
            json.dumps(fleet2, allow_nan=False)
            assert fleet2["replicas"]["up"] == 1
            assert fleet2["replicas"]["stale"] == 1
            dead = next(
                r for r in fleet2["per_replica"] if r["url"] == urls[0]
            )
            assert dead["up"] is False
            # counter history survives the death
            assert fleet2["images_total"] == member_sum
        for server in servers[1:]:
            await server.close()
        for det in dets:
            await det.aclose()

    asyncio.run(run())


def test_aggregator_switched_off_runs_nothing():
    """Scrape interval 0 is the plane's off switch: the router starts no
    scrape task, no member is ever scraped, `/metrics` carries no `fleet`
    block, and the edge serves as before."""

    async def run():
        dets, servers, urls = await _stub_fleet(2)
        pool = ReplicaPool(urls, health_interval_s=0.25)
        agg = FleetAggregator(lambda: urls, interval_s=0.0)
        app = make_router_app(pool, aggregator=agg)
        async with TestClient(TestServer(app)) as client:
            for i in range(4):
                resp = await client.post(
                    "/detect", json={"image_urls": [f"http://img/{i}.jpg"]}
                )
                assert resp.status == 200
            await asyncio.sleep(0.05)  # a scrape task, had one started, ran
            snap = json.loads(await (await client.get("/metrics")).read())
            assert not agg.enabled and agg._task is None
            assert agg.scrapes_total == 0 and agg.scrape_errors_total == 0
            assert "fleet" not in snap
            text = await (
                await client.get("/metrics?format=prometheus")
            ).text()
            assert "spotter_tpu_fleet_" not in text
        served = sum(d.engine.metrics.snapshot()["images_total"] for d in dets)
        for server in servers:
            await server.close()
        for det in dets:
            await det.aclose()
        return served

    assert asyncio.run(run()) == 4


def test_debug_fleet_admin_gated(monkeypatch):
    async def run():
        dets, servers, urls = await _stub_fleet(1)
        pool = ReplicaPool(urls, health_interval_s=0.25)
        agg = FleetAggregator(lambda: urls, interval_s=30.0)
        app = make_router_app(pool, aggregator=agg)
        async with TestClient(TestServer(app)) as client:
            await agg.scrape_once()
            monkeypatch.setenv(obs_http.ADMIN_TOKEN_ENV, "sekrit")
            resp = await client.get("/debug/fleet")
            assert resp.status == 401
            resp = await client.get(
                "/debug/fleet", headers={"X-Admin-Token": "sekrit"}
            )
            assert resp.status == 200
            body = json.loads(await resp.read())
            assert body["replicas"]["up"] == 1
            row = body["per_replica"][0]
            for key in ("url", "images_per_sec", "latency_ms_p99",
                        "slo_burn_fast", "mfu_pct", "hbm_bytes_in_use",
                        "brownout_rung", "cache_hit_rate", "generation"):
                assert key in row, key
        for server in servers:
            await server.close()
        for det in dets:
            await det.aclose()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# cross-replica trace stitching (the replica is a REAL subprocess, so its
# flight recorder is genuinely not the edge's)


@pytest.fixture(scope="module")
def slow_replica(tmp_path_factory):
    workdir = str(tmp_path_factory.mktemp("fleet-obs-replica"))
    replicas = cluster.start_replicas(
        1, workdir,
        env={"SPOTTER_TPU_FAULTS": "slow_stage=device:120"},
    )
    try:
        yield replicas[0]
    finally:
        for r in replicas:
            r.shutdown()


def test_fleet_trace_stitching_end_to_end(slow_replica):
    replica = slow_replica

    async def run():
        pool = ReplicaPool([replica.url])
        agg = FleetAggregator(lambda: [replica.url], interval_s=30.0)
        app = make_router_app(pool, aggregator=agg)
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(
                "/detect",
                json={"image_urls": ["http://img/slow.jpg"]},
                headers={"X-Request-ID": "fleet-stitch-1"},
            )
            assert resp.status == 200
            # the slowest-K list view stitches the injected-slow request
            resp = await client.get("/debug/traces?fleet=1")
            assert resp.status == 200
            payload = json.loads(await resp.read())
            assert payload["fleet"] is True
            assert payload["stitched"], "no stitched trees"
            tree = payload["stitched"][0]
            edge_spans = {s["name"] for s in tree["edge"]["spans"]}
            assert obs.ROUTE in edge_spans
            assert tree["replicas"], "no replica joined the edge trace"
            joined = tree["replicas"][0]
            assert joined["url"] == replica.url
            rep_trace = joined["traces"][0]
            assert rep_trace["trace_id"] == tree["edge"]["trace_id"]
            device = [
                s for s in rep_trace["spans"] if s["name"] == obs.DEVICE
            ]
            assert device and device[0]["duration_ms"] >= 100.0
            # by-id lookup returns the same single tree; a bogus id is 404
            tid = tree["edge"]["trace_id"]
            resp = await client.get(f"/debug/traces?fleet=1&trace_id={tid}")
            assert resp.status == 200
            one = json.loads(await resp.read())
            assert len(one["stitched"]) >= 1
            resp = await client.get("/debug/traces?fleet=1&trace_id=" + "0" * 32)
            assert resp.status == 404
        await agg.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# fleet_top rendering (pure)


def test_fleet_top_render():
    from tools.fleet_top import render

    snapshot = {
        "fleet": {
            "replicas": {"seen": 2, "up": 1, "stale": 1,
                         "generation_resets_total": 3},
            "images_per_sec": 123.4,
            "latency_ms_p99": 87.5,
            "slo_burn_rate": {"fast": 1.25, "slow": 0.5},
            "mfu_pct": 42.0,
            "brownout_rung": 2,
            "per_replica": [
                {"url": "http://r1:8000", "up": True, "stale": False,
                 "generation": 1, "model": "rtdetr_v2_r101vd",
                 "images_per_sec": 100.0, "latency_ms_p50": 20.0,
                 "latency_ms_p99": 55.0, "slo_burn_fast": 0.9,
                 "mfu_pct": 44.0, "device_duty_cycle_pct": 70.0,
                 "cache_hit_rate": 0.82, "brownout_rung": 0},
                {"url": "http://r2:8000", "up": False, "stale": True,
                 "generation": 2, "model": None,
                 "images_per_sec": 0.0, "latency_ms_p50": 0.0,
                 "latency_ms_p99": 0.0, "slo_burn_fast": 0.0,
                 "mfu_pct": 0.0, "device_duty_cycle_pct": 0.0,
                 "cache_hit_rate": 0.0, "brownout_rung": 0},
            ],
        }
    }
    out = render(snapshot)
    lines = out.splitlines()
    assert "1/2 up" in lines[0] and "burn 1.25/0.50" in lines[0]
    assert "REPLICA" in lines[2] and "RUNG" in lines[2]
    r1 = next(ln for ln in lines if "http://r1:8000" in ln)
    assert "ready" in r1 and "rtdetr_v2_r101" in r1 and "82" in r1
    r2 = next(ln for ln in lines if "http://r2:8000" in ln)
    assert "down" in r2
    # an edge without the aggregator armed is reported, not rendered empty
    assert "aggregator" in render({"pool_requests_total": 0})


def test_fleet_top_render_autoscale_pools():
    from tools.fleet_top import _autoscale_lines, render

    snapshot = {
        "fleet": {"replicas": {"seen": 0, "up": 0, "stale": 0,
                               "generation_resets_total": 0},
                  "per_replica": []},
        "autoscale": {
            "decisions_total": 7,
            "scale_ups_total": 2,
            "scale_downs_total": 1,
            "wakes_total": 1,
            "flood_suppressions_total": 3,
            "routing_rejections_total": 4,
            "default_pool": "rtdetr",
            "pools": {
                "rtdetr": {
                    "model": "rtdetr", "open_vocab": False,
                    "tp": 1, "dp": 2, "desired": 2, "ready": 2,
                    "scaled_to_zero": False, "restoring": False,
                    "time_to_ready_s": 0.42, "admits_total": 19,
                    "inflight": 1,
                    "last_decision": {"current": 1, "desired": 2,
                                      "reason": "up: queue 5.0",
                                      "age_s": 12.3},
                },
                "owlvit": {
                    "model": "owlvit", "open_vocab": True,
                    "tp": 2, "dp": 1, "desired": 0, "ready": 0,
                    "scaled_to_zero": True, "restoring": False,
                    "time_to_ready_s": None, "admits_total": 0,
                    "inflight": 0, "last_decision": None,
                },
                "yolos": {
                    "model": "yolos", "open_vocab": False,
                    "tp": 1, "dp": 1, "desired": 1, "ready": 0,
                    "scaled_to_zero": False, "restoring": True,
                    "time_to_ready_s": None, "admits_total": 3,
                    "inflight": 0, "last_decision": None,
                },
            },
        },
    }
    out = render(snapshot)
    lines = out.splitlines()
    totals = next(ln for ln in lines if ln.startswith("autoscale:"))
    assert "7 decisions (2 up, 1 down, 1 wakes)" in totals
    assert "flood holds 3" in totals and "routing 400s 4" in totals
    assert "default rtdetr" in totals
    header = next(ln for ln in lines if "LAST DECISION" in ln)
    assert "POOL" in header and "DES" in header and "TTR_S" in header
    rt = next(ln for ln in lines if ln.startswith("rtdetr"))
    assert "tp1xdp2" in rt and "0.42" in rt
    assert "1->2 up: queue 5.0 (12s ago)" in rt
    owl = next(ln for ln in lines if ln.startswith("owlvit"))
    assert "owlvit*" in owl and "zero" in owl and "tp2xdp1" in owl
    yo = next(ln for ln in lines if ln.startswith("yolos"))
    assert "restoring" in yo
    # pool rows sort by name regardless of dict order
    assert lines.index(owl) < lines.index(rt) < lines.index(yo)
    # absent-plane discipline: no autoscale block, no autoscale lines
    assert _autoscale_lines({"fleet": {}}) == []
    assert "autoscale:" not in render({"fleet": snapshot["fleet"]})
