"""Fleet-tier tests (ISSUE 6): request classing + SLO pinning, preemption
storms draining only the marked member, jittered respawn of dead members,
scale-to-zero + demand restore, the preempt_storm fault hook, and the fleet
HTTP surface. Most cases drive in-process scripted members (aiohttp
TestServer + a fake handle); the cross-process preemption-file propagation
test runs REAL supervised stub replicas via testing/cluster.py."""

import asyncio
import random
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestServer

from spotter_tpu.serving.fleet import (
    BULK,
    SLO,
    FleetController,
    PoolSpec,
    classify_request,
    make_fleet_app,
)
from spotter_tpu.testing import faults

PAYLOAD = {"image_urls": ["http://example.com/room.jpg"]}

FAST_POOL_KWARGS = dict(
    eject_threshold=1,
    backoff_base_s=0.1,
    backoff_max_s=0.5,
    health_interval_s=0.05,
)


class FakeMember:
    """In-process scripted replica + fleet member handle: /detect and
    /healthz with mutable behavior, plus the sync handle surface
    (alive/preempt/clear_preemption/shutdown) the controller drives."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.status = 200
        self.health_status = 200
        self.detect_calls = 0
        self._alive = True
        self.preempted = False
        self.clears = 0
        self.shutdowns = 0
        self.on_shutdown = None
        app = web.Application()
        app.router.add_post("/detect", self._detect)
        app.router.add_get("/healthz", self._healthz)
        self.server = TestServer(app)
        self.url = ""

    async def _detect(self, request: web.Request) -> web.Response:
        self.detect_calls += 1
        return web.json_response({"served_by": self.name}, status=self.status)

    async def _healthz(self, request: web.Request) -> web.Response:
        return web.json_response({}, status=self.health_status)

    async def start(self) -> str:
        await self.server.start_server()
        self.url = f"http://{self.server.host}:{self.server.port}"
        return self.url

    async def close(self) -> None:
        await self.server.close()

    # ---- MemberHandle surface ----

    def alive(self) -> bool:
        return self._alive

    def preempt(self) -> None:
        """Drain-like: readiness flips and /detect sheds, the shape a
        maintenance notice produces on a real replica."""
        self.preempted = True
        self.status = 503
        self.health_status = 503

    def revive(self) -> None:
        self.preempted = False
        self._alive = True
        self.status = 200
        self.health_status = 200

    def clear_preemption(self) -> None:
        self.clears += 1

    def shutdown(self, timeout_s: float = 10.0) -> str:
        self.shutdowns += 1
        self._alive = False
        self.status = 503
        self.health_status = 503
        if self.on_shutdown is not None:
            self.on_shutdown()
        return ""


async def _members(*names: str) -> list[FakeMember]:
    ms = [FakeMember(n) for n in names]
    for m in ms:
        await m.start()
    return ms


async def _wait(predicate, timeout_s: float = 5.0, interval_s: float = 0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(interval_s)
    raise TimeoutError("condition not met in time")


async def _start_fleet(od: list, spot: list, **kw) -> FleetController:
    specs = [
        PoolSpec("on_demand", handles=od),
        PoolSpec("spot", handles=spot),
    ]
    defaults = dict(tick_s=0.02, pool_kwargs=dict(FAST_POOL_KWARGS))
    defaults.update(kw)
    ctrl = FleetController(specs, **defaults)
    await ctrl.start()
    await _wait(lambda: all(
        fp.pool.has_available() for fp in ctrl.pools.values() if fp.members
    ))
    return ctrl


def test_classify_request_precedence_and_stripping():
    # header wins
    cls, payload = classify_request(
        {"X-Request-Class": "bulk"}, {"image_urls": [], "request_class": "slo"}
    )
    assert cls == "bulk"
    # routing metadata never reaches the detector
    assert "request_class" not in payload
    # payload key next
    assert classify_request(None, {"request_class": "bulk"})[0] == "bulk"
    # a deadline tag means latency-critical
    assert classify_request(None, {"deadline_ms": 50})[0] == "slo"
    # unclassified defaults conservative (slo)
    assert classify_request(None, {"image_urls": []})[0] == "slo"
    # explicit default honored
    assert classify_request(None, {}, default="bulk")[0] == "bulk"
    # garbage falls back to the default
    assert classify_request({"X-Request-Class": "weird"}, {})[0] == "slo"


def test_slo_pins_on_demand_bulk_drains_spot():
    async def run():
        od, s0, s1 = await _members("od0", "s0", "s1")
        ctrl = await _start_fleet([od], [s0, s1])
        for _ in range(6):
            assert (await ctrl.detect(PAYLOAD, SLO))["served_by"] == "od0"
        bulk_served = {
            (await ctrl.detect(PAYLOAD, BULK))["served_by"] for _ in range(6)
        }
        assert bulk_served <= {"s0", "s1"}
        assert od.detect_calls == 6  # bulk never touched the SLO pool
        snap = ctrl.snapshot()
        assert snap["requests_total"] == {SLO: 6, BULK: 6}
        assert snap["failures_total"] == {SLO: 0, BULK: 0}
        await ctrl.stop(shutdown_members=False)
        for m in (od, s0, s1):
            await m.close()

    asyncio.run(run())


def test_storm_drains_only_marked_member_slo_untouched():
    """The storm fault hook preempts ONE spot member; the other spot member
    keeps serving bulk throughout, and SLO traffic neither fails nor ever
    touches the spot pool."""

    async def run():
        od, s0, s1 = await _members("od0", "s0", "s1")
        ctrl = await _start_fleet([od], [s0, s1])
        with faults.inject(preempt_storm=1) as plan:
            await _wait(lambda: plan.preempt_storm == 0)
        victims = [m for m in (s0, s1) if m.preempted]
        assert len(victims) == 1, "storm must mark exactly one member"
        victim = victims[0]
        survivor = s1 if victim is s0 else s0
        # the controller observes the drain, counts the preemption, and
        # clears the maintenance source exactly once
        await _wait(lambda: ctrl.snapshot()["preemptions_total"] >= 1)
        await _wait(lambda: victim.clears == 1)
        # mid-storm: bulk lands on the survivor (replay is invisible), SLO
        # stays pinned and clean
        for _ in range(4):
            assert (await ctrl.detect(PAYLOAD, BULK))["served_by"] == survivor.name
        for _ in range(4):
            assert (await ctrl.detect(PAYLOAD, SLO))["served_by"] == "od0"
        snap = ctrl.snapshot()
        assert snap["failures_total"] == {SLO: 0, BULK: 0}
        assert snap["pool_size"]["spot"]["ready"] >= 1
        assert snap["storms_total"] == 1
        # recovery (the supervisor's job on a real member): spot refills
        victim.revive()
        await _wait(lambda: ctrl.snapshot()["pool_size"]["spot"]["ready"] == 2)
        await ctrl.stop(shutdown_members=False)
        for m in (od, s0, s1):
            await m.close()

    asyncio.run(run())


def test_dead_member_respawned_with_jittered_backoff():
    """A member whose SUPERVISOR process dies (not a preemption — the
    supervisor would absorb that) is retired and replaced by the spawner
    after a jittered backoff."""

    async def run():
        (m0,) = await _members("gen0")
        replacement = FakeMember("gen1")
        await replacement.start()
        stock = [replacement]

        def spawner():
            m = stock.pop(0)
            m.revive()
            return m

        specs = [
            PoolSpec("spot", handles=[m0], spawner=spawner, target_size=1),
        ]
        ctrl = FleetController(
            specs,
            tick_s=0.02,
            respawn_base_s=0.05,
            rng=random.Random(7),
            pool_kwargs=dict(FAST_POOL_KWARGS),
        )
        await ctrl.start()
        await _wait(lambda: ctrl.pools["spot"].pool.has_available())
        m0._alive = False  # the supervisor process is gone
        await _wait(lambda: ctrl.snapshot()["pools"]["spot"]["respawns_total"] == 1)
        await _wait(lambda: ctrl.pools["spot"].pool.has_available())
        assert (await ctrl.detect(PAYLOAD, BULK))["served_by"] == "gen1"
        assert not stock  # the spawner was actually used
        await ctrl.stop(shutdown_members=False)
        for m in (m0, replacement):
            await m.close()

    asyncio.run(run())


def test_scale_to_zero_and_demand_restore():
    async def run():
        (m0,) = await _members("z0")
        m0._alive = False  # not managed yet; spawner revives it
        stock = [m0]
        m0.on_shutdown = lambda: stock.append(m0)

        def spawner():
            m = stock.pop(0)
            m.revive()
            return m

        specs = [
            PoolSpec("spot", spawner=spawner, target_size=1,
                     scale_to_zero_s=0.25),
        ]
        ctrl = FleetController(
            specs,
            tick_s=0.02,
            restore_wait_s=5.0,
            pool_kwargs=dict(FAST_POOL_KWARGS),
        )
        await ctrl.start()
        assert (await ctrl.detect(PAYLOAD, BULK))["served_by"] == "z0"
        first_ttr = ctrl.pools["spot"].time_to_ready_s
        assert first_ttr is not None and first_ttr > 0
        # idle past the threshold: the pool drains to zero members
        await _wait(lambda: ctrl.snapshot()["pools"]["spot"]["scaled_to_zero"])
        # the flag is set before the members' shutdowns, which run in an
        # executor: wait for the one this asserts on
        await _wait(lambda: m0.shutdowns >= 1)
        assert m0.shutdowns == 1
        snap = ctrl.snapshot()
        assert snap["pools"]["spot"]["size"] == 0
        assert snap["pools"]["spot"]["scale_to_zero_total"] == 1
        # demand restore: the next bulk request wakes the pool and waits
        assert (await ctrl.detect(PAYLOAD, BULK))["served_by"] == "z0"
        # the restore is booked by the tick that finds the pool available; the
        # reply can come back before that tick (it did, on a loaded host)
        await _wait(lambda: ctrl.snapshot()["pools"]["spot"]["restores_total"] >= 1)
        snap = ctrl.snapshot()
        assert snap["pools"]["spot"]["restores_total"] == 1
        assert not snap["pools"]["spot"]["scaled_to_zero"]
        assert snap["time_to_ready_s"]["spot"] > 0
        await ctrl.stop(shutdown_members=False)
        await m0.close()

    asyncio.run(run())


def test_take_preempt_storm_consumes_whole_value():
    assert faults.take_preempt_storm() == 0  # no plan active
    with faults.inject(preempt_storm=2):
        assert faults.take_preempt_storm() == 2  # one correlated event
        assert faults.take_preempt_storm() == 0
    assert faults.take_preempt_storm() == 0


def test_fleet_app_routes_and_pool_gauges():
    from aiohttp.test_utils import TestClient

    async def run():
        od, s0 = await _members("od0", "s0")
        specs = [
            PoolSpec("on_demand", endpoints=[od.url]),
            PoolSpec("spot", endpoints=[s0.url]),
        ]
        ctrl = FleetController(
            specs, tick_s=0.02, pool_kwargs=dict(FAST_POOL_KWARGS)
        )
        app = make_fleet_app(ctrl)
        async with TestClient(TestServer(app)) as client:
            # header-classed bulk rides spot
            resp = await client.post(
                "/detect", json=PAYLOAD, headers={"X-Request-Class": "bulk"}
            )
            assert resp.status == 200
            assert (await resp.json())["served_by"] == "s0"
            # payload-classed slo pins on demand (and the key is stripped)
            resp = await client.post(
                "/detect", json={**PAYLOAD, "request_class": "slo"}
            )
            assert resp.status == 200
            assert (await resp.json())["served_by"] == "od0"

            health = await client.get("/healthz")
            assert health.status == 200
            body = await health.json()
            assert body["pools_available"] == {"on_demand": True, "spot": True}

            assert (await client.get("/livez")).status == 200

            metrics = await (await client.get("/metrics")).json()
            for key in (
                "pool_size",
                "preemptions_total",
                "replays_total",
                "retry_budget_exhausted_total",
                "requests_total",
                "time_to_ready_s",
            ):
                assert key in metrics
            assert set(metrics["pool_size"]) == {"on_demand", "spot"}
            assert set(metrics["pool_size"]["spot"]) == {
                "ready", "starting", "down", "dead",
            }
            assert metrics["requests_total"] == {"slo": 1, "bulk": 1}

            bad = await client.post("/detect", data=b"{nope")
            assert bad.status == 400
        for m in (od, s0):
            await m.close()

    asyncio.run(run())


def test_fleet_suspended_pool_answers_503_with_retry_after():
    """An SLO request against a fleet whose on_demand pool is entirely down
    must answer 503 + Retry-After fast — not burn the request deadline."""
    from aiohttp.test_utils import TestClient

    async def run():
        specs = [
            # an endpoint that exists but is health-marked down immediately
            PoolSpec("on_demand", endpoints=["http://127.0.0.1:1"]),
        ]
        ctrl = FleetController(
            specs,
            tick_s=0.02,
            unavailable_wait_s=0.2,
            pool_kwargs=dict(FAST_POOL_KWARGS),
        )
        app = make_fleet_app(ctrl)
        async with TestClient(TestServer(app)) as client:
            # let the health loop mark the dead endpoint down, then the
            # request path must fail fast (suspended), not ride the rounds
            fp = ctrl.pools["on_demand"]
            await _wait(lambda: not fp.pool.replicas[0].healthy, timeout_s=3.0)
            t0 = time.perf_counter()
            resp = await client.post("/detect", json=PAYLOAD)
            elapsed = time.perf_counter() - t0
            assert resp.status == 503
            assert "Retry-After" in resp.headers
            assert int(resp.headers["Retry-After"]) >= 1
            assert elapsed < 1.0

    asyncio.run(run())


# ---- cross-process: the PR 2 maintenance-file machinery through the fleet
# controller (ISSUE 6 satellite) ----


def test_preemption_file_drains_only_marked_member_cross_process(tmp_path):
    """REAL supervised stub replicas: a preemption storm (maintenance file
    via the storm hook) on one spot member drains ONLY that member — the
    other spot member serves bulk throughout, SLO traffic never fails and
    never touches spot, and the supervisor brings the victim back to ready
    so the spot pool refills on its own."""
    from spotter_tpu.testing import cluster

    async def run():
        ctrl = FleetController(
            [
                PoolSpec(
                    "on_demand",
                    spawner=cluster.fleet_spawner(str(tmp_path), "on_demand"),
                    target_size=1,
                ),
                PoolSpec(
                    "spot",
                    spawner=cluster.fleet_spawner(str(tmp_path), "spot"),
                    target_size=2,
                ),
            ],
            tick_s=0.05,
            pool_kwargs=dict(
                eject_threshold=1,
                backoff_base_s=0.2,
                health_interval_s=0.1,
                request_timeout_s=10.0,
            ),
        )
        await ctrl.start()
        await _wait(
            lambda: (
                ctrl.snapshot()["pool_size"]["on_demand"]["ready"] >= 1
                and ctrl.snapshot()["pool_size"]["spot"]["ready"] >= 2
            ),
            timeout_s=90.0,
            interval_s=0.2,
        )

        failures = {SLO: 0, BULK: 0}
        spot_always_had_capacity = {"ok": True}
        done = {"n": 0}

        async def one(cls):
            try:
                await ctrl.detect(PAYLOAD, cls)
            except Exception:
                failures[cls] += 1
            done["n"] += 1

        async def load():
            for _ in range(20):
                await asyncio.gather(one(SLO), one(BULK))

        async def storm():
            # land the storm mid-load
            while done["n"] < 8:
                await asyncio.sleep(0.02)
            with faults.inject(preempt_storm=1) as plan:
                while plan.preempt_storm > 0:
                    await asyncio.sleep(0.02)

        async def watch_spot():
            while done["n"] < 40:
                snap = ctrl.snapshot()
                if snap["pool_size"]["spot"]["ready"] < 1:
                    spot_always_had_capacity["ok"] = False
                await asyncio.sleep(0.05)

        await asyncio.gather(load(), storm(), watch_spot())

        # zero client-visible failures in EITHER class
        assert failures == {SLO: 0, BULK: 0}
        # only the marked member drained: bulk capacity never hit zero
        assert spot_always_had_capacity["ok"]
        snap = ctrl.snapshot()
        assert snap["preemptions_total"] >= 1
        assert snap["requests_total"] == {SLO: 20, BULK: 20}
        # the on_demand member served exactly the 20 SLO requests: no bulk
        # leaked onto it, and no SLO request ever needed a replay
        od_replicas = snap["pools"]["on_demand"]["pool"]["replicas"]
        assert sum(r["requests"] for r in od_replicas) == 20
        spot_requests = sum(
            r["requests"] for r in snap["pools"]["spot"]["pool"]["replicas"]
        )
        assert spot_requests >= 20  # all bulk + its replays stayed on spot
        # the supervisor restarts the drained member: spot refills to 2
        await _wait(
            lambda: ctrl.snapshot()["pool_size"]["spot"]["ready"] >= 2,
            timeout_s=60.0,
            interval_s=0.2,
        )
        await ctrl.stop()

    asyncio.run(run())
