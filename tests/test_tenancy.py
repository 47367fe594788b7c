"""Multi-tenant isolation plane tests (ISSUE 19): token-bucket quota
properties, identity precedence, inflight caps, occupancy scoping,
deficit-weighted round-robin fairness (and its FIFO bit-identity opt-out),
the bounded /metrics tenant view, the noisy-neighbor chaos matrix, and the
table-driven 429/503 shed contract over the real HTTP surfaces."""

import asyncio
import json
import os
import random

import pytest
from aiohttp.test_utils import TestClient, TestServer

os.environ.setdefault("SPOTTER_TPU_TINY", "1")

from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.scheduler import QueueItem, Scheduler
from spotter_tpu.serving import tenancy
from spotter_tpu.serving.detector import AmenitiesDetector
from spotter_tpu.serving.standalone import make_app
from spotter_tpu.serving.tenancy import (
    ANON,
    TENANT_CONFIG_ENV,
    TENANT_HEADER,
    TENANT_KEYS_ENV,
    TENANT_RPS_DEFAULT_ENV,
    TenantPlane,
    TenantQuotaError,
    TokenBucket,
)
from spotter_tpu.testing.chaos_matrix import (
    TENANT_MATRIX,
    run_tenant_scenario,
)
from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient


class FakeClock:
    def __init__(self, now: float = 1000.0) -> None:
        self.now = now

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> None:
        self.now += dt


def _plane(config=None, **kw) -> TenantPlane:
    kw.setdefault("rng", random.Random(0))
    return TenantPlane(config=config, **kw)


# ------------------------------------------------- token bucket properties


def test_bucket_never_exceeds_burst():
    """Property: whatever the take/advance schedule, the token count never
    exceeds the burst capacity and never goes negative."""
    clock = FakeClock()
    bucket = TokenBucket(rate=10.0, burst=20.0, clock=clock)
    rng = random.Random(42)
    for _ in range(500):
        if rng.random() < 0.5:
            clock.advance(rng.uniform(0.0, 5.0))
        granted = bucket.try_take()
        assert 0.0 <= bucket.tokens <= bucket.burst
        if granted:
            assert bucket.tokens <= bucket.burst - 0.0
    # a long idle period refills to exactly burst, not beyond
    clock.advance(1e6)
    assert not bucket.try_take(bucket.burst + 1)
    assert bucket.try_take(bucket.burst)


def test_bucket_refill_is_monotone():
    """Property: with no takes, available tokens never decrease as time
    advances (in arbitrary increments)."""
    clock = FakeClock()
    bucket = TokenBucket(rate=3.0, burst=30.0, clock=clock)
    assert bucket.try_take(30.0)  # drain to zero
    rng = random.Random(7)
    last = 0.0
    for _ in range(200):
        clock.advance(rng.uniform(0.0, 1.0))
        bucket._refill(clock.now)
        assert bucket.tokens >= last - 1e-9
        last = bucket.tokens


def test_bucket_exact_quota_pacing_never_starves():
    """Arrival at exactly the sustained rate is admitted forever — the
    quota boundary belongs to the tenant, not the shedder."""
    clock = FakeClock()
    bucket = TokenBucket(rate=10.0, burst=20.0, clock=clock)
    assert bucket.try_take(20.0)  # start from an empty bucket: worst case
    for _ in range(1000):
        clock.advance(0.1)  # exactly 1 token per arrival at rate 10
        assert bucket.try_take()


def test_bucket_retry_after_tracks_deficit():
    clock = FakeClock()
    bucket = TokenBucket(rate=2.0, burst=2.0, clock=clock)
    assert bucket.try_take(2.0)
    assert not bucket.try_take()
    # 1 token at 2/s = 0.5 s away
    assert bucket.retry_after_s() == pytest.approx(0.5)
    clock.advance(0.25)
    assert bucket.retry_after_s() == pytest.approx(0.25)


# --------------------------------------------------- identity + admission


def test_identity_distrusts_bare_header():
    """REVIEW: the tenant header is client-controlled — bare, it must
    NOT be honored. Spoofers collapse to the key-resolved tenant or to
    the one shared anon bucket (id rotation gains nothing)."""
    plane = _plane(key_map={"sekrit": "acme"})
    # bare header: spoofable -> anon, and the reject is counted
    assert plane.resolve({TENANT_HEADER: "victim"}) == ANON
    assert plane.header_rejects_total == 1
    # valid key + mismatched header: the AUTHENTICATED identity wins
    assert plane.resolve(
        {TENANT_HEADER: "victim", "X-API-Key": "sekrit"}
    ) == "acme"
    # header matching the key-resolved tenant is honored
    assert plane.resolve(
        {TENANT_HEADER: "acme", "X-API-Key": "sekrit"}
    ) == "acme"
    assert plane.resolve({"X-API-Key": "sekrit"}) == "acme"
    assert plane.resolve({"X-API-Key": "unknown"}) == ANON
    assert plane.resolve({}) == ANON
    assert plane.resolve(None) == ANON
    snap = plane.snapshot()
    assert snap["header_rejects_total"] == 2  # victim x2 above
    assert snap["trust_header"] is False
    assert snap["edge_attested"] is False


def test_identity_edge_attestation_and_trust_opt_in():
    plane = _plane(edge_secret="shh")
    # the matching edge token attests the header (the edge->replica hop)
    headers: dict = {}
    plane.stamp(headers, "acme")
    assert headers[TENANT_HEADER] == "acme"
    assert headers[tenancy.EDGE_TOKEN_HEADER] == "shh"
    assert plane.resolve(headers) == "acme"
    # a wrong/missing token does not
    assert plane.resolve(
        {TENANT_HEADER: "acme", tenancy.EDGE_TOKEN_HEADER: "guess"}
    ) == ANON
    assert plane.resolve({TENANT_HEADER: "acme"}) == ANON
    # explicit deployment opt-in (attested upstream: mTLS/mesh) trusts bare
    trusting = _plane(trust_header=True)
    assert trusting.resolve({TENANT_HEADER: "acme"}) == "acme"
    assert trusting.header_rejects_total == 0
    # stamp without a secret forwards the id alone
    bare: dict = {}
    trusting.stamp(bare, "acme")
    assert bare == {TENANT_HEADER: "acme"}


def test_from_env_edge_secret_and_trust(monkeypatch, tmp_path):
    monkeypatch.setenv(TENANT_RPS_DEFAULT_ENV, "10")
    secret_file = tmp_path / "edge.secret"
    secret_file.write_text("filesecret\n")
    monkeypatch.setenv(tenancy.TENANT_EDGE_SECRET_ENV, str(secret_file))
    plane = tenancy.from_env()
    assert plane is not None and plane._edge_secret == "filesecret"
    assert plane.trust_header is False
    # a non-path value is the literal secret (test/drill ergonomics)
    monkeypatch.setenv(tenancy.TENANT_EDGE_SECRET_ENV, "inline-secret")
    monkeypatch.setenv(tenancy.TENANT_TRUST_HEADER_ENV, "1")
    plane = tenancy.from_env()
    assert plane._edge_secret == "inline-secret"
    assert plane.trust_header is True


def test_rate_quota_sheds_with_retry_after():
    clock = FakeClock()
    plane = _plane(
        config={"abuser": {"rps": 1.0, "burst": 2.0}}, clock=clock
    )
    plane.try_admit("abuser").release()
    plane.try_admit("abuser").release()
    with pytest.raises(TenantQuotaError) as exc_info:
        plane.try_admit("abuser")
    exc = exc_info.value
    assert exc.status == 429
    assert exc.kind == tenancy.SHED_RATE
    assert exc.tenant == "abuser"
    assert exc.retry_after_s >= 0.05
    snap = plane.snapshot()
    assert snap["tenants"]["abuser"]["sheds_rate_total"] == 1
    assert snap["sheds_total"]["rate"] == 1
    assert plane.admits_total == 2
    # refill un-sheds: the bucket, not a ban list
    clock.advance(1.0)
    plane.try_admit("abuser").release()


def test_inflight_cap_sheds_and_release_frees():
    plane = _plane(config={"loris": {"rps": 1000.0, "max_inflight": 2}})
    a = plane.try_admit("loris")
    b = plane.try_admit("loris")
    with pytest.raises(TenantQuotaError) as exc_info:
        plane.try_admit("loris")
    assert exc_info.value.kind == tenancy.SHED_INFLIGHT
    assert plane.snapshot()["tenants"]["loris"]["sheds_inflight_total"] == 1
    a.release()
    c = plane.try_admit("loris")  # a freed seat admits again
    # double-release is a no-op, not a double-free
    a.release()
    assert plane.inflight("loris") == 2
    b.release()
    c.release()
    assert plane.inflight("loris") == 0


def test_release_neutral_keeps_burn_untouched():
    """REVIEW leak guard: the abandoned-request release (good=None) frees
    the slot without recording an outcome — a disconnect flood must not
    poison (or credit) a tenant's SLO burn."""
    plane = _plane(config={"t": {"rps": 1000.0}})
    adm = plane.try_admit("t")
    adm.release(good=None)
    assert plane.inflight("t") == 0
    assert plane.snapshot()["tenants"]["t"]["slo_burn"] == 0.0
    # still exactly-once: a later release with an outcome is a no-op
    adm.release(good=False)
    assert plane.snapshot()["tenants"]["t"]["slo_burn"] == 0.0
    # contrast: a real bad outcome does burn
    plane.try_admit("t").release(good=False)
    assert plane.snapshot()["tenants"]["t"]["slo_burn"] > 0.0


def test_stale_inflight_tenants_are_evictable():
    """REVIEW backstop: leaked inflight slots (nothing live looks 10
    minutes old) must not make their tenants immortal, or a disconnecting
    tenant-id flood defeats the MAX_TRACKED_TENANTS memory bound."""
    clock = FakeClock()
    plane = _plane(clock=clock)
    held = [
        plane.try_admit(f"leak-{i:04d}")
        for i in range(tenancy.MAX_TRACKED_TENANTS)
    ]
    assert len(plane._tenants) == tenancy.MAX_TRACKED_TENANTS
    # every slot occupied and fresh: nothing evictable, the map holds
    plane.try_admit("fresh-a").release()
    assert len(plane._tenants) == tenancy.MAX_TRACKED_TENANTS
    assert "fresh-a" not in plane._tenants
    # past the stale horizon the leaked slots become reclaimable
    clock.advance(tenancy.INFLIGHT_STALE_S + 1.0)
    plane.try_admit("fresh-b").release()
    assert "fresh-b" in plane._tenants
    assert len(plane._tenants) <= tenancy.MAX_TRACKED_TENANTS
    del held


def test_over_share_and_top_occupancy():
    plane = _plane(config={"big": {"weight": 3.0}})
    grabbed = [plane.try_admit("hog") for _ in range(3)]
    one = plane.try_admit("small")
    # hog holds 3/4 of inflight on weight 1/2 of active weight
    assert plane.over_share("hog") is True
    assert plane.over_share("small") is False
    assert plane.over_share(None) is False
    assert plane.over_share("idle-unknown") is False
    assert plane.top_occupancy_tenant() == "hog"
    for adm in grabbed:
        adm.release()
    one.release()
    assert plane.top_occupancy_tenant() is None
    # weight normalizes occupancy: 2 inflight at weight 3 scores UNDER
    # 1 inflight at weight 1
    big = [plane.try_admit("big"), plane.try_admit("big")]
    small = plane.try_admit("tiny")
    assert plane.top_occupancy_tenant() == "tiny"
    for adm in big:
        adm.release()
    small.release()


def test_metrics_view_bounded_top_k_plus_other():
    plane = _plane(top_k=4)
    for i in range(12):
        for _ in range(12 - i):  # t00 admits most
            plane.try_admit(f"t{i:02d}").release()
    view = plane.metrics_view()
    assert len(view) == 5  # top 4 + "other"
    assert "other" in view
    assert set(view) > {"t00", "t01", "t02", "t03"}
    # nothing is lost to the bounding: totals add up
    total = sum(int(row["admits_total"]) for row in view.values())
    assert total == plane.admits_total
    # numeric-only rows: the prom renderer labels every stat
    for row in view.values():
        assert all(isinstance(v, float) for v in row.values())


def test_from_env_opt_out(monkeypatch):
    for env in (TENANT_KEYS_ENV, TENANT_CONFIG_ENV, TENANT_RPS_DEFAULT_ENV):
        monkeypatch.delenv(env, raising=False)
    assert tenancy.from_env() is None
    monkeypatch.setenv(TENANT_RPS_DEFAULT_ENV, "25")
    plane = tenancy.from_env()
    assert plane is not None and plane.default_rps == 25.0
    monkeypatch.delenv(TENANT_RPS_DEFAULT_ENV)
    monkeypatch.setenv(
        TENANT_CONFIG_ENV, '{"acme": {"rps": 100, "weight": 4}}'
    )
    plane = tenancy.from_env()
    assert plane is not None and plane.weight("acme") == 4.0


# --------------------------------------------------------------------- DRR


def _items(*tenants: str) -> list:
    return [f"{t}#{i}" for i, t in enumerate(tenants)]


def _tenant_of(item: str) -> str:
    return item.partition("#")[0]


def test_drr_single_tenant_is_identity():
    """Work-conserving degenerate case: one tenant (or zero) returns the
    INPUT LIST OBJECT — the bit-identity opt-out, assertable as `is`."""
    plane = _plane()
    items = _items("a", "a", "a")
    assert plane.drr_order(items, _tenant_of) is items
    empty: list = []
    assert plane.drr_order(empty, _tenant_of) is empty


def test_drr_equal_weights_round_robin():
    plane = _plane()
    items = _items("a", "a", "a", "b", "b", "b", "c", "c", "c")
    out = plane.drr_order(items, _tenant_of)
    assert sorted(out) == sorted(items)  # a permutation: nothing dropped
    assert [_tenant_of(x) for x in out] == [
        "a", "b", "c", "a", "b", "c", "a", "b", "c"
    ]
    # per-tenant arrival order is preserved inside the interleave
    assert [x for x in out if _tenant_of(x) == "a"] == [
        x for x in items if _tenant_of(x) == "a"
    ]


def test_drr_bounded_inter_tenant_gap():
    """Property: while every tenant still has queued items, any window of
    N consecutive grants serves all N tenants — no tenant waits more than
    one full round behind a backlog that isn't its own."""
    plane = _plane()
    tenants = ["a", "b", "c", "d"]
    items = _items(*(t for t in tenants for _ in range(8)))
    out = plane.drr_order(items, _tenant_of)
    n = len(tenants)
    # all tenants have equal depth, so every full window is a full round
    for i in range(0, len(out) - n + 1, n):
        assert {_tenant_of(x) for x in out[i:i + n]} == set(tenants)


def test_drr_weights_scale_service():
    plane = _plane(config={"heavy": {"weight": 2.0}})
    items = _items("heavy", "heavy", "heavy", "heavy", "light", "light")
    out = plane.drr_order(items, _tenant_of)
    # quantum = weight: heavy drains 2 per round for light's 1
    assert [_tenant_of(x) for x in out] == [
        "heavy", "heavy", "light", "heavy", "heavy", "light"
    ]


def test_drr_no_credit_survives_across_calls():
    """Classic DRR: a deficit resets when its queue empties, and every
    queue drains within a call — so NOTHING banks across calls (REVIEW:
    fairness is per-call by design, and a round-1 leftover must not
    reorder round 2)."""
    plane = _plane(config={"a": {"weight": 5.0}})
    # a's 5-credit quantum drains only 1 item here; leftover must not bank
    plane.drr_order(_items("a", "b", "b", "b"), _tenant_of)
    fresh = _plane(config={"a": {"weight": 5.0}})
    items = _items("b", "b", "b", "a", "a")
    assert plane.drr_order(list(items), _tenant_of) == fresh.drr_order(
        list(items), _tenant_of
    )


def test_scheduler_fifo_bit_identical_without_tenancy():
    sch = Scheduler(spec=None, ragged=False)  # tenancy=None: unconfigured
    items = [
        QueueItem(image=None, fut=None, tenant=t, t_submit=float(i))
        for i, t in enumerate(["a", "b", "a", "c", "b"])
    ]
    pending = list(items)
    plan = sch.plan(pending, target=5)
    assert plan.items == items  # exact arrival order
    assert all(x is y for x, y in zip(plan.items, items))  # same objects
    assert pending == []


def test_scheduler_fifo_bit_identical_single_tenant_with_plane():
    sch = Scheduler(spec=None, ragged=False, tenancy=_plane())
    items = [
        QueueItem(image=None, fut=None, tenant="only", t_submit=float(i))
        for i in range(4)
    ]
    pending = list(items)
    plan = sch.plan(pending, target=4)
    assert all(x is y for x, y in zip(plan.items, items))


def test_scheduler_fifo_drr_interleaves_tenants():
    sch = Scheduler(spec=None, ragged=False, tenancy=_plane())
    items = [
        QueueItem(image=None, fut=None, tenant=t, t_submit=float(i))
        for i, t in enumerate(["a", "a", "a", "b", "b", "b"])
    ]
    pending = list(items)
    plan = sch.plan(pending, target=6)
    assert [it.tenant for it in plan.items] == [
        "a", "b", "a", "b", "a", "b"
    ]


def test_flood_over_a_window_admits_burst_plus_rate_times_window():
    """The storm's quota gate on the injected clock: an abuser sending ten
    times its rate for three seconds is admitted its burst and what the
    bucket refills in the window and no more; an honest tenant pacing
    inside its own quota beside it loses nothing."""
    clock = FakeClock()
    plane = _plane(
        config={"abuser": {"rps": 20.0, "burst": 40.0},
                "honest": {"rps": 200.0}},
        clock=clock,
    )
    window_s, tick_s = 3.0, 0.005  # the abuser sends every tick: 200 a second
    honest_failures = 0
    for i in range(int(window_s / tick_s)):
        clock.advance(tick_s)
        try:
            plane.try_admit("abuser").release()
        except TenantQuotaError as exc:
            assert exc.tenant == "abuser" and exc.kind == tenancy.SHED_RATE
        if i % 2 == 0:  # 100 a second against a quota of 200
            try:
                plane.try_admit("honest").release()
            except TenantQuotaError:
                honest_failures += 1
    snap = plane.snapshot()["tenants"]
    assert honest_failures == 0 and snap["honest"]["admits_total"] == 300
    # never over the allowance; one under where the last token's refill
    # falls a float's width short of whole at the window's last tick
    allowance = 40 + 20 * 3
    assert allowance - 1 <= snap["abuser"]["admits_total"] <= allowance
    assert (
        snap["abuser"]["sheds_rate_total"]
        == 600 - snap["abuser"]["admits_total"]
    )


# -------------------------------------------------- noisy-neighbor matrix


@pytest.mark.parametrize("sc", TENANT_MATRIX, ids=lambda sc: sc.name)
def test_tenant_matrix_row(sc):
    report = asyncio.run(run_tenant_scenario(sc))
    assert report["ok"], json.dumps(
        {k: v for k, v in report.items() if k != "plane"},
        indent=2,
        default=str,
    )


# ------------------------------------------------- HTTP surface contracts


def _stub_detector() -> AmenitiesDetector:
    eng = StubEngine(service_ms=1.0)
    return AmenitiesDetector(
        eng, MicroBatcher(eng, max_delay_ms=1.0), StubHttpClient()
    )


def test_unconfigured_server_has_no_tenancy_surface(monkeypatch):
    """The opt-out discipline, end to end: no tenancy env -> no plane
    object, no /metrics tenants block, /debug/tenants reports disabled."""
    for env in (TENANT_KEYS_ENV, TENANT_CONFIG_ENV, TENANT_RPS_DEFAULT_ENV):
        monkeypatch.delenv(env, raising=False)

    async def run():
        det = _stub_detector()
        app = make_app(detector=det)
        assert app["tenancy"] is None
        assert det.tenancy is None
        async with TestClient(TestServer(app)) as client:
            health = await (await client.get("/healthz")).json()
            assert health["tenancy"] == {"enabled": False}
            metrics = await (await client.get("/metrics")).json()
            assert "tenants" not in metrics
            dbg = await client.get("/debug/tenants")
            assert dbg.status == 200
            assert (await dbg.json()) == {"enabled": False}
            # requests with tenant headers still serve normally — the
            # header is inert without the plane
            r = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers={TENANT_HEADER: "ghost"},
            )
            assert r.status == 200
        await det.aclose()

    asyncio.run(run())


def test_standalone_quota_shed_contract(monkeypatch):
    """The 429 contract at the replica edge: shed BEFORE parse, request-id
    echoed, Retry-After present, admit-shed counters charged, and the
    per-tenant rows visible in /metrics and /debug/tenants."""
    monkeypatch.setenv(
        TENANT_CONFIG_ENV, '{"default": {"rps": 1, "burst": 1}}'
    )
    # bare tenant headers are distrusted by default (REVIEW); this test
    # reads the shed CONTRACT, so opt the replica into header identity
    monkeypatch.setenv(tenancy.TENANT_TRUST_HEADER_ENV, "1")

    async def run():
        det = _stub_detector()
        app = make_app(detector=det)
        assert app["tenancy"] is not None
        async with TestClient(TestServer(app)) as client:
            headers = {TENANT_HEADER: "acme", "X-Request-ID": "rid-quota-1"}
            ok = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers=headers,
            )
            assert ok.status == 200
            shed = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers=headers,
            )
            assert shed.status == 429
            assert shed.headers["X-Request-ID"] == "rid-quota-1"
            assert "Retry-After" in shed.headers
            body = await shed.json()
            assert body["status"] == 429
            metrics = await (await client.get("/metrics")).json()
            assert metrics["shed_total"] >= 1
            assert sum(metrics["admit_sheds_total"].values()) >= 1
            assert metrics["tenants"]["acme"]["sheds_rate_total"] == 1
            assert metrics["tenants"]["acme"]["admits_total"] == 1
            prom = await (
                await client.get("/metrics?format=prometheus")
            ).text()
            assert (
                'spotter_tpu_tenants{tenant="acme",stat="sheds_rate_total"}'
                in prom
            )
            dbg = await (await client.get("/debug/tenants")).json()
            assert dbg["tenants"]["acme"]["sheds_rate_total"] == 1
        await det.aclose()

    asyncio.run(run())


def test_router_quota_shed_contract(monkeypatch):
    """The 429 contract at the fleet edge: quota charged BEFORE the body
    is read, request-id echoed, Retry-After + tenant named in the body,
    tenant identity forwarded to the replica, per-tenant /metrics rows."""
    for env in (TENANT_KEYS_ENV, TENANT_CONFIG_ENV, TENANT_RPS_DEFAULT_ENV):
        monkeypatch.delenv(env, raising=False)

    async def run():
        from spotter_tpu.obs.aggregate import FleetAggregator
        from spotter_tpu.serving.replica_pool import ReplicaPool
        from spotter_tpu.serving.router import make_router_app

        det = _stub_detector()
        replica_server = TestServer(make_app(detector=det))
        await replica_server.start_server()
        url = f"http://{replica_server.host}:{replica_server.port}"
        plane = _plane(
            config={"abuser": {"rps": 1.0, "burst": 1.0}},
            trust_header=True,  # clients model an attested upstream here
        )
        pool = ReplicaPool([url], health_interval_s=0.05)
        app = make_router_app(
            pool,
            aggregator=FleetAggregator(lambda: [], interval_s=0.0),
            tenancy_plane=plane,
        )
        async with TestClient(TestServer(app)) as client:
            headers = {TENANT_HEADER: "abuser", "X-Request-ID": "rid-r-1"}
            ok = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers=headers,
            )
            assert ok.status == 200
            shed = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers=headers,
            )
            assert shed.status == 429
            assert shed.headers["X-Request-ID"] == "rid-r-1"
            assert "Retry-After" in shed.headers
            body = await shed.json()
            assert body["tenant"] == "abuser"
            metrics = await (await client.get("/metrics")).json()
            assert metrics["tenants"]["abuser"]["admits_total"] == 1
            assert metrics["tenants"]["abuser"]["sheds_rate_total"] == 1
            dbg = await (await client.get("/debug/tenants")).json()
            assert dbg["tenants"]["abuser"]["sheds_rate_total"] == 1
            health = await (await client.get("/healthz")).json()
            assert health["tenancy"] is True
        await pool.stop()
        await replica_server.close()
        await det.aclose()

    asyncio.run(run())


def test_router_releases_inflight_on_handler_crash():
    """REVIEW leak guard at the router edge: an exception the handler
    does NOT turn into a response (transport bug, cancellation) must
    still free the tenant's inflight slot — else a disconnecting client
    permanently 429-locks its tenant at max_inflight and skews
    top_occupancy/over_share forever."""

    async def run():
        from spotter_tpu.obs.aggregate import FleetAggregator
        from spotter_tpu.serving.replica_pool import ReplicaPool
        from spotter_tpu.serving.router import make_router_app

        plane = _plane(
            config={"t": {"rps": 1000.0, "max_inflight": 1}},
            trust_header=True,
        )
        pool = ReplicaPool(
            ["http://127.0.0.1:1"], health_interval_s=1000.0
        )

        async def boom(*a, **kw):
            raise RuntimeError("injected transport bug")

        pool.request = boom  # not PoolExhaustedError: escapes the handler
        app = make_router_app(
            pool,
            aggregator=FleetAggregator(lambda: [], interval_s=0.0),
            tenancy_plane=plane,
        )
        async with TestClient(TestServer(app)) as client:
            for i in range(3):  # > max_inflight: only a leak would 429
                r = await client.post(
                    "/detect",
                    json={"queries": ["sofa"]},
                    headers={TENANT_HEADER: "t"},
                )
                assert r.status == 500, f"request {i}: {r.status}"
            assert plane.inflight("t") == 0
            # no outcome was served: the crash must not burn the budget
            assert plane.snapshot()["tenants"]["t"]["slo_burn"] == 0.0
        await pool.stop()

    asyncio.run(run())


def test_standalone_releases_inflight_on_handler_crash(monkeypatch):
    """Same leak guard at the replica edge, via an admission-check path
    that raises outside every except clause."""
    monkeypatch.setenv(
        TENANT_CONFIG_ENV,
        '{"default": {"rps": 1000, "max_inflight": 1}}',
    )
    monkeypatch.setenv(tenancy.TENANT_TRUST_HEADER_ENV, "1")

    async def run():
        det = _stub_detector()

        def boom(*a, **kw):
            raise RuntimeError("injected check_admission bug")

        det.check_admission = boom
        app = make_app(detector=det)
        plane = app["tenancy"]
        async with TestClient(TestServer(app)) as client:
            for i in range(3):
                r = await client.post(
                    "/detect",
                    json={"image_urls": ["http://example.com/a.jpg"]},
                    headers={TENANT_HEADER: "t"},
                )
                assert r.status == 500, f"request {i}: {r.status}"
            assert plane.inflight("t") == 0
        await det.aclose()

    asyncio.run(run())


def test_retry_after_header_never_zero():
    """REVIEW: sub-second tenant hints (rate-shed jitter floors at
    0.05 s) must not render `Retry-After: 0` — that invites the
    immediate retry the shed exists to push back. The precise float
    rides in the JSON body instead."""
    from spotter_tpu.serving.router import tenant_shed_response
    from spotter_tpu.serving.standalone import _shed_response

    exc = TenantQuotaError("t", tenancy.SHED_RATE, retry_after_s=0.07)
    for resp in (tenant_shed_response(exc), _shed_response(exc)):
        assert int(resp.headers["Retry-After"]) >= 1
        assert json.loads(resp.body)["retry_after_s"] == 0.07
    # larger hints ceil, not truncate
    slow = TenantQuotaError("t", tenancy.SHED_RATE, retry_after_s=3.2)
    assert tenant_shed_response(slow).headers["Retry-After"] == "4"


def test_shed_contract_table_across_surfaces(monkeypatch):
    """Table-driven shed/reject contract: EVERY rejecting surface echoes
    the request id and returns a JSON error body with the status
    repeated — whichever layer rejected (tenant quota 429, the brownout
    bulk rung 503, batcher queue-full 429, model-routing 400). Load
    sheds carry Retry-After; routing 400s must NOT (a client defect —
    retrying it unchanged can never succeed) and name the registry
    instead so the caller can self-correct (ISSUE 20 parity)."""
    monkeypatch.delenv(TENANT_KEYS_ENV, raising=False)
    monkeypatch.delenv(TENANT_RPS_DEFAULT_ENV, raising=False)

    async def quota_app():
        det = _stub_detector()
        return det.aclose, make_app(detector=det), {TENANT_HEADER: "t"}, 429

    async def brownout_app():
        from spotter_tpu.serving.overload import BrownoutController

        eng = StubEngine(service_ms=1.0)
        clock = FakeClock()
        bc = BrownoutController(
            lambda: True, arm_s=1.0, disarm_s=100.0, clock=clock,
            metrics=eng.metrics,
        )
        det = AmenitiesDetector(
            eng,
            MicroBatcher(eng, max_delay_ms=1.0, brownout=bc),
            StubHttpClient(),
        )
        bc.evaluate()
        for _ in range(4):  # rung 4: bulk-only 503
            clock.advance(1.1)
            bc.evaluate()
        return (
            det.aclose, make_app(detector=det),
            {"X-Request-Class": "bulk"}, 503,
        )

    async def queue_full_app():
        eng = StubEngine(service_ms=200.0)
        det = AmenitiesDetector(
            eng,
            MicroBatcher(eng, max_delay_ms=200.0, max_queue=1),
            StubHttpClient(),
        )
        return det.aclose, make_app(detector=det), {}, 429

    async def routing_app():
        # closed-set single-family fleet edge with the autoscaler armed:
        # an unroutable request 400s BEFORE any pool access, so the pool
        # stays empty (target 0) and no member is ever needed
        from spotter_tpu.obs.aggregate import FleetAggregator
        from spotter_tpu.serving.autoscale import AutoscalerBrain, ModelPool
        from spotter_tpu.serving.fleet import (
            FleetController,
            PoolSpec,
            make_fleet_app,
        )

        controller = FleetController(
            [PoolSpec("rtdetr", spawner=lambda: None, target_size=0)],
            tick_s=0.05,
        )
        brain = AutoscalerBrain(
            controller,
            [ModelPool(model="rtdetr", min_size=0, max_size=1,
                       default=True)],
            tick_s=0.25,
        )
        app = make_fleet_app(
            controller,
            aggregator=FleetAggregator(lambda: [], interval_s=0.0),
            autoscaler=brain,
        )

        async def noop():
            return None

        return noop, app, {}, 400

    async def run():
        # (name, build, tenant_cfg, payload_extra, retry_after)
        rows = [
            ("tenant-quota", quota_app,
             '{"default": {"rps": 0.001, "burst": 1}}', {}, True),
            ("brownout-bulk", brownout_app, "", {}, True),
            ("queue-full", queue_full_app, "", {}, True),
            ("unknown-model", routing_app, "",
             {"model": "segment-anything"}, False),
            ("closed-set-queries", routing_app, "",
             {"queries": ["a solar panel"]}, False),
        ]
        for name, build, tenant_cfg, payload_extra, retry_after in rows:
            if tenant_cfg:
                monkeypatch.setenv(TENANT_CONFIG_ENV, tenant_cfg)
            else:
                monkeypatch.delenv(TENANT_CONFIG_ENV, raising=False)
            aclose, app, headers, want_status = await build()
            async with TestClient(TestServer(app)) as client:
                # concurrent burst: one request fills the quota/queue slot,
                # the rest hit the shed surface under test (routing rows
                # reject all 8 — the defect is in the request itself)
                resps = await asyncio.gather(*(
                    client.post(
                        "/detect",
                        json={
                            "image_urls": [f"http://example.com/{i}.jpg"],
                            **payload_extra,
                        },
                        headers={
                            **headers, "X-Request-ID": f"rid-{name}-{i}"
                        },
                    )
                    for i in range(8)
                ))
                sheds = [
                    (i, r) for i, r in enumerate(resps)
                    if r.status == want_status
                ]
                assert sheds, (
                    f"{name}: no {want_status} among "
                    f"{[r.status for r in resps]}"
                )
                for i, shed in sheds:
                    assert (
                        shed.headers["X-Request-ID"] == f"rid-{name}-{i}"
                    ), name
                    assert ("Retry-After" in shed.headers) is retry_after, (
                        f"{name}: Retry-After "
                        f"{'missing' if retry_after else 'present'}"
                    )
                    body = await shed.json()
                    assert body["status"] == want_status, name
                    if want_status == 400:
                        assert body["kind"] in (
                            "unknown_model", "closed_set_queries"
                        ), name
                        assert "rtdetr" in body["families"], name
                for _, r in enumerate(resps):
                    await r.read()
                metrics = await (await client.get("/metrics")).json()
                if want_status == 400:
                    block = metrics["autoscale"]
                    assert block["routing_rejections_total"] >= 8, name
                else:
                    assert metrics["shed_total"] >= 1, name
            await aclose()

    asyncio.run(run())
