"""Overload-control tier tests (ISSUE 8): the AIMD limiter state machine,
class-ordered shedding (bulk strictly before slo), brownout rung hysteresis
(no flapping across the arm/disarm boundary), the serve-stale cache path,
jittered Retry-After hints, deadline-aware fetch attempts, and the
opt-in contract (SPOTTER_TPU_ADMIT_* unset keeps the static queue-depth
semantics). The state machines are pure units — fake clock, scripted
saturation, no engine; the integration half drives the real MicroBatcher
over the stub engine and the standalone HTTP surface."""

import asyncio
import os
import random
import time

import httpx
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

os.environ.setdefault("SPOTTER_TPU_TINY", "1")

from spotter_tpu.caching.result_cache import ResultCache
from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.serving.detector import AmenitiesDetector, FetchError
from spotter_tpu.serving.overload import (
    ADMIT_EDGE_TARGET_ENV,
    ADMIT_TARGET_ENV,
    BULK,
    SLO,
    AdaptiveLimiter,
    AdmitLimitError,
    BrownoutController,
    BrownoutShedError,
    build_overload_control,
    edge_limiter_from_env,
)
from spotter_tpu.serving.resilience import (
    BACKOFF_JITTER_ENV,
    Deadline,
    DeadlineExceededError,
    QueueFullError,
    jittered_retry_after,
)
from spotter_tpu.serving.standalone import make_app
from spotter_tpu.testing import faults
from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient


class FakeClock:
    def __init__(self, now: float = 1000.0) -> None:
        self.now = now

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> None:
        self.now += dt


def _img():
    return Image.fromarray(np.zeros((8, 8, 3), np.uint8))


# ---------------------------------------------------------------- limiter


def test_aimd_decrease_increase_floor_ceiling():
    clock = FakeClock()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=2, ceiling=10, increase=1.0, decrease=0.5,
        interval_s=1.0, clock=clock,
    )
    assert lim.limit == 10  # starts at the ceiling (optimistic)

    # over target -> multiplicative decrease
    clock.advance(1.1)
    lim.observe(200.0)
    assert lim.limit == 5
    clock.advance(1.1)
    lim.observe(200.0)
    assert lim.limit == 2  # int(2.5)
    # floor clamp + pinned signal
    clock.advance(1.1)
    lim.observe(200.0)
    clock.advance(1.1)
    lim.observe(200.0)
    assert lim.limit == 2
    assert lim.pinned_at_floor()

    # under target -> additive increase, one step per interval
    clock.advance(1.1)
    lim.observe(5.0)
    assert not lim.pinned_at_floor()
    for _ in range(20):
        clock.advance(1.1)
        lim.observe(5.0)
    assert lim.limit == 10  # ceiling clamp


def test_aimd_idle_tick_recovers_and_signal_decays():
    clock = FakeClock()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=1, ceiling=8, increase=1.0, decrease=0.5,
        interval_s=1.0, clock=clock,
    )
    for _ in range(6):
        clock.advance(1.1)
        lim.observe(500.0)
    assert lim.pinned_at_floor() and lim.last_p90_ms == 500.0
    # zero traffic: idle ticks probe the limit back up and decay the p90 —
    # without this a floor-pinned limiter could never disarm the brownout
    clock.advance(1.1)
    lim.tick()
    assert lim.last_p90_ms == 0.0
    for _ in range(10):
        clock.advance(1.1)
        lim.tick()
    assert lim.limit == 8 and not lim.pinned_at_floor()


def test_aimd_update_rate_is_interval_bound():
    clock = FakeClock()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=1, ceiling=8, decrease=0.5, interval_s=1.0,
        clock=clock,
    )
    # many over-target samples inside ONE interval -> at most one decrease
    clock.advance(1.1)
    for _ in range(50):
        lim.observe(500.0)
    assert lim.limit == 4


def test_class_order_bulk_sheds_strictly_before_slo():
    clock = FakeClock()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=1, ceiling=2, interval_s=1e9, clock=clock,
    )
    b = lim.try_admit(BULK)
    s = lim.try_admit(SLO)
    assert b is not None and s is not None
    # at the same instant, over the limit: bulk sheds, slo does not (it
    # rides the bounded soft overage while bulk holds a slot)
    assert lim.try_admit(BULK) is None
    s2 = lim.try_admit(SLO)
    assert s2 is not None
    assert lim.sheds_total[BULK] == 1 and lim.sheds_total[SLO] == 0
    for adm in (b, s, s2):
        adm.release()
    # slo alone at the limit DOES shed — the overage is bulk-backed only
    a1, a2 = lim.try_admit(SLO), lim.try_admit(SLO)
    assert a1 is not None and a2 is not None
    assert lim.try_admit(SLO) is None
    assert lim.sheds_total[SLO] == 1


def test_revocation_newest_bulk_first():
    clock = FakeClock()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=1, ceiling=3, interval_s=1e9, clock=clock,
    )
    revoked = []
    admissions = {}
    for name in ("b1", "b2", "b3"):
        adm = lim.try_admit(BULK)
        adm.attach_revoke(lambda n=name: revoked.append(n))
        admissions[name] = adm
    # full; an slo arrival revokes the NEWEST queued bulk (LIFO-ish)
    s1 = lim.try_admit(SLO)
    assert s1 is not None and revoked == ["b3"]
    assert lim.in_flight == 3  # the revoked slot was reused, not leaked
    # dispatched work leaves the stack: b2 becomes unrevocable, so the next
    # revocation takes b1 (the only queued bulk left)
    admissions["b2"].make_unrevocable()
    s2 = lim.try_admit(SLO)
    assert s2 is not None and revoked == ["b3", "b1"]
    # nothing revocable left, but bulk (b2) still holds a slot -> soft admit
    s3 = lim.try_admit(SLO)
    assert s3 is not None and revoked == ["b3", "b1"]
    assert lim.revoked_total == 2


def test_release_is_idempotent():
    lim = AdaptiveLimiter(target_ms=50.0, floor=1, ceiling=4, interval_s=1e9)
    adm = lim.try_admit(BULK)
    adm.release()
    adm.release()
    assert lim.in_flight == 0


def test_limiter_from_env_opt_in(monkeypatch):
    monkeypatch.delenv(ADMIT_TARGET_ENV, raising=False)
    assert AdaptiveLimiter.from_env() is None
    assert build_overload_control() == (None, None)
    monkeypatch.setenv(ADMIT_TARGET_ENV, "0")
    assert AdaptiveLimiter.from_env() is None
    monkeypatch.setenv(ADMIT_TARGET_ENV, "25")
    lim = AdaptiveLimiter.from_env()
    assert lim is not None and lim.target_ms == 25.0
    limiter, brownout = build_overload_control()
    assert limiter is not None and brownout is not None
    # the edge knob is independent
    monkeypatch.delenv(ADMIT_EDGE_TARGET_ENV, raising=False)
    assert edge_limiter_from_env() is None
    monkeypatch.setenv(ADMIT_EDGE_TARGET_ENV, "100")
    assert edge_limiter_from_env().target_ms == 100.0


def test_overload_spike_fault_cuts_limit_without_traffic():
    clock = FakeClock()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=1, ceiling=8, decrease=0.5, interval_s=1.0,
        clock=clock,
    )
    with faults.inject(overload_spike=2):
        clock.advance(1.1)
        lim.tick()
        assert lim.limit == 4 and lim.last_p90_ms == 500.0
        clock.advance(1.1)
        lim.tick()
        assert lim.limit == 2
        # spike exhausted: the next idle tick recovers (default additive
        # increase is 2.0)
        clock.advance(1.1)
        lim.tick()
        assert lim.limit == 4


# --------------------------------------------------------------- brownout


def _stepped_brownout(clock, sat, **kwargs):
    kwargs.setdefault("arm_s", 1.0)
    kwargs.setdefault("disarm_s", 2.0)
    return BrownoutController(lambda: sat["v"], clock=clock, **kwargs)


def test_brownout_rungs_arm_one_at_a_time_and_disarm_with_hysteresis():
    clock = FakeClock()
    sat = {"v": False}
    bc = _stepped_brownout(clock, sat)
    assert bc.evaluate() == 0

    sat["v"] = True
    assert bc.evaluate() == 0  # saturation must SUSTAIN for arm_s
    clock.advance(0.5)
    assert bc.evaluate() == 0
    clock.advance(0.6)
    assert bc.evaluate() == 1
    assert bc.evaluate() == 1  # no double-step within the arm window
    clock.advance(1.1)
    assert bc.evaluate() == 2
    clock.advance(1.1)
    assert bc.evaluate() == 3
    clock.advance(1.1)
    assert bc.evaluate() == 4
    clock.advance(5.0)
    assert bc.evaluate() == 4  # max rung, stays

    # clear must SUSTAIN for disarm_s (2x arm here); the clear window
    # starts at the first evaluate() that SEES the clear signal
    sat["v"] = False
    assert bc.evaluate() == 4
    clock.advance(1.9)
    assert bc.evaluate() == 4
    clock.advance(0.2)
    assert bc.evaluate() == 3
    for expected in (2, 1, 0):
        clock.advance(2.1)
        assert bc.evaluate() == expected
    clock.advance(10.0)
    assert bc.evaluate() == 0


def test_brownout_no_flap_across_boundary():
    clock = FakeClock()
    sat = {"v": True}
    bc = _stepped_brownout(clock, sat)
    bc.evaluate()  # prime: the saturation window starts when first seen
    clock.advance(1.1)
    bc.evaluate()
    clock.advance(1.1)
    assert bc.evaluate() == 2
    # a signal oscillating FASTER than both windows moves nothing: every
    # toggle resets the opposite window before it can complete
    for _ in range(20):
        sat["v"] = not sat["v"]
        clock.advance(0.4)
        assert bc.evaluate() == 2


def test_brownout_transitions_pin_recorder_traces_and_gauge():
    from spotter_tpu.engine.metrics import Metrics
    from spotter_tpu.obs import FlightRecorder

    clock = FakeClock()
    sat = {"v": True}
    metrics = Metrics()
    recorder = FlightRecorder(ring=8, slowest_k=0)
    bc = BrownoutController(
        lambda: sat["v"], arm_s=1.0, disarm_s=2.0, clock=clock,
        metrics=metrics, recorder=recorder,
    )
    bc.evaluate()  # prime the saturation window
    clock.advance(1.1)
    bc.evaluate()
    clock.advance(1.1)
    bc.evaluate()
    snap = metrics.snapshot()
    assert snap["brownout_rung"] == 2
    assert snap["brownout_transitions_total"] == 2
    rec = recorder.snapshot()
    assert rec["errors_total"] == 2
    assert all(t["status"] == "brownout" for t in rec["errors"])
    assert "rung 1" in rec["errors"][-1]["error"]


def test_brownout_rung_effects():
    clock = FakeClock()
    sat = {"v": True}
    bc = _stepped_brownout(clock, sat, threshold_boost=0.2)
    bc.evaluate()  # prime the saturation window
    rung_effects = []
    for _ in range(4):
        clock.advance(1.1)
        bc.evaluate()
        rung_effects.append(
            (bc.stale_ok(), bc.bucket_cap_active(),
             bc.threshold_boost_value(), bc.shed_bulk())
        )
    assert rung_effects == [
        (True, False, 0.0, False),
        (True, True, 0.0, False),
        (True, True, 0.2, False),
        (True, True, 0.2, True),
    ]
    assert bc.markers() == ["bucket_cap", "threshold"]


def test_brownout_hold_blocks_deescalation_but_never_escalates():
    clock = FakeClock()
    sat = {"v": True}
    holding = {"v": False}
    bc = BrownoutController(
        lambda: sat["v"], arm_s=1.0, disarm_s=2.0, clock=clock,
        hold=lambda: holding["v"],
    )
    bc.evaluate()
    for _ in range(2):
        clock.advance(1.1)
        bc.evaluate()
    assert bc.rung == 2
    # not saturated but still shedding: the rung HOLDS (no exit, no entry)
    sat["v"] = False
    holding["v"] = True
    for _ in range(10):
        clock.advance(2.5)
        assert bc.evaluate() == 2
    # shedding stops: the clear window finally runs and the ladder exits
    holding["v"] = False
    bc.evaluate()  # clear window starts when first seen
    clock.advance(2.1)
    assert bc.evaluate() == 1
    clock.advance(2.1)
    assert bc.evaluate() == 0
    # hold never escalates a calm system
    holding["v"] = True
    for _ in range(5):
        clock.advance(2.5)
        assert bc.evaluate() == 0


def test_saturation_signals_shed_delta_holds():
    from spotter_tpu.engine.metrics import Metrics
    from spotter_tpu.serving.overload import saturation_signals

    metrics = Metrics()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=1, ceiling=8, interval_s=1e9, metrics=metrics
    )
    saturated, hold = saturation_signals(lim, 400.0, metrics=metrics)
    assert saturated() is False and hold() is False
    metrics.record_admit_shed(BULK)
    assert hold() is True  # new sheds since last poll
    assert hold() is False  # delta consumed; calm until the next shed


# ----------------------------------------------------- jittered Retry-After


def test_jittered_retry_after_band_and_seed(monkeypatch):
    monkeypatch.delenv(BACKOFF_JITTER_ENV, raising=False)  # default on
    rng = random.Random(42)
    vals = [jittered_retry_after(10.0, rng=rng) for _ in range(200)]
    assert all(7.5 <= v <= 12.5 for v in vals)  # +-25% full jitter
    assert len({round(v, 6) for v in vals}) > 100  # actually spread
    # seeded determinism: same seed, same draw
    assert jittered_retry_after(10.0, rng=random.Random(7)) == pytest.approx(
        jittered_retry_after(10.0, rng=random.Random(7))
    )
    # knob off -> exact value
    monkeypatch.setenv(BACKOFF_JITTER_ENV, "0")
    assert jittered_retry_after(10.0) == 10.0
    assert jittered_retry_after(10.0, enabled=False) == 10.0


# --------------------------------------------------------- stale-serve path


def test_result_cache_stale_entry_served_only_when_allowed():
    clock = FakeClock()
    rc = ResultCache(max_bytes=1 << 20, ttl_s=10.0, clock=clock)
    rc.put("k", [{"label": "tv", "score": 0.9, "box": [1, 2, 3, 4]}])
    fresh, stale = rc.get_entry("k")
    assert fresh and stale is False
    clock.advance(11.0)
    # brownout rung 1: the expired entry is acceptable AND kept
    value, stale = rc.get_entry("k", stale_ok=True)
    assert value and stale is True
    value, stale = rc.get_entry("k", stale_ok=True)
    assert value and stale is True
    # fresh path: expired entry drops and misses, exactly as before
    assert rc.get_entry("k") == (None, False)
    assert rc.get_entry("k", stale_ok=True) == (None, False)


# ----------------------------------------------- batcher integration (async)


def test_batcher_static_semantics_preserved_without_admit_env(monkeypatch):
    """Acceptance: SPOTTER_TPU_ADMIT_* unset -> no limiter, no brownout,
    bounded queue with the exact static QueueFullError shed."""
    monkeypatch.delenv(ADMIT_TARGET_ENV, raising=False)

    async def run():
        eng = StubEngine(service_ms=50.0)
        b = MicroBatcher(
            eng, max_batch=1, max_delay_ms=1.0, max_in_flight=1, max_queue=2
        )
        assert b.limiter is None and b.brownout is None
        assert b._queue.maxsize == 2
        img = _img()
        tasks = [asyncio.create_task(b.submit(img)) for _ in range(6)]
        results = await asyncio.gather(*tasks, return_exceptions=True)
        shed = [r for r in results if isinstance(r, QueueFullError)]
        ok = [r for r in results if isinstance(r, list)]
        assert shed and ok  # bounded queue shed some, served the rest
        await b.stop()

    asyncio.run(run())


def test_batcher_limiter_revokes_queued_bulk_for_slo():
    async def run():
        eng = StubEngine(service_ms=80.0)
        lim = AdaptiveLimiter(
            target_ms=10_000.0, floor=1, ceiling=2, interval_s=1e9
        )
        b = MicroBatcher(
            eng, max_batch=1, max_delay_ms=1.0, max_in_flight=1,
            limiter=lim, brownout=None,
        )
        img = _img()
        t_b1 = asyncio.create_task(b.submit(img, cls=BULK))
        await asyncio.sleep(0.03)  # b1 dispatched (unrevocable)
        t_b2 = asyncio.create_task(b.submit(img, cls=BULK))
        await asyncio.sleep(0.01)  # b2 queued, revocable
        # the limit (2) is fully held; the slo arrival revokes b2
        slo_result = await b.submit(img, cls=SLO)
        assert slo_result
        with pytest.raises(AdmitLimitError):
            await t_b2
        assert await t_b1  # the dispatched bulk still completes
        assert lim.revoked_total == 1
        # queue_wait joined the stage histograms (the control signal is
        # observable in /metrics)
        assert "stage_queue_wait_ms_p90" in eng.metrics.snapshot()
        await b.stop()

    asyncio.run(run())


def test_batcher_limiter_sheds_bulk_when_full():
    async def run():
        eng = StubEngine(service_ms=60.0)
        lim = AdaptiveLimiter(
            target_ms=10_000.0, floor=1, ceiling=1, interval_s=1e9,
            metrics=eng.metrics,
        )
        b = MicroBatcher(
            eng, max_batch=1, max_delay_ms=1.0, max_in_flight=1,
            limiter=lim, brownout=None,
        )
        img = _img()
        t1 = asyncio.create_task(b.submit(img, cls=BULK))
        await asyncio.sleep(0.02)
        with pytest.raises(AdmitLimitError) as ei:
            await b.submit(img, cls=BULK)
        assert ei.value.status == 429 and ei.value.retry_after_s > 0
        assert await t1
        assert eng.metrics.snapshot()["admit_sheds_total"]["bulk"] == 1
        await b.stop()

    asyncio.run(run())


def test_batcher_brownout_bulk_503_and_bucket_cap():
    async def run():
        eng = StubEngine(service_ms=1.0)  # buckets (1, 2, 4, 8)
        clock = FakeClock()
        sat = {"v": True}
        bc = BrownoutController(
            lambda: sat["v"], arm_s=1.0, disarm_s=100.0, clock=clock,
            metrics=eng.metrics,
        )
        b = MicroBatcher(
            eng, max_delay_ms=1.0, limiter=None, brownout=bc
        )
        assert b._dispatch_bucket() == 8
        bc.evaluate()  # prime the saturation window
        clock.advance(1.1)
        bc.evaluate()
        clock.advance(1.1)
        bc.evaluate()  # rung 2: bucket cap
        assert b._dispatch_bucket() == 4
        img = _img()
        assert await b.submit(img, cls=BULK)  # rung 2 serves bulk fine
        clock.advance(1.1)
        bc.evaluate()
        clock.advance(1.1)
        bc.evaluate()  # rung 4: bulk-only 503
        with pytest.raises(BrownoutShedError) as ei:
            await b.submit(img, cls=BULK)
        assert ei.value.status == 503
        assert await b.submit(img, cls=SLO)  # slo keeps serving
        await b.stop()

    asyncio.run(run())


# ------------------------------------------------ detector + HTTP surface


def test_detector_serves_stale_with_degraded_marker():
    async def run():
        eng = StubEngine(service_ms=1.0)
        cache_clock = FakeClock()
        rc = ResultCache(
            max_bytes=1 << 20, ttl_s=5.0, clock=cache_clock,
            metrics=eng.metrics,
        )
        clock = FakeClock()
        sat = {"v": True}
        bc = BrownoutController(
            lambda: sat["v"], arm_s=1.0, disarm_s=100.0, clock=clock,
            metrics=eng.metrics,
        )
        b = MicroBatcher(eng, max_delay_ms=1.0, limiter=None, brownout=bc)
        det = AmenitiesDetector(eng, b, StubHttpClient(), cache=rc)
        payload = {"image_urls": ["http://example.com/room.jpg"]}
        resp1 = await det.detect(payload)
        assert resp1.degraded is None  # fresh fill, no brownout shaping
        batches_after_fill = eng.metrics.snapshot()["batches_total"]
        cache_clock.advance(10.0)  # entry expires
        bc.evaluate()  # prime the saturation window
        clock.advance(1.1)
        bc.evaluate()  # rung 1: serve-stale
        resp2 = await det.detect(payload)
        assert resp2.degraded == ["stale"]
        assert resp2.images[0].detections  # real content, just stale
        snap = eng.metrics.snapshot()
        assert snap["batches_total"] == batches_after_fill  # no engine pass
        assert snap["stale_served_total"] == 1
        await det.aclose()

    asyncio.run(run())


def test_standalone_brownout_surface():
    """/healthz status=brownout + rung, /metrics brownout_rung, the
    degraded marker on the wire, and the bulk-only 503 — end to end over
    the real HTTP surface with X-Request-Class."""

    async def run():
        eng = StubEngine(service_ms=1.0)
        clock = FakeClock()
        sat = {"v": True}
        bc = BrownoutController(
            lambda: sat["v"], arm_s=1.0, disarm_s=100.0, clock=clock,
            metrics=eng.metrics,
        )
        b = MicroBatcher(eng, max_delay_ms=1.0, limiter=None, brownout=bc)
        det = AmenitiesDetector(eng, b, StubHttpClient(), cache=None)
        bc.evaluate()  # prime the saturation window
        for _ in range(2):  # step to rung 2 (bucket_cap)
            clock.advance(1.1)
            bc.evaluate()
        app = make_app(detector=det)
        async with TestClient(TestServer(app)) as client:
            h = await client.get("/healthz")
            assert h.status == 200
            body = await h.json()
            assert body["status"] == "brownout"
            assert body["brownout"]["rung"] == 2
            assert body["admit"] == {"enabled": False}

            m = await (await client.get("/metrics")).json()
            assert m["brownout_rung"] == 2
            assert m["brownout_transitions_total"] == 2

            prom_text = await (
                await client.get("/metrics?format=prometheus")
            ).text()
            assert "spotter_tpu_brownout_rung 2" in prom_text

            r = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
            )
            assert r.status == 200
            rbody = await r.json()
            assert rbody["degraded"] == ["bucket_cap"]

            for _ in range(2):  # step to rung 4 (bulk-only 503)
                clock.advance(1.1)
                bc.evaluate()
            shed = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers={"X-Request-Class": "bulk"},
            )
            assert shed.status == 503
            assert "Retry-After" in shed.headers
            ok = await client.post(
                "/detect",
                json={"image_urls": ["http://example.com/a.jpg"]},
                headers={"X-Request-Class": "slo"},
            )
            assert ok.status == 200

    asyncio.run(run())


# ------------------------------------------- deadline-aware fetch attempts


class _SlowConnectClient:
    """Every GET hangs `delay_s` then fails with a retryable error."""

    def __init__(self, delay_s: float) -> None:
        self.delay_s = delay_s
        self.attempts = 0

    async def get(self, url: str):
        self.attempts += 1
        await asyncio.sleep(self.delay_s)
        raise httpx.ConnectError(f"injected connect failure for {url}")

    async def aclose(self) -> None:
        pass


class _InstantFailClient(_SlowConnectClient):
    def __init__(self) -> None:
        super().__init__(0.0)

    async def get(self, url: str):
        self.attempts += 1
        raise httpx.ConnectError(f"injected connect failure for {url}")


def test_fetch_attempt_timeout_clamped_to_deadline():
    async def run():
        eng = StubEngine()
        client = _SlowConnectClient(delay_s=5.0)
        det = AmenitiesDetector(
            eng, MicroBatcher(eng, max_delay_ms=1.0), client, cache=None
        )
        deadline = Deadline.after(0.25)
        t0 = time.monotonic()
        with pytest.raises((DeadlineExceededError, FetchError)):
            await det._fetch_with_retries("http://x/a.jpg", deadline)
        elapsed = time.monotonic() - t0
        # a 5 s hang against a 250 ms budget must die in ~one budget, not
        # 3 attempts x 5 s + 8 s of backoff
        assert elapsed < 1.5
        assert client.attempts == 1
        await det.batcher.stop()

    asyncio.run(run())


def test_fetch_retries_skipped_when_budget_cannot_cover_backoff():
    async def run():
        eng = StubEngine()
        client = _InstantFailClient()
        det = AmenitiesDetector(
            eng, MicroBatcher(eng, max_delay_ms=1.0), client, cache=None
        )
        deadline = Deadline.after(1.0)  # backoff min is 4 s > budget
        t0 = time.monotonic()
        with pytest.raises(httpx.ConnectError):
            await det._fetch_with_retries("http://x/a.jpg", deadline)
        elapsed = time.monotonic() - t0
        assert elapsed < 0.5  # no pointless 4 s sleep
        assert client.attempts == 1  # the remaining attempts were skipped
        await det.batcher.stop()

    asyncio.run(run())


def test_fetch_deadline_none_keeps_reference_retry_policy(monkeypatch):
    """Without a deadline the 3-attempt contract is untouched (backoff is
    patched to zero so the test doesn't sleep 8 s)."""
    from spotter_tpu.serving import detector as detector_mod

    monkeypatch.setattr(detector_mod, "FETCH_RETRY_WAIT_MIN_S", 0.0)
    monkeypatch.setattr(detector_mod, "FETCH_RETRY_WAIT_MAX_S", 0.0)

    async def run():
        eng = StubEngine()
        client = _InstantFailClient()
        det = AmenitiesDetector(
            eng, MicroBatcher(eng, max_delay_ms=1.0), client, cache=None
        )
        with pytest.raises(httpx.ConnectError):
            await det._fetch_with_retries("http://x/a.jpg")
        assert client.attempts == 3
        await det.batcher.stop()

    asyncio.run(run())


# ------------------------------------------- the storm, as counts and states


def test_burst_at_four_times_the_limit_slo_never_fails_bulk_absorbs_it():
    """Four times the admission limit offered in one tick, a quarter of it
    slo-class: every slo request is answered, the limiter sheds no slo
    request, and what is refused or revoked is bulk, with a 429."""

    async def run():
        eng = StubEngine(service_ms=5.0)
        lim = AdaptiveLimiter(
            target_ms=10_000.0, floor=1, ceiling=4, interval_s=1e9,
            metrics=eng.metrics,
        )
        b = MicroBatcher(
            eng, max_batch=2, max_delay_ms=1.0, max_in_flight=1,
            limiter=lim, brownout=None,
        )
        img = _img()
        bulk = [asyncio.create_task(b.submit(img, cls=BULK)) for _ in range(12)]
        slo = [asyncio.create_task(b.submit(img, cls=SLO)) for _ in range(4)]
        slo_results = await asyncio.gather(*slo, return_exceptions=True)
        bulk_results = await asyncio.gather(*bulk, return_exceptions=True)
        await b.stop()
        return lim, eng, slo_results, bulk_results

    lim, eng, slo_results, bulk_results = asyncio.run(run())
    assert all(isinstance(r, list) for r in slo_results), slo_results
    refused = [r for r in bulk_results if not isinstance(r, list)]
    assert refused and all(isinstance(r, AdmitLimitError) for r in refused)
    assert all(r.status == 429 for r in refused)
    assert lim.sheds_total[SLO] == 0
    # the limit of 4 went to the first four bulk requests, the other eight
    # were refused at the door, and each slo arrival then took a queued
    # bulk request's place (a revocation counts among the bulk sheds)
    assert lim.sheds_total[BULK] == len(refused) == 12
    assert lim.revoked_total == 4
    assert eng.metrics.snapshot()["admit_sheds_total"]["slo"] == 0


def test_storm_on_the_clock_arms_two_rungs_and_comes_back_to_zero():
    """The whole chain on the injected clock, no traffic and no sleeps: a
    storm of over-target queue waits pins the limiter at its floor, the
    default signal pair arms the ladder one rung at a time to at least two,
    shedding holds it there, and once the storm ends idle ticks bring the
    limit back up and the rung back to 0, every rung left as it was
    entered."""
    from spotter_tpu.engine.metrics import Metrics
    from spotter_tpu.serving.overload import saturation_signals

    clock = FakeClock()
    metrics = Metrics()
    lim = AdaptiveLimiter(
        target_ms=50.0, floor=2, ceiling=16, increase=2.0, decrease=0.5,
        interval_s=0.1, clock=clock, metrics=metrics,
    )
    saturated, hold = saturation_signals(lim, 400.0, metrics=metrics)
    bc = BrownoutController(
        saturated, arm_s=0.4, disarm_s=0.8, clock=clock, metrics=metrics,
        hold=hold,
    )
    rungs = [bc.evaluate()]
    for _ in range(30):  # 3.3 s of storm
        clock.advance(0.11)
        lim.observe(500.0)
        metrics.record_admit_shed(BULK)
        rungs.append(bc.evaluate())
    assert lim.pinned_at_floor()
    peak = max(rungs)
    assert peak >= 2
    # still shedding with the queue gone quiet: the rung holds
    for _ in range(10):
        clock.advance(0.11)
        lim.tick()
        metrics.record_admit_shed(BULK)
        rungs.append(bc.evaluate())
    assert rungs[-1] == peak
    for _ in range(100):  # 11 s of calm
        clock.advance(0.11)
        lim.tick()
        rungs.append(bc.evaluate())
    assert rungs[-1] == 0 and lim.limit == 16
    steps = [b - a for a, b in zip(rungs, rungs[1:]) if b != a]
    assert set(steps) == {1, -1}  # one rung at a time, both ways
    assert steps.count(1) == steps.count(-1) == peak  # no flap on the way
    assert metrics.snapshot()["brownout_transitions_total"] == 2 * peak
