"""Caching tier (ISSUE 5): content-addressed result cache + single-flight
coalescing, exercised through the REAL detector/batcher plumbing with a fake
engine (the quantity under test is the cache/coalescing machinery, not the
forward pass).

Covers the acceptance + edge matrix: N concurrent identical-URL requests do
exactly 1 fetch and <= 1 engine call; a waiter's deadline expiring mid-flight
fails only that waiter; a shared-flight poison fans `PoisonImageError` to
every waiter exactly once AND fills the negative cache (so a repeat skips the
bisect machinery); eviction respects the byte budget under concurrent fill;
negative-cache TTL expiry really re-attempts the fetch; retryable failures
(5xx) are never cached; `SPOTTER_TPU_CACHE_MAX_MB=0` constructs none of the
tier (bit-identical admission behavior); injected cache faults degrade to
misses, never failed requests.
"""

import asyncio
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from io import BytesIO

import httpx
import numpy as np
import pytest
from PIL import Image

from spotter_tpu.caching.result_cache import ResultCache, content_key, url_key
from spotter_tpu.caching.singleflight import SingleFlight
from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.errors import PoisonImageError
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.serving.detector import AmenitiesDetector
from spotter_tpu.serving.resilience import (
    CircuitBreaker,
    Deadline,
    DeadlineExceededError,
)
from spotter_tpu.testing import faults

DETS = [{"label": "tv", "score": 0.9, "box": [1.0, 2.0, 20.0, 30.0]}]


@pytest.fixture(autouse=True)
def _zero_retry_backoff(monkeypatch):
    import spotter_tpu.serving.detector as det_mod

    monkeypatch.setattr(det_mod, "FETCH_RETRY_WAIT_MIN_S", 0.0)
    monkeypatch.setattr(det_mod, "FETCH_RETRY_WAIT_MAX_S", 0.0)


class FakeEngine:
    def __init__(self, service_s: float = 0.0, detections=DETS):
        self.metrics = Metrics()
        self.batch_buckets = (1, 2, 4, 8)
        self.threshold = 0.5
        self.calls: list[int] = []
        self.service_s = service_s
        self.detections = detections

    def detect(self, images):
        self.calls.append(len(images))
        if self.service_s:
            time.sleep(self.service_s)
        return [list(self.detections) for _ in images]


class FailingEngine(FakeEngine):
    def detect(self, images):
        self.calls.append(len(images))
        raise RuntimeError("synthetic model failure")


class BrightPoisonEngine(FakeEngine):
    """Fails any batch containing a bright (mean > 200) image — the
    deterministic per-input failure shape the bisect-retry isolates to a
    `PoisonImageError` once a co-batched innocent proves the engine works."""

    def detect(self, images):
        self.calls.append(len(images))
        if any(np.asarray(im).mean() > 200 for im in images):
            raise RuntimeError("bright image poisoned its batch")
        return [list(self.detections) for _ in images]


def _jpeg(seed: int = 0) -> bytes:
    img = Image.fromarray(np.full((16, 16, 3), seed % 256, np.uint8))
    buf = BytesIO()
    img.save(buf, format="JPEG")
    return buf.getvalue()


class CountingClient:
    """Duck-typed httpx client: per-URL fetch counts, per-URL content, an
    optional latency, and optional canned failures."""

    def __init__(self, latency_s: float = 0.0, fail_with=None, content_for=None):
        self.fetches: dict[str, int] = {}
        self.latency_s = latency_s
        self.fail_with = fail_with  # callable(url) -> response
        self.content_for = content_for  # callable(url) -> bytes

    async def get(self, url: str):
        self.fetches[url] = self.fetches.get(url, 0) + 1
        if self.latency_s:
            await asyncio.sleep(self.latency_s)
        if self.fail_with is not None:
            return self.fail_with(url)
        body = (
            self.content_for(url)
            if self.content_for is not None
            else _jpeg(abs(hash(url)) % 251)
        )

        class _Resp:
            content = body

            def raise_for_status(self):
                pass

        return _Resp()

    async def aclose(self):
        pass


def _img():
    return Image.fromarray(np.zeros((8, 8, 3), np.uint8))


def _detector(engine, client=None, cache=None, **batcher_kwargs):
    batcher_kwargs.setdefault("max_delay_ms", 1.0)
    batcher = MicroBatcher(engine, **batcher_kwargs)
    return AmenitiesDetector(
        engine, batcher, client or CountingClient(), cache=cache
    )


def _cache(engine, max_bytes=1 << 20, **kwargs):
    return ResultCache(max_bytes=max_bytes, metrics=engine.metrics, **kwargs)


# --- acceptance: N identical concurrent requests -> 1 fetch, <= 1 engine call


def test_concurrent_identical_urls_one_fetch_one_engine_call():
    engine = FakeEngine(service_s=0.01)
    client = CountingClient(latency_s=0.01)
    det = _detector(engine, client, cache=_cache(engine))

    async def run():
        payload = {"image_urls": ["http://cdn/x.jpg"] * 8}
        resp = await det.detect(payload)
        assert all(isinstance(d.detections, list) for d in resp.images)
        await det.aclose()

    asyncio.run(run())
    assert client.fetches == {"http://cdn/x.jpg": 1}
    assert sum(engine.calls) <= 1
    snap = engine.metrics.snapshot()
    assert snap["coalesced_fetches_total"] == 7
    assert snap["coalesced_submits_total"] == 7


def test_repeat_request_is_cache_hit_no_engine_call():
    engine = FakeEngine()
    det = _detector(engine, cache=_cache(engine))

    async def run():
        await det.detect({"image_urls": ["http://cdn/a.jpg"]})
        calls_after_first = sum(engine.calls)
        resp = await det.detect({"image_urls": ["http://cdn/a.jpg"]})
        assert isinstance(resp.images[0].detections, list)
        assert resp.images[0].detections[0].label == "TV"
        assert sum(engine.calls) == calls_after_first  # served from cache
        await det.aclose()

    asyncio.run(run())
    snap = engine.metrics.snapshot()
    assert snap["cache_hits_total"] == 1
    assert snap["cache_entries"] == 1


# --- coalescing edges ---------------------------------------------------------


def test_waiter_deadline_expires_mid_flight_others_succeed():
    engine = FakeEngine(service_s=0.25)
    batcher = MicroBatcher(engine, max_delay_ms=1.0)

    async def run():
        img = _img()
        t_ok = asyncio.create_task(batcher.submit(img, key="k"))
        await asyncio.sleep(0.05)  # flight is queued/dispatched
        with pytest.raises(DeadlineExceededError):
            await batcher.submit(
                _img(), deadline=Deadline.after(0.05), key="k"
            )
        assert await t_ok == DETS  # the shared flight survived the expiry
        await batcher.stop()

    asyncio.run(run())
    assert sum(engine.calls) == 1
    assert engine.metrics.snapshot()["deadline_exceeded_total"] == 1


def test_shared_flight_poison_fans_to_all_waiters_exactly_once():
    engine = BrightPoisonEngine()
    cache = ResultCache(max_bytes=1 << 20, metrics=engine.metrics)
    batcher = MicroBatcher(
        engine,
        max_delay_ms=50.0,  # wide window: poison + innocent share one batch
        breaker=CircuitBreaker(threshold=100, metrics=engine.metrics),
        result_cache=cache,
    )
    poison = Image.fromarray(np.full((8, 8, 3), 255, np.uint8))
    observed: list[BaseException] = []

    async def run():
        async def one():
            try:
                await batcher.submit(poison, key="poisoned")
            except PoisonImageError as exc:
                observed.append(exc)

        innocent = asyncio.create_task(batcher.submit(_img()))
        await asyncio.gather(*(one() for _ in range(5)))
        assert await innocent == DETS  # co-batched innocent succeeded
        await batcher.stop()

    asyncio.run(run())
    # every waiter saw the poison exactly once, off ONE coalesced queue entry
    assert len(observed) == 5
    assert len({id(e) for e in observed}) == 1  # the same fanned instance
    # 1 original batch + its bisect halves — never one call per waiter
    assert len(engine.calls) == 3 and engine.calls[0] == 2
    # ... and the verdict landed in the negative cache for repeat traffic
    assert isinstance(cache.get_negative("poisoned"), PoisonImageError)
    assert engine.metrics.snapshot()["poison_isolated_total"] == 1


def test_repeat_poison_skips_bisect_via_negative_cache():
    engine = BrightPoisonEngine()

    def content(url):
        return _jpeg(255) if "poison" in url else _jpeg(0)

    det = _detector(
        engine,
        CountingClient(content_for=content),
        cache=_cache(engine),
        breaker=CircuitBreaker(threshold=100, metrics=engine.metrics),
        max_delay_ms=50.0,
    )

    async def run():
        r1 = await det.detect(
            {"image_urls": ["http://cdn/poison.jpg", "http://cdn/ok.jpg"]}
        )
        by_url = {i.url: i for i in r1.images}
        assert "PoisonImageError" in by_url["http://cdn/poison.jpg"].error
        assert isinstance(by_url["http://cdn/ok.jpg"].detections, list)
        engine_calls = len(engine.calls)
        r2 = await det.detect({"image_urls": ["http://cdn/poison.jpg"]})
        assert "Processing Error" in r2.images[0].error
        assert len(engine.calls) == engine_calls  # no re-bisect, no engine work
        await det.aclose()

    asyncio.run(run())
    assert engine.metrics.snapshot()["cache_negative_hits_total"] == 1


def test_draining_shared_flight_not_cached():
    """A keyed flight failed by shutdown (the 429/503 shed family) must fan
    the error to its waiters but never write a cache entry."""
    engine = FakeEngine(service_s=10.0)  # never completes inside the test
    cache = ResultCache(max_bytes=1 << 20, metrics=engine.metrics)
    batcher = MicroBatcher(engine, max_delay_ms=50.0, result_cache=cache)

    async def run():
        tasks = [
            asyncio.create_task(batcher.submit(_img(), key="k"))
            for _ in range(3)
        ]
        await asyncio.sleep(0.02)
        # fail the queued entry without running it: stop() fails leftovers
        batcher._pump_task.cancel()
        try:
            await batcher._pump_task
        except asyncio.CancelledError:
            pass
        results = await asyncio.gather(*tasks, return_exceptions=True)
        assert all(isinstance(r, Exception) for r in results)

    asyncio.run(run())
    assert cache.stats()["entries"] == 0
    assert cache.get_negative("k") is None


def test_keyed_churn_never_strands_waiters():
    """Regression: a submit landing between the primary future settling and
    its done-callback running sees `done()` and starts a successor flight
    for the same key — the settled flight's waiters must still be fanned
    out (the callback owns its own waiter list; re-reading the dict there
    stranded them forever and deadlocked the load loop)."""
    engine = FakeEngine()
    batcher = MicroBatcher(engine, max_delay_ms=0.5)

    async def run():
        async def worker(n):
            for i in range(60):
                out = await batcher.submit(_img(), key=f"hot-{i % 2}")
                assert out == DETS

        await asyncio.wait_for(
            asyncio.gather(*(worker(w) for w in range(8))), timeout=30
        )
        await batcher.stop()

    asyncio.run(run())
    assert batcher._keyed == {}


# --- result cache semantics ---------------------------------------------------


def test_eviction_under_concurrent_fill_respects_byte_budget():
    metrics = Metrics()
    cache = ResultCache(max_bytes=4096, metrics=metrics)

    def fill(base):
        for i in range(100):
            key = f"m|{base}-{i}|t0.50"
            cache.put(key, [dict(DETS[0], score=float(i))])
            cache.get(key)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(8)))

    stats = cache.stats()
    assert 0 < stats["bytes"] <= 4096
    assert stats["entries"] > 0
    snap = metrics.snapshot()
    assert snap["cache_evictions_total"] > 0
    assert snap["cache_bytes"] <= 4096


def test_ttl_expiry_and_copy_semantics():
    now = [1000.0]
    cache = ResultCache(max_bytes=1 << 20, ttl_s=10.0, clock=lambda: now[0])
    cache.put("k", DETS)
    hit = cache.get("k")
    assert hit == DETS
    hit[0]["label"] = "mutated"  # a caller's mutation must not poison the cache
    assert cache.get("k")[0]["label"] == "tv"
    now[0] += 11.0
    assert cache.get("k") is None  # TTL expired


def test_oversized_value_not_stored():
    cache = ResultCache(max_bytes=64)
    cache.put("k", [dict(DETS[0], label="x" * 500)])
    assert cache.get("k") is None
    assert cache.stats()["bytes"] == 0


def test_negative_cache_ttl_expiry_reattempts_fetch():
    engine = FakeEngine()
    now = [0.0]
    cache = ResultCache(
        max_bytes=1 << 20,
        negative_ttl_s=5.0,
        metrics=engine.metrics,
        clock=lambda: now[0],
    )

    def not_found(url):
        resp = httpx.Response(404, request=httpx.Request("GET", url))
        return resp

    client = CountingClient(fail_with=not_found)
    det = _detector(engine, client, cache=cache)
    url = "http://cdn/missing.jpg"

    async def run():
        r1 = await det.detect({"image_urls": [url]})
        assert "HTTP Error" in r1.images[0].error
        assert client.fetches[url] == 1  # 404 fails fast, no retries
        r2 = await det.detect({"image_urls": [url]})
        assert "HTTP Error" in r2.images[0].error
        assert client.fetches[url] == 1  # negative hit: no second fetch
        now[0] += 6.0  # past the negative TTL
        r3 = await det.detect({"image_urls": [url]})
        assert "HTTP Error" in r3.images[0].error
        assert client.fetches[url] == 2  # expiry really re-attempted
        await det.aclose()

    asyncio.run(run())
    assert engine.metrics.snapshot()["cache_negative_hits_total"] == 1


def test_retryable_5xx_failures_never_cached():
    engine = FakeEngine()
    cache = _cache(engine)

    def server_error(url):
        return httpx.Response(500, request=httpx.Request("GET", url))

    client = CountingClient(fail_with=server_error)
    det = _detector(engine, client, cache=cache)
    url = "http://cdn/flaky.jpg"

    async def run():
        r1 = await det.detect({"image_urls": [url]})
        assert "HTTP Error" in r1.images[0].error
        fetches_first = client.fetches[url]
        assert fetches_first == 3  # full retry contract for retryable 5xx
        r2 = await det.detect({"image_urls": [url]})
        assert "HTTP Error" in r2.images[0].error
        assert client.fetches[url] == fetches_first * 2  # nothing was cached
        await det.aclose()

    asyncio.run(run())
    assert cache.get_negative(url_key(url)) is None
    assert engine.metrics.snapshot()["cache_negative_hits_total"] == 0


# --- disable switch + env knobs ----------------------------------------------


def test_cache_max_mb_zero_fully_disables_tier(monkeypatch):
    monkeypatch.setenv("SPOTTER_TPU_CACHE_MAX_MB", "0")
    engine = FakeEngine()
    client = CountingClient()
    batcher = MicroBatcher(engine, max_delay_ms=1.0)
    det = AmenitiesDetector(engine, batcher, client)
    assert det.cache is None
    assert batcher.result_cache is None

    async def run():
        # sequential duplicates: today's behavior is a fetch per request
        for _ in range(3):
            resp = await det.detect({"image_urls": ["http://cdn/a.jpg"]})
            assert isinstance(resp.images[0].detections, list)
        await det.aclose()

    asyncio.run(run())
    assert client.fetches == {"http://cdn/a.jpg": 3}
    assert sum(engine.calls) == 3
    assert batcher._keyed == {}
    snap = engine.metrics.snapshot()
    for counter in (
        "cache_hits_total",
        "cache_misses_total",
        "cache_negative_hits_total",
        "coalesced_fetches_total",
        "coalesced_submits_total",
        "cache_entries",
        "cache_bytes",
    ):
        assert snap[counter] == 0, counter


def test_from_env_knobs(monkeypatch):
    monkeypatch.delenv("SPOTTER_TPU_CACHE_MAX_MB", raising=False)
    assert ResultCache.from_env() is None  # off by default
    monkeypatch.setenv("SPOTTER_TPU_CACHE_MAX_MB", "8")
    monkeypatch.setenv("SPOTTER_TPU_CACHE_TTL_S", "120")
    monkeypatch.setenv("SPOTTER_TPU_CACHE_NEGATIVE_TTL_S", "7")
    cache = ResultCache.from_env()
    assert cache is not None
    assert cache.max_bytes == 8 * 1024 * 1024
    assert cache.ttl_s == 120.0
    assert cache.negative_ttl_s == 7.0
    # the explicit override (--cache-mb) wins over the env budget
    assert ResultCache.from_env(max_mb=0) is None
    assert ResultCache.from_env(max_mb=2).max_bytes == 2 * 1024 * 1024


def test_health_reports_cache_state():
    engine = FakeEngine()
    det = _detector(engine, cache=_cache(engine))
    health = det.health()
    assert health["cache"]["enabled"] is True
    assert health["cache"]["max_bytes"] == 1 << 20
    det_off = _detector(FakeEngine(), cache=None)
    assert det_off.health()["cache"] == {"enabled": False}


# --- chaos: faults on the cache path -----------------------------------------


def test_cache_faults_degrade_to_miss_never_fail_requests():
    engine = FakeEngine()
    det = _detector(engine, cache=_cache(engine))

    async def run():
        with faults.inject(cache_error=-1):  # every cache op raises
            for _ in range(2):
                resp = await det.detect({"image_urls": ["http://cdn/a.jpg"]})
                assert isinstance(resp.images[0].detections, list)
        await det.aclose()

    asyncio.run(run())
    # the cache never worked, so both requests paid the engine (miss path) —
    # and neither surfaced the injected failure
    assert sum(engine.calls) == 2
    assert engine.metrics.snapshot()["cache_hits_total"] == 0


# --- single-flight primitive --------------------------------------------------


def test_singleflight_failure_fans_to_every_waiter():
    calls = {"n": 0}

    async def run():
        flights = SingleFlight()

        async def boom():
            calls["n"] += 1
            await asyncio.sleep(0.02)
            raise ValueError("flight failed")

        results = await asyncio.gather(
            *(flights.run("k", boom) for _ in range(4)), return_exceptions=True
        )
        assert calls["n"] == 1
        assert all(isinstance(r, ValueError) for r in results)
        assert len({id(r) for r in results}) == 1

    asyncio.run(run())


def test_singleflight_waiter_cancellation_keeps_flight_alive():
    async def run():
        flights = SingleFlight()
        started = asyncio.Event()
        done = threading.Event()

        async def work():
            started.set()
            await asyncio.sleep(0.05)
            done.set()
            return 42

        t1 = asyncio.create_task(flights.run("k", work))
        await started.wait()
        t2 = asyncio.create_task(flights.run("k", work))
        await asyncio.sleep(0)
        t2.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t2
        assert await t1 == 42  # the shared flight survived t2's cancellation
        assert done.is_set()

    asyncio.run(run())


# --- the duplicate-heavy workload, as counts ----------------------------------


@pytest.mark.parametrize("cached", [True, False], ids=["cache-on", "cache-off"])
def test_zipf_duplicates_cost_one_engine_image_per_distinct_picture(cached):
    """Sixty requests over twelve pictures, Zipf-skewed from a seed, eight
    at a time: with the tier on the engine is handed each distinct picture
    once and every other request is a hit or rides a flight already in the
    air; with it off the engine is handed all sixty."""
    rng = np.random.default_rng(5)
    weights = 1.0 / np.arange(1, 13) ** 1.1
    picks = rng.choice(12, size=60, p=weights / weights.sum())
    urls = [f"http://cdn/listing-{i}.jpg" for i in picks]
    distinct = len(set(urls))
    assert distinct < 30  # duplicate-heavy: over half the requests repeat

    engine = FakeEngine(service_s=0.002)
    client = CountingClient(
        latency_s=0.001,
        content_for=lambda url: _jpeg(int(url.rsplit("-", 1)[1][:-4]) * 20),
    )
    det = _detector(engine, client, cache=_cache(engine) if cached else None)

    async def run():
        sem = asyncio.Semaphore(8)

        async def one(url):
            async with sem:
                resp = await det.detect({"image_urls": [url]})
                assert isinstance(resp.images[0].detections, list)

        await asyncio.gather(*(one(u) for u in urls))
        await det.aclose()

    asyncio.run(run())
    snap = engine.metrics.snapshot()
    if cached:
        assert sum(engine.calls) == distinct
        assert (
            snap["cache_hits_total"] + snap["coalesced_submits_total"]
            == len(urls) - distinct
        )
        assert snap["cache_entries"] == distinct
    else:
        assert sum(engine.calls) == len(urls)
        assert sum(client.fetches.values()) == len(urls)
        assert "cache_hits_total" not in snap or snap["cache_hits_total"] == 0
