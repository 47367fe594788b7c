"""Edge data plane integration (ISSUE 11): binary wire format, X-Cache,
fleet-shared negative cache, affinity fan-out/fan-in, annotated-JPEG cache
entries — driven over real in-process HTTP (aiohttp test servers, model-free
synthetic engines, CPU-safe).
"""

import asyncio
import base64
import json
from io import BytesIO

import httpx
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

from spotter_tpu.caching.result_cache import ResultCache
from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.serving import wire
from spotter_tpu.serving.detector import AmenitiesDetector
from spotter_tpu.serving.replica_pool import ReplicaPool
from spotter_tpu.serving.router import make_router_app
from spotter_tpu.serving.standalone import make_app

URLS = [f"http://cdn.example.com/photo-{i}.jpg" for i in range(8)]
BAD_URL = "http://cdn.example.com/gone.jpg"


def _jpeg(seed: int, size: int = 48) -> bytes:
    rng = np.random.default_rng(seed)
    img = Image.fromarray(rng.integers(0, 255, (size, size, 3), dtype=np.uint8))
    buf = BytesIO()
    img.save(buf, format="JPEG")
    return buf.getvalue()


BODIES = {u: _jpeg(i) for i, u in enumerate(URLS)}


class SyntheticEngine:
    def __init__(self) -> None:
        self.metrics = Metrics()
        self.batch_buckets = (8,)
        self.threshold = 0.5
        self.calls = 0

    def detect(self, images):
        self.calls += 1
        return [
            [{"label": "tv", "score": 0.9, "box": [1.0, 1.0, 9.0, 9.0]}]
            for _ in images
        ]


class CannedClient:
    def __init__(self, bodies: dict) -> None:
        self.bodies = bodies
        self.fetches = 0

    async def get(self, url: str):
        self.fetches += 1
        if url not in self.bodies:
            req = httpx.Request("GET", url)
            resp = httpx.Response(404, request=req)
            raise httpx.HTTPStatusError("404 Not Found", request=req, response=resp)
        body = self.bodies[url]

        class _Resp:
            content = body

            def raise_for_status(self):
                pass

        return _Resp()

    async def aclose(self):
        pass


def build_replica(cache_mb: float = 8.0, annotated: bool = True):
    engine = SyntheticEngine()
    cache = (
        ResultCache(
            max_bytes=int(cache_mb * 1024 * 1024),
            metrics=engine.metrics,
            annotated=annotated,
        )
        if cache_mb > 0
        else None
    )
    det = AmenitiesDetector(
        engine,
        MicroBatcher(engine, max_batch=8, max_delay_ms=1.0),
        CannedClient(dict(BODIES)),
        cache=cache,
    )
    return det, make_app(detector=det)


# -- frame unit tests --------------------------------------------------------


def _sample_body(degraded=None) -> dict:
    body = {
        "amenities_description": "The property contains: TV.",
        "images": [
            {
                "url": URLS[0],
                "detections": [{"label": "TV", "box": [1.0, 2.0, 3.0, 4.0]}],
                "labeled_image_base64": base64.b64encode(_jpeg(0)).decode(),
            },
            {"url": BAD_URL, "error": "Fetch Error: nope"},
        ],
    }
    if degraded is not None:
        body["degraded"] = degraded
    return body


def test_frame_roundtrip_and_layout():
    body = _sample_body(degraded=["stale"])
    frame = wire.encode_frame(body)
    assert frame[:4] == wire.FRAME_MAGIC
    assert frame[4] == wire.FRAME_VERSION
    assert wire.decode_frame(frame) == body
    header, segments = wire.split_frame(frame)
    # one raw segment (the success image), error image carried inline
    assert len(segments) == 1 and segments[0] == _jpeg(0)
    assert header["images"][0]["image_segment"] == 0
    assert "labeled_image_base64" not in header["images"][0]
    assert wire.build_frame(header, segments) == frame
    # the frame strictly beats JSON+base64 on the wire
    assert len(frame) < len(wire.to_json_bytes(body))


def test_frame_rejects_garbage():
    import pytest

    for bad in (b"", b"XXXX" + b"\x00" * 20, wire.encode_frame(_sample_body())[:-3]):
        with pytest.raises(wire.FrameError):
            wire.decode_frame(bad)


# -- wire integrity: frame v2 checksums + fuzz (ISSUE 14) --------------------


def test_frame_v2_layout_and_v1_interop():
    """v2 is the default encoding (header + per-segment checksums); v1
    frames (SPOTTER_TPU_WIRE_CRC=0, or an old peer) still decode."""
    body = _sample_body(degraded=["stale"])
    v2 = wire.encode_frame(body)
    assert v2[4] == wire.FRAME_VERSION == 2
    assert wire.decode_frame(v2) == body
    header, segments = wire.strip_segments(body)
    v1 = wire.build_frame(header, segments, crc=False)
    assert v1[4] == wire.FRAME_VERSION_V1 == 1
    assert wire.decode_frame(v1) == body
    # the v2 integrity layer costs exactly 4 bytes + 4 per segment
    assert len(v2) == len(v1) + 4 + 4 * len(segments)


def test_frame_corruption_is_typed_never_garbage():
    """A flipped bit in a CRC-protected region must raise
    FrameCorruptError (a FrameError subclass), not decode to garbage."""
    import pytest

    frame = wire.encode_frame(_sample_body())
    # flip one byte in the segment region (the JPEG tail)
    bad = bytearray(frame)
    bad[-2] ^= 0xFF
    with pytest.raises(wire.FrameCorruptError):
        wire.decode_frame(bytes(bad))
    # and one in the header region (after the 20-byte preamble)
    bad = bytearray(frame)
    bad[24] ^= 0x01
    with pytest.raises(wire.FrameCorruptError):
        wire.decode_frame(bytes(bad))
    assert issubclass(wire.FrameCorruptError, wire.FrameError)
    # verify_frame (the pool validator body) raises the same way
    with pytest.raises(wire.FrameCorruptError):
        frame_bad = bytearray(frame)
        frame_bad[-1] ^= 0x40
        wire.verify_frame(bytes(frame_bad))
    wire.verify_frame(frame)  # intact frame passes silently


def test_frame_fuzz_truncation_and_bitflips_always_typed():
    """The fuzz contract (ISSUE 14 satellite): ANY truncation and ANY
    single-byte corruption of a valid frame raises FrameError (or its
    FrameCorruptError subclass) — never struct.error, KeyError,
    UnicodeDecodeError, or a silent garbage decode. Exhaustive over every
    byte of a small frame plus seeded random multi-byte damage."""
    import random

    import pytest

    frame = wire.encode_frame(_sample_body(degraded=["stale"]))
    # every possible truncation
    for i in range(len(frame)):
        with pytest.raises(wire.FrameError):
            wire.decode_frame(frame[:i])
    # every single-byte flip: v2 checksums cover the preamble, header and
    # segments, so nothing slips through as a silent/garbage decode
    for i in range(len(frame)):
        bad = bytearray(frame)
        bad[i] ^= 0xFF
        with pytest.raises(wire.FrameError):
            wire.decode_frame(bytes(bad))
    # seeded random multi-byte damage (flips + slices + garbage splices)
    rng = random.Random(0xC0FFEE)
    for _ in range(300):
        bad = bytearray(frame)
        for _ in range(rng.randint(1, 8)):
            bad[rng.randrange(len(bad))] ^= 1 << rng.randrange(8)
        if rng.random() < 0.3:
            cut = rng.randrange(len(bad))
            bad = bad[:cut] + bytearray(rng.randbytes(rng.randint(0, 32)))
        try:
            wire.decode_frame(bytes(bad))
        except wire.FrameError:
            pass  # typed — the contract
        # any OTHER exception type propagates and fails the test


def test_corrupt_frame_fault_flips_a_checked_byte():
    """The chaos-matrix injection (faults.corrupt_frame_bytes) must damage
    a CRC-protected region: armed -> the frame fails validation exactly N
    times; unarmed -> identity."""
    import pytest

    from spotter_tpu.testing import faults

    frame = wire.encode_frame(_sample_body())
    assert faults.corrupt_frame_bytes(frame) == frame  # no plan: identity
    with faults.inject(corrupt_frame=2):
        first = faults.corrupt_frame_bytes(frame)
        second = faults.corrupt_frame_bytes(frame)
        third = faults.corrupt_frame_bytes(frame)  # armed count consumed
    assert first != frame and second != frame
    assert third == frame
    for bad in (first, second):
        with pytest.raises(wire.FrameCorruptError):
            wire.decode_frame(bad)


def test_negotiation_and_cache_summary():
    assert wire.wants_frame("application/x-spotter-frame")
    assert wire.wants_frame("application/json, application/x-spotter-frame;q=0.9")
    assert not wire.wants_frame("application/json")
    assert not wire.wants_frame(None)
    assert wire.summarize_cache_outcomes([]) is None
    assert wire.summarize_cache_outcomes(["hit", "hit"]) == "hit"
    assert wire.summarize_cache_outcomes(["hit", "negative"]) == "negative"
    assert wire.summarize_cache_outcomes(["hit", "coalesced"]) == "coalesced"
    assert wire.summarize_cache_outcomes(["hit", "miss"]) == "miss"


# -- annotated cache entries (ISSUE 11 satellite) ----------------------------


def test_annotated_entry_lifecycle():
    cache = ResultCache(max_bytes=1 << 20, annotated=True)
    raw = [{"label": "tv", "score": 0.9, "box": [1.0, 1.0, 9.0, 9.0]}]
    cache.put("k", raw)
    dets, stale, annotated = cache.get_entry_full("k")
    assert dets == raw and not stale and annotated is None
    jpeg = _jpeg(1)
    cache.attach_annotated("k", jpeg, [{"label": "TV", "box": [1.0, 1.0, 9.0, 9.0]}])
    dets, stale, annotated = cache.get_entry_full("k")
    assert annotated is not None and annotated["jpeg"] == jpeg
    assert cache.stats()["annotated_entries"] == 1
    # the sidecar's bytes count against the budget as ONE unit with the
    # entry: dropping the entry reclaims both
    bytes_with = cache.stats()["bytes"]
    assert bytes_with > len(jpeg)
    cache.put("k2", raw)  # refill elsewhere, then evict k by budget pressure
    cache.max_bytes = 200
    cache.put("k3", raw)
    assert cache.stats()["bytes"] <= max(200, 0) or cache.stats()["entries"] <= 1


def test_annotated_disabled_keeps_plain_entries():
    cache = ResultCache(max_bytes=1 << 20, annotated=False)
    cache.put("k", [{"label": "tv", "score": 0.9, "box": [1.0, 1.0, 9.0, 9.0]}])
    cache.attach_annotated("k", _jpeg(1), [])
    assert cache.get_entry_full("k")[2] is None


# -- replica HTTP surface ----------------------------------------------------


def test_replica_json_byte_identity_and_frame_negotiation():
    """The wire contract: not negotiated -> the JSON body is byte-identical
    to the pre-frame encoding (including exclude_none: no `degraded` key);
    negotiated -> the frame decodes to the same response."""

    async def run():
        det, app = build_replica()
        async with TestClient(TestServer(app)) as client:
            payload = {"image_urls": [URLS[0], BAD_URL]}
            resp = await client.post("/detect", json=payload)
            assert resp.status == 200
            raw = await resp.read()
            parsed = json.loads(raw)
            # byte-identity: the body IS the default json.dumps encoding of
            # the model dump (exactly what web.json_response(dump) emits)
            assert raw == json.dumps(parsed).encode()
            assert "degraded" not in parsed
            assert resp.headers[wire.X_CACHE_HEADER] == "miss"
            # the 404 produced a deterministic verdict header
            verdicts = wire.parse_negative_header(
                resp.headers.get(wire.NEGATIVE_HEADER)
            )
            assert [v["url"] for v in verdicts] == [BAD_URL]
            assert verdicts[0]["ttl_s"] > 0

            framed = await client.post(
                "/detect",
                json=payload,
                headers={"Accept": wire.FRAME_CONTENT_TYPE},
            )
            assert framed.status == 200
            assert framed.content_type == wire.FRAME_CONTENT_TYPE
            frame_raw = await framed.read()
            assert wire.decode_frame(frame_raw) == parsed
            assert len(frame_raw) < len(raw)
            # wire accounting on the replica
            snap = det.engine.metrics.snapshot()
            assert snap["wire_requests_total"] == 2
            assert snap["wire_frame_responses_total"] == 1
            assert snap["wire_json_responses_total"] == 1
            assert snap["wire_bytes_out_total"] == len(raw) + len(frame_raw)
        await det.aclose()

    asyncio.run(run())


def test_replica_x_cache_hit_and_annotated_fast_path():
    async def run():
        det, app = build_replica()
        async with TestClient(TestServer(app)) as client:
            payload = {"image_urls": [URLS[1]]}
            first = await client.post("/detect", json=payload)
            assert first.headers[wire.X_CACHE_HEADER] == "miss"
            second = await client.post("/detect", json=payload)
            assert second.headers[wire.X_CACHE_HEADER] == "hit"
            # hit responses are literally the same bytes (same annotated
            # JPEG, not a re-draw): the annotated sidecar served it
            assert (await first.read()) == (await second.read())
            assert det.cache.stats()["annotated_entries"] == 1
            assert det.engine.calls == 1  # the hit never reached the engine

            # second POST of the BAD url: served from the replica's own
            # negative cache
            await client.post("/detect", json={"image_urls": [BAD_URL]})
            neg = await client.post("/detect", json={"image_urls": [BAD_URL]})
            assert neg.headers[wire.X_CACHE_HEADER] == "negative"
            assert det.client.fetches == 3  # 2 images + 1 bad (cached after)
        await det.aclose()

    asyncio.run(run())


# -- router data plane -------------------------------------------------------


async def _start_fleet(n: int, **replica_kwargs):
    dets, servers, urls = [], [], []
    for _ in range(n):
        det, app = build_replica(**replica_kwargs)
        server = TestServer(app)
        await server.start_server()
        dets.append(det)
        servers.append(server)
        urls.append(f"http://{server.host}:{server.port}")
    return dets, servers, urls


async def _stop_fleet(dets, servers):
    for server in servers:
        await server.close()
    for det in dets:
        await det.aclose()


def test_router_affinity_fanout_reassembles_in_order():
    async def run():
        dets, servers, urls = await _start_fleet(3)
        pool = ReplicaPool(urls, health_interval_s=0.2)
        router_app = make_router_app(pool, affinity=True)
        async with TestClient(TestServer(router_app)) as client:
            payload = {"image_urls": list(URLS)}
            resp = await client.post("/detect", json=payload)
            assert resp.status == 200
            body = json.loads(await resp.read())
            assert [img["url"] for img in body["images"]] == list(URLS)
            assert body["amenities_description"] == "The property contains: TV."
            assert "degraded" not in body
            # run the same workload again: every URL must land on the
            # replica that cached it the first time — the affinity claim
            resp2 = await client.post("/detect", json=payload)
            assert resp2.headers[wire.X_CACHE_HEADER] == "hit"
            metrics = json.loads(await (await client.get("/metrics")).read())
            assert metrics["affinity"]["enabled"] is True
            assert metrics["affinity"]["routed_total"] >= 2
            assert metrics["affinity"]["hit_rate"] == 1.0
            assert metrics["affinity"]["ring_members"] == 3
            assert metrics["wire"]["bytes_out_total"] > 0
            assert metrics["wire"]["requests_total"] == 2
            # fleet-wide: the second pass was all hits, no new engine calls
            assert sum(d.engine.calls for d in dets) == len(URLS) or all(
                d.engine.calls <= len(URLS) for d in dets
            )
            hits = sum(
                d.engine.metrics.snapshot()["cache_hits_total"] for d in dets
            )
            assert hits == len(URLS)
        await _stop_fleet(dets, servers)

    asyncio.run(run())


def test_router_json_passthrough_byte_identity():
    """Single-owner requests pass the replica body through unchanged: the
    router adds NOTHING to the non-negotiated wire contract."""

    async def run():
        dets, servers, urls = await _start_fleet(2)
        pool = ReplicaPool(urls, health_interval_s=0.2)
        router_app = make_router_app(pool, affinity=True)
        async with TestClient(TestServer(router_app)) as client:
            payload = {"image_urls": [URLS[2]]}
            via_router = await (await client.post("/detect", json=payload)).read()
            # ask every replica directly; one of them served it
            direct_bodies = []
            async with httpx.AsyncClient() as hc:
                for u in urls:
                    r = await hc.post(f"{u}/detect", json=payload)
                    direct_bodies.append(r.content)
            assert via_router in direct_bodies
        await _stop_fleet(dets, servers)

    asyncio.run(run())


def test_router_frame_negotiation_and_merge():
    async def run():
        dets, servers, urls = await _start_fleet(3)
        pool = ReplicaPool(urls, health_interval_s=0.2)
        router_app = make_router_app(pool, affinity=True)
        async with TestClient(TestServer(router_app)) as client:
            payload = {"image_urls": list(URLS)}
            json_raw = await (await client.post("/detect", json=payload)).read()
            framed = await client.post(
                "/detect", json=payload,
                headers={"Accept": wire.FRAME_CONTENT_TYPE},
            )
            assert framed.content_type == wire.FRAME_CONTENT_TYPE
            frame_raw = await framed.read()
            assert wire.decode_frame(frame_raw) == json.loads(json_raw)
            # the ≥25% bytes-on-wire cut, observed at the client
            assert len(frame_raw) < 0.75 * len(json_raw), (
                f"frame {len(frame_raw)} vs json {len(json_raw)}"
            )
        await _stop_fleet(dets, servers)

    asyncio.run(run())


def test_router_edge_negative_cache_answers_without_replica():
    async def run():
        dets, servers, urls = await _start_fleet(2)
        pool = ReplicaPool(urls, health_interval_s=0.2)
        router_app = make_router_app(pool, affinity=True, edge_negative_ttl_s=30.0)
        async with TestClient(TestServer(router_app)) as client:
            payload = {"image_urls": [BAD_URL]}
            first = await client.post("/detect", json=payload)
            assert first.status == 200
            assert "error" in json.loads(await first.read())["images"][0]
            fetches_before = sum(d.client.fetches for d in dets)
            requests_before = pool.requests_total
            second = await client.post("/detect", json=payload)
            assert second.status == 200
            body = json.loads(await second.read())
            assert "error" in body["images"][0]
            assert body["images"][0]["url"] == BAD_URL
            assert second.headers[wire.X_CACHE_HEADER] == "negative"
            # the edge answered: zero replica work for the repeat
            assert sum(d.client.fetches for d in dets) == fetches_before
            assert pool.requests_total == requests_before
            metrics = json.loads(await (await client.get("/metrics")).read())
            assert metrics["edge_negative"]["hits_total"] == 1
            assert metrics["edge_negative"]["entries_added_total"] >= 1
        await _stop_fleet(dets, servers)

    asyncio.run(run())


def test_router_affinity_off_keeps_round_robin():
    async def run():
        dets, servers, urls = await _start_fleet(2)
        pool = ReplicaPool(urls, health_interval_s=0.2)
        router_app = make_router_app(pool, affinity=False)
        async with TestClient(TestServer(router_app)) as client:
            for _ in range(4):
                resp = await client.post(
                    "/detect", json={"image_urls": [URLS[0]]}
                )
                assert resp.status == 200
            metrics = json.loads(await (await client.get("/metrics")).read())
            assert metrics["affinity"]["enabled"] is False
            assert metrics["affinity"]["routed_total"] == 0
            # round-robin: BOTH replicas saw the same URL (the ~1/N decay
            # affinity exists to fix)
            assert all(d.client.fetches > 0 for d in dets)
        await _stop_fleet(dets, servers)

    asyncio.run(run())


@pytest.mark.parametrize("affinity", [True, False], ids=["affinity", "round-robin"])
def test_fleet_hit_count_holds_under_affinity_and_decays_without(affinity):
    """One URL asked for six times in turn of a fleet of three caches. Under
    affinity it has one owner: the fleet computes it once and the other
    five answers are hits, what one replica alone would give. Round-robin
    hands it to each replica in turn, each computes it once for its own
    cache, and only the second lap hits: the 1/N decay, as counts. (The
    cache is keyed by content, so a hit still fetches: the last number is
    which replicas were asked at all.)"""

    async def run():
        dets, servers, urls = await _start_fleet(3)
        pool = ReplicaPool(urls, health_interval_s=0.2)
        router_app = make_router_app(pool, affinity=affinity)
        async with TestClient(TestServer(router_app)) as client:
            for _ in range(6):
                resp = await client.post(
                    "/detect", json={"image_urls": [URLS[0]]}
                )
                assert resp.status == 200
        computed = sum(d.engine.calls for d in dets)
        hits = sum(
            d.engine.metrics.snapshot()["cache_hits_total"] for d in dets
        )
        fetched = sorted(d.client.fetches for d in dets)
        await _stop_fleet(dets, servers)
        return computed, hits, fetched

    computed, hits, fetched = asyncio.run(run())
    if affinity:
        assert (computed, hits, fetched) == (1, 5, [0, 0, 6])
    else:
        assert (computed, hits, fetched) == (3, 3, [2, 2, 2])


def test_router_prometheus_exposition_carries_wire_gauges():
    async def run():
        dets, servers, urls = await _start_fleet(1)
        pool = ReplicaPool(urls, health_interval_s=0.2)
        router_app = make_router_app(pool, affinity=True)
        async with TestClient(TestServer(router_app)) as client:
            await client.post("/detect", json={"image_urls": [URLS[0]]})
            text = await (
                await client.get("/metrics?format=prometheus")
            ).text()
            for needle in (
                "spotter_tpu_wire_bytes_in_total",
                "spotter_tpu_wire_bytes_out_total",
                "spotter_tpu_affinity_hit_rate",
                "spotter_tpu_edge_negative_hits_total",
                "spotter_tpu_affinity_ring_members",
            ):
                assert needle in text, f"{needle} missing from exposition"
        await _stop_fleet(dets, servers)

    asyncio.run(run())
