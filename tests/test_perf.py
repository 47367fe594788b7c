"""Device-efficiency plane (ISSUE 10): MFU/duty-cycle accounting, the
compile ledger, HBM telemetry, SLO burn-rate, and the /debug/perf surface.

The zero-traffic cases are acceptance criteria in their own right: every
perf gauge must be present and NaN-free on an idle replica, because a
scraper hits /metrics whether or not traffic ever arrived.
"""

import asyncio
import math
import os
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

os.environ["SPOTTER_TPU_TINY"] = "1"

from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.obs import perf as perf_mod
from spotter_tpu.obs import prom
from spotter_tpu.obs.perf import (
    CompileLedger,
    PerfLedger,
    SloBurn,
    peak_tflops_for,
    sample_hbm_once,
)
from spotter_tpu.serving.detector import AmenitiesDetector
from spotter_tpu.serving.standalone import make_app
from spotter_tpu.testing import faults
from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient


def _walk_numbers(obj, path=""):
    if isinstance(obj, dict):
        for k, v in obj.items():
            yield from _walk_numbers(v, f"{path}.{k}")
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            yield from _walk_numbers(v, f"{path}[{i}]")
    elif isinstance(obj, float):
        yield path, obj


# ---------------------------------------------------------------------------
# zero-traffic safety (acceptance: idle snapshots are well-formed)


def test_zero_traffic_snapshot_is_present_and_nan_free():
    snap = Metrics().snapshot()
    for key in (
        "mfu_pct", "useful_mfu_pct", "device_duty_cycle_pct",
        "compiles_total", "compile_seconds_total",
        "program_cache_hits_total", "hbm_bytes_in_use", "hbm_peak_bytes",
        "hbm_limit_bytes", "slo_target_pct", "slo_burn_rate",
    ):
        assert key in snap, key
    assert snap["mfu_pct"] == 0.0
    assert snap["useful_mfu_pct"] == 0.0
    assert snap["device_duty_cycle_pct"] == 0.0
    assert snap["compiles_total"] == 0
    assert snap["compile_seconds_total"] == 0.0
    assert snap["hbm_bytes_in_use"] == 0
    assert snap["slo_burn_rate"] == {"fast": 0.0, "slow": 0.0}
    for path, value in _walk_numbers(snap):
        assert not math.isnan(value), f"NaN at {path}"


def test_zero_traffic_prometheus_render_has_perf_gauges():
    text = prom.render(Metrics().snapshot())
    assert "spotter_tpu_mfu_pct 0.0" in text
    assert "spotter_tpu_useful_mfu_pct 0.0" in text
    assert "spotter_tpu_device_duty_cycle_pct 0.0" in text
    assert "spotter_tpu_compiles_total 0" in text
    assert "# TYPE spotter_tpu_compiles_total counter" in text
    assert "spotter_tpu_hbm_bytes_in_use 0" in text
    assert 'spotter_tpu_slo_burn_rate{window="fast"} 0.0' in text
    assert 'spotter_tpu_slo_burn_rate{window="slow"} 0.0' in text
    assert "nan" not in text.lower()


# ---------------------------------------------------------------------------
# unit: SLO burn-rate


def test_slo_burn_idle_is_zero():
    burn = SloBurn(target_pct=99.0)
    assert burn.burn(60.0) == 0.0
    assert burn.rates() == {"fast": 0.0, "slow": 0.0}


def test_slo_burn_math():
    burn = SloBurn(target_pct=99.0)  # budget = 1%
    burn.good(99)
    burn.bad(1)  # error ratio 1% -> burn exactly 1.0
    assert burn.burn(60.0) == pytest.approx(1.0)
    burn.bad(100)  # ratio 101/200 -> burn ~50x
    assert burn.burn(60.0) == pytest.approx((101 / 200) / 0.01)
    block = burn.block()
    assert block["target_pct"] == 99.0
    assert block["fast"]["good"] == 99 and block["fast"]["bad"] == 101
    assert block["fast"]["burn_rate"] == pytest.approx(50.5, abs=0.1)


def test_slo_target_env_and_100pct_clamp(monkeypatch):
    monkeypatch.setenv(perf_mod.SLO_TARGET_PCT_ENV, "99.9")
    burn = SloBurn()
    assert burn.target_pct == 99.9
    burn.good(999)
    burn.bad(1)  # ratio 0.1% against a 0.1% budget -> 1.0
    assert burn.burn(60.0) == pytest.approx(1.0, rel=0.01)
    # a 100% target must not divide by zero
    b2 = SloBurn(target_pct=100.0)
    b2.bad(1)
    assert math.isfinite(b2.burn(60.0))


def test_sheds_and_deadline_misses_feed_the_burn():
    m = Metrics()
    m.record_batch(8, 0.01)
    m.record_shed(2)
    m.record_deadline_exceeded(1)
    block = m.perf.slo.block()
    assert block["fast"]["good"] == 8
    assert block["fast"]["bad"] == 3
    assert m.snapshot()["slo_burn_rate"]["fast"] > 0.0


# ---------------------------------------------------------------------------
# unit: peak-TFLOPs resolution


def test_peak_tflops_autodetect(monkeypatch):
    monkeypatch.delenv(perf_mod.PEAK_TFLOPS_ENV, raising=False)
    assert peak_tflops_for("TPU v5 lite") == 197.0
    assert peak_tflops_for("TPU v5e") == 197.0
    assert peak_tflops_for("TPU v5p") == 459.0
    assert peak_tflops_for("TPU v4") == 275.0
    assert peak_tflops_for("TPU v6 lite") == 918.0
    # no CPU row and no catch-all generation: an unknown device has no peak
    assert peak_tflops_for("cpu") is None
    assert peak_tflops_for("TPU v5") is None
    assert peak_tflops_for("weird-new-chip") is None
    assert peak_tflops_for(None) is None
    monkeypatch.setenv(perf_mod.PEAK_TFLOPS_ENV, "123.5")
    assert peak_tflops_for("TPU v5e") == 123.5  # env override wins
    monkeypatch.setenv(perf_mod.PEAK_TFLOPS_ENV, "not-a-number")
    assert peak_tflops_for("TPU v5e") == 197.0  # bad env falls through


# ---------------------------------------------------------------------------
# unit: PerfLedger MFU / duty-cycle math


def _aged_ledger(**kwargs) -> PerfLedger:
    ledger = PerfLedger(**kwargs)
    # age the ledger so the trailing window spans exactly window_s and the
    # rate math is deterministic
    ledger._created = time.monotonic() - 2 * ledger.window_s
    return ledger


def test_mfu_and_duty_cycle_math(monkeypatch):
    monkeypatch.setenv(perf_mod.PEAK_TFLOPS_ENV, "0.000001")  # 1e6 FLOP/s
    ledger = _aged_ledger(window_s=60.0, enabled=True)
    ledger.set_device_info("test-chip", 1)
    assert ledger.peak_tflops == 1e-6
    # 6e6 FLOPs over a 60 s window against 1e6 FLOP/s peak = 10% MFU;
    # half the pixels valid -> useful MFU 5%; 3 s device time -> 5% duty
    ledger.record_dispatch(
        device_s=3.0, batch=4, padded_px=100, valid_px=50, flops=6e6,
        trace_id="t-1", shape="s",
    )
    snap = ledger.snapshot()
    assert snap["mfu_pct"] == pytest.approx(10.0, rel=0.01)
    assert snap["useful_mfu_pct"] == pytest.approx(5.0, rel=0.01)
    assert snap["device_duty_cycle_pct"] == pytest.approx(5.0, rel=0.01)


def test_mfu_zero_when_peak_unknown():
    ledger = _aged_ledger(window_s=60.0, enabled=True)
    ledger.set_device_info("mystery-accelerator", 2)
    ledger.record_dispatch(device_s=1.0, batch=2, flops=1e9)
    snap = ledger.snapshot()
    assert snap["peak_tflops"] is None
    assert snap["mfu_pct"] == 0.0  # never NaN, never a made-up number
    assert snap["device_duty_cycle_pct"] > 0.0  # duty needs no peak


def test_perf_ledger_disabled_is_noop(monkeypatch):
    monkeypatch.setenv(perf_mod.PERF_LEDGER_ENV, "0")
    ledger = PerfLedger()
    assert not ledger.enabled
    ledger.record_dispatch(device_s=1.0, batch=2, flops=1e9)
    snap = ledger.snapshot()
    assert snap["mfu_pct"] == 0.0 and snap["device_duty_cycle_pct"] == 0.0
    assert ledger.top_dispatches() == []


def test_top_dispatches_bounded_and_sorted():
    ledger = _aged_ledger(window_s=60.0, enabled=True, top_k=3)
    for i in range(10):
        ledger.record_dispatch(
            device_s=i / 1000.0, batch=1, trace_id=f"t-{i}", shape="s"
        )
    top = ledger.top_dispatches()
    assert len(top) == 3
    assert [e["trace_id"] for e in top] == ["t-9", "t-8", "t-7"]
    assert top[0]["device_ms"] >= top[1]["device_ms"] >= top[2]["device_ms"]


def test_flops_for_caches_failures():
    ledger = PerfLedger(enabled=True)
    calls = []

    def boom():
        calls.append(1)
        raise RuntimeError("cost analysis broken")

    assert ledger.flops_for("s", boom) is None
    assert ledger.flops_for("s", boom) is None  # cached: no second attempt
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# unit: kernel FLOPs ledger (ISSUE 18 — pallas calls cost 0 under XLA)


def test_note_kernel_flops_collector_scoping():
    from spotter_tpu.obs.perf import collect_kernel_flops, note_kernel_flops

    note_kernel_flops("orphan", 123.0)  # no collector active: dropped
    with collect_kernel_flops() as outer:
        note_kernel_flops("msda_fused", 100.0)
        with collect_kernel_flops() as inner:
            note_kernel_flops("msda_fused", 50.0)
            note_kernel_flops("owl_class_logits", 7.0)
        note_kernel_flops("bad", float("nan"))  # rejected
        note_kernel_flops("bad", -1)  # rejected
        note_kernel_flops("bad", "x")  # rejected
    assert inner == {"msda_fused": 50.0, "owl_class_logits": 7.0, "__total__": 57.0}
    assert outer["msda_fused"] == 150.0 and outer["__total__"] == 157.0
    assert "bad" not in outer and "orphan" not in outer


def test_combine_flops_rules():
    from spotter_tpu.obs.perf import combine_flops

    # cost_analysis empty -> manual total stands alone (None when both empty)
    assert combine_flops(None, None) is None
    assert combine_flops(0, 0.0) is None
    assert combine_flops(None, 5e6) == 5e6
    # ca below the manual total: XLA missed the custom calls -> add
    assert combine_flops(1e6, 5e6) == 6e6
    # ca at/above the manual total: already counted -> trust ca
    assert combine_flops(5e6, 5e6) == 5e6
    assert combine_flops(9e6, 5e6) == 9e6
    # garbage inputs degrade, never raise
    assert combine_flops(float("nan"), 5e6) == 5e6
    assert combine_flops("junk", None) is None
    assert combine_flops(1e6, float("inf")) == 1e6


# ---------------------------------------------------------------------------
# unit: compile ledger


def test_compile_ledger_hits_and_table():
    ledger = CompileLedger(storm_threshold=100)
    assert ledger.record_dispatch("a") is True
    ledger.record_compile("a", 0.5, "warmup")
    assert ledger.record_dispatch("a") is False  # steady state: a hit
    assert ledger.record_dispatch("a") is False
    snap = ledger.snapshot()
    assert snap["compiles_total"] == 1
    assert snap["compile_seconds_total"] == pytest.approx(0.5)
    assert snap["program_cache_hits_total"] == 2
    (entry,) = snap["compile_shapes"]
    assert entry["shape"] == "a" and entry["source"] == "warmup"
    assert entry["count"] == 1


def test_compile_storm_warning(caplog):
    ledger = CompileLedger(storm_threshold=2)
    with caplog.at_level("WARNING", logger="spotter_tpu.obs.perf"):
        for i in range(4):
            ledger.record_dispatch(f"shape-{i}")
            ledger.record_compile(f"shape-{i}", 0.01, "traffic")
    assert any("recompile storm" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# unit: HBM telemetry


class _FakeDevice:
    def __init__(self, dev_id, stats):
        self.id = dev_id
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_hbm_sample_none_safe_and_sums():
    ledger = PerfLedger(enabled=True)
    devices = [
        _FakeDevice(0, None),  # CPU backends return None
        _FakeDevice(1, {"bytes_in_use": 10, "peak_bytes_in_use": 20,
                        "bytes_limit": 100}),
        _FakeDevice(2, {"bytes_in_use": 5, "peak_bytes_in_use": 6,
                        "bytes_limit": 100}),
        _FakeDevice(3, RuntimeError("backend gone")),
    ]
    assert sample_hbm_once(lambda: devices, ledger) == 2
    snap = ledger.snapshot()
    assert snap["hbm_bytes_in_use"] == 15
    assert snap["hbm_peak_bytes"] == 26
    assert snap["hbm_limit_bytes"] == 200
    assert snap["hbm_per_device"]["1"]["bytes_in_use"] == 10
    text = prom.render(snap)
    assert (
        'spotter_tpu_hbm_per_device{device="1",stat="bytes_in_use"} 10'
        in text
    )


def test_hbm_sampler_thread_start_stop():
    ledger = PerfLedger(enabled=True)
    devices = [_FakeDevice(0, {"bytes_in_use": 7, "peak_bytes_in_use": 7,
                               "bytes_limit": 10})]
    sampler = perf_mod.HbmSampler(lambda: devices, ledger, interval_s=0.01)
    assert sampler.start()
    try:
        deadline = time.monotonic() + 2.0
        while (
            ledger.snapshot()["hbm_bytes_in_use"] == 0
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
    finally:
        sampler.stop()
    assert ledger.snapshot()["hbm_bytes_in_use"] == 7
    disabled = perf_mod.HbmSampler(lambda: devices, ledger, interval_s=0)
    assert not disabled.start()  # interval 0 = off


# ---------------------------------------------------------------------------
# engine integration (tiny models, real jit on CPU)


@pytest.fixture(scope="module")
def rtdetr_engine():
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    built = build_detector("PekingU/rtdetr_v2_r18vd")
    engine = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2))
    engine.warmup()
    return engine


def test_warmup_fills_compile_ledger_then_steady_state_hits(rtdetr_engine):
    """Acceptance: the ledger counts exactly the warmup programs, and
    steady-state traffic never adds a compile — only cache hits."""
    snap = rtdetr_engine.metrics.snapshot()
    assert snap["compiles_total"] == 2  # one program per bucket
    assert {e["source"] for e in snap["compile_shapes"]} == {"warmup"}
    assert snap["compile_seconds_total"] > 0.0
    hits_before = snap["program_cache_hits_total"]
    img = Image.fromarray(np.full((48, 64, 3), 128, np.uint8))
    for _ in range(3):
        rtdetr_engine.detect([img, img])
    snap = rtdetr_engine.metrics.snapshot()
    assert snap["compiles_total"] == 2  # test-asserted: no recompiles
    assert snap["program_cache_hits_total"] >= hits_before + 3


def test_engine_dispatches_land_in_mfu_ledger(rtdetr_engine):
    img = Image.fromarray(np.full((48, 64, 3), 128, np.uint8))
    rtdetr_engine.detect([img])
    snap = rtdetr_engine.metrics.snapshot()
    assert snap["device_kind"] == "cpu"
    # a CPU run measures no MFU: no peak for it, so no number under the
    # device metric's name
    assert snap["peak_tflops"] is None
    assert snap["mfu_pct"] == 0.0 and snap["useful_mfu_pct"] == 0.0
    assert snap["device_duty_cycle_pct"] > 0.0
    assert snap["perf_raw"]["flops"] > 0.0  # cost-analysis FLOPs resolved
    top = rtdetr_engine.metrics.perf.top_dispatches()
    assert top and top[0]["flops"] and top[0]["flops"] > 0


def test_oom_downgrade_shows_up_in_the_ledger(tiny_built_rtdetr):
    """Acceptance: an OOM-downgrade path produces new ledger entries (the
    halves' bucket compiles tagged oom_downgrade)."""
    from spotter_tpu.engine.engine import InferenceEngine

    engine = InferenceEngine(
        tiny_built_rtdetr, threshold=0.0, batch_buckets=(2, 4)
    )
    rng = np.random.default_rng(3)
    images = [
        Image.fromarray(rng.integers(0, 255, (48, 64, 3), dtype=np.uint8))
        for _ in range(4)
    ]
    with faults.inject(engine_oom=1):
        results = engine.detect(images)
    assert len(results) == 4
    snap = engine.metrics.snapshot()
    sources = {e["source"] for e in snap["compile_shapes"]}
    assert "oom_downgrade" in sources
    assert engine.metrics.snapshot()["batch_retries_total"] >= 1


@pytest.fixture(scope="module")
def tiny_built_rtdetr():
    from spotter_tpu.models import build_detector

    return build_detector("PekingU/rtdetr_v2_r18vd")


def test_ragged_canvas_snap_compiles_once():
    """Acceptance: a ragged sub-bucket canvas is ONE new compile-ledger
    entry on first use, then a cache hit — the bounded-compile-count
    invariant (PR 9) as an observable."""
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    built = build_detector("facebook/detr-resnet-50")
    engine = InferenceEngine(
        built, threshold=0.0, batch_buckets=(2,), device_preprocess=True
    )
    engine.warmup()
    base = engine.metrics.snapshot()["compiles_total"]
    imgs = [
        # (80, 60) resizes to exactly the (64, 48) canvas; the extreme
        # (160, 60) aspect lands at (64, 24) — real padding waste, so the
        # valid/padded split below has something to discount
        Image.fromarray(np.full((80, 60, 3), 90, np.uint8)),
        Image.fromarray(np.full((160, 60, 3), 90, np.uint8)),
    ]
    engine.detect(imgs, canvas_hw=(64, 48))
    snap = engine.metrics.snapshot()
    assert snap["compiles_total"] == base + 1
    assert any(
        e["source"] == "traffic" and "64x48" in e["shape"]
        for e in snap["compile_shapes"]
    )
    hits = snap["program_cache_hits_total"]
    engine.detect(imgs, canvas_hw=(64, 48))  # steady state: no recompile
    snap = engine.metrics.snapshot()
    assert snap["compiles_total"] == base + 1
    assert snap["program_cache_hits_total"] == hits + 1
    # useful MFU discounts padding: the ragged dispatch recorded real pad
    # waste (valid < padded), so the weighted series sits at or below raw
    # MFU (at this tiny scale the rounded gauges may collapse — assert on
    # the per-dispatch record the weighting derives from)
    assert snap["useful_mfu_pct"] <= snap["mfu_pct"]
    ragged_top = [
        e for e in engine.metrics.perf.top_dispatches()
        if e["shape"] and "64x48" in e["shape"]
    ]
    assert ragged_top and all(
        e["valid_px"] < e["padded_px"] for e in ragged_top
    )


# ---------------------------------------------------------------------------
# HTTP surface: /debug/perf + /healthz slo_burn


def _stub_detector() -> AmenitiesDetector:
    engine = StubEngine()
    batcher = MicroBatcher(engine, max_delay_ms=2.0)
    return AmenitiesDetector(engine, batcher, StubHttpClient())


def test_debug_perf_endpoint_admin_gated(monkeypatch):
    monkeypatch.setenv("SPOTTER_TPU_ADMIN_TOKEN", "s3cret")

    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            resp = await client.post(
                "/detect", json={"image_urls": ["http://example.com/a.jpg"]}
            )
            assert resp.status == 200
            unauth = await client.get("/debug/perf")
            assert unauth.status == 401
            ok = await client.get(
                "/debug/perf", headers={"X-Admin-Token": "s3cret"}
            )
            assert ok.status == 200
            body = await ok.json()
            for key in ("top_dispatches", "compile_shapes", "slo_burn",
                        "mfu_pct", "device_duty_cycle_pct",
                        "hbm_bytes_in_use"):
                assert key in body, key
            assert body["top_dispatches"], "stub dispatch must be recorded"
            assert body["top_dispatches"][0]["device_ms"] >= 0.0
            assert body["slo_burn"]["fast"]["good"] >= 1
            bad_k = await client.get(
                "/debug/perf?k=zap", headers={"X-Admin-Token": "s3cret"}
            )
            assert bad_k.status == 400

    asyncio.run(run())


def test_healthz_reports_slo_burn_block():
    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            body = await (await client.get("/healthz")).json()
            burn = body["slo_burn"]
            assert burn["target_pct"] > 0
            assert burn["fast"]["burn_rate"] == 0.0
            assert burn["slow"]["window_s"] == 1800.0

    asyncio.run(run())


def test_metrics_surface_has_perf_gauges_json_and_prom():
    async def run():
        detector = _stub_detector()
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            await client.post(
                "/detect", json={"image_urls": ["http://example.com/a.jpg"]}
            )
            js = await (await client.get("/metrics")).json()
            for key in ("mfu_pct", "useful_mfu_pct",
                        "device_duty_cycle_pct", "compiles_total",
                        "compile_seconds_total", "hbm_bytes_in_use",
                        "slo_burn_rate"):
                assert key in js, key
            assert js["device_duty_cycle_pct"] >= 0.0
            text = await (
                await client.get("/metrics?format=prometheus")
            ).text()
            assert "spotter_tpu_mfu_pct" in text
            assert 'spotter_tpu_slo_burn_rate{window="fast"}' in text
            assert "# TYPE spotter_tpu_compiles_total counter" in text

    asyncio.run(run())
