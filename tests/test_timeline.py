"""The span timeline (`obs.Timeline`, `/metrics` `host_timeline`), the
benchmark's join of it to a device trace (`benchmarks/metrics/_timeline.py`)
and the compile cache's counters. CPU; the trace is synthetic."""

import asyncio
import gc
import os
import sys

import numpy as np
import pytest

from spotter_tpu import obs
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.obs import aggregate, prom
from spotter_tpu.obs import trace as obs_trace

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                "benchmarks", "metrics"))
import _timeline  # noqa: E402


@pytest.fixture(autouse=True)
def timeline_off():
    obs.disable_timeline()
    obs_trace.reset_host_spans()
    yield
    obs.disable_timeline()
    obs_trace.reset_host_spans()


def _entries(snapshot):
    names = snapshot["names"]
    return [(names[n], t0, t1, batch) for n, _, t0, t1, batch in snapshot["entries"]]


# ---------------------------------------------------------------------------
# the sink


def test_off_by_default_allocates_nothing():
    assert obs.timeline_snapshot() is None
    before = obs.trace_stats()
    for _ in range(50):
        with obs.span("engine.decode", obs.batch_traces(), stage=obs.DECODE, batch=1):
            pass
        obs.record_span("batcher.queue_wait", 0.001, start=1.0)
    assert obs_trace._timeline is None and obs.trace_stats() == before
    assert obs_trace._on_gc not in gc.callbacks
    assert "host_timeline" not in Metrics().snapshot()


def test_the_profiler_service_turns_it_on(monkeypatch):
    from spotter_tpu.engine import profiler

    started = []
    monkeypatch.setattr(profiler.jax.profiler, "start_server", started.append)
    monkeypatch.setattr(profiler, "_server_started", False)
    monkeypatch.setenv(profiler.PROFILER_PORT_ENV, "40123")
    assert profiler.maybe_start_profiler_server() == 40123
    assert started == [40123] and obs.timeline_snapshot() is not None
    assert obs_trace._on_gc in gc.callbacks


def test_spans_with_an_await_land_with_their_batch():
    obs.enable_timeline()

    async def handler():
        with obs.span("detector.image", obs.NO_TRACE):
            await asyncio.sleep(0.02)
        with obs.span("engine.dispatch", obs.NO_TRACE, batch=7, bucket=8):
            pass

    asyncio.run(handler())
    obs.record_span("batcher.queue_wait", 0.25, start=obs_trace._now() - 0.25)
    rows = {name: (t0, t1, batch) for name, t0, t1, batch in _entries(obs.timeline_snapshot())
            if name != obs_trace.GC_SPAN}
    assert set(rows) == {"detector.image", "engine.dispatch", "batcher.queue_wait"}
    t0, t1, batch = rows["detector.image"]
    assert t1 - t0 >= 20_000 and batch is None  # microseconds
    assert rows["engine.dispatch"][2] == 7
    assert rows["batcher.queue_wait"][1] - rows["batcher.queue_wait"][0] == pytest.approx(250_000, abs=2)
    assert obs.host_spans_snapshot()["detector.image"]["count"] == 1  # and the table


def test_collector_pauses_only_while_on():
    gc.collect()
    assert obs_trace.GC_SPAN not in obs.host_spans_snapshot()
    obs.enable_timeline()
    gc.collect()
    pauses = [e for e in _entries(obs.timeline_snapshot()) if e[0] == obs_trace.GC_SPAN]
    assert pauses and all(t1 >= t0 for _, t0, t1, _ in pauses)
    assert obs.host_spans_snapshot()[obs_trace.GC_SPAN]["count"] >= 1
    obs.disable_timeline()
    assert obs_trace._on_gc not in gc.callbacks


def test_ring_keeps_the_last_seconds_and_its_count(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(obs_trace, "_now", lambda: now[0])
    timeline = obs.Timeline(seconds=10.0, entries=4)
    for i in range(3):
        timeline.add("a", 100.0 + i, 101.0 + i)
    now[0] = 112.5  # the first stamp ended 11.5 s ago
    snap = timeline.snapshot()
    assert [e[3] for e in snap["entries"]] == [103_000_000]
    assert snap["complete_from_us"] == 102_500_000
    for i in range(3, 8):
        timeline.add("a", 100.0 + i, 101.0 + i)
    snap = timeline.snapshot()  # four kept; the ring is complete from the oldest
    assert [e[3] for e in snap["entries"]] == [105_000_000, 106_000_000, 107_000_000, 108_000_000]
    assert snap["complete_from_us"] == 105_000_000


def test_metrics_json_only_and_no_fleet_merge():
    obs.enable_timeline()
    with obs.span("engine.put", obs.NO_TRACE, batch=1):
        pass
    m = Metrics()
    m.record_batch(3, 0.01, stages={"decode": 0.004}, bucket=4)
    snap = m.snapshot()
    assert "engine.put" in snap["host_timeline"]["names"]
    text = prom.render(snap)
    assert text == prom.render({k: v for k, v in snap.items() if k != "host_timeline"})
    assert "timeline" not in text
    assert not [k for k in aggregate.flatten_counters(snap) if k.startswith("host_timeline")]


# ---------------------------------------------------------------------------
# the join (benchmarks/metrics/_timeline.py) on a known trace

OFFSET = 1000.0  # host seconds = window seconds + OFFSET
RUNS = [(0.5, 1.5), (2.0, 3.0), (4.0, 5.0), (6.0, 7.0)]


def _known(runs=RUNS, complete_from=OFFSET - 30.0):
    """A capture of 10 s with `runs`, and a timeline whose idle stretches
    hold known spans (written here in window seconds): each batch dispatched
    0.2 s before its run, waited on from 0.1 s after its start, its result
    0.5 ms after its end; one batch before the capture, whose run is not in
    it."""
    names = ["engine.dispatch", "engine.device_wait", "engine.postprocess", "python.gc",
             "engine.h2d", "detector.image"]
    spans = [(0, -3.2, -3.1, 99), (1, -3.0, -2.4995, 99)]
    for b, (s, e) in enumerate(runs):
        spans += [(0, s - 0.2, s - 0.1, b), (1, s + 0.1, e + 0.0005, b)]
    spans += [(2, 7.0005, 7.2, None),  # the last result taken off
              (3, 3.2, 3.3, None), (4, 3.25, 3.35, None),  # gc over staging
              (3, 1.0, 1.1, None),  # a pause under a run: no idle time
              (5, 7.1, 9.0, None)]
    entries = [[n, 0, round((t0 + OFFSET) * 1e6), round((t1 + OFFSET) * 1e6), b]
               for n, t0, t1, b in spans]
    trace = {"devices": 1, "window_s": 10.0,
             "program_runs": [{"plane": "/device:TPU:0", "name": "jit_forward.8", "start_s": s, "end_s": e}
                              for s, e in runs]}
    timeline = {"names": names, "entries": entries, "now_us": round((OFFSET + 20.0) * 1e6),
                "complete_from_us": round(complete_from * 1e6)}
    return {"trace": trace, "metrics_after": {"host_timeline": timeline}}


def test_join_recovers_the_offset_and_each_class():
    ctx = _known()
    shares, info = _timeline.classify(ctx["trace"], ctx["metrics_after"]["host_timeline"])
    assert info["offset_s"] == pytest.approx(OFFSET, abs=1e-3)
    assert info["anchors"] == 4 and info["residual_ms"] <= 2.0
    # idle: 6 s of 10; gc 0.1 (before the staging span under it), staging
    # 0.4 of dispatches + 0.05 of h2d, result the last postprocess's 0.2,
    # upstream the image's 1.8 after it, and the rest nobody's
    want = {"gc": 1.0, "staging": 4.5, "result": 2.0, "upstream": 18.0, "unseen": 34.5}
    for cls, pct in want.items():
        assert shares[cls] == pytest.approx(pct, abs=0.02), cls
        assert _timeline.share(ctx, cls) == pytest.approx(pct, abs=0.02)
    assert sum(shares.values()) == pytest.approx(info["idle_pct"]) == pytest.approx(60.0)


@pytest.mark.parametrize("case", ["two_anchors", "ring_after_the_window", "no_timeline"])
def test_join_declines(case):
    ctx = {"two_anchors": lambda: _known(runs=RUNS[:2]),
           "ring_after_the_window": lambda: _known(complete_from=OFFSET + 1.0),
           "no_timeline": lambda: dict(_known(), metrics_after={})}[case]()
    assert _timeline.share(ctx, "unseen") is None


# ---------------------------------------------------------------------------
# the compile cache's counters


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))  # placed from outside
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    try:
        yield jax
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_compile_cache_counts_a_miss_then_a_hit(compile_cache):
    from spotter_tpu.serving import lifecycle

    jax = compile_cache
    lifecycle.enable_compile_cache()
    lifecycle.enable_compile_cache()  # one listener, however often it is armed

    def affine(x):
        return x * 3.0 + 1.0

    start = lifecycle.compile_cache_totals()
    jax.jit(affine)(np.ones(3, np.float32)).block_until_ready()
    first = lifecycle.compile_cache_totals()
    assert first["compile_cache_misses_total"] == start["compile_cache_misses_total"] + 1
    assert first["compile_cache_hits_total"] == start["compile_cache_hits_total"]
    jax.clear_caches()
    jax.jit(affine)(np.ones(3, np.float32)).block_until_ready()
    again = lifecycle.compile_cache_totals()
    assert again["compile_cache_hits_total"] == first["compile_cache_hits_total"] + 1
    assert again["compile_cache_misses_total"] == first["compile_cache_misses_total"]
