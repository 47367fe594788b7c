"""The host timeline inside the program (ISSUE 26): `obs.span`'s three sinks,
the engine's live stage spans and their children, the per-bucket counters,
the starvation clock, the set-up phases, and the named scopes on the model's
sections. CPU, a model of four numbers: nothing here needs a real network."""

import contextlib
import re
import subprocess
import sys
import threading

import numpy as np
import pytest
from PIL import Image

from spotter_tpu import obs
from spotter_tpu.engine.engine import BuiltDetector, InferenceEngine
from spotter_tpu.engine.metrics import Metrics, StarvationClock
from spotter_tpu.obs import prom
from spotter_tpu.obs import trace as obs_trace
from spotter_tpu.ops.preprocess import DecodePool, PreprocessSpec

ENGINE_STAGE_KEYS = {"decode", "h2d", "device", "postprocess"}


class FakeClock:
    """Time that moves only when the test says so."""

    def __init__(self) -> None:
        self.now = 100.0

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> None:
        self.now += seconds


class _Annotation:
    """Stands where `jax.profiler.TraceAnnotation` stands in the server."""

    seen: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        _Annotation.seen.append(("enter", self.name, self.args))
        return self

    def __exit__(self, *exc):
        _Annotation.seen.append(("exit", self.name, self.args))


@pytest.fixture(autouse=True)
def clean_table():
    obs_trace.reset_host_spans()
    _Annotation.seen = []
    yield
    obs.set_annotator(None)
    obs_trace.reset_host_spans()


@pytest.fixture
def fake_clock(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(obs_trace, "_now", clock)
    return clock


class _FourNumberModel:
    """`module.apply` of two queries a picture: enough for the softmax
    postprocess, compiled in a blink."""

    def apply(self, variables, pixels):
        import jax.numpy as jnp

        b = pixels.shape[0]
        mean = pixels.mean(axis=(1, 2, 3))[:, None, None] * variables["params"]["w"]
        logits = jnp.zeros((b, 2, 3)) + mean
        boxes = jnp.full((b, 2, 4), 0.5)
        return {"logits": logits, "pred_boxes": boxes}


def _engine(buckets=(2, 4), workers=1) -> InferenceEngine:
    built = BuiltDetector(
        model_name="four-numbers",
        module=_FourNumberModel(),
        params={"w": np.ones((), np.float32)},
        preprocess_spec=PreprocessSpec(mode="fixed", size=(8, 8)),
        postprocess="softmax",
        id2label={0: "a", 1: "b"},
    )
    return InferenceEngine(
        built, threshold=0.0, batch_buckets=buckets,
        decode_pool=DecodePool(workers=workers),
    )


def _imgs(n):
    rng = np.random.default_rng(0)
    return [Image.fromarray(rng.integers(0, 255, (12, 10, 3), dtype=np.uint8))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# one helper, three sinks


@pytest.mark.parametrize("cpu", [False, True], ids=["loop-span", "pool-span"])
def test_span_reaches_trace_table_and_annotation(cpu):
    obs.set_annotator(_Annotation)
    tr = obs.begin_trace(request_id="r-sinks")
    with obs.span("engine.stack_pad", tr, annotate=True, cpu=cpu, batch=7, bucket=8):
        sum(i * i for i in range(20000))  # some CPU, so a pool span reads > 0
    # (a) the request trace, as a detail that names its batch
    (span,) = [s for s in tr.to_dict()["spans"] if s["name"] == "engine.stack_pad"]
    assert span["detail"] is True and span["batch"] == 7 and span["bucket"] == 8
    assert "engine.stack_pad" not in tr.stage_totals()
    # (b) the table: wall for every span, CPU only where the span asked
    row = obs.host_spans_snapshot()["engine.stack_pad"]
    assert row["count"] == 1 and row["wall_ms"] > 0.0
    assert (row["cpu_ms"] > 0.0) == cpu
    # (c) the annotation, open exactly while the span ran
    assert _Annotation.seen == [
        ("enter", "engine.stack_pad", {"batch": 7, "bucket": 8}),
        ("exit", "engine.stack_pad", {"batch": 7, "bucket": 8}),
    ]


def test_stage_span_keeps_the_stage_name_on_the_trace():
    tr = obs.begin_trace(request_id="r-stage")
    with obs.span("detector.fetch", tr, stage=obs.FETCH):
        pass
    assert obs.FETCH in tr.stage_totals()  # Server-Timing still says `fetch`
    assert "detector.fetch" in obs.host_spans_snapshot()  # the table: its own
    assert _Annotation.seen == []  # a wait is no annotation


def test_unannotated_span_and_no_annotator_are_quiet():
    with obs.span("detector.fetch", annotate=False):
        pass
    with obs.span("engine.put", annotate=True):  # nothing installed
        pass
    assert _Annotation.seen == []
    assert set(obs.host_spans_snapshot()) == {"detector.fetch", "engine.put"}


def test_no_trace_path_allocates_nothing():
    obs.set_current_trace(None)
    before = obs.trace_stats()
    for _ in range(50):
        with obs.span("engine.decode", obs.batch_traces(), stage=obs.DECODE, batch=1):
            with obs.span("engine.stack_pad", obs.batch_traces(), annotate=True):
                pass
    assert obs.trace_stats() == before
    assert obs.host_spans_snapshot()["engine.decode"]["count"] == 50


def test_batch_fanout_stage_to_each_image_detail_once_a_request():
    a = obs.begin_trace(request_id="a")
    b = obs.begin_trace(request_id="b")
    obs.set_batch_traces([a, a, a, b])  # one entry per image
    try:
        with obs.span("engine.decode", obs.batch_traces(), stage=obs.DECODE):
            with obs.span("engine.stack_pad", obs.batch_traces()):
                pass
    finally:
        obs.set_batch_traces([])
    names = lambda t: [s.name for s in t.spans]  # noqa: E731
    assert names(a).count(obs.DECODE) == 3 and names(b).count(obs.DECODE) == 1
    assert names(a).count("engine.stack_pad") == 1 == names(b).count("engine.stack_pad")


def test_obs_trace_imports_without_jax():
    code = ("import sys; from spotter_tpu.obs import trace; "
            "with trace.span('x', annotate=True): pass; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    code = code.replace("; with", "\nwith")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_prometheus_view_labels_the_new_keys():
    obs.record_span("engine.put", 0.002, count=2)
    m = Metrics()
    m.record_batch(3, 0.01, stages={"decode": 0.004}, bucket=4)
    text = prom.render(m.snapshot())
    assert 'spotter_tpu_host_spans{span="engine.put",stat="count"} 2' in text
    assert 'spotter_tpu_bucket_batches_total{bucket="4"} 1' in text
    assert "spotter_tpu_slots_total 4" in text
    assert "spotter_tpu_starved_staging_s_total" in text


# ---------------------------------------------------------------------------
# the engine's stages, live, and what they are made of


def test_decode_and_h2d_children_tile_their_parents(fake_clock, monkeypatch):
    eng = _engine()
    eng.warmup()
    obs_trace.reset_host_spans()
    pool_map, fill, put = eng._decode_pool.map, eng._fill_pad_rows, eng._put

    def slow_map(fn, items):
        fake_clock.advance(3.0)
        return pool_map(fn, items)

    def slow_fill(pixels, second, done):
        fake_clock.advance(0.5)
        return fill(pixels, second, done)

    def slow_put(arr):
        fake_clock.advance(0.25)
        return put(arr)

    monkeypatch.setattr(eng._decode_pool, "map", slow_map)
    monkeypatch.setattr(eng, "_fill_pad_rows", slow_fill)
    monkeypatch.setattr(eng, "_put", slow_put)
    assert len(eng.detect(_imgs(3))) == 3
    ms = {name: row["wall_ms"] for name, row in obs.host_spans_snapshot().items()}
    assert ms["engine.preprocess_map"] == pytest.approx(3000.0)
    assert ms["engine.stack_pad"] == pytest.approx(500.0)
    assert ms["engine.decode"] == pytest.approx(
        ms["engine.preprocess_map"] + ms["engine.stack_pad"])
    assert ms["engine.put"] == pytest.approx(750.0)  # pixels, masks, sizes
    assert ms["engine.h2d"] == pytest.approx(ms["engine.h2d_lock_wait"] + ms["engine.put"])
    assert ms["engine.h2d_lock_wait"] == 0.0  # nobody held the lock
    assert obs.host_spans_snapshot()["engine.preprocess_image"]["count"] == 3
    # and the stage histogram got the same seconds, under the names it had
    hist = eng.metrics.snapshot()["stage_ms_histogram"]
    assert set(hist) == ENGINE_STAGE_KEYS
    assert hist["decode"]["sum"] == pytest.approx(3500.0)
    assert hist["h2d"]["sum"] == pytest.approx(750.0)


def test_h2d_lock_wait_is_what_a_second_thread_held(fake_clock):
    eng = _engine()
    eng.warmup()
    obs_trace.reset_host_spans()
    eng._h2d_lock.acquire()

    def holder():
        # the engine thread is parked on the lock once its decode span closed
        while "engine.decode" not in obs.host_spans_snapshot():
            threading.Event().wait(0.005)
        fake_clock.advance(2.0)
        eng._h2d_lock.release()

    t = threading.Thread(target=holder)
    t.start()
    eng.detect(_imgs(2))
    t.join()
    ms = {name: row["wall_ms"] for name, row in obs.host_spans_snapshot().items()}
    assert ms["engine.h2d_lock_wait"] == pytest.approx(2000.0)
    # `h2d` keeps its meaning: end of staging to end of the puts, wait included
    assert ms["engine.h2d"] == pytest.approx(2000.0 + ms["engine.put"])


def test_engine_spans_name_their_batch_on_the_request_trace():
    eng = _engine()
    tr = obs.begin_trace(request_id="r-batch")
    obs.set_batch_traces([tr, tr])
    try:
        eng.detect(_imgs(2))
    finally:
        obs.set_batch_traces([])
    spans = tr.to_dict()["spans"]
    stage = [s for s in spans if not s.get("detail")]
    assert {s["name"] for s in stage} == ENGINE_STAGE_KEYS
    assert len({s["batch"] for s in spans}) == 1  # one batch caused them all
    assert {s["name"] for s in spans if s.get("detail")} >= {
        "engine.batch", "engine.preprocess_map", "engine.stack_pad",
        "engine.h2d_lock_wait", "engine.put", "engine.dispatch", "engine.device_wait",
    }


@pytest.mark.parametrize("sizes", [[1], [2, 3, 4], [5, 1, 2, 2]], ids=str)
def test_bucket_counters_agree_with_images_and_the_ladder(sizes):
    eng = _engine(buckets=(2, 4))
    for n in sizes:
        eng.detect(_imgs(n))
    snap = eng.metrics.snapshot()
    chunks = [c for n in sizes for c in ([4, n - 4] if n > 4 else [n])]
    want: dict = {}
    for c in chunks:
        want[str(eng.bucket_for(c))] = want.get(str(eng.bucket_for(c)), 0) + 1
    assert snap["bucket_batches_total"] == dict(sorted(want.items()))
    assert snap["images_total"] == sum(sizes)
    assert snap["batches_total"] == sum(snap["bucket_batches_total"].values())
    assert snap["slots_total"] == sum(int(b) * n for b, n in snap["bucket_batches_total"].items())
    assert set(snap["bucket_batches_total"]) <= {str(b) for b in eng.batch_buckets}


def test_stage_histogram_keys_are_the_ones_it_had():
    """Through the batcher, so `queue_wait` is there too: five keys, none of
    the detector's, no `fetch`."""
    import asyncio

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient

    async def run():
        engine = StubEngine()
        det = AmenitiesDetector(engine, MicroBatcher(engine, max_delay_ms=2.0), StubHttpClient())
        try:
            await det.detect({"image_urls": ["http://example.com/a.jpg", "http://example.com/b.jpg"]})
        finally:
            await det.aclose()
        # the house is empty again: every image that came in went out
        assert engine.metrics.starvation._upstream == 0
        return engine.metrics.snapshot()

    snap = asyncio.run(run())
    assert set(snap["stage_ms_histogram"]) == ENGINE_STAGE_KEYS | {"queue_wait"}
    spans = snap["host_spans"]
    assert {"detector.fetch", "detector.decode", "detector.pil_decode",
            "detector.draw_encode", "batcher.queue_wait", "engine.batch"} <= set(spans)
    assert spans["detector.pil_decode"]["count"] == 2 == spans["batcher.queue_wait"]["count"]
    assert snap["bucket_batches_total"] and snap["slots_total"] >= snap["images_total"] == 2


# ---------------------------------------------------------------------------
# why the chip had nothing to do

CASES = {
    # name: (in_flight, staging, upstream) -> (staging seconds, upstream seconds) of 10
    "in_flight": ((1, 1, 1), (0.0, 0.0)),
    "staging": ((0, 2, 5), (10.0, 0.0)),
    "upstream": ((0, 0, 3), (0.0, 10.0)),
    "empty_house": ((0, 0, 0), (0.0, 0.0)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_starvation_clock(case):
    (in_flight, staging, upstream), want = CASES[case]
    clock = FakeClock()
    starved = StarvationClock(clock)
    clock.advance(7.0)  # an empty house books nothing
    starved.move(in_flight=in_flight, staging=staging, upstream=upstream)
    clock.advance(10.0)
    assert starved.totals() == pytest.approx(want)
    starved.move(in_flight=-in_flight, staging=-staging, upstream=-upstream)
    clock.advance(5.0)
    assert starved.totals() == pytest.approx(want)


def test_starvation_through_the_engine(fake_clock, monkeypatch):
    """Staging with nothing in flight is starved time; the same staging under
    a program in flight is not."""
    eng = _engine(buckets=(2,))
    eng.warmup()
    eng.metrics.starvation = StarvationClock(fake_clock)
    pool_map = eng._decode_pool.map

    def slow_map(fn, items):
        fake_clock.advance(1.0)
        return pool_map(fn, items)

    monkeypatch.setattr(eng._decode_pool, "map", slow_map)
    eng.detect(_imgs(4))  # two chunks, pipelined: the second stages under the first
    staging_s, upstream_s = eng.metrics.starvation.totals()
    assert staging_s == pytest.approx(1.0) and upstream_s == 0.0
    snap = eng.metrics.snapshot()
    assert snap["starved_staging_s_total"] == pytest.approx(1.0)
    assert snap["starved_upstream_s_total"] == 0.0


def test_failed_staging_leaves_the_clock_balanced(fake_clock, monkeypatch):
    eng = _engine(buckets=(2,))
    eng.metrics.starvation = StarvationClock(fake_clock)

    def boom(fn, items):
        raise ValueError("poisoned image")

    monkeypatch.setattr(eng._decode_pool, "map", boom)
    with pytest.raises(ValueError):
        eng.detect(_imgs(1))
    fake_clock.advance(9.0)
    assert eng.metrics.starvation.totals() == (0.0, 0.0)


# ---------------------------------------------------------------------------
# set-up phases, reserved bytes


def test_setup_phases_are_one_dict_in_metrics():
    eng = _engine(buckets=(2, 4))
    eng.warmup()
    phases = eng.metrics.snapshot()["setup_phases_s"]
    assert {"warmup.f32:2x8x8", "warmup.f32:4x8x8", "flops.f32:2x8x8", "flops.f32:4x8x8"} <= set(phases)
    assert all(seconds >= 0.0 for seconds in phases.values())
    shapes = {e["shape"]: e["wall_s"] for e in eng.metrics.snapshot()["compile_shapes"]}
    assert shapes["f32:2x8x8"] == pytest.approx(phases["warmup.f32:2x8x8"], abs=2e-3)


def test_hbm_rows_carry_what_the_programs_reserve():
    m = Metrics()
    m.perf.set_hbm("0", {"bytes_in_use": 5, "peak_bytes_in_use": 7, "bytes_limit": 100,
                         "bytes_reserved": 11, "peak_bytes_reserved": 13})
    m.perf.ensure_hbm_device("1")
    rows = m.snapshot()["hbm_per_device"]
    assert rows["0"]["bytes_reserved"] == 11 and rows["0"]["peak_bytes_reserved"] == 13
    assert rows["0"]["peak_bytes"] == 7  # the two it had mean what they meant
    assert rows["1"] == {"bytes_in_use": 0, "peak_bytes": 0, "limit_bytes": 0,
                         "bytes_reserved": 0, "peak_bytes_reserved": 0}


def test_process_age_counts_from_before_this_module():
    from spotter_tpu.serving import lifecycle

    age = lifecycle.process_age_s()
    assert age > 0.0
    import time

    assert age >= time.monotonic() - lifecycle._PROCESS_START - 1e-3


# ---------------------------------------------------------------------------
# named scopes: metadata only

def _toy_yolos_lowered(jax, scoped: bool) -> tuple[str, str]:
    """(the lowered program with its op names left out, with them in)."""
    import jax.numpy as jnp

    from spotter_tpu.models.yolos import YolosDetector
    from spotter_tpu.models.zoo import tiny_yolos_config

    cfg = tiny_yolos_config()
    model = YolosDetector(cfg)
    x = jnp.zeros((1, *cfg.image_size, 3))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x))["params"]
    scope = jax.named_scope if scoped else (lambda name: contextlib.nullcontext())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", scope)
        lowered = jax.jit(lambda p, x: model.apply({"params": p}, x)).lower(params, x)
    return lowered.as_text(), lowered.as_text(debug_info=True)


def _op_names(text: str) -> set:
    return set(re.findall(r'loc\("(jit[^"]*)"', text))


def test_named_scopes_change_nothing_but_op_names():
    import jax

    (program, named), (bare_program, bare_named) = (
        _toy_yolos_lowered(jax, True), _toy_yolos_lowered(jax, False))
    assert program == bare_program  # the lowered text, op names apart: the same
    assert len(program) > 10_000  # and it is the whole model
    names = _op_names(named)
    for section in ("/embed/", "/encoder/", "/mlp/", "/attention/", "/heads/"):
        assert any(section in name for name in names), section
    assert not any("/encoder/" in name for name in _op_names(bare_named))


def test_engine_program_names_its_postprocess():
    import jax

    eng = _engine(buckets=(2,))
    args = (jax.ShapeDtypeStruct((2, 8, 8, 3), np.float32),
            jax.ShapeDtypeStruct((2, 8, 8), np.float32),
            jax.ShapeDtypeStruct((2, 2), np.float32))
    text = eng._forward.lower(eng.params, *args).as_text(debug_info=True)
    assert any("/postprocess/" in name for name in _op_names(text))
