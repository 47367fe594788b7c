"""Ragged mixed-resolution scheduling (ISSUE 9, engine/scheduler.py).

Three contracts, per the acceptance criteria:
- masked-region invariance: padding pixels NEVER change detections (the
  uint8 valid-dims substrate zeroes them before the model sees anything);
- mixed-bucket parity: a ragged (sub-bucket) canvas on the scheduler's
  grid produces the per-bucket reference's detections, query for query, to
  float32 rounding (a canvas off the grid changes which tokens are valid,
  and `ragged_step` refuses a grid that allows one);
- deadline-slack ordering: under a saturated queue an slo arrival enters
  the next dispatch ahead of older bulk work.

Plus the opt-out: with SPOTTER_TPU_RAGGED unset the scheduler is FIFO and
the engine is called without any canvas — the pre-ISSUE-9 behavior.
"""

import asyncio
import os
import threading
import time

import numpy as np
import pytest
from PIL import Image
from staging_reference import stack_reference

os.environ["SPOTTER_TPU_TINY"] = "1"

from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.engine.scheduler import (
    COARSEST_FEATURE_STRIDE,
    QueueItem,
    Scheduler,
)
from spotter_tpu.ops.preprocess import (
    PreprocessSpec,
    decode_resize_uint8,
    ragged_canvas_supported,
    shortest_edge_size,
)
from spotter_tpu.serving.overload import BULK, SLO
from spotter_tpu.serving.resilience import Deadline

TINY_DETR_SPEC = PreprocessSpec(
    mode="shortest_edge", size=(48, 64),
    mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), pad_to=(64, 64),
)


def _img(h, w, seed=0):
    rng = np.random.default_rng(seed)
    return Image.fromarray(rng.integers(0, 255, size=(h, w, 3), dtype=np.uint8))


def _item(h, w, cls=SLO, deadline=None, t=0.0):
    return QueueItem(
        image=_img(h, w), fut=None, deadline=deadline, t_submit=t, cls=cls
    )


# --- scheduler unit tests (pure, no engine) --------------------------------


def test_fifo_plan_is_arrival_order_and_drains_buffer():
    s = Scheduler(spec=TINY_DETR_SPEC, ragged=False)
    items = [_item(30, 40, t=i) for i in range(5)]
    buf = list(items)
    plan = s.plan(buf, 4)
    assert [id(i) for i in plan.items] == [id(i) for i in items[:4]]
    assert plan.canvas_hw is None  # FIFO never passes a canvas
    assert len(buf) == 1 and buf[0] is items[4]
    # waste is still measured against the static bucket (the baseline view)
    assert plan.padding_waste_pct is not None and plan.padding_waste_pct > 0


def test_ragged_pack_prefers_fit_over_growth():
    """Backfill takes same-shape items before growing the canvas: the big
    straggler waits one dispatch, the pack stays small."""
    s = Scheduler(spec=TINY_DETR_SPEC, ragged=True, step=16)
    # portrait images resize to (64, 48); the full-bucket one to (48, 64)
    small = [_item(80, 60, t=i) for i in range(3)]
    big = _item(60, 80, t=1.5)  # arrives between small[1] and small[2]
    buf = [small[0], small[1], big, small[2]]
    plan = s.plan(buf, 3)
    assert big not in plan.items  # displaced by the fitting backfill
    assert plan.items == [small[0], small[1], small[2]]
    assert plan.canvas_hw == (64, 48)
    assert buf == [big]
    # the straggler seeds the next pack
    plan2 = s.plan(buf, 3)
    assert plan2.items == [big] and plan2.canvas_hw == (48, 64)


def test_ragged_pack_grows_canvas_to_fill_target():
    """A dispatch costs padded_batch x canvas FLOPs whether its slots are
    full or not — with too few same-shape items the canvas grows rather
    than dispatching a runt pack."""
    s = Scheduler(spec=TINY_DETR_SPEC, ragged=True, step=16)
    buf = [_item(80, 60, t=0), _item(60, 80, t=1)]
    plan = s.plan(buf, 2)
    assert len(plan.items) == 2 and buf == []
    assert plan.canvas_hw == (64, 64)  # covers both aspects


def test_ragged_urgent_deadline_is_mandatory():
    """An item whose slack shrank below urgent_ms enters the pack even when
    it forces canvas growth — packing never displaces urgency."""
    s = Scheduler(spec=TINY_DETR_SPEC, ragged=True, step=16, urgent_ms=100.0)
    urgent = _item(60, 80, deadline=Deadline.after(0.05), t=5.0)
    relaxed = [_item(80, 60, t=i) for i in range(3)]
    buf = relaxed + [urgent]
    plan = s.plan(buf, 2)
    assert urgent in plan.items
    assert plan.canvas_hw == (64, 64)


def test_priority_orders_slo_before_bulk_then_slack():
    s = Scheduler(spec=TINY_DETR_SPEC, ragged=True)
    now = time.monotonic()
    bulk_old = _item(30, 40, cls=BULK, t=0.0)
    slo_loose = _item(30, 40, cls=SLO, deadline=Deadline.after(10.0), t=2.0)
    slo_tight = _item(30, 40, cls=SLO, deadline=Deadline.after(0.5), t=3.0)
    order = sorted(
        [bulk_old, slo_loose, slo_tight], key=lambda it: s.priority_key(it, now)
    )
    assert order == [slo_tight, slo_loose, bulk_old]


def test_ragged_packs_pad_fewer_pixels_than_fifo_on_a_skewed_mix():
    """What the ragged policy is for, as a count: a Zipf-skewed mix of five
    shapes drained four at a time. Both policies dispatch every item once;
    FIFO pays the full bucket for every pack, the ragged packs pay their
    snapped canvases: 143,360 padded pixels against 196,608 on this seed,
    held here to a fifth fewer."""
    shapes = [(80, 60), (160, 60), (60, 160), (60, 80), (64, 64)]
    rng = np.random.default_rng(9)
    weights = 1.0 / np.arange(1, len(shapes) + 1) ** 1.2
    picks = rng.choice(len(shapes), size=48, p=weights / weights.sum())

    buckets = (1, 2, 4)

    def drain(ragged):
        s = Scheduler(spec=TINY_DETR_SPEC, ragged=ragged, step=16)
        buf = [
            _item(*shapes[k], cls=BULK, t=float(i)) for i, k in enumerate(picks)
        ]
        seen, padded_px = [], 0
        while buf:
            plan = s.plan(buf, 4, buckets=buckets)
            ch, cw = plan.canvas_hw or TINY_DETR_SPEC.pad_to
            padded_px += s._padded_batch(len(plan.items), buckets) * ch * cw
            seen += [id(it) for it in plan.items]
        assert len(seen) == len(set(seen)) == len(picks)
        return padded_px

    fifo, ragged = drain(False), drain(True)
    assert fifo == 12 * 4 * 64 * 64
    assert ragged <= 0.8 * fifo, (ragged, fifo)


def test_canvas_snap_caps_at_static_bucket():
    s = Scheduler(spec=TINY_DETR_SPEC, ragged=True, step=48)
    assert s._snap((50, 50)) == (64, 64)  # 48 -> 96 capped at bucket 64
    assert s._snap((10, 10)) == (48, 48)


def test_fixed_spec_gets_slack_ordering_but_no_canvas():
    spec = PreprocessSpec(mode="fixed", size=(64, 64))
    assert not ragged_canvas_supported(spec)
    s = Scheduler(spec=spec, ragged=True)
    buf = [_item(30, 40, cls=BULK, t=0.0), _item(30, 40, cls=SLO, t=1.0)]
    plan = s.plan(buf, 2)
    assert plan.canvas_hw is None
    assert plan.items[0].cls == SLO  # ordering still applies


def test_too_small_canvas_fails_loudly():
    img = _img(80, 60)
    rh, rw = shortest_edge_size((80, 60), 48, 64)
    with pytest.raises(ValueError, match="cannot hold"):
        decode_resize_uint8(img, TINY_DETR_SPEC, canvas_hw=(rh - 8, rw))


# --- engine integration (tiny DETR, real jit on CPU) -----------------------


@pytest.fixture(scope="module")
def detr_engine():
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    built = build_detector("facebook/detr-resnet-50")
    return InferenceEngine(
        built, threshold=0.0, batch_buckets=(1, 2, 4), device_preprocess=True
    )


def test_masked_region_invariance(detr_engine):
    """Padding pixels never change detections: garbage bytes in the pad
    region of the staged uint8 batch produce BIT-IDENTICAL outputs (the
    in-jit mask zeroes them before the backbone sees anything)."""
    spec = detr_engine.built.preprocess_spec
    imgs = [_img(80, 60, seed=1), _img(40, 64, seed=2)]
    pixels, valid, sizes = stack_reference(imgs, spec, uint8=True)
    garbage = pixels.copy()
    for j, img in enumerate(imgs):
        rh, rw = decode_resize_uint8(img, spec)[1]
        garbage[j, rh:, :] = 201
        garbage[j, :, rw:] = 77
    assert (garbage != pixels).any()
    clean = [np.asarray(o) for o in detr_engine._forward(
        detr_engine.params, pixels, valid, sizes
    )]
    dirty = [np.asarray(o) for o in detr_engine._forward(
        detr_engine.params, garbage, valid, sizes
    )]
    for a, b in zip(clean, dirty):
        np.testing.assert_array_equal(a, b)


def test_ragged_canvas_parity_vs_per_bucket_reference(detr_engine):
    """Mixed-bucket parity, as the served path makes canvases: a sub-bucket
    canvas on the scheduler's default grid answers what the full bucket
    answers, query for query.

    The tiny DETR is built over a 256 x 256 bucket, so that a canvas on the
    grid (a multiple of `COARSEST_FEATURE_STRIDE`) exists below the bucket;
    its own 64 x 64 bucket is two tokens wide and has none. Two 400 x 150
    images resize to 256 x 96, the scheduler snaps that to 256 x 128, and
    both runs then see the same three columns of valid tokens: what is left
    between them is where the backbone's zero padding starts, 32 pixels
    beyond the valid region, and float32 rounding. (The engine's detections
    at threshold 0 are its raw outputs: one row a query, in query order.)"""
    import dataclasses

    from spotter_tpu.engine.engine import InferenceEngine

    built = detr_engine.built
    spec = dataclasses.replace(
        built.preprocess_spec, size=(192, 256), pad_to=(256, 256)
    )
    engine = InferenceEngine(
        dataclasses.replace(built, preprocess_spec=spec),
        threshold=0.0, batch_buckets=(2,), device_preprocess=True,
    )
    imgs = [_img(400, 150, seed=3), _img(400, 150, seed=4)]
    valid_hw = shortest_edge_size((400, 150), *spec.size)
    assert valid_hw == (256, 96)
    canvas = Scheduler(spec=spec, ragged=True)._snap(valid_hw)
    assert canvas == (256, 128)  # on the grid, and below the bucket

    full = engine.detect(imgs)
    ragged = engine.detect(imgs, canvas_hw=canvas)
    for a, b in zip(full, ragged):
        # threshold 0 keeps every query, in query order: row i is query i
        assert len(a) == len(b) > 0
        assert [d["label"] for d in a] == [d["label"] for d in b]
        sa = np.asarray([d["score"] for d in a], np.float32)
        sb = np.asarray([d["score"] for d in b], np.float32)
        ba = np.asarray([d["box"] for d in a], np.float32)
        bb = np.asarray([d["box"] for d in b], np.float32)
        # Read on this tree (CPU, float32, matmuls at "highest"): scores
        # apart by 1.1e-6 and boxes by 0.0026 px of a 400 x 150 image,
        # which is rounding. A canvas off the grid (256 x 144, 256 x 120,
        # 256 x 104) turns a fourth column of tokens valid: labels flip,
        # scores move by 0.007 and boxes by 25-28 px; a staging defect (the
        # image six pixels to one side) reads 0.008 and 11 px. Each bound
        # stands well over a decade from both readings; 0.1 px is a tenth
        # of the golden boxes' +-1 px contract.
        np.testing.assert_allclose(sa, sb, atol=1e-4)
        assert float(np.abs(ba - bb).max()) < 0.1


def test_ragged_full_canvas_is_identical(detr_engine):
    """canvas == the static bucket stages byte-identical arrays, so the
    detections are bit-equal to the canvas-less call."""
    imgs = [_img(80, 60, seed=5)]
    a = detr_engine.detect(imgs)
    b = detr_engine.detect(imgs, canvas_hw=TINY_DETR_SPEC.pad_to)
    for da, db in zip(a[0], b[0]):
        assert da["label"] == db["label"]
        np.testing.assert_allclose(da["box"], db["box"], atol=1e-5)


# --- batcher integration ----------------------------------------------------


class RecordingEngine:
    """Synthetic engine: records every dispatch (image widths + canvas) and
    optionally blocks the first batch so a test can stack the queue."""

    def __init__(self, buckets=(2,), block_first=False):
        self.metrics = Metrics()
        self.batch_buckets = buckets
        self.batches: list[tuple[list[int], tuple | None]] = []
        self.release = threading.Event()
        self._block_first = block_first

    def detect(self, images, canvas_hw=None):
        first = not self.batches
        self.batches.append(([im.width for im in images], canvas_hw))
        if self._block_first and first:
            self.release.wait(5.0)
        return [[] for _ in images]


class PlainEngine:
    """Pre-ISSUE-9 signature: no canvas parameter. The batcher must detect
    this and never pass one, ragged or not."""

    def __init__(self):
        self.metrics = Metrics()
        self.batch_buckets = (4,)
        self.batches = []

    def detect(self, images):
        self.batches.append([im.width for im in images])
        return [[] for _ in images]


def test_deadline_slack_ordering_under_saturated_queue():
    """The acceptance scenario: the engine is busy, bulk work is queued,
    an slo request arrives late — the NEXT dispatch leads with the slo
    item, bulk backfills."""
    eng = RecordingEngine(buckets=(2,), block_first=True)
    batcher = MicroBatcher(
        eng, max_batch=2, max_delay_ms=20.0, max_in_flight=1, max_queue=0,
        scheduler=Scheduler(spec=None, ragged=True, step=8, urgent_ms=1e9),
    )

    async def drive():
        tasks = [
            asyncio.create_task(
                batcher.submit(_img(8, 16 + i), cls=BULK)
            )
            for i in range(4)
        ]
        await asyncio.sleep(0.15)  # first batch dispatched + blocked
        slo_task = asyncio.create_task(
            batcher.submit(
                _img(8, 96), deadline=Deadline.after(5.0), cls=SLO
            )
        )
        await asyncio.sleep(0.05)
        eng.release.set()
        await asyncio.gather(*tasks, slo_task)
        await batcher.stop()

    asyncio.run(drive())
    assert len(eng.batches) >= 2
    # the slo image (width 96) is in the dispatch right after the blocked
    # batch, ahead of bulk that arrived before it
    assert 96 in eng.batches[1][0]
    remaining_bulk = [w for ws, _ in eng.batches[1:] for w in ws if w != 96]
    assert remaining_bulk  # bulk still served (backfill, not starvation)


def test_ragged_off_is_fifo_and_never_passes_canvas(monkeypatch):
    monkeypatch.delenv("SPOTTER_TPU_RAGGED", raising=False)
    eng = PlainEngine()
    batcher = MicroBatcher(eng, max_batch=4, max_delay_ms=5.0)
    assert batcher.scheduler.fifo
    assert not batcher._engine_takes_canvas

    async def drive():
        await asyncio.gather(
            *(batcher.submit(_img(8, 10 + i)) for i in range(4))
        )
        await batcher.stop()

    asyncio.run(drive())
    assert all(sorted(ws) == ws for ws in eng.batches)  # arrival order


def test_ragged_env_arms_scheduler(monkeypatch):
    monkeypatch.setenv("SPOTTER_TPU_RAGGED", "1")
    eng = PlainEngine()
    batcher = MicroBatcher(eng, max_batch=4)
    assert batcher.scheduler.ragged
    assert batcher.scheduler.step % COARSEST_FEATURE_STRIDE == 0
    # plain-signature engine still never sees a canvas
    assert not batcher._engine_takes_canvas
    # a grid the token masks do not share across canvases is refused where
    # the step is read, not found later as an image that answers differently
    monkeypatch.setenv("SPOTTER_TPU_RAGGED_STEP", "48")
    with pytest.raises(ValueError, match="multiple of 64"):
        MicroBatcher(PlainEngine(), max_batch=4)


def test_padding_waste_and_slack_flow_to_metrics_and_prom():
    eng = RecordingEngine(buckets=(4,))
    batcher = MicroBatcher(
        eng, max_batch=4, max_delay_ms=5.0,
        scheduler=Scheduler(spec=None, ragged=True, step=8),
    )

    async def drive():
        await asyncio.gather(*(
            batcher.submit(
                _img(16, 16 * (1 + i % 2)), deadline=Deadline.after(5.0)
            )
            for i in range(8)
        ))
        await batcher.stop()

    asyncio.run(drive())
    snap = eng.metrics.snapshot()
    assert snap["ragged_packs_total"] >= 1
    assert snap["padding_waste_pct"] is not None
    assert snap["slack_at_dispatch_ms"]["p50"] > 0
    from spotter_tpu.obs import prom

    text = prom.render(snap)
    assert 'spotter_tpu_slack_at_dispatch_ms{quantile="0.5"}' in text
    assert "spotter_tpu_padding_waste_pct" in text
    assert "spotter_tpu_ragged_packs_total" in text


def test_ragged_batcher_end_to_end_with_real_engine(detr_engine):
    """Mixed-size images through MicroBatcher + the tiny DETR engine with
    the ragged scheduler armed: every request completes, packs use a
    ragged canvas, and per-request detection counts match a direct
    per-image reference call."""
    batcher = MicroBatcher(
        detr_engine, max_batch=4, max_delay_ms=20.0,
        scheduler=Scheduler(spec=TINY_DETR_SPEC, ragged=True, step=16),
    )
    sizes = [(80, 60), (96, 72), (80, 60), (40, 64)]
    imgs = [_img(h, w, seed=10 + i) for i, (h, w) in enumerate(sizes)]

    async def drive():
        results = await asyncio.gather(*(batcher.submit(img) for img in imgs))
        await batcher.stop()
        return results

    results = asyncio.run(drive())
    assert len(results) == 4
    for r in results:
        assert r and all({"label", "score", "box"} == set(d) for d in r)
    assert detr_engine.metrics.snapshot()["ragged_packs_total"] >= 1
