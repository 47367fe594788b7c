"""MicroBatcher shutdown and admission edges (ISSUE 1 satellite): stop()
racing a full queue, submit() after stop(), slot release on batch exception,
bounded-queue shedding, and deadline-expired entries skipped by the pump."""

import asyncio
import threading

import numpy as np
import pytest
from PIL import Image

from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.metrics import Metrics
from spotter_tpu.serving.resilience import (
    CircuitBreaker,
    Deadline,
    DeadlineExceededError,
    DrainingError,
    QueueFullError,
)
from spotter_tpu.testing import faults

DETS = [{"label": "tv", "score": 0.9, "box": [0.0, 0.0, 5.0, 5.0]}]


class FakeEngine:
    def __init__(self):
        self.metrics = Metrics()
        self.batch_buckets = (1, 2, 4)
        self.calls = []

    def detect(self, images):
        self.calls.append(len(images))
        return [list(DETS) for _ in images]


class BlockingEngine(FakeEngine):
    def __init__(self):
        super().__init__()
        self.release = threading.Event()

    def detect(self, images):
        self.release.wait(timeout=10.0)
        return super().detect(images)


def _img():
    return Image.fromarray(np.zeros((8, 8, 3), np.uint8))


def _batcher(engine, **kwargs):
    kwargs.setdefault("max_delay_ms", 1.0)
    kwargs.setdefault("breaker", CircuitBreaker(threshold=100, metrics=engine.metrics))
    return MicroBatcher(engine, **kwargs)


def test_submit_after_stop_raises_not_silently_restarts():
    engine = FakeEngine()
    batcher = _batcher(engine)

    async def run():
        assert await batcher.submit(_img()) == DETS
        await batcher.stop()
        with pytest.raises(DrainingError):
            await batcher.submit(_img())
        assert batcher._pump_task is None  # stop() is sticky: no hidden pump
        # an explicit start() re-opens (symmetric lifecycle)
        await batcher.start()
        assert await batcher.submit(_img()) == DETS
        await batcher.stop()

    asyncio.run(run())
    assert engine.metrics.snapshot()["shed_total"] == 1


def test_stop_racing_full_queue_fails_all_pending():
    """stop() with a wedged batch in flight, one batch in the pump's hand,
    and entries still queued: the in-flight batch finishes, everything else
    fails promptly — no submit() caller waits forever."""
    engine = BlockingEngine()
    batcher = _batcher(engine, max_batch=1, max_in_flight=1, max_queue=8)

    async def run():
        r1 = asyncio.create_task(batcher.submit(_img()))
        await asyncio.sleep(0.1)  # r1's batch now blocks inside detect()
        r2 = asyncio.create_task(batcher.submit(_img()))
        r3 = asyncio.create_task(batcher.submit(_img()))
        await asyncio.sleep(0.1)  # r2 held by the pump at the slot; r3 queued
        stop = asyncio.create_task(batcher.stop())
        await asyncio.sleep(0.05)
        engine.release.set()  # let the in-flight batch finish
        await stop
        return r1, r2, r3

    r1, r2, r3 = asyncio.run(run())
    assert r1.result() == DETS  # dispatched work completes
    for r in (r2, r3):
        with pytest.raises(DrainingError, match="MicroBatcher stopped"):
            r.result()


def test_slot_released_on_batch_exception():
    """Two consecutive failing batches with max_in_flight=1: a leaked slot
    would wedge the second submit forever."""
    engine = FakeEngine()
    batcher = _batcher(engine, max_batch=1, max_in_flight=1)

    async def run():
        with faults.inject(engine_error=2):
            for _ in range(2):
                with pytest.raises(RuntimeError, match="injected engine failure"):
                    await asyncio.wait_for(batcher.submit(_img()), timeout=5.0)
        ok = await asyncio.wait_for(batcher.submit(_img()), timeout=5.0)
        await batcher.stop()
        return ok

    assert asyncio.run(run()) == DETS
    assert engine.metrics.snapshot()["errors_total"] == 2


def test_bounded_queue_sheds_with_retry_hint():
    engine = BlockingEngine()
    batcher = _batcher(engine, max_batch=1, max_in_flight=1, max_queue=1)

    async def run():
        r1 = asyncio.create_task(batcher.submit(_img()))
        await asyncio.sleep(0.1)  # in engine
        r2 = asyncio.create_task(batcher.submit(_img()))
        await asyncio.sleep(0.05)  # held by pump
        r3 = asyncio.create_task(batcher.submit(_img()))
        await asyncio.sleep(0.05)  # fills the depth-1 queue
        with pytest.raises(QueueFullError) as exc_info:
            await batcher.submit(_img())
        assert exc_info.value.status == 429
        assert exc_info.value.retry_after_s > 0
        engine.release.set()
        results = await asyncio.gather(r1, r2, r3)
        await batcher.stop()
        return results

    results = asyncio.run(run())
    assert all(r == DETS for r in results)
    assert engine.metrics.snapshot()["shed_total"] == 1


def test_pump_skips_deadline_expired_entries():
    """An entry whose caller already gave up must not consume a device call."""
    engine = BlockingEngine()
    batcher = _batcher(engine, max_batch=1, max_in_flight=1)

    async def run():
        r1 = asyncio.create_task(batcher.submit(_img()))
        await asyncio.sleep(0.1)  # r1 wedged in engine
        with pytest.raises(DeadlineExceededError):
            await batcher.submit(_img(), deadline=Deadline.after(0.1))
        engine.release.set()
        await r1
        # give the pump a moment to pick up (and discard) the dead entry
        await asyncio.sleep(0.2)
        await batcher.stop()

    asyncio.run(run())
    # only r1 reached the engine; the expired entry was skipped
    assert engine.calls == [1]
    assert engine.metrics.snapshot()["deadline_exceeded_total"] == 1


def test_drain_flushes_then_rejects():
    engine = FakeEngine()
    batcher = _batcher(engine, max_batch=2, max_delay_ms=20.0)

    async def run():
        pending = [asyncio.create_task(batcher.submit(_img())) for _ in range(3)]
        await asyncio.sleep(0)  # let the submits enqueue
        summary = await batcher.drain(timeout_s=5.0)
        assert summary["status"] == "drained"
        results = await asyncio.gather(*pending)
        assert all(r == DETS for r in results)
        with pytest.raises(DrainingError):
            await batcher.submit(_img())

    asyncio.run(run())


def test_poison_max_splits_env(monkeypatch):
    """SPOTTER_TPU_POISON_MAX_SPLITS defaults and env override."""
    from spotter_tpu.engine.errors import DEFAULT_POISON_MAX_SPLITS

    engine = FakeEngine()
    assert _batcher(engine).poison_max_splits == DEFAULT_POISON_MAX_SPLITS
    monkeypatch.setenv("SPOTTER_TPU_POISON_MAX_SPLITS", "2")
    assert _batcher(engine).poison_max_splits == 2


def test_two_poisons_both_isolated():
    """Bisect isolation handles more than one poison per batch: both fail
    with PoisonImageError, both innocents succeed, breaker stays closed."""
    from spotter_tpu.engine.errors import PoisonImageError

    engine = FakeEngine()
    breaker = CircuitBreaker(threshold=2, metrics=engine.metrics)
    batcher = _batcher(engine, max_batch=4, max_delay_ms=100.0, breaker=breaker)
    images = [_img() for _ in range(4)]
    faults.poison_image(images[0])
    faults.poison_image(images[3])

    async def run():
        with faults.inject(poison_item=1):
            results = await asyncio.gather(
                *(batcher.submit(im) for im in images), return_exceptions=True
            )
        await batcher.stop()
        return results

    results = asyncio.run(run())
    assert isinstance(results[0], PoisonImageError)
    assert isinstance(results[3], PoisonImageError)
    assert results[1] == DETS and results[2] == DETS
    assert breaker.state == CircuitBreaker.CLOSED
    assert engine.metrics.snapshot()["poison_isolated_total"] == 2


def test_splits_budget_bounds_isolation_depth():
    """With a 1-deep split budget a poisoned batch of 4 can only reach
    2-image sub-batches: the poisoned half fails raw (nothing isolated to a
    single image), the clean half still succeeds."""
    engine = FakeEngine()
    breaker = CircuitBreaker(threshold=100, metrics=engine.metrics)
    batcher = _batcher(
        engine, max_batch=4, max_delay_ms=100.0, breaker=breaker, poison_max_splits=1
    )
    images = [_img() for _ in range(4)]
    faults.poison_image(images[1])

    async def run():
        with faults.inject(poison_item=1):
            results = await asyncio.gather(
                *(batcher.submit(im) for im in images), return_exceptions=True
            )
        await batcher.stop()
        return results

    results = asyncio.run(run())
    from spotter_tpu.engine.errors import PoisonImageError

    # poisoned half [0, 1] fails (raw, not PoisonImageError); clean half succeeds
    assert isinstance(results[0], RuntimeError)
    assert isinstance(results[1], RuntimeError)
    assert not isinstance(results[0], PoisonImageError)
    assert results[2] == DETS and results[3] == DETS
    snap = engine.metrics.snapshot()
    assert snap["poison_isolated_total"] == 0
    assert snap["batch_retries_total"] == 1


@pytest.mark.parametrize("deadline_s", [None, 0.05], ids=["no-deadline", "tight-deadline"])
def test_burst_at_four_times_capacity_accounts_for_every_request(deadline_s):
    """A burst of four times what the batcher can hold (queue + the pump's
    hand + the engine), offered in one tick while the engine is wedged:
    every request ends as accepted, shed or expired and none is lost; the
    shed counter reads what the clients saw; and a request that expired in
    the queue costs no device call."""
    engine = BlockingEngine()
    max_batch, max_queue = 2, 4
    batcher = _batcher(
        engine, max_batch=max_batch, max_in_flight=1, max_queue=max_queue
    )
    holds = max_queue + 2 * max_batch  # queue + pump's hand + in the engine
    offered = 4 * holds

    async def run():
        async def one():
            deadline = None if deadline_s is None else Deadline.after(deadline_s)
            try:
                return await batcher.submit(_img(), deadline=deadline)
            except QueueFullError:
                return "shed"
            except DeadlineExceededError:
                return "expired"

        tasks = [asyncio.create_task(one()) for _ in range(offered)]
        # past the tight deadline, with the engine still wedged
        await asyncio.sleep(0.2)
        engine.release.set()
        results = await asyncio.gather(*tasks)
        await batcher.stop()
        return results

    results = asyncio.run(run())
    shed = results.count("shed")
    expired = results.count("expired")
    accepted = [r for r in results if r == DETS]
    assert len(accepted) + shed + expired == offered
    assert shed >= offered - holds  # nothing past capacity was let in
    snap = engine.metrics.snapshot()
    assert snap["shed_total"] == shed
    assert snap["deadline_exceeded_total"] == expired
    if deadline_s is None:
        assert expired == 0 and 0 < len(accepted) <= holds
        assert sum(engine.calls) == len(accepted)
    else:
        # whoever waited behind the wedged engine gave up and was skipped:
        # the one batch that was in the engine already is all it ever saw
        assert expired == offered - shed and not accepted
        assert engine.calls == [max_batch]
