"""In-place staging (ISSUE 27): the engine leases a host slab a batch, each
decode-pool task writes its image into its own row, and the slab comes back
only after the batch's outputs are on the host. CPU, a model of four
numbers; every byte is held against the plain `np.stack` + pad
(`staging_reference.py`)."""

import threading

import numpy as np
import pytest
from PIL import Image
from staging_reference import stack_reference

from spotter_tpu.engine import staging
from spotter_tpu.engine.engine import BuiltDetector, InferenceEngine
from spotter_tpu.engine.errors import TransientEngineError
from spotter_tpu.obs import prom
from spotter_tpu.ops.preprocess import (
    DecodePool,
    ImageTooLargeError,
    PreprocessSpec,
    decode_resize_uint8,
    preprocess_image,
    shortest_edge_size,
)
from spotter_tpu.testing import faults

MEAN, STD = (0.5, 0.4, 0.3), (0.2, 0.3, 0.4)
FIXED = PreprocessSpec(mode="fixed", size=(8, 12), mean=MEAN, std=STD)
FIXED_RAW = PreprocessSpec(mode="fixed", size=(8, 12))  # no mean: the rescale is the last step
EDGE = PreprocessSpec(mode="shortest_edge", size=(16, 24), mean=MEAN, std=STD,
                      pad_to=(24, 24))
SQUARE = PreprocessSpec(mode="pad_square", size=(12, 12), mean=MEAN, std=STD)

# name -> (spec, uint8 ingest, ragged canvas)
MODES = {
    "fixed": (FIXED, False, None),
    "fixed_no_mean": (FIXED_RAW, False, None),
    "shortest_edge": (EDGE, False, None),
    "shortest_edge_ragged": (EDGE, False, (16, 22)),
    "pad_square": (SQUARE, False, None),
    "uint8_fixed": (FIXED, True, None),
    "uint8_shortest_edge": (EDGE, True, None),
    "uint8_shortest_edge_ragged": (EDGE, True, (16, 22)),
}


class _FourNumberModel:
    """`module.apply` of two queries a picture whose logits follow the
    picture's mean: a stale or foreign row changes its answer."""

    def apply(self, variables, pixels):
        import jax.numpy as jnp

        b = pixels.shape[0]
        mean = pixels.mean(axis=(1, 2, 3))[:, None, None] * variables["params"]["w"]
        logits = jnp.zeros((b, 2, 3)) + mean * jnp.arange(1.0, 4.0)
        return {"logits": logits, "pred_boxes": jnp.full((b, 2, 4), 0.5)}


def _engine(spec=FIXED, uint8=False, buckets=(2, 4), workers=3) -> InferenceEngine:
    built = BuiltDetector(
        model_name="four-numbers",
        module=_FourNumberModel(),
        params={"w": np.ones((), np.float32)},
        preprocess_spec=spec,
        postprocess="softmax",
        id2label={0: "a", 1: "b"},
    )
    return InferenceEngine(
        built, threshold=0.0, batch_buckets=buckets, device_preprocess=uint8,
        decode_pool=DecodePool(workers=workers),
    )


def _imgs(n, seed=0):
    """Landscape pictures of a few sizes, all of which resize into (16, 22)."""
    rng = np.random.default_rng(seed)
    sizes = [(20, 26), (31, 40), (18, 24), (40, 52)]
    return [
        Image.fromarray(rng.integers(0, 255, (*sizes[i % 4], 3), dtype=np.uint8))
        for i in range(n)
    ]


def _assert_same_bytes(staged, reference):
    for got, want in zip(staged, reference, strict=True):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


def _scores(results):
    return [[d["score"] for d in dets] for dets in results]


# ---------------------------------------------------------------------------
# (a) byte equality with np.stack + pad of the per-image results


@pytest.mark.parametrize("n", [4, 3], ids=["full", "short"])
@pytest.mark.parametrize("mode", list(MODES))
def test_staged_arrays_are_the_stacked_and_padded_bytes(mode, n):
    spec, uint8, canvas = MODES[mode]
    eng = _engine(spec, uint8)
    images = _imgs(n, seed=3)
    batch = eng._stage_host(images, canvas)
    assert batch.bucket == 4 and batch.slab is not None
    _assert_same_bytes(
        batch.arrays, stack_reference(images, spec, uint8, canvas, bucket=4)
    )
    # views of the leased slab's front, not copies of it
    assert all(np.shares_memory(a, s) for a, s in
               zip(batch.arrays, (batch.slab.pixels, batch.slab.second)))
    assert batch.arrays[0].flags.c_contiguous and batch.arrays[1].flags.c_contiguous


@pytest.mark.parametrize("spec", [FIXED, FIXED_RAW, EDGE], ids=["fixed", "no_mean", "edge"])
def test_without_a_destination_the_formula_is_the_plain_one(spec):
    """`preprocess_image` with no `out` is what the reference above stands
    on: hold it against the arithmetic written out."""
    img = _imgs(1, seed=5)[0]
    hw = (img.height, img.width)
    th, tw = spec.size if spec.mode == "fixed" else shortest_edge_size(hw, *spec.size)
    a = np.asarray(img.resize((tw, th), resample=spec.resample), dtype=np.float32)
    a = a * spec.rescale_factor
    if spec.mean is not None:
        a = (a - np.asarray(spec.mean, np.float32)) / np.asarray(spec.std, np.float32)
    want = np.zeros((*spec.input_hw, 3), np.float32)
    want[:th, :tw] = a
    mask = np.zeros(spec.input_hw, np.float32)
    mask[:th, :tw] = 1.0
    got, got_mask, orig = preprocess_image(img, spec)
    assert got.tobytes() == want.tobytes() and got_mask.tobytes() == mask.tobytes()
    assert orig == hw


def test_pooled_in_place_writes_match_the_serial_stack():
    """Pool tasks writing rows of one array side by side give the bytes of
    the serial loop, on both ingest paths, and the backlog drains."""
    images = _imgs(7, seed=9)
    pool = DecodePool(workers=4)
    try:
        px = np.empty((7, 24, 24, 3), np.float32)
        mask = np.empty((7, 24, 24), np.float32)
        origs = pool.map(
            lambda job: preprocess_image(job[1], EDGE, out=(px[job[0]], mask[job[0]]))[2],
            list(enumerate(images)),
        )
        _assert_same_bytes(
            (px, mask, np.asarray(origs, np.float32)), stack_reference(images, EDGE)
        )
        u8 = np.empty((7, 24, 24, 3), np.uint8)
        done = pool.map(
            lambda job: decode_resize_uint8(job[1], EDGE, out=u8[job[0]])[1:],
            list(enumerate(images)),
        )
        want = stack_reference(images, EDGE, uint8=True)
        assert u8.tobytes() == want[0].tobytes()
        assert [d[0] for d in done] == [tuple(v) for v in want[1]]
        assert pool.queue_depth() == 0
    finally:
        pool.close()


@pytest.mark.parametrize("uint8", [False, True], ids=["float", "uint8"])
def test_a_destination_of_the_wrong_shape_or_dtype_is_refused(uint8):
    img = _imgs(1)[0]
    bad = np.empty((8, 12, 3), np.uint8 if not uint8 else np.float32)
    small = np.empty((4, 4, 3), np.uint8 if uint8 else np.float32)
    for dst in (bad, small):
        with pytest.raises(ValueError, match="destination"):
            if uint8:
                decode_resize_uint8(img, FIXED, out=dst)
            else:
                preprocess_image(img, FIXED, out=(dst, None))


# ---------------------------------------------------------------------------
# (b) reuse leaves nothing behind


@pytest.mark.parametrize("mode", ["fixed", "shortest_edge", "shortest_edge_ragged",
                                  "uint8_shortest_edge"])
def test_a_reused_slab_holds_nothing_of_the_batch_before(mode):
    spec, uint8, canvas = MODES[mode]
    eng = _engine(spec, uint8)
    first = eng.detect(_imgs(4, seed=1))  # a full batch at the static canvas
    assert len(first) == 4 and eng._slabs.free_count() == 1
    images = _imgs(2, seed=2)[::-1] + _imgs(1, seed=4)  # other pictures, other sizes
    batch = eng._stage_host(images, canvas)
    assert eng._slabs.free_count() == 0  # the same slab, leased again
    pixels, second, sizes = batch.arrays
    _assert_same_bytes(batch.arrays, stack_reference(images, spec, uint8, canvas, bucket=4))
    # pad rows: exactly zero pixels; a mask of ones, or the canvas; sizes of one
    assert not pixels[3:].any()
    if uint8:
        assert second[3:].tolist() == [list(pixels.shape[1:3])]
    else:
        assert (second[3:] == 1.0).all()
    assert (sizes[3:] == 1.0).all()
    # and the answers are a fresh engine's
    assert _scores(eng.detect(images, canvas)) == _scores(
        _engine(spec, uint8).detect(images, canvas)
    )


def test_the_mask_of_a_fixed_spec_is_written_once():
    eng = _engine(FIXED)
    batch = eng._stage_host(_imgs(3))
    slab = batch.slab
    assert eng._slabs.mask_is_ones and (slab.second == 1.0).all()
    slab.second[:] = 7.0  # nobody writes it again: what is there is staged
    eng._slabs.release(slab)
    assert (eng._stage_host(_imgs(2)).arrays[1] == 7.0).all()


def test_a_smaller_rung_is_a_shorter_view_of_the_same_slab():
    eng = _engine(EDGE, buckets=(2, 4))
    big = eng._stage_host(_imgs(4))
    eng._slabs.release(big.slab)
    small = eng._stage_host(_imgs(2), (16, 22))
    assert small.slab is big.slab and small.arrays[0].shape == (2, 16, 22, 3)
    assert small.slab.pixels.size == 4 * 24 * 24 * 3  # sized once, for the largest rung
    # a canvas the static one cannot hold gets a slab of its own, never kept
    eng._slabs.release(small.slab)
    over = eng._slabs.lease(4, 30, 30)
    assert over is not big.slab and over.views(4, 30, 30)[0].shape == (4, 30, 30, 3)
    eng._slabs.release(over)
    assert eng._slabs.free_count() == 1


# ---------------------------------------------------------------------------
# (c) the lease ends at the fetch, not at the put


def test_no_slab_is_leased_twice_before_its_finish(monkeypatch):
    """Two threads' batches in flight and a multi-chunk call's two leases:
    from lease to release a slab belongs to one batch."""
    eng = _engine(FIXED, buckets=(2,))
    held, overlaps, most, lock = set(), [], [0], threading.Lock()
    lease, release = eng._slabs.lease, eng._slabs.release

    def watched_lease(*shape):
        slab = lease(*shape)
        with lock:
            overlaps.append(id(slab) in held)
            held.add(id(slab))
            most[0] = max(most[0], len(held))
        return slab

    def watched_release(slab):
        with lock:
            held.discard(id(slab))
        release(slab)

    monkeypatch.setattr(eng._slabs, "lease", watched_lease)
    monkeypatch.setattr(eng._slabs, "release", watched_release)
    finish, gate = eng._finish, threading.Barrier(2, timeout=30)

    def finish_together(batch):  # both threads hold a dispatched batch here
        if threading.current_thread().name.startswith("pair"):
            gate.wait()
        return finish(batch)

    monkeypatch.setattr(eng, "_finish", finish_together)
    want = _scores(_engine(FIXED, buckets=(2,)).detect(_imgs(2, seed=6)))
    got = {}
    threads = [
        threading.Thread(target=lambda k=k: got.update({k: eng.detect(_imgs(2, seed=6))}),
                         name=f"pair{k}")
        for k in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert most[0] == 2 and not any(overlaps) and not held
    assert _scores(got[0]) == _scores(got[1]) == want
    # one call of three chunks holds two leases at once (depth-2 pipeline)
    assert len(eng.detect(_imgs(6, seed=7))) == 6
    assert most[0] == 2 and not any(overlaps) and not held
    assert eng._slabs.free_count() == 2


def test_back_to_back_detects_answer_as_a_fresh_engine_does():
    """On the CPU backend the device array may alias the slab: the second
    call stages into the memory the first call's inputs lived in."""
    eng = _engine(EDGE)
    a, b = _imgs(4, seed=11), _imgs(3, seed=12)
    got = (_scores(eng.detect(a)), _scores(eng.detect(b)), _scores(eng.detect(a)))
    assert eng.metrics.snapshot()["staging_slab_allocs_total"] == 1
    assert got[0] == got[2] == _scores(_engine(EDGE).detect(a))
    assert got[1] == _scores(_engine(EDGE).detect(b))


# ---------------------------------------------------------------------------
# (d) a batch that fails drops its slab


def test_a_failed_dispatch_drops_its_slab_and_the_halves_lease_their_own():
    eng = _engine(FIXED)
    images = _imgs(4, seed=13)
    want = _scores(eng.detect(images))
    assert eng._slabs.free_count() == 1
    with faults.inject(engine_oom=1):  # the dispatch raises RESOURCE_EXHAUSTED
        assert _scores(eng.detect(images)) == want  # recovered in two halves
    snap = eng.metrics.snapshot()
    assert snap["batch_retries_total"] == 1
    # leases: the first batch, the failed one, its two halves; the failed one
    # took the free slab with it, so the first half had to allocate
    assert snap["staging_slab_leases_total"] == 4
    assert snap["staging_slab_allocs_total"] == 2
    assert eng._slabs.free_count() == 1  # no larger than before
    with faults.inject(engine_oom=-1), pytest.raises(TransientEngineError):
        eng.detect(images)
    assert eng._slabs.free_count() == 0  # every lease of that call was dropped
    assert _scores(eng.detect(images)) == want


def test_a_failed_pool_task_drops_the_slab(monkeypatch):
    eng = _engine(FIXED)
    images = _imgs(3, seed=14)
    want = _scores(eng.detect(images))
    monkeypatch.setenv("SPOTTER_TPU_MAX_IMAGE_PIXELS", "1000")  # 40x52 is over
    with pytest.raises(ImageTooLargeError):
        eng.detect(_imgs(4, seed=15))
    monkeypatch.delenv("SPOTTER_TPU_MAX_IMAGE_PIXELS")
    assert eng._slabs.free_count() == 0
    assert _scores(eng.detect(images)) == want
    assert eng._slabs.free_count() == 1


def test_a_failed_fetch_drops_the_slab(monkeypatch):
    import jax

    eng = _engine(FIXED)
    eng.detect(_imgs(2))

    def lost(outputs):
        raise RuntimeError("fetch failed")

    with monkeypatch.context() as m:
        m.setattr(jax, "device_get", lost)
        with pytest.raises(RuntimeError, match="fetch failed"):
            eng.detect(_imgs(2))
    assert eng._slabs.free_count() == 0
    assert len(eng.detect(_imgs(2))) == 2 and eng._slabs.free_count() == 1


# ---------------------------------------------------------------------------
# (e) the two counters, and what the free-list keeps


def test_counters_say_leases_and_allocations():
    eng = _engine(FIXED)
    eng.warmup()  # makes its own zero inputs: leases none
    snap = eng.metrics.snapshot()
    assert snap["staging_slab_leases_total"] == snap["staging_slab_allocs_total"] == 0
    for k in range(5):
        eng.detect(_imgs(1 + k % 4, seed=k))
    snap = eng.metrics.snapshot()
    assert snap["staging_slab_leases_total"] == 5
    assert snap["staging_slab_allocs_total"] == 1  # every later lease reused it
    text = prom.render(snap)
    assert "spotter_tpu_staging_slab_leases_total 5" in text
    assert "spotter_tpu_staging_slab_allocs_total 1" in text


def test_the_free_list_keeps_a_fixed_few():
    eng = _engine(FIXED)
    slabs = [eng._slabs.lease(4, 8, 12) for _ in range(staging.KEEP_SLABS + 2)]
    assert len({id(s) for s in slabs}) == len(slabs)
    for slab in slabs:
        eng._slabs.release(slab)
    assert eng._slabs.free_count() == staging.KEEP_SLABS == 3
    snap = eng.metrics.snapshot()
    assert snap["staging_slab_allocs_total"] == snap["staging_slab_leases_total"] == 5


def test_a_rebuilt_ladder_starts_its_own_free_list():
    eng = _engine(FIXED, buckets=(2, 4))
    batch = eng._stage_host(_imgs(2))
    eng._place(None, None, (2,))  # a re-place, as `rebuild_degraded` makes it
    eng._slabs.release(batch.slab)  # the old ladder's slab is not kept
    assert eng._slabs.free_count() == 0 and eng._slabs.rows == 2
