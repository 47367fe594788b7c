"""Engine tests on the tiny RT-DETR (real jit path, CPU, no torch)."""

import asyncio
import os

import numpy as np
import pytest
from PIL import Image

os.environ["SPOTTER_TPU_TINY"] = "1"

from spotter_tpu.engine.engine import InferenceEngine
from spotter_tpu.models import build_detector


@pytest.fixture(scope="module")
def engine():
    built = build_detector("PekingU/rtdetr_v2_r101vd")
    return InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2, 4))


def _imgs(n, hw=(48, 64)):
    rng = np.random.default_rng(0)
    return [
        Image.fromarray(rng.integers(0, 255, size=(*hw, 3), dtype=np.uint8))
        for _ in range(n)
    ]


def test_detect_shapes_and_fields(engine):
    results = engine.detect(_imgs(2))
    assert len(results) == 2
    for dets in results:
        assert len(dets) > 0  # threshold 0 -> top-k all returned
        det = dets[0]
        assert set(det.keys()) == {"label", "score", "box"}
        assert len(det["box"]) == 4
        # boxes are scaled to original-image pixel coords (48x64 image):
        # cxcywh in (0,1) -> xyxy in (-w/2, 1.5w)
        xs = [d["box"][0] for d in dets] + [d["box"][2] for d in dets]
        assert -32.0 <= min(xs) and max(xs) <= 96.0


def test_batch_padding_strips_pad_results(engine):
    # 3 images -> bucket 4; must return exactly 3 results
    results = engine.detect(_imgs(3))
    assert len(results) == 3


def test_oversize_batch_splits(engine):
    results = engine.detect(_imgs(6))  # max bucket 4 -> two chunks
    assert len(results) == 6
    snap = engine.metrics.snapshot()
    assert snap["images_total"] >= 6
    assert snap["batches_total"] >= 2


def test_empty_detect_returns_empty(engine):
    assert engine.detect([]) == []


def test_pipelined_multichunk_matches_serial(engine):
    """detect()'s depth-2 pipeline returns the same per-image results, in
    order, as running each chunk through the serial path."""
    images = _imgs(9)  # 3 chunks at max bucket 4 (4+4+1)
    pipelined = engine.detect(images)
    serial = []
    for i in range(0, len(images), engine.batch_buckets[-1]):
        serial.extend(engine._detect_chunk(images[i : i + engine.batch_buckets[-1]]))
    assert len(pipelined) == len(serial) == 9
    for p, s in zip(pipelined, serial):
        assert [d["label"] for d in p] == [d["label"] for d in s]
        np.testing.assert_allclose(
            np.asarray([d["box"] for d in p], np.float32),
            np.asarray([d["box"] for d in s], np.float32),
            atol=1e-5,
        )


def test_device_preprocess_matches_host_path(engine):
    """SPOTTER_TPU_DEVICE_PREPROCESS's uint8 ingest (ISSUE 3) produces the
    same detections as the host float path, while shipping >=3.5x fewer H2D
    bytes/image (uint8 pixels + (B,2) valid vs float32 pixels + full mask)."""
    images = _imgs(5)
    dev = InferenceEngine(
        engine.built, threshold=0.0, batch_buckets=(1, 2, 4), device_preprocess=True
    )
    assert dev.device_preprocess
    a = engine.detect(images)
    b = dev.detect(images)
    assert len(a) == len(b) == 5
    for da, db in zip(a, b):
        assert [d["label"] for d in da] == [d["label"] for d in db]
        np.testing.assert_allclose(
            np.asarray([d["box"] for d in da], np.float32),
            np.asarray([d["box"] for d in db], np.float32),
            atol=1e-3,
        )
    host_bpi = engine.metrics.snapshot()["h2d_bytes_per_image"]
    dev_bpi = dev.metrics.snapshot()["h2d_bytes_per_image"]
    assert dev_bpi > 0 and host_bpi / dev_bpi >= 3.5


@pytest.mark.parametrize(
    "device_preprocess, bytes_a_pixel, bytes_a_slot",
    [(False, 16, 8), (True, 3, 16)],
    ids=["host-float", "device-uint8"],
)
def test_a_full_bucket_ships_its_ingest_paths_bytes_a_pixel(
    engine, device_preprocess, bytes_a_pixel, bytes_a_slot
):
    """What crosses to the device for a full bucket, counted: the host path
    ships float32 pixels and a float32 mask (12 + 4 B a pixel) and the
    sizes; the uint8 ingest ships the pixels as decoded (3 B a pixel), the
    valid region and the sizes."""
    eng = InferenceEngine(
        engine.built, threshold=0.0, batch_buckets=(4,),
        device_preprocess=device_preprocess,
    )
    eng.detect(_imgs(4))
    h, w = engine.built.preprocess_spec.input_hw
    snap = eng.metrics.snapshot()
    assert snap["h2d_bytes_per_image"] == h * w * bytes_a_pixel + bytes_a_slot
    assert snap["h2d_bytes_total"] == 4 * snap["h2d_bytes_per_image"]


def test_device_preprocess_falls_back_for_pad_square():
    """OWLv2's pad_square spec can't defer its float warp to the device —
    the engine must quietly keep the host path rather than mis-normalize."""
    import dataclasses

    built = build_detector("PekingU/rtdetr_v2_r18vd")
    padded = dataclasses.replace(
        built, preprocess_spec=dataclasses.replace(
            built.preprocess_spec, mode="pad_square"
        )
    )
    eng = InferenceEngine(padded, threshold=0.0, batch_buckets=(1,),
                          device_preprocess=True)
    assert not eng.device_preprocess


def test_tiny_registry_model_name_matching():
    built = build_detector("PekingU/rtdetr_v2_r18vd")
    assert built.postprocess == "sigmoid_topk"
    assert built.id2label[62] == "tv"


def test_threshold_filters(engine):
    # with a high threshold the random model should return nothing
    high = InferenceEngine(engine.built, threshold=0.99, batch_buckets=(1,))
    results = high.detect(_imgs(1))
    assert results == [[]]


@pytest.mark.slow  # compile-heavy on 1-core CPU; full/CI run covers it
def test_detr_family_end_to_end():
    """Tiny DETR through the full engine path (shortest-edge + mask + softmax)."""
    built = build_detector("facebook/detr-resnet-50")
    assert built.postprocess == "softmax" and built.needs_mask
    eng = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2))
    results = eng.detect(_imgs(3, hw=(40, 72)))
    assert len(results) == 3
    for dets in results:
        assert all(set(d) == {"label", "score", "box"} for d in dets)


def test_yolos_family_end_to_end():
    """Tiny YOLOS through the full engine path (fixed warp + softmax)."""
    built = build_detector("hustvl/yolos-base")
    assert built.postprocess == "softmax" and not built.needs_mask
    eng = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2))
    results = eng.detect(_imgs(2, hw=(50, 70)))
    assert len(results) == 2
    assert all(len(d) > 0 for d in results)


@pytest.mark.slow  # compile-heavy on 1-core CPU; full/CI run covers it
def test_owlvit_family_end_to_end(monkeypatch):
    """Tiny OWL-ViT: cached text-query embeds ride apply_kwargs; labels come
    from the deploy-time query list, not checkpoint metadata."""
    monkeypatch.setenv("SPOTTER_TPU_TEXT_QUERIES", "tv,couch,bed")
    built = build_detector("google/owlvit-base-patch32")
    assert built.postprocess == "sigmoid_max"
    assert built.id2label == {0: "tv", 1: "couch", 2: "bed"}
    qe = built.apply_kwargs["query_embeds"]
    assert qe.shape == (3, 16)  # tiny projection_dim
    np.testing.assert_allclose(np.linalg.norm(qe, axis=-1), np.ones(3), atol=1e-5)
    eng = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2))
    results = eng.detect(_imgs(2, hw=(40, 40)))
    assert len(results) == 2
    labels = {d["label"] for dets in results for d in dets}
    assert labels <= {"tv", "couch", "bed"} and labels


@pytest.mark.slow  # compile-heavy on 1-core CPU; full/CI run covers it
def test_deformable_detr_family_end_to_end():
    """Tiny Deformable-DETR through the full engine path (shortest-edge +
    mask + sigmoid top-k)."""
    built = build_detector("SenseTime/deformable-detr-with-box-refine")
    assert built.postprocess == "sigmoid_topk" and built.needs_mask
    eng = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2))
    results = eng.detect(_imgs(3, hw=(40, 72)))
    assert len(results) == 3
    for dets in results:
        assert all(set(d) == {"label", "score", "box"} for d in dets)


def test_conditional_detr_registry_routing():
    """'conditional-detr-resnet-50' contains the 'detr-resnet' substring; the
    registry must route it to the conditional family (registration order)."""
    built = build_detector("microsoft/conditional-detr-resnet-50")
    assert built.postprocess == "sigmoid_topk"
    assert type(built.module).__name__ == "ConditionalDetrDetector"


def test_owlv2_registry_routing(monkeypatch):
    """owlv2 names resolve to the owlvit family (shared architecture)."""
    monkeypatch.setenv("SPOTTER_TPU_TEXT_QUERIES", "tv")
    built = build_detector("google/owlv2-base-patch16-ensemble")
    assert built.postprocess == "sigmoid_max"
    assert type(built.module).__name__ == "OwlViTDetector"


def test_dab_detr_registry_routing():
    """'dab-detr-resnet-50' contains 'detr-resnet'; must route to dab_detr."""
    built = build_detector("IDEA-Research/dab-detr-resnet-50")
    assert built.postprocess == "sigmoid_topk" and built.needs_mask
    assert type(built.module).__name__ == "DabDetrDetector"


@pytest.mark.slow  # compile-heavy on 1-core CPU; full/CI run covers it
def test_dab_detr_family_end_to_end():
    """Tiny DAB-DETR through the full engine path (shortest-edge + mask +
    sigmoid top-k)."""
    built = build_detector("IDEA-Research/dab-detr-resnet-50")
    eng = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2))
    results = eng.detect(_imgs(3, hw=(40, 72)))
    assert len(results) == 3
    for dets in results:
        assert all(set(d) == {"label", "score", "box"} for d in dets)


def test_host_float_path_emits_no_donation_warning():
    """ISSUE 5 satellite: only the uint8 staging buffer that
    device_rescale_normalize consumes is donated. The host-float path's
    float pixels can never alias the tiny postprocess outputs, so donating
    them freed nothing and warned "Some donated buffers were not usable:
    float32[...]" on every call (pre-round record r05)."""
    import warnings

    built = build_detector("PekingU/rtdetr_v2_r101vd")
    eng = InferenceEngine(
        built, threshold=0.0, batch_buckets=(2,), device_preprocess=False
    )
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        results = eng.detect(_imgs(2))
    assert len(results) == 2
    donation = [
        w for w in caught if "donated buffers" in str(w.message).lower()
    ]
    assert donation == [], [str(w.message) for w in donation]
