"""Parity of the split uint8/device preprocess against the host float path.

The device path (ISSUE 3) moves rescale/normalize/mask into the forward jit
and ships uint8; golden boxes ride on the host path's exact numerics
(tests/test_preprocess_hf_parity.py pins those against HF), so the device
path must reproduce them within golden tolerance — including the
shortest_edge pixel-mask case, where pad pixels must be exactly 0 (the torch
DETR processor pads AFTER normalization). Runs the real jit on CPU.
"""

import numpy as np
import pytest
from PIL import Image
from staging_reference import stack_reference

from spotter_tpu.ops.preprocess import (
    DETR_SPEC,
    OWLV2_SPEC,
    RTDETR_SPEC,
    DecodePool,
    PreprocessSpec,
    batch_images,
    decode_resize_uint8,
    device_preprocess_supported,
    device_rescale_normalize,
    preprocess_image,
)


def _img(h, w, seed=0):
    rng = np.random.default_rng(seed)
    return Image.fromarray(rng.integers(0, 255, size=(h, w, 3), dtype=np.uint8))


def _device_path(images, spec):
    import jax

    pixels_u8, valid, sizes = stack_reference(images, spec, uint8=True)
    fn = jax.jit(lambda p, v: device_rescale_normalize(p, v, spec))
    pixels, masks = fn(pixels_u8, valid)
    return np.asarray(pixels), np.asarray(masks), sizes


@pytest.mark.parametrize(
    "spec", [RTDETR_SPEC, PreprocessSpec(mode="fixed", size=(64, 64),
                                         mean=(0.5, 0.4, 0.3), std=(0.2, 0.3, 0.4))]
)
def test_fixed_mode_matches_host_path(spec):
    images = [_img(48, 64), _img(100, 80, seed=1)]
    host_px, host_mask, host_sizes = batch_images(images, spec)
    dev_px, dev_mask, dev_sizes = _device_path(images, spec)
    np.testing.assert_allclose(dev_px, host_px, atol=1e-5)
    np.testing.assert_array_equal(dev_mask, host_mask)
    np.testing.assert_array_equal(dev_sizes, host_sizes)


def test_shortest_edge_matches_host_path_including_mask():
    """The DETR family's padded-bucket case: valid region matches the host
    float path, the pad region is exactly 0 (not (0 - mean)/std), and the
    pixel mask marks exactly the valid region."""
    images = [_img(480, 640), _img(1000, 500, seed=2), _img(97, 131, seed=3)]
    host_px, host_mask, host_sizes = batch_images(images, DETR_SPEC)
    dev_px, dev_mask, dev_sizes = _device_path(images, DETR_SPEC)
    np.testing.assert_allclose(dev_px, host_px, atol=1e-5)
    np.testing.assert_array_equal(dev_mask, host_mask)
    np.testing.assert_array_equal(dev_sizes, host_sizes)
    for j, img in enumerate(images):
        rh, rw = decode_resize_uint8(img, DETR_SPEC)[1]
        assert (dev_px[j, rh:] == 0).all() and (dev_px[j, :, rw:] == 0).all()
        assert dev_mask[j, :rh, :rw].all()
        assert not dev_mask[j, rh:].any() and not dev_mask[j, :, rw:].any()


def test_decode_resize_uint8_is_exact_resize_output():
    """The uint8 host half must be byte-identical to the resize the float
    path feeds its normalize — same PIL call, no extra rounding."""
    img = _img(300, 200, seed=4)
    arr_u8, valid, orig = decode_resize_uint8(img, RTDETR_SPEC)
    th, tw = RTDETR_SPEC.size
    expected = np.asarray(
        img.resize((tw, th), resample=RTDETR_SPEC.resample), dtype=np.uint8
    )
    np.testing.assert_array_equal(arr_u8, expected)
    assert arr_u8.dtype == np.uint8
    assert valid == (th, tw) and orig == (300, 200)


def test_pad_square_unsupported_and_raises():
    """OWLv2's pad_square rescales before its warp — host-float only; the
    engine must gate on device_preprocess_supported, and a direct uint8
    decode call must fail loudly rather than silently mis-normalize."""
    assert not device_preprocess_supported(OWLV2_SPEC)
    assert device_preprocess_supported(RTDETR_SPEC)
    assert device_preprocess_supported(DETR_SPEC)
    with pytest.raises(ValueError):
        decode_resize_uint8(_img(32, 32), OWLV2_SPEC)


def test_pooled_in_place_staging_matches_batch_images():
    """Pool tasks that each write their own row of one array (how the
    engine stages, ISSUE 27) give the same numbers as the serial loop."""
    images = [_img(40, 60, seed=s) for s in range(5)]
    jobs = list(enumerate(images))
    pool = DecodePool(workers=4)
    try:
        px = np.empty((5, 1333, 1333, 3), np.float32)
        mask = np.empty((5, 1333, 1333), np.float32)
        origs = pool.map(
            lambda job: preprocess_image(
                job[1], DETR_SPEC, out=(px[job[0]], mask[job[0]]))[2],
            jobs,
        )
        ref = batch_images(images, DETR_SPEC)
        for a, b in zip(ref, (px, mask, np.asarray(origs, np.float32))):
            np.testing.assert_array_equal(a, b)
        u8 = np.empty((5, 1333, 1333, 3), np.uint8)
        valid = pool.map(
            lambda job: decode_resize_uint8(job[1], DETR_SPEC, out=u8[job[0]])[1],
            jobs,
        )
        u8_serial = stack_reference(images, DETR_SPEC, uint8=True)
        np.testing.assert_array_equal(u8_serial[0], u8)
        np.testing.assert_array_equal(u8_serial[1], np.asarray(valid, np.int32))
        assert pool.queue_depth() == 0  # backlog drains back to idle
    finally:
        pool.close()


def test_decode_pool_workers_env(monkeypatch):
    monkeypatch.setenv("SPOTTER_TPU_DECODE_WORKERS", "3")
    pool = DecodePool()
    try:
        assert pool.workers == 3
        out = pool.map(lambda x: x * 2, [1, 2, 3, 4])
        assert out == [2, 4, 6, 8]  # order preserved across threads
    finally:
        pool.close()
    serial = DecodePool(workers=1)
    assert serial.map(lambda x: x + 1, [1, 2]) == [2, 3]
    serial.close()


def test_sizes_semantics_match_host():
    """target_sizes (original h, w) drive box rescale — identical either path."""
    images = [_img(123, 45, seed=7)]
    _, _, host_sizes = batch_images(images, RTDETR_SPEC)
    _, _, dev_sizes = stack_reference(images, RTDETR_SPEC, uint8=True)
    np.testing.assert_array_equal(host_sizes, np.asarray([[123, 45]], np.float32))
    np.testing.assert_array_equal(dev_sizes.astype(np.float32), host_sizes)
