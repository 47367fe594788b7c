"""What a staged batch has to hold, written the plain way: every image
preprocessed into an array of its own, `np.stack`, then a pad to the bucket
by `np.concatenate`. This is how the engine staged a batch before it wrote
each image into its row of a leased slab (ISSUE 27); the tests hold the
slab's bytes against it."""

import numpy as np

from spotter_tpu.ops.preprocess import decode_resize_uint8, preprocess_image


def stack_reference(images, spec, uint8=False, canvas_hw=None, bucket=None):
    """-> (pixels (B,H,W,3), masks (B,H,W) float32 or valid (B,2) int32,
    sizes (B,2) float32), padded to `bucket` rows when one is given."""
    per_image = decode_resize_uint8 if uint8 else preprocess_image
    done = [per_image(img, spec, canvas_hw) for img in images]
    pixels = np.stack([d[0] for d in done])
    if uint8:
        second = np.asarray([d[1] for d in done], dtype=np.int32)
    else:
        second = np.stack([d[1] for d in done])
    sizes = np.asarray([d[2] for d in done], dtype=np.float32)
    pad = 0 if bucket is None else bucket - len(images)
    if pad > 0:
        if uint8:  # a pad row's valid region: the canvas
            fill = np.tile(np.asarray([pixels.shape[1:3]], np.int32), (pad, 1))
        else:
            fill = np.ones((pad, *second.shape[1:]), second.dtype)
        pixels = np.concatenate(
            [pixels, np.zeros((pad, *pixels.shape[1:]), pixels.dtype)]
        )
        second = np.concatenate([second, fill])
        sizes = np.concatenate([sizes, np.ones((pad, 2), sizes.dtype)])
    return pixels, second, sizes
