"""Image preprocess: host-side decode/resize, device-friendly static shapes.

Replaces the reference's `processor(images=image, return_tensors="pt")` call
(apps/spotter/src/spotter/serve.py:98). TPU discipline (SURVEY.md §5.7): every
tensor that reaches jit has a shape from a small fixed set, so XLA never
recompiles per request. Aspect-changing models (RT-DETR, OWL-ViT) already have a
single static size; shortest-edge models (DETR, YOLOS) resize
aspect-preserving and pad into a fixed bucket with a pixel mask.

Arrays are NHWC — the natural TPU/XLA convolution layout (torch parity tests
transpose to NCHW at the boundary).
"""

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np
from PIL import Image

MAX_IMAGE_PIXELS_ENV = "SPOTTER_TPU_MAX_IMAGE_PIXELS"
DEFAULT_MAX_IMAGE_PIXELS = 64_000_000  # ~64 MP; <= 0 disables the guard

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
CLIP_MEAN = (0.48145466, 0.4578275, 0.40821073)
CLIP_STD = (0.26862954, 0.26130258, 0.27577711)


@dataclass(frozen=True)
class PreprocessSpec:
    """How to turn a PIL image into a model input array.

    mode "fixed": warp-resize to `size` (h, w) — RT-DETR (640, 640), OWL-ViT
    (768, 768). mode "shortest_edge": aspect-preserving resize so the short side
    is size[0] without the long side exceeding size[1], then zero-pad to the
    (size[1], size[1])-bounded bucket — DETR/YOLOS (800, 1333). mode
    "pad_square": pad bottom/right to a square with mid-gray (0.5
    pre-normalization), then warp to `size` — OWLv2 (960, 960); the reported
    target size is (max(h,w), max(h,w)), matching HF Owlv2ImageProcessor's
    box rescale (its `_scale_boxes` uses the padded-square side for both axes).
    """

    mode: str = "fixed"
    size: tuple[int, int] = (640, 640)
    rescale_factor: float = 1.0 / 255.0
    mean: tuple[float, float, float] | None = None
    std: tuple[float, float, float] | None = None
    pad_to: tuple[int, int] | None = None  # static bucket for shortest_edge mode
    # PIL resample filter. Families differ: RT-DETR/DETR/YOLOS processors
    # default to BILINEAR, OWL-ViT's to BICUBIC — a wrong filter shifts
    # edge pixels by ~0.4 post-normalize and silently eats the reference's
    # ±1 px golden tolerance (tests/test_preprocess_hf_parity.py pins each).
    resample: int = Image.BILINEAR

    @property
    def input_hw(self) -> tuple[int, int]:
        """The static (h, w) every preprocessed array has."""
        if self.mode in ("fixed", "pad_square"):
            return self.size
        assert self.pad_to is not None
        return self.pad_to


RTDETR_SPEC = PreprocessSpec(mode="fixed", size=(640, 640))
# Bucket must cover both orientations: a portrait image resizes to up to
# (1333, 800), landscape to (800, 1333). The serving engine narrows this to
# per-orientation buckets; the static spec must hold any legal resize.
DETR_SPEC = PreprocessSpec(
    mode="shortest_edge", size=(800, 1333), mean=IMAGENET_MEAN, std=IMAGENET_STD,
    pad_to=(1333, 1333),
)
OWLVIT_SPEC = PreprocessSpec(
    mode="fixed", size=(768, 768), mean=CLIP_MEAN, std=CLIP_STD,
    resample=Image.BICUBIC,
)
OWLV2_SPEC = PreprocessSpec(
    mode="pad_square", size=(960, 960), mean=CLIP_MEAN, std=CLIP_STD
)


class ImageTooLargeError(ValueError):
    """Decode-bomb guard tripped: the image's pixel count exceeds
    SPOTTER_TPU_MAX_IMAGE_PIXELS. A per-image error, never a host OOM."""


def check_image_pixels(image: Image.Image) -> None:
    """Reject decode bombs BEFORE any full decode/resize touches them.

    PIL reads dimensions from the header without decoding pixel data, so
    this check is cheap; a 4 GB-decoded "tiny" JPEG otherwise OOMs the host
    inside convert()/resize(). Called from the detector (right after
    Image.open) and from both DecodePool preprocess paths; inside the
    engine a tripped guard is a per-image poison the bisect-retry isolates.
    """
    raw = os.environ.get(MAX_IMAGE_PIXELS_ENV, "").strip()
    try:
        cap = int(raw) if raw else DEFAULT_MAX_IMAGE_PIXELS
    except ValueError:
        raise ValueError(
            f"{MAX_IMAGE_PIXELS_ENV} must be an integer, got {raw!r}"
        ) from None
    if cap <= 0:
        return
    n = image.width * image.height
    if n > cap:
        raise ImageTooLargeError(
            f"image {image.width}x{image.height} = {n} px exceeds "
            f"{MAX_IMAGE_PIXELS_ENV}={cap} (decode-bomb guard)"
        )


def shortest_edge_size(hw: tuple[int, int], short: int, longest: int) -> tuple[int, int]:
    """Output (h, w) for aspect-preserving shortest-edge resize with a long-side cap.

    Mirrors the HF DETR processor's `get_size_with_aspect_ratio` arithmetic
    exactly (int truncation, and the capped short side re-rounded before the
    long side is derived from the UNROUNDED cap) — golden boxes depend on
    the processor's exact output dims, and `round()` here would drift by a
    pixel on cap-boundary aspect ratios (tests/test_preprocess_hf_parity.py).
    """
    h, w = hw
    raw_size = None
    size = short
    mn, mx = (h, w) if h <= w else (w, h)
    if mx / mn * size > longest:
        raw_size = longest * mn / mx
        size = int(round(raw_size))
    # HF checks the already-at-size equality case FIRST (the DETR variant;
    # YOLOS orders its branches differently but serving warps YOLOS to a
    # fixed size, so DETR's order is the one golden parity depends on).
    if (h <= w and h == size) or (w <= h and w == size):
        oh, ow = h, w
    elif w < h:
        ow = size
        oh = int(raw_size * h / w) if raw_size is not None else int(size * h / w)
    else:
        oh = size
        ow = int(raw_size * w / h) if raw_size is not None else int(size * w / h)
    # Two deviations where HF's own output cannot feed a static TPU bucket:
    # the equality branch can return original dims ONE pixel over `longest`
    # (e.g. 666x1334 -> HF keeps 1334; clamp to the bucket), and extreme
    # aspect ratios can truncate an edge to 0 (HF would crash in PIL too).
    return max(1, min(oh, longest)), max(1, min(ow, longest))


def ragged_canvas_supported(spec: PreprocessSpec) -> bool:
    """Only shortest_edge specs (the DETR family) have a variable valid
    region inside their static bucket — the slack the ragged scheduler
    (ISSUE 9) exploits by staging into a smaller padded canvas. fixed /
    pad_square specs fill their whole canvas with signal."""
    return spec.mode == "shortest_edge"


def _canvas_for(
    spec: PreprocessSpec,
    canvas_hw: tuple[int, int] | None,
    resized_hw: tuple[int, int],
) -> tuple[int, int]:
    """Resolve the padded canvas a shortest_edge image stages into: the
    scheduler's ragged canvas when given (must cover the resize — the
    scheduler guarantees it; a too-small canvas is a caller bug and fails
    loudly rather than silently cropping), else the static bucket."""
    if canvas_hw is None:
        return spec.input_hw
    ch, cw = int(canvas_hw[0]), int(canvas_hw[1])
    rh, rw = resized_hw
    if rh > ch or rw > cw:
        raise ValueError(
            f"ragged canvas {ch}x{cw} cannot hold resized image {rh}x{rw}"
        )
    return ch, cw


def _slot(out, shape: tuple[int, ...], dtype) -> np.ndarray:
    """The caller's destination for one image's array, checked, or a new
    array when it gave none (every byte of it is then written below)."""
    if out is None:
        return np.empty(shape, dtype=dtype)
    if out.shape != shape or out.dtype != dtype:
        raise ValueError(
            f"destination {out.dtype}{out.shape} is not the "
            f"{np.dtype(dtype)}{shape} this image stages into"
        )
    return out


def preprocess_image(
    image: Image.Image,
    spec: PreprocessSpec,
    canvas_hw: tuple[int, int] | None = None,
    out: tuple[np.ndarray, np.ndarray | None] | None = None,
) -> tuple[np.ndarray, np.ndarray, tuple[int, int]]:
    """PIL image -> (pixels NHWC-sans-N float32, pixel_mask (H, W) float32, orig (h, w)).

    pixel_mask is all-ones for fixed mode; for shortest_edge mode it marks valid
    (non-pad) pixels, the analog of HF DETR's pixel_mask. `canvas_hw`
    (ragged batching, ISSUE 9) shrinks the shortest_edge pad target below
    the static bucket; ignored for modes whose canvas IS the signal.

    `out` (in-place staging, ISSUE 27): `(pixels, mask)` destinations of the
    canvas's shape, written in place and returned, so that a batch is
    staged without a copy: the last arithmetic step writes the pixels
    there, and every byte is what the arrays made without `out` hold. The
    mask may be None where it is all ones (`fixed`, `pad_square`) and the
    caller's already is.
    """
    check_image_pixels(image)
    orig_hw = (image.height, image.width)
    out_px, mask = out if out is not None else (None, None)
    normalized = spec.mean is not None and spec.std is not None

    def normalize(a: np.ndarray, dst: np.ndarray) -> None:
        np.divide(
            a - np.asarray(spec.mean, dtype=np.float32),
            np.asarray(spec.std, dtype=np.float32),
            out=dst,
        )

    def rescale_normalize(a: np.ndarray, dst: np.ndarray) -> None:
        if normalized:
            normalize(a * spec.rescale_factor, dst)
        else:
            np.multiply(a, spec.rescale_factor, out=dst)

    if spec.mode == "fixed":
        th, tw = spec.size
        resized = image.resize((tw, th), resample=spec.resample)
        arr = _slot(out_px, (th, tw, 3), np.float32)
        rescale_normalize(np.asarray(resized, dtype=np.float32), arr)
        if out is None:
            mask = np.ones((th, tw), dtype=np.float32)
    elif spec.mode == "pad_square":
        # OWLv2: rescale to [0,1], pad bottom/right to square with 0.5 gray,
        # resize the PADDED square to `size`, then normalize — the exact HF
        # Owlv2ImageProcessor order (pad → skimage-style warp), so patch
        # features across the content/gray seam match the torch pipeline
        # pixel-for-pixel (tests/test_preprocess.py pins this). Boxes come
        # back in padded-square coordinates, hence the (max, max) size.
        import scipy.ndimage as ndi  # the HF processor itself requires scipy

        th, tw = spec.size
        h, w = orig_hw
        side = max(h, w)
        padded = np.full((side, side, 3), 0.5, dtype=np.float32)
        padded[:h, :w] = np.asarray(image, dtype=np.float32) * spec.rescale_factor
        # skimage.transform.resize semantics (anti_aliasing=True, order=1,
        # mode="mirror", grid_mode zoom), as vendored by the HF processor
        factors = np.divide(padded.shape, (th, tw, 3))
        sigma = np.maximum(0.0, (factors - 1.0) / 2.0)
        filtered = (
            ndi.gaussian_filter(padded, sigma, mode="mirror") if sigma.any() else padded
        )
        zoomed = ndi.zoom(
            filtered, 1.0 / factors, order=1, mode="mirror", grid_mode=True
        )
        warped = np.clip(zoomed, padded.min(), padded.max()).astype(np.float32)
        arr = _slot(out_px, (th, tw, 3), np.float32)
        if normalized:
            normalize(warped, arr)
        else:
            arr[...] = warped
        if out is None:
            mask = np.ones((th, tw), dtype=np.float32)
        orig_hw = (side, side)
    elif spec.mode == "shortest_edge":
        rh, rw = shortest_edge_size(orig_hw, spec.size[0], spec.size[1])
        resized = image.resize((rw, rh), resample=spec.resample)
        ph, pw = _canvas_for(spec, canvas_hw, (rh, rw))
        arr = _slot(out_px, (ph, pw, 3), np.float32)
        mask = _slot(mask, (ph, pw), np.float32)
        # Normalize BEFORE padding: pad pixels must be exactly 0 (the torch
        # DETR processor pads after normalization; checkpoints expect 0 pads).
        # Only the pad margin is zeroed: the valid region is written once.
        rescale_normalize(np.asarray(resized, dtype=np.float32), arr[:rh, :rw])
        arr[rh:] = 0.0
        arr[:rh, rw:] = 0.0
        mask[:rh, :rw] = 1.0
        mask[rh:] = 0.0
        mask[:rh, rw:] = 0.0
    else:
        raise ValueError(f"Unknown preprocess mode: {spec.mode}")

    return arr, mask, orig_hw


def batch_images(
    images: list[Image.Image], spec: PreprocessSpec
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack preprocessed images -> (pixels (B,H,W,3), masks (B,H,W), sizes (B,2) [h,w])."""
    pixels, masks, sizes = [], [], []
    for img in images:
        p, m, hw = preprocess_image(img, spec)
        pixels.append(p)
        masks.append(m)
        sizes.append(hw)
    return (
        np.stack(pixels),
        np.stack(masks),
        np.asarray(sizes, dtype=np.float32),
    )


# --- uint8 zero-copy ingest + on-device preprocess (ISSUE 3) -----------------
#
# The host float path above ships (B, H, W, 3) float32 pixels plus a
# (B, H, W) float32 mask per batch — 16 bytes/pixel of H2D traffic, with the
# rescale/normalize arithmetic on a single host core. The uint8 path keeps
# only decode + resize-to-bucket on the host (PIL releases the GIL, so a
# DecodePool parallelizes it), ships 3 bytes/pixel of uint8 NHWC plus a
# (B, 2) valid-region tensor, and runs rescale/normalize/mask inside the
# SAME jit program as the model forward (`device_rescale_normalize`), where
# XLA fuses it into the first conv's input chain. Gated by
# SPOTTER_TPU_DEVICE_PREPROCESS in the engine; the float path stays for
# parity testing (tests/test_device_preprocess.py).

DECODE_WORKERS_ENV = "SPOTTER_TPU_DECODE_WORKERS"


def device_preprocess_supported(spec: PreprocessSpec) -> bool:
    """pad_square (OWLv2) rescales BEFORE its skimage-style warp, so its
    host work is inherently float — only the fixed/shortest_edge families
    can defer rescale/normalize to the device."""
    return spec.mode in ("fixed", "shortest_edge")


class DecodePool:
    """Thread pool for the per-image host work of staging: decode/resize
    (all that is left under device preprocess), and on the float path the
    rescale and normalize, each task writing its image into its own row of
    the batch's staging buffer (engine/staging.py). PIL's resize and the
    numpy arithmetic release the GIL, so the tasks run side by side (5.3
    wide on 13 shared cores, PERF.md section 5); workers default to
    SPOTTER_TPU_DECODE_WORKERS or a core-count heuristic. `queue_depth()`
    (submitted-but-unfinished items) feeds the /metrics gauge that shows
    when decode — not the device — is the binding constraint."""

    def __init__(self, workers: int | None = None) -> None:
        if workers is None:
            raw = os.environ.get(DECODE_WORKERS_ENV, "").strip()
            workers = int(raw) if raw else min(8, max(2, (os.cpu_count() or 2) - 1))
        self.workers = max(1, workers)
        self._pool = (
            ThreadPoolExecutor(
                max_workers=self.workers, thread_name_prefix="spotter-decode"
            )
            if self.workers > 1
            else None
        )
        self._pending = 0
        self._lock = threading.Lock()

    def queue_depth(self) -> int:
        with self._lock:
            return self._pending

    def map(self, fn, items: list) -> list:
        """Ordered map over the pool (serial for 1 worker / 1 item)."""
        if self._pool is None or len(items) <= 1:
            return [fn(item) for item in items]
        with self._lock:
            self._pending += len(items)

        def run(item):
            try:
                return fn(item)
            finally:
                with self._lock:
                    self._pending -= 1

        return list(self._pool.map(run, items))

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)


def decode_resize_uint8(
    image: Image.Image,
    spec: PreprocessSpec,
    canvas_hw: tuple[int, int] | None = None,
    out: np.ndarray | None = None,
) -> tuple[np.ndarray, tuple[int, int], tuple[int, int]]:
    """PIL image -> (uint8 (H, W, 3) in the static bucket, valid (h, w), orig (h, w)).

    Host half of the split preprocess: decode + resize only, same resample
    filter and shortest-edge arithmetic as `preprocess_image` (golden parity
    depends on them) — rescale/normalize/mask move to the device.
    `canvas_hw` (ragged batching, ISSUE 9) shrinks the shortest_edge pad
    target below the static bucket. `out` (ISSUE 27): the canvas-shaped
    destination the bytes are written to and that is returned, as in
    `preprocess_image`.
    """
    check_image_pixels(image)
    orig_hw = (image.height, image.width)
    if spec.mode == "fixed":
        th, tw = spec.size
        resized = image.resize((tw, th), resample=spec.resample)
        if out is None:
            return np.asarray(resized, dtype=np.uint8), (th, tw), orig_hw
        arr = _slot(out, (th, tw, 3), np.uint8)
        arr[...] = np.asarray(resized, dtype=np.uint8)
        return arr, (th, tw), orig_hw
    if spec.mode == "shortest_edge":
        rh, rw = shortest_edge_size(orig_hw, spec.size[0], spec.size[1])
        resized = image.resize((rw, rh), resample=spec.resample)
        arr = _slot(out, (*_canvas_for(spec, canvas_hw, (rh, rw)), 3), np.uint8)
        arr[:rh, :rw] = np.asarray(resized, dtype=np.uint8)
        arr[rh:] = 0
        arr[:rh, rw:] = 0
        return arr, (rh, rw), orig_hw
    raise ValueError(f"device preprocess does not support mode: {spec.mode}")


def device_rescale_normalize(pixels_u8, valid_hw, spec: PreprocessSpec):
    """Device half of the split preprocess (traced inside the forward jit).

    uint8 NHWC + per-image valid (h, w) -> (float32 pixels, float32 mask),
    matching `preprocess_image`'s output: rescale, normalize, then zero the
    pad region (the torch DETR processor pads AFTER normalization, so pad
    pixels must be exactly 0, not (0 - mean)/std). Fused into the forward
    program, so the intermediate float tensor never exists in host memory
    and the uint8 input buffer is donated.
    """
    import jax.numpy as jnp

    x = pixels_u8.astype(jnp.float32) * spec.rescale_factor
    if spec.mean is not None and spec.std is not None:
        x = (x - jnp.asarray(spec.mean, dtype=jnp.float32)) / jnp.asarray(
            spec.std, dtype=jnp.float32
        )
    b, h, w = pixels_u8.shape[:3]
    if spec.mode == "fixed":
        return x, jnp.ones((b, h, w), dtype=jnp.float32)
    rows = jnp.arange(h, dtype=jnp.int32)[None, :] < valid_hw[:, :1]  # (B, H)
    cols = jnp.arange(w, dtype=jnp.int32)[None, :] < valid_hw[:, 1:]  # (B, W)
    mask = (rows[:, :, None] & cols[:, None, :]).astype(jnp.float32)
    return x * mask[..., None], mask
