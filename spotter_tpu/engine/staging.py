"""Host staging buffers the engine leases a batch at a time (ISSUE 27).

A batch used to be written three times on its way to the device: each
decode-pool task allocated its own image, the caller `np.stack`ed them into
a third allocation, and the pad to the bucket copied all of that again, the
last two on one thread and into pages the kernel had to fault in first.
Here the batch's arrays exist once: a `Slab` is one flat allocation for the
pixels and one for the array beside them (the float path's mask, the uint8
path's valid regions), sized for the ladder's largest rung at the spec's
static canvas. A batch takes views of the front of it, each pool task
writes its image into its own row, and a slab that comes back is reused,
its pages resident.

The lease outlives the upload: `jax.device_put` of a numpy array is
asynchronous, the runtime may read the host memory until the transfer
completes, and on the CPU backend the device array may alias it outright.
So the engine returns a slab only after the batch's outputs are on the host
(`InferenceEngine._finish`), and a batch that fails anywhere before that
drops its slab: a pool task may still be writing into it.
"""

import threading

import numpy as np

from spotter_tpu.ops.preprocess import PreprocessSpec

# What the free-list keeps: the batcher runs two batches at a time
# (`max_in_flight` 2), each holding its slab from staging to fetch, and one
# multi-chunk `detect()` holds two by itself. More than that is allocated on
# demand and not kept.
KEEP_SLABS = 3


class Slab:
    """One batch's host arrays. `pixels` is flat, in the dtype the ingest
    path stages; `second` is the float mask (flat) or the uint8 path's
    `(rows, 2)` valid regions. A mask that is all ones for every image of
    the spec (`fixed`, `pad_square`) is written here, once, and never
    again."""

    def __init__(self, rows: int, hw: tuple[int, int], uint8: bool,
                 mask_is_ones: bool) -> None:
        self.uint8 = uint8
        self.area = rows * hw[0] * hw[1]
        self.pixels = np.empty(self.area * 3, np.uint8 if uint8 else np.float32)
        if uint8:
            self.second = np.empty((rows, 2), np.int32)
        elif mask_is_ones:
            self.second = np.ones(self.area, np.float32)
        else:
            self.second = np.empty(self.area, np.float32)

    def views(self, bucket: int, h: int, w: int) -> tuple[np.ndarray, np.ndarray]:
        """`(bucket, h, w, 3)` pixels and the array beside them, contiguous
        at the front of the slab: a smaller rung or a ragged canvas is a
        shorter view, nothing is sized per rung."""
        px = bucket * h * w
        pixels = self.pixels[: px * 3].reshape(bucket, h, w, 3)
        if self.uint8:
            return pixels, self.second[:bucket]
        return pixels, self.second[:px].reshape(bucket, h, w)


class StagingSlabs:
    """The engine's free-list of slabs for one placement: its size follows
    from the ladder and the spec, which the engine already holds."""

    def __init__(self, rows: int, spec: PreprocessSpec, uint8: bool, metrics) -> None:
        self.rows, self.hw, self.uint8 = rows, spec.input_hw, uint8
        self.mask_is_ones = spec.mode != "shortest_edge"
        self.area = rows * self.hw[0] * self.hw[1]
        self._metrics = metrics
        self._free: list[Slab] = []
        self._lock = threading.Lock()

    def lease(self, bucket: int, h: int, w: int) -> Slab:
        """A slab that holds `(bucket, h, w)`: a free one, else a new one.
        A canvas the static one cannot hold gets a slab of its own size,
        which `release` will not keep."""
        fits = bucket * h * w <= self.area
        with self._lock:
            slab = self._free.pop() if fits and self._free else None
        self._metrics.record_slab_lease(allocated=slab is None)
        if slab is None:
            rows, hw = (self.rows, self.hw) if fits else (bucket, (h, w))
            slab = Slab(rows, hw, self.uint8, self.mask_is_ones)
        return slab

    def release(self, slab: Slab) -> None:
        """Back to the free-list: only ever called once the batch's outputs
        are on the host."""
        with self._lock:
            if slab.area == self.area and len(self._free) < KEEP_SLABS:
                self._free.append(slab)

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)
