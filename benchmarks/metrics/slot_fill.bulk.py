"""Batcher layer: images over the bucket slots of the batches that finished,
over the whole window, from the engine's own counters (`images_total`,
`slots_total`: both grow when a batch finishes, so no batch is counted on
one side only)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    images = _program.counter_delta(ctx, "images_total")
    slots = _program.counter_delta(ctx, "slots_total")
    if images is None or slots is None or slots <= 0:
        return None
    return 100.0 * images / slots
