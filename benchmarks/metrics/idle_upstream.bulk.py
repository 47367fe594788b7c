"""Detector layer: the share of the traced window in which the chip ran no
program, the engine neither staged nor took off a result, and an image was
inside the server (`batcher.queue_wait`, `detector.image` and its children,
the `/detect` handler `app.detect`, `app.serialize`). Each idle instant goes
to the first class open, in the order gc, staging, result, upstream, unseen
(_timeline.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _timeline  # noqa: E402


def read(ctx):
    return _timeline.share(ctx, "upstream")
