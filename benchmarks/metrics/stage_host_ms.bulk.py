"""Engine layer: the `/metrics` decode and h2d stages (decode, host resize,
staging, transfer) summed over the window, per image the engine served."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _delta  # noqa: E402


def read(ctx):
    return _delta.stage_per_image(ctx, ["decode", "h2d"])
