"""Detector layer: the `/metrics` postprocess stage (threshold, amenity filter,
draw, JPEG-encode) summed over the window, per image the engine served."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _delta  # noqa: E402


def read(ctx):
    return _delta.stage_per_image(ctx, ["postprocess"])
