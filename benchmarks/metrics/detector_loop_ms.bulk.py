"""Detector layer: what the one event-loop thread does inline per image: PIL
decode (`detector.pil_decode`), draw + JPEG-encode + base64
(`detector.draw_encode`) and the reply built and dumped (`app.serialize`),
wall ms per image the engine served. 1000 over it is the loop's ceiling in
images a second."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    return _program.spans_per_image(
        ctx, ["detector.pil_decode", "detector.draw_encode", "app.serialize"])
