"""Engine layer: the share of the traced window in which the chip ran no
program and Python's collector was running (`python.gc`: a collection stops
every thread, so whatever the chip waited on waited on it). Each idle
instant goes to the first class open, in the order gc, staging, result,
upstream, unseen (_timeline.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _timeline  # noqa: E402


def read(ctx):
    return _timeline.share(ctx, "gc")
