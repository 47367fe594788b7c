"""Engine layer: the share of the traced window in which the chip ran no
program and the next program's inputs were being made (`engine.decode` and
its children, `engine.h2d` and its children, `engine.dispatch`), no
collection running. Each idle instant goes to the first class open, in the
order gc, staging, result, upstream, unseen (_timeline.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _timeline  # noqa: E402


def read(ctx):
    return _timeline.share(ctx, "staging")
