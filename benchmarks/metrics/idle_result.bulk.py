"""Engine layer: the share of the traced window in which the chip ran no
program, none was being staged, and the last result was being taken off
(`engine.device_wait` after its program's end, `engine.postprocess`): the
stretch the starvation clock books as in flight. Each idle instant goes to
the first class open, in the order gc, staging, result, upstream, unseen
(_timeline.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _timeline  # noqa: E402


def read(ctx):
    return _timeline.share(ctx, "result")
