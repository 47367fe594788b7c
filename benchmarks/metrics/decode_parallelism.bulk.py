"""Engine layer: thread CPU seconds of the decode pool's per-image work
(`engine.preprocess_image`) over the wall seconds of the `decode` stage
(`engine.decode`), over the window. 1 is one core's worth (the tasks
serialise, on the GIL or otherwise), 8 is the whole pool."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    cpu_ms = _program.span_delta(ctx, "engine.preprocess_image", "cpu_ms")
    wall_ms = _program.span_delta(ctx, "engine.decode", "wall_ms")
    if cpu_ms is None or wall_ms is None or wall_ms <= 0:
        return None
    return cpu_ms / wall_ms
