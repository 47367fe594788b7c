"""Engine layer: the caller's own copies inside the `decode` stage
(`engine.stack_pad`: `np.stack` of the batch, then the `concatenate`s that
pad it to its bucket), wall ms per image the engine served."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    return _program.spans_per_image(ctx, ["engine.stack_pad"])
