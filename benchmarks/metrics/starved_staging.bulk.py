"""Engine layer: the share of the window in which the engine had no program
dispatched and not yet fetched while at least one batch was being staged
(`starved_staging_s_total`): the chip waits for the engine's host work. A
lower bound of the device's idle share."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    return _program.share_of_window(ctx, "starved_staging_s_total")
