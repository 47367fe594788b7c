"""Detector layer: the share of the window in which the engine had no program
in flight and no batch staging while at least one image was inside the
detector (`starved_upstream_s_total`: in fetch, PIL decode or the batcher's
queue, or its reply being drawn and encoded): the chip waits for the event
loop. A lower bound of the device's idle share."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    return _program.share_of_window(ctx, "starved_upstream_s_total")
