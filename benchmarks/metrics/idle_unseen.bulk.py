"""Device layer: the share of the traced window in which the chip ran no
program and no span of the server was open: what the program does not see
(a request not yet in a handler, a reply on its way out, the clients'
turn). Each idle instant goes to the first class open, in the order gc,
staging, result, upstream, unseen (_timeline.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _timeline  # noqa: E402


def read(ctx):
    return _timeline.share(ctx, "unseen")
