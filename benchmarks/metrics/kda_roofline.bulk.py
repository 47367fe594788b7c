"""Kernels layer: the least time the chip could take for the chunked Kimi Delta
Attention recurrence the KDA layers need (operations and bytes from shapes,
kernels/kda.py, whatever implements it: the float32 gate a key channel makes it
a matter of bytes), over the summed device time of the `kda_kernel` events in
the trace. A program with no such kernel reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernel_share  # noqa: E402


def read(ctx):
    return _kernel_share.share(ctx, "kda")
