"""Program layer: the share of the traced forward programs' device time that
the KDA recurrence's kernel takes: the summed device time of the `kda_kernel`
events over the summed time of the programs ("XLA Modules") the configuration's
`bench.forward` file takes for forward passes. It says whether a KDA layer's
time is its recurrence or what XLA does around it (the projections, the three
short convolutions, the gates, the running sum of the float32 gate). A program
with no such kernel reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernel_share  # noqa: E402


def read(ctx):
    seconds = _kernel_share.kernel_seconds(ctx, ctx["kernel"]("kda"))
    if seconds is None:
        return None
    forward = ctx["kernel"](ctx["config"]["bench"]["forward"])
    _, programs = forward.slots_in_trace(ctx["config"], ctx["trace"])
    if programs <= 0:
        return None
    return 100.0 * seconds / programs
