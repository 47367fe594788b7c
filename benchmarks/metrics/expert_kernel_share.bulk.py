"""Program layer: the share of the traced forward programs' device time that
the grouped expert products themselves take: the summed device time of the
`expert_matmul_kernel` events over the summed time of the programs ("XLA
Modules") the configuration's `bench.forward` file takes for forward passes.
It says whether a routed layer's time is its products or the loop around them
(the plan, the gathers, the scatter-add). A program with no such kernel reads
None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernel_share  # noqa: E402


def read(ctx):
    seconds = _kernel_share.kernel_seconds(ctx, ctx["kernel"]("expert_matmul"))
    if seconds is None:
        return None
    forward = ctx["kernel"](ctx["config"]["bench"]["forward"])
    _, programs = forward.slots_in_trace(ctx["config"], ctx["trace"])
    if programs <= 0:
        return None
    return 100.0 * seconds / programs
