"""Program layer: the share of the traced forward programs' device time that
the latent-attention kernel takes: the summed device time of the
`splash_mha_fwd_no_residuals` events over the summed time of the programs
("XLA Modules") the configuration's `bench.forward` file takes for forward
passes. In a step whose every mixer is latent attention it says whether the
step's time is the kernel or what XLA does around it: the query latent, the
rotary term, the expansion of the latent, the pads at 192 lanes. A program
with no such kernel reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernel_share  # noqa: E402


def read(ctx):
    seconds = _kernel_share.kernel_seconds(ctx, ctx["kernel"]("latent_attention"))
    if seconds is None:
        return None
    forward = ctx["kernel"](ctx["config"]["bench"]["forward"])
    _, programs = forward.slots_in_trace(ctx["config"], ctx["trace"])
    if programs <= 0:
        return None
    return 100.0 * seconds / programs
