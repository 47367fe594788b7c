"""Program layer: how far apart the key channels of a KDA head decay, in nats a
token: `kda_gate_spread_total` (per image served, summed over KDA layers and
heads: the mean over the image's tokens of max_d(-g) - min_d(-g)) over
`kda_gate_heads_total` (the heads so counted), of the images served in the
window. 0 says the per-channel gate is a scalar gate in disguise, and the
kernel's distinct path untested; a program with no such counter reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    spread = _program.counter_delta(ctx, "kda_gate_spread_total")
    heads = _program.counter_delta(ctx, "kda_gate_heads_total")
    if spread is None or not heads or heads <= 0:
        return None
    return spread / heads
