"""Program layer: the share of the k-a-token selections that the router's
selection bias moved: those not among the k best of the unbiased scores
(`moe_bias_moved_total`) over all selections (`moe_assignments_total`), of the
images served in the window. 0 says the bias is dead weight (or zero); a
program whose router has no bias, or no such counter, reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _program  # noqa: E402


def read(ctx):
    moved = _program.counter_delta(ctx, "moe_bias_moved_total")
    assignments = _program.counter_delta(ctx, "moe_assignments_total")
    if moved is None or not assignments or assignments <= 0:
        return None
    return 100.0 * moved / assignments
