"""Kernels layer: the least time the chip could take for the causal attention
that every latent-attention layer of the configuration needs once keys and
values are expanded a head (operations and bytes from shapes,
kernels/latent_attention.py: keys of 192, values of 128, the half of the
square under the diagonal, `num_hidden_layers` layers), over the summed device
time of the `splash_mha_fwd_no_residuals` events in the trace. A program with
no such kernel reads None."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _kernel_share  # noqa: E402


def read(ctx):
    return _kernel_share.share(ctx, "latent_attention")
