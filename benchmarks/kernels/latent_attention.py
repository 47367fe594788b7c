"""Causal softmax attention over keys and values expanded a head out of a
shared latent, in a configuration whose every layer is latent attention
(`joyai_flash_det`): the count of kernels/mla_causal_attention.py, whose
functions it takes, over `num_hidden_layers` latent-attention layers, where
that file counts the layers its configuration lists in
`linear_attn_config.full_attn_layers`. Per image and layer, H heads over T
tokens, keys of Dk = 192 (the rotary term is part of the keys), values of Dv =
128:

    operations = 2 * (Dk + Dv) * H * T (T + 1) / 2     scores, and their use
    bytes      = q and k at Dk, v and the result at Dv, H heads, served type

The projections, the query latent, the expansion of the latent and the
rotation are counted with the projections (kernels/joyai_flash_det_forward.py).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from mla_causal_attention import BYTES, images_of_event, is_kernel_event, key_width, tokens  # noqa: E402, F401


def layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def operations_per_image(cfg: dict) -> float:
    t = tokens(cfg)
    return float(2 * (key_width(cfg) + cfg["v_head_dim"]) * cfg["num_attention_heads"]
                 * (t * (t + 1) // 2) * layers(cfg))


def bytes_per_image(cfg: dict) -> float:
    width = BYTES[cfg["serve"]["dtype_policy"]]
    per_token = cfg["num_attention_heads"] * (2 * key_width(cfg) + 2 * cfg["v_head_dim"])
    return float(per_token * tokens(cfg) * width * layers(cfg))


def least_seconds(cfg: dict, peaks: dict) -> float:
    """Per image (all latent-attention layers)."""
    by_ops = operations_per_image(cfg) / (peaks["bf16_tflops"] * 1e12)
    by_bytes = bytes_per_image(cfg) / (peaks["hbm_gb_per_s"] * 1e9)
    return max(by_ops, by_bytes)
