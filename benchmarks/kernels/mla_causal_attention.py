"""Causal softmax attention over keys and values expanded a head out of a
shared latent (multi-head latent attention as a prefill runs it), as the
algorithm needs it: operations and bytes from the configuration's shapes,
whatever implements it (the program runs the splash kernel's multi-head form
with `head_dim_v` narrower than the keys, spotter_tpu/models/layers.py:
causal_latent_attention).

Per image and layer, H heads over T tokens, keys of Dk = qk_nope_head_dim +
qk_rope_head_dim (192), values of Dv = v_head_dim (128); token t attends to
t + 1 tokens:

    operations = 2 * (Dk + Dv) * H * T (T + 1) / 2     scores, and their use
    bytes      = q and k at Dk, v and the result at Dv, H heads, served type

T is the configuration's own token count (4300), not the multiple of the
kernel's block it is padded to, and the half of the square above the diagonal
is not counted: a kernel that computes it gains nothing here. The expansion of
the latent (`kv_b_proj`) is a projection and counted with the projections
(kernels/kimi_linear_det_forward.py).
"""

import re

BYTES = {"bfloat16": 2, "float32": 4}
EVENT_MARK = "splash_mha_fwd_no_residuals"


def is_kernel_event(name: str) -> bool:
    head, _, rest = name.partition(" = ")
    if EVENT_MARK not in head.lower():
        return False
    return "custom-call(" in rest or not rest


def images_of_event(name: str, cfg: dict) -> int | None:
    """From the event's own result: the member of shape (images, heads, tokens
    padded, value width)."""
    if not is_kernel_event(name):
        return None
    result = name.partition(" = ")[2].partition(" custom-call(")[0]
    for dims in re.findall(r"\w+\[([\d,]+)\]", result):
        dims = [int(d) for d in dims.split(",")]
        if len(dims) == 4 and dims[1:4:2] == [cfg["num_attention_heads"], cfg["v_head_dim"]]:
            return dims[0]
    return None


def tokens(cfg: dict) -> int:
    h, w = cfg["image_size"]
    return (h // cfg["patch_size"]) * (w // cfg["patch_size"]) + cfg["num_detection_tokens"]


def layers(cfg: dict) -> int:
    return len(cfg["linear_attn_config"]["full_attn_layers"])


def key_width(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


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
