"""Multi-scale deformable attention's sampling, as the algorithm needs it:
operations and bytes from the configuration's shapes, whatever implements it.

Per image and decoder layer, Q queries x H heads x L levels x P points each
read 4 neighbours of a D-wide value row, blend them bilinearly, and add the
result into the head's output with the point's attention weight:

    operations = Q*H*L*P*D * (4 corners * 2 + 2)
    bytes      = value maps read once (sum over levels of h*w, x H*D, in the
                 served type) + locations and weights in (float32, 3 numbers
                 a point) + the output out (Q x H*D, served type)

The least time is the larger of operations over the chip's peak and bytes over
its memory bandwidth; at these shapes the bytes bound it by far.
"""

import re

BYTES = {"bfloat16": 2, "float32": 4}
INPUT_HW = (640, 640)
# The kernel's events on the device's "XLA Ops" line (looked at by hand in a
# trace of r101_bulk, PR 25): the Pallas kernel is a custom call named after
# the module that calls it, "%encoder_attn.7 = f32[224,320,32]{...}
# custom-call(...)", one per decoder layer and program run.
EVENT_MARKS = ("encoder_attn", "msda", "deformable")


def is_kernel_event(name: str) -> bool:
    head, _, rest = name.partition(" = ")
    low = head.lower()
    if not any(mark in low for mark in EVENT_MARKS):
        return False
    return "custom-call(" in rest or not rest  # a bare name (tests) counts too


def images_of_event(name: str, cfg: dict) -> int | None:
    """The images a kernel event worked on, from its own shape: the custom
    call's result is (images x heads, queries padded, head_dim)."""
    match = re.search(r" = \w+\[(\d+),", name)
    if not match or not is_kernel_event(name):
        return None
    rows, heads = int(match.group(1)), cfg["decoder_attention_heads"]
    return rows // heads if rows % heads == 0 else None


def shapes(cfg: dict) -> dict:
    h, w = INPUT_HW
    tokens = sum((h // s) * (w // s) for s in cfg["feat_strides"][: cfg["num_feature_levels"]])
    heads = cfg["decoder_attention_heads"]
    return {
        "layers": cfg["decoder_layers"], "queries": cfg["num_queries"], "heads": heads,
        "levels": cfg["num_feature_levels"], "points": cfg["decoder_n_points"],
        "head_dim": cfg["d_model"] // heads, "tokens": tokens,
    }


def operations_per_image(cfg: dict) -> float:
    s = shapes(cfg)
    per_layer = s["queries"] * s["heads"] * s["levels"] * s["points"] * s["head_dim"] * (4 * 2 + 2)
    return float(per_layer * s["layers"])


def bytes_per_image(cfg: dict) -> float:
    s = shapes(cfg)
    width = BYTES[cfg["serve"]["dtype_policy"]]
    values = s["tokens"] * s["heads"] * s["head_dim"] * width
    points = s["queries"] * s["heads"] * s["levels"] * s["points"] * 3 * 4
    out = s["queries"] * s["heads"] * s["head_dim"] * width
    return float((values + points + out) * s["layers"])


def least_seconds(cfg: dict, peaks: dict) -> float:
    """Per image (all decoder layers)."""
    by_ops = operations_per_image(cfg) / (peaks["bf16_tflops"] * 1e12)
    by_bytes = bytes_per_image(cfg) / (peaks["hbm_gb_per_s"] * 1e9)
    return max(by_ops, by_bytes)
