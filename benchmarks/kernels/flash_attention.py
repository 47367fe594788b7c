"""Unmasked multi-head self-attention over a ViT's tokens, as the algorithm
needs it: operations and bytes from the configuration's shapes, whatever
implements it (the program runs a Pallas splash/flash kernel, layers.py).

Per image and layer, H heads of width D over T tokens:

    operations = 2 * 2*T*T*(H*D)       scores, and their use on the values
    bytes      = q, k, v in and the result out: 4 * T*(H*D), in the served type

At a ViT detector's token counts the operations bound it by far (4301 tokens:
57 GFLOP against 26 MB a layer), so the least time is operations over the
chip's peak in the served type. T is the configuration's own token count, not
the multiple of the kernel's block it is padded to: padding is the
implementation's cost.
"""

import re

BYTES = {"bfloat16": 2, "float32": 4}
# The kernel's events on the device's "XLA Ops" line (looked at by hand in a
# trace of yolos_base_bulk, PR 25): the Pallas kernel is a custom call named
# after the kernel's own function, its result a tuple whose last member is
# the attention's output (images, heads, tokens padded to the block, head
# width): "%splash_mha_fwd_segmented_no_residuals.12 = (f32[48,512,128]{...},
# f32[48,512,128]{...}, f32[48,512,64]{...}, bf16[48,12,4608,64]{...})
# custom-call(...)", one per layer and program run.
EVENT_MARKS = ("splash_mha", "flash_attention", "mha_fwd")


def is_kernel_event(name: str) -> bool:
    head, _, rest = name.partition(" = ")
    if not any(mark in head.lower() for mark in EVENT_MARKS):
        return False
    return "custom-call(" in rest or not rest  # a bare name (tests) counts too


def images_of_event(name: str, cfg: dict) -> int | None:
    """The images a kernel event worked on, from its own result: the member
    of shape (images, heads, tokens, head width)."""
    if not is_kernel_event(name):
        return None
    result = name.partition(" = ")[2].partition(" custom-call(")[0]
    heads = cfg["num_attention_heads"]
    width = cfg["hidden_size"] // heads
    for dims in re.findall(r"\w+\[([\d,]+)\]", result):
        dims = [int(d) for d in dims.split(",")]
        if len(dims) == 4 and dims[1] == heads and dims[3] == width:
            return dims[0]
    return None


def tokens(cfg: dict) -> int:
    h, w = cfg["image_size"]
    return 1 + (h // cfg["patch_size"]) * (w // cfg["patch_size"]) + cfg["num_detection_tokens"]


def operations_per_image(cfg: dict) -> float:
    t = tokens(cfg)
    return float(2 * 2 * t * t * cfg["hidden_size"] * cfg["num_hidden_layers"])


def bytes_per_image(cfg: dict) -> float:
    width = BYTES[cfg["serve"]["dtype_policy"]]
    return float(4 * tokens(cfg) * cfg["hidden_size"] * width * cfg["num_hidden_layers"])


def least_seconds(cfg: dict, peaks: dict) -> float:
    """Per image (all layers)."""
    by_ops = operations_per_image(cfg) / (peaks["bf16_tflops"] * 1e12)
    by_bytes = bytes_per_image(cfg) / (peaks["hbm_gb_per_s"] * 1e9)
    return max(by_ops, by_bytes)
