"""Kimi Delta Attention's recurrence (the delta rule with a decay a key
channel), as the chunked algorithm needs it: operations and bytes from the
configuration's shapes, whatever implements it (the program runs a Pallas
kernel, spotter_tpu/ops/kda.py; its events are `kda_kernel`, which no reader of
`gated_delta_rule` events counts).

Per head and chunk of C tokens (key and value width d = head_dim, a state of
d x d carried from chunk to chunk), 2 operations a multiply-add, counted as
kernels/gated_delta_rule.py counts the scalar-gated rule:

    k k^T and q k^T                          2 * 2*C*C*d
    T (beta v) and T (beta exp(G) k)         2 * 2*C*C*d
    w S, (exp(G) q) S, (decayed k)^T v'      3 * 2*C*d*d
    the chunk's scores on v'                 2*C*C*d
    the unit-triangular solve for T          2*C*C*C/3   (by substitution)

    bytes = q, k, v and the gate in, o out, all in the served type, a value a
            head, token AND key channel each (the scalar-gated rule's gate is
            one a head and token); beta in float32. The gate is counted at the
            served type's width, not float32's: an implementation can be handed
            the projection that feeds the gate and compute the float32 decay
            where it uses it (the program's kernel does), so no algorithm needs
            more, and the share errs low

What the per-channel decay adds is not counted: it sits inside the contraction
of k k^T and q k^T, and however an implementation splits the chunk to keep its
exponentials finite (six products for one, here), the algorithm needs those
two products once. The decays, normalisations and the gate are elementwise and
not counted either: the share errs low, never high. The chunk length is the
source's (64); tokens are the configuration's own (4300), not the multiple of
the chunk they are padded to.
"""

import re

BYTES = {"bfloat16": 2, "float32": 4}
CHUNK = 64
EVENT_MARK = "kda_kernel"


def is_kernel_event(name: str) -> bool:
    head, _, rest = name.partition(" = ")
    if EVENT_MARK not in head.lower():
        return False
    return "custom-call(" in rest or not rest  # a bare name (tests) counts too


def _width(cfg: dict) -> int:
    linear = cfg["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"]


def images_of_event(name: str, cfg: dict) -> int | None:
    """The images a kernel event worked on, from its own result: (images,
    tokens padded to the chunk, heads x head width)."""
    if not is_kernel_event(name):
        return None
    result = name.partition(" = ")[2].partition(" custom-call(")[0]
    for dims in re.findall(r"\w+\[([\d,]+)\]", result):
        dims = [int(d) for d in dims.split(",")]
        if len(dims) == 3 and dims[2] == _width(cfg):
            return dims[0]
    return None


def tokens(cfg: dict) -> int:
    h, w = cfg["image_size"]
    return (h // cfg["patch_size"]) * (w // cfg["patch_size"]) + cfg["num_detection_tokens"]


def layers(cfg: dict) -> int:
    return len(cfg["linear_attn_config"]["kda_layers"])


def operations_per_token(cfg: dict) -> float:
    """One layer, all heads."""
    c, d = CHUNK, cfg["linear_attn_config"]["head_dim"]
    chunk = (2 * 2 * c * c * d + 2 * 2 * c * c * d + 3 * 2 * c * d * d + 2 * c * c * d
             + 2 * c * c * c / 3)
    return cfg["linear_attn_config"]["num_heads"] * chunk / c


def operations_per_image(cfg: dict) -> float:
    return float(operations_per_token(cfg) * tokens(cfg) * layers(cfg))


def bytes_per_image(cfg: dict) -> float:
    width = BYTES[cfg["serve"]["dtype_policy"]]
    per_token = 5 * _width(cfg) * width + cfg["linear_attn_config"]["num_heads"] * 4
    return float(per_token * tokens(cfg) * layers(cfg))


def least_seconds(cfg: dict, peaks: dict) -> float:
    """Per image (all KDA layers)."""
    by_ops = operations_per_image(cfg) / (peaks["bf16_tflops"] * 1e12)
    by_bytes = bytes_per_image(cfg) / (peaks["hbm_gb_per_s"] * 1e9)
    return max(by_ops, by_bytes)
