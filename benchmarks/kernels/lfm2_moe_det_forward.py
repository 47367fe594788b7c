"""Operations of one forward pass of a `lfm2_moe_det` configuration, from its
shapes: a hand count of the matrix products the published layers need for one
image at the checkpoint's own `image_size` (2 operations a multiply-add).
tests/test_lfm2_moe_det.py holds it against hand-worked shapes.

    tokens T = (H/p)(W/p) patches + detection tokens
    patch projection        2 * patches * (p*p*channels) * d
    short-conv layer        in_proj (d -> 3 d), out_proj (d -> d)
    attention layer         q, k, v and out projections; causal scores and
                            their use (kernels/causal_gqa_attention.py)
    dense feed-forward      w1, w3, w2: three d x intermediate_size products
    routed feed-forward     the router (d -> experts); the experts' products
                            for the assignments (kernels/expert_matmul.py)
    heads                   two 3-layer MLPs over the detection tokens

Every expert is held here, so the routed assignments do not depend on the
routing: exactly T x num_experts_per_tok a routed layer, num_hidden_layers -
num_dense_layers routed layers. `flops_per_image(cfg, assignments)` takes
another count (the program's counter, all layers) where a caller has one.
Elementwise work (the gates around the conv and its 3 taps, the SwiGLU's
product), normalisations, the softmax and the sigmoid are not counted: a share
of the peak built on this count errs low.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _kernels():
    sys.path.insert(0, HERE)
    import causal_gqa_attention as attention
    import expert_matmul as experts

    return attention, experts


def tokens(cfg: dict) -> tuple[int, int]:
    """(all tokens, patch tokens)."""
    h, w = cfg["image_size"]
    patches = (h // cfg["patch_size"]) * (w // cfg["patch_size"])
    return patches + cfg["num_detection_tokens"], patches


def routed_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def assignments_per_image(cfg: dict) -> int:
    """All routed layers: each token's k selections, every one on a held expert."""
    return tokens(cfg)[0] * cfg["num_experts_per_tok"] * routed_layers(cfg)


def flops_by_part(cfg: dict, assignments: float | None = None) -> dict:
    attention, experts = _kernels()
    t, patches = tokens(cfg)
    d, det = cfg["hidden_size"], cfg["num_detection_tokens"]
    n_attention = sum(kind == "full_attention" for kind in cfg["layer_types"])
    assert n_attention == attention.layers(cfg), "full_attention_interval disagrees with layer_types"
    n_conv = cfg["num_hidden_layers"] - n_attention
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    if assignments is None:
        assignments = assignments_per_image(cfg)
    heads = 2 * det * (2 * d * d + d * (cfg["num_labels"] + 1)) + 2 * det * (2 * d * d + d * 4)
    return {
        "patch_projection": 2 * patches * (cfg["patch_size"] ** 2 * cfg["num_channels"]) * d,
        "short_conv_projections": n_conv * 2 * t * d * (3 * d + d),
        "attention_projections": n_attention * (2 * t * d * (q_width + 2 * kv_width)
                                                + 2 * t * q_width * d),
        "causal_attention": attention.operations_per_image(cfg),
        "dense_mlps": cfg["num_dense_layers"] * 3 * 2 * t * d * cfg["intermediate_size"],
        "routers": routed_layers(cfg) * 2 * t * d * cfg["num_experts"],
        "routed_experts": experts.operations(cfg, assignments),
        "heads": heads,
    }


def flops_per_image(cfg: dict, assignments: float | None = None) -> float:
    return float(sum(flops_by_part(cfg, assignments).values()))


def slots_in_trace(cfg: dict, trace: dict) -> tuple[float, float]:
    """(image slots the traced forward passes ran, their summed device
    seconds), per chip, from the trace alone. A forward pass runs the causal
    attention kernel once per attention layer, and each kernel event carries
    its images in its own shape (kernels/causal_gqa_attention.py): the slots
    are the kernel events' images over those layers, whatever program or
    bucket they ran in. The seconds are those of the programs ("XLA Modules")
    that hold such a kernel."""
    attention = _kernels()[0]
    images = 0.0
    for name, calls in trace.get("op_calls", {}).items():
        per_event = attention.images_of_event(name, cfg)
        if per_event is not None:
            images += per_event * calls
    seconds = sum(
        row["seconds"] for name, row in trace.get("programs", {}).items()
        if any(attention.is_kernel_event(op) for op in trace.get("program_ops", {}).get(name, ())))
    return images / attention.layers(cfg), seconds


def slots_finished(cfg: dict, trace: dict, edge_s: float = 2e-3) -> float:
    """Image slots of the forward passes that FINISHED inside the traced
    window, per chip: each program run that ends before the capture does
    counts its whole bucket (read from its kernel events' shape), and a run
    the capture's end cut counts nothing (as kernels/qwen3_next_det_forward.py)."""
    attention = _kernels()[0]
    bucket = {}
    for name, ops in trace.get("program_ops", {}).items():
        sizes = [n for n in (attention.images_of_event(op, cfg) for op in ops) if n is not None]
        if sizes:
            bucket[name] = max(sizes)
    runs = [r for r in trace.get("program_runs", ()) if r["name"] in bucket
            and r["end_s"] < trace["window_s"] - edge_s]
    return sum(bucket[r["name"]] for r in runs) / max(trace.get("devices", 1), 1)
