"""Operations of one forward pass of a `kimi_linear_det` configuration, from its
shapes: a hand count of the matrix products the published layers need for one
image at the checkpoint's own `image_size` (2 operations a multiply-add).
tests/test_kimi_linear_det.py holds it against hand-worked shapes.

    tokens T = (H/p)(W/p) patches + detection tokens
    patch projection        2 * patches * (p*p*channels) * d
    KDA layer               q, k, v and out projections (d <-> heads x 128); the
                            low-rank decay and output gates (d -> r -> heads x
                            128, twice); beta (d -> heads); the recurrence
                            (kernels/kda.py)
    latent-attention layer  q (d -> heads x 192), kv_a (d -> 512 + 64), kv_b
                            (512 -> heads x 256), out (heads x 128 -> d); causal
                            scores and their use (kernels/mla_causal_attention.py)
    dense feed-forward      gate, up, down: three d x intermediate_size products
    routed feed-forward     the router over all routed experts; the shared
                            expert; the held experts' products for the
                            assignments that fell on them (kernels/expert_matmul.py)
    heads                   two 3-layer MLPs over the detection tokens

The routed part depends on the routing. `flops_per_image(cfg, assignments)`
takes the held assignments an image (all routed layers) from the program's
counter; without them (`step_mfu.bulk`'s reader passes a configuration alone)
it counts what a uniform router sends: num_experts_per_token x held / routed a
token and routed layer (2 of 8 at a quarter of the experts). Elementwise work
(the convs' 4 taps, the gates' softplus and sigmoids, the SwiGLUs' products),
normalisations and the softmax are not counted: a share of the peak built on
this count errs low.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _kernels():
    sys.path.insert(0, HERE)
    import expert_matmul as experts
    import kda
    import mla_causal_attention as attention

    return kda, attention, experts


def tokens(cfg: dict) -> tuple[int, int]:
    """(all tokens, patch tokens)."""
    h, w = cfg["image_size"]
    patches = (h // cfg["patch_size"]) * (w // cfg["patch_size"])
    return patches + cfg["num_detection_tokens"], patches


def routed_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def uniform_assignments(cfg: dict) -> float:
    """Per image, all routed layers, if every expert were as likely as any other."""
    share = cfg["num_experts"] / cfg["num_routed_experts"]
    return tokens(cfg)[0] * cfg["num_experts_per_token"] * share * routed_layers(cfg)


def flops_by_part(cfg: dict, assignments: float | None = None) -> dict:
    kda, attention, experts = _kernels()
    t, patches = tokens(cfg)
    d, det = cfg["hidden_size"], cfg["num_detection_tokens"]
    linear = cfg["linear_attn_config"]
    n_kda, n_mla = kda.layers(cfg), attention.layers(cfg)
    assert n_kda + n_mla == cfg["num_hidden_layers"], "the layer lists do not name every layer once"
    width, rank = linear["num_heads"] * linear["head_dim"], cfg["gate_low_rank_dim"]
    kda_proj = 4 * 2 * t * d * width + 2 * (2 * t * d * rank + 2 * t * rank * width)
    kda_proj += 2 * t * d * linear["num_heads"]
    heads_n, key, value = cfg["num_attention_heads"], attention.key_width(cfg), cfg["v_head_dim"]
    latent = cfg["kv_lora_rank"]
    mla_proj = (2 * t * d * heads_n * key + 2 * t * d * (latent + cfg["qk_rope_head_dim"])
                + 2 * t * latent * heads_n * (cfg["qk_nope_head_dim"] + value)
                + 2 * t * heads_n * value * d)
    shared = 3 * 2 * t * d * cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
    if assignments is None:
        assignments = uniform_assignments(cfg)
    heads = 2 * det * (2 * d * d + d * (cfg["num_labels"] + 1)) + 2 * det * (2 * d * d + d * 4)
    return {
        "patch_projection": 2 * patches * (cfg["patch_size"] ** 2 * cfg["num_channels"]) * d,
        "kda_projections": n_kda * kda_proj,
        "kda_rule": kda.operations_per_image(cfg),
        "latent_attention_projections": n_mla * mla_proj,
        "causal_attention": attention.operations_per_image(cfg),
        "dense_mlps": cfg["first_k_dense_replace"] * 3 * 2 * t * d * cfg["intermediate_size"],
        "routers": routed_layers(cfg) * 2 * t * d * cfg["num_routed_experts"],
        "shared_experts": routed_layers(cfg) * shared,
        "routed_experts": experts.operations(cfg, assignments),
        "heads": heads,
    }


def flops_per_image(cfg: dict, assignments: float | None = None) -> float:
    return float(sum(flops_by_part(cfg, assignments).values()))


def slots_in_trace(cfg: dict, trace: dict) -> tuple[float, float]:
    """(image slots the traced forward passes ran, their summed device
    seconds), per chip, from the trace alone. A forward pass runs the KDA
    kernel once per KDA layer, and each kernel event carries its images in its
    own shape (kernels/kda.py): the slots are the kernel events' images over
    those layers, whatever program or bucket they ran in, and a pass the
    capture's edge cut counts for the part that was seen. The seconds are those
    of the programs ("XLA Modules") that hold such a kernel."""
    kda = _kernels()[0]
    images = 0.0
    for name, calls in trace.get("op_calls", {}).items():
        per_event = kda.images_of_event(name, cfg)
        if per_event is not None:
            images += per_event * calls
    seconds = sum(
        row["seconds"] for name, row in trace.get("programs", {}).items()
        if any(kda.is_kernel_event(op) for op in trace.get("program_ops", {}).get(name, ())))
    return images / kda.layers(cfg), seconds


def slots_finished(cfg: dict, trace: dict, edge_s: float = 2e-3) -> float:
    """Image slots of the forward passes that FINISHED inside the traced
    window, per chip: each program run that ends before the capture does counts
    its whole bucket (read from its kernel events' shape), and a run the
    capture's end cut counts nothing (as kernels/qwen3_next_det_forward.py)."""
    kda = _kernels()[0]
    bucket = {}
    for name, ops in trace.get("program_ops", {}).items():
        sizes = [n for n in (kda.images_of_event(op, cfg) for op in ops) if n is not None]
        if sizes:
            bucket[name] = max(sizes)
    runs = [r for r in trace.get("program_runs", ()) if r["name"] in bucket
            and r["end_s"] < trace["window_s"] - edge_s]
    return sum(bucket[r["name"]] for r in runs) / max(trace.get("devices", 1), 1)
