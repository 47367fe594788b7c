"""Checkpoint -> Flax conversion for `kimi_linear_det` (models/kimi_linear.py).

The checkpoint keeps the public `modeling_kimi.py`'s names and layouts for the
decoder layers (`layers.{i}.self_attn.*` for either mixer, `.input_layernorm`,
`.post_attention_layernorm`, `.mlp.gate_proj / up_proj / down_proj` in a dense
layer, `.block_sparse_moe.gate.weight`, `.gate.e_score_correction_bias`,
`.experts.{e}.w1 / w2 / w3` and `.shared_experts.*` in a routed one, `norm`),
YOLOS's for the detector's seams. Most tensors map one to one
(`kimi_linear_rules`); three groups are rearranged once, here
(`convert_kimi_linear`):

- `q_conv1d.weight`, `k_conv1d.weight`, `v_conv1d.weight` (channels, 1, taps)
  -> (taps, channels); `A_log` (1, 1, heads, 1) -> (heads,);
- the held experts `experts.{offset + e}`: w1 (gate) and w3 (up) side by side
  and the experts stacked, (held, d, 2 I); w2 (down) stacked, (held, I, d):
  what `ops/moe.py` multiplies a row tile by.

Tensors keep the type they are read in (a bfloat16 checkpoint stays
bfloat16); `zoo.py` decides what the device holds.
"""

import numpy as np

from spotter_tpu.convert.torch_to_jax import Rules, convert_state_dict
from spotter_tpu.models.configs import KimiLinearDetConfig

KDA_PROJECTIONS = ("q_proj", "k_proj", "v_proj", "f_a_proj", "f_b_proj", "b_proj", "g_a_proj",
                   "g_b_proj", "o_proj")
MLA_PROJECTIONS = ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
MLP_PROJECTIONS = ("gate_proj", "up_proj", "down_proj")


def kimi_linear_rules(cfg: KimiLinearDetConfig) -> Rules:
    """The tensors that map one to one."""
    r = Rules()
    r.conv(("patch_projection",), "patch_embeddings.projection.weight")
    r.add(("patch_projection", "bias"), "patch_embeddings.projection.bias")
    r.add(("detection_tokens",), "detection_tokens")
    for i in range(cfg.num_hidden_layers):
        f, t = (f"layer{i}",), f"layers.{i}"
        for norm in ("input_layernorm", "post_attention_layernorm"):
            r.add((*f, norm, "weight"), f"{t}.{norm}.weight")
        if cfg.layer_kind(i) == "kda":
            for proj in KDA_PROJECTIONS:
                r.dense((*f, "self_attn", proj), f"{t}.self_attn.{proj}", bias=False)
            r.add((*f, "self_attn", "dt_bias"), f"{t}.self_attn.dt_bias")
            r.add((*f, "self_attn", "o_norm"), f"{t}.self_attn.o_norm.weight")
        else:
            for proj in MLA_PROJECTIONS:
                r.dense((*f, "self_attn", proj), f"{t}.self_attn.{proj}", bias=False)
            r.add((*f, "self_attn", "kv_a_layernorm", "weight"),
                  f"{t}.self_attn.kv_a_layernorm.weight")
        if i < cfg.first_k_dense_replace:
            for proj in MLP_PROJECTIONS:
                r.dense((*f, "mlp", proj), f"{t}.mlp.{proj}", bias=False)
        else:
            moe = (*f, "block_sparse_moe")
            r.add((*moe, "router"), f"{t}.block_sparse_moe.gate.weight", "dense")
            r.add((*moe, "e_score_correction_bias"),
                  f"{t}.block_sparse_moe.gate.e_score_correction_bias")
            for proj in MLP_PROJECTIONS:
                r.dense((*moe, "shared_experts", proj),
                        f"{t}.block_sparse_moe.shared_experts.{proj}", bias=False)
    r.add(("norm", "weight"), "norm.weight")
    r.mlp_head(("class_labels_classifier",), "class_labels_classifier", 3)
    r.mlp_head(("bbox_predictor",), "bbox_predictor", 3)
    return r


def convert_kimi_linear(tensors, cfg: KimiLinearDetConfig) -> dict:
    """`tensors`: name -> array (numpy, or torch tensors from a state_dict)."""

    def get(name):
        value = tensors[name]
        if hasattr(value, "detach"):
            value = value.detach().cpu().numpy()
        return np.asarray(value)

    params = convert_state_dict(tensors, kimi_linear_rules(cfg), strict=True, dtype=None)
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)
    for i in range(cfg.num_hidden_layers):
        layer, t = params[f"layer{i}"], f"layers.{i}"
        if cfg.layer_kind(i) == "kda":
            mixer = layer["self_attn"]
            for name in ("q", "k", "v"):
                mixer[f"{name}_conv"] = np.ascontiguousarray(
                    get(f"{t}.self_attn.{name}_conv1d.weight")[:, 0].T)
            mixer["A_log"] = get(f"{t}.self_attn.A_log").reshape(-1)
        if i >= cfg.first_k_dense_replace:
            experts = [f"{t}.block_sparse_moe.experts.{e}" for e in held]
            layer["block_sparse_moe"]["experts_gate_up"] = np.stack([np.concatenate(
                [get(f"{e}.w1.weight").T, get(f"{e}.w3.weight").T], axis=1) for e in experts])
            layer["block_sparse_moe"]["experts_down"] = np.stack(
                [get(f"{e}.w2.weight").T for e in experts])
    return params
