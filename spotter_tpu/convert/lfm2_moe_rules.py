"""Checkpoint -> Flax conversion for `lfm2_moe_det` (models/lfm2_moe.py).

The checkpoint keeps transformers' names and layouts for the decoder layers
(`modeling_lfm2.py` / `modeling_lfm2_moe.py`: `layers.{i}.conv.*`,
`.self_attn.*`, `.operator_norm`, `.ffn_norm`, `.feed_forward.w1 / w2 / w3`
in a dense layer, `.feed_forward.gate`, `.expert_bias` and `.experts.{e}.w1 /
w2 / w3` in a routed one, `embedding_norm`), YOLOS's for the detector's
seams. Most tensors map one to one (`lfm2_moe_rules`); two groups are
rearranged once, here (`convert_lfm2_moe`):

- `conv.conv.weight` (channels, 1, taps) -> (taps, channels);
- the experts: w1 (gate) and w3 (up) side by side and the experts stacked,
  (experts, d, 2 I); w2 (down) stacked, (experts, I, d): what `ops/moe.py`
  multiplies a row tile by.

Tensors keep the type they are read in (a bfloat16 checkpoint stays
bfloat16); `zoo.py` decides what the device holds.
"""

import numpy as np

from spotter_tpu.convert.torch_to_jax import Rules, convert_state_dict
from spotter_tpu.models.configs import Lfm2MoeDetConfig


def lfm2_moe_rules(cfg: Lfm2MoeDetConfig) -> Rules:
    """The tensors that map one to one."""
    r = Rules()
    r.conv(("patch_projection",), "patch_embeddings.projection.weight")
    r.add(("patch_projection", "bias"), "patch_embeddings.projection.bias")
    r.add(("detection_tokens",), "detection_tokens")
    for i, kind in enumerate(cfg.layer_types):
        f, t = (f"layer{i}",), f"layers.{i}"
        for norm in ("operator_norm", "ffn_norm"):
            r.add((*f, norm, "weight"), f"{t}.{norm}.weight")
        if kind == "full_attention":
            for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
                r.dense((*f, "self_attn", proj), f"{t}.self_attn.{proj}", bias=False)
            for norm in ("q_layernorm", "k_layernorm"):
                r.add((*f, "self_attn", norm, "weight"), f"{t}.self_attn.{norm}.weight")
        else:
            for proj in ("in_proj", "out_proj"):
                r.dense((*f, "conv", proj), f"{t}.conv.{proj}", bias=False)
        if i < cfg.num_dense_layers:
            for proj in ("w1", "w2", "w3"):
                r.dense((*f, "feed_forward", proj), f"{t}.feed_forward.{proj}", bias=False)
        else:
            r.add((*f, "feed_forward", "router"), f"{t}.feed_forward.gate.weight", "dense")
            r.add((*f, "feed_forward", "expert_bias"), f"{t}.feed_forward.expert_bias")
    r.add(("embedding_norm", "weight"), "embedding_norm.weight")
    r.mlp_head(("class_labels_classifier",), "class_labels_classifier", 3)
    r.mlp_head(("bbox_predictor",), "bbox_predictor", 3)
    return r


def convert_lfm2_moe(tensors, cfg: Lfm2MoeDetConfig) -> dict:
    """`tensors`: name -> array (numpy, or torch tensors from a state_dict)."""

    def get(name):
        value = tensors[name]
        if hasattr(value, "detach"):
            value = value.detach().cpu().numpy()
        return np.asarray(value)

    params = convert_state_dict(tensors, lfm2_moe_rules(cfg), strict=True, dtype=None)
    for i, kind in enumerate(cfg.layer_types):
        layer, t = params[f"layer{i}"], f"layers.{i}"
        if kind != "full_attention":
            layer["conv"]["conv"] = np.ascontiguousarray(get(f"{t}.conv.conv.weight")[:, 0].T)
        if i >= cfg.num_dense_layers:
            experts = [f"{t}.feed_forward.experts.{e}" for e in range(cfg.num_experts)]
            layer["feed_forward"]["experts_gate_up"] = np.stack([np.concatenate(
                [get(f"{e}.w1.weight").T, get(f"{e}.w3.weight").T], axis=1) for e in experts])
            layer["feed_forward"]["experts_down"] = np.stack(
                [get(f"{e}.w2.weight").T for e in experts])
    return params
