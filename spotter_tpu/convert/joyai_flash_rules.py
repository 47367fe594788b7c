"""Checkpoint -> Flax conversion for `joyai_flash_det` (models/joyai_flash.py).

The checkpoint keeps DeepSeek-V3's names and layouts for the decoder layers
(transformers' `modeling_deepseek_v3.py`: `layers.{i}.self_attn.q_a_proj`,
`.q_a_layernorm`, `.q_b_proj`, `.kv_a_proj_with_mqa`, `.kv_a_layernorm`,
`.kv_b_proj`, `.o_proj`; `.input_layernorm`, `.post_attention_layernorm`;
`.mlp.gate_proj / up_proj / down_proj` in a dense layer, `.mlp.gate.weight`,
`.mlp.gate.e_score_correction_bias`, `.mlp.experts.{e}.*` and
`.mlp.shared_experts.*` in a routed one; `norm`), YOLOS's for the detector's
seams. Most tensors map one to one (`joyai_flash_rules`); two groups are
rearranged once, here (`convert_joyai_flash`):

- the held experts `experts.{offset + e}`: gate and up side by side and the
  experts stacked, (held, d, 2 I); down stacked, (held, I, d): what
  `ops/moe.py` multiplies a row tile by;
- the checkpoint's rotary channels are pairs `(x[2i], x[2i + 1])`
  (`rope_interleave`): the outputs of `q_b_proj` that are each head's last
  `qk_rope_head_dim` channels, and the last `qk_rope_head_dim` outputs of
  `kv_a_proj_with_mqa`, are put in half-split order, the even channels then
  the odd ones (`half_split_order`): transformers' `apply_rotary_pos_emb_interleave`
  makes the same permutation of the projected channels at every call. q and
  k move alike, so every q . k is what it was.

Tensors keep the type they are read in (a bfloat16 checkpoint stays
bfloat16); `zoo.py` decides what the device holds.
"""

import numpy as np

from spotter_tpu.convert.torch_to_jax import Rules, convert_state_dict
from spotter_tpu.models.configs import JoyAIFlashDetConfig

LATENT_PROJECTIONS = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
MLP_PROJECTIONS = ("gate_proj", "up_proj", "down_proj")


def half_split_order(width: int) -> np.ndarray:
    """Channel order that takes interleaved pairs to the half-split layout:
    [0, 2, ..., width - 2, 1, 3, ..., width - 1]."""
    return np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])


def rotary_columns(cfg: JoyAIFlashDetConfig) -> tuple[np.ndarray, np.ndarray]:
    """The output orders of `q_b_proj` and `kv_a_proj_with_mqa` (Flax kernels'
    columns) that put their rotary channels in half-split order."""
    pe, head = cfg.qk_rope_head_dim, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    turn = half_split_order(pe)
    query = np.arange(cfg.num_attention_heads * head).reshape(-1, head)
    query[:, -pe:] = query[:, -pe:][:, turn]
    latent = np.arange(cfg.kv_lora_rank + pe)
    latent[-pe:] = latent[-pe:][turn]
    return query.reshape(-1), latent


def to_half_split(mixer: dict, cfg: JoyAIFlashDetConfig) -> dict:
    """A latent-attention parameter tree with interleaved rotary channels ->
    the same in half-split order (a new dict; the rest is shared)."""
    query, latent = rotary_columns(cfg)
    return {**mixer,
            "q_b_proj": {**mixer["q_b_proj"], "kernel": np.asarray(mixer["q_b_proj"]["kernel"])[:, query]},
            "kv_a_proj_with_mqa": {**mixer["kv_a_proj_with_mqa"], "kernel": np.asarray(
                mixer["kv_a_proj_with_mqa"]["kernel"])[:, latent]}}


def joyai_flash_rules(cfg: JoyAIFlashDetConfig) -> Rules:
    """The tensors that map one to one."""
    r = Rules()
    r.conv(("patch_projection",), "patch_embeddings.projection.weight")
    r.add(("patch_projection", "bias"), "patch_embeddings.projection.bias")
    r.add(("detection_tokens",), "detection_tokens")
    for i in range(cfg.num_hidden_layers):
        f, t = (f"layer{i}",), f"layers.{i}"
        for norm in ("input_layernorm", "post_attention_layernorm"):
            r.add((*f, norm, "weight"), f"{t}.{norm}.weight")
        for proj in LATENT_PROJECTIONS:
            r.dense((*f, "self_attn", proj), f"{t}.self_attn.{proj}", bias=False)
        for norm in ("q_a_layernorm", "kv_a_layernorm"):
            r.add((*f, "self_attn", norm, "weight"), f"{t}.self_attn.{norm}.weight")
        if i < cfg.first_k_dense_replace:
            for proj in MLP_PROJECTIONS:
                r.dense((*f, "mlp", proj), f"{t}.mlp.{proj}", bias=False)
        else:
            r.add((*f, "mlp", "router"), f"{t}.mlp.gate.weight", "dense")
            r.add((*f, "mlp", "e_score_correction_bias"), f"{t}.mlp.gate.e_score_correction_bias")
            for proj in MLP_PROJECTIONS:
                r.dense((*f, "mlp", "shared_experts", proj), f"{t}.mlp.shared_experts.{proj}",
                        bias=False)
    r.add(("norm", "weight"), "norm.weight")
    r.mlp_head(("class_labels_classifier",), "class_labels_classifier", 3)
    r.mlp_head(("bbox_predictor",), "bbox_predictor", 3)
    return r


def convert_joyai_flash(tensors, cfg: JoyAIFlashDetConfig) -> dict:
    """`tensors`: name -> array (numpy, or torch tensors from a state_dict)."""

    def get(name):
        value = tensors[name]
        if hasattr(value, "detach"):
            value = value.detach().cpu().numpy()
        return np.asarray(value)

    params = convert_state_dict(tensors, joyai_flash_rules(cfg), strict=True, dtype=None)
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)
    for i in range(cfg.num_hidden_layers):
        layer, t = params[f"layer{i}"], f"layers.{i}"
        layer["self_attn"] = to_half_split(layer["self_attn"], cfg)
        if i >= cfg.first_k_dense_replace:
            experts = [f"{t}.mlp.experts.{e}" for e in held]
            layer["mlp"]["experts_gate_up"] = np.stack([np.concatenate(
                [get(f"{e}.gate_proj.weight").T, get(f"{e}.up_proj.weight").T], axis=1)
                for e in experts])
            layer["mlp"]["experts_down"] = np.stack([get(f"{e}.down_proj.weight").T for e in experts])
    return params
