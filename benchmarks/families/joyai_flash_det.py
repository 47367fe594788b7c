"""JoyAI-LLM-Flash's decoder layers as a detector body (`model_type:
joyai_flash_det`): the benchmark's own copy of the reference, the seeded
weights, preprocessing and postprocessing. Imports nothing of `spotter_tpu`.

JoyAI-LLM-Flash's config.json carries DeepSeek-V3's keys letter for letter
and its `modeling` file is remote code that is not here, so the layer is
transformers' (4.57.6) DeepSeek-V3 layer, built from its own classes (float32,
torch, CPU), under its own tensor names, in the published order: `h = x +
self_attn(input_layernorm(x))`, `y = h + mlp(post_attention_layernorm(h))`.

- `DeepseekV3Attention` with `rope_interleave` true: the query latent
  (`q_a_proj`, `q_a_layernorm`, `q_b_proj`), the key-value latent
  (`kv_a_proj_with_mqa`, `kv_a_layernorm`, `kv_b_proj`), the rotary term on
  each query head's last 64 channels and on the one key every head shares,
  the pairs (x[2i], x[2i + 1]) of the checkpoint turned by
  `apply_rotary_pos_emb_interleave` at `rope_theta` and the token's index
  (`DeepseekV3RotaryEmbedding`), scale 192^-0.5, `o_proj`. The causal softmax
  goes through the attention function this file registers with transformers
  (`joyai_flash_det_sdpa`: torch's `scaled_dot_product_attention`, the
  values padded with zero columns to the keys' width, as kimi_linear_det's;
  `_reference_attention: eager` in the config takes transformers' eager
  function and an additive causal mask, for the test that holds the two
  together);
- `DeepseekV3RMSNorm` everywhere, `DeepseekV3MLP` for the dense SwiGLU, the
  shared expert and each routed expert;
- the routed block written out: `DeepseekV3TopkRouter` over all
  `num_routed_experts` (sigmoid in float32, the eight best by `s +
  e_score_correction_bias`, the weights `s` over (their sum + 1e-20) times
  `routed_scaling_factor`; `n_group` 1, so the grouped choice is the plain
  one); only the terms of the experts held here (`n_routed_experts` of them,
  from `expert_offset` on) are added, plus the shared expert, ungated. What
  the absent experts would add is left out, as in the program.

Around them, in YOLOS's form: a 16x16 patch projection over the warped image,
the patch tokens in raster order, the learned detection tokens appended (causal
layers: they see the whole image), the final RMSNorm (`norm`), two 3-layer MLP
heads on the detection tokens. No token embedding, output head or
multi-token-prediction module.

Weights ("scaled_normal_joyai_flash_det"): every tensor is drawn with numpy
from (seed, crc32(tensor name)): matrices, the patch projection and the router
N(0, 1 / fan_in); biases 0; RMSNorm weights 1; `e_score_correction_bias` N(0,
`expert_bias_std`^2), so that the selection bias moves a share of the choices;
the detection tokens N(0, `token_std`^2); the class head's last layer N(0,
`class_gain`^2 / fan_in) and the "no object" class's bias `no_object_bias`: the
regime of the answer, recorded in the configuration's file. The checkpoint is
written in bfloat16 (config.json + model.safetensors), as the source ships its
weights; the reference reads the same rounded values back into float32.
"""

import json
import os
import types
import zlib

NAME_TAG = "joyai_flash_det"
ARCHITECTURE = "JoyAIFlashDetForObjectDetection"
MEAN_STD = ((0.485, 0.456, 0.406), (0.229, 0.224, 0.225))
BLOCK = 1  # an image's activations are 100 MB an array (4300 tokens x 32 heads x 192 floats): one at a time
THRESHOLD = 0.5
ATTENTION = "joyai_flash_det_sdpa"
DEEPSEEK_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
                 "num_key_value_heads", "n_shared_experts", "routed_scaling_factor", "kv_lora_rank",
                 "q_lora_rank", "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "n_group",
                 "topk_group", "num_experts_per_tok", "first_k_dense_replace", "norm_topk_prob",
                 "hidden_act", "rms_norm_eps", "rope_theta", "rope_scaling", "rope_interleave",
                 "attention_bias")


def _padded_sdpa(module, query, key, value, attention_mask, scaling, dropout=0.0, **kwargs):
    """Causal softmax attention through torch's fused kernel: on the CPU it
    takes values as wide as the keys, so the values get zero columns and the
    result's extra columns, zero, are dropped. (B, T, H, Dv), as transformers'
    attention functions return it."""
    from torch.nn import functional as F

    wide = F.pad(value, (0, query.shape[-1] - value.shape[-1]))
    out = F.scaled_dot_product_attention(query, key, wide, is_causal=True, scale=scaling)
    return out[..., :value.shape[-1]].transpose(1, 2).contiguous(), None


def deepseek_config(hf: dict):
    """transformers' `DeepseekV3Config` of the layers: the router over all
    `num_routed_experts`."""
    from transformers import AttentionInterface, DeepseekV3Config

    AttentionInterface.register(ATTENTION, _padded_sdpa)
    config = DeepseekV3Config(**{k: hf[k] for k in DEEPSEEK_KEYS}, num_hidden_layers=hf["num_hidden_layers"],
                              n_routed_experts=hf["num_routed_experts"], vocab_size=0,
                              max_position_embeddings=hf["max_position_embeddings"])
    config._attn_implementation = "eager" if hf.get("_reference_attention") == "eager" else ATTENTION
    return config


def _build(hf: dict):
    import torch
    from torch import nn
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as ds

    config = deepseek_config(hf)
    d = hf["hidden_size"]

    class SparseMoe(nn.Module):
        """The router over all, the routed experts held here, the shared expert."""

        def __init__(self):
            super().__init__()
            self.held = range(hf["expert_offset"], hf["expert_offset"] + hf["n_routed_experts"])
            self.gate = ds.DeepseekV3TopkRouter(config)
            width = hf["moe_intermediate_size"]
            self.experts = nn.ModuleDict(
                {str(e): ds.DeepseekV3MLP(config, intermediate_size=width) for e in self.held})
            self.shared_experts = ds.DeepseekV3MLP(config, intermediate_size=width * hf["n_shared_experts"])

        def forward(self, x):
            shape = x.shape
            x = x.reshape(-1, shape[-1])
            chosen, weights = self.gate(x)
            out = self.shared_experts(x)
            for e in self.held:
                rows, slot = torch.where(chosen == e)
                if rows.numel():
                    out.index_add_(0, rows, self.experts[str(e)](x[rows]) * weights[rows, slot, None])
            return out.reshape(shape)

    class Layer(nn.Module):
        def __init__(self, index: int):
            super().__init__()
            self.self_attn = ds.DeepseekV3Attention(config, index)
            self.mlp = ds.DeepseekV3MLP(config) if index < hf["first_k_dense_replace"] else SparseMoe()
            self.input_layernorm = ds.DeepseekV3RMSNorm(d, eps=hf["rms_norm_eps"])
            self.post_attention_layernorm = ds.DeepseekV3RMSNorm(d, eps=hf["rms_norm_eps"])

        def forward(self, x, position_embeddings, mask):
            x = x + self.self_attn(self.input_layernorm(x), position_embeddings, mask)[0]
            return x + self.mlp(self.post_attention_layernorm(x))

    class Head(nn.Module):
        def __init__(self, out: int):
            super().__init__()
            self.layers = nn.ModuleList([nn.Linear(d, d), nn.Linear(d, d), nn.Linear(d, out)])

        def forward(self, x):
            for i, layer in enumerate(self.layers):
                x = layer(x) if i == len(self.layers) - 1 else torch.relu(layer(x))
            return x

    class PatchEmbeddings(nn.Module):
        def __init__(self):
            super().__init__()
            p = hf["patch_size"]
            self.projection = nn.Conv2d(hf["num_channels"], d, p, stride=p)

    class Detector(nn.Module):
        def __init__(self):
            super().__init__()
            self.hf = hf
            self.patch_embeddings = PatchEmbeddings()
            self.detection_tokens = nn.Parameter(torch.zeros(1, hf["num_detection_tokens"], d))
            self.layers = nn.ModuleList([Layer(i) for i in range(hf["num_hidden_layers"])])
            self.norm = ds.DeepseekV3RMSNorm(d, eps=hf["rms_norm_eps"])
            self.class_labels_classifier = Head(hf["num_labels"] + 1)
            self.bbox_predictor = Head(4)

        def forward(self, pixel_values):
            x = self.patch_embeddings.projection(pixel_values).flatten(2).transpose(1, 2)
            n_det = self.detection_tokens.shape[1]
            x = torch.cat([x, self.detection_tokens.expand(x.shape[0], -1, -1)], dim=1)
            t = x.shape[1]
            # built here, not in __init__: `load_model` builds the modules on the meta device
            rotary = ds.DeepseekV3RotaryEmbedding(config)
            position_embeddings = rotary(x, torch.arange(t)[None])
            mask = None  # the registered function is causal by itself
            if config._attn_implementation == "eager":
                mask = torch.full((t, t), float("-inf")).triu(1)[None, None]
            for layer in self.layers:
                x = layer(x, position_embeddings, mask)
            x = self.norm(x)[:, -n_det:]
            return types.SimpleNamespace(
                logits=self.class_labels_classifier(x),
                pred_boxes=torch.sigmoid(self.bbox_predictor(x)))

        def save_pretrained(self, path: str, safe_serialization: bool = True):
            from safetensors.torch import save_file

            assert safe_serialization
            os.makedirs(path, exist_ok=True)
            with open(os.path.join(path, "config.json"), "w") as f:
                json.dump(self.hf, f, indent=1)
            save_file({k: v.to(torch.bfloat16).contiguous() for k, v in self.state_dict().items()},
                      os.path.join(path, "model.safetensors"))

    return Detector()


def new_model(hf: dict):
    """The model with torch's default weights (`seed_weights` fills it). A
    checkout whose program has no such family (the commit before the one that
    added it, run on this cell) is told so here, before minutes of seeding and five
    gigabytes of checkpoint for a server that would then refuse the name: a
    path is looked at, nothing of the program is imported."""
    import torch

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if not os.path.exists(os.path.join(root, "spotter_tpu", "models", "joyai_flash.py")):
        raise RuntimeError("this checkout's program has no joyai_flash_det family: nothing to run")
    with torch.no_grad():
        return _build(hf)


def load_model(checkpoint: str):
    import torch
    from safetensors.torch import load_file

    import weights

    with open(os.path.join(checkpoint, "config.json")) as f:
        hf = json.load(f)
    with torch.no_grad():
        with torch.device("meta"):  # no default weights are drawn: the checkpoint's tensors become the model's
            model = _build(hf)
        state = load_file(os.path.join(checkpoint, "model.safetensors"))
        model.load_state_dict({k: v.float() for k, v in state.items()}, strict=True, assign=True)
    # the allocator as kimi_linear_det's reference leaves it: a freed 100 MB block is kept for the next pass
    weights.family("kimi_linear_det").keep_freed_blocks_mapped()
    return model


def input_hw(hf: dict) -> tuple:
    return tuple(hf["image_size"])


def seed_weights(model, w: dict) -> None:
    import numpy as np
    import torch

    assert w["scheme"] == "scaled_normal_joyai_flash_det", w["scheme"]
    last = "class_labels_classifier.layers.2"
    for name, tensor in model.state_dict().items():
        rng = np.random.default_rng([int(w["seed"]), zlib.crc32(name.encode())])
        shape = tuple(tensor.shape)
        leaf = name.rsplit(".", 1)[-1]
        if name == "detection_tokens":
            value = rng.standard_normal(shape) * w["token_std"]
        elif leaf == "e_score_correction_bias":
            value = rng.standard_normal(shape) * w["expert_bias_std"]
        elif tensor.ndim >= 2:
            fan_in = int(np.prod(shape[1:]))
            gain = w["class_gain"] if name == f"{last}.weight" else 1.0
            value = rng.standard_normal(shape, dtype=np.float32) * np.float32(gain / np.sqrt(fan_in))
        elif leaf == "bias":
            value = np.zeros(shape)
            if name == f"{last}.bias":
                value[-1] = w["no_object_bias"]
        elif leaf == "weight":  # an RMSNorm's weight
            value = np.ones(shape)
        else:
            raise ValueError(f"no rule for tensor {name} {shape}")
        tensor.copy_(torch.from_numpy(np.asarray(value, dtype=np.float32)))


def threshold_logits(logits):
    """(Q, C) numbers on the scale the threshold cuts at 0, and the (query,
    class) pairs the softmax postprocess keeps (YOLOS's: a token's class
    passes where its probability is over 0.5; "no object" is never an
    answer): log(p / (1 - p)) = its logit minus the log-sum-exp of the
    others."""
    import numpy as np

    z = logits.astype(np.float64)
    top = z.max(-1, keepdims=True)
    e = np.exp(z - top)
    rest = e.sum(-1, keepdims=True) - e
    gap = (z - top) - np.log(np.maximum(rest, 1e-300))
    gap = gap[:, :-1].astype(np.float32)
    kept = {(int(q), int(c)) for q, c in zip(*np.nonzero(gap > 0))}
    return gap, kept
