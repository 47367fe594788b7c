"""LFM2-MoE's decoder layers as a detector body (`model_type: lfm2_moe_det`):
the benchmark's own copy of the reference, the seeded weights, preprocessing
and postprocessing. Imports nothing of `spotter_tpu`.

The model (float32, torch, CPU) is assembled from transformers' own layers of
the published implementation (`models/lfm2/modeling_lfm2.py`, 4.57):
`Lfm2ShortConv` (its `slow_forward`), `Lfm2Attention`, `Lfm2RMSNorm` and
`Lfm2RotaryEmbedding`, in the published order: `h = x + mixer(operator_norm(x))`,
`y = h + ffn(ffn_norm(h))`, the mixer of layer i what `layer_types[i]` says.
Around them, in YOLOS's form: a 16x16 patch projection over the warped image,
the patch tokens in raster order, the learned detection tokens appended
(causal layers: they see the whole image), no position table, the published
final RMSNorm (`embedding_norm`), two 3-layer MLP heads on the detection
tokens.

The installed transformers has no `lfm2_moe`, so the two feed-forward blocks
are written out plainly here, under the names that model's checkpoint uses:
the first `num_dense_layers` layers a SwiGLU of `intermediate_size` as it
stands (`w2(silu(w1 x) * w3 x)`; `Lfm2MLP` would shrink the width unless
`block_auto_adjust_ff_dim` is false); the others `num_experts` routed experts
of `moe_intermediate_size`: `s = sigmoid(gate(x))` in float32, the
`num_experts_per_tok` best by `s + expert_bias`, their weights `s` over `(the
chosen ones' sum + 1e-6)` times `routed_scaling_factor`, every expert held.

Attention runs through torch's `scaled_dot_product_attention` (causal):
eager attention holds heads x tokens^2 floats (2.4 GB an image at 4300
tokens); tests/test_lfm2_moe_det.py holds the two against each other.

Weights ("scaled_normal_lfm2_moe_det"): every tensor is drawn with numpy from
(seed, crc32(tensor name)).

- matrices, the patch projection, the conv taps and the router: N(0, 1 /
  fan_in) (router logits of unit scale over unit-RMS tokens); biases 0;
- `expert_bias`: N(0, `expert_bias_std`^2). A zero bias would test nothing:
  at 0.05 it is as wide as the gap between a token's fourth and fifth score,
  so it moves a share of the choices (`routing_bias_moved.bulk`);
- RMSNorm weights 1 (plain);
- the detection tokens N(0, `token_std`^2);
- the class head's last layer N(0, `class_gain`^2 / fan_in) and the "no
  object" class's bias `no_object_bias`: the regime of the answer
  (tools/regime.py), recorded in the configuration's file.

The checkpoint is written in bfloat16 (config.json + model.safetensors), as
the source ships its weights; the reference reads the same rounded values
back into float32, the program holds them as they are.
"""

import json
import os
import types
import zlib

NAME_TAG = "lfm2_moe_det"
ARCHITECTURE = "Lfm2MoeDetForObjectDetection"
MEAN_STD = ((0.485, 0.456, 0.406), (0.229, 0.224, 0.225))
BLOCK = 1  # 4300 tokens x 7168 wide twice, and 32 experts: one image at a time
THRESHOLD = 0.5
NORM_TOPK_EPS = 1e-6
LM_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "max_position_embeddings", "norm_eps", "rope_theta", "conv_bias",
    "conv_L_cache", "layer_types",
)


def _lm_config(hf: dict):
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config

    lm = Lfm2Config(**{k: hf[k] for k in LM_KEYS if k in hf}, block_auto_adjust_ff_dim=False)
    lm._attn_implementation = hf.get("_reference_attention", "sdpa")
    return lm


def _build(hf: dict):
    import torch
    from torch import nn
    from torch.nn import functional as F
    from transformers.models.lfm2 import modeling_lfm2 as hf_mod

    lm = _lm_config(hf)
    d = lm.hidden_size

    class SwiGlu(nn.Module):
        def __init__(self, width: int):
            super().__init__()
            self.w1 = nn.Linear(d, width, bias=False)
            self.w3 = nn.Linear(d, width, bias=False)
            self.w2 = nn.Linear(width, d, bias=False)

        def forward(self, x):
            return self.w2(F.silu(self.w1(x)) * self.w3(x))

    class RoutedExperts(nn.Module):
        """Every routed expert is held: the sum over a token's chosen experts."""

        def __init__(self):
            super().__init__()
            self.gate = nn.Linear(d, hf["num_experts"], bias=False)
            self.expert_bias = nn.Parameter(torch.zeros(hf["num_experts"]))
            self.experts = nn.ModuleList(
                [SwiGlu(hf["moe_intermediate_size"]) for _ in range(hf["num_experts"])])

        def route(self, x):
            scores = torch.sigmoid(self.gate(x).float())
            if hf["use_expert_bias"]:
                _, chosen = torch.topk(scores + self.expert_bias, hf["num_experts_per_tok"], dim=-1)
                weights = torch.gather(scores, 1, chosen)
            else:
                weights, chosen = torch.topk(scores, hf["num_experts_per_tok"], dim=-1)
            if hf["norm_topk_prob"]:
                weights = weights / (weights.sum(-1, keepdim=True) + NORM_TOPK_EPS)
            return weights * hf["routed_scaling_factor"], chosen

        def forward(self, x):
            shape = x.shape
            x = x.reshape(-1, shape[-1])
            weights, chosen = self.route(x)
            out = torch.zeros_like(x)
            for e, expert in enumerate(self.experts):
                rows, slot = torch.where(chosen == e)
                if rows.numel():
                    out.index_add_(0, rows, expert(x[rows]) * weights[rows, slot, None])
            return out.reshape(shape)

    class Layer(nn.Module):
        def __init__(self, index: int):
            super().__init__()
            self.attends = lm.layer_types[index] == "full_attention"
            if self.attends:
                self.self_attn = hf_mod.Lfm2Attention(lm, index)
            else:
                self.conv = hf_mod.Lfm2ShortConv(lm, index)
            self.feed_forward = (SwiGlu(lm.intermediate_size) if index < hf["num_dense_layers"]
                                 else RoutedExperts())
            self.operator_norm = hf_mod.Lfm2RMSNorm(d, eps=lm.norm_eps)
            self.ffn_norm = hf_mod.Lfm2RMSNorm(d, eps=lm.norm_eps)

        def forward(self, x, position_embeddings, mask):
            normed = self.operator_norm(x)
            if self.attends:
                x = x + self.self_attn(normed, position_embeddings, mask)[0]
            else:
                x = x + self.conv.slow_forward(normed)
            return x + self.feed_forward(self.ffn_norm(x))

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
            self.layers = nn.ModuleList([Layer(i) for i in range(lm.num_hidden_layers)])
            self.embedding_norm = hf_mod.Lfm2RMSNorm(d, eps=lm.norm_eps)
            self.rotary = hf_mod.Lfm2RotaryEmbedding(lm)
            self.class_labels_classifier = Head(hf["num_labels"] + 1)
            self.bbox_predictor = Head(4)

        def forward(self, pixel_values):
            x = self.patch_embeddings.projection(pixel_values).flatten(2).transpose(1, 2)
            n_det = self.detection_tokens.shape[1]
            x = torch.cat([x, self.detection_tokens.expand(x.shape[0], -1, -1)], dim=1)
            tokens = x.shape[1]
            position_embeddings = self.rotary(x, torch.arange(tokens)[None])
            mask = None
            if lm._attn_implementation == "eager":
                mask = torch.full((tokens, tokens), float("-inf")).triu(1)[None, None]
            for layer in self.layers:
                x = layer(x, position_embeddings, mask)
            x = self.embedding_norm(x)[:, -n_det:]
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
    checkout whose program has no such family (the parent of the PR that
    added it, run on this cell) is told so here, before minutes of seeding
    and three gigabytes of checkpoint for a server that would then refuse the
    name: a path is looked at, nothing of the program is imported."""
    import torch

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if not os.path.exists(os.path.join(root, "spotter_tpu", "models", "lfm2_moe.py")):
        raise RuntimeError("this checkout's program has no lfm2_moe_det family: nothing to run")
    with torch.no_grad():
        return _build(hf)


def load_model(checkpoint: str):
    import torch
    from safetensors.torch import load_file

    with open(os.path.join(checkpoint, "config.json")) as f:
        hf = json.load(f)
    with torch.no_grad():
        model = _build(hf)
        state = load_file(os.path.join(checkpoint, "model.safetensors"))
        model.load_state_dict({k: v.float() for k, v in state.items()}, strict=True)
    return model


def input_hw(hf: dict) -> tuple:
    return tuple(hf["image_size"])


def seed_weights(model, w: dict) -> None:
    import numpy as np
    import torch

    assert w["scheme"] == "scaled_normal_lfm2_moe_det", w["scheme"]
    last = "class_labels_classifier.layers.2"
    for name, tensor in model.state_dict().items():
        rng = np.random.default_rng([int(w["seed"]), zlib.crc32(name.encode())])
        shape = tuple(tensor.shape)
        leaf = name.rsplit(".", 1)[-1]
        if name == "detection_tokens":
            value = rng.standard_normal(shape) * w["token_std"]
        elif leaf == "expert_bias":
            value = rng.standard_normal(shape) * w["expert_bias_std"]
        elif tensor.ndim >= 2:
            fan_in = int(np.prod(shape[1:]))
            gain = w["class_gain"] if name == f"{last}.weight" else 1.0
            value = rng.standard_normal(shape, dtype=np.float32) * np.float32(gain / np.sqrt(fan_in))
        elif leaf == "bias":
            value = np.zeros(shape)
            if name == f"{last}.bias":
                value[-1] = w["no_object_bias"]
        elif leaf == "weight":  # an RMSNorm's plain weight
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
