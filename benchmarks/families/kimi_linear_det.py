"""Kimi Linear's decoder layers as a detector body (`model_type:
kimi_linear_det`): the benchmark's own copy of the reference, the seeded
weights, preprocessing and postprocessing. Imports nothing of `spotter_tpu`.

The installed transformers (4.57.6) has no `kimi_linear` and `fla` is absent,
so every block is written out here from the equations (float32, torch, CPU),
under the names the public `modeling_kimi.py` gives its tensors, in the
published order: `h = x + mixer(input_layernorm(x))`, `y = h +
ffn(post_attention_layernorm(h))`, RMSNorm's weight plain, eps `rms_norm_eps`.

- `KimiDeltaAttention` (layers `linear_attn_config.kda_layers`, counted from 1
  as published): `q_proj`, `k_proj`, `v_proj` (d -> heads x 128, no bias),
  each through its own depthwise causal `Conv1d` of 4 taps and a SiLU; `g =
  -exp(A_log) softplus(f_b_proj(f_a_proj(x)) + dt_bias)`, a log decay a head,
  token and key channel; `beta = sigmoid(b_proj(x))`; q and k L2-normalised
  (eps 1e-6), q scaled by 128^-0.5; the recurrence, a head's state S (128 x
  128) from zero: `S <- diag(exp(g_t)) S; S <- S + k_t (beta_t (v_t - S^T
  k_t))^T; o_t = S^T q_t`; `o_proj(rmsnorm(o; o_norm) * sigmoid(g_b_proj(
  g_a_proj(x))))`. The recurrence is written twice. `recurrence_by_token` is
  the three statements as they stand (a token reads S once and writes it once:
  `S^T (exp(g) k)` is `(diag(exp(g)) S)^T k`): 17,200 steps an image, 5.2 s of
  an image's 9.4 on the chip machine's host. `recurrence` is what runs (2.5 s): the same statements
  unrolled over a block of 32 tokens. With G_t the sum of g from the block's
  first token through t and u_t = beta_t (v_t - S^T k_t) the row token t writes,
  `S_t = diag(e^{G_t}) S_0 + sum_{s<=t} diag(e^{G_t - G_s}) k_s u_s^T`, so the
  rows solve the unit lower triangular system `u_t + beta_t sum_{s<t} (k_t .
  e^{G_t - G_s} k_s) u_s = beta_t (v_t - S_0^T (e^{G_t} k_t))` and `o_t = S_0^T
  (e^{G_t} q_t) + sum_{s<=t} (q_t . e^{G_t - G_s} k_s) u_s`. Every exponent is
  a decay over tokens s+1..t, at most 0, and is taken as it stands, a (32, 32,
  128) array a head: no quotient of exponentials, no reference row, no
  sub-blocks (the program's kernel has all three). `_reference_recurrence:
  tokens` in the config runs the first form, for the test that holds the two
  together.
- `LatentAttention` (layers `full_attn_layers`): `q_proj` gives each head 128
  + 64 channels (`q_lora_rank` null); `kv_a_proj_with_mqa` a latent of 512 and
  64 key channels that every head shares; `kv_b_proj(kv_a_layernorm(latent))`
  each head's 128 key and 128 value channels; no rotary term on either side
  (`mla_use_nope`); causal softmax over keys of 192, scale 192^-0.5, through
  torch's `scaled_dot_product_attention`, the values padded with zero columns
  to the keys' width (eager attention holds heads x tokens^2 floats, 2.4 GB an
  image; `_reference_attention: eager` in the
  config computes it so, for the test that holds the two together); `o_proj`.
- feed-forward: the first `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size`; the others `s = sigmoid(gate(x))` in float32 over all
  `num_routed_experts`, the `num_experts_per_token` best by `s +
  e_score_correction_bias`, their weights `s` over (the chosen ones' sum +
  1e-20) times `routed_scaling_factor`; only the terms of the experts held
  here (`num_experts` of them, from `expert_offset` on) are added, plus the
  shared expert, ungated. What the absent experts would add is left out, as in
  the program.

Around them, in YOLOS's form: a 16x16 patch projection over the warped image,
the patch tokens in raster order, the learned detection tokens appended (causal
layers: they see the whole image), no position table, the final RMSNorm
(`norm`), two 3-layer MLP heads on the detection tokens.

Weights ("scaled_normal_kimi_linear_det"): every tensor is drawn with numpy
from (seed, crc32(tensor name)).

- matrices, the patch projection, the conv taps and the router: N(0, 1 /
  fan_in) (router logits of unit scale over unit-RMS tokens); biases 0;
- `A_log` = log U(1, 16) and `dt_bias` = softplus^-1 of exp U(log 0.001, log
  0.1): the KDA / Gated DeltaNet authors' initialisation, a value a channel;
- `e_score_correction_bias`: N(0, `expert_bias_std`^2). A zero bias would test
  nothing: at 0.02 it is as wide as three gaps between a token's eighth and
  ninth score of 256, so it moves a share of the choices
  (`routing_bias_moved.bulk`);
- RMSNorm weights 1 (plain), `o_norm` among them;
- the detection tokens N(0, `token_std`^2);
- the class head's last layer N(0, `class_gain`^2 / fan_in) and the "no
  object" class's bias `no_object_bias`: the regime of the answer
  (tools/regime.py), recorded in the configuration's file.

The checkpoint is written in bfloat16 (config.json + model.safetensors), as
the source ships its weights; the reference reads the same rounded values
back into float32, the program holds them as they are.

Time (the chip machine's host, 13 cores; PERF.md section 6 has the readings):
the driver stops a run at 360 s, and the reference's 16 images are part of it.
An image is 3.2 TFLOP of products and, around the recurrence, some forty passes
over arrays of 70 MB (tokens x 4096 floats). glibc hands a block of that size
back to the system when it is freed and maps a new one for the next pass, every
page of it faulted in and zeroed: 7 s of an image's 16.3 (`load_model` ends
with `keep_freed_blocks_mapped`, which changes no number); the recurrence in
blocks is another 2.7; 6.7 are left.
"""

import json
import os
import types
import zlib

NAME_TAG = "kimi_linear_det"
ARCHITECTURE = "KimiLinearDetForObjectDetection"
MEAN_STD = ((0.485, 0.456, 0.406), (0.229, 0.224, 0.225))
BLOCK = 1  # an image's activations are 70 MB an array (4300 tokens x 4096 floats): one at a time
THRESHOLD = 0.5
NORM_TOPK_EPS = 1e-20
L2_EPS = 1e-6


def _build(hf: dict):
    import torch
    from torch import nn
    from torch.nn import functional as F

    d, eps = hf["hidden_size"], hf["rms_norm_eps"]
    linear = hf["linear_attn_config"]
    low_rank = hf["gate_low_rank_dim"]

    def proj(inputs: int, outputs: int):
        return nn.Linear(inputs, outputs, bias=False)

    class RMSNorm(nn.Module):
        def __init__(self, width: int):
            super().__init__()
            self.weight = nn.Parameter(torch.ones(width))

        def forward(self, x):
            return self.weight * x * torch.rsqrt(x.pow(2).mean(-1, keepdim=True) + eps)

    class KimiDeltaAttention(nn.Module):
        def __init__(self):
            super().__init__()
            self.heads, self.dk = linear["num_heads"], linear["head_dim"]
            width, taps = self.heads * self.dk, linear["short_conv_kernel_size"]
            for name in ("q", "k", "v"):
                setattr(self, f"{name}_proj", proj(d, width))
                setattr(self, f"{name}_conv1d", nn.Conv1d(width, width, taps, groups=width,
                                                          padding=taps - 1, bias=False))
            self.f_a_proj, self.f_b_proj = proj(d, low_rank), proj(low_rank, width)
            self.g_a_proj, self.g_b_proj = proj(d, low_rank), proj(low_rank, width)
            self.b_proj = proj(d, self.heads)
            self.A_log = nn.Parameter(torch.zeros(1, 1, self.heads, 1))
            self.dt_bias = nn.Parameter(torch.zeros(width))
            self.o_norm = RMSNorm(self.dk)
            self.o_proj = proj(width, d)

        def mixed(self, name, x):
            """(B, T, heads, dk): projection, causal depthwise conv, SiLU."""
            y = getattr(self, f"{name}_proj")(x).transpose(1, 2)
            y = getattr(self, f"{name}_conv1d")(y)[..., :x.shape[1]].transpose(1, 2)
            return F.silu(y).reshape(*x.shape[:2], self.heads, self.dk)

        def recurrence(self, q, k, v, g, beta, block: int = 32):
            """The recurrence a block of tokens at a time (the module's docstring has the
            algebra). q, k, v, g: (N, T, dk) for N images x heads; beta: (N, T, 1)."""
            n, tokens, dk = q.shape
            state = torch.zeros(n, dk, dk)
            out = torch.empty(n, tokens, dk)
            for start in range(0, tokens, block):
                at = slice(start, start + block)
                qb, kb, vb, bb = q[:, at], k[:, at], v[:, at], beta[:, at]
                since = g[:, at].cumsum(1)  # G_t: a channel's log decay from the block's start through t
                # e^{G_t - G_s}, (N, t, s, dk): pairs t < s would be growth and are masked below
                span = (since[:, :, None] - since[:, None, :]).clamp_(max=0).exp_()
                pairs = torch.matmul(span.mul_(kb[:, None]), torch.stack([kb, qb], -1))  # (N, t, s, 2)
                kk, qk = pairs[..., 0].tril(-1), pairs[..., 1].tril()
                entering = since.exp()
                reads = torch.cat([kb * entering, qb * entering], 1) @ state  # S_0^T (e^{G_t} [k_t | q_t])
                rows = kb.shape[1]
                writes = torch.linalg.solve_triangular(bb * kk, bb * (vb - reads[:, :rows]),
                                                       upper=False, unitriangular=True)
                out[:, at] = reads[:, rows:] + qk @ writes
                leaving = (since[:, -1:] - since).exp_()  # from after token s to the block's end
                state = entering[:, -1, :, None] * state + (kb * leaving).transpose(1, 2) @ writes
            return out

        def recurrence_by_token(self, q, k, v, g, beta):
            """One image, token by token. q, k, v, g: (T, H, dk); beta: (T, H)."""
            tokens, heads, dk = q.shape
            # token-major in memory: the convolutions leave a channel's tokens side by side
            q, k, v, beta = (x.contiguous() for x in (q, k, v, beta))
            state = torch.zeros(heads, dk, dk)
            decay = torch.exp(g).contiguous()
            # a token reads the state once: S^T [exp(g) k | exp(g) q]
            reads = torch.stack([decay * k, decay * q], dim=-1)
            kq = (k * q).sum(-1)
            out = torch.empty(tokens, heads, dk)
            for t in range(tokens):
                memory = torch.bmm(state.transpose(1, 2), reads[t])  # (H, dv, 2)
                delta = beta[t, :, None] * (v[t] - memory[..., 0])
                out[t] = memory[..., 1] + delta * kq[t, :, None]
                state.mul_(decay[t, :, :, None]).baddbmm_(k[t, :, :, None], delta[:, None, :])
            return out

        def forward(self, x):
            q, k, v = self.mixed("q", x), self.mixed("k", x), self.mixed("v", x)
            raw = self.f_b_proj(self.f_a_proj(x)).float() + self.dt_bias
            g = -torch.exp(self.A_log) * F.softplus(raw).reshape(q.shape)
            beta = torch.sigmoid(self.b_proj(x).float())
            q = q * torch.rsqrt(q.pow(2).sum(-1, keepdim=True) + L2_EPS) * self.dk**-0.5
            k = k * torch.rsqrt(k.pow(2).sum(-1, keepdim=True) + L2_EPS)
            if hf.get("_reference_recurrence", "blocks") == "tokens":
                out = torch.stack([self.recurrence_by_token(q[i], k[i], v[i], g[i], beta[i])
                                   for i in range(x.shape[0])])
            else:
                by_head = [a.transpose(1, 2).flatten(0, 1) for a in (q, k, v, g, beta[..., None])]
                out = self.recurrence(*by_head).unflatten(0, (-1, self.heads)).transpose(1, 2)
            gate = torch.sigmoid(self.g_b_proj(self.g_a_proj(x))).reshape(q.shape)
            return self.o_proj((self.o_norm(out) * gate).flatten(2))

    class LatentAttention(nn.Module):
        def __init__(self):
            super().__init__()
            self.heads = hf["num_attention_heads"]
            self.nope, self.pe = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
            self.dv, self.rank = hf["v_head_dim"], hf["kv_lora_rank"]
            self.q_proj = proj(d, self.heads * (self.nope + self.pe))
            self.kv_a_proj_with_mqa = proj(d, self.rank + self.pe)
            self.kv_a_layernorm = RMSNorm(self.rank)
            self.kv_b_proj = proj(self.rank, self.heads * (self.nope + self.dv))
            self.o_proj = proj(self.heads * self.dv, d)

        def forward(self, x):
            b, t, _ = x.shape
            q = self.q_proj(x).reshape(b, t, self.heads, self.nope + self.pe).transpose(1, 2)
            latent = self.kv_a_proj_with_mqa(x)
            kv = self.kv_b_proj(self.kv_a_layernorm(latent[..., :self.rank]))
            kv = kv.reshape(b, t, self.heads, self.nope + self.dv).transpose(1, 2)
            k_pe = latent[:, None, :, self.rank:].expand(b, self.heads, t, self.pe)
            k, v = torch.cat([kv[..., :self.nope], k_pe], dim=-1), kv[..., self.nope:]
            scale = (self.nope + self.pe) ** -0.5
            if hf.get("_reference_attention", "sdpa") == "eager":
                scores = q @ k.transpose(-1, -2) * scale
                scores = scores + torch.full((t, t), float("-inf")).triu(1)
                out = torch.softmax(scores, dim=-1) @ v
            else:
                # zero columns up to the keys' width: torch's CPU flash kernel takes values as
                # wide as the keys (0.4 s an image against 2.7 through its other path); the
                # result's extra columns are zero and dropped
                wide = F.pad(v, (0, self.nope + self.pe - self.dv))
                out = F.scaled_dot_product_attention(q, k, wide, is_causal=True, scale=scale)
                out = out[..., :self.dv]
            return self.o_proj(out.transpose(1, 2).reshape(b, t, self.heads * self.dv))

    class SwiGlu(nn.Module):
        def __init__(self, width: int, names=("gate_proj", "up_proj", "down_proj")):
            super().__init__()
            self.names = names
            setattr(self, names[0], proj(d, width))
            setattr(self, names[1], proj(d, width))
            setattr(self, names[2], proj(width, d))

        def forward(self, x):
            gate, up, down = (getattr(self, name) for name in self.names)
            return down(F.silu(gate(x)) * up(x))

    class Gate(nn.Module):
        def __init__(self):
            super().__init__()
            self.weight = nn.Parameter(torch.zeros(hf["num_routed_experts"], d))
            self.e_score_correction_bias = nn.Parameter(torch.zeros(hf["num_routed_experts"]))

        def forward(self, x):
            scores = torch.sigmoid(F.linear(x.float(), self.weight.float()))
            _, chosen = torch.topk(scores + self.e_score_correction_bias,
                                   hf["num_experts_per_token"], dim=-1)
            weights = torch.gather(scores, 1, chosen)
            if hf["moe_renormalize"]:
                weights = weights / (weights.sum(-1, keepdim=True) + NORM_TOPK_EPS)
            return weights * hf["routed_scaling_factor"], chosen

    class SparseMoe(nn.Module):
        """The router over all, the routed experts held here, the shared expert."""

        def __init__(self):
            super().__init__()
            self.held = range(hf["expert_offset"], hf["expert_offset"] + hf["num_experts"])
            self.gate = Gate()
            width = hf["moe_intermediate_size"]
            self.experts = nn.ModuleDict(
                {str(e): SwiGlu(width, ("w1", "w3", "w2")) for e in self.held})
            self.shared_experts = SwiGlu(width * hf["num_shared_experts"])

        def forward(self, x):
            shape = x.shape
            x = x.reshape(-1, shape[-1])
            weights, chosen = self.gate(x)
            out = self.shared_experts(x)
            for e in self.held:
                rows, slot = torch.where(chosen == e)
                if rows.numel():
                    term = self.experts[str(e)](x[rows]) * weights[rows, slot, None]
                    out.index_add_(0, rows, term)
            return out.reshape(shape)

    class Layer(nn.Module):
        def __init__(self, index: int):
            super().__init__()
            self.self_attn = (KimiDeltaAttention() if index + 1 in linear["kda_layers"]
                              else LatentAttention())
            if index < hf["first_k_dense_replace"]:
                self.mlp = SwiGlu(hf["intermediate_size"])
            else:
                self.block_sparse_moe = SparseMoe()
            self.input_layernorm = RMSNorm(d)
            self.post_attention_layernorm = RMSNorm(d)

        def forward(self, x):
            x = x + self.self_attn(self.input_layernorm(x))
            ffn = self.mlp if hasattr(self, "mlp") else self.block_sparse_moe
            return x + ffn(self.post_attention_layernorm(x))

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
            self.norm = RMSNorm(d)
            self.class_labels_classifier = Head(hf["num_labels"] + 1)
            self.bbox_predictor = Head(4)

        def forward(self, pixel_values):
            x = self.patch_embeddings.projection(pixel_values).flatten(2).transpose(1, 2)
            n_det = self.detection_tokens.shape[1]
            x = torch.cat([x, self.detection_tokens.expand(x.shape[0], -1, -1)], dim=1)
            for layer in self.layers:
                x = layer(x)
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
    checkout whose program has no such family (the parent of the PR that added
    it, run on this cell) is told so here, before minutes of seeding and four
    gigabytes of checkpoint for a server that would then refuse the name: a
    path is looked at, nothing of the program is imported."""
    import torch

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if not os.path.exists(os.path.join(root, "spotter_tpu", "models", "kimi_linear.py")):
        raise RuntimeError("this checkout's program has no kimi_linear_det family: nothing to run")
    with torch.no_grad():
        return _build(hf)


def keep_freed_blocks_mapped() -> bool:
    """From here on this process's allocator serves every request from its heap
    and gives nothing back: an activation's block is the one a pass before it
    freed, its pages already there. The weights, allocated before, stay where
    they are. Nothing is done where the C library has no `mallopt`."""
    import ctypes

    mallopt = getattr(ctypes.CDLL(None), "mallopt", None)
    if mallopt is None:
        return False
    m_trim_threshold, m_mmap_max = -1, -4  # <malloc.h>
    return bool(mallopt(m_mmap_max, 0) and mallopt(m_trim_threshold, 2**31 - 1))


def load_model(checkpoint: str):
    import torch
    from safetensors.torch import load_file

    with open(os.path.join(checkpoint, "config.json")) as f:
        hf = json.load(f)
    with torch.no_grad():
        with torch.device("meta"):  # no default weights are drawn: the checkpoint's tensors become the model's
            model = _build(hf)
        state = load_file(os.path.join(checkpoint, "model.safetensors"))
        model.load_state_dict({k: v.float() for k, v in state.items()}, strict=True, assign=True)
    keep_freed_blocks_mapped()
    return model


def input_hw(hf: dict) -> tuple:
    return tuple(hf["image_size"])


def seed_weights(model, w: dict) -> None:
    import numpy as np
    import torch

    assert w["scheme"] == "scaled_normal_kimi_linear_det", w["scheme"]
    last = "class_labels_classifier.layers.2"
    for name, tensor in model.state_dict().items():
        rng = np.random.default_rng([int(w["seed"]), zlib.crc32(name.encode())])
        shape = tuple(tensor.shape)
        leaf = name.rsplit(".", 1)[-1]
        if name == "detection_tokens":
            value = rng.standard_normal(shape) * w["token_std"]
        elif leaf == "A_log":
            value = np.log(rng.uniform(1.0, 16.0, shape))
        elif leaf == "dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            value = dt + np.log(-np.expm1(-dt))  # softplus(value) == dt
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
