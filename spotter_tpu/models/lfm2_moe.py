"""Flax LFM2-MoE decoder layers as a detector body (`lfm2_moe_det`).

LFM2-8B-A1B's decoder layers, as published and causal as published, in
YOLOS's form (the seams are `qwen3_next.py`'s): the patch tokens of an image
in raster order, the learned detection tokens appended (so they see the whole
image), the decoder layers, the published final RMSNorm (`embedding_norm`),
and YOLOS's two MLP heads on the detection tokens. Semantics follow
transformers' `modeling_lfm2.py` (4.57: `Lfm2ShortConv.slow_forward`,
`Lfm2Attention`, `Lfm2RMSNorm`) and, for the feed-forward blocks,
`modeling_lfm2_moe.py`: `h = x + mixer(operator_norm(x))`, `y = h +
ffn(ffn_norm(h))`; RMSNorm's weight is plain, `w * x / sqrt(mean(x^2) + eps)`.

- Gated short convolution (`short_conv`, layer type "conv"): `in_proj` gives
  B | C | u; a depthwise causal conv of `conv_L_cache` taps over `B * u`;
  `out_proj(C * conv)`. No activation, no bias.
- Attention (`causal_attention`, "full_attention"): q, k, v projections;
  RMSNorm over each q and k head; rotary embedding over the whole head;
  causal softmax attention, scale head_dim^-0.5, each key-value head serving
  `heads / kv_heads` query heads; `out_proj`. No gate, no biases.
- Feed-forward: the first `num_dense_layers` layers a SwiGLU of
  `intermediate_size` (`dense_mlp`), `w2(silu(w1 x) * w3 x)`; the others
  (`moe`) `num_experts` routed experts of `moe_intermediate_size`, all held
  here: `s = sigmoid(x W_r)`, the `num_experts_per_tok` best by `s +
  expert_bias`, weighed by `s` over `(their sum + 1e-6)` times
  `routed_scaling_factor` (`ops/moe.py`). No shared expert.

Departures, each in the configuration's `assumed` too: the router's product
runs in float32 at the highest precision (the source computes the logits in
the model's type); no token embedding, no output head. The matrices are held
in the serving policy's type (`zoo.py` casts the tree once at build).

Beside the detections the module returns, per image and routed layer,
`moe_expert_tokens` (B, routed layers, experts): the tokens each expert took,
`moe_assignments` (B, routed layers): the image's tokens times k, and
`moe_bias_moved` (B, routed layers): how many of those selections are not
among the k best of the unbiased scores. The engine adds them to `/metrics`.
"""

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from spotter_tpu.models.configs import Lfm2MoeDetConfig
from spotter_tpu.models.layers import (
    FLASH_ATTN_MIN_SEQ,
    MLPHead,
    PatchEmbed,
    QuantDense,
    causal_gqa_attention,
    flash_attention_enabled,
)
from spotter_tpu.models.qwen3_next import apply_rotary, rms_norm, rotary_tables
from spotter_tpu.ops import moe as moe_ops

NORM_TOPK_EPS = 1e-6  # transformers' Lfm2MoeSparseMoeBlock: weights / (sum + 1e-6)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        return rms_norm(x, weight, self.eps, zero_centred=False)


def _dense(features: int, dtype, name: str):
    return QuantDense(features, use_bias=False, dtype=dtype, name=name)


class ShortConv(nn.Module):
    config: Lfm2MoeDetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d, taps = self.config.hidden_size, self.config.conv_L_cache
        t = x.shape[1]
        bcu = _dense(3 * d, self.dtype, "in_proj")(x)
        conv = self.param("conv", nn.initializers.lecun_normal(), (taps, d), jnp.float32)
        gate_in, gate_out, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
        # depthwise causal conv: y_t = sum_j w_j x_(t - taps + 1 + j)
        padded = jnp.pad(gate_in * u, ((0, 0), (taps - 1, 0), (0, 0)))
        mixed = sum(padded[:, j:j + t] * conv[j].astype(self.dtype) for j in range(taps))
        return _dense(d, self.dtype, "out_proj")(gate_out * mixed)


class Attention(nn.Module):
    config: Lfm2MoeDetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, _ = x.shape
        heads, kv_heads, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(heads * hd, self.dtype, "q_proj")(x).reshape(b, t, heads, hd)
        k = _dense(kv_heads * hd, self.dtype, "k_proj")(x).reshape(b, t, kv_heads, hd)
        v = _dense(kv_heads * hd, self.dtype, "v_proj")(x).reshape(b, t, kv_heads, hd)
        q = RMSNorm(cfg.norm_eps, name="q_layernorm")(q)
        k = RMSNorm(cfg.norm_eps, name="k_layernorm")(k)
        cos, sin = rotary_tables(t, hd, cfg.rope_theta)
        q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        q = q * hd**-0.5
        if flash_attention_enabled() and t >= FLASH_ATTN_MIN_SEQ:
            out = causal_gqa_attention(q, k, v)
        else:
            group = heads // kv_heads
            qh = q.reshape(b, t, kv_heads, group, hd)
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qh, k).astype(jnp.float32)
            logits = jnp.where(np.tril(np.ones((t, t), bool)), logits, -jnp.inf)
            weights = nn.softmax(logits, axis=-1).astype(self.dtype)
            out = jnp.einsum("bkgqs,bskd->bqkgd", weights, v)
        return _dense(cfg.hidden_size, self.dtype, "out_proj")(out.reshape(b, t, heads * hd))


class DenseMlp(nn.Module):
    config: Lfm2MoeDetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        inter = self.config.intermediate_size
        hidden = nn.silu(_dense(inter, self.dtype, "w1")(x)) * _dense(inter, self.dtype, "w3")(x)
        return _dense(self.config.hidden_size, self.dtype, "w2")(hidden)


class SparseMoe(nn.Module):
    """Returns (the layer's output, the tokens each expert took of each image
    (B, experts), the selections of each image the bias moved (B,))."""

    config: Lfm2MoeDetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, d = x.shape
        n, inter = cfg.num_experts, cfg.moe_intermediate_size
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (d, n), jnp.float32)
        bias = self.param("expert_bias", nn.initializers.zeros, (n,), jnp.float32)
        gate_up = self.param("experts_gate_up", init, (n, d, 2 * inter), jnp.float32)
        down = self.param("experts_down", init, (n, inter, d), jnp.float32)

        flat = x.reshape(b * t, d)
        with jax.named_scope("router"):
            scores = moe_ops.router_scores(flat, router, "sigmoid")
            weights, experts = moe_ops.select(
                scores, cfg.num_experts_per_tok, cfg.norm_topk_prob,
                bias=bias if cfg.use_expert_bias else None, eps=NORM_TOPK_EPS,
                scale=cfg.routed_scaling_factor)
            counts = moe_ops.held_tokens(experts.reshape(b, -1), 0, n)
            moved = moe_ops.moved_by_bias(scores, experts).reshape(b, t).sum(-1)
        with jax.named_scope("experts"):
            routed = moe_ops.routed_experts(
                flat, weights, experts, gate_up.astype(self.dtype), down.astype(self.dtype))
        return routed.astype(self.dtype).reshape(b, t, d), counts, moved


class DecoderLayer(nn.Module):
    """Returns (x, None) from a dense layer, (x, (counts, moved)) from a routed one."""

    config: Lfm2MoeDetConfig
    kind: str
    dense: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        normed = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
        if self.kind == "full_attention":
            with jax.named_scope("causal_attention"):
                x = x + Attention(cfg, dtype=self.dtype, name="self_attn")(normed)
        else:
            with jax.named_scope("short_conv"):
                x = x + ShortConv(cfg, dtype=self.dtype, name="conv")(normed)
        normed = RMSNorm(cfg.norm_eps, name="ffn_norm")(x)
        if self.dense:
            with jax.named_scope("dense_mlp"):
                return x + DenseMlp(cfg, dtype=self.dtype, name="feed_forward")(normed), None
        with jax.named_scope("moe"):
            out, counts, moved = SparseMoe(cfg, dtype=self.dtype, name="feed_forward")(normed)
        return x + out, (counts, moved)


class Lfm2MoeDetector(nn.Module):
    """{"logits": (B, Q, C + 1), "pred_boxes": (B, Q, 4), "moe_expert_tokens":
    (B, routed layers, experts), "moe_assignments", "moe_bias_moved": (B,
    routed layers)}."""

    config: Lfm2MoeDetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixel_values):
        cfg = self.config
        b = pixel_values.shape[0]
        n_det = cfg.num_detection_tokens
        with jax.named_scope("embed"):
            x = PatchEmbed(cfg.hidden_size, cfg.patch_size, dtype=self.dtype,
                           name="patch_projection")(pixel_values)
            det = self.param("detection_tokens", nn.initializers.zeros,
                             (1, n_det, cfg.hidden_size), jnp.float32)
            x = jnp.concatenate(
                [x, jnp.broadcast_to(det.astype(self.dtype), (b, n_det, cfg.hidden_size))], axis=1)
        routed = []
        with jax.named_scope("decoder"):
            for i, kind in enumerate(cfg.layer_types):
                x, counted = DecoderLayer(cfg, kind, dense=i < cfg.num_dense_layers,
                                          dtype=self.dtype, name=f"layer{i}")(x)
                if counted is not None:
                    routed.append(counted)
            x = RMSNorm(cfg.norm_eps, name="embedding_norm")(x)
        det_out = x[:, -n_det:]
        with jax.named_scope("heads"):
            # fp32 head outputs under bf16 compute, as yolos.py
            logits = MLPHead(cfg.hidden_size, cfg.num_labels + 1, 3, dtype=self.dtype,
                             name="class_labels_classifier")(det_out)
            boxes = nn.sigmoid(MLPHead(cfg.hidden_size, 4, 3, dtype=self.dtype,
                                       name="bbox_predictor")(det_out).astype(jnp.float32))
        return {
            "logits": logits.astype(jnp.float32),
            "pred_boxes": boxes,
            "moe_expert_tokens": jnp.stack([counts for counts, _ in routed], axis=1),
            "moe_assignments": jnp.full(
                (b, len(routed)), x.shape[1] * cfg.num_experts_per_tok, jnp.int32),
            "moe_bias_moved": jnp.stack([moved for _, moved in routed], axis=1),
        }
