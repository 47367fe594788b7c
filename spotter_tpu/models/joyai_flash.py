"""Flax JoyAI-LLM-Flash decoder layers as a detector body (`joyai_flash_det`).

JoyAI-LLM-Flash's decoder layers are DeepSeek-V3's (its config.json's keys are
`DeepseekV3Config`'s, letter for letter), as published and causal as
published, in YOLOS's form (the seams are `qwen3_next.py`'s): the patch tokens
of an image in raster order, the learned detection tokens appended (so they
see the whole image), the decoder layers, the final RMSNorm, and YOLOS's two
MLP heads on the detection tokens. `h = x + mixer(input_layernorm(x))`, `y =
h + ffn(post_attention_layernorm(h))`; RMSNorm's weight is plain. Names follow
transformers' `modeling_deepseek_v3.py`.

- Every mixer is latent attention (`latent_attention`;
  `kimi_linear.LatentAttention`): queries out of a latent of `q_lora_rank`
  (`latent_q`), keys and values out of a latent of `kv_lora_rank`
  (`latent_kv`), the 64 decoupled channels of each query head and the one key
  every head shares turned by the token's index at `rope_theta`
  (`latent_rope`), causal softmax over keys of 192, values of 128. The
  checkpoint's rotary channels are interleaved pairs; the converter permutes
  them once into the half-split order this module turns
  (`convert/joyai_flash_rules.py`), which leaves every q . k as it was.
- Feed-forward: the first `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size` (`dense_mlp`); the others (`moe`) `s = sigmoid(x W_r)`
  over all `num_routed_experts`, the `num_experts_per_token` best by `s +
  e_score_correction_bias`, weighed by `s` over (their sum + 1e-20) times
  `routed_scaling_factor`; the terms of the `num_experts` held here from
  `expert_offset` on, plus the shared expert, ungated (`kimi_linear.SparseMoe`:
  the router is the same but for its scale).

Departures, each in the configuration's `assumed` too: the router's product
runs in float32 at the highest precision; no token embedding, no output head,
no multi-token-prediction module. The matrices are held in the serving
policy's type (`zoo.py`).

Beside the detections the module returns the three `moe_*` counters of the
other routed families (`models/kimi_linear.py`).
"""

import jax
import jax.numpy as jnp
from flax import linen as nn

from spotter_tpu.models.configs import JoyAIFlashDetConfig
from spotter_tpu.models.kimi_linear import DenseMlp, LatentAttention, SparseMoe
from spotter_tpu.models.layers import MLPHead, PatchEmbed
from spotter_tpu.models.lfm2_moe import RMSNorm


class DecoderLayer(nn.Module):
    """Returns (x, (counts, moved) of a routed layer or None)."""

    config: JoyAIFlashDetConfig
    dense: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        normed = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        with jax.named_scope("latent_attention"):
            x = x + LatentAttention(cfg, dtype=self.dtype, q_lora_rank=cfg.q_lora_rank,
                                    rope_theta=cfg.rope_theta, name="self_attn")(normed)
        normed = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if self.dense:
            with jax.named_scope("dense_mlp"):
                return x + DenseMlp(cfg, cfg.intermediate_size, dtype=self.dtype, name="mlp")(normed), None
        with jax.named_scope("moe"):
            out, counts, moved = SparseMoe(cfg, dtype=self.dtype, name="mlp")(normed)
        return x + out, (counts, moved)


class JoyAIFlashDetector(nn.Module):
    """{"logits": (B, Q, C + 1), "pred_boxes": (B, Q, 4), "moe_expert_tokens":
    (B, routed layers, held), "moe_assignments", "moe_bias_moved": (B, routed
    layers)}."""

    config: JoyAIFlashDetConfig
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
            for i in range(cfg.num_hidden_layers):
                x, counted = DecoderLayer(cfg, dense=i < cfg.first_k_dense_replace,
                                          dtype=self.dtype, name=f"layer{i}")(x)
                if counted is not None:
                    routed.append(counted)
            x = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
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
                (b, len(routed)), x.shape[1] * cfg.num_experts_per_token, jnp.int32),
            "moe_bias_moved": jnp.stack([moved for _, moved in routed], axis=1),
        }
