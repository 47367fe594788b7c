"""Plain reference of `joyai_flash_det` (models/joyai_flash.py): DeepSeek-V3's
layer equations (arXiv:2412.19437, section 2.1) in straightforward `jax.numpy`
and float32, with no kernel, no barrier, no parts, no window loop and no
batching. It reads a parameter tree of the served module's shape whose
rotary channels are in the checkpoint's own interleaved order, the tree
before the converter permutes them (`convert/joyai_flash_rules.py:
to_half_split`), so the tests hold the permutation and the served module's
half-split rotation against the equations (tests/test_joyai_flash.py); the
benchmark's torch module of transformers' DeepSeek-V3 classes is held against
this one in benchmarks/tests/test_joyai_flash_det.py.

- latent attention: `q = q_b(rmsnorm(q_a x))`, each head's channels
  `[q_nope | q_pe]`; `[c | k_pe] = kv_a x`; `[k_nope_h | v_h] =
  kv_b(rmsnorm(c))`; the rotary term written from the equations, each pair
  `(x[2i], x[2i + 1])` of q_pe and of the one k_pe every head shares turned by
  `pos * theta^(-2i / 64)`, `pos` the token's index; the whole (T, T) score
  matrix of every head, `(q_nope . k_nope + q_pe . k_pe) / sqrt(192)`, masked
  above the diagonal; `o_proj`;
- routed experts: a loop over the held experts, each applied to every token
  and weighted by the token's weight for it (`kimi_linear_reference`: the
  choice by `sigmoid + bias`, the weight the plain sigmoid over (the chosen
  ones' sum + 1e-20) times the scale); the shared expert added as it is.

Departures from the published model, as in the served one: no token
embedding, output head or multi-token-prediction module; YOLOS's patch
projection, detection tokens and heads around the layers. Every function runs
under `jax.default_matmul_precision("highest")`.
"""

import jax
import jax.numpy as jnp
import numpy as np

from spotter_tpu.models.configs import JoyAIFlashDetConfig
from spotter_tpu.testing.kimi_linear_reference import dense_mlp, rms_norm, sigmoid, sparse_moe
from spotter_tpu.testing.qwen3_next_reference import _f32, _head, _highest, embed


def interleaved_rotary(x, theta: float):
    """x: (T, ..., D); each pair (x[2i], x[2i + 1]) of token t turned by the
    angle t * theta^(-2i / D). The pairs stay where they are."""
    t, d = x.shape[0], x.shape[-1]
    angle = np.arange(t, dtype=np.float64)[:, None] * theta ** (-np.arange(0, d, 2) / d)
    shape = (t,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(angle).reshape(shape), jnp.float32)
    sin = jnp.asarray(np.sin(angle).reshape(shape), jnp.float32)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


@_highest
def latent_attention(p, x, cfg: JoyAIFlashDetConfig):
    """x: (T, d), one image."""
    p = _f32(p)
    t, heads = x.shape[0], cfg.num_attention_heads
    nope, pe = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv, rank = cfg.v_head_dim, cfg.kv_lora_rank
    q = rms_norm(x @ p["q_a_proj"]["kernel"], p["q_a_layernorm"]["weight"], cfg.rms_norm_eps)
    q = (q @ p["q_b_proj"]["kernel"]).reshape(t, heads, nope + pe)
    latent = x @ p["kv_a_proj_with_mqa"]["kernel"]
    c, k_pe = latent[:, :rank], latent[:, rank:]
    kv = (rms_norm(c, p["kv_a_layernorm"]["weight"], cfg.rms_norm_eps)
          @ p["kv_b_proj"]["kernel"]).reshape(t, heads, nope + dv)
    q_pe = interleaved_rotary(q[..., nope:], cfg.rope_theta)
    k_pe = interleaved_rotary(k_pe, cfg.rope_theta)
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * (nope + pe)**-0.5
    scores = jnp.where(np.tril(np.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return out.reshape(t, heads * dv) @ p["o_proj"]["kernel"]


def decoder_layer(p, x, cfg: JoyAIFlashDetConfig, index: int, held=None):
    """`held=(offset, n)`: the tree's experts are those; None: the configuration's share."""
    normed = rms_norm(x, p["input_layernorm"]["weight"], cfg.rms_norm_eps)
    x = x + latent_attention(p["self_attn"], normed, cfg)
    normed = rms_norm(x, p["post_attention_layernorm"]["weight"], cfg.rms_norm_eps)
    if index < cfg.first_k_dense_replace:
        return x + dense_mlp(p["mlp"], normed)
    return x + sparse_moe(p["mlp"], normed, cfg, held)


def detector(params, pixels, cfg: JoyAIFlashDetConfig):
    """One image (H, W, C) -> {"logits": (Q, C + 1), "pred_boxes": (Q, 4)}."""
    x = embed(params, jnp.asarray(pixels, jnp.float32), cfg)
    for i in range(cfg.num_hidden_layers):
        x = decoder_layer(params[f"layer{i}"], x, cfg, i)
    det = rms_norm(x, params["norm"]["weight"], cfg.rms_norm_eps)[-cfg.num_detection_tokens:]
    return {
        "logits": _head(params["class_labels_classifier"], det),
        "pred_boxes": sigmoid(_head(params["bbox_predictor"], det)),
    }
