"""Plain reference of `lfm2_moe_det` (models/lfm2_moe.py): the published layer
equations in straightforward `jax.numpy` and float32, with no kernel, no
window loop, no grouping and no batching. It reads the same parameter tree as
the served module, so the two are compared layer kind by layer kind and end
to end on seeded weights (tests/test_lfm2_moe.py); transformers' torch layers
are held against the served module in benchmarks/tests/test_lfm2_moe_det.py.

- short convolution: three shifted products, `y_t = sum_j w_j (B u)_(t - 2 + j)`;
- attention: the whole (T, T) score matrix, masked above the diagonal, the
  key-value heads repeated;
- routed experts: a loop over all the experts, each applied to every token
  and weighted by the token's weight for it, zero where it was not among the
  token's k. The choice is by `sigmoid + bias` (a stable argsort, the lower
  index first among equals), the weight the unbiased sigmoid over `(the
  chosen ones' sum + 1e-6)`, times `routed_scaling_factor`.

Every function runs under `jax.default_matmul_precision("highest")`: on a TPU
a float32 product is otherwise a bfloat16 one. Departures from the source are
the served module's (its docstring): none in the arithmetic.
"""

import jax
import jax.numpy as jnp
import numpy as np

from spotter_tpu.models.configs import Lfm2MoeDetConfig
from spotter_tpu.testing.qwen3_next_reference import _f32, _head, _highest, embed, silu


def rms_norm(x, weight, eps):
    return jnp.asarray(weight, jnp.float32) * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


@_highest
def short_conv(p, x, cfg: Lfm2MoeDetConfig):
    """x: (T, d), one image."""
    p = _f32(p)
    t, d, taps = x.shape[0], cfg.hidden_size, cfg.conv_L_cache
    bcu = x @ p["in_proj"]["kernel"]
    gate_in, gate_out, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d)), gate_in * u])
    mixed = sum(padded[j:j + t] * p["conv"][j] for j in range(taps))
    return (gate_out * mixed) @ p["out_proj"]["kernel"]


@_highest
def attention(p, x, cfg: Lfm2MoeDetConfig):
    p = _f32(p)
    t = x.shape[0]
    heads, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = rms_norm((x @ p["q_proj"]["kernel"]).reshape(t, heads, hd), p["q_layernorm"]["weight"], cfg.norm_eps)
    k = rms_norm((x @ p["k_proj"]["kernel"]).reshape(t, kv, hd), p["k_layernorm"]["weight"], cfg.norm_eps)
    v = (x @ p["v_proj"]["kernel"]).reshape(t, kv, hd)
    inv_freq = 1.0 / cfg.rope_theta ** (np.arange(0, hd, 2) / hd)
    angle = np.arange(t)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.cos(np.concatenate([angle, angle], -1)), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(np.concatenate([angle, angle], -1)), jnp.float32)[:, None]

    def turn(y):
        return y * cos + jnp.concatenate([-y[..., hd // 2:], y[..., :hd // 2]], -1) * sin

    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    scores = jnp.where(np.tril(np.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, heads * hd) @ p["out_proj"]["kernel"]


@_highest
def dense_mlp(p, x):
    p = _f32(p)
    return (silu(x @ p["w1"]["kernel"]) * (x @ p["w3"]["kernel"])) @ p["w2"]["kernel"]


@_highest
def routing_weights(p, x, cfg: Lfm2MoeDetConfig):
    """(T, E): each token's weight for every expert, zero off its k."""
    s = 1.0 / (1.0 + jnp.exp(-(x @ jnp.asarray(p["router"], jnp.float32))))
    choose_by = s + jnp.asarray(p["expert_bias"], jnp.float32) if cfg.use_expert_bias else s
    order = jnp.argsort(-choose_by, axis=-1, stable=True)[:, :cfg.num_experts_per_tok]
    chosen = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], order].set(1.0)
    weights = s * chosen
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return weights * cfg.routed_scaling_factor


@_highest
def sparse_moe(p, x, cfg: Lfm2MoeDetConfig):
    weights = routing_weights(p, x, cfg)
    p = _f32(p)
    inter = cfg.moe_intermediate_size
    out = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        hidden = x @ p["experts_gate_up"][e]
        out = out + weights[:, e, None] * (
            (silu(hidden[:, :inter]) * hidden[:, inter:]) @ p["experts_down"][e])
    return out


def decoder_layer(p, x, cfg: Lfm2MoeDetConfig, index: int):
    normed = rms_norm(x, p["operator_norm"]["weight"], cfg.norm_eps)
    if cfg.layer_types[index] == "full_attention":
        x = x + attention(p["self_attn"], normed, cfg)
    else:
        x = x + short_conv(p["conv"], normed, cfg)
    normed = rms_norm(x, p["ffn_norm"]["weight"], cfg.norm_eps)
    if index < cfg.num_dense_layers:
        return x + dense_mlp(p["feed_forward"], normed)
    return x + sparse_moe(p["feed_forward"], normed, cfg)


def detector(params, pixels, cfg: Lfm2MoeDetConfig):
    """One image (H, W, C) -> {"logits": (Q, C + 1), "pred_boxes": (Q, 4)}."""
    x = embed(params, jnp.asarray(pixels, jnp.float32), cfg)
    for i in range(cfg.num_hidden_layers):
        x = decoder_layer(params[f"layer{i}"], x, cfg, i)
    det = rms_norm(x, params["embedding_norm"]["weight"], cfg.norm_eps)[-cfg.num_detection_tokens:]
    return {
        "logits": _head(params["class_labels_classifier"], det),
        "pred_boxes": 1.0 / (1.0 + jnp.exp(-_head(params["bbox_predictor"], det))),
    }
