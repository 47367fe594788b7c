"""Plain reference of `kimi_linear_det` (models/kimi_linear.py): the layer
equations in straightforward `jax.numpy` and float32, with no kernel, no
chunk, no window loop, no grouping and no batching. It reads the same
parameter tree as the served module, so the two are compared mixer by mixer
and end to end on seeded weights (tests/test_kimi_linear.py); the benchmark's
torch copy of the same equations is held against this one in
benchmarks/tests/test_kimi_linear_det.py.

- Kimi Delta Attention: three projections, each through its own depthwise
  causal convolution of 4 taps (shifted products) and a SiLU; the decay
  `g = -exp(A_log) softplus(f_b(f_a x) + dt_bias)`, a value a head, token and
  key channel; the recurrence token by token, `S <- diag(exp(g_t)) S; S <- S +
  k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t`, a `lax.scan` over the tokens;
  a per-head RMSNorm times `sigmoid(g_b(g_a x))`; the output projection;
- latent attention: the whole (T, T) score matrix of every head, masked above
  the diagonal; keys `[k_nope_h | k_pe]` with the one `k_pe` every head
  shares, no rotation on either side;
- routed experts: a loop over the held experts, each applied to every token
  and weighted by the token's weight for it, zero where it was not among the
  token's k; the choice by `sigmoid + bias` (a stable argsort), the weight the
  plain sigmoid over (the chosen ones' sum + 1e-20) times the scale; the shared
  expert added as it is.

Every function runs under `jax.default_matmul_precision("highest")`.
"""

import jax
import jax.numpy as jnp
import numpy as np

from spotter_tpu.models.configs import KimiLinearDetConfig
from spotter_tpu.testing.qwen3_next_reference import _f32, _head, _highest, embed, silu


def rms_norm(x, weight, eps):
    scale = jnp.asarray(weight, jnp.float32)
    return scale * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


@_highest
def kda_rule(q, k, v, g, beta):
    """q, k: (T, H, dk) normalised, q scaled; v: (T, H, dv); g: (T, H, dk);
    beta: (T, H). Token by token."""

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[:, :, None]
        memory = jnp.einsum("hkv,hk->hv", state, k_t)
        delta = (v_t - memory) * beta_t[:, None]
        state = state + jnp.einsum("hk,hv->hkv", k_t, delta)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, out = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32), (q, k, v, g, beta))
    return out


def kda_gate(p, x, cfg: KimiLinearDetConfig):
    """(T, H, dk): the log decay of every head, token and key channel."""
    p = _f32(p)
    heads, dk = cfg.linear_num_heads, cfg.linear_head_dim
    raw = (x @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"] + p["dt_bias"]
    return -jnp.exp(p["A_log"])[:, None] * jnp.logaddexp(raw, 0.0).reshape(-1, heads, dk)


@_highest
def kimi_delta_attention(p, x, cfg: KimiLinearDetConfig):
    """x: (T, d), one image."""
    g = kda_gate(p, x, cfg)
    p = _f32(p)
    t = x.shape[0]
    heads, dk, taps = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel

    def conv(name):
        y = x @ p[f"{name}_proj"]["kernel"]
        padded = jnp.concatenate([jnp.zeros((taps - 1, y.shape[1])), y])
        mixed = sum(padded[j:j + t] * p[f"{name}_conv"][j] for j in range(taps))
        return silu(mixed).reshape(t, heads, dk)

    q, k, v = conv("q"), conv("k"), conv("v")
    beta = sigmoid(x @ p["b_proj"]["kernel"])
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk**-0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    out = kda_rule(q, k, v, g, beta)
    gate = sigmoid((x @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"]).reshape(t, heads, dk)
    out = rms_norm(out, p["o_norm"], cfg.rms_norm_eps) * gate
    return out.reshape(t, heads * dk) @ p["o_proj"]["kernel"]


@_highest
def latent_attention(p, x, cfg: KimiLinearDetConfig):
    p = _f32(p)
    t, heads = x.shape[0], cfg.num_attention_heads
    nope, pe = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dv, rank = cfg.v_head_dim, cfg.kv_lora_rank
    q = (x @ p["q_proj"]["kernel"]).reshape(t, heads, nope + pe)
    latent = x @ p["kv_a_proj_with_mqa"]["kernel"]
    c, k_pe = latent[:, :rank], latent[:, rank:]
    kv = (rms_norm(c, p["kv_a_layernorm"]["weight"], cfg.rms_norm_eps)
          @ p["kv_b_proj"]["kernel"]).reshape(t, heads, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, heads, pe))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (nope + pe)**-0.5
    scores = jnp.where(np.tril(np.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return out.reshape(t, heads * dv) @ p["o_proj"]["kernel"]


@_highest
def dense_mlp(p, x):
    p = _f32(p)
    hidden = silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
    return hidden @ p["down_proj"]["kernel"]


@_highest
def routing_weights(p, x, cfg: KimiLinearDetConfig):
    """(T, all routed experts): each token's weight for every expert, zero off its k."""
    s = sigmoid(x @ jnp.asarray(p["router"], jnp.float32))
    choose_by = s + jnp.asarray(p["e_score_correction_bias"], jnp.float32)
    order = jnp.argsort(-choose_by, axis=-1, stable=True)[:, :cfg.num_experts_per_token]
    chosen = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], order].set(1.0)
    weights = s * chosen
    if cfg.moe_renormalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor


@_highest
def sparse_moe(p, x, cfg: KimiLinearDetConfig, held=None):
    """`held=(offset, n)`: the tree's expert matrices are those experts; None:
    the configuration's own share. The shared expert is added whole."""
    offset, n = held or (cfg.expert_offset, cfg.num_experts)
    weights = routing_weights(p, x, cfg)
    p = _f32(p)
    inter = cfg.moe_intermediate_size
    out = dense_mlp(p["shared_experts"], x)
    for e in range(n):
        hidden = x @ p["experts_gate_up"][e]
        out = out + weights[:, offset + e, None] * (
            (silu(hidden[:, :inter]) * hidden[:, inter:]) @ p["experts_down"][e])
    return out


def decoder_layer(p, x, cfg: KimiLinearDetConfig, index: int):
    normed = rms_norm(x, p["input_layernorm"]["weight"], cfg.rms_norm_eps)
    if cfg.layer_kind(index) == "kda":
        x = x + kimi_delta_attention(p["self_attn"], normed, cfg)
    else:
        x = x + latent_attention(p["self_attn"], normed, cfg)
    normed = rms_norm(x, p["post_attention_layernorm"]["weight"], cfg.rms_norm_eps)
    if index < cfg.first_k_dense_replace:
        return x + dense_mlp(p["mlp"], normed)
    return x + sparse_moe(p["block_sparse_moe"], normed, cfg)


def detector(params, pixels, cfg: KimiLinearDetConfig):
    """One image (H, W, C) -> {"logits": (Q, C + 1), "pred_boxes": (Q, 4)}."""
    x = embed(params, jnp.asarray(pixels, jnp.float32), cfg)
    for i in range(cfg.num_hidden_layers):
        x = decoder_layer(params[f"layer{i}"], x, cfg, i)
    det = rms_norm(x, params["norm"]["weight"], cfg.rms_norm_eps)[-cfg.num_detection_tokens:]
    return {
        "logits": _head(params["class_labels_classifier"], det),
        "pred_boxes": sigmoid(_head(params["bbox_predictor"], det)),
    }
