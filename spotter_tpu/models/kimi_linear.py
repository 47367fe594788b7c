"""Flax Kimi Linear decoder layers as a detector body (`kimi_linear_det`).

Kimi-Linear-48B-A3B's decoder layers, as published and causal as published,
in YOLOS's form (the seams are `qwen3_next.py`'s): the patch tokens of an
image in raster order, the learned detection tokens appended (so they see the
whole image), the decoder layers, the final RMSNorm, and YOLOS's two MLP heads
on the detection tokens. `h = x + mixer(input_layernorm(x))`, `y = h +
ffn(post_attention_layernorm(h))`; RMSNorm's weight is plain. Names follow the
public `modeling_kimi.py`.

- Kimi Delta Attention (`kda`; layers `kda_layers`): `q_proj`, `k_proj`,
  `v_proj`, each through its own depthwise causal conv of 4 taps and a SiLU
  (`short_conv`); `g = -exp(A_log) softplus(f_b(f_a x) + dt_bias)`, float32, a
  log decay a head, token and key channel, and `beta = sigmoid(b_proj x)`
  (`kda_gate`); q and k L2-normalised, q scaled by dk^-0.5; the recurrence
  (`ops/kda.py`, `kda_rule`, whose chunks compute g from `f_b`'s output: it
  never lies in memory); per head `RMSNorm(o) * sigmoid(g_b(g_a x))`;
  `o_proj`. No bias anywhere.
- Latent attention (`latent_attention`; layers `full_attn_layers`): `q_proj`
  gives each head 128 + 64 channels (no query latent); `kv_a_proj_with_mqa`
  a latent of 512 and 64 key channels that every head shares; `kv_b_proj`
  of the latent's RMSNorm gives each head 128 key and 128 value channels
  (`latent_kv`); **no rotary term** (`mla_use_nope`); causal softmax over keys
  of 192, scale 192^-0.5, values of 128 (`causal_attention`); `o_proj`. Keys
  and values are expanded a head: one forward pass is a prefill, nothing is
  cached.
- Feed-forward: the first `first_k_dense_replace` layers a SwiGLU of
  `intermediate_size` (`dense_mlp`); the others (`moe`) `s = sigmoid(x W_r)`
  over all `num_routed_experts`, the `num_experts_per_token` best by `s +
  e_score_correction_bias`, weighed by `s` over (their sum + 1e-20) times
  `routed_scaling_factor`; the terms of the `num_experts` held here from
  `expert_offset` on (`ops/moe.py`), plus the shared expert, ungated.

Departures, each in the configuration's `assumed` too: the router's product
runs in float32 at the highest precision; no token embedding, no output head.
The matrices are held in the serving policy's type (`zoo.py`).

Beside the detections the module returns the three `moe_*` counters of the
other routed families (`models/lfm2_moe.py`) and `kda_gate_spread` (B, KDA
layers, heads): the mean over an image's tokens of `max_d(-g) - min_d(-g)`,
how far apart a head's channels decay. 0 would be a scalar gate in disguise.
"""

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from spotter_tpu.models.configs import JoyAIFlashDetConfig, KimiLinearDetConfig
from spotter_tpu.models.layers import (
    FLASH_ATTN_MIN_SEQ,
    MLPHead,
    PatchEmbed,
    causal_latent_attention,
    flash_attention_enabled,
)
from spotter_tpu.models.lfm2_moe import RMSNorm, _dense  # a plain-weight norm, a bias-free QuantDense
from spotter_tpu.models.qwen3_next import apply_rotary, rms_norm, rotary_tables
from spotter_tpu.ops import moe as moe_ops
from spotter_tpu.ops.kda import RawGate, chunked_kda

NORM_TOPK_EPS = 1e-20  # modeling_kimi.py's KimiMoEGate: weights / (sum + 1e-20)
ATTENTION_IMAGES = 16  # images the latent-attention layer works at once


class KimiDeltaAttention(nn.Module):
    """Returns (the mixer's output, the gate's spread a head (B, H))."""

    config: KimiLinearDetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, _ = x.shape
        heads, dk, taps = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_conv_kernel
        width, rank = heads * dk, cfg.gate_low_rank_dim

        def short_conv(name):
            y = _dense(width, self.dtype, f"{name}_proj")(x)
            conv = self.param(f"{name}_conv", nn.initializers.lecun_normal(), (taps, width),
                              jnp.float32)
            # depthwise causal conv: y_t = sum_j w_j x_(t - taps + 1 + j)
            padded = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
            mixed = sum(padded[:, j:j + t] * conv[j].astype(self.dtype) for j in range(taps))
            return nn.silu(mixed.astype(jnp.float32)).astype(self.dtype).reshape(b, t, heads, dk)

        with jax.named_scope("short_conv"):
            q, k, v = short_conv("q"), short_conv("k"), short_conv("v")
        with jax.named_scope("kda_gate"):
            a_log = self.param("A_log", nn.initializers.zeros, (heads,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (width,), jnp.float32)
            raw = _dense(width, self.dtype, "f_b_proj")(_dense(rank, self.dtype, "f_a_proj")(x))
            raw = raw.reshape(b, t, heads, dk)
            beta = nn.sigmoid(_dense(heads, self.dtype, "b_proj")(x).astype(jnp.float32))
            # g itself, float32 a head, token and channel, is left to the rule's
            # chunks (`RawGate`); the counter needs its widest and narrowest
            # channel alone, and a softplus keeps their order
            reach = raw.astype(jnp.float32) + dt_bias.reshape(heads, dk)
            spread = jnp.exp(a_log) * (nn.softplus(reach.max(-1)) - nn.softplus(reach.min(-1)))
            spread = spread.mean(axis=1)
            # read the gate for the counter now, not when the program ends: XLA otherwise
            # keeps every layer's `raw` (1 GB at the bucket of 32) alive until then
            raw, spread = jax.lax.optimization_barrier((raw, spread))

        # the rule's chunks L2-normalise q and k a head (eps 1e-6) and scale q by dk^-0.5
        out = chunked_kda(q, k, v, RawGate(raw, a_log, dt_bias), beta, normalise=True)
        norm_weight = self.param("o_norm", nn.initializers.ones, (dk,), jnp.float32)
        gate = _dense(width, self.dtype, "g_b_proj")(_dense(rank, self.dtype, "g_a_proj")(x))
        out = rms_norm(out, norm_weight, cfg.rms_norm_eps, zero_centred=False)
        out = out * nn.sigmoid(gate.reshape(b, t, heads, dk).astype(jnp.float32)).astype(self.dtype)
        return _dense(cfg.hidden_size, self.dtype, "o_proj")(out.reshape(b, t, width)), spread


class LatentAttention(nn.Module):
    """Multi-head latent attention as a prefill runs it, over a batch.

    Keys and values come out of a latent: `kv_a_proj_with_mqa` gives
    `kv_lora_rank` channels and `qk_rope_head_dim` key channels that every
    head shares; `kv_b_proj` of the latent's RMSNorm gives each head
    `qk_nope_head_dim` key and `v_head_dim` value channels (`latent_kv`).
    Queries come from `q_proj`, or, given `q_lora_rank`, out of a latent of
    their own, `q_b_proj(q_a_layernorm(q_a_proj x))` (`latent_q`). Given
    `rope_theta`, the decoupled channels (each query head's last
    `qk_rope_head_dim` and the shared key channels) turn by the token's index
    in the half-split convention of `qwen3_next.apply_rotary` (`latent_rope`);
    without it nothing turns (Kimi's `mla_use_nope`). Causal softmax over keys
    of nope + rope channels, scaled by their count^-0.5,
    then `o_proj`. Keys and values are expanded a head: nothing is cached.

    Keys of 192 fill lanes of 256, and the kernel wants them padded and
    head-major: a batch's q, k and their copies are the program's peak (7.7 GB
    at 32 images). Over `ATTENTION_IMAGES` the layer runs in parts, one after
    the other, each part's copies freed."""

    config: KimiLinearDetConfig | JoyAIFlashDetConfig
    dtype: jnp.dtype = jnp.float32
    q_lora_rank: int | None = None
    rope_theta: float | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if self.q_lora_rank is None:
            q_proj = _dense(heads * (nope + pe), self.dtype, "q_proj")
        else:
            q_a = _dense(self.q_lora_rank, self.dtype, "q_a_proj")
            q_norm = RMSNorm(cfg.rms_norm_eps, name="q_a_layernorm")
            q_b = _dense(heads * (nope + pe), self.dtype, "q_b_proj")

            def q_proj(x):
                with jax.named_scope("latent_q"):
                    return q_b(q_norm(q_a(x)))

        kv_a = _dense(rank + pe, self.dtype, "kv_a_proj_with_mqa")
        kv_norm = RMSNorm(cfg.rms_norm_eps, name="kv_a_layernorm")
        kv_b = _dense(heads * (nope + dv), self.dtype, "kv_b_proj")
        o_proj = _dense(cfg.hidden_size, self.dtype, "o_proj")

        def attend(x):
            b, t, _ = x.shape
            q = q_proj(x).reshape(b, t, heads, nope + pe)
            with jax.named_scope("latent_kv"):
                latent = kv_a(x)
                kv = kv_b(kv_norm(latent[..., :rank])).reshape(b, t, heads, nope + dv)
                k_pe = latent[:, :, None, rank:]
            if self.rope_theta is not None:
                with jax.named_scope("latent_rope"):
                    cos, sin = rotary_tables(t, pe, self.rope_theta)
                    q = jnp.concatenate([q[..., :nope], apply_rotary(q[..., nope:], cos, sin)], -1)
                    k_pe = apply_rotary(k_pe, cos, sin)
            with jax.named_scope("latent_kv"):
                k_pe = jnp.broadcast_to(k_pe, (b, t, heads, pe))
                k, v = jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]
            q = q * (nope + pe)**-0.5
            if flash_attention_enabled() and t >= FLASH_ATTN_MIN_SEQ:
                out = causal_latent_attention(q, k, v)
            else:
                logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
                logits = jnp.where(np.tril(np.ones((t, t), bool)), logits, -jnp.inf)
                weights = nn.softmax(logits, axis=-1).astype(self.dtype)
                out = jnp.einsum("bhqs,bshd->bqhd", weights, v)
            return o_proj(out.reshape(b, t, heads * dv))

        parts = [x[i:i + ATTENTION_IMAGES] for i in range(0, x.shape[0], ATTENTION_IMAGES)]
        done = [attend(parts[0])]
        for part in parts[1:]:
            part, done[-1] = jax.lax.optimization_barrier((part, done[-1]))
            done.append(attend(part))
        return done[0] if len(done) == 1 else jnp.concatenate(done, axis=0)


class DenseMlp(nn.Module):
    config: KimiLinearDetConfig | JoyAIFlashDetConfig
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        hidden = (nn.silu(_dense(self.width, self.dtype, "gate_proj")(x))
                  * _dense(self.width, self.dtype, "up_proj")(x))
        return _dense(self.config.hidden_size, self.dtype, "down_proj")(hidden)


class SparseMoe(nn.Module):
    """Returns (the layer's output, the tokens each held expert took of each
    image (B, held), the selections of each image the bias moved (B,))."""

    config: KimiLinearDetConfig | JoyAIFlashDetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, t, d = x.shape
        held, inter = cfg.num_experts, cfg.moe_intermediate_size
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (d, cfg.num_routed_experts), jnp.float32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (cfg.num_routed_experts,), jnp.float32)
        gate_up = self.param("experts_gate_up", init, (held, d, 2 * inter), jnp.float32)
        down = self.param("experts_down", init, (held, inter, d), jnp.float32)

        flat = x.reshape(b * t, d)
        with jax.named_scope("router"):
            scores = moe_ops.router_scores(flat, router, "sigmoid")
            weights, experts = moe_ops.select(
                scores, cfg.num_experts_per_token, cfg.moe_renormalize, bias=bias,
                eps=NORM_TOPK_EPS, scale=cfg.routed_scaling_factor)
            counts = moe_ops.held_tokens(experts.reshape(b, -1), cfg.expert_offset, held)
            moved = moe_ops.moved_by_bias(scores, experts).reshape(b, t).sum(-1)
        with jax.named_scope("experts"):
            routed = moe_ops.routed_experts(
                flat, weights, experts, gate_up.astype(self.dtype), down.astype(self.dtype),
                offset=cfg.expert_offset)
        with jax.named_scope("shared_expert"):
            shared = DenseMlp(cfg, inter, dtype=self.dtype, name="shared_experts")(flat)
        out = routed + shared.astype(jnp.float32)
        return out.astype(self.dtype).reshape(b, t, d), counts, moved


class DecoderLayer(nn.Module):
    """Returns (x, the gate's spread (B, H) of a KDA layer or None, (counts,
    moved) of a routed layer or None)."""

    config: KimiLinearDetConfig
    kind: str
    dense: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        normed = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x)
        spread = None
        if self.kind == "kda":
            with jax.named_scope("kda"):
                mixed, spread = KimiDeltaAttention(cfg, dtype=self.dtype, name="self_attn")(normed)
        else:
            with jax.named_scope("latent_attention"):
                mixed = LatentAttention(cfg, dtype=self.dtype, name="self_attn")(normed)
        x = x + mixed
        normed = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if self.dense:
            with jax.named_scope("dense_mlp"):
                out = DenseMlp(cfg, cfg.intermediate_size, dtype=self.dtype, name="mlp")(normed)
            return x + out, spread, None
        with jax.named_scope("moe"):
            out, counts, moved = SparseMoe(cfg, dtype=self.dtype, name="block_sparse_moe")(normed)
        return x + out, spread, (counts, moved)


class KimiLinearDetector(nn.Module):
    """{"logits": (B, Q, C + 1), "pred_boxes": (B, Q, 4), "moe_expert_tokens":
    (B, routed layers, held), "moe_assignments", "moe_bias_moved": (B, routed
    layers), "kda_gate_spread": (B, KDA layers, heads)}."""

    config: KimiLinearDetConfig
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
        spreads, routed = [], []
        with jax.named_scope("decoder"):
            for i in range(cfg.num_hidden_layers):
                x, spread, counted = DecoderLayer(
                    cfg, cfg.layer_kind(i), dense=i < cfg.first_k_dense_replace,
                    dtype=self.dtype, name=f"layer{i}")(x)
                if spread is not None:
                    spreads.append(spread)
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
            "kda_gate_spread": jnp.stack(spreads, axis=1),
        }
