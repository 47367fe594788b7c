"""Flax YOLOS (hustvl/yolos-*): plain ViT with appended detection tokens.

Semantics match HF's YolosForObjectDetection (modeling_yolos.py): patch
embedding conv, [CLS] + patch + detection tokens with a single learned
position table, pre-norm ViT layers, optional per-layer "mid" position
embeddings added after every non-final layer, final layernorm, and two
3-layer MLP heads (class incl. "no object", sigmoid boxes) applied to the
detection-token outputs only.

TPU-first notes: the serving preprocess warp-resizes to the checkpoint's
native `image_size`, so position tables are used exactly as trained and every
shape is static (SURVEY.md §5.7). For other static input sizes the tables are
interpolated bicubically at trace time (jax.image uses the Catmull-Rom kernel
a=-0.5 vs torch bicubic a=-0.75 — trained-size inputs avoid the difference
entirely). The reference serves this family through the same
`AutoModelForObjectDetection` boundary (serve.py:199-205).
"""

import jax
import jax.numpy as jnp
from flax import linen as nn

from spotter_tpu.models.configs import YolosConfig
from spotter_tpu.models.layers import (
    FLASH_ATTN_MIN_SEQ,
    MLPHead,
    PatchEmbed,
    QuantDense,
    flash_self_attention,
    flash_attention_enabled,
    get_activation,
)


def _interpolate_patch_pos(
    table: jnp.ndarray, src_hw: tuple[int, int], dst_hw: tuple[int, int]
) -> jnp.ndarray:
    """(1, src_h*src_w, D) patch position table -> (1, dst_h*dst_w, D)."""
    if src_hw == dst_hw:
        return table
    d = table.shape[-1]
    grid = table.reshape(1, *src_hw, d)
    grid = jax.image.resize(grid, (1, *dst_hw, d), method="bicubic")
    return grid.reshape(1, dst_hw[0] * dst_hw[1], d)


class YolosAttention(nn.Module):
    """ViT-style self-attention (separate query/key/value + output dense)."""

    config: YolosConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        heads = cfg.num_attention_heads
        head_dim = cfg.hidden_size // heads

        def proj(name):
            return QuantDense(
                cfg.hidden_size, use_bias=cfg.qkv_bias, dtype=self.dtype, name=name
            )(x).reshape(*x.shape[:-1], heads, head_dim)

        q = proj("query")
        k = proj("key")
        v = proj("value")
        if flash_attention_enabled() and q.shape[1] >= FLASH_ATTN_MIN_SEQ:
            # ViT-detector sequences (800x1344 -> 4300 tokens) make the
            # naive path HBM-bound on the (B, H, S, S) scores; the flash
            # kernel never materializes them (layers.py cutover notes)
            out = flash_self_attention(q * (head_dim**-0.5), k, v)
        else:
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (head_dim**-0.5)
            weights = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
                self.dtype
            )
            out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
        out = out.reshape(*out.shape[:-2], cfg.hidden_size)
        return QuantDense(cfg.hidden_size, dtype=self.dtype, name="out")(out)


class YolosLayer(nn.Module):
    """Pre-norm ViT block (YolosLayer)."""

    config: YolosConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        normed = nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="layernorm_before"
        )(x)
        x = x + YolosAttention(cfg, dtype=self.dtype, name="attention")(normed)
        normed = nn.LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="layernorm_after"
        )(x)
        with jax.named_scope("mlp"):
            ffn = QuantDense(cfg.intermediate_size, dtype=self.dtype, name="fc1")(normed)
            ffn = get_activation(cfg.hidden_act)(ffn)
            return x + QuantDense(cfg.hidden_size, dtype=self.dtype, name="fc2")(ffn)


class YolosDetector(nn.Module):
    """YOLOS detector: returns {"logits": (B, T, C+1), "pred_boxes": (B, T, 4)}."""

    config: YolosConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixel_values: jnp.ndarray) -> dict[str, jnp.ndarray]:
        cfg = self.config
        b, h, w, _ = pixel_values.shape
        p = cfg.patch_size
        if h % p or w % p:
            raise ValueError(f"input {h}x{w} not divisible by patch size {p}")
        gh, gw = h // p, w // p
        src_hw = cfg.grid_hw
        n_src = src_hw[0] * src_hw[1]
        t = cfg.num_detection_tokens

        with jax.named_scope("embed"):
            # row-dot patchify (layers.PatchEmbed): exact conv rewrite, ~2x on
            # v5e for 3-channel patchify (pre-round note, round 4, git history)
            x = PatchEmbed(
                cfg.hidden_size, p, dtype=self.dtype, name="patch_projection"
            )(pixel_values)

            cls_token = self.param(
                "cls_token", nn.initializers.zeros, (1, 1, cfg.hidden_size), jnp.float32
            )
            det_tokens = self.param(
                "detection_tokens", nn.initializers.zeros, (1, t, cfg.hidden_size), jnp.float32
            )
            pos_table = self.param(
                "position_embeddings",
                nn.initializers.zeros,
                (1, n_src + t + 1, cfg.hidden_size),
                jnp.float32,
            )
            x = jnp.concatenate(
                [
                    jnp.broadcast_to(cls_token.astype(self.dtype), (b, 1, cfg.hidden_size)),
                    x,
                    jnp.broadcast_to(det_tokens.astype(self.dtype), (b, t, cfg.hidden_size)),
                ],
                axis=1,
            )

            def split_pos(table):
                return (
                    table[:, :1],
                    _interpolate_patch_pos(table[:, 1 : 1 + n_src], src_hw, (gh, gw)),
                    table[:, 1 + n_src :],
                )

            pos = jnp.concatenate(split_pos(pos_table), axis=1)
            x = x + pos.astype(self.dtype)

        with jax.named_scope("encoder"):
            if cfg.use_mid_position_embeddings:
                mid_table = self.param(
                    "mid_position_embeddings",
                    nn.initializers.zeros,
                    (cfg.num_hidden_layers - 1, 1, n_src + t + 1, cfg.hidden_size),
                    jnp.float32,
                )
            for i in range(cfg.num_hidden_layers):
                x = YolosLayer(cfg, dtype=self.dtype, name=f"layer{i}")(x)
                if cfg.use_mid_position_embeddings and i < cfg.num_hidden_layers - 1:
                    mid = jnp.concatenate(split_pos(mid_table[i]), axis=1)
                    x = x + mid.astype(self.dtype)

            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="layernorm")(x)
        det_out = x[:, -t:]

        with jax.named_scope("heads"):
            # fp32 head outputs under bf16 compute (box precision at 640 px scale)
            logits = MLPHead(
                cfg.hidden_size, cfg.num_labels + 1, 3, dtype=self.dtype,
                name="class_labels_classifier",
            )(det_out)
            boxes = nn.sigmoid(
                MLPHead(cfg.hidden_size, 4, 3, dtype=self.dtype, name="bbox_predictor")(
                    det_out
                ).astype(jnp.float32)
            )
        return {"logits": logits.astype(jnp.float32), "pred_boxes": boxes}
