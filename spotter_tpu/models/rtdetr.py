"""Flax RT-DETR / RT-DETRv2 detector — TPU-first implementation.

Replaces the reference's torch `AutoModelForObjectDetection` forward
(apps/spotter/src/spotter/serve.py:99-100) for MODEL_NAME values in the
PekingU/rtdetr* family. Architecture semantics follow the published RT-DETRv2
model (hybrid encoder with AIFI + CSP-RepVGG FPN/PAN; NMS-free deformable
decoder with iterative box refinement), implemented in NHWC with static
shapes so jit compiles once per input bucket:

- anchors, sin-cos position tables, and per-level token spans are computed in
  numpy at trace time from static spatial shapes — XLA constant-folds them;
- multiscale deformable attention runs through the shared sampling core
  (spotter_tpu/ops/msda.py): on TPU the gather-free level-split one-hot
  Pallas kernel (one-hot weight tiles contracted on the MXU), XLA
  row-gathers elsewhere; this is the TPU-native replacement for the torch
  lineage's custom CUDA sampler;
- the whole forward is one jit region: backbone -> encoder -> decoder ->
  (logits, boxes); no data-dependent control flow.
"""

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from spotter_tpu.models.configs import RTDetrConfig
from spotter_tpu.models.layers import (
    ConvNorm,
    ConvNormParams,
    DenseParams,
    MLPHead,
    MultiHeadAttention,
    get_activation,
    inverse_sigmoid,
    sincos_2d_position_embedding,
)
from spotter_tpu.models.resnet import ResNetBackbone
from spotter_tpu.ops.msda import (
    deformable_sampling,
    deformable_sampling_fused,
    locality_presort,
    msda_prep_fused,
    presort_wanted,
)
from spotter_tpu.ops.topk import top_k as fast_top_k
from spotter_tpu.utils.precision import compute_dtype
from spotter_tpu.utils.quant import int8_conv, int8_wanted


def generate_anchors(
    spatial_shapes: tuple[tuple[int, int], ...],
    grid_size: float = 0.05,
    eps: float = 1e-2,
) -> tuple[np.ndarray, np.ndarray]:
    """Static anchor logits per multi-level grid cell.

    Returns (anchors_logit (1, S, 4), valid_mask (1, S, 1)) in numpy; invalid
    anchors get float32 max so sigmoid saturates at 1 (matching the torch
    semantics of masking with finfo.max before sigmoid).
    """
    all_anchors = []
    for level, (h, w) in enumerate(spatial_shapes):
        gy, gx = np.meshgrid(
            np.arange(h, dtype=np.float32), np.arange(w, dtype=np.float32), indexing="ij"
        )
        gxy = np.stack([gx, gy], axis=-1) + 0.5
        gxy[..., 0] /= w
        gxy[..., 1] /= h
        wh = np.ones_like(gxy) * grid_size * (2.0**level)
        all_anchors.append(np.concatenate([gxy, wh], -1).reshape(h * w, 4))
    anchors = np.concatenate(all_anchors, 0)[None]
    valid = ((anchors > eps) & (anchors < 1 - eps)).all(-1, keepdims=True)
    anchors_logit = np.log(anchors / (1 - anchors))
    anchors_logit = np.where(valid, anchors_logit, np.finfo(np.float32).max)
    return anchors_logit.astype(np.float32), valid.astype(np.float32)


class EncoderLayer(nn.Module):
    """AIFI transformer encoder layer (post-norm)."""

    embed_dim: int
    num_heads: int
    ffn_dim: int
    activation: str = "gelu"
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, pos: Optional[jnp.ndarray]) -> jnp.ndarray:
        with jax.named_scope("attention"):
            attn_out = MultiHeadAttention(
                self.embed_dim, self.num_heads, dtype=self.dtype, name="self_attn"
            )(x, position_embeddings=pos)
            x = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype, name="self_attn_layer_norm")(
                x + attn_out
            )
        with jax.named_scope("mlp"):
            y = nn.Dense(self.ffn_dim, dtype=self.dtype, name="fc1")(x)
            y = get_activation(self.activation)(y)
            y = nn.Dense(self.embed_dim, dtype=self.dtype, name="fc2")(y)
            return nn.LayerNorm(epsilon=self.eps, dtype=self.dtype, name="final_layer_norm")(x + y)


# RepVGG re-parameterization at trace time (the classic inference-time
# identity the torch reference never applies): conv3x3+BN + conv1x1+BN
# summed == ONE 3x3 conv with kernel w3*mul3 + center-pad(w1*mul1) and bias
# add3+add1 — exact up to float reassociation. Saves the 1x1 conv's HBM
# pass + the elementwise add per RepVgg block (30 blocks in the R101
# encoder; measured 235.5 -> 239.7 img/s on v5e, bf16 batch 8). Default
# follows the precision policy like the MSDA sampling precision: fused only
# when the encoder half (where RepVgg blocks live) already runs bf16 —
# i.e. the "bfloat16" policy; "mixed" deliberately pins the transformer
# half to exact fp32, so it stays unfused there like under "float32".
# Override with SPOTTER_TPU_REP_FUSE=0/1 (read at import, like the other
# process knobs).
def _rep_fuse_default() -> bool:
    flag = os.environ.get("SPOTTER_TPU_REP_FUSE", "").strip()
    if flag:
        return flag != "0"
    return compute_dtype() == jnp.bfloat16


REP_FUSE = _rep_fuse_default()


class RepVggBlock(nn.Module):
    features: int
    activation: str = "silu"
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if REP_FUSE:
            w3, b3 = ConvNormParams(
                self.features, 3, x.shape[-1], self.eps, name="conv1"
            )()
            w1, b1 = ConvNormParams(
                self.features, 1, x.shape[-1], self.eps, name="conv2"
            )()
            wf = w3.at[1:2, 1:2].add(w1)
            if int8_wanted(x.shape[-1], batch=x.shape[0]):
                # int8 MXU path on the already-fused kernel (utils/quant.py):
                # these 384-ch 3x3 convs are the encoder's measured hot spot
                # (tools/bench_int8_conv.py: 1.5-1.6x at 80^2/40^2)
                y = int8_conv(x, wf, (1, 1), ((1, 1), (1, 1)), self.dtype)
            else:
                y = jax.lax.conv_general_dilated(
                    x,
                    wf.astype(self.dtype),
                    window_strides=(1, 1),
                    padding=((1, 1), (1, 1)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
            y = y + (b3 + b1).astype(self.dtype)
            return get_activation(self.activation)(y)
        y = ConvNorm(self.features, 3, 1, padding=1, eps=self.eps, dtype=self.dtype, name="conv1")(x)
        z = ConvNorm(self.features, 1, 1, padding=0, eps=self.eps, dtype=self.dtype, name="conv2")(x)
        return get_activation(self.activation)(y + z)


class CSPRepLayer(nn.Module):
    """Cross-stage-partial fusion block with RepVGG bottlenecks."""

    out_channels: int
    hidden_channels: int
    num_blocks: int = 3
    activation: str = "silu"
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        h1 = ConvNorm(
            self.hidden_channels, 1, 1, activation=self.activation, eps=self.eps,
            dtype=self.dtype, name="conv1",
        )(x)
        for i in range(self.num_blocks):
            h1 = RepVggBlock(
                self.hidden_channels, self.activation, self.eps, self.dtype,
                name=f"bottleneck{i}",
            )(h1)
        h2 = ConvNorm(
            self.hidden_channels, 1, 1, activation=self.activation, eps=self.eps,
            dtype=self.dtype, name="conv2",
        )(x)
        y = h1 + h2
        if self.hidden_channels != self.out_channels:
            y = ConvNorm(
                self.out_channels, 1, 1, activation=self.activation, eps=self.eps,
                dtype=self.dtype, name="conv3",
            )(y)
        return y


class DeformableAttention(nn.Module):
    """Multiscale deformable cross-attention (RT-DETRv2 semantics).

    Sampling offsets are scaled by 1/n_points, the reference-box size, and
    `offset_scale` (v2); sampling itself is bilinear ("default") or
    nearest-integer ("discrete") over each level's value map.
    """

    d_model: int
    num_heads: int
    num_levels: int
    num_points: int
    offset_scale: float = 0.5
    method: str = "default"
    dtype: jnp.dtype = jnp.float32
    presorted: bool = False

    @nn.compact
    def __call__(
        self,
        hidden_states: jnp.ndarray,  # (B, Q, D)
        position_embeddings: Optional[jnp.ndarray],
        encoder_hidden_states: jnp.ndarray,  # (B, S, D)
        reference_points: jnp.ndarray,  # (B, Q, 4) normalized cxcywh
        spatial_shapes: tuple[tuple[int, int], ...],
    ) -> jnp.ndarray:
        b, q, _ = hidden_states.shape
        heads, levels, points = self.num_heads, self.num_levels, self.num_points
        head_dim = self.d_model // heads
        hs = hidden_states
        if position_embeddings is not None:
            hs = hs + position_embeddings

        value = nn.Dense(self.d_model, dtype=self.dtype, name="value_proj")(
            encoder_hidden_states
        )
        s = value.shape[1]
        value = value.reshape(b, s, heads, head_dim)

        if msda_prep_fused():
            # SPOTTER_TPU_MSDA_PREP=fused: the offset/attention projections,
            # softmax, and location arithmetic run inside the Pallas MSDA
            # kernel's prologue. DenseParams declares the SAME param paths
            # (sampling_offsets/attention_weights {kernel, bias}, identical
            # inits) as the nn.Dense calls below, so checkpoints swap freely
            # between the fused and unfused paths.
            w_off, b_off = DenseParams(
                heads * levels * points * 2, self.d_model, name="sampling_offsets"
            )()
            w_att, b_att = DenseParams(
                heads * levels * points, self.d_model, name="attention_weights"
            )()
            out = deformable_sampling_fused(
                value, hs, reference_points, w_off, b_off, w_att, b_att,
                spatial_shapes, points, offset_scale=self.offset_scale,
                method=self.method, presorted=self.presorted,
            )
            return nn.Dense(self.d_model, dtype=self.dtype, name="output_proj")(out)

        offsets = nn.Dense(
            heads * levels * points * 2, dtype=self.dtype, name="sampling_offsets"
        )(hs).reshape(b, q, heads, levels * points, 2)
        attn = nn.Dense(heads * levels * points, dtype=self.dtype, name="attention_weights")(
            hs
        ).reshape(b, q, heads, levels * points)
        attn = nn.softmax(attn.astype(jnp.float32), axis=-1).astype(self.dtype)

        # v2 offset semantics: offsets * (1/n_points) * ref_wh * offset_scale
        n_points_scale = np.repeat(
            1.0 / np.asarray([points] * levels, np.float32), points
        )[None, None, None, :, None]
        ref_xy = reference_points[:, :, None, None, :2]
        ref_wh = reference_points[:, :, None, None, 2:]
        loc = ref_xy + offsets * jnp.asarray(n_points_scale, self.dtype) * ref_wh * self.offset_scale
        # loc: (B, Q, H, L*P, 2) in [0, 1]

        # Shared sampling core (spotter_tpu/ops/msda.py): level-split one-hot
        # Pallas kernel on TPU, XLA row-gathers elsewhere (SPOTTER_TPU_MSDA).
        out = deformable_sampling(
            value, loc, attn, spatial_shapes, points, method=self.method,
            presorted=self.presorted,
        )
        return nn.Dense(self.d_model, dtype=self.dtype, name="output_proj")(out)


class DecoderLayer(nn.Module):
    config: RTDetrConfig
    dtype: jnp.dtype = jnp.float32
    presorted: bool = False

    @nn.compact
    def __call__(
        self,
        hidden_states: jnp.ndarray,
        position_embeddings: jnp.ndarray,
        encoder_hidden_states: jnp.ndarray,
        reference_points: jnp.ndarray,
        spatial_shapes: tuple[tuple[int, int], ...],
        self_attention_mask: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        eps = cfg.layer_norm_eps
        with jax.named_scope("attention"):
            attn_out = MultiHeadAttention(
                cfg.d_model, cfg.decoder_attention_heads, dtype=self.dtype, name="self_attn"
            )(hidden_states, position_embeddings=position_embeddings,
              attention_mask=self_attention_mask)
            h = nn.LayerNorm(epsilon=eps, dtype=self.dtype, name="self_attn_layer_norm")(
                hidden_states + attn_out
            )
        with jax.named_scope("cross_attention"):
            cross = DeformableAttention(
                cfg.d_model,
                cfg.decoder_attention_heads,
                cfg.num_feature_levels,
                cfg.decoder_n_points,
                offset_scale=cfg.decoder_offset_scale,
                method=cfg.decoder_method,
                dtype=self.dtype,
                presorted=self.presorted,
                name="encoder_attn",
            )(h, position_embeddings, encoder_hidden_states, reference_points, spatial_shapes)
            h = nn.LayerNorm(epsilon=eps, dtype=self.dtype, name="encoder_attn_layer_norm")(h + cross)
        with jax.named_scope("mlp"):
            y = nn.Dense(cfg.decoder_ffn_dim, dtype=self.dtype, name="fc1")(h)
            y = get_activation(cfg.decoder_activation_function)(y)
            y = nn.Dense(cfg.d_model, dtype=self.dtype, name="fc2")(y)
            return nn.LayerNorm(epsilon=eps, dtype=self.dtype, name="final_layer_norm")(h + y)


class RTDetrDetector(nn.Module):
    """Full RT-DETR(v2) detector: pixels (B, H, W, 3) -> logits + boxes.

    Returns a dict: logits (B, Q, C), pred_boxes (B, Q, 4) normalized cxcywh,
    aux_logits/aux_boxes stacked over decoder layers (for training losses),
    enc_topk_logits/enc_topk_bboxes (encoder auxiliary head).
    """

    config: RTDetrConfig
    dtype: jnp.dtype = jnp.float32
    # Optional separate backbone compute dtype ("mixed" policy): the ResNet's
    # convs are HBM-bandwidth-bound and win from bf16 (measured v5e R101
    # batch 8: 22.3 -> 17.9 ms) while the transformer+sampling half is
    # fastest fp32 — casting only at the 1/8-resolution feature boundary
    # keeps the decoder's fp32 fusions intact.
    backbone_dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(
        self,
        pixel_values: jnp.ndarray,
        decoder_input_queries: Optional[jnp.ndarray] = None,
        decoder_input_ref_logits: Optional[jnp.ndarray] = None,
        self_attention_mask: Optional[jnp.ndarray] = None,
    ) -> dict:
        cfg = self.config
        feats = ResNetBackbone(
            cfg.backbone, dtype=self.backbone_dtype or self.dtype, name="backbone"
        )(pixel_values)
        feats = [f.astype(self.dtype) for f in feats]

        with jax.named_scope("encoder"):
            proj = [
                ConvNorm(
                    cfg.encoder_hidden_dim, 1, 1, activation=None, eps=cfg.batch_norm_eps,
                    dtype=self.dtype, name=f"enc_proj{i}",
                )(f)
                for i, f in enumerate(feats)
            ]

            # --- AIFI: transformer encoder on selected (stride-32) levels ---
            for i, enc_ind in enumerate(cfg.encode_proj_layers):
                b, h, w, c = proj[enc_ind].shape
                src = proj[enc_ind].reshape(b, h * w, c)
                pos = jnp.asarray(
                    sincos_2d_position_embedding(
                        w, h, cfg.encoder_hidden_dim, cfg.positional_encoding_temperature
                    ),
                    self.dtype,
                )
                for j in range(cfg.encoder_layers):
                    src = EncoderLayer(
                        cfg.encoder_hidden_dim,
                        cfg.encoder_attention_heads,
                        cfg.encoder_ffn_dim,
                        cfg.encoder_activation_function,
                        cfg.layer_norm_eps,
                        self.dtype,
                        name=f"aifi{i}_layer{j}",
                    )(src, pos)
                proj[enc_ind] = src.reshape(b, h, w, c)

            # --- top-down FPN ---
            hidden_channels = int(cfg.encoder_hidden_dim * cfg.hidden_expansion)
            num_stages = len(cfg.encoder_in_channels) - 1
            fpn = [proj[-1]]
            for idx in range(num_stages):
                backbone_fm = proj[num_stages - idx - 1]
                top = ConvNorm(
                    cfg.encoder_hidden_dim, 1, 1, activation=cfg.activation_function,
                    eps=cfg.batch_norm_eps, dtype=self.dtype, name=f"lateral_conv{idx}",
                )(fpn[-1])
                fpn[-1] = top
                up = jnp.repeat(jnp.repeat(top, 2, axis=1), 2, axis=2)  # 2x nearest
                fused = jnp.concatenate([up, backbone_fm], axis=-1)
                fpn.append(
                    CSPRepLayer(
                        cfg.encoder_hidden_dim, hidden_channels, cfg.csp_num_blocks,
                        cfg.activation_function, cfg.batch_norm_eps, self.dtype,
                        name=f"fpn_block{idx}",
                    )(fused)
                )
            fpn = fpn[::-1]

            # --- bottom-up PAN ---
            pan = [fpn[0]]
            for idx in range(num_stages):
                down = ConvNorm(
                    cfg.encoder_hidden_dim, 3, 2, activation=cfg.activation_function,
                    eps=cfg.batch_norm_eps, dtype=self.dtype, name=f"downsample_conv{idx}",
                )(pan[-1])
                fused = jnp.concatenate([down, fpn[idx + 1]], axis=-1)
                pan.append(
                    CSPRepLayer(
                        cfg.encoder_hidden_dim, hidden_channels, cfg.csp_num_blocks,
                        cfg.activation_function, cfg.batch_norm_eps, self.dtype,
                        name=f"pan_block{idx}",
                    )(fused)
                )

            # --- decoder input projection + flatten ---
            sources = [
                ConvNorm(
                    cfg.d_model, 1, 1, activation=None, eps=cfg.batch_norm_eps,
                    dtype=self.dtype, name=f"dec_proj{i}",
                )(p)
                for i, p in enumerate(pan)
            ]
            for i in range(len(sources), cfg.num_feature_levels):
                sources.append(
                    ConvNorm(
                        cfg.d_model, 3, 2, padding=1, activation=None, eps=cfg.batch_norm_eps,
                        dtype=self.dtype, name=f"dec_proj{i}",
                    )(sources[-1])
                )

            spatial_shapes = tuple((s.shape[1], s.shape[2]) for s in sources)
            b = sources[0].shape[0]
            source_flatten = jnp.concatenate(
                [s.reshape(b, -1, cfg.d_model) for s in sources], axis=1
            )

        with jax.named_scope("decoder"):
            # --- encoder head: anchor scoring + top-k query selection ---
            anchors_np, valid_np = generate_anchors(spatial_shapes, cfg.anchor_grid_size)
            anchors = jnp.asarray(anchors_np, self.dtype)
            valid_mask = jnp.asarray(valid_np, self.dtype)

            memory = valid_mask * source_flatten
            output_memory = nn.Dense(cfg.d_model, dtype=self.dtype, name="enc_output_dense")(memory)
            output_memory = nn.LayerNorm(
                epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="enc_output_norm"
            )(output_memory)

            enc_class = nn.Dense(cfg.num_labels, dtype=self.dtype, name="enc_score_head")(
                output_memory
            )
            enc_coord_logits = (
                MLPHead(cfg.d_model, 4, 3, dtype=self.dtype, name="enc_bbox_head")(output_memory)
                + anchors
            )

            # ops/topk.py: lax.top_k by default; SPOTTER_TPU_TOPK=bisect swaps in
            # the sort-free radix path (identical result, for wider-S hardware)
            _, topk_ind = fast_top_k(enc_class.max(-1), cfg.num_queries)
            gather = lambda arr: jnp.take_along_axis(arr, topk_ind[..., None], axis=1)
            reference_logits = gather(enc_coord_logits)
            enc_topk_logits = gather(enc_class)
            enc_topk_bboxes = nn.sigmoid(reference_logits.astype(jnp.float32))

            if cfg.learn_initial_query:
                target = self.param(
                    "query_embed", nn.initializers.normal(1.0), (cfg.num_queries, cfg.d_model)
                )
                target = jnp.broadcast_to(target, (b, cfg.num_queries, cfg.d_model)).astype(self.dtype)
            else:
                target = jax.lax.stop_gradient(gather(output_memory))

            reference_logits = jax.lax.stop_gradient(reference_logits)

            # Denoising groups (training) enter here as extra queries.
            if decoder_input_queries is not None:
                target = jnp.concatenate([decoder_input_queries, target], axis=1)
                reference_logits = jnp.concatenate(
                    [decoder_input_ref_logits, reference_logits], axis=1
                )

            # --- decoder with iterative refinement ---
            # Box-refinement arithmetic stays fp32 even under bf16 compute: the
            # sigmoid/inverse-sigmoid iteration across decoder layers would
            # otherwise accumulate bf16 rounding into multi-pixel box drift
            # (the heavy matmuls in DecoderLayer/MLPHead still run self.dtype).
            ref = nn.sigmoid(reference_logits.astype(jnp.float32))
            h = target
            # Model-level locality presort (ops/msda.py presort_wanted): the six
            # decoder layers share one spatial ordering of the queries, so sort
            # ONCE here by the initial reference centers (layer sampling points
            # cluster around them; later refinement moves boxes only slightly)
            # instead of paying argsort + two q-row permutes inside every
            # sampling op. Exact: queries are permutation-equivariant through
            # full self-attention, and outputs are un-permuted below. Skipped
            # when a self-attention mask is present (denoising training) —
            # ordering would have to permute the mask too; the in-op sort
            # handles that case unchanged.
            presort = presort_wanted() and self_attention_mask is None
            if presort:
                sort_q, unsort_q = locality_presort(ref[..., :2])
                h, ref = sort_q(h), sort_q(ref)
            query_pos_head = MLPHead(
                2 * cfg.d_model, cfg.d_model, 2, dtype=self.dtype, name="query_pos_head"
            )
            aux_logits, aux_boxes = [], []
            for i in range(cfg.decoder_layers):
                pos = query_pos_head(ref.astype(self.dtype))
                h = DecoderLayer(
                    cfg, dtype=self.dtype, presorted=presort, name=f"decoder_layer{i}"
                )(
                    h, pos, source_flatten, ref.astype(self.dtype), spatial_shapes,
                    self_attention_mask,
                )
                with jax.named_scope("heads"):
                    box_delta = MLPHead(cfg.d_model, 4, 3, dtype=self.dtype, name=f"bbox_head{i}")(h)
                    new_ref = nn.sigmoid(box_delta.astype(jnp.float32) + inverse_sigmoid(ref))
                    logits_i = nn.Dense(cfg.num_labels, dtype=self.dtype, name=f"class_head{i}")(h)
                    aux_logits.append(logits_i.astype(jnp.float32))
                    aux_boxes.append(new_ref)
                ref = jax.lax.stop_gradient(new_ref)

            if presort:
                aux_logits = [unsort_q(a) for a in aux_logits]
                aux_boxes = [unsort_q(a) for a in aux_boxes]

        return {
            "logits": aux_logits[-1],
            "pred_boxes": aux_boxes[-1],
            "aux_logits": jnp.stack(aux_logits, axis=1),
            "aux_boxes": jnp.stack(aux_boxes, axis=1),
            "enc_topk_logits": enc_topk_logits.astype(jnp.float32),
            "enc_topk_bboxes": enc_topk_bboxes,
        }
