"""Flax ResNet backbones: "d" (RT-DETR presnet) and "v1" (classic / DETR).

style "d" matches HF's RTDetrResNetBackbone (modeling_rt_detr_resnet.py): deep
3-conv stem, max-pool, and — the "D" trick — 2x2 ceil-mode average pooling in
front of 1x1 projection shortcuts when downsampling. style "v1" matches HF's
ResNetBackbone / timm resnet (modeling_resnet.py): single 7x7 stride-2 stem and
strided 1x1 projection shortcuts — the backbone of facebook/detr-resnet-*.
NHWC layout, frozen BN.
"""

import os
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from spotter_tpu.models.configs import ResNetConfig
from spotter_tpu.models.layers import (
    ConvKernel,
    ConvNorm,
    FrozenBatchNorm,
    get_activation,
)

# Space-to-depth first stem conv (process-start knob, default off until the
# measured win is recorded in the pre-round notes, git history): the deep stem's 3x3 stride-2
# conv on (H, W, 3) runs at a few percent of MXU peak on v5e (3 input
# channels). With SPOTTER_TPU_S2D_STEM=1 the same conv executes as
# space-to-depth(2) + a 2x2 stride-1 conv over 12 channels — an EXACT
# weight rearrangement of the checkpoint's (3, 3, 3, C) kernel done at
# trace time, so the param tree, converter, and numerics (up to float
# reassociation) are unchanged. Requires even H and W (every serving
# bucket; odd inputs fall back to the plain conv).
S2D_STEM = os.environ.get("SPOTTER_TPU_S2D_STEM", "0") != "0"


class DeepStemS2DConv(nn.Module):
    """stem0 (ConvNorm 3x3 s2 pad 1) as space-to-depth + 2x2 s1 conv.

    Derivation: out(i,j) = sum_{d in {0,1,2}^2} x[2i+di-1, 2j+dj-1] w[di,dj].
    Packing 2x2 input blocks as channels (a = row-in-block, b = col), the
    receptive rows {2i-1, 2i, 2i+1} live in blocks {i-1, i}: kernel index
    ki = (di+1)//2, in-block row a = (di+1)%2 (slot (ki=0, a=0) = row 2i-2
    is never read -> zero weight), with one zero block padded in front —
    identical zeros to the plain conv's pad-by-1.
    """

    features: int
    activation: Optional[str] = None
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, w, c = x.shape
        kern = ConvKernel((3, 3, c, self.features), name="conv")()
        w2 = jnp.zeros((2, 2, 4 * c, self.features), kern.dtype)
        for di in range(3):
            ki, a = (di + 1) // 2, (di + 1) % 2
            for dj in range(3):
                kj, bb = (dj + 1) // 2, (dj + 1) % 2
                lo = a * 2 * c + bb * c
                w2 = w2.at[ki, kj, lo : lo + c].set(kern[di, dj])
        blocks = x.reshape(b, h // 2, 2, w // 2, 2, c)
        blocks = blocks.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
        y = jax.lax.conv_general_dilated(
            blocks.astype(self.dtype),
            w2.astype(self.dtype),
            window_strides=(1, 1),
            padding=((1, 0), (1, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = FrozenBatchNorm(self.features, eps=self.eps, dtype=self.dtype, name="bn")(y)
        return get_activation(self.activation)(y)


def avg_pool_2x2_ceil(x: jnp.ndarray) -> jnp.ndarray:
    """torch AvgPool2d(2, 2, ceil_mode=True): clipped edge windows divide by
    their actual element count."""
    b, h, w, c = x.shape
    ph, pw = h % 2, w % 2
    summed = nn.avg_pool(
        x, (2, 2), strides=(2, 2), padding=((0, ph), (0, pw)), count_include_pad=False
    )
    return summed


class BasicBlock(nn.Module):
    """Two 3x3 convs + residual (resnet-18/34)."""

    out_channels: int
    stride: int = 1
    shortcut: str = "none"  # "none" | "proj" | "avgpool_proj"
    hidden_act: str = "relu"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        residual = x
        y = ConvNorm(
            self.out_channels, 3, self.stride, activation=self.hidden_act,
            dtype=self.dtype, name="conv0",
        )(x)
        y = ConvNorm(self.out_channels, 3, 1, activation=None, dtype=self.dtype, name="conv1")(y)
        if self.shortcut == "proj":
            residual = ConvNorm(
                self.out_channels, 1, self.stride, activation=None,
                dtype=self.dtype, name="shortcut",
            )(x)
        elif self.shortcut == "avgpool_proj":
            residual = avg_pool_2x2_ceil(x)
            residual = ConvNorm(
                self.out_channels, 1, 1, activation=None, dtype=self.dtype, name="shortcut"
            )(residual)
        return get_activation(self.hidden_act)(y + residual)


class BottleneckBlock(nn.Module):
    """1x1 reduce -> 3x3 -> 1x1 expand + residual (resnet-50/101)."""

    out_channels: int
    stride: int = 1
    shortcut: str = "none"
    downsample_in_bottleneck: bool = False
    hidden_act: str = "relu"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        reduced = self.out_channels // 4
        s1 = self.stride if self.downsample_in_bottleneck else 1
        s2 = self.stride if not self.downsample_in_bottleneck else 1
        y = ConvNorm(reduced, 1, s1, activation=self.hidden_act, dtype=self.dtype, name="conv0")(x)
        y = ConvNorm(reduced, 3, s2, activation=self.hidden_act, dtype=self.dtype, name="conv1")(y)
        y = ConvNorm(self.out_channels, 1, 1, activation=None, dtype=self.dtype, name="conv2")(y)
        residual = x
        if self.shortcut == "proj":
            residual = ConvNorm(
                self.out_channels, 1, self.stride, activation=None,
                dtype=self.dtype, name="shortcut",
            )(x)
        elif self.shortcut == "avgpool_proj":
            residual = avg_pool_2x2_ceil(x)
            residual = ConvNorm(
                self.out_channels, 1, 1, activation=None, dtype=self.dtype, name="shortcut"
            )(residual)
        elif self.shortcut == "avgpool":
            residual = avg_pool_2x2_ceil(x)
        return get_activation(self.hidden_act)(y + residual)


def _basic_shortcut(in_ch: int, out_ch: int, stride: int, apply: bool) -> str:
    # modeling_rt_detr_resnet.py RTDetrResNetBasicLayer.__init__ semantics
    if in_ch != out_ch:
        return "avgpool_proj" if apply else "none"
    return "proj" if apply else "none"


def _v1_shortcut(in_ch: int, out_ch: int, stride: int) -> str:
    # modeling_resnet.py ResNet{Basic,BottleNeck}Layer: strided 1x1 projection
    # whenever shape or stride changes, no avg-pool trick
    return "proj" if (in_ch != out_ch or stride != 1) else "none"


def _bottleneck_shortcut(in_ch: int, out_ch: int, stride: int) -> str:
    # RTDetrResNetBottleNeckLayer.__init__: stride==2 always takes the avg-pool
    # path (projection only when shapes change); stride==1 projects iff needed.
    should_project = in_ch != out_ch or stride != 1
    if stride == 2:
        return "avgpool_proj" if should_project else "avgpool"
    return "proj" if should_project else "none"


class ResNetBackbone(nn.Module):
    """Returns feature maps at `config.out_indices` of
    (stem_out, stage1, stage2, stage3, stage4)."""

    config: ResNetConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixel_values: jnp.ndarray) -> list[jnp.ndarray]:
        cfg = self.config
        act = cfg.hidden_act
        x = pixel_values.astype(self.dtype)
        with jax.named_scope("stem"):  # op metadata only
            if cfg.style == "v1":
                # Classic stem: single 7x7 s2 conv, then 3x3 s2 max pool.
                x = ConvNorm(cfg.embedding_size, 7, 2, activation=act, dtype=self.dtype, name="stem0")(x)
            elif S2D_STEM and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
                # Deep stem, first conv via space-to-depth (exact rearrangement).
                x = DeepStemS2DConv(
                    cfg.embedding_size // 2, activation=act, dtype=self.dtype, name="stem0"
                )(x)
                x = ConvNorm(cfg.embedding_size // 2, 3, 1, activation=act, dtype=self.dtype, name="stem1")(x)
                x = ConvNorm(cfg.embedding_size, 3, 1, activation=act, dtype=self.dtype, name="stem2")(x)
            else:
                # Deep stem: 3x3 s2 -> 3x3 -> 3x3.
                x = ConvNorm(cfg.embedding_size // 2, 3, 2, activation=act, dtype=self.dtype, name="stem0")(x)
                x = ConvNorm(cfg.embedding_size // 2, 3, 1, activation=act, dtype=self.dtype, name="stem1")(x)
                x = ConvNorm(cfg.embedding_size, 3, 1, activation=act, dtype=self.dtype, name="stem2")(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))

        hidden_states = [x]
        in_ch = cfg.embedding_size
        for stage_idx, (out_ch, depth) in enumerate(zip(cfg.hidden_sizes, cfg.depths)):
            stride = 2 if (stage_idx > 0 or cfg.downsample_in_first_stage) else 1
            for block_idx in range(depth):
                block_stride = stride if block_idx == 0 else 1
                block_in = in_ch if block_idx == 0 else out_ch
                name = f"stage{stage_idx}_block{block_idx}"
                if cfg.layer_type == "bottleneck":
                    if block_idx != 0:
                        shortcut = "none"
                    elif cfg.style == "v1":
                        shortcut = _v1_shortcut(block_in, out_ch, block_stride)
                    else:
                        shortcut = _bottleneck_shortcut(block_in, out_ch, block_stride)
                    x = BottleneckBlock(
                        out_ch, block_stride, shortcut, cfg.downsample_in_bottleneck,
                        act, self.dtype, name=name,
                    )(x)
                else:
                    if cfg.style == "v1":
                        shortcut = (
                            _v1_shortcut(block_in, out_ch, block_stride)
                            if block_idx == 0
                            else "none"
                        )
                    else:
                        shortcut = _basic_shortcut(block_in, out_ch, block_stride, block_idx == 0)
                    x = BasicBlock(out_ch, block_stride, shortcut, act, self.dtype, name=name)(x)
            hidden_states.append(x)
            in_ch = out_ch

        return [hidden_states[i] for i in cfg.out_indices]
