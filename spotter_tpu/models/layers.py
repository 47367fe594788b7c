"""Shared Flax building blocks for the detection model families.

Design notes (TPU-first):
- NHWC layout everywhere; conv kernels HWIO (XLA's native TPU layout).
- BatchNorms are "frozen": affine + running stats folded into 4 per-channel
  params. This matches detection-serving practice (the torch lineage freezes
  backbone BN: RTDetrV2FrozenBatchNorm2d / DetrFrozenBatchNorm2d) and keeps the
  param tree a single pure-functional collection.
- `dtype` on each module is the compute dtype (bf16 on TPU for the MXU);
  params stay fp32.
- Position tables, anchors, and sampling grids are computed with numpy at
  trace time from static shapes, so XLA constant-folds them.
"""

import math
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from spotter_tpu.utils.quant import (
    int8_attn_wanted,
    int8_av,
    int8_conv,
    int8_dense,
    int8_dense_wanted,
    int8_qk,
    int8_wanted,
)

# GELU policy: torch's default nn.GELU / HF ACT2FN["gelu"] is the exact erf
# form, which costs ~14 VPU transcendental-class ops per element — measured
# 1.13 vs 0.08 ms against the tanh form at one yolos MLP activation
# (8, 4300, 3072) bf16 on v5e, ~1 ms x 12 layers of pure erf. On bf16
# tensors the tanh approximation's error (<~1e-3 absolute) sits below the
# bf16 rounding already accepted for that tensor, so "auto" (default) uses
# tanh there and exact erf on fp32 — the parity-pinned fp32 policy is
# unchanged. SPOTTER_TPU_GELU=exact|tanh overrides both ways.
_GELU_MODE = os.environ.get("SPOTTER_TPU_GELU", "auto").strip().lower()
if _GELU_MODE not in ("auto", "exact", "tanh"):
    raise ValueError(
        f"SPOTTER_TPU_GELU must be auto|exact|tanh, got {_GELU_MODE!r}"
    )


def _gelu(x: jnp.ndarray) -> jnp.ndarray:
    if _GELU_MODE == "tanh" or (_GELU_MODE == "auto" and x.dtype == jnp.bfloat16):
        return nn.gelu(x, approximate=True)
    return nn.gelu(x, approximate=False)


ACTIVATIONS: dict[str, Callable] = {
    "relu": nn.relu,
    "gelu": _gelu,
    "silu": nn.silu,
    "swish": nn.silu,
    "tanh": jnp.tanh,
    "sigmoid": nn.sigmoid,
    "quick_gelu": lambda x: x * nn.sigmoid(1.702 * x),
}


def get_activation(name: Optional[str]) -> Callable:
    if name is None:
        return lambda x: x
    return ACTIVATIONS[name]


# Flash attention cutover: unmasked self-attention at or above this many
# tokens runs the Pallas TPU flash kernel instead of materializing the
# (B, H, S, S) score matrix. ViT-detector sequences make naive attention
# HBM-catastrophic — yolos-base at 800x1344 is 4300 tokens, i.e. ~7 GB of
# fp32 scores per batch-8 forward (measured 7.6 img/s naive). Short
# sequences (AIFI's 400, decoder's 300) stay on the fused-XLA path, which
# wins there and is the torch-parity-pinned reference. Process-start knob:
# SPOTTER_TPU_FLASH_ATTN=0 disables.
FLASH_ATTN_MIN_SEQ = 1024
_FLASH_ATTN_ENABLED = os.environ.get("SPOTTER_TPU_FLASH_ATTN", "1") != "0"
_FLASH_BLOCK = 512

# Which Pallas attention kernel backs the cutover. "splash" is the newer
# TPU kernel and measured faster at ViT-detector shapes — yolos-base
# (8, 12, 4608, 64): 11.8 vs 13.9 ms/layer raw against flash_attention with
# its best swept blocks (same session, segment ids in both). "auto"
# (default) follows the repo's numerics-default convention (GELU policy,
# RepVGG fusion, MSDA precision): the faster-but-different kernel only
# where bf16 rounding is already accepted — bf16 tensors take splash, fp32
# keeps the established flash kernel. Process-start knob like the others.
_FLASH_IMPL = os.environ.get("SPOTTER_TPU_FLASH_IMPL", "auto").strip().lower()
if _FLASH_IMPL not in ("auto", "splash", "flash"):
    raise ValueError(
        f"SPOTTER_TPU_FLASH_IMPL must be auto|splash|flash, got {_FLASH_IMPL!r}"
    )
# splash block sizes swept on v5e at (8, 12, 4608, 64): bq/bkv 384/2304
# (compute 768) beat 512/512, 768/768, 1536/1536, 256/2304, */4608.
# Round-5 bq re-sweep at the same shape: bq 512 and 768 tie at 12.0
# ms/layer vs 384's 13.6 (-12%); 512 is kept (768's full-kv variants hit
# compile-helper OOMs) and scoped to s_pad >= 4608 where it was measured —
# _splash_block_q below. The ADVICE-r4 3072 interpolation is now measured,
# not extrapolated: full-row 3072 at 6.93 ms vs 1536 at 9.04 / 1024 at
# 9.12 / 768 at 9.59 (s=3000).
_SPLASH_BQ = 384
_SPLASH_BQ_WIDE = 512
_SPLASH_BKV = 2304
_SPLASH_BKV_COMPUTE = 768


def _splash_block_q(s_pad: int) -> int:
    """block_q policy: 512 at the measured >=4608 wide shapes it divides
    (yolos 4608: 12.0 vs 13.6 ms/layer), else the 384 default; both pinned
    by tests/test_flash_attention.py."""
    if s_pad >= 4608 and s_pad % _SPLASH_BQ_WIDE == 0:
        return _SPLASH_BQ_WIDE
    return min(_SPLASH_BQ, s_pad)


def flash_attention_enabled() -> bool:
    """True when the flash path may be taken on this backend (shared by
    every attention implementation in the model zoo)."""
    return _FLASH_ATTN_ENABLED and jax.default_backend() == "tpu"


@jax.named_scope("flash_attention")  # the pad and transposes around the kernel too
def flash_self_attention(q, k, v):
    """(B, S, H, hd) pre-scaled q/k/v -> (B, S, H, hd) via a Pallas TPU
    attention kernel (splash on bf16 tensors / flash on fp32 under the
    default "auto" policy — see _FLASH_IMPL). Pads S to the kernel block
    size; padded tokens live in a
    different segment id, so they can never attend to or be attended by real
    tokens (exact zeros-free equivalence with the naive path)."""
    if _FLASH_IMPL == "splash" or (
        _FLASH_IMPL == "auto" and q.dtype == jnp.bfloat16
    ):
        return _splash_self_attention(q, k, v)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        SegmentIds,
        flash_attention,
    )

    b, s, h, hd = q.shape
    s_pad = -(-s // _FLASH_BLOCK) * _FLASH_BLOCK

    def prep(x):
        x = x.transpose(0, 2, 1, 3)  # (B, H, S, hd)
        if s_pad != s:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        return x

    seg = jnp.broadcast_to(
        (jnp.arange(s_pad) >= s).astype(jnp.int32)[None], (b, s_pad)
    )
    # Explicit uniform block sizes: the kernel's defaults picked a
    # pathological schedule on v5e (64.6 ms vs 3.3 ms at yolos-base shapes,
    # (8, 12, 4608, 64)); s_pad is a _FLASH_BLOCK multiple by construction.
    blk = min(_FLASH_BLOCK, s_pad)
    bs = BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk,
        block_q_dkv=blk, block_k_dkv=blk,
        block_q_dq=blk, block_k_dq=blk, block_k_major_dq=blk,
    )
    out = flash_attention(
        prep(q), prep(k), prep(v),
        segment_ids=SegmentIds(q=seg, kv=seg),
        sm_scale=1.0,  # q arrives pre-scaled by head_dim**-0.5
        block_sizes=bs,
    )
    return out[:, :, :s].transpose(0, 2, 1, 3)


def _splash_block_kv(s_pad: int) -> int:
    """block_kv for a 768-padded sequence (see _splash_self_attention's
    block-size policy notes; swept on v5e round 3 at 4608 and round 4 at
    3840 — tests/test_flash_attention.py pins the chosen ladder)."""
    if s_pad % _SPLASH_BKV == 0:
        return _SPLASH_BKV
    if s_pad <= 3840:
        return s_pad
    return next(c for c in (1536, 768) if s_pad % c == 0)


def _splash_self_attention(q, k, v, interpret: bool = False):
    """Splash-kernel backend of `flash_self_attention` (same contract:
    (B, S, H, hd) pre-scaled inputs, padded tokens isolated by segment ids).

    Block-size policy: pad S to a multiple of 768 so block_q=384 and a
    768-multiple block_kv always divide it; block_kv prefers the swept-best
    2304 (yolos 4608: 11.53 vs 12.49 ms/layer full-kv), else FULL-row kv
    up to 3840 (owlv2's 3601->3840: full-kv 10.18 vs 12.67 at the old
    768 fallback, round-4 sweep), else the largest 768-multiple divisor.
    Splash has no sm_scale — q arrives pre-scaled, matching the flash
    path's sm_scale=1.
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
    )
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as _sm,
    )

    b, s, h, hd = q.shape
    s_pad = -(-s // 768) * 768
    bkv = _splash_block_kv(s_pad)
    bq = _splash_block_q(s_pad)
    bs = _sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=min(_SPLASH_BKV_COMPUTE, bkv),
        block_q_dkv=bq, block_kv_dkv=bkv,
        block_kv_dkv_compute=min(_SPLASH_BKV_COMPUTE, bkv),
        block_q_dq=bq, block_kv_dq=bkv,
    )
    kernel = _sk.make_splash_mha(
        mask=_sm.MultiHeadMask([_sm.FullMask((s_pad, s_pad))] * h),
        head_shards=1,
        q_seq_shards=1,
        block_sizes=bs,
        interpret=interpret,
    )

    def prep(x):
        x = x.transpose(0, 2, 1, 3)  # (B, H, S, hd)
        if s_pad != s:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        return x

    seg = (jnp.arange(s_pad) >= s).astype(jnp.int32)
    segs = _sk.SegmentIds(q=seg, kv=seg)
    out = jax.vmap(kernel, in_axes=(0, 0, 0, None))(prep(q), prep(k), prep(v), segs)
    return out[:, :, :s].transpose(0, 2, 1, 3)


_CAUSAL_BLOCK = 512


@jax.named_scope("causal_attention")
def causal_gqa_attention(q, k, v, interpret: bool = False):
    """Causal grouped-query attention through the splash kernel: q (B, S, H,
    hd) pre-scaled, k and v (B, S, KV, hd), each key-value head serving
    H / KV query heads -> (B, S, H, hd). A function of its own beside
    `_splash_self_attention` (whose lines, and with them the compile-cache
    key of the programs that call it, stay as they were): the multi-query
    form of the kernel (`splash_mqa_fwd_no_residuals` on a device trace),
    once per image and key-value head under vmap, with a causal mask, so the
    blocks above the diagonal are never visited. S is padded to a multiple
    of the block; the pad follows every real token, and no real token
    attends forward, so it needs no segment ids."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
    )
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as _sm,
    )

    b, s, h, hd = q.shape
    kv = k.shape[2]
    group = h // kv
    blk = min(_CAUSAL_BLOCK, -(-s // 128) * 128)
    s_pad = -(-s // blk) * blk
    bs = _sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk,
    )
    kernel = _sk.make_splash_mqa_single_device(
        mask=_sm.MultiHeadMask([_sm.CausalMask((s_pad, s_pad))] * group),
        block_sizes=bs,
        interpret=interpret,
    )

    def prep(x):  # (B, S, heads, hd) -> (B, heads, S_pad, hd)
        x = x.transpose(0, 2, 1, 3)
        if s_pad != s:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        return x

    qh = prep(q).reshape(b, kv, group, s_pad, hd)
    out = jax.vmap(jax.vmap(kernel))(qh, prep(k), prep(v))
    return out.reshape(b, h, s_pad, hd)[:, :, :s].transpose(0, 2, 1, 3)


def inverse_sigmoid(x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x = jnp.clip(x, 0.0, 1.0)
    x1 = jnp.clip(x, eps, None)
    x2 = jnp.clip(1.0 - x, eps, None)
    return jnp.log(x1 / x2)


def fold_bn(
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    mean: jnp.ndarray,
    var: jnp.ndarray,
    eps: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Frozen-BN stats folded to one (mul, add) pair — the single source of
    the fold arithmetic, shared by FrozenBatchNorm and the fused RepVgg path
    (models/rtdetr.py) so the two can never diverge numerically."""
    mul = scale * jax.lax.rsqrt(var + eps)
    return mul, bias - mean * mul


class FrozenBatchNorm(nn.Module):
    """Inference-mode batch norm: y = (x - mean) / sqrt(var + eps) * scale + bias.

    Converted from torch BatchNorm2d running stats. Kept frozen during
    fine-tuning (the DETR-family convention).
    """

    features: int
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        mean = self.param("mean", nn.initializers.zeros, (self.features,), jnp.float32)
        var = self.param("var", nn.initializers.ones, (self.features,), jnp.float32)
        # Fold into a single multiply-add (XLA fuses this into the preceding conv).
        mul, add = fold_bn(scale, bias, mean, var, self.eps)
        return (x * mul.astype(self.dtype) + add.astype(self.dtype)).astype(self.dtype)


class ConvNorm(nn.Module):
    """Conv (no bias) + frozen BN + optional activation.

    Equivalent of the torch ConvNormLayer used across the RT-DETR lineage
    (conv k, stride s, padding (k-1)//2, bias=False, then BN, then act).
    """

    features: int
    kernel_size: int = 3
    stride: int = 1
    padding: Optional[int] = None
    activation: Optional[str] = None
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        pad = (self.kernel_size - 1) // 2 if self.padding is None else self.padding
        # batch-aware: the small-batch guard (utils/quant.py INT8_MIN_BATCH)
        # keeps the latency-SLO buckets bf16 — batch is static under jit
        if int8_wanted(x.shape[-1], batch=x.shape[0]):
            # Quantized path (SPOTTER_TPU_INT8=1, utils/quant.py): int8 MXU
            # conv with the dequant feeding the same frozen-BN chain. The
            # kernel param is declared at nn.Conv's exact path/shape/init so
            # checkpoints and converters are unaffected.
            kernel = ConvKernel(
                (self.kernel_size, self.kernel_size, x.shape[-1], self.features),
                name="conv",
            )()
            x = int8_conv(
                x,
                kernel,
                (self.stride, self.stride),
                [(pad, pad), (pad, pad)],
                self.dtype,
            )
        else:
            x = nn.Conv(
                self.features,
                (self.kernel_size, self.kernel_size),
                strides=(self.stride, self.stride),
                padding=[(pad, pad), (pad, pad)],
                use_bias=False,
                dtype=self.dtype,
                name="conv",
            )(x)
        x = FrozenBatchNorm(self.features, eps=self.eps, dtype=self.dtype, name="bn")(x)
        return get_activation(self.activation)(x)


class ConvNormParams(nn.Module):
    """The exact param tree of ConvNorm (conv/kernel + bn stats) WITHOUT the
    computation, returned as a BN-folded (kernel*mul, add) pair.

    Lives here, directly below the two modules whose param contract it
    shadows (nn.Conv-in-ConvNorm and FrozenBatchNorm): any change to their
    param names/shapes/initializers must be mirrored in the declarations
    below, and tests/test_rep_fuse.py pins the two trees identical. Used by
    the fused RepVgg path (models/rtdetr.py REP_FUSE).
    """

    features: int
    kernel_size: int
    in_features: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        k = self.kernel_size
        kernel = ConvKernel((k, k, self.in_features, self.features), name="conv")()
        mul, add = _BNStats(self.features, self.eps, name="bn")()
        return kernel * mul, add


class PatchEmbed(nn.Module):
    """ViT patch embedding: Conv(P, stride P) rewritten as P row-dots.

    Exact algebraic rewrite of the non-overlapping patchify conv that
    avoids both XLA's small-channel conv lowering and the patch transpose:
    each `pixels[:, ry::P]` slice strides over CONTIGUOUS (gw*P*C)-element
    blocks (XLA copies those well — unlike the per-element minor-dim
    strides that make 3-channel convs slow, pre-round note, round 4, git history), and each
    slice feeds one (B*gh*gw, P*C) @ (P*C, D) dot, accumulated in fp32.
    Measured on v5e bf16 at OWL-ViT patchify shapes ((8, 768^2, 3), P=32):
    2.89 ms vs 5.76 for the conv (the transpose-based reshape+matmul TIES
    the conv at 5.06 — the transpose is the cost, not the contraction).

    Param tree is identical to nn.Conv(features, (P, P), strides=(P, P),
    name=...): "kernel" (P, P, C, D) lecun-normal + optional "bias" zeros,
    so converters and checkpoints are unaffected.
    """

    features: int
    patch_size: int
    use_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, pixels: jnp.ndarray) -> jnp.ndarray:
        p = self.patch_size
        b, h, w, c = pixels.shape
        assert h % p == 0 and w % p == 0, (h, w, p)
        gh, gw = h // p, w // p
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (p, p, c, self.features),
            jnp.float32,
        )
        bias = (
            self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
            if self.use_bias
            else None
        )
        x4 = pixels.reshape(b, h, gw, p * c)  # minor merge (rx, c): trivial
        wr = kernel.reshape(p, p * c, self.features).astype(self.dtype)
        out = None
        for ry in range(p):
            t = jax.lax.dot_general(
                x4[:, ry::p].astype(self.dtype),
                wr[ry],
                (((3,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            out = t if out is None else out + t
        if bias is not None:
            out = out + bias
        return out.astype(self.dtype).reshape(b, gh * gw, self.features)


class QuantDense(nn.Module):
    """nn.Dense-compatible projection (identical param tree: `kernel`
    lecun-normal (in, out) + optional `bias` zeros) that takes the int8 MXU
    path (utils/quant.py int8_dense, STE backward) when SPOTTER_TPU_INT8
    enables it for this width. With the knob off the float path reproduces
    nn.Dense exactly, so the torch-parity tests pin the default numerics.

    Used by the ViT-family projections (yolos, OWL-ViT): their qkv/out/
    fc1/fc2 matmuls carry most of each layer's non-attention FLOPs."""

    features: int
    use_bias: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.features),
            jnp.float32,
        )
        # batch is static under jit, so the small-batch guard (int8 regresses
        # under-filled MXU batches — utils/quant.py INT8_MIN_BATCH) resolves
        # per compiled bucket with no runtime branch
        if int8_dense_wanted(x.shape[-1], batch=x.shape[0]):
            y = int8_dense(x, kernel, self.dtype)
        else:
            y = jnp.matmul(x.astype(self.dtype), kernel.astype(self.dtype))
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, (self.features,), jnp.float32
            )
            y = y + bias.astype(self.dtype)
        return y


class ConvKernel(nn.Module):
    """`kernel` at the path/shape/init nn.Conv(name=...) declares it."""

    shape: tuple

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        return self.param(
            "kernel", nn.initializers.lecun_normal(), self.shape, jnp.float32
        )


class DenseParams(nn.Module):
    """The exact param tree of nn.Dense(features, name=...) — `kernel`
    lecun-normal (in, out) + `bias` zeros — WITHOUT the matmul, returned
    raw. The ConvNormParams pattern for dense layers: the fused MSDA
    prologue kernel (models/rtdetr.py / ops/msda.py) consumes the
    sampling_offsets / attention_weights projection weights directly, and
    declaring them at nn.Dense's paths keeps checkpoints and converters
    unaffected."""

    features: int
    in_features: int

    @nn.compact
    def __call__(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (self.in_features, self.features),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), jnp.float32
        )
        return kernel, bias


class _BNStats(nn.Module):
    """The four FrozenBatchNorm params at its exact paths, returned folded
    as (mul, add)."""

    features: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        scale = self.param("scale", nn.initializers.ones, (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.features,), jnp.float32)
        mean = self.param("mean", nn.initializers.zeros, (self.features,), jnp.float32)
        var = self.param("var", nn.initializers.ones, (self.features,), jnp.float32)
        return fold_bn(scale, bias, mean, var, self.eps)


class PReLU(nn.Module):
    """torch nn.PReLU with num_parameters=1: max(0,x) + a*min(0,x), learned a.

    DAB-DETR's FFN activation (ACT2FN["prelu"]) — the one activation in the
    zoo that carries a weight, so it can't go through get_activation."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        a = self.param("weight", nn.initializers.constant(0.25), (1,), jnp.float32)
        return jnp.maximum(x, 0) + a.astype(x.dtype) * jnp.minimum(x, 0)


class MLPHead(nn.Module):
    """DETR-style MLP prediction head: Linear stack with ReLU between layers."""

    hidden_dim: int
    out_dim: int
    num_layers: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for i in range(self.num_layers):
            out = self.out_dim if i == self.num_layers - 1 else self.hidden_dim
            x = nn.Dense(out, dtype=self.dtype, name=f"layer{i}")(x)
            if i < self.num_layers - 1:
                x = nn.relu(x)
        return x


class MultiHeadAttention(nn.Module):
    """Standard MHA with separate q/k/v/out projections (torch-convertible).

    DETR-lineage peculiarity: position embeddings are added to queries and keys
    only — values come from the un-positioned hidden states.
    """

    embed_dim: int
    num_heads: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        hidden_states: jnp.ndarray,
        position_embeddings: Optional[jnp.ndarray] = None,
        key_value_states: Optional[jnp.ndarray] = None,
        attention_mask: Optional[jnp.ndarray] = None,
        key_position_embeddings: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        head_dim = self.embed_dim // self.num_heads
        q_in = hidden_states
        if position_embeddings is not None:
            q_in = hidden_states + position_embeddings
        if key_value_states is None:  # self-attention
            k_in, v_in = q_in, hidden_states
        else:  # cross-attention
            k_in = key_value_states
            if key_position_embeddings is not None:
                k_in = key_value_states + key_position_embeddings
            v_in = key_value_states

        def proj(x, name):
            return QuantDense(self.embed_dim, dtype=self.dtype, name=name)(x)

        def split(x):
            return x.reshape(*x.shape[:-1], self.num_heads, head_dim)

        q = split(proj(q_in, "q_proj")) * (head_dim**-0.5)
        k = split(proj(k_in, "k_proj"))
        v = split(proj(v_in, "v_proj"))

        if (
            flash_attention_enabled()
            and attention_mask is None
            and key_value_states is None
            and q.shape[1] >= FLASH_ATTN_MIN_SEQ
        ):
            out = flash_self_attention(q, k, v)
            out = out.reshape(*out.shape[:-2], self.embed_dim)
            return proj(out, "out_proj")

        # int8 attention matmuls (SPOTTER_TPU_INT8_ATTN, utils/quant.py):
        # QK^T and attn·V on the int8 MXU with per-(sample, head) dynamic
        # scales. batch is static under jit, so the INT8_MIN_BATCH guard
        # resolves per compiled bucket — the latency-SLO bucket stays bf16.
        # With the knob unset this branch is never taken and the forward is
        # bit-identical to the plain einsum path below (test-asserted).
        quantized = int8_attn_wanted(head_dim, batch=q.shape[0])

        # (B, H, Tq, Tk)
        if quantized:
            logits = int8_qk(q, k)
        else:
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        if attention_mask is not None:
            logits = logits + attention_mask.astype(logits.dtype)
        weights = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(self.dtype)
        if quantized:
            out = int8_av(weights, v, self.dtype)
        else:
            out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
        out = out.reshape(*out.shape[:-2], self.embed_dim)
        return proj(out, "out_proj")


def sincos_2d_position_embedding(
    width: int, height: int, embed_dim: int, temperature: float = 10000.0
) -> np.ndarray:
    """AIFI 2D sin-cos table, (1, W*H, D) — computed in numpy from static shapes.

    Grid is built with 'ij' indexing over (w, h), matching the RT-DETR hybrid
    encoder's layout (tokens enumerate width-major after the flatten-permute).
    """
    if embed_dim % 4 != 0:
        raise ValueError("embed_dim must be divisible by 4 for 2D sin-cos embeddings")
    grid_w, grid_h = np.meshgrid(
        np.arange(width, dtype=np.float32),
        np.arange(height, dtype=np.float32),
        indexing="ij",
    )
    pos_dim = embed_dim // 4
    omega = 1.0 / (temperature ** (np.arange(pos_dim, dtype=np.float32) / pos_dim))
    out_w = grid_w.reshape(-1)[:, None] * omega[None]
    out_h = grid_h.reshape(-1)[:, None] * omega[None]
    table = np.concatenate(
        [np.sin(out_w), np.cos(out_w), np.sin(out_h), np.cos(out_h)], axis=1
    )
    return table[None].astype(np.float32)


def sine_position_embedding_nhwc(
    height: int,
    width: int,
    embed_dim: int,
    temperature: float = 10000.0,
    normalize: bool = True,
    scale: float = 2.0 * math.pi,
    eps: float = 1e-6,
) -> np.ndarray:
    """DETR-style interleaved sine position embedding, (1, H, W, D) numpy.

    Matches DetrSinePositionEmbedding on an all-ones pixel mask: cumulative row
    and column indices (1-based), optionally normalized to [0, scale].
    """
    half = embed_dim // 2
    y = np.arange(1, height + 1, dtype=np.float32)[:, None].repeat(width, 1)
    x = np.arange(1, width + 1, dtype=np.float32)[None, :].repeat(height, 0)
    if normalize:
        y = y / (y[-1:, :] + eps) * scale
        x = x / (x[:, -1:] + eps) * scale
    dim_t = temperature ** (2 * (np.arange(half, dtype=np.float32) // 2) / half)
    pos_x = x[..., None] / dim_t
    pos_y = y[..., None] / dim_t
    pos_x = np.stack([np.sin(pos_x[..., 0::2]), np.cos(pos_x[..., 1::2])], axis=-1)
    pos_y = np.stack([np.sin(pos_y[..., 0::2]), np.cos(pos_y[..., 1::2])], axis=-1)
    pos_x = pos_x.reshape(height, width, half)
    pos_y = pos_y.reshape(height, width, half)
    return np.concatenate([pos_y, pos_x], axis=-1)[None].astype(np.float32)


def grid_sample_bilinear_nhwc(value: jnp.ndarray, grid: jnp.ndarray) -> jnp.ndarray:
    """Bilinear grid sample, align_corners=False, zeros padding — jnp/gather based.

    value: (B, H, W, C); grid: (B, N, P, 2) in [-1, 1] with (x, y) order.
    Returns (B, N, P, C). Semantics match torch.nn.functional.grid_sample so the
    deformable-attention parity holds; implemented as 4 gathers + lerp, which XLA
    lowers to efficient dynamic-gathers on TPU.
    """
    _, h, w, _ = value.shape
    gx = (grid[..., 0] + 1.0) * w / 2.0 - 0.5
    gy = (grid[..., 1] + 1.0) * h / 2.0 - 0.5

    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(yi, xi):
        valid = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
        xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        flat = value.reshape(value.shape[0], h * w, value.shape[-1])
        idx = yc * w + xc  # (B, N, P)
        out = jnp.take_along_axis(
            flat, idx.reshape(idx.shape[0], -1, 1), axis=1
        ).reshape(*idx.shape, value.shape[-1])
        return out * valid[..., None].astype(value.dtype)

    v00 = gather(y0, x0)
    v01 = gather(y0, x0 + 1)
    v10 = gather(y0 + 1, x0)
    v11 = gather(y0 + 1, x0 + 1)
    wx = wx[..., None].astype(value.dtype)
    wy = wy[..., None].astype(value.dtype)
    top = v00 * (1 - wx) + v01 * wx
    bot = v10 * (1 - wx) + v11 * wx
    return top * (1 - wy) + bot * wy


def latent_attention_tokens(s: int) -> int:
    """The token count `causal_latent_attention` takes for S tokens: S padded
    to a multiple of its block, the block at most `_CAUSAL_BLOCK`."""
    blk = min(_CAUSAL_BLOCK, -(-s // 128) * 128)
    return -(-s // blk) * blk


@jax.named_scope("causal_attention")
def causal_latent_attention(q, k, v, interpret: bool = False):
    """Causal multi-head attention whose value width is not its key width
    (latent attention expanded a head: keys of 192, values of 128): q, k (B,
    H, S, dqk), q pre-scaled; v (B, H, S, dv) -> (B, H, S, dv), head-major as
    the kernel reads and writes them, so nothing here moves a byte. The splash
    kernel's multi-head form (`splash_mha_fwd_no_residuals` on a device
    trace) with `head_dim_v` the values' own, once per image under vmap, under
    a causal mask, so the blocks above the diagonal are never visited. Below
    `causal_gqa_attention`, whose lines stay where they are. S is already a
    multiple of the block (`latent_attention_tokens`); padded tokens follow
    every real one, and no real token attends forward, so it needs no segment
    ids."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
    )
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as _sm,
    )

    _, h, s, _ = q.shape
    if s != latent_attention_tokens(s):
        raise ValueError(f"{s} tokens: pad them to {latent_attention_tokens(s)} first")
    blk = min(_CAUSAL_BLOCK, s)
    bs = _sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk,
    )
    kernel = _sk.make_splash_mha_single_device(
        mask=_sm.MultiHeadMask([_sm.CausalMask((s, s))] * h),
        block_sizes=bs,
        interpret=interpret,
    )
    return jax.vmap(kernel)(q, k, v)
