"""Symmetric int8 quantization for the CNN half (`SPOTTER_TPU_INT8=1`).

Why this exists (VERDICT r4 next #2): the round-3 int8 rejection ("0-5%,
not the 2x spec ratio") did not verify the lowering. Re-probed with
asm-level evidence (tools/bench_int8.py, v5e session 2026-07-31):

- the optimized HLO of an int8 x int8 -> int32 `dot_general` shows the MXU
  op consuming `s8` operands directly (`convolution(s8, s8) -> s32`) — the
  int8 path IS emitted by XLA on this toolchain;
- floor-calibrated loop-in-jit: 8192^3 matmul 3.88 ms int8 vs 6.54 ms bf16
  (283.6 TOP/s vs 168.0 TFLOP/s, 1.69x); conv shapes measured separately in
  tools/bench_int8_conv.py.

Scheme: dynamic symmetric per-SAMPLE activation scales (one scale per batch
row — NOT per-tensor: a batch-wide max would couple each image's quantization
grid to its batch neighbors, breaking bit-determinism under the
MicroBatcher's traffic-dependent batch shapes; see
test_quantize_activation_per_sample_scale) + per-out-channel weight scales,
int32 accumulation, dequant folded into the frozen-BN
multiply that already follows every conv (models/layers.py ConvNorm). No
calibration state: the activation scale is max|x|/127 computed per sample —
XLA fuses the reduce into the producing elementwise chain, and the int8
cast HALVES the conv's activation-read traffic, so the quantize pass is
nearly free on the compute-bound 3x3 convs it targets.

Accuracy contract: int8 is OFF by default and sits behind the same golden
-box gate as every numerical rewrite (tests/test_golden_boxes.py runs the
reference anchor ±1 px; tools/golden_check.py gates the Docker build).
Reference anchor: /root/reference/apps/spotter/tests/spotter/test_serve.py
:293-300 — the accuracy bar quantization must clear on real weights.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp

INT8_ENV = "SPOTTER_TPU_INT8"
INT8 = os.environ.get(INT8_ENV, "0").strip() != "0"

# Channel floor: small-channel convs (the stem) are lowering-bound, not
# MXU-bound (pre-round note, round 4, git history — the ~2.5 ms stem gap is a compiler/ISA
# limitation int8 cannot touch), and quantizing them would add a quantize
# pass for no MXU win. Contraction dim (k*k*cin) must fill the MXU.
INT8_MIN_CH = int(os.environ.get("SPOTTER_TPU_INT8_MIN_CH", "64"))

# Batch floor (ISSUE 3): int8 REGRESSES small batches — R101 bucket 4
# measured 33.0 vs 18.7 ms/call bf16 (pre-round note, round 5, git history): under-filled MXU
# contractions make the quantize/dequant passes pure overhead. Batch is a
# static shape under jit, so the guard resolves per compiled bucket: the
# default `--int8` serving config quantizes the batch>=8 throughput buckets
# and leaves the latency-SLO bucket (4) bf16. Floor of 1 disables the guard
# (the CI golden gate runs batch 1 and pins quantized accuracy there).
INT8_MIN_BATCH = int(os.environ.get("SPOTTER_TPU_INT8_MIN_BATCH", "8"))


def int8_wanted(in_channels: int, batch: int | None = None) -> bool:
    if batch is not None and batch < INT8_MIN_BATCH:
        return False
    return INT8 and in_channels >= INT8_MIN_CH


# Dense projections (QuantDense in models/layers.py) are a SEPARATE opt-in:
# SPOTTER_TPU_INT8=1 reproduces exactly the conv-only config the R101/R18
# numbers were measured with (pre-round note, round 5, git history), while
# SPOTTER_TPU_INT8_DENSE=1 additionally quantizes the attention/FFN
# projections routed through QuantDense (ViT towers, MultiHeadAttention —
# measured +6% on yolos on top of the block-q win). Keeping the gates
# independent also lets a golden-gate failure be bisected.
INT8_DENSE = os.environ.get("SPOTTER_TPU_INT8_DENSE", "0").strip() != "0"


def int8_dense_wanted(in_features: int, batch: int | None = None) -> bool:
    # "additionally": dense quantization is an extension OF the int8 mode,
    # never active without it (INT8_DENSE=1 alone is a no-op) — keeps
    # bench/serving labels and the golden-gate bisection truthful
    if batch is not None and batch < INT8_MIN_BATCH:
        return False
    return INT8 and INT8_DENSE and in_features >= INT8_MIN_CH


# Attention matmuls (ISSUE 18): QK^T and attn·V are the two activation x
# activation contractions the conv/dense scheme never touches — no weight
# tensor, so BOTH operands take dynamic scales. Same "additionally"
# convention as INT8_DENSE (never active without SPOTTER_TPU_INT8=1), same
# INT8_MIN_BATCH small-batch guard (the measured batch-4 regression must
# not leak into the latency-SLO bucket). Scales are per-(sample, head):
# per-sample for the MicroBatcher batch-independence contract
# (test_quantize_activation_per_sample_scale), per-head because head
# activation ranges differ by an order of magnitude post-projection and a
# shared scale would crush the quiet heads' resolution.
INT8_ATTN = os.environ.get("SPOTTER_TPU_INT8_ATTN", "0").strip() != "0"

# head_dim floor: QK^T contracts over head_dim, and a head_dim below ~32
# lanes leaves the MXU contraction too shallow for the quantize/dequant
# passes to pay off. 32 (not INT8_MIN_CH's 64) so the RT-DETR decoder's
# 32-dim heads participate by default. No reading on this chip sets it
# (ROADMAP S8, D5).
INT8_ATTN_MIN_HD = int(os.environ.get("SPOTTER_TPU_INT8_ATTN_MIN_HD", "32"))


def int8_attn_wanted(head_dim: int, batch: int | None = None) -> bool:
    if batch is not None and batch < INT8_MIN_BATCH:
        return False
    return INT8 and INT8_ATTN and head_dim >= INT8_ATTN_MIN_HD


def quantize_weight(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(k, k, cin, cout) float -> (int8 kernel, (cout,) f32 scales).

    Per-out-channel symmetric: scale_c = max|w[..., c]| / 127. Runs on
    device per call — the kernel tensors are small (<=1.3 MB for the
    largest R101 conv) and XLA CSEs the quantization across iterations of
    a serving loop only when weights are donated/constant; per-call cost is
    noise either way.
    """
    amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    wq = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return wq, scale.reshape(-1).astype(jnp.float32)


def quantize_activation(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dynamic per-SAMPLE symmetric: (int8 x, (B, 1, ..., 1) f32 scales).

    Per-sample (not whole-batch) scales keep a served request's
    quantization independent of what the MicroBatcher co-batched with it —
    a batch-mate with an activation outlier must not shift this image's
    boxes (review finding, round 5). Rank-1 inputs fall back to a global
    scale."""
    xf = x.astype(jnp.float32)
    # rank-1: one global scale (axis=() would reduce over NOTHING and
    # yield per-element scales)
    axes = tuple(range(1, x.ndim)) if x.ndim > 1 else (0,)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    xq = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return xq, scale


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _int8_conv_core(x, kernel, strides, padding):
    xq, sx = quantize_activation(x)
    wq, sw = quantize_weight(kernel)
    y = jax.lax.conv_general_dilated(
        xq,
        wq,
        window_strides=strides,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32,
    )
    return y.astype(jnp.float32) * (sx * sw)


def _int8_conv_fwd(x, kernel, strides, padding):
    return _int8_conv_core(x, kernel, strides, padding), (x, kernel)


def _int8_conv_bwd(strides, padding, res, g):
    # Straight-through estimator: the backward pass is the FLOAT conv's —
    # round/clip are flat almost everywhere, so the true int8 gradient would
    # silently zero the CNN half under fine-tuning (QAT convention).
    x, kernel = res

    def float_conv(xx, ww):
        return jax.lax.conv_general_dilated(
            xx.astype(jnp.float32),
            ww.astype(jnp.float32),
            window_strides=strides,
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    _, vjp = jax.vjp(float_conv, x, kernel)
    dx, dk = vjp(g.astype(jnp.float32))
    return dx.astype(x.dtype), dk.astype(kernel.dtype)


_int8_conv_core.defvjp(_int8_conv_fwd, _int8_conv_bwd)


@jax.custom_vjp
def _int8_dense_core(x, kernel):
    """(..., K) @ (K, N) with int8 operands and int32 accumulation."""
    xq, sx = quantize_activation(x)
    wq, sw = quantize_weight(kernel)
    y = jax.lax.dot_general(
        xq.reshape(-1, xq.shape[-1]),
        wq,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    y = y.reshape(*x.shape[:-1], kernel.shape[-1])
    return y.astype(jnp.float32) * (sx * sw)


def _int8_dense_fwd(x, kernel):
    return _int8_dense_core(x, kernel), (x, kernel)


def _int8_dense_bwd(res, g):
    # straight-through: the float matmul's gradients (see _int8_conv_bwd)
    x, kernel = res

    def float_dense(xx, ww):
        return jnp.einsum(
            "...k,kn->...n", xx.astype(jnp.float32), ww.astype(jnp.float32)
        )

    _, vjp = jax.vjp(float_dense, x, kernel)
    dx, dk = vjp(g.astype(jnp.float32))
    return dx.astype(x.dtype), dk.astype(kernel.dtype)


_int8_dense_core.defvjp(_int8_dense_fwd, _int8_dense_bwd)


def int8_dense(
    x: jnp.ndarray, kernel: jnp.ndarray, out_dtype: jnp.dtype
) -> jnp.ndarray:
    """Quantized dense: drop-in for `x @ kernel` (bias stays outside — it
    adds in float after dequant). Same scheme and STE backward as
    `int8_conv`; the ViT families' qkv/out/fc1/fc2 projections are where
    the matmul FLOPs live (e.g. ~52% of a yolos layer's budget)."""
    return _int8_dense_core(x, kernel).astype(out_dtype)


def quantize_per_head(x: jnp.ndarray, head_axis: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dynamic symmetric int8 with one scale per (sample, head).

    Reduces |x| over every axis except batch (0) and `head_axis`, keeping
    dims so the scale broadcasts back. Per-sample keeps a served request's
    grid independent of its batch-mates (the conv-path contract); per-head
    keeps loud heads from crushing quiet heads' resolution.
    """
    xf = x.astype(jnp.float32)
    axes = tuple(a for a in range(x.ndim) if a not in (0, head_axis % x.ndim))
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    xq = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return xq, scale


@jax.custom_vjp
def _int8_qk_core(q, k):
    """(B, Tq, H, hd) x (B, Tk, H, hd) -> (B, H, Tq, Tk) fp32 logits.

    Both operands quantized with per-(sample, head) dynamic scales, int8 x
    int8 -> int32 on the MXU, dequant folded into one fp32 multiply.
    """
    qq, sq = quantize_per_head(q, head_axis=2)
    kq, sk = quantize_per_head(k, head_axis=2)
    y = jax.lax.dot_general(
        qq, kq,
        (((3,), (3,)), ((0, 2), (0, 2))),  # contract hd; batch over (B, H)
        preferred_element_type=jnp.int32,
    )  # (B, H, Tq, Tk)
    # sq/sk arrive (B, 1, H, 1); fold to (B, H, 1, 1) for the output layout
    s = (sq * sk).transpose(0, 2, 1, 3)
    return y.astype(jnp.float32) * s


def _int8_qk_fwd(q, k):
    return _int8_qk_core(q, k), (q, k)


def _int8_qk_bwd(res, g):
    # straight-through: the float einsum's gradients (see _int8_conv_bwd)
    q, k = res

    def float_qk(qq, kk):
        return jnp.einsum(
            "bqhd,bkhd->bhqk", qq.astype(jnp.float32), kk.astype(jnp.float32)
        )

    _, vjp = jax.vjp(float_qk, q, k)
    dq, dk = vjp(g.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype)


_int8_qk_core.defvjp(_int8_qk_fwd, _int8_qk_bwd)


@jax.custom_vjp
def _int8_av_core(w, v):
    """(B, H, Tq, Tk) softmax weights x (B, Tk, H, hd) -> (B, Tq, H, hd).

    The weights are post-softmax probabilities in [0, 1]; their per-head
    amax is <= 1 so the int8 grid resolves ~1/127 steps of probability —
    coarse in absolute terms but weighted by values whose own grid carries
    the head scale, and gated by the same accuracy tolerance tests as the
    conv path. int32 accumulation over Tk.
    """
    wq, sw = quantize_per_head(w, head_axis=1)
    vq, sv = quantize_per_head(v, head_axis=2)
    y = jax.lax.dot_general(
        wq, vq.transpose(0, 2, 1, 3),  # (B, H, Tk, hd)
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.int32,
    )  # (B, H, Tq, hd)
    s = sw * sv.transpose(0, 2, 1, 3)  # (B, H, 1, 1)
    return (y.astype(jnp.float32) * s).transpose(0, 2, 1, 3)


def _int8_av_fwd(w, v):
    return _int8_av_core(w, v), (w, v)


def _int8_av_bwd(res, g):
    w, v = res

    def float_av(ww, vv):
        return jnp.einsum(
            "bhqk,bkhd->bqhd", ww.astype(jnp.float32), vv.astype(jnp.float32)
        )

    _, vjp = jax.vjp(float_av, w, v)
    dw, dv = vjp(g.astype(jnp.float32))
    return dw.astype(w.dtype), dv.astype(v.dtype)


_int8_av_core.defvjp(_int8_av_fwd, _int8_av_bwd)


def int8_qk(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Quantized QK^T: drop-in for einsum("bqhd,bkhd->bhqk", q, k), fp32
    out (the softmax that follows runs fp32 either way). STE backward."""
    return _int8_qk_core(q, k)


def int8_av(w: jnp.ndarray, v: jnp.ndarray, out_dtype: jnp.dtype) -> jnp.ndarray:
    """Quantized attn·V: drop-in for einsum("bhqk,bkhd->bqhd", w, v)."""
    return _int8_av_core(w, v).astype(out_dtype)


def int8_conv(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    strides: tuple[int, int],
    padding,
    out_dtype: jnp.dtype,
) -> jnp.ndarray:
    """Quantized NHWC conv: int8 x int8 -> int32 MXU, dequantized to
    `out_dtype`. Drop-in for the float conv inside ConvNorm (the frozen-BN
    multiply-add that follows absorbs into the dequant elementwise chain
    under XLA fusion). Differentiable via a straight-through estimator
    (float-conv backward), so SPOTTER_TPU_INT8=1 under the train step
    fine-tunes instead of freezing the CNN half."""
    strides = tuple(int(s) for s in strides)
    padding = tuple((int(a), int(b)) for a, b in padding)
    return _int8_conv_core(x, kernel, strides, padding).astype(out_dtype)
