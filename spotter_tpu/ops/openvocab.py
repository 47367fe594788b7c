"""Fused open-vocab logit head for the OWL-ViT path (ISSUE 18 tentpole).

The unfused `OwlViTClassHead` tail is four elementwise/matmul HLOs with the
(B, P, Q) logits tensor materialized between them: per-patch L2 normalize,
cosine matmul against the text-query bank, learned per-patch (shift,
elu-scale) affine, and the NEG_INF padded-query mask. This module fuses all
four into one Pallas kernel so the logits tensor is produced exactly once,
already masked — the natural fused shape named by ROADMAP item 1.

Knob: `SPOTTER_TPU_OWL_FUSED` = auto|1|0 (default auto = on for TPU, off
elsewhere; `1` forces the kernel — off-TPU it then fails to lower, like the
MSDA kernels: only a test that passes `interpret=True` runs it on a CPU,
nothing picks interpret mode quietly). The dense0 / logit_shift
/ logit_scale projections stay in XLA — they are plain GEMMs XLA already
fuses well; the win is the (B, P, Q)-shaped tail.

Sharding: under the PR 13 tp partition rules the OWL-ViT heads are
replicated (their params are omitted from TRANSFORMER_TP_RULES), so every
input to this kernel arrives replicated and the pallas_call needs no
sharding annotations of its own.

Padded-query contract: query slots beyond the real count (lane padding to
128) get mask 0 and therefore NEG_INF logits — same value the reference
writes for caller-masked queries — so a padded slot can never win an
argmax over any real query (test-asserted).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)
LANE = 128
P_TILE = 128  # patch rows per grid cell

OWL_FUSED = os.environ.get("SPOTTER_TPU_OWL_FUSED", "auto").strip().lower()
if OWL_FUSED not in ("auto", "1", "0"):
    raise ValueError(f"SPOTTER_TPU_OWL_FUSED must be auto|1|0, got {OWL_FUSED!r}")


def owl_fused_wanted() -> bool:
    """True when OwlViTClassHead should route through the fused kernel.
    Checked at trace time (module constant + backend), monkeypatchable in
    tests like the MSDA knobs."""
    if OWL_FUSED == "1":
        return True
    if OWL_FUSED == "0":
        return False
    return jax.default_backend() == "tpu"


def _class_logits_kernel(img_ref, qt_ref, ss_ref, qmask_ref, out_ref):
    x = img_ref[0].astype(jnp.float32)  # (P_TILE, Dt)
    n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)) + 1e-6
    xn = x / n
    logits = jnp.dot(
        xn, qt_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32
    )  # (P_TILE, Qp)
    sh = ss_ref[0][:, 0:1].astype(jnp.float32)
    sc_raw = ss_ref[0][:, 1:2].astype(jnp.float32)
    # jax.nn.elu(x) + 1 == where(x > 0, x + 1, exp(x)). Spelled with exp:
    # Pallas TPU lowering has no expm1 ("Unimplemented primitive in Pallas
    # TPU lowering for KernelType.TC: expm1", jax 0.9.0), and expm1(x) + 1
    # rounds to exp(x) within 1 ulp of fp32 — inside the 1e-6 the parity
    # tests allow against the unfused tail.
    sc = jnp.where(sc_raw > 0, sc_raw + 1.0, jnp.exp(sc_raw))
    out = (logits + sh) * sc
    out_ref[0] = jnp.where(qmask_ref[...] == 0.0, NEG_INF, out)


def _class_logits_ref(img, qt, ss, qmask):
    """jnp reference (VJP + interpret parity): same math as the kernel.
    img (B, Pp, Dt), qt (Dt, Qp) pre-normalized queries, ss (B, Pp, 2)
    raw (shift, scale) lanes, qmask (1, Qp) float 1=valid -> (B, Pp, Qp)."""
    x = img.astype(jnp.float32)
    xn = x / (jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)) + 1e-6)
    logits = jnp.einsum("bpd,dq->bpq", xn, qt.astype(jnp.float32))
    sh = ss[..., 0:1].astype(jnp.float32)
    sc = jax.nn.elu(ss[..., 1:2].astype(jnp.float32)) + 1.0
    out = (logits + sh) * sc
    return jnp.where(qmask[:, None, :] == 0.0, NEG_INF, out)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def pallas_class_logits(img, qt, ss, qmask, interpret: bool = False):
    """Fused normalize + cosine-logit + affine + mask kernel.

    img: (B, Pp, Dt) raw dense0 output, patch rows padded to P_TILE (zero
    rows normalize to zero and their output is sliced off by the caller);
    qt: (Dt, Qp) pre-L2-normalized query bank, transposed, lane-padded with
    zero columns; ss: (B, Pp, 2) raw logit_shift/logit_scale lanes (elu
    applied in-kernel); qmask: (1, Qp) float, 0 for caller-masked AND
    lane-padded query slots -> those columns come out NEG_INF.
    """
    b, pp, dt = img.shape
    qp = qt.shape[1]
    n_pt = pp // P_TILE
    assert ss.shape == (b, pp, 2), (ss.shape, img.shape)
    assert qmask.shape == (1, qp), (qmask.shape, qt.shape)
    flops = 2 * b * pp * dt * qp + 5 * b * pp * (dt + qp)
    # XLA costs pallas custom-calls as 0 FLOPs; self-report for MFU honesty
    from spotter_tpu.obs.perf import note_kernel_flops

    note_kernel_flops("owl_class_logits", flops)
    return pl.pallas_call(
        _class_logits_kernel,
        out_shape=jax.ShapeDtypeStruct((b, pp, qp), jnp.float32),
        grid=(b, n_pt),
        in_specs=[
            pl.BlockSpec(
                (1, P_TILE, dt), lambda i, pt: (i, pt, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (dt, qp), lambda i, pt: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, P_TILE, 2), lambda i, pt: (i, pt, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, qp), lambda i, pt: (0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, P_TILE, qp), lambda i, pt: (i, pt, 0), memory_space=pltpu.VMEM
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=img.size * 4 + qt.size * 4 * b + b * pp * qp * 4,
            transcendentals=2 * b * pp,  # rsqrt + exp per patch row
        ),
        interpret=interpret,
    )(img, qt, ss, qmask)


def _cl_fwd(img, qt, ss, qmask, interpret):
    out = pallas_class_logits(img, qt, ss, qmask, interpret)
    return out, (img, qt, ss, qmask)


def _cl_bwd(interpret, res, g):
    img, qt, ss, qmask = res
    # NEG_INF columns carry zero cotangent in any sane loss; the reference
    # where() kills their gradient regardless.
    _, vjp = jax.vjp(_class_logits_ref, img, qt, ss, qmask)
    d_img, d_qt, d_ss, d_qmask = vjp(g)
    return d_img.astype(img.dtype), d_qt.astype(qt.dtype), d_ss.astype(ss.dtype), d_qmask


pallas_class_logits.defvjp(_cl_fwd, _cl_bwd)


def fused_class_logits(
    img_cls: jnp.ndarray,  # (B, P, Dt) raw dense0 output (unnormalized)
    query_embeds: jnp.ndarray,  # (Q, Dt) pre-L2-normalized text queries
    shift: jnp.ndarray,  # (B, P) raw logit_shift
    scale_raw: jnp.ndarray,  # (B, P) raw logit_scale (pre-elu)
    query_mask: jnp.ndarray | None,  # (Q,) 1=valid, or None
    interpret: bool = False,
) -> jnp.ndarray:
    """Pad/transpose prep + fused kernel; returns (B, P, Q) fp32 logits.

    `interpret=True` is for CPU tests only (the MSDA convention): the
    default compiles the kernel for the chip or fails.
    """
    b, p, dt = img_cls.shape
    q = query_embeds.shape[0]

    qp = -(-q // LANE) * LANE
    pp = -(-p // P_TILE) * P_TILE
    qt = query_embeds.astype(jnp.float32).T  # (Dt, Q)
    if qp != q:
        qt = jnp.pad(qt, ((0, 0), (0, qp - q)))
    mask = (
        jnp.ones((q,), jnp.float32)
        if query_mask is None
        else (query_mask != 0).astype(jnp.float32)
    )
    mask = jnp.pad(mask, (0, qp - q))[None] if qp != q else mask[None]
    ss = jnp.stack([shift, scale_raw], axis=-1)  # (B, P, 2)
    img = img_cls
    if pp != p:
        img = jnp.pad(img, ((0, 0), (0, pp - p), (0, 0)))
        ss = jnp.pad(ss, ((0, 0), (0, pp - p), (0, 0)))
    out = pallas_class_logits(img, qt, ss, mask, interpret)
    return out[:, :p, :q]
