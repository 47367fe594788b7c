"""Multiscale deformable-attention sampling — gather-free Pallas MXU kernel,
XLA row-gather path, and an experimental Pallas lane-gather kernel.

This is the one custom op of the RT-DETR family (the torch lineage ships a
CUDA kernel for it; HF's port falls back to `grid_sample` per level —
modeling_rt_detr_v2's multi_scale_deformable_attention_v2). On TPU the op
dominates the whole model when expressed as gathers — measured on v5e,
R101 batch 8: the six decoder layers' sampling costs ~69 of the 78 ms
forward, and scales super-linearly with batch (11.5 -> 73 ms per layer from
batch 8 to 16) because XLA's gather lowering falls off a vectorized path.
Every gather formulation (2 batch dims, flattened batch, global-row take,
folded corners) hits the same wall.

The production Pallas kernel ("pallas", auto-selected on TPU) therefore
eliminates the gather entirely — TPU-first thinking: turn irregular memory
access into regular compute on the MXU/VPU:

    out(q, hd) = OneHot(q, s) @ V(s, hd)

where OneHot folds ALL of a query's sample weights — L*P points x 4
bilinear corners x attention weight x in-bounds validity — into one row:
OneHot[q, s] = sum_{point, corner} w[point, corner, q] * (idx[point,
corner, q] == s). The kernel builds OneHot *tiles* in VMEM from iota
comparisons (pure VPU, no scatter/gather) and contracts them against value
tiles on the MXU, accumulating over source tiles via output revisiting.
The full one-hot matrix never exists: a (Q, S_TILE) tile lives per grid
step. The comparisons are the cost: 48*Q*S per (batch, head) on the VPU —
regular, vectorizable work instead of 48*Q irregular row fetches.

Two more backends:
- "xla": row gathers along S of (S, head_dim) value rows — the fastest
  *gather-based* XLA formulation (minor-axis gathers are ~40x worse:
  2650 ms/call measured). CPU/GPU default, and the VJP reference.
- "pallas_gather": fused lane-dimension `take_along_axis` kernel. Blocked
  today by Mosaic's single-vreg gather limit ("Not implemented: Multiple
  source vregs along gather dimension" for S > 128); kept for when Mosaic
  grows multi-vreg gathers, correct under interpret mode and on
  single-vreg sources (pinned by tests/test_msda.py).

Differentiation: both Pallas kernels carry a custom VJP whose backward
recomputes through the pure-jnp XLA reference — exactly differentiable, so
the train step works with kernels enabled.

Two sparsity layers cut the compare cost:

- Level-split: the kernel runs once per feature level — a sample only ever
  lands inside its own level's span of the flat source, so comparing it
  against other levels' positions is pure waste (the stride-8 level holds
  ~76% of positions but only 1/3 of samples; ~3x fewer compares).
- Block-sparse: queries are sorted by quantized mean sample location
  (y-major, matching the row-major source so source tiles are horizontal
  bands), and a per-(query-tile, source-tile) hit table — scalar-prefetched
  into SMEM — lets the kernel skip pairs no sample touches. Sampling
  offsets cluster around each query's reference box, so sorted neighbors
  touch few bands. The sort/unsort are two tiny Q-row permutes in XLA; the
  mask provably never suppresses a hit (built from idx where w > 0).

Measured on v5e (R101, 640x640, clean chip, full model forward, batch
8 / 16): XLA row-gathers 77.7 / 500.6 ms (the gather lowering collapses
above batch*heads ~96); dense one-hot 109.9 / 228.9; level-split 71.2 /
145.2; level-split + block-sparse (production) 63.2 / 137.9 — every
formulation parity-tested against the gather reference.

Backend policy: `SPOTTER_TPU_MSDA` = auto (pallas on TPU, xla elsewhere) |
xla | pallas | pallas_sep | pallas_gather.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MSDA_ENV = "SPOTTER_TPU_MSDA"
LANE = 128


def locality_sort_key(xy: jnp.ndarray) -> jnp.ndarray:
    """(…, 2) normalized xy -> (…,) int32 quantized y-major sort key.

    Shared by the in-op locality sort below and model-level presorting
    (models/rtdetr.py): y-major matches the row-major source layout, so
    neighboring sorted queries sample the same horizontal bands and the
    kernels' block-sparse hit tables prune."""
    return (
        jnp.clip((xy[..., 1] * 64).astype(jnp.int32), 0, 63) * 64
        + jnp.clip((xy[..., 0] * 64).astype(jnp.int32), 0, 63)
    )


def locality_presort(xy: jnp.ndarray):
    """(B, Q, 2) normalized centers -> (sort, unsort) callables that
    permute / un-permute (B, Q, ...) tensors along axis 1 by
    `locality_sort_key` order. The single implementation of the model-level
    presort contract (rtdetr.py / deformable_detr.py decoders): both
    decoders and the kernels' tiling assumption stay in lockstep by
    construction."""
    perm = jnp.argsort(locality_sort_key(xy), axis=1)
    inv_perm = jnp.argsort(perm, axis=1)

    def sort(a: jnp.ndarray) -> jnp.ndarray:
        return jnp.take_along_axis(a, perm[:, :, None], axis=1)

    def unsort(a: jnp.ndarray) -> jnp.ndarray:
        return jnp.take_along_axis(a, inv_perm[:, :, None], axis=1)

    return sort, unsort


def encoder_presorted() -> bool:
    """Whether MSDA *encoder* self-attention may claim its queries are
    already locality-ordered. Encoder tokens arrive level-major row-major —
    exactly the y-major band order the hit tables want — so the in-op
    argsort + two q-row permutes over the full token set (10k+ at 800x1333)
    are pure waste and default off. SPOTTER_TPU_MSDA_ENC_PRESORTED=0
    restores the in-op mean-sample-location sort for checkpoints whose
    encoder offsets reach far enough that sample-location order beats
    token order (ADVICE r3: the knob must exist or such checkpoints have
    no way back to the sorted path)."""
    return os.environ.get("SPOTTER_TPU_MSDA_ENC_PRESORTED", "1") != "0"


def presort_wanted() -> bool:
    """True when a caller that can order its queries by spatial locality
    ONCE (e.g. the RT-DETR decoder stack, whose six layers share one
    ordering) should do so and pass `presorted=True` per op, instead of
    paying the sort + two q-row permutes inside every sampling op
    (measured 3.34 -> 2.97 ms per R101 layer cell, v5e). False when the
    active backend ignores ordering (XLA gathers) or the sort is disabled."""
    return MSDA_SORT and msda_backend(None) in ("pallas", "pallas_sep")


def msda_backend(override: str | None = None, batch_heads: int | None = None) -> str:
    """`batch_heads` is accepted for callers that want to specialize the
    policy by problem size; with the level-split kernel the measured answer
    is uniform, so it is currently unused."""
    del batch_heads
    name = (override or os.environ.get(MSDA_ENV, "auto")).strip().lower()
    if name not in ("auto", "xla", "pallas", "pallas_sep", "pallas_gather"):
        raise ValueError(
            f"{MSDA_ENV} must be auto|xla|pallas|pallas_sep|pallas_gather, "
            f"got {name!r}"
        )
    if name == "auto":
        # TPU: the merged-level one-hot kernel wins at every measured size
        # (R101 decoder stack, v5e, 1-pass precision: 24 ms vs 36 ms for the
        # separable-dot kernel and 205 ms for XLA row-gathers, whose
        # lowering collapses above batch*heads ~96). CPU/GPU: always XLA
        # (interpret-mode pallas would be pointlessly slow there).
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return name


def _level_offsets(spatial_shapes: tuple[tuple[int, int], ...]) -> np.ndarray:
    sizes = [h * w for h, w in spatial_shapes]
    return np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)


def _corner_terms(xs, ys, at, w_const, h_const, method):
    """Shared corner math of the loc-prep kernel and its jnp reference.

    xs/ys/at: (..., LP) normalized sample coords + attention weights;
    w_const/h_const: (1, LP) (or broadcastable) per-lane level dims.
    Returns [(idx_level_local, weight)] per active corner, each (..., LP).
    """
    if method == "discrete":
        cx = jnp.clip(jnp.floor(xs * w_const + 0.5), 0, w_const - 1)
        cy = jnp.clip(jnp.floor(ys * h_const + 0.5), 0, h_const - 1)
        idx0 = (cy * w_const + cx).astype(jnp.int32)
        return [(idx0, at.astype(jnp.float32))]
    gx = xs * w_const - 0.5
    gy = ys * h_const - 0.5
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    fx = (gx - x0).astype(jnp.float32)
    fy = (gy - y0).astype(jnp.float32)
    out = []
    for dy in (0, 1):
        for dx in (0, 1):
            xc = x0 + dx
            yc = y0 + dy
            valid = (xc >= 0) & (xc <= w_const - 1) & (yc >= 0) & (yc <= h_const - 1)
            wx = fx if dx else 1.0 - fx
            wy = fy if dy else 1.0 - fy
            wgt = jnp.where(valid, wx * wy * at.astype(jnp.float32), 0.0)
            idxc = (
                jnp.clip(yc, 0, h_const - 1) * w_const + jnp.clip(xc, 0, w_const - 1)
            ).astype(jnp.int32)
            out.append((idxc, wgt))
    return out


def prepare_msda_gather(
    loc: jnp.ndarray,  # (B, H, LP, Q, 2) normalized [0,1] sample points
    attn: jnp.ndarray,  # (B, H, LP, Q) softmaxed attention weights
    spatial_shapes: tuple[tuple[int, int], ...],
    num_points: int,
    method: str = "default",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Corner indices + folded weights for the gather kernel.

    Returns idx (B, H, 4, LP*Q) int32 into the padded flat space and
    w (B, H, 4, LP*Q) fp32. For method="discrete" only corner 0 is active
    (nearest-neighbor, border-clamped — RT-DETRv2 discrete sampling
    semantics); for "default" the four bilinear corners carry
    align_corners=False, zeros-padding semantics.
    """
    b, h_axis, lp, q, _ = loc.shape
    levels = len(spatial_shapes)
    offs = _level_offsets(spatial_shapes)
    # per-sample level id: sample axis is level-major (L blocks of P points)
    lvl_h = np.repeat([hh for hh, _ in spatial_shapes], num_points).astype(np.float32)
    lvl_w = np.repeat([ww for _, ww in spatial_shapes], num_points).astype(np.float32)
    lvl_off = np.repeat(offs, num_points).astype(np.int32)
    assert lvl_h.shape[0] == lp, (lp, levels, num_points)
    shp = (1, 1, lp, 1)
    lvl_h = lvl_h.reshape(shp)
    lvl_w = lvl_w.reshape(shp)
    lvl_off = lvl_off.reshape(shp)

    # Corner decomposition shared with the in-kernel prep path
    # (_corner_terms is THE single implementation of the discrete/bilinear
    # corner semantics); this wrapper adds the global level offsets and the
    # fixed 4-slot corner axis the gather consumers index.
    corners = _corner_terms(loc[..., 0], loc[..., 1], attn, lvl_w, lvl_h, method)
    while len(corners) < 4:  # discrete: one active corner + zero slots
        corners.append(
            (jnp.zeros_like(corners[0][0]), jnp.zeros_like(corners[0][1]))
        )
    idx = jnp.stack([lvl_off + c for c, _ in corners], axis=2)
    w = jnp.stack([cw for _, cw in corners], axis=2)

    # (B, H, 4, LP, Q) -> (B, H, 4, LP*Q): sample-major flat layout so the
    # kernel's group-sum is LP contiguous static slices of Q lanes.
    idx = idx.reshape(b, h_axis, 4, lp * q)
    w = w.reshape(b, h_axis, 4, lp * q)
    return idx, w


def _gather_weighted_sum(vt, idx, w, lp: int, q: int):
    """Reference math shared by the XLA path and the kernel's VJP.

    vt: (B, H, hd, S); idx/w: (B, H, 4, LP*Q). Returns (B, H, hd, Q).

    Gather-axis choice is the whole performance story here, and it differs
    per backend: XLA lowers *row* gathers (major axis, contiguous minor dim)
    to fast vector loads but per-element minor-axis gathers to a ~40x-slower
    generic path, while Mosaic's DynamicGather vectorizes only along lanes
    (the minor axis). So this XLA-side reference works row-major — value
    rows (S, hd) gathered along S — on the transpose of the kernel's
    (hd, S) lane layout.
    """
    rows = vt.transpose(0, 1, 3, 2)  # (B, H, S, hd): gather rows along S
    return _row_gather_weighted_sum(rows, idx, w, lp, q).transpose(0, 1, 3, 2)


def _row_gather_weighted_sum(rows, idx, w, lp: int, q: int):
    """Row-major core: rows (B, H, S, hd), idx/w (B, H, 4, LP*Q) ->
    (B, H, Q, hd)."""
    hd = rows.shape[-1]
    acc = None
    for c in range(4):  # corner loop: never broadcast the value maps 4x
        g = jnp.take_along_axis(rows, idx[:, :, c, :, None], axis=2)
        term = g * w[:, :, c, :, None].astype(rows.dtype)  # (B, H, N, hd)
        acc = term if acc is None else acc + term
    return acc.reshape(*acc.shape[:2], lp, q, hd).sum(axis=2)


def xla_deformable_sampling(vt, idx, w, lp: int, q: int):
    """Pure-XLA fallback with identical semantics to the Pallas kernel."""
    return _gather_weighted_sum(vt, idx, w, lp, q)


def _msda_kernel(vt_ref, idx_ref, w_ref, out_ref, *, lp: int, q: int):
    # vt, idx, w all share the lane extent G = max(S, LP*Q) rounded up to a
    # lane multiple: Mosaic's vectorized gather requires indices broadcast
    # to exactly the input shape (dynamic_gather is an elementwise lookup).
    vt = vt_ref[0, 0]  # (hd, G)
    hd, g_lanes = vt.shape
    acc = jnp.zeros((hd, g_lanes), vt.dtype)
    for c in range(4):
        ids = jnp.broadcast_to(idx_ref[0, 0, c][None, :], (hd, g_lanes))
        g = jnp.take_along_axis(vt, ids, axis=1)
        acc = acc + g * w_ref[0, 0, c][None, :].astype(vt.dtype)
    out = jnp.zeros((hd, q), vt.dtype)
    for j in range(lp):  # static contiguous slices: sample-major layout
        out = out + acc[:, j * q : (j + 1) * q]
    out_ref[0, 0] = out


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pallas_deformable_sampling(vt, idx, w, lp: int, q: int, interpret: bool = False):
    """Fused gather + weighted group-sum on TPU.

    vt: (B, H, hd, S) value maps (S padded to a lane multiple);
    idx/w: (B, H, 4, LP*Q) from `prepare_msda_gather`. Returns (B, H, hd, Q).
    """
    b, h_axis, hd, s = vt.shape
    n = idx.shape[-1]
    # Common lane extent: Mosaic's gather needs source and (broadcast)
    # indices to share a shape. Pad source and samples to G lanes; padded
    # sample slots carry idx 0 / weight 0 and never enter the group-sum.
    g_lanes = max(-(-s // LANE) * LANE, -(-n // LANE) * LANE)
    if g_lanes != s:
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, 0), (0, g_lanes - s)))
    if g_lanes != n:
        idx = jnp.pad(idx, ((0, 0), (0, 0), (0, 0), (0, g_lanes - n)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, g_lanes - n)))
    kernel = partial(_msda_kernel, lp=lp, q=q)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, h_axis, hd, q), vt.dtype),
        grid=(b, h_axis),
        in_specs=[
            pl.BlockSpec(
                (1, 1, hd, g_lanes), lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, 1, 4, g_lanes), lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, 1, 4, g_lanes), lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, hd, q), lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(vt, idx, w)


def _msda_fwd(vt, idx, w, lp, q, interpret):
    return pallas_deformable_sampling(vt, idx, w, lp, q, interpret), (vt, idx, w)


def _msda_bwd(lp, q, interpret, res, g):
    # Backward through the pure-jnp reference: exactly the same math, so the
    # kernel stays a drop-in under jax.grad (train step with pallas on).
    vt, idx, w = res
    _, vjp = jax.vjp(lambda v, ww: _gather_weighted_sum(v, idx, ww, lp, q), vt, w)
    dvt, dw = vjp(g)
    return dvt, None, dw


pallas_deformable_sampling.defvjp(_msda_fwd, _msda_bwd)


# --- gather-free one-hot MXU kernel (the production TPU backend) ---

# Five 128-lane vregs per one-hot tile column block. Swept on v5e (R101
# batch 8, mixed policy): S_TILE 256/384/512/640/768 -> 64.0/58.5/54.4/
# 52.1/54.9 ms end-to-end. 640 wins on tile-count alignment: the stride-8
# level's 80x80=6400 positions split into exactly 10 tiles (512 pads 12.5
# ->13) while staying small enough that the hit table still prunes.
# Process-start-only env overrides (like SPOTTER_TPU_MSDA_PRECISION) for
# hardware tile sweeps; values are baked into compiled programs.
S_TILE = int(os.environ.get("SPOTTER_TPU_MSDA_STILE", "640"))

# Optional finer tile for the FIRST (stride-8, densest) level only: its
# 80x80 span holds ~76% of positions, so a hit there compares a whole
# S_TILE (8 rows at 640) even when the query tile's samples span fewer
# rows. 0 = use S_TILE (default; the round-3 uniform sweep showed smaller
# GLOBAL tiles lose — this knob changes level 0 alone).
S_TILE0 = int(os.environ.get("SPOTTER_TPU_MSDA_STILE0", "0"))

# Locality sort ON by default: sorting queries by quantized mean sample
# position makes the block-sparse hit table prune (neighbor queries share
# source bands). SPOTTER_TPU_MSDA_SORT=0 uses the identity permutation —
# for hardware where the argsort + q-row permutes cost more than the
# sparsity saves (process-start-only knob like the tile sizes).
MSDA_SORT = os.environ.get("SPOTTER_TPU_MSDA_SORT", "1") != "0"


def _onehot_ref_math(rows, idx, w):
    """jnp reference for the one-hot kernel (VJP + interpret parity).

    rows: (BH, S, hd); idx/w: (BH, Qp, JC). Returns (BH, Qp, hd) fp32 —
    the kernel accumulates and emits fp32 regardless of the rows dtype.
    """
    bh, qp, jc = idx.shape
    hd = rows.shape[-1]
    flat = idx.reshape(bh, qp * jc, 1)
    g = jnp.take_along_axis(rows, flat, axis=1).reshape(bh, qp, jc, hd)
    return (g.astype(jnp.float32) * w[..., None].astype(jnp.float32)).sum(axis=2)


# --- block-sparse kernel: skip (query-tile, source-tile) pairs no sample
# hits. Queries are pre-sorted by spatial locality (dispatcher), so a tile
# of neighboring queries samples a narrow band of each level's source and
# most pairs are misses — the compare cost drops by the miss rate.

Q_TILE = int(os.environ.get("SPOTTER_TPU_MSDA_QTILE", "64"))

# Sub-query-tile sparsity (SPOTTER_TPU_MSDA_SG): the hit table says "some
# query in this 64-row tile touches source tile k", but a SINGLE query's
# 16 corners only span 1-2 source tiles — the sorted 64-query tile's span
# (~6 tiles on the stride-8 level; reference points, not offsets, dominate
# it) is what forces every hit tile to pay all 64 rows of compares. With
# SG=8 the one-hot build runs per 8-query sublane group, each predicated on
# its OWN hit bit (the mask becomes a bitfield over groups), writing its
# slice of a shared VMEM scratch tile; the MXU contraction still happens
# ONCE per source tile over the full 64-row tile, so dot count is
# unchanged while compare elements drop by the per-group miss rate
# (measured span statistics: ~2.5x fewer on the stride-8 level). 0 = off.
# Nested-select one-hot build (SPOTTER_TPU_MSDA_NEST=1): the 4 bilinear
# corners of ONE sample point are always 4 distinct cells, so their four
# (compare, select, add) chains can fold into a first-match select tree —
# 4 cmp + 4 sel + 1 add per point instead of 4x(cmp+sel+add), ~25% off
# the kernel's dominant op count. Exactness needs collision-free indices:
# a clamped out-of-bounds corner (weight 0) can alias an in-bounds
# neighbor's cell and would shadow its weight in first-match order, so
# the dispatcher rewrites every weight<=0 corner's index to a unique
# negative sentinel (never matches a column). Sum semantics are then
# identical; the VJP reference is unchanged.
MSDA_NEST = os.environ.get("SPOTTER_TPU_MSDA_NEST", "0") != "0"

MSDA_SG = int(os.environ.get("SPOTTER_TPU_MSDA_SG", "0"))
if MSDA_SG and (
    Q_TILE % MSDA_SG or MSDA_SG % 8 or Q_TILE // MSDA_SG > 32
):
    # <= 32 groups: the per-group hit bits live in ONE int32 mask entry
    raise ValueError(
        f"SPOTTER_TPU_MSDA_SG must be 0 or a multiple of 8 dividing "
        f"Q_TILE={Q_TILE} into at most 32 groups, got {MSDA_SG}"
    )
if (MSDA_SG or MSDA_NEST) and os.environ.get(
    MSDA_ENV, "auto"
).strip().lower() not in ("auto", "pallas"):
    # only the merged one-hot kernel on the XLA-prep path implements
    # subgroup masks / nested corner selects; silently no-op'ing a knob
    # would record a wrong A/B conclusion — exactly what the flags exist
    # to measure. (The PREP=kernel conflicts are checked below, after
    # MSDA_PREP is parsed.)
    raise ValueError(
        "SPOTTER_TPU_MSDA_SG/NEST require the merged one-hot backend "
        "(SPOTTER_TPU_MSDA=auto|pallas); other backends ignore them"
    )
if (MSDA_SG or MSDA_NEST) and os.environ.get(
    MSDA_ENV, "auto"
).strip().lower() == "auto":
    # ADVICE r5 #3: under `auto`, CPU/GPU hosts resolve to the XLA backend
    # and the knobs would be silently ignored — or, worse, abort every
    # forward if checked per call. Fail fast HERE, at import, where the
    # operator set the env; the call-time check below is reserved for
    # explicit per-call `backend=` overrides. (Exported knobs on a TPU host
    # still work: auto resolves to pallas there.)
    if jax.default_backend() != "tpu":
        raise ValueError(
            f"SPOTTER_TPU_MSDA_SG/NEST require the pallas backend, but "
            f"SPOTTER_TPU_MSDA=auto resolves to 'xla' on this "
            f"{jax.default_backend()!r} host — unset the knobs or run on TPU"
        )


def _mxu_precision() -> jax.lax.Precision:
    """MXU pass count for the one-hot contraction (SPOTTER_TPU_MSDA_PRECISION).

    "highest" (default): 6-pass fp32 — bit-faithful to the gather reference
    (kernel parity tests pin this). "default": single bf16 pass — the one-hot
    weights are bilinear coefficients in [0,1] and values are activations, so
    bf16 rounding costs ~1e-3 relative on sampled values; opt in when that
    drift is acceptable for the deployment.

    Read ONCE at import (module constant below) like the other env knobs:
    the value is baked into jit-compiled programs and is not part of any jit
    cache key, so changing the env after first trace could never take effect.

    Default follows the serving precision policy: SPOTTER_TPU_DTYPE of
    "mixed"/"bfloat16" already accepts bf16 rounding in the model, so the
    sampling contraction defaults to the 1-pass MXU there; fp32 policies
    keep the bit-faithful 6-pass default.
    """
    from spotter_tpu.utils.precision import DTYPE_ENV  # no heavy imports

    policy = os.environ.get(DTYPE_ENV, "").strip().lower()
    policy_default = (
        "default" if policy in ("mixed", "bfloat16", "bf16") else "highest"
    )
    name = (
        os.environ.get("SPOTTER_TPU_MSDA_PRECISION", policy_default)
        .strip()
        .lower()
    )
    table = {
        "highest": jax.lax.Precision.HIGHEST,
        "default": jax.lax.Precision.DEFAULT,
    }
    if name not in table:
        raise ValueError(
            f"Unsupported SPOTTER_TPU_MSDA_PRECISION={name!r}; "
            f"expected one of {sorted(table)}"
        )
    return table[name]


# process-start-only knob (see _mxu_precision docstring)
MSDA_MXU_PRECISION = _mxu_precision()




# --- separable bilinear kernel ("pallas_sep"): MXU work instead of compares.
#
# The one-hot kernel's cost is the tile BUILD: 4 corners x P points x
# (compare+select+add) over every (query, source) element — ~48 VPU ops per
# element, measured ~80% of the op's time (the MXU contraction is a minority).
# Bilinear weights are separable: w_corner = attn*(wy0|wy1)*(wx0|wx1), so per
# point the whole (Q, S) one-hot block factors into wy(Q, rows) (x) wx(Q, W).
# This kernel never builds the (Q, S) block at all:
#
#     g_p(q, r*hd)   = Wx_p(q, W) @ V_band(W, R*hd)          [MXU dot 1]
#     m_p            = g_p * WyExpand_p(q, R*hd)             [VPU, 2 compares]
#     out_p(q, hd)   = m_p @ SumBlock(R*hd, hd)              [MXU dot 2, 0/1]
#
# where V_band is the source band transposed to (W, R*hd) lanes r-major and
# SumBlock is the constant 0/1 matrix summing each row group. Compares drop
# from 16 full-width columns to 2 narrow + 2 full-width per point.
# Out-of-band rows and out-of-bounds corners match nothing (unclamped
# indices never equal an in-range lane id), so band masking and the
# zeros-padding sampling semantics fall out of the compares for free.
#
# Status: measured SLOWER than the merged one-hot kernel on v5e at R101
# decoder shapes (36 vs 24 ms per 6-layer stack at 1-pass precision — the
# per-cell dot issues, not the compares, dominate there), so `auto` never
# picks it; it stays as an explicit `SPOTTER_TPU_MSDA=pallas_sep` backend
# for re-evaluation on hardware where the trade flips.

SEP_R_BAND = 8  # rows per band when W <= 128; wider maps halve it


def _sep_band_kernel(
    mask_ref, xi_ref, xw0_ref, xw1_ref, yi_ref, yw0_ref, yw1_ref, v_ref,
    out_ref, *, w_level: int, r_band: int, n_points: int, precision,
):
    # All query-side blocks are point-STACKED columns (1, P*Q_TILE, 1) with
    # point p owning sublane rows [p*Q_TILE, (p+1)*Q_TILE). The whole cell
    # issues TWO dots — one (P*QT, W) x-contraction and one group-sum —
    # instead of 2*P small ones; matmul issue latency was the measured
    # bottleneck of the per-point variant.
    pqt = xi_ref.shape[1]
    qt = pqt // n_points
    hd = out_ref.shape[-1]
    i, nq, ns = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ns == 0)
    def _():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    @pl.when(mask_ref[i, nq, ns] != 0)
    def _():
        r0 = ns * r_band
        cx = jax.lax.broadcasted_iota(jnp.int32, (pqt, w_level), 1)
        x0 = xi_ref[0]  # (P*QT, 1) column, broadcast along lanes
        wx = jnp.where(cx == x0, xw0_ref[0], 0.0) + jnp.where(
            cx == x0 + 1, xw1_ref[0], 0.0
        )
        g = jnp.dot(
            wx, v_ref[0, 0], preferred_element_type=jnp.float32, precision=precision
        )  # (P*QT, R*hd)
        # lane r-id of the dot-1 output: lane = r*hd + hd_i
        lane_r = jax.lax.broadcasted_iota(jnp.int32, (pqt, r_band * hd), 1) // hd
        y0 = yi_ref[0] - r0
        wy = jnp.where(lane_r == y0, yw0_ref[0], 0.0) + jnp.where(
            lane_r == y0 + 1, yw1_ref[0], 0.0
        )
        m = g * wy
        acc = m[:qt]
        for p in range(1, n_points):  # static sublane slices: point group-sum
            acc = acc + m[p * qt : (p + 1) * qt]
        # constant 0/1 group-sum matrix (R*hd, hd): lane l feeds column l%hd
        sum_block = (
            jax.lax.broadcasted_iota(jnp.int32, (r_band * hd, hd), 0) % hd
            == jax.lax.broadcasted_iota(jnp.int32, (r_band * hd, hd), 1)
        ).astype(jnp.float32)
        out = jnp.dot(
            acc, sum_block, preferred_element_type=jnp.float32, precision=precision
        )
        out_ref[0] = out_ref[0] + out.astype(out_ref.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def pallas_sep_sampling(
    rows, xi, xw0, xw1, yi, yw0, yw1, mask,
    w_level: int, r_band: int, n_points: int, interpret: bool = False,
):
    """Separable bilinear sampling over one level (point-stacked layout).

    rows: (BH, n_bands, W, R*hd) band-transposed values; xi/yi:
    (BH, n_qt*P*Q_TILE, 1) int32 column-vector UNCLAMPED level-local x0/y0,
    point-major within each query tile; xw0/xw1/yw0/yw1: same-shape f32
    corner-pair weights (attn folded into the x pair, validity folded by
    zeroing); mask: (BH, n_qt, n_bands) int32 hit table. Returns
    (BH, n_qt*Q_TILE, hd) f32.
    """
    bh, n_bands, w_lvl, rhd = rows.shape
    hd = rhd // r_band
    n_qt = mask.shape[1]
    pqt = xi.shape[1] // n_qt
    qp = n_qt * (pqt // n_points)
    kernel = partial(
        _sep_band_kernel,
        w_level=w_level,
        r_band=r_band,
        n_points=n_points,
        precision=MSDA_MXU_PRECISION,
    )
    flops = 2 * bh * n_bands * (n_qt * pqt * w_lvl * rhd + qp * rhd * hd)
    _note_flops("msda_sep_band", flops)
    qblock = [
        pl.BlockSpec(
            (1, pqt, 1), lambda i, nq, s, *_: (i, nq, 0), memory_space=pltpu.VMEM
        )
        for _ in range(6)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_qt, n_bands),
        in_specs=qblock
        + [
            pl.BlockSpec(
                (1, 1, w_lvl, rhd), lambda i, nq, s, *_: (i, s, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, Q_TILE, hd), lambda i, nq, s, *_: (i, nq, 0),
            memory_space=pltpu.VMEM,
        ),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, qp, hd), jnp.float32),
        grid_spec=grid_spec,
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=rows.size * 4 * n_qt
            + (xi.size + yi.size) * 4
            + 4 * xw0.size * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(mask, xi, xw0, xw1, yi, yw0, yw1, rows)


def _sep_ref_math(rows, xi, xw0, xw1, yi, yw0, yw1, r_band, n_points):
    """jnp reference of the separable kernel (VJP + parity tests).

    Same contraction order (x-dot, y-weight, point sum, group sum in fp32),
    so under HIGHEST precision it matches the kernel bit-for-bit-ish.
    """
    bh, n_bands, w_lvl, rhd = rows.shape
    hd = rhd // r_band
    qt = Q_TILE
    n_qt = xi.shape[1] // (n_points * qt)
    cx = jnp.arange(w_lvl, dtype=jnp.int32)
    rr = jnp.arange(n_bands * r_band, dtype=jnp.int32)
    # rows (BH, bands, W, R, hd) -> (BH, bands*R rows, W, hd)
    v = rows.reshape(bh, n_bands, w_lvl, r_band, hd).transpose(0, 1, 3, 2, 4)
    v = v.reshape(bh, n_bands * r_band, w_lvl, hd)

    def unstack(a):  # (BH, n_qt*P*QT, 1) -> (BH, P, n_qt*QT)
        return a.reshape(bh, n_qt, n_points, qt).transpose(0, 2, 1, 3).reshape(
            bh, n_points, n_qt * qt
        )

    xi_u, yi_u = unstack(xi), unstack(yi)
    xw0_u, xw1_u = unstack(xw0), unstack(xw1)
    yw0_u, yw1_u = unstack(yw0), unstack(yw1)
    out = jnp.zeros((bh, n_qt * qt, hd), jnp.float32)
    for p in range(n_points):
        wx = (
            (cx[None, None, :] == xi_u[:, p, :, None]) * xw0_u[:, p, :, None]
            + (cx[None, None, :] == xi_u[:, p, :, None] + 1) * xw1_u[:, p, :, None]
        ).astype(jnp.float32)
        wy = (
            (rr[None, None, :] == yi_u[:, p, :, None]) * yw0_u[:, p, :, None]
            + (rr[None, None, :] == yi_u[:, p, :, None] + 1) * yw1_u[:, p, :, None]
        ).astype(jnp.float32)
        g = jnp.einsum("bqw,brwd->bqrd", wx, v)  # (BH, Qp, rows, hd)
        out = out + (g * wy[..., None]).sum(axis=2)
    return out


def _sep_fwd(rows, xi, xw0, xw1, yi, yw0, yw1, mask, w_level, r_band, n_points, interpret):
    return (
        pallas_sep_sampling(
            rows, xi, xw0, xw1, yi, yw0, yw1, mask, w_level, r_band, n_points, interpret
        ),
        (rows, xi, xw0, xw1, yi, yw0, yw1),
    )


def _sep_bwd(w_level, r_band, n_points, interpret, res, g):
    rows, xi, xw0, xw1, yi, yw0, yw1 = res
    _, vjp = jax.vjp(
        lambda r, a0, a1, b0, b1: _sep_ref_math(
            r, xi, a0, a1, yi, b0, b1, r_band, n_points
        ),
        rows, xw0, xw1, yw0, yw1,
    )
    d_rows, d_xw0, d_xw1, d_yw0, d_yw1 = vjp(g)
    return d_rows, None, d_xw0, d_xw1, None, d_yw0, d_yw1, None


pallas_sep_sampling.defvjp(_sep_fwd, _sep_bwd)


def _sep_level_dispatch(
    value_l,  # (BH, S_l, hd) this level's rows (unpadded)
    loc_l,  # (B, Q, H, P, 2) this level's sample points in [0, 1]
    attn_l,  # (B, Q, H, P)
    lh: int,
    lw: int,
    method: str,
    interpret: bool,
) -> jnp.ndarray:
    """Prepare separable operands for one level and run the kernel."""
    b, q, h_axis, pts, _ = loc_l.shape
    bh = b * h_axis
    hd = value_l.shape[-1]
    qp = -(-q // Q_TILE) * Q_TILE

    # band geometry: R_BAND rows per grid step, W on the dot's K axis
    r_band = SEP_R_BAND if lw <= 128 else max(1, SEP_R_BAND // 2)
    n_bands = -(-lh // r_band)

    attn_f = attn_l.astype(jnp.float32)
    if method == "discrete":
        # nearest-integer, border-clamped (RT-DETRv2 discrete semantics):
        # single active corner, always valid after the clamp
        x0 = jnp.clip(jnp.floor(loc_l[..., 0] * lw + 0.5), 0, lw - 1)
        y0 = jnp.clip(jnp.floor(loc_l[..., 1] * lh + 0.5), 0, lh - 1)
        xw0, xw1 = attn_f, jnp.zeros_like(attn_f)
        yw0, yw1 = jnp.ones_like(attn_f), jnp.zeros_like(attn_f)
    else:
        gx = loc_l[..., 0] * lw - 0.5
        gy = loc_l[..., 1] * lh - 0.5
        x0 = jnp.floor(gx)
        y0 = jnp.floor(gy)
        fx = (gx - x0).astype(jnp.float32)
        fy = (gy - y0).astype(jnp.float32)
        # validity folds into the weights; indices stay UNCLAMPED so an
        # out-of-bounds corner can never equal an in-range lane/row id
        vx0 = ((x0 >= 0) & (x0 <= lw - 1)).astype(jnp.float32)
        vx1 = (x0 + 1 <= lw - 1).astype(jnp.float32) * (x0 + 1 >= 0)
        vy0 = ((y0 >= 0) & (y0 <= lh - 1)).astype(jnp.float32)
        vy1 = (y0 + 1 <= lh - 1).astype(jnp.float32) * (y0 + 1 >= 0)
        xw0 = (1.0 - fx) * vx0 * attn_f  # attn folded into the x pair
        xw1 = fx * vx1 * attn_f
        yw0 = (1.0 - fy) * vy0
        yw1 = fy * vy1

    n_qt = qp // Q_TILE

    def stack(a, pad_value=0):  # (B, Q, H, P) -> (BH, n_qt, P*Q_TILE)
        a = a.transpose(0, 2, 1, 3).reshape(bh, q, pts)
        if qp != q:
            a = jnp.pad(
                a, ((0, 0), (0, qp - q), (0, 0)), constant_values=pad_value
            )
        # point-major within each query tile, as a column vector (the
        # kernel's (P*QT, 1) sublane layout)
        return a.reshape(bh, n_qt, Q_TILE, pts).transpose(0, 1, 3, 2).reshape(
            bh, n_qt * pts * Q_TILE, 1
        )

    xi = stack(x0.astype(jnp.int32), pad_value=-7)
    yi = stack(y0.astype(jnp.int32), pad_value=-7)
    xw0_s, xw1_s = stack(xw0), stack(xw1)
    yw0_s, yw1_s = stack(yw0), stack(yw1)

    # band-transposed values: (BH, S_l, hd) -> (BH, n_bands, W, R*hd) r-major
    h_pad = n_bands * r_band
    v = value_l.reshape(bh, lh, lw, hd)
    if h_pad != lh:
        v = jnp.pad(v, ((0, 0), (0, h_pad - lh), (0, 0), (0, 0)))
    v = v.reshape(bh, n_bands, r_band, lw, hd).transpose(0, 1, 3, 2, 4)
    rows = v.reshape(bh, n_bands, lw, r_band * hd)

    # hit table: which row bands does each query tile touch? (from y0/y0+1
    # where the corner weight can be nonzero — never suppresses a real hit).
    # A corner's y-weight gates BOTH its row candidates (wy0 -> y0 row,
    # wy1 -> y0+1 row); x-weights don't matter, the band spans the width.
    band_ids = jnp.arange(n_bands, dtype=jnp.int32)
    y_hits = [
        jnp.where(yw0_s > 0, yi // r_band, -1),
        jnp.where(yw1_s > 0, (yi + 1) // r_band, -1),
    ]
    # columns (BH, n_qt*P*QT, 1) -> per-query-tile rows (BH, n_qt, 2*P*QT)
    bands = jnp.concatenate(y_hits, axis=-1).reshape(bh, n_qt, -1)
    mask = (bands[..., None] == band_ids).any(axis=2).astype(jnp.int32)
    return pallas_sep_sampling(
        rows, xi, xw0_s, xw1_s, yi, yw0_s, yw1_s, mask, lw, r_band, pts, interpret
    )[:, :q]


# --- merged-level one-hot kernel: ONE pallas_call per MSDA op.
#
# Measured on v5e (R101 decoder shapes): each pallas_call costs ~0.9 ms of
# launch overhead and each grid step ~0.5 us even when the hit mask skips
# the body — with 3 per-level calls x 6 decoder layers, launches alone were
# ~16 ms of the ~30 ms sampling stack. This kernel runs every level's source
# tiles in one grid: the s axis walks the CONCATENATED per-level padded
# spans, index maps route the per-level (Q_TILE, jc) idx/w blocks by which
# level the s-step belongs to (static thresholds -> plain id arithmetic),
# and the output accumulates across all levels' steps, so the per-level
# partial sums come free.


def _onehot_merged_kernel(
    mask_ref, idx_ref, w_ref, v_ref, out_ref, *scratch,
    level_tiles: tuple, precision, subgroup: int = 0, nested: bool = False,
):
    # Grid is (bh, n_qt) ONLY: the s-walk over every level's tiles is a
    # static Python unroll over slices of the fully-fetched value block.
    # Measured on v5e, each pipelined grid step costs ~0.7 us of machinery
    # even when the hit mask skips the body — a (bh, n_qt, n_s) grid spent
    # ~3 ms/layer on machinery alone at R101 decoder shapes (4480 steps);
    # this layout pays it for 320. The s-loop being in-kernel also means the
    # value block is fetched once per (bh, nq), and each unrolled step knows
    # its level (and its level's tile size) STATICALLY. `level_tiles` is a
    # per-level (tile_size, span_count) tuple: finer tiles on the dense
    # stride-8 level shrink each hit's compare footprint without touching
    # the coarser levels (SPOTTER_TPU_MSDA_STILE0).
    #
    # `subgroup` (MSDA_SG): build the one-hot per SG-query sublane group,
    # each predicated on its own bit of the (bitfield) hit mask, into a
    # shared VMEM scratch tile; contract ONCE per source tile. Compare work
    # drops by the per-group miss rate; dot count is unchanged.
    qt, jc = idx_ref.shape[2], idx_ref.shape[3]
    i, nq = pl.program_id(0), pl.program_id(1)

    out_ref[0] = jnp.zeros_like(out_ref[0])
    step0 = 0
    v_off = 0
    for lvl, (ts, span) in enumerate(level_tiles):
        idx = idx_ref[0, lvl]
        w = w_ref[0, lvl]
        for k in range(span):
            ns = step0 + k

            @pl.when(mask_ref[i, nq, ns] != 0)
            def _(k=k, idx=idx, w=w, ts=ts, lo=v_off):
                def oh_chain(rows_sl):
                    """The one one-hot build over (rows, ts) at tile k —
                    shared verbatim by the full-tile and per-subgroup paths
                    so the two can never drift. `nested` folds each point's
                    4 corner chains into a first-match select tree (exact
                    under the dispatcher's sentinel-index rewrite — see
                    MSDA_NEST)."""
                    n_rows = idx[rows_sl].shape[0]
                    col = jax.lax.broadcasted_iota(
                        jnp.int32, (n_rows, ts), 1
                    ) + (k * ts)
                    oh = jnp.zeros((n_rows, ts), jnp.float32)
                    if nested:
                        points = jc // 4
                        for p in range(points):
                            sel = jnp.zeros((n_rows, ts), jnp.float32)
                            for c in reversed(range(4)):
                                j = c * points + p
                                sel = jnp.where(
                                    col == idx[rows_sl, j : j + 1],
                                    w[rows_sl, j : j + 1].astype(jnp.float32),
                                    sel,
                                )
                            oh = oh + sel
                        return oh
                    for j in range(jc):
                        oh = oh + jnp.where(
                            col == idx[rows_sl, j : j + 1],
                            w[rows_sl, j : j + 1].astype(jnp.float32),
                            0.0,
                        )
                    return oh

                if subgroup:
                    oh_ref = scratch[0]
                    oh_ref[:, :ts] = jnp.zeros((qt, ts), jnp.float32)
                    for g in range(qt // subgroup):

                        @pl.when(((mask_ref[i, nq, ns] >> g) & 1) != 0)
                        def _(g=g, ts=ts):
                            sl = slice(g * subgroup, (g + 1) * subgroup)
                            oh_ref[sl, :ts] = oh_chain(sl)

                    oh = oh_ref[:, :ts]
                else:
                    oh = oh_chain(slice(None))
                acc = jnp.dot(
                    oh,
                    v_ref[0, lo + k * ts : lo + (k + 1) * ts].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=precision,
                )
                out_ref[0] = out_ref[0] + acc.astype(out_ref.dtype)

        step0 += span
        v_off += ts * span


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def pallas_onehot_sampling_merged(
    rows, idx, w, mask, level_tiles: tuple, interpret: bool = False
):
    """Block-sparse one-hot sampling over ALL levels in one pallas_call.

    rows: (BH, s_cat, hd) — per-level spans each padded to their own tile
    multiple and concatenated; idx/w: (BH, L, Qp, jc) level-LOCAL corner
    indices/weights (invalid slots negative/zero); mask: (BH, Qp//Q_TILE,
    n_s_total) hit table over the concatenated s-steps; level_tiles: static
    per-level (tile_size, span_count) pairs (sum of tile*span = s_cat).
    Returns (BH, Qp, hd) fp32.
    """
    bh, s_cat, hd = rows.shape
    _, n_levels, qp, jc = idx.shape
    level_tiles = tuple((int(t), int(s)) for t, s in level_tiles)
    n_s = sum(span for _, span in level_tiles)
    n_qt = qp // Q_TILE
    assert sum(t * s for t, s in level_tiles) == s_cat, (level_tiles, s_cat)
    assert mask.shape[2] == n_s, (mask.shape, level_tiles)
    kernel = partial(
        _onehot_merged_kernel,
        level_tiles=level_tiles,
        precision=MSDA_MXU_PRECISION,
        subgroup=MSDA_SG,
        nested=MSDA_NEST,
    )
    scratch_shapes = (
        [pltpu.VMEM((Q_TILE, max(t for t, _ in level_tiles)), jnp.float32)]
        if MSDA_SG
        else []
    )
    flops = sum(
        2 * bh * span * (qp * ts * hd + jc * qp * ts) for ts, span in level_tiles
    )
    _note_flops("msda_onehot_merged", flops)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_qt),
        in_specs=[
            pl.BlockSpec(
                (1, n_levels, Q_TILE, jc),
                lambda i, nq, *_: (i, 0, nq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, n_levels, Q_TILE, jc),
                lambda i, nq, *_: (i, 0, nq, 0),
                memory_space=pltpu.VMEM,
            ),
            # the whole concatenated value block rides along per bh; the
            # index map ignores nq, so the pipeline fetches it once per i
            pl.BlockSpec(
                (1, s_cat, hd), lambda i, nq, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, Q_TILE, hd), lambda i, nq, *_: (i, nq, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=scratch_shapes,
    )
    if MSDA_NEST:
        # unique negative sentinels for match-incapable corners so a
        # clamped OOB corner can never shadow a sibling's cell in the
        # first-match select tree. Applied HERE (kernel-facing primal
        # only): the custom-VJP residuals keep the caller's true indices,
        # whose gather-backward needs the real corner cells even for
        # exactly-zero-weight corners (their d_w drives the loc gradient).
        sent = -1 - jnp.arange(jc, dtype=jnp.int32)
        idx = jnp.where(w > 0, idx, sent)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, qp, hd), jnp.float32),
        grid_spec=grid_spec,
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=rows.size * 4 + 2 * idx.size * 4 + mask.size * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(mask, idx, w, rows)


def _onehot_merged_ref(rows, idx, w, level_tiles):
    """Dense reference for the merged kernel (identical primal -> exact VJP)."""
    bh, _, hd = rows.shape
    out = None
    off = 0
    for lvl, (ts, span) in enumerate(level_tiles):
        rows_l = rows[:, off : off + ts * span]
        off += ts * span
        part = _onehot_ref_math(rows_l, idx[:, lvl], w[:, lvl])
        out = part if out is None else out + part
    return out


def _onehot_merged_fwd(rows, idx, w, mask, level_tiles, interpret):
    return (
        pallas_onehot_sampling_merged(rows, idx, w, mask, level_tiles, interpret),
        (rows, idx, w),
    )


def _onehot_merged_bwd(level_tiles, interpret, res, g):
    rows, idx, w = res
    _, vjp = jax.vjp(
        lambda r, ww: _onehot_merged_ref(r, idx, ww, level_tiles), rows, w
    )
    d_rows, d_w = vjp(g)
    return d_rows, None, d_w, None


pallas_onehot_sampling_merged.defvjp(_onehot_merged_fwd, _onehot_merged_bwd)


# --- in-kernel-prep variant (SPOTTER_TPU_MSDA_PREP=kernel): the corner
# decomposition (floor, bilinear weights, validity, level-local indices)
# moves INSIDE the kernel as ~45 VPU ops on one (Q_TILE, LP) lane group per
# grid cell, replacing the XLA-side prep passes over (B, H, Q, 4, LP)
# idx/w tensors (~0.3 ms/layer measured after the presort change). The hit
# table is built outside from the y coordinates alone — exact for every
# in-bounds corner when each level tile spans whole rows (ts % W == 0:
# tile_of(y0*W + x0) == y0 // rows_per_tile for any x0 < W), a superset
# otherwise only for out-of-bounds corners whose weight the kernel zeroes.
# Default stays "xla" until the on-chip A/B records a win.
#
# TRAINING caveat (ADVICE r3): this path's custom VJP backward runs the
# jnp gather reference (_loc_ref) plus a forward recompute, so under
# PREP=kernel the kernel's benefit exists in the FORWARD only — a training
# A/B that reads end-to-end step time would misattribute the gather-cost
# backward to the kernel. Serving (forward-only) is the intended consumer.

MSDA_PREP = os.environ.get("SPOTTER_TPU_MSDA_PREP", "xla").strip().lower()
if MSDA_PREP not in ("xla", "kernel", "fused"):
    raise ValueError(
        f"SPOTTER_TPU_MSDA_PREP must be xla|kernel|fused, got {MSDA_PREP!r}"
    )
if MSDA_SG and MSDA_PREP != "xla":
    # the loc-prep / fused-prologue kernels build their own hit logic (see
    # the SG guard at the MSDA_SG definition for why silent no-ops are
    # rejected)
    raise ValueError(
        "SPOTTER_TPU_MSDA_SG requires SPOTTER_TPU_MSDA_PREP=xla "
        "(the loc-prep/fused kernels do not implement subgroup hit bits)"
    )
if MSDA_NEST and MSDA_PREP != "xla":
    raise ValueError(
        "SPOTTER_TPU_MSDA_NEST requires SPOTTER_TPU_MSDA_PREP=xla "
        "(the loc-prep/fused kernels build their own corner chains)"
    )


def msda_prep_fused() -> bool:
    """True when the model layer should route deformable cross-attention
    through `deformable_sampling_fused` (SPOTTER_TPU_MSDA_PREP=fused): the
    sampling-offset / attention-weight projections + softmax + location
    arithmetic fold into the Pallas kernel's prologue, so the gather-heavy
    one-hot core runs as fewer, fatter dispatches (ISSUE 18 tentpole).
    Checked at trace time like the other knobs."""
    return MSDA_PREP == "fused"


def _note_flops(name: str, flops) -> None:
    """Report this dispatch's analytic FLOPs (the same formula handed to
    pl.CostEstimate) to the perf ledger's trace-time collector — XLA's
    cost_analysis counts pallas custom-calls as 0 FLOPs, so without this
    the MFU attribution under-reports every kernel-path program (ISSUE 18
    FLOPs honesty). Lazy import: obs must stay importable without jax."""
    from spotter_tpu.obs.perf import note_kernel_flops

    note_kernel_flops(name, flops)


def _onehot_merged_loc_kernel(
    mask_ref, xy_ref, attn_ref, v_ref, out_ref,
    *, level_tiles: tuple, level_dims: tuple, n_points: int, method: str, precision,
):
    qt, lp2 = xy_ref.shape[1], xy_ref.shape[2]
    lp = lp2 // 2
    i, nq = pl.program_id(0), pl.program_id(1)
    out_ref[0] = jnp.zeros_like(out_ref[0])

    step0 = 0
    v_off = 0
    for lvl, (ts, span) in enumerate(level_tiles):
        # per-level corner build with PYTHON-scalar dims (pallas kernels may
        # not capture trace-time array constants): ~45 VPU ops on a
        # (Q_TILE, P) block, once per grid cell per level
        lh, lw = level_dims[lvl]
        sl = slice(lvl * n_points, (lvl + 1) * n_points)
        corners = _corner_terms(
            xy_ref[0, :, sl],
            xy_ref[0, :, lp + lvl * n_points : lp + (lvl + 1) * n_points],
            attn_ref[0, :, sl],
            float(lw), float(lh), method,
        )
        for k in range(span):
            ns = step0 + k

            @pl.when(mask_ref[i, nq, ns] != 0)
            def _(k=k, ts=ts, lo=v_off, corners=corners):
                col = jax.lax.broadcasted_iota(jnp.int32, (qt, ts), 1) + (k * ts)
                oh = jnp.zeros((qt, ts), jnp.float32)
                for idxc, wgt in corners:
                    for p_ in range(idxc.shape[1]):
                        oh = oh + jnp.where(
                            col == idxc[:, p_ : p_ + 1], wgt[:, p_ : p_ + 1], 0.0
                        )
                acc = jnp.dot(
                    oh,
                    v_ref[0, lo + k * ts : lo + (k + 1) * ts].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=precision,
                )
                out_ref[0] = out_ref[0] + acc.astype(out_ref.dtype)

        step0 += span
        v_off += ts * span


def _loc_ref(rows, xy, attn_cols, level_tiles, level_dims, n_points, method):
    """jnp reference of the loc-prep kernel (VJP + interpret parity):
    rows (BH, s_cat, hd), xy (BH, Qp, 2*LP), attn_cols (BH, Qp, LP) ->
    (BH, Qp, hd) fp32."""
    lp = attn_cols.shape[-1]
    w_const = jnp.asarray(
        np.repeat([float(w) for (_, w) in level_dims], n_points)[None, None, :],
        jnp.float32,
    )
    h_const = jnp.asarray(
        np.repeat([float(h) for (h, _) in level_dims], n_points)[None, None, :],
        jnp.float32,
    )
    corners = _corner_terms(
        xy[..., :lp], xy[..., lp:], attn_cols, w_const, h_const, method
    )
    offs_cat = np.concatenate(
        [[0], np.cumsum([ts * span for ts, span in level_tiles])[:-1]]
    ).astype(np.int32)
    lane_off = jnp.asarray(
        np.repeat(offs_cat, n_points)[None, None, :], jnp.int32
    )
    out = None
    for idxc, wgt in corners:
        g = jnp.take_along_axis(
            rows.astype(jnp.float32),
            (idxc + lane_off).reshape(rows.shape[0], -1, 1),
            axis=1,
        ).reshape(*idxc.shape, rows.shape[-1])
        term = (g * wgt[..., None]).sum(axis=2)
        out = term if out is None else out + term
    return out


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def pallas_onehot_sampling_merged_loc(
    rows, xy, attn_cols, mask,
    level_tiles: tuple, level_dims: tuple, n_points: int, method: str,
    interpret: bool = False,
):
    """Loc-prep merged kernel: corner decomposition happens in-kernel.

    rows: (BH, s_cat, hd) as in `pallas_onehot_sampling_merged`; xy:
    (BH, Qp, 2*LP) normalized sample coords, x lanes then y lanes, level-
    major points within each half; attn_cols: (BH, Qp, LP); mask as before.
    Padded query rows must carry zero attention (their corner weights then
    vanish regardless of where their zero coords land).
    """
    bh, s_cat, hd = rows.shape
    qp = xy.shape[1]
    level_tiles = tuple((int(t), int(s)) for t, s in level_tiles)
    level_dims = tuple((int(h), int(w)) for h, w in level_dims)
    n_s = sum(span for _, span in level_tiles)
    n_qt = qp // Q_TILE
    lp = attn_cols.shape[-1]
    assert sum(t * s for t, s in level_tiles) == s_cat, (level_tiles, s_cat)
    assert mask.shape[2] == n_s, (mask.shape, level_tiles)
    kernel = partial(
        _onehot_merged_loc_kernel,
        level_tiles=level_tiles,
        level_dims=level_dims,
        n_points=n_points,
        method=method,
        precision=MSDA_MXU_PRECISION,
    )
    jc = (1 if method == "discrete" else 4) * n_points
    flops = sum(
        2 * bh * span * (qp * ts * hd + jc * qp * ts) for ts, span in level_tiles
    )
    _note_flops("msda_onehot_merged_loc", flops)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_qt),
        in_specs=[
            pl.BlockSpec(
                (1, Q_TILE, 2 * lp),
                lambda i, nq, *_: (i, nq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, Q_TILE, lp),
                lambda i, nq, *_: (i, nq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, s_cat, hd), lambda i, nq, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, Q_TILE, hd), lambda i, nq, *_: (i, nq, 0),
            memory_space=pltpu.VMEM,
        ),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, qp, hd), jnp.float32),
        grid_spec=grid_spec,
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=rows.size * 4 + xy.size * 4 + attn_cols.size * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(mask, xy, attn_cols, rows)


def _loc_fwd(rows, xy, attn_cols, mask, level_tiles, level_dims, n_points, method, interpret):
    out = pallas_onehot_sampling_merged_loc(
        rows, xy, attn_cols, mask, level_tiles, level_dims, n_points, method, interpret
    )
    return out, (rows, xy, attn_cols)


def _loc_bwd(level_tiles, level_dims, n_points, method, interpret, res, g):
    rows, xy, attn_cols = res
    _, vjp = jax.vjp(
        lambda r, x, a: _loc_ref(r, x, a, level_tiles, level_dims, n_points, method),
        rows, xy, attn_cols,
    )
    d_rows, d_xy, d_attn = vjp(g)
    return d_rows.astype(rows.dtype), d_xy, d_attn, None


pallas_onehot_sampling_merged_loc.defvjp(_loc_fwd, _loc_bwd)


def _onehot_merged_fused_kernel(
    hs_ref, woff_ref, boff_ref, watt_ref, batt_ref, base_ref, scale_ref,
    v_ref, out_ref,
    *, level_tiles: tuple, level_dims: tuple, n_points: int, method: str,
    precision,
):
    """Fused-prologue variant of `_onehot_merged_loc_kernel`: the sampling-
    offset and attention-weight projections, the per-head softmax, and the
    location arithmetic all run in the kernel's prologue, so the op consumes
    raw decoder hidden states instead of precomputed coords.

    Per grid cell (bh, nq): two small MXU dots against this head's weight
    slices (hs_tile @ w_off -> offsets, hs_tile @ w_att -> logits), a
    row-softmax over the LP lanes, xy = base + offs * scale, then the same
    corner build + one-hot MXU walk as the loc kernel. The per-head split
    does no redundant projection work — the unfused Dense computes all H
    heads at once; here each grid cell computes exactly its own head's
    slice. The hit test is DYNAMIC (computed from the in-kernel corner
    indices) because sample locations do not exist outside the kernel.
    """
    qt = hs_ref.shape[1]
    lp = watt_ref.shape[2]
    out_ref[0] = jnp.zeros_like(out_ref[0])

    hs = hs_ref[0].astype(jnp.float32)  # (Q_TILE, D)
    offs = (
        jnp.dot(
            hs, woff_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32, precision=precision,
        )
        + boff_ref[0].astype(jnp.float32)
    )
    xy = base_ref[0].astype(jnp.float32) + offs * scale_ref[0].astype(jnp.float32)
    logits = (
        jnp.dot(
            hs, watt_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32, precision=precision,
        )
        + batt_ref[0].astype(jnp.float32)
    )
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits)
    at = e / jnp.sum(e, axis=-1, keepdims=True)  # (Q_TILE, LP)

    v_off = 0
    for lvl, (ts, span) in enumerate(level_tiles):
        lh, lw = level_dims[lvl]
        sl = slice(lvl * n_points, (lvl + 1) * n_points)
        corners = _corner_terms(
            xy[:, sl],
            xy[:, lp + lvl * n_points : lp + (lvl + 1) * n_points],
            at[:, sl],
            float(lw), float(lh), method,
        )
        # dynamic block-sparsity: a source tile is visited only if some
        # corner of some query in this Q_TILE lands in it (zero-weight
        # corners excluded — skipping them changes nothing)
        tiles_of = [jnp.where(wgt > 0, idxc // ts, -1) for idxc, wgt in corners]
        for k in range(span):
            hit = tiles_of[0] == k
            for t in tiles_of[1:]:
                hit = hit | (t == k)

            @pl.when(jnp.any(hit))
            def _(k=k, ts=ts, lo=v_off, corners=corners):
                col = jax.lax.broadcasted_iota(jnp.int32, (qt, ts), 1) + (k * ts)
                oh = jnp.zeros((qt, ts), jnp.float32)
                for idxc, wgt in corners:
                    for p_ in range(idxc.shape[1]):
                        oh = oh + jnp.where(
                            col == idxc[:, p_ : p_ + 1], wgt[:, p_ : p_ + 1], 0.0
                        )
                acc = jnp.dot(
                    oh,
                    v_ref[0, lo + k * ts : lo + (k + 1) * ts].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=precision,
                )
                out_ref[0] = out_ref[0] + acc.astype(out_ref.dtype)

        v_off += ts * span


def _fused_ref(
    rows, hs, w_off, b_off, w_att, b_att, base, scale,
    level_tiles, level_dims, n_points, method,
):
    """jnp reference of the fused-prologue kernel (VJP + interpret parity):
    prologue in einsum form, core through `_loc_ref`. rows (BH, s_cat, hd),
    hs (B, Qp, D), w_off (H, D, 2*LP), b_off (H, 1, 2*LP), w_att (H, D, LP),
    b_att (H, 1, LP), base/scale (B, Qp, 2*LP) -> (BH, Qp, hd) fp32."""
    h_axis = w_off.shape[0]
    b, qp, _ = hs.shape
    lp = w_att.shape[-1]
    hs32 = hs.astype(jnp.float32)
    offs = (
        jnp.einsum("bqd,hdl->bhql", hs32, w_off.astype(jnp.float32))
        + b_off.astype(jnp.float32)[None]
    )
    xy = base[:, None] + offs * scale[:, None]  # (B, H, Qp, 2*LP)
    logits = (
        jnp.einsum("bqd,hdl->bhql", hs32, w_att.astype(jnp.float32))
        + b_att.astype(jnp.float32)[None]
    )
    at = jax.nn.softmax(logits, axis=-1)
    return _loc_ref(
        rows,
        xy.reshape(b * h_axis, qp, 2 * lp),
        at.reshape(b * h_axis, qp, lp),
        level_tiles, level_dims, n_points, method,
    )


@partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12))
def pallas_msda_fused(
    rows, hs, w_off, b_off, w_att, b_att, base, scale,
    level_tiles: tuple, level_dims: tuple, n_points: int, method: str,
    interpret: bool = False,
):
    """Fused-prologue merged kernel (SPOTTER_TPU_MSDA_PREP=fused).

    rows: (BH, s_cat, hd) per-level-padded concatenation as in the other
    merged kernels; hs: (B, Qp, D) decoder hidden states (query + pos),
    zero-padded rows beyond the real query count; w_off/b_off, w_att/b_att:
    per-head weight slices pre-permuted by `deformable_sampling_fused` into
    the kernel's x-lanes-then-y-lanes layout; base/scale: (B, Qp, 2*LP)
    reference-point anchors so xy = base + (hs @ w_off + b_off) * scale.
    Padded query rows carry zero hs/base/scale: their coords collapse to 0
    (in-bounds, garbage-but-finite) and their output rows are discarded by
    the caller's [:, :q] slice; the VJP sees zero cotangent for them.
    """
    bh, s_cat, hd = rows.shape
    b, qp, d = hs.shape
    h_axis = w_off.shape[0]
    lp = w_att.shape[-1]
    level_tiles = tuple((int(t), int(s)) for t, s in level_tiles)
    level_dims = tuple((int(h), int(w)) for h, w in level_dims)
    n_qt = qp // Q_TILE
    assert bh == b * h_axis, (rows.shape, hs.shape, w_off.shape)
    assert sum(t * s for t, s in level_tiles) == s_cat, (level_tiles, s_cat)
    kernel = partial(
        _onehot_merged_fused_kernel,
        level_tiles=level_tiles,
        level_dims=level_dims,
        n_points=n_points,
        method=method,
        precision=MSDA_MXU_PRECISION,
    )
    jc = (1 if method == "discrete" else 4) * n_points
    flops = 2 * bh * qp * d * 3 * lp + sum(  # prologue dots + one-hot core
        2 * bh * span * (qp * ts * hd + jc * qp * ts) for ts, span in level_tiles
    )
    _note_flops("msda_fused", flops)
    h = h_axis  # python int, closed over by the index maps
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, qp, hd), jnp.float32),
        grid=(bh, n_qt),
        in_specs=[
            pl.BlockSpec(
                (1, Q_TILE, d), lambda i, nq: (i // h, nq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, d, 2 * lp), lambda i, nq: (i % h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, 2 * lp), lambda i, nq: (i % h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, d, lp), lambda i, nq: (i % h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, lp), lambda i, nq: (i % h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, Q_TILE, 2 * lp), lambda i, nq: (i // h, nq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, Q_TILE, 2 * lp), lambda i, nq: (i // h, nq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, s_cat, hd), lambda i, nq: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, Q_TILE, hd), lambda i, nq: (i, nq, 0),
            memory_space=pltpu.VMEM,
        ),
        cost_estimate=pl.CostEstimate(
            flops=flops,
            bytes_accessed=(
                rows.size * 4
                + hs.size * 4 * h_axis  # each head re-reads the hs tile
                + (w_off.size + w_att.size) * 4 * n_qt
                + (base.size + scale.size) * 4 * h_axis
            ),
            transcendentals=bh * qp * lp,
        ),
        interpret=interpret,
    )(hs, w_off, b_off, w_att, b_att, base, scale, rows)


def _fused_fwd(
    rows, hs, w_off, b_off, w_att, b_att, base, scale,
    level_tiles, level_dims, n_points, method, interpret,
):
    out = pallas_msda_fused(
        rows, hs, w_off, b_off, w_att, b_att, base, scale,
        level_tiles, level_dims, n_points, method, interpret,
    )
    return out, (rows, hs, w_off, b_off, w_att, b_att, base, scale)


def _fused_bwd(level_tiles, level_dims, n_points, method, interpret, res, g):
    rows, hs, w_off, b_off, w_att, b_att, base, scale = res
    _, vjp = jax.vjp(
        lambda r, q_, wo, bo, wa, ba, bs, sc: _fused_ref(
            r, q_, wo, bo, wa, ba, bs, sc,
            level_tiles, level_dims, n_points, method,
        ),
        rows, hs, w_off, b_off, w_att, b_att, base, scale,
    )
    d_rows, d_hs, d_wo, d_bo, d_wa, d_ba, d_base, d_scale = vjp(g)
    return (
        d_rows.astype(rows.dtype), d_hs.astype(hs.dtype),
        d_wo.astype(w_off.dtype), d_bo.astype(b_off.dtype),
        d_wa.astype(w_att.dtype), d_ba.astype(b_att.dtype),
        d_base, d_scale,
    )


pallas_msda_fused.defvjp(_fused_fwd, _fused_bwd)


def deformable_sampling(
    value: jnp.ndarray,  # (B, S, H, hd)
    loc: jnp.ndarray,  # (B, Q, H, LP, 2) in [0, 1]
    attn: jnp.ndarray,  # (B, Q, H, LP)
    spatial_shapes: tuple[tuple[int, int], ...],
    num_points: int,
    method: str = "default",
    backend: str | None = None,
    interpret: bool | None = None,
    presorted: bool = False,
) -> jnp.ndarray:
    """Full MSDA core: returns (B, Q, H*hd) aggregated values.

    Backends (module docstring): "pallas" = gather-free one-hot MXU kernel
    (auto on TPU), "xla" = row-gather math (auto elsewhere, VJP reference),
    "pallas_gather" = experimental lane-gather kernel. `interpret=True`
    forces kernel interpret mode (CPU tests). `presorted=True` promises the
    queries already arrive ordered by `locality_sort_key` (see
    `presort_wanted`), so the kernel branches skip the in-op sort and the
    two q-row permutes; hit tables are still built from the actual indices,
    so a broken promise only costs sparsity, never correctness.
    """
    b, s, h_axis, hd = value.shape
    q = loc.shape[1]
    lp = loc.shape[3]

    chosen = msda_backend(backend, batch_heads=b * h_axis)
    if (MSDA_SG or MSDA_NEST) and backend is not None and chosen != "pallas":
        # Same contract as the import-time env guards (above, after the
        # MSDA_SG parse) but scoped to EXPLICIT per-call `backend=`
        # overrides, so e.g. an A/B harness with SPOTTER_TPU_MSDA_SG=8 over
        # backends pallas,pallas_sep cannot silently no-op the knobs and
        # record a wrong A/B conclusion. Auto resolution is NOT re-checked
        # here: the import-time guard already rejected hosts where auto
        # cannot mean pallas (ADVICE r5 #3 — the old resolved-backend check
        # aborted every CPU/GPU forward under exported knobs).
        raise ValueError(
            f"SPOTTER_TPU_MSDA_SG/NEST apply only to the merged one-hot "
            f"backend; this call's explicit backend={chosen!r} override "
            f"would silently ignore them"
        )
    interp = bool(interpret) if interpret is not None else False

    def locality_perm():
        """Quantized mean-sample-position sort key, y-major (source tiles
        are horizontal bands of each level's row-major span). Shared by both
        kernel backends so their tiling behavior can't desynchronize.
        (None, None) when MSDA_SORT is off or the caller presorted —
        callers skip the permutes entirely (the sort is a sparsity
        heuristic, never a correctness requirement)."""
        if presorted or not MSDA_SORT:
            return None, None
        mean_xy = loc.mean(axis=(2, 3))  # (B, Q, 2) in [0, 1]
        key = locality_sort_key(mean_xy)
        p = jnp.argsort(key, axis=1)  # (B, Q)
        return p, jnp.argsort(p, axis=1)

    def corner_idx_w():
        """Lazy XLA-side corner prep — (B, H, LP, Q) head-major layout.
        Skipped entirely by the backends that do their own decomposition
        (pallas_sep; pallas under MSDA_PREP=kernel)."""
        loc_t = loc.transpose(0, 2, 3, 1, 4)
        attn_t = attn.transpose(0, 2, 3, 1)
        return prepare_msda_gather(loc_t, attn_t, spatial_shapes, num_points, method)

    if chosen == "pallas_sep":
        # Separable bilinear kernel, one call per level (level-split as in
        # the one-hot kernel). Sorted queries make a Q_TILE of neighbors
        # touch few row bands, so the hit table prunes; the sort/unsort are
        # two Q-row permutes.
        perm, inv_perm = locality_perm()
        loc_s, attn_s = loc, attn
        if perm is not None:
            loc_s = jnp.take_along_axis(loc, perm[:, :, None, None, None], axis=1)
            attn_s = jnp.take_along_axis(attn, perm[:, :, None, None], axis=1)

        rows_all = value.transpose(0, 2, 1, 3).reshape(b * h_axis, s, hd)
        offs = _level_offsets(spatial_shapes)
        out = None
        for lvl, (lh, lw) in enumerate(spatial_shapes):
            part = _sep_level_dispatch(
                rows_all[:, offs[lvl] : offs[lvl] + lh * lw],
                loc_s[:, :, :, lvl * num_points : (lvl + 1) * num_points, :],
                attn_s[:, :, :, lvl * num_points : (lvl + 1) * num_points],
                lh,
                lw,
                method,
                interp,
            )
            out = part if out is None else out + part
        out = out.reshape(b, h_axis, q, hd)
        if inv_perm is not None:
            out = jnp.take_along_axis(out, inv_perm[:, None, :, None], axis=2)
        return out.transpose(0, 2, 1, 3).reshape(b, q, h_axis * hd)
    if chosen == "pallas":
        # Level-split: a sample only ever lands inside its own level's span
        # of the flat source (block-diagonal one-hot), so each per-level
        # kernel call compares its 4*P sample columns against that level's
        # positions only — a ~3x compare reduction vs one dense call (the
        # stride-8 level holds ~76% of positions but only 1/3 of samples).
        # Block-sparsity on top: queries sorted by spatial locality so a
        # Q_TILE of neighbors samples a narrow band of each level, and the
        # kernel skips (query-tile, source-tile) pairs with no hit.
        jc = 4 * lp
        qp = -(-q // Q_TILE) * Q_TILE
        perm, inv_perm = locality_perm()

        if MSDA_PREP == "kernel" and all(
            ((S_TILE0 if (lvl == 0 and S_TILE0) else S_TILE) % lw) == 0
            for lvl, (lh, lw) in enumerate(spatial_shapes)
        ):
            # In-kernel corner prep (module comment at MSDA_PREP): ship raw
            # coords + attention; the y-only hit table is exact for every
            # in-bounds corner because each level tile spans whole rows.
            loc_s, attn_s = loc, attn
            if perm is not None:
                loc_s = jnp.take_along_axis(loc, perm[:, :, None, None, None], axis=1)
                attn_s = jnp.take_along_axis(attn, perm[:, :, None, None], axis=1)
            loc_bh = loc_s.transpose(0, 2, 1, 3, 4).reshape(b * h_axis, q, lp, 2)
            xy = jnp.concatenate(
                [loc_bh[..., 0], loc_bh[..., 1]], axis=-1
            ).astype(jnp.float32)
            at_bh = (
                attn_s.transpose(0, 2, 1, 3)
                .reshape(b * h_axis, q, lp)
                .astype(jnp.float32)
            )
            if qp != q:  # padded queries: zero attention -> zero weights
                xy = jnp.pad(xy, ((0, 0), (0, qp - q), (0, 0)))
                at_bh = jnp.pad(at_bh, ((0, 0), (0, qp - q), (0, 0)))

            rows_all = value.transpose(0, 2, 1, 3).reshape(b * h_axis, s, hd)
            offs = _level_offsets(spatial_shapes)
            points = num_points
            n_qt = qp // Q_TILE
            ys_cols = xy[:, :, lp:]
            rows_cat, masks, tiles = [], [], []
            for lvl, (lh, lw) in enumerate(spatial_shapes):
                ts = S_TILE0 if (lvl == 0 and S_TILE0) else S_TILE
                s_l = lh * lw
                rows_l = rows_all[:, offs[lvl] : offs[lvl] + s_l]
                s_pad = -(-s_l // ts) * ts
                if s_pad != s_l:
                    rows_l = jnp.pad(rows_l, ((0, 0), (0, s_pad - s_l), (0, 0)))
                n_s = s_pad // ts
                rpt = ts // lw  # rows per tile (whole rows by the guard)
                y_l = ys_cols[:, :, lvl * points : (lvl + 1) * points]
                if method == "discrete":
                    cy = jnp.clip(
                        jnp.floor(y_l * lh + 0.5).astype(jnp.int32), 0, lh - 1
                    )
                    cand = [cy // rpt]
                else:
                    y0 = jnp.floor(y_l * lh - 0.5).astype(jnp.int32)
                    cand = [
                        jnp.where((y0 >= 0) & (y0 <= lh - 1), y0 // rpt, -1),
                        jnp.where(
                            (y0 + 1 >= 0) & (y0 + 1 <= lh - 1), (y0 + 1) // rpt, -1
                        ),
                    ]
                bands = jnp.concatenate(cand, axis=-1).reshape(
                    b * h_axis, n_qt, -1
                )
                mask = (
                    (bands[..., None] == jnp.arange(n_s, dtype=jnp.int32))
                    .any(axis=2)
                    .astype(jnp.int32)
                )
                rows_cat.append(rows_l)
                masks.append(mask)
                tiles.append((ts, n_s))
            out = pallas_onehot_sampling_merged_loc(
                jnp.concatenate(rows_cat, axis=1),
                xy,
                at_bh,
                jnp.concatenate(masks, axis=2),
                tuple(tiles),
                tuple(spatial_shapes),
                points,
                method,
                interp,
            )
            out = out[:, :q].reshape(b, h_axis, q, hd)
            if inv_perm is not None:
                out = jnp.take_along_axis(out, inv_perm[:, None, :, None], axis=2)
            return out.transpose(0, 2, 1, 3).reshape(b, q, h_axis * hd)

        idx, w = corner_idx_w()
        idx_q = idx.reshape(b, h_axis, 4, lp, q).transpose(0, 1, 4, 2, 3)
        w_q = w.reshape(b, h_axis, 4, lp, q).transpose(0, 1, 4, 2, 3)
        if perm is not None:
            psel = perm[:, None, :, None, None]
            idx_q = jnp.take_along_axis(idx_q, psel, axis=2)
            w_q = jnp.take_along_axis(w_q, psel, axis=2)
        idx_q = idx_q.reshape(b * h_axis, q, jc)
        w_q = w_q.reshape(b * h_axis, q, jc)
        if qp != q:  # padded queries: idx 0, weight 0 -> zero rows, no hits
            idx_q = jnp.pad(idx_q, ((0, 0), (0, qp - q), (0, 0)))
            w_q = jnp.pad(w_q, ((0, 0), (0, qp - q), (0, 0)))

        rows_all = value.transpose(0, 2, 1, 3).reshape(b * h_axis, s, hd)
        offs = _level_offsets(spatial_shapes)
        points = lp // len(spatial_shapes)
        n_qt = qp // Q_TILE
        # Per-level blocks, all feeding ONE merged pallas_call (launch
        # overhead per call is ~0.9 ms on v5e — one call per op, not per
        # level): each level's span padded to its OWN tile multiple and
        # concatenated, per-level idx/w stacked, hit masks concatenated
        # along the s-step axis. The first (densest, stride-8) level may
        # take a finer tile via SPOTTER_TPU_MSDA_STILE0: its rows-per-tile
        # footprint shrinks, cutting each hit's compare cost without
        # touching the coarser levels.
        rows_cat, idx_levels, w_levels, masks, tiles = [], [], [], [], []
        for lvl, (lh, lw) in enumerate(spatial_shapes):
            ts = S_TILE0 if (lvl == 0 and S_TILE0) else S_TILE
            s_l = lh * lw
            rows_l = rows_all[:, offs[lvl] : offs[lvl] + s_l]
            s_pad = -(-s_l // ts) * ts
            if s_pad != s_l:
                rows_l = jnp.pad(rows_l, ((0, 0), (0, s_pad - s_l), (0, 0)))
            cols = [
                c * lp + lvl * points + p for c in range(4) for p in range(points)
            ]
            # level-local indices; padded/invalid slots (global idx 0, w 0)
            # may go negative here — they simply never match a column.
            # (MSDA_NEST's sentinel rewrite happens INSIDE the kernel
            # wrapper's primal so the VJP residuals keep the true indices —
            # the gather-based backward must read the real corner cells
            # even for exactly-zero-weight corners, whose d_w feeds the
            # location gradient.)
            idx_l = idx_q[:, :, cols] - np.int32(offs[lvl])
            w_l = w_q[:, :, cols]
            # hit mask: which source tiles does each query tile touch?
            # Under MSDA_SG the mask is a BITFIELD: bit g set iff sublane
            # group g (queries [g*SG, (g+1)*SG)) has a corner in the tile;
            # "any bit set" keeps the same outer skip condition.
            n_s = s_pad // ts
            tile_of = jnp.where(w_l > 0, idx_l // ts, -1)  # (BH, Qp, JCl)
            hits = tile_of[..., None] == jnp.arange(n_s, dtype=jnp.int32)
            if MSDA_SG:
                n_g = Q_TILE // MSDA_SG
                hits_g = hits.reshape(
                    b * h_axis, n_qt, n_g, MSDA_SG, len(cols), n_s
                ).any(axis=(3, 4))
                bits = jnp.left_shift(
                    hits_g.astype(jnp.int32),
                    jnp.arange(n_g, dtype=jnp.int32)[None, None, :, None],
                )
                mask = bits.sum(axis=2)
            else:
                mask = (
                    hits.reshape(b * h_axis, n_qt, Q_TILE, len(cols), n_s)
                    .any(axis=(2, 3))
                    .astype(jnp.int32)
                )
            rows_cat.append(rows_l)
            idx_levels.append(idx_l)
            w_levels.append(w_l)
            masks.append(mask)
            tiles.append((ts, n_s))
        out = pallas_onehot_sampling_merged(
            jnp.concatenate(rows_cat, axis=1),
            jnp.stack(idx_levels, axis=1),
            jnp.stack(w_levels, axis=1),
            jnp.concatenate(masks, axis=2),
            tuple(tiles),
            interp,
        )
        out = out[:, :q].reshape(b, h_axis, q, hd)
        if inv_perm is not None:
            out = jnp.take_along_axis(out, inv_perm[:, None, :, None], axis=2)
        return out.transpose(0, 2, 1, 3).reshape(b, q, h_axis * hd)
    if chosen == "pallas_gather":
        idx, w = corner_idx_w()
        vt = value.transpose(0, 2, 3, 1)  # (B, H, hd, S): spatial on lanes
        out = pallas_deformable_sampling(vt, idx, w, lp, q, interp)
        # (B, H, hd, Q) -> (B, Q, H*hd)
        return out.transpose(0, 3, 1, 2).reshape(b, q, h_axis * hd)
    idx, w = corner_idx_w()
    rows = value.transpose(0, 2, 1, 3)  # (B, H, S, hd): row gathers for XLA
    out = _row_gather_weighted_sum(rows, idx, w, lp, q)  # (B, H, Q, hd)
    return out.transpose(0, 2, 1, 3).reshape(b, q, h_axis * hd)


def deformable_sampling_fused(
    value: jnp.ndarray,  # (B, S, H, hd)
    hs: jnp.ndarray,  # (B, Q, D) decoder hidden states (query + pos embed)
    reference_points: jnp.ndarray,  # (B, Q, 4) normalized cxcywh
    w_off: jnp.ndarray,  # (D, H*LP*2) sampling_offsets Dense kernel
    b_off: jnp.ndarray,  # (H*LP*2,)
    w_att: jnp.ndarray,  # (D, H*LP) attention_weights Dense kernel
    b_att: jnp.ndarray,  # (H*LP,)
    spatial_shapes: tuple[tuple[int, int], ...],
    num_points: int,
    offset_scale: float = 0.5,
    method: str = "default",
    backend: str | None = None,
    interpret: bool | None = None,
    presorted: bool = False,
) -> jnp.ndarray:
    """MSDA with the projection/softmax/location prologue fused into the
    kernel (SPOTTER_TPU_MSDA_PREP=fused): the model layer hands over raw
    hidden states + the offset/attention Dense params instead of computing
    offsets and attention weights in XLA. Returns (B, Q, H*hd).

    Weight layout contract: w_off/b_off and w_att/b_att arrive in the plain
    `nn.Dense` layout (the model declares them via `DenseParams` at the
    same param paths, so checkpoints are interchangeable with the unfused
    path); this wrapper pre-permutes them into per-head x-lanes-then-y-lanes
    slices once per trace — a cheap (D, H*LP*2) shuffle that XLA folds into
    the weight constant.

    There is no in-op locality sort on this path (sample locations do not
    exist before the kernel runs): callers that want sorted queries must
    presort (`presorted=True`, see `presort_wanted`). Non-pallas backends
    and CPU hosts fall back to the einsum prologue + `deformable_sampling`,
    which is also the VJP reference — so the fused path keeps the xla
    bit-parity contract of the other kernel backends.
    """
    b, s, h_axis, hd = value.shape
    q = hs.shape[1]
    d = hs.shape[2]
    lp = len(spatial_shapes) * num_points

    # nn.Dense layout -> per-head kernel layout (x lanes then y lanes,
    # level-major points within each half, matching the loc kernel's xy)
    w_off_h = (
        w_off.reshape(d, h_axis, lp, 2)
        .transpose(1, 0, 3, 2)
        .reshape(h_axis, d, 2 * lp)
    )
    b_off_h = b_off.reshape(h_axis, lp, 2).transpose(0, 2, 1).reshape(h_axis, 1, 2 * lp)
    w_att_h = w_att.reshape(d, h_axis, lp).transpose(1, 0, 2)
    b_att_h = b_att.reshape(h_axis, lp)[:, None, :]

    # reference-point anchors: xy = base + offs * scale, per lane
    ref_xy = reference_points[..., :2].astype(jnp.float32)
    ref_wh = reference_points[..., 2:].astype(jnp.float32)
    ps = np.float32(offset_scale / num_points)
    base = jnp.concatenate(
        [
            jnp.broadcast_to(ref_xy[..., 0:1], (b, q, lp)),
            jnp.broadcast_to(ref_xy[..., 1:2], (b, q, lp)),
        ],
        axis=-1,
    )
    scale = jnp.concatenate(
        [
            jnp.broadcast_to(ref_wh[..., 0:1] * ps, (b, q, lp)),
            jnp.broadcast_to(ref_wh[..., 1:2] * ps, (b, q, lp)),
        ],
        axis=-1,
    )

    chosen = msda_backend(backend, batch_heads=b * h_axis)
    if chosen != "pallas":
        # XLA prologue + whatever core `chosen` names. This branch IS the
        # reference numerics (`_fused_ref` computes the same einsums).
        hs32 = hs.astype(jnp.float32)
        offs = (
            jnp.einsum("bqd,hdl->bqhl", hs32, w_off_h.astype(jnp.float32))
            + b_off_h[:, 0][None, None]
        )
        xy = base[:, :, None, :] + offs * scale[:, :, None, :]
        logits = (
            jnp.einsum("bqd,hdl->bqhl", hs32, w_att_h.astype(jnp.float32))
            + b_att_h[:, 0][None, None]
        )
        attn = jax.nn.softmax(logits, axis=-1)
        loc = jnp.stack([xy[..., :lp], xy[..., lp:]], axis=-1)
        return deformable_sampling(
            value, loc, attn.astype(value.dtype), spatial_shapes, num_points,
            method=method, backend=backend, interpret=interpret,
            presorted=presorted,
        )

    interp = bool(interpret) if interpret is not None else False
    qp = -(-q // Q_TILE) * Q_TILE
    hs_p, base_p, scale_p = hs, base, scale
    if qp != q:  # padded queries: zero hs/base/scale -> discarded rows
        hs_p = jnp.pad(hs, ((0, 0), (0, qp - q), (0, 0)))
        base_p = jnp.pad(base, ((0, 0), (0, qp - q), (0, 0)))
        scale_p = jnp.pad(scale, ((0, 0), (0, qp - q), (0, 0)))

    rows_all = value.transpose(0, 2, 1, 3).reshape(b * h_axis, s, hd)
    offs_l = _level_offsets(spatial_shapes)
    rows_cat, tiles = [], []
    for lvl, (lh, lw) in enumerate(spatial_shapes):
        ts = S_TILE0 if (lvl == 0 and S_TILE0) else S_TILE
        s_l = lh * lw
        rows_l = rows_all[:, offs_l[lvl] : offs_l[lvl] + s_l]
        s_pad = -(-s_l // ts) * ts
        if s_pad != s_l:
            rows_l = jnp.pad(rows_l, ((0, 0), (0, s_pad - s_l), (0, 0)))
        rows_cat.append(rows_l)
        tiles.append((ts, s_pad // ts))
    out = pallas_msda_fused(
        jnp.concatenate(rows_cat, axis=1),
        hs_p, w_off_h, b_off_h, w_att_h, b_att_h, base_p, scale_p,
        tuple(tiles), tuple(spatial_shapes), num_points, method, interp,
    )
    out = out[:, :q].reshape(b, h_axis, q, hd)
    return out.transpose(0, 2, 1, 3).reshape(b, q, h_axis * hd)
