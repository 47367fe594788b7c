"""ISSUE 18 kernel parity suite: interpret-mode gates for the int8 attention
matmuls, the fused-prologue MSDA kernel, and the fused OWL-ViT logit head.

These are the CPU-side acceptance tests for the per-chip-throughput arc:
- `SPOTTER_TPU_INT8_ATTN` unset (or set without `SPOTTER_TPU_INT8`) keeps the
  forward bit-identical — the opt-out is asserted exactly, not approximately.
- `SPOTTER_TPU_MSDA_PREP=fused` keeps the param tree and (via its XLA
  fallback, which is also the VJP reference) the outputs bit-compatible with
  the unfused layer; the Pallas kernel is held to interpret-mode parity for
  both sampling methods, forward and backward.
- The fused OWL logit head matches the unfused tail, and NEG_INF masking
  guarantees padded/masked query slots can never win an argmax.
- Kernel dispatches self-report analytic FLOPs (XLA costs pallas
  custom-calls as 0) so MFU attribution stays honest on kernel paths.

Pallas runs in interpret mode on the CPU test mesh, same convention as
tests/test_msda.py.
"""

import os
import subprocess
import sys
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spotter_tpu.models.rtdetr as R
import spotter_tpu.ops.msda as M
import spotter_tpu.ops.openvocab as OV
import spotter_tpu.utils.quant as quant
from spotter_tpu.models.owlvit import OwlViTClassHead, OwlViTDetector
from spotter_tpu.models.rtdetr import RTDetrDetector
from spotter_tpu.models.zoo import tiny_owlvit_config, tiny_rtdetr_config
from spotter_tpu.obs.perf import collect_kernel_flops, combine_flops
from spotter_tpu.ops.msda import deformable_sampling_fused
from spotter_tpu.ops.openvocab import NEG_INF, fused_class_logits, pallas_class_logits

# ---------------------------------------------------------------------------
# fused-prologue MSDA: op-level parity (kernel interpret vs xla fallback)
# ---------------------------------------------------------------------------

SHAPES = ((8, 8), (4, 4))
B, Q, H, D, HD, P = 2, 70, 2, 32, 32, 2  # Q=70: exercises Q_TILE padding
LP = len(SHAPES) * P
S = sum(h * w for h, w in SHAPES)


def _fused_inputs(seed=0):
    rng = np.random.default_rng(seed)
    value = jnp.asarray(rng.standard_normal((B, S, H, HD)).astype(np.float32))
    hs = jnp.asarray(rng.standard_normal((B, Q, D)).astype(np.float32))
    # cxcywh in (0, 1) with non-degenerate wh
    ref = jnp.asarray(
        np.concatenate(
            [
                rng.uniform(0.2, 0.8, (B, Q, 2)),
                rng.uniform(0.2, 0.6, (B, Q, 2)),
            ],
            axis=-1,
        ).astype(np.float32)
    )
    w_off = jnp.asarray(
        (rng.standard_normal((D, H * LP * 2)) * 0.1).astype(np.float32)
    )
    b_off = jnp.asarray((rng.standard_normal((H * LP * 2,)) * 0.1).astype(np.float32))
    w_att = jnp.asarray((rng.standard_normal((D, H * LP)) * 0.1).astype(np.float32))
    b_att = jnp.asarray((rng.standard_normal((H * LP,)) * 0.1).astype(np.float32))
    return value, hs, ref, w_off, b_off, w_att, b_att


@pytest.mark.parametrize("method", ["default", "discrete"])
def test_fused_msda_kernel_matches_xla_fallback(method):
    args = _fused_inputs()
    got = deformable_sampling_fused(
        *args, SHAPES, P, method=method, backend="pallas", interpret=True
    )
    ref = deformable_sampling_fused(*args, SHAPES, P, method=method, backend="xla")
    assert got.shape == (B, Q, H * HD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_fused_msda_grad_parity():
    """Extended custom VJP: gradients w.r.t. value, hidden states, and all
    four fused projection params must match the XLA reference path."""
    value, hs, ref, w_off, b_off, w_att, b_att = _fused_inputs(1)

    def loss(backend):
        def f(value, hs, w_off, b_off, w_att, b_att):
            out = deformable_sampling_fused(
                value, hs, ref, w_off, b_off, w_att, b_att, SHAPES, P,
                backend=backend, interpret=(backend == "pallas"),
            )
            return jnp.sum(jnp.sin(out))

        return jax.grad(f, argnums=(0, 1, 2, 3, 4, 5))(
            value, hs, w_off, b_off, w_att, b_att
        )

    g_k = loss("pallas")
    g_x = loss("xla")
    names = ("d_value", "d_hs", "d_w_off", "d_b_off", "d_w_att", "d_b_att")
    for name, a, b in zip(names, g_k, g_x):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=name
        )


@pytest.fixture(scope="module")
def tiny_rtdetr():
    """Tiny RT-DETR + baseline forward, shared across the model-level tests
    (computed once under default knobs — every test patches knobs inside
    its body, after this resolves)."""
    cfg = tiny_rtdetr_config()
    model = RTDetrDetector(cfg)
    x = np.random.default_rng(0).standard_normal((2, 64, 64, 3)).astype(np.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    ref = model.apply({"params": params}, x)
    return model, params, x, ref


def test_fused_prep_model_param_tree_and_output_parity(monkeypatch, tiny_rtdetr):
    """SPOTTER_TPU_MSDA_PREP=fused on the tiny RT-DETR: the DenseParams
    declarations must produce the exact same param tree as the nn.Dense
    layers they replace (checkpoints interchange), and the XLA fallback —
    the fused op's reference numerics — must be bit-identical to the
    unfused layer. (Kernel-vs-fallback parity is pinned op-level above;
    kernel engagement through the model layer is pinned by the FLOPs test
    below, which lowers the forced-kernel model.)"""
    model, params, x, ref_out = tiny_rtdetr
    monkeypatch.setattr(M, "MSDA_PREP", "fused")
    fused_params = model.init(jax.random.PRNGKey(0), x)["params"]
    ref_paths = {
        "/".join(str(k) for k in p): v.shape
        for p, v in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    fused_paths = {
        "/".join(str(k) for k in p): v.shape
        for p, v in jax.tree_util.tree_flatten_with_path(fused_params)[0]
    }
    assert ref_paths == fused_paths, "param tree changed under MSDA_PREP=fused"

    # CPU host -> msda_backend picks xla -> fallback branch, the reference
    # numerics of the fused op: bit-identical to the unfused layer
    fb_out = model.apply({"params": params}, x)
    for key in ref_out:
        np.testing.assert_array_equal(
            np.asarray(ref_out[key]), np.asarray(fb_out[key]), err_msg=key
        )


def test_fused_prep_rejects_sg_and_nest():
    """SPOTTER_TPU_MSDA_SG / _NEST are xla-prep-only experiments; combining
    them with the fused prologue must fail loudly at import, not silently
    drop the subgroup/nest behavior."""
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "SPOTTER_TPU_MSDA": "pallas",  # SG needs the pallas backend first
        "SPOTTER_TPU_MSDA_PREP": "fused",
        "SPOTTER_TPU_MSDA_SG": "8",
    }
    proc = subprocess.run(
        [sys.executable, "-c", "import spotter_tpu.ops.msda"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "SPOTTER_TPU_MSDA_SG requires SPOTTER_TPU_MSDA_PREP=xla" in proc.stderr


# ---------------------------------------------------------------------------
# int8 attention: guard truth table, exact opt-out, score/box tolerance
# ---------------------------------------------------------------------------


def test_int8_attn_guard_truth_table(monkeypatch):
    monkeypatch.setattr(quant, "INT8", True)
    monkeypatch.setattr(quant, "INT8_ATTN", True)
    monkeypatch.setattr(quant, "INT8_ATTN_MIN_HD", 32)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 8)
    assert quant.int8_attn_wanted(64, batch=8)
    assert quant.int8_attn_wanted(32)  # batch unknown -> head_dim rules
    assert not quant.int8_attn_wanted(16, batch=8)  # below head-dim floor
    assert not quant.int8_attn_wanted(64, batch=4)  # below batch floor
    # "additionally" convention: INT8_ATTN rides on INT8, never alone
    monkeypatch.setattr(quant, "INT8", False)
    assert not quant.int8_attn_wanted(64, batch=8)
    monkeypatch.setattr(quant, "INT8", True)
    monkeypatch.setattr(quant, "INT8_ATTN", False)
    assert not quant.int8_attn_wanted(64, batch=8)


def test_int8_attn_opt_out_is_bit_identical(monkeypatch, tiny_rtdetr):
    """Acceptance gate: with SPOTTER_TPU_INT8_ATTN effectively off — here,
    set WITHOUT the base SPOTTER_TPU_INT8 opt-in — the forward must be
    bit-identical, not merely close. The quantized branch must be dead."""
    model, params, x, ref = tiny_rtdetr
    monkeypatch.setattr(quant, "INT8_ATTN", True)  # no INT8 -> still off
    monkeypatch.setattr(quant, "INT8_ATTN_MIN_HD", 1)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 1)
    got = model.apply({"params": params}, x)
    for key in ref:
        np.testing.assert_array_equal(
            np.asarray(ref[key]), np.asarray(got[key]), err_msg=key
        )


def test_int8_attn_score_box_parity(monkeypatch, tiny_rtdetr):
    """int8 QK^T + attn.V live on the tiny RT-DETR (floors lowered to hit
    head_dim=8, batch=2; conv/dense quant floored out to isolate attention):
    scores and boxes stay within the same drift bar as the other int8
    surfaces, and the output provably changed (the path is live)."""
    model, params, x, ref = tiny_rtdetr
    monkeypatch.setattr(quant, "INT8", True)
    monkeypatch.setattr(quant, "INT8_ATTN", True)
    monkeypatch.setattr(quant, "INT8_ATTN_MIN_HD", 8)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 1)
    monkeypatch.setattr(quant, "INT8_MIN_CH", 10**9)  # convs/denses stay float
    got = model.apply({"params": params}, x)
    assert not np.array_equal(
        np.asarray(ref["logits"]), np.asarray(got["logits"])
    ), "int8 attention path did not engage"
    score_ref = float(jax.nn.sigmoid(ref["logits"]).max())
    score_q = float(jax.nn.sigmoid(got["logits"]).max())
    assert abs(score_ref - score_q) < 0.05, (score_ref, score_q)
    box_ref = float(jnp.abs(ref["pred_boxes"]).mean())
    box_q = float(jnp.abs(got["pred_boxes"]).mean())
    assert abs(box_ref - box_q) < 0.05, (box_ref, box_q)


# ---------------------------------------------------------------------------
# fused OWL-ViT logit head: parity, NEG_INF masking, gradients
# ---------------------------------------------------------------------------

OWL_B, OWL_P, OWL_Q = 2, 65, 7  # P=65: exercises P_TILE padding

# Tolerance note: the kernel spells elu(x) + 1 as where(x > 0, x + 1, exp(x))
# because the chip's Pallas lowering has no expm1; against the unfused tail's
# jax.nn.elu that is a <= 1 ulp fp32 difference, inside the atol=1e-6 below
# (these comparisons were never bit-for-bit).


@pytest.fixture
def owl_fused_interpreted(monkeypatch):
    """Force the fused head on, on this CPU: the kernel's `interpret`
    defaults to False (it compiles for the chip or fails), so the test — not
    the program — picks interpret mode, at the one call site the model has."""
    import spotter_tpu.models.owlvit as owlvit_mod

    def arm():
        monkeypatch.setattr(OV, "OWL_FUSED", "1")
        monkeypatch.setattr(
            owlvit_mod,
            "fused_class_logits",
            partial(fused_class_logits, interpret=True),
        )

    return arm


def _owl_head_inputs(seed=0):
    cfg = tiny_owlvit_config()
    rng = np.random.default_rng(seed)
    feats = jnp.asarray(
        rng.standard_normal((OWL_B, OWL_P, cfg.vision.hidden_size)).astype(np.float32)
    )
    queries = jnp.asarray(
        rng.standard_normal((OWL_Q, cfg.text.hidden_size)).astype(np.float32)
    )
    return cfg, feats, queries


@pytest.mark.parametrize("masked", [False, True])
def test_owl_fused_head_matches_unfused(monkeypatch, owl_fused_interpreted, masked):
    cfg, feats, queries = _owl_head_inputs()
    qmask = (
        jnp.asarray(np.array([1, 1, 0, 1, 1, 0, 1], np.float32)) if masked else None
    )
    head = OwlViTClassHead(cfg)
    monkeypatch.setattr(OV, "OWL_FUSED", "0")
    params = head.init(jax.random.PRNGKey(0), feats, queries, qmask)["params"]
    ref = head.apply({"params": params}, feats, queries, qmask)
    owl_fused_interpreted()
    got = head.apply({"params": params}, feats, queries, qmask)
    assert got.shape == (OWL_B, OWL_P, OWL_Q)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=1e-6)
    if masked:
        assert np.all(np.asarray(got)[:, :, [2, 5]] == NEG_INF)


def test_owl_fused_mask_padded_slots_never_win_argmax(monkeypatch):
    """NEG_INF contract on the raw kernel output: lane-padded query slots
    (columns beyond the real query count) and caller-masked queries come out
    exactly NEG_INF, so an argmax over the padded width can only ever pick a
    real, unmasked query."""
    rng = np.random.default_rng(3)
    dt, q, qp, pp = 16, 7, OV.LANE, OV.P_TILE
    img = jnp.asarray(rng.standard_normal((1, pp, dt)).astype(np.float32))
    qt = jnp.zeros((dt, qp), jnp.float32)
    qbank = rng.standard_normal((dt, q)).astype(np.float32)
    qbank = qbank / np.linalg.norm(qbank, axis=0, keepdims=True)
    qt = qt.at[:, :q].set(jnp.asarray(qbank))
    ss = jnp.asarray(rng.standard_normal((1, pp, 2)).astype(np.float32))
    mask = jnp.zeros((1, qp), jnp.float32).at[0, :q].set(1.0)
    mask = mask.at[0, 4].set(0.0)  # caller-masked real query
    out = np.asarray(pallas_class_logits(img, qt, ss, mask, True))
    assert np.all(out[:, :, q:] == NEG_INF), "lane padding must be NEG_INF"
    assert np.all(out[:, :, 4] == NEG_INF), "masked query must be NEG_INF"
    winners = out.argmax(axis=-1).ravel()
    assert np.all(winners < q) and not np.any(winners == 4)


def test_owl_fused_head_grad_parity(monkeypatch, owl_fused_interpreted):
    cfg, feats, queries = _owl_head_inputs(1)
    head = OwlViTClassHead(cfg)
    monkeypatch.setattr(OV, "OWL_FUSED", "0")
    params = head.init(jax.random.PRNGKey(0), feats, queries)["params"]

    def loss(feats_, params_):
        out = head.apply({"params": params_}, feats_, queries)
        return jnp.sum(jnp.tanh(out / 10.0))

    g_ref = jax.grad(loss, argnums=(0, 1))(feats, params)
    owl_fused_interpreted()
    g_fused = jax.grad(loss, argnums=(0, 1))(feats, params)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_owl_fused_model_level_parity(monkeypatch, owl_fused_interpreted):
    """Full tiny OWL-ViT detector under SPOTTER_TPU_OWL_FUSED=1: logits and
    boxes match the unfused forward (param tree is shared by construction —
    the fused branch reuses the same three Dense declarations)."""
    cfg = tiny_owlvit_config()
    model = OwlViTDetector(cfg)
    rng = np.random.default_rng(0)
    pixels = rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
    queries = jnp.asarray(
        rng.standard_normal((3, cfg.projection_dim)).astype(np.float32)
    )
    monkeypatch.setattr(OV, "OWL_FUSED", "0")
    params = model.init(jax.random.PRNGKey(0), pixels, queries)["params"]
    ref = model.apply({"params": params}, pixels, queries)
    owl_fused_interpreted()
    got = model.apply({"params": params}, pixels, queries)
    np.testing.assert_allclose(
        np.asarray(ref["logits"]), np.asarray(got["logits"]), atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(ref["pred_boxes"]), np.asarray(got["pred_boxes"])
    )


# ---------------------------------------------------------------------------
# FLOPs honesty: kernel dispatches feed the MFU ledger
# ---------------------------------------------------------------------------


def test_fused_kernel_path_reports_flops(monkeypatch, tiny_rtdetr):
    """XLA's cost_analysis counts pallas custom-calls as 0 FLOPs; the fused
    MSDA dispatch must self-report its analytic count so combine_flops can
    repair the MFU denominator (finite, and strictly above what XLA alone
    credits the kernel-path program). Lowering the forced-kernel model also
    pins that MSDA_PREP=fused actually engages the kernel through the
    model layer."""
    model, params, x, _ = tiny_rtdetr
    monkeypatch.setattr(M, "MSDA_PREP", "fused")
    forced = partial(deformable_sampling_fused, backend="pallas", interpret=True)
    monkeypatch.setattr(M, "deformable_sampling_fused", forced)
    monkeypatch.setattr(R, "deformable_sampling_fused", forced)

    fwd = jax.jit(lambda p, xx: model.apply({"params": p}, xx))
    with collect_kernel_flops() as noted:
        lowered = fwd.lower(params, x)
    assert noted.get("msda_fused", 0) > 0, sorted(noted)
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca_flops = ca.get("flops") if hasattr(ca, "get") else None
    total = combine_flops(ca_flops, noted.get("__total__"))
    assert total is not None and np.isfinite(total) and total > 1e6
    if ca_flops is not None and np.isfinite(ca_flops) and ca_flops > 0:
        assert total > ca_flops  # the kernel's work was actually added
