"""int8 quantization path (utils/quant.py, SPOTTER_TPU_INT8=1).

Numerical contract: dynamic per-tensor activation + per-out-channel weight
symmetric quantization. The hard accuracy gate on real weights is the
golden-box test (±1 px, tests/test_golden_boxes.py); these tests pin the
machinery — scales, error bounds, param-tree invariance — on random data.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from spotter_tpu.utils.quant import (
    int8_conv,
    quantize_activation,
    quantize_weight,
)


def test_int8_min_batch_guard(monkeypatch):
    """SPOTTER_TPU_INT8_MIN_BATCH (ISSUE 3): int8 regresses under-filled MXU
    batches (R101 bucket 4: 33.0 vs 18.7 ms/call — BASELINE round 5), so the
    guard keeps buckets below the floor bf16 even with INT8=1. Batch is a
    static jit shape, so the decision is per compiled bucket; batch=None
    (non-serving callers) keeps the old behavior."""
    from spotter_tpu.utils import quant

    monkeypatch.setattr(quant, "INT8", True)
    monkeypatch.setattr(quant, "INT8_DENSE", True)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 8)
    assert quant.int8_wanted(128) and quant.int8_wanted(128, batch=None)
    assert not quant.int8_wanted(128, batch=4)  # latency-SLO bucket stays bf16
    assert quant.int8_wanted(128, batch=8)
    assert quant.int8_wanted(128, batch=16)
    assert not quant.int8_dense_wanted(128, batch=4)
    assert quant.int8_dense_wanted(128, batch=8)
    # floor of 1 disables the guard (the CI golden gate runs batch 1)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 1)
    assert quant.int8_wanted(128, batch=1)
    # channel floor still applies regardless of batch
    assert not quant.int8_wanted(8, batch=16)


def test_quantize_weight_per_channel_roundtrip():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((3, 3, 32, 16)) * 0.1, jnp.float32)
    wq, scale = quantize_weight(w)
    assert wq.dtype == jnp.int8 and scale.shape == (16,)
    err = np.abs(np.asarray(wq, np.float32) * np.asarray(scale) - np.asarray(w))
    # symmetric rounding: per-element error <= scale/2 of that channel
    assert (err <= np.asarray(scale)[None, None, None, :] * 0.5 + 1e-7).all()


def test_quantize_activation_per_sample_scale():
    """Scales are per leading-axis sample: a batch-mate's outlier must not
    coarsen this sample's quantization (serving determinism — a request's
    boxes cannot depend on what the MicroBatcher co-batched with it)."""
    x = jnp.asarray([[1.0, -3.0], [0.5, 2.0]], jnp.float32)
    xq, s = quantize_activation(x)
    assert xq.dtype == jnp.int8 and s.shape == (2, 1)
    np.testing.assert_allclose(
        np.asarray(s)[:, 0], [3.0 / 127.0, 2.0 / 127.0], rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(xq, np.float32) * np.asarray(s),
        np.asarray(x),
        atol=float(np.asarray(s).max()) / 2 + 1e-7,
    )
    # sample 0 unchanged when its batch-mate changes
    x2 = x.at[1].mul(100.0)
    xq2, s2 = quantize_activation(x2)
    np.testing.assert_array_equal(np.asarray(xq2[0]), np.asarray(xq[0]))
    np.testing.assert_allclose(float(s2[0, 0]), float(s[0, 0]), rtol=1e-7)


def test_int8_conv_approximates_float_conv():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 64, 32)) * 0.05, jnp.float32)
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    got = int8_conv(x, w, (1, 1), [(1, 1), (1, 1)], jnp.float32)
    assert got.dtype == jnp.float32 and got.shape == ref.shape
    # per-tensor int8: relative error on the output scale, not per element
    rel = np.abs(np.asarray(got) - np.asarray(ref)).max() / np.abs(
        np.asarray(ref)
    ).max()
    assert rel < 0.02, rel


def test_int8_conv_gradients_are_straight_through():
    """The backward pass must be the float conv's (STE): round/clip are flat
    almost everywhere, so without it SPOTTER_TPU_INT8=1 under the train step
    would silently zero every conv-kernel gradient."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 16, 8)) * 0.1, jnp.float32)
    cot = jnp.asarray(rng.standard_normal((1, 8, 8, 8)), jnp.float32)

    def loss_q(xx, ww):
        return jnp.sum(int8_conv(xx, ww, (1, 1), [(1, 1), (1, 1)], jnp.float32) * cot)

    def loss_f(xx, ww):
        y = jax.lax.conv_general_dilated(
            xx, ww, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return jnp.sum(y * cot)

    gq = jax.grad(loss_q, (0, 1))(x, w)
    gf = jax.grad(loss_f, (0, 1))(x, w)
    for a, b in zip(gq, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
        assert float(jnp.abs(a).max()) > 0  # not silently zeroed


def test_quant_dense_matches_nn_dense_param_tree_and_output():
    """QuantDense with the knob off must BE nn.Dense: same param paths,
    shapes, and (given the same params) identical outputs — the ViT torch-
    parity tests rest on this."""
    from flax import linen as nn

    from spotter_tpu.models.layers import QuantDense

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 5, 32)), jnp.float32)
    ref = nn.Dense(16)
    got = QuantDense(16)
    pref = ref.init(jax.random.PRNGKey(7), x)["params"]
    pgot = got.init(jax.random.PRNGKey(7), x)["params"]
    assert jax.tree_util.tree_structure(pref) == jax.tree_util.tree_structure(pgot)

    def by_path(tree):
        return sorted(
            (jax.tree_util.keystr(path), leaf.shape)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        )

    assert by_path(pref) == by_path(pgot)
    np.testing.assert_allclose(
        np.asarray(ref.apply({"params": pref}, x)),
        np.asarray(got.apply({"params": pref}, x)),
        rtol=1e-6,
        atol=1e-6,
    )


def test_int8_dense_approximates_and_ste_grads():
    from spotter_tpu.utils.quant import int8_dense

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((48, 24)) * 0.1, jnp.float32)
    ref = x @ w
    got = int8_dense(x, w, jnp.float32)
    rel = np.abs(np.asarray(got) - np.asarray(ref)).max() / np.abs(np.asarray(ref)).max()
    assert rel < 0.02, rel

    gq = jax.grad(lambda a, b: jnp.sum(int8_dense(a, b, jnp.float32) ** 2), (0, 1))(x, w)
    # STE: gradients of sum(f^2) differ between quantized/float f, so check
    # against the float-backward applied at the quantized output cotangent
    cot = 2 * got
    _, vjp = jax.vjp(lambda a, b: a @ b, x, w)
    gf = vjp(cot)
    for a, b in zip(gq, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_quant_dense_takes_int8_path_and_stays_close(monkeypatch):
    """SPOTTER_TPU_INT8_DENSE end-to-end at the layer (ISSUE 9 satellite):
    with the knobs armed QuantDense must actually route through int8_dense
    (output differs from the exact float matmul — the path is live) while
    staying within quantization tolerance of it (the parity half)."""
    from flax import linen as nn

    from spotter_tpu.models import layers
    from spotter_tpu.utils import quant

    monkeypatch.setattr(quant, "INT8", True)
    monkeypatch.setattr(quant, "INT8_DENSE", True)
    monkeypatch.setattr(quant, "INT8_MIN_CH", 8)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 1)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 7, 32)), jnp.float32)
    ref = nn.Dense(16)
    got = layers.QuantDense(16)
    params = ref.init(jax.random.PRNGKey(11), x)["params"]
    exact = np.asarray(ref.apply({"params": params}, x))
    quantized = np.asarray(got.apply({"params": params}, x))
    assert not np.allclose(quantized, exact, atol=1e-7)  # int8 path is live
    rel = np.abs(quantized - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel
    # below the batch floor the layer must stay exactly bf16/float
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 8)
    np.testing.assert_allclose(
        np.asarray(got.apply({"params": params}, x)), exact, atol=1e-6
    )


def test_int8_dense_env_score_box_parity_bf16_reference():
    """bf16-vs-int8-dense parity on the tiny RT-DETR forward (ISSUE 9
    satellite, ROADMAP item 1 leftover): SPOTTER_TPU_INT8_DENSE=1 (which
    quantizes the attention/FFN projections on top of the convs) must keep
    scores and boxes within tolerance of the float reference, and must not
    change the param tree. Knobs are import-time, hence the subprocess."""
    code = """
import os, numpy as np, jax, jax.numpy as jnp
from spotter_tpu.models.zoo import tiny_rtdetr_config
from spotter_tpu.models.rtdetr import RTDetrDetector
cfg = tiny_rtdetr_config()
m = RTDetrDetector(cfg)
x = np.random.default_rng(0).standard_normal((1, 64, 64, 3)).astype(np.float32)
p = m.init(jax.random.PRNGKey(0), x)["params"]
out = m.apply({"params": p}, x)
leaf_paths = sorted(
    "/".join(str(k) for k in path)
    for path, _ in jax.tree_util.tree_flatten_with_path(p)[0]
)
import hashlib
print("TREE", hashlib.sha256("\\n".join(leaf_paths).encode()).hexdigest()[:16])
print("BOX", float(jnp.abs(out["pred_boxes"]).mean()))
print("SCORE", float(jax.nn.sigmoid(out["logits"]).max()))
"""
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "SPOTTER_TPU_INT8_MIN_CH": "8",
        "SPOTTER_TPU_INT8_MIN_BATCH": "1",
    }
    outs = {}
    for tag, int8, dense in (("bf16", "0", "0"), ("int8dense", "1", "1")):
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env={
                **env_base,
                "SPOTTER_TPU_INT8": int8,
                "SPOTTER_TPU_INT8_DENSE": dense,
            },
            capture_output=True,
            text=True,
            timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = dict(
            ln.split(" ", 1) for ln in proc.stdout.splitlines() if " " in ln
        )
        outs[tag] = lines
    assert outs["bf16"]["TREE"] == outs["int8dense"]["TREE"], (
        "param tree changed under INT8_DENSE"
    )
    box_ref, box_q = (float(outs[t]["BOX"]) for t in ("bf16", "int8dense"))
    score_ref, score_q = (
        float(outs[t]["SCORE"]) for t in ("bf16", "int8dense")
    )
    # boxes are sigmoid-bounded cxcywh in (0,1): 0.05 aggregate drift on the
    # random-init tiny model is the same bar the conv-only test pins
    assert abs(box_ref - box_q) < 0.05, (box_ref, box_q)
    assert abs(score_ref - score_q) < 0.05, (score_ref, score_q)


def test_int8_env_keeps_param_tree_and_output_close():
    """SPOTTER_TPU_INT8=1 must not change the param tree (checkpoints stay
    loadable) and the tiny-model forward must stay close to float. The knob
    is read at import, so this runs in a subprocess with a forced channel
    floor low enough to trigger on the tiny config."""
    code = """
import os, numpy as np, jax, jax.numpy as jnp
from spotter_tpu.models.zoo import tiny_rtdetr_config
from spotter_tpu.models.rtdetr import RTDetrDetector
cfg = tiny_rtdetr_config()
m = RTDetrDetector(cfg)
x = np.random.default_rng(0).standard_normal((1, 64, 64, 3)).astype(np.float32)
p = m.init(jax.random.PRNGKey(0), x)["params"]
out = m.apply({"params": p}, x)
leaf_paths = sorted(
    "/".join(str(k) for k in path)
    for path, _ in jax.tree_util.tree_flatten_with_path(p)[0]
)
import hashlib
print("TREE", hashlib.sha256("\\n".join(leaf_paths).encode()).hexdigest()[:16])
print("BOX", float(jnp.abs(out["pred_boxes"]).mean()))
"""
    env_base = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "SPOTTER_TPU_INT8_MIN_CH": "8",
        # the subprocess forward runs batch 1 — disable the small-batch
        # guard so INT8=1 actually takes the quantized path under test
        "SPOTTER_TPU_INT8_MIN_BATCH": "1",
    }
    outs = {}
    for flag in ("0", "1"):
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env={**env_base, "SPOTTER_TPU_INT8": flag},
            capture_output=True,
            text=True,
            timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = dict(
            ln.split(" ", 1) for ln in proc.stdout.splitlines() if " " in ln
        )
        outs[flag] = lines
    assert outs["0"]["TREE"] == outs["1"]["TREE"], "param tree changed under INT8"
    b0, b1 = float(outs["0"]["BOX"]), float(outs["1"]["BOX"])
    # boxes are sigmoid-bounded; int8 drift on a random-init tiny model stays
    # small in aggregate
    assert abs(b0 - b1) < 0.05, (b0, b1)
