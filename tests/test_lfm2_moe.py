"""`lfm2_moe_det` (PR 33) on the CPU at tiny widths: the served modules
(`models/lfm2_moe.py`: the conv as slices of a padded array, grouped experts
through the window loop) against the plain `jax.numpy` float32 reference
(`testing/lfm2_moe_reference.py`: three shifted products, eager attention, a
loop over experts), layer kind by layer kind and the six together; the
router's sigmoid, selection bias and epsilon against the equations, and the
softmax router unchanged; the shares of an uncut layer; the grouped product at
the published expert width; a bfloat16 policy's gap; the registry, the loader,
the engine, `/detect` and `/metrics`. hidden 64, six layers (conv, conv,
attention, conv, conv, conv; two dense, four routed), 8 experts, top 2, 40
tokens.

Tolerances: both sides are float32 at the highest matmul precision (conftest),
so they differ by summation order only. 2e-4 absolute on activations of order
1-10 is some ten times what is seen (1e-5); a wrong tap, gate, mask, expert or
weight reads 1e-2 and more.
"""

import asyncio
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from PIL import Image
from test_qwen3_next import _jpeg_client, _write_safetensors

from spotter_tpu.models import lfm2_moe as served
from spotter_tpu.models.configs import Lfm2MoeDetConfig
from spotter_tpu.ops import moe
from spotter_tpu.testing import lfm2_moe_reference as ref
from spotter_tpu.utils import quant

ATOL = 2e-4
CFG = Lfm2MoeDetConfig(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
    image_size=(32, 80), patch_size=16, num_detection_tokens=30, num_labels=5,
)
TOKENS = CFG.num_tokens  # 10 patches + 30 detection tokens


def _randomise(params, seed=0):
    """Flax's initial values (norm weights 1, bias 0, detection tokens 0)
    would hide wiring faults: every leaf gets seeded values of a sane scale,
    the selection bias as wide as the gaps between a token's scores."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        rng = np.random.default_rng([seed, i])
        if leaf.ndim >= 2:
            value = rng.standard_normal(leaf.shape) / np.sqrt(np.prod(leaf.shape[:-1]) / max(
                1, leaf.shape[0] if leaf.ndim == 3 else 1))
        elif "expert_bias" in jax.tree_util.keystr(path):
            value = rng.uniform(-0.2, 0.2, leaf.shape)
        else:
            value = rng.uniform(0.7, 1.3, leaf.shape)
        out.append(np.asarray(value, np.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def params():
    x = np.zeros((1, *CFG.image_size, 3), np.float32)
    return _randomise(served.Lfm2MoeDetector(CFG).init(jax.random.PRNGKey(0), x)["params"])


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).standard_normal((2, TOKENS, CFG.hidden_size)).astype(np.float32)


@pytest.mark.parametrize("kind", ["short_conv", "attention", "dense_mlp"])
def test_layer_kinds_one_by_one(params, tokens, kind):
    module, p, want = {
        "short_conv": (served.ShortConv(CFG), params["layer0"]["conv"],
                       lambda p, x: ref.short_conv(p, x, CFG)),
        "attention": (served.Attention(CFG), params["layer2"]["self_attn"],
                      lambda p, x: ref.attention(p, x, CFG)),
        "dense_mlp": (served.DenseMlp(CFG), params["layer1"]["feed_forward"], ref.dense_mlp),
    }[kind]
    got = module.apply({"params": p}, tokens)
    for i in range(2):
        np.testing.assert_allclose(got[i], want(p, tokens[i]), atol=ATOL)


def test_short_conv_is_causal_and_three_taps_wide(params, tokens):
    """A change at token 20 moves tokens 20, 21 and 22 and no other."""
    p = params["layer3"]["conv"]
    moved = tokens[:1].copy()
    moved[0, 20] += 1.0
    apply = served.ShortConv(CFG).apply
    changed = np.abs(np.asarray(apply({"params": p}, moved) - apply({"params": p}, tokens[:1]))[0])
    assert np.nonzero(changed.max(-1) > 1e-6)[0].tolist() == [20, 21, 22]


def test_moe_layer_and_its_counters(params, tokens):
    p = params["layer3"]["feed_forward"]
    got, counts, moved = served.SparseMoe(CFG).apply({"params": p}, tokens)
    k = CFG.num_experts_per_tok
    for i in range(2):
        np.testing.assert_allclose(got[i], ref.sparse_moe(p, tokens[i], CFG), atol=ATOL)
        weights = np.asarray(ref.routing_weights(p, tokens[i], CFG))
        chosen = weights > 0
        assert (chosen.sum(-1) == k).all()
        # the counter: the selections that fell on each expert, per image; all are held
        np.testing.assert_array_equal(np.asarray(counts[i]), chosen.sum(0))
        # and those the bias moved: chosen, and not among the k best of the plain scores
        s = 1 / (1 + np.exp(-(tokens[i] @ np.asarray(p["router"]))))
        plain = np.argsort(-s, axis=-1, kind="stable")[:, :k]
        best = np.zeros_like(chosen)
        np.put_along_axis(best, plain, True, axis=-1)
        assert int(moved[i]) == int((chosen & ~best).sum()) and 0 < int(moved[i]) < TOKENS * k
    assert int(counts.sum()) == 2 * TOKENS * k


def test_detector_end_to_end(params):
    pixels = np.random.default_rng(3).standard_normal((2, *CFG.image_size, 3)).astype(np.float32)
    got = jax.jit(served.Lfm2MoeDetector(CFG).apply)({"params": params}, pixels)
    assert got["moe_expert_tokens"].shape == (2, 4, 8)
    assert got["moe_assignments"].shape == got["moe_bias_moved"].shape == (2, 4)
    assert (np.asarray(got["moe_assignments"]) == TOKENS * CFG.num_experts_per_tok).all()
    for i in range(2):
        want = ref.detector(params, pixels[i], CFG)
        # 2e-3: four layers of routing amplify a last-bit difference a little;
        # a token that changes expert moves logits by whole units
        np.testing.assert_allclose(got["logits"][i], want["logits"], atol=2e-3)
        np.testing.assert_allclose(got["pred_boxes"][i], want["pred_boxes"], atol=ATOL)


def test_sigmoid_router_with_a_selection_bias_against_the_equations():
    """Three tokens over four experts, two a token, by hand: the bias enters
    the choice and not the weight; the weights are the plain sigmoids over
    (their sum + eps), times the scale."""
    logits = np.array([[2.0, 1.0, 0.0, -1.0],    # plain best two: 0, 1
                       [0.0, 0.1, 0.2, 0.3],     # plain best two: 3, 2
                       [-3.0, -3.0, -3.0, -3.0]], np.float32)  # all equal: the lower indices
    bias = np.array([0.0, -0.5, 0.4, 0.0], np.float32)
    x, router = logits, np.eye(4, dtype=np.float32)  # x @ router == logits
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    weights, experts = moe.route(x, router, 2, scoring="sigmoid", bias=bias, eps=1e-6, scale=2.5)
    # token 0: s = .881 .731 .5 .269; + bias = .881 .231 .9 .269 -> 2 then 0: the bias moved a choice
    # token 1: s = .5 .525 .55 .574; + bias = .5 .025 .95 .574 -> 2 then 3
    # token 2: equal scores; + bias -> 2, then the lowest index of the rest: 0
    assert np.asarray(experts).tolist() == [[2, 0], [2, 3], [2, 0]]
    for t, (a, b) in enumerate(np.asarray(experts)):
        pair = np.array([s[t, a], s[t, b]])
        np.testing.assert_allclose(weights[t], 2.5 * pair / (pair.sum() + 1e-6), rtol=1e-6)
    assert np.asarray(moe.moved_by_bias(moe.router_scores(x, router, "sigmoid"), experts)).tolist() == [1, 0, 1]
    # the epsilon: without it the weights sum to one exactly; with a large one they do not
    plain, _ = moe.route(x, router, 2, scoring="sigmoid", bias=bias)
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, rtol=1e-6)
    damped, _ = moe.route(x, router, 2, scoring="sigmoid", bias=bias, eps=1.0)
    np.testing.assert_allclose(damped[0], [s[0, 2] / (s[0, 2] + s[0, 0] + 1), s[0, 0] / (s[0, 2] + s[0, 0] + 1)],
                               rtol=1e-6)
    # no bias: the plain best, weighed alike
    _, unbiased = moe.route(x, router, 2, scoring="sigmoid")
    assert np.asarray(unbiased).tolist() == [[0, 1], [3, 2], [0, 1]]
    # a bias below the mask's old floor of -1 still loses to every untaken expert
    _, deep = moe.route(x, router, 3, scoring="sigmoid", bias=np.array([0, -5.0, 0, 0], np.float32))
    assert 1 not in np.asarray(deep)


def _route_as_pr28_had_it(x, router, top_k, normalise=True):
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    weights, experts = moe._top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if normalise:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights, experts


@pytest.mark.parametrize("normalise", [True, False])
def test_the_softmax_router_is_unchanged_bit_for_bit(normalise):
    """`qwen3_next_det`'s router through the generalised `route`: the same
    numbers and the same program (the jaxprs print alike)."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal((300, 64)).astype(np.float32)
    router = rng.standard_normal((64, 48)).astype(np.float32) / 8
    want_w, want_e = jax.jit(_route_as_pr28_had_it, static_argnums=(2, 3))(x, router, 10, normalise)
    got_w, got_e = jax.jit(moe.route, static_argnums=(2, 3))(x, router, 10, normalise)
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
    assert np.array_equal(np.asarray(got_w), np.asarray(want_w))
    assert str(jax.make_jaxpr(lambda a, b: moe.route(a, b, 10, normalise))(x, router)) == str(
        jax.make_jaxpr(lambda a, b: _route_as_pr28_had_it(a, b, 10, normalise))(x, router))


def test_shares_of_the_experts_add_up_to_the_uncut_layer(params, tokens):
    """`routed_experts` told to hold [0, 2), [2, 4), [4, 6), [6, 8) of this
    router's eight experts: the four parts add up to what the uncut
    reference gives for the layer (there is no shared expert to count once)."""
    p = params["layer4"]["feed_forward"]
    x = tokens[0]
    weights, experts = moe.route(x, p["router"], CFG.num_experts_per_tok, CFG.norm_topk_prob,
                                 scoring="sigmoid", bias=p["expert_bias"], eps=served.NORM_TOPK_EPS)
    total = np.zeros_like(x)
    for offset in range(0, 8, 2):
        part = moe.routed_experts(x, weights, experts, p["experts_gate_up"][offset:offset + 2],
                                  p["experts_down"][offset:offset + 2], offset=offset)
        assert np.abs(np.asarray(part)).max() > 0
        total += np.asarray(part)
    np.testing.assert_allclose(total, ref.sparse_moe(p, x, CFG), atol=ATOL)


def _silu(a):
    return a / (1 + np.exp(-a))


@pytest.mark.parametrize("k, n", [(2048, 3584), (1792, 2048)], ids=["gate_up", "down"])
def test_expert_matmul_kernel_in_interpret_mode_at_the_published_expert_width(k, n):
    """The two calls of a window at d 2048, I 1792 in the forms it makes them:
    gate | up with the SwiGLU on the way out, (rows, 1792); the down product
    with each row's weight, written as (rows, 16, 128). Three row tiles of
    128, two experts, one tile dead, a live tile whose last rows are dead."""
    rng = np.random.default_rng(k)
    x = rng.standard_normal((384, k)).astype(np.float32)
    w = rng.standard_normal((3, k, n)).astype(np.float32) / np.sqrt(k)
    tile_expert, tile_live = np.array([2, 0, 1], np.int32), np.array([128, 100, 0], np.int32)
    form = {"swiglu": True} if n == 3584 else {"row_weight": rng.uniform(0.1, 1, 384).astype(np.float32)}
    got = moe.expert_matmul(x, w, tile_expert, tile_live, 128, impl="pallas", interpret=True, **form)
    want = moe.expert_matmul(x, w, tile_expert, tile_live, 128, impl="einsum", **form)
    np.testing.assert_allclose(got, want, atol=1e-4)
    if n == 3584:
        assert got.shape == (384, 1792)
        np.testing.assert_allclose(got[:128], _silu(x[:128] @ w[2, :, :1792]) * (x[:128] @ w[2, :, 1792:]), atol=1e-4)
    else:
        assert got.shape == (384, 16, 128)
        np.testing.assert_allclose(np.asarray(got[:128]).reshape(128, n),
                                   (x[:128] @ w[2]) * form["row_weight"][:128, None], atol=1e-4)
        assert np.asarray(got[128:228]).any() and not np.asarray(got[228:256]).any()
        plain = moe.expert_matmul(x, w, tile_expert, tile_live, 128, impl="pallas", interpret=True)
        np.testing.assert_allclose(plain[:256], np.concatenate([x[:128] @ w[2], x[128:256] @ w[0]]), atol=1e-4)
    assert not np.asarray(got[256:]).any()


@pytest.mark.parametrize("inter", [1792, 512], ids=["lfm2", "qwen3_next"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_first_call_does_its_swiglu_on_the_way_out(inter, dtype):
    """K 2048 against both families' expert widths (the column block is 256 at
    either: 512 does not divide 1792): `silu(x @ gate) * (x @ up)` in float32,
    cast once to the type asked for; the kernel, the einsum form and the
    equation agree, and a dead tile comes back zero."""
    rng = np.random.default_rng(inter)
    x = jnp.asarray(rng.standard_normal((256, 2048)), dtype)
    w = jnp.asarray(rng.standard_normal((2, 2048, 2 * inter)) / np.sqrt(2048), dtype)
    tile_expert, tile_live = np.array([1, 0], np.int32), np.array([128, 0], np.int32)
    got = moe.expert_matmul(x, w, tile_expert, tile_live, 128, out_dtype=dtype, swiglu=True,
                            impl="pallas", interpret=True)
    einsum = moe.expert_matmul(x, w, tile_expert, tile_live, 128, out_dtype=dtype, swiglu=True, impl="einsum")
    assert got.shape == (256, inter) and got.dtype == dtype == einsum.dtype
    x32, w32 = np.asarray(x, np.float32), np.asarray(w[1], np.float32)
    want = _silu(x32[:128] @ w32[:, :inter]) * (x32[:128] @ w32[:, inter:])
    close = {"atol": 1e-4} if dtype == jnp.float32 else {"rtol": 2**-7, "atol": 1e-3}  # one rounding to 8 bits
    np.testing.assert_allclose(np.asarray(got[:128], np.float32), want, **close)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(einsum, np.float32), **close)
    assert not np.asarray(got[128:], np.float32).any()


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_a_dead_row_inside_a_live_tile_is_exactly_zero_when_its_product_is_inf(impl):
    """The weighted call masks with a `where`, not with a weight of zero:
    PR 28's first served run answered NaN, and `inf * 0` is one."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((16, 128)).astype(np.float32)
    x[5:8] = np.inf  # dead rows of the first tile: whatever the gather left there
    w = rng.standard_normal((2, 128, 256)).astype(np.float32)
    got = np.asarray(moe.expert_matmul(
        x, w, np.array([1, 0], np.int32), np.array([5, 8], np.int32), 8,
        row_weight=np.zeros(16, np.float32) + 0.5, impl=impl, interpret=True))
    assert got.shape == (16, 2, 128) and np.isfinite(got).all()
    assert not got[5:8].any() and got[:5].all() and got[8:].all()
    np.testing.assert_allclose(got[:5].reshape(5, 256), 0.5 * (x[:5] @ w[1]), atol=1e-4)


def _median_box_gap(module_dtype, params, pixels):
    """The module in `module_dtype` with its matrices held in it (as
    `zoo.hold_matrices_in` does) against the float32 reference on the same
    rounded matrices: the median gap over every box coordinate."""
    held = jax.tree_util.tree_map(
        lambda a: np.asarray(jnp.asarray(a, module_dtype)) if a.ndim >= 2 else a, params)
    got = served.Lfm2MoeDetector(CFG, dtype=module_dtype).apply({"params": held}, pixels)
    gaps = [np.abs(got["pred_boxes"][i] - ref.detector(held, pixels[i], CFG)["pred_boxes"])
            for i in range(len(pixels))]
    return float(np.median(np.stack(gaps)))


def test_bfloat16_policy_stays_under_a_bound_that_int8_and_float8_break(params, monkeypatch):
    """The served policy against the float32 reference, six layers deep: the
    median box gap under 0.005 of the image. Seen over three seeds of four
    images: bfloat16 0.0015-0.0019; the program's own lower-precision path
    (int8 projections, `utils/quant.py`) 0.0094-0.0103; the module in float8
    e4m3 0.021-0.024. The median, because a routed token that changes expert
    moves single boxes by a tenth under any rounding (the largest gap reads
    0.08-0.14 in bfloat16 and 0.14-0.26 in int8: it tells nothing apart)."""
    pixels = np.random.default_rng(11).standard_normal((4, *CFG.image_size, 3)).astype(np.float32)
    assert _median_box_gap(jnp.bfloat16, params, pixels) < 0.005
    assert _median_box_gap(jnp.float8_e4m3fn, params, pixels) > 0.005
    monkeypatch.setattr(quant, "INT8", True)
    monkeypatch.setattr(quant, "INT8_DENSE", True)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 1)
    monkeypatch.setattr(quant, "INT8_MIN_CH", 16)
    assert _median_box_gap(jnp.bfloat16, params, pixels) > 0.005


def test_detect_round_trip_and_counters_reach_metrics(monkeypatch):
    """`/detect` through the real engine, batcher and server at the tiny
    size; the program's counters, the new one among them, arrive in `/metrics`."""
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector
    from spotter_tpu.models.zoo import tiny_lfm2_moe_det_config
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.serving.standalone import make_app

    monkeypatch.setenv("SPOTTER_TPU_TINY", "1")
    tiny = tiny_lfm2_moe_det_config()

    async def run():
        built = build_detector("lfm2_moe_det_tiny")
        assert built.postprocess == "softmax" and not built.needs_mask
        engine = InferenceEngine(built, threshold=0.0, batch_buckets=(2, 4))
        detector = AmenitiesDetector(
            engine, MicroBatcher(engine, max_delay_ms=1.0), _jpeg_client())
        async with TestClient(TestServer(make_app(detector=detector))) as client:
            urls = [f"http://example.com/{i}.jpg" for i in range(3)]
            resp = await client.post("/detect", json={"image_urls": urls})
            assert resp.status == 200
            body = await resp.json()
            assert [i["url"] for i in body["images"]] == urls
            assert all("detections" in i for i in body["images"])
            snap = await (await client.get("/metrics")).json()
        assert snap["images_total"] == 3  # the padded slot is not counted
        per_image = tiny.num_tokens * tiny.num_experts_per_tok * (tiny.num_hidden_layers - tiny.num_dense_layers)
        assert snap["moe_assignments_total"] == 3 * per_image
        assert snap["moe_assignments_local_total"] == snap["moe_assignments_total"]  # all held
        assert snap["moe_expert_tokens_max_total"] >= snap["moe_expert_tokens_mean_total"] > 0
        # Flax's initial bias is zero: it moves nothing
        assert snap["moe_bias_moved_total"] == 0

    asyncio.run(run())


def test_a_live_bias_is_counted_through_the_engine(monkeypatch):
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    monkeypatch.setenv("SPOTTER_TPU_TINY", "1")
    built = build_detector("lfm2_moe_det_tiny")
    params = jax.tree_util.tree_map(np.asarray, built.params)
    for i in range(2, 6):
        params[f"layer{i}"]["feed_forward"]["expert_bias"] = np.linspace(-0.3, 0.3, 8, dtype=np.float32)
    engine = InferenceEngine(dataclasses.replace(built, params=params), threshold=0.0,
                             batch_buckets=(2,))
    engine.detect([Image.fromarray(np.full((40, 60, 3), 90, np.uint8))])
    snap = engine.metrics.snapshot()
    assert 0 < snap["moe_bias_moved_total"] < snap["moe_assignments_total"]


def test_checkpoint_directory_loads_without_torch(tmp_path, monkeypatch):
    """config.json + model.safetensors under transformers' names -> (config,
    params) through the direct reader: the conv taps transposed, w1 | w3 side
    by side and the experts stacked, the bias and the router in place."""
    import ml_dtypes

    from spotter_tpu.convert import loader

    monkeypatch.setenv("SPOTTER_TPU_CACHE", str(tmp_path / "cache"))
    cfg = dataclasses.replace(CFG, id2label=tuple((i, f"c{i}") for i in range(5)))
    rng = np.random.default_rng(1)
    d, tensors = cfg.hidden_size, {}

    def put(name, shape):
        tensors[name] = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)

    put("patch_embeddings.projection.weight", (d, 3, 16, 16))
    put("patch_embeddings.projection.bias", (d,))
    put("detection_tokens", (1, cfg.num_detection_tokens, d))
    put("embedding_norm.weight", (d,))
    for head, out in (("class_labels_classifier", 6), ("bbox_predictor", 4)):
        for i, width in enumerate((d, d, out)):
            put(f"{head}.layers.{i}.weight", (width, d))
            put(f"{head}.layers.{i}.bias", (width,))
    for i, kind in enumerate(cfg.layer_types):
        t = f"layers.{i}"
        put(f"{t}.operator_norm.weight", (d,))
        put(f"{t}.ffn_norm.weight", (d,))
        if kind == "full_attention":
            for proj, rows in (("q_proj", 64), ("k_proj", 32), ("v_proj", 32), ("out_proj", d)):
                put(f"{t}.self_attn.{proj}.weight", (rows, d))
            put(f"{t}.self_attn.q_layernorm.weight", (16,))
            put(f"{t}.self_attn.k_layernorm.weight", (16,))
        else:
            put(f"{t}.conv.in_proj.weight", (3 * d, d))
            put(f"{t}.conv.conv.weight", (d, 1, 3))
            put(f"{t}.conv.out_proj.weight", (d, d))
        prefixes = [f"{t}.feed_forward"]
        if i >= cfg.num_dense_layers:
            put(f"{t}.feed_forward.gate.weight", (8, d))
            put(f"{t}.feed_forward.expert_bias", (8,))
            prefixes = [f"{t}.feed_forward.experts.{e}" for e in range(8)]
        width = cfg.intermediate_size if i < cfg.num_dense_layers else cfg.moe_intermediate_size
        for prefix in prefixes:
            put(f"{prefix}.w1.weight", (width, d))
            put(f"{prefix}.w3.weight", (width, d))
            put(f"{prefix}.w2.weight", (d, width))
    ckpt = tmp_path / "lfm2_moe_det_handmade"
    ckpt.mkdir()
    _write_safetensors(ckpt / "model.safetensors", tensors)
    hf = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "id2label"}
    hf.update(id2label={str(i): f"c{i}" for i in range(5)}, head_dim=16, vocab_size=0)
    (ckpt / "config.json").write_text(json.dumps(hf))

    got_cfg, params = loader.load_lfm2_moe_det(str(ckpt))
    assert got_cfg == cfg
    conv = params["layer0"]["conv"]
    assert conv["in_proj"]["kernel"].dtype == ml_dtypes.bfloat16  # kept as read
    assert np.array_equal(conv["conv"], tensors["layers.0.conv.conv.weight"][:, 0].T)
    assert np.array_equal(params["layer1"]["feed_forward"]["w3"]["kernel"],
                          tensors["layers.1.feed_forward.w3.weight"].T)
    ffn = params["layer4"]["feed_forward"]
    assert ffn["experts_gate_up"].shape == (8, d, 64) and ffn["experts_down"].shape == (8, 32, d)
    assert np.array_equal(ffn["experts_gate_up"][3, :, :32], tensors["layers.4.feed_forward.experts.3.w1.weight"].T)
    assert np.array_equal(ffn["experts_gate_up"][3, :, 32:], tensors["layers.4.feed_forward.experts.3.w3.weight"].T)
    assert np.array_equal(ffn["experts_down"][5], tensors["layers.4.feed_forward.experts.5.w2.weight"].T)
    assert np.array_equal(ffn["router"], tensors["layers.4.feed_forward.gate.weight"].T)
    assert np.array_equal(ffn["expert_bias"], tensors["layers.4.feed_forward.expert_bias"])
    # the tree is the module's own: it applies, and a wrong head_dim is refused
    shapes = jax.eval_shape(lambda: served.Lfm2MoeDetector(cfg).init(
        jax.random.PRNGKey(0), np.zeros((1, *cfg.image_size, 3), np.float32))["params"])
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == jax.tree_util.tree_map(
        lambda a: a.shape, shapes)
    out = served.Lfm2MoeDetector(cfg).apply(
        {"params": params}, np.zeros((1, *cfg.image_size, 3), np.float32))
    assert np.isfinite(np.asarray(out["logits"])).all()
    with pytest.raises(ValueError, match="head_dim"):
        Lfm2MoeDetConfig.from_hf({**hf, "head_dim": 32})
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeDetConfig.from_hf({**hf, "num_hidden_layers": 5})
