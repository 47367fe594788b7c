"""`kimi_linear_det` (PR 35) on the CPU at tiny widths: the served modules
(`models/kimi_linear.py`: the chunked per-channel delta rule of `ops/kda.py`,
latent attention expanded a head, grouped experts through the window loop)
against the plain `jax.numpy` float32 reference
(`testing/kimi_linear_reference.py`: token by token, eager attention, a loop
over experts), mixer by mixer and the five layers together; the chunked rule
on gates that overflow a quotient of exponentials; its tie to the scalar-gated
rule; the router against the equations; the shares of an uncut layer; the
grouped product at hidden 2304 beside 2048; a bfloat16 policy's gap;
the registry, the loader, the engine, `/detect` and `/metrics`. hidden 64, five
layers (KDA, KDA, KDA, latent attention, KDA; one dense, four routed), 8
experts, top 2, 40 tokens.

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
from PIL import Image
from test_qwen3_next import _jpeg_client, _write_safetensors

from spotter_tpu.models import kimi_linear as served
from spotter_tpu.models import layers
from spotter_tpu.models.configs import KimiLinearDetConfig
from spotter_tpu.ops import kda, moe
from spotter_tpu.ops.delta_rule import chunked_gated_delta_rule
from spotter_tpu.testing import kimi_linear_reference as ref
from spotter_tpu.utils import quant

ATOL = 2e-4
CFG = KimiLinearDetConfig(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_attention_heads=4,
    kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, linear_head_dim=16,
    linear_num_heads=4, gate_low_rank_dim=8, num_routed_experts=8, num_experts=8,
    num_experts_per_token=2, image_size=(32, 80), patch_size=16, num_detection_tokens=30,
    num_labels=5,
)
TOKENS = CFG.num_tokens  # 10 patches + 30 detection tokens


def _randomise(params, seed=0):
    """Flax's initial values (norm weights 1, gates and bias 0, detection
    tokens 0) would hide wiring faults: every leaf gets seeded values of a sane
    scale, `A_log` and `dt_bias` as the authors draw them, the selection bias as
    wide as the gaps between a token's scores."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        rng = np.random.default_rng([seed, i])
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            value = np.log(rng.uniform(1.0, 16.0, leaf.shape))
        elif "dt_bias" in name:
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), leaf.shape))
            value = dt + np.log(-np.expm1(-dt))
        elif leaf.ndim >= 2:
            value = rng.standard_normal(leaf.shape) / np.sqrt(np.prod(leaf.shape[:-1]) / max(
                1, leaf.shape[0] if leaf.ndim == 3 else 1))
        elif "e_score_correction_bias" in name:
            value = rng.uniform(-0.2, 0.2, leaf.shape)
        else:
            value = rng.uniform(0.7, 1.3, leaf.shape)
        out.append(np.asarray(value, np.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def params():
    x = np.zeros((1, *CFG.image_size, 3), np.float32)
    return _randomise(served.KimiLinearDetector(CFG).init(jax.random.PRNGKey(0), x)["params"])


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).standard_normal((2, TOKENS, CFG.hidden_size)).astype(np.float32)


def _rule_inputs(seed=0, b=2, t=150, h=3, dk=16, dv=16):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = (unit(rng.standard_normal((b, t, h, dk))) * dk**-0.5).astype(np.float32)
    k = unit(rng.standard_normal((b, t, h, dk))).astype(np.float32)
    v = rng.standard_normal((b, t, h, dv)).astype(np.float32)
    beta = rng.uniform(0, 1, (b, t, h)).astype(np.float32)
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (b, t, h, dk)))
    g = (-rng.uniform(1, 16, (h, 1)) * step).astype(np.float32)  # the authors' initialisation
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta):
    return np.stack([ref.kda_rule(q[i], k[i], v[i], g[i], beta[i]) for i in range(len(q))])


@pytest.mark.parametrize("gates", ["authors_initialisation", "past_minus_200_in_a_chunk"])
def test_chunked_kda_against_the_recurrence_token_by_token(gates):
    """150 tokens (two chunks of 64 carry a state, the third is padded), three
    heads of 16 channels. On the second case four channels lose 5 a token
    (-320 a chunk), four lose 30 (-1920), the rest 1e-4: `exp(-G)` overflows
    float32 on the first and stays at 1 on the last, so a quotient of two
    exponentials reads inf or nan; the chunked form is finite and equal."""
    q, k, v, g, beta = _rule_inputs()
    if gates == "past_minus_200_in_a_chunk":
        g = g.copy()
        g[..., :4], g[..., 4:8], g[..., 8:] = -5.0, -30.0, -1e-4
        running = np.cumsum(g[:, :64], axis=1)
        assert running.min() < -200 and running[..., 8:].min() > -0.01
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(-running)).any()  # what a quotient form would divide by
    got = np.asarray(kda.chunked_kda(q, k, v, g, beta, impl="scan"))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _token_by_token(q, k, v, g, beta), atol=2e-6)


def test_one_gate_a_head_is_the_scalar_gated_delta_rule():
    q, k, v, g, beta = _rule_inputs(seed=1)
    scalar = g[..., 0]
    got = kda.chunked_kda(q, k, v, np.broadcast_to(scalar[..., None], g.shape), beta, impl="scan")
    want = chunked_gated_delta_rule(q, k, v, scalar, beta, impl="scan")
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("heads_per_step", [1, 3])
def test_the_pallas_body_in_interpret_mode_is_the_scan(heads_per_step):
    q, k, v, g, beta = _rule_inputs(seed=2, b=1, t=100)
    got = kda.chunked_kda(q, k, v, g, beta, impl="pallas", interpret=True,
                          heads_per_step=heads_per_step)
    np.testing.assert_allclose(got, kda.chunked_kda(q, k, v, g, beta, impl="scan"), atol=1e-6)


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_a_gate_left_to_the_chunks_is_the_gate_computed_outside(impl):
    """`RawGate`: the chunk computes `-exp(A_log) softplus(raw + dt_bias)` and
    its running sum itself (as the module asks, so that the float32 gate never
    lies in memory); a raw gate of unit scale makes -g reach tens a token."""
    q, k, v, _, beta = _rule_inputs(seed=3, b=1, t=100)
    rng = np.random.default_rng(3)
    raw = 2 * rng.standard_normal(q.shape).astype(np.float32)
    a_log = np.log(rng.uniform(1, 16, 3)).astype(np.float32)
    dt_bias = rng.uniform(-6, -2, 48).astype(np.float32)
    g = -np.exp(a_log)[:, None] * np.logaddexp(raw + dt_bias.reshape(3, 16), 0)
    assert g.min() < -20
    kwargs = {"impl": impl, "interpret": True, "heads_per_step": 3}
    got = kda.chunked_kda(q, k, v, kda.RawGate(raw, a_log, dt_bias), beta, **kwargs)
    # 5e-6: a softplus of 50 computed in float32 here and in float64 there differs in its
    # last bit, 4e-6 in an exponent
    np.testing.assert_allclose(got, kda.chunked_kda(q, k, v, g, beta, **kwargs), atol=5e-6)
    np.testing.assert_allclose(got, _token_by_token(q, k, v, g.astype(np.float32), beta), atol=5e-6)


def test_the_chunks_normalise_q_and_k_as_the_caller_would():
    """`normalise`: q and k as the convolutions leave them; each head's L2 norm
    (eps 1e-6) and q's scale are the chunk's work, so that XLA keeps no float32
    copy of either for the norm's two readers."""
    q, k, v, g, beta = _rule_inputs(seed=4, b=1, t=100)
    rng = np.random.default_rng(4)
    loose_q, loose_k = (rng.standard_normal(q.shape).astype(np.float32) * 3 for _ in range(2))

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    want = kda.chunked_kda(unit(loose_q) * 16**-0.5, unit(loose_k), v, g, beta, impl="scan")
    for kwargs in ({"impl": "scan"}, {"impl": "pallas", "interpret": True, "heads_per_step": 3}):
        got = kda.chunked_kda(loose_q, loose_k, v, g, beta, normalise=True, **kwargs)
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_chunk_that_is_no_power_of_two_is_refused():
    q, k, v, g, beta = _rule_inputs(b=1, t=20)
    with pytest.raises(ValueError, match="power of two"):
        kda.chunked_kda(q, k, v, g, beta, chunk=48)


@pytest.mark.parametrize("kind", ["kda", "latent_attention", "dense_mlp"])
def test_layer_kinds_one_by_one(params, tokens, kind):
    module, p, want = {
        "kda": (served.KimiDeltaAttention(CFG), params["layer1"]["self_attn"],
                lambda p, x: ref.kimi_delta_attention(p, x, CFG)),
        "latent_attention": (served.LatentAttention(CFG), params["layer3"]["self_attn"],
                             lambda p, x: ref.latent_attention(p, x, CFG)),
        "dense_mlp": (served.DenseMlp(CFG, CFG.intermediate_size), params["layer0"]["mlp"],
                      ref.dense_mlp),
    }[kind]
    got = module.apply({"params": p}, tokens)
    if kind == "kda":
        got, spread = got
        for i in range(2):  # the counter: the token-mean of max - min of -g, a head
            g = np.asarray(ref.kda_gate(p, tokens[i], CFG))
            np.testing.assert_allclose(spread[i], (g.max(-1) - g.min(-1)).mean(0), rtol=1e-5)
        assert np.asarray(spread).min() > 0.05  # a head's channels do decay apart
    for i in range(2):
        np.testing.assert_allclose(got[i], want(p, tokens[i]), atol=ATOL)


def test_kda_short_convs_are_causal_and_four_taps_wide(params, tokens):
    """A change at token 20 moves q, k, v of tokens 20-23 alone; through the
    state it then reaches every later token and no earlier one."""
    p = params["layer2"]["self_attn"]
    moved = tokens[:1].copy()
    moved[0, 20] += 1.0
    apply = served.KimiDeltaAttention(CFG).apply
    changed = np.abs(np.asarray(apply({"params": p}, moved)[0] - apply({"params": p}, tokens[:1])[0])[0])
    assert not (changed[:20].max(-1) > 1e-6).any() and (changed[20:].max(-1) > 1e-6).all()


def test_latent_attention_by_hand(params, tokens):
    """From the equations, with numpy alone: one shared `k_pe` for every head,
    no rotation on either side, keys of 24 (16 + 8) and values of 16."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params["layer3"]["self_attn"])
    x = tokens[0].astype(np.float64)
    t, heads, nope, pe, dv, rank = TOKENS, 4, 16, 8, 16, 24
    q = (x @ p["q_proj"]["kernel"]).reshape(t, heads, nope + pe)
    latent = x @ p["kv_a_proj_with_mqa"]["kernel"]
    c, k_pe = latent[:, :rank], latent[:, rank:]
    c = p["kv_a_layernorm"]["weight"] * c / np.sqrt((c * c).mean(-1, keepdims=True) + CFG.rms_norm_eps)
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(t, heads, nope + dv)
    out = np.zeros((t, heads, dv))
    for h in range(heads):
        scores = (q[:, h, :nope] @ kv[:, h, :nope].T + q[:, h, nope:] @ k_pe.T) / np.sqrt(nope + pe)
        scores = np.where(np.tril(np.ones((t, t), bool)), scores, -np.inf)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        out[:, h] = (weights / weights.sum(-1, keepdims=True)) @ kv[:, h, nope:]
    want = out.reshape(t, heads * dv) @ p["o_proj"]["kernel"]
    got = served.LatentAttention(CFG).apply({"params": params["layer3"]["self_attn"]}, tokens[:1])
    np.testing.assert_allclose(got[0], want, atol=ATOL)


def test_the_causal_kernel_takes_values_narrower_than_keys():
    """`causal_latent_attention` (the splash kernel, interpret mode) at keys of
    192 and values of 128, 300 tokens (padded to 384): eager attention's answer."""
    rng = np.random.default_rng(4)
    q = rng.standard_normal((1, 300, 2, 192)).astype(np.float32) * 192**-0.5
    k = rng.standard_normal((1, 300, 2, 192)).astype(np.float32)
    v = rng.standard_normal((1, 300, 2, 128)).astype(np.float32)
    got = layers.causal_latent_attention(q, k, v, interpret=True)
    scores = np.einsum("bqhd,bshd->bhqs", q, k)
    scores = np.where(np.tril(np.ones((300, 300), bool)), scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bhqs,bshd->bqhd", weights / weights.sum(-1, keepdims=True), v)
    assert got.shape == (1, 300, 2, 128)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_moe_layer_and_its_counters(params, tokens):
    p = params["layer2"]["block_sparse_moe"]
    got, counts, moved = served.SparseMoe(CFG).apply({"params": p}, tokens)
    k = CFG.num_experts_per_token
    for i in range(2):
        np.testing.assert_allclose(got[i], ref.sparse_moe(p, tokens[i], CFG), atol=ATOL)
        chosen = np.asarray(ref.routing_weights(p, tokens[i], CFG)) > 0
        assert (chosen.sum(-1) == k).all()
        np.testing.assert_array_equal(np.asarray(counts[i]), chosen.sum(0))
        assert 0 < int(moved[i]) < TOKENS * k
    assert int(counts.sum()) == 2 * TOKENS * k


def test_the_router_against_the_equations():
    """Three tokens over four experts, two a token, by hand: the bias enters the
    choice and not the weight; the weights are the plain sigmoids over (their
    sum + 1e-20), times 2.446."""
    logits = np.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.1, 0.2, 0.3], [-3.0] * 4], np.float32)
    bias = np.array([0.0, -0.5, 0.4, 0.0], np.float32)
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    scores = moe.router_scores(logits, np.eye(4, dtype=np.float32), "sigmoid")
    weights, experts = moe.select(scores, 2, True, bias=bias, eps=served.NORM_TOPK_EPS, scale=2.446)
    assert np.asarray(experts).tolist() == [[2, 0], [2, 3], [2, 0]]
    for t, (a, b) in enumerate(np.asarray(experts)):
        pair = np.array([s[t, a], s[t, b]])
        np.testing.assert_allclose(weights[t], 2.446 * pair / (pair.sum() + 1e-20), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.446, rtol=1e-6)  # 1e-20 is no damping
    assert np.asarray(moe.moved_by_bias(scores, experts)).tolist() == [1, 0, 1]


def test_four_shares_of_the_experts_and_one_shared_expert_add_up_to_the_uncut_layer(params, tokens):
    """Four chips, each told to hold a quarter of this router's eight experts
    (`expert_offset` 0, 2, 4, 6): their routed parts and the shared expert,
    counted once, add up to what the uncut reference gives for the layer."""
    p = params["layer4"]["block_sparse_moe"]
    x = tokens[0]
    total = np.array(ref.dense_mlp(p["shared_experts"], x))
    for offset in range(0, 8, 2):
        cut = dataclasses.replace(CFG, num_experts=2, expert_offset=offset)
        share = {**p, "experts_gate_up": p["experts_gate_up"][offset:offset + 2],
                 "experts_down": p["experts_down"][offset:offset + 2]}
        got, counts, _ = served.SparseMoe(cut).apply({"params": share}, x[None])
        part = np.asarray(got[0]) - np.asarray(ref.dense_mlp(p["shared_experts"], x))
        assert np.abs(part).max() > 0 and counts.shape == (1, 2)
        np.testing.assert_allclose(got[0], ref.sparse_moe(share, x, cut), atol=ATOL)
        total += part
    np.testing.assert_allclose(total, ref.sparse_moe(p, x, CFG), atol=ATOL)


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_expert_matmul_at_hidden_2304(impl):
    """The down product of a window at d 2304 = 18 x 128, I 1024: a token's sums
    are (18, 128), no whole (8, 128) tiles, and the weighted call writes the row
    whole against the expert's whole matrix (`ops/moe.py` as PR 34 left it: the
    arm that split the row into three parts of six lane tiles, a column block of
    768 a step, was 2 % slower on the chip and is not in the tree). Three row
    tiles of 128, two experts, one tile dead, a live tile whose last rows are
    dead."""
    rng = np.random.default_rng(9)
    x = rng.standard_normal((384, 1024)).astype(np.float32)
    w = rng.standard_normal((3, 1024, 2304)).astype(np.float32) / 32
    tile_expert, tile_live = np.array([2, 0, 1], np.int32), np.array([128, 100, 0], np.int32)
    weight = rng.uniform(0.1, 1, 384).astype(np.float32)
    got = np.asarray(moe.expert_matmul(x, w, tile_expert, tile_live, 128, row_weight=weight,
                                       impl=impl, interpret=True))
    assert got.shape == (384, 18, 128) == (384, *moe._sums_row(2304))
    flat = got.reshape(384, 2304)
    np.testing.assert_allclose(flat[:128], (x[:128] @ w[2]) * weight[:128, None], atol=1e-4)
    np.testing.assert_allclose(flat[128:228], (x[128:228] @ w[0]) * weight[128:228, None], atol=1e-4)
    assert not flat[228:].any()


def test_routed_experts_at_hidden_2304_against_a_loop_over_experts():
    """`routed_experts` whole at d 2304 (the sums carried as (tokens, 18,
    128)), interpret mode: a loop over the held experts' answer."""
    rng = np.random.default_rng(12)
    m, d, inter, held = 40, 2304, 128, 4
    x = rng.standard_normal((m, d)).astype(np.float32)
    gate_up = rng.standard_normal((held, d, 2 * inter)).astype(np.float32) / 48
    down = rng.standard_normal((held, inter, d)).astype(np.float32) / 11
    weights, experts = moe.route(x, rng.standard_normal((d, 8)).astype(np.float32) / 48, 2,
                                 scoring="sigmoid")
    got = moe.routed_experts(x, weights, experts, gate_up, down, offset=2, tile=8,
                             impl="pallas", interpret=True)
    want = np.zeros((m, d), np.float32)
    for e in range(held):
        hidden = x @ gate_up[e]
        y = (hidden[:, :inter] / (1 + np.exp(-hidden[:, :inter])) * hidden[:, inter:]) @ down[e]
        want += y * np.where(np.asarray(experts) == e + 2, np.asarray(weights), 0).sum(-1)[:, None]
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("d, row, block", [(2048, (16, 128), 1024), (2304, (18, 128), 2304)],
                         ids=["d2048", "d2304"])
def test_the_sums_layout_and_the_weighted_calls_block_follow_the_width(d, row, block):
    """`ops/moe.py` is PR 34's, line for line (so d 2048's programs and their
    numbers are the other routed cells' own): a token's sums are d / 128 lane
    tiles; the weighted call works 1024 columns a step where that divides d and
    the whole width where it does not. Read off the kernel's jaxpr."""
    hidden, w_down = jnp.zeros((256, 512), jnp.bfloat16), jnp.zeros((2, 512, d), jnp.bfloat16)
    te, tl = np.array([1, 0], np.int32), np.array([128, 5], np.int32)
    assert moe._sums_row(d) == row
    program = str(jax.make_jaxpr(lambda a, b, c: moe.expert_matmul(
        a, b, te, tl, 128, row_weight=c, impl="pallas"))(hidden, w_down, jnp.ones(256)))
    assert "expert_matmul_kernel" in program and f"f32[256,{row[0]},128]" in program
    assert f"bf16[1,512,{block}]" in program or f"(1, 512, {block})" in program, program[:3000]


def test_detector_end_to_end(params):
    pixels = np.random.default_rng(3).standard_normal((2, *CFG.image_size, 3)).astype(np.float32)
    got = jax.jit(served.KimiLinearDetector(CFG).apply)({"params": params}, pixels)
    assert got["moe_expert_tokens"].shape == (2, 4, 8)
    assert got["moe_assignments"].shape == got["moe_bias_moved"].shape == (2, 4)
    assert got["kda_gate_spread"].shape == (2, 4, 4) and np.asarray(got["kda_gate_spread"]).min() > 0
    assert (np.asarray(got["moe_assignments"]) == TOKENS * CFG.num_experts_per_token).all()
    for i in range(2):
        want = ref.detector(params, pixels[i], CFG)
        # 2e-3: four layers of routing amplify a last-bit difference a little;
        # a token that changes expert moves logits by whole units
        np.testing.assert_allclose(got["logits"][i], want["logits"], atol=2e-3)
        np.testing.assert_allclose(got["pred_boxes"][i], want["pred_boxes"], atol=ATOL)


def _median_box_gap(module_dtype, params, pixels):
    """The module in `module_dtype` with its matrices held in it (as
    `zoo.hold_matrices_in` does) against the float32 reference on the same
    rounded matrices: the median gap over every box coordinate."""
    held = jax.tree_util.tree_map(
        lambda a: np.asarray(jnp.asarray(a, module_dtype)) if a.ndim >= 2 else a, params)
    got = served.KimiLinearDetector(CFG, dtype=module_dtype).apply({"params": held}, pixels)
    gaps = [np.abs(got["pred_boxes"][i] - ref.detector(held, pixels[i], CFG)["pred_boxes"])
            for i in range(len(pixels))]
    return float(np.median(np.stack(gaps)))


def test_bfloat16_policy_stays_under_a_bound_that_int8_and_float8_break(params, monkeypatch):
    """The served policy against the float32 reference, five layers deep: the
    median box gap under 0.008 of the image. Seen over three seeds of four
    images: bfloat16 0.0037-0.0050; the program's own lower-precision path (int8
    projections, `utils/quant.py`) 0.0138-0.0195; the module in float8 e4m3
    0.044-0.048. The median, because a routed token that changes expert moves
    single boxes by a tenth under any rounding. (The short convs' SiLU runs in
    float32: in float8 its exponential overflows at -6.5 and the control read
    NaN, which tells nothing apart.)"""
    pixels = np.random.default_rng(11).standard_normal((4, *CFG.image_size, 3)).astype(np.float32)
    bound = 0.008
    served_gap = _median_box_gap(jnp.bfloat16, params, pixels)
    float8_gap = _median_box_gap(jnp.float8_e4m3fn, params, pixels)
    monkeypatch.setattr(quant, "INT8", True)
    monkeypatch.setattr(quant, "INT8_DENSE", True)
    monkeypatch.setattr(quant, "INT8_MIN_BATCH", 1)
    monkeypatch.setattr(quant, "INT8_MIN_CH", 16)
    int8_gap = _median_box_gap(jnp.bfloat16, params, pixels)
    assert served_gap < bound < min(float8_gap, int8_gap), (served_gap, int8_gap, float8_gap)


def test_detect_round_trip_and_counters_reach_metrics(monkeypatch):
    """`/detect` through the real engine, batcher and server at the tiny size;
    the program's counters, the gate's spread among them, arrive in `/metrics`."""
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector
    from spotter_tpu.models.zoo import tiny_kimi_linear_det_config
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.serving.standalone import make_app

    monkeypatch.setenv("SPOTTER_TPU_TINY", "1")
    tiny = tiny_kimi_linear_det_config()

    async def run():
        built = build_detector("kimi_linear_det_tiny")
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
        routed = tiny.num_hidden_layers - tiny.first_k_dense_replace
        per_image = tiny.num_tokens * tiny.num_experts_per_token * routed
        assert snap["moe_assignments_total"] == 3 * per_image
        assert 0 < snap["moe_assignments_local_total"] < snap["moe_assignments_total"]  # half held
        assert snap["moe_bias_moved_total"] == 0  # Flax's initial bias is zero
        assert snap["kda_gate_heads_total"] == 3 * len(tiny.kda_layers) * tiny.linear_num_heads
        assert snap["kda_gate_spread_total"] > 0  # a random f_b makes the channels differ

    asyncio.run(run())


def test_a_program_without_the_mixer_counts_no_gate(monkeypatch):
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    monkeypatch.setenv("SPOTTER_TPU_TINY", "1")
    engine = InferenceEngine(build_detector("lfm2_moe_det_tiny"), threshold=0.0, batch_buckets=(2,))
    engine.detect([Image.fromarray(np.zeros((40, 60, 3), np.uint8))])
    snap = engine.metrics.snapshot()
    assert snap["kda_gate_heads_total"] == 0 and snap["kda_gate_spread_total"] == 0
    assert snap["moe_assignments_total"] > 0


def test_checkpoint_directory_loads_without_torch(tmp_path, monkeypatch):
    """config.json + model.safetensors under the source's names -> (config,
    params) through the direct reader: the conv taps transposed, `A_log`
    flattened, w1 | w3 side by side and the held experts (2 .. 5 of 8) stacked,
    the bias and the router in place."""
    import ml_dtypes

    from spotter_tpu.convert import loader

    monkeypatch.setenv("SPOTTER_TPU_CACHE", str(tmp_path / "cache"))
    cfg = dataclasses.replace(CFG, num_experts=4, expert_offset=2,
                              id2label=tuple((i, f"c{i}") for i in range(5)))
    rng = np.random.default_rng(1)
    d, tensors = cfg.hidden_size, {}

    def put(name, shape):
        tensors[name] = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)

    def mlp(prefix, width, names=("gate_proj", "up_proj", "down_proj")):
        put(f"{prefix}.{names[0]}.weight", (width, d))
        put(f"{prefix}.{names[1]}.weight", (width, d))
        put(f"{prefix}.{names[2]}.weight", (d, width))

    put("patch_embeddings.projection.weight", (d, 3, 16, 16))
    put("patch_embeddings.projection.bias", (d,))
    put("detection_tokens", (1, cfg.num_detection_tokens, d))
    put("norm.weight", (d,))
    for head, out in (("class_labels_classifier", 6), ("bbox_predictor", 4)):
        for i, width in enumerate((d, d, out)):
            put(f"{head}.layers.{i}.weight", (width, d))
            put(f"{head}.layers.{i}.bias", (width,))
    for i in range(cfg.num_hidden_layers):
        t = f"layers.{i}"
        put(f"{t}.input_layernorm.weight", (d,))
        put(f"{t}.post_attention_layernorm.weight", (d,))
        a = f"{t}.self_attn"
        if cfg.layer_kind(i) == "kda":
            for proj, rows, cols in (("q_proj", 64, d), ("k_proj", 64, d), ("v_proj", 64, d),
                                     ("f_a_proj", 8, d), ("f_b_proj", 64, 8), ("b_proj", 4, d),
                                     ("g_a_proj", 8, d), ("g_b_proj", 64, 8), ("o_proj", d, 64)):
                put(f"{a}.{proj}.weight", (rows, cols))
            for name in ("q", "k", "v"):
                put(f"{a}.{name}_conv1d.weight", (64, 1, 4))
            put(f"{a}.A_log", (1, 1, 4, 1))
            put(f"{a}.dt_bias", (64,))
            put(f"{a}.o_norm.weight", (16,))
        else:
            for proj, rows, cols in (("q_proj", 96, d), ("kv_a_proj_with_mqa", 32, d),
                                     ("kv_b_proj", 128, 24), ("o_proj", d, 64)):
                put(f"{a}.{proj}.weight", (rows, cols))
            put(f"{a}.kv_a_layernorm.weight", (24,))
        if i < cfg.first_k_dense_replace:
            mlp(f"{t}.mlp", cfg.intermediate_size)
        else:
            m = f"{t}.block_sparse_moe"
            put(f"{m}.gate.weight", (8, d))
            put(f"{m}.gate.e_score_correction_bias", (8,))
            mlp(f"{m}.shared_experts", cfg.moe_intermediate_size)
            for e in range(8):  # a whole layer's experts: the loader takes its share
                mlp(f"{m}.experts.{e}", cfg.moe_intermediate_size, ("w1", "w3", "w2"))
    ckpt = tmp_path / "kimi_linear_det_handmade"
    ckpt.mkdir()
    _write_safetensors(ckpt / "model.safetensors", tensors)
    flat = {"kda_layers", "full_attn_layers", "linear_head_dim", "linear_num_heads",
            "linear_conv_kernel", "id2label"}
    hf = {k: v for k, v in dataclasses.asdict(cfg).items() if k not in flat}
    hf.update(id2label={str(i): f"c{i}" for i in range(5)}, vocab_size=0, q_lora_rank=None,
              mla_use_nope=True, linear_attn_config={
                  "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "head_dim": 16,
                  "num_heads": 4, "short_conv_kernel_size": 4})
    (ckpt / "config.json").write_text(json.dumps(hf))

    got_cfg, params = loader.load_kimi_linear_det(str(ckpt))
    assert got_cfg == cfg
    mixer = params["layer0"]["self_attn"]
    assert mixer["q_proj"]["kernel"].dtype == ml_dtypes.bfloat16  # kept as read
    assert np.array_equal(mixer["k_conv"], tensors["layers.0.self_attn.k_conv1d.weight"][:, 0].T)
    assert np.array_equal(mixer["A_log"], tensors["layers.0.self_attn.A_log"].reshape(4))
    assert np.array_equal(mixer["f_b_proj"]["kernel"], tensors["layers.0.self_attn.f_b_proj.weight"].T)
    assert np.array_equal(params["layer3"]["self_attn"]["kv_b_proj"]["kernel"],
                          tensors["layers.3.self_attn.kv_b_proj.weight"].T)
    ffn = params["layer4"]["block_sparse_moe"]
    assert ffn["experts_gate_up"].shape == (4, d, 64) and ffn["experts_down"].shape == (4, 32, d)
    w1 = tensors["layers.4.block_sparse_moe.experts.5.w1.weight"]  # held expert 3 is expert 5
    assert np.array_equal(ffn["experts_gate_up"][3, :, :32], w1.T)
    assert np.array_equal(ffn["experts_gate_up"][3, :, 32:],
                          tensors["layers.4.block_sparse_moe.experts.5.w3.weight"].T)
    assert np.array_equal(ffn["experts_down"][0], tensors["layers.4.block_sparse_moe.experts.2.w2.weight"].T)
    assert np.array_equal(ffn["router"], tensors["layers.4.block_sparse_moe.gate.weight"].T)
    assert np.array_equal(ffn["shared_experts"]["up_proj"]["kernel"],
                          tensors["layers.4.block_sparse_moe.shared_experts.up_proj.weight"].T)
    # the tree is the module's own: it applies; what the family cannot compute is refused
    shapes = jax.eval_shape(lambda: served.KimiLinearDetector(cfg).init(
        jax.random.PRNGKey(0), np.zeros((1, *cfg.image_size, 3), np.float32))["params"])
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == jax.tree_util.tree_map(
        lambda a: a.shape, shapes)
    out = served.KimiLinearDetector(cfg).apply(
        {"params": params}, np.zeros((1, *cfg.image_size, 3), np.float32))
    assert np.isfinite(np.asarray(out["logits"])).all()
    with pytest.raises(ValueError, match="q_lora_rank"):
        KimiLinearDetConfig.from_hf({**hf, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="each of 6 layers once"):
        KimiLinearDetConfig.from_hf({**hf, "num_hidden_layers": 6})
