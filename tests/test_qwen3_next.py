"""`qwen3_next_det` (PR 28) on the CPU at tiny widths: the served modules
(`models/qwen3_next.py`: chunked delta rule, grouped experts) against the
plain `jax.numpy` float32 reference (`testing/qwen3_next_reference.py`: token
by token, eager attention, a loop over experts), layer by layer and end to
end; the share test; the ops alone; the registry, the engine, `/detect` and
`/metrics`. hidden 64, 4 layers, 16 routed experts in 2 shares of 8, top 4,
40 tokens: not a multiple of the chunk (16 here, so that three chunks carry
a state and the last is padded).

Tolerances: both sides are float32 at the highest matmul precision (conftest),
so they differ by summation order only: the chunked form sums a chunk's
tokens in one product where the reference adds them one by one, over four
layers. 2e-4 absolute on activations of order 1-10 is some ten times what is
seen (1e-5), and a wrong decay, mask, expert or weight reads 1e-2 and more.
"""

import asyncio
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from PIL import Image

from spotter_tpu.models import qwen3_next as served
from spotter_tpu.models.configs import Qwen3NextDetConfig
from spotter_tpu.ops import delta_rule, moe
from spotter_tpu.testing import qwen3_next_reference as ref

ATOL = 2e-4
CFG = Qwen3NextDetConfig(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_key_head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=16, moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_routed_experts=16, num_experts=8, expert_offset=0, num_experts_per_tok=4,
    image_size=(32, 80), patch_size=16, num_detection_tokens=30, num_labels=5,
)
TOKENS = CFG.num_tokens  # 10 patches + 30 detection tokens


def _randomise(params, seed=0):
    """Flax's zeros (norm weights, detection tokens, A_log) would hide wiring
    faults: every leaf gets seeded values of a sane scale."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        rng = np.random.default_rng([seed, i])
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            value = np.log(rng.uniform(0.5, 4.0, leaf.shape))
        elif "dt_bias" in name:
            value = rng.uniform(-2.0, 0.0, leaf.shape)
        elif leaf.ndim >= 2:
            value = rng.standard_normal(leaf.shape) / np.sqrt(np.prod(leaf.shape[:-1]) / max(
                1, leaf.shape[0] if leaf.ndim == 3 else 1))
        else:
            value = rng.uniform(-0.3, 0.3, leaf.shape)
        out.append(np.asarray(value, np.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def params():
    x = np.zeros((1, *CFG.image_size, 3), np.float32)
    return _randomise(served.Qwen3NextDetector(CFG).init(jax.random.PRNGKey(0), x)["params"])


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).standard_normal((2, TOKENS, CFG.hidden_size)).astype(np.float32)


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    monkeypatch.setattr(delta_rule, "CHUNK", 16)


def test_gated_delta_net_layer(params, tokens):
    p = params["layer0"]["linear_attn"]
    got = served.GatedDeltaNet(CFG).apply({"params": p}, tokens)
    for i in range(2):
        np.testing.assert_allclose(got[i], ref.gated_delta_net(p, tokens[i], CFG), atol=ATOL)


def test_gated_attention_layer(params, tokens):
    p = params["layer3"]["self_attn"]
    got = served.GatedAttention(CFG).apply({"params": p}, tokens)
    for i in range(2):
        np.testing.assert_allclose(got[i], ref.gated_attention(p, tokens[i], CFG), atol=ATOL)


def test_moe_layer_and_its_counts(params, tokens):
    p = params["layer1"]["mlp"]
    got, counts = served.SparseMoe(CFG).apply({"params": p}, tokens)
    for i in range(2):
        want = ref.sparse_moe(p, tokens[i], CFG, held=(0, CFG.num_experts))
        np.testing.assert_allclose(got[i], want, atol=ATOL)
    # the counter: the selections that fell on each held expert, per image
    probs = jax.nn.softmax(tokens @ np.asarray(p["router"]), axis=-1)
    chosen = np.argsort(-np.asarray(probs), axis=-1)[..., :CFG.num_experts_per_tok]
    want_counts = np.stack([[(chosen[i] == e).sum() for e in range(CFG.num_experts)] for i in (0, 1)])
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    assert 0 < want_counts.sum() < 2 * TOKENS * CFG.num_experts_per_tok


def test_shares_add_up_to_the_uncut_layer(params, tokens):
    """The routed parts of all the shares, plus the shared expert counted
    once, equal the uncut reference's layer: two chips of 8 experts each
    against one holding all 16."""
    rng = np.random.default_rng(9)
    p = dict(params["layer2"]["mlp"])
    whole = dict(p)
    whole["experts_gate_up"] = rng.standard_normal((16, 64, 64)).astype(np.float32) / 8
    whole["experts_down"] = rng.standard_normal((16, 32, 64)).astype(np.float32) / 6
    x = tokens[0]
    total = np.zeros_like(x)
    for offset in (0, 8):
        cfg = dataclasses.replace(CFG, expert_offset=offset)
        share = dict(p, experts_gate_up=whole["experts_gate_up"][offset:offset + 8],
                     experts_down=whole["experts_down"][offset:offset + 8])
        out, counts = served.SparseMoe(cfg).apply({"params": share}, x[None])
        total += np.asarray(out[0])
        assert int(counts.sum()) > 0
    total -= np.asarray(ref.shared_expert(p, x))  # every chip computes it; count it once
    uncut = ref.sparse_moe(whole, x, dataclasses.replace(CFG, num_experts=16), held=None)
    np.testing.assert_allclose(total, uncut, atol=ATOL)


def test_detector_end_to_end(params):
    pixels = np.random.default_rng(3).standard_normal((2, *CFG.image_size, 3)).astype(np.float32)
    got = jax.jit(served.Qwen3NextDetector(CFG).apply)({"params": params}, pixels)
    assert got["moe_expert_tokens"].shape == (2, 4, 8) and got["moe_assignments"].shape == (2, 4)
    assert int(got["moe_assignments"][0, 0]) == TOKENS * CFG.num_experts_per_tok
    for i in range(2):
        want = ref.detector(params, pixels[i], CFG)
        # 2e-3: four layers of routing amplify a last-bit difference a little;
        # a token that changes expert moves logits by whole units
        np.testing.assert_allclose(got["logits"][i], want["logits"], atol=2e-3)
        np.testing.assert_allclose(got["pred_boxes"][i], want["pred_boxes"], atol=ATOL)


# rep: the value heads a key head serves: 2 takes them as a pair, 1 and 3 one at a time
@pytest.mark.parametrize("tokens_n,chunk,rep", [(40, 16, 2), (64, 64, 2), (150, 64, 2),
                                                 (40, 16, 1), (150, 64, 3), (100, 16, 4)])
def test_chunked_delta_rule_against_token_by_token(tokens_n, chunk, rep):
    rng = np.random.default_rng(tokens_n)
    hk, dk, dv = 2, 16, 24
    hv = hk * rep
    q, k = (rng.standard_normal((1, tokens_n, hk, dk)) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((1, tokens_n, hv, dv))
    g = -np.exp(rng.standard_normal((1, tokens_n, hv)))
    beta = 1 / (1 + np.exp(-rng.standard_normal((1, tokens_n, hv))))
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    got = delta_rule.chunked_gated_delta_rule(f(q), f(k), f(v), f(g), f(beta), chunk=chunk)
    want = ref.delta_rule(
        f(np.repeat(q[0], rep, 1)), f(np.repeat(k[0], rep, 1)), f(v[0]), f(g[0]), f(beta[0]))
    np.testing.assert_allclose(got[0], want, atol=1e-5)


def _strictly_lower(kind, c, rng):
    """The X of a chunk, as `chunk_step` forms it: -beta (k_i . k_j) decay
    below the diagonal, of noise or of tokens that resemble each other."""
    if kind == "random":
        k = rng.standard_normal((c, 16))
    else:
        k = rng.standard_normal((1, 16)) + 0.02 * rng.standard_normal((c, 16))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    gc = np.cumsum(-rng.uniform(1e-3, 0.3, c))
    x = -rng.uniform(0.5, 1.0, (c, 1)) * (k @ k.T) * np.exp(gc[:, None] - gc[None])
    return np.tril(x, -1).astype(np.float32)


@pytest.mark.parametrize("c", [16, 64])
@pytest.mark.parametrize("kind", ["random", "resembling"])
def test_the_inverse_of_a_pair_side_by_side_is_each_heads_own(kind, c):
    """[X1 | X2] (C, 2C) through the ten products that serve both heads
    gives [T1 | T2], the two (C, C) solves; and each is (I - X)^-1."""
    rng = np.random.default_rng(c)
    x1, x2 = _strictly_lower(kind, c, rng), _strictly_lower(kind, c, rng)
    alone = delta_rule._unit_lower_inverse([x1, x2], *delta_rule._lower_masks(c, c))
    (pair,) = delta_rule._unit_lower_inverse(
        [np.concatenate([x1, x2], 1)], *delta_rule._lower_masks(c, 2 * c))
    assert pair.shape == (c, 2 * c)
    np.testing.assert_allclose(pair, np.concatenate(alone, 1), atol=1e-6)
    for t, x in zip(alone, (x1, x2)):
        want = np.linalg.inv(np.eye(c) - x.astype(np.float64))
        np.testing.assert_allclose(t, want, atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("rep", [2, 1])
def test_delta_rule_kernel_in_interpret_mode_is_the_scan(rep):
    """The Pallas kernel's body is `chunk_step`, the function the scan runs:
    at the published head widths (128) the two agree to rounding, where a key
    head's two value heads go side by side (rep 2, the published ratio) and
    where each head goes alone (rep 1)."""
    rng = np.random.default_rng(1)
    b, t, hk, d = 1, 100, 2, 128
    hv = hk * rep
    q, k = (rng.standard_normal((b, t, hk, d)).astype(np.float32) * d**-0.5 for _ in range(2))
    v = rng.standard_normal((b, t, hv, d)).astype(np.float32)
    g = -np.exp(rng.standard_normal((b, t, hv))).astype(np.float32)
    beta = rng.uniform(0, 1, (b, t, hv)).astype(np.float32)
    scan = delta_rule.chunked_gated_delta_rule(q, k, v, g, beta, chunk=64, impl="scan")
    kernel = delta_rule.chunked_gated_delta_rule(
        q, k, v, g, beta, chunk=64, impl="pallas", interpret=True)
    np.testing.assert_allclose(kernel, scan, atol=1e-5)


def _per_token_loop(x, weights, experts, gate_up, down, offset):
    """What `routed_experts` must give: each token's held choices, one by one."""
    n_local, inter = down.shape[:2]
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = int(experts[t, j]) - offset
            if 0 <= e < n_local:
                h = x[t] @ gate_up[e]
                want[t] += float(weights[t, j]) * ((h[:inter] / (1 + np.exp(-h[:inter])) * h[inter:]) @ down[e])
    return want


def _expert_layer(d, m=90, inter=16, seed=2):
    rng = np.random.default_rng(seed)
    wider = np.sqrt(d / 32)  # keeps the logits and the hidden values at the scale they have at 32
    x = rng.standard_normal((m, d)).astype(np.float32)
    router = rng.standard_normal((d, 8)).astype(np.float32) / wider
    router[:, 5] += 3 * np.sign(router[:, 5]) / wider  # expert 5 draws a crowd
    gate_up = rng.standard_normal((4, d, 2 * inter)).astype(np.float32) / (6 * wider)
    down = rng.standard_normal((4, inter, d)).astype(np.float32) / 4
    return x, router, gate_up, down


# 32: the sums stay (tokens, d); 256: they are carried as (tokens, 2, 128)
# the router PR 28 had, and PR 33's: sigmoid scores, a bias on the choice alone
ROUTERS = {"softmax": {}, "sigmoid_biased": {
    "scoring": "sigmoid", "bias": np.linspace(-0.3, 0.3, 8, dtype=np.float32), "eps": 1e-6}}


@pytest.mark.parametrize("router_kind", ROUTERS)
@pytest.mark.parametrize("d", [32, 256])
@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_routed_experts_never_drop_a_token(impl, d, router_kind):
    """Windows smaller than the routed rows, a skewed router (most tokens on
    one expert), both implementations, both layouts of the sums, both
    routers: every held assignment is computed."""
    k = 3
    x, router, gate_up, down = _expert_layer(d)
    weights, experts = moe.route(x, router, k, **ROUTERS[router_kind])
    got = moe.routed_experts(x, weights, experts, gate_up, down, offset=4, tile=8,
                             window_rows=32, impl=impl, interpret=True)
    assert got.shape == x.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _per_token_loop(x, weights, experts, gate_up, down, 4), atol=1e-4)
    counts = moe.held_tokens(experts.reshape(1, -1), 4, 4)
    assert int(counts.sum()) == int(((experts >= 4) & (experts < 8)).sum())


@pytest.mark.parametrize("case", ["one_window", "partly_dead_last_window", "same_held_experts"])
def test_tiled_sums_add_every_row_once(case):
    """The tiled width (256) against the per-token loop: one window that holds
    every padded row; many windows, the last partly dead; and tokens that all
    choose the same held experts, so that one expert's rows span several
    windows and every token is added to k times."""
    k, tile, offset = 3, 8, 4
    x, router, gate_up, down = _expert_layer(256)
    m = x.shape[0]
    weights, experts = moe.route(x, router, k)
    window_rows = {"one_window": 4096, "partly_dead_last_window": 40, "same_held_experts": 64}[case]
    if case == "same_held_experts":
        experts = jnp.broadcast_to(jnp.array([6, 4, 7], jnp.int32), (m, k))
    got = moe.routed_experts(x, weights, experts, gate_up, down, offset=offset, tile=tile,
                             window_rows=window_rows, impl="einsum")
    held = np.asarray(moe.held_tokens(experts.reshape(1, -1), offset, 4))[0]
    padded = int((-(-held // tile) * tile).sum())
    if case == "one_window":
        assert padded <= window_rows
    elif case == "partly_dead_last_window":
        assert padded > 3 * window_rows and padded % window_rows  # several windows, a dead tail
    else:
        assert held.tolist() == [m, 0, m, m] and m > window_rows  # an expert spans windows
    np.testing.assert_allclose(
        got, _per_token_loop(x, weights, experts, gate_up, down, offset), atol=1e-4)


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("d, row", [(2048, (16, 128)), (32, (32,))])
def test_the_jitted_layer_carries_its_sums_in_the_layout_of_the_width(d, row):
    """The layout is a matter of device time alone, so no CPU test of values
    sees it go: read it from the jaxpr, traced with abstract inputs. At the
    published width the window loop's float32 carry and the scatter-add's
    operand are (tokens, 16, 128); at 32 they are (tokens, 32)."""
    m, k, n_local, inter = 512, 10, 8, 64
    args = (jax.ShapeDtypeStruct((m, d), jnp.bfloat16), jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.int32),
            jax.ShapeDtypeStruct((n_local, d, 2 * inter), jnp.bfloat16),
            jax.ShapeDtypeStruct((n_local, inter, d), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(functools.partial(moe.routed_experts, impl="einsum"))(*args)
    (loop,) = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "while"]
    carries = [v.aval.shape for v in loop.outvars if v.aval.dtype == jnp.float32]
    operands = [eqn.invars[0].aval.shape for eqn in _all_eqns(loop.params["body_jaxpr"].jaxpr)
                if eqn.primitive.name == "scatter-add"]
    assert carries == [(m, *row)] and operands == [(m, *row)]
    assert jaxpr.out_avals[0].shape == (m, d) and jaxpr.out_avals[0].dtype == jnp.float32


def test_a_window_searches_nothing_and_reads_no_table_of_the_experts():
    """A tile belongs to one expert, so the plan is made by the tile once a
    layer: the window loop's body holds no nested `while` (the search for each
    row's expert) and no gather from a table of `n_local` entries (a row's
    padded start, count, first assignment). What it still gathers: the sorted
    assignments, the tokens' rows, their weights (and, in the einsum form, the
    tiles' matrices)."""
    m, k, n_local, inter, d = 512, 10, 8, 64, 256
    args = (jax.ShapeDtypeStruct((m, d), jnp.bfloat16), jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.int32),
            jax.ShapeDtypeStruct((n_local, d, 2 * inter), jnp.bfloat16),
            jax.ShapeDtypeStruct((n_local, inter, d), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(functools.partial(moe.routed_experts, impl="einsum"))(*args)
    (loop,) = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "while"]
    body = list(_all_eqns(loop.params["body_jaxpr"].jaxpr))
    assert not [eqn for eqn in body if eqn.primitive.name == "while"]
    gathered = [eqn.invars[0].aval.shape for eqn in body if eqn.primitive.name == "gather"]
    assert sorted(gathered) == sorted([(m * k,), (m * k,), (m, d), (n_local, d, 2 * inter), (n_local, inter, d)])
    assert [eqn.primitive.name for eqn in body].count("dynamic_slice") == 3  # the window's tiles of the plan


def _plan_by_row(counts, start, tile, rows):
    """The plan a row at a time, as PR 29's window made it: each padded row's
    expert by a search over the padded ends, then its place and whether it is
    live from the experts' tables."""
    padded = -(-counts // tile) * tile
    padded_end = np.cumsum(padded)
    r = np.arange(rows)
    e = np.minimum(np.searchsorted(padded_end, r, side="right"), len(counts) - 1)
    within = r - (padded_end - padded)[e]
    live = (within < counts[e]) & (r < padded_end[-1])
    return e, np.where(live, start[e] + within, 0), live


@pytest.mark.parametrize("counts", [
    [70, 3, 1, 2], [9, 0, 0, 14], [16, 8, 24, 5], [5, 6, 0, 0], [0, 0, 0, 0]],
    ids=["skewed", "experts_with_no_rows", "ends_exactly_on_a_tile", "a_dead_last_window", "nothing_held"])
def test_the_plan_by_the_tile_is_the_plan_by_the_row(counts):
    """`_plan_by_tile` against the per-row plan written out in numpy, over
    whole windows of 4 tiles of 8 rows to the worst case's end."""
    tile, per_window = 8, 4
    counts = np.asarray(counts, np.int32)
    start = (np.cumsum(counts) - counts).astype(np.int32)
    worst = -(-(int(counts.sum()) + len(counts) * (tile - 1)) // tile)
    tiles = -(-(worst + 9) // per_window) * per_window  # and windows past every live row
    expert, first, live_rows = map(np.asarray, moe._plan_by_tile(counts, start, tile, tiles))
    e, source, live = _plan_by_row(counts, start, tile, tiles * tile)
    lane = np.arange(tile)
    np.testing.assert_array_equal((lane < live_rows[:, None]).reshape(-1), live)
    np.testing.assert_array_equal(np.where(live, (first[:, None] + lane).reshape(-1), 0), source)
    np.testing.assert_array_equal(expert[live_rows > 0], e[::tile][live_rows > 0])
    assert expert.min() >= 0 and expert.max() < len(counts)
    assert live.sum() == counts.sum() and not live_rows[int((-(-counts // tile)).sum()):].any()


# Qwen3-Next's shape class (a lane tile and more a head) and LFM2's (half a lane tile, four to a group)
@pytest.mark.parametrize("h, kv, hd", [(4, 2, 128), (8, 2, 64)])
def test_causal_gqa_kernel_in_interpret_mode(h, kv, hd):
    """The splash kernel's multi-query form under a causal mask, as the TPU
    path calls it, against eager attention (interpret mode, CPU)."""
    from spotter_tpu.models.layers import causal_gqa_attention

    rng = np.random.default_rng(4)
    b, s = 1, 200
    q = rng.standard_normal((b, s, h, hd)).astype(np.float32) * hd**-0.5
    k, v = (rng.standard_normal((b, s, kv, hd)).astype(np.float32) for _ in range(2))
    got = causal_gqa_attention(q, k, v, interpret=True)
    kk, vv = np.repeat(k, h // kv, 2), np.repeat(v, h // kv, 2)
    scores = np.einsum("bqhd,bkhd->bhqk", q, kk)
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, np.einsum("bhqk,bkhd->bqhd", probs, vv), atol=2e-5)


@pytest.mark.parametrize("name, family", [
    ("qwen3-next-det-ep8", "qwen3_next_det"), ("/ckpt/qwen3_next_det_ep8-0123abcd", "qwen3_next_det"),
    ("org/Qwen3_Next_Det", "qwen3_next_det"), ("lfm2-moe-det-pp4", "lfm2_moe_det"),
    ("/root/repo/.bench_work/checkpoints/lfm2_moe_det_pp4-0123abcd", "lfm2_moe_det"),
    ("org/LFM2_MoE_Det", "lfm2_moe_det")])
def test_registry_resolves_the_family(name, family):
    from spotter_tpu.models.registry import family_for

    assert family_for(name).name == family


def test_registry_keeps_yolos_and_rtdetr():
    from spotter_tpu.models.registry import family_for

    assert family_for("hustvl/yolos-base").name == "yolos"
    assert family_for("rtdetr_v2_r101vd").name == "rtdetr"


def _jpeg_client():
    from io import BytesIO
    from unittest.mock import AsyncMock

    import httpx

    img = Image.fromarray(np.random.default_rng(0).integers(0, 255, (40, 60, 3), dtype=np.uint8))
    buf = BytesIO()
    img.save(buf, format="JPEG")
    resp = AsyncMock()
    resp.content = buf.getvalue()
    resp.raise_for_status = lambda: None
    client = AsyncMock(spec=httpx.AsyncClient)
    client.get.return_value = resp
    return client


def test_detect_round_trip_and_counters_reach_metrics(monkeypatch):
    """`/detect` through the real engine, batcher and server at the tiny
    size; the program's expert counters arrive in `/metrics`."""
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector
    from spotter_tpu.models.zoo import tiny_qwen3_next_det_config
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.serving.standalone import make_app

    monkeypatch.setenv("SPOTTER_TPU_TINY", "1")
    tiny = tiny_qwen3_next_det_config()

    async def run():
        built = build_detector("qwen3_next_det_tiny")
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
        images = snap["images_total"]
        assert images == 3  # the padded slot is not counted
        per_image = tiny.num_tokens * tiny.num_experts_per_tok * tiny.num_hidden_layers
        assert snap["moe_assignments_total"] == images * per_image
        assert 0 < snap["moe_assignments_local_total"] < snap["moe_assignments_total"]
        assert snap["moe_expert_tokens_max_total"] >= snap["moe_expert_tokens_mean_total"] > 0

    asyncio.run(run())


def test_families_without_experts_report_zero_counters(monkeypatch):
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    monkeypatch.setenv("SPOTTER_TPU_TINY", "1")
    engine = InferenceEngine(build_detector("hustvl/yolos-base"), threshold=0.0, batch_buckets=(2,))
    engine.detect([Image.fromarray(np.zeros((40, 60, 3), np.uint8))])
    snap = engine.metrics.snapshot()
    assert snap["images_total"] == 1 and snap["moe_assignments_total"] == 0


def test_matrices_are_held_in_the_policy_type(monkeypatch):
    """bfloat16 policy: the tree the engine keeps, attests and re-places
    holds its matrices in bfloat16 and its vectors in float32."""
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    monkeypatch.setenv("SPOTTER_TPU_TINY", "1")
    monkeypatch.setenv("SPOTTER_TPU_DTYPE", "bfloat16")
    built = build_detector("qwen3_next_det_tiny")
    for path, leaf in jax.tree_util.tree_leaves_with_path(built.params):
        want = jnp.bfloat16 if leaf.ndim >= 2 else np.float32
        assert leaf.dtype == want, jax.tree_util.keystr(path)
    engine = InferenceEngine(built, threshold=0.0, batch_buckets=(2,))
    assert engine.attest()["ok"]
    out = engine.detect([Image.fromarray(np.full((40, 60, 3), 90, np.uint8))])
    assert len(out) == 1


def _write_safetensors(path, tensors):
    import json
    import struct

    header, blobs, at = {}, [], 0
    names = {"float32": "F32", "bfloat16": "BF16", "int32": "I32"}
    for name, value in tensors.items():
        raw = np.ascontiguousarray(value).tobytes()
        header[name] = {"dtype": names[str(value.dtype)], "shape": list(value.shape),
                        "data_offsets": [at, at + len(raw)]}
        blobs.append(raw)
        at += len(raw)
    head = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(head)) + head + b"".join(blobs))


def test_safetensors_are_read_straight_into_numpy(tmp_path):
    import ml_dtypes

    from spotter_tpu.convert.loader import read_safetensors_numpy

    rng = np.random.default_rng(0)
    tensors = {"a.weight": rng.standard_normal((3, 5)).astype(ml_dtypes.bfloat16),
               "b": rng.standard_normal((7,)).astype(np.float32),
               "c": np.arange(6, dtype=np.int32).reshape(2, 3)}
    _write_safetensors(tmp_path / "model.safetensors", tensors)
    got = read_safetensors_numpy(tmp_path / "model.safetensors")
    assert set(got) == set(tensors)
    for name, value in tensors.items():
        assert got[name].dtype == value.dtype and np.array_equal(got[name], value), name


def test_checkpoint_directory_loads_without_torch_and_caches(tmp_path, monkeypatch):
    """config.json + model.safetensors -> (config, params) through the direct
    reader; the second load comes from the Orbax cache keyed on the name."""
    import json

    import ml_dtypes

    from spotter_tpu.convert import loader
    from spotter_tpu.convert.qwen3_next_rules import ba_order, qkvz_order

    monkeypatch.setenv("SPOTTER_TPU_CACHE", str(tmp_path / "cache"))
    cfg = dataclasses.replace(CFG, num_labels=5, id2label=tuple((i, f"c{i}") for i in range(5)))
    rng = np.random.default_rng(1)
    d, tensors = cfg.hidden_size, {}

    def put(name, shape):
        tensors[name] = rng.standard_normal(shape).astype(ml_dtypes.bfloat16)

    put("patch_embeddings.projection.weight", (d, 3, 16, 16))
    put("patch_embeddings.projection.bias", (d,))
    put("detection_tokens", (1, cfg.num_detection_tokens, d))
    put("norm.weight", (d,))
    for head, out in (("class_labels_classifier", 6), ("bbox_predictor", 4)):
        for i, width in enumerate((d, d, out)):
            put(f"{head}.layers.{i}.weight", (width, d))
            put(f"{head}.layers.{i}.bias", (width,))
    for i in range(4):
        t = f"layers.{i}"
        put(f"{t}.input_layernorm.weight", (d,))
        put(f"{t}.post_attention_layernorm.weight", (d,))
        if cfg.layer_kind(i) == "linear_attention":
            put(f"{t}.linear_attn.in_proj_qkvz.weight", (192, d))
            put(f"{t}.linear_attn.in_proj_ba.weight", (8, d))
            put(f"{t}.linear_attn.conv1d.weight", (128, 1, 4))
            put(f"{t}.linear_attn.out_proj.weight", (d, 64))
            for vec, n in (("A_log", 4), ("dt_bias", 4), ("norm.weight", 16)):
                put(f"{t}.linear_attn.{vec}", (n,))
        else:
            put(f"{t}.self_attn.q_proj.weight", (128, d))
            put(f"{t}.self_attn.k_proj.weight", (32, d))
            put(f"{t}.self_attn.v_proj.weight", (32, d))
            put(f"{t}.self_attn.o_proj.weight", (d, 64))
            put(f"{t}.self_attn.q_norm.weight", (16,))
            put(f"{t}.self_attn.k_norm.weight", (16,))
        put(f"{t}.mlp.gate.weight", (16, d))
        put(f"{t}.mlp.shared_expert_gate.weight", (1, d))
        for e in [*range(8), "shared"]:
            prefix = f"{t}.mlp.shared_expert" if e == "shared" else f"{t}.mlp.experts.{e}"
            put(f"{prefix}.gate_proj.weight", (32, d))
            put(f"{prefix}.up_proj.weight", (32, d))
            put(f"{prefix}.down_proj.weight", (d, 32))
    ckpt = tmp_path / "qwen3_next_det_handmade"
    ckpt.mkdir()
    _write_safetensors(ckpt / "model.safetensors", tensors)
    hf = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "id2label"}
    hf["id2label"] = {str(i): f"c{i}" for i in range(5)}
    (ckpt / "config.json").write_text(json.dumps(hf))

    got_cfg, params = loader.load_qwen3_next_det(str(ckpt))
    assert got_cfg == cfg
    la = params["layer0"]["linear_attn"]
    assert la["in_proj_qkvz"]["kernel"].dtype == ml_dtypes.bfloat16  # kept as read
    want = tensors["layers.0.linear_attn.in_proj_qkvz.weight"][qkvz_order(cfg)].T
    assert np.array_equal(la["in_proj_qkvz"]["kernel"], want)
    assert np.array_equal(la["in_proj_ba"]["kernel"],
                          tensors["layers.0.linear_attn.in_proj_ba.weight"][ba_order(cfg)].T)
    assert np.array_equal(la["conv1d"], tensors["layers.0.linear_attn.conv1d.weight"][:, 0].T)
    mlp = params["layer2"]["mlp"]
    assert mlp["experts_gate_up"].shape == (8, d, 64) and mlp["experts_down"].shape == (8, 32, d)
    assert np.array_equal(mlp["experts_gate_up"][3, :, 32:],
                          tensors["layers.2.mlp.experts.3.up_proj.weight"].T)
    assert np.array_equal(mlp["router"], tensors["layers.2.mlp.gate.weight"].T)

    monkeypatch.setattr(loader, "read_safetensors_numpy", lambda path: 1 / 0)  # the cache serves it
    again_cfg, again = loader.load_qwen3_next_det(str(ckpt))
    assert again_cfg == cfg
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    out = served.Qwen3NextDetector(cfg).apply(
        {"params": again}, np.zeros((1, *cfg.image_size, 3), np.float32))
    assert np.isfinite(np.asarray(out["logits"])).all()


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_delta_rule_on_tokens_that_resemble_each_other(impl):
    """An image's neighbouring patches give nearly the same key: the chunk's
    triangular system is then far from the identity, and a power series over
    the whole chunk reads 1e30 where the answer is of order one (found on the
    chip, PR 28: the first served run answered nothing). The block-merged
    inverse stays at rounding, in the scan and in the kernel's body
    (interpret mode)."""
    rng = np.random.default_rng(0)
    t, hk, hv, d = 200, 1, 2, 32
    base = rng.standard_normal((1, 1, hk, d))
    k = base + 0.02 * rng.standard_normal((1, t, hk, d))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = base + 0.02 * rng.standard_normal((1, t, hk, d))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * d**-0.5
    v = rng.standard_normal((1, t, hv, d))
    g, beta = -np.full((1, t, hv), 1e-3), np.full((1, t, hv), 0.95)
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    got = delta_rule.chunked_gated_delta_rule(
        f(q), f(k), f(v), f(g), f(beta), chunk=64, impl=impl, interpret=True)
    want = ref.delta_rule(f(np.repeat(q[0], 2, 1)), f(np.repeat(k[0], 2, 1)), f(v[0]), f(g[0]), f(beta[0]))
    np.testing.assert_allclose(got[0], want, atol=2e-5)
