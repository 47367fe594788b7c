"""A routed-expert layer for a chip that holds a share of the experts.

The router is the whole model's: every token is scored against all E experts
in float32 (softmax over E, or a sigmoid of each logit), takes its k best (by
score, or by score plus a selection bias that does not enter the weight), and
the k scores are renormalised to sum to one, as published. This chip holds the experts
`[offset, offset + n_local)`. It computes, for each token, the terms of the
experts it holds,

    sum over held e among the token's k:   w_e * down_e(silu(gate_e x) * up_e x)

and nothing else: what the absent experts would add is the other chips' part
of the sum (their exchange is not here, and nothing stands in for it).

No token is dropped and no shape depends on the routing. The (token, choice)
pairs that fall on held experts are sorted by expert; each expert's rows are
padded up to a whole number of row tiles, so that a tile belongs to one
expert; the padded rows are then worked through in windows of a fixed number
of rows, as many windows as the routing needs (a `fori_loop` with a bound
read from the counts): gather the window's tokens, two grouped matrix
products (`expert_matmul`: each row tile against its own expert's matrix),
weight, scatter-add into the tokens' sums. Memory is one window's, whatever
the router does; time follows the rows routed here.

The sums are float32 and their layout follows the width. Where d is a multiple
of 128 lanes the loop carries them as (tokens, d / 128, 128), so that a
token's row is whole (8, 128) tiles (two of them at d = 2048) and the
scatter-add's update for a row rewrites only that row; flat, as (tokens, d),
a row is one sublane of d / 128 tiles and every row added rewrites them all
for an eighth of their content. XLA's scatter-add of 32768 rows at d = 2048
takes 4.0 ms tiled and 21.9 ms flat (my chip runs, PR 28); in the served
program a window's took 10.4 ms flat (32768 rows) and takes 0.74 tiled (8192
rows), and a bucket of 32's four loops 545 and 239 ms (my chip runs, PR 29). A
width that is not a multiple of 128 keeps the flat form. The result is
(tokens, d) either way, reshaped once after the loop.

`expert_matmul` is a Pallas kernel on a TPU (`expert_matmul_kernel` on the
device trace) and a gather of matrices and an einsum in `jax.numpy` elsewhere;
the planning around it is the same code on both.

Departure from the source, noted in the configuration: the router's product
runs in float32 at the highest matmul precision whatever the policy
(transformers computes the logits in the model's type, then the softmax in
float32).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE = 128
# Rows a window works. A layer's last window works its dead rows like live ones,
# so a long window wastes more of it; a short one pays its fixed costs (the
# search for each row's expert, two kernel launches) more often. My chip runs,
# PR 29, tiled sums. One layer alone at the published shapes, ms at the buckets
# of 8 / 32: 32768 rows 22.6 / 59.6, 16384 18.4 / 60.6, 8192 15.8-16.0 /
# 59.6-63.3, 4096 14.2 / 56.3-58.9, 2048 15.9 / 70.5, 1024 19.8 / 93.1. In the
# served program's trace a window of 4096 takes 0.95 ms and one of 8192 2.07.
# Through the server the two are not told apart, and 8192 is the steadier: five
# seeds each, 8192 read 19.58-20.58 images/s (median 19.90), 4096 19.15-20.95
# (19.46), 16384 20.70 on one seed, the parent's 32768 flat 16.60-19.50 (17.41).
WINDOW_ROWS = 8192
_TILE_N = 512
_LANES = 128


def router_scores(x, router, scoring: str = "softmax"):
    """x: (M, d); router: (d, E). Each token's score for every expert, (M, E)
    float32: the softmax over E of the router's logits, or their sigmoid."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1) if scoring == "softmax" else jax.nn.sigmoid(logits)


def select(scores, top_k: int, normalise: bool = True, bias=None, eps: float = 0.0,
           scale: float = 1.0):
    """scores: (M, E) from `router_scores`. Returns (weights (M, k) float32, experts
    (M, k) int32): each token's k best experts and its scores for them,
    renormalised to sum to one (over `+ eps`, where a source adds one) and
    scaled. `bias` (E,) enters the choice and not the weight: the k best by
    `scores + bias`, weighed by `scores`."""
    if bias is None:
        weights, experts = _top_k(scores, top_k)
    else:
        _, experts = _top_k(scores + bias.astype(jnp.float32), top_k, floor=-jnp.inf)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalise:
        total = weights.sum(-1, keepdims=True)
        weights = weights / (total + eps if eps else total)
    return weights * scale if scale != 1.0 else weights, experts


def route(x, router, top_k: int, normalise: bool = True, scoring: str = "softmax",
          bias=None, eps: float = 0.0, scale: float = 1.0):
    """x: (M, d); router: (d, E). Returns (weights (M, k) float32, experts
    (M, k) int32): `select` over `router_scores`. The defaults are the one router
    PR 28 had: each token's k most probable experts of all E."""
    return select(router_scores(x, router, scoring), top_k, normalise, bias, eps, scale)


def moved_by_bias(scores, experts):
    """How many of each token's selections (`experts`, (M, k), chosen with a
    bias) are not among the k best of the unbiased `scores`: (M,) int32."""
    _, plain = _top_k(scores, experts.shape[-1])
    return (experts[..., :, None] != plain[..., None, :]).all(-1).sum(-1, dtype=jnp.int32)


def _top_k(probs, k: int, floor: float = -1.0):
    """`lax.top_k`'s answer (descending, the lower index first among equals)
    by k passes of max-and-mask. On a TPU `lax.top_k` over E = 512 sorts the
    whole row: 37 ms a layer at 137600 tokens, a tenth of the step (device
    trace, PR 28); k = 10 passes over the same array read it ten times and
    sort nothing. `floor` masks a taken entry: under every value of `probs`."""
    e = probs.shape[-1]
    ids = lax.broadcasted_iota(jnp.int32, probs.shape, probs.ndim - 1)
    values, indices = [], []
    for _ in range(k):
        best = probs.max(-1, keepdims=True)
        index = jnp.min(jnp.where(probs == best, ids, e), axis=-1, keepdims=True)
        values.append(best)
        indices.append(index)
        probs = jnp.where(ids == index, floor, probs)
    return jnp.concatenate(values, -1), jnp.concatenate(indices, -1)


def held_tokens(experts, offset: int, n_local: int):
    """experts: (..., A) expert ids. Returns (..., n_local) int32: how many
    of the A selections fell on each held expert."""
    local = experts - offset
    hits = local[..., None] == jnp.arange(n_local, dtype=jnp.int32)
    return hits.sum(-2, dtype=jnp.int32)


def _matmul_kernel(tile_expert, tile_live, x_ref, w_ref, o_ref):
    del tile_expert

    @pl.when(tile_live[pl.program_id(1)] > 0)
    def _():
        o_ref[...] = jnp.dot(
            x_ref[...], w_ref[0], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)

    @pl.when(tile_live[pl.program_id(1)] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def expert_matmul(x, w, tile_expert, tile_live, tile: int, out_dtype=jnp.float32,
                  impl: str | None = None, interpret: bool = False):
    """x: (R, K), R a multiple of `tile`; w: (E, K, N); row tile i of x is
    multiplied by w[tile_expert[i]]; a tile with tile_live[i] == 0 is not
    computed and comes back zero. Returns (R, N)."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "einsum"
    r, k = x.shape
    n = w.shape[2]
    tiles = r // tile
    if impl == "einsum":
        out = jnp.einsum("tmk,tkn->tmn", x.reshape(tiles, tile, k), w[tile_expert].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        out = jnp.where(tile_live[:, None, None] > 0, out, 0.0)
        return out.reshape(r, n).astype(out_dtype)
    tn = _TILE_N if n % _TILE_N == 0 else n
    # row tiles innermost: consecutive tiles of one expert find its matrix
    # block already in place
    return pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((r, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, i, te, live: (i, 0)),
                pl.BlockSpec((1, k, tn), lambda j, i, te, live: (te[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tile, tn), lambda j, i, te, live: (i, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="expert_matmul_kernel",
    )(tile_expert, tile_live, x, w)


def routed_experts(x, weights, experts, gate_up, down, offset: int = 0,
                   tile: int | None = None, window_rows: int | None = None,
                   impl: str | None = None, interpret: bool = False):
    """The held experts' part of the layer's sum. x: (M, d); weights,
    experts: (M, k) from `route`; gate_up: (n_local, d, 2 I) (gate | up);
    down: (n_local, I, d). Returns (M, d) float32."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "einsum"
    if tile is None:
        tile = ROW_TILE if impl == "pallas" else 8
    x, weights, experts, gate_up, down = map(jnp.asarray, (x, weights, experts, gate_up, down))
    m, d = x.shape
    top_k = experts.shape[1]
    n_local, inter = down.shape[:2]

    # the plan: assignments sorted by held expert, the others last
    local = experts.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < n_local), local, n_local)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    first = jnp.searchsorted(key[order], jnp.arange(n_local + 1, dtype=jnp.int32)).astype(jnp.int32)
    start, counts = first[:-1], jnp.diff(first)
    padded_end = jnp.cumsum(-(-counts // tile) * tile)
    padded_start = padded_end - -(-counts // tile) * tile
    total = padded_end[-1]
    worst = -(-(m * top_k + n_local * (tile - 1)) // tile) * tile
    rows = min(-(-(window_rows or WINDOW_ROWS) // tile) * tile, worst)
    flat_w = weights.reshape(-1)
    # a token's sums as whole (8, 128) tiles where the width allows (module docstring)
    row = (d // _LANES, _LANES) if d % _LANES == 0 else (d,)
    matmul = functools.partial(expert_matmul, tile=tile, impl=impl, interpret=interpret)

    def window(j, acc):
        r = j * rows + jnp.arange(rows, dtype=jnp.int32)
        e = jnp.minimum(jnp.searchsorted(padded_end, r, side="right"), n_local - 1).astype(jnp.int32)
        within = r - padded_start[e]
        live = (within < counts[e]) & (r < total)
        pick = order[jnp.where(live, start[e] + within, 0)]
        token = pick // top_k
        tile_e, tile_live = e[::tile], live[::tile].astype(jnp.int32)
        hidden = matmul(x[token], gate_up, tile_e, tile_live)
        hidden = (jax.nn.silu(hidden[:, :inter]) * hidden[:, inter:]).astype(x.dtype)
        y = matmul(hidden, down, tile_e, tile_live)
        y = jnp.where(live[:, None], y * flat_w[pick][:, None], 0.0)
        return acc.at[token].add(y.reshape(rows, *row))

    acc = lax.fori_loop(0, -(-total // rows), window, jnp.zeros((m, *row), jnp.float32))
    return acc.reshape(m, d)
