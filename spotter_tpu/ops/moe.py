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
read from the counts). Memory is one window's, whatever the router does; time
follows the rows routed here.

What is planned is planned by the tile, once a layer (`_plan_by_tile`): each
tile's expert (one comparison of the tiles with every expert's end), the
place among the sorted assignments of its first row and how many of its rows
are live. A window then does, in this order: slice its 64 tiles of the plan;
a row's place and whether it is live from its tile's values and an iota;
gather the sorted assignments, the tokens' rows and their weights; the first
grouped product with the SwiGLU on its way out; the second with the weights
and the sums' layout on its way out; scatter-add into the tokens' sums. It
searches nothing, reads no table of the experts a row, and XLA makes no pass
over a kernel's result. Milliseconds a window of 8192 rows, one layer alone
at the bucket of 32 (my chip runs, PR 34, `tools/probe_moe_window.py`; PR 29's
form / this one), at I 1792 with 32 experts held and at I 512 with 64:

                                   I 1792           I 512
    the search, the table gathers  0.18  / -        0.41  / 0.004
    gather order, tokens, weights  0.34  / 0.34     0.34  / 0.34
    first product                  0.72  / 0.72     0.21  / 0.21
    SwiGLU (XLA's fusion)          0.16  / -        0.013 / -
    second product                 0.37  / 0.35     0.15  / 0.125
    weight, mask, layout (XLA)     0.19  / -        0.19  / -
    scatter-add                    0.74  / 0.65     0.74  / 0.66
    a window                       2.70  / 2.07     2.06  / 1.34
    a layer (68 / 22 windows)      193.4 / 147.0    61.9  / 37.2

(the scatter-add reads its rows from the kernel's own result now, and the
kernel writes the sums' layout at no cost: reshaped in one piece, stored a
column block at a time or transposed, all three read the same to 0.01 ms).
Before the loop the experts' counts are one comparison with the held ids
(`held_tokens`): gathering the sorted keys back to search them took 3.6 and
9.1 ms of a layer's 150.6 and 46.1.

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
device trace, both calls) and a gather of matrices and an einsum in
`jax.numpy` elsewhere, with the same SwiGLU, weights and layout; the planning
around it is the same code on both.

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
# so a long window wastes more of it; a short one pays its fixed costs (two
# kernel launches, three gathers, a scatter-add) more often. My chip runs,
# PR 29, tiled sums, the plan made a row at a time. One layer alone at the
# published shapes, ms at the buckets of 8 / 32: 32768 rows 22.6 / 59.6, 16384
# 18.4 / 60.6, 8192 15.8-16.0 / 59.6-63.3, 4096 14.2 / 56.3-58.9, 2048 15.9 /
# 70.5, 1024 19.8 / 93.1. In the served program's trace a window of 4096 took
# 0.95 ms and one of 8192 2.07. Through the server the two were not told apart,
# and 8192 is the steadier: five seeds each, 8192 read 19.58-20.58 images/s
# (median 19.90), 4096 19.15-20.95 (19.46), 16384 20.70 on one seed, the
# parent's 32768 flat 16.60-19.50 (17.41). Since PR 34 a window of 8192 takes
# 1.34 ms there and 2.07 at I 1792 (module docstring); the length was not
# tried again.
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


def _matmul_kernel(tile_expert, tile_live, x_ref, *refs, weighted: bool):
    """One row tile against a column block of its expert's matrix, float32 on
    the way out. Two blocks (gate, up): `silu(x @ gate) * (x @ up)`. `weighted`:
    each row times its weight, rows past the tile's live ones zero, written in
    the sums' layout (row, column block of 128 lanes, lane)."""
    del tile_expert
    *w_refs, o_ref = refs
    live = tile_live[pl.program_id(1)]

    @pl.when(live > 0)
    def _():
        x = x_ref[...]
        if weighted:
            w_ref, scale_ref = w_refs
            out = jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)
            rows = lax.broadcasted_iota(jnp.int32, out.shape, 0)
            # a where, not a weight of zero: a dead row's product may be inf
            out = jnp.where(rows < live, out * scale_ref[...], 0.0).reshape(o_ref.shape)
        else:
            out = [jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32) for w_ref in w_refs]
            out = jax.nn.silu(out[0]) * out[1] if len(out) == 2 else out[0]
        o_ref[...] = out.astype(o_ref.dtype)

    @pl.when(live == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _column_block(n: int, widest: int) -> int:
    """The widest multiple of 128 lanes up to `widest` that divides n; n whole
    where none does."""
    return next((c for c in range(widest, 0, -_LANES) if n % c == 0), n)


def _sums_row(d: int) -> tuple:
    """A token's sums as whole (8, 128) tiles where the width allows (module
    docstring): the shape of one row."""
    return (d // _LANES, _LANES) if d % _LANES == 0 else (d,)


def expert_matmul(x, w, tile_expert, tile_live, tile: int, out_dtype=jnp.float32,
                  swiglu: bool = False, row_weight=None, impl: str | None = None,
                  interpret: bool = False):
    """x: (R, K), R a multiple of `tile`; w: (E, K, N); row tile i of x is
    multiplied by w[tile_expert[i]]; tile_live[i] is how many of the tile's
    rows (its first ones) are live: a tile with none is not computed and comes
    back zero. Returns (R, N). With `swiglu`, w is (E, K, 2 I) (gate | up) and
    the result `silu(x @ gate) * (x @ up)`, (R, I), computed in float32 and
    cast once. With `row_weight` (R,) float32, each live row is multiplied by
    its weight in float32, a dead row is exactly zero, and the result has the
    sums' layout: (R, N / 128, 128) where N is a multiple of 128."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "einsum"
    r, k = x.shape
    n = w.shape[2] // 2 if swiglu else w.shape[2]
    tiles = r // tile
    weighted = row_weight is not None
    out_shape = (r, *_sums_row(n)) if weighted else (r, n)
    if impl == "einsum":
        out = jnp.einsum("tmk,tkn->tmn", x.reshape(tiles, tile, k), w[tile_expert].astype(x.dtype),
                         preferred_element_type=jnp.float32)
        if swiglu:
            out = jax.nn.silu(out[..., :n]) * out[..., n:]
        if weighted:
            out = out * row_weight.reshape(tiles, tile, 1)
        live = tile_live[:, None, None] > (jnp.arange(tile)[:, None] if weighted else 0)
        return jnp.where(live, out, 0.0).reshape(out_shape).astype(out_dtype)
    # a step works _TILE_N columns of the matrix: one block, or a gate and an up
    # block of half; weighted, a row's whole (8, 128) tiles of the sums' layout
    if weighted:
        tn = 8 * _LANES if n % (8 * _LANES) == 0 else n
    else:
        tn = _column_block(n, _TILE_N // 2 if swiglu else _TILE_N)
    firsts = [0, n // tn] if swiglu else [0]
    operands = [x, *[w] * len(firsts)]
    in_specs = [pl.BlockSpec((tile, k), lambda j, i, te, live: (i, 0)),
                *(pl.BlockSpec((1, k, tn), lambda j, i, te, live, b=first: (te[i], 0, b + j))
                  for first in firsts)]
    if weighted:
        operands.append(row_weight.astype(jnp.float32).reshape(r, 1))
        in_specs.append(pl.BlockSpec((tile, 1), lambda j, i, te, live: (i, 0)))
    out_row = _sums_row(tn) if weighted else (tn,)
    out_block = pl.BlockSpec((tile, *out_row), lambda j, i, te, live: (i, j, 0)[:1 + len(out_row)])
    # row tiles innermost: consecutive tiles of one expert find its matrix
    # block already in place
    return pl.pallas_call(
        functools.partial(_matmul_kernel, weighted=weighted),
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, tiles), in_specs=in_specs, out_specs=out_block),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="expert_matmul_kernel",
    )(tile_expert, tile_live, *operands)


def _plan_by_tile(counts, start, tile: int, tiles: int):
    """The padded rows by the tile. counts, start: (n_local,) how many sorted
    assignments each held expert has and where its first lies. Each expert's
    rows are padded up to whole tiles, so a tile belongs to one expert. For
    each of `tiles` tiles, (n,) int32 each: its expert, the place among the
    sorted assignments of its first row, and how many of its rows (its first
    ones) are live: none in a tile past the last expert's."""
    expert_tiles = -(-counts // tile)
    tile_end = jnp.cumsum(expert_tiles)
    t = jnp.arange(tiles, dtype=jnp.int32)
    # one comparison with every end: no search, and nothing a row
    expert = jnp.minimum((t[:, None] >= tile_end).sum(-1, dtype=jnp.int32), counts.shape[0] - 1)
    within = (t - (tile_end - expert_tiles)[expert]) * tile
    return expert, start[expert] + within, jnp.clip(counts[expert] - within, 0, tile)


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
    n_local = down.shape[0]

    # the plan: assignments sorted by held expert, the others last
    local = experts.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < n_local), local, n_local)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = held_tokens(experts.reshape(-1), offset, n_local)
    start = jnp.cumsum(counts) - counts
    worst = -(-(m * top_k + n_local * (tile - 1)) // tile) * tile
    rows = min(-(-(window_rows or WINDOW_ROWS) // tile) * tile, worst)
    per_window = rows // tile
    # whole windows of tiles, so that a window's slice of the plan never runs off its end
    tile_expert, tile_first, tile_rows = _plan_by_tile(
        counts, start, tile, -(-worst // rows) * per_window)
    windows = -(-(tile_rows > 0).sum() // per_window)  # a padded tile has a live row
    lane = jnp.arange(tile, dtype=jnp.int32)
    flat_w = weights.reshape(-1)
    matmul = functools.partial(expert_matmul, tile=tile, impl=impl, interpret=interpret)

    def window(j, acc):
        tile_e, first_row, live_rows = (lax.dynamic_slice(a, (j * per_window,), (per_window,))
                                        for a in (tile_expert, tile_first, tile_rows))
        live = (lane < live_rows[:, None]).reshape(rows)
        pick = order[jnp.where(live, (first_row[:, None] + lane).reshape(rows), 0)]
        token = pick // top_k
        hidden = matmul(x[token], gate_up, tile_e, live_rows, out_dtype=x.dtype, swiglu=True)
        y = matmul(hidden, down, tile_e, live_rows, row_weight=flat_w[pick])
        return acc.at[token].add(y)

    acc = lax.fori_loop(0, windows, window, jnp.zeros((m, *_sums_row(d)), jnp.float32))
    return acc.reshape(m, d)
