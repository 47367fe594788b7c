"""Kimi Delta Attention's recurrence, chunked: the delta rule with a decay that
is a vector over the key channels.

Per head, with a state S (dk x dv) that starts at zero, for each token t:

    S <- diag(exp(g_t)) S                       g_t in R^dk, <= 0
    S <- S + k_t (beta_t (v_t - S^T k_t))^T
    o_t = S^T q_t

(q and k arrive L2-normalised, q scaled by dk^-0.5.) With every channel of a
head given the same g this is `ops/delta_rule.py`'s recurrence; the plain
reference (`testing/kimi_linear_reference.py`) computes it token by token.
What is served is the chunked form: with G the running sum of g inside a chunk
of C tokens (a vector a token), S0 the state the chunk starts from,

    A_ij = beta_i sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])      i > j
    P_ij =        sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d])      i >= j
    T   = (I + A)^-1
    u   = T (beta v)               w = T (beta exp(G) k)
    v'  = u - w S0
    o   = (exp(G) q) S0 + P v'
    S  <- diag(exp(G_C)) S0 + (exp(G_C - G) k)^T v'

**The decay sits inside the contraction over d**, so A and P are no product of
q, k and a (C, C) matrix, as they are under a scalar gate. The product form
`(k e^G)(k e^-G)^T` overflows float32 inside one chunk (-g reaches 1.6 a token
under the authors' initialisation and tens under seeded projections: e^100
and more). So no quotient of two exponentials is ever taken over a span that
can overflow. The chunk is halved again and again (levels of blocks of 64,
32, ..., 2 tokens). At a level, a block's lower-left quarter (rows in its
second half, columns in its first) takes the first row of the second half as
its reference r, which lies between its rows and its columns:

    exp(G_i - G_j) = exp(G_i - G_r) exp(G_r - G_j),     j < r <= i

both factors at most 1 (underflow to zero is then the right answer), so the
quarter is one matrix product of `k exp(-|G - G_r|)` with itself (q likewise
for P). Every pair i > j falls in exactly one quarter of one level: six
products a head and chunk, independent of one another, and six exponentials
of a (C, dk) array, whatever the gate does. The diagonal sub-blocks that are
left are single tokens, whose masked difference is zero: P_ii = q_i . k_i.

**The gate never lies in memory as float32.** g is a float32 a head, token and
key channel: 2.25 GB a layer at the bucket of 32, and XLA kept the softplus's
result, g, its running sum and two copies of them in other layouts alive at
once (the bucket of 32's program asked for 12.6 GB of temporaries beside 4.35
of weights: compiled for a described v5e, PR 35). So the chunk computes g
itself where it is handed the projection that feeds it (`RawGate`: `g =
-exp(A_log) softplus(raw + dt_bias)`, raw in the served type), and the running
sum G in either case, by six shifted adds of the chunk's rows (Mosaic has no
cumsum; `jnp.roll` lowers on both sides). For the same reason the chunk can
L2-normalise q and k itself (`normalise`): done outside, XLA kept both in
float32 for the norm's two readers, 2.1 GB each at the bucket of 32.

`chunk_step` is that mathematics for one chunk of the heads a grid step of the
kernel works on, written once and run in two places: inside the Pallas kernel
on a TPU (`kda_kernel` on the device trace: a name no reader of
`gated_delta_rule` events counts) and under `vmap` and `lax.scan` in plain
`jax.numpy` everywhere else. There is no interpret-mode fallback on a TPU.
The state is kept transposed, (dv, dk), so that a chunk's decay of it is a
row times its lanes. The solve is `ops/delta_rule.py`'s (`_unit_lower_inverse`,
float32, imported as it is); under a bfloat16 policy the other products take
bfloat16 operands and accumulate in float32.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spotter_tpu.ops.delta_rule import _NN, _NT, _TN, _dot, _unit_lower_inverse

CHUNK = 64
HEADS_PER_STEP = 16  # a layer at the bucket of 16: 23.1 ms at 4, 16.5 at 8, 15.4 at 16 (my chip run, PR 35)
_SUBLANES = 8
L2_EPS = 1e-6
# a raw gate whose softplus is 0 in float32 and that float8 holds: a padded token's g is 0
PAD_RAW_GATE = -300.0


class RawGate(NamedTuple):
    """The log decay left for the chunk to compute: `g = -exp(a_log)[head] *
    softplus(raw + dt_bias)`. raw: (B, T, H, dk) in any float type; a_log:
    (H,); dt_bias: (H dk,) or (H, dk)."""
    raw: jax.Array
    a_log: jax.Array
    dt_bias: jax.Array


def _running_sum(g):
    """g: (C, W) float32 -> the sum of rows 0..i in row i: log2(C) shifted adds."""
    row = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    shift = 1
    while shift < g.shape[0]:
        g = g + jnp.where(row >= shift, jnp.roll(g, shift, axis=0), 0.0)
        shift *= 2
    return g


def _level_reference(gc, level: int):
    """gc: (C, W). Each row's reference at `level` (blocks of 2^level rows):
    the first row of its block's second half, for every row of the block."""
    c, w = gc.shape
    b = 1 << level
    if b >= _SUBLANES:
        blocks = gc.reshape(c // b, b, w)
        return jnp.broadcast_to(blocks[:, b // 2:b // 2 + 1], blocks.shape).reshape(c, w)
    # blocks inside a tile of 8 rows: one row broadcast a block, chosen by the row
    tiles = gc.reshape(c // _SUBLANES, _SUBLANES, w)
    at = lax.broadcasted_iota(jnp.int32, tiles.shape, 1) >> level
    ref = jnp.broadcast_to(tiles[:, b // 2:b // 2 + 1], tiles.shape)
    for block in range(1, _SUBLANES // b):
        mid = block * b + b // 2
        ref = jnp.where(at == block, jnp.broadcast_to(tiles[:, mid:mid + 1], tiles.shape), ref)
    return ref.reshape(c, w)


def chunk_step(q, k, v, g, beta, states, mm, gate=None, normalise=False):
    """One chunk of n heads, side by side along the lanes. q, k: (C, n dk);
    v: (C, n dv); g: (C, n dk), the log decay of every token and channel in
    float32, or with `gate` = (scale, bias), two rows (1, n dk) float32, what
    it is made from: g = -scale * softplus(g + bias); beta: (C, n) float32;
    states: n arrays (dv, dk) float32, each head's state transposed. `mm` is
    the operand type of the large products. With `normalise`, q and k arrive
    as the convolutions leave them and are L2-normalised a head here (eps
    1e-6), q scaled by dk^-0.5.
    What is elementwise runs over all the heads at once; every kind of
    product is written for every head before the next kind (the chains are
    independent and fill each other's waits: `ops/delta_rule.py`). Returns
    (o (C, n dv) float32, the n states after the chunk)."""
    c, n = beta.shape
    dk, dv = q.shape[1] // n, v.shape[1] // n

    def head(x, h, width):
        return x[:, h * width:(h + 1) * width]

    q32, k32, v = q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    g = g.astype(jnp.float32)
    if normalise:
        def unit(x, scale):
            squares = [jnp.sum(jnp.square(head(x, h, dk)), axis=1, keepdims=True) for h in range(n)]
            norms = [jnp.broadcast_to(scale * lax.rsqrt(s + L2_EPS), (c, dk)) for s in squares]
            return x * jnp.concatenate(norms, axis=1)

        q32, k32 = unit(q32, dk**-0.5), unit(k32, 1.0)
    if gate is not None:
        g = g + gate[1]
        g = -gate[0] * (jnp.maximum(g, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(g))))
    gc = _running_sum(g)
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)

    # k k^T over q k^T with the decay inside, a level at a time
    kq = [jnp.zeros((2 * c, c), jnp.float32)] * n
    for level in range(1, c.bit_length()):
        decay = jnp.exp(-jnp.abs(gc - _level_reference(gc, level)))
        kd, qd = (k32 * decay).astype(mm), (q32 * decay).astype(mm)
        quarter = (((row >> level) == (col >> level))
                   & (((row >> (level - 1)) & 1) == 1) & (((col >> (level - 1)) & 1) == 0))
        quarter = jnp.concatenate([quarter, quarter], axis=0)
        prods = [_dot(jnp.concatenate([head(kd, h, dk), head(qd, h, dk)], axis=0),
                      head(kd, h, dk), _NT, mm) for h in range(n)]
        kq = [jnp.where(quarter, prod, acc) for prod, acc in zip(prods, kq)]
    own = [jnp.sum(head(q32, h, dk) * head(k32, h, dk), axis=1, keepdims=True) for h in range(n)]
    betas = [beta[:, h:h + 1] for h in range(n)]
    xs = [-(b * a[:c]) for b, a in zip(betas, kq)]  # zero on and above the diagonal already
    scores = [jnp.where(row == col, d, a[c:]).astype(mm) for d, a in zip(own, kq)]
    ts = [t.astype(mm) for t in _unit_lower_inverse(xs, row, col)]

    grow = jnp.exp(gc)
    last = gc[c - 1:c]
    k_in = k32 * grow  # what the state so far adds to a token's read
    q_in = (q32 * grow).astype(mm)
    k_out = (k32 * jnp.exp(last - gc)).astype(mm)  # what a token leaves in the chunk's last state
    keep = jnp.exp(last)
    us = [_dot(t, b * head(v, h, dv), _NN, mm) for h, (t, b) in enumerate(zip(ts, betas))]
    ws = [_dot(t, b * head(k_in, h, dk), _NN, mm) for h, (t, b) in enumerate(zip(ts, betas))]
    v_news = [u - _dot(w, state, _NT, mm) for u, w, state in zip(us, ws, states)]
    outs = [_dot(head(q_in, h, dk), state, _NT, mm) + _dot(score, v_new, _NN, mm)
            for h, (state, score, v_new) in enumerate(zip(states, scores, v_news))]
    states = [state * head(keep, h, dk) + _dot(v_new, head(k_out, h, dk), _TN, mm)
              for h, (state, v_new) in enumerate(zip(states, v_news))]
    return jnp.concatenate(outs, axis=1), states


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *refs, heads, mm, normalise):
    *gate_refs, o_ref, state_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _():  # a new (image, head block): the state starts at zero
        state_ref[...] = jnp.zeros_like(state_ref)

    gate = tuple(ref[...] for ref in gate_refs) or None
    out, states = chunk_step(q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0, 0],
                             [state_ref[h] for h in range(heads)], mm, gate, normalise)
    for h, state in enumerate(states):
        state_ref[h] = state
    o_ref[0] = out.astype(o_ref.dtype)


def _heads_per_step(h: int, wanted: int) -> int:
    return next(n for n in range(min(wanted, h), 0, -1) if h % n == 0)


def _pallas(q, k, v, g, beta, gate, normalise: bool, chunk: int, mm, heads_per_step: int,
            interpret: bool):
    """q, k, g: (B, Tp, H, dk); v: (B, Tp, H, dv); beta: (B, Tp, H); gate:
    None or two rows (1, H dk)."""
    b, tp, h, dk = q.shape
    dv = v.shape[3]
    hb = _heads_per_step(h, heads_per_step)
    nhb, n = h // hb, tp // chunk
    betas = beta.reshape(b, tp, nhb, hb).transpose(0, 2, 1, 3)  # (B, nhb, Tp, hb)
    k_spec = pl.BlockSpec((1, chunk, hb * dk), lambda i, j, s: (i, s, j))
    v_spec = pl.BlockSpec((1, chunk, hb * dv), lambda i, j, s: (i, s, j))
    out = pl.pallas_call(
        functools.partial(_kernel, heads=hb, mm=mm, normalise=normalise),
        out_shape=jax.ShapeDtypeStruct((b, tp, h * dv), v.dtype),
        grid=(b, nhb, n),
        in_specs=[k_spec, k_spec, v_spec, k_spec,
                  pl.BlockSpec((1, 1, chunk, hb), lambda i, j, s: (i, j, s, 0)),
                  *[pl.BlockSpec((1, hb * dk), lambda i, j, s: (0, j))] * len(gate or ())],
        out_specs=v_spec,
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_kernel",
    )(q.reshape(b, tp, h * dk), k.reshape(b, tp, h * dk), v.reshape(b, tp, h * dv),
      g.reshape(b, tp, h * dk), betas, *(gate or ()))
    return out.reshape(b, tp, h, dv)


def _scan(q, k, v, g, beta, gate, normalise: bool, chunk: int, mm):
    """The same chunks in plain jax.numpy: `chunk_step` over an image's heads
    under vmap over the images, scanned over the chunks."""
    b, tp, h, dk = q.shape
    dv = v.shape[3]
    n = tp // chunk

    def chunks(x):  # (B, Tp, ...) -> (N, B, C, the rest flat)
        return x.reshape(b, n, chunk, -1).transpose(1, 0, 2, 3)

    def step(q, k, v, g, beta, state):
        states = [state[i] for i in range(h)]
        out, states = chunk_step(q, k, v, g, beta, states, mm, gate, normalise)
        return out, jnp.stack(states)

    def body(state, xs):
        out, state = jax.vmap(step)(*xs, state)
        return state, out

    state = jnp.zeros((b, h, dv, dk), jnp.float32)
    _, out = lax.scan(body, state, tuple(chunks(x) for x in (q, k, v, g, beta)))
    return out.transpose(1, 0, 2, 3).reshape(b, tp, h, dv).astype(v.dtype)


def chunked_kda(q, k, v, g, beta, chunk: int | None = None, impl: str | None = None,
                heads_per_step: int | None = None, interpret: bool = False,
                normalise: bool = False):
    """q, k: (B, T, H, dk), L2-normalised, q scaled (or, with `normalise`, as
    the convolutions leave them: the chunks do both); v: (B, T, H, dv); g (the
    log decay of every key channel, <= 0): (B, T, H, dk) float32, or a
    `RawGate` that it is made from; beta: (B, T, H) float32. Returns o: (B, T,
    H, dv) in v's type.

    T is padded to a multiple of `chunk` (the module's `CHUNK` unless given, a
    power of two) with tokens of q = k = v = 0, g = 0 (a raw gate of -300,
    whose softplus is 0), beta = 0: they follow every real token and a causal
    recurrence never lets them reach one. (Left to the kernel, with its last
    block over the arrays' end and masked on the way in, the bucket of 32's
    program asked for 8.5 GiB of temporaries where the padded one asks for 6.5:
    compiled for a described v5e, PR 35. Not measured on the chip.)
    `impl`: "pallas" (the TPU's kernel) or "scan" (jax.numpy); by default the
    backend decides, and a TPU gets the kernel."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    chunk = chunk or CHUNK
    if chunk & (chunk - 1) or chunk < _SUBLANES:
        raise ValueError(f"chunk must be a power of two of at least {_SUBLANES}, got {chunk}")
    gate = None
    if isinstance(g, RawGate):
        width = g.raw.shape[2] * g.raw.shape[3]
        scale = jnp.repeat(jnp.exp(g.a_log.astype(jnp.float32)), g.raw.shape[3])
        gate = (scale.reshape(1, width), g.dt_bias.astype(jnp.float32).reshape(1, width))
        g = g.raw
    else:
        g = g.astype(jnp.float32)
    t = q.shape[1]
    pad = -t % chunk
    if pad:
        widths = ((0, 0), (0, pad))
        q, k, v, beta = (jnp.pad(x, widths + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, beta))
        g = jnp.pad(g, widths + ((0, 0), (0, 0)),
                    constant_values=0.0 if gate is None else PAD_RAW_GATE)
    beta = beta.astype(jnp.float32)
    mm = jnp.bfloat16 if v.dtype == jnp.bfloat16 else jnp.float32
    with jax.named_scope("kda_rule"):
        if impl == "pallas":
            out = _pallas(q, k, v, g, beta, gate, normalise, chunk, mm,
                          heads_per_step or HEADS_PER_STEP, interpret)
        elif impl == "scan":
            out = _scan(q, k, v, g, beta, gate, normalise, chunk, mm)
        else:
            raise ValueError(f"impl must be pallas or scan, got {impl!r}")
    return out[:, :t]
