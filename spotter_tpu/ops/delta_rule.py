"""The gated delta rule of a linear-attention layer (Gated DeltaNet), chunked.

Per head, with a state S (dk x dv) that starts at zero, for each token t:

    S <- exp(g_t) S
    S <- S + k_t (beta_t (v_t - S^T k_t))^T
    o_t = S^T q_t

(q and k arrive L2-normalised, q scaled by dk^-0.5; g <= 0 is the log of the
decay.) Token by token that is T dependent steps of rank-one updates: the
plain reference (`testing/qwen3_next_reference.py`) computes it so. What is
served is the chunked form of the same recurrence: inside a chunk of C tokens
the dependence between tokens is a unit lower-triangular system, solved with
matrix products, and only the state is carried from chunk to chunk. With
gc the running sum of g inside the chunk and D_ij = exp(gc_i - gc_j), i >= j:

    L   = strict_lower(beta_i (k_i . k_j) D_ij)
    T   = (I + L)^-1
    u   = T (beta v)               w = T (beta exp(gc) k)
    v'  = u - w S
    o   = (exp(gc) q) S + lower(q k^T D) v'
    S  <- exp(gc_C) S + (exp(gc_C - gc) k)^T v'

`chunk_step` is that mathematics for one chunk of the value heads a grid
step of the kernel works on. It is written once and runs in two places:
inside the Pallas kernel on a TPU (`gated_delta_rule_kernel`, the custom call
the device trace shows under that name) over a step's 16 heads, and under
`vmap` and `lax.scan` in plain `jax.numpy` everywhere else (CPU tests) a
group of heads at a time. There is no interpret-mode fallback on a TPU.

What the kernel's time is made of, and how the body is laid out for it (PR
31; `tools/probe_delta_rule.py` times each part on the chip): the solve is a
chain of ten products, each waiting for the one before, and a product's round
trip through the MXU costs the same whether its operands are 64 or 128 wide,
float32 or bfloat16. So (1) the two value heads of one key head go side by
side in the 128 lanes for every quantity that is C x C: k k^T and q k^T are
computed once a pair, the decay and the masks run on whole vregs, and the
pair's two solves are one chain of ten products against block-diagonal
operands; a key head that serves an odd number of value heads takes them one
at a time. (2) Every kind of product is written for all the heads of a step
before the next kind: the scheduler keeps close to the order it is given, and
chains written one after the other run one after the other.

Departures from transformers' `torch_chunk_gated_delta_rule`, none of them
in the mathematics: T is formed by matrix products in float32 (a short
series inside blocks of 8 tokens, then block merges: `_unit_lower_inverse`)
where torch substitutes row by row; the decay is applied as exp of a masked
difference, never as a quotient of two exponentials; under a bfloat16
policy the products with the state and the values take bfloat16 operands
and accumulate in float32, the solve stays in float32.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def _dot(a, b, dims, dtype):
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())),
        preferred_element_type=jnp.float32,
    )


def _lower_masks(c: int, width: int):
    """(row, col) of every entry of width / c matrices (C, C) laid side by
    side along the lanes: the column counts inside its own matrix."""
    row = lax.broadcasted_iota(jnp.int32, (c, width), 0)
    lane = lax.broadcasted_iota(jnp.int32, (c, width), 1)
    return row, jnp.where(lane >= c, lane - c, lane)


def _side_by_side(cols, c: int):
    """One column (C, 1) a head -> (C, n C): head h's value fills its own C
    lanes. One head: the column itself (broadcasting does the rest)."""
    if cols.shape[1] == 1:
        return cols
    lane = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    return jnp.where(lane < c, cols[:, 0:1], cols[:, 1:2])


def _unit_lower_inverse(xs, row, col):
    """(I - X)^-1 for each strictly lower-triangular X (C, C) of the list
    `xs`, in float32, by matrix products alone and without the growth a plain
    power series has. Inside diagonal blocks of 8 the series I + X + ... +
    X^7 is short (its terms stay within a few tens of X's scale); then
    neighbouring blocks are merged, 8 -> 16 -> 32 -> ..., by the block
    formula: with T the inverse of the block-diagonal part and E the entries
    that join two neighbours, (I - X_blocks - E)^-1 = T + T E T. Every step
    multiplies true inverses of sub-blocks, so what it computes stays as
    bounded as the answer is. (A power series over all C tokens sums terms of
    size C-choose-C/2: on tokens that resemble each other, an image's
    neighbouring patches, it reads 1e30 where the answer is of order one.)

    An x may hold two such matrices side by side, [X1 | X2] (C, 2C): its
    answer is then [T1 | T2], by the same ten products, each serving both:
    the pair's products A1 B1 and A2 B2 are one product [A1 | A2]
    blockdiag(B1, B2), 64 rows through a whole 128 x 128 tile at the served
    chunk. And every step is written for all of `xs` before the next step:
    a product here waits for the one before it (its round trip through the
    MXU is what the solve costs, not its rows), the chip's scheduler keeps
    close to the order the products are written in, and the chains of the
    list are independent, so each fills the others' waits. `row`, `col`:
    `_lower_masks`."""
    c, width = xs[0].shape
    if width == c:
        def times(a, b):
            return _dot(a, b, _NN, jnp.float32)
    else:
        at = lax.broadcasted_iota(jnp.int32, (width, width), 0)
        lane = lax.broadcasted_iota(jnp.int32, (width, width), 1)
        own = (at < c) == (lane < c)

        def times(a, b):  # blockdiag: b twice down the sublanes, and a select
            return _dot(a, jnp.where(own, jnp.concatenate([b, b], axis=0), 0.0), _NN, jnp.float32)

    def same_block(shift):
        return (row >> shift) == (col >> shift)

    eye = jnp.where(row == col, 1.0, 0.0).astype(jnp.float32)
    powers = [jnp.where(same_block(3), x, 0.0) for x in xs]
    ts = [eye + inner for inner in powers]
    for _ in range(2):  # (I + X)(I + X^2)(I + X^4): X^8 = 0 inside a block of 8
        powers = [times(power, power) for power in powers]
        ts = [t + times(t, power) for t, power in zip(ts, powers)]
    shift = 3
    while (1 << shift) < c:
        joins = same_block(shift + 1) & ~same_block(shift)
        reached = [times(t, jnp.where(joins, x, 0.0)) for t, x in zip(ts, xs)]
        ts = [t + times(te, t) for t, te in zip(ts, reached)]
        shift += 1
    return ts


class _Head(NamedTuple):
    """One value head's part of a chunk step, after the solve."""
    q: jax.Array  # (C, dk), its key head's
    k: jax.Array
    v: jax.Array  # (C, dv)
    gc: jax.Array  # (C, 1)
    beta: jax.Array  # (C, 1)
    last: jax.Array  # (1, 1): gc of the chunk's last token
    state: jax.Array  # (dk, dv)
    t: jax.Array  # (C, C) in the served type
    scores: jax.Array  # (C, C) in the served type


def chunk_step(groups, mm):
    """One chunk of several groups of value heads, a group being the n heads
    (one, or a pair) that share a key head: `groups` lists, a group, (q, k,
    v, gc_col, gc_row, beta_col, state). q, k: (C, dk); v: (C, n dv), the
    heads' values side by side; gc_col (C, n) and gc_row (1, n C): the
    running sum of g inside the chunk, both ways round; beta_col (C, n);
    state (n, dk, dv) float32. `mm` is the operand type of the large
    products. Every (C, C) quantity of a pair is one (C, 2C) array, head h in
    lanes [h C, (h + 1) C): k k^T and q k^T are computed once, the decay, the
    masks and the solve work on whole 128-lane rows at the served chunk. The
    products that are dv wide stay a head's. Each kind of product is written
    for every head before the next kind (`_unit_lower_inverse` says why).
    Returns, a group, (o (C, n dv) float32, the state after the chunk)."""
    c, n = groups[0][0].shape[0], groups[0][3].shape[1]
    dv = groups[0][2].shape[1] // n
    row, col = _lower_masks(c, n * c)
    xs, scores = [], []
    for q, k, _, gc_col, gc_row, beta_col, _ in groups:
        decay = jnp.where(
            row >= col, jnp.exp(jnp.minimum(_side_by_side(gc_col, c) - gc_row, 0.0)), 0.0)
        # [k; q] [k; ..; k]^T: k k^T over q k^T, each already once a head along the lanes
        kq = _dot(jnp.concatenate([k, q], axis=0), jnp.concatenate([k] * n, axis=0), _NT, mm)
        xs.append(jnp.where(row > col, -(_side_by_side(beta_col, c) * kq[:c] * decay), 0.0))
        scores.append(jnp.where(row >= col, kq[c:] * decay, 0.0).astype(mm))
    ts = [t.astype(mm) for t in _unit_lower_inverse(xs, row, col)]
    heads = [
        _Head(q, k, v[:, h * dv:(h + 1) * dv], gc_col[:, h:h + 1], beta_col[:, h:h + 1],
              gc_row[:, (h + 1) * c - 1:(h + 1) * c], state[h],
              t[:, h * c:(h + 1) * c], score[:, h * c:(h + 1) * c])
        for (q, k, v, gc_col, gc_row, beta_col, state), t, score in zip(groups, ts, scores)
        for h in range(n)]
    grows = [jnp.exp(h.gc) for h in heads]
    us = [_dot(h.t, h.beta * h.v, _NN, mm) for h in heads]
    ws = [_dot(h.t, (h.beta * grow) * h.k, _NN, mm) for h, grow in zip(heads, grows)]
    v_news = [u - _dot(w, h.state, _NN, mm) for h, u, w in zip(heads, us, ws)]
    outs = [_dot(grow * h.q, h.state, _NN, mm) + _dot(h.scores, v_new, _NN, mm)
            for h, grow, v_new in zip(heads, grows, v_news)]
    # (1, 1) -> a row -> the state's rows: the chip's compiler broadcasts
    # along one axis at a time
    states = [h.state * jnp.exp(jnp.broadcast_to(h.last, (1, dv)))
              + _dot(jnp.exp(h.last - h.gc) * h.k, v_new, _TN, mm)
              for h, v_new in zip(heads, v_news)]
    return [(jnp.concatenate(outs[i:i + n], axis=1), jnp.stack(states[i:i + n]))
            for i in range(0, len(heads), n)]


def _side(rep: int) -> int:
    """Value heads a chunk step takes side by side: neighbours 2j, 2j + 1
    share their key head where each key head serves an even number."""
    return 2 if rep % 2 == 0 else 1


def _heads_per_block(hv: int, rep: int, dk: int) -> int:
    """Value heads one grid step works on: a block of q a whole number of
    128-lane tiles, and up to 16 heads, whose independent chains of products
    fill each other's waits (a layer at the bucket of 32 took 37.6 ms with 4
    a step, 24.9 with 8, 19.8 with 16 and 20.0 with 32: PERF.md, PR 31)."""
    for cand in (16, 8, 4, 2, 1):
        if hv % cand == 0 and cand % rep == 0 and (cand // rep) * dk % 128 == 0:
            return cand
    return hv


def _kernel(q_ref, k_ref, v_ref, col_ref, row_ref, o_ref, state_ref, *,
            heads, rep, dk, dv, mm):
    @pl.when(pl.program_id(2) == 0)
    def _():  # a new (image, head block): the state starts at zero
        state_ref[...] = jnp.zeros_like(state_ref)

    n = _side(rep)
    starts = range(0, heads, n)
    results = chunk_step([(
        q_ref[0, :, h // rep * dk:(h // rep + 1) * dk],
        k_ref[0, :, h // rep * dk:(h // rep + 1) * dk],
        v_ref[0, :, h * dv:(h + n) * dv],
        col_ref[0, 0, :, h:h + n],
        row_ref[0, 0, 0, h // n:h // n + 1, :],
        col_ref[0, 0, :, heads + h:heads + h + n],
        state_ref[h:h + n]) for h in starts], mm)
    for h, (out, state) in zip(starts, results):
        state_ref[h:h + n] = state
        o_ref[0, :, h * dv:(h + n) * dv] = out.astype(o_ref.dtype)


def _rows(gc, b: int, n_chunks: int, chunk: int, groups: int, side: int):
    """gc (B, Tp, Hv) -> (B, groups, N, Hv / groups / side, side C): a chunk's
    running sums as rows, the heads of one step side by side along the lanes."""
    gc = gc.reshape(b, n_chunks, chunk, groups, -1, side)
    return gc.transpose(0, 3, 1, 4, 5, 2).reshape(b, groups, n_chunks, -1, side * chunk)


def _pallas(q, k, v, gc, beta, chunk: int, mm, interpret: bool):
    """q, k: (B, Tp, Hk, dk); v: (B, Tp, Hv, dv); gc, beta: (B, Tp, Hv)."""
    b, tp, hk, dk = q.shape
    hv, dv = v.shape[2:]
    rep = hv // hk
    side = _side(rep)
    hb = _heads_per_block(hv, rep, dk)
    nhb, n = hv // hb, tp // chunk
    # the per-token scalars in the two layouts the chunk needs them in: as
    # columns (tokens down the sublanes: gc | beta) and gc as rows
    cols = jnp.concatenate(
        [gc.reshape(b, tp, nhb, hb), beta.reshape(b, tp, nhb, hb)], -1
    ).transpose(0, 2, 1, 3)  # (B, nhb, Tp, 2 hb)
    rows = _rows(gc, b, n, chunk, nhb, side)  # (B, nhb, N, hb / side, side C)
    qk_spec = pl.BlockSpec((1, chunk, hb // rep * dk), lambda i, j, s: (i, s, j))
    v_spec = pl.BlockSpec((1, chunk, hb * dv), lambda i, j, s: (i, s, j))
    out = pl.pallas_call(
        functools.partial(_kernel, heads=hb, rep=rep, dk=dk, dv=dv, mm=mm),
        out_shape=jax.ShapeDtypeStruct((b, tp, hv * dv), v.dtype),
        grid=(b, nhb, n),
        in_specs=[
            qk_spec, qk_spec, v_spec,
            pl.BlockSpec((1, 1, chunk, 2 * hb), lambda i, j, s: (i, j, s, 0)),
            pl.BlockSpec((1, 1, 1, hb // side, side * chunk), lambda i, j, s: (i, j, s, 0, 0)),
        ],
        out_specs=v_spec,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_rule_kernel",
    )(q.reshape(b, tp, hk * dk), k.reshape(b, tp, hk * dk), v.reshape(b, tp, hv * dv),
      cols, rows)
    return out.reshape(b, tp, hv, dv)


def _scan(q, k, v, gc, beta, chunk: int, mm):
    """The same chunks in plain jax.numpy: `chunk_step` under vmap over
    images and the heads a step takes together, scanned over the chunks."""
    b, tp, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n, rep = tp // chunk, hv // hk
    side = _side(rep)
    steps = hv // side

    def chunks(x):  # (B, Tp, H, d) -> (N, B, H, C, d)
        return x.reshape(b, n, chunk, *x.shape[2:]).transpose(1, 0, 3, 2, 4)

    q, k = (jnp.repeat(chunks(x), rep // side, axis=2) for x in (q, k))
    v = chunks(v.reshape(b, tp, steps, side * dv))
    col, beta = (x.reshape(b, n, chunk, steps, side).transpose(1, 0, 3, 2, 4) for x in (gc, beta))
    row = _rows(gc, b, n, chunk, 1, side)[:, 0].transpose(1, 0, 2, 3)[..., None, :]
    step = jax.vmap(jax.vmap(lambda *group: chunk_step([group], mm)[0]))

    def body(state, xs):
        out, state = step(*xs, state)
        return state, out

    state = jnp.zeros((b, steps, side, dk, dv), jnp.float32)
    _, out = lax.scan(body, state, (q, k, v, col, row, beta))
    return out.transpose(1, 0, 3, 2, 4).reshape(b, tp, hv, dv).astype(v.dtype)


def chunked_gated_delta_rule(q, k, v, g, beta, chunk: int | None = None,
                             impl: str | None = None, interpret: bool = False):
    """q, k: (B, T, Hk, dk), L2-normalised, q scaled; v: (B, T, Hv, dv), Hv a
    multiple of Hk (each key head serves Hv / Hk value heads); g (log decay,
    <= 0) and beta: (B, T, Hv) float32. Returns o: (B, T, Hv, dv) in v's type.

    T is padded to a multiple of `chunk` (the module's `CHUNK`, the source's
    64, unless given) with tokens of q = k = v = 0,
    g = 0, beta = 0: they follow every real token and a causal recurrence
    never lets them reach one. `impl`: "pallas" (the TPU's kernel) or "scan"
    (jax.numpy); by default the backend decides, and a TPU gets the kernel."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    chunk = chunk or CHUNK
    t = q.shape[1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    b, tp, hv = g.shape
    g = g.astype(jnp.float32)
    gc = jnp.cumsum(g.reshape(b, tp // chunk, chunk, hv), axis=2).reshape(b, tp, hv)
    beta = beta.astype(jnp.float32)
    mm = jnp.bfloat16 if v.dtype == jnp.bfloat16 else jnp.float32
    with jax.named_scope("delta_rule"):
        if impl == "pallas":
            out = _pallas(q, k, v, gc, beta, chunk, mm, interpret)
        elif impl == "scan":
            out = _scan(q, k, v, gc, beta, chunk, mm)
        else:
            raise ValueError(f"impl must be pallas or scan, got {impl!r}")
    return out[:, :t]
