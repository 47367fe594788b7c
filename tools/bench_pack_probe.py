"""Probe: what does the one-hot tile build ACTUALLY cost per formulation?

The merged one-hot MSDA kernel's dominant cost is the tile build:
jc x (compare + select + add) over (Q_TILE, S_TILE) elements per hit tile.
This probe isolates that loop shape — no dot, no hit masks — and times
formulation variants via loop-in-jit:

  base    per-chain broadcast compare (the production kernel's idiom)
  hoist   idx/w broadcasts materialized ONCE outside the tile walk
  arith   mask.astype(f32) * w instead of where(mask, w, 0)
  i16/bf16 variants: 2x-packed VPU lanes (Mosaic permitting)
  null    empty body — fixed machinery cost to subtract

Findings (2026-07-31): pre-round note, round 4, git history.
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QT, TS, JC, REPS = 64, 640, 16, 32


def _body_base(idx, w, col, k, acc_dtype, cmp_dtype):
    oh = jnp.zeros((QT, TS), acc_dtype)
    for j in range(JC):
        oh = oh + jnp.where(
            col == idx[:, j : j + 1].astype(cmp_dtype),
            w[:, j : j + 1].astype(acc_dtype),
            jnp.zeros((), acc_dtype),
        )
    return oh


def _kernel(idx_ref, w_ref, out_ref, *, variant, cmp_dtype, acc_dtype):
    idx = idx_ref[0]
    w = w_ref[0]
    acc = jnp.zeros((QT, TS), acc_dtype)
    col0 = jax.lax.broadcasted_iota(jnp.int32, (QT, TS), 1).astype(cmp_dtype)

    if variant in ("hoist", "arith", "hoist16"):
        bj = [
            jnp.broadcast_to(idx[:, j : j + 1], (QT, TS)).astype(cmp_dtype)
            for j in range(JC)
        ]
        wj = [
            jnp.broadcast_to(w[:, j : j + 1], (QT, TS)).astype(acc_dtype)
            for j in range(JC)
        ]

    for k in range(REPS):
        col = col0 + jnp.asarray(k, cmp_dtype)
        if variant == "null":
            oh = col.astype(acc_dtype)
        elif variant == "base":
            oh = _body_base(idx, w, col, k, acc_dtype, cmp_dtype)
        elif variant in ("hoist", "hoist16"):
            oh = jnp.zeros((QT, TS), acc_dtype)
            for j in range(JC):
                oh = oh + jnp.where(bj[j] == col, wj[j], jnp.zeros((), acc_dtype))
        elif variant == "arith":
            oh = jnp.zeros((QT, TS), acc_dtype)
            for j in range(JC):
                oh = oh + (bj[j] == col).astype(acc_dtype) * wj[j]
        acc = acc + oh
    out_ref[0] = acc.astype(jnp.float32)


def run(name, variant, cmp_dtype, acc_dtype, idx, w):
    kernel = partial(
        _kernel, variant=variant, cmp_dtype=cmp_dtype, acc_dtype=acc_dtype
    )
    bh = idx.shape[0]

    def call(idx, w):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((bh, QT, TS), jnp.float32),
            grid=(bh,),
            in_specs=[
                pl.BlockSpec((1, QT, JC), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, QT, JC), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (1, QT, TS), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
        )(idx, w)

    def loop(idx, w):
        def body(i, carry):
            return carry + jnp.sum(call(idx + i, w))

        return jax.lax.fori_loop(0, 10, body, jnp.float32(0))

    try:
        f = jax.jit(loop)
        jax.device_get(f(idx, w))
        t0 = time.perf_counter()
        for _ in range(3):
            r = f(idx, w)
        jax.device_get(r)
        ms = (time.perf_counter() - t0) / (3 * 10) * 1e3
        el = idx.shape[0] * REPS * JC * QT * TS
        print(
            f"{name:28s}: {ms:7.3f} ms/call  "
            f"({el / (ms * 1e-3) / 1e9:6.1f} Gel/s chain-elements)",
            flush=True,
        )
        return ms
    except Exception as e:
        msg = str(e).split("\n")[0][:120]
        print(f"{name:28s}: FAILED {msg}", flush=True)
        return None


def main():
    bh = 16
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, TS, (bh, QT, JC)), jnp.int32)
    w = jnp.asarray(rng.random((bh, QT, JC)), jnp.float32)
    print(f"grid=({bh},) reps={REPS} jc={JC} tile=({QT},{TS})", flush=True)
    run("null (machinery)", "null", jnp.int32, jnp.float32, idx, w)
    run("base i32/f32", "base", jnp.int32, jnp.float32, idx, w)
    run("hoist i32/f32", "hoist", jnp.int32, jnp.float32, idx, w)
    run("arith i32/f32", "arith", jnp.int32, jnp.float32, idx, w)
    run("hoist i32/bf16", "hoist", jnp.int32, jnp.bfloat16, idx, w)
    run("hoist i16/bf16 (2x-packed?)", "hoist16", jnp.int16, jnp.bfloat16, idx, w)
    run("arith i16/bf16", "arith", jnp.int16, jnp.bfloat16, idx, w)
    run("base i32/bf16", "base", jnp.int32, jnp.bfloat16, idx, w)


if __name__ == "__main__":
    main()
