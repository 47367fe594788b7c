"""Splash-attention block-size sweep at ViT detector shapes.

Produced the round-4 block_kv policy (models/layers.py _splash_block_kv):
full-row kv at s_pad=3840 (owlv2) beat the 768 fallback by 20%/layer;
2304 stays best at 4608 (yolos). Round 5 adds CLI configs so new shapes
(yolos bq/bkv grid, reduced-padding s_pad=4352/4480 points, the
ADVICE-r4 s_pad=3072 interpolation check) sweep without editing the file.

Usage on the real chip:
  python tools/bench_splash.py --s 4300 --configs \
      4608:384:2304:768 4608:512:2304:1152 4352:256:2176:2176
(each config is s_pad:block_q:block_kv:block_kv_compute; s_pad must be a
multiple of block_q and block_kv, all multiples of 128). Calibrate the
session's fori_loop floor first (pre-round note, round 4, git history) if absolute
numbers matter — deltas at the same loop count cancel it.
"""

import argparse
import sys
import time

sys.path.insert(0, "/root/repo")
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as sk,
)
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as sm,
)


def run(q, k, v, s, s_pad, bq, bkv, bkvc, loop=8, iters=3):
    h = q.shape[1]
    try:
        bs = sk.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
            block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkvc,
            block_q_dq=bq, block_kv_dq=bkv,
        )
        kern = sk.make_splash_mha(
            mask=sm.MultiHeadMask([sm.FullMask((s_pad, s_pad))] * h),
            head_shards=1, q_seq_shards=1, block_sizes=bs,
        )
    except Exception as e:  # e.g. block size not dividing s_pad
        print(f"s_pad={s_pad} bq={bq} bkv={bkv} bkvc={bkvc}: "
              f"FAILED {str(e).splitlines()[0][:90]}", flush=True)
        return
    pad = s_pad - s

    def f(q, k, v):
        def prep(x):
            return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))

        seg = (jnp.arange(s_pad) >= s).astype(jnp.int32)
        segs = sk.SegmentIds(q=seg, kv=seg)

        def body(i, c):
            out = jax.vmap(kern, in_axes=(0, 0, 0, None))(
                prep(q + i * jnp.asarray(1e-6, q.dtype)), prep(k), prep(v), segs
            )
            return c + jnp.sum(out.astype(jnp.float32))

        return jax.lax.fori_loop(0, loop, body, jnp.float32(0))

    jf = jax.jit(f)
    try:
        jax.device_get(jf(q, k, v))
        t0 = time.perf_counter()
        for _ in range(iters):
            r = jf(q, k, v)
        jax.device_get(r)
        ms = (time.perf_counter() - t0) / (iters * loop) * 1e3
        print(f"s_pad={s_pad} bq={bq} bkv={bkv} bkvc={bkvc}: "
              f"{ms:.3f} ms/layer-attn", flush=True)
    except Exception as e:
        print(f"s_pad={s_pad} bq={bq} bkv={bkv} bkvc={bkvc}: "
              f"FAILED {str(e).splitlines()[0][:90]}", flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--b", type=int, default=8)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--s", type=int, default=3601)
    p.add_argument("--hd", type=int, default=64)
    p.add_argument("--loop", type=int, default=8)
    p.add_argument(
        "--configs", nargs="+",
        default=["3840:384:768:768", "3840:384:3840:768", "4608:384:2304:768"],
        help="s_pad:block_q:block_kv:block_kv_compute per point",
    )
    args = p.parse_args()

    rng = np.random.default_rng(0)
    q = jnp.asarray(
        rng.standard_normal((args.b, args.heads, args.s, args.hd)), jnp.bfloat16
    ) * 0.125
    k = jnp.asarray(
        rng.standard_normal((args.b, args.heads, args.s, args.hd)), jnp.bfloat16
    )
    v = jnp.asarray(
        rng.standard_normal((args.b, args.heads, args.s, args.hd)), jnp.bfloat16
    )
    for cfg in args.configs:
        s_pad, bq, bkv, bkvc = (int(x) for x in cfg.split(":"))
        if s_pad < args.s:
            print(f"skip {cfg}: s_pad < s={args.s}")
            continue
        run(q, k, v, args.s, s_pad, bq, bkv, bkvc, loop=args.loop)


if __name__ == "__main__":
    main()
