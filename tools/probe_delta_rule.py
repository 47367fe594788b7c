"""Chip probe (hand use): the delta rule's kernel alone, one linear-attention
layer's call at the published shapes (4300 tokens, 16 key / 32 value heads of
128, bfloat16), and what it spends its time on.

    chiprun -- python3 tools/probe_delta_rule.py 8,32 [variant ...]

Each variant swaps a piece of `ops/delta_rule.py`'s chunk step for a stub
(wrong answers, timing only), or the number of heads a grid step takes, and
times the call: milliseconds of the `gated_delta_rule_kernel` events in a
profiler trace of five calls, and the host's clock around them. `as_is` is the module as it stands and is also
compared with the float32 scan on one image. Without variants: all of them.
"""

import functools
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))

import jax
import jax.numpy as jnp
import numpy as np

import reduce_trace
from spotter_tpu.ops import delta_rule

T, HK, HV, D = 4300, 16, 32, 128
_NN, _NT, _TN = delta_rule._NN, delta_rule._NT, delta_rule._TN
_dot = delta_rule._dot


_inverse = delta_rule._unit_lower_inverse


def solve_identity(xs, row, col):
    return [jnp.where(row == col, 1.0, 0.0) + x for x in xs]


def solve_bfloat16(xs, row, col):
    """The same ten products on bfloat16 operands: NOT a candidate (a
    different result); it tells the passes float32 takes from the length of
    the chain."""
    dot = delta_rule._dot
    delta_rule._dot = lambda a, b, dims, dtype: dot(a, b, dims, jnp.bfloat16)
    try:
        return _inverse(xs, row, col)
    finally:
        delta_rule._dot = dot


def _shapes(group):
    """(n, C, dv of one head): what a stub needs of a group's shapes."""
    n = group[3].shape[1]
    return n, group[2].shape[0], group[2].shape[1] // n


def step_elementwise_stubbed(groups, mm):
    """Every product and the solve, none of the exp, masks and scalings."""
    n, c, dv = _shapes(groups[0])
    row, col = delta_rule._lower_masks(c, n * c)
    kqs = [_dot(jnp.concatenate([k, q], axis=0), jnp.concatenate([k] * n, axis=0), _NT, mm)
           for q, k, *_ in groups]
    ts = delta_rule._unit_lower_inverse([kq[:c] for kq in kqs], row, col)
    heads = [(q, k, v[:, h * dv:(h + 1) * dv], state[h], t.astype(mm)[:, h * c:(h + 1) * c],
              kq[c:].astype(mm)[:, h * c:(h + 1) * c])
             for (q, k, v, _, _, _, state), t, kq in zip(groups, ts, kqs) for h in range(n)]
    us = [_dot(t, v, _NN, mm) for _, _, v, _, t, _ in heads]
    ws = [_dot(t, k, _NN, mm) for _, k, _, _, t, _ in heads]
    v_news = [u - _dot(w, state, _NN, mm) for (_, _, _, state, _, _), u, w in zip(heads, us, ws)]
    outs = [_dot(q, state, _NN, mm) + _dot(score, v_new, _NN, mm)
            for (q, _, _, state, _, score), v_new in zip(heads, v_news)]
    states = [state + _dot(k, v_new, _TN, mm) for (_, k, _, state, _, _), v_new in zip(heads, v_news)]
    return [(jnp.concatenate(outs[i:i + n], axis=1), jnp.stack(states[i:i + n]))
            for i in range(0, len(heads), n)]


def step_products_stubbed(groups, mm):
    """The elementwise work and the solve, none of the products in the
    served type."""
    n, c, dv = _shapes(groups[0])
    row, col = delta_rule._lower_masks(c, n * c)
    decays = [jnp.where(row >= col, jnp.exp(jnp.minimum(
        delta_rule._side_by_side(gc_col, c) - gc_row, 0.0)), 0.0) for _, _, _, gc_col, gc_row, _, _ in groups]
    xs = [jnp.where(row > col, -(delta_rule._side_by_side(group[5], c) * decay), 0.0)
          for group, decay in zip(groups, decays)]
    ts = delta_rule._unit_lower_inverse(xs, row, col)
    results = []
    for (q, k, v, gc_col, gc_row, beta_col, state), t, decay in zip(groups, ts, decays):
        outs, states = [], []
        for h in range(n):
            col_h, beta_h = gc_col[:, h:h + 1], beta_col[:, h:h + 1]
            grow = jnp.exp(col_h)
            lead = jnp.sum((t * decay)[:, h * c:(h + 1) * c], axis=1, keepdims=True)
            v_new = beta_h * v[:, h * dv:(h + 1) * dv] * lead - (beta_h * grow) * k
            outs.append(grow * q + v_new)
            g_last = gc_row[:, (h + 1) * c - 1:(h + 1) * c]
            states.append(state[h] * jnp.exp(jnp.broadcast_to(g_last, (1, dv))))
        results.append((jnp.concatenate(outs, axis=1), jnp.stack(states)))
    return results


def step_empty(groups, mm):
    """Nothing: the grid's steps, their DMAs and the stores."""
    return [(v.astype(jnp.float32), state) for _, _, v, _, _, _, state in groups]


VARIANTS = {
    "as_is": {},
    "solve_identity": {"_unit_lower_inverse": solve_identity},
    "solve_bfloat16": {"_unit_lower_inverse": solve_bfloat16},
    "elementwise_stubbed": {"chunk_step": step_elementwise_stubbed},
    "products_stubbed": {"chunk_step": step_products_stubbed},
    "solve_identity_elementwise_stubbed": {
        "_unit_lower_inverse": solve_identity, "chunk_step": step_elementwise_stubbed},
    "empty": {"chunk_step": step_empty},
    # another number of value heads a grid step (the module takes up to 16)
    **{f"heads_{n}": {"_heads_per_block": lambda hv, rep, dk, n=n: n} for n in (4, 8, 32)},
}


def inputs(b, key):
    """On the device: a bucket of 32 is 1.4 GB of operands."""
    kq, kk, kv, kg, kb = jax.random.split(key, 5)
    q, k = (jax.random.normal(s, (b, T, HK, D), jnp.float32) for s in (kq, kk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(kv, (b, T, HV, D), jnp.float32)
    g = -jnp.exp(jax.random.uniform(kg, (b, T, HV), minval=np.log(1e-3), maxval=np.log(1.6)))
    beta = jax.random.uniform(kb, (b, T, HV))
    return q, k, v, g, beta


def kernel_ms(fn, args, calls=5):
    """(ms a call of the kernel's events in a trace, ms a call on the host's
    clock), the first call (compile) outside both."""
    jax.block_until_ready(fn(*args))
    trace_dir = os.path.join("chiprun_out", "probe_delta_rule_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    t0 = time.time()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    wall = (time.time() - t0) / calls
    jax.profiler.stop_trace()
    events, capture_ns = reduce_trace.load_xplane(reduce_trace.find_xplane(trace_dir))
    reduced = reduce_trace.reduce(events, capture_ns=capture_ns)
    shutil.rmtree(trace_dir, ignore_errors=True)
    kernel = sum(s for name, s in reduced.get("op_seconds", {}).items() if "gated_delta_rule" in name)
    return 1e3 * kernel / calls, 1e3 * wall


def main():
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    buckets = [int(a) for a in sys.argv[1].split(",")]
    names = sys.argv[2:] or list(VARIANTS)
    bf = functools.partial(jnp.asarray, dtype=jnp.bfloat16)

    q, k, v, g, beta = inputs(1, jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda *a: delta_rule.chunked_gated_delta_rule(*a, impl="scan"))(q, k, v, g, beta))
    got = np.asarray(jax.jit(lambda *a: delta_rule.chunked_gated_delta_rule(*a, impl="pallas"))(
        bf(q), bf(k), bf(v), g, beta), np.float32)
    print(f"bfloat16 kernel vs float32 scan, one image: max gap {np.abs(got - want).max():.5f}, "
          f"mean gap {np.abs(got - want).mean():.6f}, scale {np.abs(want).mean():.4f}", flush=True)

    results = {}
    for b in buckets:
        q, k, v, g, beta = jax.jit(inputs, static_argnums=0)(b, jax.random.PRNGKey(b))
        args = (bf(q), bf(k), bf(v), g, beta)
        del q, k, v
        for name in names:
            saved = {attr: getattr(delta_rule, attr) for attr in VARIANTS[name]}
            for attr, stub in VARIANTS[name].items():
                setattr(delta_rule, attr, stub)
            try:
                fn = jax.jit(lambda *a: delta_rule.chunked_gated_delta_rule(*a, impl="pallas"))
                t0 = time.time()
                kernel, wall = kernel_ms(fn, args)
                results[f"{name}@{b}"] = {"kernel_ms": kernel, "wall_ms": wall}
                print(f"bucket {b:2d} {name:36s} kernel {kernel:8.3f} ms  wall {wall:8.3f} ms  "
                      f"(compile and six calls {time.time() - t0:.1f} s)", flush=True)
            finally:
                for attr, fn_ in saved.items():
                    setattr(delta_rule, attr, fn_)
        del args
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "probe_delta_rule.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
