"""Chip probe (hand use) for `kimi_linear_det_ep4`: whether the TPU's kernels
agree with the plain `jax.numpy` forms of the same mathematics on the chip
itself at this configuration's shapes, what each new kernel takes in each of
the forms the builder chose between, and what each bucket's program holds and
takes.

    chiprun -- python3 benchmarks/tools/probe_kimi_linear_det.py 8,16,32

Prints, at one image's real shapes: the KDA kernel (32 heads of 128, bfloat16)
against the float32 `lax.scan` of the same chunks, on gates as the seeded
projections give them; the latent attention kernel (keys of 192, values of
128) against eager attention; the routed layer through the grouped kernel (d
2304, I 1024, 64 of 256 experts held, sigmoid router with a bias) against the
float32 einsum (largest and mean absolute gap, the outputs' scale). Then the
arms, timed by their own device events at the bucket of `PROBE_ARMS_BATCH`
(default 16): the KDA kernel at 4, 8 and 16 heads a grid step as the module
calls it (the gate and the L2 norms computed in its chunks), and handed the
float32 gate and normalised q, k; the routed layer alone (PR 35 also timed
it with a token's sums as three parts of six lane tiles, column blocks of 768:
42.55 ms a layer against 41.79 whole, so `ops/moe.py` stayed as it was). Then,
per batch: compile seconds, the
compiler's own memory analysis, device seconds a batch over five runs, the
program's counters, `memory_stats()` after; with `PROBE_TRACE=<batch>` the
traced batch's ops by kind.
"""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "benchmarks"))
os.environ.setdefault("SPOTTER_TPU_DTYPE", "bfloat16")
import jax
import jax.numpy as jnp
import numpy as np

import reduce_trace
from spotter_tpu.models.configs import KimiLinearDetConfig
from spotter_tpu.models.kimi_linear import NORM_TOPK_EPS, KimiLinearDetector
from spotter_tpu.models.layers import causal_latent_attention
from spotter_tpu.ops import kda, moe

dev = jax.devices()[0]
print("device", dev.platform, dev.device_kind, flush=True)
with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "configs", "kimi_linear_det_ep4.json")) as f:
    cfg = KimiLinearDetConfig.from_hf(json.load(f))
h, w = cfg.image_size
d, inter, held, routed = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts, cfg.num_routed_experts
heads, dk, t = cfg.linear_num_heads, cfg.linear_head_dim, cfg.num_tokens
rng = np.random.default_rng(0)
bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
ARMS_BATCH = int(os.environ.get("PROBE_ARMS_BATCH", "16"))


def gap(name, got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    print(f"{name}: max gap {np.abs(got - want).max():.5f}, mean gap {np.abs(got - want).mean():.6f}, "
          f"scale {np.abs(want).mean():.4f}, finite {bool(np.isfinite(got).all())}", flush=True)


def device_ms(label, fn, *args, marks=()):
    """Compile, run once, then trace three runs: the device's busy time a run
    and the time of the events whose names hold each of `marks`."""
    jax.block_until_ready(fn(*args))
    trace_dir = os.path.join("chiprun_out", "probe_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    events, capture_ns = reduce_trace.load_xplane(reduce_trace.find_xplane(trace_dir))
    reduced = reduce_trace.reduce(events, capture_ns=capture_ns)
    shutil.rmtree(trace_dir, ignore_errors=True)
    rows = {mark: 1e3 * sum(s for name, s in reduced["op_seconds"].items()
                            if mark in name.partition(" = ")[0]) / 3 for mark in marks}
    print(f"{label}: busy {1e3 * reduced['busy_s'] / 3:.3f} ms a run; "
          + ", ".join(f"{mark} {ms:.3f} ms" for mark, ms in rows.items()), flush=True)
    return reduced


def unit(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


STEP = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (heads * dk,)))
A_LOG = np.log(rng.uniform(1, 16, (heads,))).astype(np.float32)
DT_BIAS = (STEP + np.log(-np.expm1(-STEP))).astype(np.float32)


def rule_inputs(b):
    """q, k, v as the mixer hands them over; the gate as seeded projections
    give it: A in U(1, 16) a head, a unit normal from f_b around dt_bias.
    Returns (q, k, v, the raw gate, g computed from it, beta)."""
    q = (unit(rng.standard_normal((b, t, heads, dk))) * dk**-0.5).astype(np.float32)
    k = unit(rng.standard_normal((b, t, heads, dk))).astype(np.float32)
    v = rng.standard_normal((b, t, heads, dk)).astype(np.float32)
    raw = rng.standard_normal((b, t, heads, dk)).astype(np.float32)
    g = -(np.exp(A_LOG)[:, None] * np.logaddexp(raw + DT_BIAS.reshape(heads, dk), 0)).astype(np.float32)
    beta = rng.uniform(0, 1, (b, t, heads)).astype(np.float32)
    return q, k, v, raw, g, beta


# ---- the kernels against jax.numpy, on the chip, one image's shapes ----------
q, k, v, raw, g, beta = rule_inputs(1)
print("gate: -g a token, mean", round(float(-g.mean()), 3), "largest", round(float((-g).max()), 2),
      "largest running sum in a chunk", round(float(np.abs(np.cumsum(g[0, :64], 0)).max()), 1), flush=True)
with jax.default_matmul_precision("highest"):
    want = jax.jit(lambda *a: kda.chunked_kda(*a, impl="scan"))(q, k, v, g, beta)
for label, cast in (("bfloat16", bf), ("float32", jnp.asarray)):
    try:
        gap(f"KDA, {label} kernel vs float32 scan of the same chunks",
            jax.jit(lambda *a: kda.chunked_kda(*a, impl="pallas"))(cast(q), cast(k), cast(v), g, beta), want)
    except Exception as exc:
        print(f"KDA, {label} kernel: FAILED {str(exc)[:800]}", flush=True)
gap("KDA, bfloat16 kernel that computes its gate from the raw projection vs the same scan",
    jax.jit(lambda q, k, v, r, b: kda.chunked_kda(q, k, v, kda.RawGate(r, A_LOG, DT_BIAS), b, impl="pallas"))(
        bf(q), bf(k), bf(v), raw, beta), want)
del want

nope, pe, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
ah = cfg.num_attention_heads
qa = rng.standard_normal((1, t, ah, nope + pe)).astype(np.float32) * (nope + pe)**-0.5
ka = rng.standard_normal((1, t, ah, nope + pe)).astype(np.float32)
va = rng.standard_normal((1, t, ah, dv)).astype(np.float32)


def eager(qa, ka, va):
    logits = jnp.einsum("bqhd,bshd->bhqs", qa, ka)
    logits = jnp.where(np.tril(np.ones((t, t), bool)), logits, -jnp.inf)
    return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(logits, -1), va)


with jax.default_matmul_precision("highest"):
    want = jax.jit(eager)(qa, ka, va)
gap("latent attention, bfloat16 kernel vs float32 eager",
    jax.jit(causal_latent_attention)(bf(qa), bf(ka), bf(va)), want)
del want

x = rng.standard_normal((t, d)).astype(np.float32)
router = rng.standard_normal((d, routed)).astype(np.float32) / np.sqrt(d)
bias = (rng.standard_normal(routed) * 0.02).astype(np.float32)
gate_up = (rng.standard_normal((held, d, 2 * inter)) / np.sqrt(d)).astype(np.float32)
down = (rng.standard_normal((held, inter, d)) / np.sqrt(inter)).astype(np.float32)
scores = jax.jit(lambda a, b: moe.router_scores(a, b, "sigmoid"))(x, router)
weights, experts = jax.jit(lambda s, b: moe.select(
    s, cfg.num_experts_per_token, bias=b, eps=NORM_TOPK_EPS, scale=cfg.routed_scaling_factor))(scores, bias)
counts = np.asarray(moe.held_tokens(experts.reshape(1, -1), 0, held))[0]
print("held assignments", int(counts.sum()), "of", experts.size, "fullest / mean",
      round(float(counts.max() / counts.mean()), 2), "moved by the bias",
      int(moe.moved_by_bias(scores, experts).sum()), flush=True)
with jax.default_matmul_precision("highest"):
    want = jax.jit(lambda *a: moe.routed_experts(*a, impl="einsum", tile=128, window_rows=2048))(
        x, weights, experts, gate_up, down)
gap("routed experts, bfloat16 kernel vs float32 einsum",
    jax.jit(lambda *a: moe.routed_experts(*a, impl="pallas"))(
        bf(x), weights, experts, bf(gate_up), bf(down)), want)
del want

# ---- the arms, by their own device events ------------------------------------
if os.environ.get("PROBE_ARMS", "1") != "0":
    b = ARMS_BATCH
    qs, ks, vs, raws, gs, betas = (jax.device_put(a) for a in rule_inputs(b))
    qs, ks, vs, raws = bf(qs), bf(ks), bf(vs), bf(raws)
    for hb in (4, 8, 16):
        device_ms(f"KDA layer's rule at the bucket of {b}, gate computed in the chunks, {hb} heads a step",
                  jax.jit(lambda q, k, v, r, bb, hb=hb: kda.chunked_kda(
                      q, k, v, kda.RawGate(r, A_LOG, DT_BIAS), bb, impl="pallas", heads_per_step=hb,
                      normalise=True)),
                  qs, ks, vs, raws, betas, marks=("kda_kernel", "fusion", "copy", "pad"))
    device_ms(f"KDA layer's rule at the bucket of {b}, float32 gate handed over, 8 heads a step",
              jax.jit(lambda *a: kda.chunked_kda(*a, impl="pallas", heads_per_step=8)),
              qs, ks, vs, gs, betas, marks=("kda_kernel", "fusion", "copy", "pad"))
    del qs, ks, vs, raws, gs, betas
    xs = bf(rng.standard_normal((b * t, d)).astype(np.float32))
    sc = jax.jit(lambda a, r: moe.router_scores(a, r, "sigmoid"))(xs, router)
    ws, es = jax.jit(lambda s, bb: moe.select(
        s, cfg.num_experts_per_token, bias=bb, eps=NORM_TOPK_EPS, scale=cfg.routed_scaling_factor))(sc, bias)
    gu, dn = bf(gate_up), bf(down)
    device_ms(f"routed layer at the bucket of {b}",
              jax.jit(lambda *a: moe.routed_experts(*a, impl="pallas")), xs, ws, es, gu, dn,
              marks=("expert_matmul_kernel", "while", "fusion", "copy"))
    del xs, sc, ws, es, gu, dn
del gate_up, down

# ---- the whole forward pass per bucket --------------------------------------
module = KimiLinearDetector(cfg, dtype=jnp.bfloat16)


def init(key):
    params = module.init(key, np.zeros((1, h, w, 3), np.float32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), sub in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if leaf.ndim >= 2:
            fan_in = np.prod(leaf.shape[:-1]) / (leaf.shape[0] if leaf.ndim == 3 else 1)
            out.append((jax.random.normal(sub, leaf.shape) / np.sqrt(fan_in)).astype(jnp.bfloat16))
        elif "e_score_correction_bias" in name:
            out.append(0.02 * jax.random.normal(sub, leaf.shape))
        elif "A_log" in name:
            out.append(jnp.log(jax.random.uniform(sub, leaf.shape, minval=1.0, maxval=16.0)))
        elif "dt_bias" in name:
            step = jnp.exp(jax.random.uniform(sub, leaf.shape, minval=np.log(1e-3), maxval=np.log(1e-1)))
            out.append(step + jnp.log(-jnp.expm1(-step)))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


print("fresh", json.dumps(dev.memory_stats()), flush=True)
params = jax.jit(init)(jax.random.PRNGKey(1))
jax.block_until_ready(params)
count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
print("parameters held", count, "after init", json.dumps(dev.memory_stats()), flush=True)
fwd = jax.jit(lambda p, x: module.apply({"params": p}, x))
for b in [int(a) for a in sys.argv[1].split(",") if a]:
    pixels = jax.device_put(rng.standard_normal((b, h, w, 3), np.float32))
    t0 = time.time()
    try:
        lo = fwd.lower(params, pixels).compile()
    except Exception as exc:
        print("batch", b, "COMPILE FAILED", str(exc)[:1500], flush=True)
        continue
    m = lo.memory_analysis()
    print("batch", b, "compile_s", round(time.time() - t0, 1), "analysis args",
          m.argument_size_in_bytes, "temp", m.temp_size_in_bytes, "code",
          m.generated_code_size_in_bytes, flush=True)
    out = fwd(params, pixels)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(5):
        out = fwd(params, pixels)
    jax.block_until_ready(out)
    dt = (time.time() - t0) / 5
    tokens = np.asarray(out["moe_expert_tokens"]).sum(0)
    print("batch", b, "s/batch", round(dt, 4), "ms/image", round(1e3 * dt / b, 2),
          "finite", bool(np.isfinite(np.asarray(out["logits"])).all()),
          "bias moved / assignments / held", int(np.asarray(out["moe_bias_moved"]).sum()),
          int(np.asarray(out["moe_assignments"]).sum()), int(tokens.sum()),
          "fullest/mean per layer", (tokens.max(-1) / tokens.mean(-1)).round(2).tolist(),
          "gate spread, nats a token", round(float(np.asarray(out["kda_gate_spread"]).mean()), 3), flush=True)
    if os.environ.get("PROBE_TRACE") and b == int(os.environ["PROBE_TRACE"]):
        trace_dir = os.path.join("chiprun_out", "probe_trace")
        jax.profiler.start_trace(trace_dir)
        jax.block_until_ready(fwd(params, pixels))
        jax.profiler.stop_trace()
        events, capture_ns = reduce_trace.load_xplane(reduce_trace.find_xplane(trace_dir))
        reduced = reduce_trace.reduce(events, capture_ns=capture_ns)
        print("traced one batch of", b, ": busy", round(reduced["busy_s"], 4), "s; ops by kind:",
              [[k, round(s, 4)] for k, s in reduced["device_ops"]], flush=True)
        top = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:30]
        for name, s in top:
            print(f"  {s:.4f} s x{reduced['op_calls'][name]:.0f}  {name[:170]}", flush=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    del pixels, out
    print("after batch", b, json.dumps(dev.memory_stats()), flush=True)
