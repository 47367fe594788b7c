"""Chip probe (hand use) for `lfm2_moe_det_pp4`: whether the TPU's kernels agree
with the plain `jax.numpy` forms of the same mathematics on the chip itself at
this configuration's shapes, and what each bucket's program holds and takes.

    chiprun -- python3 benchmarks/tools/probe_lfm2_moe_det.py 8,16,32

Prints, at one image's real shapes: the routed layer through the grouped
kernel (I 1792, all 32 experts held, sigmoid router with a bias) against the
float32 einsum, the causal attention kernel (32 / 8 heads of 64) against eager
attention (largest and mean absolute gap, the outputs' scale). Then, per
batch: compile seconds, the compiler's own memory analysis, device seconds a
batch over five runs, the program's counters, `memory_stats()` after; with
`PROBE_TRACE=<batch>` the traced batch's ops by kind.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
os.environ.setdefault("SPOTTER_TPU_DTYPE", "bfloat16")
import jax
import jax.numpy as jnp
import numpy as np

from spotter_tpu.models.configs import Lfm2MoeDetConfig
from spotter_tpu.models.layers import causal_gqa_attention
from spotter_tpu.models.lfm2_moe import NORM_TOPK_EPS, Lfm2MoeDetector
from spotter_tpu.ops import moe

dev = jax.devices()[0]
print("device", dev.platform, dev.device_kind, flush=True)
with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "configs", "lfm2_moe_det_pp4.json")) as f:
    cfg = Lfm2MoeDetConfig.from_hf(json.load(f))
h, w = cfg.image_size
d, inter, n = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
rng = np.random.default_rng(0)
bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731


def gap(name, got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    print(f"{name}: max gap {np.abs(got - want).max():.5f}, mean gap {np.abs(got - want).mean():.6f}, "
          f"scale {np.abs(want).mean():.4f}", flush=True)


# ---- the kernels against jax.numpy, on the chip, one image's shapes ----------
t = cfg.num_tokens
x = rng.standard_normal((t, d)).astype(np.float32)
router = rng.standard_normal((d, n)).astype(np.float32) / np.sqrt(d)
bias = (rng.standard_normal(n) * 0.05).astype(np.float32)
gate_up = (rng.standard_normal((n, d, 2 * inter)) / np.sqrt(d)).astype(np.float32)
down = (rng.standard_normal((n, inter, d)) / np.sqrt(inter)).astype(np.float32)
scores = jax.jit(lambda a, b: moe.router_scores(a, b, "sigmoid"))(x, router)
weights, experts = jax.jit(lambda s, b: moe.select(s, cfg.num_experts_per_tok, bias=b, eps=NORM_TOPK_EPS))(
    scores, bias)
counts = np.asarray(moe.held_tokens(experts.reshape(1, -1), 0, n))[0]
print("assignments", int(counts.sum()), "fullest / mean", round(float(counts.max() / counts.mean()), 2),
      "moved by the bias", int(moe.moved_by_bias(scores, experts).sum()), flush=True)
with jax.default_matmul_precision("highest"):
    want = jax.jit(lambda *a: moe.routed_experts(*a, impl="einsum", tile=128, window_rows=2048))(
        x, weights, experts, gate_up, down)
gap("routed experts, bfloat16 kernel vs float32 einsum",
    jax.jit(lambda *a: moe.routed_experts(*a, impl="pallas"))(
        bf(x), weights, experts, bf(gate_up), bf(down)), want)
del gate_up, down, want

heads, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
qa = rng.standard_normal((1, t, heads, hd)).astype(np.float32) * hd**-0.5
ka, va = (rng.standard_normal((1, t, kv, hd)).astype(np.float32) for _ in range(2))


def eager(qa, ka, va):
    qh = qa.reshape(1, t, kv, heads // kv, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qh, ka)
    logits = jnp.where(np.tril(np.ones((t, t), bool)), logits, -jnp.inf)
    return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(logits, -1), va).reshape(1, t, heads, hd)


with jax.default_matmul_precision("highest"):
    want = jax.jit(eager)(qa, ka, va)
gap("causal attention, bfloat16 kernel vs float32 eager",
    jax.jit(causal_gqa_attention)(bf(qa), bf(ka), bf(va)), want)
del want

# ---- the whole forward pass per bucket --------------------------------------
module = Lfm2MoeDetector(cfg, dtype=jnp.bfloat16)


def init(key):
    params = module.init(key, np.zeros((1, h, w, 3), np.float32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), sub in zip(leaves, keys):
        if leaf.ndim >= 2:
            fan_in = np.prod(leaf.shape[:-1]) / (leaf.shape[0] if leaf.ndim == 3 else 1)
            out.append((jax.random.normal(sub, leaf.shape) / np.sqrt(fan_in)).astype(jnp.bfloat16))
        elif "expert_bias" in jax.tree_util.keystr(path):
            out.append(0.05 * jax.random.normal(sub, leaf.shape))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


print("fresh", json.dumps(dev.memory_stats()), flush=True)
params = jax.jit(init)(jax.random.PRNGKey(1))
jax.block_until_ready(params)
print("after init", json.dumps(dev.memory_stats()), flush=True)
fwd = jax.jit(lambda p, x: module.apply({"params": p}, x))
for b in [int(a) for a in sys.argv[1].split(",")]:
    pixels = jax.device_put(rng.standard_normal((b, h, w, 3), np.float32))
    t0 = time.time()
    lo = fwd.lower(params, pixels).compile()
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
          "bias moved / assignments", int(np.asarray(out["moe_bias_moved"]).sum()),
          int(np.asarray(out["moe_assignments"]).sum()),
          "fullest/mean per layer", (tokens.max(-1) / tokens.mean(-1)).round(2).tolist(), flush=True)
    if os.environ.get("PROBE_TRACE") and b == int(os.environ["PROBE_TRACE"]):
        import shutil

        trace_dir = os.path.join("chiprun_out", "probe_trace")
        jax.profiler.start_trace(trace_dir)
        jax.block_until_ready(fwd(params, pixels))
        jax.profiler.stop_trace()
        sys.path.insert(0, os.path.join(os.getcwd(), "benchmarks"))
        import reduce_trace

        events, capture_ns = reduce_trace.load_xplane(reduce_trace.find_xplane(trace_dir))
        reduced = reduce_trace.reduce(events, capture_ns=capture_ns)
        print("traced one batch of", b, ": busy", round(reduced["busy_s"], 4), "s; ops by kind:",
              [[k, round(s, 4)] for k, s in reduced["device_ops"]], flush=True)
        top = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])[:25]
        for name, s in top:
            print(f"  {s:.4f} s x{reduced['op_calls'][name]:.0f}  {name[:160]}", flush=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    del pixels, out
    print("after batch", b, json.dumps(dev.memory_stats()), flush=True)
