"""Chip probe (hand use) for `joyai_flash_det_ep2`: whether the served latent
attention (query latent, rotary term, the splash kernel with keys of 192 and
values of 128) agrees on the chip with the same module computed eagerly in
float32, at one image's real shapes, and what each bucket's program holds and
takes.

    python3 benchmarks/tools/probe_joyai_flash_det.py 8,16,32   # one chip

Prints the module's two forms' largest and mean absolute gap and the
outputs' scale; then, per batch: compile seconds, the compiler's own memory
analysis, device seconds a batch over five runs, the program's routing
counters, `memory_stats()` after; with `PROBE_TRACE=<batch>` the traced
batch's ops by kind and its heaviest ops.
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
from spotter_tpu.models import kimi_linear
from spotter_tpu.models.configs import JoyAIFlashDetConfig
from spotter_tpu.models.joyai_flash import JoyAIFlashDetector

dev = jax.devices()[0]
print("device", dev.platform, dev.device_kind, flush=True)
with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "configs", "joyai_flash_det_ep2.json")) as f:
    cfg = JoyAIFlashDetConfig.from_hf(json.load(f))
h, w = cfg.image_size
t, d = cfg.num_tokens, cfg.hidden_size
rng = np.random.default_rng(0)


def seeded(key, shapes, bias_std=0.02):
    """Matrices N(0, 1 / fan_in) in bfloat16, norms 1, the selection bias
    N(0, bias_std^2): the benchmark's scheme, drawn on the device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), sub in zip(leaves, keys):
        if leaf.ndim >= 2:
            fan_in = np.prod(leaf.shape[:-1]) / (leaf.shape[0] if leaf.ndim == 3 else 1)
            out.append((jax.random.normal(sub, leaf.shape) / np.sqrt(fan_in)).astype(jnp.bfloat16))
        elif "e_score_correction_bias" in jax.tree_util.keystr(path):
            out.append(bias_std * jax.random.normal(sub, leaf.shape))
        else:
            out.append(jnp.ones(leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---- the latent attention, kernel against eager float32, one image ----------
mixer = kimi_linear.LatentAttention(cfg, dtype=jnp.bfloat16, q_lora_rank=cfg.q_lora_rank,
                                    rope_theta=cfg.rope_theta)
x = jnp.asarray(rng.standard_normal((1, t, d)), jnp.bfloat16)
p = jax.jit(lambda k: seeded(k, jax.eval_shape(mixer.init, k, x)["params"]))(jax.random.PRNGKey(2))
got = jax.jit(lambda p, x: mixer.apply({"params": p}, x))(p, x)
kernel_on = kimi_linear.flash_attention_enabled
kimi_linear.flash_attention_enabled = lambda: False  # the module's eager form
eager = kimi_linear.LatentAttention(cfg, q_lora_rank=cfg.q_lora_rank, rope_theta=cfg.rope_theta)
with jax.default_matmul_precision("highest"):
    want = jax.jit(lambda p, x: eager.apply({"params": p}, x))(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p), x.astype(jnp.float32))
kimi_linear.flash_attention_enabled = kernel_on
got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
print(f"latent attention, bfloat16 kernel vs float32 eager: max gap {np.abs(got - want).max():.5f}, "
      f"mean gap {np.abs(got - want).mean():.6f}, scale {np.abs(want).mean():.4f}, "
      f"finite {bool(np.isfinite(got).all())}", flush=True)
del x, p, got, want

# ---- the whole forward pass per bucket --------------------------------------
module = JoyAIFlashDetector(cfg, dtype=jnp.bfloat16)
print("fresh", json.dumps(dev.memory_stats()), flush=True)
shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32))
params = jax.jit(lambda k: seeded(k, shapes["params"]))(jax.random.PRNGKey(1))
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
          "fullest/mean per layer", (tokens.max(-1) / tokens.mean(-1)).round(2).tolist(), flush=True)
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
