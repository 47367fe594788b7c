"""Chip probe (hand use): what `memory_stats()` counts, and YOLOS-base's device time a batch."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())
os.environ.setdefault("SPOTTER_TPU_DTYPE", "bfloat16")
import jax
import jax.numpy as jnp
import numpy as np

from spotter_tpu.models.configs import YolosConfig
from spotter_tpu.models.yolos import YolosDetector
from spotter_tpu.utils.precision import backbone_dtype

dev = jax.devices()[0]
print("device", dev.platform, dev.device_kind, flush=True)
print("fresh", json.dumps(dev.memory_stats()), flush=True)
cfg = YolosConfig()
module = YolosDetector(cfg, dtype=backbone_dtype())
h, w = cfg.image_size
init = jax.jit(lambda k: module.init(k, np.zeros((1, h, w, 3), np.float32))["params"])
params = init(jax.random.PRNGKey(1))
jax.block_until_ready(params)
print("after init", json.dumps(dev.memory_stats()), flush=True)
fwd = jax.jit(lambda p, x: module.apply({"params": p}, x))
for b in [int(a) for a in sys.argv[1].split(",")]:
    x = jax.device_put(np.random.default_rng(0).standard_normal((b, h, w, 3), np.float32))
    t0 = time.time()
    lo = fwd.lower(params, x).compile()
    m = lo.memory_analysis()
    print("batch", b, "compile_s", time.time() - t0, "analysis args", m.argument_size_in_bytes,
          "temp", m.temp_size_in_bytes, flush=True)
    jax.block_until_ready(fwd(params, x))
    t0 = time.time()
    for _ in range(5):
        out = fwd(params, x)
    jax.block_until_ready(out)
    dt = (time.time() - t0) / 5
    print("batch", b, "s/batch", dt, "ms/image", 1e3 * dt / b, flush=True)
    del x, out
    print("after batch", b, json.dumps(dev.memory_stats()), flush=True)
