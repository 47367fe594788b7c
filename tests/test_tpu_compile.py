"""The main path's kernels, compiled for a described v5e (no chip attached).

The TPU's compiler is installed wherever libtpu is, and it compiles for a chip
that is described and not attached. Interpret-mode tests cannot show what it
shows: kernels that had passed every one of them were refused here for a
primitive the Pallas TPU lowering lacks (`expm1`, ops/openvocab.py) and for
more scalar memory than a kernel may prefetch (Deformable-DETR's hit table,
below). A compile that passes is not a chip run: nothing executes, so these
say nothing about results or times.

Shapes are the published ones: the RT-DETRv2-R101 decoder's sampling at serving
batch 8, the ViT towers' token counts (YOLOS-base 4396, OWLv2-B/16 3601), the
OWL logit head at OWLv2-B/16 (3600 patches) and OWL-ViT-B/32 (576) against the
22-amenity vocabulary, LFM2-8B-A1B's expert and attention shapes and its six
served layers whole at the bucket of 8 (~55 s), Kimi Linear's KDA kernel,
latent attention, grouped products at hidden 2304 and its five served layers
whole at the bucket of 8, JoyAI-LLM-Flash's grouped products at I 768 and its
five served layers whole at the bucket of 32. The whole R101 engine program per bucket (~30 s each)
is under `-m slow`.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import spotter_tpu.models.rtdetr as rtdetr_mod
from spotter_tpu.models import layers
from spotter_tpu.ops import delta_rule, kda, moe, msda
from spotter_tpu.ops.openvocab import fused_class_logits


@pytest.fixture(scope="module")
def host_of_four():
    """The four described chips of a v5e 2x2 host."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu here, or another process holds it
        pytest.skip(f"cannot describe a v5e topology: {exc!r}")
    return topo.devices


@pytest.fixture(scope="module")
def chip(host_of_four):
    """One described v5e chip, as a sharding for abstract arguments."""
    return SingleDeviceSharding(host_of_four[0])


@pytest.fixture(scope="module", autouse=True)
def as_deployed():
    """Persistent cache off: a compile for a described chip is written to it
    but cannot be read back without one, so the next would warn and compile
    again. And matmul precision as a deployment has it: conftest pins
    "highest" for the torch-parity tests, under which a bf16 program is not
    what is served (and the splash kernel is refused: fp32 contraction
    precision on bf16 operands, "Bad lhs type")."""
    from jax.experimental.compilation_cache import compilation_cache

    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_default_matmul_precision", precision)
    compilation_cache.reset_cache()


def compile_for(chip, fn, *shapes_dtypes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile()


def sampling_args(
    batch, queries, spatial_shapes, dtype=jnp.bfloat16, heads=8, head_dim=32, points=4
):
    """(value, loc, attn) of one MSDA call."""
    s = sum(h * w for h, w in spatial_shapes)
    lp = len(spatial_shapes) * points
    return (
        ((batch, s, heads, head_dim), dtype),
        ((batch, queries, heads, lp, 2), dtype),
        ((batch, queries, heads, lp), dtype),
    )


# the two policies a replica serves under, with the MXU pass count
# ops/msda.py derives from each at import (utils/precision.py)
POLICIES = {
    "bfloat16": (jnp.bfloat16, jax.lax.Precision.DEFAULT),
    "float32": (jnp.float32, jax.lax.Precision.HIGHEST),
}


@pytest.mark.parametrize("policy", POLICIES)
def test_onehot_msda_kernel_at_r101_decoder_shapes(chip, policy, monkeypatch):
    # 640x640 -> strides 8/16/32; 300 queries, 8 heads of 32, 3 levels x 4 points
    dtype, mxu_precision = POLICIES[policy]
    monkeypatch.setattr(msda, "MSDA_MXU_PRECISION", mxu_precision)
    shapes = ((80, 80), (40, 40), (20, 20))
    fn = partial(
        msda.deformable_sampling, spatial_shapes=shapes, num_points=4,
        backend="pallas", presorted=True,
    )
    compiled = compile_for(chip, fn, *sampling_args(8, 300, shapes, dtype))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens", [4396, 3601], ids=["yolos-base", "owlv2-b16"])
def test_splash_attention_at_vit_token_counts(chip, tokens):
    qkv = ((8, tokens, 12, 64), jnp.bfloat16)
    compiled = compile_for(chip, layers._splash_self_attention, qkv, qkv, qkv)
    assert "tpu_custom_call" in compiled.as_text()


def test_delta_rule_kernel_at_qwen3_next_shapes(chip):
    """One Gated DeltaNet layer's call at the bucket of 8: 4300 tokens, 16 key
    and 32 value heads of 128, bfloat16 (a key head's two value heads side by
    side in the lanes: slices at lane 64 and a (128, 128) select a product)."""
    fn = partial(delta_rule.chunked_gated_delta_rule, impl="pallas")
    qk, scalars = ((8, 4300, 16, 128), jnp.bfloat16), ((8, 4300, 32), jnp.float32)
    compiled = compile_for(chip, fn, qk, qk, ((8, 4300, 32, 128), jnp.bfloat16), scalars, scalars)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_delta_rule_kernel" in text


@pytest.mark.parametrize("experts, k, n, form, result", [
    (32, 2048, 3584, {"swiglu": True, "out_dtype": jnp.bfloat16}, "bf16[8192,1792]"),
    (32, 1792, 2048, {}, "f32[8192,16,128]"),
    (64, 2048, 1024, {"swiglu": True, "out_dtype": jnp.bfloat16}, "bf16[8192,512]"),
], ids=["gate_up", "down", "gate_up_qwen3_next"])
def test_expert_matmul_kernel_at_lfm2_moe_shapes(chip, experts, k, n, form, result):
    """A window's two grouped products in the forms it makes them, at d 2048,
    I 1792 with all 32 experts held: 8192 rows in 64 tiles, each against its
    own expert's matrix. Gate | up in two blocks 256 wide with the SwiGLU on
    the way out, written in the served type; the down product with each row's
    weight, written in the sums' layout (blocks 1024 wide at K 1792: whether
    they fit VMEM, and whether Mosaic turns (8, 128) tiles of rows into a
    row's (8, 128) tile of column blocks, only this compile says). And the
    first call at Qwen3-Next's I 512 against 64 held experts."""
    def fn(x, w, tile_expert, tile_live, *row_weight):
        return moe.expert_matmul(x, w, tile_expert, tile_live, moe.ROW_TILE, impl="pallas",
                                 row_weight=row_weight[0] if row_weight else None, **form)

    tiles = ((moe.WINDOW_ROWS // moe.ROW_TILE,), jnp.int32)
    weights = [] if form else [((moe.WINDOW_ROWS,), jnp.float32)]
    text = compile_for(chip, fn, ((moe.WINDOW_ROWS, k), jnp.bfloat16), ((experts, k, n), jnp.bfloat16),
                       tiles, tiles, *weights).as_text()
    assert "tpu_custom_call" in text and "expert_matmul_kernel" in text and result in text


def test_causal_gqa_kernel_at_lfm2_moe_shapes(chip):
    """The attention layer's call at the bucket of 8: 4300 tokens, 32 query
    heads over 8 key-value heads, 64 wide: half a lane tile a head."""
    q, kv = ((8, 4300, 32, 64), jnp.bfloat16), ((8, 4300, 8, 64), jnp.bfloat16)
    text = compile_for(chip, layers.causal_gqa_attention, q, kv, kv).as_text()
    assert "tpu_custom_call" in text and "splash_mqa_fwd" in text
    # the shape benchmarks/kernels/causal_gqa_attention.py reads the bucket from
    assert "bf16[8,8,4,4608,64]" in text


def test_lfm2_moe_program_at_the_bucket_of_8(chip, monkeypatch):
    """The six served layers at the published widths, bfloat16 held, one
    bucket: every kernel inside one program the chip's compiler accepts, in
    the memory of one chip. `jax.default_backend()` is the CPU here, so the
    two call sites that ask it are steered onto the kernels the chip runs."""
    import json
    import pathlib

    from spotter_tpu.models import lfm2_moe
    from spotter_tpu.models.configs import Lfm2MoeDetConfig

    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = Lfm2MoeDetConfig.from_hf(json.loads(
        (root / "benchmarks" / "configs" / "lfm2_moe_det_pp4.json").read_text()))
    monkeypatch.setattr(lfm2_moe, "flash_attention_enabled", lambda: True)
    monkeypatch.setattr(moe, "routed_experts", partial(moe.routed_experts, impl="pallas"))
    module = lfm2_moe.Lfm2MoeDetector(cfg, dtype=jnp.bfloat16)
    h, w = cfg.image_size
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, h, w, 3), jnp.float32))["params"])
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 1_610_819_936
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim >= 2 else jnp.float32, sharding=chip), shapes)
    pixels = jax.ShapeDtypeStruct((8, h, w, 3), jnp.float32, sharding=chip)
    compiled = jax.jit(lambda p, x: module.apply({"params": p}, x)).lower(params, pixels).compile()
    text = compiled.as_text()
    assert "expert_matmul_kernel" in text and "splash_mqa_fwd" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16 * 2**30


def test_kda_kernel_at_kimi_linear_shapes(chip):
    """One KDA layer's call at the bucket of 8: 4300 tokens, 32 heads of 128,
    bfloat16, the float32 gate a value a key channel (reference rows taken out
    of tiles of 8 sublanes, six masked products a head and chunk)."""
    fn = partial(kda.chunked_kda, impl="pallas")
    x, gate = ((8, 4300, 32, 128), jnp.bfloat16), ((8, 4300, 32, 128), jnp.float32)
    text = compile_for(chip, fn, x, x, x, gate, ((8, 4300, 32), jnp.float32)).as_text()
    assert "tpu_custom_call" in text and "kda_kernel" in text
    assert "gated_delta_rule_kernel" not in text  # no reader of that kernel's events counts this one
    # the shape benchmarks/kernels/kda.py reads the bucket from
    assert "bf16[8,4352,4096]" in text


def test_latent_attention_kernel_at_kimi_linear_shapes(chip):
    """The latent-attention layer's call at the bucket of 8: 32 heads, keys of
    192 (one and a half lane tiles), values of 128, head-major and 4300 tokens
    already padded to 4608."""
    qk, v = ((8, 32, 4608, 192), jnp.bfloat16), ((8, 32, 4608, 128), jnp.bfloat16)
    text = compile_for(chip, layers.causal_latent_attention, qk, qk, v).as_text()
    assert "tpu_custom_call" in text and "splash_mha_fwd_no_residuals" in text
    # the shape benchmarks/kernels/mla_causal_attention.py reads the bucket from
    assert "bf16[8,32,4608,128]" in text


RELAYOUT_OPS = ("copy", "pad", "slice", "broadcast", "convert")
_BYTES = {"bf16": 2, "f16": 2, "s16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1}


def relayout_bytes(text, floor=50e6):
    """Output bytes of the entry computation's copies, pads, slices,
    broadcasts, converts and loop fusions, each over `floor`: what the
    program writes to move or reshape data rather than compute it."""
    import re

    total = 0
    for line in text[text.index("\nENTRY"):].splitlines():
        found = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([\w-]+)\(", line)
        if not found:
            continue
        shapes, op = found.groups()
        if op not in RELAYOUT_OPS and not (op == "fusion" and "kind=kLoop" in line):
            continue
        size = sum(_BYTES[dt] * int(np.prod([int(d) for d in dims.split(",") if d]))
                   for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", shapes))
        total += size if size > floor else 0
    return total


@pytest.mark.parametrize("family", ["joyai_flash_det_ep2", "kimi_linear_det_ep4"])
def test_latent_attention_layer_writes_its_operands_once(chip, monkeypatch, family):
    """One latent-attention layer at the published widths, one part of 16
    images, bfloat16, the flash path on: between the products and the kernel,
    and between the kernel and `o_proj`, the program moves at most 5 GB
    (2.68 GB on JoyAI's widths and 1.54 on Kimi's when this was written;
    11.22 and 9.07, in 21 and 15 such ops, when q, k and v were made
    token-major and the kernel's wrapper turned and padded each). The
    kernel is there, and its result holds the member the benchmark counts
    images from, (images, heads, tokens padded, values)."""
    import json
    import pathlib

    from spotter_tpu.models import kimi_linear
    from spotter_tpu.models.configs import JoyAIFlashDetConfig, KimiLinearDetConfig

    root = pathlib.Path(__file__).resolve().parents[1]
    raw = json.loads((root / "benchmarks" / "configs" / f"{family}.json").read_text())
    monkeypatch.setattr(kimi_linear, "flash_attention_enabled", lambda: True)
    if family.startswith("joyai"):
        cfg = JoyAIFlashDetConfig.from_hf(raw)
        module = kimi_linear.LatentAttention(cfg, dtype=jnp.bfloat16, q_lora_rank=cfg.q_lora_rank,
                                             rope_theta=cfg.rope_theta)
    else:
        cfg = KimiLinearDetConfig.from_hf(raw)
        module = kimi_linear.LatentAttention(cfg, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((16, cfg.num_tokens, cfg.hidden_size), jnp.bfloat16, sharding=chip)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16 if a.ndim >= 2 else jnp.float32,
                                       sharding=chip), shapes)
    text = jax.jit(module.apply).lower(params, x).compile().as_text()
    assert "splash_mha_fwd_no_residuals" in text
    assert f"bf16[16,{cfg.num_attention_heads},4608,{cfg.v_head_dim}]" in text
    assert relayout_bytes(text) <= 5e9


@pytest.mark.parametrize("k, n, form, result", [
    (2304, 2048, {"swiglu": True, "out_dtype": jnp.bfloat16}, "bf16[8192,1024]"),
    (1024, 2304, {}, "f32[8192,18,128]"),
], ids=["gate_up", "down"])
def test_expert_matmul_kernel_at_kimi_linear_shapes(chip, k, n, form, result):
    """A window's two grouped products at d 2304 = 18 x 128, I 1024, 64 experts
    held: the first call's row block 2304 wide; the weighted call writing a
    token's eighteen lane tiles at once against the expert's whole matrix (a
    block of 1024 x 2304 bfloat16, 4.7 MB, twice for the pipeline: whether that
    fits VMEM only this compile says)."""

    def fn(x, w, tile_expert, tile_live, *row_weight):
        return moe.expert_matmul(x, w, tile_expert, tile_live, moe.ROW_TILE, impl="pallas",
                                 row_weight=row_weight[0] if row_weight else None, **form)

    tiles = ((moe.WINDOW_ROWS // moe.ROW_TILE,), jnp.int32)
    weights = [] if form else [((moe.WINDOW_ROWS,), jnp.float32)]
    text = compile_for(chip, fn, ((moe.WINDOW_ROWS, k), jnp.bfloat16), ((64, k, n), jnp.bfloat16),
                       tiles, tiles, *weights).as_text()
    assert "tpu_custom_call" in text and "expert_matmul_kernel" in text and result in text


def test_kimi_linear_program_at_the_bucket_of_8(chip, monkeypatch):
    """The five served layers at the published widths, bfloat16 held, one
    bucket: every kernel inside one program the chip's compiler accepts, in
    the memory of one chip; the held parameters counted."""
    import json
    import pathlib

    from spotter_tpu.models import kimi_linear
    from spotter_tpu.models.configs import KimiLinearDetConfig

    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = KimiLinearDetConfig.from_hf(json.loads(
        (root / "benchmarks" / "configs" / "kimi_linear_det_ep4.json").read_text()))
    monkeypatch.setattr(kimi_linear, "flash_attention_enabled", lambda: True)
    monkeypatch.setattr(kimi_linear, "chunked_kda", partial(kda.chunked_kda, impl="pallas"))
    monkeypatch.setattr(moe, "routed_experts", partial(moe.routed_experts, impl="pallas"))
    module = kimi_linear.KimiLinearDetector(cfg, dtype=jnp.bfloat16)
    h, w = cfg.image_size
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, h, w, 3), jnp.float32))["params"])
    held = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    # ISSUE 35: 2.117 B to within the seams: four KDA mixers of 39,514,272, the latent
    # attention's 29,114,880, the dense SwiGLU's 63,700,992, four routed layers of 460,652,800
    assert abs(held - 2.117e9) < 5e6, held
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim >= 2 else jnp.float32, sharding=chip), shapes)
    pixels = jax.ShapeDtypeStruct((8, h, w, 3), jnp.float32, sharding=chip)
    compiled = jax.jit(lambda p, x: module.apply({"params": p}, x)).lower(params, pixels).compile()
    text = compiled.as_text()
    assert "kda_kernel" in text and "expert_matmul_kernel" in text and "splash_mha_fwd" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("k, n, form, result", [
    (2048, 1536, {"swiglu": True, "out_dtype": jnp.bfloat16}, "bf16[8192,768]"),
    (768, 2048, {}, "f32[8192,16,128]"),
], ids=["gate_up", "down"])
def test_expert_matmul_kernel_at_joyai_flash_shapes(chip, k, n, form, result):
    """A window's two grouped products at d 2048, I 768 = 6 x 128, 128 of
    256 experts held: the first call's gate | up 1536 wide with the SwiGLU on
    the way out; the weighted call against a matrix only 768 deep."""

    def fn(x, w, tile_expert, tile_live, *row_weight):
        return moe.expert_matmul(x, w, tile_expert, tile_live, moe.ROW_TILE, impl="pallas",
                                 row_weight=row_weight[0] if row_weight else None, **form)

    tiles = ((moe.WINDOW_ROWS // moe.ROW_TILE,), jnp.int32)
    weights = [] if form else [((moe.WINDOW_ROWS,), jnp.float32)]
    text = compile_for(chip, fn, ((moe.WINDOW_ROWS, k), jnp.bfloat16), ((128, k, n), jnp.bfloat16),
                       tiles, tiles, *weights).as_text()
    assert "tpu_custom_call" in text and "expert_matmul_kernel" in text and result in text


def test_joyai_flash_program_at_the_bucket_of_32(chip, monkeypatch):
    """The five served layers at the published widths, bfloat16 held, the
    largest bucket: every kernel inside one program the chip's compiler
    accepts, in the memory of one chip (5.70 GB of arguments and 7.85 GB of
    temporaries when this was written); the held parameters counted."""
    import json
    import pathlib

    from spotter_tpu.models import joyai_flash, kimi_linear
    from spotter_tpu.models.configs import JoyAIFlashDetConfig

    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = JoyAIFlashDetConfig.from_hf(json.loads(
        (root / "benchmarks" / "configs" / "joyai_flash_det_ep2.json").read_text()))
    monkeypatch.setattr(kimi_linear, "flash_attention_enabled", lambda: True)
    monkeypatch.setattr(moe, "routed_experts", partial(moe.routed_experts, impl="pallas"))
    module = joyai_flash.JoyAIFlashDetector(cfg, dtype=jnp.bfloat16)
    h, w = cfg.image_size
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, h, w, 3), jnp.float32))["params"])
    # 2.63 B: the dense layer's 70.39 M, four routed layers of 635.58 M, the seams
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 2_631_453_792
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim >= 2 else jnp.float32, sharding=chip), shapes)
    pixels = jax.ShapeDtypeStruct((32, h, w, 3), jnp.float32, sharding=chip)
    compiled = jax.jit(lambda p, x: module.apply({"params": p}, x)).lower(params, pixels).compile()
    text = compiled.as_text()
    assert "expert_matmul_kernel" in text and "splash_mha_fwd" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("patches", [3600, 576], ids=["owlv2-b16", "owlvit-b32"])
def test_owl_logit_kernel_at_published_sizes(chip, patches):
    """The repaired head (exp in place of expm1) against the 22 amenities."""
    fn = partial(fused_class_logits, query_mask=None)
    compiled = compile_for(
        chip, fn,
        ((8, patches, 512), jnp.float32), ((22, 512), jnp.float32),
        ((8, patches), jnp.float32), ((8, patches), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(
    strict=True,
    reason="ROADMAP R1: at Deformable-DETR's published 800x1333 the one-hot "
    "kernel's scalar-prefetched block-sparse hit table (ops/msda.py, "
    "num_scalar_prefetch=1) outgrows SMEM at ~22k encoder queries: "
    "RESOURCE_EXHAUSTED ... space=smem ... 'prefetched SMEM operand 0'. "
    "Recorded, not fixed; it fails loudly at warm-up. When R1 lands and this "
    "compiles, strict xfail turns the pass into a failure: drop the marker.",
)
def test_onehot_msda_kernel_at_deformable_detr_encoder_shapes(chip):
    # 800x1333 -> four levels, every encoder token is a query (22,223 of them)
    shapes = ((100, 167), (50, 84), (25, 42), (13, 21))
    queries = sum(h * w for h, w in shapes)
    fn = partial(
        msda.deformable_sampling, spatial_shapes=shapes, num_points=4,
        backend="pallas", presorted=True,
    )
    compile_for(chip, fn, *sampling_args(2, queries, shapes))


def lower_engine_program(monkeypatch, bucket, batch_sharding, param_shardings, mesh=None):
    """Lower the engine's own program (host-float ingest + forward +
    postprocess) at one bucket for described devices. `auto` asks
    `jax.default_backend()`, which is the CPU here, so the one sampling call
    site is steered onto the kernel the chip would pick — after the build,
    whose init RUNS on the CPU. The engine cannot be PLACED on devices that
    are not attached: it is built on the CPU and, for a mesh, handed it."""
    from spotter_tpu.engine.engine import InferenceEngine
    from spotter_tpu.models import build_detector

    built = build_detector("rtdetr_v2_r101vd")  # the tiny toy under TINY_ENV
    monkeypatch.setattr(
        rtdetr_mod, "deformable_sampling",
        partial(msda.deformable_sampling, backend="pallas"),
    )
    engine = InferenceEngine(built, batch_buckets=(bucket,))
    if mesh is not None:
        engine.mesh = mesh
        engine._jit_programs()
    h, w = built.preprocess_spec.input_hw
    params = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        built.params, param_shardings(built.params),
    )
    return engine._forward.lower(
        params,
        jax.ShapeDtypeStruct((bucket, h, w, 3), jnp.float32, sharding=batch_sharding),
        jax.ShapeDtypeStruct((bucket, h, w), jnp.float32, sharding=batch_sharding),
        jax.ShapeDtypeStruct((bucket, 2), jnp.float32, sharding=batch_sharding),
    )


def lower_tiny_engine_on(devices, dp, tp, monkeypatch):
    """The tiny RT-DETR's bucket-4 program over a described dp x tp mesh."""
    from spotter_tpu.models import zoo
    from spotter_tpu.parallel.sharding import (
        RTDETR_TP_RULES, data_sharding, param_shardings,
    )

    monkeypatch.setenv(zoo.TINY_ENV, "1")
    mesh = Mesh(np.asarray(devices).reshape(dp, tp), ("dp", "tp"))
    rules = RTDETR_TP_RULES if tp > 1 else ()
    return lower_engine_program(
        monkeypatch, 4, data_sharding(mesh),
        lambda params: param_shardings(params, mesh, rules), mesh=mesh,
    )


def test_kernel_program_lowers_data_parallel_over_four_chips(host_of_four, monkeypatch):
    """dp=4: the engine runs the one-chip program under shard_map, which is
    the form a kernel-bearing program lowers in."""
    lowered = lower_tiny_engine_on(host_of_four, dp=4, tp=1, monkeypatch=monkeypatch)
    assert "tpu_custom_call" in lowered.as_text()


@pytest.mark.xfail(
    strict=True,
    raises=NotImplementedError,
    reason="ROADMAP D8: with tp > 1 the SPMD partitioner places the "
    "collectives, and it refuses a program that holds a Pallas kernel: "
    "'Mosaic kernels cannot be automatically partitioned. Please wrap the "
    "call in a shard_map.' So --serve-tp cannot serve a kernel-bearing family "
    "on a TPU (it fails loudly at warm-up); only kernels-off "
    "(SPOTTER_TPU_MSDA=xla) tensor-parallel serving lowers today.",
)
def test_kernel_program_lowers_tensor_parallel(host_of_four, monkeypatch):
    lower_tiny_engine_on(host_of_four, dp=2, tp=2, monkeypatch=monkeypatch)


@pytest.mark.slow
@pytest.mark.parametrize("bucket", [1, 2, 4, 8])
def test_r101_engine_program_per_bucket(chip, bucket, monkeypatch):
    """The seeded R101's engine program at every bucket of the default ladder."""
    from spotter_tpu.models import zoo

    monkeypatch.delenv(zoo.TINY_ENV, raising=False)  # earlier files may leave it set
    lowered = lower_engine_program(
        monkeypatch, bucket, chip,
        lambda params: jax.tree_util.tree_map(lambda _: chip, params),
    )
    assert "tpu_custom_call" in lowered.compile().as_text()
