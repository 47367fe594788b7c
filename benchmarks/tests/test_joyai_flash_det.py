"""The files that bring `joyai_flash_det` to the benchmark: the forward file's
count against hand-worked shapes and the published widths, the latent
attention count over every layer, the two new readers over a made-up trace
(and `None` on a program without the kernel), the shared readers through this
configuration's forward file, the manifest and the configuration's file, the
family's seeding and its save / load round trip, its attention (sdpa) against
eager, the family against the plain `jax.numpy` reference and the program at
the tiny rehearsal size, and `--rehearse` end to end."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import run as bench
import weights
from conftest import BENCH, ROOT

PEAKS = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
NEW_READERS = ["latent_attention_roofline.bulk", "latent_attention_share.bulk"]


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


SMALL = {"image_size": [32, 48], "patch_size": 16, "num_detection_tokens": 4, "num_channels": 3,
         "hidden_size": 8, "intermediate_size": 6, "moe_intermediate_size": 2, "num_hidden_layers": 5,
         "first_k_dense_replace": 1, "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 5,
         "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4, "num_experts": 2,
         "num_routed_experts": 8, "num_experts_per_tok": 4, "n_shared_experts": 1, "num_labels": 3,
         "serve": {"dtype_policy": "bfloat16", "batch_buckets": [2, 4]}}


def test_forward_count_against_hand_worked_shapes():
    forward = bench.load_reader("kernels", "joyai_flash_det_forward")
    # 6 patches + 4 tokens = 10; five latent-attention layers; one dense, four routed
    assert forward.tokens(SMALL) == (10, 6) and forward.routed_layers(SMALL) == 4
    assert forward.uniform_assignments(SMALL) == 10 * 4 * (2 / 8) * 4
    parts = forward.flops_by_part(SMALL)
    assert parts["patch_projection"] == 2 * 6 * (16 * 16 * 3) * 8
    # q_a 8 -> 3, q_b 3 -> 2 x 6, kv_a 8 -> 5 + 2, kv_b 5 -> 2 x 8, out 2 x 4 -> 8; five layers
    assert parts["latent_attention_projections"] == 5 * (2 * 10 * 8 * 3 + 2 * 10 * 3 * 12 + 2 * 10 * 8 * 7
                                                         + 2 * 10 * 5 * 16 + 2 * 10 * 8 * 8)
    assert parts["causal_attention"] == 5 * 2 * (6 + 4) * 2 * 55  # 55 (query, key) pairs, 2 heads
    assert parts["dense_mlps"] == 3 * 2 * 10 * 8 * 6
    assert parts["routers"] == 4 * 2 * 10 * 8 * 8
    assert parts["shared_experts"] == 4 * 3 * 2 * 10 * 8 * 2
    assert parts["routed_experts"] == 40 * 3 * 2 * 8 * 2
    assert parts["heads"] == 2 * 4 * (2 * 64 + 8 * 4) + 2 * 4 * (2 * 64 + 8 * 4)
    assert forward.flops_per_image(SMALL) == sum(parts.values())
    assert forward.flops_per_image(SMALL, assignments=0) == sum(parts.values()) - parts["routed_experts"]


def test_forward_count_published_widths():
    """The published widths' arithmetic: 3.30 TFLOP an image at a uniform router's four
    held choices a token: the dense layer 0.795, each routed layer 0.623
    (projections 0.227, causal core 0.189, held experts 0.162, shared expert
    0.041, router 0.005); latent attention's projections and core 2.08 of it
    (63 %), the core alone 0.95 (29 %), the routed experts 0.65 (20 %)."""
    forward = bench.load_reader("kernels", "joyai_flash_det_forward")
    c = cfg("joyai_flash_det_ep2")
    parts = forward.flops_by_part(c)
    total = forward.flops_per_image(c)
    assert total == pytest.approx(3.30e12, rel=5e-3)
    assert forward.uniform_assignments(c) == 4 * 4300 * 4
    assert parts["latent_attention_projections"] / 5 == pytest.approx(0.227e12, rel=5e-3)
    assert parts["causal_attention"] / 5 == pytest.approx(0.189e12, rel=5e-3)
    assert parts["dense_mlps"] + (parts["latent_attention_projections"] + parts["causal_attention"]) / 5 \
        == pytest.approx(0.795e12, rel=5e-3)
    assert parts["routed_experts"] / 4 == pytest.approx(0.162e12, rel=5e-3)
    assert parts["shared_experts"] / 4 == pytest.approx(0.041e12, rel=2e-2)
    assert parts["routers"] / 4 == pytest.approx(0.0045e12, rel=2e-2)
    latent = parts["latent_attention_projections"] + parts["causal_attention"]
    assert latent == pytest.approx(2.08e12, rel=5e-3) and 0.62 < latent / total < 0.64
    assert 0.28 < parts["causal_attention"] / total < 0.29
    assert 0.19 < parts["routed_experts"] / total < 0.20


def test_the_latent_attention_count_is_mla_causal_attentions_over_every_layer():
    c = cfg("joyai_flash_det_ep2")
    attention = bench.load_reader("kernels", "latent_attention")
    mla = bench.load_reader("kernels", "mla_causal_attention")
    kimi = cfg("kimi_linear_det_ep4")  # the same heads, keys and values, one such layer
    assert attention.layers(c) == 5 and mla.layers(kimi) == 1
    assert attention.operations_per_image(c) == 5 * mla.operations_per_image(kimi)
    assert attention.bytes_per_image(c) == 5 * mla.bytes_per_image(kimi)
    assert attention.operations_per_image(c) == 5 * 2 * (192 + 128) * 32 * (4300 * 4301 // 2)
    assert attention.least_seconds(c, PEAKS) == pytest.approx(attention.operations_per_image(c) / 197e12)
    experts = bench.load_reader("kernels", "expert_matmul")
    assert experts.operations(c, 68800) == 68800 * 3 * 2 * 2048 * 768


SPLASH = ("%splash_mha_fwd_no_residuals.{n} = (f32[{b},512,128]{{2,1,0}}, f32[{b},512,128]{{2,1,0}}, "
          "f32[{b},512,128]{{2,1,0}}, bf16[{b},32,4608,128]{{3,2,1,0:T(8,128)(2,1)}}) custom-call(%a, %b)")
EXPERT = "%expert_matmul_kernel.{n} = f32[8192,{w}]{{1,0:T(8,128)}} custom-call(%a, %b, %c, %d)"


def made_up_trace():
    """Two program runs: a bucket of 32 whole (five layers, each in two parts
    of 16 images), a bucket of 8 that the capture cut after two layers."""
    big = [SPLASH.format(n=n, b=16) for n in range(10)]
    small = [SPLASH.format(n=20 + n, b=8) for n in range(5)]
    ops = {**{op: 0.02 for op in big}, **{op: 0.01 for op in small[:2]},
           EXPERT.format(n=3, w=768): 0.06, EXPERT.format(n=4, w=2048): 0.04,
           "%fusion.9 = bf16[137600,2048]{1,0} fusion(%a)": 0.3}
    calls = {**{op: 1 for op in big}, **{op: 1 for op in small[:2]},
             EXPERT.format(n=3, w=768): 40, EXPERT.format(n=4, w=2048): 40}
    return {
        "devices": 1, "window_s": 2.0, "busy_s": 1.1, "op_seconds": ops, "op_calls": calls,
        "programs": {"jit_big": {"runs": 1, "seconds": 0.8}, "jit_small": {"runs": 1, "seconds": 0.2},
                     "jit_other": {"runs": 3, "seconds": 0.5}},
        "program_ops": {"jit_big": {*big, EXPERT.format(n=3, w=768)}, "jit_small": set(small),
                        "jit_other": {"%fusion.1 = f32[8]{0} fusion(%a)"}},
        "program_runs": [{"name": "jit_big", "start_s": 0.1, "end_s": 0.9},
                         {"name": "jit_small", "start_s": 1.8, "end_s": 2.0}],
    }


def test_slots_from_the_latent_attention_kernels_events():
    forward = bench.load_reader("kernels", "joyai_flash_det_forward")
    c = cfg("joyai_flash_det_ep2")
    slots, seconds = forward.slots_in_trace(c, made_up_trace())
    assert slots == (10 * 16 + 2 * 8) / 5 and seconds == pytest.approx(1.0)
    assert forward.slots_finished(c, made_up_trace()) == 32  # the cut run counts nothing


def reader_ctx(trace, before, after, name="joyai_flash_det_ep2"):
    return {"config": cfg(name), "trace": trace, "peaks": PEAKS, "metrics_before": before,
            "metrics_after": after, "kernel": lambda name: bench.load_reader("kernels", name)}


def test_readers_over_a_made_up_trace_and_counters():
    before = {"images_total": 10, "moe_assignments_total": 1376000, "moe_assignments_local_total": 688000,
              "moe_bias_moved_total": 100000, "moe_expert_tokens_max_total": 100,
              "moe_expert_tokens_mean_total": 80.0}
    after = {"images_total": 110, "moe_assignments_total": 1376000 + 100 * 137600,
             "moe_assignments_local_total": 688000 + 100 * 68800,
             "moe_bias_moved_total": 100000 + 25 * 137600, "moe_expert_tokens_max_total": 100 + 5400,
             "moe_expert_tokens_mean_total": 80.0 + 3600.0}
    ctx = reader_ctx(made_up_trace(), before, after)
    c = ctx["config"]
    read = lambda name: bench.load_reader("metrics", name).read(ctx)  # noqa: E731
    attention = bench.load_reader("kernels", "latent_attention")
    experts = bench.load_reader("kernels", "expert_matmul")
    forward = bench.load_reader("kernels", "joyai_flash_det_forward")
    slots = 35.2  # (160 + 16) / 5; the kernel's 0.22 s of the forward programs' 1.0 s
    assert read("latent_attention_roofline.bulk") == pytest.approx(
        100 * attention.least_seconds(c, PEAKS) * slots / 0.22)
    assert read("latent_attention_share.bulk") == pytest.approx(22.0)
    assert read("expert_kernel_share.bulk") == pytest.approx(10.0)
    assert read("routing_bias_moved.bulk") == pytest.approx(25.0)
    assert read("expert_matmul_roofline.bulk") == pytest.approx(
        100 * experts.least_seconds(c, PEAKS, 68800.0) * slots / 0.1)
    assert read("expert_imbalance.bulk") == pytest.approx(1.5)
    assert read("step_mfu.bulk") == pytest.approx(100 * forward.flops_per_image(c) * slots / (1.0 * 197e12))


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_them(name):
    """A program with no latent-attention kernel (another family, or none at
    all): the reader returns None and does not raise."""
    delta = "%gated_delta_rule_kernel.3 = bf16[16,4352,4096]{2,1,0:T(8,128)(2,1)} custom-call(%copy.1)"
    trace = {"devices": 1, "window_s": 2.0, "busy_s": 1.0,
             "op_seconds": {"%fusion.1 = f32[8]{0} fusion(%a)": 0.5, delta: 0.1}, "op_calls": {delta: 3},
             "programs": {"jit_f": {"runs": 1, "seconds": 0.8}}, "program_ops": {"jit_f": {delta}},
             "program_runs": []}
    ctx = reader_ctx(trace, {"images_total": 1}, {"images_total": 9})
    assert bench.load_reader("metrics", name).read(ctx) is None
    ctx["trace"] = None
    assert bench.load_reader("metrics", name).read(ctx) is None


def test_manifest_lists_the_cell_where_its_readers_read():
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in manifest["workloads"] if w["name"] == "joyai_flash_det_bulk")
    assert cell["config"] == "joyai_flash_det_ep2" and cell["traffic"] == "bulk_closed" and cell["chips"] == 1
    listed = {m["name"] for m in bench.metrics_of(manifest, cell, "per_layer")}
    assert {"step_mfu.bulk", "expert_matmul_roofline.bulk", "expert_imbalance.bulk", "expert_kernel_share.bulk",
            "routing_bias_moved.bulk", "device_idle.bulk", "slot_fill.bulk", "batch_fill.bulk",
            "idle_unseen.bulk", "warmup_s.bulk", *NEW_READERS} <= listed
    assert not {"attention_roofline.bulk", "delta_rule_roofline.bulk", "causal_attention_roofline.bulk",
                "mla_attention_roofline.bulk", "kda_roofline.bulk", "kda_kernel_share.bulk",
                "kda_gate_spread.bulk"} & listed
    assert {m["name"] for m in bench.metrics_of(manifest, cell, "end_to_end")} == {"images_per_s", "setup_s"}
    # what the benchmark had stands first and as it was; this cell follows it (later ones may follow it)
    assert [w["name"] for w in manifest["workloads"]][:5] == [
        "yolos_base_bulk", "qwen3_next_det_bulk", "lfm2_moe_det_bulk", "kimi_linear_det_bulk",
        "joyai_flash_det_bulk"]
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert names[at:at + 2] == NEW_READERS and names[at - 1] == "warmup_s.bulk"
    for name in NEW_READERS:
        metric = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert metric["workloads"] == ["joyai_flash_det_bulk"] and metric["moves"] == "images_per_s"


def test_the_configuration_holds_the_catalog_numbers():
    """Every width as published; `reduced` is the depth, the experts held and
    the vocabulary, each with its published value; the share is two chips'."""
    c = cfg("joyai_flash_det_ep2")
    published = c["published"]
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"] == list(published)
    assert published == {"num_hidden_layers": 40, "n_routed_experts": 256, "vocab_size": 129280}
    widths = {"hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 768, "q_lora_rank": 1536,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
              "qk_head_dim": 192, "head_dim": 64, "num_attention_heads": 32, "num_experts_per_tok": 8,
              "rope_theta": 32000000, "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6}
    assert {k: c[k] for k in widths} == widths
    assert c["n_routed_experts"] == c["num_experts"] == 128
    assert c["num_experts"] * c["expert_parallel_chips"] == c["num_routed_experts"] == 256
    assert c["num_hidden_layers"] == 5 and c["first_k_dense_replace"] == 1


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    return weights.ensure_checkpoint(cfg("joyai_flash_det_tiny_rehearsal"),
                                     str(tmp_path_factory.mktemp("ckpt")))


def test_family_seeding_and_round_trip(tiny_checkpoint):
    """Seeded twice, the tensors are the same; what is saved is what is loaded
    (bfloat16-rounded); the special tensors follow their rules; the names are
    transformers' DeepSeek-V3 names."""
    import torch

    c = cfg("joyai_flash_det_tiny_rehearsal")
    assert os.path.basename(tiny_checkpoint).startswith("joyai_flash_det_tiny_rehearsal-")
    assert sorted(os.listdir(tiny_checkpoint)) == ["DONE", "config.json", "model.safetensors"]
    fam = weights.family("joyai_flash_det")
    built = weights.build_model(c)
    loaded = fam.load_model(tiny_checkpoint)
    state, again = built.state_dict(), loaded.state_dict()
    assert set(state) == set(again)
    for name, tensor in state.items():
        assert torch.equal(tensor.to(torch.bfloat16).float(), again[name]), name
    for norm in ("layers.0.input_layernorm", "layers.2.self_attn.q_a_layernorm", "layers.2.self_attn.kv_a_layernorm"):
        assert torch.all(state[f"{norm}.weight"] == 1)
    bias = state["layers.3.mlp.gate.e_score_correction_bias"]
    assert bias.shape == (8,) and 0 < float(bias.abs().max()) < 4 * c["weights"]["expert_bias_std"]
    assert state["layers.3.mlp.gate.weight"].shape == (8, c["hidden_size"])  # the router over all 8
    assert state["layers.1.self_attn.q_b_proj.weight"].shape == (4 * 24, 48)
    assert "layers.0.mlp.gate.weight" not in state  # the first layer is dense
    assert state["layers.0.mlp.gate_proj.weight"].shape == (c["intermediate_size"], c["hidden_size"])
    experts = {n.split(".")[4] for n in state if ".mlp.experts." in n}
    assert experts == {str(e) for e in range(c["n_routed_experts"])}  # the share held, of 8 routed
    with open(os.path.join(tiny_checkpoint, "config.json")) as f:
        hf = json.load(f)
    assert hf["n_routed_experts"] == 4 and hf["num_routed_experts"] == 8 and "serve" not in hf


def test_family_sdpa_reads_as_eager(tiny_checkpoint):
    import torch

    fam = weights.family("joyai_flash_det")
    model = fam.load_model(tiny_checkpoint).eval()
    with open(os.path.join(tiny_checkpoint, "config.json")) as f:
        hf = json.load(f)
    eager = fam.new_model({**hf, "_reference_attention": "eager"}).eval()
    eager.load_state_dict(model.state_dict())
    x = torch.from_numpy(np.random.default_rng(0).standard_normal((2, 3, 64, 96)).astype(np.float32))
    with torch.no_grad():
        a, b = model(pixel_values=x), eager(pixel_values=x)
    assert float((a.logits - b.logits).abs().max()) < 1e-4
    assert float((a.pred_boxes - b.pred_boxes).abs().max()) < 1e-5
    gap, kept = fam.threshold_logits(a.logits[0].numpy())
    assert gap.shape == (20, 91) and all(gap[q, c] > 0 for q, c in kept)


def test_the_torch_family_against_the_plain_reference_and_the_program(tiny_checkpoint, tmp_path, monkeypatch):
    """Three implementations read one rehearsal checkpoint in float32: the
    benchmark's module of transformers' DeepSeek-V3 classes, the plain `jax.numpy`
    reference (the checkpoint's interleaved channels turned pair by pair) and
    the served Flax module (the converter's half-split channels). They agree
    to rounding: logits (the class head's gain is 12) to 3e-4, boxes to 1e-5;
    the router's bias moves choices in the program as in the reference."""
    import jax
    import torch

    from spotter_tpu.convert import joyai_flash_rules, loader
    from spotter_tpu.models.joyai_flash import JoyAIFlashDetector
    from spotter_tpu.testing import joyai_flash_reference as ref

    monkeypatch.setenv("SPOTTER_TPU_CACHE", str(tmp_path / "cache"))
    model = weights.family("joyai_flash_det").load_model(tiny_checkpoint).eval()
    config, params = loader.load_joyai_flash_det(tiny_checkpoint)
    params = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), params)
    # the reference reads the checkpoint's channel order: the converter's permutation undone
    query, latent = joyai_flash_rules.rotary_columns(config)
    interleaved = {k: {**v, "self_attn": {
        **v["self_attn"],
        "q_b_proj": {"kernel": v["self_attn"]["q_b_proj"]["kernel"][:, np.argsort(query)]},
        "kv_a_proj_with_mqa": {"kernel": v["self_attn"]["kv_a_proj_with_mqa"]["kernel"][:, np.argsort(latent)]},
    }} if k.startswith("layer") else v for k, v in params.items()}
    x = np.random.default_rng(0).standard_normal((3, 64, 96, 3)).astype(np.float32)
    with torch.no_grad():
        want = model(pixel_values=torch.from_numpy(x).permute(0, 3, 1, 2))
    with jax.default_matmul_precision("highest"):
        got = JoyAIFlashDetector(config).apply({"params": params}, x)
        plain = [ref.detector(interleaved, x[i], config) for i in range(3)]
    for i in range(3):
        np.testing.assert_allclose(plain[i]["logits"], want.logits[i].numpy(), atol=3e-4)
        np.testing.assert_allclose(plain[i]["pred_boxes"], want.pred_boxes[i].numpy(), atol=1e-5)
    np.testing.assert_allclose(got["logits"], want.logits.numpy(), atol=3e-4)
    np.testing.assert_allclose(got["pred_boxes"], want.pred_boxes.numpy(), atol=1e-5)
    assert 0 < int(np.asarray(got["moe_bias_moved"]).sum()) < int(np.asarray(got["moe_assignments"]).sum())


def test_a_checkout_without_the_family_is_refused_before_seeding(tmp_path):
    """The commit before the one that brought this family, run on its cell:
    the family file says so at once, before any weight is drawn."""
    import importlib.util
    import shutil

    (tmp_path / "spotter_tpu" / "models").mkdir(parents=True)
    (tmp_path / "benchmarks" / "families").mkdir(parents=True)
    path = tmp_path / "benchmarks" / "families" / "joyai_flash_det.py"
    shutil.copy(os.path.join(BENCH, "families", "joyai_flash_det.py"), path)
    spec = importlib.util.spec_from_file_location("parent_family", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    with pytest.raises(RuntimeError, match="no joyai_flash_det family"):
        module.new_model(weights.hf_config_dict(cfg("joyai_flash_det_tiny_rehearsal")))


@pytest.mark.slow
def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "joyai_flash_det_bulk",
         "--seed", "2147483997", "--seconds", "6", "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert {"expert_imbalance.bulk", "routing_bias_moved.bulk", "slot_fill.bulk"} <= set(line["readers_ran"])
