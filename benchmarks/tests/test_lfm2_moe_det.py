"""The files PR 33 added for `lfm2_moe_det`: the forward file's count against
hand-worked shapes and the published widths, the kernels' files read with this
configuration's keys, the two new readers over a made-up trace and counters,
the family's seeding and its save / load round trip, its attention (sdpa)
against eager, the torch family against the program at the tiny rehearsal
size, and `--rehearse` end to end."""

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


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


SMALL = {"image_size": [32, 48], "patch_size": 16, "num_detection_tokens": 4, "num_channels": 3,
         "hidden_size": 8, "intermediate_size": 6, "moe_intermediate_size": 2,
         "num_hidden_layers": 6, "num_dense_layers": 2, "full_attention_interval": 4,
         "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
         "num_experts": 4, "num_routed_experts": 4, "num_experts_per_tok": 2, "num_labels": 3,
         "serve": {"dtype_policy": "bfloat16", "batch_buckets": [2, 4]}}


def test_forward_count_against_hand_worked_shapes():
    forward = bench.load_reader("kernels", "lfm2_moe_det_forward")
    # 6 patches + 4 tokens = 10; five conv layers, one attention layer; two dense, four routed
    assert forward.tokens(SMALL) == (10, 6) and forward.routed_layers(SMALL) == 4
    assert forward.assignments_per_image(SMALL) == 10 * 2 * 4
    parts = forward.flops_by_part(SMALL)
    assert parts["patch_projection"] == 2 * 6 * (16 * 16 * 3) * 8
    assert parts["short_conv_projections"] == 5 * (2 * 10 * 8 * 24 + 2 * 10 * 8 * 8)
    # q 4 x 2 = 8 wide, k and v 2 x 2 = 4 wide, out 8 -> 8
    assert parts["attention_projections"] == 2 * 10 * 8 * (8 + 4 + 4) + 2 * 10 * 8 * 8
    assert parts["causal_attention"] == 2 * 2 * 55 * 4 * 2  # 55 (query, key) pairs, 4 heads of 2
    assert parts["dense_mlps"] == 2 * 3 * 2 * 10 * 8 * 6
    assert parts["routers"] == 4 * 2 * 10 * 8 * 4
    assert parts["routed_experts"] == 80 * 3 * 2 * 8 * 2
    assert parts["heads"] == 2 * 4 * (2 * 64 + 8 * 4) + 2 * 4 * (2 * 64 + 8 * 4)
    assert forward.flops_per_image(SMALL) == sum(parts.values())
    # another count of assignments moves the routed part alone
    assert forward.flops_per_image(SMALL, assignments=0) == sum(parts.values()) - parts["routed_experts"]
    with pytest.raises(AssertionError):  # the derived key must agree with the layer list
        forward.flops_by_part({**SMALL, "layer_types": ["conv"] * 6})


def test_forward_count_published_widths():
    """ISSUE 33's arithmetic: 3.18 TFLOP an image: routed experts 1.515 (48 %),
    dense feed-forwards 0.758, conv mixers 0.721, attention 0.166 of which the
    scores 0.076, patch projection and heads 0.017."""
    forward = bench.load_reader("kernels", "lfm2_moe_det_forward")
    c = cfg("lfm2_moe_det_pp4")
    parts = forward.flops_by_part(c)
    total = forward.flops_per_image(c)
    assert 3.17e12 < total < 3.19e12
    assert parts["routed_experts"] == 4300 * 4 * 4 * 3 * 2 * 2048 * 1792
    assert 0.47 < parts["routed_experts"] / total < 0.48
    assert parts["dense_mlps"] == pytest.approx(0.758e12, rel=2e-3)
    assert parts["short_conv_projections"] == pytest.approx(0.721e12, rel=2e-3)
    assert parts["causal_attention"] == pytest.approx(0.076e12, rel=1e-2)
    assert parts["attention_projections"] + parts["causal_attention"] == pytest.approx(0.166e12, rel=1e-2)
    assert parts["patch_projection"] + parts["heads"] == pytest.approx(0.017e12, rel=3e-2)


def test_the_kernels_files_read_this_configurations_keys():
    """`causal_gqa_attention.py` and `expert_matmul.py` were written for the
    other routed configuration: with this one's keys (two of them derived and
    under `assumed`) they count one attention layer of 32 heads of 64, and an
    assignment's three 2048 x 1792 products."""
    c = cfg("lfm2_moe_det_pp4")
    attention = bench.load_reader("kernels", "causal_gqa_attention")
    experts = bench.load_reader("kernels", "expert_matmul")
    assert attention.layers(c) == c["layer_types"].count("full_attention") == 1
    assert c["head_dim"] == c["hidden_size"] // c["num_attention_heads"]
    assert attention.operations_per_image(c) == 2 * 2 * (4300 * 4301 // 2) * 32 * 64
    assert attention.bytes_per_image(c) == (2 * 32 + 2 * 8) * 64 * 4300 * 2
    assert experts.operations(c, 68800) == 68800 * 3 * 2 * 2048 * 1792
    # operations bound it: an assignment's products against its 8 KB of rows
    assert experts.least_seconds(c, PEAKS, 68800) == pytest.approx(
        68800 * 3 * 2 * 2048 * 1792 / 197e12)


SPLASH = ("%splash_mqa_fwd_no_residuals.1 = (f32[{b},8,512,128]{{3,2,1,0}}, f32[{b},8,512,128]{{3,2,1,0}}, "
          "f32[{b},8,512,64]{{3,2,1,0}}, bf16[{b},8,4,4608,64]{{4,3,2,1,0:T(8,128)(2,1)}}) custom-call(%a, %b)")
EXPERT = "%expert_matmul_kernel.{n} = f32[8192,{w}]{{1,0:T(8,128)}} custom-call(%a, %b, %c, %d)"


def made_up_trace():
    """Two program runs: a bucket of 16 whole, a bucket of 8 that the capture
    cut after its attention layer."""
    ops = {SPLASH.format(b=16): 0.05, SPLASH.format(b=8): 0.03,
           EXPERT.format(n=3, w=3584): 0.3, EXPERT.format(n=4, w=2048): 0.2,
           "%fusion.9 = bf16[68800,2048]{1,0} fusion(%a)": 0.1}
    return {
        "devices": 1, "window_s": 2.0, "busy_s": 1.1,
        "op_seconds": ops,
        "op_calls": {SPLASH.format(b=16): 1, SPLASH.format(b=8): 1, EXPERT.format(n=3, w=3584): 40,
                     EXPERT.format(n=4, w=2048): 40},
        "programs": {"jit_big": {"runs": 1, "seconds": 0.8}, "jit_small": {"runs": 1, "seconds": 0.2},
                     "jit_other": {"runs": 3, "seconds": 0.5}},
        "program_ops": {"jit_big": {SPLASH.format(b=16), EXPERT.format(n=3, w=3584)},
                        "jit_small": {SPLASH.format(b=8)},
                        "jit_other": {"%fusion.1 = f32[8]{0} fusion(%a)"}},
        "program_runs": [{"name": "jit_big", "start_s": 0.1, "end_s": 0.9},
                         {"name": "jit_small", "start_s": 1.8, "end_s": 2.0}],
    }


def test_slots_from_the_attention_kernels_events():
    forward = bench.load_reader("kernels", "lfm2_moe_det_forward")
    c = cfg("lfm2_moe_det_pp4")
    attention = bench.load_reader("kernels", "causal_gqa_attention")
    assert attention.images_of_event(SPLASH.format(b=32), c) == 32
    other = cfg("qwen3_next_det_ep8")  # 2 key-value heads of 8 query heads, 256 wide: another shape
    assert attention.images_of_event(SPLASH.format(b=32), other) is None
    slots, seconds = forward.slots_in_trace(c, made_up_trace())
    assert slots == 24 and seconds == pytest.approx(1.0)
    assert forward.slots_finished(c, made_up_trace()) == 16  # the cut run counts nothing


def reader_ctx(trace, before, after, name="lfm2_moe_det_pp4"):
    return {"config": cfg(name), "trace": trace, "peaks": PEAKS, "metrics_before": before,
            "metrics_after": after, "kernel": lambda name: bench.load_reader("kernels", name)}


def test_readers_over_a_made_up_trace_and_counters():
    before = {"images_total": 10, "moe_assignments_total": 688000, "moe_assignments_local_total": 688000,
              "moe_bias_moved_total": 100000, "moe_expert_tokens_max_total": 100,
              "moe_expert_tokens_mean_total": 80.0}
    after = {"images_total": 110, "moe_assignments_total": 688000 + 100 * 68800,
             "moe_assignments_local_total": 688000 + 100 * 68800,
             "moe_bias_moved_total": 100000 + 25 * 68800, "moe_expert_tokens_max_total": 100 + 5400,
             "moe_expert_tokens_mean_total": 80.0 + 3600.0}
    ctx = reader_ctx(made_up_trace(), before, after)
    c = ctx["config"]
    read = lambda name: bench.load_reader("metrics", name).read(ctx)  # noqa: E731
    # the two new ones: the kernel's 0.5 s of the forward programs' 1.0 s; a quarter of the selections
    assert read("expert_kernel_share.bulk") == pytest.approx(50.0)
    assert read("routing_bias_moved.bulk") == pytest.approx(25.0)
    # and the shared ones through this configuration's forward file
    experts = bench.load_reader("kernels", "expert_matmul")
    attention = bench.load_reader("kernels", "causal_gqa_attention")
    forward = bench.load_reader("kernels", "lfm2_moe_det_forward")
    assert read("expert_matmul_roofline.bulk") == pytest.approx(
        100 * experts.least_seconds(c, PEAKS, 68800.0) * 24 / 0.5)
    assert read("causal_attention_roofline.bulk") == pytest.approx(
        100 * attention.least_seconds(c, PEAKS) * 24 / 0.08)
    assert read("expert_imbalance.bulk") == pytest.approx(1.5)
    assert read("step_mfu.bulk") == pytest.approx(100 * forward.flops_per_image(c) * 24 / (1.0 * 197e12))
    assert read("delta_rule_roofline.bulk") is None  # this family has no such kernel


def test_expert_kernel_share_reads_the_other_routed_cell_too():
    """`qwen3_next_det_bulk` is on the new reader's list: its forward file
    takes the programs that hold the delta rule's kernel."""
    delta = "%gated_delta_rule_kernel.3 = bf16[16,4352,4096]{2,1,0:T(8,128)(2,1)} custom-call(%copy.1)"
    expert = "%expert_matmul_kernel.48 = f32[8192,1024]{1,0:T(8,128)} custom-call(%a, %b, %c, %d)"
    trace = {"devices": 1, "window_s": 2.0, "busy_s": 1.0, "op_seconds": {delta: 0.1, expert: 0.04},
             "op_calls": {delta: 3, expert: 100}, "programs": {"jit_f": {"runs": 1, "seconds": 0.8}},
             "program_ops": {"jit_f": {delta, expert}}, "program_runs": []}
    ctx = reader_ctx(trace, {}, {}, "qwen3_next_det_ep8")
    assert bench.load_reader("metrics", "expert_kernel_share.bulk").read(ctx) == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["expert_kernel_share.bulk", "routing_bias_moved.bulk"])
def test_new_readers_find_nothing_in_a_program_without_them(name):
    """The parent of PR 33, or a family with no experts: no such event, no
    such counter. The reader returns None and does not raise."""
    trace = {"devices": 1, "window_s": 2.0, "busy_s": 1.0,
             "op_seconds": {"%fusion.1 = f32[8]{0} fusion(%a)": 0.5}, "op_calls": {}, "programs": {},
             "program_ops": {}, "program_runs": []}
    for config in ("lfm2_moe_det_pp4", "qwen3_next_det_ep8", "yolos_base"):
        ctx = reader_ctx(trace, {"images_total": 1}, {"images_total": 9}, config)
        assert bench.load_reader("metrics", name).read(ctx) is None
        ctx["trace"] = None
        assert bench.load_reader("metrics", name).read(ctx) is None
    # the parent's counters: assignments, and no count of what a bias moved
    ctx = reader_ctx(None, {"moe_assignments_total": 0}, {"moe_assignments_total": 500})
    assert bench.load_reader("metrics", "routing_bias_moved.bulk").read(ctx) is None


def test_manifest_lists_the_cell_where_its_readers_read():
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in manifest["workloads"] if w["name"] == "lfm2_moe_det_bulk")
    assert cell["config"] == "lfm2_moe_det_pp4" and cell["traffic"] == "bulk_closed" and cell["chips"] == 1
    listed = {m["name"] for m in bench.metrics_of(manifest, cell, "per_layer")}
    assert {"step_mfu.bulk", "expert_matmul_roofline.bulk", "causal_attention_roofline.bulk",
            "expert_imbalance.bulk", "expert_kernel_share.bulk", "routing_bias_moved.bulk",
            "device_idle.bulk", "slot_fill.bulk"} <= listed
    assert not {"attention_roofline.bulk", "delta_rule_roofline.bulk"} & listed
    assert {m["name"] for m in bench.metrics_of(manifest, cell, "end_to_end")} == {"images_per_s", "setup_s"}
    # what the benchmark had stands first and as it was; the new entries come last
    assert [w["name"] for w in manifest["workloads"]] == [
        "yolos_base_bulk", "qwen3_next_det_bulk", "lfm2_moe_det_bulk"]
    assert [m["name"] for m in manifest["per_layer"]][-2:] == [
        "expert_kernel_share.bulk", "routing_bias_moved.bulk"]
    c = cfg("lfm2_moe_det_pp4")
    published = c["published"]
    assert c["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"] == list(published)
    assert c["layer_types"] == published["layer_types"][:c["num_hidden_layers"]]
    assert published["num_hidden_layers"] == len(published["layer_types"]) == 24


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    return weights.ensure_checkpoint(cfg("lfm2_moe_det_tiny_rehearsal"),
                                     str(tmp_path_factory.mktemp("ckpt")))


def test_family_seeding_and_round_trip(tiny_checkpoint):
    """Seeded twice, the tensors are the same; what is saved is what is
    loaded (bfloat16-rounded); the special tensors follow their rules."""
    import torch

    c = cfg("lfm2_moe_det_tiny_rehearsal")
    assert os.path.basename(tiny_checkpoint).startswith("lfm2_moe_det_tiny_rehearsal-")
    assert sorted(os.listdir(tiny_checkpoint)) == ["DONE", "config.json", "model.safetensors"]
    fam = weights.family("lfm2_moe_det")
    built = weights.build_model(c)
    loaded = fam.load_model(tiny_checkpoint)
    state, again = built.state_dict(), loaded.state_dict()
    assert set(state) == set(again)
    for name, tensor in state.items():
        assert torch.equal(tensor.to(torch.bfloat16).float(), again[name]), name
    assert torch.all(state["layers.0.operator_norm.weight"] == 1)
    assert torch.all(state["layers.2.self_attn.q_layernorm.weight"] == 1)
    bias = state["layers.3.feed_forward.expert_bias"]
    assert 0 < float(bias.abs().max()) < 4 * c["weights"]["expert_bias_std"]  # live, and small
    assert "layers.2.conv.in_proj.weight" not in state  # the third layer attends
    assert "layers.1.feed_forward.gate.weight" not in state  # the first two are dense
    assert state["layers.0.feed_forward.w1.weight"].shape == (c["intermediate_size"], c["hidden_size"])
    experts = {n.split(".")[4] for n in state if ".feed_forward.experts." in n}
    assert experts == {str(e) for e in range(c["num_experts"])}
    with open(os.path.join(tiny_checkpoint, "config.json")) as f:
        hf = json.load(f)
    assert hf["num_experts"] == 8 and hf["num_dense_layers"] == 2 and "serve" not in hf


def test_family_sdpa_reads_as_eager(tiny_checkpoint):
    import torch

    fam = weights.family("lfm2_moe_det")
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


def test_the_torch_family_against_the_program(tiny_checkpoint, tmp_path, monkeypatch):
    """The benchmark's torch module (transformers' Lfm2 layers, the routed
    block written out) and the served Flax module, both float32, read the one
    checkpoint and agree to rounding: logits (the class head's gain is 12) to
    2e-4, boxes to 1e-5; the router's bias moves choices in both alike."""
    import jax
    import torch

    from spotter_tpu.convert import loader
    from spotter_tpu.models.lfm2_moe import Lfm2MoeDetector

    monkeypatch.setenv("SPOTTER_TPU_CACHE", str(tmp_path / "cache"))
    model = weights.family("lfm2_moe_det").load_model(tiny_checkpoint).eval()
    config, params = loader.load_lfm2_moe_det(tiny_checkpoint)
    params = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), params)
    x = np.random.default_rng(0).standard_normal((3, 64, 96, 3)).astype(np.float32)
    with torch.no_grad():
        want = model(pixel_values=torch.from_numpy(x).permute(0, 3, 1, 2))
    with jax.default_matmul_precision("highest"):
        got = Lfm2MoeDetector(config).apply({"params": params}, x)
    np.testing.assert_allclose(got["logits"], want.logits.numpy(), atol=2e-4)
    np.testing.assert_allclose(got["pred_boxes"], want.pred_boxes.numpy(), atol=1e-5)
    assert 0 < int(np.asarray(got["moe_bias_moved"]).sum()) < int(np.asarray(got["moe_assignments"]).sum())


@pytest.mark.slow
def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "lfm2_moe_det_bulk",
         "--seed", "2147483997", "--seconds", "6", "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert {"expert_imbalance.bulk", "routing_bias_moved.bulk", "slot_fill.bulk"} <= set(line["readers_ran"])
