"""The files PR 35 added for `kimi_linear_det`: the forward file's count against
hand-worked shapes and the published widths, the two new kernels' counts, the
four new readers over a made-up trace and counters (and `None` on a program
without the kernel), the family's seeding and its save / load round trip, its
attention (sdpa) against eager, its recurrence in blocks against the same
token by token, both against the plain `jax.numpy` reference and against the
program at the tiny rehearsal size, the allocator `load_model` fixes, and
`--rehearse` end to end."""

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
         "hidden_size": 8, "intermediate_size": 6, "moe_intermediate_size": 2, "num_hidden_layers": 5,
         "first_k_dense_replace": 1, "gate_low_rank_dim": 3,
         "linear_attn_config": {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "head_dim": 4,
                                "num_heads": 2, "short_conv_kernel_size": 4},
         "num_attention_heads": 2, "kv_lora_rank": 5, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
         "v_head_dim": 4, "num_experts": 2, "num_routed_experts": 8, "num_experts_per_token": 4,
         "num_experts_per_tok": 4, "num_shared_experts": 1, "num_labels": 3,
         "serve": {"dtype_policy": "bfloat16", "batch_buckets": [2, 4]}}


def test_forward_count_against_hand_worked_shapes():
    forward = bench.load_reader("kernels", "kimi_linear_det_forward")
    # 6 patches + 4 tokens = 10; four KDA layers and one latent-attention layer; one dense, four routed
    assert forward.tokens(SMALL) == (10, 6) and forward.routed_layers(SMALL) == 4
    assert forward.uniform_assignments(SMALL) == 10 * 4 * (2 / 8) * 4
    parts = forward.flops_by_part(SMALL)
    assert parts["patch_projection"] == 2 * 6 * (16 * 16 * 3) * 8
    # q, k, v, out: 8 <-> 2 x 4; two low-rank gates 8 -> 3 -> 8; beta 8 -> 2
    assert parts["kda_projections"] == 4 * (4 * 2 * 10 * 8 * 8 + 2 * (2 * 10 * 8 * 3 + 2 * 10 * 3 * 8)
                                            + 2 * 10 * 8 * 2)
    # q 8 -> 2 x 6, kv_a 8 -> 5 + 2, kv_b 5 -> 2 x 8, out 2 x 4 -> 8
    assert parts["latent_attention_projections"] == (2 * 10 * 8 * 12 + 2 * 10 * 8 * 7 + 2 * 10 * 5 * 16
                                                     + 2 * 10 * 8 * 8)
    assert parts["causal_attention"] == 2 * (6 + 4) * 2 * 55  # 55 (query, key) pairs, 2 heads
    assert parts["dense_mlps"] == 3 * 2 * 10 * 8 * 6
    assert parts["routers"] == 4 * 2 * 10 * 8 * 8
    assert parts["shared_experts"] == 4 * 3 * 2 * 10 * 8 * 2
    assert parts["routed_experts"] == 40 * 3 * 2 * 8 * 2
    assert parts["heads"] == 2 * 4 * (2 * 64 + 8 * 4) + 2 * 4 * (2 * 64 + 8 * 4)
    assert forward.flops_per_image(SMALL) == sum(parts.values())
    assert forward.flops_per_image(SMALL, assignments=0) == sum(parts.values()) - parts["routed_experts"]
    with pytest.raises(AssertionError):  # the layer lists must name every layer
        forward.flops_by_part({**SMALL, "num_hidden_layers": 6})


def test_forward_count_published_widths():
    """ISSUE 35's arithmetic: about 3.2 TFLOP an image: KDA projections and
    gates 1.36, the chunked rule 0.10, latent attention 0.44 (0.19 of it the
    scores), the dense layer 0.55, routed experts 0.49 under a uniform router
    (8,600 held assignments a layer), shared experts and routers 0.26; the two
    new mixers 59 % of it."""
    forward = bench.load_reader("kernels", "kimi_linear_det_forward")
    c = cfg("kimi_linear_det_ep4")
    parts = forward.flops_by_part(c)
    total = forward.flops_per_image(c)
    assert 3.20e12 < total < 3.23e12
    assert forward.uniform_assignments(c) == 4 * 8600
    assert parts["kda_projections"] == pytest.approx(1.36e12, rel=5e-3)
    assert parts["kda_rule"] == pytest.approx(0.10e12, rel=2e-2)
    assert parts["latent_attention_projections"] + parts["causal_attention"] == pytest.approx(0.44e12, rel=1e-2)
    assert parts["causal_attention"] == pytest.approx(0.19e12, rel=1e-2)
    assert parts["dense_mlps"] == pytest.approx(0.55e12, rel=5e-3)
    assert parts["routed_experts"] == 34400 * 3 * 2 * 2304 * 1024
    assert parts["shared_experts"] + parts["routers"] == pytest.approx(0.26e12, rel=2e-2)
    mixers = sum(parts[k] for k in ("kda_projections", "kda_rule", "latent_attention_projections",
                                    "causal_attention"))
    assert 0.58 < mixers / total < 0.60


def test_the_new_kernels_counts():
    c = cfg("kimi_linear_det_ep4")
    kda = bench.load_reader("kernels", "kda")
    attention = bench.load_reader("kernels", "mla_causal_attention")
    delta = bench.load_reader("kernels", "gated_delta_rule")
    assert kda.layers(c) == 4 and attention.layers(c) == 1
    # counted as the scalar-gated rule is counted: that file, given these heads as its own
    as_delta = {**c, "linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_num_key_heads": 32,
                "linear_num_value_heads": 32}
    assert kda.operations_per_token(c) == delta.operations_per_token(as_delta)
    # bytes: q, k, v, the gate and o in bfloat16 a channel, beta a float32 a head
    assert kda.bytes_per_image(c) == (5 * 4096 * 2 + 32 * 4) * 4300 * 4
    assert kda.least_seconds(c, PEAKS) == pytest.approx(kda.bytes_per_image(c) / 819e9)  # bytes bind it
    assert attention.operations_per_image(c) == 2 * (192 + 128) * 32 * (4300 * 4301 // 2)
    assert attention.bytes_per_image(c) == 32 * (2 * 192 + 2 * 128) * 4300 * 2
    assert attention.least_seconds(c, PEAKS) == pytest.approx(attention.operations_per_image(c) / 197e12)
    experts = bench.load_reader("kernels", "expert_matmul")
    assert experts.operations(c, 34400) == 34400 * 3 * 2 * 2304 * 1024


KDA = "%kda_kernel.{n} = bf16[{b},4352,4096]{{2,1,0:T(8,128)(2,1)}} custom-call(%a, %b, %c, %d, %e)"
SPLASH = ("%splash_mha_fwd_no_residuals.1 = (f32[{b},512,128]{{2,1,0}}, f32[{b},512,128]{{2,1,0}}, "
          "f32[{b},512,128]{{2,1,0}}, bf16[{b},32,4608,128]{{3,2,1,0:T(8,128)(2,1)}}) custom-call(%a, %b)")
EXPERT = "%expert_matmul_kernel.{n} = f32[8192,{w}]{{1,0:T(8,128)}} custom-call(%a, %b, %c, %d)"


def made_up_trace():
    """Two program runs: a bucket of 16 whole (four KDA layers, one attention
    layer), a bucket of 8 that the capture cut after two KDA layers."""
    ops = {KDA.format(n=1, b=16): 0.20, KDA.format(n=2, b=8): 0.05, SPLASH.format(b=16): 0.05,
           EXPERT.format(n=3, w=1024): 0.06, EXPERT.format(n=4, w=2304): 0.04,
           "%fusion.9 = bf16[68800,2304]{1,0} fusion(%a)": 0.1}
    return {
        "devices": 1, "window_s": 2.0, "busy_s": 1.1,
        "op_seconds": ops,
        "op_calls": {KDA.format(n=1, b=16): 4, KDA.format(n=2, b=8): 2, SPLASH.format(b=16): 1,
                     EXPERT.format(n=3, w=1024): 40, EXPERT.format(n=4, w=2304): 40},
        "programs": {"jit_big": {"runs": 1, "seconds": 0.8}, "jit_small": {"runs": 1, "seconds": 0.2},
                     "jit_other": {"runs": 3, "seconds": 0.5}},
        "program_ops": {"jit_big": {KDA.format(n=1, b=16), SPLASH.format(b=16), EXPERT.format(n=3, w=1024)},
                        "jit_small": {KDA.format(n=2, b=8)},
                        "jit_other": {"%fusion.1 = f32[8]{0} fusion(%a)"}},
        "program_runs": [{"name": "jit_big", "start_s": 0.1, "end_s": 0.9},
                         {"name": "jit_small", "start_s": 1.8, "end_s": 2.0}],
    }


def test_slots_from_the_kda_kernels_events():
    forward = bench.load_reader("kernels", "kimi_linear_det_forward")
    c = cfg("kimi_linear_det_ep4")
    kda = bench.load_reader("kernels", "kda")
    attention = bench.load_reader("kernels", "mla_causal_attention")
    delta = bench.load_reader("kernels", "gated_delta_rule")
    assert kda.images_of_event(KDA.format(n=7, b=32), c) == 32
    assert attention.images_of_event(SPLASH.format(b=32), c) == 32
    # no reader of the scalar-gated rule's events counts this kernel, nor the other way round
    assert not delta.is_kernel_event(KDA.format(n=7, b=32))
    assert not kda.is_kernel_event("%gated_delta_rule_kernel.3 = bf16[16,4352,4096]{2,1,0} custom-call(%a)")
    assert not attention.is_kernel_event("%splash_mqa_fwd_no_residuals.1 = bf16[8,8,4,4608,64]{4,3,2,1,0} custom-call(%a)")
    slots, seconds = forward.slots_in_trace(c, made_up_trace())
    assert slots == (4 * 16 + 2 * 8) / 4 and seconds == pytest.approx(1.0)
    assert forward.slots_finished(c, made_up_trace()) == 16  # the cut run counts nothing


def reader_ctx(trace, before, after, name="kimi_linear_det_ep4"):
    return {"config": cfg(name), "trace": trace, "peaks": PEAKS, "metrics_before": before,
            "metrics_after": after, "kernel": lambda name: bench.load_reader("kernels", name)}


def test_readers_over_a_made_up_trace_and_counters():
    before = {"images_total": 10, "moe_assignments_total": 1376000, "moe_assignments_local_total": 344000,
              "moe_bias_moved_total": 100000, "moe_expert_tokens_max_total": 100,
              "moe_expert_tokens_mean_total": 80.0, "kda_gate_spread_total": 1000.0,
              "kda_gate_heads_total": 1280}
    after = {"images_total": 110, "moe_assignments_total": 1376000 + 100 * 137600,
             "moe_assignments_local_total": 344000 + 100 * 34400,
             "moe_bias_moved_total": 100000 + 25 * 137600, "moe_expert_tokens_max_total": 100 + 5400,
             "moe_expert_tokens_mean_total": 80.0 + 3600.0, "kda_gate_spread_total": 1000.0 + 100 * 128 * 2.5,
             "kda_gate_heads_total": 1280 + 100 * 128}
    ctx = reader_ctx(made_up_trace(), before, after)
    c = ctx["config"]
    read = lambda name: bench.load_reader("metrics", name).read(ctx)  # noqa: E731
    kda = bench.load_reader("kernels", "kda")
    attention = bench.load_reader("kernels", "mla_causal_attention")
    experts = bench.load_reader("kernels", "expert_matmul")
    forward = bench.load_reader("kernels", "kimi_linear_det_forward")
    # the four new ones: 20 slots; the kernel's 0.25 s of the forward programs' 1.0 s; 2.5 nats a token
    assert read("kda_roofline.bulk") == pytest.approx(100 * kda.least_seconds(c, PEAKS) * 20 / 0.25)
    assert read("mla_attention_roofline.bulk") == pytest.approx(
        100 * attention.least_seconds(c, PEAKS) * 20 / 0.05)
    assert read("kda_kernel_share.bulk") == pytest.approx(25.0)
    assert read("kda_gate_spread.bulk") == pytest.approx(2.5)
    # and the shared ones through this configuration's forward file
    assert read("expert_kernel_share.bulk") == pytest.approx(10.0)
    assert read("routing_bias_moved.bulk") == pytest.approx(25.0)
    assert read("expert_matmul_roofline.bulk") == pytest.approx(
        100 * experts.least_seconds(c, PEAKS, 34400.0) * 20 / 0.1)
    assert read("expert_imbalance.bulk") == pytest.approx(1.5)
    assert read("step_mfu.bulk") == pytest.approx(100 * forward.flops_per_image(c) * 20 / (1.0 * 197e12))
    assert read("delta_rule_roofline.bulk") is None  # another kernel's events
    assert read("causal_attention_roofline.bulk") is None


NEW_READERS = ["kda_roofline.bulk", "mla_attention_roofline.bulk", "kda_kernel_share.bulk",
               "kda_gate_spread.bulk"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_them(name):
    """The parent of PR 35, or a family with no such mixer: no such event, no
    such counter. The reader returns None and does not raise."""
    delta = "%gated_delta_rule_kernel.3 = bf16[16,4352,4096]{2,1,0:T(8,128)(2,1)} custom-call(%copy.1)"
    trace = {"devices": 1, "window_s": 2.0, "busy_s": 1.0,
             "op_seconds": {"%fusion.1 = f32[8]{0} fusion(%a)": 0.5, delta: 0.1}, "op_calls": {delta: 3},
             "programs": {"jit_f": {"runs": 1, "seconds": 0.8}}, "program_ops": {"jit_f": {delta}},
             "program_runs": []}
    for config in ("kimi_linear_det_ep4", "lfm2_moe_det_pp4", "qwen3_next_det_ep8", "yolos_base"):
        ctx = reader_ctx(trace, {"images_total": 1}, {"images_total": 9}, config)
        assert bench.load_reader("metrics", name).read(ctx) is None
        ctx["trace"] = None
        assert bench.load_reader("metrics", name).read(ctx) is None
    # counters of a program that serves no such mixer: present and zero
    ctx = reader_ctx(None, {"kda_gate_heads_total": 0, "kda_gate_spread_total": 0},
                     {"kda_gate_heads_total": 0, "kda_gate_spread_total": 0})
    assert bench.load_reader("metrics", "kda_gate_spread.bulk").read(ctx) is None


def test_manifest_lists_the_cell_where_its_readers_read():
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in manifest["workloads"] if w["name"] == "kimi_linear_det_bulk")
    assert cell["config"] == "kimi_linear_det_ep4" and cell["traffic"] == "bulk_closed" and cell["chips"] == 1
    listed = {m["name"] for m in bench.metrics_of(manifest, cell, "per_layer")}
    assert {"step_mfu.bulk", "expert_matmul_roofline.bulk", "expert_imbalance.bulk",
            "expert_kernel_share.bulk", "routing_bias_moved.bulk", "device_idle.bulk", "slot_fill.bulk",
            *NEW_READERS} <= listed
    assert not {"attention_roofline.bulk", "delta_rule_roofline.bulk", "causal_attention_roofline.bulk"} & listed
    assert {m["name"] for m in bench.metrics_of(manifest, cell, "end_to_end")} == {"images_per_s", "setup_s"}
    # what the benchmark had stands first and as it was; this PR's entries follow it (later ones may follow these)
    assert [w["name"] for w in manifest["workloads"]][:4] == [
        "yolos_base_bulk", "qwen3_next_det_bulk", "lfm2_moe_det_bulk", "kimi_linear_det_bulk"]
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("kda_roofline.bulk")
    assert names[at - 2:at + 4] == ["expert_kernel_share.bulk", "routing_bias_moved.bulk", *NEW_READERS]
    for name in NEW_READERS:
        metric = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert metric["workloads"] == ["kimi_linear_det_bulk"] and metric["moves"] == "images_per_s"
    c = cfg("kimi_linear_det_ep4")
    published = c["published"]
    assert c["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"] == list(published)
    lists = published["linear_attn_config"]
    assert sorted(lists["kda_layers"] + lists["full_attn_layers"]) == list(range(1, 28))
    held = c["linear_attn_config"]
    assert held["kda_layers"] == [n for n in lists["kda_layers"] if n <= 5]
    assert held["full_attn_layers"] == [n for n in lists["full_attn_layers"] if n <= 5]
    assert {k: v for k, v in held.items() if "layers" not in k} == {
        k: v for k, v in lists.items() if "layers" not in k}  # every width of the group as published
    assert c["num_experts"] * c["expert_parallel_chips"] == c["num_routed_experts"] == published["num_experts"]


@pytest.fixture(scope="module")
def tiny_checkpoint(tmp_path_factory):
    return weights.ensure_checkpoint(cfg("kimi_linear_det_tiny_rehearsal"),
                                     str(tmp_path_factory.mktemp("ckpt")))


def test_family_seeding_and_round_trip(tiny_checkpoint):
    """Seeded twice, the tensors are the same; what is saved is what is loaded
    (bfloat16-rounded); the special tensors follow their rules."""
    import torch

    c = cfg("kimi_linear_det_tiny_rehearsal")
    assert os.path.basename(tiny_checkpoint).startswith("kimi_linear_det_tiny_rehearsal-")
    assert sorted(os.listdir(tiny_checkpoint)) == ["DONE", "config.json", "model.safetensors"]
    fam = weights.family("kimi_linear_det")
    built = weights.build_model(c)
    loaded = fam.load_model(tiny_checkpoint)
    state, again = built.state_dict(), loaded.state_dict()
    assert set(state) == set(again)
    for name, tensor in state.items():
        assert torch.equal(tensor.to(torch.bfloat16).float(), again[name]), name
    assert torch.all(state["layers.0.input_layernorm.weight"] == 1)
    assert torch.all(state["layers.1.self_attn.o_norm.weight"] == 1)
    a_log, dt_bias = state["layers.0.self_attn.A_log"], state["layers.0.self_attn.dt_bias"]
    assert a_log.shape == (1, 1, 4, 1) and 0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16)
    step = torch.nn.functional.softplus(dt_bias)  # a value a channel, a log-uniform step
    assert dt_bias.shape == (64,) and 1e-3 <= float(step.min()) and float(step.max()) <= 0.1001
    bias = state["layers.3.block_sparse_moe.gate.e_score_correction_bias"]
    assert bias.shape == (8,) and 0 < float(bias.abs().max()) < 4 * c["weights"]["expert_bias_std"]
    assert "layers.3.self_attn.A_log" not in state and "layers.3.self_attn.kv_b_proj.weight" in state
    assert "layers.0.block_sparse_moe.gate.weight" not in state  # the first layer is dense
    assert state["layers.0.mlp.gate_proj.weight"].shape == (c["intermediate_size"], c["hidden_size"])
    experts = {n.split(".")[4] for n in state if ".block_sparse_moe.experts." in n}
    assert experts == {str(e) for e in range(c["num_experts"])}  # the share held, of 8 routed
    assert not any(n.endswith(".bias") and n.startswith("layers.") for n in state)  # no projection carries one
    with open(os.path.join(tiny_checkpoint, "config.json")) as f:
        hf = json.load(f)
    assert hf["num_experts"] == 4 and hf["num_routed_experts"] == 8 and "serve" not in hf


def test_family_sdpa_reads_as_eager(tiny_checkpoint):
    import torch

    fam = weights.family("kimi_linear_det")
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


def test_family_blocks_read_as_tokens(tiny_checkpoint):
    """The recurrence unrolled over blocks of 32 tokens (what the reference
    runs) is the recurrence token by token: through the whole tiny model (24
    patch tokens + 20: a whole block and one of 12), and at the published
    head width on the authors' gate with a stretch that loses 40 nats a token
    on a head's even channels and nothing on its odd ones, over 150 tokens
    (four blocks and one of 22; e^{1280} inside a block would overflow a
    quotient of exponentials), in blocks of 32 and of 16. A state that forgets in a token and one that never does
    sit side by side: both forms agree to float32's rounding and stay finite."""
    import torch

    fam = weights.family("kimi_linear_det")
    model = fam.load_model(tiny_checkpoint).eval()
    with open(os.path.join(tiny_checkpoint, "config.json")) as f:
        hf = json.load(f)
    by_token = fam.new_model({**hf, "_reference_recurrence": "tokens"}).eval()
    by_token.load_state_dict(model.state_dict())
    x = torch.from_numpy(np.random.default_rng(0).standard_normal((2, 3, 64, 96)).astype(np.float32))
    with torch.no_grad():
        a, b = model(pixel_values=x), by_token(pixel_values=x)
    assert float((a.logits - b.logits).abs().max()) < 1e-4
    assert float((a.pred_boxes - b.pred_boxes).abs().max()) < 1e-5

    rng = np.random.default_rng(1)
    tokens, heads, dk = 150, 3, 128
    q, k, v = (torch.from_numpy(rng.standard_normal((tokens, heads, dk)).astype(np.float32)) for _ in range(3))
    q = q / q.norm(dim=-1, keepdim=True) * dk**-0.5
    k = k / k.norm(dim=-1, keepdim=True)
    rate = torch.from_numpy(rng.uniform(1.0, 16.0, (1, heads, 1)).astype(np.float32))
    step = torch.from_numpy(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, heads, dk))).astype(np.float32))
    raw = torch.from_numpy(rng.standard_normal((tokens, heads, dk)).astype(np.float32))
    g = -rate * torch.nn.functional.softplus(raw + torch.log(torch.expm1(step)))
    g[40:90, :, 0::2] = -40.0
    g[40:90, :, 1::2] = 0.0
    beta = torch.from_numpy(rng.uniform(0, 1, (tokens, heads)).astype(np.float32))
    mixer = model.layers[0].self_attn
    with torch.no_grad():
        want = mixer.recurrence_by_token(q, k, v, g, beta)
        by_head = [a.transpose(0, 1) for a in (q, k, v, g, beta[..., None])]
        got = [mixer.recurrence(*by_head, block=block).transpose(0, 1) for block in (32, 16)]
    assert float(want.abs().mean()) > 1e-3
    for out in got:
        assert bool(torch.isfinite(out).all())
        assert float((out - want).abs().max()) < 2e-6


def test_load_model_keeps_freed_blocks_mapped(tiny_checkpoint):
    """`load_model` ends by fixing the allocator (glibc here): a block freed
    after it is kept, so the next array of its size faults no page in."""
    import resource

    import torch

    fam = weights.family("kimi_linear_det")
    assert fam.keep_freed_blocks_mapped() is True
    fam.load_model(tiny_checkpoint)
    torch.ones(64 << 20).sum()  # 256 MB: brought into the heap once

    def faults_of_a_block():
        before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        torch.ones(64 << 20).sum()
        return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before

    assert max(faults_of_a_block() for _ in range(3)) < 1000  # 65,536 pages were it mapped anew


def test_the_torch_family_against_the_plain_reference_and_the_program(tiny_checkpoint, tmp_path, monkeypatch):
    """Three implementations of the equations read one rehearsal checkpoint in
    float32: the benchmark's torch module (a token reads the state once), the
    plain `jax.numpy` reference (decay, read, write, read: token by token) and
    the served Flax module (chunked). They agree to rounding: logits (the class
    head's gain is 12) to 3e-4, boxes to 1e-5; the router's bias moves choices,
    and the gate's channels decay apart, in the program as in the reference."""
    import jax
    import torch

    from spotter_tpu.convert import loader
    from spotter_tpu.models.kimi_linear import KimiLinearDetector
    from spotter_tpu.testing import kimi_linear_reference as ref

    monkeypatch.setenv("SPOTTER_TPU_CACHE", str(tmp_path / "cache"))
    model = weights.family("kimi_linear_det").load_model(tiny_checkpoint).eval()
    config, params = loader.load_kimi_linear_det(tiny_checkpoint)
    params = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), params)
    x = np.random.default_rng(0).standard_normal((3, 64, 96, 3)).astype(np.float32)
    with torch.no_grad():
        want = model(pixel_values=torch.from_numpy(x).permute(0, 3, 1, 2))
    with jax.default_matmul_precision("highest"):
        got = KimiLinearDetector(config).apply({"params": params}, x)
        plain = [ref.detector(params, x[i], config) for i in range(3)]
    for i in range(3):
        np.testing.assert_allclose(plain[i]["logits"], want.logits[i].numpy(), atol=3e-4)
        np.testing.assert_allclose(plain[i]["pred_boxes"], want.pred_boxes[i].numpy(), atol=1e-5)
    np.testing.assert_allclose(got["logits"], want.logits.numpy(), atol=3e-4)
    np.testing.assert_allclose(got["pred_boxes"], want.pred_boxes.numpy(), atol=1e-5)
    assert 0 < int(np.asarray(got["moe_bias_moved"]).sum()) < int(np.asarray(got["moe_assignments"]).sum())
    assert float(np.asarray(got["kda_gate_spread"]).min()) > 0


@pytest.mark.slow
def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "kimi_linear_det_bulk",
         "--seed", "2147483997", "--seconds", "6", "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert {"expert_imbalance.bulk", "routing_bias_moved.bulk", "kda_gate_spread.bulk",
            "slot_fill.bulk"} <= set(line["readers_ran"])
