import json
import os

import pytest

import run as bench
from conftest import BENCH


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_msda_against_a_hand_count():
    msda = bench.load_reader("kernels", "msda")
    small = {"decoder_layers": 2, "num_queries": 5, "decoder_attention_heads": 2,
             "num_feature_levels": 2, "decoder_n_points": 3, "d_model": 8,
             "feat_strides": [8, 16], "serve": {"dtype_policy": "bfloat16"}}
    # 5 queries x 2 heads x 2 levels x 3 points x 4-wide rows x (4*2 + 2) x 2 layers
    assert msda.operations_per_image(small) == 5 * 2 * 2 * 3 * 4 * 10 * 2 == 4800
    tokens = 80 * 80 + 40 * 40
    per_layer = tokens * 8 * 2 + 5 * 2 * 2 * 3 * 3 * 4 + 5 * 8 * 2
    assert msda.bytes_per_image(small) == per_layer * 2
    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    assert msda.least_seconds(small, peaks) == pytest.approx(per_layer * 2 / 819e9)


def test_msda_published_shapes():
    msda = bench.load_reader("kernels", "msda")
    s = msda.shapes(cfg("rtdetr_v2_r101vd"))
    assert s == {"layers": 6, "queries": 300, "heads": 8, "levels": 3, "points": 4,
                 "head_dim": 32, "tokens": 8400}
    assert msda.is_kernel_event(
        "%encoder_attn.7 = f32[224,320,32]{2,1,0:T(8,128)} custom-call(s32[224,5,14]{2,1,0} %x)")
    assert not msda.is_kernel_event("%fusion.12 = bf16[28,320,320,64]{3,0,2,1} fusion(%a)")
    assert not msda.is_kernel_event("%encoder_attn.3 = f32[8]{0} fusion(%a)")


@pytest.mark.slow
@pytest.mark.parametrize("name,published_gflops", [
    ("rtdetr_v2_r50vd", 136.0), ("rtdetr_v2_r101vd", 259.0)])
def test_forward_count_against_the_source(name, published_gflops):
    """The source's model cards give 136 and 259 GFLOPs at 640x640."""
    forward = bench.load_reader("kernels", "rtdetr_forward")
    got = forward.flops_per_image(cfg(name)) / 1e9
    assert abs(got - published_gflops) / published_gflops < 0.03, got


KERNEL = ("%encoder_attn.7 = f32[{rows},320,32]{{2,1,0:T(8,128)}} "
          "custom-call(s32[{rows},5,14]{{2,1,0}} %x)")


def test_slots_from_the_kernel_events_shapes():
    forward = bench.load_reader("kernels", "rtdetr_forward")
    msda = bench.load_reader("kernels", "msda")
    c = cfg("rtdetr_v2_r101vd")  # 8 heads, 6 decoder layers
    big, small = KERNEL.format(rows=224), KERNEL.format(rows=32)
    assert msda.images_of_event(big, c) == 28 and msda.images_of_event(small, c) == 4
    assert msda.images_of_event(KERNEL.format(rows=225), c) is None
    assert msda.images_of_event("%fusion.1 = bf16[224,3]{1,0} fusion(%a)", c) is None
    resize = "%fusion.9 = f32[28,640,640,3]{3,2,1,0} fusion(%a)"
    trace = {
        "programs": {
            "jit_apply_post(1)": {"runs": 3, "seconds": 0.06},
            "jit_apply_post(2)": {"runs": 10.5, "seconds": 1.1},
            "jit_resize(3)": {"runs": 50, "seconds": 0.001},
        },
        "program_ops": {
            "jit_apply_post(1)": {"%fusion.1 = bf16[4,3]{1,0} fusion(%a)", small},
            "jit_apply_post(2)": {big},
            "jit_resize(3)": {resize},
        },
        # ten whole passes of 28 and one cut after three of its six layers; three of 4
        "op_calls": {big: 10 * 6 + 3, small: 3 * 6, resize: 50},
    }
    slots, seconds = forward.slots_in_trace(c, trace)
    assert slots == pytest.approx(10.5 * 28 + 3 * 4) and seconds == pytest.approx(1.16)


SMALL_YOLOS = {"hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
               "intermediate_size": 48, "image_size": [64, 96], "patch_size": 16,
               "num_channels": 3, "num_detection_tokens": 5, "num_labels": 91,
               "serve": {"dtype_policy": "bfloat16"}}


def test_yolos_forward_and_attention_against_a_hand_count():
    forward = bench.load_reader("kernels", "yolos_forward")
    attention = bench.load_reader("kernels", "flash_attention")
    # 4 x 6 patches, 1 + 24 + 5 = 30 tokens of width 32
    assert forward.tokens(SMALL_YOLOS) == (30, 24) and attention.tokens(SMALL_YOLOS) == 30
    patch = 2 * 24 * (16 * 16 * 3) * 32
    layer = 4 * 2 * 30 * 32 * 32 + 2 * 2 * 30 * 30 * 32 + 2 * 2 * 30 * 32 * 48
    heads = 2 * 5 * (32 * 32 + 32 * 32 + 32 * 92) + 2 * 5 * (32 * 32 + 32 * 32 + 32 * 4)
    assert forward.flops_per_image(SMALL_YOLOS) == patch + 2 * layer + heads
    assert attention.operations_per_image(SMALL_YOLOS) == 2 * 2 * 30 * 30 * 32 * 2
    assert attention.bytes_per_image(SMALL_YOLOS) == 4 * 30 * 32 * 2 * 2
    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    base = cfg("yolos_base")  # 4301 tokens: the operations bound it
    assert attention.least_seconds(base, peaks) == pytest.approx(
        attention.operations_per_image(base) / 197e12)


def test_yolos_forward_count_against_torch():
    """transformers' own model of the small shape under torch's counter: the
    hand count leaves out nothing that multiplies matrices. The counter does
    not see inside the fused attention op the model calls on the CPU, so the
    scores and their use (kernels/flash_attention.py's count) are added."""
    import torch
    from torch.utils.flop_counter import FlopCounterMode
    from transformers import YolosConfig, YolosForObjectDetection

    forward = bench.load_reader("kernels", "yolos_forward")
    attention = bench.load_reader("kernels", "flash_attention")
    hf = {k: v for k, v in SMALL_YOLOS.items() if k != "serve"}
    model = YolosForObjectDetection(YolosConfig(**hf)).eval()
    with torch.no_grad(), FlopCounterMode(display=False) as counter:
        model(pixel_values=torch.zeros(1, 3, 64, 96))
    counted = counter.get_total_flops() + attention.operations_per_image(SMALL_YOLOS)
    assert counted == forward.flops_per_image(SMALL_YOLOS)


SPLASH = ("%splash_mha_fwd_segmented_no_residuals.12 = (f32[{b},512,128]{{2,1,0:T(8,128)}}, "
          "f32[{b},512,128]{{2,1,0:T(8,128)}}, f32[{b},512,64]{{2,1,0:T(8,128)}}, "
          "bf16[{b},12,4608,64]{{3,2,1,0:T(8,128)(2,1)}}) custom-call(s8[1,9,2]{{2,1,0}} %copy-done.408)")


def test_yolos_slots_from_the_attention_kernels_own_result():
    """The event as a trace of yolos_base_bulk shows it (PR 25): a tuple whose
    last member is (images, heads, tokens padded, head width)."""
    forward = bench.load_reader("kernels", "yolos_forward")
    attention = bench.load_reader("kernels", "flash_attention")
    c = cfg("yolos_base")  # 12 heads of 64, 12 layers
    big, small = SPLASH.format(b=48), SPLASH.format(b=8)
    assert attention.images_of_event(big, c) == 48 and attention.images_of_event(small, c) == 8
    assert attention.images_of_event("%pad.0 = bf16[48,12,4608,64]{3,2,1,0} pad(%a)", c) is None
    assert not attention.is_kernel_event("%fusion.3 = bf16[48,4301,768]{2,1,0} fusion(%attention)")
    trace = {
        "devices": 1, "window_s": 8.05,
        "programs": {"jit_apply_post(1)": {"runs": 3, "seconds": 2.1},
                     "jit_apply_post(2)": {"runs": 2, "seconds": 0.3}},
        "program_ops": {"jit_apply_post(1)": {big, "%pad.0 = bf16[48,12,4608,64]{3,2,1,0} pad(%a)"},
                        "jit_apply_post(2)": {small}},
        # two whole passes of 48 and a quarter of one; two of 8
        "op_calls": {big: 2 * 12 + 3, small: 2 * 12},
        "program_runs": [
            {"name": "jit_apply_post(1)", "start_s": 0.05, "end_s": 0.3},   # began before the capture
            {"name": "jit_apply_post(2)", "start_s": 1.0, "end_s": 1.15},
            {"name": "jit_apply_post(1)", "start_s": 3.0, "end_s": 3.97},
            {"name": "jit_apply_post(2)", "start_s": 5.0, "end_s": 5.15},
            {"name": "jit_apply_post(1)", "start_s": 7.85, "end_s": 8.05},  # cut by the capture's end
        ],
    }
    slots, seconds = forward.slots_in_trace(c, trace)
    assert slots == pytest.approx(2.25 * 48 + 2 * 8) and seconds == pytest.approx(2.4)
    assert forward.slots_finished(c, trace) == 48 + 8 + 48 + 8
    fill = bench.load_reader("metrics", "batch_fill.bulk").read({
        "trace": trace, "config": c, "kernel": lambda name: bench.load_reader("kernels", name),
        "capture_metrics": [(0.0, {"images_total": 100}), (8.0, {"images_total": 184})]})
    assert fill == pytest.approx(75.0)
