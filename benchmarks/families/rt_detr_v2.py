"""RT-DETRv2 (`model_type: rt_detr_v2`): the seeded weights and the plain
reference's model, preprocessing and postprocessing, as the source publishes
them. Imports nothing of `spotter_tpu`.

Weights ("scaled_normal"): every tensor is drawn with numpy from (seed,
crc32(tensor name)), so it does not depend on the order tensors are visited
in or on torch's generator.

- conv and linear weights: N(0, gain^2 / fan_in), gain = `conv_gain` for
  convolutions (He: a ReLU/SiLU follows), 1 for linear layers; biases 0;
- norm scales 1 and shifts 0, running mean 0 and variance 1, except the last
  norm of every residual branch, whose scale is `residual_gamma`, so that
  the activations' scale does not double at each of the 33 (R101) blocks;
- the class heads (decoder `class_embed.*` and the encoder's
  `enc_score_head`): N(0, class_gain^2 / fan_in), bias `class_bias`. The
  bias sets the regime of the answer, how many (query, class) scores pass the
  server's 0.5 threshold; a trained model puts a handful of boxes on an
  image, and the configuration's file records what its values gave.

Reference: preprocess as the published image processor does it: decode the
JPEG, RGB, warp to 640x640 with PIL's bilinear filter, scale by 1/255, no
mean/std; postprocess as the published one: sigmoid over (query, class), top
300 of the flattened scores, cxcywh -> corner pixels of the original image,
keep score > 0.5.
"""

import zlib

NAME_TAG = "rtdetr"  # the server picks the family by the checkpoint directory's name
ARCHITECTURE = "RTDetrV2ForObjectDetection"
INPUT_HW = (640, 640)
MEAN_STD = None
BLOCK = 8  # images per forward pass of the reference
THRESHOLD = 0.5
TOP_K = 300


def new_model(hf: dict):
    from transformers import RTDetrV2Config, RTDetrV2ForObjectDetection

    return RTDetrV2ForObjectDetection(RTDetrV2Config(**hf))


def load_model(checkpoint: str):
    from transformers import RTDetrV2ForObjectDetection

    return RTDetrV2ForObjectDetection.from_pretrained(checkpoint, local_files_only=True)


def input_hw(hf: dict) -> tuple:
    return INPUT_HW


def seed_weights(model, w: dict) -> None:
    import numpy as np
    import torch

    assert w["scheme"] == "scaled_normal", w["scheme"]
    last_norms = set()
    for name, module in model.named_modules():
        if hasattr(module, "shortcut") and hasattr(module, "layer"):
            branch = list(module.layer.named_modules())
            norms = [n for n, m in branch if n.endswith("normalization")]
            last_norms.add(f"{name}.layer.{norms[-1]}.weight")
    state = model.state_dict()
    # buffers that the model derives from its config (1/n_points scales,
    # anchors) stay as built; a frozen norm keeps its tensors as buffers
    norm_tails = (".weight", ".bias", ".running_mean", ".running_var")
    derived = {n for n, _ in model.named_buffers() if not n.endswith(norm_tails)}
    for name, tensor in state.items():
        if name in derived:
            continue
        rng = np.random.default_rng([int(w["seed"]), zlib.crc32(name.encode())])
        shape = tuple(tensor.shape)
        is_class = "class_embed" in name or "enc_score_head" in name
        if tensor.ndim >= 2:
            fan_in = int(np.prod(shape[1:]))
            gain = w["conv_gain"] if tensor.ndim == 4 else 1.0
            if is_class:
                gain = w["class_gain"]
            value = rng.standard_normal(shape) * (gain / np.sqrt(fan_in))
        elif name.endswith("running_var"):
            value = np.ones(shape)
        elif name.endswith("running_mean"):
            value = np.zeros(shape)
        elif name in last_norms:
            value = np.full(shape, w["residual_gamma"])
        elif name.endswith(".bias"):
            value = np.full(shape, w["class_bias"] if is_class else 0.0)
        elif name.endswith(".weight"):  # a norm's scale
            value = np.ones(shape)
        else:
            raise ValueError(f"no rule for tensor {name} {shape}")
        tensor.copy_(torch.from_numpy(value.astype(np.float32)))
    assert last_norms <= set(state), sorted(last_norms - set(state))[:3]


def threshold_logits(logits):
    """(Q, C) numbers on the scale the threshold cuts at 0, and the (query,
    class) pairs the published postprocess keeps: sigmoid over (query, class),
    the top 300 of the flattened scores, score > 0.5."""
    import numpy as np

    n_cls = logits.shape[1]
    flat = logits.reshape(-1)
    top = np.argpartition(-flat, TOP_K)[:TOP_K]
    kept = {(int(i // n_cls), int(i % n_cls)) for i in top
            if 1.0 / (1.0 + np.exp(-flat[i])) > THRESHOLD}
    return logits, kept
