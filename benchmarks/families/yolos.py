"""YOLOS (`model_type: yolos`, hustvl/yolos-*): the seeded weights and the
plain reference's model, preprocessing and postprocessing. Imports nothing of
`spotter_tpu`.

Weights ("scaled_normal_vit"): every tensor is drawn with numpy from (seed,
crc32(tensor name)).

- linear and patch-projection weights: N(0, 1 / fan_in); biases 0; norm
  scales 1 and shifts 0;
- [CLS], the detection tokens and both position tables: N(0, `token_std`^2).
  At the published initialisation (0.02) every detection token would be the
  same token to rounding, and the hundred boxes of an image one box;
- the last layer of the class head: N(0, `class_gain`^2 / fan_in), and the
  "no object" class's bias `no_object_bias`. The gain spreads a token's class
  scores far enough apart for a softmax to pass 0.5; the bias sets how many
  of the hundred tokens keep an object: the regime of the answer, which the
  configuration's file records.

Reference: the program serves this family by warping every image to the
checkpoint's own `image_size` (spotter_tpu/models/zoo.py: static shapes, the
position tables as trained) where the published processor resizes by the
shortest edge and pads; the reference follows the served path here, or no
answer could be compared: decode, RGB, warp to `image_size` with PIL's
bilinear filter, 1/255, ImageNet mean/std. Postprocess as published: softmax
over the classes, the last ("no object") dropped, the best class of each
detection token kept where its probability passes 0.5; cxcywh -> corner
pixels of the original image.
"""

import zlib

NAME_TAG = "yolos"
ARCHITECTURE = "YolosForObjectDetection"
MEAN_STD = ((0.485, 0.456, 0.406), (0.229, 0.224, 0.225))
BLOCK = 2  # eager attention holds heads x tokens^2 floats a layer: 0.9 GB an image at 4301 tokens
THRESHOLD = 0.5


def new_model(hf: dict):
    from transformers import YolosConfig, YolosForObjectDetection

    return YolosForObjectDetection(YolosConfig(**hf))


def load_model(checkpoint: str):
    from transformers import YolosForObjectDetection

    return YolosForObjectDetection.from_pretrained(checkpoint, local_files_only=True)


def input_hw(hf: dict) -> tuple:
    return tuple(hf["image_size"])


def seed_weights(model, w: dict) -> None:
    import numpy as np
    import torch

    assert w["scheme"] == "scaled_normal_vit", w["scheme"]
    state = model.state_dict()
    heads = [n for n in state if n.startswith("class_labels_classifier.") and n.endswith(".weight")]
    last = max(heads, key=lambda n: int(n.split(".")[2]))  # ...layers.<i>.weight
    tokens = ("cls_token", "detection_tokens", "position_embeddings")
    for name, tensor in state.items():
        rng = np.random.default_rng([int(w["seed"]), zlib.crc32(name.encode())])
        shape = tuple(tensor.shape)
        if name.rsplit(".", 1)[-1] in tokens or "mid_position_embeddings" in name:
            value = rng.standard_normal(shape) * w["token_std"]
        elif tensor.ndim >= 2:
            fan_in = int(np.prod(shape[1:]))
            gain = w["class_gain"] if name == last else 1.0
            value = rng.standard_normal(shape) * (gain / np.sqrt(fan_in))
        elif name.endswith(".bias"):
            value = np.zeros(shape)
            if name == last[: -len("weight")] + "bias":
                value[-1] = w["no_object_bias"]
        elif name.endswith(".weight"):  # a norm's scale
            value = np.ones(shape)
        else:
            raise ValueError(f"no rule for tensor {name} {shape}")
        tensor.copy_(torch.from_numpy(value.astype(np.float32)))


def threshold_logits(logits):
    """(Q, C) numbers on the scale the threshold cuts at 0, and the (query,
    class) pairs the published postprocess keeps. A token's class passes where
    its softmax probability p is over 0.5, and then it is the token's best
    class: log(p / (1 - p)) = its logit minus the log-sum-exp of the others
    ("no object" among them)."""
    import numpy as np

    z = logits.astype(np.float64)
    top = z.max(-1, keepdims=True)
    e = np.exp(z - top)
    rest = e.sum(-1, keepdims=True) - e
    gap = (z - top) - np.log(np.maximum(rest, 1e-300))
    gap = gap[:, :-1].astype(np.float32)  # "no object" is never an answer
    kept = {(int(q), int(c)) for q, c in zip(*np.nonzero(gap > 0))}
    return gap, kept
