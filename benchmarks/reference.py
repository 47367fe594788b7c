"""The plain reference of the served path, independent of the program: it
imports nothing of `spotter_tpu` and takes nothing the program made.

- model: transformers' own torch model of the configuration's family (the
  published implementation its source ships), float32 on the host's CPU,
  loaded from the checkpoint the benchmark wrote (weights.py);
- preprocess and postprocess as `families/<model_type>.py` says of its
  family; then the wire contract's amenity filter (data/amenities.json).

It runs on the CPU in blocks of the family's `BLOCK` images, after the window
has closed and the server has exited, so it touches neither the chip nor its
memory peak.

The control (`control="fp8"`) is this reference computed in the nearest
precision below the configuration's bfloat16: every convolution and linear
layer sees its input and its weight rounded to float8 e4m3 (per-tensor
scale to the type's range), the step a later PR would be tempted by.
"""

import io
import json
import os

import weights

HERE = os.path.dirname(os.path.abspath(__file__))


def amenity_groups(labels: list) -> dict:
    """amenity name -> class ids that map to it."""
    with open(os.path.join(HERE, "data", "amenities.json")) as f:
        mapping = json.load(f)
    groups: dict[str, list[int]] = {}
    for idx, label in enumerate(labels):
        if label in mapping:
            groups.setdefault(mapping[label], []).append(idx)
    return groups


def _fp8_round(t):
    import torch

    amax = t.abs().amax().clamp(min=1e-12)
    scale = 448.0 / amax  # e4m3's largest finite value
    return (t * scale).to(torch.float8_e4m3fn).to(torch.float32) / scale


def _install_fp8(model) -> None:
    import torch

    for module in model.modules():
        if isinstance(module, (torch.nn.Conv2d, torch.nn.Linear)):
            module.weight.data = _fp8_round(module.weight.data)
            module.register_forward_pre_hook(lambda m, args: (_fp8_round(args[0]),) + args[1:])


class Reference:
    def __init__(self, checkpoint: str, control: str | None = None, threads: int | None = None):
        import torch

        if threads:
            torch.set_num_threads(threads)
        self.torch = torch
        with open(os.path.join(checkpoint, "config.json")) as f:
            self.hf = json.load(f)
        self.family = weights.family(self.hf["model_type"])
        self.model = self.family.load_model(checkpoint).eval().float()
        if control == "fp8":
            _install_fp8(self.model)
        elif control is not None:
            raise ValueError(f"unknown control {control!r}")
        self.input_hw = self.family.input_hw(self.hf)
        labels = [self.hf["id2label"][str(i)] for i in range(len(self.hf["id2label"]))]
        self.groups = amenity_groups(labels)

    def preprocess(self, jpeg: bytes):
        import numpy as np
        from PIL import Image

        image = Image.open(io.BytesIO(jpeg)).convert("RGB")
        size = image.size  # (w, h)
        resized = image.resize((self.input_hw[1], self.input_hw[0]), Image.BILINEAR)
        x = np.asarray(resized, dtype=np.float32) / 255.0
        if self.family.MEAN_STD is not None:
            mean, std = (np.asarray(v, np.float32) for v in self.family.MEAN_STD)
            x = (x - mean) / std
        return x, size

    def images(self, jpegs: list) -> list:
        """Per image: {"size": (w, h), "candidates": {amenity: (logit[Q, its
        classes], boxes_px[Q, 4])}, "kept": [(amenity, query, class index
        within the amenity, logit)]}. A logit is on the scale the server's
        threshold cuts at 0 (the family's `threshold_logits`). A candidate is
        what the reference says of one query under one class of an amenity,
        whether or not it passes the threshold: the served answer is compared
        against these."""
        import numpy as np

        torch = self.torch
        out = []
        block = self.family.BLOCK
        for start in range(0, len(jpegs), block):
            pre = [self.preprocess(j) for j in jpegs[start:start + block]]
            x = torch.from_numpy(np.stack([p[0] for p in pre])).permute(0, 3, 1, 2)
            with torch.no_grad():
                res = self.model(pixel_values=x)
            logits = res.logits.float().numpy()
            boxes = res.pred_boxes.float().numpy()
            for i, (_, (w, h)) in enumerate(pre):
                out.append(self._postprocess(logits[i], boxes[i], w, h))
        return out

    def _postprocess(self, logits, boxes, w: int, h: int) -> dict:
        import numpy as np

        cx, cy, bw, bh = boxes.T
        scale = np.asarray([w, h, w, h], np.float32)
        corners = np.stack([cx - bw / 2, cy - bh / 2, cx + bw / 2, cy + bh / 2], -1) * scale
        logits, kept_pairs = self.family.threshold_logits(logits)
        candidates, kept = {}, []
        for amenity, classes in self.groups.items():
            candidates[amenity] = (logits[:, classes], corners)
            for q, c in kept_pairs:
                if c in classes:
                    kept.append((amenity, q, classes.index(c), float(logits[q, c])))
        return {"size": (w, h), "candidates": candidates, "kept": kept}


def wire(record: dict) -> list:
    """What a record says crosses the wire: [(amenity, [x0, y0, x1, y1])], the
    form a reply has. The control's answers are put in the program's place
    through this."""
    out = []
    for amenity, q, _, _ in record["kept"]:
        _, corners = record["candidates"][amenity]
        out.append((amenity, [float(v) for v in corners[q]]))
    return out
