"""Operations of one forward pass of a YOLOS configuration, from its shapes:
a hand count of the matrix multiplies the published architecture needs for
one image at the checkpoint's own `image_size` (2 operations per
multiply-add). tests/test_kernels.py holds it against torch's
`FlopCounterMode` over transformers' own model at a small shape.

    tokens T = 1 [CLS] + (H/p)(W/p) patches + detection tokens
    patch projection      2 * patches * (p*p*channels) * d
    per layer             4 projections (q, k, v, out): 4 * 2*T*d*d
                          scores and their use:         2 * 2*T*T*d
                          MLP:                          2 * 2*T*d*I
    heads                 two 3-layer MLPs over the detection tokens

Elementwise work, normalisation and the softmax are not counted, nor the
tokens the served kernel pads the sequence to (4301 -> 4608): the share of
the peak built on this count errs low, never high.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def tokens(cfg: dict) -> tuple[int, int]:
    """(all tokens, patch tokens)."""
    h, w = cfg["image_size"]
    patches = (h // cfg["patch_size"]) * (w // cfg["patch_size"])
    return 1 + patches + cfg["num_detection_tokens"], patches


def flops_per_image(cfg: dict) -> float:
    t, patches = tokens(cfg)
    d, inter, det = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_detection_tokens"]
    patch = 2 * patches * (cfg["patch_size"] ** 2 * cfg["num_channels"]) * d
    layer = 4 * 2 * t * d * d + 2 * 2 * t * t * d + 2 * 2 * t * d * inter
    heads = 2 * det * (2 * d * d + d * (cfg["num_labels"] + 1)) + 2 * det * (2 * d * d + d * 4)
    return float(patch + cfg["num_hidden_layers"] * layer + heads)


def slots_in_trace(cfg: dict, trace: dict) -> tuple[float, float]:
    """(image slots the traced forward passes ran, their summed device
    seconds), per chip, from the trace alone. A forward pass runs the
    attention kernel once per layer, and each kernel event carries its images
    in its own shape (kernels/flash_attention.py): the slots are the kernel
    events' images over the layers, whatever program or bucket they ran in,
    and a pass cut by the capture's edge counts for the part that was seen.
    The seconds are those of the programs ("XLA Modules") that hold such a
    kernel; a program without one is not the forward pass."""
    sys.path.insert(0, HERE)
    import flash_attention as fa

    images = 0.0
    for name, calls in trace.get("op_calls", {}).items():
        per_event = fa.images_of_event(name, cfg)
        if per_event is not None:
            images += per_event * calls
    seconds = sum(
        row["seconds"] for name, row in trace.get("programs", {}).items()
        if any(fa.is_kernel_event(op) for op in trace.get("program_ops", {}).get(name, ())))
    return images / cfg["num_hidden_layers"], seconds


def slots_finished(cfg: dict, trace: dict, edge_s: float = 2e-3) -> float:
    """Image slots of the forward passes that FINISHED inside the traced
    window, per chip: each program run that ends before the capture does
    counts its whole bucket (read from its kernel events' shape, also where
    the capture began in the middle of it), and a run the capture's end cut
    counts nothing. This is what the server's `images_total` can be held
    against: it grows when a batch finishes."""
    sys.path.insert(0, HERE)
    import flash_attention as fa

    bucket = {}
    for name, ops in trace.get("program_ops", {}).items():
        sizes = [n for n in (fa.images_of_event(op, cfg) for op in ops) if n is not None]
        if sizes:
            bucket[name] = max(sizes)
    runs = [r for r in trace.get("program_runs", ()) if r["name"] in bucket
            and r["end_s"] < trace["window_s"] - edge_s]
    return sum(bucket[r["name"]] for r in runs) / max(trace.get("devices", 1), 1)
