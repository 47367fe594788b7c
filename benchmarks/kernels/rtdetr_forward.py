"""Operations of one forward pass of an RT-DETR(v2) configuration, from its
shapes. The count is the matrix-multiply and convolution work (2 operations
per multiply-add) that the published architecture needs for one 640x640
image: transformers' own torch model of the configuration (default weights;
only its shapes matter) runs one zero image on the CPU under torch's
`FlopCounterMode`, a second or two (the model's own shape checks read values,
so the `meta` device cannot be used). Elementwise work, normalisation and the bilinear
sampling itself (kernels/msda.py) are not counted, so the share of the peak
that is built on this count errs low, never high.
"""

import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
INPUT_HW = (640, 640)


@functools.lru_cache(maxsize=None)
def _flops(config_json: str) -> float:
    import torch
    from torch.utils.flop_counter import FlopCounterMode
    from transformers import RTDetrV2Config, RTDetrV2ForObjectDetection

    sys.path.insert(0, os.path.dirname(HERE))
    import weights

    hf = RTDetrV2Config(**weights.hf_config_dict(json.loads(config_json)))
    model = RTDetrV2ForObjectDetection(hf).eval()
    x = torch.zeros(1, 3, *INPUT_HW)
    with torch.no_grad(), FlopCounterMode(display=False) as counter:
        model(pixel_values=x)
    return float(counter.get_total_flops())


def flops_per_image(cfg: dict) -> float:
    return _flops(json.dumps(cfg, sort_keys=True))


def slots_in_trace(cfg: dict, trace: dict) -> tuple[float, float]:
    """(image slots the traced forward passes ran, their summed device
    seconds), per chip, from the trace alone. A forward pass runs the sampling
    kernel once per decoder layer, and each kernel event carries images x heads
    as its leading dimension (kernels/msda.py): the slots are the kernel
    events' images over the decoder's layers, whatever program or bucket they
    ran in, and a pass cut by the capture's edge counts for the part that was
    seen. The seconds are those of the programs ("XLA Modules") that hold such
    a kernel; a program without one is not the forward pass."""
    sys.path.insert(0, HERE)
    import msda

    images = 0.0
    for name, calls in trace.get("op_calls", {}).items():
        per_event = msda.images_of_event(name, cfg)
        if per_event is not None:
            images += per_event * calls
    seconds = sum(
        row["seconds"] for name, row in trace.get("programs", {}).items()
        if any(msda.is_kernel_event(op) for op in trace.get("program_ops", {}).get(name, ())))
    return images / cfg["decoder_layers"], seconds


def slots_finished(cfg: dict, trace: dict, edge_s: float = 2e-3) -> float:
    """Image slots of the forward passes that FINISHED inside the traced
    window, per chip: each program run that ends before the capture does
    counts its whole bucket (read from its kernel events' shape, also where
    the capture began in the middle of it), and a run the capture's end cut
    counts nothing. This is what the server's `images_total` can be held
    against: it grows when a batch finishes."""
    sys.path.insert(0, HERE)
    import msda as fa

    bucket = {}
    for name, ops in trace.get("program_ops", {}).items():
        sizes = [n for n in (fa.images_of_event(op, cfg) for op in ops) if n is not None]
        if sizes:
            bucket[name] = max(sizes)
    runs = [r for r in trace.get("program_runs", ()) if r["name"] in bucket
            and r["end_s"] < trace["window_s"] - edge_s]
    return sum(bucket[r["name"]] for r in runs) / max(trace.get("devices", 1), 1)
