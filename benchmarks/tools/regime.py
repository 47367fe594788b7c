#!/usr/bin/env python3
"""The regime of a configuration's answer: how many detections the plain
reference puts on the wire per image of the benchmark's own pool, and how that
count moves with a constant added to every score on the threshold's scale (for
a sigmoid head that is the class heads' bias, so one forward pass serves every
bias; for a softmax head it shows how steep the cliff is). CPU, in the sandbox:

    python3 benchmarks/tools/regime.py rtdetr_v2_r101vd [--images 16] [--seed 5]

The configuration's file records under `regime` what its `weights` gave. A
trained model puts a handful of boxes on an image; seeded weights make one or
two classes win every query, so the count is a cliff: pick the weights'
parameters where the mean is a handful and most images get one.
"""

import argparse
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import server as srv  # noqa: E402
import weights  # noqa: E402
from reference import Reference  # noqa: E402


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("config")
    parser.add_argument("--images", type=int, default=16)
    parser.add_argument("--seed", type=int, default=5)
    args = parser.parse_args()
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", "bulk_closed.json")) as f:
        sizes = json.load(f)["pool"]["sizes"]
    pool = srv.make_images(args.seed, sizes, -(-args.images // len(sizes)))
    jpegs = [v[0] for v in pool.values()][: args.images]
    with tempfile.TemporaryDirectory() as tmp:
        ref = Reference(weights.ensure_checkpoint(cfg, tmp))
        records = ref.images(jpegs)
    n_amenity = sum(len(v) for v in ref.groups.values())
    for shift in np.arange(-0.3, 0.31, 0.05):
        counts = []
        for rec in records:
            # every class's scores are not kept; the amenity ones suffice while
            # the family's own cap on kept pairs (RT-DETR: 300) is not reached
            passing = sum(int((lg + shift > 0).sum()) for lg, _ in rec["candidates"].values())
            counts.append(passing)
        mark = "  <- the file's weights" if abs(shift) < 1e-9 else ""
        print(f"shift {shift:+.2f}: mean {np.mean(counts):6.2f} per image, "
              f"{sum(c > 0 for c in counts)}/{len(counts)} images with one, "
              f"max {max(counts)}{mark}")
    print(f"({n_amenity} of {cfg['num_labels']} classes are amenities)")


if __name__ == "__main__":
    main()
