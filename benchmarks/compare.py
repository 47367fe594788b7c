"""The comparison that decides `correct`: what the window's own replies say,
against the plain reference's view of the same images.

A reply carries labels and pixel boxes, no scores. Each served detection
(amenity, box) is paired with a reference candidate: a (query, class of that
amenity) entry whose box lies within `PAIR_EPS` of the image's longer side
(widest corner distance), each entry used once (`compare_image` says in what
order). Three numbers are compared, each with its own limit
(limits/<config>.json, set from readings: PERF.md section 2):

- `box_gap_median`: median over paired detections of that corner distance, as
  a share of the longer side. The steadiest: rounding moves every box a little.
- `box_gap_mean`: their mean, a served detection with no candidate near
  enough counted as `UNPAIRED_GAP`. The model picks its 300 queries by an
  internal top-k over 8400 near-tied anchor scores; a flipped pick leaves a
  served box with no counterpart, so a few are unpaired in sound runs; boxes
  thrown far off, or labels the reference cannot give there, read large.
- `flip_share`: the share of detections on which the two sides disagree about
  the threshold by more than `FLIP_TOL` logits: a served detection whose best
  candidate the reference puts below the threshold, or one the reference
  keeps and no served detection pairs with. Near-ties flip under any
  rounding; coarser arithmetic flips more of them.

`flip_gap_max` (the widest such disagreement) and `unpaired_share` are printed
beside them and not judged: sound runs read whole logits on a third of the
seeds (one flipped anchor pick), as the controls do (PERF.md section 2).

A sample that holds fewer than `MIN_COMPARED` served detections, or an image
that came back as an error, is not correct: nothing was shown.
"""

import json
import os

import numpy as np

PAIR_EPS = 0.05
UNPAIRED_GAP = 0.1
FLIP_TOL = 0.05
MIN_COMPARED = 30
HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(config_name: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{config_name}.json")) as f:
        return json.load(f)


def compare_image(served: list, ref: dict) -> dict:
    """served: [(amenity, [x0, y0, x1, y1])] of one image; ref: reference.py's
    record of it. Returns gaps of this image.

    Pairing, per amenity: first the served detections against the entries the
    reference keeps (logit > 0), nearest pairs first, each side used once;
    then each served detection left over against the unused entries near it,
    taking the one the reference rates highest (how far below the threshold
    is the best the reference can offer for this answer?); what the reference
    keeps and nothing paired with is a dropped detection."""
    w, h = ref["size"]
    longer = float(max(w, h))
    box_gaps, flips, unpaired = [], [], 0
    by_amenity: dict[str, list] = {}
    for amenity, box in served:
        by_amenity.setdefault(amenity, []).append(np.asarray(box, np.float32))
    kept_by_amenity: dict[str, list] = {}
    for amenity, q, c, logit in ref["kept"]:
        kept_by_amenity.setdefault(amenity, []).append((q, c, logit))
    for amenity, boxes in by_amenity.items():
        cand = ref["candidates"].get(amenity)
        if cand is None:  # a label the wire contract cannot produce here
            unpaired += len(boxes)
            flips += [float("inf")] * len(boxes)
            continue
        logits, corners = cand
        dist = np.stack([np.abs(corners - b[None, :]).max(-1) / longer for b in boxes])  # (S, Q)
        kept = kept_by_amenity.get(amenity, [])
        pairs = sorted(
            (float(dist[i, q]), i, k) for i in range(len(boxes))
            for k, (q, _, _) in enumerate(kept) if dist[i, q] <= PAIR_EPS)
        done_s, done_k, taken = set(), set(), set()
        for d, i, k in pairs:
            if i in done_s or k in done_k:
                continue
            done_s.add(i), done_k.add(k), taken.add(kept[k][:2])
            box_gaps.append(d)
            flips.append(0.0)
        for i in range(len(boxes)):
            if i in done_s:
                continue
            near = [(float(logits[q, c]), int(q), c) for q in np.nonzero(dist[i] <= PAIR_EPS)[0]
                    for c in range(logits.shape[1]) if (int(q), c) not in taken]
            if not near:
                unpaired += 1
                continue
            logit, q, c = max(near)
            taken.add((q, c))
            box_gaps.append(float(dist[i, q]))
            flips.append(max(0.0, -logit))
        flips += [float(kept[k][2]) for k in range(len(kept)) if k not in done_k
                  and kept[k][:2] not in taken]
    for amenity, kept in kept_by_amenity.items():
        if amenity not in by_amenity:
            flips += [float(logit) for _, _, logit in kept]
    return {"box_gaps": box_gaps, "flips": flips, "unpaired": unpaired,
            "served": len(served)}


def compare(images: list, raw: bool = False) -> dict:
    """images: [(served detections, reference record)]. The numbers compared
    (`raw` adds every paired box gap and every flip, for tools/readings.py)."""
    box_gaps, flips, unpaired, served = [], [], 0, 0
    for dets, ref in images:
        got = compare_image(dets, ref)
        box_gaps += got["box_gaps"]
        flips += got["flips"]
        unpaired += got["unpaired"]
        served += got["served"]
    extra = {"box_gaps": box_gaps, "flips": [f for f in flips if f > 0]} if raw else {}
    with_unpaired = box_gaps + [UNPAIRED_GAP] * unpaired
    return {
        **extra,
        "compared": served,
        "box_gap_median": float(np.median(box_gaps)) if box_gaps else float("inf"),
        "box_gap_mean": float(np.mean(with_unpaired)) if with_unpaired else float("inf"),
        "flip_share": sum(1 for f in flips if f > FLIP_TOL) / served if served else 1.0,
        "flip_gap_max": float(max(flips)) if flips else 0.0,
        "unpaired_share": unpaired / served if served else 1.0,
    }


JUDGED = ("box_gap_median", "box_gap_mean", "flip_share")


def judge(numbers: dict, limits: dict, errors: int) -> tuple[bool, dict]:
    """(correct, {name: [number, limit]}), the names short and plain. A limit
    of None marks a number that is shown and not judged."""
    table = {name: [numbers[name], limits[name]] for name in JUDGED}
    table["compared_min"] = [numbers["compared"], MIN_COMPARED]
    table["error_images"] = [errors, 0]
    ok = (
        all(numbers[name] <= limits[name] for name in JUDGED)
        and numbers["compared"] >= MIN_COMPARED
        and errors == 0
    )
    table["flip_gap_max_shown"] = [numbers["flip_gap_max"], None]
    table["unpaired_share_shown"] = [numbers["unpaired_share"], None]
    return bool(ok), table
