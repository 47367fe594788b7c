"""The comparison that decides `correct`, on made-up records, and its control
at a size a test run can hold."""

import json
import os

import numpy as np
import pytest

import compare
from conftest import BENCH


def record(boxes, logits, size=(1000, 500)):
    """One amenity ("chair", one class) with the given candidate boxes/logits."""
    corners = np.asarray(boxes, np.float32)
    lg = np.asarray(logits, np.float32)[:, None]
    kept = [("chair", q, 0, float(v)) for q, v in enumerate(logits) if v > 0]
    return {"size": size, "candidates": {"chair": (lg, corners)}, "kept": kept}


BOXES = [[100, 100, 300, 200], [500, 50, 700, 400], [10, 10, 50, 60]]


def test_identical_answers_read_zero():
    ref = record(BOXES, [1.0, 0.5, -2.0])
    got = compare.compare([([("chair", BOXES[0]), ("chair", BOXES[1])], ref)])
    assert got == {"compared": 2, "box_gap_median": 0.0, "box_gap_mean": 0.0,
                   "flip_share": 0.0, "flip_gap_max": 0.0, "unpaired_share": 0.0}


def test_a_moved_box_reads_its_distance():
    ref = record(BOXES, [1.0, 0.5, -2.0])
    moved = [110, 100, 300, 200]  # 10 px of a 1000 px side
    got = compare.compare([([("chair", moved), ("chair", BOXES[1])], ref)])
    assert got["box_gap_mean"] == pytest.approx(0.005)
    assert got["box_gap_median"] == pytest.approx(0.005)
    far = [400, 100, 600, 200]  # no candidate within 5 %
    got = compare.compare([([("chair", far), ("chair", BOXES[1])], ref)])
    assert got["unpaired_share"] == 0.5 and got["flip_gap_max"] == 1.0  # and one kept is missing
    assert got["box_gap_mean"] == pytest.approx(compare.UNPAIRED_GAP / 2)
    assert got["flip_share"] == 0.5


def test_a_near_tie_reads_small_and_a_wrong_label_reads_large():
    ref = record(BOXES, [1.0, 0.5, -0.03])
    got = compare.compare([([("chair", b) for b in BOXES], ref)])
    assert got["flip_gap_max"] == pytest.approx(0.03) and got["unpaired_share"] == 0.0
    assert got["flip_share"] == 0.0  # a near-tie is within FLIP_TOL
    got = compare.compare([([("chair", BOXES[0])], ref)])  # a kept one dropped
    assert got["flip_gap_max"] == pytest.approx(0.5) and got["flip_share"] == 1.0
    got = compare.compare([([("sofa", BOXES[0])], ref)])  # a label the reference lacks
    assert got["unpaired_share"] == 1.0


def test_judge_needs_something_compared():
    limits = {"box_gap_median": 0.004, "box_gap_mean": 0.01, "flip_share": 0.1}
    numbers = {"compared": 0, "box_gap_median": float("inf"), "box_gap_mean": float("inf"),
               "flip_share": 1.0, "flip_gap_max": 0.0, "unpaired_share": 1.0}
    assert compare.judge(numbers, limits, 0)[0] is False
    good = {"compared": 100, "box_gap_median": 0.001, "box_gap_mean": 0.002, "flip_share": 0.01,
            "flip_gap_max": 1.1, "unpaired_share": 0.0}
    assert compare.judge(good, limits, 0)[0] is True
    assert compare.judge(good, limits, 1)[0] is False


@pytest.mark.slow
@pytest.mark.parametrize("name,images", [("rtdetr_v2_r50vd", 8), ("yolos_base", 4)])
def test_control_fp8_is_not_correct(tmp_path, name, images):
    """The control: the reference put in the program's place and computed in
    float8 e4m3 must fail one of the configuration's limits, and the float32
    reference in its own place must pass them all. Published widths, a few
    images (a run compares 12 of YOLOS-base, 32 of R50)."""
    import server as srv
    import weights
    from reference import Reference, wire

    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    checkpoint = weights.ensure_checkpoint(cfg, str(tmp_path))
    with open(os.path.join(BENCH, "traffic", "bulk_closed.json")) as f:
        sizes = json.load(f)["pool"]["sizes"]
    jpegs = [v[0] for v in srv.make_images(12345, sizes, 1).values()][:images]
    records = Reference(checkpoint).images(jpegs)
    low = Reference(checkpoint, control="fp8").images(jpegs)
    limits = compare.load_limits(cfg["name"])
    own = compare.compare([(wire(r), r) for r in records])
    assert compare.judge({**own, "compared": 100}, limits, 0)[0] is True
    control = compare.compare([(wire(l), r) for l, r in zip(low, records)])
    assert compare.judge({**control, "compared": 100}, limits, 0)[0] is False, control


def test_yolos_threshold_scale_is_the_softmaxs_half():
    """families/yolos.py: a token's class passes where its softmax probability
    is over 0.5; "no object" is never an answer."""
    import weights

    fam = weights.family("yolos")
    logits = np.asarray([[3.0, 0.0, 0.0, 0.0],     # p = e^3 / (e^3 + 3) = 0.87: kept
                         [1.0, 0.9, 0.0, 0.0],     # best class at 0.36: not kept
                         [0.0, 0.0, 0.0, 5.0]],    # "no object" wins: nothing
                        np.float32)
    gap, kept = fam.threshold_logits(logits)
    assert gap.shape == (3, 3) and kept == {(0, 0)}
    p = np.exp(3.0) / (np.exp(3.0) + 3.0)
    assert gap[0, 0] == pytest.approx(np.log(p / (1 - p)), rel=1e-5)
    assert (gap[1] < 0).all() and (gap[2] < 0).all()


def test_the_sample_takes_some_images_of_several_replies():
    import run as bench
    import traffic

    def reply(sender, ordinal, n):
        r = traffic.Request("bulk", sender, ordinal, n, keep=True)
        r.urls = [f"http://x/img{sender}_{ordinal}_{i}.jpg" for i in range(n)]
        r.status, r.body = 200, b"{}"
        return r

    window = traffic.WindowResult(0.0, 1.0, 2.0, [reply(s, k, 8 + 4 * k) for s in range(3)
                                                  for k in range(5)])
    whole = bench.sample_replies(window, 7, 32)
    assert [len(p) for _, p in whole][0] == 24 and sum(len(p) for _, p in whole) <= 32
    assert all(p == list(range(len(r.urls))) for r, p in whole)
    some = bench.sample_replies(window, 7, 12, per_reply=4)
    assert [len(p) for _, p in some] == [4, 4, 4] and len(some[0][0].urls) == 24
    assert some == bench.sample_replies(window, 7, 12, per_reply=4)  # drawn from the seed
    assert some != bench.sample_replies(window, 8, 12, per_reply=4)
