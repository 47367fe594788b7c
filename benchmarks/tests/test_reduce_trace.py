"""The trace reduction, on made-up events and on a small recorded trace
(tests/data/trace_r101_bulk.json.gz: the device lines of a `POST /profile`
capture on a TPU v5e under r101_bulk's load, PR 25, cut to its first programs)."""

import gzip
import json
import os

import pytest

import reduce_trace

DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return {"plane": plane, "line": line, "name": name, "start_ns": start, "dur_ns": dur}


def test_busy_is_the_union_and_gaps_are_named():
    events = [
        ev("XLA Modules", "jit_forward", 0, 500),
        ev("XLA Ops", "%fusion.1 = bf16[2]{0} fusion(%a)", 0, 200),
        ev("XLA Ops", "fusion.2", 100, 200),   # overlaps fusion.1: union 0..300
        ev("XLA Ops", "custom-call.7", 400, 100),
        ev("XLA Modules", "jit_forward", 1000, 500),
        ev("XLA Ops", "fusion.1", 1000, 500),
        ev("python", "decode_jpeg", 520, 450, plane="/host:CPU"),
        ev("python", "tiny", 300, 50, plane="/host:CPU"),
    ]
    out = reduce_trace.reduce(events)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1500e-9)
    assert out["busy_s"] == pytest.approx((300 + 100 + 500) * 1e-9)
    wider = reduce_trace.reduce(events + [ev("python", "loop", -500, 9000, plane="/host:CPU")])
    assert wider["window_s"] == out["window_s"]  # the host's events are no guide
    longer = reduce_trace.reduce(events, capture_s=4000e-9)
    assert longer["window_s"] == pytest.approx(4000e-9)  # an idle tail counts
    assert longer["busy_s"] == out["busy_s"]
    assert reduce_trace.reduce(events, capture_s=100e-9)["window_s"] == out["window_s"]
    # anchored to the capture: from its start, for the length the profiler recorded
    anchored = reduce_trace.reduce(events, capture_ns=2000.0)
    assert anchored["window_anchored"] and anchored["window_s"] == pytest.approx(2000e-9)
    assert anchored["busy_s"] == out["busy_s"]
    assert anchored["idle_gaps"][0][1] == pytest.approx(500e-9)  # 1500..2000 ties 500..1000
    # a device clock that does not fit the capture: the span rule again
    adrift = reduce_trace.reduce(events, capture_ns=1000.0, capture_s=4000e-9)
    assert not adrift["window_anchored"] and adrift["window_s"] == pytest.approx(4000e-9)
    assert out["program_ops"]["jit_forward"] == {  # over both runs
        "%fusion.1 = bf16[2]{0} fusion(%a)", "fusion.2", "custom-call.7", "fusion.1"}
    assert out["op_seconds"]["fusion.1"] == pytest.approx(500e-9)
    assert out["op_calls"]["fusion.1"] == 1
    assert out["programs"]["jit_forward"]["runs"] == 2
    assert out["programs"]["jit_forward"]["seconds"] == pytest.approx(1000e-9)
    assert out["device_ops"][0][0] == "fusion"  # fusion.1 + fusion.2, by kind
    longest = out["idle_gaps"][0]
    assert longest[1] == pytest.approx(500e-9) and "decode_jpeg" in longest[0]


def test_two_devices_are_averaged():
    events = [ev("XLA Ops", "a", 0, 100), ev("XLA Ops", "a", 0, 300, plane="/device:TPU:1")]
    out = reduce_trace.reduce(events)
    assert out["devices"] == 2 and out["busy_s"] == pytest.approx(200e-9)
    assert out["window_s"] == pytest.approx(300e-9)


def test_no_device_plane_reads_nothing():
    assert reduce_trace.reduce([ev("python", "x", 0, 10, plane="/host:CPU")]) == {"devices": 0}


def test_recorded_trace():
    path = os.path.join(os.path.dirname(__file__), "data", "trace_r101_bulk.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with gzip.open(path, "rt") as f:
        recorded = json.load(f)
    out = reduce_trace.reduce(recorded["events"])
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(recorded["expect"]["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(recorded["expect"]["window_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert sum(r["runs"] for r in out["programs"].values()) == recorded["expect"]["program_runs"]
    # slots come from the kernel events' own shapes: the recorded capture opens
    # inside a program, so its first run is a part of one
    import json as _json

    import run as bench
    from conftest import BENCH

    with open(os.path.join(BENCH, "configs", "rtdetr_v2_r101vd.json")) as f:
        cfg = _json.load(f)
    forward = bench.load_reader("kernels", "rtdetr_forward")
    slots, seconds = forward.slots_in_trace(cfg, out)
    assert 2 * 28 <= slots <= 3 * 28 and (slots * 6) % 28 == 0
    assert seconds == pytest.approx(sum(r["seconds"] for r in out["programs"].values()))
    # the runs that finished inside the capture count whole buckets
    finished = forward.slots_finished(cfg, out)
    assert finished % 4 == 0 and 0 < finished <= 28 * len(out["program_runs"])


def test_the_window_is_the_length_asked_for_and_runs_carry_their_times():
    """The profiler records a longer capture than the device is traced for: a
    program that ends where the asked length ends was cut (PERF.md, PR 25)."""
    events = [
        ev("XLA Modules", "jit_a(1)", 100, 300),
        ev("XLA Ops", "fusion.1", 100, 300),
        ev("XLA Modules", "jit_a(1)", 7000, 1050),  # runs past the 8000 asked for
        ev("XLA Ops", "fusion.1", 7000, 1050),
    ]
    out = reduce_trace.reduce(events, capture_ns=8400.0, capture_s=8000e-9)
    assert out["window_anchored"] and out["window_s"] == pytest.approx(8050e-9)
    runs = out["program_runs"]
    assert [(r["name"], r["start_s"], r["end_s"]) for r in runs] == [
        ("jit_a(1)", pytest.approx(100e-9), pytest.approx(400e-9)),
        ("jit_a(1)", pytest.approx(7000e-9), pytest.approx(8050e-9))]
