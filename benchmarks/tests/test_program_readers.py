"""The six readers of what the program says of itself (PR 26: `host_spans`,
`slots_total`, `starved_*_s_total`), each on a recorded pair of `/metrics`
reads; on a program without those keys (the parent of that PR) each reads
None and the metric is left out."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import run as bench
from conftest import BENCH, ROOT

NEW = {
    # reader: (what it reads from the recorded pair, the keys it stands on)
    "decode_parallelism.bulk": (0.41611, ("host_spans",)),
    "stage_copy_ms.bulk": (1.02830, ("host_spans",)),
    "detector_loop_ms.bulk": (16.67242, ("host_spans",)),
    "slot_fill.bulk": (36.02151, ("slots_total",)),
    "starved_staging.bulk": (46.36869, ("starved_staging_s_total",)),
    "starved_upstream.bulk": (36.91524, ("starved_upstream_s_total",)),
}


def recorded(drop=()):
    with open(os.path.join(BENCH, "tests", "data", "metrics_pair_rehearsal.json")) as f:
        pair = json.load(f)
    sides = [{k: v for k, v in pair[side].items() if k not in drop} for side in ("before", "after")]
    return {"metrics_before": sides[0], "metrics_after": sides[1],
            "window": SimpleNamespace(window_s=pair["window_s"])}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_a_recorded_pair(name):
    want, _ = NEW[name]
    assert bench.load_reader("metrics", name).read(recorded()) == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_reads_nothing_from_a_program_without_its_keys(name):
    _, keys = NEW[name]
    assert bench.load_reader("metrics", name).read(recorded(drop=keys)) is None


def test_span_that_never_ran_reads_nothing():
    ctx = recorded()
    for side in ("metrics_before", "metrics_after"):
        ctx[side]["host_spans"].pop("app.serialize")
    assert bench.load_reader("metrics", "detector_loop_ms.bulk").read(ctx) is None
    assert bench.load_reader("metrics", "stage_copy_ms.bulk").read(ctx) is not None


def test_manifest_lists_the_six_for_the_cell():
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in manifest["workloads"] if w["name"] == "yolos_base_bulk")
    listed = {m["name"]: m for m in bench.metrics_of(manifest, cell, "per_layer")}
    assert set(NEW) <= set(listed)
    for name in NEW:
        assert listed[name]["moves"] == "images_per_s"
        assert listed[name]["source"] in ("program_span", "program_counter")
    # what the benchmark had comes first and is as it was
    assert [m["name"] for m in manifest["per_layer"]][:7] == [
        "postprocess_ms.bulk", "boxes_per_image.bulk", "batch_fill.bulk", "stage_host_ms.bulk",
        "step_mfu.bulk", "attention_roofline.bulk", "device_idle.bulk"]


@pytest.mark.slow
def test_rehearsal_runs_all_six():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "yolos_base_bulk",
         "--seed", "2147483998", "--seconds", "6", "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and set(NEW) <= set(line["readers_ran"]), line
