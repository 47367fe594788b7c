"""The manifest's names and units, and that every entry loads by name: a
later PR adds a configuration, a mix, a metric and a cell by adding files and
entries, editing nothing that is there."""

import json
import os
import re

import run as bench
import traffic
import weights
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in m["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for metric in m["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["moves"] in e2e
    for cell in m["workloads"]:
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])


def test_every_cell_reports_enough():
    m = manifest()
    for cell in m["workloads"]:
        e2e = {x["name"] for x in bench.metrics_of(m, cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        layer = bench.metrics_of(m, cell, "per_layer")
        assert layer, cell["name"]
        for metric in layer:  # it moves a metric this cell reports
            assert metric["moves"] in e2e, (cell["name"], metric["name"])


def test_every_entry_loads_by_name():
    m = manifest()
    used = {cell["config"] for cell in m["workloads"]}
    for config in m["configs"]:
        assert config["name"] in used
        assert config["file"].startswith("benchmarks/")
        cfg = bench.load_json(os.path.join(ROOT, config["file"]))
        assert cfg["name"] == config["name"] and cfg["source"] == config["source"]
        assert cfg["reduced"] == config["reduced"]
        hf = weights.hf_config_dict(cfg)
        family = weights.family(cfg["model_type"])  # families/<model_type>.py, by name
        assert hf["architectures"] == [family.ARCHITECTURE]
        assert len(hf["id2label"]) == hf["num_labels"] == len(weights.labels_of(cfg))
        # the server picks the family by the checkpoint directory's name
        assert family.NAME_TAG
        assert os.path.exists(os.path.join(BENCH, "configs", f"{cfg['serve']['rehearse_config']}.json"))
        forward = bench.load_reader("kernels", cfg["bench"]["forward"])
        assert forward.flops_per_image and forward.slots_in_trace
        assert set(bench.compare.load_limits(config["name"])) == set(bench.compare.JUDGED)
    for cell in m["workloads"]:
        _, cfg, mix = bench.load_cell(m, cell["name"])
        plan = traffic.Plan(mix, ["a.jpg", "b.jpg"], "http://x", 1, float(m["run_seconds"]))
        assert plan.closed
    for metric in m["end_to_end"] + m["per_layer"]:
        assert callable(bench.load_reader("metrics", metric["name"]).read)
    for kernel in os.listdir(os.path.join(BENCH, "kernels")):
        if kernel.endswith(".py"):
            bench.load_reader("kernels", kernel[:-3])
    assert "TPU v5 lite" in bench.load_json(os.path.join(BENCH, "peaks.json"))


def test_unknown_device_kind_is_an_error():
    import pytest

    with pytest.raises(bench.srv.BenchFailure):
        bench.peaks_for("TPU v9 imaginary")
