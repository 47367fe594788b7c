"""tools/bench_compare.py (ISSUE 10): the bench regression gate and its
schema guard — a malformed bench record must fail loudly (exit 2), never
silently pass the gate."""

import json

import pytest

from tools.bench_compare import (
    compare,
    extract_record,
    load_record,
    main,
    validate_record,
)

GOOD = {
    "metric": "model images/sec/chip",
    "value": 264.2,
    "unit": "images/sec",
    "vs_baseline": 0.528,
}


def _write(tmp_path, name, payload):
    path = tmp_path / name
    path.write_text(json.dumps(payload))
    return str(path)


def test_extract_unwraps_the_evidence_shape():
    wrapper = {"n": 5, "cmd": "python bench.py", "rc": 0, "parsed": GOOD}
    assert extract_record(wrapper) == GOOD
    assert extract_record(GOOD) == GOOD
    assert extract_record([1, 2]) is None


def test_validate_accepts_good_and_null_baseline():
    assert validate_record(GOOD, "x") == []
    ok_null = dict(GOOD, vs_baseline=None)
    assert validate_record(ok_null, "x") == []


@pytest.mark.parametrize(
    "mutation, fragment",
    [
        ({"metric": 5}, "key 'metric'"),
        ({"value": "264"}, "key 'value'"),
        ({"value": float("nan")}, "key 'value'"),
        ({"value": True}, "key 'value'"),
        ({"unit": ""}, "key 'unit'"),
        ({"vs_baseline": "x"}, "key 'vs_baseline'"),
    ],
)
def test_validate_rejects_badly_typed_fields(mutation, fragment):
    record = dict(GOOD, **mutation)
    problems = validate_record(record, "BENCH_bad.json")
    assert problems and any(fragment in p for p in problems)
    assert all(p.startswith("BENCH_bad.json") for p in problems)


def test_validate_reports_every_missing_key():
    problems = validate_record({}, "x")
    assert len(problems) == 4  # one readable line per missing field


def test_compare_regression_gate():
    old = dict(GOOD, value=100.0)
    flat = compare(old, dict(GOOD, value=96.0), threshold_pct=5.0)
    assert not flat["regression"]  # -4% inside the 5% tolerance
    reg = compare(old, dict(GOOD, value=90.0), threshold_pct=5.0)
    assert reg["regression"] and reg["delta_pct"] == -10.0
    gain = compare(old, dict(GOOD, value=120.0), threshold_pct=5.0)
    assert not gain["regression"]


def test_compare_lower_is_better_flips_direction():
    old = dict(GOOD, value=100.0, unit="ms")
    worse = compare(
        old, dict(GOOD, value=110.0, unit="ms"), 5.0, lower_is_better=True
    )
    assert worse["regression"]
    better = compare(
        old, dict(GOOD, value=90.0, unit="ms"), 5.0, lower_is_better=True
    )
    assert not better["regression"]


def test_main_exit_codes(tmp_path, capsys):
    old = _write(tmp_path, "old.json", dict(GOOD, value=100.0))
    ok = _write(tmp_path, "ok.json", dict(GOOD, value=101.0))
    reg = _write(tmp_path, "reg.json", dict(GOOD, value=90.0))
    bad = _write(tmp_path, "bad.json", {"metric": "m", "unit": "images/sec"})
    other_unit = _write(tmp_path, "unit.json", dict(GOOD, unit="ms"))

    assert main([old, ok]) == 0
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["regression"] is False

    assert main([old, reg]) == 1  # the synthetic 10% regression gate
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["regression"] is True and verdict["delta_pct"] == -10.0

    assert main([old, bad]) == 2  # schema guard: loud, not a silent pass
    out = capsys.readouterr()
    payload = json.loads(out.out.strip().splitlines()[-1])
    assert payload["error"] == "schema"
    assert any("missing key 'value'" in p for p in payload["problems"])

    assert main([old, other_unit]) == 2  # apples-to-oranges refused
    assert "unit mismatch" in capsys.readouterr().err


def test_main_handles_unreadable_file(tmp_path, capsys):
    old = _write(tmp_path, "old.json", GOOD)
    garbage = tmp_path / "garbage.json"
    garbage.write_text("{not json")
    assert main([old, str(garbage)]) == 2
    assert "unreadable" in capsys.readouterr().err


def test_load_record_roundtrip_on_evidence_wrapper(tmp_path):
    # the driver's evidence wrapper ({"n", "cmd", "rc", "parsed"}) around a
    # bench record must satisfy the guard; the test writes its own wrapper
    # (the repo commits no bench records any more)
    wrapper = {"n": 5, "cmd": "python bench.py", "rc": 0, "parsed": GOOD}
    path = _write(tmp_path, "BENCH_wrapped.json", wrapper)
    record, problems = load_record(path)
    assert problems == [], problems
    assert record == GOOD and record["unit"] == "images/sec"
