"""chip_smoke.py's contract off the chip: without an accelerator it exits
non-zero and prints no result; its CPU rehearsal (tiny size, kernels in
interpret mode) runs end to end and a second run hits the compile cache."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def run_smoke(args, cwd, env_changes, timeout):
    env = dict(os.environ)
    # the child and parent of the smoke share a real persistent cache; the
    # test places it, so conftest's "cache off" must not leak in
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    env.pop("XLA_FLAGS", None)
    for key, value in env_changes.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    script = os.path.join(cwd, "chip_smoke.py")
    return subprocess.run(
        [sys.executable, script, *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout,
    )


def results(stdout: str) -> list[dict]:
    """Every JSON object line that claims a result."""
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_fails_without_a_result_when_jax_is_held_to_the_cpu():
    proc = run_smoke([], REPO, {"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert proc.returncode != 0
    assert results(proc.stdout) == []
    assert "needs a TPU" in proc.stderr


def test_fails_without_a_result_alone_in_a_directory(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo: the
    server child cannot start, so the smoke fails — whatever the platform."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = run_smoke([], str(tmp_path), {"JAX_PLATFORMS": None, "PYTHONPATH": None}, timeout=120)
    assert proc.returncode != 0
    assert results(proc.stdout) == []
    assert "during bring-up" in proc.stderr and "No module named" in proc.stderr


@pytest.mark.slow
def test_cpu_rehearsal_end_to_end_and_a_second_run_hits_the_cache(tmp_path):
    placed = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax-cache")}
    hits = []
    for _ in range(2):
        proc = run_smoke(["--rehearse"], REPO, placed, timeout=900)
        assert proc.returncode == 0, proc.stderr[-4000:]
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        assert last == {
            "ok": True,
            "rehearsal": True,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        }
        assert "parity[kernel_f32 vs reference]" in proc.stdout
        found = re.search(r"(\d+) hits, (\d+) misses in this process", proc.stdout)
        hits.append(tuple(int(g) for g in found.groups()))
    assert os.listdir(placed["JAX_COMPILATION_CACHE_DIR"])  # where it was placed
    assert hits[1][0] > 0 and hits[1][1] == 0, hits  # the second run only hits
