"""Drives the rest of a run (the CPU rehearsal: the tiny configuration through
the real server, the same harness code past the look for a chip) with the
timed path broken underneath, and sees `correct` come out false. Of the faults
a served cell can have there is one: an answer altered where it is produced.
The program has that seam itself (`SPOTTER_TPU_FAULTS=sdc=<pct>` perturbs that
share of the engine's answers before they leave it)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT


def rehearse(**server_env):
    """The server child inherits the caller's environment (server.child_env):
    the fault is switched on there, not by an argument of the benchmark."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **server_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "yolos_base_bulk",
         "--seed", "2147483999", "--seconds", "6", "--trace", "0", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sound_run_is_correct_and_altered_answers_are_not():
    sound = rehearse()
    assert sound["rehearsal"] is True and sound["correct"] is True, sound
    assert "metrics" not in sound  # a rehearsal stands under no metric's name
    broken = rehearse(SPOTTER_TPU_FAULTS="sdc=100")
    assert broken["correct"] is False, broken


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", "yolos_base_bulk",
         "--seed", "1", "--seconds", "5", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
