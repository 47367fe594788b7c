"""Tests of the benchmark's own code, on the CPU: `python -m pytest benchmarks/tests`.
The slow ones (`-m slow`: a real-size control, whole rehearsal runs with the
timed path broken underneath) are run with `-m slow` or `-m ""`."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: minutes on the CPU")
