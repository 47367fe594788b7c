#!/usr/bin/env python3
"""A cell's pre-flight on the chip (hand use): traced runs of run.py, one
after another in one call, with the raw trace, `/metrics` and the server's
log kept under chiprun_out/preflight/ for a look by hand.

    chiprun -- python3 benchmarks/tools/preflight.py <seconds> <workload>:<seed> [...]
"""

import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402


def main() -> int:
    out = os.path.join(bench.ROOT, "chiprun_out", "preflight")
    seconds, code = sys.argv[1], 0
    for pair in sys.argv[2:]:
        workload, seed = pair.split(":")
        code |= bench.main(["--workload", workload, "--seed", seed, "--seconds", seconds,
                            "--trace", "1"], keep_trace_to=out)
        log = os.path.join(bench.WORK_DIR, "logs", f"server-{workload}.log")
        if os.path.exists(log):
            os.makedirs(out, exist_ok=True)
            with open(log, errors="replace") as f:
                lines = [ln for ln in f if "aiohttp.access" not in ln and "HTTP Request" not in ln]
            with open(os.path.join(out, f"server-{workload}.log"), "w") as f:
                f.writelines(lines[-400:])
    return code


if __name__ == "__main__":
    sys.exit(main())
