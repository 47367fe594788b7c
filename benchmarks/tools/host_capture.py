#!/usr/bin/env python3
"""Traced runs of run.py with the host's tracer at a level of your choosing
(hand use, on the chip): what a capture that holds the program's own spans
costs, and what it names the idle gaps by.

    chiprun -- python3 benchmarks/tools/host_capture.py <seconds> <level>@<capture seconds>:<workload>:<seed> [...]

run.py's own traced run asks for level 0 (no host events) and 8 s; this
changes those two arguments and nothing else: the same set-up, window,
reduction (`reduce_trace.reduce` names each idle gap after the host event that
overlaps it most) and result line. Each run's line, with the capture's size,
the seconds it took to come back and what the host's planes hold, lands in
chiprun_out/host_capture/. A capture that does not come back inside the
profiler's session deadline (60 s: 8 s at level 1 under yolos_base_bulk, PR
26) fails the run; its row says so.
"""

import contextlib
import io
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402


def collector_at(level: int):
    class AtLevel(bench.srv.TraceCollector):
        def __init__(self, port, seconds, log_dir, host_level=0):
            super().__init__(port, seconds, log_dir, host_level=level)

    return AtLevel


class Tee(io.StringIO):
    def __init__(self, stream):
        super().__init__()
        self.stream = stream

    def write(self, text):
        self.stream.write(text)
        self.stream.flush()
        return super().write(text)


def host_events(path: str) -> dict | None:
    """What the capture's host planes hold: events a line, and the names that
    take the most time (the program's own spans among them); the raw file is
    large and is removed."""
    if not os.path.exists(path):
        return None
    import reduce_trace

    events, _ = reduce_trace.load_xplane(path)
    host = [e for e in events if not e["plane"].startswith(reduce_trace.DEVICE_PREFIX)]
    by_name: dict = {}
    for e in host:
        row = by_name.setdefault(e["name"], [0, 0.0])
        row[0] += 1
        row[1] += e["dur_ns"] / 1e9
    top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:40]
    ours = {n: v for n, v in by_name.items() if n.startswith(("engine.", "detector.", "app."))}
    os.remove(path)
    return {"events": len(host), "lines": reduce_trace.describe(host),
            "top_by_seconds": top, "program_spans": ours}


def main() -> int:
    out = os.path.join(bench.ROOT, "chiprun_out", "host_capture")
    os.makedirs(out, exist_ok=True)
    seconds, code = sys.argv[1], 0
    plain = bench.srv.TraceCollector
    for triple in sys.argv[2:]:
        level, workload, seed = triple.split(":")
        level, capture_s = level.split("@")
        bench.srv.TraceCollector = collector_at(int(level))
        bench.TRACE_SECONDS = float(capture_s)
        raw = os.path.join(out, "raw")
        said, printed = Tee(sys.stderr), Tee(sys.stdout)
        try:
            with contextlib.redirect_stderr(said), contextlib.redirect_stdout(printed):
                code |= bench.main(["--workload", workload, "--seed", seed, "--seconds", seconds,
                                    "--trace", "1"], keep_trace_to=raw)
        finally:
            bench.srv.TraceCollector = plain
        log = said.getvalue()
        row = {"host_tracer_level": int(level), "capture_s": float(capture_s),
               "workload": workload, "seed": int(seed)}
        for key, pattern in (("capture_back_after_s", r"came back after ([0-9.]+) s"),
                             ("trace_mib", r"trace ([0-9.]+) MiB reduced"),
                             ("window_s", r"window ([0-9.]+) s"),
                             ("images", r"by the last reply ([0-9]+)")):
            found = re.search(pattern, log)
            row[key] = float(found.group(1)) if found else None
        if row["images"] and row["window_s"]:
            row["images_per_s"] = row["images"] / row["window_s"]
        lines = printed.getvalue().strip().splitlines()
        row["result"] = json.loads(lines[-1]) if lines else None
        row["host"] = host_events(os.path.join(raw, f"{workload}-{seed}.xplane.pb"))
        with open(os.path.join(out, f"{workload}-{seed}-level{level}.json"), "w") as f:
            json.dump(row, f, indent=1)
    return code


if __name__ == "__main__":
    sys.exit(main())
