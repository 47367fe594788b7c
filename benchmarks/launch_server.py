#!/usr/bin/env python3
"""The server child's entry: `python -m spotter_tpu.serving.standalone` with
one thread beside it that reads the device's memory.

    python3 benchmarks/launch_server.py <memory file> <the server's own arguments...>

Why it exists: `/metrics` gives the runtime's `peak_bytes_in_use`, which on a
TPU counts the buffers the process holds (weights, inputs, results) and not
the memory its compiled programs reserve for their temporaries; that stands
under `bytes_reserved` / `peak_bytes_reserved` of the same
`device.memory_stats()` (tools/probe_memory.py: YOLOS-base at batch 48 holds
1.36 GB in use and 3.43 GB reserved, and `largest_free_block_bytes` falls by
both; PERF.md section 5). What a chip holds at its peak is the two together,
and only the process that owns the chip can read them.

The thread waits until the harness asks (it touches `<memory file>.go` once
the server is ready, so the thread is never the one that initialises the
backend), then writes both of the runtime's own peaks for every local device
a few times a second. It changes nothing of the server: same module, same
arguments, same environment.
"""

import json
import os
import runpy
import sys
import threading
import time

PERIOD_S = 0.25


def watch(path: str) -> None:
    while not os.path.exists(path + ".go"):
        time.sleep(PERIOD_S)
    import jax

    while True:
        rows = {}
        for device in jax.local_devices():
            stats = device.memory_stats() or {}
            rows[str(device.id)] = {
                key: int(stats.get(key, 0) or 0)
                for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                            "peak_bytes_reserved", "bytes_limit")
            }
        with open(path + ".tmp", "w") as f:
            json.dump(rows, f)
        os.replace(path + ".tmp", path)
        time.sleep(PERIOD_S)


def main() -> None:
    path = sys.argv[1]
    sys.argv = ["spotter_tpu.serving.standalone", *sys.argv[2:]]
    threading.Thread(target=watch, args=(path,), name="bench-memory", daemon=True).start()
    runpy.run_module("spotter_tpu.serving.standalone", run_name="__main__", alter_sys=True)


if __name__ == "__main__":
    main()
