#!/usr/bin/env python3
"""The traced run's collector, a process of its own:

    python3 benchmarks/collect_trace.py <port> <milliseconds> <log dir> <host tracer level>

It asks the server's profiler service (`jax.profiler.start_server`, which the
server starts where `SPOTTER_TPU_PROFILER_PORT` is set) for one trace of the
device, with Python's own tracer off and the host's off too (level 0: the
runtime's host events, 600,000 in half a second, are most of what `POST
/profile` brings back: 17-37 MiB after 10-28 s, PERF.md, PR 25), so that a
capture of seconds comes back small as soon as it ends. The CPU rehearsal
asks for the host's events (level 1): there is no device, and a trace with no
event at all is an error. It prints "ready" once it can start, starts when a
line comes on its standard input, and touches neither jax nor the chip.
"""

import sys


def main() -> int:
    port, milliseconds, log_dir, host_level = sys.argv[1:5]
    from xprof.convert import _pywrap_profiler_plugin as plugin

    options = {"host_tracer_level": int(host_level), "device_tracer_level": 1,
               "python_tracer_level": 0}
    print("ready", flush=True)
    sys.stdin.readline()
    plugin.trace(f"localhost:{port}", log_dir, "", True, int(milliseconds), 3, options)
    return 0


if __name__ == "__main__":
    sys.exit(main())
