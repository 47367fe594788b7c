"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read. Two halves:

- `load_xplane(path)`: the only part that needs jax (`jax.profiler.
  ProfileData`, which reads the file with nothing but jaxlib; no backend is
  initialised). Returns plain events.
- `reduce(events)`: pure Python over those events; checked on a small
  recorded trace by tests/test_reduce_trace.py.

Events are dicts: {"plane", "line", "name", "start_ns", "dur_ns"}, their times
counted from the capture's start. A device plane is one whose name starts with
"/device:TPU:" (one per chip). On it, the line "XLA Ops" holds one event per
executed HLO operation, and "XLA Modules" one per executed program; busy time
is the union of the op intervals (ops on one core do not overlap, but the
union is what the definition says).
"""

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


def load_xplane(path: str) -> tuple[list, float | None]:
    """(events, the capture's length in ns as the profiler itself recorded it:
    the "Task Environment" plane's profile_stop_time - profile_start_time, or
    None where the file has none)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events, capture_ns = [], None
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats and "profile_stop_time" in stats:
                capture_ns = float(stats["profile_stop_time"] - stats["profile_start_time"])
        for line in plane.lines:
            for ev in line.events:
                events.append({
                    "plane": plane.name, "line": line.name, "name": ev.name,
                    "start_ns": float(ev.start_ns), "dur_ns": float(ev.duration_ns),
                })
    return events, capture_ns


def describe(events: list) -> dict:
    """plane / line -> how many events, and its first few names: for a look
    at a trace by hand."""
    out: dict = {}
    for ev in events:
        row = out.setdefault(f"{ev['plane']} / {ev['line']}", {"events": 0, "names": []})
        row["events"] += 1
        if len(row["names"]) < 12 and ev["name"] not in row["names"]:
            row["names"].append(ev["name"])
    return out


def _union(intervals: list) -> tuple[float, list]:
    """(covered length, gaps between covered stretches) of [(start, end)]."""
    covered, gaps = 0.0, []
    end = None
    for s, e in sorted(intervals):
        if end is None:
            covered, end = e - s, e
        elif s > end:
            gaps.append((end, s))
            covered += e - s
            end = e
        elif e > end:
            covered += e - end
            end = e
    return covered, gaps


def instruction(name: str) -> str:
    """An op event's name is its whole HLO line, "%fusion.53 = bf16[...]
    fusion(...)": the instruction's own name, "fusion.53"."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _base_name(name: str) -> str:
    """"%fusion.123 = ..." -> "fusion": ops are grouped by kind for the breakdown."""
    name = instruction(name)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def reduce(events: list, capture_ns: float | None = None, capture_s: float | None = None) -> dict:
    """busy_s (mean over device planes), window_s, per-device busy, op time
    and calls by name, program (module) runs with the names of the ops that ran
    inside them and each run's start and end in the window, idle gaps with
    what the host did.

    The traced window is the capture, from its start, for the length that
    was asked for (`capture_s`), where the device's events lie inside the
    length the profiler recorded (`capture_ns`): the recorded length holds a
    third of a second in which the session starts and stops and the device is
    not traced any more (a program of a second that "ends" 8.05 s into a
    capture of 8 s, recorded as 8.38 s, was cut: PERF.md, PR 25). Where there
    is no recorded length (or the device's clock does not fit it), the window
    is the span of the device's events, stretched to `capture_s`, so that an
    idle tail counts."""
    planes: dict[str, list] = {}
    modules: dict[str, list] = {}
    host = []
    for ev in events:
        if ev["plane"].startswith(DEVICE_PREFIX):
            if ev["line"] == OPS_LINE:
                planes.setdefault(ev["plane"], []).append(ev)
            elif ev["line"] == MODULES_LINE:
                modules.setdefault(ev["plane"], []).append(ev)
        elif ev["dur_ns"] > 0:
            host.append(ev)
    if not planes:
        return {"devices": 0}
    spans = [e for evs in planes.values() for e in evs]
    start = min(e["start_ns"] for e in spans)
    end = max(e["start_ns"] + e["dur_ns"] for e in spans)
    anchored = capture_ns is not None and start >= 0.0 and end <= capture_ns * 1.02
    if anchored:
        asked = capture_ns if capture_s is None else min(capture_ns, capture_s * 1e9)
        w0, w1 = 0.0, max(asked, end)
    else:
        w0, w1 = start, end
        if capture_s is not None:
            w1 = max(end, start + capture_s * 1e9)
    busy, op_s, op_calls, all_gaps = {}, {}, {}, []
    for plane, evs in planes.items():
        spans = [(max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)) for e in evs]
        spans = [(s, e) for s, e in spans if e > s]
        covered, gaps = _union(spans)
        busy[plane] = covered / 1e9
        edges = [(w0, min(s for s, _ in spans))] if spans else [(w0, w1)]
        if spans:
            edges.append((max(e for _, e in spans), w1))
        all_gaps += [(plane, s, e) for s, e in gaps + edges if e > s]
        for e in evs:
            op_s[e["name"]] = op_s.get(e["name"], 0.0) + e["dur_ns"] / 1e9
            op_calls[e["name"]] = op_calls.get(e["name"], 0) + 1
    n = len(planes)
    by_kind: dict[str, float] = {}
    for name, s in op_s.items():
        kind = _base_name(name)
        by_kind[kind] = by_kind.get(kind, 0.0) + s / n
    device_ops = sorted(([k, v] for k, v in by_kind.items()), key=lambda kv: -kv[1])[:10]
    all_gaps.sort(key=lambda g: g[1] - g[2])
    idle_gaps = []
    for plane, s, e in all_gaps[:10]:
        best, best_overlap = "host: nothing traced", 0.0
        for ev in host:
            overlap = min(e, ev["start_ns"] + ev["dur_ns"]) - max(s, ev["start_ns"])
            if overlap > best_overlap:
                best, best_overlap = f"{ev['line']}: {ev['name']}", overlap
        idle_gaps.append([best[:120], (e - s) / 1e9])
    programs, program_ops, program_runs = {}, {}, []
    for plane, evs in modules.items():
        ops = sorted(planes.get(plane, []), key=lambda e: e["start_ns"])
        starts = [e["start_ns"] for e in ops]
        for e in evs:
            row = programs.setdefault(e["name"], {"runs": 0, "seconds": 0.0})
            row["runs"] += 1 / n
            row["seconds"] += e["dur_ns"] / 1e9 / n
            program_runs.append({"plane": plane, "name": e["name"],
                                 "start_s": (e["start_ns"] - w0) / 1e9,
                                 "end_s": (e["start_ns"] + e["dur_ns"] - w0) / 1e9})
            # over all its runs: a run cut by the capture's edge holds only some
            lo = bisect.bisect_left(starts, e["start_ns"])
            hi = bisect.bisect_right(starts, e["start_ns"] + e["dur_ns"])
            program_ops.setdefault(e["name"], set()).update(o["name"] for o in ops[lo:hi])
    return {
        "devices": n,
        "window_anchored": anchored,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy.values()) / n,
        "busy_per_device_s": busy,
        "op_seconds": {k: v / n for k, v in op_s.items()},
        "op_calls": {k: v / n for k, v in op_calls.items()},
        "programs": programs,
        "program_ops": program_ops,
        "program_runs": program_runs,
        "device_ops": device_ops,
        "idle_gaps": idle_gaps,
    }


def main() -> None:
    """`python benchmarks/reduce_trace.py <trace dir or .xplane.pb>`: a look at
    a trace by hand: planes, lines, the heaviest ops."""
    import json
    import sys

    path = sys.argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    events, capture_ns = load_xplane(path)
    for where, row in describe(events).items():
        print(f"{where}: {row['events']} events, first {row['names'][:3]}")
    out = reduce(events, capture_ns=capture_ns)
    out.pop("op_seconds", None), out.pop("op_calls", None), out.pop("program_ops", None)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
