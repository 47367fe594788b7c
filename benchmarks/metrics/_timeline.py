"""Shared by the readers of the device's idle time by class (`idle_*.bulk`):
the server's span timeline put on the device trace's clock, and each idle
instant of the traced window charged to the host work nearest the chip that
was open at that moment.

The timeline is `/metrics` `host_timeline` (read after the window, so it holds
the whole capture): every span of the server, `(name, thread, t0, t1, batch)`
in microseconds of the host's monotonic clock, where the server runs a
profiler service (a traced run). A program without it (the parent of the PR
that added it) reads None, and the metrics are left out.

The join. Both sides see the forward programs. On the device, each run's
`[start_s, end_s]` in window seconds (`trace["program_runs"]`); on the host,
each batch's `engine.dispatch` (enqueued), `engine.device_wait` (its start:
the host begins to wait; its end: the result is on the host). Runs and
batches are paired in order, at every shift; a shift's offset (host seconds
= window seconds + offset) is the latest at which no run ends after its
result. A pair is bracketed where the run starts after its dispatch began
and its result came at most `ANCHOR_S` after the later of the run's end and
the host's wait; an anchor is a bracketed run the host waited on. The shift
that brackets the most runs (then has the most anchors) wins; with fewer
than `MIN_ANCHORS` anchors, or a timeline that does not reach back to the
window's start, there is no join and no reading.

The charge. The idle instants are the window less the union of the program
runs. Each goes to the first class that has a span open then:

    gc        python.gc (a collection stops every thread)
    staging   the next program's inputs being made: engine.decode and its
              children, engine.h2d and its children, engine.dispatch
    result    the last result being taken off with no next program queued:
              engine.device_wait, engine.postprocess
    upstream  an image inside the server: batcher.queue_wait, detector.image
              and its children, the /detect handler (app.detect),
              app.serialize
    unseen    none: what the program does not see

as a percentage of the traced window. The five add up to the idle share of
the program runs, which differs from `device_idle.bulk`'s (the union of the
ops) by the gaps between ops inside a run."""

import statistics
import sys

ANCHOR_S = 2e-3
MIN_ANCHORS = 3
CLASSES = ("gc", "staging", "result", "upstream", "unseen")
STAGING = {"engine.decode", "engine.preprocess_map", "engine.preprocess_image",
           "engine.stack_pad", "engine.h2d", "engine.h2d_lock_wait", "engine.put",
           "engine.dispatch"}
RESULT = {"engine.device_wait", "engine.postprocess"}
UPSTREAM = {"batcher.queue_wait", "app.detect", "app.serialize"}

_last = (None, None)  # (the run's ctx, its reading): five readers, one join


def class_of(name):
    if name == "python.gc":
        return "gc"
    if name in STAGING:
        return "staging"
    if name in RESULT:
        return "result"
    if name in UPSTREAM or name.startswith("detector."):
        return "upstream"
    return None


def union(intervals):
    """Sorted, disjoint cover of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        elif e > s:
            out.append([s, e])
    return out


def subtract(base, cut):
    """`base` less `cut`, both sorted and disjoint."""
    out, i = [], 0
    for s, e in base:
        while i < len(cut) and cut[i][1] <= s:
            i += 1
        j = i
        while j < len(cut) and cut[j][0] < e:
            if cut[j][0] > s:
                out.append([s, cut[j][0]])
            s = max(s, cut[j][1])
            j += 1
        if e > s:
            out.append([s, e])
    return out


def length(intervals):
    return sum(e - s for s, e in intervals)


def forward_runs(trace):
    """The program runs of the device plane that ran most, sorted by start,
    and those of the programs that take the time (a program whose mean run is
    under a tenth of the longest's is no forward pass)."""
    by_plane = {}
    for r in trace.get("program_runs", ()):
        by_plane.setdefault(r["plane"], []).append(r)
    if not by_plane:
        return [], []
    runs = sorted(max(by_plane.values(), key=len), key=lambda r: r["start_s"])
    mean = {}
    for r in runs:
        mean.setdefault(r["name"], []).append(r["end_s"] - r["start_s"])
    mean = {name: sum(d) / len(d) for name, d in mean.items()}
    longest = max(mean.values())
    forward = [r for r in runs if mean[r["name"]] >= 0.1 * longest]
    return runs, forward


def host_spans(timeline):
    """[(name, t0_s, t1_s, batch)] from the encoded timeline."""
    names = timeline["names"]
    return [(names[n], t0 / 1e6, t1 / 1e6, batch) for n, _, t0, t1, batch in timeline["entries"]]


def batches(spans):
    """[(dispatch start, wait start, result)] of each batch that has both
    spans, in order of dispatch."""
    dispatch, wait = {}, {}
    for name, t0, t1, batch in spans:
        if batch is None:
            continue
        if name == "engine.dispatch":
            dispatch[batch] = t0
        elif name == "engine.device_wait":
            wait[batch] = (t0, t1)
    return sorted((dispatch[b], *wait[b]) for b in dispatch if b in wait)


def join(runs, host):
    """(offset, anchors' residuals) of the shift that brackets the most runs,
    or None. `runs`: [(start_s, end_s)] in window seconds; `host`: `batches`."""
    best = None
    for shift in range(-len(runs) + 1, len(host)):
        pairs = [(run, host[j + shift]) for j, run in enumerate(runs)
                 if 0 <= j + shift < len(host)]
        if len(pairs) < MIN_ANCHORS:
            continue
        offset = min(result - end for (_, end), (_, _, result) in pairs)
        bracketed, residuals = 0, []
        for (start, end), (dispatched, waited, result) in pairs:
            start, end = start + offset, end + offset
            if start < dispatched - ANCHOR_S or result - max(end, waited) > ANCHOR_S:
                continue
            bracketed += 1
            if waited <= end:
                residuals.append(result - end)
        key = (bracketed, len(residuals))
        if best is None or key > best[0]:
            best = (key, offset, residuals)
    if best is None or len(best[2]) < MIN_ANCHORS:
        return None
    return best[1], best[2]


def classify(trace, timeline):
    """class -> % of the traced window, and what the join found; None where
    there is nothing to join."""
    runs, forward = forward_runs(trace)
    if not forward:
        return None
    spans = host_spans(timeline)
    joined = join([(r["start_s"], r["end_s"]) for r in forward], batches(spans))
    if joined is None:
        return None
    offset, residuals = joined
    window_s = trace["window_s"]
    if timeline["complete_from_us"] / 1e6 > offset or timeline["now_us"] / 1e6 < offset + window_s:
        return None  # the ring does not cover the window
    remaining = subtract([[0.0, window_s]], union((r["start_s"], r["end_s"]) for r in runs))
    idle_s = length(remaining)
    by_class = {c: [] for c in CLASSES[:-1]}
    for name, t0, t1, _ in spans:
        c = class_of(name)
        if c is not None and t1 - offset > 0.0 and t0 - offset < window_s:
            by_class[c].append((t0 - offset, t1 - offset))
    shares = {}
    for c in CLASSES[:-1]:
        left = subtract(remaining, union(by_class[c]))
        shares[c] = 100.0 * (length(remaining) - length(left)) / window_s
        remaining = left
    shares["unseen"] = 100.0 * length(remaining) / window_s
    info = {"offset_s": offset, "runs": len(forward), "anchors": len(residuals),
            "residual_ms": 1e3 * statistics.median(residuals), "idle_pct": 100.0 * idle_s / window_s}
    return shares, info


def share(ctx, cls):
    """The `idle_<cls>.bulk` reading of this run (the join is made once)."""
    global _last
    if _last[0] is not ctx:
        trace, timeline = ctx["trace"], ctx["metrics_after"].get("host_timeline")
        reading = None
        if trace and trace.get("devices") and timeline:
            reading = classify(trace, timeline)
        if reading is not None:
            shares, info = reading
            print("[bench] timeline joined: " + ", ".join(f"{k} {v:.6g}" for k, v in info.items())
                  + "; idle by class (%): " + ", ".join(f"{k} {v:.4g}" for k, v in shares.items()),
                  file=sys.stderr, flush=True)
        _last = (ctx, reading)
    reading = _last[1]
    return None if reading is None else reading[0][cls]
