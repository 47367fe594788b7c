"""Shared by the readers of what the program says of itself in `/metrics`
(PR 26): the span table `host_spans` (every `obs.span` by name: count,
wall_ms, cpu_ms) and the engine's counters, as deltas over the window. A
program that has no such key (the parent of the PR that added it) reads
None: the metric is left out."""


def span_delta(ctx, name, stat):
    after = ctx["metrics_after"].get("host_spans", {}).get(name)
    if after is None:
        return None
    before = ctx["metrics_before"].get("host_spans", {}).get(name, {})
    return after[stat] - before.get(stat, 0.0)


def counter_delta(ctx, key):
    if key not in ctx["metrics_after"]:
        return None
    return ctx["metrics_after"][key] - ctx["metrics_before"].get(key, 0)


def spans_per_image(ctx, names):
    """Summed wall ms of the spans `names`, per image the engine served."""
    images = counter_delta(ctx, "images_total")
    sums = [span_delta(ctx, name, "wall_ms") for name in names]
    if not images or images <= 0 or any(s is None for s in sums):
        return None
    return sum(sums) / images


def share_of_window(ctx, key):
    """A counter of seconds, as a percentage of the window."""
    seconds = counter_delta(ctx, key)
    if seconds is None or ctx["window"].window_s <= 0:
        return None
    return 100.0 * seconds / ctx["window"].window_s
