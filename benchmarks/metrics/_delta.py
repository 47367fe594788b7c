"""Shared by the readers of `/metrics` deltas over the window."""


def images(ctx):
    return ctx["metrics_after"]["images_total"] - ctx["metrics_before"]["images_total"]


def stage_sum_ms(ctx, stage):
    after = ctx["metrics_after"]["stage_ms_histogram"].get(stage)
    if after is None:
        return None
    before = ctx["metrics_before"]["stage_ms_histogram"].get(stage, {"sum": 0.0})
    return after["sum"] - before["sum"]


def stage_per_image(ctx, stages):
    n = images(ctx)
    sums = [stage_sum_ms(ctx, s) for s in stages]
    if n <= 0 or any(s is None for s in sums):
        return None
    return sum(sums) / n
