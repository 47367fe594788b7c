"""Shared by the readers of the reduced device trace."""


def idle_pct(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("devices") or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
