"""Bring-up: the server's warm-up, seconds (`setup_phases_s["warmup"]` in
`/metrics`, read at readiness): every bucket of the ladder compiled or
loaded from the compile cache, and run once."""


def read(ctx):
    return ctx["metrics_before"].get("setup_phases_s", {}).get("warmup")
