"""Set-up: from the run's start to the window's start. The checkpoint (first
run of a checkout only), the JPEG pool, spawn to ready (imports, weights,
warm-up of the ladder: compile or cache load) and the warm requests."""


def read(ctx):
    return ctx["setup_s"]
