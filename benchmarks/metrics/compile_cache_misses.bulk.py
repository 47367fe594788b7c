"""Bring-up: the programs the server compiled and wrote to the persistent
compile cache before it was ready (`compile_cache_misses_total` in `/metrics`,
read at readiness): the ladder's buckets and whatever else set-up compiles
on an empty cache, 0 where every program was loaded from it. A program
without the counter (the parent of the PR that added it) reads None."""


def read(ctx):
    return ctx["metrics_before"].get("compile_cache_misses_total")
