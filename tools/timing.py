"""Shared loop-in-jit timing harness for the TPU tools.

An op cheaper than a dispatch is timed INSIDE one jit: it runs in a
fori_loop whose input is perturbed per iteration (or XLA hoists the
loop-invariant call), and the single dispatch amortizes over the loop.
"""

import time


def timeit_loop(step, x, *, loop=30, iters=3):
    """Mean ms per `step(x)` call. `step` maps the perturbed input to a
    scalar (reduce outputs, so the fetch stays out of the timing)."""
    import jax
    import jax.numpy as jnp

    def run(x0):
        eps = jnp.asarray(1e-6, x0.dtype)

        def body(i, carry):
            return carry + step(x0 + i * eps)

        return jax.lax.fori_loop(0, loop, body, 0.0)

    f = jax.jit(run)
    jax.device_get(f(x))  # compile + settle
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(x)
    jax.device_get(out)
    return (time.perf_counter() - t0) / (iters * loop) * 1e3
