"""Program layer: the whole forward step's share of the chip's bf16 peak while
it runs, from the trace alone: the benchmark's own count of the forward
pass's operations for the configuration's shapes (the configuration's `bench.forward` file under kernels/)
times the bucket slots the traced programs ran (each program's bucket read
from its operations' shapes), over the programs' summed device time ("XLA
Modules") x the peak. A padded slot costs the chip what a real image costs, so
it counts here; how many slots were real is `batch_fill.bulk`."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("programs") or ctx["peaks"] is None:
        return None
    forward = ctx["kernel"](ctx["config"]["bench"]["forward"])
    slots, seconds = forward.slots_in_trace(ctx["config"], trace)
    if seconds <= 0 or slots <= 0:
        return None
    flops = forward.flops_per_image(ctx["config"]) * slots
    return 100.0 * flops / (seconds * ctx["peaks"]["bf16_tflops"] * 1e12)
