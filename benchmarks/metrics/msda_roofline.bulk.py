"""Kernels layer: the least time the chip could take for the multi-scale
deformable sampling the algorithm needs (operations and bytes from shapes,
kernels/msda.py, whatever implements it), over the summed device time of the
sampling kernel's events in the trace."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("op_seconds") or ctx["peaks"] is None:
        return None
    msda = ctx["kernel"]("msda")
    seconds = sum(s for name, s in trace["op_seconds"].items() if msda.is_kernel_event(name))
    if seconds <= 0:
        return None
    forward = ctx["kernel"](ctx["config"]["bench"]["forward"])
    slots, _ = forward.slots_in_trace(ctx["config"], trace)
    if not slots:
        return None
    least = msda.least_seconds(ctx["config"], ctx["peaks"]) * slots
    return 100.0 * least / seconds
