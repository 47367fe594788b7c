"""Kernels layer: the least time the chip could take for the self-attention
the algorithm needs (operations and bytes from shapes,
kernels/flash_attention.py, whatever implements it), over the summed device
time of the attention kernel's events in the trace."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("op_seconds") or ctx["peaks"] is None:
        return None
    attention = ctx["kernel"]("flash_attention")
    seconds = sum(s for name, s in trace["op_seconds"].items() if attention.is_kernel_event(name))
    if seconds <= 0:
        return None
    slots, _ = ctx["kernel"](ctx["config"]["bench"]["forward"]).slots_in_trace(ctx["config"], trace)
    if not slots:
        return None
    return 100.0 * attention.least_seconds(ctx["config"], ctx["peaks"]) * slots / seconds
