"""Batcher layer: real images over the bucket slots of the batches that
finished, over the capture: the growth of the server's `images_total` (it
grows when a batch finishes) between two reads of `/metrics` that bracket the
capture, over the slots of the traced programs that ended inside it (each
program's bucket from its kernel events' shape; the configuration's
`bench.forward` file under kernels/). The reads bracket the capture to a few
tenths of a second, so a batch that finishes in between is counted on one side
only: with batches of up to 48 a single capture can read a fifth off."""


def read(ctx):
    trace, bracket = ctx["trace"], ctx["capture_metrics"]
    if not trace or not trace.get("program_runs") or len(bracket) != 2:
        return None
    slots = ctx["kernel"](ctx["config"]["bench"]["forward"]).slots_finished(ctx["config"], trace)
    (_, before), (_, after) = bracket
    images = after["images_total"] - before["images_total"]
    if slots <= 0 or images <= 0:
        return None
    return 100.0 * images / slots
