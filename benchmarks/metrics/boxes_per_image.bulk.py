"""Detector layer: detections on the wire per image answered, over every reply
of the window. The regime of the answer, so that a drift is seen: the host
draws and encodes per box."""


def read(ctx):
    window = ctx["window"]
    done = window.done()
    images = sum(len(r.urls) for r in done)
    return sum(r.n_detections for r in done) / images if images else None
