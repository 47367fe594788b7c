"""All images of the requests that were sent in the window and answered in
full, over the window: from the first send to the last reply (traffic.py). A
failed or refused request's images do not count; its time does."""


def read(ctx):
    window = ctx["window"]
    return sum(len(r.urls) for r in window.done()) / window.window_s
