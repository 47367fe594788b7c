"""One general traffic generator. A mix is a data file (`traffic/<mix>.json`)
of streams; a later PR adds a mix by adding a file, not code.

    {"pool": {"sizes": [[w, h], ...], "per_size": 12},
     "streams": [
       {"name": "bulk", "loop": "closed", "clients": 8, "images": [8, 24]}]}

- `loop: closed`: `clients` workers, each sends its next request when the
  reply to the last has come (callers that wait for their reply). It is the
  only loop there is: an open loop comes with the first cell that needs one.
- `images: [lo, hi]`: image URLs per request, spread evenly over lo..hi
  (`_sizes`). Every URL carries its own query string, so nothing can coalesce
  or hit a cache.

Every seed gives the same multiset of request sizes, in another order, and
walks the pool from another start: the seed changes the order of the work,
not its amount.

The window: clients send for `seconds`; then nothing more is sent and every
reply still due is waited for. The window runs from the first send to the
last reply, so all the work that was sent counts and all the time it took
(`WindowResult.window_s`). A window that ended at the close of sending left
the eight requests in flight out of the count, about a tenth of the images:
a rate 2-8 % low (PERF.md, PR 25).
"""

import http.client
import json
import threading
import time
from dataclasses import dataclass, field
from urllib.parse import urlsplit

import numpy as np

REQUEST_TIMEOUT_S = 120.0
KEEP_ONE_IN = 3  # a reply is kept for the comparison with this chance, all through the window
CYCLE = 8  # a sender's request sizes repeat in cycles of (at most) this many
LOOPS = ("closed",)


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for stream in mix["streams"]:
        assert stream["loop"] in LOOPS, stream
        lo, hi = stream["images"]
        assert 1 <= lo <= hi, stream
    return mix


def _sizes(stream: dict, n: int, rng) -> list[int]:
    """`n` request sizes over lo..hi: a cycle of at most CYCLE sizes spread
    evenly over the range, each cycle in an order of its own. A sender that
    gets through a few cycles in a window has sent the same sizes under every
    seed, in another order (sizes drawn freely made seeds differ by a fifth
    in images/s, reproducibly: PERF.md, PR 25)."""
    lo, hi = stream["images"]
    k = min(CYCLE, hi - lo + 1)
    base = [int(round(lo + i * (hi - lo) / max(k - 1, 1))) for i in range(k)]
    out = []
    while len(out) < n:
        block = list(base)
        rng.shuffle(block)
        out += block
    return out[:n]


@dataclass
class Request:
    stream: str
    sender: int
    ordinal: int
    n_images: int
    keep: bool = False
    # filled when sent
    urls: list = field(default_factory=list)
    sent_at: float = 0.0
    done_at: float = 0.0
    status: int = 0
    n_errors: int = 0
    n_detections: int = 0
    body: bytes | None = None
    error: str | None = None


class Plan:
    """What a run sends, fixed by (mix, seed) before the window: each
    client's request sizes, and which of its replies are kept to be compared.
    URLs are drawn when a request is sent: a client sends as many as it can."""

    def __init__(self, mix: dict, names: list, base_url: str, seed: int, seconds: float):
        self.mix, self.seconds = mix, float(seconds)
        rng = np.random.default_rng([int(seed), 7])
        order = list(rng.permutation(len(names)))
        self._names = [names[i] for i in order]
        self._base = base_url
        self._cursor = 0
        self._lock = threading.Lock()
        self.closed: list[list[list[Request]]] = []
        for stream in mix["streams"]:
            per_client = []
            for c in range(int(stream["clients"])):
                sizes = _sizes(stream, 4096, rng)
                keep = rng.integers(0, KEEP_ONE_IN, size=len(sizes)) == 0
                per_client.append([
                    Request(stream["name"], c, k, n, keep=bool(keep[k]))
                    for k, n in enumerate(sizes)
                ])
            self.closed.append(per_client)

    def materialise(self, req: Request) -> None:
        """A request gets its URLs when it is about to be sent."""
        with self._lock:
            for _ in range(req.n_images):
                name = self._names[self._cursor % len(self._names)]
                req.urls.append(f"{self._base}/{name}?r={self._cursor}")
                self._cursor += 1


def _post(conn_box: list, server_url: str, req: Request) -> None:
    parts = urlsplit(server_url)
    payload = json.dumps({"image_urls": req.urls}).encode()
    req.sent_at = time.monotonic()
    try:
        if not conn_box:
            conn_box.append(http.client.HTTPConnection(
                parts.hostname, parts.port, timeout=REQUEST_TIMEOUT_S))
        conn = conn_box[0]
        conn.request("POST", "/detect", body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        req.done_at = time.monotonic()
        req.status = resp.status
        req.n_errors = body.count(b'"error":')
        req.n_detections = body.count(b'"label":')
        if req.keep or req.status != 200 or req.n_errors:
            req.body = body
    except Exception as exc:  # a refused or broken request fails; it is counted
        req.done_at = time.monotonic()
        req.error = f"{type(exc).__name__}: {exc}"
        if conn_box:
            conn_box.pop().close()


@dataclass
class WindowResult:
    t0: float  # the first send
    t_close: float  # nothing is sent from here on
    t1: float  # the last reply
    requests: list  # every request that was sent, finished or failed

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def ok(self, r: Request) -> bool:
        return r.error is None and r.status == 200 and r.n_errors == 0

    def done(self) -> list:
        """The requests that were answered in full."""
        return [r for r in self.requests if self.ok(r)]


def run_window(plan: Plan, server_url: str, on_started=None) -> WindowResult:
    """Drive the plan for `plan.seconds`, then wait for what is in flight."""
    sent: list[Request] = []
    sent_lock = threading.Lock()
    t0 = time.monotonic()
    t_close = t0 + plan.seconds

    def closed_client(queue):
        conn_box = []
        for req in queue:
            if time.monotonic() >= t_close:
                break
            plan.materialise(req)
            with sent_lock:
                sent.append(req)
            _post(conn_box, server_url, req)
        if conn_box:
            conn_box[0].close()

    threads = [threading.Thread(target=closed_client, args=(queue,))
               for per_client in plan.closed for queue in per_client]
    for t in threads:
        t.start()
    if on_started is not None:
        on_started(t0)
    for t in threads:
        t.join()
    t1 = max([r.done_at for r in sent], default=t_close)
    return WindowResult(t0, t_close, t1, sent)
