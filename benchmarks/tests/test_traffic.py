import collections
import os
import threading
import time

import traffic
from conftest import BENCH

NAMES = [f"img{i}.jpg" for i in range(16)]


def mixes():
    folder = os.path.join(BENCH, "traffic")
    return {f[:-5]: traffic.load_mix(os.path.join(folder, f))
            for f in sorted(os.listdir(folder)) if f.endswith(".json")}


def plan_signature(plan):
    return [[(r.ordinal, r.n_images, r.keep) for r in q[:50]]
            for clients in plan.closed for q in clients]


def test_same_seed_same_plan():
    for mix in mixes().values():
        a = traffic.Plan(mix, NAMES, "http://x", 2**31 + 5, 30.0)
        b = traffic.Plan(mix, NAMES, "http://x", 2**31 + 5, 30.0)
        assert plan_signature(a) == plan_signature(b)


def test_seeds_share_the_work_in_another_order():
    for mix in mixes().values():
        a = traffic.Plan(mix, NAMES, "http://x", 1, 30.0)
        b = traffic.Plan(mix, NAMES, "http://x", 2, 30.0)
        assert plan_signature(a) != plan_signature(b)
        for ca, cb in zip(a.closed, b.closed):
            for qa, qb in zip(ca, cb):
                count = lambda q: collections.Counter(r.n_images for r in q)  # noqa: E731
                assert count(qa) == count(qb)
                # every cycle of eight holds the same sizes under every seed
                assert sorted(r.n_images for r in qa[:8]) == sorted(r.n_images for r in qb[8:16])


def test_replies_are_kept_all_through_the_window():
    plan = traffic.Plan(mixes()["bulk_closed"], NAMES, "http://x", 5, 30.0)
    for clients in plan.closed:
        for queue in clients:
            kept = [r.ordinal for r in queue[:60] if r.keep]
            assert 8 <= len(kept) <= 32 and max(kept) > 40 and min(kept) < 20


def test_urls_are_distinct_and_drawn_when_sent():
    plan = traffic.Plan(mixes()["bulk_closed"], NAMES, "http://x", 9, 30.0)
    reqs = [plan.closed[0][c][k] for k in range(3) for c in range(2)]
    for r in reqs:
        assert r.urls == []
        plan.materialise(r)
        assert len(r.urls) == r.n_images
    urls = [u for r in reqs for u in r.urls]
    assert len(set(urls)) == len(urls)


def test_the_window_runs_to_the_last_reply(monkeypatch):
    """Nothing is sent after the close, every reply is waited for, and the
    window's length is first send to last reply."""
    def fake_post(conn_box, server_url, req):
        req.sent_at = time.monotonic()
        time.sleep(0.05 * req.n_images / 8)
        req.done_at = time.monotonic()
        req.status = 200

    monkeypatch.setattr(traffic, "_post", fake_post)
    mix = {"streams": [{"name": "bulk", "loop": "closed", "clients": 3, "images": [8, 24]}]}
    plan = traffic.Plan(mix, NAMES, "http://x", 4, 0.5)
    started = threading.Event()
    window = traffic.run_window(plan, "http://x", on_started=lambda t0: started.set())
    assert started.is_set()
    assert all(r.sent_at < window.t_close for r in window.requests)
    assert window.t1 == max(r.done_at for r in window.requests) >= window.t_close
    assert window.window_s == window.t1 - window.t0
    assert len(window.done()) == len(window.requests) >= 6
    window.requests[0].status = 503
    assert len(window.done()) == len(window.requests) - 1
