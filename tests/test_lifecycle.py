"""Replica-lifecycle tests (ISSUE 2): startup state machine + /startupz,
preemption watcher (file source + explicit trigger), admin-token guard on
state-changing endpoints, compile-cache env plumbing, and the /metrics
lifecycle fields surviving a drain/restart cycle."""

import asyncio
import os
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.serving import lifecycle
from spotter_tpu.serving.detector import AmenitiesDetector
from spotter_tpu.serving.resilience import CircuitBreaker
from spotter_tpu.serving.standalone import ADMIN_TOKEN_ENV, ADMIN_TOKEN_HEADER, make_app
from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient


def _detector():
    engine = StubEngine()
    batcher = MicroBatcher(
        engine,
        max_delay_ms=1.0,
        breaker=CircuitBreaker(threshold=100, metrics=engine.metrics),
    )
    return AmenitiesDetector(engine, batcher, StubHttpClient()), engine


# ---- startup state machine ----


def test_startup_tracker_transitions():
    tracker = lifecycle.StartupTracker()
    assert tracker.state == lifecycle.LOADING and not tracker.ready
    tracker.mark(lifecycle.WARMING)
    assert tracker.state == lifecycle.WARMING and not tracker.ready
    engine = StubEngine()
    ttr = tracker.mark_ready(engine.metrics)
    assert tracker.ready and ttr > 0
    assert engine.metrics.snapshot()["time_to_ready_s"] == ttr
    with pytest.raises(ValueError):
        tracker.mark("bogus")


def test_startupz_endpoint_with_prebuilt_detector():
    detector, engine = _detector()

    async def run():
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            resp = await client.get("/startupz")
            assert resp.status == 200
            body = await resp.json()
            assert body["state"] == "ready"
            assert body["time_to_ready_s"] > 0

    asyncio.run(run())


def test_startupz_503_while_loading_and_detect_shed():
    """While bring-up runs, /startupz and /healthz answer 503 (startupProbe
    territory), /livez 200, and /detect sheds with Retry-After instead of
    erroring — then everything flips once the build completes."""

    async def run(monkeypatch_release: asyncio.Event):
        app = make_app(detector=None, model_name="unused")

        # substitute a slow bring-up for the real model build
        async def fake_bring_up(app):
            await monkeypatch_release.wait()
            det, engine = _detector()
            app["startup"].mark(lifecycle.WARMING)
            app["detector"] = det
            app["startup"].mark_ready(engine.metrics)

        async def start_fake_bring_up(app):
            app["bringup_task"] = asyncio.create_task(fake_bring_up(app))

        app.on_startup.clear()
        app.on_startup.append(start_fake_bring_up)
        async with TestClient(TestServer(app)) as client:
            startup = await client.get("/startupz")
            assert startup.status == 503
            assert (await startup.json())["state"] == "loading"
            health = await client.get("/healthz")
            assert health.status == 503
            live = await client.get("/livez")
            assert live.status == 200
            shed = await client.post("/detect", json={"image_urls": ["http://x/y.jpg"]})
            assert shed.status == 503
            assert "Retry-After" in shed.headers
            metrics = await client.get("/metrics")
            assert (await metrics.json())["startup"]["state"] == "loading"

            monkeypatch_release.set()
            for _ in range(100):
                startup = await client.get("/startupz")
                if startup.status == 200:
                    break
                await asyncio.sleep(0.01)
            assert startup.status == 200
            ok = await client.post("/detect", json={"image_urls": ["http://x/y.jpg"]})
            assert ok.status == 200
            await app["detector"].batcher.stop()

    asyncio.run(run(asyncio.Event()))


def test_startup_tracker_mark_failed():
    tracker = lifecycle.StartupTracker()
    tracker.mark_failed("RuntimeError: boom")
    assert tracker.state == lifecycle.FAILED and not tracker.ready
    snap = tracker.snapshot()
    assert snap["state"] == "failed" and snap["error"] == "RuntimeError: boom"


def test_bringup_failure_marks_failed_and_exits(monkeypatch):
    """A bring-up that raises must not wedge the replica in 'loading'
    forever: it marks the terminal failed state (visible on /startupz for
    whatever probe window remains) and exits non-zero so the supervisor's
    crash-loop/backoff machinery — which only reacts to process exit — can
    take over."""
    import spotter_tpu.serving.standalone as standalone

    def exploding_build(model_name):
        raise RuntimeError("boom: no such model")

    monkeypatch.setattr(standalone, "_build_detector_blocking", exploding_build)
    exit_codes = []

    async def run():
        app = make_app(model_name="nonexistent", bringup_exit_cb=exit_codes.append)
        async with TestClient(TestServer(app)) as client:
            for _ in range(200):
                if exit_codes:
                    break
                await asyncio.sleep(0.01)
            assert exit_codes == [lifecycle.BRINGUP_FAILED_EXIT_CODE]
            startup = await client.get("/startupz")
            assert startup.status == 503
            body = await startup.json()
            assert body["state"] == "failed"
            assert "boom" in body["error"]
            live = await client.get("/livez")
            assert live.status == 200  # exit_cb stubbed: process still serves

    asyncio.run(run())


# ---- preemption watcher ----


def test_preemption_file_source_drains_and_exits(tmp_path):
    """The maintenance-file source: file appears -> readiness flips via
    drain() -> distinct exit code handed to exit_cb. No SIGTERM involved."""
    detector, engine = _detector()
    marker = tmp_path / "preempt-now"
    exit_codes = []

    async def run():
        watcher = lifecycle.PreemptionWatcher(
            on_preempt=detector.drain,
            poll_s=0.02,
            file_source=str(marker),
            url_source=None,
            exit_cb=exit_codes.append,
            install_sigterm=False,
        )
        await watcher.start()
        await asyncio.sleep(0.1)
        assert not watcher.preempted  # no event yet
        marker.write_text("maintenance")
        for _ in range(200):
            if exit_codes:
                break
            await asyncio.sleep(0.01)
        assert exit_codes == [lifecycle.PREEMPTED_EXIT_CODE]
        assert watcher.preempted and "maintenance file" in watcher.reason
        assert detector.batcher.draining  # drain actually ran
        await watcher.stop()

    asyncio.run(run())
    assert engine.metrics.snapshot()["draining"] is True


def test_preemption_trigger_is_idempotent():
    drains = []

    async def run():
        async def on_preempt():
            drains.append(1)

        exit_codes = []
        watcher = lifecycle.PreemptionWatcher(
            on_preempt=on_preempt,
            poll_s=0.02,
            file_source=None,
            url_source=None,
            exit_cb=exit_codes.append,
            install_sigterm=False,
        )
        await watcher.start()
        watcher.trigger("SIGTERM")
        watcher.trigger("SIGTERM again")  # must not double-drain
        for _ in range(100):
            if exit_codes:
                break
            await asyncio.sleep(0.01)
        assert drains == [1]
        assert exit_codes == [lifecycle.PREEMPTED_EXIT_CODE]
        assert watcher.reason == "SIGTERM"
        await watcher.stop()

    asyncio.run(run())


# ---- warm restart plumbing ----


@pytest.fixture
def restore_jax_cache_dir():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_placed_by_jax_env(monkeypatch, tmp_path, restore_jax_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own handling stands — the helper
    resolves that directory and sets NO directory in code."""
    import jax

    cache_dir = tmp_path / "compile-cache"
    jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
    monkeypatch.setenv(lifecycle.JAX_CACHE_DIR_ENV, str(cache_dir))
    assert lifecycle.compile_cache_dir() == str(cache_dir)
    assert lifecycle.enable_compile_cache() == str(cache_dir)
    assert jax.config.jax_compilation_cache_dir == "/sentinel/untouched"
    assert not cache_dir.exists()  # JAX creates what JAX was pointed at
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_default_is_fixed_path_in_checkout(
    monkeypatch, tmp_path, restore_jax_cache_dir
):
    """Unset: the cache is `<checkout>/.jax_cache` — a fixed path (the
    directory is part of the cache key), never a temp name, pid or time."""
    import jax

    monkeypatch.delenv(lifecycle.JAX_CACHE_DIR_ENV)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert lifecycle.DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert lifecycle.compile_cache_dir() == lifecycle.DEFAULT_COMPILE_CACHE_DIR
    # armed against a stand-in so the test leaves the checkout alone
    fixed = tmp_path / ".jax_cache"
    monkeypatch.setattr(lifecycle, "DEFAULT_COMPILE_CACHE_DIR", str(fixed))
    assert lifecycle.enable_compile_cache() == str(fixed)
    assert fixed.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(fixed)


def test_restarts_from_env(monkeypatch):
    monkeypatch.delenv(lifecycle.RESTARTS_ENV, raising=False)
    assert lifecycle.restarts_from_env() == 0
    monkeypatch.setenv(lifecycle.RESTARTS_ENV, "3")
    assert lifecycle.restarts_from_env() == 3
    monkeypatch.setenv(lifecycle.RESTARTS_ENV, "garbage")
    assert lifecycle.restarts_from_env() == 0


# ---- admin-token guard ----


def test_admin_endpoints_open_when_token_unset(monkeypatch):
    monkeypatch.delenv(ADMIN_TOKEN_ENV, raising=False)
    detector, _ = _detector()

    async def run():
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            drained = await client.post("/drain")
            assert drained.status == 200

    asyncio.run(run())


def test_admin_endpoints_guarded_when_token_set(monkeypatch):
    monkeypatch.setenv(ADMIN_TOKEN_ENV, "s3cret")
    detector, _ = _detector()

    async def run():
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            # missing and wrong tokens are rejected before any state changes
            no_token = await client.post("/drain")
            assert no_token.status == 401
            wrong = await client.post("/drain", headers={ADMIN_TOKEN_HEADER: "nope"})
            assert wrong.status == 401
            profile_no_token = await client.post("/profile", json={})
            assert profile_no_token.status == 401
            # the replica kept serving: the failed drains changed nothing
            health = await client.get("/healthz")
            assert health.status == 200
            # correct token drains
            ok = await client.post("/drain", headers={ADMIN_TOKEN_HEADER: "s3cret"})
            assert ok.status == 200
            assert (await ok.json())["status"] == "drained"

    asyncio.run(run())


# ---- /metrics lifecycle fields across drain/restart ----


def test_metrics_lifecycle_fields_survive_drain_restart(monkeypatch):
    """time_to_ready_s and restarts_total are process-lifetime gauges: a
    batcher drain + restart (the in-process analog of readiness flapping)
    must not reset them."""
    monkeypatch.setenv(lifecycle.RESTARTS_ENV, "2")
    detector, engine = _detector()

    async def run():
        app = make_app(detector=detector)
        async with TestClient(TestServer(app)) as client:
            snap = await (await client.get("/metrics")).json()
            assert snap["time_to_ready_s"] > 0
            assert snap["restarts_total"] == 2

            await client.post("/drain")
            snap_drained = await (await client.get("/metrics")).json()
            assert snap_drained["draining"] is True
            assert snap_drained["time_to_ready_s"] == snap["time_to_ready_s"]
            assert snap_drained["restarts_total"] == 2

            # explicit re-open (the supervisor-restart analog inside one
            # process) keeps the gauges
            await detector.batcher.start()
            ok = await client.post("/detect", json={"image_urls": ["http://x/a.jpg"]})
            assert ok.status == 200
            snap_restarted = await (await client.get("/metrics")).json()
            assert snap_restarted["draining"] is False
            assert snap_restarted["time_to_ready_s"] == snap["time_to_ready_s"]
            assert snap_restarted["restarts_total"] == 2

    asyncio.run(run())


# ---- multihost coordinator timeout (satellite) ----


def test_coordinator_timeout_default_and_env(monkeypatch):
    from spotter_tpu.parallel import multihost

    monkeypatch.delenv(multihost.COORD_TIMEOUT_ENV, raising=False)
    assert multihost.coordinator_timeout_s() == multihost.DEFAULT_COORD_TIMEOUT_S
    monkeypatch.setenv(multihost.COORD_TIMEOUT_ENV, "45")
    assert multihost.coordinator_timeout_s() == 45
    assert multihost.multihost_env_summary()["SPOTTER_TPU_COORD_TIMEOUT_S"] == "45"
    for bad in ("abc", "0", "-5"):
        monkeypatch.setenv(multihost.COORD_TIMEOUT_ENV, bad)
        with pytest.raises(ValueError):
            multihost.coordinator_timeout_s()


def test_initialize_passes_timeout_to_jax(monkeypatch):
    """The env knob must actually reach jax.distributed.initialize as
    initialization_timeout — the whole point is failing fast on a dead
    coordinator."""
    import jax

    from spotter_tpu.parallel import multihost

    captured = {}

    def fake_initialize(**kwargs):
        captured.update(kwargs)

    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
    monkeypatch.setenv(multihost.COORD_TIMEOUT_ENV, "17")
    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
    assert multihost.initialize_multihost() is True
    assert captured["initialization_timeout"] == 17
    assert captured["num_processes"] == 2
    assert captured["process_id"] == 0


def test_initialize_wraps_coordinator_failure(monkeypatch):
    import jax

    from spotter_tpu.parallel import multihost

    def exploding_initialize(**kwargs):
        raise RuntimeError("DEADLINE_EXCEEDED: connect to coordinator")

    monkeypatch.setenv("TPU_WORKER_ID", "1")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1")
    monkeypatch.setattr(jax.distributed, "initialize", exploding_initialize)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
    with pytest.raises(RuntimeError, match="multihost bring-up failed"):
        multihost.initialize_multihost()


def test_time_to_ready_anchor_is_monotonic():
    # _PROCESS_START is captured at module import; mark_ready measured from
    # it must be >= any tracker's own age
    tracker = lifecycle.StartupTracker()
    time.sleep(0.01)
    ttr = tracker.mark_ready()
    assert ttr >= 0.01
    assert tracker.snapshot()["time_to_ready_s"] == ttr


def test_stub_engine_detects_and_records_metrics():
    engine = StubEngine(service_ms=1.0)
    out = engine.detect([object(), object()])
    assert len(out) == 2 and out[0][0]["label"] == "tv"
    snap = engine.metrics.snapshot()
    assert snap["images_total"] == 2


def test_pool_label_from_env(monkeypatch):
    """SPOTTER_TPU_POOL is a pure label (set by the fleet spawner) surfaced
    through /startupz + /healthz so capacity classes are tellable apart."""
    from spotter_tpu.serving import lifecycle

    monkeypatch.delenv("SPOTTER_TPU_POOL", raising=False)
    assert lifecycle.pool_from_env() is None
    tracker = lifecycle.StartupTracker()
    assert tracker.snapshot()["pool"] is None
    monkeypatch.setenv("SPOTTER_TPU_POOL", "spot")
    assert lifecycle.pool_from_env() == "spot"
    assert tracker.snapshot()["pool"] == "spot"


# ---- supervisor policy (in-process; the cross-process path is in
# tests/test_failover.py) ----


def test_supervisor_backoff_jitter_desynchronizes():
    """ISSUE 6 satellite: two supervisors preempted by the same maintenance
    wave must NOT re-enter backoff in lockstep. With full jitter (default
    on) their waits decorrelate while the deterministic doubling cap — the
    thing the crash-loop window is calibrated against — stays identical."""
    import random
    import sys

    from spotter_tpu.serving.supervisor import Supervisor

    cmd = [sys.executable, "-c", "pass"]
    a = Supervisor(cmd, rng=random.Random(1), jitter=True)
    b = Supervisor(cmd, rng=random.Random(2), jitter=True)
    seq_a = [a._bump_backoff() for _ in range(6)]
    seq_b = [b._bump_backoff() for _ in range(6)]
    assert seq_a != seq_b  # desynchronized waits
    caps = [0.5, 1.0, 2.0, 4.0, 8.0, 16.0]
    for wait_a, wait_b, cap in zip(seq_a, seq_b, caps):
        assert 0.0 <= wait_a <= cap
        assert 0.0 <= wait_b <= cap
    assert a._backoff_s == b._backoff_s == 16.0  # identical cap trajectory
    # jitter off: the exact exponential sequence, reproducible
    c = Supervisor(cmd, jitter=False)
    assert [c._bump_backoff() for _ in range(3)] == [0.5, 1.0, 2.0]
    # env knob: explicit 0 disables, unset enables
    import os

    from spotter_tpu.serving.supervisor import jitter_enabled_from_env

    old = os.environ.pop("SPOTTER_TPU_BACKOFF_JITTER", None)
    try:
        assert jitter_enabled_from_env()
        os.environ["SPOTTER_TPU_BACKOFF_JITTER"] = "0"
        assert not jitter_enabled_from_env()
    finally:
        os.environ.pop("SPOTTER_TPU_BACKOFF_JITTER", None)
        if old is not None:
            os.environ["SPOTTER_TPU_BACKOFF_JITTER"] = old


def test_supervisor_crash_loop_circuit():
    import sys

    from spotter_tpu.serving.supervisor import CRASH_LOOP_EXIT_CODE, Supervisor

    sup = Supervisor(
        [sys.executable, "-c", "import sys; sys.exit(1)"],
        backoff_base_s=0.02,
        backoff_max_s=0.05,
        min_uptime_s=1.0,
        crash_loop_limit=3,
    )
    assert sup.run() == CRASH_LOOP_EXIT_CODE
    assert sup.restarts_total == 3  # circuit tripped before the 4th respawn


def test_supervisor_clean_exit_propagates():
    import sys

    from spotter_tpu.serving.supervisor import Supervisor

    sup = Supervisor([sys.executable, "-c", "pass"])
    assert sup.run() == 0
    assert sup.restarts_total == 0


def test_supervisor_exports_restart_count_and_pidfile(tmp_path):
    """Each spawn exports SPOTTER_TPU_RESTARTS and rewrites the pidfile —
    the plumbing behind restarts_total in /metrics and behind harnesses
    targeting the current child."""
    import sys

    from spotter_tpu.serving.supervisor import Supervisor

    out = tmp_path / "restarts.log"
    pidfile = tmp_path / "child.pid"
    script = (
        "import os, sys\n"
        f"with open({str(out)!r}, 'a') as f:\n"
        "    f.write(os.environ['SPOTTER_TPU_RESTARTS'] + '\\n')\n"
        "sys.exit(0 if os.environ['SPOTTER_TPU_RESTARTS'] == '2' else 1)\n"
    )
    sup = Supervisor(
        [sys.executable, "-c", script],
        backoff_base_s=0.02,
        backoff_max_s=0.05,
        min_uptime_s=1.0,
        crash_loop_limit=10,
        pidfile=str(pidfile),
    )
    assert sup.run() == 0  # third generation (RESTARTS=2) exits cleanly
    assert out.read_text().split() == ["0", "1", "2"]
    assert pidfile.exists() and int(pidfile.read_text()) > 0


def test_supervisor_sigterm_during_backoff_exits_without_respawn():
    """REVIEW fix: SIGTERM landing while no child runs (mid-backoff) must
    end the supervisor with the last child's code — not resume the sleep
    (PEP 475) and spawn a fresh child the signal can never reach."""
    import sys
    import threading

    from spotter_tpu.serving.supervisor import Supervisor

    sup = Supervisor(
        [sys.executable, "-c", "import sys; sys.exit(1)"],
        backoff_base_s=10.0,  # far longer than the test: must be interrupted
        min_uptime_s=1.0,
        crash_loop_limit=10,
    )
    # the handler body is what SIGTERM would run; invoking it from a timer
    # thread exercises the same code path without needing a real signal
    threading.Timer(0.5, sup._forward_term, args=(None, None)).start()
    started = time.monotonic()
    assert sup.run() == 1  # the crashed child's code, not a fresh spawn's
    assert time.monotonic() - started < 5.0  # backoff wait was interrupted
    assert sup.restarts_total == 0  # no respawn after termination


def test_supervisor_persistent_preemption_falls_back_to_backoff(tmp_path):
    """REVIEW fix: when the preemption source outlives the child (marker
    file never deleted), exit-83 restarts must not hot-loop — after
    `preempt_fast_limit` consecutive fast preemption exits the normal
    exponential backoff applies. Preemption exits never trip the
    crash-loop circuit."""
    import sys

    from spotter_tpu.serving.supervisor import Supervisor

    counter = tmp_path / "count"
    script = (
        "import pathlib, sys\n"
        f"p = pathlib.Path({str(counter)!r})\n"
        "n = int(p.read_text()) + 1 if p.exists() else 1\n"
        "p.write_text(str(n))\n"
        "sys.exit(83 if n <= 5 else 0)\n"
    )
    sup = Supervisor(
        [sys.executable, "-c", script],
        backoff_base_s=0.2,
        backoff_max_s=0.4,
        min_uptime_s=5.0,  # every child exit here counts as "fast"
        crash_loop_limit=3,  # < the 5 preemption exits: must NOT trip
        preempt_fast_limit=2,
        jitter=False,  # this test times the deterministic cap trajectory
    )
    started = time.monotonic()
    assert sup.run() == 0
    elapsed = time.monotonic() - started
    assert sup.restarts_total == 5
    # exits 3..5 were past the fast limit: backoffs 0.2 + 0.4 + 0.4 = 1.0 s
    assert elapsed >= 0.9


@pytest.mark.skipif(os.name != "posix", reason="posix-only")
def test_preemption_env_source_construction(monkeypatch, tmp_path):
    """Env-driven construction: file/url/poll knobs are read when the
    constructor args are left at None."""
    monkeypatch.setenv(lifecycle.PREEMPTION_FILE_ENV, str(tmp_path / "m"))
    monkeypatch.setenv(lifecycle.PREEMPTION_POLL_ENV, "0.5")
    monkeypatch.delenv(lifecycle.PREEMPTION_URL_ENV, raising=False)

    async def noop():
        pass

    watcher = lifecycle.PreemptionWatcher(on_preempt=noop, install_sigterm=False)
    assert watcher.file_source == str(tmp_path / "m")
    assert watcher.url_source is None
    assert watcher.poll_s == 0.5


def test_supervisor_fatal_engine_exit_restarts_immediately(tmp_path):
    """ISSUE 4: FATAL_ENGINE_EXIT_CODE (85) gets an immediate warm restart
    — no crash backoff, no crash-loop debt — but a device that STAYS dead
    falls back to backoff after the fast limit, like persistent preemption."""
    import sys

    from spotter_tpu.engine.errors import FATAL_ENGINE_EXIT_CODE
    from spotter_tpu.serving.supervisor import Supervisor

    counter = tmp_path / "count"
    script = (
        "import pathlib, sys\n"
        f"p = pathlib.Path({str(counter)!r})\n"
        "n = int(p.read_text()) + 1 if p.exists() else 1\n"
        "p.write_text(str(n))\n"
        f"sys.exit({FATAL_ENGINE_EXIT_CODE} if n <= 2 else 0)\n"
    )
    sup = Supervisor(
        [sys.executable, "-c", script],
        backoff_base_s=5.0,  # immediate restarts must never hit this
        min_uptime_s=5.0,  # every exit here counts as "fast"
        crash_loop_limit=1,  # fatal-engine exits must NOT trip the circuit
        preempt_fast_limit=3,
    )
    started = time.monotonic()
    assert sup.run() == 0
    assert sup.restarts_total == 2
    assert time.monotonic() - started < 4.0  # no 5 s backoff was paid


def test_supervisor_persistent_fatal_engine_falls_back_to_backoff(tmp_path):
    """A chip that stays dead (exit 85 forever-fast) must not hot-loop
    spawn->fatal->exit: past the fast limit the exponential backoff applies."""
    import sys

    from spotter_tpu.engine.errors import FATAL_ENGINE_EXIT_CODE
    from spotter_tpu.serving.supervisor import Supervisor

    counter = tmp_path / "count"
    script = (
        "import pathlib, sys\n"
        f"p = pathlib.Path({str(counter)!r})\n"
        "n = int(p.read_text()) + 1 if p.exists() else 1\n"
        "p.write_text(str(n))\n"
        f"sys.exit({FATAL_ENGINE_EXIT_CODE} if n <= 4 else 0)\n"
    )
    sup = Supervisor(
        [sys.executable, "-c", script],
        backoff_base_s=0.2,
        backoff_max_s=0.3,
        min_uptime_s=5.0,
        crash_loop_limit=2,  # < the 4 fatal exits: must NOT trip
        preempt_fast_limit=2,
        jitter=False,  # this test times the deterministic cap trajectory
    )
    started = time.monotonic()
    assert sup.run() == 0
    elapsed = time.monotonic() - started
    assert sup.restarts_total == 4
    # exits 3 and 4 were past the fast limit: backoffs 0.2 + 0.3 = 0.5 s
    assert elapsed >= 0.45
