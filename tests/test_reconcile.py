"""Crash-safe control plane (ISSUE 16): the durable desired-state store
(CRC-framed journal + atomic-rename snapshot), leader-lease fencing, the
endpoints manifest, rollout resume planning, and the controller chaos
drills (testing/chaos_matrix.py::CONTROLLER_MATRIX) — a controller killed
-9 mid-rollout must be replaceable by a successor that adopts every live
member instead of double-spawning, resumes or rolls back the in-flight
wave, and reconverges desired == observed with zero client failures."""

import json
import os
import subprocess
import sys
import time

import pytest

from spotter_tpu.engine.metrics import ControlPlaneMetrics
from spotter_tpu.serving.reconcile import healthz_block, load_or_rebuild
from spotter_tpu.serving.rollout import resume_plan
from spotter_tpu.serving.statestore import (
    JOURNAL_NAME,
    SNAPSHOT_NAME,
    EndpointsManifest,
    LeaderLease,
    StaleLeaderError,
    StateCorruptError,
    StateStore,
    decode_records,
    encode_record,
    supervisor_alive,
)


def _seeded_store(directory: str) -> StateStore:
    """Snapshot + a live journal tail: the compaction-plus-appends shape a
    real controller leaves on disk."""
    store = StateStore.load(directory)
    store.set_pool("spot", size=3, version="v1", **{"class": "spot"})
    store.set_pool("serve", size=2, version="v1", **{"class": "on_demand"})
    store.compact()
    store.set_pool("spot", size=4)
    store.set_rollout({"state": "canary", "wave": 1, "version_to": "v2"})
    store.set_pool("serve", version="v2")
    return store


# ---------------------------------------------------------------------------
# desired-state store: fold, merge, compaction, sequence discipline


def test_store_roundtrip_merge_and_compaction(tmp_path):
    d = str(tmp_path / "state")
    store = _seeded_store(d)
    # set_pool merges over the existing spec: the size-only update must
    # not have dropped class/version
    assert store.state["pools"]["spot"] == {
        "class": "spot", "size": 4, "version": "v1",
    }
    assert store.state["pools"]["serve"]["version"] == "v2"
    assert store.state["rollout"]["state"] == "canary"

    again = StateStore.load(d)
    assert again.state == store.state
    assert again.seq == store.seq == 5
    assert again.journal_records == 3  # post-compaction appends only

    again.compact()
    assert StateStore.load(d).state == store.state
    assert os.path.getsize(os.path.join(d, JOURNAL_NAME)) == 0


def test_compaction_overlap_tail_is_skipped_not_corrupt(tmp_path):
    """Crash between compaction's two renames leaves snapshot(new) +
    journal(old tail): every tail seq is <= the snapshot seq, so load()
    skips them instead of double-applying or raising."""
    d = str(tmp_path / "state")
    store = _seeded_store(d)
    with open(os.path.join(d, JOURNAL_NAME), "rb") as f:
        old_tail = f.read()
    store.compact()
    with open(os.path.join(d, JOURNAL_NAME), "wb") as f:
        f.write(old_tail)
    again = StateStore.load(d)
    assert again.state == store.state
    assert again.journal_records == 0  # all skipped by seq


def test_sequence_gap_is_corruption(tmp_path):
    d = str(tmp_path / "state")
    os.makedirs(d)
    blob = encode_record({"op": "set_pool", "seq": 1, "name": "a",
                          "pool": {"size": 1}})
    blob += encode_record({"op": "set_pool", "seq": 3, "name": "a",
                           "pool": {"size": 2}})  # seq 2 lost
    with open(os.path.join(d, JOURNAL_NAME), "wb") as f:
        f.write(blob)
    with pytest.raises(StateCorruptError, match="sequence gap"):
        StateStore.load(d)


def test_unknown_op_and_snapshot_in_journal_are_corrupt(tmp_path):
    d = str(tmp_path / "state")
    os.makedirs(d)
    path = os.path.join(d, JOURNAL_NAME)
    with open(path, "wb") as f:
        f.write(encode_record({"op": "format_disk", "seq": 1}))
    with pytest.raises(StateCorruptError, match="unknown journal op"):
        StateStore.load(d)
    with open(path, "wb") as f:
        f.write(encode_record({"op": "remove_pool", "seq": 1, "name": "a"},
                              snapshot=True))
    with pytest.raises(StateCorruptError, match="snapshot record inside"):
        StateStore.load(d)


# ---------------------------------------------------------------------------
# the journal fuzz contract (satellite): damage is DETECTED, typed, and
# survivable — never silently replayed, never a crash loop


def _record_boundaries(blob: bytes, where: str) -> set[int]:
    """Offsets where a truncation leaves only whole records. Payloads are
    canonical JSON, so re-encoding reproduces the exact on-disk bytes."""
    offs, off = {0}, 0
    for flags, payload in decode_records(blob, where):
        off += len(encode_record(payload, snapshot=bool(flags & 0x01)))
        offs.add(off)
    return offs


def test_journal_fuzz_every_flip_and_truncation_is_typed(tmp_path):
    """The test_wire.py fuzz contract applied to the state files: every
    single-byte flip of snapshot or journal raises StateCorruptError, and
    every truncation either raises (mid-record: a torn write) or loads a
    strict prefix of the recorded intent (whole-record: byte-identical to
    fewer appends having happened — no framing can tell those apart, and
    reconciliation re-derives the lost tail from observation)."""
    d = str(tmp_path / "state")
    full = _seeded_store(d)
    jpath = os.path.join(d, JOURNAL_NAME)
    spath = os.path.join(d, SNAPSHOT_NAME)
    with open(jpath, "rb") as f:
        jblob = f.read()
    with open(spath, "rb") as f:
        sblob = f.read()

    def _restore():
        with open(jpath, "wb") as f:
            f.write(jblob)
        with open(spath, "wb") as f:
            f.write(sblob)

    try:
        # every truncation of the journal
        bounds = _record_boundaries(jblob, JOURNAL_NAME)
        for i in range(len(jblob) + 1):
            with open(jpath, "wb") as f:
                f.write(jblob[:i])
            if i in bounds:
                got = StateStore.load(d)
                assert got.seq <= full.seq
            else:
                with pytest.raises(StateCorruptError):
                    StateStore.load(d)
        # every single-byte flip of the journal
        with open(spath, "wb") as f:
            f.write(sblob)
        for i in range(len(jblob)):
            bad = bytearray(jblob)
            bad[i] ^= 0xFF
            with open(jpath, "wb") as f:
                f.write(bytes(bad))
            with pytest.raises(StateCorruptError):
                StateStore.load(d)
        # every single-byte flip of the snapshot
        with open(jpath, "wb") as f:
            f.write(jblob)
        for i in range(len(sblob)):
            bad = bytearray(sblob)
            bad[i] ^= 0xFF
            with open(spath, "wb") as f:
                f.write(bytes(bad))
            with pytest.raises(StateCorruptError):
                StateStore.load(d)
        # every mid-record truncation of the snapshot (its only whole-record
        # prefixes are empty and complete)
        sbounds = _record_boundaries(sblob, SNAPSHOT_NAME)
        assert sbounds == {0, len(sblob)}
        for i in range(1, len(sblob)):
            with open(spath, "wb") as f:
                f.write(sblob[:i])
            with pytest.raises(StateCorruptError):
                StateStore.load(d)
    finally:
        _restore()
    assert StateStore.load(d).state == full.state  # intact files still load


def test_load_or_rebuild_counts_and_quarantines_never_crash_loops(tmp_path):
    d = str(tmp_path / "state")
    _seeded_store(d)
    jpath = os.path.join(d, JOURNAL_NAME)
    with open(jpath, "r+b") as f:
        blob = bytearray(f.read())
        blob[len(blob) // 2] ^= 0xFF
        f.seek(0)
        f.write(bytes(blob))

    metrics = ControlPlaneMetrics()
    store = load_or_rebuild(d, metrics)
    assert metrics.journal_rebuilds_total == 1
    assert store.state == {"pools": {}, "rollout": None}  # rebuild, no replay
    # damaged intent is quarantined for the post-mortem, not deleted
    assert os.path.exists(jpath + ".corrupt")
    assert not os.path.exists(jpath)
    # the rebuilt store is immediately writable and the NEXT load is clean:
    # detection is a one-time event, not a crash loop
    store.set_pool("spot", size=1)
    again = load_or_rebuild(d, metrics)
    assert metrics.journal_rebuilds_total == 1
    assert again.state["pools"]["spot"]["size"] == 1


# ---------------------------------------------------------------------------
# leader lease: monotonic fencing epochs


def test_lease_takeover_bumps_epoch_and_fences_the_deposed(tmp_path):
    path = str(tmp_path / "leader.lease")
    a = LeaderLease(path, "A", ttl_s=10.0)
    b = LeaderLease(path, "B", ttl_s=10.0)

    assert a.try_acquire(now=100.0) and a.epoch == 1
    assert a.try_acquire(now=105.0) and a.epoch == 1  # renewal keeps epoch
    assert not b.try_acquire(now=106.0)  # A's lease is live
    assert a.check() == 1

    assert b.try_acquire(now=120.0)  # A expired: takeover MUST bump
    assert b.epoch == 2
    with pytest.raises(StaleLeaderError):
        a.check()  # the deposed leader's actuations are refused
    assert not a.leading
    assert b.check() == 2

    # voluntary release lets the standby take over immediately, still fenced
    b.release()
    c = LeaderLease(path, "C", ttl_s=10.0)
    assert c.try_acquire(now=121.0) and c.epoch == 3
    with pytest.raises(StaleLeaderError):
        b.check()


def test_lease_self_takeover_after_pause_kills_own_old_epoch(tmp_path):
    """A paused-past-TTL leader re-acquiring its OWN stale lease must get a
    new epoch: another controller may have acted during the pause."""
    path = str(tmp_path / "leader.lease")
    a = LeaderLease(path, "A", ttl_s=5.0)
    assert a.try_acquire(now=100.0) and a.epoch == 1
    assert a.try_acquire(now=200.0)  # own lease, long expired
    assert a.epoch == 2


def test_never_led_check_raises(tmp_path):
    lease = LeaderLease(str(tmp_path / "leader.lease"), "standby")
    with pytest.raises(StaleLeaderError):
        lease.check()


# ---------------------------------------------------------------------------
# endpoints manifest + liveness probe


def test_manifest_upsert_merge_and_remove(tmp_path):
    m = EndpointsManifest(str(tmp_path / "endpoints.json"))
    assert m.entries() == {}  # absent file = empty, never an error
    m.add("http://127.0.0.1:1", pool="spot", version="v1", supervisor_pid=7)
    m.add("http://127.0.0.1:1", supervisor_pid=8)  # restart re-registers
    m.add("http://127.0.0.1:2", pool="serve")
    entries = m.entries()
    assert entries["http://127.0.0.1:1"] == {
        "pool": "spot", "version": "v1", "supervisor_pid": 8,
    }
    m.remove("http://127.0.0.1:1")
    m.remove("http://127.0.0.1:1")  # idempotent
    assert list(m.entries()) == ["http://127.0.0.1:2"]


def test_manifest_garbage_file_reads_as_empty(tmp_path):
    path = tmp_path / "endpoints.json"
    path.write_text("{not json")
    m = EndpointsManifest(str(path))
    assert m.entries() == {}
    m.add("http://127.0.0.1:1", pool="spot")  # and is rebuilt by the next add
    assert list(m.entries()) == ["http://127.0.0.1:1"]


def test_supervisor_alive_rejects_dead_and_zombie_pids():
    assert supervisor_alive(os.getpid()) is True
    assert supervisor_alive(None) is False
    assert supervisor_alive(0) is False
    assert supervisor_alive(-5) is False

    # a zombie (exited, unreaped — exactly what a retired member's
    # supervisor becomes while its parent harness runs on) still answers
    # signal 0 but serves nothing: it must read as dead, or adoption would
    # adopt a corpse and shutdown would wait a full escalation timeout
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        try:
            with open(f"/proc/{proc.pid}/stat", "rb") as f:
                stat = f.read()
            if stat.rsplit(b")", 1)[-1].split()[0] == b"Z":
                break
        except OSError:
            break
        time.sleep(0.02)
    try:
        assert supervisor_alive(proc.pid) is False
    finally:
        proc.wait()  # reap
    assert supervisor_alive(proc.pid) is False  # fully gone


# ---------------------------------------------------------------------------
# adoption and fencing at the actuation boundary, in one process: what the
# crash drills (slow tier, real controllers) show end to end


def test_successor_adopts_every_live_member_once_and_spawns_none(tmp_path):
    """A controller that comes up over a manifest of three live members of
    a pool of three adopts all three before it spawns, so its fenced
    spawner never runs: 0 double-spawns. A dead supervisor's entry and a
    pool it does not actuate are left alone, and asking twice adopts
    nothing twice."""
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestServer

    from spotter_tpu.serving.fleet import FleetController, PoolSpec
    from spotter_tpu.serving.reconcile import Reconciler

    async def run():
        async def healthz(request):
            return web.json_response({})

        servers = []
        for _ in range(3):
            app = web.Application()
            app.router.add_get("/healthz", healthz)
            server = TestServer(app)
            await server.start_server()
            servers.append(server)
        urls = [f"http://{s.host}:{s.port}" for s in servers]
        # a live process to stand for the members' supervisor
        supervisor = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)"]
        )
        corpse = subprocess.Popen([sys.executable, "-c", "pass"])
        corpse.wait()
        manifest = EndpointsManifest(str(tmp_path / "endpoints.json"))
        for url in urls:
            manifest.add(
                url, pool="spot", version="v1", supervisor_pid=supervisor.pid
            )
        manifest.add(
            "http://127.0.0.1:9", pool="spot", supervisor_pid=corpse.pid
        )
        manifest.add(
            "http://127.0.0.1:10", pool="elsewhere",
            supervisor_pid=supervisor.pid,
        )
        spawned = []

        def spawner():
            spawned.append(1)
            raise AssertionError("a member was spawned beside a live one")

        controller = FleetController(
            [PoolSpec("spot", spawner=spawner, target_size=3)], tick_s=0.02,
            pool_kwargs=dict(health_interval_s=0.05),
        )
        rec = Reconciler(
            controller, StateStore.fresh(str(tmp_path / "state")),
            manifest=manifest,
        )
        controller.fence = rec.fence
        spec = controller.pools["spot"].spec
        spec.spawner = rec.fenced_spawner(spec.spawner)
        try:
            assert rec.adopt_existing() == 3
            assert rec.adopt_existing() == 0
            await controller.start()
            members = sorted(m.url for m in controller.pools["spot"].members)
        finally:
            await controller.stop(shutdown_members=False)
            supervisor.kill()
            supervisor.wait()
            for server in servers:
                await server.close()
        return members, spawned, rec.metrics, urls

    members, spawned, metrics, urls = asyncio.run(run())
    assert members == sorted(urls)
    assert spawned == []
    assert metrics.adoptions_total == 3 and metrics.spawns_total == 0


def test_deposed_leaders_spawn_is_refused_and_counted(tmp_path):
    """The fencing check sits in front of every spawn: while it leads, a
    controller's spawner runs and is counted; once a standby has taken the
    lease at a higher epoch, the old leader's next spawn raises before the
    spawner is called and books one fencing rejection."""
    from spotter_tpu.serving.fleet import FleetController, PoolSpec
    from spotter_tpu.serving.reconcile import Reconciler

    path = str(tmp_path / "leader.lease")
    old = LeaderLease(path, "old", ttl_s=10.0)
    standby = LeaderLease(path, "standby", ttl_s=10.0)
    assert old.try_acquire(now=100.0)
    calls = []
    rec = Reconciler(
        FleetController([PoolSpec("spot", target_size=0)]),
        StateStore.fresh(str(tmp_path / "state")), lease=old,
    )
    spawn = rec.fenced_spawner(lambda: calls.append(1) or "member")
    assert spawn() == "member"
    assert standby.try_acquire(now=120.0) and standby.epoch == old.epoch + 1
    with pytest.raises(StaleLeaderError):
        spawn()
    assert calls == [1]
    assert rec.metrics.spawns_total == 1
    assert rec.metrics.fencing_rejections_total == 1


# ---------------------------------------------------------------------------
# rollout resume planning (tentpole part c, decision table)


def test_resume_plan_nothing_in_flight():
    assert resume_plan(None) is None
    assert resume_plan({"state": "done"}) is None
    assert resume_plan({"state": "rolled_back"}) is None
    assert resume_plan({"state": "idle"}) is None


def test_resume_plan_live_window_resumes_with_remainder():
    plan = resume_plan(
        {"state": "canary", "wave": 1, "canary_url": "http://c:1",
         "version_to": "v2", "window_s": 8.0, "window_deadline": 1005.0},
        now=1000.0,
    )
    assert plan["action"] == "resume"
    assert plan["canary_url"] == "http://c:1"
    assert plan["window_s"] == pytest.approx(5.0)  # remainder, not fresh


def test_resume_plan_expired_window_rolls_back():
    plan = resume_plan(
        {"state": "canary", "canary_url": "http://c:1",
         "window_deadline": 999.0},
        now=1000.0,
    )
    assert plan["action"] == "rollback"
    assert plan["reason"] == "verdict_window_expired"


def test_resume_plan_between_waves_restarts_the_wave():
    for state in ("spawning", "promoting"):
        plan = resume_plan(
            {"state": state, "wave": 2, "canary_url": "http://c:1"},
            now=1000.0,
        )
        assert plan["action"] == "restart_wave"
        assert plan["canary_url"] is None  # respawn/adopt, don't trust it


def test_healthz_block_none_safe():
    assert healthz_block(None) == {}


def test_fleet_top_renders_control_plane_drift():
    """fleet_top's control line (ISSUE 16 satellite): desired-vs-observed
    drift per pool from the `reconcile` block, absent (no phantom line)
    on edges without a control plane."""
    from tools.fleet_top import render

    fleet = {"replicas": {"up": 1, "seen": 1}, "per_replica": [],
             "slo_burn_rate": {}}
    out = render({
        "fleet": fleet,
        "reconcile": {
            "leader": True, "epoch": 3, "owner": "ctrl-b",
            "drift": {"spot": 1, "serve": 0},
            "drift_detail": {
                "spot": {"desired": 3, "ready": 2},
                "serve": {"desired": 2, "ready": 2},
            },
            "drift_total": 1, "converged": False,
            "adoptions_total": 5, "spawns_total": 1,
            "fencing_rejections_total": 0, "journal_rebuilds_total": 0,
        },
    })
    control = next(
        line for line in out.splitlines() if line.startswith("control:")
    )
    assert "leading epoch 3" in control
    assert "drift 1" in control
    assert "spot 2/3 ready" in control
    assert "serve 2/2 ready" in control
    assert "adopted 5" in control

    assert not any(
        line.startswith("control:")
        for line in render({"fleet": fleet}).splitlines()
    )


# ---------------------------------------------------------------------------
# the controller chaos drills (the acceptance surface): real subprocess
# controllers, kill -9 / SIGSTOP / journal corruption, successor adoption


@pytest.mark.slow
@pytest.mark.parametrize(
    "name",
    [
        "crash-mid-rollout-resume",
        "crash-expired-window-rollback",
        "crash-mid-storm",
        "journal-corrupt-rebuild",
        "stale-leader-fencing",
    ],
)
def test_controller_chaos_row(name, tmp_path):
    from spotter_tpu.testing.chaos_matrix import (
        CONTROLLER_MATRIX,
        run_controller_scenario,
    )

    sc = next(s for s in CONTROLLER_MATRIX if s.name == name)
    report = run_controller_scenario(sc, str(tmp_path))
    assert report["ok"], json.dumps(report, indent=2, default=str)
    if sc.converge_timeout_s:
        assert report["converge_s"] <= sc.converge_timeout_s
