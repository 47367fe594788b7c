"""Deterministic gray-failure + deployment chaos matrix (ISSUE 14/15).

The fleet chaos coverage grew scenario by scenario (kill a replica, storm
the spot pool, poison a batch...), each hand-rolled in its own test. This
module is the scenario RUNNER for the gray-failure class: a `Scenario` is
a named fault shape (whole-replica slowdown, deterministic flaky 500s,
corrupt binary frames — the faults.py ISSUE 14 injections) plus a workload
and a set of invariants, executed over a model-free in-process topology:
N stub replicas (the REAL standalone `make_app` over stub detectors)
behind the REAL `ReplicaPool` + edge router, adaptive hedging and outlier
scoring armed. Everything is deterministic by construction — Bresenham
fault thinning, counter-armed corruptions, a fixed URL cycle — so a
scenario's invariants are exact assertions, not flaky thresholds.

`GRAY_MATRIX` is the default matrix; `tests/test_grayfail.py` runs every
row. Scenarios are cheap (~a second each): the point is that adding a new
gray-failure shape is one dataclass literal, not a new harness.

ISSUE 15 adds the DEPLOYMENT half: `DeployScenario`/`DEPLOY_MATRIX` run a
full versioned rollout (serving/rollout.py) over the same in-process
topology — N v1 stub replicas behind the real pool + router, a
RolloutController whose spawner produces the "new version" replica with a
scripted defect (10x slow / Bresenham-deterministic flaky 500s / corrupt
frames scoped to the canary via `faults.only_replica` / different
detections for the shadow lane), live load the whole time. Bad deploys
must AUTO-ROLLBACK with zero client-visible failures and a pinned
flight-recorder trace; the good deploy must roll every member to v2 with
zero failures. `tests/test_rollout.py` runs every row.
"""

import asyncio
from dataclasses import dataclass, field

from spotter_tpu.testing import faults

# fixed URL cycle: distinct keys so affinity routing spreads ownership,
# repeated so per-URL behavior is exercised more than once
URL_CYCLE = [f"http://chaos.example.com/img-{i}.jpg" for i in range(16)]


@dataclass
class Scenario:
    """One deterministic gray-failure scenario.

    `gray` / `gray_factor`: mid-load, multiply replica `gray`'s stub
    service time by the factor (the in-process form of the
    `slow_replica=<ms>` injection — per-replica by construction, since
    each stub engine is its own object). `faults`: a faults.inject(...)
    plan active for the whole load (flaky=<pct>, corrupt_frame=<n>, ...).
    `frame`: clients negotiate the binary frame, so the edge CRC validator
    is on the response path. `invariants`: exact checks over the final
    report — every key must hold or the scenario fails.
    """

    name: str
    requests: int = 90
    concurrency: int = 4
    replicas: int = 3
    service_ms: float = 5.0
    gray: int | None = None
    gray_factor: float = 20.0
    gray_at: float = 0.3  # fraction of the load after which `gray` slows
    faults: dict = field(default_factory=dict)
    frame: bool = False
    invariants: dict = field(default_factory=dict)


GRAY_MATRIX = [
    Scenario(
        name="baseline",
        invariants={
            "client_failures": 0,
            "soft_ejections": 0,
            "invalid_responses": 0,
        },
    ),
    Scenario(
        name="gray-slow",
        gray=0,
        requests=140,
        invariants={
            "client_failures": 0,
            "gray_detected": True,
            # the gray replica's share of the post-detection load must
            # collapse toward the outlier weight (5%); 30% is the loose
            # exact-free bound that still proves the weight-down works
            "gray_tail_share_lt": 0.30,
        },
    ),
    Scenario(
        name="flaky",
        # 5%, deliberately UNDER the 10% retry budget: every injected 500
        # is masked by a budgeted replay. (A flaky rate past the budget is
        # a different, correct outcome — fast 503s instead of retry
        # amplification — covered by test_replica_pool's budget tests.)
        faults={"flaky": 5},
        requests=100,
        invariants={
            "client_failures": 0,  # every injected 500 masked by replay
            "replays_gt": 0,
        },
    ),
    Scenario(
        name="corrupt-frames",
        faults={"corrupt_frame": 3},
        frame=True,
        invariants={
            "client_failures": 0,  # every corrupt frame replayed, not 502'd
            "invalid_responses": 3,
        },
    ),
    Scenario(
        name="gray-plus-corrupt",
        gray=1,
        requests=140,
        faults={"corrupt_frame": 2},
        frame=True,
        invariants={
            "client_failures": 0,
            "gray_detected": True,
            "invalid_responses": 2,
        },
    ),
]


async def run_scenario(sc: Scenario) -> dict:
    """Execute one scenario; returns the report dict (see `evaluate`)."""
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.obs.aggregate import FleetAggregator
    from spotter_tpu.serving import wire
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.serving.replica_pool import ReplicaPool
    from spotter_tpu.serving.router import make_router_app
    from spotter_tpu.serving.standalone import make_app
    from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient

    engines, dets, servers, urls = [], [], [], []
    for i in range(sc.replicas):
        engine = StubEngine(service_ms=sc.service_ms)
        engine.metrics.set_identity(replica_id=f"chaos-r{i}")
        det = AmenitiesDetector(
            engine, MicroBatcher(engine, max_delay_ms=1.0), StubHttpClient()
        )
        server = TestServer(make_app(detector=det))
        await server.start_server()
        engines.append(engine)
        dets.append(det)
        servers.append(server)
        urls.append(f"http://{server.host}:{server.port}")

    pool = ReplicaPool(
        urls,
        health_interval_s=0.05,
        adaptive_hedge=True,
        # fast, test-friendly outlier knobs: same machinery, smaller
        # evidence requirements so a ~1 s scenario converges
        outlier_min_samples=5,
        outlier_min_ms=5.0,
        outlier_alpha=0.4,
    )
    aggregator = FleetAggregator(lambda: [], interval_s=0.0)  # determinism
    router_app = make_router_app(pool, aggregator=aggregator)

    gray_after = int(sc.requests * sc.gray_at)
    tail_from = int(sc.requests * 0.7)
    counts_at_tail: list[int] = []
    client_failures = 0
    statuses: dict[int, int] = {}
    headers = (
        {"Accept": wire.FRAME_CONTENT_TYPE} if sc.frame else {}
    )

    async with TestClient(TestServer(router_app)) as client:
        cursor = {"i": 0}

        async def worker() -> None:
            nonlocal client_failures
            while cursor["i"] < sc.requests:
                i = cursor["i"]
                cursor["i"] += 1
                if sc.gray is not None and i == gray_after:
                    engines[sc.gray].service_s *= sc.gray_factor
                if i == tail_from:
                    counts_at_tail.extend(
                        r.requests for r in pool.replicas
                    )
                resp = await client.post(
                    "/detect",
                    json={"image_urls": [URL_CYCLE[i % len(URL_CYCLE)]]},
                    headers=headers,
                )
                await resp.read()
                statuses[resp.status] = statuses.get(resp.status, 0) + 1
                if resp.status != 200:
                    client_failures += 1

        with faults.inject(**sc.faults):
            await asyncio.gather(*(worker() for _ in range(sc.concurrency)))

        snap = pool.snapshot()

    for server in servers:
        await server.close()
    for det in dets:
        await det.aclose()

    tail_requests = [
        r["requests"] - (counts_at_tail[j] if j < len(counts_at_tail) else 0)
        for j, r in enumerate(snap["replicas"])
    ]
    tail_total = sum(tail_requests) or 1
    gray_idx = sc.gray if sc.gray is not None else -1
    report = {
        "name": sc.name,
        "statuses": statuses,
        "client_failures": client_failures,
        "replays": snap["pool_replays_total"],
        "hedges": snap["pool_hedges_total"],
        "soft_ejections": snap["pool_soft_ejections_total"],
        "invalid_responses": snap["pool_invalid_responses_total"],
        "gray_state": (
            snap["replicas"][gray_idx]["outlier_state"]
            if 0 <= gray_idx < len(snap["replicas"])
            else None
        ),
        "gray_tail_share": (
            tail_requests[gray_idx] / tail_total
            if 0 <= gray_idx < len(tail_requests)
            else 0.0
        ),
        "replica_snapshots": snap["replicas"],
    }
    report["checks"] = evaluate(sc, report)
    report["ok"] = all(report["checks"].values())
    return report


def evaluate(sc: Scenario, report: dict) -> dict:
    """Invariant name -> bool for every invariant the scenario declares."""
    checks: dict[str, bool] = {}
    for key, want in sc.invariants.items():
        if key == "client_failures":
            checks[key] = report["client_failures"] == want
        elif key == "soft_ejections":
            checks[key] = report["soft_ejections"] == want
        elif key == "invalid_responses":
            checks[key] = report["invalid_responses"] == want
        elif key == "replays_gt":
            checks[key] = report["replays"] > want
        elif key == "gray_detected":
            # gray OR already recovering through canary counts as detected
            checks[key] = (
                report["gray_state"] in ("gray", "canary")
                and report["soft_ejections"] >= 1
            ) == want
        elif key == "gray_tail_share_lt":
            checks[key] = report["gray_tail_share"] < want
        else:
            raise ValueError(f"unknown invariant {key!r} in {sc.name}")
    return checks


# ---------------------------------------------------------------------------
# deployment drills (ISSUE 15)


@dataclass
class DeployScenario:
    """One deterministic deployment drill: a full rollout attempt over an
    in-process stub fleet under live load.

    `bad` names the new version's defect: None (a good deploy that must
    promote every wave), "slow" (service time x `slow_factor` — the p99
    verdict), "flaky" (`flaky_pct`% deterministic 500s scoped to the
    canary — the error-rate verdict), "corrupt" (every canary frame
    corrupted post-encode; clients negotiate frames so the edge CRC
    validator feeds the error-rate verdict), or "diff" (the canary answers
    DIFFERENT detections — only the shadow lane can see it).
    `invariants` are exact checks over the final report."""

    name: str
    replicas: int = 3
    concurrency: int = 4
    service_ms: float = 5.0
    bad: str | None = None
    slow_factor: float = 10.0
    flaky_pct: int = 20
    frame: bool = False
    window_s: float = 1.2
    confirm_window_s: float = 0.5
    min_requests: int = 8
    shadow_pct: float = 50.0
    canary_weight: float = 0.1
    tail_requests: int = 10  # post-terminal probes: the fleet still serves
    invariants: dict = field(default_factory=dict)


DEPLOY_MATRIX = [
    DeployScenario(
        name="good-deploy",
        invariants={
            "client_failures": 0,
            "state": "done",
            "fleet_all_v2": True,
            "promoted_rollouts": 1,
        },
    ),
    DeployScenario(
        name="bad-deploy-slow",
        bad="slow",
        invariants={
            "client_failures": 0,
            "state": "rolled_back",
            "reason": "p99_vs_baseline",
            "canary_gone": True,
            "fleet_size": 3,
            "rolled_back_rollouts": 1,
            "trace_pinned": True,
        },
    ),
    DeployScenario(
        name="bad-deploy-flaky",
        bad="flaky",
        invariants={
            "client_failures": 0,
            "state": "rolled_back",
            "reason": "error_rate",
            "canary_gone": True,
            "fleet_size": 3,
            "trace_pinned": True,
        },
    ),
    DeployScenario(
        name="bad-deploy-corrupt",
        bad="corrupt",
        frame=True,
        invariants={
            "client_failures": 0,
            "state": "rolled_back",
            "reason": "error_rate",
            "invalid_responses_gt": 0,
            "canary_gone": True,
            "trace_pinned": True,
        },
    ),
    DeployScenario(
        name="bad-deploy-wrong-output",
        bad="diff",
        invariants={
            "client_failures": 0,
            "state": "rolled_back",
            "reason": "shadow_diff",
            "canary_gone": True,
            "trace_pinned": True,
        },
    ),
]


class _InProcMember:
    """In-process rollout member handle: a real aiohttp TestServer over a
    stub detector, closable from the controller's retire path."""

    def __init__(self, server, det, version: str) -> None:
        self.server = server
        self.det = det
        self.version = version
        self.url = f"http://{server.host}:{server.port}"

    async def shutdown(self) -> None:
        await self.server.close()
        await self.det.aclose()


async def _spawn_stub_member(
    replica_id: str, version: str, service_ms: float,
    detections: list | None = None,
) -> "_InProcMember":
    from aiohttp.test_utils import TestServer

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.serving.standalone import make_app
    from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient

    engine = StubEngine(service_ms=service_ms, detections=detections)
    engine.metrics.set_identity(replica_id=replica_id, version=version)
    engine.metrics.set_identity(weights_digest=engine.weights_digest())
    det = AmenitiesDetector(
        engine, MicroBatcher(engine, max_delay_ms=1.0), StubHttpClient()
    )
    server = TestServer(make_app(detector=det))
    await server.start_server()
    return _InProcMember(server, det, version)


async def run_deploy_scenario(sc: DeployScenario) -> dict:
    """Execute one deployment drill; returns the report dict."""
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu import obs
    from spotter_tpu.obs.aggregate import FleetAggregator
    from spotter_tpu.serving import wire
    from spotter_tpu.serving.replica_pool import ReplicaPool
    from spotter_tpu.serving.rollout import DONE, ROLLED_BACK, RolloutController
    from spotter_tpu.serving.router import make_router_app
    from spotter_tpu.testing.stub_engine import STUB_DETECTIONS

    obs.reset_recorder()  # scenario isolation for the pinned-trace check
    members = [
        await _spawn_stub_member(f"deploy-r{i}", "v1", sc.service_ms)
        for i in range(sc.replicas)
    ]
    pool = ReplicaPool(
        [m.url for m in members], health_interval_s=0.05
    )
    for m in members:
        pool.set_version(m.url, "v1")
    aggregator = FleetAggregator(
        lambda: [r.url for r in pool.replicas], interval_s=0.2
    )

    canary_service = sc.service_ms * (
        sc.slow_factor if sc.bad == "slow" else 1.0
    )
    canary_detections = (
        [{"label": "oven", "score": 0.4, "box": [1.0, 1.0, 9.0, 9.0]}]
        if sc.bad == "diff"
        else None
    )

    def spawner():
        return _spawn_stub_member(
            "deploy-canary", "v2", canary_service, canary_detections
        )

    controller = RolloutController(
        pool,
        members=list(members),
        spawner=spawner,
        version_to="v2",
        version_from="v1",
        aggregator=aggregator,
        canary_weight=sc.canary_weight,
        window_s=sc.window_s,
        confirm_window_s=sc.confirm_window_s,
        min_requests=sc.min_requests,
        max_error_rate=0.05,
        shadow_pct=sc.shadow_pct,
        drain_deadline_ms=2000.0,
        spawn_wait_s=10.0,
        tick_s=0.05,
    )
    app = make_router_app(pool, aggregator=aggregator, rollout=controller)

    fault_plan = {}
    if sc.bad == "flaky":
        fault_plan = {"flaky": sc.flaky_pct, "only_replica": "deploy-canary"}
    elif sc.bad == "corrupt":
        fault_plan = {"corrupt_frame": -1, "only_replica": "deploy-canary"}

    client_failures = 0
    requests_done = 0
    statuses: dict[int, int] = {}
    headers = {"Accept": wire.FRAME_CONTENT_TYPE} if sc.frame else {}

    async with TestClient(TestServer(app)) as client:

        async def one_request(i: int) -> None:
            nonlocal client_failures, requests_done
            resp = await client.post(
                "/detect",
                json={"image_urls": [URL_CYCLE[i % len(URL_CYCLE)]]},
                headers=headers,
            )
            await resp.read()
            requests_done += 1
            statuses[resp.status] = statuses.get(resp.status, 0) + 1
            if resp.status != 200:
                client_failures += 1

        async def worker() -> None:
            i = 0
            while controller.state not in (DONE, ROLLED_BACK):
                await one_request(i)
                i += 1

        with faults.inject(**fault_plan):
            rollout_task = asyncio.create_task(controller.run())
            workers = [
                asyncio.create_task(worker())
                for _ in range(sc.concurrency)
            ]
            await asyncio.wait_for(rollout_task, timeout=60.0)
            await asyncio.gather(*workers)
        # post-terminal probes: the fleet must still serve cleanly after a
        # rollback (old members restored) or a full roll (all new members)
        for i in range(sc.tail_requests):
            await one_request(i)

        pool_snap = pool.snapshot()
        rollout_snap = controller.snapshot()
        await controller.stop()

    # members the controller retired were already shut down by its retire
    # path; everything still in the pool is ours to close
    for m in members + controller.new_members:
        if pool.replica_for(m.url) is not None:
            try:
                await m.shutdown()
            except Exception:
                pass
    await pool.stop()
    await aggregator.stop()

    rec = obs.get_recorder().snapshot()
    pinned = any(
        str(t.get("request_id", "")).startswith("rollout-rollback")
        for t in rec.get("errors", []) + rec.get("ring", [])
    )
    report = {
        "name": sc.name,
        "statuses": statuses,
        "requests": requests_done,
        "client_failures": client_failures,
        "state": rollout_snap["state"],
        "reason": rollout_snap["rollback_reason"],
        "last_verdict": rollout_snap["last_verdict"],
        "rollouts_total": rollout_snap["rollouts_total"],
        "shadow": rollout_snap["shadow"],
        "invalid_responses": pool_snap["pool_invalid_responses_total"],
        "fleet_versions": [r["version"] for r in pool_snap["replicas"]],
        "fleet_size": len(pool_snap["replicas"]),
        "canary_in_pool": any(
            r["url"] == (rollout_snap["canary_url"] or "")
            for r in pool_snap["replicas"]
        ),
        "trace_pinned": pinned,
        "replica_snapshots": pool_snap["replicas"],
    }
    report["checks"] = evaluate_deploy(sc, report)
    report["ok"] = all(report["checks"].values())
    return report


def evaluate_deploy(sc: DeployScenario, report: dict) -> dict:
    """Invariant name -> bool for every invariant the scenario declares."""
    checks: dict[str, bool] = {}
    for key, want in sc.invariants.items():
        if key == "client_failures":
            checks[key] = report["client_failures"] == want
        elif key == "state":
            checks[key] = report["state"] == want
        elif key == "reason":
            checks[key] = report["reason"] == want
        elif key == "canary_gone":
            checks[key] = (not report["canary_in_pool"]) == want
        elif key == "fleet_size":
            checks[key] = report["fleet_size"] == want
        elif key == "fleet_all_v2":
            checks[key] = (
                bool(report["fleet_versions"])
                and all(v == "v2" for v in report["fleet_versions"])
            ) == want
        elif key == "promoted_rollouts":
            checks[key] = report["rollouts_total"]["promoted"] == want
        elif key == "rolled_back_rollouts":
            checks[key] = report["rollouts_total"]["rolled_back"] == want
        elif key == "invalid_responses_gt":
            checks[key] = report["invalid_responses"] > want
        elif key == "trace_pinned":
            checks[key] = report["trace_pinned"] == want
        else:
            raise ValueError(f"unknown invariant {key!r} in {sc.name}")
    return checks


# ---------------------------------------------------------------------------
# controller chaos drills (ISSUE 16)


@dataclass
class ControllerScenario:
    """One crash-safe control-plane drill: REAL controller processes
    (`python -m spotter_tpu.serving.reconcile`) over REAL supervised stub
    replicas, killed/paused/corrupted at deterministic points.

    Topology: an optional fleet-managed "spot" pool (the controller spawns
    and maintains it from the journaled desired state) plus an optional
    rollout-managed "serve" pool (`serve_size` v1 members the HARNESS
    spawns — they register in the endpoints manifest, so any controller
    finds them). The chaos point is either observed (`kill_at_rollout_state`:
    SIGKILL the leader the moment its status file shows that rollout
    state; `pause_leader`: SIGSTOP past the lease TTL, then SIGCONT) or
    tick-deterministic (`faults`: a SPOTTER_TPU_FAULTS plan for the FIRST
    controller — `controller_crash=<tick>` self-SIGKILLs, `journal_corrupt=1`
    flips a journal byte first). A successor controller then takes the
    lease and must adopt, resume/rollback, rebuild, or fence per the
    scenario's invariants."""

    name: str
    spot_size: int = 0
    serve_size: int = 0
    rollout_to: str = ""
    rollout_window_s: float = 2.5
    kill_at_rollout_state: str | None = None
    wait_before_successor_s: float = 0.0  # let a journaled window expire
    faults: str = ""
    pause_leader: bool = False
    converge_timeout_s: float = 15.0
    invariants: dict = field(default_factory=dict)


CONTROLLER_MATRIX = [
    ControllerScenario(
        # kill -9 mid-canary with window time left: the successor must
        # re-adopt the live canary from the manifest and serve out the
        # REMAINING window, then finish the rollout — 1 fresh spawn (the
        # second wave's canary), everything else adopted.
        name="crash-mid-rollout-resume",
        spot_size=1,
        serve_size=2,
        rollout_to="v2",
        rollout_window_s=2.5,
        kill_at_rollout_state="canary",
        converge_timeout_s=25.0,
        invariants={
            "rollout_resumes": 1,
            "rollout_result": "done",
            "adopted_all": True,
            "spawns": 1,
            "journal_rebuilds": 0,
            "serve_versions": ["v2", "v2"],
            "converged": True,
        },
    ),
    ControllerScenario(
        # kill -9 mid-canary and let the journaled verdict window EXPIRE
        # before the successor starts: the canary carried live weight with
        # nobody watching, so the only safe resume is rollback.
        name="crash-expired-window-rollback",
        spot_size=1,
        serve_size=1,
        rollout_to="v2",
        rollout_window_s=1.0,
        kill_at_rollout_state="canary",
        wait_before_successor_s=2.0,
        invariants={
            "rollout_resumes": 1,
            "rollout_result": "rolled_back",
            "adopted_all": True,
            "spawns": 0,
            "serve_versions": ["v1"],
            "converged": True,
        },
    ),
    ControllerScenario(
        # kill -9 mid-preemption-storm: preempt files written, children
        # exiting 83, THEN the controller dies — the classic lingering-
        # marker trap. The successor must adopt every live supervisor
        # (0 double-spawns), clear the stale markers, and reconverge.
        name="crash-mid-storm",
        spot_size=3,
        invariants={
            "adoptions": 3,
            "adopted_all": True,
            "spawns": 0,
            "journal_rebuilds": 0,
            "converged": True,
        },
    ),
    ControllerScenario(
        # journal_corrupt flips a byte of the leader's own journal, then
        # controller_crash SIGKILLs it: the successor's load must FAIL the
        # CRC (detected, not replayed), count one rebuild-from-observation,
        # and re-seed desired state from the manifest it can verify.
        name="journal-corrupt-rebuild",
        spot_size=2,
        faults="journal_corrupt=1,controller_crash=3",
        invariants={
            "journal_rebuilds": 1,
            "adoptions": 2,
            "adopted_all": True,
            "spawns": 0,
            "converged": True,
        },
    ),
    ControllerScenario(
        # stale-leader fencing: SIGSTOP the leader past its TTL, let the
        # standby take over (epoch +1), SIGCONT the old leader — its next
        # actuation-boundary check must raise StaleLeaderError (counted)
        # and demote it, never touch the fleet.
        name="stale-leader-fencing",
        spot_size=1,
        pause_leader=True,
        invariants={
            "fencing_rejections_ge": 1,
            "old_leader_demoted": True,
            "epoch_monotonic": True,
            "converged": True,
        },
    ),
]


class ControllerProc:
    """One controller subprocess + its status-file protocol."""

    def __init__(self, workdir: str, state_dir: str, manifest: str,
                 owner: str, extra_args: list | None = None,
                 faults_spec: str = "") -> None:
        import subprocess
        import sys

        from spotter_tpu.testing import cluster

        self.owner = owner
        self.status_path = f"{state_dir}/status-{owner}.json"
        self.log_path = f"{workdir}/{owner}.log"
        self._log_file = open(self.log_path, "w")
        cmd = [
            sys.executable, "-m", "spotter_tpu.serving.reconcile",
            "--state-dir", state_dir, "--manifest", manifest,
            "--workdir", workdir, "--owner", owner,
            "--tick", "0.1", "--lease-ttl", "0.8",
        ] + list(extra_args or [])
        env = cluster._hermetic_env(
            {faults.FAULTS_ENV: faults_spec} if faults_spec else None
        )
        self.proc = subprocess.Popen(
            cmd, env=env, cwd=cluster.REPO_ROOT,
            stdout=self._log_file, stderr=subprocess.STDOUT, text=True,
        )

    def status(self) -> dict:
        import json

        try:
            with open(self.status_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def wait_status(self, pred, timeout_s: float, what: str) -> dict:
        import time as _time

        deadline = _time.monotonic() + timeout_s
        last: dict = {}
        while _time.monotonic() < deadline:
            last = self.status()
            try:
                if last and pred(last):
                    return last
            except (KeyError, TypeError, AttributeError):
                pass
            if self.proc.poll() is not None and not last:
                break
            _time.sleep(0.05)
        raise TimeoutError(
            f"{self.owner}: {what} not reached in {timeout_s} s "
            f"(last status: {last}, exit: {self.proc.poll()})"
        )

    def sigkill(self) -> None:
        import signal as _signal

        self.proc.send_signal(_signal.SIGKILL)
        self.proc.wait()

    def sigstop(self) -> None:
        import signal as _signal

        self.proc.send_signal(_signal.SIGSTOP)

    def sigcont(self) -> None:
        import signal as _signal

        self.proc.send_signal(_signal.SIGCONT)

    def shutdown(self, timeout_s: float = 10.0) -> None:
        import signal as _signal
        import subprocess

        if self.proc.poll() is None:
            self.proc.send_signal(_signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log_file.close()


def _teardown_members(manifest_path: str) -> None:
    """Best-effort fleet teardown: SIGTERM every registered supervisor
    (it forwards to its child and deregisters), then SIGKILL stragglers."""
    import signal as _signal
    import time as _time

    from spotter_tpu.serving.statestore import (
        EndpointsManifest,
        supervisor_alive,
    )

    manifest = EndpointsManifest(manifest_path)
    pids = [
        int(e.get("supervisor_pid") or 0)
        for e in manifest.entries().values()
    ]
    for pid in pids:
        if supervisor_alive(pid):
            try:
                import os as _os

                _os.kill(pid, _signal.SIGTERM)
            except OSError:
                pass
    deadline = _time.monotonic() + 10.0
    while _time.monotonic() < deadline and any(
        supervisor_alive(p) for p in pids
    ):
        _time.sleep(0.1)
    for pid in pids:
        if supervisor_alive(pid):
            try:
                import os as _os

                _os.kill(pid, _signal.SIGKILL)
            except OSError:
                pass


def run_controller_scenario(sc: ControllerScenario, workdir: str) -> dict:
    """Execute one controller chaos drill in `workdir`; returns the
    report dict (see `evaluate_controller`)."""
    import os as _os
    import time as _time

    from spotter_tpu.serving.statestore import EndpointsManifest
    from spotter_tpu.testing import cluster

    sc_dir = _os.path.join(workdir, sc.name)
    state_dir = _os.path.join(sc_dir, "state")
    members_dir = _os.path.join(sc_dir, "members")
    _os.makedirs(state_dir, exist_ok=True)
    _os.makedirs(members_dir, exist_ok=True)
    manifest_path = _os.path.join(sc_dir, "endpoints.json")
    manifest = EndpointsManifest(manifest_path)

    ctl_args = []
    if sc.spot_size:
        ctl_args += ["--pool", f"spot={sc.spot_size}"]
    if sc.serve_size:
        ctl_args += [
            "--serve-pool", "serve", "--serve-size", str(sc.serve_size),
            "--serve-version", "v1",
        ]
    if sc.rollout_to:
        ctl_args += [
            "--rollout-to", sc.rollout_to,
            "--rollout-window", str(sc.rollout_window_s),
            "--rollout-min-requests", "0",
            "--drain-ms", "500",
        ]

    serve_members = []
    controllers: list[ControllerProc] = []
    report: dict = {"name": sc.name}
    try:
        # harness-spawned v1 serve members (the rollout's old cohort)
        spawn_v1 = cluster.rollout_spawner(
            members_dir, "v1", pool="serve", manifest=manifest_path
        )
        for _ in range(sc.serve_size):
            serve_members.append(spawn_v1())
        for m in serve_members:
            cluster.wait_ready(m.url)
        a = ControllerProc(sc_dir, state_dir, manifest_path, "ctrl-a",
                           ctl_args, faults_spec=sc.faults)
        controllers.append(a)

        def _spot_ready(st: dict) -> bool:
            return (
                st.get("phase") == "leading"
                and st["reconcile"]["drift"].get("spot") == 0
            )

        if sc.faults:
            # tick-deterministic death: the fault plan kills A itself
            import subprocess

            try:
                a.proc.wait(timeout=30.0)
            except subprocess.TimeoutExpired:
                raise TimeoutError(
                    f"{sc.name}: fault plan {sc.faults!r} never killed "
                    "the first controller"
                ) from None
            report["first_exit"] = a.proc.poll()
        elif sc.kill_at_rollout_state:
            a.wait_status(
                lambda st: (st.get("rollout") or {}).get("state")
                == sc.kill_at_rollout_state,
                30.0, f"rollout state {sc.kill_at_rollout_state}",
            )
            a.sigkill()
        elif sc.pause_leader:
            a.wait_status(_spot_ready, 30.0, "spot pool converged")
        else:
            # crash-mid-storm: converge, storm half the pool via the
            # members' maintenance files, then kill -9 the leader while
            # the storm is still in flight
            a.wait_status(_spot_ready, 30.0, "spot pool converged")
            stormed = 0
            for url, entry in sorted(manifest.entries().items()):
                if entry.get("pool") != "spot" or stormed >= 2:
                    continue
                pf = entry.get("preempt_file") or ""
                if pf:
                    tmp = f"{pf}.tmp"
                    with open(tmp, "w") as f:
                        f.write("injected preemption storm")
                    _os.replace(tmp, pf)
                    stormed += 1
            report["stormed"] = stormed
            _time.sleep(0.4)  # children draining/exiting 83 right now
            a.sigkill()

        if sc.wait_before_successor_s:
            _time.sleep(sc.wait_before_successor_s)
        report["alive_at_takeover"] = sum(
            1 for e in manifest.entries().values()
            if _supervisor_alive(e)
        )

        b = ControllerProc(sc_dir, state_dir, manifest_path, "ctrl-b",
                           ctl_args)
        controllers.append(b)

        if sc.pause_leader:
            a.sigstop()
            b.wait_status(
                lambda st: st.get("phase") == "leading",
                30.0, "standby takeover",
            )
            a.sigcont()
            a_status = a.wait_status(
                lambda st: st.get("phase") == "deposed"
                and st["reconcile"]["fencing_rejections_total"] >= 1,
                15.0, "stale leader fenced",
            )
            report["old_leader"] = a_status

        def _converged(st: dict) -> bool:
            if st.get("phase") != "leading":
                return False
            rec = st["reconcile"]
            if sc.spot_size and rec["drift"].get("spot") != 0:
                return False
            if sc.rollout_to and st.get("rollout_result") is None:
                return False
            return bool(rec["converged"])

        t0 = _time.monotonic()
        final = b.wait_status(
            _converged, sc.converge_timeout_s, "successor convergence"
        )
        report["converge_s"] = _time.monotonic() - t0
        report["converged"] = True
        report["successor"] = final
        report["serve_versions"] = sorted(
            str(e.get("version") or "")
            for e in manifest.entries().values()
            if e.get("pool") == "serve" and _supervisor_alive(e)
        )
    except TimeoutError as exc:
        report["converged"] = False
        report["error"] = str(exc)
        report.setdefault("alive_at_takeover", None)
        report.setdefault("successor", controllers[-1].status()
                          if controllers else {})
        report.setdefault("serve_versions", [])
    finally:
        for ctl in controllers:
            ctl.shutdown()
        _teardown_members(manifest_path)
        for m in serve_members:
            try:
                m.shutdown(timeout_s=2.0)
            except Exception:
                pass

    report["checks"] = evaluate_controller(sc, report)
    report["ok"] = all(report["checks"].values())
    return report


def _supervisor_alive(entry: dict) -> bool:
    from spotter_tpu.serving.statestore import supervisor_alive

    return supervisor_alive(int(entry.get("supervisor_pid") or 0))


def evaluate_controller(sc: ControllerScenario, report: dict) -> dict:
    """Invariant name -> bool for every invariant the scenario declares."""
    succ = (report.get("successor") or {}).get("reconcile") or {}
    old = (report.get("old_leader") or {})
    checks: dict[str, bool] = {}
    for key, want in sc.invariants.items():
        if key == "rollout_resumes":
            checks[key] = succ.get("rollout_resumes_total") == want
        elif key == "rollout_result":
            checks[key] = (
                report.get("successor", {}).get("rollout_result") == want
            )
        elif key == "adoptions":
            checks[key] = succ.get("adoptions_total") == want
        elif key == "adopted_all":
            checks[key] = (
                succ.get("adoptions_total") == report.get("alive_at_takeover")
            ) == want
        elif key == "spawns":
            checks[key] = succ.get("spawns_total") == want
        elif key == "journal_rebuilds":
            checks[key] = succ.get("journal_rebuilds_total") == want
        elif key == "serve_versions":
            checks[key] = report.get("serve_versions") == want
        elif key == "converged":
            checks[key] = report.get("converged") == want
        elif key == "fencing_rejections_ge":
            checks[key] = (
                (old.get("reconcile") or {}).get("fencing_rejections_total", 0)
                >= want
            )
        elif key == "old_leader_demoted":
            checks[key] = (old.get("phase") == "deposed") == want
        elif key == "epoch_monotonic":
            # takeover must FENCE: strictly higher epoch than the deposed
            # leader ever held
            succ_epoch = report.get("successor", {}).get("epoch", 0)
            checks[key] = (succ_epoch > old.get("epoch", 0) >= 1) == want
        else:
            raise ValueError(f"unknown invariant {key!r} in {sc.name}")
    return checks


# ---------------------------------------------------------------------------
# output-integrity drills (ISSUE 17)


@dataclass
class IntegrityScenario:
    """One deterministic silent-data-corruption drill.

    Same in-process topology as the gray matrix — N stub replicas behind
    the real pool + router — but with the ISSUE 17 integrity plane armed:
    every replica passes verified readiness (attest + golden probe via a
    real `IntegrityPlane`) before joining the pool, and the router runs a
    `QuorumSampler` with drill-fast knobs. Corruption shapes:

    `sdc`: that replica index starts answering plausible garbage for the
    WHOLE load (the `faults.py sdc=<pct>` seam, scoped by replica id) —
    the quorum must hard-quarantine it with bounded wrong-answer exposure
    and zero client failures. `corrupt_weights` / `corrupt_compile_cache`:
    that replica index is corrupted BEFORE verification (flipped weight
    leaf / poisoned compile-cache restore) — it must exit 86 at the
    readiness gate and never serve one request. `gray` + fleet `faults`
    (flaky): the false-positive row — a slow-but-correct replica plus
    masked 500s must produce ZERO quarantines.

    Wrong answers are counted exactly: the stub's detections are a
    deterministic function of input content (ISSUE 17 bugfix), so each
    URL's honest answer is captured once before any fault is armed and
    every load response is compared against it with the shared
    obs/compare.py tolerance comparator."""

    name: str
    requests: int = 160
    concurrency: int = 4
    replicas: int = 4
    service_ms: float = 2.0
    sdc: int | None = None
    corrupt_weights: int | None = None
    corrupt_compile_cache: int | None = None
    gray: int | None = None
    gray_factor: float = 20.0
    gray_at: float = 0.3
    faults: dict = field(default_factory=dict)
    quorum_pct: float = 50.0
    invariants: dict = field(default_factory=dict)


INTEGRITY_MATRIX = [
    IntegrityScenario(
        name="sdc-replica",
        sdc=0,
        invariants={
            "client_failures": 0,
            "sdc_quarantined": True,
            # bounded exposure: wrong answers stop at the quarantine, so
            # the count stays well under the corrupt replica's fair share
            # of the load (~40 of 160)
            "wrong_answers_lt": 40,
            "exits_86": 0,
        },
    ),
    IntegrityScenario(
        name="corrupt-weights",
        corrupt_weights=1,
        requests=100,
        invariants={
            "client_failures": 0,
            "exits_86": 1,  # caught at the attestation gate
            "corrupt_served": 0,  # ... BEFORE a single request
            "wrong_answers": 0,
            "quarantines": 0,
        },
    ),
    IntegrityScenario(
        name="corrupt-compile-cache",
        corrupt_compile_cache=2,
        requests=100,
        invariants={
            "client_failures": 0,
            "exits_86": 1,  # attest is clean; the golden probe catches it
            "corrupt_served": 0,
            "wrong_answers": 0,
            "quarantines": 0,
        },
    ),
    IntegrityScenario(
        name="false-positive-immunity",
        gray=1,
        faults={"flaky": 5},
        requests=140,
        invariants={
            # slow-but-correct + masked flaky 500s must charge nothing:
            # wrong answers quarantine, slowness and transport errors never
            "client_failures": 0,
            "quarantines": 0,
            "exits_86": 0,
            "wrong_answers": 0,
        },
    ),
]


async def run_integrity_scenario(sc: IntegrityScenario) -> dict:
    """Execute one integrity drill; returns the report dict."""
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.obs import compare
    from spotter_tpu.obs.aggregate import FleetAggregator
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.serving.integrity import IntegrityPlane, QuorumSampler
    from spotter_tpu.serving.replica_pool import ReplicaPool
    from spotter_tpu.serving.router import make_router_app
    from spotter_tpu.serving.standalone import make_app
    from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient

    exits: list[int] = []
    engines, dets, servers, urls = [], [], [], []
    corrupt_ids: list[str] = []
    for i in range(sc.replicas):
        engine = StubEngine(service_ms=sc.service_ms)
        engine.metrics.set_identity(replica_id=f"integ-r{i}")
        det = AmenitiesDetector(
            engine, MicroBatcher(engine, max_delay_ms=1.0), StubHttpClient()
        )
        if sc.corrupt_weights == i:
            engine.corrupt_weights(1)
        # verified readiness (the standalone _bring_up gate, run inline):
        # attest + probe must pass before the replica may join the pool;
        # a failure is the exit-86 path and the replica never serves
        plane = IntegrityPlane(
            det.engine, det.batcher, family="stub",
            probe_interval_s=0, attest_interval_s=0, exit_cb=exits.append,
        )
        if sc.corrupt_compile_cache == i:
            # poisoned compile-cache restore: the weights attest CLEAN but
            # the restored executable answers wrong — only the probe sees it
            with faults.inject(corrupt_compile_cache=1):
                ok = await plane.verify("warm-restore")
        else:
            ok = await plane.verify("cold-start")
        engines.append(engine)
        dets.append(det)
        if not ok:
            plane.integrity_exit(plane.last_error or "integrity")
            corrupt_ids.append(engine.metrics.replica_id)
            continue
        server = TestServer(make_app(detector=det))
        await server.start_server()
        servers.append(server)
        urls.append(f"http://{server.host}:{server.port}")

    pool = ReplicaPool(urls, health_interval_s=0.05, adaptive_hedge=True)
    quorum = QuorumSampler(
        pool,
        pct=sc.quorum_pct,
        # drill-fast evidence knobs: same machinery, a ~1 s scenario must
        # converge. alpha .5 / threshold .6 -> two charged disagreements
        # past min_samples trip the quarantine.
        ewma_threshold=0.6,
        min_samples=3,
        alpha=0.5,
    )
    aggregator = FleetAggregator(lambda: [], interval_s=0.0)
    router_app = make_router_app(pool, aggregator=aggregator, quorum=quorum)

    plan = dict(sc.faults)
    if sc.sdc is not None:
        plan.update(sdc=100, only_replica=f"integ-r{sc.sdc}")

    gray_after = int(sc.requests * sc.gray_at)
    client_failures = 0
    wrong_answers = 0
    corrupt_served = 0
    statuses: dict[int, int] = {}
    quarantine_at: int | None = None
    expected: dict[str, list] = {}

    async with TestClient(TestServer(router_app)) as client:
        # pin every URL's honest answer BEFORE any fault is armed: the
        # stub's detections are a deterministic function of input content,
        # identical on every honest replica
        for url in URL_CYCLE:
            resp = await client.post("/detect", json={"image_urls": [url]})
            body = await resp.json()
            assert resp.status == 200, (resp.status, body)
            expected[url] = [
                img.get("detections") for img in body.get("images", [])
            ]

        cursor = {"i": 0}

        async def worker() -> None:
            nonlocal client_failures, wrong_answers, quarantine_at
            nonlocal corrupt_served
            while cursor["i"] < sc.requests:
                i = cursor["i"]
                cursor["i"] += 1
                if sc.gray is not None and i == gray_after:
                    engines[sc.gray].service_s *= sc.gray_factor
                url = URL_CYCLE[i % len(URL_CYCLE)]
                resp = await client.post(
                    "/detect", json={"image_urls": [url]}
                )
                statuses[resp.status] = statuses.get(resp.status, 0) + 1
                # a replica that failed verification must never answer:
                # the identity header names who served every response
                served_by = resp.headers.get("X-Spotter-Replica", "")
                if served_by and any(
                    cid in served_by.split(",") for cid in corrupt_ids
                ):
                    corrupt_served += 1
                if resp.status != 200:
                    client_failures += 1
                    await resp.read()
                    continue
                body = await resp.json()
                got = [
                    img.get("detections")
                    for img in body.get("images", [])
                ]
                if not compare.images_equivalent(expected[url], got):
                    wrong_answers += 1
                if (
                    quarantine_at is None
                    and pool.quarantines_total > 0
                ):
                    quarantine_at = i

        with faults.inject(**plan):
            await asyncio.gather(*(worker() for _ in range(sc.concurrency)))
            # let in-flight fire-and-forget quorum samples settle
            await asyncio.sleep(0.1)

        snap = pool.snapshot()
        qsnap = quorum.snapshot()

    for server in servers:
        await server.close()
    for det in dets:
        await det.aclose()

    sdc_url = None
    if sc.sdc is not None and sc.sdc < len(urls):
        sdc_url = urls[sc.sdc]
    report = {
        "name": sc.name,
        "statuses": statuses,
        "client_failures": client_failures,
        "wrong_answers": wrong_answers,
        "quarantines": snap["pool_quarantines_total"],
        "exits_86": exits.count(86),
        "exits": exits,
        "corrupt_served": corrupt_served,
        "quarantine_at": quarantine_at,
        "sdc_quarantined": bool(
            sdc_url is not None
            and any(
                r["url"] == sdc_url and r.get("quarantined")
                for r in snap["replicas"]
            )
        ),
        "quorum": qsnap,
        "replica_snapshots": snap["replicas"],
    }
    report["checks"] = evaluate_integrity(sc, report)
    report["ok"] = all(report["checks"].values())
    return report


def evaluate_integrity(sc: IntegrityScenario, report: dict) -> dict:
    """Invariant name -> bool, same contract as `evaluate`."""
    checks: dict[str, bool] = {}
    for key, want in sc.invariants.items():
        if key == "client_failures":
            checks[key] = report["client_failures"] == want
        elif key == "wrong_answers":
            checks[key] = report["wrong_answers"] == want
        elif key == "wrong_answers_lt":
            checks[key] = report["wrong_answers"] < want
        elif key == "quarantines":
            checks[key] = report["quarantines"] == want
        elif key == "sdc_quarantined":
            checks[key] = report["sdc_quarantined"] == want
        elif key == "exits_86":
            checks[key] = report["exits_86"] == want
        elif key == "corrupt_served":
            checks[key] = report["corrupt_served"] == want
        else:
            raise ValueError(f"unknown invariant {key!r} in {sc.name}")
    return checks


# ---------------------------------------------------------------------------
# tenant-isolation tier (ISSUE 19): noisy-neighbor drills over the real
# router edge with the TenantPlane armed
# ---------------------------------------------------------------------------


@dataclass
class TenantScenario:
    """One deterministic noisy-neighbor drill.

    Same in-process topology — stub replicas behind the real pool +
    router — but the router carries a `TenantPlane` built from
    `config`/`default_rps`, driven by a FROZEN manual clock so token
    buckets never refill mid-drill: a tenant's admit count is EXACTLY
    min(sent, burst), an exact assertion instead of a pacing-dependent
    threshold.

    `load` maps tenant -> base request count; each tenant's load runs
    concurrently with every other's. The abusive shapes come from the
    faults.py ISSUE 19 seams: `tenant_flood=<t>:<x>` multiplies tenant
    `t`'s base count by `x` (the fault IS the client's behavior — the
    serving path is unmodified), and `tenant_retry_storm=<n>` makes the
    flooding tenant fire `n` immediate Retry-After-ignoring re-sends per
    429. `abuser` names the tenant under scrutiny for the occupancy row
    (slow-loris holds connections open rather than flooding, so there is
    no flood fault to name it)."""

    name: str
    config: dict = field(default_factory=dict)
    default_rps: float = 0.0
    load: dict = field(default_factory=dict)
    concurrency: int = 4  # workers PER TENANT
    abuser: str | None = None
    replicas: int = 2
    service_ms: float = 2.0
    faults: dict = field(default_factory=dict)
    invariants: dict = field(default_factory=dict)


TENANT_MATRIX = [
    TenantScenario(
        name="tenant-flood",
        # abuser quota 20 rps (burst 40); honest tenants 200 rps. The
        # flood sends 6x the abuser's base 20 -> 120 requests against a
        # frozen bucket holding exactly 40 tokens.
        config={"abuser": {"rps": 20}, "honest-a": {"rps": 200},
                "honest-b": {"rps": 200}},
        load={"abuser": 20, "honest-a": 30, "honest-b": 30},
        faults={"tenant_flood": "abuser:6"},
        invariants={
            "honest_failures": 0,   # not one in-quota request shed
            "abuser_admits": 40,    # capped at burst, exactly
            "abuser_sheds": 80,     # everything past the burst 429s
        },
    ),
    TenantScenario(
        name="tenant-retry-storm",
        # every 429 is answered with 2 immediate re-sends that ignore
        # Retry-After. Retries must gain NOTHING: admits stay pinned at
        # the burst while the shed counter absorbs the storm.
        config={"abuser": {"rps": 20}, "honest-a": {"rps": 200},
                "honest-b": {"rps": 200}},
        load={"abuser": 20, "honest-a": 30, "honest-b": 30},
        faults={"tenant_flood": "abuser:4", "tenant_retry_storm": 2},
        invariants={
            "honest_failures": 0,
            "abuser_admits": 40,
            "abuser_sheds_gt": 40,  # 40 base sheds + storm amplification
        },
    ),
    TenantScenario(
        name="slow-loris-occupancy",
        # the loris doesn't flood — it OCCUPIES: 6 workers hold slow
        # requests open. Its max_inflight=2 bounds the seats it can take;
        # overflow sheds with kind="inflight" and the honest tenant never
        # waits behind it.
        config={"loris": {"rps": 1000, "max_inflight": 2},
                "honest-a": {"rps": 1000}},
        load={"loris": 30, "honest-a": 30},
        concurrency=6,
        service_ms=20.0,
        abuser="loris",
        invariants={
            "honest_failures": 0,
            "inflight_sheds_gt": 0,
        },
    ),
    TenantScenario(
        name="many-small-tenants",
        # 40 distinct tenant ids churning through: the tracked table grows
        # to 40 but the /metrics view stays bounded at top_k rows plus the
        # "other" overflow bucket — label cardinality is capped by design,
        # not by scrape luck.
        default_rps=50.0,
        load={f"t{i:02d}": 3 for i in range(40)},
        concurrency=1,
        invariants={
            "total_failures": 0,
            "total_sheds": 0,
            "tracked": 40,
            "tenant_rows_lte": 9,  # top_k (8) + "other"
        },
    ),
    TenantScenario(
        name="bursty-in-quota",
        # the false-positive row: a bursty-but-IN-QUOTA tenant dumps its
        # entire burst allowance at once next to a steady neighbor and
        # must see ZERO sheds — "bursty" alone is not abuse.
        config={"bursty": {"rps": 30}, "steady": {"rps": 200}},
        load={"bursty": 60, "steady": 30},  # 60 == bursty's burst, exactly
        invariants={
            "total_failures": 0,
            "total_sheds": 0,
        },
    ),
]


async def run_tenant_scenario(sc: TenantScenario) -> dict:
    """Execute one noisy-neighbor drill; returns the report dict."""
    import random

    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.obs.aggregate import FleetAggregator
    from spotter_tpu.serving import tenancy
    from spotter_tpu.serving.detector import AmenitiesDetector
    from spotter_tpu.serving.replica_pool import ReplicaPool
    from spotter_tpu.serving.router import make_router_app
    from spotter_tpu.serving.standalone import make_app
    from spotter_tpu.testing.stub_engine import StubEngine, StubHttpClient

    engines, dets, servers, urls = [], [], [], []
    for i in range(sc.replicas):
        engine = StubEngine(service_ms=sc.service_ms)
        engine.metrics.set_identity(replica_id=f"tenant-r{i}")
        det = AmenitiesDetector(
            engine, MicroBatcher(engine, max_delay_ms=1.0), StubHttpClient()
        )
        server = TestServer(make_app(detector=det))
        await server.start_server()
        engines.append(engine)
        dets.append(det)
        servers.append(server)
        urls.append(f"http://{server.host}:{server.port}")

    # frozen clock: buckets never refill, so admits == min(sent, burst)
    # exactly; seeded rng pins the Retry-After jitter. trust_header: the
    # drill clients model traffic whose identity an attested edge already
    # resolved — identity spoofing has its own tests; these rows measure
    # isolation BETWEEN known tenants
    plane = tenancy.TenantPlane(
        config=sc.config,
        default_rps=sc.default_rps,
        clock=lambda: 0.0,
        rng=random.Random(0),
        trust_header=True,
    )
    pool = ReplicaPool(urls, health_interval_s=0.05, adaptive_hedge=True)
    aggregator = FleetAggregator(lambda: [], interval_s=0.0)  # determinism
    router_app = make_router_app(
        pool, aggregator=aggregator, tenancy_plane=plane
    )

    per_tenant: dict[str, dict[int, int]] = {
        t: {} for t in sc.load
    }

    with faults.inject(**sc.faults):
        flood = faults.tenant_flood_spec()
        storm_n = faults.tenant_retry_storm_n()
        loads = dict(sc.load)
        if flood is not None:
            flood_tenant, factor = flood
            loads[flood_tenant] = int(loads.get(flood_tenant, 0) * factor)

        async with TestClient(TestServer(router_app)) as client:

            async def one(tenant: str, i: int) -> int:
                resp = await client.post(
                    "/detect",
                    json={"image_urls": [URL_CYCLE[i % len(URL_CYCLE)]]},
                    headers={tenancy.TENANT_HEADER: tenant},
                )
                await resp.read()
                stats = per_tenant[tenant]
                stats[resp.status] = stats.get(resp.status, 0) + 1
                return resp.status

            async def tenant_load(tenant: str, n: int) -> None:
                storming = (
                    flood is not None and tenant == flood[0] and storm_n > 0
                )
                cursor = {"i": 0}

                async def worker() -> None:
                    while cursor["i"] < n:
                        i = cursor["i"]
                        cursor["i"] += 1
                        status = await one(tenant, i)
                        if status == 429 and storming:
                            # the storm IGNORES Retry-After: immediate
                            # re-sends, which must gain nothing
                            for _ in range(storm_n):
                                await one(tenant, i)

                await asyncio.gather(
                    *(worker() for _ in range(sc.concurrency))
                )

            await asyncio.gather(
                *(tenant_load(t, n) for t, n in loads.items())
            )

    snap = plane.snapshot()
    view = plane.metrics_view()

    for server in servers:
        await server.close()
    for det in dets:
        await det.aclose()

    abuser = sc.abuser
    if abuser is None and sc.faults.get("tenant_flood"):
        abuser = str(sc.faults["tenant_flood"]).partition(":")[0]
    honest = [t for t in sc.load if t != abuser]
    arow = snap["tenants"].get(abuser, {}) if abuser else {}
    report = {
        "name": sc.name,
        "per_tenant": per_tenant,
        "abuser": abuser,
        "honest_failures": sum(
            c
            for t in honest
            for s, c in per_tenant[t].items()
            if s != 200
        ),
        "total_failures": sum(
            c
            for stats in per_tenant.values()
            for s, c in stats.items()
            if s != 200
        ),
        "abuser_admits": int(arow.get("admits_total", 0)),
        "abuser_sheds": int(
            arow.get("sheds_rate_total", 0)
            + arow.get("sheds_inflight_total", 0)
        ),
        "inflight_sheds": snap["sheds_total"]["inflight"],
        "total_sheds": sum(snap["sheds_total"].values()),
        "tracked": snap["tracked"],
        "tenant_rows": len(view),
        "plane": snap,
    }
    report["checks"] = evaluate_tenant(sc, report)
    report["ok"] = all(report["checks"].values())
    return report


def evaluate_tenant(sc: TenantScenario, report: dict) -> dict:
    """Invariant name -> bool, same contract as `evaluate`."""
    checks: dict[str, bool] = {}
    for key, want in sc.invariants.items():
        if key == "honest_failures":
            checks[key] = report["honest_failures"] == want
        elif key == "total_failures":
            checks[key] = report["total_failures"] == want
        elif key == "abuser_admits":
            checks[key] = report["abuser_admits"] == want
        elif key == "abuser_sheds":
            checks[key] = report["abuser_sheds"] == want
        elif key == "abuser_sheds_gt":
            checks[key] = report["abuser_sheds"] > want
        elif key == "inflight_sheds_gt":
            checks[key] = report["inflight_sheds"] > want
        elif key == "total_sheds":
            checks[key] = report["total_sheds"] == want
        elif key == "tracked":
            checks[key] = report["tracked"] == want
        elif key == "tenant_rows_lte":
            checks[key] = report["tenant_rows"] <= want
        else:
            raise ValueError(f"unknown invariant {key!r} in {sc.name}")
    return checks


# ---------------------------------------------------------------------------
# model-multiplexed autoscaling drills (ISSUE 20): per-model pools behind
# the real fleet edge, sized by the AutoscalerBrain under scripted demand
# ---------------------------------------------------------------------------


@dataclass
class ScaleScenario:
    """One deterministic autoscaling drill.

    In-process rows (`crash=False`): per-model pools of `_ScaleMember`
    stubs (real aiohttp servers whose /healthz stays 503 for
    `cold_start_s` after a spawn — the compile-cache-restore window)
    behind the REAL `FleetController` + `make_fleet_app` edge with an
    `AutoscalerBrain` attached. `pools` maps model -> config
    (initial/max/cold_start_s/scale_to_zero_s/open_vocab); `phases` is
    the scripted workload: {"send": n, "model": ..., "tenant": ...,
    "concurrency": k}, {"sleep": s}, or {"wait_zero": pool} (bounded
    wait for the idle reclaim). `tenants` arms a frozen-clock
    TenantPlane and `faults` carries the ISSUE 19 flood seams, so the
    flood row proves the brain scales in-quota demand while quotas hold
    the abuser flat.

    The `crash=True` row is the subprocess sibling: a REAL controller
    (`python -m spotter_tpu.serving.reconcile --scale-pool`) journals a
    scale-up, spawns, and is SIGKILLed mid-scale-up; the successor must
    adopt the live members and converge to the JOURNALED size with zero
    double-spawns — run via `run_scale_crash_scenario(sc, workdir)`."""

    name: str
    pools: dict = field(default_factory=dict)
    default_pool: str = "rtdetr"
    phases: list = field(default_factory=list)
    tenants: dict = field(default_factory=dict)
    faults: dict = field(default_factory=dict)
    brain: dict = field(default_factory=dict)  # AutoscalerBrain overrides
    service_ms: float = 2.0
    crash: bool = False
    scale_size: int = 3  # crash row: journaled scale-up target
    converge_timeout_s: float = 60.0
    invariants: dict = field(default_factory=dict)


SCALE_MATRIX = [
    ScaleScenario(
        # a burst of traffic for a model whose pool is COLD (size 0): the
        # first routed request wakes the pool through the brain's fenced
        # demand-restore path, the burst waits out the cold start, and
        # every request completes — time_to_ready measured per restore.
        name="burst-to-cold-model",
        pools={
            "rtdetr": {"initial": 1, "min": 1},
            "yolos": {"initial": 0, "cold_start_s": 0.2},
        },
        phases=[
            {"send": 4, "model": "rtdetr"},
            {"send": 10, "model": "yolos", "concurrency": 5},
        ],
        invariants={
            "client_failures": 0,
            "wakes_ge": 1,
            "ready_ge": {"yolos": 1},
            "routed_correctly": True,
            "time_to_ready_lt": 15.0,
        },
    ),
    ScaleScenario(
        # idle reclaim: a warm pool idle past scale_to_zero_s is drained
        # to zero by the controller's idle timer (chips released); the
        # next routed request restores it through the brain's wake path
        # with the restore timed — and zero client-visible failures.
        name="idle-reclaim",
        pools={
            "rtdetr": {"initial": 1, "min": 1},
            "yolos": {
                "initial": 1, "scale_to_zero_s": 0.8, "cold_start_s": 0.15,
            },
        },
        phases=[
            {"send": 4, "model": "yolos", "concurrency": 1},
            {"wait_zero": "yolos"},
            {"send": 3, "model": "yolos", "concurrency": 1},
        ],
        invariants={
            "client_failures": 0,
            "scale_to_zero": {"yolos": 1},
            "restores": {"yolos": 1},
            "routed_correctly": True,
            "time_to_ready_lt": 15.0,
        },
    ),
    ScaleScenario(
        # flood vs in-quota demand, concurrently: an over-quota tenant
        # floods yolos at 8x its (tiny) quota while an honest tenant runs
        # sustained in-quota load on rtdetr. The quotas shed the flood
        # BEFORE routing, so the brain sees only admitted demand: rtdetr
        # scales UP for the honest tenant, the flooded pool's target
        # stays flat (its admitted trickle is under every threshold),
        # honest traffic never fails, and the brain records explicit
        # flood holds while sheds are rising.
        name="flood-vs-in-quota-demand",
        pools={
            "rtdetr": {"initial": 1, "min": 1, "max": 2},
            "yolos": {"initial": 1, "min": 1, "max": 2},
        },
        tenants={"abuser": {"rps": 1}, "honest": {"rps": 500}},
        faults={"tenant_flood": "abuser:8"},
        brain={"inflight_high": 3.0},
        service_ms=20.0,
        phases=[
            {
                "parallel": [
                    {"send": 12, "model": "yolos", "tenant": "abuser",
                     "concurrency": 6},
                    {"send": 40, "model": "rtdetr", "tenant": "honest",
                     "concurrency": 4},
                ]
            },
            # second flood wave after the honest load: sheds keep rising
            # across policy ticks with zero in-quota yolos demand — the
            # explicit-hold path
            {"sleep": 0.06},
            {"send": 6, "model": "yolos", "tenant": "abuser",
             "concurrency": 6},
            {"sleep": 0.06},
        ],
        invariants={
            "honest_failures": 0,
            "abuser_sheds_gt": 0,
            "scale_ups_ge": 1,       # in-quota rtdetr demand DID scale
            "targets_eq": {"yolos": 1},  # the flooded pool never moved
            "flood_suppressions_ge": 1,
        },
    ),
    ScaleScenario(
        # kill -9 mid-scale-up: the leader journals desired size 3 via the
        # fenced autoscaler path, spawns, and dies before the members are
        # ready. The successor must adopt every live member from the
        # manifest and converge to the JOURNALED size — zero double-spawns.
        name="controller-crash-mid-scale",
        crash=True,
        scale_size=3,
        invariants={
            "adopted_all": True,
            "no_double_spawn": True,
            "journaled_size": 3,
            "converged": True,
        },
    ),
]

class _ScaleMember:
    """In-process managed member for the scale drills: a real aiohttp
    server whose /healthz stays 503 for `cold_start_s` after each spawn
    (the compile-cache-restore window), with the MemberHandle surface the
    FleetController drives. `shutdown` only flips flags — it is called
    from an executor thread by the controller's retire path."""

    def __init__(self, name: str, pool: str, service_s: float,
                 cold_start_s: float) -> None:
        self.name = name
        self.pool = pool
        self.service_s = service_s
        self.cold_start_s = cold_start_s
        self.url = ""
        self.server = None
        self._serving = False
        self._up_at = 0.0
        self.spawns = 0

    async def start(self) -> None:
        from aiohttp import web
        from aiohttp.test_utils import TestServer

        async def detect(request):
            await asyncio.sleep(self.service_s)
            if not self._serving:
                return web.json_response({"error": "down"}, status=503)
            return web.json_response(
                {"served_by": self.name, "pool": self.pool}
            )

        async def healthz(request):
            import time as _time

            if self._serving and _time.monotonic() >= self._up_at:
                return web.json_response({"status": "ok"})
            return web.json_response({"status": "starting"}, status=503)

        app = web.Application()
        app.router.add_post("/detect", detect)
        app.router.add_get("/healthz", healthz)
        self.server = TestServer(app)
        await self.server.start_server()
        self.url = f"http://{self.server.host}:{self.server.port}"

    def spawn(self) -> "_ScaleMember":
        import time as _time

        self._serving = True
        self._up_at = _time.monotonic() + self.cold_start_s
        self.spawns += 1
        return self

    # -- MemberHandle protocol --

    def alive(self) -> bool:
        return True

    def preempt(self) -> None:
        self._serving = False

    def clear_preemption(self) -> None:
        pass

    def shutdown(self, timeout_s: float = 10.0) -> str:
        self._serving = False
        return "stopped"

    async def close(self) -> None:
        if self.server is not None:
            await self.server.close()


async def run_scale_scenario(sc: ScaleScenario) -> dict:
    """Execute one in-process autoscaling drill; returns the report dict
    (see `evaluate_scale`). Crash rows go through
    `run_scale_crash_scenario` instead."""
    import random

    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.obs.aggregate import FleetAggregator
    from spotter_tpu.serving import tenancy
    from spotter_tpu.serving.autoscale import AutoscalerBrain, ModelPool
    from spotter_tpu.serving.fleet import (
        FleetController,
        PoolSpec,
        make_fleet_app,
    )

    if sc.crash:
        raise ValueError(
            f"{sc.name}: crash rows need run_scale_crash_scenario(sc, workdir)"
        )

    # one pre-started stock of members per pool; the spawner pops and
    # "boots" them (cold_start_s of 503 /healthz before ready)
    stocks: dict[str, list[_ScaleMember]] = {}
    all_members: list[_ScaleMember] = []
    specs = []
    model_pools = []
    for pool_name, cfg in sc.pools.items():
        max_size = int(cfg.get("max", 2))
        stock = []
        for i in range(max_size):
            m = _ScaleMember(
                f"{pool_name}-m{i}", pool_name,
                service_s=sc.service_ms / 1000.0,
                cold_start_s=float(cfg.get("cold_start_s", 0.0)),
            )
            await m.start()
            stock.append(m)
            all_members.append(m)
        stocks[pool_name] = stock

        def make_spawner(name=pool_name):
            def spawner():
                st = stocks[name]
                for m in st:
                    if not m._serving:
                        return m.spawn()
                raise RuntimeError(f"pool {name}: stock exhausted")
            return spawner

        specs.append(
            PoolSpec(
                pool_name,
                spawner=make_spawner(),
                target_size=int(cfg.get("initial", 0)),
                scale_to_zero_s=float(cfg.get("scale_to_zero_s", 0.0)),
            )
        )
        model_pools.append(
            ModelPool(
                model=pool_name,
                open_vocab=bool(cfg.get("open_vocab", False)),
                min_size=int(cfg.get("min", 0)),
                max_size=max_size,
                default=pool_name == sc.default_pool,
            )
        )

    controller = FleetController(
        specs,
        tick_s=0.05,
        restore_wait_s=10.0,
        unavailable_wait_s=2.0,
        respawn_base_s=0.05,
        pool_kwargs=dict(
            eject_threshold=1, backoff_base_s=0.05, backoff_max_s=0.2,
            health_interval_s=0.05,
        ),
    )
    plane = None
    if sc.tenants:
        # frozen clock: buckets never refill — admits == min(sent, burst)
        plane = tenancy.TenantPlane(
            config=sc.tenants,
            clock=lambda: 0.0,
            rng=random.Random(0),
            trust_header=True,
        )
    brain = AutoscalerBrain(
        controller,
        model_pools,
        tenancy_plane=plane,
        tick_s=0.05,
        down_steps=3,
        **sc.brain,
    )
    aggregator = FleetAggregator(lambda: [], interval_s=0.0)  # determinism
    app = make_fleet_app(
        controller, aggregator=aggregator, tenancy_plane=plane,
        autoscaler=brain,
    )

    statuses: dict[int, int] = {}
    per_tenant: dict[str, dict[int, int]] = {}
    client_failures = 0
    misrouted = 0

    with faults.inject(**sc.faults):
        flood = faults.tenant_flood_spec()

        async with TestClient(TestServer(app)) as client:
            # initial population must be READY before the script starts —
            # a half-booted warm pool would fail fast (it is not
            # `restoring`, so SLO requests don't wait), which is a boot
            # race, not the behavior under test
            deadline = asyncio.get_running_loop().time() + 10.0
            import time as _time

            def _warm() -> bool:
                return all(
                    controller.pools[n].member_states(_time.monotonic()).get(
                        "ready", 0
                    ) >= int(cfg.get("initial", 0))
                    for n, cfg in sc.pools.items()
                )

            while not _warm():
                if asyncio.get_running_loop().time() > deadline:
                    raise TimeoutError(f"{sc.name}: initial pools not ready")
                await asyncio.sleep(0.02)

            async def one(model: str, tenant, i: int) -> None:
                nonlocal client_failures, misrouted
                headers = {}
                if tenant:
                    headers[tenancy.TENANT_HEADER] = tenant
                resp = await client.post(
                    "/detect",
                    json={
                        "model": model,
                        "image_urls": [URL_CYCLE[i % len(URL_CYCLE)]],
                    },
                    headers=headers,
                )
                body = await resp.json()
                statuses[resp.status] = statuses.get(resp.status, 0) + 1
                if tenant:
                    stats = per_tenant.setdefault(tenant, {})
                    stats[resp.status] = stats.get(resp.status, 0) + 1
                if resp.status != 200:
                    client_failures += 1
                elif body.get("pool") != model:
                    misrouted += 1

            async def send_phase(ph: dict) -> None:
                n = int(ph["send"])
                tenant = ph.get("tenant")
                if (
                    flood is not None and tenant == flood[0]
                ):  # the fault IS the client's behavior
                    n = int(n * flood[1])
                cursor = {"i": 0}

                async def worker() -> None:
                    while cursor["i"] < n:
                        i = cursor["i"]
                        cursor["i"] += 1
                        await one(ph["model"], tenant, i)

                await asyncio.gather(
                    *(worker() for _ in range(int(ph.get("concurrency", 2))))
                )

            async def wait_zero(pool_name: str) -> None:
                fp = controller.pools[pool_name]
                deadline = asyncio.get_running_loop().time() + 10.0
                while not fp.scaled_to_zero:
                    if asyncio.get_running_loop().time() > deadline:
                        raise TimeoutError(
                            f"{sc.name}: {pool_name} never scaled to zero"
                        )
                    await asyncio.sleep(0.05)

            for ph in sc.phases:
                if "send" in ph:
                    await send_phase(ph)
                elif "parallel" in ph:
                    await asyncio.gather(
                        *(send_phase(p) for p in ph["parallel"])
                    )
                elif "sleep" in ph:
                    await asyncio.sleep(float(ph["sleep"]))
                elif "wait_zero" in ph:
                    await wait_zero(ph["wait_zero"])
                else:
                    raise ValueError(f"unknown phase {ph!r} in {sc.name}")

            # settle: requests can complete a beat before the controller
            # tick observes availability (it re-checks the replica pool
            # directly), so wait for restore bookkeeping to land before
            # snapshotting
            settle_deadline = asyncio.get_running_loop().time() + 2.0
            while any(fp.restoring for fp in controller.pools.values()):
                if asyncio.get_running_loop().time() > settle_deadline:
                    break
                await asyncio.sleep(0.05)

            brain_snap = brain.snapshot()
            fleet_snap = controller.snapshot()
            plane_snap = plane.snapshot() if plane is not None else None

    for m in all_members:
        await m.close()

    abuser = None
    if sc.faults.get("tenant_flood"):
        abuser = str(sc.faults["tenant_flood"]).partition(":")[0]
    honest = [t for t in per_tenant if t != abuser]
    arow = (
        (plane_snap or {}).get("tenants", {}).get(abuser, {}) if abuser else {}
    )
    restores = {
        name: p["restores_total"] for name, p in fleet_snap["pools"].items()
    }
    report = {
        "name": sc.name,
        "statuses": statuses,
        "per_tenant": per_tenant,
        "client_failures": client_failures,
        "misrouted": misrouted,
        "honest_failures": sum(
            c
            for t in honest
            for s, c in per_tenant.get(t, {}).items()
            if s != 200
        ),
        "abuser_sheds": int(
            arow.get("sheds_rate_total", 0)
            + arow.get("sheds_inflight_total", 0)
        ),
        "wakes": brain_snap["wakes_total"],
        "scale_ups": brain_snap["scale_ups_total"],
        "flood_suppressions": brain_snap["flood_suppressions_total"],
        "restores": restores,
        "scale_to_zero": {
            name: p["scale_to_zero_total"]
            for name, p in fleet_snap["pools"].items()
        },
        "targets": {
            name: p["desired"] for name, p in brain_snap["pools"].items()
        },
        "ready": {
            name: p["ready"] for name, p in brain_snap["pools"].items()
        },
        "time_to_ready_s": {
            name: p["time_to_ready_s"]
            for name, p in fleet_snap["pools"].items()
        },
        "autoscale": brain_snap,
    }
    report["checks"] = evaluate_scale(sc, report)
    report["ok"] = all(report["checks"].values())
    return report


def run_scale_crash_scenario(sc: ScaleScenario, workdir: str) -> dict:
    """The controller-crash-mid-scale drill: REAL controller processes
    over REAL supervised stub members. ctrl-a seeds one member, then
    journals `--scale-pool rtdetr=<scale_size>` through the fenced
    autoscaler path and spawns; the harness SIGKILLs it the moment the
    status file shows the scale applied (members spawned, not yet ready).
    ctrl-b must adopt every live member and converge to the JOURNALED
    size with zero double-spawns."""
    import os as _os
    import time as _time

    from spotter_tpu.serving.statestore import EndpointsManifest

    pool_name = "rtdetr"
    sc_dir = _os.path.join(workdir, sc.name)
    state_dir = _os.path.join(sc_dir, "state")
    _os.makedirs(state_dir, exist_ok=True)
    manifest_path = _os.path.join(sc_dir, "endpoints.json")
    manifest = EndpointsManifest(manifest_path)

    base_args = ["--pool", f"{pool_name}=1"]
    controllers: list[ControllerProc] = []
    report: dict = {"name": sc.name}
    try:
        a = ControllerProc(
            sc_dir, state_dir, manifest_path, "ctrl-a",
            base_args + ["--scale-pool", f"{pool_name}={sc.scale_size}"],
        )
        controllers.append(a)
        # the scale actuation fires only after the initial population
        # converges; `scaled` in the status marks journal + spawn done —
        # the members themselves are still booting, which is the point
        a.wait_status(
            lambda st: st.get("scaled") is True, 60.0, "scale-up journaled"
        )
        a.sigkill()

        # the spawned supervisors self-register and OUTLIVE the dead
        # controller; give registration a beat so alive_at_takeover counts
        # what ctrl-b can actually see in the manifest
        deadline = _time.monotonic() + 15.0
        while _time.monotonic() < deadline:
            alive = sum(
                1 for e in manifest.entries().values() if _supervisor_alive(e)
            )
            if alive >= sc.scale_size:
                break
            _time.sleep(0.1)
        report["alive_at_takeover"] = sum(
            1 for e in manifest.entries().values() if _supervisor_alive(e)
        )

        b = ControllerProc(sc_dir, state_dir, manifest_path, "ctrl-b",
                           base_args)
        controllers.append(b)

        def _converged(st: dict) -> bool:
            if st.get("phase") != "leading":
                return False
            rec = st["reconcile"]
            if rec["drift"].get(pool_name) != 0:
                return False
            pools = (st.get("fleet") or {}).get("pools") or {}
            psnap = pools.get(pool_name) or {}
            return (
                bool(rec["converged"])
                and psnap.get("size") == sc.scale_size
                and psnap.get("state", {}).get("ready") == sc.scale_size
            )

        t0 = _time.monotonic()
        final = b.wait_status(
            _converged, sc.converge_timeout_s, "successor convergence"
        )
        report["converge_s"] = _time.monotonic() - t0
        report["converged"] = True
        report["successor"] = final
        report["live_members"] = sum(
            1
            for e in manifest.entries().values()
            if e.get("pool") == pool_name and _supervisor_alive(e)
        )
    except TimeoutError as exc:
        report["converged"] = False
        report["error"] = str(exc)
        report.setdefault("alive_at_takeover", None)
        report.setdefault(
            "successor", controllers[-1].status() if controllers else {}
        )
        report.setdefault("live_members", None)
    finally:
        for ctl in controllers:
            ctl.shutdown()
        _teardown_members(manifest_path)

    report["checks"] = evaluate_scale(sc, report)
    report["ok"] = all(report["checks"].values())
    return report


def evaluate_scale(sc: ScaleScenario, report: dict) -> dict:
    """Invariant name -> bool, same contract as `evaluate`."""
    succ = (report.get("successor") or {}).get("reconcile") or {}
    checks: dict[str, bool] = {}
    for key, want in sc.invariants.items():
        if key == "client_failures":
            checks[key] = report["client_failures"] == want
        elif key == "honest_failures":
            checks[key] = report["honest_failures"] == want
        elif key == "abuser_sheds_gt":
            checks[key] = report["abuser_sheds"] > want
        elif key == "wakes_ge":
            checks[key] = report["wakes"] >= want
        elif key == "scale_ups_ge":
            checks[key] = report["scale_ups"] >= want
        elif key == "flood_suppressions_ge":
            checks[key] = report["flood_suppressions"] >= want
        elif key == "routed_correctly":
            checks[key] = (report["misrouted"] == 0) == want
        elif key == "ready_ge":
            checks[key] = all(
                report["ready"].get(p, 0) >= n for p, n in want.items()
            )
        elif key == "targets_eq":
            checks[key] = all(
                report["targets"].get(p) == n for p, n in want.items()
            )
        elif key == "restores":
            checks[key] = all(
                report["restores"].get(p) == n for p, n in want.items()
            )
        elif key == "scale_to_zero":
            checks[key] = all(
                report["scale_to_zero"].get(p) == n for p, n in want.items()
            )
        elif key == "time_to_ready_lt":
            # at least one measured restore, and every one under the bound
            timed = [
                t for t in report["time_to_ready_s"].values() if t is not None
            ]
            checks[key] = bool(timed) and max(timed) < want
        elif key == "adopted_all":
            checks[key] = (
                succ.get("adoptions_total") == report.get("alive_at_takeover")
            ) == want
        elif key == "no_double_spawn":
            # every live member is either adopted or a fresh spawn filling
            # the journaled size — never one more than the journal asks
            alive = report.get("alive_at_takeover")
            spawned = succ.get("spawns_total")
            checks[key] = (
                alive is not None
                and spawned == sc.scale_size - alive
                and report.get("live_members") == sc.scale_size
            ) == want
        elif key == "journaled_size":
            pools = (
                (report.get("successor") or {}).get("fleet") or {}
            ).get("pools") or {}
            checks[key] = (pools.get("rtdetr") or {}).get("target_size") == want
        elif key == "converged":
            checks[key] = report.get("converged") == want
        else:
            raise ValueError(f"unknown invariant {key!r} in {sc.name}")
    return checks


