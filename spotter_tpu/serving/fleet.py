"""Fleet controller: spot-aware pools above supervisor + replica_pool + router.

PRs 1-4 made ONE replica survivable (drain/exit-83 lifecycle, supervisor,
engine fault domain); the fleet above it was still a flat list — one
correlated preemption wave, the NORMAL failure mode of spot/preemptible TPU
capacity (Spotlight, arXiv:2606.19004), took down SLO and bulk traffic alike
and then amplified the damage with unbudgeted replays. This module is the
tier that makes preemptible capacity first-class (DeepServe,
arXiv:2501.14417, is the blueprint for the serverless half):

- **Pools**: replicas are grouped into `on_demand` and `spot` pools, each a
  `ReplicaPool` (health loop, ejection, replay) with its own retry-budget
  slice, supervised members, and gauges. Requests are CLASSED — an
  `X-Request-Class: slo|bulk` header or a `request_class` payload key (a
  payload carrying `deadline_ms` defaults to slo) — and SLO traffic is
  PINNED to on_demand while bulk drains to spot. Bulk never spills onto the
  on_demand pool while spot capacity exists: protecting the SLO pool from a
  bulk stampede is the point of the split. (Bulk falls back to on_demand
  only when NO spot capacity is configured at all.)
- **Preemption-storm survival**: a maintenance signal on a spot member
  (exit 83, SPOTTER_TPU_PREEMPTION_FILE/_URL — the PR 2 machinery) drains
  only that member; its in-flight and queued work replays onto survivors
  under the pool's retry budget (SPOTTER_TPU_RETRY_BUDGET_PCT,
  replica_pool.RetryBudget), so spot loss degrades bulk goodput but never
  fails an SLO request. Members whose SUPERVISOR process dies (crash-loop
  exit 84, host gone) are re-spawned with full-jittered exponential backoff
  so a storm's restarts don't thunder-herd. The chaos harness can inject a
  storm in-process: `SPOTTER_TPU_FAULTS=preempt_storm=N` preempts N ready
  spot members through their handles (testing/faults.py).
- **Scale-to-zero + restore**: a managed pool idle for
  `SPOTTER_TPU_SCALE_TO_ZERO_S` drains and stops all members; the next
  classed request triggers a demand restore through the persistent compile
  cache (`lifecycle.compile_cache_dir()`), with `time_to_ready_s` measured
  restore-trigger -> first member available and published in /metrics
  (`tests/test_fleet.py::test_scale_to_zero_and_demand_restore`).

`make_fleet_app` is the HTTP surface (/detect with classification,
/healthz, /livez, /metrics with `pool_size{pool,state}`,
`preemptions_total`, `replays_total`, `retry_budget_exhausted_total`);
`python -m spotter_tpu.serving.fleet` runs it over static endpoint lists,
and `python -m spotter_tpu.serving.router --spot-endpoints ...` reuses the
same app from the existing edge entrypoint. Managed (spawning) fleets are
built in-process: `testing/cluster.py::fleet_spawner` supplies subprocess
member handles for the bench and chaos tests.
"""

import argparse
import asyncio
import json
import logging
import os
import random
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Protocol

from aiohttp import web

from spotter_tpu import obs
from spotter_tpu.obs import http as obs_http
from spotter_tpu.obs import logs as obs_logs
from spotter_tpu.obs.aggregate import FleetAggregator
from spotter_tpu.serving import wire
from spotter_tpu.serving.replica_pool import (
    PoolExhaustedError,
    ReplicaPool,
    RetryBudget,
)
from spotter_tpu.testing import faults

logger = logging.getLogger(__name__)

# request classes
SLO = "slo"
BULK = "bulk"
# canonical pool names (specs may add others; these two get the routing rules)
ON_DEMAND = "on_demand"
SPOT = "spot"

REQUEST_CLASS_HEADER = "X-Request-Class"
REQUEST_CLASS_KEY = "request_class"

DEFAULT_CLASS_ENV = "SPOTTER_TPU_POOL_DEFAULT_CLASS"
SCALE_TO_ZERO_ENV = "SPOTTER_TPU_SCALE_TO_ZERO_S"
RESTORE_WAIT_ENV = "SPOTTER_TPU_POOL_RESTORE_WAIT_S"
UNAVAILABLE_WAIT_ENV = "SPOTTER_TPU_POOL_UNAVAILABLE_WAIT_S"
RESPAWN_BASE_ENV = "SPOTTER_TPU_POOL_RESPAWN_BASE_S"

DEFAULT_RESTORE_WAIT_S = 20.0
DEFAULT_UNAVAILABLE_WAIT_S = 3.0
DEFAULT_RESPAWN_BASE_S = 0.5
DEFAULT_RESPAWN_MAX_S = 30.0
DEFAULT_TICK_S = 0.2

# member states for the pool_size{pool,state} gauge
READY = "ready"
STARTING = "starting"
DOWN = "down"
DEAD = "dead"


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def default_class_from_env() -> str:
    """Unclassified traffic defaults to SLO: treating unknown requests as
    latency-critical (pinned to on-demand) is the conservative choice —
    bulk must OPT IN to ride preemptible capacity."""
    raw = os.environ.get(DEFAULT_CLASS_ENV, "").strip().lower()
    return raw if raw in (SLO, BULK) else SLO


def classify_request(
    headers=None, payload=None, default: Optional[str] = None
) -> tuple[str, dict]:
    """(request_class, forwardable_payload). Precedence: the
    X-Request-Class header, then a `request_class` payload key (stripped
    before forwarding — it is fleet routing metadata, not detector input),
    then "slo" for payloads carrying a deadline tag, then the default."""
    cls = ""
    if headers is not None:
        cls = str(headers.get(REQUEST_CLASS_HEADER, "")).strip().lower()
    if isinstance(payload, dict):
        if not cls:
            cls = str(payload.get(REQUEST_CLASS_KEY, "")).strip().lower()
        if REQUEST_CLASS_KEY in payload:
            payload = {
                k: v for k, v in payload.items() if k != REQUEST_CLASS_KEY
            }
        if not cls and "deadline_ms" in payload:
            cls = SLO
    if cls not in (SLO, BULK):
        cls = default if default in (SLO, BULK) else default_class_from_env()
    return cls, payload


class MemberHandle(Protocol):
    """What the controller needs from a managed member: the subprocess
    implementation is testing/cluster.py::FleetMember (supervisor +
    standalone stub server + per-member maintenance file); tests substitute
    in-process fakes."""

    url: str

    def alive(self) -> bool: ...

    def preempt(self) -> None: ...

    def clear_preemption(self) -> None: ...

    def shutdown(self, timeout_s: float = 10.0) -> str: ...


@dataclass
class PoolSpec:
    """One pool's configuration. Exactly one population style per spec:
    `endpoints` (static, unmanaged — no respawn/scale-to-zero),
    `handles` (pre-spawned managed members), or `spawner` + `target_size`
    (the controller spawns and maintains the population)."""

    name: str
    endpoints: list[str] = field(default_factory=list)
    handles: list = field(default_factory=list)
    spawner: Optional[Callable[[], MemberHandle]] = None
    target_size: int = 0
    # None -> SPOTTER_TPU_SCALE_TO_ZERO_S (managed pools only); <= 0 -> off
    scale_to_zero_s: Optional[float] = None


class _Member:
    def __init__(self, url: str, handle: Optional[MemberHandle] = None) -> None:
        self.url = url.rstrip("/")
        self.handle = handle
        self.was_available = False
        self.ever_available = False
        self.preempt_pending = False


class FleetPool:
    """A named pool: its ReplicaPool (routing/health/replay), its managed
    members, and its lifecycle state (scale-to-zero, restore timing)."""

    def __init__(self, spec: PoolSpec, pool: ReplicaPool,
                 scale_to_zero_s: float) -> None:
        self.spec = spec
        self.pool = pool
        self.scale_to_zero_s = scale_to_zero_s
        self.members: list[_Member] = [_Member(u) for u in spec.endpoints]
        self.last_used = time.monotonic()
        self.scaled_to_zero = False
        self.restoring = False
        self.restore_started: Optional[float] = None
        self._restore_counts = False  # True only for post-scale-to-zero restores
        self.time_to_ready_s: Optional[float] = None
        self.available = asyncio.Event()
        # gauges/counters
        self.preemptions_total = 0
        self.respawns_total = 0
        self.scale_to_zero_total = 0
        self.restores_total = 0
        # jittered-respawn state
        self._respawn_backoff_s = 0.0
        self._respawn_due: list[float] = []

    @property
    def managed(self) -> bool:
        return self.spec.spawner is not None or any(
            m.handle is not None for m in self.members
        )

    def has_capacity(self) -> bool:
        """Can this pool EVER serve — members now, or a spawner that can
        make some? (Routing falls back across pools only when this is
        False: an empty-because-scaled-to-zero pool still has capacity.)"""
        if self.members:
            return True
        return self.spec.spawner is not None and self.spec.target_size > 0

    def member_for(self, url: str) -> Optional[_Member]:
        url = url.rstrip("/")
        for m in self.members:
            if m.url == url:
                return m
        return None

    def member_states(self, now: float) -> dict[str, int]:
        sizes = {READY: 0, STARTING: 0, DOWN: 0, DEAD: 0}
        for m in self.members:
            if m.handle is not None and not m.handle.alive():
                sizes[DEAD] += 1
                continue
            r = self.pool.replica_for(m.url)
            if r is not None and r.available(now):
                sizes[READY] += 1
            elif m.ever_available:
                sizes[DOWN] += 1
            else:
                sizes[STARTING] += 1
        return sizes


class FleetController:
    """Routes classed traffic to pools and keeps the pools alive: observes
    member health transitions, re-spawns dead members with jittered backoff,
    applies injected preemption storms, scales idle pools to zero, and
    restores them on demand. One background tick task; all state is
    event-loop-confined."""

    def __init__(
        self,
        specs: list[PoolSpec],
        tick_s: float = DEFAULT_TICK_S,
        retry_budget_pct: Optional[float] = None,
        restore_wait_s: Optional[float] = None,
        unavailable_wait_s: Optional[float] = None,
        respawn_base_s: Optional[float] = None,
        respawn_max_s: float = DEFAULT_RESPAWN_MAX_S,
        rng: Optional[random.Random] = None,
        pool_kwargs: Optional[dict] = None,
    ) -> None:
        if not specs:
            raise ValueError("FleetController needs at least one PoolSpec")
        self.tick_s = tick_s
        self.restore_wait_s = (
            restore_wait_s
            if restore_wait_s is not None
            else _env_float(RESTORE_WAIT_ENV, DEFAULT_RESTORE_WAIT_S)
        )
        self.unavailable_wait_s = (
            unavailable_wait_s
            if unavailable_wait_s is not None
            else _env_float(UNAVAILABLE_WAIT_ENV, DEFAULT_UNAVAILABLE_WAIT_S)
        )
        self.respawn_base_s = (
            respawn_base_s
            if respawn_base_s is not None
            else _env_float(RESPAWN_BASE_ENV, DEFAULT_RESPAWN_BASE_S)
        )
        self.respawn_max_s = respawn_max_s
        self._rng = rng if rng is not None else random.Random()
        self.default_class = default_class_from_env()
        env_stz = _env_float(SCALE_TO_ZERO_ENV, 0.0)
        self.pools: dict[str, FleetPool] = {}
        for spec in specs:
            if spec.name in self.pools:
                raise ValueError(f"duplicate pool {spec.name!r}")
            # each pool gets its OWN budget slice: a bulk-tier storm must not
            # starve SLO-tier failover of replay tokens
            rp = ReplicaPool(
                list(spec.endpoints),
                allow_empty=True,
                retry_budget=RetryBudget(pct=retry_budget_pct),
                **(pool_kwargs or {}),
            )
            stz = spec.scale_to_zero_s
            if stz is None:
                stz = env_stz if (spec.spawner is not None) else 0.0
            self.pools[spec.name] = FleetPool(spec, rp, stz)
        self._task: Optional[asyncio.Task] = None
        self.storms_total = 0
        self.class_requests = {SLO: 0, BULK: 0}
        self.class_failures = {SLO: 0, BULK: 0}
        # leader fencing hook (ISSUE 16): when set (serving/reconcile.py
        # installs `Reconciler.fence`), every spawn re-checks leadership
        # and raises statestore.StaleLeaderError for a deposed controller
        # — stale actuations are refused at the boundary, not logged after
        self.fence: Optional[Callable[[], object]] = None

    # ---- lifecycle ----

    async def start(self) -> None:
        for fp in self.pools.values():
            for h in fp.spec.handles:
                self._adopt(fp, h)
            if fp.spec.spawner is not None:
                while len(fp.members) < fp.spec.target_size:
                    self._spawn(fp)
            if fp.members and fp.pool.has_available() is False:
                # initial bring-up: measure time-to-first-available
                fp.restoring = True
                fp.restore_started = time.monotonic()
                fp._restore_counts = False
            await fp.pool.start()
        if self._task is None:
            self._task = asyncio.create_task(self._run())

    async def stop(self, shutdown_members: bool = True) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        for fp in self.pools.values():
            await fp.pool.stop()
        if shutdown_members:
            loop = asyncio.get_running_loop()
            waits = [
                loop.run_in_executor(None, m.handle.shutdown)
                for fp in self.pools.values()
                for m in fp.members
                if m.handle is not None
            ]
            if waits:
                await asyncio.gather(*waits, return_exceptions=True)

    def _adopt(self, fp: FleetPool, handle: MemberHandle) -> None:
        fp.pool.add_endpoint(handle.url, healthy=False)
        fp.members.append(_Member(handle.url, handle))

    def _spawn(self, fp: FleetPool) -> None:
        if self.fence is not None:
            self.fence()  # StaleLeaderError for a deposed controller
        handle = fp.spec.spawner()
        self._adopt(fp, handle)
        logger.info("pool %s: spawned member %s", fp.spec.name, handle.url)

    # ---- reconciler surface (ISSUE 16) ----

    def adopt_endpoint(
        self, pool_name: str, handle: MemberHandle,
        version: Optional[str] = None,
    ) -> bool:
        """Adopt an already-running member (orphan adoption: the reconcile
        loop found it in the endpoints manifest after a controller
        restart). Idempotent per URL — re-adoption of a known member is a
        no-op, which is what makes restart free of double-spawns."""
        fp = self.pools.get(pool_name)
        if fp is None or fp.member_for(handle.url) is not None:
            return False
        self._adopt(fp, handle)
        if version:
            fp.pool.set_version(handle.url, version)
        logger.info("pool %s: adopted member %s", pool_name, handle.url)
        return True

    async def set_target_size(self, pool_name: str, n: int) -> None:
        """Apply a journaled desired size. Growth is satisfied by
        `ensure_population` on the next reconcile step; shrink retires the
        newest members past the target (remove from routing first, then
        shut down — the scale-to-zero discipline, per member)."""
        fp = self.pools[pool_name]
        fp.spec.target_size = max(int(n), 0)
        excess = list(fp.members)[fp.spec.target_size:]
        if not excess:
            return
        for m in excess:
            fp.pool.remove_endpoint(m.url)
            fp.members.remove(m)
        logger.info(
            "pool %s: shrunk to target %d (%d members retired)",
            pool_name, fp.spec.target_size, len(excess),
        )
        loop = asyncio.get_running_loop()
        waits = [
            loop.run_in_executor(None, m.handle.shutdown)
            for m in excess
            if m.handle is not None
        ]
        if waits:
            await asyncio.gather(*waits, return_exceptions=True)

    def ensure_population(self, pool_name: str) -> int:
        """Spawn up to the desired size, counting members a retire already
        scheduled for jittered respawn — the reconcile loop's convergence
        step must not race the controller's own backoff machinery into
        double-spawning."""
        fp = self.pools.get(pool_name)
        if fp is None or fp.spec.spawner is None or fp.scaled_to_zero:
            return 0
        spawned = 0
        while len(fp.members) + len(fp._respawn_due) < fp.spec.target_size:
            self._spawn(fp)
            spawned += 1
        return spawned

    # ---- routing ----

    def pool_for_class(self, cls: str) -> FleetPool:
        """SLO pins to on_demand; bulk drains to spot. The fallback pool is
        used only when the preferred one has NO capacity configured at all
        (a storm-suspended or scaled-to-zero pool still HAS capacity — bulk
        rides out the storm on spot rather than stampeding the SLO pool)."""
        preferred = ON_DEMAND if cls == SLO else SPOT
        fallback = ON_DEMAND if cls == BULK else SPOT
        fp = self.pools.get(preferred)
        if fp is not None and fp.has_capacity():
            return fp
        alt = self.pools.get(fallback)
        if alt is not None and alt.has_capacity():
            return alt
        pick = fp or alt
        return pick if pick is not None else next(iter(self.pools.values()))

    def _maybe_restore(self, fp: FleetPool) -> None:
        """Demand restore: spawn the missing population NOW (no backoff —
        this is deliberate demand, not a crash loop) and start the
        time-to-ready clock."""
        if fp.spec.spawner is None or fp.restoring:
            return
        missing = fp.spec.target_size - len(fp.members)
        if missing <= 0:
            return
        fp.restoring = True
        fp.restore_started = time.monotonic()
        fp._restore_counts = fp.scaled_to_zero
        fp._respawn_due.clear()
        for _ in range(missing):
            self._spawn(fp)

    async def request(
        self,
        path: str,
        payload: dict,
        cls: Optional[str] = None,
        headers: Optional[dict] = None,
        pool: Optional[str] = None,
    ):
        """Route one classed request through its pool, waking a
        scaled-to-zero pool on the way. Bulk requests tolerate a bounded
        wait for a restoring/stormed pool; SLO requests fail fast (the
        caller turns PoolExhaustedError subclasses into 503 + Retry-After).
        `pool` (ISSUE 20) overrides class routing with a named pool — the
        model-multiplexed edge resolves the model FIRST and pins the
        request to that family's pool; the class still drives wait/accounting
        behavior."""
        if cls not in (SLO, BULK):
            cls = self.default_class
        self.class_requests[cls] += 1
        fp = self.pools[pool] if pool is not None else self.pool_for_class(cls)
        fp.last_used = time.monotonic()
        if not fp.pool.has_available():
            self._maybe_restore(fp)
            if fp.restoring or cls == BULK:
                wait_s = (
                    self.restore_wait_s if fp.restoring
                    else self.unavailable_wait_s
                )
                deadline = time.monotonic() + wait_s
                # re-check REAL availability each wakeup: the event may be
                # stale-set for a beat around a scale-down/retire transition
                while not fp.pool.has_available():
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break  # fall through: the pool raises its fast 503
                    try:
                        await asyncio.wait_for(
                            fp.available.wait(), min(remaining, self.tick_s)
                        )
                    except asyncio.TimeoutError:
                        pass
            fp.last_used = time.monotonic()
        try:
            return await fp.pool.request(path, payload, headers=headers)
        except PoolExhaustedError:
            self.class_failures[cls] += 1
            raise

    async def detect(self, payload: dict, cls: Optional[str] = None) -> dict:
        resp = await self.request("/detect", payload, cls)
        return resp.json()

    # ---- supervision tick ----

    async def _run(self) -> None:
        while True:
            try:
                await self._tick()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("fleet tick failed")
            await asyncio.sleep(self.tick_s)

    async def _tick(self) -> None:
        now = time.monotonic()
        self._apply_storm()
        for fp in self.pools.values():
            self._observe_members(fp, now)
            self._respawn_due_members(fp, now)
            await self._maybe_scale_to_zero(fp, now)
            if fp.pool.has_available():
                if fp.restoring:
                    fp.restoring = False
                    fp.time_to_ready_s = time.monotonic() - fp.restore_started
                    if fp._restore_counts:
                        fp.restores_total += 1
                    fp.scaled_to_zero = False
                    # the idle clock starts when capacity is READY: a
                    # bring-up longer than scale_to_zero_s must not get the
                    # fresh pool reclaimed on the very next tick
                    fp.last_used = time.monotonic()
                    logger.info(
                        "pool %s: available after %.2f s",
                        fp.spec.name, fp.time_to_ready_s,
                    )
                fp.available.set()
            else:
                fp.available.clear()

    def _apply_storm(self) -> None:
        """Injected preemption storm (SPOTTER_TPU_FAULTS=preempt_storm=N or
        faults.inject in-process): preempt up to N currently-available spot
        members through their handles — the chaos entry point of
        `tests/test_fleet.py::
        test_storm_drains_only_marked_member_slo_untouched`."""
        spot = self.pools.get(SPOT)
        now = time.monotonic()
        candidates = []
        for m in (spot.members if spot is not None else []):
            if m.handle is None:
                continue
            r = spot.pool.replica_for(m.url)
            if r is not None and r.available(now):
                candidates.append(m)
        if not candidates:
            # leave an armed storm for a tick that HAS ready targets: a
            # maintenance wave hits running capacity, not an empty pool
            return
        n = faults.take_preempt_storm()
        if n <= 0:
            return
        targets = candidates[:n]
        for m in targets:
            try:
                m.handle.preempt()
                m.preempt_pending = True
            except Exception:
                logger.exception("storm: preempting %s failed", m.url)
        if targets:
            self.storms_total += 1
            logger.warning(
                "preemption storm injected: %d of %d spot members",
                len(targets), len(spot.members),
            )

    def _observe_members(self, fp: FleetPool, now: float) -> None:
        for m in list(fp.members):
            if m.handle is not None and not m.handle.alive():
                # the SUPERVISOR process died (crash-loop exit 84, host
                # gone): retire the member and re-spawn on jittered backoff
                self._retire(fp, m, now)
                continue
            r = fp.pool.replica_for(m.url)
            avail = r is not None and r.available(now)
            if avail:
                m.ever_available = True
            if m.was_available and not avail:
                if fp.spec.name == SPOT:
                    # a spot member dropping out of ready IS a preemption in
                    # this capacity class (drain via maintenance signal or a
                    # straight kill) — the gauge the storm bench watches
                    fp.preemptions_total += 1
                if m.preempt_pending and m.handle is not None:
                    # the maintenance file did its job (the child saw it and
                    # drained): clear it so the supervisor's respawned child
                    # doesn't immediately re-preempt itself
                    try:
                        m.handle.clear_preemption()
                    except Exception:
                        logger.exception("clearing preemption on %s failed", m.url)
                    m.preempt_pending = False
            m.was_available = avail

    def _retire(self, fp: FleetPool, m: _Member, now: float) -> None:
        fp.pool.remove_endpoint(m.url)
        fp.members.remove(m)
        logger.warning("pool %s: member %s dead; retired", fp.spec.name, m.url)
        if fp.spec.spawner is None or fp.scaled_to_zero:
            return
        # full-jitter exponential backoff on the replacement spawn: a storm
        # that kills many members at once must not respawn them in lockstep
        fp._respawn_backoff_s = min(
            max(fp._respawn_backoff_s * 2.0, self.respawn_base_s),
            self.respawn_max_s,
        )
        delay = self._rng.uniform(0.0, fp._respawn_backoff_s)
        fp._respawn_due.append(now + delay)
        fp._respawn_due.sort()

    def _respawn_due_members(self, fp: FleetPool, now: float) -> None:
        while (
            fp._respawn_due
            and fp._respawn_due[0] <= now
            and len(fp.members) < fp.spec.target_size
        ):
            fp._respawn_due.pop(0)
            self._spawn(fp)
            fp.respawns_total += 1
        if (
            not fp._respawn_due
            and fp.members
            and len(fp.members) >= fp.spec.target_size
            and fp.pool.has_available()
        ):
            fp._respawn_backoff_s = 0.0

    async def _maybe_scale_to_zero(self, fp: FleetPool, now: float) -> None:
        if (
            fp.scale_to_zero_s <= 0
            or fp.scaled_to_zero
            or fp.restoring
            or not fp.members
            or fp.spec.spawner is None
            or now - fp.last_used < fp.scale_to_zero_s
        ):
            return
        members = list(fp.members)
        logger.info(
            "pool %s: idle %.1f s; scaling %d members to zero",
            fp.spec.name, now - fp.last_used, len(members),
        )
        fp.scaled_to_zero = True
        fp.scale_to_zero_total += 1
        fp._respawn_due.clear()
        for m in members:
            fp.pool.remove_endpoint(m.url)
            fp.members.remove(m)
        # clear availability NOW: the member shutdowns awaited below take
        # seconds, and a demand-restore request landing in that window must
        # wait on the event, not sail through on its stale set state
        fp.available.clear()
        loop = asyncio.get_running_loop()
        waits = [
            loop.run_in_executor(None, m.handle.shutdown)
            for m in members
            if m.handle is not None
        ]
        if waits:
            await asyncio.gather(*waits, return_exceptions=True)

    # ---- observability ----

    def snapshot(self) -> dict:
        now = time.monotonic()
        pools = {}
        pool_size = {}
        preemptions = replays = budget_exhausted = suspended = 0
        time_to_ready = {}
        for name, fp in self.pools.items():
            sizes = fp.member_states(now)
            psnap = fp.pool.snapshot()
            preemptions += fp.preemptions_total
            replays += psnap["pool_replays_total"]
            budget_exhausted += psnap["pool_retry_budget_exhausted_total"]
            suspended += psnap["pool_suspended_total"]
            pool_size[name] = sizes
            time_to_ready[name] = fp.time_to_ready_s
            pools[name] = {
                "size": len(fp.members),
                "target_size": fp.spec.target_size,
                "state": sizes,
                "managed": fp.managed,
                "scaled_to_zero": fp.scaled_to_zero,
                "restoring": fp.restoring,
                "scale_to_zero_s": fp.scale_to_zero_s,
                "time_to_ready_s": fp.time_to_ready_s,
                "preemptions_total": fp.preemptions_total,
                "respawns_total": fp.respawns_total,
                "scale_to_zero_total": fp.scale_to_zero_total,
                "restores_total": fp.restores_total,
                "pool": psnap,
            }
        return {
            "pool_size": pool_size,
            "pools": pools,
            "preemptions_total": preemptions,
            "replays_total": replays,
            "retry_budget_exhausted_total": budget_exhausted,
            "suspended_total": suspended,
            "storms_total": self.storms_total,
            "requests_total": dict(self.class_requests),
            "failures_total": dict(self.class_failures),
            "time_to_ready_s": time_to_ready,
        }


# ---- HTTP surface ----


def retry_after_header(exc: PoolExhaustedError) -> dict[str, str]:
    return {"Retry-After": f"{max(1, round(getattr(exc, 'retry_after_s', 1.0)))}"}


def fleet_member_urls(controller: FleetController) -> list[str]:
    """Every member URL across every pool — the fleet aggregator's
    membership source (re-read each scrape, so spot churn, respawns and
    scale-to-zero are followed)."""
    return [
        m.url for fp in controller.pools.values() for m in fp.members
    ]


def make_fleet_app(
    controller: FleetController, limiter=None,
    aggregator: FleetAggregator | None = None,
    reconciler=None,
    tenancy_plane=None,
    autoscaler=None,
) -> web.Application:
    """The fleet edge: /detect classifies (header/payload) and routes
    through the controller; /metrics serves the pool gauges the storm bench
    parses. The controller's tick loop starts/stops with the app.
    `limiter` (an `overload.AdaptiveLimiter`, default off; armed via
    `SPOTTER_TPU_ADMIT_EDGE_TARGET_MS` by the entrypoints) is the ISSUE 8
    AIMD edge gate: adaptive concurrency on observed round-trip latency,
    shedding bulk before slo when the limit is hit. `aggregator` (default:
    built over every pool's members from `SPOTTER_TPU_FLEET_SCRAPE_S`; 0
    disables) is the ISSUE 12 fleet telemetry plane — the merged `fleet`
    /metrics block, /debug/fleet, and /debug/traces?fleet=1 stitching.
    `reconciler` (ISSUE 16, default None) attaches a
    `reconcile.Reconciler`: /healthz grows the leadership + drift block
    and /metrics the `reconcile` counters (adoptions, fencing rejections,
    journal rebuilds, per-pool drift). `tenancy_plane` (ISSUE 19, default
    `tenancy.from_env()` — None when unconfigured) arms per-tenant edge
    quotas exactly like the plain router: over-quota tenants shed 429
    with a tenant-scoped Retry-After before the body is read, and the
    resolved id rides downstream in X-Spotter-Tenant. `autoscaler` (ISSUE
    20, default None) attaches an `autoscale.AutoscalerBrain`: /detect
    resolves a MODEL pool (X-Spotter-Model header / `model` payload key /
    `queries` -> open-vocab pool) before class routing, unplaceable
    requests get a structured 400 naming the registry, and /metrics grows
    the `autoscale` per-model-pool block fleet_top renders."""
    from spotter_tpu.serving import tenancy

    if aggregator is None:
        aggregator = FleetAggregator(lambda: fleet_member_urls(controller))
    if tenancy_plane is None:
        tenancy_plane = tenancy.from_env()
    app = web.Application(client_max_size=64 * 1024 * 1024)
    app["fleet"] = controller
    app["edge_limiter"] = limiter
    app["fleet_aggregator"] = aggregator
    app["tenancy"] = tenancy_plane
    app["autoscaler"] = autoscaler

    async def on_startup(app: web.Application) -> None:
        await controller.start()
        await aggregator.start()
        if autoscaler is not None:
            await autoscaler.start()

    async def on_cleanup(app: web.Application) -> None:
        if autoscaler is not None:
            await autoscaler.stop()
        await aggregator.stop()
        await controller.stop()

    async def detect(request: web.Request) -> web.Response:
        # Same edge-trace contract as the plain router (ISSUE 7): ids
        # minted/continued and echoed on EVERY outcome (storm 503s
        # included), traceparent forwarded, replica Server-Timing merged
        # behind a route span that also covers the pool pick.
        trace, request_id = obs_http.begin_http_trace(request)
        tenant = None
        tadm = None
        mtrack = None

        def done(resp: web.Response) -> web.Response:
            # per-tenant occupancy + SLO accounting (ISSUE 19)
            if tadm is not None:
                tadm.release(
                    good=resp.status not in (429, 503) and resp.status < 500
                )
            # per-model-pool edge accounting (ISSUE 20)
            if mtrack is not None:
                mtrack.done(resp.status)
            return obs_http.finish_http_trace(
                trace, request_id, resp, server_timing=True
            )

        if tenancy_plane is not None:
            # edge quota (ISSUE 19): header-only identity, shed 429 before
            # the body is read — strictly before any in-quota shed below
            from spotter_tpu.serving import tenancy as tenancy_mod
            from spotter_tpu.serving.router import tenant_shed_response

            tenant = tenancy_plane.resolve(request.headers)
            try:
                tadm = tenancy_plane.try_admit(tenant)
            except tenancy_mod.TenantQuotaError as exc:
                return done(tenant_shed_response(exc))
        try:
            with obs.span(obs.ROUTE, trace):
                try:
                    payload = await request.json()
                except json.JSONDecodeError:
                    return done(web.Response(status=400, text="Invalid JSON body"))
                cls, payload = classify_request(
                    request.headers, payload, default=controller.default_class
                )
            model_pool = None
            if autoscaler is not None:
                # model-multiplexed routing (ISSUE 20): resolve the MODEL
                # pool before class routing; unplaceable requests are
                # structured 400s naming the registry, through done() so
                # the request id echoes like every other shed
                from spotter_tpu.serving.autoscale import ModelRoutingError
                from spotter_tpu.serving.router import model_routing_response

                try:
                    model_pool, payload = autoscaler.route(
                        request.headers, payload
                    )
                except ModelRoutingError as exc:
                    return done(model_routing_response(exc))
                mtrack = autoscaler.track(model_pool)
            adm = None
            if limiter is not None:
                adm = limiter.try_admit(cls)
                if adm is None:  # over the adaptive edge limit: bulk sheds first
                    from spotter_tpu.serving.router import edge_shed_response

                    return done(edge_shed_response(limiter, cls))
            # forward the class so replica-level overload control (limiter
            # class ordering, brownout bulk rung) sees the same verdict
            headers = obs_http.forward_headers(trace, request_id)
            headers[REQUEST_CLASS_HEADER] = cls
            if tenant is not None:
                # resolved tenant id rides downstream alongside X-Request-ID
                # (ISSUE 19) so the replica scopes by the same identity;
                # stamp() adds the edge-attestation token when configured
                # (REVIEW: a bare forwarded header is untrusted there too)
                tenancy_plane.stamp(headers, tenant)
            t_fwd = time.monotonic()
            try:
                resp = await controller.request(
                    "/detect", payload, cls, headers=headers, pool=model_pool
                )
            except PoolExhaustedError as exc:
                return done(
                    web.json_response(
                        {"error": str(exc), "status": 503, "request_class": cls},
                        status=503,
                        headers=retry_after_header(exc),
                    )
                )
            finally:
                elapsed_s = time.monotonic() - t_fwd
                if limiter is not None:
                    limiter.observe(elapsed_s * 1000.0)
                if adm is not None:
                    adm.release()
            with obs.span(obs.ROUTE, trace):
                # replica stages + the transport remainder as a network span:
                # the edge trace tiles against the latency the client saw
                obs_http.merge_downstream(trace, resp.headers, elapsed_s)
                out = web.Response(
                    status=resp.status_code,
                    body=resp.content,
                    content_type="application/json",
                )
                rid = resp.headers.get(wire.REPLICA_HEADER)
                if rid:  # replica identity rides through the fleet edge too
                    out.headers[wire.REPLICA_HEADER] = rid
                ver = resp.headers.get(wire.VERSION_HEADER)
                if ver:  # deploy version too (ISSUE 15)
                    out.headers[wire.VERSION_HEADER] = ver
            return done(out)
        finally:
            # leak guard (REVIEW): a client disconnect (CancelledError
            # in any await) or an uncaught error below must still free
            # the tenant's inflight slot, or the tenant is permanently
            # 429-locked at its inflight cap and its occupancy skews
            # the limiter/brownout forever. Idempotent: when done()
            # ran, it already released with the real outcome; this
            # no-outcome release never touches the SLO burn.
            if tadm is not None:
                tadm.release(good=None)
            if mtrack is not None:
                mtrack.done(None)

    async def healthz(request: web.Request) -> web.Response:
        available = {
            name: fp.pool.has_available()
            for name, fp in controller.pools.items()
        }
        body: dict = {"pools_available": available}
        if reconciler is not None:
            # control-plane block (ISSUE 16): leadership + per-pool drift
            from spotter_tpu.serving.reconcile import healthz_block

            body.update(healthz_block(reconciler))
        return web.json_response(
            body,
            status=200 if any(available.values()) else 503,
        )

    async def livez(request: web.Request) -> web.Response:
        return web.json_response({"status": "alive"})

    async def metrics(request: web.Request) -> web.Response:
        # JSON unchanged; Prometheus text exposition of the pool_size /
        # preemption / replay gauges behind the standard negotiation. The
        # edge limiter's state rides along under "edge_admit" when armed.
        snap = controller.snapshot()
        if limiter is not None:
            snap["edge_admit"] = limiter.snapshot()
        # fleet telemetry plane (ISSUE 12): the merged member view across
        # every pool — the single answer to "what is the fleet's goodput/
        # burn/MFU right now", and the autoscaling signal source for
        # ROADMAP item 2
        if aggregator.enabled:
            snap["fleet"] = aggregator.fleet_snapshot()
        # crash-safe control plane (ISSUE 16): reconcile loop counters +
        # the desired-vs-ready drift gauge, labeled per pool by prom
        if reconciler is not None:
            snap["reconcile"] = reconciler.snapshot()
        # tenant isolation plane (ISSUE 19): bounded top-K per-tenant rows
        if tenancy_plane is not None:
            snap["tenants"] = tenancy_plane.metrics_view()
        # model-multiplexed autoscaler (ISSUE 20): per-model-pool desired/
        # ready, last decision + reason, restore timing — fleet_top's rows
        if autoscaler is not None:
            snap["autoscale"] = autoscaler.snapshot()
        return obs_http.metrics_response(request, snap)

    async def debug_tenants(request: web.Request) -> web.Response:
        """Full per-tenant table (ISSUE 19) — admin-token-gated."""
        rejected = obs_http.admin_rejection(request)
        if rejected is not None:
            return rejected
        if tenancy_plane is None:
            return web.json_response({"enabled": False})
        return web.json_response(tenancy_plane.snapshot())

    app.router.add_post("/detect", detect)
    app.router.add_get("/debug/tenants", debug_tenants)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/livez", livez)
    app.router.add_get("/metrics", metrics)
    app.router.add_get(
        "/debug/traces",
        obs_http.make_debug_traces_handler(aggregator=aggregator),
    )
    app.router.add_get(
        "/debug/fleet", obs_http.make_debug_fleet_handler(aggregator)
    )
    app.on_startup.append(on_startup)
    app.on_cleanup.append(on_cleanup)
    return app


def static_fleet(
    on_demand: list[str], spot: list[str], **controller_kwargs
) -> FleetController:
    """Fleet over fixed endpoint lists (no spawning — the
    router-as-data-plane deployment where members are k8s pods someone else
    manages)."""
    specs = []
    if on_demand:
        specs.append(PoolSpec(ON_DEMAND, endpoints=on_demand))
    if spot:
        specs.append(PoolSpec(SPOT, endpoints=spot))
    return FleetController(specs, **controller_kwargs)


def main() -> None:
    parser = argparse.ArgumentParser(
        description="spotter-tpu spot-aware fleet edge"
    )
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument(
        "--on-demand",
        default=os.environ.get("SPOTTER_TPU_REPLICAS", ""),
        help="comma-separated on-demand replica base URLs "
        "(default SPOTTER_TPU_REPLICAS)",
    )
    parser.add_argument(
        "--spot",
        default=os.environ.get("SPOTTER_TPU_SPOT_REPLICAS", ""),
        help="comma-separated spot replica base URLs "
        "(default SPOTTER_TPU_SPOT_REPLICAS)",
    )
    args = parser.parse_args()
    on_demand = [e.strip() for e in args.on_demand.split(",") if e.strip()]
    spot = [e.strip() for e in args.spot.split(",") if e.strip()]
    if not on_demand and not spot:
        raise SystemExit("no endpoints: pass --on-demand and/or --spot")
    logging.basicConfig(level=logging.INFO)
    obs_logs.maybe_setup_json_logging()
    from spotter_tpu.serving.overload import edge_limiter_from_env

    controller = static_fleet(on_demand, spot)
    web.run_app(
        make_fleet_app(controller, limiter=edge_limiter_from_env()),
        host=args.host,
        port=args.port,
    )


if __name__ == "__main__":
    main()
