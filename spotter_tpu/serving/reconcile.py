"""Crash-safe control plane (ISSUE 16 tentpole, parts b-d): the reconcile
loop that converges observed fleet state onto the durable desired-state
spec, with orphan adoption, leader fencing, and rebuild-from-observation.

The FleetController (serving/fleet.py) and RolloutController
(serving/rollout.py) are good ACTUATORS — spawn, drain, retire, re-pin —
but before this module they were also the only copy of the fleet's intent:
kill the controller mid-rollout and the canary was stranded at a pinned
weight forever; kill it mid-storm and dead members were never respawned.
This module splits intent from actuation:

- **Desired state** lives in `statestore.StateStore` (CRC-framed journal +
  snapshot). The reconciler never trusts memory over the journal, and
  never trusts the journal over a failed CRC: `load_or_rebuild` turns
  `StateCorruptError` into a counted rebuild-from-observation (adopt what
  is verifiably running, journal THAT as the new desired state) — the
  Spotlight posture, where observed spot capacity outranks replayed
  intent.
- **Orphan adoption**: supervisors register their replica in an
  `EndpointsManifest` (url -> pool/version/pidfile/preempt_file/
  supervisor_pid) and deregister only on permanent exit, so the manifest
  stays truthful while no controller is alive. A (re)started controller
  adopts every still-live entry — `ManifestHandle` rebuilds the
  MemberHandle surface from the manifest entry alone — instead of
  double-spawning next to it or killing it as unknown. The /healthz
  identity block (replica_id, version, weights_digest — PR 12/15) is
  probed to confirm what was adopted.
- **Leader fencing**: with a `LeaderLease`, any number of controllers can
  run; exactly one acts. Every actuation path (the controller's spawns
  via its `fence` hook, the rollout spawner, the reconciler's own
  convergence steps) calls `Reconciler.fence()` — `LeaderLease.check()`
  plus a counted `StaleLeaderError` — so a deposed controller (paused
  past its TTL, then resumed) is refused at the actuation boundary, not
  after it has half-acted.
- **Drift** is the reconciler's public health signal: per pool,
  `desired - ready`. `/healthz` on an edge wired with a reconciler
  reports leadership + drift; `tools/fleet_top.py` renders the same
  block; the drill gates on drift reconverging to zero after every chaos
  scenario.

`python -m spotter_tpu.serving.reconcile` is the standalone controller
process the controller chaos drills (`tests/test_reconcile.py::
test_controller_chaos_row`) kill and restart: it stands by on
the lease, loads-or-rebuilds the journal, adopts orphans, runs the fleet
tick + reconcile loop + (resumable) rollout, and writes an atomic status
JSON each tick for the drill to parse.
"""

import argparse
import asyncio
import json
import logging
import os
import signal
import sys
import time
from typing import Callable, Optional

from spotter_tpu.engine.metrics import ControlPlaneMetrics
from spotter_tpu.serving.statestore import (
    JOURNAL_NAME,
    EndpointsManifest,
    LeaderLease,
    StaleLeaderError,
    StateCorruptError,
    StateStore,
    _atomic_write,
    supervisor_alive,
)

logger = logging.getLogger(__name__)

DEFAULT_INTERVAL_S = 0.25
IDENTITY_PROBE_TIMEOUT_S = 1.5


class ManifestHandle:
    """A fleet MemberHandle reconstructed from an endpoints-manifest entry
    — what orphan adoption hands the controller when the process object
    that spawned the member died with the previous controller. Same
    surface as testing/cluster.py::FleetMember, driven through the
    supervisor pid and the maintenance file instead of a Popen handle."""

    def __init__(self, url: str, entry: dict) -> None:
        self.url = url.rstrip("/")
        self.pool = str(entry.get("pool") or "")
        self.version = str(entry.get("version") or "")
        self.pidfile = entry.get("pidfile") or ""
        self.preempt_file = entry.get("preempt_file") or ""
        self.supervisor_pid = int(entry.get("supervisor_pid") or 0)

    def alive(self) -> bool:
        return supervisor_alive(self.supervisor_pid)

    def preempt(self) -> None:
        if not self.preempt_file:
            raise RuntimeError(f"{self.url}: no maintenance file to write")
        tmp = f"{self.preempt_file}.tmp"
        with open(tmp, "w") as f:
            f.write("preempted by reconciler")
        os.replace(tmp, self.preempt_file)

    def clear_preemption(self) -> None:
        try:
            os.unlink(self.preempt_file)
        except OSError:
            pass

    def shutdown(self, timeout_s: float = 10.0) -> str:
        """SIGTERM the supervisor (it forwards to the child and deregisters
        itself from the manifest on exit); escalate to SIGKILL past the
        timeout."""
        if not self.alive():
            return ""
        try:
            os.kill(self.supervisor_pid, signal.SIGTERM)
        except OSError:
            return ""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if not self.alive():
                return ""
            time.sleep(0.05)
        try:
            os.kill(self.supervisor_pid, signal.SIGKILL)
        except OSError:
            pass
        return ""


def load_or_rebuild(
    state_dir: str, metrics: ControlPlaneMetrics
) -> StateStore:
    """Load the journal strictly; on ANY corruption, count a rebuild and
    start from empty state (the caller re-seeds desired state from what it
    OBSERVES running). The damaged files are kept aside as `.corrupt` —
    detected and quarantined, never silently replayed, never a crash
    loop."""
    try:
        return StateStore.load(state_dir)
    except StateCorruptError as exc:
        logger.error(
            "state journal corrupt (%s); rebuilding desired state from "
            "observation", exc,
        )
        metrics.journal_rebuilds_total += 1
        return StateStore.fresh(state_dir)


class Reconciler:
    """Converges observed fleet membership onto the journaled desired
    state through a FleetController's actuators, one `step()` at a time.

    Each step: (1) hold/renew the lease (standby short-circuits; a
    controller deposed mid-reign books a fencing rejection and demotes);
    (2) adopt manifest orphans into their pools and prune dead entries;
    (3) converge pool target sizes and populations (all spawns fenced);
    (4) publish per-pool drift. Everything is event-loop-confined, like
    the controller it drives."""

    def __init__(
        self,
        controller,
        store: StateStore,
        lease: Optional[LeaderLease] = None,
        manifest: Optional[EndpointsManifest] = None,
        interval_s: float = DEFAULT_INTERVAL_S,
        metrics: Optional[ControlPlaneMetrics] = None,
    ) -> None:
        self.controller = controller
        self.store = store
        self.lease = lease
        self.manifest = manifest
        self.interval_s = interval_s
        self.metrics = metrics if metrics is not None else ControlPlaneMetrics()
        self.was_leading = False
        self._task: Optional[asyncio.Task] = None
        self._client = None

    # ---- fencing ----

    @property
    def leading(self) -> bool:
        return self.lease.leading if self.lease is not None else True

    def fence(self) -> int:
        """The actuation-boundary check every mutation goes through
        (installed as `controller.fence`, wrapped around spawners): the
        current fencing epoch, or a counted StaleLeaderError for a deposed
        controller."""
        if self.lease is None:
            return 0
        try:
            return self.lease.check()
        except StaleLeaderError:
            self.metrics.fencing_rejections_total += 1
            raise

    def fenced_spawner(self, spawner: Callable) -> Callable:
        """Wrap a member spawner: refuse when deposed, count when it
        runs — the `spawns_total` the drill uses to prove 0 double-spawns
        after adoption."""

        def spawn():
            self.fence()
            member = spawner()
            self.metrics.spawns_total += 1
            return member

        return spawn

    # ---- adoption ----

    def adopt_existing(self) -> int:
        """Pre-start adoption: push a ManifestHandle for every still-live
        manifest entry into its pool's spec.handles, so
        `FleetController.start()` adopts them FIRST and spawns only the
        genuinely missing remainder. This is what makes a controller
        restart free of double-spawns."""
        if self.manifest is None:
            return 0
        adopted = 0
        for url, entry in sorted(self.manifest.entries().items()):
            handle = ManifestHandle(url, entry)
            if not handle.alive():
                continue  # step() prunes; don't mutate the manifest here
            fp = self.controller.pools.get(handle.pool)
            if fp is None or fp.member_for(url) is not None:
                continue
            if any(h.url.rstrip("/") == handle.url for h in fp.spec.handles):
                continue
            fp.spec.handles.append(handle)
            if handle.preempt_file and os.path.exists(handle.preempt_file):
                # a storm marker that outlived its controller: the storm is
                # over once a new controller owns the fleet — clear it so
                # the restarted child doesn't re-preempt itself forever
                handle.clear_preemption()
            if handle.version:
                fp.pool.set_version(url, handle.version)
            adopted += 1
            self.metrics.adoptions_total += 1
            logger.info(
                "adopting orphan %s into pool %s (supervisor pid %d)",
                url, handle.pool, handle.supervisor_pid,
            )
        return adopted

    async def _adopt_orphans(self) -> None:
        """Steady-state adoption + manifest pruning: entries that appeared
        since start (a supervisor another actor spawned) are adopted;
        entries whose supervisor died are pruned once no pool claims
        them."""
        if self.manifest is None:
            return
        known = {
            m.url
            for fp in self.controller.pools.values()
            for m in fp.members
        }
        for url, entry in sorted(self.manifest.entries().items()):
            handle = ManifestHandle(url, entry)
            if not handle.alive():
                if url not in known:
                    self.manifest.remove(url)
                    self.metrics.manifest_pruned_total += 1
                continue
            if url in known or handle.pool not in self.controller.pools:
                continue
            self.fence()
            if self.controller.adopt_endpoint(
                handle.pool, handle, version=handle.version
            ):
                if handle.preempt_file and os.path.exists(
                    handle.preempt_file
                ):
                    handle.clear_preemption()
                self.metrics.adoptions_total += 1
                identity = await self.probe_identity(url)
                logger.info(
                    "adopted orphan %s into pool %s (identity: %s)",
                    url, handle.pool, identity,
                )

    async def probe_identity(self, url: str) -> Optional[dict]:
        """The /healthz identity block (replica_id, version,
        weights_digest, pool — PR 12/15): confirms WHAT was adopted.
        Best-effort — a member mid-restart answers later; adoption is
        gated on the supervisor, not the child."""
        try:
            import httpx

            if self._client is None:
                self._client = httpx.AsyncClient(
                    timeout=IDENTITY_PROBE_TIMEOUT_S
                )
            resp = await self._client.get(f"{url}/healthz")
            body = resp.json()
            return {
                "pool": body.get("pool"),
                **(body.get("replica") or {}),
            }
        except Exception:
            return None

    # ---- convergence ----

    async def _converge(self) -> None:
        for name, spec in dict(self.store.state["pools"]).items():
            fp = self.controller.pools.get(name)
            if fp is None:
                continue  # not a pool this controller actuates (e.g. the
                # rollout-managed pool — drift still covers it via spec)
            size = spec.get("size")
            if size is not None and int(size) != fp.spec.target_size:
                self.fence()
                await self.controller.set_target_size(name, int(size))
            self.controller.ensure_population(name)

    def compute_drift(self) -> dict:
        """Per-pool desired-vs-ready drift (positive = under-provisioned),
        published via metrics, /healthz, and fleet_top."""
        now = time.monotonic()
        detail = {}
        for name, fp in self.controller.pools.items():
            desired = int(
                (self.store.state["pools"].get(name) or {}).get(
                    "size", fp.spec.target_size
                )
            )
            ready = fp.member_states(now).get("ready", 0)
            detail[name] = {
                "desired": desired,
                "ready": ready,
                "drift": desired - ready,
            }
        self.metrics.set_drift(
            {name: d["drift"] for name, d in detail.items()}, detail
        )
        return detail

    # ---- the loop ----

    async def step(self) -> str:
        """One reconcile round; returns "leading" or "standby"."""
        self.metrics.reconcile_loops_total += 1
        if self.lease is not None:
            acquired = False
            try:
                acquired = self.lease.try_acquire()
            except OSError:
                logger.exception("lease acquisition failed")
            if not acquired:
                if self.was_leading:
                    # deposed mid-reign (paused past TTL, another controller
                    # took over): the round in flight dies at the fencing
                    # check — counted, demoted, never actuated
                    try:
                        self.fence()
                    except StaleLeaderError:
                        logger.warning(
                            "deposed: fencing epoch superseded; demoting"
                        )
                    self.was_leading = False
                return "standby"
            self.was_leading = True
        try:
            await self._adopt_orphans()
            await self._converge()
        except StaleLeaderError:
            # fence() already counted it; this controller stops acting now
            self.was_leading = False
            return "standby"
        self.compute_drift()
        return "leading"

    async def _run(self) -> None:
        while True:
            try:
                await self.step()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("reconcile step failed")
            await asyncio.sleep(self.interval_s)

    def start(self) -> asyncio.Task:
        if self._task is None:
            self._task = asyncio.create_task(self._run())
        return self._task

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        if self._client is not None:
            await self._client.aclose()
            self._client = None

    # ---- observability ----

    def snapshot(self) -> dict:
        snap = self.metrics.snapshot()
        snap.update(
            {
                "leader": self.leading,
                "epoch": self.lease.epoch if self.lease is not None else 0,
                "owner": self.lease.owner if self.lease is not None else "",
            }
        )
        return snap


def healthz_block(reconciler: Optional["Reconciler"]) -> dict:
    """The leadership + drift block /healthz grows on reconciler-wired
    edges (router.py, fleet.py) — None-safe so unwired edges stay
    byte-identical."""
    if reconciler is None:
        return {}
    snap = reconciler.snapshot()
    return {
        "control_plane": {
            "leader": snap["leader"],
            "epoch": snap["epoch"],
            "drift": snap["drift"],
            "converged": snap["converged"],
        }
    }


# ---- standalone controller process (the drill target) ----


def parse_pool_args(pairs: list[str], flag: str = "--pool") -> dict[str, int]:
    pools: dict[str, int] = {}
    for pair in pairs or []:
        name, sep, size = pair.partition("=")
        name = name.strip()
        if not sep or not name:
            raise ValueError(f"bad {flag} {pair!r}: expected NAME=SIZE")
        try:
            pools[name] = int(size)
        except ValueError:
            raise ValueError(f"bad {flag} {pair!r}: SIZE must be int") from None
    return pools


def _alive_entries(manifest: EndpointsManifest) -> dict:
    return {
        url: e
        for url, e in manifest.entries().items()
        if supervisor_alive(int(e.get("supervisor_pid") or 0))
    }


def _seed_desired(
    store: StateStore,
    manifest: EndpointsManifest,
    pool_sizes: dict[str, int],
    serve_pool: str,
    serve_size: int,
    serve_version: str,
) -> None:
    """First boot or post-corruption: desired state comes from OBSERVATION
    first (live manifest counts), CLI seed second — a corrupt journal next
    to a healthy running fleet converges to the fleet, not to replayed or
    default intent."""
    observed: dict[str, int] = {}
    for _url, entry in _alive_entries(manifest).items():
        pool = str(entry.get("pool") or "")
        observed[pool] = observed.get(pool, 0) + 1
    for name, size in pool_sizes.items():
        store.set_pool(name, size=observed.get(name) or size, **{"class": name})
    if serve_pool:
        store.set_pool(
            serve_pool,
            size=observed.get(serve_pool) or serve_size,
            version=serve_version,
        )


def _flip_journal_byte(state_dir: str) -> bool:
    """The `journal_corrupt` fault: flip one byte mid-journal on disk so
    the NEXT controller's load fails the CRC (detected, quarantined,
    rebuilt from observation — never silently replayed)."""
    path = os.path.join(state_dir, JOURNAL_NAME)
    try:
        with open(path, "r+b") as f:
            blob = bytearray(f.read())
            if not blob:
                return False
            idx = len(blob) // 2
            blob[idx] ^= 0xFF
            f.seek(0)
            f.write(bytes(blob))
            f.flush()
            os.fsync(f.fileno())
    except OSError:
        return False
    logger.error("journal_corrupt fault: flipped a byte of %s", path)
    return True


async def _amain(args) -> int:
    from spotter_tpu.serving import rollout as rollout_mod
    from spotter_tpu.serving.fleet import FleetController, PoolSpec
    from spotter_tpu.serving.replica_pool import ReplicaPool
    from spotter_tpu.testing import cluster, faults

    os.makedirs(args.state_dir, exist_ok=True)
    workdir = args.workdir or args.state_dir
    os.makedirs(workdir, exist_ok=True)
    metrics = ControlPlaneMetrics()
    manifest = EndpointsManifest(args.manifest)
    lease = LeaderLease(
        os.path.join(args.state_dir, "leader.lease"),
        owner=args.owner,
        ttl_s=args.lease_ttl,
    )
    status_path = args.status_file or os.path.join(
        args.state_dir, f"status-{args.owner}.json"
    )
    stop_event = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop_event.set)

    def write_status(phase: str, extra: Optional[dict] = None) -> None:
        payload = {
            "pid": os.getpid(),
            "owner": args.owner,
            "phase": phase,
            "leader": lease.leading,
            "epoch": lease.epoch,
            "reconcile": metrics.snapshot(),
            "ts": time.time(),
        }
        if extra:
            payload.update(extra)
        try:
            _atomic_write(
                status_path, json.dumps(payload, sort_keys=True).encode()
            )
        except OSError:
            logger.exception("writing status failed")

    # -- standby: wait for the lease (the passive half of active-passive) --
    while not stop_event.is_set():
        if lease.try_acquire():
            break
        write_status("standby")
        try:
            await asyncio.wait_for(stop_event.wait(), args.tick)
        except asyncio.TimeoutError:
            pass
    if stop_event.is_set():
        write_status("stopped")
        return 0
    logger.info("%s leading with fencing epoch %d", args.owner, lease.epoch)

    # -- desired state: journal, or rebuild from observation --
    store = load_or_rebuild(args.state_dir, metrics)
    pool_sizes = parse_pool_args(args.pool)
    if not store.state["pools"]:
        _seed_desired(
            store, manifest, pool_sizes, args.serve_pool,
            args.serve_size, args.serve_version,
        )

    # -- fleet controller over the journaled pools (minus the rollout's) --
    member_env = {}
    if args.member_env:
        member_env = dict(
            pair.split("=", 1) for pair in args.member_env.split(",") if pair
        )
    specs = []
    for name, spec in store.state["pools"].items():
        if name == args.serve_pool:
            continue
        specs.append(
            PoolSpec(
                name,
                spawner=cluster.fleet_spawner(
                    workdir, name, env=member_env, manifest=args.manifest
                ),
                target_size=int(spec.get("size") or 0),
            )
        )
    controller = None
    reconciler = None
    if specs:
        controller = FleetController(specs, tick_s=args.tick)
        reconciler = Reconciler(
            controller, store, lease=lease, manifest=manifest,
            interval_s=args.tick, metrics=metrics,
        )
        controller.fence = reconciler.fence
        for spec in specs:
            spec.spawner = reconciler.fenced_spawner(spec.spawner)
        adopted = reconciler.adopt_existing()
        logger.info("pre-start adoption: %d members", adopted)
        await controller.start()
        reconciler.start()

    # -- rollout: resume the journaled wave, or start a requested one --
    serve_rp = None
    rollout_ctl = None
    rollout_task = None
    if args.serve_pool:
        serve_entries = {
            url: e
            for url, e in _alive_entries(manifest).items()
            if e.get("pool") == args.serve_pool
        }
        serve_rp = ReplicaPool(list(serve_entries), allow_empty=True)
        for url, entry in serve_entries.items():
            if entry.get("version"):
                serve_rp.set_version(url, str(entry["version"]))
        # serve members found in the manifest are adoptions too — the
        # rollout pool's members survived the previous controller
        metrics.adoptions_total += len(serve_entries)
        await serve_rp.start()
        plan = rollout_mod.resume_plan(store.state.get("rollout"))
        version_to = (plan or {}).get("version_to") or args.rollout_to
        versions = {str(e.get("version") or "") for e in serve_entries.values()}
        if version_to and (plan or versions != {version_to}):
            canary_url = (plan or {}).get("canary_url")
            old = [
                rollout_mod.RolloutMember(
                    url=url,
                    handle=ManifestHandle(url, entry),
                    version=str(entry.get("version") or ""),
                )
                for url, entry in sorted(serve_entries.items())
                if url != canary_url
                and str(entry.get("version") or "") != version_to
            ]
            resume = None
            resume_handle = None
            if plan is not None:
                if canary_url and canary_url in serve_entries:
                    resume_handle = ManifestHandle(
                        canary_url, serve_entries[canary_url]
                    )
                else:
                    canary_url = None  # canary died with the controller:
                    # restart the wave from a fresh spawn
                resume = {
                    "wave": int(plan.get("wave") or 0),
                    "canary_url": canary_url,
                    "window_s": plan.get("window_s"),
                    "expired": plan.get("action") == "rollback",
                }
                metrics.rollout_resumes_total += 1
                logger.info("resuming journaled rollout: %s", plan)
            spawner = cluster.rollout_spawner(
                workdir, version_to, pool=args.serve_pool,
                env=member_env, manifest=args.manifest,
            )
            if reconciler is not None:
                spawner = reconciler.fenced_spawner(spawner)
            rollout_ctl = rollout_mod.RolloutController(
                serve_rp,
                old,
                spawner,
                version_to,
                version_from=args.serve_version,
                window_s=args.rollout_window,
                confirm_window_s=args.rollout_window,
                min_requests=args.rollout_min_requests,
                spawn_wait_s=args.spawn_wait,
                drain_deadline_ms=args.drain_ms,
                store=store,
                resume=resume,
                resume_handle=resume_handle,
            )
            rollout_task = asyncio.create_task(rollout_ctl.run())

    # -- autoscale actuation seam (ISSUE 20): once the initial population
    # converges, apply --scale-pool sizes through the brain's fenced +
    # journaled path — the chaos harness times a kill -9 against this to
    # prove a successor adopts mid-scale-up instead of double-spawning --
    scale_sizes = parse_pool_args(args.scale_pool, flag="--scale-pool")
    scale_sizes = {
        n: s for n, s in scale_sizes.items()
        if controller is not None and n in controller.pools
    }
    scale_brain = None
    if scale_sizes:
        from spotter_tpu.serving.autoscale import AutoscalerBrain, ModelPool

        scale_brain = AutoscalerBrain(
            controller,
            [
                ModelPool(model=n, max_size=max(s, 1))
                for n, s in scale_sizes.items()
            ],
            store=store,
            fence=reconciler.fence if reconciler is not None else None,
        )
    scaled = False

    # -- run until told to stop --
    rollout_result = None
    while not stop_event.is_set():
        # control-plane chaos seams (ISSUE 16): a deterministic kill -9 at
        # a chosen tick, and a one-shot journal byte-flip the NEXT load
        # must detect. Checked first so the crash lands mid-cycle, with
        # journaled state exactly as a real kill would leave it.
        if faults.take_journal_corrupt():
            _flip_journal_byte(args.state_dir)
        if faults.take_controller_crash():
            logger.error("controller_crash fault: SIGKILL self (pid %d)",
                         os.getpid())
            os.kill(os.getpid(), signal.SIGKILL)
        if rollout_task is not None and rollout_task.done():
            try:
                rollout_result = rollout_task.result()
            except Exception as exc:
                rollout_result = f"error: {exc!r}"
                logger.exception("rollout task failed")
            rollout_task = None
            # the rollout reached a terminal state: fold the journal into
            # a fresh snapshot (the compaction path, exercised live)
            try:
                store.compact()
            except OSError:
                logger.exception("journal compaction failed")
        if reconciler is None and lease is not None:
            # rollout-only controller still heartbeats its lease
            lease.try_acquire()
        if scale_brain is not None and not scaled:
            converged = all(
                controller.pools[n].pool.has_available()
                and len(controller.pools[n].members)
                >= controller.pools[n].spec.target_size
                for n in scale_sizes
            )
            if converged:
                try:
                    for n, s in scale_sizes.items():
                        scale_brain.actuate(n, s, "drill: --scale-pool")
                    scaled = True
                except Exception:
                    logger.exception("--scale-pool actuation failed")
                    scaled = True  # fenced-out or broken: do not retry-spam
        extra = {
            "rollout": rollout_ctl.snapshot() if rollout_ctl else None,
            "rollout_result": rollout_result,
            "fleet": controller.snapshot() if controller else None,
            "seq": store.seq,
            "scaled": scaled,
        }
        write_status("leading" if lease.leading else "deposed", extra)
        try:
            await asyncio.wait_for(stop_event.wait(), args.tick)
        except asyncio.TimeoutError:
            pass

    # -- clean stop: members OUTLIVE the controller (that is the point) --
    if rollout_task is not None:
        rollout_task.cancel()
        try:
            await rollout_task
        except (asyncio.CancelledError, Exception):
            pass
    if rollout_ctl is not None:
        await rollout_ctl.stop()
    if serve_rp is not None:
        await serve_rp.stop()
    if reconciler is not None:
        await reconciler.stop()
    if controller is not None:
        await controller.stop(shutdown_members=args.shutdown_members)
    lease.release()
    write_status("stopped", {"rollout_result": rollout_result})
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="spotter-tpu crash-safe fleet controller "
        "(durable desired state + reconcile loop + leader lease)"
    )
    parser.add_argument("--state-dir", required=True,
                        help="journal/snapshot/lease directory")
    parser.add_argument("--manifest", required=True,
                        help="endpoints manifest path (shared with supervisors)")
    parser.add_argument("--workdir", default=None,
                        help="member pidfiles/logs (default: state dir)")
    parser.add_argument("--owner", default=f"ctrl-{os.getpid()}",
                        help="lease owner name (status file suffix)")
    parser.add_argument("--lease-ttl", type=float, default=2.0)
    parser.add_argument("--tick", type=float, default=DEFAULT_INTERVAL_S)
    parser.add_argument("--status-file", default=None)
    parser.add_argument("--pool", action="append", default=[],
                        metavar="NAME=SIZE",
                        help="fleet-managed pool seed (repeatable)")
    parser.add_argument("--scale-pool", action="append", default=[],
                        metavar="NAME=SIZE",
                        help="after initial convergence, scale this pool to "
                        "SIZE through the fenced+journaled autoscaler path "
                        "(repeatable; the crash-mid-scale drill seam)")
    parser.add_argument("--serve-pool", default="",
                        help="rollout-managed pool name (not fleet-spawned)")
    parser.add_argument("--serve-size", type=int, default=0)
    parser.add_argument("--serve-version", default="")
    parser.add_argument("--rollout-to", default="",
                        help="start (or resume) a rollout to this version")
    parser.add_argument("--rollout-window", type=float, default=8.0)
    parser.add_argument("--rollout-min-requests", type=int, default=0)
    parser.add_argument("--spawn-wait", type=float, default=30.0)
    parser.add_argument("--drain-ms", type=float, default=1000.0)
    parser.add_argument("--member-env", default="",
                        help="extra child env as K=V[,K=V...]")
    parser.add_argument("--shutdown-members", action="store_true",
                        help="tear the fleet down on clean exit (default: "
                        "members outlive the controller)")
    args = parser.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO,
        format=f"%(asctime)s {args.owner} %(levelname)s %(name)s: %(message)s",
    )
    from spotter_tpu.testing import faults

    plan = faults.maybe_activate_from_env()
    if plan is not None:
        logger.warning("CONTROLLER FAULT PLAN ACTIVE: %s", plan)
    return asyncio.run(_amain(args))


if __name__ == "__main__":
    sys.exit(main())
