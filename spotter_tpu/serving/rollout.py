"""Safe deployment plane (ISSUE 15): versioned canary rollouts with
SLO-burn auto-rollback, shadow traffic, and wave-by-wave member
replacement under live traffic.

Every robustness tier so far hardens the fleet against ENVIRONMENTAL
failure — preemption (ISSUE 2/6), overload (ISSUE 8), gray replicas
(ISSUE 14). The leading cause of real outages at fleet scale is none of
those: it is a BAD DEPLOY, and until now a new build replaced every
replica at once with a human as the only rollback path. DeepServe
(PAPERS.md) treats deployment as a first-class automated fleet-lifecycle
operation; this module is that operation for the spotter fleet:

- **Waves**: `RolloutController.run()` replaces the fleet one member per
  wave. Each wave spawns ONE new-version replica (through the caller's
  spawner — the supervisor + persistent compile cache from ISSUE 2 make
  it a warm bring-up), adds it to the live `ReplicaPool` and HOLDS it at
  `SPOTTER_TPU_ROLLOUT_CANARY_WEIGHT` (default 5%) via the pool's
  pinned-weight machinery (the ISSUE 14 smooth-weighted-RR + affinity
  credit thinning, driven by deployment intent instead of a gray score).
- **Verdict**: after a verdict window of live evidence the canary is
  judged on the ISSUE 12 fleet-telemetry signals — per-replica error
  rate (pool transport/5xx failures + shadow-lane errors), p99 vs the
  BASELINE COHORT's median p99 (the aggregator's per-member snapshots),
  and the canary's fast-window `slo_burn_rate` (ISSUE 10) — plus the
  shadow lane's detection-diff rate. A failing signal rolls back EARLY
  (mid-window, as soon as minimum evidence exists); a clean window
  promotes: the canary goes to full weight and one old-version member is
  drained (`POST /drain {"deadline_ms": ...}` — the ISSUE 15 precise
  drain) and retired. Wave 1 runs the full window; later waves run a
  shorter confirmation window — the canary wave already proved the build.
- **Auto-rollback**: on any failed verdict the canary is removed from the
  pool FIRST (no new traffic), drained, and shut down; remaining members'
  weights are restored; the rollout FREEZES in `rolled_back` (promoted
  waves are not un-done — a frozen mixed fleet is an operator decision,
  not an automated flap). The rollback pins a flight-recorder trace
  (`/debug/traces`, request id `rollout-rollback-*`) and bumps
  `rollouts_total{verdict="rolled_back"}`; zero client-visible failures
  is the contract the deployment chaos drills
  (`testing/chaos_matrix.py::DEPLOY_MATRIX`, run by
  `tests/test_rollout.py`) enforce.
- **Shadow lane**: with `SPOTTER_TPU_SHADOW_PCT` > 0 the router mirrors a
  deterministically-sampled share of live requests to the canary
  (fire-and-forget, responses DISCARDED — never client-visible) and
  counts the detection-diff rate against the primary's answer. Shadow
  evidence feeds the verdict without exposing clients to the canary at
  all, so even a 0%-weight canary can be judged.

Version identity threads the whole stack: `SPOTTER_TPU_BUILD_VERSION` and
the weights digest live in the ISSUE 12 identity block (/metrics,
/healthz) and the `X-Spotter-Version` response header; the pool learns
per-replica versions from that header and PINS a request's replays and
hedges within one version during the mixed-version window
(replica_pool.py), so deploy skew can never double-process a request
across incompatible builds.
"""

import asyncio
import inspect
import json
import logging
import os
import time
from dataclasses import dataclass
from typing import Callable, Optional

from spotter_tpu.obs import compare
from spotter_tpu.obs import http as obs_http
from spotter_tpu.serving.replica_pool import ReplicaPool

logger = logging.getLogger(__name__)

# rollout states
IDLE = "idle"
SPAWNING = "spawning"
CANARY = "canary"
PROMOTING = "promoting"
ROLLING_BACK = "rolling_back"
ROLLED_BACK = "rolled_back"  # terminal: frozen, operator owns the next move
DONE = "done"  # terminal: every member serves the new version

CANARY_WEIGHT_ENV = "SPOTTER_TPU_ROLLOUT_CANARY_WEIGHT"
WINDOW_ENV = "SPOTTER_TPU_ROLLOUT_WINDOW_S"
CONFIRM_WINDOW_ENV = "SPOTTER_TPU_ROLLOUT_CONFIRM_S"
MIN_REQUESTS_ENV = "SPOTTER_TPU_ROLLOUT_MIN_REQUESTS"
MAX_ERROR_RATE_ENV = "SPOTTER_TPU_ROLLOUT_MAX_ERROR_RATE"
P99_RATIO_ENV = "SPOTTER_TPU_ROLLOUT_P99_RATIO"
BURN_LIMIT_ENV = "SPOTTER_TPU_ROLLOUT_BURN_LIMIT"
SHADOW_PCT_ENV = "SPOTTER_TPU_SHADOW_PCT"
SHADOW_DIFF_RATE_ENV = "SPOTTER_TPU_ROLLOUT_SHADOW_DIFF_RATE"
DRAIN_MS_ENV = "SPOTTER_TPU_ROLLOUT_DRAIN_MS"
SPAWN_WAIT_ENV = "SPOTTER_TPU_ROLLOUT_SPAWN_WAIT_S"

DEFAULT_CANARY_WEIGHT = 0.05
DEFAULT_WINDOW_S = 30.0
DEFAULT_MIN_REQUESTS = 20
DEFAULT_MAX_ERROR_RATE = 0.02
DEFAULT_P99_RATIO = 2.0
DEFAULT_BURN_LIMIT = 2.0
DEFAULT_SHADOW_PCT = 0.0
DEFAULT_SHADOW_DIFF_RATE = 0.02
DEFAULT_DRAIN_MS = 5000.0
DEFAULT_SPAWN_WAIT_S = 60.0
# the latency signal needs this many canary-served requests before its
# quantiles mean anything (below it, one sample IS the tail)
LATENCY_MIN_SERVED = 8
# a hard cap on waiting for verdict evidence: past this multiple of the
# window an idle fleet simply has no signal, and "no evidence of badness"
# promotes (the canary stays observable at full weight; the alternative —
# rolling back every deploy on a quiet fleet — would make rollouts
# impossible exactly when they are safest)
EVIDENCE_WAIT_FACTOR = 3.0

SHADOW_HEADER = "X-Spotter-Shadow"


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


@dataclass
class RolloutMember:
    """One fleet member the rollout knows about: `handle` is whatever the
    spawner returned (must expose `.url`; `shutdown()` may be sync or
    async) or None for members someone else manages (static endpoints —
    retire then only removes them from the pool and drains them)."""

    url: str
    handle: object = None
    version: str = ""


async def _shutdown_handle(handle) -> None:
    """Run a member handle's shutdown, whichever color its function is:
    in-process harness members are async (closing an aiohttp TestServer),
    subprocess members (testing/cluster.py) block on process exit."""
    if handle is None:
        return
    fn = getattr(handle, "shutdown", None)
    if fn is None:
        return
    if inspect.iscoroutinefunction(fn):
        await fn()
        return
    res = await asyncio.get_running_loop().run_in_executor(None, fn)
    if inspect.isawaitable(res):  # defensive: sync fn returning a coroutine
        await res


# The detection-diff definition moved to obs/compare.py (ISSUE 17) so the
# shadow verdict and the router's integrity quorum sampler judge "same
# answer" identically; re-exported under the old name for existing callers.
_norm_detections = compare.norm_detections


class ShadowLane:
    """Mirror a sampled share of live traffic to the canary and count the
    detection-diff rate. Deterministic Bresenham sampling (no RNG — the
    drills assert exact shares), responses discarded, every failure
    contained: nothing on this lane can ever surface to a client."""

    def __init__(self, pct: Optional[float] = None) -> None:
        if pct is None:
            pct = _env_float(SHADOW_PCT_ENV, DEFAULT_SHADOW_PCT)
        self.pct = min(max(float(pct), 0.0), 100.0)
        self._credit = 0.0
        self.requests_total = 0
        self.errors_total = 0
        self.compared_total = 0
        self.diffs_total = 0

    def take(self) -> bool:
        if self.pct <= 0:
            return False
        self._credit += self.pct
        if self._credit >= 100.0:
            self._credit -= 100.0
            return True
        return False

    async def run_one(
        self, client, canary_url: str, payload: dict, primary_body
    ) -> None:
        """One mirrored request: POST the canary, compare detections
        against the primary's already-serialized JSON body."""
        self.requests_total += 1
        try:
            resp = await client.post(
                f"{canary_url}/detect",
                json=payload,
                headers={SHADOW_HEADER: "1"},
            )
            if resp.status_code != 200:
                self.errors_total += 1
                return
            canary = resp.json()
        except Exception:
            self.errors_total += 1
            return
        try:
            primary = (
                json.loads(primary_body)
                if isinstance(primary_body, (bytes, bytearray, str))
                else primary_body
            )
            self.compared_total += 1
            if _norm_detections(primary.get("images")) != _norm_detections(
                canary.get("images")
            ):
                self.diffs_total += 1
        except Exception:
            # an uncomparable primary (frame body, unexpected shape) is a
            # skipped comparison, never an error charged to the canary
            self.compared_total = max(self.compared_total - 1, 0)

    def snapshot(self) -> dict:
        return {
            "pct": self.pct,
            "requests_total": self.requests_total,
            "errors_total": self.errors_total,
            "compared_total": self.compared_total,
            "diffs_total": self.diffs_total,
            "diff_rate": (
                self.diffs_total / self.compared_total
                if self.compared_total
                else 0.0
            ),
        }


def resume_plan(record, now: Optional[float] = None) -> Optional[dict]:
    """What a restarted controller should do about a journaled rollout
    (ISSUE 16): None when there is nothing in flight (no record, or a
    terminal state); otherwise a directive dict:

    - `{"action": "resume", ...}` — the crash landed inside a live canary
      window: re-adopt the canary at `canary_url` and serve out the
      REMAINING `window_s`;
    - `{"action": "rollback", ...}` — the canary window expired while no
      controller was alive to judge it (the canary carried live weight
      unwatched), so the only safe move is rollback;
    - `{"action": "restart_wave", ...}` — the crash landed between waves
      (spawning/promoting): start the wave over; orphan adoption has
      already reclaimed any half-spawned canary via the manifest.

    Wall-clock (`time.time`) on purpose: the journal outlives the process
    whose monotonic clock stamped it."""
    if not isinstance(record, dict):
        return None
    state = record.get("state")
    if state not in (SPAWNING, CANARY, PROMOTING):
        return None
    now = time.time() if now is None else now
    plan = {
        "wave": int(record.get("wave") or 0),
        "version_to": record.get("version_to") or "",
        "version_from": record.get("version_from") or "",
        "canary_url": record.get("canary_url"),
        "old_urls": list(record.get("old_urls") or []),
    }
    if state == CANARY and record.get("canary_url"):
        remaining = float(record.get("window_deadline") or 0.0) - now
        if remaining <= 0:
            plan["action"] = "rollback"
            plan["reason"] = "verdict_window_expired"
        else:
            plan["action"] = "resume"
            plan["window_s"] = remaining
        return plan
    plan["action"] = "restart_wave"
    plan["canary_url"] = None  # not yet serving at weight; respawn/adopt
    return plan


class RolloutController:
    """Wave-by-wave versioned rollout over a live `ReplicaPool`.

    The controller OWNS the deployment lifecycle but not the fleet: the
    pool keeps routing, health-checking, ejecting and replaying exactly as
    before; the controller only adds/weights/retires members and renders
    verdicts. `await run()` drives the whole rollout to a terminal state
    (`done` or `rolled_back`); `start()` wraps it in a background task for
    server wiring. Everything is event-loop-confined."""

    def __init__(
        self,
        pool: ReplicaPool,
        members: list,
        spawner: Callable[[], object],
        version_to: str,
        version_from: str = "",
        aggregator=None,
        canary_weight: Optional[float] = None,
        window_s: Optional[float] = None,
        confirm_window_s: Optional[float] = None,
        min_requests: Optional[int] = None,
        max_error_rate: Optional[float] = None,
        p99_ratio: Optional[float] = None,
        burn_limit: Optional[float] = None,
        shadow_pct: Optional[float] = None,
        shadow_diff_rate: Optional[float] = None,
        drain_deadline_ms: Optional[float] = None,
        spawn_wait_s: Optional[float] = None,
        tick_s: float = 0.1,
        store=None,
        resume: Optional[dict] = None,
        resume_handle=None,
    ) -> None:
        self.pool = pool
        self.old_members = [
            m if isinstance(m, RolloutMember) else (
                RolloutMember(url=m) if isinstance(m, str)
                else RolloutMember(url=m.url, handle=m)
            )
            for m in members
        ]
        self.new_members: list[RolloutMember] = []
        self.spawner = spawner
        self.version_to = version_to
        self.version_from = version_from
        self.aggregator = aggregator
        self.canary_weight = (
            canary_weight
            if canary_weight is not None
            else _env_float(CANARY_WEIGHT_ENV, DEFAULT_CANARY_WEIGHT)
        )
        self.window_s = (
            window_s if window_s is not None
            else _env_float(WINDOW_ENV, DEFAULT_WINDOW_S)
        )
        self.confirm_window_s = (
            confirm_window_s
            if confirm_window_s is not None
            else _env_float(CONFIRM_WINDOW_ENV, self.window_s / 3.0)
        )
        self.min_requests = (
            min_requests
            if min_requests is not None
            else _env_int(MIN_REQUESTS_ENV, DEFAULT_MIN_REQUESTS)
        )
        self.max_error_rate = (
            max_error_rate
            if max_error_rate is not None
            else _env_float(MAX_ERROR_RATE_ENV, DEFAULT_MAX_ERROR_RATE)
        )
        self.p99_ratio = (
            p99_ratio if p99_ratio is not None
            else _env_float(P99_RATIO_ENV, DEFAULT_P99_RATIO)
        )
        self.burn_limit = (
            burn_limit if burn_limit is not None
            else _env_float(BURN_LIMIT_ENV, DEFAULT_BURN_LIMIT)
        )
        self.shadow = ShadowLane(shadow_pct)
        self.shadow_diff_rate = (
            shadow_diff_rate
            if shadow_diff_rate is not None
            else _env_float(SHADOW_DIFF_RATE_ENV, DEFAULT_SHADOW_DIFF_RATE)
        )
        self.drain_deadline_ms = (
            drain_deadline_ms
            if drain_deadline_ms is not None
            else _env_float(DRAIN_MS_ENV, DEFAULT_DRAIN_MS)
        )
        self.spawn_wait_s = (
            spawn_wait_s
            if spawn_wait_s is not None
            else _env_float(SPAWN_WAIT_ENV, DEFAULT_SPAWN_WAIT_S)
        )
        self.tick_s = tick_s
        # durable intent (ISSUE 16): every wave transition is journaled to
        # the statestore BEFORE the fleet mutation it describes, so a
        # controller killed mid-wave leaves enough recorded state for its
        # successor to resume the wave (or roll back an expired one) —
        # `resume` is that successor's directive (see `resume_plan`), and
        # `resume_handle` re-attaches the orphaned canary's member handle
        # (a reconcile.ManifestHandle) so retire/shutdown still work.
        self.store = store
        self._resume = resume
        self._resume_handle = resume_handle
        # state
        self.state = IDLE
        self.wave = int(resume.get("wave") or 0) if resume else 0
        self.canary: Optional[RolloutMember] = None
        self.canary_since: Optional[float] = None
        self.rollback_reason: Optional[str] = None
        self.last_verdict: Optional[dict] = None
        self.rollback_s: Optional[float] = None
        self.verdict_window_s_used: Optional[float] = None
        # counters (the acceptance surface: rollouts_total{verdict})
        self.rollouts_total = {"promoted": 0, "rolled_back": 0}
        self.waves_promoted_total = 0
        self._task: Optional[asyncio.Task] = None
        self._shadow_tasks: set[asyncio.Task] = set()

    # ---- server wiring ----

    def start(self) -> asyncio.Task:
        if self._task is None:
            self._task = asyncio.create_task(self.run())
        return self._task

    async def stop(self) -> None:
        if self._task is not None and not self._task.done():
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        await self._drain_shadow_tasks()

    def maybe_shadow(self, payload: dict, primary_body) -> None:
        """Router hook: mirror this (already-served) request to the canary
        on the sampled lane. Synchronous and O(1) on the decline path —
        the idle-rollout hot-path cost is one state check."""
        if self.state != CANARY or self.canary is None:
            return
        if not self.shadow.take():
            return
        task = asyncio.create_task(
            self.shadow.run_one(
                self.pool.client, self.canary.url, payload, primary_body
            )
        )
        self._shadow_tasks.add(task)
        task.add_done_callback(self._shadow_tasks.discard)

    async def _drain_shadow_tasks(self) -> None:
        if self._shadow_tasks:
            await asyncio.gather(
                *list(self._shadow_tasks), return_exceptions=True
            )

    # ---- the rollout ----

    async def run(self) -> str:
        """Drive the rollout to a terminal state; returns it ("done" /
        "rolled_back"). One wave per old member; the first wave is the
        canary wave (full verdict window), later waves confirm on the
        shorter window."""
        if self._resume is not None and self._resume.get("expired"):
            # crashed mid-window and the verdict window expired while no
            # controller was alive to judge it: the canary got live weight
            # with nobody watching, so the ONLY safe resume is rollback
            url = self._resume.get("canary_url")
            if url:
                self.canary = RolloutMember(
                    url=url, handle=self._resume_handle,
                    version=self.version_to,
                )
                if self.pool.replica_for(url) is None:
                    self.pool.add_endpoint(url, healthy=False)
            await self._rollback("verdict_window_expired")
            return self.state
        if not self.old_members and not (
            self._resume and self._resume.get("canary_url")
        ):
            self.state = DONE
            self._journal(DONE)
            return self.state
        logger.info(
            "rollout %s -> %s: %d members, canary weight %.0f%%, "
            "window %.1f s",
            self.version_from or "?", self.version_to,
            len(self.old_members), self.canary_weight * 100, self.window_s,
        )
        try:
            first = True
            while self.old_members or (
                first and self._resume and self._resume.get("canary_url")
            ):
                resume_url = None
                window = (
                    self.window_s if self.wave == 0 else self.confirm_window_s
                )
                if first and self._resume is not None:
                    resume_url = self._resume.get("canary_url")
                    if resume_url and self._resume.get("window_s"):
                        # serve out the REMAINDER of the journaled window,
                        # not a fresh one — the dead controller's clock
                        # still binds its successor
                        window = float(self._resume["window_s"])
                first = False
                ok, reason = await self._one_wave(window, resume_url=resume_url)
                if not ok:
                    await self._rollback(reason)
                    return self.state
                self.wave += 1
                self.waves_promoted_total += 1
            self.state = DONE
            self._journal(DONE)
            self.rollouts_total["promoted"] += 1
            logger.info(
                "rollout to %s complete: %d waves promoted",
                self.version_to, self.wave,
            )
            return self.state
        finally:
            await self._drain_shadow_tasks()

    async def _one_wave(
        self, window_s: float, resume_url: Optional[str] = None
    ) -> tuple[bool, str]:
        self.state = SPAWNING
        if resume_url is None:
            self._journal(SPAWNING)
            handle = self.spawner()
            if inspect.isawaitable(handle):
                handle = await handle
            url = handle.url.rstrip("/")
            version = getattr(handle, "version", "") or self.version_to
        else:
            # resuming a journaled wave (ISSUE 16): the canary is already
            # running (adopted from the endpoints manifest) — re-attach it
            # instead of spawning a sibling
            url = resume_url.rstrip("/")
            handle = self._resume_handle
            version = self.version_to
        self.canary = RolloutMember(url=url, handle=handle, version=version)
        if self.pool.replica_for(url) is None:
            self.pool.add_endpoint(url, healthy=False)
        self.pool.set_version(url, version)
        self.pool.set_weight(url, self.canary_weight)
        # wait for the health loop to promote the new member
        deadline = time.monotonic() + self.spawn_wait_s
        while True:
            r = self.pool.replica_for(url)
            if r is not None and r.available(time.monotonic()):
                break
            if time.monotonic() > deadline:
                return False, "spawn_timeout"
            await asyncio.sleep(self.tick_s)
        self.state = CANARY
        self.canary_since = time.monotonic()
        self.verdict_window_s_used = window_s
        # journal the canary phase with a WALL-CLOCK window deadline: a
        # successor controller (new process, new monotonic epoch) must be
        # able to decide "is this window still live" from the record alone
        self._journal(CANARY, window_s=window_s,
                      window_deadline=time.time() + window_s)
        r = self.pool.replica_for(url)
        base = {
            "requests": r.requests,
            "failures": r.failures,
            "shadow_requests": self.shadow.requests_total,
            "shadow_errors": self.shadow.errors_total,
            "shadow_compared": self.shadow.compared_total,
            "shadow_diffs": self.shadow.diffs_total,
        }
        hard_deadline = (
            self.canary_since + window_s * EVIDENCE_WAIT_FACTOR
        )
        window_end = self.canary_since + window_s
        while True:
            await asyncio.sleep(self.tick_s)
            now = time.monotonic()
            verdict = self._verdict(base)
            self.last_verdict = verdict
            enough = verdict["evidence"] >= self.min_requests
            if enough and not verdict["ok"]:
                # fail fast: a bad deploy must not get the window's full
                # courtesy — rollback starts the moment the evidence bar
                # and a failing signal coincide
                return False, verdict["reason"]
            if (now >= window_end and enough) or now >= hard_deadline:
                # window served (or evidence never arrived on an idle
                # fleet, where no signal of badness promotes — see
                # EVIDENCE_WAIT_FACTOR)
                if verdict["ok"]:
                    await self._promote()
                return verdict["ok"], verdict.get("reason") or ""

    def _member_snapshot(self, url: str) -> Optional[dict]:
        if self.aggregator is None:
            return None
        try:
            return self.aggregator.member_snapshot(url)
        except Exception:
            return None

    def _verdict(self, base: dict) -> dict:
        """Render the canary verdict from the live signals. `ok=False`
        carries the FIRST failing signal as `reason` (error_rate beats
        latency beats burn beats shadow-diff — ordered by how direct the
        client harm is)."""
        assert self.canary is not None
        r = self.pool.replica_for(self.canary.url)
        attempts = (r.requests - base["requests"]) if r is not None else 0
        failures = (r.failures - base["failures"]) if r is not None else 0
        shadow_req = self.shadow.requests_total - base["shadow_requests"]
        shadow_err = self.shadow.errors_total - base["shadow_errors"]
        shadow_cmp = self.shadow.compared_total - base["shadow_compared"]
        shadow_diff = self.shadow.diffs_total - base["shadow_diffs"]
        evidence = attempts + shadow_req
        bad = failures + shadow_err
        error_rate = bad / evidence if evidence else 0.0

        canary_snap = self._member_snapshot(self.canary.url) or {}
        canary_p99 = float(canary_snap.get("latency_ms_p99") or 0.0)
        # the canary SIDE of the latency signal is its p90: early in the
        # window the canary has served tens of requests, where p99 IS the
        # single worst sample — one cold-start hiccup would roll back a
        # healthy build. A genuinely slow deploy moves every percentile
        # (10x service time moves p90 exactly as far as p99), so p90 keeps
        # the detection and drops the single-sample noise.
        canary_p90 = float(
            canary_snap.get("latency_ms_p90") or canary_p99 or 0.0
        )
        baseline_p99s = sorted(
            p
            for m in self.old_members + self.new_members
            for p in [
                float(
                    (self._member_snapshot(m.url) or {}).get(
                        "latency_ms_p99"
                    )
                    or 0.0
                )
            ]
            if p > 0.0
        )
        baseline_p99 = (
            baseline_p99s[len(baseline_p99s) // 2] if baseline_p99s else 0.0
        )
        burn = canary_snap.get("slo_burn_rate") or {}
        burn_fast = float(burn.get("fast") or 0.0)
        diff_rate = shadow_diff / shadow_cmp if shadow_cmp else 0.0

        # requests the canary actually SERVED (pool-routed + shadow): the
        # aggregator's canary quantiles cover both, so a 0%-weight canary
        # judged purely on shadow traffic still has a latency signal
        served = attempts + shadow_cmp
        reason = None
        if bad >= 2 and error_rate >= self.max_error_rate:
            reason = "error_rate"
        elif (
            canary_p90 > 0.0
            and baseline_p99 > 0.0
            and served >= LATENCY_MIN_SERVED
            and canary_p90 >= self.p99_ratio * baseline_p99
        ):
            reason = "p99_vs_baseline"
        elif burn_fast >= self.burn_limit:
            reason = "slo_burn"
        elif shadow_diff >= 2 and diff_rate >= self.shadow_diff_rate:
            reason = "shadow_diff"
        return {
            "ok": reason is None,
            "reason": reason,
            "evidence": evidence,
            "attempts": attempts,
            "failures": failures,
            "error_rate": round(error_rate, 4),
            "canary_p90_ms": round(canary_p90, 3),
            "canary_p99_ms": round(canary_p99, 3),
            "baseline_p99_ms": round(baseline_p99, 3),
            "slo_burn_fast": round(burn_fast, 4),
            "shadow_compared": shadow_cmp,
            "shadow_diffs": shadow_diff,
            "shadow_diff_rate": round(diff_rate, 4),
        }

    async def _drain_member(self, url: str) -> Optional[dict]:
        """POST /drain with the precise deadline (ISSUE 15 satellite);
        best-effort — a member that cannot drain still gets shut down."""
        headers = {}
        token = os.environ.get(obs_http.ADMIN_TOKEN_ENV, "")
        if token:
            headers[obs_http.ADMIN_TOKEN_HEADER] = token
        try:
            resp = await self.pool.client.post(
                f"{url}/drain",
                json={"deadline_ms": self.drain_deadline_ms},
                headers=headers,
            )
            summary = resp.json() if resp.status_code == 200 else None
            if summary is not None and summary.get("in_flight"):
                logger.warning(
                    "drain of %s timed out with %s batches in flight",
                    url, summary["in_flight"],
                )
            return summary
        except Exception:
            logger.warning("draining %s failed", url, exc_info=True)
            return None

    async def _retire(self, member: RolloutMember) -> None:
        """Retire a member under traffic, client-invisibly: out of the
        pool first (no new picks; in-flight replays still mask), drain
        what it holds, then shut the process down."""
        self.pool.remove_endpoint(member.url)
        await self._drain_member(member.url)
        try:
            await _shutdown_handle(member.handle)
        except Exception:
            logger.exception("shutting down %s failed", member.url)

    async def _promote(self) -> None:
        assert self.canary is not None
        self.state = PROMOTING
        self.pool.set_weight(self.canary.url, None)  # full weight
        # a resumed final wave can arrive with the retired cohort already
        # empty (the predecessor promoted it before dying) — promote the
        # canary, nothing left to retire
        old = self.old_members.pop(0) if self.old_members else None
        logger.info(
            "rollout wave %d promoted: %s (%s) in, retiring %s",
            self.wave, self.canary.url, self.canary.version,
            old.url if old else "(nothing)",
        )
        self._journal(PROMOTING, promoted_url=self.canary.url)
        if old is not None:
            await self._retire(old)
        self.new_members.append(self.canary)
        self.canary = None

    async def _rollback(self, reason: str) -> None:
        self.state = ROLLING_BACK
        self.rollback_reason = reason
        t0 = time.monotonic()
        logger.warning(
            "rollout to %s ROLLING BACK at wave %d: %s (verdict %s)",
            self.version_to, self.wave, reason, self.last_verdict,
        )
        if self.canary is not None:
            await self._retire(self.canary)
            self.canary = None
        # restore weights: nothing but the (now removed) canary is pinned,
        # but clear defensively so a frozen fleet routes at full weight
        for r in self.pool.replicas:
            r.pinned_weight = None
        self.rollback_s = time.monotonic() - t0
        self.state = ROLLED_BACK
        self._journal(ROLLED_BACK, reason=reason)
        self.rollouts_total["rolled_back"] += 1
        self._pin_rollback_trace(reason)

    def _journal(self, state: str, **extra) -> None:
        """Record this transition in the durable statestore (ISSUE 16).
        Best-effort by policy: a full state disk must degrade the rollout
        to the pre-journal (memory-only) behavior, not abort a promotion
        mid-flight — the chaos matrix covers the crash/resume paths where
        the journal DID land."""
        if self.store is None:
            return
        record = {
            "state": state,
            "wave": self.wave,
            "version_to": self.version_to,
            "version_from": self.version_from,
            "canary_weight": self.canary_weight,
            "canary_url": self.canary.url if self.canary else None,
            "old_urls": [m.url for m in self.old_members],
        }
        record.update(extra)
        try:
            self.store.set_rollout(record)
        except Exception:
            logger.exception("journaling rollout state %r failed", state)

    def _pin_rollback_trace(self, reason: str) -> None:
        """Pin a synthetic flight-recorder trace (the brownout pattern):
        /debug/traces answers 'when did the deploy roll back, and why'
        without scraping logs. Best effort, never fails the rollback."""
        try:
            from spotter_tpu import obs

            recorder = obs.get_recorder()
            if not recorder.enabled:
                return
            trace = obs.begin_trace(
                request_id=(
                    f"rollout-rollback-wave{self.wave}-{self.version_to}"
                )
            )
            trace.set_error(
                "rollout_rollback",
                f"{self.version_from or '?'} -> {self.version_to} "
                f"wave {self.wave}: {reason} ({self.last_verdict})",
            )
            recorder.record(trace)
        except Exception:
            logger.exception("pinning rollback trace failed")

    # ---- observability ----

    def snapshot(self) -> dict:
        return {
            "state": self.state,
            "version_from": self.version_from,
            "version_to": self.version_to,
            "wave": self.wave,
            "members_remaining": len(self.old_members),
            "members_promoted": len(self.new_members),
            "canary_url": self.canary.url if self.canary else None,
            "canary_weight": self.canary_weight,
            "window_s": self.window_s,
            "verdict_window_s": self.verdict_window_s_used,
            "rollouts_total": dict(self.rollouts_total),
            "waves_promoted_total": self.waves_promoted_total,
            "rollback_reason": self.rollback_reason,
            "rollback_s": (
                round(self.rollback_s, 3)
                if self.rollback_s is not None
                else None
            ),
            "last_verdict": self.last_verdict,
            "shadow": self.shadow.snapshot(),
        }
