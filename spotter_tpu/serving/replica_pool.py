"""Async failover client pool: health-checked replicas, ejection, replay.

A single hardened replica (ISSUE 1) still leaves clients staring at hard
errors the moment that replica is preempted — on spot TPU capacity that is
routine, not exceptional (Spotlight, arXiv:2606.19004). This pool is the
fleet-side answer, DeepServe-style health-aware routing (arXiv:2501.14417)
in one file:

- **Selection**: round-robin over replicas that are neither ejected nor
  marked unhealthy by the background health loop (`/healthz` readiness, so
  a draining or breaker-open replica stops receiving traffic BEFORE it
  starts refusing connections).
- **Outlier ejection**: `eject_threshold` consecutive transport failures
  eject a replica for an exponentially growing backoff (doubling up to
  `backoff_max_s`); a later health-check success resets it.
- **Gray-failure scoring + soft ejection** (ISSUE 14): hard ejection only
  fires on transport FAILURES, so a replica that answers /healthz but
  serves 10x slow — spot-VM throttling, a noisy neighbor (Spotlight's
  gray-failure signature) — used to poison fleet p99 indefinitely. Every
  replica now carries two latency EWMAs (request latency and health-probe
  latency; the probe one means a silent-slow replica is detected with ZERO
  traffic) compared against the pool median of the same kind: a score of
  `ewma / median`, taking the worse of the two kinds. A score past
  `SPOTTER_TPU_OUTLIER_RATIO` soft-ejects the replica — it stays in the
  ring but its selection weight drops to `SPOTTER_TPU_OUTLIER_WEIGHT`
  (default 5%), in both the round-robin path (smooth weighted RR) and the
  cache-affinity `prefer` path (deterministic thinning: the gray owner
  keeps a weight-sized trickle of its keyed traffic, the rest falls to the
  next-ranked holder). The trickle plus the probes keep the EWMAs honest;
  once the score recovers under the restore ratio the replica enters a
  CANARY state (quarter weight) and only returns to full weight after
  `canary_ok` consecutive good responses — no binary eject flap. The last
  available non-gray replica is never soft-ejected, and scores below an
  absolute floor (`SPOTTER_TPU_OUTLIER_MIN_MS`) never trip it, so
  microsecond-noise on a fast fleet cannot manufacture outliers.
- **Replay**: a `/detect` attempt that dies on a transport error
  (connection reset — the signature of a killed replica), times out,
  answers 5xx/429, or fails the caller's response `validator` (a corrupt
  binary frame — wire.py CRC, ISSUE 14) is replayed against the next
  replica. Detection is idempotent, so replay is safe; the client sees one
  answer, not the preemption. Replays spend from a `RetryBudget` (ISSUE 6):
  a correlated failure — a preemption storm taking half the fleet — must
  not amplify offered load with unbudgeted retries, so replays in a sliding
  window are capped at `SPOTTER_TPU_RETRY_BUDGET_PCT` of the recent request
  count (with a small floor so single-replica deaths still fail over
  cleanly); an exhausted budget fails the request FAST with a 503-shaped
  error instead of piling more attempts onto survivors.
- **Fast-fail when suspended** (ISSUE 6 bugfix): when every replica is
  ejected or health-marked down — or the pool is empty because its tier
  scaled to zero — `request()` raises `PoolSuspendedError` immediately
  (with a Retry-After hint derived from the soonest un-ejection) instead of
  burning the client's whole deadline on a candidate set that cannot serve.
- **Budgeted adaptive hedging** (ISSUE 14, upgrading the ISSUE 2 fixed
  timer): with `adaptive_hedge=True` the hedge trigger is the live pool
  p95 (a sliding window of observed request latencies) instead of a static
  `hedge_after_s` — the timer tracks what "slow" means for THIS pool under
  THIS load. Hedge spend is capped by a sliding-window hedge budget
  (`SPOTTER_TPU_HEDGE_BUDGET_PCT` of recent requests, floor
  `SPOTTER_TPU_HEDGE_BUDGET_MIN`) exactly like the retry budget: an
  exhausted budget falls back to un-hedged waiting (never an error).
  The losing attempt is CANCELLED (the underlying HTTP request torn down,
  awaited to completion) and excluded from breaker/ejection counts — a
  cancelled loser is the hedge's fault, not the replica's — though its
  elapsed time does feed the loser's latency EWMA, so chronic hedge losers
  converge to gray.

Membership is dynamic (`add_endpoint` / `remove_endpoint`): the fleet
controller (serving/fleet.py) grows and shrinks pools as spot capacity
churns and idle tiers scale to zero.

`tests/test_failover.py` and the gray-failure matrix
(`tests/test_grayfail.py`) drive this pool;
`python -m spotter_tpu.serving.router` runs it as a tiny edge router.
Counters surface in `snapshot()` (and the router's /metrics): ejections,
soft ejections/restores, replays, hedges (+ budget exhaustions and loser
cancellations), invalid responses, budget exhaustions, client-visible
failures.
"""

import asyncio
import itertools
import logging
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import httpx

from spotter_tpu.serving.resilience import Ewma
from spotter_tpu.serving.wire import VERSION_HEADER

logger = logging.getLogger(__name__)

DEFAULT_EJECT_THRESHOLD = 3
DEFAULT_BACKOFF_BASE_S = 0.5
DEFAULT_BACKOFF_MAX_S = 30.0
DEFAULT_HEALTH_INTERVAL_S = 0.5
DEFAULT_REQUEST_TIMEOUT_S = 30.0

RETRY_BUDGET_PCT_ENV = "SPOTTER_TPU_RETRY_BUDGET_PCT"
RETRY_BUDGET_MIN_ENV = "SPOTTER_TPU_RETRY_BUDGET_MIN"
DEFAULT_RETRY_BUDGET_PCT = 10.0
# Floor: a single killed replica can strand up to a client-concurrency's
# worth of in-flight requests at once; those replays must never be the ones
# the budget refuses, or plain one-replica failover (ISSUE 2) breaks.
DEFAULT_RETRY_BUDGET_MIN = 10
DEFAULT_RETRY_BUDGET_WINDOW_S = 30.0

# Gray-failure outlier scoring (ISSUE 14). Ratios are against the pool
# median of the same latency kind; the restore ratio sits well under the
# trip ratio (hysteresis) so a replica hovering at the boundary doesn't
# flap between full and thinned weight.
OUTLIER_RATIO_ENV = "SPOTTER_TPU_OUTLIER_RATIO"
OUTLIER_RESTORE_RATIO_ENV = "SPOTTER_TPU_OUTLIER_RESTORE_RATIO"
OUTLIER_ALPHA_ENV = "SPOTTER_TPU_OUTLIER_ALPHA"
OUTLIER_WEIGHT_ENV = "SPOTTER_TPU_OUTLIER_WEIGHT"
OUTLIER_MIN_SAMPLES_ENV = "SPOTTER_TPU_OUTLIER_MIN_SAMPLES"
OUTLIER_MIN_MS_ENV = "SPOTTER_TPU_OUTLIER_MIN_MS"
DEFAULT_OUTLIER_RATIO = 3.0  # <= 0 disables the scorer entirely
DEFAULT_OUTLIER_RESTORE_RATIO = 1.5
DEFAULT_OUTLIER_ALPHA = 0.3
DEFAULT_OUTLIER_WEIGHT = 0.05  # gray replica's traffic share
DEFAULT_OUTLIER_MIN_SAMPLES = 8
DEFAULT_OUTLIER_MIN_MS = 20.0  # below this an EWMA can never be an outlier
CANARY_WEIGHT = 0.25  # re-probe share while confirming recovery
CANARY_OK_REQUIRED = 3  # consecutive good canary responses to restore

# replica outlier states
OUTLIER_OK = "ok"
OUTLIER_GRAY = "gray"
OUTLIER_CANARY = "canary"

# Budgeted adaptive hedging (ISSUE 14)
HEDGE_BUDGET_PCT_ENV = "SPOTTER_TPU_HEDGE_BUDGET_PCT"
HEDGE_BUDGET_MIN_ENV = "SPOTTER_TPU_HEDGE_BUDGET_MIN"
DEFAULT_HEDGE_BUDGET_PCT = 10.0
DEFAULT_HEDGE_BUDGET_MIN = 5
DEFAULT_HEDGE_QUANTILE = 0.95
# adaptive trigger needs this many windowed samples before the observed
# quantile is trusted; colder pools fall back to the static timer (if any)
HEDGE_MIN_SAMPLES = 20
HEDGE_WINDOW = 512  # sliding sample window behind the adaptive trigger
# The trigger is floored at this multiple of the observed p50: on a TIGHT
# latency distribution the p95 sits just above typical, so a bare-quantile
# trigger would hedge ~5% of perfectly healthy requests by construction —
# pure duplicate load for zero tail win (measured +1.3% unloaded p50).
# Hedging only pays when the tail is DETACHED from typical (a drowning
# replica), which is exactly tail >= 2x p50.
HEDGE_MIN_P50_RATIO = 2.0
# the sorted-window quantile is recomputed at most every this many new
# samples (a 512-float sort per request is measurable at 20 ms services)
_HEDGE_RECOMPUTE_EVERY = 16

# statuses that mean "this replica can't serve it right now, another might":
# 429 queue-full, 503 draining/breaker, 500 engine fault
REPLAYABLE_STATUSES = frozenset({429, 500, 502, 503})


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


class PoolExhaustedError(RuntimeError):
    """Every replica failed or was ejected for one request."""

    def __init__(self, msg: str, retry_after_s: float = 1.0) -> None:
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class PoolSuspendedError(PoolExhaustedError):
    """No replica is even worth trying right now (all ejected/down, or the
    pool is empty): fail fast with a Retry-After instead of waiting out the
    request deadline against a candidate set that cannot serve."""


class RetryBudgetExhaustedError(PoolExhaustedError):
    """A replay was needed but the budget refuses to amplify load further."""


class RetryBudget:
    """Sliding-window retry budget (Envoy-style, rate-based): replays in the
    last `window_s` seconds are capped at max(`min_retries`,
    `pct`% of requests seen in the same window). Shared budgets are fine —
    the fleet controller gives each pool its own slice so a bulk-tier storm
    cannot starve SLO-tier failover. The hedge budget (ISSUE 14) is a
    second instance of this same class over its own knobs: hedges are
    deliberate load amplification too, just cheaper per event.
    """

    def __init__(
        self,
        pct: Optional[float] = None,
        min_retries: Optional[int] = None,
        window_s: float = DEFAULT_RETRY_BUDGET_WINDOW_S,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        if pct is None:
            raw = os.environ.get(RETRY_BUDGET_PCT_ENV, "").strip()
            pct = float(raw) if raw else DEFAULT_RETRY_BUDGET_PCT
        if min_retries is None:
            raw = os.environ.get(RETRY_BUDGET_MIN_ENV, "").strip()
            min_retries = int(raw) if raw else DEFAULT_RETRY_BUDGET_MIN
        self.pct = max(float(pct), 0.0)
        self.min_retries = max(int(min_retries), 0)
        self.window_s = window_s
        self._clock = clock
        self._requests: deque[float] = deque()
        self._retries: deque[float] = deque()
        self.exhausted_total = 0

    @classmethod
    def for_hedging(cls, clock: Callable[[], float] = time.monotonic) -> "RetryBudget":
        """The hedge-spend budget from its own env knobs (ISSUE 14)."""
        return cls(
            pct=_env_float(HEDGE_BUDGET_PCT_ENV, DEFAULT_HEDGE_BUDGET_PCT),
            min_retries=_env_int(
                HEDGE_BUDGET_MIN_ENV, DEFAULT_HEDGE_BUDGET_MIN
            ),
            clock=clock,
        )

    def _trim(self, now: float) -> None:
        horizon = now - self.window_s
        while self._requests and self._requests[0] < horizon:
            self._requests.popleft()
        while self._retries and self._retries[0] < horizon:
            self._retries.popleft()

    def record_request(self) -> None:
        now = self._clock()
        self._trim(now)
        self._requests.append(now)

    def allowed(self) -> float:
        """Replays currently permitted in the window."""
        self._trim(self._clock())
        return max(
            float(self.min_retries), self.pct / 100.0 * len(self._requests)
        )

    def try_spend(self) -> bool:
        """Reserve one replay; False (and a bumped exhausted counter) when
        the window is already at its cap."""
        now = self._clock()
        self._trim(now)
        if len(self._retries) + 1 > self.allowed():
            self.exhausted_total += 1
            return False
        self._retries.append(now)
        return True

    def snapshot(self) -> dict:
        now = self._clock()
        self._trim(now)
        return {
            "pct": self.pct,
            "min_retries": self.min_retries,
            "window_s": self.window_s,
            "window_requests": len(self._requests),
            "window_retries": len(self._retries),
            "allowed": self.allowed(),
            "exhausted_total": self.exhausted_total,
        }


@dataclass
class Replica:
    url: str  # base URL, e.g. http://127.0.0.1:8001
    healthy: bool = True
    consecutive_failures: int = 0
    ejected_until: float = 0.0
    eject_backoff_s: float = 0.0
    # gray-failure scoring state (ISSUE 14): request-latency and
    # probe-latency EWMAs, the score vs the pool median, the soft-eject
    # state machine, and the deterministic weighted-selection accumulators
    req_ewma: Ewma = field(default_factory=Ewma)
    probe_ewma: Ewma = field(default_factory=Ewma)
    outlier_state: str = OUTLIER_OK
    outlier_score: float = 0.0
    canary_ok: int = 0
    soft_ejections: int = 0
    wrr_credit: float = 0.0  # smooth weighted round-robin accumulator
    prefer_credit: float = 0.0  # affinity-path thinning accumulator
    # deployment identity (ISSUE 15): which build this replica serves —
    # set by the rollout controller at membership time and kept fresh from
    # the X-Spotter-Version response header. "" = unknown (pre-version
    # fleets), which matches every pin.
    version: str = ""
    # externally pinned selection weight (rollout canary hold): None =
    # unpinned; combined with the outlier-state weight by taking the min
    pinned_weight: Optional[float] = None
    # hard quarantine (ISSUE 17): set by the integrity plane when the
    # replica's answers disagree with the quorum. Unlike gray soft
    # ejection (a 5% trickle so latency can recover), quarantine is
    # ABSOLUTE — zero weight, no canary trickle, no health-loop
    # restoration — because a wrong answer served is a wrong answer a
    # client acted on. Only an explicit unquarantine (operator, or the
    # replica's verified post-86 restart) lifts it.
    quarantined: bool = False
    quarantine_reason: str = ""
    # diagnostics
    requests: int = 0
    failures: int = 0
    ejections: int = 0
    last_error: str = ""
    lock: asyncio.Lock = field(default_factory=asyncio.Lock)

    def available(self, now: float) -> bool:
        return (
            self.healthy and not self.quarantined and now >= self.ejected_until
        )


def _median(values: list[float]) -> Optional[float]:
    if not values:
        return None
    vals = sorted(values)
    n = len(vals)
    if n % 2:
        return vals[n // 2]
    return 0.5 * (vals[n // 2 - 1] + vals[n // 2])


class ReplicaPool:
    def __init__(
        self,
        endpoints: list[str],
        client: Optional[httpx.AsyncClient] = None,
        eject_threshold: int = DEFAULT_EJECT_THRESHOLD,
        backoff_base_s: float = DEFAULT_BACKOFF_BASE_S,
        backoff_max_s: float = DEFAULT_BACKOFF_MAX_S,
        health_interval_s: float = DEFAULT_HEALTH_INTERVAL_S,
        request_timeout_s: float = DEFAULT_REQUEST_TIMEOUT_S,
        hedge_after_s: Optional[float] = None,
        adaptive_hedge: bool = False,
        hedge_quantile: float = DEFAULT_HEDGE_QUANTILE,
        hedge_budget: Optional[RetryBudget] = None,
        max_rounds: int = 2,
        round_pause_s: float = 0.25,
        retry_budget: Optional[RetryBudget] = None,
        outlier_ratio: Optional[float] = None,
        outlier_restore_ratio: Optional[float] = None,
        outlier_alpha: Optional[float] = None,
        outlier_weight: Optional[float] = None,
        outlier_min_samples: Optional[int] = None,
        outlier_min_ms: Optional[float] = None,
        allow_empty: bool = False,
    ) -> None:
        if not endpoints and not allow_empty:
            raise ValueError("ReplicaPool needs at least one endpoint")
        self.retry_budget = retry_budget or RetryBudget()
        self.client = client or httpx.AsyncClient(
            timeout=httpx.Timeout(request_timeout_s, connect=2.0)
        )
        self._owns_client = client is None
        self.eject_threshold = max(1, eject_threshold)
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.health_interval_s = health_interval_s
        # hedging (ISSUE 2 static timer; ISSUE 14 adaptive trigger + budget)
        self.hedge_after_s = hedge_after_s
        self.adaptive_hedge = adaptive_hedge
        self.hedge_quantile = min(max(hedge_quantile, 0.5), 0.999)
        self.hedge_budget = hedge_budget or RetryBudget.for_hedging()
        self._lat_window: deque[float] = deque(maxlen=HEDGE_WINDOW)
        self._lat_samples = 0
        self._hedge_trigger_cache: Optional[float] = None
        self._hedge_trigger_at = 0
        # gray-failure scoring knobs (ISSUE 14); ratio <= 0 disables
        if outlier_ratio is None:
            outlier_ratio = _env_float(OUTLIER_RATIO_ENV, DEFAULT_OUTLIER_RATIO)
        if outlier_restore_ratio is None:
            outlier_restore_ratio = _env_float(
                OUTLIER_RESTORE_RATIO_ENV, DEFAULT_OUTLIER_RESTORE_RATIO
            )
        if outlier_alpha is None:
            outlier_alpha = _env_float(OUTLIER_ALPHA_ENV, DEFAULT_OUTLIER_ALPHA)
        if outlier_weight is None:
            outlier_weight = _env_float(
                OUTLIER_WEIGHT_ENV, DEFAULT_OUTLIER_WEIGHT
            )
        if outlier_min_samples is None:
            outlier_min_samples = _env_int(
                OUTLIER_MIN_SAMPLES_ENV, DEFAULT_OUTLIER_MIN_SAMPLES
            )
        if outlier_min_ms is None:
            outlier_min_ms = _env_float(
                OUTLIER_MIN_MS_ENV, DEFAULT_OUTLIER_MIN_MS
            )
        self.outlier_ratio = float(outlier_ratio)
        self.outlier_restore_ratio = min(
            float(outlier_restore_ratio), max(self.outlier_ratio, 0.0)
        )
        self.outlier_alpha = float(outlier_alpha)
        self.outlier_weight = min(max(float(outlier_weight), 0.001), 1.0)
        self.outlier_min_samples = max(int(outlier_min_samples), 2)
        self.outlier_min_ms = max(float(outlier_min_ms), 0.0)
        self.max_rounds = max(1, max_rounds)
        self.round_pause_s = round_pause_s
        self._rr = itertools.count()
        self._health_task: Optional[asyncio.Task] = None
        self.replicas = [self._new_replica(u.rstrip("/")) for u in endpoints]
        # counters (event-loop only — no lock needed)
        self.requests_total = 0
        self.replays_total = 0
        self.hedges_total = 0
        self.hedge_wins_total = 0
        self.hedge_cancels_total = 0
        self.ejections_total = 0
        self.soft_ejections_total = 0
        self.soft_restores_total = 0
        self.invalid_responses_total = 0  # validator rejections (frame CRC)
        self.failures_total = 0  # client-visible (pool exhausted)
        self.suspended_total = 0  # fast-failed: nothing worth trying
        # mixed-version request pinning (ISSUE 15)
        self.version_pinned_replays_total = 0
        self.version_pin_relaxed_total = 0
        # hard quarantine (ISSUE 17)
        self.quarantines_total = 0
        self.quarantines_refused_total = 0

    def _new_replica(self, url: str, healthy: bool = True) -> Replica:
        r = Replica(url=url, healthy=healthy)
        r.req_ewma = Ewma(self.outlier_alpha)
        r.probe_ewma = Ewma(self.outlier_alpha)
        return r

    # ---- membership (fleet controller: spot churn, scale-to-zero) ----

    def add_endpoint(self, url: str, healthy: bool = False) -> Replica:
        """Add a replica at runtime. New members default to `healthy=False`
        ("starting"): the health loop promotes them on the first /healthz 200,
        so live traffic never races a replica that is still binding/compiling."""
        url = url.rstrip("/")
        existing = self.replica_for(url)
        if existing is not None:
            return existing
        r = self._new_replica(url, healthy=healthy)
        self.replicas.append(r)
        return r

    def remove_endpoint(self, url: str) -> Optional[Replica]:
        url = url.rstrip("/")
        r = self.replica_for(url)
        if r is not None:
            self.replicas.remove(r)
        return r

    def replica_for(self, url: str) -> Optional[Replica]:
        url = url.rstrip("/")
        for r in self.replicas:
            if r.url == url:
                return r
        return None

    def set_version(self, url: str, version: str) -> None:
        """Pin a replica's deploy version (ISSUE 15). The rollout
        controller calls this when it adds a canary so version pinning
        works BEFORE the first response teaches the pool; live responses
        keep it fresh afterwards (the X-Spotter-Version header)."""
        r = self.replica_for(url)
        if r is not None:
            r.version = version

    def set_weight(self, url: str, weight: Optional[float]) -> None:
        """Pin (or with None clear) a replica's selection weight — the
        rollout canary hold (ISSUE 15). Composes with the gray-failure
        scorer by taking the min, so a gray canary is thinned even
        further, never boosted."""
        r = self.replica_for(url)
        if r is not None:
            r.pinned_weight = (
                None if weight is None
                else min(max(float(weight), 0.001), 1.0)
            )

    def has_available(self) -> bool:
        now = time.monotonic()
        return any(r.available(now) for r in self.replicas)

    # ---- hard quarantine (ISSUE 17 output-integrity plane) ----

    def quarantine(self, url: str, reason: str = "") -> bool:
        """Hard-quarantine a replica: out of the ring at ZERO weight —
        primaries, replays, hedges, affinity preferences and quorum
        witnessing all stop immediately (`available()` is the single
        gate they share). Refused (False, counted) for an unknown or
        already-quarantined url, and for the LAST available replica:
        quarantining the whole fleet turns "some wrong answers" into
        "no answers at all", which is an operator decision, not an
        automated one."""
        r = self.replica_for(url)
        if r is None or r.quarantined:
            self.quarantines_refused_total += 1
            return False
        now = time.monotonic()
        peers = sum(
            1 for o in self.replicas if o is not r and o.available(now)
        )
        if peers < 1:
            self.quarantines_refused_total += 1
            logger.error(
                "REFUSING to quarantine %s (%s): it is the last available "
                "replica — operator attention required", url, reason,
            )
            return False
        r.quarantined = True
        r.quarantine_reason = reason
        self.quarantines_total += 1
        logger.error(
            "replica %s HARD-QUARANTINED (zero weight, no trickle): %s",
            url, reason,
        )
        return True

    def unquarantine(self, url: str) -> bool:
        """Lift a quarantine (operator path, or a replica readmitted
        after its post-86 restart passed verified readiness)."""
        r = self.replica_for(url)
        if r is None or not r.quarantined:
            return False
        r.quarantined = False
        r.quarantine_reason = ""
        logger.warning("replica %s quarantine lifted", url)
        return True

    def pick_other(self, exclude=()) -> Optional[str]:
        """Public witness selection for the integrity quorum sampler: the
        next ranked AVAILABLE replica outside `exclude`, through the same
        smooth-WRR the primary path uses (so dual-dispatch load spreads
        and a thinned gray replica witnesses proportionally less)."""
        r = self._pick({u.rstrip("/") for u in exclude})
        return r.url if r is not None else None

    # ---- lifecycle ----

    async def start(self) -> None:
        if self._health_task is None:
            self._health_task = asyncio.create_task(self._health_loop())

    async def stop(self) -> None:
        if self._health_task is not None:
            self._health_task.cancel()
            try:
                await self._health_task
            except asyncio.CancelledError:
                pass
            self._health_task = None
        if self._owns_client:
            await self.client.aclose()

    # ---- health ----

    async def _probe(self, r: Replica) -> None:
        t0 = time.monotonic()
        try:
            resp = await self.client.get(f"{r.url}/healthz", timeout=2.0)
            ok = resp.status_code == 200
        except Exception as exc:
            ok = False
            r.last_error = f"health: {exc!r}"
        if self.replica_for(r.url) is not r:
            # the member was retired (remove_endpoint) — or removed and
            # re-added as a NEW Replica object — while this probe was in
            # flight (ISSUE 16 satellite): mutating the stale object now
            # would resurrect a retiring member into the ring mid-drain,
            # exactly the adoption/retire race the reconcile loop surfaced
            return
        if not ok:
            r.healthy = False
            return
        # probe latency feeds the gray-failure score (ISSUE 14 satellite:
        # it used to be measured and discarded) — a replica whose event
        # loop is starved answers /healthz slow long before live traffic
        # would show it, so a silent-slow replica is flagged with ZERO
        # /detect traffic
        self._observe_latency(r, (time.monotonic() - t0) * 1e3, probe=True)
        if not r.available(time.monotonic()):
            # only an UNAVAILABLE replica is promoted by a probe success; on
            # an available one the success is a no-op so probes cannot reset
            # the consecutive-failure count live traffic is accumulating
            self._record_success(r)

    async def _health_loop(self) -> None:
        """Probe every replica: an unavailable one so recovery (supervisor
        restart, breaker close, drain replaced by a fresh pod) un-ejects it
        without risking live traffic on a dead endpoint, and an available
        one so a readiness flip (drain, maintenance notice — the preemption
        signature the fleet controller watches) stops routing BEFORE the
        replica starts refusing connections, even on an idle pool."""
        while True:
            probes = [self._probe(r) for r in self.replicas]
            if probes:
                await asyncio.gather(*probes, return_exceptions=True)
            await asyncio.sleep(self.health_interval_s)

    def _record_success(self, r: Replica) -> None:
        r.consecutive_failures = 0
        r.eject_backoff_s = 0.0
        r.ejected_until = 0.0
        r.healthy = True

    def _record_failure(self, r: Replica, err: str) -> None:
        r.failures += 1
        r.last_error = err
        r.consecutive_failures += 1
        if r.consecutive_failures >= self.eject_threshold:
            r.eject_backoff_s = min(
                max(r.eject_backoff_s * 2.0, self.backoff_base_s),
                self.backoff_max_s,
            )
            r.ejected_until = time.monotonic() + r.eject_backoff_s
            r.ejections += 1
            self.ejections_total += 1
            logger.warning(
                "replica %s ejected for %.1f s after %d consecutive failures (%s)",
                r.url, r.eject_backoff_s, r.consecutive_failures, err,
            )

    # ---- gray-failure scoring (ISSUE 14) ----

    def _observe_latency(
        self, r: Replica, ms: float, probe: bool = False, window: bool = True
    ) -> None:
        """One latency observation for `r`: update the kind's EWMA, feed
        the pool-wide hedge-trigger window (request latencies only), count
        canary evidence, and re-run the outlier state machine."""
        if probe:
            r.probe_ewma.update(ms)
        else:
            r.req_ewma.update(ms)
            if window:
                self._lat_window.append(ms)
                self._lat_samples += 1
            if r.outlier_state == OUTLIER_CANARY:
                r.canary_ok += 1
        if self.outlier_ratio > 0:
            self._update_outliers()

    def _outlier_score(
        self,
        r: Replica,
        med_req: Optional[float],
        med_probe: Optional[float],
    ) -> float:
        """`ewma / pool median`, the worse of the request and probe kinds.
        A kind contributes only with enough samples AND an EWMA above the
        absolute floor — a 0.3 ms probe against a 0.1 ms median is noise,
        not a gray failure."""
        score = 0.0
        if (
            med_req
            and r.req_ewma.samples >= self.outlier_min_samples
            and r.req_ewma.value >= self.outlier_min_ms
        ):
            score = r.req_ewma.value / med_req
        if (
            med_probe
            and r.probe_ewma.samples >= self.outlier_min_samples
            and r.probe_ewma.value >= self.outlier_min_ms
        ):
            score = max(score, r.probe_ewma.value / med_probe)
        return score

    def _update_outliers(self) -> None:
        """Recompute every replica's score against the pool medians and run
        the soft-ejection state machine:

            ok ---(score >= ratio, peers exist)--> gray (weight-down)
            gray --(score <= restore ratio)------> canary (quarter weight)
            canary --(CANARY_OK good responses)--> ok (full restore)
            canary --(score >= ratio again)------> gray

        The medians need at least two contributing replicas — with one
        member there is no peer to be slower than."""
        req_vals = [
            r.req_ewma.value
            for r in self.replicas
            if r.req_ewma.samples >= self.outlier_min_samples
        ]
        probe_vals = [
            r.probe_ewma.value
            for r in self.replicas
            if r.probe_ewma.samples >= self.outlier_min_samples
        ]
        med_req = _median(req_vals) if len(req_vals) >= 2 else None
        med_probe = _median(probe_vals) if len(probe_vals) >= 2 else None
        if not med_req and not med_probe:
            return
        now = time.monotonic()
        for r in self.replicas:
            score = self._outlier_score(r, med_req, med_probe)
            r.outlier_score = score
            if r.outlier_state == OUTLIER_OK:
                if score >= self.outlier_ratio:
                    # never soft-eject the last non-gray available replica:
                    # a thinned pool of one is just a slower pool of one
                    peers = sum(
                        1
                        for o in self.replicas
                        if o is not r
                        and o.available(now)
                        and o.outlier_state != OUTLIER_GRAY
                    )
                    if peers >= 1:
                        r.outlier_state = OUTLIER_GRAY
                        r.canary_ok = 0
                        r.soft_ejections += 1
                        self.soft_ejections_total += 1
                        logger.warning(
                            "replica %s soft-ejected (gray): latency score "
                            "%.2fx pool median (req %.1f ms, probe %.1f ms)",
                            r.url, score, r.req_ewma.value, r.probe_ewma.value,
                        )
            elif r.outlier_state == OUTLIER_GRAY:
                if score <= self.outlier_restore_ratio:
                    r.outlier_state = OUTLIER_CANARY
                    r.canary_ok = 0
                    logger.info(
                        "replica %s score recovered (%.2fx): canary re-probe",
                        r.url, score,
                    )
            elif r.outlier_state == OUTLIER_CANARY:
                if score >= self.outlier_ratio:
                    r.outlier_state = OUTLIER_GRAY
                    r.canary_ok = 0
                elif (
                    score <= self.outlier_restore_ratio
                    and r.canary_ok >= CANARY_OK_REQUIRED
                ):
                    r.outlier_state = OUTLIER_OK
                    self.soft_restores_total += 1
                    logger.info(
                        "replica %s restored to full weight after %d good "
                        "canary responses", r.url, r.canary_ok,
                    )

    def _weight(self, r: Replica) -> float:
        w = 1.0
        if r.outlier_state == OUTLIER_GRAY:
            w = self.outlier_weight
        elif r.outlier_state == OUTLIER_CANARY:
            w = CANARY_WEIGHT
        if r.pinned_weight is not None:  # rollout canary hold (ISSUE 15)
            w = min(w, r.pinned_weight)
        return w

    # ---- routing ----

    def _pick(
        self,
        exclude: set[str],
        prefer: Optional[list[str]] = None,
        version: Optional[str] = None,
    ) -> Optional[Replica]:
        """Next replica to try. `prefer` (cache-affinity routing, ISSUE 11)
        is a ranked candidate order — the rendezvous ring's weight ordering
        for this request's key: the first AVAILABLE preferred replica wins,
        so a dead/ejected/draining owner deterministically falls to the
        next-highest-weight holder instead of a random survivor. A
        soft-ejected (gray/canary) preferred holder is THINNED, not
        skipped: a deterministic credit accumulator gives it its weight's
        share of its keyed traffic (the canary trickle that lets its EWMA
        recover) and hands the rest to the next-ranked holder. With the
        preference order exhausted (or absent) selection is round-robin
        while every candidate is at full weight, else smooth weighted
        round-robin over the outlier weights.

        `version` (ISSUE 15) restricts candidates to that deploy version
        during a mixed-version window: a replica of unknown version ("")
        always matches, so pre-version fleets are unaffected. Callers
        decide the fallback policy when nothing matches (request() relaxes
        the pin for replays; hedges stay strict)."""
        now = time.monotonic()

        def version_ok(r: Replica) -> bool:
            return not version or not r.version or r.version == version

        if prefer:
            for url in prefer:
                if url in exclude:
                    continue
                r = self.replica_for(url)
                if r is None or not r.available(now) or not version_ok(r):
                    continue
                w = self._weight(r)
                if w >= 1.0:
                    return r
                r.prefer_credit += w
                if r.prefer_credit >= 1.0:
                    r.prefer_credit -= 1.0
                    return r
                # thinned away this time: fall to the next-ranked holder
        candidates = [
            r for r in self.replicas
            if r.url not in exclude and r.available(now) and version_ok(r)
        ]
        if not candidates:
            return None
        if all(
            r.outlier_state == OUTLIER_OK and r.pinned_weight is None
            for r in candidates
        ):
            # the pre-ISSUE-14 behavior, bit-identical while nothing is
            # gray and no rollout canary holds a pinned weight
            return candidates[next(self._rr) % len(candidates)]
        # smooth weighted round-robin (the nginx algorithm): deterministic,
        # proportional to weight, and maximally spread — no RNG in routing
        total = 0.0
        best: Optional[Replica] = None
        for r in candidates:
            w = self._weight(r)
            total += w
            r.wrr_credit += w
            if best is None or r.wrr_credit > best.wrr_credit:
                best = r
        assert best is not None
        best.wrr_credit -= total
        return best

    def _raise_if_suspended(self) -> None:
        """Fail fast when nothing is worth trying: the pool is empty (scaled
        to zero) or every replica is ejected/down. The Retry-After hint is
        the soonest un-ejection (or one health-probe interval for replicas
        merely marked down), so clients back off just long enough."""
        now = time.monotonic()
        if any(r.available(now) for r in self.replicas):
            return
        waits = [
            r.ejected_until - now
            for r in self.replicas
            if r.ejected_until > now
        ]
        if waits:
            retry_after = min(waits)
        elif self.replicas:  # health-marked down: next probe may revive them
            retry_after = self.health_interval_s
        else:  # empty pool — membership has to change first
            retry_after = 1.0
        retry_after = min(max(retry_after, 0.5), self.backoff_max_s)
        self.suspended_total += 1
        self.failures_total += 1
        raise PoolSuspendedError(
            f"pool suspended: 0 of {len(self.replicas)} replicas available",
            retry_after_s=retry_after,
        )

    async def _attempt(
        self, r: Replica, path: str, payload: dict,
        headers: Optional[dict] = None,
        validator: Optional[Callable] = None,
    ):
        r.requests += 1
        t0 = time.monotonic()
        resp = await self.client.post(
            f"{r.url}{path}", json=payload, headers=headers
        )
        # version learning (ISSUE 15): every direct response names its
        # build, so the pool's per-replica version map stays fresh with no
        # extra round trips (fan-in responses are comma-joined and skipped)
        ver = resp.headers.get(VERSION_HEADER, "")
        if ver and "," not in ver:
            r.version = ver
        if validator is not None and resp.status_code == 200:
            # wire-integrity check (ISSUE 14): a 200 whose body fails the
            # caller's validator (corrupt frame CRC) is a transport-shaped
            # failure — the raise feeds ejection counts and the replay
            # loop, exactly like a connection reset, and the client never
            # sees it
            try:
                validator(resp)
            except Exception:
                self.invalid_responses_total += 1
                raise
        if resp.status_code not in REPLAYABLE_STATUSES:
            self._observe_latency(r, (time.monotonic() - t0) * 1e3)
        return resp

    def _hedge_trigger_s(self) -> Optional[float]:
        """When to fire the hedge: the live pool quantile once the window
        is warm (adaptive mode), else the static timer. None = no hedging.
        The adaptive trigger is floored at HEDGE_MIN_P50_RATIO x the
        observed p50 (see the constant) and cached between recomputes."""
        if self.adaptive_hedge and len(self._lat_window) >= HEDGE_MIN_SAMPLES:
            if (
                self._hedge_trigger_cache is None
                or self._lat_samples - self._hedge_trigger_at
                >= _HEDGE_RECOMPUTE_EVERY
            ):
                lats = sorted(self._lat_window)
                n = len(lats)
                q = lats[min(int(self.hedge_quantile * n), n - 1)]
                p50 = lats[n // 2]
                self._hedge_trigger_cache = max(
                    q, HEDGE_MIN_P50_RATIO * p50, 1.0
                ) / 1000.0
                self._hedge_trigger_at = self._lat_samples
            return self._hedge_trigger_cache
        return self.hedge_after_s

    async def request(
        self,
        path: str,
        payload: dict,
        headers: Optional[dict] = None,
        prefer: Optional[list[str]] = None,
        validator: Optional[Callable] = None,
    ) -> httpx.Response:
        """POST `payload` with failover: try each distinct replica at most
        once per round, replaying on transport errors, replayable statuses,
        and validator rejections (corrupt frames); after a fully-failed
        round, pause briefly and run up to `max_rounds - 1` more (a
        preemption that takes the whole pool down for a beat — e.g. both
        replicas mid-drain — should cost the client milliseconds, not an
        error). Every attempt after the first spends from the retry budget;
        an exhausted budget raises RetryBudgetExhaustedError rather than
        amplifying a correlated failure. A pool with NO available replica
        fails fast with PoolSuspendedError (503 + Retry-After at the
        router) instead of waiting out the request deadline. Raises
        PoolExhaustedError when every round exhausted every replica.

        `validator` (optional) is called on every 200 response body BEFORE
        it is accepted; a raise is treated as a transport failure of that
        replica (counted in `invalid_responses_total`, replayed against the
        next ranked holder) — the wire-integrity hook (ISSUE 14)."""
        self.requests_total += 1
        self.retry_budget.record_request()
        self.hedge_budget.record_request()
        self._raise_if_suspended()
        last_err = ""
        first_attempt = True
        # mixed-version pinning (ISSUE 15): once the first attempt lands on
        # a versioned replica, replays prefer the SAME deploy version —
        # during a rollout window a request must not be re-processed by an
        # incompatible build. A replay relaxes the pin when no same-version
        # candidate remains (the pinned attempt already failed; masking the
        # failure beats skew purity). Hedges stay strict (_hedged_attempt):
        # a hedge DOUBLE-processes by design, which is exactly what must
        # never straddle two versions.
        pinned_version: Optional[str] = None
        for round_idx in range(self.max_rounds):
            if round_idx:
                await asyncio.sleep(self.round_pause_s)
            tried: set[str] = set()
            for attempt in range(len(self.replicas)):
                r = self._pick(tried, prefer, version=pinned_version)
                if r is None and pinned_version is not None:
                    self.version_pin_relaxed_total += 1
                    pinned_version = None
                    r = self._pick(tried, prefer)
                if r is None:
                    if not self.has_available():
                        # everything got ejected mid-request (e.g. a storm
                        # took the last survivor): stop burning the deadline
                        self._raise_if_suspended()
                    break  # all available replicas tried — next round
                if pinned_version is None and r.version:
                    pinned_version = r.version
                elif not first_attempt and pinned_version:
                    self.version_pinned_replays_total += 1
                if not first_attempt:
                    # about to replay: spend budget BEFORE the attempt, so a
                    # correlated failure cannot amplify offered load
                    if not self.retry_budget.try_spend():
                        self.failures_total += 1
                        raise RetryBudgetExhaustedError(
                            f"retry budget exhausted "
                            f"({self.retry_budget.snapshot()['window_retries']}"
                            f" replays in {self.retry_budget.window_s:.0f} s "
                            f"window; last: {last_err})",
                            retry_after_s=1.0,
                        )
                    self.replays_total += 1
                first_attempt = False
                tried.add(r.url)
                try:
                    trigger_s = self._hedge_trigger_s()
                    if trigger_s is not None and attempt == 0:
                        resp = await self._hedged_attempt(
                            r, tried, path, payload, headers, prefer,
                            trigger_s, validator,
                        )
                    else:
                        resp = await self._attempt(
                            r, path, payload, headers, validator
                        )
                except Exception as exc:  # connect/reset/timeout/corrupt
                    self._record_failure(r, repr(exc))
                    last_err = f"{r.url}: {exc!r}"
                    continue
                if resp.status_code in REPLAYABLE_STATUSES:
                    # the replica answered but can't serve (draining,
                    # breaker, queue full, engine fault): not a transport
                    # outlier unless it keeps happening — count a failure,
                    # replay elsewhere
                    self._record_failure(r, f"HTTP {resp.status_code}")
                    last_err = f"{r.url}: HTTP {resp.status_code}"
                    continue
                self._record_success(r)
                return resp
        self.failures_total += 1
        raise PoolExhaustedError(
            f"all {len(self.replicas)} replicas failed over "
            f"{self.max_rounds} rounds (last: {last_err})"
        )

    async def _hedged_attempt(
        self, first: Replica, tried: set[str], path: str, payload: dict,
        headers: Optional[dict] = None, prefer: Optional[list[str]] = None,
        trigger_s: float = 0.0, validator: Optional[Callable] = None,
    ) -> httpx.Response:
        """Fire at `first`; if no answer within the trigger, spend one unit
        of hedge budget and also fire at a second replica, taking whichever
        succeeds first. The loser is CANCELLED — its HTTP request torn down
        and awaited, no failure recorded against its replica (a cancelled
        hedge is the hedge's doing, not the replica's), though the loser's
        elapsed time feeds its latency EWMA so chronic losers converge to
        gray. An exhausted budget degrades to un-hedged waiting. An error
        from every in-flight attempt propagates so request()'s replay logic
        treats it like an unhedged failure."""
        t0 = time.monotonic()
        primary = asyncio.create_task(
            self._attempt(first, path, payload, headers, validator)
        )
        done, _ = await asyncio.wait({primary}, timeout=trigger_s)
        if done:
            return primary.result()  # success or raise-through to replay
        # version-strict backup (ISSUE 15): a hedge runs BOTH attempts to
        # completion-or-cancel — the one shape that genuinely
        # double-processes — so during a mixed-version window the backup
        # must serve the primary's deploy version; with no same-version
        # candidate the hedge is skipped (un-hedged waiting, never an
        # error), exactly like an exhausted hedge budget.
        backup_replica = self._pick(
            tried | {first.url}, prefer, version=first.version or None
        )
        if backup_replica is None:  # nowhere to hedge: wait the primary out
            return await primary
        if not self.hedge_budget.try_spend():
            # budget refused: fall back to un-hedged (never an error) — the
            # counter rides self.hedge_budget.exhausted_total
            return await primary
        self.hedges_total += 1
        backup = asyncio.create_task(
            self._attempt(backup_replica, path, payload, headers, validator)
        )
        pending = {primary, backup}
        last_exc: Optional[BaseException] = None
        while pending:
            done, pending = await asyncio.wait(
                pending, return_when=asyncio.FIRST_COMPLETED
            )
            for t in done:
                if t.exception() is None:
                    if pending:
                        for p in pending:
                            p.cancel()
                        # actually tear the losing request down (the
                        # cancelled task closes its HTTP stream) before
                        # returning — a hedge must not leak work
                        await asyncio.gather(
                            *pending, return_exceptions=True
                        )
                        self.hedge_cancels_total += len(pending)
                        if t is backup:
                            # the loser ran at least this long: a truthful
                            # lower-bound latency sample for its EWMA (kept
                            # out of the hedge-trigger window — it is not a
                            # completed request latency)
                            self._observe_latency(
                                first,
                                (time.monotonic() - t0) * 1e3,
                                window=False,
                            )
                    if t is backup:
                        self.hedge_wins_total += 1
                        self._record_success(backup_replica)
                    return t.result()
                last_exc = t.exception()
                if t is backup:  # request() only accounts for `first`
                    self._record_failure(backup_replica, repr(last_exc))
        assert last_exc is not None
        raise last_exc

    async def detect(self, payload: dict) -> dict:
        """POST /detect through the pool; returns the decoded JSON body."""
        resp = await self.request("/detect", payload)
        return resp.json()

    # ---- observability ----

    def snapshot(self) -> dict:
        now = time.monotonic()
        trigger_s = self._hedge_trigger_s()
        return {
            "pool_requests_total": self.requests_total,
            "pool_replays_total": self.replays_total,
            "pool_hedges_total": self.hedges_total,
            "pool_hedge_wins_total": self.hedge_wins_total,
            "pool_hedge_cancels_total": self.hedge_cancels_total,
            "pool_hedge_budget_exhausted_total": self.hedge_budget.exhausted_total,
            "pool_ejections_total": self.ejections_total,
            "pool_soft_ejections_total": self.soft_ejections_total,
            "pool_soft_restores_total": self.soft_restores_total,
            "pool_invalid_responses_total": self.invalid_responses_total,
            "pool_failures_total": self.failures_total,
            "pool_suspended_total": self.suspended_total,
            "pool_retry_budget_exhausted_total": self.retry_budget.exhausted_total,
            "pool_version_pinned_replays_total": self.version_pinned_replays_total,
            "pool_version_pin_relaxed_total": self.version_pin_relaxed_total,
            "pool_quarantines_total": self.quarantines_total,
            "pool_quarantines_refused_total": self.quarantines_refused_total,
            "retry_budget": self.retry_budget.snapshot(),
            "hedge": {
                "adaptive": self.adaptive_hedge,
                "trigger_ms": (
                    round(trigger_s * 1e3, 3) if trigger_s is not None else None
                ),
                "quantile": self.hedge_quantile,
                "budget": self.hedge_budget.snapshot(),
            },
            "outlier": {
                "ratio": self.outlier_ratio,
                "restore_ratio": self.outlier_restore_ratio,
                "weight": self.outlier_weight,
                "min_samples": self.outlier_min_samples,
                "min_ms": self.outlier_min_ms,
            },
            "replicas": [
                {
                    "url": r.url,
                    "healthy": r.healthy,
                    "available": r.available(now),
                    "ejected_for_s": max(r.ejected_until - now, 0.0),
                    "consecutive_failures": r.consecutive_failures,
                    "requests": r.requests,
                    "failures": r.failures,
                    "ejections": r.ejections,
                    "outlier_state": r.outlier_state,
                    "outlier_score": round(r.outlier_score, 3),
                    "quarantined": r.quarantined,
                    "quarantine_reason": r.quarantine_reason,
                    "weight": self._weight(r),
                    "version": r.version,
                    "pinned_weight": r.pinned_weight,
                    "req_ewma_ms": round(r.req_ewma.value, 3),
                    "probe_ewma_ms": round(r.probe_ewma.value, 3),
                    "soft_ejections": r.soft_ejections,
                    "last_error": r.last_error,
                }
                for r in self.replicas
            ],
        }
