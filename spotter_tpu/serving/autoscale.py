"""Model-multiplexed autoscaling: one fleet, per-model pools, live signals.

ROADMAP item 2's control loop, assembled from parts every prior arc built:
PR 12's FleetAggregator computes the scaling signals (queue depth, fast-window
SLO burn, cache-miss rate), PR 15/16's reconcile plane is the crash-safe
actuator (journaled desired sizes, leader-fenced spawns), PR 6 proved
scale-to-zero with compile-cache restore, and PR 13 made dp×tp pool shape a
per-model decision. DeepServe (arXiv:2501.14417) is the blueprint: a shared
fleet serves many models, each model family gets its own pool, and pool sizes
follow live demand instead of static provisioning.

Two halves:

- **Model routing** (`AutoscalerBrain.route`): every /detect request resolves
  to a model pool — `X-Spotter-Model` header first, then a `model` payload
  key (stripped before forwarding, like `request_class`), then `queries`
  presence (open-vocabulary detection needs an OWL-ViT-capable pool), then
  the fleet's default pool. Names resolve through the same
  earliest-start-then-longest substring scoring as `models/registry.py`, so
  "dab-detr-resnet-50" lands on the dab_detr pool, not plain detr. Unknown
  models and `queries` against a closed-set-only fleet are 400s that NAME the
  registry — a client can self-correct from the error body alone.
- **Scaling policy** (`AutoscalerBrain.step`): per pool, desired size follows
  (1) edge demand the brain counts itself at route time — only ADMITTED
  requests, which is what makes the loop flood-proof: `TenantPlane` sheds
  over-quota traffic 429 before routing, so a flood never shows up as demand;
  (2) aggregator boosters — summed `decode_pool_queue_depth`, fast-window
  `slo_burn_rate` > 1, cache-miss rate; (3) `TenantPlane.metrics_view()` shed
  pressure as a guard: when sheds are rising and in-quota signals are flat,
  the brain records an explicit hold (`flood_suppressions_total`) instead of
  scaling — quotas hold abusive load flat, the scaler serves what the quotas
  admit. Idle pools step down and eventually scale to zero through the
  controller's idle timer; the next routed request wakes them and the cold
  restore (persistent compile cache) is measured per restore as
  `time_to_ready_s`.

Every actuation is leader-fenced (the reconciler's fence raises
StaleLeaderError for a deposed controller) and journaled through
`statestore.py` BEFORE the controller's target changes, so a kill -9
mid-scale-up leaves a successor that adopts live members and converges to
the journaled size — never a double-spawn.
"""

import asyncio
import logging
import os
import time
from dataclasses import dataclass
from typing import Callable, Optional

from spotter_tpu.models.registry import match_score

logger = logging.getLogger(__name__)

MODEL_HEADER = "X-Spotter-Model"
MODEL_KEY = "model"

# The zoo's open-vocabulary-capable families (text queries at inference).
OPEN_VOCAB_FAMILIES = ("owlvit",)

# Per-family pool shape (ISSUE 20d): big dual-tower models shard tp over the
# PR 13 mesh; small single-tower detectors pack dp replicas instead.
POOL_SHAPES: dict[str, tuple[int, int]] = {
    "owlvit": (2, 1),           # CLIP towers shard cleanly over tp=2
    "deformable_detr": (2, 1),  # heaviest closed-set family in the zoo
}
DEFAULT_SHAPE = (1, 2)

TICK_ENV = "SPOTTER_TPU_AUTOSCALE_TICK_S"
MAX_SIZE_ENV = "SPOTTER_TPU_AUTOSCALE_MAX_SIZE"
QUEUE_HIGH_ENV = "SPOTTER_TPU_AUTOSCALE_QUEUE_HIGH"
BURN_HIGH_ENV = "SPOTTER_TPU_AUTOSCALE_BURN_HIGH"
MISS_HIGH_ENV = "SPOTTER_TPU_AUTOSCALE_MISS_HIGH"
INFLIGHT_HIGH_ENV = "SPOTTER_TPU_AUTOSCALE_INFLIGHT_HIGH"
DOWN_STEPS_ENV = "SPOTTER_TPU_AUTOSCALE_DOWN_STEPS"

DEFAULT_TICK_S = 1.0
DEFAULT_MAX_SIZE = 4
DEFAULT_QUEUE_HIGH = 4.0       # queued items per ready replica
DEFAULT_BURN_HIGH = 1.0        # fast-window burn > 1 = eating error budget
DEFAULT_MISS_HIGH = 0.5        # cache-miss rate marking a cold working set
DEFAULT_INFLIGHT_HIGH = 2.0    # edge in-flight per ready replica
DEFAULT_DOWN_STEPS = 3         # consecutive idle decides before stepping down


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


class ModelRoutingError(ValueError):
    """A request the model router cannot place. Always a client error (400)
    with a structured body that NAMES the registry, so the caller can fix
    the request without reading server logs."""

    status = 400
    kind = "model_routing"

    def __init__(self, message: str, families: dict[str, tuple]) -> None:
        super().__init__(message)
        self.families = {k: list(v) for k, v in families.items()}


class UnknownModelError(ModelRoutingError):
    kind = "unknown_model"


class ClosedSetQueriesError(ModelRoutingError):
    """`queries` (open-vocabulary text prompts) sent to a fleet — or an
    explicitly-named model — that only serves closed-set detectors."""

    kind = "closed_set_queries"


@dataclass(frozen=True)
class ModelPool:
    """One model family's pool: routing patterns + shape + size bounds.
    The pool name doubles as the FleetController pool name."""

    model: str                     # family name (models/registry.py)
    matches: tuple = ()            # substrings of MODEL_NAME that select it
    open_vocab: bool = False       # can serve `queries` (OWL-ViT lineage)
    tp: int = 1                    # tensor-parallel ways per member
    dp: int = 1                    # data-parallel replicas per member
    min_size: int = 0              # floor the brain never steps below
    max_size: int = DEFAULT_MAX_SIZE
    default: bool = False          # unrouted traffic lands here

    @property
    def name(self) -> str:
        return self.model

    @property
    def chips_per_member(self) -> int:
        return max(self.tp, 1) * max(self.dp, 1)


def pool_shape(family_name: str) -> tuple[int, int]:
    """(tp, dp) for one family — POOL_SHAPES with a dp-packing default."""
    return POOL_SHAPES.get(family_name, DEFAULT_SHAPE)


def model_pools_from_registry(
    max_size: Optional[int] = None, default_family: str = "rtdetr"
) -> list[ModelPool]:
    """One ModelPool per registered zoo family. Lazy zoo import (jax/PIL) —
    tests and the CPU bench construct explicit ModelPool lists instead."""
    from spotter_tpu.models import zoo  # noqa: F401  (self-registers families)
    from spotter_tpu.models.registry import MODEL_REGISTRY

    cap = max_size if max_size is not None else _env_int(
        MAX_SIZE_ENV, DEFAULT_MAX_SIZE
    )
    pools = []
    names = list(MODEL_REGISTRY)
    default = default_family if default_family in names else names[0]
    for name, family in MODEL_REGISTRY.items():
        tp, dp = pool_shape(name)
        pools.append(
            ModelPool(
                model=name,
                matches=tuple(family.matches),
                open_vocab=name in OPEN_VOCAB_FAMILIES,
                tp=tp,
                dp=dp,
                max_size=cap,
                default=name == default,
            )
        )
    return pools


@dataclass
class ScaleDecision:
    """One applied (or explicitly held) sizing decision, kept per pool for
    /metrics and fleet_top."""

    pool: str
    current: int
    desired: int
    reason: str
    at: float = 0.0


class _Track:
    """Edge in-flight tracking for one routed request. `done` is idempotent
    (the handler calls it with the real status AND from a finally leak
    guard, mirroring the tenancy admission discipline)."""

    __slots__ = ("_brain", "_pool", "_done")

    def __init__(self, brain: "AutoscalerBrain", pool: str) -> None:
        self._brain = brain
        self._pool = pool
        self._done = False

    def done(self, status: Optional[int] = None) -> None:
        if self._done:
            return
        self._done = True
        st = self._brain._pool_state[self._pool]
        st["inflight"] = max(st["inflight"] - 1, 0)
        if status is not None:
            if 200 <= status < 500 and status not in (429, 503):
                st["ok_total"] += 1
            else:
                st["fail_total"] += 1


class AutoscalerBrain:
    """Per-model-pool routing + scaling over a FleetController.

    The brain owns no replicas: the controller is the actuator (spawn,
    retire, scale-to-zero, restore), the state store is the intent journal,
    and the fence is the leadership check. `step()` is one decision round —
    the background loop calls it every `tick_s`; deterministic tests call it
    directly."""

    def __init__(
        self,
        controller,
        pools: list[ModelPool],
        aggregator=None,
        tenancy_plane=None,
        store=None,
        fence: Optional[Callable[[], object]] = None,
        tick_s: Optional[float] = None,
        queue_high: Optional[float] = None,
        burn_high: Optional[float] = None,
        miss_high: Optional[float] = None,
        inflight_high: Optional[float] = None,
        down_steps: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        if not pools:
            raise ValueError("AutoscalerBrain needs at least one ModelPool")
        self.controller = controller
        self.pools: dict[str, ModelPool] = {}
        for p in pools:
            if p.name in self.pools:
                raise ValueError(f"duplicate model pool {p.name!r}")
            if p.name not in controller.pools:
                raise ValueError(
                    f"model pool {p.name!r} has no FleetController pool"
                )
            self.pools[p.name] = p
        self.aggregator = aggregator
        self.tenancy_plane = tenancy_plane
        self.store = store
        self.fence = fence
        self.tick_s = tick_s if tick_s is not None else _env_float(
            TICK_ENV, DEFAULT_TICK_S
        )
        self.queue_high = queue_high if queue_high is not None else _env_float(
            QUEUE_HIGH_ENV, DEFAULT_QUEUE_HIGH
        )
        self.burn_high = burn_high if burn_high is not None else _env_float(
            BURN_HIGH_ENV, DEFAULT_BURN_HIGH
        )
        self.miss_high = miss_high if miss_high is not None else _env_float(
            MISS_HIGH_ENV, DEFAULT_MISS_HIGH
        )
        self.inflight_high = (
            inflight_high if inflight_high is not None
            else _env_float(INFLIGHT_HIGH_ENV, DEFAULT_INFLIGHT_HIGH)
        )
        self.down_steps = down_steps if down_steps is not None else _env_int(
            DOWN_STEPS_ENV, DEFAULT_DOWN_STEPS
        )
        self._clock = clock
        self._default = next(
            (p for p in self.pools.values() if p.default),
            next(iter(self.pools.values())),
        )
        self._open_vocab = next(
            (p for p in self.pools.values() if p.open_vocab), None
        )
        self._pool_state: dict[str, dict] = {
            name: {
                "admits_total": 0,
                "ok_total": 0,
                "fail_total": 0,
                "inflight": 0,
                "last_admits": 0,
                "idle_streak": 0,
                "last_decision": None,
            }
            for name in self.pools
        }
        self._last_step = self._clock()
        self._last_sheds: Optional[float] = None
        self._task: Optional[asyncio.Task] = None
        # counters (the `autoscale` /metrics block)
        self.decisions_total = 0
        self.scale_ups_total = 0
        self.scale_downs_total = 0
        self.wakes_total = 0
        self.flood_suppressions_total = 0
        self.routing_rejections_total = 0

    # ---- model routing (the data plane half) ----

    def _families(self) -> dict[str, tuple]:
        return {p.model: p.matches for p in self.pools.values()}

    def resolve_model(self, name: str) -> Optional[ModelPool]:
        """Pool for one model name: exact family-name match first (so bare
        "rtdetr" works), then the registry's earliest-start-then-longest
        substring scoring over each pool's patterns."""
        key = name.strip().lower()
        if not key:
            return None
        if key in self.pools:
            return self.pools[key]
        best = None
        best_score = None
        for p in self.pools.values():
            score = match_score(key, tuple(p.matches))
            if score is not None and (best_score is None or score < best_score):
                best, best_score = p, score
        return best

    def route(self, headers=None, payload=None) -> tuple[str, dict]:
        """(pool_name, forwardable_payload). Precedence: X-Spotter-Model
        header, `model` payload key (stripped — routing metadata, not
        detector input), `queries` presence -> the open-vocab pool, default
        pool. Raises ModelRoutingError subclasses for unplaceable requests;
        counts admitted demand and wakes scaled-to-zero pools."""
        name = ""
        if headers is not None:
            name = str(headers.get(MODEL_HEADER, "")).strip()
        has_queries = isinstance(payload, dict) and bool(payload.get("queries"))
        if isinstance(payload, dict):
            if not name:
                name = str(payload.get(MODEL_KEY, "")).strip()
            if MODEL_KEY in payload:
                payload = {k: v for k, v in payload.items() if k != MODEL_KEY}
        if name:
            pool = self.resolve_model(name)
            if pool is None:
                self.routing_rejections_total += 1
                raise UnknownModelError(
                    f"model '{name}' does not match any pool in this fleet",
                    self._families(),
                )
            if has_queries and not pool.open_vocab:
                self.routing_rejections_total += 1
                raise ClosedSetQueriesError(
                    f"model '{name}' resolves to closed-set family "
                    f"'{pool.model}' but the payload carries open-vocabulary "
                    f"`queries`",
                    self._families(),
                )
        elif has_queries:
            pool = self._open_vocab
            if pool is None:
                self.routing_rejections_total += 1
                raise ClosedSetQueriesError(
                    "payload carries open-vocabulary `queries` but this "
                    "fleet serves closed-set families only",
                    self._families(),
                )
        else:
            pool = self._default
        st = self._pool_state[pool.name]
        st["admits_total"] += 1
        self._maybe_wake(pool)
        return pool.name, payload

    def track(self, pool_name: str) -> _Track:
        st = self._pool_state[pool_name]
        st["inflight"] += 1
        return _Track(self, pool_name)

    # ---- actuation (journal first, fence always) ----

    def _journal(self, pool: ModelPool, size: int) -> None:
        if self.store is None:
            return
        self.store.set_pool(
            pool.name, size=size, model=pool.model, tp=pool.tp, dp=pool.dp
        )

    def _record(self, pool: ModelPool, current: int, desired: int,
                reason: str) -> ScaleDecision:
        dec = ScaleDecision(
            pool=pool.name, current=current, desired=desired,
            reason=reason, at=self._clock(),
        )
        self._pool_state[pool.name]["last_decision"] = dec
        self.decisions_total += 1
        return dec

    def _grow(self, pool: ModelPool, desired: int, reason: str) -> None:
        """Synchronous scale-up: fence, journal intent, raise the target,
        spawn the missing population. Sync so `route()` can wake a cold
        pool in the request path — the demand restore must not wait for
        the next policy tick."""
        fp = self.controller.pools[pool.name]
        current = fp.spec.target_size
        if self.fence is not None:
            self.fence()  # StaleLeaderError for a deposed controller
        self._journal(pool, desired)
        fp.spec.target_size = desired
        if fp.scaled_to_zero or not fp.members:
            # demand restore: the controller measures time_to_ready_s
            # restore-trigger -> first available member
            self.controller._maybe_restore(fp)
        else:
            self.controller.ensure_population(pool.name)
        self._record(pool, current, desired, reason)
        logger.info(
            "autoscale %s: %d -> %d (%s)", pool.name, current, desired, reason
        )

    async def _shrink(self, pool: ModelPool, desired: int, reason: str) -> None:
        current = self.controller.pools[pool.name].spec.target_size
        if self.fence is not None:
            self.fence()
        self._journal(pool, desired)
        await self.controller.set_target_size(pool.name, desired)
        self._record(pool, current, desired, reason)
        logger.info(
            "autoscale %s: %d -> %d (%s)", pool.name, current, desired, reason
        )

    def actuate(self, pool_name: str, size: int, reason: str) -> None:
        """One externally-driven sizing actuation through the full fenced +
        journaled path (the reconcile CLI's --scale-pool seam). Growth only
        spawns; a smaller size journals intent and lets the reconcile loop
        converge the shrink."""
        pool = self.pools[pool_name]
        size = max(min(int(size), pool.max_size), 0)
        fp = self.controller.pools[pool_name]
        if size >= fp.spec.target_size:
            self._grow(pool, size, reason)
        else:
            # journal the shrink intent; the reconcile loop converges it
            current = fp.spec.target_size
            if self.fence is not None:
                self.fence()
            self._journal(pool, size)
            fp.spec.target_size = size
            self._record(pool, current, size, reason)

    def _maybe_wake(self, pool: ModelPool) -> None:
        fp = self.controller.pools[pool.name]
        if fp.spec.spawner is None:
            return
        if fp.spec.target_size > 0 and not fp.scaled_to_zero:
            return
        desired = max(pool.min_size, 1)
        self.wakes_total += 1
        self._grow(pool, max(desired, fp.spec.target_size), "wake: demand after idle")

    # ---- scaling policy (the control loop half) ----

    def _aggregator_signals(self, fp) -> dict:
        """Per-pool sums over the aggregator's member snapshots: queue
        depth, fast-window burn, cache-miss rate. Zeroes when the
        aggregator is off or hasn't scraped — the edge demand counters
        carry the loop alone then."""
        out = {"queue_depth": 0.0, "burn_fast": 0.0, "cache_miss_rate": 0.0}
        agg = self.aggregator
        if agg is None or not getattr(agg, "enabled", False):
            return out
        hits = misses = 0.0
        for m in fp.members:
            snap = agg.member_snapshot(m.url)
            if not snap:
                continue
            qd = snap.get("decode_pool_queue_depth")
            if isinstance(qd, (int, float)):
                out["queue_depth"] += float(qd)
            burn = snap.get("slo_burn_rate")
            if isinstance(burn, dict):
                fast = burn.get("fast")
                if isinstance(fast, (int, float)):
                    out["burn_fast"] = max(out["burn_fast"], float(fast))
            hits += float(snap.get("cache_hits_total") or 0.0)
            misses += float(snap.get("cache_misses_total") or 0.0)
        if hits + misses > 0:
            out["cache_miss_rate"] = misses / (hits + misses)
        return out

    def _shed_pressure(self) -> bool:
        """True while the tenant plane's total shed count is RISING — the
        flood-in-progress marker. Demand already excludes shed traffic;
        this only gates the explicit `flood hold` bookkeeping."""
        if self.tenancy_plane is None:
            return False
        total = 0.0
        for row in self.tenancy_plane.metrics_view().values():
            total += float(row.get("sheds_rate_total", 0.0))
            total += float(row.get("sheds_inflight_total", 0.0))
        last = self._last_sheds
        self._last_sheds = total
        return last is not None and total > last

    async def step(self) -> list[ScaleDecision]:
        """One decision round over every pool. Returns the decisions
        APPLIED this round (holds are recorded in flood counters, not
        returned)."""
        now = self._clock()
        dt = max(now - self._last_step, 1e-6)
        self._last_step = now
        flood = self._shed_pressure()
        applied: list[ScaleDecision] = []
        for name, pool in self.pools.items():
            fp = self.controller.pools[name]
            if fp.spec.spawner is None:
                continue  # static pools are someone else's capacity plan
            st = self._pool_state[name]
            admits = st["admits_total"] - st["last_admits"]
            st["last_admits"] = st["admits_total"]
            demand_rps = admits / dt
            ready = fp.member_states(now)["ready"]
            target = fp.spec.target_size
            sig = self._aggregator_signals(fp)
            inflight = st["inflight"]
            per_ready = max(ready, 1)
            overload = (
                sig["queue_depth"] / per_ready >= self.queue_high
                or sig["burn_fast"] > self.burn_high
                or inflight / per_ready >= self.inflight_high
                or (
                    sig["cache_miss_rate"] >= self.miss_high
                    and sig["queue_depth"] / per_ready >= self.queue_high / 2
                )
            )
            if (target == 0 or fp.scaled_to_zero) and admits > 0:
                # normally route() already woke the pool; this catches
                # demand observed between wake and a racing scale-down
                self._maybe_wake(pool)
                applied.append(st["last_decision"])
                continue
            if overload and target < pool.max_size and ready > 0:
                st["idle_streak"] = 0
                if flood and admits == 0:
                    # shed pressure with no in-quota demand: the overload
                    # signal is the flood knocking, not real work — hold
                    self.flood_suppressions_total += 1
                    self._record(
                        pool, target, target,
                        "hold: sheds rising, no in-quota demand",
                    )
                    continue
                reasons = []
                if sig["queue_depth"] / per_ready >= self.queue_high:
                    reasons.append(f"queue {sig['queue_depth']:.0f}")
                if sig["burn_fast"] > self.burn_high:
                    reasons.append(f"burn {sig['burn_fast']:.2f}")
                if inflight / per_ready >= self.inflight_high:
                    reasons.append(f"inflight {inflight}")
                if sig["cache_miss_rate"] >= self.miss_high:
                    reasons.append(f"miss {sig['cache_miss_rate']:.2f}")
                self._grow(
                    pool, target + 1, "up: " + ", ".join(reasons or ["overload"])
                )
                self.scale_ups_total += 1
                applied.append(st["last_decision"])
                continue
            if flood and admits == 0 and st["inflight"] == 0 and target > 0:
                # flood in progress, this pool has zero in-quota demand:
                # record the hold that proves we never scale INTO a flood
                self.flood_suppressions_total += 1
            floor = max(
                pool.min_size, 1 if fp.scale_to_zero_s > 0 else pool.min_size
            )
            if admits == 0 and inflight == 0 and target > floor:
                st["idle_streak"] += 1
                if st["idle_streak"] >= self.down_steps:
                    st["idle_streak"] = 0
                    await self._shrink(
                        pool, target - 1,
                        f"down: idle {self.down_steps} rounds",
                    )
                    self.scale_downs_total += 1
                    applied.append(st["last_decision"])
            else:
                if demand_rps > 0 or inflight > 0:
                    st["idle_streak"] = 0
        return applied

    # ---- lifecycle ----

    async def start(self) -> None:
        if self._task is None:
            self._task = asyncio.create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    async def _run(self) -> None:
        while True:
            try:
                await self.step()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("autoscale step failed")
            await asyncio.sleep(self.tick_s)

    # ---- observability ----

    def snapshot(self) -> dict:
        """The `autoscale` /metrics block: per-pool desired/ready, shape,
        last decision + reason + age, restore timing; loop totals."""
        now = self._clock()
        pools = {}
        for name, pool in self.pools.items():
            fp = self.controller.pools[name]
            st = self._pool_state[name]
            dec = st["last_decision"]
            pools[name] = {
                "model": pool.model,
                "open_vocab": pool.open_vocab,
                "tp": pool.tp,
                "dp": pool.dp,
                "desired": fp.spec.target_size,
                "size": len(fp.members),
                "ready": fp.member_states(now)["ready"],
                "max_size": pool.max_size,
                "scaled_to_zero": fp.scaled_to_zero,
                "restoring": fp.restoring,
                "time_to_ready_s": fp.time_to_ready_s,
                "restores_total": fp.restores_total,
                "admits_total": st["admits_total"],
                "inflight": st["inflight"],
                "ok_total": st["ok_total"],
                "fail_total": st["fail_total"],
                "last_decision": (
                    None if dec is None else {
                        "desired": dec.desired,
                        "current": dec.current,
                        "reason": dec.reason,
                        "age_s": round(max(now - dec.at, 0.0), 3),
                    }
                ),
            }
        return {
            "pools": pools,
            "default_pool": self._default.name,
            "open_vocab_pool": (
                self._open_vocab.name if self._open_vocab else None
            ),
            "decisions_total": self.decisions_total,
            "scale_ups_total": self.scale_ups_total,
            "scale_downs_total": self.scale_downs_total,
            "wakes_total": self.wakes_total,
            "flood_suppressions_total": self.flood_suppressions_total,
            "routing_rejections_total": self.routing_rejections_total,
        }

    def chips_desired(self) -> int:
        """Chip budget implied by current targets (tp×dp per member) — the
        capacity a statically sized fleet is compared with."""
        return sum(
            self.controller.pools[name].spec.target_size
            * pool.chips_per_member
            for name, pool in self.pools.items()
        )
