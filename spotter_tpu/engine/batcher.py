"""Async micro-batcher: many concurrent requests -> few big device batches.

The reference fans out per image with asyncio.gather and runs batch-size-1
forwards (serve.py:98-109, 180-181) — fine on CPU, starves a TPU. Here each
request submits images to a shared queue; a pump task drains up to max_batch
images or waits at most max_delay_ms, then runs the engine in a worker thread
(device work releases the GIL). Up to `max_in_flight` batches run
concurrently (VERDICT r2 next #2): while batch N computes on device, batch
N+1 stages on host — jit dispatch is async and thread-safe, so the two
worker threads interleave host staging with device compute instead of
serializing. Per-image error containment is preserved: a failed batch
rejects only its own futures.

Request-lifecycle hardening (ISSUE 1): the queue is bounded
(`SPOTTER_TPU_QUEUE_DEPTH`) and a full queue sheds with `QueueFullError`
instead of buffering unboundedly; `submit()` takes an optional `Deadline`
and raises `DeadlineExceededError` instead of waiting past it; a watchdog
(`SPOTTER_TPU_BATCH_TIMEOUT_MS`) fails a hung `engine.detect` call's futures
and releases its in-flight slot instead of deadlocking the pump; a
`CircuitBreaker` trips after consecutive batch failures and sheds at
admission while open; `drain()` stops admitting, flushes the queue, and
waits for in-flight batches (the k8s preStop hook).

Engine fault domain (ISSUE 4): a failed batch is no longer all-or-nothing.
Plain errors trigger a bisect-retry (split in half, retry the halves,
recurse, bounded by `SPOTTER_TPU_POISON_MAX_SPLITS`) so only a genuinely
poisonous item's future fails — with `PoisonImageError` — while co-batched
innocents succeed; an isolated poison does NOT count as an engine failure
for the breaker (a batch where every item fails still does). A
`FatalEngineError` from the engine (device lost) triggers the degraded-dp
path: rebuild the engine at the largest viable width over the surviving
shards (lifecycle re-enters `warming` during the rebuild) or, when nothing
is left to degrade to, a controlled exit with `FATAL_ENGINE_EXIT_CODE` so
the supervisor warm-restarts through the persistent compile cache.

Caching tier (ISSUE 5): `submit(..., key=<content hash>)` coalesces at
admission — a second submit with the same key while the first is still in
flight attaches a waiter future to the existing entry instead of enqueuing
a duplicate image, so N byte-identical images in the batcher cost ONE
engine slot and the result fans out to every waiter. Each waiter owns its
OWN future: one waiter's expired deadline cancels only that waiter, never
the shared entry, and a shared `PoisonImageError` reaches every waiter
exactly once. On completion the optional `result_cache` is filled (success
-> positive entry; poison -> negative entry; admission sheds and
fatal/transient engine errors are NEVER cached). Unkeyed submits take the
exact pre-cache path, so `SPOTTER_TPU_CACHE_MAX_MB=0` keeps serving
bit-identical to a cache-less build.

Unified scheduler (ISSUE 9): the pump no longer owns its dispatch policy —
a `Scheduler` (engine/scheduler.py) does. Queue entries are `QueueItem`
dataclasses (no more positional tuple), dp superbatches are just a bigger
fill target, keyed coalescing packs to zero items, and the policy is
swappable: FIFO (default, bit-identical to the pre-ISSUE-9 batcher) or
ragged (`SPOTTER_TPU_RAGGED=1`) — deadline-slack-ordered admission (slo
fills the next dispatch first, bulk backfills) and mixed-size images
packed into one padded superbatch whose canvas minimizes padded-pixel
waste; the engine stages it over the PR 3 uint8 + `(B, 2)` valid-dims
substrate. `padding_waste_pct` and `slack_at_dispatch_ms` land in
/metrics either way so the FIFO baseline is measurable.

Overload control (ISSUE 8, opt-in via `SPOTTER_TPU_ADMIT_TARGET_MS`): the
static queue-depth shed is replaced by an AIMD adaptive concurrency
limiter driven by measured queue_wait p90 (the queue becomes unbounded;
the limiter is the bound). Admission is class-aware — `submit(..., cls=
"bulk")` entries shed strictly before slo: a bulk arrival over the limit
sheds 429 immediately, while an slo arrival first revokes the NEWEST
queued bulk entry (its future fails with `QueueFullError`; the pump skips
done futures) and takes its slot. A `BrownoutController` rides along:
under sustained saturation it caps the dispatch bucket one rung down
(rung 2) and shed ALL bulk with 503 (rung 4); the detector layer consumes
the stale-serve (rung 1) and threshold (rung 3) effects. With the knob
unset both are None and admission is bit-identical to the static build
(test-asserted).
"""

import asyncio
import inspect
import logging
import time
from typing import Callable, Optional

from PIL import Image

from spotter_tpu import obs
from spotter_tpu.engine.engine import InferenceEngine
from spotter_tpu.engine.errors import (
    DEFAULT_POISON_MAX_SPLITS,
    FATAL_ENGINE_EXIT_CODE,
    POISON_MAX_SPLITS_ENV,
    FatalEngineError,
    PoisonImageError,
    TransientEngineError,
)
from spotter_tpu.engine.scheduler import PackPlan, QueueItem, Scheduler
from spotter_tpu.serving.overload import (
    BULK,
    SLO,
    AdaptiveLimiter,
    AdmitLimitError,
    BrownoutController,
    BrownoutShedError,
    build_overload_control,
)
from spotter_tpu.serving.resilience import (
    BATCH_TIMEOUT_ENV,
    DEFAULT_BATCH_TIMEOUT_MS,
    DEFAULT_DRAIN_TIMEOUT_S,
    DEFAULT_QUEUE_DEPTH,
    DRAIN_TIMEOUT_ENV,
    QUEUE_DEPTH_ENV,
    CircuitBreaker,
    CircuitOpenError,
    Deadline,
    DrainingError,
    QueueFullError,
    _env_float,
    _env_int,
    jittered_retry_after,
)
from spotter_tpu.testing import faults

logger = logging.getLogger(__name__)

# default for MicroBatcher(limiter=/brownout=...): build from the env knobs
# (None when SPOTTER_TPU_ADMIT_TARGET_MS is unset/0). Pass None to force
# the overload-control tier off regardless of the env.
_FROM_ENV = object()


class BatchTimeoutError(RuntimeError):
    """The watchdog gave up on a hung engine call; the batch's futures fail
    with this instead of waiting forever (the orphaned worker thread keeps
    running — Python can't kill it — but its slot is released and its result
    discarded)."""


class MicroBatcher:
    def __init__(
        self,
        engine: InferenceEngine,
        max_batch: Optional[int] = None,
        max_delay_ms: float = 5.0,
        max_in_flight: int = 2,
        max_queue: Optional[int] = None,
        batch_timeout_ms: Optional[float] = None,
        breaker: Optional[CircuitBreaker] = None,
        poison_max_splits: Optional[int] = None,
        fatal_exit_cb: Optional[Callable[[int], None]] = None,
        result_cache=None,
        limiter: Optional[AdaptiveLimiter] = _FROM_ENV,
        brownout: Optional[BrownoutController] = _FROM_ENV,
        scheduler: Optional[Scheduler] = None,
    ) -> None:
        """`max_queue`/`batch_timeout_ms` default from the env knobs
        (`SPOTTER_TPU_QUEUE_DEPTH`, `SPOTTER_TPU_BATCH_TIMEOUT_MS`);
        `max_queue <= 0` means unbounded, `batch_timeout_ms <= 0` disables
        the watchdog. `poison_max_splits` (default
        `SPOTTER_TPU_POISON_MAX_SPLITS`) bounds the bisect-retry recursion
        depth; `<= 0` disables isolation (a failed batch fails whole, the
        pre-ISSUE-4 behavior). `fatal_exit_cb` is invoked with
        `FATAL_ENGINE_EXIT_CODE` when a fatal device error cannot be
        survived by a degraded rebuild — the serving runtime wires
        `os._exit` here so the supervisor can warm-restart; `None` (library
        use, tests) just leaves the breaker to shed. `result_cache`
        (ISSUE 5, a `caching.ResultCache` or None) is filled from keyed
        submits on completion; keyed coalescing itself works with or
        without it. `scheduler` (ISSUE 9) is the dispatch policy — default
        `Scheduler.from_env(engine)`: FIFO unless `SPOTTER_TPU_RAGGED=1`
        arms slack-ordered ragged packing."""
        self.engine = engine
        self.max_batch = max_batch or engine.batch_buckets[-1]
        # Aggregate bucket sizing (ISSUE 3): under dp-sharded serving the
        # engine ladder is aggregate (dp × per-chip bucket — serving/app.py
        # scales it), so the pump fills all chips' worth of images before
        # dispatching, under the SAME max_delay/deadline/shed semantics as
        # single-chip serving: a sparse queue still dispatches a partial
        # batch after max_delay rather than stalling for the full bucket.
        # The gauge makes the fill target visible next to mean_batch_size.
        engine.metrics.set_aggregate_bucket(self.max_batch)
        self.max_delay_s = max_delay_ms / 1000.0
        self.max_in_flight = max(1, max_in_flight)
        if max_queue is None:
            max_queue = _env_int(QUEUE_DEPTH_ENV, DEFAULT_QUEUE_DEPTH)
        self.max_queue = max_queue
        if batch_timeout_ms is None:
            batch_timeout_ms = _env_float(BATCH_TIMEOUT_ENV, DEFAULT_BATCH_TIMEOUT_MS)
        self.batch_timeout_s = batch_timeout_ms / 1000.0 if batch_timeout_ms > 0 else None
        self.breaker = breaker or CircuitBreaker.from_env(metrics=engine.metrics)
        if poison_max_splits is None:
            poison_max_splits = _env_int(
                POISON_MAX_SPLITS_ENV, DEFAULT_POISON_MAX_SPLITS
            )
        self.poison_max_splits = poison_max_splits
        self.fatal_exit_cb = fatal_exit_cb
        self.result_cache = result_cache
        # Overload control (ISSUE 8): both default from the env —
        # SPOTTER_TPU_ADMIT_TARGET_MS unset/0 leaves them None and every
        # admission below takes the exact static queue-depth path. With the
        # limiter armed, the queue is unbounded: the adaptive limit IS the
        # bound, and the static depth would otherwise second-guess it.
        if limiter is _FROM_ENV or brownout is _FROM_ENV:
            env_limiter, env_brownout = build_overload_control(
                metrics=engine.metrics
            )
            if limiter is _FROM_ENV:
                limiter = env_limiter
            if brownout is _FROM_ENV:
                brownout = env_brownout
        self.limiter = limiter
        self.brownout = brownout
        # Unified scheduler (ISSUE 9): the pump's dispatch policy. The
        # pending buffer lives here (not in the scheduler) so drain()/stop()
        # account for it; under FIFO it never holds anything between plans.
        self.scheduler = scheduler or Scheduler.from_env(engine)
        self._sched_buf: list[QueueItem] = []
        # Only pass a ragged canvas to engines that accept one: stub and
        # synthetic engines (tests, benches) may keep the plain
        # detect(images) signature, and the scheduler still gives them
        # slack ordering.
        try:
            detect_params = inspect.signature(engine.detect).parameters
            self._engine_takes_canvas = "canvas_hw" in detect_params
            # open-vocab query sets (ISSUE 13): only the real engine's
            # detect() speaks them; stub/synthetic engines keep the plain
            # signature and never receive queried work (the detector layer
            # rejects queries when the engine lacks a text encoder)
            self._engine_takes_qset = "qset" in detect_params
        except (TypeError, ValueError):
            self._engine_takes_canvas = False
            self._engine_takes_qset = False
        # key -> (primary future, waiter futures): one queue entry per key,
        # its result fanned to every waiter when the primary settles
        self._keyed: dict[str, tuple[asyncio.Future, list[asyncio.Future]]] = {}
        self._lifecycle_tracker = None
        # verified readiness hook (ISSUE 17): when the serving runtime wires
        # an integrity recheck, a degraded rebuild must re-prove its outputs
        # (attest + golden probe) before re-entering READY. The callback
        # owns the exit-86 path on failure.
        self.integrity_recheck_cb: Optional[Callable[[str], bool]] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._fatal_fired = False
        self._fatal_traces: list = []
        self._queue: asyncio.Queue = asyncio.Queue(
            maxsize=0 if self.limiter is not None else max(0, max_queue)
        )
        self._pump_task: Optional[asyncio.Task] = None
        self._control_task: Optional[asyncio.Task] = None
        self._in_flight: set[asyncio.Task] = set()
        self._slots: Optional[asyncio.Semaphore] = None
        self._rebuild_lock: Optional[asyncio.Lock] = None
        self._closed = False
        self._draining = False
        # True while the pump holds a dequeued-but-undispatched batch in
        # hand — drain() must not treat "queue empty, nothing in flight" as
        # done while a batch sits here, or stop() would fail its futures
        self._pump_busy = False

    @property
    def draining(self) -> bool:
        return self._draining or self._closed

    def in_flight(self, key: str) -> bool:
        """True while a keyed entry for `key` is in flight — a submit with
        this key right now would coalesce onto it instead of enqueuing new
        engine work (the detector's X-Cache: coalesced observation,
        ISSUE 11)."""
        entry = self._keyed.get(key)
        return entry is not None and not entry[0].done()

    def attach_lifecycle(self, tracker) -> None:
        """Give the batcher the replica's StartupTracker so a degraded
        rebuild can re-enter `warming` (and return to `ready`) on /startupz."""
        self._lifecycle_tracker = tracker

    async def start(self) -> None:
        """Idempotent; an explicit start() after stop()/drain() re-opens the
        batcher (submit() never restarts a stopped batcher on its own)."""
        if self._pump_task is None:
            self._closed = False
            self._draining = False
            self._loop = asyncio.get_running_loop()
            self.engine.metrics.set_draining(False)
            self._slots = asyncio.Semaphore(self.max_in_flight)
            self._rebuild_lock = asyncio.Lock()
            self._pump_task = asyncio.create_task(self._pump())
            if (
                self._control_task is None
                and (self.limiter is not None or self.brownout is not None)
            ):
                # idle-path control ticks: the AIMD limit must recover and
                # the brownout ladder must disarm even with zero traffic
                # flowing after a storm
                self._control_task = asyncio.create_task(self._control_loop())

    async def _control_loop(self) -> None:
        interval = (
            self.limiter.interval_s if self.limiter is not None else 0.25
        )
        while True:
            await asyncio.sleep(interval)
            if self.limiter is not None:
                self.limiter.tick()
            if self.brownout is not None:
                self.brownout.evaluate()

    async def stop(self) -> None:
        self._closed = True
        if self._control_task is not None:
            self._control_task.cancel()
            try:
                await self._control_task
            except asyncio.CancelledError:
                pass
            self._control_task = None
        if self._pump_task is not None:
            self._pump_task.cancel()
            try:
                await self._pump_task
            except asyncio.CancelledError:
                pass
            self._pump_task = None
        # let dispatched batches finish (their futures get real results) …
        if self._in_flight:
            await asyncio.gather(*self._in_flight, return_exceptions=True)
        # … then fail anything still queued (or held in the scheduler's
        # pending buffer) so no submit() caller waits forever
        while not self._queue.empty():
            fut = self._queue.get_nowait().fut
            if not fut.done():
                fut.set_exception(DrainingError("MicroBatcher stopped"))
        for item in self._sched_buf:
            if not item.fut.done():
                item.fut.set_exception(DrainingError("MicroBatcher stopped"))
        self._sched_buf.clear()

    async def drain(self, timeout_s: Optional[float] = None) -> dict:
        """Graceful shutdown (k8s preStop): stop admitting, let the pump flush
        the queue, wait for in-flight batches, then stop. Returns a summary;
        on timeout any leftovers are failed by stop() rather than stranded."""
        if timeout_s is None:
            timeout_s = _env_float(DRAIN_TIMEOUT_ENV, DEFAULT_DRAIN_TIMEOUT_S)
        t0 = time.monotonic()
        self._draining = True
        self.engine.metrics.set_draining(True)
        deadline = t0 + timeout_s
        while (
            not self._queue.empty() or self._pump_busy or self._in_flight
        ) and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        leftover = self._queue.qsize() + sum(
            1 for it in self._sched_buf if not it.fut.done()
        )
        # remaining in-flight batches at the wait's end (ISSUE 15): 0 on a
        # clean drain; on timeout the count a caller — the rollout
        # controller, a k8s preStop hook — needs to decide whether to wait
        # again or accept the loss, instead of sleeping a blind grace period
        in_flight = sum(1 for t in self._in_flight if not t.done())
        if self._pump_busy:
            in_flight += 1
        await self.stop()
        return {
            "status": "drained" if leftover == 0 and in_flight == 0
            else "drain_timeout",
            "queued_failed": leftover,
            "in_flight": in_flight,
            "waited_ms": (time.monotonic() - t0) * 1000.0,
        }

    def attach_tenancy(self, plane) -> None:
        """Wire the tenant isolation plane (ISSUE 19) into every shared-
        capacity arbiter the batcher owns: the scheduler's within-class DRR
        ordering, the limiter's top-occupancy-first revocation, and the
        brownout ladder's per-tenant rung 4. `None` (tenancy unconfigured)
        leaves all three exactly as built — bit-identical serving."""
        if plane is None:
            return
        self.scheduler.tenancy = plane
        if self.limiter is not None:
            self.limiter.tenancy = plane
        if self.brownout is not None:
            self.brownout.tenancy = plane

    async def submit(
        self,
        image: Image.Image,
        deadline: Optional[Deadline] = None,
        key: Optional[str] = None,
        cls: Optional[str] = None,
        qset=None,
        tenant: Optional[str] = None,
    ) -> list[dict]:
        """One image in, its detections out (awaits the batched device call).

        Raises `DrainingError` / `CircuitOpenError` / `QueueFullError` at
        admission and `DeadlineExceededError` when `deadline` expires before
        the result lands; every caller gets an answer in bounded time.

        `key` (the caching tier's content hash) coalesces: while a keyed
        entry is in flight, a second submit with the same key attaches a
        waiter future instead of enqueuing a duplicate image — no breaker /
        queue-capacity check, because it adds ZERO engine work. Every keyed
        caller (the first included) awaits a private waiter future, so a
        deadline expiry cancels only that caller's wait, never the shared
        entry. `key=None` (cache tier disabled) takes the exact pre-cache
        path.

        `cls` ("slo" | "bulk", ISSUE 8; None means slo — the conservative
        PR 6 default) matters only with the overload-control tier armed:
        over the adaptive limit, bulk sheds strictly before slo (a queued
        bulk entry may be revoked — its future fails `AdmitLimitError` —
        to make room for an slo arrival), and the deepest brownout rung
        sheds all bulk with `BrownoutShedError` (503).

        `qset` (open vocabulary, ISSUE 13): the request's resolved
        `QuerySet`. Its key is the item's batch-compatibility group — the
        scheduler never mixes two query sets into one dispatch, and the
        engine detects the pack against that vocabulary. None keeps the
        closed-set path bit-identical.

        `tenant` (ISSUE 19): the resolved tenant id, stamped into the
        `QueueItem` so the scheduler's DRR ordering and the limiter's
        top-occupancy revocation can scope by it. None (tenancy
        unconfigured) keeps every path bit-identical.
        """
        metrics = self.engine.metrics
        if self.draining:
            metrics.record_shed()
            raise DrainingError("MicroBatcher is draining or stopped")
        await self.start()
        loop = asyncio.get_running_loop()
        if key is not None:
            entry = self._keyed.get(key)
            if entry is not None and not entry[0].done():
                metrics.record_coalesced_submit()
                waiter: asyncio.Future = loop.create_future()
                entry[1].append(waiter)
                return await self._await_result(waiter, deadline, metrics)
        if not self.breaker.allow():
            metrics.record_shed()
            raise CircuitOpenError(
                "circuit breaker open (engine failing)",
                retry_after_s=self.breaker.retry_after_s(),
            )
        if deadline is not None and deadline.expired():
            metrics.record_deadline_exceeded()
            raise deadline.exceeded("queue admission")
        cls = BULK if cls == BULK else SLO
        adm = self._admit(cls, metrics, tenant)
        fut: asyncio.Future = loop.create_future()
        if adm is not None:
            # release the slot whenever the result lands, however it lands
            # (success, poison, deadline-cancel, drain); idempotent with the
            # limiter's own revocation release
            fut.add_done_callback(lambda f, a=adm: a.release())
        if key is not None:
            waiters: list[asyncio.Future] = []
            self._keyed[key] = (fut, waiters)
            # the callback captures ITS OWN waiters list: between the primary
            # settling and this callback running, a fresh submit for the same
            # key may have replaced the dict entry (it sees fut.done() and
            # starts a new flight) — re-reading the dict there would strand
            # these waiters unresolved forever
            fut.add_done_callback(
                lambda f, k=key, ws=waiters: self._settle_keyed(k, f, ws)
            )
        try:
            # keyed entries carry no deadline on the item: the shared
            # primary must outlive any single waiter's budget. The ambient
            # request trace (ISSUE 7) rides along so the pump can attribute
            # this item's queue wait and the engine its stage windows; with
            # the flight recorder off it is None and costs nothing.
            self._queue.put_nowait(QueueItem(
                image=image,
                fut=fut,
                deadline=deadline if key is None else None,
                trace=obs.current_trace(),
                t_submit=time.monotonic(),
                adm=adm,
                cls=cls,
                key=key,
                tenant=tenant,
                qset=qset,
            ))
        except asyncio.QueueFull:
            if key is not None and self._keyed.get(key, (None,))[0] is fut:
                del self._keyed[key]
            if adm is not None:  # unreachable (limiter queue is unbounded)
                adm.release()
            metrics.record_shed()
            raise QueueFullError(
                f"batch queue full ({self.max_queue} deep)",
                retry_after_s=jittered_retry_after(
                    max(self.max_delay_s * 2.0, 0.05)
                ),
            ) from None
        if adm is not None and cls == BULK:
            # newest-bulk-first revocation target: an over-limit slo arrival
            # fails this future instead of being shed itself. Once the pump
            # dispatches the item the admission leaves the revocation stack
            # (failing it then would waste the engine work already spent).
            adm.attach_revoke(
                lambda f=fut: (
                    None if f.done() else f.set_exception(
                        AdmitLimitError(
                            "bulk entry revoked for an slo admission",
                            retry_after_s=jittered_retry_after(
                                max(self.max_delay_s * 2.0, 0.05)
                            ),
                        )
                    )
                )
            )
        if key is None:
            return await self._await_result(fut, deadline, metrics)
        waiter = loop.create_future()
        waiters.append(waiter)
        return await self._await_result(waiter, deadline, metrics)

    def _admit(self, cls: str, metrics, tenant: Optional[str] = None):
        """Overload-control admission (None when the tier is off — the
        static queue-depth put_nowait below stays the only gate, exactly
        the pre-ISSUE-8 semantics). `tenant` (ISSUE 19) scopes brownout
        rung 4 (over-share tenants brown out, in-quota tenants keep full
        service) and tags the limiter admission for top-occupancy-first
        revocation; None keeps both class-wide."""
        if self.brownout is not None:
            self.brownout.evaluate()
            if cls == BULK and self.brownout.shed_bulk(tenant):
                metrics.record_shed()
                metrics.record_admit_shed(BULK)
                raise BrownoutShedError(
                    "brownout: bulk traffic shed (rung "
                    f"{self.brownout.rung})",
                    retry_after_s=jittered_retry_after(
                        self.brownout.disarm_s
                    ),
                )
        if self.limiter is None:
            return None
        adm = self.limiter.try_admit(cls, tenant)
        if adm is None:
            metrics.record_shed()
            raise AdmitLimitError(
                f"adaptive admission limit hit ({self.limiter.limit} "
                f"in flight)",
                retry_after_s=jittered_retry_after(
                    max(self.max_delay_s * 2.0, 0.05)
                ),
            )
        return adm

    async def _await_result(
        self, fut: asyncio.Future, deadline: Optional[Deadline], metrics
    ) -> list[dict]:
        if deadline is None:
            return await fut
        try:
            # shield: wait_for must not cancel the pump's handle on the
            # future; on expiry we cancel it ourselves so the pump (which
            # checks fut.done()) skips the dead entry
            return await asyncio.wait_for(
                asyncio.shield(fut), max(deadline.remaining(), 0.0)
            )
        except asyncio.TimeoutError:
            if fut.done() and not fut.cancelled():
                # result landed on the expiry tick: consume the exception so
                # nothing logs "never retrieved"; the deadline still rules
                fut.exception()
            else:
                fut.cancel()
            metrics.record_deadline_exceeded()
            raise deadline.exceeded("batched detect") from None

    def _settle_keyed(
        self, key: str, primary: asyncio.Future, waiters: list[asyncio.Future]
    ) -> None:
        """Primary-future done callback: retire the keyed entry (only if it
        is still ours — a successor flight may already own the key), fill
        the result cache (success -> positive, poison -> negative; sheds and
        engine faults are never cached), and fan the outcome to every
        waiter. No waiter can attach after the primary is done (submit
        checks `done()` before attaching), so `waiters` is complete here."""
        entry = self._keyed.get(key)
        if entry is not None and entry[0] is primary:
            del self._keyed[key]
        cache = self.result_cache
        if primary.cancelled():  # defensive: nothing cancels keyed primaries
            for w in waiters:
                if not w.done():
                    w.cancel()
            return
        exc = primary.exception()
        if exc is None:
            result = primary.result()
            if cache is not None:
                cache.put(key, result)
            for w in waiters:
                if not w.done():
                    w.set_result([dict(d) for d in result])
        else:
            if cache is not None and isinstance(exc, PoisonImageError):
                cache.put_negative(key, exc)
            for w in waiters:
                if not w.done():
                    w.set_exception(exc)

    async def _pump(self) -> None:
        buf = self._sched_buf
        while True:
            self._pump_busy = bool(buf)
            if not buf:
                first = await self._queue.get()
                self._pump_busy = True
                if first.fut.done():  # deadline-cancelled while queued
                    continue
                buf.append(first)
            try:
                target = self._dispatch_bucket()
                gather = self.scheduler.gather_target(target)
                # top up within one bounded delay window (leftover items
                # from a prior ragged plan re-enter it — the window, not
                # arrival order, bounds their extra wait, same as FIFO's
                # per-batch delay semantics)
                deadline = time.monotonic() + self.max_delay_s
                while len(buf) < gather:
                    if len(buf) >= target:
                        # past the fill target, the ragged lookahead only
                        # takes what is already queued — never waits (the
                        # window exists to fill the bucket, not the choice
                        # pool)
                        try:
                            item = self._queue.get_nowait()
                        except asyncio.QueueEmpty:
                            break
                    else:
                        timeout = deadline - time.monotonic()
                        if timeout <= 0:
                            break
                        try:
                            item = await asyncio.wait_for(
                                self._queue.get(), timeout
                            )
                        except asyncio.TimeoutError:
                            break
                    if not item.fut.done():
                        buf.append(item)
                # deadline-cancelled (or revoked) while pending: dead weight
                buf[:] = [it for it in buf if not it.fut.done()]
                if not buf:
                    continue
                await self._slots.acquire()
                if not self.scheduler.fifo:
                    # slack ordering's critical window: everything that
                    # queued while we waited for a slot joins the plan, so
                    # an slo arrival beats older bulk into THIS dispatch
                    # (FIFO keeps the pre-ISSUE-9 fixed-batch semantics)
                    while len(buf) < gather:
                        try:
                            item = self._queue.get_nowait()
                        except asyncio.QueueEmpty:
                            break
                        if not item.fut.done():
                            buf.append(item)
                plan = self.scheduler.plan(
                    buf, target,
                    buckets=getattr(self.engine, "batch_buckets", None),
                )
            except asyncio.CancelledError:
                # stop() cancelled us while we hold drained items that no
                # in-flight task owns yet — fail their futures or their
                # submit() callers would wait forever
                for item in buf:
                    if not item.fut.done():
                        item.fut.set_exception(
                            DrainingError("MicroBatcher stopped")
                        )
                buf.clear()
                raise
            task = asyncio.create_task(self._run_batch(plan))
            self._in_flight.add(task)
            task.add_done_callback(self._in_flight.discard)

    def _dispatch_bucket(self) -> int:
        """The pump's fill target: `max_batch`, capped one rung down the
        engine's bucket ladder while the brownout bucket-cap rung is active
        (smaller padded dispatches -> fewer wasted pad FLOPs and a shorter
        per-batch device window under load — the PR 4 bucket-downgrade
        machinery driven by saturation instead of OOM)."""
        if self.brownout is None or not self.brownout.bucket_cap_active():
            return self.max_batch
        below = [
            b for b in self.engine.batch_buckets if b < self.max_batch
        ]
        return below[-1] if below else self.max_batch

    def _detect_outcomes(
        self,
        images: list[Image.Image],
        splits_left: int,
        canvas_hw: Optional[tuple[int, int]] = None,
        qset=None,
    ) -> list:
        """Worker-thread engine call with poison bisect-retry (ISSUE 4).

        Returns one outcome per image: a detections list, or the exception
        to set on that image's future. A failed multi-image batch is split
        in half and each half retried (recursing up to `splits_left` deep),
        so a deterministic per-input failure converges to exactly one
        `PoisonImageError` while every innocent neighbor gets its result.
        Typed engine errors (transient after the engine's own retry, fatal)
        are never bisected — they are batch-independent and propagate.
        `canvas_hw` (ragged, ISSUE 9) rides through the recursion so bisect
        halves stay in the pack's canvas (same numerics, no recompiles
        beyond the pack's own shape).

        The fault hook runs at every level, exactly where a wedged or
        poisoned device call would fail on a retry too.
        """
        try:
            faults.on_engine_batch(images)
            kwargs = {}
            if canvas_hw is not None:
                kwargs["canvas_hw"] = canvas_hw
            if qset is not None:
                kwargs["qset"] = qset
            return list(self.engine.detect(images, **kwargs))
        except (FatalEngineError, TransientEngineError):
            raise
        except Exception as exc:
            if len(images) == 1:
                err = PoisonImageError(f"image poisoned its batch: {exc!r}")
                err.__cause__ = exc
                return [err]
            if splits_left <= 0:
                # isolation exhausted/disabled: every image in this
                # sub-batch fails with the raw error
                return [exc] * len(images)
            self.engine.metrics.record_batch_retry()
            mid = len(images) // 2
            return self._detect_outcomes(
                images[:mid], splits_left - 1, canvas_hw, qset
            ) + self._detect_outcomes(
                images[mid:], splits_left - 1, canvas_hw, qset
            )

    async def _run_batch(self, plan: PackPlan) -> None:
        try:
            # deadline-cancelled entries waiting for this slot are dead weight
            batch = [item for item in plan.items if not item.fut.done()]
            if not batch:
                return
            images = [item.image for item in batch]
            canvas_hw = plan.canvas_hw if self._engine_takes_canvas else None
            # group isolation (ISSUE 13): the scheduler guarantees one query
            # set per plan, so the pack's first item speaks for all of it
            qset = batch[0].qset if self._engine_takes_qset else None
            # queue-wait attribution (ISSUE 7): each item's submit -> here.
            # slow_stage=queue_wait:<ms> injects before the dispatch stamp
            # so the injected latency lands inside the queue_wait span.
            qw_delay = faults.stage_delay_s(obs.QUEUE_WAIT)
            if qw_delay > 0.0:
                await asyncio.sleep(qw_delay)
            t_dispatch = time.monotonic()
            traces = []
            queue_waits_ms = []
            slack_ms = []
            for item in batch:
                wait_ms = (t_dispatch - item.t_submit) * 1000.0
                queue_waits_ms.append(wait_ms)
                if item.deadline is not None:
                    # the slack-ordering control signal (ISSUE 9): budget
                    # left when the scheduler actually dispatched the item
                    slack_ms.append(item.deadline.remaining() * 1000.0)
                if self.limiter is not None:
                    # the AIMD control signal (ISSUE 8): measured queue wait
                    self.limiter.observe(wait_ms)
                if item.adm is not None:
                    # dispatched work leaves the revocation stack: failing
                    # it now would waste the engine slot it already holds
                    item.adm.make_unrevocable()
                if item.trace is not None:
                    item.trace.add_span(obs.QUEUE_WAIT, item.t_submit, t_dispatch)
                    traces.append(item.trace)
            # queue_wait joins the /metrics stage histograms (the PR 7
            # vocabulary) so the limiter's control signal is observable
            self.engine.metrics.record_stage_samples(
                obs.QUEUE_WAIT, queue_waits_ms
            )
            # and the span table (`host_spans`) and the timeline, an image
            # each: a wait, measured from the items' own stamps, so no
            # `obs.span` object and no annotation
            for item in batch:
                obs.record_span(
                    "batcher.queue_wait", t_dispatch - item.t_submit,
                    start=item.t_submit,
                )
            self.engine.metrics.record_pack(
                padding_waste_pct=plan.padding_waste_pct,
                slack_ms=slack_ms,
                ragged=canvas_hw is not None,
            )
            # the engine worker thread inherits this via asyncio.to_thread's
            # context copy and fans its stage windows out to these traces
            obs.set_batch_traces(traces)
            try:
                detect = asyncio.to_thread(
                    self._detect_outcomes, images, self.poison_max_splits,
                    canvas_hw, qset,
                )
                if self.batch_timeout_s is not None:
                    outcomes = await asyncio.wait_for(detect, self.batch_timeout_s)
                else:
                    outcomes = await detect
            except asyncio.TimeoutError:
                # watchdog: the engine call is wedged — fail this batch and
                # release the slot; the breaker decides whether to keep
                # admitting (the orphaned thread's eventual result is dropped)
                self.engine.metrics.record_batch_timeout(len(batch))
                self.breaker.record_failure()
                exc = BatchTimeoutError(
                    f"engine batch of {len(batch)} timed out after "
                    f"{self.batch_timeout_s:.1f} s (watchdog)"
                )
                for item in batch:
                    if not item.fut.done():
                        item.fut.set_exception(exc)
                return
            except FatalEngineError as exc:
                await self._handle_fatal(batch, exc)
                return
            except Exception as exc:  # transient-after-retry or unexpected:
                # contain failure to this batch only
                self.engine.metrics.record_error(len(batch))
                self.breaker.record_failure()
                for item in batch:
                    if not item.fut.done():
                        item.fut.set_exception(exc)
                return
            self._settle_outcomes(batch, outcomes)
        finally:
            self._slots.release()

    def _settle_outcomes(self, batch, outcomes: list) -> None:
        """Per-image results/errors plus the breaker accounting contract:
        an isolated poison (some co-batched items succeeded) is NOT an
        engine failure; a batch where nothing succeeded still is."""
        failed = [o for o in outcomes if isinstance(o, BaseException)]
        all_failed = failed and len(failed) == len(outcomes)
        if all_failed:
            self.breaker.record_failure()
            self.engine.metrics.record_error(len(failed))
        else:
            self.breaker.record_success()
            if failed:
                poisons = sum(1 for o in failed if isinstance(o, PoisonImageError))
                self.engine.metrics.record_poison_isolated(poisons)
                self.engine.metrics.record_error(len(failed))
        for item, out in zip(batch, outcomes):
            f, trace = item.fut, item.trace
            if isinstance(out, BaseException) and trace is not None:
                # pin the trace even when the future is already settled (a
                # deadline-expired waiter): the flight recorder's error set
                # is where a poison post-mortem starts
                trace.set_error(type(out).__name__, str(out))
            if f.done():
                continue
            if isinstance(out, BaseException):
                # when the whole batch failed the "poison" label is wrong —
                # nothing was isolated — so surface the underlying error
                if (
                    all_failed
                    and isinstance(out, PoisonImageError)
                    and out.__cause__ is not None
                ):
                    f.set_exception(out.__cause__)
                else:
                    f.set_exception(out)
            else:
                f.set_result(out)

    async def _handle_fatal(self, batch, exc: FatalEngineError) -> None:
        """A device died mid-batch: fail this batch's futures (the replica
        pool replays them on a peer), then either rebuild the engine at a
        lower dp in place or hand the process to the supervisor."""
        self.engine.metrics.record_fatal_engine_error()
        self.engine.metrics.record_error(len(batch))
        self.breaker.record_failure()
        fatal_traces = []
        for item in batch:
            if item.trace is not None:
                item.trace.set_error("fatal", str(exc))
                fatal_traces.append(item.trace)
            if not item.fut.done():
                item.fut.set_exception(exc)
        self._fatal_traces = fatal_traces
        gen = getattr(self.engine, "generation", None)
        if getattr(self.engine, "can_degrade", lambda: False)():
            if await self._rebuild_degraded(gen):
                return
        self._fatal_exit(exc)

    async def _rebuild_degraded(self, gen_at_failure) -> bool:
        """Single-flight degraded rebuild: probe the shards, rebuild the
        engine at the largest viable dp, rescale the batcher's fill target.
        Concurrent fatal batches queue on the lock and observe the bumped
        generation instead of rebuilding (or exiting) again."""
        from spotter_tpu.serving import lifecycle

        async with self._rebuild_lock:
            eng = self.engine
            if gen_at_failure is not None and eng.generation != gen_at_failure:
                return True  # a racing batch already rebuilt past this failure
            tracker = self._lifecycle_tracker
            if tracker is not None:
                tracker.mark(lifecycle.WARMING)
            old_dp = eng.dp
            try:
                alive = await asyncio.to_thread(eng.probe_shards)
                new_dp = await asyncio.to_thread(eng.rebuild_degraded, alive)
            except Exception:
                logger.exception(
                    "degraded rebuild failed (dp=%d); falling through to "
                    "fatal exit", old_dp,
                )
                return False
            self.max_batch = eng.batch_buckets[-1]
            eng.metrics.set_aggregate_bucket(self.max_batch)
            # verified readiness (ISSUE 17): a rebuilt engine is a restore
            # path, and restore paths are SDC ingress — re-prove attest +
            # golden probe before re-entering READY. The callback owns the
            # exit-86 path on failure, so a False return must NOT cascade
            # into the fatal(85) exit underneath this rebuild.
            recheck = self.integrity_recheck_cb
            if recheck is not None:
                if tracker is not None:
                    tracker.mark(lifecycle.VERIFYING)
                if not await asyncio.to_thread(recheck, "degraded-rebuild"):
                    return True
            if tracker is not None:
                tracker.mark(lifecycle.READY)
            logger.warning(
                "engine rebuilt degraded dp=%d -> dp=%d (aggregate bucket %d)",
                old_dp, new_dp, self.max_batch,
            )
            return True

    def _fatal_exit(self, exc: FatalEngineError) -> None:
        """Controlled exit on an unsurvivable device loss: distinct code so
        the supervisor warm-restarts immediately (compile cache makes it
        cheap) instead of applying crash backoff. Without a callback
        (library/test use) the breaker is left to shed."""
        if self._fatal_fired:
            return
        self._fatal_fired = True
        if self.fatal_exit_cb is not None:
            logger.error(
                "fatal engine error with nothing left to degrade to; exiting "
                "%d for supervisor warm restart: %s", FATAL_ENGINE_EXIT_CODE, exc,
            )
            # flight-recorder post-mortem (ISSUE 7): the offending batch's
            # traces never reach an HTTP handler on this path (os._exit is
            # next), so record them here and dump the recorder to disk —
            # the on-disk artifact is how "which request killed dp=1" gets
            # answered after the warm restart
            for trace in getattr(self, "_fatal_traces", []):
                obs.get_recorder().record(trace)
            obs.dump_for_exit(FATAL_ENGINE_EXIT_CODE)
            self.fatal_exit_cb(FATAL_ENGINE_EXIT_CODE)
