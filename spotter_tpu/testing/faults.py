"""Fault-injection harness for the serving path (ISSUE 1 chaos suite).

Spotlight's spot-instance orientation (PAPER.md) makes "the engine just
died / hung / returned garbage" a first-class scenario, not an edge case.
This module lets tests (and staging deployments) inject those faults at the
two seams where the real failures happen, without monkeypatching internals:

- `detector._fetch_image_bytes` calls `await on_fetch(url)` — may raise a
  connection error, sleep (slow CDN), or substitute malformed bytes;
- the MicroBatcher's worker thread calls `on_engine_batch(images)` right
  before `engine.detect` (and again on every bisect-retry sub-batch) — may
  raise (XLA error, preempted device, poison tag) or hang (wedged device
  call; the watchdog's reason to exist);
- the engine's dispatch and shard-probe paths call `on_engine_dispatch` /
  `on_shard_probe` — device-shaped faults (OOM, dead shard) with the
  status markers the failure classifier keys on.

Activation is explicit: either the `inject(...)` context manager (tests) or
`maybe_activate_from_env()` reading `SPOTTER_TPU_FAULTS` (e.g.
`"fetch_error=2,engine_hang_s=30"`) for a chaos-staging server. When no
plan is active every hook is a single global None check — zero cost on the
production path.

Counters (`fetch_error=N`, `engine_error=N`, `malformed_image=N`,
`engine_oom=N`) arm the next N occurrences; `-1` means "every one".
Durations (`fetch_delay_s`, `engine_hang_s`) apply to every call while the
plan is active; a hang waits on `plan.release` so a test can un-wedge the
engine deterministically.

Engine fault domain (ISSUE 4) adds three injections at the engine seams:

- `poison_item=1` enables poison checking: any image tagged with
  `poison_image(img)` raises on every engine call whose batch contains it —
  exactly the "this input deterministically breaks its batch" shape the
  MicroBatcher's bisect-retry isolates;
- `engine_oom=N` arms N dispatch-time failures carrying the
  RESOURCE_EXHAUSTED marker (the engine's bucket-downgrade retry target);
- `shard_dead=<device_id>` makes that device fail the engine's shard
  health probe AND any dispatch placing work on it, with the DATA_LOSS /
  device-halted markers the fatal classifier keys on — the degraded-dp
  rebuild scenario, runnable on CPU virtual devices.

The caching tier (ISSUE 5) adds one more seam: `cache_error=N` arms the
next N `ResultCache` operations (get/put, positive or negative) to raise.
The cache CONTAINS these — a broken cache must degrade to a miss or a
skipped fill, never to a failed request — so the chaos case asserts
requests keep succeeding (at miss-path latency) while the fault is armed.

The fleet tier (ISSUE 6) adds the CORRELATED failure shape —
`preempt_storm=N`: the fleet controller (serving/fleet.py) consumes the
whole value on its next supervision tick via `take_preempt_storm()` and
preempts N currently-ready spot members at once through their handles
(maintenance file -> drain -> exit 83 -> supervisor restart). This is the
normal failure mode of spot TPU capacity — a maintenance wave, not an
independent crash — and the scenario the fleet chaos tests
(`tests/test_fleet.py`) run.

The overload tier (ISSUE 8) adds `overload_spike=N`: the adaptive
admission limiter (serving/overload.py) consumes one per CONTROL TICK via
`take_overload_spike()` and treats that interval's queue-wait p90 as 10x
its target — N ticks of synthetic saturation, enough to cut the AIMD limit
to its floor and (sustained past the arm window) walk the brownout ladder,
all without generating real queue pressure.

The gray-failure tier (ISSUE 14) adds the three injections the chaos
matrix (testing/chaos_matrix.py, run by `tests/test_grayfail.py`) composes:

- `slow_replica=<ms>`: every engine call in THIS process sleeps that long
  first — a replica that still answers /healthz 200 but serves everything
  slow, the gray-failure signature the outlier score exists to catch. Per
  replica by construction: each supervised replica subprocess reads its
  own SPOTTER_TPU_FAULTS (testing/cluster.py), so exactly the marked
  member turns gray.
- `flaky=<pct>`: the replica answers HTTP 500 for that percentage of
  /detect requests, DETERMINISTICALLY (a Bresenham-style credit counter,
  not a random draw) — the intermittent-error half of gray failure, below
  the consecutive-failure threshold hard ejection needs.
- `corrupt_frame=<n>`: the next N binary-frame response bodies get one
  byte flipped after encoding (`corrupt_frame_bytes`), so the edge's CRC
  validator (wire.py v2) must catch each one, count it, and replay on
  another replica with zero client-visible errors.

The control-plane tier (ISSUE 16) adds the two faults the controller
chaos matrix (CONTROLLER_MATRIX) composes:

- `controller_crash=<tick>`: the reconcile controller process
  (serving/reconcile.py) consumes one unit per main-loop tick via
  `take_controller_crash()` and SIGKILLs ITSELF when the countdown hits
  zero — a deterministic kill -9 at a chosen point in the reconcile
  cycle (mid-rollout, mid-storm), with no external kill racing the tick.
- `journal_corrupt=1`: on the armed tick the controller flips one byte
  of its own state journal on disk (`take_journal_corrupt()`), so the
  NEXT controller's load fails the CRC and must take the counted
  rebuild-from-observation path instead of replaying damaged intent.

The output-integrity tier (ISSUE 17) adds the three silent-data-corruption
shapes the INTEGRITY_MATRIX (`tests/test_integrity.py`) composes:

- `sdc=<pct>`: that percentage of this replica's engine answers get a
  deterministic "plausible garbage" perturbation (`corrupt_detections` at
  the engine-output seam: scores and boxes move far outside the
  obs/compare.py tolerances, HTTP stays 200) — the silently-wrong replica
  the router's quorum sampler must hard-quarantine. Bresenham credit like
  `flaky`, scopable with `only_replica`.
- `corrupt_weights=<n>`: consumed whole at replica bring-up
  (`take_corrupt_weights()`), perturbing N loaded "weights" before any
  traffic — the WeightsAttestor must catch the checksum mismatch in the
  `verifying` readiness gate, exit 86, never serve.
- `corrupt_compile_cache=1`: one-shot (`take_corrupt_compile_cache()`),
  consumed at the golden-probe seam — a miscompiled-program restore:
  weights attest CLEAN but the probe's observed answer is perturbed, so
  only the `verifying` probe can catch it (exit 86; the supervisor
  quarantines the suspect compile-cache dir before the cold restart).

The tenant-isolation tier (ISSUE 19) adds the two noisy-neighbor shapes
the TENANT_MATRIX (`tests/test_tenancy.py`) composes. Unlike the other
tiers these don't fire inside the serving path — they parameterize the
drill's LOAD GENERATOR (the abusive client is the fault, not the server):

- `tenant_flood=<tenant>:<xQuota>`: the named tenant sends at xQuota
  times its sustained rate (`tenant_flood_spec()` hands the parsed pair
  to the generator) — the flood the token bucket must absorb while
  honest tenants keep their goodput;
- `tenant_retry_storm=<n>`: the abusive client ignores Retry-After and
  immediately re-sends up to n times per shed (`tenant_retry_storm_n()`)
  — the retry amplification the tenant-scoped jittered hint exists to
  de-synchronize.
"""

import asyncio
import contextlib
import os
import threading
from dataclasses import dataclass, field

FAULTS_ENV = "SPOTTER_TPU_FAULTS"

MALFORMED_BYTES = b"\x00\x01not-an-image\xff"

# Attribute set on a PIL image by `poison_image()`; the engine-batch hook
# raises whenever a tagged image is co-batched (poison_item plans only).
POISON_ATTR = "_spotter_tpu_poison"


@dataclass
class FaultPlan:
    fetch_error: int = 0
    fetch_delay_s: float = 0.0
    malformed_image: int = 0
    engine_error: int = 0
    engine_hang_s: float = 0.0
    # ISSUE 4 engine fault domain: poison tagging on/off, armed device-OOM
    # count, and the device id whose shard is "dead" (-1 = none)
    poison_item: int = 0
    engine_oom: int = 0
    shard_dead: int = -1
    # ISSUE 5 caching tier: armed ResultCache get/put failures (contained
    # by the cache — requests must survive at miss-path cost)
    cache_error: int = 0
    # ISSUE 6 fleet tier: preempt this many ready spot members at once on
    # the controller's next tick (consumed whole, not one-by-one — a storm
    # is one correlated event)
    preempt_storm: int = 0
    # ISSUE 8 overload tier: the AdaptiveLimiter's next N control ticks see
    # a synthetic far-over-target queue-wait p90 — the deterministic way to
    # drive the AIMD cut and arm the brownout ladder without generating
    # real queue pressure (consumed one per control interval)
    overload_spike: int = 0
    # ISSUE 7 observability tier: "<stage>:<ms>" injects that much latency
    # into the named pipeline stage (obs.STAGES vocabulary: fetch, decode,
    # queue_wait, h2d, device, postprocess, route) on EVERY pass through it
    # while the plan is active, so trace/SLO tests can assert attribution
    # deterministically ("the device span grew by exactly the injected
    # amount"). Multiple stages: ";"-separated pairs.
    slow_stage: str = ""
    # ISSUE 14 gray-failure tier: whole-replica slowdown (ms per engine
    # call — the gray signature), deterministic intermittent 500s (percent
    # of /detect requests), and armed frame corruptions (next N binary
    # frame responses get a byte flipped after encoding)
    slow_replica: float = 0.0
    flaky: int = 0
    corrupt_frame: int = 0
    # ISSUE 15 deployment drills: scope the gray-failure-tier injections
    # (slow_replica / flaky / corrupt_frame) to ONE replica id. Subprocess
    # fleets get per-replica faults for free (each process reads its own
    # SPOTTER_TPU_FAULTS); this is the in-process equivalent — the chaos
    # matrix runs N stub replicas in one process and only the "bad deploy"
    # canary must misbehave. Empty = unscoped (every replica).
    only_replica: str = ""
    # ISSUE 16 control-plane tier: SIGKILL the controller on the Nth
    # main-loop tick (countdown; 0 = disarmed), and arm a one-shot
    # flip-a-journal-byte so the NEXT load must rebuild from observation
    controller_crash: int = 0
    journal_corrupt: int = 0
    # ISSUE 17 output-integrity tier: percent of engine answers perturbed
    # into plausible garbage (Bresenham, scopable via only_replica), number
    # of weights corrupted at bring-up (attestation must catch), and a
    # one-shot miscompiled-restore arm (golden probe must catch)
    sdc: int = 0
    corrupt_weights: int = 0
    corrupt_compile_cache: int = 0
    # ISSUE 19 tenant-isolation tier: "<tenant>:<xQuota>" (the named tenant
    # floods at that multiple of its sustained rate) and the per-shed
    # immediate-retry amplification of an abusive client — both consumed by
    # drill load generators via tenant_flood_spec()/tenant_retry_storm_n()
    tenant_flood: str = ""
    tenant_retry_storm: int = 0
    # set() to un-wedge hanging engine calls early (tests)
    release: threading.Event = field(default_factory=threading.Event)
    _lock: threading.Lock = field(default_factory=threading.Lock)
    _flaky_credit: int = 0
    _sdc_credit: int = 0

    def _consume(self, attr: str) -> bool:
        with self._lock:
            n = getattr(self, attr)
            if n == 0:
                return False
            if n > 0:
                setattr(self, attr, n - 1)
            return True


_active: FaultPlan | None = None


def active() -> FaultPlan | None:
    return _active


@contextlib.contextmanager
def inject(**kwargs):
    """Activate a fault plan for the enclosed block (re-entrant: restores
    whatever plan was active before)."""
    global _active
    prev = _active
    plan = FaultPlan(**kwargs)
    _active = plan
    try:
        yield plan
    finally:
        _active = prev


def maybe_activate_from_env() -> FaultPlan | None:
    """Arm a process-wide plan from SPOTTER_TPU_FAULTS (chaos staging only —
    the standalone server calls this at startup and logs loudly)."""
    global _active
    spec = os.environ.get(FAULTS_ENV, "").strip()
    if not spec:
        return None
    kwargs: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, value = part.partition("=")
        key = key.strip()
        if key not in (
            "fetch_error",
            "fetch_delay_s",
            "malformed_image",
            "engine_error",
            "engine_hang_s",
            "poison_item",
            "engine_oom",
            "shard_dead",
            "cache_error",
            "preempt_storm",
            "overload_spike",
            "slow_stage",
            "slow_replica",
            "flaky",
            "corrupt_frame",
            "only_replica",
            "controller_crash",
            "journal_corrupt",
            "sdc",
            "corrupt_weights",
            "corrupt_compile_cache",
            "tenant_flood",
            "tenant_retry_storm",
        ):
            raise ValueError(f"unknown {FAULTS_ENV} fault {key!r}")
        if key == "slow_stage":
            kwargs[key] = value.strip()
            _parse_slow_stage(kwargs[key])  # fail loudly at activation
            continue
        if key == "tenant_flood":
            kwargs[key] = value.strip()
            _parse_tenant_flood(kwargs[key])  # fail loudly at activation
            continue
        if key == "only_replica":
            kwargs[key] = value.strip()
            continue
        try:
            if key.endswith("_s") or key == "slow_replica":  # durations
                kwargs[key] = float(value)
            else:
                kwargs[key] = int(value)
        except ValueError:
            raise ValueError(f"bad {FAULTS_ENV} entry {part!r}") from None
    _active = FaultPlan(**kwargs)
    return _active


def _parse_slow_stage(spec: str) -> dict[str, float]:
    """`"device:100"` (or `"device:100;fetch:25"`) -> {stage: seconds}."""
    delays: dict[str, float] = {}
    for pair in spec.split(";"):
        pair = pair.strip()
        if not pair:
            continue
        stage, sep, ms = pair.partition(":")
        if not sep:
            raise ValueError(
                f"bad slow_stage entry {pair!r}: expected <stage>:<ms>"
            )
        try:
            delays[stage.strip()] = float(ms) / 1000.0
        except ValueError:
            raise ValueError(
                f"bad slow_stage entry {pair!r}: ms must be a number"
            ) from None
    return delays


def stage_delay_s(stage: str) -> float:
    """Injected latency (seconds) for a named pipeline stage; 0.0 when no
    plan is active — the usual single None check on the production path."""
    plan = _active
    if plan is None or not plan.slow_stage:
        return 0.0
    return _parse_slow_stage(plan.slow_stage).get(stage, 0.0)


def sleep_stage(stage: str) -> None:
    """Blocking form for worker-thread stage sites (the engine's staging/
    fetch/postprocess windows run in threads, so a sleep is attributable
    and harmless)."""
    delay = stage_delay_s(stage)
    if delay > 0.0:
        import time

        time.sleep(delay)


async def on_fetch(url: str) -> bytes | None:
    """Detector fetch hook: returns substitute bytes, raises, sleeps, or
    (the usual case) returns None meaning "fetch normally"."""
    plan = _active
    if plan is None:
        return None
    if plan.fetch_delay_s > 0:
        await asyncio.sleep(plan.fetch_delay_s)
    if plan._consume("fetch_error"):
        import httpx

        raise httpx.ConnectError(f"injected fetch failure for {url}")
    if plan._consume("malformed_image"):
        return MALFORMED_BYTES
    return None


def poison_image(image):
    """Tag a PIL image as poisonous: while a `poison_item` plan is active,
    every engine call whose batch contains it raises (so bisect-retry has a
    deterministic target). Returns the image for chaining."""
    setattr(image, POISON_ATTR, True)
    return image


def on_engine_batch(images: list) -> None:
    """Batcher worker-thread hook, called just before engine.detect — on the
    first attempt AND on every bisect-retry sub-batch, so a poison tag keeps
    failing exactly the subsets that contain it."""
    plan = _active
    if plan is None:
        return
    if plan.engine_hang_s > 0:
        plan.release.wait(plan.engine_hang_s)
    if plan._consume("engine_error"):
        raise RuntimeError(f"injected engine failure (batch of {len(images)})")
    if plan.poison_item and any(
        getattr(im, POISON_ATTR, False) for im in images
    ):
        raise RuntimeError(
            f"injected poison image broke its batch (batch of {len(images)})"
        )


def on_engine_dispatch(n_images: int, device_ids: list) -> None:
    """Engine dispatch hook (inside detect, after staging): device-shaped
    faults with the status markers the failure classifier keys on."""
    plan = _active
    if plan is None:
        return
    if plan.shard_dead >= 0 and plan.shard_dead in device_ids:
        raise RuntimeError(
            f"injected shard loss: DATA_LOSS: device {plan.shard_dead} halted "
            f"(batch of {n_images})"
        )
    if plan._consume("engine_oom"):
        raise RuntimeError(
            f"injected device OOM: RESOURCE_EXHAUSTED while allocating batch "
            f"of {n_images}"
        )


def on_cache(op: str, key: str) -> None:
    """ResultCache hook, called on every get/put (positive and negative).
    The cache wraps this in its own try/except: an injected raise exercises
    the containment contract — degrade to miss/skipped fill, never fail the
    request."""
    plan = _active
    if plan is None:
        return
    if plan._consume("cache_error"):
        raise RuntimeError(f"injected cache failure ({op} {key!r})")


def take_preempt_storm() -> int:
    """Fleet-controller hook: consume the armed storm size in one go (0 when
    no plan or no storm armed). One storm is one correlated event — the
    controller preempts that many spot members on the same tick."""
    plan = _active
    if plan is None:
        return 0
    with plan._lock:
        n = plan.preempt_storm
        plan.preempt_storm = 0
    return n


def take_overload_spike() -> bool:
    """AdaptiveLimiter hook (serving/overload.py): consume ONE armed
    overload-spike tick — that control interval evaluates a synthetic
    far-over-target p90, cutting the limit and (sustained long enough)
    arming the brownout ladder. `overload_spike=N` arms N consecutive
    saturated control ticks."""
    plan = _active
    if plan is None:
        return False
    return plan._consume("overload_spike")


def on_shard_probe(device_id: int) -> None:
    """Engine shard-health-probe hook: the dead shard fails its ping."""
    plan = _active
    if plan is None:
        return
    if plan.shard_dead >= 0 and device_id == plan.shard_dead:
        raise RuntimeError(
            f"injected shard loss: device {device_id} halted (probe)"
        )


# ---- gray-failure tier (ISSUE 14) ----


def _in_scope(plan: FaultPlan, replica_id: str | None) -> bool:
    """Replica scoping (ISSUE 15): an `only_replica` plan only fires for
    the matching replica id; an unscoped plan fires everywhere (the
    pre-ISSUE-15 behavior — callers that don't pass an id keep it)."""
    return not plan.only_replica or (
        replica_id is not None and replica_id == plan.only_replica
    )


def replica_delay_s(replica_id: str | None = None) -> float:
    """Whole-replica slowdown for this process (seconds per engine call);
    0.0 when no plan is active — the usual single None check. The stub
    engine sleeps this inside its `device` stage window so the slowdown is
    visible in traces and stage histograms like a real throttled device."""
    plan = _active
    if plan is None or plan.slow_replica <= 0:
        return 0.0
    if not _in_scope(plan, replica_id):
        return 0.0
    return plan.slow_replica / 1000.0


def take_flaky(replica_id: str | None = None) -> bool:
    """/detect handler hook: True when THIS request should answer 500.
    Deterministic Bresenham-style thinning — `flaky=25` fails exactly every
    4th request, no RNG — so chaos-matrix scenarios assert exact counts."""
    plan = _active
    if plan is None or plan.flaky <= 0:
        return False
    if not _in_scope(plan, replica_id):
        return False
    with plan._lock:
        plan._flaky_credit += min(plan.flaky, 100)
        if plan._flaky_credit >= 100:
            plan._flaky_credit -= 100
            return True
    return False


# ---- control-plane tier (ISSUE 16) ----


def take_controller_crash() -> bool:
    """Reconcile-controller hook, one call per main-loop tick: True when
    the armed countdown reaches zero — the tick on which the controller
    must SIGKILL itself. `controller_crash=3` crashes ON the 3rd tick, so
    a drill can place the kill deterministically inside a rollout wave or
    a preemption storm instead of racing an external kill."""
    plan = _active
    if plan is None or plan.controller_crash <= 0:
        return False
    with plan._lock:
        if plan.controller_crash <= 0:
            return False
        plan.controller_crash -= 1
        return plan.controller_crash == 0


def take_journal_corrupt() -> bool:
    """Reconcile-controller hook: consume the one-shot journal-corruption
    arm. The controller flips a byte of its own journal on disk; the next
    load fails the CRC and rebuilds from observation (counted)."""
    plan = _active
    if plan is None:
        return False
    return plan._consume("journal_corrupt")


# ---- output-integrity tier (ISSUE 17) ----


def perturb_detections(dets: list) -> list:
    """Deterministic 'plausible garbage': same labels and shapes, scores
    and boxes moved far outside the obs/compare.py tolerances. This is
    what silent data corruption looks like from the edge — an HTTP 200
    with a confident wrong answer — so every integrity seam (sdc,
    corrupt_compile_cache) perturbs the same way and the drills can
    assert exact disagreement counts."""
    out = []
    for d in dets or []:
        if isinstance(d, dict):
            d = dict(d)
            try:
                score = float(d.get("score", 0.0))
            except (TypeError, ValueError):
                score = 0.0
            # move the score ~0.17 (>> score_tol) while keeping it a
            # confident, above-threshold answer — SDC that conveniently
            # deleted its own detections would be caught by shape alone
            if score < 0.8:
                d["score"] = round(min(score + 0.17, 0.99), 4)
            else:
                d["score"] = round(max(score - 0.17, 0.01), 4)
            box = d.get("box")
            if isinstance(box, (list, tuple)) and len(box) == 4:
                d["box"] = [float(v) + 17.0 for v in box]
        out.append(d)
    return out


def corrupt_detections(dets: list, replica_id: str | None = None) -> list:
    """Engine-output hook: while an `sdc=<pct>` plan is in scope, perturb
    that share of answers deterministically (Bresenham credit, like
    `flaky`). Identity when not armed — one None check on the hot path."""
    plan = _active
    if plan is None or plan.sdc <= 0 or not _in_scope(plan, replica_id):
        return dets
    with plan._lock:
        plan._sdc_credit += min(plan.sdc, 100)
        if plan._sdc_credit < 100:
            return dets
        plan._sdc_credit -= 100
    return perturb_detections(dets)


def take_corrupt_weights() -> int:
    """Bring-up hook (serving/standalone.py): consume the whole armed
    count in one go — corruption landed in the restore, not one flip per
    request. The caller perturbs that many loaded weights BEFORE the
    `verifying` gate, which must then fail attestation and exit 86."""
    plan = _active
    if plan is None:
        return 0
    with plan._lock:
        n = plan.corrupt_weights
        plan.corrupt_weights = 0
    return max(n, 0)


def take_corrupt_compile_cache() -> bool:
    """Golden-probe hook (serving/integrity.py): one-shot miscompiled
    restore — the probe's OBSERVED answer gets perturbed while weights
    attest clean, so only the probe can catch it. Consumed once: the
    respawn (with the quarantined cache dir recompiling from scratch)
    probes clean."""
    plan = _active
    if plan is None:
        return False
    return plan._consume("corrupt_compile_cache")


# ---- tenant-isolation tier (ISSUE 19) ----


def _parse_tenant_flood(spec: str) -> tuple[str, float]:
    """`"abuser:8"` -> ("abuser", 8.0): the named tenant floods at that
    multiple of its sustained quota rate."""
    tenant, sep, mult = spec.partition(":")
    tenant = tenant.strip()
    if not sep or not tenant:
        raise ValueError(
            f"bad tenant_flood entry {spec!r}: expected <tenant>:<xQuota>"
        )
    try:
        factor = float(mult)
    except ValueError:
        raise ValueError(
            f"bad tenant_flood entry {spec!r}: xQuota must be a number"
        ) from None
    if factor <= 0:
        raise ValueError(
            f"bad tenant_flood entry {spec!r}: xQuota must be > 0"
        )
    return tenant, factor


def tenant_flood_spec() -> tuple[str, float] | None:
    """Drill load-generator hook: (tenant, xQuota) while a tenant_flood
    plan is active, else None. The fault is the CLIENT's behavior — the
    generator sends the named tenant's traffic at xQuota times its
    sustained rate; the serving path is unmodified (its token bucket is
    the thing under test)."""
    plan = _active
    if plan is None or not plan.tenant_flood:
        return None
    return _parse_tenant_flood(plan.tenant_flood)


def tenant_retry_storm_n() -> int:
    """Drill load-generator hook: how many immediate (Retry-After-ignoring)
    re-sends the abusive client fires per shed; 0 when not armed."""
    plan = _active
    if plan is None:
        return 0
    return max(plan.tenant_retry_storm, 0)


def corrupt_frame_bytes(data: bytes, replica_id: str | None = None) -> bytes:
    """Response-encode hook: while armed, flip one byte near the tail of
    the encoded frame (segment bytes — a CRC-protected region) and consume
    one `corrupt_frame` unit. Identity when not armed."""
    plan = _active
    if plan is None or not data or not _in_scope(plan, replica_id):
        return data
    if not plan._consume("corrupt_frame"):
        return data
    idx = max(len(data) - 2, 0)
    return data[:idx] + bytes([data[idx] ^ 0xFF]) + data[idx + 1:]
