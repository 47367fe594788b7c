"""Replica lifecycle: startup state machine, preemption watcher, warm restart.

PR 1 hardened the request path inside one replica; this module makes the
*replica itself* a managed, restartable unit — the prerequisite for running
the fleet on spot/preemptible TPU capacity (Spotlight, arXiv:2606.19004:
preemption-aware scheduling recovers most on-demand throughput; DeepServe,
arXiv:2501.14417: fast cold start + health-aware routing is what makes
serverless serving viable). Three pieces:

- `StartupTracker`: the `loading -> warming -> ready` state machine behind
  the `/startupz` endpoint, so a k8s startupProbe can distinguish "still
  compiling the bucket ladder" from "dead" and not kill a long warmup.
  `mark_ready()` records `time_to_ready_s` into the engine metrics — the
  number warm-restart work optimizes (the benchmark's `setup_s` holds it).
- `PreemptionWatcher`: SIGTERM plus an env-configured maintenance-event
  source (`SPOTTER_TPU_PREEMPTION_FILE`: a path whose appearance signals the
  event — fault-injectable from tests and chaos staging;
  `SPOTTER_TPU_PREEMPTION_URL`: a metadata endpoint polled like GCE's
  maintenance-event URL). On the first signal it flips readiness, drains via
  the detector's existing `drain()`, and exits with a DISTINCT code
  (`PREEMPTED_EXIT_CODE`) so the supervisor can tell preemption from a crash
  and skip the crash-loop backoff.
- `enable_compile_cache()`: arms JAX's persistent compilation cache before
  any program is compiled, so a restarted replica (same model, same bucket
  ladder) skips recompilation — the difference between a minutes-long and a
  seconds-long `time_to_ready_s`. The cache is placed from outside by JAX's
  own `JAX_COMPILATION_CACHE_DIR`; unset, it lives at the fixed
  `<checkout>/.jax_cache` (`compile_cache_dir()`).
"""

import asyncio
import logging
import os
import signal
import threading
import time
from typing import Awaitable, Callable, Optional

logger = logging.getLogger(__name__)

# JAX's own variable: when set, JAX reads it itself and this module sets no
# directory in code.
JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# The fallback is a fixed path inside the checkout — never a temp name, pid
# or timestamp: the directory is part of the cache key, so one that moves
# never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
PREEMPTION_FILE_ENV = "SPOTTER_TPU_PREEMPTION_FILE"
PREEMPTION_URL_ENV = "SPOTTER_TPU_PREEMPTION_URL"
PREEMPTION_POLL_ENV = "SPOTTER_TPU_PREEMPTION_POLL_S"
RESTARTS_ENV = "SPOTTER_TPU_RESTARTS"
# Which fleet pool this replica belongs to ("on_demand" / "spot"), set by
# whatever spawned it (testing/cluster.py fleet members, a k8s nodeSelector
# wrapper). Purely a label: it surfaces in /startupz + /healthz so an
# operator — and the fleet controller's logs — can tell capacity classes
# apart without consulting the spawner.
POOL_ENV = "SPOTTER_TPU_POOL"

DEFAULT_PREEMPTION_POLL_S = 5.0

# Distinct from any Python/aiohttp crash code: the supervisor restarts a
# preempted replica immediately (capacity came back or k8s rescheduled us)
# instead of treating it as a crash loop.
PREEMPTED_EXIT_CODE = 83

# Startup states, in order. "ready" is terminal for a healthy bring-up;
# "failed" is terminal for a bring-up that raised — the server exits
# non-zero right after marking it so the supervisor/kubelet restart path
# (with backoff) takes over instead of the replica serving 503s forever.
# "verifying" (ISSUE 17) sits between warming and ready: the golden probe
# and weights attestation must pass before the replica may serve — on cold
# start, warm compile-cache restore, OOM downgrade, and degraded-dp
# rebuild alike. A warmup that compiled fine can still answer WRONG
# (corrupt restore, poisoned compile cache), and readiness is the last
# gate before clients see those answers.
LOADING = "loading"
WARMING = "warming"
VERIFYING = "verifying"
READY = "ready"
FAILED = "failed"

# Exit code for a failed bring-up: distinct from PREEMPTED_EXIT_CODE (83)
# and the supervisor's CRASH_LOOP_EXIT_CODE (84) so logs tell the three
# apart; the supervisor treats it as a plain crash (exponential backoff).
BRINGUP_FAILED_EXIT_CODE = 82

# Exit code for a failed integrity verification (ISSUE 17): the replica's
# golden probe or weights attestation failed — it was about to serve (or
# WAS serving) wrong answers. Distinct from every other rung because the
# supervisor's response is unique: COLD restart with the suspect
# compile-cache dir quarantined, since a warm restart would faithfully
# restore the very state that just failed verification.
INTEGRITY_EXIT_CODE = 86

# Process-start anchor for time_to_ready_s. Module import happens at the top
# of server bootstrap, so this slightly undercounts interpreter start — the
# compile/warmup cost it exists to expose dwarfs that.
_PROCESS_START = time.monotonic()


def process_age_s() -> float:
    """Seconds since the kernel started this process: interpreter start and
    every import before this module included, which `_PROCESS_START` leaves
    out. From /proc (the start time in clock ticks since boot against the
    boot clock); where that cannot be read, since this module's import."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's closing parenthesis; the start
            # time is the 22nd of the line, the 20th of these
            started_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        boot_now = time.clock_gettime(time.CLOCK_BOOTTIME)
        return boot_now - started_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic() - _PROCESS_START


def compile_cache_dir() -> str:
    """The persistent compile-cache directory this process uses: wherever
    `JAX_COMPILATION_CACHE_DIR` places it, else `<checkout>/.jax_cache`.
    jax-free, so the supervisor resolves (and quarantines) the same dir."""
    return os.environ.get(JAX_CACHE_DIR_ENV, "").strip() or DEFAULT_COMPILE_CACHE_DIR


def enable_compile_cache() -> str:
    """Arm JAX's persistent compilation cache (idempotent).

    Must run before the first jit compilation of the process. Thresholds are
    zeroed so every bucket program is cached — the ladder is a handful of
    programs and a preempted replica wants all of them back. From here on
    every program the process compiles is counted as a hit or a miss of the
    cache (`compile_cache_totals`, in `/metrics`).
    """
    global _cache_listening
    import jax

    cache_dir = compile_cache_dir()
    if cache_dir != (os.environ.get(JAX_CACHE_DIR_ENV) or "").strip():
        # not placed from outside (then JAX's own handling stands)
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    with _cache_lock:
        if not _cache_listening:
            jax.monitoring.register_event_listener(_count_cache_event)
            _cache_listening = True
    logger.info("persistent compile cache at %s", cache_dir)
    return cache_dir


# jax.monitoring's events for a program read from the cache and for one
# compiled and written to it (jax 0.9.0: `_src/compiler.py`,
# `_src/compilation_cache.py`)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_cache_lock = threading.Lock()
_cache_counts = {"hits": 0, "misses": 0}
_cache_listening = False


def _count_cache_event(event: str, **_kwargs) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _cache_lock:
            _cache_counts[key] += 1


def compile_cache_totals() -> dict:
    """Programs this process loaded from the persistent compile cache and
    programs it compiled and wrote there, since `enable_compile_cache`."""
    with _cache_lock:
        return {
            "compile_cache_hits_total": _cache_counts["hits"],
            "compile_cache_misses_total": _cache_counts["misses"],
        }


def pool_from_env() -> Optional[str]:
    """The fleet pool label this replica was spawned into, or None."""
    return os.environ.get(POOL_ENV, "").strip() or None


def restarts_from_env() -> int:
    """How many times the supervisor has restarted this replica (0 on the
    first launch or outside a supervisor)."""
    raw = os.environ.get(RESTARTS_ENV, "").strip()
    try:
        return max(0, int(raw)) if raw else 0
    except ValueError:
        return 0


class StartupTracker:
    """`loading -> warming -> ready` behind /startupz.

    A k8s startupProbe polls /startupz with a generous failureThreshold;
    readiness/liveness probes only take over once startup has succeeded, so
    a cold compile cache cannot get the pod killed mid-warmup.
    """

    def __init__(self) -> None:
        self._state = LOADING
        self._since = time.monotonic()
        self.time_to_ready_s: Optional[float] = None
        self.error: Optional[str] = None

    @property
    def state(self) -> str:
        return self._state

    @property
    def ready(self) -> bool:
        return self._state == READY

    def mark(self, state: str) -> None:
        if state not in (LOADING, WARMING, VERIFYING, READY):
            raise ValueError(f"unknown startup state {state!r}")
        self._state = state
        self._since = time.monotonic()

    def mark_ready(self, metrics=None) -> float:
        """Transition to ready; record time_to_ready_s (process start ->
        now) into `metrics` when given. Returns the gauge value."""
        self._state = READY
        self._since = time.monotonic()
        self.time_to_ready_s = time.monotonic() - _PROCESS_START
        if metrics is not None:
            metrics.set_time_to_ready(self.time_to_ready_s)
        return self.time_to_ready_s

    def mark_failed(self, error: str) -> None:
        """Terminal: bring-up raised. /startupz keeps answering 503 with the
        error for whatever probe window remains before the process exits."""
        self._state = FAILED
        self._since = time.monotonic()
        self.error = error

    def snapshot(self) -> dict:
        # deploy identity (ISSUE 15): a replica that is still loading
        # already declares WHICH build is coming up — the rollout
        # controller (and an operator watching a canary spawn) reads it
        # from /startupz before the engine exists. Imported lazily so this
        # module stays cheap for the supervisor's import path.
        from spotter_tpu.engine.metrics import default_build_version

        return {
            "state": self._state,
            "ready": self.ready,
            "state_age_s": time.monotonic() - self._since,
            "time_to_ready_s": self.time_to_ready_s,
            "error": self.error,
            "pool": pool_from_env(),
            "version": default_build_version(),
        }


class PreemptionWatcher:
    """Watch for preemption (SIGTERM or a maintenance-event source) and run
    one graceful drain-then-exit sequence.

    `on_preempt` is awaited exactly once (typically `detector.drain()` — it
    already flips readiness so the LB stops routing); then `exit_cb` is
    called with `PREEMPTED_EXIT_CODE`. Tests inject a no-op `exit_cb`; the
    server default is `os._exit`, which is deliberate: after a drain there is
    nothing left worth unwinding, and a preempted host may have seconds.
    """

    def __init__(
        self,
        on_preempt: Callable[[], Awaitable],
        poll_s: Optional[float] = None,
        file_source: Optional[str] = None,
        url_source: Optional[str] = None,
        exit_cb: Callable[[int], None] = os._exit,
        install_sigterm: bool = True,
    ) -> None:
        if poll_s is None:
            raw = os.environ.get(PREEMPTION_POLL_ENV, "").strip()
            poll_s = float(raw) if raw else DEFAULT_PREEMPTION_POLL_S
        self.on_preempt = on_preempt
        self.poll_s = max(poll_s, 0.01)
        self.file_source = (
            file_source
            if file_source is not None
            else os.environ.get(PREEMPTION_FILE_ENV, "").strip() or None
        )
        self.url_source = (
            url_source
            if url_source is not None
            else os.environ.get(PREEMPTION_URL_ENV, "").strip() or None
        )
        self.exit_cb = exit_cb
        self.install_sigterm = install_sigterm
        self.preempted = False
        self.reason: Optional[str] = None
        self._task: Optional[asyncio.Task] = None
        self._triggered = asyncio.Event()

    async def start(self) -> None:
        loop = asyncio.get_running_loop()
        if self.install_sigterm:
            try:
                loop.add_signal_handler(
                    signal.SIGTERM, self.trigger, "SIGTERM (kubelet/preemption)"
                )
            except (NotImplementedError, RuntimeError):  # non-main thread
                logger.warning("could not install SIGTERM handler")
        self._task = asyncio.create_task(self._run())

    def trigger(self, reason: str) -> None:
        """Idempotent: the first trigger wins; later ones are logged only."""
        if self.preempted:
            logger.info("preemption re-signaled (%s); drain already running", reason)
            return
        self.preempted = True
        self.reason = reason
        self._triggered.set()

    async def _check_sources(self) -> Optional[str]:
        if self.file_source and os.path.exists(self.file_source):
            return f"maintenance file {self.file_source}"
        if self.url_source:
            try:
                import httpx

                async with httpx.AsyncClient(timeout=2.0) as client:
                    resp = await client.get(self.url_source)
                body = resp.text.strip().upper()
                if resp.status_code == 200 and body not in ("", "NONE", "FALSE"):
                    return f"maintenance event from {self.url_source}: {body[:80]}"
            except Exception:  # metadata endpoint flaky — never a crash source
                logger.debug("preemption URL poll failed", exc_info=True)
        return None

    async def _run(self) -> None:
        while not self._triggered.is_set():
            reason = await self._check_sources()
            if reason is not None:
                self.trigger(reason)
                break
            try:
                await asyncio.wait_for(self._triggered.wait(), self.poll_s)
            except asyncio.TimeoutError:
                continue
        await self._triggered.wait()
        logger.warning("preemption: %s — draining then exiting %d",
                       self.reason, PREEMPTED_EXIT_CODE)
        try:
            await self.on_preempt()
        except Exception:
            logger.exception("drain during preemption failed; exiting anyway")
        # flight-recorder post-mortem (ISSUE 7): the in-memory trace ring
        # dies with the process — persist it so "what was in flight when
        # the preemption landed" is answerable after the restart
        from spotter_tpu.obs.recorder import dump_for_exit

        dump_for_exit(PREEMPTED_EXIT_CODE)
        self.exit_cb(PREEMPTED_EXIT_CODE)

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
