"""Subprocess supervisor: restart a crashed/preempted replica with backoff.

k8s restarts pods, but inside a pod (and on bare VMs, and in the failover
test/bench harness) something must bring a dead server back — and do it in
seconds when the death was a preemption that already drained cleanly, while
NOT hot-looping when the server crashes at import time. Policy:

- exit 0 (operator stop) → supervisor exits 0;
- `PREEMPTED_EXIT_CODE` (drained preemption exit, serving/lifecycle.py) →
  immediate restart, backoff reset: the replica told us it shut down
  healthy. But a preemption SOURCE can outlive the child (the maintenance
  file is not deleted, a GCE maintenance window spans minutes), so only the
  first `--preempt-fast` consecutive sub-min-uptime preemption exits restart
  for free — after that the normal exponential backoff applies so the pair
  cannot hot-loop spawn→drain→exit;
- `FATAL_ENGINE_EXIT_CODE` (engine/errors.py: fatal device error with
  nothing left to degrade to) → immediate warm restart: the persistent
  compile cache makes the respawn cheap and the device usually comes back
  healthy after a re-init. Same fast-limit guard as preemption — a chip
  that stays dead must not hot-loop spawn→fatal→exit;
- `INTEGRITY_EXIT_CODE` (serving/lifecycle.py: weights attestation or
  golden-probe failure, ISSUE 17) → COLD restart with the persistent
  compile-cache dir quarantined (renamed aside, preserved for forensics):
  a warm restart would faithfully restore the exact cached state that just
  produced wrong answers, so this is the one exit where the cache is
  suspect by construction. Same fast-limit guard — corruption that
  survives a cold rebuild (bad checkpoint on disk, bad chip) must not
  hot-loop;
- any other exit → restart after exponential backoff (`--backoff-base`,
  doubling to `--backoff-max`); a child that stayed up ≥ `--min-uptime`
  resets the backoff. Backoff waits are FULL-JITTERED by default
  (`SPOTTER_TPU_BACKOFF_JITTER=0` disables): the actual wait is drawn
  uniformly from (0, cap] while the cap keeps its deterministic doubling.
  A fleet of supervisors preempted by the same maintenance wave would
  otherwise re-enter backoff in lockstep and thunder-herd the restarts
  (ISSUE 6) — with full jitter, seeded differently per process, they
  desynchronize;
- crash-loop circuit: more than `--crash-loop` consecutive sub-min-uptime
  crashes → give up and exit non-zero (let the orchestrator above decide).

Each (re)start exports `SPOTTER_TPU_RESTARTS=<n>` to the child so
`restarts_total` lands in the replica's /metrics, and rewrites `--pidfile`
so harnesses (`tests/test_failover.py`) can target the CURRENT child with
preemption faults. SIGTERM to the supervisor forwards to the child and
exits with the child's code — the pod-level preStop path stays intact.

With `--manifest PATH --url URL` (ISSUE 16) the supervisor registers its
replica in the shared endpoints manifest at startup and deregisters only
on PERMANENT exit (clean stop, crash-loop circuit, SIGTERM) — it stays
registered across preemption (83) and fatal-engine (85) restarts, because
the replica identity survives them. That makes the manifest the control
plane's observation of record: a restarted controller adopts every entry
whose supervisor pid is still alive instead of double-spawning, and prunes
entries whose supervisor died without the finally block running (kill -9).
"""

import argparse
import logging
import os
import random
import signal
import subprocess
import sys
import threading
import time

from spotter_tpu.engine.errors import FATAL_ENGINE_EXIT_CODE
from spotter_tpu.serving.lifecycle import (
    INTEGRITY_EXIT_CODE,
    PREEMPTED_EXIT_CODE,
    RESTARTS_ENV,
    compile_cache_dir,
)

# The jitter knob moved to serving/resilience.py (ISSUE 8 satellite: the
# same switch now also governs the +-25% Retry-After jitter on 429/503
# hints); re-exported here so existing imports keep working.
from spotter_tpu.serving.resilience import (
    BACKOFF_JITTER_ENV,  # noqa: F401
    jitter_enabled_from_env,
)

logger = logging.getLogger(__name__)

DEFAULT_BACKOFF_BASE_S = 0.5
DEFAULT_BACKOFF_MAX_S = 30.0
DEFAULT_MIN_UPTIME_S = 5.0
DEFAULT_CRASH_LOOP_LIMIT = 5
DEFAULT_PREEMPT_FAST_LIMIT = 3
CRASH_LOOP_EXIT_CODE = 84  # distinct from the child's codes and from 83


def quarantine_compile_cache() -> str | None:
    """Move the persistent compile-cache dir aside (ISSUE 17).

    Called before respawning after an integrity exit (86): the cache is
    the one piece of state a cold restart would otherwise faithfully
    re-ingest, so it is renamed — never deleted, the quarantined copy IS
    the forensic artifact — to `<dir>.quarantined.<n>`. The child then
    recreates the dir empty and recompiles from scratch. Returns the
    quarantine path, or None when the cache dir does not exist yet."""
    cache_dir = compile_cache_dir()
    if not os.path.isdir(cache_dir):
        return None
    n = 0
    while True:
        target = f"{cache_dir.rstrip(os.sep)}.quarantined.{n}"
        if not os.path.exists(target):
            break
        n += 1
    try:
        os.rename(cache_dir, target)
    except OSError:
        logger.exception("could not quarantine compile cache %s", cache_dir)
        return None
    logger.warning(
        "quarantined suspect compile cache: %s -> %s", cache_dir, target
    )
    return target


class Supervisor:
    def __init__(
        self,
        cmd: list[str],
        backoff_base_s: float = DEFAULT_BACKOFF_BASE_S,
        backoff_max_s: float = DEFAULT_BACKOFF_MAX_S,
        min_uptime_s: float = DEFAULT_MIN_UPTIME_S,
        crash_loop_limit: int = DEFAULT_CRASH_LOOP_LIMIT,
        preempt_fast_limit: int = DEFAULT_PREEMPT_FAST_LIMIT,
        pidfile: str | None = None,
        jitter: bool | None = None,
        rng: random.Random | None = None,
    ) -> None:
        if not cmd:
            raise ValueError("supervisor needs a command")
        self.cmd = cmd
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self.min_uptime_s = min_uptime_s
        self.crash_loop_limit = crash_loop_limit
        self.preempt_fast_limit = preempt_fast_limit
        self.pidfile = pidfile
        self.jitter = jitter_enabled_from_env() if jitter is None else jitter
        # per-process RNG (seedable in tests): two supervisors restarted by
        # the same preemption wave draw different waits and desynchronize
        self._rng = rng if rng is not None else random.Random()
        self._backoff_s = 0.0  # deterministic doubling cap; waits jitter off it
        self.restarts_total = 0
        self.child: subprocess.Popen | None = None
        self._terminating = False
        # Set by _forward_term so the backoff wait wakes immediately instead
        # of time.sleep resuming after the handler (PEP 475) and the loop
        # spawning a child nobody asked for.
        self._term_event = threading.Event()

    def _spawn(self) -> subprocess.Popen:
        env = dict(os.environ)
        env[RESTARTS_ENV] = str(self.restarts_total)
        child = subprocess.Popen(self.cmd, env=env)
        if self.pidfile:
            tmp = f"{self.pidfile}.tmp"
            with open(tmp, "w") as f:
                f.write(str(child.pid))
            os.replace(tmp, self.pidfile)  # atomic: readers never see partial
        logger.info(
            "spawned child pid=%d (restart #%d): %s",
            child.pid, self.restarts_total, " ".join(self.cmd),
        )
        return child

    def _forward_term(self, signum, frame) -> None:
        self._terminating = True
        self._term_event.set()
        if self.child is not None and self.child.poll() is None:
            self.child.send_signal(signal.SIGTERM)

    def _reset_backoff(self) -> None:
        self._backoff_s = 0.0

    def _bump_backoff(self) -> float:
        """Advance the deterministic doubling cap, then draw the actual wait:
        full jitter (uniform over (0, cap]) when enabled, else the cap
        itself. The cap trajectory stays identical across supervisors (so
        the crash-loop window is predictable); only the waits decorrelate."""
        self._backoff_s = min(
            max(self._backoff_s * 2.0, self.backoff_base_s), self.backoff_max_s
        )
        if not self.jitter:
            return self._backoff_s
        return self._rng.uniform(0.0, self._backoff_s)

    def run(self) -> int:
        """Supervise until the child exits cleanly, the crash-loop circuit
        trips, or SIGTERM. Returns the exit code to propagate."""
        signal.signal(signal.SIGTERM, self._forward_term)
        self._reset_backoff()
        consecutive_fast_crashes = 0
        consecutive_fast_preempts = 0
        consecutive_fast_fatals = 0
        consecutive_fast_integrity = 0
        code = 0
        while True:
            if self._terminating:
                # SIGTERM landed while no child was running (e.g. during the
                # backoff wait): do NOT spawn a replacement the signal could
                # never reach — propagate the last child's code.
                logger.info("terminated between children; exiting %d", code)
                return code
            started = time.monotonic()
            self.child = self._spawn()
            if self._terminating and self.child.poll() is None:
                # signal raced the spawn: the handler ran before self.child
                # pointed at this child, so forward SIGTERM ourselves
                self.child.send_signal(signal.SIGTERM)
            code = self.child.wait()
            uptime = time.monotonic() - started
            if self._terminating:
                logger.info("terminated; child exited %d", code)
                return code
            if code == 0:
                logger.info("child exited cleanly; supervisor done")
                return 0
            if code == FATAL_ENGINE_EXIT_CODE:
                # controlled fatal-device exit (engine fault domain): restart
                # immediately — the persistent compile cache makes it a warm
                # bring-up and a re-initialized runtime usually gets the
                # device back. Same hot-loop guard as preemption: a chip
                # that STAYS dead falls back to exponential backoff after
                # `preempt_fast_limit` consecutive fast exits.
                consecutive_fast_crashes = 0
                consecutive_fast_preempts = 0
                consecutive_fast_integrity = 0
                if uptime >= self.min_uptime_s:
                    consecutive_fast_fatals = 0
                else:
                    consecutive_fast_fatals += 1
                if consecutive_fast_fatals <= self.preempt_fast_limit:
                    logger.warning(
                        "child hit a fatal engine error (exit %d); immediate "
                        "warm restart via compile cache", code,
                    )
                    self._reset_backoff()
                else:
                    wait_s = self._bump_backoff()
                    logger.warning(
                        "child hit fatal engine errors (exit %d) %d times under "
                        "%.1f s uptime — device appears to stay dead; "
                        "restarting in %.2f s",
                        code, consecutive_fast_fatals, self.min_uptime_s, wait_s,
                    )
                    if self._term_event.wait(wait_s):
                        logger.info("terminated during backoff; exiting %d", code)
                        return code
            elif code == INTEGRITY_EXIT_CODE:
                # integrity failure (ISSUE 17): attestation or golden probe
                # caught wrong outputs. COLD restart — quarantine the
                # compile-cache dir first, because a warm restart would
                # faithfully restore the exact state that just failed. The
                # fast-limit guard catches corruption a cold rebuild cannot
                # fix (bad checkpoint on disk, bad chip): backoff, don't
                # hot-loop recompiles.
                consecutive_fast_crashes = 0
                consecutive_fast_preempts = 0
                consecutive_fast_fatals = 0
                if uptime >= self.min_uptime_s:
                    consecutive_fast_integrity = 0
                else:
                    consecutive_fast_integrity += 1
                quarantine_compile_cache()
                if consecutive_fast_integrity <= self.preempt_fast_limit:
                    logger.warning(
                        "child failed integrity verification (exit %d); "
                        "cold restart with compile cache quarantined", code,
                    )
                    self._reset_backoff()
                else:
                    wait_s = self._bump_backoff()
                    logger.warning(
                        "child failed integrity verification (exit %d) %d "
                        "times under %.1f s uptime — corruption survives "
                        "cold restarts; restarting in %.2f s",
                        code, consecutive_fast_integrity, self.min_uptime_s,
                        wait_s,
                    )
                    if self._term_event.wait(wait_s):
                        logger.info("terminated during backoff; exiting %d", code)
                        return code
            elif code == PREEMPTED_EXIT_CODE:
                # drained preemption: the replica is healthy software on
                # yanked capacity — restart immediately, no backoff debt. But
                # the source can persist (the maintenance file is never
                # deleted, a GCE window spans minutes), so only the first
                # `preempt_fast_limit` consecutive sub-min-uptime preemption
                # exits restart for free; after that, normal backoff.
                consecutive_fast_crashes = 0
                consecutive_fast_fatals = 0
                consecutive_fast_integrity = 0
                if uptime >= self.min_uptime_s:
                    consecutive_fast_preempts = 0
                else:
                    consecutive_fast_preempts += 1
                if consecutive_fast_preempts <= self.preempt_fast_limit:
                    logger.warning(
                        "child preempted (exit %d); immediate warm restart", code
                    )
                    self._reset_backoff()
                else:
                    wait_s = self._bump_backoff()
                    logger.warning(
                        "child preempted (exit %d) %d times under %.1f s uptime "
                        "— preemption source persists; restarting in %.2f s",
                        code, consecutive_fast_preempts, self.min_uptime_s, wait_s,
                    )
                    if self._term_event.wait(wait_s):
                        logger.info("terminated during backoff; exiting %d", code)
                        return code
            else:
                consecutive_fast_preempts = 0
                consecutive_fast_fatals = 0
                consecutive_fast_integrity = 0
                if uptime >= self.min_uptime_s:
                    self._reset_backoff()
                    consecutive_fast_crashes = 0
                else:
                    consecutive_fast_crashes += 1
                    if consecutive_fast_crashes > self.crash_loop_limit:
                        logger.error(
                            "crash loop: %d consecutive crashes under %.1f s "
                            "uptime; giving up",
                            consecutive_fast_crashes, self.min_uptime_s,
                        )
                        # persist whatever the supervisor-side flight
                        # recorder holds (ISSUE 7; usually empty — the
                        # replica's own ring dumps on 83/85 in-process)
                        from spotter_tpu.obs.recorder import dump_for_exit

                        dump_for_exit(CRASH_LOOP_EXIT_CODE)
                        return CRASH_LOOP_EXIT_CODE
                wait_s = self._bump_backoff()
                logger.warning(
                    "child crashed (exit %d, uptime %.1f s); restarting in %.2f s",
                    code, uptime, wait_s,
                )
                if self._term_event.wait(wait_s):
                    logger.info("terminated during backoff; exiting %d", code)
                    return code
            self.restarts_total += 1


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="spotter-tpu replica supervisor",
        usage="python -m spotter_tpu.serving.supervisor [opts] -- CMD [ARG...]",
    )
    parser.add_argument("--backoff-base", type=float, default=DEFAULT_BACKOFF_BASE_S)
    parser.add_argument("--backoff-max", type=float, default=DEFAULT_BACKOFF_MAX_S)
    parser.add_argument("--min-uptime", type=float, default=DEFAULT_MIN_UPTIME_S)
    parser.add_argument("--crash-loop", type=int, default=DEFAULT_CRASH_LOOP_LIMIT)
    parser.add_argument("--preempt-fast", type=int, default=DEFAULT_PREEMPT_FAST_LIMIT,
                        help="consecutive sub-min-uptime preemption exits that "
                        "restart immediately before normal backoff applies")
    parser.add_argument("--backoff-jitter", choices=["on", "off"], default=None,
                        help=f"full-jitter backoff waits (default from "
                        f"{BACKOFF_JITTER_ENV}, on unless set to 0)")
    parser.add_argument("--pidfile", default=None,
                        help="rewritten with the current child pid on every spawn")
    parser.add_argument("--manifest", default=None,
                        help="endpoints manifest (serving/statestore.py) to "
                        "register this replica in for controller adoption")
    parser.add_argument("--url", default=None,
                        help="replica base URL recorded in --manifest")
    parser.add_argument("cmd", nargs=argparse.REMAINDER,
                        help="child command (after --)")
    args = parser.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        parser.error("no child command given (use -- CMD ARG...)")
    if args.manifest and not args.url:
        parser.error("--manifest requires --url (the manifest key)")
    logging.basicConfig(level=logging.INFO)
    sup = Supervisor(
        cmd,
        backoff_base_s=args.backoff_base,
        backoff_max_s=args.backoff_max,
        min_uptime_s=args.min_uptime,
        crash_loop_limit=args.crash_loop,
        preempt_fast_limit=args.preempt_fast,
        pidfile=args.pidfile,
        jitter=None if args.backoff_jitter is None
        else args.backoff_jitter == "on",
    )
    manifest = None
    if args.manifest:
        # stdlib-only import (no jax/httpx): keep supervisor bring-up light
        from spotter_tpu.serving.statestore import EndpointsManifest

        manifest = EndpointsManifest(args.manifest)
        manifest.add(
            args.url,
            pool=os.environ.get("SPOTTER_TPU_POOL", ""),
            version=os.environ.get("SPOTTER_TPU_BUILD_VERSION", ""),
            preempt_file=os.environ.get("SPOTTER_TPU_PREEMPTION_FILE", ""),
            pidfile=args.pidfile or "",
            supervisor_pid=os.getpid(),
        )
    try:
        return sup.run()
    finally:
        if manifest is not None:
            # permanent exit only: preemption/fatal restarts never reach here
            try:
                manifest.remove(args.url)
            except OSError:
                pass  # best-effort — the reconciler prunes dead entries


if __name__ == "__main__":
    sys.exit(main())
