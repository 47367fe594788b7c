"""Output-integrity plane (ISSUE 17): silent-data-corruption immunity.

Every robustness tier so far catches a replica that is DEAD, SLOW, or
OVERLOADED. None of them catches a replica that is healthy, fast, and
WRONG — a flipped weight bit after a spot-capacity warm restore, a
poisoned persistent-compile-cache entry, a chip emitting plausible
garbage. At the north-star scale (PAPER.md; Spotlight's preempt→restore
churn and DeepServe's scale-to-zero restores in PAPERS.md) silent data
corruption is a *when*, not an *if*, and every restore path is an ingress
for it. Three layers, one module:

- **GoldenProbe** — a deterministic per-model-family probe image with a
  pinned reference answer, injected through the REAL batcher path (bulk
  class so it never displaces slo traffic; `key=None` so it can never
  pollute the ResultCache or coalesce onto a live flight) and compared
  with the shared obs/compare.py tolerance comparator. Families without
  a pinned registry entry self-pin at the `verifying` readiness gate —
  after attestation has already vouched for the weights — and every later
  probe must match that answer.
- **WeightsAttestor** — wraps the engine's jit'd on-device bitpattern
  checksum (`engine.attest()`): every param shard is checksummed WHERE IT
  LIVES under dp×tp and compared against the trusted host checkpoint
  copy, so a single bad chip's shard is caught and named. Runs at every
  readiness verification and on a period.
- **IntegrityPlane** — composes the two behind the `verifying` lifecycle
  state (serving/lifecycle.py): probe + attestation must pass before
  READY on cold start, warm compile-cache restore, OOM downgrade, and
  degraded-dp rebuild. A failure — at the gate or from the periodic
  loop — exits with `INTEGRITY_EXIT_CODE` (86) after pinning a
  flight-recorder trace; the supervisor cold-restarts with the suspect
  compile-cache dir quarantined (a warm restart would faithfully restore
  the exact state that just failed).

The fourth layer lives at the edge: **QuorumSampler** (used by
serving/router.py) dual-dispatches a deterministically-sampled slice of
live traffic to a second ranked replica — reusing the pool's transport
but COMPARING instead of racing, the inverse of a hedge — and tracks a
per-replica disagreement EWMA. On a disagreement it asks a third replica
to arbitrate, so the deviant is charged and the honest witness is not
(without arbitration a corrupt replica would drag every peer it is
compared against toward the threshold with it). A replica over threshold
is HARD-quarantined via `pool.quarantine()`: out of the ring at zero
weight — unlike gray soft-ejection's 5% trickle, because wrong answers
must not keep ANY trickle — with a pinned flight-recorder trace
(`integrity-quarantine-*`). Its own periodic probe then takes it through
the exit-86 → cold-restart path.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from typing import Callable, Optional

from spotter_tpu import obs
from spotter_tpu.obs import compare
from spotter_tpu.serving.lifecycle import INTEGRITY_EXIT_CODE
from spotter_tpu.serving.overload import BULK
from spotter_tpu.testing import faults

logger = logging.getLogger(__name__)

INTEGRITY_ENV = "SPOTTER_TPU_INTEGRITY"
PROBE_INTERVAL_ENV = "SPOTTER_TPU_PROBE_INTERVAL_S"
ATTEST_INTERVAL_ENV = "SPOTTER_TPU_ATTEST_INTERVAL_S"
QUORUM_PCT_ENV = "SPOTTER_TPU_QUORUM_PCT"
QUORUM_EWMA_ENV = "SPOTTER_TPU_QUORUM_EWMA"
QUORUM_MIN_SAMPLES_ENV = "SPOTTER_TPU_QUORUM_MIN_SAMPLES"
QUORUM_ALPHA_ENV = "SPOTTER_TPU_QUORUM_ALPHA"

DEFAULT_PROBE_INTERVAL_S = 30.0
DEFAULT_ATTEST_INTERVAL_S = 60.0
DEFAULT_QUORUM_PCT = 0.0  # off unless the edge opts in
DEFAULT_QUORUM_EWMA = 0.6
DEFAULT_QUORUM_MIN_SAMPLES = 6
DEFAULT_QUORUM_ALPHA = 0.25

# Probe canvas: small enough to be negligible engine work, big enough to
# exercise the real preprocess/postprocess path.
PROBE_HW = 32


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


def integrity_enabled() -> bool:
    """Master switch (default ON): readiness verification + periodic
    probe/attest. `SPOTTER_TPU_INTEGRITY=0` disables the whole plane."""
    return os.environ.get(INTEGRITY_ENV, "1").strip() not in ("", "0")


def probe_image(family: str, size: int = PROBE_HW):
    """Deterministic probe image for a model family: a fixed arithmetic
    pixel pattern seeded by the family name. Built directly as a PIL
    array — never through an encoder — so the SAME bytes reach the
    engine on every platform, every process, every restart (a lossy
    JPEG round-trip would vary with codec build and sink the pinned
    references)."""
    import hashlib

    import numpy as np
    from PIL import Image

    seed = hashlib.blake2b(family.encode(), digest_size=2).digest()
    s0, s1 = seed[0], seed[1]
    y = np.arange(size, dtype=np.uint32)[:, None, None]
    x = np.arange(size, dtype=np.uint32)[None, :, None]
    c = np.arange(3, dtype=np.uint32)[None, None, :]
    arr = ((x * (3 + s0) + y * (7 + s1) + c * 11 + s0) % 256).astype("uint8")
    return Image.fromarray(arr, "RGB")


# Pinned reference answers per model family. The stub family's entry is
# the contract the model-free drills and the chaos matrix assert against:
# it pins BOTH the probe-image rule above AND the stub's content-hash
# detection rule (testing/stub_engine.py) — if either drifts, the probe
# fails loudly instead of the integrity plane silently verifying nothing.
# Real model families self-pin at the verifying gate (references captured
# after attestation passes) because their answers depend on checkpoint
# bytes this repo does not pin.
PROBE_REFERENCES: dict[str, list[dict]] = {
    "stub": [{"label": "tv", "score": 0.89, "box": [6.0, 6.0, 24.0, 28.0]}],
}


class GoldenProbe:
    """Golden-probe canary: ask the REAL serving path the question we
    already know the answer to, through the real batcher (bulk class,
    cache/coalescing-bypassed via `key=None`)."""

    def __init__(
        self,
        family: str,
        reference: Optional[list[dict]] = None,
        score_tol: float = compare.DEFAULT_SCORE_TOL,
        box_tol: float = compare.DEFAULT_BOX_TOL,
    ) -> None:
        self.family = family
        self.image = probe_image(family)
        self.reference = (
            list(reference)
            if reference is not None
            else PROBE_REFERENCES.get(family)
        )
        self.score_tol = score_tol
        self.box_tol = box_tol
        self.probes_total = 0
        self.failures_total = 0
        self.last_error: Optional[str] = None

    async def run(self, batcher) -> Optional[str]:
        """One probe through the batcher; None on pass, else the reason.
        `key=None` is load-bearing twice over: keyed submits are the only
        cache-filling path (a probe must never pollute the ResultCache)
        and the only coalescing path (a probe must never attach to a live
        flight and vacuously compare an answer it didn't produce)."""
        self.probes_total += 1
        try:
            dets = await batcher.submit(self.image, key=None, cls=BULK)
        except Exception as exc:  # a probe that can't run is a failure
            self.failures_total += 1
            self.last_error = f"probe submit failed: {exc!r}"
            return self.last_error
        if faults.take_corrupt_compile_cache():
            # miscompiled-restore chaos seam: weights attest clean but the
            # program computes garbage — only this probe can catch it
            dets = faults.perturb_detections(dets)
        if self.reference is None:
            # self-pin (families without a registry entry): trusted because
            # the verifying gate runs attestation BEFORE the first probe
            self.reference = [dict(d) for d in dets if isinstance(d, dict)]
            logger.info(
                "golden probe self-pinned %d reference detections for %r",
                len(self.reference), self.family,
            )
            return None
        reason = compare.diff_detections(
            self.reference, dets,
            score_tol=self.score_tol, box_tol=self.box_tol,
        )
        if reason is not None:
            self.failures_total += 1
            self.last_error = reason
        return reason

    def snapshot(self) -> dict:
        return {
            "family": self.family,
            "pinned": self.reference is not None,
            "probes_total": self.probes_total,
            "failures_total": self.failures_total,
            "last_error": self.last_error,
        }


class WeightsAttestor:
    """On-device weights attestation driver around `engine.attest()`."""

    def __init__(self, engine) -> None:
        self.engine = engine
        self.attests_total = 0
        self.failures_total = 0
        self.last_error: Optional[str] = None
        self.last_duration_s: float = 0.0

    def attest(self) -> Optional[str]:
        """One attestation; None on pass, else the reason (naming the
        mismatched shard locations)."""
        self.attests_total += 1
        t0 = time.monotonic()
        try:
            result = self.engine.attest()
        except Exception as exc:
            self.last_duration_s = time.monotonic() - t0
            self.failures_total += 1
            self.last_error = f"attestation errored: {exc!r}"
            return self.last_error
        self.last_duration_s = time.monotonic() - t0
        if result.get("ok"):
            return None
        self.failures_total += 1
        self.last_error = (
            f"weights checksum mismatch on {result.get('mismatched')} "
            f"(digest {getattr(self.engine, 'weights_digest', lambda: '?')()})"
        )
        return self.last_error

    def snapshot(self) -> dict:
        return {
            "attests_total": self.attests_total,
            "failures_total": self.failures_total,
            "last_duration_s": round(self.last_duration_s, 6),
            "last_error": self.last_error,
        }


class IntegrityPlane:
    """Probe + attestation behind the `verifying` readiness gate and a
    periodic re-verification loop. `exit_cb` (default `os._exit`) is the
    86 path; tests inject a recorder."""

    def __init__(
        self,
        engine,
        batcher,
        family: Optional[str] = None,
        probe_interval_s: Optional[float] = None,
        attest_interval_s: Optional[float] = None,
        exit_cb: Callable[[int], None] = os._exit,
    ) -> None:
        if family is None:
            built = getattr(engine, "built", None)
            family = getattr(built, "model_name", None) or "stub"
        self.engine = engine
        self.batcher = batcher
        self.probe = GoldenProbe(family)
        self.attestor = WeightsAttestor(engine)
        self.probe_interval_s = (
            _env_float(PROBE_INTERVAL_ENV, DEFAULT_PROBE_INTERVAL_S)
            if probe_interval_s is None
            else probe_interval_s
        )
        self.attest_interval_s = (
            _env_float(ATTEST_INTERVAL_ENV, DEFAULT_ATTEST_INTERVAL_S)
            if attest_interval_s is None
            else attest_interval_s
        )
        self.exit_cb = exit_cb
        self.verifications_total = 0
        self.verification_failures_total = 0
        self.last_verify_s: float = 0.0
        self.last_error: Optional[str] = None
        self._task: Optional[asyncio.Task] = None

    async def verify(self, source: str) -> bool:
        """The `verifying` gate: attestation first (the weights vouch for
        the probe's self-pin), then the golden probe through the real
        batcher. Runs on cold start, warm compile-cache restore, OOM
        downgrade, and degraded-dp rebuild (`source` says which)."""
        self.verifications_total += 1
        # two set-up phases (`setup_phases_s` in /metrics)
        with obs.span("setup.attest") as attest:
            reason = self.attestor.attest()
        self.last_verify_s = attest.seconds
        if reason is None:
            with obs.span("setup.ready_probe") as probe:
                reason = await self.probe.run(self.batcher)
            self.last_verify_s += probe.seconds
        if reason is None:
            logger.info(
                "integrity verification passed (%s): attest+probe in %.3fs",
                source, self.last_verify_s,
            )
            return True
        self.verification_failures_total += 1
        self.last_error = f"{source}: {reason}"
        logger.error("integrity verification FAILED (%s): %s", source, reason)
        self._pin_trace(source, reason)
        return False

    def verify_blocking(self, source: str) -> bool:
        """Sync wrapper for non-async callers (the batcher's degraded-
        rebuild thread). Attestation runs inline; the probe is submitted
        onto the batcher's own loop and awaited from this thread."""
        reason = self.attestor.attest()
        if reason is None:
            loop = getattr(self.batcher, "_loop", None)
            if loop is not None and loop.is_running():
                fut = asyncio.run_coroutine_threadsafe(
                    self.probe.run(self.batcher), loop
                )
                reason = fut.result(timeout=60.0)
            else:
                reason = asyncio.run(self.probe.run(self.batcher))
        self.verifications_total += 1
        if reason is None:
            return True
        self.verification_failures_total += 1
        self.last_error = f"{source}: {reason}"
        logger.error("integrity verification FAILED (%s): %s", source, reason)
        self._pin_trace(source, reason)
        return False

    def _pin_trace(self, source: str, reason: str) -> None:
        """Pin a flight-recorder trace so the post-exit dump says WHAT
        disagreed, not just that something did."""
        try:
            trace = obs.begin_trace(request_id=f"integrity-{source}")
            trace.set_error(f"integrity: {reason}")
            obs.get_recorder().record(trace)
        except Exception:
            logger.debug("could not pin integrity trace", exc_info=True)

    def integrity_exit(self, reason: str) -> None:
        """The 86 path: dump the flight recorder, then exit. The
        supervisor cold-restarts us with the compile-cache dir
        quarantined."""
        logger.error(
            "integrity failure (%s); exiting %d for a cold restart with "
            "the compile cache quarantined", reason, INTEGRITY_EXIT_CODE,
        )
        from spotter_tpu.obs.recorder import dump_for_exit

        dump_for_exit(INTEGRITY_EXIT_CODE)
        self.exit_cb(INTEGRITY_EXIT_CODE)

    async def start(self) -> None:
        """Start the periodic re-verification loop (probe and attest on
        their own cadences; either interval <= 0 disables that check)."""
        if self._task is None:
            self._task = asyncio.create_task(self._run())

    async def _run(self) -> None:
        now = time.monotonic()
        next_probe = (
            now + self.probe_interval_s if self.probe_interval_s > 0 else None
        )
        next_attest = (
            now + self.attest_interval_s
            if self.attest_interval_s > 0
            else None
        )
        while next_probe is not None or next_attest is not None:
            due = min(t for t in (next_probe, next_attest) if t is not None)
            await asyncio.sleep(max(due - time.monotonic(), 0.01))
            reason = None
            source = None
            if next_attest is not None and time.monotonic() >= next_attest:
                next_attest = time.monotonic() + self.attest_interval_s
                source = "periodic-attest"
                reason = await asyncio.get_running_loop().run_in_executor(
                    None, self.attestor.attest
                )
            if (
                reason is None
                and next_probe is not None
                and time.monotonic() >= next_probe
            ):
                next_probe = time.monotonic() + self.probe_interval_s
                source = "periodic-probe"
                reason = await self.probe.run(self.batcher)
            if reason is not None:
                self.verification_failures_total += 1
                self.last_error = f"{source}: {reason}"
                self._pin_trace(source or "periodic", reason)
                self.integrity_exit(self.last_error)
                return

    async def aclose(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass
            self._task = None

    def snapshot(self) -> dict:
        return {
            "verifications_total": self.verifications_total,
            "verification_failures_total": self.verification_failures_total,
            "last_verify_s": round(self.last_verify_s, 6),
            "last_error": self.last_error,
            "probe": self.probe.snapshot(),
            "attest": self.attestor.snapshot(),
        }


class QuorumSampler:
    """Edge quorum sampling: dual-dispatch a sampled slice of live
    traffic to a second ranked replica and compare (the inverse of a
    hedge — same transport, but disagreement is the signal, not
    latency). Disagreements are arbitrated by a third replica when one
    exists, so only the DEVIANT's EWMA is charged; a replica whose EWMA
    crosses the threshold is hard-quarantined out of the ring."""

    def __init__(
        self,
        pool,
        pct: Optional[float] = None,
        ewma_threshold: Optional[float] = None,
        min_samples: Optional[int] = None,
        alpha: Optional[float] = None,
        score_tol: float = compare.DEFAULT_SCORE_TOL,
        box_tol: float = compare.DEFAULT_BOX_TOL,
    ) -> None:
        self.pool = pool
        if pct is None:
            pct = _env_float(QUORUM_PCT_ENV, DEFAULT_QUORUM_PCT)
        self.pct = min(max(float(pct), 0.0), 100.0)
        self.ewma_threshold = (
            _env_float(QUORUM_EWMA_ENV, DEFAULT_QUORUM_EWMA)
            if ewma_threshold is None
            else ewma_threshold
        )
        self.min_samples = (
            _env_int(QUORUM_MIN_SAMPLES_ENV, DEFAULT_QUORUM_MIN_SAMPLES)
            if min_samples is None
            else min_samples
        )
        self.alpha = (
            _env_float(QUORUM_ALPHA_ENV, DEFAULT_QUORUM_ALPHA)
            if alpha is None
            else alpha
        )
        self.score_tol = score_tol
        self.box_tol = box_tol
        self._credit = 0.0
        self._ewma: dict[str, float] = {}
        self._samples: dict[str, int] = {}
        self.samples_total = 0
        self.compared_total = 0
        self.disagreements_total = 0
        self.arbitrations_total = 0
        self.errors_total = 0
        self.quarantines_total = 0

    def take(self) -> bool:
        """Deterministic Bresenham sampling, like the shadow lane and the
        flaky fault — drills assert exact shares, so no RNG."""
        if self.pct <= 0:
            return False
        self._credit += self.pct
        if self._credit >= 100.0:
            self._credit -= 100.0
            return True
        return False

    async def _ask(self, client, url: str, payload: dict) -> Optional[dict]:
        try:
            resp = await client.post(f"{url}/detect", json=payload)
            if resp.status_code != 200:
                return None
            return resp.json()
        except Exception:
            return None

    def _charge(self, url: str, disagreed: bool) -> None:
        prev = self._ewma.get(url, 0.0)
        self._ewma[url] = prev * (1.0 - self.alpha) + (
            self.alpha if disagreed else 0.0
        )
        self._samples[url] = self._samples.get(url, 0) + 1

    def _maybe_quarantine(self, url: str) -> None:
        if self._samples.get(url, 0) < self.min_samples:
            return
        if self._ewma.get(url, 0.0) < self.ewma_threshold:
            return
        reason = (
            f"quorum disagreement ewma {self._ewma[url]:.2f} >= "
            f"{self.ewma_threshold} over {self._samples[url]} samples"
        )
        if not self.pool.quarantine(url, reason=reason):
            return
        self.quarantines_total += 1
        try:
            from spotter_tpu import obs

            trace = obs.begin_trace(request_id=f"integrity-quarantine-{url}")
            trace.set_error(f"hard quarantine: {reason}")
            obs.get_recorder().record(trace)
        except Exception:
            logger.debug("could not pin quarantine trace", exc_info=True)

    async def run_one(
        self, client, payload: dict, primary_body, primary_url: str
    ) -> None:
        """One sampled comparison: ask a second ranked replica the same
        question, compare with the tolerance comparator, arbitrate
        disagreements with a third opinion. Everything here is contained:
        nothing on this lane can surface to a client."""
        import json as _json

        self.samples_total += 1
        witness_url = self.pool.pick_other(exclude=(primary_url,))
        if witness_url is None:
            return
        witness = await self._ask(client, witness_url, payload)
        if witness is None:
            self.errors_total += 1
            return
        try:
            primary = (
                _json.loads(primary_body)
                if isinstance(primary_body, (bytes, bytearray, str))
                else primary_body
            )
            primary_images = primary.get("images")
        except Exception:
            return  # uncomparable primary (frame body): skipped, not charged
        self.compared_total += 1
        agree = compare.images_equivalent(
            primary_images, witness.get("images"),
            score_tol=self.score_tol, box_tol=self.box_tol,
        )
        if agree:
            self._charge(primary_url, False)
            self._charge(witness_url, False)
            return
        self.disagreements_total += 1
        arbiter_url = self.pool.pick_other(
            exclude=(primary_url, witness_url)
        )
        arbiter = (
            await self._ask(client, arbiter_url, payload)
            if arbiter_url is not None
            else None
        )
        if arbiter is not None:
            self.arbitrations_total += 1
            arb_images = arbiter.get("images")
            primary_ok = compare.images_equivalent(
                primary_images, arb_images,
                score_tol=self.score_tol, box_tol=self.box_tol,
            )
            witness_ok = compare.images_equivalent(
                witness.get("images"), arb_images,
                score_tol=self.score_tol, box_tol=self.box_tol,
            )
            if primary_ok and not witness_ok:
                self._charge(primary_url, False)
                self._charge(witness_url, True)
            elif witness_ok and not primary_ok:
                self._charge(primary_url, True)
                self._charge(witness_url, False)
            else:
                # arbiter agreed with both (tolerance chains) or neither:
                # no majority — charge both, the EWMA sorts out repeats
                self._charge(primary_url, True)
                self._charge(witness_url, True)
        else:
            # no third replica: a 2-fleet can't attribute — charge both
            self._charge(primary_url, True)
            self._charge(witness_url, True)
        self._maybe_quarantine(primary_url)
        self._maybe_quarantine(witness_url)

    def snapshot(self) -> dict:
        return {
            "pct": self.pct,
            "samples_total": self.samples_total,
            "compared_total": self.compared_total,
            "disagreements_total": self.disagreements_total,
            "arbitrations_total": self.arbitrations_total,
            "errors_total": self.errors_total,
            "quarantines_total": self.quarantines_total,
            "ewma": {
                url: round(v, 4) for url, v in sorted(self._ewma.items())
            },
        }
