"""Serving metrics: images/sec and latency percentiles.

The reference has no metrics endpoint (SURVEY.md §5.5); the north-star targets
(BASELINE.json: >=2000 img/s, p50 < 40 ms) make them mandatory here. Lock-light
counters + a bounded reservoir; snapshot() is what /metrics serves.
"""

import os
import socket
import threading
import time
from collections import deque

from spotter_tpu.obs import trace as obs_trace
from spotter_tpu.obs.perf import PerfLedger

# Cumulative-histogram bucket bounds (ms) for batch latency — the
# Prometheus-exposition view (ISSUE 7) renders these as
# spotter_tpu_latency_ms_bucket{le="..."} with trace-id exemplars, so a
# tail bucket links straight to the flight-recorder trace that landed in
# it. The JSON snapshot carries them additively under
# "latency_ms_histogram"; every pre-existing field is unchanged.
LATENCY_BUCKETS_MS = (
    5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
    float("inf"),
)

# Per-stage bucket bounds (ms) for the MERGEABLE stage histograms
# (ISSUE 12): the point p50/p90/p99 stage summaries cannot be aggregated
# across replicas (an average of medians is not a fleet median), so every
# snapshot also carries raw cumulative bucket counts per stage. Finer than
# the batch-latency ladder — stage slices (h2d, postprocess) are routinely
# sub-millisecond.
STAGE_BUCKETS_MS = (
    0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
    2500.0, float("inf"),
)

REPLICA_ID_ENV = "SPOTTER_TPU_REPLICA_ID"
# Deployment version identity (ISSUE 15): the build/version tag this
# replica is serving, stamped into the snapshot identity block, /healthz,
# and the X-Spotter-Version response header. The rollout controller keys
# canary-vs-baseline cohorts (and the pool keys replay/hedge pinning) on
# exactly this string, so set it per deploy (image tag, git sha, model
# rev). Unset -> "dev".
BUILD_VERSION_ENV = "SPOTTER_TPU_BUILD_VERSION"
WEIGHTS_DIGEST_ENV = "SPOTTER_TPU_WEIGHTS_DIGEST"
DEFAULT_BUILD_VERSION = "dev"


def _median(ring) -> float | None:
    """Median of a sample deque, None when empty (prom skips None)."""
    if not ring:
        return None
    vals = sorted(ring)
    return vals[len(vals) // 2]


def default_replica_id() -> str:
    """Stable-per-process replica identity: the env override wins (fleet
    operators can pin pod names), else host:pid — unique across a fleet
    and across restarts on one host."""
    rid = os.environ.get(REPLICA_ID_ENV, "").strip()
    if rid:
        return rid
    try:
        host = socket.gethostname() or "localhost"
    except OSError:
        host = "localhost"
    return f"{host}:{os.getpid()}"


def default_build_version() -> str:
    """The deploy version this process serves (env, else "dev")."""
    return os.environ.get(BUILD_VERSION_ENV, "").strip() or DEFAULT_BUILD_VERSION


def default_weights_digest() -> str | None:
    """Operator-pinned weights digest, or None until an engine stamps one."""
    return os.environ.get(WEIGHTS_DIGEST_ENV, "").strip() or None


class ControlPlaneMetrics:
    """Counters for the crash-safe control plane (ISSUE 16): how often the
    reconcile loop ran, what it adopted instead of double-spawning, what
    fencing refused, and how far observed capacity sits from desired.

    Single-threaded by design (the reconciler is event-loop-confined like
    the fleet controller), so these are plain ints — no locks. `drift` is
    the prom-labeled gauge ({pool: desired - ready}); `drift_detail`
    carries the desired/ready split for /healthz and fleet_top."""

    def __init__(self) -> None:
        self.reconcile_loops_total = 0
        self.adoptions_total = 0
        self.fencing_rejections_total = 0
        self.journal_rebuilds_total = 0
        self.manifest_pruned_total = 0
        self.spawns_total = 0
        self.rollout_resumes_total = 0
        self.drift: dict[str, int] = {}
        self.drift_detail: dict[str, dict] = {}

    def set_drift(self, drift: dict, detail: dict | None = None) -> None:
        self.drift = dict(drift)
        if detail is not None:
            self.drift_detail = detail

    def snapshot(self) -> dict:
        return {
            "reconcile_loops_total": self.reconcile_loops_total,
            "adoptions_total": self.adoptions_total,
            "fencing_rejections_total": self.fencing_rejections_total,
            "journal_rebuilds_total": self.journal_rebuilds_total,
            "manifest_pruned_total": self.manifest_pruned_total,
            "spawns_total": self.spawns_total,
            "rollout_resumes_total": self.rollout_resumes_total,
            "drift": dict(self.drift),
            "drift_detail": {
                k: dict(v) for k, v in self.drift_detail.items()
            },
            "drift_total": sum(abs(v) for v in self.drift.values()),
            "converged": all(v == 0 for v in self.drift.values()),
        }


# Set-up phases are spans like any other (`obs.span("setup.weights_load")`):
# the snapshot shows the rows under this prefix as one dict of seconds.
SETUP_SPAN_PREFIX = "setup."


def setup_phases_s(host_spans: dict | None = None) -> dict:
    """phase -> seconds, from the span table: `imports` (process start to
    bring-up), `weights_load`, `engine_place`, `warmup` (all of it; inside
    it, per bucket, `warmup.<shape>`: compile or cache load with the first
    run, and `flops.<shape>`: the FLOPs ledger's second lowering), `attest`,
    `ready_probe`."""
    if host_spans is None:
        host_spans = obs_trace.host_spans_snapshot()
    return {
        name[len(SETUP_SPAN_PREFIX):]: round(row["wall_ms"] / 1e3, 3)
        for name, row in host_spans.items()
        if name.startswith(SETUP_SPAN_PREFIX)
    }


class StarvationClock:
    """Why the chip had nothing to do (ISSUE 26): seconds in which the
    engine had no program dispatched and not yet fetched, split by what the
    host was doing meanwhile. `staging`: at least one batch between the
    start of `_stage_host` and the return of `_dispatch` (the host was
    preparing the chip's next work). `upstream`: nothing staging, and at
    least one image inside `Detector._process_single_image` (in fetch, PIL
    decode or the batcher's queue, or its reply being drawn and encoded).
    With nothing anywhere the house is empty and no clock runs. Both are
    lower bounds of the device's idle time: a result reaches the host a D2H
    after the device ends. Counted by the engine and the detector at their
    own boundaries, so the numbers exist in every run, traced or not."""

    def __init__(self, clock=time.monotonic) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self._in_flight = 0
        self._staging = 0
        self._upstream = 0
        self._since = clock()
        self._staging_s = 0.0
        self._upstream_s = 0.0

    def _settle(self) -> None:
        """Book the time since the last change (caller holds the lock)."""
        now = self._clock()
        if self._in_flight == 0:
            if self._staging > 0:
                self._staging_s += now - self._since
            elif self._upstream > 0:
                self._upstream_s += now - self._since
        self._since = now

    def move(self, in_flight: int = 0, staging: int = 0, upstream: int = 0) -> None:
        with self._lock:
            self._settle()
            self._in_flight += in_flight
            self._staging += staging
            self._upstream += upstream

    def totals(self) -> tuple[float, float]:
        """(starved while staging, starved on upstream), seconds so far."""
        with self._lock:
            self._settle()
            return self._staging_s, self._upstream_s


class Metrics:
    def __init__(self, window: int = 2048) -> None:
        self._lock = threading.Lock()
        self._latencies_ms: deque[float] = deque(maxlen=window)
        self._latency_bucket_counts = [0] * len(LATENCY_BUCKETS_MS)
        self._latency_sum_ms = 0.0
        self._latency_count = 0
        # le -> {"trace_id", "value", "ts"}: the most recent traced batch
        # to land in each bucket (OpenMetrics exemplar shape)
        self._latency_exemplars: dict[str, dict] = {}
        self._images_total = 0
        self._errors_total = 0
        self._batches_total = 0
        self._batch_sizes: deque[int] = deque(maxlen=window)
        # per-bucket batch counts and the slots they ran (ISSUE 26): images
        # over slots is the fill of the ladder, from the counters alone
        self._bucket_batches: dict[int, int] = {}
        self._slots_total = 0
        # routed-expert layers (PR 28): what the program counted of its own
        # routing, per batch, over the images the batch really held
        self._moe = {"assignments": 0, "assignments_local": 0, "bias_moved": 0,
                     "expert_tokens_max": 0, "expert_tokens_mean": 0.0}
        # per-channel gates of a delta-rule mixer (PR 35): how far apart a
        # head's channels decay, summed over the heads counted beside it
        self._kda = {"gate_spread": 0.0, "gate_heads": 0}
        # host staging slabs (ISSUE 27): leases, and how many of them found
        # the free-list empty and allocated; the rest reused a slab
        self._slab_leases_total = 0
        self._slab_allocs_total = 0
        self.starvation = StarvationClock()
        self._started = time.monotonic()
        # (timestamp, batch_size) ring for rate computation — snapshot() reads
        # it without mutating shared state, so concurrent scrapers don't
        # corrupt each other's view
        self._arrivals: deque[tuple[float, int]] = deque(maxlen=window)
        self._stages: dict[str, deque[float]] = {}
        # Mergeable stage state (ISSUE 12): name -> [bucket_counts, sum,
        # count]. Cumulative (never windowed) so fleet aggregation adds
        # bucket counts across replicas exactly like Prometheus would.
        self._stage_hist: dict[str, list] = {}
        # Replica identity stamp (ISSUE 12): every snapshot carries who
        # produced it, so cross-replica aggregation, staleness tracking,
        # and restart detection (generation bump => counter reset) are
        # principled rather than heuristic. Generation defaults to the
        # supervisor's restart count (set_restarts); the model name is
        # stamped by the serving bootstrap once it knows it.
        self._replica_id = default_replica_id()
        self._model: str | None = None
        self._generation = 0
        # Deployment identity (ISSUE 15): build version + weights digest —
        # what the rollout verdict and mixed-version request pinning key on
        self._version = default_build_version()
        self._weights_digest = default_weights_digest()
        # Resilience counters (ISSUE 1): overload shedding, deadline expiry,
        # watchdog batch timeouts, breaker state/transitions, drain state.
        self._shed_total = 0
        self._deadline_exceeded_total = 0
        self._batch_timeouts_total = 0
        self._breaker_state = "closed"
        self._breaker_transitions_total = 0
        self._draining = False
        # Replica-lifecycle gauges (ISSUE 2): process-start -> ready (warm
        # restart evidence) and how many times the supervisor has restarted
        # this replica (set from SPOTTER_TPU_RESTARTS at bootstrap). Both
        # live on the Metrics object, so they survive a drain/restart of the
        # batcher — only a process death resets them.
        self._time_to_ready_s: float | None = None
        self._restarts_total = 0
        # Ingest-pipeline observability (ISSUE 3): host->device transfer
        # volume (the quantity SPOTTER_TPU_DEVICE_PREPROCESS exists to cut),
        # how many images that volume staged (-> bytes/image), the decode
        # pool's backlog, and the batcher's aggregate dispatch bucket
        # (dp × per-chip bucket under dp-sharded serving).
        self._h2d_bytes_total = 0
        self._h2d_images_total = 0
        self._decode_queue_depth = 0
        self._aggregate_bucket = 0
        # Engine fault domain (ISSUE 4): poison items isolated by the
        # bisect-retry, batch retries it (and the OOM bucket-downgrade)
        # spent, fatal device errors seen, in-place engine rebuilds, and the
        # current degraded-dp shape ({"from": n, "to": m} once a shard has
        # been lost; None while serving at full width).
        self._poison_isolated_total = 0
        self._batch_retries_total = 0
        self._fatal_engine_errors_total = 0
        self._engine_rebuilds_total = 0
        self._dp_degraded: dict | None = None
        # Caching tier (ISSUE 5): result-cache hit/miss/negative-hit,
        # single-flight coalescing at the two layers (URL-level fetch,
        # content-hash-level engine submit), eviction count, and the cache's
        # current size (entries + bytes, published by ResultCache on fill).
        self._cache_hits_total = 0
        self._cache_misses_total = 0
        self._cache_negative_hits_total = 0
        self._cache_evictions_total = 0
        self._coalesced_fetches_total = 0
        self._coalesced_submits_total = 0
        self._cache_entries = 0
        self._cache_bytes = 0
        # Overload-control tier (ISSUE 8): the AIMD limiter's current
        # limit/in-flight (None limit while the tier is off, so the JSON
        # view shows "unarmed" rather than a misleading 0), per-class
        # admission sheds, the brownout ladder's rung gauge + transition
        # counter, and how many responses were served from expired-TTL
        # cache entries under the stale rung.
        self._admit_limit: float | None = None
        self._admit_in_flight = 0
        self._admit_sheds_total = {"slo": 0, "bulk": 0}
        self._brownout_rung = 0
        self._brownout_transitions_total = 0
        self._stale_served_total = 0
        # Ragged scheduling (ISSUE 9): per-dispatch padded-pixel waste (the
        # quantity ragged packing exists to cut — measured in FIFO mode too,
        # so the per-bucket baseline is observable), per-item deadline slack
        # remaining at dispatch (the slack-ordering control signal), and how
        # many dispatches actually used a ragged canvas.
        self._padding_waste_pct: deque[float] = deque(maxlen=window)
        self._slack_at_dispatch_ms: deque[float] = deque(maxlen=window)
        self._ragged_packs_total = 0
        # Edge data plane (ISSUE 11): bytes on the /detect wire in each
        # direction plus how many responses went out as binary frames vs
        # default JSON — the measured substrate for the ≥25% bytes-per-
        # request claim (wire_bytes_out_per_request in snapshot()).
        self._wire_bytes_in_total = 0
        self._wire_bytes_out_total = 0
        self._wire_requests_total = 0
        self._wire_frame_responses_total = 0
        self._wire_json_responses_total = 0
        # Open-vocabulary text-embedding cache (ISSUE 13): hit/miss counts
        # and resolve wall times — the "repeated vocabularies cost one
        # encode" claim's measured substrate (hit p50 must sit far under
        # miss p50, which carries the text-tower forward).
        self._text_cache_hits_total = 0
        self._text_cache_misses_total = 0
        self._text_hit_ms: deque[float] = deque(maxlen=window)
        self._text_miss_ms: deque[float] = deque(maxlen=window)
        # Device-efficiency plane (ISSUE 10): MFU/duty-cycle accounting,
        # compile ledger, HBM gauges, and SLO burn-rate. The ledger is
        # stdlib-only and owns its own lock; the engine feeds dispatches
        # and compiles directly (`metrics.perf.record_dispatch(...)`),
        # while the SLO burn windows are fed from the request-level
        # counters below (completed images = good, sheds + deadline
        # misses = bad). `SPOTTER_TPU_PERF_LEDGER=0` makes every perf
        # record a no-op while keeping the snapshot keys present.
        self.perf = PerfLedger()

    def record_program_counters(self, counters: dict) -> None:
        """What a program counted of itself, by the name it returned it under
        (`engine.PROGRAM_COUNTERS`), over the images a batch really held."""
        if "moe_expert_tokens" in counters:
            self.record_moe(counters["moe_expert_tokens"], counters["moe_assignments"],
                            counters.get("moe_bias_moved"))
        spread = counters.get("kda_gate_spread")
        if spread is not None:  # (images, layers, heads): a token-mean of max - min of -g
            with self._lock:
                self._kda["gate_spread"] += float(spread.sum())
                self._kda["gate_heads"] += int(spread.size)

    def record_moe(self, expert_tokens, assignments, bias_moved=None) -> None:
        """`expert_tokens`: (images, layers, held experts), the tokens of each
        image that each held expert of each layer took; `assignments`:
        (images, layers), each image's tokens times k; `bias_moved` (images,
        layers), from a router that chooses with a bias: the selections that
        are not among the k best of the unbiased scores. Per batch and layer the
        fullest held expert's tokens and the mean over the held experts are
        added up, so that their quotient over a window is the imbalance the
        grouped product saw."""
        per_layer = expert_tokens.sum(axis=0)  # (layers, held): one batch's groups
        with self._lock:
            self._moe["assignments"] += int(assignments.sum())
            self._moe["assignments_local"] += int(per_layer.sum())
            if bias_moved is not None:
                self._moe["bias_moved"] += int(bias_moved.sum())
            self._moe["expert_tokens_max"] += int(per_layer.max(axis=-1).sum())
            self._moe["expert_tokens_mean"] += float(per_layer.mean(axis=-1).sum())

    def record_batch(
        self,
        batch_size: int,
        latency_s: float,
        stages: dict[str, float] | None = None,
        trace_id: str | None = None,
        bucket: int | None = None,
    ) -> None:
        """`stages`: optional per-stage seconds keyed by the obs.STAGES
        vocabulary (decode/h2d/device/postprocess) — the breakdown
        SURVEY.md §5.1 calls for. `trace_id` (when the batch carried a
        traced request) becomes the exemplar on the latency-histogram
        bucket this batch landed in. `bucket`: the ladder rung the batch
        was padded to (the slots the program ran)."""
        latency_ms = latency_s * 1000.0
        with self._lock:
            self._images_total += batch_size
            self._batches_total += 1
            if bucket is not None:
                self._bucket_batches[bucket] = (
                    self._bucket_batches.get(bucket, 0) + 1
                )
                self._slots_total += bucket
            self._batch_sizes.append(batch_size)
            self._latencies_ms.append(latency_ms)
            self._latency_sum_ms += latency_ms
            self._latency_count += 1
            for i, le in enumerate(LATENCY_BUCKETS_MS):
                if latency_ms <= le:
                    self._latency_bucket_counts[i] += 1
                    if trace_id is not None:
                        key = "+Inf" if le == float("inf") else f"{le:g}"
                        self._latency_exemplars[key] = {
                            "trace_id": trace_id,
                            "value": latency_ms,
                            "ts": time.time(),
                        }
                    break
            self._arrivals.append((time.monotonic(), batch_size))
            # SLO burn (ISSUE 10): completed images are good events (the
            # enabled gate keeps SPOTTER_TPU_PERF_LEDGER=0 a true no-op)
            if self.perf.enabled:
                self.perf.slo.good(batch_size)
            if stages:
                for name, secs in stages.items():
                    ring = self._stages.get(name)
                    if ring is None:
                        ring = self._stages[name] = deque(
                            maxlen=self._latencies_ms.maxlen
                        )
                    ms = secs * 1000.0
                    ring.append(ms)
                    self._stage_hist_observe(name, ms)

    def record_error(self, n: int = 1) -> None:
        with self._lock:
            self._errors_total += n

    def record_shed(self, n: int = 1) -> None:
        """A request rejected at admission (queue full / breaker open / drain)."""
        with self._lock:
            self._shed_total += n
        if self.perf.enabled:  # sheds spend SLO error budget (ISSUE 10)
            self.perf.slo.bad(n)

    def record_deadline_exceeded(self, n: int = 1) -> None:
        with self._lock:
            self._deadline_exceeded_total += n
        if self.perf.enabled:  # deadline misses spend SLO error budget
            self.perf.slo.bad(n)

    def record_batch_timeout(self, n_images: int) -> None:
        """Watchdog fired on a hung engine call; images count as errors too."""
        with self._lock:
            self._batch_timeouts_total += 1
            self._errors_total += n_images

    def record_breaker_transition(self, state: str) -> None:
        with self._lock:
            self._breaker_state = state
            self._breaker_transitions_total += 1

    def set_draining(self, draining: bool) -> None:
        with self._lock:
            self._draining = draining

    def record_h2d_bytes(self, nbytes: int, n_images: int) -> None:
        """One staged batch's host->device transfer volume."""
        with self._lock:
            self._h2d_bytes_total += nbytes
            self._h2d_images_total += n_images

    def record_poison_isolated(self, n: int = 1) -> None:
        """n poisonous items isolated to their own futures by bisect-retry."""
        with self._lock:
            self._poison_isolated_total += n

    def record_slab_lease(self, allocated: bool) -> None:
        """One batch leased a host staging slab (engine/staging.py);
        `allocated`: none was free, so a new one was made."""
        with self._lock:
            self._slab_leases_total += 1
            self._slab_allocs_total += bool(allocated)

    def record_batch_retry(self, n: int = 1) -> None:
        """A failed batch was split and retried (poison bisect or OOM downgrade)."""
        with self._lock:
            self._batch_retries_total += n

    def record_fatal_engine_error(self) -> None:
        with self._lock:
            self._fatal_engine_errors_total += 1

    def record_engine_rebuild(self, from_dp: int, to_dp: int) -> None:
        """The engine rebuilt itself in place at a different dp width."""
        with self._lock:
            self._engine_rebuilds_total += 1
            self._dp_degraded = {"from": from_dp, "to": to_dp}

    def record_cache_hit(self, n: int = 1) -> None:
        """A /detect answered from the content-addressed result cache."""
        with self._lock:
            self._cache_hits_total += n

    def record_cache_miss(self, n: int = 1) -> None:
        with self._lock:
            self._cache_misses_total += n

    def record_cache_negative_hit(self, n: int = 1) -> None:
        """A cached deterministic failure (4xx fetch / poison) short-circuited
        the fetch/bisect machinery."""
        with self._lock:
            self._cache_negative_hits_total += n

    def record_cache_eviction(self, n: int = 1) -> None:
        with self._lock:
            self._cache_evictions_total += n

    def record_coalesced_fetch(self, n: int = 1) -> None:
        """A request attached to an in-flight fetch for the same URL."""
        with self._lock:
            self._coalesced_fetches_total += n

    def record_coalesced_submit(self, n: int = 1) -> None:
        """A request attached to an in-flight engine call for the same
        content hash instead of enqueuing its own image."""
        with self._lock:
            self._coalesced_submits_total += n

    def record_wire(self, bytes_in: int, bytes_out: int, frame: bool) -> None:
        """One /detect exchange's bytes on the wire (ISSUE 11): request body
        in, response body out, and which encoding the response used."""
        with self._lock:
            self._wire_bytes_in_total += int(bytes_in)
            self._wire_bytes_out_total += int(bytes_out)
            self._wire_requests_total += 1
            if frame:
                self._wire_frame_responses_total += 1
            else:
                self._wire_json_responses_total += 1

    def record_stage_samples(self, name: str, values_ms: list[float]) -> None:
        """Feed per-item samples into a named stage histogram outside
        `record_batch` (the batcher's queue_wait attribution — ISSUE 8: the
        AIMD limiter's control signal is the same histogram /metrics
        shows). One lock hold for the whole batch."""
        if not values_ms:
            return
        with self._lock:
            ring = self._stages.get(name)
            if ring is None:
                ring = self._stages[name] = deque(
                    maxlen=self._latencies_ms.maxlen
                )
            ring.extend(values_ms)
            for ms in values_ms:
                self._stage_hist_observe(name, ms)

    def _stage_hist_observe(self, name: str, ms: float) -> None:
        """Cumulative per-stage bucket counts (caller holds the lock)."""
        h = self._stage_hist.get(name)
        if h is None:
            h = self._stage_hist[name] = [[0] * len(STAGE_BUCKETS_MS), 0.0, 0]
        counts = h[0]
        for i, le in enumerate(STAGE_BUCKETS_MS):
            if ms <= le:
                counts[i] += 1
                break
        h[1] += ms
        h[2] += 1

    def set_identity(
        self,
        model: str | None = None,
        replica_id: str | None = None,
        generation: int | None = None,
        version: str | None = None,
        weights_digest: str | None = None,
    ) -> None:
        """Stamp the snapshot identity block (ISSUE 12). Only non-None
        fields change, so the bootstrap can stamp the model name without
        clobbering a generation the supervisor already set."""
        with self._lock:
            if model is not None:
                self._model = model
            if replica_id is not None:
                self._replica_id = replica_id
            if generation is not None:
                self._generation = int(generation)
            if version is not None:
                self._version = version
            if weights_digest is not None:
                self._weights_digest = weights_digest

    @property
    def version(self) -> str:
        """The identity stamp's build version (ISSUE 15: echoed as the
        X-Spotter-Version response header at replica and edge)."""
        with self._lock:
            return self._version

    @property
    def replica_id(self) -> str:
        """The identity stamp's replica id (ISSUE 14 satellite: echoed as
        the X-Spotter-Replica response header at replica and edge)."""
        with self._lock:
            return self._replica_id

    def set_admit_state(self, limit: int, in_flight: int) -> None:
        """The AIMD limiter publishes its state on every control tick."""
        with self._lock:
            self._admit_limit = limit
            self._admit_in_flight = in_flight

    def record_admit_shed(self, cls: str, n: int = 1) -> None:
        """A request shed (or revoked) by the adaptive limiter, by class."""
        with self._lock:
            if cls not in self._admit_sheds_total:
                cls = "slo"
            self._admit_sheds_total[cls] += n

    def admit_sheds_count(self) -> int:
        """Cheap all-classes shed count (no full snapshot): the brownout
        saturation signal polls this — demand that is being SHED is still
        demand, so the ladder must not read a shed-quiet queue as calm."""
        with self._lock:
            return sum(self._admit_sheds_total.values())

    def set_brownout_rung(self, rung: int) -> None:
        with self._lock:
            self._brownout_rung = rung

    def record_brownout_transition(self, n: int = 1) -> None:
        with self._lock:
            self._brownout_transitions_total += n

    def record_stale_served(self, n: int = 1) -> None:
        """A response served from an expired-TTL cache entry (brownout
        stale rung) — the `degraded: stale` marker's counter."""
        with self._lock:
            self._stale_served_total += n

    def record_pack(
        self,
        padding_waste_pct: float | None = None,
        slack_ms: list[float] | None = None,
        ragged: bool = False,
    ) -> None:
        """One scheduler dispatch (ISSUE 9): its padded-pixel waste, the
        deadline slack each deadline-carrying item had left at dispatch,
        and whether it staged to a ragged (sub-bucket) canvas."""
        with self._lock:
            if padding_waste_pct is not None:
                self._padding_waste_pct.append(padding_waste_pct)
            if slack_ms:
                self._slack_at_dispatch_ms.extend(slack_ms)
            if ragged:
                self._ragged_packs_total += 1

    def record_text_cache(self, hit: bool, resolve_ms: float | None) -> None:
        """One open-vocab query-set resolve (ISSUE 13): cache outcome plus
        the resolve wall time (a miss's time includes the text-tower
        encode; a hit's is the dict lookup)."""
        with self._lock:
            if hit:
                self._text_cache_hits_total += 1
                if resolve_ms is not None:
                    self._text_hit_ms.append(resolve_ms)
            else:
                self._text_cache_misses_total += 1
                if resolve_ms is not None:
                    self._text_miss_ms.append(resolve_ms)

    def set_cache_size(self, entries: int, nbytes: int) -> None:
        with self._lock:
            self._cache_entries = entries
            self._cache_bytes = nbytes

    def set_decode_queue_depth(self, depth: int) -> None:
        with self._lock:
            self._decode_queue_depth = depth

    def set_aggregate_bucket(self, bucket: int) -> None:
        with self._lock:
            self._aggregate_bucket = bucket

    def set_time_to_ready(self, seconds: float) -> None:
        with self._lock:
            self._time_to_ready_s = seconds

    def set_restarts(self, n: int) -> None:
        with self._lock:
            self._restarts_total = n
            # restart count IS the counter-reset generation: every process
            # restart starts the cumulative counters over from zero, and
            # the fleet aggregator folds the previous generation's totals
            # into its base when it sees this number move (ISSUE 12)
            self._generation = int(n)

    def snapshot(self) -> dict:
        # outside the metrics lock: the perf ledger locks itself, and
        # nesting the two here would be the only place the order matters
        perf_snap = self.perf.snapshot()
        host_spans = obs_trace.host_spans_snapshot()
        starved_staging_s, starved_upstream_s = self.starvation.totals()
        with self._lock:
            lats = sorted(self._latencies_ms)
            now = time.monotonic()
            # rate over the last 30 s of arrivals (read-only)
            recent = [(t, n) for t, n in self._arrivals if now - t <= 30.0]
            if recent:
                span = max(now - recent[0][0], 1e-9)
                images_per_sec = sum(n for _, n in recent) / span
            else:
                images_per_sec = 0.0

            def pct(p: float) -> float:
                if not lats:
                    return 0.0
                return lats[min(int(p * len(lats)), len(lats) - 1)]

            # per-stage histograms (ISSUE 3): p50 alone hid tail behavior in
            # the staging/device stages the new ingest pipeline splits out
            stage_stats = {}
            for name, ring in self._stages.items():
                vals = sorted(ring)
                if vals:
                    for p, tag in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
                        stage_stats[f"stage_{name}_ms_{tag}"] = vals[
                            min(int(p * len(vals)), len(vals) - 1)
                        ]

            # cumulative counts, Prometheus-style: bucket i covers <= le
            cumulative = 0
            buckets = []
            for le, count in zip(LATENCY_BUCKETS_MS, self._latency_bucket_counts):
                cumulative += count
                buckets.append(
                    [None if le == float("inf") else le, cumulative]
                )

            # mergeable stage histograms (ISSUE 12): the raw cumulative
            # bucket counts behind the point summaries above — fleet
            # aggregation adds these across replicas and recomputes the
            # quantiles, instead of averaging averages
            stage_hists = {}
            for name, (counts, total_ms, n) in self._stage_hist.items():
                cum = 0
                sbuckets = []
                for le, c in zip(STAGE_BUCKETS_MS, counts):
                    cum += c
                    sbuckets.append(
                        [None if le == float("inf") else le, cum]
                    )
                stage_hists[name] = {
                    "buckets": sbuckets,
                    "sum": round(total_ms, 3),
                    "count": n,
                }

            # ragged-scheduling stats (ISSUE 9): windowed mean waste + a
            # slack quantile summary (obs/prom.py renders the dict with
            # {quantile="..."} labels)
            waste = (
                sum(self._padding_waste_pct) / len(self._padding_waste_pct)
                if self._padding_waste_pct
                else None
            )
            slacks = sorted(self._slack_at_dispatch_ms)
            slack_summary = (
                {
                    tag: slacks[min(int(p * len(slacks)), len(slacks) - 1)]
                    for p, tag in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99"))
                }
                if slacks
                else None
            )

            return {
                **perf_snap,
                **stage_stats,
                # identity stamp (ISSUE 12): who produced this snapshot —
                # the substrate for fleet aggregation (staleness, restart
                # detection via generation, per-replica labels)
                "replica": {
                    "replica_id": self._replica_id,
                    "pid": os.getpid(),
                    "generation": self._generation,
                    "uptime_s": round(now - self._started, 3),
                    "model": self._model,
                    # deployment identity (ISSUE 15): which build/weights
                    # this replica serves — the rollout verdict's cohort key
                    "version": self._version,
                    "weights_digest": self._weights_digest,
                },
                "stage_ms_histogram": stage_hists,
                # every `obs.span` of the process, by name (ISSUE 26): what
                # the stages above are made of, and the detector's share
                "host_spans": host_spans,
                "setup_phases_s": setup_phases_s(host_spans),
                "bucket_batches_total": {
                    str(b): n for b, n in sorted(self._bucket_batches.items())
                },
                "slots_total": self._slots_total,
                "moe_assignments_total": self._moe["assignments"],
                "moe_assignments_local_total": self._moe["assignments_local"],
                "moe_bias_moved_total": self._moe["bias_moved"],
                "moe_expert_tokens_max_total": self._moe["expert_tokens_max"],
                "moe_expert_tokens_mean_total": round(self._moe["expert_tokens_mean"], 3),
                "kda_gate_spread_total": round(self._kda["gate_spread"], 4),
                "kda_gate_heads_total": self._kda["gate_heads"],
                "staging_slab_leases_total": self._slab_leases_total,
                "staging_slab_allocs_total": self._slab_allocs_total,
                "starved_staging_s_total": round(starved_staging_s, 6),
                "starved_upstream_s_total": round(starved_upstream_s, 6),
                "padding_waste_pct": waste,
                "slack_at_dispatch_ms": slack_summary,
                "ragged_packs_total": self._ragged_packs_total,
                "latency_ms_histogram": {
                    "buckets": buckets,
                    "sum": self._latency_sum_ms,
                    "count": self._latency_count,
                    "exemplars": dict(self._latency_exemplars),
                },
                "h2d_bytes_total": self._h2d_bytes_total,
                "h2d_bytes_per_image": (
                    self._h2d_bytes_total / self._h2d_images_total
                    if self._h2d_images_total
                    else 0.0
                ),
                "decode_pool_queue_depth": self._decode_queue_depth,
                "aggregate_bucket": self._aggregate_bucket,
                "images_total": self._images_total,
                "errors_total": self._errors_total,
                "poison_isolated_total": self._poison_isolated_total,
                "batch_retries_total": self._batch_retries_total,
                "fatal_engine_errors_total": self._fatal_engine_errors_total,
                "engine_rebuilds_total": self._engine_rebuilds_total,
                "dp_degraded": self._dp_degraded,
                "cache_hits_total": self._cache_hits_total,
                "cache_misses_total": self._cache_misses_total,
                "cache_negative_hits_total": self._cache_negative_hits_total,
                "cache_evictions_total": self._cache_evictions_total,
                "coalesced_fetches_total": self._coalesced_fetches_total,
                "coalesced_submits_total": self._coalesced_submits_total,
                "cache_entries": self._cache_entries,
                "cache_bytes": self._cache_bytes,
                "text_cache_hits_total": self._text_cache_hits_total,
                "text_cache_misses_total": self._text_cache_misses_total,
                "text_cache_hit_ms_p50": _median(self._text_hit_ms),
                "text_cache_miss_ms_p50": _median(self._text_miss_ms),
                "wire_bytes_in_total": self._wire_bytes_in_total,
                "wire_bytes_out_total": self._wire_bytes_out_total,
                "wire_requests_total": self._wire_requests_total,
                "wire_frame_responses_total": self._wire_frame_responses_total,
                "wire_json_responses_total": self._wire_json_responses_total,
                "wire_bytes_out_per_request": (
                    self._wire_bytes_out_total / self._wire_requests_total
                    if self._wire_requests_total
                    else 0.0
                ),
                "admit_limit": self._admit_limit,
                "admit_in_flight": self._admit_in_flight,
                "admit_sheds_total": dict(self._admit_sheds_total),
                "brownout_rung": self._brownout_rung,
                "brownout_transitions_total": self._brownout_transitions_total,
                "stale_served_total": self._stale_served_total,
                "shed_total": self._shed_total,
                "deadline_exceeded_total": self._deadline_exceeded_total,
                "batch_timeouts_total": self._batch_timeouts_total,
                "breaker_state": self._breaker_state,
                "breaker_transitions_total": self._breaker_transitions_total,
                "draining": self._draining,
                "time_to_ready_s": self._time_to_ready_s,
                "restarts_total": self._restarts_total,
                "batches_total": self._batches_total,
                "mean_batch_size": (
                    sum(self._batch_sizes) / len(self._batch_sizes) if self._batch_sizes else 0.0
                ),
                "images_per_sec": images_per_sec,
                "latency_ms_p50": pct(0.50),
                "latency_ms_p90": pct(0.90),
                "latency_ms_p99": pct(0.99),
                "uptime_s": now - self._started,
            }
