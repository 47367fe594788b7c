"""Fleet-wide telemetry plane (ISSUE 12): mergeable snapshot math and the
FleetAggregator that scrapes it.

PRs 7 and 10 made one replica deeply observable; every surface was still
per-process. This module is the fleet half, in two layers:

- **Pure merge functions** — `merge_snapshots` and its helpers fold N
  member `/metrics` JSON snapshots into one fleet view with explicit
  semantics per metric class (the DeepServe framing: scaling decisions are
  only as good as the cluster-wide telemetry they consume):

  * counters (`*_total`, histogram bucket counts/sums) ADD;
  * fleet quantiles (latency p50/p99, per-stage summaries) are recomputed
    from the merged raw bucket counts — never averaged member quantiles;
  * SLO burn is recomputed from merged good/bad second-buckets
    (`slo_burn_raw`), fleet MFU/duty from merged window sums (`perf_raw`)
    as sum(flops) / sum(span x peak) — never averaged percentages;
  * additive gauges (goodput, in-flight, HBM bytes) SUM; state gauges
    (brownout rung) take the MAX; per-replica gauges survive unmerged in
    the `per_replica` table, which the Prometheus renderer labels by url.

- **FleetAggregator** — the stateful plane on the edge (router/fleet
  apps): a background task scrapes every member's `/metrics` JSON on
  `SPOTTER_TPU_FLEET_SCRAPE_S` (default 2 s; 0 disables), tracks
  per-replica up/down and staleness (`SPOTTER_TPU_FLEET_STALE_S`), and
  handles counter resets via the snapshot identity stamp: a `generation`
  bump (supervisor restart) — or any counter moving backwards — folds the
  dead generation's last-seen totals into a per-replica base, so fleet
  counters stay monotone and never go negative. Stale/dead members keep
  contributing their counter HISTORY (counters are cumulative facts) but
  drop out of every gauge/rate the moment they go stale — a dead replica
  must not pin fleet goodput or MFU to its last good second. It also
  stitches cross-replica traces: the edge's slowest-K flight-recorder
  traces joined with the owning replica's spans by trace id
  (`/debug/traces?fleet=1`), the "Answer Fast" attribution discipline at
  fleet scope — a fleet number (or a slow fleet request) decomposes back
  to the replica and stage that produced it.

Module layering: stdlib-only at import time (httpx is imported lazily when
a scrape client is first needed), and NOT re-exported from the package
root — `engine.metrics` imports `spotter_tpu.obs.perf`, which initializes
the package, so re-exporting this module (which imports `engine.metrics`
for the bucket bounds) would cycle. Import it explicitly:
`from spotter_tpu.obs import aggregate`.

ROADMAP note: `fleet_snapshot()` is the signal source ROADMAP item 2's
model-multiplexed autoscaler consumes (fleet queue depth, cache-miss rate,
`slo_burn_rate`) and item 5b's autotune oracle reads.
"""

import asyncio
import logging
import math
import os
import threading
import time

from spotter_tpu.engine.metrics import LATENCY_BUCKETS_MS, STAGE_BUCKETS_MS
from spotter_tpu.obs.perf import FAST_WINDOW_S, SLOW_WINDOW_S

logger = logging.getLogger(__name__)

SCRAPE_INTERVAL_ENV = "SPOTTER_TPU_FLEET_SCRAPE_S"
STALE_AFTER_ENV = "SPOTTER_TPU_FLEET_STALE_S"

DEFAULT_SCRAPE_S = 2.0

# additive gauges: a fleet total is the sum over FRESH members
_SUM_GAUGE_KEYS = (
    "images_per_sec",
    "admit_in_flight",
    "cache_entries",
    "cache_bytes",
    "hbm_bytes_in_use",
    "hbm_peak_bytes",
    "hbm_limit_bytes",
    "decode_pool_queue_depth",
    "devices",
)
# state gauges: the fleet is as degraded as its most-degraded fresh member
_MAX_GAUGE_KEYS = ("brownout_rung",)


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


# ---------------------------------------------------------------------------
# pure merge math


def flatten_counters(snap: dict) -> dict[str, float]:
    """The monotonic-counter leaves of one member snapshot, flattened to
    dotted keys. Includes the latency/stage histogram bucket counts, sums
    and counts — cumulative, so they merge (and reset) exactly like
    counters. Numeric leaves inside a `*_total` container (the class-keyed
    `admit_sheds_total`) count as counters too."""
    out: dict[str, float] = {}

    def walk(prefix: str, obj: dict, counter_ctx: bool) -> None:
        for k, v in obj.items():
            if k in ("latency_ms_histogram", "stage_ms_histogram"):
                continue  # handled below with explicit bucket keys
            if k == "host_timeline":
                continue  # one process's stamps: nothing in it adds up
            key = f"{prefix}{k}"
            ctx = counter_ctx or k.endswith("_total")
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                if ctx and math.isfinite(v):
                    out[key] = float(v)
            elif isinstance(v, dict):
                walk(key + ".", v, ctx)

    walk("", snap, False)

    def hist(prefix: str, h: dict) -> None:
        for i, pair in enumerate(h.get("buckets") or []):
            try:
                out[f"{prefix}.bucket.{i}"] = float(pair[1])
            except (TypeError, ValueError, IndexError):
                continue
        for leaf in ("sum", "count"):
            v = h.get(leaf)
            if isinstance(v, (int, float)) and math.isfinite(v):
                out[f"{prefix}.{leaf}"] = float(v)

    h = snap.get("latency_ms_histogram")
    if isinstance(h, dict):
        hist("latency_ms_histogram", h)
    stage = snap.get("stage_ms_histogram")
    if isinstance(stage, dict):
        for name, sh in stage.items():
            if isinstance(sh, dict):
                hist(f"stage_ms_histogram.{name}", sh)
    return out


def _assemble_hist(flat: dict, prefix: str, bounds) -> dict:
    buckets = []
    for i, le in enumerate(bounds):
        cum = flat.get(f"{prefix}.bucket.{i}", 0.0)
        buckets.append([None if math.isinf(le) else le, int(cum)])
    return {
        "buckets": buckets,
        "sum": round(flat.get(f"{prefix}.sum", 0.0), 3),
        "count": int(flat.get(f"{prefix}.count", 0.0)),
    }


def quantile_from_hist(hist: dict, q: float) -> float:
    """Upper-bound quantile estimate from cumulative bucket counts — the
    mergeable replacement for averaging member quantiles. The +Inf bucket
    reports the last finite bound (an underestimate, never a NaN)."""
    count = hist.get("count", 0)
    if not count:
        return 0.0
    target = q * count
    prev_le = 0.0
    for le, cum in hist.get("buckets", []):
        if cum >= target:
            return le if le is not None else prev_le
        if le is not None:
            prev_le = le
    return prev_le


def fleet_burn(raws: list[dict]) -> tuple[dict, float]:
    """({"fast": x, "slow": y}, target_pct) recomputed from merged
    good/bad second-buckets. Buckets carry ages, so scrape-time skew of a
    second or two between members is absorbed by the window sum."""
    target = next(
        (
            float(r["target_pct"])
            for r in raws
            if isinstance(r, dict)
            and isinstance(r.get("target_pct"), (int, float))
        ),
        99.0,
    )
    budget = max(1.0 - target / 100.0, 1e-4)
    out = {}
    for name, window_s in (("fast", FAST_WINDOW_S), ("slow", SLOW_WINDOW_S)):
        good = bad = 0
        for r in raws:
            if not isinstance(r, dict):
                continue
            for entry in r.get("buckets") or []:
                try:
                    age, g, b = entry
                except (TypeError, ValueError):
                    continue
                if age <= window_s:
                    good += g
                    bad += b
        total = good + bad
        out[name] = round((bad / total) / budget, 4) if total > 0 else 0.0
    return out, target


def fleet_mfu(raws: list[dict]) -> dict:
    """Fleet MFU/duty from merged window sums: sum(flops) / sum(span x
    peak) over members that know their peak — the flops-weighted truth,
    not an average of member percentages. Members with unknown peak
    (stub engines, unrecognized devices) contribute duty but not MFU."""
    span = dev = fl = uf = denom = 0.0
    for r in raws:
        if not isinstance(r, dict):
            continue

        def num(key: str) -> float:
            v = r.get(key)
            return float(v) if isinstance(v, (int, float)) and math.isfinite(v) else 0.0

        s = max(num("window_span_s"), 0.0)
        span += s
        dev += max(num("device_s"), 0.0)
        peak = num("peak_flops")
        if peak > 0.0 and s > 0.0:
            fl += num("flops")
            uf += num("useful_flops")
            denom += s * peak
    return {
        "mfu_pct": round(100.0 * fl / denom, 3) if denom > 0 else 0.0,
        "useful_mfu_pct": round(100.0 * uf / denom, 3) if denom > 0 else 0.0,
        "device_duty_cycle_pct": (
            round(min(100.0 * dev / span, 100.0), 3) if span > 0 else 0.0
        ),
    }


def _merged_view(counters: dict[str, float], fresh_snaps: list[dict]) -> dict:
    """The fleet snapshot body from summed counters + fresh member
    snapshots. Every gauge is finite by construction (guarded divisions,
    0.0 at zero members) — the NaN-free acceptance criterion."""
    out: dict = {}
    for k, v in counters.items():
        if "." not in k:
            out[k] = int(v) if float(v).is_integer() else v
    sheds = {
        k.split(".", 1)[1]: int(v)
        for k, v in counters.items()
        if k.startswith("admit_sheds_total.")
    }
    if sheds:
        out["admit_sheds_total"] = sheds

    hist = _assemble_hist(counters, "latency_ms_histogram", LATENCY_BUCKETS_MS)
    out["latency_ms_histogram"] = hist
    for q, tag in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
        out[f"latency_ms_{tag}"] = quantile_from_hist(hist, q)

    stage_names = sorted(
        {
            k.split(".")[1]
            for k in counters
            if k.startswith("stage_ms_histogram.")
        }
    )
    stage_hists = {}
    for name in stage_names:
        sh = _assemble_hist(
            counters, f"stage_ms_histogram.{name}", STAGE_BUCKETS_MS
        )
        stage_hists[name] = sh
        for q, tag in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
            out[f"stage_{name}_ms_{tag}"] = quantile_from_hist(sh, q)
    out["stage_ms_histogram"] = stage_hists

    for key in _SUM_GAUGE_KEYS:
        total = 0.0
        for s in fresh_snaps:
            v = s.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and math.isfinite(v):
                total += v
        out[key] = int(total) if total.is_integer() else round(total, 3)
    for key in _MAX_GAUGE_KEYS:
        vals = [
            v
            for s in fresh_snaps
            if isinstance(v := s.get(key), (int, float))
            and not isinstance(v, bool)
            and math.isfinite(v)
        ]
        out[key] = max(vals, default=0)

    rates, target = fleet_burn(
        [s.get("slo_burn_raw") for s in fresh_snaps]
    )
    out["slo_burn_rate"] = rates
    out["slo_target_pct"] = target
    out.update(fleet_mfu([s.get("perf_raw") for s in fresh_snaps]))

    hits = counters.get("cache_hits_total", 0.0)
    misses = counters.get("cache_misses_total", 0.0)
    lookups = hits + misses
    out["cache_hit_rate"] = round(hits / lookups, 4) if lookups else 0.0
    return out


def merge_snapshots(snaps: list[dict]) -> dict:
    """Pure fleet merge of member snapshots, all treated as fresh (no
    reset state — the golden-test surface). The stateful FleetAggregator
    runs the same math over reset-adjusted counter views."""
    counters: dict[str, float] = {}
    for s in snaps:
        for k, v in flatten_counters(s).items():
            counters[k] = counters.get(k, 0.0) + v
    return _merged_view(counters, snaps)


# ---------------------------------------------------------------------------
# the stateful aggregation plane


class _MemberState:
    def __init__(self, url: str) -> None:
        self.url = url
        # counters retired by past generations of this replica: folded in
        # on every detected reset so the fleet view stays monotone
        self.base: dict[str, float] = {}
        self.last: dict[str, float] | None = None
        self.snapshot: dict | None = None
        self.generation: int | None = None
        self.last_ok: float | None = None
        self.up = False
        self.last_error = ""
        self.resets_total = 0

    def effective(self) -> dict[str, float]:
        out = dict(self.base)
        for k, v in (self.last or {}).items():
            out[k] = out.get(k, 0.0) + v
        return out


class FleetAggregator:
    """Scrape, merge, and serve the fleet telemetry view (see module
    docstring). `members_fn` returns the current member base URLs — the
    router's pool or the fleet controller's pools; membership churn is
    re-read every scrape. Ingestion (`observe`/`mark_down`) is separable
    from transport so tests drive the state machine with synthetic
    snapshots and no sockets."""

    def __init__(
        self,
        members_fn,
        client=None,
        interval_s: float | None = None,
        stale_after_s: float | None = None,
    ) -> None:
        if interval_s is None:
            interval_s = _env_float(SCRAPE_INTERVAL_ENV, DEFAULT_SCRAPE_S)
        self.interval_s = interval_s
        if stale_after_s is None:
            stale_after_s = _env_float(STALE_AFTER_ENV, 0.0)
        if stale_after_s <= 0:
            # a member is stale after missing ~3 scrapes (floor 5 s so a
            # sub-second test interval doesn't flap real deployments' view)
            stale_after_s = max(3.0 * max(interval_s, 0.1), 5.0)
        self.stale_after_s = stale_after_s
        self._members_fn = members_fn
        self._client = client
        self._owns_client = client is None
        self._task: asyncio.Task | None = None
        self._lock = threading.Lock()
        self._states: dict[str, _MemberState] = {}
        self.scrapes_total = 0
        self.scrape_errors_total = 0

    @property
    def enabled(self) -> bool:
        return self.interval_s > 0

    # ---- ingestion (pure state machine) ----

    def observe(self, url: str, snapshot: dict) -> None:
        """Fold one successful member scrape in. Detects counter resets
        two ways: the principled one (the identity stamp's `generation`
        moved — a supervisor restart) and the defensive one (any counter
        went backwards, e.g. a replica replaced behind the same URL
        without a generation source). Either way the previous totals are
        retired into the base — fleet counters never go negative."""
        url = url.rstrip("/")
        flat = flatten_counters(snapshot)
        rep = snapshot.get("replica")
        gen = rep.get("generation") if isinstance(rep, dict) else None
        with self._lock:
            st = self._states.setdefault(url, _MemberState(url))
            if st.last is not None:
                bumped = gen != st.generation
                regressed = any(
                    flat.get(k, 0.0) < v - 1e-9 for k, v in st.last.items()
                )
                if bumped or regressed:
                    for k, v in st.last.items():
                        st.base[k] = st.base.get(k, 0.0) + v
                    st.resets_total += 1
                    logger.info(
                        "fleet member %s reset (generation %r -> %r): "
                        "counters folded into base", url, st.generation, gen,
                    )
            st.generation = gen
            st.last = flat
            st.snapshot = snapshot
            st.last_ok = time.monotonic()
            st.up = True
            st.last_error = ""

    def mark_down(self, url: str, error: str) -> None:
        """A failed scrape: the member keeps its counter history but drops
        out of every fleet gauge until it answers again."""
        with self._lock:
            st = self._states.setdefault(
                url.rstrip("/"), _MemberState(url.rstrip("/"))
            )
            st.up = False
            st.last_error = str(error)[:200]
            self.scrape_errors_total += 1

    # ---- transport ----

    def _ensure_client(self):
        if self._client is None:
            import httpx

            self._client = httpx.AsyncClient(
                timeout=httpx.Timeout(2.0, connect=1.0)
            )
        return self._client

    async def scrape_once(self) -> None:
        urls = [u.rstrip("/") for u in (self._members_fn() or [])]
        client = self._ensure_client()

        async def one(url: str) -> None:
            try:
                resp = await client.get(f"{url}/metrics")
                if resp.status_code != 200:
                    raise RuntimeError(f"HTTP {resp.status_code}")
                snap = resp.json()
                if not isinstance(snap, dict):
                    raise RuntimeError("non-object /metrics body")
            except Exception as exc:
                self.mark_down(url, repr(exc))
                return
            self.observe(url, snap)

        if urls:
            await asyncio.gather(*(one(u) for u in urls))
        self.scrapes_total += 1

    async def start(self) -> None:
        if self.enabled and self._task is None:
            self._task = asyncio.create_task(self._run())

    async def _run(self) -> None:
        while True:
            try:
                await self.scrape_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("fleet scrape failed")
            await asyncio.sleep(self.interval_s)

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        if self._owns_client and self._client is not None:
            await self._client.aclose()
            self._client = None

    # ---- views ----

    def member_snapshot(self, url: str) -> dict | None:
        """The last successfully-scraped /metrics snapshot for one member
        (None when never scraped). The rollout controller's verdict reads
        canary and baseline-cohort signals (p99, errors, fast-window burn)
        from exactly the view the fleet plane already maintains."""
        with self._lock:
            st = self._states.get(url.rstrip("/"))
            return st.snapshot if st is not None else None

    def _is_stale(self, st: _MemberState, now: float) -> bool:
        if st.last_ok is None:
            return True
        return not st.up or (now - st.last_ok) > self.stale_after_s

    def fleet_snapshot(self) -> dict:
        """The merged fleet view: counters over every member ever seen
        (history is cumulative), gauges/rates over fresh members only."""
        now = time.monotonic()
        with self._lock:
            states = list(self._states.values())
            counters: dict[str, float] = {}
            for st in states:
                for k, v in st.effective().items():
                    counters[k] = counters.get(k, 0.0) + v
            fresh = [
                st.snapshot
                for st in states
                if st.snapshot is not None and not self._is_stale(st, now)
            ]
            stale = sum(1 for st in states if self._is_stale(st, now))
            resets = sum(st.resets_total for st in states)
            rows = [self._row(st, now) for st in states]
        out = _merged_view(counters, fresh)
        out["replicas"] = {
            "configured": len(list(self._members_fn() or [])),
            "seen": len(states),
            "up": len(fresh),
            "stale": stale,
            "generation_resets_total": resets,
        }
        out["scrape_interval_s"] = self.interval_s
        out["stale_after_s"] = self.stale_after_s
        out["scrapes_total"] = self.scrapes_total
        out["scrape_errors_total"] = self.scrape_errors_total
        out["per_replica"] = rows
        return out

    def _row(self, st: _MemberState, now: float) -> dict:
        """One /debug/fleet table row (also rendered into the Prometheus
        exposition with {url=...} labels by the list-of-dicts path)."""
        snap = st.snapshot or {}
        rep = snap.get("replica") if isinstance(snap.get("replica"), dict) else {}
        burn = snap.get("slo_burn_rate")
        burn = burn if isinstance(burn, dict) else {}
        staleness = (now - st.last_ok) if st.last_ok is not None else None
        hits = snap.get("cache_hits_total", 0) or 0
        misses = snap.get("cache_misses_total", 0) or 0
        lookups = hits + misses
        return {
            "url": st.url,
            "up": st.up,
            "stale": self._is_stale(st, now),
            "staleness_s": (
                round(staleness, 3) if staleness is not None else None
            ),
            "generation": st.generation if st.generation is not None else 0,
            "generation_resets": st.resets_total,
            "pid": rep.get("pid"),
            "model": rep.get("model"),
            # deployment identity (ISSUE 15): which build each member
            # serves — the /debug/fleet column that makes a mixed-version
            # rollout window (and its canary) readable at a glance
            "version": rep.get("version"),
            "weights_digest": rep.get("weights_digest"),
            "uptime_s": rep.get("uptime_s"),
            "images_total": snap.get("images_total", 0),
            "images_per_sec": snap.get("images_per_sec", 0.0),
            "latency_ms_p50": snap.get("latency_ms_p50", 0.0),
            "latency_ms_p99": snap.get("latency_ms_p99", 0.0),
            "slo_burn_fast": burn.get("fast", 0.0),
            "mfu_pct": snap.get("mfu_pct", 0.0),
            "device_duty_cycle_pct": snap.get("device_duty_cycle_pct", 0.0),
            "hbm_bytes_in_use": snap.get("hbm_bytes_in_use", 0),
            "brownout_rung": snap.get("brownout_rung", 0),
            "cache_hit_rate": (
                round(hits / lookups, 4) if lookups else 0.0
            ),
            "last_error": st.last_error,
        }

    # ---- cross-replica trace stitching ----

    async def stitched_traces(
        self,
        recorder,
        trace_id: str | None = None,
        k: int | None = None,
        headers: dict | None = None,
    ) -> dict:
        """Join edge traces with the owning replica's flight-recorder
        spans by trace id: one tiled tree per request, so a slow fleet
        request reads end-to-end without ssh'ing into a replica. With no
        `trace_id`, the edge's pinned slowest-K are stitched (the traces
        an operator chasing tail latency actually wants); `headers`
        forwards the caller's admin token to the member /debug/traces
        gates."""
        if trace_id:
            edge = recorder.lookup(trace_id)
        else:
            edge = recorder.slowest_traces(k)
        edge = edge[: k or 8]
        with self._lock:
            known = set(self._states)
        urls = sorted(
            known | {u.rstrip("/") for u in (self._members_fn() or [])}
        )
        client = self._ensure_client()

        async def fetch(url: str, tid: str) -> dict | None:
            try:
                resp = await client.get(
                    f"{url}/debug/traces",
                    params={"trace_id": tid},
                    headers=headers or {},
                )
                if resp.status_code != 200:
                    return None
                data = resp.json()
                traces = data.get("traces")
                return {"url": url, "traces": traces} if traces else None
            except Exception:
                return None

        stitched = []
        for t in edge:
            tid = t.get("trace_id")
            if not tid:
                continue
            results = await asyncio.gather(*(fetch(u, tid) for u in urls))
            stitched.append(
                {
                    "edge": t,
                    "replicas": [r for r in results if r is not None],
                }
            )
        return {"fleet": True, "members": urls, "stitched": stitched}
