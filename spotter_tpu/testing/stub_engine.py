"""Stub engine + stub fetch for model-free multi-replica testing (ISSUE 2).

The failover/chaos layer under test is everything ABOVE the forward pass:
startup state machine, drain, supervisor restart, pool replay. A real model
would add minutes of compile per replica subprocess and prove nothing about
that layer, so `SPOTTER_TPU_STUB_ENGINE=1` (or `--stub-engine`) makes the
standalone server run this engine instead: canned detections, optional fixed
service time (`SPOTTER_TPU_STUB_SERVICE_MS`) so load tests have a realistic
queueing profile, no jax device work, CPU-safe. The stub also short-circuits
image fetching (the detector's httpx client is replaced by `StubHttpClient`)
so request URLs never leave the process.

Never production: the standalone server logs loudly when stub mode is on,
the same way it does for SPOTTER_TPU_FAULTS.
"""

import hashlib
import os
import time
from io import BytesIO

STUB_ENGINE_ENV = "SPOTTER_TPU_STUB_ENGINE"
STUB_SERVICE_MS_ENV = "SPOTTER_TPU_STUB_SERVICE_MS"

# Labels must be AMENITIES_MAPPING keys so stub responses contain real
# detections end-to-end (taxonomy.py: "tv" -> "TV").
STUB_DETECTIONS = [{"label": "tv", "score": 0.9, "box": [2.0, 2.0, 20.0, 24.0]}]


def content_fingerprint(image) -> int:
    """Deterministic 16-bit fingerprint of an image's pixel content.

    Raw pixel bytes, not re-encoded JPEG: two in-process decodes of the
    same fetched bytes must fingerprint identically, and a probe image
    built directly as a PIL array (serving/integrity.py — never through
    an encoder) must fingerprint the same everywhere."""
    try:
        payload = image.tobytes()
    except Exception:
        payload = repr(image).encode()
    digest = hashlib.blake2b(payload, digest_size=2).digest()
    return digest[0] | (digest[1] << 8)


def stub_image_bytes(w: int = 32, h: int = 32, fill: int = 128) -> bytes:
    import numpy as np
    from PIL import Image

    img = Image.fromarray(np.full((h, w, 3), fill % 256, np.uint8))
    buf = BytesIO()
    img.save(buf, format="JPEG")
    return buf.getvalue()


class StubEngine:
    """Duck-typed InferenceEngine: metrics + batch_buckets + detect()."""

    def __init__(
        self,
        service_ms: float | None = None,
        detections: list[dict] | None = None,
    ) -> None:
        from spotter_tpu.engine.metrics import Metrics

        if service_ms is None:
            raw = os.environ.get(STUB_SERVICE_MS_ENV, "").strip()
            service_ms = float(raw) if raw else 0.0
        self.service_s = max(service_ms, 0.0) / 1000.0
        # `detections` overrides the canned output (ISSUE 15: a "new
        # version" stub whose answers DIFFER is how the shadow lane's
        # detection-diff verdict is exercised model-free)
        # per-instance deep copy: corrupt_weights() mutates in place, and
        # aliasing the module-level STUB_DETECTIONS would corrupt every
        # stub in the process
        self.detections = [
            dict(d)
            for d in (detections if detections is not None else STUB_DETECTIONS)
        ]
        self.metrics = Metrics()
        # identity stamp (ISSUE 12): stub fleets exercise the same
        # mergeable-snapshot contract the real engine carries, so the
        # aggregator's per-replica table and restart detection work in
        # the model-free chaos/bench harnesses too
        self.metrics.set_identity(model="stub")
        self.batch_buckets = (1, 2, 4, 8)
        # Trusted attestation reference (ISSUE 17): captured at load time,
        # BEFORE any fault can corrupt the live "weights" — the same role
        # the host-side checkpoint copy plays for the real engine.
        self._attest_reference = self._checksum()

    def _checksum(self) -> int:
        digest = hashlib.sha256(repr(self.detections).encode()).digest()
        return int.from_bytes(digest[:4], "big")

    def attest(self) -> dict:
        """Same contract as InferenceEngine.attest(): live checksum over
        whatever the stub would answer with NOW vs the load-time
        reference — diverges iff something mutated the detections after
        load (the corrupt_weights fault, a buggy test)."""
        observed = self._checksum()
        ok = observed == self._attest_reference
        return {
            "ok": ok,
            "checked": 1,
            "mismatched": [] if ok else ["stub:0"],
            "observed": {"stub:0": observed},
            "expected": {"stub:0": self._attest_reference},
        }

    def corrupt_weights(self, n: int) -> None:
        """Test-only SDC injection seam (faults.py corrupt_weights=<n>):
        perturb the first `n` canned detections the way a flipped weight
        bit perturbs real outputs — scores move beyond the comparator
        tolerance and the attestation checksum stops matching."""
        for det in self.detections[: max(int(n), 0)]:
            det["score"] = round(
                min(float(det.get("score", 0.0)) + 0.11, 1.0), 4
            )

    def _detections_for(self, image) -> list[dict]:
        h = content_fingerprint(image)
        d_score = (h % 8) / 100.0
        d_box = float((h >> 3) % 8)
        out = []
        for det in self.detections:
            d = dict(det)
            try:
                score = float(d.get("score", 0.0))
            except (TypeError, ValueError):
                score = 0.0
            d["score"] = round(min(max(score - d_score, 0.0), 1.0), 4)
            box = d.get("box")
            if isinstance(box, (list, tuple)) and len(box) == 4:
                d["box"] = [round(float(v) + d_box, 2) for v in box]
            out.append(d)
        return out

    def weights_digest(self) -> str:
        """Content fingerprint of this stub's canned output (ISSUE 15):
        the same role the real engine's param digest plays — two stubs
        with different detections report different digests."""
        return hashlib.sha256(
            repr(self.detections).encode()
        ).hexdigest()[:12]

    def warmup(self) -> None:  # parity with InferenceEngine's surface
        pass

    def detect(self, images):
        # Mirror the real engine's stage spans (obs.STAGES vocabulary,
        # ISSUE 7): the stub's "device" window is its service sleep, the
        # other engine stages are real-but-tiny, and the slow_stage fault
        # injects into the same seam (obs.span's) — so fleet/trace tests
        # over stub replicas see the same span set (and the same /metrics
        # stage histograms) the production engine emits.
        from spotter_tpu import obs
        from spotter_tpu.testing import faults

        n = len(images)
        traces = obs.batch_traces()
        stages = {}
        total = obs.span("engine.batch", traces, n=n).start()
        # an empty window each: the slow_stage fault sleeps inside it
        for name, stage in (("engine.decode", obs.DECODE), ("engine.h2d", obs.H2D)):
            with obs.span(name, traces, stage=stage) as sp:
                pass
            stages[stage] = sp.seconds
        starvation = self.metrics.starvation
        starvation.move(in_flight=+1)
        with obs.span("engine.device", traces, stage=obs.DEVICE) as device:
            # gray-failure injection (ISSUE 14): a slow_replica plan makes
            # THIS process's every engine call slower inside the device
            # window — /healthz stays green while /detect latency grows,
            # the signature the pool's outlier score must catch
            delay_s = faults.replica_delay_s(self.metrics.replica_id)
            if delay_s > 0:
                time.sleep(delay_s)
            if self.service_s > 0:
                time.sleep(self.service_s)
        starvation.move(in_flight=-1)
        stages[obs.DEVICE] = device.seconds
        with obs.span("engine.postprocess", traces, stage=obs.POSTPROCESS) as post:
            # Detections are a deterministic FUNCTION OF INPUT CONTENT
            # (ISSUE 17 bugfix): the old `list(self.detections)` was
            # input-independent, so any diff-based test — shadow-lane
            # verdicts, quorum comparisons, cache-poisoning checks — passed
            # vacuously (every answer "agreed" because every answer was
            # identical). Now each image's content hash perturbs score and
            # box inside the comparator's tolerance-equivalence classes:
            # same input -> same output on every honest replica with the
            # same weights, different input -> measurably different output.
            out = [self._detections_for(img) for img in images]
            out = [
                faults.corrupt_detections(dets, self.metrics.replica_id)
                for dets in out
            ]
        stages[obs.POSTPROCESS] = post.seconds
        total.stop()
        bucket = next(
            (b for b in self.batch_buckets if n <= b), self.batch_buckets[-1]
        )
        self.metrics.record_batch(
            n, total.seconds, stages=stages, trace_id=obs.batch_trace_id(),
            bucket=bucket,
        )
        # Device-efficiency ledger (ISSUE 10): the stub's "device" window
        # is its service sleep; no FLOPs (no compiled program), so MFU
        # stays 0 while duty-cycle and the top-dispatch table are real.
        self.metrics.perf.record_dispatch(
            device_s=device.seconds,
            batch=n,
            trace_id=obs.batch_trace_id(),
            shape=f"stub:{n}",
        )
        return out


class _StubResponse:
    def __init__(self, content: bytes) -> None:
        self.content = content

    def raise_for_status(self) -> None:
        pass


class StubHttpClient:
    """Replaces the detector's httpx.AsyncClient in stub mode: every GET
    "fetches" a tiny canned JPEG without touching the network. DISTINCT
    URLs get DISTINCT bytes (fill value from the URL hash, ISSUE 11) so
    content-addressed cache keys behave like real traffic — affinity
    benches over stub replicas measure per-URL hit locality, not one
    degenerate shared key. A small encode memo keeps repeat fetches free."""

    _MEMO_MAX = 64

    def __init__(self) -> None:
        self._memo: dict[int, bytes] = {}

    async def get(self, url: str) -> _StubResponse:
        fill = hashlib.blake2b(url.encode(), digest_size=1).digest()[0]
        body = self._memo.get(fill)
        if body is None:
            if len(self._memo) >= self._MEMO_MAX:
                self._memo.clear()
            body = stub_image_bytes(fill=fill)
            self._memo[fill] = body
        return _StubResponse(body)

    async def aclose(self) -> None:
        pass


def stub_mode_enabled() -> bool:
    return os.environ.get(STUB_ENGINE_ENV, "0") not in ("", "0")


def build_stub_detector():
    """AmenitiesDetector over a StubEngine + StubHttpClient (the standalone
    server's bring-up path when stub mode is on)."""
    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.serving.detector import AmenitiesDetector

    engine = StubEngine()
    batcher = MicroBatcher(engine, max_delay_ms=2.0)
    return AmenitiesDetector(engine, batcher, StubHttpClient())
