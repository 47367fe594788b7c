"""AmenitiesDetector: fetch -> detect -> draw -> encode, per-image error containment.

Behavior contract with the reference detector (serve.py:64-196), observable
bit-for-bit at the /detect wire:
- async URL fetch with tenacity retry (3 attempts, exponential backoff
  multiplier 1, min 4 s, max 10 s, reraise) — serve.py:84-91
- PIL open + convert("RGB") — serve.py:96-97
- detections filtered through AMENITIES_MAPPING; irrelevant labels dropped —
  serve.py:123-126
- red box width 3, amenity text at (x+5, y+5), white fill / black stroke —
  serve.py:127-134
- JPEG + base64 of the annotated image — serve.py:139-142
- httpx errors -> "HTTP Error: ..."; anything else -> "Processing Error: ..."
  with traceback; one bad URL never fails the batch — serve.py:150-157
- response joins detected amenities into "The property contains: ..." /
  "No relevant amenities detected." — serve.py:190-194

The difference is under the hood: detection goes through the MicroBatcher into
the jit-compiled TPU engine instead of a per-image torch forward.

Request-lifecycle hardening (ISSUE 1): an optional per-request `Deadline`
(env `SPOTTER_TPU_REQUEST_DEADLINE_MS`) bounds fetch+retries, queue wait, and
the device call — on expiry the image gets a structured
`DetectionErrorResult` ("Deadline exceeded: ...") instead of hanging through
22+ s of retry backoff. Admission rejections (queue full, breaker open,
draining) stay per-image errors when the request is partially served, but a
fully-shed request re-raises so the HTTP layer can answer 429/503 with
Retry-After. tenacity is optional: when absent (minimal images) a local
retry loop preserves the same 3-attempt/4-10 s-backoff contract.

Fetch hardening (ISSUE 4 satellite): fetches are bounded in time
(`SPOTTER_TPU_FETCH_TIMEOUT_S`) and bytes (`SPOTTER_TPU_FETCH_MAX_BYTES`,
content-length reject + streamed read cap), failures are a typed
`FetchError`, deterministic 4xx statuses are not retried, and
`SPOTTER_TPU_MAX_IMAGE_PIXELS` rejects decode bombs before convert()
decodes them.

Caching tier (ISSUE 5, opt-in via `SPOTTER_TPU_CACHE_MAX_MB`): listing-photo
traffic is heavily duplicated and detection is deterministic per
(model, image bytes, threshold), so the detector front-loads three exact
short-circuits before any engine work: (1) URL-level single-flight — N
concurrent requests for one URL share ONE fetch; (2) a negative cache —
a recently-seen deterministic failure (non-retryable 4xx fetch, poison
image) re-raises instantly instead of re-fetching/re-bisecting; (3) a
content-addressed result cache — byte-identical images skip the engine
entirely (the hit still decodes + draws, so the wire response is
unchanged). Misses submit with the content hash as `key`, which the
MicroBatcher uses for hash-level coalescing and cache fill. With the knob
unset/0 none of this machinery is constructed and the path is bit-identical
to a cache-less build.
"""

import asyncio
import base64
import traceback
from io import BytesIO

import httpx
from PIL import Image, ImageDraw

try:
    from tenacity import (
        AsyncRetrying,
        retry_if_exception,
        stop_after_attempt,
        wait_exponential,
    )

    _HAVE_TENACITY = True
except ImportError:  # minimal image — fallback loop below keeps the contract
    _HAVE_TENACITY = False

from spotter_tpu import obs
from spotter_tpu.caching.keys import content_key, url_key
from spotter_tpu.caching.result_cache import ResultCache
from spotter_tpu.caching.singleflight import SingleFlight
from spotter_tpu.caching.text_cache import TextQueryResolver
from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.errors import PoisonImageError
from spotter_tpu.engine.engine import InferenceEngine
from spotter_tpu.schemas import (
    DetectionErrorResult,
    DetectionRequest,
    DetectionResponse,
    DetectionResult,
    DetectionSuccessResult,
    ImageResult,
)
from spotter_tpu.serving.overload import BULK, BrownoutShedError
from spotter_tpu.serving.resilience import (
    AdmissionError,
    CircuitBreaker,
    CircuitOpenError,
    Deadline,
    DeadlineExceededError,
    DrainingError,
    _env_float,
    _env_int,
    jittered_retry_after,
)
from spotter_tpu.ops.preprocess import check_image_pixels
from spotter_tpu.taxonomy import AMENITIES_MAPPING
from spotter_tpu.testing import faults

# Fetch retry policy (serve.py:84-88). Module-level so tests can zero the
# backoff instead of sleeping through it.
FETCH_RETRY_ATTEMPTS = 3
FETCH_RETRY_WAIT_MIN_S = 4.0
FETCH_RETRY_WAIT_MAX_S = 10.0

# Fetch hardening (ISSUE 4 satellite): every outbound image fetch is bounded
# in time and bytes, and client errors that can never succeed (404 and
# friends) are not retried through 22 s of backoff.
FETCH_TIMEOUT_ENV = "SPOTTER_TPU_FETCH_TIMEOUT_S"
DEFAULT_FETCH_TIMEOUT_S = 15.0
FETCH_MAX_BYTES_ENV = "SPOTTER_TPU_FETCH_MAX_BYTES"
DEFAULT_FETCH_MAX_BYTES = 32 * 1024 * 1024
# 4xx statuses that ARE worth retrying (timeout, rate limit); every other
# 4xx is deterministic and fails fast
RETRYABLE_4XX = (408, 429)


class QueriesUnsupportedError(ValueError):
    """A /detect carried free-text `queries` but the served model family is
    closed-set (no text encoder). The HTTP layer answers 400 — the request
    can never succeed on this deployment, so retrying or 500ing would both
    mislead the client."""


class FetchError(RuntimeError):
    """Typed image-fetch failure (size cap, retries exhausted). Replaces the
    bare `Exception("Failed to fetch image after retries")`; `retryable`
    tells the retry loop whether another attempt could possibly succeed."""

    def __init__(self, message: str, retryable: bool = False) -> None:
        super().__init__(message)
        self.retryable = retryable


def _fetch_retryable(exc: BaseException) -> bool:
    """Retry connect/timeout/5xx; never deterministic failures (non-408/429
    4xx, size-cap rejections)."""
    if isinstance(exc, FetchError):
        return exc.retryable
    if isinstance(exc, httpx.HTTPStatusError):
        code = exc.response.status_code
        if 400 <= code < 500:
            return code in RETRYABLE_4XX
    return True


# default for AmenitiesDetector(cache=...): build from the env knobs (None
# when SPOTTER_TPU_CACHE_MAX_MB is unset/0). Pass None to force the tier off
# or a ResultCache instance to use it regardless of the env.
_CACHE_FROM_ENV = object()


def _mark_outcome(info: dict | None, url: str, outcome: str) -> None:
    """Per-URL caching-tier outcome for the `X-Cache` header (ISSUE 11
    satellite). First write wins: "the cache served this" outranks any
    later bookkeeping on the same URL."""
    if info is not None:
        info.setdefault("cache", {}).setdefault(url, outcome)


def _note_verdict(
    info: dict | None, url: str, kind: str, error: str, ttl_s: float
) -> None:
    """Record a deterministic-failure verdict for this URL so the HTTP
    layer can surface it in `X-Spotter-Negative` (ISSUE 11): the edge
    router folds these into its fleet-shared negative cache. ONLY the
    PR 5 taxonomy's deterministic failures may land here."""
    if info is not None:
        info.setdefault("negative", {})[url] = {
            "kind": kind,
            "error": error,
            "ttl_s": ttl_s,
        }


class AmenitiesDetector:
    """Framework-agnostic core; Ray Serve / aiohttp adapters wrap this."""

    def __init__(
        self,
        engine: InferenceEngine,
        batcher: MicroBatcher | None = None,
        client: httpx.AsyncClient | None = None,
        cache: ResultCache | None | object = _CACHE_FROM_ENV,
    ) -> None:
        self.engine = engine
        self.batcher = batcher or MicroBatcher(engine)
        self.fetch_timeout_s = _env_float(FETCH_TIMEOUT_ENV, DEFAULT_FETCH_TIMEOUT_S)
        self.fetch_max_bytes = _env_int(FETCH_MAX_BYTES_ENV, DEFAULT_FETCH_MAX_BYTES)
        self.client = client or httpx.AsyncClient(timeout=self.fetch_timeout_s)
        # Caching tier (ISSUE 5): per-detector, never global — two detectors
        # in one process (tests, replicas) must not share entries. None means
        # the tier is fully off and every path below is bit-identical to a
        # cache-less build.
        if cache is _CACHE_FROM_ENV:
            cache = ResultCache.from_env(metrics=engine.metrics)
        self.cache: ResultCache | None = cache
        self._fetch_flights = SingleFlight(
            on_coalesced=engine.metrics.record_coalesced_fetch
        )
        if self.cache is not None and self.batcher.result_cache is None:
            self.batcher.result_cache = self.cache
        # content-key ingredients: the engine's identity half of the key
        built = getattr(engine, "built", None)
        self._cache_model = getattr(built, "model_name", None) or type(engine).__name__
        self._cache_threshold = float(getattr(engine, "threshold", 0.5))
        # Open vocabulary (ISSUE 13): text-conditioned families get a
        # memoized query-set resolver (the text-embedding cache); closed-set
        # families keep None and /detect `queries` answer 400.
        text_encoder = getattr(built, "text_encoder", None)
        self._text_resolver = (
            TextQueryResolver(
                self._cache_model, text_encoder, metrics=engine.metrics
            )
            if text_encoder is not None
            else None
        )
        # Tenant isolation plane (ISSUE 19): None unless the serving layer
        # wires one via attach_tenancy() — every tenant-aware branch below
        # is a no-op then (bit-identical serving).
        self.tenancy = None

    def attach_tenancy(self, plane) -> None:
        """Wire the tenant isolation plane (ISSUE 19) through the detector
        and down into the batcher's arbiters (scheduler DRR, limiter
        revocation scoping, per-tenant brownout). None is a no-op."""
        if plane is None:
            return
        self.tenancy = plane
        self.batcher.attach_tenancy(plane)

    def _check_fetch_size(self, url: str, nbytes: int) -> None:
        if self.fetch_max_bytes > 0 and nbytes > self.fetch_max_bytes:
            raise FetchError(
                f"image at {url} is {nbytes} bytes, over "
                f"{FETCH_MAX_BYTES_ENV}={self.fetch_max_bytes}",
                retryable=False,
            )

    async def _fetch_streamed(self, url: str) -> bytes:
        """Streamed fetch with the byte cap enforced as bytes arrive: a
        mis-labeled (or absent) content-length cannot buffer past the cap."""
        async with self.client.stream("GET", url) as response:
            response.raise_for_status()
            declared = response.headers.get("content-length")
            if declared is not None:
                try:
                    self._check_fetch_size(url, int(declared))
                except ValueError:
                    pass  # unparsable header: the read cap still applies
            chunks: list[bytes] = []
            total = 0
            async for chunk in response.aiter_bytes():
                total += len(chunk)
                self._check_fetch_size(url, total)
                chunks.append(chunk)
            return b"".join(chunks)

    async def _fetch_image_bytes(self, url: str) -> bytes:
        injected = await faults.on_fetch(url)
        if injected is not None:
            return injected
        # Streaming (early content-length reject + incremental read cap)
        # needs a REAL httpx client; duck-typed stand-ins (the stub engine's
        # canned fetcher, mocked clients in tests) keep the plain get()
        # contract and still get the post-hoc size check.
        if type(self.client) is httpx.AsyncClient:
            return await self._fetch_streamed(url)
        response = await self.client.get(url)
        response.raise_for_status()
        self._check_fetch_size(url, len(response.content))
        return response.content

    async def _fetch_with_retries(
        self, url: str, deadline: Deadline | None = None
    ) -> bytes:
        """3 attempts, exponential backoff in [min, max] s, reraise — the
        reference policy, with or without tenacity installed. Deterministic
        failures (non-408/429 4xx, size-cap rejections) are NOT retried: a
        404 re-fetched 3 times through 22 s of backoff is pure added load
        and latency with an unchanged outcome.

        Deadline-aware attempts (ISSUE 8 satellite): with a `deadline`,
        each attempt's timeout is clamped to
        `min(SPOTTER_TPU_FETCH_TIMEOUT_S, deadline.remaining)` and the
        retry loop STOPS once the remaining budget cannot cover the
        backoff plus another attempt — a 15 s per-attempt default must not
        burn a 200 ms deadline three times over. Deadline-free calls keep
        the exact reference policy (tenacity when installed)."""
        if deadline is None and _HAVE_TENACITY:
            image_bytes = None
            retries = AsyncRetrying(
                stop=stop_after_attempt(FETCH_RETRY_ATTEMPTS),
                wait=wait_exponential(
                    multiplier=1, min=FETCH_RETRY_WAIT_MIN_S, max=FETCH_RETRY_WAIT_MAX_S
                ),
                retry=retry_if_exception(_fetch_retryable),
                reraise=True,
            )
            async for attempt in retries:
                with attempt:
                    image_bytes = await self._fetch_image_bytes(url)
            if image_bytes is None:
                raise FetchError("failed to fetch image after retries")
            return image_bytes
        for attempt in range(1, FETCH_RETRY_ATTEMPTS + 1):
            attempt_timeout = self.fetch_timeout_s
            if deadline is not None:
                remaining = deadline.remaining()
                if remaining <= 0:
                    raise deadline.exceeded("image fetch")
                if attempt_timeout > 0:
                    attempt_timeout = min(attempt_timeout, remaining)
                else:
                    attempt_timeout = remaining
            try:
                fetch = self._fetch_image_bytes(url)
                if attempt_timeout > 0:
                    try:
                        return await asyncio.wait_for(fetch, attempt_timeout)
                    except asyncio.TimeoutError:
                        raise FetchError(
                            f"fetch attempt timed out after "
                            f"{attempt_timeout:.3f} s",
                            retryable=True,
                        ) from None
                return await fetch
            except Exception as exc:
                if attempt == FETCH_RETRY_ATTEMPTS or not _fetch_retryable(exc):
                    raise
                wait = min(
                    max(float(2**attempt), FETCH_RETRY_WAIT_MIN_S),
                    FETCH_RETRY_WAIT_MAX_S,
                )
                if deadline is not None and deadline.remaining() <= wait:
                    # the budget cannot cover the backoff, let alone the
                    # attempt after it: skip the pointless retries and
                    # surface the real failure now
                    raise
                await asyncio.sleep(wait)
        raise FetchError("failed to fetch image after retries")  # unreachable

    async def _fetch_flight(self, url: str) -> bytes:
        """The shared fetch flight body (cache tier on): one per URL at a
        time, deadline-free — waiters apply their own budgets around it.
        Deterministic failures land in the negative cache on the way out;
        retryable ones (5xx, 429/408, timeouts, connect errors) never do."""
        try:
            return await self._fetch_with_retries(url)
        except FetchError as exc:
            if not exc.retryable:
                self.cache.put_negative(url_key(url), exc)
            raise
        except httpx.HTTPStatusError as exc:
            code = exc.response.status_code
            if 400 <= code < 500 and code not in RETRYABLE_4XX:
                self.cache.put_negative(url_key(url), exc)
            raise

    async def _fetch_for_request(
        self, url: str, deadline: Deadline | None, info: dict | None = None
    ) -> bytes:
        if self.cache is None:  # tier off: the exact pre-cache path
            fetch = self._fetch_with_retries(url, deadline)
            if deadline is not None:
                return await deadline.wait_for(fetch, "image fetch")
            return await fetch
        cached_failure = self.cache.get_negative(url_key(url))
        if cached_failure is not None:
            _mark_outcome(info, url, "negative")
            raise cached_failure
        return await self._fetch_flights.run(
            url,
            lambda: self._fetch_flight(url),
            deadline=deadline,
            what="image fetch",
        )

    async def _process_single_image(self, url: str, *args, **kwargs) -> ImageResult:
        """One image, entry to reply. The starvation clock counts it as
        upstream work for as long as it is in here (in fetch, PIL decode
        or the batcher's queue, or its reply being drawn and encoded): with
        the chip idle and no batch staging, that is what the chip waits
        for (`starved_upstream_s_total`). The same interval is the span
        `detector.image` (a wait: no annotation, and on no request trace)."""
        starvation = self.engine.metrics.starvation
        starvation.move(upstream=+1)
        try:
            with obs.span("detector.image", obs.NO_TRACE):
                return await self._process_image(url, *args, **kwargs)
        finally:
            starvation.move(upstream=-1)

    async def _process_image(
        self,
        url: str,
        deadline: Deadline | None = None,
        cls: str | None = None,
        degraded: set[str] | None = None,
        info: dict | None = None,
        qset=None,
        tenant: str | None = None,
    ) -> ImageResult:
        # the ambient request trace (ISSUE 7): span capture below is a
        # monotonic read + list append per stage; None (recorder off, or a
        # bare library call) makes every `with obs.span(...)` a no-op
        trace = obs.current_trace()
        brownout = self.batcher.brownout
        # brownout threshold rung (ISSUE 8): read once, up front — the
        # annotated fast path below is only valid at the BASE threshold
        # (the sidecar JPEG was drawn without a boost), and the filter
        # further down must agree with that decision for this request
        boost = brownout.threshold_boost_value() if brownout is not None else 0.0
        try:
            # the stage spans carry the detector's own names in the span
            # table; a wait (fetch) is no profiler annotation
            with obs.span("detector.fetch", trace, stage=obs.FETCH):
                image_bytes = await self._fetch_for_request(url, deadline, info)

            with obs.span("detector.decode", trace, stage=obs.DECODE):
                cache_key: str | None = None
                raw_detections: list[dict] | None = None
                annotated: dict | None = None
                if self.cache is not None:
                    cache_key = content_key(
                        self._cache_model, image_bytes, self._cache_threshold
                    )
                    if qset is not None:
                        # the detections depend on the vocabulary too: a
                        # closed-set hit must never answer a queried request
                        # (or two different vocabularies each other)
                        cache_key = f"{cache_key}|q{qset.digest}"
                    # repeat poison: re-raise the cached verdict instead of
                    # letting the same bytes re-poison a batch through the
                    # bisect machinery
                    cached_failure = self.cache.get_negative(cache_key)
                    if cached_failure is not None:
                        _mark_outcome(info, url, "negative")
                        raise cached_failure
                    # brownout serve-stale rung (ISSUE 8): under sustained
                    # saturation an expired-TTL entry beats an engine pass —
                    # the response is marked `degraded: ["stale"]`
                    raw_detections, was_stale, annotated = (
                        self.cache.get_entry_full(
                            cache_key,
                            stale_ok=brownout is not None
                            and brownout.stale_ok(),
                        )
                    )
                    if was_stale and degraded is not None:
                        degraded.add("stale")
                    if raw_detections is not None:
                        _mark_outcome(info, url, "hit")

                # annotated fast hit (ISSUE 11 satellite): the entry carries
                # the finished JPEG + filtered boxes, so the whole pillow
                # round trip (decode + draw + re-encode — most of PR 5's
                # ~3.3 ms hit p50) is skipped. Only at the base threshold:
                # a boosted view must re-filter and re-draw.
                use_annotated = (
                    raw_detections is not None
                    and annotated is not None
                    and boost == 0.0
                )
                if not use_annotated:
                    with obs.span("detector.pil_decode", trace, annotate=True), \
                            Image.open(BytesIO(image_bytes)) as img_raw:
                        # decode-bomb guard: the header-declared pixel count
                        # is checked BEFORE convert() decodes anything
                        # (preprocess.py)
                        check_image_pixels(img_raw)
                        image = img_raw.convert("RGB")

            if use_annotated:
                with obs.span("detector.annotated_hit", trace,
                              stage=obs.POSTPROCESS, annotate=True):
                    return DetectionSuccessResult(
                        url=url,
                        detections=[
                            DetectionResult(
                                label=d["label"], box=list(d["box"])
                            )
                            for d in annotated["detections"]
                        ],
                        labeled_image_base64=base64.b64encode(
                            annotated["jpeg"]
                        ).decode("utf-8"),
                    )

            if raw_detections is None:
                # miss: the content hash rides into the batcher for
                # hash-level coalescing + cache fill on completion
                if cache_key is not None:
                    _mark_outcome(
                        info,
                        url,
                        "coalesced"
                        if self.batcher.in_flight(cache_key)
                        else "miss",
                    )
                raw_detections = await self.batcher.submit(
                    image, deadline=deadline, key=cache_key, cls=cls,
                    qset=qset, tenant=tenant,
                )

            # brownout threshold rung (ISSUE 8): raise the effective
            # detection bar so fewer boxes survive into the draw/encode
            # path (cache entries keep the BASE threshold key — the boost
            # is a view over them, not a new key space)
            if boost > 0.0:
                eff_threshold = min(self._cache_threshold + boost, 0.99)
                raw_detections = [
                    d for d in raw_detections
                    if d.get("score", 1.0) >= eff_threshold
                ]

            # draw, JPEG-encode, base64: inline on the event loop, so its
            # summed time is the one loop's ceiling (detector_loop_ms.bulk)
            with obs.span("detector.draw_encode", trace,
                          stage=obs.POSTPROCESS, annotate=True):
                draw = ImageDraw.Draw(image)
                image_detections: list[DetectionResult] = []
                for det in raw_detections:
                    # open-vocab (ISSUE 13): the client's own queries ARE the
                    # label set — the amenity taxonomy filter only applies to
                    # the closed-set deployment vocabulary
                    amenity = (
                        det["label"] if qset is not None
                        else AMENITIES_MAPPING.get(det["label"])
                    )
                    if amenity is None:
                        continue
                    box = det["box"]
                    draw.rectangle(box, outline="red", width=3)
                    draw.text(
                        xy=(box[0] + 5, box[1] + 5),
                        text=amenity,
                        fill="white",
                        stroke_width=1,
                        stroke_fill="black",
                    )
                    image_detections.append(
                        DetectionResult(label=amenity, box=box)
                    )

                buffer = BytesIO()
                image.save(buffer, format="JPEG")
                jpeg_bytes = buffer.getvalue()
                image_b64 = base64.b64encode(jpeg_bytes).decode("utf-8")

            # annotated sidecar fill (ISSUE 11 satellite): the next hit on
            # this content skips the pillow work we just did. Base
            # threshold only — a boosted view must not poison the base
            # entry with its narrower box set — and attach_annotated
            # itself refuses stale/absent entries.
            if (
                self.cache is not None
                and cache_key is not None
                and boost == 0.0
            ):
                self.cache.attach_annotated(
                    cache_key,
                    jpeg_bytes,
                    [
                        {"label": d.label, "box": list(d.box)}
                        for d in image_detections
                    ],
                )

            return DetectionSuccessResult(
                url=url, detections=image_detections, labeled_image_base64=image_b64
            )
        except DeadlineExceededError as e:
            # structured, bounded-time answer — never a hang (ISSUE 1)
            if trace is not None:
                trace.set_error("deadline", str(e))
            return DetectionErrorResult(url=url, error=f"Deadline exceeded: {e}")
        except AdmissionError:
            # propagate so detect() can turn a fully-shed request into
            # HTTP 429/503; partially-shed requests degrade per image there
            raise
        except FetchError as e:
            if trace is not None:
                trace.set_error("fetch_error", str(e))
            if self.cache is not None and not e.retryable:
                _note_verdict(
                    info, url, "fetch", f"Fetch Error: {e}",
                    self.cache.negative_ttl_s,
                )
            return DetectionErrorResult(url=url, error=f"Fetch Error: {e}")
        except httpx.HTTPError as e:
            if trace is not None:
                trace.set_error("fetch_error", str(e))
            if (
                self.cache is not None
                and isinstance(e, httpx.HTTPStatusError)
                and 400 <= e.response.status_code < 500
                and e.response.status_code not in RETRYABLE_4XX
            ):
                _note_verdict(
                    info, url, "fetch", f"HTTP Error: {e}",
                    self.cache.negative_ttl_s,
                )
            return DetectionErrorResult(url=url, error=f"HTTP Error: {e}")
        except Exception as e:
            tb_str = traceback.format_exc()
            if trace is not None:
                # poison/engine failures pin the trace in the flight
                # recorder's error set under their exception type
                trace.set_error(type(e).__name__, str(e))
            if self.cache is not None and isinstance(e, PoisonImageError):
                # poison is keyed by content hash in the replica cache, but
                # the edge only knows URLs: surface the verdict against the
                # URL that carried the bytes (short TTL bounds the harm if
                # the URL later serves different content)
                _note_verdict(
                    info, url, "poison", f"Processing Error: {e}",
                    self.cache.negative_ttl_s,
                )
            return DetectionErrorResult(url=url, error=f"Processing Error: {e}\n{tb_str}")

    async def detect(
        self,
        payload: dict,
        deadline: Deadline | None = None,
        cls: str | None = None,
        info: dict | None = None,
        tenant: str | None = None,
    ) -> DetectionResponse:
        """`info` (ISSUE 11, optional dict) collects per-URL data-plane
        observations for the HTTP layer: `info["cache"]` maps url ->
        hit|miss|negative|coalesced (the X-Cache header) and
        `info["negative"]` carries deterministic-failure verdicts for the
        X-Spotter-Negative header. Pass None (the default) and nothing is
        collected — the pre-ISSUE-11 path, bit-identical. `tenant`
        (ISSUE 19) rides into every batcher submit so the scheduler's DRR
        ordering and the limiter's revocation scoping see it; None keeps
        the tenant-blind path."""
        request = DetectionRequest.model_validate(payload)
        if deadline is None:
            deadline = Deadline.from_env()
        # Open vocabulary (ISSUE 13): resolve the request's query set ONCE
        # through the text-embedding cache (a repeated vocabulary costs a
        # dict lookup, a novel one pays the text-tower encode off the event
        # loop) — every image in the request shares the resolved set, which
        # is also its batch-compatibility group downstream.
        qset = None
        if request.queries:
            if self._text_resolver is None:
                raise QueriesUnsupportedError(
                    f"model '{self._cache_model}' is closed-set: free-text "
                    f"`queries` need a text-conditioned family (OWL-ViT/OWLv2)"
                )
            qset = await asyncio.get_running_loop().run_in_executor(
                None, self._text_resolver.resolve, list(request.queries)
            )
        urls = [str(u) for u in request.image_urls]
        degraded: set[str] = set()
        tasks = [
            self._process_single_image(
                u, deadline, cls=cls, degraded=degraded, info=info, qset=qset,
                tenant=tenant,
            )
            for u in urls
        ]
        gathered = await asyncio.gather(*tasks, return_exceptions=True)

        shed = [r for r in gathered if isinstance(r, AdmissionError)]
        if shed and len(shed) == len(gathered):
            raise shed[0]  # whole request shed -> HTTP 429/503 + Retry-After

        results: list[ImageResult] = []
        for url, r in zip(urls, gathered):
            if isinstance(r, AdmissionError):
                results.append(DetectionErrorResult(url=url, error=f"Overloaded: {r}"))
            elif isinstance(r, BaseException):
                raise r  # unexpected: _process_single_image contains the rest
            else:
                results.append(r)

        amenities: set[str] = set()
        for result in results:
            if isinstance(result, DetectionSuccessResult):
                amenities.update(d.label for d in result.detections)

        description = (
            f"The property contains: {', '.join(sorted(amenities))}."
            if amenities
            else "No relevant amenities detected."
        )
        # the `degraded:` marker contract (ISSUE 8): absent from the wire
        # unless a brownout concession actually shaped THIS response —
        # "stale" when any image was served from an expired cache entry,
        # plus the globally-active rung markers ("bucket_cap", "threshold")
        brownout = self.batcher.brownout
        if brownout is not None:
            degraded.update(brownout.markers())
        return DetectionResponse(
            amenities_description=description,
            images=results,
            degraded=sorted(degraded) if degraded else None,
        )

    def check_admission(
        self, cls: str | None = None, tenant: str | None = None
    ) -> AdmissionError | None:
        """HTTP-layer fast path: an AdmissionError to answer with (mapped to
        429/503 + Retry-After) before any fetch work, or None to proceed.
        Never consumes the breaker's half-open probe slot — a request that
        could probe must reach `MicroBatcher.submit` to do so. `cls`
        ("slo"|"bulk") lets the deepest brownout rung shed bulk BEFORE the
        fetch spends bytes on work the batcher would refuse anyway;
        `tenant` (ISSUE 19) scopes that rung so only over-share tenants
        brown out while in-quota tenants keep full service."""
        if self.batcher.draining:
            self.engine.metrics.record_shed()
            return DrainingError("server draining")
        breaker = self.batcher.breaker
        if breaker.would_reject():
            self.engine.metrics.record_shed()
            return CircuitOpenError(
                "circuit breaker open", retry_after_s=breaker.retry_after_s()
            )
        brownout = self.batcher.brownout
        if brownout is not None and cls == BULK:
            brownout.evaluate()
            if brownout.shed_bulk(tenant):
                self.engine.metrics.record_shed()
                self.engine.metrics.record_admit_shed(BULK)
                return BrownoutShedError(
                    f"brownout: bulk traffic shed (rung {brownout.rung})",
                    retry_after_s=jittered_retry_after(brownout.disarm_s),
                )
        return None

    def health(self) -> dict:
        """Readiness snapshot for /healthz: not-ready while the breaker is
        open/probing or a drain is in progress (liveness is /livez)."""
        breaker = self.batcher.breaker
        draining = self.batcher.draining
        ready = breaker.state == CircuitBreaker.CLOSED and not draining
        dp = getattr(self.engine, "dp", 1)
        initial_dp = getattr(self.engine, "initial_dp", dp)
        # brownout state (ISSUE 8): a browned-out replica is READY (it
        # serves, shedding quality for survival) but /healthz says so —
        # `status=brownout` outranks the dp-degraded label because it is
        # the condition an operator can influence (shift load away)
        brownout = self.batcher.brownout
        brownout_rung = brownout.evaluate() if brownout is not None else 0
        return {
            "status": (
                "brownout" if ready and brownout_rung > 0
                else "ok" if ready and dp >= initial_dp
                else "degraded" if ready
                else "unready"
            ),
            # overload-control tier state: absent-as-disabled mirrors the
            # cache block below
            "brownout": (
                brownout.snapshot() if brownout is not None
                else {"enabled": False}
            ),
            "admit": (
                self.batcher.limiter.snapshot()
                if self.batcher.limiter is not None
                else {"enabled": False}
            ),
            "ready": ready,
            "breaker": breaker.state,
            "draining": draining,
            # deployment identity (ISSUE 15): which build/weights this
            # replica serves — a mixed-version window during a rollout is
            # auditable per pod, same as the topology flags below
            "version": self.engine.metrics.version,
            # what JAX placed the engine on (platform, device_kind, count);
            # None for the model-free stub engine
            "device": getattr(self.engine, "device_info", None),
            # ingest/topology config (ISSUE 3): which serving shape this
            # replica runs — dp width and whether preprocess is on-device —
            # so a fleet rollout of the new pipeline is auditable per pod
            "dp": dp,
            # tensor-parallel topology (ISSUE 13): the RESOLVED mesh this
            # replica actually serves on (tp=1 single-chip included) plus
            # which knob produced it — the MESH-vs-SERVE_DP/TP precedence
            # is auditable here instead of silently losing (satellite 2)
            "tp": getattr(self.engine, "tp", 1),
            "mesh": (
                {
                    "dp": dp,
                    "tp": getattr(self.engine, "tp", 1),
                    "source": getattr(self.engine, "mesh_source", None),
                }
                if getattr(self.engine, "mesh", None) is not None
                else None
            ),
            # open-vocabulary capability (ISSUE 13): whether this replica
            # accepts free-text `queries`, with the text-embedding cache's
            # size state when it does
            "open_vocab": (
                self._text_resolver.stats()
                if self._text_resolver is not None
                else {"enabled": False}
            ),
            "device_preprocess": getattr(self.engine, "device_preprocess", False),
            # ragged scheduling (ISSUE 9): which dispatch policy this
            # replica runs (FIFO unless SPOTTER_TPU_RAGGED=1), auditable
            # per pod like the ingest/topology flags above
            "ragged": self.batcher.scheduler.ragged,
            # engine fault domain (ISSUE 4): lost-shard degradation state
            "dp_degraded": (
                {"from": initial_dp, "to": dp} if dp < initial_dp else None
            ),
            "engine_generation": getattr(self.engine, "generation", 0),
            # caching tier (ISSUE 5): size state for fleet dashboards; the
            # hit/miss/coalesce counters live in /metrics
            "cache": (
                self.cache.stats() if self.cache is not None
                else {"enabled": False}
            ),
            # device-efficiency plane (ISSUE 10): fast/slow-window error-
            # budget burn over deadline misses + sheds — the brownout
            # ladder's effect shows up here as budget recovery
            "slo_burn": self.engine.metrics.perf.slo.block(),
            # tenant isolation plane (ISSUE 19): quota/fairness state when
            # configured; absent-as-disabled mirrors the cache block
            "tenancy": (
                self.tenancy.snapshot() if self.tenancy is not None
                else {"enabled": False}
            ),
        }

    async def drain(self, timeout_s: float | None = None) -> dict:
        """Stop admitting, flush the queue, wait for in-flight batches.
        `timeout_s` (ISSUE 15) overrides the env-default drain window —
        the /drain handler maps its `deadline_ms` body field here so a
        rollout retire (or k8s preStop) waits exactly as long as it can
        afford."""
        return await self.batcher.drain(timeout_s)

    async def aclose(self) -> None:
        await self.batcher.stop()
        await self.client.aclose()
