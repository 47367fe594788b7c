"""Serving bootstrap: MODEL_NAME env -> engine -> detector (+ Ray adapter).

Mirrors the reference's module-import bootstrap (serve.py:199-205): MODEL_NAME
is required and raises if unset; the built app object is what the RayService
manifest names as import_path (rayservice-template.yaml:8-9).

Ray Serve is optional in this build (it is the production fabric when
installed — reference pyproject.toml:11 — but the framework degrades to the
standalone aiohttp server, and tests never need Ray, matching the reference's
own practice of testing the undecorated class: test_serve.py:32).
"""

import logging
import os

from spotter_tpu import obs
from spotter_tpu.engine.batcher import MicroBatcher
from spotter_tpu.engine.engine import InferenceEngine, default_batch_buckets
from spotter_tpu.models import build_detector
from spotter_tpu.models.registry import family_for
from spotter_tpu.serving.detector import AmenitiesDetector

logger = logging.getLogger(__name__)

DETECTION_THRESHOLD = 0.5  # serve.py:107

SERVE_DP_ENV = "SPOTTER_TPU_SERVE_DP"
SERVE_TP_ENV = "SPOTTER_TPU_SERVE_TP"
MESH_ENV = "SPOTTER_TPU_MESH"


def serve_dp_from_env() -> int:
    """SPOTTER_TPU_SERVE_DP: data-parallel serving width (0/1/unset = one
    chip; `all` = every local chip). Malformed values fail loudly."""
    raw = os.environ.get(SERVE_DP_ENV, "").strip()
    if not raw:
        return 1
    if raw.lower() == "all":
        import jax

        return max(1, len(jax.local_devices()))
    if not raw.isdigit():
        raise ValueError(f"{SERVE_DP_ENV} must be a positive int or 'all', got {raw!r}")
    return max(1, int(raw))


def serve_tp_from_env() -> int:
    """SPOTTER_TPU_SERVE_TP: tensor-parallel width (0/1/unset = params whole
    on every chip). Composes with SERVE_DP into a dp×tp mesh; the bucket
    ladder scales by dp ONLY — tp splits weights, not the batch."""
    raw = os.environ.get(SERVE_TP_ENV, "").strip()
    if not raw:
        return 1
    if not raw.isdigit():
        raise ValueError(f"{SERVE_TP_ENV} must be a positive int, got {raw!r}")
    return max(1, int(raw))


def parse_mesh_spec(spec: str) -> dict[str, int]:
    """"dp=4" / "dp=4,tp=2" -> {"dp": 4, "tp": 2} (the SPOTTER_TPU_MESH knob)."""
    out = {"tp": 1}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, value = part.partition("=")
        if key not in ("dp", "tp") or not value.isdigit() or int(value) < 1:
            raise ValueError(
                f"bad SPOTTER_TPU_MESH entry '{part}' (expected dp=<n>[,tp=<n>])"
            )
        out[key] = int(value)
    if "dp" not in out:
        raise ValueError(f"SPOTTER_TPU_MESH '{spec}' must set dp=<n>")
    return out


def parse_batch_buckets(spec: str) -> tuple[int, ...]:
    """SPOTTER_TPU_BATCH_BUCKETS: comma-separated ascending bucket ladder."""
    try:
        buckets = tuple(int(v) for v in spec.split(","))
    except ValueError:
        buckets = ()
    if not buckets or any(b < 1 for b in buckets) or list(buckets) != sorted(
        set(buckets)
    ):
        raise ValueError(
            f"SPOTTER_TPU_BATCH_BUCKETS must be ascending positive ints, "
            f"got {spec!r}"
        )
    return buckets


def build_detector_app(
    model_name: str | None = None,
    threshold: float = DETECTION_THRESHOLD,
    batch_buckets: tuple[int, ...] | None = None,
    max_delay_ms: float = 5.0,
    warmup: bool = False,
    mesh_spec: str | None = None,
    serve_dp: int | None = None,
    cache_mb: float | None = None,
) -> AmenitiesDetector:
    model_name = model_name or os.environ.get("MODEL_NAME")
    if not model_name:
        raise ValueError("MODEL_NAME environment variable not set.")
    # Warm restart (ISSUE 2): arm JAX's persistent compilation cache
    # (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) before the
    # first jit — a preempted replica restarting on the same model + bucket
    # ladder then loads its compiled programs from disk instead of
    # recompiling them, which is most of time_to_ready_s.
    from spotter_tpu.serving.lifecycle import enable_compile_cache

    enable_compile_cache()
    env_buckets = False
    if batch_buckets is None:
        # Per-model ladder tuning is a deployment concern: R18's per-chip
        # peak is batch 16 (485 vs 449 img/s — pre-round note, round 4, git
        # history), R101's is batch 8; the default stays the conservative
        # 8-max.
        # `is not None` (not truthiness): an explicitly-set empty value is
        # a malformed spec and must raise, not silently serve the default.
        spec = os.environ.get("SPOTTER_TPU_BATCH_BUCKETS")
        env_buckets = spec is not None
        batch_buckets = (
            parse_batch_buckets(spec)
            if spec is not None
            else default_batch_buckets()
        )

    # Sharded serving (VERDICT r1 weak #5): SPOTTER_TPU_MESH=dp=4[,tp=2]
    # builds a mesh and the engine shards batches over "dp" / params over
    # "tp"; unset means the single-device path (one Serve replica per chip,
    # Ray pinning each replica via TPU_VISIBLE_CHIPS).
    mesh = None
    tp_rules = ()
    mesh_source = None
    mesh_spec = mesh_spec or os.environ.get(MESH_ENV)
    # dp×tp serving as a first-class config (ISSUES 3 + 13):
    # SPOTTER_TPU_SERVE_DP=<n|all> shards the batch over n chip GROUPS and
    # SPOTTER_TPU_SERVE_TP=<m> splits the params m-way inside each group.
    # Unlike the expert SPOTTER_TPU_MESH knob (which keeps the configured
    # ladder and merely rounds it up), the bucket ladder here stays per-
    # group semantics and is scaled by dp ONLY: the batcher fills
    # dp × per_chip_bucket before dispatch — tp splits weights, never the
    # batch, so each tp group keeps the batch the ladder was tuned for.
    serve_dp_set = serve_dp is not None or bool(
        os.environ.get(SERVE_DP_ENV, "").strip()
    )
    serve_tp_set = bool(os.environ.get(SERVE_TP_ENV, "").strip())
    if mesh_spec:
        mesh_source = MESH_ENV
        if serve_dp_set or serve_tp_set:
            # the knob conflict, loud instead of silent (ISSUE 13 satellite:
            # SERVE_DP previously just lost here with no trace)
            logger.warning(
                "%s=%r wins over %s/%s — the SERVE_* knobs are ignored while"
                " an explicit mesh spec is set; the resolved mesh is surfaced"
                " in /healthz",
                MESH_ENV, mesh_spec, SERVE_DP_ENV, SERVE_TP_ENV,
            )
    else:
        dp = serve_dp if serve_dp is not None else serve_dp_from_env()
        tp = serve_tp_from_env()
        if dp > 1 or tp > 1:
            batch_buckets = tuple(b * dp for b in batch_buckets)
            mesh_spec = f"dp={dp},tp={tp}"
            mesh_source = (
                f"{SERVE_DP_ENV} x {SERVE_TP_ENV}" if tp > 1 else SERVE_DP_ENV
            )
    if mesh_spec:
        from spotter_tpu.parallel import initialize_multihost, make_mesh

        # Multi-host bring-up belongs to the SPMD-mesh mode ONLY: exactly one
        # process per host may join jax.distributed, which is true when the
        # replica owns the whole host's chips via a mesh — and false in the
        # per-chip-replica mode, where N replicas per pod would all race to
        # register the same TPU_WORKER_ID. jax.distributed must be
        # initialized before any backend use, hence before make_mesh; the
        # single-host case is a no-op (multihost.py).
        initialize_multihost()

        axes = parse_mesh_spec(mesh_spec)
        if env_buckets and any(b % axes["dp"] for b in batch_buckets):
            # An OPERATOR-configured ladder that doesn't divide the dp axis
            # is a config contradiction: reject up front with both knobs
            # named (ISSUE 13 satellite) instead of silently rounding up.
            # Constructor-arg ladders (library/tests) keep the engine's
            # documented round-up semantics.
            raise ValueError(
                f"SPOTTER_TPU_BATCH_BUCKETS={list(batch_buckets)} not "
                f"divisible by dp={axes['dp']} (from "
                f"{mesh_source or MESH_ENV}): every bucket must split "
                f"evenly across the dp axis"
            )
        mesh = make_mesh(
            dp=axes["dp"], tp=axes["tp"], source=mesh_source or MESH_ENV
        )
        # Per-family TP rule set from the registry (ISSUE 13): tp=2 on an
        # OWL-ViT deployment shards the CLIP towers, RT-DETR its
        # encoder/decoder stacks; non-matching params fall back to
        # replicated, and a rule matching NOTHING fails loud in the engine
        # (sharding.check_rules_cover).
        tp_rules = family_for(model_name).tp_rules if axes["tp"] > 1 else ()

    # set-up phases (`setup_phases_s` in /metrics): the checkpoint read and
    # converted, then the params put on the device(s) and the programs jitted
    with obs.span("setup.weights_load"):
        built = build_detector(model_name)
    with obs.span("setup.engine_place"):
        engine = InferenceEngine(
            built,
            threshold=threshold,
            batch_buckets=batch_buckets,
            mesh=mesh,
            tp_rules=tp_rules,
        )
    # /healthz surfaces which knob produced the serving mesh (satellite 2)
    engine.mesh_source = mesh_source
    if warmup:
        engine.warmup()
    # Resilience knobs (ISSUE 1) ride the environment into the batcher:
    # SPOTTER_TPU_QUEUE_DEPTH (bounded admission queue),
    # SPOTTER_TPU_BATCH_TIMEOUT_MS (hung-engine watchdog),
    # SPOTTER_TPU_BREAKER_THRESHOLD / _COOLDOWN_S (circuit breaker) are read
    # inside MicroBatcher/CircuitBreaker; SPOTTER_TPU_MAX_IN_FLIGHT is the
    # dispatch-depth knob that already existed as a constructor arg.
    max_in_flight = int(os.environ.get("SPOTTER_TPU_MAX_IN_FLIGHT", "2"))
    batcher = MicroBatcher(engine, max_delay_ms=max_delay_ms, max_in_flight=max_in_flight)
    # Caching tier (ISSUE 5): opt-in result cache + single-flight coalescing
    # in front of the engine. SPOTTER_TPU_CACHE_MAX_MB (or the explicit
    # `cache_mb` arg, i.e. --cache-mb) arms it; unset/0 constructs none of
    # the machinery — SPOTTER_TPU_CACHE_TTL_S / _CACHE_NEGATIVE_TTL_S bound
    # entry lifetimes when it is on.
    if cache_mb is None:
        return AmenitiesDetector(engine, batcher)
    from spotter_tpu.caching.result_cache import ResultCache

    cache = ResultCache.from_env(metrics=engine.metrics, max_mb=cache_mb)
    return AmenitiesDetector(engine, batcher, cache=cache)


def explain_sharding(
    model_name: str | None = None, mesh_spec: str | None = None
) -> str:
    """The `--explain-sharding` dump (ISSUE 13): build the model + the
    resolved serving mesh and report param path -> PartitionSpec ->
    per-device bytes, plus the dead-rule list. Read-only: no engine, no
    warmup, no compile — just the param tree and the rule set.
    """
    from spotter_tpu.parallel import make_mesh
    from spotter_tpu.parallel.sharding import (
        format_sharding_report,
        sharding_report,
    )

    model_name = model_name or os.environ.get("MODEL_NAME")
    if not model_name:
        raise ValueError("MODEL_NAME environment variable not set.")
    mesh_spec = mesh_spec or os.environ.get(MESH_ENV)
    if mesh_spec:
        axes = parse_mesh_spec(mesh_spec)
        source = MESH_ENV
    else:
        dp = serve_dp_from_env()
        tp = serve_tp_from_env()
        axes = {"dp": dp, "tp": tp}
        source = f"{SERVE_DP_ENV} x {SERVE_TP_ENV}"
    mesh = make_mesh(dp=axes["dp"], tp=axes["tp"], source=source)
    family = family_for(model_name)
    rules = family.tp_rules if axes["tp"] > 1 else ()
    built = build_detector(model_name)
    report = sharding_report(built.params, mesh, rules)
    header = (
        f"model {model_name} (family {family.name}), "
        f"{len(rules)} TP rule(s) active"
    )
    return header + "\n" + format_sharding_report(report)


def ray_deployment():
    """Ray Serve deployment graph node (the manifest's import_path target)."""
    from ray import serve
    from starlette.requests import Request

    @serve.deployment
    class RayAmenitiesDetector:
        def __init__(self, model_name: str) -> None:
            self._inner = build_detector_app(model_name, warmup=True)

        async def __call__(self, raw_payload: "Request"):
            return await self._inner.detect(await raw_payload.json())

    model_name = os.environ.get("MODEL_NAME")
    if not model_name:
        raise ValueError("MODEL_NAME environment variable not set.")
    return RayAmenitiesDetector.bind(model_name)


try:  # module-level `deployment` preserved for manifest import_path parity
    import ray  # noqa: F401
except ImportError:  # Ray not installed — standalone mode
    deployment = None
else:
    # With Ray present, real bootstrap errors (missing MODEL_NAME, model load
    # failure) must propagate like the reference's import-time raise
    # (serve.py:199-201), not turn into an opaque import_path=None deploy.
    deployment = ray_deployment()
