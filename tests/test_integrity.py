"""Output-integrity plane tests (ISSUE 17): the shared detection-diff
comparator (edge-case fuzz), the content-deterministic stub engine, the
golden probe + on-device weights attestation, verified readiness with the
exit-86 path, hard quarantine at the pool, quorum sampling with
third-replica arbitration, the supervisor's full exit-code ladder in one
table, and the INTEGRITY chaos matrix."""

import asyncio
import os
import sys
import time
import types

import numpy as np
import pytest
from PIL import Image

from spotter_tpu.obs import compare
from spotter_tpu.serving import integrity, lifecycle
from spotter_tpu.testing import faults
from spotter_tpu.testing.stub_engine import (
    StubEngine,
    StubHttpClient,
    content_fingerprint,
)

# ---------------------------------------------------------------------------
# obs/compare.py — the shared comparator (satellite: extracted from rollout)

TV = {"label": "tv", "score": 0.90, "box": [2.0, 2.0, 20.0, 24.0]}
BED = {"label": "bed", "score": 0.70, "box": [5.0, 5.0, 30.0, 30.0]}


def test_compare_empty_detections():
    assert compare.detections_equivalent([], [])
    assert not compare.detections_equivalent([TV], [])
    assert not compare.detections_equivalent([], [TV])
    # empty image lists and count mismatches
    assert compare.images_equivalent([], [])
    assert not compare.images_equivalent([[TV]], [])
    assert compare.images_equivalent([[]], [[]])
    assert compare.diff_detections([], []) is None
    assert compare.diff_detections([TV], []) is not None


def test_compare_label_permutation_is_order_invariant():
    a = [dict(TV), dict(BED)]
    b = [dict(BED), dict(TV)]  # same set, different order
    assert compare.detections_equivalent(a, b)
    # a LABEL swap (same scores/boxes, different labels) is NOT equivalent
    swapped = [dict(TV, label="bed"), dict(BED, label="tv")]
    assert not compare.detections_equivalent(a, swapped)


def test_compare_near_threshold_score_flutter():
    """Scores fluttering around a rounding boundary must compare EQUAL
    under the tolerance matcher — 0.494 vs 0.496 round to different 2dp
    values, and one false diff here could start a quarantine countdown."""
    a = [dict(TV, score=0.494)]
    b = [dict(TV, score=0.496)]
    assert compare.detections_equivalent(a, b)  # |d| = .002 << tol .05
    # just inside vs just past the tolerance
    assert compare.detections_equivalent(
        [dict(TV, score=0.50)], [dict(TV, score=0.549)]
    )
    assert not compare.detections_equivalent(
        [dict(TV, score=0.50)], [dict(TV, score=0.56)]
    )


def test_compare_box_order_and_tolerance():
    a = [dict(TV, box=[2.0, 2.0, 20.0, 24.0])]
    assert compare.detections_equivalent(
        a, [dict(TV, box=[3.9, 0.1, 21.9, 22.1])]  # every coord within 2px
    )
    assert not compare.detections_equivalent(
        a, [dict(TV, box=[2.0, 2.0, 20.0, 27.0])]  # one coord 3px off
    )
    # a box-less detection only matches a box-less detection
    assert compare.detections_equivalent(
        [{"label": "tv", "score": 0.9}], [{"label": "tv", "score": 0.9}]
    )
    assert not compare.detections_equivalent(
        [{"label": "tv", "score": 0.9}], [dict(TV)]
    )


def test_compare_rollout_reexport_intact():
    """rollout.py re-exports the moved normalizer; the 2dp shadow-diff
    semantics must be byte-compatible with the pre-extraction local."""
    from spotter_tpu.serving.rollout import _norm_detections

    assert _norm_detections is compare.norm_detections
    assert compare.norm_detections(
        [{"detections": [dict(TV, score=0.904)]}]
    ) == compare.norm_detections([{"detections": [dict(TV, score=0.898)]}])


# ---------------------------------------------------------------------------
# stub engine determinism (satellite bugfix: input-independent detections
# made every diff-based test vacuous)


def _pil(fill: int):
    return Image.fromarray(np.full((8, 8, 3), fill % 256, np.uint8))


def test_stub_detections_are_function_of_input_content():
    eng_a, eng_b = StubEngine(), StubEngine()
    img1, img2 = _pil(10), _pil(200)
    # same input -> same output, across engine instances (honest replicas
    # with the same weights must agree)
    out_a = eng_a.detect([img1])[0]
    out_b = eng_b.detect([img1])[0]
    assert out_a == out_b
    # different input -> measurably different output (the regression: the
    # old stub answered identically for EVERY input)
    assert content_fingerprint(img1) != content_fingerprint(img2)
    assert eng_a.detect([img2])[0] != out_a
    # and repeatable
    assert eng_a.detect([img1])[0] == out_a


def test_stub_attest_catches_corrupt_weights():
    eng = StubEngine()
    assert eng.attest()["ok"]
    before = eng.detect([_pil(10)])[0]
    eng.corrupt_weights(1)
    report = eng.attest()
    assert not report["ok"] and report["mismatched"] == ["stub:0"]
    # the corruption perturbs outputs past the comparator tolerance — the
    # same signature a flipped real weight bit produces
    after = eng.detect([_pil(10)])[0]
    assert not compare.detections_equivalent(before, after)
    # corrupting one stub must not leak into others (deep-copy regression)
    assert StubEngine().attest()["ok"]


# ---------------------------------------------------------------------------
# faults.py seams


def test_faults_env_parses_integrity_keys(monkeypatch):
    monkeypatch.setenv(
        faults.FAULTS_ENV,
        "sdc=25,corrupt_weights=2,corrupt_compile_cache=1",
    )
    plan = faults.maybe_activate_from_env()
    try:
        assert plan.sdc == 25
        assert plan.corrupt_weights == 2
        assert plan.corrupt_compile_cache == 1
    finally:
        faults._active = None


def test_perturb_detections_exceeds_tolerance():
    dets = [dict(TV), dict(TV, score=0.2)]
    out = faults.perturb_detections(dets)
    assert not compare.detections_equivalent(dets, out)
    for d in out:
        assert 0.0 <= d["score"] <= 1.0


def test_corrupt_detections_bresenham_and_scope():
    with faults.inject(sdc=50, only_replica="r0"):
        fired = sum(
            faults.corrupt_detections([dict(TV)], "r0") != [dict(TV)]
            for _ in range(8)
        )
        assert fired == 4  # exact 50% share, no RNG
        # out-of-scope replica: never corrupted
        for _ in range(8):
            assert faults.corrupt_detections([dict(TV)], "r1") == [dict(TV)]
    # unarmed: passthrough
    assert faults.corrupt_detections([dict(TV)], "r0") == [dict(TV)]


def test_take_corrupt_weights_consumes_whole():
    with faults.inject(corrupt_weights=3):
        assert faults.take_corrupt_weights() == 3
        assert faults.take_corrupt_weights() == 0  # consumed whole
    assert faults.take_corrupt_weights() == 0


# ---------------------------------------------------------------------------
# golden probe + attestor + plane


def _stub_det():
    from spotter_tpu.engine.batcher import MicroBatcher
    from spotter_tpu.serving.detector import AmenitiesDetector

    eng = StubEngine()
    return AmenitiesDetector(
        eng, MicroBatcher(eng, max_delay_ms=1.0), StubHttpClient()
    )


def test_probe_image_deterministic():
    a, b = integrity.probe_image("stub"), integrity.probe_image("stub")
    assert a.tobytes() == b.tobytes()
    assert (
        integrity.probe_image("stub").tobytes()
        != integrity.probe_image("owlv2").tobytes()
    )


def test_golden_probe_pinned_stub_passes_and_catches_corruption():
    det = _stub_det()

    async def run():
        probe = integrity.GoldenProbe("stub")
        assert probe.reference is not None  # pinned in the registry
        assert await probe.run(det.batcher) is None
        # corrupt the live weights: the probe's answer moves past tolerance
        det.engine.corrupt_weights(1)
        reason = await probe.run(det.batcher)
        assert reason is not None and "tol" in reason
        snap = probe.snapshot()
        assert snap["probes_total"] == 2 and snap["failures_total"] == 1
        await det.batcher.stop()

    asyncio.run(run())


def test_golden_probe_self_pins_unknown_family():
    det = _stub_det()

    async def run():
        probe = integrity.GoldenProbe("some-unpinned-model")
        assert probe.reference is None
        assert await probe.run(det.batcher) is None  # first run self-pins
        assert probe.reference is not None
        assert await probe.run(det.batcher) is None  # and must keep matching
        await det.batcher.stop()

    asyncio.run(run())


def test_plane_verify_corrupt_compile_cache_attests_clean_probe_fails():
    """The miscompiled-restore shape: weights attest CLEAN (the cache
    poisoned the executable, not the params) — only the probe catches it."""
    det = _stub_det()

    async def run():
        exits = []
        plane = integrity.IntegrityPlane(
            det.engine, det.batcher, family="stub",
            probe_interval_s=0, attest_interval_s=0, exit_cb=exits.append,
        )
        with faults.inject(corrupt_compile_cache=1):
            ok = await plane.verify("warm-restore")
        assert not ok
        assert plane.attestor.failures_total == 0  # attest was clean
        assert plane.probe.failures_total == 1
        # the fault is consume-once: a re-verify (post cold restart) passes
        assert await plane.verify("cold-start")
        await det.batcher.stop()

    asyncio.run(run())


def test_plane_periodic_loop_exits_86_on_corruption():
    det = _stub_det()

    async def run():
        exits = []
        plane = integrity.IntegrityPlane(
            det.engine, det.batcher, family="stub",
            probe_interval_s=0.05, attest_interval_s=0.05,
            exit_cb=exits.append,
        )
        assert await plane.verify("cold-start")
        await plane.start()
        det.engine.corrupt_weights(1)  # silent corruption mid-serving
        for _ in range(100):
            if exits:
                break
            await asyncio.sleep(0.02)
        assert exits == [lifecycle.INTEGRITY_EXIT_CODE]
        await plane.aclose()
        await det.batcher.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# on-device attestation over real jax arrays (CPU; shards across however
# many devices the platform exposes — CI runs this with 2 virtual devices)


def test_engine_attest_bit_exact_across_dtypes_and_shards():
    import jax

    from spotter_tpu.engine.engine import InferenceEngine

    params = {
        "w_f32": jax.numpy.arange(64, dtype=jax.numpy.float32) / 7.0,
        "w_i8": jax.numpy.array([-1, 0, 1, 127, -128], dtype=jax.numpy.int8),
        "w_bf16": jax.numpy.arange(32, dtype=jax.numpy.bfloat16) / 3.0,
    }
    host = {k: np.asarray(v) for k, v in params.items()}
    fake = types.SimpleNamespace(
        params=params, built=types.SimpleNamespace(params=host)
    )
    report = InferenceEngine.attest(fake)
    assert report["ok"], report
    assert report["checked"] >= 1
    assert report["observed"] == report["expected"]

    # a single flipped element on device is caught; host copy is pristine
    InferenceEngine.corrupt_weights(fake, 1)
    report = InferenceEngine.attest(fake)
    assert not report["ok"]
    assert report["mismatched"]

    # sharded placement: same checksums wherever the shards live
    devs = jax.devices()
    if len(devs) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(np.array(devs), ("dp",))
        arr = jax.device_put(
            jax.numpy.arange(len(devs) * 8, dtype=jax.numpy.float32),
            NamedSharding(mesh, PartitionSpec("dp")),
        )
        fake2 = types.SimpleNamespace(
            params={"w": arr},
            built=types.SimpleNamespace(params={"w": np.asarray(arr)}),
        )
        report = InferenceEngine.attest(fake2)
        assert report["ok"], report
        assert report["checked"] == len(devs)  # one checksum per device


# ---------------------------------------------------------------------------
# hard quarantine at the pool


def _pool(n=3):
    from spotter_tpu.serving.replica_pool import ReplicaPool

    return ReplicaPool(
        [f"http://10.0.0.{i}:80" for i in range(n)], health_interval_s=3600
    )


def test_pool_quarantine_zero_weight_and_refusals():
    pool = _pool(3)
    url = pool.replicas[0].url
    assert pool.quarantine(url, reason="test")
    assert not pool.replicas[0].available(time.monotonic())
    assert pool.quarantines_total == 1
    # idempotent refusal + unknown refusal, both counted
    assert not pool.quarantine(url)
    assert not pool.quarantine("http://nope:1")
    assert pool.quarantines_refused_total == 2
    snap = pool.snapshot()
    assert snap["pool_quarantines_total"] == 1
    assert snap["replicas"][0]["quarantined"]
    assert snap["replicas"][0]["quarantine_reason"] == "test"
    assert pool.unquarantine(url)
    assert pool.replicas[0].available(time.monotonic())


def test_pool_quarantine_never_takes_last_available_replica():
    pool = _pool(2)
    assert pool.quarantine(pool.replicas[0].url)
    # refusing the last one: wrong answers from ONE suspect replica beat a
    # full outage of the pool — and the refusal is loud, not silent
    assert not pool.quarantine(pool.replicas[1].url)
    assert pool.replicas[1].available(time.monotonic())


def test_pool_pick_other_excludes():
    pool = _pool(3)
    urls = [r.url for r in pool.replicas]
    w = pool.pick_other(exclude=(urls[0],))
    assert w in urls[1:]
    third = pool.pick_other(exclude=(urls[0], w))
    assert third in urls and third not in (urls[0], w)
    assert pool.pick_other(exclude=tuple(urls)) is None


# ---------------------------------------------------------------------------
# quorum sampler: Bresenham share + arbitration attribution


def test_quorum_take_exact_share():
    q = integrity.QuorumSampler(_pool(3), pct=25.0)
    assert sum(q.take() for _ in range(100)) == 25


class _ScriptedClient:
    """Answers /detect per url: callable -> body dict, None -> HTTP 500."""

    def __init__(self, answers):
        self.answers = answers

    async def post(self, url, json=None):
        base = url.rsplit("/detect", 1)[0]
        fn = self.answers[base]
        body = fn() if callable(fn) else fn

        class R:
            status_code = 500 if body is None else 200

            def json(self):
                return body

        return R()


def _quorum_fleet(n=3):
    pool = _pool(n)
    q = integrity.QuorumSampler(
        pool, pct=100.0, ewma_threshold=0.6, min_samples=2, alpha=0.5
    )
    return pool, q, [r.url for r in pool.replicas]


GOOD = {"images": [{"url": "u", "detections": [dict(TV)]}]}
BAD = {"images": [{"url": "u", "detections": [dict(TV, score=0.2)]}]}


def test_quorum_arbitration_charges_only_the_deviant():
    pool, q, urls = _quorum_fleet(3)
    corrupt = urls[0]
    client = _ScriptedClient(
        {corrupt: BAD, urls[1]: GOOD, urls[2]: GOOD}
    )

    async def run():
        import json as j

        # honest primary, corrupt witness possible: drive samples with the
        # corrupt replica as PRIMARY — the arbiter must side against it
        for _ in range(3):
            await q.run_one(client, {}, j.dumps(BAD), corrupt)
        assert q.disagreements_total == 3
        assert q.arbitrations_total == 3
        # only the deviant crossed the threshold
        assert not pool.replicas[0].available(time.monotonic())
        assert pool.replicas[1].available(time.monotonic()) and pool.replicas[2].available(time.monotonic())
        assert q.quarantines_total == 1
        # honest witnesses were charged NOTHING
        snap = q.snapshot()
        assert snap["ewma"][corrupt] >= 0.6
        for u in urls[1:]:
            assert snap["ewma"].get(u, 0.0) == 0.0

    asyncio.run(run())


def test_quorum_two_fleet_charges_both_but_honest_decays():
    """No third replica to arbitrate: both sides are charged on a
    disagreement — the EWMA's decay on agreeing samples is what keeps an
    honest replica under threshold over time."""
    pool, q, urls = _quorum_fleet(2)
    client = _ScriptedClient({urls[0]: GOOD, urls[1]: BAD})

    async def run():
        import json as j

        await q.run_one(client, {}, j.dumps(GOOD), urls[0])
        assert q.disagreements_total == 1 and q.arbitrations_total == 0
        snap = q.snapshot()
        assert snap["ewma"][urls[0]] == snap["ewma"][urls[1]] == 0.5

    asyncio.run(run())


def test_quorum_witness_error_not_charged():
    pool, q, urls = _quorum_fleet(3)
    client = _ScriptedClient({u: None for u in urls})  # every witness 500s

    async def run():
        import json as j

        for _ in range(5):
            await q.run_one(client, {}, j.dumps(GOOD), urls[0])
        assert q.errors_total == 5
        assert q.compared_total == 0 and q.disagreements_total == 0
        assert q.snapshot()["ewma"] == {}  # transport failure charges no one
        for r in pool.replicas:
            assert r.available(time.monotonic())

    asyncio.run(run())


# ---------------------------------------------------------------------------
# verified readiness through the real standalone bring-up (stub engine)


def test_bringup_verifies_then_ready(monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.serving.standalone import make_app

    monkeypatch.setenv("SPOTTER_TPU_STUB_ENGINE", "1")

    async def run():
        exits = []
        app = make_app(
            model_name=None, warmup=False,
            bringup_exit_cb=exits.append, integrity_exit_cb=exits.append,
        )
        async with TestClient(TestServer(app)) as client:
            for _ in range(300):
                r = await client.get("/startupz")
                if r.status == 200:
                    break
                await asyncio.sleep(0.01)
            assert r.status == 200
            snap = await (await client.get("/metrics")).json()
            integ = snap["integrity"]
            assert integ["verifications_total"] == 1
            assert integ["verification_failures_total"] == 0
            assert integ["probe"]["pinned"]
            assert not exits
            await app["detector"].batcher.stop()

    asyncio.run(run())


def test_bringup_corrupt_weights_exits_86_before_traffic(monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.serving.standalone import make_app

    monkeypatch.setenv("SPOTTER_TPU_STUB_ENGINE", "1")
    monkeypatch.setenv(faults.FAULTS_ENV, "corrupt_weights=1")
    faults.maybe_activate_from_env()

    async def run():
        exits = []
        app = make_app(
            model_name=None, warmup=False,
            bringup_exit_cb=exits.append, integrity_exit_cb=exits.append,
        )
        async with TestClient(TestServer(app)) as client:
            for _ in range(300):
                if exits:
                    break
                await asyncio.sleep(0.01)
            assert exits == [lifecycle.INTEGRITY_EXIT_CODE]
            r = await client.get("/startupz")
            body = await r.json()
            # never reached ready: the corruption was caught BEFORE traffic
            assert r.status == 503
            assert "checksum mismatch" in body["error"]

    try:
        asyncio.run(run())
    finally:
        faults._active = None


def test_integrity_disabled_skips_verification(monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from spotter_tpu.serving.standalone import make_app

    monkeypatch.setenv("SPOTTER_TPU_STUB_ENGINE", "1")
    monkeypatch.setenv(integrity.INTEGRITY_ENV, "0")

    async def run():
        app = make_app(model_name=None, warmup=False)
        async with TestClient(TestServer(app)) as client:
            for _ in range(300):
                r = await client.get("/startupz")
                if r.status == 200:
                    break
                await asyncio.sleep(0.01)
            assert r.status == 200
            snap = await (await client.get("/metrics")).json()
            assert "integrity" not in snap
            await app["detector"].batcher.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# degraded-rebuild re-verification (the batcher-side gate)


class _DegradableEngine:
    def __init__(self):
        from spotter_tpu.engine.metrics import Metrics

        self.metrics = Metrics()
        self.batch_buckets = (1, 2, 4)
        self.generation = 0
        self.dp = 2

    def can_degrade(self):
        return True

    def probe_shards(self):
        return [0]

    def rebuild_degraded(self, alive):
        self.generation += 1
        self.dp = 1
        return 1

    def detect(self, images):
        return [[dict(TV)] for _ in images]


def test_rebuild_degraded_runs_integrity_recheck():
    from spotter_tpu.engine.batcher import MicroBatcher

    async def run():
        eng = _DegradableEngine()
        batcher = MicroBatcher(eng, max_delay_ms=1.0)
        tracker = lifecycle.StartupTracker()
        tracker.mark(lifecycle.WARMING)
        tracker.mark_ready(eng.metrics)
        batcher.attach_lifecycle(tracker)
        calls = []

        def recheck(source):
            calls.append((source, tracker.state))
            return True

        batcher.integrity_recheck_cb = recheck
        await batcher.start()
        assert await batcher._rebuild_degraded(0)
        # the recheck ran, in the VERIFYING state, before READY returned
        assert calls == [("degraded-rebuild", lifecycle.VERIFYING)]
        assert tracker.state == lifecycle.READY
        await batcher.stop()

    asyncio.run(run())


def test_rebuild_degraded_failed_recheck_blocks_ready_no_fatal_cascade():
    from spotter_tpu.engine.batcher import MicroBatcher

    async def run():
        eng = _DegradableEngine()
        batcher = MicroBatcher(eng, max_delay_ms=1.0)
        tracker = lifecycle.StartupTracker()
        tracker.mark(lifecycle.WARMING)
        tracker.mark_ready(eng.metrics)
        batcher.attach_lifecycle(tracker)
        batcher.integrity_recheck_cb = lambda source: False
        await batcher.start()
        # True = "handled": the recheck callback owns the exit-86 path and
        # the rebuild must NOT cascade into the fatal(85) exit underneath
        assert await batcher._rebuild_degraded(0)
        assert tracker.state == lifecycle.VERIFYING  # never back to ready
        await batcher.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------------
# the supervisor exit-code ladder, pinned in one table


def _ladder_child(counter_path: str, cache_dir: str, code: int) -> list[str]:
    """A child that exits `code` while the counter is positive, then 0 —
    and recreates the compile-cache dir each run, like a real bring-up."""
    script = (
        "import os,sys\n"
        f"p = {counter_path!r}\n"
        "n = int(open(p).read())\n"
        "open(p, 'w').write(str(n - 1))\n"
        f"os.makedirs({cache_dir!r}, exist_ok=True)\n"
        f"sys.exit({code} if n > 0 else 0)\n"
    )
    return [sys.executable, "-c", script]


# (code, failures, expected_return, expected_restarts, quarantined_dirs)
LADDER = [
    # clean stop: no restart at all
    (0, 0, 0, 0, 0),
    # bring-up failure (82) is a plain crash: backoff, then the crash-loop
    # circuit trips at the limit and the supervisor gives up with 84
    (lifecycle.BRINGUP_FAILED_EXIT_CODE, 99, 84, 3, 0),
    # drained preemption (83): immediate warm restarts, cache untouched
    (lifecycle.PREEMPTED_EXIT_CODE, 2, 0, 2, 0),
    # fatal engine (85): immediate warm restarts, cache untouched
    (85, 2, 0, 2, 0),
    # integrity (86): cold restarts, compile cache quarantined EVERY time
    (lifecycle.INTEGRITY_EXIT_CODE, 2, 0, 2, 2),
]


@pytest.mark.parametrize(
    "code,failures,want_return,want_restarts,want_quarantined",
    LADDER,
    ids=[f"exit-{row[0]}" for row in LADDER],
)
def test_supervisor_exit_code_ladder(
    tmp_path, monkeypatch, code, failures, want_return, want_restarts,
    want_quarantined,
):
    from spotter_tpu.serving.supervisor import Supervisor

    cache_dir = tmp_path / "compile-cache"
    monkeypatch.setenv(lifecycle.JAX_CACHE_DIR_ENV, str(cache_dir))
    counter = tmp_path / "count"
    counter.write_text(str(failures))
    sup = Supervisor(
        _ladder_child(str(counter), str(cache_dir), code),
        backoff_base_s=0.01,
        backoff_max_s=0.02,
        min_uptime_s=1.0,  # every exit counts as "fast"
        crash_loop_limit=3,
        preempt_fast_limit=3,
        jitter=False,
    )
    assert sup.run() == want_return
    assert sup.restarts_total == want_restarts
    quarantined = sorted(
        p.name for p in tmp_path.glob("compile-cache.quarantined.*")
    )
    assert len(quarantined) == want_quarantined
    if want_quarantined:
        # deterministic, collision-free naming preserved for forensics
        assert quarantined == [
            f"compile-cache.quarantined.{i}"
            for i in range(want_quarantined)
        ]


def test_quarantine_follows_the_resolved_cache_dir(tmp_path, monkeypatch):
    """The supervisor quarantines whatever `lifecycle.compile_cache_dir()`
    resolves: JAX's variable when set, the fixed checkout path when not."""
    from spotter_tpu.serving import supervisor

    placed = tmp_path / "placed"
    fixed = tmp_path / ".jax_cache"
    monkeypatch.setattr(lifecycle, "DEFAULT_COMPILE_CACHE_DIR", str(fixed))
    for d in (placed, fixed):
        d.mkdir()
    monkeypatch.setenv(lifecycle.JAX_CACHE_DIR_ENV, str(placed))
    assert supervisor.quarantine_compile_cache() == f"{placed}.quarantined.0"
    assert fixed.is_dir() and not placed.exists()
    monkeypatch.delenv(lifecycle.JAX_CACHE_DIR_ENV)
    assert supervisor.quarantine_compile_cache() == f"{fixed}.quarantined.0"
    assert supervisor.quarantine_compile_cache() is None  # nothing left to move


def test_exit_codes_are_distinct():
    """Every ladder rung is a distinct code — a collision would silently
    merge two restart policies."""
    from spotter_tpu.engine.errors import FATAL_ENGINE_EXIT_CODE
    from spotter_tpu.serving.supervisor import CRASH_LOOP_EXIT_CODE

    codes = [
        lifecycle.BRINGUP_FAILED_EXIT_CODE,
        lifecycle.PREEMPTED_EXIT_CODE,
        CRASH_LOOP_EXIT_CODE,
        FATAL_ENGINE_EXIT_CODE,
        lifecycle.INTEGRITY_EXIT_CODE,
    ]
    assert codes == [82, 83, 84, 85, 86]
    assert len(set(codes)) == len(codes)


# ---------------------------------------------------------------------------
# the integrity chaos matrix


@pytest.mark.parametrize(
    "idx", range(4), ids=[sc.name for sc in __import__(
        "spotter_tpu.testing.chaos_matrix", fromlist=["INTEGRITY_MATRIX"]
    ).INTEGRITY_MATRIX],
)
def test_integrity_chaos_matrix(idx):
    from spotter_tpu.testing.chaos_matrix import (
        INTEGRITY_MATRIX,
        run_integrity_scenario,
    )

    sc = INTEGRITY_MATRIX[idx]
    report = asyncio.run(run_integrity_scenario(sc))
    assert report["ok"], {
        "name": report["name"],
        "checks": report["checks"],
        "wrong_answers": report["wrong_answers"],
        "quarantines": report["quarantines"],
        "exits": report["exits"],
        "quorum": report["quorum"],
    }
