"""Model-multiplexed autoscaling tests (ISSUE 20): registry ambiguous-name
resolution, model routing precedence + structured 400s, the AutoscalerBrain
policy loop (deterministic via injectable clock and direct step() calls),
the fleet-app integration (routing + the `autoscale` /metrics block), and
the SCALE_MATRIX chaos rows. The cross-process drills (controller crash
mid-scale, scale-to-zero over real supervised stub replicas) are marked
slow."""

import asyncio
import time
from types import SimpleNamespace

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from spotter_tpu.models.registry import family_for, match_score
from spotter_tpu.obs.aggregate import FleetAggregator
from spotter_tpu.serving.autoscale import (
    MODEL_HEADER,
    AutoscalerBrain,
    ClosedSetQueriesError,
    ModelPool,
    UnknownModelError,
    model_pools_from_registry,
    pool_shape,
)
from spotter_tpu.serving.fleet import (
    FleetController,
    PoolSpec,
    make_fleet_app,
)

PAYLOAD = {"image_urls": ["http://example.com/room.jpg"]}

FAST_POOL_KWARGS = dict(
    eject_threshold=1,
    backoff_base_s=0.1,
    backoff_max_s=0.5,
    health_interval_s=0.05,
)


# ---- satellite: registry ambiguous-name resolution ----


def test_match_score_earliest_start_then_longest():
    # earliest start wins even against a longer match further in
    assert match_score("dab-detr-resnet-50", ("dab-detr",)) == (0, -8)
    assert match_score("dab-detr-resnet-50", ("detr-resnet",)) == (4, -11)
    assert (0, -8) < (4, -11)
    # same start: longer match wins (smaller negated length)
    assert match_score("rtdetr_v2_r50", ("rtdetr", "rt")) == (0, -6)
    # absent pattern scores None
    assert match_score("yolos-small", ("detr",)) is None


def test_family_for_ambiguous_names_deterministic():
    """The PR 20 bugfix: family resolution must not depend on registration
    order. Prefixed DETR variants resolve to THEIR family even though the
    plain detr patterns ("detr-resnet") also appear inside the name."""
    cases = {
        "dab-detr-resnet-50": "dab_detr",
        "conditional-detr-resnet-50": "conditional_detr",
        "SenseTime/deformable-detr": "deformable_detr",
        "detr-resnet-50": "detr",
        "facebook/detr_resnet_101": "detr",
        "table-transformer-detection": "detr",
        "rtdetr_r50vd": "rtdetr",
        "PekingU/rtdetr_v2_r18vd": "rtdetr",
        "owlvit-base-patch32": "owlvit",
        "hustvl/yolos-small": "yolos",
    }
    for name, want in cases.items():
        assert family_for(name).name == want, name
    with pytest.raises(ValueError):
        family_for("segment-anything-vit-h")


# ---- routing (no fleet needed: a stub controller satisfies the brain) ----


def _stub_controller(pool_names):
    return SimpleNamespace(
        pools={
            n: SimpleNamespace(
                spec=SimpleNamespace(spawner=None, target_size=1),
                scaled_to_zero=False,
                members=[object()],
            )
            for n in pool_names
        }
    )


def _routing_brain():
    pools = [
        ModelPool(model="rtdetr", matches=("rtdetr",), default=True),
        ModelPool(model="dab_detr", matches=("dab-detr", "dab_detr")),
        ModelPool(model="detr", matches=("detr-resnet", "detr_resnet")),
        ModelPool(model="owlvit", matches=("owlvit",), open_vocab=True),
    ]
    return AutoscalerBrain(
        _stub_controller([p.name for p in pools]), pools, clock=lambda: 0.0
    )


def test_route_precedence_header_payload_queries_default():
    brain = _routing_brain()
    # no hints -> default pool
    assert brain.route(None, dict(PAYLOAD))[0] == "rtdetr"
    # payload `model` key routes and is STRIPPED before forwarding
    name, fwd = brain.route(None, {**PAYLOAD, "model": "dab-detr-resnet-50"})
    assert name == "dab_detr"
    assert "model" not in fwd and fwd["image_urls"] == PAYLOAD["image_urls"]
    # header beats payload
    name, _ = brain.route(
        {MODEL_HEADER: "owlvit-base-patch32"}, {**PAYLOAD, "model": "rtdetr"}
    )
    assert name == "owlvit"
    # bare `queries` -> the open-vocab pool
    name, fwd = brain.route(None, {**PAYLOAD, "queries": ["a cat"]})
    assert name == "owlvit" and fwd["queries"] == ["a cat"]
    # ambiguous name resolves like the registry (earliest-start-then-longest)
    assert brain.route(None, {"model": "dab-detr-resnet-50"})[0] == "dab_detr"
    assert brain.route(None, {"model": "detr-resnet-50"})[0] == "detr"


def test_route_unknown_model_is_structured_400():
    brain = _routing_brain()
    with pytest.raises(UnknownModelError) as ei:
        brain.route(None, {**PAYLOAD, "model": "segment-anything"})
    exc = ei.value
    assert exc.status == 400 and exc.kind == "unknown_model"
    assert set(exc.families) == {"rtdetr", "dab_detr", "detr", "owlvit"}
    assert brain.routing_rejections_total == 1


def test_route_queries_against_closed_set():
    brain = _routing_brain()
    # a named closed-set model cannot take open-vocab queries
    with pytest.raises(ClosedSetQueriesError):
        brain.route(None, {**PAYLOAD, "model": "rtdetr", "queries": ["cat"]})
    # a named open-vocab model can
    assert (
        brain.route(
            None, {**PAYLOAD, "model": "owlvit-base", "queries": ["cat"]}
        )[0]
        == "owlvit"
    )
    # a fleet with no open-vocab pool rejects bare queries
    closed = AutoscalerBrain(
        _stub_controller(["rtdetr"]),
        [ModelPool(model="rtdetr", default=True)],
        clock=lambda: 0.0,
    )
    with pytest.raises(ClosedSetQueriesError) as ei:
        closed.route(None, {**PAYLOAD, "queries": ["cat"]})
    assert ei.value.kind == "closed_set_queries"


def test_model_pools_from_registry_covers_the_zoo():
    pools = model_pools_from_registry()
    by_name = {p.model: p for p in pools}
    assert set(by_name) == {
        "conditional_detr", "dab_detr", "deformable_detr", "rtdetr",
        "owlvit", "yolos", "detr", "qwen3_next_det", "lfm2_moe_det",
        "kimi_linear_det", "joyai_flash_det",
    }
    assert by_name["owlvit"].open_vocab
    # big models shard tp, small models pack dp (ISSUE 20d)
    assert (by_name["owlvit"].tp, by_name["owlvit"].dp) == pool_shape("owlvit")
    assert by_name["owlvit"].tp > 1
    assert by_name["yolos"].dp > 1
    assert by_name["rtdetr"].default
    assert sum(1 for p in pools if p.default) == 1


# ---- the policy loop, deterministically ----


class _Member:
    """Minimal in-process managed member (aiohttp server + sync handle)."""

    def __init__(self, name: str, pool: str) -> None:
        self.name = name
        self.pool = pool
        self.serving = False
        self.last_payload = None
        app = web.Application()
        app.router.add_post("/detect", self._detect)
        app.router.add_get("/healthz", self._healthz)
        self.server = TestServer(app)
        self.url = ""

    async def _detect(self, request: web.Request) -> web.Response:
        self.last_payload = await request.json()
        if not self.serving:
            return web.json_response({}, status=503)
        return web.json_response({"served_by": self.name, "pool": self.pool})

    async def _healthz(self, request: web.Request) -> web.Response:
        return web.json_response({}, status=200 if self.serving else 503)

    async def start(self) -> None:
        await self.server.start_server()
        self.url = f"http://{self.server.host}:{self.server.port}"

    async def close(self) -> None:
        await self.server.close()

    # MemberHandle surface
    def alive(self) -> bool:
        return True

    def preempt(self) -> None:
        self.serving = False

    def clear_preemption(self) -> None:
        pass

    def shutdown(self, timeout_s: float = 10.0) -> str:
        self.serving = False
        return ""


class _RecordingStore:
    def __init__(self) -> None:
        self.pools: dict = {}
        self.calls: list = []

    def set_pool(self, name: str, **spec) -> None:
        self.calls.append((name, dict(spec)))
        self.pools.setdefault(name, {}).update(spec)


async def _brain_fleet(pool_cfgs, **brain_kw):
    """(controller, brain, members): per-model pools of _Member stock."""
    members = []
    specs = []
    model_pools = []
    for cfg in pool_cfgs:
        stock = []
        for i in range(cfg.get("stock", 2)):
            m = _Member(f"{cfg['model']}-m{i}", cfg["model"])
            await m.start()
            stock.append(m)
            members.append(m)

        def spawner(stock=stock):
            for m in stock:
                if not m.serving:
                    m.serving = True
                    return m
            raise RuntimeError("stock exhausted")

        specs.append(
            PoolSpec(
                cfg["model"], spawner=spawner,
                target_size=cfg.get("initial", 1),
                scale_to_zero_s=cfg.get("scale_to_zero_s"),
            )
        )
        model_pools.append(
            ModelPool(
                model=cfg["model"],
                matches=tuple(cfg.get("matches", ())),
                open_vocab=cfg.get("open_vocab", False),
                min_size=cfg.get("min", 0),
                max_size=cfg.get("max", 2),
                default=cfg.get("default", False),
            )
        )
    controller = FleetController(
        [s for s in specs], tick_s=0.02, restore_wait_s=5.0,
        pool_kwargs=dict(FAST_POOL_KWARGS),
    )
    brain = AutoscalerBrain(controller, model_pools, **brain_kw)
    return controller, brain, members


async def _wait(predicate, timeout_s: float = 5.0, interval_s: float = 0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        await asyncio.sleep(interval_s)
    raise TimeoutError("condition not met in time")


def test_step_scales_up_on_edge_inflight():
    async def run():
        store = _RecordingStore()
        ctrl, brain, members = await _brain_fleet(
            [{"model": "rtdetr", "default": True, "min": 1, "max": 2}],
            store=store, inflight_high=2.0, clock=lambda: 0.0,
        )
        await ctrl.start()
        await _wait(lambda: ctrl.pools["rtdetr"].pool.has_available())
        brain.route(None, dict(PAYLOAD))
        t1, t2 = brain.track("rtdetr"), brain.track("rtdetr")
        applied = await brain.step()
        assert [d.reason for d in applied] == ["up: inflight 2"]
        assert ctrl.pools["rtdetr"].spec.target_size == 2
        assert brain.scale_ups_total == 1
        # journal carries intent + shape BEFORE the spawn landed
        assert store.pools["rtdetr"]["size"] == 2
        assert store.pools["rtdetr"]["tp"] == 1
        # capped at max_size: another overloaded round does not grow past it
        applied = await brain.step()
        assert ctrl.pools["rtdetr"].spec.target_size == 2
        t1.done(200), t2.done(200)
        # done() is idempotent and classifies outcomes
        t1.done(500)
        st = brain._pool_state["rtdetr"]
        assert (st["ok_total"], st["fail_total"], st["inflight"]) == (2, 0, 0)
        await ctrl.stop(shutdown_members=False)
        for m in members:
            await m.close()

    asyncio.run(run())


def test_step_scales_down_after_consecutive_idle_rounds():
    async def run():
        ctrl, brain, members = await _brain_fleet(
            [{"model": "rtdetr", "default": True, "initial": 2, "max": 2}],
            down_steps=2, clock=lambda: 0.0,
        )
        await ctrl.start()
        await _wait(
            lambda: len(ctrl.pools["rtdetr"].members) == 2
            and ctrl.pools["rtdetr"].pool.has_available()
        )
        assert await brain.step() == []  # idle round 1: streak, no action
        applied = await brain.step()    # idle round 2: step down
        assert [d.desired for d in applied] == [1]
        assert brain.scale_downs_total == 1
        await _wait(lambda: len(ctrl.pools["rtdetr"].members) == 1)
        # demand resets the streak: no further step-down
        brain.route(None, dict(PAYLOAD))
        assert await brain.step() == []
        assert await brain.step() == []
        assert ctrl.pools["rtdetr"].spec.target_size == 1
        await ctrl.stop(shutdown_members=False)
        for m in members:
            await m.close()

    asyncio.run(run())


def test_step_holds_during_flood_instead_of_scaling():
    """Rising tenant sheds with zero admitted demand must never scale a
    pool — the brain records an explicit hold."""

    class _RisingSheds:
        def __init__(self) -> None:
            self.total = 0.0

        def metrics_view(self):
            self.total += 100.0
            return {
                "abuser": {
                    "sheds_rate_total": self.total,
                    "sheds_inflight_total": 0.0,
                }
            }

    async def run():
        ctrl, brain, members = await _brain_fleet(
            [{"model": "rtdetr", "default": True, "min": 1, "max": 2}],
            tenancy_plane=_RisingSheds(), clock=lambda: 0.0,
        )
        await ctrl.start()
        await _wait(lambda: ctrl.pools["rtdetr"].pool.has_available())
        await brain.step()  # baseline shed observation
        assert await brain.step() == []
        assert brain.flood_suppressions_total >= 1
        assert brain.scale_ups_total == 0
        assert ctrl.pools["rtdetr"].spec.target_size == 1
        await ctrl.stop(shutdown_members=False)
        for m in members:
            await m.close()

    asyncio.run(run())


def test_route_wakes_scaled_to_zero_pool():
    async def run():
        store = _RecordingStore()
        ctrl, brain, members = await _brain_fleet(
            [
                {"model": "rtdetr", "default": True, "min": 1},
                {"model": "owlvit", "open_vocab": True, "initial": 0},
            ],
            store=store, clock=lambda: 0.0,
        )
        await ctrl.start()
        assert ctrl.pools["owlvit"].spec.target_size == 0
        name, _ = brain.route(None, {**PAYLOAD, "queries": ["cat"]})
        assert name == "owlvit"
        assert brain.wakes_total == 1
        assert ctrl.pools["owlvit"].spec.target_size == 1
        assert store.pools["owlvit"]["size"] == 1  # journaled intent
        await _wait(lambda: ctrl.pools["owlvit"].pool.has_available())
        await ctrl.stop(shutdown_members=False)
        for m in members:
            await m.close()

    asyncio.run(run())


def test_actuation_is_fenced_journal_first():
    """A deposed controller's actuation dies at the fence BEFORE any
    journal write or target change."""

    class _Fence:
        def __init__(self) -> None:
            self.raises = False
            self.calls = 0

        def __call__(self):
            self.calls += 1
            if self.raises:
                raise RuntimeError("stale leader")
            return 1

    async def run():
        store = _RecordingStore()
        fence = _Fence()
        ctrl, brain, members = await _brain_fleet(
            [{"model": "rtdetr", "default": True, "max": 3}],
            store=store, fence=fence, clock=lambda: 0.0,
        )
        await ctrl.start()
        await _wait(lambda: ctrl.pools["rtdetr"].pool.has_available())
        brain.actuate("rtdetr", 2, "drill")
        assert fence.calls == 1
        assert store.pools["rtdetr"]["size"] == 2
        await _wait(lambda: len(ctrl.pools["rtdetr"].members) == 2)
        fence.raises = True
        with pytest.raises(RuntimeError):
            brain.actuate("rtdetr", 3, "drill")
        # fenced out BEFORE journal and target mutation
        assert store.pools["rtdetr"]["size"] == 2
        assert ctrl.pools["rtdetr"].spec.target_size == 2
        await ctrl.stop(shutdown_members=False)
        for m in members:
            await m.close()

    asyncio.run(run())


def test_chips_desired_accounts_pool_shape():
    pools = [
        ModelPool(model="owlvit", tp=2, dp=1, default=True),   # 2 chips/member
        ModelPool(model="yolos", tp=1, dp=2),                  # 2 chips/member
    ]
    ctrl = _stub_controller(["owlvit", "yolos"])
    ctrl.pools["owlvit"].spec.target_size = 2
    ctrl.pools["yolos"].spec.target_size = 1
    brain = AutoscalerBrain(ctrl, pools, clock=lambda: 0.0)
    assert brain.chips_desired() == 2 * 2 + 1 * 2


# ---- the fleet edge end to end (in-process) ----


def test_fleet_app_model_routing_and_metrics_block():
    async def run():
        ctrl, brain, members = await _brain_fleet(
            [
                {"model": "rtdetr", "matches": ("rtdetr",), "default": True,
                 "min": 1},
                {"model": "owlvit", "matches": ("owlvit",),
                 "open_vocab": True, "min": 1},
            ],
            clock=time.monotonic,
        )
        app = make_fleet_app(
            ctrl,
            aggregator=FleetAggregator(lambda: [], interval_s=0.0),
            autoscaler=brain,
        )
        async with TestClient(TestServer(app)) as client:
            await _wait(
                lambda: all(
                    fp.pool.has_available() for fp in ctrl.pools.values()
                )
            )
            # payload model key routes to the named pool and is stripped
            resp = await client.post(
                "/detect", json={**PAYLOAD, "model": "rtdetr_r50vd"}
            )
            assert resp.status == 200
            assert (await resp.json())["pool"] == "rtdetr"
            served = next(m for m in members if m.last_payload is not None)
            assert "model" not in served.last_payload
            # header routing to the open-vocab pool
            resp = await client.post(
                "/detect", json=dict(PAYLOAD),
                headers={MODEL_HEADER: "owlvit-base-patch32"},
            )
            assert resp.status == 200
            assert (await resp.json())["pool"] == "owlvit"
            # queries land open-vocab without naming a model
            resp = await client.post(
                "/detect", json={**PAYLOAD, "queries": ["a cat"]}
            )
            assert (await resp.json())["pool"] == "owlvit"
            # unknown model: structured 400 naming the registry, no
            # Retry-After (client defect, not load)
            resp = await client.post(
                "/detect", json={**PAYLOAD, "model": "segment-anything"}
            )
            assert resp.status == 400
            body = await resp.json()
            assert body["status"] == 400
            assert body["kind"] == "unknown_model"
            assert set(body["families"]) == {"rtdetr", "owlvit"}
            assert "Retry-After" not in resp.headers
            # /metrics grows the autoscale block
            snap = await (await client.get("/metrics")).json()
            auto = snap["autoscale"]
            assert auto["default_pool"] == "rtdetr"
            assert auto["open_vocab_pool"] == "owlvit"
            assert auto["routing_rejections_total"] == 1
            assert auto["pools"]["rtdetr"]["admits_total"] == 1
            assert auto["pools"]["rtdetr"]["desired"] == 1
            assert auto["pools"]["owlvit"]["admits_total"] == 2
        for m in members:
            await m.close()

    asyncio.run(run())


# ---- the chaos rows ----


def _scale_row(name):
    from spotter_tpu.testing.chaos_matrix import SCALE_MATRIX

    return next(sc for sc in SCALE_MATRIX if sc.name == name)


@pytest.mark.parametrize(
    "row", ["burst-to-cold-model", "idle-reclaim", "flood-vs-in-quota-demand"]
)
def test_scale_matrix_fast_rows(row):
    from spotter_tpu.testing.chaos_matrix import run_scale_scenario

    report = asyncio.run(run_scale_scenario(_scale_row(row)))
    assert report["ok"], report["checks"]


def test_evaluate_scale_rejects_unknown_invariant():
    from spotter_tpu.testing.chaos_matrix import ScaleScenario, evaluate_scale

    sc = ScaleScenario(name="x", invariants={"not_a_real_invariant": 1})
    with pytest.raises(ValueError, match="not_a_real_invariant"):
        evaluate_scale(sc, {"client_failures": 0})


@pytest.mark.slow
def test_scale_matrix_controller_crash_mid_scale(tmp_path):
    """kill -9 against a REAL controller mid-scale-up: the successor adopts
    every live supervised member and converges to the JOURNALED size with
    zero double-spawns."""
    from spotter_tpu.testing.chaos_matrix import run_scale_crash_scenario

    report = run_scale_crash_scenario(
        _scale_row("controller-crash-mid-scale"), str(tmp_path)
    )
    assert report["ok"], report


# ---- satellite: scale-to-zero -> cold restore over REAL supervised
# replicas, timed through /metrics ----


@pytest.mark.slow
def test_scale_to_zero_cold_restore_cross_process(tmp_path, monkeypatch):
    """A real supervised stub pool idles past SPOTTER_TPU_SCALE_TO_ZERO_S
    and is reclaimed; the next routed request restores it through the
    persistent compile cache path and /metrics reports time_to_ready_s
    under 15 s with zero client-visible failures."""
    from spotter_tpu.testing import cluster

    monkeypatch.setenv("SPOTTER_TPU_SCALE_TO_ZERO_S", "1.0")

    async def run():
        ctrl = FleetController(
            [
                PoolSpec(
                    "rtdetr",
                    spawner=cluster.fleet_spawner(str(tmp_path), "rtdetr"),
                    target_size=1,
                    # scale_to_zero_s unset: the env knob drives it
                ),
            ],
            tick_s=0.05,
            restore_wait_s=60.0,
            pool_kwargs=dict(
                eject_threshold=1,
                backoff_base_s=0.2,
                health_interval_s=0.1,
                request_timeout_s=10.0,
            ),
        )
        brain = AutoscalerBrain(
            ctrl,
            [ModelPool(model="rtdetr", matches=("rtdetr",), default=True,
                       min_size=1)],
            tick_s=0.1,
        )
        app = make_fleet_app(
            ctrl,
            aggregator=FleetAggregator(lambda: [], interval_s=0.0),
            autoscaler=brain,
        )
        async with TestClient(TestServer(app)) as client:
            fp = ctrl.pools["rtdetr"]
            assert fp.scale_to_zero_s == 1.0  # env knob wired through
            await _wait(
                lambda: fp.pool.has_available(), timeout_s=90.0,
                interval_s=0.2,
            )
            resp = await client.post("/detect", json=dict(PAYLOAD))
            assert resp.status == 200
            # idle past the knob: the supervised member is reclaimed
            await _wait(
                lambda: fp.scaled_to_zero, timeout_s=30.0, interval_s=0.2
            )
            snap = await (await client.get("/metrics")).json()
            assert snap["autoscale"]["pools"]["rtdetr"]["scaled_to_zero"]
            assert snap["autoscale"]["pools"]["rtdetr"]["size"] == 0
            # the next request wakes + restores through the compile cache
            t0 = time.monotonic()
            resp = await client.post("/detect", json=dict(PAYLOAD))
            assert resp.status == 200, await resp.text()
            restore_wall_s = time.monotonic() - t0
            await _wait(
                lambda: not fp.restoring, timeout_s=10.0, interval_s=0.1
            )
            snap = await (await client.get("/metrics")).json()
            auto = snap["autoscale"]["pools"]["rtdetr"]
            assert auto["restores_total"] == 1
            assert not auto["scaled_to_zero"]
            assert auto["time_to_ready_s"] is not None
            assert auto["time_to_ready_s"] < 15.0, auto
            assert restore_wall_s < 60.0
            assert auto["fail_total"] == 0
        await ctrl.stop(shutdown_members=True)

    asyncio.run(run())
