"""The registry's third build mode (ROADMAP R0, RT-DETR slice): a MODEL_NAME
that is a bare `RTDETR_PRESETS` key builds that preset at its published widths
from a fixed seed — no network, no torch — and bring-up says what device the
engine landed on, refusing a CPU nobody asked for."""

import asyncio
import dataclasses
import sys
import types

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

from spotter_tpu.convert.rtdetr_rules import rtdetr_rules
from spotter_tpu.engine.engine import InferenceEngine, describe_devices
from spotter_tpu.models import build_detector, zoo
from spotter_tpu.models.configs import RTDETR_PRESETS
from spotter_tpu.models.rtdetr import RTDetrDetector
from spotter_tpu.ops.preprocess import RTDETR_SPEC
from spotter_tpu.serving import lifecycle
from spotter_tpu.serving.standalone import make_app


def test_r101_preset_has_published_widths_and_the_converters_param_tree():
    """`rtdetr_v2_r101vd` by `jax.eval_shape` (no 300 MB of params): the
    published PekingU/rtdetr_v2_r101vd widths, and exactly the param paths
    the torch->Flax converter's rule table fills — so a seeded build and a
    converted checkpoint are interchangeable in every program."""
    cfg = RTDETR_PRESETS["rtdetr_v2_r101vd"]
    assert cfg.backbone.depths == (3, 4, 23, 3)
    assert (cfg.d_model, cfg.encoder_hidden_dim, cfg.encoder_ffn_dim) == (256, 384, 2048)
    assert (cfg.num_queries, cfg.decoder_layers, cfg.num_labels) == (300, 6, 80)
    h, w = RTDETR_SPEC.input_hw
    assert (h, w) == (640, 640)
    shapes = jax.eval_shape(
        lambda key: RTDetrDetector(cfg).init(key, np.zeros((1, h, w, 3), np.float32)),
        jax.random.PRNGKey(0),
    )["params"]
    flat = {
        tuple(k.key for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]
    }
    assert set(flat) == {rule[0] for rule in rtdetr_rules(cfg).rules}
    assert 75e6 < sum(int(np.prod(s)) for s in flat.values()) < 78e6
    assert flat[("enc_score_head", "kernel")] == (256, 80)
    assert flat[("decoder_layer5", "encoder_attn", "sampling_offsets", "kernel")] == (
        256, 8 * 3 * 4 * 2,
    )
    assert flat[("aifi0_layer0", "fc1", "kernel")] == (384, 2048)


def test_bare_preset_key_selects_the_seeded_build(monkeypatch):
    """Chosen by the model name and by no variable: a bare preset key builds
    that preset (jitted init, COCO labels); TINY still wins over any name; a
    hub id goes to the converter."""
    built_with = []

    def fake_init(module, input_hw):
        built_with.append((module.config, input_hw))
        return {}

    monkeypatch.setattr(zoo, "_init_seeded", fake_init)
    monkeypatch.setattr(zoo, "_init_random", lambda module, input_hw: {})
    monkeypatch.delenv(zoo.TINY_ENV, raising=False)
    built = build_detector("rtdetr_v2_r34vd")
    (cfg, input_hw), = built_with
    assert input_hw == (640, 640) and built.preprocess_spec == RTDETR_SPEC
    assert dataclasses.replace(cfg, id2label=()) == RTDETR_PRESETS["rtdetr_v2_r34vd"]
    assert len(built.id2label) == 80 and built.id2label[0] == "person"
    assert built.postprocess == "sigmoid_topk" and built.num_top_queries == 300

    monkeypatch.setenv(zoo.TINY_ENV, "1")
    assert build_detector("rtdetr_v2_r34vd").preprocess_spec.input_hw == (64, 64)
    assert len(built_with) == 1  # the tiny toy never took the seeded path

    monkeypatch.delenv(zoo.TINY_ENV)

    def no_network(name):
        raise ConnectionError(name)

    # a stand-in for the lazily imported converter (importing the real one
    # pulls in torch + transformers)
    monkeypatch.setitem(
        sys.modules,
        "spotter_tpu.convert.loader",
        types.SimpleNamespace(load_rtdetr_from_hf=no_network),
    )
    with pytest.raises(ConnectionError, match="PekingU/rtdetr_v2_r34vd"):
        build_detector("PekingU/rtdetr_v2_r34vd")


def test_seeded_r18_builds_and_serves_one_image(monkeypatch):
    """The real thing on the CPU at the smallest published preset: seeded
    build -> engine -> one 640x640 detect through the compiled program."""
    monkeypatch.delenv(zoo.TINY_ENV, raising=False)  # earlier files may leave it set
    built = build_detector("rtdetr_v2_r18vd")
    leaves = jax.tree_util.tree_leaves(built.params)
    assert {type(a) for a in leaves} == {np.ndarray}  # the host copy
    assert 20.0e6 < sum(a.size for a in leaves) < 20.4e6
    engine = InferenceEngine(built, threshold=0.0, batch_buckets=(1,))
    assert engine.device_info == {"platform": "cpu", "device_kind": "cpu", "count": 1}
    image = Image.fromarray(
        (np.random.default_rng(0).random((480, 640, 3)) * 255).astype(np.uint8)
    )
    (detections,) = engine.detect([image])
    assert len(detections) == 300
    assert all(np.isfinite(d["score"]) and 0.0 <= d["score"] <= 1.0 for d in detections)
    assert all(np.isfinite(d["box"]).all() for d in detections)
    assert {d["label"] for d in detections} <= set(built.id2label.values())


@pytest.fixture
def nobody_named_cpu():
    """JAX_PLATFORMS as on a machine where nothing chose a platform: JAX found
    no accelerator and fell back to the host on its own."""
    named = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    yield
    jax.config.update("jax_platforms", named)


def test_describe_devices_refuses_an_unnamed_cpu(nobody_named_cpu):
    with pytest.raises(RuntimeError, match="no accelerator"):
        describe_devices(jax.devices()[:1])
    for named in ("cpu", "tpu,cpu"):
        jax.config.update("jax_platforms", named)
        assert describe_devices(jax.devices()[:2]) == {
            "platform": "cpu", "device_kind": "cpu", "count": 2,
        }


def test_bringup_on_an_unnamed_cpu_fails_through_the_bringup_exit(
    monkeypatch, nobody_named_cpu
):
    """The server does not serve from a CPU it was not asked for: bring-up
    raises at placement, /startupz says why, and the process exits 82 — the
    existing failed-bring-up path, which the supervisor backs off on."""
    monkeypatch.setenv(zoo.TINY_ENV, "1")
    monkeypatch.setattr(zoo, "_init_random", lambda module, input_hw: {})
    exit_codes = []

    async def run():
        app = make_app(model_name="rtdetr_v2_r18vd", bringup_exit_cb=exit_codes.append)
        async with TestClient(TestServer(app)) as client:
            for _ in range(3000):
                if exit_codes:
                    break
                await asyncio.sleep(0.01)
            assert exit_codes == [lifecycle.BRINGUP_FAILED_EXIT_CODE]
            startup = await client.get("/startupz")
            assert startup.status == 503
            body = await startup.json()
            assert body["state"] == "failed" and "no accelerator" in body["error"]

    asyncio.run(run())


def test_healthz_names_the_device(monkeypatch):
    """/healthz carries the engine's device block: platform, kind, count."""
    from spotter_tpu.serving.app import build_detector_app

    monkeypatch.setenv(zoo.TINY_ENV, "1")

    async def run():
        detector = build_detector_app("rtdetr_v2_r18vd", batch_buckets=(1,))
        async with TestClient(TestServer(make_app(detector=detector))) as client:
            health = await (await client.get("/healthz")).json()
            assert health["device"] == {
                "platform": "cpu", "device_kind": "cpu", "count": 1,
            }
        await detector.batcher.stop()

    asyncio.run(run())
