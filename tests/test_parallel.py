"""Mesh + sharding tests on the virtual 8-device CPU mesh (SURVEY.md §4.4)."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from spotter_tpu.engine.engine import InferenceEngine
from spotter_tpu.models.rtdetr import RTDetrDetector
from spotter_tpu.models.zoo import tiny_rtdetr_config
from spotter_tpu.parallel import (
    RTDETR_TP_RULES,
    data_sharding,
    make_mesh,
    param_shardings,
    shard_params,
    spec_for_path,
)
from spotter_tpu.engine.engine import BuiltDetector
from spotter_tpu.ops.preprocess import PreprocessSpec


def test_make_mesh_shapes():
    mesh = make_mesh()
    assert mesh.shape == {"dp": 8, "tp": 1}
    mesh = make_mesh(tp=2)
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh = make_mesh(dp=2, tp=2)
    assert mesh.shape == {"dp": 2, "tp": 2}
    with pytest.raises(ValueError):
        make_mesh(tp=3)  # 8 % 3 != 0
    with pytest.raises(ValueError):
        make_mesh(dp=8, tp=2)  # needs 16 devices


def test_tp_rule_matching():
    assert spec_for_path("decoder_layer0/fc1/kernel", RTDETR_TP_RULES) == P(None, "tp")
    assert spec_for_path("decoder_layer0/fc2/kernel", RTDETR_TP_RULES) == P("tp", None)
    assert spec_for_path("aifi0_layer0/self_attn/q_proj/kernel", RTDETR_TP_RULES) == P(
        None, "tp"
    )
    assert spec_for_path("aifi0_layer0/self_attn/out_proj/kernel", RTDETR_TP_RULES) == P(
        "tp", None
    )
    # backbone convs and norms stay replicated
    assert spec_for_path("backbone/stem0/conv/kernel", RTDETR_TP_RULES) == P()
    assert spec_for_path("decoder_layer0/fc1/nothing", RTDETR_TP_RULES) == P()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_rtdetr_config()
    module = RTDetrDetector(cfg)
    params = module.init(jax.random.PRNGKey(0), np.zeros((1, 64, 64, 3), np.float32))[
        "params"
    ]
    return cfg, module, params


def test_param_shardings_tree(tiny_model):
    _, _, params = tiny_model
    mesh = make_mesh(dp=4, tp=2)
    shardings = param_shardings(params, mesh, RTDETR_TP_RULES)
    flat = jax.tree_util.tree_leaves_with_path(shardings)
    assert all(isinstance(s, NamedSharding) for _, s in flat)
    # at least one TP-sharded leaf and most leaves replicated
    specs = [s.spec for _, s in flat]
    assert P(None, "tp") in specs
    assert specs.count(P()) > len(specs) // 2


@pytest.mark.slow  # compile-heavy on 1-core CPU; full/CI run covers it
def test_sharded_forward_matches_single_device(tiny_model):
    """DP+TP sharded forward == single-device forward (same params, inputs)."""
    cfg, module, params = tiny_model
    x = np.random.default_rng(0).standard_normal((4, 64, 64, 3)).astype(np.float32)

    ref = module.apply({"params": params}, x)

    mesh = make_mesh(dp=4, tp=2)
    sharded_params = shard_params(params, mesh, RTDETR_TP_RULES)
    xs = jax.device_put(x, data_sharding(mesh))
    out = jax.jit(lambda p, v: module.apply({"params": p}, v))(sharded_params, xs)

    np.testing.assert_allclose(
        np.asarray(out["logits"]), np.asarray(ref["logits"]), atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(out["pred_boxes"]), np.asarray(ref["pred_boxes"]), atol=1e-5
    )


@pytest.mark.slow  # real R18 train step on the CPU mesh; full/CI run covers it
def test_dryrun_real_r18_architecture_sharded():
    """The REAL rtdetr_v2_r18vd architecture (real d_model/heads/layer names)
    trains one dp×tp-sharded step on the virtual 8-device mesh — so the TP
    rule set is validated against the real param tree, not just the tiny
    config (VERDICT r2 weak #4)."""
    import __graft_entry__ as graft

    # conftest.py forced the 8-device CPU mesh in this process
    assert jax.device_count() >= 8
    graft.dryrun_multichip(8, preset="rtdetr_v2_r18vd")


def test_dp2_serving_engine_fast_tier(tiny_model):
    """Fast-tier dp=2 smoke (ISSUE 3): the REAL serving path — engine with a
    dp=2 mesh fed by the MicroBatcher at the aggregate bucket — over the
    virtual CPU devices. The batcher fills dp × per-chip bucket in one
    dispatch and detections match the single-chip path at the same config.
    dp-only (tp=1) keeps per-image compute identical, so boxes match tightly.
    """
    import asyncio

    from PIL import Image

    from spotter_tpu.engine.batcher import MicroBatcher

    cfg, module, params = tiny_model
    spec = PreprocessSpec(mode="fixed", size=(64, 64))
    built = BuiltDetector(
        model_name="tiny",
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_topk",
        id2label=cfg.id2label_dict,
        num_top_queries=10,
    )
    rng = np.random.default_rng(2)
    images = [
        Image.fromarray(rng.integers(0, 255, (80, 100, 3), np.uint8))
        for _ in range(4)
    ]
    per_chip = 2
    single = InferenceEngine(built, threshold=0.0, batch_buckets=(per_chip,))
    mesh = make_mesh(dp=2, tp=1)
    # aggregate bucket = dp × per-chip (what serving/app.py configures)
    sharded = InferenceEngine(
        built, threshold=0.0, batch_buckets=(2 * per_chip,), mesh=mesh
    )
    batcher = MicroBatcher(sharded, max_delay_ms=50.0)
    assert batcher.max_batch == 4  # fills the aggregate bucket

    async def drive():
        results = await asyncio.gather(*(batcher.submit(im) for im in images))
        await batcher.stop()
        return results

    via_batcher = asyncio.run(drive())
    snap = sharded.metrics.snapshot()
    assert snap["aggregate_bucket"] == 4
    # all four concurrent submits ride ONE aggregate dispatch
    assert snap["batches_total"] == 1 and snap["mean_batch_size"] == 4.0
    assert snap["h2d_bytes_total"] > 0

    reference = single.detect(images)
    assert len(via_batcher) == len(reference) == 4
    for da, db in zip(reference, via_batcher):
        assert [d["label"] for d in da] == [d["label"] for d in db]
        np.testing.assert_allclose(
            np.asarray([d["box"] for d in da], np.float32),
            np.asarray([d["box"] for d in db], np.float32),
            atol=1e-4,
        )


def test_dp2_device_preprocess_sharded_matches(tiny_model):
    """uint8 ingest + dp sharding compose: same detections as the host-float
    single-chip path (the two tentpole halves run together in prod)."""
    from PIL import Image

    cfg, module, params = tiny_model
    spec = PreprocessSpec(mode="fixed", size=(64, 64))
    built = BuiltDetector(
        model_name="tiny",
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_topk",
        id2label=cfg.id2label_dict,
        num_top_queries=10,
    )
    rng = np.random.default_rng(3)
    images = [
        Image.fromarray(rng.integers(0, 255, (60, 90, 3), np.uint8))
        for _ in range(4)
    ]
    single = InferenceEngine(built, threshold=0.0, batch_buckets=(4,))
    sharded = InferenceEngine(
        built, threshold=0.0, batch_buckets=(4,), mesh=make_mesh(dp=2, tp=1),
        device_preprocess=True,
    )
    a = single.detect(images)
    b = sharded.detect(images)
    for da, db in zip(a, b):
        assert [d["label"] for d in da] == [d["label"] for d in db]
        np.testing.assert_allclose(
            np.asarray([d["box"] for d in da], np.float32),
            np.asarray([d["box"] for d in db], np.float32),
            atol=1e-3,
        )


@pytest.mark.slow  # compile-heavy on 1-core CPU; full/CI run covers it
def test_engine_with_mesh_matches_unsharded(tiny_model):
    """The serving engine produces identical detections with and without a mesh."""
    from PIL import Image

    cfg, module, params = tiny_model
    spec = PreprocessSpec(mode="fixed", size=(64, 64))
    built = BuiltDetector(
        model_name="tiny",
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_topk",
        id2label=cfg.id2label_dict,
        num_top_queries=10,
    )
    rng = np.random.default_rng(1)
    images = [
        Image.fromarray(rng.integers(0, 255, (80, 100, 3), np.uint8)) for _ in range(5)
    ]

    plain = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2, 4, 8))
    mesh = make_mesh(dp=4, tp=2)
    sharded = InferenceEngine(built, threshold=0.0, batch_buckets=(1, 2, 4, 8), mesh=mesh)
    # buckets got rounded up to multiples of dp=4
    assert all(b % 4 == 0 for b in sharded.batch_buckets)

    a = plain.detect(images)
    b = sharded.detect(images)
    assert len(a) == len(b) == 5
    for da, db in zip(a, b):
        assert [d["label"] for d in da] == [d["label"] for d in db]
        np.testing.assert_allclose(
            np.asarray([d["box"] for d in da], np.float32),
            np.asarray([d["box"] for d in db], np.float32),
            atol=1e-2,
        )
