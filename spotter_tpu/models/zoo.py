"""Model zoo: loaders that turn a MODEL_NAME into a BuiltDetector.

The loading boundary mirrors the reference's
`AutoModelForObjectDetection.from_pretrained(MODEL_NAME)` (serve.py:203):
torch weights come from the local HF cache (baked into the serving image the
way the reference bakes them — Dockerfile:17, download.py), get converted to
Flax params once, and are cached as an Orbax checkpoint keyed by MODEL_NAME
so later pod starts skip torch entirely.

Offline/test path: SPOTTER_TPU_TINY=1 builds a tiny random-init model (no
network, no torch) — the serving stack's equivalent of the reference tests'
MagicMock model (test_serve.py:24-28), but running the real engine.

Seeded published-width path (RT-DETR only so far): a MODEL_NAME that is a
bare key of `RTDETR_PRESETS` ("rtdetr_v2_r101vd" — no organisation prefix,
so it cannot be a hub id) builds that preset at its published widths with
random params from a fixed seed. No network, no torch: what a sealed chip
machine can build, and what parity-on-chip compares against.
"""

import dataclasses
import logging
import os

import jax
import numpy as np

from spotter_tpu.engine.engine import BuiltDetector
from spotter_tpu.models.coco import coco_id2label_80
from spotter_tpu.models.configs import (
    ConditionalDetrConfig,
    RTDETR_PRESETS,
    DabDetrConfig,
    DeformableDetrConfig,
    DetrConfig,
    JoyAIFlashDetConfig,
    KimiLinearDetConfig,
    Lfm2MoeDetConfig,
    OwlViTConfig,
    OwlViTTextConfig,
    OwlViTVisionConfig,
    Qwen3NextDetConfig,
    ResNetConfig,
    RTDetrConfig,
    YolosConfig,
)
from spotter_tpu.models.conditional_detr import ConditionalDetrDetector
from spotter_tpu.models.dab_detr import DabDetrDetector
from spotter_tpu.models.deformable_detr import DeformableDetrDetector
from spotter_tpu.models.detr import DetrDetector
from spotter_tpu.models.joyai_flash import JoyAIFlashDetector
from spotter_tpu.models.kimi_linear import KimiLinearDetector
from spotter_tpu.models.lfm2_moe import Lfm2MoeDetector
from spotter_tpu.models.owlvit import OwlViTDetector
from spotter_tpu.models.qwen3_next import Qwen3NextDetector
from spotter_tpu.models.yolos import YolosDetector
from spotter_tpu.models.registry import ModelFamily, register
from spotter_tpu.models.rtdetr import RTDetrDetector
from spotter_tpu.utils.precision import backbone_dtype, compute_dtype
from spotter_tpu.ops.preprocess import (
    CLIP_MEAN,
    CLIP_STD,
    DETR_SPEC,
    IMAGENET_MEAN,
    IMAGENET_STD,
    OWLV2_SPEC,
    OWLVIT_SPEC,
    RTDETR_SPEC,
    PreprocessSpec,
)

logger = logging.getLogger(__name__)

TINY_ENV = "SPOTTER_TPU_TINY"


def tiny_rtdetr_config(num_labels: int = 80) -> RTDetrConfig:
    return RTDetrConfig(
        backbone=ResNetConfig(
            embedding_size=16, hidden_sizes=(16, 24, 32, 48), depths=(1, 1, 1, 1),
            layer_type="basic",
        ),
        num_labels=num_labels,
        d_model=32,
        num_queries=30,
        encoder_hidden_dim=32,
        encoder_in_channels=(24, 32, 48),
        encoder_ffn_dim=48,
        decoder_ffn_dim=48,
        encoder_attention_heads=4,
        decoder_attention_heads=4,
        decoder_layers=2,
        decoder_n_points=2,
        id2label=tuple(coco_id2label_80().items()),
    )


def _init_random(module, input_hw: tuple[int, int]) -> dict:
    h, w = input_hw
    variables = module.init(jax.random.PRNGKey(0), np.zeros((1, h, w, 3), np.float32))
    return variables["params"]


# Seed of the published-width random builds. Random weights make one or two
# classes win every query of every image; under seed 0 R101's winner is
# "banana", which the amenity taxonomy drops, so nothing would cross the wire
# and a smoke or a benchmark would draw, encode and compare nothing. Under
# seed 1 the winners are "chair" and "vase": most detections survive the
# filter and some are dropped. chip_smoke.py requires a non-empty answer, so a
# change that empties it again fails there, not silently.
SEEDED_BUILD_SEED = 1


def _init_seeded(module, input_hw: tuple[int, int]) -> dict:
    """`_init_random` for published sizes, as ONE jitted program: un-jitted,
    `module.init` at 640x640 dispatches op by op, which on a chip is
    hundreds of tiny compiles. Returns the host copy the engine keeps."""
    h, w = input_hw
    init = jax.jit(
        lambda key: module.init(key, np.zeros((1, h, w, 3), np.float32))["params"]
    )
    return jax.device_get(init(jax.random.PRNGKey(SEEDED_BUILD_SEED)))


def _build_rtdetr(model_name: str) -> BuiltDetector:
    if os.environ.get(TINY_ENV):
        cfg = tiny_rtdetr_config()
        spec = PreprocessSpec(mode="fixed", size=(64, 64))
        module = RTDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
        params = _init_random(module, spec.input_hw)
        logger.info("Built tiny random RT-DETR for %s (%s)", model_name, TINY_ENV)
    elif model_name in RTDETR_PRESETS:
        cfg = dataclasses.replace(
            RTDETR_PRESETS[model_name], id2label=tuple(coco_id2label_80().items())
        )
        spec = RTDETR_SPEC
        module = RTDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
        params = _init_seeded(module, spec.input_hw)
        logger.info("Built seeded random %s at published widths", model_name)
    else:
        from spotter_tpu.convert.loader import load_rtdetr_from_hf  # lazy: needs torch

        cfg, params = load_rtdetr_from_hf(model_name)
        spec = RTDETR_SPEC
        module = RTDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
    return BuiltDetector(
        model_name=model_name,
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_topk",
        id2label=cfg.id2label_dict,
        num_top_queries=min(300, cfg.num_queries),
    )


def tiny_detr_config(num_labels: int = 80) -> DetrConfig:
    return DetrConfig(
        backbone=ResNetConfig(
            embedding_size=8, hidden_sizes=(8, 12, 16, 24), depths=(1, 1, 1, 1),
            layer_type="basic", style="v1", out_indices=(4,),
        ),
        num_labels=num_labels,
        d_model=32,
        num_queries=9,
        encoder_layers=1,
        decoder_layers=2,
        encoder_attention_heads=4,
        decoder_attention_heads=4,
        encoder_ffn_dim=48,
        decoder_ffn_dim=48,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_detr(model_name: str) -> BuiltDetector:
    if os.environ.get(TINY_ENV):
        cfg = tiny_detr_config()
        spec = PreprocessSpec(
            mode="shortest_edge", size=(48, 64), mean=IMAGENET_MEAN, std=IMAGENET_STD,
            pad_to=(64, 64),
        )
        module = DetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
        params = _init_random(module, spec.input_hw)
        logger.info("Built tiny random DETR for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import load_detr_from_hf  # lazy: needs torch

        cfg, params = load_detr_from_hf(model_name)
        spec = DETR_SPEC
        module = DetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
    return BuiltDetector(
        model_name=model_name,
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="softmax",
        id2label=cfg.id2label_dict,
        num_top_queries=cfg.num_queries,
        needs_mask=True,
    )


def tiny_yolos_config(num_labels: int = 80) -> YolosConfig:
    return YolosConfig(
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=48,
        image_size=(32, 48),
        patch_size=8,
        num_detection_tokens=5,
        num_labels=num_labels,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_yolos(model_name: str) -> BuiltDetector:
    if os.environ.get(TINY_ENV):
        cfg = tiny_yolos_config()
        # The ViT body IS the HBM-bound half of this model (there is no CNN
        # backbone), so it follows the backbone dtype: bf16 under "mixed"
        # (measured v5e: the fp32 body is bandwidth-bound at 4300 tokens).
        # Heads/logits/boxes stay fp32 inside the module.
        module = YolosDetector(cfg, dtype=backbone_dtype())
        spec = PreprocessSpec(
            mode="fixed", size=cfg.image_size, mean=IMAGENET_MEAN, std=IMAGENET_STD
        )
        params = _init_random(module, spec.input_hw)
        logger.info("Built tiny random YOLOS for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import load_yolos_from_hf  # lazy: needs torch

        cfg, params = load_yolos_from_hf(model_name)
        module = YolosDetector(cfg, dtype=backbone_dtype())  # see tiny note
        # Warp-resize to the trained image size: position tables apply exactly
        # and every shape is static. (The torch processor instead pads to the
        # batch max and interpolates position tables per size — a recompile
        # per shape under XLA.)
        spec = PreprocessSpec(
            mode="fixed", size=cfg.image_size, mean=IMAGENET_MEAN, std=IMAGENET_STD
        )
    return BuiltDetector(
        model_name=model_name,
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="softmax",
        id2label=cfg.id2label_dict,
        num_top_queries=cfg.num_detection_tokens,
    )


def tiny_qwen3_next_det_config(num_labels: int = 80) -> Qwen3NextDetConfig:
    return Qwen3NextDetConfig(
        hidden_size=32,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        linear_key_head_dim=8,
        linear_num_key_heads=2,
        linear_num_value_heads=4,
        linear_value_head_dim=8,
        moe_intermediate_size=16,
        shared_expert_intermediate_size=16,
        num_routed_experts=8,
        num_experts=4,
        num_experts_per_tok=2,
        image_size=(32, 48),
        patch_size=8,
        num_detection_tokens=5,
        num_labels=num_labels,
        id2label=tuple(coco_id2label_80().items()),
    )


def hold_matrices_in(params: dict, dtype) -> dict:
    """The tree with every matrix (two axes or more) in `dtype`, on the
    host: what the device then holds, what attestation sums and what a
    re-placement puts back. Vectors (norm weights, biases, decay rates) keep
    float32."""

    def cast(leaf):
        leaf = np.asarray(leaf)
        if leaf.ndim >= 2:
            return leaf if leaf.dtype == dtype else leaf.astype(dtype)
        return leaf.astype(np.float32)

    return jax.tree_util.tree_map(cast, params)


def _held_detector(model_name: str, module_cls, cfg, params) -> BuiltDetector:
    """A decoder body served in YOLOS's form: the same warp to the
    checkpoint's `image_size`, the same softmax postprocess. Its matrices (a
    billion parameters and more) are held in the policy's type (a float32
    tree cast at every use would stream twice the bytes a deployment does):
    the tree is cast once, here."""
    dtype = backbone_dtype()  # the body is the model, as in _build_yolos
    return BuiltDetector(
        model_name=model_name,
        module=module_cls(cfg, dtype=dtype),
        params=hold_matrices_in(params, dtype),
        preprocess_spec=PreprocessSpec(
            mode="fixed", size=cfg.image_size, mean=IMAGENET_MEAN, std=IMAGENET_STD
        ),
        postprocess="softmax",
        id2label=cfg.id2label_dict,
        num_top_queries=cfg.num_detection_tokens,
    )


def _build_qwen3_next_det(model_name: str) -> BuiltDetector:
    """Qwen3-Next's decoder layers as a detector body (0.97 B parameters at
    one chip's share of the experts)."""
    if os.environ.get(TINY_ENV):
        cfg = tiny_qwen3_next_det_config()
        params = _init_random(Qwen3NextDetector(cfg), cfg.image_size)
        logger.info("Built tiny random qwen3_next_det for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import load_qwen3_next_det

        cfg, params = load_qwen3_next_det(model_name)
    return _held_detector(model_name, Qwen3NextDetector, cfg, params)


def tiny_lfm2_moe_det_config(num_labels: int = 80) -> Lfm2MoeDetConfig:
    return Lfm2MoeDetConfig(
        hidden_size=32,
        intermediate_size=48,
        moe_intermediate_size=16,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_experts=8,
        num_experts_per_tok=2,
        image_size=(32, 48),
        patch_size=8,
        num_detection_tokens=5,
        num_labels=num_labels,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_lfm2_moe_det(model_name: str) -> BuiltDetector:
    """LFM2-MoE's decoder layers as a detector body (1.6 B parameters at six
    layers, every expert held)."""
    if os.environ.get(TINY_ENV):
        cfg = tiny_lfm2_moe_det_config()
        params = _init_random(Lfm2MoeDetector(cfg), cfg.image_size)
        logger.info("Built tiny random lfm2_moe_det for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import load_lfm2_moe_det

        cfg, params = load_lfm2_moe_det(model_name)
    return _held_detector(model_name, Lfm2MoeDetector, cfg, params)


def tiny_kimi_linear_det_config(num_labels: int = 80) -> KimiLinearDetConfig:
    return KimiLinearDetConfig(
        hidden_size=32,
        intermediate_size=48,
        moe_intermediate_size=16,
        num_attention_heads=2,
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=4,
        v_head_dim=8,
        linear_head_dim=8,
        linear_num_heads=4,
        gate_low_rank_dim=8,
        num_routed_experts=8,
        num_experts=4,
        num_experts_per_token=2,
        image_size=(32, 48),
        patch_size=8,
        num_detection_tokens=5,
        num_labels=num_labels,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_kimi_linear_det(model_name: str) -> BuiltDetector:
    """Kimi Linear's decoder layers as a detector body (2.1 B parameters at
    five layers and one chip's quarter of the experts)."""
    if os.environ.get(TINY_ENV):
        cfg = tiny_kimi_linear_det_config()
        params = _init_random(KimiLinearDetector(cfg), cfg.image_size)
        logger.info("Built tiny random kimi_linear_det for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import load_kimi_linear_det

        cfg, params = load_kimi_linear_det(model_name)
    return _held_detector(model_name, KimiLinearDetector, cfg, params)


def tiny_joyai_flash_det_config(num_labels: int = 80) -> JoyAIFlashDetConfig:
    return JoyAIFlashDetConfig(
        hidden_size=32,
        intermediate_size=48,
        moe_intermediate_size=16,
        num_attention_heads=2,
        q_lora_rank=24,
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=4,
        v_head_dim=8,
        rope_theta=10000.0,
        num_routed_experts=8,
        num_experts=4,
        num_experts_per_token=2,
        image_size=(32, 48),
        patch_size=8,
        num_detection_tokens=5,
        num_labels=num_labels,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_joyai_flash_det(model_name: str) -> BuiltDetector:
    """JoyAI-LLM-Flash's decoder layers as a detector body (2.6 B parameters
    at five layers and one chip's half of the experts)."""
    if os.environ.get(TINY_ENV):
        cfg = tiny_joyai_flash_det_config()
        params = _init_random(JoyAIFlashDetector(cfg), cfg.image_size)
        logger.info("Built tiny random joyai_flash_det for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import load_joyai_flash_det

        cfg, params = load_joyai_flash_det(model_name)
    return _held_detector(model_name, JoyAIFlashDetector, cfg, params)


def tiny_owlvit_config() -> OwlViTConfig:
    return OwlViTConfig(
        text=OwlViTTextConfig(
            vocab_size=99, hidden_size=16, intermediate_size=24,
            num_hidden_layers=2, num_attention_heads=2, max_position_embeddings=8,
        ),
        vision=OwlViTVisionConfig(
            hidden_size=20, intermediate_size=28, num_hidden_layers=2,
            num_attention_heads=2, image_size=32, patch_size=8,
        ),
        projection_dim=16,
    )


QUERIES_ENV = "SPOTTER_TPU_TEXT_QUERIES"


def owlvit_query_labels() -> list[str]:
    """Deploy-time label set for open-vocab detection.

    Defaults to the amenity taxonomy's COCO labels (so the downstream
    AMENITIES_MAPPING filter behaves exactly as with closed-set detectors);
    operators override with a comma-separated SPOTTER_TPU_TEXT_QUERIES — the
    capability the reference's fixed-vocab models cannot offer.
    """
    env = os.environ.get(QUERIES_ENV, "")
    if env.strip():
        labels = [s.strip() for s in env.split(",") if s.strip()]
        if not labels:
            raise ValueError(
                f"{QUERIES_ENV} is set but contains no labels: {env!r}"
            )
        return labels
    from spotter_tpu.taxonomy import AMENITIES_MAPPING

    return list(AMENITIES_MAPPING)


def _tiny_tokenize(prompts: list[str], vocab_size: int, t: int):
    """Deterministic pseudo-tokenizer for the tiny (no-torch) OWL-ViT: each
    prompt hashes to a stable token sequence, so runtime `encode_text` of the
    same query string is reproducible across processes (the text-embedding
    cache key contract) without an HF tokenizer in the image."""
    import hashlib

    rows = []
    for p in prompts:
        seed = int.from_bytes(hashlib.sha256(p.encode()).digest()[:8], "little")
        rng = np.random.default_rng(seed)
        rows.append(rng.integers(1, vocab_size, (t,)))
    ids = np.stack(rows).astype(np.int32)
    return ids, np.ones_like(ids)


def _build_owlvit(model_name: str) -> BuiltDetector:
    labels = owlvit_query_labels()
    prompts = [f"a photo of a {label}" for label in labels]
    tiny = bool(os.environ.get(TINY_ENV))
    if tiny:
        cfg = tiny_owlvit_config()
        module = OwlViTDetector(
            cfg, dtype=compute_dtype(), vision_dtype=backbone_dtype()
        )
        spec = PreprocessSpec(mode="fixed", size=(32, 32), mean=CLIP_MEAN, std=CLIP_STD)
        ids, mask = _tiny_tokenize(
            prompts, cfg.text.vocab_size, cfg.text.max_position_embeddings
        )
        params = module.init(
            jax.random.PRNGKey(0),
            np.zeros((1, 32, 32, 3), np.float32),
            ids,
            mask,
            method=OwlViTDetector.detect_with_text,
        )["params"]
        logger.info("Built tiny random OWL-ViT for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import (  # lazy: needs torch first time
            load_owlvit_from_hf,
            owlvit_tokenize,
        )

        cfg, params = load_owlvit_from_hf(model_name)
        module = OwlViTDetector(
            cfg, dtype=compute_dtype(), vision_dtype=backbone_dtype()
        )
        spec = OWLV2_SPEC if cfg.objectness else OWLVIT_SPEC
        ids, mask = owlvit_tokenize(model_name, prompts, cfg.text.max_position_embeddings)
    # TPU-first split: the text tower runs ONCE here; the serving hot path is
    # vision-only with the (Q, proj) query matrix riding as a jit constant.
    query_embeds = np.asarray(
        module.apply({"params": params}, ids, mask, method=OwlViTDetector.encode_text)
    )

    def encode_text(queries: list[str]) -> np.ndarray:
        """Runtime text encoder for the open-vocabulary /detect path: query
        strings -> normalized (Q, proj) embeddings, same prompt template and
        text tower as the build-time vocabulary. Callers cache the result
        (caching/text_cache.py) so a repeated vocabulary costs one encode."""
        q_prompts = [f"a photo of a {q}" for q in queries]
        if tiny:
            q_ids, q_mask = _tiny_tokenize(
                q_prompts, cfg.text.vocab_size, cfg.text.max_position_embeddings
            )
        else:
            from spotter_tpu.convert.loader import owlvit_tokenize  # lazy

            q_ids, q_mask = owlvit_tokenize(
                model_name, q_prompts, cfg.text.max_position_embeddings
            )
        return np.asarray(
            module.apply(
                {"params": params}, q_ids, q_mask,
                method=OwlViTDetector.encode_text,
            ),
            np.float32,
        )

    return BuiltDetector(
        model_name=model_name,
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_max",
        id2label=dict(enumerate(labels)),
        num_top_queries=len(labels),
        apply_kwargs={"query_embeds": query_embeds},
        text_encoder=encode_text,
    )



def tiny_conditional_detr_config(num_labels: int = 80) -> ConditionalDetrConfig:
    return ConditionalDetrConfig(
        backbone=ResNetConfig(
            embedding_size=8, hidden_sizes=(8, 12, 16, 24), depths=(1, 1, 1, 1),
            layer_type="basic", style="v1", out_indices=(4,),
        ),
        num_labels=num_labels,
        d_model=32,
        num_queries=9,
        encoder_layers=1,
        decoder_layers=2,
        encoder_attention_heads=4,
        decoder_attention_heads=4,
        encoder_ffn_dim=48,
        decoder_ffn_dim=48,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_conditional_detr(model_name: str) -> BuiltDetector:
    if os.environ.get(TINY_ENV):
        cfg = tiny_conditional_detr_config()
        spec = PreprocessSpec(
            mode="shortest_edge", size=(48, 64), mean=IMAGENET_MEAN, std=IMAGENET_STD,
            pad_to=(64, 64),
        )
        module = ConditionalDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
        params = _init_random(module, spec.input_hw)
        logger.info(
            "Built tiny random Conditional-DETR for %s (%s)", model_name, TINY_ENV
        )
    else:
        from spotter_tpu.convert.loader import (  # lazy: needs torch
            load_conditional_detr_from_hf,
        )

        cfg, params = load_conditional_detr_from_hf(model_name)
        spec = DETR_SPEC
        module = ConditionalDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
    return BuiltDetector(
        model_name=model_name,
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_topk",  # focal head, NMS-free top-k like RT-DETR
        id2label=cfg.id2label_dict,
        # ConditionalDetrImageProcessor.post_process_object_detection defaults
        # to top_k=100; matching it keeps the serve contract identical
        num_top_queries=min(100, cfg.num_queries),
        needs_mask=True,
    )


def tiny_deformable_detr_config(num_labels: int = 80) -> DeformableDetrConfig:
    return DeformableDetrConfig(
        backbone=ResNetConfig(
            embedding_size=8, hidden_sizes=(8, 12, 16, 24), depths=(1, 1, 1, 1),
            layer_type="basic", style="v1", out_indices=(2, 3, 4),
        ),
        num_labels=num_labels,
        d_model=32,
        num_queries=9,
        encoder_layers=1,
        decoder_layers=2,
        encoder_attention_heads=4,
        decoder_attention_heads=4,
        encoder_ffn_dim=48,
        decoder_ffn_dim=48,
        encoder_n_points=2,
        decoder_n_points=2,
        with_box_refine=True,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_deformable_detr(model_name: str) -> BuiltDetector:
    if os.environ.get(TINY_ENV):
        cfg = tiny_deformable_detr_config()
        spec = PreprocessSpec(
            mode="shortest_edge", size=(48, 64), mean=IMAGENET_MEAN, std=IMAGENET_STD,
            pad_to=(64, 64),
        )
        module = DeformableDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
        params = _init_random(module, spec.input_hw)
        logger.info(
            "Built tiny random Deformable-DETR for %s (%s)", model_name, TINY_ENV
        )
    else:
        from spotter_tpu.convert.loader import (  # lazy: needs torch
            load_deformable_detr_from_hf,
        )

        cfg, params = load_deformable_detr_from_hf(model_name)
        spec = DETR_SPEC
        module = DeformableDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
    return BuiltDetector(
        model_name=model_name,
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_topk",  # focal head, NMS-free top-k (HF top_k=100)
        id2label=cfg.id2label_dict,
        num_top_queries=min(100, cfg.num_queries),
        needs_mask=True,
    )


def tiny_dab_detr_config(num_labels: int = 80) -> DabDetrConfig:
    return DabDetrConfig(
        backbone=ResNetConfig(
            embedding_size=8, hidden_sizes=(8, 12, 16, 24), depths=(1, 1, 1, 1),
            layer_type="basic", style="v1", out_indices=(4,),
        ),
        num_labels=num_labels,
        d_model=32,
        num_queries=9,
        encoder_layers=1,
        decoder_layers=2,
        encoder_attention_heads=4,
        decoder_attention_heads=4,
        encoder_ffn_dim=48,
        decoder_ffn_dim=48,
        id2label=tuple(coco_id2label_80().items()),
    )


def _build_dab_detr(model_name: str) -> BuiltDetector:
    if os.environ.get(TINY_ENV):
        cfg = tiny_dab_detr_config()
        spec = PreprocessSpec(
            mode="shortest_edge", size=(48, 64), mean=IMAGENET_MEAN, std=IMAGENET_STD,
            pad_to=(64, 64),
        )
        module = DabDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
        params = _init_random(module, spec.input_hw)
        logger.info("Built tiny random DAB-DETR for %s (%s)", model_name, TINY_ENV)
    else:
        from spotter_tpu.convert.loader import load_dab_detr_from_hf  # lazy: needs torch

        cfg, params = load_dab_detr_from_hf(model_name)
        spec = DETR_SPEC
        module = DabDetrDetector(
            cfg, dtype=compute_dtype(), backbone_dtype=backbone_dtype()
        )
    return BuiltDetector(
        model_name=model_name,
        module=module,
        params=params,
        preprocess_spec=spec,
        postprocess="sigmoid_topk",  # focal head, NMS-free top-k
        id2label=cfg.id2label_dict,
        # HF DAB-DETR has no processor of its own; its checkpoints pair with
        # ConditionalDetrImageProcessor, whose post_process_object_detection
        # defaults to top_k=100 — detections ranked 101+ would never be
        # returned by the reference serve path
        num_top_queries=min(100, cfg.num_queries),
        needs_mask=True,
    )


# Per-family TP rule sets (ISSUE 13): the registry is where the serving
# bootstrap looks them up, so tp>1 shards the weights of the family actually
# being served. All current families speak the shared layers.py transformer
# vocabulary (fc1/fc2, q/k/v/out_proj); OWL-ViT keeps its own name for the
# towers-specific documentation in sharding.py.
from spotter_tpu.parallel.sharding import (  # noqa: E402  (after model imports)
    OWLVIT_TP_RULES,
    RTDETR_TP_RULES,
    TRANSFORMER_TP_RULES,
    VIT_TP_RULES,
)

# Registration order carries no precedence: family_for resolves ambiguous
# names ("dab-detr-resnet-50" contains both "dab-detr" and "detr-resnet")
# by earliest-start-then-longest match, so the specific family always wins.
register(
    ModelFamily(
        name="conditional_detr",
        matches=("conditional-detr", "conditional_detr"),
        build=_build_conditional_detr,
        tp_rules=tuple(TRANSFORMER_TP_RULES),
    )
)
register(
    ModelFamily(
        name="dab_detr", matches=("dab-detr", "dab_detr"), build=_build_dab_detr,
        tp_rules=tuple(TRANSFORMER_TP_RULES),
    )
)
register(
    ModelFamily(
        name="deformable_detr",
        matches=("deformable-detr", "deformable_detr"),
        build=_build_deformable_detr,
        tp_rules=tuple(TRANSFORMER_TP_RULES),
    )
)
register(
    ModelFamily(
        name="rtdetr", matches=("rtdetr", "rt_detr", "rt-detr"),
        build=_build_rtdetr, tp_rules=tuple(RTDETR_TP_RULES),
    )
)
register(
    ModelFamily(
        name="owlvit",  # OWL-ViT and OWLv2 (same architecture + objectness head)
        matches=("owlvit", "owl-vit", "owl_vit", "owlv2", "owl-v2", "owl_v2"),
        build=_build_owlvit,
        tp_rules=tuple(OWLVIT_TP_RULES),
    )
)
register(ModelFamily(
    name="yolos", matches=("yolos",), build=_build_yolos,
    tp_rules=tuple(VIT_TP_RULES),
))
register(ModelFamily(
    # no tp rules: the expert layer's split is by expert (the chip's share
    # is in the checkpoint's config), and parallel/sharding.py has no such axis
    name="qwen3_next_det", matches=("qwen3-next-det", "qwen3_next_det"),
    build=_build_qwen3_next_det,
))
register(ModelFamily(
    # no tp rules, as above: every layer is held whole on its chip
    name="lfm2_moe_det", matches=("lfm2-moe-det", "lfm2_moe_det"),
    build=_build_lfm2_moe_det,
))
register(ModelFamily(
    # no tp rules, as above: every mixer is whole and the experts' share is the configuration's
    name="kimi_linear_det", matches=("kimi-linear-det", "kimi_linear_det"),
    build=_build_kimi_linear_det,
))
register(ModelFamily(
    # no tp rules, as above: every mixer is whole and the experts' share is the configuration's
    name="joyai_flash_det", matches=("joyai-flash-det", "joyai_flash_det"),
    build=_build_joyai_flash_det,
))
register(
    # plain DETR (+ Table-Transformer, a pre-norm DETR with identical keys)
    ModelFamily(
        name="detr",
        matches=("detr-resnet", "detr_resnet", "table-transformer", "table_transformer"),
        build=_build_detr,
        tp_rules=tuple(TRANSFORMER_TP_RULES),
    )
)
