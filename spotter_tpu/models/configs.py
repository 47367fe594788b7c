"""Config dataclasses for the detection model families.

Mirrors the semantic content of the HF configs (RTDetrV2Config etc.) so that a
checkpoint's config.json can be adapted 1:1 (`from_hf`), while staying plain
frozen dataclasses — hashable, so they can be static args under jax.jit.
"""

from dataclasses import dataclass, field, fields, replace


@dataclass(frozen=True)
class ResNetConfig:
    """ResNet backbone in two flavors.

    style "d": RT-DETR's "presnet" (deep 3-conv stem, avg-pool downsample
    shortcuts — HF RTDetrResNetBackbone). style "v1": the classic
    torchvision-style ResNet (single 7x7 stem, strided 1x1 projection
    shortcuts — HF ResNetBackbone / timm resnet, the DETR backbone).
    """

    num_channels: int = 3
    embedding_size: int = 64
    hidden_sizes: tuple[int, ...] = (256, 512, 1024, 2048)
    depths: tuple[int, ...] = (3, 4, 6, 3)
    layer_type: str = "bottleneck"  # "basic" | "bottleneck"
    hidden_act: str = "relu"
    downsample_in_first_stage: bool = False
    downsample_in_bottleneck: bool = False
    style: str = "d"  # "d" (RT-DETR ResNet-D) | "v1" (classic / DETR)
    # indices into (stem, stage1, ..., stage4); RT-DETR taps strides 8/16/32
    out_indices: tuple[int, ...] = (2, 3, 4)

    @classmethod
    def from_hf(cls, hf) -> "ResNetConfig":
        return cls(
            num_channels=hf.num_channels,
            embedding_size=hf.embedding_size,
            hidden_sizes=tuple(hf.hidden_sizes),
            depths=tuple(hf.depths),
            layer_type=hf.layer_type,
            hidden_act=hf.hidden_act,
            downsample_in_first_stage=hf.downsample_in_first_stage,
            downsample_in_bottleneck=hf.downsample_in_bottleneck,
            style="v1" if hf.model_type == "resnet" else "d",
            out_indices=tuple(hf.out_indices),
        )


@dataclass(frozen=True)
class RTDetrConfig:
    """RT-DETR / RT-DETRv2 detector (hybrid encoder + deformable decoder)."""

    backbone: ResNetConfig = field(default_factory=ResNetConfig)
    num_labels: int = 80
    d_model: int = 256
    num_queries: int = 300
    # hybrid encoder
    encoder_hidden_dim: int = 256
    encoder_in_channels: tuple[int, ...] = (512, 1024, 2048)
    feat_strides: tuple[int, ...] = (8, 16, 32)
    encoder_ffn_dim: int = 1024
    encode_proj_layers: tuple[int, ...] = (2,)
    encoder_layers: int = 1
    encoder_attention_heads: int = 8
    encoder_activation_function: str = "gelu"
    activation_function: str = "silu"
    hidden_expansion: float = 1.0
    positional_encoding_temperature: float = 10000.0
    csp_num_blocks: int = 3
    # decoder
    decoder_ffn_dim: int = 1024
    num_feature_levels: int = 3
    decoder_n_points: int = 4
    decoder_layers: int = 6
    decoder_attention_heads: int = 8
    decoder_activation_function: str = "relu"
    learn_initial_query: bool = False
    anchor_grid_size: float = 0.05
    # v2-specific deformable-attention semantics (configuration_rt_detr_v2.py)
    decoder_offset_scale: float = 0.5
    decoder_method: str = "default"  # "default" (bilinear) | "discrete"
    version: int = 2
    layer_norm_eps: float = 1e-5
    batch_norm_eps: float = 1e-5
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @classmethod
    def from_hf(cls, hf) -> "RTDetrConfig":
        version = 2 if hf.model_type == "rt_detr_v2" else 1
        return cls(
            backbone=ResNetConfig.from_hf(hf.backbone_config),
            num_labels=hf.num_labels,
            d_model=hf.d_model,
            num_queries=hf.num_queries,
            encoder_hidden_dim=hf.encoder_hidden_dim,
            encoder_in_channels=tuple(hf.encoder_in_channels),
            feat_strides=tuple(hf.feat_strides),
            encoder_ffn_dim=hf.encoder_ffn_dim,
            encode_proj_layers=tuple(hf.encode_proj_layers),
            encoder_layers=hf.encoder_layers,
            encoder_attention_heads=hf.encoder_attention_heads,
            encoder_activation_function=hf.encoder_activation_function,
            activation_function=hf.activation_function,
            hidden_expansion=hf.hidden_expansion,
            positional_encoding_temperature=float(hf.positional_encoding_temperature),
            decoder_ffn_dim=hf.decoder_ffn_dim,
            num_feature_levels=hf.num_feature_levels,
            decoder_n_points=hf.decoder_n_points,
            decoder_layers=hf.decoder_layers,
            decoder_attention_heads=hf.decoder_attention_heads,
            decoder_activation_function=hf.decoder_activation_function,
            learn_initial_query=hf.learn_initial_query,
            decoder_offset_scale=getattr(hf, "decoder_offset_scale", 0.5),
            decoder_method=getattr(hf, "decoder_method", "default"),
            version=version,
            layer_norm_eps=hf.layer_norm_eps,
            batch_norm_eps=hf.batch_norm_eps,
            id2label=tuple(sorted((int(k), v) for k, v in hf.id2label.items())),
        )


@dataclass(frozen=True)
class DetrConfig:
    """DETR (facebook/detr-resnet-*) — CNN backbone + vanilla enc-dec transformer.

    Mirrors HF DetrConfig (configuration_detr.py); the reference serves this
    family through the same AutoModel boundary (serve.py:199-205).
    """

    backbone: "ResNetConfig" = field(
        default_factory=lambda: ResNetConfig(style="v1", out_indices=(4,))
    )
    num_labels: int = 91
    d_model: int = 256
    num_queries: int = 100
    encoder_layers: int = 6
    decoder_layers: int = 6
    encoder_attention_heads: int = 8
    decoder_attention_heads: int = 8
    encoder_ffn_dim: int = 2048
    decoder_ffn_dim: int = 2048
    activation_function: str = "relu"
    positional_encoding_temperature: float = 10000.0
    layer_norm_eps: float = 1e-5  # torch nn.LayerNorm default (DETR never overrides)
    # Table-Transformer (microsoft/table-transformer-*) is DETR with pre-norm
    # layers and a final encoder LayerNorm (modeling_table_transformer.py
    # normalizes before attention/FFN; DETR normalizes after)
    pre_norm: bool = False
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @classmethod
    def from_hf(cls, hf) -> "DetrConfig":
        check_no_dilation(hf)
        if hf.use_timm_backbone:
            backbone = timm_resnet_backbone(hf.backbone)
        else:
            backbone = replace(
                ResNetConfig.from_hf(hf.backbone_config),
                out_indices=(len(hf.backbone_config.depths),),
            )
        return cls(
            backbone=backbone,
            num_labels=hf.num_labels,
            d_model=hf.d_model,
            num_queries=hf.num_queries,
            encoder_layers=hf.encoder_layers,
            decoder_layers=hf.decoder_layers,
            encoder_attention_heads=hf.encoder_attention_heads,
            decoder_attention_heads=hf.decoder_attention_heads,
            encoder_ffn_dim=hf.encoder_ffn_dim,
            decoder_ffn_dim=hf.decoder_ffn_dim,
            activation_function=hf.activation_function,
            pre_norm=hf.model_type == "table-transformer",
            id2label=tuple(sorted((int(k), v) for k, v in hf.id2label.items())),
        )


@dataclass(frozen=True)
class ConditionalDetrConfig:
    """Conditional DETR (microsoft/conditional-detr-resnet-*).

    DETR-shaped encoder plus the conditional decoder (content/spatial
    decoupled cross-attention, reference-point box regression, focal
    classification without a "no-object" class). Mirrors HF
    ConditionalDetrConfig (configuration_conditional_detr.py).
    """

    backbone: "ResNetConfig" = field(
        default_factory=lambda: ResNetConfig(style="v1", out_indices=(4,))
    )
    num_labels: int = 91
    d_model: int = 256
    num_queries: int = 300
    encoder_layers: int = 6
    decoder_layers: int = 6
    encoder_attention_heads: int = 8
    decoder_attention_heads: int = 8
    encoder_ffn_dim: int = 2048
    decoder_ffn_dim: int = 2048
    activation_function: str = "relu"
    positional_encoding_temperature: float = 10000.0
    layer_norm_eps: float = 1e-5
    pre_norm: bool = False  # encoder layers are shared with DETR's post-norm
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @classmethod
    def from_hf(cls, hf) -> "ConditionalDetrConfig":
        check_no_dilation(hf)
        if hf.use_timm_backbone:
            backbone = timm_resnet_backbone(hf.backbone)
        else:
            backbone = replace(
                ResNetConfig.from_hf(hf.backbone_config),
                out_indices=(len(hf.backbone_config.depths),),
            )
        return cls(
            backbone=backbone,
            num_labels=hf.num_labels,
            d_model=hf.d_model,
            num_queries=hf.num_queries,
            encoder_layers=hf.encoder_layers,
            decoder_layers=hf.decoder_layers,
            encoder_attention_heads=hf.encoder_attention_heads,
            decoder_attention_heads=hf.decoder_attention_heads,
            encoder_ffn_dim=hf.encoder_ffn_dim,
            decoder_ffn_dim=hf.decoder_ffn_dim,
            activation_function=hf.activation_function,
            id2label=tuple(sorted((int(k), v) for k, v in hf.id2label.items())),
        )


def check_no_dilation(hf) -> None:
    """Reject dc5 checkpoints (timm `dilation=True` turns stage-4 stride into
    dilation-2 convs, which our ResNet doesn't model — converting anyway would
    produce a half-resolution final feature map and silently-garbage boxes)."""
    if getattr(hf, "dilation", False):
        raise ValueError(
            "dilated (dc5) backbones are not supported; use the non-dc5 checkpoint"
        )


# timm checkpoints name their backbone: facebook/detr-resnet-50/101 and
# microsoft/conditional-detr-resnet-* (bottleneck), microsoft/
# table-transformer-* (resnet18, basic blocks). One table shared by every
# DETR-lineage from_hf so new backbones are added in one place.
_TIMM_RESNET_PRESETS = {
    "resnet18": dict(
        layer_type="basic", depths=(2, 2, 2, 2), hidden_sizes=(64, 128, 256, 512)
    ),
    "resnet34": dict(
        layer_type="basic", depths=(3, 4, 6, 3), hidden_sizes=(64, 128, 256, 512)
    ),
    "resnet50": dict(depths=(3, 4, 6, 3)),
    "resnet101": dict(depths=(3, 4, 23, 3)),
}


def timm_resnet_backbone(name: str) -> ResNetConfig:
    if name not in _TIMM_RESNET_PRESETS:
        raise ValueError(
            f"Unsupported timm backbone {name!r}; known: {sorted(_TIMM_RESNET_PRESETS)}"
        )
    return ResNetConfig(style="v1", out_indices=(4,), **_TIMM_RESNET_PRESETS[name])


@dataclass(frozen=True)
class DabDetrConfig:
    """DAB-DETR (IDEA-Research/dab-detr-resnet-*) — DETR with 4D dynamic
    anchor-box queries: each query is a learned (x, y, w, h) anchor whose sine
    embedding conditions both self- and cross-attention, refined per decoder
    layer through a shared box head. Mirrors HF DabDetrConfig
    (configuration_dab_detr.py).
    """

    backbone: "ResNetConfig" = field(
        default_factory=lambda: ResNetConfig(style="v1", out_indices=(4,))
    )
    num_labels: int = 91
    d_model: int = 256  # hf "hidden_size"
    num_queries: int = 300
    query_dim: int = 4
    encoder_layers: int = 6
    decoder_layers: int = 6
    encoder_attention_heads: int = 8
    decoder_attention_heads: int = 8
    encoder_ffn_dim: int = 2048
    decoder_ffn_dim: int = 2048
    activation_function: str = "prelu"
    temperature_height: float = 20.0
    temperature_width: float = 20.0
    keep_query_pos: bool = False
    layer_norm_eps: float = 1e-5
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @classmethod
    def from_hf(cls, hf) -> "DabDetrConfig":
        check_no_dilation(hf)
        if hf.query_dim != 4:
            raise ValueError(f"Only query_dim=4 is supported, got {hf.query_dim}")
        if getattr(hf, "num_patterns", 0):
            raise ValueError("num_patterns > 0 is not supported")
        if getattr(hf, "normalize_before", False):
            raise ValueError("normalize_before (pre-norm) DAB-DETR is not supported")
        if hf.activation_function != "prelu":
            # the Flax model hardcodes the learned-PReLU FFN of the published
            # checkpoints; other activations carry no activation_fn.weight
            raise ValueError(
                f"Only activation_function='prelu' is supported, got "
                f"{hf.activation_function!r}"
            )
        if hf.use_timm_backbone:
            backbone = timm_resnet_backbone(hf.backbone)
        else:
            backbone = replace(
                ResNetConfig.from_hf(hf.backbone_config),
                out_indices=(len(hf.backbone_config.depths),),
            )
        return cls(
            backbone=backbone,
            num_labels=hf.num_labels,
            d_model=hf.hidden_size,
            num_queries=hf.num_queries,
            query_dim=hf.query_dim,
            encoder_layers=hf.encoder_layers,
            decoder_layers=hf.decoder_layers,
            encoder_attention_heads=hf.encoder_attention_heads,
            decoder_attention_heads=hf.decoder_attention_heads,
            encoder_ffn_dim=hf.encoder_ffn_dim,
            decoder_ffn_dim=hf.decoder_ffn_dim,
            activation_function=hf.activation_function,
            temperature_height=float(hf.temperature_height),
            temperature_width=float(hf.temperature_width),
            keep_query_pos=hf.keep_query_pos,
            id2label=tuple(sorted((int(k), v) for k, v in hf.id2label.items())),
        )


@dataclass(frozen=True)
class DeformableDetrConfig:
    """Deformable DETR (SenseTime/deformable-detr*) — multiscale deformable
    attention in BOTH encoder and decoder, with the plain / with-box-refine /
    two-stage variants. Mirrors HF DeformableDetrConfig
    (configuration_deformable_detr.py); the reference serves this family
    through the same AutoModel boundary (serve.py:199-205).
    """

    backbone: "ResNetConfig" = field(
        default_factory=lambda: ResNetConfig(style="v1", out_indices=(2, 3, 4))
    )
    num_labels: int = 91
    d_model: int = 256
    num_queries: int = 300
    encoder_layers: int = 6
    decoder_layers: int = 6
    encoder_attention_heads: int = 8
    decoder_attention_heads: int = 8
    encoder_ffn_dim: int = 1024
    decoder_ffn_dim: int = 1024
    activation_function: str = "relu"
    num_feature_levels: int = 4
    encoder_n_points: int = 4
    decoder_n_points: int = 4
    with_box_refine: bool = False
    two_stage: bool = False
    two_stage_num_proposals: int = 300
    positional_encoding_temperature: float = 10000.0
    layer_norm_eps: float = 1e-5  # torch nn.LayerNorm/GroupNorm default
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @property
    def num_pred_heads(self) -> int:
        # two-stage keeps one extra head pair for scoring encoder proposals
        return self.decoder_layers + (1 if self.two_stage else 0)

    @classmethod
    def from_hf(cls, hf) -> "DeformableDetrConfig":
        if hf.position_embedding_type != "sine":
            raise ValueError(
                f"Unsupported position_embedding_type {hf.position_embedding_type!r}"
            )
        check_no_dilation(hf)
        if hf.use_timm_backbone:
            out_indices = (2, 3, 4) if hf.num_feature_levels > 1 else (4,)
            backbone = replace(timm_resnet_backbone(hf.backbone), out_indices=out_indices)
        else:
            # the AutoBackbone path taps backbone_config.out_features as-is
            backbone = ResNetConfig.from_hf(hf.backbone_config)
        return cls(
            backbone=backbone,
            num_labels=hf.num_labels,
            d_model=hf.d_model,
            num_queries=hf.num_queries,
            encoder_layers=hf.encoder_layers,
            decoder_layers=hf.decoder_layers,
            encoder_attention_heads=hf.encoder_attention_heads,
            decoder_attention_heads=hf.decoder_attention_heads,
            encoder_ffn_dim=hf.encoder_ffn_dim,
            decoder_ffn_dim=hf.decoder_ffn_dim,
            activation_function=hf.activation_function,
            num_feature_levels=hf.num_feature_levels,
            encoder_n_points=hf.encoder_n_points,
            decoder_n_points=hf.decoder_n_points,
            with_box_refine=hf.with_box_refine,
            two_stage=hf.two_stage,
            two_stage_num_proposals=hf.two_stage_num_proposals,
            id2label=tuple(sorted((int(k), v) for k, v in hf.id2label.items())),
        )


@dataclass(frozen=True)
class YolosConfig:
    """YOLOS (hustvl/yolos-*) — plain ViT with appended detection tokens."""

    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    image_size: tuple[int, int] = (800, 1344)
    patch_size: int = 16
    num_channels: int = 3
    num_detection_tokens: int = 100
    use_mid_position_embeddings: bool = True
    qkv_bias: bool = True
    layer_norm_eps: float = 1e-12
    num_labels: int = 91
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @property
    def grid_hw(self) -> tuple[int, int]:
        return self.image_size[0] // self.patch_size, self.image_size[1] // self.patch_size

    @classmethod
    def from_hf(cls, hf) -> "YolosConfig":
        return cls(
            hidden_size=hf.hidden_size,
            num_hidden_layers=hf.num_hidden_layers,
            num_attention_heads=hf.num_attention_heads,
            intermediate_size=hf.intermediate_size,
            hidden_act=hf.hidden_act,
            image_size=tuple(hf.image_size),
            patch_size=hf.patch_size,
            num_channels=hf.num_channels,
            num_detection_tokens=hf.num_detection_tokens,
            use_mid_position_embeddings=hf.use_mid_position_embeddings,
            qkv_bias=hf.qkv_bias,
            layer_norm_eps=hf.layer_norm_eps,
            num_labels=hf.num_labels,
            id2label=tuple(sorted((int(k), v) for k, v in hf.id2label.items())),
        )


@dataclass(frozen=True)
class OwlViTTextConfig:
    """CLIP-style text tower of OWL-ViT."""

    vocab_size: int = 49408
    hidden_size: int = 512
    intermediate_size: int = 2048
    num_hidden_layers: int = 12
    num_attention_heads: int = 8
    max_position_embeddings: int = 16
    hidden_act: str = "quick_gelu"
    layer_norm_eps: float = 1e-5

    @classmethod
    def from_hf(cls, hf) -> "OwlViTTextConfig":
        return cls(
            vocab_size=hf.vocab_size,
            hidden_size=hf.hidden_size,
            intermediate_size=hf.intermediate_size,
            num_hidden_layers=hf.num_hidden_layers,
            num_attention_heads=hf.num_attention_heads,
            max_position_embeddings=hf.max_position_embeddings,
            hidden_act=hf.hidden_act,
            layer_norm_eps=hf.layer_norm_eps,
        )


@dataclass(frozen=True)
class OwlViTVisionConfig:
    """CLIP-style vision tower of OWL-ViT."""

    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    image_size: int = 768
    patch_size: int = 32
    num_channels: int = 3
    hidden_act: str = "quick_gelu"
    layer_norm_eps: float = 1e-5

    @property
    def grid(self) -> int:
        return self.image_size // self.patch_size

    @classmethod
    def from_hf(cls, hf) -> "OwlViTVisionConfig":
        return cls(
            hidden_size=hf.hidden_size,
            intermediate_size=hf.intermediate_size,
            num_hidden_layers=hf.num_hidden_layers,
            num_attention_heads=hf.num_attention_heads,
            image_size=hf.image_size,
            patch_size=hf.patch_size,
            num_channels=hf.num_channels,
            hidden_act=hf.hidden_act,
            layer_norm_eps=hf.layer_norm_eps,
        )


@dataclass(frozen=True)
class OwlViTConfig:
    """OWL-ViT / OWLv2 open-vocabulary detector (google/owlvit-*, google/owlv2-*).

    OWLv2 is architecturally OWL-ViT plus an objectness head (and a
    pad-to-square preprocess handled by the serving spec); `objectness` is
    therefore the one config switch between the two families.
    """

    text: OwlViTTextConfig = field(default_factory=OwlViTTextConfig)
    vision: OwlViTVisionConfig = field(default_factory=OwlViTVisionConfig)
    projection_dim: int = 512
    objectness: bool = False  # True = OWLv2

    @classmethod
    def from_hf(cls, hf) -> "OwlViTConfig":
        return cls(
            text=OwlViTTextConfig.from_hf(hf.text_config),
            vision=OwlViTVisionConfig.from_hf(hf.vision_config),
            projection_dim=hf.projection_dim,
            objectness=hf.model_type == "owlv2",
        )


RESNET_PRESETS = {
    "r18": ResNetConfig(
        embedding_size=64, hidden_sizes=(64, 128, 256, 512), depths=(2, 2, 2, 2),
        layer_type="basic",
    ),
    "r34": ResNetConfig(
        embedding_size=64, hidden_sizes=(64, 128, 256, 512), depths=(3, 4, 6, 3),
        layer_type="basic",
    ),
    "r50": ResNetConfig(),
    "r101": ResNetConfig(depths=(3, 4, 23, 3)),
}

# Published RT-DETRv2 variants (PekingU/rtdetr_v2_*). When loading a checkpoint,
# `from_hf` of the checkpoint's own config takes precedence; presets exist for
# offline/synthetic use.
RTDETR_PRESETS = {
    "rtdetr_v2_r18vd": RTDetrConfig(
        backbone=RESNET_PRESETS["r18"],
        encoder_in_channels=(128, 256, 512),
        decoder_layers=3,
        hidden_expansion=0.5,
    ),
    "rtdetr_v2_r34vd": RTDetrConfig(
        backbone=RESNET_PRESETS["r34"],
        encoder_in_channels=(128, 256, 512),
        decoder_layers=4,
        hidden_expansion=0.5,
    ),
    "rtdetr_v2_r50vd": RTDetrConfig(),
    "rtdetr_v2_r101vd": RTDetrConfig(
        backbone=RESNET_PRESETS["r101"],
        encoder_hidden_dim=384,
        encoder_ffn_dim=2048,
    ),
}


@dataclass(frozen=True)
class Qwen3NextDetConfig:
    """Qwen3-Next's decoder layers as a detector body (`qwen3_next_det`):
    the language model's widths under their published keys, the detector's
    seams (patches in, detection tokens and two heads out) under YOLOS's.

    `num_routed_experts` is the router's width, the model's whole count;
    `num_experts` is how many of them this chip holds, from `expert_offset`
    on (`models/qwen3_next.py`, `ops/moe.py`)."""

    hidden_size: int = 2048
    num_hidden_layers: int = 4
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rms_norm_eps: float = 1e-6
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_routed_experts: int = 512
    num_experts: int = 64
    expert_offset: int = 0
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    image_size: tuple[int, int] = (800, 1344)
    patch_size: int = 16
    num_channels: int = 3
    num_detection_tokens: int = 100
    num_labels: int = 91
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @property
    def num_tokens(self) -> int:
        h, w = self.image_size
        return (h // self.patch_size) * (w // self.patch_size) + self.num_detection_tokens

    def layer_kind(self, i: int) -> str:
        return (
            "full_attention" if (i + 1) % self.full_attention_interval == 0
            else "linear_attention"
        )

    @classmethod
    def from_hf(cls, hf: dict) -> "Qwen3NextDetConfig":
        """From a checkpoint's config.json as a dict: transformers has no
        class for this model_type."""
        names = {f.name for f in fields(cls)} - {"id2label", "image_size"}
        if hf.get("num_routed_experts") is None:
            hf = {**hf, "num_routed_experts": hf["num_experts"]}
        return cls(
            image_size=tuple(hf["image_size"]),
            id2label=tuple(sorted((int(k), v) for k, v in hf.get("id2label", {}).items())),
            **{k: hf[k] for k in names if k in hf},
        )


@dataclass(frozen=True)
class Lfm2MoeDetConfig:
    """LFM2-MoE's decoder layers as a detector body (`lfm2_moe_det`): the
    language model's widths under their published keys (`lfm2_moe`'s
    config.json), the detector's seams under YOLOS's. Every layer is held
    whole: `num_experts` is both the router's width and the experts held
    (`models/lfm2_moe.py`)."""

    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 6
    layer_types: tuple[str, ...] = ("conv", "conv", "full_attention", "conv", "conv", "conv")
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    image_size: tuple[int, int] = (800, 1344)
    patch_size: int = 16
    num_channels: int = 3
    num_detection_tokens: int = 100
    num_labels: int = 91
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_tokens(self) -> int:
        h, w = self.image_size
        return (h // self.patch_size) * (w // self.patch_size) + self.num_detection_tokens

    @classmethod
    def from_hf(cls, hf: dict) -> "Lfm2MoeDetConfig":
        """From a checkpoint's config.json as a dict: the installed
        transformers has no class for this model_type."""
        names = {f.name for f in fields(cls)} - {"id2label", "image_size", "layer_types"}
        cfg = cls(
            image_size=tuple(hf["image_size"]),
            layer_types=tuple(hf["layer_types"]),
            id2label=tuple(sorted((int(k), v) for k, v in hf.get("id2label", {}).items())),
            **{k: hf[k] for k in names if k in hf},
        )
        if len(cfg.layer_types) != cfg.num_hidden_layers:
            raise ValueError(f"{len(cfg.layer_types)} layer_types for "
                             f"{cfg.num_hidden_layers} layers")
        if hf.get("head_dim", cfg.head_dim) != cfg.head_dim:
            raise ValueError(f"head_dim {hf['head_dim']} is not hidden_size / heads = {cfg.head_dim}")
        return cfg


@dataclass(frozen=True)
class KimiLinearDetConfig:
    """Kimi Linear's decoder layers as a detector body (`kimi_linear_det`):
    the language model's widths under their published keys (`kimi_linear`'s
    config.json; its `linear_attn_config` group flattened to `linear_*` and
    the two layer lists, which count layers from 1 as published), the
    detector's seams under YOLOS's. `num_routed_experts` is the router's
    width, `num_experts` how many of them this chip holds, from
    `expert_offset` on (`models/kimi_linear.py`, `ops/moe.py`)."""

    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 5
    kda_layers: tuple[int, ...] = (1, 2, 3, 5)
    full_attn_layers: tuple[int, ...] = (4,)
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    linear_head_dim: int = 128
    linear_num_heads: int = 32
    linear_conv_kernel: int = 4
    gate_low_rank_dim: int = 128
    rms_norm_eps: float = 1e-5
    num_routed_experts: int = 256
    num_experts: int = 64
    expert_offset: int = 0
    num_experts_per_token: int = 8
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    image_size: tuple[int, int] = (800, 1344)
    patch_size: int = 16
    num_channels: int = 3
    num_detection_tokens: int = 100
    num_labels: int = 91
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @property
    def num_tokens(self) -> int:
        h, w = self.image_size
        return (h // self.patch_size) * (w // self.patch_size) + self.num_detection_tokens

    def layer_kind(self, i: int) -> str:
        """Layer i, counted from 0: "kda" or "mla"."""
        return "kda" if i + 1 in self.kda_layers else "mla"

    @classmethod
    def from_hf(cls, hf: dict) -> "KimiLinearDetConfig":
        """From a checkpoint's config.json as a dict: the installed
        transformers has no class for this model_type."""
        names = {f.name for f in fields(cls)} - {"id2label", "image_size"}
        linear = hf["linear_attn_config"]
        if hf.get("num_routed_experts") is None:
            hf = {**hf, "num_routed_experts": hf["num_experts"]}
        cfg = cls(
            image_size=tuple(hf["image_size"]),
            id2label=tuple(sorted((int(k), v) for k, v in hf.get("id2label", {}).items())),
            **{**{k: hf[k] for k in names if k in hf},
               "kda_layers": tuple(linear["kda_layers"]),
               "full_attn_layers": tuple(linear["full_attn_layers"]),
               "linear_head_dim": linear["head_dim"], "linear_num_heads": linear["num_heads"],
               "linear_conv_kernel": linear["short_conv_kernel_size"]},
        )
        layers = sorted(cfg.kda_layers + cfg.full_attn_layers)
        if layers != list(range(1, cfg.num_hidden_layers + 1)):
            raise ValueError(f"kda_layers and full_attn_layers name {layers}, "
                             f"not each of {cfg.num_hidden_layers} layers once")
        for key, want in (("q_lora_rank", None), ("mla_use_nope", True), ("num_shared_experts", 1),
                          ("moe_router_activation_func", "sigmoid"), ("num_expert_group", 1)):
            if hf.get(key, want) != want:
                raise ValueError(f"{key} {hf[key]!r} is not served: this family computes {want!r}")
        return cfg


@dataclass(frozen=True)
class JoyAIFlashDetConfig:
    """JoyAI-LLM-Flash's decoder layers as a detector body (`joyai_flash_det`):
    the language model's widths under DeepSeek-V3's keys (its config.json's
    keys are those of transformers' `DeepseekV3Config`), the detector's seams
    under YOLOS's. `num_routed_experts` is the router's width, `num_experts`
    how many of them this chip holds (the checkpoint's `n_routed_experts`),
    from `expert_offset` on (`models/joyai_flash.py`, `ops/moe.py`)."""

    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    num_routed_experts: int = 256
    num_experts: int = 128
    expert_offset: int = 0
    num_experts_per_token: int = 8
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.5
    image_size: tuple[int, int] = (800, 1344)
    patch_size: int = 16
    num_channels: int = 3
    num_detection_tokens: int = 100
    num_labels: int = 91
    id2label: tuple[tuple[int, str], ...] = ()

    @property
    def id2label_dict(self) -> dict[int, str]:
        return dict(self.id2label)

    @property
    def num_tokens(self) -> int:
        h, w = self.image_size
        return (h // self.patch_size) * (w // self.patch_size) + self.num_detection_tokens

    @classmethod
    def from_hf(cls, hf: dict) -> "JoyAIFlashDetConfig":
        """From a checkpoint's config.json as a dict. `n_routed_experts` is
        the share held; the router's width is `num_routed_experts` where the
        share is not the whole."""
        names = {f.name for f in fields(cls)} - {"id2label", "image_size"}
        held = hf["n_routed_experts"]
        renamed = {"num_experts": held, "num_routed_experts": hf.get("num_routed_experts", held),
                   "num_experts_per_token": hf["num_experts_per_tok"],
                   "moe_renormalize": hf["norm_topk_prob"]}
        if hf.get("q_lora_rank") is None:
            raise ValueError("q_lora_rank None is not served: this family computes a query latent")
        for key, want in (("n_shared_experts", 1), ("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1), ("topk_group", 1),
                          ("rope_scaling", None), ("rope_interleave", True), ("attention_bias", False),
                          ("hidden_act", "silu"), ("moe_layer_freq", 1)):
            if hf.get(key, want) != want:
                raise ValueError(f"{key} {hf[key]!r} is not served: this family computes {want!r}")
        return cls(
            image_size=tuple(hf["image_size"]),
            id2label=tuple(sorted((int(k), v) for k, v in hf.get("id2label", {}).items())),
            **{**{k: hf[k] for k in names if k in hf}, **renamed},
        )
