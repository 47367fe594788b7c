"""Checkpoint loading: HF torch checkpoint -> (config, Flax params), with an
Orbax cache so torch is only needed the first time.

Plays the role of the reference's weight-baking flow (download.py +
from_pretrained at serve.py:203): `spotter-tpu-download` pre-converts at image
build; pod start loads the converted Orbax checkpoint directly.
"""

import dataclasses
import json
import logging
import os
import typing
from pathlib import Path

import numpy as np

from spotter_tpu.models.configs import (
    ConditionalDetrConfig,
    DabDetrConfig,
    DeformableDetrConfig,
    DetrConfig,
    KimiLinearDetConfig,
    Lfm2MoeDetConfig,
    OwlViTConfig,
    Qwen3NextDetConfig,
    RTDetrConfig,
    YolosConfig,
)

logger = logging.getLogger(__name__)

CACHE_ENV = "SPOTTER_TPU_CACHE"
DEFAULT_CACHE = "~/.cache/spotter_tpu"
# Bump when conversion rules or the cache layout change: the cache key must
# invalidate old conversions, or a fixed rule table would keep serving stale
# params forever.
CACHE_VERSION = "v3"


def _tuplify(v):
    return tuple(_tuplify(x) for x in v) if isinstance(v, list) else v


def config_from_dict(cls, data: dict):
    """Rebuild a (possibly nested) frozen config dataclass from JSON data.

    JSON round-trips tuples as lists; config fields are tuples (hashability
    under jit), so sequences are re-tuplified and nested dataclasses recursed.
    """
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in data:
            continue
        value = data[f.name]
        hint = hints.get(f.name)
        if dataclasses.is_dataclass(hint) and isinstance(value, dict):
            value = config_from_dict(hint, value)
        elif isinstance(value, list):
            value = _tuplify(value)
        kwargs[f.name] = value
    return cls(**kwargs)


def cache_dir() -> Path:
    return Path(os.environ.get(CACHE_ENV, DEFAULT_CACHE)).expanduser()


def _cache_path(model_name: str) -> Path:
    return cache_dir() / f"{model_name.replace('/', '--')}--{CACHE_VERSION}"


def _save_cache(path: Path, cfg, params: dict) -> None:
    try:
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        ckptr.save(path.absolute() / "params", params, force=True)
        ckptr.wait_until_finished()
        # Config is written LAST: its presence marks the cache entry complete,
        # and it is what lets the runtime load path skip torch+transformers
        # entirely (the serving image uninstalls them after baking).
        (path / "config.json").write_text(json.dumps(dataclasses.asdict(cfg)))
    except Exception:  # cache is best-effort; serving works without it
        logger.exception("Failed to write param cache at %s", path)


def _load_cache(path: Path, config_cls):
    if not ((path / "params").exists() and (path / "config.json").exists()):
        return None
    try:
        import orbax.checkpoint as ocp

        cfg = config_from_dict(config_cls, json.loads((path / "config.json").read_text()))
        ckptr = ocp.StandardCheckpointer()
        return cfg, ckptr.restore(path.absolute() / "params")
    except Exception:
        logger.exception("Failed to read param cache at %s", path)
        return None


def load_rtdetr_from_hf(model_name: str) -> tuple[RTDetrConfig, dict]:
    """Load + convert an RT-DETR(v2) checkpoint; Orbax-cached per MODEL_NAME.

    The cache (params + config.json) is consulted FIRST so the runtime path in
    the baked serving image never imports torch/transformers (Dockerfile
    uninstalls them after `spotter-tpu-download` converts the weights).
    """
    cached = _load_cache(_cache_path(model_name), RTDetrConfig)
    if cached is not None:
        logger.info("Loaded converted config+params for %s from cache", model_name)
        return cached

    # Cache miss: first-time conversion (build-time bake or developer machine).
    import torch
    from transformers import AutoConfig, AutoModelForObjectDetection

    from spotter_tpu.convert.rtdetr_rules import rtdetr_rules
    from spotter_tpu.convert.torch_to_jax import convert_state_dict

    cfg = RTDetrConfig.from_hf(AutoConfig.from_pretrained(model_name))
    with torch.no_grad():
        model = AutoModelForObjectDetection.from_pretrained(model_name).eval()
    # strict: a rule whose torch key is absent means the rule table and the
    # checkpoint disagree — caching such a partial tree would serve a broken
    # model silently on every later pod start.
    params = convert_state_dict(model.state_dict(), rtdetr_rules(cfg), strict=True)
    _save_cache(_cache_path(model_name), cfg, params)
    return cfg, params


def _load_detr_lineage_from_hf(model_name: str, config_cls, rules_fn):
    """Shared loader for the DETR-lineage families (DETR/Table-Transformer,
    Conditional-DETR, Deformable-DETR): AutoConfig -> config dataclass,
    AutoModel state_dict -> `rules_fn(cfg, naming)` rule-table conversion
    (timm- or HF-backbone serialization), Orbax-cached per MODEL_NAME."""
    cached = _load_cache(_cache_path(model_name), config_cls)
    if cached is not None:
        logger.info("Loaded converted config+params for %s from cache", model_name)
        return cached

    import torch
    from transformers import AutoConfig, AutoModelForObjectDetection

    from spotter_tpu.convert.torch_to_jax import convert_state_dict

    hf_cfg = AutoConfig.from_pretrained(model_name)
    cfg = config_cls.from_hf(hf_cfg)
    with torch.no_grad():
        model = AutoModelForObjectDetection.from_pretrained(model_name).eval()
    naming = "timm" if hf_cfg.use_timm_backbone else "hf"
    params = convert_state_dict(model.state_dict(), rules_fn(cfg, naming), strict=True)
    _save_cache(_cache_path(model_name), cfg, params)
    return cfg, params


def load_detr_from_hf(model_name: str) -> tuple[DetrConfig, dict]:
    from spotter_tpu.convert.detr_rules import detr_rules

    return _load_detr_lineage_from_hf(model_name, DetrConfig, detr_rules)


def load_conditional_detr_from_hf(
    model_name: str,
) -> tuple[ConditionalDetrConfig, dict]:
    from spotter_tpu.convert.conditional_detr_rules import conditional_detr_rules

    return _load_detr_lineage_from_hf(
        model_name, ConditionalDetrConfig, conditional_detr_rules
    )


def load_deformable_detr_from_hf(
    model_name: str,
) -> tuple[DeformableDetrConfig, dict]:
    from spotter_tpu.convert.deformable_detr_rules import deformable_detr_rules

    return _load_detr_lineage_from_hf(
        model_name, DeformableDetrConfig, deformable_detr_rules
    )


def load_dab_detr_from_hf(model_name: str) -> tuple[DabDetrConfig, dict]:
    from spotter_tpu.convert.dab_detr_rules import dab_detr_rules

    return _load_detr_lineage_from_hf(model_name, DabDetrConfig, dab_detr_rules)


def load_owlvit_from_hf(model_name: str) -> tuple[OwlViTConfig, dict]:
    """Load + convert an OWL-ViT / OWLv2 checkpoint; Orbax-cached per MODEL_NAME."""
    cached = _load_cache(_cache_path(model_name), OwlViTConfig)
    if cached is not None:
        logger.info("Loaded converted config+params for %s from cache", model_name)
        return cached

    import torch
    from transformers import AutoConfig

    from spotter_tpu.convert.owlvit_rules import owlvit_rules
    from spotter_tpu.convert.torch_to_jax import convert_state_dict

    cfg = OwlViTConfig.from_hf(AutoConfig.from_pretrained(model_name))
    if cfg.objectness:
        from transformers.models.owlv2.modeling_owlv2 import (
            Owlv2ForObjectDetection as DetectionModel,
        )
    else:
        from transformers.models.owlvit.modeling_owlvit import (
            OwlViTForObjectDetection as DetectionModel,
        )
    with torch.no_grad():
        model = DetectionModel.from_pretrained(model_name).eval()
    # The rule table maps the detection path only (contrastive-only weights —
    # visual_projection, logit_scale — are deliberately unmapped); strict still
    # requires every mapped torch key to exist in the checkpoint.
    params = convert_state_dict(model.state_dict(), owlvit_rules(cfg), strict=True)
    _save_cache(_cache_path(model_name), cfg, params)
    return cfg, params


def owlvit_tokenize(
    model_name: str, prompts: list[str], max_length: int
) -> tuple[np.ndarray, np.ndarray]:
    """Tokenize text queries, cached per MODEL_NAME alongside the param cache.

    The cache file makes the runtime path tokenizer-free: queries seen at bake
    time (the default taxonomy — download.py runs build_detector) resolve from
    JSON; only novel runtime queries import transformers.
    """
    path = _cache_path(model_name) / "tokenized.json"
    table: dict[str, dict] = {}
    if path.exists():
        try:
            table = json.loads(path.read_text())
        except Exception:
            logger.exception("Failed to read tokenization cache at %s", path)
    missing = [p for p in prompts if p not in table]
    if missing:
        from transformers import AutoTokenizer  # lazy: bake/dev machines only

        tok = AutoTokenizer.from_pretrained(model_name)
        enc = tok(
            missing, padding="max_length", max_length=max_length, truncation=True
        )
        for p, ids, mask in zip(missing, enc["input_ids"], enc["attention_mask"]):
            table[p] = {"ids": ids, "mask": mask}
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(json.dumps(table))
        except Exception:
            logger.exception("Failed to write tokenization cache at %s", path)
    ids = np.asarray([table[p]["ids"] for p in prompts], dtype=np.int32)
    mask = np.asarray([table[p]["mask"] for p in prompts], dtype=np.int32)
    return ids, mask


def load_yolos_from_hf(model_name: str) -> tuple[YolosConfig, dict]:
    """Load + convert a YOLOS checkpoint; Orbax-cached per MODEL_NAME."""
    cached = _load_cache(_cache_path(model_name), YolosConfig)
    if cached is not None:
        logger.info("Loaded converted config+params for %s from cache", model_name)
        return cached

    import torch
    from transformers import AutoConfig, AutoModelForObjectDetection

    from spotter_tpu.convert.torch_to_jax import convert_state_dict
    from spotter_tpu.convert.yolos_rules import yolos_rules

    cfg = YolosConfig.from_hf(AutoConfig.from_pretrained(model_name))
    with torch.no_grad():
        model = AutoModelForObjectDetection.from_pretrained(model_name).eval()
    params = convert_state_dict(model.state_dict(), yolos_rules(cfg), strict=True)
    _save_cache(_cache_path(model_name), cfg, params)
    return cfg, params


_SAFETENSORS_DTYPES = {
    "F64": "float64", "F32": "float32", "F16": "float16", "BF16": "bfloat16",
    "I64": "int64", "I32": "int32", "I16": "int16", "I8": "int8", "U8": "uint8",
    "BOOL": "bool",
}


def read_safetensors_numpy(path) -> dict[str, np.ndarray]:
    """A `.safetensors` file's tensors as numpy views of one memory map, in
    the types they were written in (bfloat16 through ml_dtypes). No torch,
    no transformers, no copy: the format is an 8-byte header length, a JSON
    header (name -> dtype, shape, byte range), then the bytes."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

    with open(path, "rb") as f:
        header_len = int.from_bytes(f.read(8), "little")
        header = json.loads(f.read(header_len))
    data = np.memmap(path, dtype=np.uint8, mode="r", offset=8 + header_len)
    out = {}
    for name, entry in header.items():
        if name == "__metadata__":
            continue
        lo, hi = entry["data_offsets"]
        dtype = np.dtype(_SAFETENSORS_DTYPES[entry["dtype"]])
        out[name] = data[lo:hi].view(dtype).reshape(entry["shape"])
    return out


def load_config_and_safetensors(model_name: str, config_cls, convert):
    """Load a checkpoint directory (`config.json` + `model.safetensors`) of a
    family transformers' auto-classes do not know: the config as a dict into
    `config_cls.from_hf`, the tensors straight into numpy
    (`read_safetensors_numpy`; no torch module is built) through
    `convert(tensors, cfg)`. Orbax-cached per MODEL_NAME like the others."""
    cached = _load_cache(_cache_path(model_name), config_cls)
    if cached is not None:
        logger.info("Loaded converted config+params for %s from cache", model_name)
        return cached
    root = Path(model_name)
    cfg = config_cls.from_hf(json.loads((root / "config.json").read_text()))
    params = convert(read_safetensors_numpy(root / "model.safetensors"), cfg)
    _save_cache(_cache_path(model_name), cfg, params)
    return cfg, params


def load_qwen3_next_det(model_name: str) -> tuple[Qwen3NextDetConfig, dict]:
    from spotter_tpu.convert.qwen3_next_rules import convert_qwen3_next

    return load_config_and_safetensors(model_name, Qwen3NextDetConfig, convert_qwen3_next)


def load_lfm2_moe_det(model_name: str) -> tuple[Lfm2MoeDetConfig, dict]:
    from spotter_tpu.convert.lfm2_moe_rules import convert_lfm2_moe

    return load_config_and_safetensors(model_name, Lfm2MoeDetConfig, convert_lfm2_moe)


def load_kimi_linear_det(model_name: str) -> tuple[KimiLinearDetConfig, dict]:
    from spotter_tpu.convert.kimi_linear_rules import convert_kimi_linear

    return load_config_and_safetensors(model_name, KimiLinearDetConfig, convert_kimi_linear)
