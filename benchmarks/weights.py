"""Weights of a configuration, made by the benchmark from the configuration's
`weights.seed`, as a Hugging Face checkpoint directory (config.json +
model.safetensors) that BOTH sides read: the server loads it through its
production path (`--model <dir>`: AutoModelForObjectDetection -> the repo's
torch->Flax conversion), the plain reference (reference.py) loads it into
transformers' own torch model. Nothing the program made is handed to the
reference.

What belongs to one family of models (which transformers class, how its
tensors are drawn) is a file of its own, `families/<model_type>.py`, found by
the `model_type` in the configuration's file; the label table is
`data/<bench.labels>.json`.
"""

import copy
import hashlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
HARNESS_KEYS = ("name", "source", "reduced", "assumed", "weights", "serve", "regime", "bench")


def family(model_type: str):
    """benchmarks/families/<model_type>.py as a module."""
    path = os.path.join(HERE, "families", f"{model_type}.py")
    if not os.path.exists(path):
        raise ValueError(f"no family file for model_type {model_type!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_family_{model_type}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def labels_of(cfg: dict) -> list:
    with open(os.path.join(HERE, "data", f"{cfg['bench']['labels']}.json")) as f:
        return json.load(f)


def hf_config_dict(cfg: dict) -> dict:
    """The Hugging Face config.json of a configuration file: every key of
    the file that is not the harness's own, plus the label table."""
    # a deep copy: transformers' config classes pop keys out of nested dicts
    hf = copy.deepcopy({k: v for k, v in cfg.items() if k not in HARNESS_KEYS})
    labels = labels_of(cfg)
    assert len(labels) == hf["num_labels"]
    hf["id2label"] = {str(i): name for i, name in enumerate(labels)}
    hf["label2id"] = {name: i for i, name in enumerate(labels)}
    hf["architectures"] = [family(cfg["model_type"]).ARCHITECTURE]
    return hf


def build_model(cfg: dict):
    """transformers' torch model of the configuration with the seeded weights."""
    import torch

    fam = family(cfg["model_type"])
    with torch.no_grad():
        model = fam.new_model(hf_config_dict(cfg)).eval()
        fam.seed_weights(model, cfg["weights"])
    return model


def checkpoint_tag(cfg: dict) -> str:
    blob = json.dumps({k: cfg[k] for k in sorted(cfg)
                       if k not in ("serve", "regime", "assumed", "bench")}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def ensure_checkpoint(cfg: dict, work_dir: str) -> str:
    """Write the checkpoint once per checkout; later runs find it. The
    directory's name carries the family's tag (the server picks the family
    by name) and a hash of what the weights depend on."""
    tag = family(cfg["model_type"]).NAME_TAG
    name = cfg["name"] if tag in cfg["name"] else f"{tag}-{cfg['name']}"
    path = os.path.join(work_dir, f"{name}-{checkpoint_tag(cfg)}")
    done = os.path.join(path, "DONE")
    if os.path.exists(done):
        return path
    os.makedirs(path, exist_ok=True)
    model = build_model(cfg)
    model.save_pretrained(path, safe_serialization=True)
    with open(done, "w") as f:
        f.write("ok\n")
    return path
