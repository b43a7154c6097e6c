"""A model by name, for the tools: the registry's, or a benchmark
configuration's (``benchmark/configs/<name>.json``).  A configuration that
cuts a model to one chip's share serves it under a name of its own (its
``model``), which the file registers with its ``model_config``, as the
benchmark's replica does before ``serve`` resolves it."""

from __future__ import annotations

import json
from pathlib import Path

CONFIGS = Path(__file__).resolve().parent.parent / "benchmark" / "configs"


def model_config_for(name: str):
    """``ModelConfig`` of a registry name (quant suffix and all) or of the
    benchmark configuration ``name``."""
    from distributed_inference_demo_tpu.models import get_model_config
    from distributed_inference_demo_tpu.models.base import ModelConfig
    from distributed_inference_demo_tpu.models.registry import MODEL_REGISTRY
    path = CONFIGS / f"{name}.json"
    if path.is_file():
        conf = json.loads(path.read_text())
        MODEL_REGISTRY.setdefault(conf["model"],
                                  ModelConfig(**conf["model_config"]))
        name = conf["serve_model"]
    return get_model_config(name)
