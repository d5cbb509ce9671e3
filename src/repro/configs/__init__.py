"""Architecture registry: ``get_config(name)`` / ``--arch <id>``."""
from __future__ import annotations

import importlib

from .base import SHAPES, ModelConfig, ShapeConfig, cell_applicable

ARCHS = (
    "internlm2_20b",
    "llama3_8b",
    "granite_20b",
    "qwen3_14b",
    "mamba2_1p3b",
    "internvl2_76b",
    "kimi_k2_1t",
    "grok1_314b",
    "musicgen_medium",
    "hymba_1p5b",
    "falcon_h1_34b",
)

# one chip's pipeline stage of a published model, ``<arch>-stage``: that
# module's ``stage()``. Not in ARCHS, which hold whole models.
STAGES = ("falcon_h1_34b_stage",)

# CLI ids (dashes) ↔ module names (underscores)
_ALIAS = {a.replace("_", "-"): a for a in ARCHS}


def get_config(name: str) -> ModelConfig:
    key = name.replace("-", "_").replace(".", "p")
    if key in STAGES:
        mod = importlib.import_module(
            f"repro.configs.{key.removesuffix('_stage')}")
        return mod.stage()
    if key not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_ALIAS)}")
    mod = importlib.import_module(f"repro.configs.{key}")
    return mod.config()


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCHS}


__all__ = ["ARCHS", "STAGES", "SHAPES", "ModelConfig", "ShapeConfig",
           "cell_applicable", "get_config", "all_configs"]
