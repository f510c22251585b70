"""Config registry: ``get_config(name)`` / ``list_archs()`` / shape lookup."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401 (public re-exports)
    LONG_500K,
    DECODE_32K,
    PREFILL_32K,
    SHAPES,
    TRAIN_4K,
    ModelConfig,
    MoEConfig,
    PGMConfig,
    RNNTConfig,
    ShapeConfig,
    TrainConfig,
    reduce_for_smoke,
)

_ARCH_MODULES: Dict[str, str] = {
    "mixtral-8x7b": "mixtral_8x7b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "minitron-8b": "minitron_8b",
    "starcoder2-3b": "starcoder2_3b",
    "gemma3-27b": "gemma3_27b",
    "gemma-7b": "gemma_7b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "rwkv6-3b": "rwkv6_3b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "paligemma-3b": "paligemma_3b",
    "rnnt-crdnn": "rnnt_crdnn",
    "conformer-l-transducer": "conformer_l_transducer",
}

#: the transducer architectures, which the LM dry-run cells leave out
TRANSDUCER_ARCHS = ("rnnt-crdnn", "conformer-l-transducer")
ASSIGNED_ARCHS: List[str] = [a for a in _ARCH_MODULES
                             if a not in TRANSDUCER_ARCHS]


def get_config(name: str) -> ModelConfig:
    smoke = name.endswith("-smoke")
    base = name[: -len("-smoke")] if smoke else name
    if base not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[base]}")
    cfg: ModelConfig = mod.CONFIG
    return reduce_for_smoke(cfg) if smoke else cfg


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def list_archs(include_paper: bool = True) -> List[str]:
    return list(_ARCH_MODULES) if include_paper else list(ASSIGNED_ARCHS)


def cells(include_skips: bool = False):
    """Yield every (arch, shape) dry-run cell.  ``long_500k`` is skipped for
    pure full-attention archs (DESIGN.md §4) unless include_skips."""
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if shape.name == "long_500k" and not cfg.is_subquadratic():
                if include_skips:
                    yield arch, shape.name, "skip"
                continue
            yield (arch, shape.name, "run") if include_skips else (arch, shape.name)
