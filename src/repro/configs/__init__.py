from repro.configs.base import (
    ARCH_IDS,
    SHAPES,
    ClusterKVConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    YarnConfig,
    all_cells,
    cells,
    get_config,
    reduced_config,
)

__all__ = [
    "ARCH_IDS",
    "SHAPES",
    "ClusterKVConfig",
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "YarnConfig",
    "all_cells",
    "cells",
    "get_config",
    "reduced_config",
]
