from repro.configs.base import (
    FULL,
    SHAPES,
    SLIDING,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    ShapeConfig,
    YarnConfig,
    get_config,
    list_archs,
    register,
    shape_applicable,
)

__all__ = [
    "FULL", "SHAPES", "SLIDING", "ModelConfig", "MoEConfig", "SSMConfig",
    "ShapeConfig", "YarnConfig", "get_config", "list_archs", "register",
    "shape_applicable",
]
