from repro.config.base import (
    LayerDesc,
    LayerLayout,
    MoEConfig,
    MambaConfig,
    MLAConfig,
    EncoderConfig,
    MemComConfig,
    ModelConfig,
    ShapeSpec,
    SHAPES,
    YarnScaling,
)

__all__ = [
    "LayerDesc",
    "LayerLayout",
    "MoEConfig",
    "MambaConfig",
    "MLAConfig",
    "EncoderConfig",
    "MemComConfig",
    "ModelConfig",
    "ShapeSpec",
    "SHAPES",
    "YarnScaling",
]
