"""Config registry: importing this package registers all architectures."""
from repro.configs.base import (
    REGISTRY, SHAPES, ElasticConfig, ModelConfig, MoEConfig, ShapeConfig,
    default_elastic, get_config, get_elastic, list_archs, shape_applicable,
)

# Assigned architectures (registration side effects).
from repro.configs import (  # noqa: F401
    phi3_medium_14b, gemma3_27b, qwen2_7b, granite_34b, mamba2_780m,
    qwen2_moe_a2p7b, grok1_314b, recurrentgemma_2b, whisper_medium,
    llama32_vision_11b, elasti_toy, lfm2_8b_a1b,
)

ASSIGNED = [
    "phi3-medium-14b", "gemma3-27b", "qwen2-7b", "granite-34b",
    "mamba2-780m", "qwen2-moe-a2.7b", "grok-1-314b", "recurrentgemma-2b",
    "whisper-medium", "llama-3.2-vision-11b",
]

__all__ = [
    "REGISTRY", "SHAPES", "ASSIGNED", "ElasticConfig", "ModelConfig",
    "MoEConfig", "ShapeConfig", "default_elastic", "get_config",
    "get_elastic", "list_archs", "shape_applicable",
]
