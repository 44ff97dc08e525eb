"""Architecture registry: importing this package registers all assigned
architectures. ``get_config("<id>")`` / ``--arch <id>`` resolve here."""

from .base import (
    ModelConfig,
    ShapeConfig,
    SHAPES,
    get_config,
    list_configs,
    register_config,
    smoke_config,
)

# importing registers each config
from . import (  # noqa: F401
    gemma2_2b,
    granite_moe_3b_a800m,
    h2o_danube_3_4b,
    llama3_2_3b,
    mamba2_130m,
    musicgen_large,
    qwen2_vl_72b,
    qwen3_moe_235b_a22b,
    starcoder2_15b,
    zamba2_2_7b,
    zamba2_7b,
)

ALL_ARCHS = list_configs()

__all__ = [
    "ModelConfig",
    "ShapeConfig",
    "SHAPES",
    "get_config",
    "list_configs",
    "register_config",
    "smoke_config",
    "ALL_ARCHS",
]
