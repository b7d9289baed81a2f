"""Architecture configs + registry (one module per assigned arch)."""

from .base import (  # noqa: F401
    ModelConfig, MoEConfig, SSMConfig, HybridConfig, EncDecConfig,
    MLAConfig, YarnConfig,
    ShapeConfig, SHAPES, SHAPES_BY_NAME, applicable,
    TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K,
)
from .registry import ARCHS, get_config  # noqa: F401
