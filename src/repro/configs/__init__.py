"""Architecture configs + registry (one module per assigned arch)."""

from .base import (  # noqa: F401
    ModelConfig, MoEConfig, SSMConfig, HybridConfig, EncDecConfig,
    MLAConfig, YarnConfig,
)
from .registry import ARCHS, get_config  # noqa: F401
