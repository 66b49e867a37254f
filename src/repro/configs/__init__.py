from .base import ModelConfig, ARCH_IDS, ALIASES, get_config, registry
