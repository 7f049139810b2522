from repro.configs.base import ARCH_IDS, ModelConfig, get_config, list_archs

__all__ = ["ARCH_IDS", "ModelConfig", "get_config", "list_archs"]
