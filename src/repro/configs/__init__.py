"""Architecture configs (assigned pool + the paper's own TinyML models)."""
from repro.configs.registry import (ALIASES, ARCH_IDS, all_configs,
                                    canonical, describe, get_config,
                                    get_smoke_config)

__all__ = ["ALIASES", "ARCH_IDS", "all_configs", "canonical", "describe",
           "get_config", "get_smoke_config"]
