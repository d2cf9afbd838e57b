"""Config registry: importing this package registers all architectures."""
from repro.configs import base
from repro.configs.base import (
    SHAPES,
    ArchConfig,
    FedCfg,
    MeshCfg,
    ParamCfg,
    RunCfg,
    ShapeCfg,
    get_arch,
    list_archs,
    register,
)

# Assigned architectures (importing registers them).
from repro.configs import (  # noqa: E402,F401
    chameleon_34b,
    chatglm3_6b,
    gemma3_12b,
    granite_4_0_h_micro,
    llama3_405b,
    llama4_scout_17b_a16e,
    mixtral_8x22b,
    qwen3_8b,
    whisper_small,
    xlstm_125m,
    zamba2_2p7b,
)

ASSIGNED = [
    "llama4-scout-17b-a16e",
    "mixtral-8x22b",
    "chatglm3-6b",
    "llama3-405b",
    "gemma3-12b",
    "qwen3-8b",
    "chameleon-34b",
    "zamba2-2.7b",
    "whisper-small",
    "xlstm-125m",
    "granite-4.0-h-micro",
]

__all__ = [
    "base",
    "SHAPES",
    "ArchConfig",
    "FedCfg",
    "MeshCfg",
    "ParamCfg",
    "RunCfg",
    "ShapeCfg",
    "get_arch",
    "list_archs",
    "register",
    "ASSIGNED",
]
