"""Assigned-architecture registry (+ the paper's FIR testbed config)."""
from .base import AmmConfig, ArchConfig, ShapeConfig, SHAPES, reduced

_MODULES = {
    "deepseek-v3-671b": "deepseek_v3_671b",
    "grok-1-314b": "grok1_314b",
    "mamba2-370m": "mamba2_370m",
    "qwen1.5-110b": "qwen1_5_110b",
    "qwen2-0.5b": "qwen2_0_5b",
    "llama3.2-3b": "llama3_2_3b",
    "yi-34b": "yi_34b",
    "whisper-base": "whisper_base",
    "chameleon-34b": "chameleon_34b",
    "zamba2-2.7b": "zamba2_2_7b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ARCH_NAMES = sorted(_MODULES)


def get_arch(name: str) -> ArchConfig:
    import importlib
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}")
    mod = importlib.import_module(f".{_MODULES[name]}", __package__)
    return mod.CONFIG


__all__ = ["AmmConfig", "ArchConfig", "ShapeConfig", "SHAPES", "reduced",
           "ARCH_NAMES", "get_arch"]
