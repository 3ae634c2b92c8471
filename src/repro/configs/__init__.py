"""Architecture registry: ``get_config(arch_id)`` / ``list_archs()``."""
from repro.configs.base import (ModelConfig, MoEConfig, MambaConfig,
                                ShapeConfig, SHAPES, YarnConfig, reduced)

from repro.configs.rwkv6_1p6b import CONFIG as _rwkv6
from repro.configs.deepseek_moe_16b import CONFIG as _deepseek
from repro.configs.musicgen_medium import CONFIG as _musicgen
from repro.configs.qwen2_1p5b import CONFIG as _qwen2
from repro.configs.granite_20b import CONFIG as _granite
from repro.configs.qwen2_vl_2b import CONFIG as _qwen2vl
from repro.configs.jamba_v01_52b import CONFIG as _jamba
from repro.configs.qwen3_0p6b import CONFIG as _qwen3
from repro.configs.dbrx_132b import CONFIG as _dbrx
from repro.configs.h2o_danube_1p8b import CONFIG as _danube
from repro.configs.llama2 import CONFIGS as _llama2
from repro.configs.deepseek_v2_lite import CONFIG as _deepseek_v2_lite

ASSIGNED = {
    c.name: c for c in (
        _rwkv6, _deepseek, _musicgen, _qwen2, _granite,
        _qwen2vl, _jamba, _qwen3, _dbrx, _danube)
}

REGISTRY = dict(ASSIGNED)
REGISTRY.update(_llama2)
REGISTRY[_deepseek_v2_lite.name] = _deepseek_v2_lite


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[name]


def list_archs(assigned_only: bool = False):
    return sorted(ASSIGNED if assigned_only else REGISTRY)


def supports_shape(cfg: ModelConfig, shape: ShapeConfig) -> bool:
    """long_500k requires sub-quadratic sequence mixing (see DESIGN.md §4)."""
    if shape.name != "long_500k":
        return True
    if cfg.mixer in ("rwkv6", "mamba"):   # ssm / hybrid: O(1)-state decode
        return True
    return cfg.sliding_window > 0          # SWA dense: window-bounded cache


__all__ = [
    "ModelConfig", "MoEConfig", "MambaConfig", "ShapeConfig", "SHAPES",
    "YarnConfig",
    "reduced", "ASSIGNED", "REGISTRY", "get_config", "list_archs",
    "supports_shape",
]
