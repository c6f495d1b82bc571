"""Architecture registry: ``get_config(name)`` / ``ARCHS``."""
from repro.configs.base import (
    MLAConfig, MoEConfig, ModelConfig, RopeScaling, SSMConfig, ShapeConfig, SHAPES,
    LayerSpec, layer_pattern, input_specs, smoke_variant,
)

from repro.configs.deepseek_v2_236b import CONFIG as _dsv2
from repro.configs.deepseek_v2_lite_16b import CONFIG as _dsv2l
from repro.configs.yi_9b import CONFIG as _yi
from repro.configs.deepseek_7b import CONFIG as _ds7
from repro.configs.gemma_2b import CONFIG as _g2b
from repro.configs.gemma2_27b import CONFIG as _g27
from repro.configs.chameleon_34b import CONFIG as _cham
from repro.configs.whisper_large_v3 import CONFIG as _whis
from repro.configs.mamba2_1_3b import CONFIG as _mamba
from repro.configs.jamba_1_5_large_398b import CONFIG as _jamba

ARCHS = {c.name: c for c in
         [_dsv2, _dsv2l, _yi, _ds7, _g2b, _g27, _cham, _whis, _mamba, _jamba]}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


__all__ = [
    "ARCHS", "get_config", "ModelConfig", "MoEConfig", "MLAConfig",
    "RopeScaling",
    "SSMConfig", "ShapeConfig", "SHAPES", "LayerSpec", "layer_pattern",
    "input_specs", "smoke_variant",
]
