"""Model zoo: config schema, shared layers, and the block implementations
(GQA attention, MLA, MoE/EP, Mamba2-SSD, RG-LRU) assembled in
``transformer.py``."""

from repro.models.config import (
    BlockSpec,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RGLRUConfig,
    SSMConfig,
    ShapeConfig,
    YarnConfig,
    reduced_for_smoke,
)
from repro.models.param import (
    ParamDef,
    abstract_params,
    init_params,
    param_specs,
    stack_defs,
)
from repro.models.transformer import (
    ShardCtx,
    decode_step,
    forward,
    init_cache,
    logits_fn,
    model_defs,
)

__all__ = [
    "BlockSpec",
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "RGLRUConfig",
    "SSMConfig",
    "ShapeConfig",
    "YarnConfig",
    "reduced_for_smoke",
    "ParamDef",
    "abstract_params",
    "init_params",
    "param_specs",
    "stack_defs",
    "ShardCtx",
    "decode_step",
    "forward",
    "init_cache",
    "logits_fn",
    "model_defs",
]
