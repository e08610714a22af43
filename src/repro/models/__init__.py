"""Model zoo: all assigned architectures as pure-function families."""
from .base import (
    ModelConfig,
    ParamSpec,
    abstract_params,
    ce_loss,
    count_params,
    init_params,
    param_pspecs,
    param_shardings,
    ps,
    serving_specs,
)
from .registry import FAMILIES, FamilyOps, concrete_batch, input_specs, loss_mask, ops_for

__all__ = [
    "ModelConfig", "ParamSpec", "ps", "abstract_params", "init_params",
    "param_pspecs", "param_shardings", "serving_specs", "count_params", "ce_loss",
    "FAMILIES", "FamilyOps", "ops_for", "input_specs", "concrete_batch",
    "loss_mask",
]
