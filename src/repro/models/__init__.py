"""Model registry: family string -> model class.

Families (models/config.py): dense / moe / vlm -> TransformerLM,
ssm -> MambaLM, hybrid -> HybridLM (GraniteHybridLM when the config lists
its ``layer_types``), encdec -> EncDecLM.  All expose the
same functional surface: init_params / param_specs / loss_fn / prefill /
init_cache / cache_specs / decode_step.
"""
from __future__ import annotations

from typing import Optional

from repro.models.config import ModelConfig, ParallelConfig
from repro.parallel.sharding import ShardCtx


def build_model(cfg: ModelConfig, par: Optional[ParallelConfig] = None,
                ctx: Optional[ShardCtx] = None):
    from repro.models.encdec import EncDecLM
    from repro.models.granite_hybrid import GraniteHybridLM
    from repro.models.hybrid import HybridLM
    from repro.models.mamba_lm import MambaLM
    from repro.models.transformer import TransformerLM

    par = par if par is not None else ParallelConfig()
    family = cfg.family
    if family in ("dense", "moe", "vlm"):
        return TransformerLM(cfg, par, ctx)
    if family == "ssm":
        return MambaLM(cfg, par, ctx)
    if family == "hybrid" and cfg.layer_types is not None:
        return GraniteHybridLM(cfg, par, ctx)
    if family == "hybrid":
        return HybridLM(cfg, par, ctx)
    if family in ("encdec", "audio"):
        return EncDecLM(cfg, par, ctx)
    raise ValueError(f"unknown model family {family!r}")
