"""Assigned-architecture registry (11 archs) + dry-run input specs.

Every module in this package defines:
  CONFIG   — the exact assigned full-size ModelConfig
  REDUCED  — a same-family config small enough for a CPU smoke test

``get_config(name)`` / ``get_reduced(name)`` resolve by arch id (dashes or
underscores).  ``input_specs(cfg, shape, par)`` builds the
ShapeDtypeStruct stand-ins each dry-run cell lowers against — no device
allocation anywhere on this path.
"""
from __future__ import annotations

import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import (ModelConfig, ParallelConfig, ShapeConfig,
                                 SHAPES, shape_applicable)

ARCHS = (
    "llama4-scout-17b-16e",
    "granite-moe-3b-a800m",
    "mistral-nemo-12b",
    "granite-8b",
    "qwen3-32b",
    "mistral-large-123b",
    "whisper-base",
    "zamba2-1.2b",
    "mamba2-2.7b",
    "llava-next-mistral-7b",
    "granite-4.0-h-micro",
)


_REDUCED_SUFFIX = "-reduced"


def _module(name: str):
    mod_name = name.replace("-", "_").replace(".", "p")
    return importlib.import_module(f"repro.configs.{mod_name}")


def get_config(name: str) -> ModelConfig:
    """The arch's full config, or its REDUCED one by that config's name
    (``<arch>-reduced``)."""
    if name.endswith(_REDUCED_SUFFIX):
        return get_reduced(name[:-len(_REDUCED_SUFFIX)])
    return _module(name).CONFIG


def get_reduced(name: str) -> ModelConfig:
    return _module(name).REDUCED


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCHS}


# ---------------------------------------------------------------------------
# Dry-run input specs
# ---------------------------------------------------------------------------


def runnable_cells():
    """All (arch, shape) pairs; skipped cells carry a reason string."""
    cells = []
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if not shape_applicable(cfg, shape):
                cells.append((arch, shape.name, "SKIP: full-attention arch; "
                              "long_500k requires sub-quadratic attention"))
            else:
                cells.append((arch, shape.name, None))
    return cells


def batch_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Train/prefill batch as ShapeDtypeStructs.

    VLM: the patch stub occupies part of the assigned seq_len so the
    backbone sees exactly shape.seq_len positions.
    """
    b = shape.global_batch
    s = shape.seq_len
    specs = {}
    if cfg.family == "vlm":
        s_txt = s - cfg.vlm.num_patches
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.vlm.num_patches, cfg.d_model), jnp.float32)
        specs["tokens"] = jax.ShapeDtypeStruct((b, s_txt), jnp.int32)
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, s_txt), jnp.int32)
        return specs
    specs["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if cfg.family in ("encdec", "audio"):
        specs["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encdec.num_frames, cfg.d_model), jnp.float32)
    return specs


def decode_specs(model, shape: ShapeConfig):
    """(tokens, cache) ShapeDtypeStructs for one serve_step."""
    b = shape.global_batch
    cache = jax.eval_shape(
        lambda: model.init_cache(b, shape.seq_len))
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    return tokens, cache


def params_specs(model):
    """Parameter tree as ShapeDtypeStructs (no allocation)."""
    return jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0)))
