"""Production mesh definitions.

TPU v5e pod = 16×16 = 256 chips.  Single-pod mesh: (data=16, model=16).
Multi-pod adds a leading ``pod`` axis (2 pods = 512 chips): plain data
parallelism across pods, so the only cross-pod traffic is the gradient
all-reduce — deliberately matched to the ICI-vs-DCN bandwidth asymmetry.

Functions, not module constants: importing this module must never touch
jax device state (device count is frozen at first use, and tests want 1
device while the dry-run wants 512).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.models.config import ParallelConfig
from repro.parallel.sharding import ShardCtx


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests use (1,1)/(2,2); elastic restarts resize).

    Every axis is ``Auto``: the model places data with logical-axis
    ``with_sharding_constraint`` calls and lets the partitioner propagate
    the rest (a vocab-sharded embedding gather, say).  ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which such ops demand an
    ``out_sharding`` at every call site."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_ctx(mesh: Optional[Mesh], par: ParallelConfig,
             cfg=None) -> ShardCtx:
    """``cfg`` (a ModelConfig) gates the dedicated ``qkv_heads`` rule:
    the persisted [wq|wk|wv] concat shards over the model axis only when
    every segment's head count divides it — otherwise a shard boundary
    would cut across the q/k/v seams (8 KV heads on a 16-way axis) and
    the concat would stop being layout-neutral, so it replicates."""
    qkv_ok = True
    if cfg is not None and mesh is not None:
        t = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        hkv = cfg.num_kv_heads or cfg.num_heads
        qkv_ok = (cfg.num_heads % t == 0 and hkv % t == 0)
    return ShardCtx(mesh=mesh, fsdp=par.fsdp,
                    seq_shard_acts=par.seq_shard_acts,
                    cache_layout=par.cache_layout,
                    qkv_heads_shardable=qkv_ok)


# Hardware constants for the roofline (TPU v5e, per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
