"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside JAX, and it compiles for a topology
that is described rather than attached.  What it refuses here (more
scoped VMEM than a kernel may take, a block that breaks the (8, 128)
tiling rule) would otherwise surface only on the chip; interpret mode
accepts both.  So every Pallas kernel the ``"auto"`` served path selects
is compiled here with ``interpret=False`` at the operand shapes granite-8b
hands it (d_model 4096, 32/8 heads, head_dim 128, d_ff 14336, vocab
49152, bf16; 2048-token prefill rows, 16 decode slots, 128-token pages),
together with the native rows, and so are a 2-layer full-width prefill
and paged decode step.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and pytest-xdist
workers all import this file.  The persistent compilation cache is off
around these compiles (a cached entry for a described chip cannot be
read back without one).
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs.granite_8b import CONFIG as GRANITE
from repro.core.registry import REGISTRY, ExecutionPolicy
from repro.kernels import ops
from repro.models import build_model
from repro.models.config import ParallelConfig

D, F, V = GRANITE.d_model, GRANITE.d_ff, GRANITE.vocab_size
H, HKV, HD = GRANITE.num_heads, GRANITE.num_kv_heads, GRANITE.head_dim
QKV = (H + 2 * HKV) * HD
PREFILL, SLOTS, PAGE, PAGES = 2048, 16, 128, 17
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip; the persistent cache off while compiling."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def shape(chip, *dims, dtype=BF16):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)


def compiled_kernels(fn, *args):
    """Compile for the chip; the uisa kernels lowered as Mosaic calls."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return set(re.findall(r"uisa_[a-z0-9_]+", text))


def policy(mode):
    return ExecutionPolicy(mode=mode, interpret=False)


def _rmsnorm_matmul(p):
    return lambda x, w, wp: ops.fused_rmsnorm_matmul(x, w, wp, policy=p)


def _rmsnorm_swiglu(p):
    return lambda x, w, wc: ops.fused_rmsnorm_swiglu(x, w, wc, policy=p)


def _add_rmsnorm(p):
    return lambda x, r, w: ops.fused_add_rmsnorm(x, r, w, policy=p)


#: the norm-prologue hot spots of granite-8b's served path: (kernel name
#: stem, entry point, operand shapes at a row count); the head sees one
#: row per prefill (the last position) and one per decode slot
NORM_CASES = {
    "qkv": ("rmsnorm_matmul", _rmsnorm_matmul,
            lambda rows: ((rows, D), (D,), (D, QKV))),
    "head": ("rmsnorm_matmul", _rmsnorm_matmul,
             lambda rows: ((rows, D), (D,), (D, V))),
    "swiglu": ("rmsnorm_swiglu", _rmsnorm_swiglu,
               lambda rows: ((rows, D), (D,), (D, 2 * F))),
    "add_rmsnorm": ("add_rmsnorm", _add_rmsnorm,
                    lambda rows: ((rows, D), (rows, D), (D,))),
}
NORM_SHAPES = [("qkv", PREFILL), ("qkv", SLOTS), ("head", 1),
               ("head", SLOTS), ("swiglu", PREFILL), ("swiglu", SLOTS),
               ("add_rmsnorm", PREFILL), ("add_rmsnorm", SLOTS)]


@pytest.mark.parametrize("mode", ["auto", "native"])
@pytest.mark.parametrize("case,rows", NORM_SHAPES)
def test_norm_kernels_compile(chip, case, rows, mode):
    stem, build, dims = NORM_CASES[case]
    args = [shape(chip, *d) for d in dims(rows)]
    found = compiled_kernels(build(policy(mode)), *args)
    assert any(k.startswith(f"uisa_{stem}_") for k in found), found


@pytest.mark.parametrize("mode", ["auto", "native"])
def test_flash_attention_matmul_seq_compiles(chip, mode):
    q = shape(chip, 1, H, PREFILL, HD)
    kv = shape(chip, 1, H, PREFILL, HD)
    found = compiled_kernels(
        lambda q, k, v, w: ops.fused_flash_attention_matmul(
            q, k, v, w, block_q=256, block_kv=256, policy=policy(mode)),
        q, kv, kv, shape(chip, H * HD, D))
    assert any(k.startswith("uisa_flash_attention_matmul_")
               for k in found), found


@pytest.mark.parametrize("mode", ["auto", "native"])
def test_paged_attention_matmul_compiles(chip, mode):
    """The decode frontier rides in SMEM: a (1, 1) block of the [B]
    frontier would break the (8, 128) tiling rule at 16 slots."""
    pool = shape(chip, SLOTS * PAGES, HKV, PAGE, HD)
    found = compiled_kernels(
        lambda q, k, v, w, t, pos: ops.fused_flash_attention_matmul(
            q, k, v, w, pos=pos, block_tables=t, policy=policy(mode)),
        shape(chip, SLOTS, H, 1, HD), pool, pool, shape(chip, H * HD, D),
        shape(chip, SLOTS, PAGES, dtype=jnp.int32),
        shape(chip, SLOTS, dtype=jnp.int32))
    assert any(k.startswith("uisa_paged_attention_matmul_")
               for k in found), found


@pytest.fixture(scope="module")
def model_2l(chip):
    """granite-8b at full width, 2 layers, under the Pallas policy."""
    cfg = dataclasses.replace(GRANITE, num_layers=2)
    par = ParallelConfig(remat="none", isa_mode="auto", use_pallas_attn=True)
    model = build_model(cfg, par)
    model = model.with_policy(dataclasses.replace(model.policy,
                                                  interpret=False))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)
    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    return model, params, on_chip


def test_full_width_prefill_compiles_with_kernels(chip, model_2l):
    model, params, _ = model_2l
    n_events = len(REGISTRY.fallback_events)
    found = compiled_kernels(
        model.prefill, params,
        {"tokens": shape(chip, 1, PREFILL, dtype=jnp.int32)})
    assert {"uisa_rmsnorm_matmul_abstract_shuffle",
            "uisa_rmsnorm_swiglu_abstract_shuffle",
            "uisa_flash_attention_matmul_abstract_shuffle"} <= found, found
    assert len(REGISTRY.fallback_events) == n_events


def test_full_width_paged_decode_compiles_with_kernels(chip, model_2l):
    model, params, on_chip = model_2l
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(SLOTS, SLOTS * PAGES, PAGE, PAGES)))
    found = compiled_kernels(model.decode_step, params,
                             shape(chip, SLOTS, dtype=jnp.int32), cache)
    assert {"uisa_rmsnorm_matmul_abstract_shuffle",
            "uisa_rmsnorm_swiglu_abstract_shuffle",
            "uisa_paged_attention_matmul_abstract_shuffle"} <= found, found
