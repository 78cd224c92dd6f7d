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
and paged decode step.  The default policy's paged tick and page write
are compiled at a served pool's size and searched for pool-sized copies
and for gathers of capacity strips, and so is one period of
granite-4.0-h-micro's hybrid prefill and tick, whose tick is searched
for copies of its SSM state.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and pytest-xdist
workers all import this file.  The persistent compilation cache is off
around these compiles (a cached entry for a described chip cannot be
read back without one).
"""
import dataclasses
import math
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
from repro.serve import BatchedEngine, ServeConfig

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
    """One described v5e chip; the persistent cache off while compiling,
    and the backend probe answering "tpu", as it does on the chip, so
    the model picks the kernels it picks there."""
    was = jax.config.jax_enable_compilation_cache
    probe = ops._backend_probe
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ops._backend_probe = lambda: "tpu"
    yield SingleDeviceSharding(topo.devices[0])
    ops._backend_probe = probe
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


#: a served pool: 32 slots over 400 pages, 12 pages a slot
TICK_SLOTS, TICK_PAGES, TICK_MAXP = 32, 400, 12
#: what moves a pool without computing: by opcode, or in a fusion's name
#: (which XLA takes from the fused root); a dynamic-update-slice moves
#: its update, in place, and is judged by that
_COPYING = ("dynamic-slice", "copy", "transpose")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(%?([\w.\-]+)?(?:, %?([\w.\-]+))?")
#: pool-sized copies in the Pallas path's compiled tick before the XLA
#: tick carried its pools whole (each layer's pool sliced in, relaid out
#: and stacked back, and the stack copied onto the donated input)
PALLAS_POOL_COPIES = {"bf16": 20, "int8": 32}


def pool_copies(text, pools):
    """Names of the instructions in compiled HLO ``text`` that slice,
    stack, copy or transpose an array the size of one of ``pools``
    (``[L, P, ...]`` shapes): the whole pool, the flat ``[L·P, ...]``
    one, or one layer's."""
    sizes = set()
    for shape in pools:
        sizes |= {math.prod(shape), math.prod(shape[1:])}
    instrs = [m.groups() for m in map(_INSTR.match, text.splitlines()) if m]
    size = {name: math.prod(int(d) for d in dims.split(",") if d)
            for name, dims, *_ in instrs}
    found = []
    for name, dims, op, _, update in instrs:
        if op == "dynamic-update-slice":
            moved = size.get(update, 0)
        elif any(w in (name if op == "fusion" else op).replace("_", "-")
                 for w in _COPYING):
            moved = size[name]
        else:
            continue
        if moved in sizes:
            found.append(name)
    return found


def paged_model(chip, pallas: bool, int8: bool):
    """granite-8b at full width, 2 layers, and the shapes of its paged
    tick's operands on the chip: (model, params, tokens, cache)."""
    cfg = dataclasses.replace(GRANITE, num_layers=2)
    extra = dict(isa_mode="auto", use_pallas_attn=True) if pallas else {}
    model = build_model(cfg, ParallelConfig(remat="none",
                                            kv_cache_int8=int8, **extra))
    model = model.with_policy(dataclasses.replace(model.policy,
                                                  interpret=False))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)
    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_paged_cache(
        TICK_SLOTS, TICK_PAGES, PAGE, TICK_MAXP)))
    return model, params, shape(chip, TICK_SLOTS, dtype=jnp.int32), cache


def pool_shapes(cache):
    return [a.shape for k, a in cache.items() if k.endswith("pages")]


def compiled_tick(model, params, tokens, cache):
    return jax.jit(model.decode_step, donate_argnums=2).lower(
        params, tokens, cache).compile().as_text()


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_default_paged_tick_copies_no_pool(chip, int8):
    """The default policy's tick carries token-major pools whole through
    the layer scan: what is left of a pool's traffic is the scatter of
    the new rows and the gather of the slots' strips."""
    model, params, tokens, cache = paged_model(chip, False, int8)
    assert model.token_major_pages
    text = compiled_tick(model, params, tokens, cache)
    assert "scatter" in text
    assert pool_copies(text, pool_shapes(cache)) == []


def strip_gathers(text, slots, max_pages):
    """Names of the gathers in compiled HLO ``text`` whose result is a
    capacity strip, ``[slots, max_pages, page_size, ...]``: every table
    entry of every slot, live or not."""
    lead = f"{slots},{max_pages},{PAGE}"
    return [m.group(1) for m in map(_INSTR.match, text.splitlines())
            if m and m.group(3) == "gather"
            and m.group(2).startswith(lead)]


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_default_paged_tick_reads_pages_in_place(chip, int8):
    """At granite-8b widths over a served pool, the default tick reads
    bf16 pages in place through the paged decode kernel and gathers no
    capacity strip; int8 pools keep the gather-then-dequantize path."""
    model, params, tokens, cache = paged_model(chip, False, int8)
    text = compiled_tick(model, params, tokens, cache)
    found = strip_gathers(text, TICK_SLOTS, TICK_MAXP)
    if int8:
        assert "uisa_paged_decode_attention" not in text
        assert found
    else:
        assert "uisa_paged_decode_attention" in text
        assert found == []


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_default_page_write_copies_no_pool(chip, int8):
    """Admission's page write, pools donated, writes the prompt strip's
    token-major pages in place."""
    model, _, _, cache = paged_model(chip, False, int8)
    engine = BatchedEngine(model, None, ServeConfig(
        batch_slots=1, max_seq_len=PAGE, eos_id=-1, page_size=PAGE,
        num_pages=1))
    pools = {k: a for k, a in cache.items() if k.endswith("pages")}
    strips = {k: shape(chip, a.shape[0], 1, PREFILL, HKV,
                       1 if "scale" in k else HD, dtype=a.dtype)
              for k, a in pools.items()}
    ids = shape(chip, PREFILL // PAGE, dtype=jnp.int32)
    text = jax.jit(engine._write_pages_impl, donate_argnums=0).lower(
        pools, strips, ids).compile().as_text()
    assert pool_copies(text, pool_shapes(cache)) == []


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_pallas_paged_tick_copies_no_more_pools(chip, int8):
    """The Pallas paged kernel keeps head-major pages and the per-layer
    scan; its tick moves no more pools than it did before."""
    model, params, tokens, cache = paged_model(chip, True, int8)
    assert not model.token_major_pages
    text = compiled_tick(model, params, tokens, cache)
    assert "tpu_custom_call" in text
    found = pool_copies(text, pool_shapes(cache))
    assert len(found) <= PALLAS_POOL_COPIES["int8" if int8 else "bf16"], \
        found



#: granite-4.0-h-micro at published widths, one period of its pattern
#: (layers 0-9: Mamba-2 x5, attention, Mamba-2 x4), over a served pool
HYBRID_SLOTS, HYBRID_PAGES, HYBRID_MAXP = 32, 240, 24


def top_level_copies(text, shape):
    """Instructions outside fused computations that copy, transpose or
    slice out an array the size of ``shape`` or of its ``shape[1:]``
    (one layer's): a fusion's own reads and its in-place
    dynamic-update-slice are the traffic the state needs."""
    sizes = {math.prod(shape), math.prod(shape[1:])}
    found, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            fused = line.startswith("%fused")
            continue
        m = _INSTR.match(line)
        if fused or not m:
            continue
        name, dims, op = m.group(1), m.group(2), m.group(3)
        if not any(w in (name if op == "fusion" else op).replace("_", "-")
                   for w in _COPYING):
            continue
        if math.prod(int(d) for d in dims.split(",") if d) in sizes:
            found.append(name)
    return found


@pytest.fixture(scope="module")
def hybrid(chip):
    """(model, params, tokens, paged cache) of one period on the chip."""
    from repro.configs.granite_4p0_h_micro import CONFIG as MICRO
    cfg = dataclasses.replace(MICRO, num_layers=10,
                              layer_types=MICRO.layer_types[:10])
    model = build_model(cfg, ParallelConfig(remat="none"))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)
    params = on_chip(jax.eval_shape(model.init_params,
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_paged_cache(
        HYBRID_SLOTS, HYBRID_PAGES, PAGE, HYBRID_MAXP)))
    return model, params, shape(chip, HYBRID_SLOTS, dtype=jnp.int32), cache


def test_hybrid_prefill_compiles(chip, hybrid):
    """The chunked SSD scan of a 2048-token prompt, 9 Mamba layers and one
    attention layer at published widths."""
    model, params, _, _ = hybrid
    compiled = jax.jit(model.prefill).lower(
        params, {"tokens": shape(chip, 1, PREFILL, dtype=jnp.int32)}
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_hybrid_tick_carries_state_in_place(chip, hybrid):
    """The hybrid tick, donated, carries the SSM state whole: each Mamba
    layer reads its slice inside the fusion that writes it back in place,
    and nothing the size of the state or of one layer's state is copied,
    sliced out or relaid out beside that.  The KV pools of the attention
    layer move no more than the dense model's do."""
    model, params, tokens, cache = hybrid
    assert model.kv_layers == 1 and model.ssm_layers == 9
    text = compiled_tick(model, params, tokens, cache)
    assert "dynamic-update-slice" in text and "scatter" in text
    assert top_level_copies(text, cache["ssm_state"].shape) == []
    assert pool_copies(text, pool_shapes(cache)) == []


def test_hybrid_tick_reads_pages_in_place(chip, hybrid):
    """The hybrid's attention layer reads its pages, heads flattened into
    512-lane rows at a head of 64, through the same kernel, and gathers
    no capacity strip."""
    model, params, tokens, cache = hybrid
    text = compiled_tick(model, params, tokens, cache)
    assert "uisa_paged_decode_attention" in text
    assert strip_gathers(text, HYBRID_SLOTS, HYBRID_MAXP) == []
