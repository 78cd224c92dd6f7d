"""Flash attention Pallas kernel — the framework's compound hot-spot.

The paper lists attention as future work ("compound workloads ... remain
as future work", §VII.D); this framework supplies it because every
assigned architecture's serving/training path is attention- (or SSD-)
dominated.  Built entirely from UISA primitives + native features:

- online-softmax accumulators live in VMEM scratch (managed scratchpad),
- the KV loop is the sequential ('arbitrary') grid dimension with async
  block pipelining (async memory + barrier primitives),
- causal block *skipping* is masked-divergence predication lifted to the
  grid level (a native feature: it exploits dimension_semantics),
- the two matmuls route through the queried MXU tile.

Variants:
- ``native``: block-skip + MXU-aligned blocks + target-native row reduce.
- ``abstract+shuffle``: the online-softmax row-max/row-sum cross-lane
  stages run through the in-register rotate tree (primitive 11,
  ``row_reduce_shuffle``) — zero scratch round-trips.
- ``abstract``: the same stages tree-reduce through *scratchpad
  round-trips* (``scratch_tree_reduce``), no block-skip (mask-only,
  every block visited).

The jnp chunked oracle used by models for CPU dry-runs lives in
models/layers.py; the dense oracle is kernels/ref.py:attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (IsaMode, KernelContract, Primitive, REGISTRY,
                        TARGET, align_up, fold_rows, register_op_space,
                        row_reduce_shuffle, scratch_tree_bytes,
                        scratch_tree_reduce, tree_stages,
                        tuned_attention_blocks, validate_contract)
from repro.core.pipeline import compiler_params
from repro.kernels import ref as _ref

NEG_INF = -1e30  # finite sentinel: keeps exp() NaN-free on fully-masked rows
LANES = TARGET.W
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 256
register_op_space("flash_attention", "attention")


def resolve_blocks(mode: str, sq: int, skv: int, d: int,
                   block_q=None, block_kv=None,
                   plan_dialect: str | None = None):
    """Caller-pinned blocks win; otherwise the autotuner table (the
    ``plan_dialect`` slice; None = ambient policy's dialect), then the
    static defaults.  Shared by the kernel and ``structural_cost`` so the
    modeled block accounting matches the executed tiling."""
    if block_q is None or block_kv is None:
        tuned = tuned_attention_blocks(mode, sq, skv, d,
                                       dialect=plan_dialect)
        tq, tkv = tuned if tuned else (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_KV)
        block_q = tq if block_q is None else block_q
        block_kv = tkv if block_kv is None else block_kv
    return block_q, block_kv

ABSTRACT_CONTRACT = KernelContract(
    kernel="flash_attention", mode=IsaMode.ABSTRACT,
    primitives=frozenset({
        Primitive.LOCKSTEP_GROUP, Primitive.MASKED_DIVERGENCE,
        Primitive.MANAGED_SCRATCHPAD, Primitive.WORKGROUP_BARRIER,
        Primitive.HIERARCHICAL_MEMORY, Primitive.IDENTITY_REGISTERS,
        Primitive.ASYNC_MEMORY, Primitive.REGISTER_OCCUPANCY,
    }))
SHUFFLE_CONTRACT = KernelContract(
    kernel="flash_attention", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=ABSTRACT_CONTRACT.primitives | {Primitive.LANE_SHUFFLE})
NATIVE_CONTRACT = KernelContract(
    kernel="flash_attention", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"mxu_aligned_tiles", "dimension_semantics",
                               "multi_buffering"}))
for _c in (ABSTRACT_CONTRACT, SHUFFLE_CONTRACT, NATIVE_CONTRACT):
    validate_contract(_c)


def _row_reduce(x, op, mode: str, scratch_ref):
    """The cross-lane stage of online softmax, budget-selected.

    x: (bq, bkv) -> (bq, 1).  Native spends the target's fused reduce;
    shuffle spends primitive 11; abstract folds to one vreg then pays
    log2(W) scratchpad round-trips (§VII.C).
    """
    if mode == "native":
        return op.reduce(x)
    if mode == "abstract+shuffle":
        return row_reduce_shuffle(x, op.combine)
    return scratch_tree_reduce(fold_rows(x, op.combine), scratch_ref,
                               op.combine)


class _Max:
    combine = staticmethod(jnp.maximum)
    reduce = staticmethod(
        lambda x: jnp.max(x, axis=-1, keepdims=True))


class _Sum:
    combine = staticmethod(jnp.add)
    reduce = staticmethod(
        lambda x: jnp.sum(x, axis=-1, keepdims=True))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  red_ref, *, scale: float, causal: bool, kv_offset: int,
                  block_q: int, block_kv: int, n_kv: int, mode: str,
                  skip: bool, kv_len: int | None = None, q_axis: int = 2,
                  kv_axis: int = 3, epilogue=None, pos=None,
                  skip_dead: bool = False, k_scale_ref=None,
                  v_scale_ref=None):
    """One online-softmax block program.

    ``kv_len`` is the true (unpadded) kv length: when the sequence was
    padded to a block multiple and the causal mask (which already covers
    the pad for valid rows) is off, the padded zero-keys must be masked
    explicitly or they receive softmax weight.  ``q_axis``/``kv_axis``
    name the grid dimensions carrying the q-block and kv-block indices
    (the fused ``flash_attention_matmul`` lowering reorders the grid so
    heads are sequential).  ``epilogue`` is the hook the fused lowerings
    plug into: called with the finalized ``acc / l`` block *in VMEM*
    instead of the plain ``o_ref`` store — the attention output then
    never exists in HBM (kernels/fused.py).  ``pos`` is the
    decode-shaped mask source: the sequence's int32 scalar (read from an
    SMEM prefetch operand) holding the number of valid cache entries
    minus one — keys at columns ``> pos`` are masked, replacing the static causal triangle with the
    traced per-slot cache frontier (the serve tick's batch mixes
    positions, so the mask cannot be a static kv_offset).

    ``k_scale_ref``/``v_scale_ref`` are the int8-KV dequant hooks: when
    set, k/v blocks arrive as int8 values and the (bkv, 1) per-token
    scale blocks rescale them *in VMEM* — quantized cache pages never
    stage through HBM at f32 width (ISSUE 7).
    """
    qi, ki = pl.program_id(q_axis), pl.program_id(kv_axis)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bkv, d)
        if k_scale_ref is not None:
            k = k * k_scale_ref[0, 0]                    # (bkv, 1) bcast
        if v_scale_ref is not None:
            v = v * v_scale_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        if pos is not None:
            cols = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(cols <= pos, s, NEG_INF)
        elif causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) + kv_offset
            cols = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        elif kv_len is not None and kv_len < n_kv * block_kv:
            # non-causal with a padded kv axis: the causal mask is not
            # there to hide the zero-key pad, so mask it explicitly
            cols = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_cur = jnp.maximum(m_prev, _row_reduce(s, _Max, mode, red_ref))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * corr + _row_reduce(p, _Sum, mode, red_ref)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    if skip_dead and pos is not None:
        # Paged decode: grid-level predication on the *traced* per-slot
        # frontier — table entries whose first logical column lies past
        # ``pos`` are dead (reserved-but-unreached or sentinel) and are
        # skipped entirely, so the kv walk only visits live pages.
        @pl.when(ki * block_kv <= pos)
        def _():
            body()
    elif causal and skip:
        # Native: grid-level predication — skip blocks entirely above the
        # diagonal (first kv column of the block vs last q row).
        first_col = ki * block_kv
        last_row = qi * block_q + block_q - 1 + kv_offset
        @pl.when(first_col <= last_row)
        def _():
            body()
    else:
        body()

    @pl.when(ki == n_kv - 1)
    def _store():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                   # fully-masked rows
        out = acc_ref[...] / l
        if epilogue is None:
            o_ref[0, 0] = out.astype(o_ref.dtype)
        else:
            epilogue(out)


@functools.partial(jax.jit, static_argnames=(
    "causal", "mode", "interpret", "block_q", "block_kv", "kv_offset",
    "plan_dialect"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, kv_offset: int | None = None,
                    mode: str = "native", interpret: bool = True,
                    block_q: int | None = None,
                    block_kv: int | None = None,
                    plan_dialect: str | None = None) -> jax.Array:
    """q: [B,H,Sq,D]; k/v: [B,Hkv,Skv,D] (GQA via index-map head folding).

    ``plan_dialect`` (static) pins which dialect's tuned block table the
    trace binds; None degrades to the ambient policy's dialect."""
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    if kv_offset is None:
        kv_offset = skv - sq
    scale = 1.0 / (d ** 0.5)

    block_q, block_kv = resolve_blocks(mode, sq, skv, d, block_q, block_kv,
                                       plan_dialect)
    block_q = min(block_q, align_up(sq, 128))
    block_kv = min(block_kv, align_up(skv, 128))
    if mode != "native":
        # The abstract/shuffle cross-lane stages fold rows into 128-lane
        # vregs, so their kv block must be a lane multiple.
        block_kv = max(LANES, (block_kv // LANES) * LANES)
    q_p = _pad_seq(q, block_q)
    k_p = _pad_seq(k, block_kv)
    v_p = _pad_seq(v, block_kv)
    sqp, skvp = q_p.shape[2], k_p.shape[2]
    grid = (b, h, sqp // block_q, skvp // block_kv)
    skip = (mode == "native")

    params = compiler_params(
        ("parallel", "parallel", "parallel", "arbitrary")
        if mode == "native" else None)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, kv_offset=kv_offset,
            block_q=block_q, block_kv=block_kv, n_kv=grid[3], mode=mode,
            skip=skip, kv_len=skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, qi, ki, g=group: (bb, hh // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bb, hh, qi, ki, g=group: (bb, hh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q_p.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # m
            pltpu.VMEM((block_q, 1), jnp.float32),      # l
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
            # row-reduce staging: only the abstract budget round-trips
            pltpu.VMEM((block_q, LANES) if mode == "abstract"
                       else (8, LANES), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name=f"uisa_flash_attention_{mode.replace('+', '_')}",
    )(q_p, k_p, v_p)
    return out[:, :, :sq, :]


def _pad_seq(x: jax.Array, block: int) -> jax.Array:
    pad = (-x.shape[2]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def structural_cost(b: int, h: int, sq: int, skv: int, d: int,
                    causal: bool, mode: str,
                    block_q: int | None = None,
                    block_kv: int | None = None,
                    dtype=jnp.float32,
                    plan_dialect: str | None = None) -> dict:
    """Visited-block accounting + the §VII.C scratch-traffic delta.

    Grid-level predication (native block-skip) controls how many blocks
    run; the online-softmax cross-lane stages control what each visited
    block pays: two rowwise reductions (max, sum) per block, each either
    log2(W) scratch round-trips (abstract), log2(W) register shuffles
    (abstract+shuffle), or one native fused reduce.

    ``hbm_bytes`` is the logical stream traffic (read q/k/v once, write o
    once) and is mode-invariant — block revisits are VMEM pipelining the
    visited-block columns account for, and keeping the HBM term equal
    across modes keeps the §VII.C scratch ordering the auto-selection
    tiebreak.  The o write term is what the fused ``flash_attention →
    matmul`` lowering eliminates (kernels/fused.py)."""
    block_q, block_kv = resolve_blocks(mode, sq, skv, d, block_q, block_kv,
                                       plan_dialect)
    nq = -(-sq // block_q)
    nk = -(-skv // block_kv)
    total = nq * nk
    if causal and mode == "native":
        offset = skv - sq
        visited = sum(
            1 for qi in range(nq) for ki in range(nk)
            if ki * block_kv <= qi * block_q + block_q - 1 + offset)
    else:
        visited = total
    flops_per_block = 4 * block_q * block_kv * d
    reduces_per_block = 2                       # row-max + row-sum
    if mode == "abstract":
        round_trips = reduces_per_block * tree_stages(LANES)
        scratch_bytes = (b * h * visited * reduces_per_block *
                         scratch_tree_bytes(LANES, rows=block_q))
        shuffles = 0
    elif mode == "abstract+shuffle":
        round_trips = 0
        scratch_bytes = 0
        shuffles = reduces_per_block * tree_stages(LANES)
    else:                                       # native / library
        round_trips = 0
        scratch_bytes = 0
        shuffles = 0
    itemsize = jnp.dtype(dtype).itemsize
    return {
        "blocks_total": b * h * total,
        "blocks_visited": b * h * visited,
        "flops": b * h * visited * flops_per_block,
        "flops_dense": b * h * total * flops_per_block,
        "skip_fraction": 1.0 - visited / total,
        "hbm_bytes": b * h * d * (2 * sq + 2 * skv) * itemsize,
        "scratch_round_trips_per_block": round_trips,
        "scratch_bytes_total": scratch_bytes,
        "lane_shuffles_per_block": shuffles,
    }


def _library_attention(q, k, v, *, causal: bool = True,
                       kv_offset=None, interpret=None,
                       block_q: int = 256, block_kv: int = 256,
                       plan_dialect: str | None = None):
    """XLA-native reference (the cuBLAS-analogue row of Table V)."""
    # library: XLA decides every staging parameter
    del kv_offset, interpret, block_q, block_kv, plan_dialect
    return _ref.attention(q, k, v, causal=causal)


# Registry: the compound hot-spot carries the full mode matrix.
for _mode, _contract in (("abstract", ABSTRACT_CONTRACT),
                         ("abstract+shuffle", SHUFFLE_CONTRACT),
                         ("native", NATIVE_CONTRACT)):
    REGISTRY.register("flash_attention", _mode,
                      functools.partial(flash_attention, mode=_mode),
                      contract=_contract,
                      cost=functools.partial(structural_cost, mode=_mode))
REGISTRY.register("flash_attention", IsaMode.LIBRARY, _library_attention,
                  cost=functools.partial(structural_cost, mode="library"))
