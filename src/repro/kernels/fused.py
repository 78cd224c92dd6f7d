"""Fused-epilogue lowerings — eliminating the inter-kernel HBM round trip.

The paper's §VII.C lesson generalized one level up: traffic to the
nearest memory that *could* have stayed on-chip is the quantity that
decides performance.  Between two kernels, that traffic is the activation
staged to HBM by the producer and immediately read back by the consumer —
one full ``hbm_bytes`` round trip per transformer sublayer that no
per-kernel optimization can remove.  These are the first registrations
where HBM traffic is the *treatment*, not the control:

- :func:`rmsnorm_matmul` — the norm is computed as a GEMM prologue: each
  row block is normalized in VMEM and consumed directly by the MXU
  contraction, so the normalized activation is **never materialized to
  HBM**.  Its ``structural_cost.hbm_bytes`` is the unfused
  ``rmsnorm + gemm`` sum minus exactly one activation round trip
  (``2 · rows · d · itemsize``: the write plus the read-back).
- :func:`add_rmsnorm` — the residual add is fused into the norm's load
  stage: the kernel reads the two addends directly and emits both the
  summed residual (the stream the next sublayer needs) and its norm.
  The *read-back* leg of the staging round trip disappears
  (``rows · d · itemsize``); the write survives because the residual
  stream owns the sum — the cost model says so honestly rather than
  claiming the full round trip.
- :func:`flash_attention_matmul` — the post-attention ``wo`` projection
  consumed from the online-softmax accumulator in VMEM
  (kernels/attention.py's epilogue hook).  The flash grid is reordered so
  heads run on a *sequential* axis and every head's ``(acc / l) @ wo_h``
  contribution accumulates into one shared ``[bq, N]`` output block — the
  ``[B, S, H, D]`` attention output never exists in HBM (write + read-back
  = ``2 · B·S·H·D · itemsize``, the largest single round trip in a
  transformer sublayer).
- :func:`rmsnorm_swiglu` — ln2 → ``wi``/``wg`` as one fused call against
  the concatenated ``[wi|wg]`` weight, with the silu gate applied in the
  epilogue: the normalized activation feeds both projections from VMEM
  (same ``2 · rows · d · itemsize`` saving as :func:`rmsnorm_matmul`; the
  ``hi``/``hg`` products additionally never stage — claimed conservatively,
  the pinned delta stays exactly one activation round trip).

Both ops carry the full Table V mode matrix.  The fused *program
structure* (two abstract ops realized by one kernel) is a lowering
decision available to every budget; within the kernel each mode spends
only its own cross-lane budget — the abstract variant still pays the
scratch-tree round-trips for the moment reduction and the moment
re-stage (the universal budget carries no fusion guarantee *inside* the
kernel either), while only ``native`` claims the target's
``fused_epilogue`` feature.  The ``library`` row is the **unfused jnp
pair** — simultaneously the numerical reference and the declared
fallback target when no fused lowering is legal (never a silent rewrite).

Tile shapes come from the shared GEMM resolver
(``repro.kernels.gemm.block_shape_for``, autotuner-aware) so the modeled
traffic and the executed tiling cannot drift apart, and the row plan of
``add_rmsnorm`` consults the tuning table like every other rowwise
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (IsaMode, KernelContract, Primitive, REGISTRY,
                        TARGET, align_up, register_op_space,
                        scratch_tree_bytes, tree_stages, tuned_plan,
                        validate_contract)
from repro.core.pipeline import compiler_params
from repro.core.tuning import (attention_matmul_bucket, swiglu_bucket,
                               tuned_entry)
from repro.kernels import attention as _attention
from repro.kernels import gemm as _gemm
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rmsnorm

LANES = TARGET.W
_MAX_BLOCK_ROWS = 64          # add_rmsnorm latency cap (mirrors rmsnorm)
register_op_space("add_rmsnorm", "rowwise", max_block_rows=_MAX_BLOCK_ROWS)
# rmsnorm_matmul's tile IS a GEMM tile: it shares the "gemm" tuning space
# (one table row tunes both, so no separate op space); the two ops below
# have genuinely different working sets and get their own Eq. 1 grids.
register_op_space("rmsnorm_swiglu", "swiglu")
register_op_space("flash_attention_matmul", "attention_matmul")
# The quantized twins tune separately: their weight tiles are int8, so a
# given scratchpad budget admits larger (or deeper-buffered) tiles — the
# tuner must be allowed to find that.  rmsnorm_matmul_q8 rides gemm's
# space exactly like its f32 twin.
register_op_space("rmsnorm_swiglu_q8", "swiglu")
register_op_space("flash_attention_matmul_q8", "attention_matmul")

#: every fused multi-op lowering this module registers — the sweep target
#: for validate_contracts' cost-accounting gate and the property tests.
#: Extended with QUANT_OPS at the bottom of this module once the quantized
#: twins are registered.
FUSED_OPS = ("add_rmsnorm", "flash_attention_matmul", "rmsnorm_matmul",
             "rmsnorm_swiglu")

#: the int8 dialect variants (ISSUE 7): same fused program structure, but
#: the weight prologue loads int8 blocks + per-channel f32 scales and
#: dequantizes in VMEM — quantized weights never stage through HBM at f32
#: width.  ``REGISTRY.select`` retargets the f32 op onto its twin when the
#: policy carries ``precision="int8"``.
QUANT_OPS = ("flash_attention_matmul_q8", "rmsnorm_matmul_q8",
             "rmsnorm_swiglu_q8")


def quantize_weight(w: jax.Array):
    """Per-output-channel symmetric int8 quantization of a weight matrix.

    ``w``: [..., K, N] — the scale reduces over the contraction axis
    (axis -2), one f32 scale per output channel: [..., N].  The channel
    max maps to exactly ±127, so ``dequantize_weight`` round-trips the
    extreme value losslessly.
    """
    m = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
    scale = jnp.maximum(m / 127.0, 1e-8).astype(jnp.float32)
    q = jnp.clip(jnp.round(w.astype(jnp.float32)
                           / jnp.expand_dims(scale, -2)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_weight(q: jax.Array, scale: jax.Array,
                      dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_weight` (the library rows' prologue)."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scale, -2)).astype(dtype)


def _weight_stream(m: int, n: int, k: int, mode: str, dtype,
                   plan_dialect: str | None):
    """The B-matrix leg of the composed GEMM's hbm stream.

    Mirrors ``gemm.structural_cost`` exactly (same resolver, same library
    constant, same re-read count) so a quantized cost can substitute its
    own weight stream into the composed sum without breaking the
    ``hbm == unfused - saved`` identity validate_contracts pins."""
    itemsize = jnp.dtype(dtype).itemsize
    if mode == "library":
        bm = 512
    else:
        bm, _, _ = _gemm.block_shape_for(mode, m, n, k, dtype, plan_dialect)
    rereads = max(1, -(-m // bm))
    return k * n * itemsize * rereads, rereads


def _q8_weight_stream(rereads: int, k: int, n: int) -> int:
    """int8 weight elements + the f32 per-channel scale row, re-fetched
    once per row-block sweep like the f32 weight tile they replace."""
    return (k * n * 1 + n * 4) * rereads


def _padded_d(d: int, mode: str) -> int:
    """The feature width a norm-prologue kernel stages: the portable
    budgets pad it to the lanes, native takes it as is."""
    return d if mode == "native" else align_up(d, LANES)


def _fit_tile(tile: int, dim: int) -> int:
    """The largest lane multiple ``<= tile`` that divides ``dim`` rounded
    up to the lanes: a dimension is never padded past its lane alignment
    (a 1152 tile would pad 6144 columns to 6912)."""
    units = align_up(dim, LANES) // LANES
    t = max(1, min(tile, units * LANES) // LANES)
    while units % t:
        t -= 1
    return t * LANES


def row_kernel_tiles(bm: int, bn: int, rows: int, d: int, n: int,
                     itemsize: int, n_weights: int = 1):
    """``(bm, bn, vmem_bytes)`` for the norm-prologue kernels.

    Their feature row stays whole (the moment needs it), so a step holds
    a ``(bm, d)`` row block, ``n_weights`` ``(d, bn)`` weight tiles and a
    ``(bm, bn)`` output block.  The resolved tile is fitted to the problem
    (:func:`_fit_tile`), then halved along its longer edge until one
    stage keeps the Eq. 1 double-buffered occupancy of 2 on the chip's
    VMEM — the same invariant the tuner enforces, and what leaves room
    for the kernel's f32 temporaries.  ``vmem_bytes`` is the resident
    pipeline (two buffers per operand) the compiler must admit."""
    bm, bn = _fit_tile(bm, rows), _fit_tile(bn, n)

    def stage(bm, bn):
        return (bm * d + d + n_weights * d * bn + bm * bn) * itemsize

    while (TARGET.buffer_occupancy(stage(bm, bn), 2) < 2
           and max(bm, bn) > LANES):
        if bn >= bm:
            bn = _fit_tile(bn // 2, n)
        else:
            bm = _fit_tile(bm // 2, rows)
    return bm, bn, 2 * stage(bm, bn)

# --------------------------------------------------------------------------
# Contracts: the fused ops spend the union of their constituents' budgets.
# --------------------------------------------------------------------------

_RM_ABSTRACT = KernelContract(
    kernel="rmsnorm_matmul", mode=IsaMode.ABSTRACT,
    primitives=frozenset({
        Primitive.LOCKSTEP_GROUP, Primitive.MANAGED_SCRATCHPAD,
        Primitive.WORKGROUP_BARRIER, Primitive.HIERARCHICAL_MEMORY,
        Primitive.IDENTITY_REGISTERS, Primitive.ASYNC_MEMORY,
        Primitive.REGISTER_OCCUPANCY,
    }))
_RM_SHUFFLE = KernelContract(
    kernel="rmsnorm_matmul", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_RM_ABSTRACT.primitives | {Primitive.LANE_SHUFFLE})
_RM_NATIVE = KernelContract(
    kernel="rmsnorm_matmul", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"fused_epilogue", "mxu_aligned_tiles",
                               "dimension_semantics", "multi_buffering"}))

_AR_ABSTRACT = KernelContract(
    kernel="add_rmsnorm", mode=IsaMode.ABSTRACT,
    primitives=frozenset({
        Primitive.LOCKSTEP_GROUP, Primitive.MANAGED_SCRATCHPAD,
        Primitive.WORKGROUP_BARRIER, Primitive.HIERARCHICAL_MEMORY,
        Primitive.IDENTITY_REGISTERS, Primitive.ASYNC_MEMORY,
    }))
_AR_SHUFFLE = KernelContract(
    kernel="add_rmsnorm", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_AR_ABSTRACT.primitives | {Primitive.LANE_SHUFFLE})
_AR_NATIVE = KernelContract(
    kernel="add_rmsnorm", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"fused_epilogue", "dimension_semantics",
                               "multi_buffering"}))

# flash_attention_matmul spends attention's budget (the epilogue is an MMA
# on data already resident); rmsnorm_swiglu spends rmsnorm_matmul's.
_FA_ABSTRACT = KernelContract(
    kernel="flash_attention_matmul", mode=IsaMode.ABSTRACT,
    primitives=_attention.ABSTRACT_CONTRACT.primitives)
_FA_SHUFFLE = KernelContract(
    kernel="flash_attention_matmul", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_FA_ABSTRACT.primitives | {Primitive.LANE_SHUFFLE})
_FA_NATIVE = KernelContract(
    kernel="flash_attention_matmul", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"fused_epilogue", "mxu_aligned_tiles",
                               "dimension_semantics", "multi_buffering"}))

_SW_ABSTRACT = KernelContract(
    kernel="rmsnorm_swiglu", mode=IsaMode.ABSTRACT,
    primitives=_RM_ABSTRACT.primitives)
_SW_SHUFFLE = KernelContract(
    kernel="rmsnorm_swiglu", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_SW_ABSTRACT.primitives | {Primitive.LANE_SHUFFLE})
_SW_NATIVE = KernelContract(
    kernel="rmsnorm_swiglu", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"fused_epilogue", "mxu_aligned_tiles",
                               "dimension_semantics", "multi_buffering"}))

# The quantized twins spend the identical primitive budgets: dequantize
# is an elementwise multiply on a block already resident in VMEM — no new
# cross-lane or native capability is consumed, only the *operand dtype*
# of the prologue load changes.  (Same contract discipline, new kernel
# names: contract.kernel must match the registered op.)
_RMQ_ABSTRACT = KernelContract(
    kernel="rmsnorm_matmul_q8", mode=IsaMode.ABSTRACT,
    primitives=_RM_ABSTRACT.primitives)
_RMQ_SHUFFLE = KernelContract(
    kernel="rmsnorm_matmul_q8", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_RM_SHUFFLE.primitives)
_RMQ_NATIVE = KernelContract(
    kernel="rmsnorm_matmul_q8", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=_RM_NATIVE.native_features)

_FAQ_ABSTRACT = KernelContract(
    kernel="flash_attention_matmul_q8", mode=IsaMode.ABSTRACT,
    primitives=_FA_ABSTRACT.primitives)
_FAQ_SHUFFLE = KernelContract(
    kernel="flash_attention_matmul_q8", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_FA_SHUFFLE.primitives)
_FAQ_NATIVE = KernelContract(
    kernel="flash_attention_matmul_q8", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=_FA_NATIVE.native_features)

_SWQ_ABSTRACT = KernelContract(
    kernel="rmsnorm_swiglu_q8", mode=IsaMode.ABSTRACT,
    primitives=_SW_ABSTRACT.primitives)
_SWQ_SHUFFLE = KernelContract(
    kernel="rmsnorm_swiglu_q8", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_SW_SHUFFLE.primitives)
_SWQ_NATIVE = KernelContract(
    kernel="rmsnorm_swiglu_q8", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=_SW_NATIVE.native_features)

for _c in (_RM_ABSTRACT, _RM_SHUFFLE, _RM_NATIVE,
           _AR_ABSTRACT, _AR_SHUFFLE, _AR_NATIVE,
           _FA_ABSTRACT, _FA_SHUFFLE, _FA_NATIVE,
           _SW_ABSTRACT, _SW_SHUFFLE, _SW_NATIVE,
           _RMQ_ABSTRACT, _RMQ_SHUFFLE, _RMQ_NATIVE,
           _FAQ_ABSTRACT, _FAQ_SHUFFLE, _FAQ_NATIVE,
           _SWQ_ABSTRACT, _SWQ_SHUFFLE, _SWQ_NATIVE):
    validate_contract(_c)


# --------------------------------------------------------------------------
# rmsnorm @ w_proj: the norm as a GEMM prologue
# --------------------------------------------------------------------------


def _rmsnorm_matmul_kernel(*refs, eps: float, mode: str, d_true: int,
                           quant: bool = False):
    if quant:
        x_ref, w_ref, p_ref, s_ref, o_ref, scratch_ref = refs
    else:
        x_ref, w_ref, p_ref, o_ref, scratch_ref = refs
        s_ref = None
    x = x_ref[...].astype(jnp.float32)                    # (bm, d)
    w = w_ref[...].astype(jnp.float32)                    # (1, d)
    # one shared source for the per-mode moment discipline (rmsnorm.py)
    y = _rmsnorm.normalize_block(x, w, scratch_ref, eps=eps, mode=mode,
                                 d_true=d_true)
    p = p_ref[...]                                        # (d, bn)
    if s_ref is not None:
        # the quantized prologue: the weight block arrives int8 and its
        # (1, bn) per-channel scales rescale it HERE, in VMEM — the f32
        # weight never exists in HBM (ISSUE 7).
        p = p.astype(jnp.float32) * s_ref[...]
    # the epilogue: the normalized block goes straight into the MXU
    # contraction from VMEM — it never exists in HBM.  Both operands
    # enter at the weight's width (the unfused pair's einsum does the
    # same), so a bf16 weight tile is never widened to an f32 copy.
    o_ref[...] = jax.lax.dot_general(
        y.astype(p.dtype), p, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "interpret",
                                             "plan_dialect"))
def rmsnorm_matmul(x: jax.Array, weight: jax.Array, w_proj: jax.Array, *,
                   w_scale: jax.Array | None = None, eps: float = 1e-6,
                   mode: str = "native", interpret: bool = True,
                   plan_dialect: str | None = None) -> jax.Array:
    """``rmsnorm(x, weight) @ w_proj`` in one kernel.

    x: [..., D]; weight: [D]; w_proj: [D, N] -> [..., N] (x.dtype, f32
    accumulation).  Tiled over (row blocks × N blocks) with the shared
    GEMM tile resolver; the full feature row stays resident per block
    (the moment needs the whole row), so D is not tiled.

    ``w_scale`` ([N] f32, with ``w_proj`` int8) selects the quantized
    prologue: the weight block is dequantized per-channel in VMEM
    (the ``rmsnorm_matmul_q8`` registry rows; the library row
    dequantizes up front and runs the unfused pair).
    """
    if mode == "library":
        if w_scale is not None:
            w_proj = dequantize_weight(w_proj, w_scale, x.dtype)
        y = _ref.rmsnorm(x, weight, eps)
        return jnp.einsum("...d,dn->...n", y, w_proj.astype(y.dtype))
    *lead, d = x.shape
    n = w_proj.shape[1]
    assert w_proj.shape[0] == d, (x.shape, w_proj.shape)
    rows = 1
    for s in lead:
        rows *= s
    x2d = x.reshape(rows, d)
    w2d = weight.reshape(1, d)
    p2d = w_proj
    s2d = None if w_scale is None else w_scale.reshape(1, n)

    d_padded = d
    if mode != "native":
        pad_d = (-d) % LANES
        if pad_d:
            d_padded = d + pad_d
            x2d = jnp.pad(x2d, ((0, 0), (0, pad_d)))
            w2d = jnp.pad(w2d, ((0, 0), (0, pad_d)))
            p2d = jnp.pad(p2d, ((0, pad_d), (0, 0)))

    bm, bn, _ = _gemm.block_shape_for(mode, rows, n, d, x.dtype,
                                      plan_dialect)
    bm, bn, vmem = row_kernel_tiles(bm, bn, rows, d_padded, n,
                                    jnp.dtype(x.dtype).itemsize)
    pad_m = (-rows) % bm
    pad_n = (-n) % bn
    if pad_m:
        x2d = jnp.pad(x2d, ((0, pad_m), (0, 0)))
    if pad_n:
        p2d = jnp.pad(p2d, ((0, 0), (0, pad_n)))
        if s2d is not None:
            s2d = jnp.pad(s2d, ((0, 0), (0, pad_n)))
    mp, np_ = rows + pad_m, n + pad_n
    grid = (mp // bm, np_ // bn)
    params = compiler_params(
        ("parallel", "parallel") if mode == "native" else None, vmem)

    in_specs = [
        pl.BlockSpec((bm, d_padded), lambda i, j: (i, 0)),
        pl.BlockSpec((1, d_padded), lambda i, j: (0, 0)),
        pl.BlockSpec((d_padded, bn), lambda i, j: (0, j)),
    ]
    operands = [x2d, w2d, p2d]
    if s2d is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        operands.append(s2d)
    kernel_name = ("uisa_rmsnorm_matmul_q8_" if s2d is not None
                   else "uisa_rmsnorm_matmul_") + mode.replace('+', '_')

    out = pl.pallas_call(
        functools.partial(_rmsnorm_matmul_kernel, eps=eps, mode=mode,
                          d_true=d, quant=s2d is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        # only the abstract moment tree stages through scratch
        scratch_shapes=[pltpu.VMEM(
            (bm, LANES) if mode == "abstract" else (8, LANES),
            jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=kernel_name,
    )(*operands)
    return out[:rows, :n].reshape(*lead, n)


def structural_cost_rmsnorm_matmul(rows: int, d: int, n: int, mode: str,
                                   dtype=jnp.float32,
                                   plan_dialect: str | None = None) -> dict:
    """The unfused pair's traffic minus exactly one activation round trip.

    Composes the registered ``gemm`` and ``rmsnorm`` cost models (same
    shapes, same mode, same autotuned tiles), then removes the write and
    read-back of the normalized activation — the two legs of the
    inter-kernel staging this lowering eliminates.  ``library`` is the
    unfused pair itself: full sum, nothing saved.
    """
    itemsize = jnp.dtype(dtype).itemsize
    g = _gemm.structural_cost(m=rows, n=n, k=d, mode=mode, dtype=dtype,
                              plan_dialect=plan_dialect)
    r = _rmsnorm.structural_cost(rows=rows, d=d, mode=mode, dtype=dtype,
                                 plan_dialect=plan_dialect)
    unfused = g["hbm_bytes"] + r["hbm_bytes"]
    saved = 0 if mode == "library" else 2 * rows * d * itemsize
    if mode == "library":
        bm = bn = 512
    else:
        # the kernel's own fitted tiles, so block/steps/scratch report the
        # executed tiling; the hbm terms keep the GEMM model's tile (a
        # VMEM-halved tile re-reads the weight more than they count)
        bm, bn, _ = _gemm.block_shape_for(mode, rows, n, d, dtype,
                                          plan_dialect)
        bm, bn, _ = row_kernel_tiles(bm, bn, rows, _padded_d(d, mode), n,
                                     itemsize)
    steps = -(-rows // bm) * -(-n // bn)
    if mode == "abstract":
        round_trips = tree_stages(LANES) + 1   # tree + moment re-stage
        scratch_bytes = steps * (scratch_tree_bytes(LANES, rows=bm)
                                 + 3 * bm * 4)
    else:
        round_trips = 0
        scratch_bytes = 0
    ws, _ = _weight_stream(rows, n, d, mode, dtype, plan_dialect)
    return {
        "hbm_bytes": unfused - saved,
        "hbm_bytes_unfused_pair": unfused,
        "hbm_bytes_saved": saved,
        "weight_stream_bytes": ws,
        "flops": g["flops"],
        "block": (bm, bn),
        "blocks": steps,
        "scratch_round_trips_per_block": round_trips,
        "scratch_bytes_total": scratch_bytes,
        "lane_shuffles_per_block": tree_stages(LANES)
        if mode == "abstract+shuffle" else 0,
        "fused_epilogue": mode != "library",
    }


def structural_cost_rmsnorm_matmul_q8(rows: int, d: int, n: int, mode: str,
                                      dtype=jnp.float32,
                                      plan_dialect: str | None = None
                                      ) -> dict:
    """The f32 cost with the weight stream swapped to int8 + scales.

    Same composed sum, same identities (``hbm == unfused - saved``, the
    saving unchanged — the fusion still removes exactly one activation
    round trip); only the B-matrix leg of the GEMM term shrinks from f32
    width to int8 + one f32 scale row per re-read.  The library row is
    the dequantize-then-unfused-pair reference and carries the same
    substitution (XLA fuses the dequant into the consumer's read)."""
    base = structural_cost_rmsnorm_matmul(rows, d, n, mode, dtype,
                                          plan_dialect)
    ws_f32, rereads = _weight_stream(rows, n, d, mode, dtype, plan_dialect)
    ws_q8 = _q8_weight_stream(rereads, d, n)
    delta = ws_f32 - ws_q8
    base.update(
        hbm_bytes=base["hbm_bytes"] - delta,
        hbm_bytes_unfused_pair=base["hbm_bytes_unfused_pair"] - delta,
        weight_stream_bytes=ws_q8,
        weight_stream_bytes_f32=ws_f32,
        weight_precision="int8",
    )
    return base


# --------------------------------------------------------------------------
# (x + residual) -> rmsnorm: the add fused into the norm's load stage
# --------------------------------------------------------------------------


def _add_rmsnorm_kernel(x_ref, r_ref, w_ref, o_ref, s_ref, scratch_ref, *,
                        eps: float, mode: str, d_true: int):
    # the load stage IS the residual add: both addends arrive in VMEM and
    # the staged sum is never read back from HBM by the norm.
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    s_ref[...] = s.astype(s_ref.dtype)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = _rmsnorm.normalize_block(
        s, w, scratch_ref, eps=eps, mode=mode,
        d_true=d_true).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "interpret",
                                             "plan_dialect"))
def add_rmsnorm(x: jax.Array, residual: jax.Array, weight: jax.Array, *,
                eps: float = 1e-6, mode: str = "native",
                interpret: bool = True, plan_dialect: str | None = None):
    """``(rmsnorm(x + residual, weight), x + residual)`` in one kernel.

    Returns the norm *and* the summed residual stream (both [..., D],
    x.dtype) — the residual→norm hot pair of every transformer sublayer.
    """
    assert x.shape == residual.shape, (x.shape, residual.shape)
    if mode == "library":
        s = x + residual
        return _ref.rmsnorm(s, weight, eps), s
    *lead, d = x.shape
    rows = 1
    for sdim in lead:
        rows *= sdim
    x2d = x.reshape(rows, d)
    r2d = residual.reshape(rows, d)
    w2d = weight.reshape(1, d)
    d_padded = d
    if mode != "native":
        pad_d = (-d) % LANES
        if pad_d:
            d_padded = d + pad_d
            x2d = jnp.pad(x2d, ((0, 0), (0, pad_d)))
            r2d = jnp.pad(r2d, ((0, 0), (0, pad_d)))
            w2d = jnp.pad(w2d, ((0, 0), (0, pad_d)))

    itemsize = jnp.dtype(x.dtype).itemsize
    plan = tuned_plan("add_rmsnorm", rows, 2 * d_padded * itemsize,
                      mode=mode, dialect=plan_dialect,
                      max_block_rows=_MAX_BLOCK_ROWS,
                      semantics=("parallel",))
    block = plan.block_rows
    pad = plan.padded_rows - rows
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
        r2d = jnp.pad(r2d, ((0, pad), (0, 0)))

    normed, summed = pl.pallas_call(
        functools.partial(_add_rmsnorm_kernel, eps=eps, mode=mode,
                          d_true=d),
        grid=plan.grid,
        in_specs=[
            pl.BlockSpec((block, d_padded), lambda i: (i, 0)),
            pl.BlockSpec((block, d_padded), lambda i: (i, 0)),
            pl.BlockSpec((1, d_padded), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, d_padded), lambda i: (i, 0)),
            pl.BlockSpec((block, d_padded), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x.dtype),
            jax.ShapeDtypeStruct(x2d.shape, x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM(
            (block, LANES) if mode == "abstract" else (8, LANES),
            jnp.float32)],
        compiler_params=plan.compiler_params,
        interpret=interpret,
        name=f"uisa_add_rmsnorm_{mode.replace('+', '_')}",
    )(x2d, r2d, w2d)
    normed = normed[:rows, :d].reshape(x.shape)
    summed = summed[:rows, :d].reshape(x.shape)
    return normed, summed


def structural_cost_add_rmsnorm(rows: int, d: int, mode: str,
                                dtype=jnp.float32,
                                plan_dialect: str | None = None) -> dict:
    """The read-back leg of the staging round trip, eliminated.

    Unfused pair = elementwise add (read x, read residual, write sum) +
    registered rmsnorm (read sum, read weight, write norm): five
    activation-sized HBM terms.  Fused = read x, read residual, write sum,
    write norm: four.  The surviving write is the residual stream's own
    output, so the honest saving is ``rows·d·itemsize`` — one leg, not
    the full round trip (cf. ``rmsnorm_matmul``, where the activation
    vanishes from HBM entirely).
    """
    itemsize = jnp.dtype(dtype).itemsize
    r = _rmsnorm.structural_cost(rows=rows, d=d, mode=mode, dtype=dtype,
                                 plan_dialect=plan_dialect)
    unfused = 3 * rows * d * itemsize + r["hbm_bytes"]
    saved = 0 if mode == "library" else rows * d * itemsize
    d_padded = d if mode == "native" else d + ((-d) % LANES)
    plan = tuned_plan("add_rmsnorm", rows, 2 * d_padded * itemsize,
                      mode=mode if mode != "library" else "native",
                      dialect=plan_dialect,
                      max_block_rows=_MAX_BLOCK_ROWS,
                      semantics=("parallel",))
    blocks = plan.grid[0]
    if mode == "abstract":
        round_trips = tree_stages(LANES) + 1   # tree + moment re-stage
        scratch_bytes = blocks * (
            scratch_tree_bytes(LANES, rows=plan.block_rows)
            + 3 * plan.block_rows * 4)
    else:
        round_trips = 0
        scratch_bytes = 0
    return {
        "hbm_bytes": unfused - saved,
        "hbm_bytes_unfused_pair": unfused,
        "hbm_bytes_saved": saved,
        "blocks": blocks,
        "block_rows": plan.block_rows,
        "pipeline_occupancy": plan.occupancy,
        "scratch_round_trips_per_block": round_trips,
        "scratch_bytes_total": scratch_bytes,
        "lane_shuffles_per_block": tree_stages(LANES)
        if mode == "abstract+shuffle" else 0,
        "fused_epilogue": mode != "library",
    }


# --------------------------------------------------------------------------
# flash_attention -> wo: the output projection consumed from the
# online-softmax accumulator (the epilogue hook in kernels/attention.py)
# --------------------------------------------------------------------------


def resolve_attention_matmul_blocks(mode: str, sq: int, skv: int, d: int,
                                    n: int, block_q=None, block_kv=None,
                                    plan_dialect: str | None = None,
                                    op: str = "flash_attention_matmul"):
    """Caller-pinned blocks win; then this op's own tuned entry (its
    working set includes the wo slice and the shared output block, so it
    tunes separately from bare flash); then the flash resolution.  Shared
    by the kernel and ``structural_cost`` — modeled == executed.
    ``plan_dialect`` names the table slice consulted; ``op`` names the
    table *row* — the quantized twin consults its own tuned slice."""
    if block_q is None or block_kv is None:
        entry = tuned_entry(op, mode,
                            attention_matmul_bucket(sq, skv, d, n),
                            dialect=plan_dialect)
        if entry and "block_q" in entry and "block_kv" in entry:
            tq, tkv = int(entry["block_q"]), int(entry["block_kv"])
        else:
            tq, tkv = _attention.resolve_blocks(
                mode, sq, skv, d, plan_dialect=plan_dialect)
        block_q = tq if block_q is None else block_q
        block_kv = tkv if block_kv is None else block_kv
    block_q = min(block_q, align_up(sq, 128))
    block_kv = min(block_kv, align_up(skv, 128))
    if mode != "native":
        # abstract/shuffle row reduces fold into 128-lane vregs
        block_kv = max(LANES, (block_kv // LANES) * LANES)
    return block_q, block_kv


def _flash_matmul_kernel(*refs, scale: float,
                         causal: bool, kv_offset: int, block_q: int,
                         block_kv: int, n_kv: int, n_heads: int,
                         kv_len: int | None, mode: str,
                         has_pos: bool = False, paged: bool = False,
                         quant_w: bool = False, quant_kv: bool = False):
    # Operand order (optional members gated by the static flags):
    #   [tbl,] [pos,] q, k, [k_scale,] v, [v_scale,] w, [w_scale,]
    # tbl and pos are scalar-prefetch operands in SMEM.  paged: the block
    # table is consumed entirely by the kv index maps (the gather); the
    # per-slot frontier pos[b] masks the keys.  quant_kv: the kv blocks
    # arrive int8 with (block_kv, 1) per-token scales; quant_w: the wo
    # slice arrives int8 with a (1, n) per-channel scale row.  All
    # dequantization happens in VMEM, on blocks already resident.
    refs = list(refs)
    if paged:
        refs.pop(0)                               # block table (index maps)
    pos_ref = refs.pop(0) if (paged or has_pos) else None
    q_ref = refs.pop(0)
    k_ref = refs.pop(0)
    k_scale_ref = refs.pop(0) if quant_kv else None
    v_ref = refs.pop(0)
    v_scale_ref = refs.pop(0) if quant_kv else None
    w_ref = refs.pop(0)
    ws_ref = refs.pop(0) if quant_w else None
    o_ref, m_ref, l_ref, acc_ref, red_ref, oacc_ref = refs
    hh = pl.program_id(2)

    def epilogue(out):
        # the hook: (acc / l) goes straight into the head's wo slice from
        # VMEM; heads run sequentially and accumulate into one shared f32
        # scratch (a single output-dtype cast at the last head — the same
        # accumulation discipline as the unfused einsum), so the
        # attention output never exists in HBM.
        w = w_ref[0].astype(jnp.float32)
        if ws_ref is not None:
            w = w * ws_ref[...]                   # (1, n) channel scales
        contrib = jax.lax.dot_general(
            out, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(hh == 0)
        def _first_head():
            oacc_ref[...] = contrib

        @pl.when(hh != 0)
        def _accumulate():
            oacc_ref[...] += contrib

        @pl.when(hh == n_heads - 1)
        def _store_block():
            o_ref[0] = oacc_ref[...].astype(o_ref.dtype)

    _attention._flash_kernel(
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, red_ref,
        scale=scale, causal=causal, kv_offset=kv_offset, block_q=block_q,
        block_kv=block_kv, n_kv=n_kv, mode=mode,
        skip=(mode == "native" and causal), kv_len=kv_len, q_axis=1,
        kv_axis=3, epilogue=epilogue,
        pos=None if pos_ref is None else pos_ref[pl.program_id(0)],
        skip_dead=paged,
        k_scale_ref=k_scale_ref, v_scale_ref=v_scale_ref)


@functools.partial(jax.jit, static_argnames=(
    "causal", "mode", "interpret", "block_q", "block_kv", "kv_offset",
    "plan_dialect", "tuning_op"))
def flash_attention_matmul(q: jax.Array, k: jax.Array, v: jax.Array,
                           w_out: jax.Array, *, causal: bool = True,
                           kv_offset: int | None = None,
                           mode: str = "native", interpret: bool = True,
                           block_q: int | None = None,
                           block_kv: int | None = None,
                           pos: jax.Array | None = None,
                           block_tables: jax.Array | None = None,
                           w_scale: jax.Array | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           plan_dialect: str | None = None,
                           tuning_op: str = "flash_attention_matmul"
                           ) -> jax.Array:
    """``flash_attention(q, k, v)`` -> ``wo`` projection in one kernel.

    q: [B,H,Sq,D]; k/v: [B,Hkv,Skv,D]; w_out: [H·D, N] -> [B,Sq,N].
    The grid is ``(batch, q-block, head, kv-block)`` with the head axis
    *sequential*: each head finishes its online softmax, projects the
    accumulator through its wo slice, and adds into a shared f32 VMEM
    accumulator (cast to the output dtype once, at the last head) — the
    `[B,S,H,D]` activation the unfused pair stages to HBM is never
    materialized.

    ``pos`` is the decode shape of the same op: per-sequence [B] int32
    cache frontiers (keys at columns > pos[b] masked), replacing the
    static causal triangle — how the serve tick, whose batch mixes slot
    positions, runs this fusion against the KV cache.  ``plan_dialect``
    (static) pins the tuned-table slice the trace binds.

    ``block_tables`` is the *paged* decode shape: k/v become page pools
    ``[P, Hkv, page_size, D]`` and ``block_tables`` a [B, max_pages]
    int32 table mapping each slot's logical kv blocks to pool pages
    (entries past the slot's reservation hold the sentinel ``P``).  The
    table rides as a scalar-prefetch operand so the sequential kv grid
    walks table entries instead of a contiguous strip, and a ``pl.when``
    on the ``pos`` frontier skips dead blocks entirely — the kernel only
    ever visits live pages.  Requires ``pos``; ``causal`` is ignored.

    ``w_scale`` ([N] f32, with ``w_out`` int8) selects the quantized
    weight prologue — the head's wo slice is dequantized per-channel in
    VMEM.  ``k_scale``/``v_scale`` (paged shape only: per-token scale
    pools ``[P, Hkv, page_size, 1]``, with int8 kv pools) select the
    int8-KV gather: pages are dequantized in VMEM after the block-table
    gather.  These are the ``flash_attention_matmul_q8`` registry rows;
    ``tuning_op`` (static) names the tuned-table row consulted so the
    quantized twin runs its own staging plans.
    """
    if block_tables is not None:
        return _paged_attention_matmul(
            q, k, v, w_out, block_tables=block_tables, pos=pos, mode=mode,
            interpret=interpret, block_q=block_q, w_scale=w_scale,
            k_scale=k_scale, v_scale=v_scale, plan_dialect=plan_dialect,
            tuning_op=tuning_op)
    if k_scale is not None or v_scale is not None:
        raise ValueError("int8 kv scales are a paged-shape operand; the "
                         "dense decode path dequantizes its cache strip "
                         "up front (models/attention.py)")
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    assert w_out.shape[0] == h * d, (w_out.shape, h, d)
    n = w_out.shape[1]
    if mode == "library":
        if w_scale is not None:
            w_out = dequantize_weight(w_out, w_scale, q.dtype)
        if pos is None:
            o = _ref.attention(q, k, v, causal=causal)
        else:
            # the unfused decode pair: masked softmax over the cache
            # frontier (models/attention.py::decode_attention), then wo
            k_r = jnp.repeat(k, group, axis=1).astype(jnp.float32)
            v_r = jnp.repeat(v, group, axis=1).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                           k_r) * (d ** -0.5)
            valid = jnp.arange(skv)[None] <= pos[:, None]    # [B,Skv]
            s = jnp.where(valid[:, None, None], s, -1e30)
            o = jnp.einsum("bhqs,bhsd->bhqd", jax.nn.softmax(s, axis=-1),
                           v_r).astype(q.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(b, sq, h * d)
        return jnp.einsum("bsh,hn->bsn", o, w_out.astype(o.dtype))
    if kv_offset is None:
        kv_offset = skv - sq
    scale = 1.0 / (d ** 0.5)
    causal = causal and pos is None
    block_q, block_kv = resolve_attention_matmul_blocks(
        mode, sq, skv, d, n, block_q, block_kv, plan_dialect,
        op=tuning_op)
    q_p = _attention._pad_seq(q, block_q)
    k_p = _attention._pad_seq(k, block_kv)
    v_p = _attention._pad_seq(v, block_kv)
    sqp, skvp = q_p.shape[2], k_p.shape[2]
    n_p = align_up(n, 128)
    w3 = w_out.reshape(h, d, n)
    if n_p != n:
        w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, n_p - n)))
    grid = (b, sqp // block_q, h, skvp // block_kv)

    # the decode frontier is a scalar-prefetch operand (SMEM): a (1, 1)
    # VMEM block of a [B, 1] array breaks the (8, 128) tiling rule
    prefetch = [] if pos is None else [pos.astype(jnp.int32)]
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bb, qi, hh, ki, *_: (bb, hh, qi, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bb, qi, hh, ki, *_, g=group:
                     (bb, hh // g, ki, 0)),
        pl.BlockSpec((1, 1, block_kv, d),
                     lambda bb, qi, hh, ki, *_, g=group:
                     (bb, hh // g, ki, 0)),
        pl.BlockSpec((1, d, n_p), lambda bb, qi, hh, ki, *_: (hh, 0, 0)),
    ]
    operands = [q_p, k_p, v_p, w3]
    if w_scale is not None:
        s2d = w_scale.reshape(1, n).astype(jnp.float32)
        if n_p != n:
            s2d = jnp.pad(s2d, ((0, 0), (0, n_p - n)))
        in_specs.append(pl.BlockSpec((1, n_p),
                                     lambda bb, qi, hh, ki, *_: (0, 0)))
        operands.append(s2d)

    itemsize = jnp.dtype(q.dtype).itemsize
    vmem = (2 * ((block_q + 2 * block_kv) * d + block_q * n_p) * itemsize
            + 2 * d * n_p * jnp.dtype(w3.dtype).itemsize
            + (block_q * (d + n_p + 2) + 8 * LANES) * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, n_p),
                               lambda bb, qi, hh, ki, *_: (bb, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # m
            pltpu.VMEM((block_q, 1), jnp.float32),      # l
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
            pltpu.VMEM((block_q, LANES) if mode == "abstract"
                       else (8, LANES), jnp.float32),
            pltpu.VMEM((block_q, n_p), jnp.float32),    # cross-head acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_matmul_kernel, scale=scale, causal=causal,
            kv_offset=kv_offset, block_q=block_q, block_kv=block_kv,
            n_kv=grid[3], n_heads=h, kv_len=skv, mode=mode,
            has_pos=pos is not None, quant_w=w_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sqp, n_p), q.dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary", "arbitrary")
            if mode == "native" else None, vmem),
        interpret=interpret,
        name=f"uisa_flash_attention_matmul_{mode.replace('+', '_')}",
    )(*prefetch, *operands)
    return out[:, :sq, :n]


def _paged_attention_matmul(q, k_pages, v_pages, w_out, *, block_tables,
                            pos, mode: str, interpret: bool,
                            block_q: int | None,
                            w_scale=None, k_scale=None, v_scale=None,
                            plan_dialect: str | None = None,
                            tuning_op: str = "flash_attention_matmul"):
    """The paged decode lowering of ``flash_attention_matmul``.

    The kv grid dimension indexes *table entries*: the block table is a
    scalar-prefetch operand, so each kv step's index map gathers page
    ``block_tables[b, ki]`` straight out of the pool — no contiguous
    strip is ever materialized.  ``block_kv`` IS ``page_size``.  Sentinel
    entries clamp onto a real page whose contents the ``pos`` mask hides,
    and the ``skip_dead`` predicate in the shared flash kernel skips
    every block past the frontier before it computes anything.

    ``k_scale``/``v_scale`` ([P, Hkv, page_size, 1] f32 per-token scale
    pools, with int8 ``k_pages``/``v_pages``) ride the *same* block-table
    index maps as the value pools, so the gather stays one scalar-prefetch
    plan and dequantization happens in VMEM on the gathered page.
    """
    if pos is None:
        raise ValueError("paged flash_attention_matmul requires the "
                         "per-slot pos frontier")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 paged kv needs BOTH k_scale and v_scale")
    b, h, sq, d = q.shape
    num_pages, hkv, page_size, _ = k_pages.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    assert w_out.shape[0] == h * d, (w_out.shape, h, d)
    n = w_out.shape[1]
    maxp = block_tables.shape[1]
    skv = maxp * page_size
    tbl = jnp.minimum(block_tables, num_pages - 1).astype(jnp.int32)
    if mode == "library":
        # the unfused pair: gather the logical strip, masked softmax over
        # the frontier, then wo — the dense decode library row applied to
        # the gathered pages (models/attention.py::gather_paged_kv math).
        # int8 pools dequantize at gather time (host-side reference).
        if k_scale is not None:
            k_pages = k_pages.astype(jnp.float32) * k_scale
            v_pages = v_pages.astype(jnp.float32) * v_scale

        def strip(pages):
            s = pages[tbl]                     # [B, maxp, Hkv, ps, D]
            return s.transpose(0, 2, 1, 3, 4).reshape(b, hkv, skv, d)
        return flash_attention_matmul(
            q, strip(k_pages).astype(q.dtype),
            strip(v_pages).astype(q.dtype), w_out, causal=False,
            mode="library", interpret=interpret, pos=pos,
            w_scale=w_scale, plan_dialect=plan_dialect)
    if page_size % LANES != 0 and mode != "native":
        raise ValueError(
            f"paged decode under mode={mode!r} needs page_size to be a "
            f"multiple of {LANES} (the abstract row reduces fold into "
            f"{LANES}-lane vregs); got page_size={page_size}")
    scale = 1.0 / (d ** 0.5)
    bq, _ = resolve_attention_matmul_blocks(mode, sq, skv, d, n, block_q,
                                            page_size, plan_dialect,
                                            op=tuning_op)
    q_p = _attention._pad_seq(q, bq)
    sqp = q_p.shape[2]
    n_p = align_up(n, 128)
    w3 = w_out.reshape(h, d, n)
    if n_p != n:
        w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, n_p - n)))
    grid = (b, sqp // bq, h, maxp)

    # the block table and the per-slot frontier are the scalar-prefetch
    # operands (SMEM); the index maps receive both
    page_spec = pl.BlockSpec((1, 1, page_size, d),
                             lambda bb, qi, hh, ki, tr, pr, g=group:
                             (tr[bb, ki], hh // g, 0, 0))
    # per-token scale pools ride the SAME table-gather index map as the
    # value pools — one scalar-prefetch plan covers both widths
    scale_spec = pl.BlockSpec((1, 1, page_size, 1),
                              lambda bb, qi, hh, ki, tr, pr, g=group:
                              (tr[bb, ki], hh // g, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, bq, d),
                     lambda bb, qi, hh, ki, tr, pr: (bb, hh, qi, 0)),
        page_spec,
    ]
    operands = [q_p, k_pages]
    if k_scale is not None:
        in_specs.append(scale_spec)
        operands.append(k_scale.astype(jnp.float32))
    in_specs.append(page_spec)
    operands.append(v_pages)
    if v_scale is not None:
        in_specs.append(scale_spec)
        operands.append(v_scale.astype(jnp.float32))
    in_specs.append(pl.BlockSpec((1, d, n_p),
                                 lambda bb, qi, hh, ki, tr, pr: (hh, 0, 0)))
    operands.append(w3)
    if w_scale is not None:
        s2d = w_scale.reshape(1, n).astype(jnp.float32)
        if n_p != n:
            s2d = jnp.pad(s2d, ((0, 0), (0, n_p - n)))
        in_specs.append(pl.BlockSpec((1, n_p),
                                     lambda bb, qi, hh, ki, tr, pr: (0, 0)))
        operands.append(s2d)

    itemsize = jnp.dtype(q.dtype).itemsize
    vmem = (2 * (bq * d + bq * n_p) * itemsize
            + 2 * 2 * page_size * d * jnp.dtype(k_pages.dtype).itemsize
            + 2 * d * n_p * jnp.dtype(w3.dtype).itemsize
            + (bq * (d + n_p + 2) + 8 * LANES) * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, n_p),
                               lambda bb, qi, hh, ki, tr, pr: (bb, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),           # m
            pltpu.VMEM((bq, 1), jnp.float32),           # l
            pltpu.VMEM((bq, d), jnp.float32),           # acc
            pltpu.VMEM((bq, LANES) if mode == "abstract"
                       else (8, LANES), jnp.float32),
            pltpu.VMEM((bq, n_p), jnp.float32),         # cross-head acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_matmul_kernel, scale=scale, causal=False, kv_offset=0,
            block_q=bq, block_kv=page_size, n_kv=maxp, n_heads=h,
            kv_len=None, mode=mode, has_pos=True, paged=True,
            quant_w=w_scale is not None, quant_kv=k_scale is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sqp, n_p), q.dtype),
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary", "arbitrary")
            if mode == "native" else None, vmem),
        interpret=interpret,
        name=f"uisa_paged_attention_matmul_{mode.replace('+', '_')}",
    )(tbl, pos.astype(jnp.int32), *operands)
    return out[:, :sq, :n]


def structural_cost_flash_attention_matmul(
        b: int, h: int, sq: int, skv: int, d: int, n: int, causal: bool,
        mode: str, block_q=None, block_kv=None, dtype=jnp.float32,
        plan_dialect: str | None = None, page_size: int | None = None,
        pages_occupied: int | None = None,
        op: str = "flash_attention_matmul") -> dict:
    """The unfused pair's traffic minus exactly one ``[B,S,H,D]`` trip.

    Composes the registered ``flash_attention`` and ``gemm`` cost models
    (``m = B·S``, ``k = H·D``) and removes the write plus read-back of the
    attention output (``2·B·S·H·D·itemsize``) — the two legs of the
    staging the epilogue hook eliminates.  The kernel-describing columns
    (visited blocks, scratch traffic) come from attention's visited-block
    model evaluated at *this* lowering's resolved tiling.

    The paged decode shape (``page_size`` set) swaps the kv traffic term:
    the kernel gathers *pages* through the block table and the
    ``skip_dead`` predicate never visits a block past the frontier, so
    its kv bytes scale with ``pages_occupied`` (live pages across the
    batch; default ``b · ceil(skv / page_size)``, the fully-occupied
    worst case used for static auto-selection) — **not** with the
    ``max_len`` capacity a dense strip would stream."""
    itemsize = jnp.dtype(dtype).itemsize
    if page_size is not None:
        return _structural_cost_paged(
            b=b, h=h, sq=sq, skv=skv, d=d, n=n, mode=mode, block_q=block_q,
            dtype=dtype, plan_dialect=plan_dialect, page_size=page_size,
            pages_occupied=pages_occupied, op=op)
    if mode == "library":
        bq, bkv = 256, 256
    else:
        bq, bkv = resolve_attention_matmul_blocks(mode, sq, skv, d, n,
                                                  block_q, block_kv,
                                                  plan_dialect, op=op)
    # ONE attention evaluation at this lowering's resolved tiling: its
    # hbm term is block-independent (so the pair sum is unaffected) and
    # its flops/visited/scratch columns then all describe the same grid.
    att = _attention.structural_cost(
        b=b, h=h, sq=sq, skv=skv, d=d, causal=causal, mode=mode,
        block_q=bq, block_kv=bkv, dtype=dtype, plan_dialect=plan_dialect)
    g = _gemm.structural_cost(m=b * sq, n=n, k=h * d, mode=mode,
                              dtype=dtype, plan_dialect=plan_dialect)
    unfused = att["hbm_bytes"] + g["hbm_bytes"]
    saved = 0 if mode == "library" else 2 * b * sq * h * d * itemsize
    ws, _ = _weight_stream(b * sq, n, h * d, mode, dtype, plan_dialect)
    return {
        "hbm_bytes": unfused - saved,
        "hbm_bytes_unfused_pair": unfused,
        "hbm_bytes_saved": saved,
        "weight_stream_bytes": ws,
        "flops": att["flops"] + g["flops"],
        "block": (bq, bkv),
        "blocks_visited": att["blocks_visited"],
        "skip_fraction": att["skip_fraction"],
        "scratch_round_trips_per_block":
            att["scratch_round_trips_per_block"],
        "scratch_bytes_total": att["scratch_bytes_total"],
        "lane_shuffles_per_block": att["lane_shuffles_per_block"],
        "fused_epilogue": mode != "library",
    }


def _structural_cost_paged(*, b: int, h: int, sq: int, skv: int, d: int,
                           n: int, mode: str, block_q, dtype,
                           plan_dialect: str | None, page_size: int,
                           pages_occupied: int | None,
                           op: str = "flash_attention_matmul") -> dict:
    """Occupied-page accounting for the paged decode shape.

    ``skv`` is the logical capacity (``max_pages · page_size``); the kv
    stream term reads ``pages_occupied · page_size`` rows because the
    table gather only touches live pages and ``skip_dead`` predication
    skips the rest at the grid level.  Capacity (``skv``) appears in
    ``blocks_total`` only — growing ``max_len`` with fixed occupancy
    leaves ``hbm_bytes`` unchanged, which is the whole point of paging.
    """
    itemsize = jnp.dtype(dtype).itemsize
    maxp = -(-skv // page_size)
    total_pages = b * maxp
    if pages_occupied is None:
        pages_occupied = total_pages
    pages_occupied = min(pages_occupied, total_pages)
    if mode == "library":
        bq = 256
    else:
        bq, _ = resolve_attention_matmul_blocks(mode, sq, skv, d, n,
                                                block_q, page_size,
                                                plan_dialect, op=op)
    visited = h * pages_occupied        # every head walks live pages only
    reduces_per_block = 2               # row-max + row-sum
    if mode == "abstract":
        round_trips = reduces_per_block * tree_stages(LANES)
        scratch_bytes = (visited * reduces_per_block *
                         scratch_tree_bytes(LANES, rows=bq))
        shuffles = 0
    elif mode == "abstract+shuffle":
        round_trips, scratch_bytes = 0, 0
        shuffles = reduces_per_block * tree_stages(LANES)
    else:                               # native / library
        round_trips, scratch_bytes, shuffles = 0, 0, 0
    kv_stream = 2 * h * d * pages_occupied * page_size * itemsize
    att_hbm = h * d * 2 * b * sq * itemsize + kv_stream
    g = _gemm.structural_cost(m=b * sq, n=n, k=h * d, mode=mode,
                              dtype=dtype, plan_dialect=plan_dialect)
    unfused = att_hbm + g["hbm_bytes"]
    saved = 0 if mode == "library" else 2 * b * sq * h * d * itemsize
    ws, _ = _weight_stream(b * sq, n, h * d, mode, dtype, plan_dialect)
    return {
        "hbm_bytes": unfused - saved,
        "hbm_bytes_unfused_pair": unfused,
        "hbm_bytes_saved": saved,
        "weight_stream_bytes": ws,
        "kv_stream_bytes": kv_stream,
        "flops": visited * 4 * bq * page_size * d + g["flops"],
        "block": (bq, page_size),
        "blocks_visited": visited,
        "blocks_total": h * total_pages,
        "skip_fraction": 1.0 - pages_occupied / total_pages,
        "scratch_round_trips_per_block": round_trips,
        "scratch_bytes_total": scratch_bytes,
        "lane_shuffles_per_block": shuffles,
        "fused_epilogue": mode != "library",
        "page_size": page_size,
        "pages_occupied": pages_occupied,
    }


def structural_cost_flash_attention_matmul_q8(
        b: int, h: int, sq: int, skv: int, d: int, n: int, causal: bool,
        mode: str, block_q=None, block_kv=None, dtype=jnp.float32,
        plan_dialect: str | None = None, page_size: int | None = None,
        pages_occupied: int | None = None) -> dict:
    """The f32 model with the weight stream (and, on the paged shape, the
    kv stream) re-priced at int8 width: int8 values plus the f32 scale
    sideband (per-channel for wo, per-token for kv pages) replace each
    f32 stream, and the delta comes off both the fused bytes and the
    unfused pair — the saving is a *stream width* effect, orthogonal to
    what fusion saves."""
    base = structural_cost_flash_attention_matmul(
        b, h, sq, skv, d, n, causal, mode, block_q, block_kv, dtype,
        plan_dialect, page_size, pages_occupied,
        op="flash_attention_matmul_q8")
    ws_f32, rereads = _weight_stream(b * sq, n, h * d, mode, dtype,
                                     plan_dialect)
    ws_q8 = _q8_weight_stream(rereads, h * d, n)
    delta = ws_f32 - ws_q8
    if page_size is not None:
        # int8 page rows: 2·d value bytes + two f32 per-token scales
        kv_q8 = (h * base["pages_occupied"] * page_size * (2 * d + 8))
        delta += base["kv_stream_bytes"] - kv_q8
        base["kv_stream_bytes"] = kv_q8
        base["kv_precision"] = "int8"
    base.update(
        hbm_bytes=base["hbm_bytes"] - delta,
        hbm_bytes_unfused_pair=base["hbm_bytes_unfused_pair"] - delta,
        weight_stream_bytes=ws_q8,
        weight_stream_bytes_f32=ws_f32,
        weight_precision="int8",
    )
    return base


# --------------------------------------------------------------------------
# rmsnorm -> [wi|wg] swiglu: the norm as prologue, the silu gate as epilogue
# --------------------------------------------------------------------------


def resolve_swiglu_blocks(mode: str, rows: int, d: int, f: int,
                          dtype=jnp.float32,
                          plan_dialect: str | None = None,
                          op: str = "rmsnorm_swiglu"):
    """The (bm, bn) tile over ``rows × f``: this op's tuned entry first
    (its working set holds *two* weight tiles plus the hi/hg/out trio),
    then the shared GEMM heuristic.  Shared by kernel and cost;
    ``plan_dialect`` names the table slice consulted; ``op`` names the
    table row — the quantized twin tunes its own staging."""
    entry = tuned_entry(op, mode, swiglu_bucket(rows, d, f),
                        dialect=plan_dialect)
    if entry and "block" in entry:
        bm, bn = entry["block"]
        return int(bm), int(bn)
    bm, bn, _ = _gemm.block_shape_for(mode, rows, f, d, dtype, plan_dialect)
    return bm, bn


def _rmsnorm_swiglu_kernel(*refs, eps: float, mode: str, d_true: int,
                           quant: bool = False):
    # operands: x, w, wi, wg, [si, sg] — the scale rows ride only the
    # quantized rows and dequantize the int8 weight tiles in VMEM
    if quant:
        (x_ref, w_ref, wi_ref, wg_ref, si_ref, sg_ref, o_ref,
         scratch_ref) = refs
    else:
        x_ref, w_ref, wi_ref, wg_ref, o_ref, scratch_ref = refs
        si_ref = sg_ref = None
    x = x_ref[...].astype(jnp.float32)                    # (bm, d)
    w = w_ref[...].astype(jnp.float32)                    # (1, d)
    y = _rmsnorm.normalize_block(x, w, scratch_ref, eps=eps, mode=mode,
                                 d_true=d_true)
    # both halves of the concatenated [wi|wg] weight consume the
    # normalized block from VMEM; the silu gate runs in the epilogue on
    # products that never left the core.
    wi, wg = wi_ref[...], wg_ref[...]
    if si_ref is not None:
        wi = wi.astype(jnp.float32) * si_ref[...]         # (1, bn) scales
        wg = wg.astype(jnp.float32) * sg_ref[...]
    y = y.astype(wi.dtype)              # the MXU takes the weight's width
    hi = jax.lax.dot_general(
        y, wi, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    hg = jax.lax.dot_general(
        y, wg, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] = (jax.nn.silu(hg) * hi).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "interpret",
                                             "plan_dialect", "tuning_op"))
def rmsnorm_swiglu(x: jax.Array, weight: jax.Array, w_cat: jax.Array, *,
                   eps: float = 1e-6, mode: str = "native",
                   interpret: bool = True,
                   w_scale: jax.Array | None = None,
                   plan_dialect: str | None = None,
                   tuning_op: str = "rmsnorm_swiglu") -> jax.Array:
    """``silu(y @ wg) * (y @ wi)`` with ``y = rmsnorm(x, weight)``, fused.

    x: [..., D]; weight: [D]; w_cat: [D, 2F] — the concatenated
    ``[wi|wg]`` weight (wi the first F columns, wg the last) -> [..., F].
    One call per sublayer: the residual is read and the moment computed
    once, the normalized activation and both projection products stay in
    VMEM.

    ``w_scale`` ([2F] f32, with ``w_cat`` int8) selects the quantized
    weight prologue: both int8 weight tiles dequantize per-channel in
    VMEM — the ``rmsnorm_swiglu_q8`` registry rows.  ``tuning_op``
    (static) names the tuned-table row consulted.
    """
    *lead, d = x.shape
    assert w_cat.shape[0] == d and w_cat.shape[1] % 2 == 0, \
        (x.shape, w_cat.shape)
    f = w_cat.shape[1] // 2
    if mode == "library":
        if w_scale is not None:
            w_cat = dequantize_weight(w_cat, w_scale, x.dtype)
        y = _ref.rmsnorm(x, weight, eps)
        hi = jnp.einsum("...d,df->...f", y, w_cat[:, :f].astype(y.dtype))
        hg = jnp.einsum("...d,df->...f", y, w_cat[:, f:].astype(y.dtype))
        return jax.nn.silu(hg) * hi
    rows = 1
    for s in lead:
        rows *= s
    x2d = x.reshape(rows, d)
    w2d = weight.reshape(1, d)
    wc2d = w_cat
    sc2d = None if w_scale is None else \
        w_scale.reshape(1, 2 * f).astype(jnp.float32)

    d_padded = d
    if mode != "native":
        pad_d = (-d) % LANES
        if pad_d:
            d_padded = d + pad_d
            x2d = jnp.pad(x2d, ((0, 0), (0, pad_d)))
            w2d = jnp.pad(w2d, ((0, 0), (0, pad_d)))
            wc2d = jnp.pad(wc2d, ((0, pad_d), (0, 0)))

    bm, bn = resolve_swiglu_blocks(mode, rows, d, f, x.dtype, plan_dialect,
                                   op=tuning_op)
    bm, bn, vmem = row_kernel_tiles(bm, bn, rows, d_padded, f,
                                    jnp.dtype(x.dtype).itemsize, n_weights=2)
    pad_m = (-rows) % bm
    if pad_m:
        x2d = jnp.pad(x2d, ((0, pad_m), (0, 0)))
    pad_n = (-f) % bn
    if pad_n:
        # pad each half to the tile, so wg's columns start on a tile
        # boundary; when f divides, [wi|wg] is read in place, uncopied
        def pad_halves(a):
            pad = ((0, 0), (0, pad_n))
            return jnp.concatenate([jnp.pad(a[:, :f], pad),
                                    jnp.pad(a[:, f:], pad)], axis=1)
        wc2d = pad_halves(wc2d)
        if sc2d is not None:
            sc2d = pad_halves(sc2d)
    mp, fp = rows + pad_m, f + pad_n
    grid = (mp // bm, fp // bn)
    params = compiler_params(
        ("parallel", "parallel") if mode == "native" else None, vmem)

    wg_off = fp // bn               # wg's first column block in [wi|wg]
    in_specs = [
        pl.BlockSpec((bm, d_padded), lambda i, j: (i, 0)),
        pl.BlockSpec((1, d_padded), lambda i, j: (0, 0)),
        pl.BlockSpec((d_padded, bn), lambda i, j: (0, j)),
        pl.BlockSpec((d_padded, bn), lambda i, j: (0, j + wg_off)),
    ]
    operands = [x2d, w2d, wc2d, wc2d]
    if sc2d is not None:
        in_specs += [pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                     pl.BlockSpec((1, bn), lambda i, j: (0, j + wg_off))]
        operands += [sc2d, sc2d]
    kernel_name = ("uisa_rmsnorm_swiglu_q8_" if sc2d is not None
                   else "uisa_rmsnorm_swiglu_") + mode.replace('+', '_')
    out = pl.pallas_call(
        functools.partial(_rmsnorm_swiglu_kernel, eps=eps, mode=mode,
                          d_true=d, quant=sc2d is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, fp), x.dtype),
        scratch_shapes=[pltpu.VMEM(
            (bm, LANES) if mode == "abstract" else (8, LANES),
            jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=kernel_name,
    )(*operands)
    return out[:rows, :f].reshape(*lead, f)


def structural_cost_rmsnorm_swiglu(rows: int, d: int, f: int, mode: str,
                                   dtype=jnp.float32,
                                   plan_dialect: str | None = None,
                                   op: str = "rmsnorm_swiglu") -> dict:
    """The unfused pair's traffic minus exactly one activation round trip.

    The pair is ``rmsnorm`` + one GEMM against the concatenated
    ``[D, 2F]`` weight; the fused lowering removes the normalized
    activation's write + read-back (``2 · rows · d · itemsize``) —
    claimed conservatively: the hi/hg products the epilogue gate consumes
    also never stage, but only the norm round trip is pinned."""
    itemsize = jnp.dtype(dtype).itemsize
    g = _gemm.structural_cost(m=rows, n=2 * f, k=d, mode=mode, dtype=dtype,
                              plan_dialect=plan_dialect)
    r = _rmsnorm.structural_cost(rows=rows, d=d, mode=mode, dtype=dtype,
                                 plan_dialect=plan_dialect)
    unfused = g["hbm_bytes"] + r["hbm_bytes"]
    saved = 0 if mode == "library" else 2 * rows * d * itemsize
    if mode == "library":
        bm = bn = 512
    else:
        bm, bn = resolve_swiglu_blocks(mode, rows, d, f, dtype,
                                       plan_dialect, op=op)
        bm, bn, _ = row_kernel_tiles(bm, bn, rows, _padded_d(d, mode), f,
                                     itemsize, n_weights=2)
    steps = -(-rows // bm) * -(-f // bn)
    if mode == "abstract":
        round_trips = tree_stages(LANES) + 1   # tree + moment re-stage
        scratch_bytes = steps * (scratch_tree_bytes(LANES, rows=bm)
                                 + 3 * bm * 4)
    else:
        round_trips = 0
        scratch_bytes = 0
    ws, _ = _weight_stream(rows, 2 * f, d, mode, dtype, plan_dialect)
    return {
        "hbm_bytes": unfused - saved,
        "hbm_bytes_unfused_pair": unfused,
        "hbm_bytes_saved": saved,
        "weight_stream_bytes": ws,
        "flops": g["flops"],
        "block": (bm, bn),
        "blocks": steps,
        "scratch_round_trips_per_block": round_trips,
        "scratch_bytes_total": scratch_bytes,
        "lane_shuffles_per_block": tree_stages(LANES)
        if mode == "abstract+shuffle" else 0,
        "fused_epilogue": mode != "library",
    }


def structural_cost_rmsnorm_swiglu_q8(rows: int, d: int, f: int, mode: str,
                                      dtype=jnp.float32,
                                      plan_dialect: str | None = None
                                      ) -> dict:
    """The f32 model with the ``[wi|wg]`` stream re-priced at int8 width
    (int8 tiles + one f32 per-channel scale row), off both the fused
    bytes and the unfused pair."""
    base = structural_cost_rmsnorm_swiglu(rows, d, f, mode, dtype,
                                          plan_dialect,
                                          op="rmsnorm_swiglu_q8")
    ws_f32, rereads = _weight_stream(rows, 2 * f, d, mode, dtype,
                                     plan_dialect)
    ws_q8 = _q8_weight_stream(rereads, d, 2 * f)
    delta = ws_f32 - ws_q8
    base.update(
        hbm_bytes=base["hbm_bytes"] - delta,
        hbm_bytes_unfused_pair=base["hbm_bytes_unfused_pair"] - delta,
        weight_stream_bytes=ws_q8,
        weight_stream_bytes_f32=ws_f32,
        weight_precision="int8",
    )
    return base


# --------------------------------------------------------------------------
# Library rows: the unfused jnp pairs (numerical reference AND the declared
# fallback target — requesting an illegal fused mode degrades to the pair
# with a warning + a recorded event, never silently).
# --------------------------------------------------------------------------


def _rmsnorm_matmul_library(x, weight, w_proj, *, eps: float = 1e-6,
                            interpret: bool = True,
                            plan_dialect: str | None = None):
    del interpret, plan_dialect
    return rmsnorm_matmul(x, weight, w_proj, eps=eps, mode="library")


def _add_rmsnorm_library(x, residual, weight, *, eps: float = 1e-6,
                         interpret: bool = True,
                         plan_dialect: str | None = None):
    del interpret, plan_dialect
    return add_rmsnorm(x, residual, weight, eps=eps, mode="library")


def _flash_attention_matmul_library(q, k, v, w_out, *, causal: bool = True,
                                    kv_offset=None, interpret: bool = True,
                                    block_q=None, block_kv=None, pos=None,
                                    block_tables=None,
                                    plan_dialect: str | None = None):
    # library: XLA decides every staging parameter
    del kv_offset, interpret, block_q, block_kv, plan_dialect
    return flash_attention_matmul(q, k, v, w_out, causal=causal,
                                  mode="library", pos=pos,
                                  block_tables=block_tables)


def _rmsnorm_swiglu_library(x, weight, w_cat, *, eps: float = 1e-6,
                            interpret: bool = True,
                            plan_dialect: str | None = None):
    del interpret, plan_dialect
    return rmsnorm_swiglu(x, weight, w_cat, eps=eps, mode="library")


# --------------------------------------------------------------------------
# Quantized twins: the SAME fused bodies behind int8 weight prologues.
# Each accepts pre-quantized operands (int8 + per-channel f32 scale, the
# checkpoint's stored form) or, with ``w_scale=None``, f32 weights it
# quantizes on the fly — so ``REGISTRY.select`` under an int8 precision
# policy can retarget a call site that still holds f32 operands.
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode", "eps", "interpret",
                                             "plan_dialect"))
def rmsnorm_matmul_q8(x: jax.Array, weight: jax.Array, w_proj: jax.Array,
                      *, eps: float = 1e-6, mode: str = "native",
                      interpret: bool = True,
                      w_scale: jax.Array | None = None,
                      plan_dialect: str | None = None) -> jax.Array:
    if w_scale is None:
        w_proj, w_scale = quantize_weight(w_proj)
    return rmsnorm_matmul(x, weight, w_proj, eps=eps, mode=mode,
                          interpret=interpret, w_scale=w_scale,
                          plan_dialect=plan_dialect)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "interpret",
                                             "plan_dialect"))
def rmsnorm_swiglu_q8(x: jax.Array, weight: jax.Array, w_cat: jax.Array,
                      *, eps: float = 1e-6, mode: str = "native",
                      interpret: bool = True,
                      w_scale: jax.Array | None = None,
                      plan_dialect: str | None = None) -> jax.Array:
    if w_scale is None:
        w_cat, w_scale = quantize_weight(w_cat)
    return rmsnorm_swiglu(x, weight, w_cat, eps=eps, mode=mode,
                          interpret=interpret, w_scale=w_scale,
                          plan_dialect=plan_dialect,
                          tuning_op="rmsnorm_swiglu_q8")


@functools.partial(jax.jit, static_argnames=(
    "causal", "mode", "interpret", "block_q", "block_kv", "kv_offset",
    "plan_dialect"))
def flash_attention_matmul_q8(q: jax.Array, k: jax.Array, v: jax.Array,
                              w_out: jax.Array, *, causal: bool = True,
                              kv_offset: int | None = None,
                              mode: str = "native", interpret: bool = True,
                              block_q: int | None = None,
                              block_kv: int | None = None,
                              pos: jax.Array | None = None,
                              block_tables: jax.Array | None = None,
                              w_scale: jax.Array | None = None,
                              k_scale: jax.Array | None = None,
                              v_scale: jax.Array | None = None,
                              plan_dialect: str | None = None
                              ) -> jax.Array:
    if w_scale is None:
        w_out, w_scale = quantize_weight(w_out)
    return flash_attention_matmul(
        q, k, v, w_out, causal=causal, kv_offset=kv_offset, mode=mode,
        interpret=interpret, block_q=block_q, block_kv=block_kv, pos=pos,
        block_tables=block_tables, w_scale=w_scale, k_scale=k_scale,
        v_scale=v_scale, plan_dialect=plan_dialect,
        tuning_op="flash_attention_matmul_q8")


def _rmsnorm_matmul_q8_library(x, weight, w_proj, *, eps: float = 1e-6,
                               interpret: bool = True, w_scale=None,
                               plan_dialect: str | None = None):
    del interpret, plan_dialect
    return rmsnorm_matmul_q8(x, weight, w_proj, eps=eps, mode="library",
                             w_scale=w_scale)


def _rmsnorm_swiglu_q8_library(x, weight, w_cat, *, eps: float = 1e-6,
                               interpret: bool = True, w_scale=None,
                               plan_dialect: str | None = None):
    del interpret, plan_dialect
    return rmsnorm_swiglu_q8(x, weight, w_cat, eps=eps, mode="library",
                             w_scale=w_scale)


def _flash_attention_matmul_q8_library(q, k, v, w_out, *,
                                       causal: bool = True, kv_offset=None,
                                       interpret: bool = True, block_q=None,
                                       block_kv=None, pos=None,
                                       block_tables=None, w_scale=None,
                                       k_scale=None, v_scale=None,
                                       plan_dialect: str | None = None):
    del kv_offset, interpret, block_q, block_kv, plan_dialect
    return flash_attention_matmul_q8(
        q, k, v, w_out, causal=causal, mode="library", pos=pos,
        block_tables=block_tables, w_scale=w_scale, k_scale=k_scale,
        v_scale=v_scale)


for _mode, _contract in (("abstract", _RM_ABSTRACT),
                         ("abstract+shuffle", _RM_SHUFFLE),
                         ("native", _RM_NATIVE)):
    REGISTRY.register(
        "rmsnorm_matmul", _mode,
        functools.partial(rmsnorm_matmul, mode=_mode), contract=_contract,
        cost=functools.partial(structural_cost_rmsnorm_matmul, mode=_mode))
REGISTRY.register(
    "rmsnorm_matmul", IsaMode.LIBRARY, _rmsnorm_matmul_library,
    cost=functools.partial(structural_cost_rmsnorm_matmul, mode="library"))

for _mode, _contract in (("abstract", _AR_ABSTRACT),
                         ("abstract+shuffle", _AR_SHUFFLE),
                         ("native", _AR_NATIVE)):
    REGISTRY.register(
        "add_rmsnorm", _mode,
        functools.partial(add_rmsnorm, mode=_mode), contract=_contract,
        cost=functools.partial(structural_cost_add_rmsnorm, mode=_mode))
REGISTRY.register(
    "add_rmsnorm", IsaMode.LIBRARY, _add_rmsnorm_library,
    cost=functools.partial(structural_cost_add_rmsnorm, mode="library"))

for _mode, _contract in (("abstract", _FA_ABSTRACT),
                         ("abstract+shuffle", _FA_SHUFFLE),
                         ("native", _FA_NATIVE)):
    REGISTRY.register(
        "flash_attention_matmul", _mode,
        functools.partial(flash_attention_matmul, mode=_mode),
        contract=_contract,
        cost=functools.partial(structural_cost_flash_attention_matmul,
                               mode=_mode))
REGISTRY.register(
    "flash_attention_matmul", IsaMode.LIBRARY,
    _flash_attention_matmul_library,
    cost=functools.partial(structural_cost_flash_attention_matmul,
                           mode="library"))

for _mode, _contract in (("abstract", _SW_ABSTRACT),
                         ("abstract+shuffle", _SW_SHUFFLE),
                         ("native", _SW_NATIVE)):
    REGISTRY.register(
        "rmsnorm_swiglu", _mode,
        functools.partial(rmsnorm_swiglu, mode=_mode), contract=_contract,
        cost=functools.partial(structural_cost_rmsnorm_swiglu, mode=_mode))
REGISTRY.register(
    "rmsnorm_swiglu", IsaMode.LIBRARY, _rmsnorm_swiglu_library,
    cost=functools.partial(structural_cost_rmsnorm_swiglu, mode="library"))

# Declared per-mode fallbacks (warned + recorded in fallback_events):
# the shuffle moment tree degrades to scratch round-trips on a no-shuffle
# dialect; the target-pinned native epilogue degrades to the unfused XLA
# pair (the library row) anywhere it is illegal.
for _op in FUSED_OPS:
    REGISTRY.declare_fallback(
        _op, IsaMode.ABSTRACT_SHUFFLE, IsaMode.ABSTRACT,
        reason="no lane shuffle on this dialect; the cross-lane reduction "
               "degrades to the scratch-tree lowering")
    REGISTRY.declare_fallback(
        _op, IsaMode.NATIVE, IsaMode.LIBRARY,
        reason="fused native epilogue is target-pinned; the unfused XLA "
               "pair is the declared escape")

# Quantized rows: same mode grid, q8 contracts, q8 cost models.  Their
# cost dicts re-price the weight (and paged-kv) streams at int8 width,
# so auto-selection sees the traffic cut before anything runs.
for _mode, _contract in (("abstract", _RMQ_ABSTRACT),
                         ("abstract+shuffle", _RMQ_SHUFFLE),
                         ("native", _RMQ_NATIVE)):
    REGISTRY.register(
        "rmsnorm_matmul_q8", _mode,
        functools.partial(rmsnorm_matmul_q8, mode=_mode),
        contract=_contract,
        cost=functools.partial(structural_cost_rmsnorm_matmul_q8,
                               mode=_mode))
REGISTRY.register(
    "rmsnorm_matmul_q8", IsaMode.LIBRARY, _rmsnorm_matmul_q8_library,
    cost=functools.partial(structural_cost_rmsnorm_matmul_q8,
                           mode="library"))

for _mode, _contract in (("abstract", _FAQ_ABSTRACT),
                         ("abstract+shuffle", _FAQ_SHUFFLE),
                         ("native", _FAQ_NATIVE)):
    REGISTRY.register(
        "flash_attention_matmul_q8", _mode,
        functools.partial(flash_attention_matmul_q8, mode=_mode),
        contract=_contract,
        cost=functools.partial(structural_cost_flash_attention_matmul_q8,
                               mode=_mode))
REGISTRY.register(
    "flash_attention_matmul_q8", IsaMode.LIBRARY,
    _flash_attention_matmul_q8_library,
    cost=functools.partial(structural_cost_flash_attention_matmul_q8,
                           mode="library"))

for _mode, _contract in (("abstract", _SWQ_ABSTRACT),
                         ("abstract+shuffle", _SWQ_SHUFFLE),
                         ("native", _SWQ_NATIVE)):
    REGISTRY.register(
        "rmsnorm_swiglu_q8", _mode,
        functools.partial(rmsnorm_swiglu_q8, mode=_mode),
        contract=_contract,
        cost=functools.partial(structural_cost_rmsnorm_swiglu_q8,
                               mode=_mode))
REGISTRY.register(
    "rmsnorm_swiglu_q8", IsaMode.LIBRARY, _rmsnorm_swiglu_q8_library,
    cost=functools.partial(structural_cost_rmsnorm_swiglu_q8,
                           mode="library"))

for _op in QUANT_OPS:
    REGISTRY.declare_fallback(
        _op, IsaMode.ABSTRACT_SHUFFLE, IsaMode.ABSTRACT,
        reason="no lane shuffle on this dialect; the cross-lane reduction "
               "degrades to the scratch-tree lowering")
    REGISTRY.declare_fallback(
        _op, IsaMode.NATIVE, IsaMode.LIBRARY,
        reason="fused native epilogue is target-pinned; the unfused XLA "
               "pair (dequantize, then the pair) is the declared escape")

# the precision axis: ExecutionPolicy(precision="int8") retargets the f32
# op names at select() time — call sites never spell the q8 names.
for _base in ("rmsnorm_matmul", "rmsnorm_swiglu", "flash_attention_matmul"):
    REGISTRY.register_precision_variant(_base, "int8", _base + "_q8")

FUSED_OPS = FUSED_OPS + QUANT_OPS

# the fused chunked SSD scan (ISSUE 8) registers itself on import; pulling
# it in here keeps FUSED_OPS authoritative for every consumer regardless
# of import order (kernels/ssd.py depends only on repro.core — no cycle).
from repro.kernels import ssd as _ssd  # noqa: E402,F401

FUSED_OPS = FUSED_OPS + ("ssd_scan", "ssd_decode")
