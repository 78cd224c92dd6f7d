"""Tiled GEMM under the UISA methodology (paper Table V, row 1).

Three Pallas variants of the *same* algorithm (single-pass tiled matmul
with f32 accumulation), differing only in which primitive budget they
spend — the TPU transposition of the paper's native/abstract CUDA/Metal
pairs:

- ``abstract``: universal primitives only.  Square tiles sized purely by
  the dialect scratchpad budget (Eq. 1 algebra; ``choose_block_bytes``),
  no matrix-tile alignment query, no pipeline annotations.  The MMA itself
  is the *opaque queryable* matrix op the abstract model permits (§V:
  "Optional: matrix MMA with queryable tiles").
- ``native``: full target feature set — block shapes aligned to the queried
  MXU tile (mxu_aligned_tiles), ``dimension_semantics`` annotations
  (parallel/parallel/arbitrary), larger rectangular tiles for reuse.
- ``library``: XLA's own dot (the cuBLAS analogue).

The paper found abstract ≥ native on both its platforms for GEMM (126.1% /
101.2%) because vendor-specific layout tricks encoded stale assumptions.
On TPU the structural prediction is the opposite — MXU alignment is load
bearing — which `structural_cost` quantifies and EXPERIMENTS.md discusses.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (IsaMode, KernelContract, Primitive, REGISTRY,
                        TARGET, UNIVERSAL_SET, align_up, choose_block_bytes,
                        register_op_space, tuned_block, validate_contract)
from repro.core.pipeline import compiler_params

register_op_space("gemm", "gemm")

# --------------------------------------------------------------------------
# Contracts (validated at import: the abstract variant cannot regress into
# using native features without failing tests).
# --------------------------------------------------------------------------

ABSTRACT_CONTRACT = KernelContract(
    kernel="gemm", mode=IsaMode.ABSTRACT,
    primitives=frozenset({
        Primitive.LOCKSTEP_GROUP, Primitive.MANAGED_SCRATCHPAD,
        Primitive.HIERARCHICAL_MEMORY, Primitive.WORKGROUP_BARRIER,
        Primitive.IDENTITY_REGISTERS, Primitive.ASYNC_MEMORY,
        Primitive.REGISTER_OCCUPANCY,
    }))
NATIVE_CONTRACT = KernelContract(
    kernel="gemm", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"mxu_aligned_tiles", "dimension_semantics",
                               "multi_buffering"}))
validate_contract(ABSTRACT_CONTRACT)
validate_contract(NATIVE_CONTRACT)


def abstract_block_shape(dtype=jnp.float32) -> Tuple[int, int, int]:
    """Tile edge from the scratchpad budget alone (no MXU query).

    Working set of one step = 3 square tiles (A, B, acc).  Solve
    3·e²·bytes ≤ budget with double-buffered occupancy ≥ 2, then round
    *down* to the minimal legal TPU tile granule (8×128 layout => edge
    multiple of 128 on the minor dim; we keep square tiles, the abstract
    kernel's whole point is not to shape for the MXU).
    """
    itemsize = jnp.dtype(dtype).itemsize
    budget = choose_block_bytes(TARGET.S, n_buffers=2, min_occupancy=2)
    edge = int((budget / (3 * max(itemsize, 4))) ** 0.5)
    edge = max(128, (edge // 128) * 128)
    return (edge, edge, edge)


def native_block_shape(dtype=jnp.float32) -> Tuple[int, int, int]:
    """Rectangular tiles aligned to the queried matrix unit, shaped for
    A/B reuse: bm=512, bn=512, bk=2·tile for pipeline depth."""
    tile_m, tile_n, tile_k = TARGET.matrix_unit.tile
    return (4 * tile_m, 4 * tile_n, 2 * tile_k)


def block_shape_for(mode: str, m: int, n: int, k: int,
                    dtype=jnp.float32,
                    plan_dialect: str | None = None) -> Tuple[int, int, int]:
    """The (bm, bn, bk) tile for one call: autotuner winner first.

    Consulted by both the kernel and ``structural_cost`` (and by the
    fused ``rmsnorm_matmul`` lowering), so the modeled traffic and the
    executed tiling cannot drift apart.  ``plan_dialect`` names the table
    slice consulted (None = ambient policy's dialect).  The ``library``
    row is XLA's own tiling and is not tunable — callers keep their
    indicative constant.
    """
    tuned = tuned_block("gemm", mode, m, n, k, dialect=plan_dialect)
    if tuned is not None:
        return tuned
    if mode == "native":
        return native_block_shape(dtype)
    return abstract_block_shape(dtype)


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, out_dtype):
    """Shared body: the algorithm is identical across variants (the paper's
    'structurally equivalent implementations' requirement)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _pad_to(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("mode", "out_dtype", "interpret",
                                             "plan_dialect"))
def gemm(a: jax.Array, b: jax.Array, *, mode: str = "native",
         out_dtype=jnp.float32, interpret: bool = True,
         plan_dialect: str | None = None) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N], f32 accumulation, UISA-mode selectable."""
    assert a.shape[1] == b.shape[0], (a.shape, b.shape)
    m, k = a.shape
    _, n = b.shape
    if mode == "library":
        return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)

    if mode not in ("abstract", "abstract+shuffle", "native"):
        raise ValueError(f"unknown isa mode {mode!r}")
    bm, bn, bk = block_shape_for(mode, m, n, k, a.dtype, plan_dialect)
    # cap blocks at the (tile-rounded) problem size for small inputs
    bm, bn, bk = (min(bm, align_up(m, 128)), min(bn, align_up(n, 128)),
                  min(bk, align_up(k, 128)))
    # double-buffered A/B/C blocks plus the f32 accumulator
    vmem = (2 * (bm * bk + bk * bn) * jnp.dtype(a.dtype).itemsize
            + 2 * bm * bn * jnp.dtype(out_dtype).itemsize + bm * bn * 4)
    params = compiler_params(("parallel", "parallel", "arbitrary")
                             if mode == "native" else None, vmem)
    a_p = _pad_to(a, bm, bk)
    b_p = _pad_to(b, bk, bn)
    mp, kp = a_p.shape
    _, np_ = b_p.shape
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=grid[2], out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name=f"uisa_gemm_{mode.replace('+', '_')}",
    )(a_p, b_p)
    return out[:m, :n]


def structural_cost(m: int, n: int, k: int, mode: str,
                    dtype=jnp.float32,
                    plan_dialect: str | None = None) -> dict:
    """Modeled HBM traffic + FLOPs for the roofline discussion.

    A is re-read N/bn times, B re-read M/bm times, C written once — the
    classic tiled-GEMM traffic model.  This is the quantity the block
    shape actually controls, and the term the paper's Table V wall-clock
    differences trace back to.
    """
    itemsize = jnp.dtype(dtype).itemsize
    if mode == "library":
        bm = bn = bk = 512  # XLA's default-ish tiling; indicative only
    else:
        bm, bn, bk = block_shape_for(mode, m, n, k, dtype, plan_dialect)
    n_reads_a = max(1, -(-n // bn))
    n_reads_b = max(1, -(-m // bm))
    hbm_bytes = (m * k * itemsize * n_reads_a
                 + k * n * itemsize * n_reads_b
                 + m * n * jnp.dtype(jnp.float32).itemsize)
    mxu_tile = TARGET.matrix_unit.tile[0]
    pad = lambda d, b: -(-d // b) * b
    padded_flops = 2 * pad(m, bm) * pad(n, bn) * pad(k, bk)
    return {
        "flops": 2 * m * n * k,
        "padded_flops": padded_flops,
        "hbm_bytes": int(hbm_bytes),
        "block": (bm, bn, bk),
        "mxu_aligned": (bm % mxu_tile == 0 and bn % mxu_tile == 0
                        and bk % mxu_tile == 0),
        "vmem_working_set": (bm * bk + bk * bn) * itemsize + bm * bn * 4,
    }


# --------------------------------------------------------------------------
# Registry: contract-checked installation of every variant (Table V row 1).
# The cross-lane stage of GEMM *is* the MXU contraction, so there is no
# shuffle variant — requesting one takes the declared (recorded, warned)
# fallback instead of a silent rewrite.
# --------------------------------------------------------------------------

REGISTRY.register("gemm", IsaMode.ABSTRACT,
                  functools.partial(gemm, mode="abstract"),
                  contract=ABSTRACT_CONTRACT,
                  cost=functools.partial(structural_cost, mode="abstract"))
REGISTRY.register("gemm", IsaMode.NATIVE,
                  functools.partial(gemm, mode="native"),
                  contract=NATIVE_CONTRACT,
                  cost=functools.partial(structural_cost, mode="native"))
REGISTRY.register("gemm", IsaMode.LIBRARY,
                  functools.partial(gemm, mode="library"),
                  cost=functools.partial(structural_cost, mode="library"))
REGISTRY.declare_fallback(
    "gemm", IsaMode.ABSTRACT_SHUFFLE, IsaMode.ABSTRACT,
    reason="lane shuffle does not participate in the MXU contraction")
