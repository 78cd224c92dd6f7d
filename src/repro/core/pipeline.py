"""Shared multi-buffered pipeline planning (Eq. 1 occupancy algebra).

Every rowwise kernel in ``repro.kernels`` used to hand-roll the same
staging decisions: a hardcoded ``_BLOCK_ROWS``, ad-hoc padding, and a
per-kernel ``CompilerParams`` switch.  This module centralizes them behind
the paper's own occupancy algebra (Eq. 1, re-derived for buffers in
``Dialect.buffer_occupancy``):

    O = floor(S / (n_buffers × block_bytes))

A :class:`PipelinePlan` picks the largest block that keeps at least
``min_occupancy`` pipeline stages resident (``choose_block_bytes``),
clamped by a per-kernel latency cap, and carries the grid, the padding,
and the ``dimension_semantics`` annotation that only the *native* budget
may spend (``multi_buffering`` + ``dimension_semantics`` are native
features — see ``repro.core.primitives.NATIVE_FEATURES``).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.dialect import Dialect, TARGET
from repro.core.execution_model import choose_block_bytes

#: minimal second-minor granule of a TPU f32 tile (sublanes)
SUBLANES = 8

#: the scoped-VMEM limit the TPU compiler gives a kernel that sets none
#: (16 MiB on v5e); tiles planned against the dialect's larger VMEM
#: budget must raise it, or the compiler refuses them
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024


def compiler_params(semantics: Optional[Tuple[str, ...]] = None,
                    vmem_bytes: int = 0):
    """The ``compiler_params`` of one ``pallas_call``.

    ``semantics`` is the native budget's ``dimension_semantics`` (None
    for the abstract budgets, which claim no pipeline annotation).
    ``vmem_bytes`` is the plan's resident working set: past the
    compiler's default scoped limit the limit is raised to the target's
    VMEM budget, the size every plan was tiled against.  Kernels always
    compile for the target chip, whatever dialect chose their tiles."""
    kw = {}
    if semantics is not None:
        kw["dimension_semantics"] = semantics
    if vmem_bytes > DEFAULT_SCOPED_VMEM:
        kw["vmem_limit_bytes"] = TARGET.scratchpad_bytes
    return pltpu.CompilerParams(**kw) if kw else None


@dataclasses.dataclass(frozen=True)
class PipelinePlan:
    """Staging decision for a rowwise (grid over row-blocks) kernel."""

    block_rows: int                 # rows per grid step
    row_bytes: int                  # bytes per row of the working set
    n_buffers: int                  # DMA multi-buffer depth
    occupancy: int                  # resident block buffers under Eq. 1
    grid: Tuple[int, ...]           # 1-D grid over row-blocks
    padded_rows: int                # rows after padding to a block multiple
    mode: str                       # isa mode the plan was made for
    semantics: Tuple[str, ...]      # dimension_semantics for native mode

    @property
    def compiler_params(self):
        """Pipeline annotations are a native feature: abstract budgets get
        none (the compiler still runs, but the kernel claims nothing)."""
        if self.mode == "native":
            return compiler_params(self.semantics)
        return None

    @property
    def block_bytes(self) -> int:
        return self.block_rows * self.row_bytes


def plan_row_pipeline(total_rows: int, row_bytes: int, *, mode: str,
                      dialect: Dialect = TARGET, n_buffers: int = 2,
                      max_block_rows: Optional[int] = None,
                      min_occupancy: int = 2, pow2_blocks: bool = False,
                      semantics: Tuple[str, ...] = ("arbitrary",),
                      tuned: Optional[Mapping] = None) -> PipelinePlan:
    """Size a row-block from the dialect scratchpad budget.

    ``max_block_rows`` is the kernel's latency/tail cap (small inputs
    should not pad up to a 16 MB block just because VMEM would fit one).
    ``pow2_blocks`` rounds the block down to a power of two — required by
    kernels whose cross-lane stage tree-reduces over the block rows.

    ``tuned`` is an optional autotuner override (``repro.core.tuning``):
    a mapping with ``block_rows`` / ``n_buffers`` keys.  A tuned block
    supersedes the heuristic *and* the ``max_block_rows`` cap (the cap is
    the untuned guard; table entries are validated against the bounded
    candidate corridor by CI), but the Eq. 1 occupancy invariant and the
    problem-size/pow2 clamps still apply — an entry that would break them
    silently degrades to the heuristic point.
    """
    if total_rows <= 0 or row_bytes <= 0:
        raise ValueError("total_rows and row_bytes must be positive")
    tuned_block = None
    if tuned:
        n_buffers = int(tuned.get("n_buffers", n_buffers))
        if tuned.get("block_rows"):
            tuned_block = max(SUBLANES,
                              int(tuned["block_rows"]) // SUBLANES * SUBLANES)
    budget = choose_block_bytes(total_rows * row_bytes, dialect,
                                n_buffers=n_buffers,
                                min_occupancy=min_occupancy)
    block_rows = max(SUBLANES, (budget // row_bytes) // SUBLANES * SUBLANES)
    if max_block_rows is not None:
        block_rows = min(block_rows, max_block_rows)
    if tuned_block is not None and dialect.buffer_occupancy(
            tuned_block * row_bytes, n_buffers) >= min_occupancy:
        block_rows = tuned_block
    # never pad a small input past one block of its own (rounded) size
    rounded_total = -(-total_rows // SUBLANES) * SUBLANES
    block_rows = min(block_rows, rounded_total)
    if pow2_blocks:
        block_rows = 1 << (block_rows.bit_length() - 1)
    padded_rows = -(-total_rows // block_rows) * block_rows
    return PipelinePlan(
        block_rows=block_rows, row_bytes=row_bytes, n_buffers=n_buffers,
        occupancy=dialect.buffer_occupancy(block_rows * row_bytes, n_buffers),
        grid=(padded_rows // block_rows,), padded_rows=padded_rows,
        mode=mode, semantics=semantics)


def pad_rows(x2d: jax.Array, plan: PipelinePlan,
             constant_value=0) -> jax.Array:
    """Pad a ``(rows, d)`` array up to the plan's block multiple."""
    pad = plan.padded_rows - x2d.shape[0]
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)),
                      constant_values=constant_value)
    return x2d
