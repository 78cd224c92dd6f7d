"""Fused chunked SSD scan — the whole Mamba2 chunk algorithm in ONE grid.

`models/ssd.py::ssd_scan` is the repo's one hot spot that mixes the two
shapes the paper's invariant-primitive analysis distinguishes: the
intra-chunk quadratic form is GEMM-shaped (MXU), the inter-chunk state
recurrence is reduction-shaped.  The jnp chunk path (the library row
here) leaves every one of its contractions — `C·Bᵀ`, the decay-weighted
`w·x`, the carried-state contribution `C·h`, and the state update's
`Bᵀ·(wS·x)` — as a separate surface dot whose operands and results stage
through HBM (`scripts/audit_chunked_fusion.py --target ssd`).

This kernel runs the whole scan in one `pl.pallas_call`: grid
``(batch, head, chunk)`` with the chunk axis **sequential** and the
``[N, P]`` per-head state carried in f32 VMEM scratch across it — the
same sequential-axis-accumulator pattern `flash_attention_matmul` uses
for heads and `_paged_attention_matmul` uses for pages.  The
`[B,nc,Q,G,Hg,·]` intermediates (scores, decay weights, per-chunk state
contributions, the carried state itself) never touch HBM; the structural
cost pins that saving against the unfused six-dot sum.

The §VII.C mode distinction lives in the within-chunk decay prefix scan
(`ldec = cumsum(dt·A)`), the scan's one genuinely cross-lane stage:

- ``abstract``        — Hillis–Steele, every doubling stage staged
                        through a VMEM scratch row (store + shifted
                        reload; program order plays the barrier).
- ``abstract+shuffle``— the same stages as lane rotations
                        (`pltpu.roll`), zero scratch traffic.
- ``native``          — the target's fused `cumsum` lowering.
- ``library``         — the jnp chunk path (`ssd_scan_reference`), which
                        is also the registered fallback for ``native``
                        on foreign dialects.

``ssd_decode`` (ISSUE 9) is the decode-side twin: ONE Pallas kernel
batching the one-token recurrence (``h ← exp(dt·A)·h + dt·B⊗x``,
``y = C·h``) across the whole serve batch, grid ``(batch-tile, head)``
with each program's ``[N, P]`` state slice resident in VMEM for the
tick.  The jnp einsum trio (the library row, ``ssd_decode_reference``)
round-trips the ``[B,G,Hg,N,P]``-sized update tensor through HBM per
layer per token; the fused kernel's stream is the operand/result IO
alone.  The §VII.C mode split lives in the cross-lane ``C·h``
contraction over N: abstract stages a scratch-tree reduce, shuffle runs
the lane rotate tree, native issues one MXU dot.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import (IsaMode, KernelContract, Primitive, REGISTRY)
from repro.core.pipeline import compiler_params
from repro.core.shuffle import (LANES, lane_shuffle_up, lane_tree_reduce,
                                scratch_tree_reduce)
from repro.core.tuning import (active_dialect, register_op_space,
                               ssd_bucket, ssd_candidates,
                               ssd_decode_bucket, ssd_decode_candidates,
                               tuned_entry)

__all__ = ["fused_ssd_scan", "ssd_scan_reference", "resolve_chunk",
           "structural_cost_ssd_scan", "fused_ssd_decode",
           "ssd_decode_reference", "resolve_decode_block",
           "structural_cost_ssd_decode"]


# ---------------------------------------------------------------------------
# Library reference: the jnp chunk path (moved from models/ssd.py so the
# registry's library row and the model wrapper share one implementation).
# ---------------------------------------------------------------------------


def ssd_scan_reference(x, dt, A, B_mat, C_mat, chunk: int,
                       initial_state: Optional[jax.Array] = None,
                       state_hook=None):
    """Chunked SSD, jnp end to end (the unfused six-dot program).

    x:     [B, L, H, P]   (H heads of dim P)
    dt:    [B, L, H]      (positive step sizes)
    A:     [H]            (negative)
    B_mat: [B, L, G, N]
    C_mat: [B, L, G, N]
    Returns y [B, L, H, P] and final state [B, G, Hg, N, P] (Hg = H // G).

    ``state_hook`` (optional) is applied to the carried state inside the
    scan body — models/ssd.py threads its sharding constraint through it
    so the [B,G,Hg,N,P] carry stays placed under a mesh.
    """
    b, l, h, p = x.shape
    g, n = B_mat.shape[2], B_mat.shape[3]
    hg = h // g
    chunk = min(chunk, l)
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)) + ((0, 0),))
        B_mat = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_mat = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    lp = x.shape[1]
    nc = lp // chunk

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, g, hg, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, g, hg)
    Bf = B_mat.astype(jnp.float32).reshape(b, nc, chunk, g, n)
    Cf = C_mat.astype(jnp.float32).reshape(b, nc, chunk, g, n)
    dA = dtf * A.reshape(g, hg)                       # [B,nc,Q,G,Hg] (<=0)
    ldec = jnp.cumsum(dA, axis=2)                     # inclusive within chunk

    if initial_state is None:
        h0 = jnp.zeros((b, g, hg, n, p), jnp.float32)
    else:
        h0 = initial_state.astype(jnp.float32)

    causal = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])

    def body(state, inp):
        xq, dtq, ldq, Bq, Cq = inp                    # leading axis: nc
        # ---- intra-chunk (quadratic / 'attention' form) ----
        gts = jnp.einsum("bqgn,bsgn->bgqs", Cq, Bq)   # [B,G,Qt,Qs]
        diff = ldq[:, :, None] - ldq[:, None]         # [B,Qt,Qs,G,Hg]
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None],
                                  diff, -jnp.inf))
        w = decay * jnp.moveaxis(gts, 1, 3)[..., None] \
            * dtq[:, None]                            # [B,Qt,Qs,G,Hg]
        y = jnp.einsum("bqsgh,bsghp->bqghp", w, xq)
        # ---- contribution of carried state ----
        y += jnp.einsum("bqgn,bghnp->bqghp", Cq, state) \
            * jnp.exp(ldq)[..., None]
        # ---- state update ----
        total = ldq[:, -1]                            # [B,G,Hg]
        wS = dtq * jnp.exp(total[:, None] - ldq)      # [B,Q,G,Hg]
        s_c = jnp.einsum("bsgn,bsgh,bsghp->bghnp", Bq, wS, xq)
        state = jnp.exp(total)[..., None, None] * state + s_c
        if state_hook is not None:
            state = state_hook(state)
        return state, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xf, dtf, ldec, Bf, Cf))
    final_state, ys = jax.lax.scan(body, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, lp, h, p)[:, :l]
    return y.astype(x.dtype), final_state


def ssd_decode_reference(state, x_t, dt_t, A, B_t, C_t):
    """One-token recurrence, jnp end to end (the unfused einsum trio).

    state: [B,G,Hg,N,P] (any float dtype; carried in f32)
    x_t:   [B,H,P]; dt_t: [B,H]; A: [H]; B_t/C_t: [B,G,N].
    Returns ``(new_state f32 [B,G,Hg,N,P], y [B,H,P] in x_t's dtype)`` —
    the registry's library row for ``ssd_decode`` and the math
    ``models/ssd.py::ssd_decode_step`` delegates to.
    """
    b, g, hg, n, p = state.shape
    xf = x_t.astype(jnp.float32).reshape(b, g, hg, p)
    dtf = dt_t.astype(jnp.float32).reshape(b, g, hg)
    da = jnp.exp(dtf * A.astype(jnp.float32).reshape(g, hg))  # [B,G,Hg]
    upd = jnp.einsum("bgn,bgh,bghp->bghnp", B_t.astype(jnp.float32),
                     dtf, xf)
    state = da[..., None, None] * state.astype(jnp.float32) + upd
    y = jnp.einsum("bgn,bghnp->bghp", C_t.astype(jnp.float32), state)
    return state, y.reshape(b, g * hg, p).astype(x_t.dtype)


# ---------------------------------------------------------------------------
# Chunk resolution: explicit wins, then the tuned table, then the ranked
# candidate grid's structural winner (one source of truth with autotune).
# ---------------------------------------------------------------------------


def resolve_chunk(mode: str, seq: int, p: int, n: int,
                  chunk: Optional[int] = None,
                  plan_dialect: Optional[str] = None,
                  op: str = "ssd_scan") -> int:
    """The effective chunk length: never longer than the sequence."""
    if chunk is not None:
        return max(1, min(int(chunk), seq))
    entry = tuned_entry(op, mode, ssd_bucket(seq, p, n), plan_dialect)
    if entry and "chunk" in entry:
        return max(1, min(int(entry["chunk"]), seq))
    cands = ssd_candidates(seq, p, n, active_dialect(plan_dialect))
    return max(1, min(int(cands[0]["chunk"]), seq))


def resolve_decode_block(mode: str, b: int, p: int, n: int,
                         block_b: Optional[int] = None,
                         plan_dialect: Optional[str] = None,
                         op: str = "ssd_decode") -> int:
    """The effective decode batch tile: never wider than the batch."""
    if block_b is not None:
        return max(1, min(int(block_b), b))
    entry = tuned_entry(op, mode, ssd_decode_bucket(b, p, n), plan_dialect)
    if entry and "block_b" in entry:
        return max(1, min(int(entry["block_b"]), b))
    cands = ssd_decode_candidates(b, p, n, active_dialect(plan_dialect))
    return max(1, min(int(cands[0]["block_b"]), b))


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def _prefix_sum(v, scratch_ref, q: int, mode: str):
    """Inclusive prefix sum over the (1, q) lane row — the scan's one
    cross-lane stage, realized per §VII.C budget.

    abstract: each Hillis–Steele doubling stage stores the partial to a
    VMEM scratch row and reloads it shifted (program order plays the
    workgroup barrier) — ceil(log2(q)) round trips.  abstract+shuffle:
    the same stages as lane rotations, zero scratch traffic.  native:
    the target's fused cumsum.
    """
    if mode == "native":
        return jnp.cumsum(v, axis=-1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    off = 1
    if mode == "abstract+shuffle":
        while off < q:
            shifted = lane_shuffle_up(v, off, axis=-1)
            v = v + jnp.where(idx >= off, shifted, 0.0)
            off *= 2
        return v
    # abstract: the shuffle-free realization — stage through scratch
    while off < q:
        scratch_ref[...] = v                          # store | barrier
        shifted = jnp.concatenate(
            [jnp.zeros((1, off), jnp.float32),
             scratch_ref[:, :q - off]], axis=1)       # shifted reload
        v = v + shifted
        off *= 2
    return v


def _ssd_scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                     y_ref, hf_ref, state_ref, pscan_ref, *,
                     q: int, n_chunks: int, mode: str):
    """One (batch, head, chunk) step; state carried in VMEM across cc."""
    cc = pl.program_id(2)

    xq = x_ref[0, 0].astype(jnp.float32)              # [Q, P]
    dtq = dt_ref[0].astype(jnp.float32)               # [1, Q]
    a = a_ref[0, 0].astype(jnp.float32)               # scalar (negative)
    Bq = b_ref[0, 0].astype(jnp.float32)              # [Q, N]
    Cq = c_ref[0, 0].astype(jnp.float32)              # [Q, N]

    ld = _prefix_sum(dtq * a, pscan_ref, q, mode)     # [1, Q] inclusive
    ld_col = ld.reshape(q, 1)

    @pl.when(cc == 0)
    def _seed_state():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    state = state_ref[...]                            # [N, P] pre-update

    # ---- intra-chunk quadratic form (MXU) ----
    gts = jax.lax.dot_general(Cq, Bq, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Q,Q]
    qi = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.exp(jnp.where(qi >= si, ld_col - ld, -jnp.inf))
    w = decay * gts * dtq                             # w[t,s] ∝ dt[s]
    y = jax.lax.dot_general(w, xq, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # [Q,P]
    # ---- carried-state contribution ----
    y = y + jax.lax.dot_general(Cq, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * jnp.exp(ld_col)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # ---- inter-chunk state update (the recurrence) ----
    total = ld[0, q - 1]
    wS = dtq * jnp.exp(total - ld)                    # [1, Q]
    s_c = jax.lax.dot_general(Bq * wS.reshape(q, 1), xq,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [N,P]
    state_ref[...] = jnp.exp(total) * state + s_c

    @pl.when(cc == n_chunks - 1)
    def _emit_state():
        hf_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "chunk", "mode", "interpret", "plan_dialect", "tuning_op"))
def fused_ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
                   B_mat: jax.Array, C_mat: jax.Array,
                   initial_state: Optional[jax.Array] = None, *,
                   chunk: Optional[int] = None, mode: str = "native",
                   interpret: bool = True,
                   plan_dialect: Optional[str] = None,
                   tuning_op: str = "ssd_scan"):
    """The whole chunked SSD scan as one Pallas kernel.

    Same signature contract as :func:`ssd_scan_reference`: returns the
    identical ``(y [B,L,H,P], final_state f32 [B,G,Hg,N,P])`` pair, so
    the final state seeds the decode recurrence unchanged.  ``chunk``
    ``None`` defers to the tuned table (then the candidate grid) via
    :func:`resolve_chunk`; explicit values pin.  ``initial_state`` rides
    in as a kernel input — hybrid prefill-with-state seeds the VMEM
    carry at the first chunk step.
    """
    b, l, h, p = x.shape
    g, n = B_mat.shape[2], B_mat.shape[3]
    hg = h // g
    q = resolve_chunk(mode, l, p, n, chunk, plan_dialect, op=tuning_op)
    if mode == "library":
        return ssd_scan_reference(x, dt, A, B_mat, C_mat, q,
                                  initial_state=initial_state)
    if initial_state is None:
        h0 = jnp.zeros((b, g, hg, n, p), jnp.float32)
    else:
        h0 = initial_state.astype(jnp.float32)

    # head-major layouts: every grid program owns one (batch, head) lane
    xh = jnp.moveaxis(x, 1, 2)                        # [B, H, L, P]
    dth = jnp.moveaxis(dt, 1, 2)                      # [B, H, L]
    Bh = jnp.moveaxis(B_mat, 1, 2)                    # [B, G, L, N]
    Ch = jnp.moveaxis(C_mat, 1, 2)
    pad = (-l) % q
    if pad:
        # zero dt kills every padded position's contribution (w, wS ∝ dt)
        xh = jnp.pad(xh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dth = jnp.pad(dth, ((0, 0), (0, 0), (0, pad)))
        Bh = jnp.pad(Bh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // q
    a2 = A.astype(jnp.float32).reshape(h, 1)
    h0h = h0.reshape(b, h, n, p)

    grid = (b, h, nc)                                 # chunk axis last
    params = compiler_params(("parallel", "parallel", "arbitrary")
                             if mode == "native" else None)

    y, hf = pl.pallas_call(
        functools.partial(_ssd_scan_kernel, q=q, n_chunks=nc, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, q), lambda bb, hh, cc: (bb, hh, cc)),
            pl.BlockSpec((1, 1), lambda bb, hh, cc: (hh, 0)),
            pl.BlockSpec((1, 1, q, n),
                         lambda bb, hh, cc, g_=hg: (bb, hh // g_, cc, 0)),
            pl.BlockSpec((1, 1, q, n),
                         lambda bb, hh, cc, g_=hg: (bb, hh // g_, cc, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bb, hh, cc: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lp, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, p), jnp.float32),          # carried state
            pltpu.VMEM((1, q) if mode == "abstract" else (1, 8),
                       jnp.float32),                  # prefix-scan stage
        ],
        compiler_params=params,
        interpret=interpret,
        name=f"uisa_ssd_scan_{mode.replace('+', '_')}",
    )(xh, dth, a2, Bh, Ch, h0h)
    return (jnp.moveaxis(y, 1, 2)[:, :l],
            hf.reshape(b, g, hg, n, p))


def _ssd_scan_library(x, dt, A, B_mat, C_mat, initial_state=None, *,
                      chunk=None, interpret=None, plan_dialect=None,
                      tuning_op: str = "ssd_scan"):
    """jnp chunk-path reference (the unfused six-dot row of Table V).

    ``tuning_op`` threads through to :func:`resolve_chunk` exactly like
    ``fused_ssd_scan``'s static argname does (ISSUE 9 bug fix: the call
    used to drop ``op=``, so with a second ssd op space in the table a
    library fallback would resolve its chunk from the wrong slice).
    """
    del interpret
    q = resolve_chunk("library", x.shape[1], x.shape[3], B_mat.shape[3],
                      chunk, plan_dialect, op=tuning_op)
    return ssd_scan_reference(x, dt, A, B_mat, C_mat, q,
                              initial_state=initial_state)


# ---------------------------------------------------------------------------
# The decode kernel: one-token recurrence batched across the serve batch
# ---------------------------------------------------------------------------


def _ssd_decode_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                       y_ref, hf_ref, red_ref, *, bb: int, n: int, p: int,
                       mode: str):
    """One (batch-tile, head) program: ``bb`` slots' [N,P] states updated
    in VMEM, then the cross-lane ``C·h`` contraction per §VII.C budget."""
    x = x_ref[:, 0].astype(jnp.float32)               # [bb, P]
    dt = dt_ref[...].astype(jnp.float32)              # [bb, 1]
    a = a_ref[0, 0].astype(jnp.float32)               # scalar (negative)
    Bv = b_ref[:, 0].astype(jnp.float32)              # [bb, N]
    Cv = c_ref[:, 0].astype(jnp.float32)              # [bb, N]
    h0 = h0_ref[:, 0].astype(jnp.float32)             # [bb, N, P]

    # the recurrence: decay + rank-1 update, all register/VMEM arithmetic
    da = jnp.exp(dt * a)                              # [bb, 1]
    state = da[..., None] * h0 \
        + (dt * Bv)[..., None] * x[:, None, :]        # [bb, N, P]
    hf_ref[:, 0] = state

    # cross-lane stage: y[p] = sum_n C[n] * state[n, p], per slot
    if mode == "native":
        y = jax.lax.dot_general(Cv, state, (((1,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
    else:
        u = Cv[..., None] * state                     # [bb, N, P]
        rows = []
        for i in range(bb):                           # static unroll
            if mode == "abstract+shuffle":
                # N in lanes: log2(N) rotate tree, zero scratch traffic
                red = lane_tree_reduce(u[i].T, axis=-1)
                rows.append(red[:, :1].T)             # [1, P]
            else:
                # abstract: halving stages through the VMEM scratch ref,
                # program order playing the workgroup barrier
                rows.append(scratch_tree_reduce(u[i], red_ref, axis=0))
        y = jnp.concatenate(rows, axis=0)             # [bb, P]
    y_ref[:, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_b", "mode", "interpret", "plan_dialect", "tuning_op"))
def fused_ssd_decode(state: jax.Array, x_t: jax.Array, dt_t: jax.Array,
                     A: jax.Array, B_t: jax.Array, C_t: jax.Array, *,
                     block_b: Optional[int] = None, mode: str = "native",
                     interpret: bool = True,
                     plan_dialect: Optional[str] = None,
                     tuning_op: str = "ssd_decode"):
    """The batched one-token SSD recurrence as one Pallas kernel.

    Same signature contract as :func:`ssd_decode_reference`: returns the
    identical ``(new_state f32 [B,G,Hg,N,P], y [B,H,P])`` pair, so the
    serve tick's cache carry is unchanged.  Grid ``(batch-tile, head)``
    with each program's ``[bb, N, P]`` state slice resident in VMEM for
    the tick — the jnp path's ``[B,G,Hg,N,P]`` update tensor never
    stages through HBM.  ``block_b`` ``None`` defers to the tuned table
    (then the candidate grid) via :func:`resolve_decode_block`; explicit
    values pin.  N must be a power of two for the non-native tree
    reduces (every registered mamba2 state width is).
    """
    b, g, hg, n, p = state.shape
    h = g * hg
    bb = resolve_decode_block(mode, b, p, n, block_b, plan_dialect,
                              op=tuning_op)
    if mode == "library":
        return ssd_decode_reference(state, x_t, dt_t, A, B_t, C_t)

    h0h = state.astype(jnp.float32).reshape(b, h, n, p)
    a2 = A.astype(jnp.float32).reshape(h, 1)
    pad = (-b) % bb
    if pad:
        # zero dt/x/B kill the padded slots' update (their state rows are
        # zeros and sliced off before return)
        h0h = jnp.pad(h0h, ((0, pad),) + ((0, 0),) * 3)
        x_t = jnp.pad(x_t, ((0, pad), (0, 0), (0, 0)))
        dt_t = jnp.pad(dt_t, ((0, pad), (0, 0)))
        B_t = jnp.pad(B_t, ((0, pad), (0, 0), (0, 0)))
        C_t = jnp.pad(C_t, ((0, pad), (0, 0), (0, 0)))
    bp = b + pad

    grid = (bp // bb, h)
    params = compiler_params(("parallel", "parallel")
                             if mode == "native" else None)

    y, hf = pl.pallas_call(
        functools.partial(_ssd_decode_kernel, bb=bb, n=n, p=p, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1, p), lambda bi, hh: (bi, hh, 0)),
            pl.BlockSpec((bb, 1), lambda bi, hh: (bi, hh)),
            pl.BlockSpec((1, 1), lambda bi, hh: (hh, 0)),
            pl.BlockSpec((bb, 1, n),
                         lambda bi, hh, g_=hg: (bi, hh // g_, 0)),
            pl.BlockSpec((bb, 1, n),
                         lambda bi, hh, g_=hg: (bi, hh // g_, 0)),
            pl.BlockSpec((bb, 1, n, p), lambda bi, hh: (bi, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1, p), lambda bi, hh: (bi, hh, 0)),
            pl.BlockSpec((bb, 1, n, p), lambda bi, hh: (bi, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, h, p), x_t.dtype),
            jax.ShapeDtypeStruct((bp, h, n, p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, p) if mode == "abstract" else (1, 8),
                       jnp.float32),                  # tree-reduce stage
        ],
        compiler_params=params,
        interpret=interpret,
        name=f"uisa_ssd_decode_{mode.replace('+', '_')}",
    )(x_t, dt_t, a2, B_t, C_t, h0h)
    return hf[:b].reshape(b, g, hg, n, p), y[:b]


def _ssd_decode_library(state, x_t, dt_t, A, B_t, C_t, *, block_b=None,
                        interpret=None, plan_dialect=None):
    """jnp einsum-trio reference — the per-layer state round trip row."""
    del block_b, interpret, plan_dialect
    return ssd_decode_reference(state, x_t, dt_t, A, B_t, C_t)


# ---------------------------------------------------------------------------
# Structural cost: fused stream vs the unfused six-dot boundary traffic
# ---------------------------------------------------------------------------


def _scan_stages(q: int) -> int:
    """Hillis–Steele doubling stages of a ``q``-wide inclusive scan."""
    return int(math.ceil(math.log2(q))) if q > 1 else 0


def _scan_scratch_bytes(q: int, itemsize: int = 4) -> int:
    """Scratch traffic of one abstract prefix scan: stage ``k`` stores
    the full ``q`` row and reloads ``q - 2^k`` shifted lanes."""
    return sum((q + (q - (1 << k))) * itemsize
               for k in range(_scan_stages(q)))


def structural_cost_ssd_scan(b: int, seq: int, h: int, p: int, g: int,
                             n: int, mode: str,
                             chunk: Optional[int] = None,
                             dtype=jnp.float32,
                             plan_dialect: Optional[str] = None) -> dict:
    """Fused stream traffic vs the unfused chunk path's six-dot sum.

    ``hbm_bytes_unfused_pair`` is what the jnp chunk program stages: the
    operand/result stream **plus** every per-chunk intermediate the six
    separate contractions round-trip through HBM — the `[B,nc,G,Q,Q]`
    scores, the `[B,nc,Q,Q,G,Hg]` decay weights, the `[B,nc,Q,G,Hg]`
    decay rows (ldec, wS), the `[B,nc,Q,G,Hg,P]` carried-state
    contribution, the per-chunk `[G,Hg,N,P]` state updates, and the
    carried state itself between chunks.  The fused kernel keeps all of
    them in VMEM (the state in scratch across the sequential chunk
    axis), so its ``hbm_bytes`` is the operand/result stream alone —
    the identity ``hbm_bytes == hbm_bytes_unfused_pair -
    hbm_bytes_saved`` is validated by scripts/validate_contracts.py.

    The scratch columns account only the §VII.C cross-lane mechanism
    (the decay prefix scan), exactly like attention's cost model: the
    VMEM-resident state is pipelining, not barrier traffic, and keeping
    it out of the columns keeps the declared fallbacks never-cheaper.
    """
    q = resolve_chunk(mode, seq, p, n, chunk, plan_dialect)
    nc = -(-seq // q)
    lp = nc * q
    hg = max(1, h // g)
    itemsize = jnp.dtype(dtype).itemsize
    f32 = 4
    # fused operand/result stream (read x/dt/B/C/A/h0 once, write y + hf)
    io = (b * lp * h * p * itemsize                   # x read
          + b * lp * h * itemsize                     # dt read
          + 2 * b * lp * g * n * itemsize             # B + C reads
          + h * f32                                   # A
          + b * h * n * p * f32                       # h0 read
          + b * lp * h * p * itemsize                 # y write
          + b * h * n * p * f32)                      # final state write
    # per-chunk intermediates the unfused six-dot program materializes
    inter = (b * nc * g * q * q * f32                 # gts scores
             + b * nc * q * q * g * hg * f32          # decay weights w
             + 2 * b * nc * q * g * hg * f32          # ldec + wS rows
             + b * nc * q * g * hg * p * f32          # C·h contribution
             + b * nc * g * hg * n * p * f32          # s_c per chunk
             + b * nc * g * hg * n * p * f32)         # carried state trip
    pair = io + 2 * inter                             # write + read back
    saved = 0 if mode == "library" else 2 * inter
    flops = b * h * nc * (2 * q * q * n               # C·Bᵀ
                          + 2 * q * q * p             # w·x
                          + 2 * q * n * p             # C·h
                          + 2 * q * n * p)            # Bᵀ·(wS·x)
    stages = _scan_stages(q)
    if mode == "abstract":
        round_trips = stages
        scratch_bytes = b * h * nc * _scan_scratch_bytes(q)
        shuffles = 0
    elif mode == "abstract+shuffle":
        round_trips = 0
        scratch_bytes = 0
        shuffles = stages
    else:                                             # native / library
        round_trips = 0
        scratch_bytes = 0
        shuffles = 0
    return {
        "hbm_bytes": pair - saved,
        "hbm_bytes_unfused_pair": pair,
        "hbm_bytes_saved": saved,
        "flops": flops,
        "chunk": q,
        "n_chunks": nc,
        "blocks_visited": b * h * nc,
        "state_bytes_resident": n * p * f32,          # the VMEM carry
        "scratch_round_trips_per_block": round_trips,
        "scratch_bytes_total": scratch_bytes,
        "lane_shuffles_per_block": shuffles,
        "fused_epilogue": mode != "library",
    }


def structural_cost_ssd_decode(b: int, h: int, p: int, g: int, n: int,
                               mode: str,
                               block_b: Optional[int] = None,
                               dtype=jnp.float32,
                               plan_dialect: Optional[str] = None) -> dict:
    """Fused decode-tick traffic vs the unfused einsum trio's round trip.

    ``hbm_bytes_unfused_pair`` is what the jnp recurrence stages per
    layer per token: the operand/result stream (the state itself must
    round-trip HBM once per tick either way — it lives in the decode
    cache between ticks) **plus** the intermediates the separate einsums
    materialize — the ``[B,G,Hg,N,P]`` update tensor ``dt·B⊗x`` (a full
    state-sized HBM round trip, the §VII.C tail-latency tax ISSUE 9
    removes) and the ``[B,G,Hg]`` decay row.  The fused kernel keeps
    both in VMEM, so its ``hbm_bytes`` is the operand/result stream
    alone; the identity ``hbm_bytes == hbm_bytes_unfused_pair -
    hbm_bytes_saved`` is validated by scripts/validate_contracts.py.

    The scratch columns account only the cross-lane ``C·h`` contraction
    (the §VII.C mechanism): a log2(N) tree per slot, staged through VMEM
    in abstract mode and through lane rotations in shuffle mode.
    """
    bb = resolve_decode_block(mode, b, p, n, block_b, plan_dialect)
    itemsize = jnp.dtype(dtype).itemsize
    f32 = 4
    # fused operand/result stream (read x/dt/A/B/C + the cached state,
    # write y + the updated state — the cache round trip both paths pay)
    io = (b * h * p * itemsize                        # x_t read
          + b * h * itemsize                          # dt read
          + h * f32                                   # A
          + 2 * b * g * n * itemsize                  # B_t + C_t reads
          + b * h * n * p * f32                       # state read (cache)
          + b * h * n * p * f32                       # state write (cache)
          + b * h * p * itemsize)                     # y write
    # intermediates the unfused einsum trio materializes per layer/token
    inter = (b * h * n * p * f32                      # dt·B⊗x update tensor
             + b * h * f32)                           # exp(dt·A) decay row
    pair = io + 2 * inter                             # write + read back
    saved = 0 if mode == "library" else 2 * inter
    flops = b * h * (2 * n * p                        # decay scale + add
                     + 2 * n * p                      # rank-1 update
                     + 2 * n * p)                     # C·h contraction
    stages = _scan_stages(n)
    blocks = -(-b // bb) * h
    if mode == "abstract":
        round_trips = bb * stages
        # per tree: stage k reads two (n >> k, P) slices and writes one
        per_tree = p * sum(3 * (n >> k) * f32
                           for k in range(1, stages + 1))
        scratch_bytes = blocks * bb * per_tree
        shuffles = 0
    elif mode == "abstract+shuffle":
        round_trips = 0
        scratch_bytes = 0
        shuffles = bb * stages
    else:                                             # native / library
        round_trips = 0
        scratch_bytes = 0
        shuffles = 0
    return {
        "hbm_bytes": pair - saved,
        "hbm_bytes_unfused_pair": pair,
        "hbm_bytes_saved": saved,
        "flops": flops,
        "block_b": bb,
        "blocks_visited": blocks,
        "state_bytes_resident": bb * n * p * f32,     # the VMEM residency
        "scratch_round_trips_per_block": round_trips,
        "scratch_bytes_total": scratch_bytes,
        "lane_shuffles_per_block": shuffles,
        "fused_epilogue": mode != "library",
    }


# ---------------------------------------------------------------------------
# Contracts + registration (the full IsaMode matrix, six dialects)
# ---------------------------------------------------------------------------

_SSD_ABSTRACT = KernelContract(
    kernel="ssd_scan", mode=IsaMode.ABSTRACT,
    primitives=frozenset({
        Primitive.LOCKSTEP_GROUP, Primitive.MASKED_DIVERGENCE,
        Primitive.MANAGED_SCRATCHPAD, Primitive.WORKGROUP_BARRIER,
        Primitive.HIERARCHICAL_MEMORY, Primitive.IDENTITY_REGISTERS,
        Primitive.ASYNC_MEMORY, Primitive.REGISTER_OCCUPANCY,
    }))
_SSD_SHUFFLE = KernelContract(
    kernel="ssd_scan", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_SSD_ABSTRACT.primitives | {Primitive.LANE_SHUFFLE})
_SSD_NATIVE = KernelContract(
    kernel="ssd_scan", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"fused_epilogue", "mxu_aligned_tiles",
                               "dimension_semantics", "multi_buffering"}))

register_op_space("ssd_scan", "ssd")

for _mode, _contract in (("abstract", _SSD_ABSTRACT),
                         ("abstract+shuffle", _SSD_SHUFFLE),
                         ("native", _SSD_NATIVE)):
    REGISTRY.register("ssd_scan", _mode,
                      functools.partial(fused_ssd_scan, mode=_mode),
                      contract=_contract,
                      cost=functools.partial(structural_cost_ssd_scan,
                                             mode=_mode))
REGISTRY.register("ssd_scan", IsaMode.LIBRARY, _ssd_scan_library,
                  cost=functools.partial(structural_cost_ssd_scan,
                                         mode="library"))
REGISTRY.declare_fallback(
    "ssd_scan", IsaMode.ABSTRACT_SHUFFLE, IsaMode.ABSTRACT,
    reason="no lane shuffle: decay prefix scan stages through the VMEM "
           "scratch tree instead (§VII.C)")
REGISTRY.declare_fallback(
    "ssd_scan", IsaMode.NATIVE, IsaMode.LIBRARY,
    reason="fused native chunk scan is target-pinned; the declared escape "
           "is the unfused jnp chunk path")

_SSDD_ABSTRACT = KernelContract(
    kernel="ssd_decode", mode=IsaMode.ABSTRACT,
    primitives=_SSD_ABSTRACT.primitives)
_SSDD_SHUFFLE = KernelContract(
    kernel="ssd_decode", mode=IsaMode.ABSTRACT_SHUFFLE,
    primitives=_SSD_ABSTRACT.primitives | {Primitive.LANE_SHUFFLE})
_SSDD_NATIVE = KernelContract(
    kernel="ssd_decode", mode=IsaMode.NATIVE,
    primitives=frozenset(Primitive),
    native_features=frozenset({"fused_epilogue", "mxu_aligned_tiles",
                               "dimension_semantics", "multi_buffering"}))

register_op_space("ssd_decode", "ssd_decode")

for _mode, _contract in (("abstract", _SSDD_ABSTRACT),
                         ("abstract+shuffle", _SSDD_SHUFFLE),
                         ("native", _SSDD_NATIVE)):
    REGISTRY.register("ssd_decode", _mode,
                      functools.partial(fused_ssd_decode, mode=_mode),
                      contract=_contract,
                      cost=functools.partial(structural_cost_ssd_decode,
                                             mode=_mode))
REGISTRY.register("ssd_decode", IsaMode.LIBRARY, _ssd_decode_library,
                  cost=functools.partial(structural_cost_ssd_decode,
                                         mode="library"))
REGISTRY.declare_fallback(
    "ssd_decode", IsaMode.ABSTRACT_SHUFFLE, IsaMode.ABSTRACT,
    reason="no lane shuffle: the C·h contraction reduces through the VMEM "
           "scratch tree instead (§VII.C)")
REGISTRY.declare_fallback(
    "ssd_decode", IsaMode.NATIVE, IsaMode.LIBRARY,
    reason="batched native decode recurrence is target-pinned; the declared "
           "escape is the unfused jnp einsum trio")
