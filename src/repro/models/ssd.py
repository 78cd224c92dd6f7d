"""Mamba2 / SSD (state-space duality) blocks — arXiv:2405.21060.

Chunked SSD algorithm: within a chunk the recurrence is expanded into an
attention-like quadratic form (the 'duality'); across chunks a linear
recurrence carries the (N×P) state.  We scan over chunks so peak memory is
one chunk's quadratic term, and the final carry doubles as the decode/
prefill cache state.

Decode is the pure recurrence: h <- exp(dt·A)·h + dt·B⊗x, y = C·h + D·x.

The UISA connection (DESIGN.md §5): the intra-chunk term is a GEMM-shaped
hot-spot (MXU), the cross-chunk state update is a reduction-shaped one —
the shuffle-vs-barrier tradeoff of kernels/reduction.py applies inside the
chunk reduction.  Attention kernels are inapplicable to this family.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.registry import LIBRARY_POLICY, resolve_policy
from repro.kernels import ssd as kernel_ssd
from repro.models import common
from repro.models.config import ModelConfig, SSMConfig
from repro.parallel.sharding import ShardCtx, shard


class SSMState(NamedTuple):
    """Decode cache for one scanned stack of mamba blocks."""

    h: jax.Array          # [layers, B, G, Hg, N, P] ssm state
    conv: jax.Array       # [layers, B, W-1, conv_dim] conv tap history


def conv_dim(cfg: SSMConfig, d_model: int) -> int:
    d_inner = cfg.expand * d_model
    return d_inner + 2 * cfg.n_groups * cfg.state_dim


#: the input projection's three parts, stored apart: at d_inner 4096,
#: d_state 128 and 64 heads the whole would be 8512 columns, which the TPU
#: lays out transposed and a decode step would relay out every call
IN_PARTS = ("in_z", "in_xbc", "in_dt")


def init_mamba_block(key, d_model: int, cfg: SSMConfig, dtype):
    d_inner = cfg.expand * d_model
    nh = d_inner // cfg.head_dim
    cdim = conv_dim(cfg, d_model)
    ks = jax.random.split(key, 6)
    # x @ [z | x B C | dt], drawn whole and stored as its three parts
    gn2 = 2 * cfg.n_groups * cfg.state_dim
    in_proj = common.dense_init(ks[0], (d_model, 2 * d_inner + gn2 + nh),
                                0, dtype)
    params = {
        **dict(zip(IN_PARTS, jnp.split(
            in_proj, [d_inner, 2 * d_inner + gn2], axis=-1))),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, cdim))
                   * (cfg.conv_width ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((cdim,), dtype),
        "dt_bias": jnp.log(jnp.exp(
            jnp.linspace(cfg.dt_min, cfg.dt_max, nh)) - 1.0
        ).astype(jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "out_proj": common.dense_init(ks[5], (d_inner, d_model), 0, dtype),
    }
    specs = {
        **{k: ("embed", "ssm_inner") for k in IN_PARTS},
        "conv_w": ("conv_width", "ssm_inner"),
        "conv_b": ("ssm_inner",),
        "dt_bias": ("ssm_heads",),
        "A_log": ("ssm_heads",),
        "D": ("ssm_heads",),
        "norm_scale": ("ssm_inner",),
        "out_proj": ("ssm_inner", "embed"),
    }
    return params, specs


def _causal_conv(x, w, b):
    """Depthwise causal conv. x: [B,L,C]; w: [W,C]; b: [C].

    Returns f32: the silu that always follows must run in f32 on both the
    prefill and decode paths (decode already did; prefill used to cast to
    the storage dtype *before* the silu, so the same token picked up
    numerically different activations per path — the ISSUE 9 precision
    drift).  The caller applies the one cast back to storage dtype after
    the activation.
    """
    width, c = w.shape
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w[:, None, :].astype(jnp.float32),
        window_strides=(1,), padding=[(width - 1, 0)],
        dimension_numbers=("NHC", "HIO", "NHC"), feature_group_count=c)
    return out + b.astype(jnp.float32)


def ssd_scan(x, dt, A, B_mat, C_mat, chunk: int,
             initial_state: Optional[jax.Array] = None,
             ctx: Optional[ShardCtx] = None):
    """Chunked SSD — the unfused jnp chunk path.

    x:     [B, L, H, P]   (H heads of dim P)
    dt:    [B, L, H]      (positive step sizes)
    A:     [H]            (negative)
    B_mat: [B, L, G, N]
    C_mat: [B, L, G, N]
    Returns y [B, L, H, P] and final state [B, G, Hg, N, P] (Hg = H // G).

    The chunk math lives in ``kernels/ssd.py::ssd_scan_reference`` (also
    the lowering registry's library row for ``ssd_scan``); this wrapper
    threads the mesh placement: ``ctx`` pins the carried [B,G,Hg,N,P]
    state to its logical axes inside the scan body, so a sharded prefill
    keeps the carry resident on the heads axis instead of letting GSPMD
    re-derive its placement per chunk step.
    """
    hook = None
    if ctx is not None:
        def hook(state):
            return shard(state, ("act_batch", None, "act_ssm_heads",
                                 "act_ssm_state", None), ctx)
    return kernel_ssd.ssd_scan_reference(x, dt, A, B_mat, C_mat, chunk,
                                         initial_state=initial_state,
                                         state_hook=hook)


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """One-token recurrence — the unfused jnp einsum trio.

    state: [B,G,Hg,N,P]; x_t: [B,H,P]; dt_t: [B,H]; B_t/C_t: [B,G,N].
    The math lives in ``kernels/ssd.py::ssd_decode_reference`` (also the
    lowering registry's library row for ``ssd_decode``).
    """
    return kernel_ssd.ssd_decode_reference(state, x_t, dt_t, A, B_t, C_t)


def gated_rmsnorm(y, z, weight, eps: float, n_groups: int, policy=None):
    """Mamba-2's gated norm: ``y * silu(z)`` in float32, RMS-normalised
    over each of the ``n_groups`` groups of ``d_inner / n_groups``
    channels, times ``weight``.  Returns float32."""
    h = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if n_groups == 1:
        return common.rmsnorm(h, weight, eps, policy=policy)
    hg = h.reshape(h.shape[:-1] + (n_groups, h.shape[-1] // n_groups))
    hg = hg * jax.lax.rsqrt(jnp.mean(hg * hg, axis=-1, keepdims=True) + eps)
    return hg.reshape(h.shape) * weight.astype(jnp.float32)


def _in_proj(params, x):
    """``x @ [z | x B C | dt]``, one matmul per stored part."""
    return tuple(jnp.einsum("...d,de->...e", x, params[k].astype(x.dtype))
                 for k in IN_PARTS)


def apply_mamba_block(params, x, cfg: SSMConfig, d_model: int,
                      eps: float, ctx: Optional[ShardCtx],
                      initial_state: Optional[jax.Array] = None,
                      return_state: bool = False, policy=None):
    """Full mamba2 block (train/prefill). x: [B,L,D] -> [B,L,D]."""
    b, l, d = x.shape
    d_inner = cfg.expand * d
    nh = d_inner // cfg.head_dim
    gn2 = 2 * cfg.n_groups * cfg.state_dim

    z, xbc_pre, dt_raw = _in_proj(params, x)
    xbc = jax.nn.silu(_causal_conv(xbc_pre, params["conv_w"],
                                   params["conv_b"])
                      ).astype(x.dtype)                # silu in f32, one cast
    xs = xbc[..., :d_inner]
    B_mat = xbc[..., d_inner:d_inner + gn2 // 2].reshape(
        b, l, cfg.n_groups, cfg.state_dim)
    C_mat = xbc[..., d_inner + gn2 // 2:].reshape(
        b, l, cfg.n_groups, cfg.state_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"])         # [B,L,H]
    A = -jnp.exp(params["A_log"])                     # [H]

    xh = xs.reshape(b, l, nh, cfg.head_dim)
    xh = shard(xh, ("act_batch", "act_seq_unsharded", "act_ssm_heads",
                    "act_ssm_state"), ctx)
    pol = resolve_policy(policy=policy, default=LIBRARY_POLICY)
    if pol.fuses():
        # kernel-routed hot spot (same gate as the attention-path fusions
        # in models/common.py): the whole chunk scan runs as one Pallas
        # grid with the carried state in VMEM scratch — the per-chunk
        # intermediates never stage through HBM.  Same (y, final_state)
        # pair, so the decode cache seed is unchanged.
        from repro.kernels import ops as kernel_ops
        y, state = kernel_ops.fused_ssd_scan(
            xh, dt, A, B_mat, C_mat, chunk=cfg.chunk_size,
            initial_state=initial_state, policy=pol.kernel())
    else:
        y, state = ssd_scan(xh, dt, A, B_mat, C_mat, cfg.chunk_size,
                            initial_state=initial_state, ctx=ctx)
    y = y + (params["D"].reshape(nh, 1)
             * xh.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(b, l, d_inner)
    y = gated_rmsnorm(y, z, params["norm_scale"], eps, cfg.n_groups,
                      policy=policy).astype(x.dtype)
    out = jnp.einsum("ble,ed->bld", y, params["out_proj"].astype(x.dtype))
    if return_state:
        conv_tail = _conv_tail(xbc_pre_conv=xbc_pre, width=cfg.conv_width)
        return out, (state, conv_tail)
    return out


def _conv_tail(xbc_pre_conv, width: int):
    """Last (width-1) pre-conv inputs — the decode conv cache seed."""
    b, l, c = xbc_pre_conv.shape
    if l >= width - 1:
        return xbc_pre_conv[:, l - (width - 1):, :]
    pad = width - 1 - l
    return jnp.pad(xbc_pre_conv, ((0, 0), (pad, 0), (0, 0)))


def mamba_decode_step(params, x_t, cfg: SSMConfig, d_model: int,
                      eps: float, state: jax.Array, conv_buf: jax.Array,
                      ctx: Optional[ShardCtx] = None, policy=None):
    """One-token mamba2 step.

    x_t: [B,D]; state: [B,G,Hg,N,P]; conv_buf: [B,W-1,conv_dim].
    Returns (y [B,D], new_state, new_conv_buf).
    """
    b, d = x_t.shape
    d_inner = cfg.expand * d
    nh = d_inner // cfg.head_dim
    gn2 = 2 * cfg.n_groups * cfg.state_dim

    z, xbc_new, dt_raw = _in_proj(params, x_t)

    window = jnp.concatenate([conv_buf, xbc_new[:, None, :]], axis=1)
    w = params["conv_w"].astype(jnp.float32)          # [W,C]
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w) \
        + params["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv_out).astype(x_t.dtype)
    new_conv_buf = window[:, 1:, :]

    xs = xbc[..., :d_inner]
    B_t = xbc[..., d_inner:d_inner + gn2 // 2].reshape(
        b, cfg.n_groups, cfg.state_dim)
    C_t = xbc[..., d_inner + gn2 // 2:].reshape(
        b, cfg.n_groups, cfg.state_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    xh = xs.reshape(b, nh, cfg.head_dim)
    pol = resolve_policy(policy=policy, default=LIBRARY_POLICY)
    if pol.fuses():
        # kernel-routed hot spot (same gate as the prefill chunk scan
        # above): the batched recurrence runs as one Pallas grid with each
        # slot's [N,P] state resident in VMEM for the tick — the
        # state-sized dt·B⊗x update tensor never stages through HBM, so
        # the engine's compiled tick stays one program.
        from repro.kernels import ops as kernel_ops
        state, y = kernel_ops.fused_ssd_decode(
            state, xh, dt, A, B_t, C_t, policy=pol.kernel())
    else:
        state, y = ssd_decode_step(state, xh, dt, A, B_t, C_t)
    y = y + (params["D"].reshape(nh, 1)
             * xh.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(b, d_inner)
    y = gated_rmsnorm(y, z, params["norm_scale"], eps, cfg.n_groups,
                      policy=policy).astype(x_t.dtype)
    out = jnp.einsum("be,ed->bd", y, params["out_proj"].astype(x_t.dtype))
    return out, state, new_conv_buf
