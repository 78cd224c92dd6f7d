"""Decoder-only transformer LM (dense / MoE / VLM backbone).

Covers: llama4-scout (MoE top-1), granite-moe (MoE top-8), mistral-nemo,
granite-8b, qwen3 (qk_norm), mistral-large, and the llava backbone (text
decoder over a stub patch-embedding prefix).

Layers are scanned (stacked params) so the lowered HLO is one block's
program — essential for 512-device dry-run compile times.  Remat policy
and sharding constraints follow ParallelConfig.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.registry import ExecutionPolicy
from repro.models import common, mlp
from repro.models.attention import (chunked_attention, decode_attention,
                                    dequantize_kv, paged_decode_attention,
                                    quantize_kv, scale_page_shape,
                                    update_cache, update_cache_int8,
                                    update_paged_cache,
                                    update_paged_cache_int8)
from repro.models.config import (LEGACY_LAYOUT, ModelConfig, ParallelConfig,
                                 ParamLayout)
from repro.parallel.sharding import ShardCtx, shard


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# --------------------------------------------------------------------------
# Per-block params
# --------------------------------------------------------------------------


def _qkv_widths(cfg: ModelConfig):
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return (h * hd, hkv * hd, hkv * hd)


def init_attn(key, cfg: ModelConfig, dtype,
              layout: ParamLayout = LEGACY_LAYOUT):
    d, h, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    # the three projections draw from the same keys on either layout, so
    # the two layouts of one seed are the same weights (migration and the
    # fusion-equivalence tests rely on it)
    wq = common.dense_init(ks[0], (d, h * hd), 0, dtype)
    wk = common.dense_init(ks[1], (d, hkv * hd), 0, dtype)
    wv = common.dense_init(ks[2], (d, hkv * hd), 0, dtype)
    params = {"wo": common.dense_init(ks[3], (h * hd, d), 0, dtype)}
    specs = {"wo": ("q_heads", "embed")}
    if layout.attn_qkv:
        params["wqkv"] = jnp.concatenate([wq, wk, wv], axis=1)
        specs["wqkv"] = ("embed", "qkv_heads")
    else:
        params.update(wq=wq, wk=wk, wv=wv)
        specs.update(wq=("embed", "q_heads"), wk=("embed", "kv_heads"),
                     wv=("embed", "kv_heads"))
    if cfg.qk_norm:
        params["q_norm"] = jnp.ones((hd,), dtype)
        params["k_norm"] = jnp.ones((hd,), dtype)
        specs["q_norm"] = ("head_dim",)
        specs["k_norm"] = ("head_dim",)
    return params, specs


def init_block(key, cfg: ModelConfig, dtype,
               layout: ParamLayout = LEGACY_LAYOUT):
    ks = jax.random.split(key, 4)
    attn, attn_specs = init_attn(ks[0], cfg, dtype, layout)
    params = {"attn": attn,
              "ln1": common.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
              "ln2": common.init_norm(ks[3], cfg.d_model, cfg.norm, dtype)}
    specs = {"attn": attn_specs,
             "ln1": common.norm_specs(cfg.norm),
             "ln2": common.norm_specs(cfg.norm)}
    if cfg.moe is not None:
        params["moe"], specs["moe"] = mlp.init_moe(
            ks[1], cfg.d_model, cfg.d_ff, cfg.moe, cfg.act, dtype, layout)
    else:
        params["mlp"], specs["mlp"] = mlp.init_mlp(
            ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype, layout)
    return params, specs


# --------------------------------------------------------------------------
# Attention sublayer
# --------------------------------------------------------------------------


def _project_qkv(params, x, cfg: ModelConfig, positions, ctx,
                 constrain_kv: bool = True, policy=None, norm_scale=None):
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if norm_scale is not None:
        # The pre-attention norm rides into the projection as a fused
        # GEMM prologue (x is the *raw* residual here): the normalized
        # activation is consumed from VMEM, never staged to HBM.  One
        # call against the concatenated [wq|wk|wv] — the persisted tensor
        # when the layout planner placed one, a per-call concat on legacy
        # params — so the residual is read and the moment computed once
        # per sublayer, not thrice.
        w_qkv = common.concat_param(params, "wqkv", ("wq", "wk", "wv"))
        qkv = common.rmsnorm_matmul(x, norm_scale, w_qkv,
                                    cfg.norm_eps, policy=policy,
                                    w_scale=params.get("wqkv_scale"))
        q, k, v = jnp.split(qkv, [h * hd, (h + hkv) * hd], axis=-1)
    else:
        if "wqkv_scale" in params:
            # int8 concat on the unfused path: dequantize once, then take
            # the usual per-matrix views (only the persisted concat is
            # ever quantized — see common.quantize_params)
            params = dict(params, wqkv=common.dequantize_weight(
                params["wqkv"], params["wqkv_scale"], x.dtype))
        wq, wk, wv = common.split_param(params, "wqkv", ("wq", "wk", "wv"),
                                        _qkv_widths(cfg))
        q = jnp.einsum("bsd,dh->bsh", x, wq.astype(x.dtype))
        k = jnp.einsum("bsd,dh->bsh", x, wk.astype(x.dtype))
        v = jnp.einsum("bsd,dh->bsh", x, wv.astype(x.dtype))
    q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = common.rmsnorm(q, params["q_norm"], cfg.norm_eps, policy=policy)
        k = common.rmsnorm(k, params["k_norm"], cfg.norm_eps, policy=policy)
    if cfg.pos_emb == "rope":
        q = common.apply_rope(q, positions[:, None, :], cfg.rope_theta)
        k = common.apply_rope(k, positions[:, None, :], cfg.rope_theta)
    q = shard(q, ("act_batch", "act_heads", "act_seq_unsharded",
                  "act_head_dim"), ctx)
    if constrain_kv:
        # Baseline layout.  When num_kv_heads < model-axis size this
        # forces a [shard,replica] representation that every consumer
        # re-gathers — the constrain_kv_pre_repeat=False §Perf lever
        # skips it and lets propagation keep K/V in the producer layout.
        k = shard(k, ("act_batch", "act_kv_heads", "act_seq_unsharded",
                      "act_head_dim"), ctx)
        v = shard(v, ("act_batch", "act_kv_heads", "act_seq_unsharded",
                      "act_head_dim"), ctx)
    return q, k, v


def _wo_weight(params, dtype):
    """The output projection at math width: dequantized when the
    precision policy stored it int8 (unfused paths only — fused lowerings
    take the int8 leaf + scale and dequantize blocks in VMEM)."""
    if "wo_scale" in params:
        return common.dequantize_weight(params["wo"], params["wo_scale"],
                                        dtype)
    return params["wo"].astype(dtype)


def _repeat_kv(k, v, group: int, ctx):
    """Materialize GQA groups so the attention compute is uniformly
    head-sharded.

    With num_kv_heads < mesh 'model' size, a [B,Hkv,S,D] operand forces
    GSPMD into [shard,replica] <-> [full-shard] transitions *inside* the
    attention chunk scans — one involuntary all-gather per chunk step per
    layer (~10 TB/chip/step at qwen3 scale; see EXPERIMENTS.md §Perf).
    Repeating KV to H heads costs only the repeated chunk in VMEM-scale
    activation memory but makes every attention tensor share one clean
    16-way head sharding.  The *cache* keeps the un-repeated [B,Hkv,S,D]
    layout — this is a compute-layout choice, not a memory-layout one.
    """
    if group == 1:
        return k, v
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    k = shard(k, ("act_batch", "act_heads", "act_seq_unsharded",
                  "act_head_dim"), ctx)
    v = shard(v, ("act_batch", "act_heads", "act_seq_unsharded",
                  "act_head_dim"), ctx)
    return k, v


def attn_seq(params, x, cfg: ModelConfig, par: ParallelConfig,
             positions, ctx, causal: bool = True,
             return_kv: bool = False, policy=None, norm_scale=None):
    """Full-sequence attention (train / prefill).

    With ``norm_scale`` set, ``x`` is the raw residual and the
    pre-attention rmsnorm fuses into the q/k/v projections."""
    b, s, d = x.shape
    policy = policy or par.execution_policy()
    q, k, v = _project_qkv(params, x, cfg, positions, ctx,
                           constrain_kv=par.constrain_kv_pre_repeat,
                           policy=policy, norm_scale=norm_scale)
    k_rep, v_rep = _repeat_kv(k, v, cfg.num_heads // cfg.num_kv_heads, ctx)
    if par.use_pallas_attn:
        # TPU execution path: the framework's own flash kernel.  The
        # variant comes from the threaded policy's kernel view — the
        # registry, not this call site, decides the lowering.
        from repro.kernels import ops as kernel_ops
        if policy.fuses():
            # Fused epilogue: the wo projection consumes the online-
            # softmax accumulator in VMEM (kernels/fused.py) — the
            # [B,S,H,D] attention output never round-trips through HBM.
            out = kernel_ops.fused_flash_attention_matmul(
                q, k_rep, v_rep, params["wo"], causal=causal,
                block_q=min(par.attn_chunk_q, 256),
                block_kv=min(par.attn_chunk_kv, 256),
                policy=policy.kernel(),
                w_scale=params.get("wo_scale"))
        else:
            o = kernel_ops.flash_attention(
                q, k_rep, v_rep, causal=causal,
                block_q=min(par.attn_chunk_q, 256),
                block_kv=min(par.attn_chunk_kv, 256),
                policy=policy.kernel())
            o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
            out = jnp.einsum("bsh,hd->bsd", o, _wo_weight(params, x.dtype))
    else:
        o = chunked_attention(
            q, k_rep, v_rep, causal=causal, kv_offset=0,
            chunk_q=par.attn_chunk_q, chunk_kv=par.attn_chunk_kv,
            exact_causal=par.causal_folding, scale=cfg.attention_multiplier,
            ctx=ctx)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        out = jnp.einsum("bsh,hd->bsd", o, _wo_weight(params, x.dtype))
    if par.rs_outputs:
        # Constrain the row-parallel partial sum to the seq-sharded
        # residual layout so the TP combine compiles to reduce-scatter.
        out = shard(out, ("act_batch", "act_seq", "act_embed"), ctx)
    if return_kv:
        return out, (k, v)
    return out


def attn_decode(params, x_t, cfg: ModelConfig, kv_cache, pos, ctx,
                int8: bool = False, policy=None, norm_scale=None,
                fuse_wo: bool = False, block_tables=None):
    """One-token attention. x_t: [B,1,D]; kv_cache: (K,V) [B,Hkv,S,hd]
    (bf16) or (Kq,Ks,Vq,Vs) (int8 + scales).

    With ``norm_scale`` set, ``x_t`` is the raw residual and the
    pre-attention rmsnorm fuses into the q/k/v projections — decode-legal
    only because the caller verified the concatenated ``wqkv`` is
    *persisted* (zero weight-traffic overhead; see block_decode's gate).
    ``fuse_wo`` routes the cache attention + wo projection through the
    decode shape of ``flash_attention_matmul`` (per-slot ``pos``
    frontiers mask the cache), eliminating the `[B,1,H,D]` attention
    output round trip per layer per tick.

    ``block_tables`` switches the cache to its *paged* form: kv_cache is
    (k_pages, v_pages) pools and the table maps each slot's logical kv
    blocks to pool pages.  The one-token write scatters through the
    table (sentinel entries drop).  The pools are token-major
    ``[P, page_size, Hkv, hd]``, and attention runs over the gathered
    strips; with ``fuse_wo`` they are the head-major
    ``[P, Hkv, page_size, hd]`` pools the paged decode shape of
    ``flash_attention_matmul`` reads, and the kernel gets the table so
    it only visits live pages (``TransformerLM.token_major_pages``)."""
    b = x_t.shape[0]
    positions = pos[:, None]                       # [B,1]
    q, k_new, v_new = _project_qkv(params, x_t, cfg, positions, ctx,
                                   policy=policy, norm_scale=norm_scale)
    if block_tables is not None:
        token_major = not fuse_wo
        with jax.named_scope("kv_write"):
            if int8:
                # int8 paged cache: quantize-on-write through the same
                # table scatter, per-token scales riding parallel pools.
                k_pages, k_sc, v_pages, v_sc = kv_cache
                k_pages, k_sc = update_paged_cache_int8(
                    k_pages, k_sc, k_new, block_tables, pos,
                    token_major=token_major)
                v_pages, v_sc = update_paged_cache_int8(
                    v_pages, v_sc, v_new, block_tables, pos,
                    token_major=token_major)
                new_cache = (k_pages, k_sc, v_pages, v_sc)
            else:
                k_pages, v_pages = kv_cache
                k_pages = update_paged_cache(k_pages, k_new, block_tables,
                                             pos, token_major=token_major)
                v_pages = update_paged_cache(v_pages, v_new, block_tables,
                                             pos, token_major=token_major)
                k_sc = v_sc = None
                new_cache = (k_pages, v_pages)
        if fuse_wo:
            from repro.kernels import ops as kernel_ops
            with jax.named_scope("kv_read"):
                out = kernel_ops.fused_flash_attention_matmul(
                    q, k_pages, v_pages, params["wo"], pos=pos,
                    block_tables=block_tables,
                    policy=policy.kernel() if policy is not None else None,
                    w_scale=params.get("wo_scale"), k_scale=k_sc,
                    v_scale=v_sc)
            return out, new_cache
        with jax.named_scope("kv_read"):
            # float pools on a TPU are read in place, live pages only;
            # int8 strips dequantize after the gather (the fused kernel
            # dequantizes per page, in VMEM, only for live table entries)
            o = paged_decode_attention(
                q, k_pages, v_pages, block_tables, pos, k_scale=k_sc,
                v_scale=v_sc, scale=cfg.attention_multiplier, ctx=ctx,
                interpret=policy.interpret if policy is not None else None)
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, -1)
        out = jnp.einsum("bsh,hd->bsd", o, _wo_weight(params, x_t.dtype))
        return out, new_cache
    with jax.named_scope("kv_write"):
        if int8:
            k_q, k_s, v_q, v_s = kv_cache
            k_q, k_s = update_cache_int8(k_q, k_s, k_new, pos)
            v_q, v_s = update_cache_int8(v_q, v_s, v_new, pos)
            new_cache = (k_q, k_s, v_q, v_s)
        else:
            k_cache, v_cache = kv_cache
            k_cache = update_cache(k_cache, k_new, pos)
            v_cache = update_cache(v_cache, v_new, pos)
            new_cache = (k_cache, v_cache)
    with jax.named_scope("kv_read"):
        if int8:
            k_cache = dequantize_kv(k_q, k_s, x_t.dtype)
            v_cache = dequantize_kv(v_q, v_s, x_t.dtype)
        if fuse_wo:
            from repro.kernels import ops as kernel_ops
            out = kernel_ops.fused_flash_attention_matmul(
                q, k_cache, v_cache, params["wo"], pos=pos,
                policy=policy.kernel() if policy is not None else None,
                w_scale=params.get("wo_scale"))
            return out, new_cache
        o = decode_attention(q, k_cache, v_cache, pos,
                             scale=cfg.attention_multiplier, ctx=ctx)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, -1)
    out = jnp.einsum("bsh,hd->bsd", o, _wo_weight(params, x_t.dtype))
    return out, new_cache


# --------------------------------------------------------------------------
# Block
# --------------------------------------------------------------------------


def block_seq(params, x, cfg: ModelConfig, par: ParallelConfig, positions,
              ctx, return_kv: bool = False, policy=None):
    policy = policy or par.execution_policy()
    # Fused-epilogue routing (policy-gated): the ln1→projection pair fuses
    # into the q/k/v GEMMs and the residual→ln2 pair into one kernel —
    # the two per-sublayer activation round trips the unfused sequence
    # stages through HBM (see kernels/fused.py).
    fuse = policy.fuses() and cfg.norm == "rmsnorm"
    with jax.named_scope("attention"):
        if fuse:
            h, norm_scale = x, params["ln1"]["scale"]
        else:
            h = common.apply_norm(x, params["ln1"], cfg.norm, cfg.norm_eps,
                                  policy=policy)
            norm_scale = None
        if return_kv:
            a, kv = attn_seq(params["attn"], h, cfg, par, positions, ctx,
                             return_kv=True, policy=policy,
                             norm_scale=norm_scale)
        else:
            a = attn_seq(params["attn"], h, cfg, par, positions, ctx,
                         policy=policy, norm_scale=norm_scale)
            kv = None
    # ln2 routing: when a fusable wi/wg pair sits downstream (dense silu
    # MLP, or a MoE with shared experts), the fused path keeps the
    # residual RAW — ln2 rides into the wi/wg projections as a fused
    # prologue (rmsnorm_swiglu saves the full norm round trip, strictly
    # more than add_rmsnorm's read-back leg).  Otherwise (gelu MLPs, MoE
    # without shared experts — the router path needs the norm explicitly
    # and there is no dense pair to absorb it) the PR 3 residual→norm
    # fusion stays.
    swiglu_fuse = (fuse and cfg.act == "silu"
                   and (cfg.moe is None or bool(cfg.moe.shared_experts)))
    with jax.named_scope("mlp"):
        if swiglu_fuse:
            x = x + a
            h, mlp_scale = x, params["ln2"]["scale"]
        elif fuse:
            h, x = common.add_rmsnorm(x, a, params["ln2"]["scale"],
                                      cfg.norm_eps, policy=policy)
            mlp_scale = None
        else:
            x = x + a
            h = common.apply_norm(x, params["ln2"], cfg.norm, cfg.norm_eps,
                                  policy=policy)
            mlp_scale = None
        if cfg.moe is not None:
            m, aux = mlp.apply_moe(params["moe"], h, cfg.moe, cfg.act, ctx,
                                   policy=policy, norm_scale=mlp_scale,
                                   eps=cfg.norm_eps)
        else:
            m, aux = mlp.apply_mlp(params["mlp"], h, cfg.act, ctx,
                                   policy=policy, norm_scale=mlp_scale,
                                   eps=cfg.norm_eps), 0.0
        if par.rs_outputs:
            m = shard(m, ("act_batch", "act_seq", "act_embed"), ctx)
        x = x + m
    x = shard(x, ("act_batch", "act_seq", "act_embed"), ctx)
    return (x, aux, kv) if return_kv else (x, aux)


def block_decode(params, x_t, cfg: ModelConfig, kv_cache, pos, ctx,
                 int8: bool = False, policy=None, fuse_wo: bool = False,
                 block_tables=None):
    fuse = (policy is not None and policy.fuses()
            and cfg.norm == "rmsnorm")
    # Decode fusion gates (ISSUE 5): the qkv / ln2→[wi|wg] prologues fuse
    # exactly when the concatenated tensor is *persisted* (the ParamLayout
    # planner's init-time choice) — then the fused call reads the same
    # weight bytes the unfused sequence would and the activation round
    # trip is a pure saving.  On legacy per-matrix params the per-call
    # concat materializes a weight-sized tensor to save a token-sized
    # round trip (rows = B) — a net traffic loss — so the gates stay shut,
    # which is exactly the PR 4 behavior.  The activation-sized
    # residual→norm fusion has no weight term and is layout-independent.
    qkv_fuse = fuse and common.stored_concat(params["attn"], "wqkv")
    with jax.named_scope("attention"):
        if qkv_fuse:
            h, ln1_scale = x_t, params["ln1"]["scale"]
        else:
            h = common.apply_norm(x_t, params["ln1"], cfg.norm,
                                  cfg.norm_eps, policy=policy)
            ln1_scale = None
        a, kv_cache = attn_decode(params["attn"], h, cfg, kv_cache, pos,
                                  ctx, int8=int8, policy=policy,
                                  norm_scale=ln1_scale, fuse_wo=fuse_wo,
                                  block_tables=block_tables)
    if cfg.moe is None:
        mlp_params = params["mlp"]
    elif cfg.moe.shared_experts:
        mlp_params = params["moe"]["shared"]
    else:
        mlp_params = {}                  # router-only MoE: no fusable pair
    swiglu_fuse = (fuse and cfg.act == "silu"
                   and common.stored_concat(mlp_params, "wig"))
    with jax.named_scope("mlp"):
        if swiglu_fuse:
            x_t = x_t + a
            h, mlp_scale = x_t, params["ln2"]["scale"]
        elif fuse:
            h, x_t = common.add_rmsnorm(x_t, a, params["ln2"]["scale"],
                                        cfg.norm_eps, policy=policy)
            mlp_scale = None
        else:
            x_t = x_t + a
            h = common.apply_norm(x_t, params["ln2"], cfg.norm,
                                  cfg.norm_eps, policy=policy)
            mlp_scale = None
        if cfg.moe is not None:
            m, _ = mlp.apply_moe(params["moe"], h, cfg.moe, cfg.act, ctx,
                                 policy=policy, norm_scale=mlp_scale,
                                 eps=cfg.norm_eps)
        else:
            m = mlp.apply_mlp(params["mlp"], h, cfg.act, ctx,
                              policy=policy, norm_scale=mlp_scale,
                              eps=cfg.norm_eps)
        x_t = x_t + m
    return x_t, kv_cache


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------


class TransformerLM:
    """Functional decoder-only LM with scanned layers."""

    def __init__(self, cfg: ModelConfig, par: ParallelConfig,
                 ctx: Optional[ShardCtx] = None,
                 policy: Optional[ExecutionPolicy] = None):
        self.cfg = cfg
        self.par = par
        self.ctx = ctx
        # the lowering policy every hot spot below threads (resolved ONCE)
        self.policy = policy or par.execution_policy()
        # the parameter layout the policy earns (resolved ONCE, at init —
        # the fusion-legality decision made where it is free, at rest).
        # Consumers stay layout-agnostic via the common.py accessors, so
        # params initialized under either plan still run under either
        # policy; only *this* model's init_params/param_specs emit the
        # planned layout.
        self.param_layout = ParamLayout.plan(cfg, self.policy)
        # the decode shape of the flash→wo fusion (the cache attention +
        # output projection in one kernel, per-slot pos frontiers): on
        # the Pallas execution path whenever the policy fuses — wo is a
        # single matrix, so unlike qkv/wig it needs no layout plan
        self.fuse_wo = (par.use_pallas_attn and self.policy.fuses()
                        and cfg.num_heads > 0)
        # the page layout follows (resolved ONCE, with the policy): that
        # kernel reads (page, head) tiles of head-major pages; every other
        # paged tick writes a token's all-head row in one scatter and
        # carries the pools whole through the layer scan, which takes
        # token-major pages to stay free of pool-sized copies
        self.token_major_pages = not self.fuse_wo
        self.aux_weight = 0.01 if cfg.moe is not None else 0.0

    def with_policy(self, policy: ExecutionPolicy) -> "TransformerLM":
        return type(self)(self.cfg, self.par, self.ctx, policy=policy)

    # ---- params ----

    def init_params(self, rng):
        cfg = self.cfg
        dtype = _dtype(cfg)
        k_embed, k_blocks, k_out, k_norm = jax.random.split(rng, 4)
        block_keys = jax.random.split(k_blocks, cfg.num_layers)
        layout = self.param_layout
        blocks = jax.vmap(
            lambda k: init_block(k, cfg, dtype, layout)[0])(block_keys)
        params = {
            "embed": common.embed_init(k_embed,
                                       (cfg.vocab_size, cfg.d_model)),
            "blocks": blocks,
            "final_norm": common.init_norm(k_norm, cfg.d_model, cfg.norm,
                                           dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = common.dense_init(
                k_out, (cfg.d_model, cfg.vocab_size), 0, dtype)
        return params

    def param_specs(self):
        cfg = self.cfg
        _, block_specs = init_block(jax.random.PRNGKey(0), cfg, jnp.float32,
                                    self.param_layout)
        # scanned leading 'layers' axis is unsharded
        block_specs = jax.tree.map(lambda ax: (None,) + ax, block_specs,
                                   is_leaf=lambda x: isinstance(x, tuple))
        specs = {
            "embed": ("vocab", "embed"),
            "blocks": block_specs,
            "final_norm": common.norm_specs(cfg.norm),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = ("embed", "vocab")
        return specs

    # ---- embedding / head ----

    def _embed(self, params, tokens, batch):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0
                         ).astype(_dtype(cfg))
            if cfg.family == "vlm" and "patch_embeds" in batch:
                patches = batch["patch_embeds"].astype(_dtype(cfg))
                x = jnp.concatenate([patches, x], axis=1)
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
            return shard(x, ("act_batch", "act_seq_unsharded", "act_embed"),
                         self.ctx)

    def _head(self, params, x):
        cfg = self.cfg
        w = params.get("lm_head")
        with jax.named_scope("head"):
            if w is None:
                w = params["embed"].T
            if cfg.norm == "rmsnorm":
                # the final norm→lm_head pair: fused (policy-gated) or the
                # historical norm-then-einsum, decided in one place
                logits = common.rmsnorm_matmul(
                    x, params["final_norm"]["scale"], w, cfg.norm_eps,
                    policy=self.policy)
            else:
                x = common.apply_norm(x, params["final_norm"], cfg.norm,
                                      cfg.norm_eps, policy=self.policy)
                logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
            return shard(logits.astype(jnp.float32),
                         ("act_batch", "act_seq_unsharded", "act_vocab"),
                         self.ctx)

    # ---- layer stack ----

    def _scan_blocks(self, params, x, positions, return_kv=False):
        cfg, par, ctx = self.cfg, self.par, self.ctx
        policy = self.policy

        def body(carry, layer_params):
            h, aux = carry
            if return_kv:
                h, a, kv = block_seq(layer_params, h, cfg, par, positions,
                                     ctx, return_kv=True, policy=policy)
                return (h, aux + a), kv
            h, a = block_seq(layer_params, h, cfg, par, positions, ctx,
                             policy=policy)
            return (h, aux + a), None

        if par.remat == "full":
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        elif par.remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        (x, aux), kvs = jax.lax.scan(body, (x, 0.0), params["blocks"])
        return x, aux, kvs

    # ---- public API ----

    def loss_fn(self, params, batch):
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        x = self._embed(params, tokens, batch)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                     (x.shape[0], x.shape[1]))
        x, aux, _ = self._scan_blocks(params, x, positions)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            x = x[:, batch["patch_embeds"].shape[1]:]
        logits = self._head(params, x)
        loss = common.cross_entropy(logits, labels, self.ctx)
        total = loss + self.aux_weight * aux / max(cfg.num_layers, 1)
        return total, {"ce_loss": loss, "aux_loss": aux}

    def prefill(self, params, batch):
        """Full forward building a decode cache; returns last-pos logits."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, batch)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                     (x.shape[0], x.shape[1]))
        x, _, kvs = self._scan_blocks(params, x, positions, return_kv=True)
        logits = self._head(params, x[:, -1:, :])
        pos = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
        if self.par.kv_cache_int8:
            k_q, k_s = quantize_kv(kvs[0])
            v_q, v_s = quantize_kv(kvs[1])
            cache = {"k": k_q, "k_scale": k_s, "v": v_q, "v_scale": v_s,
                     "pos": pos}
        else:
            cache = {"k": kvs[0], "v": kvs[1], "pos": pos}
        return logits[:, 0], cache

    def init_cache(self, batch_size: int, cache_len: int):
        cfg = self.cfg
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        shape = (cfg.num_layers, batch_size, hkv, cache_len, hd)
        if self.par.kv_cache_int8:
            sshape = shape[:-1] + (1,)
            return {
                "k": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.full(sshape, 1e-8, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "v_scale": jnp.full(sshape, 1e-8, jnp.float32),
                "pos": jnp.zeros((batch_size,), jnp.int32),
            }
        return {
            "k": jnp.zeros(shape, _dtype(cfg)),
            "v": jnp.zeros(shape, _dtype(cfg)),
            "pos": jnp.zeros((batch_size,), jnp.int32),
        }

    def init_paged_cache(self, batch_size: int, num_pages: int,
                         page_size: int, max_pages_per_slot: int):
        """The paged form of :meth:`init_cache`: fixed-size KV pages plus
        per-slot block tables (capacity by pages, not slots).

        Pools are token-major ``[L, P, page_size, Hkv, hd]``, or
        head-major ``[L, P, Hkv, page_size, hd]`` for the Pallas paged
        kernel (:attr:`token_major_pages`; int8 scale pools follow, see
        ``models/attention.py``) — the page index axis is
        shared across layers, so one table serves the whole scan.  Tables
        init to the sentinel ``num_pages`` (out of range): a write
        through a sentinel entry drops and a gather clamps onto a page
        the ``pos`` mask hides, which is what makes reaped slots inert
        inside the one-program tick.  Allocation/refcounts live in
        ``repro.serve.engine.PagePool``."""
        cfg = self.cfg
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        if self.token_major_pages:
            shape = (cfg.num_layers, num_pages, page_size, hkv, hd)
        else:
            shape = (cfg.num_layers, num_pages, hkv, page_size, hd)
        tables = jnp.full((batch_size, max_pages_per_slot), num_pages,
                          jnp.int32)
        pos = jnp.zeros((batch_size,), jnp.int32)
        if self.par.kv_cache_int8:
            # int8 pools + per-(token,head) f32 scale pools riding the
            # same page index axis — a page costs hd + 4 bytes per token
            # per head instead of 4*hd, which is where the PagePool
            # capacity multiplier comes from (serve/engine.py)
            if self.token_major_pages:
                sshape = (cfg.num_layers, num_pages) + scale_page_shape(
                    page_size, hkv)
            else:
                sshape = shape[:-1] + (1,)
            return {
                "k_pages": jnp.zeros(shape, jnp.int8),
                "k_scale_pages": jnp.full(sshape, 1e-8, jnp.float32),
                "v_pages": jnp.zeros(shape, jnp.int8),
                "v_scale_pages": jnp.full(sshape, 1e-8, jnp.float32),
                "block_tables": tables,
                "pos": pos,
            }
        return {
            "k_pages": jnp.zeros(shape, _dtype(cfg)),
            "v_pages": jnp.zeros(shape, _dtype(cfg)),
            "block_tables": tables,
            "pos": pos,
        }

    def cache_specs(self):
        kv = (None, "act_cache_batch", "act_kv_heads", "act_kv_seq",
              "act_head_dim")
        if self.par.kv_cache_int8:
            sc = (None, "act_cache_batch", "act_kv_heads", "act_kv_seq",
                  None)
            return {"k": kv, "k_scale": sc, "v": kv, "v_scale": sc,
                    "pos": (None,)}
        return {"k": kv, "v": kv, "pos": (None,)}

    def decode_step(self, params, tokens, cache):
        """tokens: [B] int32 -> (logits [B,V], new cache).

        A cache carrying ``block_tables`` routes through the paged decode
        path: token-major pools ride the scan's carry whole
        (:meth:`_scan_carried_pools`); the Pallas kernel's head-major
        pools ride it per layer as ``xs``/``ys``, while the table and
        ``pos`` frontier broadcast — same one-program shape,
        page-gathered attention."""
        cfg, ctx = self.cfg, self.ctx
        int8 = self.par.kv_cache_int8
        paged = "block_tables" in cache
        tables = cache["block_tables"] if paged else None
        pos = cache["pos"]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens[:, None], axis=0
                         ).astype(_dtype(cfg))
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if paged and self.token_major_pages:
            x, pools = self._scan_carried_pools(params, x, cache)
            logits = self._head(params, x)[:, 0]
            return logits, dict(cache, **pools, pos=pos + 1)

        def body(h, layer):
            layer_params, kv = layer
            h, new_kv = block_decode(layer_params, h, cfg, kv, pos, ctx,
                                     int8=int8, policy=self.policy,
                                     fuse_wo=self.fuse_wo,
                                     block_tables=tables)
            return h, new_kv

        if paged and int8:
            kv_in = (cache["k_pages"], cache["k_scale_pages"],
                     cache["v_pages"], cache["v_scale_pages"])
        elif paged:
            kv_in = (cache["k_pages"], cache["v_pages"])
        elif int8:
            kv_in = (cache["k"], cache["k_scale"], cache["v"],
                     cache["v_scale"])
        else:
            kv_in = (cache["k"], cache["v"])
        x, new_kvs = jax.lax.scan(body, x, (params["blocks"], kv_in))
        logits = self._head(params, x)[:, 0]
        if paged and int8:
            new_cache = {"k_pages": new_kvs[0], "k_scale_pages": new_kvs[1],
                         "v_pages": new_kvs[2], "v_scale_pages": new_kvs[3],
                         "block_tables": tables, "pos": pos + 1}
        elif paged:
            new_cache = {"k_pages": new_kvs[0], "v_pages": new_kvs[1],
                         "block_tables": tables, "pos": pos + 1}
        elif int8:
            new_cache = {"k": new_kvs[0], "k_scale": new_kvs[1],
                         "v": new_kvs[2], "v_scale": new_kvs[3],
                         "pos": pos + 1}
        else:
            new_cache = {"k": new_kvs[0], "v": new_kvs[1], "pos": pos + 1}
        return logits, new_cache

    def _scan_carried_pools(self, params, x, cache):
        """The layer scan of the token-major paged tick.

        Handed to the scan as ``xs`` and stacked back as ``ys``, every
        layer's pool is sliced out, relaid out and copied back whole each
        tick.  Here the pools ride the carry instead, flattened to
        ``[L·P, ...]`` (a bitcast), and layer ``l`` reaches its own pages
        through the table offset by ``l·P``: the scatter writes in place
        and only the slots' strips are gathered.  The sentinel maps to
        ``L·P``, still out of range, so a reaped slot's writes drop and
        its gathers clamp onto a page the ``pos`` mask hides.  Returns the
        final residual and the pools at ``[L, P, ...]``.
        """
        cfg = self.cfg
        tables, pos = cache["block_tables"], cache["pos"]
        names = [n for n in ("k_pages", "k_scale_pages", "v_pages",
                             "v_scale_pages") if n in cache]
        nl, npages = cache["k_pages"].shape[:2]
        flat = tuple(cache[n].reshape((nl * npages,) + cache[n].shape[2:])
                     for n in names)

        def body(carry, layer):
            h, pools = carry
            layer_params, l = layer
            tbl = jnp.where(tables < npages, tables + l * npages,
                            nl * npages)
            h, pools = block_decode(layer_params, h, cfg, pools, pos,
                                    self.ctx, int8=self.par.kv_cache_int8,
                                    policy=self.policy, block_tables=tbl)
            return (h, pools), None

        (x, flat), _ = jax.lax.scan(body, (x, flat),
                                    (params["blocks"], jnp.arange(nl)))
        return x, {n: p.reshape(cache[n].shape) for n, p in zip(names, flat)}
