"""Granite-4.0-H hybrid LM (``granitemoehybrid`` without experts).

Layers follow ``cfg.layer_types``: each is a Mamba-2 mixer
(``models/ssd.py``) or a GQA attention with its own weights and no
position embedding, and each is followed by a SwiGLU MLP.  Every
sublayer is pre-normed, and its branch is scaled by the residual
multiplier before it joins the stream::

    x = embed(tokens) * embedding_multiplier
    for each layer:
        x = x + residual_multiplier * mixer(rmsnorm(x))     # mamba | attn
        x = x + residual_multiplier * mlp(rmsnorm(x))
    logits = rmsnorm(x) @ head / logits_scaling

Attention scores are scaled by ``attention_multiplier``.  The residual
stream is carried in float32 and each sublayer's input is normalised into
the weights' dtype: with 80 bfloat16 additions of x0.22 branches, a
bfloat16 stream puts the logits (about 0.13 wide after the /8 at
published widths) 0.088 at worst from the float32 reference's; a
float32 stream, 0.025.  The layer stack
is scanned over the pattern's periods (four periods of ten for the
published model), and inside a period over each run of layers of one
kind (Mamba x5, attention, Mamba x4), so that a program holds one
layer's code per run.  Each layer reads its weights from the whole
stacks by index: a period's or a run's slice, handed to an inner loop,
would be copied out first, every step.

Decode state is of two kinds.  The attention layers keep KV, paged
(``init_paged_cache``: token-major pools for those layers only, the page
index shared by them) or dense; the Mamba layers keep a per-slot SSM state
``[Lm, B, H, P, N]`` (float32; heads of all groups in one axis and ``N``
last, the layout the TPU compiler gives the recurrence, so that the
carried state is never relaid out: the mixer's ``[.., N, P]`` view of a
layer's slice, taken on reading and undone on writing, compiles to no
operation of its own) and
conv history ``[Lm, B, (W-1)·C]`` (the ``W-1`` taps flattened into the
channels, for the same reason).  The decode step carries both whole
through the scan, as the dense model carries its pools: each layer reads
its own slice and writes it back in place, so the tick moves no
state-sized array beyond that read and write.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.registry import ExecutionPolicy
from repro.models import common, mlp, ssd, transformer
from repro.models.config import ModelConfig, ParallelConfig, ParamLayout
from repro.parallel.sharding import ShardCtx

MAMBA, ATTENTION = "mamba", "attention"


def _period(kinds) -> int:
    """The shortest period the layer pattern repeats with."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return p
    return n


def _runs(kinds):
    """The period as runs of one kind: ``(kind, first layer, length,
    index of the first among layers of its kind)``."""
    runs, seen = [], {MAMBA: 0, ATTENTION: 0}
    for i, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, i, 1, seen[kind]])
        seen[kind] += 1
    return [tuple(r) for r in runs]


def _at(tree, i):
    """Layer ``i`` of each stacked array in ``tree``."""
    return jax.tree.map(lambda a: _index(a, i), tree)


def _index(a, i):
    """``a[i]`` for a traced ``i`` that is never negative (no wrap-around
    is computed: a few scalar operations fewer in every layer)."""
    return jax.lax.dynamic_index_in_dim(a, i, 0, False,
                                        allow_negative_indices=False)


def _update(a, x, i):
    """``a.at[i].set(x)`` for a traced ``i`` that is never negative."""
    return jax.lax.dynamic_update_index_in_dim(a, x, i, 0,
                                               allow_negative_indices=False)


def _mixer(lp):
    """A Mamba layer's parameters as ``models/ssd.py`` reads them."""
    lp = dict(lp)
    lp["norm_scale"] = lp.pop("gate_norm")["scale"]
    return lp


class GraniteHybridLM:
    #: cache entries that hold per-slot recurrent state: the serving
    #: engine writes them from the prefill at admission, and shares no
    #: prompt pages for a model that has them (pages cannot resume a
    #: recurrence)
    recurrent_state = ("ssm_state", "conv_state")
    #: the paged KV pools are token-major ``[La, P, page_size, Hkv·hd]``
    token_major_pages = True

    def __init__(self, cfg: ModelConfig, par: ParallelConfig,
                 ctx: Optional[ShardCtx] = None,
                 policy: Optional[ExecutionPolicy] = None):
        kinds = cfg.layer_types
        if cfg.ssm is None or not kinds or set(kinds) != {MAMBA, ATTENTION}:
            raise ValueError("a granite hybrid needs an SSM config and both "
                             "layer kinds in layer_types")
        if par.use_pallas_attn:
            raise ValueError("the Pallas attention kernels take no "
                             "attention_multiplier")
        self.cfg, self.par, self.ctx = cfg, par, ctx
        self.policy = policy or par.execution_policy()
        self.param_layout = ParamLayout.plan(cfg, self.policy)
        self.period = _period(kinds)
        self.n_periods = cfg.num_layers // self.period
        self.runs = _runs(kinds[:self.period])
        #: layers with a KV cache, and layers with recurrent state
        self.kv_layers = cfg.attention_layers
        self.ssm_layers = cfg.num_layers - self.kv_layers
        self._per_period = {MAMBA: self.ssm_layers // self.n_periods,
                            ATTENTION: self.kv_layers // self.n_periods}
        self._dtype = jnp.dtype(cfg.dtype)

    def with_policy(self, policy: ExecutionPolicy) -> "GraniteHybridLM":
        return type(self)(self.cfg, self.par, self.ctx, policy=policy)

    # ---- params ----

    def _init_mamba(self, key):
        """``models/ssd.py``'s block, its gated norm's weight as
        ``gate_norm/scale``."""
        p, specs = ssd.init_mamba_block(key, self.cfg.d_model, self.cfg.ssm,
                                        self._dtype)
        p["gate_norm"] = {"scale": p.pop("norm_scale")}
        specs["gate_norm"] = {"scale": specs.pop("norm_scale")}
        return p, specs

    def init_params(self, rng):
        cfg, dt, layout = self.cfg, self._dtype, self.param_layout
        ks = jax.random.split(rng, 7)
        stack = lambda f, k, n: jax.vmap(lambda kk: f(kk)[0])(
            jax.random.split(k, n))
        params = {
            "embed": common.embed_init(ks[0], (cfg.vocab_size, cfg.d_model)),
            "mamba": stack(self._init_mamba, ks[1], self.ssm_layers),
            "attn": stack(lambda k: transformer.init_attn(k, cfg, dt, layout),
                          ks[2], self.kv_layers),
            "mlp": stack(lambda k: mlp.init_mlp(k, cfg.d_model, cfg.d_ff,
                                                cfg.act, dt, layout),
                         ks[3], cfg.num_layers),
            "ln1": stack(lambda k: (common.init_norm(k, cfg.d_model,
                                                     cfg.norm, dt), None),
                         ks[4], cfg.num_layers),
            "ln2": stack(lambda k: (common.init_norm(k, cfg.d_model,
                                                     cfg.norm, dt), None),
                         ks[5], cfg.num_layers),
            "final_norm": common.init_norm(ks[6], cfg.d_model, cfg.norm, dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = common.dense_init(
                ks[6], (cfg.d_model, cfg.vocab_size), 0, dt)
        return params

    def param_specs(self):
        cfg, key = self.cfg, jax.random.PRNGKey(0)
        stacked = lambda t: jax.tree.map(
            lambda ax: (None,) + ax, t,
            is_leaf=lambda x: isinstance(x, tuple))
        norm = stacked(common.norm_specs(cfg.norm))
        specs = {
            "embed": ("vocab", "embed"),
            "mamba": stacked(self._init_mamba(key)[1]),
            "attn": stacked(transformer.init_attn(
                key, cfg, jnp.float32, self.param_layout)[1]),
            "mlp": stacked(mlp.init_mlp(key, cfg.d_model, cfg.d_ff, cfg.act,
                                        jnp.float32, self.param_layout)[1]),
            "ln1": norm, "ln2": norm,
            "final_norm": common.norm_specs(cfg.norm),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = ("embed", "vocab")
        return specs

    def _scan_layers(self, params, layer_fns, carry, remat: bool = False):
        """Scan ``carry`` through every layer in order: over the periods,
        and in each over its runs of one kind.  ``layer_fns[kind](carry,
        (weights, k))`` runs one layer, ``weights`` = (mixer, MLP, first
        norm, second norm), ``k`` its index among layers of its kind.
        Returns the carry and, per kind, what the layers returned,
        layer-stacked (a ``[L_kind, ...]`` tree, or None)."""

        def period(carry, p):
            out = {MAMBA: [], ATTENTION: []}
            for kind, lo, n, ko in self.runs:
                def layer(carry, j, kind=kind, lo=lo, ko=ko):
                    k = p * self._per_period[kind] + ko + j
                    i = p * self.period + lo + j
                    mixer = params["mamba" if kind == MAMBA else "attn"]
                    weights = tuple(_at(t, x) for t, x in (
                        (mixer, k), (params["mlp"], i), (params["ln1"], i),
                        (params["ln2"], i)))
                    return layer_fns[kind](carry, (weights, k))
                carry, ys = jax.lax.scan(layer, carry, jnp.arange(n))
                out[kind].append(ys)
            return carry, {kind: jax.tree.map(
                lambda *a: jnp.concatenate(a), *ys) if ys[0] is not None
                else None for kind, ys in out.items()}

        if remat:
            period = jax.checkpoint(
                period, policy=jax.checkpoint_policies.nothing_saveable)
        carry, ys = jax.lax.scan(period, carry, jnp.arange(self.n_periods))
        return carry, {kind: None if y is None else jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), y)
            for kind, y in ys.items()}

    # ---- embedding / head / sublayers ----

    def _embed(self, params, tokens):
        """The residual stream's start, float32."""
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
            return x * (self.cfg.embedding_multiplier or 1.0)

    def _head(self, params, x):
        cfg = self.cfg
        with jax.named_scope("head"):
            w = params.get("lm_head")
            if w is None:
                w = params["embed"].T
            logits = common.rmsnorm_matmul(x, params["final_norm"]["scale"],
                                           w, cfg.norm_eps,
                                           policy=self.policy)
            return logits.astype(jnp.float32) / cfg.logits_scaling

    def _branch(self, x, out):
        return x + out.astype(x.dtype) * self.cfg.residual_multiplier

    def _norm(self, x, ln):
        """A sublayer's input: the stream normalised, in the weights'
        dtype."""
        return common.rmsnorm(x, ln["scale"], self.cfg.norm_eps,
                              policy=self.policy).astype(self._dtype)

    def _mlp(self, fp, ln, x):
        with jax.named_scope("mlp"):
            m = mlp.apply_mlp(fp, self._norm(x, ln), self.cfg.act, self.ctx,
                              policy=self.policy)
            return self._branch(x, m)

    # ---- full sequence (train / prefill) ----

    def _forward(self, params, x, collect: bool):
        """The layer stack over ``x`` [B,S,D]; with ``collect``, also the
        decode state: (SSM states, conv tails, K, V), layer-stacked."""
        cfg, par, ctx = self.cfg, self.par, self.ctx
        s = cfg.ssm
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

        def mamba(x, layer):
            (lp, fp, l1, l2), _ = layer
            with jax.named_scope("ssm"):
                out = ssd.apply_mamba_block(
                    _mixer(lp), self._norm(x, l1), s, cfg.d_model,
                    cfg.norm_eps, ctx, return_state=collect,
                    policy=self.policy)
                state = None
                if collect:
                    out, (st, cv) = out
                    with jax.named_scope("ssm_state"):
                        state = (self._to_cache(st),
                                 cv.reshape(cv.shape[0], -1).astype(
                                     self._dtype))
                x = self._branch(x, out)
            return self._mlp(fp, l2, x), state

        def attention(x, layer):
            (lp, fp, l1, l2), _ = layer
            with jax.named_scope("attention"):
                a = transformer.attn_seq(lp, self._norm(x, l1), cfg, par,
                                         positions, ctx, return_kv=collect,
                                         policy=self.policy)
                kv = None
                if collect:
                    a, kv = a
                x = self._branch(x, a)
            return self._mlp(fp, l2, x), kv

        x, ys = self._scan_layers(
            params, {MAMBA: mamba, ATTENTION: attention}, x,
            remat=par.remat == "full" and not collect)
        if not collect:
            return x, None
        return x, ys[MAMBA] + ys[ATTENTION]

    def loss_fn(self, params, batch):
        x = self._embed(params, batch["tokens"])
        x, _ = self._forward(params, x, collect=False)
        logits = self._head(params, x)
        loss = common.cross_entropy(logits, batch["labels"], self.ctx)
        return loss, {"ce_loss": loss}

    def prefill(self, params, batch):
        """Full forward building the decode state; last-position logits.
        The cache: ``k``/``v`` [La,B,Hkv,S,hd] of the attention layers,
        ``ssm_state`` and ``conv_state`` of the Mamba layers, ``pos``."""
        x = self._embed(params, batch["tokens"])
        x, (h, conv, k, v) = self._forward(params, x, collect=True)
        logits = self._head(params, x[:, -1:, :])
        b, s = x.shape[:2]
        return logits[:, 0], {"k": k, "v": v, "ssm_state": h,
                              "conv_state": conv,
                              "pos": jnp.full((b,), s, jnp.int32)}

    # ---- decode ----

    @staticmethod
    def _to_cache(state):
        """A mixer's ``[B,G,Hg,N,P]`` state as the cache keeps it,
        ``[B,H,P,N]`` float32."""
        state = state.swapaxes(-1, -2)
        return state.reshape(state.shape[0], -1, *state.shape[-2:]).astype(
            jnp.float32)

    def _to_mixer(self, state):
        """A layer's ``[B,H,P,N]`` cached state as the mixer takes it,
        ``[B,G,Hg,N,P]``."""
        return state.reshape(state.shape[0], self.cfg.ssm.n_groups, -1,
                             *state.shape[-2:]).swapaxes(-1, -2)

    def _state(self, batch_size: int) -> dict:
        cfg, s = self.cfg, self.cfg.ssm
        nh = s.expand * cfg.d_model // s.head_dim
        return {
            "ssm_state": jnp.zeros((self.ssm_layers, batch_size, nh,
                                    s.head_dim, s.state_dim), jnp.float32),
            "conv_state": jnp.zeros((self.ssm_layers, batch_size,
                                     (s.conv_width - 1)
                                     * ssd.conv_dim(s, cfg.d_model)),
                                    self._dtype),
        }

    def init_cache(self, batch_size: int, cache_len: int):
        cfg = self.cfg
        kv = (self.kv_layers, batch_size, cfg.num_kv_heads, cache_len,
              cfg.resolved_head_dim)
        return dict(self._state(batch_size),
                    k=jnp.zeros(kv, self._dtype), v=jnp.zeros(kv, self._dtype),
                    pos=jnp.zeros((batch_size,), jnp.int32))

    def init_paged_cache(self, batch_size: int, num_pages: int,
                         page_size: int, max_pages_per_slot: int):
        """KV pages for the attention layers only, token-major with the
        heads flattened into each token's row, ``[La, P, page_size,
        Hkv·hd]`` (at a head of 64 the TPU compiler would relay out a
        ``[.., Hkv, 64]`` pool on entering and leaving the tick), with
        per-slot block tables (the
        sentinel ``num_pages`` drops writes, see
        ``TransformerLM.init_paged_cache``), beside the per-slot SSM and
        conv state of the Mamba layers."""
        cfg = self.cfg
        shape = (self.kv_layers, num_pages, page_size,
                 cfg.num_kv_heads * cfg.resolved_head_dim)
        return dict(self._state(batch_size),
                    k_pages=jnp.zeros(shape, self._dtype),
                    v_pages=jnp.zeros(shape, self._dtype),
                    block_tables=jnp.full((batch_size, max_pages_per_slot),
                                          num_pages, jnp.int32),
                    pos=jnp.zeros((batch_size,), jnp.int32))

    def cache_specs(self):
        kv = (None, "act_cache_batch", "act_kv_heads", "act_kv_seq",
              "act_head_dim")
        return {"ssm_state": (None, "act_cache_batch", "act_ssm_heads",
                              None, "act_ssm_state"),
                "conv_state": (None, "act_cache_batch", None),
                "k": kv, "v": kv, "pos": (None,)}

    def decode_step(self, params, tokens, cache):
        """tokens [B] -> (logits [B,V], new cache), for a dense cache
        (``k``/``v``) or a paged one (``k_pages``/``v_pages`` and
        ``block_tables``).  State, conv history and KV ride the scan's
        carry whole; layer ``l`` of a kind reads and writes its own slice
        (paged: its own pages, through the table offset by ``l·P``)."""
        cfg, ctx, s = self.cfg, self.ctx, self.cfg.ssm
        pos = cache["pos"]
        paged = "block_tables" in cache
        x = self._embed(params, tokens[:, None])
        if paged:
            tables = cache["block_tables"]
            nl, npages = cache["k_pages"].shape[:2]
            kv = tuple(cache[n].reshape((nl * npages,) + cache[n].shape[2:])
                       for n in ("k_pages", "v_pages"))
        else:
            kv = (cache["k"], cache["v"])
        def mamba(carry, layer):
            x, h, conv, kv = carry
            (lp, fp, l1, l2), idx = layer
            with jax.named_scope("ssm_state"):
                st = self._to_mixer(
                    _index(h, idx))
                cv = _index(conv, idx).reshape(x.shape[0],
                                               s.conv_width - 1, -1)
            with jax.named_scope("ssm"):
                out, st, cv = ssd.mamba_decode_step(
                    _mixer(lp), self._norm(x[:, 0], l1), s, cfg.d_model,
                    cfg.norm_eps, st, cv, ctx, policy=self.policy)
                x = self._branch(x, out[:, None])
            with jax.named_scope("ssm_state"):
                h = _update(h, self._to_cache(st), idx)
                conv = _update(
                    conv, cv.reshape(x.shape[0], -1).astype(conv.dtype), idx)
            return (self._mlp(fp, l2, x), h, conv, kv), None

        def attention(carry, layer):
            x, h, conv, kv = carry
            (lp, fp, l1, l2), idx = layer
            with jax.named_scope("attention"):
                hin = self._norm(x, l1)
                if paged:
                    tbl = jnp.where(tables < npages, tables + idx * npages,
                                    nl * npages)
                    a, kv = transformer.attn_decode(
                        lp, hin, cfg, kv, pos, ctx, policy=self.policy,
                        block_tables=tbl)
                else:
                    a, new = transformer.attn_decode(
                        lp, hin, cfg, tuple(_index(c, idx) for c in kv),
                        pos, ctx, policy=self.policy)
                    kv = tuple(_update(c, n, idx) for c, n in zip(kv, new))
                x = self._branch(x, a)
            return (self._mlp(fp, l2, x), h, conv, kv), None

        (x, h, conv, kv), _ = self._scan_layers(
            params, {MAMBA: mamba, ATTENTION: attention},
            (x, cache["ssm_state"], cache["conv_state"], kv))
        logits = self._head(params, x)[:, 0]
        new = dict(cache, ssm_state=h, conv_state=conv, pos=pos + 1)
        if paged:
            new.update(k_pages=kv[0].reshape(cache["k_pages"].shape),
                       v_pages=kv[1].reshape(cache["v_pages"].shape))
        else:
            new.update(k=kv[0], v=kv[1])
        return logits, new
