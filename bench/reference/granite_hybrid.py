"""Plain float32 forward of a Granite-4.0-H hybrid decoder
(``granitemoehybrid`` without experts): the yardstick of every hybrid
cell.

It imports nothing of the program.  It follows the published layer
equations, layer by layer as ``layer_types`` lists them::

    x = embed(tokens) * embedding_multiplier
    each layer:  x = x + residual_multiplier * mixer(rmsnorm(x))
                 x = x + residual_multiplier * swiglu(rmsnorm(x))
    logits = rmsnorm(x) @ embed.T / logits_scaling

The attention mixer is grouped-query attention with no position
embedding (``position_embedding_type: nope``), scores scaled by
``attention_multiplier``.  The Mamba-2 mixer projects to
``[z | x B C | dt]``, runs a causal depthwise conv of width
``mamba_d_conv`` (with its bias) and a SiLU over ``x B C``, sets
``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, and runs the
recurrence one step at a time (the published per-step update, not the
chunked dual form the program computes)::

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T      (per head, [N, P])
    y_t = C_t h_t + D x_t

then the gated norm ``rmsnorm(y * silu(z))`` over each of the
``mamba_n_groups`` groups of channels, and the output projection.

It reads the parameter tree the benchmark made: ``mamba`` (stacked over
the Mamba layers: the input projection as its three parts
``in_z``/``in_xbc``/``in_dt``; the gated-norm weight at
``gate_norm/scale``), ``attn``
(over the attention layers, per-matrix ``wq``/``wk``/``wv`` or
concatenated ``wqkv``), ``mlp`` and ``ln1``/``ln2`` (over all layers,
``wi``/``wg`` or ``wig = [wi|wg]``), ``final_norm``, and ``lm_head``
where the head is not tied.  Every matrix product runs at
``Precision.HIGHEST``, one layer at a time, so that it fits on the chip
beside the weights.  Departures from the published model: none in the
equations; the embedding table is read in the float32 the program keeps
it in.

``quant="fp8"`` gives the control: every projection matrix (Mamba in and
out, attention, MLP, head) rounded to float8 (e4m3) with a scale per
output column, the rest as above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: query rows per attention block (bounds the [heads, rows, T] scores)
Q_BLOCK = 512
#: vocabulary columns per block of the output head
V_BLOCK = 16384
#: sequences are padded up to a multiple of this (one compile per bucket)
BUCKET = 512
#: scored positions are padded up to a multiple of this
POS_BUCKET = 128
FP8_MAX = 448.0

_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "rms_norm_eps", "embedding_multiplier",
         "residual_multiplier", "logits_scaling", "attention_multiplier",
         "mamba_d_state", "mamba_d_head", "mamba_n_heads", "mamba_expand",
         "mamba_n_groups", "mamba_d_conv", "mamba_conv_bias")


def _fp8(w):
    """Round to float8 e4m3 with one scale per output column."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mat(w, quant):
    w = w.astype(jnp.float32)
    return _fp8(w) if quant == "fp8" else w


def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _take(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _split(w, a, b):
    return w[:, :a], w[:, a:a + b], w[:, a + b:]


def _mlp(params, il, x, cfg, quant):
    mlp = _take(params["mlp"], il)
    if "wig" in mlp:
        f = mlp["wig"].shape[-1] // 2
        wi, wg = mlp["wig"][:, :f], mlp["wig"][:, f:]
    else:
        wi, wg = mlp["wi"], mlp["wg"]
    y = _rmsnorm(x, _take(params["ln2"], il)["scale"], cfg["rms_norm_eps"])
    ff = jax.nn.silu(_dot(y, _mat(wg, quant))) * _dot(y, _mat(wi, quant))
    return x + cfg["residual_multiplier"] * _dot(ff, _mat(mlp["wo"], quant))


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _mamba_layer(params, im, il, x, cfg_items, quant):
    cfg = dict(cfg_items)
    t, d = x.shape
    n, p = cfg["mamba_d_state"], cfg["mamba_d_head"]
    nh, g = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    di = cfg["mamba_expand"] * d
    width = cfg["mamba_d_conv"]
    w = _take(params["mamba"], im)
    y = _rmsnorm(x, _take(params["ln1"], il)["scale"], cfg["rms_norm_eps"])
    z, xbc, dt = (_dot(y, _mat(w[k], quant))
                  for k in ("in_z", "in_xbc", "in_dt"))
    # causal depthwise conv: out[t] = sum_k w[k] * xbc[t - width + 1 + k]
    conv_w = w["conv_w"].astype(jnp.float32)
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = sum(conv_w[k] * padded[k:k + t] for k in range(width))
    if cfg["mamba_conv_bias"]:
        conv = conv + w["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    xs, b_mat, c_mat = _split(xbc, di, g * n)
    xh = xs.reshape(t, nh, p)
    # each head reads its group's B and C
    heads_b = jnp.repeat(b_mat.reshape(t, g, n), nh // g, axis=1)
    heads_c = jnp.repeat(c_mat.reshape(t, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))   # [T, H]
    a = -jnp.exp(w["A_log"].astype(jnp.float32))                   # [H]

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * b_t)[:, :, None] * x_t[:, None, :]
        return h, jnp.einsum("hn,hnp->hp", c_t, h, precision=HIGHEST)

    _, ys = jax.lax.scan(step, jnp.zeros((nh, n, p), jnp.float32),
                         (xh, heads_b, heads_c, dt))
    ys = ys + w["D"].astype(jnp.float32)[:, None] * xh
    gated = ys.reshape(t, di) * jax.nn.silu(z)
    grouped = gated.reshape(t, g, di // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True)
        + cfg["rms_norm_eps"])
    gated = grouped.reshape(t, di) \
        * w["gate_norm"]["scale"].astype(jnp.float32)
    x = x + cfg["residual_multiplier"] * _dot(gated,
                                              _mat(w["out_proj"], quant))
    return _mlp(params, il, x, cfg, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _attention_layer(params, ia, il, x, cfg_items, quant):
    cfg = dict(cfg_items)
    t, d = x.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    grp = h // hkv
    attn = _take(params["attn"], ia)
    if "wqkv" in attn:
        wq, wk, wv = _split(attn["wqkv"], h * hd, hkv * hd)
    else:
        wq, wk, wv = attn["wq"], attn["wk"], attn["wv"]
    y = _rmsnorm(x, _take(params["ln1"], il)["scale"], cfg["rms_norm_eps"])
    q = _dot(y, _mat(wq, quant)).reshape(t, h, hd)
    k = _dot(y, _mat(wk, quant)).reshape(t, hkv, hd)
    v = _dot(y, _mat(wv, quant)).reshape(t, hkv, hd)
    outs = []
    for c in range(0, t, Q_BLOCK):
        qc = q[c:c + Q_BLOCK].reshape(-1, hkv, grp, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k, precision=HIGHEST) \
            * cfg["attention_multiplier"]
        rows = c + jnp.arange(qc.shape[0])[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", pr, v, precision=HIGHEST)
                    .reshape(-1, h * hd))
    x = x + cfg["residual_multiplier"] * _dot(
        jnp.concatenate(outs, axis=0), _mat(attn["wo"], quant))
    return _mlp(params, il, x, cfg, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(table, tokens, cfg_items):
    cfg = dict(cfg_items)
    return (jnp.take(table, tokens, axis=0).astype(jnp.float32)
            * cfg["embedding_multiplier"])


@jax.jit
def _rows(x, pos):
    return x[pos]


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _head(params, x, cfg_items, quant):
    cfg = dict(cfg_items)
    y = _rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    cols = []
    for c in range(0, w.shape[1], V_BLOCK):
        cols.append(_dot(y, _mat(w[:, c:c + V_BLOCK], quant)))
    return jnp.concatenate(cols, axis=-1) / cfg["logits_scaling"]


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _KEYS if k in cfg)


def logits(params, cfg: dict, tokens, positions, quant=None):
    """float32 logits ``[len(positions), vocab]`` on the device: position
    ``p`` holds the prediction made after ``tokens[: p + 1]``."""
    items = _items(cfg)
    t = len(tokens)
    padded = np.zeros((-(-t // BUCKET) * BUCKET,), np.int32)
    padded[:t] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(padded), items)
        im = ia = 0
        for il, kind in enumerate(cfg["layer_types"]):
            if kind == "mamba":
                x = _mamba_layer(params, jnp.int32(im), jnp.int32(il), x,
                                 items, quant)
                im += 1
            else:
                x = _attention_layer(params, jnp.int32(ia), jnp.int32(il),
                                     x, items, quant)
                ia += 1
        n = len(positions)
        pos = np.full((-(-n // POS_BUCKET) * POS_BUCKET,), positions[-1],
                      np.int32)
        pos[:n] = positions
        out = _head(params, _rows(x, jnp.asarray(pos)), items, quant)
    return out[:n]
