"""Plain float32 forward of a dense decoder: the yardstick of every
dense cell (Llama and Mistral style: RMSNorm, rotary positions in two
halves, grouped-query attention, SwiGLU feed-forward).

It imports nothing of the program.  It reads the parameter tree the
benchmark made, in either layout the program stores: per-matrix
(``wq``/``wk``/``wv``, ``wi``/``wg``) or concatenated (``wqkv``,
``wig = [wi|wg]``).  It runs one layer at a time, upcasting that
layer's weights to float32, with every matrix product at
``Precision.HIGHEST``, so that it fits on the chip beside the weights.

What it computes follows the configuration file: ``embedding_multiplier``
scales the embedded tokens and ``rms_norm_eps`` is the norms' epsilon.

``quant="fp8"`` gives the control: every matrix rounded to float8
(e4m3) with a scale per output column, the rest as above.
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


def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h)


def _fp8(w):
    """Round to float8 e4m3 with one scale per output column."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mat(w, quant):
    w = w.astype(jnp.float32)
    return _fp8(w) if quant == "fp8" else w


def _rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x: [T, heads, hd]; rotary embedding in two halves."""
    t, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def layer_weights(blocks, i, cfg: dict, quant):
    """Layer ``i`` of the stacked blocks, float32, in split form."""
    d, h, hkv, hd = dims(cfg)
    take = lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
    attn, mlp = blocks["attn"], blocks["mlp"]
    if "wqkv" in attn:
        wqkv = take(attn["wqkv"])
        wq = wqkv[:, :h * hd]
        wk = wqkv[:, h * hd:(h + hkv) * hd]
        wv = wqkv[:, (h + hkv) * hd:]
    else:
        wq, wk, wv = take(attn["wq"]), take(attn["wk"]), take(attn["wv"])
    if "wig" in mlp:
        wig = take(mlp["wig"])
        f = wig.shape[-1] // 2
        wi, wg = wig[:, :f], wig[:, f:]
    else:
        wi, wg = take(mlp["wi"]), take(mlp["wg"])
    m = functools.partial(_mat, quant=quant)
    return {"wq": m(wq), "wk": m(wk), "wv": m(wv), "wo": m(take(attn["wo"])),
            "wi": m(wi), "wg": m(wg), "w2": m(take(mlp["wo"])),
            "ln1": take(blocks["ln1"]["scale"]),
            "ln2": take(blocks["ln2"]["scale"])}


def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(blocks, i, x, cfg_items, quant):
    cfg = dict(cfg_items)
    d, h, hkv, hd = dims(cfg)
    g = h // hkv
    w = layer_weights(blocks, i, cfg, quant)
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    y = _rmsnorm(x, w["ln1"], eps)
    q = _rope(_dot(y, w["wq"]).reshape(t, h, hd), cfg["rope_theta"])
    k = _rope(_dot(y, w["wk"]).reshape(t, hkv, hd), cfg["rope_theta"])
    v = _dot(y, w["wv"]).reshape(t, hkv, hd)
    outs = []
    for c in range(0, t, Q_BLOCK):
        qc = q[c:c + Q_BLOCK].reshape(-1, hkv, g, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k, precision=HIGHEST) \
            * hd ** -0.5
        rows = c + jnp.arange(qc.shape[0])[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)
                    .reshape(-1, h * hd))
    x = x + _dot(jnp.concatenate(outs, axis=0), w["wo"])
    y = _rmsnorm(x, w["ln2"], eps)
    ff = jax.nn.silu(_dot(y, w["wg"])) * _dot(y, w["wi"])
    return x + _dot(ff, w["w2"])


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(table, tokens, cfg_items):
    cfg = dict(cfg_items)
    return (jnp.take(table, tokens, axis=0).astype(jnp.float32)
            * cfg.get("embedding_multiplier", 1.0))


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
    return jnp.concatenate(cols, axis=-1)


def _items(cfg: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "embedding_multiplier")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


def logits(params, cfg: dict, tokens, positions, quant=None):
    """float32 logits ``[len(positions), vocab]`` on the device: position
    ``p`` holds the prediction made after ``tokens[: p + 1]``."""
    items = _items(cfg)
    t = len(tokens)
    padded = np.zeros((-(-t // BUCKET) * BUCKET,), np.int32)
    padded[:t] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(padded), items)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer(params["blocks"], jnp.int32(i), x, items, quant)
        n = len(positions)
        pos = np.full((-(-n // POS_BUCKET) * POS_BUCKET,), positions[-1],
                      np.int32)
        pos[:n] = positions
        out = _head(params, _rows(x, jnp.asarray(pos)), items, quant)
    return out[:n]
