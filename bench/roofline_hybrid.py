"""The operations and bytes a Granite-4.0-H hybrid step needs.

The counts follow from a configuration file's sizes and the live lengths
alone (``bench/roofline.py`` counts the dense models the same way):

- the weights are read once per step, in ``torch_dtype``; the head is
  the embedding table where it is tied, read once, in
  ``embedding_dtype``;
- each live slot's SSM state (``mamba_n_heads x mamba_d_head x
  mamba_d_state`` in ``ssm_state_dtype``) and conv history
  (``mamba_d_conv - 1`` taps of the conv channels, in ``torch_dtype``)
  are read and written once in every Mamba layer;
- attention reads only the positions a sequence has filled, in the
  attention layers only, and writes the new token's K and V there;
- a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations,
  and the prefill's chunked SSD scan is counted as its matrix products
  per chunk of ``mamba_chunk_size`` positions.
"""
from __future__ import annotations

from typing import Tuple

from bench import roofline

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _kinds(cfg: dict) -> Tuple[int, int]:
    """(Mamba layers, attention layers)."""
    kinds = cfg["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def _ssm(cfg: dict):
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    nh, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    conv = di + 2 * g * n
    return d, di, nh, p, n, g, conv


def mamba_params(cfg: dict) -> int:
    """Parameters of one Mamba-2 mixer: the input projection to
    ``[z | x B C | dt]``, the conv (and its bias), ``dt_bias``,
    ``A_log``, ``D``, the gated norm's weight, the output projection."""
    d, di, nh, p, n, g, conv = _ssm(cfg)
    bias = conv if cfg.get("mamba_conv_bias", True) else 0
    return (d * (di + conv + nh) + cfg["mamba_d_conv"] * conv + bias
            + 3 * nh + di + di * d)


def attention_params(cfg: dict) -> int:
    d, h, hkv, hd, _, _ = roofline._dims(cfg)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def block_params(cfg: dict) -> int:
    """Parameters of the layer stack: mixers, MLPs and the two norms of
    every layer."""
    nm, na = _kinds(cfg)
    nl = nm + na
    return (nm * mamba_params(cfg) + na * attention_params(cfg)
            + nl * (mlp_params(cfg) + 2 * cfg["hidden_size"]))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights one step reads: blocks and the final norm in
    ``torch_dtype``, the head in ``embedding_dtype`` where it is the tied
    embedding table (counted once)."""
    dt = _BYTES[cfg["torch_dtype"]]
    head_dt = _BYTES[cfg.get("embedding_dtype", cfg["torch_dtype"])] \
        if cfg.get("tie_word_embeddings") else dt
    return (block_params(cfg) + cfg["hidden_size"]) * dt \
        + head_params(cfg) * head_dt


def state_bytes_per_slot(cfg: dict) -> int:
    """One slot's recurrent state over the Mamba layers: SSM state and
    conv history."""
    _, _, nh, p, n, _, conv = _ssm(cfg)
    nm, _ = _kinds(cfg)
    ssm = nh * p * n * _BYTES[cfg["ssm_state_dtype"]]
    taps = (cfg["mamba_d_conv"] - 1) * conv * _BYTES[cfg["torch_dtype"]]
    return nm * (ssm + taps)


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one position, in the attention layers only."""
    _, _, hkv, hd, _, _ = roofline._dims(cfg)
    return _kinds(cfg)[1] * 2 * hkv * hd * _BYTES[cfg["torch_dtype"]]


def _attn_flops(cfg: dict, pairs: int) -> int:
    _, h, _, hd, _, _ = roofline._dims(cfg)
    return _kinds(cfg)[1] * 4 * h * hd * pairs


def decode_step(cfg: dict, slots: int, positions: int) -> tuple:
    """(flops, bytes) of one decode step for ``slots`` live sequences
    whose KV lengths (each new token included) sum to ``positions``."""
    _, _, nh, p, n, _, conv = _ssm(cfg)
    nm, _ = _kinds(cfg)
    # the recurrence: decay, dt B x^T and the sum into the state, C h
    recur = 5 * nh * p * n + 2 * cfg["mamba_d_conv"] * conv
    flops = 2 * (block_params(cfg) + head_params(cfg)) * slots \
        + _attn_flops(cfg, positions) + nm * recur * slots
    embed_row = cfg["hidden_size"] * _BYTES[cfg.get("embedding_dtype",
                                                    cfg["torch_dtype"])]
    nbytes = weight_bytes(cfg) + 2 * slots * state_bytes_per_slot(cfg) \
        + positions * kv_bytes_per_token(cfg) + slots * embed_row
    return flops, nbytes


def ssd_flops(cfg: dict, n_tokens: int) -> int:
    """The chunked SSD scan's matrix products over ``n_tokens``, every
    Mamba layer: per chunk of ``q`` positions, ``C B^T`` per group
    (``2 q^2 N``), the masked scores times ``x`` per head (``2 q^2 P``),
    the chunk's state from ``B`` and ``x`` and the carried state's
    output through ``C`` (``2 q N P`` each, per head)."""
    _, _, nh, p, n, g, _ = _ssm(cfg)
    chunk = cfg["mamba_chunk_size"]
    total = 0
    for start in range(0, n_tokens, chunk):
        q = min(chunk, n_tokens - start)
        total += 2 * q * q * n * g + nh * (2 * q * q * p + 4 * q * n * p)
    return _kinds(cfg)[0] * total


def prefill(cfg: dict, n: int) -> tuple:
    """(flops, bytes) of one causal prefill of ``n`` tokens, logits of the
    last position only: weights read once, K and V of the attention
    layers and every Mamba layer's final state written."""
    flops = 2 * block_params(cfg) * n + _attn_flops(cfg, n * (n + 1) // 2) \
        + ssd_flops(cfg, n) + 2 * head_params(cfg)
    nbytes = weight_bytes(cfg) + n * kv_bytes_per_token(cfg) \
        + state_bytes_per_slot(cfg)
    return flops, nbytes
