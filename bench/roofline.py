"""The chip's peaks, and the operations and bytes the algorithm needs.

The counts follow from a configuration file's sizes and the live lengths
alone, the same whatever implements the step: the weights are read once
per step, attention reads only the positions a sequence has filled (not
the capacity of its cache), and a matrix product of ``m x k`` by
``k x n`` is ``2 m k n`` operations.
"""
from __future__ import annotations

from typing import Iterable

#: Published peaks of one chip, keyed by ``device.device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9},
}

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add the "
                       f"chip's published numbers to bench/roofline.py")
    return PEAKS[device_kind]


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, hkv, hd, cfg["intermediate_size"], cfg["vocab_size"]


def block_params(cfg: dict) -> int:
    """Matrix parameters of the layer stack (norm scales left out)."""
    d, h, hkv, hd, ff, _ = _dims(cfg)
    per_layer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer


def head_params(cfg: dict) -> int:
    d, *_, v = _dims(cfg)
    return d * v


def kv_bytes_per_token(cfg: dict) -> int:
    _, _, hkv, hd, _, _ = _dims(cfg)
    return (cfg["num_hidden_layers"] * 2 * hkv * hd
            * _BYTES[cfg["torch_dtype"]])


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights one forward step reads: blocks, norms, head."""
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return (block_params(cfg) + head_params(cfg) + norms) \
        * _BYTES[cfg["torch_dtype"]]


def _attn_flops(cfg: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, every layer."""
    _, h, _, hd, _, _ = _dims(cfg)
    return cfg["num_hidden_layers"] * 4 * h * hd * pairs


def decode_step(cfg: dict, contexts: Iterable[int]) -> tuple:
    """(flops, bytes) of one decode step for the live sequences, each
    attending over ``contexts[i]`` positions (its own new token included)."""
    contexts = list(contexts)
    b = len(contexts)
    ctx = sum(contexts)
    flops = 2 * (block_params(cfg) + head_params(cfg)) * b \
        + _attn_flops(cfg, ctx)
    kv = kv_bytes_per_token(cfg)
    embed_row = cfg["hidden_size"] * _BYTES[cfg.get("embedding_dtype",
                                                   cfg["torch_dtype"])]
    nbytes = weight_bytes(cfg) + (ctx - b) * kv + b * kv + b * embed_row
    return flops, nbytes


def prefill(cfg: dict, n: int) -> tuple:
    """(flops, bytes) of one causal prefill of ``n`` tokens, logits of the
    last position only."""
    flops = 2 * block_params(cfg) * n + _attn_flops(cfg, n * (n + 1) // 2) \
        + 2 * head_params(cfg)
    nbytes = weight_bytes(cfg) + n * kv_bytes_per_token(cfg)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_s"])
