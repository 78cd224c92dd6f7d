"""Three-term roofline from the compiled dry-run.

    compute term    = HLO_FLOPs  / peak_FLOP/s        (per chip)
    memory term     = HLO_bytes  / HBM_bw             (per chip)
    collective term = wire_bytes / link_bw            (per chip)

``cost_analysis()`` of the SPMD-partitioned executable reports *per-chip*
FLOPs and bytes, so all three terms are per-chip seconds and directly
comparable; the dominant one is the step-time lower bound.

collective_bytes is NOT in cost_analysis: ``parse_collectives`` scans the
post-optimization HLO for all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops and models per-chip wire traffic with
the standard ring costs:

    all-reduce      2·S·(G-1)/G      (reduce-scatter + all-gather phases)
    all-gather      S·(G-1)/G        (S = gathered result size)
    reduce-scatter  S_in·(G-1)/G
    all-to-all      S·(G-1)/G
    collective-permute  S

where G is the replica-group size parsed from the op's replica_groups.
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Dict, List, Optional

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16
from repro.models.config import ModelConfig, ShapeConfig

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. "bf16[256,4096,128]{3,2,1,0}"  or  "f32[]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# start-style:  %x = TYPE all-gather(...)  /  fusion-wrapped variants
_OP_RE = re.compile(
    r"=\s+(?:\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
# iota-format groups: replica_groups=[2,256]<=[512]
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
# explicit groups: replica_groups={{0,1,2},{3,4,5}}
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
# permute pairs: source_target_pairs={{0,1},{1,2}}
_PAIRS_RE = re.compile(r"source_target_pairs=\{\{")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    elems = 1
    if dims:
        for d in dims.split(","):
            elems *= int(d)
    return elems * _DTYPE_BYTES[dtype]


def _result_bytes(line: str) -> float:
    """Sum of the op's result-tuple byte size (first shape group(s))."""
    # take shapes before the opcode name (the '=' left side result types)
    head = line.split("(", 1)[0]
    total = 0.0
    for m in _SHAPE_RE.finditer(head):
        total += _shape_bytes(m.group(1), m.group(2))
    return total


def _operand_bytes(line: str) -> float:
    tail = line.split("(", 1)[1] if "(" in line else ""
    total = 0.0
    for m in _SHAPE_RE.finditer(tail.split(")")[0]):
        total += _shape_bytes(m.group(1), m.group(2))
    return total


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, g = int(m.group(1)), int(m.group(2))
        return max(g, 1)
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return total_devices


def parse_collectives(hlo_text: str, total_devices: int) -> List[Dict]:
    """Scan post-optimization HLO; one record per collective op."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        if "-done(" in line:
            continue                      # count the -start only
        res = _result_bytes(line)
        opd = _operand_bytes(line)
        g = _group_size(line, total_devices)
        if op == "all-reduce":
            wire = 2.0 * res * (g - 1) / max(g, 1)
        elif op == "all-gather":
            wire = res * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            wire = opd * (g - 1) / max(g, 1)
        elif op == "all-to-all":
            wire = res * (g - 1) / max(g, 1)
        else:                             # collective-permute
            wire = res
        out.append({"op": op, "result_bytes": res, "operand_bytes": opd,
                    "group_size": g, "wire_bytes": wire})
    return out


def collective_summary(records: List[Dict]) -> Dict:
    by_op = defaultdict(lambda: {"count": 0, "wire_bytes": 0.0})
    for r in records:
        by_op[r["op"]]["count"] += 1
        by_op[r["op"]]["wire_bytes"] += r["wire_bytes"]
    total = sum(v["wire_bytes"] for v in by_op.values())
    return {"total_wire_bytes": total, "by_op": dict(by_op),
            "n_ops": len(records)}


def analytic_hbm_bytes(cfg: ModelConfig, shape: ShapeConfig,
                       chips: int,
                       weight_shards: Optional[int] = None,
                       kv_cache_int8: bool = False) -> Dict:
    """Compulsory per-chip HBM traffic for one step (TPU fusion model).

    The CPU-compiled HLO's fusion granularity is far finer than the TPU
    target's (flash-attention/MLP chains that live in VMEM on TPU hit
    fusion boundaries on CPU), so surface-byte counts from the dry-run
    HLO overstate HBM traffic by ~10×.  This model counts only the
    traffic NO schedule can avoid, per chip:

      weights    P/chips × bytes × passes   (3 for train: fwd+remat+bwd)
      optimizer  38 B/param/chip (grad rw4+4, m rw, v rw, master rw @f32,
                 param write @bf16) — train only
      acts       per-token-per-layer boundary tensors × tokens/chips ×
                 3 (train) or 1 (prefill); flash/MLP internals excluded
                 (VMEM-resident on the TPU target)
      moe        dispatch/combine one-hot [S,E,C] tensors (GShard
                 baseline) — the honest cost of one-hot routing
      cache      full read (+ slot write) for decode; write for prefill
      logits     B·S·V f32 × 3 for train (fwd write, bwd read+write)

    Returned dict itemizes the terms (EXPERIMENTS.md shows the split).
    """
    db = 2  # bf16 weights/activations
    P = cfg.param_count()
    L = cfg.num_layers
    B, S = shape.global_batch, shape.seq_len
    hd = cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    d, dff, v = cfg.d_model, cfg.d_ff, cfg.vocab_size

    if shape.kind == "train":
        tokens, passes, logit_passes = B * S, 3, 3
    elif shape.kind == "prefill":
        tokens, passes, logit_passes = B * S, 1, 0
    else:
        tokens, passes, logit_passes = B, 1, 0

    # weight_shards: how many ways the resident weights are split
    # (== chips under FSDP+TP; == model-axis size when fsdp=False and
    # each data replica holds a full TP shard)
    wsh = weight_shards or chips
    weights = P * db * (3 if shape.kind == "train" else 1) / wsh
    optimizer = 38.0 * P / chips if shape.kind == "train" else 0.0

    # per-token per-layer activation boundary elements
    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        act_elems = 5 * d + 2 * h * hd + 2 * hkv * hd + 2 * dff
    elif cfg.family in ("ssm",):
        d_in = cfg.ssm.expand * d
        act_elems = 3 * d + 6 * d_in
    else:  # hybrid: mamba backbone + amortized shared attn block
        d_in = cfg.ssm.expand * d
        act_elems = 3 * d + 6 * d_in + (5 * d + 4 * h * hd + 2 * dff) \
            * cfg.attention_layers / L
    acts = act_elems * db * tokens * L * passes / chips

    moe_bytes = 0.0
    if cfg.moe is not None:
        m = cfg.moe
        cap_per_token = m.top_k * m.capacity_factor
        ec = cfg.moe.num_experts * max(
            8, int(m.group_size * cap_per_token / m.num_experts))
        # dispatch + combine one-hots, written + read, f32
        moe_bytes = tokens * ec * 4.0 * 2 * 2 * passes / chips

    cache_bytes = 0.0
    if shape.kind in ("prefill", "decode"):
        if cfg.family in ("dense", "moe", "vlm", "encdec"):
            # int8 cache: 1 byte/elem + f32 scale per (token, head)
            kv_db = (1.0 + 4.0 / hd) if kv_cache_int8 else db
            kv = L * B * hkv * S * hd * kv_db * 2       # K and V
            cache_bytes = kv / chips
        elif cfg.family == "ssm":
            s_ = cfg.ssm
            nh = s_.expand * d // s_.head_dim
            cache_bytes = (L * B * nh * s_.state_dim * s_.head_dim * 4 * 2
                           / chips)
        else:  # hybrid
            s_ = cfg.ssm
            nh = s_.expand * d // s_.head_dim
            n_apps = cfg.attention_layers
            cache_bytes = (L * B * nh * s_.state_dim * s_.head_dim * 4 * 2
                           + n_apps * B * hkv * S * hd * db * 2) / chips
        if shape.kind == "decode":
            cache_bytes *= 1.0      # full read dominates; slot write ~0
    logits = (B * S * v * 4.0 * logit_passes / chips
              if shape.kind == "train"
              else B * v * 4.0 / chips)

    total = weights + optimizer + acts + moe_bytes + cache_bytes + logits
    return {"total": total, "weights": weights, "optimizer": optimizer,
            "acts": acts, "moe_dispatch": moe_bytes, "cache": cache_bytes,
            "logits": logits}


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Useful-work FLOPs: 6·N·D train, 2·N·D prefill, 2·N·B decode
    (N = active params)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch          # one decoded token


def roofline_terms(flops_per_chip: float, bytes_per_chip: float,
                   wire_bytes_per_chip: float, chips: int,
                   mflops: float,
                   peak: float = PEAK_FLOPS_BF16,
                   hbm: float = HBM_BW,
                   link: float = ICI_BW) -> Dict:
    t_compute = flops_per_chip / peak
    t_memory = bytes_per_chip / hbm
    t_collective = wire_bytes_per_chip / link
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    useful = mflops / chips / peak if mflops else 0.0
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "step_lower_bound_s": bound,
        "model_flops": mflops,
        "model_flops_per_chip": mflops / chips if mflops else 0.0,
        "useful_compute_s": useful,
        # fraction of the bound that is useful model compute — the
        # roofline fraction this report optimizes
        "roofline_fraction": useful / bound if bound > 0 else 0.0,
        # how much of compiled compute is useful (remat/padding waste)
        "model_vs_hlo_flops": (mflops / chips) / flops_per_chip
        if flops_per_chip > 0 else 0.0,
    }
